"""Batched multi-world engine (gol_tpu/batch, docs/BATCHING.md).

The bit-exactness contract under test everywhere: a batched run of B
worlds is bit-identical **per world** to B sequential single-world runs
of the existing engines — exact and padded+masked buckets, every tier,
world-axis sharding on and off — plus the serving machinery around it:
schema-v4 telemetry, batched checkpoints on the PR 4 validated-resume
path, cooperative preemption, the persistent compilation cache, the CLI
surface, and the trace-identity pin (building batched programs leaves
every single-world jaxpr byte-identical).
"""

from __future__ import annotations

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gol_tpu import resilience
from gol_tpu.batch import (
    GolBatchRuntime,
    bucket_shape,
    bucketize,
    cache_entries,
    compiled_batch_evolver,
    make_batch_mesh,
    resolve_bucket_engine,
)
from gol_tpu.batch.runtime import Bucket
from gol_tpu.models.state import Geometry
from gol_tpu.ops import stencil
from gol_tpu.runtime import GolRuntime
from gol_tpu.utils import checkpoint as ckpt_mod

from tests import oracle

jax.config.update("jax_platforms", "cpu")

STEPS = 12


def _worlds(shapes, seed=7, density=0.35):
    return [
        oracle.random_board(h, w, seed=seed + i, density=density)
        for i, (h, w) in enumerate(shapes)
    ]


def _refs(worlds, steps=STEPS):
    return [
        np.asarray(stencil.run(jnp.asarray(w.copy()), steps)) for w in worlds
    ]


# -- bucketing ---------------------------------------------------------------


def test_bucket_shape_rounds_up():
    assert bucket_shape(48, 64, 64) == (64, 64)
    assert bucket_shape(64, 64, 64) == (64, 64)
    assert bucket_shape(65, 1, 64) == (128, 64)
    with pytest.raises(ValueError):
        bucket_shape(8, 8, 0)


def test_bucketize_groups_and_masks():
    buckets = bucketize([(64, 64), (48, 32), (64, 64), (96, 96)], 64)
    assert [(b.shape, b.batch, b.masked) for b in buckets] == [
        ((64, 64), 3, True),  # two exact 64x64 + one padded 48x32
        ((128, 128), 1, True),
    ]
    # Exact-only bucket is unmasked.
    (b,) = bucketize([(64, 64), (64, 64)], 64)
    assert not b.masked and b.indices == (0, 1)


def test_resolve_bucket_engine():
    shapes = [(64, 64), (48, 32)]
    exact = Bucket(shape=(64, 64), indices=(0,), masked=False)
    masked = Bucket(shape=(64, 64), indices=(0, 1), masked=True)
    assert resolve_bucket_engine("auto", exact, shapes) == "bitpack"
    assert resolve_bucket_engine("dense", masked, shapes) == "dense"
    # The fused kernel has no masked form: documented bit-exact fallback.
    assert resolve_bucket_engine("pallas_bitpack", masked, shapes) == "bitpack"
    # Unpackable world width: auto degrades, explicit bitpack refuses.
    shapes_odd = [(64, 64), (48, 20)]
    masked_odd = Bucket(shape=(64, 64), indices=(0, 1), masked=True)
    assert resolve_bucket_engine("auto", masked_odd, shapes_odd) == "dense"
    with pytest.raises(ValueError, match="pack"):
        resolve_bucket_engine("bitpack", masked_odd, shapes_odd)


# -- bit-equality per tier ---------------------------------------------------


@pytest.mark.parametrize("engine", ["dense", "bitpack", "pallas_bitpack"])
def test_exact_batch_bit_equal_to_sequential(engine):
    worlds = _worlds([(32, 64)] * 3)
    refs = _refs(worlds)
    brt = GolBatchRuntime(
        worlds=[w.copy() for w in worlds], engine=engine, bucket_quantum=32
    )
    _, out = brt.run(STEPS)
    assert brt._engines == [engine]
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)


@pytest.mark.parametrize("engine", ["dense", "bitpack", "auto"])
def test_masked_mixed_sizes_bit_equal(engine):
    # One bucket (quantum 64) holding 64x64 exact, 48x64 and 40x32 padded
    # — the masked program must reproduce each world's own torus.
    worlds = _worlds([(64, 64), (48, 64), (40, 32)])
    refs = _refs(worlds)
    brt = GolBatchRuntime(worlds=[w.copy() for w in worlds], engine=engine)
    assert len(brt.buckets) == 1 and brt.buckets[0].masked
    _, out = brt.run(STEPS)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)


def test_masked_dense_handles_unpackable_widths():
    worlds = _worlds([(30, 50), (17, 23), (64, 64)])
    refs = _refs(worlds)
    brt = GolBatchRuntime(worlds=[w.copy() for w in worlds], engine="auto")
    assert "dense" in brt._engines
    _, out = brt.run(STEPS)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)


def test_padding_stays_dead():
    # A full-live world in a padded bucket: no cell may leak outside.
    worlds = [np.ones((40, 40), np.uint8), np.zeros((64, 64), np.uint8)]
    brt = GolBatchRuntime(worlds=[w.copy() for w in worlds], engine="dense")
    fn, masked = brt._evolver(0, 3)
    assert masked
    stack, hs, ws = brt._stack(brt.buckets[0])
    out = np.asarray(fn(stack, hs, ws))
    pad = out[0].copy()
    pad[:40, :40] = 0
    assert not pad.any()


@pytest.mark.parametrize("engine", ["dense", "bitpack", "pallas_bitpack"])
def test_worlds_mesh_sharding_bit_equal(engine):
    # B=8 on the 8-device CPU mesh: every bucket actually shards.
    worlds = _worlds([(32, 64)] * 8)
    refs = _refs(worlds)
    mesh = make_batch_mesh()
    brt = GolBatchRuntime(
        worlds=[w.copy() for w in worlds],
        engine=engine,
        mesh=mesh,
        bucket_quantum=32,
    )
    assert brt._bucket_mesh(brt.buckets[0]) is mesh
    _, out = brt.run(STEPS)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)


def test_worlds_mesh_indivisible_batch_falls_back_unsharded():
    worlds = _worlds([(32, 32)] * 3)  # 3 % 8 != 0
    brt = GolBatchRuntime(
        worlds=[w.copy() for w in worlds], engine="dense",
        mesh=make_batch_mesh(),
    )
    assert brt._bucket_mesh(brt.buckets[0]) is None
    _, out = brt.run(4)
    for i, ref in enumerate(_refs(worlds, 4)):
        np.testing.assert_array_equal(out[i], ref)


def test_masked_worlds_mesh_bit_equal():
    worlds = _worlds([(64, 64), (48, 32)] * 4)  # one masked bucket, B=8
    refs = _refs(worlds)
    brt = GolBatchRuntime(
        worlds=[w.copy() for w in worlds], engine="auto",
        mesh=make_batch_mesh(),
    )
    assert len(brt.buckets) == 1 and brt.buckets[0].masked
    assert brt._bucket_mesh(brt.buckets[0]) is not None
    _, out = brt.run(STEPS)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)


# -- retrace / program identity ----------------------------------------------


def test_builder_returns_cached_programs():
    a = compiled_batch_evolver("bitpack", 8, False, 512, None)
    b = compiled_batch_evolver("bitpack", 8, False, 512, None)
    assert a is b


def test_trace_identity_single_world_jaxprs_unchanged():
    """Building batched programs must leave every single-world engine's
    jaxpr byte-identical — the PR 2 trace-identity pin, extended."""
    from gol_tpu.analysis import walker

    spec = jax.ShapeDtypeStruct((64, 64), np.uint8)

    def single_world_jaxprs():
        out = {}
        for engine in ("dense", "bitpack"):
            rt = GolRuntime(
                geometry=Geometry(size=64, num_ranks=1), engine=engine
            )
            fn, dynamic, static = rt._evolve_fn(4)
            out[engine] = str(walker.trace_jaxpr(fn, spec, *dynamic, *static))
        return out

    before = single_world_jaxprs()
    # Build + run batched programs for the same tiers and geometry.
    worlds = _worlds([(64, 64), (48, 64)])
    for engine in ("dense", "bitpack"):
        GolBatchRuntime(
            worlds=[w.copy() for w in worlds], engine=engine
        ).run(4)
    after = single_world_jaxprs()
    assert before == after


# -- telemetry (schema v4) ---------------------------------------------------


def _read_events(path):
    return [json.loads(ln) for ln in open(path)]


def test_batch_telemetry_v4_events(tmp_path):
    from gol_tpu import telemetry

    # v4 introduced the batch fields; the current schema (v5 at this
    # round) keeps them additive-forever.
    assert telemetry.SCHEMA_VERSION >= 4
    worlds = _worlds([(64, 64), (48, 32), (64, 64)])
    brt = GolBatchRuntime(
        worlds=[w.copy() for w in worlds],
        engine="auto",
        checkpoint_every=4,
        checkpoint_dir=str(tmp_path / "ck"),
        telemetry_dir=str(tmp_path / "tl"),
        run_id="b4",
    )
    report, _ = brt.run(8)
    recs = _read_events(tmp_path / "tl" / "b4.rank0.jsonl")
    head = recs[0]
    assert head["schema"] == telemetry.SCHEMA_VERSION
    assert head["config"]["driver"] == "batch"
    assert head["config"]["buckets"][0]["B"] == 3
    compiles = [r for r in recs if r["event"] == "compile"]
    assert all("batch" in c for c in compiles)
    chunks = [r for r in recs if r["event"] == "chunk"]
    assert len(chunks) == 2  # one bucket x two 4-gen chunks
    for c in chunks:
        b = c["batch"]
        assert b["bucket"] == [64, 64] and b["B"] == 3 and b["masked"]
        assert b["per_world_updates_per_sec"] > 0
    assert [r["event"] for r in recs].count("checkpoint") == 2
    assert recs[-1]["event"] == "summary"


def test_batch_summarize_renders_and_exits_zero(tmp_path, capsys):
    import io

    from gol_tpu.telemetry import summarize as summ_mod

    worlds = _worlds([(64, 64)] * 2)
    GolBatchRuntime(
        worlds=worlds, engine="bitpack",
        telemetry_dir=str(tmp_path / "tl"), run_id="bs",
    ).run(6)
    out = io.StringIO()
    assert summ_mod.summarize(str(tmp_path / "tl"), out) == 0
    text = out.getvalue()
    assert "driver=batch" in text
    assert "B=2" in text and "/world" in text


# -- checkpoints on the PR 4 resilience path ---------------------------------


def test_batch_snapshot_roundtrip_and_corruption(tmp_path):
    worlds = _worlds([(16, 16), (24, 32)])
    path = ckpt_mod.batch_checkpoint_path(str(tmp_path), 5)
    ckpt_mod.save_batch(path, worlds, 5)
    snap = ckpt_mod.load_batch(path)
    assert snap.generation == 5
    for got, want in zip(snap.boards, worlds):
        np.testing.assert_array_equal(got, want)
    assert ckpt_mod.verify_snapshot(path) == 5
    with open(path, "r+b") as f:
        f.seek(40)
        f.write(b"\xde\xad\xbe\xef")
    with pytest.raises(ckpt_mod.CorruptSnapshotError):
        ckpt_mod.load_batch(path)


def test_batch_resume_completes_bit_identically(tmp_path):
    worlds = _worlds([(64, 64), (48, 32), (96, 96)])
    full_rt = GolBatchRuntime(worlds=[w.copy() for w in worlds])
    _, full = full_rt.run(8)

    ck = str(tmp_path / "ck")
    GolBatchRuntime(
        worlds=[w.copy() for w in worlds], checkpoint_every=2,
        checkpoint_dir=ck,
    ).run(4)
    resume, info = resilience.resolve_auto_resume(ck, kind="batch")
    assert info["generation"] == 4 and not info["fallback"]
    rt2 = GolBatchRuntime(
        worlds=[w.copy() for w in worlds], checkpoint_every=2,
        checkpoint_dir=ck,
    )
    _, done = rt2.run(4, resume=resume)
    assert rt2.generation == 8
    for i, ref in enumerate(full):
        np.testing.assert_array_equal(done[i], ref)


def test_batch_auto_resume_falls_back_past_corruption(tmp_path):
    worlds = _worlds([(32, 32), (24, 16)])
    ck = str(tmp_path / "ck")
    GolBatchRuntime(
        worlds=[w.copy() for w in worlds], checkpoint_every=2,
        checkpoint_dir=ck,
    ).run(6)
    snaps = ckpt_mod.list_snapshots(ck, kind="batch")
    assert len(snaps) == 3
    with open(snaps[-1], "r+b") as f:
        f.seek(33)
        f.write(b"\xff\xff\xff\xff")
    resume, info = resilience.resolve_auto_resume(ck, kind="batch")
    assert info["generation"] == 4 and info["fallback"]
    import os as _os

    assert info["skipped"] == [_os.path.basename(snaps[-1])]
    # The fallback resume still lands bit-identically on the full run.
    _, full = GolBatchRuntime(worlds=[w.copy() for w in worlds]).run(8)
    rt2 = GolBatchRuntime(
        worlds=[w.copy() for w in worlds], checkpoint_dir=ck,
    )
    _, done = rt2.run(4, resume=resume)
    for i, ref in enumerate(full):
        np.testing.assert_array_equal(done[i], ref)


def test_batch_retention_gc(tmp_path):
    worlds = _worlds([(16, 16)])
    ck = str(tmp_path / "ck")
    GolBatchRuntime(
        worlds=worlds, checkpoint_every=1, checkpoint_dir=ck,
        keep_snapshots=2,
    ).run(6)
    snaps = ckpt_mod.list_snapshots(ck, kind="batch")
    assert len(snaps) == 2
    assert [ckpt_mod.snapshot_generation(p) for p in snaps] == [5, 6]


def test_batch_preemption_checkpoints_and_resumes(tmp_path):
    worlds = _worlds([(48, 64), (64, 64)])
    _, full = GolBatchRuntime(worlds=[w.copy() for w in worlds]).run(9)

    ck = str(tmp_path / "ck")
    tl = str(tmp_path / "tl")
    rt = GolBatchRuntime(
        worlds=[w.copy() for w in worlds], checkpoint_every=3,
        checkpoint_dir=ck, telemetry_dir=tl, run_id="pre",
    )
    resilience.request_preemption()
    try:
        with pytest.raises(resilience.Preempted) as exc:
            rt.run(9)
    finally:
        resilience.clear_preemption()
    assert exc.value.generation == 3
    recs = _read_events(tmp_path / "tl" / "pre.rank0.jsonl")
    pre = [r for r in recs if r["event"] == "preempt"]
    assert pre and pre[0]["checkpointed"] and pre[0]["generation"] == 3
    # Relaunch with the remaining work: bit-identical to uninterrupted.
    resume, info = resilience.resolve_auto_resume(ck, kind="batch")
    assert info["generation"] == 3
    rt2 = GolBatchRuntime(
        worlds=[w.copy() for w in worlds], checkpoint_every=3,
        checkpoint_dir=ck,
    )
    _, done = rt2.run(6, resume=resume)
    for i, ref in enumerate(full):
        np.testing.assert_array_equal(done[i], ref)


def test_batch_resume_shape_mismatch_rejected(tmp_path):
    path = ckpt_mod.batch_checkpoint_path(str(tmp_path), 2)
    ckpt_mod.save_batch(path, _worlds([(16, 16)]), 2)
    rt = GolBatchRuntime(worlds=_worlds([(32, 32)]))
    with pytest.raises(ValueError, match="configured"):
        rt.run(2, resume=path)
    rt2 = GolBatchRuntime(worlds=_worlds([(16, 16), (16, 16)]))
    with pytest.raises(ValueError, match="worlds"):
        rt2.run(2, resume=path)


# -- compile cache -----------------------------------------------------------


def test_compile_cache_populates(tmp_path):
    import jax

    cc = str(tmp_path / "cc")
    worlds = _worlds([(32, 32)])
    was = jax.config.jax_enable_compilation_cache
    try:
        brt = GolBatchRuntime(worlds=worlds, engine="dense", compile_cache=cc)
        brt.run(3)
        assert cache_entries(cc)
    finally:
        # The explicit request turned the cache on process-wide; later
        # tests in this worker expect the suite's cache-off default.
        jax.config.update("jax_enable_compilation_cache", was)
    # (Cross-process hit behavior is asserted by scripts/batch_smoke.py —
    # in-process a second run is served by the jit cache before XLA.)


# -- CLI ---------------------------------------------------------------------


def test_cli_batch_smoke(tmp_path, capsys):
    from gol_tpu import cli

    rc = cli.main([
        "6", "64", "8", "512", "1",
        "--batch", "4", "--batch-sizes", "64,96",
        "--outdir", str(tmp_path),
        "--telemetry", str(tmp_path / "tl"), "--run-id", "c",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "TOTAL DURATION" in out and "BATCH" in out
    for i in range(4):
        assert (tmp_path / f"world_{i:04d}" / "Rank_0_of_1.txt").exists()
    # World 0 (size 64) dump equals the sequential single-world CLI dump.
    seq = tmp_path / "seq"
    assert cli.main(["6", "64", "8", "512", "1", "--outdir", str(seq)]) == 0
    capsys.readouterr()
    a = (tmp_path / "world_0000" / "Rank_0_of_1.txt").read_bytes()
    b = (seq / "Rank_0_of_1.txt").read_bytes()
    assert a == b


def test_cli_batch_rejections(tmp_path, capsys):
    from gol_tpu import cli

    base = ["6", "64", "4", "512", "0", "--outdir", str(tmp_path)]
    for extra, msg in [
        (["--batch", "-1"], "--batch must be"),
        (["--batch-sizes", "64"], "--batch-sizes applies"),
        (["--batch", "2", "--halo", "stale_t0"], "fresh halos"),
        (["--batch", "2", "--rule", "B36/S23"], "B3/S23"),
        (["--batch", "2", "--stats", "--telemetry", str(tmp_path)],
         "single-world"),
        # (--batch + --guard-every is now a supported combination —
        # PR 10's batched guard; see tests/test_guard_tiers.py.)
        (["--batch", "2", "--mesh", "2d"], "1-D"),
        (["--batch", "2", "--engine", "pallas"], "no batched tier"),
        (["--batch", "2", "--batch-sizes", "xyz"], "no sizes"),
    ]:
        rc = cli.main(base + extra)
        out = capsys.readouterr().out
        assert rc == 255, extra
        assert msg in out, (extra, out)


def test_cli_batch_auto_resume_total_target(tmp_path, capsys):
    from gol_tpu import cli

    args = [
        "6", "64", "8", "512", "0", "--batch", "2",
        "--checkpoint-every", "4",
        "--checkpoint-dir", str(tmp_path / "ck"),
        "--auto-resume", "--outdir", str(tmp_path),
    ]
    assert cli.main(args) == 0
    capsys.readouterr()
    # Identical argv relaunch: already at the total target -> 0 more gens.
    assert cli.main(args) == 0
    out = capsys.readouterr().out
    assert "auto-resume: generation 8" in out


# -- verifier ----------------------------------------------------------------


def test_batchcheck_matrix_passes():
    from gol_tpu.analysis import batchcheck
    from gol_tpu.analysis.report import FAIL

    reports = batchcheck.run_batch_checks()
    assert len(reports) == 7
    for rep in reports:
        assert all(c.status != FAIL for c in rep.checks), rep.config_name


def test_batchcheck_catches_coupled_worlds():
    """A program that mixes worlds must fail batch-invariance."""
    from gol_tpu.analysis import batchcheck

    cfg = batchcheck.BatchConfig(
        "broken", "dense", False, False, batch=3, shape=(16, 32)
    )

    def broken(stack):
        rolled = jnp.roll(stack, 1, axis=0)  # world i reads world i-1
        return jax.vmap(stencil.step)(rolled)

    res = batchcheck.check_batch_invariance(cfg, jax.jit(broken), None)
    assert res.status == "FAIL"
