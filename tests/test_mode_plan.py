"""The (engine, shard-mode, halo-depth) legality matrix — message pins.

``gol_tpu/parallel/modes.py`` is the single source of truth the runtime
validates every sharded configuration through; these tests pin each
cell's verdict AND its error text, so the stale-message drift that PR 9
cleaned up (the ``halo_depth > 1 requires shard_mode 'explicit'`` chain
that survived two releases after overlap learned deep bands) cannot
quietly come back.
"""

from __future__ import annotations

import jax
import pytest

from gol_tpu.parallel import mesh as mesh_mod
from gol_tpu.parallel import modes

jax.config.update("jax_platforms", "cpu")


# -- the positive matrix -----------------------------------------------------


@pytest.mark.parametrize("engine,mode", [
    (e, m) for e, ms in modes.ENGINE_MODES.items() for m in ms
])
def test_supported_cells_have_no_rejection(engine, mode):
    assert modes.mode_rejection(engine, mode) is None


@pytest.mark.parametrize("engine,mode,k", [
    ("dense", "explicit", 4),
    ("dense", "overlap", 4),
    ("dense", "pipeline", 4),
    ("bitpack", "explicit", 2),
    ("bitpack", "overlap", 2),
    ("bitpack", "pipeline", 2),
    ("pallas_bitpack", "explicit", 8),
    ("pallas_bitpack", "overlap", 16),
    ("pallas_bitpack", "pipeline", 8),
    ("activity", "explicit", 1),
])
def test_legal_combos_pass_check(engine, mode, k):
    modes.check_combo(engine, mode, k)  # must not raise


# -- per-combo rejection messages --------------------------------------------


@pytest.mark.parametrize("engine,mode,match", [
    ("bitpack", "auto", "no auto-SPMD program"),
    ("pallas_bitpack", "auto", "explicit, overlap and pipeline ring "
                               "programs only"),
    ("activity", "overlap", "explicit ring program only"),
    ("activity", "pipeline", "explicit ring program only"),
    ("activity", "auto", "explicit ring program only"),
])
def test_unsupported_cells_pin_their_message(engine, mode, match):
    assert match in modes.mode_rejection(engine, mode)
    with pytest.raises(ValueError, match=match):
        modes.check_combo(engine, mode, 1)


def test_unknown_mode_rejected():
    with pytest.raises(ValueError, match="unknown shard_mode"):
        modes.check_combo("dense", "psychic", 1)


def test_unknown_engine_passes_through():
    # Engines outside the matrix (e.g. 'pallas' single-device) are not
    # this module's business; the runtime rejects them elsewhere.
    assert modes.mode_rejection("pallas", "explicit") is None


@pytest.mark.parametrize("engine,mode,k,match", [
    ("dense", "explicit", 0, "must be >= 1"),
    ("dense", "auto", 2, "no band to deepen"),
    ("pallas_bitpack", "pipeline", 12, "multiple of 8"),
    ("pallas_bitpack", "explicit", 7, "multiple of 8"),
    ("activity", "explicit", 2, "must be 1"),
])
def test_depth_rules_pin_their_message(engine, mode, k, match):
    with pytest.raises(ValueError, match=match):
        modes.check_combo(engine, mode, k)


@pytest.mark.parametrize("two_d,shard_h,shard_w,k,ok", [
    (False, 8, 1, 8, True),   # 1-D: width extent not a band axis
    (False, 8, 1, 9, False),
    (True, 8, 2, 2, True),
    (True, 8, 2, 3, False),   # 2-D: min extent governs
])
def test_depth_vs_shard_extent(two_d, shard_h, shard_w, k, ok):
    if ok:
        modes.check_depth(k, shard_h, shard_w, two_d)
    else:
        with pytest.raises(ValueError, match="exceeds the shard extent"):
            modes.check_depth(k, shard_h, shard_w, two_d)


# -- check_run: the one entry point the runtime and the CLI call -------------
#
# Each case is one cell: (requested engine, resolved engine, knobs) and
# the message it must raise, or None for a cell that has a program.


def _cell(id_, engine, resolved, match=None, **kw):
    return pytest.param(engine, resolved, kw, match, id=id_)


LEGALITY_MATRIX = [
    # Cells with a program.
    _cell("activity-meshless-ignores-mode", "activity", "activity",
          shard_mode="overlap"),
    _cell("auto-pallas-2d-pipeline-custom-rule", "auto", "pallas_bitpack",
          mesh="2d", shard_mode="pipeline", halo_depth=8, custom_rule=True),
    _cell("pallas-bitpack-2d-depth-at-column-band", "pallas_bitpack",
          "pallas_bitpack", mesh="2d", halo_depth=32),
    _cell("dense-meshless-stale", "dense", "dense", halo_mode="stale_t0"),
    _cell("auto-meshless-stale", "auto", "dense", halo_mode="stale_t0"),
    _cell("pallas-meshless", "pallas", "pallas"),
    _cell("dense-2d-auto-spmd", "dense", "dense", mesh="2d",
          shard_mode="auto"),
    _cell("bitpack-1d-explicit-custom-rule", "bitpack", "bitpack",
          mesh="1d", custom_rule=True),
    _cell("batch-auto-1d", "auto", "auto", mesh="1d", batch=True),
    _cell("batch-pallas-bitpack-meshless", "pallas_bitpack",
          "pallas_bitpack", batch=True),
    # Unknown names and depths.
    _cell("unknown-engine", "life", "life", "unknown engine"),
    _cell("unknown-halo-mode", "dense", "dense", "unknown halo_mode",
          halo_mode="frozen"),
    _cell("unknown-shard-mode-meshless", "bitpack", "bitpack",
          "unknown shard_mode", shard_mode="ring"),
    _cell("negative-depth-meshless", "bitpack", "bitpack", "must be >= 1",
          halo_depth=-1),
    # Custom rules.
    _cell("custom-rule-stale-bitpack", "bitpack", "bitpack",
          "no stale_t0 reference-compat mode", halo_mode="stale_t0",
          custom_rule=True),
    _cell("custom-rule-pallas-1d", "pallas", "pallas", "hard-wired",
          mesh="1d", custom_rule=True),
    _cell("custom-rule-dense-2d-pipeline", "dense", "dense",
          "Conway-specific", mesh="2d", shard_mode="pipeline",
          halo_depth=2, custom_rule=True),
    _cell("custom-rule-auto-to-bitpack-overlap", "auto", "bitpack",
          "Conway-specific", mesh="1d", shard_mode="overlap",
          custom_rule=True),
    # The activity tier.
    _cell("activity-1d-stale", "activity", "activity", "gated tier",
          mesh="1d", halo_mode="stale_t0"),
    _cell("activity-1d-custom-rule", "activity", "activity",
          "B3/S23 fast paths", mesh="1d", custom_rule=True),
    _cell("activity-2d-depth-2", "activity", "activity",
          "halo_depth must be 1", mesh="2d", halo_depth=2),
    _cell("activity-2d-pipeline", "activity", "activity",
          "explicit ring program only", mesh="2d", shard_mode="pipeline"),
    _cell("activity-meshless-pipeline", "activity", "activity",
          "pass a mesh", shard_mode="pipeline"),
    # The out-of-core tier.
    _cell("ooc-stale", "ooc", "ooc", "streaming tier has no analog",
          halo_mode="stale_t0"),
    _cell("ooc-custom-rule", "ooc", "ooc", "bit-packed band step",
          custom_rule=True),
    _cell("ooc-meshless-auto-mode", "ooc", "ooc", "no sharded ring program",
          shard_mode="auto"),
    _cell("ooc-reshard", "ooc", "ooc", "replans onto a different mesh",
          reshard=True),
    _cell("ooc-2d", "ooc", "ooc", "no sharded ring program", mesh="2d"),
    # Meshless runs and meshes.
    _cell("dense-meshless-depth-2", "dense", "dense",
          "only applies to sharded runs", halo_depth=2),
    _cell("bitpack-meshless-stale", "bitpack", "bitpack",
          "engine 'bitpack' implements fresh halos only",
          halo_mode="stale_t0"),
    _cell("pallas-bitpack-meshless-stale", "pallas_bitpack",
          "pallas_bitpack",
          "engine 'pallas_bitpack' implements fresh halos only",
          halo_mode="stale_t0"),
    _cell("auto-1d-stale", "auto", "dense", "single-device only",
          mesh="1d", halo_mode="stale_t0"),
    _cell("pallas-2d", "pallas", "pallas", "no sharded path", mesh="2d"),
    _cell("auto-pallas-2d-past-column-band", "auto", "pallas_bitpack",
          "column band supports halo_depth <= 32", mesh="2d",
          halo_depth=64),
    # Batched runs (the CLI's --batch).
    _cell("batch-bitpack-stale", "bitpack", "bitpack",
          "--batch runs fresh halos only", halo_mode="stale_t0",
          batch=True),
    _cell("batch-bitpack-rule", "bitpack", "bitpack",
          "single-world feature", custom_rule=True, batch=True),
    _cell("batch-pallas-bitpack-2d", "pallas_bitpack", "pallas_bitpack",
          "world axis", mesh="2d", batch=True),
]


@pytest.mark.parametrize("engine,resolved,kw,match", LEGALITY_MATRIX)
def test_check_run_legality_matrix(engine, resolved, kw, match):
    if match is None:
        modes.check_run(engine, resolved, **kw)  # must not raise
    else:
        with pytest.raises(ValueError, match=match):
            modes.check_run(engine, resolved, **kw)


# -- the runtime validates THROUGH the matrix --------------------------------


def _rt(**kw):
    from gol_tpu.models.state import Geometry
    from gol_tpu.runtime import GolRuntime

    kw.setdefault("geometry", Geometry(size=64, num_ranks=1))
    return GolRuntime(**kw)


@pytest.mark.parametrize("engine,mode,k,match", [
    ("bitpack", "auto", 1, "no auto-SPMD program"),
    ("dense", "auto", 2, "no band to deepen"),
    ("pallas_bitpack", "pipeline", 12, "multiple of 8"),
    ("activity", "explicit", 2, "must be 1"),
])
def test_runtime_surfaces_canonical_messages(engine, mode, k, match):
    with pytest.raises(ValueError, match=match):
        _rt(
            engine=engine,
            mesh=mesh_mod.make_mesh_1d(4),
            shard_mode=mode,
            halo_depth=k,
        )


def test_runtime_rejects_pipeline_without_mesh():
    with pytest.raises(ValueError, match="pass a mesh"):
        _rt(shard_mode="pipeline")


def test_runtime_accepts_every_dense_cell():
    for mode in modes.ENGINE_MODES["dense"]:
        rt = _rt(
            engine="dense", mesh=mesh_mod.make_mesh_1d(4), shard_mode=mode
        )
        assert rt.shard_mode == mode


# -- the out-of-core row: meshless by construction ---------------------------
#
# Engine 'ooc' (docs/STREAMING.md) streams host-resident bands through
# ONE device; there is no sharded ring program to pick a mode for, so
# every (ooc, mode) cell rejects with one canonical message naming the
# legal alternatives, and the serve/batch tiers refuse it by name.


@pytest.mark.parametrize("mode", sorted(modes.SHARD_MODES))
def test_every_ooc_cell_pins_the_canonical_message(mode):
    msg = modes.mode_rejection("ooc", mode)
    assert "no sharded ring program" in msg
    assert "--engine ooc without a mesh" in msg
    # The rejection must name the engines that DO shard, or the message
    # is a dead end for the user it fires on.
    for alt in ("'dense'", "'bitpack'", "'pallas_bitpack'", "'activity'"):
        assert alt in msg


def test_runtime_surfaces_ooc_mesh_rejection():
    with pytest.raises(ValueError, match="no sharded ring program"):
        _rt(
            engine="ooc",
            mesh=mesh_mod.make_mesh_1d(4),
            shard_mode="explicit",
        )


def test_runtime_surfaces_ooc_mode_rejection_without_mesh():
    # shard_mode is a ring knob; a meshless ooc run still rejects a
    # non-default mode through the same canonical message.
    with pytest.raises(ValueError, match="no sharded ring program"):
        _rt(engine="ooc", shard_mode="overlap", halo_depth=2)


def test_runtime_accepts_meshless_ooc_with_deep_visits():
    # halo_depth doubles as the per-visit generation depth k, so the
    # "temporal blocking needs a mesh" rejection must exempt ooc.
    rt = _rt(engine="ooc", halo_depth=4)
    assert rt._resolved == "ooc" and rt._ooc_plan.depth == 4


def test_serve_rejects_ooc_naming_supported_engines(tmp_path):
    from gol_tpu.serve.scheduler import ServeScheduler, ValidationError

    sched = ServeScheduler(str(tmp_path / "state"), quantum=32)
    try:
        with pytest.raises(ValidationError, match="is not served") as ei:
            sched.submit(
                {"pattern": 4, "size": 32, "generations": 1, "engine": "ooc"}
            )
        assert "supported engines" in str(ei.value)
    finally:
        sched.close()


def test_batch_rejects_ooc_naming_batched_engines():
    import numpy as np

    from gol_tpu.batch import GolBatchRuntime

    with pytest.raises(ValueError, match="streams one bigger-than-device"):
        GolBatchRuntime(
            worlds=[np.zeros((8, 8), dtype=np.uint8)], engine="ooc"
        )


def test_cli_rejects_batch_times_ooc(capsys, tmp_path):
    from gol_tpu import cli

    rc = cli.main(
        ["7", "64", "8", "32", "0", "--engine", "ooc", "--batch", "2",
         "--outdir", str(tmp_path)]
    )
    assert rc == 255
    assert "run it unbatched" in capsys.readouterr().out


def test_cli_rejects_guard_times_ooc(capsys, tmp_path):
    from gol_tpu import cli

    rc = cli.main(
        ["7", "64", "8", "32", "0", "--engine", "ooc", "--guard-every", "2",
         "--outdir", str(tmp_path)]
    )
    assert rc == 255
    assert "guard an in-core engine" in capsys.readouterr().out
