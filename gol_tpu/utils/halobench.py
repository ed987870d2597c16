"""Halo-exchange latency measurement (SURVEY §5 observability).

The reference's only performance artifact is one end-to-end wall-clock
line (gol-main.c:121-125); it cannot attribute time to communication vs
compute.  This tool times three compiled programs on the live mesh:

- ``exchange``: ``steps`` back-to-back halo exchanges alone (the ppermute
  ring traffic, nothing else) — the TPU analog of timing the reference's
  ``MPI_Irecv``/``Isend``/``Wait`` block;
- ``step``: the full exchange+stencil generation loop;
- ``stencil``: the halo-free torus stencil loop (pure compute ceiling).

``step - stencil`` estimates the *exposed* (non-overlapped) exchange cost
per generation; ``exchange`` bounds the raw ring latency.  All loops run
inside single compiled programs so host round-trips don't pollute the
numbers.

Run as a module for a JSON report:
``python -m gol_tpu.utils.halobench [size] [steps] [mesh {1d,2d}]
[engine {dense,bitpack,pallas,pallas_overlap}]``.  The sharded 3-D
flagship has its own mode (:func:`measure3d`):
``python -m gol_tpu.utils.halobench DxHxW steps 3d:P,R,C``.
"""

from __future__ import annotations

import functools
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from gol_tpu.ops import stencil
from gol_tpu.parallel import sharded
from gol_tpu.parallel.mesh import COLS, PLANES, ROWS, board_sharding
from gol_tpu.utils.timing import time_best


@functools.lru_cache(maxsize=32)
def _exchange_only(mesh: Mesh, steps: int):
    """jit: `steps` chained halo exchanges, no stencil.

    Each iteration folds the received halos back into the block's
    *boundary rows/columns only* — O(boundary) work, so the loop has a
    genuine data dependency (the next exchange ships the just-modified
    edges, XLA cannot elide the ppermutes) while ``exchange_s`` measures
    ring traffic + launch and nothing else.  The previous fold added the
    halos across the whole block, a full-board HBM pass per iteration
    that at 16384² made "exchange alone" read 3× the full step.
    """
    from gol_tpu.parallel.halo import ring

    two_d = COLS in mesh.axis_names
    num_rows = mesh.shape[ROWS]
    num_cols = mesh.shape.get(COLS, 1)

    if two_d:

        def body(_, blk):
            # Two-phase edge exchange hand-rolled at O(boundary): phase 2
            # ships the *phase-1-folded* edge columns, so the corner
            # two-hop chain is live and none of the four ppermutes is
            # dead code.  (exchange_block_halos itself concatenates a
            # full [h+2, w+2] extension — a whole-board copy the real
            # engines amortize over a k-deep chunk, which an
            # exchange-ONLY loop must not pay per iteration.)
            top, bottom = sharded.exchange_row_halos(blk, num_rows)
            blk = blk.at[0].add(top).at[-1].add(bottom)
            left = lax.ppermute(blk[:, -1:], COLS, ring(num_cols, 1))
            right = lax.ppermute(blk[:, :1], COLS, ring(num_cols, -1))
            return blk.at[:, :1].add(left).at[:, -1:].add(right)

        spec = P(ROWS, COLS)
    else:

        def body(_, blk):
            top, bottom = sharded.exchange_row_halos(blk, num_rows)
            return blk.at[0].add(top).at[-1].add(bottom)

        spec = P(ROWS, None)

    local = jax.shard_map(
        lambda b: lax.fori_loop(0, steps, body, b),
        mesh=mesh,
        in_specs=spec,
        out_specs=spec,
    )
    return jax.jit(local)


def _time(fn, arg, repeats: int = 3) -> float:
    """Shared warm best-of-N timer; the lambdas passed here copy their own
    donated inputs, so the same ``arg`` is safe for every repeat."""
    return time_best(fn, lambda: arg, repeats)


ENGINES = ("dense", "bitpack", "pallas", "pallas_overlap")


def measure(
    mesh: Mesh, size, steps: int = 100, engine: str = "dense"
) -> Dict[str, float]:
    """Per-generation seconds for exchange-only / full step / pure stencil.

    ``size`` is a square side or an ``(h, w)`` pair — rectangular boards
    reach the lane-folded narrow-shard geometries (e.g. the 16×16-pod
    config-3 shard, 16384×1024) whose exchange-vs-compute split is
    exactly where the folded overlap story lives.

    ``stencil_s`` is the pure-compute ceiling: the torus stencil on an
    *unsharded single-device* board of one shard's dimensions (what each
    device computes per generation, minus all communication).  Handing the
    sharded global board to ``stencil.run`` would instead compile an
    auto-SPMD program whose rolls insert their own collectives.

    ``engine="bitpack"`` attributes the packed ring engine instead: the
    full step is :func:`gol_tpu.parallel.packed.compiled_evolve_packed`
    (packed-word halos — 8× less wire) and the compute ceiling the packed
    single-device evolve; ``exchange_s`` still times dense-row ppermutes,
    an upper bound on the packed exchange's wire time.

    ``engine="pallas"`` / ``"pallas_overlap"`` attribute the flagship
    sharded Pallas engine's serial and comm/compute-overlap forms
    (:func:`gol_tpu.parallel.packed.compiled_evolve_packed_pallas`); the
    compute ceiling is the single-device fused-kernel evolve.  Comparing
    the two engines' ``exposed_exchange_s`` (same mesh, same size) measures
    exactly what the overlap form hides.  ``steps`` should be a multiple of
    8 (the band depth) so no jnp remainder tail pollutes the attribution.

    Returns ``{"exchange_s": ..., "step_s": ..., "stencil_s": ...,
    "exposed_exchange_s": ...}``, all per generation.
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected {ENGINES}")
    h, w = (size, size) if isinstance(size, int) else size
    rng = np.random.default_rng(0)
    board_np = (rng.random((h, w)) < 0.35).astype(np.uint8)
    board = jax.device_put(jnp.asarray(board_np), board_sharding(mesh))
    t_exch = _time(_exchange_only(mesh, steps), board) / steps
    if engine in ("pallas", "pallas_overlap"):
        from gol_tpu.parallel import packed as packed_mod

        packed_mod.validate_packed_geometry(board.shape, mesh)
        step_fn = packed_mod.compiled_evolve_packed_pallas(
            mesh, steps, overlap=engine == "pallas_overlap"
        )
    elif engine == "bitpack":
        from gol_tpu.parallel import packed as packed_mod

        packed_mod.validate_packed_geometry(board.shape, mesh)
        step_fn = packed_mod.compiled_evolve_packed(mesh, steps)
    else:
        step_fn = sharded.compiled_evolve(mesh, steps, "explicit", 1)
    t_step = (
        _time(lambda b: step_fn(jnp.array(b, copy=True)), board) / steps
    )
    local_h = h // mesh.shape[ROWS]
    local_w = w // mesh.shape.get(COLS, 1)
    shard = jax.device_put(
        jnp.asarray(board_np[:local_h, :local_w]),
        mesh.devices.ravel()[0],
    )
    ceiling_note = None
    if engine in ("pallas", "pallas_overlap"):
        from gol_tpu.ops import bitlife, pallas_bitlife

        fold = pallas_bitlife.fold_factor(bitlife.packed_width(local_w))
        if fold == 1 or jax.default_backend() != "tpu":
            sten_fn = lambda b: pallas_bitlife.evolve(b, steps)
        else:
            # Narrow (lane-folded) shard: no bare-kernel program exists
            # at this width (folding is the whole point), so the compute
            # ceiling is the serial folded engine on a 1-ring — the
            # closest pure-compute proxy.  Degenerate caveat, flagged in
            # the output: when the measurement mesh IS that 1-ring, the
            # serial proxy is the identical compiled program and the
            # subtraction reads noise, not exchange exposure (for the
            # overlap engine it reads overlap-over-serial overhead).
            from gol_tpu.parallel import mesh as mesh_mod
            from gol_tpu.parallel import packed as packed_mod

            ring1 = mesh_mod.make_mesh_1d(
                devices=[mesh.devices.ravel()[0]]
            )
            fold_fn = packed_mod.compiled_evolve_packed_pallas(
                ring1, steps
            )
            sten_fn = lambda b: fold_fn(b)
            if mesh.devices.size == 1:
                # Any one-device mesh (1-D 1-ring OR a (1,1) 2-D mesh) is
                # equally degenerate: the proxy is the same program.
                ceiling_note = (
                    "folded 1-ring proxy equals the measured step "
                    "program on a 1-device mesh: exposed_exchange_s is "
                    "definitional noise (serial engine) or "
                    "overlap-over-serial overhead (overlap engine), NOT "
                    "exchange exposure"
                )
            elif engine == "pallas_overlap":
                ceiling_note = (
                    "ceiling is the SERIAL folded 1-ring engine; "
                    "exposed_exchange_s mixes exchange exposure with the "
                    "overlap form's reassembly overhead"
                )
    elif engine == "bitpack":
        from gol_tpu.ops import bitlife

        sten_fn = lambda b: bitlife.evolve_dense_io(b, steps)
    else:
        sten_fn = lambda b: stencil.run(b, steps)
    t_sten = (
        _time(lambda b: sten_fn(jnp.array(b, copy=True)), shard) / steps
    )
    out = {
        "exchange_s": t_exch,
        "step_s": t_step,
        "stencil_s": t_sten,
        "exposed_exchange_s": max(0.0, t_step - t_sten),
    }
    if ceiling_note is not None:
        out["ceiling_note"] = ceiling_note
    return out


SWEEP_MODES = ("explicit", "overlap", "pipeline")


def measure_depth_sweep(
    mesh: Mesh,
    size,
    steps: int,
    engine: str,
    depths,
    modes=SWEEP_MODES,
) -> Dict[str, dict]:
    """The k-vs-MFU curve the pipelined halo engine exists for (PR 9).

    For every (shard mode, halo depth k) cell, times the FULL sharded
    chunk program — explicit serial chunks, the depth-k interior/boundary
    overlap split, or the cross-chunk pipelined double buffer — and
    reports per-generation seconds, cell-updates/s, and the VPU-roofline
    fraction (``telemetry.roofline_utilization`` over the same
    ``xla_flops_model`` the chunk telemetry uses, so the sweep's MFU
    column and a run's v8 ``halo`` block share one model).  Every row is
    written only after a bit-equality receipt against the explicit
    depth-1 program on the same board (the sparsebench discipline: a
    fast wrong program must not enter an artifact).  Cells the engine
    rejects (k beyond the shard extent, non-8-multiple Pallas depths)
    become ``{"skipped": reason}`` rows — visible, never silently
    dropped.

    ``engine``: ``dense`` | ``bitpack`` | ``pallas`` (the fused sharded
    Pallas engine; its depth quantum is 8, so k=1 measures the default
    8-deep band and non-multiples of 8 skip).
    """
    from gol_tpu import telemetry as telemetry_mod
    from gol_tpu.parallel import packed as packed_mod

    if engine not in ("dense", "bitpack", "pallas"):
        raise ValueError(
            f"sweep engine {engine!r}: expected dense/bitpack/pallas"
        )
    h, w = (size, size) if isinstance(size, int) else size
    rng = np.random.default_rng(0)
    board_np = (rng.random((h, w)) < 0.35).astype(np.uint8)
    place = lambda: jax.device_put(
        jnp.asarray(board_np), board_sharding(mesh)
    )
    devices = mesh.devices.size
    shard_cells = (h * w) // devices
    model_engine = {"pallas": "pallas_bitpack"}.get(engine, engine)

    def build(mode: str, k: int):
        if engine == "pallas":
            depth = 8 if k == 1 else k
            if depth % 8:
                raise ValueError(
                    "the sharded Pallas engine needs halo_depth to be a "
                    f"multiple of 8, got {k}"
                )
            packed_mod.validate_packed_geometry((h, w), mesh)
            return depth, packed_mod.compiled_evolve_packed_pallas(
                mesh,
                steps,
                halo_depth=depth,
                overlap=mode == "overlap",
                pipeline=mode == "pipeline",
            )
        if engine == "bitpack":
            packed_mod.validate_packed_geometry((h, w), mesh)
            return k, packed_mod.compiled_evolve_packed(
                mesh, steps, k, mode=mode
            )
        return k, sharded.compiled_evolve(mesh, steps, mode, k)

    _, ref_fn = build("explicit", 1)
    ref = np.asarray(ref_fn(place()))
    out: Dict[str, dict] = {}
    for mode in modes:
        for k in depths:
            name = f"{engine}_{mode}_k{k}"
            try:
                depth, fn = build(mode, k)
                got = np.asarray(fn(place()))
                if not np.array_equal(got, ref):
                    raise AssertionError(
                        "bit-equality receipt FAILED vs explicit depth-1"
                    )
            except (ValueError, AssertionError) as e:
                out[name] = {"skipped": str(e).splitlines()[0]}
                continue
            t_gen = _time(lambda b: fn(jnp.array(b, copy=True)), place()) / steps
            mfu = telemetry_mod.roofline_utilization(
                model_engine, shard_cells, steps, depth, True,
                t_gen * steps,
            )
            out[name] = {
                "step_s": t_gen,
                "updates_per_sec": (h * w) / t_gen,
                "mfu": mfu,
                "halo_depth": depth,
                "shard_mode": mode,
                "bit_equal_explicit_k1": True,
            }
    return out


@functools.lru_cache(maxsize=32)
def _exchange_only_3d(mesh: Mesh, steps: int):
    """jit: ``steps`` chained exchanges of the 3-D flagship's own wire
    quanta, no stencil, O(face) per iteration.

    Mirrors :func:`gol_tpu.parallel.sharded3d.compiled_evolve3d_pallas`'s
    two-ring structure in its packed plane-leading ``[band, nw, lanes]``
    layout: per iteration, one packed *band plane* rides the banded
    spatial ring and one packed *ghost word column* per side rides the
    COLS ring (4 ppermutes; the third volume axis is the kernel's lane
    axis, which the engine's mesh constraint leaves unsharded — there is
    nothing to exchange on it).  This is a tight upper bound on the
    engine's per-generation wire: the engine ships ``pad``-deep bands
    once per ``pad`` generations (same band bytes/generation) and its
    ghost columns only once per chunk.

    Anti-DCE state is four *face accumulators* — the packed volume stays
    loop-invariant, each shipped face mixes in the previously received
    one (and the column phase mixes a sliver of the just-received band
    plane, sequencing the phases like the real corner two-hop), and the
    accumulators fold into the output's boundary once after the loop.
    Two measured dead ends this loop must not repeat (r5, real chip at
    512³): in-loop ``vol.at[...].add`` chains — XLA copies the volume,
    2.1 ms/gen — and *dense* uint8 faces, whose minor-axis slicing
    relayouts at ~0.94 ms/gen; the packed-layout faces cost ~34 µs/gen.
    """
    from gol_tpu.ops import bitlife3d
    from gol_tpu.parallel.halo import ring

    np_ = mesh.shape.get(PLANES, 1)
    nr = mesh.shape.get(ROWS, 1)
    nc = mesh.shape.get(COLS, 1)
    if np_ != 1 and nr != 1:
        raise ValueError(
            "the 3-D exchange harness mirrors the fused engine's mesh "
            "constraint: planes or rows axis must be size 1, got "
            f"{dict(mesh.shape)}"
        )
    band_over_planes = nr == 1
    band_axis_name = PLANES if band_over_planes else ROWS
    band_ring = np_ if band_over_planes else nr

    def local(vol):
        p3 = bitlife3d.pack3d(vol)  # [d, h, nw]
        p = p3.transpose((0, 2, 1) if band_over_planes else (1, 2, 0))

        def body(_, c):
            ctop, cbot, cw, ce = c
            top = lax.ppermute(
                p[-1] + ctop, band_axis_name, ring(band_ring, 1)
            )
            bot = lax.ppermute(
                p[0] + cbot, band_axis_name, ring(band_ring, -1)
            )
            west = lax.ppermute(
                p[:, -1] + cw + top[-1:, :], COLS, ring(nc, 1)
            )
            east = lax.ppermute(
                p[:, 0] + ce + bot[:1, :], COLS, ring(nc, -1)
            )
            return (top, bot, west, east)

        c0 = (p[-1] * 0, p[0] * 0, p[:, -1] * 0, p[:, 0] * 0)
        ctop, cbot, cw, ce = lax.fori_loop(0, steps, body, c0)
        # One post-loop boundary fold keeps every accumulator live.
        p = p.at[0].add(ctop).at[-1].add(cbot)
        p = p.at[:, 0].add(cw).at[:, -1].add(ce)
        p3 = p.transpose((0, 2, 1) if band_over_planes else (2, 0, 1))
        return bitlife3d.unpack3d(p3)

    spec = P(PLANES, ROWS, COLS)
    local_sharded = jax.shard_map(
        local, mesh=mesh, in_specs=spec, out_specs=spec
    )
    return jax.jit(local_sharded)


def measure3d(mesh: Mesh, size, steps: int = 64) -> Dict[str, float]:
    """Per-generation attribution for the sharded 3-D flagship
    (:func:`gol_tpu.parallel.sharded3d.compiled_evolve3d_pallas`) — the
    band + ghost-word-column exchange structure the 2-D sections cannot
    see (VERDICT r4 #4).

    ``size`` is a cube side or a ``(d, h, w)`` triple.  Columns mirror
    :func:`measure`: ``exchange_s`` times the engine's own exchange
    quanta — one packed band plane on the banded ring + one packed ghost
    word column per side on the COLS ring, per generation (4 ppermutes,
    O(face) accumulator folds; a tight upper bound on the fused engine's
    per-generation wire, see :func:`_exchange_only_3d`); ``step_s`` the
    full fused sharded program; ``stencil_s`` the single-device
    fused-kernel evolve at one shard's dimensions (pure compute ceiling,
    no exchange, whatever kernel form the dispatch picks there);
    ``exposed_exchange_s`` their difference.  ``steps`` should be a multiple of 8 (the band depth) so
    no per-step jnp remainder tail pollutes the attribution.  On a
    one-device mesh the subtraction reads the chunk/ring machinery's
    overhead, not exchange exposure — flagged in ``ceiling_note``.
    """
    from gol_tpu.ops import pallas_bitlife3d
    from gol_tpu.parallel import sharded3d
    from gol_tpu.parallel.sharded3d import volume_sharding

    d, h, w = (size, size, size) if isinstance(size, int) else size
    rng = np.random.default_rng(0)
    vol_np = (rng.random((d, h, w)) < 0.3).astype(np.uint8)
    vol = jax.device_put(jnp.asarray(vol_np), volume_sharding(mesh))
    t_exch = _time(_exchange_only_3d(mesh, steps), vol) / steps
    step_fn = sharded3d.compiled_evolve3d_pallas(mesh, steps)
    t_step = (
        _time(lambda v: step_fn(jnp.array(v, copy=True)), vol) / steps
    )
    ld = d // mesh.shape.get(PLANES, 1)
    lh = h // mesh.shape.get(ROWS, 1)
    lw = w // mesh.shape.get(COLS, 1)
    shard = jax.device_put(
        jnp.asarray(vol_np[:ld, :lh, :lw]), mesh.devices.ravel()[0]
    )
    sten_fn = lambda v: pallas_bitlife3d.evolve3d(v, steps)
    t_sten = (
        _time(lambda v: sten_fn(jnp.array(v, copy=True)), shard) / steps
    )
    out = {
        "exchange_s": t_exch,
        "step_s": t_step,
        "stencil_s": t_sten,
        "exposed_exchange_s": max(0.0, t_step - t_sten),
    }
    if mesh.devices.size == 1:
        out["ceiling_note"] = (
            "one-device mesh: every ppermute is a self-copy, so "
            "exposed_exchange_s reads the sharded program's chunk/ring "
            "machinery overhead over the bare kernel, NOT exchange "
            "exposure"
        )
    return out


def main(argv=None) -> None:
    import sys

    args = list(sys.argv[1:] if argv is None else argv)
    # Optional flags, peeled off before the positional surface so the
    # published CLI is unchanged: the structured-telemetry sink
    # (docs/OBSERVABILITY.md) and the depth sweep (PR 9): a comma list of
    # halo depths swept per shard mode, emitting the k-vs-MFU curve.
    telemetry_dir = run_id = sweep_depths = None
    for flag in ("--telemetry", "--run-id", "--halo-depth-sweep"):
        if flag in args:
            k = args.index(flag)
            value = args[k + 1]
            del args[k : k + 2]
            if flag == "--telemetry":
                telemetry_dir = value
            elif flag == "--run-id":
                run_id = value
            else:
                sweep_depths = [int(v) for v in value.split(",")]
    if len(args) > 0 and "x" in args[0]:
        parts = tuple(int(v) for v in args[0].split("x"))
        size = parts if len(parts) > 1 else parts[0]
    else:
        size = int(args[0]) if len(args) > 0 else 4096
    steps = int(args[1]) if len(args) > 1 else 100
    kind = args[2] if len(args) > 2 else "1d"
    engine = args[3] if len(args) > 3 else "dense"

    from gol_tpu.parallel import mesh as mesh_mod

    if kind.startswith("3d"):
        # 3-D flagship attribution: mesh shape after a colon selects the
        # decomposition AND band orientation ("3d:4,1,2" bands over the
        # PLANES ring, "3d:1,4,2" the transposed ROWS-banded layout);
        # bare "3d" is the one-device ring.
        pshape = (
            tuple(int(v) for v in kind.split(":", 1)[1].split(","))
            if ":" in kind
            else (1, 1, 1)
        )
        n = pshape[0] * pshape[1] * pshape[2]
        mesh = mesh_mod.make_mesh_3d(pshape, devices=jax.devices()[:n])
        out = measure3d(mesh, size, steps)
        engine = "pallas3d"
    else:
        # "1d:N" / "2d:R,C" pin the device count (a sweep wants shard
        # extents that admit its deepest band); bare kinds keep the
        # all-devices default.
        if ":" in kind:
            base, spec_s = kind.split(":", 1)
            if base == "1d":
                n = int(spec_s)
                mesh = mesh_mod.make_mesh_1d(n, devices=jax.devices()[:n])
            else:
                r, c = (int(v) for v in spec_s.split(","))
                mesh = mesh_mod.make_mesh_2d(
                    (r, c), devices=jax.devices()[: r * c]
                )
        else:
            mesh = (
                mesh_mod.make_mesh_2d()
                if kind == "2d"
                else mesh_mod.make_mesh_1d()
            )
        if sweep_depths is not None:
            out = {
                "note": (
                    "k-vs-MFU sweep of the ring chunk forms (PR 9): "
                    "step_s/updates_per_sec/mfu per (shard_mode, "
                    "halo_depth), every row bit-equality-receipted "
                    "against the explicit depth-1 program on the same "
                    "board before timing; rejected cells appear as "
                    "skipped rows. mfu shares xla_flops_model with the "
                    "v8 chunk telemetry."
                    + (
                        " THIS CAPTURE IS CPU (virtual-device ring): "
                        "curve SHAPE only — CPU cores timeshare, so "
                        "exchange latency is not the bottleneck the "
                        "pipeline hides and absolute MFU is "
                        "meaningless. TPU headline command: python -m "
                        "gol_tpu.utils.halobench 16384 8192 1d "
                        "dense,bitpack,pallas --halo-depth-sweep "
                        "1,2,4,8,16 (and 2d:4,2 for the pod "
                        "decomposition)."
                        if jax.default_backend() != "tpu"
                        else ""
                    )
                ),
            }
            # The engine positional accepts a comma list here so one
            # invocation (one reproducible argv) captures the whole
            # artifact.
            kind_key = kind.replace(":", "x").replace(",", "x")
            for eng in engine.split(","):
                out.update(
                    {
                        f"{jax.default_backend()}_mesh{kind_key}_{key}": body
                        for key, body in measure_depth_sweep(
                            mesh, size, steps, eng, sweep_depths
                        ).items()
                    }
                )
            out.update(
                {
                    "size": list(size) if isinstance(size, tuple) else size,
                    "steps": steps,
                    "mesh": dict(mesh.shape),
                    "devices": len(mesh.devices.ravel()),
                    "depths": sweep_depths,
                }
            )
            print(json.dumps(out, indent=1))
            if telemetry_dir:
                from gol_tpu import telemetry as telemetry_mod

                with telemetry_mod.EventLog(
                    telemetry_dir, run_id=run_id
                ) as ev:
                    ev.run_header(
                        dict(tool="halobench", sweep=True, kind=kind)
                    )
                    for key, body in out.items():
                        if isinstance(body, dict) and "step_s" in body:
                            ev.bench_row("halobench", {**body, "name": key})
            return
        out = measure(mesh, size, steps, engine)
    out.update(
        {
            "size": list(size) if isinstance(size, tuple) else size,
            "steps": steps,
            "mesh": dict(mesh.shape),
            "devices": len(mesh.devices.ravel()),
            "engine": engine,
        }
    )
    print(json.dumps(out))
    if telemetry_dir:
        from gol_tpu import telemetry as telemetry_mod

        with telemetry_mod.EventLog(telemetry_dir, run_id=run_id) as ev:
            ev.run_header(dict(tool="halobench", engine=engine, kind=kind))
            ev.bench_row("halobench", out)


if __name__ == "__main__":
    main()
