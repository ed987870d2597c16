"""Weak-scaling efficiency harness (BASELINE.md config 4's metric).

The reference's scaling model is weak scaling by construction: every MPI
rank owns a fixed ``S×S`` block, so "scaling the domain" means adding ranks
(global world ``numRank·S × S``, gol-main.c:22,124-125).  This harness
measures the TPU equivalent: for each device count ``n`` it evolves an
``(n·S) × S`` board row-sharded over an ``n``-device ring and reports

- aggregate and per-chip cell-updates/sec, and
- **weak-scaling efficiency**: per-chip throughput at ``n`` devices divided
  by the 1-device throughput (1.0 = perfect scaling; the loss is the
  exposed halo-exchange cost, which :mod:`gol_tpu.utils.halobench`
  attributes in detail).

On this repo's single-real-TPU hosts the sweep runs on the host-local
virtual CPU mesh (``--xla_force_host_platform_device_count``) — valid for
the *shape* of the scaling curve and for regression-testing the comm
structure, not for absolute numbers; on a real pod the same harness runs
unchanged over ICI.

Run as a module for a JSON report:
``python -m gol_tpu.utils.scalebench [size_per_chip] [steps] [engine]
[mesh {1d,2d}]`` (engine ``dense`` | ``bitpack`` | ``pallas`` |
``pallas_overlap`` — the last two are the flagship fused-kernel-per-shard
program in its serial and comm/compute-overlap forms).

``mesh 2d`` sweeps the *pod decomposition* (BASELINE config 3's 16×16
block mesh, scaled to each device count as the near-square factorization
with rows <= cols: 8 devices -> 2×4): every device owns a fixed
``S×S`` block of a ``(rows·S) × (cols·S)`` world, the two-phase
row+word-column exchange replaces the 1-D ring, and narrow shards take
the lane-folded kernel — the engine/mesh combination a real pod would
run, which the 1-D sweep cannot see (VERDICT r4 #3).

**Multi-host sweeps** (the config-4 pod shape): pass the same trio as the
CLI — ``--coordinator HOST:PORT --num-processes N --process-id I`` — on
every participating process.  Device counts then sweep the *global*
device list: rows using only some processes' devices are measured by
those processes while the rest idle at the between-row barrier (the
1-device baseline every efficiency number divides by stays measurable),
and rows spanning processes run the exact cross-host programs a pod
would.  Process 0 prints the report.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gol_tpu.parallel import mesh as mesh_mod
from gol_tpu.parallel import packed as packed_mod
from gol_tpu.parallel import sharded as sharded_mod
from gol_tpu.utils.timing import time_best

ENGINES = ("dense", "bitpack", "pallas", "pallas_overlap")


def device_counts(limit: Optional[int] = None) -> List[int]:
    """Powers of two up to the visible device count (always including 1)."""
    n = len(jax.devices())
    if limit is not None:
        n = min(n, limit)
    counts = [1]
    while counts[-1] * 2 <= n:
        counts.append(counts[-1] * 2)
    return counts


def factor_2d(n: int):
    """Near-square ``(rows, cols)`` with rows <= cols: the config-3 pod
    decomposition (16×16 at 256 devices) scaled to ``n`` (8 -> 2×4)."""
    r = int(np.sqrt(n))
    while n % r:
        r -= 1
    return (min(r, n // r), max(r, n // r))


def _mesh_and_shape(n: int, size_per_chip: int, mesh_kind: str):
    """The row's mesh and world shape: every device owns ``S×S`` cells."""
    if mesh_kind == "2d":
        rows, cols = factor_2d(n)
        mesh = mesh_mod.make_mesh_2d(
            (rows, cols), devices=jax.devices()[:n]
        )
        return mesh, (rows * size_per_chip, cols * size_per_chip)
    mesh = mesh_mod.make_mesh_1d(num_devices=n)
    return mesh, (n * size_per_chip, size_per_chip)


def measure_weak_scaling(
    size_per_chip: int,
    steps: int,
    engine: str = "dense",
    counts: Optional[List[int]] = None,
    mesh_kind: str = "1d",
) -> List[Dict[str, float]]:
    """One weak-scaling sweep; returns a row per device count.

    Multi-process jobs: every process must call this (rows spanning
    processes run cross-host programs; a between-row barrier keeps the
    job in lockstep).  A process only measures rows whose mesh includes
    its devices, so the returned list is complete — and the efficiency
    baseline correct — on process 0, whose devices lead the global device
    list; other processes' partial lists are for their own logging only.
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected {ENGINES}")
    if mesh_kind not in ("1d", "2d"):
        raise ValueError(f"unknown mesh kind {mesh_kind!r}; expected 1d|2d")
    counts = device_counts() if counts is None else list(counts)
    if not counts or counts[0] != 1:
        # Efficiency is defined against the 1-device throughput; a sweep
        # that skips it would silently re-baseline on its first row.
        raise ValueError(f"counts must start at 1, got {counts}")
    pallas_like = engine in ("pallas", "pallas_overlap")
    if (
        pallas_like
        and mesh_kind == "1d"
        and jax.default_backend() == "tpu"
    ):
        # Surface the fused kernel's lane constraint early (it otherwise
        # raises deep inside shard_map tracing).  Loop-invariant: the
        # width axis is unsharded on the 1-D row mesh.  (2-D sweeps
        # lane-fold narrow shards instead; their geometry is validated
        # per row below.)
        from gol_tpu.ops import bitlife, pallas_bitlife

        lane_cells = pallas_bitlife._LANE * bitlife.BITS
        if size_per_chip % lane_cells:
            raise ValueError(
                f"engine {engine!r} on TPU needs size_per_chip to be a "
                f"multiple of {lane_cells} (128-lane packed width); got "
                f"{size_per_chip}"
            )
    multi = jax.process_count() > 1
    me = jax.process_index()
    # Validate every row's geometry up front, on every process: the checks
    # are deterministic, so a bad configuration fails identically
    # everywhere *before* the first row barrier — a participant raising
    # mid-sweep would leave the idle processes deadlocked at theirs.
    for n in counts:
        mesh, shape = _mesh_and_shape(n, size_per_chip, mesh_kind)
        if pallas_like or engine == "bitpack":
            # Packable widths are >= 32, so the square shard also always
            # clears the overlap form's 24-row interior/boundary minimum.
            packed_mod.validate_packed_geometry(shape, mesh)
        else:
            mesh_mod.validate_geometry(shape, mesh)
    rows: List[Dict[str, float]] = []
    base_per_chip: Optional[float] = None
    for n in counts:
        mesh, world = _mesh_and_shape(n, size_per_chip, mesh_kind)
        participating = {d.process_index for d in mesh.devices.flat}
        try:
            if me in participating:
                # Per-row seed: every process that measures row n builds
                # the identical board with no sequential PRNG coupling, so
                # idle processes skip at zero cost.
                rng = np.random.default_rng((0, n))
                board_np = (rng.random(world) < 0.35).astype(np.uint8)
                board = mesh_mod.shard_board(jnp.asarray(board_np), mesh)
                if pallas_like:
                    # The flagship multi-chip program (fused kernel per
                    # shard over the ring), serial or overlap form.
                    # Meaningful curves need a real TPU — interpret mode
                    # is far too slow.
                    evolve = packed_mod.compiled_evolve_packed_pallas(
                        mesh, steps, overlap=engine == "pallas_overlap"
                    )
                elif engine == "bitpack":
                    evolve = packed_mod.compiled_evolve_packed(mesh, steps)
                else:
                    evolve = sharded_mod.compiled_evolve(
                        mesh, steps, "explicit", 1
                    )
                dt = time_best(evolve, lambda b=board: jnp.array(b, copy=True))
                updates = world[0] * world[1] * steps
                per_chip = updates / dt / n
                if base_per_chip is None:
                    base_per_chip = per_chip
                rows.append(
                    {
                        "devices": n,
                        "mesh": dict(mesh.shape),
                        "seconds": dt,
                        "updates_per_s": updates / dt,
                        "per_chip": per_chip,
                        "efficiency": per_chip / base_per_chip,
                    }
                )
        finally:
            # Reached even if a participant's row fails at runtime, so the
            # others' barrier is never left waiting on a dead process.
            if multi:
                from jax.experimental import multihost_utils

                multihost_utils.sync_global_devices(f"scalebench_row_{n}")
    return rows


def main(argv=None) -> None:
    import argparse
    import sys

    from gol_tpu.parallel.multihost import add_multihost_args

    p = argparse.ArgumentParser(prog="scalebench")
    p.add_argument("positionals", nargs="*", metavar="ARG")
    # The multi-host trio, same surface as the main CLI: every process of
    # the job runs this module with its own --process-id.
    add_multihost_args(p)
    # Structured-telemetry sink (docs/OBSERVABILITY.md): every process
    # writes its own rank file with the rows it measured.
    p.add_argument("--telemetry", default=None, metavar="DIR")
    p.add_argument("--run-id", default=None, metavar="NAME")
    ns = p.parse_args(list(sys.argv[1:] if argv is None else argv))
    size = int(ns.positionals[0]) if len(ns.positionals) > 0 else 1024
    steps = int(ns.positionals[1]) if len(ns.positionals) > 1 else 64
    engine = ns.positionals[2] if len(ns.positionals) > 2 else "dense"
    mesh_kind = ns.positionals[3] if len(ns.positionals) > 3 else "1d"

    from gol_tpu.parallel import multihost

    topo = multihost.init_multihost(
        ns.coordinator, ns.num_processes, ns.process_id
    )
    rows = measure_weak_scaling(size, steps, engine, mesh_kind=mesh_kind)
    if ns.telemetry:
        from gol_tpu import telemetry as telemetry_mod

        with telemetry_mod.EventLog(ns.telemetry, run_id=ns.run_id) as ev:
            ev.run_header(
                dict(
                    tool="scalebench",
                    engine=engine,
                    mesh_kind=mesh_kind,
                    size_per_chip=size,
                    steps=steps,
                )
            )
            for row in rows:
                ev.bench_row("scalebench", row)
    if topo.is_coordinator:
        # Process 0 owns the full curve (its devices lead the global list,
        # so it participates in every row, including the 1-device
        # baseline); it reports alone, like the reference's rank 0.
        print(
            json.dumps(
                {
                    "size_per_chip": size,
                    "steps": steps,
                    "engine": engine,
                    "mesh_kind": mesh_kind,
                    "platform": jax.devices()[0].platform,
                    "processes": topo.process_count,
                    "rows": rows,
                }
            )
        )


if __name__ == "__main__":
    main()
