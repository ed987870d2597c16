"""Command-line driver preserving the reference's exact 5-argument surface.

The reference driver (``main``, gol-main.c:30-146) takes
``./gol <pattern> <worldSize> <iterations> <threadsPerBlock> <on_off>``
(parse at gol-main.c:43-53), runs the halo-exchange generation loop, prints
rank 0's timing line (gol-main.c:124-125) and a closing banner
(gol-main.c:132), and — when ``on_off == 1`` — dumps each rank's final block
to ``Rank_<r>_of_<n>.txt`` (gol-main.c:64-73,135-139).

This TPU driver keeps that surface verbatim and adds optional flags *after*
the five positionals:

- ``--ranks N``: logical rank count (the reference gets this from
  ``mpirun -np N``; here the world is ``N`` stacked ``S×S`` blocks evolved
  on however many TPU devices exist — logical decomposition is decoupled
  from physical chips).
- ``--halo {fresh,stale_t0}``: correct torus semantics (default) or the
  reference's as-implemented stale-halo semantics (bug B1) for bit-exact
  output parity.
- ``--engine {auto,dense,bitpack,pallas,pallas_bitpack,activity,ooc}``:
  stencil implementation tier (pallas_bitpack: fused carry-save kernel,
  fastest in-core; ooc: host-resident board streamed through a fixed
  device footprint — docs/STREAMING.md).
- ``--outdir DIR``, ``--profile DIR``, ``--compat-banner``,
  ``--checkpoint-every K`` / ``--resume PATH`` (capability additions).

One subcommand rides in front of the reference surface: ``python -m
gol_tpu verify`` runs the static invariant verifier
(:mod:`gol_tpu.analysis`) over the engine×mesh matrix and exits non-zero
on any violation — see ``docs/ANALYSIS.md``.

``threadsPerBlock`` configured the CUDA launch (gol-main.c:52,
gol-with-cuda.cu:272-275); XLA owns tiling here, so the value is validated
(fixing bug B5's silent 0-block no-op) and forwarded as the Pallas tile-size
hint where applicable.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from typing import Optional, Sequence

import numpy as np

from gol_tpu.parallel import modes as modes_mod

USAGE = (
    "GOL requires 5 arguments: pattern number, sq size of the world and the "
    "number of itterations, threads per block and output-on-off e.g. "
    "./gol 0 32 2 512 0 \n"
)

_ATOI_RE = re.compile(r"\s*([+-]?\d+)")


def atoi(text: str) -> int:
    """C ``atoi`` semantics (gol-main.c:49-53): leading integer, else 0."""
    m = _ATOI_RE.match(text)
    return int(m.group(1)) if m else 0


def parse_args(argv: Sequence[str]) -> Optional[argparse.Namespace]:
    """Parse the 5 reference positionals + extension flags.

    Returns None (after printing usage) when the positional count is wrong —
    the caller exits with the reference's status (-1 → 255).
    """
    ext = argparse.ArgumentParser(prog="gol", add_help=True)
    ext.add_argument("positionals", nargs="*", metavar="ARG")
    ext.add_argument("--ranks", type=int, default=1)
    ext.add_argument("--halo", choices=modes_mod.HALO_MODES, default="fresh")
    ext.add_argument(
        "--engine",
        choices=modes_mod.ENGINES,
        default="auto",
    )
    # Activity-gated tier knobs (docs/SPARSE.md): mask tile edge (0 =
    # auto-pick) and worklist capacity as a fraction of the per-shard
    # tile count (overflow generations fall back to one dense step).
    ext.add_argument("--activity-tile", type=int, default=0, metavar="T")
    ext.add_argument(
        "--activity-capacity", type=float, default=0.25, metavar="FRAC"
    )
    # Out-of-core streaming tier knobs (docs/STREAMING.md): device
    # footprint budget the band planner inverts (MiB; the board itself
    # lives in host RAM), an explicit band height override (rows; 0 =
    # derive from the budget), and the dead-band H2D/D2H skip switch.
    ext.add_argument("--ooc-budget-mb", type=int, default=256, metavar="MB")
    ext.add_argument("--ooc-band-rows", type=int, default=0, metavar="R")
    ext.add_argument(
        "--no-ooc-skip-dead", dest="ooc_skip_dead", action="store_false"
    )
    ext.add_argument("--mesh", choices=["none", "1d", "2d"], default="none")
    # Shard-mode matrix (gol_tpu/parallel/modes.py): hand-placed
    # ppermutes / depth-k comm-compute overlap / XLA auto-SPMD /
    # cross-chunk double-buffered pipeline (chunk N+1's ghost band ships
    # while chunk N's interior computes — docs/DESIGN.md).
    ext.add_argument(
        "--shard-mode",
        choices=modes_mod.SHARD_MODES,
        default="explicit",
    )
    ext.add_argument("--halo-depth", type=int, default=1, metavar="K")
    # Capability addition: any totalistic rule, e.g. --rule B36/S23
    # (HighLife). B3/S23 (the reference's hard-wired rule) is the default.
    ext.add_argument("--rule", default=None, metavar="B<d>/S<d>")
    ext.add_argument("--outdir", default=".")
    ext.add_argument("--profile", default=None, metavar="TRACE_DIR")
    # Structured JSONL telemetry (docs/OBSERVABILITY.md): per-process
    # event stream in DIR, summarized/diffed by `python -m
    # gol_tpu.telemetry`.  Multi-host jobs should pass an explicit
    # --run-id so every rank's file shares one prefix.
    ext.add_argument("--telemetry", default=None, metavar="DIR")
    ext.add_argument("--run-id", default=None, metavar="NAME")
    # Live metrics endpoint (docs/OBSERVABILITY.md): rank 0 serves
    # Prometheus text on 127.0.0.1:<P>/metrics (0 = ephemeral port,
    # printed at startup), fed by the same in-process event stream as
    # the JSONL files.  Requires --telemetry.
    ext.add_argument("--metrics-port", type=int, default=None, metavar="P")
    # Batched multi-world mode (gol_tpu/batch, docs/BATCHING.md): evolve
    # B independent worlds in one compiled program per size bucket,
    # amortizing the per-invocation launch overhead B-fold.  --batch-sizes
    # gives per-world square sizes (comma list, cycled over the B worlds;
    # default: every world uses the positional worldSize).  Mixed sizes
    # are padded+masked into buckets — one program per bucket, not per
    # shape.  --mesh 1d shards the world axis across devices.
    ext.add_argument("--batch", type=int, default=0, metavar="B")
    ext.add_argument("--batch-sizes", default=None, metavar="S1,S2,...")
    # XLA persistent compilation cache: repeat invocations load compiled
    # programs from DIR instead of re-running XLA (docs/BATCHING.md).
    # Applies to every mode, not just --batch.
    ext.add_argument("--compile-cache", default=None, metavar="DIR")
    # In-graph simulation statistics: each chunk additionally returns
    # fused device reductions (population, births/deaths, changed,
    # boundary-band populations — global via psum on sharded runs),
    # emitted as schema-v2 `stats` events.  Requires --telemetry (the
    # events are the output) and excludes --guard-every (the guard's
    # audit already reports population, and its rollback replay needs
    # the donated buffers stats mode must keep alive).
    ext.add_argument("--stats", action="store_true")
    ext.add_argument("--compat-banner", action="store_true")
    ext.add_argument("--checkpoint-every", type=int, default=0, metavar="K")
    ext.add_argument("--checkpoint-dir", default=None)
    ext.add_argument("--resume", default=None, metavar="CKPT")
    # Process-tier resilience (docs/RESILIENCE.md): --auto-resume starts
    # from the newest snapshot in the checkpoint dir that fully
    # fingerprint-verifies, falling back past corrupt/torn candidates
    # (multi-host ranks agree on min(newest valid)); `iterations` then
    # means the run's TOTAL generation target, so a preempted job
    # relaunched with identical argv completes exactly the remaining
    # work.  --keep-snapshots K retains only the newest K valid
    # snapshots after each save (0 keeps all).  SIGTERM/SIGINT stop the
    # run at the next chunk boundary with a final checkpoint and exit
    # code 75 (EX_TEMPFAIL: preempted, resumable).
    ext.add_argument("--auto-resume", action="store_true")
    ext.add_argument("--keep-snapshots", type=int, default=3, metavar="K")
    # Elastic meshes (docs/RESILIENCE.md): --allow-shrink lets a run
    # whose board cannot tile every visible device proceed on the
    # largest device count it divides (the degraded-pod relaunch path;
    # supervised children get it via GOL_ALLOW_SHRINK=1).
    # --sharded-snapshots writes the piece-table checkpoint directory
    # format even single-process.  --reshard-at GEN stops at the first
    # chunk boundary reaching GEN, snapshots, and continues the
    # remaining generations on --reshard-mesh — the in-flight reshard
    # drill knob (resume-on-a-new-mesh without leaving the process).
    ext.add_argument("--allow-shrink", action="store_true")
    ext.add_argument("--sharded-snapshots", action="store_true")
    ext.add_argument("--reshard-at", type=int, default=0, metavar="GEN")
    ext.add_argument(
        "--reshard-mesh", choices=["none", "1d", "2d"], default=None
    )
    # Multi-host (the `mpirun -np N` analog): connect this process to the
    # job before any device work; the mesh then spans the whole pod.
    from gol_tpu.parallel.multihost import add_multihost_args

    add_multihost_args(ext)
    # Failure detection + elastic recovery: audit the board every K
    # generations, roll back and replay on corruption (utils/guard.py).
    ext.add_argument("--guard-every", type=int, default=0, metavar="K")
    ext.add_argument("--guard-max-restores", type=int, default=3, metavar="N")
    # Cross-engine redundancy audit: recompute each audited chunk on a
    # second bit-exact engine and require matching fingerprints (catches
    # in-range flips the 0/1 invariant cannot see; ~2x audited compute).
    ext.add_argument("--guard-redundant", action="store_true")
    # Sampling for the redundancy audit: recompute every Nth audited
    # chunk (see utils/guard.py GuardConfig.redundant_every for the
    # coverage trade-off).
    ext.add_argument(
        "--guard-redundant-every", type=int, default=1, metavar="N"
    )
    # Declarative fault injection (docs/RESILIENCE.md "The fault
    # plane"): PATH to a JSON FaultPlan, or inline JSON.  The
    # GOL_FAULT_PLAN env var is the equivalent (supervised children
    # inherit it); legacy GOL_CKPT_TEST_WRITE_DELAY keeps working as a
    # documented alias for a checkpoint.rename_delay entry.
    ext.add_argument("--fault-plan", default=None, metavar="PLAN")
    ns = ext.parse_args(list(argv))
    if len(ns.positionals) != 5:
        sys.stdout.write(USAGE)
        return None
    ns.pattern = atoi(ns.positionals[0])
    ns.world_size = atoi(ns.positionals[1])
    ns.iterations = atoi(ns.positionals[2])
    ns.threads = atoi(ns.positionals[3])
    ns.on_off = atoi(ns.positionals[4])
    return ns


def _run_batch(
    ns, sizes, resume, resume_info, iterations, restart_attempt
) -> int:
    """The ``--batch`` driver: B independent worlds, one launch per bucket.

    Worlds are the CLI pattern at the ``--batch-sizes`` geometries
    (cycled over the B worlds; default: every world at the positional
    ``worldSize``).  Reuses the reference surface end to end — the
    TOTAL DURATION line counts every world's cell updates, ``on_off=1``
    dumps each world's rank files under ``outdir/world_<i>/``, and the
    resilience exit codes (75 = preempted, resumable) are unchanged.
    """
    from gol_tpu import resilience
    from gol_tpu.batch import GolBatchRuntime, make_batch_mesh
    from gol_tpu.models import patterns

    worlds = [
        patterns.init_global(ns.pattern, sizes[i % len(sizes)], ns.ranks)
        for i in range(ns.batch)
    ]
    try:
        brt = GolBatchRuntime(
            worlds=worlds,
            engine=ns.engine,
            mesh=make_batch_mesh() if ns.mesh == "1d" else None,
            tile_hint=ns.threads,
            checkpoint_every=ns.checkpoint_every,
            checkpoint_dir=ns.checkpoint_dir,
            keep_snapshots=ns.keep_snapshots,
            telemetry_dir=ns.telemetry,
            run_id=ns.run_id,
            compile_cache=ns.compile_cache,
            restart_attempt=restart_attempt,
            resume_info=resume_info,
            metrics_port=ns.metrics_port,
            guard_every=ns.guard_every,
            guard_max_restores=ns.guard_max_restores,
            guard_redundant=ns.guard_redundant,
            guard_redundant_every=ns.guard_redundant_every,
        )
        with resilience.preemption_guard():
            report, boards = brt.run(iterations, resume=resume)
    except resilience.Preempted as e:
        print(e)
        return resilience.EX_TEMPFAIL
    except (ValueError, OSError) as e:
        print(e)
        return 255

    print(report.duration_line())
    print(
        f"BATCH          : {ns.batch} worlds in {len(brt.buckets)} "
        f"bucket(s), {report.updates_per_sec / max(ns.batch, 1):.4g} "
        "cell-updates/sec per world"
    )
    if brt.last_guard is not None:
        print(brt.last_guard.summary_line())
    accelerator = "GPU" if ns.compat_banner else "TPU"
    print(
        f"This is the Game of Life running in parallel on a {accelerator} "
        "on multiple ranks."
    )
    if ns.on_off == 1:
        from gol_tpu.utils import io as gol_io

        for i, b in enumerate(boards):
            wdir = os.path.join(ns.outdir, f"world_{i:04d}")
            os.makedirs(wdir, exist_ok=True)
            gol_io.write_world_dumps(np.asarray(b), ns.ranks, wdir)
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "verify":
        # Static verification pass (gol_tpu.analysis): prove engine
        # invariants from traced programs before anything runs on a pod.
        from gol_tpu.analysis.__main__ import main as verify_main

        return verify_main(argv[1:])
    ns = parse_args(argv)
    if ns is None:
        return 255  # exit(-1) in the reference (gol-main.c:46)

    from gol_tpu.resilience import faults as faults_mod

    try:
        if ns.fault_plan:
            faults_mod.install(faults_mod.FaultPlan.load(ns.fault_plan))
        else:
            faults_mod.install_from_env()
    except faults_mod.FaultPlanError as e:
        print(e)
        return 255

    from gol_tpu.models import patterns
    from gol_tpu.models.state import Geometry
    from gol_tpu.parallel import multihost
    from gol_tpu.runtime import GolRuntime, build_mesh

    try:
        topo = multihost.init_multihost(
            coordinator_address=ns.coordinator,
            num_processes=ns.num_processes,
            process_id=ns.process_id,
        )
    except (ValueError, RuntimeError) as e:
        print(e)
        return 255

    if topo.process_count > 1 and ns.mesh == "none":
        # Without a pod-spanning mesh every process would evolve its own
        # private single-device world and race to write the same dump and
        # checkpoint files.
        print(
            f"multi-host run ({topo.process_count} processes) requires a "
            "device mesh; pass --mesh 1d or --mesh 2d"
        )
        return 255

    if ns.on_off == 1 and not ns.batch:
        # Reference lifecycle (gol-main.c:64-73): every rank's dump file is
        # fopen'd "w" right after MPI_Init, BEFORE world init/validation —
        # files exist (truncated) from startup even if the run later dies,
        # and open failure prints the exact "ERROR IN RANK %d" diagnostic.
        from gol_tpu.utils import io as gol_io

        try:
            if topo.process_count > 1:
                try:
                    multihost.precreate_host_dump_files(
                        build_mesh(ns.mesh),
                        (ns.world_size * ns.ranks, ns.world_size),
                        ns.ranks,
                        ns.outdir,
                    )
                except ValueError:
                    pass  # invalid geometry/mesh: validation below reports it
            else:
                gol_io.create_rank_files(
                    range(max(ns.ranks, 0)), ns.ranks, ns.outdir
                )
        except gol_io.RankFileError as e:
            sys.stdout.write(f"ERROR IN RANK {e.rank}")
            return 255  # exit(-1) in the reference (gol-main.c:70)

    try:
        geom = Geometry(size=ns.world_size, num_ranks=ns.ranks)
        patterns.validate_pattern_size(ns.pattern, ns.world_size)
        if ns.threads <= 0:
            raise ValueError(
                f"threads per block must be positive, got {ns.threads} "
                "(the reference silently launched zero blocks here — bug B5)"
            )
        if ns.iterations < 0:
            raise ValueError(f"iterations must be >= 0, got {ns.iterations}")
        if ns.guard_redundant and ns.guard_every <= 0:
            raise ValueError(
                "--guard-redundant audits chunks, so it requires "
                "--guard-every K > 0"
            )
        if ns.guard_redundant_every != 1 and not ns.guard_redundant:
            raise ValueError(
                "--guard-redundant-every samples the redundancy audit, "
                "so it requires --guard-redundant"
            )
        if ns.guard_every < 0:
            raise ValueError(
                f"--guard-every must be >= 0, got {ns.guard_every} "
                "(0 disables the guard)"
            )
        if ns.stats and not ns.telemetry:
            raise ValueError(
                "--stats emits schema-v2 stats events, so it requires "
                "--telemetry DIR"
            )
        if ns.metrics_port is not None and not ns.telemetry:
            raise ValueError(
                "--metrics-port serves the in-process event stream, so "
                "it requires --telemetry DIR"
            )
        if ns.metrics_port is not None and not (
            0 <= ns.metrics_port <= 65535
        ):
            raise ValueError(
                f"--metrics-port must be 0..65535 (0 = ephemeral), got "
                f"{ns.metrics_port}"
            )
        if ns.stats and ns.guard_every > 0:
            raise ValueError(
                "--stats applies to unguarded runs; drop --guard-every "
                "(the guard's audit already reports population per chunk)"
            )
        if (ns.activity_tile or ns.activity_capacity != 0.25) \
                and ns.engine != "activity":
            raise ValueError(
                "--activity-tile/--activity-capacity configure the "
                "activity tier; pass --engine activity"
            )
        if (
            ns.ooc_budget_mb != 256
            or ns.ooc_band_rows
            or not ns.ooc_skip_dead
        ) and ns.engine != "ooc":
            raise ValueError(
                "--ooc-budget-mb/--ooc-band-rows/--no-ooc-skip-dead "
                "configure the out-of-core streaming tier; pass "
                "--engine ooc"
            )
        if ns.engine == "ooc" and ns.guard_every > 0:
            raise ValueError(
                "the checkpoint-restore guard re-executes chunks through "
                "the compiled in-core evolvers; engine 'ooc' streams a "
                "host-resident board, so drop --guard-every (its band "
                "write-backs already run under the retry/containment "
                "plane), or guard an in-core engine ('dense', 'bitpack', "
                "'pallas_bitpack', 'activity')"
            )
        if ns.auto_resume and ns.resume:
            raise ValueError(
                "--auto-resume selects the snapshot itself; pass one of "
                "--resume/--auto-resume, not both"
            )
        if ns.keep_snapshots < 0:
            raise ValueError(
                f"--keep-snapshots must be >= 0, got {ns.keep_snapshots} "
                "(0 keeps every snapshot)"
            )
        if ns.reshard_at < 0:
            raise ValueError(
                f"--reshard-at must be >= 0, got {ns.reshard_at} "
                "(0 disables the in-flight reshard stop)"
            )
        if ns.reshard_at > 0:
            if ns.reshard_mesh is None:
                raise ValueError(
                    "--reshard-at stops to continue on a new topology; "
                    "pass --reshard-mesh {none,1d,2d}"
                )
            if topo.process_count > 1:
                raise ValueError(
                    "--reshard-at is single-process (a multi-host job "
                    "reshapes by relaunching under --auto-resume)"
                )
            if ns.guard_every > 0:
                raise ValueError(
                    "--reshard-at applies to unguarded runs; drop "
                    "--guard-every"
                )
            if ns.batch:
                raise ValueError(
                    "--reshard-at applies to single-world runs; drop "
                    "--batch"
                )
            if ns.halo != "fresh":
                raise ValueError(
                    "--reshard-at runs fresh halos only (stale_t0 worlds "
                    "are single-device by definition)"
                )
            # The stop writes through a snapshot; give it a home.
            ns.checkpoint_dir = ns.checkpoint_dir or "checkpoints"
        elif ns.reshard_mesh is not None:
            raise ValueError(
                "--reshard-mesh names the post-stop topology; pass "
                "--reshard-at GEN"
            )
        if ns.sharded_snapshots and ns.mesh == "none" and not ns.reshard_at:
            raise ValueError(
                "--sharded-snapshots writes the piece-table directory "
                "format, which shards over a mesh; pass --mesh 1d/2d"
            )
        if ns.batch < 0:
            raise ValueError(f"--batch must be >= 0, got {ns.batch}")
        if ns.batch_sizes and not ns.batch:
            raise ValueError(
                "--batch-sizes applies to batched runs; pass --batch B"
            )
        batch_sizes = None
        if ns.batch:
            # Batched multi-world mode: single-process, fresh-halo,
            # Conway-only (the batched tiers are the B3/S23 fast paths);
            # the guard/stats observers are single-world subsystems.
            if topo.process_count > 1:
                raise ValueError(
                    "--batch is single-process (its mesh spans local "
                    "devices); drop the multi-host flags"
                )
            modes_mod.check_run(
                ns.engine,
                ns.engine,
                halo_mode=ns.halo,
                custom_rule=bool(ns.rule),
                mesh=None if ns.mesh == "none" else ns.mesh,
                batch=True,
            )
            if ns.stats:
                raise ValueError(
                    "--stats is a single-world observer; drop it in "
                    "--batch mode (guarded batch runs report per-world "
                    "audit populations instead)"
                )
            if ns.profile:
                raise ValueError(
                    "--profile applies to single-world runs; drop --batch"
                )
            sizes_text = ns.batch_sizes or str(ns.world_size)
            batch_sizes = [atoi(s) for s in sizes_text.split(",") if s]
            if not batch_sizes or any(s <= 0 for s in batch_sizes):
                raise ValueError(
                    f"--batch-sizes {sizes_text!r} must be a comma list "
                    "of positive world sizes (parses to no sizes)"
                )
            for s in batch_sizes:
                Geometry(size=s, num_ranks=ns.ranks)
                patterns.validate_pattern_size(ns.pattern, s)
    except ValueError as e:
        print(e)
        return 255

    # Persistent XLA compilation cache (docs/BATCHING.md): placed before
    # any program compiles so every mode benefits.
    from gol_tpu.batch import cache as cache_mod

    cache_mod.setup_compile_cache(ns.compile_cache)

    from gol_tpu import resilience

    resume = ns.resume
    resume_info = None
    iterations = ns.iterations
    if ns.auto_resume:
        # The walk + (multi-host) min-generation agreement is collective:
        # every process calls it, every process gets the same answer.
        ns.checkpoint_dir = ns.checkpoint_dir or "checkpoints"
        try:
            resume, resume_info = resilience.resolve_auto_resume(
                ns.checkpoint_dir, kind="batch" if ns.batch else "2d"
            )
        except (ValueError, OSError) as e:
            print(e)
            return 255
        if resume is not None:
            # Under auto-resume `iterations` is the TOTAL target: a
            # relaunch with identical argv completes the remaining work.
            iterations = max(0, ns.iterations - resume_info["generation"])
            if topo.is_coordinator:
                print(
                    f"auto-resume: generation "
                    f"{resume_info['generation']} from {resume}"
                    + (
                        "  [fallback: skipped "
                        + ", ".join(resume_info["skipped"])
                        + "]"
                        if resume_info["fallback"] and resume_info["skipped"]
                        else "  [fallback]"
                        if resume_info["fallback"]
                        else ""
                    )
                )
        elif topo.is_coordinator:
            print(
                f"auto-resume: no valid snapshot in {ns.checkpoint_dir}; "
                "starting fresh"
            )

    try:
        restart_attempt = int(os.environ.get("GOL_RESTART_ATTEMPT", "0"))
    except ValueError:
        restart_attempt = 0

    if ns.batch:
        return _run_batch(
            ns, batch_sizes, resume, resume_info, iterations, restart_attempt
        )

    # Elastic shrink policy (docs/RESILIENCE.md): opt in via the flag or
    # the supervisor's environment export, so a supervised relaunch that
    # comes up with a device count the board cannot tile proceeds on a
    # smaller mesh instead of crashing its restart budget.
    allow_shrink = ns.allow_shrink or (
        os.environ.get("GOL_ALLOW_SHRINK") == "1"
    )
    board_shape = (ns.world_size * ns.ranks, ns.world_size)

    def make_runtime(mesh_kind, run_id, reshard_at, rt_resume_info):
        return GolRuntime(
            geometry=geom,
            engine=ns.engine,
            halo_mode=ns.halo,
            tile_hint=ns.threads,
            checkpoint_every=ns.checkpoint_every,
            checkpoint_dir=ns.checkpoint_dir,
            mesh=build_mesh(
                mesh_kind, shape=board_shape, allow_shrink=allow_shrink
            ),
            shard_mode=ns.shard_mode,
            halo_depth=ns.halo_depth,
            rule=ns.rule,
            telemetry_dir=ns.telemetry,
            run_id=run_id,
            stats=ns.stats,
            keep_snapshots=ns.keep_snapshots,
            restart_attempt=restart_attempt,
            resume_info=rt_resume_info,
            activity_tile=ns.activity_tile,
            activity_capacity=ns.activity_capacity,
            ooc_budget_mb=ns.ooc_budget_mb,
            ooc_band_rows=ns.ooc_band_rows,
            ooc_skip_dead=ns.ooc_skip_dead,
            metrics_port=ns.metrics_port,
            reshard_at=reshard_at,
            sharded_snapshots=ns.sharded_snapshots,
        )

    try:
        rt = make_runtime(ns.mesh, ns.run_id, ns.reshard_at, resume_info)
        guard_report = None
        with resilience.preemption_guard():
            if ns.guard_every > 0:
                from gol_tpu.utils import guard as guard_mod

                if ns.profile:
                    raise ValueError(
                        "--profile applies to unguarded runs; drop "
                        "--guard-every"
                    )
                report, final_state, guard_report = guard_mod.run_guarded(
                    rt,
                    pattern=ns.pattern,
                    iterations=iterations,
                    config=guard_mod.GuardConfig(
                        check_every=ns.guard_every,
                        max_restores=ns.guard_max_restores,
                        redundant=ns.guard_redundant,
                        redundant_every=ns.guard_redundant_every,
                    ),
                    resume=resume,
                )
            else:
                try:
                    report, final_state = rt.run(
                        pattern=ns.pattern,
                        iterations=iterations,
                        resume=resume,
                        profile_dir=ns.profile,
                    )
                except resilience.ReshardPoint as rp:
                    # In-flight reshard (--reshard-at): the run stopped
                    # at a chunk boundary through a snapshot; replan and
                    # finish the remaining generations on the new mesh
                    # in this same process.  The resumed runtime detects
                    # the topology change itself and stamps the v7
                    # reshard telemetry event.
                    if topo.is_coordinator:
                        print(
                            f"reshard: generation {rp.generation}, mesh "
                            f"{ns.mesh} -> {ns.reshard_mesh} "
                            f"({rp.remaining} generations remain)"
                        )
                    rt = make_runtime(
                        ns.reshard_mesh,
                        f"{ns.run_id}-reshard" if ns.run_id else None,
                        0,
                        None,
                    )
                    report, final_state = rt.run(
                        pattern=ns.pattern,
                        iterations=rp.remaining,
                        resume=rp.snapshot_path,
                        profile_dir=None,
                    )
    except resilience.Preempted as e:
        # NOT the error path: the run stopped cleanly at a chunk
        # boundary with a resumable snapshot.  EX_TEMPFAIL tells a
        # scheduler/supervisor "relaunch me" — this run relaunched with
        # --auto-resume completes the remaining generations bit-exactly.
        if topo.is_coordinator:
            print(e)
        return resilience.EX_TEMPFAIL
    except (ValueError, OSError) as e:
        # Same clean-error convention as the pre-validation path: bad
        # --resume paths/shapes, unavailable engines, unwritable dirs,
        # corrupt snapshots, exhausted guard restore budgets (both are
        # ValueError subclasses).
        print(e)
        from gol_tpu.utils.checkpoint import CorruptSnapshotError

        if isinstance(e, CorruptSnapshotError) and ns.resume:
            # Satellite fix: a corrupt --resume target is rarely the end
            # of the line — say where the walk would have landed.
            hint = resilience.corrupt_resume_hint(ns.resume, kind="2d")
            if hint:
                print(
                    f"hint: an earlier valid snapshot exists at {hint}; "
                    "resume from it, or rerun with --auto-resume to "
                    "select it (and fall back) automatically"
                )
        elif ns.resume and (
            "not divisible by mesh" in str(e)
            or "does not divide" in str(e)
            or "empty shards" in str(e)
        ):
            # Topology mismatch on a plain --resume: the board in the
            # snapshot cannot tile the requested mesh.  Resharding is
            # automatic on any mesh that CAN tile it — say so instead
            # of leaving the raw divisibility error as the last word.
            hint = resilience.topology_resume_hint(ns.resume, kind="2d")
            if hint:
                print(hint)
        return 255

    # Rank 0's report (gol-main.c:121-128) + closing banner (gol-main.c:132);
    # only the coordinator prints, exactly as only MPI rank 0 did.
    if topo.is_coordinator:
        print(report.duration_line())
        if guard_report is not None:
            print(guard_report.summary_line())
        accelerator = "GPU" if ns.compat_banner else "TPU"
        print(
            f"This is the Game of Life running in parallel on a {accelerator} "
            "on multiple ranks."
        )

    if ns.on_off == 1:
        if topo.process_count > 1:
            # Each host writes the rank files its shards cover (the MPI
            # every-rank-writes-its-own-block I/O pattern, gol-main.c:135-139).
            multihost.write_host_dumps(
                final_state.board, geom.num_ranks, ns.outdir
            )
        else:
            from gol_tpu.utils import io as gol_io

            gol_io.write_world_dumps(
                np.asarray(final_state.board), geom.num_ranks, ns.outdir
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
