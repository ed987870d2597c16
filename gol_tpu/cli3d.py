"""3-D Life driver: the volume counterpart of the 2-D reference CLI.

A capability addition (the reference is strictly 2-D), styled after the
same surface so the two drivers feel like one tool:

    python -m gol_tpu.cli3d <pattern> <size> <iterations> <threads> <on_off>
        [--rule NAME|B../S..] [--engine {auto,dense,bitpack,pallas}]
        [--mesh {none,3d}] [--outdir DIR]
        [--checkpoint-every K] [--checkpoint-dir DIR] [--resume CKPT]

Patterns: 0 all-zeros, 1 all-ones, 2 random (density 0.3, fixed seed 0 —
deterministic across engines and meshes).  ``size`` is the cube edge
D = H = W; ``threads`` is accepted for surface parity with the 2-D driver
and validated (>0, fixing the reference's bug-B5 class) but tiling is
chosen automatically by the engines.
Rules default to Bays 4555 (named: ``bays4555``, ``bays5766``, or any
``B<counts>/S<counts>`` with comma-separated multi-digit counts, e.g.
``B5/S4,5``).  With ``on_off=1`` the final volume is written to
``World3D_of_<n>.npy`` in ``--outdir`` (NumPy format — there is no
reference 3-D dump format to match).

Prints the reference-style duration line plus the live-cell population
(the 3-D analog of eyeballing rank dumps).
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from typing import Optional, Sequence

import numpy as np

from gol_tpu import resilience
from gol_tpu.cli import atoi

ENGINES3D = ("auto", "dense", "bitpack", "pallas")

USAGE3D = (
    "gol3d requires 5 arguments: pattern number (0 zeros, 1 ones, 2 "
    "random), cube edge size, iterations, threads per block and "
    "output-on-off e.g. python -m gol_tpu.cli3d 2 64 10 512 0 \n"
)

_RULE3D_RE = re.compile(r"^B([\d,]*)/S([\d,]*)$", re.IGNORECASE)


def parse_rule3d(text: str):
    """Named rule or ``B<counts>/S<counts>`` (comma-separated counts 0-26)."""
    from gol_tpu.ops import life3d

    named = {"bays4555": life3d.BAYS_4555, "bays5766": life3d.BAYS_5766}
    if text.lower() in named:
        return named[text.lower()]
    m = _RULE3D_RE.match(text.strip())
    if not m:
        raise ValueError(
            f"malformed 3-D rule {text!r}; expected a name "
            f"({', '.join(sorted(named))}) or B<counts>/S<counts> with "
            "comma-separated counts, e.g. B5/S4,5"
        )

    def counts(group: str):
        return frozenset(int(t) for t in group.split(",") if t)

    rule = life3d.Rule3D(birth=counts(m.group(1)), survive=counts(m.group(2)))
    if any(c > 26 for c in rule.birth | rule.survive):
        raise ValueError(f"3-D rule {text!r} has counts > 26")
    return rule


def init_volume(pattern: int, size: int) -> np.ndarray:
    if pattern == 0:
        return np.zeros((size, size, size), np.uint8)
    if pattern == 1:
        return np.ones((size, size, size), np.uint8)
    if pattern == 2:
        rng = np.random.default_rng(0)
        return (rng.random((size, size, size)) < 0.3).astype(np.uint8)
    raise ValueError(f"Pattern {pattern} has not been implemented")


def _halo3d_block(mode: str, k: int, mesh, size: int, take: int) -> dict:
    """One 3-D chunk's schema-v8 ``halo`` block: the packed ring tier's
    exchange depth/count and band traffic (three ppermute phases — plane
    band, row band of the plane-extended shard, word column of both)."""
    from gol_tpu.parallel.mesh import COLS, PLANES, ROWS

    npl = mesh.shape.get(PLANES, 1)
    nr = mesh.shape.get(ROWS, 1)
    nc = mesh.shape.get(COLS, 1)
    d, h, nw = size // npl, size // nr, size // nc // 32

    def band_bytes(dd: int) -> int:
        planes = 2 * dd * h * nw * 4
        rows = 2 * dd * (d + 2 * dd) * nw * 4
        cols = 2 * dd * (d + 2 * dd) * (h + 2 * dd) * 4
        return planes + rows + cols

    full, rem = divmod(take, k)
    chunk_bytes = full * band_bytes(k) + (band_bytes(rem) if rem else 0)
    state = d * h * nw * 4
    payload = chunk_bytes + take * state
    return {
        "depth": k,
        "mode": mode,
        "exchanges": full + (1 if rem else 0),
        "band_bytes": chunk_bytes,
        "exchange_share": chunk_bytes / payload if payload else 0.0,
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    ext = argparse.ArgumentParser(prog="gol3d", add_help=True)
    ext.add_argument("positionals", nargs="*", metavar="ARG")
    ext.add_argument("--rule", default="bays4555")
    ext.add_argument("--engine", choices=ENGINES3D, default="auto")
    ext.add_argument("--mesh", choices=["none", "3d"], default="none")
    # Ring chunk form + temporal blocking for the packed sharded tier
    # (--engine bitpack --mesh 3d): explicit serial chunks, the depth-k
    # interior/boundary overlap split, or the cross-chunk pipelined
    # double buffer — same matrix as the 2-D driver, one dimension up
    # (gol_tpu/parallel/modes.py).
    ext.add_argument(
        "--shard-mode",
        choices=["explicit", "overlap", "pipeline"],
        default="explicit",
    )
    ext.add_argument("--halo-depth", type=int, default=1, metavar="K")
    # Explicit (planes, rows, cols) factorization: the fused sharded
    # kernel needs one of planes/rows to be 1 ((P,1,C) or (1,R,C)),
    # which the default most-cubic factorization of 8 devices (2,2,2)
    # is not.
    ext.add_argument("--mesh-shape", default=None, metavar="P,R,C")
    ext.add_argument("--outdir", default=".")
    # Checkpoint/resume, mirroring the 2-D driver: periodic
    # fingerprint-stamped volume snapshots, verified + rule-checked on
    # resume.  Sharded (mesh) runs write the piece-file directory format —
    # no host ever assembles the volume (utils/checkpoint.py
    # save_sharded3d); single-device runs keep the monolithic npz.
    ext.add_argument("--checkpoint-every", type=int, default=0, metavar="K")
    ext.add_argument("--checkpoint-dir", default="checkpoints3d")
    ext.add_argument("--resume", default=None, metavar="CKPT")
    # Process-tier resilience, exactly the 2-D driver's surface
    # (docs/RESILIENCE.md): validated auto-resume with total-target
    # iteration semantics, keep-last-K snapshot retention, and
    # SIGTERM/SIGINT → chunk-boundary checkpoint + exit 75.
    ext.add_argument("--auto-resume", action="store_true")
    ext.add_argument("--keep-snapshots", type=int, default=3, metavar="K")
    # Multi-host trio + failure detection, exactly the 2-D driver's
    # surface (gol_tpu/cli.py).
    from gol_tpu.parallel import multihost

    multihost.add_multihost_args(ext)
    ext.add_argument("--guard-every", type=int, default=0, metavar="K")
    ext.add_argument("--guard-max-restores", type=int, default=3, metavar="N")
    ext.add_argument("--guard-redundant", action="store_true")
    ext.add_argument(
        "--guard-redundant-every", type=int, default=1, metavar="N"
    )
    # jax.profiler trace of the steady-state loop (2-D driver parity).
    ext.add_argument("--profile", default=None, metavar="TRACE_DIR")
    # Structured JSONL telemetry, same surface and schema as the 2-D
    # driver (docs/OBSERVABILITY.md).
    ext.add_argument("--telemetry", default=None, metavar="DIR")
    ext.add_argument("--run-id", default=None, metavar="NAME")
    # Live metrics endpoint, same surface as the 2-D driver
    # (docs/OBSERVABILITY.md): rank 0 serves Prometheus text fed by the
    # in-process event stream.  Requires --telemetry.
    ext.add_argument("--metrics-port", type=int, default=None, metavar="P")
    # In-graph volume statistics per chunk (schema-v2 `stats` events):
    # population/births/deaths/changed fused onto the chunk program —
    # same surface and constraints as the 2-D driver's --stats.
    ext.add_argument("--stats", action="store_true")
    # Declarative fault injection, same surface as the 2-D driver
    # (docs/RESILIENCE.md): PATH or inline JSON; GOL_FAULT_PLAN is the
    # env equivalent.  3-D board.bitflip entries use plane/row/col.
    ext.add_argument("--fault-plan", default=None, metavar="PLAN")
    ns = ext.parse_args(argv)
    if len(ns.positionals) != 5:
        sys.stdout.write(USAGE3D)
        return 255
    pattern = atoi(ns.positionals[0])
    size = atoi(ns.positionals[1])
    iterations = atoi(ns.positionals[2])
    threads = atoi(ns.positionals[3])
    on_off = atoi(ns.positionals[4])

    from gol_tpu.batch import cache as cache_mod

    cache_mod.setup_compile_cache()

    from gol_tpu.resilience import degrade as degrade_mod
    from gol_tpu.resilience import faults as faults_mod

    try:
        if ns.fault_plan:
            faults_mod.install(faults_mod.FaultPlan.load(ns.fault_plan))
        else:
            faults_mod.install_from_env()
    except faults_mod.FaultPlanError as e:
        print(e)
        return 255
    plan_on = faults_mod.active() is not None

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
        print(
            f"multi-host run ({topo.process_count} processes) requires a "
            "device mesh; pass --mesh 3d"
        )
        return 255

    guard_report = None
    ckpt_writer = None
    events = None
    try:
        if size <= 0:
            raise ValueError(f"size must be positive, got {size}")
        if iterations < 0:
            raise ValueError(f"iterations must be >= 0, got {iterations}")
        if threads <= 0:
            raise ValueError(f"threads per block must be positive, got {threads}")
        if ns.checkpoint_every < 0:
            raise ValueError(
                f"--checkpoint-every must be >= 0, got {ns.checkpoint_every}"
            )
        if ns.guard_every < 0:
            raise ValueError(
                f"--guard-every must be >= 0, got {ns.guard_every} "
                "(0 disables the guard)"
            )
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
        if ns.profile and ns.guard_every > 0:
            raise ValueError(
                "--profile applies to unguarded runs; drop --guard-every"
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
        rule = parse_rule3d(ns.rule)

        import jax

        from gol_tpu.ops.life3d import rulestring3d
        from gol_tpu.utils import checkpoint as ckpt_mod

        rulestr = rulestring3d(rule)

        mesh = None
        if ns.mesh == "3d":
            from gol_tpu.parallel import mesh as mesh_mod

            shape3 = None
            if ns.mesh_shape:
                parts = ns.mesh_shape.split(",")
                if len(parts) != 3 or not all(
                    p.strip().isdigit() for p in parts
                ):
                    raise ValueError(
                        f"--mesh-shape must be P,R,C integers, got "
                        f"{ns.mesh_shape!r}"
                    )
                shape3 = tuple(int(p) for p in parts)
            if shape3 is not None:
                # An explicit factorization may use a subset of the
                # visible devices (e.g. an H-unsharded mesh on a pod
                # whose count doesn't factor as P*1*C).
                n3 = shape3[0] * shape3[1] * shape3[2]
                if n3 > len(jax.devices()):
                    raise ValueError(
                        f"--mesh-shape {ns.mesh_shape} needs {n3} devices, "
                        f"only {len(jax.devices())} visible"
                    )
                mesh = mesh_mod.make_mesh_3d(
                    shape3, devices=jax.devices()[:n3]
                )
            else:
                mesh = mesh_mod.make_mesh_3d()
        elif ns.mesh_shape:
            raise ValueError("--mesh-shape requires --mesh 3d")

        def check_meta(shape, found_rule):
            if tuple(shape) != (size, size, size):
                raise ValueError(
                    f"checkpoint volume {tuple(shape)} != configured "
                    f"{(size, size, size)}"
                )
            if found_rule != rulestr:
                raise ValueError(
                    f"checkpoint was written by a {found_rule} run; this "
                    f"run is configured for {rulestr} — pass the matching "
                    "--rule to resume"
                )

        resume_src = ns.resume
        resume_info = None
        if ns.auto_resume:
            # Collective on multi-host jobs (min-generation agreement).
            resume_src, resume_info = resilience.resolve_auto_resume(
                ns.checkpoint_dir, kind="3d"
            )
            if resume_src is not None:
                # Total-target semantics: identical argv after a
                # preemption completes exactly the remaining generations.
                iterations = max(0, iterations - resume_info["generation"])
                if topo.is_coordinator:
                    print(
                        f"auto-resume: generation "
                        f"{resume_info['generation']} from {resume_src}"
                        + ("  [fallback]" if resume_info["fallback"] else "")
                    )
            elif topo.is_coordinator:
                print(
                    f"auto-resume: no valid snapshot in "
                    f"{ns.checkpoint_dir}; starting fresh"
                )

        generation = 0
        vol = None
        placed = None  # sharded resumes build the device array directly
        if resume_src:
            if ckpt_mod.is_sharded(resume_src):
                meta = ckpt_mod.load_sharded3d_meta(resume_src)
                check_meta(meta.shape, meta.rule)
                generation = meta.generation
                if mesh is not None:
                    from gol_tpu.parallel import sharded3d

                    # Each host reads back only the boxes its devices own.
                    placed = jax.make_array_from_callback(
                        meta.shape,
                        sharded3d.volume_sharding(mesh),
                        lambda idx: ckpt_mod.read_sharded3d_region(
                            resume_src, meta, idx
                        ),
                    )
                else:
                    vol = ckpt_mod.read_sharded3d_region(
                        resume_src,
                        meta,
                        (slice(None), slice(None), slice(None)),
                    )
            else:
                snap = ckpt_mod.load3d(resume_src)
                check_meta(snap.volume.shape, snap.rule)
                vol = snap.volume
                generation = snap.generation
        else:
            vol = init_volume(pattern, size)

        from gol_tpu.utils.timing import Stopwatch, force_ready

        from gol_tpu.volume import VolumeRuntime

        # One runtime resolves the engine and compiles and steps the
        # chunk programs; the resolved name also picks the redundant
        # checker's counterpart engine.
        rt = VolumeRuntime(
            size=size, rule=rule, engine=ns.engine, mesh=mesh,
            shard_mode=ns.shard_mode, halo_depth=ns.halo_depth,
            stats=ns.stats,
        )
        resolved = rt.resolved

        if ns.halo_depth < 1:
            raise ValueError(
                f"--halo-depth must be >= 1, got {ns.halo_depth}"
            )
        if ns.shard_mode != "explicit" or ns.halo_depth != 1:
            # The chunk-form knobs configure the packed ring tier's
            # exchange; everything else either has no ring (mesh none,
            # dense) or owns its own banding (the fused Pallas engine).
            if mesh is None:
                raise ValueError(
                    "--shard-mode/--halo-depth configure the sharded "
                    "ring exchange; pass --mesh 3d"
                )
            if resolved != "bitpack":
                raise ValueError(
                    f"--shard-mode {ns.shard_mode!r}/--halo-depth "
                    f"{ns.halo_depth} apply to the packed ring tier "
                    f"(engine 'bitpack'); resolved engine is "
                    f"{resolved!r} — pass --engine bitpack (the fused "
                    "3-D Pallas engine keeps its own 8-deep banding)"
                )

        from gol_tpu import telemetry as telemetry_mod

        num_devices = 1 if mesh is None else mesh.devices.size
        shard_cells = size**3 // max(num_devices, 1)
        try:
            restart_attempt = int(os.environ.get("GOL_RESTART_ATTEMPT", "0"))
        except ValueError:
            restart_attempt = 0
        if ns.telemetry:
            events = telemetry_mod.EventLog(ns.telemetry, run_id=ns.run_id)
            if ns.metrics_port is not None and topo.is_coordinator:
                # Rank 0 only: one scrape surface per job, attached
                # before the header emits (main's finally closes the
                # server with the event log).
                from gol_tpu.telemetry import metrics as metrics_mod

                metrics_mod.serve_event_metrics(events, ns.metrics_port)
            events.run_header(
                dict(
                    driver="3d",
                    engine=ns.engine,
                    resolved_engine=resolved,
                    mesh=None if mesh is None else dict(mesh.shape),
                    shard_mode=ns.shard_mode,
                    halo_depth=ns.halo_depth,
                    rule=rulestr,
                    size=size,
                    checkpoint_every=ns.checkpoint_every,
                )
            )
            if restart_attempt > 0:
                events.restart_event(restart_attempt)
            if resume_info is not None and resume_info.get("path"):
                events.resume_event(
                    generation=resume_info["generation"],
                    path=resume_info["path"],
                    fallback=bool(resume_info.get("fallback")),
                    skipped=resume_info.get("skipped") or [],
                )

        def util3d(take, wall_s):
            return telemetry_mod.roofline_utilization_3d(
                resolved, shard_cells, take, wall_s
            )

        # Async writer for the single-device path (same overlap +
        # final-flush contract as GolRuntime.run; the sharded save ends
        # in a device barrier and must stay on the main thread).  The
        # close() in main's finally drains queued writes even when the
        # loop raises — e.g. a guard restore-budget exhaustion, the exact
        # case mid-run snapshots exist for.
        ckpt_writer = (
            ckpt_mod.AsyncSnapshotWriter()
            if ns.checkpoint_every > 0 and mesh is None and iterations > 0
            else None
        )

        def gc_old_snapshots():
            if ns.keep_snapshots > 0:
                resilience.gc_snapshots(
                    ns.checkpoint_dir,
                    ns.keep_snapshots,
                    kind="3d",
                    protect=(resume_src,),
                )

        # Checkpoint containment (docs/RESILIENCE.md "Retry and shed"):
        # transient write errors retry with backoff; persistent ENOSPC
        # sheds telemetry first, then checkpointing — never the run.
        ckpt_state = {"shed": False}

        def shed_telemetry(reason):
            if events is not None:
                events.request_shed("telemetry", reason)

        def save_snapshot(b, g, fp=None):
            if ckpt_state["shed"]:
                return
            if mesh is not None:
                ok = degrade_mod.write_with_retry(
                    lambda: ckpt_mod.save_sharded3d(
                        ckpt_mod.sharded_checkpoint3d_path(
                            ns.checkpoint_dir, g
                        ),
                        b,
                        g,
                        rulestr,
                        fingerprint=fp,
                    ),
                    generation=g,
                    shed_telemetry=shed_telemetry,
                )
                from jax.experimental import multihost_utils

                # The barrier runs even on a shed write: a degraded
                # rank must not strand its peers in the fence.
                multihost_utils.sync_global_devices("gol3d_checkpoint")
                if not ok:
                    ckpt_state["shed"] = True
                    return
                # Retention after the barrier, one process sweeping.
                if jax.process_index() == 0:
                    gc_old_snapshots()
            else:
                path = ckpt_mod.checkpoint3d_path(ns.checkpoint_dir, g)
                # Host fetch on this thread (donation fence — and a
                # background fetch would contend with the next chunk's
                # device execution, see GolRuntime._save_snapshot); the
                # compressed write overlaps.  GC rides behind the save
                # on whichever thread performs it.
                vol_np = np.asarray(b)

                def write(p=path, v=vol_np, g=g, fp=fp):
                    ok = degrade_mod.write_with_retry(
                        lambda: ckpt_mod.save3d(
                            p, v, g, rulestr, fingerprint=fp
                        ),
                        generation=g,
                        shed_telemetry=shed_telemetry,
                    )
                    if not ok:
                        ckpt_state["shed"] = True
                        return
                    gc_old_snapshots()

                if ckpt_writer is not None:
                    ckpt_writer.submit(write)
                else:
                    write()

        # Cooperative-preemption exit (docs/RESILIENCE.md): called at a
        # chunk boundary when SIGTERM/SIGINT arrived and work remains.
        # A final snapshot is persisted when checkpointing is configured
        # (skipped when one just landed at this boundary), the async
        # writer is fenced, and Preempted maps to exit code 75 below.
        preempt_can_save = ns.checkpoint_every > 0 or ns.auto_resume

        def preempt_exit(b, g, fp=None, just_saved=False):
            checkpointed = just_saved
            if preempt_can_save and not just_saved:
                with sw.phase("checkpoint"):
                    save_snapshot(b, g, fp)
                checkpointed = True
            if ckpt_writer is not None and checkpointed:
                with sw.phase("checkpoint"):
                    ckpt_writer.flush()
            if events is not None:
                events.preempt_event(g, checkpointed=checkpointed)
            raise resilience.Preempted(
                g,
                checkpoint_dir=ns.checkpoint_dir if checkpointed else None,
            )

        sw = Stopwatch()
        if iterations > 0:
            # GolRuntime's schedule policy: full audit/checkpoint
            # intervals plus one tail, one AOT evolver per distinct size.
            from gol_tpu.runtime import chunk_schedule

            interval = (
                ns.guard_every
                if ns.guard_every > 0
                else (
                    ns.checkpoint_every
                    if ns.checkpoint_every > 0
                    else iterations
                )
            )
            schedule = chunk_schedule(iterations, interval)
            with sw.phase("compile"):
                evolvers = rt.compile(schedule, events)
                board = placed if placed is not None else rt.place(vol)
                force_ready(board)
                checker_evolvers = None
                if ns.guard_redundant:
                    # Second bit-exact engine: an independent program a
                    # random flip cannot reproduce (guard._checker_runtime's
                    # reasoning; bitlife3d and life3d are mutually
                    # bit-exact, pinned by the 3-D equivalence tests).
                    checker = "dense" if resolved != "dense" else "bitpack"
                    if checker == "bitpack" and size % 32:
                        raise ValueError(
                            "the redundant audit needs a second bit-exact "
                            "engine, and the only check for a dense run is "
                            f"bit-packed — size {size} does not pack into "
                            "32-cell words"
                        )
                    checker_rt = VolumeRuntime(
                        size=size, rule=rule, engine=checker, mesh=mesh
                    )
                    checker_evolvers = {
                        take: (c, ())
                        for take, c in checker_rt.compile(schedule).items()
                    }
            if ns.guard_every > 0:
                from gol_tpu.utils import guard as guard_mod

                guard_report = guard_mod.GuardReport()
                with resilience.preemption_guard():
                    board, generation = guard_mod.guarded_loop(
                        sw,
                        guard_report,
                        board,
                        generation,
                        schedule,
                        {t: (c, ()) for t, c in evolvers.items()},
                        checker_evolvers,
                        guard_mod.GuardConfig(
                            check_every=ns.guard_every,
                            max_restores=ns.guard_max_restores,
                            redundant=ns.guard_redundant,
                            redundant_every=ns.guard_redundant_every,
                        ),
                        save_snapshot=save_snapshot,
                        checkpoint_every=ns.checkpoint_every,
                        events=events,
                        chunk_utilization=util3d,
                        checkpoint_overlapped=ckpt_writer is not None,
                        preempt_hook=preempt_exit,
                    )
            else:
                from gol_tpu.utils.timing import maybe_profile

                # Span attribution (schema v6), same shape as the 2-D
                # runtime loop: telemetry-off never builds the clock.
                sc = (
                    telemetry_mod.SpanClock()
                    if events is not None
                    else None
                )
                with resilience.preemption_guard(), maybe_profile(
                    ns.profile
                ), telemetry_mod.trace_annotation("gol.run.evolve", dims=3):
                    for i, take in enumerate(schedule):
                        with sw.phase("total"):
                            board, dev_stats, dispatch_s, dt = rt.chunk(
                                i, take, board
                            )
                        # Fault-plane SDC injection (board.bitflip,
                        # plane/row/col) rides gol.advance: a host-side
                        # functional cell update — the un-audited path
                        # takes the corruption silently by design.
                        board, generation = rt.advance(
                            board, generation, take
                        )
                        if events is not None:
                            # dispatch = enqueue until the async call
                            # returns; ready = the fence.  Timed once,
                            # by the runtime's spans.
                            sc.add("dispatch", dispatch_s)
                            sc.add("ready", dt - dispatch_s)
                            spans = sc.take()
                            extra3 = {}
                            if mesh is not None and resolved == "bitpack":
                                # Schema v8: the packed ring tier's
                                # exchange accounting for this chunk.
                                extra3["halo"] = _halo3d_block(
                                    ns.shard_mode, ns.halo_depth,
                                    mesh, size, take,
                                )
                            with sc.span("telemetry"):
                                events.chunk_event(
                                    i,
                                    take,
                                    generation,
                                    dt,
                                    size**3 * take,
                                    util3d(take, dt),
                                    spans=spans,
                                    **extra3,
                                )
                        if dev_stats is not None and events is not None:
                            from gol_tpu.telemetry import (
                                stats as stats_mod,
                            )

                            with sc.span("telemetry"):
                                events.stats_event(
                                    i,
                                    take,
                                    generation,
                                    stats_mod.stats_values(dev_stats),
                                )
                        if ns.checkpoint_every > 0 and not ckpt_state[
                            "shed"
                        ]:
                            with telemetry_mod.trace_annotation(
                                "gol.checkpoint.save"
                            ) as save, sw.phase("checkpoint"):
                                save_snapshot(board, generation)
                            dt = save.seconds
                            if sc is not None:
                                sc.add("checkpoint", dt)
                            if events is not None:
                                with sc.span("telemetry"):
                                    events.checkpoint_event(
                                        generation,
                                        dt,
                                        size**3,
                                        overlapped=ckpt_writer is not None,
                                    )
                        if plan_on:
                            faults_mod.crash_or_stall(generation)
                        if events is not None:
                            for frec in faults_mod.drain_fired():
                                events.fault_event(**frec)
                            for drec in degrade_mod.drain_reports():
                                events.degraded_event(**drec)
                        if i < len(schedule) - 1:
                            if sc is None:
                                preempt_now = (
                                    resilience.agreed_preempt_requested()
                                )
                            else:
                                with sc.span("preempt_poll"):
                                    preempt_now = (
                                        resilience.agreed_preempt_requested()
                                    )
                            if preempt_now:
                                # Chunk-boundary preemption poll (host-
                                # side only; the compiled programs never
                                # see it).
                                preempt_exit(
                                    board,
                                    generation,
                                    just_saved=ns.checkpoint_every > 0,
                                )
            if ckpt_writer is not None:
                # Completion fence only; main's finally owns the close.
                with sw.phase("checkpoint"):
                    ckpt_writer.flush()
            out = board
        else:
            out = placed if placed is not None else vol
        # Population via a device reduce (collective-safe on sharded
        # volumes, and no 1 GB host gather at 1024³ just for the line).
        # Per-plane uint32 counts (each < 2^32: a plane has size² cells)
        # combined in uint64 on host — a single uint32 total would wrap
        # for volumes with >= 2^32 live cells.
        import jax.numpy as jnp

        if hasattr(out, "sharding"):
            reps = None
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                reps = NamedSharding(mesh, PartitionSpec())
            plane_pops = jax.jit(
                lambda b: jnp.sum(b.astype(jnp.uint32), axis=(1, 2)),
                out_shardings=reps,
            )(out)
            population = int(
                np.asarray(plane_pops).astype(np.uint64).sum()
            )
        else:
            population = int(np.asarray(out).sum())
        report = sw.report(size**3 * iterations)
        if events is not None:
            events.summary(report)
    except resilience.Preempted as e:
        # Clean chunk-boundary stop, resumable snapshot on disk:
        # EX_TEMPFAIL (75), not the 255 error path.
        if topo.is_coordinator:
            print(e)
        return resilience.EX_TEMPFAIL
    except (ValueError, OSError) as e:
        # Same surface as the 2-D driver (gol_tpu/cli.py): bad --resume
        # paths, corrupt snapshots, unavailable engines, unwritable dirs
        # all exit cleanly with the message, not a traceback.
        print(e)
        from gol_tpu.utils.checkpoint import CorruptSnapshotError

        if isinstance(e, CorruptSnapshotError) and ns.resume:
            hint = resilience.corrupt_resume_hint(ns.resume, kind="3d")
            if hint:
                print(
                    f"hint: an earlier valid snapshot exists at {hint}; "
                    "resume from it, or rerun with --auto-resume to "
                    "select it (and fall back) automatically"
                )
        elif ns.resume and (
            "not divisible" in str(e)
            or "does not divide" in str(e)
            or "divisible by" in str(e)
        ):
            # Topology mismatch on a plain 3-D --resume: unlike the 2-D
            # driver there is no reshard path — the hint names the
            # writing topology instead (docs/RESILIENCE.md).
            hint = resilience.topology_resume_hint(ns.resume, kind="3d")
            if hint:
                print(hint)
        return 255
    finally:
        if ckpt_writer is not None:
            # Drain queued snapshot writes even when the loop raised
            # (e.g. a guard restore-budget exhaustion — the exact case
            # mid-run snapshots exist for); close() never raises.
            ckpt_writer.close()
        if events is not None:
            # The rank file keeps everything emitted before a failure —
            # telemetry exists precisely for runs that die mid-loop.
            events.close()

    if topo.is_coordinator:
        print(report.duration_line())
        if guard_report is not None:
            print(guard_report.summary_line())
        print(f"POPULATION     : {population} live cells of {size**3}")
        print("This is 3-D Life running on a TPU (capability addition).")
    if on_off == 1:
        if topo.process_count > 1:
            # Replication collective; only the coordinator writes.
            full = multihost.fetch_global(out)
            if not topo.is_coordinator:
                return 0
            out_np = full
        else:
            out_np = np.asarray(out)
        os.makedirs(ns.outdir, exist_ok=True)
        path = os.path.join(ns.outdir, "World3D_of_1.npy")
        np.save(path, out_np)
    return 0


if __name__ == "__main__":
    sys.exit(main())
