"""Merge, render, and compare telemetry runs (the read side).

``summarize <dir>`` merges every ``<run_id>.rank<k>.jsonl`` file, validates
each record against the schema, prints a per-phase table and a per-chunk
table (with the roofline-utilization column), and flags anomalies:

- **chunk-time outliers** — a chunk wall time > 2× the median of its
  chunk-size class (same ``take``; the tail chunk is legitimately shorter,
  so classes never mix sizes);
- **utilization cliffs** — a chunk's roofline fraction < half the run's
  best;
- **audit divergence** — the same generation fingerprinted differently by
  different ranks (replicated audit scalars MUST agree everywhere; a
  divergence means a rank computed a different world — the exact
  multi-host SDC signature the guard exists for);
- **chunk/total drift** — per-chunk wall times not summing to the
  summary's total phase within 5%.

Schema-v2 ``stats`` events add the simulation watchdogs (shared with
``watch`` — a multi-hour run's extinction should be caught live, not at
the post-mortem):

- **extinction** — the population hits zero after having been nonzero;
- **all-static fixpoint** — a whole chunk changed no cell: Life is
  deterministic, so the world will never change again (an oscillator
  still flips cells every chunk — only a true fixpoint trips this);
- **cross-rank population disagreement** — ``stats`` values are global
  (psummed over the mesh), so two ranks reporting different populations
  for the same generation mean a rank computed a different world — the
  same SDC signature as audit-fingerprint divergence, caught from the
  stats stream alone.

``diff <dir_a> <dir_b>`` compares two runs phase-by-phase and
chunk-size-by-chunk-size.

Schema-v6 ``spans`` blocks render as a per-phase breakdown table
(dispatch / block-until-ready / checkpoint / telemetry / preempt-poll
host seconds summed over the run).

Exit codes: 0 on success (anomalies are reported, not fatal — they are
the tool's *output*), 2 on schema-invalid or unreadable input.
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
from typing import Dict, List, Optional, Tuple

from gol_tpu.telemetry import SchemaError, validate_record

_RANK_RE = re.compile(r"^(?P<run>.+)\.rank(?P<rank>\d+)\.jsonl$")


class Run:
    """All records of one run_id, keyed by rank."""

    def __init__(self, run_id: str) -> None:
        self.run_id = run_id
        self.ranks: Dict[int, List[dict]] = {}

    def records(self, event: str, rank: Optional[int] = None) -> List[dict]:
        out = []
        for r, recs in sorted(self.ranks.items()):
            if rank is not None and r != rank:
                continue
            out.extend(rec for rec in recs if rec["event"] == event)
        return out

    @property
    def header(self) -> Optional[dict]:
        heads = self.records("run_header", rank=min(self.ranks, default=None))
        return heads[0] if heads else None

    @property
    def summary_record(self) -> Optional[dict]:
        s = self.records("summary", rank=min(self.ranks, default=None))
        return s[-1] if s else None


def load_dir(directory: str) -> Dict[str, Run]:
    """Parse + schema-validate every rank file; group by run_id.

    Raises :class:`SchemaError` (exit 2 at the CLI) on any invalid line —
    a telemetry directory that fails validation is worse than no
    telemetry, because downstream analysis would silently trust it.
    """
    if not os.path.isdir(directory):
        raise SchemaError(f"{directory}: not a directory")
    runs: Dict[str, Run] = {}
    paths = sorted(glob.glob(os.path.join(directory, "*.jsonl")))
    # Black-box crash dumps share the directory and the .jsonl suffix
    # but are a different artifact with a different reader (`telemetry
    # postmortem`) — a dir that holds both must still summarize.
    paths = [
        p for p in paths
        if ".blackbox.jsonl" not in os.path.basename(p)
    ]
    if not paths:
        raise SchemaError(f"{directory}: no .jsonl telemetry files")
    for path in paths:
        m = _RANK_RE.match(os.path.basename(path))
        if not m:
            raise SchemaError(
                f"{path}: filename is not <run_id>.rank<k>.jsonl"
            )
        run_id, rank = m.group("run"), int(m.group("rank"))
        run = runs.setdefault(run_id, Run(run_id))
        recs = run.ranks.setdefault(rank, [])
        with open(path) as f:
            for lineno, line in enumerate(f, 1):
                if not line.strip():
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError as e:
                    raise SchemaError(f"{path}:{lineno}: bad JSON ({e})")
                try:
                    validate_record(rec)
                except SchemaError as e:
                    raise SchemaError(f"{path}:{lineno}: {e}")
                recs.append(rec)
    return runs


def latest_run(runs: Dict[str, Run]) -> Run:
    """The run whose header timestamp is newest (ties: run_id order)."""

    def key(run: Run):
        head = run.header
        return (head["t"] if head else 0.0, run.run_id)

    return max(runs.values(), key=key)


# -- anomaly detection -------------------------------------------------------


def find_anomalies(run: Run) -> List[str]:
    flags: List[str] = []
    rank0 = min(run.ranks, default=0)
    chunks = run.records("chunk", rank=rank0)

    # Chunk-time outliers, per chunk-size class.  Batched runs (schema
    # v4) emit one record per bucket per chunk, so the class additionally
    # keys on the bucket — a big bucket's wall is not an outlier just
    # because a small bucket shares its take.
    by_take: Dict[tuple, List[dict]] = {}
    for c in chunks:
        b = c.get("batch") or {}
        key = (c["take"], tuple(b.get("bucket", ())), b.get("B"))
        by_take.setdefault(key, []).append(c)
    for (take, _, _), cs in sorted(by_take.items()):
        if len(cs) < 3:
            continue  # no meaningful baseline
        med = statistics.median(c["wall_s"] for c in cs)
        for c in cs:
            if med > 0 and c["wall_s"] > 2.0 * med:
                flags.append(
                    f"chunk-time outlier: chunk {c['index']} "
                    f"({take} gens) took {c['wall_s']:.4f}s, "
                    f"{c['wall_s'] / med:.1f}x the {med:.4f}s median of "
                    "its size class"
                )

    # Activity engine falling back every generation: the worklist
    # capacity is too small for this workload's active set, so the run
    # pays dense compute *plus* the gating overhead (schema v5,
    # docs/SPARSE.md — raise --activity-capacity or the tile).
    acts = [c["activity"] for c in chunks if c.get("activity")]
    if acts:
        gens = sum(c["take"] for c in chunks if c.get("activity"))
        fallbacks = sum(a.get("fallback_gens", 0) for a in acts)
        if gens and fallbacks == gens:
            flags.append(
                f"activity fallback storm: all {gens} generations "
                "overflowed the worklist capacity — the gated tier is "
                "paying dense compute plus gating overhead; raise "
                "--activity-capacity or use a dense tier for this "
                "workload"
            )

    # Utilization cliffs.
    utils = [
        (c["index"], c["roofline_util"])
        for c in chunks
        if c.get("roofline_util") is not None
    ]
    if len(utils) >= 2:
        best = max(u for _, u in utils)
        for idx, u in utils:
            if best > 0 and u < 0.5 * best:
                flags.append(
                    f"utilization cliff: chunk {idx} at "
                    f"{100 * u:.3g}% roofline vs the run's best "
                    f"{100 * best:.3g}%"
                )

    # Audit fingerprint divergence across ranks.
    by_gen: Dict[int, Dict[int, int]] = {}
    for rank in sorted(run.ranks):
        for a in run.records("guard_audit", rank=rank):
            by_gen.setdefault(a["generation"], {})[rank] = a["fingerprint"]
    for gen, fps in sorted(by_gen.items()):
        if len(set(fps.values())) > 1:
            detail = ", ".join(
                f"rank{r}={fp:#010x}" for r, fp in sorted(fps.items())
            )
            flags.append(
                f"audit fingerprint divergence at generation {gen}: "
                f"{detail} — ranks disagree about the world (SDC or a "
                "broken collective)"
            )

    # Simulation watchdogs over the --stats stream (schema v2).
    flags.extend(stats_watchdogs(run))

    # Resilience watchdog (schema v3): a resume that had to fall back
    # past newer snapshots means corruption happened — worth a flag even
    # though the run recovered.
    for r in run.records("resume"):
        if r["fallback"]:
            skipped = r.get("skipped") or []
            detail = f" (skipped {', '.join(skipped)})" if skipped else ""
            flags.append(
                f"resume fallback: resumed from generation "
                f"{r['generation']} instead of the newest snapshot"
                f"{detail} — a newer candidate was corrupt/torn or "
                "another rank forced an earlier generation"
            )

    # Containment watchdog (schema v9): a degraded run finished, but
    # something was sacrificed to get there — retried checkpoint
    # writes, a shed telemetry stream, shed checkpointing.
    for r in run.records("degraded"):
        flag = (
            f"degraded: {r['resource']} {r['action']}"
            + (
                f" at generation {r['generation']}"
                if r.get("generation") is not None
                else ""
            )
            + (f" — {r['detail']}" if r.get("detail") else "")
        )
        if r.get("dropped"):
            # Schema v13 shed census: the EventLog's close() stamps how
            # many records of each type the degrade plane dropped — the
            # only after-the-fact accounting of what the stream is
            # missing (live, gol_telemetry_shed_total carries it).
            census = ", ".join(
                f"{n} {event}"
                for event, n in sorted(r["dropped"].items())
            )
            total = r.get(
                "dropped_total", sum(r["dropped"].values())
            )
            flag += (
                f" — shed {total} record(s) after degrading "
                f"({census}); the tables above undercount by exactly "
                "this census"
            )
        flags.append(flag)

    # Per-chunk walls must account for the summary's total phase.
    summ = run.summary_record
    if summ is not None and chunks:
        total = summ["phases"].get("total", summ["duration_s"])
        acc = sum(c["wall_s"] for c in chunks)
        if total > 0 and abs(acc - total) > 0.05 * total + 1e-3:
            flags.append(
                f"chunk/total drift: per-chunk walls sum to {acc:.4f}s "
                f"but the total phase is {total:.4f}s"
            )
    return flags


def stats_watchdogs(run: Run) -> List[str]:
    """Extinction / static-fixpoint / cross-rank disagreement flags.

    Shared verbatim by ``summarize`` and the live ``watch`` dashboard so
    the two tools can never disagree about what "unhealthy" means.
    """
    flags: List[str] = []
    rank0 = min(run.ranks, default=0)
    stats = run.records("stats", rank=rank0)

    seen_alive = False
    flagged_extinct = False
    for s in stats:
        if s["population"] > 0:
            seen_alive = True
        elif seen_alive and not flagged_extinct:
            flags.append(
                f"extinction: population hit 0 at generation "
                f"{s['generation']} (was alive earlier) — the run can be "
                "stopped, nothing further will happen"
            )
            flagged_extinct = True
    for s in stats:
        if s["take"] > 0 and s["changed"] == 0:
            flags.append(
                f"all-static fixpoint at generation {s['generation']}: no "
                f"cell changed across the {s['take']}-generation chunk — "
                "the world is frozen (deterministic rule: it stays frozen)"
            )
            break  # one flag; every later chunk is the same fixpoint

    # Cross-rank disagreement: stats are global (psummed), so every
    # rank must report the identical population per generation.
    by_gen: Dict[int, Dict[int, int]] = {}
    for rank in sorted(run.ranks):
        for s in run.records("stats", rank=rank):
            by_gen.setdefault(s["generation"], {})[rank] = s["population"]
    for gen, pops in sorted(by_gen.items()):
        if len(set(pops.values())) > 1:
            detail = ", ".join(
                f"rank{r}={p}" for r, p in sorted(pops.items())
            )
            flags.append(
                f"cross-rank population disagreement at generation {gen}: "
                f"{detail} — the psummed global value must be identical "
                "everywhere; a rank computed a different world (SDC or a "
                "broken collective)"
            )
    return flags


def restart_storm_flags(
    runs: Dict[str, Run],
    max_restarts: int = 3,
    window_s: float = 300.0,
) -> List[str]:
    """Directory-level watchdog: too many supervised restarts, too fast.

    Every restarted attempt is its own run (its own rank files), so the
    per-run anomaly scan cannot see a storm; this counts ``restart``
    events (schema v3 — one per restarted attempt, stamped by the child
    from ``GOL_RESTART_ATTEMPT``) across *all* runs in the directory and
    flags more than ``max_restarts`` of them inside any ``window_s``
    sliding window: the supervisor is respawning a child that keeps
    dying — a persistent fault burning the restart budget, not a
    preemption blip.  Shared by ``summarize`` and ``watch``.
    """
    times = sorted(
        rec["t"]
        for run in runs.values()
        for rank in run.ranks.values()
        for rec in rank
        if rec["event"] == "restart"
    )
    need = max_restarts + 1
    for i in range(len(times) - need + 1):
        span = times[i + need - 1] - times[i]
        if span <= window_s:
            return [
                f"restart storm: {need} restarts within {span:.0f}s "
                f"(> {max_restarts} per {window_s:.0f}s window) — the "
                "child keeps dying; check the supervisor manifest and "
                "the last attempt's stderr"
            ]
    return []


def load_manifests(directory: str) -> List[dict]:
    """Supervisor run-manifests (``*.manifest.json``) in the directory.

    The join handle between the event streams and the process tier:
    the manifest carries attempts/exit codes/resume generations keyed
    by ``run_id`` (docs/RESILIENCE.md).  Unreadable manifests are
    skipped — they come from a different writer than the schema-gated
    rank files.
    """
    out = []
    for path in sorted(glob.glob(os.path.join(directory, "*.manifest.json"))):
        try:
            with open(path) as f:
                m = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if isinstance(m, dict):
            m["_path"] = path
            out.append(m)
    return out


def render_manifest(m: dict, out) -> None:
    attempts = m.get("attempts") or []
    print(
        f"supervisor manifest {os.path.basename(m['_path'])}"
        + (f" (run {m['run_id']})" if m.get("run_id") else ""),
        file=out,
    )
    print(
        f"  attempts: {len(attempts)}  budget: {m.get('max_restarts')}  "
        f"finished: {m.get('finished')}  final_exit: {m.get('final_exit')}",
        file=out,
    )
    for a in attempts:
        rc = a.get("exit_code")
        state = (
            "running" if rc is None
            else "ok" if rc == 0
            else "preempted" if rc == 75
            else f"crashed({rc})"
        )
        gen = a.get("resume_generation")
        print(
            f"    attempt {a.get('attempt')}: {state}, resumed from "
            f"{'fresh start' if gen is None else f'generation {gen}'}",
            file=out,
        )


# -- rendering ---------------------------------------------------------------


def _fmt_rate(x: float) -> str:
    return f"{x:.3e}"


def _fmt_util(u: Optional[float]) -> str:
    if u is None:
        return "-"
    pct = 100 * u
    # Sub-0.01% fractions (CPU backends vs the TPU peak) stay legible
    # instead of rounding to a meaningless 0.00%.
    return f"{pct:6.2f}%" if pct >= 0.005 else f"{pct:.1e}%"


def render_run(run: Run, out) -> None:
    head = run.header
    print(f"run {run.run_id}", file=out)
    if head is not None:
        cfg = head.get("config", {})
        print(
            f"  ranks: {len(run.ranks)}/{head['process_count']}  "
            f"backend: {head.get('backend', '?')}  "
            f"jax: {head.get('jax_version', '?')}",
            file=out,
        )
        items = ", ".join(f"{k}={v}" for k, v in sorted(cfg.items()))
        print(f"  config: {items}", file=out)

    rank0 = min(run.ranks, default=0)
    compiles = run.records("compile", rank=rank0)
    if compiles:
        print("  compile:", file=out)
        for c in compiles:
            line = (
                f"    chunk {c['chunk']:>8} gens  lower {c['lower_s']:.3f}s"
                f"  compile {c['compile_s']:.3f}s"
            )
            # Schema v13 (docs/OBSERVABILITY.md, "Compilation as a
            # first-class observable"): the persistent-cache verdict.
            # The key is stamped only on a miss — that is when the
            # entry is written and XLA names it.
            hit = c.get("cache_hit")
            if hit is True:
                line += "  [cache hit]"
            elif hit is False:
                key = c.get("cache_key")
                line += "  [cache miss" + (
                    f" -> {key}]" if key else "]"
                )
            print(line, file=out)
        stamped = [c for c in compiles if c.get("cache_hit") is not None]
        total_s = sum(c["lower_s"] + c["compile_s"] for c in compiles)
        if stamped:
            hits = sum(1 for c in stamped if c["cache_hit"])
            print(
                f"    cache: {hits}/{len(stamped)} hit(s) "
                f"({100 * hits / len(stamped):.0f}% hit rate), "
                f"{total_s:.3f}s total lower+compile",
                file=out,
            )
        else:
            print(
                f"    cache: not attached, {total_s:.3f}s total "
                "lower+compile (set --compile-cache or "
                "JAX_COMPILATION_CACHE_DIR to stamp hit/miss)",
                file=out,
            )
        if any(c.get("memory") for c in compiles):
            # Compiled-program footprint per chunk size (schema v2): the
            # argument/output/temp/peak bytes XLA reports — the number
            # that actually caps whole-board geometry, next to the
            # durations that never showed it.
            print(
                "  memory: chunk      arg_B      out_B     temp_B"
                "     peak_B    alias_B",
                file=out,
            )
            for c in compiles:
                m = c.get("memory") or {}

                def cell(key, m=m):
                    v = m.get(key)
                    return "-" if v is None else str(v)

                print(
                    f"  {c['chunk']:>12} {cell('argument_bytes'):>10} "
                    f"{cell('output_bytes'):>10} {cell('temp_bytes'):>10} "
                    f"{cell('peak_bytes'):>10} {cell('alias_bytes'):>10}",
                    file=out,
                )

    for s in run.records("storm", rank=rank0):
        # Schema v13: the scheduler's compile-storm detector fired —
        # K cold compiles inside one admission window; admissions were
        # throttled until the window drained (docs/SERVING.md).
        print(
            f"  storm: {s['kind']} — {s['count']} cold compiles within "
            f"{s['window_s']:.0f}s (threshold {s['threshold']}); "
            "admission depth halved for the window",
            file=out,
        )

    chunks = run.records("chunk", rank=rank0)
    if chunks:
        batched = any(c.get("batch") for c in chunks)
        spans_any = any(c.get("spans") for c in chunks)
        gated = any(c.get("activity") for c in chunks)
        ringed = any(c.get("halo") for c in chunks)
        streamed = any(c.get("ooc") for c in chunks)
        print(
            "  chunk     gens       gen      wall_s     updates/s  "
            "roofline"
            + ("  batch (bucket B eng per-world/s)" if batched else "")
            + ("  activity (active% skipped fallbacks)" if gated else "")
            + ("  halo (mode k exch band)" if ringed else "")
            + ("  ooc (bands skip h2d/d2h ovl%)" if streamed else ""),
            file=out,
        )
        for c in chunks:
            line = (
                f"  {c['index']:>5} {c['take']:>8} {c['generation']:>9} "
                f"{c['wall_s']:>11.4f}  {_fmt_rate(c['updates_per_sec']):>12}"
                f"  {_fmt_util(c.get('roofline_util')):>8}"
            )
            a = c.get("activity")
            if a:
                # Schema v5 (docs/SPARSE.md): the sparse tier's skip
                # accounting — what fraction of tile-generations were
                # active, how many the worklist skipped outright, and
                # how often it overflowed to the dense fallback.
                line += (
                    f"  act {100 * a['active_fraction']:.1f}%"
                    f" skip {a['skipped_tile_gens']}/{a['tile_gens']}"
                )
                if a.get("fallback_gens"):
                    line += f" fb={a['fallback_gens']}"
            hb = c.get("halo")
            if hb:
                # Schema v8 (docs/OBSERVABILITY.md): the ring program's
                # exchange accounting — band depth/mode, exchanges this
                # chunk, and the band traffic's share of the payload.
                line += (
                    f"  {hb.get('mode', '?')} k={hb.get('depth', '?')}"
                    f" x{hb.get('exchanges', '?')}"
                    f" {hb.get('band_bytes', 0)}B"
                    f" ({100 * hb.get('exchange_share', 0.0):.1f}%)"
                )
            o = c.get("ooc")
            if o:
                # Schema v15 (docs/STREAMING.md): the out-of-core tier's
                # streaming accounting — band count, dead bands that
                # moved zero bytes, the chunk's transfer volume, and the
                # measured fraction of transfer wall hidden behind
                # in-flight compute.
                line += (
                    f"  {o.get('bands', '?')}b"
                    f" skip {o.get('skipped_bands', 0)}"
                    f" {o.get('bytes_h2d', 0)}/{o.get('bytes_d2h', 0)}B"
                    f" ovl {100 * o.get('overlap_fraction', 0.0):.0f}%"
                )
            b = c.get("batch")
            if b:
                # Schema v4 (docs/BATCHING.md): one chunk record per
                # bucket; per-world throughput is the serving metric.
                shape = "x".join(str(x) for x in b.get("bucket", []))
                pw = b.get("per_world_updates_per_sec")
                line += (
                    f"  {shape} B={b.get('B')} {b.get('engine', '?')}"
                    + (f" {_fmt_rate(pw)}/world" if pw is not None else "")
                    + (" masked" if b.get("masked") else "")
                )
            print(line, file=out)
        if spans_any:
            # Schema-v6 span attribution: per-phase host seconds summed
            # over the run's chunks — "where does the non-MFU time go"
            # from the JSONL alone.  dispatch+ready partition the fenced
            # chunk walls; the rest are boundary phases between fences.
            totals: Dict[str, float] = {}
            for c in chunks:
                for phase, secs in (c.get("spans") or {}).items():
                    totals[phase] = totals.get(phase, 0.0) + secs
            span_sum = sum(totals.values())
            wall_sum = sum(c["wall_s"] for c in chunks)
            print("  spans: phase        total_s    share", file=out)
            for phase, secs in sorted(
                totals.items(), key=lambda kv: -kv[1]
            ):
                share = 100 * secs / span_sum if span_sum > 0 else 0.0
                print(
                    f"    {phase:<14} {secs:>10.4f}  {share:>6.1f}%",
                    file=out,
                )
            print(
                f"    (chunk walls sum {wall_sum:.4f}s; spans cover "
                f"{span_sum:.4f}s of host loop time)",
                file=out,
            )

    stats = run.records("stats", rank=rank0)
    if stats:
        print(
            "  stats     gen  population     births     deaths    "
            "changed  faces(t/b/l/r)",
            file=out,
        )
        for s in stats:
            f = s.get("faces") or {}
            faces = "/".join(
                str(f[k]) for k in ("top", "bottom", "left", "right")
                if k in f
            ) or "-"
            print(
                f"  {s['generation']:>11} {s['population']:>11} "
                f"{s['births']:>10} {s['deaths']:>10} {s['changed']:>10}"
                f"  {faces}",
                file=out,
            )

    audits = run.records("guard_audit", rank=rank0)
    if audits:
        failures = sum(1 for a in audits if not a["ok"])
        print(
            f"  guard: {len(audits)} audits, {failures} failures "
            f"(population {audits[-1]['population']} at gen "
            f"{audits[-1]['generation']})",
            file=out,
        )

    ckpts = run.records("checkpoint", rank=rank0)
    if ckpts:
        fenced = sum(c["wall_s"] for c in ckpts)
        nbytes = sum(c["bytes"] for c in ckpts)
        overlapped = sum(1 for c in ckpts if c["overlapped"])
        print(
            f"  checkpoints: {len(ckpts)} ({overlapped} overlapped), "
            f"{nbytes} payload bytes, {fenced:.4f}s fenced",
            file=out,
        )

    for r in run.records("restart", rank=rank0):
        print(
            f"  restart: supervised attempt {r['attempt']}",
            file=out,
        )
    for r in run.records("resume", rank=rank0):
        print(
            f"  resume: generation {r['generation']} from {r['path']}"
            + ("  [FALLBACK]" if r["fallback"] else ""),
            file=out,
        )
    for r in run.records("preempt", rank=rank0):
        print(
            f"  preempt: stopped at generation {r['generation']} "
            f"({'checkpointed' if r['checkpointed'] else 'NO checkpoint'})",
            file=out,
        )
    for r in run.records("reshard", rank=rank0):
        src, dst = r["src_mesh"], r["dst_mesh"]

        def _mesh(m):
            return (
                m["kind"]
                if m["kind"] == "none"
                else f"{m['kind']} {m['rows']}x{m['cols']}"
            )

        print(
            f"  reshard: generation {r['generation']} "
            f"{_mesh(src)} -> {_mesh(dst)}, "
            f"{r['bytes_moved']} packed bytes moved"
            + (
                f" ({r['seam_splits']} seam splits)"
                if "seam_splits" in r
                else ""
            )
            + ("  [legacy manifest]" if r.get("legacy_manifest") else ""),
            file=out,
        )

    faults_fired = run.records("fault", rank=rank0)
    if faults_fired:
        sites: Dict[str, int] = {}
        for r in faults_fired:
            sites[r["site"]] = sites.get(r["site"], 0) + 1
        detail = ", ".join(
            f"{site}×{n}" for site, n in sorted(sites.items())
        )
        print(
            f"  faults: {len(faults_fired)} injection(s) fired "
            f"({detail}) — fault plan active (docs/RESILIENCE.md)",
            file=out,
        )

    serves = run.records("serve", rank=rank0)
    if serves:
        # Schema v10 (docs/SERVING.md): the serving tier's request
        # lifecycle — distinct ids that were committed (admit/requeue)
        # next to the per-transition counts, so an exactly-once miss
        # (completes != admitted ids) is visible from the stream alone.
        by_action: Dict[str, int] = {}
        committed = set()
        for r in serves:
            by_action[r["action"]] = by_action.get(r["action"], 0) + 1
            if r["action"] in ("admit", "requeue"):
                committed.add(r["request_id"])
        detail = ", ".join(
            f"{n} {a}" for a, n in sorted(by_action.items())
        )
        lats = [
            r["latency_s"]
            for r in serves
            if r["action"] == "complete" and r.get("latency_s") is not None
        ]
        lat = (
            f"  (median latency {statistics.median(lats):.3f}s)"
            if lats
            else ""
        )
        print(
            f"  serve: {len(committed)} request(s) committed — "
            f"{detail}{lat}",
            file=out,
        )

    spans = run.records("span", rank=rank0)
    if spans:
        # Schema v12 (docs/OBSERVABILITY.md, "Request tracing & SLOs"):
        # a one-line census pointing at the real tool — root spans are
        # terminals, so traces != roots means requests still in flight
        # (or crashed: their roots live in the replaying run's file).
        traces = {r["trace_id"] for r in spans}
        roots = sum(1 for r in spans if r["span_id"] == "root")
        print(
            f"  trace: {len(spans)} span(s) across {len(traces)} "
            f"trace(s), {roots} complete — `telemetry trace` for the "
            "decomposition",
            file=out,
        )

    healths = run.records("health", rank=rank0)
    if healths:
        # Schema v11 (docs/RESILIENCE.md, "Live elasticity"): verdict
        # counts plus the final alive-device count — a health line next
        # to a reshard line above is the live-elasticity signature.
        by_kind: Dict[str, int] = {}
        for r in healths:
            by_kind[r["verdict"]] = by_kind.get(r["verdict"], 0) + 1
        detail = ", ".join(
            f"{n} {k}" for k, n in sorted(by_kind.items())
        )
        alive = [r["alive"] for r in healths if "alive" in r]
        tail = f" (alive devices now {alive[-1]})" if alive else ""
        print(f"  health: {detail}{tail}", file=out)

    fleets = run.records("fleet", rank=rank0)
    if fleets:
        # Schema v14 (docs/SERVING.md, "The fleet"): the front tier's
        # decisions — route/handoff/epoch/replica counts plus the final
        # routing epoch, so a handoff next to a replica_dead verdict is
        # the migration signature readable from the stream alone.
        by_action: Dict[str, int] = {}
        for r in fleets:
            by_action[r["action"]] = by_action.get(r["action"], 0) + 1
        detail = ", ".join(
            f"{n} {a}" for a, n in sorted(by_action.items())
        )
        epochs = [r["epoch"] for r in fleets if "epoch" in r]
        tail = f" (routing epoch now {max(epochs)})" if epochs else ""
        print(f"  fleet: {detail}{tail}", file=out)

    benches = run.records("bench_row")
    if benches:
        for b in benches:
            print(f"  bench[{b['bench']}]: {json.dumps(b['data'])}", file=out)

    summ = run.summary_record
    if summ is not None:
        print(
            f"  total: {summ['duration_s']:.5f}s  "
            f"{summ['cell_updates']} cell updates  "
            f"{_fmt_rate(summ['updates_per_sec'])} updates/s",
            file=out,
        )
        for name, secs in sorted(summ["phases"].items()):
            print(f"    phase {name:<12} {secs:>10.4f}s", file=out)

    for flag in find_anomalies(run):
        print(f"  ANOMALY: {flag}", file=out)


def summarize(directory: str, out) -> int:
    runs = load_dir(directory)
    for run_id in sorted(runs):
        render_run(runs[run_id], out)
    for m in load_manifests(directory):
        render_manifest(m, out)
    # Directory-level: supervised restarts span runs, so the storm
    # watchdog cannot live inside the per-run anomaly scan.
    for flag in restart_storm_flags(runs):
        print(f"ANOMALY: {flag}", file=out)
    return 0


# -- diff --------------------------------------------------------------------


def _phase_table(run: Run) -> Dict[str, float]:
    summ = run.summary_record
    return dict(summ["phases"]) if summ else {}


def _chunk_medians(run: Run) -> Dict[int, Tuple[float, Optional[float]]]:
    """take -> (median wall_s, median roofline_util) on rank 0."""
    rank0 = min(run.ranks, default=0)
    by_take: Dict[int, List[dict]] = {}
    for c in run.records("chunk", rank=rank0):
        by_take.setdefault(c["take"], []).append(c)
    out = {}
    for take, cs in by_take.items():
        walls = [c["wall_s"] for c in cs]
        utils = [
            c["roofline_util"]
            for c in cs
            if c.get("roofline_util") is not None
        ]
        out[take] = (
            statistics.median(walls),
            statistics.median(utils) if utils else None,
        )
    return out


def _delta(a: float, b: float) -> str:
    if a == 0:
        return "   n/a"
    return f"{100 * (b - a) / a:+6.1f}%"


def diff(dir_a: str, dir_b: str, out) -> int:
    run_a = latest_run(load_dir(dir_a))
    run_b = latest_run(load_dir(dir_b))
    print(f"A: {dir_a} run {run_a.run_id}", file=out)
    print(f"B: {dir_b} run {run_b.run_id}", file=out)

    pa, pb = _phase_table(run_a), _phase_table(run_b)
    names = sorted(set(pa) | set(pb))
    if names:
        print("  phase            A_s         B_s    delta", file=out)
        for name in names:
            a, b = pa.get(name, 0.0), pb.get(name, 0.0)
            print(
                f"  {name:<12} {a:>10.4f}  {b:>10.4f}  {_delta(a, b)}",
                file=out,
            )

    sa, sb = run_a.summary_record, run_b.summary_record
    if sa and sb:
        a, b = sa["updates_per_sec"], sb["updates_per_sec"]
        print(
            f"  updates/s    {_fmt_rate(a):>10}  {_fmt_rate(b):>10}  "
            f"{_delta(a, b)}",
            file=out,
        )

    ca, cb = _chunk_medians(run_a), _chunk_medians(run_b)
    common = sorted(set(ca) & set(cb))
    if common:
        print(
            "  chunk_gens   A_med_wall_s  B_med_wall_s    delta  "
            "A_util  B_util",
            file=out,
        )
        for take in common:
            (wa, ua), (wb, ub) = ca[take], cb[take]
            print(
                f"  {take:>10} {wa:>13.4f} {wb:>13.4f}  {_delta(wa, wb)}"
                f"  {_fmt_util(ua):>6}  {_fmt_util(ub):>6}",
                file=out,
            )
    only = sorted(set(ca) ^ set(cb))
    if only:
        print(f"  chunk sizes present in only one run: {only}", file=out)
    return 0


def main(argv=None) -> int:
    import argparse
    import sys

    p = argparse.ArgumentParser(
        prog="gol_tpu.telemetry",
        description="Summarize or diff structured run telemetry "
        "(docs/OBSERVABILITY.md)",
    )
    sub = p.add_subparsers(dest="command", required=True)
    ps = sub.add_parser("summarize", help="merge rank files, render tables")
    ps.add_argument("directory")
    pd = sub.add_parser("diff", help="compare two telemetry runs")
    pd.add_argument("dir_a")
    pd.add_argument("dir_b")
    pt = sub.add_parser(
        "trace",
        help="rebuild per-request span trees: latency decomposition, "
        "SLO burn rates, Perfetto export (docs/OBSERVABILITY.md)",
    )
    pt.add_argument("directory")
    pt.add_argument(
        "--request", default=None, metavar="ID",
        help="render one request's full span tree instead of the table",
    )
    pt.add_argument(
        "--perfetto", default=None, metavar="FILE",
        help="export Chrome-trace/Perfetto JSON (load at "
        "ui.perfetto.dev or chrome://tracing)",
    )
    pt.add_argument(
        "--slo", default=None, metavar="FILE",
        help="declarative objectives JSON (default: the built-in "
        "commit-p99 + queue-fraction objectives)",
    )
    pp = sub.add_parser(
        "postmortem",
        help="reconstruct the last seconds before a crash from the "
        "black-box dump, cross-checked against the journal "
        "(docs/OBSERVABILITY.md)",
    )
    pp.add_argument(
        "directory",
        help="directory holding *.blackbox.jsonl (the state dir or its "
        "telemetry/ subdirectory)",
    )
    pw = sub.add_parser(
        "watch", help="live dashboard tailing a run's rank files"
    )
    pw.add_argument("directory")
    pw.add_argument("--run-id", default=None, metavar="NAME")
    pw.add_argument(
        "--interval", type=float, default=2.0, metavar="SECONDS"
    )
    pw.add_argument(
        "--once",
        action="store_true",
        help="render a single frame and exit (tests, cron probes)",
    )
    ns = p.parse_args(list(sys.argv[1:] if argv is None else argv))
    try:
        if ns.command == "summarize":
            return summarize(ns.directory, sys.stdout)
        if ns.command == "trace":
            from gol_tpu.telemetry import trace as trace_mod

            return trace_mod.main_trace(
                ns.directory,
                sys.stdout,
                request=ns.request,
                perfetto=ns.perfetto,
                slo_path=ns.slo,
            )
        if ns.command == "postmortem":
            from gol_tpu.telemetry import blackbox as blackbox_mod

            return blackbox_mod.render_postmortem(
                ns.directory, sys.stdout
            )
        if ns.command == "watch":
            from gol_tpu.telemetry import watch as watch_mod

            return watch_mod.watch(
                ns.directory,
                sys.stdout,
                run_id=ns.run_id,
                interval=ns.interval,
                frames=1 if ns.once else None,
            )
        return diff(ns.dir_a, ns.dir_b, sys.stdout)
    except (SchemaError, OSError) as e:
        print(f"telemetry: {e}", file=sys.stderr)
        return 2
