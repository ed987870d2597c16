"""Structured run telemetry: a schema-versioned JSONL event stream.

The reference's entire observability surface is one rank-0 wall-clock pair
and a single printed line (gol-main.c:124-125); our own ``Stopwatch``/
``RunReport`` still reduces a run to a handful of phase floats while the
loops compute — and then discard — per-chunk device timings, guard audit
scalars, checkpoint latencies, and compile times.  This package keeps all
of it:

- :class:`EventLog` appends schema-versioned JSONL records to
  ``<dir>/<run_id>.rank<k>.jsonl``.  Every process writes only its own
  file, so multi-host runs never gather (the same no-gather discipline as
  the sharded checkpoint format).  Record types: ``run_header``,
  ``compile``, ``chunk``, ``guard_audit``, ``checkpoint``, ``bench_row``,
  ``summary``, and (schema v2) ``stats`` — see ``REQUIRED_FIELDS``.
  Schema v4: batched runs (:mod:`gol_tpu.batch`) stamp ``chunk`` and
  ``compile`` events with a ``batch`` block (bucket shape, B, per-world
  throughput — docs/BATCHING.md).
  ``--stats`` chunks carry in-graph simulation reductions
  (:mod:`gol_tpu.telemetry.stats`), ``compile`` events the compiled
  program's memory footprint, and ``python -m gol_tpu.telemetry watch``
  tails a live run (:mod:`gol_tpu.telemetry.watch`).
- :func:`roofline_utilization` stamps each chunk with how far the run sits
  from the VPU roofline the repo already models
  (:func:`gol_tpu.utils.roofline.xla_flops_model` per-chip FLOPs over the
  device kind's VPU peak), so utilization cliffs are visible per chunk,
  not per run; off the TPU the stamp is ``None``.
- :func:`step_annotation` / :func:`trace_annotation` wrap the host-side
  loop bodies in ``jax.profiler`` annotations so ``--profile`` traces are
  navigable (named chunks/audits/saves) instead of anonymous, and record
  each span in an always-on, bounded in-memory span log
  (:func:`span_log`) with the compile-path counters of ``jax.monitoring``.
- ``python -m gol_tpu.telemetry summarize <dir>`` merges rank files,
  renders per-phase/per-chunk tables with the roofline column, and flags
  anomalies; ``diff`` compares two runs (:mod:`gol_tpu.telemetry.
  summarize`).
- Schema v6: ``chunk`` events carry a ``spans`` block decomposing the
  host wall between force_ready fences (:class:`SpanClock`); and
  ``--metrics-port`` serves the same in-process event stream as
  Prometheus text (:mod:`gol_tpu.telemetry.metrics`).
- Schema v12: the serving tier threads a request-scoped span tree
  through every lifecycle phase (``span`` events keyed by ``trace_id``);
  ``python -m gol_tpu.telemetry trace <dir>`` rebuilds the trees, prints
  the queue/compute/stall/interference/hedge latency decomposition,
  exports Chrome-trace/Perfetto JSON (:mod:`gol_tpu.telemetry.trace`),
  and evaluates declarative SLOs with burn rates
  (:mod:`gol_tpu.telemetry.slo`) — docs/OBSERVABILITY.md, "Request
  tracing & SLOs".

Purity invariant: everything here is host-side Python running strictly
outside compiled code, after the ``force_ready`` fences — emission can
never change a traced program (pinned by the trace-identity test in
``tests/test_telemetry.py``; the static verifier's purity check would
catch any callback that leaked inside).
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time
from typing import Dict, Optional

# The always-on flight recorder (docs/OBSERVABILITY.md, "Black box &
# postmortems").  Imported eagerly so the ring tap in :meth:`EventLog.
# emit` is one attribute lookup; blackbox itself imports this package
# only lazily (inside its dump path), so there is no cycle.
from gol_tpu.telemetry import blackbox

# Version 15 (this round) gives the out-of-core streaming tier
# (``--engine ooc``, docs/STREAMING.md) its observability block: each
# ``chunk`` event of an ooc run carries an ``ooc`` block — ``bands``
# (the plan's band count), ``visits`` (band visits actually computed),
# ``skipped_bands`` (dead bands that moved zero bytes), ``bytes_h2d`` /
# ``bytes_d2h`` (the chunk's transfer volume), ``overlap_fraction``
# (measured fraction of host-side transfer wall hidden behind an
# in-flight compute — the number the streaming tier's whole design
# optimizes), plus the timing internals ``sweeps`` / ``h2d_s`` /
# ``d2h_s`` / ``hidden_s``.  Additive like every block before it:
# readers that don't know ``ooc`` ignore it, and ``summarize`` renders
# an ooc column only when some run carries the block.
# Version 14 lifts observability from one server to the
# fleet (docs/SERVING.md, "The fleet"): a ``fleet`` record marks one
# decision of the replicated front tier (:mod:`gol_tpu.serve.fleet`) —
# ``action`` is one of ``route`` (a request was pinned to a replica by
# consistent hash of its bucket key; carries ``request_id``, ``bucket``,
# ``replica``, ``epoch``), ``epoch`` (the routing epoch advanced on a
# membership change; carries ``epoch``, ``members``, ``reason``),
# ``handoff`` (a dead/unreachable replica's open intent was re-admitted
# to a surviving replica under the SAME id; carries ``request_id``,
# ``src``, ``dst``, ``epoch``), or ``replica`` (a HostMonitor verdict —
# ``verdict`` is ``replica_dead`` / ``replica_slow`` /
# ``replica_restore``, with ``replica``, ``alive``, and for slow
# verdicts ``latency_s``/``baseline_s``).  The ``gol_fleet_*`` metrics
# are fed from the same records.
# Version 13 made the process a black box and compilation
# a first-class observable (docs/OBSERVABILITY.md, "Black box &
# postmortems"): :mod:`gol_tpu.telemetry.blackbox` keeps a bounded
# in-memory ring of the last N records — every event the v12 stream
# would carry, captured even when no EventLog file sink is attached —
# and dumps it as a ``<run_id>.blackbox.jsonl`` file on unhandled
# exception, fatal signal, fault-plane ``crash.exit``, or on demand
# (serve's ``GET /debug/blackbox``); ``python -m gol_tpu.telemetry
# postmortem <dir>`` cross-checks a dump against the journal fold and
# renders a one-page verdict.  On the stream itself, v13 adds a
# ``storm`` record (the scheduler's compile-storm detector: K cold
# compiles inside one admission window — ``kind``, ``count``,
# ``window_s``, ``threshold``), stamps ``compile`` events with the
# persistent-cache outcome (optional ``cache_hit`` / ``cache_key``,
# :mod:`gol_tpu.batch.cache`), and lets a shedding EventLog leave one
# last best-effort ``degraded`` record carrying the per-event-type
# ``dropped`` census (today shed records vanish silently).
# Version 12 added the request-scoped tracing plane
# (docs/OBSERVABILITY.md, "Request tracing & SLOs"): a ``span`` record is
# one node of a request's span tree — ``trace_id`` (minted at admission,
# carried on the journal's admit/complete records so crash-replayed
# requests keep their pre-crash spans), ``request_id``, ``span_id`` /
# ``parent_id`` (the root span's id is the literal ``"root"``), ``name``
# (``request`` / ``queue`` / ``chunk`` / ``hedge`` / ``reshard`` /
# ``straggler`` / ``cancel`` / ``commit``), wall-clock ``start_t`` /
# ``end_t``, and an ``attrs`` block (chunk spans: device ``wall_s``,
# ``co_resident`` count, roofline ``utilization``; the root span: the
# queue/compute/interference/hedge/stall latency decomposition).
# ``python -m gol_tpu.telemetry trace`` rebuilds the trees
# (:mod:`gol_tpu.telemetry.trace`); :mod:`gol_tpu.telemetry.slo`
# evaluates declarative objectives over them.
# Version 11 added the health-plane event
# (docs/RESILIENCE.md, "Live elasticity"): a ``health`` record marks one
# verdict of :mod:`gol_tpu.resilience.health` — ``verdict`` is one of
# ``device_loss`` / ``device_restore`` (a device left or rejoined the
# usable set; carries ``device`` and the surviving ``alive`` count),
# ``straggler`` (a chunk wall exceeded the fitted baseline; carries
# ``rank``, ``wall_s``, ``baseline_s``), or ``hedge`` (the serving
# tier's hedged replay of a straggler's chunk; carries the ``winner``
# and whether the fingerprints agreed).  A serving run that live-
# reshards on a verdict stamps the existing v7 ``reshard`` record next
# to it — the pair on one stream is the proof the in-process elasticity
# path ran instead of a supervisor restart.
# Version 10 added the serving-tier event
# (docs/SERVING.md): a ``serve`` record marks one request-lifecycle
# transition of the continuous-batching scheduler
# (:mod:`gol_tpu.serve`) — ``action`` is one of ``admit`` (journaled,
# committed), ``start`` (placed into a batch slot), ``complete``
# (result written; carries ``latency_s``), ``reject`` (backpressure 429
# or admissions shed), ``deadline`` (cancelled at a chunk boundary), or
# ``requeue`` (re-admitted from the journal after a restart) — with the
# ``request_id`` and, where known, the ``bucket`` and live
# ``queue_depth``/``inflight`` the metrics registry gauges ride on.
# Version 9 added the fault-plane events
# (docs/RESILIENCE.md): a ``fault`` record marks one fired injection of
# the declarative fault plan (``--fault-plan`` / ``GOL_FAULT_PLAN``,
# :mod:`gol_tpu.resilience.faults`) — the site name, the generation it
# fired at (null for sites with no generation context), and the spec
# detail — and a ``degraded`` record marks a containment decision
# (:mod:`gol_tpu.resilience.degrade`): a checkpoint write that needed
# retries, a disk-full run shedding telemetry before checkpoints, or a
# telemetry stream that dropped events after a write failure instead of
# killing the run.
# Version 8 added the halo-exchange chunk block
# (docs/OBSERVABILITY.md): ``chunk`` events of a sharded ring-engine run
# carry a ``halo`` block — ``{depth, mode, exchanges, band_bytes,
# exchange_share}`` — the exchange depth/mode the chunk program actually
# compiled (``--shard-mode pipeline`` double-buffers the k-deep band
# across chunks), how many ring exchanges the chunk performed, and the
# band traffic in bytes with its share of the chunk's total payload (a
# traffic share: device-side exchange *time* is not host-observable —
# halobench owns time attribution).
# Version 7 added the elastic-mesh event
# (docs/RESILIENCE.md): a ``reshard`` record marks a run whose board was
# repartitioned across topologies — a cross-topology resume or an
# in-flight ``--reshard-at`` stop — carrying the source/destination mesh
# layouts (``{kind, rows, cols}``), the validated move-table accounting
# (``dst_shards``, ``src_pieces``, ``moves``, ``seam_splits``,
# ``cells``), and ``bytes_moved`` (pieces travel bit-packed, 32
# cells/word).  Version 6 added host-side span attribution
# (docs/OBSERVABILITY.md): ``chunk`` events carry a ``spans`` block —
# ``{phase: seconds, ...}`` with phases like ``dispatch``, ``ready``,
# ``checkpoint``, ``telemetry``, ``preempt_poll`` (the guard adds
# ``audit``/``redundant``/``snapshot``/``restore``) — decomposing the
# host wall between consecutive force_ready fences, so "where does the
# non-MFU time go" is answerable from the JSONL alone.  Version 5 added
# the activity-gated tier fields (docs/SPARSE.md): ``chunk`` events of
# an ``--engine activity`` run carry an ``activity`` block — ``{tile,
# tiles, tile_gens, active_tile_gens, computed_tile_gens,
# skipped_tile_gens, fallback_gens, active_fraction}`` — the skip
# accounting of the sparse worklist.  Version 4 added the batched
# multi-world fields (docs/BATCHING.md): ``chunk`` and ``compile``
# events may carry a ``batch`` block — ``{bucket: [H, W], B, masked,
# engine, per_world_updates_per_sec}`` — and a batch run's
# ``run_header.config`` records the bucket layout.  Version 3 added the
# resilience events — ``preempt``, ``resume``, ``restart``
# (docs/RESILIENCE.md); version 2 the ``stats`` event type and optional
# ``memory``/``cost`` blocks on ``compile`` events.  Older streams stay
# readable: every v1-v13 event type and field survives unchanged, so
# consumers only ever *gain* records (back-compat pinned by the
# committed v1..v14 fixture tests).
# Streams NEWER than this reader refuse loudly: ``validate_record``
# raises a "schema vN is newer than this reader supports" SchemaError
# (exit 2 at the CLI) instead of letting a consumer KeyError on a field
# it has never heard of.
SCHEMA_VERSION = 15
SUPPORTED_SCHEMAS = (1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15)

# Required fields per event type (beyond the envelope's "event" and "t").
# Extra fields are always allowed — the schema pins what consumers may
# rely on, not everything a producer may add.
REQUIRED_FIELDS: Dict[str, frozenset] = {
    # One per rank file, first record: who ran what, where.
    "run_header": frozenset(
        {"schema", "run_id", "process_index", "process_count", "config"}
    ),
    # One per distinct chunk size: AOT lowering + compile durations.
    # v13: optionally carries the persistent-cache outcome — ``cache_hit``
    # (bool; omitted when no cache directory is configured) and
    # ``cache_key`` (the new cache entry's key on a miss; null on a hit —
    # the key is stamped when the entry is written).
    "compile": frozenset({"chunk", "lower_s", "compile_s"}),
    # One per executed chunk (including guard replays): the device wall
    # time between force_ready fences, and the roofline fraction.
    "chunk": frozenset(
        {"index", "take", "generation", "wall_s", "updates_per_sec",
         "roofline_util"}
    ),
    # One per guard audit: the detection scalars the recovery decision
    # used (fingerprints compare across ranks and across runs).
    "guard_audit": frozenset(
        {"generation", "ok", "max_cell", "population", "fingerprint"}
    ),
    # One per snapshot: fenced (non-overlapped) seconds and payload size.
    "checkpoint": frozenset(
        {"generation", "wall_s", "bytes", "overlapped"}
    ),
    # One per bench-harness measurement row (halobench/scalebench).
    "bench_row": frozenset({"bench", "data"}),
    # v2: one per executed chunk in --stats mode — in-graph simulation
    # reductions (global values on sharded runs via psum, so every
    # rank's record must agree).  "faces" is a dict of boundary-band
    # populations ({top,bottom,left,right}; empty for 3-D volumes).
    "stats": frozenset(
        {"index", "take", "generation", "population", "births", "deaths",
         "changed", "faces"}
    ),
    # v3: cooperative preemption fired — the run stopped at a chunk
    # boundary (generation) and whether a resumable snapshot was written.
    "preempt": frozenset({"generation", "checkpointed"}),
    # v3: this run started from a snapshot.  ``fallback`` is True when a
    # newer candidate was skipped as corrupt/torn or another rank forced
    # an earlier generation (the auto-resume min agreement).
    "resume": frozenset({"generation", "path", "fallback"}),
    # v3: this run is attempt N (> 0) of a supervised job — the
    # restart-storm watchdog counts these across a directory's runs.
    "restart": frozenset({"attempt"}),
    # v7: this run's board was repartitioned across mesh topologies
    # (cross-topology resume or an in-flight --reshard-at stop).
    # src_mesh/dst_mesh are {kind, rows, cols} layout dicts; bytes_moved
    # is the bit-packed transport volume of the validated move table.
    "reshard": frozenset(
        {"generation", "src_mesh", "dst_mesh", "bytes_moved"}
    ),
    # v9: one fired injection of the declarative fault plan
    # (gol_tpu/resilience/faults.py).  ``generation`` is null for sites
    # with no generation context (e.g. a telemetry write fault).
    "fault": frozenset({"site", "generation"}),
    # v9: a containment decision fired (gol_tpu/resilience/degrade.py):
    # ``resource`` names what degraded (checkpoint / telemetry),
    # ``action`` what was done about it (retried / shed / dropped).
    "degraded": frozenset({"resource", "action"}),
    # v10: one request-lifecycle transition of the serving tier
    # (gol_tpu/serve, docs/SERVING.md): ``action`` is admit / start /
    # complete / reject / deadline / requeue; extras carry bucket,
    # queue_depth, inflight, latency_s, generation.
    "serve": frozenset({"action", "request_id"}),
    # v11: one health-plane verdict (gol_tpu/resilience/health.py):
    # ``verdict`` is device_loss / device_restore / straggler / hedge;
    # extras carry device, alive, rank, wall_s, baseline_s, winner.
    # ``generation`` is the chunk boundary that produced it.
    "health": frozenset({"verdict", "generation"}),
    # v12: one node of a request's span tree (gol_tpu/telemetry/trace.py,
    # docs/OBSERVABILITY.md "Request tracing & SLOs"): ``span_id`` /
    # optional ``parent_id`` link the tree (root id = "root"); ``name``
    # is request/queue/chunk/hedge/reshard/straggler/cancel/commit;
    # ``start_t``/``end_t`` are wall-clock; extras ride in ``attrs``.
    "span": frozenset(
        {"trace_id", "request_id", "span_id", "name", "start_t", "end_t"}
    ),
    # v13: the scheduler's compile-storm detector fired — ``count`` cold
    # compiles landed inside one ``window_s`` admission window (threshold
    # K); the admission throttle engages until the window drains
    # (docs/SERVING.md, "Compile storms").
    "storm": frozenset({"kind", "count", "window_s", "threshold"}),
    # v14: one decision of the replicated front tier
    # (gol_tpu/serve/fleet.py, docs/SERVING.md "The fleet"): ``action``
    # is route / epoch / handoff / replica (a HostMonitor verdict) /
    # drain; extras carry request_id, bucket, replica, epoch, members,
    # src, dst, verdict, alive, latency_s, baseline_s.
    "fleet": frozenset({"action"}),
    # One per run, last record: matches RunReport exactly.
    "summary": frozenset(
        {"duration_s", "cell_updates", "updates_per_sec", "phases"}
    ),
}

# Injection hook for the fault plane (gol_tpu/resilience/faults.py
# installs/clears it): called before every rank-file write, may raise
# ``OSError`` to simulate a failing telemetry disk.  ``None`` (no plan
# active) costs one attribute check per record.
_telemetry_write_hook = None


class SchemaError(ValueError):
    """A telemetry record violates the JSONL schema."""


def validate_record(rec: dict) -> None:
    """Raise :class:`SchemaError` unless ``rec`` is schema-valid.

    Shared by the writer (:meth:`EventLog.emit` — an invalid record is a
    bug at the emission site, not something to discover at read time) and
    the ``summarize`` reader (whose input may come from anywhere).
    """
    if not isinstance(rec, dict):
        raise SchemaError(f"record is {type(rec).__name__}, not an object")
    event = rec.get("event")
    if event not in REQUIRED_FIELDS:
        raise SchemaError(
            f"unknown event type {event!r}; expected one of "
            f"{sorted(REQUIRED_FIELDS)}"
        )
    if not isinstance(rec.get("t"), (int, float)):
        raise SchemaError(f"{event}: missing/non-numeric timestamp 't'")
    missing = REQUIRED_FIELDS[event] - rec.keys()
    if missing:
        raise SchemaError(f"{event}: missing fields {sorted(missing)}")
    if event == "run_header" and rec["schema"] not in SUPPORTED_SCHEMAS:
        schema = rec["schema"]
        if isinstance(schema, int) and schema > SCHEMA_VERSION:
            # A future-versioned stream: fail loudly and actionably
            # (exit 2 at the CLI), never a KeyError three consumers deep
            # on a field this reader has never heard of.
            raise SchemaError(
                f"run_header: schema v{schema} is newer than this reader "
                f"supports (max v{SCHEMA_VERSION}) — upgrade gol_tpu to "
                "read this stream"
            )
        raise SchemaError(
            f"run_header: schema {schema!r} not in supported "
            f"{SUPPORTED_SCHEMAS}"
        )


def rank_file(directory: str, run_id: str, process_index: int) -> str:
    return os.path.join(directory, f"{run_id}.rank{process_index}.jsonl")


class EventLog:
    """Per-process JSONL event writer.

    ``run_id`` defaults to a wall-clock stamp — fine for single-process
    runs; multi-host jobs should pass an explicit ``--run-id`` so every
    rank's file shares one prefix (processes start at slightly different
    times, and there is deliberately no cross-host coordination here).
    Lines are flushed per record so a killed run keeps everything emitted
    up to the failure — telemetry exists precisely for runs that die.

    ``observer`` (settable after construction) is called with every
    validated record *after* it is written — the in-process tap the live
    metrics endpoint feeds from (:mod:`gol_tpu.telemetry.metrics`); a
    :class:`~gol_tpu.telemetry.metrics.MetricsServer` assigned to
    ``metrics_server`` is shut down by :meth:`close`, so the scrape
    surface lives exactly as long as the event stream.
    """

    def __init__(
        self,
        directory: str,
        run_id: Optional[str] = None,
        process_index: Optional[int] = None,
    ) -> None:
        import jax

        self.directory = directory
        self.run_id = run_id or time.strftime("run-%Y%m%dT%H%M%S")
        self.process_index = (
            jax.process_index() if process_index is None else process_index
        )
        os.makedirs(directory, exist_ok=True)
        self.path = rank_file(directory, self.run_id, self.process_index)
        # Rerunning with an existing --run-id must not clobber (or, worse,
        # interleave with) the old stream: the previous rank file is
        # rotated to ``<path>.<n>`` — a suffix the ``summarize`` glob
        # (``*.jsonl``) deliberately does not match, so rotated history
        # stays on disk without polluting the merge.
        if os.path.exists(self.path):
            n = 1
            while os.path.exists(f"{self.path}.{n}"):
                n += 1
            os.replace(self.path, f"{self.path}.{n}")
        self._f = open(self.path, "w")
        self.observer = None
        self.metrics_server = None
        # IO containment (docs/RESILIENCE.md): a failing rank-file write
        # must never kill the run — telemetry is an observer, not a
        # participant.  After the first write failure (real ENOSPC/EIO or
        # an injected ``telemetry.write_error`` fault) the stream warns
        # once on stderr, stamps a best-effort ``degraded`` record, and
        # sheds: subsequent records are dropped from the file but still
        # reach ``observer`` (the live metrics endpoint stays truthful).
        # ``degraded`` records the shed decision for the caller/tests.
        self.degraded: Optional[dict] = None
        self._shed = False
        # Thread-safe shed request (the disk-full checkpoint policy runs
        # on the async writer thread; file writes stay on this one).
        self._pending_shed: Optional[tuple] = None
        # v13: drops are counted per event type while shedding (they
        # still reach observer/on_shed — only the file write is lost),
        # and close() leaves one last best-effort ``degraded`` record
        # carrying the census.  ``on_shed`` is the live-counter tap the
        # metrics registry attaches next to ``observer``
        # (``gol_telemetry_shed_total``).
        self.shed_counts: Dict[str, int] = {}
        self.on_shed = None

    # -- envelope -----------------------------------------------------------
    def emit(self, event: str, **fields) -> None:
        rec = {"event": event, "t": time.time(), **fields}
        validate_record(rec)
        # The black-box ring sees every validated record before the file
        # does — a crash between here and the write still leaves the
        # record recoverable from the dump (zero file IO on this tap).
        blackbox.record(rec)
        self._write_contained(rec)
        if self.observer is not None:
            self.observer(rec)

    def _write_contained(self, rec: dict) -> None:
        if self._pending_shed is not None:
            resource, reason = self._pending_shed
            self._pending_shed = None
            self._stamp_degraded(resource, "shed", reason)
        if self._shed:
            event = rec["event"]
            self.shed_counts[event] = self.shed_counts.get(event, 0) + 1
            if self.on_shed is not None:
                self.on_shed(rec)
            return
        try:
            if _telemetry_write_hook is not None:
                _telemetry_write_hook()
            self._f.write(json.dumps(rec, sort_keys=True) + "\n")
            self._f.flush()
        # ValueError covers a file handle that died under us ("I/O
        # operation on closed file") — same containment as a disk error.
        except (OSError, ValueError) as e:
            import sys

            print(
                f"gol: telemetry degraded: rank-file write failed ({e}); "
                "dropping further events (the run continues)",
                file=sys.stderr,
            )
            self._stamp_degraded("telemetry", "dropped", str(e))

    def request_shed(self, resource: str, reason: str) -> None:
        """Ask the stream to shed (stop file writes) at the next emit —
        callable from any thread; the degraded stamp and the shed itself
        happen on the emitting thread (file writes are single-threaded).
        The disk-full checkpoint policy uses this: telemetry is the
        first thing sacrificed when the disk fills."""
        if not self._shed and self._pending_shed is None:
            self._pending_shed = (resource, reason)

    def _stamp_degraded(self, resource: str, action: str, detail: str) -> None:
        """Best-effort final ``degraded`` record, then shed.  The stamp
        itself may fail (the disk that broke the stream is still broken)
        — then it survives only in :attr:`degraded` and the observer."""
        rec = {
            "event": "degraded",
            "t": time.time(),
            "resource": resource,
            "action": action,
            "detail": detail,
        }
        self.degraded = rec
        self._shed = True
        blackbox.record(rec)
        try:
            self._f.write(json.dumps(rec, sort_keys=True) + "\n")
            self._f.flush()
        except (OSError, ValueError):
            pass
        if self.observer is not None:
            self.observer(rec)

    def close(self) -> None:
        if self._shed and self.shed_counts:
            # One last best-effort stamp: how much the shed actually
            # cost, per event type.  A stream shed by *policy* (disk-full
            # checkpoint priority) still has a working telemetry disk,
            # so the census usually lands; a stream shed by a broken
            # disk loses it from the file but keeps it in the ring,
            # the observer, and :attr:`degraded`.
            rec = {
                "event": "degraded",
                "t": time.time(),
                "resource": "telemetry",
                "action": "shed_summary",
                "dropped": dict(self.shed_counts),
                "dropped_total": sum(self.shed_counts.values()),
            }
            self.degraded = rec
            blackbox.record(rec)
            try:
                self._f.write(json.dumps(rec, sort_keys=True) + "\n")
                self._f.flush()
            except (OSError, ValueError):
                pass
            if self.observer is not None:
                self.observer(rec)
        if not self._f.closed:
            self._f.close()
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None

    def __enter__(self) -> "EventLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- typed convenience emitters ----------------------------------------
    def run_header(self, config: dict, devices: bool = True) -> None:
        """``devices=False`` for a process that must not claim a device
        (the fleet front, whose replicas own the chips): the backend
        fields are then ``None`` instead of initializing one."""
        import jax

        self.emit(
            "run_header",
            schema=SCHEMA_VERSION,
            run_id=self.run_id,
            process_index=self.process_index,
            process_count=jax.process_count() if devices else 1,
            jax_version=jax.__version__,
            backend=jax.default_backend() if devices else None,
            device_count=len(jax.devices()) if devices else None,
            config=config,
        )

    def compile_event(
        self,
        chunk: int,
        lower_s: float,
        compile_s: float,
        memory: Optional[dict] = None,
        batch: Optional[dict] = None,
        cache_hit: Optional[bool] = None,
        cache_key: Optional[str] = None,
    ) -> None:
        """``memory`` (v2, optional): the compiled program's
        ``memory_analysis``/``cost_analysis`` distillation
        (:func:`gol_tpu.telemetry.stats.compiled_memory`) — peak HBM and
        argument/output/temp bytes per chunk size, the actual scaling
        limit compile *durations* never showed.  ``batch`` (v4,
        optional): the bucket this program serves (``bucket`` shape,
        ``B``, ``masked``, resolved ``engine``) — a persistent-cache hit
        shows as near-zero ``compile_s`` on the same bucket block.
        ``cache_hit``/``cache_key`` (v13, optional): the persistent
        compilation cache's verdict for this program
        (:class:`gol_tpu.batch.cache.CompileCacheProbe`) — omitted
        entirely when no cache directory is configured."""
        extra = {} if memory is None else {"memory": memory}
        if batch is not None:
            extra["batch"] = batch
        if cache_hit is not None:
            extra["cache_hit"] = cache_hit
            extra["cache_key"] = cache_key
        self.emit(
            "compile", chunk=chunk, lower_s=lower_s, compile_s=compile_s,
            **extra,
        )

    def chunk_event(
        self,
        index: int,
        take: int,
        generation: int,
        wall_s: float,
        updates: int,
        roofline_util: Optional[float],
        **extra,
    ) -> None:
        self.emit(
            "chunk",
            index=index,
            take=take,
            generation=generation,
            wall_s=wall_s,
            updates_per_sec=(updates / wall_s) if wall_s > 0 else 0.0,
            roofline_util=roofline_util,
            **extra,
        )

    def guard_event(self, audit, **extra) -> None:
        """One :class:`gol_tpu.utils.guard.Audit`'s scalars.  ``extra``
        labels batched audits (``world``/``bucket``, schema v9)."""
        self.emit(
            "guard_audit",
            generation=audit.generation,
            ok=audit.ok,
            max_cell=audit.max_cell,
            population=audit.population,
            fingerprint=audit.fingerprint,
            redundant_fingerprint=audit.redundant_fingerprint,
            **extra,
        )

    def checkpoint_event(
        self,
        generation: int,
        wall_s: float,
        nbytes: int,
        overlapped: bool,
        **extra,
    ) -> None:
        self.emit(
            "checkpoint",
            generation=generation,
            wall_s=wall_s,
            bytes=nbytes,
            overlapped=overlapped,
            **extra,
        )

    def bench_row(self, bench: str, data: dict) -> None:
        self.emit("bench_row", bench=bench, data=data)

    def preempt_event(self, generation: int, checkpointed: bool) -> None:
        """Cooperative preemption at a chunk boundary (v3; exit 75)."""
        self.emit(
            "preempt", generation=generation, checkpointed=checkpointed
        )

    def resume_event(
        self,
        generation: int,
        path: Optional[str],
        fallback: bool,
        **extra,
    ) -> None:
        """This run started from a snapshot (v3).  ``extra`` may carry
        ``skipped`` — the corrupt/torn newer candidates the validated
        walk rejected."""
        self.emit(
            "resume",
            generation=generation,
            path=path,
            fallback=fallback,
            **extra,
        )

    def restart_event(self, attempt: int, **extra) -> None:
        """Supervised restart marker (v3): this run is attempt N > 0."""
        self.emit("restart", attempt=attempt, **extra)

    def reshard_event(
        self,
        generation: int,
        src_mesh: dict,
        dst_mesh: dict,
        bytes_moved: int,
        **extra,
    ) -> None:
        """Elastic-mesh repartition marker (v7).  ``extra`` carries the
        plan accounting (``dst_shards``/``src_pieces``/``moves``/
        ``seam_splits``/``cells``), the snapshot ``path``, and
        ``legacy_manifest`` (layout was inferred, not stamped)."""
        self.emit(
            "reshard",
            generation=generation,
            src_mesh=src_mesh,
            dst_mesh=dst_mesh,
            bytes_moved=bytes_moved,
            **extra,
        )

    def fault_event(
        self, site: str, generation: Optional[int], **extra
    ) -> None:
        """One fired fault-plan injection (v9).  ``extra`` carries the
        spec detail the plane recorded (row/col/value/world/path...)."""
        self.emit("fault", site=site, generation=generation, **extra)

    def degraded_event(
        self, resource: str, action: str, **extra
    ) -> None:
        """One containment decision (v9): ``resource`` checkpoint/
        telemetry, ``action`` retried/shed/dropped; ``extra`` carries
        generation/errno/attempt detail."""
        self.emit("degraded", resource=resource, action=action, **extra)

    def serve_event(self, action: str, request_id: str, **extra) -> None:
        """One serving-tier request transition (v10): ``action`` is
        admit/start/complete/reject/deadline/requeue; ``extra`` carries
        bucket/queue_depth/inflight/latency_s/generation detail
        (docs/SERVING.md)."""
        self.emit("serve", action=action, request_id=request_id, **extra)

    def storm_event(
        self,
        kind: str,
        count: int,
        window_s: float,
        threshold: int,
        **extra,
    ) -> None:
        """The compile-storm detector fired (v13): ``count`` cold
        compiles landed inside one ``window_s`` admission window
        against a threshold of K (docs/SERVING.md, "Compile storms");
        ``extra`` carries generation/throttled detail."""
        self.emit(
            "storm",
            kind=kind,
            count=count,
            window_s=window_s,
            threshold=threshold,
            **extra,
        )

    def health_event(
        self, verdict: str, generation: int, **extra
    ) -> None:
        """One health-plane verdict (v11): ``verdict`` is device_loss/
        device_restore/straggler/hedge; ``extra`` carries device/alive/
        rank/wall_s/baseline_s/winner detail (docs/RESILIENCE.md,
        "Live elasticity")."""
        self.emit(
            "health", verdict=verdict, generation=generation, **extra
        )

    def fleet_event(self, action: str, **extra) -> None:
        """One front-tier decision (v14): ``action`` is route / epoch /
        handoff / replica / drain; ``extra`` carries request_id, bucket,
        replica, epoch, members, src, dst, verdict, alive, latency_s,
        baseline_s (docs/SERVING.md, "The fleet")."""
        self.emit("fleet", action=action, **extra)

    def span_event(
        self,
        trace_id: str,
        request_id: str,
        span_id: str,
        name: str,
        start_t: float,
        end_t: float,
        **extra,
    ) -> None:
        """One node of a request's span tree (v12): ``extra`` carries
        ``parent_id`` (absent on the root span, whose id is the literal
        ``"root"``) and the ``attrs`` block — chunk spans stamp device
        ``wall_s``/``co_resident``/``utilization``, the root span the
        latency decomposition (docs/OBSERVABILITY.md, "Request tracing
        & SLOs")."""
        self.emit(
            "span",
            trace_id=trace_id,
            request_id=request_id,
            span_id=span_id,
            name=name,
            start_t=start_t,
            end_t=end_t,
            **extra,
        )

    def stats_event(
        self, index: int, take: int, generation: int, values: dict
    ) -> None:
        """One chunk's in-graph simulation stats (v2; ``--stats`` mode).

        ``values`` maps :data:`gol_tpu.ops.stats.STATS_FIELDS` (or the
        3-D subset) to host ints; ``face_*`` entries fold into the
        ``faces`` dict.
        """
        faces = {
            k[len("face_"):]: v
            for k, v in values.items()
            if k.startswith("face_")
        }
        self.emit(
            "stats",
            index=index,
            take=take,
            generation=generation,
            population=values["population"],
            births=values["births"],
            deaths=values["deaths"],
            changed=values["changed"],
            faces=faces,
        )

    def summary(self, report) -> None:
        """The final record, mirroring :class:`~gol_tpu.utils.timing.
        RunReport` field-for-field so the JSONL stream is a superset of
        the printed report."""
        self.emit(
            "summary",
            duration_s=report.duration_s,
            cell_updates=report.cell_updates,
            updates_per_sec=report.updates_per_sec,
            phases=dict(report.phases),
        )


class SpanClock:
    """Accumulates named host-side phase seconds for ``spans`` blocks (v6).

    The chunk loops time their host phases with this: ``add`` for spans
    whose endpoints were already captured (dispatch / block-until-ready),
    ``span`` as a context manager for everything else (checkpoint save,
    telemetry write, preempt poll, guard audit...).  ``take`` drains the
    accumulator into one dict — the ``spans`` block of the next emitted
    ``chunk`` event — so the block for chunk *i* decomposes the host wall
    between the (i-1)-th and i-th ``force_ready`` fences: chunk i's own
    dispatch/ready plus the boundary phases that ran after chunk i-1's
    event was written (chunk 0 carries dispatch/ready only).  Purely
    host-side — a traced program can never see it (the trace-identity
    pin covers the spans-on path).
    """

    def __init__(self) -> None:
        self._acc: Dict[str, float] = {}

    def add(self, phase: str, seconds: float) -> None:
        self._acc[phase] = self._acc.get(phase, 0.0) + seconds

    @contextlib.contextmanager
    def span(self, phase: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(phase, time.perf_counter() - t0)

    def take(self) -> Dict[str, float]:
        out, self._acc = self._acc, {}
        return out


def roofline_utilization(
    engine: str,
    shard_cells: int,
    take: int,
    halo_depth: int,
    sharded: bool,
    wall_s: float,
) -> Optional[float]:
    """Per-chip roofline fraction of one executed chunk.

    ``xla_flops_model`` predicts one shard's compiled FLOPs for the chunk
    (lane-ops for the packed tiers); dividing by the chunk's wall seconds
    gives a per-chip op rate, and the fraction is that rate over the
    device kind's VPU peak (:func:`gol_tpu.utils.roofline.vpu_lane_ops`).
    An *estimate with the model's own ± caveats* (see the roofline module
    docstring) meant to expose utilization cliffs between chunks/configs.
    ``None`` off the TPU: a device share is never put on a CPU number.
    """
    import jax

    from gol_tpu.utils import roofline

    if wall_s <= 0 or jax.default_backend() != "tpu":
        return None
    flops = roofline.xla_flops_model(
        engine, shard_cells, take, halo_depth, sharded=sharded
    )
    return (flops / wall_s) / roofline.vpu_lane_ops()


def roofline_utilization_3d(
    engine: str, shard_cells: int, take: int, wall_s: float
) -> Optional[float]:
    """3-D counterpart for the packed volume engines (flat per-word op
    model — the tiled kernels' recompute multipliers are attribution the
    bench harnesses own; ``None`` for the dense tier, whose 26-neighbor
    FLOP count has no audited model)."""
    import jax

    from gol_tpu.utils import roofline

    if (
        wall_s <= 0
        or engine not in ("bitpack", "pallas")
        or jax.default_backend() != "tpu"
    ):
        return None
    lane_ops = (
        roofline.OPS_3D_WT_PER_WORD * (shard_cells / roofline.BITS) * take
    )
    return (lane_ops / wall_s) / roofline.vpu_lane_ops()


# -- program spans: profiler annotations and the in-memory span log --------
#
# A span opened by trace_annotation / step_annotation opens a
# jax.profiler annotation (on the profiler's host plane, on the device
# trace's clock, while a trace is live) and, when it closes, lands in
# the process-wide span log: always on, in memory, host-side only.  The
# log keeps exact per-name aggregates that never drop (count, total
# seconds, self seconds -- the duration less that of the child spans it
# encloses -- and the counters that fired while the span was innermost)
# and the newest SPAN_LOG_CAPACITY records: name, start and end on
# time.perf_counter, the enclosing open span on the same thread, attrs
# and counters.  ``dropped`` counts the records that fell off the front.
# Read it with span_log(); docs/OBSERVABILITY.md lists the spans.

#: Records the span log keeps; older ones fall off into ``dropped``.
SPAN_LOG_CAPACITY = 4096
#: jax.monitoring duration events counted against the innermost open
#: span: tracing, lowering and XLA compiles, and persistent-cache reads.
COMPILE_EVENT_PREFIXES = ("/jax/core/compile/", "/jax/compilation_cache/")
#: Fires on a persistent-cache hit, and only then...
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: ...and this one around every XLA compile, a cache read included.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_span_lock = threading.Lock()
_span_records: collections.deque = collections.deque(maxlen=SPAN_LOG_CAPACITY)
_span_totals: Dict[str, list] = {}  # name -> [count, total_s, self_s, counts]
_counters: Dict[str, list] = {}  # name -> [n, seconds]
_listening = False


class _OpenSpans(threading.local):
    def __init__(self) -> None:
        self.stack: list = []  # this thread's open spans, innermost last


_open_spans = _OpenSpans()


def _add(into: dict, name: str, n: int, seconds: float) -> None:
    c = into.get(name)
    if c is None:
        into[name] = [n, seconds]
    else:
        c[0] += n
        c[1] += seconds


class _Span:
    """One span: its profiler annotation while open, its record after.
    ``t0``/``t1``/``seconds`` are readable once it has closed, so a
    caller that needs the interval times it once, through the span."""

    __slots__ = ("name", "attrs", "counts", "parent", "thread", "t0", "t1",
                 "_child_s", "_annotation")

    def __init__(self, name: str, annotation, attrs: dict) -> None:
        self.name = name
        self.attrs = attrs
        self.counts: Optional[Dict[str, list]] = None  # made by count()
        self._child_s = 0.0
        self._annotation = annotation

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "_Span":
        if not _listening:
            _listen()
        self._annotation.__enter__()
        stack = _open_spans.stack
        self.parent = stack[-1] if stack else None
        self.thread = threading.get_ident()
        stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1 = time.perf_counter()
        _open_spans.stack.pop()
        seconds = self.t1 - self.t0
        if self.parent is not None:
            self.parent._child_s += seconds
        with _span_lock:
            _span_records.append(self)
            tot = _span_totals.get(self.name)
            if tot is None:
                tot = _span_totals[self.name] = [0, 0.0, 0.0, {}]
            tot[0] += 1
            tot[1] += seconds
            tot[2] += seconds - self._child_s
            if self.counts:
                for name, (n, s) in self.counts.items():
                    _add(tot[3], name, n, s)
        self._annotation.__exit__(exc_type, exc, tb)

    def record(self) -> dict:
        return {
            "name": self.name,
            "t0": self.t0,
            "t1": self.t1,
            "parent": None if self.parent is None else self.parent.name,
            "thread": self.thread,
            "attrs": dict(self.attrs),
            "counts": {k: list(v) for k, v in (self.counts or {}).items()},
        }


def step_annotation(name: str, step: int) -> _Span:
    """A span for one chunk: a numbered ``StepTraceAnnotation`` in xprof,
    and a log record with attr ``step``."""
    import jax

    return _Span(
        name, jax.profiler.StepTraceAnnotation(name, step_num=step),
        {"step": step},
    )


def trace_annotation(name: str, **attrs) -> _Span:
    """A named span (compile, dispatch, fence, audit, checkpoint save): a
    ``TraceAnnotation`` in xprof, and a log record carrying ``attrs``
    (the span's ``attrs`` dict may be added to while it is open)."""
    import jax

    return _Span(name, jax.profiler.TraceAnnotation(name), attrs)


def count(name: str, n: int = 1, seconds: float = 0.0) -> None:
    """Count ``n`` occurrences of ``name`` (taking ``seconds``) in the
    process-wide counters and against this thread's innermost open
    span."""
    stack = _open_spans.stack
    if stack:
        span = stack[-1]
        if span.counts is None:
            span.counts = {}
        _add(span.counts, name, n, seconds)
    with _span_lock:
        _add(_counters, name, n, seconds)


def annotate(**attrs) -> None:
    """Add ``attrs`` to this thread's innermost open span, if any: for
    what code deep inside a span decides, such as the kernel a traced
    program picked."""
    stack = _open_spans.stack
    if stack:
        stack[-1].attrs.update(attrs)


def _on_jax_duration(event: str, duration: float, **_) -> None:
    if event.startswith(COMPILE_EVENT_PREFIXES):
        count(event, 1, duration)


def _listen() -> None:
    """Register the compile-path counters once, at the first span."""
    global _listening
    with _span_lock:
        if _listening:
            return
        _listening = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def cache_outcome(counts: Optional[dict]) -> Optional[str]:
    """``"hit"`` where a span's counters show a persistent-cache read,
    ``"miss"`` where XLA compiled without one, None where neither ran."""
    counts = counts or {}
    if CACHE_READ_EVENT in counts:
        return "hit"
    if BACKEND_COMPILE_EVENT in counts:
        return "miss"
    return None


def span_log() -> dict:
    """A consistent copy of the span log: ``records`` (oldest first, as
    dicts), per-name ``totals`` ({count, total_s, self_s, counts}),
    process-wide ``counters`` ({name: [n, seconds]}), ``recorded``,
    ``dropped`` and ``capacity``."""
    with _span_lock:
        spans = list(_span_records)
        recorded = sum(tot[0] for tot in _span_totals.values())
        totals = {
            name: {
                "count": n, "total_s": total, "self_s": own,
                "counts": {k: list(v) for k, v in counts.items()},
            }
            for name, (n, total, own, counts) in _span_totals.items()
        }
        counters = {k: list(v) for k, v in _counters.items()}
    return {
        "capacity": SPAN_LOG_CAPACITY,
        "recorded": recorded,
        "dropped": recorded - len(spans),
        "records": [s.record() for s in spans],
        "totals": totals,
        "counters": counters,
    }
