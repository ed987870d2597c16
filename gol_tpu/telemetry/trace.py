"""Request-scoped tracing: span trees, latency decomposition, Perfetto.

The serving tier interleaves many users' worlds in shared bucket groups,
live-reshards under device loss, and hedge-replays stragglers — so "why
was THIS request slow" is unanswerable from run-scoped telemetry alone.
This module is both sides of the answer (docs/OBSERVABILITY.md, "Request
tracing & SLOs"):

**Write side** — :class:`SpanRecorder` is the scheduler's host-side span
emitter.  One trace per request (``trace_id`` minted at admission and
carried on the journal's admit/complete records), one schema-v12
``span`` event per lifecycle phase:

- ``request`` — the root span (``span_id`` = ``"root"``), admission to
  terminal, stamped with the authoritative latency decomposition;
- ``queue`` — last-became-waiting to slot assignment (a crash-replayed
  request opens a fresh wait epoch: its pre-crash time is history, not
  queue wait);
- ``chunk`` — one per masked chunk the request rode, annotated with the
  device ``wall_s``, the ``co_resident`` count, and the chunk's
  roofline ``utilization`` (:func:`gol_tpu.utils.roofline.
  xla_flops_model` over the VPU peak);
- ``hedge`` / ``reshard`` / ``straggler`` / ``cancel`` / ``commit`` —
  event spans for the robustness plane's interventions.

All of it is host-side Python after the ``force_ready`` fences — the
trace-identity pin (tests/test_trace.py) proves tracing on/off compiles
byte-identical serve programs.

**Read side** — :func:`collect_traces` merges every rank file of every
run in a directory and regroups spans by ``trace_id`` (a crash-replayed
request's pre-crash spans live in the dead run's file; the trace_id
restored from the journal's admit record stitches them to the replay's
spans).  :func:`decompose` recomputes the five-phase latency
decomposition from the spans alone::

    queue         last-waiting -> slot assignment
    compute       this request's own share of each chunk wall (wall/co)
    interference  the co-residents' share (wall * (co-1)/co)
    hedge         straggler hedge-replay walls
    stall         everything else (scheduler overhead, guard replays,
                  reshard windows, the crash gap of a replayed request)
                  = e2e - queue - chunks - hedge, clamped at 0

The phases are disjoint wall intervals plus a residual, so they sum to
the end-to-end latency exactly (the acceptance bound is 1%; the
construction gives 0 up to rounding).  ``python -m gol_tpu.telemetry
trace <dir>`` renders the table, ``--perfetto out.json`` exports
Chrome-trace JSON (validated against the committed
``docs/schemas/perfetto_trace.schema.json``), and ``--slo`` evaluates
declarative objectives (:mod:`gol_tpu.telemetry.slo`) with burn rates.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

#: The root span's well-known id: children of the request root carry
#: ``parent_id: "root"`` even when the root itself is emitted later (at
#: the terminal transition) or by a different process (crash replay).
ROOT_SPAN_ID = "root"

#: The decomposition phases, in render order.
PHASES = ("queue_s", "compute_s", "stall_s", "interference_s", "hedge_s")


def new_trace_id(request_id: str) -> str:
    """Mint one trace id at admission.  The request id alone is not
    enough: a caller may reuse an id across server lifetimes (the result
    files are GC'd), so the id carries a random suffix — while staying
    prefixed by the request id for human greppability."""
    return f"tr-{request_id}-{os.urandom(4).hex()}"


class SpanRecorder:
    """The serve scheduler's span emitter (host-side, post-fence).

    Routes through the same :class:`~gol_tpu.telemetry.EventLog` /
    :class:`~gol_tpu.telemetry.metrics.MetricsRegistry` pair as every
    other serve emission — one stream, never two sources of truth.
    ``epoch`` prefixes the generated span ids so the spans of a
    crash-replayed request (same trace, different process) can never
    collide.  With neither sink attached the recorder is "disabled":
    nothing reaches a file or the registry — but the span still rings
    in the always-on black box (v13, docs/OBSERVABILITY.md), because a
    postmortem's open-span census must exist for every process.  (With
    an EventLog attached, its own emit() taps the ring — no double
    record.)
    """

    def __init__(self, events=None, registry=None, epoch: str = "") -> None:
        self._events = events
        self._registry = registry
        self._epoch = epoch or f"p{os.getpid()}"
        self._seq = 0
        self.enabled = events is not None or registry is not None

    def span(
        self,
        trace_id: str,
        request_id: str,
        name: str,
        start_t: float,
        end_t: float,
        parent_id: Optional[str] = ROOT_SPAN_ID,
        span_id: Optional[str] = None,
        **attrs,
    ) -> Optional[str]:
        """Emit one span; returns its id."""
        if span_id is None:
            self._seq += 1
            span_id = f"{self._epoch}#{self._seq}"
        fields = dict(
            trace_id=trace_id,
            request_id=request_id,
            span_id=span_id,
            name=name,
            start_t=round(float(start_t), 6),
            end_t=round(float(end_t), 6),
        )
        if parent_id is not None:
            fields["parent_id"] = parent_id
        if attrs:
            fields["attrs"] = attrs
        if self._events is not None:
            self._events.span_event(**fields)
            return span_id
        from gol_tpu.telemetry import blackbox

        rec = {"event": "span", "t": time.time(), **fields}
        blackbox.record(rec)
        if self._registry is not None:
            self._registry.observe(rec)
        return span_id


# -- read side ---------------------------------------------------------------


class Trace:
    """One request's reconstructed span tree (spans may come from
    multiple rank files and multiple runs — crash replay)."""

    def __init__(self, trace_id: str) -> None:
        self.trace_id = trace_id
        self.spans: List[dict] = []

    @property
    def request_id(self) -> str:
        return self.spans[0]["request_id"] if self.spans else "?"

    def root(self) -> Optional[dict]:
        for s in self.spans:
            if s["span_id"] == ROOT_SPAN_ID:
                return s
        return None

    def named(self, name: str) -> List[dict]:
        return [s for s in self.spans if s["name"] == name]

    def children(self, parent_id: str) -> List[dict]:
        return [
            s for s in self.spans if s.get("parent_id") == parent_id
        ]

    def orphans(self) -> List[dict]:
        """Spans whose parent does not resolve within the trace — a
        complete tree has none (the acceptance criterion)."""
        ids = {s["span_id"] for s in self.spans}
        return [
            s
            for s in self.spans
            if s.get("parent_id") is not None
            and s["parent_id"] not in ids
        ]


def collect_traces(runs: Dict[str, "object"]) -> Dict[str, Trace]:
    """Regroup every run's ``span`` records by ``trace_id``.

    ``runs`` is :func:`gol_tpu.telemetry.summarize.load_dir` output.
    Deliberately crosses run boundaries: a crash-replayed request's
    pre-crash spans live in the dead run's rank file, and only the
    journal-restored trace_id joins them to the replaying run's spans.
    Spans are time-ordered within each trace.
    """
    traces: Dict[str, Trace] = {}
    for run in runs.values():
        for rank in sorted(run.ranks):
            for rec in run.records("span", rank=rank):
                tr = traces.setdefault(
                    rec["trace_id"], Trace(rec["trace_id"])
                )
                tr.spans.append(rec)
    for tr in traces.values():
        tr.spans.sort(key=lambda s: (s["start_t"], s["end_t"]))
    return traces


def _dur(span: dict) -> float:
    return max(span["end_t"] - span["start_t"], 0.0)


def decompose(trace: Trace) -> Optional[dict]:
    """The five-phase latency decomposition, recomputed from spans alone
    (the root span's stamped attrs are the writer's view; recomputing
    here keeps the reader honest about what the tree actually says).
    None without a root span — the request never reached a terminal."""
    root = trace.root()
    if root is None:
        return None
    e2e = _dur(root)
    queue = sum(_dur(s) for s in trace.named("queue"))
    chunk_wall = compute = 0.0
    for s in trace.named("chunk"):
        d = _dur(s)
        co = max(int((s.get("attrs") or {}).get("co_resident", 1)), 1)
        chunk_wall += d
        compute += d / co
    hedge = sum(_dur(s) for s in trace.named("hedge"))
    attrs = root.get("attrs") or {}
    return {
        "e2e_s": round(e2e, 6),
        "queue_s": round(queue, 6),
        "compute_s": round(compute, 6),
        "interference_s": round(chunk_wall - compute, 6),
        "hedge_s": round(hedge, 6),
        "stall_s": round(max(e2e - queue - chunk_wall - hedge, 0.0), 6),
        "status": attrs.get("status", "?"),
        "chunks": len(trace.named("chunk")),
        "commit_t": root["end_t"],
    }


def _percentile(sorted_vals: List[float], q: float) -> Optional[float]:
    if not sorted_vals:
        return None
    idx = min(
        len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1)))
    )
    return sorted_vals[idx]


def decomposition_percentiles(
    decomps: List[dict], qs=(0.50, 0.99)
) -> Dict[str, dict]:
    """Per-phase percentiles over a trace set (the table footer)."""
    out: Dict[str, dict] = {}
    for phase in ("e2e_s",) + PHASES:
        vals = sorted(
            d[phase] for d in decomps if isinstance(d.get(phase), float)
            or isinstance(d.get(phase), int)
        )
        out[phase] = {
            f"p{int(q * 100)}": _percentile(vals, q) for q in qs
        }
    return out


# -- rendering ---------------------------------------------------------------


def _render_tree(trace: Trace, out) -> None:
    root = trace.root()
    printed = set()

    def walk(span: dict, depth: int) -> None:
        printed.add(id(span))
        attrs = span.get("attrs") or {}
        detail = " ".join(
            f"{k}={v}" for k, v in sorted(attrs.items())
            if not isinstance(v, (dict, list))
        )
        print(
            f"    {'  ' * depth}{span['name']:<10} "
            f"{_dur(span) * 1e3:>9.3f}ms  {detail}",
            file=out,
        )
        for child in trace.children(span["span_id"]):
            walk(child, depth + 1)

    if root is not None:
        walk(root, 0)
    for span in trace.spans:  # orphans (should not exist) still show
        if id(span) not in printed:
            print(
                f"    ORPHAN {span['name']} span {span['span_id']} "
                f"(parent {span.get('parent_id')!r} unresolved)",
                file=out,
            )


def render_traces(
    traces: Dict[str, Trace], out, request: Optional[str] = None
) -> int:
    """The decomposition table (+ full tree with ``--request``).
    Returns the number of traces rendered."""
    selected = sorted(
        (
            tr for tr in traces.values()
            if request is None or tr.request_id == request
        ),
        key=lambda tr: tr.spans[0]["start_t"] if tr.spans else 0.0,
    )
    if not selected:
        what = f"request {request!r}" if request else "any request"
        print(f"trace: no spans for {what}", file=out)
        return 0
    print(
        "  request          status    e2e_s   queue_s compute_s "
        "  stall_s interf_s  hedge_s  chunks",
        file=out,
    )
    decomps = []
    for tr in selected:
        d = decompose(tr)
        if d is None:
            print(
                f"  {tr.request_id:<16} (no root span — request never "
                "reached a terminal; crashed mid-flight or still open)",
                file=out,
            )
            continue
        decomps.append(d)
        print(
            f"  {tr.request_id:<16} {d['status']:<7} {d['e2e_s']:>8.4f} "
            f"{d['queue_s']:>9.4f} {d['compute_s']:>9.4f} "
            f"{d['stall_s']:>9.4f} {d['interference_s']:>8.4f} "
            f"{d['hedge_s']:>8.4f}  {d['chunks']:>6}",
            file=out,
        )
        orphans = tr.orphans()
        if orphans:
            print(
                f"  ANOMALY: trace {tr.trace_id} has {len(orphans)} "
                "orphan span(s) — the tree is incomplete",
                file=out,
            )
        if request is not None:
            _render_tree(tr, out)
    if len(decomps) > 1:
        pct = decomposition_percentiles(decomps)
        parts = "  ".join(
            f"{phase[:-2]} p50 {pct[phase]['p50']:.4f}s "
            f"p99 {pct[phase]['p99']:.4f}s"
            for phase in ("e2e_s", "queue_s", "stall_s")
        )
        print(f"  ({len(decomps)} committed trace(s))  {parts}", file=out)
    return len(selected)


# -- Perfetto / Chrome-trace export ------------------------------------------


def perfetto_trace(traces: Dict[str, Trace]) -> dict:
    """Chrome-trace JSON (``chrome://tracing`` / ui.perfetto.dev): one
    thread track per trace, complete (``ph: "X"``) events in
    microseconds relative to the earliest span.  The shape is pinned by
    the committed ``docs/schemas/perfetto_trace.schema.json``."""
    events: List[dict] = []
    starts = [
        s["start_t"] for tr in traces.values() for s in tr.spans
    ]
    base = min(starts) if starts else 0.0
    for tid, trace_id in enumerate(sorted(traces), start=1):
        tr = traces[trace_id]
        events.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": 1,
                "tid": tid,
                "args": {"name": f"{tr.request_id} ({trace_id})"},
            }
        )
        for s in tr.spans:
            args = {
                "trace_id": s["trace_id"],
                "request_id": s["request_id"],
                "span_id": s["span_id"],
            }
            if s.get("parent_id") is not None:
                args["parent_id"] = s["parent_id"]
            args.update(s.get("attrs") or {})
            events.append(
                {
                    "name": s["name"],
                    "cat": "serve",
                    "ph": "X",
                    "ts": round((s["start_t"] - base) * 1e6, 3),
                    "dur": round(_dur(s) * 1e6, 3),
                    "pid": 1,
                    "tid": tid,
                    "args": args,
                }
            )
    return {
        "displayTimeUnit": "ms",
        "otherData": {"schema": "gol-trace-perfetto/1"},
        "traceEvents": events,
    }


def validate_json_schema(doc, schema: dict, path: str = "$") -> List[str]:
    """A dependency-free JSON-Schema subset validator (``type``,
    ``required``, ``properties``, ``items``, ``enum``) — enough to give
    the committed export schema CI teeth without adding a package the
    container may not have.  Returns human-readable violations."""
    errors: List[str] = []
    types = {
        "object": dict,
        "array": list,
        "string": str,
        "number": (int, float),
        "integer": int,
        "boolean": bool,
        "null": type(None),
    }
    expected = schema.get("type")
    if expected is not None:
        py = types.get(expected)
        ok = isinstance(doc, py) if py is not None else True
        if expected in ("number", "integer") and isinstance(doc, bool):
            ok = False
        if not ok:
            errors.append(
                f"{path}: expected {expected}, got {type(doc).__name__}"
            )
            return errors  # children would only cascade the same error
    if "enum" in schema and doc not in schema["enum"]:
        errors.append(f"{path}: {doc!r} not in {schema['enum']}")
    if isinstance(doc, dict):
        for key in schema.get("required", ()):
            if key not in doc:
                errors.append(f"{path}: missing required key {key!r}")
        for key, sub in (schema.get("properties") or {}).items():
            if key in doc:
                errors.extend(
                    validate_json_schema(doc[key], sub, f"{path}.{key}")
                )
    if isinstance(doc, list) and "items" in schema:
        for i, item in enumerate(doc):
            errors.extend(
                validate_json_schema(
                    item, schema["items"], f"{path}[{i}]"
                )
            )
    return errors


def export_perfetto(
    traces: Dict[str, Trace], path: str, schema_path: Optional[str] = None
) -> dict:
    """Write the export; with ``schema_path``, self-validate first and
    raise :class:`~gol_tpu.telemetry.SchemaError` on any violation — an
    export that fails its own committed schema must never land."""
    from gol_tpu.telemetry import SchemaError

    doc = perfetto_trace(traces)
    if schema_path is not None:
        with open(schema_path) as f:
            schema = json.load(f)
        errors = validate_json_schema(doc, schema)
        if errors:
            raise SchemaError(
                f"perfetto export violates {schema_path}: "
                + "; ".join(errors[:5])
            )
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return doc


# -- CLI ---------------------------------------------------------------------


def main_trace(
    directory: str,
    out,
    request: Optional[str] = None,
    perfetto: Optional[str] = None,
    slo_path: Optional[str] = None,
) -> int:
    """``python -m gol_tpu.telemetry trace <dir>`` — the routed body."""
    from gol_tpu.telemetry import slo as slo_mod
    from gol_tpu.telemetry import summarize as summ_mod

    runs = summ_mod.load_dir(directory)
    traces = collect_traces(runs)
    if not traces:
        print(
            f"trace: no span events in {directory} (schema v12 — the "
            "serve scheduler emits them when telemetry is attached)",
            file=out,
        )
        return 0
    n_runs = len(runs)
    n_files = sum(len(r.ranks) for r in runs.values())
    print(
        f"trace: {len(traces)} trace(s) from {n_files} rank file(s) "
        f"across {n_runs} run(s) in {directory}",
        file=out,
    )
    render_traces(traces, out, request=request)
    decomps = [
        d
        for d in (decompose(tr) for tr in traces.values())
        if d is not None
    ]
    if decomps and request is None:
        results = slo_mod.evaluate(slo_mod.load_slos(slo_path), decomps)
        slo_mod.render(results, out)
    if perfetto:
        doc = export_perfetto(traces, perfetto)
        print(
            f"trace: wrote {len(doc['traceEvents'])} Perfetto events "
            f"to {perfetto}",
            file=out,
        )
    return 0
