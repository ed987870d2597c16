"""The in-memory span log behind ``telemetry.trace_annotation`` /
``step_annotation``, and the spans ``GolRuntime.run`` records in it.

What they pin:

- a span's record: name, start and end on ``time.perf_counter``, the
  enclosing open span on the same thread as parent, attrs; per-name
  count, total and self seconds;
- one stack of open spans per thread;
- the bounded record deque: a ``dropped`` count once it overflows, while
  the per-name aggregates stay exact;
- counters, by ``count`` and from ``jax.monitoring``'s compile-path
  events, against the innermost open span, and the cache verdict a
  compile span carries;
- one ``run`` records gol.runtime.init, gol.init, gol.compile.<n> over
  gol.lower.<n>, gol.chunk over gol.dispatch and gol.fence, and
  gol.advance; a second ``run`` lowers its program again (a count);
- the spans block of telemetry chunk events is fed by the same timings;
- the traced program is byte-identical inside and outside spans.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gol_tpu import telemetry
from gol_tpu.models.state import Geometry
from gol_tpu.runtime import GolRuntime

_ids = itertools.count()


def _prefix(tag: str) -> str:
    """Span names no other test of the process uses."""
    return f"test.{tag}.{next(_ids)}"


def _records(prefix: str) -> list:
    return [r for r in telemetry.span_log()["records"]
            if r["name"].startswith(prefix)]


# -- the log -----------------------------------------------------------------


def test_nested_spans_record_parents_and_self_time():
    p = _prefix("nest")
    with telemetry.trace_annotation(p + ".outer", job=7) as outer:
        with telemetry.trace_annotation(p + ".a") as a:
            time.sleep(0.01)
        with telemetry.trace_annotation(p + ".b") as b:
            time.sleep(0.01)
        time.sleep(0.005)
    recs = sorted(_records(p), key=lambda r: r["t0"])
    assert [(r["name"], r["parent"]) for r in recs] == [
        (p + ".outer", None), (p + ".a", p + ".outer"), (p + ".b", p + ".outer")]
    assert recs[0]["attrs"] == {"job": 7} and recs[1]["attrs"] == {}
    # Children lie inside their parent, in order, on the same clock.
    assert outer.t0 <= a.t0 < a.t1 <= b.t0 < b.t1 <= outer.t1
    assert [(r["t0"], r["t1"]) for r in recs] == [
        (outer.t0, outer.t1), (a.t0, a.t1), (b.t0, b.t1)]
    totals = telemetry.span_log()["totals"]
    assert totals[p + ".outer"]["count"] == 1
    assert totals[p + ".outer"]["total_s"] == pytest.approx(outer.seconds)
    assert totals[p + ".outer"]["self_s"] == pytest.approx(
        outer.seconds - a.seconds - b.seconds)
    assert 0.004 < totals[p + ".outer"]["self_s"] < outer.seconds
    assert totals[p + ".a"]["self_s"] == pytest.approx(a.seconds)


def test_step_annotation_is_a_span_with_its_step():
    p = _prefix("step")
    for i in range(3):
        with telemetry.step_annotation(p, i):
            pass
    assert [r["attrs"] for r in _records(p)] == [{"step": 0}, {"step": 1}, {"step": 2}]
    assert telemetry.span_log()["totals"][p]["count"] == 3


def test_attrs_added_while_open_are_recorded():
    p = _prefix("attrs")
    with telemetry.trace_annotation(p) as span:
        span.attrs["cache"] = "hit"
    assert _records(p)[0]["attrs"] == {"cache": "hit"}


def test_each_thread_keeps_its_own_stack():
    """Two threads with spans open at once: each inner span's parent is
    its own thread's outer span, never the other thread's."""
    p = _prefix("threads")
    both_open = threading.Barrier(2)

    def work(tag):
        with telemetry.trace_annotation(f"{p}.{tag}.outer"):
            both_open.wait()
            with telemetry.trace_annotation(f"{p}.{tag}.inner"):
                both_open.wait()

    threads = [threading.Thread(target=work, args=(t,)) for t in ("x", "y")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = {r["name"]: r for r in _records(p)}
    assert len(recs) == 4
    for tag in ("x", "y"):
        inner, outer = recs[f"{p}.{tag}.inner"], recs[f"{p}.{tag}.outer"]
        assert inner["parent"] == f"{p}.{tag}.outer"
        assert outer["parent"] is None
        assert inner["thread"] == outer["thread"]
    assert recs[f"{p}.x.outer"]["thread"] != recs[f"{p}.y.outer"]["thread"]
    # The spans of the two threads overlap in time.
    assert recs[f"{p}.x.inner"]["t0"] < recs[f"{p}.y.outer"]["t1"]
    assert recs[f"{p}.y.inner"]["t0"] < recs[f"{p}.x.outer"]["t1"]


def test_threads_hammering_the_log_lose_no_update():
    """More threads than cores, switching as often as the interpreter
    lets them: every span and every count lands in the aggregates."""
    p, c = _prefix("stress"), _prefix("stress-counter")
    workers, per = 2 * (os.cpu_count() or 4), 200
    parents = []

    def work():
        for _ in range(per):
            with telemetry.trace_annotation(p) as outer:
                with telemetry.trace_annotation(p + ".inner") as inner:
                    telemetry.count(c)
                parents.append(inner.parent is outer)

    threads = [threading.Thread(target=work) for _ in range(workers)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert len(parents) == workers * per and all(parents)
    log = telemetry.span_log()
    totals = log["totals"]
    assert totals[p]["count"] == totals[p + ".inner"]["count"] == workers * per
    assert log["counters"][c][0] == workers * per
    assert log["totals"][p + ".inner"]["counts"][c][0] == workers * per


def test_bounded_records_count_drops_and_aggregates_stay_exact():
    p = _prefix("bounded")
    cap = telemetry.SPAN_LOG_CAPACITY
    before = telemetry.span_log()["recorded"]
    n = cap + 100
    for _ in range(n):
        with telemetry.trace_annotation(p):
            pass
    log = telemetry.span_log()
    assert log["capacity"] == cap == len(log["records"])
    assert log["recorded"] - before == n
    assert log["dropped"] == log["recorded"] - cap >= 100
    # The newest records are the ones kept; the aggregate saw every span.
    assert all(r["name"] == p for r in log["records"])
    assert log["totals"][p]["count"] == n
    assert log["totals"][p]["total_s"] == pytest.approx(
        log["totals"][p]["self_s"])


def test_counters_count_process_wide_and_against_the_innermost_span():
    p, c = _prefix("count"), _prefix("counter")
    telemetry.count(c, 2, 0.5)  # no span open: process-wide only
    with telemetry.trace_annotation(p) as outer:
        telemetry.count(c)
        with telemetry.trace_annotation(p + ".child") as child:
            telemetry.count(c, seconds=0.25)
    log = telemetry.span_log()
    assert log["counters"][c] == [4, 0.75]
    assert outer.counts == {c: [1, 0.0]}
    assert child.counts == {c: [1, 0.25]}
    recs = {r["name"]: r for r in _records(p)}
    assert recs[p]["counts"] == {c: [1, 0.0]}
    assert recs[p + ".child"]["counts"] == {c: [1, 0.25]}
    assert log["totals"][p]["counts"] == {c: [1, 0.0]}


def test_compile_path_events_count_against_the_open_span():
    """jax.monitoring's duration events under /jax/core/compile/ and
    /jax/compilation_cache/ are counters of the innermost open span; a
    persistent-cache read makes its compile a hit."""
    p = _prefix("monitoring")
    with telemetry.trace_annotation(p) as span:
        jax.monitoring.record_event_duration_secs(telemetry.CACHE_READ_EVENT, 0.01)
        jax.monitoring.record_event_duration_secs(
            telemetry.BACKEND_COMPILE_EVENT, 0.02)
        jax.monitoring.record_event_duration_secs("/jax/elsewhere/x", 1.0)
    assert span.counts == {
        telemetry.CACHE_READ_EVENT: [1, 0.01],
        telemetry.BACKEND_COMPILE_EVENT: [1, 0.02],
    }
    assert telemetry.cache_outcome(span.counts) == "hit"
    assert telemetry.cache_outcome(
        {telemetry.BACKEND_COMPILE_EVENT: [1, 0.1]}) == "miss"
    assert telemetry.cache_outcome({}) is None


# -- the runtime's spans -----------------------------------------------------

#: (name, parent) of one run's spans, in the order they start.
RUN_SPANS = [
    ("gol.init", None),
    ("gol.compile.8", None),
    ("gol.lower.8", "gol.compile.8"),
    ("gol.run.evolve", None),
    ("gol.chunk", "gol.run.evolve"),
    ("gol.dispatch", "gol.chunk"),
    ("gol.fence", "gol.chunk"),
    ("gol.advance", "gol.run.evolve"),
]


def _since(t0: float) -> list:
    me = threading.get_ident()
    return sorted(
        (r for r in telemetry.span_log()["records"]
         if r["t0"] >= t0 and r["thread"] == me),
        key=lambda r: r["t0"],
    )


def test_runtime_run_records_its_spans_with_their_parents():
    t0 = time.perf_counter()
    rt = GolRuntime(geometry=Geometry(size=64, num_ranks=1))
    rt.run(pattern=4, iterations=8)
    recs = _since(t0)
    assert [(r["name"], r["parent"]) for r in recs] == [
        ("gol.runtime.init", None)] + RUN_SPANS
    by = {r["name"]: r for r in recs}
    for name, parent in RUN_SPANS:
        if parent is not None:
            outer, inner = by[parent], by[name]
            assert outer["t0"] <= inner["t0"] <= inner["t1"] <= outer["t1"]
    assert by["gol.chunk"]["attrs"] == {"step": 0}
    # The compile span saw the program's lowering (a jaxpr trace) in its
    # child, and gives a cache verdict only where XLA compiled.
    assert any(k.startswith("/jax/core/compile/") for k in by["gol.lower.8"]["counts"])
    assert by["gol.compile.8"]["attrs"].get("cache") in (None, "miss")


def test_second_run_lowers_its_program_again():
    """Today each run lowers its chunk program anew: one gol.lower.<n>
    per run, and a jaxpr trace under it each time."""
    rt = GolRuntime(geometry=Geometry(size=64, num_ranks=1))
    rt.run(pattern=4, iterations=5)
    before = telemetry.span_log()["totals"]["gol.lower.5"]
    t0 = time.perf_counter()
    rt.run(pattern=4, iterations=5)
    after = telemetry.span_log()["totals"]["gol.lower.5"]
    assert after["count"] - before["count"] == 1
    assert [(r["name"], r["parent"]) for r in _since(t0)] == [
        (n.replace(".8", ".5"), p and p.replace(".8", ".5")) for n, p in RUN_SPANS]
    trace = "/jax/core/compile/jaxpr_trace_duration"
    assert after["counts"][trace][0] - before["counts"].get(trace, [0])[0] >= 1


def test_spans_block_is_fed_by_the_span_timings(tmp_path):
    """dispatch and ready in a chunk event's spans block are the
    gol.dispatch span's seconds and the rest of the fenced chunk wall:
    one timing, two sinks."""
    t0 = time.perf_counter()
    rt = GolRuntime(geometry=Geometry(size=64, num_ranks=1), checkpoint_every=4,
                    checkpoint_dir=str(tmp_path / "ck"),
                    telemetry_dir=str(tmp_path / "t"), run_id="fed")
    rt.run(pattern=4, iterations=8)
    dispatch = [r for r in _since(t0) if r["name"] == "gol.dispatch"]
    fences = [r for r in _since(t0) if r["name"] == "gol.fence"]
    chunks = [json.loads(ln) for ln in open(tmp_path / "t" / "fed.rank0.jsonl")]
    chunks = [c for c in chunks if c["event"] == "chunk"]
    assert len(chunks) == len(dispatch) == len(fences) == 2
    for c, d, f in zip(chunks, dispatch, fences):
        assert c["spans"]["dispatch"] == d["t1"] - d["t0"]
        assert c["wall_s"] == f["t1"] - d["t0"]
        spans = c["spans"]
        assert spans["dispatch"] + spans["ready"] == pytest.approx(c["wall_s"])


def test_telemetry_off_run_never_probes_the_compile_cache(monkeypatch):
    from gol_tpu.batch import cache as cache_mod

    class Refuse:
        def __init__(self):
            raise AssertionError("a telemetry-off run listed the cache")

    monkeypatch.setattr(cache_mod, "CompileCacheProbe", Refuse)
    rt = GolRuntime(geometry=Geometry(size=64, num_ranks=1))
    _, state = rt.run(pattern=4, iterations=3)
    assert int(state.generation) == 3


@pytest.mark.parametrize("engine", ["dense", "bitpack"])
def test_span_log_never_changes_the_traced_program(engine):
    """The job program's jaxpr and HLO are byte-identical lowered inside
    the runtime's spans and outside any span, and the program
    compile_evolvers builds under them is the one compiled bare."""
    from gol_tpu.analysis import walker

    rt = GolRuntime(geometry=Geometry(size=64, num_ranks=1), engine=engine)
    spec = jax.ShapeDtypeStruct((64, 64), np.uint8)
    fn, dynamic, static = rt._evolve_fn(4)
    bare = (str(walker.trace_jaxpr(fn, spec, *dynamic, *static)),
            fn.lower(spec, *dynamic, *static).as_text())
    with telemetry.trace_annotation("gol.compile.4"):
        with telemetry.trace_annotation("gol.lower.4"):
            spanned = (str(walker.trace_jaxpr(fn, spec, *dynamic, *static)),
                       fn.lower(spec, *dynamic, *static).as_text())
    assert spanned == bare
    compiled, _ = rt.compile_evolvers(jnp.zeros((64, 64), jnp.uint8), [4])[4]
    assert compiled.as_text() == fn.lower(spec, *dynamic, *static).compile().as_text()
