"""Share of the window in which device 0 idles while the innermost open
host span is one of the program's own (``gol.*``): the runtime's part of
``device_idle_share.batch`` (profiler trace).

Device 0's idle time inside the window is cut at every start and end of
a harness or program span (``perf.*``, ``gol.*``) on the host, and each
piece is credited to the innermost span open over it: a gap that opens
in the runtime's fence and closes in the harness's next job counts for
each in part.  Every gap of the run's xplane counts, not only the ten
that the breakdown names.
"""
import bisect

from perf import trace_reduce as tr

PROGRAM = "gol."


def credit(gaps, spans) -> dict:
    """``{span name: seconds}`` of ``gaps`` (sorted, disjoint), each cut
    at the span boundaries inside it and credited piece by piece to the
    innermost of ``spans`` (``(start, end, name)``) open over the piece."""
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    pieces = []
    for s, e in gaps:
        inner = cuts[bisect.bisect_right(cuts, s):bisect.bisect_left(cuts, e)]
        points = [s, *inner, e]
        pieces += zip(points, points[1:])
    out = {}
    for (s, e), name in zip(pieces, tr.spans_at(spans, [(s + e) / 2 for s, e in pieces])):
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def idle_by_span(path: str):
    """``(window_s, {span name: seconds})``: device 0's idle time inside
    the window, as :func:`credit` shares it out; device 0 and its busy
    time as ``trace_reduce.reduce_trace`` takes them."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    spans = [(ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
             for p in planes if p.name == tr.HOST_PLANE
             for _, ev in tr._events(p) if ev.name.startswith(tr.SPAN_PREFIXES)]
    window = [(s, e) for s, e, name in spans if name == tr.WINDOW_SPAN]
    if not window:
        raise ValueError(f"no {tr.WINDOW_SPAN!r} span in {path}")
    lo, hi = window[0]
    for p in sorted((p for p in planes if p.name.startswith(tr.DEVICE_PREFIX)),
                    key=lambda p: p.name):
        busy = tr.union(
            (max(ev.start_ns * 1e-9, lo), min(ev.end_ns * 1e-9, hi))
            for _, ev in tr._events(p, tr.OPS_LINE)
            if ev.end_ns * 1e-9 > lo and ev.start_ns * 1e-9 < hi
            and not tr.is_control(ev.name))
        if busy:
            return hi - lo, credit(tr.subtract([(lo, hi)], busy), spans)
    raise ValueError(f"no device ops inside the window in {path}")


def read(ctx):
    if ctx.reduced is None:  # no trace read: --trace 0, or off the TPU
        return None
    window_s, idle = idle_by_span(tr.find_xplane(ctx.h.trace_dir))
    return 100.0 * sum(s for name, s in idle.items()
                       if name.startswith(PROGRAM)) / window_s
