"""Seconds of the program's compile spans that started before the window
(``gol.compile.<n>``: lowering, then the XLA compile or the persistent-
cache read), from the program's own span log on the harness's clock: the
part of ``setup_s`` in which the runtime makes its programs.

The window starts at ``harness.T_START + setup_s``.  Nothing is read
where the log dropped records (a compile span may be among them), or
where no compile span started before the window.
"""
from perf import harness

PREFIX = "gol.compile."


def read(ctx):
    h = ctx.h
    if h.setup_s is None:
        return None
    from gol_tpu import telemetry

    log = telemetry.span_log()
    if log["dropped"]:
        return None
    start = harness.T_START + h.setup_s
    spans = [r for r in log["records"]
             if r["name"].startswith(PREFIX) and r["t0"] < start]
    if not spans:
        return None
    h.notes["setup_compiles"] = [
        [r["name"], r["t1"] - r["t0"], r["attrs"].get("cache")] for r in spans
    ]
    return sum(r["t1"] - r["t0"] for r in spans)
