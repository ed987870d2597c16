"""One run of a job cell, as ``perf.run`` makes it, with the runtime
layer's metrics and the split of its set-up.

    python -m perf.tools.setup_split --workload ref3-16k.soup --seed 7 \
        --seconds 3 --trace 0

Prints what ``perf.run`` prints, with ``compile_s.setup`` and (traced)
``runtime_idle_share.batch`` listed for the cell, then one line
``SPLIT {...}``: ``setup_s`` cut at the spans of the program's span log
(``telemetry.span_log()``), into which this run also puts the harness's
own ``perf.*`` spans, and in a traced run device 0's idle time by span.
"""

from __future__ import annotations

import argparse
import copy
import importlib
import json
import os
import time
import types

from perf import harness, trace_reduce

os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")

#: The runtime layer's per-layer metrics, read by ``perf/metrics/``.
RUNTIME_METRICS = [
    {"name": "compile_s.setup", "unit": "s", "better": "lower",
     "source": "host_clock", "layer": "runtime", "moves": "setup_s"},
    {"name": "runtime_idle_share.batch", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "runtime", "moves": "cell_updates_per_s"},
]


def with_runtime_metrics(bench: dict, cell: str) -> dict:
    """A copy of ``bench`` that lists the runtime metrics for ``cell``."""
    bench = copy.deepcopy(bench)
    bench["per_layer"] += [dict(m, workloads=[cell]) for m in RUNTIME_METRICS]
    return bench


class SplitHarness(harness.Harness):
    """The harness, with its spans in the program's span log too and the
    time its imports took before ``jax.devices()``."""

    @staticmethod
    def span(name: str):
        from gol_tpu import telemetry

        return telemetry.trace_annotation(name)

    def devices(self):
        self.notes["imports_s"] = time.perf_counter() - harness.T_START
        return super().devices()


def split(h) -> dict:
    """``setup_s`` in parts, from the span log and the harness's notes."""
    from gol_tpu import telemetry

    log = telemetry.span_log()
    start = harness.T_START + h.setup_s
    pre = [r for r in log["records"] if r["t0"] < start]

    def first(name):
        return next((r for r in pre if r["name"] == name), {"t0": 0.0, "t1": 0.0})

    def secs(prefix):
        return sum(r["t1"] - r["t0"] for r in pre if r["name"].startswith(prefix))

    job = first("perf.job")
    ready = h.notes["jax_ready_s"]
    parts = {
        "jax_ready_s": ready,
        "imports_s": h.notes["imports_s"],
        "backend_start_s": ready - h.notes["imports_s"],
        "to_warmup_job_s": job["t0"] - harness.T_START - ready,
        "runtime_init_s": secs("gol.runtime.init"),
        "warmup_make_board_s": secs("perf.make_board"),
        "warmup_gol_init_s": secs("gol.init"),
        "compile_s": harness.reader("compile_s.setup").read(
            types.SimpleNamespace(h=h)),
        "lower_s": secs("gol.lower."),
        "warmup_evolve_s": secs("gol.run.evolve"),
        "warmup_job_s": job["t1"] - job["t0"],
        "after_warmup_job_s": start - job["t1"],
    }
    parts["warmup_job_other_s"] = parts["warmup_job_s"] - sum(
        parts[k] or 0.0 for k in ("warmup_make_board_s", "warmup_gol_init_s",
                                  "compile_s", "warmup_evolve_s"))
    return {"setup_s": h.setup_s, "dropped": log["dropped"], "parts": parts,
            "compiles": [[r["name"], r["t1"] - r["t0"], r["attrs"].get("cache"),
                          r["counts"]] for r in pre
                         if r["name"].startswith("gol.compile.")]}


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(prog="python -m perf.tools.setup_split")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--benchmark", default=None)
    p.add_argument("--rehearse", action="store_true",
                   help="run on whatever JAX finds (the tool's own tests, on the CPU)")
    args = p.parse_args(argv)
    bench = with_runtime_metrics(harness.load_benchmark(args.benchmark), args.workload)
    h = SplitHarness(bench, args.workload, args.seed, args.seconds, bool(args.trace),
                     require_accelerator=not args.rehearse)
    try:
        importlib.import_module(f"perf.drivers.{h.mix['kind']}").run(h)
        h.reduce_trace()
        out = harness.emit(h)
        res = split(h)
        res["metrics"] = out["metrics"]
        if h.reduced is not None:
            idle_by_span = harness.reader("runtime_idle_share.batch").idle_by_span
            window_s, idle = idle_by_span(trace_reduce.find_xplane(h.trace_dir))
            res["idle_by_span"] = dict(sorted(idle.items(), key=lambda kv: -kv[1]))
            res["trace_window_s"] = window_s
        print("SPLIT " + json.dumps(res), flush=True)
        return res
    finally:
        h.close()


if __name__ == "__main__":
    main()
