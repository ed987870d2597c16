"""The runtime layer's readers: ``compile_s.setup`` on a synthetic span
log and through a traced run of a tiny cell on the CPU by
``perf.tools.setup_split``, and ``runtime_idle_share.batch`` on the
traces recorded on the chip."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from perf import harness
from perf import trace_reduce as tr
from perf.tests.cells import added_cells

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACES = ("soup_1chip.xplane.pb", "ring4_4chip.xplane.pb")


def _span(name, t0, t1, cache=None):
    return {"name": name, "t0": t0, "t1": t1, "parent": None, "thread": 1,
            "attrs": {} if cache is None else {"cache": cache}, "counts": {}}


def _compile_s(monkeypatch, records, setup_s, dropped=0):
    from gol_tpu import telemetry

    log = {"records": records, "dropped": dropped, "recorded": len(records) + dropped,
           "capacity": 4096, "totals": {}, "counters": {}}
    monkeypatch.setattr(telemetry, "span_log", lambda: log)
    h = types.SimpleNamespace(setup_s=setup_s, notes={})
    return harness.reader("compile_s.setup").read(types.SimpleNamespace(h=h)), h


def test_compile_s_setup_splits_at_the_window_start(monkeypatch):
    t = harness.T_START
    records = [
        _span("gol.runtime.init", t + 1.0, t + 1.5),
        _span("gol.lower.16", t + 2.0, t + 2.5),      # inside the compile span
        _span("gol.compile.16", t + 2.0, t + 4.0, "miss"),
        _span("gol.compile.8", t + 4.5, t + 5.0, "hit"),
        _span("gol.compile.16", t + 10.5, t + 10.75),  # in the window
    ]
    value, h = _compile_s(monkeypatch, records, setup_s=10.0)
    assert value == pytest.approx(2.5)
    assert h.notes["setup_compiles"] == [
        ["gol.compile.16", pytest.approx(2.0), "miss"],
        ["gol.compile.8", pytest.approx(0.5), "hit"]]
    # A later window start takes the window's compile in too.
    assert _compile_s(monkeypatch, records, setup_s=11.0)[0] == pytest.approx(2.75)


def test_compile_s_setup_reads_nothing_after_a_drop(monkeypatch):
    t = harness.T_START
    records = [_span("gol.compile.16", t + 2.0, t + 4.0)]
    assert _compile_s(monkeypatch, records, setup_s=10.0, dropped=1)[0] is None


def test_compile_s_setup_reads_nothing_without_a_compile(monkeypatch):
    t = harness.T_START
    no_compile = [_span("gol.init", t, t + 1)]
    assert _compile_s(monkeypatch, no_compile, setup_s=10.0)[0] is None
    assert _compile_s(monkeypatch, [], setup_s=None)[0] is None


def test_setup_split_of_a_traced_run(tmp_path):
    """A tiny cell run by ``perf.tools.setup_split`` on the CPU, in a
    process of its own as every run is: ``compile_s.setup`` is the warm-up
    job's one compile, and the parts of set-up add up to ``setup_s``."""
    with added_cells(tmp_path) as path:
        p = subprocess.run(
            [sys.executable, "-m", "perf.tools.setup_split", "--workload", "tiny.soup",
             "--seed", "2900000011", "--seconds", "0.05", "--trace", "1",
             "--benchmark", str(path), "--rehearse"],
            cwd=harness.ROOT, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-2])
    assert out["correct"] is True
    assert out["metrics"]["compile_s.setup"]["unit"] == "s"
    value = out["metrics"]["compile_s.setup"]["value"]
    note = [ln for ln in lines if "setup_compiles:" in ln][0]
    note = json.loads(note.split(": ", 1)[1])
    assert [name for name, _, _ in note] == ["gol.compile.16"]
    assert 0 < value == pytest.approx(note[0][1])
    split = json.loads(lines[-1].split(" ", 1)[1])
    parts = split["parts"]
    assert split["dropped"] == 0 and parts["compile_s"] == pytest.approx(value)
    assert [c[0] for c in split["compiles"]] == ["gol.compile.16"]
    assert 0 < parts["lower_s"] < parts["compile_s"]
    assert 0 < parts["imports_s"] < parts["jax_ready_s"]
    assert parts["runtime_init_s"] > 0 and parts["warmup_evolve_s"] > 0
    assert parts["warmup_job_other_s"] >= 0
    whole = sum(parts[k] for k in ("jax_ready_s", "to_warmup_job_s", "warmup_job_s",
                                   "after_warmup_job_s"))
    assert whole == pytest.approx(split["setup_s"], rel=1e-9)
    assert "runtime_idle_share.batch" not in out["metrics"]  # no trace read on the CPU


def test_credit_cuts_gaps_at_span_boundaries():
    """A gap is shared among the spans open over its parts, not given
    whole to the span open at its middle."""
    credit = harness.reader("runtime_idle_share.batch").credit
    spans = [(0, 20, "perf.job"), (1, 4, "gol.fence"), (4, 5, "gol.advance"),
             (30, 40, "perf.job")]
    assert credit([(2, 10), (19, 31)], spans) == {
        "gol.fence": 2, "gol.advance": 1, "perf.job": 5 + 1 + 1, "(no span)": 10}
    assert credit([], spans) == {}


def _full(name):
    return harness.reader("runtime_idle_share.batch").idle_by_span(
        os.path.join(DATA, name))


@pytest.mark.parametrize("name", TRACES)
def test_full_gap_attribution_fills_device_0_idle(name):
    """The pieces of every span, the harness's included, add up to device
    0's idle time in the reduction's window."""
    r = tr.reduce_trace(os.path.join(DATA, name))
    window_s, idle = _full(name)
    assert window_s == pytest.approx(r["window_s"], rel=1e-12)
    first = r["devices"][sorted(r["devices"])[0]]
    assert sum(idle.values()) == pytest.approx(r["window_s"] - first["busy_s"], rel=1e-9)
    assert set(dict(r["idle_gaps"])) <= set(idle)
    assert all(s > 0 for s in idle.values())


@pytest.mark.parametrize("name", TRACES)
def test_runtime_idle_share_is_the_program_spans_part_of_device_idle(name, tmp_path):
    path = os.path.join(DATA, name)
    r = tr.reduce_trace(path)
    window_s, idle = _full(name)
    want = 100.0 * sum(s for n, s in idle.items() if n.startswith("gol.")) / window_s
    # Read as a run reads it, from the xplane in its trace directory.
    trace_dir = tmp_path / "trace" / "plugins" / "profile" / "run"
    trace_dir.mkdir(parents=True)
    shutil.copy(path, trace_dir / name)
    ctx = types.SimpleNamespace(h=types.SimpleNamespace(trace_dir=str(tmp_path / "trace")),
                                reduced=r)
    value = harness.reader("runtime_idle_share.batch").read(ctx)
    assert value == pytest.approx(want, rel=1e-12)
    assert 0 < value < harness.reader("device_idle_share.batch").read(ctx)


def test_runtime_idle_share_reads_nothing_without_a_trace():
    ctx = types.SimpleNamespace(h=types.SimpleNamespace(trace_dir="/nonexistent"),
                                reduced=None)
    assert harness.reader("runtime_idle_share.batch").read(ctx) is None
