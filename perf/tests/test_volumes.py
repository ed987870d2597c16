"""The volume cells: a tiny one run end to end on the CPU, the kernel's
roofline reader on a synthetic reduction, and the faults a volume cell
can have, each of which must come out as ``correct`` false:

- a step that returns its volume unchanged for the second half of the
  job (the job stops at half its generations);
- the wrap of the plane axis left out: the control, put in the
  program's place;
- an answer altered where it is produced: one cell of the job's final
  volume flipped.
"""

import types

import jax.numpy as jnp
import pytest

from perf import harness, peaks, work3d
from perf.reference import life3d_torus
from perf.tests.cells import run_cell
from perf.tests.cells3d import CELL, TINY3D, added_volume_cell


@pytest.fixture
def bench(tmp_path):
    with added_volume_cell(tmp_path) as path:
        yield path


def test_added_volume_cell_runs_and_is_correct(bench, capsys):
    out = run_cell(bench, CELL)
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"cell_updates_per_s", "setup_s"}
    assert out["checks"]["mismatched_cells"] == {"value": 0, "limit": 0}
    notes = capsys.readouterr().out
    assert 'engine: "bitpack"' in notes  # auto, off the chip


def test_volume_cell_lists_the_kernel_roofline_when_traced(bench):
    listed = harness.metrics_for(harness.load_benchmark(bench), CELL, traced=True)
    assert {m["name"] for m in listed} == {"pallas3d_roofline", "device_idle_share.batch"}
    out = run_cell(bench, CELL, trace=1)
    # On the CPU no trace is read, so neither device metric is.
    assert out["correct"] is True and out["metrics"] == {}


def _ctx(op_s, jobs, n_devices=1):
    h = types.SimpleNamespace(jobs=jobs, notes={})
    return types.SimpleNamespace(
        h=h, reduced={"op_s": op_s, "n_devices": n_devices},
        peaks=peaks.peaks("TPU v5 lite"))


def test_pallas3d_roofline_is_fixed_work_over_kernel_time():
    read = harness.reader("pallas3d_roofline").read
    kernel = ('%closed_call.3 = s32[1024,32,1024]{2,1,0} custom-call(%x), '
              'custom_call_target="tpu_custom_call"')
    job = {"cells": 1024 ** 3, "generations": 64, "cell_updates": 64 * 1024 ** 3}
    ctx = _ctx({kernel: 0.1, "%copy.3 = copy(%y)": 0.05}, [job, job])
    peak = peaks.peaks("TPU v5 lite")
    ops_s = 2 * 1024 ** 3 / 32 * 64 * 113 / peak["vpu_lane_ops"]
    bytes_s = 2 * 2 * 1024 ** 3 / 8 / peak["hbm_bytes_per_s"]
    assert work3d.job_ops(job["cells"], 64) * 2 / peak["vpu_lane_ops"] == ops_s
    assert ops_s > bytes_s
    assert read(ctx) == pytest.approx(100 * ops_s / 0.1)
    assert ctx.h.notes["pallas3d_roofline_bound"] == "ops"
    assert ctx.h.notes["pallas3d_kernel_s"] == 0.1
    # The XLA tiers run no kernel: nothing is read.
    assert read(_ctx({"%copy.3 = copy(%y)": 0.05}, [job])) is None
    assert read(types.SimpleNamespace(h=ctx.h, reduced=None, peaks=None)) is None


def _patch_run(monkeypatch, change):
    """Replace what ``VolumeRuntime.run`` returns by ``change(run, rt,
    volume_in, generations)``, ``run`` being the real method."""
    from gol_tpu.volume import VolumeRuntime

    orig = VolumeRuntime.run

    def run(self, volume, generations):
        return change(orig, self, jnp.copy(volume), generations)

    monkeypatch.setattr(VolumeRuntime, "run", run)


def test_second_half_left_unstepped_fails(bench, monkeypatch):
    _patch_run(monkeypatch, lambda run, rt, v, gens: run(rt, v, gens // 2))
    out = run_cell(bench, CELL)
    assert out["correct"] is False
    assert out["checks"]["mismatched_cells"]["value"] > 0


def test_plane_wrap_left_out_fails(bench, monkeypatch):
    _patch_run(monkeypatch, lambda run, rt, v, gens: life3d_torus.evolve(
        v, gens, rule=TINY3D["rule"], wrap_planes=False))
    out = run_cell(bench, CELL)
    assert out["correct"] is False
    assert out["checks"]["mismatched_cells"]["value"] > 0


def test_one_flipped_cell_fails(bench, monkeypatch):
    def flip(run, rt, v, gens):
        out = run(rt, v, gens)
        return out.at[3, 5, 7].set(1 - out[3, 5, 7])

    _patch_run(monkeypatch, flip)
    out = run_cell(bench, CELL)
    assert out["correct"] is False
    assert out["checks"]["mismatched_cells"]["value"] == 1
