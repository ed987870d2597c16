"""The volume runtime against the plain 3-D reference, on seeded soups.

What they pin:

- ``VolumeRuntime.run`` on the dense, bitpack and Pallas (interpret
  mode) engines equals ``perf/reference/life3d_torus.py`` bit for bit,
  for generation counts that do and do not divide by the kernels' block
  of 8;
- the runtime keeps its programs: a second job of the same shape
  compiles nothing;
- the volume path's spans (``gol.compile.<n>`` over ``gol.lower.<n>``,
  ``gol.run.evolve``, ``gol.chunk`` over ``gol.dispatch`` and
  ``gol.fence``, ``gol.advance``) carry ``dims: 3``, and the fused
  kernel's choice is counted and put on the lowering span;
- the reference itself: equal to a per-cell NumPy loop on a 16³ torus,
  each axis wraps, and the control (the plane wrap left out) differs.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
import pytest

from gol_tpu import telemetry
from gol_tpu.ops import life3d
from gol_tpu.volume import VolumeRuntime
from perf.reference import life3d_torus as ref

jax.config.update("jax_platforms", "cpu")


def _soup(size, seed, density=0.3):
    rng = np.random.default_rng(seed)
    return (rng.random((size,) * 3) < density).astype(np.uint8)


def _run(engine, size, gens, seed, rule=life3d.BAYS_4555):
    rt = VolumeRuntime(size=size, rule=rule, engine=engine)
    return np.asarray(rt.run(jax.device_put(_soup(size, seed)), gens))


@pytest.mark.parametrize(
    "engine,size,gens",
    [
        ("dense", 32, 8),
        ("dense", 64, 11),
        ("bitpack", 32, 16),
        ("bitpack", 64, 13),
        ("bitpack", 128, 9),
        ("pallas", 32, 8),
        ("pallas", 32, 11),
    ],
)
def test_runtime_equals_reference(engine, size, gens):
    seed = size + gens
    want = np.asarray(ref.evolve(jax.device_put(_soup(size, seed)), gens))
    np.testing.assert_array_equal(_run(engine, size, gens, seed), want)


def test_runtime_equals_reference_under_another_rule():
    want = np.asarray(ref.evolve(jax.device_put(_soup(32, 5)), 6, rule="B6/S5,6,7"))
    np.testing.assert_array_equal(_run("bitpack", 32, 6, 5, life3d.BAYS_5766), want)


def test_second_job_of_the_same_shape_compiles_nothing():
    rt = VolumeRuntime(size=32, engine="bitpack")
    first = rt.run(jax.device_put(_soup(32, 1)), 7)
    programs = dict(rt.programs)
    before = telemetry.span_log()["totals"]["gol.compile.7"]["count"]
    second = rt.run(jax.device_put(_soup(32, 2)), 7)
    assert telemetry.span_log()["totals"]["gol.compile.7"]["count"] == before
    assert rt.programs == programs and list(programs) == [7]
    assert not np.array_equal(np.asarray(first), np.asarray(second))


def _since(t0):
    me = threading.get_ident()
    return sorted((r for r in telemetry.span_log()["records"]
                   if r["t0"] >= t0 and r["thread"] == me), key=lambda r: r["t0"])


def test_run_records_the_volume_spans_with_dims_3():
    t0 = time.perf_counter()
    rt = VolumeRuntime(size=32, engine="bitpack")
    rt.run(jax.device_put(_soup(32, 3)), 5)
    recs = _since(t0)
    assert [(r["name"], r["parent"]) for r in recs] == [
        ("gol.compile.5", None), ("gol.lower.5", "gol.compile.5"),
        ("gol.run.evolve", None), ("gol.chunk", "gol.run.evolve"),
        ("gol.dispatch", "gol.chunk"), ("gol.fence", "gol.chunk"),
        ("gol.advance", "gol.run.evolve"),
    ]
    assert all(r["attrs"]["dims"] == 3 for r in recs)
    assert recs[3]["attrs"] == {"step": 0, "dims": 3}


def test_fused_kernel_choice_is_counted_on_the_lowering_span():
    """Traced once per program: the counter and the plan land on the
    gol.lower span that traced ``evolve3d``."""
    t0 = time.perf_counter()
    before = telemetry.span_log()["counters"].get("gol3d.kernel.plane", [0])[0]
    rt = VolumeRuntime(size=32, engine="pallas")
    rt.run(jax.device_put(_soup(32, 4)), 3)
    lower = [r for r in _since(t0) if r["name"] == "gol.lower.3"]
    assert len(lower) == 1
    assert {k: lower[0]["attrs"][k] for k in ("kernel", "tile", "k")} == {
        "kernel": "plane", "tile": 32, "k": 3}
    assert lower[0]["counts"]["gol3d.kernel.plane"][0] == 1
    assert telemetry.span_log()["counters"]["gol3d.kernel.plane"][0] == before + 1


def test_auto_off_the_chip_resolves_to_bitpack():
    assert VolumeRuntime(size=64).resolved == "bitpack"
    assert VolumeRuntime(size=48).resolved == "dense"


# -- the reference ------------------------------------------------------------


def _loop_step(v, birth, survive):
    """One generation, cell by cell in NumPy: every axis wraps."""
    d, h, w = v.shape
    out = np.zeros_like(v)
    for z in range(d):
        for y in range(h):
            for x in range(w):
                n = 0
                for dz in (-1, 0, 1):
                    for dy in (-1, 0, 1):
                        for dx in (-1, 0, 1):
                            if dz or dy or dx:
                                n += v[(z + dz) % d, (y + dy) % h, (x + dx) % w]
                out[z, y, x] = n in (survive if v[z, y, x] else birth)
    return out


def test_reference_equals_a_per_cell_loop():
    v = _soup(16, 11)
    want = v
    for _ in range(2):
        want = _loop_step(want, {5}, {4, 5})
    np.testing.assert_array_equal(np.asarray(ref.evolve(v, 2)), want)


@pytest.mark.parametrize("axis", [0, 1, 2])
def test_reference_wraps_each_axis(axis):
    """On a torus, stepping commutes with a roll along any axis; an axis
    that did not wrap would break that at its seam.  The control breaks
    it along the plane axis alone."""
    v = _soup(16, 12 + axis)

    def moved(volume):
        return np.roll(np.asarray(volume), 7, axis=axis)

    got = ref.evolve(moved(v), 3)
    np.testing.assert_array_equal(np.asarray(got), moved(ref.evolve(v, 3)))
    ctrl = ref.evolve(moved(v), 3, wrap_planes=False)
    differs = ref.mismatches(ctrl, moved(ref.evolve(v, 3, wrap_planes=False))) > 0
    assert differs == (axis == 0)


def test_control_differs_and_only_where_planes_wrap():
    v = _soup(32, 13)
    want = ref.evolve(v, 6)
    ctrl = ref.evolve(v, 6, wrap_planes=False)
    assert ref.mismatches(ctrl, want) > 0
    assert ref.mismatches(want, want) == 0
    # After one generation only the two boundary planes can differ.
    one = np.asarray(ref.evolve(v, 1)) != np.asarray(ref.evolve(v, 1, wrap_planes=False))
    assert one.any() and not one[1:-1].any()


def test_reference_rule_strings():
    assert ref.parse_rule("B5/S4,5") == (frozenset({5}), frozenset({4, 5}))
    assert ref.parse_rule("b5,6,7/s") == (frozenset({5, 6, 7}), frozenset())
    with pytest.raises(ValueError):
        ref.parse_rule("45/5")
