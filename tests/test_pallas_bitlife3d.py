"""Pallas 3-D kernel vs the XLA bit-packed 3-D engine (interpret mode on CPU)."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gol_tpu.ops import bitlife3d, life3d, pallas_bitlife3d

jax.config.update("jax_platforms", "cpu")


def _rand_vol(d, h, w, seed=0):
    return np.random.default_rng(seed).integers(0, 2, (d, h, w), np.uint8)


@pytest.mark.parametrize("rule", [life3d.BAYS_4555, life3d.BAYS_5766])
@pytest.mark.parametrize("steps", [1, 3])
def test_matches_xla_packed(rule, steps):
    vol = _rand_vol(16, 8, 64, seed=steps + len(rule.survive))
    got = np.asarray(pallas_bitlife3d.evolve3d(jnp.asarray(vol), steps, rule))
    ref = np.asarray(bitlife3d.evolve3d_dense_io(jnp.asarray(vol), steps, rule))
    np.testing.assert_array_equal(got, ref)


def test_temporal_blocking_matches_sequential():
    vol = _rand_vol(16, 8, 32, seed=9)
    pt = jax.lax.bitcast_convert_type(
        bitlife3d.pack3d(jnp.asarray(vol)), jnp.int32
    ).transpose(0, 2, 1)
    ref = pt
    for _ in range(5):
        ref = pallas_bitlife3d.multi_step_pallas_packed3d(ref, 8, 1)
    got = pallas_bitlife3d.multi_step_pallas_packed3d(pt, 8, 5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_remainder_path():
    vol = _rand_vol(8, 8, 32, seed=3)
    got = np.asarray(pallas_bitlife3d.evolve3d(jnp.asarray(vol), 11))
    ref = np.asarray(bitlife3d.evolve3d_dense_io(jnp.asarray(vol), 11))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("rule", [life3d.BAYS_4555, life3d.BAYS_5766])
@pytest.mark.parametrize("k", [1, 3, 8])
def test_roll_kernel_matches_xla_packed(rule, k):
    """The rolling-plane kernel (r4): per-plane fori_loop with a count9
    carry, in-place stores, manual output DMA — vs the XLA oracle."""
    vol = _rand_vol(32, 8, 64, seed=k + len(rule.birth))
    pt = jax.lax.bitcast_convert_type(
        bitlife3d.pack3d(jnp.asarray(vol)), jnp.int32
    ).transpose(0, 2, 1)
    got = bitlife3d.unpack3d(
        jax.lax.bitcast_convert_type(
            pallas_bitlife3d.multi_step_pallas_packed3d_roll(
                pt, 8, k, rule
            ).transpose(0, 2, 1),
            jnp.uint32,
        )
    )
    ref = bitlife3d.evolve3d_dense_io(jnp.asarray(vol), k, rule)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_roll_kernel_matches_monolithic_plane_kernel():
    """Bit-equality between the rolling and monolithic plane kernels on
    the same tiling — the restructure moves memory, not arithmetic."""
    vol = _rand_vol(32, 16, 32, seed=17)
    pt = jax.lax.bitcast_convert_type(
        bitlife3d.pack3d(jnp.asarray(vol)), jnp.int32
    ).transpose(0, 2, 1)
    a = pallas_bitlife3d.multi_step_pallas_packed3d_roll(pt, 8, 5)
    b = pallas_bitlife3d.multi_step_pallas_packed3d(pt, 8, 5)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_roll_kernel_single_tile_whole_volume():
    """tile == depth: grid of one, the window IS the volume (the 512³
    configuration the big-window picker produces)."""
    vol = _rand_vol(16, 8, 32, seed=23)
    pt = jax.lax.bitcast_convert_type(
        bitlife3d.pack3d(jnp.asarray(vol)), jnp.int32
    ).transpose(0, 2, 1)
    got = pallas_bitlife3d.multi_step_pallas_packed3d_roll(pt, 16, 8)
    ref = pallas_bitlife3d.multi_step_pallas_packed3d(pt, 8, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_roll_kernel_validation():
    pt = jnp.zeros((16, 2, 32), jnp.int32)
    with pytest.raises(ValueError, match="tile"):
        pallas_bitlife3d.multi_step_pallas_packed3d_roll(pt, 12, 1)
    with pytest.raises(ValueError, match="pad"):
        pallas_bitlife3d.multi_step_pallas_packed3d_roll(pt, 8, 16)
    with pytest.raises(ValueError, match=">= 1"):
        pallas_bitlife3d.multi_step_pallas_packed3d_roll(pt, 8, 0)


def test_pick_tile3d_roll_big_windows():
    """The rolling model fits far larger windows than the monolithic
    one: whole-volume windows at 512³, 64-plane windows at 1024³ (where
    the monolithic plane kernel fits nothing at all)."""
    assert pallas_bitlife3d.pick_tile3d_roll(512, 16, 512) == 256
    assert pallas_bitlife3d.pick_tile3d_roll(1024, 32, 1024) == 64
    assert pallas_bitlife3d.pick_tile3d(1024, 32, 1024) == 0
    # Degenerate: a single plane larger than the whole budget.
    assert pallas_bitlife3d.pick_tile3d_roll(8, 4096, 4096) == 0


def test_tile_and_depth_validation():
    pt = jnp.zeros((16, 2, 32), jnp.int32)
    with pytest.raises(ValueError, match="tile"):
        pallas_bitlife3d.multi_step_pallas_packed3d(pt, 12, 1)
    with pytest.raises(ValueError, match="pad"):
        pallas_bitlife3d.multi_step_pallas_packed3d(pt, 8, 16)
    with pytest.raises(ValueError, match=">= 1"):
        pallas_bitlife3d.multi_step_pallas_packed3d(pt, 8, 0)
    with pytest.raises(ValueError, match="divisible"):
        pallas_bitlife3d.pick_tile3d(12, 2, 32)


def test_pick_tile3d_budget():
    assert pallas_bitlife3d.pick_tile3d(512, 16, 512) == 32
    assert pallas_bitlife3d.pick_tile3d(16, 2, 32) == 16
    # A 1024-cube's (32, 1024)-word plane exceeds the scoped-VMEM window:
    # infeasible, signalled by 0 (evolve3d falls back to the XLA path).
    assert pallas_bitlife3d.pick_tile3d(1024, 32, 1024) == 0


def test_evolve3d_fallback_when_vmem_infeasible(monkeypatch):
    # Force the infeasible branch regardless of geometry and check the
    # result still matches the XLA engine.  The Pallas entries are patched
    # to raise, so a cached/alternate trace taking a kernel path cannot let
    # this test pass vacuously (both paths are bit-exact otherwise).
    monkeypatch.setattr(pallas_bitlife3d, "pick_tile3d", lambda *a, **k: 0)
    monkeypatch.setattr(
        pallas_bitlife3d, "pick_tile3d_wt", lambda *a, **k: None
    )
    monkeypatch.setattr(
        pallas_bitlife3d, "pick_tile3d_roll", lambda *a, **k: 0
    )

    def _boom(*a, **k):
        raise AssertionError("Pallas path taken despite tile == 0")

    monkeypatch.setattr(pallas_bitlife3d, "multi_step_pallas_packed3d", _boom)
    monkeypatch.setattr(
        pallas_bitlife3d, "multi_step_pallas_packed3d_wt", _boom
    )
    vol = _rand_vol(8, 8, 32, seed=12)
    got = np.asarray(pallas_bitlife3d.evolve3d(jnp.asarray(vol), 4))
    ref = np.asarray(bitlife3d.evolve3d_dense_io(jnp.asarray(vol), 4))
    np.testing.assert_array_equal(got, ref)


# -- word-tiled kernel: the 1024³-class path ---------------------------------


def _to_word_leading(vol):
    return jax.lax.bitcast_convert_type(
        bitlife3d.pack3d(jnp.asarray(vol)), jnp.int32
    ).transpose(2, 0, 1)


def _from_word_leading(pw):
    return np.asarray(
        bitlife3d.unpack3d(
            jax.lax.bitcast_convert_type(pw.transpose(1, 2, 0), jnp.uint32)
        )
    )


@pytest.mark.parametrize("rule", [life3d.BAYS_4555, life3d.BAYS_5766])
@pytest.mark.parametrize("k", [1, 3, 8])
@pytest.mark.parametrize("tile_w", [1, 2])
def test_wt_kernel_matches_xla_packed(rule, k, tile_w):
    """tile_w=1 forces a word-chunk seam: the ghost word's bit light cone
    must carry the x neighborhood across chunks for all k generations."""
    vol = _rand_vol(16, 16, 64, seed=k + len(rule.birth))  # nw = 2
    pw = _to_word_leading(vol)
    got = pallas_bitlife3d.multi_step_pallas_packed3d_wt(
        pw, 8, tile_w, k, rule
    )
    ref = np.asarray(
        bitlife3d.evolve3d_dense_io(jnp.asarray(vol), k, rule)
    )
    np.testing.assert_array_equal(_from_word_leading(got), ref)


def test_wt_kernel_wide_volume_seams():
    """4 words × tile_w=2: seams at word 2 and at the torus x wrap."""
    vol = _rand_vol(16, 8, 128, seed=5)  # nw = 4
    pw = _to_word_leading(vol)
    got = pallas_bitlife3d.multi_step_pallas_packed3d_wt(pw, 8, 2, 4)
    ref = np.asarray(bitlife3d.evolve3d_dense_io(jnp.asarray(vol), 4))
    np.testing.assert_array_equal(_from_word_leading(got), ref)


def test_wt_kernel_validation():
    pw = jnp.zeros((2, 16, 32), jnp.int32)
    with pytest.raises(ValueError, match="tile"):
        pallas_bitlife3d.multi_step_pallas_packed3d_wt(pw, 12, 1, 1)
    with pytest.raises(ValueError, match="word tile"):
        pallas_bitlife3d.multi_step_pallas_packed3d_wt(pw, 8, 3, 1)
    with pytest.raises(ValueError, match="light cone"):
        pallas_bitlife3d.multi_step_pallas_packed3d_wt(pw, 8, 1, 33)
    with pytest.raises(ValueError, match="pad"):
        pallas_bitlife3d.multi_step_pallas_packed3d_wt(pw, 8, 1, 16)


def test_pick_tile3d_wt_covers_1024_cube():
    # The headline size: a (32, 1024)-word plane doesn't fit whole, the
    # word-tiled split does.
    got = pallas_bitlife3d.pick_tile3d_wt(1024, 32, 1024)
    assert got is not None
    tile_d, tile_w = got
    assert 1024 % tile_d == 0 and 32 % tile_w == 0
    window = (
        (tile_w + 2)
        * (tile_d + 16)
        * 1024
        * 4
        * pallas_bitlife3d._LIVE_WINDOWS_WT
    )
    assert window <= pallas_bitlife3d._SCOPED_LIMIT


def test_evolve3d_strict_raises_instead_of_fallback(monkeypatch):
    """ADVICE r1: an explicit --engine pallas run must never be silently
    relabeled as Pallas throughput while running the XLA path."""
    monkeypatch.setattr(pallas_bitlife3d, "pick_tile3d", lambda *a, **k: 0)
    monkeypatch.setattr(
        pallas_bitlife3d, "pick_tile3d_wt", lambda *a, **k: None
    )
    monkeypatch.setattr(
        pallas_bitlife3d, "pick_tile3d_roll", lambda *a, **k: 0
    )
    vol = jnp.zeros((8, 8, 32), jnp.uint8)
    with pytest.raises(ValueError, match="scoped VMEM"):
        pallas_bitlife3d.evolve3d(vol, 2, life3d.BAYS_4555, True)


def test_cli3d_explicit_pallas_fails_loud(monkeypatch, capsys):
    from gol_tpu import cli3d

    monkeypatch.setattr(pallas_bitlife3d, "pick_tile3d", lambda *a, **k: 0)
    monkeypatch.setattr(
        pallas_bitlife3d, "pick_tile3d_wt", lambda *a, **k: None
    )
    monkeypatch.setattr(
        pallas_bitlife3d, "pick_tile3d_roll", lambda *a, **k: 0
    )
    rc = cli3d.main(["2", "32", "2", "64", "0", "--engine", "pallas"])
    assert rc == 255
    assert "scoped VMEM" in capsys.readouterr().out


def test_evolve3d_dispatches_to_wt(monkeypatch):
    """When the plane window is infeasible but the word-tiled one fits,
    evolve3d must take the wt kernel (not the XLA fallback)."""
    monkeypatch.setattr(pallas_bitlife3d, "pick_tile3d", lambda *a, **k: 0)
    monkeypatch.setattr(
        pallas_bitlife3d, "pick_tile3d_roll", lambda *a, **k: 0
    )
    calls = []
    real = pallas_bitlife3d.multi_step_pallas_packed3d_wt

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(
        pallas_bitlife3d, "multi_step_pallas_packed3d_wt", spy
    )
    vol = _rand_vol(16, 8, 64, seed=21)
    got = np.asarray(pallas_bitlife3d.evolve3d(jnp.asarray(vol), 11))
    ref = np.asarray(bitlife3d.evolve3d_dense_io(jnp.asarray(vol), 11))
    np.testing.assert_array_equal(got, ref)
    assert calls  # the wt kernel actually ran (incl. the remainder launch)


def test_evolve3d_dispatches_to_roll(monkeypatch):
    """The rolling kernel wins the score dispatch when its (bigger)
    window recomputes least — the 1024³ situation, shrunk to interpret
    size: roll(96) scores 1.09 against wt (48,4)'s 1.78 and plane(8)'s
    2.13 (shrinking-window mean, pad 8)."""
    monkeypatch.setattr(pallas_bitlife3d, "pick_tile3d", lambda *a, **k: 8)
    monkeypatch.setattr(
        pallas_bitlife3d, "pick_tile3d_wt", lambda *a, **k: (48, 4)
    )
    monkeypatch.setattr(
        pallas_bitlife3d, "pick_tile3d_roll", lambda *a, **k: 96
    )
    calls = []
    real = pallas_bitlife3d.multi_step_pallas_packed3d_roll

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(
        pallas_bitlife3d, "multi_step_pallas_packed3d_roll", spy
    )
    vol = _rand_vol(96, 8, 128, seed=37)
    got = np.asarray(pallas_bitlife3d.evolve3d(jnp.asarray(vol), 11))
    ref = np.asarray(bitlife3d.evolve3d_dense_io(jnp.asarray(vol), 11))
    np.testing.assert_array_equal(got, ref)
    assert calls  # the rolling kernel won the dispatch


def test_score_dispatch_prefers_lower_recompute(monkeypatch):
    """When both kernels fit, the halo-recompute score decides: a plane
    tile of 8 (score 2.13) must lose to wt (48, 4) (score 1.78) — the
    768³ situation, shrunk to interpret-mode size (shrinking-window mean,
    pad 8)."""
    monkeypatch.setattr(pallas_bitlife3d, "pick_tile3d", lambda *a, **k: 8)
    monkeypatch.setattr(
        pallas_bitlife3d, "pick_tile3d_wt", lambda *a, **k: (48, 4)
    )
    monkeypatch.setattr(
        pallas_bitlife3d, "pick_tile3d_roll", lambda *a, **k: 0
    )
    calls = []
    real = pallas_bitlife3d.multi_step_pallas_packed3d_wt

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(
        pallas_bitlife3d, "multi_step_pallas_packed3d_wt", spy
    )
    vol = _rand_vol(96, 8, 128, seed=31)  # depth 96 % 48 == 0, nw 4 % 4 == 0
    got = np.asarray(pallas_bitlife3d.evolve3d(jnp.asarray(vol), 3))
    ref = np.asarray(bitlife3d.evolve3d_dense_io(jnp.asarray(vol), 3))
    np.testing.assert_array_equal(got, ref)
    assert calls  # the word-tiled kernel won the dispatch


# -- the conversion between the uint8 volume and the kernels' layout ---------


def _volume(kind, shape):
    d, h, w = shape
    if kind == "ones":
        return np.ones(shape, np.uint8)
    if kind == "zeros":
        return np.zeros(shape, np.uint8)
    vol = _rand_vol(d, h, w, seed=d + h + w)
    if kind == "sign_bit":  # column 32k+31 live: every word is negative
        vol[..., 31::32] = 1
    return vol


@pytest.mark.parametrize("kind", ["random", "ones", "zeros", "sign_bit"])
@pytest.mark.parametrize(
    "shape", [(8, 16, 64), (16, 8, 128), (24, 40, 96), (8, 8, 32)]
)
def test_pack_transposed_is_pack3d_transposed(kind, shape):
    """Bit j of word k is column 32k+j, as in ``pack3d``, and the pair
    round-trips."""
    vol = jnp.asarray(_volume(kind, shape))
    want = jax.lax.bitcast_convert_type(
        bitlife3d.pack3d(vol), jnp.int32
    ).transpose(0, 2, 1)
    got = pallas_bitlife3d.pack_transposed(vol)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    back = pallas_bitlife3d.unpack_transposed(got)
    assert back.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(back), np.asarray(vol))


def _lowered_in_span(name, vol_shape, steps):
    from gol_tpu import telemetry

    spec = jax.ShapeDtypeStruct(vol_shape, jnp.uint8)
    with telemetry.trace_annotation(name):
        pallas_bitlife3d.evolve3d.lower(spec, steps)
    return [r for r in telemetry.span_log()["records"] if r["name"] == name][-1]


def test_conversion_is_counted_and_annotated():
    """``evolve3d`` counts the conversion it traced and names it on the
    open span, beside the kernel."""
    rec = _lowered_in_span("test.convert.mxu", (8, 8, 160), 2)
    assert rec["attrs"]["convert"] == "mxu"
    assert rec["counts"]["gol3d.convert.mxu"][0] == 1


def test_xla_fallback_counts_the_xla_conversion(monkeypatch):
    monkeypatch.setattr(pallas_bitlife3d, "pick_tile3d", lambda *a, **k: 0)
    monkeypatch.setattr(
        pallas_bitlife3d, "pick_tile3d_wt", lambda *a, **k: None
    )
    monkeypatch.setattr(
        pallas_bitlife3d, "pick_tile3d_roll", lambda *a, **k: 0
    )
    rec = _lowered_in_span("test.convert.xla", (8, 8, 224), 2)
    assert rec["attrs"]["convert"] == "xla"
    assert rec["attrs"]["kernel"] == "xla_fallback"
    assert rec["counts"]["gol3d.convert.xla"][0] == 1
