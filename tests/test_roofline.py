"""Roofline attribution arithmetic: audited constants, recompute sums,
and the degenerate cases that keep the numbers meaningful."""

import pytest

from gol_tpu.utils import roofline

V5E = "TPU v5 lite"


def test_flat_kernel_no_recompute():
    """k=1, huge tile: the recompute factor approaches 1 and ops/word
    approaches the flat per-word count."""
    r = roofline.roofline_2d(1e12, tile=4096, k=1, device_kind=V5E)
    flat = roofline.OPS_2D_HSUM_PER_EXT_ROW + roofline.OPS_2D_RULE_PER_OUT_ROW
    assert r.ops_per_useful_word == pytest.approx(flat, rel=0.01)
    assert r.recompute_factor == pytest.approx(1.0, abs=0.01)


def test_recompute_grows_with_depth_and_shrinks_with_tile():
    shallow = roofline.recompute_2d(tile=128, k=8)
    deep = roofline.recompute_2d(tile=128, k=32)
    wide = roofline.recompute_2d(tile=256, k=8)
    assert 1.0 < shallow < deep
    assert wide < shallow
    # Exact closed form: (t + 2*pad) / t, pad = k rounded up to 8.
    assert shallow == pytest.approx(1 + 16 / 128)
    assert roofline.recompute_2d(tile=128, k=3) == shallow


def test_bench_roofline_matches_engine_pickers():
    """The attribution must use the exact tile/k the benchmarked engine
    picks, not assumptions that can drift."""
    from gol_tpu.ops import bitlife, pallas_bitlife

    r = roofline.bench_roofline_2d(
        1.85e12, 16384, 16384, 10240, device_kind=V5E
    )
    tile = pallas_bitlife.pick_tile(
        16384, bitlife.packed_width(16384), pallas_bitlife._BLOCK_TILE
    )
    k = pallas_bitlife._pick_block(10240, tile)
    assert r.ops_per_useful_word == pytest.approx(
        roofline.ops_2d_per_useful_word(tile, k)
    )
    # The round-2 headline rate lands at a plausible VPU fraction —
    # neither >1 (impossible) nor <0.2 (which would mean the op model or
    # the measurement is broken).
    assert 0.3 < r.mfu < 1.0


def test_3d_wt_recompute_includes_both_axes():
    r = roofline.roofline_3d_wt(
        2.4e11, tile_d=32, tile_w=4, k=8, device_kind=V5E
    )
    # word factor 6/4 = 1.5; plane factor mean of (32 + 2(8-j))/32.
    word = (4 + 2) / 4
    plane = sum(32 + 2 * (8 - j) for j in range(8)) / (32 * 8)
    assert r.recompute_factor == pytest.approx(word * plane)
    assert 0.2 < r.mfu < 1.0


def test_folded_costs_more_per_row():
    plain = roofline.ops_2d_per_useful_word(128, 8)
    folded = roofline.ops_2d_per_useful_word(128, 8, folded=True)
    assert folded > plain
    assert (folded - plain) < 5  # ~4 extra ops on the hsum stage

def test_ring_attribution_matches_engine_tiling():
    """The ring attribution must mirror the engine's own shard/fold tile
    derivation — pinned against hand-derived expected configurations, not
    by re-running the attribution's implementation."""
    # Wide single-device ring at the bench geometry: nw=512 fills lanes,
    # no fold; engine defaults tile_hint=1024 (r5), halo_depth=8 — the
    # VMEM budget at nw=512 caps the tile at 256.
    r = roofline.bench_roofline_2d_ring(
        1.8e12, 16384, 16384, device_kind=V5E
    )
    assert r.ops_per_useful_word == pytest.approx(
        roofline.ops_2d_per_useful_word(256, 8)
    )
    # Folded narrow board: nw=32 -> fold=4; the engine tiles the FOLDED
    # height 640/4=160 (capped by the height itself under the 1024
    # hint), not the unfolded pick(640, 32).
    r = roofline.bench_roofline_2d_ring(1e12, 640, 1024, device_kind=V5E)
    assert r.ops_per_useful_word == pytest.approx(
        roofline.ops_2d_per_useful_word(160, 8, folded=True)
    )
    # Multi-device ring tiles the shard height, not the global height:
    # 4 devices over 512 rows -> shard 128 -> tile 128 even though the
    # global height would allow bigger windows.
    r = roofline.bench_roofline_2d_ring(
        1e12, 512, 16384, num_devices=4, device_kind=V5E
    )
    assert r.ops_per_useful_word == pytest.approx(
        roofline.ops_2d_per_useful_word(128, 8)
    )


def test_folded_recompute_factor_isolates_blocking():
    """k=1 folded: recompute factor ~1 even though folded rows cost more
    — fold overhead must not masquerade as halo recompute."""
    r = roofline.roofline_2d(
        1e12, tile=4096, k=1, folded=True, device_kind=V5E
    )
    assert r.recompute_factor == pytest.approx(1.0, abs=0.01)

def test_ring_attribution_rejects_unfoldable_geometry():
    """Geometries the engine cannot run must not get an attribution."""
    with pytest.raises(ValueError, match="lane-fold"):
        roofline.bench_roofline_2d_ring(1e12, 648, 1024, device_kind=V5E)


def test_peak_is_keyed_by_device_kind():
    """The VPU peak comes from the device kind's table entry; a kind with
    no entry (the CPU backend here) raises instead of borrowing v5e's."""
    assert roofline.vpu_lane_ops(V5E) == pytest.approx(3.85e12, rel=0.01)
    with pytest.raises(ValueError, match="no VPU peak"):
        roofline.vpu_lane_ops()
    with pytest.raises(ValueError, match="no VPU peak"):
        roofline.roofline_2d(1e12, tile=1024, k=1)
