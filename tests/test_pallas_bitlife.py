"""Fused bit-packed Pallas kernel vs. the oracle (interpreter mode on CPU).

The top perf tier: the carry-save adder tree of bitlife runs fused over
VMEM tiles of the packed board.  Interpreter mode executes the same kernel
logic on CPU, covering the DMA halo indexing (mod-H row wrap), the lane-
roll word ring, and the native logical shift on int32.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from gol_tpu.ops import pallas_bitlife

from tests import oracle


@pytest.mark.parametrize("shape", [(32, 64), (64, 128), (8, 32), (16, 256)])
@pytest.mark.parametrize("steps", [1, 3])
def test_matches_oracle(shape, steps):
    h, w = shape
    board = oracle.random_board(h, w, seed=h + w + steps)
    got = np.asarray(pallas_bitlife.evolve(jnp.asarray(board), steps, 512))
    np.testing.assert_array_equal(got, oracle.run_torus(board, steps))


def test_blinker_wrap():
    from gol_tpu.models import patterns

    board = patterns.init_global(4, 64, num_ranks=1)
    got = np.asarray(pallas_bitlife.evolve(jnp.asarray(board), 2, 512))
    np.testing.assert_array_equal(got, board)  # period 2 across the x-wrap


def test_tile_smaller_than_board():
    """Multi-tile grid: the row-wrap halo DMAs cross tile boundaries."""
    board = oracle.random_board(64, 64, seed=9)
    got = np.asarray(pallas_bitlife.evolve(jnp.asarray(board), 4, 16))
    np.testing.assert_array_equal(got, oracle.run_torus(board, 4))


@pytest.mark.parametrize("k", [2, 5, 8, 16])
@pytest.mark.slow  # minutes-scale interpret-mode sweep; run with -m slow
def test_multi_step_matches_sequential(k):
    """Temporal blocking: k fused generations == k single-step launches."""
    from jax import lax

    from gol_tpu.ops import bitlife

    board = oracle.random_board(64, 64, seed=20 + k)
    packed = lax.bitcast_convert_type(
        bitlife.pack(jnp.asarray(board)), jnp.int32
    )
    ref = packed
    for _ in range(k):
        ref = pallas_bitlife.step_pallas_packed(ref, 16)
    got = pallas_bitlife.multi_step_pallas_packed(packed, 16, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_multi_step_remainder_path():
    """steps not divisible by the block: full blocks + one remainder launch."""
    board = oracle.random_board(32, 64, seed=31)
    got = np.asarray(pallas_bitlife.evolve(jnp.asarray(board), 21, 512))
    np.testing.assert_array_equal(got, oracle.run_torus(board, 21))


def test_multi_step_depth_validation():
    packed = jnp.zeros((64, 2), jnp.int32)
    with pytest.raises(ValueError, match="pad"):
        pallas_bitlife.multi_step_pallas_packed(packed, 8, 16)
    with pytest.raises(ValueError, match=">= 1"):
        pallas_bitlife.multi_step_pallas_packed(packed, 8, 0)


def test_pick_block_respects_geometry():
    # Default block depth re-tuned to 8 in round 3 (overhead-amortized
    # x10240 sweep: k=8 beats k=16 by its recompute-factor gap).
    assert pallas_bitlife._pick_block(1000, 256) == pallas_bitlife._BLOCK
    assert pallas_bitlife._pick_block(5, 256) == 5
    assert pallas_bitlife._pick_block(1000, 8) == 8
    assert pallas_bitlife._pick_block(1000, 256, block=16) == 16


def test_pick_tile():
    assert pallas_bitlife.pick_tile(64, 2, 512) == 64
    assert pallas_bitlife.pick_tile(64, 2, 16) == 16
    with pytest.raises(ValueError, match="divisible"):
        pallas_bitlife.pick_tile(12, 2, 512)


def test_width_must_pack():
    board = jnp.zeros((32, 48), jnp.uint8)  # 48 % 32 != 0
    with pytest.raises(ValueError, match="divisible"):
        pallas_bitlife.evolve(board, 1, 512)


# -- the aligned-window generation: every kernel that steps it, bit-exact ----


def _packed(board):
    from jax import lax

    from gol_tpu.ops import bitlife

    return lax.bitcast_convert_type(
        bitlife.pack(jnp.asarray(board)), jnp.int32
    )


@pytest.mark.parametrize("density", [0.2, 0.35, 0.6])
@pytest.mark.parametrize("k", [1, 3, 8])
def test_torus_window_generation_bit_exact(k, density):
    """The torus kernel at a tile smaller than the board: the wrong rows
    the window's edges make stay inside the pad for every depth."""
    board = oracle.random_board(64, 64, seed=k * 10 + int(density * 10),
                                density=density)
    got = pallas_bitlife.multi_step_pallas_packed(_packed(board), 16, k)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_packed(oracle.run_torus(board, k)))
    )


@pytest.mark.parametrize("edges", [False, True])
@pytest.mark.parametrize("kernel", ["ext", "bands"])
def test_ghost_row_kernels_bit_exact(kernel, edges):
    """The pre-extended and banded kernels, k ghost rows a side taken from
    the torus, against the torus; an ``edges`` operand replaces the first
    and last word of every row."""
    k, tile = 8, 16
    board = oracle.random_board(64, 128, seed=41 + edges)
    p = _packed(board)
    rng = np.random.default_rng(5)
    edge = (
        jnp.asarray(rng.integers(-(2**31), 2**31, (64, 2)), jnp.int32)
        if edges else None
    )
    if kernel == "ext":
        ext = jnp.concatenate([p[-k:], p, p[:k]])
        got = pallas_bitlife.multi_step_pallas_packed_ext(
            ext, tile, k, edges_i32=edge
        )
    else:
        bands = jnp.concatenate([p[-k:], p[:k]])
        got = pallas_bitlife.multi_step_pallas_packed_bands(
            p, bands, tile, k, edges_i32=edge
        )
    want = np.array(_packed(oracle.run_torus(board, k)))
    if edges:
        want[:, 0], want[:, -1] = np.asarray(edge).T
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("groups", [2, 4])
def test_folded_window_generation_bit_exact(groups):
    """``groups`` boards side by side in lanes, each its own word ring:
    every group evolves as its own torus."""
    k = 8
    boards = [
        oracle.random_board(32, 64, seed=60 + g) for g in range(groups)
    ]
    ext = jnp.concatenate(
        [jnp.concatenate([p[-k:], p, p[:k]]) for p in map(_packed, boards)],
        axis=1,
    )
    got = pallas_bitlife.multi_step_pallas_packed_ext(
        ext, 16, k, groups=groups
    )
    want = np.concatenate(
        [np.asarray(_packed(oracle.run_torus(b, k))) for b in boards], axis=1
    )
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("kernel", ["torus", "ext"])
def test_generic_rule_window_generation_bit_exact(kernel):
    """B36/S23 through the count-of-9 tail of the aligned window."""
    from gol_tpu.ops import rules

    k = 8
    board = oracle.random_board(64, 64, seed=77)
    p = _packed(board)
    if kernel == "torus":
        got = pallas_bitlife.multi_step_pallas_packed(
            p, 16, k, rules.HIGHLIFE
        )
    else:
        got = pallas_bitlife.multi_step_pallas_packed_ext(
            jnp.concatenate([p[-k:], p, p[:k]]), 16, k, rules.HIGHLIFE
        )
    want = rules.run_rule(jnp.asarray(board), k, rules.HIGHLIFE)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(_packed(want)))


@pytest.mark.parametrize("gates", [(1, 1, 1, 1), (1, 0, 1, 1), (0, 1, 0, 0)])
def test_sparse_gated_kernel_bit_exact(gates):
    """The gated grid's bands: a live band is one torus generation, a
    gated-off band its input."""
    from gol_tpu.sparse import pallas as sparse_pallas

    board = oracle.random_board(128, 64, seed=90 + sum(gates))
    got = sparse_pallas.step_gated_pallas(
        _packed(board), jnp.asarray(gates, jnp.int32), 32
    )
    live = np.repeat(np.asarray(gates, bool), 32)[:, None]
    want = np.where(live, oracle.step_torus(board), board)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(_packed(want)))


@pytest.mark.parametrize("rule", [None, "B3/S23"])
def test_every_neighbourhood_follows_b3s23(rule):
    """All 512 3x3 neighbourhoods, one a patch, against the B3/S23 table:
    the count-of-8 tail (``rule=None``) and the generic count-of-9 tail."""
    from gol_tpu.ops import rules

    codes = np.arange(512).reshape(16, 32)
    bits = (codes[..., None] >> np.arange(9)) & 1  # row-major 3x3
    board = (
        bits.reshape(16, 32, 3, 3).transpose(0, 2, 1, 3).reshape(48, 96)
    ).astype(np.uint8)
    got = np.asarray(pallas_bitlife.evolve(
        jnp.asarray(board), 1, 512,
        None if rule is None else rules.parse_rulestring(rule),
    ))
    centre = bits[..., 4]
    count = bits.sum(-1) - centre
    want = (count == 3) | ((centre == 1) & (count == 2))
    np.testing.assert_array_equal(got[1::3, 1::3], want.astype(np.uint8))
