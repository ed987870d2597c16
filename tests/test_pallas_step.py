"""Pallas fused kernel vs. the oracle — interpret mode on the CPU backend.

The same kernel code compiles through Mosaic on a real TPU; interpret
mode checks semantics: DMA halo layout, aligned offsets, lane-roll column
wrap, rule fusion.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from gol_tpu.ops import pallas_step, stencil

from tests import oracle


random_board = oracle.random_board


@pytest.mark.parametrize(
    "shape,tile",
    [((64, 128), 32), ((128, 128), 32), ((96, 256), 32), ((160, 128), 32)],
)
@pytest.mark.parametrize("seed", [0, 1])
def test_step_matches_oracle(shape, tile, seed):
    board = random_board(*shape, seed)
    got = np.asarray(pallas_step.step_pallas(jnp.asarray(board), tile))
    np.testing.assert_array_equal(got, oracle.step_torus(board))


def test_single_tile_grid():
    """tile == height: the halo blocks wrap to the board's own edges."""
    board = random_board(32, 128, 3)
    got = np.asarray(pallas_step.step_pallas(jnp.asarray(board), 32))
    np.testing.assert_array_equal(got, oracle.step_torus(board))


def test_evolve_matches_dense_engine():
    board = random_board(64, 128, 5)
    got = np.asarray(pallas_step.evolve(jnp.asarray(board), 6, 512))
    want = np.asarray(stencil.run(jnp.asarray(board), 6))
    np.testing.assert_array_equal(got, want)


def test_blinker_period_two():
    board = np.zeros((32, 128), np.uint8)
    board[0, 0] = board[0, 1] = board[0, 127] = 1  # pattern 4's wrap blinker
    one = np.asarray(pallas_step.step_pallas(jnp.asarray(board), 32))
    two = np.asarray(pallas_step.step_pallas(jnp.asarray(one), 32))
    np.testing.assert_array_equal(two, board)
    assert not np.array_equal(one, board)


def test_pick_tile_divides_and_aligns():
    assert pallas_step.pick_tile(16384, 16384, 512) % 32 == 0
    assert 16384 % pallas_step.pick_tile(16384, 16384, 512) == 0
    assert pallas_step.pick_tile(64, 128, 1 << 30) == 64  # capped by height
    # tiny hint clamps up to the minimum aligned tile
    assert pallas_step.pick_tile(64, 128, 1) == 32


def test_pick_tile_vmem_budget_shrinks_with_width():
    wide = pallas_step.pick_tile(16384, 65536, 512)
    narrow = pallas_step.pick_tile(16384, 2048, 512)
    assert wide < narrow
    assert (2 * wide + 2) * 65536 <= 32 * 1024 * 1024  # sane VMEM footprint


def test_rejects_bad_geometry():
    with pytest.raises(ValueError, match="divisible"):
        pallas_step.pick_tile(12, 128, 512)
    with pytest.raises(ValueError, match="multiple"):
        pallas_step.step_pallas(jnp.zeros((32, 128), jnp.uint8), 12)


def test_long_evolution_matches_oracle():
    board = random_board(96, 128, 9)
    got = np.asarray(pallas_step.evolve(jnp.asarray(board), 12, 32))
    np.testing.assert_array_equal(got, oracle.run_torus(board, 12))
