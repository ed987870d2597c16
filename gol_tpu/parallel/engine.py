"""Generation-loop engines over the whole (possibly multi-rank) world.

Two semantics are offered, per the bug-register decision in SURVEY §5 (B1):

- **fresh** (default): correct torus Game of Life.  The halo rows a block
  sees are always the neighbors' *current* boundary rows — on a sharded mesh
  they are delivered by ``lax.ppermute`` every step
  (:mod:`gol_tpu.parallel.sharded`); on a single device the plain torus
  stencil is equivalent.
- **stale_t0** (reference-compat): the reference fills its halo send buffers
  once at t=0 and never refreshes them (``init_Ghost_rows``,
  gol-with-cuda.cu:40-47; no re-copy anywhere in the loop,
  gol-main.c:94-116), so every step each rank receives its ring neighbors'
  t=0 boundary rows.  After t=0 the rank blocks evolve independently — which
  is exactly how we implement it: the frozen halos are computed once from
  the initial board and the per-rank evolution is a ``vmap`` over the rank
  axis, the whole multi-generation loop one compiled ``fori_loop``.

Both keep all generations on-device in a single compiled program — no
per-step host round-trip (the reference pays ``cudaDeviceSynchronize`` +
2×``MPI_Wait`` per generation, gol-with-cuda.cu:277 / gol-main.c:110-111).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from gol_tpu.ops import stencil
from gol_tpu.parallel.modes import HALO_MODES


def split_ranks(global_board: jax.Array, num_ranks: int) -> jax.Array:
    """[R*S, W] -> [R, S, W] stack of per-rank blocks."""
    height, width = global_board.shape
    if height % num_ranks:
        raise ValueError(f"height {height} not divisible by {num_ranks} ranks")
    return global_board.reshape(num_ranks, height // num_ranks, width)


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(0,))
def evolve_fresh(global_board: jax.Array, steps: int) -> jax.Array:
    """Correct torus semantics on one device (halos implicit in the wrap)."""
    return lax.fori_loop(0, steps, lambda _, b: stencil.step(b), global_board)


def frozen_halos(
    global_board: jax.Array, num_ranks: int
) -> tuple[jax.Array, jax.Array]:
    """The t=0 ghost rows every rank keeps receiving under bug B1.

    Rank r's top ghost row is rank (r-1)%R's t=0 last row, its bottom ghost
    row is rank (r+1)%R's t=0 first row (ring neighbor ids as in
    gol-main.c:86-87).  Shapes: ([R, W], [R, W]).
    """
    blocks = split_ranks(global_board, num_ranks)
    top0 = jnp.roll(blocks[:, -1, :], 1, axis=0)
    bottom0 = jnp.roll(blocks[:, 0, :], -1, axis=0)
    return top0, bottom0


@functools.partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0,))
def evolve_stale_with_halos(
    global_board: jax.Array,
    top0: jax.Array,
    bottom0: jax.Array,
    num_ranks: int,
    steps: int,
) -> jax.Array:
    """Reference-compat evolution given explicitly frozen halos.

    Split out from :func:`evolve_stale_t0` so chunked/checkpointed/resumed
    runs keep the *original* t=0 halos instead of re-freezing from the
    current board (which would silently change the semantics mid-run).
    """
    blocks = split_ranks(global_board, num_ranks)  # [R, S, W]
    step_all = jax.vmap(stencil.step_halo_rows)
    out = lax.fori_loop(0, steps, lambda _, b: step_all(b, top0, bottom0), blocks)
    return out.reshape(global_board.shape)


def evolve_stale_t0(global_board: jax.Array, num_ranks: int, steps: int) -> jax.Array:
    """Reference-compat (bug B1) semantics, halos frozen from this board."""
    top0, bottom0 = frozen_halos(global_board, num_ranks)
    return evolve_stale_with_halos(global_board, top0, bottom0, num_ranks, steps)


def evolve(
    global_board: jax.Array, steps: int, num_ranks: int = 1, halo_mode: str = "fresh"
) -> jax.Array:
    """Dispatch on halo semantics. ``num_ranks`` only matters for stale_t0."""
    if halo_mode == "fresh":
        return evolve_fresh(global_board, steps)
    if halo_mode == "stale_t0":
        return evolve_stale_t0(global_board, num_ranks, steps)
    raise ValueError(f"unknown halo_mode {halo_mode!r}; expected one of {HALO_MODES}")
