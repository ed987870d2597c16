"""Mask-gated Pallas grid: early-out row-band programs.

The gated-grid alternative to the worklist engine
(:mod:`gol_tpu.sparse.engine`): the grid still covers the whole packed
board in row bands, but the per-band activity gate rides in as a
**scalar-prefetch operand** (SMEM, available before the body runs) and
an inactive band's program early-outs under ``pl.when`` — it copies its
input block to the output instead of running the ~22-op carry-save
adder tree.  Work skipped is the VPU compute; the band's HBM round trip
still happens (the BlockSpec machinery DMAs every block), which is the
structural tradeoff against the worklist form:

- **worklist** (the runtime's form): O(active) gather/scatter traffic
  *and* compute, but per-generation ``nonzero`` + scatter indexing
  overhead and a static capacity with a dense fallback;
- **gated grid** (this form): O(area) traffic at O(active) compute, no
  capacity cliff, no indexing overhead — the right shape when the
  kernel is VPU-bound (the fused tier is, see ops/pallas_bitlife.py) and
  activity is moderately dense.

Gating granularity is one row *band* of tiles (= ``tile`` board rows):
band i is live iff any tile in mask row i is dilated-active
(:func:`gol_tpu.sparse.mask.band_mask`).  The three shifted input views
(band above / center / below, torus-wrapped block index maps) give the
kernel an aligned 8-row ghost band a side, so an active band next to an
inactive one still reads fresh neighbor rows — the same
one-generation-per-call contract as :func:`gol_tpu.ops.bitlife.step_packed`.

Like every Pallas tier, bit-identity is the contract: interpret mode
(any backend) pins this kernel against the jnp packed step in
tests/test_sparse.py; on TPU the same program compiles via Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gol_tpu.ops import bitlife, pallas_common
from gol_tpu.ops.pallas_bitlife import _ALIGN, _one_generation
from gol_tpu.sparse import mask as mask_mod


def _kernel(mask_ref, above, center, below, out_ref):
    i = pl.program_id(0)

    @pl.when(mask_ref[i] != 0)
    def _():
        # An aligned 8-row ghost band a side: the generation gets only
        # the window's outer row wrong.
        band = center.shape[0]
        ext = jnp.concatenate(
            [above[band - _ALIGN : band], center[:], below[0:_ALIGN]], axis=0
        )
        out_ref[:] = _one_generation(ext)[_ALIGN : _ALIGN + band]

    @pl.when(mask_ref[i] == 0)
    def _():
        out_ref[:] = center[:]


def step_gated_pallas(
    packed_i32: jax.Array, band_active: jax.Array, band: int
) -> jax.Array:
    """One gated torus generation on an int32-bitcast packed board.

    ``band_active`` is int32[H // band]; bands with a zero gate are
    copied through (exact by the dilation invariant — their mask row and
    both neighbors saw no change last generation).
    """
    height, nw = packed_i32.shape
    if band < 1 or height % band or band % _ALIGN:
        raise ValueError(
            f"gated band {band} must divide the height ({height}) and "
            f"the {_ALIGN}-row DMA alignment"
        )
    nbands = height // band
    spec = functools.partial(pl.BlockSpec, (band, nw))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nbands,),
        in_specs=[
            # Index maps under scalar prefetch receive the gate ref too.
            spec(lambda i, m: ((i + nbands - 1) % nbands, 0)),
            spec(lambda i, m: (i, 0)),
            spec(lambda i, m: ((i + 1) % nbands, 0)),
        ],
        out_specs=spec(lambda i, m: (i, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(packed_i32.shape, packed_i32.dtype),
        interpret=pallas_common.interpret(),
    )(band_active, packed_i32, packed_i32, packed_i32)


@functools.partial(jax.jit, static_argnums=(2, 3), donate_argnums=(0, 1))
def evolve_gated_pallas(
    board: jax.Array, changed: jax.Array, steps: int, tile: int
):
    """``steps`` gated generations, dense-in/dense-out, Pallas grid.

    Same ``(board, changed, activity)`` contract as the worklist
    engines; ``fallback_gens`` is always 0 (the gated grid has no
    capacity cliff).  Mask maintenance (dilate + per-tile flip
    reduction) runs as fused jnp over the packed words — O(area/32)
    word traffic per generation, the documented cost of this form.
    ``tile`` is word-quantized like the packed worklist's (a multiple
    of 32, so mask tiles stay square over whole words).
    """
    mask_mod.validate_tile(board.shape[0], board.shape[1], tile, packed=True)
    packed = lax.bitcast_convert_type(bitlife.pack(board), jnp.int32)

    tw = jnp.uint32(changed.shape[1])  # tiles per row band

    def body(_, carry):
        packed, changed, agens, cgens = carry
        active = mask_mod.dilate(changed)
        bands = mask_mod.band_mask(active)
        new = step_gated_pallas(packed, bands, tile)
        changed = mask_mod.tile_any_packed(packed ^ new, tile)
        agens = agens + jnp.sum(active, dtype=jnp.uint32)
        # The grid computes whole live row bands (band granularity).
        cgens = cgens + jnp.sum(bands, dtype=jnp.uint32) * tw
        return new, changed, agens, cgens

    packed, changed, agens, cgens = lax.fori_loop(
        0, steps, body, (packed, changed, jnp.uint32(0), jnp.uint32(0))
    )
    board = bitlife.unpack(lax.bitcast_convert_type(packed, jnp.uint32))
    return board, changed, {
        "active_tile_gens": agens,
        "computed_tile_gens": cgens,
        "fallback_gens": jnp.uint32(0),
    }
