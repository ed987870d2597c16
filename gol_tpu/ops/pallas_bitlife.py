"""Pallas TPU kernel over the bit-packed board: fused carry-save Life.

The top perf tier, composing the two fast paths:

- the **bit-packed** representation of :mod:`gol_tpu.ops.bitlife` (32
  cells/uint32 word, ~22 bitwise VPU ops per word per generation), and
- the **explicit VMEM tiling** of :mod:`gol_tpu.ops.pallas_step` (HBM-
  resident board, DMA'd row tiles with wrap halo rows).

The XLA lowering of the pure-jnp packed step materializes the bit-plane
temporaries between fusions, so it runs far below both VPU and HBM peak.
Here the entire adder tree + rule runs fused over one VMEM tile, and
generations are **temporally blocked**: each kernel launch loads its tile
with a k-deep halo pad and evolves k generations in VMEM (the exact rows
shrinking one row per side per step inside a window that stays put), so
k generations cost one HBM round trip and one launch instead of k.  The
window DMAs are **double-buffered across grid steps** (tile i+1's three
mod-H fetches issued into a second scratch slot before tile i's adder
tree): best-of-8 samples at
16384²×1024 measure 8.96/9.77e11 cell-updates/s vs 8.20/8.69e11 for the
serial-DMA form — ~10% from hiding the input fetch under the VPU work.
Earlier same-session sweep (k=16, tile=256, serial DMA): ~8.6e11 vs
~7.3e11 for the k=1 kernel (+17%); the kernel is VPU-bound (~22 bitwise
ops per 32-cell word), which is why deeper blocking saturates — the
recomputed halo bands add ~2k/tile extra compute.

**Every generation steps the whole aligned window in place**, the rows
above and below coming from sublane rolls (:func:`_one_generation`).
Slicing a shrinking window at odd row offsets instead made Mosaic
relayout every misaligned value: the post-finalize LLO of one grid step
at tile 256, k 8 counted 58,588 VALU ops (16,412 of them selects) and
14,704 sublane rotates; the aligned window with the count-of-8 B3/S23
tail counts 36,992 and 4,352 (``scripts/kernel_llo_tally.py``).  An
earlier fully VMEM-resident variant that rolled the whole board's height
measured 3× *slower* per cell; that was the whole-board kernel, not rolls
over one tile's window.

Mosaic notes: compute is int32 (bit-identical to uint32 for the bitwise
adder ops — the adder algebra itself is reused from
``bitlife._full_add``); logical right shifts are
``lax.shift_right_logical`` (Mosaic's ``arith.shrui``); the word-ring
column wrap (gol-with-cuda.cu:210-211) is a ``pltpu.roll`` along lanes,
carry bits crossing words via shifts exactly as in ``bitlife._west_east``.
Row wrap is handled at DMA time with mod-H aligned halo fetches
(:func:`gol_tpu.ops.pallas_common.tile_halo_copies` descriptors, started
and waited under the double-buffer protocol in :func:`_kernel` — the
wait must reconstruct the start's descriptors identically).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gol_tpu.ops import bitlife, pallas_common
from gol_tpu.ops.pallas_common import (
    load_window_double_buffered,
    pick_tile as _pick,
    tile_halo_copies,
    validate_tile,
)

_ALIGN = 8  # TPU tiling for 32-bit data is (8, 128): 8-row DMA alignment
_LANE = 128  # Mosaic lane tiling for 32-bit data: packed width granularity
# ~12 live int32 [tile, nw] temporaries across the adder tree, plus the
# second scratch slot both double-buffered kernels carry (~1.1 more rows
# per body row at the torus kernel's pad=16).
_BYTES_PER_ROW = 57


def pick_tile(height: int, packed_width: int, hint: int) -> int:
    """Largest divisor of ``height`` <= hint whose working set fits VMEM."""
    return _pick(height, packed_width, hint, _ALIGN, _BYTES_PER_ROW)


def fold_factor(packed_width: int) -> int:
    """Smallest row-fold ``f`` making ``f * packed_width`` fill whole
    128-lane tiles.

    1 when the width already fills them.  A shard too narrow for the lane
    tiling (BASELINE config 3 on a 16×16 mesh: 1024-cell = 32-word shards)
    is evolved in a folded ``[h/f, f*nw]`` layout — ``f`` row groups side
    by side in lanes — by :func:`gol_tpu.parallel.packed.
    compiled_evolve_packed_pallas`; the kernel's group-local rolls
    (``_one_generation(groups=f)``) keep the fold exact, so only
    column-*sharded* meshes need their usual edge repair (folded to one
    column pair per group).
    """
    return _LANE // math.gcd(packed_width, _LANE)


def fold_feasible(
    shard_h: int, fold: int, overlap: bool, depth: int
) -> bool:
    """Geometric feasibility of evolving a fold-``f`` narrow shard.

    The ONE predicate behind the engine's trace-time check
    (``packed.local``), the runtime's up-front validation
    (``GolRuntime.__post_init__``), and the auto-resolution gate
    (``GolRuntime._resolve_auto``) — shared so the three sites cannot
    drift: the folded layout needs shard height divisible by
    ``fold * _ALIGN`` (every group an aligned row block), and overlap
    mode additionally needs the *folded* height to keep one aligned
    interior tile clear of both exchanged bands.
    """
    return shard_h % (fold * _ALIGN) == 0 and (
        not overlap or shard_h // fold >= 2 * depth + _ALIGN
    )


def _one_generation(ext: jax.Array, rule=None, groups: int = 1) -> jax.Array:
    """One packed generation over a whole row window, same shape out.

    Per-row 3-cell horizontal sums once per row (bit planes), column wrap
    via a lane roll with carry bits crossing words by shifts.  The rows
    above and below come from sublane rolls of those sums, so every load,
    store and elementwise op stays (8, 128)-aligned when the window is a
    whole number of 8-row tiles.  The window's first and last rows read
    the opposite edge through the roll and come out wrong: after ``j``
    generations the outer ``j`` rows on each side are garbage, which the
    callers keep inside a ghost band at least as deep as the generations
    they step.

    ``rule=None`` runs the hard-wired B3/S23 tail on the count of the 8
    neighbours: the horizontal full adder is ordered so its first half
    adder is the middle row's neighbour-only sum, and a count of 8 reads
    0 in both planes the tail tests.  A ``Rule2D`` runs the generic plane
    matcher on the count-of-9 with the +1 survive identity (see
    :func:`gol_tpu.ops.rules.step_rule_packed`).

    ``groups > 1`` is the lane-folded narrow-shard layout (``groups`` row
    groups side by side in lanes, :func:`gol_tpu.parallel.packed.
    fold_rows`): the word ring becomes **group-local** — each group's edge
    word takes its carry from its *own* group's opposite edge (two masked
    rolls), so the fold introduces no seam wrongness at all and the
    row-sharded engine needs no repair.  Cost: 2 extra rolls + 2 selects
    per row per generation (~13% on the 30-op generation).
    """
    nw = ext.shape[1]
    if groups == 1:
        prev_word = pltpu.roll(ext, 1, axis=1)
        next_word = pltpu.roll(ext, nw - 1, axis=1)  # roll by -1
    else:
        gw = nw // groups
        # Masks via in-kernel iota (pallas_call forbids captured
        # constants); Mosaic CSEs the repeats across the unrolled k loop.
        lane = lax.rem(lax.broadcasted_iota(jnp.int32, (1, nw), 1), gw)
        first = lane == 0
        last = lane == gw - 1
        prev_word = jnp.where(
            first,
            # group-local wrap: lane g*gw reads its own group's last word
            pltpu.roll(ext, (nw - gw + 1) % nw, axis=1),
            pltpu.roll(ext, 1, axis=1),
        )
        next_word = jnp.where(
            last,
            pltpu.roll(ext, gw - 1, axis=1),
            pltpu.roll(ext, nw - 1, axis=1),
        )
    west = (ext << 1) | lax.shift_right_logical(prev_word, 31)
    east = lax.shift_right_logical(ext, 1) | (next_word << 31)
    h0 = west ^ east
    h1 = west & east
    s0 = h0 ^ ext
    s1 = h1 | (ext & h0)
    rows = ext.shape[0]
    sa = (pltpu.roll(s0, 1, axis=0), pltpu.roll(s1, 1, axis=0))  # above
    sb = (pltpu.roll(s0, rows - 1, axis=0), pltpu.roll(s1, rows - 1, axis=0))
    if rule is None:
        # above + below + middle neighbours: count = l0 + 2*a1 + 4*a2 (+8)
        l0, c0 = bitlife._full_add(sa[0], sb[0], h0)
        u, v = bitlife._full_add(sa[1], sb[1], h1)
        a1 = u ^ c0
        a2 = v ^ (u & c0)
        return (l0 | ext) & a1 & ~a2  # a count of 3, or of 2 and alive
    from gol_tpu.ops.rules import _rule_from_count9

    return _rule_from_count9(
        ext, bitlife._sum3_2bit(sa, (s0, s1), sb), rule
    )


def _kernel(
    packed_hbm, out_ref, scratch, sems, *, tile: int, height: int, k: int,
    pad: int, rule=None,
):
    """k torus generations per VMEM residency (temporal blocking).

    The tile is loaded with a ``pad``-deep halo (``pad >= k``, a multiple
    of 8) on each side; every generation evolves the whole aligned
    ``tile + 2*pad`` window in place, and the wrong rows the window's
    edges make grow inward one row per generation, so after k steps they
    stay inside the pad and the body tile is exact.  Neighboring tiles
    recompute the overlapping halo bands independently — the in-kernel
    analog of the sharded engines' ``--halo-depth`` temporal blocking,
    trading O(k²) duplicated edge rows for k× fewer HBM round trips and
    kernel launches.

    Like :func:`_kernel_ext`, the three window DMAs are double-buffered
    across grid steps: tile ``i+1``'s mod-H fetches are issued into the
    other scratch slot before tile ``i``'s adder tree runs.
    """
    i = pl.program_id(0)
    nt = pl.num_programs(0)
    slot = jax.lax.rem(i, 2)

    def copies(j, s):
        return tile_halo_copies(
            packed_hbm, scratch.at[s], sems.at[s], j,
            tile=tile, height=height, align=_ALIGN, pad=pad,
        )

    load_window_double_buffered(copies, i, i + 1, slot, i == 0, i + 1 < nt)
    for _ in range(k):
        scratch[slot] = _one_generation(scratch[slot], rule)
    out_ref[:] = scratch[slot, pad : pad + tile]


def multi_step_pallas_packed(
    packed_i32: jax.Array, tile: int, k: int, rule=None
) -> jax.Array:
    """k fused torus generations on an int32-bitcast packed board [H, W/32].

    ``rule`` (a :class:`gol_tpu.ops.rules.Rule2D`, hashable) switches the
    kernel tail to the generic plane matcher; None keeps hard-wired B3/S23.
    """
    height, nw = packed_i32.shape
    validate_tile(height, tile, _ALIGN)
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    pad = -(-k // _ALIGN) * _ALIGN
    if pad > tile:
        raise ValueError(
            f"temporal block depth {k} needs halo pad {pad} <= tile {tile}"
        )
    grid = height // tile
    return pl.pallas_call(
        functools.partial(
            _kernel, tile=tile, height=height, k=k, pad=pad, rule=rule
        ),
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (tile, nw), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct(packed_i32.shape, packed_i32.dtype),
        scratch_shapes=[
            # Two slots × (3 DMAs each): tile i computes from slot i%2
            # while tile i+1's mod-H window lands in the other.
            pltpu.VMEM((2, tile + 2 * pad, nw), packed_i32.dtype),
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
        interpret=pallas_common.interpret(),
    )(packed_i32)


def step_pallas_packed(packed_i32: jax.Array, tile: int) -> jax.Array:
    """One torus generation on an int32-bitcast packed board [H, W/32]."""
    return multi_step_pallas_packed(packed_i32, tile, 1)


def _kernel_ext(*refs, tile: int, k: int, rule=None, groups: int = 1):
    """k generations of one tile of a halo-extended (no-wrap) board.

    The input already carries k ghost rows on each side (a sharded
    engine's ppermute exchange materialized them), so the window for tile
    ``i`` is the contiguous rows ``[i*tile, i*tile + tile + 2k)`` of the
    extended array — one aligned DMA, no mod-H arithmetic.  The DMA is
    **double-buffered across grid steps**: tile ``i+1``'s window is
    issued into the other scratch slot before tile ``i``'s adder tree
    runs, so the input fetch (~0.5 MB/tile at the 16384² shape) rides
    under the VPU work instead of serializing ahead of it (the output
    store is already pipelined by pallas_call's out_specs machinery).

    With an ``edges`` input (the 2-D-mesh sharded engine), the caller's
    pre-computed exact edge word-columns overwrite lanes ``0`` and
    ``nw-1`` during the same output store — the kernel's local column
    wrap is wrong in those words' outer k bits, and merging the fix here
    costs two masked lane stores instead of a separate full-lane-tile
    read-modify-write scatter pass over the output in HBM.
    """
    if len(refs) == 4:
        ext_hbm, out_ref, scratch, sems = refs
        edges_ref = None
    else:
        ext_hbm, edges_ref, out_ref, scratch, sems = refs
    i = pl.program_id(0)
    nt = pl.num_programs(0)
    slot = jax.lax.rem(i, 2)

    def copies(j, s):
        start = pl.multiple_of(j * tile, _ALIGN)
        return (
            pltpu.make_async_copy(
                ext_hbm.at[pl.ds(start, tile + 2 * k)],
                scratch.at[s],
                sems.at[s],
            ),
        )

    load_window_double_buffered(copies, i, i + 1, slot, i == 0, i + 1 < nt)
    _evolve_window_and_store(
        scratch, slot, out_ref, edges_ref, tile, k, rule, groups
    )


def multi_step_pallas_packed_ext(
    ext_i32: jax.Array, tile: int, k: int, rule=None, edges_i32=None,
    groups: int = 1,
) -> jax.Array:
    """k fused generations on a k-deep row-halo-extended packed board.

    ``ext_i32[h + 2k, W/32]``: rows ``[0, k)`` and ``[h+k, h+2k)`` are
    ghost rows from the ring neighbors (fresh, by construction — the
    sharded engines build them with ``halo_extend`` inside the same traced
    program).  Columns wrap locally, so this is the 1-D row-decomposition
    kernel.  ``k`` must be a multiple of the DMA row alignment so every
    tile window stays aligned.  Returns the updated interior ``[h, W/32]``.

    ``edges_i32[h, 2]`` (optional, the 2-D-mesh path) holds the exact
    post-step left/right edge word-columns; they replace lanes 0 and nw-1
    of the output inside the kernel (see :func:`_kernel_ext`).
    """
    if k < 1 or k % _ALIGN:
        raise ValueError(
            f"extended kernel needs k to be a positive multiple of "
            f"{_ALIGN}, got {k}"
        )
    height = ext_i32.shape[0] - 2 * k
    nw = ext_i32.shape[1]
    validate_tile(height, tile, _ALIGN)
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    operands = [ext_i32]
    _validate_groups(groups, nw)
    if edges_i32 is not None:
        _validate_edges(edges_i32, height, nw, groups)
        in_specs.append(
            pl.BlockSpec(
                (tile, edges_i32.shape[1]),
                lambda i: (i, 0),
                memory_space=pltpu.VMEM,
            )
        )
        operands.append(edges_i32)
    return pl.pallas_call(
        functools.partial(_kernel_ext, tile=tile, k=k, rule=rule, groups=groups),
        grid=(height // tile,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (tile, nw), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((height, nw), ext_i32.dtype),
        scratch_shapes=[
            # Two slots: tile i computes from slot i%2 while tile i+1's
            # window lands in the other (see _kernel_ext).
            pltpu.VMEM((2, tile + 2 * k, nw), ext_i32.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=pallas_common.interpret(),
    )(*operands)


def _evolve_window_and_store(
    scratch, slot, out_ref, edges_ref, tile: int, k: int, rule,
    groups: int = 1,
):
    """The ext kernels' shared compute tail: k in-place generations over
    the slot's whole ``tile + 2k`` window (its k ghost rows a side hold
    the rows :func:`_one_generation` gets wrong), body store, and the
    optional exact-edge-word overwrite (see
    :func:`multi_step_pallas_packed_ext`)."""
    for _ in range(k):
        scratch[slot] = _one_generation(scratch[slot], rule, groups)
    out_ref[:] = scratch[slot, k : k + tile]
    if edges_ref is not None:
        # One (left, right) exact column pair per lane-fold row group —
        # [tile, 2] unfolded, [tile, 2f] folded (group g's pair at columns
        # 2g, 2g+1; its words at lanes g*gw and (g+1)*gw - 1).
        nw = out_ref.shape[1]
        groups = edges_ref.shape[1] // 2
        gw = nw // groups
        for g in range(groups):
            out_ref[:, g * gw : g * gw + 1] = edges_ref[:, 2 * g : 2 * g + 1]
            out_ref[:, (g + 1) * gw - 1 : (g + 1) * gw] = edges_ref[
                :, 2 * g + 1 : 2 * g + 2
            ]


def _validate_groups(groups: int, nw: int) -> None:
    if groups < 1 or nw % groups:
        raise ValueError(
            f"groups ({groups}) must be >= 1 and divide the packed "
            f"width {nw}"
        )


def _validate_edges(edges, height: int, nw: int, groups: int) -> None:
    """Edges operand contract shared by the ext and banded kernels: one
    (left, right) exact column pair per row group, >= 2 words per group
    (so the two stores never collide)."""
    if edges.shape != (height, 2 * groups):
        raise ValueError(
            f"edges must be [height, 2*groups] = {(height, 2 * groups)}, "
            f"got {edges.shape}"
        )
    if nw // groups < 2:
        raise ValueError(
            f"edge repair needs >= 2 packed words per row group, got "
            f"{nw // groups}"
        )


def _kernel_ext_bands(*refs, tile: int, k: int, rule=None, groups: int = 1):
    """k generations of one tile, ghost band as a separate operand.

    Same compute as :func:`_kernel_ext`, but the k-row ghost bands arrive
    as their own ``[2k, nw]`` operand instead of pre-concatenated onto
    the block — so the sharded engine never materializes the
    ``[h+2k, nw]`` extended array (a full-board HBM copy per chunk, ~1/9
    of the chunk's traffic at k=8).  Each tile's window is assembled in
    VMEM from three fixed-size segments: a k-row top segment (the band's
    top half for tile 0, else block rows), the tile body, and a k-row
    bottom segment (block rows, or the band's bottom half for the last
    tile).  Segment source is resolved by ``pl.when`` pairs whose wait
    mirrors the start, and the whole plan is double-buffered across grid
    steps like the other kernels.
    """
    if len(refs) == 5:
        blk_hbm, bands_hbm, out_ref, scratch, sems = refs
        edges_ref = None
    else:
        blk_hbm, bands_hbm, edges_ref, out_ref, scratch, sems = refs
    i = pl.program_id(0)
    nt = pl.num_programs(0)
    slot = jax.lax.rem(i, 2)

    def segs(j, s):
        """(predicate, descriptor) pairs for window j into slot s; the
        body descriptor's predicate is None (unconditional)."""
        base = pl.multiple_of(j * tile, _ALIGN)
        # Clamped so the never-started branch's descriptor stays in
        # bounds (the clamps are no-ops whenever the branch does start).
        top_blk = pl.multiple_of(jnp.maximum(base - k, 0), _ALIGN)
        bot_blk = pl.multiple_of(
            jnp.minimum(base + tile, blk_hbm.shape[0] - k), _ALIGN
        )
        mk = pltpu.make_async_copy
        return (
            (
                j == 0,
                mk(
                    bands_hbm.at[pl.ds(0, k)],
                    scratch.at[s, pl.ds(0, k)],
                    sems.at[s, 0],
                ),
            ),
            (
                j > 0,
                mk(
                    blk_hbm.at[pl.ds(top_blk, k)],
                    scratch.at[s, pl.ds(0, k)],
                    sems.at[s, 0],
                ),
            ),
            (
                None,
                mk(
                    blk_hbm.at[pl.ds(base, tile)],
                    scratch.at[s, pl.ds(k, tile)],
                    sems.at[s, 1],
                ),
            ),
            (
                j == nt - 1,
                mk(
                    bands_hbm.at[pl.ds(k, k)],
                    scratch.at[s, pl.ds(k + tile, k)],
                    sems.at[s, 2],
                ),
            ),
            (
                j < nt - 1,
                mk(
                    blk_hbm.at[pl.ds(bot_blk, k)],
                    scratch.at[s, pl.ds(k + tile, k)],
                    sems.at[s, 2],
                ),
            ),
        )

    def for_each_seg(j, s, action):
        for pred, desc in segs(j, s):
            if pred is None:
                action(desc)
            else:
                @pl.when(pred)
                def _(d=desc):
                    action(d)

    def start_all(j, s):
        for_each_seg(j, s, lambda d: d.start())

    def wait_all(j, s):
        for_each_seg(j, s, lambda d: d.wait())

    @pl.when(i == 0)
    def _():
        start_all(i, slot)

    @pl.when(i + 1 < nt)
    def _():
        start_all(i + 1, 1 - slot)

    wait_all(i, slot)
    _evolve_window_and_store(
        scratch, slot, out_ref, edges_ref, tile, k, rule, groups
    )


def multi_step_pallas_packed_bands(
    blk_i32: jax.Array,
    bands_i32: jax.Array,
    tile: int,
    k: int,
    rule=None,
    edges_i32=None,
    groups: int = 1,
) -> jax.Array:
    """k fused generations of a packed block with a separate ghost band.

    ``blk_i32[h, W/32]`` is the shard's own rows; ``bands_i32[2k, W/32]``
    stacks the k-row top and bottom ghost bands a ring exchange produced
    (fresh, same traced program).  Columns wrap locally; ``edges_i32``
    follows the :func:`multi_step_pallas_packed_ext` contract.  Returns
    the updated ``[h, W/32]``.
    """
    if k < 1 or k % _ALIGN:
        raise ValueError(
            f"banded kernel needs k to be a positive multiple of "
            f"{_ALIGN}, got {k}"
        )
    height, nw = blk_i32.shape
    if bands_i32.shape != (2 * k, nw):
        raise ValueError(
            f"bands must be [2k, nw] = {(2 * k, nw)}, got {bands_i32.shape}"
        )
    validate_tile(height, tile, _ALIGN)
    if tile < k:
        # An interior tile's k-row halo segments come from adjacent block
        # rows in ONE descriptor each; with tile < k the segment would
        # span more than one neighboring tile and the in-bounds clamps
        # would silently fetch the wrong rows.  Callers with tile < k use
        # the pre-extended kernel (multi_step_pallas_packed_ext) instead.
        raise ValueError(
            f"banded kernel needs tile ({tile}) >= band depth k ({k})"
        )
    if height < tile + k:
        # A single-tile block still needs k rows below the body for the
        # bot_blk descriptor's clamped source to stay in bounds; with
        # height == tile that descriptor is never started (j == nt-1) but
        # must still describe valid memory (tile >= k above keeps its
        # clamped start non-negative).
        if height != tile:
            raise ValueError(
                f"banded kernel needs block height {height} >= tile + k"
            )
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [blk_i32, bands_i32]
    _validate_groups(groups, nw)
    if edges_i32 is not None:
        _validate_edges(edges_i32, height, nw, groups)
        in_specs.append(
            pl.BlockSpec(
                (tile, edges_i32.shape[1]),
                lambda i: (i, 0),
                memory_space=pltpu.VMEM,
            )
        )
        operands.append(edges_i32)
    return pl.pallas_call(
        functools.partial(
            _kernel_ext_bands, tile=tile, k=k, rule=rule, groups=groups
        ),
        grid=(height // tile,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (tile, nw), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((height, nw), blk_i32.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, tile + 2 * k, nw), blk_i32.dtype),
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
        interpret=pallas_common.interpret(),
    )(*operands)


# Benchmarked sweet spot on v5e at 16384² (see module docstring): deeper
# blocks win until the recomputed halo bands (~2k²/tile extra rows per k
# steps) eat the launch/HBM savings.  Round 3 re-measured at the
# overhead-amortized x10240 loop length: k=8 at tile 256 runs ~2.5% ahead of
# k=16 (1.87 vs 1.82e12 same-session sweep) — exactly the roofline's
# recompute-factor gap (1.035 vs 1.066); the deeper block's saved
# launches no longer pay once the loop is long enough to amortize them.
# Round 4 negative: tile 512 at k=8 (recompute x1.017) measures ~2.5%
# *behind* tile 256 (1.82 vs 1.87e12, interleaved best-of-5) — the
# larger window loses more to scheduling/DMA than the halved band
# recompute saves, so the cap stays.
_BLOCK = 8
_BLOCK_TILE = 256


def _pick_block(
    steps: int, tile: int, block: int = _BLOCK, align: int = _ALIGN
) -> int:
    """Largest supported temporal depth <= ``block`` for this tile.

    Shared with the 3-D kernel (which passes its own cap and alignment)."""
    k = min(block, steps, tile)
    while k > 1 and -(-k // align) * align > tile:
        k -= 1
    return max(1, k)


def blocking_plan(
    height: int, packed_width: int, steps: int, tile_hint: int
) -> tuple:
    """(tile, k) exactly as :func:`evolve` runs them — shared with the
    roofline attribution (utils/roofline.py) so the reported
    configuration cannot drift from the executed one."""
    cap = min(tile_hint, _BLOCK_TILE) if steps > 1 else tile_hint
    tile = pick_tile(height, packed_width, cap)
    return tile, _pick_block(steps, tile)


@functools.partial(jax.jit, static_argnums=(1, 2, 3), donate_argnums=(0,))
def evolve(
    board: jax.Array, steps: int, tile_hint: int = 512, rule=None
) -> jax.Array:
    """Dense uint8 in/out; pack, evolve fused-packed, unpack — one program.

    Generations run in temporally-blocked groups of up to ``_BLOCK`` per
    kernel launch (full groups first, then one remainder launch), cutting
    kernel launches and HBM round trips ~k-fold.
    """
    nw = bitlife.packed_width(board.shape[1])
    if not pallas_common.interpret() and nw % _LANE != 0:
        raise ValueError(
            "pallas bitpack engine needs the packed width to fill whole "
            f"{_LANE}-lane tiles on TPU: board width must be a multiple of "
            f"{_LANE * bitlife.BITS}, got {board.shape[1]}"
        )
    packed = bitlife.pack(board)
    packed_i32 = lax.bitcast_convert_type(packed, jnp.int32)
    height = packed_i32.shape[0]
    # The blocked path prefers its own (smaller) tile: the k-deep scratch
    # plus temporaries must still fit VMEM.  Single-step runs keep the
    # caller's full hint — no pad, no reason to halve the tile.
    tile, k = blocking_plan(height, nw, steps, tile_hint)
    full, rem = divmod(steps, k)
    packed_i32 = lax.fori_loop(
        0,
        full,
        lambda _, p: multi_step_pallas_packed(p, tile, k, rule),
        packed_i32,
    )
    if rem:
        packed_i32 = multi_step_pallas_packed(packed_i32, tile, rem, rule)
    return bitlife.unpack(lax.bitcast_convert_type(packed_i32, jnp.uint32))
