"""Pallas TPU kernel for bit-packed 3-D Life: fused plane adders in VMEM.

The XLA lowering of :mod:`gol_tpu.ops.bitlife3d` materializes the ~15
uint32 bit-plane temporaries between fusions; this kernel fuses the whole
x/h/d adder tree + rule matcher over VMEM-resident plane tiles.

**Layout is the key move.**  A packed volume ``[D, H, W/32]`` has only
``W/32`` words on the minor axis (16 at 512³) — far short of the 128-lane
Mosaic tiling, which would waste 8× of every vector op.  So the kernel
operates on the *transposed* layout ``[D, nw, H]``: lanes are the H axis
(512+, always lane-aligned for real volumes), the x word ring lives on the
sublane axis (carry bits via sublane-adjacent words — cheap slices), and
the plane axis is tiled with DMA'd mod-D halos exactly like the 2-D
kernel's row tiles (:mod:`gol_tpu.ops.pallas_common` plan).  Per
generation: 2 sublane shifts (x carries), 4 lane rolls (h neighbors),
plane slices (d), one fused adder tree, the bit-plane rule matcher — any
totalistic B/S rule, still branchless.

Temporal blocking (k generations per VMEM residency, the
:mod:`~gol_tpu.ops.pallas_bitlife` treatment) is supported but the kernel
is VPU-bound like its 2-D sibling, so gains are small.

At sizes where a whole ``(nw, H)`` word plane no longer fits the scoped-
VMEM window (1024³: 32×1024 words), the plane splits along the *word*
axis instead (:func:`multi_step_pallas_packed3d_wt`): word-chunk windows
ride the untiled leading axis of a ``[nw, D, H]`` layout (any slice
offset legal — no DMA alignment lost), carry one ghost word per side
whose 32-bit light cone supports k <= 32 in-VMEM generations, and keep H
whole so the h wrap stays a lane roll.  x/d wraps are XLA-pre-extended
ghost words/planes, one concat pair per k-generation launch.

Dispatch between the two kernels is by halo-recompute score (the
kernels are VPU-bound, so duplicated ghost compute decides); the
word-tiled kernel's window DMA is double-buffered across plane chunks,
the plane kernel's measured better serial (see :func:`_kernel`).

Older rounds' timings (r2–r4, ``BASELINE.md``; kernels since changed,
not the benchmark's): 7.3e10 cell-updates/s at 512³ via the plane kernel
(XLA packed: 5.9e10), 1.78e11 at 768³ (wt (48, 4)), 2.35e11 at 1024³ (wt
(32, 4)) and, measured together at ×256, 4.8e11 for roll(32/64)
against wt's 3.3e11.  The benchmark's cell ``life3d-1024.soup`` times the kernel
``auto`` picks at 1024³ (roll, tile 64, k 8) through ``VolumeRuntime``;
``PERF.md`` holds its numbers.
"""

from __future__ import annotations

import functools
from typing import FrozenSet

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gol_tpu import telemetry
from gol_tpu.ops import bitlife, bitlife3d, pallas_common
from gol_tpu.ops.life3d import BAYS_4555, Rule3D
from gol_tpu.ops.pallas_bitlife import _pick_block
from gol_tpu.ops.pallas_common import (
    load_tile_with_halo,
    load_window_double_buffered,
    validate_tile,
)

_ALIGN = 8  # plane-axis DMA alignment for 32-bit data
_LANE = 128  # Mosaic lane tiling: H must fill whole lane tiles


def _one_generation(
    ext: jax.Array, birth: FrozenSet[int], survive: FrozenSet[int]
) -> jax.Array:
    """One generation over a plane-extended window ``ext[dp, nw, H]``.

    x wraps on the sublane word ring, h wraps via lane rolls, d consumes
    one plane layer per side (returns ``[dp-2, nw, H]``).
    """
    h = ext.shape[2]
    prev_w = jnp.concatenate([ext[:, -1:], ext[:, :-1]], axis=1)
    next_w = jnp.concatenate([ext[:, 1:], ext[:, :1]], axis=1)
    west = (ext << 1) | lax.shift_right_logical(prev_w, 31)
    east = lax.shift_right_logical(ext, 1) | (next_w << 31)
    s0, s1 = bitlife._full_add(west, ext, east)
    count9 = bitlife._sum3_2bit(
        (pltpu.roll(s0, 1, axis=2), pltpu.roll(s1, 1, axis=2)),
        (s0, s1),
        (pltpu.roll(s0, h - 1, axis=2), pltpu.roll(s1, h - 1, axis=2)),
    )
    count27 = bitlife3d._sum3_planes(
        tuple(p[:-2] for p in count9),
        tuple(p[1:-1] for p in count9),
        tuple(p[2:] for p in count9),
        width=5,
    )
    center = ext[1:-1]
    count26 = bitlife._sub_bit(count27, center)
    born = bitlife._match_counts(count26, birth)
    keep = bitlife._match_counts(count26, survive)
    return (~center & born) | (center & keep)


def _count9_plane(plane: jax.Array, wrap: bool = True):
    """In-plane count-of-9 bit planes for one ``[nw, H]`` word plane.

    The x/h stage of :func:`_one_generation` restricted to a single
    plane: the x word ring on the sublane axis (``wrap=True``: torus
    concats; ``wrap=False``: zero edge carries — word-extended planes
    whose outer ghost words accumulate light-cone garbage, the
    :func:`_one_generation_wt` contract), h neighbors via lane rolls.
    Returns the 4-bit-plane tuple ``_sum3_2bit`` produces.
    """
    h = plane.shape[1]
    if wrap:
        prev_w = jnp.concatenate([plane[-1:], plane[:-1]], axis=0)
        next_w = jnp.concatenate([plane[1:], plane[:1]], axis=0)
    else:
        zero = jnp.zeros_like(plane[:1])
        prev_w = jnp.concatenate([zero, plane[:-1]], axis=0)
        next_w = jnp.concatenate([plane[1:], zero], axis=0)
    west = (plane << 1) | lax.shift_right_logical(prev_w, 31)
    east = lax.shift_right_logical(plane, 1) | (next_w << 31)
    s0, s1 = bitlife._full_add(west, plane, east)
    return bitlife._sum3_2bit(
        (pltpu.roll(s0, 1, axis=1), pltpu.roll(s1, 1, axis=1)),
        (s0, s1),
        (pltpu.roll(s0, h - 1, axis=1), pltpu.roll(s1, h - 1, axis=1)),
    )


def _roll_generations(
    scratch, *, tile, k, pad, birth, survive, read=None, store=None,
    wrap=True,
):
    """The rolling kernels' shared k-generation loop over one window.

    Each generation is a plane-ascending ``fori_loop`` carrying the
    count-of-9 bit planes of the two planes below the write cursor,
    storing each output plane in place as soon as it is complete.
    In-place safety: storing plane ``p`` clobbers only data whose count9
    is already carried; ``center`` (plane ``p``) and the count9 of plane
    ``p+1`` are read through ``read`` BEFORE ``store`` runs.  The valid
    window shrinks one plane per side per generation.  ``read(p)`` /
    ``store(p, out)`` default to plain scratch access; the ghost-word
    kernel passes accessors that assemble ``[ghostL | body | ghostR]``
    planes and split the store — so the tricky invariants live here
    once, whatever the plane layout.
    """
    if read is None:
        read = lambda p: scratch[p]
    if store is None:
        def store(p, out):
            scratch[p] = out

    for j in range(k):
        lo = pad - (k - j)
        hi = pad + tile + (k - j)  # window [lo, hi); outputs [lo+1, hi-1)

        def body(p, carry, _birth=birth, _survive=survive):
            c9_prev, c9_cur = carry[:4], carry[4:]
            c9_next = _count9_plane(read(p + 1), wrap)
            count27 = bitlife3d._sum3_planes(
                c9_prev, c9_cur, c9_next, width=5
            )
            center = read(p)
            count26 = bitlife._sub_bit(count27, center)
            born = bitlife._match_counts(count26, _birth)
            keep = bitlife._match_counts(count26, _survive)
            store(p, (~center & born) | (center & keep))
            return (*c9_cur, *c9_next)

        carry = (
            *_count9_plane(read(lo), wrap),
            *_count9_plane(read(lo + 1), wrap),
        )
        jax.lax.fori_loop(lo + 1, hi - 1, body, carry)


def _kernel_roll(
    vol_hbm, out_ref, scratch, sems, *, tile, depth, k, pad, birth, survive
):
    """Plane-tiled kernel body, rolling per-plane generation (r4).

    Same windowing/DMA as :func:`_kernel`, but each generation runs as a
    plane-ascending ``fori_loop`` carrying the count-of-9 bit planes of
    the two planes below the write cursor, storing each output plane in
    place as soon as it is complete.  Peak VMEM is therefore ONE window
    plus ~a dozen plane-sized temporaries — not the ~9 whole-window live
    arrays the monolithic adder tree holds — so the plane tile can grow
    several-fold and the halo-recompute factor drops toward
    ``(tile + k + 1)/tile`` with NO word-ghost term at all (the r3
    verdict's 3-D ask: the wt kernel's word ghosts taxed 1024³ ×1.5).

    In-place safety: storing plane ``p`` clobbers only data whose count9
    is already carried; ``center`` (plane ``p``) and ``count9`` of plane
    ``p+1`` are read before the store.  Op count per useful word is
    identical to the monolithic kernel — the restructure moves memory,
    not arithmetic.
    """
    load_tile_with_halo(
        vol_hbm, scratch, sems, pl.program_id(0),
        tile=tile, height=depth, align=_ALIGN, pad=pad,
    )
    _roll_generations(
        scratch, tile=tile, k=k, pad=pad, birth=birth, survive=survive
    )
    # Manual output DMA instead of an out_specs VMEM block: pallas_call
    # double-buffers out blocks for its store pipeline, which at big
    # plane tiles costs 2*tile plane-buffers of VMEM — more than the
    # whole halo window.  The explicit copy keeps peak VMEM at ONE
    # window; the serial wait stalls only for an HBM write that is tiny
    # next to the k-generation VPU work.
    i = pl.program_id(0)
    store = pltpu.make_async_copy(
        scratch.at[pl.ds(pad, tile)],
        out_ref.at[pl.ds(pl.multiple_of(i * tile, _ALIGN), tile)],
        sems.at[3],
    )
    store.start()
    store.wait()


def multi_step_pallas_packed3d_roll(
    packed_t: jax.Array, tile: int, k: int, rule: Rule3D = BAYS_4555
) -> jax.Array:
    """k fused rolling-plane generations on a transposed volume [D, nw, H].

    The big-window plane kernel: identical contract to
    :func:`multi_step_pallas_packed3d`, peak VMEM ~1 window (see
    :func:`_kernel_roll`), so it fits plane tiles the monolithic kernel
    cannot — at 1024³ a whole-(nw,H)-plane window of 64+ planes.
    """
    depth, nw, h = packed_t.shape
    validate_tile(depth, tile, _ALIGN)
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    pad = -(-k // _ALIGN) * _ALIGN
    if pad > tile:
        raise ValueError(
            f"temporal block depth {k} needs halo pad {pad} <= tile {tile}"
        )
    return pl.pallas_call(
        functools.partial(
            _kernel_roll,
            tile=tile,
            depth=depth,
            k=k,
            pad=pad,
            birth=rule.birth,
            survive=rule.survive,
        ),
        grid=(depth // tile,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(packed_t.shape, packed_t.dtype),
        scratch_shapes=[
            pltpu.VMEM((tile + 2 * pad, nw, h), packed_t.dtype),
            pltpu.SemaphoreType.DMA((4,)),
        ],
        interpret=pallas_common.interpret(),
    )(packed_t)


def _kernel_roll_ext(
    ext_hbm, out_ref, scratch, sems, *, tile, k, pad, birth, survive
):
    """Rolling-plane kernel on a band-extended shard (sharded engine form).

    ``ext_hbm[band + 2*pad, nw, lanes]``: ghost planes from the ring band
    exchange on the leading axis (windows are contiguous aligned slices —
    one DMA, no mod arithmetic).  The x axis is the shard's FULL width
    (the sharded engine only takes this kernel on x-unsharded meshes), so
    x wraps locally exactly as in :func:`_kernel_roll`.  A word-extended
    variant was a measured dead end: ghost word columns put ``nw + 2``
    on the sublane axis, whose tiled HBM layout Mosaic cannot slice at
    unaligned extents (r4, memref_slice failure at 34-of-40 sublanes).
    """
    i = pl.program_id(0)
    cp = pltpu.make_async_copy(
        ext_hbm.at[pl.ds(pl.multiple_of(i * tile, _ALIGN), tile + 2 * pad)],
        scratch.at[:],
        sems.at[0],
    )
    cp.start()
    cp.wait()
    _roll_generations(
        scratch, tile=tile, k=k, pad=pad, birth=birth, survive=survive
    )
    store = pltpu.make_async_copy(
        scratch.at[pl.ds(pad, tile)],
        out_ref.at[pl.ds(pl.multiple_of(i * tile, _ALIGN), tile)],
        sems.at[1],
    )
    store.start()
    store.wait()


def multi_step_pallas_packed3d_roll_ext(
    ext: jax.Array, tile: int, k: int, rule: Rule3D = BAYS_4555
) -> jax.Array:
    """k rolling generations on a band-extended shard volume.

    ``ext[band + 2*pad, nw, lanes]`` carries ring-ghost planes on the
    leading axis — the sharded 3-D engine's band-exchange product in the
    plane-leading layout, for meshes whose x axis is unsharded (the
    shard's local x wrap IS the torus).  Returns ``[band, nw, lanes]``.
    """
    pad = -(-k // _ALIGN) * _ALIGN
    band = ext.shape[0] - 2 * pad
    validate_tile(band, tile, _ALIGN)
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if pad > tile:
        raise ValueError(
            f"temporal block depth {k} needs halo pad {pad} <= tile {tile}"
        )
    return pl.pallas_call(
        functools.partial(
            _kernel_roll_ext,
            tile=tile,
            k=k,
            pad=pad,
            birth=rule.birth,
            survive=rule.survive,
        ),
        grid=(band // tile,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(
            (band, ext.shape[1], ext.shape[2]), ext.dtype
        ),
        scratch_shapes=[
            pltpu.VMEM(
                (tile + 2 * pad, ext.shape[1], ext.shape[2]), ext.dtype
            ),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=pallas_common.interpret(),
    )(ext)


def _kernel_roll_ext_g(
    ext_hbm, gh_hbm, out_ref, scratch, gscratch, sems, *, tile, k, pad,
    birth, survive,
):
    """Rolling-plane kernel with ghost word columns — the x-sharded form.

    The r4 fix for Mosaic's tiled-HBM constraint (a ``[*, nw+2, lanes]``
    array cannot be sliced at 34-of-40 sublanes): the two ghost word
    columns ride a SEPARATE ``[band+2*pad, 8, lanes]`` operand — slots 0
    (left) and 1 (right) real, 6 dead sublanes for alignment, costing
    DMA bytes but no compute.  Each rolling step concatenates
    ``[ghostL | body | ghostR]`` per plane (``nw+2`` words), evolves it
    with zero outer carries, and splits the store back — so the compute
    tax over the body is ``(nw+2)/nw``, replacing the wt kernel's
    ``(tw+2)/tw`` at tw=4 (×1.06 vs ×1.5 at a 32-word shard).
    """
    i = pl.program_id(0)
    base = pl.multiple_of(i * tile, _ALIGN)
    cp = pltpu.make_async_copy(
        ext_hbm.at[pl.ds(base, tile + 2 * pad)], scratch.at[:], sems.at[0]
    )
    gcp = pltpu.make_async_copy(
        gh_hbm.at[pl.ds(base, tile + 2 * pad)], gscratch.at[:], sems.at[1]
    )
    cp.start()
    gcp.start()
    cp.wait()
    gcp.wait()

    def read(p):
        return jnp.concatenate(
            [gscratch[p, 0:1], scratch[p], gscratch[p, 1:2]], axis=0
        )

    def split_store(p, out):
        scratch[p] = out[1:-1]
        gscratch[p, 0:1] = out[0:1]
        gscratch[p, 1:2] = out[-1:]

    _roll_generations(
        scratch, tile=tile, k=k, pad=pad, birth=birth, survive=survive,
        read=read, store=split_store, wrap=False,
    )
    store = pltpu.make_async_copy(
        scratch.at[pl.ds(pad, tile)],
        out_ref.at[pl.ds(base, tile)],
        sems.at[2],
    )
    store.start()
    store.wait()


GHOST_SLOTS = 8  # sublane-aligned ghost operand width (2 real + 6 dead)


def multi_step_pallas_packed3d_roll_ext_g(
    ext: jax.Array,
    ghosts: jax.Array,
    tile: int,
    k: int,
    rule: Rule3D = BAYS_4555,
) -> jax.Array:
    """k rolling generations of a band- AND word-extended shard.

    ``ext[band + 2*pad, nw, lanes]`` is the shard's own words behind the
    ring band exchange; ``ghosts[band + 2*pad, 8, lanes]`` carries the
    exchanged ghost word columns in sublane slots 0 (left) / 1 (right)
    (slots 2-7 ignored).  Returns the body ``[band, nw, lanes]`` — the
    evolved ghosts are NOT returned (the next chunk's exchange rebuilds
    them from the neighbors' bodies).  ``k <= 32``: one ghost word's bit
    light cone.
    """
    pad = -(-k // _ALIGN) * _ALIGN
    band = ext.shape[0] - 2 * pad
    validate_tile(band, tile, _ALIGN)
    if k < 1 or k > bitlife.BITS:
        raise ValueError(
            f"ghost-word rolling kernel supports 1 <= k <= {bitlife.BITS}, "
            f"got {k}"
        )
    if pad > tile:
        raise ValueError(
            f"temporal block depth {k} needs halo pad {pad} <= tile {tile}"
        )
    if ghosts.shape != (ext.shape[0], GHOST_SLOTS, ext.shape[2]):
        raise ValueError(
            f"ghosts must be {(ext.shape[0], GHOST_SLOTS, ext.shape[2])}, "
            f"got {ghosts.shape}"
        )
    return pl.pallas_call(
        functools.partial(
            _kernel_roll_ext_g,
            tile=tile,
            k=k,
            pad=pad,
            birth=rule.birth,
            survive=rule.survive,
        ),
        grid=(band // tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(
            (band, ext.shape[1], ext.shape[2]), ext.dtype
        ),
        scratch_shapes=[
            pltpu.VMEM(
                (tile + 2 * pad, ext.shape[1], ext.shape[2]), ext.dtype
            ),
            pltpu.VMEM(
                (tile + 2 * pad, GHOST_SLOTS, ext.shape[2]), ext.dtype
            ),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        interpret=pallas_common.interpret(),
    )(ext, ghosts)


def _kernel(
    vol_hbm, out_ref, scratch, sems, *, tile, depth, k, pad, birth, survive
):
    """Plane-tiled kernel body, serial window DMA.

    Measured negative result (v5e, 512³×128, same session): the
    cross-grid-step double-buffer that wins ~10% on the 2-D torus kernel
    *loses* ~9% here (6.6/6.7e10 vs 7.2/7.3e10 serial) — the dynamic
    scratch-slot indexing taxes the much larger 3-D windows more than the
    hidden fetch saves — so this kernel keeps the serial loader.  The
    word-tiled kernel (:func:`_kernel_wt`), whose windows are narrower,
    keeps its double-buffer (+5-11% at 768³/1024³).
    """
    load_tile_with_halo(
        vol_hbm, scratch, sems, pl.program_id(0),
        tile=tile, height=depth, align=_ALIGN, pad=pad,
    )
    for j in range(k):
        lo = pad - (k - j)
        hi = pad + tile + (k - j)
        scratch[lo + 1 : hi - 1] = _one_generation(
            scratch[lo:hi], birth, survive
        )
    out_ref[:] = scratch[pad : pad + tile]


def multi_step_pallas_packed3d(
    packed_t: jax.Array, tile: int, k: int, rule: Rule3D = BAYS_4555
) -> jax.Array:
    """k fused torus generations on a transposed packed volume [D, nw, H]."""
    depth, nw, h = packed_t.shape
    validate_tile(depth, tile, _ALIGN)
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    pad = -(-k // _ALIGN) * _ALIGN
    if pad > tile:
        raise ValueError(
            f"temporal block depth {k} needs halo pad {pad} <= tile {tile}"
        )
    return pl.pallas_call(
        functools.partial(
            _kernel,
            tile=tile,
            depth=depth,
            k=k,
            pad=pad,
            birth=rule.birth,
            survive=rule.survive,
        ),
        grid=(depth // tile,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (tile, nw, h), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct(packed_t.shape, packed_t.dtype),
        scratch_shapes=[
            pltpu.VMEM((tile + 2 * pad, nw, h), packed_t.dtype),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        interpret=pallas_common.interpret(),
    )(packed_t)


def _one_generation_wt(
    ext: jax.Array, birth: FrozenSet[int], survive: FrozenSet[int]
) -> jax.Array:
    """One generation over a word-leading window ``ext[tw+2, dp, H]``.

    The word-tiled layout's twin of :func:`_one_generation`: the x word
    ring lives on the *leading* (untiled) axis with zero-filled edge
    carries — the window's outer ghost words accumulate garbage one bit
    per generation (stencil light cone), which the caller's k <= 32 cap
    keeps inside the single ghost word per side.  d neighbors are sublane
    slices (shrink one plane layer per side), h wraps via lane rolls.
    Returns ``[tw+2, dp-2, H]``.
    """
    h = ext.shape[2]
    zero = jnp.zeros_like(ext[:1])
    prev_w = jnp.concatenate([zero, ext[:-1]], axis=0)
    next_w = jnp.concatenate([ext[1:], zero], axis=0)
    west = (ext << 1) | lax.shift_right_logical(prev_w, 31)
    east = lax.shift_right_logical(ext, 1) | (next_w << 31)
    s0, s1 = bitlife._full_add(west, ext, east)
    count9 = bitlife._sum3_2bit(
        (pltpu.roll(s0, 1, axis=2), pltpu.roll(s1, 1, axis=2)),
        (s0, s1),
        (pltpu.roll(s0, h - 1, axis=2), pltpu.roll(s1, h - 1, axis=2)),
    )
    count27 = bitlife3d._sum3_planes(
        tuple(p[:, :-2] for p in count9),
        tuple(p[:, 1:-1] for p in count9),
        tuple(p[:, 2:] for p in count9),
        width=5,
    )
    center = ext[:, 1:-1]
    count26 = bitlife._sub_bit(count27, center)
    born = bitlife._match_counts(count26, birth)
    keep = bitlife._match_counts(count26, survive)
    return (~center & born) | (center & keep)


def _kernel_wt(
    ext_hbm, out_ref, scratch, sems, *, tile_d, tile_w, k, pad, birth,
    survive,
):
    """Word-tiled kernel body: window = word chunk × plane chunk × full H.

    ``ext_hbm[nw+2, D+2*pad, H]`` is the XLA-pre-extended volume (x wrap
    words on the leading axis, d wrap planes on the sublane axis), so both
    window slices are plain in-bounds reads: the leading axis is untiled
    (any offset legal) and the plane slice stays 8-aligned — no mod
    arithmetic, one DMA.
    """
    j = pl.program_id(0)  # word chunk
    i = pl.program_id(1)  # plane chunk
    ni = pl.num_programs(1)
    # Double-buffered across the plane-chunk (inner) grid axis: window
    # (j, i+1) lands in the other slot under (j, i)'s adder tree.  The
    # first plane chunk of each word chunk loads serially (prefetching
    # across the word-chunk boundary would need j+1's window at i==ni-1;
    # the once-per-word-chunk stall is 1/ni of the fetches).
    step_lin = j * ni + i
    slot = jax.lax.rem(step_lin, 2)

    def copies(ii, s):
        return (
            pltpu.make_async_copy(
                ext_hbm.at[
                    pl.ds(j * tile_w, tile_w + 2),
                    pl.ds(
                        pl.multiple_of(ii * tile_d, _ALIGN),
                        tile_d + 2 * pad,
                    ),
                ],
                scratch.at[s],
                sems.at[s],
            ),
        )

    load_window_double_buffered(copies, i, i + 1, slot, i == 0, i + 1 < ni)
    for step in range(k):
        lo = pad - (k - step)
        hi = pad + tile_d + (k - step)
        scratch[slot, :, lo + 1 : hi - 1] = _one_generation_wt(
            scratch[slot, :, lo:hi], birth, survive
        )
    out_ref[:] = scratch[slot, 1:-1, pad : pad + tile_d]


def multi_step_pallas_packed3d_wt(
    packed_w: jax.Array,
    tile_d: int,
    tile_w: int,
    k: int,
    rule: Rule3D = BAYS_4555,
) -> jax.Array:
    """k fused torus generations on a word-leading packed volume [nw, D, H].

    The big-volume variant (VERDICT r1 #3): when a full ``(nw, H)`` word
    plane no longer fits the scoped-VMEM window (1024³: 32×1024 words),
    the plane is split along the *word* axis instead of the lane axis —
    word-chunk windows carry one ghost word per side whose 32-bit light
    cone supports k <= 32 in-VMEM generations, and word slices ride the
    untiled leading axis so no DMA alignment is lost.  H stays whole
    (lane rolls keep the h wrap); d halos are pre-extended wrap planes.
    """
    nw, depth, h = packed_w.shape
    validate_tile(depth, tile_d, _ALIGN)
    if nw % tile_w:
        raise ValueError(
            f"word tile {tile_w} must divide the packed width {nw}"
        )
    if k < 1 or k > bitlife.BITS:
        raise ValueError(
            f"word-tiled kernel supports 1 <= k <= {bitlife.BITS} (one "
            f"ghost word's bit light cone), got {k}"
        )
    pad = -(-k // _ALIGN) * _ALIGN
    if pad > tile_d:
        raise ValueError(
            f"temporal block depth {k} needs halo pad {pad} <= plane tile "
            f"{tile_d}"
        )
    ext = jnp.concatenate([packed_w[-1:], packed_w, packed_w[:1]], axis=0)
    ext = jnp.concatenate(
        [ext[:, -pad:], ext, ext[:, :pad]], axis=1
    )  # [nw+2, D+2*pad, H]
    return multi_step_pallas_packed3d_wt_ext(ext, tile_d, tile_w, k, rule)


def multi_step_pallas_packed3d_wt_ext(
    ext: jax.Array,
    tile_d: int,
    tile_w: int,
    k: int,
    rule: Rule3D = BAYS_4555,
) -> jax.Array:
    """Word-tiled kernel on a pre-extended volume ``[nw+2, D+2*pad, H]``.

    The extension's source is the caller's business: the single-device
    wrapper (:func:`multi_step_pallas_packed3d_wt`) concats torus wraps;
    the sharded engine (:func:`gol_tpu.parallel.sharded3d.
    compiled_evolve3d_pallas`) concats ``lax.ppermute`` ring ghosts —
    ghost word columns (x, one word per side: the 32-bit light cone
    covers k <= 32) and a ``pad``-plane band (d), with the word columns
    sliced from the already plane-extended array so the x/d corner data
    rides the second hop, exactly like the 2-D engine's two-phase
    exchange.  ``pad`` is inferred from the extension: ``(ext.shape[1] -
    D) / 2`` must equal ``ceil(k/8)*8``.
    """
    nw = ext.shape[0] - 2
    h = ext.shape[2]
    pad = -(-k // _ALIGN) * _ALIGN
    depth = ext.shape[1] - 2 * pad
    validate_tile(depth, tile_d, _ALIGN)
    if nw % tile_w:
        raise ValueError(
            f"word tile {tile_w} must divide the packed width {nw}"
        )
    if k < 1 or k > bitlife.BITS:
        raise ValueError(
            f"word-tiled kernel supports 1 <= k <= {bitlife.BITS}, got {k}"
        )
    if pad > tile_d:
        raise ValueError(
            f"temporal block depth {k} needs halo pad {pad} <= plane tile "
            f"{tile_d}"
        )
    return pl.pallas_call(
        functools.partial(
            _kernel_wt,
            tile_d=tile_d,
            tile_w=tile_w,
            k=k,
            pad=pad,
            birth=rule.birth,
            survive=rule.survive,
        ),
        grid=(nw // tile_w, depth // tile_d),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (tile_w, tile_d, h), lambda j, i: (j, i, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((nw, depth, h), ext.dtype),
        scratch_shapes=[
            # Two slots for the cross-grid-step prefetch (see _kernel_wt).
            pltpu.VMEM(
                (2, tile_w + 2, tile_d + 2 * pad, h), ext.dtype
            ),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=pallas_common.interpret(),
    )(ext)


# The wt kernel's own live-window factor: the 1024³ compile with a
# 9-window model overflowed scoped VMEM by 1.73 MB at a 1.77 MB window —
# the compiler's measured peak was 10.02 windows; 11 leaves margin, +1
# for the double-buffered scratch's second slot.
_LIVE_WINDOWS_WT = 12


def recompute_score(tile_d: int, tile_w: int, pad: int = _ALIGN) -> float:
    """Halo-recompute ratio of a kernel window: duplicated ghost compute
    per useful output cell.  ``tile_w`` counts ghost *words* (2 total,
    carried the whole way); the plane-axis factor is the *mean of the
    shrinking windows* — every kernel form evolves ``tile_d + 2*(pad-j)``
    planes at generation ``j``, so the per-generation mean is
    ``(tile_d + pad + 1) / tile_d``, exactly the basis of
    ``roofline.ops_3d_roll_per_useful_word`` / ``ops_3d_wt_per_useful_
    word`` — not the full first-window ``(tile_d + 2*pad) / tile_d``,
    which overweighted deep pads and could keep wt on near-tie shards
    where roll recomputes less (ADVICE r4).  The plane kernel is the
    ``tile_w -> inf`` special case (no word ghosts).  One definition
    shared by the wt tile picker and the dispatch sites (evolve3d,
    sharded3d), so the picker's objective and the dispatchers'
    comparisons cannot drift.
    """
    word_factor = (tile_w + 2) / tile_w if tile_w else 1.0
    return word_factor * ((tile_d + pad + 1) / tile_d)


def pick_tile3d_wt(depth: int, nw: int, h: int, pad: int = _ALIGN):
    """(tile_d, tile_w) for the word-tiled kernel, or None if nothing fits.

    Minimizes :func:`recompute_score` (the kernel is VPU-bound, so
    duplicated ghost compute is the cost that matters) over all feasible
    tiles under the scoped-VMEM window model; ties prefer the larger
    plane tile (fewer launches/DMAs).
    """
    budget = _SCOPED_LIMIT // (_LIVE_WINDOWS_WT * 4 * h)
    best = None
    best_score = None
    for tile_w in (w for w in (16, 8, 4, 2, 1) if nw % w == 0):
        cap = min(budget // (tile_w + 2) - 2 * pad, depth)
        if cap < _ALIGN:
            continue
        for tile_d in range(cap - cap % _ALIGN, 0, -_ALIGN):
            if depth % tile_d == 0:
                score = recompute_score(tile_d, tile_w, pad)
                if (
                    best is None
                    or score < best_score - 1e-12
                    or (abs(score - best_score) <= 1e-12 and tile_d > best[0])
                ):
                    best, best_score = (tile_d, tile_w), score
                break
    return best


# Benchmarked on v5e at 512³: blocking is marginal (VPU-bound) but k=8
# still wins slightly; the tile is VMEM-budget-limited.
_BLOCK = 8
# Scoped-VMEM feasibility model, calibrated against the compiler: ~9 live
# int32 arrays of the full halo-extended window at the scheduler's peak
# (the 1024³ failure measured 26.8 MB for a 24-plane window of 32×1024
# words — 9 × 24 × 32768 × 4 = 28 MB predicts it; 512³'s 48-plane window
# of 8192 words predicts 14 MB, which compiles).  Mosaic's hard scoped
# limit is 16 MB.
_SCOPED_LIMIT = 16 * 1024 * 1024
_LIVE_WINDOWS = 9


# The rolling kernel's VMEM model: ONE window (the scratch) plus
# plane-sized temporaries — three count9 sets in flight (12 bit planes),
# count27/count26/match intermediates, and slack for Mosaic's scheduling.
# Calibrated on-chip r4: tile 64 at 1024³ (80-plane window, 10 MB + temps)
# compiles; tile 128 (18 MB window alone) cannot.
_LIVE_PLANES_ROLL = 24


def pick_tile3d_roll(depth: int, nw: int, h: int, pad: int = _ALIGN) -> int:
    """Largest aligned divisor of ``depth`` whose window fits the rolling
    kernel's VMEM model (one window + ~24 plane-sized temps).

    Same contract as :func:`pick_tile3d`; returns 0 when nothing fits.
    The rolling kernel's restructured compute (per-plane ``fori_loop``
    with a count9 carry) is what shrinks the model from ~9 live windows
    to ~1 — see :func:`_kernel_roll`.
    """
    if depth % _ALIGN:
        raise ValueError(
            f"pallas 3-D engine needs volume depth divisible by {_ALIGN}, "
            f"got {depth}"
        )
    budget_planes = _SCOPED_LIMIT // (4 * nw * h) - _LIVE_PLANES_ROLL
    cap = min(budget_planes - 2 * pad, depth)
    if cap < _ALIGN:
        return 0
    for tile in range(cap - cap % _ALIGN, 0, -_ALIGN):
        if depth % tile == 0:
            return tile
    return 0


def pick_tile3d(depth: int, nw: int, h: int, pad: int = _ALIGN) -> int:
    """Largest _ALIGN-multiple divisor of ``depth`` whose halo-extended
    window (tile + 2*pad planes of nw×h words) fits scoped VMEM.

    Returns 0 when no tile fits — a single plane is too large (huge
    ``nw*h``); callers fall back to the XLA packed path.
    """
    if depth % _ALIGN:
        raise ValueError(
            f"pallas 3-D engine needs volume depth divisible by {_ALIGN}, "
            f"got {depth}"
        )
    max_window = _SCOPED_LIMIT // (_LIVE_WINDOWS * 4 * nw * h)
    cap = min(max_window - 2 * pad, depth)
    if cap < _ALIGN:
        return 0
    for tile in range(cap - cap % _ALIGN, 0, -_ALIGN):
        if depth % tile == 0:
            return tile
    return 0


def _contraction_dtype():
    """The pack contraction's operand dtype: bf16 where the kernels
    compile (the MXU multiplies bf16), f32 where they are interpreted
    (the CPU's dot has no bf16 x bf16 -> f32).  Both hold its operands,
    0/1 and powers of two up to 2^15, exactly."""
    return jnp.float32 if pallas_common.interpret() else jnp.bfloat16


def pack_transposed(vol: jax.Array) -> jax.Array:
    """uint8[D, H, W] 0/1 volume -> int32[D, W//32, H], the kernels' layout.

    Bit-identical to ``bitcast(bitlife3d.pack3d(vol), int32)
    .transpose(0, 2, 1)``: bit j of word k is column 32k+j.  One
    contraction over W packs and transposes at once, on the MXU:
    ``half[d, c, h] = sum_w vol[d, h, w] * E[w, c]`` with
    ``E[w, c] = 2^(w % 16)`` where ``c`` is ``w // 32`` for the word's
    low 16 columns and ``nw + w // 32`` for its high ones.  Every product
    is exact and every sum is at most 65535, well inside the f32
    accumulator, so the halves are exact; a word is then
    ``lo | hi << 16`` over two sublane-aligned row blocks.  The uint8
    volume is read once, by the contraction, and never relaid out.
    """
    _, _, w = vol.shape
    nw = bitlife.packed_width(w)
    col = np.arange(w)
    weights = np.zeros((w, 2 * nw), np.float32)
    weights[col, (col // 16) % 2 * nw + col // 32] = 2.0 ** (col % 16)
    dt = _contraction_dtype()
    halves = jnp.einsum(
        "dhw,wc->dch", vol.astype(dt), jnp.asarray(weights, dt),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    return halves[:, :nw] | (halves[:, nw:] << 16)


def unpack_transposed(packed_t: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_transposed`: int32[D, nw, H] -> uint8[D, H, 32 nw].

    The words split into bytes along the sublane axis (row block ``b``
    holds byte ``b`` of every word), and one int8 contraction
    ``[H, 4 nw] . [4 nw, W]`` per plane puts each byte, read as a signed
    int8, under its 8 columns.  A cell is bit ``w % 8`` of that int32:
    the low byte of a two's complement value keeps its bits under an
    arithmetic shift.  The uint8 volume is written once, by the
    contraction's epilogue.
    """
    _, nw, _ = packed_t.shape
    w = nw * bitlife.BITS
    col = np.arange(w)
    byte = col // 8
    spread = np.zeros((4 * nw, w), np.int8)
    spread[byte % 4 * nw + byte // 4, col] = 1
    by = jnp.concatenate(
        [packed_t >> (8 * b) for b in range(4)], axis=1
    ).astype(jnp.int8)
    v = jnp.einsum(
        "dch,cw->dhw", by, jnp.asarray(spread),
        preferred_element_type=jnp.int32,
    )
    return ((v >> jnp.asarray(col % 8, jnp.int32)) & 1).astype(jnp.uint8)


def _record_kernel(kernel: str, tile=None, k=None, convert="mxu") -> None:
    """Count the kernel ``evolve3d`` picked (``gol3d.kernel.<kernel>``)
    and the conversion around it (``gol3d.convert.<convert>``), and put
    them, the tile and k on the open span: at trace time, so once per
    compiled program."""
    telemetry.count(f"gol3d.kernel.{kernel}")
    telemetry.count(f"gol3d.convert.{convert}")
    telemetry.annotate(kernel=kernel, tile=tile, k=k, convert=convert)


@functools.partial(jax.jit, static_argnums=(1, 2, 3), donate_argnums=(0,))
def evolve3d(
    vol: jax.Array, steps: int, rule: Rule3D = BAYS_4555,
    strict: bool = False,
) -> jax.Array:
    """Dense uint8 in/out: pack and transpose, fused-evolve, restore.

    :func:`pack_transposed` and :func:`unpack_transposed` convert between
    the uint8 volume and the kernels' layout, one contraction each way;
    between them the generation loop runs as temporally-blocked Pallas
    launches (full k-blocks then one remainder).

    ``strict=True`` raises instead of taking the XLA fallback when no
    kernel window fits scoped VMEM — for callers who *explicitly* asked
    for the Pallas engine and must not have their benchmark silently
    relabeled (the cli3d ``--engine pallas`` contract); ``auto`` callers
    keep the silent substitution.
    """
    d, h, w = vol.shape
    nw = bitlife.packed_width(w)
    if not pallas_common.interpret():
        if h % _LANE != 0:
            raise ValueError(
                "pallas 3-D engine needs the H axis to fill whole "
                f"{_LANE}-lane tiles on TPU: got H={h}"
            )
    # Three kernels, one objective: lowest halo-recompute score wins (the
    # kernels are VPU-bound, so duplicated ghost compute decides).  The
    # rolling kernel fits windows several times the monolithic plane
    # kernel's (one live window vs ~9), so it usually scores lowest and
    # is what retired the wt kernel's ×1.92 recompute at 1024³ — measured
    # same-session ×256 on v5e (BASELINE.md r4): roll(32/64) 4.8e11
    # cell-updates/s vs wt(32,4) 3.3e11.  On score ties prefer the
    # monolithic plane kernel (bigger fused ops, measured slightly ahead
    # at equal tile); the tie can only happen when both max out at the
    # full depth.
    tile = pick_tile3d(d, nw, h)
    wt = pick_tile3d_wt(d, nw, h)
    roll = pick_tile3d_roll(d, nw, h)
    cands = []
    if tile:
        cands.append((recompute_score(tile, 0), 0, "plane"))
    if roll:
        cands.append((recompute_score(roll, 0), 1, "roll"))
    if wt is not None:
        cands.append((recompute_score(wt[0], wt[1]), 2, "wt"))
    if not cands:
        # Not even a word-tiled window fits: take the XLA packed path —
        # same bit-exact result, still one compiled program.
        if strict:
            raise ValueError(
                f"the fused Pallas 3-D kernel cannot fit a volume of shape "
                f"{(d, h, w)} in scoped VMEM (neither whole, rolling, nor "
                "word-tiled plane windows); use engine 'auto' or 'bitpack'"
            )
        _record_kernel("xla_fallback", convert="xla")
        return bitlife3d.unpack3d(
            bitlife3d.run3d_packed(bitlife3d.pack3d(vol), steps, rule)
        )
    choice = min(cands)[2]
    if choice == "wt":
        tile_d, tile_w = wt
        packed_w = pack_transposed(vol).transpose(1, 0, 2)
        k = _pick_block(steps, tile_d, _BLOCK, _ALIGN)
        _record_kernel("wt", [tile_d, tile_w], k)
        full, rem = divmod(steps, k)
        packed_w = lax.fori_loop(
            0,
            full,
            lambda _, p: multi_step_pallas_packed3d_wt(
                p, tile_d, tile_w, k, rule
            ),
            packed_w,
        )
        if rem:
            packed_w = multi_step_pallas_packed3d_wt(
                packed_w, tile_d, tile_w, rem, rule
            )
        return unpack_transposed(packed_w.transpose(1, 0, 2))
    step_fn = (
        multi_step_pallas_packed3d
        if choice == "plane"
        else multi_step_pallas_packed3d_roll
    )
    t = tile if choice == "plane" else roll
    packed_t = pack_transposed(vol)
    k = _pick_block(steps, t, _BLOCK, _ALIGN)
    _record_kernel(choice, t, k)
    full, rem = divmod(steps, k)
    packed_t = lax.fori_loop(
        0,
        full,
        lambda _, p: step_fn(p, t, k, rule),
        packed_t,
    )
    if rem:
        packed_t = step_fn(packed_t, t, rem, rule)
    return unpack_transposed(packed_t)
