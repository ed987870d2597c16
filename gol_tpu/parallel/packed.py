"""Bit-packed sharded engine: 32-cells/word Life under shard_map halo rings.

The two perf tiers of SURVEY §7 composed: the carry-save bit-packed step
(:mod:`gol_tpu.ops.bitlife`, 8× less HBM traffic than dense uint8) runs
per-shard under ``shard_map``, with ``lax.ppermute`` ring exchanges shipping
*packed* halos — so the wire traffic of the reference's ghost-row messages
(``MPI_UNSIGNED_CHAR`` × width, gol-main.c:97-107) also drops 8×: one
uint32 word per 32 cells of boundary instead of 32 bytes.

Decompositions mirror :mod:`gol_tpu.parallel.sharded`:

- **1-D rows**: two ppermutes/step deliver packed up/down ghost rows;
  columns wrap locally (width axis unsharded) via the lane-carry roll inside
  the packed step.
- **2-D blocks**: two-phase exchange — edge *rows* of packed words
  vertically, then edge *word columns* of the row-extended block
  horizontally, which carries the four corner words for free.  The
  horizontal halo quantum is a full 32-cell word even though only 1
  boundary bit is consumed; a word is the cheapest addressable unit and
  the traffic is still ≤ the dense engine's 1-byte column halo.

Pack/unpack happen once per evolve call, per shard, inside the compiled
program — dense uint8 in, dense uint8 out, cost amortized over the whole
``fori_loop`` (same contract as :func:`gol_tpu.ops.bitlife.evolve_dense_io`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from gol_tpu.ops import bitlife, pallas_common
from gol_tpu.parallel.halo import build_ring_engine, ring
from gol_tpu.parallel.mesh import COLS, ROWS, validate_geometry
from gol_tpu.parallel.sharded import (
    exchange_block_halos,
    exchange_row_halos,
    place_private,
)


def validate_packed_geometry(shape, mesh: Mesh) -> None:
    """Packed sharding needs each shard's width to pack into whole words."""
    validate_geometry(shape, mesh)
    cols = mesh.shape.get(COLS, 1)
    shard_w = shape[1] // cols
    if shard_w % bitlife.BITS != 0:
        raise ValueError(
            f"bit-packed sharded engine needs shard width divisible by "
            f"{bitlife.BITS}; board width {shape[1]} over {cols} mesh cols "
            f"gives shard width {shard_w}"
        )


def fold_rows(x: jax.Array, f: int) -> jax.Array:
    """``[h, nw] -> [h/f, f*nw]``: row group ``g`` (shard rows
    ``[g*h/f, (g+1)*h/f)``) occupies lanes ``[g*nw, (g+1)*nw)``.

    The narrow-shard layout of the sharded Pallas engine: vertical
    neighbors stay vertically adjacent *within* each group, so the fused
    kernel's row-window stencil is untouched; the group seams (vertical at
    the band rows, horizontal at the lane wrap) are repaired by the band
    construction and the exact-edge overwrite.
    """
    h, nw = x.shape
    return x.reshape(f, h // f, nw).transpose(1, 0, 2).reshape(h // f, f * nw)


def unfold_rows(x: jax.Array, f: int) -> jax.Array:
    """Inverse of :func:`fold_rows`."""
    hg, fnw = x.shape
    nw = fnw // f
    return x.reshape(hg, f, nw).transpose(1, 0, 2).reshape(f * hg, nw)


def step_packed_halo_rows(block: jax.Array, num_rows: int) -> jax.Array:
    """One packed generation of a row-sharded shard with fresh ring halos.

    ``block`` is the shard's packed words ``uint32[h, W/32]``.  The dense
    engine's ring exchange (:func:`~gol_tpu.parallel.sharded.
    exchange_row_halos`, dtype-agnostic) ships the packed boundary rows —
    the ``previous_last_row``/``next_first_row`` of gol-main.c:11, re-sliced
    live each step (B1 fixed by construction), at 1/8th the bytes.
    """
    top, bottom = exchange_row_halos(block, num_rows)
    ext = jnp.concatenate([top[None], block, bottom[None]], axis=0)
    return bitlife.step_packed_vext(ext)


def step_packed_halo_blocks(
    block: jax.Array, num_rows: int, num_cols: int
) -> jax.Array:
    """One packed generation of a 2-D-sharded shard with fresh ring halos.

    The same two-phase edge exchange as the dense engine
    (:func:`gol_tpu.parallel.sharded.exchange_block_halos` is dtype-agnostic
    and reused directly), but the halo quantum is a packed word: phase 2
    ships the edge word-columns of the already row-extended block, so the
    corner *words* make two hops and land with their boundary bits intact.
    """
    ext = exchange_block_halos(block, num_rows, num_cols)  # [h+2, nw+2]
    return bitlife.step_packed_halo_full(ext)


@functools.lru_cache(maxsize=64)
def compiled_evolve_packed_overlap(mesh: Mesh, steps: int):
    """Packed 1-D ring evolve in comm/compute-overlap form.

    Counterpart of the dense engine's ``--shard-mode overlap``
    (:func:`gol_tpu.parallel.sharded.compiled_evolve`): interior rows
    never wait on the halo ppermutes.  1-D row meshes only — the 2-D
    packed boundary ring needs word-carry edge columns whose overlap form
    has no payoff at word granularity.  Single-layer halos (overlap's
    interior/boundary split assumes depth 1).
    """
    if COLS in mesh.axis_names:
        raise ValueError(
            "packed overlap mode is 1-D (row-ring) only; use shard_mode "
            "'explicit' on 2-D meshes"
        )
    num_rows = mesh.shape[ROWS]

    def body(_, blk):
        top, bottom = exchange_row_halos(blk, num_rows)
        return bitlife.step_packed_overlap_rows(blk, top, bottom)

    def local(board):
        packed = bitlife.pack(board)
        packed = lax.fori_loop(0, steps, body, packed)
        return bitlife.unpack(packed)

    shmapped = jax.shard_map(
        local, mesh=mesh, in_specs=P(ROWS, None), out_specs=P(ROWS, None)
    )
    return jax.jit(shmapped, donate_argnums=0)


@functools.lru_cache(maxsize=64)
def compiled_evolve_packed(
    mesh: Mesh, steps: int, halo_depth: int = 1, mode: str = "explicit"
):
    """Build + jit the packed sharded evolve for (mesh, steps, halo_depth).

    Dense uint8 board in/out with the canonical mesh sharding; pack /
    ``fori_loop`` over packed steps / unpack all run per-shard inside one
    compiled program.  The input buffer is donated (the double buffer).

    ``halo_depth=k > 1`` is temporal blocking on the packed words: one
    exchange ships a k-deep ghost band and the shard steps k generations
    locally, consuming one ghost layer per step.  The consumption quantum
    matches the exchange quantum — a packed *word* column (32 cells)
    horizontally on 2-D meshes, a packed row vertically — so the 2-D wire
    cost per k generations is ``2k`` ghost rows + ``2k`` ghost word-columns
    against ``2k`` rows + ``2k`` single-cell columns for the dense engine;
    still ~8× fewer bytes on the row axis, break-even on the word axis at
    k=1, and k× fewer ppermute latencies either way.

    ``mode`` picks the chunk loop (:data:`gol_tpu.parallel.halo.
    LOCAL_LOOPS`): "explicit" (serial blocked chunks), "overlap" (depth-k
    interior/boundary split — the packed counterpart of the dense
    engine's lifted overlap mode, now on 1-D AND 2-D meshes at any k),
    or "pipeline" (the cross-chunk double buffer: chunk N+1's packed
    band ships while chunk N's interior computes).  All modes are pinned
    bit-identical to explicit.
    """
    return build_ring_engine(
        mesh,
        steps,
        halo_depth,
        step_1d=bitlife.step_packed_vext,  # consumes a row layer
        step_2d=bitlife.step_packed_halo_full,  # row + word-column layer
        pack=bitlife.pack,
        unpack=bitlife.unpack,
        mode=mode,
    )


@functools.lru_cache(maxsize=64)
def compiled_evolve_packed_pallas(
    mesh: Mesh, steps: int, halo_depth: int = 8, tile_hint: int = 1024,
    rule=None, overlap: bool = False, pipeline: bool = False,
):
    """Sharded evolve running the fused Pallas kernel per shard.

    The flagship multi-chip configuration: per chunk, one ring exchange
    ships a ``halo_depth``-deep packed ghost band (``lax.ppermute`` over
    ICI), then the shard steps ``halo_depth`` generations inside a single
    Pallas launch.  The band rides its *own* kernel operand
    (:func:`gol_tpu.ops.pallas_bitlife.multi_step_pallas_packed_bands`),
    so the shard's rows are never re-copied into an extended array — the
    halo_extend concat was a full-board HBM round trip per chunk, worth
    ~4% of end-to-end throughput at 16384² (1.81e12 vs 1.73e12
    cell-updates/s at ×10240).  Tiles smaller than the band depth fall
    back to the pre-extended kernel
    (:func:`~gol_tpu.ops.pallas_bitlife.multi_step_pallas_packed_ext`),
    whose windows may span several neighbor tiles.
    ``halo_depth`` must be a multiple of 8 (DMA row alignment).  A
    non-multiple remainder of ``steps`` runs on the jnp packed step.
    Defaults: band depth 8 (deeper bands measured at parity or slightly
    behind in r5 overhead-fitted sweeps — k=32 within noise of k=8 —
    and k=8 stays inside the 2-D column-band light cone) and row tile
    hint 1024, which lets :func:`~gol_tpu.ops.pallas_bitlife.pick_tile`'s
    VMEM budget set the real cap per geometry: wide boards cap at 256
    (nw=512's budget), narrow lane-folded shards reach 1024.  Earlier
    rounds defaulted the hint to 128 off wall-clock sweeps; r5's
    two-point overhead fits (BASELINE.md r5)
    showed those walls were per-invocation-overhead artifacts — device
    side, the folded 16384×1024 pod shard runs 2.01e12 cell-updates/s at tile 1024
    vs 1.49e12 at tile 128 (+35%), and the full 16384² board gains ~4%
    at its 256 cap.  Taller tiles amortize per-tile fixed costs over
    more rows AND shrink the temporal blocking's recompute factor
    ((tile + k + 1)/tile); the VMEM budget is the only true ceiling.
    Optional ``rule`` switches the kernel tail to the generic plane
    matcher.

    ``overlap=True`` restructures each chunk for comm/compute overlap —
    the interior-first split the reference attempted with nonblocking MPI
    but forfeited by calling ``MPI_Wait`` before the kernel
    (gol-main.c:110-114).  The shard's interior rows ``[k, h-k)`` depend
    only on local data, so their (bulk) kernel launch carries no data
    dependency on the ring ppermutes and XLA's latency-hiding scheduler
    can run the band exchange underneath it; only two k-row boundary
    kernels wait for the band.  The price is reassembling the output from
    the three pieces (one board copy per chunk, ~1/(22·k/8) of the kernel's
    bitwise work) — hence a mode, not the default: serial wins single-chip,
    overlap wins when exchange latency is exposed (multi-chip, DCN).

    ``pipeline=True`` is the cross-chunk double buffer (``--shard-mode
    pipeline``): the chunk loop carries ``(block, bands)`` — each chunk
    consumes the k-row ghost bands exchanged DURING the previous chunk's
    compute, and ships the next chunk's bands from its own just-computed
    k-row boundary kernels (whose outputs are exactly the rows the ring
    must carry), so the ring ppermutes for chunk N+1 are in flight while
    chunk N's interior kernel — which reads only carried state — still
    runs.  Where overlap hides the exchange under the *same* chunk's
    interior (the band must still arrive before the boundary kernels),
    pipeline removes the arrival deadline entirely: the band has a full
    chunk of interior compute to cross the wire.  One exchange per chunk
    exactly (prologue + one per loop chunk; a remainder chunk consumes
    the final carried band sliced to its depth, and with no remainder
    the last chunk runs consume-only).  The carried band is one chunk
    "stale" only in wall-clock — its contents are the neighbor's
    boundary rows at this chunk's start generation, which is precisely
    what the ghost shell must hold.  Geometry constraints match overlap
    (the interior tile must clear both bands).

    **Narrow shards** (packed width not a multiple of 128 lanes — e.g.
    BASELINE config 3 on a 16×16 mesh: 1024-cell = 32-word shards) are
    evolved **lane-folded**: ``f = 128/gcd(nw, 128)`` row groups side by
    side in lanes (``[h, nw] -> [h/f, f*nw]``, :func:`fold_rows`), with
    the kernel's word ring made *group-local* (two masked rolls,
    ``pallas_bitlife._one_generation(groups=f)``) so the fold introduces
    no seam wrongness at all.  The board stays folded across the whole
    chunk loop; each chunk's ghost bands are lane-shifted slices of the
    folded block plus the two ring ppermutes.  Measured on v5e: a folded
    16384×1024 board runs within 1% of an equal-cell 4096² unfolded board
    (7.56e11 vs 7.60e11 cell-updates/s at ×16384) — the engine's fastest
    kernel now composes with pod-scale 2-D decompositions at any shard
    width >= 2 words.  Requires shard height divisible by ``8f``.
    ``overlap=True`` composes with the fold (r4): in the folded layout
    every interior group seam's band is a lane-shifted slice of the block
    itself, so the only ppermute-dependent inputs are the two ring ghosts
    — the interior kernel (folded rows ``[k, h/f - k)``, all lane groups)
    reads the folded block alone, and only two k-row boundary kernels
    wait for the exchanged band; folded overlap additionally needs
    ``h/f >= 2*halo_depth + 8`` (an aligned interior tile clear of both
    bands, same constraint as unfolded overlap one fold down).

    On **2-D block meshes** (BASELINE config 3's decomposition) the
    exchange grows a second phase: the k-row temporal band vertically, then
    a single ghost *word* column of the row-extended block horizontally
    (corner words ride the second hop).  The kernel itself still runs at
    the lane-aligned shard width with its local column wrap — wrong at the
    shard's vertical seams, but the wrongness is confined by the stencil
    light cone to the outer ``k`` bits of the two edge words (k <= 32 = one
    word).  Those two word columns are then recomputed exactly from 3-word
    strips (96-bit no-wrap windows: every edge-word bit sits >= 32 bits
    from both window boundaries) and spliced over the kernel's output.  The
    strips are O(rows) work that XLA can schedule concurrently with the
    kernel — the whole horizontal fix-up costs ~3/nw of the kernel's
    compute and none of its latency.
    """
    from gol_tpu.ops import pallas_bitlife

    two_d = COLS in mesh.axis_names
    if overlap and pipeline:
        raise ValueError(
            "overlap and pipeline are distinct chunk forms; pick one"
        )
    if halo_depth < 8 or halo_depth % 8:
        raise ValueError(
            f"the sharded Pallas engine needs halo_depth to be a multiple "
            f"of 8 (DMA row alignment), got {halo_depth}"
        )
    if two_d and halo_depth > bitlife.BITS:
        raise ValueError(
            f"on a 2-D mesh the sharded Pallas engine ships a 1-word "
            f"column band whose bit light cone supports halo_depth <= "
            f"{bitlife.BITS}, got {halo_depth}"
        )
    from gol_tpu.parallel.halo import halo_extend

    num_rows = mesh.shape[ROWS]
    num_cols = mesh.shape.get(COLS, 1)
    phases = ((0, ROWS, num_rows),)
    phases2d = ((0, ROWS, num_rows), (1, COLS, num_cols))
    full, rem = divmod(steps, halo_depth)
    # A 2-D mesh with a size-1 column ring shards only the rows: the shard
    # owns the full width, its local column wrap IS the torus, and the
    # strip/edge machinery would compute what the kernel already has — so
    # degenerate column rings take the 1-D bodies.
    strip_fix = two_d and num_cols > 1

    def kernel(ext_u32, tile, k, edges_u32=None, groups=1):
        # Bit-identical int32 view only around the kernel; the jnp packed
        # ops stay on uint32 (their right-shifts must be logical).
        out = pallas_bitlife.multi_step_pallas_packed_ext(
            lax.bitcast_convert_type(ext_u32, jnp.int32),
            tile,
            k,
            rule,
            None
            if edges_u32 is None
            else lax.bitcast_convert_type(edges_u32, jnp.int32),
            groups,
        )
        return lax.bitcast_convert_type(out, jnp.uint32)

    def kernel_bands(blk_u32, bands_u32, tile, k, edges_u32=None, groups=1):
        out = pallas_bitlife.multi_step_pallas_packed_bands(
            lax.bitcast_convert_type(blk_u32, jnp.int32),
            lax.bitcast_convert_type(bands_u32, jnp.int32),
            tile,
            k,
            rule,
            None
            if edges_u32 is None
            else lax.bitcast_convert_type(edges_u32, jnp.int32),
            groups,
        )
        return lax.bitcast_convert_type(out, jnp.uint32)

    def bands_for(p_u32):
        """The chunk's k-row ghost bands, fresh off the ring."""
        k = halo_depth
        top_ghost = lax.ppermute(p_u32[-k:], ROWS, ring(num_rows, 1))
        bottom_ghost = lax.ppermute(p_u32[:k], ROWS, ring(num_rows, -1))
        return top_ghost, bottom_ghost

    def four(a):
        """A block's four boundary word-columns, lane-packed."""
        return jnp.concatenate([a[:, :2], a[:, -2:]], axis=1)

    def edge_strips(top_ghost, middle4, bottom_ghost):
        """Exact post-chunk edge words from the three row pieces' boundary
        columns (ghost bands around the shard's own four() columns) — the
        one assembly behind every strip-repair site."""
        return exact_edges(
            jnp.concatenate(
                [four(top_ghost), middle4, four(bottom_ghost)], axis=0
            ).T
        )

    def jnp_step(ext):
        if rule is None:
            return bitlife.step_packed_vext(ext)
        from gol_tpu.ops import rules as rules_mod

        return rules_mod.step_rule_packed_vext(ext, rule)

    def jnp_step_nowrap(ext):
        if rule is None:
            return bitlife.step_packed_vext_nowrap(ext)
        from gol_tpu.ops import rules as rules_mod

        return rules_mod.step_rule_packed_vext_nowrap(ext, rule)

    def jnp_step_nowrap_t(ext_t):
        if rule is None:
            return bitlife.step_packed_vext_nowrap_t(ext_t)
        from gol_tpu.ops import rules as rules_mod

        return rules_mod.step_rule_packed_vext_nowrap_t(ext_t, rule)

    def chunk(p_u32, tile):
        # Band as its own kernel operand: the exchange ships 2k rows and
        # the shard's own rows are never re-copied into an extended array
        # (halo_extend's concat cost a full-board HBM round trip per
        # chunk — ~1/9 of chunk traffic at k=8).
        top_ghost, bottom_ghost = bands_for(p_u32)
        bands = jnp.concatenate([top_ghost, bottom_ghost])
        return kernel_bands(p_u32, bands, tile, halo_depth)

    def exact_edges(edges_t):
        """Exact post-chunk edge word-columns from the row-extended block's
        four boundary columns (transposed, ``[4, h + 2k]``).

        The horizontal phase of the two-phase exchange: ppermute a ghost
        word-column per side (corner words ride this second hop), then
        step 3-word strips (ghost + edge + 1 interior — 96-bit no-wrap
        windows: every edge-word bit sits >= 32 bits from both window
        boundaries, so k <= 32 steps stay exact), stacked so both sides
        share one op chain.  Transposed layout throughout: the long row
        axis fills the 128-wide lanes a ``[rows, 1]`` column would waste.
        Returns the ``[h, 2]`` left/right edge words after ``halo_depth``
        generations.
        """
        left_ghost_t = lax.ppermute(edges_t[3:4], COLS, ring(num_cols, 1))
        right_ghost_t = lax.ppermute(edges_t[0:1], COLS, ring(num_cols, -1))
        strips = jnp.stack(
            [
                jnp.concatenate([left_ghost_t, edges_t[0:2]], axis=0),
                jnp.concatenate([edges_t[2:4], right_ghost_t], axis=0),
            ]
        )  # [2 sides, 3 words, h + 2k rows]
        for _ in range(halo_depth):  # each step consumes one ghost row layer
            strips = jnp_step_nowrap_t(strips)
        return jnp.stack([strips[0, 1], strips[1, 1]], axis=1)  # [h, 2]

    def chunk_ext(p_u32, tile):
        # tile < halo_depth fallback: the banded kernel's one-descriptor
        # halo segments can't span multiple neighbor tiles, so small
        # tiles take the pre-extended form (one extra board copy/chunk).
        return kernel(
            halo_extend(p_u32, phases, depth=halo_depth), tile, halo_depth
        )

    def chunk2d_ext(p_u32, tile):
        ext = halo_extend(p_u32, phases, depth=halo_depth)  # rows only
        edges = exact_edges(
            jnp.concatenate([ext[:, :2], ext[:, -2:]], axis=1).T
        )
        return kernel(ext, tile, halo_depth, edges)

    def chunk2d(p_u32, tile):
        top_ghost, bottom_ghost = bands_for(p_u32)
        # One transpose pulls all four boundary columns into lane-major
        # layout up front, sliced from the pieces (no row-extended array
        # is ever materialized — the band rides its own kernel operand).
        edges = edge_strips(top_ghost, four(p_u32), bottom_ghost)
        bands = jnp.concatenate([top_ghost, bottom_ghost])
        # Kernel at the lane-aligned shard width; its local column wrap is
        # wrong at the vertical seams, confined by the light cone to the
        # outer halo_depth bits of the two edge words — which the kernel
        # overwrites with `edges` during its own output store.
        return kernel_bands(p_u32, bands, tile, halo_depth, edges)

    def bands_folded(fp, f):
        """Ring bands in the folded-lane layout (k <= hg — the banded
        path's own tile >= k constraint guarantees it).

        Row group ``g``'s vertical neighbors are shard rows
        ``[g*hg - k, g*hg)`` above and ``[(g+1)*hg, (g+1)*hg + k)`` below:
        every interior group seam's band is a lane-shifted slice of the
        folded block itself; only the outer two ride the ROWS ring.  The
        board therefore stays folded across the whole chunk loop — no
        per-chunk transpose.  Returns ``(bands, top_ghost, bottom_ghost)``
        with the ghosts in unfolded ``[k, nw]`` layout (the 2-D edge
        strips want them that way).
        """
        k = halo_depth
        hg, fnw = fp.shape
        nw = fnw // f
        top_ghost = lax.ppermute(
            fp[hg - k :, (f - 1) * nw :], ROWS, ring(num_rows, 1)
        )
        bottom_ghost = lax.ppermute(fp[:k, :nw], ROWS, ring(num_rows, -1))
        top_band = jnp.concatenate(
            [top_ghost, fp[hg - k :, : (f - 1) * nw]], axis=1
        )
        bot_band = jnp.concatenate([fp[:k, nw:], bottom_ghost], axis=1)
        return jnp.concatenate([top_band, bot_band]), top_ghost, bottom_ghost

    def four_folded(fp, f):
        """``[hg, f*nw] -> [h, 4]``: the unfolded shard's four boundary
        word columns, gathered from each group's edge lanes."""
        hg, fnw = fp.shape
        nw = fnw // f
        idx = [g * nw + j for j in (0, 1, nw - 2, nw - 1) for g in range(f)]
        cols = fp[:, jnp.asarray(idx)]  # [hg, 4f], column-kind major
        return cols.reshape(hg, 4, f).transpose(2, 0, 1).reshape(hg * f, 4)

    def folded_edges(fp, top_ghost, bottom_ghost, f):
        """Exact post-chunk edge pairs of a folded shard, in the kernel's
        folded edges layout ``[hg, 2f]`` — the one strip-repair assembly
        behind both folded chunk bodies."""
        return fold_rows(
            edge_strips(top_ghost, four_folded(fp, f), bottom_ghost), f
        )

    def chunk_folded(fp, tile, f):
        # The kernel's group-local lane rolls (groups=f) make the fold
        # seams exact by construction, so a row-sharded (1-D) narrow shard
        # needs no repair at all; a column-sharded one needs only the same
        # two exact edge columns as the unfolded 2-D path, folded to one
        # (left, right) pair per group.
        bands, top_ghost, bottom_ghost = bands_folded(fp, f)
        edges_f = None
        if strip_fix:
            edges_f = folded_edges(fp, top_ghost, bottom_ghost, f)
        return kernel_bands(fp, bands, tile, halo_depth, edges_f, f)

    def folded_band_slices(p_u32, top_ghost, bottom_ghost, f):
        """Band construction valid for any k (k > hg included): in
        ``concat([ring_ghost, shard_rows])`` coordinates every group's
        band is the contiguous slice ``[g*hg, g*hg + k)``, whatever mix
        of ghost and local rows it spans."""
        k = halo_depth
        h, nw = p_u32.shape
        hg = h // f
        ext_top = jnp.concatenate([top_ghost, p_u32[: (f - 1) * hg]])
        ext_bot = jnp.concatenate([p_u32[hg:], bottom_ghost])
        top_band = jnp.stack(
            [ext_top[g * hg : g * hg + k] for g in range(f)], axis=1
        ).reshape(k, f * nw)
        bot_band = jnp.stack(
            [ext_bot[g * hg : g * hg + k] for g in range(f)], axis=1
        ).reshape(k, f * nw)
        return jnp.concatenate([top_band, bot_band])

    def chunk_folded_ext(p_u32, tile, f):
        # tile < halo_depth fallback (the banded kernel's one-descriptor
        # segments need tile >= k; k may even exceed hg here): assemble
        # the extended folded window from unfolded-resident slices.
        top_ghost, bottom_ghost = bands_for(p_u32)
        bands = folded_band_slices(p_u32, top_ghost, bottom_ghost, f)
        k = halo_depth
        ext = jnp.concatenate([bands[:k], fold_rows(p_u32, f), bands[k:]])
        edges_f = None
        if strip_fix:
            edges_f = fold_rows(
                edge_strips(top_ghost, four(p_u32), bottom_ghost), f
            )
        return unfold_rows(kernel(ext, tile, halo_depth, edges_f, f), f)

    def _boundary_pieces(p_u32, tile_int):
        """Interior kernel (ppermute-independent) + band-gated edge kernels.

        Returns the three row pieces of the stepped shard.  The interior
        launch reads only local rows, so XLA schedules the ring ppermutes
        concurrently with it; the two k-row boundary kernels consume the
        arrived band plus a 2k-row local margin (their windows span rows
        ``[-k, 2k)`` and ``[h-2k, h+k)``).
        """
        k = halo_depth
        top_ghost, bottom_ghost = bands_for(p_u32)
        interior = kernel(p_u32, tile_int, k)  # output rows [k, h-k)
        top = kernel(jnp.concatenate([top_ghost, p_u32[: 2 * k]]), k, k)
        bottom = kernel(
            jnp.concatenate([p_u32[-2 * k :], bottom_ghost]), k, k
        )
        return top, interior, bottom, top_ghost, bottom_ghost

    def chunk_overlap(p_u32, tile_int):
        top, interior, bottom, _, _ = _boundary_pieces(p_u32, tile_int)
        return jnp.concatenate([top, interior, bottom], axis=0)

    def chunk_folded_overlap(fp, tile_int, f):
        # Folded counterpart of chunk_overlap / chunk2d_overlap.  In the
        # folded layout the interior group seams' bands are lane-shifted
        # slices of the block itself (see bands_folded), so the ONLY
        # ppermute-dependent inputs are the two ring ghosts: the interior
        # kernel (folded rows [k, hg-k), every lane group) reads fp alone
        # and XLA schedules the ring exchange underneath it; the two
        # k-row boundary kernels consume the arrived band plus a 2k-row
        # local margin, exactly as in _boundary_pieces one fold down.
        k = halo_depth
        bands, top_ghost, bottom_ghost = bands_folded(fp, f)
        interior = kernel(fp, tile_int, k, groups=f)  # folded rows [k, hg-k)
        top = kernel(
            jnp.concatenate([bands[:k], fp[: 2 * k]]), k, k, groups=f
        )
        bottom = kernel(
            jnp.concatenate([fp[-2 * k :], bands[k:]]), k, k, groups=f
        )
        rows_out = jnp.concatenate([top, interior, bottom], axis=0)
        if strip_fix:
            # Same strip repair as chunk_folded, spliced by lane concat
            # (the interior kernel must not take the edges operand — the
            # strips depend on both exchange phases).  Group g's exact
            # (left, right) pair sits at edges_f columns 2g, 2g+1; its
            # words at lanes g*nw and (g+1)*nw - 1.
            edges_f = folded_edges(fp, top_ghost, bottom_ghost, f)
            nw = fp.shape[1] // f
            rows_out = jnp.concatenate(
                [
                    piece
                    for g in range(f)
                    for piece in (
                        edges_f[:, 2 * g : 2 * g + 1],
                        rows_out[:, g * nw + 1 : (g + 1) * nw - 1],
                        edges_f[:, 2 * g + 1 : 2 * g + 2],
                    )
                ],
                axis=1,
            )
        return rows_out

    def chunk2d_overlap(p_u32, tile_int):
        top, interior, bottom, top_ghost, bottom_ghost = _boundary_pieces(
            p_u32, tile_int
        )
        rows_out = jnp.concatenate([top, interior, bottom], axis=0)
        # Same strip repair as chunk2d, with the row-extended block's four
        # boundary columns sliced from the pieces instead of a
        # materialized extension.  The kernels above could not take an
        # ``edges`` input (the strips depend on both exchange phases,
        # which the interior launch must not), so the exact edge words are
        # spliced by a lane concat instead of the kernel's own output
        # store — the serial form's advantage this mode trades away for
        # the overlap.
        edges = edge_strips(top_ghost, four(p_u32), bottom_ghost)
        return jnp.concatenate(
            [edges[:, :1], rows_out[:, 1:-1], edges[:, 1:]], axis=1
        )

    def chunk_pipe_pieces(p_u32, bt, bb, tile_int):
        """The three row pieces of one pipelined chunk, consuming the
        CARRIED bands ``bt``/``bb`` (exchanged during the previous
        chunk's compute).  Strip repair included, so the pieces are the
        exact rows the next exchange ships."""
        k = halo_depth
        interior = kernel(p_u32, tile_int, k)  # carried state only
        top = kernel(jnp.concatenate([bt, p_u32[: 2 * k]]), k, k)
        bottom = kernel(jnp.concatenate([p_u32[-2 * k :], bb]), k, k)
        if strip_fix:
            # Same repair as chunk2d_overlap, spliced per piece (concat
            # of spliced pieces == splice of the concat); the COLS
            # ppermutes inside edge_strips read only carried state, so
            # they too are in flight before the interior kernel.
            edges = edge_strips(bt, four(p_u32), bb)
            top = jnp.concatenate(
                [edges[:k, :1], top[:, 1:-1], edges[:k, 1:]], axis=1
            )
            interior = jnp.concatenate(
                [edges[k:-k, :1], interior[:, 1:-1], edges[k:-k, 1:]],
                axis=1,
            )
            bottom = jnp.concatenate(
                [edges[-k:, :1], bottom[:, 1:-1], edges[-k:, 1:]], axis=1
            )
        return top, interior, bottom

    def chunk_folded_pipe_pieces(fp, tg, bg, tile_int, f):
        """Folded counterpart: carried state is ``(fp, tg, bg)`` with the
        two RING ghosts in unfolded ``[k, nw]`` layout; the interior
        group seams' band parts are lane-shifted slices of ``fp`` itself
        (carried state, no wire), exactly as in bands_folded."""
        k = halo_depth
        hg, fnw = fp.shape
        nw = fnw // f
        top_band = jnp.concatenate([tg, fp[hg - k :, : (f - 1) * nw]], axis=1)
        bot_band = jnp.concatenate([fp[:k, nw:], bg], axis=1)
        interior = kernel(fp, tile_int, k, groups=f)  # folded [k, hg-k)
        top = kernel(
            jnp.concatenate([top_band, fp[: 2 * k]]), k, k, groups=f
        )
        bottom = kernel(
            jnp.concatenate([fp[-2 * k :], bot_band]), k, k, groups=f
        )
        if strip_fix:
            edges_f = folded_edges(fp, tg, bg, f)

            def splice(piece, rows):
                return jnp.concatenate(
                    [
                        part
                        for g in range(f)
                        for part in (
                            edges_f[rows, 2 * g : 2 * g + 1],
                            piece[:, g * nw + 1 : (g + 1) * nw - 1],
                            edges_f[rows, 2 * g + 1 : 2 * g + 2],
                        )
                    ],
                    axis=1,
                )

            top = splice(top, slice(None, k))
            interior = splice(interior, slice(k, hg - k))
            bottom = splice(bottom, slice(hg - k, None))
        return top, interior, bottom

    def tail_consume(p_u32, bt, bb):
        """The remainder chunk of a pipelined run: consume the carried
        bands (sliced to depth rem) instead of exchanging again — same
        values the serial tails' halo_extend would ship."""
        ext_rows = jnp.concatenate([bt[-rem:], p_u32, bb[:rem]])
        if strip_fix:
            left = lax.ppermute(ext_rows[:, -1:], COLS, ring(num_cols, 1))
            right = lax.ppermute(ext_rows[:, :1], COLS, ring(num_cols, -1))
            ext = jnp.concatenate([left, ext_rows, right], axis=1)
            for _ in range(rem):
                ext = jnp_step_nowrap(ext)
            return ext[:, 1:-1]
        ext = ext_rows
        for _ in range(rem):  # each step consumes one ghost layer
            ext = jnp_step(ext)
        return ext

    def local_pipeline(packed, fold):
        """The pipelined chunk loop: prologue exchange, carried
        ``(block, bands)`` iterations each shipping the next chunk's
        bands from its boundary pieces, and a band-consuming tail."""
        k = halo_depth
        if full == 0:
            # steps < band depth: a single serial-tail chunk.
            return (tail2d if strip_fix else tail)(packed)
        n_loop = full if rem else full - 1
        if fold > 1:
            hg = packed.shape[0] // fold
            nw = packed.shape[1]
            tile = pallas_bitlife.pick_tile(
                hg - 2 * k, fold * nw, tile_hint
            )
            fp = fold_rows(packed, fold)
            tg = lax.ppermute(
                fp[hg - k :, (fold - 1) * nw :], ROWS, ring(num_rows, 1)
            )
            bg = lax.ppermute(fp[:k, :nw], ROWS, ring(num_rows, -1))

            def body_f(_, carry):
                q, t, b = carry
                top, inter, bottom = chunk_folded_pipe_pieces(
                    q, t, b, tile, fold
                )
                nq = jnp.concatenate([top, inter, bottom])
                nt = lax.ppermute(
                    bottom[:, (fold - 1) * nw :], ROWS, ring(num_rows, 1)
                )
                nb = lax.ppermute(top[:, :nw], ROWS, ring(num_rows, -1))
                return nq, nt, nb

            if n_loop:
                fp, tg, bg = lax.fori_loop(
                    0, n_loop, body_f, (fp, tg, bg)
                )
            if rem:
                return tail_consume(unfold_rows(fp, fold), tg, bg)
            top, inter, bottom = chunk_folded_pipe_pieces(
                fp, tg, bg, tile, fold
            )
            return unfold_rows(
                jnp.concatenate([top, inter, bottom]), fold
            )
        tile = pallas_bitlife.pick_tile(
            packed.shape[0] - 2 * k, packed.shape[1], tile_hint
        )
        bt, bb = bands_for(packed)  # prologue

        def body(_, carry):
            q, t, b = carry
            top, inter, bottom = chunk_pipe_pieces(q, t, b, tile)
            nq = jnp.concatenate([top, inter, bottom])
            nt = lax.ppermute(bottom, ROWS, ring(num_rows, 1))
            nb = lax.ppermute(top, ROWS, ring(num_rows, -1))
            return nq, nt, nb

        if n_loop:
            packed, bt, bb = lax.fori_loop(
                0, n_loop, body, (packed, bt, bb)
            )
        if rem:
            return tail_consume(packed, bt, bb)
        top, inter, bottom = chunk_pipe_pieces(packed, bt, bb, tile)
        return jnp.concatenate([top, inter, bottom])

    def tail(p_u32):
        # One depth-rem exchange feeds all leftover generations (the
        # blocked-chunk pattern of halo.blocked_local_loop), instead of
        # rem separate ppermute pairs.
        ext = halo_extend(p_u32, phases, depth=rem)
        for _ in range(rem):  # each step consumes one ghost layer
            ext = jnp_step(ext)
        return ext

    def tail2d(p_u32):
        # rem < halo_depth <= BITS, so the no-wrap step's bit-level garbage
        # stays inside the single ghost word per side; the interior crop is
        # exact.
        ext = halo_extend(p_u32, phases2d, depth=(rem, 1))
        for _ in range(rem):
            ext = jnp_step_nowrap(ext)
        return ext[:, 1:-1]

    def local(board):
        h, w = board.shape  # per-shard block (static under shard_map)
        nw = w // bitlife.BITS
        fold = pallas_bitlife.fold_factor(nw)
        # Overlap and pipeline share the split geometry: the interior
        # kernel needs an aligned row tile clear of both k-row bands.
        split = overlap or pipeline
        split_name = "pipeline" if pipeline else "overlap"
        if fold > 1:
            # Narrow shard: evolve in the lane-folded [h/f, f*nw] layout
            # (see fold_rows) so the kernel still fills whole 128-lane
            # tiles — the fix for BASELINE config 3's 16x16-mesh shard
            # width, where nw = 32.  The kernel's group-local lane rolls
            # keep the fold exact, so the only constraints are geometric.
            feasible = pallas_bitlife.fold_feasible(
                h, fold, split, halo_depth
            )
            if not feasible:
                if not pallas_common.interpret():
                    raise ValueError(
                        f"shard width {w} = {nw} packed words does not "
                        f"fill whole 128-lane tiles; lane-folding x{fold} "
                        f"lifts that but needs shard height divisible by "
                        f"{fold * 8} (got {h})"
                        + (
                            f" and, in {split_name} mode, folded height "
                            f"h/f >= 2*halo_depth + 8 = "
                            f"{2 * halo_depth + 8} (got {h // fold})"
                            if split
                            else ""
                        )
                    )
                fold = 1  # interpret mode has no lane-tiling constraint
        if h % 8 or h < halo_depth:
            raise ValueError(
                f"the sharded Pallas engine needs shard height (got {h}) "
                f"to be a multiple of 8 and >= the exchanged band depth "
                f"{halo_depth}"
            )
        if two_d and num_cols > 1 and nw < 2:
            raise ValueError(
                f"the 2-D sharded Pallas engine needs >= 2 packed words "
                f"per shard (edge-word strips), got shard width {w}"
            )
        if split and h < 2 * halo_depth + 8:
            raise ValueError(
                f"{split_name} mode needs shard height (got {h}) >= "
                f"2*halo_depth + 8 = {2 * halo_depth + 8}: the interior "
                "kernel must keep at least one aligned row tile that does "
                "not touch the exchanged band"
            )
        packed = bitlife.pack(board)
        if pipeline:
            return bitlife.unpack(local_pipeline(packed, fold))
        if fold > 1 and overlap:
            # Interior tile lives clear of both exchanged bands, so the
            # tileable extent is the folded height minus the 2k margin.
            tile = pallas_bitlife.pick_tile(
                h // fold - 2 * halo_depth, fold * nw, tile_hint
            )
            if full:
                fp = fold_rows(packed, fold)
                fp = lax.fori_loop(
                    0,
                    full,
                    lambda _, q: chunk_folded_overlap(q, tile, fold),
                    fp,
                )
                packed = unfold_rows(fp, fold)
        elif fold > 1:
            tile = pallas_bitlife.pick_tile(h // fold, fold * nw, tile_hint)
            if full:
                if tile >= halo_depth:
                    # Folded-resident loop: fold once, chunk on the folded
                    # layout (bands are lane-shifted slices of it), unfold
                    # once — no per-chunk transpose.
                    fp = fold_rows(packed, fold)
                    fp = lax.fori_loop(
                        0, full, lambda _, q: chunk_folded(q, tile, fold), fp
                    )
                    packed = unfold_rows(fp, fold)
                else:
                    packed = lax.fori_loop(
                        0,
                        full,
                        lambda _, p: chunk_folded_ext(p, tile, fold),
                        packed,
                    )
        else:
            tile = pallas_bitlife.pick_tile(
                packed.shape[0] - (2 * halo_depth if overlap else 0),
                packed.shape[1],
                tile_hint,
            )
            if overlap:
                body = chunk2d_overlap if strip_fix else chunk_overlap
            elif tile >= halo_depth:
                body = chunk2d if strip_fix else chunk
            else:
                body = chunk2d_ext if strip_fix else chunk_ext
            if full:
                packed = lax.fori_loop(
                    0, full, lambda _, p: body(p, tile), packed
                )
        if rem:
            packed = (tail2d if strip_fix else tail)(packed)
        return bitlife.unpack(packed)

    # check_vma=False: pallas_call's out ShapeDtypeStruct carries no
    # varying-mesh-axes annotation, and the kernel is already per-shard.
    spec = P(ROWS, COLS) if two_d else P(ROWS, None)
    shmapped = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=spec,
        out_specs=spec,
        check_vma=False,
    )
    return jax.jit(shmapped, donate_argnums=0)


def evolve_sharded_packed(board: jax.Array, steps: int, mesh: Mesh) -> jax.Array:
    """Evolve a dense board over ``mesh`` with the bit-packed engine.

    Placement/copy contract matches
    :func:`gol_tpu.parallel.sharded.evolve_sharded`: the caller's array is
    never consumed (see :func:`gol_tpu.parallel.sharded.place_private`).
    """
    validate_packed_geometry(board.shape, mesh)
    return compiled_evolve_packed(mesh, steps)(place_private(board, mesh))
