"""Sharded generation engines: ``shard_map`` + ``lax.ppermute`` halo rings.

TPU-native replacement for the reference's L2 distributed layer.  The
reference exchanges one ghost row with each ring neighbor per step via
nonblocking MPI point-to-point (2×``MPI_Irecv`` gol-main.c:97-100,
2×``MPI_Isend`` gol-main.c:104-107, ``MPI_Wait`` gol-main.c:110-111) with
mod-ring neighbor ids (gol-main.c:86-87) — and, due to bug B1, actually
ships stale t=0 rows forever.  Here each step's halos are sliced from the
*live* block and shifted with ``lax.ppermute`` ring permutations inside one
compiled program: fresh by construction, no tags, no request management,
ordering owned by the XLA scheduler, traffic riding ICI.

Two decompositions (SURVEY §7 steps 4 and 6):

- **1-D rows** (the reference's own layout): two ppermutes/step deliver the
  up/down ghost rows; columns wrap locally since the width axis is
  unsharded.
- **2-D blocks** (BASELINE config 3): two-phase exchange — vertical edge
  rows first, then the *halo-extended* blocks' edge columns horizontally,
  which carries the four corner cells for free (the part with no reference
  analog: MPI codes typically need 8 messages or a diagonal phase; the
  ordered two-phase does it in 4).

Three program modes:

- ``"explicit"`` — hand-placed ppermutes (the analog of the reference's
  explicit messaging), halo-extend then stencil.
- ``"overlap"`` — same exchange, but the stencil is split interior/boundary
  so the interior (the bulk) has no data dependency on the ppermutes and
  XLA's latency-hiding scheduler runs exchange and compute concurrently —
  the interior-first overlap the reference attempted with nonblocking MPI
  but forfeited by calling ``MPI_Wait`` before the kernel
  (gol-main.c:110-114).
- ``"auto"`` — XLA's SPMD partitioner derives collective-permutes from the
  sharded torus rolls: the "annotate shardings, let the compiler insert
  collectives" recipe.

The whole multi-generation loop runs inside one jitted program
(``lax.fori_loop``), so there is no per-step host round-trip — the
reference pays ``cudaDeviceSynchronize`` per step (gol-with-cuda.cu:277).
"""

from __future__ import annotations

import functools

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from gol_tpu.ops import stencil
from gol_tpu.parallel.halo import (
    check_depth,
    halo_extend,
    overlap_local_loop,
    pipelined_local_loop,
    ring,
)
from gol_tpu.parallel.mesh import COLS, ROWS, board_sharding, validate_geometry
from gol_tpu.parallel.mesh import place_private as mesh_place_private
from gol_tpu.parallel.modes import SHARD_MODES as MODES


def exchange_row_halos(block: jax.Array, num_rows: int):
    """Fresh up/down ghost rows for a row-sharded block.

    One up-shift and one down-shift ppermute — the ``previous_last_row`` /
    ``next_first_row`` of the reference (gol-main.c:11), except re-sliced
    from the live board every step (fixing B1 by construction).
    Returns (top_row[W], bottom_row[W]).
    """
    top = lax.ppermute(block[-1:], ROWS, ring(num_rows, 1))
    bottom = lax.ppermute(block[:1], ROWS, ring(num_rows, -1))
    return top[0], bottom[0]


def exchange_block_halos(block: jax.Array, num_rows: int, num_cols: int):
    """Halo-extend a 2-D-sharded block to [h+2, w+2] via two-phase ppermute.

    Phase 1 ships edge *rows* vertically; phase 2 ships the edge *columns of
    the already row-extended block* horizontally, so each corner cell makes
    two hops (vertical then horizontal) and lands correctly — no diagonal
    messages needed.  Implemented by the generic N-phase extension in
    :mod:`gol_tpu.parallel.halo` (shared with the 3-D engine).
    """
    return halo_extend(block, ((0, ROWS, num_rows), (1, COLS, num_cols)))


@functools.lru_cache(maxsize=64)
def compiled_evolve(mesh: Mesh, steps: int, mode: str, halo_depth: int = 1):
    """Build + jit the sharded evolve for (mesh, steps, mode, halo_depth).

    ``halo_depth=k > 1`` is temporal blocking (modes "explicit",
    "overlap" and "pipeline"): each exchange ships a k-deep ghost band
    and the shard then steps k generations locally, consuming one ghost
    layer per generation — 2 ppermutes per axis per k generations
    instead of per generation, at the cost of a k-wide band of redundant
    compute at shard edges (negligible for big shards, a large win when
    exchange latency dominates).  "overlap" splits each chunk
    interior/boundary so the exchange hides under the interior stencil
    (the depth-1 split generalized by
    :func:`gol_tpu.parallel.halo.overlap_local_loop`); "pipeline"
    additionally double-buffers ACROSS chunks — the loop carries
    ``(block, bands)`` and ships chunk N+1's band from chunk N's
    boundary slabs while chunk N's interior computes
    (:func:`gol_tpu.parallel.halo.pipelined_local_loop`), so no chunk
    ever starts by waiting on the ring.

    The returned function donates its input buffer (the framework's double
    buffer); callers who need the input afterwards must pass a copy.
    """
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")
    if halo_depth < 1:
        raise ValueError(f"halo_depth must be >= 1, got {halo_depth}")
    if halo_depth > 1 and mode == "auto":
        raise ValueError(
            f"halo_depth > 1 requires mode 'explicit', 'overlap' or "
            f"'pipeline' (got mode {mode!r}): auto-SPMD derives its own "
            "per-generation exchanges, so there is no band to deepen"
        )
    if mode == "auto":
        # XLA SPMD derives collective-permutes from the sharded torus rolls.
        return jax.jit(
            lambda b: lax.fori_loop(0, steps, lambda _, x: stencil.step(x), b),
            in_shardings=board_sharding(mesh),
            out_shardings=board_sharding(mesh),
            donate_argnums=0,
        )

    two_d = COLS in mesh.axis_names
    num_rows = mesh.shape[ROWS]
    num_cols = mesh.shape.get(COLS, 1)
    overlap = mode == "overlap"

    if two_d:
        phases = ((0, ROWS, num_rows), (1, COLS, num_cols))
        shrink_step = stencil.step_halo_full

        def chunk(blk, k):
            ext = halo_extend(blk, phases, depth=k)
            for _ in range(k):  # each valid-mode step consumes one layer
                ext = stencil.step_halo_full(ext)
            return ext

        def overlap_body(_, blk):
            ext = halo_extend(blk, phases)
            return stencil.step_halo_full_overlap(blk, ext)

        spec = P(ROWS, COLS)
    else:
        phases = ((0, ROWS, num_rows),)
        shrink_step = lambda ext: stencil.step_halo_rows(
            ext[1:-1], ext[0], ext[-1]
        )

        def chunk(blk, k):
            ext = halo_extend(blk, phases, depth=k)
            for _ in range(k):
                ext = stencil.step_halo_rows(ext[1:-1], ext[0], ext[-1])
            return ext

        def overlap_body(_, blk):
            top, bottom = exchange_row_halos(blk, num_rows)
            return stencil.step_halo_rows_overlap(blk, top, bottom)

        spec = P(ROWS, None)

    if mode == "pipeline":
        # Cross-chunk double buffer: the loop carries (block, bands);
        # chunk N+1's band ships from chunk N's boundary slabs while
        # chunk N's interior computes (gol_tpu.parallel.halo).
        local_loop = pipelined_local_loop(shrink_step, phases, steps, halo_depth)
    elif overlap and halo_depth > 1:
        # Depth-k interior/boundary split: the depth-1 restriction lifted
        # — the interior launch still carries no ppermute dependency.
        local_loop = overlap_local_loop(shrink_step, phases, steps, halo_depth)
    else:
        # Depth-1 explicit mode IS a one-generation chunk; depth-1
        # overlap keeps its hand-written split (byte-identical program).
        body = overlap_body if overlap else (lambda _, blk: chunk(blk, 1))

        if halo_depth == 1:
            local_loop = lambda b: lax.fori_loop(0, steps, body, b)
        else:
            full, rem = divmod(steps, halo_depth)

            def local_loop(b):
                check_depth(b.shape, phases, halo_depth)
                if full:
                    b = lax.fori_loop(
                        0, full, lambda _, x: chunk(x, halo_depth), b
                    )
                if rem:
                    b = chunk(b, rem)
                return b

    local = jax.shard_map(
        local_loop,
        mesh=mesh,
        in_specs=spec,
        out_specs=spec,
    )
    return jax.jit(local, donate_argnums=0)


def place_private(board: jax.Array, mesh: Mesh) -> jax.Array:
    """Canonically shard ``board`` in a buffer safe to donate.

    See :func:`gol_tpu.parallel.mesh.place_private` for the aliasing
    rationale.
    """
    return mesh_place_private(board, board_sharding(mesh))


def evolve_sharded(
    board: jax.Array,
    steps: int,
    mesh: Mesh,
    mode: str = "explicit",
    halo_depth: int = 1,
) -> jax.Array:
    """Evolve a board sharded over ``mesh`` for ``steps`` generations.

    The board is placed with the canonical sharding if it isn't already, and
    the caller's array is never consumed (see :func:`place_private`).
    Performance-critical callers that *want* the donation manage placement
    themselves and call :func:`compiled_evolve`.  Semantics are the correct
    torus (fresh halos) in every mode and at every ``halo_depth``.
    """
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")
    validate_geometry(board.shape, mesh)
    return compiled_evolve(mesh, steps, mode, halo_depth)(
        place_private(board, mesh)
    )


def lower_sharded(
    shape,
    dtype,
    steps: int,
    mesh: Mesh,
    mode: str = "explicit",
    halo_depth: int = 1,
):
    """AOT-lower the sharded evolve for compile-cost inspection / warmup."""
    spec = jax.ShapeDtypeStruct(shape, dtype, sharding=board_sharding(mesh))
    return compiled_evolve(mesh, steps, mode, halo_depth).lower(spec)
