"""Property-based tests (Hypothesis) — SURVEY §4's prescription.

Random geometries (including non-square, the reference's blind spot — its
index math is square-only, bugs B3/B4) and random boards, checked against
the structurally-independent NumPy oracle and against algebraic properties
of the torus step itself:

- engine == oracle on arbitrary boards/steps;
- composition: ``run(b, m+n) == run(run(b, m), n)``;
- symmetry equivariance: the torus is homogeneous and isotropic, so the
  step commutes with translations (rolls), transposition, and flips;
- packed == dense wherever the width packs.

Each property is a whole family of tests the example-based suite samples
only pointwise.
"""

from __future__ import annotations

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property-based families need Hypothesis; the example-based "
    "suite still pins each engine pointwise",
)
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax
import jax.numpy as jnp

from gol_tpu.ops import bitlife, stencil

from tests import oracle

jax.config.update("jax_platforms", "cpu")

_SETTINGS = dict(max_examples=25, deadline=None)


def _board(h, w, seed):
    return oracle.random_board(h, w, seed=seed)


dims = st.integers(min_value=4, max_value=48)
seeds = st.integers(min_value=0, max_value=2**31 - 1)
steps = st.integers(min_value=0, max_value=6)


@given(h=dims, w=dims, seed=seeds, n=steps)
@settings(**_SETTINGS)
def test_stencil_matches_oracle_any_geometry(h, w, seed, n):
    board = _board(h, w, seed)
    got = np.asarray(stencil.run(jnp.asarray(board), n))
    np.testing.assert_array_equal(got, oracle.run_torus(board, n))


@given(h=dims, w=dims, seed=seeds, m=steps, n=steps)
@settings(**_SETTINGS)
def test_step_composition(h, w, seed, m, n):
    board = jnp.asarray(_board(h, w, seed))
    a = stencil.run(jnp.array(board, copy=True), m + n)
    b = stencil.run(stencil.run(jnp.array(board, copy=True), m), n)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(h=dims, w=dims, seed=seeds, dy=st.integers(-8, 8), dx=st.integers(-8, 8))
@settings(**_SETTINGS)
def test_translation_equivariance(h, w, seed, dy, dx):
    """step(roll(b)) == roll(step(b)) — the torus has no special origin."""
    board = _board(h, w, seed)
    rolled = np.roll(board, (dy, dx), axis=(0, 1))
    a = np.asarray(stencil.step(jnp.asarray(rolled)))
    b = np.roll(np.asarray(stencil.step(jnp.asarray(board))), (dy, dx), (0, 1))
    np.testing.assert_array_equal(a, b)


@given(h=dims, w=dims, seed=seeds)
@settings(**_SETTINGS)
def test_symmetry_equivariance(h, w, seed):
    """The 8-neighbor rule is isotropic: step commutes with transpose/flips."""
    board = _board(h, w, seed)
    stepped = np.asarray(stencil.step(jnp.asarray(board)))
    np.testing.assert_array_equal(
        np.asarray(stencil.step(jnp.asarray(board.T))), stepped.T
    )
    for axis in (0, 1):
        np.testing.assert_array_equal(
            np.asarray(stencil.step(jnp.asarray(np.flip(board, axis)))),
            np.flip(stepped, axis),
        )


@given(h=dims, words=st.integers(1, 3), seed=seeds, n=st.integers(1, 4))
@settings(**_SETTINGS)
def test_packed_matches_dense_property(h, words, seed, n):
    board = _board(h, words * bitlife.BITS, seed)
    got = np.asarray(bitlife.evolve_dense_io(jnp.asarray(board), n))
    ref = np.asarray(stencil.run(jnp.asarray(board), n))
    np.testing.assert_array_equal(got, ref)


@given(h=dims, w=dims)
@settings(**_SETTINGS)
def test_dead_board_stays_dead(h, w):
    board = jnp.zeros((h, w), jnp.uint8)
    assert int(np.asarray(stencil.run(board, 3)).sum()) == 0


@given(h=st.integers(4, 32), w=st.integers(4, 32))
@settings(**_SETTINGS)
def test_full_board_dies_of_overpopulation(h, w):
    board = jnp.ones((h, w), jnp.uint8)
    assert int(np.asarray(stencil.step(board)).sum()) == 0


# -- 3-D families ------------------------------------------------------------

from gol_tpu.ops import bitlife3d, life3d  # noqa: E402

dims3 = st.integers(min_value=4, max_value=12)


@given(d=dims3, h=dims3, words=st.integers(1, 2), seed=seeds,
       n=st.integers(0, 3))
@settings(**_SETTINGS)
def test_packed3d_matches_dense_property(d, h, words, seed, n):
    vol = oracle.random_volume(d, h, words * bitlife.BITS, seed=seed)
    got = np.asarray(bitlife3d.evolve3d_dense_io(jnp.asarray(vol), n))
    ref = life3d.run3d(jnp.asarray(vol), n)
    np.testing.assert_array_equal(got, np.asarray(ref))


@given(d=dims3, seed=seeds)
@settings(**_SETTINGS)
def test_step3d_axis_permutation_equivariance(d, seed):
    """The 26-neighbor totalistic rule is isotropic: step commutes with any
    permutation of the volume axes (cube volumes)."""
    vol = oracle.random_volume(d, d, d, seed=seed)
    stepped = np.asarray(life3d.step3d(jnp.asarray(vol)))
    for perm in ((1, 0, 2), (2, 1, 0), (1, 2, 0)):
        np.testing.assert_array_equal(
            np.asarray(life3d.step3d(jnp.asarray(vol.transpose(perm)))),
            stepped.transpose(perm),
        )


@given(d=dims3, h=dims3, w=dims3, seed=seeds,
       shift=st.integers(-4, 4), axis=st.integers(0, 2))
@settings(**_SETTINGS)
def test_step3d_translation_equivariance(d, h, w, seed, shift, axis):
    vol = oracle.random_volume(d, h, w, seed=seed)
    a = np.asarray(life3d.step3d(jnp.asarray(np.roll(vol, shift, axis))))
    b = np.roll(np.asarray(life3d.step3d(jnp.asarray(vol))), shift, axis)
    np.testing.assert_array_equal(a, b)


# -- fingerprint algebra (the sharded checkpoint format's invariant) ---------


@settings(max_examples=40, deadline=None)
@given(
    h=st.integers(4, 40),
    w=st.integers(4, 40),
    seed=st.integers(0, 2**20),
    rs=st.integers(1, 39),
    cs=st.integers(1, 39),
)
def test_fingerprint_piece_additivity(h, w, seed, rs, cs):
    """Any 2x2 rectangle cover's global-offset piece fingerprints sum
    (mod 2^32) to the whole board's fingerprint — the property that lets
    a sharded checkpoint verify a global stamp without assembling the
    board."""
    from gol_tpu.utils.guard import fingerprint_np

    rs, cs = min(rs, h - 1), min(cs, w - 1)
    board = oracle.random_board(h, w, seed=seed)
    total = np.uint32(0)
    with np.errstate(over="ignore"):
        for r0, r1 in ((0, rs), (rs, h)):
            for c0, c1 in ((0, cs), (cs, w)):
                total = total + np.uint32(
                    fingerprint_np(board[r0:r1, c0:c1], r0, c0)
                )
    assert int(total) == fingerprint_np(board)


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**20),
    r0=st.integers(0, 31),
    r1=st.integers(1, 32),
    c0=st.integers(0, 63),
    c1=st.integers(1, 64),
)
def test_sharded_region_reads_any_rectangle(tmp_path_factory, seed, r0, r1, c0, c1):
    """read_sharded_region assembles arbitrary rectangles (crossing piece
    boundaries or not) byte-exactly."""
    from gol_tpu.parallel import mesh as mesh_mod
    from gol_tpu.utils import checkpoint as ckpt

    if r0 >= r1 or c0 >= c1:
        return
    tmp = tmp_path_factory.mktemp("shards")
    board = oracle.random_board(32, 64, seed=seed)
    mesh = mesh_mod.make_mesh_2d((2, 2), devices=jax.devices()[:4])
    arr = jax.device_put(jnp.asarray(board), mesh_mod.board_sharding(mesh))
    d = ckpt.sharded_checkpoint_path(str(tmp), seed)
    ckpt.save_sharded(d, arr, seed, num_ranks=1)
    meta = ckpt.load_sharded_meta(d)
    got = ckpt.read_sharded_region(d, meta, (slice(r0, r1), slice(c0, c1)))
    np.testing.assert_array_equal(got, board[r0:r1, c0:c1])


# -- r4: randomized sweep over the sharded Pallas kernel matrix --------------
#
# VERDICT r3 #5: the flagship engine's fold x band x edges x overlap x rule
# compositions were pinned only by hand-picked examples, and the
# fold/edge-repair arithmetic is exactly the kind of code a randomized
# configuration sweep breaks.  Every example compiles a fresh interpret-mode
# program (seconds each), so the family is small — but each draw comes from
# the full matrix and Hypothesis shrinks any failure to a minimal config.


@st.composite
def _flagship_configs(draw):
    kind = draw(st.sampled_from(["1d", "2d"]))
    if kind == "1d":
        rows, cols = draw(st.sampled_from([2, 4])), 1
    else:
        rows, cols = draw(st.sampled_from([(2, 2), (2, 4), (4, 1)]))
    fold = draw(st.sampled_from([2, 4, 8]))
    k = draw(st.sampled_from([8, 8, 16]))  # deep bands rarer (slower)
    overlap = draw(st.booleans())
    if overlap:
        hg = 2 * k + 8  # minimum interior-tile room, the tightest case
    else:
        hg = draw(st.sampled_from([8, 16, 24]))
    chunks = draw(st.sampled_from([1, 2]))
    rem = draw(st.sampled_from([0, 3]))
    use_rule = draw(st.sampled_from([False, False, True]))
    seed = draw(st.integers(0, 2**20))
    return kind, rows, cols, fold, hg, k, overlap, chunks, rem, use_rule, seed


@given(cfg=_flagship_configs())
@settings(max_examples=6, deadline=None)
def test_flagship_kernel_matrix_matches_oracle(cfg):
    """Random (mesh, shard words, fold, k, overlap, rule, remainder)
    configurations of the sharded Pallas engine vs the oracle."""
    from gol_tpu.ops import rules as rules_mod
    from gol_tpu.parallel import mesh as mesh_mod
    from gol_tpu.parallel import packed
    from gol_tpu.parallel.sharded import place_private

    kind, rows, cols, fold, hg, k, overlap, chunks, rem, use_rule, seed = cfg
    nw = {2: 64, 4: 32, 8: 16}[fold]  # shard words -> that lane fold
    h = rows * fold * hg
    w = cols * nw * 32
    mesh = (
        mesh_mod.make_mesh_1d(rows)
        if kind == "1d"
        else mesh_mod.make_mesh_2d(
            (rows, cols), devices=jax.devices()[: rows * cols]
        )
    )
    steps = chunks * k + rem
    rule = rules_mod.HIGHLIFE if use_rule else None
    board = oracle.random_board(h, w, seed=seed)
    fn = packed.compiled_evolve_packed_pallas(
        mesh, steps, halo_depth=k, rule=rule, overlap=overlap
    )
    got = np.asarray(fn(place_private(jnp.asarray(board), mesh)))
    if rule is None:
        ref = oracle.run_torus(board, steps)
    else:
        ref = np.asarray(
            rules_mod.run_rule(jnp.asarray(board), steps, rule)
        )
    np.testing.assert_array_equal(got, ref)


@st.composite
def _flagship3d_configs(draw):
    mesh_shape = draw(
        st.sampled_from(
            [(2, 1, 1), (1, 2, 1), (2, 1, 2), (1, 2, 2), (1, 1, 4), (4, 1, 1)]
        )
    )
    wide = mesh_shape[2] > 1 and draw(st.sampled_from([False, False, True]))
    if wide:
        # The ghosted-rolling regime (VERDICT r4 #6): a wide odd word
        # count per shard leaves tile_w=1 as the wt kernel's only word
        # tiling (word factor 3), so the dispatch provably picks
        # roll_ext_g — ghost DMA + per-plane concat + band ring jointly,
        # the composition the dryrun tier (g) pins at one hand-picked
        # shape.  Budget guard: interpret-mode volumes this wide are
        # ~0.5M cells, so the other extents stay pinned small.
        k, band_mult, lane_extent, words_per_shard = 8, 2, 16, 17
        chunks = 1
        rem = draw(st.sampled_from([0, 2]))
        rule_5766 = False
    else:
        k = draw(st.sampled_from([8, 8, 16]))
        # Shard extents: the banded axis needs >= k layers per shard.
        band_mult = draw(st.sampled_from([2, 3]))
        lane_extent = draw(st.sampled_from([16, 32]))
        words_per_shard = draw(st.sampled_from([1, 2]))
        chunks = draw(st.sampled_from([1, 2]))
        rem = draw(st.sampled_from([0, 2]))
        rule_5766 = draw(st.sampled_from([False, False, True]))
    seed = draw(st.integers(0, 2**20))
    return (
        mesh_shape, k, band_mult, lane_extent, words_per_shard, chunks,
        rem, rule_5766, seed,
    )


@given(cfg=_flagship3d_configs())
@settings(max_examples=5, deadline=None)
def test_flagship3d_kernel_matrix_matches_oracle(cfg):
    """Random (mesh, layout orientation, shard extents, k, rule,
    remainder) configurations of the sharded 3-D Pallas engine vs the
    dense oracle — the r4 counterpart of the 2-D kernel-matrix sweep,
    covering both band orientations (natural and transposed), both ext
    kernels (rolling on x-unsharded meshes, word-tiled where x is
    sharded), and the XLA remainder tail."""
    from gol_tpu.ops import life3d
    from gol_tpu.parallel import mesh as mesh_mod
    from gol_tpu.parallel import sharded3d
    from gol_tpu.parallel.mesh import place_private
    from gol_tpu.parallel.sharded3d import volume_sharding

    (mesh_shape, k, band_mult, lane_extent, words_per_shard, chunks,
     rem, rule_5766, seed) = cfg
    p, r, c = mesh_shape
    band_extent = k * band_mult
    # Natural meshes (rows == 1) band over planes with lanes = H; the
    # transposed ones (planes == 1) band over rows with lanes = D.
    if r == 1:
        d, h = p * band_extent, lane_extent
    else:
        d, h = lane_extent, r * band_extent
    w = c * words_per_shard * 32
    rule = life3d.BAYS_5766 if rule_5766 else life3d.BAYS_4555
    steps = chunks * k + rem
    rng = np.random.default_rng(seed)
    vol = (rng.random((d, h, w)) < 0.3).astype(np.uint8)
    n = p * r * c
    mesh = mesh_mod.make_mesh_3d(mesh_shape, devices=jax.devices()[:n])
    # The sweep must provably reach the ghosted rolling kernel on its
    # wide-shard draws (the engine dispatches via the same plan helper).
    if words_per_shard >= 17:
        kind, _ = sharded3d.kernel_plan3d(
            band_extent, words_per_shard, lane_extent, k, ghosted=c > 1
        )
        assert kind == "roll_g"
    got = np.asarray(
        sharded3d.compiled_evolve3d_pallas(mesh, steps, rule, k)(
            place_private(jnp.asarray(vol), volume_sharding(mesh))
        )
    )
    ref = jnp.asarray(vol)
    for _ in range(steps):
        ref = life3d.step3d(ref, rule)
    np.testing.assert_array_equal(got, np.asarray(ref))


# -- batched multi-world families (gol_tpu/batch) ----------------------------

from gol_tpu.batch import GolBatchRuntime, make_batch_mesh  # noqa: E402

batch_engines_st = st.sampled_from(["dense", "bitpack", "auto"])
batch_mesh_st = st.sampled_from(["none", "1d"])


@given(
    seed=seeds,
    n=st.integers(1, 5),
    engine=batch_engines_st,
    mesh_kind=batch_mesh_st,
    shapes=st.lists(
        st.tuples(
            st.integers(2, 6).map(lambda k: 8 * k),  # heights 16..48
            st.integers(1, 3).map(lambda k: 32 * k),  # packable widths
        ),
        min_size=2,
        max_size=6,
    ),
)
@settings(max_examples=20, deadline=None)
def test_batched_mixed_buckets_bit_equal_per_world(
    seed, n, engine, mesh_kind, shapes
):
    """A batched run of B random worlds with mixed bucket sizes is
    bit-equal per world to sequential single-world runs — across tiers
    and world-axis sharding (the tentpole's core contract)."""
    worlds = [
        oracle.random_board(h, w, seed=seed + i) for i, (h, w) in
        enumerate(shapes)
    ]
    refs = [np.asarray(stencil.run(jnp.asarray(w.copy()), n)) for w in worlds]
    brt = GolBatchRuntime(
        worlds=[w.copy() for w in worlds],
        engine=engine,
        mesh=make_batch_mesh() if mesh_kind == "1d" else None,
        bucket_quantum=32,
    )
    _, out = brt.run(n)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(out[i], ref)


@given(
    seed=seeds,
    h=st.integers(3, 40),
    w=st.integers(3, 40),
    n=st.integers(1, 4),
)
@settings(max_examples=20, deadline=None)
def test_masked_dense_step_matches_oracle_any_geometry(seed, h, w, n):
    """The padded+masked dense step at an arbitrary (h, w) inside a
    larger bucket equals the oracle on the bare board."""
    from gol_tpu.batch.engines import step_dense_masked

    board = oracle.random_board(h, w, seed=seed)
    H, W = h + 7, w + 9  # deliberately unaligned padding
    stack = np.zeros((H, W), np.uint8)
    stack[:h, :w] = board
    out = jnp.asarray(stack)
    step = jax.jit(step_dense_masked)
    for _ in range(n):
        out = step(out, h, w)
    got = np.asarray(out)
    np.testing.assert_array_equal(got[:h, :w], oracle.run_torus(board, n))
    pad = got.copy()
    pad[:h, :w] = 0
    assert not pad.any()


# -- activity-gated tier families (gol_tpu/sparse, docs/SPARSE.md) -----------

activity_dims = st.sampled_from([16, 24, 32, 48])
activity_caps = st.integers(1, 16)


@given(h=activity_dims, w=activity_dims, seed=seeds, n=steps,
       cap=activity_caps)
@settings(**_SETTINGS)
def test_activity_gated_matches_oracle_random_soups(h, w, seed, n, cap):
    """The gated worklist — any capacity, overflow fallback included —
    equals the oracle on random soups of any density."""
    from gol_tpu.sparse import engine as sparse_engine
    from gol_tpu.sparse import mask as sparse_mask

    board = _board(h, w, seed)
    th, tw = sparse_mask.grid_shape(h, w, 8)
    out, _, _ = sparse_engine.evolve_gated_dense(
        jnp.asarray(board), sparse_mask.full_mask(th, tw), n, 8, cap
    )
    np.testing.assert_array_equal(np.asarray(out), oracle.run_torus(board, n))


@given(h=activity_dims, w=activity_dims, seed=seeds)
@settings(**_SETTINGS)
def test_activity_mask_soundness_invariant(h, w, seed):
    """No live-region tile is ever outside the dilated mask: the tiles
    that change in generation t+1 are a subset of dilate(tiles that
    changed in generation t) — the invariant that makes skipping exact
    rather than approximate."""
    from gol_tpu.sparse import mask as sparse_mask

    b0 = jnp.asarray(_board(h, w, seed))
    b1 = stencil.step(b0)
    b2 = stencil.step(b1)
    changed01 = np.asarray(sparse_mask.changed_tiles_dense(b0, b1, 8))
    changed12 = np.asarray(sparse_mask.changed_tiles_dense(b1, b2, 8))
    allowed = np.asarray(sparse_mask.dilate(jnp.asarray(changed01)))
    assert not (changed12 & ~allowed).any(), (
        "a tile changed outside the dilated active set — the light-cone "
        "invariant is broken"
    )


@given(
    dy=st.integers(0, 63),
    dx=st.integers(0, 63),
    n=st.integers(1, 48),
)
@settings(max_examples=15, deadline=None)
def test_activity_sharded_glider_any_offset(dy, dx, n):
    """A glider at ANY torus offset — wrapping edges, straddling shard
    seams — evolves bit-identically under the sharded activity engine
    (the compiled program is cached across examples; only data varies)."""
    from gol_tpu.parallel import mesh as mesh_mod
    from gol_tpu.parallel import sparse as par_sparse
    from gol_tpu.models import patterns

    mesh = mesh_mod.make_mesh_1d(4)
    board0 = patterns.init_sparse_world("glider", 64, 64, (dy, dx))
    ref = oracle.run_torus(board0, n)
    fn = par_sparse.compiled_evolve_activity(mesh, n, 8, 24)
    board = mesh_mod.shard_board(jnp.asarray(board0), mesh)
    mask = jax.device_put(
        np.ones((8, 8), bool), par_sparse.mask_sharding(mesh)
    )
    out, _, _ = fn(board, mask)
    np.testing.assert_array_equal(np.asarray(out), ref)


# -- elastic-mesh reshard families (docs/RESILIENCE.md) -----------------------

from gol_tpu.resilience import reshard as rs  # noqa: E402
from gol_tpu.utils import checkpoint as ckpt_prop  # noqa: E402


@given(
    rows=st.integers(1, 5),
    cols=st.integers(1, 5),
    drows=st.integers(1, 5),
    dcols=st.integers(1, 5),
    rh=st.integers(1, 9),
    cw=st.integers(1, 41),
    seed=seeds,
)
@settings(**_SETTINGS)
def test_reshard_repartition_matches_slicing_any_geometry(
    rows, cols, drows, dcols, rh, cw, seed
):
    """Pure-geometry pin: repartitioning a random board from any src
    grid to any dst grid through the packed piece store reproduces
    plain numpy slicing — including column seams that straddle uint32
    words (``cw`` not a multiple of 32 puts every interior seam
    sub-word, driving the shift repack)."""
    h = rows * drows * rh
    w = cols * dcols * cw
    board = _board(h, w, seed)
    src = rs.MeshLayout("2d", rows, cols) if cols > 1 else (
        rs.MeshLayout("1d", rows) if rows > 1 else rs.MeshLayout("none")
    )
    dst = rs.MeshLayout("2d", drows, dcols) if dcols > 1 else (
        rs.MeshLayout("1d", drows) if drows > 1 else rs.MeshLayout("none")
    )
    src_boxes = src.boxes((h, w))
    plan = rs.plan_reshard((h, w), src_boxes, src, dst)
    store = rs.PackedStore()
    for b in src_boxes:
        store.put(b, board[b[0] : b[1], b[2] : b[3]])
    for dbox, _ in plan.moves:
        np.testing.assert_array_equal(
            store.region(dbox), board[dbox[0] : dbox[1], dbox[2] : dbox[3]]
        )
    assert plan.cells_moved == h * w


_RESHARD_LAYOUTS = {
    "none": None,
    "1d2": ("1d", (2,)),
    "1d4": ("1d", (4,)),
    "1d8": ("1d", (8,)),
    "2d2x2": ("2d", (2, 2)),
    "2d4x2": ("2d", (4, 2)),
}


def _reshard_mesh(kind):
    from gol_tpu.parallel import mesh as mesh_mod

    if kind == "none":
        return None
    axes, shape = _RESHARD_LAYOUTS[kind]
    if axes == "1d":
        return mesh_mod.make_mesh_1d(shape[0])
    return mesh_mod.make_mesh_2d(
        shape, devices=jax.devices()[: shape[0] * shape[1]]
    )


@given(
    seed=seeds,
    src_kind=st.sampled_from(
        ["none", "1d2", "1d4", "2d2x2", "2d4x2", "batch"]
    ),
    dst_kind=st.sampled_from(
        ["none", "1d2", "1d4", "1d8", "2d2x2", "2d4x2"]
    ),
    engine=st.sampled_from(["dense", "bitpack"]),
    size=st.sampled_from([48, 64]),
    m=st.integers(1, 5),
    n=st.integers(1, 5),
)
@settings(max_examples=10, deadline=None)
def test_reshard_resume_equals_straight_run(
    seed, src_kind, dst_kind, engine, size, m, n
):
    """The acceptance pin as a family: evolve m generations, snapshot in
    a random topology's format (single-file / 1-D / 2-D sharded / batch
    world), resume-reshard onto a random destination mesh, evolve n
    more — the result must equal the straight m+n oracle run.  size=48
    puts the 2-col shard seams sub-word (24-column pieces)."""
    import tempfile

    from gol_tpu.models.state import Geometry
    from gol_tpu.parallel import mesh as mesh_mod
    from gol_tpu.runtime import GolRuntime

    if size == 48:
        engine = "dense"  # bitpack tiers need word-multiple (sub)widths
    board0 = _board(size, size, seed)
    ref = oracle.run_torus(board0, m + n)
    mid = oracle.run_torus(board0, m)
    tmp = tempfile.mkdtemp()
    src_mesh = _reshard_mesh("none" if src_kind == "batch" else src_kind)
    if src_kind == "batch":
        path = ckpt_prop.batch_checkpoint_path(tmp, m)
        ckpt_prop.save_batch(path, [np.zeros_like(mid), mid], m)
    elif src_mesh is None:
        path = ckpt_prop.checkpoint_path(tmp, m)
        ckpt_prop.save(path, mid, m, 1)
    else:
        path = ckpt_prop.sharded_checkpoint_path(tmp, m)
        arr = jax.device_put(mid, mesh_mod.board_sharding(src_mesh))
        ckpt_prop.save_sharded(
            path, arr, m, 1,
            mesh_layout=rs.MeshLayout.from_mesh(src_mesh).to_dict(),
        )
    dst_mesh = _reshard_mesh(dst_kind)
    if src_kind == "batch":
        board, _, _ = rs.load_resharded(path, dst_mesh, kind="batch", world=1)
        if dst_mesh is None:
            from gol_tpu.parallel import engine as engine_mod

            out = engine_mod.evolve_fresh(jnp.asarray(board), n)
        else:
            from gol_tpu.parallel import sharded as sharded_mod

            out = sharded_mod.compiled_evolve(dst_mesh, n, "explicit", 1)(
                mesh_mod.place_private(
                    board, mesh_mod.board_sharding(dst_mesh)
                )
            )
        np.testing.assert_array_equal(np.asarray(out), ref)
        return
    rt = GolRuntime(
        geometry=Geometry(size=size, num_ranks=1),
        engine=engine,
        mesh=dst_mesh,
    )
    _, st_out = rt.run(pattern=0, iterations=n, resume=path)
    np.testing.assert_array_equal(np.asarray(st_out.board), ref)


# -- pipelined depth-k halo families (PR 9, docs/DESIGN.md) ------------------

_halo_meshes = {}


def _halo_mesh(kind):
    """none = a degenerate 1-device ring (self-ppermute seam), 1d = 4-ring,
    2d = 2×2 block grid.  Cached so the engine builders' lru_cache hits."""
    from gol_tpu.parallel import mesh as mesh_mod

    if kind not in _halo_meshes:
        if kind == "2d":
            _halo_meshes[kind] = mesh_mod.make_mesh_2d(
                (2, 2), devices=jax.devices()[:4]
            )
        else:
            n = 1 if kind == "none" else 4
            _halo_meshes[kind] = mesh_mod.make_mesh_1d(
                n, devices=jax.devices()[:n]
            )
    return _halo_meshes[kind]


@st.composite
def _halo_cfgs(draw):
    tier = draw(st.sampled_from(["dense", "bitpack"]))
    mesh_kind = draw(st.sampled_from(["none", "1d", "2d"]))
    h = draw(st.sampled_from([8, 16, 24, 48]))
    words = draw(st.sampled_from([2, 4]))
    k = draw(st.integers(1, 6))
    n = draw(st.integers(1, 10))
    mode = draw(st.sampled_from(["overlap", "pipeline"]))
    seed = draw(seeds)
    return tier, mesh_kind, h, words, k, n, mode, seed


@given(cfg=_halo_cfgs())
@settings(max_examples=20, deadline=None)
def test_pipelined_depth_k_matches_explicit_and_oracle(cfg):
    """Pipelined/overlap depth-k == explicit depth-1 == the sequential
    oracle over random (size, k, mesh none/1d/2d, tier) — remainder
    chunks, steps < k, and tiny shards (no interior to split) included;
    a k deeper than the shard extent must raise, not corrupt (the seam
    case where the ghost shell would cross two ring hops)."""
    from gol_tpu.parallel import packed as packed_mod
    from gol_tpu.parallel import sharded as sharded_mod
    from gol_tpu.parallel import mesh as mesh_mod

    tier, mesh_kind, h, words, k, n, mode, seed = cfg
    w = 32 * words
    mesh = _halo_mesh(mesh_kind)
    rows = mesh.shape["rows"]
    cols = mesh.shape.get("cols", 1)
    two_d = "cols" in mesh.axis_names
    board = _board(h, w, seed)
    place = lambda: mesh_mod.place_private(
        jnp.asarray(board), mesh_mod.board_sharding(mesh)
    )

    if tier == "dense":
        build = lambda m, kk: sharded_mod.compiled_evolve(mesh, n, m, kk)
        limits = [h // rows] + ([w // cols] if two_d else [])
    else:
        build = lambda m, kk: packed_mod.compiled_evolve_packed(
            mesh, n, kk, mode=m
        )
        limits = [h // rows] + ([words // cols] if two_d else [])

    if k > min(limits):
        with pytest.raises(ValueError, match="exceeds shard extent"):
            build(mode, k)(place())
        return

    ref = np.asarray(build("explicit", 1)(place()))
    np.testing.assert_array_equal(ref, oracle.run_torus(board, n))
    got = np.asarray(build(mode, k)(place()))
    np.testing.assert_array_equal(got, ref)


# -- out-of-core streaming (docs/STREAMING.md) -------------------------------
#
# The ooc tier re-expresses the board as host-resident row bands pushed
# through a fixed device footprint: alternating sweep direction,
# one-visit-delayed drains, a wrap buffer for the first seam, dead-band
# skipping, and a remainder-absorbing last band.  Each of those is a
# seam a pointwise test samples once; the family drives random
# (geometry, band height, visit depth, chunk schedule, sweep parity,
# skipping) through the full scheduler against the independent oracle.


def _ooc_run(board, depth, band_rows, schedule, parity, skip):
    from gol_tpu.ooc import OocScheduler, plan_bands

    h, w = board.shape
    plan = plan_bands(h, w, depth, band_rows=band_rows)
    sched = OocScheduler(plan, skip_dead=skip)
    sched.load_dense(board)
    sched._sweep_parity = parity  # random starting sweep direction
    gen = 0
    for take in schedule:
        sched.run_chunk(take, gen)
        gen += take
    return sched.dense()


@given(
    h=st.integers(min_value=8, max_value=72),
    words=st.integers(min_value=1, max_value=2),
    seed=seeds,
    depth=st.integers(min_value=1, max_value=4),
    band=st.integers(min_value=1, max_value=24),
    schedule=st.lists(
        st.integers(min_value=1, max_value=9), min_size=1, max_size=3
    ),
    parity=st.integers(min_value=0, max_value=1),
    skip=st.booleans(),
)
@settings(**_SETTINGS)
def test_ooc_streamed_matches_oracle_any_banding(
    h, words, seed, depth, band, schedule, parity, skip
):
    """Streamed == oracle over random banding, depth, chunking, sweep
    parity and dead-band skipping — remainder bands included (any h not
    a multiple of the band height exercises the absorbing last band)."""
    w = 32 * words
    h = max(h, 2 * depth + 1)  # planner floor: more rows than two k-shells
    band = max(depth, min(band, h))  # planner floor: band height >= k
    board = _board(h, w, seed)
    got = _ooc_run(board, depth, band, schedule, parity, skip)
    np.testing.assert_array_equal(got, oracle.run_torus(board, sum(schedule)))


@given(
    seam=st.integers(min_value=1, max_value=4),
    dx=st.integers(min_value=0, max_value=24),
    seed=seeds,
    depth=st.integers(min_value=1, max_value=3),
    parity=st.integers(min_value=0, max_value=1),
    n=st.integers(min_value=1, max_value=8),
)
@settings(**_SETTINGS)
def test_ooc_seam_straddling_pattern_with_skipping(seam, dx, seed, depth, parity, n):
    """A lone glider straddling a random band seam on an otherwise-dead
    board: most bands are skippable, and the pattern's light cone
    crosses the seam every sweep — exactly the read the wrap buffer and
    deferred drain protect.  Skip-on must equal skip-off equal oracle."""
    h, w, band = 60, 32, 10
    board = np.zeros((h, w), dtype=np.uint8)
    glider = np.array([[0, 1, 0], [0, 0, 1], [1, 1, 1]], dtype=np.uint8)
    r = seam * band - 1 - (seed % 2)  # straddle: rows seam*B-2..seam*B+1
    board[r:r + 3, dx:dx + 3] = glider
    ref = oracle.run_torus(board, n)
    for skip in (True, False):
        got = _ooc_run(board, depth, band, (n,), parity, skip)
        np.testing.assert_array_equal(got, ref)
