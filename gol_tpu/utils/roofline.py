"""Roofline/MFU attribution for the fused Pallas kernels (VERDICT r2 #4).

The flagship kernels are bitwise VPU programs, so the meaningful
"model-FLOPs-utilization" analog is **lane-ops/s against the VPU's vector
issue peak**: every op processes one int32 word = 32 cells.  This module
owns the arithmetic the benchmarks report: audited per-word op counts for
each kernel, the halo-recompute multiplier of the temporal blocking, and
the peak model.

**Peak model** (documented assumption, not vendor-published), one entry
per ``device_kind`` in :data:`VPU_LANE_OPS`; a device that is not in the
table is an error, never a default.  A TPU v5e ("TPU v5 lite")
TensorCore's VPU is an (8 sublanes × 128 lanes) vector unit with 4
independent ALUs issuing per cycle at the public 940 MHz clock:

    8 * 128 * 4 * 0.94e9 = 3.85e12 int32 lane-ops/s per chip.

Counts treat every emitted vector op (roll/shift/and/or/xor/not/select)
as one issue slot; XLA/Mosaic may fuse some (e.g. and-not) or add
copies, so reported MFU is an estimate good to ~±15%, meant to answer
"which resource binds, and how far from it are we" — not to be a cycle
count.

**Audited op counts** (per 32-cell word, per generation):

2-D B3/S23 kernel (:func:`gol_tpu.ops.pallas_bitlife._one_generation`),
per row of the window, every row of it every generation:
  - horizontal stage: 2 lane rolls + west (shift, logical shift, or = 3)
    + east (3) + full adder through the neighbour-only half adder (2 xor
    + 2 and + 1 or = 5) = **13**;
  - rule tail: two full adders on the count of 8 (10) + its twos and
    fours planes (3) + ``(ones | centre) & twos & ~fours`` (4) = **17**.
    The 4 sublane rolls that bring the rows above and below are not
    counted, as the row slices they replaced were not;
  - lane-folded variant (``groups > 1``): + 2 rolls + 2 selects per row
    = **17**/17.

The post-finalize LLO of one grid step at tile 256, k 8 agrees: 34 VALU
ops a vreg, the 28 above plus 6 selects that merge the rotates across
vregs (``scripts/kernel_llo_tally.py``).

3-D Bays-4555 word-tiled kernel
(:func:`gol_tpu.ops.pallas_bitlife3d._one_generation_wt`), per window
word: x stage (2 shifts + 3 + 3 + 5) = 13; count-of-9 (4 lane rolls +
14) = 18; count-of-27 (``_sum3_planes`` width 5: 4 full adders = 20 +
zero-folded ripple ~19) = 39; count-of-26 (``_sub_bit`` over 5 planes,
zero folds) = 13; rule match (B={5}: ~8, S={4,5}: ~17) = 25; combine 5 —
**113** total.

The temporal blocking recomputes halo bands: a tile of ``t`` rows stepped
``k`` generations steps its whole aligned window of ``t + 2*pad`` rows
(``pad``: k rounded up to 8) every generation, so useful output pays a
``(t + 2*pad)/t`` multiplier; the 3-D word tiling additionally pays
``(tw + 2)/tw`` on the word axis and ``(td + 2*pad)/td`` on the plane
axis.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

# int32 lane-ops/s per chip, keyed by ``device.device_kind`` as JAX
# reports it (see the module docstring for the v5e derivation).
VPU_LANE_OPS = {"TPU v5 lite": 8 * 128 * 4 * 0.94e9}  # ~3.85e12


def vpu_lane_ops(device_kind: Optional[str] = None) -> float:
    """The VPU issue peak of ``device_kind`` (default: this process's
    first device).  Raises on a kind with no entry in the table."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return VPU_LANE_OPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no VPU peak for device kind {device_kind!r} (known: "
            f"{sorted(VPU_LANE_OPS)})"
        ) from None

# -- XLA-HLO cost model (the static verifier's cross-check) -----------------
#
# ``Compiled.cost_analysis()`` counts elementwise HLO ops as FLOPs, counts
# every ``while`` *body* exactly once (trip counts are dynamic to XLA), and
# counts fusion recompute.  For the depth-1 XLA engines the per-generation
# count is therefore exact and auditable:
#
# - dense step (stencil.step / step_halo_rows): 4 adds (separable 3-row +
#   3-col sums) + 1 subtract + rule (==3, ==2, ==1/alive, and, or, select)
#   = 11 ops/cell (measured exactly: 45056 flops for a 4096-cell shard).
# - packed step (bitlife docstring audit): ~22 bitwise ops per 32-cell
#   word (measured exactly: 11264 flops for 512 words).
# - pack+unpack (byte-staged, counted once per evolve, not per step):
#   ~6.2 ops/cell measured on XLA CPU (weighted byte sums both ways).
#
# Deep-unrolled chunks (halo_depth > 1) and interpret-mode Pallas programs
# are NOT gateable against this model: XLA fuses the unrolled generations
# and its cost analysis counts the recompute inside each fusion, growing
# superlinearly in the unroll factor.  The verifier gates only where the
# model is exact and reports attribution elsewhere.
XLA_DENSE_FLOPS_PER_CELL = 11.0
XLA_PACKED_FLOPS_PER_WORD = 22.0
XLA_PACK_UNPACK_FLOPS_PER_CELL = 6.2
XLA_COST_DRIFT = 2.0  # flagged when measured/model leaves [1/2, 2]


def xla_flops_model(
    engine: str,
    shard_cells: int,
    take: int,
    halo_depth: int,
    sharded: bool = False,
) -> float:
    """Predicted ``cost_analysis()`` FLOPs for one compiled evolve.

    Mirrors XLA's body-counted-once accounting: generations counted =
    one loop body (``halo_depth`` unrolled generations for the blocked
    sharded engines, one for depth-1 loops) plus any remainder tail, all
    over one shard.  Naive-linear in the unroll factor — see the module
    comment for why deeper unrolls under-predict (fusion recompute) and
    are attribution-only.
    """
    if engine in ("bitpack", "pallas_bitpack"):
        words = shard_cells / BITS
        if engine == "pallas_bitpack":
            depth = 8 if halo_depth == 1 else halo_depth
            gens = min(take, depth) + (take % depth if take > depth else 0)
        else:
            gens = min(take, halo_depth) + (
                take % halo_depth if take > halo_depth else 0
            )
        per_word = (
            OPS_2D_HSUM_PER_EXT_ROW + OPS_2D_RULE_PER_OUT_ROW
            if engine == "pallas_bitpack"
            else XLA_PACKED_FLOPS_PER_WORD
        )
        return per_word * words * gens + (
            XLA_PACK_UNPACK_FLOPS_PER_CELL * shard_cells
        )
    # dense tiers (incl. the Pallas dense kernel's interpret mode)
    gens = min(take, halo_depth) + (
        take % halo_depth if take > halo_depth else 0
    )
    if not sharded:
        gens = 1  # single-device fori body is one generation
    return XLA_DENSE_FLOPS_PER_CELL * shard_cells * gens

def xla_bytes_model(engine: str, shard_cells: int) -> float:
    """Predicted I/O bytes of one compiled evolve (argument + output).

    Every engine tier keeps the dense-uint8-in/dense-uint8-out contract,
    so the compiled program's argument+output residency is 2 bytes per
    shard cell regardless of the packed interior (whose word double
    buffer is a *temp*, not an I/O argument).  This is the byte-side
    twin of :func:`xla_flops_model`: ``Compiled.memory_analysis()``'s
    argument/output sizes are gated against it within
    :data:`XLA_COST_DRIFT` (2×) for the dense tier — slack for XLA's
    padding/bookkeeping buffers, tight enough that a dropped donation or
    an accidental widening (uint8 → int32 quadruples it) cannot hide.
    """
    del engine  # one I/O contract across tiers; kept for symmetry
    return 2.0 * shard_cells


# 2-D B3/S23 fused kernel, per word (see module docstring for the audit).
OPS_2D_HSUM_PER_EXT_ROW = 13
OPS_2D_HSUM_PER_EXT_ROW_FOLDED = 17
OPS_2D_RULE_PER_OUT_ROW = 17
# 3-D Bays-4555 word-tiled kernel, per window word per generation.
OPS_3D_WT_PER_WORD = 113

BITS = 32  # cells per packed word


@dataclasses.dataclass(frozen=True)
class Roofline:
    """One kernel configuration's attribution."""

    ops_per_useful_word: float  # incl. halo recompute
    recompute_factor: float  # total windowed work / useful work
    lane_ops_per_sec: float  # at the measured cell rate
    mfu: float  # fraction of the device kind's VPU_LANE_OPS

    def as_dict(self) -> dict:
        return {
            "ops_per_useful_word": round(self.ops_per_useful_word, 2),
            "recompute_factor": round(self.recompute_factor, 3),
            "lane_ops_per_sec": float(f"{self.lane_ops_per_sec:.4g}"),
            "mfu": round(self.mfu, 3),
        }


def ops_2d_per_useful_word(tile: int, k: int, folded: bool = False) -> float:
    """Mean emitted ops per useful output word of the 2-D fused kernel.

    Each of the ``k`` generations runs both stages over the whole window
    (:func:`recompute_2d`); useful output is ``tile * k`` word-rows.
    """
    h_ops = (
        OPS_2D_HSUM_PER_EXT_ROW_FOLDED if folded else OPS_2D_HSUM_PER_EXT_ROW
    )
    return recompute_2d(tile, k) * (h_ops + OPS_2D_RULE_PER_OUT_ROW)


def recompute_2d(tile: int, k: int) -> float:
    """Windowed rows / useful rows for the 2-D temporal blocking: every
    generation steps ``tile + 2*pad`` rows, ``pad`` being k rounded up to
    the kernels' 8-row tiling."""
    pad = -(-k // 8) * 8
    return (tile + 2 * pad) / tile


def roofline_2d(
    cells_per_sec: float, tile: int, k: int, folded: bool = False,
    device_kind: Optional[str] = None,
) -> Roofline:
    ops_word = ops_2d_per_useful_word(tile, k, folded)
    lane_ops = cells_per_sec / BITS * ops_word
    # Same per-row basis as the numerator, so the factor isolates the
    # temporal-blocking recompute and never conflates fold overhead.
    flat = (
        OPS_2D_HSUM_PER_EXT_ROW_FOLDED
        if folded
        else OPS_2D_HSUM_PER_EXT_ROW
    ) + OPS_2D_RULE_PER_OUT_ROW
    return Roofline(
        ops_per_useful_word=ops_word,
        recompute_factor=ops_word / flat,
        lane_ops_per_sec=lane_ops,
        mfu=lane_ops / vpu_lane_ops(device_kind),
    )


def ops_3d_wt_per_useful_word(tile_d: int, tile_w: int, k: int) -> float:
    """Mean ops per useful word of the 3-D word-tiled kernel.

    Window at step ``j``: ``(tile_w + 2)`` words × ``tile_d + 2(k-j)``
    planes (the shrink runs on the plane axis; the ghost words are carried
    the whole way); useful output ``tile_w * tile_d * k``.
    """
    total = 0.0
    for j in range(k):
        total += (tile_w + 2) * (tile_d + 2 * (k - j)) * OPS_3D_WT_PER_WORD
    return total / (tile_w * tile_d * k)


def roofline_3d_wt(
    cells_per_sec: float, tile_d: int, tile_w: int, k: int,
    device_kind: Optional[str] = None,
) -> Roofline:
    ops_word = ops_3d_wt_per_useful_word(tile_d, tile_w, k)
    lane_ops = cells_per_sec / BITS * ops_word
    return Roofline(
        ops_per_useful_word=ops_word,
        recompute_factor=ops_word / OPS_3D_WT_PER_WORD,
        lane_ops_per_sec=lane_ops,
        mfu=lane_ops / vpu_lane_ops(device_kind),
    )


def ops_3d_roll_per_useful_word(tile_d: int, k: int) -> float:
    """Mean ops per useful word of the rolling-plane 3-D kernel.

    Plane-axis windows shrink per generation exactly like the 2-D
    temporal blocking; there is NO word-ghost term — both forms of the
    kernel (torus and band-extended) run at the shard's full x width
    with a local word wrap, which is the whole point of the r4
    restructure (the wt kernel paid ``(tw+2)/tw`` = ×1.5 at 1024³).
    """
    total = 0.0
    for j in range(k):
        total += (tile_d + 2 * (k - j)) * OPS_3D_WT_PER_WORD
    return total / (tile_d * k)


def roofline_3d_roll(
    cells_per_sec: float, tile_d: int, k: int,
    device_kind: Optional[str] = None,
) -> Roofline:
    ops_word = ops_3d_roll_per_useful_word(tile_d, k)
    lane_ops = cells_per_sec / BITS * ops_word
    return Roofline(
        ops_per_useful_word=ops_word,
        recompute_factor=ops_word / OPS_3D_WT_PER_WORD,
        lane_ops_per_sec=lane_ops,
        mfu=lane_ops / vpu_lane_ops(device_kind),
    )


def bench_roofline_3d_sharded(
    cells_per_sec: float, size: int, device_kind: Optional[str] = None
) -> Roofline:
    """Attribution for the sharded 3-D flagship at a cubic volume,
    mirroring the engine's own kernel dispatch and tile derivation
    (``sharded3d.compiled_evolve3d_pallas``'s ``local``)."""
    import inspect

    from gol_tpu.ops import pallas_bitlife3d as p3
    from gol_tpu.parallel import sharded3d

    nw = size // BITS
    # The engine's default halo_depth, read off its signature (like
    # bench_roofline_2d_ring) so the attribution cannot drift from the
    # executed configuration if the default changes.
    pad = inspect.signature(
        sharded3d.compiled_evolve3d_pallas
    ).parameters["halo_depth"].default
    # x-unsharded dispatch (the cubic single-chip/(P,1,1) case this
    # bench claim measures): the rolling kernel with NO word ghosts.
    # (x-sharded shards run the ghost-word rolling form or wt — their
    # attribution is per-shard, not this cubic helper's job.)
    roll = p3.pick_tile3d_roll(size, nw, size, pad)
    if roll >= pad:  # mirror the engine's tile >= pad feasibility gate
        return roofline_3d_roll(cells_per_sec, roll, pad, device_kind)
    wt = p3.pick_tile3d_wt(size, nw, size, pad)
    if wt is None:
        raise ValueError(
            f"no fused 3-D kernel window at size {size} — nothing to "
            "attribute"
        )
    return roofline_3d_wt(cells_per_sec, wt[0], wt[1], pad, device_kind)


def bench_roofline_2d(
    cells_per_sec: float, height: int, width: int, steps: int,
    tile_hint: int = 1024, device_kind: Optional[str] = None,
) -> Roofline:
    """Attribution for ``pallas_bitlife.evolve`` exactly as the benchmark
    runs it, via the engine's own :func:`~gol_tpu.ops.pallas_bitlife.
    blocking_plan` — the reported configuration is the executed one."""
    from gol_tpu.ops import bitlife, pallas_bitlife

    tile, k = pallas_bitlife.blocking_plan(
        height, bitlife.packed_width(width), steps, tile_hint
    )
    return roofline_2d(cells_per_sec, tile, k, device_kind=device_kind)


def bench_roofline_2d_ring(
    cells_per_sec: float, height: int, width: int, num_devices: int = 1,
    device_kind: Optional[str] = None,
) -> Roofline:
    """Attribution for the 1-D sharded ring engine
    (``packed.compiled_evolve_packed_pallas``) at its defaults, read off
    the engine's own signature, with the engine's shard-height and
    lane-fold tile derivation mirrored (packed.py ``local``)."""
    import inspect

    from gol_tpu.ops import bitlife, pallas_bitlife
    from gol_tpu.parallel import packed

    sig = inspect.signature(packed.compiled_evolve_packed_pallas)
    k = sig.parameters["halo_depth"].default
    hint = sig.parameters["tile_hint"].default
    nw = bitlife.packed_width(width)  # 1-D ring: width unsharded
    shard_h = height // num_devices
    fold = pallas_bitlife.fold_factor(nw)
    folded = fold > 1
    if folded and shard_h % (fold * 8):
        # Mirror the engine's rejection: attributing an unfoldable
        # geometry would describe a configuration that cannot run.
        raise ValueError(
            f"shard height {shard_h} is not divisible by {fold * 8}; the "
            f"ring engine cannot lane-fold this geometry (nw={nw})"
        )
    if folded:
        tile = pallas_bitlife.pick_tile(shard_h // fold, fold * nw, hint)
    else:
        tile = pallas_bitlife.pick_tile(shard_h, nw, hint)
    return roofline_2d(cells_per_sec, tile, k, folded, device_kind)
