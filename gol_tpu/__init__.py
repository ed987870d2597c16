"""tpu-life: a TPU-native Game of Life framework.

A brand-new, TPU-first rebuild of the capabilities of the MPI+CUDA reference
(shoron-dutta/Game-of-Life---MPI-CUDA): torus Game of Life, five seed
patterns, spatial domain decomposition with ring halo exchange, the exact
five-argument CLI surface, per-rank world dumps, and duration/cell-update
reporting — implemented on JAX/XLA with `shard_map` + `lax.ppermute` over a
device mesh instead of MPI point-to-point, XLA stencils (with a Pallas fused
fast path and a bit-packed SWAR perf tier) instead of a CUDA kernel, and a
pure-functional double buffer via XLA input/output aliasing instead of
pointer swaps.

Layer map (mirrors SURVEY.md §1 of the reference):
  L1 CLI/driver            -> gol_tpu.cli, gol_tpu.cli3d (+ native/gol_driver.cpp)
  L2 distributed halo comm -> gol_tpu.parallel.halo (lax.ppermute rings);
                              multi-host via gol_tpu.parallel.multihost
  L3 step orchestration    -> gol_tpu.runtime, gol_tpu.volume (3-D) /
                              parallel.{sharded,packed,
                              ruled,sharded3d} engines (+ guarded loop in
                              utils.guard)
  L4 device memory/runtime -> XLA HBM arrays + donation (no explicit mgmt)
  L5 compute kernel        -> gol_tpu.ops.{stencil,bitlife,rules,life3d,
                              bitlife3d} with fused Pallas tiers
                              (pallas_step, pallas_bitlife, pallas_bitlife3d)
  L6 init patterns         -> gol_tpu.models.patterns (0-4 reference, 5-7 added)
  L7 observability/output  -> gol_tpu.utils.{io,timing,halobench,scalebench,
                              checkpoint,guard}
"""

__version__ = "0.1.0"

from gol_tpu.models.state import GolState
from gol_tpu.models import patterns
from gol_tpu.ops import stencil

__all__ = ["GolState", "patterns", "stencil", "__version__"]
