"""The fixed work of a 3-D Life job, for ``pallas3d_roofline``.

Device peaks stay in ``perf/peaks.py``; this file holds only what a
26-neighbour volume generation must do.

``OPS_PER_WORD_GENERATION`` -- int32 lane-ops per 32-cell word per
generation: the fewest of the audited implementations of the
26-neighbour count plus the Bays 4555 matcher.

- XLA's cost analysis of one packed ``gol_tpu.ops.bitlife3d.
  step3d_packed`` generation (Bays 4555, CPU backend, jax 0.9): 272 ops a
  word, exactly, at 64x64x2, 128x128x4 and 256x256x8 words;
- the fused kernel's audit, ``OPS_3D_WT_PER_WORD`` in
  ``gol_tpu/utils/roofline.py``: 113 ops a window word (x stage 13,
  count of 9 18, count of 27 39, count of 26 13, rule match 25,
  combine 5).

The fewer, 113, is the fixed work.  Fixed, so that a share reads the same
work whatever kernel does it: a kernel that recomputes halo planes, or
spends more ops a word, reads lower.
"""

from __future__ import annotations

#: Cells per packed 32-bit word.
BITS = 32

#: Lane-ops of one generation per 32-cell word (see the module docstring).
OPS_PER_WORD_GENERATION = 113.0


def job_ops(cells: int, generations: int) -> float:
    """Fixed lane-ops of ``generations`` generations of ``cells`` cells."""
    return cells / BITS * generations * OPS_PER_WORD_GENERATION


def job_bytes(cells: int) -> float:
    """Fixed HBM bytes of one job: its volume, packed one bit a cell, read
    once and written once."""
    return 2.0 * cells / 8.0
