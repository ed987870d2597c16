"""Timing/observability harness.

The reference's only instrumentation is a rank-0 wall-clock pair around the
whole loop plus one printed line (``MPI_Wtime`` at gol-main.c:81-82,122 and
the report at gol-main.c:124-125).  This module reproduces that headline
metric exactly and extends it (SURVEY §5) with per-phase breakdowns, derived
throughput, and an optional ``jax.profiler`` trace.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Iterator, Optional


@dataclasses.dataclass
class RunReport:
    duration_s: float
    cell_updates: int
    phases: Dict[str, float]

    @property
    def updates_per_sec(self) -> float:
        return self.cell_updates / self.duration_s if self.duration_s > 0 else 0.0

    def duration_line(self) -> str:
        """The reference's exact report line (gol-main.c:124-125)."""
        return (
            f"TOTAL DURATION : {self.duration_s:.5f}, "
            f"number of cell updates = {self.cell_updates}"
        )

    def throughput_line(self) -> str:
        """Extension: derived throughput (the BASELINE.json metric)."""
        return f"THROUGHPUT     : {self.updates_per_sec:.4g} cell-updates/sec"


class Stopwatch:
    """Accumulates named wall-clock phases; the whole-run phase is 'total'."""

    def __init__(self) -> None:
        self.phases: Dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = self.phases.get(name, 0.0) + time.perf_counter() - t0

    def report(self, cell_updates: int, total_phase: str = "total") -> RunReport:
        return RunReport(
            duration_s=self.phases.get(total_phase, 0.0),
            cell_updates=cell_updates,
            phases=dict(self.phases),
        )


def force_ready(x) -> None:
    """Fence device completion of every array in a pytree.

    Every timed phase must end with this, or the reported ``TOTAL
    DURATION`` measures dispatch instead of execution.
    """
    import jax

    jax.block_until_ready(x)
    if jax.process_count() > 1 and any(
        not getattr(leaf, "is_fully_addressable", True)
        for leaf in jax.tree_util.tree_leaves(x)
    ):
        # A cross-process array: this process can wait only for its own
        # shards, so a barrier is the fence — mirroring the reference's
        # MPI_Barrier before the timing stop (gol-main.c:118).
        # Fully-addressable arrays skip it even in multi-process jobs:
        # they belong to a process-local computation (e.g. a scalebench
        # row only some processes run), and a global barrier would
        # deadlock against processes sitting that row out.
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("gol_force_ready")


def time_best(fn, arg_factory, repeats: int = 3) -> float:
    """Best-of-N wall-clock of ``fn(arg_factory())``, warm-compiled.

    ``arg_factory`` returns a fresh argument per call so donating functions
    never consume a buffer the next repeat needs.  One untimed call warms
    compilation; ``force_ready`` fences every timed call.  Shared by the
    halo-latency and weak-scaling harnesses.
    """
    force_ready(fn(arg_factory()))
    best = float("inf")
    for _ in range(repeats):
        arg = arg_factory()
        # Fence the factory's (async) device work — e.g. a board copy —
        # so the timed window measures fn alone, not the copy it depends on.
        force_ready(arg)
        t0 = time.perf_counter()
        out = fn(arg)
        force_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


@contextlib.contextmanager
def maybe_profile(trace_dir: Optional[str]) -> Iterator[None]:
    """Capture a jax.profiler trace when a directory is given (else no-op).

    View with TensorBoard or xprof.  The runtime enters this around the
    timed generation loop only — compilation is warmed beforehand, so the
    trace shows steady-state device execution (the TPU-native upgrade over
    the reference's single wall-clock delta).
    """
    if not trace_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
