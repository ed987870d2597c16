"""3-D Life volumes through one runtime: engine resolution, the AOT chunk
programs and the chunk loop that ``gol_tpu.cli3d`` and library callers
share.

    rt = VolumeRuntime(size=1024, rule=life3d.BAYS_4555)
    volume = rt.run(volume, 64)  # a device volume in, a device volume out

A volume is a ``[D, H, W]`` uint8 cube of edge ``size`` on a 3-torus.
``VolumeRuntime`` resolves its engine once (``auto`` picks the fastest
tier the geometry fits, :func:`_resolve_engine3d`), compiles one program
per distinct chunk length and keeps it across calls, so a second job of
the same shape compiles nothing.  ``cli3d.main`` drives the same object
chunk by chunk (:meth:`VolumeRuntime.chunk`, :meth:`VolumeRuntime.
advance`) and keeps its checkpoint, guard, preemption and telemetry
wiring around those calls.

Spans (docs/OBSERVABILITY.md) carry the 2-D runtime's names with the
attribute ``dims: 3``: ``gol.compile.<n>`` over ``gol.lower.<n>`` (attr
``cache``), ``gol.run.evolve``, ``gol.chunk`` over ``gol.dispatch`` and
``gol.fence``, and ``gol.advance``.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from gol_tpu import telemetry
from gol_tpu.ops.life3d import BAYS_4555, Rule3D
from gol_tpu.runtime import chunk_schedule
from gol_tpu.utils.timing import force_ready


def _pallas3d_sharded_fits(mesh, size: int) -> bool:
    """Whether the fused sharded 3-D kernel supports this mesh/geometry —
    mirrors :func:`gol_tpu.parallel.sharded3d.compiled_evolve3d_pallas`'s
    constraints, for ``auto`` resolution (an explicit ``--engine pallas``
    raises the real errors instead)."""
    from gol_tpu.ops import bitlife, pallas_bitlife3d
    from gol_tpu.parallel.mesh import COLS, PLANES, ROWS

    planes = mesh.shape.get(PLANES, 1)
    rows = mesh.shape.get(ROWS, 1)
    if (planes != 1 and rows != 1) or size % 128:
        return False
    band = size // (planes if rows == 1 else rows)
    nw = size // mesh.shape.get(COLS, 1) // bitlife.BITS
    return (
        band >= 8
        and nw >= 1
        and pallas_bitlife3d.pick_tile3d_wt(band, nw, size, 8) is not None
    )


def _placer(mesh):
    """Host volume -> device volume with the sharding the programs take."""
    import jax

    if mesh is None:
        return jax.device_put
    from gol_tpu.parallel import sharded3d

    sharding = sharded3d.volume_sharding(mesh)
    return lambda v: jax.device_put(v, sharding)


def _build_evolver(
    engine: str, mesh, steps: int, rule, size: int, stats: bool = False,
    shard_mode: str = "explicit", halo_depth: int = 1,
):
    """(compiled, place) for the chosen engine/mesh.

    ``compiled`` is AOT-lowered from a ShapeDtypeStruct — like
    ``GolRuntime.compile_evolvers``, compilation never executes a throwaway
    evolution — and donates its input; ``place`` puts the host volume on
    device(s) with the sharding the compiled program expects.  The lowering
    and compile run inside ``gol.compile.<steps>`` over
    ``gol.lower.<steps>`` (attr ``dims: 3``; ``cache: hit|miss`` where
    XLA compiled or read the persistent cache).

    ``stats=True`` wraps the program in the in-graph volume reductions
    (:func:`gol_tpu.telemetry.stats.wrap_evolver_3d`): the compiled
    chunk returns ``(volume, stats)`` — population/births/deaths/changed
    — with sharded volumes reduced at the global-array level (XLA
    derives the collectives; the scalars replicate to every process).
    The chunk-start volume stays live for the diff, so the wrapped form
    forfeits the input donation (one extra volume of HBM).
    """
    import jax

    def finish(fn, static, spec, place):
        if stats:
            from gol_tpu.telemetry import stats as stats_mod

            fn, static = stats_mod.wrap_evolver_3d(fn, static), ()
        # gol.compile less its gol.lower child is the XLA compile or the
        # persistent-cache read; the counters say which.
        with telemetry.trace_annotation(f"gol.compile.{steps}", dims=3) as span:
            with telemetry.trace_annotation(f"gol.lower.{steps}", dims=3):
                lowered = fn.lower(spec, *static)
            compiled = lowered.compile()
            cache = telemetry.cache_outcome(span.counts)
            if cache is not None:
                span.attrs["cache"] = cache
        return compiled, place

    spec_shape = (size, size, size)
    explicit_pallas = engine == "pallas"
    engine = _resolve_engine3d(engine, mesh, size)
    place = _placer(mesh)
    if mesh is not None:
        from gol_tpu.parallel import sharded3d

        packable = True
        try:
            sharded3d.validate_geometry3d_packed(spec_shape, mesh)
        except ValueError:
            packable = False
        if engine in ("bitpack", "pallas") and not packable:
            raise ValueError(
                f"engine {engine!r} needs the x-shard width to pack into "
                f"whole 32-cell words (size {size} over mesh "
                f"{dict(mesh.shape)})"
            )
        if engine == "pallas":
            # The fused word-tiled kernel per shard behind the two-phase
            # ring exchange; an explicit --engine pallas surfaces its
            # geometry constraints (H-unsharded mesh etc.) as clean
            # errors — auto only resolves here when the geometry fits.
            fn = sharded3d.compiled_evolve3d_pallas(mesh, steps, rule)
        elif engine == "bitpack":
            # The packed ring tier carries the temporal-blocking and
            # chunk-form knobs: --halo-depth K ships a k-deep ghost
            # shell per exchange, --shard-mode overlap/pipeline runs the
            # depth-k interior/boundary split / cross-chunk double
            # buffer (gol_tpu.parallel.halo; same forms as the 2-D
            # CLI, three ppermute phases instead of two).
            fn = sharded3d.compiled_evolve3d_packed(
                mesh, steps, rule, halo_depth, shard_mode
            )
        else:
            sharded3d.validate_geometry3d(spec_shape, mesh)
            fn = sharded3d.compiled_evolve3d(mesh, steps, rule)
        spec = jax.ShapeDtypeStruct(
            spec_shape, np.uint8, sharding=sharded3d.volume_sharding(mesh)
        )
        return finish(fn, (), spec, place)

    if engine == "pallas":
        from gol_tpu.ops import pallas_bitlife3d

        # strict only for an explicit --engine pallas: a benchmark must
        # never be silently relabeled by the VMEM fallback; 'auto' keeps
        # the silent substitution (it promises the fastest fit, not a
        # specific program).
        fn = pallas_bitlife3d.evolve3d
        static = (steps, rule, explicit_pallas)
    elif engine == "bitpack":
        from gol_tpu.ops import bitlife3d

        fn = bitlife3d.evolve3d_dense_io
        static = (steps, rule)
    else:
        from gol_tpu.ops import life3d

        fn = life3d.run3d
        static = (steps, rule)
    spec = jax.ShapeDtypeStruct(spec_shape, np.uint8)
    return finish(fn, static, spec, place)


def _resolve_engine3d(engine: str, mesh, size: int) -> str:
    """Map ``auto`` to the fastest tier this geometry supports (explicit
    choices pass through and surface their own constraint errors).

    The ONE auto policy — ``_build_evolver`` delegates here, so the
    CLI's checker-engine selection and ``_build_evolver`` cannot drift.
    ``auto`` never resolves to a Pallas configuration that would fall
    back or raise: it promises the fastest *fit*, not a specific program.
    """
    import jax

    if engine != "auto":
        return engine
    if mesh is not None:
        from gol_tpu.parallel import sharded3d

        packable = True
        try:
            sharded3d.validate_geometry3d_packed((size,) * 3, mesh)
        except ValueError:
            packable = False
        if (
            packable
            and jax.default_backend() == "tpu"
            and _pallas3d_sharded_fits(mesh, size)
        ):
            return "pallas"
        return "bitpack" if packable else "dense"
    if jax.default_backend() == "tpu" and size % 128 == 0:
        # % 128 implies the % 32 word packing; still require a kernel
        # window to actually fit scoped VMEM, else auto prefers the tier
        # that runs as asked over one that silently substitutes.
        from gol_tpu.ops import pallas_bitlife3d

        nw = size // 32
        if (
            pallas_bitlife3d.pick_tile3d(size, nw, size)
            or pallas_bitlife3d.pick_tile3d_wt(size, nw, size) is not None
        ):
            return "pallas"
    if size % 32 == 0:
        return "bitpack"
    return "dense"


@dataclasses.dataclass
class VolumeRuntime:
    """One cube torus of edge ``size`` stepped under ``rule``.

    ``engine`` is ``auto``, ``dense``, ``bitpack`` or ``pallas``;
    ``resolved`` is what ``auto`` became.  ``mesh`` (a 3-D device mesh or
    None), ``shard_mode`` and ``halo_depth`` configure the sharded tiers;
    ``stats`` makes each chunk program return ``(volume, stats)``.
    ``programs`` holds one compiled program per chunk length, kept
    across calls.
    """

    size: int
    rule: Rule3D = BAYS_4555
    engine: str = "auto"
    mesh: object = None
    shard_mode: str = "explicit"
    halo_depth: int = 1
    stats: bool = False

    def __post_init__(self) -> None:
        self.resolved = _resolve_engine3d(self.engine, self.mesh, self.size)
        self.programs: dict = {}
        self.place = _placer(self.mesh)

    def compile(self, schedule, events=None) -> dict:
        """Compile the program of each chunk length in ``schedule`` that
        is not compiled yet.  An :class:`~gol_tpu.telemetry.EventLog` in
        ``events`` receives one ``compile`` record per program built."""
        for take in set(schedule):
            if take in self.programs:
                continue
            probe = None
            if events is not None:
                from gol_tpu.batch import cache as cache_mod

                probe = cache_mod.CompileCacheProbe()
            t0 = time.perf_counter()
            # The engine as asked: auto keeps its silent-fallback
            # contract inside _build_evolver, an explicit pallas its
            # strict one.
            self.programs[take], _ = _build_evolver(
                self.engine, self.mesh, take, self.rule, self.size,
                stats=self.stats, shard_mode=self.shard_mode,
                halo_depth=self.halo_depth,
            )
            if events is not None:
                # _build_evolver lowers + compiles in one step; the
                # record carries the combined duration (and, schema v2,
                # the compiled memory footprint).
                from gol_tpu.telemetry import stats as stats_mod

                cache_hit, cache_key = probe.resolve()
                events.compile_event(
                    take,
                    0.0,
                    time.perf_counter() - t0,
                    memory=stats_mod.compiled_memory(self.programs[take]),
                    cache_hit=cache_hit,
                    cache_key=cache_key,
                )
        return self.programs

    def chunk(self, index: int, take: int, volume):
        """Step ``volume`` ``take`` generations with the compiled chunk
        program, fenced: ``gol.chunk`` (step ``index``) over
        ``gol.dispatch`` and ``gol.fence``.  Returns ``(volume, stats,
        dispatch_s, wall_s)``; ``stats`` is None unless :attr:`stats`."""
        compiled = self.programs[take]
        with telemetry.step_annotation("gol.chunk", index) as span:
            span.attrs["dims"] = 3
            with telemetry.trace_annotation("gol.dispatch", dims=3) as dispatch:
                out = compiled(volume)
            volume, stats = out if self.stats else (out, None)
            with telemetry.trace_annotation("gol.fence", dims=3) as fence:
                force_ready(volume)
        return volume, stats, dispatch.seconds, fence.t1 - dispatch.t0

    def advance(self, volume, generation: int, take: int):
        """After a chunk's fence, ``gol.advance``: the fault plane's armed
        ``board.bitflip`` entries due at the new generation (a host-side
        functional update; the un-audited path takes it silently), and
        the generation count.  Returns ``(volume, generation + take)``."""
        from gol_tpu.resilience import faults as faults_mod

        with telemetry.trace_annotation("gol.advance", dims=3):
            if faults_mod.active() is not None:
                volume = faults_mod.apply_board_faults(volume, generation + take)
        return volume, generation + take

    def run(self, volume, generations: int):
        """``generations`` steps of a device volume, in one chunk; the
        volume comes back on the device, fenced."""
        schedule = chunk_schedule(generations, generations)
        self.compile(schedule)
        generation = 0
        with telemetry.trace_annotation("gol.run.evolve", dims=3):
            for i, take in enumerate(schedule):
                volume, _, _, _ = self.chunk(i, take, volume)
                volume, generation = self.advance(volume, generation, take)
        return volume
