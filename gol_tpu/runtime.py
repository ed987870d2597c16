"""Runtime orchestration: engine selection, compile/evolve phases, snapshots.

This is the TPU-native stand-in for the reference's L3/L4 layers:
``gol_initMaster``'s device binding + pattern dispatch
(gol-with-cuda.cu:286-328) becomes pattern init + ``jax.device_put``;
``gol_kernelLaunch``'s per-step launch/sync/swap (gol-with-cuda.cu:264-284)
becomes one ahead-of-time-compiled program holding the entire generation
loop; ``cuda_finalize`` (gol-with-cuda.cu:334-339) has no equivalent —
arrays are garbage-collected.

Every distinct chunk size is compiled *before* the timed loop starts and
checkpoint I/O happens outside it, so the reported ``TOTAL DURATION``
measures device execution only — matching what the reference measured (its
loop wall-clock, with the CUDA kernel already compiled by nvcc and no
mid-loop persistence).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from gol_tpu.models import patterns
from gol_tpu.models.state import Geometry, GolState
from gol_tpu.parallel import engine as engine_mod
from gol_tpu.parallel import mesh as mesh_mod
from gol_tpu.parallel import modes as modes_mod
from gol_tpu.parallel import packed as packed_mod
from gol_tpu.parallel import sharded as sharded_mod
from gol_tpu.utils import checkpoint as ckpt_mod
from gol_tpu.utils.timing import RunReport, Stopwatch, force_ready, maybe_profile

MESH_CHOICES = ("none", "1d", "2d")


def build_mesh(
    kind: str,
    shape: Optional[Tuple[int, int]] = None,
    allow_shrink: bool = False,
) -> Optional[Mesh]:
    """CLI-level mesh selection: shard over all visible devices.

    With ``shape`` and ``allow_shrink`` set, applies the elastic shrink
    policy (docs/RESILIENCE.md): when the board does not tile evenly
    over every visible device — the degraded-pod case, a relaunch coming
    up with fewer (or an awkward number of) chips — drop to the largest
    device count whose mesh the geometry divides instead of refusing to
    run.  The snapshot reshards onto whatever mesh results, so a
    supervised job keeps making progress on the smaller topology rather
    than burning its restart budget on a divisibility error.  (The
    policy checks the dense cell-quantum tiling; engine-specific
    constraints — packed word widths, Pallas alignment — still resolve
    downstream exactly as on a full mesh, falling back to the dense
    engine under ``auto``.)
    """
    if kind == "none":
        return None
    if kind not in ("1d", "2d"):
        raise ValueError(
            f"unknown mesh kind {kind!r}; expected one of {MESH_CHOICES}"
        )
    devices = jax.devices()
    counts = (
        range(len(devices), 0, -1)
        if allow_shrink and shape is not None
        else (len(devices),)
    )
    last_err: Optional[ValueError] = None
    for n in counts:
        if kind == "1d":
            mesh = mesh_mod.make_mesh_1d(n, devices=devices[:n])
        else:
            mesh = mesh_mod.make_mesh_2d(devices=devices[:n])
        if shape is None:
            return mesh
        try:
            mesh_mod.validate_geometry(shape, mesh)
        except ValueError as e:
            last_err = e
            continue
        if n < len(devices):
            import warnings

            warnings.warn(
                f"elastic shrink: board {shape[0]}x{shape[1]} does not "
                f"tile all {len(devices)} devices; proceeding on "
                f"{n} ({dict(mesh.shape)})",
                stacklevel=2,
            )
        return mesh
    assert last_err is not None
    raise last_err


def chunk_schedule(iterations: int, chunk: int) -> list:
    """Full chunks of ``chunk`` generations plus one tail.

    The one schedule policy behind every chunked loop (checkpoint and
    guard cadence in :class:`GolRuntime`, the 3-D driver's checkpointing)
    — shared so tail handling cannot drift between drivers.
    """
    if iterations > 0 and chunk < 1:
        raise ValueError(
            f"chunk must be >= 1 when iterations > 0 (got chunk={chunk}, "
            f"iterations={iterations})"
        )
    chunk = min(chunk, iterations) if iterations else 0
    schedule = []
    remaining = iterations
    while remaining > 0:
        take = min(chunk, remaining)
        schedule.append(take)
        remaining -= take
    return schedule


@dataclasses.dataclass
class GolRuntime:
    geometry: Geometry
    engine: str = "auto"
    halo_mode: str = "fresh"
    tile_hint: int = 512
    checkpoint_every: int = 0
    checkpoint_dir: Optional[str] = None
    mesh: Optional[Mesh] = None
    shard_mode: str = "explicit"  # shard_map+ppermute vs XLA auto-SPMD
    halo_depth: int = 1  # temporal blocking: ghost layers shipped per exchange
    rule: Optional[str] = None  # B/S rulestring; None = B3/S23 fast paths
    # Structured telemetry (gol_tpu.telemetry): per-process JSONL event
    # stream written to telemetry_dir/<run_id>.rank<k>.jsonl.  Host-side
    # only — emission happens strictly after force_ready fences and never
    # enters a compiled program (pinned by the trace-identity test).
    telemetry_dir: Optional[str] = None
    run_id: Optional[str] = None
    # In-graph simulation statistics (--stats): each chunk program is
    # wrapped in fused device reductions (population, births/deaths,
    # changed cells, boundary-band populations — psummed to the global
    # value on sharded runs) and returns (board, stats) in one launch.
    # Off (the default), the evolve programs are byte-identical to the
    # stats-less build (pinned by the trace-identity test); on, the
    # evolution itself is untouched (final grid bit-equal, pinned per
    # tier × mesh) but the chunk-start buffer stays live for the
    # births/deaths diff, so donation is forfeited: one extra board of
    # HBM.  Stats land in telemetry `stats` events and in `last_stats`.
    stats: bool = False
    # Process-tier resilience knobs (gol_tpu/resilience/,
    # docs/RESILIENCE.md) — all host-side, none touches a traced program
    # (pinned by the trace-identity tests):
    # keep_snapshots > 0 retains only the newest K *valid* snapshots
    # after each save (never the resume source); 0 keeps everything.
    keep_snapshots: int = 0
    # restart_attempt > 0 marks this run as supervised attempt N (from
    # GOL_RESTART_ATTEMPT) — stamped as a v3 `restart` telemetry event.
    restart_attempt: int = 0
    # resume_info (the dict resilience.resolve_auto_resume returns) is
    # stamped as a v3 `resume` telemetry event by open_event_log.
    resume_info: Optional[dict] = None
    # Activity-gated tier knobs (--engine activity; gol_tpu/sparse/,
    # docs/SPARSE.md).  activity_tile is the square tile edge of the
    # changed mask (0 = auto: the largest candidate dividing the
    # board/shard); activity_capacity is the worklist size as a fraction
    # of the (per-shard) tile count — a generation whose dilated active
    # set exceeds it falls back to one dense step (never wrong, never
    # worse than O(area)).  The mask itself is NOT checkpointed: resume
    # reconstructs it as all-active, which is sound and collapses to the
    # true activity after one generation (bit-identity pinned).
    activity_tile: int = 0
    activity_capacity: float = 0.25
    # Elastic-mesh knobs (docs/RESILIENCE.md):
    # reshard_at > 0 stops the run at the first chunk boundary whose
    # generation reaches it, writes a snapshot, and raises
    # resilience.ReshardPoint so the driver can replan and reload the
    # remaining generations on a different mesh (--reshard-at /
    # --reshard-mesh; the in-flight reshard drill knob).  Requires a
    # checkpoint_dir; single-process only (a multi-host job reshapes by
    # relaunching under --auto-resume, which reshards on load).
    reshard_at: int = 0
    # sharded_snapshots writes the sharded checkpoint directory format
    # even single-process (multi-host always does): the piece-table
    # format cross-topology resume repartitions, exercisable without a
    # pod.
    sharded_snapshots: bool = False
    # Out-of-core streaming tier knobs (--engine ooc; gol_tpu/ooc/,
    # docs/STREAMING.md).  The packed board lives in host RAM and
    # row-bands stream through the device under a fixed footprint:
    # ooc_budget_mb bounds the device-resident bytes (the band planner
    # inverts the three-deep-rotation footprint for the band height);
    # ooc_band_rows overrides the derived height (still validated
    # against the budget); ooc_skip_dead gates the dead-band skip
    # (a band whose one-band light cone held no live cells at sweep
    # start is neither fetched nor stepped).  All host-side: with the
    # engine unselected nothing here is consulted and every other
    # tier's programs are byte-identical (trace-identity pin).
    ooc_budget_mb: int = 256
    ooc_band_rows: int = 0
    ooc_skip_dead: bool = True
    # Live metrics endpoint (--metrics-port; docs/OBSERVABILITY.md):
    # rank 0 serves Prometheus text on 127.0.0.1:<port> (0 = ephemeral),
    # fed by the same in-process event stream the rank files get — so
    # the scrape counters can never disagree with the JSONL.  Requires
    # telemetry (the stream is the feed); host-side only, trace-
    # identity-pinned like every other observability knob.
    metrics_port: Optional[int] = None

    def __post_init__(self) -> None:
        from gol_tpu import telemetry as telemetry_mod

        with telemetry_mod.trace_annotation("gol.runtime.init"):
            self._configure()

    def _configure(self) -> None:
        """Validate the knobs, resolve the engine and check the mesh."""
        if self.checkpoint_every and not self.checkpoint_dir:
            self.checkpoint_dir = "checkpoints"
        self._rule = None
        if self.rule is not None:
            from gol_tpu.ops import rules as rules_mod

            parsed = rules_mod.parse_rulestring(self.rule)
            if parsed != rules_mod.CONWAY:
                self._rule = parsed
        self._resolved = (
            self._resolve_auto() if self.engine == "auto" else self.engine
        )
        # Which engine may run with which rule, mesh, halo mode, shard
        # mode and depth: one authority (gol_tpu.parallel.modes).  The
        # geometry limits follow.
        modes_mod.check_run(
            self.engine,
            self._resolved,
            halo_mode=self.halo_mode,
            shard_mode=self.shard_mode,
            halo_depth=self.halo_depth,
            custom_rule=self._rule is not None,
            mesh=(
                None if self.mesh is None
                else "2d" if mesh_mod.COLS in self.mesh.axis_names
                else "1d"
            ),
            reshard=self.reshard_at > 0,
        )
        if self._resolved == "activity":
            self._init_activity()
        if self._resolved == "ooc":
            self._init_ooc()
        if self.halo_depth > 1 and self.mesh is not None:
            rows = self.mesh.shape.get(mesh_mod.ROWS, 1)
            cols = self.mesh.shape.get(mesh_mod.COLS, 1)
            shard_h = self.geometry.global_height // rows
            shard_w = self.geometry.global_width // cols
            # A 2-D mesh halo-extends the width axis even when its cols
            # axis has size 1 (the ring degenerates to the local wrap), so
            # the depth limit applies to both shard extents.  The packed
            # engine's horizontal quantum is the 32-cell word, so its
            # width-axis extent counts in words.
            two_d = mesh_mod.COLS in self.mesh.axis_names
            units = "cells"
            if self._resolved == "bitpack":
                from gol_tpu.ops import bitlife

                shard_w //= bitlife.BITS
                units = "words"
            modes_mod.check_depth(
                self.halo_depth, shard_h, shard_w, two_d, units
            )
        if self.mesh is not None:
            shape = (self.geometry.global_height, self.geometry.global_width)
            if self._resolved == "pallas_bitpack":
                if self.shard_mode in ("overlap", "pipeline"):
                    # Both split forms need the interior kernel's aligned
                    # row tile clear of the exchanged bands.
                    depth = 8 if self.halo_depth == 1 else self.halo_depth
                    shard_h = self.geometry.global_height // self.mesh.shape[
                        mesh_mod.ROWS
                    ]
                    # Narrow shards evolve lane-folded on TPU, so the
                    # interior-tile room is measured at the folded height
                    # (interpret mode falls back to fold=1 and keeps the
                    # unfolded constraint).
                    from gol_tpu.ops import bitlife, pallas_bitlife

                    cols = self.mesh.shape.get(mesh_mod.COLS, 1)
                    words = (
                        self.geometry.global_width // cols // bitlife.BITS
                    )
                    fold = (
                        pallas_bitlife.fold_factor(words)
                        if jax.default_backend() == "tpu" and words > 0
                        else 1
                    )
                    if not pallas_bitlife.fold_feasible(
                        shard_h, fold, True, depth
                    ):
                        # The shared predicate gates; the clauses below
                        # only pick the message.  A fold==1 misalignment
                        # (shard_h % 8) matches neither and falls through
                        # to the engine's own 'multiple of 8' trace-time
                        # error rather than a wrong claim here.
                        if fold > 1 and shard_h % (fold * 8):
                            raise ValueError(
                                f"narrow shards lane-fold x{fold} on TPU, "
                                f"which needs shard height ({shard_h}) "
                                f"divisible by {fold * 8}"
                            )
                        if shard_h // fold < 2 * depth + 8:
                            raise ValueError(
                                f"{self.shard_mode} mode needs shard "
                                f"height ({shard_h}"
                                + (f", folded /{fold}" if fold > 1 else "")
                                + f") >= 2*halo_depth + 8 = {2 * depth + 8}; "
                                "shrink halo_depth or use shard_mode "
                                "'explicit'"
                            )
                packed_mod.validate_packed_geometry(shape, self.mesh)
            elif self._resolved == "bitpack":
                # Mode legality (incl. the auto-SPMD rejection) already
                # ran through modes.check_run — the overlap form is no
                # longer 1-D-only: depth-k interior/boundary splits cover
                # both decompositions (gol_tpu.parallel.halo).
                packed_mod.validate_packed_geometry(shape, self.mesh)
            else:
                mesh_mod.validate_geometry(shape, self.mesh)
        # Frozen t=0 halos, populated for stale_t0 runs at board init.
        self._halos: Optional[Tuple[jax.Array, jax.Array]] = None
        if self.keep_snapshots < 0:
            raise ValueError(
                f"keep_snapshots must be >= 0, got {self.keep_snapshots} "
                "(0 keeps every snapshot)"
            )
        # Async checkpoint writer, owned by run()/run_guarded while their
        # loops are live (single-process runs only — see
        # checkpoint.AsyncSnapshotWriter).
        self._ckpt_writer = None
        # Checkpoint containment (docs/RESILIENCE.md "Retry and shed"):
        # once a snapshot write hits persistent ENOSPC past the retry
        # budget, checkpointing is shed for the rest of the run (the run
        # itself never dies for an observer/persistence failure).
        # _live_events is the run's EventLog while a loop is live — the
        # shed policy's telemetry-first sacrifice goes through it.
        self._ckpt_shed = False
        self._live_events = None
        # The snapshot this run resumed from — protected from retention
        # GC for the whole run (a rollback may still need it).
        self._resume_source: Optional[str] = None
        if self.reshard_at < 0:
            raise ValueError(
                f"reshard_at must be >= 0, got {self.reshard_at} "
                "(0 disables the in-flight reshard stop)"
            )
        if self.reshard_at > 0 and not self.checkpoint_dir:
            raise ValueError(
                "reshard_at stops through a snapshot; set checkpoint_dir "
                "(or a checkpoint cadence)"
            )
        # Cross-topology resume record (docs/RESILIENCE.md): set by
        # initial_state when the snapshot's stamped/inferred topology
        # differs from this run's mesh — the v7 `reshard` telemetry
        # event's payload, and the test surface for the planner.
        self.last_reshard: Optional[dict] = None
        # Host-int stats of the last run()'s chunks (--stats mode):
        # [{"index", "take", "generation", "population", ...}, ...].
        self.last_stats: list = []
        # Host-int activity counters of the last run()'s chunks
        # (--engine activity): [{"index", "take", "generation",
        # "active_tile_gens", "computed_tile_gens", "fallback_gens",
        # "skipped_tile_gens", ...}, ...].
        self.last_activity: list = []
        # Host-int streaming counters of the last run()'s chunks
        # (--engine ooc): [{"index", "take", "generation", "bands",
        # "visits", "skipped_bands", "bytes_h2d", "bytes_d2h",
        # "overlap_fraction", ...}, ...].
        self.last_ooc: list = []
        if self.metrics_port is not None and not self.telemetry_dir:
            raise ValueError(
                "metrics_port serves the in-process event stream, so it "
                "requires telemetry_dir (--telemetry)"
            )
        # The live run's MetricsRegistry/MetricsServer (--metrics-port);
        # the registry outlives the run for reconciliation tests, the
        # server dies with the event log.
        self.last_metrics = None
        self._metrics_server = None

    def _init_activity(self) -> None:
        """Validate + resolve the activity tier's tile/capacity/repr.

        Sets ``_act_tile`` (mask tile edge), ``_act_packed`` (bit-packed
        worklist on single-device word-aligned boards), ``_act_grid``
        (global mask grid shape) and ``_act_capacity_n`` (the per-shard
        worklist capacity K).  See docs/SPARSE.md.
        """
        from gol_tpu.ops import bitlife
        from gol_tpu.sparse import engine as sparse_engine
        from gol_tpu.sparse import mask as sparse_mask

        h, w = self.geometry.global_height, self.geometry.global_width
        if self.activity_tile:
            tile = self.activity_tile
            packed = (
                self.mesh is None
                and tile % bitlife.BITS == 0
                and w % bitlife.BITS == 0
            )
            sparse_mask.validate_tile(h, w, tile, packed)
        elif self.mesh is None:
            try:
                tile, packed = sparse_mask.pick_tile(h, w, packed=True), True
            except ValueError:
                tile, packed = sparse_mask.pick_tile(h, w, packed=False), False
        else:
            rows = self.mesh.shape[mesh_mod.ROWS]
            cols = self.mesh.shape.get(mesh_mod.COLS, 1)
            tile, packed = sparse_mask.pick_tile(h // rows, w // cols), False
        if self.mesh is not None:
            from gol_tpu.parallel import sparse as par_sparse

            par_sparse.validate_activity_geometry((h, w), self.mesh, tile)
        self._act_tile = tile
        self._act_packed = packed
        self._act_grid = sparse_mask.grid_shape(h, w, tile)
        if self.mesh is not None:
            rows = self.mesh.shape[mesh_mod.ROWS]
            cols = self.mesh.shape.get(mesh_mod.COLS, 1)
            shard_th = h // rows // tile
            shard_tw = w // cols // tile
        else:
            shard_th, shard_tw = self._act_grid
        self._act_capacity_n = sparse_engine.default_capacity(
            shard_th, shard_tw, self.activity_capacity
        )

    def _init_ooc(self) -> None:
        """Validate + resolve the out-of-core tier's plan (docs/STREAMING.md).

        Sets ``_ooc_plan`` (the :class:`gol_tpu.ooc.planner.BandPlan`).
        ``halo_depth`` is reused as the per-visit generation depth k —
        the same temporal-blocking quantum the sharded tiers ship over
        the ring, here amortizing one H2D/D2H round-trip per band.
        """
        from gol_tpu.ooc import planner as ooc_planner
        from gol_tpu.ops import bitlife

        w = self.geometry.global_width
        if w % bitlife.BITS != 0:
            raise ValueError(
                "engine 'ooc' streams the packed-board layout, which "
                f"needs the board width ({w}) to be a multiple of "
                f"{bitlife.BITS}; use 'dense' for unpacked widths"
            )
        if self.ooc_budget_mb < 0 or self.ooc_band_rows < 0:
            raise ValueError(
                "ooc_budget_mb and ooc_band_rows must be >= 0, got "
                f"{self.ooc_budget_mb} / {self.ooc_band_rows}"
            )
        self._ooc_plan = ooc_planner.plan_bands(
            self.geometry.global_height,
            w,
            self.halo_depth,
            band_rows=self.ooc_band_rows,
            budget_bytes=self.ooc_budget_mb << 20,
        )

    def _resolve_auto(self) -> str:
        """Pick the fastest engine this run's geometry and mode support.

        Every engine is bit-exact (pinned by the equivalence tests), so
        'auto' is purely a performance choice — the TPU analog of the
        reference hard-coding one CUDA kernel:

        - sharded explicit runs take the bit-packed ring engine when the
          shard width packs into whole words (8× less ppermute wire);
        - single-device fresh runs take the fused Pallas bit-packed kernel
          on TPU when the width fills whole lane tiles, else the XLA
          bit-packed engine when the width packs, else dense;
        - shard_mode 'overlap' prefers the sharded Pallas engine's overlap
          form, falling back to the XLA packed overlap (1-D) or dense;
        - stale_t0 (reference-compat) and shard_mode 'auto' are dense-only
          paths.
        """
        if self.halo_mode != "fresh":
            return "dense"
        geom = (self.geometry.global_height, self.geometry.global_width)
        if self.mesh is not None:
            if self.shard_mode == "auto":
                return "dense"  # auto-SPMD exists for the dense step only
            two_d = mesh_mod.COLS in self.mesh.axis_names
            # Overlap and pipeline share the Pallas engine's split
            # geometry (interior tile clear of both bands).
            split = self.shard_mode in ("overlap", "pipeline")
            try:
                packed_mod.validate_packed_geometry(geom, self.mesh)
            except ValueError:
                return "dense"
            if self.halo_depth > 1 and two_d:
                # The packed engine's horizontal ghost quantum is the
                # 32-cell word; if the shard is too narrow in words for the
                # requested depth, dense (cell-quantum halos) still works.
                from gol_tpu.ops import bitlife

                cols = self.mesh.shape.get(mesh_mod.COLS, 1)
                words = self.geometry.global_width // cols // bitlife.BITS
                if self.halo_depth > words:
                    return "dense"
            if jax.default_backend() == "tpu" and (
                self.halo_depth == 1 or self.halo_depth % 8 == 0
            ):
                # Fused kernel per shard when the shard geometry allows:
                # lane-filling shard width, aligned shard height, room for
                # the 8-deep exchanged ghost band (overlap additionally
                # needs an aligned interior tile clear of both bands), and
                # (2-D meshes) a band depth within the 1-word column halo's
                # bit light cone.
                from gol_tpu.ops import bitlife, pallas_bitlife

                rows = self.mesh.shape[mesh_mod.ROWS]
                cols = self.mesh.shape.get(mesh_mod.COLS, 1)
                shard_h = self.geometry.global_height // rows
                shard_w = self.geometry.global_width // cols
                depth = 8 if self.halo_depth == 1 else self.halo_depth
                min_h = 2 * depth + 8 if split else depth
                words = shard_w // bitlife.BITS
                fold = pallas_bitlife.fold_factor(words)
                # Narrow shards run lane-folded: f row groups side by
                # side in lanes, exact via the kernel's group-local rolls
                # — so BASELINE config 3's 16x16-mesh 32-word shards
                # resolve here too, in explicit AND overlap/pipeline
                # modes (r4: the folded interior kernel is
                # ppermute-independent like the unfolded one; it just
                # needs its aligned tile clear of both bands at the
                # *folded* height).  Sharded columns additionally need
                # >= 2 words for edge strips.
                fold_ok = fold == 1 or (
                    pallas_bitlife.fold_feasible(
                        shard_h, fold, split, depth
                    )
                    and (cols <= 1 or words >= 2)
                )
                if (
                    fold_ok
                    and shard_h % pallas_bitlife._ALIGN == 0
                    and shard_h >= min_h
                    and (not two_d or depth <= bitlife.BITS)
                ):
                    return "pallas_bitpack"
            # The XLA packed engine now covers every explicit/overlap/
            # pipeline geometry at any depth (the depth-k split lifted
            # the old 1-D-only overlap restriction), so a pod geometry
            # that misses the fused-Pallas gate above degrades to the
            # bit-packed ring — no dense cliff, no warning needed (the
            # r3/r4 silent-dense-fallback story ends here).
            return "bitpack"
        from gol_tpu.ops import bitlife

        if geom[1] % bitlife.BITS != 0:
            return "dense"
        if jax.default_backend() == "tpu":
            from gol_tpu.ops import pallas_bitlife

            if (
                geom[1] % (pallas_bitlife._LANE * bitlife.BITS) == 0
                and geom[0] % pallas_bitlife._ALIGN == 0
            ):
                return "pallas_bitpack"
        return "bitpack"

    # -- engine dispatch ----------------------------------------------------
    def _evolve_fn(self, steps: int):
        """Returns (jitted_fn, dynamic_args, static_args).

        The full call is ``fn(board, *dynamic_args, *static_args)``; after
        AOT lowering, the Compiled object is invoked with the dynamic args
        only.  Keeping the raw jitted function (rather than a closure) lets
        the compile phase lower from a ShapeDtypeStruct — compiling without
        executing a throwaway evolution.
        """
        name = self._resolved
        if name == "activity":
            # Activity-gated tier: the chunk program carries the changed
            # mask — fn(board, changed) -> (board, changed, activity) —
            # and the run loop threads it between chunks (docs/SPARSE.md).
            if self.mesh is not None:
                from gol_tpu.parallel import sparse as par_sparse

                return (
                    par_sparse.compiled_evolve_activity(
                        self.mesh, steps, self._act_tile,
                        self._act_capacity_n,
                    ),
                    (),
                    (),
                )
            from gol_tpu.sparse import engine as sparse_engine

            fn = (
                sparse_engine.evolve_gated_packed
                if self._act_packed
                else sparse_engine.evolve_gated_dense
            )
            return fn, (), (steps, self._act_tile, self._act_capacity_n)
        if name == "pallas_bitpack" and self.mesh is not None:
            # Fused kernel per shard over the ppermute ring; a custom rule
            # rides the same program via the kernel's generic tail.
            return (
                packed_mod.compiled_evolve_packed_pallas(
                    self.mesh,
                    steps,
                    8 if self.halo_depth == 1 else self.halo_depth,
                    self.tile_hint,
                    self._rule,
                    self.shard_mode == "overlap",
                    self.shard_mode == "pipeline",
                ),
                (),
                (),
            )
        if self._rule is not None:
            from gol_tpu.ops import rules as rules_mod

            if self.mesh is not None:
                from gol_tpu.parallel import ruled

                return (
                    ruled.compiled_evolve_rule(
                        self.mesh,
                        steps,
                        self._rule,
                        name == "bitpack",
                        self.halo_depth,
                    ),
                    (),
                    (),
                )
            if name == "pallas_bitpack":
                try:
                    from gol_tpu.ops import pallas_bitlife
                except ImportError as e:
                    # Same friendly-error contract as the Conway dispatch
                    # below for the identical engine selection.
                    raise ValueError(
                        f"engine {name!r} is not available: {e}"
                    ) from e

                return (
                    pallas_bitlife.evolve,
                    (),
                    (steps, self.tile_hint, self._rule),
                )
            if name == "bitpack":
                return rules_mod.evolve_rule_dense_io, (), (steps, self._rule)
            return rules_mod.run_rule, (), (steps, self._rule)
        if name == "dense":
            if self.mesh is not None:
                return (
                    sharded_mod.compiled_evolve(
                        self.mesh, steps, self.shard_mode, self.halo_depth
                    ),
                    (),
                    (),
                )
            if self.halo_mode == "fresh":
                return engine_mod.evolve_fresh, (), (steps,)
            top0, bottom0 = self._halos
            return (
                engine_mod.evolve_stale_with_halos,
                (top0, bottom0),
                (self.geometry.num_ranks, steps),
            )
        try:
            if name == "bitpack":
                if self.mesh is not None:
                    if (
                        self.shard_mode == "overlap"
                        and self.halo_depth == 1
                        and mesh_mod.COLS not in self.mesh.axis_names
                    ):
                        # Depth-1 1-D overlap keeps its hand-written
                        # program (byte-identical to every prior round);
                        # deeper bands and 2-D meshes run the generic
                        # interior/boundary split below.
                        return (
                            packed_mod.compiled_evolve_packed_overlap(
                                self.mesh, steps
                            ),
                            (),
                            (),
                        )
                    if self.shard_mode in ("overlap", "pipeline"):
                        return (
                            packed_mod.compiled_evolve_packed(
                                self.mesh,
                                steps,
                                self.halo_depth,
                                mode=self.shard_mode,
                            ),
                            (),
                            (),
                        )
                    return (
                        packed_mod.compiled_evolve_packed(
                            self.mesh, steps, self.halo_depth
                        ),
                        (),
                        (),
                    )
                from gol_tpu.ops import bitlife

                return bitlife.evolve_dense_io, (), (steps,)
            if name == "pallas":
                from gol_tpu.ops import pallas_step

                return pallas_step.evolve, (), (steps, self.tile_hint)
            if name == "pallas_bitpack":
                from gol_tpu.ops import pallas_bitlife

                return pallas_bitlife.evolve, (), (steps, self.tile_hint)
        except ImportError as e:
            raise ValueError(f"engine {name!r} is not available: {e}") from e
        raise AssertionError(name)

    # -- board init ---------------------------------------------------------
    def initial_state(
        self, pattern: int, resume: Optional[str] = None
    ) -> GolState:
        """World state (board + generation), from a pattern or a checkpoint.

        For stale_t0 (reference-compat) runs the frozen t=0 halos are fixed
        here: computed from the t=0 board on a fresh start, or restored from
        the snapshot on resume (re-freezing from the resumed board would
        silently change the semantics mid-run).
        """
        self._resume_source = resume or None
        self.last_reshard = None
        if resume and ckpt_mod.is_sharded(resume):
            from gol_tpu.resilience import reshard as reshard_mod

            source = reshard_mod.open_source(resume, kind="2d")
            meta = source
            if meta.num_ranks != self.geometry.num_ranks:
                raise ValueError(
                    f"checkpoint has {meta.num_ranks} ranks, run configured "
                    f"for {self.geometry.num_ranks}"
                )
            expected = (self.geometry.global_height, self.geometry.global_width)
            if meta.shape != expected:
                raise ValueError(
                    f"checkpoint board {meta.shape} != configured {expected}"
                )
            mine = None if self._rule is None else self._rule.rulestring()
            if meta.rule != mine:
                raise ValueError(
                    f"checkpoint was written by a {meta.rule or 'B3/S23'} "
                    f"run; this run is configured for {mine or 'B3/S23'} — "
                    "pass the matching --rule to resume"
                )
            if self.halo_mode == "stale_t0":
                raise ValueError(
                    "sharded checkpoints are written by fresh-halo runs "
                    "only; a stale_t0 run cannot resume from one bit-exactly"
                )
            # Elastic resume: the plan repartitions the stored pieces
            # onto THIS run's topology — each host still reads only the
            # regions its devices own (the gather-free load).  A
            # matching topology yields the identity plan and no event.
            dst = reshard_mod.MeshLayout.from_mesh(self.mesh)
            plan = source.plan_onto(dst)
            board = reshard_mod.place(source, self.mesh, plan)
            if source.layout != dst:
                self.last_reshard = dict(
                    generation=source.generation,
                    path=os.path.abspath(resume),
                    legacy_manifest=source.legacy,
                    **plan.summary(),
                )
            return GolState.create(board, source.generation)
        if resume:
            snap = ckpt_mod.load(resume)
            if snap.num_ranks != self.geometry.num_ranks:
                raise ValueError(
                    f"checkpoint has {snap.num_ranks} ranks, run configured "
                    f"for {self.geometry.num_ranks}"
                )
            expected = (self.geometry.global_height, self.geometry.global_width)
            if snap.board.shape != expected:
                raise ValueError(
                    f"checkpoint board {snap.board.shape} != configured {expected}"
                )
            mine = None if self._rule is None else self._rule.rulestring()
            if snap.rule != mine:
                # Same semantic-drift guard as the frozen halos below: a
                # resumed world must keep evolving under the rule that
                # produced it.
                raise ValueError(
                    f"checkpoint was written by a {snap.rule or 'B3/S23'} "
                    f"run; this run is configured for {mine or 'B3/S23'} — "
                    "pass the matching --rule to resume"
                )
            if self.halo_mode == "stale_t0":
                if snap.top0 is None:
                    raise ValueError(
                        "checkpoint lacks frozen halos; it was not written by "
                        "a stale_t0 run and cannot resume one bit-exactly"
                    )
                self._halos = (
                    jax.device_put(snap.top0),
                    jax.device_put(snap.bottom0),
                )
            if self.mesh is not None:
                # A whole-board snapshot landing on a mesh is a reshard
                # too (layout none → this mesh); the placement itself is
                # unchanged (shard_board in run()), but the move is
                # planned/validated and recorded like the sharded case.
                from gol_tpu.resilience import reshard as reshard_mod

                h, w = snap.board.shape
                plan = reshard_mod.plan_reshard(
                    (h, w),
                    [(0, h, 0, w)],
                    reshard_mod.MeshLayout("none"),
                    reshard_mod.MeshLayout.from_mesh(self.mesh),
                )
                self.last_reshard = dict(
                    generation=snap.generation,
                    path=os.path.abspath(resume),
                    legacy_manifest=False,
                    **plan.summary(),
                )
            return GolState.create(jax.device_put(snap.board), snap.generation)

        board_np = patterns.init_global(
            pattern, self.geometry.size, self.geometry.num_ranks
        )
        board = jax.device_put(board_np)
        if self.halo_mode == "stale_t0":
            self._halos = engine_mod.frozen_halos(board, self.geometry.num_ranks)
        return GolState.create(board, 0)

    def _save_snapshot(
        self,
        state: GolState,
        fingerprint: Optional[int] = None,
    ) -> None:
        """Persist a snapshot.

        A device-computed ``fingerprint`` (the guard audit's) skips the
        host-side recompute and — multi-host — stamps the sharded manifest
        with the global hash no single host could compute.  Multi-host
        jobs write the sharded format (each process its own pieces) and
        fence with a global barrier so no host races into the next chunk
        while files are mid-write.

        Writes run under the containment policy
        (:func:`gol_tpu.resilience.degrade.write_with_retry`): transient
        IO errors get bounded retry+backoff; persistent disk-full sheds
        telemetry first, then checkpointing itself — never the run.
        """
        from gol_tpu.resilience import degrade as degrade_mod

        if self._ckpt_shed:
            return
        top0, bottom0 = self._halos if self._halos is not None else (None, None)
        multi = jax.process_count() > 1
        rule = None if self._rule is None else self._rule.rulestring()
        if multi or (self.sharded_snapshots and self.mesh is not None):
            # Sharded format: every process writes only the rectangles its
            # devices own — no all-gather, no host ever materializes the
            # board (VERDICT r1 #4; at 65536² the old fetch_global path
            # replicated 4 GB to every host per snapshot).  stale_t0 never
            # reaches here (multi-host runs are fresh-halo by validation).
            # The manifest stamps this run's mesh layout so a future
            # resume on another topology can name the reshard it does.
            from gol_tpu.resilience import reshard as reshard_mod

            ok = degrade_mod.write_with_retry(
                lambda: ckpt_mod.save_sharded(
                    ckpt_mod.sharded_checkpoint_path(
                        self.checkpoint_dir, int(state.generation)
                    ),
                    state.board,
                    int(state.generation),
                    self.geometry.num_ranks,
                    rule=rule,
                    fingerprint=fingerprint,
                    mesh_layout=reshard_mod.MeshLayout.from_mesh(
                        self.mesh
                    ).to_dict(),
                ),
                generation=int(state.generation),
                shed_telemetry=self._shed_telemetry,
            )
            from jax.experimental import multihost_utils

            # The barrier runs even on a shed write: a rank that stopped
            # persisting must not strand its peers in the fence.
            multihost_utils.sync_global_devices("gol_checkpoint")
            if not ok:
                self._ckpt_shed = True
                return
            # Retention: after the barrier (every host's pieces are
            # durably renamed) exactly one process sweeps old snapshots.
            if self.keep_snapshots > 0 and jax.process_index() == 0:
                from gol_tpu.resilience import retention

                retention.gc_snapshots(
                    self.checkpoint_dir,
                    self.keep_snapshots,
                    kind="2d",
                    protect=(self._resume_source,),
                )
            return
        path = ckpt_mod.checkpoint_path(
            self.checkpoint_dir, int(state.generation)
        )
        kwargs = dict(
            top0=None if top0 is None else np.asarray(top0),
            bottom0=None if bottom0 is None else np.asarray(bottom0),
            fingerprint=fingerprint,
            rule=rule,
        )
        generation = int(state.generation)
        ranks = self.geometry.num_ranks
        # The host fetch stays on this thread — it is the donation fence
        # (the next chunk consumes the device buffer) and it must NOT
        # move to the writer: a background device→host transfer contends
        # with the next chunk's device execution, silently inflating the
        # reported TOTAL DURATION (measured r4: 'total' 1.9 s → 6-7 s
        # with a device-copy fence + background fetch).  Only the
        # compressed write overlaps; on the chip's own host the write,
        # not the fetch, dominates the phase.
        board_np = np.asarray(state.board)

        def write():
            ok = degrade_mod.write_with_retry(
                lambda: ckpt_mod.save(
                    path, board_np, generation, ranks, **kwargs
                ),
                generation=generation,
                shed_telemetry=self._shed_telemetry,
            )
            if not ok:
                self._ckpt_shed = True
                return
            if self.keep_snapshots > 0:
                # GC rides the same thread as the save (the writer's, or
                # this one) so it always runs after the rename it follows
                # and never races an in-flight .tmp of this process.
                from gol_tpu.resilience import retention

                retention.gc_snapshots(
                    self.checkpoint_dir,
                    self.keep_snapshots,
                    kind="2d",
                    protect=(self._resume_source,),
                )

        if self._ckpt_writer is not None:
            self._ckpt_writer.submit(write)
        else:
            write()

    def _shed_telemetry(self, reason: str) -> None:
        """The disk-full first sacrifice: ask the live event stream to
        shed (thread-safe; the stamp happens on the emitting thread)."""
        events = self._live_events
        if events is not None:
            events.request_shed("telemetry", reason)

    def _preempt(
        self,
        state: GolState,
        sw: Stopwatch,
        writer,
        events,
        fingerprint: Optional[int] = None,
        already_saved: bool = False,
    ) -> None:
        """Cooperative-preemption exit path (shared by run/run_guarded).

        Persists a final fingerprinted snapshot when a checkpoint
        directory is configured (skipped when one just landed at this
        exact boundary), fences the async writer so the snapshot is
        durably renamed *before* the process exits, emits the ``preempt``
        telemetry event, and raises :class:`gol_tpu.resilience.Preempted`
        — which the CLIs map to exit code 75 (EX_TEMPFAIL).
        """
        from gol_tpu import telemetry as telemetry_mod
        from gol_tpu import resilience

        generation = int(state.generation)
        checkpointed = already_saved
        if self.checkpoint_dir and not already_saved:
            with telemetry_mod.trace_annotation("gol.checkpoint.save"):
                with sw.phase("checkpoint"):
                    self._save_snapshot(state, fingerprint=fingerprint)
            checkpointed = True
        if writer is not None and checkpointed:
            with sw.phase("checkpoint"):
                writer.flush()
        if events is not None:
            events.preempt_event(generation, checkpointed=checkpointed)
        raise resilience.Preempted(
            generation,
            checkpoint_dir=self.checkpoint_dir if checkpointed else None,
        )

    def _reshard_stop(self, state, sw: Stopwatch, writer, remaining: int) -> None:
        """In-flight reshard stop (``reshard_at``): snapshot, then raise.

        Mirrors :meth:`_preempt`'s chunk-boundary contract — the board is
        whole and fenced, the snapshot is durably renamed before the
        raise — but hands control back to the driver via
        :class:`gol_tpu.resilience.ReshardPoint` so the remaining
        generations reload on the new mesh in this same process.
        """
        from gol_tpu import resilience
        from gol_tpu import telemetry as telemetry_mod

        if jax.process_count() > 1:
            raise ValueError(
                "reshard_at is single-process (a multi-host job reshapes "
                "by relaunching under --auto-resume, which reshards on "
                "load)"
            )
        generation = int(state.generation)
        if self.checkpoint_every <= 0:
            # No cadence: this boundary has no snapshot yet — write one.
            with telemetry_mod.trace_annotation("gol.checkpoint.save"):
                with sw.phase("checkpoint"):
                    self._save_snapshot(state)
        if writer is not None:
            with sw.phase("checkpoint"):
                writer.flush()
        if self.sharded_snapshots and self.mesh is not None:
            path = ckpt_mod.sharded_checkpoint_path(
                self.checkpoint_dir, generation
            )
        else:
            path = ckpt_mod.checkpoint_path(self.checkpoint_dir, generation)
        raise resilience.ReshardPoint(generation, path, remaining)

    # -- shared compile machinery -------------------------------------------
    def chunk_schedule(self, iterations: int, chunk: int) -> list:
        """Full chunks of ``chunk`` generations plus one tail."""
        return chunk_schedule(iterations, chunk)

    def compile_evolvers(self, board, schedule, events=None) -> dict:
        """AOT-compile one evolver per distinct chunk size in ``schedule``.

        Lowers from a ShapeDtypeStruct (no execution, no throwaway board) so
        callers' timed loops measure steady-state execution only; also warms
        the ``force_ready`` readback.  Returns ``{take: (compiled, dynamic)}``
        where the full call is ``compiled(board, *dynamic)``.  Shared by
        :meth:`run` and the guarded loop (:func:`gol_tpu.utils.guard.
        run_guarded`), so engine dispatch can never diverge between them.
        An :class:`~gol_tpu.telemetry.EventLog` in ``events`` receives one
        ``compile`` record per distinct chunk size (lowering and compile
        durations separately — on TPU the XLA compile dominates and is the
        number worth tracking across rounds — plus the compiled program's
        memory/cost analysis when the backend exposes it: peak HBM and
        argument/output/temp bytes are the real scaling limit for the
        whole-board runs, and never appeared anywhere before schema v2).

        With :attr:`stats` set, each program is the stats-wrapped form
        (:func:`gol_tpu.telemetry.stats.build_stats_evolver`) returning
        ``(board, stats)``; off, this path is byte-for-byte the PR 2 one.
        """
        from gol_tpu import telemetry as telemetry_mod

        if self.mesh is not None:
            spec = jax.ShapeDtypeStruct(
                board.shape,
                board.dtype,
                sharding=mesh_mod.board_sharding(self.mesh),
            )
        else:
            spec = jax.ShapeDtypeStruct(board.shape, board.dtype)
        specs = (spec,)
        if self._resolved == "activity":
            # The activity programs additionally take the changed-tile
            # mask (and return it — the run loop threads it through).
            import jax.numpy as jnp

            if self.mesh is not None:
                from gol_tpu.parallel import sparse as par_sparse

                mask_spec = jax.ShapeDtypeStruct(
                    self._act_grid,
                    jnp.bool_,
                    sharding=par_sparse.mask_sharding(self.mesh),
                )
            else:
                mask_spec = jax.ShapeDtypeStruct(self._act_grid, jnp.bool_)
            specs = (spec, mask_spec)
        evolvers = {}
        for take in set(schedule):
            if self.stats:
                from gol_tpu.telemetry import stats as stats_mod

                fn, dynamic = stats_mod.build_stats_evolver(self, take)
                static = ()
            else:
                fn, dynamic, static = self._evolve_fn(take)
            probe = None
            if events is not None:
                from gol_tpu.batch import cache as cache_mod

                probe = cache_mod.CompileCacheProbe()
            # gol.compile less its gol.lower child is the XLA compile or
            # the persistent-cache read; the counters say which.
            with telemetry_mod.trace_annotation(f"gol.compile.{take}") as span:
                with telemetry_mod.trace_annotation(
                    f"gol.lower.{take}"
                ) as lower:
                    lowered = fn.lower(*specs, *dynamic, *static)
                compiled = lowered.compile()
                cache = telemetry_mod.cache_outcome(span.counts)
                if cache is not None:
                    span.attrs["cache"] = cache
            evolvers[take] = (compiled, dynamic)
            if probe is not None:
                from gol_tpu.telemetry import stats as stats_mod

                # Hit or miss as the span's counters saw it; the probe
                # only says whether a cache is on and names the entry a
                # miss wrote.
                _, cache_key = probe.resolve()
                events.compile_event(
                    take,
                    lower.seconds,
                    span.t1 - lower.t1,
                    memory=stats_mod.compiled_memory(compiled),
                    cache_hit=None
                    if probe.directory is None
                    else cache == "hit",
                    cache_key=cache_key,
                )
        force_ready(board)
        return evolvers

    # -- telemetry ----------------------------------------------------------
    def open_event_log(self):
        """A fresh :class:`~gol_tpu.telemetry.EventLog` with the run header
        emitted, or ``None`` when telemetry is off.  Callers own close()."""
        if not self.telemetry_dir:
            return None
        from gol_tpu import telemetry as telemetry_mod

        events = telemetry_mod.EventLog(self.telemetry_dir, run_id=self.run_id)
        # Arm the black box for this run: dumps land next to the stream
        # (unhandled exception, fault-plane crash.exit — the signal
        # triggers belong to entry points that own their handlers).
        telemetry_mod.blackbox.install(
            self.telemetry_dir,
            run_id=events.run_id,
            process_index=events.process_index,
        )
        if self.metrics_port is not None and jax.process_index() == 0:
            # Attach before the header emits so the registry sees every
            # record; the server rides events.close() (rank 0 only — the
            # scrape surface is one endpoint, like the printed report).
            from gol_tpu.telemetry import metrics as metrics_mod

            self.last_metrics, self._metrics_server = (
                metrics_mod.serve_event_metrics(events, self.metrics_port)
            )
        mesh_shape = None if self.mesh is None else dict(self.mesh.shape)
        events.run_header(
            dict(
                driver="2d",
                engine=self.engine,
                resolved_engine=self._resolved,
                mesh=mesh_shape,
                shard_mode=self.shard_mode,
                halo_mode=self.halo_mode,
                halo_depth=self.halo_depth,
                rule=self.rule,
                height=self.geometry.global_height,
                width=self.geometry.global_width,
                num_ranks=self.geometry.num_ranks,
                checkpoint_every=self.checkpoint_every,
            )
        )
        if self.restart_attempt > 0:
            events.restart_event(self.restart_attempt)
        if self.resume_info is not None and self.resume_info.get("path"):
            events.resume_event(
                generation=self.resume_info["generation"],
                path=self.resume_info["path"],
                fallback=bool(self.resume_info.get("fallback")),
                skipped=self.resume_info.get("skipped") or [],
            )
        if self.last_reshard is not None:
            # Cross-topology resume happened (schema v7): record the
            # src/dst topologies and the validated plan's accounting.
            events.reshard_event(**self.last_reshard)
        return events

    def _initial_activity_mask(self):
        """The all-active changed mask (run start AND resume: the mask
        is never checkpointed — all-ones is a sound superset that
        collapses to the true activity after one generation)."""
        import jax.numpy as jnp

        if self.mesh is not None:
            from gol_tpu.parallel import sparse as par_sparse

            return jax.device_put(
                np.ones(self._act_grid, bool),
                par_sparse.mask_sharding(self.mesh),
            )
        return jnp.ones(self._act_grid, jnp.bool_)

    def _activity_block(self, take: int, dev_act: dict) -> dict:
        """One chunk's activity telemetry block (schema v5) from the
        program's device counters."""
        th, tw = self._act_grid
        tiles = th * tw
        tile_gens = tiles * take
        active = int(dev_act["active_tile_gens"])
        computed = int(dev_act["computed_tile_gens"])
        return {
            "tile": self._act_tile,
            "tiles": tiles,
            "tile_gens": tile_gens,
            "active_tile_gens": active,
            "computed_tile_gens": computed,
            "skipped_tile_gens": tile_gens - computed,
            "fallback_gens": int(dev_act["fallback_gens"]),
            "active_fraction": active / tile_gens if tile_gens else 0.0,
        }

    def _halo_block(self, take: int) -> Optional[dict]:
        """One chunk's ``halo`` telemetry block (schema v8, sharded ring
        engines only): the exchange depth/mode actually compiled, the
        per-chunk exchange count, and the band traffic in bytes — so the
        k-vs-wire tradeoff the pipeline exists for is visible per chunk.

        ``exchange_share`` is the band bytes over the chunk's total
        shard-state + band payload — a *traffic* share (device-side
        exchange latency is not host-observable; time attribution is
        halobench's job, docs/OBSERVABILITY.md).
        """
        name = self._resolved
        if self.mesh is None or name not in (
            "dense", "bitpack", "pallas_bitpack"
        ):
            return None
        rows = self.mesh.shape.get(mesh_mod.ROWS, 1)
        cols = self.mesh.shape.get(mesh_mod.COLS, 1)
        two_d = mesh_mod.COLS in self.mesh.axis_names
        h = self.geometry.global_height // rows
        w = self.geometry.global_width // cols
        k = (
            8
            if name == "pallas_bitpack" and self.halo_depth == 1
            else self.halo_depth
        )
        if self.shard_mode == "auto":
            # XLA-derived exchanges: depth/count are the partitioner's
            # business; report the per-generation contract only.
            k = 1

        def band_bytes(d: int) -> int:
            if name == "dense":
                per_row = w  # uint8 cells
                col = 2 * d * (h + 2 * d) if two_d else 0
            elif name == "bitpack":
                per_row = (w // 32) * 4  # packed words
                col = 2 * d * (h + 2 * d) * 4 if two_d else 0
            else:  # pallas_bitpack: k-row packed band + 1-word column
                per_row = (w // 32) * 4
                col = 2 * (h + 2 * d) * 4 if two_d else 0
            return 2 * d * per_row + col

        full, rem = divmod(take, k)
        exchanges = full + (1 if rem else 0)
        chunk_bytes = full * band_bytes(k) + (band_bytes(rem) if rem else 0)
        state_bytes = h * w if name == "dense" else h * (w // 32) * 4
        payload = chunk_bytes + take * state_bytes
        return {
            "depth": k,
            "mode": self.shard_mode,
            "exchanges": exchanges,
            "band_bytes": chunk_bytes,
            "exchange_share": chunk_bytes / payload if payload else 0.0,
        }

    def chunk_utilization(self, take: int, wall_s: float):
        """Roofline fraction of one executed chunk (see telemetry module)."""
        from gol_tpu import telemetry as telemetry_mod

        if self._resolved in ("activity", "ooc"):
            # The flop model predicts dense device work; a program that
            # skips an activity-dependent fraction of it — or streams
            # bands with skip + transfer overlap (ooc) — has no honest
            # static roofline.  Report none rather than a wrong number.
            return None
        num_devices = 1 if self.mesh is None else self.mesh.devices.size
        cells = self.geometry.global_height * self.geometry.global_width
        return telemetry_mod.roofline_utilization(
            self._resolved,
            cells // max(num_devices, 1),
            take,
            self.halo_depth,
            sharded=self.mesh is not None,
            wall_s=wall_s,
        )

    # -- the out-of-core streaming tier (--engine ooc) ----------------------
    def _initial_board_host(
        self, pattern: int, resume: Optional[str] = None
    ) -> Tuple[np.ndarray, int]:
        """Host-resident board init for the ooc tier: same pattern and
        resume validation as :meth:`initial_state`, but the dense board
        never touches a device (``jax.device_put`` of a bigger-than-HBM
        board is the one thing this tier exists to avoid)."""
        self._resume_source = resume or None
        self.last_reshard = None
        if resume and ckpt_mod.is_sharded(resume):
            raise ValueError(
                "engine 'ooc' resumes from whole-board snapshots (its "
                "board is host-resident and meshless); a sharded "
                "checkpoint directory reshards through a mesh tier first"
            )
        if resume:
            snap = ckpt_mod.load(resume)
            if snap.num_ranks != self.geometry.num_ranks:
                raise ValueError(
                    f"checkpoint has {snap.num_ranks} ranks, run configured "
                    f"for {self.geometry.num_ranks}"
                )
            expected = (self.geometry.global_height, self.geometry.global_width)
            if snap.board.shape != expected:
                raise ValueError(
                    f"checkpoint board {snap.board.shape} != configured "
                    f"{expected}"
                )
            if snap.rule is not None:
                raise ValueError(
                    f"checkpoint was written by a {snap.rule} run; engine "
                    "'ooc' streams B3/S23 only — resume it on "
                    "'dense'/'bitpack' with the matching --rule"
                )
            return np.asarray(snap.board), int(snap.generation)
        board_np = patterns.init_global(
            pattern, self.geometry.size, self.geometry.num_ranks
        )
        return board_np, 0

    def _run_ooc(
        self,
        pattern: int,
        iterations: int,
        resume: Optional[str] = None,
        profile_dir: Optional[str] = None,
    ) -> Tuple[RunReport, GolState]:
        """The host-driven run loop behind ``--engine ooc``.

        Mirrors :meth:`run`'s chunk contract — schedule, telemetry,
        stats, checkpoint cadence, preemption, fault-plane drain — but
        the board stays in host RAM as a packed numpy array and each
        chunk streams row-bands through the device via
        :class:`gol_tpu.ooc.OocScheduler` (docs/STREAMING.md).  Chunk
        events carry the schema-v15 ``ooc`` block.
        """
        import time as time_mod
        import types

        from gol_tpu import telemetry as telemetry_mod
        from gol_tpu.ooc import OocScheduler
        from gol_tpu.resilience import degrade as degrade_mod
        from gol_tpu.resilience import faults as faults_mod
        from gol_tpu.telemetry import stats as tstats_mod

        if jax.process_count() > 1:
            raise ValueError(
                "engine 'ooc' is single-process: the board lives in one "
                "host's RAM and streams through one device"
            )
        plan_on = faults_mod.active() is not None
        plan = self._ooc_plan
        sw = Stopwatch()
        self.last_stats = []
        self.last_activity = []
        self.last_ooc = []
        self._ckpt_shed = False
        with sw.phase("init"):
            board_np, generation = self._initial_board_host(pattern, resume)
            sched = OocScheduler(plan, skip_dead=self.ooc_skip_dead)
            sched.load_dense(board_np)
            del board_np  # the packed host board is the state now

        schedule = self.chunk_schedule(
            iterations,
            self.checkpoint_every if self.checkpoint_every > 0 else iterations,
        )
        events = self.open_event_log()
        self._live_events = events
        sc = telemetry_mod.SpanClock() if events is not None else None

        def _drain_plane():
            if events is None:
                return
            for f in faults_mod.drain_fired():
                events.fault_event(**f)
            for d in degrade_mod.drain_reports():
                events.degraded_event(**d)

        def _host_state():
            # Dense unpack (host-side) for snapshot parity: an ooc
            # checkpoint is bit-identical to an in-core one, so resume
            # works across tiers in both directions.
            return types.SimpleNamespace(
                board=sched.dense(), generation=generation
            )

        try:
            with sw.phase("compile"):
                # Every (band height, visit depth) shape the schedule
                # needs, compiled before the timed loop (the same
                # steady-state contract as compile_evolvers).
                if events is not None:
                    def on_compile(info):
                        events.compile_event(
                            info["depth"],
                            info["lower_s"],
                            info["compile_s"],
                            memory=tstats_mod.compiled_memory(
                                info["executable"]
                            ),
                        )

                    sched.on_compile = on_compile
                depths = set()
                for take in set(schedule):
                    if take >= plan.depth:
                        depths.add(plan.depth)
                    if take % plan.depth:
                        depths.add(take % plan.depth)
                for bh in sorted(set(plan.band_heights())):
                    for kk in sorted(depths):
                        sched._program(bh, kk)

            writer = None
            if self.checkpoint_every > 0:
                writer = ckpt_mod.AsyncSnapshotWriter()
            self._ckpt_writer = writer
            try:
                with maybe_profile(profile_dir), telemetry_mod.trace_annotation(
                    "gol.run.evolve"
                ):
                    for i, take in enumerate(schedule):
                        # --stats forfeits in-place thrift the same way
                        # in-core stats forfeits donation: one extra
                        # packed board for the chunk-start diff.
                        prev_packed = (
                            sched.board.copy() if self.stats else None
                        )
                        with telemetry_mod.step_annotation("gol.chunk", i):
                            with sw.phase("total"):
                                t0 = time_mod.perf_counter()
                                rep = sched.run_chunk(take, generation)
                                dt = time_mod.perf_counter() - t0
                        generation += take
                        self.last_ooc.append(
                            dict(
                                index=i,
                                take=take,
                                generation=generation,
                                **rep,
                            )
                        )
                        if events is not None:
                            spans = sc.take()
                            extra = {"ooc": rep}
                            if spans:
                                extra["spans"] = spans
                            with sc.span("telemetry"):
                                events.chunk_event(
                                    i,
                                    take,
                                    generation,
                                    dt,
                                    self.geometry.cell_updates(take),
                                    self.chunk_utilization(take, dt),
                                    **extra,
                                )
                        if self.stats:
                            from gol_tpu.ops import stats as ops_stats

                            vals = ops_stats.ooc_chunk_stats_np(
                                prev_packed,
                                sched.board,
                                plan.bands,
                                plan.width,
                                max(1, self.halo_depth),
                            )
                            self.last_stats.append(
                                dict(
                                    index=i,
                                    take=take,
                                    generation=generation,
                                    **vals,
                                )
                            )
                            if events is not None:
                                with sc.span("telemetry"):
                                    events.stats_event(
                                        i, take, generation, vals
                                    )
                        if self.checkpoint_every > 0 and not self._ckpt_shed:
                            state = _host_state()
                            with telemetry_mod.trace_annotation(
                                "gol.checkpoint.save"
                            ):
                                with sw.phase("checkpoint"):
                                    t0 = time_mod.perf_counter()
                                    self._save_snapshot(state)
                                    ck = time_mod.perf_counter() - t0
                            if sc is not None:
                                sc.add("checkpoint", ck)
                            if events is not None:
                                with sc.span("telemetry"):
                                    events.checkpoint_event(
                                        generation,
                                        ck,
                                        int(state.board.size),
                                        overlapped=writer is not None,
                                    )
                        if plan_on:
                            faults_mod.crash_or_stall(generation)
                        _drain_plane()
                        if i < len(schedule) - 1:
                            from gol_tpu import resilience

                            if sc is None:
                                preempt_now = (
                                    resilience.agreed_preempt_requested()
                                )
                            else:
                                with sc.span("preempt_poll"):
                                    preempt_now = (
                                        resilience.agreed_preempt_requested()
                                    )
                            if preempt_now:
                                self._preempt(
                                    _host_state(),
                                    sw,
                                    writer,
                                    events,
                                    already_saved=self.checkpoint_every > 0,
                                )
                if writer is not None:
                    with sw.phase("checkpoint"):
                        writer.flush()
            finally:
                self._ckpt_writer = None
                if writer is not None:
                    writer.close()

            _drain_plane()
            report = sw.report(self.geometry.cell_updates(iterations))
            if events is not None:
                events.summary(report)
        finally:
            self._live_events = None
            if events is not None:
                events.close()
        # The returned state keeps the board HOST-resident on purpose —
        # GolState.create would device_put a board this tier exists to
        # keep off the device.  Consumers (dump paths, tests) treat it
        # as an array; np.asarray is a no-op.
        state = GolState(
            board=sched.dense(), generation=np.uint32(generation)
        )
        return report, state

    # -- main entry ---------------------------------------------------------
    def run(
        self,
        pattern: int,
        iterations: int,
        resume: Optional[str] = None,
        profile_dir: Optional[str] = None,
    ) -> Tuple[RunReport, GolState]:
        if self._resolved == "ooc":
            return self._run_ooc(pattern, iterations, resume, profile_dir)
        import time as time_mod

        from gol_tpu import telemetry as telemetry_mod
        from gol_tpu.resilience import degrade as degrade_mod
        from gol_tpu.resilience import faults as faults_mod

        plan_on = faults_mod.active() is not None
        sw = Stopwatch()
        self.last_stats = []
        self.last_activity = []
        self._ckpt_shed = False
        with telemetry_mod.trace_annotation("gol.init"):
            with sw.phase("init"):
                state = self.initial_state(pattern, resume)
                board = state.board
                act_mask = (
                    self._initial_activity_mask()
                    if self._resolved == "activity"
                    else None
                )

            # Chunk schedule: full chunks of `checkpoint_every` plus one
            # tail.
            schedule = self.chunk_schedule(
                iterations,
                self.checkpoint_every
                if self.checkpoint_every > 0
                else iterations,
            )

            if self.mesh is not None:
                board = mesh_mod.shard_board(board, self.mesh)

        events = self.open_event_log()
        self._live_events = events
        # Span attribution (schema v6): host-phase seconds between
        # force_ready fences, emitted as the `spans` block on each chunk
        # event.  Telemetry-off runs never construct the clock, so the
        # off path stays byte-for-byte the old one.
        sc = telemetry_mod.SpanClock() if events is not None else None

        def _drain_plane():
            if events is None:
                return
            for f in faults_mod.drain_fired():
                events.fault_event(**f)
            for d in degrade_mod.drain_reports():
                events.degraded_event(**d)
        try:
            with sw.phase("compile"):
                evolvers = self.compile_evolvers(board, schedule, events)

            writer = None
            if self.checkpoint_every > 0 and jax.process_count() == 1:
                # Overlap snapshot writes with the next chunk's compute;
                # the final flush (inside the checkpoint phase, so the
                # report stays honest about I/O cost that did NOT overlap)
                # fences run completion on every snapshot being durably
                # renamed.
                writer = ckpt_mod.AsyncSnapshotWriter()
            self._ckpt_writer = writer
            try:
                with maybe_profile(profile_dir), telemetry_mod.trace_annotation(
                    "gol.run.evolve"
                ):
                    for i, take in enumerate(schedule):
                        compiled, dynamic = evolvers[take]
                        dev_stats = None
                        dev_act = None
                        with telemetry_mod.step_annotation("gol.chunk", i):
                            with sw.phase("total"):
                                with telemetry_mod.trace_annotation(
                                    "gol.dispatch"
                                ) as dispatch:
                                    if act_mask is not None:
                                        out = compiled(
                                            board, act_mask, *dynamic
                                        )
                                    else:
                                        out = compiled(board, *dynamic)
                                if act_mask is not None:
                                    if self.stats:
                                        (board, act_mask, dev_act,
                                         dev_stats) = out
                                    else:
                                        board, act_mask, dev_act = out
                                else:
                                    if self.stats:
                                        board, dev_stats = out
                                    else:
                                        board = out
                                with telemetry_mod.trace_annotation(
                                    "gol.fence"
                                ) as fence:
                                    force_ready(board)
                            dt = fence.t1 - dispatch.t0
                        if sc is not None:
                            # dispatch = enqueue until the async call
                            # returns; ready = the block_until_ready
                            # fence.  Together they partition wall_s.
                            # Timed once, by the spans.
                            sc.add("dispatch", dispatch.seconds)
                            sc.add("ready", dt - dispatch.seconds)
                        if plan_on:
                            # Fault-plane SDC injection (board.bitflip):
                            # a host-side functional cell update between
                            # chunk programs — the un-audited path takes
                            # the corruption silently, which is exactly
                            # what the guard-coverage matrix proves.
                            board = faults_mod.apply_board_faults(
                                board, int(state.generation) + take
                            )
                        # Reads the generation back from the device and
                        # puts the next one there.
                        with telemetry_mod.trace_annotation("gol.advance"):
                            state = GolState.create(
                                board, int(state.generation) + take
                            )
                        act_block = None
                        if dev_act is not None:
                            # Scalar fetch after the timed fence, like
                            # the stats values below.
                            act_block = self._activity_block(take, dev_act)
                            self.last_activity.append(
                                dict(
                                    index=i,
                                    take=take,
                                    generation=int(state.generation),
                                    **act_block,
                                )
                            )
                        if events is not None:
                            extra = (
                                {"activity": act_block} if act_block else {}
                            )
                            halo_blk = self._halo_block(take)
                            if halo_blk is not None:
                                # Schema v8: the exchange accounting of
                                # this chunk's compiled ring program.
                                extra["halo"] = halo_blk
                            # The drained spans cover this chunk's
                            # dispatch/ready plus the boundary phases
                            # since the previous chunk's event; writing
                            # the event itself is timed into the NEXT
                            # chunk's block.
                            spans = sc.take()
                            if spans:
                                extra["spans"] = spans
                            with sc.span("telemetry"):
                                events.chunk_event(
                                    i,
                                    take,
                                    int(state.generation),
                                    dt,
                                    self.geometry.cell_updates(take),
                                    self.chunk_utilization(take, dt),
                                    **extra,
                                )
                        if dev_stats is not None:
                            # The scalar fetch happens after the timed
                            # fence (the same program already produced
                            # them — this moves a few dozen bytes).
                            from gol_tpu.telemetry import (
                                stats as stats_mod,
                            )

                            vals = stats_mod.stats_values(dev_stats)
                            self.last_stats.append(
                                dict(
                                    index=i,
                                    take=take,
                                    generation=int(state.generation),
                                    **vals,
                                )
                            )
                            if events is not None:
                                with sc.span("telemetry"):
                                    events.stats_event(
                                        i, take, int(state.generation), vals
                                    )
                        if self.checkpoint_every > 0 and not self._ckpt_shed:
                            with telemetry_mod.trace_annotation(
                                "gol.checkpoint.save"
                            ):
                                with sw.phase("checkpoint"):
                                    t0 = time_mod.perf_counter()
                                    self._save_snapshot(state)
                                    dt = time_mod.perf_counter() - t0
                            if sc is not None:
                                sc.add("checkpoint", dt)
                            if events is not None:
                                with sc.span("telemetry"):
                                    events.checkpoint_event(
                                        int(state.generation),
                                        dt,
                                        int(state.board.size),
                                        overlapped=writer is not None,
                                    )
                        if plan_on:
                            faults_mod.crash_or_stall(
                                int(state.generation)
                            )
                        _drain_plane()
                        if i < len(schedule) - 1:
                            # Chunk-boundary preemption poll: host-side
                            # only (the compiled programs never see it).
                            # With work remaining, stop here — the board
                            # is whole and fenced; a snapshot for this
                            # boundary either just landed or is written
                            # now.
                            from gol_tpu import resilience

                            if sc is None:
                                preempt_now = (
                                    resilience.agreed_preempt_requested()
                                )
                            else:
                                with sc.span("preempt_poll"):
                                    preempt_now = (
                                        resilience.agreed_preempt_requested()
                                    )
                            if preempt_now:
                                self._preempt(
                                    state,
                                    sw,
                                    writer,
                                    events,
                                    already_saved=self.checkpoint_every > 0,
                                )
                            if (
                                self.reshard_at > 0
                                and int(state.generation) >= self.reshard_at
                            ):
                                # In-flight reshard stop: same boundary
                                # contract as preemption, but the driver
                                # continues on a new mesh immediately.
                                self._reshard_stop(
                                    state, sw, writer,
                                    remaining=sum(schedule[i + 1 :]),
                                )
                if writer is not None:
                    with sw.phase("checkpoint"):
                        writer.flush()
            finally:
                self._ckpt_writer = None
                if writer is not None:
                    writer.close()

            # Writer-thread faults fired during the final flush surface
            # before the stream closes.
            _drain_plane()
            report = sw.report(self.geometry.cell_updates(iterations))
            if events is not None:
                events.summary(report)
        finally:
            self._live_events = None
            if events is not None:
                events.close()
        return report, state
