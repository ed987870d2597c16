"""Which engine may run with which rule, mesh, halo mode, shard mode and
depth — one authority.

:func:`check_run` is the one entry point: it raises the canonical
``ValueError`` for every combination that has no program, and returns
for every one that has.  ``GolRuntime`` calls it with the requested and
the resolved engine, and the CLI calls it for ``--batch`` runs; both
keep only the geometry checks (shard extents, fold, packed widths,
tiles, budgets) beside the geometry.  The messages are pinned by
``tests/test_mode_plan.py`` and the engine suites that match them.

The sharded positive matrix (``ENGINE_MODES``):

====================  ========  =======  ====  ========
engine                explicit  overlap  auto  pipeline
====================  ========  =======  ====  ========
dense                 any k     any k    k=1   any k
bitpack               any k     any k*   --    any k
pallas_bitpack        k%8       k%8      --    k%8
activity              k=1       --       --    --
ooc                   --        --       --    --
====================  ========  =======  ====  ========

``ooc`` (the out-of-core streaming tier, docs/STREAMING.md) is
host-driven and single-process by construction — the board lives in
host RAM and bands stream through one device, so there is no sharded
ring program to pick a mode for; every (ooc, mode) cell rejects with a
message naming the legal alternatives (mesh-none ooc, or a sharded
engine).  The dense 2-D ``pallas`` kernel has no sharded path at all.

(*) the packed depth-1 overlap keeps its hand-written 1-D program;
depth-1 2-D and every deeper form run the generic interior/boundary
split in :mod:`gol_tpu.parallel.halo`.  ``pipeline`` is the cross-chunk
double buffer: the loop carries ``(block, bands)`` and ships chunk
N+1's ghost band while chunk N's interior computes.  Depth limits
against shard extents (the ghost shell must come from the immediate
ring neighbor; packed engines count the width axis in 32-cell words)
are geometry checks, kept separate in :func:`check_depth`.
"""

from __future__ import annotations

from typing import Optional

ENGINES = (
    "auto", "dense", "bitpack", "pallas", "pallas_bitpack", "activity", "ooc"
)
HALO_MODES = ("fresh", "stale_t0")
SHARD_MODES = ("explicit", "overlap", "auto", "pipeline")

#: Shard modes with a built program per (resolved) engine.
ENGINE_MODES = {
    "dense": ("explicit", "overlap", "auto", "pipeline"),
    "bitpack": ("explicit", "overlap", "pipeline"),
    "pallas_bitpack": ("explicit", "overlap", "pipeline"),
    "activity": ("explicit",),
}

#: Modes whose exchange ships a deeper-than-one-generation ghost band.
DEEP_BAND_MODES = ("explicit", "overlap", "pipeline")

#: Requested engines with a sharded path ('auto' resolves to one).
SHARDED_ENGINES = ("auto", "dense", "bitpack", "pallas_bitpack", "activity")

#: The sharded Pallas engine's column band is one 32-cell word, so on a
#: 2-D mesh its depth is bounded by the bits of that word.
PALLAS_COLUMN_BAND_DEPTH = 32


def mode_rejection(engine: str, shard_mode: str) -> Optional[str]:
    """The canonical rejection message for an (engine, mode) cell that
    has no program, or ``None`` when the combination is supported."""
    if shard_mode not in SHARD_MODES:
        return (
            f"unknown shard_mode {shard_mode!r}; expected one of "
            f"{SHARD_MODES}"
        )
    if engine == "ooc":
        return (
            "the out-of-core streaming engine is host-driven and has no "
            f"sharded ring program (got shard_mode {shard_mode!r}); run "
            "--engine ooc without a mesh (it streams bands through one "
            "device), or pick a sharded engine ('dense', 'bitpack', "
            "'pallas_bitpack', 'activity') for mesh runs"
        )
    allowed = ENGINE_MODES.get(engine)
    if allowed is None or shard_mode in allowed:
        return None
    if engine == "bitpack" and shard_mode == "auto":
        return (
            "the bit-packed sharded engine has no auto-SPMD program; "
            "shard_mode 'auto' applies to engine 'dense'"
        )
    if engine == "pallas_bitpack":
        return (
            "the sharded Pallas engine has the explicit, overlap and "
            "pipeline ring programs only (got shard_mode "
            f"{shard_mode!r})"
        )
    if engine == "activity":
        return (
            "the sharded activity engine has the explicit ring program "
            f"only (got shard_mode {shard_mode!r})"
        )
    return (
        f"engine {engine!r} has no {shard_mode!r} program; supported "
        f"modes: {allowed}"
    )


def check_combo(engine: str, shard_mode: str, halo_depth: int) -> None:
    """Raise the canonical ``ValueError`` for an illegal (engine, mode,
    depth) combination — mesh-independent legality only."""
    reason = mode_rejection(engine, shard_mode)
    if reason is not None:
        raise ValueError(reason)
    _check_positive_depth(halo_depth)
    if halo_depth > 1 and shard_mode not in DEEP_BAND_MODES:
        # Only 'auto' survives the matrix to reach this rule today, but
        # the check is written against DEEP_BAND_MODES so a future mode
        # states its band policy instead of inheriting one silently.
        raise ValueError(
            "halo_depth > 1 (temporal blocking) requires shard_mode "
            "'explicit', 'overlap' or 'pipeline' (got "
            f"{shard_mode!r}): auto-SPMD derives its own per-generation "
            "exchanges, so there is no band to deepen"
        )
    if engine == "pallas_bitpack" and halo_depth > 1 and halo_depth % 8:
        raise ValueError(
            "the sharded Pallas engine needs halo_depth to be a "
            f"multiple of 8 (DMA row alignment), got {halo_depth}"
        )
    if engine == "activity" and halo_depth != 1:
        raise ValueError(
            "engine 'activity' exchanges one-tile mask halos per "
            f"generation; halo_depth must be 1, got {halo_depth}"
        )


def _check_positive_depth(halo_depth: int) -> None:
    if halo_depth < 1:
        raise ValueError(f"halo_depth must be >= 1, got {halo_depth}")


def check_run(
    engine: str,
    resolved: str,
    *,
    halo_mode: str = "fresh",
    shard_mode: str = "explicit",
    halo_depth: int = 1,
    custom_rule: bool = False,
    mesh: Optional[str] = None,
    reshard: bool = False,
    batch: bool = False,
) -> None:
    """Raise the canonical ``ValueError`` when the combination has no
    program; return when it has one.

    ``engine`` is the requested engine and ``resolved`` the one 'auto'
    picked (the same name otherwise).  ``custom_rule`` says a rule
    other than the B3/S23 fast paths was asked for, ``mesh`` is
    ``None``, ``"1d"`` or ``"2d"``, and ``reshard`` an in-flight
    reshard stop.  ``batch`` checks a batched multi-world run (the CLI's
    ``--batch``), where any ``--rule`` counts as custom.
    """
    if batch:
        if halo_mode != "fresh":
            raise ValueError("--batch runs fresh halos only")
        if custom_rule:
            raise ValueError(
                "--batch runs the B3/S23 fast paths; --rule is a "
                "single-world feature"
            )
        if mesh == "2d":
            raise ValueError(
                "--batch shards the world axis (a 1-D ring); use "
                "--mesh 1d or --mesh none"
            )
        if engine == "ooc":
            raise ValueError(
                "--batch evolves many in-core worlds in one compiled "
                "program; engine 'ooc' streams one bigger-than-device "
                "world through the chip — run it unbatched, or pick a "
                "batched engine ('auto', 'dense', 'bitpack', "
                "'pallas_bitpack')"
            )
        if engine in ("pallas", "activity"):
            raise ValueError(
                f"engine {engine!r} has no batched tier; "
                "use 'auto'/'dense'/'bitpack'/'pallas_bitpack'"
            )
        return
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected {ENGINES}")
    if halo_mode not in HALO_MODES:
        raise ValueError(f"unknown halo_mode {halo_mode!r}")
    if shard_mode not in SHARD_MODES:
        raise ValueError(
            f"unknown shard_mode {shard_mode!r}; expected {SHARD_MODES}"
        )
    _check_positive_depth(halo_depth)
    if custom_rule:
        if halo_mode != "fresh":
            raise ValueError(
                "custom rules have no stale_t0 reference-compat mode "
                "(the reference only implements B3/S23)"
            )
        if engine == "pallas":
            raise ValueError(
                "engine 'pallas' (dense kernel) is hard-wired to "
                "B3/S23; use 'auto'/'dense'/'bitpack'/"
                "'pallas_bitpack' with a custom rule"
            )
        # B3/S23 stays on the hard-wired fast paths; other rules run
        # the generic evaluators — sharded via the explicit ring
        # engine, or the sharded Pallas engine's overlap/pipeline
        # forms (its kernel carries the generic rule tail).  Checked
        # against the *resolved* engine so 'auto' runs that resolve to
        # the Pallas engine get the same allowance as an explicit
        # choice.
        if (
            mesh is not None
            and shard_mode != "explicit"
            and not (
                shard_mode in ("overlap", "pipeline")
                and resolved == "pallas_bitpack"
            )
        ):
            raise ValueError(
                "custom rules shard via the explicit ring engine (any "
                "engine) or the sharded Pallas engine's overlap/"
                f"pipeline forms (engine 'pallas_bitpack'); shard_mode "
                f"{shard_mode!r} with engine {resolved!r} "
                "is a Conway-specific program"
            )
    if resolved == "activity":
        if halo_mode != "fresh":
            raise ValueError(
                "engine 'activity' implements fresh halos only (the "
                "stale_t0 compat mode reproduces a reference bug the "
                "gated tier has no analog for)"
            )
        if custom_rule:
            raise ValueError(
                "engine 'activity' runs the B3/S23 fast paths; use "
                "'dense'/'bitpack' with a custom rule"
            )
        if mesh is None:
            # Its matrix row's depth rule; a meshless run has no ring
            # mode to pick (a mesh meets the whole row below).
            check_combo(resolved, "explicit", halo_depth)
    if resolved == "ooc":
        if halo_mode != "fresh":
            raise ValueError(
                "engine 'ooc' implements fresh halos only (the stale_t0 "
                "compat mode reproduces a reference bug the streaming "
                "tier has no analog for)"
            )
        if custom_rule:
            raise ValueError(
                "engine 'ooc' streams the B3/S23 bit-packed band step; "
                "use 'dense'/'bitpack' with a custom rule"
            )
        if shard_mode != "explicit" or mesh is not None:
            raise ValueError(mode_rejection("ooc", shard_mode))
        if reshard:
            raise ValueError(
                "reshard_at replans onto a different mesh; the ooc tier "
                "is meshless (its board already lives host-side — "
                "checkpoint and resume instead)"
            )
        # halo_depth is its per-visit generation depth k, not a ghost
        # band, so the mesh-coupled rules below do not apply.
        return
    if mesh is None:
        if halo_depth > 1:
            raise ValueError(
                "halo_depth > 1 (temporal blocking) only applies to "
                "sharded runs; pass a mesh"
            )
        if shard_mode == "pipeline":
            raise ValueError(
                "shard_mode 'pipeline' double-buffers ring exchanges "
                "across chunks, which only exist on sharded runs; "
                "pass a mesh"
            )
        # stale_t0 is the dense engine's reference-compat mode.
        if halo_mode != "fresh" and resolved != "dense":
            raise ValueError(f"engine {resolved!r} implements fresh halos only")
        return
    if resolved in ENGINE_MODES:
        check_combo(resolved, shard_mode, halo_depth)
    if halo_mode != "fresh":
        raise ValueError(
            "stale_t0 (reference-compat) runs are single-device only; "
            "its blocks evolve independently so a mesh adds nothing"
        )
    if engine not in SHARDED_ENGINES:
        raise ValueError(
            f"engine {engine!r} has no sharded path; with a "
            "mesh use 'dense'/'auto' (shard_map+ppermute or "
            "auto-SPMD), 'bitpack' (packed shard_map+ppermute), "
            "'pallas_bitpack' (fused kernel per shard), or "
            "'activity' (gated worklist per shard)"
        )
    if (
        resolved == "pallas_bitpack"
        and mesh == "2d"
        and halo_depth > PALLAS_COLUMN_BAND_DEPTH
    ):
        raise ValueError(
            "on a 2-D mesh the sharded Pallas engine's 1-word "
            f"column band supports halo_depth <= {PALLAS_COLUMN_BAND_DEPTH},"
            f" got {halo_depth}"
        )


def check_depth(
    halo_depth: int,
    shard_h: int,
    shard_w: int,
    two_d: bool,
    units: str = "cells",
) -> None:
    """Depth-vs-shard-extent limit: the ghost shell must come entirely
    from the immediate ring neighbor.

    ``shard_h``/``shard_w`` are the per-shard extents in each axis's
    exchange quantum — rows vertically, 32-cell words horizontally for
    the packed engines (``units`` names them for the message).  A 2-D
    mesh extends the width axis even when its cols ring has size 1 (the
    ring degenerates to the local wrap), so the width limit applies
    whenever ``two_d`` is set.
    """
    limit = min(shard_h, shard_w) if two_d else shard_h
    if halo_depth > limit:
        raise ValueError(
            f"halo_depth {halo_depth} exceeds the shard extent "
            f"({shard_h}×{shard_w} rows×{units}); the ghost shell must "
            "come from the immediate ring neighbor"
        )
