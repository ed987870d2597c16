"""Whole 3-D jobs through ``VolumeRuntime.run``, back to back (a closed
loop).

Each job is a fresh cube of edge ``world_size``, a soup made on the
device from (seed, job index), stepped ``generations`` times by the
volume runtime that ``gol_tpu.cli3d`` drives: its engine resolution, its
compiled chunk program (kept across jobs) and its fence.

Set-up makes one warm-up job with an index the window never uses, so
the job's program is compiled (or read from the cache) and run once
before the window.  The window runs whole jobs until ``--seconds`` have
passed and ends with the last of them.

Correctness: one job of the window, drawn from the seed by reservoir
sampling, is stepped again by the plain reference once the window has
closed, and its final volume is compared cell by cell.  The notes name
the engine, and the fused kernel with its tile and k as the program's
span log recorded them at trace time.
"""

from __future__ import annotations

import random
import time

from perf.traffic import generate

#: The warm-up job's index; the window's jobs count up from 0.
WARMUP_INDEX = 0xFFFFFFFF
KERNEL_COUNTER = "gol3d.kernel."


def kernel_note() -> dict:
    """The ``gol3d.kernel.*`` counters, and the plan (kernel, tile, k)
    the compile spans carry, from the program's span log."""
    from gol_tpu import telemetry

    log = telemetry.span_log()
    plans = [
        {k: r["attrs"][k] for k in ("kernel", "tile", "k")}
        for r in log["records"]
        if r["name"].startswith("gol.lower.") and "kernel" in r["attrs"]
    ]
    counters = {name: n for name, (n, _) in log["counters"].items()
                if name.startswith(KERNEL_COUNTER)}
    return {"counters": counters, "plans": plans}


def run(h) -> None:
    import jax
    import numpy as np

    from gol_tpu.batch import cache as cache_mod
    from gol_tpu.cli3d import parse_rule3d
    from gol_tpu.volume import VolumeRuntime

    devs = h.devices()
    cache_mod.setup_compile_cache()
    cfg, mix = h.config, h.mix
    if int(mix.get("ranks", 1)) != 1 or mix.get("mesh", "none") != "none":
        raise ValueError(f"mix {mix['name']}: volumes run on one chip, with no mesh")
    size, gens = int(cfg["world_size"]), int(cfg["generations"])
    rt = VolumeRuntime(size=size, rule=parse_rule3d(cfg["rule"]),
                       engine=cfg.get("engine", "auto"))
    h.notes["engine"] = rt.resolved
    make = generate.soup_maker((size,) * 3, float(mix["density"]),
                               jax.sharding.SingleDeviceSharding(devs[0]))
    k0, k1 = (np.uint32(w) for w in generate.seed_words(h.seed))

    def job(index: int):
        with h.span("perf.job"):
            with h.span("perf.make_board"):
                volume = make(k0, k1, np.uint32(index))
            return rt.run(volume, gens)

    jax.block_until_ready(job(WARMUP_INDEX))
    h.notes["kernel"] = kernel_note()  # the warm-up job traced the program
    cells = size ** 3
    sample = random.Random(f"{h.seed}/sample")
    kept = None
    with h.window():
        t_start = time.perf_counter()
        j = 0
        while True:
            t0 = time.perf_counter()
            volume = job(j)
            t1 = time.perf_counter()
            h.jobs.append({"t0": t0 - t_start, "t1": t1 - t_start,
                           "cells": cells, "generations": gens,
                           "cell_updates": cells * gens})
            if sample.random() * (j + 1) < 1.0:
                kept = (j, volume)
            del volume
            j += 1
            if t1 - t_start >= h.seconds:
                break
    h.attempted = len(h.jobs)
    h.notes["job_s"] = [round(j["t1"] - j["t0"], 5) for j in h.jobs]
    h.read_memory_peak(devs)
    index, got = kept
    del rt
    ref = h.reference()
    t0 = time.perf_counter()
    want = ref.evolve(make(k0, k1, np.uint32(index)), gens, rule=cfg["rule"])
    h.check("mismatched_cells", ref.mismatches(got, want), 0)
    h.notes["checked_job"] = index
    h.notes["reference_s"] = time.perf_counter() - t0
    h.failed = 0 if h.checks["mismatched_cells"][0] == 0 else 1
