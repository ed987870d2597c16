"""The control's readings on the chip for a volume cell, at its own size.

    python -m perf.tools.control3d --workload life3d-1024.soup --seeds 1,2,3

The control is the plain 3-D reference with the plane axis's wrap left
out (``wrap_planes=False``): it breaks the configuration's guarantee that
all three axes wrap.  It steps the job-0 soup of each seed both ways and
prints the cells that differ: the number the cell's runs compare, read
for the control in the program's place.
"""

from __future__ import annotations

import argparse
import json
import os

from perf import harness

os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    import jax
    import numpy as np

    from perf.traffic import generate

    bench = harness.load_benchmark()
    for seed in (int(s) for s in args.seeds.split(",")):
        h = harness.Harness(bench, args.workload, seed, 1.0, False)
        ref = h.reference()
        cfg = h.config
        size, gens, rule = int(cfg["world_size"]), int(cfg["generations"]), cfg["rule"]
        make = generate.soup_maker((size,) * 3, float(h.mix["density"]),
                                   jax.sharding.SingleDeviceSharding(jax.devices()[0]))
        k0, k1 = (np.uint32(w) for w in generate.seed_words(seed))
        v0 = make(k0, k1, np.uint32(0))
        want = ref.evolve(v0, gens, rule=rule)
        ctrl = ref.evolve(v0, gens, rule=rule, wrap_planes=False)
        row = {"mismatched_cells": ref.mismatches(ctrl, want),
               "live_cells": int(jax.numpy.sum(want, dtype=jax.numpy.int32))}
        h.close()
        print("CONTROL " + json.dumps({"workload": args.workload, "seed": seed, **row}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
