"""Count the vector instructions of one grid step of the fused 2-D kernel.

Compiles the flagship plan -- :func:`gol_tpu.ops.pallas_bitlife.
multi_step_pallas_packed` on a 16384² board's ``[16384, 512]`` words, at
the tile and depth ``blocking_plan`` picks for a 10000-generation job --
for a described ``v5e:2x2`` (no chip needed), with Mosaic's pass dump on,
and tallies the ``llo.v*`` ops of the kernel's ``post-finalize-llo`` pass.
The kernel body is one grid step: one tile stepped through k generations,
fully unrolled.  ``valu`` sums the bitwise ops (and/or/xor/select/shifts);
``vrot.slane`` and ``vrot.lane`` are the sublane and lane rotates.

The dump flag is read when the TPU library loads, and one process at a
time may load that library, so the compile runs in a child process that
must be the first of its kind.

Usage: python scripts/kernel_llo_tally.py [--json]
Exits 0 with the tally, 3 when no v5e:2x2 topology can be described.
"""

from __future__ import annotations

import collections
import json
import os
import pathlib
import re
import subprocess
import sys
import tempfile

REPO = pathlib.Path(__file__).resolve().parent.parent

HEIGHT, WORDS, STEPS, TILE_HINT = 16384, 512, 10000, 512
VALU = ("vand", "vor", "vxor", "vselect", "vshll", "vshrl", "vshra")
NO_TOPOLOGY = 3
_OP = re.compile(r"\bllo\.(v[a-z_]+)(?:\.([a-z]+))?")


def tally(llo_text: str) -> dict:
    """Counts of the vector ops in one LLO dump, by name without the
    element type (``vand.u32`` counts as ``vand``), plus ``valu``."""
    counts = collections.Counter()
    for base, sub in _OP.findall(llo_text):
        counts[f"vrot.{sub}" if base == "vrot" else base] += 1
    out = dict(sorted(counts.items()))
    out["valu"] = sum(counts[op] for op in VALU)
    return out


def _compile() -> int:
    """The child: compile the flagship plan for the described chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    sys.path.insert(0, str(REPO))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from gol_tpu.ops import pallas_bitlife, pallas_common

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        print(f"no v5e:2x2 topology can be described here: {e}",
              file=sys.stderr)
        return NO_TOPOLOGY
    # A cache hit would skip Mosaic, and with it the dump.
    jax.config.update("jax_enable_compilation_cache", False)
    pallas_common.interpret = lambda: False  # this process's backend is CPU
    tile, k = pallas_bitlife.blocking_plan(HEIGHT, WORDS, STEPS, TILE_HINT)
    spec = jax.ShapeDtypeStruct(
        (HEIGHT, WORDS), jnp.int32,
        sharding=SingleDeviceSharding(topo.devices[0]),
    )
    jax.jit(
        lambda p: pallas_bitlife.multi_step_pallas_packed(p, tile, k)
    ).lower(spec).compile()
    print(json.dumps({"height": HEIGHT, "words": WORDS, "tile": tile,
                      "k": k}))
    return 0


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    if args == ["--compile"]:
        return _compile()
    if args not in ([], ["--json"]):
        print(__doc__.strip().splitlines()[-2], file=sys.stderr)
        return 2
    with tempfile.TemporaryDirectory() as dump:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["LIBTPU_INIT_ARGS"] = (
            env.get("LIBTPU_INIT_ARGS", "") + f" --xla_mosaic_dump_to={dump}"
        ).strip()
        child = subprocess.run(
            [sys.executable, __file__, "--compile"],
            env=env, capture_output=True, text=True,
        )
        if child.returncode:
            sys.stderr.write(child.stderr[-4000:])
            return child.returncode
        plan = json.loads(child.stdout.strip().splitlines()[-1])
        dumps = sorted(pathlib.Path(dump).glob("*post-finalize-llo*"))
        if len(dumps) != 1:
            print(f"expected one post-finalize-llo dump, found {len(dumps)}",
                  file=sys.stderr)
            return 1
        result = {**plan, **tally(dumps[0].read_text())}
    if args == ["--json"]:
        print(json.dumps(result))
    else:
        for key, value in result.items():
            print(f"{key:>16} {value}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
