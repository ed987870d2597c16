"""A tiny volume cell for the harness's tests, added the way a later PR
adds one: a new configuration file and new entries in a copy of
BENCHMARK.json, on the shipped volume mix.  No file that is there is
edited."""

import contextlib
import copy
import json
import os

from perf import harness

#: 32³ and 12 generations: the kernels' block of 8 does not divide it.
TINY3D = {
    "world_size": 32, "generations": 12, "rule": "B5/S4,5", "engine": "auto",
    "reference": "life3d_torus",
}
CELL = "tiny3d.soup"


@contextlib.contextmanager
def added_volume_cell(tmp_path):
    """The tiny configuration's file and a BENCHMARK.json that runs it on
    ``soup3d`` and lists it wherever the shipped volume cell is listed;
    the file is removed afterwards."""
    path = os.path.join(harness.PERF, "configs", "_test_tiny3d.json")
    assert not os.path.exists(path)
    with open(path, "w") as f:
        f.write(json.dumps(TINY3D))
    bench = copy.deepcopy(harness.load_benchmark())
    bench["configs"].append({"name": "tiny3d", "source": "test", "reduced": [],
                             "file": "perf/configs/_test_tiny3d.json", "why": "test"})
    bench["workloads"].append(
        {"name": CELL, "config": "tiny3d", "traffic": "soup3d", "chips": 1, "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "life3d-1024.soup" in m.get("workloads", []):
            m["workloads"].append(CELL)
    out = tmp_path / "BENCHMARK.json"
    out.write_text(json.dumps(bench))
    try:
        yield str(out)
    finally:
        os.remove(path)
