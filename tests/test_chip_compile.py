"""The chip's compiler, without the chip: AOT compiles for a described v5e.

The kernels ``chip_smoke.py`` and the benchmark reach, at the sizes
they run them, are compiled for a ``v5e:2x2`` topology that is described, not attached
(``on-chip-measurement`` guide, section 2).  What Mosaic refuses here —
unaligned slices, too much VMEM, a program that does not fit — would
otherwise cost chip time to find.  Each test asserts the program holds a
Mosaic kernel (``tpu_custom_call``: the kernel compiled, not
interpreted), and the four-device rings the collective-permute of their
halo exchange.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every xdist worker
imports this file.  ``scripts/kernel_llo_tally.py`` compiles in a child
process (Mosaic's dump flag is read when the library loads), so its
fixture runs first, before this process loads the library.  The kernels
ask :func:`gol_tpu.ops.pallas_common.interpret` whether to interpret;
the fixture answers "no" for the duration of these tests, since this
process's backend is the CPU.
"""

from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

BOARD = 16384  # BASELINE config 3, the smoke's CLI / soup / ring board
VOLUME = 512  # config 5 cut to one chip, the smoke's 3-D phase
VOLUME_CELL = 1024  # config 5 whole, the benchmark's life3d-1024 job


TALLY = pathlib.Path(__file__).resolve().parent.parent / "scripts" / (
    "kernel_llo_tally.py"
)


@pytest.fixture(scope="module")
def llo_tally():
    """The flagship grid step's LLO tally, compiled in a child process
    that has to load the TPU library before this one does."""
    from scripts import kernel_llo_tally

    run = subprocess.run(
        [sys.executable, str(TALLY), "--json"],
        capture_output=True, text=True, timeout=600,
    )
    if run.returncode == kernel_llo_tally.NO_TOPOLOGY:
        pytest.skip(run.stderr.strip().splitlines()[-1])
    assert run.returncode == 0, run.stderr[-2000:]
    return json.loads(run.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def topo(llo_tally):
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled_kernels(topo):
    """Kernels compiled, not interpreted, and no persistent cache: an
    entry written for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache

    from gol_tpu.ops import pallas_common

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pallas_common, "interpret", lambda: False)
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _text(fn, *specs) -> str:
    return fn.lower(*specs).compile().as_text()


@pytest.fixture(scope="module")
def evolve3d_program(compiled_kernels):
    """``evolve3d`` compiled for one chip of the described topology,
    ``strict`` as an explicit ``--engine pallas`` runs it; each size
    compiled once."""
    from gol_tpu.ops import pallas_bitlife3d

    programs = {}

    def compile_(volume, gens):
        if (volume, gens) not in programs:
            spec = jax.ShapeDtypeStruct(
                (volume,) * 3, jnp.uint8,
                sharding=SingleDeviceSharding(compiled_kernels.devices[0]),
            )
            programs[volume, gens] = jax.jit(
                pallas_bitlife3d.evolve3d.__wrapped__,
                static_argnums=(1, 2, 3),
            ).lower(spec, gens, pallas_bitlife3d.BAYS_4555, True).compile()
        return programs[volume, gens]

    return compile_


_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
              "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
              "f64": 8}
_HLO_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_HLO_OP = re.compile(r"\s*(?:ROOT )?%?(\S+) = (.*?) ([\w-]+)\(")
# Ops that name bytes another op wrote, and move none themselves.
_NO_TRAFFIC = {"parameter", "bitcast", "get-tuple-element", "tuple",
               "constant"}


def _entry_writes(text: str):
    """(name, op, result bytes) of each op of the entry computation that
    writes its result; the while body and fusions are computations of
    their own, so their ops are not listed."""
    body = text[text.index("\nENTRY "):]
    body = body[body.index("{\n") + 2:body.index("\n}")]
    for line in body.splitlines():
        m = _HLO_OP.match(line)
        if not m or m.group(3) in _NO_TRAFFIC:
            continue
        size = 0
        for dtype, dims in _HLO_SHAPE.findall(m.group(2)):
            if dtype in _HLO_BYTES:
                size += _HLO_BYTES[dtype] * int(
                    np.prod([int(d) for d in dims.split(",") if d])
                )
        yield m.group(1), m.group(3), size


def test_fused_2d_kernel_compiles(compiled_kernels):
    from gol_tpu.ops import pallas_bitlife

    spec = jax.ShapeDtypeStruct(
        (BOARD, BOARD), jnp.uint8,
        sharding=SingleDeviceSharding(compiled_kernels.devices[0]),
    )
    text = _text(
        jax.jit(pallas_bitlife.evolve.__wrapped__, static_argnums=(1, 2, 3)),
        spec, 64, 512, None,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "mesh_shape,form",
    [
        ((1,), "serial"),
        ((1,), "overlap"),
        ((4,), "serial"),
        ((4,), "overlap"),
        ((2, 2), "serial"),
        ((2, 2), "pipeline"),
    ],
)
def test_packed_pallas_ring_compiles(compiled_kernels, mesh_shape, form):
    """The fused kernel per shard behind the ppermute ring: the 1-ring
    of one chip, the 4-ring and the 2x2 mesh of a four-chip host."""
    from gol_tpu.parallel import packed

    n = int(np.prod(mesh_shape))
    names = ("rows",) if len(mesh_shape) == 1 else ("rows", "cols")
    mesh = Mesh(
        np.asarray(compiled_kernels.devices[:n]).reshape(mesh_shape), names
    )
    spec = jax.ShapeDtypeStruct(
        (BOARD, BOARD), jnp.uint8,
        sharding=NamedSharding(mesh, P(*names) if len(names) == 2
                               else P("rows", None)),
    )
    fn = packed.compiled_evolve_packed_pallas(
        mesh, 64, overlap=form == "overlap", pipeline=form == "pipeline"
    )
    text = _text(fn, spec)
    assert "tpu_custom_call" in text
    if n > 1:
        assert "collective-permute" in text


@pytest.mark.parametrize("volume,gens", [(VOLUME, 16), (VOLUME_CELL, 64)])
def test_evolve3d_compiles(evolve3d_program, volume, gens):
    text = evolve3d_program(volume, gens).as_text()
    assert "tpu_custom_call" in text


def test_evolve3d_conversion_passes(evolve3d_program):
    """The benchmark's 1024³ job converts its volume in two full-volume
    passes outside the generation loop: the pack contraction's 256 MiB
    of 16-bit halves, and the unpack contraction that writes the 1 GiB
    uint8 volume.  XLA's packing of the same layout wrote the uint8
    volume or a relayout of it six times, with 2 GiB of temporaries."""
    compiled = evolve3d_program(VOLUME_CELL, 64)
    big = [(name, op, size) for name, op, size
           in _entry_writes(compiled.as_text()) if size >= 256 << 20]
    assert len(big) == 2, big
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 << 30


def test_fused_2d_grid_step_instruction_count(llo_tally):
    """One grid step of the flagship plan (tile 256 stepped 8 generations)
    stays aligned: it reads 36,992 VALU ops and 4,352 sublane rotates; a
    shrinking window sliced at odd row offsets reads 58,588 and 14,704."""
    assert (llo_tally["tile"], llo_tally["k"]) == (256, 8)
    assert llo_tally["valu"] <= 40_000
    assert llo_tally["vrot.slane"] <= 5_000
