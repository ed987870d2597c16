"""Native C++ runtime helpers vs. their Python arbiters (byte-for-byte)."""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from gol_tpu.utils import io as gol_io
from gol_tpu.utils import native

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def build_native():
    if shutil.which("make") and shutil.which("g++"):
        subprocess.run(
            ["make", "-C", os.path.join(REPO, "native")],
            check=False,
            capture_output=True,
        )
    # Reset the lazy loader so this module sees a lib built after import.
    native.reload()
    yield


needs_native = pytest.mark.skipif(
    not (shutil.which("g++") and shutil.which("make")),
    reason="native toolchain unavailable",
)


@needs_native
def test_native_available():
    assert native.available()


@needs_native
def test_native_format_matches_python():
    rng = np.random.default_rng(0)
    for shape, rank in [((3, 3), 0), ((12, 7), 4), ((120, 5), 1)]:
        block = rng.integers(0, 2, shape).astype(np.uint8)
        assert native.format_world(block, rank) == gol_io.format_world(block, rank)


@needs_native
def test_native_writer_matches_python(tmp_path):
    rng = np.random.default_rng(1)
    block = rng.integers(0, 2, (16, 9)).astype(np.uint8)
    native.write_rank_file(str(tmp_path / "n.txt"), block, 2)
    with open(tmp_path / "n.txt", "rb") as f:
        got = f.read()
    assert got == gol_io.format_rank_file(block, 2)


@needs_native
def test_native_writer_used_by_io_layer(tmp_path):
    """write_rank_file(use_native=True) and =False produce identical files."""
    block = np.random.default_rng(2).integers(0, 2, (8, 8)).astype(np.uint8)
    pa = gol_io.write_rank_file(block, 0, 1, str(tmp_path / "a"), use_native=True)
    pb = gol_io.write_rank_file(block, 0, 1, str(tmp_path / "b"), use_native=False)
    assert open(pa, "rb").read() == open(pb, "rb").read()


@needs_native
def test_native_pack_unpack_roundtrip():
    rng = np.random.default_rng(3)
    cells = rng.integers(0, 2, 32 * 17).astype(np.uint8)
    words = native.pack_bits(cells)
    assert words.dtype == np.uint32 and words.size == 17
    # Bit i of word j = cell j*32 + i.
    expected0 = sum(int(cells[b]) << b for b in range(32))
    assert int(words[0]) == expected0
    np.testing.assert_array_equal(native.unpack_bits(words), cells)


@needs_native
def test_native_driver_execs_runtime(tmp_path):
    """The C++ `gol` binary: usage on wrong argc; exec's the runtime on 5."""
    gol = os.path.join(REPO, "native", "gol")
    assert os.path.exists(gol)
    bad = subprocess.run([gol, "1", "2"], capture_output=True, text=True)
    assert bad.returncode == 255  # exit(-1)
    assert "5 arguments" in bad.stdout

    env = dict(os.environ)
    env["GOL_PYTHON"] = sys.executable
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    ok = subprocess.run(
        [gol, "4", "8", "2", "64", "1", "--outdir", str(tmp_path)],
        capture_output=True,
        text=True,
        env=env,
        timeout=300,
    )
    assert ok.returncode == 0, ok.stderr
    assert "TOTAL DURATION : " in ok.stdout
    assert (tmp_path / "Rank_0_of_1.txt").exists()


@needs_native
def test_native_refuses_library_older_than_source(tmp_path, monkeypatch):
    """A .so built before its committed source changed (or copied from
    another machine) is never loaded; the Python fallbacks serve."""
    newer = tmp_path / "golrt.cpp"
    newer.write_text("// edited after the library was built\n")
    lib_mtime = os.path.getmtime(os.path.join(REPO, "native", "libgolrt.so"))
    os.utime(newer, (lib_mtime + 60, lib_mtime + 60))
    monkeypatch.setattr(native, "_SOURCE", str(newer))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_attempted", False)
    assert not native.available()
