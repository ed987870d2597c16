"""Bring-up smoke: the program's main paths on the TPU, checked as it runs.

    python chip_smoke.py               # one chip: CLI, library, serve, 3-D
    python chip_smoke.py --four-chips  # a 2x2 host: the sharded ring only

Everything runs in this one process: a chip belongs to one process, and
a child that needed it would fail or hang.  Each phase prints one JSON
line (phase, resolved engine, wall and compile seconds, and whether its
result agreed with the reference); the last line is
``{"ok": true, "device": {...}}``.  There is no CPU branch: without a TPU
the script exits non-zero before any phase, and a failed phase exits
non-zero without that line.

References, all from the same start as the run they check: the XLA
bit-packed engine (``ops/bitlife.py``) on the chip, and a NumPy oracle
written here, independent of the package.  Boards compare by the
repo's own fingerprint and population (``utils/guard.py``) and by
``array_equal``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import glob
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from unittest import mock

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Board sizes and generation counts of every phase.  The defaults are
    the chip's (BASELINE config 3's 16384² board; config 5 cut to 512³);
    the CPU rehearsal in ``tests/test_chip_smoke.py`` shrinks them."""

    board: int = 16384  # CLI, library soup and four-chip runs
    gens: int = 1024
    oracle_board: int = 4096  # library run checked against NumPy
    oracle_gens: int = 32
    serve_sizes: tuple = (256, 4096)  # two buckets, two requests each
    serve_gens: int = 64
    volume: int = 512
    volume_gens: int = 16


def oracle_run(board: np.ndarray, steps: int) -> np.ndarray:
    """Torus B3/S23 by eight explicit shifts — no code shared with the
    package."""
    board = board.astype(np.uint8)
    for _ in range(steps):
        n = np.zeros_like(board)
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                if dy or dx:
                    n += np.roll(np.roll(board, dy, 0), dx, 1)
        board = ((n == 3) | ((board == 1) & (n == 2))).astype(np.uint8)
    return board


def audit(board) -> tuple:
    """(population, fingerprint) by the guard's on-device audit."""
    from gol_tpu.utils import guard

    _, pop, fp = guard._audit_jit(board)
    return int(pop), int(fp)


def same(got, want) -> bool:
    """Byte-equal, by audit and by ``array_equal`` (on device when both
    are device arrays)."""
    import jax.numpy as jnp

    if audit(jnp.asarray(got)) != audit(jnp.asarray(want)):
        return False
    return bool(jnp.array_equal(jnp.asarray(got), jnp.asarray(want)))


def telemetry(directory: str) -> dict:
    """Resolved engine and compile seconds from the run's event stream."""
    recs = []
    for path in glob.glob(os.path.join(directory, "*.jsonl")):
        with open(path) as f:
            recs.extend(json.loads(line) for line in f if line.strip())
    header = next(r for r in recs if r["event"] == "run_header")
    return {
        "engine": header["config"].get("resolved_engine"),
        "compile_s": sum(
            r["compile_s"] for r in recs if r["event"] == "compile"
        ),
    }


def mosaic(programs) -> bool:
    """Every compiled program holds a Mosaic kernel (not interpret mode)."""
    return bool(programs) and all(
        "tpu_custom_call" in p.as_text() for p in programs
    )


def report(phase: str, ok: bool, **fields) -> bool:
    print(json.dumps({"phase": phase, "ok": bool(ok), **fields}), flush=True)
    return bool(ok)


@contextlib.contextmanager
def captured_runtime():
    """Record the programs ``GolRuntime`` compiles and the board its
    ``run`` returns, for the duration of the block (the CLI builds its
    runtime inside ``main``, so this is how the smoke sees it)."""
    from gol_tpu.runtime import GolRuntime

    seen = {"programs": [], "board": None}
    compile_orig, run_orig = GolRuntime.compile_evolvers, GolRuntime.run

    def compile_evolvers(self, *args, **kwargs):
        evolvers = compile_orig(self, *args, **kwargs)
        seen["programs"].extend(c for c, _ in evolvers.values())
        return evolvers

    def run(self, *args, **kwargs):
        rep, state = run_orig(self, *args, **kwargs)
        seen["board"] = state.board
        return rep, state

    with mock.patch.object(GolRuntime, "compile_evolvers", compile_evolvers):
        with mock.patch.object(GolRuntime, "run", run):
            yield seen


def soup(size: int, seed: int = 7, density: float = 0.35):
    """A random soup made on the device (set-up, not measured)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.key(seed)
    return jax.random.bernoulli(key, density, (size, size)).astype(jnp.uint8)


def snapshot(path: str, board) -> str:
    """A generation-0 snapshot of ``board``: how a library user starts
    ``GolRuntime.run`` from a board of their own."""
    from gol_tpu.utils import checkpoint

    return checkpoint.save(
        path, np.asarray(board), 0, 1, fingerprint=audit(board)[1]
    )


def run_library(tmp, start, size, gens, name, **runtime_kwargs):
    """``GolRuntime.run`` of a ``size``² board from the snapshot ``start``;
    returns (final board, fields)."""
    from gol_tpu.models.state import Geometry
    from gol_tpu.runtime import GolRuntime

    tel = os.path.join(tmp, f"tel-{name}")
    rt = GolRuntime(
        geometry=Geometry(size=size, num_ranks=1),
        telemetry_dir=tel,
        run_id=name,
        **runtime_kwargs,
    )
    with captured_runtime() as seen:
        t0 = time.perf_counter()
        rep, state = rt.run(pattern=0, iterations=gens, resume=start)
        wall = time.perf_counter() - t0
    fields = telemetry(tel)
    fields.update(wall_s=wall, run_s=rep.duration_s,
                  mosaic=mosaic(seen["programs"]))
    return state.board, fields


# -- one chip ----------------------------------------------------------------


def phase_native() -> bool:
    """Build the native helpers here, from committed sources."""
    t0 = time.perf_counter()
    proc = subprocess.run(
        ["make", "-C", os.path.join(REPO, "native")],
        capture_output=True, text=True,
    )
    from gol_tpu.utils import native

    # reload(): an earlier probe in this process (a rank-file write, say)
    # memoized the library's absence before this build.
    return report("native", proc.returncode == 0 and native.reload(),
                  wall_s=time.perf_counter() - t0,
                  error=proc.stderr[-400:] if proc.returncode else None)


def phase_cli(sz: Sizes, tmp: str, expect: str) -> bool:
    """``gol_tpu.cli.main`` in process at BASELINE config 3's geometry
    (pattern 6, output off) against the XLA bitpack engine."""
    import jax.numpy as jnp

    from gol_tpu import cli
    from gol_tpu.models import patterns
    from gol_tpu.ops import bitlife

    tel = os.path.join(tmp, "tel-cli")
    argv = ["6", str(sz.board), str(sz.gens), "512", "0", "--engine", "auto",
            "--telemetry", tel, "--run-id", "cli", "--outdir", tmp]
    with captured_runtime() as seen:
        t0 = time.perf_counter()
        rc = cli.main(argv)
        wall = time.perf_counter() - t0
    start = jnp.asarray(patterns.init_global(6, sz.board, 1))
    want = bitlife.evolve_dense_io(start, sz.gens)
    fields = telemetry(tel)
    ok = (
        rc == 0
        and fields["engine"] == expect
        and (expect != "pallas_bitpack" or mosaic(seen["programs"]))
        and same(seen["board"], want)
    )
    pop, fp = audit(want)
    return report("cli", ok, wall_s=wall, board=sz.board, gens=sz.gens,
                  population=pop, fingerprint=fp,
                  mosaic=mosaic(seen["programs"]), **fields)


def phase_soup(sz: Sizes, tmp: str, expect: str) -> bool:
    """``GolRuntime`` on a seed-7 35% soup against the XLA bitpack engine,
    and a smaller soup against the NumPy oracle."""
    import jax.numpy as jnp

    from gol_tpu.ops import bitlife

    board = soup(sz.board)
    start = snapshot(os.path.join(tmp, "soup.npz"), board)
    got, fields = run_library(tmp, start, sz.board, sz.gens, "soup")
    want = bitlife.evolve_dense_io(jnp.array(board, copy=True), sz.gens)
    ok = (
        fields["engine"] == expect
        and (expect != "pallas_bitpack" or fields["mosaic"])
        and same(got, want)
    )
    pop, fp = audit(want)
    ok = report("soup", ok, board=sz.board, gens=sz.gens, population=pop,
                fingerprint=fp, **fields)

    small = (np.random.default_rng(7).random(
        (sz.oracle_board,) * 2, dtype=np.float32) < 0.35).astype(np.uint8)
    start = snapshot(os.path.join(tmp, "oracle.npz"), jnp.asarray(small))
    got, fields = run_library(tmp, start, sz.oracle_board, sz.oracle_gens,
                              "oracle")
    t0 = time.perf_counter()
    want = oracle_run(small, sz.oracle_gens)
    fields["oracle_s"] = time.perf_counter() - t0
    ok_small = fields["engine"] == expect and np.array_equal(
        np.asarray(got), want)
    return report("soup_oracle", ok_small, board=sz.oracle_board,
                  gens=sz.oracle_gens, population=int(want.sum()),
                  **fields) and ok


def phase_serve(sz: Sizes, tmp: str) -> bool:
    """``ServeScheduler`` + ``ServeServer`` as ``python -m gol_tpu.serve``
    wires them (defaults, ephemeral port): the device loop on this
    thread, clients on another, every board against the oracle."""
    from gol_tpu.models import patterns
    from gol_tpu.serve.client import SimClient
    from gol_tpu.serve.scheduler import ServeScheduler, decode_board
    from gol_tpu.serve.server import ServeServer
    from gol_tpu.telemetry.metrics import MetricsRegistry

    tel = os.path.join(tmp, "tel-serve")
    registry = MetricsRegistry()
    sched = ServeScheduler(os.path.join(tmp, "serve"), telemetry_dir=tel,
                           run_id="serve", registry=registry)
    server = ServeServer(sched, 0, registry=registry)
    reqs = [
        {"id": f"r{size}p{pat}", "pattern": pat, "size": size,
         "generations": sz.serve_gens}
        for size in sz.serve_sizes for pat in (6, 9)
    ]
    results, errors = {}, []

    def clients():
        client = SimClient(f"http://127.0.0.1:{server.port}", timeout=120.0)
        try:
            for req in reqs:
                client.submit(req)
            for req in reqs:
                results[req["id"]] = client.wait_for(
                    req["id"], timeout_s=600.0)
        except Exception as e:  # noqa: BLE001 - reported as the phase's
            errors.append(repr(e))

    t0 = time.perf_counter()
    thread = threading.Thread(target=clients, name="smoke-clients")
    thread.start()
    try:
        while thread.is_alive():
            if not sched.run_once():
                time.sleep(0.005)
        sched.drain()
        sched.run_until_drained()
    finally:
        thread.join(timeout=60.0)
        server.close()
        sched.close()
    wall = time.perf_counter() - t0

    checked = 0
    for req in reqs:
        res = results.get(req["id"])
        if res is None or res.get("status") != "done":
            continue
        start = patterns.init_global(req["pattern"], req["size"], 1)
        if np.array_equal(decode_board(res["board"]),
                          oracle_run(start, req["generations"])):
            checked += 1
    buckets = sorted({r["bucket"] for r in results.values()})
    fields = telemetry(tel)
    ok = not errors and checked == len(reqs) and len(buckets) >= 2
    return report("serve", ok, requests=len(reqs), bit_equal=checked,
                  buckets=buckets, wall_s=wall, compile_s=fields["compile_s"],
                  errors=errors or None)


def phase_3d(sz: Sizes, tmp: str, expect_kernel: bool) -> bool:
    """``gol_tpu.cli3d`` at 512³ with ``--engine pallas`` against
    ``--engine bitpack`` from the same soup (pattern 2)."""
    from gol_tpu import cli3d, volume

    boards, fields = {}, {}
    for engine in ("pallas", "bitpack"):
        seen = []
        build = volume._build_evolver

        def capture(*args, _build=build, _seen=seen, **kwargs):
            compiled, place = _build(*args, **kwargs)
            _seen.append(compiled)
            return _Recording(compiled, _seen), place

        tel = os.path.join(tmp, f"tel-3d-{engine}")
        argv = ["2", str(sz.volume), str(sz.volume_gens), "512", "0",
                "--engine", engine, "--telemetry", tel, "--run-id", engine,
                "--outdir", tmp]
        with mock.patch.object(volume, "_build_evolver", capture):
            t0 = time.perf_counter()
            rc = cli3d.main(argv)
            wall = time.perf_counter() - t0
        boards[engine] = seen[-1] if rc == 0 else None
        fields[engine] = dict(telemetry(tel), wall_s=wall, rc=rc,
                              mosaic=mosaic(seen[:1]))
    a, b = boards["pallas"], boards["bitpack"]
    ok = (
        a is not None and b is not None
        and (not expect_kernel or fields["pallas"]["mosaic"])
        and same(a.reshape(a.shape[0], -1), b.reshape(b.shape[0], -1))
    )
    return report("3d", ok, volume=sz.volume, gens=sz.volume_gens,
                  population=int(np.asarray(b).sum()) if b is not None
                  else None, **fields)


class _Recording:
    """A compiled program that remembers its last output (appended to
    ``seen``), so the smoke can read the volume ``cli3d`` discards."""

    def __init__(self, compiled, seen):
        self._compiled, self._seen = compiled, seen

    def __call__(self, *args):
        out = self._compiled(*args)
        self._seen.append(out[0] if isinstance(out, tuple) else out)
        return out

    def __getattr__(self, name):
        return getattr(self._compiled, name)


def one_chip(sz: Sizes, tmp: str, expect: str = "pallas_bitpack") -> bool:
    kernel = expect == "pallas_bitpack"
    results = [
        phase_native(),
        phase_cli(sz, tmp, expect),
        phase_soup(sz, tmp, expect),
        phase_serve(sz, tmp),
        phase_3d(sz, tmp, kernel),
    ]
    return all(results)


# -- four chips --------------------------------------------------------------


def four_chips(sz: Sizes, tmp: str, expect: str = "pallas_bitpack") -> bool:
    """The sharded ring (MPI ghost rows -> ppermute over ICI) on a 4-ring
    and a 2x2 mesh, explicit and pipeline, plus the sharded activity
    engine — each against the unsharded XLA bitpack run on device 0."""
    import jax

    from gol_tpu.ops import bitlife
    from gol_tpu.runtime import build_mesh

    board = jax.device_put(soup(sz.board), jax.devices()[0])
    t0 = time.perf_counter()
    want = bitlife.evolve_dense_io(jax.numpy.array(board, copy=True), sz.gens)
    want.block_until_ready()
    report("four_chip_reference", True, engine="bitpack",
           devices=[d.id for d in want.devices()],
           wall_s=time.perf_counter() - t0, population=audit(want)[0])
    start = snapshot(os.path.join(tmp, "soup.npz"), board)
    ok = True
    cases = [(m, mode, "auto") for m in ("1d", "2d")
             for mode in ("explicit", "pipeline")]
    cases += [(m, "explicit", "activity") for m in ("1d", "2d")]
    for kind, mode, engine in cases:
        mesh = build_mesh(kind, shape=board.shape)
        name = f"{kind}-{mode}-{engine}"
        got, fields = run_library(tmp, start, sz.board, sz.gens, name,
                                  engine=engine, mesh=mesh, shard_mode=mode)
        shards = sorted(
            (s.device.id, s.index[0].start or 0, s.index[1].start or 0)
            for s in got.addressable_shards
        )
        want_engine = expect if engine == "auto" else engine
        case_ok = (
            fields["engine"] == want_engine
            and (want_engine != "pallas_bitpack" or fields["mosaic"])
            and len({d for d, _, _ in shards}) == mesh.devices.size
            and same(jax.device_put(got, jax.devices()[0]), want)
        )
        ok &= report("four_chip", case_ok, mesh=dict(mesh.shape),
                     shard_mode=mode, shards=shards, board=sz.board,
                     gens=sz.gens, **fields)
    return ok


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run only the sharded ring on a four-chip host",
    )
    ns = parser.parse_args(argv)

    import jax

    device = jax.devices()[0]
    count = len(jax.devices())
    want_count = 4 if ns.four_chips else 1
    if device.platform != "tpu" or count < want_count:
        print(f"chip_smoke: needs {want_count} TPU chip(s); found {count} "
              f"{device.platform} device(s)", file=sys.stderr)
        return 2

    from gol_tpu.batch import cache

    cache.setup_compile_cache()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        ok = four_chips(Sizes(), tmp) if ns.four_chips else one_chip(
            Sizes(), tmp)
    if not ok:
        print("chip_smoke: a phase failed (see its line above)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": count,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
