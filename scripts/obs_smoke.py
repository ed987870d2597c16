"""Observability smoke: metrics endpoint + spans, end to end.

check.sh stage [8/9] (docs/OBSERVABILITY.md).  Drives the real CLI in a
subprocess with ``--metrics-port 0`` and asserts the continuous-
observability surface end to end:

1. the printed endpoint is scraped **while the run is alive** and
   returns parseable Prometheus text;
2. the scraped generation counter reconciles with the run's JSONL
   telemetry (it must equal a chunk-boundary generation the stream also
   recorded — one emission feeds both surfaces, so they cannot drift);
3. every chunk event carries a schema-v6 ``spans`` block whose
   dispatch+ready seconds match the chunk's fenced wall;
4. ``summarize`` renders the span phase-breakdown table and exits 0.
"""

from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

_METRIC_RE = re.compile(r"^gol_generation (\d+)", re.MULTILINE)


def scrape(port: int) -> str:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=2.0
    ) as resp:
        return resp.read().decode()


def main() -> int:
    env = {**os.environ, "PYTHONPATH": str(REPO), "JAX_PLATFORMS": "cpu"}
    with tempfile.TemporaryDirectory() as tdir:
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "gol_tpu", "6", "64", "4096", "512",
                "0", "--telemetry", tdir, "--run-id", "obssmoke",
                "--checkpoint-every", "64", "--checkpoint-dir",
                os.path.join(tdir, "ck"), "--stats", "--metrics-port", "0",
            ],
            env=env,
            stdout=subprocess.PIPE,
            text=True,
            cwd=REPO,
        )
        try:
            # The CLI prints the bound ephemeral port before compiling.
            port = None
            deadline = time.monotonic() + 120.0
            assert proc.stdout is not None
            while time.monotonic() < deadline:
                line = proc.stdout.readline()
                if not line:
                    break
                m = re.search(r"http://127\.0\.0\.1:(\d+)/metrics", line)
                if m:
                    port = int(m.group(1))
                    break
            if port is None:
                proc.kill()
                print("FAIL: CLI never printed the metrics endpoint")
                return 1

            # Scrape mid-run: retry until the run has stepped at least
            # one chunk (generation > 0) or finished.
            mid_text = None
            mid_gen = None
            while proc.poll() is None:
                try:
                    text = scrape(port)
                except OSError:
                    time.sleep(0.05)
                    continue
                m = _METRIC_RE.search(text)
                if m and int(m.group(1)) > 0:
                    mid_text, mid_gen = text, int(m.group(1))
                    break
                time.sleep(0.05)
            rest, _ = proc.communicate(timeout=300)
        finally:
            if proc.poll() is None:
                proc.kill()
        if proc.returncode != 0:
            print(f"FAIL: run exited {proc.returncode}")
            return 1
        if mid_text is None:
            print("FAIL: never scraped the live endpoint mid-run")
            return 1
        if "# TYPE gol_generation gauge" not in mid_text:
            print("FAIL: scrape is not Prometheus text exposition format")
            return 1

        recs = [
            json.loads(ln)
            for ln in open(pathlib.Path(tdir) / "obssmoke.rank0.jsonl")
        ]
        chunks = [r for r in recs if r["event"] == "chunk"]
        gens = {c["generation"] for c in chunks}
        if mid_gen not in gens:
            print(
                f"FAIL: scraped generation {mid_gen} is not a chunk "
                f"boundary the JSONL recorded ({sorted(gens)})"
            )
            return 1
        if any("spans" not in c for c in chunks):
            print("FAIL: chunk events missing the v6 spans block")
            return 1
        for c in chunks:
            inner = c["spans"]["dispatch"] + c["spans"]["ready"]
            if inner > c["wall_s"] * 1.05 + 1e-4:
                print(
                    f"FAIL: chunk {c['index']} spans dispatch+ready "
                    f"{inner:.6f}s exceed wall {c['wall_s']:.6f}s"
                )
                return 1

        summ = subprocess.run(
            [
                sys.executable, "-m", "gol_tpu.telemetry", "summarize",
                tdir,
            ],
            env=env,
            capture_output=True,
            text=True,
            cwd=REPO,
        )
        if summ.returncode != 0 or "spans: phase" not in summ.stdout:
            print(
                f"FAIL: summarize rc={summ.returncode} or missing span "
                f"table\n{summ.stdout}\n{summ.stderr}"
            )
            return 1

    print(
        f"obs smoke OK: scraped generation {mid_gen} mid-run "
        f"(reconciles with {len(chunks)} chunk events), spans on every "
        f"chunk, summarize renders the phase table"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
