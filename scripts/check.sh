#!/usr/bin/env bash
# The verify gate: everything a builder or reviewer must see green
# before trusting a change, in dependency order —
#
#   1. lint            (scripts/lint.sh: ruff, or compile-only fallback)
#   2. static verifier (python -m gol_tpu.analysis: engine invariants
#                       proven from traced programs, CPU-only)
#   3. telemetry smoke (tiny run with --telemetry; summarize must
#                       schema-validate the stream and exit 0)
#   4. stats smoke     (same run with --stats; summarize must exit 0
#                       and report a population row)
#   5. resilience drill (supervised run, SIGTERM the child once;
#                       auto-resume must finish with the same
#                       final-grid hash as an uninterrupted run)
#   6. batch smoke     (batched multi-world run bit-equal to
#                       sequential; --compile-cache populated on run 1,
#                       zero new entries on run 2 — all hits)
#   7. sparse smoke    (activity-gated glider-gun run bit-equal to the
#                       dense bitpack tier AND skipping >0 tiles, with
#                       v5 activity telemetry present)
#   8. obs smoke       (run with --metrics-port + v6 spans: scrape the
#                       live Prometheus endpoint mid-run, reconcile it
#                       with the JSONL, and summarize the span table)
#   9. reshard smoke   (elastic meshes: a 2-D-block sharded snapshot
#                       resumed on a 1-D ring must be bit-equal to a
#                       straight run, with a non-identity plan and the
#                       schema-v7 reshard event stamped)
#  10. halo smoke      (pipelined depth-k halo exchange: 512² glider,
#                       pipeline k=4 on a 1-D mesh bit-equal to
#                       explicit k=1, with v8 halo blocks on every
#                       chunk event)
#  11. chaos smoke     (unified fault plane: one plan driving
#                       bit-flip + torn-write + ENOSPC through a small
#                       guarded batch run — detected, contained, and
#                       recovered byte-equal; docs/RESILIENCE.md)
#  12. serve smoke     (serving tier, docs/SERVING.md: supervised
#                       crash mid-batch + journal re-admit — every
#                       accepted request completes exactly once,
#                       byte-equal — then a SIGTERM graceful drain)
#  13. elastic smoke   (live elasticity, docs/RESILIENCE.md: a sharded
#                       server loses a device mid-serve, live-reshards
#                       at the chunk boundary, regrows on restore,
#                       hedges a straggler — every request byte-equal,
#                       no restart, v11 verdicts on the stream)
#  14. lockcheck       (host-plane concurrency: lock-order graph,
#                       guarded-field discipline, SPMD collective
#                       consistency — AST-only, no jax backend;
#                       docs/ANALYSIS.md "The concurrency matrix")
#  15. trace smoke     (request tracing, docs/OBSERVABILITY.md: the
#                       committed v12 fixture round-trips through
#                       `telemetry trace --perfetto` and the export
#                       validates against the committed JSON schema —
#                       CI teeth for the export format)
#  16. tier-1 tests    (the exact ROADMAP.md command)
#  17. postmortem smoke (black box, docs/OBSERVABILITY.md: crash a
#                       real server via the fault plane, validate the
#                       *.blackbox.jsonl dump, run `telemetry
#                       postmortem` and assert the verdict names the
#                       open request; a supervised replay then keeps
#                       the verdict's promise; a graceful drain leaves
#                       no dump; a
#                       future-schema dump refuses with exit 2)
#  18. fleet smoke     (serving fleet, docs/SERVING.md "The fleet":
#                       3 supervised replicas behind the front tier,
#                       kill -9 one mid-flight — journaled handoff,
#                       ownership fencing on the restart, exactly-once
#                       byte-equal completion, degraded→recovered
#                       /readyz, graceful drain exit 0)
#  19. ooc smoke       (out-of-core tier, docs/STREAMING.md: a Gosper
#                       gun streamed through a device footprint the
#                       board is >=4x of — bit-equal to the in-core
#                       bitpack tier, dead bands skipped, v15 ooc
#                       blocks with measured overlap_fraction on
#                       every chunk)
#
# Any stage failing fails the gate.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== [1/19] lint =="
bash scripts/lint.sh

echo "== [2/19] static verifier (gol_tpu.analysis) =="
JAX_PLATFORMS=cpu python -m gol_tpu.analysis

echo "== [3/19] telemetry smoke (docs/OBSERVABILITY.md) =="
tdir="$(mktemp -d)"
trap 'rm -rf "$tdir"' EXIT
JAX_PLATFORMS=cpu python -m gol_tpu 0 64 8 512 0 \
    --telemetry "$tdir" --run-id smoke > /dev/null
JAX_PLATFORMS=cpu python -m gol_tpu.telemetry summarize "$tdir"

echo "== [4/19] stats smoke (in-graph simulation statistics) =="
sdir="$(mktemp -d)"
trap 'rm -rf "$tdir" "$sdir"' EXIT
JAX_PLATFORMS=cpu python -m gol_tpu 6 64 8 512 0 \
    --telemetry "$sdir" --run-id statsmoke --stats > /dev/null
JAX_PLATFORMS=cpu python -m gol_tpu.telemetry summarize "$sdir" \
    | tee /tmp/_stats_smoke.log
grep -q "stats     gen" /tmp/_stats_smoke.log

echo "== [5/19] resilience drill (docs/RESILIENCE.md) =="
JAX_PLATFORMS=cpu python scripts/resilience_drill.py

echo "== [6/19] batch smoke (docs/BATCHING.md) =="
JAX_PLATFORMS=cpu python scripts/batch_smoke.py

echo "== [7/19] sparse smoke (docs/SPARSE.md) =="
JAX_PLATFORMS=cpu python scripts/sparse_smoke.py

echo "== [8/19] obs smoke (docs/OBSERVABILITY.md) =="
JAX_PLATFORMS=cpu python scripts/obs_smoke.py

echo "== [9/19] reshard smoke (docs/RESILIENCE.md, elastic meshes) =="
JAX_PLATFORMS=cpu python scripts/reshard_smoke.py

echo "== [10/19] halo smoke (pipelined depth-k exchange, PR 9) =="
JAX_PLATFORMS=cpu python scripts/halo_smoke.py

echo "== [11/19] chaos smoke (docs/RESILIENCE.md, fault plane) =="
JAX_PLATFORMS=cpu python scripts/chaos_smoke.py

echo "== [12/19] serve smoke (docs/SERVING.md, serving tier) =="
JAX_PLATFORMS=cpu python scripts/serve_smoke.py

echo "== [13/19] elastic smoke (docs/RESILIENCE.md, live elasticity) =="
python scripts/elastic_smoke.py

echo "== [14/19] lockcheck (host-plane concurrency, docs/ANALYSIS.md) =="
python -m gol_tpu.analysis --concurrency

echo "== [15/19] trace smoke (docs/OBSERVABILITY.md, request tracing) =="
JAX_PLATFORMS=cpu python -m gol_tpu.telemetry trace \
    tests/data/telemetry_v12 --perfetto /tmp/_trace_export.json
python scripts/validate_trace_export.py /tmp/_trace_export.json \
    docs/schemas/perfetto_trace.schema.json

echo "== [16/19] tier-1 tests =="
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"

echo "== [17/19] postmortem-smoke (docs/OBSERVABILITY.md, black box) =="
make postmortem-smoke

echo "== [18/19] fleet smoke (docs/SERVING.md, the fleet) =="
JAX_PLATFORMS=cpu python scripts/fleet_smoke.py

echo "== [19/19] ooc smoke (docs/STREAMING.md, out-of-core tier) =="
JAX_PLATFORMS=cpu python scripts/ooc_smoke.py

exit "$rc"
