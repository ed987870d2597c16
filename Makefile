# Entry points for builders and reviewers.  `make check` is the one
# gate: lint + static verifier + telemetry smoke + stats smoke +
# resilience drill + batch smoke + sparse smoke + obs smoke + reshard
# smoke + halo smoke + chaos smoke + serve smoke + elastic smoke +
# lockcheck + trace smoke + tier-1 tests + postmortem smoke + fleet
# smoke + ooc smoke (see
# scripts/check.sh).

.PHONY: lint verify lockcheck test check telemetry-smoke stats-smoke \
	resilience-drill batch-smoke sparse-smoke obs-smoke reshard-smoke \
	halo-smoke halobench-sweep chaos-smoke chaos-matrix serve-smoke \
	elastic-smoke trace-smoke postmortem-smoke fleet-smoke ooc-smoke

lint:
	bash scripts/lint.sh

verify:
	JAX_PLATFORMS=cpu python -m gol_tpu.analysis

# Host-plane concurrency passes only (lockcheck + spmdcheck): pure-AST,
# never initializes a jax backend, so it is check.sh's cheapest stage
# (docs/ANALYSIS.md "The concurrency matrix").
lockcheck:
	python -m gol_tpu.analysis --concurrency

test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
	    --continue-on-collection-errors -p no:cacheprovider \
	    -p no:xdist -p no:randomly

# Tiny run with --telemetry, then `summarize` must schema-validate the
# stream and exit 0 (docs/OBSERVABILITY.md).
telemetry-smoke:
	@tdir=$$(mktemp -d); trap 'rm -rf "$$tdir"' EXIT; \
	JAX_PLATFORMS=cpu python -m gol_tpu 0 64 8 512 0 \
	    --telemetry "$$tdir" --run-id smoke > /dev/null && \
	JAX_PLATFORMS=cpu python -m gol_tpu.telemetry summarize "$$tdir"

# Tiny CPU run with --stats --telemetry; `summarize` must exit 0 and
# render the per-chunk population (stats) table.
stats-smoke:
	@sdir=$$(mktemp -d); trap 'rm -rf "$$sdir"' EXIT; \
	JAX_PLATFORMS=cpu python -m gol_tpu 6 64 8 512 0 \
	    --telemetry "$$sdir" --run-id statsmoke --stats > /dev/null && \
	JAX_PLATFORMS=cpu python -m gol_tpu.telemetry summarize "$$sdir" \
	    | grep "stats     gen"

# Supervised preempt/auto-resume smoke: SIGTERM a supervised child once
# and assert the resumed run's final-grid hash matches an uninterrupted
# run (docs/RESILIENCE.md; the kill-9 chaos matrix is `-m slow`).
resilience-drill:
	JAX_PLATFORMS=cpu python scripts/resilience_drill.py

# Batched multi-world smoke (docs/BATCHING.md): mixed-size batch
# bit-equal to sequential single-world runs, and a second process hits
# the persistent compilation cache (zero new entries).
batch-smoke:
	JAX_PLATFORMS=cpu python scripts/batch_smoke.py

# Activity-gated smoke (docs/SPARSE.md): glider-gun run bit-equal to
# the dense bitpack tier while skipping most tile-generations.
sparse-smoke:
	JAX_PLATFORMS=cpu python scripts/sparse_smoke.py

# Continuous-observability smoke (docs/OBSERVABILITY.md): live run with
# --metrics-port scraped mid-run + reconciled with the JSONL, v6 spans
# on every chunk, and summarize's span table.
obs-smoke:
	JAX_PLATFORMS=cpu python scripts/obs_smoke.py

# Elastic-mesh smoke (docs/RESILIENCE.md): a 2-D-block sharded snapshot
# resumed on a 1-D ring bit-equal to a straight run, with a
# non-identity plan and the schema-v7 reshard event stamped.
reshard-smoke:
	JAX_PLATFORMS=cpu python scripts/reshard_smoke.py

# Pipelined-halo smoke (docs/DESIGN.md): 512² glider, pipeline k=4 on a
# 1-D mesh bit-equal to explicit k=1, v8 halo blocks on every chunk.
halo-smoke:
	JAX_PLATFORMS=cpu python scripts/halo_smoke.py

# The k-vs-MFU depth sweep (curve shape only on CPU — the TPU headline
# geometry is pinned in the output's note).
halobench-sweep:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    python -m gol_tpu.utils.halobench 1024 16 1d:4 \
	    dense,bitpack,pallas --halo-depth-sweep 1,2,4,8,16

# Unified-fault-plane smoke (docs/RESILIENCE.md): one plan file driving
# bit-flip + torn-write + ENOSPC through a small guarded batch run —
# detected, contained, recovered byte-equal, v9 records on the stream.
chaos-smoke:
	JAX_PLATFORMS=cpu python scripts/chaos_smoke.py

# The full committed scenario × tier × mesh grid (minutes on CPU; also
# `pytest -m slow tests/test_chaos_matrix.py`).
chaos-matrix:
	JAX_PLATFORMS=cpu python -m gol_tpu.resilience chaos \
	    --plan tests/data/fault_plans/chaos_matrix.json

# Serving-tier smoke (docs/SERVING.md): a supervised server crashed
# mid-batch completes every accepted request exactly once from the
# journal, byte-equal to the sequential oracle; then a SIGTERM drain.
serve-smoke:
	JAX_PLATFORMS=cpu python scripts/serve_smoke.py

# Live-elasticity smoke (docs/RESILIENCE.md "Live elasticity"): a
# --mesh-devices server loses a device mid-serve, live-reshards at the
# chunk boundary, regrows on restore, and hedges a straggler — every
# request byte-equal, no restart, v11 verdicts on the stream.  The
# script forces its own 8-device virtual CPU ring.
elastic-smoke:
	python scripts/elastic_smoke.py

# Request-tracing smoke (docs/OBSERVABILITY.md "Request tracing &
# SLOs"): the committed v12 fixture round-trips through `telemetry
# trace --perfetto` and the export validates against the committed
# docs/schemas/perfetto_trace.schema.json contract.
trace-smoke:
	JAX_PLATFORMS=cpu python -m gol_tpu.telemetry trace \
	    tests/data/telemetry_v12 --perfetto /tmp/_trace_export.json
	python scripts/validate_trace_export.py /tmp/_trace_export.json \
	    docs/schemas/perfetto_trace.schema.json

# Black-box postmortem smoke (docs/OBSERVABILITY.md "Black box &
# postmortems"): crash a REAL server via the fault plane, validate the
# *.blackbox.jsonl dump, run `telemetry postmortem` and assert the
# verdict names the open request; the supervised replay then keeps the
# verdict's promise; a graceful drain leaves no dump; a future-schema
# dump refuses with exit 2.
postmortem-smoke:
	JAX_PLATFORMS=cpu python scripts/postmortem_smoke.py

# Serving-fleet smoke (docs/SERVING.md "The fleet"): 3 supervised
# replicas behind the replicated front tier, kill -9 one mid-flight —
# journaled handoff to survivors, ownership fencing on the restart,
# every request exactly-once and byte-equal, /readyz degraded and
# recovered, graceful drain exit 0.
fleet-smoke:
	JAX_PLATFORMS=cpu python scripts/fleet_smoke.py

# Out-of-core streaming smoke (docs/STREAMING.md): a Gosper gun on a
# board >=4x the rotation's device footprint, streamed through
# --engine ooc — bit-equal to the in-core bitpack tier, dead bands
# skipped, v15 ooc blocks with measured overlap_fraction on every chunk.
ooc-smoke:
	JAX_PLATFORMS=cpu python scripts/ooc_smoke.py

check:
	bash scripts/check.sh
