"""Declarative SLOs over trace decompositions, with burn rates.

Throughput alone cannot gate a serving tier: a rate sweep can hold
achieved RPS while p99 commit latency or queue-wait fraction quietly
degrades.  This module turns the per-request latency decompositions of
:mod:`gol_tpu.telemetry.trace` into pass/fail objectives:

- an :class:`SLO` names a metric over the decomposition (``commit_latency_s``,
  ``queue_fraction``, ``stall_fraction``), a target, and an error
  *budget* — the tolerated fraction of requests allowed to violate it;
- :func:`evaluate` scores a trace set and reports, per objective, the
  observed percentile, the violating fraction, and the **burn rate** =
  violating-fraction / budget.  Burn rate ≤ 1.0 means the objective
  holds within budget; 2.0 means the budget is being consumed twice as
  fast as tolerated (the standard SRE alerting quantity).

Objectives are data, not code: ``--slo objectives.json`` loads a list of
``{"name", "metric", "target", "budget", "percentile"}`` objects, so a
deployment tightens its targets without touching the repo.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Dict, List, Optional


@dataclass(frozen=True)
class SLO:
    """One objective: ``metric`` at ``percentile`` must be ≤ ``target``,
    and at most ``budget`` of requests may individually violate it."""

    name: str
    metric: str  # commit_latency_s | queue_fraction | stall_fraction
    target: float
    budget: float  # tolerated violating fraction, in (0, 1]
    percentile: float = 0.99


#: The defaults the CLI evaluates when no objectives file
#: is given: commit p99 under the scheduler's own deadline ceiling, and
#: queue wait below half of end-to-end for the typical request.
DEFAULT_SLOS = (
    SLO(name="commit_p99", metric="commit_latency_s", target=30.0,
        budget=0.01, percentile=0.99),
    SLO(name="queue_frac_p50", metric="queue_fraction", target=0.5,
        budget=0.05, percentile=0.50),
)


def load_slos(path: Optional[str] = None) -> List[SLO]:
    """Objectives from a JSON file (list of SLO-shaped objects), or the
    defaults.  Unknown keys are rejected by the dataclass constructor —
    a typo'd objective must fail loudly, not silently never gate."""
    if path is None:
        return list(DEFAULT_SLOS)
    with open(path) as f:
        raw = json.load(f)
    if not isinstance(raw, list):
        raise ValueError(f"{path}: expected a JSON list of objectives")
    return [SLO(**obj) for obj in raw]


def _metric_value(slo: SLO, d: dict) -> Optional[float]:
    e2e = d.get("e2e_s")
    if slo.metric == "commit_latency_s":
        return e2e
    if slo.metric == "queue_fraction":
        return d["queue_s"] / e2e if e2e else 0.0
    if slo.metric == "stall_fraction":
        return d["stall_s"] / e2e if e2e else 0.0
    raise ValueError(f"SLO {slo.name!r}: unknown metric {slo.metric!r}")


def _percentile(sorted_vals: List[float], q: float) -> float:
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def evaluate(slos: List[SLO], decomps: List[dict]) -> List[dict]:
    """Score each objective over a decomposition set.  Returns one row
    per SLO; with an empty trace set every row is vacuously ok (burn
    rate 0 — nothing served, nothing burned)."""
    rows: List[dict] = []
    for slo in slos:
        vals = sorted(_metric_value(slo, d) for d in decomps)
        violations = sum(1 for v in vals if v > slo.target)
        frac = violations / len(vals) if vals else 0.0
        burn = frac / slo.budget
        rows.append(
            {
                "name": slo.name,
                "metric": slo.metric,
                "percentile": slo.percentile,
                "target": slo.target,
                "budget": slo.budget,
                "observed": (
                    round(_percentile(vals, slo.percentile), 6)
                    if vals else None
                ),
                "violations": violations,
                "requests": len(vals),
                "violation_fraction": round(frac, 6),
                "burn_rate": round(burn, 6),
                "ok": burn <= 1.0,
            }
        )
    return rows


def render(rows: List[dict], out) -> None:
    """The burn-rate table the trace CLI prints under the decomposition."""
    if not rows:
        return
    print(
        "  slo              metric            pXX  observed   target "
        " viol  burn  ok",
        file=out,
    )
    for r in rows:
        obs = f"{r['observed']:.4f}" if r["observed"] is not None else "-"
        print(
            f"  {r['name']:<16} {r['metric']:<16} "
            f"p{int(r['percentile'] * 100):<3} {obs:>8} "
            f"{r['target']:>8.3f} {r['violations']:>4}/{r['requests']:<4}"
            f"{r['burn_rate']:>6.2f}  {'yes' if r['ok'] else 'NO'}",
            file=out,
        )
