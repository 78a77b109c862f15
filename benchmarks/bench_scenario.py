"""Closed-loop online-learning smoke for bench.py (BENCH_SCENARIO=1).

Runs the compressed drifting-zipf scenario — trace replay with a
mid-day hot-set churn, feedback-spool training, delta publication,
and the live hot/cold re-placement trigger — and reports the budget
metrics as one JSON-able dict:

    auc            serving-edge AUC over the second half of the day
    p99_ms         client-observed request p99
    fleet_max      peak replica count (autoscaler cap compliance)
    freshness_lag  publisher tip step - slowest replica's version
    replacements   online re-placements fired (the churn should cost 1)
    failed         client requests that raised (the bar is 0)
    passed         every budget held, chaos included

Chaos (a finite replica outage, one torn delta, lossy feedback) stays
ON: the point of the scenario is that the budgets hold through it.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def measure(steps: int = 48, replicas: int = 2,
            seed: int = 0) -> Dict[str, Any]:
    from dlrm_flexflow_tpu.scenarios import run_scenario

    verdict = run_scenario("drifting_zipf", steps=steps, fast=True,
                           replicas=replicas, seed=seed)
    m = verdict["metrics"]
    return {
        "scenario": verdict["scenario"],
        "steps": verdict["steps"],
        "auc": round(m["auc"], 4),
        "p99_ms": (round(m["p99_ms"], 3)
                   if m["p99_ms"] is not None else None),
        "fleet_max": m["fleet_max"],
        "freshness_lag": m["freshness_lag"],
        "spool_lag": m["spool_lag"],
        "replacements": m["replacements"],
        "failed": m["failed"],
        "step_time_ratio": (round(m["step_time_ratio"], 3)
                            if m["step_time_ratio"] is not None
                            else None),
        "wall_s": round(m["wall_s"], 2),
        "passed": verdict["passed"],
        "failures": verdict["failures"],
    }


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    import json
    print(json.dumps(measure(), indent=2))
