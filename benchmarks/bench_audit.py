#!/usr/bin/env python
"""Lowered-HLO collective audit of the bench_shard plans: the
predicted-vs-lowered collective-bytes drift report.

For the PR 8 row-sharded bench plan (and the replicated baseline it
beats), AOT-lowers the train step on the attached mesh and reports:

- ``collective_counts`` / ``measured_bytes`` — collectives GSPMD
  actually inserted, per kind, at their per-device buffer sizes;
- ``predicted_bytes`` — what `search/cost_model.py` + the dense
  all-to-all exchange geometry predict for the same plan
  (``all-to-all-balanced`` is the ragged/production exchange the
  simulator prices — the dense/balanced gap is the padding factor);
- ``drift`` — relative measured-vs-predicted disagreement per kind
  (the FLX513 gate fails above ``tolerance``);
- ``high_findings`` — rendered FLX51x findings (the replicated plan's
  table-scale gradient all-reduce shows up here; the row-sharded plan
  must be clean).

Prints ONE JSON line; `measure()` is imported by bench.py when
BENCH_AUDIT=1. Usage: python benchmarks/bench_audit.py
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def measure(tolerance: float = 0.25):
    import jax

    from bench_shard import _build
    from dlrm_flexflow_tpu.analysis.hlo_audit import audit_model

    ndev = len(jax.devices())
    batch = 64 * ndev
    out = {"ndev": ndev, "batch": batch, "tolerance": tolerance}
    for mode in ("row_sharded", "replicated"):
        model, _dcfg = _build(ndev, batch, mode)
        findings, report = audit_model(model, tolerance=tolerance)
        report["high_findings"] = [f.render() for f in findings
                                   if f.severity == "high"]
        report["findings"] = len(findings)
        out[mode] = report
        del model
    row = out.get("row_sharded", {})
    drift = (row.get("drift") or {}).get("all-to-all")
    out["row_a2a_within_tolerance"] = (drift is not None
                                       and drift != "inf"
                                       and float(drift) <= tolerance)
    return out


def main(argv):
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # standalone CPU smoke: virtualize the 8-device mesh like the
        # test fixture does (must run before jax initializes); on the
        # real accelerator bench.py's devices are used as-is
        from dlrm_flexflow_tpu.utils.testing import ensure_cpu_devices
        ensure_cpu_devices(
            int(os.environ.get("BENCH_AUDIT_CPU_DEVICES", "8")))
    tol = 0.25
    if "--tolerance" in argv:
        tol = float(argv[argv.index("--tolerance") + 1])
    print(json.dumps({"metric": "hlo_collective_audit", **measure(tol)}))
    return 0


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    sys.exit(main(sys.argv[1:]))
