#!/usr/bin/env python
"""Fault-tolerance smoke benchmark: what does recovery cost?

Measures, on a small DLRM (CPU or attached accelerator):

- ``save_ms`` / ``restore_ms`` — blocking rolling-checkpoint write and
  manifest-scan restore latency (the budget a `save_every` choice spends);
- ``sentinel_overhead`` — steady-state step-time ratio of
  ``anomaly_policy="skip_step"`` (fully async on-device guard) vs the
  sentinel off. This is the number that must stay ~1.0: the whole design
  point is that the finiteness check rides inside the jitted step;
- ``rollback_recovery_ms`` — wall time from an injected NaN step to
  training resumed on the restored snapshot (restore + rewind, measured
  through the real fit() rollback path).

Prints ONE JSON line (the BENCH_*.json convention); `measure()` is also
imported by bench.py when BENCH_RESILIENCE=1 so recovery-cost regressions
show up next to the headline throughput.

Usage: python benchmarks/bench_resilience.py [--steps N]
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _build(policy, batch):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm

    dcfg = DLRMConfig(embedding_size=[1024] * 8, sparse_feature_size=16,
                      mlp_bot=[13, 64, 16], mlp_top=[144, 64, 1])
    model = ff.FFModel(ff.FFConfig(batch_size=batch, seed=0,
                                   anomaly_policy=policy))
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"])
    model.init_layers()
    return model, dcfg


def _step_time(model, batches, steps):
    model.train_batch_device(batches[0])         # warm/compile
    t0 = time.perf_counter()
    mets = None
    for s in range(steps):
        mets = model.train_batch_device(batches[s % len(batches)])
    float(mets["loss"])                          # true completion
    return (time.perf_counter() - t0) / steps


def measure(steps=50, batch=128):
    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    from dlrm_flexflow_tpu.utils import faults
    from dlrm_flexflow_tpu.utils.checkpoint import CheckpointManager

    def staged(model, dcfg, n=4):
        out = []
        for i in range(n):
            x, y = synthetic_batch(dcfg, batch, seed=i)
            x["label"] = y
            out.append(model._device_batch(x))
        return out

    base, dcfg = _build("none", batch)
    t_clean = _step_time(base, staged(base, dcfg), steps)

    guarded, _ = _build("skip_step", batch)
    t_sentinel = _step_time(guarded, staged(guarded, dcfg), steps)

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep_last=2)
        t0 = time.perf_counter()
        mgr.save(base)
        save_ms = 1e3 * (time.perf_counter() - t0)
        t0 = time.perf_counter()
        assert mgr.restore_latest(base) is not None
        restore_ms = 1e3 * (time.perf_counter() - t0)

    # rollback drill through the real fit() path: one injected NaN step,
    # recovery time = (faulted fit) - (clean fit) on identical data
    def timed_fit(model, ckdir, plan):
        x, y = synthetic_batch(dcfg, batch * 8, seed=99)
        t0 = time.perf_counter()
        with faults.active_plan(plan):
            res = model.fit(x, y, epochs=1, verbose=False,
                            checkpoint_dir=ckdir, save_every=2)
        return time.perf_counter() - t0, res["rollbacks"]

    with tempfile.TemporaryDirectory() as d:
        m, _ = _build("rollback", batch)
        t_ref, rb = timed_fit(m, d, faults.FaultPlan())
        assert rb == 0
    with tempfile.TemporaryDirectory() as d:
        m, _ = _build("rollback", batch)
        t_fault, rb = timed_fit(m, d, faults.FaultPlan(nan_grad_steps={5}))
        assert rb == 1, f"expected exactly one rollback, got {rb}"

    return {
        "save_ms": round(save_ms, 2),
        "restore_ms": round(restore_ms, 2),
        "sentinel_overhead": round(t_sentinel / t_clean, 4),
        "rollback_recovery_ms": round(1e3 * max(t_fault - t_ref, 0.0), 2),
        "step_ms": round(1e3 * t_clean, 3),
    }


def main():
    steps = 50
    if "--steps" in sys.argv:
        steps = int(sys.argv[sys.argv.index("--steps") + 1])
    out = {"metric": "resilience_smoke", "unit": "ms / ratio"}
    out.update(measure(steps=steps))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    sys.exit(main())
