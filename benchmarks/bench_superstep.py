#!/usr/bin/env python
"""Fused-superstep benchmark: what does one dispatch per K steps buy?

Small-batch DLRM steps are dominated by per-dispatch overhead, not math
(ROADMAP S1; search/cost_model.py pins a round-5 ~0.55 ms per step).
Fused
supersteps (`FFConfig.superstep`, core/model.py `_train_superstep`)
compile K training steps into ONE executable, so one host→device
dispatch pays the floor once per K steps.

This bench sweeps K ∈ {1, 2, 4, 8, 16} on the two floor-sensitive DLRM
configs at b256 (floor-bound) and b1024 (compute-heavier), reporting:

- ``ms_per_step`` per K — must be STRICTLY decreasing K=1→8 on a
  floor-bound config (the ISSUE-4 acceptance bar);
- ``dispatch_floor_ms`` — the measured floor, recovered as the slope of
  the least-squares line t(K) = t_device + floor/K over 1/K (the K→∞
  intercept ``t_device_ms`` is the pure device time);
- ``speedup_k8_vs_k1`` — the headline amortization win.

On a TPU the reference run_random.sh / run_criteo_kaggle.sh shapes are
used; off-TPU the same topology scales down (CPU-runnable smoke, same
code paths). Prints ONE JSON line (the BENCH_*.json convention);
`measure()` is imported by bench.py when BENCH_SUPERSTEP=1.

Usage: python benchmarks/bench_superstep.py [--steps N]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

# Criteo-Kaggle table sizes (run_criteo_kaggle.sh / calibrate_sim.py)
KAGGLE_TABLES = [1396, 550, 2700000, 2160000, 301, 22, 11878, 619, 3,
                 64889, 5236, 2567820, 3136, 26, 12607, 471917, 11, 4970,
                 2159, 4, 2586596, 7043, 61, 4, 930, 14][:26]


def _configs(full):
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig
    if full:
        rnd = DLRMConfig.random_benchmark()
        kag = DLRMConfig(embedding_size=KAGGLE_TABLES,
                         sparse_feature_size=16,
                         mlp_bot=[13, 512, 256, 64, 16],
                         mlp_top=[432, 512, 256, 1])
    else:
        # same topology, CPU-friendly table sizes/MLP widths — the
        # dispatch-vs-math ratio stays realistic, the suite stays fast
        rnd = DLRMConfig(embedding_size=[16384] * 8,
                         sparse_feature_size=64,
                         mlp_bot=[64, 256, 256, 64],
                         mlp_top=[576, 512, 256, 1])
        kag = DLRMConfig(embedding_size=[min(s, 4096) for s in
                                         KAGGLE_TABLES],
                         sparse_feature_size=16,
                         mlp_bot=[13, 64, 32, 16],
                         mlp_top=[432, 64, 32, 1])
    return {"dlrm_random": rnd, "dlrm_kaggle": kag}


def fit_dispatch_floor(ms_per_step):
    """Recover the per-dispatch floor from a K sweep.

    Model: t(K) = t_device + floor / K — each dispatch's fixed host cost
    spreads over the K steps it trains. A least-squares line over
    (1/K, ms_per_step) gives slope = floor (ms) and intercept = t_device
    (ms), the extrapolated K→∞ per-step time."""
    import numpy as np
    ks = sorted(ms_per_step)
    if len(ks) < 2:
        raise ValueError("need at least two K points to fit the floor")
    xs = np.array([1.0 / k for k in ks], dtype=np.float64)
    ys = np.array([ms_per_step[k] for k in ks], dtype=np.float64)
    slope, intercept = np.polyfit(xs, ys, 1)
    return float(slope), float(intercept)


def _measure_config(model, dcfg, bs, steps, ks, synthetic_batch,
                    stack_batches):
    per_k = {}
    for k in sorted(ks):
        if k == 1:
            bats = []
            for i in range(2):
                x, y = synthetic_batch(dcfg, bs, seed=i)
                x["label"] = y
                bats.append(model._device_batch(x))
            mets = model.train_batch_device(bats[0])     # warm/compile
            float(mets["loss"])
            rounds = max(2, steps)
            t0 = time.perf_counter()
            for s in range(rounds):
                mets = model.train_batch_device(bats[s % 2])
            float(mets["loss"])                          # true completion
            per_k[1] = (time.perf_counter() - t0) / rounds * 1e3
        else:
            megas = []
            for i in range(2):
                group = []
                for j in range(k):
                    x, y = synthetic_batch(dcfg, bs, seed=i * k + j)
                    x["label"] = y
                    group.append(x)
                megas.append(model._stage_superstep(stack_batches(group)))
            mets = model.train_batch_staged(megas[0])    # warm/compile
            float(mets["loss"])
            rounds = max(1, steps // k)
            t0 = time.perf_counter()
            for r in range(rounds):
                mets = model.train_batch_staged(megas[r % 2])
            float(mets["loss"])
            per_k[k] = (time.perf_counter() - t0) / (rounds * k) * 1e3
    return per_k


def measure(steps=48, ks=(1, 2, 4, 8, 16), batch_sizes=(256, 1024),
            full=None, configs=("dlrm_random", "dlrm_kaggle")):
    import jax

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.data.prefetch import stack_batches
    from dlrm_flexflow_tpu.models.dlrm import build_dlrm, synthetic_batch

    if full is None:
        full = jax.default_backend() == "tpu"
    dtype = "bfloat16" if jax.default_backend() == "tpu" else "float32"
    out = {}
    for name, dcfg in _configs(full).items():
        if name not in configs:
            continue
        for bs in batch_sizes:
            model = ff.FFModel(ff.FFConfig(batch_size=bs,
                                           compute_dtype=dtype))
            build_dlrm(model, dcfg)
            model.compile(ff.SGDOptimizer(lr=0.01), "mean_squared_error",
                          ["mse"])
            model.init_layers()
            per_k = _measure_config(model, dcfg, bs, steps, ks,
                                    synthetic_batch, stack_batches)
            floor_ms, t_dev_ms = fit_dispatch_floor(per_k)
            mono = all(per_k[a] > per_k[b]
                       for a, b in ((1, 2), (2, 4), (4, 8))
                       if a in per_k and b in per_k)
            row = {
                "ms_per_step": {str(k): round(v, 4)
                                for k, v in sorted(per_k.items())},
                "dispatch_floor_ms": round(floor_ms, 4),
                "t_device_ms": round(t_dev_ms, 4),
                "strictly_decreasing_1_to_8": mono,
            }
            if 1 in per_k and 8 in per_k:
                row["speedup_k8_vs_k1"] = round(per_k[1] / per_k[8], 3)
            out[f"{name}_b{bs}"] = row
    return out


def main():
    steps = 48
    if "--steps" in sys.argv:
        steps = int(sys.argv[sys.argv.index("--steps") + 1])
    out = {"metric": "superstep_amortization",
           "unit": "ms/step by K / ms floor"}
    out.update(measure(steps=steps))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    sys.exit(main())
