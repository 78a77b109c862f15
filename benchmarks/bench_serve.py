#!/usr/bin/env python
"""Serving-engine benchmark: what does dynamic batching buy the read path?

The write path amortizes the dispatch floor with fused supersteps; the
read path amortizes it by coalescing concurrent requests into one padded
bucket dispatch (serve/engine.py). This bench quantifies that trade on
the DLRM random-benchmark topology:

- ``offline_qps``: direct ``forward_bucket`` loop at the largest bucket
  — the roofline the engine cannot beat (zero queueing);
- ``single_qps``: one caller, one row per request, engine in the loop —
  the degenerate no-coalescing case (every dispatch pays the full
  per-dispatch overhead for ONE row);
- per (bucket, max_delay) sweep: N concurrent submitter threads pushing
  single-row requests through the engine — ``qps``, ``p50_ms``,
  ``p99_ms``, ``batch_fill``;
- the same sweep with the embedding-row cache on vs off when the model
  keeps host-resident tables (``--host-tables`` serving).

Acceptance bar (ISSUE 5): the concurrent dynamically-batched
configuration sustains >= 3x ``single_qps`` on CPU.

Prints ONE JSON line; `measure()` is imported by bench.py when
BENCH_SERVE=1. Usage: python benchmarks/bench_serve.py [--requests N]
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _build(host_tables=False, cache_rows=0, max_batch=64):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
    dcfg = DLRMConfig(embedding_size=[8192] * 8, sparse_feature_size=16,
                      mlp_bot=[16, 64, 16], mlp_top=[144, 64, 1])
    cfg = ff.FFConfig(batch_size=max_batch, seed=3,
                      host_resident_tables=host_tables,
                      serve_cache_rows=cache_rows,
                      serve_max_batch=max_batch)
    model = ff.FFModel(cfg)
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"])
    model.init_layers()
    return model, dcfg


def _requests(dcfg, n, rows=1, seed=0):
    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    x, _ = synthetic_batch(dcfg, n * rows, seed=seed)
    return [{k: v[i * rows:(i + 1) * rows] for k, v in x.items()}
            for i in range(n)]


def _drive(engine, reqs, threads):
    """Push every request through the engine from `threads` concurrent
    submitters; returns wall-clock seconds."""
    import dlrm_flexflow_tpu as ff
    it = iter(range(len(reqs)))
    lock = threading.Lock()
    errors = []

    def worker():
        while True:
            with lock:
                i = next(it, None)
            if i is None:
                return
            while True:
                try:
                    engine.predict(reqs[i], timeout=60)
                    break
                except ff.Overloaded:
                    time.sleep(0.001)
                except Exception as e:     # noqa: BLE001
                    errors.append(e)
                    return

    ts = [threading.Thread(target=worker) for _ in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errors:
        raise errors[0]
    return time.perf_counter() - t0


def measure(requests=256, threads=16):
    import numpy as np
    import dlrm_flexflow_tpu as ff

    out = {"requests": requests, "threads": threads}
    model, dcfg = _build()
    reqs = _requests(dcfg, requests)

    # offline roofline: full buckets straight through forward_bucket
    bucket = model.bucket_sizes(64)[-1]
    from dlrm_flexflow_tpu.data.dataloader import coalesce_batches
    full = coalesce_batches(reqs[:bucket])
    np.asarray(model.forward_bucket(full, bucket=bucket))   # warm
    t0 = time.perf_counter()
    n_off = 0
    while n_off < requests:
        np.asarray(model.forward_bucket(full, bucket=bucket))
        n_off += bucket
    out["offline_qps"] = round(n_off / (time.perf_counter() - t0), 1)

    # single-request degenerate case: no coalescing possible
    eng = ff.InferenceEngine(model, ff.ServeConfig(
        max_batch=64, max_delay_ms=0.1, queue_capacity=1024))
    with eng:
        for r in reqs[:4]:
            eng.predict(r, timeout=60)                      # warm
        t0 = time.perf_counter()
        for r in reqs:
            eng.predict(r, timeout=60)
        single_s = time.perf_counter() - t0
    out["single_qps"] = round(requests / single_s, 1)

    # dynamic batching sweep
    sweep = []
    for max_batch in (16, 64):
        for delay_ms in (1.0, 5.0):
            eng = ff.InferenceEngine(model, ff.ServeConfig(
                max_batch=max_batch, max_delay_ms=delay_ms,
                queue_capacity=1024))
            with eng:
                _drive(eng, reqs[:64], threads)             # warm
                el = _drive(eng, reqs, threads)
                st = eng.stats()
            sweep.append({
                "max_batch": max_batch, "max_delay_ms": delay_ms,
                "qps": round(requests / el, 1),
                "p50_ms": round(st["p50_ms"], 3),
                "p99_ms": round(st["p99_ms"], 3),
                "batch_fill": round(st["batch_fill"], 3)})
    out["dynamic"] = sweep
    best = max(s["qps"] for s in sweep)
    out["best_dynamic_qps"] = best
    out["dynamic_vs_single"] = round(best / max(out["single_qps"], 1e-9), 2)

    # embedding-row cache on/off (host-resident tables)
    cache = {}
    for cache_rows in (0, 4096):
        m2, d2 = _build(host_tables=True, cache_rows=cache_rows)
        # skewed traffic: 32 hot index patterns cycled across requests
        hot = _requests(d2, 32, seed=5)
        seq = [hot[i % 32] for i in range(requests)]
        eng = ff.InferenceEngine(m2, ff.ServeConfig(
            max_batch=64, max_delay_ms=1.0, queue_capacity=1024,
            cache_rows=cache_rows))
        with eng:
            _drive(eng, seq[:64], threads)                  # warm
            el = _drive(eng, seq, threads)
            st = eng.stats()
        key = "cache_on" if cache_rows else "cache_off"
        cache[key] = {"qps": round(requests / el, 1)}
        if cache_rows:
            cache[key]["hit_rate"] = round(
                st["embedding_cache"]["hit_rate"], 3)
    out["host_tables"] = cache
    return out


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    n = 256
    if "--requests" in sys.argv:
        n = int(sys.argv[sys.argv.index("--requests") + 1])
    print(json.dumps(measure(requests=n)))
