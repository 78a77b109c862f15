#!/usr/bin/env python
"""Retrieval benchmark: what does the sharded MIPS index cost and buy?

Three questions, matching the ISSUE-20 acceptance bar:

- **Recall**: recall@k of the int8-quantized sharded top-k against an
  fp32 exact scan over the SAME item embeddings (bar: >= 0.95 at
  k=100) — the price of storing the index as ``QuantTable`` codes +
  per-row scales instead of dense fp32. The merged sharded answer is
  also checked bitwise against the single-machine exact scan over the
  same codes (that one is a correctness invariant, not a trade).
- **Per-shard scoring throughput**: rows scored per second through the
  full quantize-once → per-shard local top-k → exact heap-merge path
  for shard counts {1, 2, 4} (merge included — the ranker pays it).
- **Cascade QPS at a p99 SLO**: open-loop Poisson arrivals through
  ``CascadeEngine.predict`` (retrieve → expand → DLRM ranker →
  re-rank) reusing bench_serve_fleet's ``_poisson_drive``/
  ``_qps_at_slo`` harness (open loop for the same reason: a slow
  cascade must not slow the arrival process and flatter its own tail).
  Plus a chaos phase killing one index shard under load (bar: ZERO
  failed requests — answers come back degraded-flagged with the dead
  shard's candidates dropped, never errors).

The cascade's user encoder here is a fixed projection of the request's
dense features — the bench prices the retrieve+rank pipeline, not user-
tower compute (serve_dlrm's cascade runs the compiled two-tower head).

Prints ONE JSON line; `measure()` is imported by bench.py when
BENCH_RETRIEVE=1. Usage:
  python benchmarks/bench_retrieve.py [--requests N] [--slo-ms MS]
"""

import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

from bench_serve_fleet import _poisson_drive, _qps_at_slo   # noqa: E402


def _index(n_items, dim, nshards, seed=0):
    import numpy as np
    from dlrm_flexflow_tpu.retrieve.index import ShardedMIPSIndex
    rng = np.random.default_rng(seed)
    items = rng.standard_normal((n_items, dim)).astype(np.float32)
    sset = ShardedMIPSIndex.standalone_set(nshards)
    return ShardedMIPSIndex.build(sset, items), items, sset


def _measure_recall(n_items=20000, dim=128, k=100, queries=64):
    """recall@k of int8 sharded topk vs the fp32 exact scan, plus the
    bitwise merge-vs-exact-scan check over the same codes."""
    import numpy as np
    idx, items, sset = _index(n_items, dim, nshards=4)
    try:
        rng = np.random.default_rng(1)
        users = rng.standard_normal((queries, dim)).astype(np.float32)
        # generous per-shard deadline: the bench measures recall, not
        # tail latency, and a first-call import stall must not eject
        # shards and hollow out the answer
        r = idx.topk(users, k, deadline_s=30.0)
        ref_s, ref_i = idx.exact_scan_fp32(users, items, k)
        hits = sum(len(np.intersect1d(r.ids[b], ref_i[b]))
                   for b in range(queries))
        recall = hits / float(queries * k)
        oracle_s, oracle_i = idx.exact_scan(users, k)
        exact = (np.array_equal(r.ids, oracle_i)
                 and np.array_equal(r.scores, oracle_s))
        return {"n_items": n_items, "dim": dim, "k": k,
                "recall_at_k": round(recall, 4),
                "recall_pass": recall >= 0.95,
                "merge_bitwise_exact": bool(exact)}
    finally:
        sset.close()


def _measure_throughput(n_items=20000, dim=128, k=100, queries=32,
                        iters=8):
    """Rows scored per second through the full sharded query path for
    shard counts {1, 2, 4}."""
    import numpy as np
    rng = np.random.default_rng(2)
    users = rng.standard_normal((queries, dim)).astype(np.float32)
    out = {}
    for ns in (1, 2, 4):
        idx, _, sset = _index(n_items, dim, nshards=ns)
        try:
            idx.topk(users, k, deadline_s=30.0)             # warm
            t0 = time.perf_counter()
            for _ in range(iters):
                idx.topk(users, k, deadline_s=30.0)
            dt = time.perf_counter() - t0
            rows = n_items * queries * iters
            out[f"shards_{ns}"] = {
                "rows_per_s": round(rows / dt),
                "query_ms": round(1e3 * dt / (iters * queries), 3)}
        finally:
            sset.close()
    return out


def _cascade(k, nshards, n_items, dim):
    """A real cascade: fixed-projection user encoder, sharded int8
    index, DLRM ranker behind an InferenceEngine."""
    import numpy as np
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
    from dlrm_flexflow_tpu.retrieve import (CascadeConfig, CascadeEngine,
                                            dlrm_candidate_features)
    dcfg = DLRMConfig(embedding_size=[n_items] * 8,
                      sparse_feature_size=16, mlp_bot=[16, 64, 16],
                      mlp_top=[144, 64, 1])
    cfg = ff.FFConfig(batch_size=64, seed=3, serve_max_batch=64)
    model = ff.FFModel(cfg)
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"])
    model.init_layers()
    eng = ff.InferenceEngine(model, ff.ServeConfig(
        max_batch=64, queue_capacity=4096))
    idx, _, sset = _index(n_items, dim, nshards=nshards)
    rng = np.random.default_rng(5)
    W = rng.standard_normal((dcfg.mlp_bot[0], dim)).astype(np.float32)

    def encode(feats):
        return np.asarray(feats["dense"], np.float32) @ W

    cascade = CascadeEngine(
        idx, encode, eng,
        dlrm_candidate_features(8, dcfg.embedding_size),
        CascadeConfig(k=k, retrieve_deadline_ms=1000.0))
    return cascade, eng, sset, dcfg


def _measure_cascade(requests=128, slo_ms=150.0, k=32, nshards=2,
                     n_items=8192, dim=32):
    """Attained cascade QPS at the p99 SLO under open-loop Poisson
    load, then the one-shard-dead chaos phase at half that rate."""
    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    from dlrm_flexflow_tpu.serve import percentile
    from dlrm_flexflow_tpu.utils import faults

    cascade, eng, sset, dcfg = _cascade(k, nshards, n_items, dim)
    x, _ = synthetic_batch(dcfg, requests, seed=0)
    reqs = [{kk: v[i:i + 1] for kk, v in x.items()}
            for i in range(requests)]
    pool = ThreadPoolExecutor(max_workers=32,
                              thread_name_prefix="ff-bench-cascade")

    def submit(req):
        return pool.submit(cascade.predict, req)

    out = {"k": k, "nshards": nshards, "slo_ms": slo_ms}
    try:
        with eng:
            best, detail = _qps_at_slo(submit, reqs, slo_ms,
                                       rates=[4, 8, 16, 32, 64, 128])
            out["qps_at_slo"] = best
            out["detail"] = detail

            # chaos: shard 1's retrieval surface dead for the whole
            # phase (-1 = until the plan clears); the bar is zero
            # failed requests — degraded-flagged answers only
            rate = max(best / 2.0, 4.0)
            d0 = cascade.degraded_requests
            with faults.active_plan(faults.FaultPlan(
                    topk_drop={1: -1})):
                lat, failed, _ = _poisson_drive(submit, reqs, rate)
            out["chaos"] = {
                "offered_qps": round(rate, 1),
                "failed": failed,
                "zero_failed": failed == 0,
                "degraded_requests": cascade.degraded_requests - d0,
                "p99_ms": round(percentile(lat, 99), 2) if lat else None}
            out["stats"] = cascade.stats()
    finally:
        pool.shutdown(wait=False)
        sset.close()
    return out


def measure(requests=128, slo_ms=150.0):
    return {
        "recall": _measure_recall(),
        "throughput": _measure_throughput(),
        "cascade": _measure_cascade(requests=requests, slo_ms=slo_ms),
    }


def main(argv=None):
    args = list(sys.argv[1:] if argv is None else argv)
    requests, slo_ms = 128, 150.0
    while args:
        a = args.pop(0)
        if a == "--requests":
            requests = int(args.pop(0))
        elif a == "--slo-ms":
            slo_ms = float(args.pop(0))
        else:
            raise SystemExit(f"unknown arg {a!r}")
    out = measure(requests=requests, slo_ms=slo_ms)
    print(json.dumps({"retrieve": out}))
    return 0


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    sys.exit(main())
