#!/usr/bin/env python
"""Continual-learning freshness benchmark: train-step → servable latency.

The continual loop's whole point is that publishing only the TOUCHED
embedding rows (plus the small dense params) shrinks the trainer→server
hand-off from checkpoint-sized to touched-rows-sized. This bench runs a
combined train+serve loop on one host — a DLRM whose tables dominate the
snapshot (the production shape) — and measures, for each publish, the
time from the trained state existing (just before ``publish()``) to the
serving engine having APPLIED that version, under two publication modes:

- ``delta``: :class:`~dlrm_flexflow_tpu.utils.delta.DeltaPublisher`
  chain — atomic delta files, incremental ``apply_delta`` installs;
- ``full``: a full checkpoint per publish (the pre-ISSUE-10 path:
  write the whole npz, watcher reloads all params).

Acceptance bar (ISSUE 10): delta p99 <= 0.25 x full p99.

Prints ONE JSON line; ``measure()`` is imported by bench.py when
BENCH_FRESHNESS=1. Usage: python benchmarks/bench_freshness.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _build(seed=3, rows=120_000):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
    # tables dominate: 4 x rows x 16-d fp32 ≈ 30 MB of a ~31 MB snapshot
    dcfg = DLRMConfig(embedding_size=[rows] * 4, sparse_feature_size=16,
                      mlp_bot=[8, 32, 16], mlp_top=[80, 32, 1])
    cfg = ff.FFConfig(batch_size=64, seed=seed)
    model = ff.FFModel(cfg)
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"])
    model.init_layers()
    return model, dcfg


def _pct(sorted_vals, p):
    from dlrm_flexflow_tpu.serve.engine import percentile
    return percentile(sorted_vals, p)


def _run_mode(mode, publishes, steps_per_publish, tmp, poll_s):
    import numpy as np

    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    from dlrm_flexflow_tpu.serve import InferenceEngine, ServeConfig
    from dlrm_flexflow_tpu.serve.watcher import SnapshotWatcher
    from dlrm_flexflow_tpu.utils.delta import DeltaPublisher

    trainer, dcfg = _build(seed=3)
    x, y = synthetic_batch(dcfg, 64, seed=0)
    xb = dict(x)
    xb["label"] = y
    d = os.path.join(tmp, mode)
    os.makedirs(d, exist_ok=True)
    pub = DeltaPublisher(trainer, d, keep_last=2, compact_frac=1e9)

    def train_step():
        # observe-then-train, exactly like fit_stream's staging hook:
        # the tracker's touched-row candidates keep the publish-time
        # diff touched-rows-sized instead of table-sized
        pub.observe_batch(xb)
        trainer.train_batch(xb)

    train_step()                     # base step >= 1: a fresh engine
    base = pub.publish_full({})      # (version 0) must reload it

    server, _ = _build(seed=9)
    eng = InferenceEngine(server, ServeConfig(max_batch=64, warmup=False))
    eng.start()
    watcher = SnapshotWatcher(eng, d, poll_s=poll_s)
    watcher.start()
    lat_s = []
    bytes_published = 0
    try:
        # let the engine pick up the base before timing
        deadline = time.time() + 120
        while (eng._applied_version < base["step"]
               and time.time() < deadline):
            time.sleep(0.01)
        if eng._applied_version < base["step"]:
            raise RuntimeError("engine never loaded the base snapshot")
        # one untimed publish cycle: the first delta apply compiles its
        # row-scatter executables; freshness is the steady-state number
        train_step()
        warm = (pub.publish_delta({}) if mode == "delta"
                else pub.publish_full({}))
        deadline = time.time() + 120
        while (eng._applied_version < int(trainer._step)
               and time.time() < deadline):
            time.sleep(poll_s / 4)
        for _ in range(publishes):
            for _ in range(steps_per_publish):
                train_step()
            step = int(trainer._step)
            t0 = time.perf_counter()
            entry = (pub.publish_delta({}) if mode == "delta"
                     else pub.publish_full({}))
            deadline = time.time() + 120
            while eng._applied_version < step and time.time() < deadline:
                time.sleep(poll_s / 4)
            if eng._applied_version < step:
                raise RuntimeError(
                    f"engine never reached version {step} "
                    f"(at {eng._applied_version})")
            lat_s.append(time.perf_counter() - t0)
            if entry is not None:
                f = os.path.join(d, entry["file"])
                bytes_published += (os.path.getsize(f)
                                    if os.path.isfile(f) else 0)
        # sanity: the served scores match the trainer's, bit for bit
        got = np.asarray(eng.model.forward_bucket(
            {k: v[:4] for k, v in x.items()}))
        want = np.asarray(trainer.forward_bucket(
            {k: v[:4] for k, v in x.items()}))
        if not np.array_equal(got, want):
            raise RuntimeError("served state diverged from the trainer")
    finally:
        watcher.stop()
        eng.close()
    lat_ms = sorted(1e3 * v for v in lat_s)
    return {
        "p50_ms": round(_pct(lat_ms, 50), 2),
        "p99_ms": round(_pct(lat_ms, 99), 2),
        "mean_ms": round(sum(lat_ms) / len(lat_ms), 2),
        "publishes": len(lat_ms),
        "bytes_per_publish": int(bytes_published / max(len(lat_ms), 1)),
    }


def measure(publishes=12, steps_per_publish=4, poll_s=0.005):
    """Both modes on the same shapes; returns the comparison dict."""
    import tempfile

    tmp = tempfile.mkdtemp(prefix="bench_freshness_")
    delta = _run_mode("delta", publishes, steps_per_publish, tmp, poll_s)
    full = _run_mode("full", publishes, steps_per_publish, tmp, poll_s)
    ratio = (delta["p99_ms"] / full["p99_ms"]
             if full["p99_ms"] else float("inf"))
    return {
        "delta": delta,
        "full": full,
        "p99_ratio_delta_vs_full": round(ratio, 4),
        "bar": "delta p99 <= 0.25 x full p99",
        "pass": bool(ratio <= 0.25),
        "quant_publish": _quant_publish_bytes(),
    }


def _quant_publish_bytes(steps=8):
    """ISSUE 14 rider: measured on-disk delta-publish bytes under the
    int8 row policy vs fp32, identical training on the tables-dominated
    shape — the publish-bytes half of the quantized-storage bar (the
    row payload is the term the policy shrinks; the total is diluted by
    the dense fulls both modes ship)."""
    import tempfile as _tf

    import numpy as np

    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    from dlrm_flexflow_tpu.utils.delta import DeltaPublisher
    out = {}
    for tag, kw in (("fp32", {}), ("int8", {"emb_dtype": "int8"})):
        import dlrm_flexflow_tpu as ff
        from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
        dcfg = DLRMConfig(embedding_size=[120_000] * 4,
                          sparse_feature_size=64,
                          mlp_bot=[8, 32, 64], mlp_top=[320, 32, 1])
        model = ff.FFModel(ff.FFConfig(batch_size=64, seed=3, **kw))
        build_dlrm(model, dcfg)
        model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error",
                      ["mse"])
        model.init_layers()
        with _tf.TemporaryDirectory() as tmp2:
            pub = DeltaPublisher(model, tmp2, keep_last=2)
            pub.publish_full()
            x, y = synthetic_batch(dcfg, 64 * steps, seed=0)
            model.fit(x, y, epochs=1, verbose=False)
            entry = pub.publish()
            out[f"bytes_{tag}"] = int(entry["bytes"])
            data = np.load(os.path.join(tmp2, entry["file"]))
            out[f"row_payload_{tag}"] = int(sum(
                data[k].nbytes for k in data.files
                if k.split("/")[0] in ("rows", "scl")))
    out["ratio"] = round(out["bytes_fp32"] / max(out["bytes_int8"], 1), 2)
    out["ratio_rows"] = round(
        out["row_payload_fp32"] / max(out["row_payload_int8"], 1), 2)
    return out


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    publishes = int(os.environ.get("BENCH_FRESHNESS_PUBLISHES", "12"))
    print(json.dumps(measure(publishes=publishes)))
