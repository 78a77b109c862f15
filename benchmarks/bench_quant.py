#!/usr/bin/env python
"""Quantized embedding storage benchmark (ISSUE 14).

Measures, on one host, what the int8 row policy buys against fp32
across every byte surface it touches, plus what it costs in ranking
quality:

- ``footprint``: per-table HBM bytes (``hbm_footprint_report``) under
  fp32 vs int8 — acceptance bar >= 3.5x;
- ``exchange``: per-device all-to-all row-payload bytes of the
  row-sharded lookup under fp32 vs int8 policy (the DCN term the cost
  model prices) — bar >= 3.5x;
- ``delta``: measured on-disk delta-publish bytes (a DeltaPublisher
  pair over identical training) — row payloads bar >= 3.5x;
- ``cache``: EmbeddingCache rows-per-MB fp32 vs int8;
- ``auc``: ROC-AUC on a dlrm_kaggle-shaped model over synthetic
  learnable click data — fp32 vs int8 master_weight (structurally
  identical: delta == 0) and vs int8 stochastic_rounding (the
  measured quantized-training cost) — bar: delta <= 0.002.

Prints ONE JSON line; ``measure()`` is imported by bench.py when
BENCH_QUANT=1. Usage: python benchmarks/bench_quant.py
"""

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _kaggle_small():
    """dlrm_kaggle SHAPE (26 tables x 16-d, the run_criteo_kaggle.sh
    geometry) at CPU-bench row counts."""
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig
    # 500-row tables so the 4k-sample train set revisits each id ~enough
    # for the embeddings to learn the planted logistic signal
    return DLRMConfig(embedding_size=[500] * 26, sparse_feature_size=16,
                      embedding_bag_size=1,
                      mlp_bot=[13, 64, 16], mlp_top=[432, 64, 1])


def _build(dcfg, batch=128, seed=3, **cfg_kw):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import build_dlrm
    model = ff.FFModel(ff.FFConfig(batch_size=batch, seed=seed, **cfg_kw))
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.05), "mean_squared_error", ["mse"])
    model.init_layers()
    return model


def _click_data(dcfg, n, seed=0):
    """Synthetic LEARNABLE click data: labels from a sparse logistic
    ground truth over the categorical ids, so AUC moves off 0.5 and a
    quantization-induced quality drop is measurable."""
    import numpy as np
    rng = np.random.RandomState(seed)
    T = len(dcfg.embedding_size)
    bag = dcfg.embedding_bag_size
    dense = rng.rand(n, dcfg.mlp_bot[0]).astype(np.float32)
    sparse = np.stack(
        [rng.randint(0, rows, size=(n, bag))
         for rows in dcfg.embedding_size], axis=1).astype(np.int64)
    w = {t: rng.randn(dcfg.embedding_size[t]).astype(np.float32) * 2.0
         for t in range(T)}
    logits = sum(w[t][sparse[:, t, :]].sum(axis=1) for t in range(T))
    logits = logits / np.sqrt(T) + dense.sum(axis=1) - \
        dense.shape[1] / 2.0
    p = 1.0 / (1.0 + np.exp(-logits))
    y = (rng.rand(n) < p).astype(np.float32)[:, None]
    return {"dense": dense, "sparse": sparse}, y


def _auc(scores, labels):
    import numpy as np
    s = np.asarray(scores).reshape(-1)
    y = np.asarray(labels).reshape(-1)
    order = np.argsort(s)
    ranks = np.empty_like(order, dtype=np.float64)
    ranks[order] = np.arange(1, len(s) + 1)
    pos = y > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if not n_pos or not n_neg:
        return 0.5
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))


def _train_and_auc(dcfg, xtr, ytr, xte, yte, epochs, **cfg_kw):
    import numpy as np
    model = _build(dcfg, **cfg_kw)
    model.fit(xtr, ytr, epochs=epochs, verbose=False)
    scores = np.asarray(model.forward_batch(xte))
    return model, _auc(scores, yte)


def _measure_footprint():
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig
    from dlrm_flexflow_tpu.search.cost_model import CostModel
    from dlrm_flexflow_tpu.search.simulator import hbm_footprint_report
    dcfg = DLRMConfig(embedding_size=[200_000] * 4,
                      sparse_feature_size=64,
                      mlp_bot=[4, 16, 64], mlp_top=[320, 16, 1])
    m32 = _build(dcfg, batch=32)
    m8 = _build(dcfg, batch=32, emb_dtype="int8")
    cost = CostModel()
    r32 = hbm_footprint_report(m32, cost, m32.strategies, 1)
    r8 = hbm_footprint_report(m8, cost, m8.strategies, 1)
    name = max((k for k in r32 if k in r8), key=lambda k: r32[k])
    return {"table_fp32_mb": round(r32[name] / 1e6, 2),
            "table_int8_mb": round(r8[name] / 1e6, 2),
            "ratio": round(r32[name] / r8[name], 2)}, m32, m8, name


def _measure_exchange(m32, m8, name):
    from dlrm_flexflow_tpu.parallel.pconfig import ParallelConfig
    pc = ParallelConfig((8, 1, 1), param_degree=8)
    op32 = next(o for o in m32.ops if o.name == name)
    op8 = next(o for o in m8.ops if o.name == name)
    _, rows32, _ = op32.alltoall_payload_bytes(8, 4, pc=pc)
    _, rows8, _ = op8.alltoall_payload_bytes(8, 4, pc=pc)
    return {"rows_fp32_kb": round(rows32 / 1e3, 1),
            "rows_int8_kb": round(rows8 / 1e3, 1),
            "ratio": round(rows32 / rows8, 2)}


def _measure_delta(steps=8):
    import numpy as np

    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, synthetic_batch
    from dlrm_flexflow_tpu.utils.delta import DeltaPublisher
    dcfg = DLRMConfig(embedding_size=[20_000] * 4,
                      sparse_feature_size=64,
                      mlp_bot=[4, 16, 64], mlp_top=[320, 16, 1])
    out = {}
    for tag, kw in (("fp32", {}), ("int8", {"emb_dtype": "int8"})):
        model = _build(dcfg, batch=64, **kw)
        with tempfile.TemporaryDirectory() as tmp:
            pub = DeltaPublisher(model, tmp, keep_last=2)
            pub.publish_full()
            x, y = synthetic_batch(dcfg, 64 * steps, seed=0)
            model.fit(x, y, epochs=1, verbose=False)
            entry = pub.publish()
            out[tag] = int(entry["bytes"])
            # the ROW payload alone (the term the policy shrinks; the
            # total is diluted by the dense fulls both modes ship)
            data = np.load(os.path.join(tmp, entry["file"]))
            out[f"{tag}_row_payload"] = int(sum(
                data[k].nbytes for k in data.files
                if k.split("/")[0] in ("rows", "scl")))
            out[f"{tag}_rows"] = int(np.sum(
                [v for v in entry["touched_rows"].values()]))
    out["ratio"] = round(out["fp32"] / max(out["int8"], 1), 2)
    out["ratio_rows"] = round(out["fp32_row_payload"]
                              / max(out["int8_row_payload"], 1), 2)
    return out


def _measure_cache():
    import numpy as np

    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, synthetic_batch
    from dlrm_flexflow_tpu.serve.cache import EmbeddingCache
    dcfg = DLRMConfig(embedding_size=[4096] * 4, sparse_feature_size=64,
                      mlp_bot=[4, 16, 64], mlp_top=[320, 16, 1])
    model = _build(dcfg, batch=64, host_resident_tables=True,
                   host_tables_async=False)
    op = next(o for o in model.ops if hasattr(o, "host_lookup"))
    x, _ = synthetic_batch(dcfg, 256, seed=1)
    idx = np.ascontiguousarray(x["sparse"], np.int32)
    c32 = EmbeddingCache(4096)
    c8 = EmbeddingCache(4096, quant={op.name: "int8"})
    c32.lookup(op, model.host_params[op.name], idx)
    c8.lookup(op, model.host_params[op.name], idx)
    rows32 = len(c32) / max(c32.stored_bytes() / 1e6, 1e-9)
    rows8 = len(c8) / max(c8.stored_bytes() / 1e6, 1e-9)
    return {"rows_per_mb_fp32": round(rows32),
            "rows_per_mb_int8": round(rows8),
            "ratio": round(rows8 / rows32, 2)}


def _measure_auc(train_n=4096, test_n=4096, epochs=2):
    dcfg = _kaggle_small()
    xtr, ytr = _click_data(dcfg, train_n, seed=0)
    xte, yte = _click_data(dcfg, test_n, seed=1)
    _, auc32 = _train_and_auc(dcfg, xtr, ytr, xte, yte, epochs)
    _, auc8m = _train_and_auc(dcfg, xtr, ytr, xte, yte, epochs,
                              emb_dtype="int8")
    _, auc8s = _train_and_auc(dcfg, xtr, ytr, xte, yte, epochs,
                              emb_dtype="int8",
                              emb_update_rule="stochastic_rounding")
    return {"fp32": round(auc32, 4),
            "int8_master": round(auc8m, 4),
            "int8_sr": round(auc8s, 4),
            # master_weight trains the exact fp32 master — the delta is
            # structurally zero (bit-identical params); SR is the
            # measured quantized-training cost
            "auc_delta_master": round(abs(auc8m - auc32), 5),
            "auc_delta_sr": round(abs(auc8s - auc32), 5)}


def measure(auc_epochs=2):
    footprint, m32, m8, name = _measure_footprint()
    return {
        "footprint": footprint,
        "exchange": _measure_exchange(m32, m8, name),
        "delta": _measure_delta(),
        "cache": _measure_cache(),
        "auc": _measure_auc(epochs=auc_epochs),
    }


def main():
    out = measure()
    print(json.dumps({"quant": out}))
    return 0


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    sys.exit(main())
