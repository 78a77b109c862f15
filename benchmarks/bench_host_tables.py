#!/usr/bin/env python
"""Host-resident tables smoke/benchmark on the REAL chip: a DLRM whose
embedding tables EXCEED the chip's HBM trains on one chip with the tables
in host RAM (the reference hetero-strategy capability,
embedding_avx2.cc + dlrm_strategy_hetero.cc:28-49 — what makes
DLRM-Terabyte runnable on few devices).

Default config: 8 tables x 10M rows x 64-d fp32 = 20.5 GB of tables vs
16 GB of v5e HBM. Prints one JSON line.

  python benchmarks/bench_host_tables.py [--rows 10000000] [--steps 50]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_000_000)
    ap.add_argument("--tables", type=int, default=8)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--steps", type=int, default=50)
    args = ap.parse_args()

    import jax

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                               synthetic_batch)

    table_gb = args.tables * args.rows * 64 * 4 / 1e9
    cfg = ff.FFConfig(batch_size=args.batch, compute_dtype="bfloat16",
                      host_resident_tables=True)
    dcfg = DLRMConfig(
        embedding_size=[args.rows] * args.tables,
        sparse_feature_size=64,
        mlp_bot=[64, 512, 512, 64],
        mlp_top=[64 * (args.tables + 1), 1024, 1024, 1024, 1])
    model = ff.FFModel(cfg)
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.01), "mean_squared_error", ["mse"])
    model.init_layers()
    emb = next(iter(model.host_params))
    host_gb = sum(v.nbytes for v in model.host_params[emb].values()) / 1e9

    batches = []
    for i in range(4):
        x, y = synthetic_batch(dcfg, args.batch, seed=i)
        x["label"] = y
        batches.append(model._device_batch(x))

    model.train_batch_device(batches[0])   # warm/compile

    def window():
        t0 = time.time()
        mets = None
        for s in range(args.steps):
            mets = model.train_batch_device(batches[s % 4])
        loss = float(mets["loss"])
        model._host_drain()
        return args.steps * args.batch / (time.time() - t0), loss

    tput_sync, loss = window()
    # pipelined mode: previous step's cotangent readback + host scatter
    # overlap the next step's gather/H2D (bounded one-step staleness)
    model.config.host_tables_async = True
    tput_async, loss_a = window()
    print(json.dumps({
        "metric": "dlrm_host_resident_tables_throughput_per_chip",
        "value": round(tput_sync, 2),
        "async_value": round(tput_async, 2),
        "unit": "samples/s/chip",
        "table_gb": round(table_gb, 1),
        "host_resident_gb": round(host_gb, 1),
        "hbm_gb": 16,
        "loss": round(loss, 5)}))


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    main()
