#!/usr/bin/env python
"""End-to-end REAL-data-path benchmark: Criteo-Kaggle-format data through
preprocess_hdf.py → .ffbin → FFBinDataLoader → train loop.

The reference's Criteo path is dlrm.cc:266-484 (HDF5 X_int/X_cat/y probed,
loaded whole into zero-copy memory, device-side scatter per batch) fed by
its preprocess_hdf.py. This benchmark drives the same chain here with
generated-but-format-faithful data, so the number includes the native
mmap+ring-buffer loader (native/ffloader.cc), not just synthetic arrays.

Prints one JSON line with samples/s. Usage:
    python benchmarks/bench_real_data.py [--samples N] [--epochs E]
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# run_criteo_kaggle.sh table sizes / MLP shapes
KAGGLE_SIZES = [1396, 550, 2700000, 2160000, 301, 22, 11878, 619, 3, 64889,
                5236, 2567820, 3136, 26, 12607, 471917, 11, 4970, 2159, 4,
                2586596, 7043, 61, 4, 930, 14]


def make_raw_npz(path: str, n: int, seed: int = 0):
    """Criteo-Kaggle raw format as the preprocessor expects it: integer
    counts X_int (pre-log), categorical ids X_cat, click labels y."""
    rng = np.random.RandomState(seed)
    x_int = rng.poisson(3.0, size=(n, 13)).astype(np.int64)
    x_cat = np.stack([rng.randint(0, s, size=n) for s in KAGGLE_SIZES],
                     axis=1).astype(np.int64)
    y = rng.randint(0, 2, size=n).astype(np.int64)
    np.savez(path, X_int=x_int, X_cat=x_cat, y=y)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--samples", type=int, default=131072)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch", type=int, default=256)
    args = ap.parse_args()

    tmp = tempfile.mkdtemp(prefix="ffbench_")
    raw = os.path.join(tmp, "raw.npz")
    h5 = os.path.join(tmp, "criteo.hdf5")
    ffbin = os.path.join(tmp, "criteo.ffbin")

    make_raw_npz(raw, args.samples)
    subprocess.check_call([sys.executable,
                           os.path.join(REPO, "examples", "native",
                                        "preprocess_hdf.py"),
                           "-i", raw, "-o", h5])

    from dlrm_flexflow_tpu.data.dataloader import (load_dlrm_hdf5,
                                                   write_ffbin)
    x, y = load_dlrm_hdf5(h5)
    write_ffbin(ffbin, x["dense"], x["sparse"], y)

    import jax

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.data.dataloader import FFBinDataLoader
    from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                               dlrm_strategy)

    cfg = ff.FFConfig(batch_size=args.batch, compute_dtype="bfloat16")
    dcfg = DLRMConfig(embedding_size=KAGGLE_SIZES, sparse_feature_size=16,
                      mlp_bot=[13, 512, 256, 64, 16],
                      mlp_top=[432, 512, 256, 1])
    model = ff.FFModel(cfg)
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.01), "mean_squared_error", ["mse"],
                  strategies=dlrm_strategy(model, dcfg, 1))
    model.init_layers()

    loader = FFBinDataLoader(model, ffbin)
    # warmup/compile
    model.train_batch_device(loader.next_batch())
    jax.block_until_ready(model.params)

    steps = 0
    t0 = time.time()
    mets = None
    for _ in range(args.epochs):
        for _ in range(loader.num_batches):
            mets = model.train_batch_device(loader.next_batch())
            steps += 1
    float(mets["loss"])                      # dependent readback
    elapsed = time.time() - t0
    thr = steps * args.batch / elapsed
    print(json.dumps({
        "metric": "dlrm_criteo_kaggle_realdata_throughput_per_chip",
        "value": round(thr, 2), "unit": "samples/s/chip",
        "samples": args.samples, "epochs": args.epochs,
        "loader": "ffbin(native mmap prefetch)"}))


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    main()
