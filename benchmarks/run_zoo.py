#!/usr/bin/env python
"""Model-zoo throughput sweep (the BASELINE.md tracked configs).

Measures honest per-chip training throughput for each model family at its
reference benchmark shape, synchronizing every window with a dependent
host readback (async dispatch timing is fiction on some PJRT backends).
Prints one JSON line per config; bench.py remains the driver's single
headline metric.

Usage: python benchmarks/run_zoo.py [--quick] [--only NAME]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _measure(model, batch_dict, batch_size, steps=30, windows=3):
    import jax
    import jax.numpy as jnp

    db = model._device_batch(batch_dict)
    args = (model.params, model.opt_state, model.op_state,
            model._zero_msums(), db, jnp.asarray(0, jnp.int32))
    compiled = model._train_step.lower(*args).compile()
    # the program donates its carries (the counter too) and hands back
    # the loss and the metrics as one vector (core/metrics.StepMetrics)
    p, o, s, m, st, vec = compiled(*args)
    np.asarray(vec)
    best = 0.0
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            p, o, s, m, st, vec = compiled(p, o, s, m, db, st)
        np.asarray(vec)                     # real synchronization
        best = max(best, steps * batch_size / (time.perf_counter() - t0))
    return best


def _bench_dlrm(cfg_factory, quick):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import (build_dlrm, dlrm_strategy,
                                               synthetic_batch)
    batch = 256
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="bfloat16")
    dcfg = cfg_factory()
    model = ff.FFModel(cfg)
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.01), "mean_squared_error", ["mse"],
                  strategies=dlrm_strategy(model, dcfg, 1))
    model.init_layers()
    x, y = synthetic_batch(dcfg, batch)
    x["label"] = y
    # short-step configs need deep windows: the pipeline fill before the
    # first step completes is amortized over the window's N steps
    return _measure(model, x, batch, steps=10 if quick else 500)


def bench_dlrm_random(quick):
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig
    return _bench_dlrm(DLRMConfig.random_benchmark, quick)


def bench_dlrm_criteo(quick):
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig
    return _bench_dlrm(DLRMConfig.criteo_kaggle, quick)


def _image_batch(batch, hw, classes=1000, seed=0):
    rng = np.random.RandomState(seed)
    return {"image": rng.rand(batch, 3, hw, hw).astype(np.float32),
            "label": rng.randint(0, classes, (batch, 1)).astype(np.int32)}


def bench_alexnet(quick):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.alexnet import build_alexnet
    batch = 256
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="bfloat16")
    cfg.conv_s2d = os.environ.get("FF_CONV_S2D", "off")
    if cfg.conv_s2d not in ("on", "off", "auto"):
        raise ValueError(f"FF_CONV_S2D expects on|off|auto, "
                         f"got {cfg.conv_s2d!r}")
    model = ff.FFModel(cfg)
    build_alexnet(model, num_classes=1000, image_hw=224)
    model.compile(ff.SGDOptimizer(lr=0.01),
                  "sparse_categorical_crossentropy", ["accuracy"])
    model.init_layers()
    return _measure(model, _image_batch(batch, 224), batch,
                    steps=5 if quick else 60)


def bench_resnet18(quick):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.resnet import build_resnet
    batch = 256
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="bfloat16")
    cfg.conv_s2d = os.environ.get("FF_CONV_S2D", "off")
    if cfg.conv_s2d not in ("on", "off", "auto"):
        raise ValueError(f"FF_CONV_S2D expects on|off|auto, "
                         f"got {cfg.conv_s2d!r}")
    model = ff.FFModel(cfg)
    build_resnet(model, depth=18, num_classes=1000, image_hw=224)
    model.compile(ff.SGDOptimizer(lr=0.01),
                  "sparse_categorical_crossentropy", ["accuracy"])
    model.init_layers()
    return _measure(model, _image_batch(batch, 224), batch,
                    steps=5 if quick else 60)


def bench_inception(quick):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.inception import build_inception_v3
    batch = 256
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="bfloat16")
    cfg.conv_s2d = os.environ.get("FF_CONV_S2D", "off")
    if cfg.conv_s2d not in ("on", "off", "auto"):
        raise ValueError(f"FF_CONV_S2D expects on|off|auto, "
                         f"got {cfg.conv_s2d!r}")
    model = ff.FFModel(cfg)
    build_inception_v3(model, num_classes=1000)
    model.compile(ff.SGDOptimizer(lr=0.01),
                  "sparse_categorical_crossentropy", ["accuracy"])
    model.init_layers()
    return _measure(model, _image_batch(batch, 299), batch,
                    steps=3 if quick else 30, windows=2)


def bench_nmt(quick):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.nmt import build_nmt
    batch, seq, vocab = 64, 40, 32 * 1024
    model = ff.FFModel(ff.FFConfig(batch_size=batch,
                                   compute_dtype="bfloat16"))
    build_nmt(model, src_vocab=vocab, tgt_vocab=vocab, embed_dim=1024,
              hidden=1024, num_layers=2, src_len=seq, tgt_len=seq)
    model.compile(ff.SGDOptimizer(lr=0.1),
                  "sparse_categorical_crossentropy", ["accuracy"])
    model.init_layers()
    rng = np.random.RandomState(0)
    x = {"src": rng.randint(0, vocab, (batch, seq)).astype(np.int32),
         "tgt": rng.randint(0, vocab, (batch, seq)).astype(np.int32),
         "label": rng.randint(0, vocab, (batch, seq)).astype(np.int32)}
    return _measure(model, x, batch, steps=5 if quick else 100)


def bench_candle_uno(quick):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.candle_uno import build_candle_uno
    batch = 256
    model = ff.FFModel(ff.FFConfig(batch_size=batch,
                                   compute_dtype="bfloat16"))
    inputs = build_candle_uno(model)
    if isinstance(inputs, tuple):
        inputs = inputs[0]
    model.compile(ff.SGDOptimizer(lr=0.01), "mean_squared_error", ["mse"])
    model.init_layers()
    rng = np.random.RandomState(0)
    x = {name: rng.rand(*shape).astype(np.float32)
         for name, shape in inputs.items()}
    x["label"] = rng.rand(batch, 1).astype(np.float32)
    return _measure(model, x, batch, steps=10 if quick else 500)


BENCHES = {
    "dlrm_random": bench_dlrm_random,
    "dlrm_criteo_kaggle": bench_dlrm_criteo,
    "alexnet_224": bench_alexnet,
    "resnet18_224": bench_resnet18,
    "inception_v3_299": bench_inception,
    "nmt_lstm_2x1024": bench_nmt,
    "candle_uno": bench_candle_uno,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    for name, fn in BENCHES.items():
        if args.only and args.only not in name:
            continue
        try:
            sps = fn(args.quick)
            print(json.dumps({"config": name,
                              "samples_per_sec_per_chip": round(sps, 1)}),
                  flush=True)
        except Exception as e:  # keep sweeping
            print(json.dumps({"config": name, "error": str(e)[:200]}),
                  flush=True)


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    main()
