#!/usr/bin/env python
"""DLRM training app (reference: examples/cpp/DLRM/dlrm.cc top_level_task
at :77 — arg parsing :84-96/:201-264, graph build :103-128, data loading
:266-589, train loop :166-187, throughput report :197-198).

Accepts the reference's flag spellings, e.g.:

  python examples/native/dlrm.py -ll:gpu 8 -b 2048 -e 2 \\
      --arch-embedding-size 1000000-1000000-1000000-1000000-1000000-1000000-1000000-1000000 \\
      --arch-sparse-feature-size 64 --arch-mlp-bot 64-512-512-64 \\
      --arch-mlp-top 576-1024-1024-1024-1 \\
      --budget 200 --export best.pb

Data: --data-path file.npz (dense/sparse/label arrays) or .ffbin
(data.dataloader.write_ffbin format); otherwise synthetic random like
run_random.sh.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.data.dataloader import FFBinDataLoader, SingleDataLoader
from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                           dlrm_strategy, synthetic_batch)
from dlrm_flexflow_tpu.parallel.mesh import make_mesh
from dlrm_flexflow_tpu.parallel.strategy_io import load_strategies
from dlrm_flexflow_tpu.search.mcmc import optimize
from dlrm_flexflow_tpu.utils.logging import get_logger

log_app = get_logger("dlrm")


def _check_sparse_bounds(sparse, dcfg):
    """Fail loudly when categorical indices exceed the configured table
    sizes: the embedding gather wraps indices modulo the table (silent row
    aliasing), so a --hash-size / --arch-embedding-size mismatch would
    otherwise train on wrong rows with a plausible-looking loss."""
    maxes = sparse.reshape(sparse.shape[0], sparse.shape[1], -1).max(
        axis=(0, 2))
    for t, (mx, rows) in enumerate(zip(maxes, dcfg.embedding_size)):
        if mx >= rows:
            raise ValueError(
                f"table {t}: max categorical index {int(mx)} >= configured "
                f"table size {rows}; regenerate the dataset with a matching "
                f"--hash-size or fix --arch-embedding-size")


def main(argv=None):
    if os.environ.get("NUM_PROCESSES") or os.environ.get(
            "COORDINATOR_ADDRESS"):
        # multi-host launch (reference run_summit.sh over GASNet)
        from dlrm_flexflow_tpu.parallel.distributed import \
            initialize_distributed
        initialize_distributed()
    ff.use_compile_cache()
    cfg = ff.FFConfig.parse_args(argv)
    dcfg = DLRMConfig.parse_args(cfg.unparsed)
    data_path = None
    rest = cfg.unparsed
    if "--data-path" in rest:
        data_path = rest[rest.index("--data-path") + 1]

    import jax
    multiproc = jax.process_count() > 1
    if multiproc:
        # every rank runs this same SPMD program over the global mesh;
        # the process axis is the DCN axis (reference: one control-
        # replicated top_level_task per node, model.cc:1384-1409)
        from dlrm_flexflow_tpu.parallel.distributed import \
            make_multihost_mesh
        ndev = len(jax.devices())
        mesh = make_multihost_mesh()
    else:
        ndev = min(cfg.num_devices, len(jax.devices())) or len(jax.devices())
        mesh = make_mesh(num_devices=ndev)
    log_app.info("platform=%s device_kind=%r devices=%d processes=%d "
                 "batch=%d tables=%d zipf_alpha=%g",
                 jax.devices()[0].platform, jax.devices()[0].device_kind,
                 ndev, jax.process_count(), cfg.batch_size,
                 len(dcfg.embedding_size), dcfg.zipf_alpha)

    model = ff.FFModel(cfg)
    build_dlrm(model, dcfg)

    # strategy: --import file > MCMC search (--budget) > hand-written DLRM
    if cfg.import_strategy_file:
        strategies = load_strategies(cfg.import_strategy_file)
        log_app.info("imported strategies from %s", cfg.import_strategy_file)
    elif cfg.search_budget > 0:
        # compile() exports the searched map when cfg.export_strategy_file
        # is set (--export), matching the reference's flow
        strategies = optimize(model, budget=cfg.search_budget,
                              alpha=cfg.search_alpha, ndev=ndev, verbose=True)
    else:
        strategies = dlrm_strategy(model, dcfg, ndev)

    model.compile(ff.SGDOptimizer(lr=cfg.learning_rate), "mean_squared_error",
                  ["mse"], mesh=mesh, strategies=strategies)
    model.init_layers()

    if data_path and data_path.endswith(".ffbin"):
        loader = FFBinDataLoader(model, data_path)
        num_batches = loader.num_batches
        next_batch = loader.next_batch
    elif data_path and (data_path.endswith(".h5")
                        or data_path.endswith(".hdf5")):
        # Criteo HDF5 from examples/native/preprocess_hdf.py (reference
        # dlrm.cc:266-382 reads the same X_int/X_cat/y layout)
        from dlrm_flexflow_tpu.data import load_dlrm_hdf5
        x, y = load_dlrm_hdf5(data_path)
        _check_sparse_bounds(x["sparse"], dcfg)
        loader = SingleDataLoader(model, x, y)
        num_batches = loader.num_batches
        next_batch = loader.next_batch
    elif data_path:
        d = np.load(data_path)
        _check_sparse_bounds(d["sparse"], dcfg)
        loader = SingleDataLoader(
            model, {"dense": d["dense"], "sparse": d["sparse"]}, d["label"])
        num_batches = loader.num_batches
        next_batch = loader.next_batch
    else:  # synthetic, like run_random.sh
        x, y = synthetic_batch(dcfg, cfg.batch_size)
        x["label"] = y
        if multiproc:
            # each rank contributes its host-local slice of the global
            # batch (reference: per-node zero-copy dataset residency,
            # dlrm.cc:384-484)
            from dlrm_flexflow_tpu.parallel.distributed import (
                global_batch_from_host_local, host_local_slice)
            staged = global_batch_from_host_local(host_local_slice(x), mesh)
        else:
            staged = model._device_batch(x)
        num_batches = 64
        next_batch = lambda: staged  # noqa: E731

    # warmup epoch compiles the jitted step (the reference warms its Legion
    # trace in epoch 0 before begin_trace, dlrm.cc:178-185)
    model.train_batch_device(next_batch())
    jax.block_until_ready(model.params)

    # fused supersteps (--superstep K): the synthetic loop dispatches K
    # steps per host→device call, amortizing the dispatch floor exactly
    # like fit() does (loader-fed runs stay per-step here; use fit() for
    # the full staged/prefetched superstep pipeline, and for the default
    # "auto": the pace probe is fit()'s, so this loop resolves it to 1)
    k_super = 1
    sstaged = None
    if not multiproc and data_path is None:
        k_super = model.resolve_superstep()
        k_super = k_super if k_super <= num_batches else 1
        if k_super > 1:
            from dlrm_flexflow_tpu.data.prefetch import stack_batches
            sstaged = model._stage_superstep(stack_batches([x] * k_super))
            model.train_batch_staged(sstaged)     # warm the fused exec
            jax.block_until_ready(model.params)

    if cfg.profiling:
        # per-op timing table (reference --profiling cudaEvent prints)
        from dlrm_flexflow_tpu.utils.profiling import (format_profile,
                                                       profile_ops)
        print(format_profile(profile_ops(model)))
    from dlrm_flexflow_tpu.utils.profiling import TraceContext
    # bound the number of in-flight async steps: XLA CPU's in-process
    # collectives can starve when many multi-device executions queue up on
    # few host cores; on real TPUs the device is the bottleneck, so a much
    # deeper pipeline is safe
    throttle = 1 if jax.default_backend() == "cpu" else 16
    t0 = time.time()
    step = 0
    with TraceContext(cfg.profile_dir or None):
        for _epoch in range(cfg.epochs):
            model.reset_metrics()
            b = 0
            while b < num_batches:
                if sstaged is not None and b + k_super <= num_batches:
                    mets = model.train_batch_staged(sstaged)
                    adv = k_super
                else:
                    mets = model.train_batch_device(next_batch())
                    adv = 1
                b += adv
                prev = step
                step += adv
                if step // throttle != prev // throttle:
                    # wait for the step, read nothing
                    jax.block_until_ready(mets.vector)
        jax.block_until_ready(model.params)
    elapsed = time.time() - t0
    n_samples = cfg.epochs * num_batches * cfg.batch_size
    print(f"{model.perf.summary_line()}")
    print(f"ELAPSED TIME = {elapsed:.4f}s, THROUGHPUT = "
          f"{n_samples / elapsed:.2f} samples/s")


if __name__ == "__main__":
    main(sys.argv[1:])
