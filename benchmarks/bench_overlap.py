#!/usr/bin/env python
"""Pipelined-exchange benchmark: overlap on/off for the row-shard
all-to-all (ISSUE 19).

Measures, on the attached mesh (CPU-virtual or real accelerator):

- ``steps_per_s_{serial,overlap}`` — steady-state training rate of the
  same row-sharded DLRM with the exchange as one blocking
  ``lax.all_to_all`` vs decomposed into ppermute/chunked rounds that
  pipeline under the gather/scatter (``ParallelConfig.overlap``);
- ``overlap_vs_serial`` — the measured ratio. NOTE: on a CPU-virtual
  mesh the decomposed rounds SERIALIZE (no DMA engine to ride), so the
  ratio is expected <= 1 there — the measurement is honest about where
  the win comes from, and the simulated section prices the real
  topology;
- ``exposed_comm_fraction`` — from the obs.trace spans wrapped around
  each step: the fraction of the serial step the pipelining uncovered,
  (t_serial - t_overlap) / t_serial, alongside the cost model's
  predicted exchange/window split for the same plan;
- ``sim_overlap_dcn`` — the simulated DCN-topology bar (>= 1.5x step
  time, bench_shard._sim_overlap_dcn) plus whether a from-scratch MCMC
  walk picks the pipelined plan unforced.

Prints ONE JSON line (the BENCH_*.json convention); `measure()` is also
imported by bench.py when BENCH_OVERLAP=1.

Usage: python benchmarks/bench_overlap.py [--steps N]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

ROWS = int(os.environ.get("BENCH_OVERLAP_ROWS", "131072"))
TABLES = 8
DIM = 128
BAG = 4


def _build(ndev, batch, overlap):
    import jax

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
    from dlrm_flexflow_tpu.parallel.mesh import make_mesh
    from dlrm_flexflow_tpu.parallel.pconfig import ParallelConfig

    dcfg = DLRMConfig(embedding_size=[ROWS] * TABLES,
                      sparse_feature_size=DIM, embedding_bag_size=BAG,
                      mlp_bot=[DIM, 256, DIM],
                      mlp_top=[DIM * (TABLES + 1), 256, 1])
    model = ff.FFModel(ff.FFConfig(batch_size=batch, seed=0))
    build_dlrm(model, dcfg)
    strat = {}
    for op in model.ops:
        nd = op.outputs[0].num_dims if op.outputs else 0
        if type(op).__name__ == "EmbeddingBagStacked":
            strat[op.name] = ParallelConfig((ndev, 1, 1),
                                            param_degree=ndev,
                                            overlap=overlap)
        elif nd:
            strat[op.name] = ParallelConfig.data_parallel(nd, ndev)
    model.compile(ff.SGDOptimizer(lr=0.05), "mean_squared_error",
                  ["mse"], mesh=make_mesh(devices=jax.devices()[:ndev]),
                  strategies=strat)
    model.init_layers()
    return model, dcfg


def _timed_steps(model, batches, steps, label):
    """Run `steps` training steps, each wrapped in an obs.trace span —
    the per-variant step time is then read back OUT of the span ring
    (the exposed-comm fraction is derived from spans, not wall clocks,
    so a trace viewer shows the same numbers this bench reports)."""
    from dlrm_flexflow_tpu.obs import trace as obstrace

    model.train_batch_device(batches[0])          # warm/compile
    n = len(batches)
    for s in range(steps):
        with obstrace.span(f"bench_overlap/{label}", cat="bench"):
            mets = model.train_batch_device(batches[s % n])
            float(mets["loss"])                   # span = true step time
    durs = [ev["dur"] * 1e-6 for ev in obstrace.events()
            if ev.get("name") == f"bench_overlap/{label}"
            and ev.get("ph") == "X"]
    return min(durs) if durs else float("inf")


def measure(steps: int = 8):
    import jax

    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    from dlrm_flexflow_tpu.obs import trace as obstrace

    ndev = len(jax.devices())
    out = {"ndev": ndev, "rows": ROWS, "tables": TABLES, "dim": DIM,
           "bag": BAG}
    if ndev < 2:
        out["skipped"] = "needs >= 2 devices for a row-shard exchange"
    else:
        batch = 256 * ndev
        out["batch"] = batch
        with obstrace.override(True):
            for label, overlap in (("serial", False), ("overlap", True)):
                model, dcfg = _build(ndev, batch, overlap)
                batches = []
                for i in range(4):
                    x, y = synthetic_batch(dcfg, batch, seed=i)
                    x["label"] = y
                    batches.append(model._device_batch(x))
                jax.block_until_ready(batches)
                t = _timed_steps(model, batches, steps, label)
                out[f"step_ms_{label}"] = round(t * 1e3, 3)
                out[f"steps_per_s_{label}"] = round(1.0 / t, 3)
                del model, batches
        t_ser = out["step_ms_serial"]
        t_ovl = out["step_ms_overlap"]
        out["overlap_vs_serial"] = round(t_ser / t_ovl, 3)
        # measured uncovering, from the spans: how much of the serial
        # step the pipelined exchange removed (<= 0 on a CPU mesh)
        out["exposed_comm_fraction"] = round((t_ser - t_ovl) / t_ser, 4)
        out["predicted"] = _predicted_fraction(ndev, batch)

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from bench_shard import _sim_overlap_dcn
    out["sim_overlap_dcn"] = _sim_overlap_dcn()
    return out


def _predicted_fraction(ndev, batch):
    """Cost-model split for the measured plan: exchange time, the
    exposed-compute window it can hide under, and the exchange share of
    the serial step — the prediction FLX514 compares against."""
    import jax.numpy as jnp

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
    from dlrm_flexflow_tpu.parallel.pconfig import ParallelConfig
    from dlrm_flexflow_tpu.search.cost_model import CostModel

    dcfg = DLRMConfig(embedding_size=[ROWS] * TABLES,
                      sparse_feature_size=DIM, embedding_bag_size=BAG,
                      mlp_bot=[DIM, 256, DIM],
                      mlp_top=[DIM * (TABLES + 1), 256, 1])
    model = ff.FFModel(ff.FFConfig(batch_size=batch, seed=0))
    build_dlrm(model, dcfg)
    cost = CostModel()
    emb = next(op for op in model.ops
               if type(op).__name__ == "EmbeddingBagStacked")
    pc = ParallelConfig((ndev, 1, 1), param_degree=ndev)
    itemsize = jnp.dtype(cost.compute_dtype).itemsize
    exch = sum(cost.alltoall_time_axes(b, [("ici", ndev)])
               for b in emb.alltoall_payload_bytes(ndev, itemsize,
                                                   pc=pc))
    window = 0.0
    for op in model.ops:
        if op is emb or not op.outputs:
            continue
        opc = ParallelConfig.data_parallel(op.outputs[0].num_dims, ndev)
        window += cost.op_compute_time(op, opc)
        window += cost.op_compute_time(op, opc, backward=True)
    return {
        "exchange_ms": round(exch * 1e3, 4),
        "window_ms": round(window * 1e3, 4),
        "hideable_fraction": round(
            cost.overlap_efficiency() * min(window, exch)
            / max(exch, 1e-12), 4),
    }


def main(argv):
    steps = 8
    if "--steps" in argv:
        steps = int(argv[argv.index("--steps") + 1])
    print(json.dumps({"metric": "overlap_exchange", **measure(steps)}))
    return 0


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    sys.exit(main(sys.argv[1:]))
