#!/usr/bin/env python
"""Pod-scale embedding-sharding benchmark: row-sharded all-to-all
lookups vs replicated tables vs table-dim sharding.

Measures, on the attached mesh (CPU-virtual or real accelerator):

- ``steps_per_s_{replicated,row_sharded,table_sharded}`` — steady-state
  training rate of the same DLRM under the three table placements:
  pure data-parallel (every device holds every table), PARAM-axis row
  sharding (each device holds rows/N of every table, lookups routed by
  explicit all-to-all — the ZionEX/DLRM-Terabyte shape), and classic
  table-dim sharding (each device holds whole tables);
- ``row_vs_replicated`` — the headline ratio (the paper's bar: >= 1.5x
  pure DP on tables that fit no single device);
- ``a2a_bytes_per_step`` — all-to-all bytes one device exchanges per
  step under the balanced exchange model (ids out, rows back, gradient
  rows out);
- ``sim_pod_sweep`` — cost-model step times for replicated vs
  row-sharded plans on simulated pod topologies (flat ICI 8, 2 slices
  x 4 over DCN, 8 slices x 8 = v5e-64), where the replicated plan goes
  INFEASIBLE once the tables exceed per-chip HBM.

Prints ONE JSON line (the BENCH_*.json convention); `measure()` is also
imported by bench.py when BENCH_SHARD=1.

Usage: python benchmarks/bench_shard.py [--steps N]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

# big enough that the table working set dwarfs caches and the sparse
# update dominates; small enough that N replicated copies fit host RAM
ROWS = int(os.environ.get("BENCH_SHARD_ROWS", "131072"))
TABLES = 8
DIM = 64


def _build(ndev, batch, mode, bag=1):
    import jax

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
    from dlrm_flexflow_tpu.parallel.mesh import make_mesh
    from dlrm_flexflow_tpu.parallel.pconfig import ParallelConfig

    dcfg = DLRMConfig(embedding_size=[ROWS] * TABLES,
                      sparse_feature_size=DIM,
                      embedding_bag_size=bag,
                      mlp_bot=[DIM, 128, DIM],
                      mlp_top=[DIM * (TABLES + 1), 128, 1])
    model = ff.FFModel(ff.FFConfig(batch_size=batch, seed=0))
    build_dlrm(model, dcfg)
    strat = {}
    row_kw = {
        "row_sharded": {},
        "dedup": {"exchange": "dedup"},
        "hybrid": {"exchange": "dedup", "hot_fraction": 1.0 / 64},
    }
    for op in model.ops:
        tn = type(op).__name__
        nd = op.outputs[0].num_dims if op.outputs else 0
        if tn == "EmbeddingBagStacked":
            if mode in row_kw:
                strat[op.name] = ParallelConfig((ndev, 1, 1),
                                                param_degree=ndev,
                                                **row_kw[mode])
            elif mode == "table_sharded":
                dt = next(d for d in range(min(ndev, TABLES), 0, -1)
                          if TABLES % d == 0 and ndev % d == 0)
                strat[op.name] = ParallelConfig((1, dt, 1))
            else:
                strat[op.name] = ParallelConfig.data_parallel(nd, ndev)
        elif nd:
            strat[op.name] = ParallelConfig.data_parallel(nd, ndev)
    model.compile(ff.SGDOptimizer(lr=0.05), "mean_squared_error",
                  ["mse"], mesh=make_mesh(devices=jax.devices()[:ndev]),
                  strategies=strat)
    model.init_layers()
    return model, dcfg


def _steps_per_s(model, batches, steps):
    model.train_batch_device(batches[0])          # warm/compile
    t0 = time.perf_counter()
    mets = None
    for s in range(steps):
        mets = model.train_batch_device(batches[s % len(batches)])
    float(mets["loss"])                           # true completion
    return steps / (time.perf_counter() - t0)


def _sim_pod_sweep(ndev):
    """Cost-model pricing of replicated vs row-sharded plans across pod
    topologies, with an HBM cap the replicated tables exceed."""
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
    from dlrm_flexflow_tpu.parallel.pconfig import ParallelConfig
    from dlrm_flexflow_tpu.search.cost_model import CostModel, TPUSpec
    from dlrm_flexflow_tpu.search.mcmc import default_strategy
    from dlrm_flexflow_tpu.search.simulator import Simulator

    dcfg = DLRMConfig.random_benchmark()          # 8 x 1M x 64 (2 GB)
    out = {}
    for label, topo, n in [
        ("ici8", [("ici", 8)], 8),
        ("dcn2xici4", [("dcn", 2), ("ici", 4)], 8),
        ("dcn8xici8_v5e64", [("dcn", 8), ("ici", 8)], 64),
    ]:
        model = ff.FFModel(ff.FFConfig(batch_size=256 * n))
        build_dlrm(model, dcfg)
        model.optimizer = ff.SGDOptimizer(lr=0.1)
        emb = next(op for op in model.ops
                   if type(op).__name__ == "EmbeddingBagStacked")
        dp = default_strategy(model, n)
        row = dict(dp)
        row[emb.name] = ParallelConfig((n, 1, 1), param_degree=n)
        # 1 GB "HBM": the 2 GB replicated tables cannot fit, the row
        # shards can — the memory-feasibility half of the row-shard case
        sim_cap = Simulator(model, CostModel(
            spec=TPUSpec(hbm_capacity_bytes=1e9)), topology=topo)
        sim = Simulator(model, CostModel(), topology=topo)
        t_dp, t_row = sim.simulate(dp, n), sim.simulate(row, n)
        out[label] = {
            "sim_step_ms_replicated": round(1e3 * t_dp, 4),
            "sim_step_ms_row_sharded": round(1e3 * t_row, 4),
            "row_vs_replicated_sim": round(t_dp / t_row, 3),
            "replicated_feasible_at_1gb_hbm":
                sim_cap.simulate(dp, n) != float("inf"),
            "row_sharded_feasible_at_1gb_hbm":
                sim_cap.simulate(row, n) != float("inf"),
        }
    return out


def _skew_sweep(ndev, steps):
    """Skew sweep (ISSUE 11): alpha in {0 (uniform), 0.8, 1.0, 1.2}
    comparing the dense vs dedup'd vs hybrid exchange on the CPU mesh —
    steps/s plus the MEASURED balanced exchange bytes, computed from
    the actual per-device DISTINCT id counts of the benchmark batches
    (the dedup'd exchange's valid traffic scales with these, not with
    batch size; the hybrid's cold stream excludes hot hits on top)."""
    import jax
    import numpy as np

    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    from dlrm_flexflow_tpu.parallel.alltoall import \
        exchange_bytes_per_step

    batch = 64 * ndev
    bag = 4           # multi-hot bags are where duplicates concentrate
    out = {}
    for alpha in (0.0, 0.8, 1.0, 1.2):
        entry = {}
        batches_np = []
        for i in range(4):
            x, y = synthetic_batch(
                _bench_dcfg(bag), batch, seed=i, zipf_alpha=alpha)
            x["label"] = y
            batches_np.append(x)
        for mode in ("row_sharded", "dedup", "hybrid"):
            model, dcfg = _build(ndev, batch, mode, bag=bag)
            emb = next(op for op in model.ops
                       if type(op).__name__ == "EmbeddingBagStacked")
            plan = emb._row_plan
            if mode == "dedup":
                # measured distinct cold ids per device per step
                per_dev = batch // ndev
                dcounts = []
                for x in batches_np:
                    flat = emb.flat_lookup_ids(x["sparse"]).reshape(
                        batch, -1)
                    for d in range(ndev):
                        dcounts.append(len(np.unique(
                            flat[d * per_dev:(d + 1) * per_dev])))
                entry["measured_distinct_per_dev"] = round(
                    float(np.mean(dcounts)), 1)
                entry["a2a_bytes_dedup"] = exchange_bytes_per_step(
                    plan, batch * TABLES * bag, DIM,
                    distinct_per_device=float(np.mean(dcounts)))
                entry["a2a_bytes_dense"] = exchange_bytes_per_step(
                    plan, batch * TABLES * bag, DIM)
            staged = [model._device_batch(dict(x)) for x in batches_np]
            jax.block_until_ready(staged)
            entry[f"steps_per_s_{mode}"] = round(
                _steps_per_s(model, staged, steps), 3)
            del model, staged
        out[f"alpha_{alpha:g}"] = entry
    return out


def _bench_dcfg(bag):
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig
    return DLRMConfig(embedding_size=[ROWS] * TABLES,
                      sparse_feature_size=DIM, embedding_bag_size=bag,
                      mlp_bot=[DIM, 128, DIM],
                      mlp_top=[DIM * (TABLES + 1), 128, 1])


def _sim_skew_dcn():
    """The ISSUE 11 perf bar: >= 2x simulated step time vs the dense
    exchange at zipf(1.0) on the DCN topology — a production-scale
    step (multi-hot bag 32, 2048 samples/device, fused supersteps)
    where the exchange + touched-rows scatter dominate, priced from an
    observed zipf(1.0) histogram."""
    import numpy as np

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.data.dataloader import zipf_indices
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
    from dlrm_flexflow_tpu.parallel.pconfig import ParallelConfig
    from dlrm_flexflow_tpu.search.cost_model import CostModel
    from dlrm_flexflow_tpu.search.mcmc import default_strategy
    from dlrm_flexflow_tpu.search.simulator import Simulator
    from dlrm_flexflow_tpu.utils.histogram import IdFrequencySketch

    n = 8
    dcfg = DLRMConfig(embedding_size=[1000000] * 8,
                      embedding_bag_size=32, sparse_feature_size=64,
                      mlp_bot=[64, 512, 512, 64],
                      mlp_top=[576, 1024, 1024, 1024, 1])
    model = ff.FFModel(ff.FFConfig(batch_size=2048 * n, superstep=8))
    build_dlrm(model, dcfg)
    model.optimizer = ff.SGDOptimizer(lr=0.1)
    emb = next(op for op in model.ops
               if type(op).__name__ == "EmbeddingBagStacked")
    rng = np.random.RandomState(0)
    sk = IdFrequencySketch(8 * 1000000)
    for t in range(8):
        sk.observe(zipf_indices(rng, 1000000, 400000, 1.0)
                   + t * 1000000)
    model.attach_id_histograms({emb.name: sk})
    dp = default_strategy(model, n)

    def plan(**kw):
        s = dict(dp)
        s[emb.name] = ParallelConfig((n, 1, 1), param_degree=n, **kw)
        return s

    sim = Simulator(model, CostModel(), topology=[("dcn", 8)])
    t_dense = sim.simulate(plan(), n)
    t_dedup = sim.simulate(plan(exchange="dedup"), n)
    t_hyb = sim.simulate(plan(exchange="dedup", hot_fraction=1 / 64), n)
    return {
        "sim_step_ms_dense": round(1e3 * t_dense, 3),
        "sim_step_ms_dedup": round(1e3 * t_dedup, 3),
        "sim_step_ms_hybrid": round(1e3 * t_hyb, 3),
        "dedup_vs_dense_sim": round(t_dense / t_dedup, 3),
        "hybrid_vs_dense_sim": round(t_dense / t_hyb, 3),
    }


def _sim_overlap_dcn():
    """The ISSUE 19 perf bar: >= 1.5x simulated step time from the
    pipelined exchange on the DCN topology — a multi-hot production
    shape (4 x 1M x 384-d tables, bag 64, 2048 samples/device) where
    the row-shard all-to-all dwarfs the dense window, so decomposing it
    into ppermute rounds that ride under the gather/scatter is the
    whole step. Also runs a short MCMC walk from scratch to show the
    search picks the pipelined plan unforced."""
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
    from dlrm_flexflow_tpu.parallel.pconfig import ParallelConfig
    from dlrm_flexflow_tpu.search.cost_model import CostModel
    from dlrm_flexflow_tpu.search.mcmc import default_strategy, optimize
    from dlrm_flexflow_tpu.search.simulator import Simulator

    n, T, d = 8, 4, 384
    dcfg = DLRMConfig(embedding_size=[1000000] * T,
                      embedding_bag_size=64, sparse_feature_size=d,
                      mlp_bot=[64, 512, 512, d],
                      mlp_top=[d * (T + 1), 512, 512, 1])
    model = ff.FFModel(ff.FFConfig(batch_size=2048 * n))
    build_dlrm(model, dcfg)
    model.optimizer = ff.SGDOptimizer(lr=0.1)
    emb = next(op for op in model.ops
               if type(op).__name__ == "EmbeddingBagStacked")
    dp = default_strategy(model, n)
    sim = Simulator(model, CostModel(), topology=[("dcn", 8)])

    def t(**kw):
        s = dict(dp)
        s[emb.name] = ParallelConfig((n, 1, 1), param_degree=n, **kw)
        return sim.simulate(s, n)

    t_ser, t_ovl = t(), t(overlap=True)
    best = optimize(model, budget=400, ndev=n, seed=3,
                    topology=[("dcn", 8)])
    best_pc = best[emb.name]
    return {
        "sim_step_ms_serial": round(1e3 * t_ser, 3),
        "sim_step_ms_overlap": round(1e3 * t_ovl, 3),
        "overlap_vs_serial_sim": round(t_ser / t_ovl, 3),
        "mcmc_picked_overlap":
            bool(getattr(best_pc, "overlap", False))
            and getattr(best_pc, "param_degree", 1) > 1,
        "sim_step_ms_mcmc_best": round(1e3 * sim.simulate(best, n), 3),
    }


def measure(steps: int = 12):
    import jax

    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    from dlrm_flexflow_tpu.parallel.alltoall import \
        exchange_bytes_per_step

    ndev = len(jax.devices())
    batch = 64 * ndev
    out = {"ndev": ndev, "rows": ROWS, "tables": TABLES, "dim": DIM,
           "batch": batch}

    modes = ["replicated", "row_sharded"]
    if ndev > 1 and TABLES % 2 == 0:
        modes.append("table_sharded")
    dcfg = None
    for mode in modes:
        model, dcfg = _build(ndev, batch, mode)
        if mode == "row_sharded":
            emb = next(op for op in model.ops
                       if type(op).__name__ == "EmbeddingBagStacked")
            plan = getattr(emb, "_row_plan", None)
            out["row_plan_active"] = plan is not None
            if plan is not None:
                lookups = batch * TABLES * dcfg.embedding_bag_size
                out["a2a_bytes_per_step"] = exchange_bytes_per_step(
                    plan, lookups, DIM)
        batches = []
        for i in range(4):
            x, y = synthetic_batch(dcfg, batch, seed=i)
            x["label"] = y
            batches.append(model._device_batch(x))
        jax.block_until_ready(batches)
        out[f"steps_per_s_{mode}"] = round(
            _steps_per_s(model, batches, steps), 3)
        del model, batches

    if "steps_per_s_row_sharded" in out and \
            out.get("steps_per_s_replicated"):
        out["row_vs_replicated"] = round(
            out["steps_per_s_row_sharded"]
            / out["steps_per_s_replicated"], 3)

    # quantized-storage exchange payload (ISSUE 14): the row-sharded
    # all-to-all's ROW payload under the int8 policy vs fp32 — ids
    # route unchanged, rows ship as codes + one fp32 scale each
    if dcfg is not None:
        from dlrm_flexflow_tpu.quant.policy import QuantPolicy
        lookups_dev = batch * TABLES * dcfg.embedding_bag_size / ndev
        fp32_rows = lookups_dev * DIM * 4.0
        int8_rows = lookups_dev * QuantPolicy("int8").row_bytes(DIM)
        out["quant_exchange"] = {
            "rows_payload_fp32_kb": round(fp32_rows / 1e3, 1),
            "rows_payload_int8_kb": round(int8_rows / 1e3, 1),
            "ratio": round(fp32_rows / int8_rows, 2),
        }

    out["sim_pod_sweep"] = _sim_pod_sweep(ndev)
    out["skew_sweep"] = _skew_sweep(ndev, steps)
    out["sim_skew_dcn"] = _sim_skew_dcn()
    out["sim_overlap_dcn"] = _sim_overlap_dcn()
    return out


def main(argv):
    steps = 12
    if "--steps" in argv:
        steps = int(argv[argv.index("--steps") + 1])
    print(json.dumps({"metric": "embedding_sharding", **measure(steps)}))
    return 0


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    sys.exit(main(sys.argv[1:]))
