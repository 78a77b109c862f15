#!/usr/bin/env python
"""MEASURED-MODE SOAP search for the DLRM configs.

The reference's whole point is measured-search-found strategies: the
simulator times real kernels on the device and MCMC searches against
those timings (reference: src/runtime/simulator.cc:235-273 feeding
FFModel::optimize, model.cc:1093-1144). This script closes the same loop
on the real chip for the two tracked DLRM configs:

- kaggle   : run_criteo_kaggle.sh shape (26 tables 4..3.1M rows x 16-d),
             8-device target.
- terabyte : Criteo-TB shape (26 tables, the large ones tens of millions
             of rows, x 64-d — run_summit_large.sh territory), 64-device
             target on the 8-slice x 8 hybrid DCN+ICI topology, searched
             under the activation-aware capacity model (pure DP cannot
             fit: replicated tables are ~24 GB/chip).

With --measure (run ON the TPU) per-op costs come from timing each op's
compiled subgraph at its candidate shard shape (CostModel measure=True,
the r5-fixed path that rotates lookup indices per iteration); without it
the calibrated roofline prices ops. Exports the winner as a
reference-format .pb and prints one JSON line with the simulated
DP-vs-searched comparison.

  python benchmarks/search_dlrm.py --config kaggle --measure
  python benchmarks/search_dlrm.py --config terabyte --measure
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# public Criteo-Kaggle cardinalities (run_criteo_kaggle.sh)
KAGGLE_TABLES = [1396, 550, 2700000, 2160000, 301, 22, 11878, 619, 3,
                 64889, 5236, 2567820, 3136, 26, 12607, 471917, 11, 4970,
                 2159, 4, 2586596, 7043, 61, 4, 930, 14]
# public Criteo-Terabyte cardinalities (mlperf DLRM counts)
TB_TABLES = [39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63,
             38532951, 2953546, 403346, 10, 2208, 11938, 155, 4, 976, 14,
             39979771, 25641295, 39664984, 585935, 12972, 108, 36]


def build_config(name, batch):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm

    if name == "kaggle":
        dcfg = DLRMConfig(embedding_size=KAGGLE_TABLES,
                          sparse_feature_size=16,
                          mlp_bot=[13, 512, 256, 64, 16],
                          mlp_top=[432, 512, 256, 1])
    elif name == "terabyte":
        dcfg = DLRMConfig(embedding_size=TB_TABLES,
                          sparse_feature_size=64,
                          mlp_bot=[13, 512, 256, 64],
                          mlp_top=[64 * 27, 512, 512, 256, 1])
    else:
        raise ValueError(name)
    model = ff.FFModel(ff.FFConfig(batch_size=batch,
                                   compute_dtype="bfloat16"))
    build_dlrm(model, dcfg)
    return model


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=["kaggle", "terabyte"],
                    default="kaggle")
    ap.add_argument("--budget", type=int, default=600)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--measure", action="store_true",
                    help="measured-mode per-op costs on the attached "
                         "chip (reference simulator.cc:235-273); default "
                         "is the calibrated roofline")
    args = ap.parse_args(argv)

    if not args.measure:
        from dlrm_flexflow_tpu.utils.testing import ensure_cpu_devices
        ensure_cpu_devices(8)

    from dlrm_flexflow_tpu.search.cost_model import CostModel
    from dlrm_flexflow_tpu.search.mcmc import default_strategy, optimize
    from dlrm_flexflow_tpu.search.simulator import Simulator
    from dlrm_flexflow_tpu.parallel.strategy_io import save_strategies_pb

    if args.config == "kaggle":
        # flat single-slice ICI (DP sync cheap — an honest search may
        # confirm DP) AND a 2-host slice pair whose DP all-reduce rides
        # DCN (the reference's searched-beats-DP territory: weak
        # inter-node links, README.md:64-68)
        ndev = 8
        topos = [("ici_flat", None),
                 ("dcn_2host", [("dcn", 2), ("ici", 4)])]
    else:
        ndev = 64
        topos = [("dcn8x8", [("dcn", 8), ("ici", 8)])]
    batch = 256 * ndev

    model = build_config(args.config, batch)
    cm = CostModel(measure=args.measure,
                   compute_dtype=model.config.jnp_compute_dtype)
    mode = "measured" if args.measure else "roofline"
    dp = default_strategy(model, ndev)
    results = []
    for topo_label, topo in topos:
        sim = Simulator(model, cost_model=cm, topology=topo)
        t_dp = sim.simulate(dp, ndev)
        found = optimize(model, budget=args.budget, alpha=1.2, ndev=ndev,
                         cost_model=cm, seed=args.seed, start=dp,
                         topology=topo, verbose=True)
        t_found = sim.simulate(found, ndev)
        path = os.path.join(
            REPO, "strategies",
            f"dlrm_{args.config}_{ndev}dev_{topo_label}_{mode}.pb")
        save_strategies_pb(path, found)
        emb_pcs = {k: str(pc) for k, pc in sorted(found.items())
                   if "emb" in k or "table" in k}
        results.append({
            "topology": topo_label,
            "sim_dp_ms": (None if t_dp == float("inf")
                          else round(t_dp * 1e3, 3)),
            "dp_feasible": t_dp != float("inf"),
            # None (never Infinity — nonstandard JSON) when the budget
            # found no capacity-feasible strategy
            "search_feasible": t_found != float("inf"),
            "sim_searched_ms": (None if t_found == float("inf")
                                else round(t_found * 1e3, 3)),
            "speedup_vs_dp": (
                None if t_dp == float("inf") or t_found == float("inf")
                else round(t_dp / t_found, 4)),
            "ops_changed_from_dp": sum(
                1 for k, pc in found.items()
                if pc.degrees != dp[k].degrees
                or pc.memory_types != dp[k].memory_types),
            "embedding_placements": emb_pcs,
            "strategy_file": os.path.relpath(path, REPO),
        })
    print(json.dumps({
        "metric": f"dlrm_{args.config}_searched_vs_dp_simulated",
        "mode": mode,
        "ndev": ndev,
        "budget": args.budget,
        "results": results,
    }))


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    main()
