#!/usr/bin/env python
"""SOAP-search a hybrid strategy for InceptionV3 (BASELINE.md tracked
config 3: "InceptionV3 with SOAP-searched hybrid strategy").

Runs MCMC (`optimize`, the reference FFModel::optimize algorithm,
model.cc:1093-1144) over an 8-device target offline (structural mesh
factorization — no 8 chips needed, unlike the reference which searches
on the target cluster, simulator.cu:79-109), exports the best strategy
as a reference-format .pb, and reports the simulated speedup vs pure
data parallelism.

  python benchmarks/search_inception.py [--budget 400] [--ndev 8]

Writes strategies/inception_v3_{ndev}dev_{topology}.pb; the multichip dryrun
(__graft_entry__.dryrun_multichip) loads and EXECUTES this file as its
fourth config, closing the search -> export -> load -> train loop.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(batch):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.inception import build_inception_v3
    model = ff.FFModel(ff.FFConfig(batch_size=batch,
                                   compute_dtype="bfloat16"))
    build_inception_v3(model, num_classes=1000)
    return model


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=400)
    ap.add_argument("--ndev", type=int, default=8)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--on-tpu", action="store_true",
                    help="search against the attached accelerator instead "
                         "of a virtual CPU mesh (offline targeting is the "
                         "default: the roofline models the TPU regardless "
                         "of where the search runs)")
    args = ap.parse_args(argv)

    if not args.on_tpu:
        # must run before any jax computation
        from dlrm_flexflow_tpu.utils.testing import ensure_cpu_devices
        ensure_cpu_devices(min(args.ndev, 8))

    from dlrm_flexflow_tpu.parallel.mesh import make_mesh
    from dlrm_flexflow_tpu.parallel.strategy_io import save_strategies_pb
    from dlrm_flexflow_tpu.search.mcmc import default_strategy, optimize
    from dlrm_flexflow_tpu.search.simulator import Simulator

    model = build(args.batch * args.ndev)
    model.mesh = make_mesh(num_devices=min(args.ndev,
                                           _n_local_devices()))
    dp = default_strategy(model, args.ndev)
    results = []
    out = None
    # two targets: a flat single-slice ICI mesh (DP sync is cheap there —
    # an honest search may confirm DP) and a 2-host slice pair whose DP
    # all-reduce rides DCN (the reference's searched-beats-DP territory:
    # its clusters had weak inter-node links, README.md:64-68)
    for label, topo in (("ici_flat", None),
                        ("dcn_2host", [("dcn", 2),
                                       ("ici", args.ndev // 2)])):
        sim = Simulator(model, topology=topo)
        t_dp = sim.simulate(dp, args.ndev)
        found = optimize(model, budget=args.budget, alpha=1.2,
                         ndev=args.ndev, seed=args.seed, start=dp,
                         topology=topo)
        t_found = sim.simulate(found, args.ndev)
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "strategies",
            f"inception_v3_{args.ndev}dev_{label}.pb")
        save_strategies_pb(path, found)
        out = path
        results.append({
            "topology": label,
            "sim_dp_ms": round(t_dp * 1e3, 3),
            "sim_searched_ms": round(t_found * 1e3, 3),
            "speedup_vs_dp": round(t_dp / t_found, 4),
            "ops_changed_from_dp": sum(
                1 for k, pc in found.items()
                if pc.degrees != dp[k].degrees),
            "strategy_file": os.path.relpath(path),
        })
    print(json.dumps({
        "metric": "inception_v3_searched_vs_dp_simulated",
        "ndev": args.ndev,
        "budget": args.budget,
        "results": results,
    }))
    return out


def _n_local_devices():
    import jax
    return len(jax.devices())


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    main()
