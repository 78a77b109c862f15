#!/usr/bin/env python
"""Elastic-recovery smoke benchmark: what does surviving device loss cost?

Measures, on a small DLRM (CPU or attached accelerator):

- ``detect_ms`` — collective-watchdog detection latency: wall time from a
  stalled mesh probe to the typed ``MeshDegraded``, with a 0.2s deadline
  (the number should sit just above the configured deadline — detection
  is deadline-bound, not stall-bound);
- ``replan_ms`` — strategy re-search time for a half-fleet shrink
  (MCMC constrained to the survivors, seeded from the clamped old plan)
  and ``replan_greedy_ms`` for the zero-budget greedy clamp;
- ``reshard_ms`` — full in-place recovery: gather state to host,
  recompile on the shrunken mesh, re-split params/opt state;
- ``steps_per_s_before`` / ``steps_per_s_after`` — steady-state training
  rate on the full mesh vs the shrunken one (the capacity actually lost,
  as opposed to the whole job, which is what a non-elastic run loses);
- ``expand_*`` — scale-UP: detect (consume the return signal) → replan →
  reshard → FIRST post-expansion step, the end-to-end time from capacity
  coming back to the grown mesh training on it;
- ``warm_vs_cold`` — the persistent-cache story (ISSUE 12): the same
  recover-and-first-step cycle with an empty warm cache (cold: MCMC
  search + XLA compile) vs a populated one (warm: plan-cache hit + AOT
  executable deserialize), plus a corrupt-cache run proving the
  degradation path re-compiles instead of failing. The acceptance bar is
  warm recovery dropping from seconds to milliseconds
  (``warm_speedup`` >> 1, warm total in single-digit ms territory on
  this tiny model; real models amortize far more compile time).

Prints ONE JSON line (the BENCH_*.json convention); `measure()` is also
imported by bench.py when BENCH_ELASTIC=1 so recovery-cost regressions
show up next to the headline throughput.

Usage: python benchmarks/bench_elastic.py [--steps N]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _build(ndev, batch, **cfg_kw):
    import jax

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                               dlrm_strategy)
    from dlrm_flexflow_tpu.parallel.mesh import make_mesh

    dcfg = DLRMConfig(embedding_size=[1024] * 8, sparse_feature_size=16,
                      mlp_bot=[13, 64, 16], mlp_top=[144, 64, 1])
    model = ff.FFModel(ff.FFConfig(batch_size=batch, seed=0, **cfg_kw))
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"],
                  mesh=make_mesh(devices=jax.devices()[:ndev]),
                  strategies=dlrm_strategy(model, dcfg, ndev))
    model.init_layers()
    return model, dcfg


def _steps_per_s(model, batches, steps):
    model.train_batch_device(batches[0])         # warm/compile
    t0 = time.perf_counter()
    mets = None
    for s in range(steps):
        mets = model.train_batch_device(batches[s % len(batches)])
    float(mets["loss"])                          # true completion
    return steps / (time.perf_counter() - t0)


def measure(steps=30, batch=128, search_budget=50):
    import jax

    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    from dlrm_flexflow_tpu.parallel.distributed import (MeshDegraded,
                                                        probe_mesh)
    from dlrm_flexflow_tpu.parallel.elastic import recover
    from dlrm_flexflow_tpu.parallel.mesh import make_mesh
    from dlrm_flexflow_tpu.search.replan import replan_strategies
    from dlrm_flexflow_tpu.utils import faults

    ndev = len(jax.devices())
    half = max(ndev // 2, 1)

    def staged(model, dcfg, n=4):
        out = []
        for i in range(n):
            x, y = synthetic_batch(dcfg, batch, seed=i)
            x["label"] = y
            out.append(model._device_batch(x))
        return out

    # --- detection latency (collective-deadline watchdog) --------------
    mesh = make_mesh(devices=jax.devices()[:half])
    probe_mesh(mesh, deadline_s=30.0)   # warm the probe jit
    deadline = 0.2
    with faults.active_plan(faults.FaultPlan(stall_s={"collective": 60.0})):
        t0 = time.perf_counter()
        try:
            probe_mesh(mesh, deadline_s=deadline)
            raise RuntimeError("stalled probe did not trip the watchdog")
        except MeshDegraded:
            detect_ms = 1e3 * (time.perf_counter() - t0)

    # --- re-search time ------------------------------------------------
    model, dcfg = _build(ndev, batch, elastic="inplace",
                         elastic_search_budget=search_budget)
    t0 = time.perf_counter()
    _, info = replan_strategies(model, half, budget=search_budget)
    replan_ms = 1e3 * (time.perf_counter() - t0)
    t0 = time.perf_counter()
    replan_strategies(model, half, budget=0)
    replan_greedy_ms = 1e3 * (time.perf_counter() - t0)

    # --- steps/s before, reshard, steps/s after ------------------------
    before = _steps_per_s(model, staged(model, dcfg), steps)
    devs = list(model.mesh.devices.flat)
    t0 = time.perf_counter()
    report = recover(model, lost=devs[half:], mode="inplace",
                     budget=search_budget)
    reshard_ms = 1e3 * report.reshard_s
    recover_total_ms = 1e3 * (time.perf_counter() - t0)
    after = _steps_per_s(model, staged(model, dcfg), steps)

    # --- scale-UP: detect -> replan -> reshard -> first step -----------
    from dlrm_flexflow_tpu.parallel.elastic import expand
    from dlrm_flexflow_tpu.parallel.distributed import MeshReturned
    model.config.elastic_expand = True
    returned = [d for d in jax.devices()
                if d.id not in {dd.id for dd in model.mesh.devices.flat}]
    b0 = staged(model, dcfg, n=1)[0]
    with faults.active_plan(faults.FaultPlan(
            return_device_steps={int(model._step): len(returned)})):
        t0 = time.perf_counter()
        try:
            model.train_batch_device(b0)          # detection point
            raise RuntimeError("return-device fault did not fire")
        except MeshReturned as exc:
            detect_expand_ms = 1e3 * (time.perf_counter() - t0)
            erep = expand(model, returned=exc.returned, mode="inplace",
                          budget=search_budget)
    t0 = time.perf_counter()
    b1 = staged(model, dcfg, n=1)[0]              # restage on new mesh
    float(model.train_batch_device(b1)["loss"])   # first grown step
    expand_first_step_ms = 1e3 * (time.perf_counter() - t0)

    # --- warm vs cold recovery (persistent plan + compile caches) ------
    import shutil
    import tempfile

    def _recover_cycle(cache_dir, corrupt=False):
        m, dc = _build(ndev, batch, elastic="inplace",
                       elastic_search_budget=search_budget)
        if cache_dir:
            m.attach_plan_cache(cache_dir)
            m.attach_compile_cache(cache_dir)
        bts = staged(m, dc, n=1)
        float(m.train_batch_device(bts[0])["loss"])   # pre-shrink warm
        plan = (faults.FaultPlan(corrupt_cache_entries=10 ** 6)
                if corrupt else faults.FaultPlan())
        with faults.active_plan(plan):
            t0 = time.perf_counter()
            rep = recover(m, lost=list(m.mesh.devices.flat)[half:],
                          mode="inplace", budget=search_budget)
            bt = staged(m, dc, n=1)[0]
            float(m.train_batch_device(bt)["loss"])   # first step
            total_ms = 1e3 * (time.perf_counter() - t0)
        return total_ms, rep

    cache_dir = tempfile.mkdtemp(prefix="ff-warmcache-")
    try:
        cold_ms, cold_rep = _recover_cycle(cache_dir)      # fills cache
        warm_ms, warm_rep = _recover_cycle(cache_dir)      # hits cache
        corrupt_ms, corrupt_rep = _recover_cycle(cache_dir,
                                                 corrupt=True)
        nocache_ms, _ = _recover_cycle(None)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)

    return {
        "devices": ndev,
        "devices_after": report.surviving,
        "detect_ms": round(detect_ms, 2),
        "detect_deadline_ms": round(1e3 * deadline, 2),
        "replan_ms": round(replan_ms, 2),
        "replan_greedy_ms": round(replan_greedy_ms, 2),
        "replan_searched": bool(info.get("searched")),
        "reshard_ms": round(reshard_ms, 2),
        "recover_total_ms": round(recover_total_ms, 2),
        "steps_per_s_before": round(before, 2),
        "steps_per_s_after": round(after, 2),
        "shrink_throughput_ratio": round(after / before, 4)
        if before > 0 else None,
        # scale-UP: capacity back -> grown mesh training on it
        "expand_detect_ms": round(detect_expand_ms, 2),
        "expand_replan_ms": round(1e3 * erep.replan_s, 2),
        "expand_reshard_ms": round(1e3 * erep.reshard_s, 2),
        "expand_first_step_ms": round(expand_first_step_ms, 2),
        "expand_devices": erep.surviving,
        # warm vs cold recovery (recover + first post-reshard step)
        "warm_vs_cold": {
            "no_cache_ms": round(nocache_ms, 2),
            "cold_ms": round(cold_ms, 2),
            "warm_ms": round(warm_ms, 2),
            "warm_speedup": round(nocache_ms / warm_ms, 2)
            if warm_ms > 0 else None,
            "warm_plan_cache_hit": bool(warm_rep.plan_cache_hit),
            "cold_plan_cache_hit": bool(cold_rep.plan_cache_hit),
            # corrupt entries must degrade to a fresh compile (cold
            # speed, zero failures), never to a dead recovery
            "corrupt_cache_ms": round(corrupt_ms, 2),
            "corrupt_degraded_ok": bool(
                not corrupt_rep.plan_cache_hit
                and corrupt_rep.surviving == half),
        },
    }


def main():
    steps = 30
    if "--steps" in sys.argv:
        steps = int(sys.argv[sys.argv.index("--steps") + 1])
    out = {"metric": "elastic_smoke", "unit": "ms / steps_per_s"}
    out.update(measure(steps=steps))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    sys.exit(main())
