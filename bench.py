#!/usr/bin/env python
"""Headline benchmark: DLRM random-data training throughput, samples/s/chip.

Mirrors the reference benchmark config (reference:
examples/cpp/DLRM/run_random.sh:1-10 — batch 256/device, 8 embedding tables
× 1M rows × 64-d, bot MLP 64-512-512-64, top MLP 576-1024-1024-1024-1) and
its throughput report (dlrm.cc:197-198: THROUGHPUT = samples*epochs/elapsed).

Runs on a TPU only: any other platform is refused with the one JSON error
line and a non-zero exit (a CPU run must never print a number under a
device metric's name). Prints ONE JSON line:
{"metric", "value", "unit", "device", ...}.
"""

import json
import os
import sys
import time
import traceback

METRIC = "dlrm_random_train_throughput_per_chip"


def _emit_error(message):
    """Print ONE machine-readable JSON line when the run cannot measure
    the chip, so the driver can tell an unusable machine apart from a
    perf regression. The caller exits non-zero."""
    print(json.dumps({
        "metric": METRIC,
        "value": None,
        "unit": "samples/s/chip",
        "error": message,
    }))
    return 1


def _first_line(exc):
    return next((l.strip()[:200] for l in str(exc).splitlines()
                 if l.strip()), type(exc).__name__)


def _chip_health(jax, size=2048, iters0=100):
    """Measure the chip itself: in-jit bf16 matmul TFLOP/s + the wall time
    of one tiny dispatch and readback. Reported alongside the throughput
    so a reader can tell a slow machine from slow code. Each timing ends
    in a device->host readback of a scalar, which waits for the device.
    Any failure propagates: a chip that cannot run the probe fails the
    run."""
    import jax.numpy as jnp
    from jax import lax

    a = jnp.ones((size, size), jnp.bfloat16)

    tiny = jax.jit(lambda x: x + 1)
    float(tiny(jnp.float32(0.0)))
    rts = []
    for _ in range(5):
        t0 = time.time()
        float(tiny(jnp.float32(0.0)))
        rts.append(time.time() - t0)
    rt = min(rts)
    jitter = max(rts) - rt

    # the matmul window includes one dispatch; subtract it. When the
    # compute is buried under dispatch jitter, LENGTHEN the in-jit loop
    # until it dominates instead of giving up — one extra compile per
    # retry, bounded
    iters = iters0
    for _attempt in range(4):
        # return a scalar, not the 8 MB product
        mm = jax.jit(lambda a, n=iters: lax.fori_loop(
            0, n, lambda i, x: x @ a, a)[0, 0].astype(jnp.float32))
        float(mm(a))  # warm/compile + true wait
        mms = []
        for _ in range(5):
            t0 = time.time()
            float(mm(a))
            mms.append(time.time() - t0)
        compute_s = min(mms) - rt
        if compute_s >= max(2 * jitter, 1e-3):
            tflops = iters * 2 * size ** 3 / compute_s / 1e12
            return round(tflops, 1), round(rt * 1e3, 1)
        iters *= 8
    return None, round(rt * 1e3, 1)


def _env_int(name, default):
    return int(os.environ.get(name, default))


def _env_float(name, default):
    return float(os.environ.get(name, default))


# Opt-in sections, kept out of the default run so the headline metric's
# conditions stay comparable: BENCH_<KEY>=1 runs benchmarks/<module>.py's
# measure(**kwargs()) and stores the result under the lower-cased key.
_SECTIONS = [
    # save/restore latency, sentinel overhead, rollback recovery
    ("RESILIENCE", "bench_resilience", lambda: {"steps": 20}),
    # staged vs streamed vs prefetched steps/s + staging overlap fraction
    # + host-table double-buffering speedup
    ("PIPELINE", "bench_pipeline", lambda: {"steps": 30}),
    # detection latency, re-search time, reshard time, steps/s before vs
    # after a half-fleet shrink
    ("ELASTIC", "bench_elastic", lambda: {"steps": 20}),
    # ms/step for K ∈ {1,2,4,8,16} on the floor-sensitive DLRM configs
    # plus the dispatch floor (the K→∞ intercept)
    ("SUPERSTEP", "bench_superstep", lambda: {
        "steps": _env_int("BENCH_SUPERSTEP_STEPS", 48)}),
    # offline vs online throughput, p99 across bucket/deadline settings,
    # embedding cache on/off
    ("SERVE", "bench_serve", lambda: {
        "requests": _env_int("BENCH_SERVE_REQUESTS", 256)}),
    # row-sharded all-to-all lookups vs replicated vs table-sharded
    # steps/s, a2a bytes/step, and the simulated pod-topology sweep
    ("SHARD", "bench_shard", lambda: {
        "steps": _env_int("BENCH_SHARD_STEPS", 12)}),
    # row-shard all-to-all overlap on/off step time, the trace-span-
    # derived exposed-comm fraction, and the simulated DCN-topology bar
    ("OVERLAP", "bench_overlap", lambda: {
        "steps": _env_int("BENCH_OVERLAP_STEPS", 8)}),
    # predicted-vs-lowered collective-bytes drift for the bench_shard
    # row-sharded and replicated plans (shardcheck FLX51x)
    ("AUDIT", "bench_audit", lambda: {
        "tolerance": _env_float("BENCH_AUDIT_TOLERANCE", 0.25)}),
    # attained QPS at a p99 SLO for 1/2/4 replicas under open-loop
    # Poisson load, zero failed requests with one replica killed mid-run,
    # continuous vs flush-cycle batching throughput
    ("SERVE_FLEET", "bench_serve_fleet", lambda: {
        "requests": _env_int("BENCH_SERVE_FLEET_REQUESTS", 256),
        "slo_ms": _env_float("BENCH_SERVE_FLEET_SLO_MS", 50)}),
    # train-step → servable p50/p99 for delta-chain publication vs
    # full-checkpoint reloads on a tables-dominated DLRM
    ("FRESHNESS", "bench_freshness", lambda: {
        "publishes": _env_int("BENCH_FRESHNESS_PUBLISHES", 12)}),
    # footprint / exchange / delta-publish / cache byte ratios under the
    # int8 row policy, plus the AUC cost on a kaggle-shaped model
    ("QUANT", "bench_quant", lambda: {
        "auc_epochs": _env_int("BENCH_QUANT_EPOCHS", 2)}),
    # train steps/s and serve p99 with --obs off vs on (bar: <= 2% on
    # both) plus the trace-export size/latency for a 200-step run
    ("OBS", "bench_obs", lambda: {
        "steps": _env_int("BENCH_OBS_STEPS", 200)}),
    # the compressed drifting-zipf replay — feedback-spool training,
    # delta publication, live hot/cold re-placement — AUC / p99 / fleet
    # size / freshness lag and whether every budget held with chaos active
    ("SCENARIO", "bench_scenario", lambda: {
        "steps": _env_int("BENCH_SCENARIO_STEPS", 48)}),
    # recall@100 of the int8 sharded MIPS top-k vs the fp32 exact scan
    # (bar: >= 0.95), per-shard scoring throughput for 1/2/4 shards, and
    # cascade QPS at a p99 SLO with a one-shard-dead chaos phase
    ("RETRIEVE", "bench_retrieve", lambda: {
        "requests": _env_int("BENCH_RETRIEVE_REQUESTS", 128),
        "slo_ms": _env_float("BENCH_RETRIEVE_SLO_MS", 150)}),
]


def _run_sections():
    """{key: result} for every opted-in section. A section that raises
    is recorded as {"error": ...} (traceback on stderr) so the others
    still report; main() turns any such entry into a non-zero exit."""
    import importlib
    out = {}
    bench_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "benchmarks")
    for key, module, kwargs in _SECTIONS:
        if not os.environ.get("BENCH_" + key):
            continue
        if bench_dir not in sys.path:
            sys.path.insert(0, bench_dir)
        try:
            out[key.lower()] = importlib.import_module(module).measure(
                **kwargs())
        except Exception as exc:  # noqa: BLE001 — report, then fail the run
            traceback.print_exc()
            out[key.lower()] = {"error": str(exc)[:200]}
    return out


def main():
    import jax

    import dlrm_flexflow_tpu as ff

    ff.use_compile_cache()
    try:
        devs = jax.devices()
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
        if device["platform"] != "tpu":
            return _emit_error(
                "platform %r (device_kind %r, %d device(s)) is not a TPU: "
                "bench.py measures the chip and has no CPU fallback"
                % (device["platform"], device["kind"], device["count"]))
        return _run(jax, ff, device)
    except (RuntimeError, OSError) as exc:
        # backend-init failure or a device lost mid-run — either way the
        # output must stay one parseable JSON line
        return _emit_error("tpu backend unavailable: " + _first_line(exc))


def _run(jax, ff, device):
    from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                               dlrm_strategy, synthetic_batch)
    ndev = device["count"]
    tflops, roundtrip_ms = _chip_health(jax)
    batch_per_chip = 256
    batch = batch_per_chip * ndev
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="bfloat16")
    dcfg = DLRMConfig.random_benchmark()

    model = ff.FFModel(cfg)
    build_dlrm(model, dcfg)
    strat = dlrm_strategy(model, dcfg, ndev)
    model.compile(ff.SGDOptimizer(lr=0.01), "mean_squared_error",
                  ["mse"], strategies=strat)
    model.init_layers()

    # stage batches on device once, then train from device-resident data —
    # the analog of the reference's design, which loads the ENTIRE dataset
    # into zero-copy memory up front and feeds each step with a
    # device-side scatter (load_entire_dataset + next_batch,
    # dlrm.cc:384-589); per-step host→device copies are not part of its
    # steady-state loop either
    nbatch = 8
    batches = []
    for i in range(nbatch):
        x, y = synthetic_batch(dcfg, batch, seed=i)
        x["label"] = y
        batches.append(model._device_batch(x))
    jax.block_until_ready(batches)

    # warmup/compile
    model.train_batch_device(batches[0])
    jax.block_until_ready(model.params)

    # measure several windows and report the best one: the jitted step is
    # short, and interference only ever slows a window down
    steps = max(1, int(os.environ.get("BENCH_STEPS", "500")))
    windows = int(os.environ.get("BENCH_WINDOWS", "5"))
    best = 0.0
    for _w in range(windows):
        t0 = time.time()
        mets = None
        for s in range(steps):
            mets = model.train_batch_device(batches[s % nbatch])
        # the loss readback waits for the whole window
        float(mets["loss"])
        elapsed = time.time() - t0
        best = max(best, steps * batch / elapsed)

    out = {
        "metric": METRIC,
        "value": round(best / ndev, 2),
        "unit": "samples/s/chip",
        "device": device,
        # chip condition at measurement time. v5e's published bf16 peak
        # is 197 TFLOP/s (393 is the int8 figure); the probe is one
        # 2048² matmul chain, not a tuned peak measurement
        "chip_bf16_tflops": tflops,
        "chip_roundtrip_ms": roundtrip_ms,
    }
    out.update(_run_sections())
    return _finish(out)


def _finish(out):
    """Print the result line; non-zero when the chip probe gave no number
    or an opted-in section failed."""
    print(json.dumps(out))
    failed = [k for k, v in out.items()
              if isinstance(v, dict) and "error" in v]
    if out["chip_bf16_tflops"] is None:
        failed.append("chip_bf16_tflops")
    if failed:
        print("bench.py: failed: " + ", ".join(failed), file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
