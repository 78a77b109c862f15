#!/usr/bin/env python
"""Input-pipeline benchmark: what does staging cost, and how much of it
does the prefetch ring hide?

Measures, on a small DLRM (CPU or attached accelerator):

- ``steps_per_s_staged`` — everything pre-staged on device (the
  all-in-HBM fast path fit() uses when the dataset fits);
- ``steps_per_s_streamed`` — slice + ``device_put`` synchronously inside
  the hot loop (the old streaming fallback);
- ``steps_per_s_prefetched`` — the same staging work done by the
  data/prefetch.py ring (depth = FFConfig.prefetch_depth) while the
  device trains, plus ``overlap_fraction`` = share of staging time the
  ring hid under compute. The acceptance bar: prefetched within 10% of
  pre-staged (``prefetched_vs_staged`` >= 0.9);
- ``steps_per_s_host_sync`` / ``steps_per_s_host_async`` — host-resident
  tables with exact-ordered inline gather/scatter vs the double-buffered
  worker (scatter + chained next-step gather overlapping device
  compute); ``host_async_speedup`` is their ratio.

Prints ONE JSON line (the BENCH_*.json convention); `measure()` is also
imported by bench.py when BENCH_PIPELINE=1 so input-pipeline regressions
show up next to the headline throughput.

Usage: python benchmarks/bench_pipeline.py [--steps N]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _build(batch, **cfg_kw):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm

    # the reference run_random.sh shapes scaled to a CPU-friendly size —
    # a realistic compute/staging ratio (per-step input bytes are small
    # next to the MLP FLOPs, as in the real configs), not a toy MLP whose
    # step time is all dispatch
    dcfg = DLRMConfig(embedding_size=[16384] * 8, sparse_feature_size=64,
                      mlp_bot=[64, 256, 256, 64],
                      mlp_top=[576, 512, 256, 1])
    model = ff.FFModel(ff.FFConfig(batch_size=batch, seed=0, **cfg_kw))
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"])
    model.init_layers()
    return model, dcfg


def _host_batches(dcfg, batch, n=8):
    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    out = []
    for i in range(n):
        x, y = synthetic_batch(dcfg, batch, seed=i)
        x["label"] = y
        out.append(x)
    return out


def measure(steps=60, batch=128):
    from dlrm_flexflow_tpu.data.prefetch import PrefetchPipeline

    model, dcfg = _build(batch)
    depth = max(getattr(model.config, "prefetch_depth", 2), 1)
    batches = _host_batches(dcfg, batch)
    nb = len(batches)

    staged = [model._stage_step(b) for b in batches]
    model.train_batch_staged(staged[0])          # warm/compile

    def timed(run):
        t0 = time.perf_counter()
        mets = run()
        float(mets["loss"])                      # true completion
        return steps / (time.perf_counter() - t0)

    def run_staged():
        mets = None
        for s in range(steps):
            mets = model.train_batch_staged(staged[s % nb])
        return mets

    def run_streamed():
        mets = None
        for s in range(steps):
            mets = model.train_batch_staged(
                model._stage_step(batches[s % nb]))
        return mets

    sps_staged = timed(run_staged)
    sps_streamed = timed(run_streamed)

    pipe = PrefetchPipeline(
        lambda k: model._stage_step(batches[k % nb]),
        depth=depth, num_items=steps, name="bench")
    try:
        def run_prefetched():
            mets = None
            for _ in range(steps):
                mets = model.train_batch_staged(pipe.get())
            return mets

        sps_prefetched = timed(run_prefetched)
        overlap = pipe.stats()["overlap_fraction"]
    finally:
        pipe.close()

    # host-resident tables: exact inline ordering vs the double-buffered
    # worker (scatter + chained next-step gather). Both are numerically
    # exact; the async mode just overlaps the host work with the device.
    def run_host(m, chained):
        hstaged = [m._stage_step(b) for b in batches]
        m.train_batch_staged(hstaged[0])         # warm/compile
        t0 = time.perf_counter()
        mets = None
        for s in range(steps):
            nh = hstaged[(s + 1) % nb].host_idx if chained else None
            mets = m.train_batch_staged(hstaged[s % nb], next_host_idx=nh)
        float(mets["loss"])
        m._host_drain()
        return steps / (time.perf_counter() - t0)

    h_sync, _ = _build(batch, host_resident_tables=True,
                       host_tables_async=False)
    sps_host_sync = run_host(h_sync, chained=False)
    h_async, _ = _build(batch, host_resident_tables=True)  # async default
    sps_host_async = run_host(h_async, chained=True)

    return {
        "steps_per_s_staged": round(sps_staged, 2),
        "steps_per_s_streamed": round(sps_streamed, 2),
        "steps_per_s_prefetched": round(sps_prefetched, 2),
        "streamed_vs_staged": round(sps_streamed / sps_staged, 4),
        "prefetched_vs_staged": round(sps_prefetched / sps_staged, 4),
        "overlap_fraction": round(overlap, 4),
        "steps_per_s_host_sync": round(sps_host_sync, 2),
        "steps_per_s_host_async": round(sps_host_async, 2),
        "host_async_speedup": round(sps_host_async / sps_host_sync, 4),
    }


def main():
    steps = 60
    if "--steps" in sys.argv:
        steps = int(sys.argv[sys.argv.index("--steps") + 1])
    out = {"metric": "input_pipeline_smoke", "unit": "steps/s / ratio"}
    out.update(measure(steps=steps))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    sys.exit(main())
