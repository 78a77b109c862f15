#!/usr/bin/env python
"""Observability-overhead benchmark: what does ``--obs on`` cost?

The whole point of the obs layer is that it is cheap enough to leave on
in production; this bench holds it to that (the ISSUE-15 bar: <= 2%
overhead on BOTH train steps/s and serve p99). Measures, on a small
DLRM (CPU or attached accelerator):

- ``train_steps_per_s_off`` / ``train_steps_per_s_on`` — a 200-step
  pre-staged training loop with obs off vs on (spans on every dispatch,
  the drift monitor observing every step); ``train_overhead_frac`` is
  the relative slowdown and ``train_overhead_ok`` the <= 2% verdict.
- ``serve_p99_ms_off`` / ``serve_p99_ms_on`` — the serving engine's
  request p99 under a closed-loop client with obs off vs on (enqueue/
  batch-form/dispatch spans, latency reservoir registered as a scrape
  histogram, the stats collector live); ``serve_overhead_frac`` +
  ``serve_overhead_ok`` likewise.
- ``trace_export`` — size and wall time of one Chrome-trace export of
  the 200-step run's ring (the "one trace away" promise has to stay
  cheap too).

Both measurements repeat ``repeats`` times and keep the BEST throughput
/ LOWEST p99 per mode — CPU wall-clock noise at the 2% scale demands
best-of-N, the same discipline bench.py's headline windows use.

Prints ONE JSON line; ``measure()`` is imported by bench.py when
BENCH_OBS=1 so obs-overhead regressions show up next to the headline
throughput.

Usage: python benchmarks/bench_obs.py [--steps N]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

OVERHEAD_BAR = 0.02


def _build(batch, **cfg_kw):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm

    dcfg = DLRMConfig(embedding_size=[16384] * 8, sparse_feature_size=64,
                      mlp_bot=[64, 256, 256, 64],
                      mlp_top=[576, 512, 256, 1])
    model = ff.FFModel(ff.FFConfig(batch_size=batch, seed=0, **cfg_kw))
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"])
    model.init_layers()
    return model, dcfg


def _train_overhead(steps, batch, repeats):
    """(best off steps/s, best on steps/s), INTERLEAVED windows over one
    model: the span/drift hooks check the global obs switch at call
    time, so flipping it per window compares the two modes under the
    same thermal/GC conditions — at the 2% scale, back-to-back blocks
    measure the machine's drift, not the instrumentation."""
    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    from dlrm_flexflow_tpu.obs import metrics, trace
    from dlrm_flexflow_tpu.obs.drift import DriftMonitor

    model, dcfg = _build(batch)
    x, y = synthetic_batch(dcfg, batch, seed=0)
    x["label"] = y
    staged = model._stage_step(x)
    model.train_batch_staged(staged)            # warm/compile

    def window(mon):
        t0 = time.perf_counter()
        mets = None
        for _s in range(steps):
            t_step = time.perf_counter() if mon is not None else 0.0
            mets = model.train_batch_staged(staged)
            if mon is not None:
                mon.observe_step(time.perf_counter() - t_step)
        float(mets["loss"])                     # true completion
        return steps / (time.perf_counter() - t0)

    best_off = best_on = 0.0
    for _ in range(repeats):
        with metrics.override(False), trace.override(False):
            best_off = max(best_off, window(None))
        with metrics.override(True), trace.override(True):
            best_on = max(best_on,
                          window(DriftMonitor(name="bench")))
            trace.clear()
    return best_off, best_on


def _serve_overhead(requests, batch, repeats):
    """(off p99, on p99) over ONE engine: MEDIAN of `repeats`
    interleaved windows per mode (4 closed-loop client threads against
    the continuous batcher). Median-of-windows because a CPU closed
    loop's p99 is scheduler-coupled — any single window can eat a 10 ms
    GIL/timeslice outlier that has nothing to do with the
    instrumentation being measured."""
    import statistics
    import threading

    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    from dlrm_flexflow_tpu.obs import metrics, trace
    from dlrm_flexflow_tpu.obs.metrics import percentile
    from dlrm_flexflow_tpu.serve import InferenceEngine, ServeConfig

    model, dcfg = _build(batch)
    eng = InferenceEngine(model, ServeConfig(max_batch=batch,
                                             queue_capacity=4096))
    windows = {False: [], True: []}
    with eng:
        feats, _ = synthetic_batch(dcfg, 1, seed=1)
        eng.predict(feats)                      # warm

        def window():
            lat = []
            lock = threading.Lock()
            n_threads = 4
            n_per = max(requests // n_threads, 1)

            def client(n):
                f, _ = synthetic_batch(dcfg, 1, seed=n)
                for _i in range(n_per):
                    t0 = time.perf_counter()
                    eng.predict(f)
                    ms = 1e3 * (time.perf_counter() - t0)
                    with lock:
                        lat.append(ms)

            threads = [threading.Thread(target=client, args=(i,),
                                        daemon=True)
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return percentile(sorted(lat), 99)

        window()                                # settle the batcher
        for _ in range(repeats):
            for on in (False, True):
                with metrics.override(on), trace.override(on):
                    windows[on].append(window())
                    if on:
                        trace.clear()
    return (statistics.median(windows[False]),
            statistics.median(windows[True]))


def _trace_export(steps, batch, tmpdir):
    """Size + latency of exporting a 200-step run's span ring."""
    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    from dlrm_flexflow_tpu.obs import metrics, trace

    with metrics.override(True), trace.override(True,
                                                trace_dir=tmpdir):
        model, dcfg = _build(batch)
        x, y = synthetic_batch(dcfg, batch, seed=0)
        x["label"] = y
        staged = model._stage_step(x)
        model.train_batch_staged(staged)
        for _ in range(steps):
            model.train_batch_staged(staged)
        t0 = time.perf_counter()
        path = trace.export_to_dir()
        export_s = time.perf_counter() - t0
        out = {
            "events": len(trace.events()),
            "dropped": trace.dropped(),
            "export_ms": round(1e3 * export_s, 2),
            "file_bytes": os.path.getsize(path),
        }
        trace.clear()
    return out


def measure(steps=200, batch=128, requests=384, repeats=3):
    import tempfile

    train_off, train_on = _train_overhead(steps, batch, repeats)
    serve_off, serve_on = _serve_overhead(requests, batch, repeats + 4)
    with tempfile.TemporaryDirectory() as d:
        export = _trace_export(steps, batch, d)

    train_frac = (train_off - train_on) / train_off if train_off else 0.0
    serve_frac = ((serve_on - serve_off) / serve_off
                  if serve_off else 0.0)
    return {
        "train_steps_per_s_off": round(train_off, 2),
        "train_steps_per_s_on": round(train_on, 2),
        "train_overhead_frac": round(train_frac, 4),
        "train_overhead_ok": bool(train_frac <= OVERHEAD_BAR),
        "serve_p99_ms_off": round(serve_off, 3),
        "serve_p99_ms_on": round(serve_on, 3),
        "serve_overhead_frac": round(serve_frac, 4),
        "serve_overhead_ok": bool(serve_frac <= OVERHEAD_BAR),
        "overhead_bar": OVERHEAD_BAR,
        "trace_export": export,
    }


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    steps = 200
    if "--steps" in argv:
        steps = int(argv[argv.index("--steps") + 1])
    out = {"bench": "obs_overhead", **measure(steps=steps)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    sys.exit(main())
