#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, on a TPU only.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1> [--keep-trace DIR] [--rehearse]

The cell's configuration (configs/), traffic mix (traffic/), model family
(models/) and layer metrics (layer_metrics/) are found by the names in
BENCHMARK.json. What is measured is `FFModel.fit()` with the program's
defaults, as `examples/` and the Keras front end call it: one `fit` call a
phase, a callback at every epoch's end that blocks on `model.params` and
stamps the host clock, so an epoch is one window.

  set-up    build + compile(), init_layers(seed) on the device, the data set
            from the seed, a warm-up fit of two epochs over its first
            batches (builds or loads the step program, gives the step time),
            CHECK_STEPS steps on the first batch through the same `fit` and
            shapes
  measured  one fit of a lead-in epoch and E window epochs, E from the
            warm-up's step time so that the windows last --seconds
  traced    (--trace 1) one more fit over a prefix of the data set, the
            profiler on for its second epoch
  checked   the plain reference repeats the checked steps from the
            weights read before them; it runs last, on the device, so that
            neither the windows nor the peak-memory reading see it

The last line of stdout is the result object. Any platform but `tpu`, or
another device count than the cell's `chips`, is exit 1 and no result.
`--rehearse` walks the same flow on virtual CPU devices at tiny row counts
and batches, and prints no result line: it proves nothing about the chip.
"""

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse            # noqa: E402
import glob                # noqa: E402
import json                # noqa: E402
import math                # noqa: E402
import os                  # noqa: E402
import shutil              # noqa: E402
import statistics          # noqa: E402
import sys                 # noqa: E402
from types import SimpleNamespace   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the script's own directory must not shadow top-level modules
sys.path[0] = ROOT

CHECK_STEPS = 3
WARMUP_BATCHES = 64
SLICE_SECONDS, SLICE_MAX_STEPS, SLICE_MIN_STEPS = 2.0, 200, 8
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
OUT_DIR = os.path.join(ROOT, "perfbench_out")
# what a rehearsal cuts, so that a CPU walks the flow in seconds
REHEARSE = {"rows": 512, "batch_per_chip": 32, "dataset_batches": 8,
            "device_kind": "TPU v5 lite"}


def log(msg):
    print(msg, flush=True)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None,
                   help="default: the manifest's run_seconds")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", default=None, metavar="DIR",
                   help="with --trace 1: keep the profiler's .xplane.pb and "
                        "the recorded trace (trace.json.gz, as tracereduce "
                        "reads it) in DIR")
    p.add_argument("--rehearse", action="store_true")
    return p.parse_args(argv)


class Programs:
    """Counts programs XLA built or loaded (jax.monitoring): the window must
    see none, and set-up says how many came from the persistent cache."""

    def __init__(self):
        import jax.monitoring as mon
        self.built = self.from_cache = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        self.built += event == COMPILE_EVENT

    def _event(self, event, **_kw):
        self.from_cache += event == CACHE_HIT_EVENT


def timed_fit(model, x, y, epochs, loss_key, on_epoch=None):
    """One `fit` call; returns (host-clock stamp, loss) at each epoch's
    end, the stamp taken after the device has finished the epoch."""
    import jax
    stamps, losses = [], []

    def at_epoch_end(m, epoch, report):
        with jax.profiler.TraceAnnotation("perfbench/callback"):
            jax.block_until_ready(m.params)
            stamps.append(time.perf_counter())
            losses.append(float(report[loss_key]))
            if on_epoch is not None:
                on_epoch(epoch)

    model.fit(x, y, epochs=epochs, verbose=False, callbacks=[at_epoch_end])
    return stamps, losses


def peak_bytes(devices):
    """Peak bytes in use on the fullest chip; None where the backend keeps
    no such count (the CPU of a rehearsal)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return max(peaks) if all(p is not None for p in peaks) else None


def traced_slice(model, x, y, steps, batch, loss_key, trace_dir):
    """A fit over the first `steps` batches, two epochs: the first leads in
    (staging, a full pipeline), the second runs under the profiler between
    two drains. Returns its wall seconds."""
    import jax
    from perfbench.tracereduce import SLICE
    shutil.rmtree(trace_dir, ignore_errors=True)
    n = steps * batch
    state = {}

    def on_epoch(epoch):
        if epoch == 0:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # the host loop is what the
            opts.host_tracer_level = 2       # slice measures: keep it light
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            state["span"] = jax.profiler.TraceAnnotation(SLICE)
            state["span"].__enter__()
            state["t0"] = time.perf_counter()
        elif "span" in state:
            state["t1"] = time.perf_counter()
            state.pop("span").__exit__(None, None, None)

    try:
        timed_fit(model, {k: v[:n] for k, v in x.items()}, y[:n], 2,
                  loss_key, on_epoch)
    finally:
        if "span" in state:
            state.pop("span").__exit__(None, None, None)
        if "t0" in state:
            jax.profiler.stop_trace()
    return state["t1"] - state["t0"]


def traced_metrics(model, x, y, batch, batches, step_s, loss_key, cell,
                   device, keep, untraced_rate):
    """The traced slice, reduced (tracereduce.reduce), or None where the
    trace holds nothing to read."""
    from perfbench import tracereduce
    trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
    steps = max(SLICE_MIN_STEPS, min(SLICE_MAX_STEPS, batches,
                                     int(SLICE_SECONDS / step_s)))
    wall = traced_slice(model, x, y, steps, batch, loss_key, trace_dir)
    t = time.time()
    (pb,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(pb, keep)
    rec = tracereduce.load_xplane(pb)
    if keep and rec:
        import gzip
        with gzip.open(os.path.join(keep, "trace.json.gz"), "wt") as f:
            json.dump(dict(rec, steps=steps, cell=cell["name"],
                           device=device), f)
    shutil.rmtree(trace_dir, ignore_errors=True)
    rate = steps * batch / wall
    log(f"traced slice: {steps} steps in {wall:.4f}s = {rate:.1f} samples/s "
        f"under the profiler, {100 * (1 - rate / untraced_rate):.2f}% under "
        f"the untraced median; trace read in {time.time() - t:.2f}s")
    return rec and tracereduce.reduce(rec, steps)


def quartiles(values):
    if len(values) < 2:
        return values[0], values[0], values[0]
    q1, q2, q3 = statistics.quantiles(values, n=4, method="inclusive")
    return q1, q2, q3


def main(argv):
    args = parse(argv)
    from perfbench import manifest as mf
    from perfbench.traffic import gen
    man = mf.load()
    cell = mf.find_cell(man, args.workload)
    config = mf.load_config(man, cell["config"])
    mix = gen.load_mix(cell["traffic"])
    chips = int(cell["chips"])
    seconds = float(man["run_seconds"] if args.seconds is None
                    else args.seconds)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")

    import jax
    devices = jax.devices()
    t_chip = time.perf_counter() - T_START
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device: platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} jax={jax.__version__}")
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] != chips):
        print(f"perfbench: cell {cell['name']!r} needs {chips} TPU chip(s), "
              f"found {device['count']} device(s) of platform "
              f"{device['platform']!r}; no result (no CPU fallback, "
              f"--rehearse walks the flow without the chip)",
              file=sys.stderr)
        return 1

    import dlrm_flexflow_tpu as ff
    family = mf.load_family(config["family"])
    cache_dir = ff.use_compile_cache()
    programs = Programs()
    peaks = mf.load_peaks(REHEARSE["device_kind"] if args.rehearse
                          else device["kind"])

    rows = family.held_table_rows(config, chips)
    per_chip = int(mix["batch_per_chip"])
    batches = int(mix["dataset_batches"])
    if args.rehearse:
        rows = [min(r, REHEARSE["rows"]) for r in rows]
        per_chip = min(per_chip, REHEARSE["batch_per_chip"])
        batches = min(batches, REHEARSE["dataset_batches"])
    batch = per_chip * chips
    log(f"cell {cell['name']}: config {cell['config']} mix {cell['traffic']} "
        f"chips {chips} batch {batch} ({per_chip} a chip) x {batches} "
        f"batches an epoch, {sum(rows):,} table rows held, seed {args.seed}, "
        f"compile cache {cache_dir}")

    # ---- set-up --------------------------------------------------------
    model, timings = family.build(config, rows, batch, chips, args.seed)
    memory = {"init_peak_bytes": peak_bytes(devices)}
    t = time.time()
    data = gen.generate(mix, family.input_fields(config, rows),
                        batch * batches, args.seed)
    x, y = family.fit_arrays(data)
    timings["data_s"] = time.time() - t

    # the warm-up: two epochs over a prefix of the data set (the step
    # program depends on the batch's shape, not on how many there are)
    t = time.time()
    warm = min(batches, WARMUP_BATCHES) * batch
    with jax.profiler.TraceAnnotation("perfbench/warmup"):
        stamps, _ = timed_fit(model, {k: v[:warm] for k, v in x.items()},
                              y[:warm], 2, family.LOSS_METRIC)
    warm_s = stamps[1] - stamps[0]
    epoch_s = warm_s * batches * batch / warm
    timings["step_compile_s"] = (time.time() - t) - 2 * warm_s

    t = time.time()
    first = {k: v[:batch] for k, v in data.items()}
    snap = family.snapshot(model, config, first)
    x1, y1 = family.fit_arrays(first)
    _, check_losses = timed_fit(model, x1, y1, CHECK_STEPS,
                                family.LOSS_METRIC)
    rows_after = snap["touched"].read(model)
    timings["check_s"] = time.time() - t

    # ---- the measured call ---------------------------------------------
    windows_wanted = max(2, round(seconds / epoch_s))
    built_before = programs.built
    stamps, losses, error = [], [], None
    t_call = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("perfbench/measured"):
            stamps, losses = timed_fit(model, x, y, windows_wanted + 1,
                                       family.LOSS_METRIC)
    except Exception as e:  # noqa: BLE001 - a failed run still reports
        error = f"{type(e).__name__}: {e}"
        log(f"the measured fit raised {error}")
    built_inside = programs.built - built_before
    attempted = (windows_wanted + 1) * batches
    bad = [i for i, v in enumerate(losses) if not math.isfinite(v)]
    failed = (attempted - len(losses) * batches) + len(bad) * batches
    if len(stamps) < 2:
        print("perfbench: the measured fit completed no window; no result",
              file=sys.stderr)
        return 1
    timings["setup_s"] = stamps[0] - T_START
    spans = [b - a for a, b in zip(stamps, stamps[1:])]
    rates = [batches * batch / s / chips for s in spans]
    q1, q2, q3 = quartiles(rates)
    memory["peak_bytes"] = peak_bytes(devices)

    log(f"set-up {timings['setup_s']:.2f}s: imports and chip {t_chip:.2f} "
        f"build {timings['build_s']:.2f} init {timings['init_s']:.2f} data "
        f"{timings['data_s']:.2f} warm-up fit "
        f"{timings['step_compile_s'] + 2 * warm_s:.2f} (of which steps "
        f"{2 * warm_s:.2f}) checked steps {timings['check_s']:.2f} staging "
        f"and lead-in epoch {stamps[0] - t_call:.2f}; {built_before} "
        f"programs built or loaded, {programs.from_cache} of them from the "
        f"persistent cache")
    log(f"measured {len(spans)} windows of {batches} steps in "
        f"{stamps[-1] - stamps[0]:.2f}s: samples/s/chip quartiles "
        f"{q1:.1f} / {q2:.1f} / {q3:.1f}, step {1e3 * batch / chips / q2:.4f}"
        f" ms (median window), {built_inside} programs built inside the call")

    trace = None
    if args.trace:
        trace = traced_metrics(model, x, y, batch, batches,
                               statistics.median(spans) / batches,
                               family.LOSS_METRIC, cell, device,
                               args.keep_trace, q2 * chips)

    # ---- the check against the plain reference ---------------------------
    t = time.time()
    check = family.verify(snap, rows_after, check_losses, config)
    log(f"check ({time.time() - t:.2f}s, after the windows): "
        + json.dumps(check))
    correct = bool(check["ok"] and error is None and not bad
                   and built_inside == 0)

    run = SimpleNamespace(
        cell=cell, config=config, mix=mix, family=family, chips=chips,
        batch_per_chip=per_chip, timings=timings, memory=memory,
        trace=trace, peaks=peaks)
    if args.trace:
        metrics = {}
        for mod in mf.layer_metrics(cell["name"]):
            value = mod.read(run)
            if value is not None:
                metrics[mod.NAME] = {"value": value, "unit": mod.UNIT}
            if hasattr(mod, "note"):
                log(f"{mod.NAME}: {mod.note(run)}")
    else:
        metrics = {
            "samples_per_s_per_chip": {"value": q2,
                                       "unit": "samples/s/chip"},
            "setup_s": {"value": timings["setup_s"], "unit": "s"},
        }
        if memory["peak_bytes"] is not None:
            metrics["peak_hbm_gib"] = {
                "value": memory["peak_bytes"] / 2**30, "unit": "GiB"}
    device["memory_peak_bytes"] = memory["peak_bytes"]
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    if args.rehearse:
        log("REHEARSAL on the CPU (tiny rows and batches): what a run would "
            "report, and no result line; it proves nothing about the chip")
        log("rehearsed: " + json.dumps(result))
        log("rehearsal " + ("passed" if correct else "FAILED"))
        return 0 if correct else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
