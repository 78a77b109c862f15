#!/usr/bin/env python3
"""One traced run of a DLRM cell, and the thirteen numbers that read the
names the program gives its work (spanreduce.py): device time by op group
and by kernel, host time by span, the device's idle time by span.

    python3 perfbench/spanreport.py --workload <name> [--seed <n>] \\
        [--seconds <s>] [--keep-trace DIR] [--rehearse]
    python3 perfbench/spanreport.py --recorded DIR/trace.json.gz

A builder's tool, not the benchmark's command: these thirteen are no
entries of BENCHMARK.json. `run.py:traced_metrics` hands its readers
tracereduce.reduce()'s sums and drops the recorded trace, and these numbers
need every op and every host event of it. So this runs the cell through
`run.main(... --trace 1 --keep-trace DIR)`, unchanged, then completes the
recorded trace it kept with the program's scope map
(`obs.trace.program_scopes()`, still in this process) and reads that.
`DIR/trace.json.gz` is afterwards a complete fixture (tests/perfbench/data/)
and what `--recorded` reads. The last line of stdout is the report object
(after `rehearsed: ` on a CPU, whose times are no device numbers).
"""

import argparse
import gzip
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    # the script's own directory must not shadow top-level modules
    sys.path[0] = ROOT

from perfbench import spanreduce, tracereduce   # noqa: E402

# the DLRM family's ops, by the name `build_dlrm` gives them and the step's
# scopes carry; tried in this order: `update.emb_concat` is the update and
# not the lookup, `emb_flatten` is the interaction's reshape and not a lookup
GROUPS = (
    ("embedding_update", r"update\..*"),
    ("dense_update", r"optimizer|loss|metrics"),
    ("interaction", r"interaction_.*|bot3d|emb_flatten|fused_interaction"),
    ("mlp", r"(bot|top)_dense_\d+"),
    ("embedding_fwd", r"emb_.*|idx_\d+|sparse_split"),
)
TABLE_ROWS = 20


def group_of(path: str) -> str:
    """One of GROUPS' names; `unscoped` for a path with no `ff.` scope or
    with an op this family does not know."""
    op = spanreduce.group(path)
    for name, pattern in GROUPS:
        if op and re.fullmatch(pattern, op):
            return name
    return "unscoped"


def group_ms(rec: dict, name: str):
    """Device time a step under one group, in ms; None where the program
    gave no scope map (a commit before the scopes)."""
    if not rec.get("scopes"):
        return None
    s = sum(v for path, v in spanreduce.device_by_scope(rec).items()
            if group_of(path) == name)
    return 1e3 * s / rec["steps"]


def kernel_ms(rec: dict, *kernels: str):
    """Device time a step of the Mosaic calls that an instruction name or
    a scope path says are one of `kernels`, in ms; None where neither
    can say (no scope map and no such instruction name)."""
    s = [sec for name, path, klass, sec in spanreduce.device_ops(rec)
         if klass == "mosaic" and any(k in name or k in path
                                      for k in kernels)]
    if not s and not rec.get("scopes"):
        return None
    return 1e3 * sum(s) / rec["steps"]


def span_ms(rec: dict, *spans: str, idle: bool = False):
    """Host time a step inside `spans` (idle=False), or the device's idle
    time a step while the host was in them (idle=True; "" = in none), in
    ms; None where the slice's thread carries no span of the program's."""
    if not spanreduce.host_by_span(rec):
        return None
    by = (spanreduce.idle_by_span if idle else spanreduce.host_by_span)(rec)
    return 1e3 * sum(by.get(s, 0.0) for s in spans) / rec["steps"]


# name -> (layer, source, what it reads); all in ms a step, lower is better,
# all should move samples_per_s_per_chip. Written as BENCHMARK.json's
# per_layer entries would be, for the day run.py hands its readers the trace.
METRICS = {
    "mlp_ms_per_step":
        ("ops", "device_trace", lambda r: group_ms(r, "mlp")),
    "interaction_ms_per_step":
        ("ops", "device_trace", lambda r: group_ms(r, "interaction")),
    "embedding_fwd_ms_per_step":
        ("ops", "device_trace", lambda r: group_ms(r, "embedding_fwd")),
    "embedding_update_ms_per_step":
        ("ops", "device_trace", lambda r: group_ms(r, "embedding_update")),
    "dense_update_ms_per_step":
        ("ops", "device_trace", lambda r: group_ms(r, "dense_update")),
    "unscoped_ms_per_step":
        ("ops", "device_trace", lambda r: group_ms(r, "unscoped")),
    "emb_gather_kernel_ms_per_step":
        ("kernels", "device_trace", lambda r: kernel_ms(r, "emb_gather")),
    "emb_scatter_kernel_ms_per_step":
        ("kernels", "device_trace",
         lambda r: kernel_ms(r, "emb_scatter_add", "emb_scatter_write")),
    "dispatch_ms_per_step":
        ("training_loop", "program_span",
         lambda r: span_ms(r, "train/dispatch")),
    "exec_call_ms_per_step":
        ("training_loop", "program_span",
         lambda r: span_ms(r, "train/step", "train/superstep")),
    "throttle_wait_ms_per_step":
        ("training_loop", "program_span",
         lambda r: span_ms(r, "fit/throttle")),
    "idle_in_dispatch_ms_per_step":
        ("training_loop", "program_span",
         lambda r: span_ms(r, "train/dispatch", idle=True)),
    "idle_outside_spans_ms_per_step":
        ("training_loop", "program_span",
         lambda r: span_ms(r, "", idle=True)),
}
OPS = [n for n, (layer, _, _) in METRICS.items() if layer == "ops"]


def metrics(rec: dict) -> dict:
    """{name: ms a step} of a complete recorded trace; a metric whose names
    the program did not give is left out."""
    out = {name: read(rec) for name, (_, _, read) in METRICS.items()}
    return {k: v for k, v in out.items() if v is not None}


def table(rec: dict) -> str:
    """The heaviest scope paths of the traced slice, ms a step, with the
    instructions the trace shows under each: what `fusion.7` is."""
    if not rec.get("scopes"):
        return "no scope map in this recorded trace"
    steps = rec["steps"]
    by_path = {}        # path -> {instruction: seconds}
    for name, path, _, s in spanreduce.device_ops(rec):
        ops = by_path.setdefault(path, {})
        ops[name] = ops.get(name, 0.0) + s
    rows = sorted(by_path.items(),
                  key=lambda kv: -sum(kv[1].values()))[:TABLE_ROWS]
    lines = [f"device time by scope, the {len(rows)} heaviest of "
             f"{len(by_path)} paths (ms a step, group, path, instructions):"]
    for path, ops in rows:
        heaviest = sorted(ops, key=lambda n: -ops[n])
        lines.append(f"  {1e3 * sum(ops.values()) / steps:8.4f}  "
                     f"{group_of(path):<16} {path or '(no scope)'}  "
                     f"[{' '.join(heaviest[:4])}"
                     f"{' ...' if len(heaviest) > 4 else ''}]")
    return "\n".join(lines)


def report(rec: dict) -> dict:
    """Logs the table, the thirteen and the two identities that tie them
    to tracereduce's sums; returns the report object."""
    steps = rec["steps"]
    found = metrics(rec)
    old = tracereduce.reduce(rec, steps)
    print(table(rec))
    for name, value in found.items():
        layer, source, _ = METRICS[name]
        print(f"{name:<34} {value:10.4f} ms  ({layer}, {source})")
    idle = spanreduce.idle_by_span(rec)
    print("idle by span, ms a step: " + ", ".join(
        f"{k or '(no span)'} {1e3 * v / steps:.4f}"
        for k, v in sorted(idle.items(), key=lambda kv: -kv[1])))
    out = {"cell": rec.get("cell"), "steps": steps, "metrics": found}
    if old and all(n in found for n in OPS):
        out["ops_sum_ms"] = sum(found[n] for n in OPS)
        out["xla_plus_mosaic_ms"] = 1e3 * (old["xla_s"]
                                           + old["mosaic_s"]) / steps
        out["idle_by_span_sum_ms"] = 1e3 * sum(idle.values()) / steps
        out["host_gap_ms"] = 1e3 * (old["window_s"] - old["busy_s"]) / steps
        print(f"identities: six ops {out['ops_sum_ms']:.6f} = xla + mosaic "
              f"{out['xla_plus_mosaic_ms']:.6f}; idle by span "
              f"{out['idle_by_span_sum_ms']:.6f} = host gap "
              f"{out['host_gap_ms']:.6f}")
    return out


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload")
    p.add_argument("--recorded", metavar="trace.json.gz")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--keep-trace", default=None, metavar="DIR")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    if (args.workload is None) == (args.recorded is None):
        p.error("one of --workload and --recorded")
    path = args.recorded
    if args.workload:
        from perfbench import run
        keep = args.keep_trace or os.path.join(run.OUT_DIR, "spanreport",
                                               args.workload)
        path = os.path.join(keep, "trace.json.gz")
        if os.path.exists(path):
            os.remove(path)             # never report an earlier run's
        rc = run.main(
            ["--workload", args.workload, "--seed", str(args.seed),
             "--trace", "1", "--keep-trace", keep]
            + ([] if args.seconds is None
               else ["--seconds", str(args.seconds)])
            + (["--rehearse"] if args.rehearse else []))
        if rc or not os.path.exists(path):
            print("spanreport: the run kept no recorded trace",
                  file=sys.stderr)
            return rc or 1
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    if args.workload:
        from dlrm_flexflow_tpu.obs import trace as program
        rec["scopes"] = program.program_scopes()
        with gzip.open(path, "wt") as f:
            json.dump(rec, f)
    out = json.dumps(report(rec))
    # a CPU's times are no device numbers: no bare report line, as run.py
    print(("rehearsed: " if args.rehearse else "") + out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
