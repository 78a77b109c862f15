#!/usr/bin/env python
"""A fused superstep on the chip, at a benchmark cell's own shapes: is it
the same training as K single steps, and what does it buy?

    python benchmarks/superstep_on_chip.py [--workload <cell>] \\
        [--steps 64] [--k 16] [--seed N]

Builds the cell's model twice from one seed, trains the data set's first
`--steps` batches through `fit()` once a step a dispatch (`--superstep 1`)
and once `--k` steps a dispatch, and compares what the two left behind:
every table row the batches touched and the dense weights. On the CPU
`tests/test_superstep.py` holds the two bit for bit; the TPU compiles the
scan's body on its own, and the Pallas kernels run inside a `while` there.
The benchmark's `correct` never sees the fused program (its checked steps
run one batch an epoch: K = 1), so this is its witness on the chip. The
last line of stdout is the comparison.

A builder's tool (DLRM-family cells; it needs the chip's memory for one
model at a time); a CPU walks it with `--rehearse`. What one dispatch a K
steps buys: `benchmarks/bench_superstep.py`.
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def same(args):
    from perfbench import manifest as mf
    from perfbench import run as harness
    from perfbench.traffic import gen
    man = mf.load()
    cell = mf.find_cell(man, args.workload)
    config = mf.load_config(man, cell["config"])
    mix = gen.load_mix(cell["traffic"])
    chips = int(cell["chips"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    import dlrm_flexflow_tpu as ff
    family = mf.load_family(config["family"])
    ff.use_compile_cache()
    rows = family.held_table_rows(config, chips)
    batch = int(mix["batch_per_chip"]) * chips
    if args.rehearse:
        rows = [min(r, harness.REHEARSE["rows"]) for r in rows]
        batch = min(batch, harness.REHEARSE["batch_per_chip"])
    data = gen.generate(mix, family.input_fields(config, rows),
                        batch * args.steps, args.seed)
    x, y = family.fit_arrays(data)

    def read(model, touched):
        mlps = family.read_mlps(model, config)
        return [touched.read(model)] + [a for pair in mlps["bot"]
                                        + mlps["top"] for a in pair]

    def trained(k):
        gc.collect()        # the other model's 6 GB of rows, before this one
        model, _ = family.build(config, rows, batch, chips, args.seed)
        model.config.superstep = k      # an explicit K: no probe
        touched = family.Touched(model, data["sparse"])
        before = read(model, touched)
        out = model.fit(x, y, epochs=1, verbose=False)
        jax.block_until_ready(model.params)
        return (before, read(model, touched), out,
                len(model._superstep_execs))

    before, after1, _, _ = trained(1)
    _, afterk, out, fused_programs = trained(args.k)

    def compare(start, a, b):
        d = np.abs(a.astype(np.float64) - b)
        return {"elements": int(a.size), "differ": int((a != b).sum()),
                "max_abs_diff": float(d.max()),
                "max_abs_update": float(np.abs(a - start).max()),
                "finite": bool(np.isfinite(b).all())}

    report = {
        "cell": cell["name"], "steps": args.steps, "k": args.k,
        "seed": args.seed, "platform": jax.devices()[0].platform,
        "fused_programs": fused_programs,
        "fit_superstep": out.get("superstep"),
        # the touched table rows first, then each dense weight and bias
        "arrays": [compare(*abc) for abc in zip(before, after1, afterk)],
    }
    report["identical"] = not any(a["differ"] for a in report["arrays"])
    print(json.dumps(report), flush=True)
    return 0


def main(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", default="dlrm_terabyte.b128_local")
    p.add_argument("--steps", type=int, default=64)
    p.add_argument("--k", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true")
    return same(p.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
