"""Time the expert op's `combine` alone, one pass, as the XLA gathers
(`ops/moe.py:_sum_pairs`) and as the Pallas gather-sum
(`ops/pallas/combine_kernel.py`), at each language-model cell's widths,
and check that the two agree bit for bit (but for the sign of a zero).

    python benchmarks/moe_combine.py            # on a TPU
    python benchmarks/moe_combine.py --describe

A pass's rows are random bfloat16, `tokens x top_k + 512` of
them (the XLA walk's buffer); each pair falls on a held expert with
`--held-share` (default: the cell's share from the ledger) and the pairs
are sorted as `MoE.apply_with_state` sorts them, held first. Each form is
jitted and run `--reps` times after a warm-up; the host clock around
`block_until_ready` after `--reps` calls issued back to back, the least of
three such runs. `pack_ms` is the rows' pack alone (`moe_combine_pack`),
`gather_ms` the gather-sum alone (`moe_combine`). `--describe` compiles both
forms
for a described (not attached) v5e and runs nothing. One JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dlrm_flexflow_tpu.ops import moe
from dlrm_flexflow_tpu.ops.pallas import combine_kernel

# cell -> (hidden, top-k, share of the pairs on held experts): the held
# shares are the benchmark ledger's, Qwen3-Next's the middle of its layers'
MODELS = {"qwen3_next": (2048, 10, 0.30),
          "glm_4_7_flash": (2048, 4, 0.153),
          "nemotron_3_nano": (2688, 6, 0.079),
          "lfm2_24b_a2b": (2048, 4, 0.504)}


def inputs(tokens, top_k, d, share, seed):
    """(rows_buf, pos, held_pair) of one pass."""
    rng = np.random.default_rng(seed)
    pairs = tokens * top_k
    held = rng.random(pairs) < share
    order = np.argsort(~held, kind="stable")
    pos = np.argsort(order).astype(np.int32)
    rows = rng.standard_normal((pairs + moe.CHUNK_ROWS, d), np.float32)
    return (jnp.asarray(rows, jnp.bfloat16), jnp.asarray(pos),
            jnp.asarray(held))


def timed(fn, args, reps):
    """(the result, ms a call): `reps` calls issued back to back and one
    wait, the least of three such runs, so the host's dispatch hides
    behind the device's work."""
    out = jax.block_until_ready(fn(*args))      # and warms up
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            last = fn(*args)
        jax.block_until_ready(last)
        best = min(best, (time.perf_counter() - t0) / reps)
    return out, 1e3 * best


def device_us(fn, args, reps, where):
    """{instruction: us of device time a call} over `reps` traced calls,
    the twelve largest, and "all": the device's busy time a call."""
    import glob
    from collections import Counter
    from perfbench import tracereduce
    jax.block_until_ready(fn(*args))
    with jax.profiler.trace(where):
        with jax.profiler.TraceAnnotation(tracereduce.SLICE):
            for _ in range(reps):
                out = fn(*args)
            jax.block_until_ready(out)
    rec = tracereduce.load_xplane(sorted(glob.glob(
        os.path.join(where, "**", "*.xplane.pb"), recursive=True))[-1])
    ops = Counter()
    for dev in rec["devices"].values():
        for name, _, dur in dev["ops"]:
            ops[name.rsplit(".", 1)[0] if name[-1:].isdigit() else name] += (
                dur / 1e3 / reps)
    top = dict(ops.most_common(12))
    top["all"] = sum(ops.values())
    return top


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--held-share", type=float, default=None)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--models", default=",".join(MODELS))
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="trace each form's calls there and report the "
                    "device's time a call by instruction")
    args = ap.parse_args(argv)
    xla = jax.jit(moe._sum_pairs, static_argnums=(3,))
    kernel = jax.jit(combine_kernel.gather_sum, static_argnums=(3,))
    pack = jax.jit(lambda r, live: combine_kernel._pack(r, live, False))
    gather = jax.jit(combine_kernel._gather, static_argnums=(3, 4, 5))
    models = {m: MODELS[m] for m in args.models.split(",")}
    report = {"tokens": args.tokens}
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        for name, (d, k, _) in models.items():
            pairs = args.tokens * k
            shapes = (jax.ShapeDtypeStruct((pairs + moe.CHUNK_ROWS, d),
                                           jnp.bfloat16, sharding=chip),
                      jax.ShapeDtypeStruct((pairs,), jnp.int32, sharding=chip),
                      jax.ShapeDtypeStruct((pairs,), jnp.bool_, sharding=chip))
            try:
                kernel.lower(*shapes, k).compile()
                report[name] = "compiles"
            except Exception as e:      # what Mosaic refuses, said
                report[name] = str(e).splitlines()[0][:300]
        print(json.dumps(report))
        return 0
    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "needs a TPU (or --describe)",
                          "platform": jax.default_backend()}))
        return 1
    report["device"] = jax.devices()[0].device_kind
    for name, (d, k, share) in models.items():
        share = args.held_share if args.held_share is not None else share
        rows, pos, held = inputs(args.tokens, k, d, share, args.seed)
        want, xla_ms = timed(xla, (rows, pos, held, k), args.reps)
        got, kernel_ms = timed(kernel, (rows, pos, held, k), args.reps)
        live = (jnp.max(jnp.where(held, pos, -1)) + 1).reshape(1)
        entry = {"held_pairs": int(held.sum()), "xla_ms": xla_ms,
                 "kernel_ms": kernel_ms,
                 # == takes -0.0 for 0.0: the one difference allowed
                 "equal": bool(jnp.all(got == want)),
                 "bits_differ": int(jnp.sum(
                     jax.lax.bitcast_convert_type(got, jnp.uint32)
                     != jax.lax.bitcast_convert_type(want, jnp.uint32)))}
        src, entry["pack_ms"] = timed(pack, (rows, live), args.reps)
        plan = combine_kernel._plan(pos, held, k, combine_kernel.token_block(
            args.tokens, k))
        flags = held.reshape(-1, k).astype(jnp.int32)
        entry["gather_ms"] = timed(gather, (plan, flags, src, k, d, False),
                                   args.reps)[1]
        if args.profile:
            entry["device_us"] = {
                form: device_us(fn, fa, args.reps,
                                os.path.join(args.profile, f"{name}_{form}"))
                for form, fn, fa in (("xla", xla, (rows, pos, held, k)),
                                     ("kernel", kernel,
                                      (rows, pos, held, k)))}
        report[name] = entry
    print(json.dumps(report))
    return 0 if all(v["equal"] for v in report.values()
                    if isinstance(v, dict)) else 1


if __name__ == "__main__":
    sys.exit(main())
