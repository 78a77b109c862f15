"""Time one expert layer's routed part alone, value and every gradient, as
the XLA `while` over the sorted pairs (`ops/moe.py:_routed`, `CHUNK_ROWS` a
trip) and as the grid of the Pallas kernel (`_routed_grid`,
`ops/pallas/moe_kernel.py`) at several rows a trip, at one model's widths
(PERF.md, PR 37: where `moe_kernel.ROWS` comes from). Re-derives the
choice on another chip or another jax.

    chiprun --chips 1 -- python benchmarks/expert_walk.py --model qwen3_next
    python benchmarks/expert_walk.py --model glm_4_7_flash --describe

`--load zipf` gives the held experts `--held-share` of the pairs (default
held / experts, the even share: ~160 pairs an expert at Qwen3-Next's
widths, as in the cells), spread over them at zipf 1.05 in a seeded order;
`--load deployment` gives every pair to a held expert, evenly (tokens x
top_k / held pairs an expert: 2,560 at Qwen3-Next's widths). Each form is
jitted as `jax.value_and_grad` of the routed result against a fixed
cotangent (the tokens', every matrix's and the pair weights' gradients),
run `--reps` times after a warm-up; the host clock around
`block_until_ready`, the least of the reps; `<form>_fwd_ms` is the value
alone, `kernel_<rows>_fwd_alone_ms` / `_bwd_alone_ms` the
two kernels with their operands' preparation and nothing else (no sort, no
`combine`). The kernel's results are compared with the walk's
(`worst`: the largest difference of any result over that result's largest
entry). `--describe` compiles every form for a described (not attached)
v5e and runs nothing: Mosaic says there what does not fit VMEM. One JSON
line.
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
from dlrm_flexflow_tpu.ops.pallas import moe_kernel

# (hidden, expert width, experts held, experts routed over, top-k, form):
# one chip's share of the three language-model cells
MODELS = {"qwen3_next": (2048, 512, 32, 512, 10, "swiglu"),
          "glm_4_7_flash": (2048, 1536, 8, 64, 4, "swiglu"),
          "nemotron_3_nano": (2688, 1856, 8, 128, 6, "relu2")}


def routing(args, held, experts, top_k):
    """(order, counts, held_pair) as `MoE.apply_with_state` makes them."""
    rng = np.random.default_rng(args.seed)
    pairs = args.tokens * top_k
    if args.load == "deployment":
        local = rng.permutation(pairs) % held
    else:
        share = args.held_share or held / experts
        p = 1.0 / np.arange(1, held + 1) ** 1.05
        local = rng.permutation(held)[
            rng.choice(held, size=pairs, p=p / p.sum())]
        local = np.where(rng.random(pairs) < share, local, held)
    key = jnp.where(jnp.asarray(local) < held, jnp.asarray(local), held)
    order = jnp.pad(jnp.argsort(key).astype(jnp.int32), (0, moe.CHUNK_ROWS))
    counts = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    return order, counts, key < held


def timed(fn, args, reps):
    """(what the first call returned, the least of `reps` calls in ms)."""
    first = jax.block_until_ready(fn(*args))    # and warms up
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return first, 1e3 * min(times)


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", choices=sorted(MODELS), default="qwen3_next")
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--load", choices=("zipf", "deployment"), default="zipf")
    ap.add_argument("--held-share", type=float)
    ap.add_argument("--rows", default="128,256,512")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--describe", action="store_true")
    args = ap.parse_args(argv)
    d, f, held, experts, top_k, act = MODELS[args.model]
    names, cdt = moe.FORMS[act], jnp.bfloat16
    where = None
    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        where = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        jax.config.update("jax_enable_compilation_cache", False)
    elif jax.default_backend() != "tpu":
        sys.exit(f"needs the TPU (or --describe); found "
                 f"{jax.default_backend()}")

    order, counts, held_pair = routing(args, held, experts, top_k)
    ks = jax.random.split(jax.random.PRNGKey(args.seed), len(names) + 3)
    xt = jax.random.normal(ks[0], (args.tokens, d), jnp.float32)
    ct = jax.random.normal(ks[1], (args.tokens, d), jnp.float32)
    pair_w = jax.random.uniform(ks[2], (args.tokens * top_k,), jnp.float32,
                                0.02, 0.3)
    ws = tuple(0.02 * jax.random.normal(
        k, (held, f, d) if n == "down" else (held, d, f), jnp.float32)
        for k, n in zip(ks[3:], names))
    out = {"device": jax.devices()[0].device_kind, "model": args.model,
           "shape": [args.tokens, d, f, held, top_k, act], "load": args.load,
           "pairs_held": int(jnp.sum(counts)),
           "pairs_busiest": int(jnp.max(counts))}

    forms = {"walk": lambda x, w, p: moe._routed(
        moe.CHUNK_ROWS, top_k, cdt, act, x, w, p, order, counts, held_pair)}
    # on the chip only where the routing rule would send the op (Mosaic
    # refuses GLM's and Nemotron's widths: `--describe` shows it)
    fits = dict(entries=order.size, held=held)
    routed = moe_kernel.shapes_fit(d, f, len(names), **fits) or args.describe
    kernel_rows = []
    for rows in (int(r) for r in args.rows.split(",")):
        out[f"kernel_{rows}_fits"] = moe_kernel.shapes_fit(
            d, f, len(names), 2, rows, **fits)
        if not routed:
            continue
        kernel_rows.append(rows)
        forms[f"kernel_{rows}"] = lambda x, w, p, rows=rows: moe._routed_grid(
            rows, top_k, cdt, False, x, w, p, order, counts, held_pair)
        out[f"kernel_{rows}_padded_row_share"] = 1.0 - out["pairs_held"] / (
            rows * int(moe._walk_plan(rows, counts)[2]))
    out["walk_padded_row_share"] = 1.0 - out["pairs_held"] / (
        moe.CHUNK_ROWS * int(moe._walk_plan(moe.CHUNK_ROWS, counts)[2]))

    if not args.describe:
        # the two kernels alone: no sort, no `combine`
        pos = moe._sorted_position(order, held_pair.size)
        for rows in kernel_rows:
            _, out[f"kernel_{rows}_fwd_alone_ms"] = timed(jax.jit(
                lambda x, w, p, rows=rows: moe_kernel.experts_fwd(
                    rows, top_k, cdt, x, w, p, order, pos, counts)[0]),
                (xt, ws, pair_w), args.reps)
            _, out[f"kernel_{rows}_bwd_alone_ms"] = timed(jax.jit(
                lambda x, w, p, rows=rows: moe_kernel.experts_bwd(
                    rows, top_k, cdt, x, w, p, order, pos, counts, ct)[:3]),
                (xt, ws, pair_w), args.reps)

    want = None
    for name, form in forms.items():
        fwd = jax.jit(form)
        both = jax.jit(jax.value_and_grad(
            lambda x, w, p, form=form: jnp.sum(form(x, w, p) * ct),
            argnums=(0, 1, 2)))
        if args.describe:
            shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=where), (xt, ws, pair_w))
            try:
                both.lower(*shapes).compile()
                fwd.lower(*shapes).compile()
                out[f"{name}_compiles"] = True
            except Exception as e:      # Mosaic's refusal is the finding
                out[f"{name}_compiles"] = str(e).splitlines()[0][:300]
            continue
        _, out[f"{name}_fwd_ms"] = timed(fwd, (xt, ws, pair_w), args.reps)
        got, out[f"{name}_ms"] = timed(both, (xt, ws, pair_w), args.reps)
        got = [np.asarray(a, np.float32) for a in jax.tree.leaves(got)]
        if want is None:
            want = got
        else:
            out[f"{name}_worst"] = max(
                float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) or 1.0))
                for a, b in zip(got, want))
    print(json.dumps(out), flush=True)
    os.makedirs("chiprun_out/expert_walk", exist_ok=True)
    with open("chiprun_out/expert_walk/runs.jsonl", "a") as fh:
        fh.write(json.dumps(out) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
