"""Time the three forms of the Mamba-2 hand-over of state from chunk to
chunk, S_c = a_c S_(c-1) + s_c with a_c one number a head, inside the whole
chunked recurrence (`ops/mamba.py:ssd_chunked`), forward and backward, at one
model's sizes: what `ops/mamba.py:states_entering` was chosen from (PERF.md,
PR 32), and what re-derives the choice on another chip or another jax.

    chiprun --chips 1 -- python benchmarks/ssd_hand_over.py
    python benchmarks/ssd_hand_over.py --seq 256 --heads 4 --reps 2   # a CPU walks it

  matrix       one (chunks x chunks) decay matrix a head against the chunks'
               contributions (`states_entering`, the op's)
  associative  `lax.associative_scan` over (a, s) pairs
  sequential   `lax.scan`, one chunk after the other: what the delta rule of
               `ops/delta_net.py`, whose hand-over multiplies by a matrix,
               has to do

Each form is jitted as `jax.grad` of the recurrence's summed output (every
input's gradient), run `--reps` times after a warm-up; the host clock around
`block_until_ready`, the least of the reps. One JSON line.
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
from jax import lax

from dlrm_flexflow_tpu.ops import mamba


def sequential(log_a, s):
    def step(S, xs):
        la, own = xs
        return S * jnp.exp(la)[..., None, None] + own, S

    return jnp.moveaxis(lax.scan(
        step, jnp.zeros_like(s[:, 0]),
        (jnp.moveaxis(log_a, 2, 0), jnp.moveaxis(s, 1, 0)))[1], 0, 1)


def associative(log_a, s):
    def combine(left, right):
        (la, sl), (ra, sr) = left, right
        return la + ra, sl * jnp.exp(ra)[..., None, None] + sr

    _, after = lax.associative_scan(
        combine, (jnp.moveaxis(log_a, 2, 1), s), axis=1)
    return jnp.pad(after[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * 3)


FORMS = {"matrix": mamba.states_entering, "associative": associative,
         "sequential": sequential}


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--state", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)
    g = min(args.groups, args.heads)
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (1, args.seq, args.heads, args.head_dim),
                          jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, args.seq, args.heads))
                         - 4.0)
    A = -jnp.exp(jax.random.uniform(k[2], (args.heads,), minval=0.0,
                                    maxval=2.77))
    B, C = (jax.random.normal(k[i], (1, args.seq, g, args.state))
            for i in (3, 4))
    out = {"device": jax.devices()[0].device_kind,
           "shape": [args.seq, args.heads, args.head_dim, g, args.state,
                     args.chunk], "sums": {}}
    for name, form in FORMS.items():
        fn = jax.jit(jax.value_and_grad(
            lambda *a, form=form: jnp.sum(mamba.ssd_chunked(
                *a, args.chunk, jnp.bfloat16, hand_over=form)),
            argnums=(0, 1, 2, 3, 4)))
        out["sums"][name] = float(fn(x, dt, A, B, C)[0])    # and warms up
        times = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(x, dt, A, B, C))
            times.append(time.perf_counter() - t)
        out[f"{name}_ms"] = 1e3 * min(times)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
