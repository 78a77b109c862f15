"""Time the forms of a chunked recurrence's hand-over of state from chunk
to chunk inside the whole recurrence, forward and backward, at one model's
sizes. `--recurrence ssd` (the default): Mamba-2's, S_c = a_c S_(c-1) + s_c
with a_c one number a head (`ops/mamba.py:ssd_chunked`), in three forms:
what `ops/mamba.py:states_entering` was chosen from (PERF.md, PR 32).
`--recurrence delta`: the gated delta rule's, a MATRIX hand-over
(`ops/delta_net.py:gated_delta_rule_chunked`), as the `lax.scan` and as the
Pallas kernel with the state resident in VMEM (PERF.md, PR 36; the kernel
on a TPU only). Either re-derives the choice on another chip or another jax.

    chiprun --chips 1 -- python benchmarks/ssd_hand_over.py [--recurrence delta]
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

from dlrm_flexflow_tpu.ops import delta_net, mamba


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


def timed(fn, args, reps):
    """(what the first call returned, the least of `reps` calls in ms)."""
    first = jax.block_until_ready(fn(*args))    # and warms up
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return first, 1e3 * min(times)


def ssd(args, out):
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
    out["shape"] = [args.seq, args.heads, args.head_dim, g, args.state,
                    args.chunk]
    for name, form in FORMS.items():
        fn = jax.jit(jax.value_and_grad(
            lambda *a, form=form: jnp.sum(mamba.ssd_chunked(
                *a, args.chunk, jnp.bfloat16, hand_over=form)),
            argnums=(0, 1, 2, 3, 4)))
        first, out[f"{name}_ms"] = timed(fn, (x, dt, A, B, C), args.reps)
        out["sums"][name] = float(first[0])


def delta(args, out):
    """Heads of `--state` x `--state` (Qwen3-Next: 32 of 128 x 128, chunk
    64). `<form>_fwd_ms` is the recurrence alone, `<form>_ms` its value
    and every input's gradient."""
    h, d = args.heads, args.state
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    q, kk = (delta_net.l2_normalize(jax.random.normal(
        k[i], (1, args.seq, h, d))).astype(jnp.bfloat16) for i in (0, 1))
    v = jax.random.normal(k[2], (1, args.seq, h, d), jnp.bfloat16)
    g = -0.1 * jax.nn.softplus(jax.random.normal(k[3], (1, args.seq, h)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (1, args.seq, h)))
    out["shape"] = [args.seq, h, d, args.chunk]
    forms = {"scan": False}
    if jax.default_backend() == "tpu":
        forms["kernel"] = True
    for name, resident in forms.items():
        def loss(*a, resident=resident):
            return jnp.sum(delta_net.gated_delta_rule_chunked(
                *a, args.chunk, jnp.bfloat16, resident=resident))
        _, out[f"{name}_fwd_ms"] = timed(jax.jit(loss), (q, kk, v, g, beta),
                                         args.reps)
        first, out[f"{name}_ms"] = timed(
            jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))),
            (q, kk, v, g, beta), args.reps)
        out["sums"][name] = float(first[0])


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--recurrence", choices=("ssd", "delta"), default="ssd")
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--state", type=int, default=128)
    ap.add_argument("--chunk", type=int)
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)
    is_ssd = args.recurrence == "ssd"
    args.heads = args.heads or (64 if is_ssd else 32)
    args.chunk = args.chunk or (128 if is_ssd else delta_net.CHUNK)
    out = {"device": jax.devices()[0].device_kind,
           "recurrence": args.recurrence, "sums": {}}
    (ssd if is_ssd else delta)(args, out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
