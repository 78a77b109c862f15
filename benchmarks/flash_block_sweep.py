"""Sweep the block sizes of jax's TPU flash-attention kernel, one kernel at
a time (forward, dkv, dq), at one shape: what `ops/attention.py:_flash_blocks`
was written from (PERF.md, PR 31), and what re-derives it on another chip or
another jax.

    python benchmarks/flash_block_sweep.py --shape 1,20,8192,256 --describe
    chiprun --chips 1 -- python benchmarks/flash_block_sweep.py \
        --shape 1,20,8192,256 --only .scratch/sweep_ok.jsonl

`--describe` compiles every candidate for a described (not attached) v5e and
runs nothing: Mosaic refuses there what does not fit its scoped VMEM, which
costs no chip time. Without it the TPU must be attached; every candidate is
compiled, run and timed (host clock around `block_until_ready`, the least of
`--reps` calls). One JSON line a candidate goes to `--out`.

The three kernels are reached through jax's private entry points so that each
is timed alone; `--whole` times `jax.grad` of the public `flash_attention`
under `_flash_blocks`' own choice and under jax's default.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import flash_attention as fa

from dlrm_flexflow_tpu.obs.trace import program_memory


def major_minor(ladder, n):
    """(major, minor) pairs of the ladder: major divides n, minor divides
    major."""
    return [(a, b) for a in ladder for b in ladder
            if n % a == 0 and b <= a and a % b == 0]


def candidates(kernel, sq, sk, ladder):
    if kernel == "dkv":     # block_q_major, block_q, block_k_major, block_k
        return [q + k for q in major_minor(ladder, sq)
                for k in major_minor(ladder, sk)]
    # fwd and dq: block_q, block_k_major, block_k
    return [(q, a, b) for q in ladder if sq % q == 0
            for a, b in major_minor(ladder, sk)]


def kernel_fn(kernel, blocks, causal, scale):
    if kernel == "fwd":
        bq, bkm, bk = blocks
        return lambda q, k, v, l, m, do, di: fa._flash_attention_impl(
            q, k, v, None, None, True, causal, scale, 1, bq, bkm, bk, False)
    if kernel == "dkv":
        bqm, bq, bkm, bk = blocks
        return lambda q, k, v, l, m, do, di: fa._flash_attention_bwd_dkv(
            q, k, v, None, None, l, m, do, di, block_q_major=bqm, block_q=bq,
            block_k_major=bkm, block_k=bk, sm_scale=scale, causal=causal,
            mask_value=fa.DEFAULT_MASK_VALUE, debug=False)
    bq, bkm, bk = blocks
    return lambda q, k, v, l, m, do, di: fa._flash_attention_bwd_dq(
        q, k, v, None, None, l, m, do, di, block_q_major=bq,
        block_k_major=bkm, block_k=bk, sm_scale=scale, causal=causal,
        mask_value=fa.DEFAULT_MASK_VALUE, debug=False)[0]


def whole_fn(block_sizes, causal, scale):
    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=causal, sm_scale=scale,
            block_sizes=block_sizes).astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="1,20,8192,256",
                    help="b,h,s,w or b,h,sq,sk,w")
    ap.add_argument("--kernels", default="fwd,dkv,dq")
    ap.add_argument("--ladder", default="256,512,1024,2048")
    ap.add_argument("--non-causal", action="store_true")
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--only", help="JSON lines {kernel, blocks} to run, in "
                    "place of the ladder's product (a --describe run's output)")
    ap.add_argument("--whole", action="store_true")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/flash_sweep/sweep.jsonl")
    args = ap.parse_args()

    dims = [int(x) for x in args.shape.split(",")]
    b, h, sq, sk, w = dims if len(dims) == 5 else dims[:3] + dims[2:]
    causal, scale = not args.non_causal, 1.0 / math.sqrt(w)
    ladder = [int(x) for x in args.ladder.split(",")]

    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        where = SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", False)
    elif jax.default_backend() != "tpu":
        sys.exit(f"needs the TPU (or --describe); found {jax.default_backend()}")
    else:
        where = None

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    shapes = (sds((b, h, sq, w), jnp.bfloat16), sds((b, h, sk, w), jnp.bfloat16),
              sds((b, h, sk, w), jnp.bfloat16), sds((b, h, sq), jnp.float32),
              sds((b, h, sq), jnp.float32), sds((b, h, sq, w), jnp.bfloat16),
              sds((b, h, sq), jnp.float32))
    arrays = None
    if not args.describe:
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (b, h, sq, w), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, h, sk, w), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, h, sk, w), jnp.bfloat16)
        do = jax.random.normal(ks[3], (b, h, sq, w), jnp.bfloat16)
        o, l, m = jax.jit(kernel_fn("fwd", (128, 128, 128), causal, scale))(
            q, k, v, None, None, None, None)
        di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
        arrays = (q, k, v, l, m, do, di)

    if args.whole:
        from dlrm_flexflow_tpu.ops.attention import _flash_blocks
        todo = [("whole", None), ("whole", "rule")]
    elif args.only:
        with open(args.only) as f:
            todo = [(r["kernel"], tuple(r["blocks"]))
                    for r in map(json.loads, f) if r.get("ok", True)]
    else:
        todo = [(kern, blocks) for kern in args.kernels.split(",")
                for blocks in [(128,) * (4 if kern == "dkv" else 3)]
                + candidates(kern, sq, sk, ladder)]

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "a") as out:
        for kern, blocks in todo:
            rec = {"shape": [b, h, sq, sk, w], "causal": causal,
                   "kernel": kern, "blocks": blocks}
            t0 = time.perf_counter()
            try:
                if kern == "whole":
                    bs = _flash_blocks(b, sq, sk, w)[0] if blocks else None
                    rec["blocks"] = bs and dataclasses.asdict(bs)
                    fn, ins = whole_fn(bs, causal, scale), shapes[:3]
                    run_on = arrays and arrays[:3]
                else:
                    fn, ins = kernel_fn(kern, blocks, causal, scale), shapes
                    run_on = arrays
                compiled = jax.jit(fn).lower(*ins).compile()
                rec["compile_s"] = round(time.perf_counter() - t0, 2)
                rec["temp_bytes"] = program_memory(compiled)["temp"]
                if run_on is not None:
                    jax.block_until_ready(compiled(*run_on))
                    times = []
                    for _ in range(args.reps):
                        t1 = time.perf_counter()
                        jax.block_until_ready(compiled(*run_on))
                        times.append(time.perf_counter() - t1)
                    rec["ms"] = round(min(times) * 1e3, 3)
                    rec["ms_median"] = round(sorted(times)[len(times) // 2]
                                             * 1e3, 3)
                rec["ok"] = True
            except Exception as e:      # Mosaic's refusal is the finding
                rec["ok"] = False
                rec["compile_s"] = round(time.perf_counter() - t0, 2)
                text = str(e)
                hit = re.search(r"Scoped allocation with size (\S+) and "
                                r"limit (\S+)", text)
                rec["error"] = hit.group(0) if hit else text[:300]
                if hit:
                    size = hit.group(1)
                    rec["vmem_mib"] = float(size[:-1]) / {
                        "K": 1024, "M": 1, "G": 1 / 1024}[size[-1]]
            out.write(json.dumps(rec) + "\n")
            out.flush()
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
