"""Multi-head attention with sequence/context parallelism.

The reference has NO attention ops and no intra-op sequence parallelism
(reference survey §5.7: nmt/rnn.h:23,58-63 only statically partitions the
LSTM grid). This op is the designed-in TPU upgrade: long-context scaling via

- **ring attention** (seq-dim sharding, degrees[1] > 1): each device keeps
  its Q block and passes K/V blocks around the ICI ring with
  `lax.ppermute` under `shard_map`, accumulating with an online-softmax
  (flash-style, fp32 running max/sum) — seq length scales linearly with
  devices, memory per device stays O(seq/p).
- **head parallelism** (model-dim sharding, degrees[2] > 1): QKV/output
  projections column/row-sharded Megatron-style; GSPMD inserts the psum.
- plain DP (degrees[0]) composes with both.

Self-attention: pass the same tensor as q, k, v.

`GatedAttention` is the block attention of a sparse language model:
grouped KV heads, a projection to `num_heads * head_dim` whatever the
hidden size, no bias, and what the model says of three more parts, a
per-head RMS norm on q and k, rotary embedding on the leading part of each
head, a sigmoid gate on the output (Qwen3-Next has all three, Nemotron-H
none: plain grouped-query attention). `LatentAttention` is the multi-head
latent attention of the
DeepSeek-V3 / GLM-4.7 line: queries and keys/values through low-rank
bottlenecks, one rotary key shared by every head, a value head of its own
width. All three go through one `attend` core, which picks the route: the
ring, jax's shipped flash kernel, query blocks through XLA, or the dense
scores, by whether the scores fit beside what the model keeps resident.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.initializers import (ConstantInitializer, DEFAULT_KERNEL_INIT,
                                 ZeroInitializer)
from ..core.op import Op, ParamDef
from ..parallel.pconfig import ParallelConfig


def _online_softmax_block(q, k, v, m_prev, num_prev, den_prev, mask):
    """One K/V block of flash-style attention. q:(b,h,sq,hd) k:(b,h,sk,hd)
    v:(b,h,sk,vd); m/num/den are fp32 running stats. mask:(sq,sk) additive
    (0 or -inf)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s * (1.0 / math.sqrt(q.shape[-1])) + mask
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (m == -inf): exp(-inf - -inf) -> use 0
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    scale = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
    num = num_prev * scale[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    den = den_prev * scale + jnp.sum(p, axis=-1)
    return m_new, num, den


def _group_heads(q, kv_heads: int):
    """(b, h, s, hd) -> (b, kv_heads, h // kv_heads, s, hd): the query
    heads that share one K/V head, side by side."""
    b, h, s, hd = q.shape
    return q.reshape(b, kv_heads, h // kv_heads, s, hd)


def _attention_local(q, k, v, causal, q_offset=0, k_offset=0):
    """Dense attention on local blocks (single shard or within-block).
    q (b, h, sq, hd); k (b, hk, sk, hd), v (b, hk, sk, vd) with hk dividing
    h: each K/V head serves h // hk query heads, without being repeated in
    memory."""
    b, h, sq, hd = q.shape
    hk, sk, vd = k.shape[1], k.shape[2], v.shape[3]
    if causal:
        qpos = q_offset + jnp.arange(sq)[:, None]
        kpos = k_offset + jnp.arange(sk)[None, :]
        mask = jnp.where(kpos <= qpos, 0.0, -jnp.inf).astype(jnp.float32)
    else:
        mask = jnp.zeros((sq, sk), jnp.float32)
    # the G query heads of a K/V head fold into the query axis
    g = h // hk
    qg = _group_heads(q, hk).reshape(b, hk, g * sq, hd)
    mask = jnp.tile(mask, (g, 1))
    m0 = jnp.full((b, hk, g * sq), -jnp.inf, jnp.float32)
    num0 = jnp.zeros((b, hk, g * sq, vd), jnp.float32)
    den0 = jnp.zeros((b, hk, g * sq), jnp.float32)
    m, num, den = _online_softmax_block(qg, k, v, m0, num0, den0, mask)
    out = num / jnp.maximum(den, 1e-20)[..., None]
    return out.reshape(b, h, sq, vd)


BLOCK_Q = 1024      # query rows a step of the blockwise route attends with


def _attention_blockwise(q, k, v, causal, block_q: int):
    """The dense route a block of queries at a time (a `lax.scan`, each
    step recomputed in the backward): the scores alive are block_q x sk a
    head, never sq x sk. XLA only; what the flash kernel does where it may
    not run. A causal block still multiplies against every key and masks:
    half its products are wasted, which the kernel's route avoids."""
    b, h, sq, hd = q.shape
    nb = sq // block_q
    qb = q.reshape(b, h, nb, block_q, hd).transpose(2, 0, 1, 3, 4)

    @jax.checkpoint
    def one(qi, i):
        return _attention_local(qi, k, v, causal, q_offset=i * block_q)

    def body(_, xs):
        qi, i = xs
        return None, one(qi, i).astype(q.dtype)

    _, out = lax.scan(body, None, (qb, jnp.arange(nb)))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, sq, v.shape[3])


def _resident_bytes(model) -> float:
    """What a training step keeps on a chip whatever the activations: the
    parameters, their gradient and the optimizer's slabs, all fp32."""
    ndev = max(getattr(getattr(model, "mesh", None), "size", 1), 1)
    params = sum(op.param_bytes() for op in model.ops)
    opt = getattr(model, "optimizer", None)
    slabs = len(opt.sparse_slab_names()) if opt is not None else 0
    return (2 + slabs) * params / ndev


def _hbm_bytes() -> float:
    stats = jax.local_devices()[0].memory_stats() or {}
    if stats.get("bytes_limit"):
        return float(stats["bytes_limit"])
    from ..search.cost_model import TPUSpec
    return TPUSpec().hbm_capacity_bytes


def _scores_fit(model, q, k) -> bool:
    """Whether the dense route's fp32 scores fit beside what is resident.
    The dense forward and backward keep about three score-sized arrays (s,
    p, dp); a tenth of the memory is left to everything else. Measured on
    v5e: XLA's fused dense attention is FASTER while the scores fit (377k
    vs 313k tok/s @ seq 2048), so dense stays the route wherever it can."""
    b, h, sq, sk = q.shape[0], q.shape[1], q.shape[2], k.shape[2]
    score_bytes = 4.0 * b * h * sq * sk
    return _resident_bytes(model) + 3.0 * score_bytes <= 0.9 * _hbm_bytes()


def _flash_gate(model, op_name, q, k) -> bool:
    """Route single-chip TPU attention through jax's shipped Pallas
    flash-attention kernel (jax.experimental.pallas.ops.tpu): O(seq)
    memory instead of the O(seq²) scores _attention_local materializes.
    Shares the common Pallas routing policy (TPU backend, opt-in, single
    chip, not host-offloaded — a Mosaic call can't run under compute_on)
    and adds the shapes/dtypes validated on hardware (bf16, head_dim %64,
    seq %512). Taken only where the scores do not fit (`_scores_fit`).
    Everything admitted here gets blocks from `_flash_blocks`, which walks
    a ladder down to 512: a sequence the gate admits always has a rung."""
    from .embedding import _pallas_gate
    if not _pallas_gate(model, op_name, True):
        return False
    hd, sq, sk = q.shape[3], q.shape[2], k.shape[2]
    if not (q.dtype == jnp.bfloat16 and hd % 64 == 0
            and sq % 512 == 0 and sk % 512 == 0):
        return False
    return not _scores_fit(model, q, k)


# What Mosaic grants one kernel on the v5e (its scoped VMEM; jax's flash
# kernel passes no limit of its own, so this is the budget its blocks fit).
FLASH_VMEM_BYTES = 16 * 2**20

# The three flash kernels, their blocks in `BlockSizes`' order: the field of
# each block, the most it gets, the sequence it tiles (0 the queries, 1 the
# keys), where its major block stands (itself, for a major), and the VMEM
# the kernel needs at head width w.
# The most: what the sweep on the v5e chose at (1, 20, 8192, 256) and (1,
# 16, 8192, 256), bf16, causal, within 5% of the fastest at a width of 128
# too (`benchmarks/flash_block_sweep.py`; the table is in PERF.md, PR 31).
# dq's key blocks stay at 128 on purpose: jax broadcasts `di` to (b, h, sq,
# block_k_major_dq) fp32 in HBM, which at 128 is the array `l` and `m`
# already need; 512 is 1 ms of 9.5 faster at the first shape, for 250 MB.
# The bytes: the pipeline's tiles twice over (bf16 q, k, v, o, do, dk, dv,
# dq; fp32 l, m, di at 128 lanes), the fp32 accumulators, and the fp32
# score tiles Mosaic keeps on its stack. That last term is fitted, from
# above, to what the compiler counted when it refused a block for a
# described v5e: the forward's unrolled minor steps do not share their
# score tiles, the backward kernels' do.
_FLASH_KERNELS = {
    "fwd": (("block_q", "block_k_major", "block_k"),
            (1024, 1024, 1024), (0, 1, 1), (0, 1, 1),
            lambda w, bq, bkm, bk:
            12 * bq * w + 3072 * bq + 8 * bkm * w + 8 * bq * bkm),
    "dkv": (("block_q_major_dkv", "block_q_dkv", "block_k_major_dkv",
             "block_k_dkv"),
            (1024, 512, 1024, 1024), (0, 0, 1, 1), (0, 0, 2, 2),
            lambda w, bqm, bq, bkm, bk:
            8 * bqm * w + 3072 * bqm + 24 * bkm * w + 7 * bq * bk),
    "dq": (("block_q_dq", "block_k_major_dq", "block_k_dq"),
           (2048, 128, 128), (0, 1, 1), (0, 1, 1),
           lambda w, bq, bkm, bk:
           16 * bq * w + 3072 * bq + 8 * bkm * w + 5 * bq * bk),
}


def _flash_width(hd, vd):
    """The one width jax's flash kernel gets for q, k and v: the wider of
    the two heads, and above one lane tile whole tiles (it refuses 192)."""
    w = max(hd, vd)
    return w if w <= 128 else -(-w // 128) * 128


def _flash_blocks(b, sq, sk, w):
    """The blocks jax's flash kernel runs with, from what `attend` can see
    and nothing else: (batch, query length, key length, padded head width)
    -> (BlockSizes, the scope name that says them). jax's default is 128
    everywhere, where the kernel is all grid-step overhead (0.3 us a step
    against 0.085 us of products; PERF.md, PR 31). One rule for every
    caller and each kernel of `_FLASH_KERNELS`: a major block is the most
    it may get, halved until it divides its sequence (the gate admits
    multiples of 512, so that ends there at the latest); a minor block is
    the most it may get or its major, the smaller; and while the kernel
    needs more than `FLASH_VMEM_BYTES` the wider major is halved (the key
    side on a tie). A wider head gets smaller blocks, a narrower one no
    larger: past the sweep's choice the causal diagonal wastes more than
    the saved grid steps give. `b` does not enter yet: the kernel's batch
    block stays 1."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes
    del b
    chosen, names = {"block_b": 1}, []
    for kernel, (fields, most, tiles, major, need) in _FLASH_KERNELS.items():
        blocks = list(most)
        for i in set(major):
            while (sq, sk)[tiles[i]] % blocks[i]:
                blocks[i] //= 2
        while True:
            blocks = [min(most[i], blocks[m]) for i, m in enumerate(major)]
            if need(w, *blocks) <= FLASH_VMEM_BYTES:
                break
            blocks[max(set(major), key=lambda i: (blocks[i], tiles[i]))] //= 2
        chosen.update(zip(fields, blocks))
        names.append("_".join([kernel, *map(str, blocks)]))
    return BlockSizes(**chosen), "flash_" + ".".join(names)


def attend(model, op_name, q, k, v, causal: bool):
    """softmax(q k^T / sqrt(hd)) v on one shard: q (b, h, s, hd); k (b, hk,
    s, hd), v (b, hk, s, vd), hk dividing h. The one place the attention
    ops pick their route. The value head may have a width of its own
    (latent attention): the dense and the blockwise route take it as it
    is; the flash kernel wants one width (whole lane tiles above 128), so
    what is narrower is padded with zeros (a zero feature adds nothing to
    a score, a zero value column gives a zero output column, which is cut
    off again) and the scale stays that of the true `hd`. At GLM-4.7's
    published 256 / 256 nothing is padded. The kernel's blocks come from
    `_flash_blocks(b, sq, sk, w)`, w the padded width, and the scope
    around the call names them. Returns q's dtype."""
    h, hk, sq = q.shape[1], k.shape[1], q.shape[2]
    hd, vd = q.shape[3], v.shape[3]
    if vd % 64 == 0 and _flash_gate(model, op_name, q, k):
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention)
        if hk != h:     # the kernel wants one K/V head a query head
            k, v = (jnp.repeat(t, h // hk, axis=1) for t in (k, v))
        w = _flash_width(hd, vd)

        def widen(t):
            return t if t.shape[3] == w else jnp.pad(
                t, ((0, 0),) * 3 + ((0, w - t.shape[3]),))

        blocks, scope = _flash_blocks(q.shape[0], sq, k.shape[2], w)
        with jax.named_scope(scope):
            out = flash_attention(widen(q), widen(k), widen(v),
                                  causal=causal, sm_scale=1.0 / math.sqrt(hd),
                                  block_sizes=blocks)
        return (out if vd == w else out[..., :vd]).astype(q.dtype)
    if not _scores_fit(model, q, k) and sq % BLOCK_Q == 0 and sq > BLOCK_Q:
        return _attention_blockwise(q, k, v, causal, BLOCK_Q)
    return _attention_local(q, k, v, causal).astype(q.dtype)


def ring_attention(q, k, v, axis_name: str, causal: bool):
    """Blockwise ring attention under shard_map: q/k/v are LOCAL blocks
    (b, h, s_local, hd); K/V rotate around `axis_name` via ppermute."""
    p = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, sl, hd = q.shape

    m = jnp.full((b, h, sl), -jnp.inf, jnp.float32)
    num = jnp.zeros((b, h, sl, hd), jnp.float32)
    den = jnp.zeros((b, h, sl), jnp.float32)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def body(r, carry):
        m, num, den, kr, vr = carry
        # the K/V block currently held came from device (idx - r) mod p
        src = (idx - r) % p
        if causal:
            qpos = idx * sl + jnp.arange(sl)[:, None]
            kpos = src * sl + jnp.arange(sl)[None, :]
            mask = jnp.where(kpos <= qpos, 0.0, -jnp.inf).astype(jnp.float32)
        else:
            mask = jnp.zeros((sl, sl), jnp.float32)
        m, num, den = _online_softmax_block(q, kr, vr, m, num, den, mask)
        kr = lax.ppermute(kr, axis_name, perm)
        vr = lax.ppermute(vr, axis_name, perm)
        return m, num, den, kr, vr

    m, num, den, _, _ = lax.fori_loop(0, p, body, (m, num, den, k, v))
    return (num / jnp.maximum(den, 1e-20)[..., None]).astype(q.dtype)


class MultiHeadAttention(Op):
    type_name = "MultiHeadAttention"

    def __init__(self, model, q, k, v, embed_dim: int, num_heads: int,
                 causal: bool = False, name: Optional[str] = None):
        if q.num_dims != 3:
            raise ValueError("attention expects (batch, seq, dim) inputs")
        if embed_dim % num_heads != 0:
            raise ValueError("embed_dim must divide num_heads")
        inputs = [q] if (k is q and v is q) else [q, k, v]
        super().__init__(model, inputs, name)
        self.self_attention = len(inputs) == 1
        self.embed_dim = int(embed_dim)
        self.num_heads = int(num_heads)
        self.head_dim = self.embed_dim // self.num_heads
        self.causal = bool(causal)
        b, s, _ = q.shape
        self.outputs = [self._make_output((b, s, self.embed_dim))]

    def param_defs(self) -> Dict[str, ParamDef]:
        dq = self.inputs[0].shape[-1]
        dkv = self.inputs[-1].shape[-1]
        e = self.embed_dim
        init = DEFAULT_KERNEL_INIT()
        return {
            "wq": ParamDef((dq, e), jnp.float32, init),
            "wk": ParamDef((dkv, e), jnp.float32, init),
            "wv": ParamDef((dkv, e), jnp.float32, init),
            "wo": ParamDef((e, e), jnp.float32, init),
            "bo": ParamDef((e,), jnp.float32, ZeroInitializer()),
        }

    def _split_heads(self, x):
        b, s, _ = x.shape
        return x.reshape(b, s, self.num_heads, self.head_dim).transpose(
            0, 2, 1, 3)

    def apply(self, params, xs, *, training=False, rng=None):
        q_in = xs[0]
        k_in = xs[0] if self.self_attention else xs[1]
        v_in = xs[0] if self.self_attention else xs[2]
        cdt = self.model.compute_dtype
        pe = jnp.float32

        def proj(x, w):
            return jnp.einsum("bsd,de->bse", x.astype(cdt), w.astype(cdt),
                              preferred_element_type=pe).astype(cdt)

        q = self._split_heads(proj(q_in, params["wq"]))
        k = self._split_heads(proj(k_in, params["wk"]))
        v = self._split_heads(proj(v_in, params["wv"]))

        pc = getattr(self, "_compiled_pc", None)
        seq_axes = ()
        if pc is not None and len(pc.degrees) >= 2 and pc.degrees[1] > 1:
            seq_axes = getattr(self, "_seq_axes", ())

        if seq_axes:
            # ring attention over the seq-dim mesh axes
            mesh = self.model.mesh
            from jax.sharding import PartitionSpec as P
            axis = seq_axes if len(seq_axes) > 1 else seq_axes[0]
            spec = P(None, None, axis, None)
            fn = partial(ring_attention,
                         axis_name=seq_axes if len(seq_axes) > 1 else seq_axes[0],
                         causal=self.causal)
            from ..parallel.mesh import smap
            attn = smap(fn, mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)
        else:
            attn = attend(self.model, self.name, q, k, v, self.causal)

        b, h, s, hd = attn.shape
        merged = attn.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
        out = jnp.einsum("bse,ef->bsf", merged.astype(cdt),
                         params["wo"].astype(cdt),
                         preferred_element_type=pe) + params["bo"]
        return [out.astype(q_in.dtype)]

    def candidate_parallel_configs(self, num_devices, feasible_degrees):
        out = []
        b, s, _ = self.outputs[0].shape
        for ds in feasible_degrees:
            if ds <= num_devices:
                out.append(ParallelConfig((ds, 1, 1)))          # DP
        for dseq in feasible_degrees:
            if 1 < dseq <= num_devices and s % dseq == 0:
                out.append(ParallelConfig((1, dseq, 1)))        # ring SP
        for dh in feasible_degrees:
            if 1 < dh <= num_devices and self.num_heads % dh == 0:
                out.append(ParallelConfig((1, 1, dh)))          # head TP
        return out

    def param_axes(self, pc: ParallelConfig, out_axes,
                   raw_pc=None):
        ch = out_axes[2] if len(out_axes) >= 3 else ()
        # head TP: qkv projections column-sharded, wo row-sharded (psum by
        # GSPMD); bo replicated-ish (sharded on ch like bias)
        return {"wq": ((), ch), "wk": ((), ch), "wv": ((), ch),
                "wo": (ch, ()), "bo": ((),)}

    def param_shard_shapes(self, pc: ParallelConfig, ndev=None):
        dc = pc.degrees[2] if len(pc.degrees) > 2 else 1
        shapes = {n_: list(d.shape) for n_, d in self.param_defs().items()}
        if dc > 1:
            for n_ in ("wq", "wk", "wv"):
                shapes[n_][1] = max(shapes[n_][1] // dc, 1)
            shapes["wo"][0] = max(shapes["wo"][0] // dc, 1)
        return {n_: tuple(v) for n_, v in shapes.items()}

    def flops_per_sample(self) -> float:
        _, s, _ = self.outputs[0].shape
        e = self.embed_dim
        # per sample: 4 projections (2*s*e*e each) + QK^T and PV (2*s^2*e each)
        return 8.0 * s * e * e + 4.0 * s * s * e

    def mxu_utilization_factor(self) -> float:
        # measured (r4 sweep, b8 s2048 d1024 causal training): ~13% of
        # bf16 peak vs the gemm-calibrated 55% — flash attention pays
        # block-wise softmax rescaling/recomputation, the causal mask
        # discards half the score tiles' work, and small batch*heads
        # grids underfill the chip
        return 0.25


def rotary_tables(seq: int, rotary_dim: int, theta: float):
    """cos, sin (seq, rotary_dim) fp32 for positions 0..seq-1: the
    frequencies theta^(-2i/rotary_dim), laid out twice (rotate-half)."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                           / rotary_dim))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, cos, sin):
    """Rotate-half rotary embedding on the first cos.shape[-1] features of
    x (b, s, h, hd); the rest pass through."""
    rd = cos.shape[-1]
    xr, rest = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., :rd // 2], xr[..., rd // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    xr = xr * cos[None, :, None, :] + rot * sin[None, :, None, :]
    return jnp.concatenate([xr, rest], axis=-1)


class GatedAttention(Op):
    """Causal grouped-query self-attention: K/V have `num_kv_heads` heads,
    each serving num_heads / num_kv_heads query heads. No bias anywhere.
    As Qwen3-Next's full-attention layers have it (the defaults): `wq`
    makes, per query head, a query and a gate (head-major, [query | gate]
    inside a head); q and k take a per-head RMS norm (scale 1 + w) and
    rotary embedding on their first `rotary_dim` features; the attended
    values are multiplied by sigmoid(gate) before the output projection.
    The model says which of the three it has: `gate=False` (`wq` makes the
    queries alone), `qk_norm=False` (no `q_norm`, `k_norm`), `rotary_dim=0`
    (no position embedding). With none of them this is the plain
    grouped-query attention of Nemotron-H, whose state-space layers carry
    the position."""

    type_name = "GatedAttention"
    recompute = True     # the backward recomputes the block's insides

    def __init__(self, model, x, num_heads: int, num_kv_heads: int,
                 head_dim: int, rotary_dim: int, rope_theta: float = 1e7,
                 eps: float = 1e-6, kernel_initializer=None,
                 name: Optional[str] = None, gate: bool = True,
                 qk_norm: bool = True):
        if x.num_dims != 3:
            raise ValueError("attention expects (batch, seq, dim) inputs")
        if num_heads % num_kv_heads != 0:
            raise ValueError("num_kv_heads must divide num_heads")
        if rotary_dim % 2 or rotary_dim > head_dim:
            raise ValueError("rotary_dim must be even and <= head_dim")
        super().__init__(model, [x], name)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.rotary_dim = int(rotary_dim)
        self.rope_theta = float(rope_theta)
        self.eps = float(eps)
        self.gate, self.qk_norm = bool(gate), bool(qk_norm)
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT()
        self.outputs = [self._make_output(x.shape, x.dtype)]

    def param_defs(self) -> Dict[str, ParamDef]:
        d = self.inputs[0].shape[-1]
        h, hk, hd = self.num_heads, self.num_kv_heads, self.head_dim
        init = self.kernel_initializer
        defs = {
            "wq": ParamDef((d, h * hd * (2 if self.gate else 1)),
                           jnp.float32, init),
            "wk": ParamDef((d, hk * hd), jnp.float32, init),
            "wv": ParamDef((d, hk * hd), jnp.float32, init),
            "q_norm": ParamDef((hd,), jnp.float32, ZeroInitializer()),
            "k_norm": ParamDef((hd,), jnp.float32, ZeroInitializer()),
            "wo": ParamDef((h * hd, d), jnp.float32, init),
        }
        if not self.qk_norm:
            del defs["q_norm"], defs["k_norm"]
        return defs

    def apply(self, params, xs, *, training=False, rng=None):
        from .norm import rms_norm
        (x,) = xs
        b, s, _ = x.shape
        h, hk, hd = self.num_heads, self.num_kv_heads, self.head_dim
        cdt = self.model.compute_dtype
        xc = x.astype(cdt)

        def proj(w):
            return jnp.dot(xc, w.astype(cdt),
                           preferred_element_type=jnp.float32)

        with jax.named_scope("qkv_proj"):
            q = proj(params["wq"]).reshape(b, s, h, -1)
            q, gate = q[..., :hd], q[..., hd:]      # no gate: an empty slice
            k = proj(params["wk"]).reshape(b, s, hk, hd)
            v = proj(params["wv"]).reshape(b, s, hk, hd).astype(cdt)
        if self.qk_norm or self.rotary_dim:
            with jax.named_scope("qk_norm_rope"):
                if self.qk_norm:
                    q = rms_norm(q, params["q_norm"], self.eps, True)
                    k = rms_norm(k, params["k_norm"], self.eps, True)
                if self.rotary_dim:
                    cos, sin = rotary_tables(s, self.rotary_dim,
                                             self.rope_theta)
                    q, k = (apply_rotary(t, cos, sin) for t in (q, k))
        q, k = q.astype(cdt), k.astype(cdt)
        with jax.named_scope("attend"):
            attn = attend(self.model, self.name, q.transpose(0, 2, 1, 3),
                          k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                          True)
            attn = attn.transpose(0, 2, 1, 3)            # (b, s, h, hd)
        if self.gate:
            with jax.named_scope("gate"):
                attn = (attn.astype(jnp.float32)
                        * jax.nn.sigmoid(gate)).astype(cdt)
        with jax.named_scope("out_proj"):
            out = jnp.dot(attn.reshape(b, s, h * hd),
                          params["wo"].astype(cdt),
                          preferred_element_type=jnp.float32)
        return [out.astype(x.dtype)]

    def flops_per_sample(self) -> float:
        _, s, d = self.outputs[0].shape
        h, hk, hd = self.num_heads, self.num_kv_heads, self.head_dim
        proj = 2.0 * s * d * ((3 if self.gate else 2) * h * hd
                              + 2 * hk * hd)
        return proj + 2.0 * s * s * h * hd      # the causal half of 4 s^2

    def mxu_utilization_factor(self) -> float:
        return 0.25


class LatentAttention(Op):
    """Causal multi-head latent attention (MLA) in its expanded, training
    form, as DeepSeek-V3 and GLM-4.7 have it. No bias anywhere; RMS norms
    scale by `w` (init 1).

        c_q = RMSNorm(x W_qa)                     (q_rank)
        q   = c_q W_qb          a head: [q_nope | q_rope]
        [c_kv | k_rope] = x W_kva                 (kv_rank | rope_dim)
        c_kv W_kvb              a head: [k_nope | v],  c_kv = RMSNorm(c_kv)
        q_h = [q_nope_h | R(q_rope_h)],  k_h = [k_nope_h | R(k_rope)]

    The rotary key is ONE head that every head shares; the value head has
    its own width `v_dim` (`attend` takes it as it is, or pads for the
    flash kernel). The absorbed form, which attends in the latent space,
    is a decoder's: training gains nothing from it."""

    type_name = "LatentAttention"
    recompute = True     # the backward recomputes the block's insides

    def __init__(self, model, x, num_heads: int, q_rank: int, kv_rank: int,
                 nope_dim: int, rope_dim: int, v_dim: int,
                 rope_theta: float = 1e6, eps: float = 1e-5,
                 kernel_initializer=None, name: Optional[str] = None):
        if x.num_dims != 3:
            raise ValueError("attention expects (batch, seq, dim) inputs")
        if rope_dim % 2:
            raise ValueError("rope_dim must be even")
        super().__init__(model, [x], name)
        self.num_heads = int(num_heads)
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.nope_dim, self.rope_dim = int(nope_dim), int(rope_dim)
        self.v_dim = int(v_dim)
        self.rope_theta, self.eps = float(rope_theta), float(eps)
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT()
        self.outputs = [self._make_output(x.shape, x.dtype)]

    def param_defs(self) -> Dict[str, ParamDef]:
        d, h = self.inputs[0].shape[-1], self.num_heads
        init, one, f32 = self.kernel_initializer, ConstantInitializer(1.0), \
            jnp.float32
        return {
            "wq_a": ParamDef((d, self.q_rank), f32, init),
            "q_norm": ParamDef((self.q_rank,), f32, one),
            "wq_b": ParamDef(
                (self.q_rank, h * (self.nope_dim + self.rope_dim)), f32, init),
            "wkv_a": ParamDef((d, self.kv_rank + self.rope_dim), f32, init),
            "kv_norm": ParamDef((self.kv_rank,), f32, one),
            "wkv_b": ParamDef(
                (self.kv_rank, h * (self.nope_dim + self.v_dim)), f32, init),
            "wo": ParamDef((h * self.v_dim, d), f32, init),
        }

    def apply(self, params, xs, *, training=False, rng=None):
        from .norm import rms_norm
        (x,) = xs
        b, s, _ = x.shape
        h, nope, rope = self.num_heads, self.nope_dim, self.rope_dim
        cdt = self.model.compute_dtype

        def mm(a, w):
            return jnp.dot(a.astype(cdt), params[w].astype(cdt),
                           preferred_element_type=jnp.float32)

        with jax.named_scope("q_proj"):
            c_q = rms_norm(mm(x, "wq_a"), params["q_norm"], self.eps, False)
            q = mm(c_q, "wq_b").reshape(b, s, h, nope + rope)
        with jax.named_scope("kv_proj"):
            ckr = mm(x, "wkv_a")
            c_kv = rms_norm(ckr[..., :self.kv_rank], params["kv_norm"],
                            self.eps, False)
            kv = mm(c_kv, "wkv_b").reshape(b, s, h, nope + self.v_dim)
        with jax.named_scope("rope"):
            cos, sin = rotary_tables(s, rope, self.rope_theta)
            q = jnp.concatenate(
                [q[..., :nope], apply_rotary(q[..., nope:], cos, sin)],
                axis=-1).astype(cdt)
            k_rope = apply_rotary(ckr[:, :, None, self.kv_rank:], cos, sin)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, h, rope))],
                axis=-1).astype(cdt)
            v = kv[..., nope:].astype(cdt)
        with jax.named_scope("attend"):
            attn = attend(self.model, self.name, q.transpose(0, 2, 1, 3),
                          k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                          True).transpose(0, 2, 1, 3)    # (b, s, h, v_dim)
        with jax.named_scope("out_proj"):
            out = mm(attn.reshape(b, s, h * self.v_dim), "wo")
        return [out.astype(x.dtype)]

    def flops_per_sample(self) -> float:
        _, s, d = self.outputs[0].shape
        h, qk = self.num_heads, self.nope_dim + self.rope_dim
        proj = 2.0 * s * (d * self.q_rank + self.q_rank * h * qk
                          + d * (self.kv_rank + self.rope_dim)
                          + self.kv_rank * h * (self.nope_dim + self.v_dim)
                          + h * self.v_dim * d)
        return proj + s * s * h * (qk + self.v_dim)   # the causal half

    def mxu_utilization_factor(self) -> float:
        return 0.25
