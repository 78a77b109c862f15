"""Gated delta net: the linear-attention mixer of Qwen3-Next.

Per value head the layer keeps a state S in R^(dk x dv) and, at every
position t, decays it, corrects it towards the new key/value pair by the
delta rule, and reads it with the query:

    S' = exp(g_t) S_(t-1)
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

with g_t = -exp(A_log) softplus(a_t + dt_bias) <= 0 and beta_t =
sigmoid(b_t). Around it: one fused projection to q, k, v and the output
gate z, one to b and a; a causal depthwise convolution and SiLU over
(q, k, v); q and k L2-normalised per head, q scaled by dk^-0.5; each key
head serving num_v_heads / num_k_heads value heads; the output normalised
per head (RMS, plain scale), gated by SiLU(z) and projected back.

`gated_delta_rule_chunked` computes the recurrence a chunk of 64 positions
at a time (the WY / UT form): inside a chunk the u_t solve one unit
lower-triangular system, (I + M) U = V_beta - (K_beta * Gamma) S_0, done
for every chunk of a span at once (scope `prep`); only the hand-over of S
from chunk to chunk is sequential (scope `hand_over`). On one TPU with
tile-aligned heads it is one Pallas kernel a span with S resident in VMEM
and that kernel's reverse for the backward
(`ops/pallas/delta_kernel.py`: `delta_hand_over_fwd`, `_bwd`), which also
form the decayed copies of q and k and the masked scores on the tiles they
load; everywhere else those are arrays and the hand-over a `lax.scan` that
autodiff differentiates. A long sequence is walked
a span of `SPAN` positions at a time, each span recomputed in the
backward, so what the per-chunk matrices and the kernel's entering states
take is a span's and not the sequence's. The state and every accumulation
are fp32; the operands of the large products are in the compute dtype.
`gated_delta_rule_stepwise` is the recurrence as written above, for tests.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.initializers import (ConstantInitializer, DEFAULT_KERNEL_INIT,
                                 Initializer, UniformInitializer)
from ..core.op import Op, ParamDef
from .norm import rms_norm
from .pallas import delta_kernel

CHUNK = 64
SPAN = 1024     # positions whose chunks are worked on together


class LogUniformInitializer(Initializer):
    """log of a uniform draw in [lo, hi): A_log, so that the decay rates
    exp(A_log) of the heads spread evenly over [lo, hi)."""

    def __init__(self, lo: float = 1.0, hi: float = 16.0):
        self.lo, self.hi = float(lo), float(hi)

    def __call__(self, key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(key, shape, dtype, self.lo,
                                          self.hi))


def l2_normalize(x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True)
                           + eps)


def causal_depthwise_conv(x, w, bias=None):
    """x (b, s, c), w (c, width): y_t = sum_j w[:, j] x_(t - width + 1 + j)
    (+ bias (c,), where the model has one), positions before the sequence
    read as zero. fp32 out."""
    width = w.shape[1]
    s = x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    w32 = w.astype(jnp.float32)
    y = sum(xp[:, j:j + s, :] * w32[None, None, :, j] for j in range(width))
    return y if bias is None else y + bias.astype(jnp.float32)


def gated_delta_rule_stepwise(q, k, v, g, beta):
    """The recurrence, one position a step. q, k (b, s, h, dk); v
    (b, s, h, dv); g, beta (b, s, h). All fp32. Returns o (b, s, h, dv)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt))
        S = S + kt[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    xs = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0)
               for t in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)


def gated_delta_rule_chunked(q, k, v, g, beta, chunk: int = CHUNK,
                             compute_dtype=jnp.float32, span: int = SPAN,
                             resident: bool = False):
    """The same result, a chunk at a time. Shapes as the stepwise form; the
    sequence is padded to a whole number of chunks (of spans, where it is
    longer than one) with steps that leave the state as it is (k = v = 0,
    g = 0). `resident`: the hand-over as the Pallas kernel
    (`delta_kernel.resident_hand_over_ok` says where). Returns fp32
    (b, s, h, dv)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    span = -(-min(span, s) // chunk) * chunk
    pad = (-s) % span
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))

    @jax.checkpoint
    def one_span(S, xs):
        o, S = _delta_rule_span(*xs, S, chunk, compute_dtype, resident)
        return S, o

    xs = tuple(jnp.moveaxis(t.reshape((b, -1, span) + t.shape[2:]), 1, 0)
               for t in (q, k, v, g, beta))
    _, o = lax.scan(one_span, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1).reshape(b, s + pad, h, dv)[:, :s]


def _unit_lower_inverse(m):
    """(I + M)^-1 for strictly lower-triangular M (..., C, C), fp32. M is
    nilpotent (M^C = 0), so the series sum of (-M)^i ends, and it factors
    as (I - M)(I + M^2)(I + M^4)...: log2(C) squarings and products, all
    large batched matmuls, where a triangular solve walks the rows one
    after the other (on the v5e 11 ms a call against under 1 ms)."""
    c = m.shape[-1]
    eye = jnp.eye(c, dtype=m.dtype)

    def mm(a, b):
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)

    inv, power, reach = eye - m, m, 2       # inv is exact up to M^(reach-1)
    while reach < c:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        reach *= 2
    return inv


def _delta_rule_span(q, k, v, g, beta, S0, chunk, compute_dtype,
                     resident=False):
    """One span (a whole number of chunks) from state S0 (b, h, dk, dv):
    (o (b, s, h, dv) fp32, the state after it)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    n = s // chunk
    cdt, f32 = compute_dtype, jnp.float32

    def chunks(t):      # (b, S, h, ...) -> (b, h, n, chunk, ...)
        t = jnp.moveaxis(t, 2, 1)
        return t.reshape((b, h, n, chunk) + t.shape[3:])

    def mm(x, y, spec):
        return jnp.einsum(spec, x.astype(cdt), y.astype(cdt),
                          preferred_element_type=f32)

    with jax.named_scope("prep"):
        q, k, v = chunks(q), chunks(k), chunks(v)
        g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
        gc = jnp.cumsum(g, axis=-1)                     # (b, h, n, C)
        diff = gc[..., :, None] - gc[..., None, :]      # gc_i - gc_j
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        # exp only where i >= j: above the diagonal the difference is
        # positive and may overflow, and a masked inf would still poison
        # the gradient
        decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
        kb = k.astype(f32) * beta[..., None]
        vb = v.astype(f32) * beta[..., None]
        strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        m = jnp.where(strict, mm(kb, k, "bhnik,bhnjk->bhnij") * decay, 0.0)
        # (I + M) X = [V_beta | K_beta * Gamma]: one unit lower-triangular
        # system a chunk, every chunk at once, in fp32
        rhs = jnp.concatenate([vb, kb * jnp.exp(gc)[..., None]], axis=-1)
        sol = jnp.einsum("bhnij,bhnjv->bhniv", _unit_lower_inverse(m), rhs,
                         precision=lax.Precision.HIGHEST)
        value, k_cumdecay = sol[..., :dv], sol[..., dv:]

    if resident:
        # the kernel forms q exp(gc), k exp(gc_last - gc) and the masked,
        # decayed scores on the tiles it loads; heads and batch are one
        # axis to it
        with jax.named_scope("hand_over"):
            def flat(t):
                return t.reshape((b * h,) + t.shape[2:])
            o, S = delta_kernel.hand_over(
                *(flat(t.astype(cdt)) for t in (q, k, k_cumdecay)),
                flat(value), flat(gc)[:, :, None, :], flat(S0))
            o, S = o.reshape(b, h, n * chunk, dv), S.reshape(b, h, dk, dv)
        return jnp.moveaxis(o, 1, 2), S

    with jax.named_scope("prep"):
        qk = mm(q, k, "bhnik,bhnjk->bhnij") * decay     # diagonal included
        q_dec = q.astype(f32) * jnp.exp(gc)[..., None]
        k_dec = k.astype(f32) * jnp.exp(gc[..., -1:] - gc)[..., None]
        last = jnp.exp(gc[..., -1])                     # (b, h, n)

    def hand_over(S, x):
        qk_i, q_i, k_i, kcd_i, val_i, last_i = x
        v_new = val_i - mm(kcd_i, S, "bhck,bhkv->bhcv")
        o_i = (mm(q_i, S, "bhck,bhkv->bhcv")
               + mm(qk_i, v_new, "bhij,bhjv->bhiv"))
        S = (S * last_i[..., None, None]
             + mm(k_i, v_new, "bhck,bhcv->bhkv"))
        return S, o_i

    with jax.named_scope("hand_over"):
        xs = tuple(jnp.moveaxis(t, 2, 0)
                   for t in (qk, q_dec, k_dec, k_cumdecay, value, last))
        S, o = lax.scan(hand_over, S0, xs)
        o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, dv)
    return jnp.moveaxis(o, 1, 2), S


class GatedDeltaNet(Op):
    """x (batch, seq, dim) -> the same shape. The fused projections lay
    their columns out in blocks: `w_qkvz` = [q | k | v | z] (q, k:
    num_k_heads x head_k_dim; v, z: num_v_heads x head_v_dim), `w_ba` =
    [b | a] (num_v_heads each). No bias anywhere."""

    type_name = "GatedDeltaNet"
    recompute = True     # the backward recomputes the block's insides

    def __init__(self, model, x, num_k_heads: int, num_v_heads: int,
                 head_k_dim: int, head_v_dim: int, conv_width: int = 4,
                 eps: float = 1e-6, kernel_initializer=None,
                 name: Optional[str] = None):
        if x.num_dims != 3:
            raise ValueError("the delta net expects (batch, seq, dim)")
        if num_v_heads % num_k_heads != 0:
            raise ValueError("num_k_heads must divide num_v_heads")
        super().__init__(model, [x], name)
        self.hk, self.hv = int(num_k_heads), int(num_v_heads)
        self.dk, self.dv = int(head_k_dim), int(head_v_dim)
        self.conv_width = int(conv_width)
        self.eps = float(eps)
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT()
        self.outputs = [self._make_output(x.shape, x.dtype)]

    @property
    def key_dim(self) -> int:
        return self.hk * self.dk

    @property
    def value_dim(self) -> int:
        return self.hv * self.dv

    def param_defs(self) -> Dict[str, ParamDef]:
        d = self.inputs[0].shape[-1]
        init, f32 = self.kernel_initializer, jnp.float32
        conv_dim = 2 * self.key_dim + self.value_dim
        bound = self.conv_width ** -0.5      # torch's depthwise default
        return {
            "w_qkvz": ParamDef((d, 2 * self.key_dim + 2 * self.value_dim),
                               f32, init),
            "w_ba": ParamDef((d, 2 * self.hv), f32, init),
            "conv": ParamDef((conv_dim, self.conv_width), f32,
                             UniformInitializer(0, -bound, bound)),
            "A_log": ParamDef((self.hv,), f32, LogUniformInitializer()),
            "dt_bias": ParamDef((self.hv,), f32, ConstantInitializer(1.0)),
            "norm": ParamDef((self.dv,), f32, ConstantInitializer(1.0)),
            "w_out": ParamDef((self.value_dim, d), f32, init),
        }

    def apply(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        b, s, _ = x.shape
        hk, hv, dk, dv = self.hk, self.hv, self.dk, self.dv
        kd, vd = self.key_dim, self.value_dim
        cdt, f32 = self.model.compute_dtype, jnp.float32
        xc = x.astype(cdt)
        with jax.named_scope("proj"):
            qkvz = jnp.dot(xc, params["w_qkvz"].astype(cdt),
                           preferred_element_type=f32)
            ba = jnp.dot(xc, params["w_ba"].astype(cdt),
                         preferred_element_type=f32)
        qkv, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
        with jax.named_scope("conv"):
            qkv = jax.nn.silu(causal_depthwise_conv(qkv, params["conv"]))
        with jax.named_scope("scan"):
            with jax.named_scope("prep"):
                rep = hv // hk
                q = (l2_normalize(qkv[..., :kd].reshape(b, s, hk, dk))
                     * dk ** -0.5)
                k = l2_normalize(qkv[..., kd:2 * kd].reshape(b, s, hk, dk))
                q, k = (jnp.repeat(t.astype(cdt), rep, axis=2)
                        for t in (q, k))
                v = qkv[..., 2 * kd:].reshape(b, s, hv, dv).astype(cdt)
                beta = jax.nn.sigmoid(ba[..., :hv])
                g = (-jnp.exp(params["A_log"].astype(f32))
                     * jax.nn.softplus(ba[..., hv:] + params["dt_bias"]))
            o = gated_delta_rule_chunked(
                q, k, v, g, beta, CHUNK, cdt,
                resident=delta_kernel.resident_hand_over_ok(
                    self.model, CHUNK, dk, dv))
        with jax.named_scope("gate_norm"):
            o = (rms_norm(o, params["norm"], self.eps, False)
                 * jax.nn.silu(z.reshape(b, s, hv, dv)))
        out = jnp.dot(o.reshape(b, s, vd).astype(cdt),
                      params["w_out"].astype(cdt),
                      preferred_element_type=f32)
        return [out.astype(x.dtype)]

    def flops_per_sample(self) -> float:
        _, s, d = self.outputs[0].shape
        proj = 2.0 * s * d * (2 * self.key_dim + 3 * self.value_dim
                              + 2 * self.hv)
        # a position of a head: two reads and one rank-one write of S
        return proj + 6.0 * s * self.hv * self.dk * self.dv

    def sequential_steps(self, pc=None, vmem_bytes: int = 0) -> int:
        return -(-self.outputs[0].shape[1] // CHUNK)

    def scan_param_stream_bytes(self) -> int:
        # what the `lax.scan` streams every chunk is no weight but the
        # state: three reads and a write of (hv, dk, dv) fp32 a sample
        return 4 * self.outputs[0].shape[0] * self.hv * self.dk * self.dv * 4

    def scan_weights_resident(self, pc=None, vmem_bytes: int = 0) -> bool:
        """Whether the state stays in VMEM from chunk to chunk (the Pallas
        hand-over). With `pc`, for that candidate on the TPU target: one
        part, since a direct Pallas call cannot run under GSPMD."""
        if pc is None:
            return delta_kernel.resident_hand_over_ok(
                self.model, CHUNK, self.dk, self.dv)
        itemsize = jnp.dtype(self.model.compute_dtype).itemsize
        return pc.num_parts == 1 and delta_kernel.shapes_fit(
            CHUNK, self.dk, self.dv, itemsize)
