"""Mamba-2: the state-space mixer of a hybrid language model (Nemotron-H).

Per head h (H heads of P features, N states, a group of H / G heads sharing
one B and one C) the layer keeps a state S in R^(P x N) and, at every
position t, decays it by a SCALAR, adds the outer product of the input and
B, and reads it with C:

    a_t = exp(dt_t A),  A = -exp(A_log) < 0,  dt_t = softplus(dt_t + dt_bias)
    S_t = a_t S_(t-1) + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

Around it: one fused projection `w_in` = [z | x B C | dt] (H P | H P + 2 G N
| H); a causal depthwise convolution WITH a bias and SiLU over (x, B, C);
the output gated by SiLU(z) and THEN normalised (RMS over each group of
H P / G features, scale `w`); the projection back. No bias but the conv's.

`ssd_chunked` computes the recurrence a chunk of `chunk` positions at a
time (the state-space duality of the Mamba-2 paper). Inside a chunk the
result is a masked product, y = ((C B^T) * L * dt) x with L_ts = exp(sum of
dt_r A over s < r <= t) for t >= s; a chunk's own contribution to the state
is s_c = sum_s exp(sum over s < r <= end) dt_s x_s B_s^T; the state handed
from chunk to chunk obeys S_c = a_c S_(c-1) + s_c with a_c ONE number a
head, so the states entering all the chunks are one product of a (chunks x
chunks) decay matrix with the s_c (`states_entering`), where the delta rule
of `ops/delta_net.py`, whose hand-over multiplies by a matrix, must walk
them one after the other; position t then reads C_t S exp(sum from the
chunk's start to t). Every large piece is a batched matmul. The state, the
decays and every accumulation are fp32; the operands of the large products
are in the compute dtype. The backward is autodiff's. `ssd_stepwise` is
the recurrence as written above, for tests.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.initializers import (ConstantInitializer, DEFAULT_KERNEL_INIT,
                                 Initializer, UniformInitializer,
                                 ZeroInitializer)
from ..core.op import Op, ParamDef
from .delta_net import LogUniformInitializer, causal_depthwise_conv
from .norm import rms_norm


class InverseSoftplusInitializer(Initializer):
    """dt_bias: the inverse softplus of a log-uniform draw in [lo, hi)
    floored at `floor`, so that softplus(dt_bias) is that draw (Mamba's
    step sizes at initialisation)."""

    def __init__(self, lo: float = 1e-3, hi: float = 0.1,
                 floor: float = 1e-4):
        self.lo, self.hi, self.floor = float(lo), float(hi), float(floor)

    def __call__(self, key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(self.hi) - math.log(self.lo))
                     + math.log(self.lo))
        dt = jnp.maximum(dt, self.floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def gated_rms_norm(y, z, w, eps: float, group_size: int):
    """RMSNorm_g(y * silu(z)) * w: the gate BEFORE the norm
    (`norm_before_gate` false, the family's), the mean square over each
    group of `group_size` features. fp32."""
    return rms_norm(y * jax.nn.silu(z), w, eps, False, group_size)


def ssd_stepwise(x, dt, A, B, C):
    """The recurrence, one position a step. x (b, s, h, p); dt (b, s, h),
    already positive; A (h,), negative; B, C (b, s, g, n), g dividing h:
    head j reads group j // (h / g). All fp32. Returns y (b, s, h, p),
    without the skip."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]

    def step(S, xs):
        xt, dtt, Bt, Ct = xs
        Bt, Ct = (jnp.repeat(t, h // g, axis=1) for t in (Bt, Ct))
        S = (S * jnp.exp(dtt * A)[..., None, None]
             + (dtt[..., None] * xt)[..., :, None] * Bt[..., None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, Ct)

    xs = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0)
               for t in (x, dt, B, C))
    _, y = lax.scan(step, jnp.zeros((b, h, p, n), jnp.float32), xs)
    return jnp.moveaxis(y, 0, 1)


def _decay_from(cum):
    """cum (..., L): the running sum of the log decays. -> (..., L, L),
    exp(cum_t - cum_s) where t >= s and 0 above the diagonal. exp only
    where t >= s: above it the difference is positive and may overflow,
    and a masked inf would still poison the gradient."""
    size = cum.shape[-1]
    tri = jnp.tril(jnp.ones((size, size), bool))
    diff = cum[..., :, None] - cum[..., None, :]
    return jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)


def states_entering(log_a, s):
    """The state each chunk starts from. log_a (b, h, c): the log of the
    decay over each whole chunk; s (b, c, h, p, n) fp32: each chunk's own
    contribution. S_c = a_c S_(c-1) + s_c from S = 0 gives, entering chunk
    c, sum over c' < c of exp(sum of log_a over c' < r < c) s_c': one
    (c x c) strictly lower-triangular decay matrix a head against the
    contributions, in fp32. On the v5e at Nemotron-3-Nano's sizes (64
    chunks) the whole recurrence, forward and backward, took 6.5 ms a layer
    with it, 7.3 with a sequential `lax.scan` over the chunks and 16.8 with
    `lax.associative_scan` (`benchmarks/ssd_hand_over.py`; PERF.md, PR 32):
    a scalar decay makes the walk cheap whichever way it is taken."""
    decay = _decay_from(jnp.cumsum(log_a, axis=-1))     # c' <= c, inclusive
    # entering c: the states up to c - 1, not yet decayed by a_c
    decay = jnp.pad(decay[..., :-1, :], ((0, 0), (0, 0), (1, 0), (0, 0)))
    return jnp.einsum("bhcd,bdhpn->bchpn", decay, s,
                      precision=lax.Precision.HIGHEST)


def ssd_chunked(x, dt, A, B, C, chunk: int, compute_dtype=jnp.float32,
                hand_over=states_entering):
    """The same result, a chunk at a time. Shapes as the stepwise form; the
    sequence is padded to a whole number of chunks with steps that leave
    the state as it is (dt = 0). `hand_over(log_a, s)` gives the states
    the chunks start from. Returns fp32 (b, s, h, p)."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    r = h // g
    cdt, f32 = compute_dtype, jnp.float32
    pad = (-s) % chunk
    if pad:
        x, dt, B, C = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, B, C))
    c = (s + pad) // chunk

    def chunks(t):      # (b, S, ...) -> (b, c, chunk, ...)
        return t.reshape((b, c, chunk) + t.shape[2:])

    dt = chunks(dt.astype(f32))                             # (b, c, L, h)
    x = chunks(x).reshape(b, c, chunk, g, r, p)
    B, C = chunks(B).astype(cdt), chunks(C).astype(cdt)     # (b, c, L, g, n)
    cum = jnp.cumsum(jnp.moveaxis(dt * A.astype(f32), 2, 3), axis=-1)
    cum = cum.reshape(b, c, g, r, chunk)                    # log decay, <= 0

    # inside a chunk: ((C B^T) * L * dt_s) x, the scores one a GROUP
    cb = jnp.einsum("bctgn,bcsgn->bcgts", C, B, preferred_element_type=f32)
    dts = jnp.moveaxis(dt, 2, 3).reshape(b, c, g, r, 1, chunk)
    m = cb[:, :, :, None] * _decay_from(cum) * dts          # (b,c,g,r,L,L)
    xc = x.astype(cdt)
    y = jnp.einsum("bcgrts,bcsgrp->bctgrp", m.astype(cdt), xc,
                   preferred_element_type=f32)

    # a chunk's own contribution to the state, and the states handed on
    to_end = jnp.exp(cum[..., -1:] - cum) * dts[..., 0, :]  # (b,c,g,r,L)
    xw = (x.astype(f32) * jnp.moveaxis(to_end, 4, 2)[..., None]).astype(cdt)
    own = jnp.einsum("bcsgrp,bcsgn->bcgrpn", xw, B,
                     preferred_element_type=f32)
    S = hand_over(cum[..., -1].reshape(b, c, h).transpose(0, 2, 1),
                  own.reshape(b, c, h, p, n))
    # position t reads the state its chunk started from, decayed up to t
    read = jnp.einsum("bctgn,bcgrpn->bctgrp", C,
                      S.reshape(b, c, g, r, p, n).astype(cdt),
                      preferred_element_type=f32)
    y = y + read * jnp.moveaxis(jnp.exp(cum), 4, 2)[..., None]
    return y.reshape(b, s + pad, h, p)[:, :s]


class Mamba2(Op):
    """x (batch, seq, dim) -> the same shape. `w_in` lays its columns out
    in blocks, [z | x | B | C | dt]: z and x `num_heads * head_dim` each
    (head-major), B and C `n_groups * state_size` each (group-major), dt
    `num_heads`; the convolution runs over [x | B | C]."""

    type_name = "Mamba2"
    recompute = True     # the backward recomputes the block's insides

    def __init__(self, model, x, num_heads: int, head_dim: int,
                 n_groups: int, state_size: int, conv_width: int = 4,
                 chunk_size: int = 128, eps: float = 1e-5,
                 dt_min: float = 1e-3, dt_max: float = 0.1,
                 dt_floor: float = 1e-4, kernel_initializer=None,
                 name: Optional[str] = None):
        if x.num_dims != 3:
            raise ValueError("the mixer expects (batch, seq, dim)")
        if num_heads % n_groups != 0:
            raise ValueError("n_groups must divide num_heads")
        super().__init__(model, [x], name)
        self.h, self.p = int(num_heads), int(head_dim)
        self.g, self.n = int(n_groups), int(state_size)
        self.conv_width, self.chunk = int(conv_width), int(chunk_size)
        self.eps = float(eps)
        self.dt_range = (float(dt_min), float(dt_max), float(dt_floor))
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT()
        self.outputs = [self._make_output(x.shape, x.dtype)]

    @property
    def inner_dim(self) -> int:
        return self.h * self.p

    @property
    def conv_dim(self) -> int:
        return self.inner_dim + 2 * self.g * self.n

    def param_defs(self) -> Dict[str, ParamDef]:
        d = self.inputs[0].shape[-1]
        init, f32 = self.kernel_initializer, jnp.float32
        one = ConstantInitializer(1.0)
        bound = self.conv_width ** -0.5      # torch's depthwise default
        return {
            "w_in": ParamDef((d, self.inner_dim + self.conv_dim + self.h),
                             f32, init),
            "conv": ParamDef((self.conv_dim, self.conv_width), f32,
                             UniformInitializer(0, -bound, bound)),
            "conv_bias": ParamDef((self.conv_dim,), f32, ZeroInitializer()),
            "A_log": ParamDef((self.h,), f32, LogUniformInitializer()),
            "D": ParamDef((self.h,), f32, one),
            "dt_bias": ParamDef((self.h,), f32,
                                InverseSoftplusInitializer(*self.dt_range)),
            "norm": ParamDef((self.inner_dim,), f32, one),
            "w_out": ParamDef((self.inner_dim, d), f32, init),
        }

    def apply(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        b, s, _ = x.shape
        h, p, g, n = self.h, self.p, self.g, self.n
        di, gn = self.inner_dim, self.g * self.n
        cdt, f32 = self.model.compute_dtype, jnp.float32
        with jax.named_scope("in_proj"):
            zxbcdt = jnp.dot(x.astype(cdt), params["w_in"].astype(cdt),
                             preferred_element_type=f32)
        z, xbc = zxbcdt[..., :di], zxbcdt[..., di:di + self.conv_dim]
        with jax.named_scope("conv"):
            xbc = jax.nn.silu(causal_depthwise_conv(
                xbc, params["conv"], params["conv_bias"]))
        with jax.named_scope("ssd"):
            xh = xbc[..., :di].reshape(b, s, h, p)
            dt = jax.nn.softplus(zxbcdt[..., di + self.conv_dim:]
                                 + params["dt_bias"])
            y = ssd_chunked(
                xh.astype(cdt), dt, -jnp.exp(params["A_log"].astype(f32)),
                xbc[..., di:di + gn].reshape(b, s, g, n),
                xbc[..., di + gn:].reshape(b, s, g, n), self.chunk, cdt)
            y = y + params["D"].astype(f32)[:, None] * xh
        with jax.named_scope("gate_norm"):
            y = gated_rms_norm(y.reshape(b, s, di), z, params["norm"],
                               self.eps, di // g)
        with jax.named_scope("out_proj"):
            out = jnp.dot(y.astype(cdt), params["w_out"].astype(cdt),
                          preferred_element_type=f32)
        return [out.astype(x.dtype)]

    def flops_per_sample(self) -> float:
        _, s, d = self.outputs[0].shape
        proj = 2.0 * s * d * (2 * self.inner_dim + self.conv_dim + self.h)
        # a position: the causal half of a chunk's scores (a group) and of
        # its masked product (a head), one write and one read of the state
        ssd = 2.0 * s * (self.chunk / 2 * (self.g * self.n + self.h * self.p)
                         + 2 * self.h * self.p * self.n)
        return proj + ssd
