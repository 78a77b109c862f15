"""Conv2D, Pool2D, BatchNorm operators (NCHW API, matching the reference).

Parity with the reference ops (reference: src/ops/conv_2d.cu 1046 LoC —
cuDNN conv with auto-picked algorithm + fused ReLU; src/ops/pool_2d.cu 510 —
cuDNN pooling; src/ops/batch_norm.cu 565 — cuDNN BN training mode).

TPU-native redesign: `lax.conv_general_dilated` lowers to the MXU's native
convolution; algorithm picking is XLA's job (the cuDNN find-algorithm dance
at conv_2d.cu:217 has no TPU analog). BatchNorm is a fused
normalize-scale-shift in fp32 statistics; running stats are parameters
updated functionally (the train step threads them through like weights but
with direct assignment, not gradients).

Layout: the API is NCHW (reference parity) but the conv stack COMPUTES in
NHWC — the layout the TPU's vector units and XLA's conv emitter want
(channels on the 128-lane minor dim). Each op consumes its input in
whatever physical layout the producer declared (Tensor.physical) and
declares "nhwc" on its own outputs; layout-agnostic consumers ride along
and everything else transposes back to logical NCHW at the op boundary
(FFModel._forward_env). Disable with FFConfig.conv_nhwc=False / --no-nhwc.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.initializers import (ConstantInitializer, DEFAULT_BIAS_INIT,
                                 DEFAULT_KERNEL_INIT, ZeroInitializer)
from ..core.op import Op, ParamDef
from ..parallel.pconfig import ParallelConfig
from .common import AC_MODE_NONE, apply_activation

POOL_MAX = "max"
POOL_AVG = "avg"


def _nhwc_enabled(model) -> bool:
    return bool(getattr(model.config, "conv_nhwc", True))


def _to_nhwc(x, t):
    """Bring a concrete array for logical-NCHW tensor `t` into NHWC."""
    return x if t.physical == "nhwc" else jnp.transpose(x, (0, 2, 3, 1))


def _s2d_conv_nhwc(x, kernel, stride, padding, out_hw):
    """Space-to-depth lowering of a strided conv (the MLPerf ResNet stem
    reformulation): a k x k stride-s conv over C channels becomes a
    ceil(k/s) x ceil(k/s) stride-1 conv over C*s*s channels. A 3-channel
    224x224 stem fills 3/128 MXU lanes (~7% stem MFU measured in round
    5); after the transform the stem
    carries C*s*s lanes and the conv's inner dim grows s*s-fold.

    Exact algebra: with explicit input padding, output pixel i reads
    input rows s*i+p (p < k). Writing p = p'*s + u, rows s*(i+p') + u
    are exactly space-to-depth block row i+p', sub-row u — so the
    original conv equals a stride-1 VALID conv over the s2d input with
    the kernel regrouped as [o, (u, v, c), p', q'] (kernel padded with
    zero taps to a multiple of s first).

    x: NHWC; kernel: OIHW; returns NHWC conv output of spatial out_hw.
    """
    n, h, w, c = x.shape
    o, _, kh, kw = kernel.shape
    sh, sw = stride
    ph, pw = padding
    oh, ow = out_hw
    kh_p = -(-kh // sh) * sh
    kw_p = -(-kw // sw) * sw
    # exact padded extent each spatial dim must provide: the last output
    # window starts at (o-1)*s and spans the zero-padded kernel
    h_need = (oh - 1) * sh + kh_p
    w_need = (ow - 1) * sw + kw_p
    x = jnp.pad(x, ((0, 0), (ph, max(h_need - h - ph, 0)),
                    (pw, max(w_need - w - pw, 0)), (0, 0)))
    x = x[:, :h_need, :w_need]         # crop rows no window reads
    # space-to-depth: channel index becomes (u*sw + v)*C + c
    x = x.reshape(n, h_need // sh, sh, w_need // sw, sw, c)
    x = jnp.transpose(x, (0, 1, 3, 2, 4, 5)).reshape(
        n, h_need // sh, w_need // sw, sh * sw * c)
    # kernel: zero-pad taps to (kh_p, kw_p), regroup to match
    k = jnp.pad(kernel, ((0, 0), (0, 0), (0, kh_p - kh), (0, kw_p - kw)))
    k = k.reshape(o, c, kh_p // sh, sh, kw_p // sw, sw)
    k = jnp.transpose(k, (0, 3, 5, 1, 2, 4)).reshape(
        o, sh * sw * c, kh_p // sh, kw_p // sw)
    return lax.conv_general_dilated(
        x, k, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "OIHW", "NHWC"))


def _from_nhwc(x, t):
    """Bring an NHWC array back to tensor `t`'s declared physical form."""
    return x if t.physical == "nhwc" else jnp.transpose(x, (0, 3, 1, 2))


class Conv2D(Op):
    type_name = "Conv2D"

    def __init__(self, model, input_tensor, out_channels: int,
                 kernel_h: int, kernel_w: int, stride_h: int, stride_w: int,
                 padding_h: int, padding_w: int, activation=AC_MODE_NONE,
                 use_bias: bool = True, groups: int = 1,
                 kernel_initializer=None, bias_initializer=None,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        if input_tensor.num_dims != 4:
            raise ValueError("Conv2D expects NCHW rank-4 input")
        n, c, h, w = input_tensor.shape
        self.in_channels = c
        self.out_channels = int(out_channels)
        self.kernel = (int(kernel_h), int(kernel_w))
        self.stride = (int(stride_h), int(stride_w))
        self.padding = (int(padding_h), int(padding_w))
        self.activation = activation
        self.use_bias = bool(use_bias)
        self.groups = int(groups)
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT()
        self.bias_initializer = bias_initializer or DEFAULT_BIAS_INIT()
        oh = (h + 2 * self.padding[0] - self.kernel[0]) // self.stride[0] + 1
        ow = (w + 2 * self.padding[1] - self.kernel[1]) // self.stride[1] + 1
        self.outputs = [self._make_output((n, self.out_channels, oh, ow))]
        if _nhwc_enabled(model):
            self.outputs[0].physical = "nhwc"
            self._accepts_nhwc_inputs = True

    def param_defs(self) -> Dict[str, ParamDef]:
        # OIHW kernel layout (cuDNN default, conv_2d.cu)
        defs = {"kernel": ParamDef(
            (self.out_channels, self.in_channels // self.groups,
             *self.kernel), jnp.float32, self.kernel_initializer)}
        if self.use_bias:
            defs["bias"] = ParamDef((self.out_channels,), jnp.float32,
                                    self.bias_initializer)
        return defs

    def apply(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        cdt = self.model.compute_dtype
        pads = [(self.padding[0], self.padding[0]),
                (self.padding[1], self.padding[1])]
        # no preferred_element_type upcast: jax's conv transpose rule
        # rejects mixed dtypes (fp32 cotangent vs bf16 operands), so emit a
        # bf16-out conv (MXU still accumulates fp32 internally) and upcast
        if self.outputs[0].physical == "nhwc":
            if getattr(self, "_use_s2d", False):
                y = _s2d_conv_nhwc(
                    _to_nhwc(x, self.inputs[0]).astype(cdt),
                    params["kernel"].astype(cdt), self.stride,
                    self.padding,
                    self.outputs[0].shape[2:]).astype(jnp.float32)
            else:
                y = lax.conv_general_dilated(
                    _to_nhwc(x, self.inputs[0]).astype(cdt),
                    params["kernel"].astype(cdt),
                    window_strides=self.stride, padding=pads,
                    dimension_numbers=("NHWC", "OIHW", "NHWC"),
                    feature_group_count=self.groups).astype(jnp.float32)
            if self.use_bias:
                y = y + params["bias"]
        else:
            y = lax.conv_general_dilated(
                x.astype(cdt), params["kernel"].astype(cdt),
                window_strides=self.stride, padding=pads,
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                feature_group_count=self.groups).astype(jnp.float32)
            if self.use_bias:
                y = y + params["bias"][None, :, None, None]
        return [apply_activation(y, self.activation).astype(x.dtype)]

    def s2d_eligible(self) -> bool:
        """Space-to-depth pays when the conv is strided and its input
        channels underfill the 128 MXU lanes (stems: 3 channels). The
        transformed channel count must still be lane-friendly."""
        sh, sw = self.stride
        return (self.groups == 1
                and self.outputs[0].physical == "nhwc"
                and (sh > 1 or sw > 1)
                and self.in_channels <= 8
                and self.in_channels * sh * sw <= 128
                and self.kernel[0] >= sh and self.kernel[1] >= sw)

    def candidate_parallel_configs(self, num_devices, feasible_degrees):
        """Sample DP plus attribute (h/w) splits — SOAP "A" parallelism
        (reference model.cc:502-526, 738-744 partitions conv over n/c/h/w)."""
        out = []
        n, c, h, w = self.outputs[0].shape
        for ds in feasible_degrees:
            if ds <= num_devices:
                out.append(ParallelConfig((ds, 1, 1, 1)))
        for dh in feasible_degrees:
            if 1 < dh <= num_devices and h % dh == 0:
                out.append(ParallelConfig((1, 1, dh, 1)))
        for ds in feasible_degrees:
            for dc in feasible_degrees:
                if ds * dc <= num_devices and 1 < dc and self.out_channels % dc == 0:
                    out.append(ParallelConfig((ds, dc, 1, 1)))
        return out

    def param_axes(self, pc: ParallelConfig, out_axes,
                   raw_pc=None):
        ch = out_axes[1] if len(out_axes) >= 2 else ()
        out = {"kernel": (ch, (), (), ())}
        if self.use_bias:
            out["bias"] = (ch,)
        return out

    def param_shard_shapes(self, pc: ParallelConfig, ndev=None):
        dc = pc.degrees[1] if len(pc.degrees) > 1 else 1
        shapes = {n_: list(d.shape) for n_, d in self.param_defs().items()}
        if dc > 1:
            shapes["kernel"][0] = max(shapes["kernel"][0] // dc, 1)
            if "bias" in shapes:
                shapes["bias"][0] = max(shapes["bias"][0] // dc, 1)
        return {n_: tuple(v) for n_, v in shapes.items()}

    def flops_per_sample(self) -> float:
        _, co, oh, ow = self.outputs[0].shape
        kh, kw = self.kernel
        return 2.0 * co * oh * ow * (self.in_channels // self.groups) * kh * kw

    def mxu_utilization_factor(self) -> float:
        # measured (r4 sweep, re-fit r5 with the per-step-floor model):
        # ResNet-18 b128 sustains ~78% of bf16 peak end-to-end vs the
        # gemm-calibrated 55% — XLA's conv emitter tiles large spatial
        # convs onto the MXU better than the global constant assumes
        return 1.42


def measure_s2d_wins(op, iters: int = 24) -> bool:
    """Time one fwd+bwd of `op` under both lowerings on the attached
    device and return True when space-to-depth is faster — the TPU analog
    of the reference's cudnnFindConvolutionForwardAlgorithm pick
    (conv_2d.cu:217): decided by measurement on the real machine, once,
    at init. The timed graph scans applications with a data dependence
    (XLA cannot hoist the conv) and consumes the gradients; the cost is
    the MARGINAL time between a long and a short scan, which cancels
    the per-dispatch overhead both scans share."""
    import time

    import numpy as np

    t_in = op.inputs[0]
    n, c, h, w = t_in.shape
    shape = (n, h, w, c) if t_in.physical == "nhwc" else (n, c, h, w)
    rng = np.random.RandomState(0)
    cdt = op.model.compute_dtype
    x = jnp.asarray(rng.rand(*shape).astype(np.float32)).astype(cdt)
    params = {k: jnp.asarray(rng.rand(*d.shape).astype(np.float32))
              for k, d in op.param_defs().items()}

    def timed(use_s2d: bool) -> float:
        old = getattr(op, "_use_s2d", False)
        op._use_s2d = use_s2d
        try:
            def make(length):
                @jax.jit
                def f(p, xx):
                    def body(acc, _):
                        xb = xx + (acc * 1e-38).astype(xx.dtype)

                        def loss(pp, xi):
                            out = op.apply(pp, [xi], training=True)[0]
                            return jnp.sum(out.astype(jnp.float32))

                        l, (gp, gx) = jax.value_and_grad(
                            loss, argnums=(0, 1))(p, xb)
                        consume = sum(
                            jnp.sum(g).astype(jnp.float32) * 1e-30
                            for g in jax.tree.leaves(gp))
                        consume += jnp.sum(gx).astype(jnp.float32) * 1e-30
                        return acc + l + consume, None

                    acc, _ = lax.scan(body, jnp.float32(0.0), None,
                                      length=length)
                    return acc
                return f

            short, long_ = make(2), make(2 + iters)

            def best(f):
                float(f(params, x))        # compile + true wait
                ts = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    float(f(params, x))    # dependent readback
                    ts.append(time.perf_counter() - t0)
                return min(ts)

            return (best(long_) - best(short)) / iters
        finally:
            op._use_s2d = old

    return timed(True) < timed(False)


class Pool2D(Op):
    type_name = "Pool2D"

    def __init__(self, model, input_tensor, kernel_h, kernel_w, stride_h,
                 stride_w, padding_h, padding_w, pool_type: str = POOL_MAX,
                 activation=AC_MODE_NONE, name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        n, c, h, w = input_tensor.shape
        self.kernel = (int(kernel_h), int(kernel_w))
        self.stride = (int(stride_h), int(stride_w))
        self.padding = (int(padding_h), int(padding_w))
        self.pool_type = pool_type
        self.activation = activation
        oh = (h + 2 * self.padding[0] - self.kernel[0]) // self.stride[0] + 1
        ow = (w + 2 * self.padding[1] - self.kernel[1]) // self.stride[1] + 1
        self.outputs = [self._make_output((n, c, oh, ow))]
        if _nhwc_enabled(model):
            self.outputs[0].physical = "nhwc"
            self._accepts_nhwc_inputs = True

    def apply(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        nhwc = self.outputs[0].physical == "nhwc"
        if nhwc:
            x = _to_nhwc(x, self.inputs[0])
            pads = [(0, 0),
                    (self.padding[0], self.padding[0]),
                    (self.padding[1], self.padding[1]), (0, 0)]
            dims = (1, *self.kernel, 1)
            strides = (1, *self.stride, 1)
        else:
            pads = [(0, 0), (0, 0),
                    (self.padding[0], self.padding[0]),
                    (self.padding[1], self.padding[1])]
            dims = (1, 1, *self.kernel)
            strides = (1, 1, *self.stride)
        if self.pool_type == POOL_MAX:
            init = -jnp.inf
            y = lax.reduce_window(x, init, lax.max, dims, strides, pads)
        else:
            y = lax.reduce_window(x, 0.0, lax.add, dims, strides, pads)
            if self.padding != (0, 0):
                # exclude padded positions from the divisor (reference uses
                # CUDNN_POOLING_AVERAGE_COUNT_EXCLUDE_PADDING, pool_2d.cu:190)
                counts = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add,
                                           dims, strides, pads)
                y = y / counts
            else:
                y = y / float(self.kernel[0] * self.kernel[1])
        return [apply_activation(y, self.activation)]


class BatchNorm(Op):
    """BatchNorm2D over NCHW (normalize per channel). `relu` flag matches the
    reference ctor (batch_norm.cu). Running stats are non-gradient state the
    train step updates in-place-functionally; eval mode uses them."""

    type_name = "BatchNorm"

    def hbm_io_factor(self) -> float:
        # fused into the producer's epilogue by XLA (see Op.hbm_io_factor)
        return 0.5
    momentum = 0.9
    eps = 1e-5

    def __init__(self, model, input_tensor, relu: bool = True,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        self.relu = bool(relu)
        self.channels = input_tensor.shape[1]
        self.outputs = [self._make_output(input_tensor.shape)]
        if _nhwc_enabled(model):
            self.outputs[0].physical = "nhwc"
            self._accepts_nhwc_inputs = True

    def param_defs(self):
        c = self.channels
        return {
            "scale": ParamDef((c,), jnp.float32, ConstantInitializer(1.0)),
            "bias": ParamDef((c,), jnp.float32, ZeroInitializer()),
        }

    # running stats: handled as op state (see model.py state threading)
    def state_defs(self):
        c = self.channels
        return {
            "running_mean": ParamDef((c,), jnp.float32, ZeroInitializer()),
            "running_var": ParamDef((c,), jnp.float32, ConstantInitializer(1.0)),
        }

    def apply_with_state(self, params, state, xs, *, training=False, rng=None):
        (x,) = xs
        nhwc = self.outputs[0].physical == "nhwc"
        if nhwc:
            x = _to_nhwc(x, self.inputs[0])
            reduce_axes = (0, 1, 2)
        else:
            reduce_axes = (0, 2, 3)

        def _b(v):  # broadcast a (C,) vector over the channel dim
            return v[None, :, None, None] if not nhwc else v

        x32 = x.astype(jnp.float32)
        if training:
            # single-pass statistics: E[x] and E[x^2] reduce together in
            # one traversal of the activation stream (jnp.var alone would
            # re-read x after computing the mean — one extra full pass
            # over every conv output per step; round 5's per-op analysis
            # named BN stat passes as a top cost).
            # XLA fuses the two accumulations into one loop.
            mean = jnp.mean(x32, axis=reduce_axes)
            mean_sq = jnp.mean(x32 * x32, axis=reduce_axes)
            var = jnp.maximum(mean_sq - mean * mean, 0.0)
            new_state = {
                "running_mean": self.momentum * state["running_mean"]
                                + (1 - self.momentum) * mean,
                "running_var": self.momentum * state["running_var"]
                               + (1 - self.momentum) * var,
            }
        else:
            mean, var = state["running_mean"], state["running_var"]
            new_state = state
        inv = lax.rsqrt(var + self.eps)
        y = (x32 - _b(mean)) * _b(inv)
        y = y * _b(params["scale"]) + _b(params["bias"])
        if self.relu:
            y = jax.nn.relu(y)
        return [y.astype(x.dtype)], new_state

    def apply(self, params, xs, *, training=False, rng=None):
        raise RuntimeError("BatchNorm uses apply_with_state")
