"""Linear (Dense) operator.

Parity with the reference Linear op (reference: src/ops/linear.cu, 1051 LoC):
cuBLAS sgemm + bias + fused activation, with 2-D sample×channel parallelism —
`num_par_c > 1` broadcasts the input via a replica tensor and reduce-sums
input gradients in a second backward task (linear.cu:188-293, 766-794).

TPU-native redesign: y = x @ W + b is `jnp.dot` on the MXU in the configured
compute dtype (bfloat16 by default — model-level setting). Channel
parallelism is expressed by sharding W's output dim and the activation's
channel dim on the same mesh axes; GSPMD inserts the input all-gather and
input-grad reduce-scatter that the replica tensor + BWD2 task hand-coded.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..core.initializers import DEFAULT_BIAS_INIT, DEFAULT_KERNEL_INIT
from ..core.op import Op, ParamDef
from ..parallel.pconfig import ParallelConfig
from .common import AC_MODE_NONE, apply_activation


class Linear(Op):
    type_name = "Dense"

    def __init__(self, model, input_tensor, out_dim: int,
                 activation=AC_MODE_NONE, use_bias: bool = True,
                 kernel_initializer=None, bias_initializer=None,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        if input_tensor.num_dims < 2:
            raise ValueError("Linear expects rank>=2 input (sample dim first)")
        self.in_dim = int(input_tensor.shape[-1])
        self.out_dim = int(out_dim)
        self.activation = activation
        self.use_bias = bool(use_bias)
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT()
        self.bias_initializer = bias_initializer or DEFAULT_BIAS_INIT()
        out_shape = tuple(input_tensor.shape[:-1]) + (self.out_dim,)
        self.outputs = [self._make_output(out_shape)]

    def param_defs(self) -> Dict[str, ParamDef]:
        defs = {"kernel": ParamDef((self.in_dim, self.out_dim), jnp.float32,
                                   self.kernel_initializer)}
        if self.use_bias:
            defs["bias"] = ParamDef((self.out_dim,), jnp.float32,
                                    self.bias_initializer)
        return defs

    def apply(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        cdt = self.model.compute_dtype
        y = jnp.dot(x.astype(cdt), params["kernel"].astype(cdt),
                    preferred_element_type=jnp.float32)
        if self.use_bias:
            y = y + params["bias"]
        return [apply_activation(y, self.activation).astype(x.dtype)]

    # -- parallelization ---------------------------------------------------
    def candidate_parallel_configs(self, num_devices, feasible_degrees):
        """Sample × channel 2-D grid, mirroring Linear's search space
        (reference linear.cu + model.cc:295-324)."""
        out = []
        nd = self.outputs[0].num_dims
        for ds in feasible_degrees:
            for dc in feasible_degrees:
                if ds * dc <= num_devices:
                    degs = [1] * nd
                    degs[0] = ds
                    degs[-1] = dc
                    out.append(ParallelConfig(tuple(degs)))
        return out

    def param_shard_shapes(self, pc: ParallelConfig, ndev=None):
        # channel TP splits the kernel/bias out dim by the LAST degree
        # (candidate_parallel_configs/param_axes put channel TP there)
        dc = pc.degrees[-1] if len(pc.degrees) > 1 else 1
        shapes = {n: list(d.shape) for n, d in self.param_defs().items()}
        if dc > 1:
            for v in shapes.values():
                v[-1] = max(v[-1] // dc, 1)
        return {n: tuple(v) for n, v in shapes.items()}

    def param_axes(self, pc: ParallelConfig, out_axes,
                   raw_pc=None):
        # channel (last output dim) partition shards the kernel's out dim and
        # the bias *on the same mesh axes* as the activation's channel dim;
        # sample partition replicates weights (grad psum by GSPMD)
        ch = out_axes[-1] if len(out_axes) >= 2 else ()
        out = {"kernel": ((), ch)}
        if self.use_bias:
            out["bias"] = (ch,)
        return out

    def flops_per_sample(self) -> float:
        rows = math.prod(self.outputs[0].shape[1:-1]) if self.outputs[0].num_dims > 2 else 1
        return 2.0 * rows * self.in_dim * self.out_dim


class GatedMLP(Op):
    """A dense SwiGLU feed-forward part: down(silu(x W_gate) * (x W_up)),
    no bias: the leading dense layers of an expert model. Products in the
    compute dtype with fp32 accumulation, as the expert op's."""

    type_name = "GatedMLP"
    recompute = True     # the backward recomputes the block's insides

    def __init__(self, model, input_tensor, hidden_dim: int,
                 kernel_initializer=None, name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        self.dim = int(input_tensor.shape[-1])
        self.hidden_dim = int(hidden_dim)
        self.kernel_initializer = kernel_initializer or DEFAULT_KERNEL_INIT()
        self.outputs = [self._make_output(input_tensor.shape,
                                          input_tensor.dtype)]

    def param_defs(self) -> Dict[str, ParamDef]:
        d, f, init = self.dim, self.hidden_dim, self.kernel_initializer
        return {"w_gate": ParamDef((d, f), jnp.float32, init),
                "w_up": ParamDef((d, f), jnp.float32, init),
                "w_down": ParamDef((f, d), jnp.float32, init)}

    def apply(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        cdt = self.model.compute_dtype

        def mm(a, w):
            return jnp.dot(a, params[w].astype(cdt),
                           preferred_element_type=jnp.float32)

        xc = x.astype(cdt)
        h = (jax.nn.silu(mm(xc, "w_gate")) * mm(xc, "w_up")).astype(cdt)
        return [mm(h, "w_down").astype(x.dtype)]

    def flops_per_sample(self) -> float:
        rows = math.prod(self.outputs[0].shape[1:-1])
        return 6.0 * rows * self.dim * self.hidden_dim
