"""Root-mean-square normalisation.

The reference has batch norm only (src/ops/batch_norm.cu). A language
model's block normalises each position over its features with no mean and
no bias: `x / sqrt(mean(x^2) + eps) * scale`. The statistics are fp32
whatever the activation dtype; the output keeps the input's dtype.

The op stores the scale as `1 + w` with `w` initialised 0 (the Qwen3-Next
convention: weight decay then pulls the scale to 1, not to 0) or, with
`zero_centered=False`, as `w` initialised 1 (GLM's, DeepSeek's); `rms_norm`,
which the block ops call on their insides, takes either, and a
`group_size`: the mean square is then taken over each run of that many
features (Mamba-2's gated norm: 8 groups of 512), the scale still one a
feature.
`to_compute_dtype` hands the result on in the model's compute dtype: the
norm before a wide head, whose logits then take half the bytes.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..core.initializers import ConstantInitializer, ZeroInitializer
from ..core.op import Op, ParamDef


def rms_norm(x, w, eps: float, zero_centered: bool,
             group_size: Optional[int] = None):
    """`x` (..., d) normalised over its last axis (over each group of
    `group_size` features of it, where given) in fp32, times the scale;
    returns fp32 (the caller casts)."""
    x32 = x.astype(jnp.float32)
    if group_size is not None:
        x32 = x32.reshape(x.shape[:-1] + (-1, group_size))
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                     keepdims=True) + eps)
    if group_size is not None:
        y = y.reshape(x.shape)
    scale = w.astype(jnp.float32)
    return y * (1.0 + scale if zero_centered else scale)


class RMSNorm(Op):
    type_name = "RMSNorm"

    def __init__(self, model, input_tensor, eps: float = 1e-6,
                 to_compute_dtype: bool = False, zero_centered: bool = True,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        self.dim = int(input_tensor.shape[-1])
        self.eps = float(eps)
        self.to_compute_dtype = bool(to_compute_dtype)
        self.zero_centered = bool(zero_centered)
        self.outputs = [self._make_output(input_tensor.shape,
                                          input_tensor.dtype)]

    def param_defs(self) -> Dict[str, ParamDef]:
        init = (ZeroInitializer() if self.zero_centered
                else ConstantInitializer(1.0))
        return {"weight": ParamDef((self.dim,), jnp.float32, init)}

    def apply(self, params, xs, *, training=False, rng=None):
        (x,) = xs
        dtype = (self.model.compute_dtype if self.to_compute_dtype
                 else x.dtype)
        return [rms_norm(x, params["weight"], self.eps,
                         self.zero_centered).astype(dtype)]

    def hbm_io_factor(self) -> float:
        # one pass over the activation, fused with its neighbours
        return 0.5

    def flops_per_sample(self) -> float:
        t = self.outputs[0]
        n = 1
        for d in t.shape[1:]:
            n *= d
        return 4.0 * n
