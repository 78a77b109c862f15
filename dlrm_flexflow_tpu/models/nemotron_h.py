"""Nemotron-H (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, `model_type:
nemotron_h`, 31.6B-A3.2B): a hybrid language model whose every block has
ONE part,

    x = x + Mixer_i(RMSNorm_i(x))

with `Mixer_i` by letter i of `hybrid_override_pattern`: `M` a Mamba-2
state-space mixer, `E` the expert layer (sigmoid router, choice on score +
bias, `routed_scaling_factor`, experts of TWO matrices, down(relu(up x)^2),
an ungated shared expert of the same form), `*` plain grouped-query
attention (no gate, no q/k norm, no position embedding: the state-space
layers carry the position; the config's `rope_theta` is an unused key).
After the last block a norm and the untied head. RMS norms scale by `w`
(init 1); no bias but the convolution's.

Every piece is an op of the same graph (`ops/norm.py`, `ops/mamba.py`,
`ops/moe.py`, `ops/attention.py:GatedAttention`, `ops/embedding.py`,
`ops/linear.py`) under the names `l<i>_norm`, `l<i>_mamba` / `l<i>_moe` /
`l<i>_attn`, `l<i>_add`, and the model trains through `FFModel.compile /
init_layers / fit` like any other. The field names of `NemotronHConfig` are
the keys of the published `config.json`; `experts_held` / `expert_offset`
say which of the `n_routed_experts` THIS chip holds. `balance_rate` (gamma)
is DeepSeek-V3's, whose router (`n_group`, `topk_group`,
`routed_scaling_factor`, a correction bias) this is; the config has no key
for it. Left out: the group-limited choice (`n_group` 1: none to limit) and
an auxiliary balance loss. The plain reference the tests hold this builder
to is `models/nemotron_h_reference.py`.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Optional

import jax.numpy as jnp

from ..core.initializers import NormInitializer
from ..core.model import FFModel

MIXERS = {"M": "mamba", "E": "moe", "*": "attn"}


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    layer_norm_epsilon: float = 1e-5
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # experts
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    experts_held: Optional[int] = None      # None: all of them
    expert_offset: int = 0
    balance_rate: float = 1e-3
    initializer_range: float = 0.02

    @classmethod
    def from_dict(cls, d: dict) -> "NemotronHConfig":
        """From a `config.json`-style dict; keys this builder does not
        know are ignored."""
        known = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def mixer_name(self, i: int) -> str:
        return f"l{i}_{MIXERS[self.hybrid_override_pattern[i]]}"


def build_nemotron_h(model: FFModel, cfg: NemotronHConfig, seq_len: int):
    """Tokens (batch, seq_len) int32 -> logits (batch * seq_len, vocab);
    train with `sparse_categorical_crossentropy` against (batch, seq_len)
    next-token labels. Returns ({input name: shape}, logits tensor)."""
    if len(cfg.hybrid_override_pattern) != cfg.num_hidden_layers or set(
            cfg.hybrid_override_pattern) - set(MIXERS):
        raise ValueError(
            f"hybrid_override_pattern {cfg.hybrid_override_pattern!r} is "
            f"not {cfg.num_hidden_layers} letters of {sorted(MIXERS)}")
    batch, eps = model.config.batch_size, cfg.layer_norm_epsilon
    init = NormInitializer(mean=0.0, stddev=cfg.initializer_range)
    tokens = model.create_tensor((batch, seq_len), dtype=jnp.int32,
                                 name="tokens")
    x = model.embedding(tokens, cfg.vocab_size, cfg.hidden_size,
                        aggr="none", kernel_initializer=init, name="embed")
    for i, letter in enumerate(cfg.hybrid_override_pattern):
        h = model.rms_norm(x, eps, zero_centered=False, name=f"l{i}_norm")
        if letter == "M":
            h = model.mamba2(
                h, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                cfg.ssm_state_size, cfg.conv_kernel, cfg.chunk_size, eps,
                cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor,
                kernel_initializer=init, name=cfg.mixer_name(i))
        elif letter == "E":
            h = model.moe(
                h, cfg.n_routed_experts, cfg.num_experts_per_tok,
                cfg.moe_intermediate_size,
                cfg.moe_shared_expert_intermediate_size,
                experts_held=cfg.experts_held,
                expert_offset=cfg.expert_offset,
                norm_topk=cfg.norm_topk_prob, scoring="sigmoid",
                routed_scale=cfg.routed_scaling_factor, shared_gate=False,
                balance_rate=cfg.balance_rate, kernel_initializer=init,
                name=cfg.mixer_name(i), activation="relu2")
        else:
            h = model.gated_attention(
                h, cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim, rotary_dim=0, eps=eps,
                kernel_initializer=init, name=cfg.mixer_name(i),
                gate=False, qk_norm=False)
        x = model.add(x, h, name=f"l{i}_add")
    # the head's logits are the step's largest array: in the compute dtype
    x = model.rms_norm(x, eps, to_compute_dtype=True, zero_centered=False,
                       name="final_norm")
    x = model.reshape(x, (batch * seq_len, cfg.hidden_size),
                      name="fold_positions")
    logits = model.dense(x, cfg.vocab_size, use_bias=False,
                         kernel_initializer=init, name="head")
    return {"tokens": (batch, seq_len)}, logits
