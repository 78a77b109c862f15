"""Qwen3-Next (Qwen/Qwen3-Next-80B-A3B-Instruct, `model_type: qwen3_next`):
a hybrid sparse language model on the unified framework. A block is

    h = x + Mixer(RMSNorm(x));  y = h + MoE(RMSNorm(h))

with the mixer full gated attention in every `full_attention_interval`-th
block and the gated delta net (linear attention) in the others; after the
last block a norm and the untied head. Every piece is an op of the same
graph (`ops/norm.py`, `ops/delta_net.py`, `ops/attention.py`,
`ops/moe.py`, `ops/embedding.py`, `ops/linear.py`) and the model trains
through `FFModel.compile / init_layers / fit` like any other.

The field names of `Qwen3NextConfig` are the keys of the published
`config.json`. `experts_held` / `expert_offset` say which of the
`num_experts` routed experts THIS chip holds (one rank of an
expert-parallel group, `ops/moe.py`); the router keeps its full width.
Left out: the multi-token-prediction module and the auxiliary balance loss
(the config has a key for neither). The plain reference the tests hold
this builder to is `models/qwen3_next_reference.py`.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Optional

import jax.numpy as jnp

from ..core.initializers import NormInitializer
from ..core.model import FFModel


@dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    rms_norm_eps: float = 1e-6
    # gated delta net
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # gated attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # experts
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    experts_held: Optional[int] = None      # None: all of them
    expert_offset: int = 0
    initializer_range: float = 0.02

    @classmethod
    def from_dict(cls, d: dict) -> "Qwen3NextConfig":
        """From a `config.json`-style dict; keys this builder does not
        know are ignored."""
        known = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def is_full_attention(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0

    def mixer_name(self, i: int) -> str:
        return f"l{i}_attn" if self.is_full_attention(i) else f"l{i}_delta"


def build_qwen3_next(model: FFModel, cfg: Qwen3NextConfig, seq_len: int):
    """Tokens (batch, seq_len) int32 -> logits (batch * seq_len, vocab);
    train with `sparse_categorical_crossentropy` against (batch, seq_len)
    next-token labels. Returns ({input name: shape}, logits tensor)."""
    batch = model.config.batch_size
    init = NormInitializer(mean=0.0, stddev=cfg.initializer_range)
    tokens = model.create_tensor((batch, seq_len), dtype=jnp.int32,
                                 name="tokens")
    x = model.embedding(tokens, cfg.vocab_size, cfg.hidden_size,
                        aggr="none", kernel_initializer=init, name="embed")
    for i in range(cfg.num_hidden_layers):
        h = model.rms_norm(x, cfg.rms_norm_eps, name=f"l{i}_mixer_norm")
        if cfg.is_full_attention(i):
            h = model.gated_attention(
                h, cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim,
                rotary_dim=int(cfg.head_dim * cfg.partial_rotary_factor),
                rope_theta=cfg.rope_theta, eps=cfg.rms_norm_eps,
                kernel_initializer=init, name=cfg.mixer_name(i))
        else:
            h = model.gated_delta_net(
                h, cfg.linear_num_key_heads, cfg.linear_num_value_heads,
                cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                cfg.linear_conv_kernel_dim, eps=cfg.rms_norm_eps,
                kernel_initializer=init, name=cfg.mixer_name(i))
        x = model.add(x, h, name=f"l{i}_mixer_add")
        h = model.rms_norm(x, cfg.rms_norm_eps, name=f"l{i}_moe_norm")
        h = model.moe(h, cfg.num_experts, cfg.num_experts_per_tok,
                      cfg.moe_intermediate_size,
                      cfg.shared_expert_intermediate_size,
                      experts_held=cfg.experts_held,
                      expert_offset=cfg.expert_offset,
                      norm_topk=cfg.norm_topk_prob,
                      kernel_initializer=init, name=f"l{i}_moe")
        x = model.add(x, h, name=f"l{i}_moe_add")
    # the head's logits are the step's largest array: in the compute dtype
    x = model.rms_norm(x, cfg.rms_norm_eps, to_compute_dtype=True,
                       name="final_norm")
    x = model.reshape(x, (batch * seq_len, cfg.hidden_size),
                      name="fold_positions")
    logits = model.dense(x, cfg.vocab_size, use_bias=False,
                         kernel_initializer=init, name="head")
    return {"tokens": (batch, seq_len)}, logits
