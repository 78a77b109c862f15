"""GLM-4.7-Flash (zai-org/GLM-4.7-Flash, `model_type: glm4_moe_lite`,
30B-A3B): a sparse language model with latent attention on the unified
framework. A block is

    h = x + MLA(RMSNorm(x));  y = h + F_i(RMSNorm(h))

with `F_i` a dense SwiGLU in the first `first_k_dense_replace` blocks and
the expert layer in the others (sigmoid router, choice on score + bias,
`routed_scaling_factor`, an ungated shared expert); after the last block a
norm and the untied head. `num_nextn_predict_layers` multi-token-prediction
modules follow (DeepSeek-V3's report, section 2.2; one here): with `h_t`
the last block's output before the final norm,

    u_t = W_eh [RMSNorm(Emb(tok_{t+1})) ; RMSNorm(h_t)]

goes through one more sparse block and a norm of its own and then through
THE MAIN MODEL'S head, to predict tok_{t+2}; `Emb` is the main model's
token table. L = L_main + lambda L_mtp, each a mean over its own targets;
the module's last position has none.

One table and one head serve both passes because both passes go through
ONE embedding op and ONE head op: the ids of the two passes enter as one
(batch, 2 * seq_len) input, [tok_0..tok_{S-1} | tok_1..tok_S], the two
final hidden sequences are concatenated before the head, and the loss
takes one weight a row (`FFModel.compile(loss_weights=)`): 1 for the main
rows, lambda for the module's, 0 at its last position, each over its
term's number of targets. So the table has one lazy-Adam row update a step
(its two uses' gradients summed by the update's own dedup) and the head
one m and v. `mtp_labels` lays a batch's tokens out that way.

Every piece is an op of the same graph (`ops/norm.py`,
`ops/attention.py:LatentAttention`, `ops/linear.py:GatedMLP`, `ops/moe.py`,
`ops/embedding.py`) and the model trains through `FFModel.compile /
init_layers / fit` like any other. The field names of `Glm4MoeLiteConfig`
are the keys of the published `config.json`; `experts_held` /
`expert_offset` say which of the `n_routed_experts` THIS chip holds.
`balance_rate` (gamma), `mtp_loss_weight` (lambda) are DeepSeek-V3's, whose
scheme `topk_method: noaux_tc` names; the config has a key for neither.
Left out: the group-limited choice (`n_group` 1 here: none to limit). The
plain reference the tests hold this builder to is
`models/glm4_moe_lite_reference.py`.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..core.initializers import NormInitializer
from ..core.model import FFModel


@dataclass
class Glm4MoeLiteConfig:
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-5
    # latent attention
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    # feed-forward parts
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    experts_held: Optional[int] = None      # None: all of them
    expert_offset: int = 0
    balance_rate: float = 1e-3
    mtp_loss_weight: float = 0.3
    initializer_range: float = 0.02

    @classmethod
    def from_dict(cls, d: dict) -> "Glm4MoeLiteConfig":
        """From a `config.json`-style dict; keys this builder does not
        know are ignored."""
        known = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


def loss_weights(seq_len: int, mtp_loss_weight: float) -> np.ndarray:
    """One weight a logit row of a sample, (2 * seq_len,): the loss is
    mean(w * nll) over all rows, so a term's rows carry its weight times
    rows / targets of the term."""
    w = np.zeros(2 * seq_len, np.float32)
    w[:seq_len] = 2.0
    w[seq_len:-1] = mtp_loss_weight * 2.0 * seq_len / (seq_len - 1)
    return w


def mtp_labels(tokens: np.ndarray):
    """tokens (n, seq_len + 1) -> (ids (n, 2 * seq_len) as the model's
    `tokens` input takes them, labels (n, 2 * seq_len)): the main pass
    reads tok_0.. and predicts tok_1.., the module reads tok_1.. beside it
    and predicts tok_2..; its last position repeats a label that weighs
    nothing."""
    t = np.asarray(tokens)
    ids = np.concatenate([t[:, :-1], t[:, 1:]], axis=1)
    labels = np.concatenate([t[:, 1:], t[:, 2:], t[:, -1:]], axis=1)
    return np.ascontiguousarray(ids), np.ascontiguousarray(labels)


def _sparse_block(model: FFModel, cfg: Glm4MoeLiteConfig, x, tag: str,
                  dense: bool, init):
    """One block under the names `<tag>_mixer_norm`, `<tag>_mla`,
    `<tag>_mixer_add`, `<tag>_ffn_norm`, `<tag>_mlp` or `<tag>_moe`,
    `<tag>_ffn_add`."""
    eps = cfg.rms_norm_eps
    h = model.rms_norm(x, eps, zero_centered=False, name=f"{tag}_mixer_norm")
    h = model.latent_attention(
        h, cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
        cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
        rope_theta=cfg.rope_theta, eps=eps, kernel_initializer=init,
        name=f"{tag}_mla")
    x = model.add(x, h, name=f"{tag}_mixer_add")
    h = model.rms_norm(x, eps, zero_centered=False, name=f"{tag}_ffn_norm")
    if dense:
        h = model.gated_mlp(h, cfg.intermediate_size,
                            kernel_initializer=init, name=f"{tag}_mlp")
    else:
        h = model.moe(
            h, cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size,
            cfg.n_shared_experts * cfg.moe_intermediate_size,
            experts_held=cfg.experts_held, expert_offset=cfg.expert_offset,
            norm_topk=cfg.norm_topk_prob, scoring="sigmoid",
            routed_scale=cfg.routed_scaling_factor, shared_gate=False,
            balance_rate=cfg.balance_rate, kernel_initializer=init,
            name=f"{tag}_moe")
    return model.add(x, h, name=f"{tag}_ffn_add")


def build_glm4_moe_lite(model: FFModel, cfg: Glm4MoeLiteConfig,
                        seq_len: int):
    """Tokens (batch, 2 * seq_len) int32 (`mtp_labels`) -> logits (batch *
    2 * seq_len, vocab), a sample's main rows before its module's; train
    with `sparse_categorical_crossentropy` and `loss_weights(seq_len,
    cfg.mtp_loss_weight)`. Returns ({input name: shape}, logits tensor)."""
    if cfg.num_nextn_predict_layers != 1:
        raise NotImplementedError("one multi-token-prediction module")
    batch, d, eps = model.config.batch_size, cfg.hidden_size, cfg.rms_norm_eps
    init = NormInitializer(mean=0.0, stddev=cfg.initializer_range)
    tokens = model.create_tensor((batch, 2 * seq_len), dtype=jnp.int32,
                                 name="tokens")
    emb = model.embedding(tokens, cfg.vocab_size, d, aggr="none",
                          kernel_initializer=init, name="embed")
    x, nxt = model.split(emb, [seq_len, seq_len], axis=1, name="embed_split")
    for i in range(cfg.num_hidden_layers):
        x = _sparse_block(model, cfg, x, f"l{i}",
                          i < cfg.first_k_dense_replace, init)
    # the head's logits are the step's largest array: in the compute dtype
    main = model.rms_norm(x, eps, to_compute_dtype=True, zero_centered=False,
                          name="final_norm")
    # the multi-token-prediction module
    u = model.concat(
        [model.rms_norm(nxt, eps, zero_centered=False, name="mtp_enorm"),
         model.rms_norm(x, eps, zero_centered=False, name="mtp_hnorm")],
        axis=2, name="mtp_concat")
    u = model.dense(u, d, use_bias=False, kernel_initializer=init,
                    name="mtp_eh_proj")
    u = _sparse_block(model, cfg, u, "mtp", False, init)
    u = model.rms_norm(u, eps, to_compute_dtype=True, zero_centered=False,
                       name="mtp_final_norm")
    both = model.concat([main, u], axis=1, name="mtp_rows")
    both = model.reshape(both, (batch * 2 * seq_len, d),
                         name="fold_positions")
    logits = model.dense(both, cfg.vocab_size, use_bias=False,
                         kernel_initializer=init, name="head")
    return {"tokens": (batch, 2 * seq_len)}, logits
