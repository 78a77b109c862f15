"""The plain reference of the GLM-4.7-Flash block stack: forward, loss,
gradients, the router's bias update and Adam steps in straightforward
`jax.numpy`, float32, matmul precision "highest". No kernel, no sorted
walk, no concatenated passes, no line shared with the program: it imports
nothing of `dlrm_flexflow_tpu`. The tier-1 tests hold
`models/glm4_moe_lite.py` (the ops of `ops/`) to it.

What it computes (D = hidden_size, no bias in any product, eps 1e-5):

    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w              (w init 1)
    block i:   h = x + MLA(RMSNorm(x));  y = h + F_i(RMSNorm(h))
    F_i        a dense SwiGLU for i < first_k_dense_replace, else the
               expert layer
    after the last block: RMSNorm, the untied head, mean next-token NLL

- MLA, expanded: c_q = RMSNorm(x W_qa); q = c_q W_qb, a head [nope | rope];
  [c_kv | k_rope] = x W_kva; c_kv = RMSNorm(c_kv); c_kv W_kvb, a head
  [k_nope | v]; rotate-half rotary on q_rope of every head and on the ONE
  k_rope all heads share; causal softmax of q . k / sqrt(nope + rope).
- experts: s = sigmoid(x W_r) over ALL `n_routed_experts`; chosen = top-k
  of s + b; w = s[chosen] / (sum + 1e-20) * routed_scaling_factor; the
  experts numbered `expert_offset .. expert_offset + experts_held - 1` are
  held here, computed one after the other, each on every token with a
  weight that is zero where the token did not choose it; the experts held
  elsewhere add nothing. The shared expert is added whole, ungated.
- the bias b (one a sparse layer, no gradient): after a step,
  b_e += gamma * sign(mean(c) - c_e), c the pairs the step routed to each
  of ALL the experts.
- the multi-token-prediction module (DeepSeek-V3's report, 2.2): with h the
  last block's output before the final norm and t the tokens,
  u_i = W_eh [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)], one more sparse block,
  a norm, the main model's head; it predicts t_{i+2} for i = 0..S-2.
  L = L_main + lambda L_mtp, each a mean over its own targets.

The parameters are one tree, `{op name: {parameter name: array}}`, under
the names `models/glm4_moe_lite.py` gives its ops, so the system's
`model.params` is the reference's input as it is; the biases are `{expert
op name: (n_routed_experts,)}`. Fused columns are laid out in blocks:
`wq_b` head-major with [nope | rope] inside a head, `wkv_a` = [c_kv |
k_rope], `wkv_b` head-major with [k_nope | v] inside a head, `mtp_eh_proj`
= rows [embedding | hidden].

Adam as `core/optimizers.py:AdamOptimizer` has it (bias correction folded
into the rate); on the token table it is lazy, as the program's sparse row
update is: a row no token of the batch names keeps its weight, m and v.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 512   # queries the attention attends with at a time


def expert_layers(cfg: dict) -> list:
    """The expert ops' names, in the order the counts are stacked."""
    return [f"l{i}_moe" for i in range(int(cfg["first_k_dense_replace"]),
                                       int(cfg["num_hidden_layers"]))
            ] + ["mtp_moe"]


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w


def rotary(x, theta):
    """Rotate-half on all features of x (s, h, rd), positions 0..s-1."""
    s, rd = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :].astype(x.dtype)
    rot = jnp.concatenate([-x[..., rd // 2:], x[..., :rd // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def causal_attention(q, k, v):
    """q, k (s, h, hd); v (s, h, vd) -> (s, h, vd), a block of queries at a
    time."""
    s, h, hd = q.shape
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def attend(qb, first):
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / hd ** 0.5
        seen = (jnp.arange(s)[None, :]
                <= first + jnp.arange(block)[:, None])
        p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = lax.map(lambda a: attend(*a),
                  (q.reshape(-1, block, h, hd), jnp.arange(0, s, block)))
    return out.reshape(s, h, v.shape[-1])


def mla(p, x, cfg):
    """x (s, D) -> (s, D)."""
    h = int(cfg["num_attention_heads"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vd, rank = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s = x.shape[0]
    q = (rms_norm(x @ p["wq_a"], p["q_norm"], eps) @ p["wq_b"]).reshape(
        s, h, nope + rope)
    ckr = x @ p["wkv_a"]
    kv = (rms_norm(ckr[:, :rank], p["kv_norm"], eps) @ p["wkv_b"]).reshape(
        s, h, nope + vd)
    k_rope = rotary(ckr[:, None, rank:], theta)        # one head for all
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (s, h, rope))], -1)
    out = causal_attention(q, k, kv[..., nope:])
    return out.reshape(s, h * vd) @ p["wo"]


def route(p, x, cfg, bias):
    """(weights (t, k), experts (t, k)) of every token, over all experts:
    the choice on score + bias, the weights from the bare scores."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, top_e = lax.top_k(scores + bias, int(cfg["num_experts_per_tok"]))
    top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
    return top_p * float(cfg["routed_scaling_factor"]), top_e


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe(p, x, cfg, bias):
    """x (t, D) -> (out (t, D), pairs each held expert was given
    (experts_held,), pairs each of ALL experts was given
    (n_routed_experts,))."""
    held = p["w_gate"].shape[0]
    top_p, top_e = route(p, x, cfg, bias)

    def one_expert(acc, e):
        wg, wu, wd, number = e
        w = jnp.sum(jnp.where(top_e == number, top_p, 0.0), axis=-1)
        return acc + w[:, None] * swiglu(x, wg, wu, wd), jnp.sum(
            top_e == number)

    numbers = int(cfg["expert_offset"]) + jnp.arange(held)
    routed, pairs = lax.scan(one_expert, jnp.zeros_like(x),
                             (p["w_gate"], p["w_up"], p["w_down"], numbers))
    load = jnp.sum(top_e.reshape(-1, 1) == jnp.arange(
        int(cfg["n_routed_experts"])), axis=0)
    shared = swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    return routed + shared, pairs, load


def block(params, biases, x, tag, cfg):
    """One block under the builder's names `<tag>_...`: (y, (pairs, load)
    of its expert layer or None for a dense one)."""
    eps = cfg["rms_norm_eps"]
    h = x + mla(params[f"{tag}_mla"],
                rms_norm(x, params[f"{tag}_mixer_norm"]["weight"], eps), cfg)
    n = rms_norm(h, params[f"{tag}_ffn_norm"]["weight"], eps)
    if f"{tag}_mlp" in params:
        return h + swiglu(n, **params[f"{tag}_mlp"]), None
    out, pairs, load = moe(params[f"{tag}_moe"], n, cfg,
                           biases[f"{tag}_moe"])
    return h + out, (pairs, load)


def nll(params, x, targets):
    """Summed negative log-likelihood of `targets` (n,) under the head's
    logits of x (n, D), already normed."""
    logp = jax.nn.log_softmax(x @ params["head"]["kernel"], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss_fn(params, tokens, cfg, biases):
    """tokens (b, s + 1) int -> (L, (pairs (layers, held), loads (layers,
    experts), L_main, L_mtp)), the layers as `expert_layers` orders them.
    A block is recomputed in the backward, which changes no value."""
    eps, emb = cfg["rms_norm_eps"], params["embed"]["kernel"]
    run = jax.checkpoint(lambda p, x, tag: block(p, biases, x, tag, cfg),
                         static_argnums=(2,))

    def one_sequence(t):
        x, counts = emb[t[:-1]], []
        for i in range(int(cfg["num_hidden_layers"])):
            x, n = run(params, x, f"l{i}")
            counts += [n] if n is not None else []
        main = nll(params, rms_norm(x, params["final_norm"]["weight"], eps),
                   t[1:])
        u = jnp.concatenate(
            [rms_norm(emb[t[1:]], params["mtp_enorm"]["weight"], eps),
             rms_norm(x, params["mtp_hnorm"]["weight"], eps)], axis=-1
        ) @ params["mtp_eh_proj"]["kernel"]
        u, n = run(params, u, "mtp")
        u = rms_norm(u, params["mtp_final_norm"]["weight"], eps)
        # position i predicts t[i + 2]; the last position has no target
        mtp = nll(params, u[:-1], t[2:])
        pairs, loads = zip(*(counts + [n]))
        return main, mtp, jnp.stack(pairs), jnp.stack(loads)

    main, mtp, pairs, loads = zip(*(one_sequence(t) for t in tokens))
    b, s = tokens.shape[0], tokens.shape[1] - 1
    l_main, l_mtp = sum(main) / (b * s), sum(mtp) / (b * (s - 1))
    return (l_main + float(cfg["mtp_loss_weight"]) * l_mtp,
            (sum(pairs), sum(loads), l_main, l_mtp))


def bias_update(biases, loads, cfg):
    """b_e += gamma * sign(mean(c) - c_e), a layer at a time."""
    gamma = float(cfg["balance_rate"])
    return {name: biases[name] + gamma * jnp.sign(
        jnp.mean(c.astype(jnp.float32)) - c)
        for name, c in zip(expert_layers(cfg), loads)}


def adam_update(w, g, m, v, t, opt):
    """One Adam step on one array; `t` the step's number, from 1."""
    b1, b2 = opt["beta1"], opt["beta2"]
    rate = opt["alpha"] * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    return w - rate * m / (jnp.sqrt(v) + opt["epsilon"]), m, v


def adam_step(params, m, v, biases, t, tokens, cfg, opt):
    """One training step: (loss, (pairs, loads, L_main, L_mtp), params, m,
    v, biases) after it."""
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, cfg, biases)
    new = jax.tree.map(lambda *a: adam_update(*a, t, opt), params, grads,
                       m, v)
    pick = lambda i: jax.tree.map(  # noqa: E731
        lambda x: x[i], new, is_leaf=lambda x: isinstance(x, tuple))
    p2, m2, v2 = pick(0), pick(1), pick(2)
    # lazy on the token table: a row no token names keeps everything
    named = jnp.zeros((params["embed"]["kernel"].shape[0], 1), bool
                      ).at[tokens.reshape(-1)].set(True)
    for new_tree, old_tree in ((p2, params), (m2, m), (v2, v)):
        new_tree["embed"]["kernel"] = jnp.where(
            named, new_tree["embed"]["kernel"], old_tree["embed"]["kernel"])
    return loss, aux, p2, m2, v2, bias_update(biases, aux[1], cfg)
