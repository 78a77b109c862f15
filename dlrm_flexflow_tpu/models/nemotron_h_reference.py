"""The plain reference of the Nemotron-H block stack: forward, loss,
gradients, the router's bias update and Adam steps in straightforward
`jax.numpy`, float32, matmul precision "highest". No kernel, no chunking
(the state-space recurrence a position at a time), no sorted walk, no line
shared with the program: it imports nothing of `dlrm_flexflow_tpu`. The
tier-1 tests hold `models/nemotron_h.py` (the ops of `ops/`) to it.

What it computes (no bias in any product, eps 1e-5):

    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w              (w init 1)
    layer i:   x = x + Mixer_i(RMSNorm_i(x)), Mixer_i by letter i of
               `hybrid_override_pattern`: M, E or *
    after the last layer: RMSNorm, the untied head, mean next-token NLL

- M, Mamba-2 (H heads of P, G groups, N states): [z | xBC | dt] = u W_in;
  xBC = silu(conv(xBC) + b), causal, depthwise; xBC = [x (H P) | B (G N) |
  C (G N)], head h reads group h // (H / G); dt = softplus(dt + dt_bias);
  per head, S_0 = 0: S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T, A =
  -exp(A_log); y_t = S_t C_t + D x_t; then y = RMSNorm_g(y * silu(z)), the
  mean square over each group of H P / G features, the gate BEFORE the
  norm; out = y W_out.
- *, attention: q = u W_q (h heads of hd), k, v = u W_k, u W_v (hk heads),
  query head j reads KV head j // (h / hk); causal softmax of q . k /
  sqrt(hd); out = concat(o) W_o. No position embedding.
- E, experts: s = sigmoid(u W_r) over ALL `n_routed_experts`; chosen =
  top-k of s + b; w = s[chosen] / (sum + 1e-20) * routed_scaling_factor;
  E_e(u) = relu(u W_up,e)^2 W_down,e; the experts numbered `expert_offset
  .. expert_offset + experts_held - 1` are held here, computed one after
  the other, each on every token with a weight that is zero where the token
  did not choose it; the experts held elsewhere add nothing. The shared
  expert, of the same form, is added whole.
- the bias b (one an expert layer, no gradient): after a step,
  b_e += gamma * sign(mean(c) - c_e), c the pairs the step routed to each
  of ALL the experts.

The parameters are one tree, `{op name: {parameter name: array}}`, under
the names `models/nemotron_h.py` gives its ops, so the system's
`model.params` is the reference's input as it is; the biases are `{expert
op name: (n_routed_experts,)}`.

Adam as `core/optimizers.py:AdamOptimizer` has it (bias correction folded
into the rate); on the token table it is lazy, as the program's sparse row
update is: a row no token of the batch names keeps its weight, m and v.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def mixer_names(cfg: dict) -> list:
    return [f"l{i}_{KINDS[c]}"
            for i, c in enumerate(cfg["hybrid_override_pattern"])]


def expert_layers(cfg: dict) -> list:
    """The expert ops' names, in the order the counts are stacked."""
    return [n for n in mixer_names(cfg) if n.endswith("_moe")]


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w


def causal_conv(x, w, b):
    """x (s, c), w (c, width), b (c,): y_t = sum_j w[:, j] x_(t - width +
    1 + j) + b, zeros before the sequence."""
    width, s = w.shape[1], x.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return sum(xp[j:j + s] * w[:, j] for j in range(width)) + b


def ssm(x, dt, A, B, C):
    """The recurrence, a position at a time. x (s, h, p); dt (s, h); A
    (h,); B, C (s, h, n), already one a head. -> y (s, h, p)."""
    def step(S, xs):
        xt, dtt, Bt, Ct = xs
        S = (jnp.exp(dtt * A)[:, None, None] * S
             + (dtt[:, None] * xt)[:, :, None] * Bt[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, Ct)

    S0 = jnp.zeros((x.shape[1], x.shape[2], B.shape[-1]), x.dtype)
    return lax.scan(step, S0, (x, dt, B, C))[1]


def gated_norm(y, z, w, groups, eps):
    """y, z (s, d): RMSNorm(y * silu(z)) * w, the gate BEFORE the norm, the
    mean square over each of `groups` runs of d / groups features."""
    s, d = y.shape
    y = (y * jax.nn.silu(z)).reshape(s, groups, d // groups)
    return rms_norm(y, 1.0, eps).reshape(s, d) * w


def mamba(p, u, cfg):
    """u (s, D) -> (s, D)."""
    h, hp = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    g, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    s, di = u.shape[0], h * hp
    zxbcdt = u @ p["w_in"]
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * g * n],
                  zxbcdt[:, 2 * di + 2 * g * n:])
    xbc = jax.nn.silu(causal_conv(xbc, p["conv"], p["conv_bias"]))
    x = xbc[:, :di].reshape(s, h, hp)
    B, C = (jnp.repeat(t.reshape(s, g, n), h // g, axis=1)
            for t in (xbc[:, di:di + g * n], xbc[:, di + g * n:]))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssm(x, dt, -jnp.exp(p["A_log"]), B, C) + p["D"][:, None] * x
    y = gated_norm(y.reshape(s, di), z, p["norm"], g,
                   cfg["layer_norm_epsilon"])
    return y @ p["w_out"]


def attention(p, u, cfg):
    """u (s, D) -> (s, D): causal grouped-query attention, nothing else."""
    h, hk = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd, s = int(cfg["head_dim"]), u.shape[0]
    q = (u @ p["wq"]).reshape(s, h, hd)
    k, v = (jnp.repeat((u @ p[w]).reshape(s, hk, hd), h // hk, axis=1)
            for w in ("wk", "wv"))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / hd ** 0.5
    seen = jnp.tril(jnp.ones((s, s), bool))
    prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", prob, v).reshape(s, h * hd) @ p["wo"]


def route(p, x, cfg, bias):
    """(weights (t, k), experts (t, k)) of every token, over all experts:
    the choice on score + bias, the weights from the bare scores."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, top_e = lax.top_k(scores + bias, int(cfg["num_experts_per_tok"]))
    top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
    return top_p * float(cfg["routed_scaling_factor"]), top_e


def relu2_mlp(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def moe(p, x, cfg, bias):
    """x (t, D) -> (out (t, D), pairs each held expert was given
    (experts_held,), pairs each of ALL experts was given
    (n_routed_experts,))."""
    held = p["w_up"].shape[0]
    top_p, top_e = route(p, x, cfg, bias)

    def one_expert(acc, e):
        wu, wd, number = e
        w = jnp.sum(jnp.where(top_e == number, top_p, 0.0), axis=-1)
        return acc + w[:, None] * relu2_mlp(x, wu, wd), jnp.sum(
            top_e == number)

    numbers = int(cfg["expert_offset"]) + jnp.arange(held)
    routed, pairs = lax.scan(one_expert, jnp.zeros_like(x),
                             (p["w_up"], p["w_down"], numbers))
    load = jnp.sum(top_e.reshape(-1, 1) == jnp.arange(
        int(cfg["n_routed_experts"])), axis=0)
    return (routed + relu2_mlp(x, p["shared_up"], p["shared_down"]), pairs,
            load)


def loss_fn(params, tokens, cfg, biases):
    """tokens (b, s + 1) int -> (mean next-token NLL, (pairs (layers,
    held), loads (layers, experts))), the layers as `expert_layers` orders
    them."""
    eps = cfg["layer_norm_epsilon"]

    def one_sequence(t):
        x, counts = params["embed"]["kernel"][t[:-1]], []
        for i, name in enumerate(mixer_names(cfg)):
            u = rms_norm(x, params[f"l{i}_norm"]["weight"], eps)
            if name.endswith("_mamba"):
                x = x + mamba(params[name], u, cfg)
            elif name.endswith("_attn"):
                x = x + attention(params[name], u, cfg)
            else:
                out, pairs, load = moe(params[name], u, cfg, biases[name])
                x, counts = x + out, counts + [(pairs, load)]
        x = rms_norm(x, params["final_norm"]["weight"], eps)
        logp = jax.nn.log_softmax(x @ params["head"]["kernel"], axis=-1)
        nll = -jnp.sum(jnp.take_along_axis(logp, t[1:, None], axis=-1))
        pairs, loads = zip(*counts)
        return nll, jnp.stack(pairs), jnp.stack(loads)

    nll, pairs, loads = zip(*(one_sequence(t) for t in tokens))
    return (sum(nll) / (tokens.shape[0] * (tokens.shape[1] - 1)),
            (sum(pairs), sum(loads)))


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
    """One training step: (loss, (pairs, loads), params, m, v, biases)
    after it."""
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
                      ).at[tokens[:, :-1].reshape(-1)].set(True)
    for new_tree, old_tree in ((p2, params), (m2, m), (v2, v)):
        new_tree["embed"]["kernel"] = jnp.where(
            named, new_tree["embed"]["kernel"], old_tree["embed"]["kernel"])
    return loss, aux, p2, m2, v2, bias_update(biases, aux[1], cfg)
