"""The plain reference of the Qwen3-Next block stack: forward, loss,
gradients and Adam steps in straightforward `jax.numpy`, float32, matmul
precision "highest". No kernel, no chunked scan, no grouped product, no
line shared with the program: it imports nothing of `dlrm_flexflow_tpu`.
The tier-1 tests hold `models/qwen3_next.py` (the ops of `ops/`) to it.

What it computes (D = hidden_size, no bias anywhere):

    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)
    block i:   h = x + Mixer_i(RMSNorm(x));  y = h + MoE(RMSNorm(h))
    Mixer_i    full attention where (i + 1) % full_attention_interval == 0,
               else the gated delta net
    after the last block: RMSNorm, the untied head, mean next-token NLL

- gated delta net: the recurrence one position a step,
  S' = exp(g_t) S; u = beta_t (v_t - S'^T k_t); S = S' + k_t u^T;
  o_t = S^T q_t, per value head, S_0 = 0.
- gated attention: per-head RMS norm (1 + w) on q and k, rotate-half rotary
  embedding on the first `rotary_dim` features, causal softmax, each K/V
  head serving num_heads / num_kv_heads query heads, sigmoid output gate.
- experts: softmax over ALL `num_experts` router outputs, the `top_k`
  largest, normalised over the chosen; the experts numbered
  `expert_offset .. expert_offset + experts_held - 1` are held here and
  computed one after the other, each on every token with a weight that is
  zero where the token did not choose it; the experts held elsewhere add
  nothing. The shared expert, with its sigmoid gate, is added whole.

Only so that the published shapes fit a device's memory, a block is
recomputed in the backward (`jax.checkpoint`), the recurrence is recomputed
a `SEGMENT` of positions at a time, and the attention runs a block of
queries at a time. None of it changes a value.

The parameters are one tree, `{op name: {parameter name: array}}`, under
the names `models/qwen3_next.py` gives its ops (`embed`, `l<i>_mixer_norm`,
`l<i>_delta` or `l<i>_attn`, `l<i>_moe_norm`, `l<i>_moe`, `final_norm`,
`head`), so the system's `model.params` is the reference's input as it is.
The fused projections are laid out in blocks: `w_qkvz` = [q | k | v | z],
`w_ba` = [b | a]; `wq` is head-major with [query | gate] inside a head.

Adam as `core/optimizers.py:AdamOptimizer` has it (bias correction folded
into the rate); on the token table it is lazy, as the program's sparse row
update is: a row no token of the batch names keeps its weight, m and v.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

SEGMENT = 64        # positions of the recurrence recomputed together
QUERY_BLOCK = 512   # queries the attention attends with at a time


def is_full_attention(cfg: dict, i: int) -> bool:
    return (i + 1) % int(cfg["full_attention_interval"]) == 0


def mixer_name(cfg: dict, i: int) -> str:
    return f"l{i}_attn" if is_full_attention(cfg, i) else f"l{i}_delta"


def rms_norm(x, w, eps, zero_centered=True):
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y * (1.0 + w if zero_centered else w)


# --------------------------------------------------------------------------
# gated delta net
# --------------------------------------------------------------------------
def delta_rule(q, k, v, g, beta):
    """q, k (s, h, dk); v (s, h, dv); g, beta (s, h) -> o (s, h, dv)."""
    s, h, dk = q.shape
    dv = v.shape[-1]

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[:, None, None]
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    @jax.checkpoint
    def segment(S, xs):
        return lax.scan(step, S, xs)

    pad = (-s) % SEGMENT        # steps that leave the state as it is
    xs = tuple(jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
               .reshape((-1, SEGMENT) + t.shape[1:])
               for t in (q, k, v, g, beta))
    _, o = lax.scan(segment, jnp.zeros((h, dk, dv), q.dtype), xs)
    return o.reshape((-1, h, dv))[:s]


def gated_delta_net(p, x, cfg):
    """x (s, D) -> (s, D)."""
    hk, hv = int(cfg["linear_num_key_heads"]), int(
        cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(
        cfg["linear_value_head_dim"])
    width = int(cfg["linear_conv_kernel_dim"])
    kd, vd = hk * dk, hv * dv
    s = x.shape[0]
    qkvz = x @ p["w_qkvz"]
    ba = x @ p["w_ba"]
    qkv, z = qkvz[:, :2 * kd + vd], qkvz[:, 2 * kd + vd:]
    # causal depthwise convolution: y_t = sum_j conv[:, j] x_(t-width+1+j)
    padded = jnp.pad(qkv, ((width - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[j:j + s] * p["conv"][:, j]
                          for j in range(width)))

    def l2(t):
        return t * lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True)
                             + 1e-6)

    q = l2(qkv[:, :kd].reshape(s, hk, dk)) * dk ** -0.5
    k = l2(qkv[:, kd:2 * kd].reshape(s, hk, dk))
    # key head j serves value heads j * rep .. j * rep + rep - 1
    q, k = (jnp.repeat(t, hv // hk, axis=1) for t in (q, k))
    v = qkv[:, 2 * kd:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    o = (rms_norm(o, p["norm"], cfg["rms_norm_eps"], zero_centered=False)
         * jax.nn.silu(z.reshape(s, hv, dv)))
    return o.reshape(s, vd) @ p["w_out"]


# --------------------------------------------------------------------------
# gated attention
# --------------------------------------------------------------------------
def rotary(x, rotary_dim, theta):
    """Rotate-half on the first `rotary_dim` features of x (s, h, hd),
    positions 0..s-1."""
    s = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                          / rotary_dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :].astype(x.dtype)
    xr, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    rot = jnp.concatenate([-xr[..., half:], xr[..., :half]], axis=-1)
    return jnp.concatenate([xr * jnp.cos(ang) + rot * jnp.sin(ang), rest],
                           axis=-1)


def causal_attention(q, k, v):
    """q (s, h, hd); k, v (s, hk, hd) -> (s, h, hd)."""
    s, h, hd = q.shape
    hk = k.shape[1]
    k, v = (jnp.repeat(t, h // hk, axis=1) for t in (k, v))
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def attend(qb, first):
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / hd ** 0.5
        seen = (jnp.arange(s)[None, :]
                <= first + jnp.arange(block)[:, None])
        p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = lax.map(lambda a: attend(*a),
                  (q.reshape(-1, block, h, hd),
                   jnp.arange(0, s, block)))
    return out.reshape(s, h, hd)


def gated_attention(p, x, cfg):
    h, hk = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    rd = int(hd * float(cfg["partial_rotary_factor"]))
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s = x.shape[0]
    qg = (x @ p["wq"]).reshape(s, h, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (x @ p["wk"]).reshape(s, hk, hd)
    v = (x @ p["wv"]).reshape(s, hk, hd)
    q = rotary(rms_norm(q, p["q_norm"], eps), rd, theta)
    k = rotary(rms_norm(k, p["k_norm"], eps), rd, theta)
    out = causal_attention(q, k, v) * jax.nn.sigmoid(gate)
    return out.reshape(s, h * hd) @ p["wo"]


# --------------------------------------------------------------------------
# experts
# --------------------------------------------------------------------------
def route(p, x, cfg):
    """(weights (t, k), experts (t, k)) of every token, over all experts."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top_p, top_e = lax.top_k(probs, int(cfg["num_experts_per_tok"]))
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_e


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe(p, x, cfg):
    """x (t, D) -> (out (t, D), pairs each held expert was given
    (experts_held,))."""
    held = p["w_gate"].shape[0]
    top_p, top_e = route(p, x, cfg)

    def one_expert(acc, e):
        wg, wu, wd, number = e
        w = jnp.sum(jnp.where(top_e == number, top_p, 0.0), axis=-1)
        return acc + w[:, None] * swiglu(x, wg, wu, wd), jnp.sum(
            top_e == number)

    numbers = int(cfg["expert_offset"]) + jnp.arange(held)
    routed, pairs = lax.scan(one_expert, jnp.zeros_like(x),
                             (p["w_gate"], p["w_up"], p["w_down"], numbers))
    gate = jax.nn.sigmoid(x @ p["shared_router"])[:, None]
    shared = gate * swiglu(x, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
    return routed + shared, pairs


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------
def forward(params, tokens, cfg):
    """tokens (b, s) int -> (logits (b * s, vocab), pairs routed to each
    held expert (layers, experts_held))."""
    eps = cfg["rms_norm_eps"]

    @partial(jax.checkpoint, static_argnums=(2,))
    def block(p, x, i):
        mixer = gated_attention if is_full_attention(cfg, i) \
            else gated_delta_net
        h = x + mixer(p["mixer"], rms_norm(x, p["mixer_norm"], eps), cfg)
        out, pairs = moe(p["moe"], rms_norm(h, p["moe_norm"], eps), cfg)
        return h + out, pairs

    def one_sequence(seq):
        x = params["embed"]["kernel"][seq]
        pairs = []
        for i in range(int(cfg["num_hidden_layers"])):
            p = {"mixer": params[mixer_name(cfg, i)],
                 "mixer_norm": params[f"l{i}_mixer_norm"]["weight"],
                 "moe": params[f"l{i}_moe"],
                 "moe_norm": params[f"l{i}_moe_norm"]["weight"]}
            x, n = block(p, x, i)
            pairs.append(n)
        x = rms_norm(x, params["final_norm"]["weight"], eps)
        return x @ params["head"]["kernel"], jnp.stack(pairs)

    logits, pairs = zip(*(one_sequence(seq) for seq in tokens))
    return jnp.concatenate(logits), sum(pairs)


def loss_fn(params, tokens, labels, cfg):
    """Mean next-token negative log-likelihood, and the pairs routed."""
    logits, pairs = forward(params, tokens, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels.reshape(-1, 1), axis=-1)
    return jnp.mean(nll), pairs


def adam_update(w, g, m, v, t, opt):
    """One Adam step on one array; `t` the step's number, from 1."""
    b1, b2 = opt["beta1"], opt["beta2"]
    rate = opt["alpha"] * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    return w - rate * m / (jnp.sqrt(v) + opt["epsilon"]), m, v


def adam_step(params, m, v, t, tokens, labels, cfg, opt):
    """One training step. Returns (loss before the step, pairs routed,
    params, m, v after it)."""
    with jax.default_matmul_precision("highest"):
        (loss, pairs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, labels, cfg)
        new = jax.tree.map(lambda *a: adam_update(*a, t, opt),
                           params, grads, m, v)
    pick = lambda i: jax.tree.map(  # noqa: E731
        lambda x: x[i], new, is_leaf=lambda x: isinstance(x, tuple))
    new_p, new_m, new_v = pick(0), pick(1), pick(2)
    # lazy on the token table: a row no token names keeps all three
    named = jnp.zeros((params["embed"]["kernel"].shape[0], 1), bool
                      ).at[tokens.reshape(-1)].set(True)
    for old, cur in ((params, new_p), (m, new_m), (v, new_v)):
        cur["embed"] = {"kernel": jnp.where(named, cur["embed"]["kernel"],
                                            old["embed"]["kernel"])}
    return loss, pairs, new_p, new_m, new_v
