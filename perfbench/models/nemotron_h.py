"""The Nemotron-H family (`model_type: nemotron_h`): what every
configuration of it in the benchmark shares.

1. how a configuration file becomes the system under test, through the
   program's own front door (`FFConfig` -> `FFModel` -> `build_nemotron_h`
   -> `compile(AdamOptimizer, sparse_categorical_crossentropy)` ->
   `init_layers(seed)`), one chip's share of the stated deployment;
2. the plain reference (the benchmark's own copy of
   `dlrm_flexflow_tpu/models/nemotron_h_reference.py`, so that later PRs may
   change the program and not the yardstick): forward, loss, gradients, the
   router's bias update and Adam steps in straightforward `jax.numpy`,
   float32, matmul precision "highest", the experts one after the other, no
   kernel, no chunk states, no line shared with the program;
3. what the check reads of the system (every parameter, Adam's m and v, the
   step count, the expert ops' counters and bias buffers) and what it
   compares;
4. the operations and bytes one training step needs, from the shapes.

How the check fits the chip. At the published widths the state is 8.0 GB
(weights, m, v) and the reference needs its weights and their gradient,
5.3 GB, beside its activations. So the snapshot is read to the HOST before
the checked steps; `verify`, which runs after the windows and the trace,
first RELEASES the system's device state (`build` kept the handle;
everything it still needs was read right after the checked steps) and then
runs the reference on the device with the weights and their gradient
resident and m, v streamed through, one op's parameters at a time. A layer
and the head are recomputed in the backward and the attention runs a block
of queries at a time: none of it changes a value. The state-space
recurrence a position at a time would keep 8,192 states of 2 MB a layer
for its backward (17 GB), so a Mamba-2 layer is computed in its DUAL form,

    y_t = sum over s <= t of (C_t . B_s) exp(sum of dt_r A over s < r <= t)
          dt_s x_s  +  D x_t,

the same sums with no state at all, a block of query positions and a group
of heads at a time (`ssd_dual`); `ssm` is the recurrence as written, and a
CPU test holds the one to the other.

Only `build` imports the program; the module itself imports without it.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# the key of `fit`'s per-epoch report that is the training loss: the mean
# next-token negative log-likelihood over the held slice of the vocabulary
LOSS_METRIC = "sparse_cce"

# ---- the limits of the check, each with its reason -----------------------
# The system multiplies in bf16 with fp32 accumulation and keeps the
# residual stream, the norms, the router, the recurrence's state and decays,
# the loss and Adam in fp32; the reference is fp32 throughout. Every reading
# below is from PERF.md section 6, PR 32: runs of the cell on the v5e, each
# seed its own (twelve; eight when the limits were set), and on two the
# reference computed once more in bfloat16 (weights, state and all) and
# taken as if it were the system.
# The loss is a mean over 8,192 tokens of a log-softmax over a final norm,
# so the roundings largely average out: the runs read 3.9e-5 to 2.5e-4,
# the reference in bfloat16 5.9e-2 and 6.3e-2.
LOSS_RTOL = 1e-3
# Adam divides the gradient by its own running size, so every element moves
# by about `alpha` a step whatever its gradient, and elementwise limits mean
# nothing. What holds is the direction and the size of a whole parameter's
# update: its cosine with the reference's and its slope on it (1 where the
# sizes agree). The large parameters are judged each; the small ones of the
# state-space layers (`A_log`, `dt_bias`, `D`, the convolution and its
# bias, the gated norm's scale: a few thousand numbers that steer
# everything) each BY NAME, the layers' copies as one vector; the block
# norms as one vector. The runs read a cosine of 0.9877 and more (the worst
# is always a router's 344,064 weights; the parameters judged by name
# 0.9999) and a slope within 0.019 of 1 (by name within 0.0011); the
# reference in bfloat16 a cosine of -0.65 and -0.67 and a slope of -3.2 and
# -3.4 (the gated norm's scale; `A_log` 0.08, `dt_bias` 0.002, the
# convolution 0.01); an update without Adam's division reads a cosine of
# 0.1, one applied twice a slope of 2.
UPDATE_COS_MIN = 0.8
UPDATE_SLOPE_TOL = 0.2
SMALL = 65536          # elements; parameters under this are judged together
BY_NAME = ("A_log", "dt_bias", "D", "conv", "conv_bias", "norm")
# The token table is updated lazily: a row no token of the batch names must
# keep its bits, and a named row must move wherever the reference's moves.
# The router runs in fp32 on fp32 activations; what the bf16 products of the
# layers before it change in its inputs flips a token's sixth expert now
# and then. The pairs each held expert was given over the checked steps,
# and the pairs each of ALL the experts was given (`load`), are compared
# with the reference's own counts. Both are shares of ALL the pairs the
# router made (3 steps x 8,192 tokens x 6 x 4 layers = 589,824: a number no
# seed can make small; the held experts' own pairs, 6% of them when the
# router is even, were 19,517 to 28,041 in the runs and nothing keeps a
# seed from making them a few hundred, where a share of them would swing).
# A pair that went elsewhere is missing from one expert and extra at
# another, so the load's differences are halved. The runs read 9.0e-5 to
# 1.5e-4 of all pairs on the held experts and 1.5e-3 to 1.9e-3 over all
# experts; the reference in bfloat16 1.1e-3 and 1.3e-3, 1.3e-2 and 1.9e-2.
# Each limit lies a factor of three from the readings on both sides.
ROUTING_MISMATCH_MAX = 4e-4
LOAD_MISMATCH_MAX = 5e-3
# The bias: after the checked steps every expert's buffer is the snapshot's
# plus or minus gamma a step, by the sign of mean load - its load. A flipped
# pair flips that sign only for an expert whose load lies within a few
# pairs of the mean, so a few buffers in a hundred differ by 2 gamma; an
# update left out, of the wrong sign, or taken from the held experts' loads
# alone moves most of them. The share of (layer, expert) buffers that lie
# further than gamma / 2 from the reference's: 10 to 19 of 512 in the runs
# (2.0% to 3.7%), 38 and 48 of 512 for the reference in bfloat16 (7.4% and
# 9.4%: the precision moves it little, and this limit is one the bfloat16
# reference passes), 1.0 for buffers left as they were: the limit lies
# between the reading and 1, nearer the reading.
BIAS_MISMATCH_MAX = 0.1
# Those counts cannot tell a router computed in bf16 from the bf16 products
# before it. So the router is also asked directly: `snapshot` gives the
# first expert op's own `route` a seeded unit-RMS input AND a seeded
# non-zero bias, `verify` gives the reference's the same. Both are fp32, so
# the weights of the chosen experts agree to rounding (the runs: 0.0, and
# no token's choice differs); the reference in bfloat16 is off by 3.2e-3
# and 3.7e-3 and chooses other experts for 6.5% and 7.4% of the tokens;
# weights taken from score + bias (and not from the bare score) are off by
# 0.1.
PROBE_TOKENS = 1024
PROBE_BIAS = 0.1
PROBE_WEIGHT_ATOL = 1e-4
PROBE_MISMATCH_MAX = 0.005      # tokens whose chosen experts differ
# Weights, m and v are stated fp32: some element of every large array must
# use the 16 mantissa bits bfloat16 lacks (the reference in bfloat16: all 34
# large arrays fail). `STATE_SAMPLE` of them are looked at, spread over the
# whole array: an expert no token has chosen yet has an m and a v of exact
# zeros (under this router some experts of a layer go without a pair for
# many steps), and zeros say nothing of a precision.
STATE_SAMPLE = 1 << 20

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


# --------------------------------------------------------------------------
# configuration -> sizes
# --------------------------------------------------------------------------
def held_table_rows(config: dict, chips: int) -> List[int]:
    """Rows of the token table (and columns of the head) held here: the
    configuration states this chip's slice of the vocabulary itself."""
    return [int(config["vocab_size"])]


def input_fields(config: dict, rows: List[int]) -> List[dict]:
    """One field of token ids, a sequence and its next token a sample:
    seq_len + 1 ids, the generator's `bag`."""
    return [{"name": "tokens", "kind": "ids", "rows": rows,
             "bag": int(config["seq_len"]) + 1}]


def fit_arrays(data: Dict[str, np.ndarray]):
    """(inputs, labels) as `FFModel.fit` takes them: ids tok_0..tok_{S-1},
    labels tok_1..tok_S."""
    t = data["tokens"][:, 0, :]
    return ({"tokens": np.ascontiguousarray(t[:, :-1])},
            np.ascontiguousarray(t[:, 1:]))


def model_config(config: dict, vocab: int) -> dict:
    """The keys the builder and the reference read, with what this chip
    holds: `n_routed_experts` in the file counts the experts HELD (it is
    listed in `reduced`); the router keeps the published width."""
    out = {k: v for k, v in config.items()
           if isinstance(v, (int, float, bool))}
    out.update(hybrid_override_pattern=str(config["hybrid_override_pattern"]),
               vocab_size=int(vocab),
               n_routed_experts=int(config["published"]["n_routed_experts"]),
               experts_held=int(config["n_routed_experts"]),
               expert_offset=int(config["expert_offset"]))
    return out


def mixer_names(cfg: dict) -> List[str]:
    return [f"l{i}_{KINDS[c]}"
            for i, c in enumerate(cfg["hybrid_override_pattern"])]


def expert_layers(cfg: dict) -> List[str]:
    """The expert ops' names, in the order the counts are stacked."""
    return [n for n in mixer_names(cfg) if n.endswith("_moe")]


_BUILT = {}      # the handle `build` made: the counters' readers and the
                 # release in `verify` reach the system through it


def build(config: dict, rows: List[int], batch: int, chips: int, seed: int):
    """The system under test. Returns (model, timings) with the seconds of
    graph build + compile() and of init_layers()."""
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.nemotron_h import (NemotronHConfig,
                                                     build_nemotron_h)

    opt = config["optimizer"]
    if opt["type"] != "adam" or config["loss"] != (
            "sparse_categorical_crossentropy"):
        raise ValueError("the family's reference knows Adam and "
                         "sparse_categorical_crossentropy only")
    if config["mlp_hidden_act"] != "relu2" or config["use_conv_bias"] is not (
            True) or int(config["n_group"]) != 1:
        raise ValueError("the family's reference knows relu2 experts, a "
                         "convolution with a bias and no group limit only")
    if chips != 1:
        raise NotImplementedError(
            "the expert op has no exchange yet: one chip a cell")
    t0 = time.time()
    cfg = ff.FFConfig.parse_args(
        ["-b", str(batch), "--compute-dtype", config["compute_dtype"]])
    model = ff.FFModel(cfg)
    build_nemotron_h(model, NemotronHConfig.from_dict(
        model_config(config, rows[0])), int(config["seq_len"]))
    model.compile(
        ff.AdamOptimizer(alpha=opt["alpha"], beta1=opt["beta1"],
                         beta2=opt["beta2"], epsilon=opt["epsilon"]),
        config["loss"], [config["loss"]],
        mesh=ff.make_mesh(num_devices=chips))
    t1 = time.time()
    model.init_layers(seed)
    jax.block_until_ready(model.params)
    _BUILT["model"] = model
    return model, {"build_s": t1 - t0, "init_s": time.time() - t1}


def expert_counters(model=None) -> Dict[str, dict]:
    """{expert op: {"tokens", "pairs" (held,), "rows", "load" (all
    experts,), "bias" (all experts,)}}, the counts cumulative since init:
    the program's `FFModel.expert_stats()` of the model `build` made."""
    model = model or _BUILT.get("model")
    return {} if model is None else model.expert_stats()


# --------------------------------------------------------------------------
# the plain reference (a copy of models/nemotron_h_reference.py, the
# recurrence in its dual form)
# --------------------------------------------------------------------------
QUERY_BLOCK = 256   # queries the attention attends with at a time
SSD_BLOCK = 256     # positions the dual form computes at a time


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
    """The recurrence as written, a position at a time. x (s, h, p); dt
    (s, h); A (h,); B, C (s, g, n), head j reading group j // (h / g).
    -> y (s, h, p), without the skip."""
    r = x.shape[1] // B.shape[1]

    def step(S, xs):
        xt, dtt, Bt, Ct = xs
        Bt, Ct = jnp.repeat(Bt, r, axis=0), jnp.repeat(Ct, r, axis=0)
        S = (jnp.exp(dtt * A)[:, None, None] * S
             + (dtt[:, None] * xt)[:, :, None] * Bt[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, Ct)

    S0 = jnp.zeros((x.shape[1], x.shape[2], B.shape[-1]), x.dtype)
    return lax.scan(step, S0, (x, dt, B, C))[1]


def ssd_dual(x, dt, A, B, C):
    """The same y with no state: y_t = sum over s <= t of (C_t . B_s)
    exp(sum of dt_r A over s < r <= t) dt_s x_s, a block of `SSD_BLOCK`
    positions t and one group's heads at a time, each block recomputed in
    the backward. The sums of log decays are taken from the block's first
    position outwards (forward to t, backward to s), never as the
    difference of two running sums over the whole sequence, which at 8,192
    positions would cancel thousands to leave tens."""
    s, h, p = x.shape
    g = B.shape[1]
    r = h // g
    block = SSD_BLOCK if s % SSD_BLOCK == 0 else s
    pos = jnp.arange(s)
    la = (dt * A).reshape(s, g, r)
    xdt = (x * dt[..., None]).reshape(s, g, r, p)

    @jax.checkpoint
    def one(first, group):
        la_g = lax.dynamic_index_in_dim(la, group, 1, keepdims=False)
        x_g = lax.dynamic_index_in_dim(xdt, group, 1, keepdims=False)
        B_g = lax.dynamic_index_in_dim(B, group, 1, keepdims=False)
        C_q = lax.dynamic_slice_in_dim(
            lax.dynamic_index_in_dim(C, group, 1, keepdims=False),
            first, block)
        after = jnp.where(pos[:, None] > first, la_g, 0.0)
        upto = jnp.where(pos[:, None] <= first, la_g, 0.0)
        fwd = jnp.cumsum(after, axis=0)         # sum over first < r <= t
        back = jnp.cumsum(upto[::-1], axis=0)[::-1] - upto  # s < r <= first
        seen = (pos[None, :] <= first + jnp.arange(block)[:, None])[..., None]
        log_decay = (lax.dynamic_slice_in_dim(fwd, first, block)[:, None]
                     + (back - fwd)[None])      # (block, s, r)
        decay = jnp.where(seen, jnp.exp(jnp.where(seen, log_decay, 0.0)),
                          0.0)
        return jnp.einsum("ts,tsr,srp->trp", C_q @ B_g.T, decay, x_g)

    firsts, groups = jnp.meshgrid(jnp.arange(0, s, block), jnp.arange(g),
                                  indexing="ij")
    out = lax.map(lambda a: one(*a), (firsts.reshape(-1), groups.reshape(-1)))
    return out.reshape(-1, g, block, r, p).transpose(0, 2, 1, 3, 4).reshape(
        s, h, p)


def gated_norm(y, z, w, groups, eps):
    """y, z (s, d): RMSNorm(y * silu(z)) * w, the gate BEFORE the norm, the
    mean square over each of `groups` runs of d / groups features."""
    s, d = y.shape
    y = (y * jax.nn.silu(z)).reshape(s, groups, d // groups)
    return rms_norm(y, 1.0, eps).reshape(s, d) * w


def mamba(p, u, cfg):
    """u (s, D) -> (s, D). `w_in` = [z | x | B | C | dt]."""
    h, hp = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    g, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    s, di = u.shape[0], h * hp
    zxbcdt = u @ p["w_in"]
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * g * n],
                  zxbcdt[:, 2 * di + 2 * g * n:])
    xbc = jax.nn.silu(causal_conv(xbc, p["conv"], p["conv_bias"]))
    x = xbc[:, :di].reshape(s, h, hp)
    B = xbc[:, di:di + g * n].reshape(s, g, n)
    C = xbc[:, di + g * n:].reshape(s, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd_dual(x, dt, -jnp.exp(p["A_log"]), B, C) + p["D"][:, None] * x
    y = gated_norm(y.reshape(s, di), z, p["norm"], g,
                   cfg["layer_norm_epsilon"])
    return y @ p["w_out"]


def causal_attention(q, k, v):
    """q, k, v (s, h, hd) -> (s, h, hd), a block of queries at a time."""
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
    return out.reshape(s, h, hd)


def attention(p, u, cfg):
    """u (s, D) -> (s, D): causal grouped-query attention, nothing else (no
    position embedding, no norm, no gate)."""
    h, hk = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd, s = int(cfg["head_dim"]), u.shape[0]
    q = (u @ p["wq"]).reshape(s, h, hd)
    k, v = (jnp.repeat((u @ p[w]).reshape(s, hk, hd), h // hk, axis=1)
            for w in ("wk", "wv"))
    return causal_attention(q, k, v).reshape(s, h * hd) @ p["wo"]


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
    """x (t, D) -> (out (t, D), pairs each held expert was given (held,),
    pairs each of ALL experts was given (n_routed_experts,)). The held
    experts one after the other, each on every token with a weight that is
    zero where the token did not choose it; the experts held elsewhere add
    nothing; the shared expert is added whole."""
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


def layer(params, biases, x, i, name, cfg):
    """Layer i, x + Mixer_i(RMSNorm_i(x)): (y, (pairs, load) of an expert
    layer or None)."""
    u = rms_norm(x, params[f"l{i}_norm"]["weight"], cfg["layer_norm_epsilon"])
    if name.endswith("_mamba"):
        return x + mamba(params[name], u, cfg), None
    if name.endswith("_attn"):
        return x + attention(params[name], u, cfg), None
    out, pairs, load = moe(params[name], u, cfg, biases[name])
    return x + out, (pairs, load)


@jax.checkpoint
def nll(head, x, targets):
    """Summed negative log-likelihood of `targets` (n,) under the head's
    logits of x (n, D), already normed."""
    logp = jax.nn.log_softmax(x @ head, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss_fn(params, tokens, cfg, biases):
    """tokens (b, s + 1) int -> (mean next-token NLL, (pairs (layers,
    held), loads (layers, experts))), the layers as `expert_layers` orders
    them."""
    run = jax.checkpoint(
        lambda p, x, i, name: layer(p, biases, x, i, name, cfg),
        static_argnums=(2, 3))

    def one_sequence(t):
        x, counts = params["embed"]["kernel"][t[:-1]], []
        for i, name in enumerate(mixer_names(cfg)):
            x, n = run(params, x, i, name)
            counts += [n] if n is not None else []
        x = rms_norm(x, params["final_norm"]["weight"],
                     cfg["layer_norm_epsilon"])
        pairs, loads = zip(*counts)
        return (nll(params["head"]["kernel"], x, t[1:]), jnp.stack(pairs),
                jnp.stack(loads))

    total, pairs, loads = zip(*(one_sequence(t) for t in tokens))
    return (sum(total) / (tokens.shape[0] * (tokens.shape[1] - 1)),
            (sum(pairs), sum(loads)))


def bias_update(bias, load, gamma):
    """b_e += gamma * sign(mean(c) - c_e) on one layer's buffer."""
    return bias + gamma * jnp.sign(jnp.mean(load.astype(jnp.float32)) - load)


def adam_update(w, g, m, v, t, opt):
    """One Adam step on one array, as `core/optimizers.py:AdamOptimizer`
    has it (the bias correction folded into the rate); `t` the step's
    number, from 1."""
    b1, b2 = opt["beta1"], opt["beta2"]
    rate = opt["alpha"] * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    return w - rate * m / (jnp.sqrt(v) + opt["epsilon"]), m, v


# --------------------------------------------------------------------------
# the reference's steps, so that the published shapes fit the device
# --------------------------------------------------------------------------
def _hashable(d: dict) -> tuple:
    return tuple(sorted(d.items()))


@partial(jax.jit, static_argnames=("cfg",))
def _loss_and_grads(params, tokens, biases, cfg):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, dict(cfg), biases)


@partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 2, 3))
def _adam_group(w, g, m, v, t, named, opt):
    """Adam on one op's parameters. `named` (rows, 1) bool or None: the
    token table is updated lazily, a row no token names keeps its weight,
    m and v, as the program's sparse row update leaves it."""
    new = jax.tree.map(
        lambda *a: tuple(x.astype(a[0].dtype)
                         for x in adam_update(*a, t, dict(opt))), w, g, m, v)
    pick = lambda i: jax.tree.map(  # noqa: E731
        lambda x: x[i], new, is_leaf=lambda x: isinstance(x, tuple))
    if named is None:
        return pick(0), pick(1), pick(2)
    keep = lambda cur, old: jax.tree.map(  # noqa: E731
        lambda c, o: jnp.where(named, c, o), cur, old)
    return keep(pick(0), w), keep(pick(1), m), keep(pick(2), v)


def reference_steps(params, m, v, biases, step0: int, tokens, cfg: dict,
                    opt: dict, steps: int, dtype=jnp.float32):
    """`steps` training steps on one batch from host arrays. Returns the
    loss before each step, the pairs routed to each held expert and to each
    of all experts summed over the steps (layers, held) and (layers,
    experts), the biases and the parameters after the last step, on the
    host. The weights and their gradient stay on the device; m and v pass
    through it one op at a time. `dtype` is float32; the reading that sets
    the limits computes it once more in bfloat16, weights, state and all
    (the bias buffers stay fp32: they are counted, not computed)."""
    def put(tree):
        return jax.tree.map(lambda a: jnp.asarray(a).astype(dtype), tree)

    cfg_key = _hashable({k: v_ for k, v_ in cfg.items()
                         if isinstance(v_, (int, float, bool, str))})
    opt_key = _hashable(opt)
    tokens = jnp.asarray(tokens)
    named = jnp.zeros((params["embed"]["kernel"].shape[0], 1), bool
                      ).at[tokens[:, :-1].reshape(-1)].set(True)
    params = put(params)
    biases = {k: jnp.asarray(b, jnp.float32) for k, b in biases.items()}
    m, v = dict(m), dict(v)
    losses, pairs, loads = [], 0, 0
    for i in range(steps):
        (loss, (n, c)), grads = _loss_and_grads(params, tokens, biases,
                                                cfg_key)
        losses.append(float(loss))
        pairs, loads = pairs + np.asarray(n), loads + np.asarray(c)
        biases = {name: bias_update(biases[name], c[j],
                                    float(cfg["balance_rate"]))
                  for j, name in enumerate(expert_layers(cfg))}
        t = jnp.float32(step0 + i + 1)
        for name in list(params):
            w, m_, v_ = _adam_group(
                params[name], grads.pop(name), put(m[name]), put(v[name]), t,
                named if name == "embed" else None, opt_key)
            params[name] = w
            m[name] = jax.tree.map(np.asarray, m_)
            v[name] = jax.tree.map(np.asarray, v_)
    return {"losses": np.asarray(losses, np.float64),
            "pairs": pairs, "loads": loads,
            "biases": {k: np.asarray(b) for k, b in biases.items()},
            "params": jax.tree.map(
                lambda a: np.asarray(a.astype(jnp.float32)), params)}


# --------------------------------------------------------------------------
# reading the system, and the comparison
# --------------------------------------------------------------------------
def _to_host(tree):
    return jax.tree.map(np.asarray, tree)


class Touched:
    """What the check reads of the system after the checked steps: every
    parameter and the expert ops' counters and biases (the name is the
    harness's: for the DLRM family it is the touched table rows)."""

    def read(self, model) -> dict:
        return {"params": _to_host(model.params),
                "counters": expert_counters(model)}


def _probe_input(hidden: int, experts: int):
    rng = np.random.default_rng(32)
    x = rng.standard_normal((PROBE_TOKENS, hidden)).astype(np.float32)
    x /= np.sqrt(np.mean(np.square(x), axis=-1, keepdims=True))
    return x, (PROBE_BIAS * rng.standard_normal(experts)).astype(np.float32)


def snapshot(model, config: dict, batch: Dict[str, np.ndarray]) -> dict:
    """Everything the reference needs, read to the host before the checked
    steps: the parameters, Adam's m, v and step count, the counters and
    biases, and what the first expert op's router makes of the probe."""
    opt = model.opt_state
    params = _to_host(model.params)
    name = expert_layers(config)[0]
    op = model.get_layer_by_name(name)
    probe = jax.jit(op.route)(model.params[name], *map(
        jnp.asarray, _probe_input(*params[name]["router"].shape)))
    return {"touched": Touched(), "batch": batch, "params": params,
            "m": _to_host(opt["m"]), "v": _to_host(opt["v"]),
            "step": int(opt["step"]),
            "counters": expert_counters(model),
            "probe": _to_host(probe),
            "vocab": int(params["embed"]["kernel"].shape[0])}


def _release(model):
    """Free the system's device state: the reference needs the room."""
    for leaf in jax.tree.leaves((model.params, model.opt_state)):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def _update_stats(w0, w_sys, w_ref) -> dict:
    """Cosine and slope of the system's update of one vector on the
    reference's."""
    du = (w_sys - w0).astype(np.float64).ravel()
    dr = (w_ref - w0).astype(np.float64).ravel()
    rr, uu, ur = float(dr @ dr), float(du @ du), float(du @ dr)
    if rr == 0.0:
        return {"cos": 1.0 if uu == 0.0 else 0.0,
                "slope": 1.0 if uu == 0.0 else float("inf")}
    return {"cos": ur / (rr * uu) ** 0.5 if uu else 0.0, "slope": ur / rr}


def _uses_fp32(a: np.ndarray) -> bool:
    """Whether some element of a sample spread over the array needs the
    mantissa bits bfloat16 lacks; a sample of zeros alone is no evidence
    against it."""
    flat = a.ravel()
    bits = np.ascontiguousarray(
        flat[::max(1, flat.size // STATE_SAMPLE)]).view(np.uint32)
    return bool(np.any(bits & 0xFFFF)) or not bits.any()


def run_reference(snap: dict, config: dict, steps: int,
                  dtype=jnp.float32) -> dict:
    """The reference's `steps` steps from the snapshot, and what its
    router makes of the probe."""
    cfg = model_config(config, snap["vocab"])
    opt = {k: float(config["optimizer"][k])
           for k in ("alpha", "beta1", "beta2", "epsilon")}
    first = expert_layers(cfg)[0]
    x, bias = _probe_input(int(cfg["hidden_size"]),
                           int(cfg["n_routed_experts"]))
    with jax.default_matmul_precision("highest"):
        probe = jax.jit(lambda p, x_, b_: route(p, x_, cfg, b_))(
            jax.tree.map(lambda a: jnp.asarray(a).astype(dtype),
                         snap["params"][first]),
            jnp.asarray(x).astype(dtype), jnp.asarray(bias).astype(dtype))
    out = reference_steps(
        snap["params"], snap["m"], snap["v"],
        {k: c["bias"] for k, c in snap["counters"].items()}, snap["step"],
        snap["batch"]["tokens"][:, 0, :], cfg, opt, steps, dtype)
    out["probe"] = tuple(np.asarray(a.astype(jnp.float32)
                                    if a.dtype == dtype else a)
                         for a in probe)
    return out


def compare(snap: dict, after: dict, system_losses, ref: dict,
            config: dict, system_probe=None) -> dict:
    """The system's losses, parameters, counters, biases and router probe
    against the reference's, each under its limit."""
    steps = len(system_losses)
    layers = expert_layers(config)
    tokens = np.asarray(snap["batch"]["tokens"])[:, 0, :-1].reshape(-1)
    sys_losses = np.asarray(system_losses, np.float64)
    loss_err = float(np.max(np.abs(sys_losses - ref["losses"])
                            / np.abs(ref["losses"])))

    # every parameter's update: the large ones each, the state-space
    # layers' small ones by name, the rest of the small ones as one
    stats, small = {}, {}
    for name, sub in snap["params"].items():
        for pn, w0 in sub.items():
            if name == "embed":
                continue
            trio = (w0, after["params"][name][pn], ref["params"][name][pn])
            if w0.size >= SMALL:
                stats[f"{name}.{pn}"] = _update_stats(*trio)
            else:
                key = (f"mamba.{pn}" if name.endswith("_mamba")
                       and pn in BY_NAME else "small")
                small.setdefault(key, []).append(trio)
    for key, trios in small.items():
        stats[key] = _update_stats(*(
            np.concatenate([t[i].ravel() for t in trios]) for i in range(3)))
    # the token table: named rows as one vector, the others bit for bit
    e0 = snap["params"]["embed"]["kernel"]
    e1 = after["params"]["embed"]["kernel"]
    named = np.zeros(len(e0), bool)
    named[tokens] = True
    stats["embed.named_rows"] = _update_stats(
        e0[named], e1[named], ref["params"]["embed"]["kernel"][named])
    unnamed_moved = int(np.sum(np.any(e1[~named] != e0[~named], axis=1)))
    named_still = int(np.sum(
        np.all(e1[named] == e0[named], axis=1)
        & np.any(ref["params"]["embed"]["kernel"][named] != e0[named],
                 axis=1)))
    worst_cos = min(stats, key=lambda k: stats[k]["cos"])
    worst_slope = max(stats, key=lambda k: abs(stats[k]["slope"] - 1.0))

    # routing: the pairs each held expert, and each of all the experts, was
    # given over the checked steps, both as shares of ALL the pairs made
    def counted(key):
        return np.stack([after["counters"][n][key] - snap["counters"][n][key]
                         for n in layers]).astype(np.int64)

    sys_pairs, sys_loads = counted("pairs"), counted("load")
    total_all = int(ref["loads"].sum())
    routing = float(np.abs(sys_pairs - ref["pairs"]).sum() / total_all)
    # a pair that went elsewhere is missing from one expert, extra at another
    load_err = float(np.abs(sys_loads - ref["loads"]).sum()
                     / (2 * total_all))
    # the bias buffers after the steps
    gamma = float(config["balance_rate"])
    b_sys = np.stack([after["counters"][n]["bias"] for n in layers])
    b_ref = np.stack([ref["biases"][n] for n in layers])
    b_0 = np.stack([snap["counters"][n]["bias"] for n in layers])
    bias_mismatch = float(np.mean(np.abs(b_sys - b_ref) > gamma / 2))
    bias_moved = float(np.mean(b_sys != b_0))
    # the router asked directly
    (w_sys, e_sys), (w_ref, e_ref) = (system_probe or snap["probe"],
                                      ref["probe"])
    same = np.all(np.sort(e_sys, -1) == np.sort(e_ref, -1), axis=-1)
    probe_mismatch = float(1.0 - same.mean())
    probe_err = float(np.max(np.abs(np.sort(w_sys[same], -1)
                                    - np.sort(w_ref[same], -1)),
                             initial=0.0))
    # fp32 where the configuration states it
    bf16_only = [f"{kind}.{name}.{pn}"
                 for kind, tree in (("weight", after["params"]),
                                    ("m", snap["m"]), ("v", snap["v"]))
                 for name, sub in tree.items() for pn, a in sub.items()
                 if a.size >= SMALL and not _uses_fp32(a)]

    out = {
        "steps": steps,
        "loss_system": sys_losses.tolist(),
        "loss_reference": ref["losses"].tolist(),
        "loss_rel_err": loss_err, "loss_rtol": LOSS_RTOL,
        "parameters_checked": len(stats),
        "update_cos_min": stats[worst_cos]["cos"],
        "update_cos_min_at": worst_cos, "update_cos_limit": UPDATE_COS_MIN,
        "update_slope_worst": stats[worst_slope]["slope"],
        "update_slope_worst_at": worst_slope,
        "update_slope_tol": UPDATE_SLOPE_TOL,
        "update_by_name": {k: v for k, v in stats.items()
                           if k.startswith("mamba.") or k == "small"},
        "token_rows_named": int(named.sum()),
        "token_rows_named_but_still": named_still,
        "token_rows_unnamed_but_moved": unnamed_moved,
        "pairs_all_reference": total_all,
        "pairs_all_system": int(sys_loads.sum()),
        "pairs_held_reference": int(ref["pairs"].sum()),
        "pairs_held_system": int(sys_pairs.sum()),
        "routing_mismatch": routing, "load_mismatch": load_err,
        "routing_mismatch_max": ROUTING_MISMATCH_MAX,
        "load_mismatch_max": LOAD_MISMATCH_MAX,
        "bias_mismatch": bias_mismatch, "bias_moved": bias_moved,
        "bias_abs_max": float(np.abs(b_sys).max()),
        "bias_mismatch_max": BIAS_MISMATCH_MAX,
        "probe_mismatch": probe_mismatch,
        "probe_mismatch_max": PROBE_MISMATCH_MAX,
        "probe_weight_err": probe_err,
        "probe_weight_atol": PROBE_WEIGHT_ATOL,
        "arrays_in_bf16_only": bf16_only,
    }
    out["ok"] = bool(
        np.all(np.isfinite(sys_losses)) and loss_err <= LOSS_RTOL
        and out["update_cos_min"] >= UPDATE_COS_MIN
        and abs(out["update_slope_worst"] - 1.0) <= UPDATE_SLOPE_TOL
        and named_still == 0 and unnamed_moved == 0
        and routing <= ROUTING_MISMATCH_MAX
        and load_err <= LOAD_MISMATCH_MAX
        and int(sys_loads.sum()) == total_all
        and np.array_equal(
            sys_pairs, sys_loads[:, int(config["expert_offset"]):][
                :, :sys_pairs.shape[1]])
        and bias_mismatch <= BIAS_MISMATCH_MAX
        and probe_mismatch <= PROBE_MISMATCH_MAX
        and probe_err <= PROBE_WEIGHT_ATOL and not bf16_only)
    return out


def verify(snap: dict, after: dict, system_losses, config: dict) -> dict:
    """Release the system's device state, run the reference from the
    snapshot and compare: the loss before every step, every parameter's
    update, the lazy token rows, the pairs the held and all the experts
    were given, the bias buffers, the router's answer to the probe, fp32
    where it is stated."""
    if _BUILT.get("model") is not None:
        _release(_BUILT["model"])
    ref = run_reference(snap, config, len(system_losses))
    return compare(snap, after, system_losses, ref, config)


# --------------------------------------------------------------------------
# operations and bytes from the shapes
# --------------------------------------------------------------------------
def parameter_counts(config: dict) -> Dict[str, int]:
    """Parameters held here, by part."""
    c = config
    D, V = int(c["hidden_size"]), int(c["vocab_size"])
    pattern = c["hybrid_override_pattern"]
    h, hp = int(c["mamba_num_heads"]), int(c["mamba_head_dim"])
    gn = int(c["n_groups"]) * int(c["ssm_state_size"])
    di, conv = h * hp, h * hp + 2 * gn
    ah, ak, hd = (int(c["num_attention_heads"]),
                  int(c["num_key_value_heads"]), int(c["head_dim"]))
    f, fs = (int(c["moe_intermediate_size"]),
             int(c["moe_shared_expert_intermediate_size"]))
    return {
        "mamba_proj": pattern.count("M") * (D * (di + conv + h) + di * D),
        "mamba_small": pattern.count("M") * (
            conv * (int(c["conv_kernel"]) + 1) + 3 * h + di),
        "attention": pattern.count("*") * (2 * D * ah * hd + 2 * D * ak * hd),
        "experts": pattern.count("E") * int(c["n_routed_experts"]) * 2 * D * f,
        "router_shared": pattern.count("E") * (
            D * int(c["published"]["n_routed_experts"])
            + int(c["n_shared_experts"]) * 2 * D * fs),
        "norms": (len(pattern) + 1) * D,
        "embed": V * D, "head": D * V}


def flops_per_sample(config: dict) -> float:
    """Useful training FLOPs of one sequence: a forward and a backward (2
    + 4 a multiply-accumulate), no recomputation, no padded row. A token
    meets its top-k experts' share held here (k * held / published: 0.375
    experts of two products each), the causal half of the attention's
    scores, and in a state-space layer the causal half of a chunk's scores
    (one set a group) and of its masked product (a head), one write and one
    read of the state."""
    c = config
    s, D = int(c["seq_len"]), int(c["hidden_size"])
    pattern = c["hybrid_override_pattern"]
    n = parameter_counts(c)
    pairs = int(c["num_experts_per_tok"]) * int(c["n_routed_experts"]) / int(
        c["published"]["n_routed_experts"])
    macs = s * (n["mamba_proj"] + n["attention"] + n["router_shared"]
                + n["head"] + pattern.count("E") * pairs * 2 * D
                * int(c["moe_intermediate_size"]))
    macs += pattern.count("*") * s * s * int(c["num_attention_heads"]) * (
        2 * int(c["head_dim"])) / 2
    hp = int(c["mamba_num_heads"]) * int(c["mamba_head_dim"])
    gn = int(c["n_groups"]) * int(c["ssm_state_size"])
    macs += pattern.count("M") * s * (
        int(c["chunk_size"]) / 2 * (gn + hp)
        + 2 * hp * int(c["ssm_state_size"]))
    return 6.0 * macs


def bytes_per_step(config: dict, batch_per_chip: int) -> float:
    """The least HBM traffic of one training step: every weight read for
    the forward and for the backward, its gradient written and read, Adam's
    m and v read and written, the weight written (fp32: 9 x 4 bytes a
    parameter; the token table counts whole, an upper bound of its named
    rows' share), and the residual stream written and read at every layer's
    boundary."""
    n = sum(parameter_counts(config).values())
    stream = (batch_per_chip * int(config["seq_len"])
              * int(config["hidden_size"]) * 4 * 2
              * (int(config["num_hidden_layers"]) + 2))
    return 36.0 * n + stream
