"""The GLM-4.7-Flash family (`model_type: glm4_moe_lite`): what every
configuration of it in the benchmark shares.

1. how a configuration file becomes the system under test, through the
   program's own front door (`FFConfig` -> `FFModel` ->
   `build_glm4_moe_lite` -> `compile(AdamOptimizer,
   sparse_categorical_crossentropy, loss_weights)` -> `init_layers(seed)`),
   one chip's share of the stated deployment;
2. the plain reference (the benchmark's own copy of
   `dlrm_flexflow_tpu/models/glm4_moe_lite_reference.py`, so that later PRs
   may change the program and not the yardstick): forward, the two loss
   terms, gradients, the router's bias update and Adam steps in
   straightforward `jax.numpy`, float32, matmul precision "highest", the
   two passes apart (the program concatenates them), the experts one after
   the other, no kernel, no line shared with the program;
3. what the check reads of the system (every parameter, Adam's m and v, the
   step count, the expert ops' counters and bias buffers) and what it
   compares;
4. the operations and bytes one training step needs, from the shapes.

How the check fits the chip. At the published widths the state is 8.5 GB
(weights, m, v) and the reference needs its weights and their gradient,
5.7 GB, beside its activations. So the snapshot is read to the HOST before
the checked steps; `verify`, which runs after the windows and the trace,
first RELEASES the system's device state (`build` kept the handle;
everything it still needs was read right after the checked steps) and then
runs the reference on the device with the weights and their gradient
resident and m, v streamed through, one op's parameters at a time. A block
and each pass's head are recomputed in the backward, the attention runs a
block of queries at a time: none of it changes a value.

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

# the key of `fit`'s per-epoch report that is the training loss: L = L_main
# + lambda L_mtp, the weighted negative log-likelihood a logit row, times
# rows, summed over the epoch (`compile(loss_weights=)` weighs the metric
# as it weighs the loss)
LOSS_METRIC = "sparse_cce"

# ---- the limits of the check, each with its reason -----------------------
# The system multiplies in bf16 with fp32 accumulation and keeps the
# residual stream, the norms, the router, the loss and Adam in fp32; the
# reference is fp32 throughout. The loss is a mean over thousands of tokens
# of a log-softmax over a final norm, so the roundings largely average out:
# sixteen runs on the v5e read 1.4e-5 to 2.1e-4, the reference in bfloat16
# 5.6e-3 (PERF.md section 6, PR 30: every reading below is from there).
LOSS_RTOL = 1e-3
# Adam divides the gradient by its own running size, so every element moves
# by about `alpha` a step whatever its gradient, and elementwise limits mean
# nothing. What holds is the direction and the size of a whole parameter's
# update: its cosine with the reference's and its slope on it (1 where the
# sizes agree). Small parameters (the norms, 64-wide things) are judged
# together, as one vector. Sixteen runs on the v5e read a cosine of 0.9643
# and more (the worst is always a router's 131,072 weights) and a slope
# within 0.026 of 1; the reference in bfloat16 -0.74 and -8.0; an update
# without Adam's division reads a cosine of 0.1, one applied twice a slope
# of 2.
UPDATE_COS_MIN = 0.8
UPDATE_SLOPE_TOL = 0.2
SMALL = 65536          # elements; parameters under this are judged as one
# The token table is updated lazily: a row no token of the batch names must
# keep its bits, and a named row must move wherever the reference's moves.
# (Not every named row moves: the sequence's last token may be named by the
# module's last position alone, which has no target and so no gradient; the
# first chip runs found one such row in six seeds.)
# The router runs in fp32 on fp32 activations; what the bf16 products of the
# layers before it change in its inputs flips a token's fourth expert now
# and then. The pairs each held expert was given over the checked steps,
# and the pairs each of ALL the experts was given (`load`), are compared
# with the reference's own counts: the share of pairs that went to another
# expert than in the reference. Sixteen runs on the v5e read 0 to 4.8e-3 of
# the held experts' pairs (50k to 125k of them) and 2.1e-4 to 1.7e-3 of all
# experts' (491,520); the reference in bfloat16 1.6e-2 and 8.3e-3.
ROUTING_MISMATCH_MAX = 0.012
LOAD_MISMATCH_MAX = 0.005
# The bias: after the checked steps every expert's buffer is the snapshot's
# plus or minus gamma a step, by the sign of mean load - its load. A flipped
# pair flips that sign only for an expert whose load lies within a few
# pairs of the mean, so a few buffers in a hundred differ by 2 gamma; an
# update left out, of the wrong sign, or taken from the held experts' loads
# alone moves most of them. The share of (layer, expert) buffers that lie
# further than gamma / 2 from the reference's: at most 1 of 320 in eighteen
# runs on the v5e, 1 of 320 for the reference in bfloat16 (the precision hardly
# moves it), 1.0 for buffers left as they were: the limit lies between the
# reading and 1, nearer the reading.
BIAS_MISMATCH_MAX = 0.1
# Those counts cannot tell a router computed in bf16 from the bf16 products
# before it. So the router is also asked directly: `snapshot` gives the
# first expert op's own `route` a seeded unit-RMS input AND a seeded
# non-zero bias, `verify` gives the reference's the same. Both are fp32, so
# the weights of the chosen experts agree to rounding (sixteen runs on the
# v5e: 0.0, and no token's choice differs); the reference in bfloat16 is
# off by 4.5e-3 and chooses other experts for 5.6% of the tokens; weights
# taken from score + bias (and not from the bare score) are off by 0.1.
PROBE_TOKENS = 1024
PROBE_BIAS = 0.1
PROBE_WEIGHT_ATOL = 1e-4
PROBE_MISMATCH_MAX = 0.005      # tokens whose chosen experts differ
# Weights, m and v are stated fp32: some element of every large array must
# use the 16 mantissa bits bfloat16 lacks. `STATE_SAMPLE` of them are looked
# at, spread over the whole array: an expert no token has chosen yet has an
# m and a v of exact zeros (under this router some experts of a layer go
# without a pair for many steps), and zeros say nothing of a precision.
STATE_SAMPLE = 1 << 20


# --------------------------------------------------------------------------
# configuration -> sizes
# --------------------------------------------------------------------------
def held_table_rows(config: dict, chips: int) -> List[int]:
    """Rows of the token table (and columns of the head) held here: the
    configuration states this chip's slice of the vocabulary itself."""
    return [int(config["vocab_size"])]


def input_fields(config: dict, rows: List[int]) -> List[dict]:
    """One field of token ids, a sequence and its next token a sample:
    seq_len + 1 ids, the generator's `bag`. The module's target, the token
    after the next, is among them: the mix sends nothing new."""
    return [{"name": "tokens", "kind": "ids", "rows": rows,
             "bag": int(config["seq_len"]) + 1}]


def fit_arrays(data: Dict[str, np.ndarray]):
    """(inputs, labels) as `FFModel.fit` takes them, a sample's main pass
    before its module's: ids [tok_0..tok_{S-1} | tok_1..tok_S], labels
    [tok_1..tok_S | tok_2..tok_S, tok_S] (the module's last position has no
    target; its label weighs nothing)."""
    t = data["tokens"][:, 0, :]
    return ({"tokens": np.ascontiguousarray(
        np.concatenate([t[:, :-1], t[:, 1:]], axis=1))},
        np.ascontiguousarray(
            np.concatenate([t[:, 1:], t[:, 2:], t[:, -1:]], axis=1)))


def model_config(config: dict, vocab: int) -> dict:
    """The keys the builder and the reference read, with what this chip
    holds: `n_routed_experts` in the file counts the experts HELD (it is
    listed in `reduced`); the router keeps the published width."""
    out = {k: v for k, v in config.items()
           if isinstance(v, (int, float, bool))}
    out.update(vocab_size=int(vocab),
               n_routed_experts=int(config["published"]["n_routed_experts"]),
               experts_held=int(config["n_routed_experts"]),
               expert_offset=int(config["expert_offset"]))
    return out


def expert_layers(cfg: dict) -> List[str]:
    """The expert ops' names, in the order the counts are stacked."""
    return [f"l{i}_moe" for i in range(int(cfg["first_k_dense_replace"]),
                                       int(cfg["num_hidden_layers"]))
            ] + ["mtp_moe"]


_BUILT = {}      # the handle `build` made: the counters' readers and the
                 # release in `verify` reach the system through it


def build(config: dict, rows: List[int], batch: int, chips: int, seed: int):
    """The system under test. Returns (model, timings) with the seconds of
    graph build + compile() and of init_layers()."""
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.glm4_moe_lite import (
        Glm4MoeLiteConfig, build_glm4_moe_lite, loss_weights)

    opt = config["optimizer"]
    if opt["type"] != "adam" or config["loss"] != (
            "sparse_categorical_crossentropy"):
        raise ValueError("the family's reference knows Adam and "
                         "sparse_categorical_crossentropy only")
    if config["scoring_func"] != "sigmoid" or int(
            config["num_nextn_predict_layers"]) != 1:
        raise ValueError("the family's reference knows the sigmoid router "
                         "and one multi-token-prediction module only")
    if chips != 1:
        raise NotImplementedError(
            "the expert op has no exchange yet: one chip a cell")
    t0 = time.time()
    cfg = ff.FFConfig.parse_args(
        ["-b", str(batch), "--compute-dtype", config["compute_dtype"]])
    model = ff.FFModel(cfg)
    mcfg = Glm4MoeLiteConfig.from_dict(model_config(config, rows[0]))
    build_glm4_moe_lite(model, mcfg, int(config["seq_len"]))
    model.compile(
        ff.AdamOptimizer(alpha=opt["alpha"], beta1=opt["beta1"],
                         beta2=opt["beta2"], epsilon=opt["epsilon"]),
        config["loss"], [config["loss"]],
        mesh=ff.make_mesh(num_devices=chips),
        loss_weights=loss_weights(int(config["seq_len"]),
                                  mcfg.mtp_loss_weight))
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
# the plain reference (a copy of models/glm4_moe_lite_reference.py)
# --------------------------------------------------------------------------
QUERY_BLOCK = 512   # queries the attention attends with at a time


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
    """x (s, D) -> (s, D). `wq_b` is head-major with [nope | rope] inside a
    head, `wkv_a` = [c_kv | k_rope], `wkv_b` head-major with [k_nope | v]."""
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
    """x (t, D) -> (out (t, D), pairs each held expert was given (held,),
    pairs each of ALL experts was given (n_routed_experts,)). The held
    experts one after the other, each on every token with a weight that is
    zero where the token did not choose it; the experts held elsewhere add
    nothing; the shared expert is added whole, ungated."""
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
    of its expert layer, or None for a dense one)."""
    eps = cfg["rms_norm_eps"]
    h = x + mla(params[f"{tag}_mla"],
                rms_norm(x, params[f"{tag}_mixer_norm"]["weight"], eps), cfg)
    n = rms_norm(h, params[f"{tag}_ffn_norm"]["weight"], eps)
    if f"{tag}_mlp" in params:
        return h + swiglu(n, **params[f"{tag}_mlp"]), None
    out, pairs, load = moe(params[f"{tag}_moe"], n, cfg,
                           biases[f"{tag}_moe"])
    return h + out, (pairs, load)


@jax.checkpoint
def nll(head, x, targets):
    """Summed negative log-likelihood of `targets` (n,) under the head's
    logits of x (n, D), already normed."""
    logp = jax.nn.log_softmax(x @ head, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss_fn(params, tokens, cfg, biases):
    """tokens (b, s + 1) int -> (L, (pairs (layers, held), loads (layers,
    experts), L_main, L_mtp)), the layers as `expert_layers` orders them."""
    eps, emb = cfg["rms_norm_eps"], params["embed"]["kernel"]
    head = params["head"]["kernel"]
    run = jax.checkpoint(lambda p, x, tag: block(p, biases, x, tag, cfg),
                         static_argnums=(2,))

    def one_sequence(t):
        x, counts = emb[t[:-1]], []
        for i in range(int(cfg["num_hidden_layers"])):
            x, n = run(params, x, f"l{i}")
            counts += [n] if n is not None else []
        main = nll(head, rms_norm(x, params["final_norm"]["weight"], eps),
                   t[1:])
        u = jnp.concatenate(
            [rms_norm(emb[t[1:]], params["mtp_enorm"]["weight"], eps),
             rms_norm(x, params["mtp_hnorm"]["weight"], eps)], axis=-1
        ) @ params["mtp_eh_proj"]["kernel"]
        u, n = run(params, u, "mtp")
        u = rms_norm(u, params["mtp_final_norm"]["weight"], eps)
        # position i predicts t[i + 2]; the last position has no target
        mtp = nll(head, u[:-1], t[2:])
        pairs, loads = zip(*(counts + [n]))
        return main, mtp, jnp.stack(pairs), jnp.stack(loads)

    main, mtp, pairs, loads = zip(*(one_sequence(t) for t in tokens))
    b, s = tokens.shape[0], tokens.shape[1] - 1
    l_main, l_mtp = sum(main) / (b * s), sum(mtp) / (b * (s - 1))
    return (l_main + float(cfg["mtp_loss_weight"]) * l_mtp,
            (sum(pairs), sum(loads), l_main, l_mtp))


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
    loss (and its two terms) before each step, the pairs routed to each
    held expert and to each of all experts summed over the steps (layers,
    held) and (layers, experts), the biases and the parameters after the
    last step, on the host. The weights and their gradient stay on the
    device; m and v pass through it one op at a time. `dtype` is float32;
    the reading that sets the limits computes it once more in bfloat16,
    weights, state and all (the bias buffers stay fp32: they are counted,
    not computed)."""
    def put(tree):
        return jax.tree.map(lambda a: jnp.asarray(a).astype(dtype), tree)

    cfg_key = _hashable({k: v_ for k, v_ in cfg.items()
                         if isinstance(v_, (int, float, bool))})
    opt_key = _hashable(opt)
    tokens = jnp.asarray(tokens)
    named = jnp.zeros((params["embed"]["kernel"].shape[0], 1), bool
                      ).at[tokens.reshape(-1)].set(True)
    params = put(params)
    biases = {k: jnp.asarray(b, jnp.float32) for k, b in biases.items()}
    m, v = dict(m), dict(v)
    losses, terms, pairs, loads = [], [], 0, 0
    for i in range(steps):
        (loss, (n, c, l_main, l_mtp)), grads = _loss_and_grads(
            params, tokens, biases, cfg_key)
        losses.append(float(loss))
        terms.append((float(l_main), float(l_mtp)))
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
    return {"losses": np.asarray(losses, np.float64), "terms": terms,
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
    rng = np.random.default_rng(30)
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
    tokens = np.asarray(snap["batch"]["tokens"]).reshape(-1)
    sys_losses = np.asarray(system_losses, np.float64)
    loss_err = float(np.max(np.abs(sys_losses - ref["losses"])
                            / np.abs(ref["losses"])))

    # every parameter's update: the large ones each, the small ones as one
    stats, small = {}, []
    for name, sub in snap["params"].items():
        for pn, w0 in sub.items():
            if name == "embed":
                continue
            trio = (w0, after["params"][name][pn], ref["params"][name][pn])
            if w0.size < SMALL:
                small.append(trio)
            else:
                stats[f"{name}.{pn}"] = _update_stats(*trio)
    if small:
        stats["small"] = _update_stats(*(
            np.concatenate([t[i].ravel() for t in small]) for i in range(3)))
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
    # given over the checked steps
    def counted(key):
        return np.stack([after["counters"][n][key] - snap["counters"][n][key]
                         for n in layers]).astype(np.int64)

    sys_pairs, sys_loads = counted("pairs"), counted("load")
    total, total_all = int(ref["pairs"].sum()), int(ref["loads"].sum())
    routing = float(np.abs(sys_pairs - ref["pairs"]).sum() / max(total, 1))
    # a pair that went elsewhere is missing from one expert, extra at another
    load_err = float(np.abs(sys_loads - ref["loads"]).sum()
                     / max(2 * total_all, 1))
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
        "loss_reference_main_mtp": ref["terms"],
        "loss_rel_err": loss_err, "loss_rtol": LOSS_RTOL,
        "parameters_checked": len(stats),
        "update_cos_min": stats[worst_cos]["cos"],
        "update_cos_min_at": worst_cos, "update_cos_limit": UPDATE_COS_MIN,
        "update_slope_worst": stats[worst_slope]["slope"],
        "update_slope_worst_at": worst_slope,
        "update_slope_tol": UPDATE_SLOPE_TOL,
        "token_rows_named": int(named.sum()),
        "token_rows_named_but_still": named_still,
        "token_rows_unnamed_but_moved": unnamed_moved,
        "pairs_reference": total, "pairs_system": int(sys_pairs.sum()),
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
    """Parameters held here, by part (the module's block among the
    blocks)."""
    c = config
    D, V = int(c["hidden_size"]), int(c["vocab_size"])
    L, dense = int(c["num_hidden_layers"]), int(c["first_k_dense_replace"])
    h, qr, kr = (int(c["num_attention_heads"]), int(c["q_lora_rank"]),
                 int(c["kv_lora_rank"]))
    nope, rope, vd = (int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"]),
                      int(c["v_head_dim"]))
    f = int(c["moe_intermediate_size"])
    sparse = L - dense + int(c["num_nextn_predict_layers"])
    attn = (D * qr + qr + qr * h * (nope + rope) + D * (kr + rope) + kr
            + kr * h * (nope + vd) + h * vd * D)
    return {
        "attention": (L + int(c["num_nextn_predict_layers"])) * attn,
        "dense_mlp": dense * 3 * D * int(c["intermediate_size"]),
        "experts": sparse * int(c["n_routed_experts"]) * 3 * D * f,
        "router_shared": sparse * (
            D * int(c["published"]["n_routed_experts"])
            + int(c["n_shared_experts"]) * 3 * D * f),
        "mtp_eh_proj": int(c["num_nextn_predict_layers"]) * 2 * D * D,
        "norms": (2 * L + 1 + 5 * int(c["num_nextn_predict_layers"])) * D,
        "embed": V * D, "head": D * V}


def flops_per_sample(config: dict) -> float:
    """Useful training FLOPs of one sequence: a forward and a backward (2
    + 4 a multiply-accumulate), no recomputation, no padded row. A token
    meets its top-k experts' share held here (k * held / published), the
    causal half of the attention's scores in every block (the module's
    too), and the head twice: once a pass."""
    c = config
    s = int(c["seq_len"])
    n = parameter_counts(c)
    D, f = int(c["hidden_size"]), int(c["moe_intermediate_size"])
    blocks = int(c["num_hidden_layers"]) + int(c["num_nextn_predict_layers"])
    sparse = blocks - int(c["first_k_dense_replace"])
    pairs = int(c["num_experts_per_tok"]) * int(c["n_routed_experts"]) / int(
        c["published"]["n_routed_experts"])
    dense = (n["attention"] + n["dense_mlp"] + n["router_shared"]
             + n["mtp_eh_proj"]
             + (1 + int(c["num_nextn_predict_layers"])) * n["head"])
    macs = s * (dense + sparse * pairs * 3 * D * f)
    h = int(c["num_attention_heads"])
    qk = int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"])
    macs += blocks * s * s * h * (qk + int(c["v_head_dim"])) / 2
    return 6.0 * macs


def bytes_per_step(config: dict, batch_per_chip: int) -> float:
    """The least HBM traffic of one training step: every weight read for
    the forward and for the backward, its gradient written and read, Adam's
    m and v read and written, the weight written (fp32: 9 x 4 bytes a
    parameter; the token table counts whole, an upper bound of its named
    rows' share), and the residual stream written and read at every block
    boundary."""
    n = sum(parameter_counts(config).values())
    blocks = int(config["num_hidden_layers"]) + int(
        config["num_nextn_predict_layers"])
    stream = (batch_per_chip * int(config["seq_len"])
              * int(config["hidden_size"]) * 4 * 2 * (2 * blocks + 2))
    return 36.0 * n + stream
