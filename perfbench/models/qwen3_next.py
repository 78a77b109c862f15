"""The Qwen3-Next family: what every Qwen3-Next configuration of the
benchmark shares.

1. how a configuration file becomes the system under test, through the
   program's own front door (`FFConfig` -> `FFModel` -> `build_qwen3_next`
   -> `compile(AdamOptimizer, sparse_categorical_crossentropy)` ->
   `init_layers(seed)`), one chip's share of the stated deployment;
2. the plain reference (the benchmark's own copy of
   `dlrm_flexflow_tpu/models/qwen3_next_reference.py`, so that later PRs may
   change the program and not the yardstick): forward, loss, gradients and
   Adam steps in straightforward `jax.numpy`, float32, matmul precision
   "highest", the recurrence one position a step, the experts one after the
   other, no kernel, no line shared with the program;
3. what the check reads of the system (every parameter, Adam's m and v, the
   step count and the expert op's counters) and what it compares;
4. the operations and bytes one training step needs, from the shapes.

How the check fits the chip. At the published widths the state is 7.5 GB
(weights, m, v) and the reference needs the same again plus its gradient.
So the snapshot is read to the HOST before the checked steps; `verify`,
which runs after the windows and the trace, first RELEASES the system's
device state (`build` kept the handle; everything it still needs, the
updated weights and the counters, was read right after the checked steps)
and then runs the reference on the device with the weights and their
gradient resident and m, v streamed through, one op's parameters at a time.

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

# the key of `fit`'s per-epoch report that is the training loss: the summed
# next-token negative log-likelihood over the tokens of the epoch
LOSS_METRIC = "sparse_cce"

# ---- the limits of the check, each with its reason -----------------------
# The system multiplies in bf16 with fp32 accumulation and keeps the
# residual stream, the norms, the router, the recurrent state, the loss and
# Adam in fp32; the reference is fp32 throughout. The loss is a mean over
# thousands of tokens of a log-softmax over a final norm, so the roundings
# largely average out: seven runs on the v5e read 6e-5 to 1.6e-4, the
# reference in bfloat16 2e-3 and more (PERF.md section 6, PR 26).
LOSS_RTOL = 1e-3
# Adam divides the gradient by its own running size, so every element of
# every parameter moves by about `alpha` a step whatever its gradient: an
# element whose gradient is small against its bf16 noise may move the other
# way, and elementwise limits mean nothing. What holds is the direction and
# the size of a whole parameter's update: its cosine with the reference's
# and its slope on it (the projection, 1 where the sizes agree). Small
# parameters (a norm's 128 weights, `A_log`'s 32) are judged together, as
# one vector, with the large ones apart. Seven runs on the v5e read a
# cosine of 0.9963 and more and a slope of 0.9949 and more; an update
# without Adam's division reads a cosine of 0.1, one applied twice a slope
# of 2.
UPDATE_COS_MIN = 0.8
UPDATE_SLOPE_TOL = 0.2
SMALL = 65536          # elements; parameters under this are judged as one
# The token table is updated lazily: a row no token of the batch names must
# keep its bits, and a named row must move.
# The router runs in fp32 on fp32 activations; what the bf16 products of the
# layers before it change in its inputs flips a token's tenth expert now
# and then. The pairs each held expert was given over the checked steps are
# compared with the reference's own count: the share of pairs that went to
# another expert than in the reference (seven runs on the v5e read 0.46% to
# 0.52%; a pair given to a held expert that was another's reads 16%)
ROUTING_MISMATCH_MAX = 0.02
# That count cannot tell a router computed in bf16 from the bf16 products
# before it (both move a logit by a few thousandths). So the router is also
# asked directly: `snapshot` gives the first expert op's own `route` a
# seeded unit-RMS input, `verify` gives the reference's the same. Both are
# fp32, so the weights of the chosen experts agree to rounding (1e-6); a
# bf16 router is off by 1e-3 and chooses other experts for a token in ten.
PROBE_TOKENS = 1024
PROBE_WEIGHT_ATOL = 1e-4
PROBE_MISMATCH_MAX = 0.005      # tokens whose chosen experts differ
# Weights, m and v are stated fp32: some element of every large array must
# use the 16 mantissa bits bfloat16 lacks (`STATE_SAMPLE` of them are
# looked at).
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
    seq_len + 1 ids, the generator's `bag`."""
    return [{"name": "tokens", "kind": "ids", "rows": rows,
             "bag": int(config["seq_len"]) + 1}]


def fit_arrays(data: Dict[str, np.ndarray]):
    """(inputs, labels) as `FFModel.fit` takes them: the labels are the
    next token."""
    t = data["tokens"]
    return ({"tokens": np.ascontiguousarray(t[:, 0, :-1])},
            np.ascontiguousarray(t[:, 0, 1:]))


def model_config(config: dict, vocab: int) -> dict:
    """The keys the builder and the reference read, with what this chip
    holds: `num_experts` in the file counts the experts HELD (it is listed
    in `reduced`); the router keeps the published width."""
    out = {k: v for k, v in config.items()
           if isinstance(v, (int, float, bool))}
    out.update(vocab_size=int(vocab),
               num_experts=int(config["published"]["num_experts"]),
               experts_held=int(config["num_experts"]),
               expert_offset=int(config["expert_offset"]))
    return out


_BUILT = {}      # the handle `build` made: the counters' readers and the
                 # release in `verify` reach the system through it


def build(config: dict, rows: List[int], batch: int, chips: int, seed: int):
    """The system under test. Returns (model, timings) with the seconds of
    graph build + compile() and of init_layers()."""
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                     build_qwen3_next)

    opt = config["optimizer"]
    if opt["type"] != "adam" or config["loss"] != (
            "sparse_categorical_crossentropy"):
        raise ValueError("the Qwen3-Next family's reference knows Adam and "
                         "sparse_categorical_crossentropy only")
    if chips != 1:
        raise NotImplementedError(
            "the expert op has no exchange yet: one chip a cell")
    t0 = time.time()
    cfg = ff.FFConfig.parse_args(
        ["-b", str(batch), "--compute-dtype", config["compute_dtype"]])
    model = ff.FFModel(cfg)
    build_qwen3_next(
        model, Qwen3NextConfig.from_dict(model_config(config, rows[0])),
        int(config["seq_len"]))
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
    """{expert op: {"tokens", "pairs" (held,), "rows"}}, cumulative since
    init: the program's `FFModel.expert_stats()` of the model `build`
    made."""
    model = model or _BUILT.get("model")
    return {} if model is None else model.expert_stats()


# --------------------------------------------------------------------------
# the plain reference (a copy of models/qwen3_next_reference.py)
# --------------------------------------------------------------------------
SEGMENT = 64        # positions of the recurrence recomputed together
QUERY_BLOCK = 512   # queries the attention attends with at a time


def is_full_attention(cfg: dict, i: int) -> bool:
    return (i + 1) % int(cfg["full_attention_interval"]) == 0


def mixer_name(cfg: dict, i: int) -> str:
    return f"l{i}_attn" if is_full_attention(cfg, i) else f"l{i}_delta"


def rms_norm(x, w, eps, zero_centered=True):
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y * (1.0 + w if zero_centered else w)


def delta_rule(q, k, v, g, beta):
    """S' = exp(g_t) S; u = beta_t (v_t - S'^T k_t); S = S' + k_t u^T;
    o_t = S^T q_t, one position a step. q, k (s, h, dk); v (s, h, dv);
    g, beta (s, h) -> o (s, h, dv). A segment of steps is recomputed in the
    backward, which changes no value."""
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
    """x (s, D) -> (s, D). `w_qkvz` = [q | k | v | z], `w_ba` = [b | a]."""
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
    """q (s, h, hd); k, v (s, hk, hd) -> (s, h, hd), a block of queries at
    a time."""
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
    """`wq` is head-major with [query | gate] inside a head."""
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
    (experts_held,)). The held experts one after the other, each on every
    token with a weight that is zero where the token did not choose it; the
    experts held elsewhere add nothing."""
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


def forward(params, tokens, cfg):
    """tokens (b, s) int -> (logits (b * s, vocab), pairs routed to each
    held expert (layers, experts_held)). A block is recomputed in the
    backward, which changes no value."""
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
def _loss_and_grads(params, tokens, labels, cfg):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, labels, dict(cfg))


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


def reference_steps(params, m, v, step0: int, tokens, labels, cfg: dict,
                    opt: dict, steps: int, dtype=jnp.float32):
    """`steps` Adam steps on one batch from host arrays. Returns (the loss
    before each step, the pairs routed to each held expert summed over the
    steps (layers, held), the parameters after the last step, on the
    host). The weights and their gradient stay on the device; m and v pass
    through it one op at a time. `dtype` is float32; the reading that sets
    the limits computes it once more in bfloat16, weights, state and all."""
    def put(tree):
        return jax.tree.map(lambda a: jnp.asarray(a).astype(dtype), tree)

    cfg_key = _hashable({k: v_ for k, v_ in cfg.items()
                         if isinstance(v_, (int, float, bool))})
    opt_key = _hashable(opt)
    tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
    named = jnp.zeros((params["embed"]["kernel"].shape[0], 1), bool
                      ).at[tokens.reshape(-1)].set(True)
    params = put(params)
    m, v = dict(m), dict(v)
    losses, pairs = [], 0
    for i in range(steps):
        (loss, n), grads = _loss_and_grads(params, tokens, labels, cfg_key)
        losses.append(float(loss))
        pairs = pairs + np.asarray(n)
        t = jnp.float32(step0 + i + 1)
        for name in list(params):
            w, m_, v_ = _adam_group(
                params[name], grads.pop(name), put(m[name]), put(v[name]), t,
                named if name == "embed" else None, opt_key)
            params[name] = w
            m[name] = jax.tree.map(np.asarray, m_)
            v[name] = jax.tree.map(np.asarray, v_)
    return {"losses": np.asarray(losses, np.float64), "pairs": pairs,
            "params": jax.tree.map(
                lambda a: np.asarray(a.astype(jnp.float32)), params)}


# --------------------------------------------------------------------------
# reading the system, and the comparison
# --------------------------------------------------------------------------
def _to_host(tree):
    return jax.tree.map(np.asarray, tree)


class Touched:
    """What the check reads of the system after the checked steps: every
    parameter and the expert counters (the name is the harness's: for the
    DLRM family it is the touched table rows)."""

    def read(self, model) -> dict:
        return {"params": _to_host(model.params),
                "counters": expert_counters(model)}


def _probe_input(hidden: int) -> np.ndarray:
    x = np.random.default_rng(26).standard_normal(
        (PROBE_TOKENS, hidden)).astype(np.float32)
    return x / np.sqrt(np.mean(np.square(x), axis=-1, keepdims=True))


def snapshot(model, config: dict, batch: Dict[str, np.ndarray]) -> dict:
    """Everything the reference needs, read to the host before the checked
    steps: the parameters, Adam's m, v and step count, the counters, and
    what the first expert op's router makes of the probe."""
    opt = model.opt_state
    params = _to_host(model.params)
    op = model.get_layer_by_name("l0_moe")
    probe = jax.jit(op.route)(model.params[op.name], jnp.asarray(
        _probe_input(params["l0_moe"]["router"].shape[0])))
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
    """Whether some element needs the mantissa bits bfloat16 lacks."""
    bits = np.ascontiguousarray(a.ravel()[:STATE_SAMPLE]).view(np.uint32)
    return bool(np.any(bits & 0xFFFF))


def run_reference(snap: dict, config: dict, steps: int,
                  dtype=jnp.float32) -> dict:
    """The reference's `steps` steps from the snapshot, and what its
    router makes of the probe."""
    x, y = fit_arrays(snap["batch"])
    cfg = model_config(config, snap["vocab"])
    opt = {k: float(config["optimizer"][k])
           for k in ("alpha", "beta1", "beta2", "epsilon")}
    with jax.default_matmul_precision("highest"):
        probe = jax.jit(lambda p, x_: route(p, x_, cfg))(
            jax.tree.map(lambda a: jnp.asarray(a).astype(dtype),
                         snap["params"]["l0_moe"]),
            jnp.asarray(_probe_input(int(cfg["hidden_size"]))).astype(dtype))
    out = reference_steps(snap["params"], snap["m"], snap["v"],
                          snap["step"], x["tokens"], y, cfg, opt, steps,
                          dtype)
    out["probe"] = tuple(np.asarray(a.astype(jnp.float32)
                                    if a.dtype == dtype else a)
                         for a in probe)
    return out


def compare(snap: dict, after: dict, system_losses, ref: dict,
            system_probe=None) -> dict:
    """The system's losses, parameters, counters and router probe against
    the reference's, each under its limit."""
    steps = len(system_losses)
    x, _ = fit_arrays(snap["batch"])
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
    named[np.asarray(x["tokens"]).reshape(-1)] = True
    stats["embed.named_rows"] = _update_stats(
        e0[named], e1[named], ref["params"]["embed"]["kernel"][named])
    unnamed_moved = int(np.sum(np.any(e1[~named] != e0[~named], axis=1)))
    named_still = int(np.sum(np.all(e1[named] == e0[named], axis=1)))
    worst_cos = min(stats, key=lambda k: stats[k]["cos"])
    worst_slope = max(stats, key=lambda k: abs(stats[k]["slope"] - 1.0))

    # routing: the pairs each held expert was given over the checked steps
    sys_pairs = np.stack([
        after["counters"][f"l{i}_moe"]["pairs"]
        - snap["counters"][f"l{i}_moe"]["pairs"]
        for i in range(len(ref["pairs"]))]).astype(np.int64)
    total = int(ref["pairs"].sum())
    routing = float(np.abs(sys_pairs - ref["pairs"]).sum() / max(total, 1))
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
        "token_rows_named": int(named.sum()),
        "token_rows_named_but_still": named_still,
        "token_rows_unnamed_but_moved": unnamed_moved,
        "pairs_reference": total, "pairs_system": int(sys_pairs.sum()),
        "routing_mismatch": routing,
        "routing_mismatch_max": ROUTING_MISMATCH_MAX,
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
        and probe_mismatch <= PROBE_MISMATCH_MAX
        and probe_err <= PROBE_WEIGHT_ATOL and not bf16_only)
    return out


def verify(snap: dict, after: dict, system_losses, config: dict) -> dict:
    """Release the system's device state, run the reference from the
    snapshot and compare: the loss before every step, every parameter's
    update, the lazy token rows, the pairs the held experts were given,
    the router's answer to the probe, fp32 where it is stated."""
    if _BUILT.get("model") is not None:
        _release(_BUILT["model"])
    ref = run_reference(snap, config, len(system_losses))
    return compare(snap, after, system_losses, ref)


# --------------------------------------------------------------------------
# operations and bytes from the shapes
# --------------------------------------------------------------------------
def parameter_counts(config: dict) -> Dict[str, int]:
    """Parameters held here, by part."""
    c = config
    D, V, L = int(c["hidden_size"]), int(c["vocab_size"]), int(
        c["num_hidden_layers"])
    kd = int(c["linear_num_key_heads"]) * int(c["linear_key_head_dim"])
    hv = int(c["linear_num_value_heads"])
    vd = hv * int(c["linear_value_head_dim"])
    h, hk, hd = (int(c["num_attention_heads"]),
                 int(c["num_key_value_heads"]), int(c["head_dim"]))
    f, fs = int(c["moe_intermediate_size"]), int(
        c["shared_expert_intermediate_size"])
    full = sum(1 for i in range(L) if is_full_attention(c, i))
    delta = (D * (2 * kd + 2 * vd) + D * 2 * hv
             + (2 * kd + vd) * int(c["linear_conv_kernel_dim"]) + 2 * hv
             + int(c["linear_value_head_dim"]) + vd * D)
    attn = D * h * hd * 2 + 2 * D * hk * hd + 2 * hd + h * hd * D
    return {
        "delta": (L - full) * delta, "attention": full * attn,
        "experts": L * int(c["num_experts"]) * 3 * D * f,
        "router_shared": L * (D * int(c["published"]["num_experts"])
                              + 3 * D * fs + D),
        "norms": (2 * L + 1) * D, "embed": V * D, "head": D * V}


def flops_per_sample(config: dict) -> float:
    """Useful training FLOPs of one sequence: a forward and a backward (2
    + 4 a multiply-accumulate), no recomputation, no padded row. A token
    meets its top-k experts' share held here (k * held / published), the
    causal half of the attention's scores, and per position of a
    value head two reads and one rank-one write of the delta net's state."""
    c = config
    s, L = int(c["seq_len"]), int(c["num_hidden_layers"])
    n = parameter_counts(c)
    D = int(c["hidden_size"])
    held_share = int(c["num_experts"]) / int(c["published"]["num_experts"])
    pairs = int(c["num_experts_per_tok"]) * held_share
    dense = (n["delta"] + n["attention"] + n["router_shared"] + n["head"])
    macs = s * (dense + L * pairs * 3 * D * int(c["moe_intermediate_size"]))
    full = sum(1 for i in range(L) if is_full_attention(c, i))
    macs += full * s * s * int(c["num_attention_heads"]) * int(c["head_dim"])
    macs += (L - full) * 3 * s * int(c["linear_num_value_heads"]) * int(
        c["linear_key_head_dim"]) * int(c["linear_value_head_dim"])
    return 6.0 * macs


def bytes_per_step(config: dict, batch_per_chip: int) -> float:
    """The least HBM traffic of one training step: every weight read for
    the forward and for the backward, its gradient written and read, Adam's
    m and v read and written, the weight written (fp32: 9 x 4 bytes a
    parameter; the token table counts whole, an upper bound of its named
    rows' share), and the residual stream written and read at every block
    boundary."""
    n = sum(parameter_counts(config).values())
    stream = (batch_per_chip * int(config["seq_len"])
              * int(config["hidden_size"]) * 4
              * 2 * (2 * int(config["num_hidden_layers"]) + 2))
    return 36.0 * n + stream
