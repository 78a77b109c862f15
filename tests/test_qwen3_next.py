"""Qwen3-Next on the framework (ISSUE 26): each new op against the plain
reference (`models/qwen3_next_reference.py`), forward and gradient; the
chunked delta rule against the recurrence a step at a time; the whole model
through `fit()` with Adam; the share test (the routed parts of every
expert-parallel rank plus the shared expert once == the uncut layer);
dropless under the worst case; the attention's route by what fits. All at a
small size on the CPU, seeded weights, float32 compute (bf16 is the
benchmark's check, tests/perfbench/)."""

from dataclasses import asdict

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.models import qwen3_next_reference as ref
from dlrm_flexflow_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                 build_qwen3_next)
from dlrm_flexflow_tpu.ops import attention, delta_net
from dlrm_flexflow_tpu.parallel.mesh import make_mesh

B, S = 2, 128
CFG = Qwen3NextConfig(
    vocab_size=128, hidden_size=64, num_hidden_layers=4,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=16,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, experts_held=4, expert_offset=8)
OPT = dict(alpha=1e-3, beta1=0.9, beta2=0.999, epsilon=1e-8)


def _model(cfg=CFG, seq=S, batch=B, seed=5, **kw):
    model = ff.FFModel(ff.FFConfig(batch_size=batch, seed=3, **kw))
    build_qwen3_next(model, cfg, seq)
    model.compile(ff.AdamOptimizer(**OPT), "sparse_categorical_crossentropy",
                  ["sparse_categorical_crossentropy"],
                  mesh=make_mesh(devices=jax.devices()[:1]))
    model.init_layers(seed)
    return model


def _host(tree):
    return jax.tree.map(np.asarray, tree)


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def x():
    """A block's input: (B, S, D), unit scale."""
    return jax.random.normal(jax.random.PRNGKey(1), (B, S, CFG.hidden_size))


def _op_and_reference(model, kind):
    cfg = asdict(CFG)
    name = {"delta": "l0_delta", "attn": "l3_attn", "moe": "l0_moe",
            "norm": "l0_mixer_norm"}[kind]
    op, p = model.get_layer_by_name(name), model.params[name]

    def system(p, x):
        if kind == "moe":
            return op.apply_with_state(p, model.op_state[name], [x])[0][0]
        return op.apply(p, [x])[0]

    def reference(p, x):
        one = {"delta": lambda a: ref.gated_delta_net(p, a, cfg),
               "attn": lambda a: ref.gated_attention(p, a, cfg),
               "moe": lambda a: ref.moe(p, a, cfg)[0],
               "norm": lambda a: ref.rms_norm(a, p["weight"],
                                              CFG.rms_norm_eps)}[kind]
        return jnp.stack([one(a) for a in x])
    return system, reference, p


@pytest.mark.parametrize("kind", ["norm", "delta", "attn", "moe"])
def test_op_forward_and_gradient_match_the_reference(model, x, kind):
    system, reference, p = _op_and_reference(model, kind)
    # weights off their initial values, so that no term hides behind a zero
    p = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(7),
                                               a.shape), p)
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    with jax.default_matmul_precision("highest"):
        (ys, gs), (yr, gr) = (
            jax.value_and_grad(lambda p, x: jnp.sum(f(p, x) * ct),
                               argnums=(0, 1))(p, x)
            for f in (system, reference))
        np.testing.assert_allclose(system(p, x), reference(p, x),
                                   rtol=2e-4, atol=2e-5)
    assert float(ys) == pytest.approx(float(yr), rel=1e-4, abs=1e-4)
    for a, b in zip(jax.tree.leaves(gs), jax.tree.leaves(gr)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(a, b, rtol=0, atol=3e-4 * scale)


@pytest.mark.parametrize("seq,chunk,span", [
    (128, 64, 1024), (256, 64, 128), (100, 64, 1024), (200, 64, 128),
    (37, 16, 32), (64, 64, 64)])
def test_chunked_delta_rule_is_the_recurrence(seq, chunk, span):
    """Also where the length is no multiple of the chunk or of the span,
    and through the gradient."""
    k0 = jax.random.split(jax.random.PRNGKey(seq), 5)
    b, h, dk, dv = 2, 3, 16, 8
    q = delta_net.l2_normalize(jax.random.normal(k0[0], (b, seq, h, dk)))
    k = delta_net.l2_normalize(jax.random.normal(k0[1], (b, seq, h, dk)))
    v = jax.random.normal(k0[2], (b, seq, h, dv))
    g = -jax.nn.softplus(jax.random.normal(k0[3], (b, seq, h)))
    beta = jax.nn.sigmoid(jax.random.normal(k0[4], (b, seq, h)))

    def chunked(*a):
        return delta_net.gated_delta_rule_chunked(*a, chunk=chunk, span=span)

    with jax.default_matmul_precision("highest"):
        want = delta_net.gated_delta_rule_stepwise(q, k, v, g, beta)
        got = chunked(q, k, v, g, beta)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        # and the reference's own recurrence, a sequence at a time
        np.testing.assert_allclose(
            jnp.stack([ref.delta_rule(*(t[i] for t in (q, k, v, g, beta)))
                       for i in range(b)]), want, rtol=1e-4, atol=1e-5)
        ct = jax.random.normal(jax.random.PRNGKey(9), want.shape)
        gw, gg = (jax.grad(lambda *a: jnp.sum(f(*a) * ct),
                           argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
                  for f in (delta_net.gated_delta_rule_stepwise, chunked))
    for a, b_ in zip(gg, gw):
        np.testing.assert_allclose(a, b_, rtol=0,
                                   atol=2e-4 * float(jnp.max(jnp.abs(b_))))


def _tokens(seed=0, batch=B, seq=S, vocab=100):
    t = np.random.default_rng(seed).integers(
        0, vocab, size=(batch, seq + 1)).astype(np.int32)
    return {"tokens": t[:, :-1]}, t[:, 1:]


def test_the_model_trains_through_fit_like_the_reference():
    """Loss before each of three Adam steps and every weight after them;
    the token rows no token names keep their bits (lazy Adam)."""
    model = _model()
    x, y = _tokens()
    p0 = _host(model.params)
    losses = []
    model.fit(x, y, epochs=3, verbose=False, callbacks=[
        lambda m, e, rep: losses.append(rep["sparse_cce"])])
    cfg = asdict(CFG)
    params = jax.tree.map(jnp.asarray, p0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step = jax.jit(lambda p, m, v, t: ref.adam_step(
        p, m, v, t, jnp.asarray(x["tokens"]), jnp.asarray(y), cfg, OPT))
    pairs = 0
    for t in (1.0, 2.0, 3.0):
        loss, n, params, m, v = step(params, m, v, t)
        assert losses[int(t) - 1] == pytest.approx(float(loss), rel=2e-6)
        pairs = pairs + np.asarray(n)
    for name, sub in params.items():
        for pn, w in sub.items():
            # three steps of at most alpha each; Adam's division makes an
            # element with a tiny gradient sensitive to its rounding
            np.testing.assert_allclose(
                np.asarray(model.params[name][pn]), w, rtol=0,
                atol=0.1 * 3 * OPT["alpha"], err_msg=f"{name}.{pn}")
    named = np.zeros(CFG.vocab_size, bool)
    named[x["tokens"].reshape(-1)] = True
    assert np.array_equal(
        np.asarray(model.params["embed"]["kernel"])[~named],
        p0["embed"]["kernel"][~named])
    # the counters: tokens seen, pairs a held expert, rows computed
    for i in range(CFG.num_hidden_layers):
        st = _host(model.op_state[f"l{i}_moe"])
        assert st["tokens"] == 3 * B * S
        assert np.array_equal(st["pairs"], pairs[i])
        assert st["rows"] >= st["pairs"].sum()
    stats = model.expert_stats()
    assert stats["l0_moe"]["pairs"].tolist() == pairs[0].tolist()


def _moe_layer(held, offset, x, params=None, chunk_rows=None, bias=None):
    """One expert op alone, holding `held` experts from `offset`; weights
    cut out of the uncut layer's `params` where given."""
    model = ff.FFModel(ff.FFConfig(batch_size=x.shape[0], seed=3))
    t = model.create_tensor(x.shape, name="x")
    model.moe(t, CFG.num_experts, CFG.num_experts_per_tok,
              CFG.moe_intermediate_size, CFG.shared_expert_intermediate_size,
              experts_held=held, expert_offset=offset, name="moe")
    op = model.get_layer_by_name("moe")
    if chunk_rows:
        op.chunk_rows = chunk_rows
    if params is None:
        params = op.init_params(jax.random.PRNGKey(11))
        params = jax.tree.map(lambda a: 4.0 * a, params)     # a lively router
    else:
        params = dict(params, **{k: params[k][offset:offset + held]
                                 for k in ("w_gate", "w_up", "w_down")})
    if bias is not None:
        # the router has no bias of its own: feature 0 of `x` is held at 1
        # by the caller, and its row of the router carries the bias
        params = dict(params, router=params["router"].at[0].add(bias))
    state = {k: jnp.zeros(d.shape, d.dtype)
             for k, d in op.state_defs().items()}
    return op, params, state


@pytest.mark.parametrize("held", [16, 8, 4, 2])
def test_the_shares_add_up_to_the_uncut_layer(x, held):
    """The routed parts of all 16 / held ranks, each with its own offset,
    plus the shared expert counted once == the reference's whole layer."""
    cfg = dict(asdict(CFG), expert_offset=0)
    _, whole, _ = _moe_layer(CFG.num_experts, 0, x)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.moe(whole, a, cfg)[0] for a in x])
        xt = x.reshape(-1, x.shape[-1])
        gate = jax.nn.sigmoid(xt @ whole["shared_router"])[:, None]
        shared = (gate * ref.swiglu(xt, whole["shared_gate"],
                                    whole["shared_up"],
                                    whole["shared_down"])).reshape(x.shape)
        total, pairs = 0.0, 0
        for offset in range(0, CFG.num_experts, held):
            op, p, st = _moe_layer(held, offset, x, whole)
            (out,), st = op.apply_with_state(p, st, [x])
            total = total + (out - shared)          # this rank's routed part
            pairs += int(st["pairs"].sum())
    np.testing.assert_allclose(total + shared, want, rtol=2e-4, atol=2e-5)
    # every (token, chosen expert) pair was some rank's, once
    assert pairs == B * S * CFG.num_experts_per_tok


@pytest.mark.parametrize("chunk_rows", [None, 64, 200, 8])
def test_dropless_when_every_token_picks_held_experts(x, chunk_rows):
    """The worst case: a router biased so that all top-k choices of every
    token fall on the four held experts. Every pair is computed, whatever
    the tile; tiles that do not divide the pairs included."""
    held, offset = 4, 8
    x = x.at[..., 0].set(1.0)
    bias = jnp.zeros((CFG.num_experts,)).at[offset:offset + held].set(50.0)
    op, p, st = _moe_layer(held, offset, x, chunk_rows=chunk_rows, bias=bias)
    cfg = dict(asdict(CFG), expert_offset=offset)
    with jax.default_matmul_precision("highest"):
        (out,), st = op.apply_with_state(p, st, [x])
        want = jnp.stack([ref.moe(p, a, cfg)[0] for a in x])
        g = jax.grad(lambda p: jnp.sum(
            op.apply_with_state(p, st, [x])[0][0] ** 2))(p)
        gr = jax.grad(lambda p: jnp.sum(jnp.stack(
            [ref.moe(p, a, cfg)[0] for a in x]) ** 2))(p)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    pairs = B * S * CFG.num_experts_per_tok
    assert int(st["pairs"].sum()) == pairs      # four times the expected
    assert pairs <= int(st["rows"]) <= pairs + 4 * op.chunk_rows
    for k in g:
        np.testing.assert_allclose(
            g[k], gr[k], rtol=0,
            atol=3e-4 * float(jnp.max(jnp.abs(gr[k]))) + 1e-9, err_msg=k)


def test_no_token_for_the_held_experts_costs_no_row(x):
    x = x.at[..., 0].set(1.0)
    bias = jnp.zeros((CFG.num_experts,)).at[:8].set(50.0)
    op, p, st = _moe_layer(4, 8, x, bias=bias)
    (out,), st = op.apply_with_state(p, st, [x])
    assert int(st["rows"]) == 0 and int(st["pairs"].sum()) == 0
    assert bool(jnp.all(jnp.isfinite(out)))


# ---- the attention's route ------------------------------------------------
class _Model:
    """What `attend` asks of a model: its ops' parameter bytes, its
    optimizer's slabs, its mesh, its config."""

    def __init__(self, param_bytes):
        self.ops = [type("O", (), {"param_bytes":
                                   staticmethod(lambda: param_bytes)})()]
        self.optimizer = ff.AdamOptimizer()
        self.mesh = None
        self.config = ff.FFConfig()


@pytest.mark.parametrize("param_gb,heads,seq,fits", [
    (2.5, 16, 8192, False),     # ISSUE 26: 4.3 GB of scores beside 10 GB
    (0.0, 16, 8192, True),      # the same scores on an empty chip
    (2.5, 16, 2048, True),      # 0.27 GB of scores: dense stays the route
    (0.0, 16, 16384, False)])   # 17 GB of scores fit nowhere
def test_attention_routes_by_what_fits(monkeypatch, param_gb, heads, seq,
                                       fits):
    monkeypatch.setattr(attention, "_hbm_bytes", lambda: 16e9)
    q = jax.ShapeDtypeStruct((1, heads, seq, 256), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 2, seq, 256), jnp.bfloat16)
    model = _Model(param_gb * 1e9)
    assert attention._scores_fit(model, q, k) is fits
    # off the TPU there is no kernel: the gate stays shut whatever fits
    assert attention._flash_gate(model, "attn", q, k) is False
    # on it (same shapes, bf16, single chip) the gate is `not fits`
    from dlrm_flexflow_tpu.ops import embedding
    monkeypatch.setattr(embedding, "_pallas_gate", lambda *a: True)
    assert attention._flash_gate(model, "attn", q, k) is (not fits)


@pytest.mark.parametrize("route", ["dense", "blockwise"])
@pytest.mark.parametrize("causal", [True, False])
def test_attend_routes_agree_with_grouped_heads(monkeypatch, route, causal):
    """One core for both attention ops: grouped K/V heads, each route."""
    monkeypatch.setattr(attention, "BLOCK_Q", 32)
    monkeypatch.setattr(attention, "_scores_fit",
                        lambda *a: route == "dense")
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (2, 4, 128, 16))
    k = jax.random.normal(ks[1], (2, 2, 128, 16))
    v = jax.random.normal(ks[2], (2, 2, 128, 16))
    with jax.default_matmul_precision("highest"):
        got = attention.attend(_Model(0), "attn", q, k, v, causal)
        kr, vr = (jnp.repeat(t, 2, axis=1) for t in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kr) / 4.0
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((128, 128), bool)), s, -jnp.inf)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vr)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
