"""GLM-4.7-Flash on the framework (ISSUE 30): each new piece against the
plain reference (`models/glm4_moe_lite_reference.py`), forward and
gradient: latent attention with a value head narrower than the query's,
the sigmoid router under a non-zero bias, the bias update, the dense
block, the multi-token-prediction module with one table and one head; the
whole model through `fit()` with Adam; the share test over both routers.
All at a small size on the CPU, seeded weights, float32 compute (bf16 is
the benchmark's check, tests/perfbench/)."""

from dataclasses import asdict

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.core import losses
from dlrm_flexflow_tpu.models import glm4_moe_lite_reference as ref
from dlrm_flexflow_tpu.models import nemotron_h_reference as nemotron_ref
from dlrm_flexflow_tpu.models import qwen3_next_reference as qwen_ref
from dlrm_flexflow_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig,
                                                    build_glm4_moe_lite,
                                                    loss_weights, mtp_labels)
from dlrm_flexflow_tpu.ops import attention
from dlrm_flexflow_tpu.parallel.mesh import make_mesh

B, S = 2, 64
# a value head (16) narrower than the query's and key's (12 + 8): latent
# attention, not multi-head attention renamed
CFG = Glm4MoeLiteConfig(
    vocab_size=128, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=16,
    num_experts_per_tok=4, experts_held=4, expert_offset=8,
    balance_rate=1e-3, mtp_loss_weight=0.3)
OPT = dict(alpha=1e-3, beta1=0.9, beta2=0.999, epsilon=1e-8)
LAYERS = ref.expert_layers(asdict(CFG))


def _model(cfg=CFG, seq=S, batch=B, seed=5):
    model = ff.FFModel(ff.FFConfig(batch_size=batch, seed=3))
    build_glm4_moe_lite(model, cfg, seq)
    model.compile(ff.AdamOptimizer(**OPT), "sparse_categorical_crossentropy",
                  ["sparse_categorical_crossentropy"],
                  mesh=make_mesh(devices=jax.devices()[:1]),
                  loss_weights=loss_weights(seq, cfg.mtp_loss_weight))
    model.init_layers(seed)
    return model


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def _lively(tree, scale=0.05, key=7):
    """Weights off their initial values, so no term hides behind a zero
    (or a norm's one)."""
    return jax.tree.map(
        lambda a: a + scale * jax.random.normal(jax.random.PRNGKey(key),
                                                a.shape), tree)


def _tokens(seed=0, batch=B, seq=S, vocab=100):
    return np.random.default_rng(seed).integers(
        0, vocab, size=(batch, seq + 1)).astype(np.int32)


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def x():
    """A block's input: (B, S, D), unit scale."""
    return jax.random.normal(jax.random.PRNGKey(1), (B, S, CFG.hidden_size))


BIAS = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (CFG.n_routed_experts,))


def _op_and_reference(model, kind):
    cfg = asdict(CFG)
    name = {"mla": "l1_mla", "moe": "l1_moe", "mlp": "l0_mlp",
            "norm": "l0_mixer_norm"}[kind]
    op, p = model.get_layer_by_name(name), model.params[name]

    def system(p, x):
        if kind == "moe":
            st = dict(model.op_state[name], bias=BIAS)
            return op.apply_with_state(p, st, [x])[0][0]
        return op.apply(p, [x])[0]

    def reference(p, x):
        one = {"mla": lambda a: ref.mla(p, a, cfg),
               "moe": lambda a: ref.moe(p, a, cfg, BIAS)[0],
               "mlp": lambda a: ref.swiglu(a, **p),
               "norm": lambda a: ref.rms_norm(a, p["weight"],
                                              CFG.rms_norm_eps)}[kind]
        return jnp.stack([one(a) for a in x])
    return system, reference, p


@pytest.mark.parametrize("kind", ["norm", "mla", "mlp", "moe"])
def test_op_forward_and_gradient_match_the_reference(model, x, kind):
    system, reference, p = _op_and_reference(model, kind)
    p = _lively(p)
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    with jax.default_matmul_precision("highest"):
        (ys, gs), (yr, gr) = (
            jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(f(p, x) * ct),
                                       argnums=(0, 1)))(p, x)
            for f in (system, reference))
        np.testing.assert_allclose(jax.jit(system)(p, x),
                                   jax.jit(reference)(p, x),
                                   rtol=2e-4, atol=2e-5)
    assert float(ys) == pytest.approx(float(yr), rel=1e-4, abs=1e-4)
    for a, b in zip(jax.tree.leaves(gs), jax.tree.leaves(gr)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(a, b, rtol=0, atol=3e-4 * scale)


def test_the_bias_moves_the_choice_and_never_the_weights(model, x):
    """A bias that lifts four experts above every score: every token
    chooses them, and its weights are still their bare scores, normalised
    and scaled."""
    op, p = model.get_layer_by_name("l1_moe"), _lively(
        model.params["l1_moe"], 0.5)
    xt = x.reshape(-1, x.shape[-1])
    lifted = jnp.zeros((CFG.n_routed_experts,)).at[2:6].set(5.0)
    with jax.default_matmul_precision("highest"):
        w0, e0 = op.route(p, xt)
        w1, e1 = op.route(p, xt, lifted)
        scores = jax.nn.sigmoid(xt @ p["router"])
    assert not np.array_equal(np.sort(e0, -1), np.sort(e1, -1))
    assert np.array_equal(np.sort(e1, -1), np.tile(np.arange(2, 6),
                                                   (len(xt), 1)))
    want = jnp.take_along_axis(scores, e1, axis=-1)
    want = want / want.sum(-1, keepdims=True) * CFG.routed_scaling_factor
    np.testing.assert_allclose(w1, want, rtol=1e-6)
    np.testing.assert_allclose(w0.sum(-1), CFG.routed_scaling_factor,
                               rtol=1e-6)


def test_the_bias_follows_the_load_over_three_steps(model, x):
    """The op's state threaded through three applications against the
    reference's update: b_e += gamma * sign(mean(c) - c_e) over ALL the
    experts, the cumulative load beside it."""
    cfg = asdict(CFG)
    op, p = model.get_layer_by_name("l1_moe"), _lively(
        model.params["l1_moe"], 0.5)
    st = {k: jnp.zeros(d.shape, d.dtype) for k, d in op.state_defs().items()}
    bias, total = jnp.zeros((CFG.n_routed_experts,)), 0
    xt = x.reshape(-1, x.shape[-1])
    for step in range(3):
        (_, ), st = op.apply_with_state(p, st, [x])
        with jax.default_matmul_precision("highest"):
            _, _, load = ref.moe(p, xt, cfg, bias)
        (bias,) = ref.bias_update({"l1_moe": bias}, [load], dict(
            cfg, num_hidden_layers=2, first_k_dense_replace=1)).values()
        total = total + np.asarray(load)
        np.testing.assert_allclose(st["bias"], bias, atol=1e-9)
        assert np.array_equal(st["load"], total)
    assert int(st["load"].sum()) == 3 * B * S * CFG.num_experts_per_tok
    assert float(jnp.abs(st["bias"]).max()) == pytest.approx(3e-3)
    # the held experts' own counter is the load's slice
    lo = CFG.expert_offset
    assert np.array_equal(st["pairs"], total[lo:lo + CFG.experts_held])


def _system_loss(model, ids, labels, weights):
    logits_guid = model._logits_tensor.guid

    def loss(params):
        env, _ = model._forward_env(params, model.op_state,
                                    {"tokens": jnp.asarray(ids)}, True, None)
        return losses.sparse_categorical_crossentropy(
            env[logits_guid], jnp.asarray(labels), weights)
    return loss


def test_the_mtp_module_shares_the_table_and_the_head(model):
    """L = L_main + lambda L_mtp through ONE table and ONE head: their
    gradients are the two uses' summed (the reference's, which computes
    the passes apart), not the main pass's alone; the module's last
    position weighs nothing."""
    cfg, t = asdict(CFG), _tokens(4)
    ids, labels = mtp_labels(t)
    params = _lively(model.params, 0.02)
    biases = {n: jnp.zeros((CFG.n_routed_experts,)) for n in LAYERS}
    w = loss_weights(S, CFG.mtp_loss_weight)
    with jax.default_matmul_precision("highest"):
        ls, gs = jax.jit(jax.value_and_grad(
            _system_loss(model, ids, labels, w)))(params)

        def ref_grad(lam):
            return jax.jit(jax.value_and_grad(
                lambda p: ref.loss_fn(p, jnp.asarray(t),
                                      dict(cfg, mtp_loss_weight=lam), biases),
                has_aux=True))(params)

        (lr, aux), gr = ref_grad(CFG.mtp_loss_weight)
        _, g_main = ref_grad(0.0)
        # another label at the masked position changes nothing
        labels2 = labels.copy()
        labels2[:, -1] = (labels2[:, -1] + 1) % CFG.vocab_size
        ls2 = jax.jit(_system_loss(model, ids, labels2, w))(params)
    assert float(ls) == pytest.approx(float(lr), rel=2e-6)
    assert float(lr) == pytest.approx(
        float(aux[2] + CFG.mtp_loss_weight * aux[3]), rel=1e-6)
    assert float(ls2) == float(ls)
    for name, sub in gr.items():
        for pn, g in sub.items():
            scale = float(jnp.max(jnp.abs(g))) or 1.0
            np.testing.assert_allclose(gs[name][pn], g, rtol=0,
                                       atol=3e-4 * scale,
                                       err_msg=f"{name}.{pn}")
    for name in ("embed", "head"):
        g, gm = gr[name]["kernel"], g_main[name]["kernel"]
        assert float(jnp.max(jnp.abs(g - gm))) > 0.05 * float(
            jnp.max(jnp.abs(g))), name


def test_the_model_trains_through_fit_like_the_reference():
    """Loss before each of three Adam steps, every weight and every bias
    buffer after them; the token rows no token names keep their bits."""
    model = _model()
    t = _tokens()
    ids, labels = mtp_labels(t)
    p0 = _host(model.params)
    reported = []
    model.fit({"tokens": ids}, labels, epochs=3, verbose=False, callbacks=[
        lambda m, e, rep: reported.append(rep["sparse_cce"])])
    cfg = asdict(CFG)
    params = jax.tree.map(jnp.asarray, p0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    biases = {n: jnp.zeros((CFG.n_routed_experts,)) for n in LAYERS}
    step = jax.jit(lambda p, m, v, b, t_: ref.adam_step(
        p, m, v, b, t_, jnp.asarray(t), cfg, OPT))
    pairs, loads = 0, 0
    for n in (1.0, 2.0, 3.0):
        loss, aux, params, m, v, biases = step(params, m, v, biases, n)
        # the reported metric is L itself, both terms
        assert reported[int(n) - 1] == pytest.approx(float(loss), rel=2e-6)
        pairs, loads = pairs + np.asarray(aux[0]), loads + np.asarray(aux[1])
    for name, sub in params.items():
        for pn, w in sub.items():
            # three steps of at most alpha each; Adam's division makes an
            # element with a tiny gradient sensitive to its rounding
            np.testing.assert_allclose(
                np.asarray(model.params[name][pn]), w, rtol=0,
                atol=0.1 * 3 * OPT["alpha"], err_msg=f"{name}.{pn}")
    named = np.zeros(CFG.vocab_size, bool)
    named[t.reshape(-1)] = True
    assert np.array_equal(
        np.asarray(model.params["embed"]["kernel"])[~named],
        p0["embed"]["kernel"][~named])
    stats = model.expert_stats()
    assert sorted(stats) == sorted(LAYERS)
    for i, name in enumerate(LAYERS):
        st = stats[name]
        assert st["tokens"] == 3 * B * S
        assert np.array_equal(st["pairs"], pairs[i])
        assert np.array_equal(st["load"], loads[i])
        np.testing.assert_allclose(st["bias"], biases[name], atol=1e-9)
        assert st["rows"] >= st["pairs"].sum()
    assert any(np.abs(st["bias"]).max() > 2e-3 for st in stats.values())
    # and as the series `--obs on` scrapes
    series = list(model._obs_collect_experts())
    load = [v for n, lab, v in series
            if n == "ff_moe_load_total" and lab["op"] == "mtp_moe"]
    assert load == stats["mtp_moe"]["load"].tolist()
    (peak,) = [v for n, lab, v in series
               if n == "ff_moe_bias_abs_max" and lab["op"] == "l1_moe"]
    assert peak == pytest.approx(np.abs(stats["l1_moe"]["bias"]).max())


def _moe_layer(router, held, offset, x, whole=None):
    """One expert op alone under either router (`relu2`: the sigmoid
    router over experts of two matrices, Nemotron-H's), holding `held`
    experts from `offset`; weights cut out of the uncut layer's where
    given."""
    model = ff.FFModel(ff.FFConfig(batch_size=x.shape[0], seed=3))
    t = model.create_tensor(x.shape, name="x")
    kw = {"softmax": {},
          "sigmoid": dict(scoring="sigmoid", routed_scale=1.8,
                          shared_gate=False, balance_rate=1e-3),
          "relu2": dict(scoring="sigmoid", routed_scale=2.5,
                        shared_gate=False, balance_rate=1e-3,
                        activation="relu2")}[router]
    model.moe(t, CFG.n_routed_experts, CFG.num_experts_per_tok,
              CFG.moe_intermediate_size, CFG.moe_intermediate_size,
              experts_held=held, expert_offset=offset, name="moe", **kw)
    op = model.get_layer_by_name("moe")
    if whole is None:
        params = jax.tree.map(lambda a: 4.0 * a,      # a lively router
                              op.init_params(jax.random.PRNGKey(11)))
    else:
        params = dict(whole, **{k: whole[k][offset:offset + held]
                                for k in ("w_gate", "w_up", "w_down")
                                if k in whole})
    state = {k: jnp.zeros(d.shape, d.dtype)
             for k, d in op.state_defs().items()}
    if router != "softmax":
        state["bias"] = BIAS
    return op, params, state


@pytest.mark.parametrize("held", [16, 2])
@pytest.mark.parametrize("router", ["softmax", "sigmoid", "relu2"])
def test_the_shares_add_up_to_the_uncut_layer(x, router, held):
    """Under either router and either form of expert: the routed parts of
    all 16 / held ranks, each with its own offset, plus the shared expert
    counted once == that model's reference for the whole layer."""
    n = CFG.n_routed_experts
    _, whole, _ = _moe_layer(router, n, 0, x)
    xt = x.reshape(-1, x.shape[-1])
    with jax.default_matmul_precision("highest"):
        if router == "relu2":
            assert sorted(whole) == ["router", "shared_down", "shared_up",
                                     "w_down", "w_up"]
            shared = nemotron_ref.relu2_mlp(xt, whole["shared_up"],
                                            whole["shared_down"])
            want = nemotron_ref.moe(whole, xt, dict(
                asdict(CFG), expert_offset=0, routed_scaling_factor=2.5),
                BIAS)[0]
        else:
            shared = ref.swiglu(xt, whole["shared_gate"], whole["shared_up"],
                                whole["shared_down"])
        if router == "softmax":
            cfg = dict(num_experts_per_tok=CFG.num_experts_per_tok,
                       norm_topk_prob=True, expert_offset=0)
            want = qwen_ref.moe(whole, xt, cfg)[0]
            shared = shared * jax.nn.sigmoid(
                xt @ whole["shared_router"])[:, None]
        elif router == "sigmoid":
            want = ref.moe(whole, xt, dict(asdict(CFG), expert_offset=0,
                                           routed_scaling_factor=1.8),
                           BIAS)[0]
        shared = shared.reshape(x.shape)
        total, pairs = 0.0, 0
        for offset in range(0, n, held):
            op, p, st = _moe_layer(router, held, offset, x, whole)
            (out,), st = op.apply_with_state(p, st, [x])
            total = total + (out - shared)          # this rank's routed part
            pairs += int(st["pairs"].sum())
    np.testing.assert_allclose(total + shared, want.reshape(x.shape),
                               rtol=2e-4, atol=2e-5)
    # every (token, chosen expert) pair was some rank's, once
    assert pairs == B * S * CFG.num_experts_per_tok


def test_an_op_that_does_not_ask_has_no_bias(x):
    """The softmax router's op keeps the state and the parameters it had:
    no bias, no load, and the shared expert's gate."""
    op, p, st = _moe_layer("softmax", 4, 8, x)
    assert sorted(st) == ["pairs", "rows", "tokens"]
    assert "shared_router" in p
    op, p, st = _moe_layer("sigmoid", 4, 8, x)
    assert sorted(st) == ["bias", "load", "pairs", "rows", "tokens"]
    assert "shared_router" not in p
    with pytest.raises(ValueError, match="sigmoid"):
        model = ff.FFModel(ff.FFConfig(batch_size=2))
        model.moe(model.create_tensor((2, 4, 8), name="x"), 4, 2, 8, 8,
                  balance_rate=1e-3)


class _Model:
    """What `attend` asks of a model."""

    def __init__(self):
        self.ops, self.optimizer = [], ff.AdamOptimizer()
        self.mesh, self.config = None, ff.FFConfig()


@pytest.mark.parametrize("route", ["dense", "blockwise"])
@pytest.mark.parametrize("hd,vd", [(24, 16), (16, 24)])
def test_attend_takes_a_value_head_of_its_own_width(monkeypatch, route,
                                                    hd, vd):
    monkeypatch.setattr(attention, "BLOCK_Q", 32)
    monkeypatch.setattr(attention, "_scores_fit",
                        lambda *a: route == "dense")
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (2, 4, 128, hd))
    k = jax.random.normal(ks[1], (2, 4, 128, hd))
    v = jax.random.normal(ks[2], (2, 4, 128, vd))
    with jax.default_matmul_precision("highest"):
        got = attention.attend(_Model(), "attn", q, k, v, True)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / hd ** 0.5
        s = jnp.where(jnp.tril(jnp.ones((128, 128), bool)), s, -jnp.inf)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    assert got.shape == (2, 4, 128, vd)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_loss_weights_lay_over_whole_samples():
    model = ff.FFModel(ff.FFConfig(batch_size=2))
    build_glm4_moe_lite(model, CFG, S)
    with pytest.raises(ValueError, match="loss weights"):
        model.compile(ff.AdamOptimizer(**OPT),
                      "sparse_categorical_crossentropy", [],
                      mesh=make_mesh(devices=jax.devices()[:1]),
                      loss_weights=np.ones(7))
    with pytest.raises(ValueError, match="sparse_categorical"):
        model.compile(ff.AdamOptimizer(**OPT), "mean_squared_error", [],
                      mesh=make_mesh(devices=jax.devices()[:1]),
                      loss_weights=np.ones(2 * S))
