"""Nemotron-H on the framework (ISSUE 32): each new piece against the plain
reference (`models/nemotron_h_reference.py`), forward and gradient: the
chunked state-space recurrence against the stepwise one (a sequence that
is no whole number of chunks, fewer groups than heads), the convolution's
bias, the gate before a GROUPED norm, the expert of two matrices and its
shared expert, grouped-query attention with no position embedding; what
each part of the mathematics is worth (a reference without it differs);
the whole model through `fit()` with Adam. All at a small size on the CPU,
seeded weights, float32 compute (bf16 is the benchmark's check,
tests/perfbench/)."""

from dataclasses import asdict

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.models import nemotron_h_reference as ref
from dlrm_flexflow_tpu.models.nemotron_h import (NemotronHConfig,
                                                 build_nemotron_h)
from dlrm_flexflow_tpu.ops import delta_net, mamba, norm
from dlrm_flexflow_tpu.parallel.mesh import make_mesh

B, S = 2, 40        # two and a half chunks
# the inner width (4 x 8 = 32) is NOT expand x hidden; 2 groups for 4 heads
CFG = NemotronHConfig(
    vocab_size=128, hidden_size=64, num_hidden_layers=5,
    hybrid_override_pattern="ME*ME", mamba_num_heads=4, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, chunk_size=16, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, n_routed_experts=16,
    num_experts_per_tok=4, experts_held=4, expert_offset=8,
    balance_rate=1e-3)
OPT = dict(alpha=1e-3, beta1=0.9, beta2=0.999, epsilon=1e-8)
LAYERS = ref.expert_layers(asdict(CFG))
BIAS = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (CFG.n_routed_experts,))


def _model(cfg=CFG, seq=S, batch=B, seed=5):
    model = ff.FFModel(ff.FFConfig(batch_size=batch, seed=3))
    build_nemotron_h(model, cfg, seq)
    model.compile(ff.AdamOptimizer(**OPT), "sparse_categorical_crossentropy",
                  ["sparse_categorical_crossentropy"],
                  mesh=make_mesh(devices=jax.devices()[:1]))
    model.init_layers(seed)
    return model


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def _lively(tree, scale=0.05, key=7):
    """Weights off their initial values, so no term hides behind a zero
    (the convolution's bias) or a one (`D`, the norms)."""
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


def test_the_model_is_one_part_a_block(model):
    assert ref.mixer_names(asdict(CFG)) == [
        "l0_mamba", "l1_moe", "l2_attn", "l3_mamba", "l4_moe"]
    assert [CFG.mixer_name(i) for i in range(5)] == ref.mixer_names(
        asdict(CFG))
    names = [op.name for op in model.ops]
    assert names[2:5] == ["l0_norm", "l0_mamba", "l0_add"]
    # two matrices an expert, routed and shared alike; plain attention
    assert sorted(model.params["l1_moe"]) == [
        "router", "shared_down", "shared_up", "w_down", "w_up"]
    assert sorted(model.params["l2_attn"]) == ["wk", "wo", "wq", "wv"]
    assert model.params["l2_attn"]["wq"].shape == (64, 4 * 16)
    assert sorted(model.opt_state["m"]["l1_moe"]) == sorted(
        model.params["l1_moe"])
    p = model.params["l0_mamba"]
    assert p["w_in"].shape == (64, 32 + (32 + 2 * 2 * 16) + 4)
    assert p["conv"].shape == (96, 4) and p["conv_bias"].shape == (96,)
    # softplus(dt_bias) lies in [time_step_min, time_step_max]
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        build_nemotron_h(ff.FFModel(ff.FFConfig(batch_size=2)),
                         NemotronHConfig(num_hidden_layers=3,
                                         hybrid_override_pattern="MX*"), 8)


def _ssd_inputs(b=2, s=S, h=4, p=8, g=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    return (jax.random.normal(k[0], (b, s, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, s, h)) - 1.0),
            -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7)),
            jax.random.normal(k[3], (b, s, g, n)),
            jax.random.normal(k[4], (b, s, g, n))), jax.random.normal(
        k[5], (b, s, h, p))


def _sequential(log_a, s):
    """The hand-over as the recurrence has it, one chunk after the other."""
    def step(S, xs):
        la, own = xs
        return S * jnp.exp(la)[..., None, None] + own, S
    return jnp.moveaxis(jax.lax.scan(
        step, jnp.zeros_like(s[:, 0]),
        (jnp.moveaxis(log_a, 2, 0), jnp.moveaxis(s, 1, 0)))[1], 0, 1)


@pytest.mark.parametrize("hand_over", [mamba.states_entering, _sequential],
                         ids=["decay_matrix", "sequential"])
def test_the_chunked_recurrence_is_the_stepwise_one(hand_over):
    """Forward and every gradient, at 40 positions in chunks of 16 and two
    groups for four heads; the hand-over as one decay-matrix product and
    as the walk it replaces."""
    args, ct = _ssd_inputs()
    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(
            lambda *a: jnp.sum(mamba.ssd_stepwise(*a) * ct),
            argnums=(0, 1, 2, 3, 4))(*args)
        got, mine = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(mamba.ssd_chunked(
                *a, 16, hand_over=hand_over) * ct),
            argnums=(0, 1, 2, 3, 4)))(*args)
        np.testing.assert_allclose(mamba.ssd_chunked(*args, 16),
                                   mamba.ssd_stepwise(*args),
                                   rtol=1e-4, atol=1e-4)
    # a sum of 2,560 terms of size ten that cancel
    assert float(got) == pytest.approx(float(want), abs=2e-3)
    for a, b in zip(mine, grads):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * float(
            jnp.max(jnp.abs(b))))
    # the reference's own recurrence, a sequence at a time, is the same
    x, dt, A, Bm, C = args
    rep = lambda t: jnp.repeat(t, 2, axis=1)     # noqa: E731
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            ref.ssm(x[0], dt[0], A, rep(Bm[0]), rep(C[0])),
            mamba.ssd_stepwise(*args)[0], rtol=1e-5, atol=1e-5)


def test_a_fast_decay_neither_overflows_nor_poisons_the_gradient():
    """Log decays of -40 a position: above the diagonal exp(+640) would be
    inf, and a masked inf still makes the gradient NaN."""
    (x, dt, A, Bm, C), ct = _ssd_inputs()
    A = jnp.full_like(A, -40.0)
    val, grads = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(mamba.ssd_chunked(*a, 16) * ct),
        argnums=(0, 1, 2)))(x, jnp.ones_like(dt), A, Bm, C)
    assert np.isfinite(float(val))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_the_convolution_takes_a_bias():
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    xs = jax.random.normal(k[0], (2, 11, 6))
    w, b = jax.random.normal(k[1], (6, 4)), jax.random.normal(k[2], (6,))
    got = delta_net.causal_depthwise_conv(xs, w, b)
    np.testing.assert_allclose(got[1], ref.causal_conv(xs[1], w, b),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got - b,
                               delta_net.causal_depthwise_conv(xs, w),
                               rtol=1e-5, atol=1e-6)


def test_the_grouped_norm_after_the_gate_is_neither_of_its_neighbours():
    k = jax.random.split(jax.random.PRNGKey(4), 3)
    y, z = (jax.random.normal(k[i], (3, 32)) for i in (0, 1))
    w = 1.0 + 0.1 * jax.random.normal(k[2], (32,))
    gate = jax.nn.silu(z)
    got = norm.rms_norm(y * gate, w, 1e-5, False, 8)
    want = jnp.concatenate(
        [ref.rms_norm((y * gate)[:, i:i + 8], w[i:i + 8], 1e-5)
         for i in range(0, 32, 8)], axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    ungrouped = norm.rms_norm(y * gate, w, 1e-5, False)
    norm_then_gate = norm.rms_norm(y, w, 1e-5, False, 8) * gate
    for other in (ungrouped, norm_then_gate):
        assert float(jnp.max(jnp.abs(got - other))) > 0.1
    # one group over all the features is the plain norm
    np.testing.assert_allclose(norm.rms_norm(y, w, 1e-5, False, 32),
                               norm.rms_norm(y, w, 1e-5, False), rtol=1e-6)


def _op_and_reference(model, kind, cfg=None, without=None):
    cfg = cfg or asdict(CFG)
    name = {"mamba": "l0_mamba", "moe": "l1_moe", "attn": "l2_attn",
            "norm": "l0_norm"}[kind]
    op, p = model.get_layer_by_name(name), model.params[name]

    def system(p, x):
        if kind == "moe":
            st = dict(model.op_state[name], bias=BIAS)
            return op.apply_with_state(p, st, [x])[0][0]
        return op.apply(p, [x])[0]

    def reference(p, x):
        if without is not None:
            p, cfg_ = without(dict(p), dict(cfg))
        else:
            cfg_ = cfg
        one = {"mamba": lambda a: ref.mamba(p, a, cfg_),
               "moe": lambda a: ref.moe(p, a, cfg_, BIAS)[0],
               "attn": lambda a: ref.attention(p, a, cfg_),
               "norm": lambda a: ref.rms_norm(a, p["weight"],
                                              CFG.layer_norm_epsilon)}[kind]
        return jnp.stack([one(a) for a in x])
    return system, reference, p


@pytest.mark.parametrize("kind", ["norm", "mamba", "moe", "attn"])
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
    assert sorted(gs[0]) == sorted(gr[0])
    for a, b in zip(jax.tree.leaves(gs), jax.tree.leaves(gr)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(a, b, rtol=0, atol=3e-4 * scale)


def test_the_ops_in_bfloat16_stay_near_the_reference(x):
    """The benchmark's compute dtype: bf16 operands, fp32 accumulation,
    state, decays, router and norms. Each new op against the fp32
    reference, to what bf16's eight bits allow."""
    model = ff.FFModel(ff.FFConfig.parse_args(
        ["-b", str(B), "--compute-dtype", "bfloat16"]))
    build_nemotron_h(model, CFG, S)
    model.compile(ff.AdamOptimizer(**OPT), "sparse_categorical_crossentropy",
                  [], mesh=make_mesh(devices=jax.devices()[:1]))
    model.init_layers(5)
    for kind in ("mamba", "moe", "attn"):
        system, reference, p = _op_and_reference(model, kind)
        p = _lively(p)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(reference)(p, x)
        got = jax.jit(system)(p, x)
        assert got.dtype == x.dtype
        err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        assert 1e-5 < err < 3e-2, (kind, err)


def _no(key, value=0.0):
    def without(p, cfg):
        p[key] = jnp.full_like(p[key], value)
        return p, cfg
    return without


def _scale_one(p, cfg):
    return p, dict(cfg, routed_scaling_factor=1.0)


def _norm_then_gate(y, z, w, groups, eps):
    s, d = y.shape
    return ref.rms_norm(y.reshape(s, groups, d // groups), 1.0,
                        eps).reshape(s, d) * w * jax.nn.silu(z)


def _one_group(y, z, w, groups, eps):
    return ref.rms_norm(y * jax.nn.silu(z), w, eps)


@pytest.mark.parametrize("kind,without", [
    ("mamba", _no("conv_bias")), ("mamba", _no("D")),
    ("mamba", _no("dt_bias")), ("moe", _scale_one),
    ("mamba", _norm_then_gate), ("mamba", _one_group)],
    ids=["conv_bias", "skip_D", "dt_bias", "routed_scale", "gate_then_norm",
         "norm_groups"])
def test_a_reference_without_a_part_differs(model, x, kind, without,
                                            monkeypatch):
    """Each part of the mathematics is worth more than the tolerance the
    comparison above allows: a reference that leaves it out (or norms
    before it gates, or norms over one group) is far from the system, so a
    system that did the same would fail that comparison."""
    if without in (_norm_then_gate, _one_group):
        monkeypatch.setattr(ref, "gated_norm", without)
        without = None
    system, reference, p = _op_and_reference(model, kind, without=without)
    p = _lively(p, 0.3)
    with jax.default_matmul_precision("highest"):
        got, other = jax.jit(system)(p, x), jax.jit(reference)(p, x)
    assert float(jnp.max(jnp.abs(got - other))) > 0.02 * float(
        jnp.max(jnp.abs(got)))


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
    apply = jax.jit(lambda st: op.apply_with_state(p, st, [x])[1])
    with jax.default_matmul_precision("highest"):
        loads = jax.jit(lambda b: ref.moe(p, xt, cfg, b)[2])
    for _ in range(3):
        st = apply(st)
        with jax.default_matmul_precision("highest"):
            load = loads(bias)
        bias = ref.bias_update({"l1_moe": bias}, [load], dict(
            cfg, hybrid_override_pattern="ME"))["l1_moe"]
        total = total + np.asarray(load)
        np.testing.assert_allclose(st["bias"], bias, atol=1e-9)
        assert np.array_equal(st["load"], total)
    assert int(st["load"].sum()) == 3 * B * S * CFG.num_experts_per_tok
    assert float(jnp.abs(st["bias"]).max()) == pytest.approx(3e-3)
    lo = CFG.expert_offset
    assert np.array_equal(st["pairs"], total[lo:lo + CFG.experts_held])
    # the weights sum to the scale, whatever the bias
    w, _ = op.route(p, xt, st["bias"])
    np.testing.assert_allclose(w.sum(-1), CFG.routed_scaling_factor,
                               rtol=1e-6)


def test_the_model_trains_through_fit_like_the_reference():
    """Loss before each of three Adam steps, every weight and every bias
    buffer after them; the token rows no token names keep their bits; the
    counters as `expert_stats()` and `--obs on` give them."""
    model = _model()
    t = _tokens()
    p0 = _host(model.params)
    reported = []
    model.fit({"tokens": t[:, :-1]}, t[:, 1:], epochs=3, verbose=False,
              callbacks=[lambda m, e, rep: reported.append(
                  rep["sparse_cce"])])
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
        assert reported[int(n) - 1] == pytest.approx(float(loss), rel=2e-6)
        pairs, loads = pairs + np.asarray(aux[0]), loads + np.asarray(aux[1])
    assert sorted(params) == sorted(model.params)
    for name, sub in params.items():
        for pn, w in sub.items():
            # three steps of at most alpha each; Adam's division makes an
            # element with a tiny gradient sensitive to its rounding
            np.testing.assert_allclose(
                np.asarray(model.params[name][pn]), w, rtol=0,
                atol=0.1 * 3 * OPT["alpha"], err_msg=f"{name}.{pn}")
            assert np.abs(np.asarray(w) - p0[name][pn]).max() > 0.5 * OPT[
                "alpha"] or name == "embed", f"{name}.{pn} never moved"
    named = np.zeros(CFG.vocab_size, bool)
    named[t[:, :-1].reshape(-1)] = True
    assert np.array_equal(
        np.asarray(model.params["embed"]["kernel"])[~named],
        p0["embed"]["kernel"][~named])
    stats = model.expert_stats()
    assert sorted(stats) == sorted(LAYERS) == ["l1_moe", "l4_moe"]
    for i, name in enumerate(LAYERS):
        st = stats[name]
        assert st["tokens"] == 3 * B * S
        assert np.array_equal(st["pairs"], pairs[i])
        assert np.array_equal(st["load"], loads[i])
        np.testing.assert_allclose(st["bias"], biases[name], atol=1e-9)
        assert st["rows"] >= st["pairs"].sum()
    assert any(np.abs(st["bias"]).max() > 2e-3 for st in stats.values())
    # and as the series `--obs on` scrapes, with no change for this model
    series = list(model._obs_collect_experts())
    load = [v for n, lab, v in series
            if n == "ff_moe_load_total" and lab["op"] == "l4_moe"]
    assert load == stats["l4_moe"]["load"].tolist()
    held = [v for n, lab, v in series
            if n == "ff_moe_pairs_total" and lab["op"] == "l1_moe"]
    assert held == stats["l1_moe"]["pairs"].tolist()
    (peak,) = [v for n, lab, v in series
               if n == "ff_moe_bias_abs_max" and lab["op"] == "l1_moe"]
    assert peak == pytest.approx(np.abs(stats["l1_moe"]["bias"]).max())


def test_flops_count_two_products_an_expert_and_the_inner_width(model):
    moe = model.get_layer_by_name("l1_moe")
    d, pairs = 64, 4 * 4 / 16
    assert moe.flops_per_sample() == S * (
        2.0 * d * 16 + 4.0 * d * (pairs * 24 + 48))
    mix = model.get_layer_by_name("l0_mamba")
    proj = 2.0 * S * d * (2 * 32 + 96 + 4)
    ssd = 2.0 * S * (8 * (2 * 16 + 4 * 8) + 2 * 4 * 8 * 16)
    assert mix.flops_per_sample() == proj + ssd
    attn = model.get_layer_by_name("l2_attn")
    assert attn.flops_per_sample() == (
        2.0 * S * d * (2 * 64 + 2 * 32) + 2.0 * S * S * 64)
