"""The Nemotron-H family: the whole of `run.py`'s flow on the CPU at a tiny
size (a rehearsal of a cell that differs from the committed one in its
sizes alone: the harness's `REHEARSE` cannot cut a sequence or a depth),
what the check must refuse, the shape arithmetic against the built model,
the benchmark's copy of the plain reference against the program's and its
dual form of the recurrence against the stepwise one, and the three readers
on made-up counters."""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import manifest as mf
from perfbench.layer_metrics import (nemotron_h_held_pair_share,
                                     nemotron_h_padded_row_share,
                                     nemotron_h_router_load_max_over_mean)
from perfbench.models import nemotron_h as family
from perfbench.traffic import gen

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BATCH, STEPS = 2, 3
TINY = {
    "name": "nemotron_tiny", "family": "nemotron_h",
    "source": "https://example.org/a-tiny-nemotron-h",
    "hidden_size": 64, "num_hidden_layers": 5,
    "hybrid_override_pattern": "ME*ME", "layer_norm_epsilon": 1e-5,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "use_conv_bias": True, "time_step_min": 0.001, "time_step_max": 0.1,
    "time_step_floor": 0.0001, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "n_routed_experts": 4, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "balance_rate": 0.001,
    "vocab_size": 128, "expert_offset": 8, "seq_len": 40,
    "published": {"num_hidden_layers": 52, "n_routed_experts": 16,
                  "vocab_size": 1024},
    "loss": "sparse_categorical_crossentropy",
    "optimizer": {"type": "adam", "alpha": 1e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8},
    # float32 products: at 1,920 pairs ONE pair that bf16 rounding sends
    # elsewhere reads 5e-4, over the limit the cell's 589,824 pairs set (the
    # chip's runs judge bf16; `tests/test_nemotron_h.py` runs the ops in it)
    "compute_dtype": "float32", "deployment": {"chips": 4},
    "reduced": ["num_hidden_layers", "hybrid_override_pattern",
                "n_routed_experts", "vocab_size"],
    "assumed": {}, "departures": []}
ZIPF = {"ids": {"distribution": "zipf", "alpha": 1.05}}
LAYERS = ["l1_moe", "l4_moe"]
CELL = "nemotron_3_nano_30b_a3b.s8192_local"


def _checked(monkeypatch=None, fault=None):
    """The harness's own sequence: a warm-up (so that Adam's state and the
    bias buffers are not zero), the snapshot, STEPS steps through fit on
    one batch, the read."""
    if fault is not None:
        fault(monkeypatch)
    rows = family.held_table_rows(TINY, 1)
    model, timings = family.build(TINY, rows, BATCH, 1, seed=3)
    assert timings["build_s"] > 0 and timings["init_s"] > 0
    data = gen.generate(ZIPF, family.input_fields(TINY, rows), 4 * BATCH,
                        seed=3)
    x, y = family.fit_arrays(data)
    t = data["tokens"][:, 0, :]
    assert x["tokens"].shape == y.shape == (4 * BATCH, TINY["seq_len"])
    assert np.array_equal(x["tokens"], t[:, :-1])
    assert np.array_equal(y, t[:, 1:])
    model.fit(x, y, epochs=2, verbose=False)
    first = {k: v[:BATCH] for k, v in data.items()}
    snap = family.snapshot(model, TINY, first)
    x1, y1 = family.fit_arrays(first)
    losses = []
    model.fit(x1, y1, epochs=STEPS, verbose=False, callbacks=[
        lambda m, epoch, report: losses.append(report[family.LOSS_METRIC])])
    return snap, snap["touched"].read(model), losses


@pytest.fixture(scope="module")
def checked():
    return _checked()


@pytest.fixture(scope="module")
def reference(checked):
    return family.run_reference(checked[0], TINY, STEPS)


def test_system_agrees_with_the_plain_reference(checked, reference):
    snap, after, losses = checked
    out = family.compare(snap, after, losses, reference, TINY)
    assert out["ok"], out
    assert out["steps"] == STEPS and snap["step"] == 8
    assert out["loss_rel_err"] < family.LOSS_RTOL / 2
    assert out["update_cos_min"] > 0.95
    # the state-space layers' small parameters are judged by name
    assert sorted(out["update_by_name"]) == sorted(
        [f"mamba.{n}" for n in family.BY_NAME] + ["small"])
    assert 0 < out["token_rows_named"] < TINY["vocab_size"]
    assert out["pairs_all_reference"] == (
        STEPS * BATCH * TINY["seq_len"] * 4 * len(LAYERS))
    assert out["pairs_held_reference"] > 0 and out["probe_mismatch"] == 0.0
    # the bias had moved before the snapshot and moved on after it
    assert snap["counters"]["l1_moe"]["bias"].any()
    assert out["bias_moved"] > 0.5 and out["bias_abs_max"] > 8e-3
    # the counters the layer metrics read
    counters = family.expert_counters()
    assert sorted(counters) == sorted(LAYERS)
    for c in counters.values():
        tokens = (2 * 4 + STEPS) * BATCH * TINY["seq_len"]
        assert c["tokens"] == tokens
        assert c["load"].sum() == tokens * TINY["num_experts_per_tok"]
        assert c["rows"] >= c["pairs"].sum() > 0
        assert np.array_equal(c["pairs"], c["load"][8:12])


def test_verify_releases_the_system_and_compares(checked):
    snap, after, losses = checked
    out = family.verify(snap, after, losses, TINY)
    assert out["ok"], out


# ---- wrong builds of the system, each refused by some limit ---------------
def _bf16_router(mp):
    import jax
    import jax.numpy as jnp
    from dlrm_flexflow_tpu.ops.moe import MoE

    def route(self, params, xt, bias=None):
        scores = jax.nn.sigmoid(jnp.dot(
            xt.astype(jnp.bfloat16), params["router"].astype(jnp.bfloat16)
        ).astype(jnp.float32))
        _, top_e = jax.lax.top_k(scores + bias, self.top_k)
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
        return 2.5 * top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_e
    mp.setattr(MoE, "route", route)


def _scale_left_out(mp):
    from dlrm_flexflow_tpu.ops.moe import MoE
    route = MoE.route

    def unscaled(self, params, xt, bias=None):
        w, e = route(self, params, xt, bias)
        return w / self.routed_scale, e
    mp.setattr(MoE, "route", unscaled)


def _bias_never_updated(mp):
    from dlrm_flexflow_tpu.ops.moe import MoE
    apply = MoE.apply_with_state

    def frozen(self, params, state, xs, **kw):
        outs, new = apply(self, params, state, xs, **kw)
        return outs, dict(new, bias=state["bias"])
    mp.setattr(MoE, "apply_with_state", frozen)


def _conv_bias_left_out(mp):
    from dlrm_flexflow_tpu.ops import mamba
    conv = mamba.causal_depthwise_conv
    mp.setattr(mamba, "causal_depthwise_conv",
               lambda x, w, bias=None: conv(x, w))


def _skip_left_out(mp):
    """y = S C without + D x: `D` then has no gradient either."""
    import jax.numpy as jnp
    from dlrm_flexflow_tpu.ops import mamba
    apply = mamba.Mamba2.apply
    mp.setattr(mamba.Mamba2, "apply", lambda self, params, xs, **kw: apply(
        self, dict(params, D=jnp.zeros_like(params["D"])), xs, **kw))


def _norm_before_gate(mp):
    import jax
    from dlrm_flexflow_tpu.ops import mamba
    mp.setattr(mamba, "gated_rms_norm", lambda y, z, w, eps, group:
               mamba.rms_norm(y, w, eps, False, group) * jax.nn.silu(z))


def _norm_over_one_group(mp):
    import jax
    from dlrm_flexflow_tpu.ops import mamba
    mp.setattr(mamba, "gated_rms_norm", lambda y, z, w, eps, group:
               mamba.rms_norm(y * jax.nn.silu(z), w, eps, False))


def _adam_without_v(mp):
    """m and v are kept as they should be; the step forgets to divide."""
    import jax
    import dlrm_flexflow_tpu as ff

    class NoV(ff.AdamOptimizer):
        def update(self, params, grads, state):
            _, new_state = super().update(params, grads, state)
            return jax.tree.map(lambda w, m: w - self.alpha * m, params,
                                new_state["m"]), new_state
    mp.setattr(ff, "AdamOptimizer", NoV)


@pytest.mark.parametrize("fault", [
    _bf16_router, _scale_left_out, _bias_never_updated, _conv_bias_left_out,
    _skip_left_out, _norm_before_gate, _norm_over_one_group,
    _adam_without_v],
    ids=lambda f: f.__name__.lstrip("_"))
def test_the_check_refuses_a_wrong_build(monkeypatch, fault):
    snap, after, losses = _checked(monkeypatch, fault)
    out = family.verify(snap, after, losses, TINY)
    assert not out["ok"], out


def test_the_check_refuses_the_reference_in_bfloat16(checked, reference):
    """The reading that sets the limits: the reference computed in the
    nearest precision below the stated one, weights and state and all,
    taken as if it were the system, is not correct; and not by every limit
    at once, so the limits are not all loose."""
    import jax.numpy as jnp
    snap, after, losses = checked
    low = family.run_reference(snap, TINY, STEPS, dtype=jnp.bfloat16)
    counters = {
        name: dict(c, pairs=c["pairs"] + low["pairs"][i],
                   load=c["load"] + low["loads"][i],
                   bias=low["biases"][name])
        for i, (name, c) in enumerate(
            (n, snap["counters"][n]) for n in LAYERS)}
    out = family.compare(snap, {"params": low["params"],
                                "counters": counters},
                         low["losses"], reference, TINY,
                         system_probe=low["probe"])
    assert not out["ok"], out
    assert out["loss_rel_err"] > family.LOSS_RTOL
    assert out["probe_weight_err"] > family.PROBE_WEIGHT_ATOL
    assert out["update_cos_min"] < family.UPDATE_COS_MIN
    assert out["load_mismatch"] > family.LOAD_MISMATCH_MAX
    # (on the chip it passes the bias buffers' limit, 7-9% of 10%; at this
    # size it still names the rows the reference names, and loses no pair)
    assert out["token_rows_named_but_still"] == 0
    assert out["pairs_all_system"] == out["pairs_all_reference"]


@pytest.mark.parametrize("fault", ["loss", "nan", "rows_dropped",
                                   "lazy_rows_moved", "bias_reset",
                                   "load_of_held_only", "held_pairs_lost"])
def test_the_check_refuses(checked, reference, fault):
    snap, after, losses = checked
    counters, params = after["counters"], after["params"]
    if fault == "loss":
        losses = [1.01 * v for v in losses]
    elif fault == "nan":
        losses = [losses[0], float("nan"), losses[2]]
    elif fault == "bias_reset":
        counters = {n: dict(c, bias=np.zeros_like(c["bias"]))
                    for n, c in counters.items()}
    elif fault == "load_of_held_only":
        counters = {n: dict(c, load=np.where(
            np.arange(16) // 4 == 2, c["load"], snap["counters"][n]["load"]))
            for n, c in counters.items()}
    elif fault == "held_pairs_lost":
        counters = {n: dict(c, pairs=c["pairs"] - np.array([2, 0, 0, 0]))
                    for n, c in counters.items()}
    else:
        kernel = (snap if fault == "rows_dropped" else after)[
            "params"]["embed"]["kernel"]
        moved = kernel + (1e-6 if fault == "lazy_rows_moved" else 0.0)
        params = dict(params, embed={"kernel": moved})
    out = family.compare(snap, {"params": params, "counters": counters},
                         losses, reference, TINY)
    assert not out["ok"], out


def test_no_share_divides_by_what_a_seed_can_make_small(checked, reference):
    """A seed whose held experts drew almost nothing: the routing share is
    still of ALL the pairs, so two pairs gone astray stay two in 3,840."""
    snap, after, losses = checked
    few = {n: dict(c, pairs=snap["counters"][n]["pairs"] + [1, 0, 0, 0])
           for n, c in after["counters"].items()}
    ref = dict(reference, pairs=np.array([[1, 0, 0, 0], [0, 1, 0, 0]]))
    out = family.compare(snap, {"params": after["params"], "counters": few},
                         losses, ref, TINY)
    assert out["pairs_held_reference"] == 2
    assert out["routing_mismatch"] == pytest.approx(
        2 / out["pairs_all_reference"])


def test_the_dual_form_is_the_recurrence():
    """`ssd_dual` (no state, the log decays summed outwards from a block's
    first position) against `ssm`, the recurrence a position at a time, and
    against the program's own reference; forward and gradients; two blocks
    of positions and two groups."""
    import jax
    import jax.numpy as jnp
    from dlrm_flexflow_tpu.models import nemotron_h_reference as program
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    s, h, p, g, n = 32, 4, 8, 2, 16
    x = jax.random.normal(k[0], (s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (s, h)) - 1.0)
    A = -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7))
    B, C = (jax.random.normal(k[i], (s, g, n)) for i in (3, 4))
    ct = jax.random.normal(k[5], (s, h, p))
    args = (x, dt, A, B, C)
    with jax.default_matmul_precision("highest"):
        block = family.SSD_BLOCK
        try:
            family.SSD_BLOCK = 16
            got, mine = jax.value_and_grad(
                lambda *a: jnp.sum(family.ssd_dual(*a) * ct),
                argnums=(0, 1, 2, 3, 4))(*args)
            y = family.ssd_dual(*args)
        finally:
            family.SSD_BLOCK = block
        want, grads = jax.value_and_grad(
            lambda *a: jnp.sum(family.ssm(*a) * ct),
            argnums=(0, 1, 2, 3, 4))(*args)
        np.testing.assert_allclose(y, family.ssm(*args), rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(
            y, program.ssm(x, dt, A, jnp.repeat(B, 2, 1),
                           jnp.repeat(C, 2, 1)), rtol=1e-4, atol=1e-5)
    assert float(got) == pytest.approx(float(want), abs=1e-3)
    for a, b in zip(mine, grads):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * float(
            jnp.max(jnp.abs(b))))


def test_the_two_copies_of_the_reference_agree(checked):
    """The program's plain reference and the benchmark's copy: the same
    loss, counts, biases and updated weights from the same snapshot."""
    import jax
    import jax.numpy as jnp
    from dlrm_flexflow_tpu.models import nemotron_h_reference as program
    snap, _, _ = checked
    cfg = family.model_config(TINY, snap["vocab"])
    opt = {k: v for k, v in TINY["optimizer"].items() if k != "type"}
    mine = family.run_reference(snap, TINY, 1)
    put = lambda t: jax.tree.map(jnp.asarray, t)     # noqa: E731
    biases = {n: jnp.asarray(snap["counters"][n]["bias"]) for n in LAYERS}
    loss, aux, params, _, _, biases = jax.jit(
        lambda p, m, v, b: program.adam_step(
            p, m, v, b, float(snap["step"] + 1),
            jnp.asarray(snap["batch"]["tokens"][:, 0, :]), cfg, opt))(
        put(snap["params"]), put(snap["m"]), put(snap["v"]), biases)
    assert program.expert_layers(cfg) == family.expert_layers(cfg) == LAYERS
    assert float(loss) == pytest.approx(mine["losses"][0], rel=1e-6)
    assert np.array_equal(np.asarray(aux[0]), mine["pairs"])
    assert np.array_equal(np.asarray(aux[1]), mine["loads"])
    for n in LAYERS:
        assert np.array_equal(np.asarray(biases[n]), mine["biases"][n])
    for name, sub in mine["params"].items():
        for pn, w in sub.items():
            np.testing.assert_allclose(np.asarray(params[name][pn]), w,
                                       rtol=0, atol=2e-6,
                                       err_msg=f"{name}.{pn}")


def test_operations_from_the_shapes(checked):
    config = mf.load_config(mf.load(), "nemotron_3_nano_30b_a3b")
    n = family.parameter_counts(config)
    # ISSUE 32's table: 38.74 M a Mamba-2 layer, 100.12 M an expert layer,
    # 23.4 M the attention, 44.0 M a vocabulary matrix, 667.0 M in all
    assert n["mamba_proj"] + n["mamba_small"] == 4 * 38_742_208
    assert n["attention"] == 23_396_352
    assert n["experts"] == 4 * 8 * 2 * 2688 * 1856
    assert n["router_shared"] == 4 * (2688 * 128 + 2 * 2688 * 3712)
    assert n["experts"] + n["router_shared"] == 4 * 100_122_624
    assert n["embed"] == n["head"] == 16_384 * 2688
    assert n["norms"] == 10 * 2688
    assert sum(n.values()) == config["parameters"] == 666_962_944
    # useful FLOPs a sequence: 0.375 experts of two products a token, half
    # the scores of one attention layer, the recurrence's four products
    macs = 8192 * (n["mamba_proj"] + n["attention"] + n["router_shared"]
                   + n["head"] + 4 * 0.375 * 2 * 2688 * 1856)
    assert macs == pytest.approx(8192 * 318.4e6, rel=1e-3)
    macs += 8192 * 8192 * 32 * 256 / 2
    macs += 4 * 8192 * (64 * (1024 + 4096) + 2 * 4096 * 128)
    assert family.flops_per_sample(config) == 6.0 * macs
    assert 17.5e12 < family.flops_per_sample(config) < 17.7e12
    assert family.bytes_per_step(config, 1) == pytest.approx(
        36 * 666_962_944 + 8192 * 2688 * 4 * 2 * 11)
    assert family.held_table_rows(config, 1) == [16_384]
    assert 16_384 * 8 == config["published"]["vocab_size"]
    (field,) = family.input_fields(config, [16_384])
    assert (field["bag"], field["rows"]) == (8193, [16_384])
    # and against a model that was built: the tiny one's parameters, by part
    built = {name: sum(int(a.size) for a in sub.values())
             for name, sub in checked[0]["params"].items()}
    n = family.parameter_counts(TINY)
    assert sum(n.values()) == sum(built.values())
    assert n["embed"] == built["embed"] and n["head"] == built["head"]
    assert n["attention"] == built["l2_attn"]
    assert n["mamba_proj"] + n["mamba_small"] == (built["l0_mamba"]
                                                  + built["l3_mamba"])
    assert n["experts"] + n["router_shared"] == sum(built[k] for k in LAYERS)


def test_the_committed_configuration_is_the_catalogs_row():
    """Every number of the published config under the same key, but the
    four that `reduced` lists."""
    config = mf.load_config(mf.load(), "nemotron_3_nano_30b_a3b")
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_num_heads": 64,
        "mamba_proj_bias": False, "max_position_embeddings": 262144,
        "mlp_bias": False, "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 52, "num_key_value_heads": 2,
        "partial_rotary_factor": 1, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "vocab_size": 131072}
    assert config["reduced"] == ["num_hidden_layers",
                                 "hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"]
    assert config["mlp_hidden_act"] == "relu2" and config[
        "model_type"] == "nemotron_h"
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value and len(str(config[key])) <= len(
                str(value))
        else:
            assert config[key] == value, key
    # the held layers are the published pattern's first nine
    assert config["hybrid_override_pattern"] == published[
        "hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert config["deployment"]["chips"] == 16
    cell = mf.find_cell(mf.load(), CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "b1_zipf_8"
    assert "all of their share" in cell["why"] and "sixteenth" in cell["why"]
    assert len(cell["why"]) <= 200


# ---- the three readers, on made-up counters -------------------------------
def _run(counters):
    return SimpleNamespace(family=SimpleNamespace(
        expert_counters=lambda: counters))


def test_the_readers_on_made_up_counters():
    even = {"tokens": 8, "rows": 16,
            "pairs": np.array([4, 4]), "load": np.array([4, 4, 4, 4] * 2)}
    skew = {"tokens": 8, "rows": 32,
            "pairs": np.array([12, 0]),
            "load": np.array([12, 0, 2, 2, 4, 4, 4, 4])}
    run = _run({"l1_moe": even, "l3_moe": skew})
    readers = (nemotron_h_router_load_max_over_mean,
               nemotron_h_held_pair_share, nemotron_h_padded_row_share)
    load, held, padded = readers
    # the worst layer: 12 pairs on the busiest of eight against a mean of 4
    assert load.read(run) == pytest.approx(3.0)
    # 8 + 12 of the 32 + 32 pairs fell on the held experts
    assert held.read(run) == pytest.approx(100 * 20 / 64)
    # 48 rows computed for 20 pairs
    assert padded.read(run) == pytest.approx(100 * 28 / 48)
    assert load.read(_run({"a": even})) == 1.0
    assert held.read(_run({"a": even})) == 25.0
    # a program without the `load` counter, a family without counters (the
    # parent's), a model that has not stepped: nothing, and no error
    bare = {"l0_moe": {"tokens": 8, "rows": 16, "pairs": np.array([4, 4])}}
    for reader in (load, held):
        assert reader.read(_run(bare)) is None
    idle = {"a": dict(even, rows=0, pairs=np.zeros(2, int),
                      load=np.zeros(8, int))}
    man = mf.load()
    for reader in readers:
        assert reader.read(_run({})) is None
        assert reader.read(SimpleNamespace(family=object())) is None
        assert reader.read(_run(idle)) is None
        assert reader.CELLS == "nemotron_3_nano_30b_a3b.*"
        (entry,) = [m for m in man["per_layer"] if m["name"] == reader.NAME]
        assert entry["workloads"] == [CELL] and entry["layer"] == "ops"


def test_a_tiny_cell_of_the_family_runs_through_run_py(tmp_path):
    """`perfbench/run.py --rehearse` on a cell that differs from the
    committed one in its sizes alone: the flow, the check and the layer
    metrics the new cell reports, the three new ones among them."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(root, "tests", "perfbench"))
    with open(os.path.join(root, "perfbench", "configs",
                           "nemotron_tiny.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    cell = "nemotron_3_nano_30b_a3b.tiny"      # the readers' CELLS pattern
    man["configs"].append({
        "name": "nemotron_tiny", "source": TINY["source"],
        "file": "perfbench/configs/nemotron_tiny.json",
        "reduced": TINY["reduced"], "why": "a test's configuration"})
    man["workloads"].append({
        "name": cell, "config": "nemotron_tiny", "traffic": "b1_zipf_8",
        "chips": 1, "why": "a test's cell"})
    new = ("nemotron_h_router_load_max_over_mean",
           "nemotron_h_held_pair_share", "nemotron_h_padded_row_share")
    for m in man["per_layer"]:
        if m["name"] in new:
            m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    env.pop("XLA_FLAGS", None)
    lint = subprocess.run(
        [sys.executable, "-c", "from perfbench import manifest as m; "
         "print(m.lint(m.load()))"], cwd=root, text=True,
        capture_output=True, timeout=120, env=env)
    assert lint.stdout.strip() == "[]", lint.stdout + lint.stderr
    p = subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py"),
         "--workload", cell, "--seed", "2147484032", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=root, env=env, text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert lines[-1] == "rehearsal passed"
    (rehearsed,) = [ln for ln in lines if ln.startswith("rehearsed: ")]
    result = json.loads(rehearsed[len("rehearsed: "):])
    assert result["correct"] and result["failed"] == 0
    metrics = result["metrics"]
    assert metrics["nemotron_h_router_load_max_over_mean"]["value"] >= 1.0
    assert 0.0 < metrics["nemotron_h_held_pair_share"]["value"] < 100.0
    assert 0.0 <= metrics["nemotron_h_padded_row_share"]["value"] < 100.0
    assert "held_pair_share" not in metrics
    assert "moe_padded_row_share" not in metrics
    assert metrics["programs_per_step"]["value"] >= 1
    assert metrics["step_roofline"]["value"] > 0
