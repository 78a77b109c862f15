"""The GLM-4.7-Flash family: the whole of `run.py`'s flow on the CPU at a
tiny size (a rehearsal of a cell that differs from the committed one in its
sizes alone: the harness's `REHEARSE` cannot cut a sequence or a depth),
what the check must refuse, the shape arithmetic against the built model,
the benchmark's copy of the plain reference against the program's, and the
three readers on made-up counters."""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import manifest as mf
from perfbench.layer_metrics import (expert_padded_row_share,
                                     held_pair_share,
                                     router_load_max_over_mean)
from perfbench.models import glm4_moe_lite as family
from perfbench.traffic import gen

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BATCH, STEPS = 2, 3
TINY = {
    "name": "glm_tiny", "family": "glm4_moe_lite",
    "source": "https://example.org/a-tiny-glm4-moe-lite",
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-5,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 12, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 1000000, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 1.8, "scoring_func": "sigmoid",
    "balance_rate": 0.001, "mtp_loss_weight": 0.3,
    "vocab_size": 128, "expert_offset": 8, "seq_len": 64,
    "published": {"num_hidden_layers": 47, "n_routed_experts": 16,
                  "vocab_size": 1024},
    "loss": "sparse_categorical_crossentropy",
    "optimizer": {"type": "adam", "alpha": 1e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8},
    "compute_dtype": "bfloat16", "deployment": {"chips": 4},
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "assumed": {}, "departures": []}
ZIPF = {"ids": {"distribution": "zipf", "alpha": 1.05}}
LAYERS = ["l1_moe", "l2_moe", "mtp_moe"]


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
    s = TINY["seq_len"]
    assert x["tokens"].shape == y.shape == (4 * BATCH, 2 * s)
    t = data["tokens"][:, 0, :]
    # the main pass and its next token; the module reads the next token and
    # predicts the one after; its last label is a filler that weighs nothing
    assert np.array_equal(x["tokens"][:, :s], t[:, :-1])
    assert np.array_equal(y[:, :s], t[:, 1:])
    assert np.array_equal(x["tokens"][:, s:], t[:, 1:])
    assert np.array_equal(y[:, s:-1], t[:, 2:])
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
    assert out["loss_rel_err"] < family.LOSS_RTOL / 4
    assert out["update_cos_min"] > 0.99
    assert 0 < out["token_rows_named"] < TINY["vocab_size"]
    assert out["pairs_reference"] > 0 and out["probe_mismatch"] == 0.0
    # both terms are in the loss the system reports
    main, mtp = out["loss_reference_main_mtp"][0]
    assert out["loss_reference"][0] == pytest.approx(main + 0.3 * mtp)
    assert mtp > 1.0
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
        return 1.8 * top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_e
    mp.setattr(MoE, "route", route)


def _weights_from_the_biased_scores(mp):
    import jax
    import jax.numpy as jnp
    from dlrm_flexflow_tpu.ops.moe import MoE

    def route(self, params, xt, bias=None):
        scores = jax.nn.sigmoid(jnp.dot(
            xt.astype(jnp.float32), params["router"],
            precision=jax.lax.Precision.HIGHEST)) + bias
        top_p, top_e = jax.lax.top_k(scores, self.top_k)
        return 1.8 * top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_e
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


def _mtp_term_left_out(mp):
    from dlrm_flexflow_tpu.models import glm4_moe_lite as program
    weights = program.loss_weights
    mp.setattr(program, "loss_weights",
               lambda seq, lam: weights(seq, 0.0))


def _mtp_last_position_counted(mp):
    from dlrm_flexflow_tpu.models import glm4_moe_lite as program
    weights = program.loss_weights

    def unmasked(seq, lam):
        w = weights(seq, lam)
        w[-1] = w[-2]
        return w
    mp.setattr(program, "loss_weights", unmasked)


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
    _bf16_router, _weights_from_the_biased_scores, _scale_left_out,
    _bias_never_updated, _mtp_term_left_out, _mtp_last_position_counted,
    _adam_without_v], ids=lambda f: f.__name__.lstrip("_"))
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
    assert out["update_cos_min"] >= family.UPDATE_COS_MIN


@pytest.mark.parametrize("fault", ["loss", "nan", "rows_dropped",
                                   "lazy_rows_moved", "bias_reset",
                                   "load_of_held_only"])
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
    else:
        kernel = (snap if fault == "rows_dropped" else after)[
            "params"]["embed"]["kernel"]
        moved = kernel + (1e-6 if fault == "lazy_rows_moved" else 0.0)
        params = dict(params, embed={"kernel": moved})
    out = family.compare(snap, {"params": params, "counters": counters},
                         losses, reference, TINY)
    assert not out["ok"], out


def test_state_kept_in_bfloat16_is_seen_past_an_idle_expert():
    """An expert no token chose has an m and a v of zeros: the sample is
    spread over the array, and zeros alone are no evidence."""
    import jax.numpy as jnp
    w = np.random.default_rng(0).standard_normal(
        (8, family.STATE_SAMPLE // 2)).astype(np.float32)
    low = np.array(jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32))
    w[:3], low[:3] = 0.0, 0.0                  # the first experts idle
    assert family._uses_fp32(w) and not family._uses_fp32(low)
    assert family._uses_fp32(np.zeros_like(w))


def test_a_named_row_may_rest_where_the_references_rests(checked, reference):
    """The sequence's last token, named by the module's unweighted last
    position alone, has no gradient: such a row is still in the reference
    too and is no fault; a row that rests while the reference's moves is."""
    snap, after, losses = checked
    tokens = np.asarray(snap["batch"]["tokens"]).reshape(-1)
    row = int(tokens[0])
    e0 = snap["params"]["embed"]["kernel"]

    def with_row(tree, value):
        kernel = tree["params"]["embed"]["kernel"].copy()
        kernel[row] = value
        return dict(tree, params=dict(tree["params"],
                                      embed={"kernel": kernel}))

    out = family.compare(snap, with_row(after, e0[row]), losses,
                         with_row(reference, e0[row]), TINY)
    assert out["token_rows_named_but_still"] == 0
    out = family.compare(snap, with_row(after, e0[row]), losses, reference,
                         TINY)
    assert out["token_rows_named_but_still"] == 1 and not out["ok"]


def test_the_two_copies_of_the_reference_agree(checked):
    """The program's plain reference and the benchmark's copy: the same
    loss, counts, biases and updated weights from the same snapshot."""
    import jax
    import jax.numpy as jnp
    from dlrm_flexflow_tpu.models import glm4_moe_lite_reference as program
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
                                       rtol=0, atol=2e-7)


def test_operations_from_the_shapes(checked):
    config = mf.load_config(mf.load(), "glm_4_7_flash")
    n = family.parameter_counts(config)
    # ISSUE 30's table: 21.76 M an MLA, 62.91 M the dense SwiGLU, 9.44 M an
    # expert, 39.6 M a vocabulary matrix, 706.5 M in all
    assert n["attention"] == 6 * 21_759_232
    assert n["dense_mlp"] == 3 * 2048 * 10_240
    assert n["experts"] == 5 * 8 * 3 * 2048 * 1536
    assert n["router_shared"] == 5 * (2048 * 64 + 3 * 2048 * 1536)
    assert n["embed"] == n["head"] == 19_360 * 2048
    assert sum(n.values()) == config["parameters"] == 706_518_528
    # useful FLOPs a sequence: the held share of top-4, half the scores in
    # six blocks, the head twice
    pairs = 4 * 8 / 64
    macs = 8192 * (n["attention"] + n["dense_mlp"] + n["router_shared"]
                   + n["mtp_eh_proj"] + 2 * n["head"]
                   + 5 * pairs * 3 * 2048 * 1536)
    macs += 6 * 8192 * 8192 * 20 * (256 + 256) / 2
    assert family.flops_per_sample(config) == 6.0 * macs
    assert 29e12 < family.flops_per_sample(config) < 30e12
    assert family.bytes_per_step(config, 1) == pytest.approx(
        36 * 706_518_528 + 8192 * 2048 * 4 * 2 * 14)
    assert family.held_table_rows(config, 1) == [19_360]
    assert 19_360 * 8 == config["published"]["vocab_size"]
    (field,) = family.input_fields(config, [19_360])
    assert (field["bag"], field["rows"]) == (8193, [19_360])
    # and against a model that was built: the tiny one's parameters, by part
    built = {name: sum(int(a.size) for a in sub.values())
             for name, sub in checked[0]["params"].items()}
    n = family.parameter_counts(TINY)
    assert sum(n.values()) == sum(built.values())
    assert n["embed"] == built["embed"] and n["head"] == built["head"]
    assert n["dense_mlp"] == built["l0_mlp"]
    assert n["mtp_eh_proj"] == built["mtp_eh_proj"]
    assert n["attention"] == sum(v for k, v in built.items()
                                 if k.endswith("_mla"))
    assert n["experts"] + n["router_shared"] == sum(built[k] for k in LAYERS)


def test_the_committed_configuration_is_the_catalogs_row():
    """Every number of the published config under the same key, but the
    three that `reduced` lists."""
    config = mf.load_config(mf.load(), "glm_4_7_flash")
    published = {
        "attention_bias": False, "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "moe_intermediate_size": 1536, "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_theta": 1000000, "tie_word_embeddings": False,
        "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["topk_method"] == "noaux_tc" and config[
        "model_type"] == "glm4_moe_lite"
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] < value
        else:
            assert config[key] == value, key
    assert config["deployment"]["chips"] == 8
    cell = mf.find_cell(mf.load(), "glm_4_7_flash.s8192_local")
    assert cell["chips"] == 1 and cell["traffic"] == "b1_zipf_8"
    assert "eighth" in cell["why"] and len(cell["why"]) <= 200
    assert gen.load_mix("b1_zipf_8")["dataset_batches"] == 8


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
    run = _run({"l1_moe": even, "mtp_moe": skew})
    # the worst layer: 12 pairs on the busiest of eight against a mean of 4
    assert router_load_max_over_mean.read(run) == pytest.approx(3.0)
    # 8 + 12 of the 32 + 32 pairs fell on the held experts
    assert held_pair_share.read(run) == pytest.approx(100 * 20 / 64)
    # 48 rows computed for 20 pairs
    assert expert_padded_row_share.read(run) == pytest.approx(100 * 28 / 48)
    assert router_load_max_over_mean.read(_run({"a": even})) == 1.0
    assert held_pair_share.read(_run({"a": even})) == 25.0
    # a program without the `load` counter (the parent's), a family without
    # counters, a model that has not stepped: nothing, and no error
    qwen = {"l0_moe": {"tokens": 8, "rows": 16, "pairs": np.array([4, 4])}}
    for reader in (router_load_max_over_mean, held_pair_share):
        assert reader.read(_run(qwen)) is None
        assert reader.read(_run({})) is None
        assert reader.read(SimpleNamespace(family=object())) is None
    idle = {"a": dict(even, rows=0, pairs=np.zeros(2, int),
                      load=np.zeros(8, int))}
    for reader in (router_load_max_over_mean, held_pair_share,
                   expert_padded_row_share):
        assert reader.read(_run(idle)) is None
        assert reader.CELLS == "glm_4_7_flash.*" and reader.LAYER == "ops"
    assert expert_padded_row_share.read(SimpleNamespace(
        family=object())) is None


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
                           "glm_tiny.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    cell = "glm_4_7_flash.tiny"            # the readers' CELLS pattern
    man["configs"].append({
        "name": "glm_tiny", "source": TINY["source"],
        "file": "perfbench/configs/glm_tiny.json",
        "reduced": TINY["reduced"], "why": "a test's configuration"})
    man["workloads"].append({
        "name": cell, "config": "glm_tiny", "traffic": "b1_zipf_8",
        "chips": 1, "why": "a test's cell"})
    new = ("router_load_max_over_mean", "held_pair_share",
           "expert_padded_row_share")
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
         "--workload", cell, "--seed", "2147484030", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=root, env=env, text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert lines[-1] == "rehearsal passed"
    (rehearsed,) = [ln for ln in lines if ln.startswith("rehearsed: ")]
    result = json.loads(rehearsed[len("rehearsed: "):])
    assert result["correct"] and result["failed"] == 0
    metrics = result["metrics"]
    assert metrics["router_load_max_over_mean"]["value"] >= 1.0
    assert 0.0 < metrics["held_pair_share"]["value"] < 100.0
    assert 0.0 <= metrics["expert_padded_row_share"]["value"] < 100.0
    assert "moe_padded_row_share" not in metrics
    assert metrics["programs_per_step"]["value"] >= 1
    assert metrics["step_roofline"]["value"] > 0
