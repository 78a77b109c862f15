"""The Qwen3-Next family: the whole of `run.py`'s flow on the CPU at a tiny
size (a rehearsal of a cell that differs from the committed one in its sizes
alone), what the check must refuse, the shape arithmetic, and the
benchmark's copy of the plain reference against the program's."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from perfbench import manifest as mf
from perfbench.models import qwen3_next as family
from perfbench.traffic import gen

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BATCH, STEPS = 2, 3
TINY = {
    "name": "qwen3_next_tiny", "family": "qwen3_next",
    "source": "https://example.org/a-tiny-qwen3-next",
    "hidden_size": 64, "num_hidden_layers": 4, "full_attention_interval": 4,
    "rms_norm_eps": 1e-6, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "num_experts": 8, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
    "vocab_size": 128, "expert_offset": 4, "seq_len": 128,
    "published": {"num_hidden_layers": 48, "num_experts": 16,
                  "vocab_size": 1024},
    "loss": "sparse_categorical_crossentropy",
    "optimizer": {"type": "adam", "alpha": 1e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8},
    "compute_dtype": "bfloat16", "deployment": {"chips": 2},
    "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
    "assumed": {}, "departures": []}
ZIPF = {"ids": {"distribution": "zipf", "alpha": 1.05}}


def _checked(monkeypatch=None, fault=None):
    """The harness's own sequence: a warm-up (so that Adam's state is not
    zero), the snapshot, STEPS steps through fit on one batch, the read."""
    if fault is not None:
        fault(monkeypatch)
    rows = family.held_table_rows(TINY, 1)
    model, timings = family.build(TINY, rows, BATCH, 1, seed=3)
    assert timings["build_s"] > 0 and timings["init_s"] > 0
    data = gen.generate(ZIPF, family.input_fields(TINY, rows), 4 * BATCH,
                        seed=3)
    x, y = family.fit_arrays(data)
    assert x["tokens"].shape == y.shape == (4 * BATCH, TINY["seq_len"])
    assert np.array_equal(x["tokens"][:, 1:], y[:, :-1])    # the next token
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


def test_system_agrees_with_the_plain_reference(checked):
    snap, after, losses = checked
    out = family.verify(snap, after, losses, TINY)
    assert out["ok"], out
    assert out["steps"] == STEPS and snap["step"] == 8
    assert out["loss_rel_err"] < family.LOSS_RTOL / 10
    assert out["update_cos_min"] > 0.99
    assert 0 < out["token_rows_named"] < TINY["vocab_size"]
    assert out["pairs_reference"] > 0 and out["probe_mismatch"] == 0.0
    # the counters the layer metrics read
    counters = family.expert_counters()
    assert sorted(counters) == [f"l{i}_moe" for i in range(4)]
    for c in counters.values():
        assert c["tokens"] == (2 * 4 + STEPS) * BATCH * TINY["seq_len"]
        assert c["rows"] >= c["pairs"].sum() > 0


# ---- wrong builds of the system, each refused by some limit ---------------
def _bf16_router(mp):
    import jax
    import jax.numpy as jnp
    from dlrm_flexflow_tpu.ops.moe import MoE

    def route(self, params, xt):
        logits = jnp.dot(xt.astype(jnp.bfloat16),
                         params["router"].astype(jnp.bfloat16))
        top_p, top_e = jax.lax.top_k(
            jax.nn.softmax(logits.astype(jnp.float32), axis=-1), self.top_k)
        return top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_e
    mp.setattr(MoE, "route", route)


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


def _absent_expert_added(mp):
    """The pairs of one expert held elsewhere are computed by a held one."""
    import jax.numpy as jnp
    from dlrm_flexflow_tpu.ops.moe import MoE
    route = MoE.route

    def widened(self, params, xt):
        w, e = route(self, params, xt)
        return w, jnp.where(e == self.expert_offset - 1,
                            self.expert_offset, e)
    mp.setattr(MoE, "route", widened)


def _attention_gate_left_out(mp):
    import jax
    import jax.numpy as jnp
    from dlrm_flexflow_tpu.ops import attention

    class Nn:
        def __getattr__(self, name):
            return (jnp.ones_like if name == "sigmoid"
                    else getattr(jax.nn, name))

    class Jax:
        nn = Nn()

        def __getattr__(self, name):
            return getattr(jax, name)
    mp.setattr(attention, "jax", Jax())


@pytest.mark.parametrize("fault", [
    _bf16_router, _adam_without_v, _absent_expert_added,
    _attention_gate_left_out], ids=lambda f: f.__name__.lstrip("_"))
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
    ref = reference
    low = family.run_reference(snap, TINY, STEPS, dtype=jnp.bfloat16)
    counters = {name: {"pairs": c["pairs"] + low["pairs"][i]}
                for i, (name, c) in enumerate(sorted(
                    snap["counters"].items()))}
    out = family.compare(snap, {"params": low["params"],
                                "counters": counters},
                         low["losses"], ref, system_probe=low["probe"])
    assert not out["ok"], out
    assert out["loss_rel_err"] > family.LOSS_RTOL
    assert out["probe_weight_err"] > family.PROBE_WEIGHT_ATOL
    assert out["update_cos_min"] >= family.UPDATE_COS_MIN


@pytest.fixture(scope="module")
def reference(checked):
    return family.run_reference(checked[0], TINY, STEPS)


@pytest.mark.parametrize("fault", ["loss", "nan", "rows_dropped",
                                   "lazy_rows_moved"])
def test_the_check_refuses(checked, reference, fault):
    snap, after, losses = checked
    if fault == "loss":
        losses = [1.01 * v for v in losses]
    elif fault == "nan":
        losses = [losses[0], float("nan"), losses[2]]
    else:
        kernel = (snap if fault == "rows_dropped" else after)[
            "params"]["embed"]["kernel"]
        moved = kernel + (1e-6 if fault == "lazy_rows_moved" else 0.0)
        after = {"params": dict(after["params"], embed={"kernel": moved}),
                 "counters": after["counters"]}
    out = family.compare(snap, after, losses, reference)
    assert not out["ok"], out


def test_state_kept_in_bfloat16_is_seen():
    import jax.numpy as jnp
    w = np.random.default_rng(0).standard_normal(4096).astype(np.float32)
    assert family._uses_fp32(w)
    assert not family._uses_fp32(np.asarray(
        jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32)))


def test_the_two_copies_of_the_reference_agree(checked):
    """The program's plain reference and the benchmark's copy: the same
    loss, pairs and updated weights from the same snapshot."""
    import jax
    import jax.numpy as jnp
    from dlrm_flexflow_tpu.models import qwen3_next_reference as program
    snap, _, _ = checked
    cfg = family.model_config(TINY, snap["vocab"])
    opt = {k: v for k, v in TINY["optimizer"].items() if k != "type"}
    x, y = family.fit_arrays(snap["batch"])
    mine = family.run_reference(snap, TINY, 1)
    put = lambda t: jax.tree.map(jnp.asarray, t)     # noqa: E731
    loss, pairs, params, _, _ = jax.jit(
        lambda p, m, v: program.adam_step(
            p, m, v, float(snap["step"] + 1), jnp.asarray(x["tokens"]),
            jnp.asarray(y), cfg, opt))(
        put(snap["params"]), put(snap["m"]), put(snap["v"]))
    assert float(loss) == pytest.approx(mine["losses"][0], rel=1e-6)
    assert np.array_equal(np.asarray(pairs), mine["pairs"])
    for name, sub in mine["params"].items():
        for pn, w in sub.items():
            np.testing.assert_allclose(np.asarray(params[name][pn]), w,
                                       rtol=0, atol=2e-7)


def test_operations_from_the_shapes():
    config = mf.load_config(mf.load(), "qwen3_next_80b_a3b")
    n = family.parameter_counts(config)
    # ISSUE 26's table: 33.7 M a delta layer, 27.3 M the full layer, 100.7 M
    # of experts a layer, 38.9 M a vocabulary matrix, 625.7 M in all
    assert n["delta"] == 3 * 33_718_464 and n["attention"] == 27_263_488
    assert n["experts"] == 4 * 32 * 3 * 2048 * 512
    assert n["embed"] == n["head"] == 18_992 * 2048
    assert sum(n.values()) == 625_667_136
    # useful FLOPs a sequence: the held share of top-10, half the scores
    pairs = 10 * 32 / 512
    macs = 8192 * (n["delta"] + n["attention"] + n["router_shared"]
                   + n["head"] + 4 * pairs * 3 * 2048 * 512)
    macs += 8192 * 8192 * 16 * 256 + 3 * 3 * 8192 * 32 * 128 * 128
    assert family.flops_per_sample(config) == 6.0 * macs
    assert 11e12 < family.flops_per_sample(config) < 12e12
    assert family.bytes_per_step(config, 1) == pytest.approx(
        36 * 625_667_136 + 8192 * 2048 * 4 * 2 * 10)
    assert family.held_table_rows(config, 1) == [18_992]
    assert 18_992 * 8 == config["published"]["vocab_size"]
    (field,) = family.input_fields(config, [18_992])
    assert (field["bag"], field["rows"]) == (8193, [18_992])


def test_the_committed_configuration_is_the_catalogs_row():
    """Every number of the published config under the same key, but the
    three that `reduced` lists."""
    config = mf.load_config(mf.load(), "qwen3_next_80b_a3b")
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "norm_topk_prob": True,
        "num_attention_heads": 16, "num_experts": 512,
        "num_experts_per_tok": 10, "num_hidden_layers": 48,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] < value
        else:
            assert config[key] == value, key


def test_a_tiny_cell_of_the_family_runs_through_run_py(tmp_path):
    """`perfbench/run.py --rehearse` on a cell that differs from the
    committed one in its sizes alone: the flow, the check and the layer
    metrics the new cell reports, the two new ones among them."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(root, "tests", "perfbench"))
    with open(os.path.join(root, "perfbench", "configs",
                           "qwen3_next_tiny.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    cell = "qwen3_next_80b_a3b.tiny"       # the readers' CELLS pattern
    man["configs"].append({
        "name": "qwen3_next_tiny", "source": TINY["source"],
        "file": "perfbench/configs/qwen3_next_tiny.json",
        "reduced": TINY["reduced"], "why": "a test's configuration"})
    man["workloads"].append({
        "name": cell, "config": "qwen3_next_tiny", "traffic": "b1_zipf",
        "chips": 1, "why": "a test's cell"})
    for m in man["per_layer"]:
        if m["name"].startswith("moe_"):
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
         "--workload", cell, "--seed", "2147483999", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=root, env=env, text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert lines[-1] == "rehearsal passed"
    (rehearsed,) = [ln for ln in lines if ln.startswith("rehearsed: ")]
    result = json.loads(rehearsed[len("rehearsed: "):])
    assert result["correct"] and result["failed"] == 0
    metrics = result["metrics"]
    assert metrics["moe_expert_load_max_over_mean"]["value"] >= 1.0
    assert 0.0 <= metrics["moe_padded_row_share"]["value"] < 100.0
    assert metrics["programs_per_step"]["value"] >= 1
    assert metrics["step_roofline"]["value"] > 0
