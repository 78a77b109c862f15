"""The program names its work (ISSUE 24): an `ff.<op name>` scope on every
op of the compiled step, a `name=` on every Pallas kernel, the scope map
`obs.trace.program_scopes()` reads back from the executable, and the host
spans of `fit()`'s loop on the profiler's clock. All on the CPU: scopes are
compile-time metadata, the same on every backend."""

import ast
import contextlib
import glob
import os
import re

import numpy as np
import pytest

import jax

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                           synthetic_batch)
from dlrm_flexflow_tpu.obs import trace
from dlrm_flexflow_tpu.parallel.mesh import make_mesh

PKG = os.path.dirname(os.path.abspath(ff.__file__))
BS, NB = 16, 4
MODELS = {
    # the stacked (uniform tables, `cat`) and the concatenated (uneven
    # tables, `dot`) embedding ops: the two routes the benchmark's cells take
    "cat": DLRMConfig(embedding_size=[64] * 4, sparse_feature_size=8,
                      mlp_bot=[4, 16, 8], mlp_top=[40, 16, 1]),
    "dot": DLRMConfig(embedding_size=[64, 32, 16], sparse_feature_size=8,
                      mlp_bot=[4, 16, 8], mlp_top=[14, 16, 1],
                      arch_interaction_op="dot"),
}
# what may go without a scope of the program's: instructions that do no
# work of an op (XLA's own plumbing carries no metadata, a parameter or its
# bitcast carries the argument's name) and the step counter's `step + 1`
PLUMBING = {"parameter", "constant", "broadcast", "bitcast", "copy",
            "tuple", "get-tuple-element", "iota"}
STEP_COUNTER = "jit(train_step)/add"


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A test that ran earlier in this process may have switched JAX's
    persistent cache on (`use_compile_cache`, through an example's main):
    a step that took over a second to compile on a loaded machine is then
    LOADED the next time, with the scopes it was stored under, and these
    tests read the scopes of the step they have just built."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _fit(kind, epochs=1, **cfg_kw):
    dcfg = MODELS[kind]
    model = ff.FFModel(ff.FFConfig(batch_size=BS, seed=2, **cfg_kw))
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"],
                  mesh=make_mesh(devices=jax.devices()[:1]))
    model.init_layers()
    x, y = synthetic_batch(dcfg, BS * NB, seed=1)
    model.fit(x, y, epochs=epochs, verbose=False)
    return model


def _step_text():
    return trace.programs("train")[-1].executable.as_text()


def _opcodes(hlo_text):
    """{instruction name: opcode} of an optimized HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = .*?[\]})] ([\w\-]+)\(",
                     line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def _census():
    """{instruction: path} of the newest step program, after the check
    that every instruction doing an op's work lies under an `ff.` scope."""
    scopes = trace.program_scopes()["jit_train_step"]
    opcodes = _opcodes(_step_text())
    assert set(scopes) == set(opcodes) and len(scopes) > 100
    unscoped = {
        name: path for name, path in scopes.items()
        if "ff." not in path and opcodes[name] not in PLUMBING
        # a reduction's or a scatter's combiner: its own tiny computation,
        # named after the primitive (inside a recomputed block:
        # `checkpoint/<primitive>`; inside a scope of a recomputed span:
        # `checkpoint/prep/<primitive>`), never an event of the trace
        and "/" in re.sub(r"^(checkpoint/((prep|hand_over)/)?)?", "", path)}
    assert set(unscoped.values()) <= {STEP_COUNTER}, unscoped
    return scopes


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_every_instruction_of_the_step_has_a_path(kind):
    model = _fit(kind)
    scopes = _census()
    # every op of the graph, its update and the step's bookkeeping are there
    found = {m for p in scopes.values()
             for m in re.findall(r"ff\.[\w.]+", p)}
    # (a reshape compiles to nothing, so it names nothing)
    wanted = {f"ff.{op.name}" for op in model.ops
              if type(op).__name__ not in ("InputOp", "Reshape", "Flat")}
    wanted |= {f"ff.update.{n}" for n in model._sparse_update_ops}
    wanted |= {"ff.optimizer", "ff.loss", "ff.metrics"}
    assert wanted <= found, wanted - found
    # autodiff names the backward by itself, and the sub-scopes nest
    paths = set(scopes.values())
    assert any("transpose(jvp(ff.top_dense_0))" in p for p in paths)
    emb = model._sparse_update_ops[0]
    sub = {"cat": "gather", "dot": "index"}[kind]
    assert any(re.search(rf"ff\.{emb}/(vmap\()?{sub}\)?/", p)
               for p in paths), sorted(p for p in paths if emb in p)


def _qwen3_next(model):
    from dlrm_flexflow_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                     build_qwen3_next)
    cfg = Qwen3NextConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=4,
        linear_num_key_heads=1, linear_num_value_heads=2,
        linear_key_head_dim=8, linear_value_head_dim=8,
        num_attention_heads=2, num_key_value_heads=1, head_dim=16,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, experts_held=4)
    build_qwen3_next(model, cfg, 32)
    tokens = (jax.numpy.arange(4 * 33).reshape(4, 33) % 64).astype("int32")
    inside = {"l0_moe": ["router", "dispatch", "experts", "combine",
                         "shared"],
              "l0_delta": ["proj", "conv", "scan", "gate_norm"],
              "l3_attn": ["qkv_proj", "qk_norm_rope", "attend", "gate",
                          "out_proj"]}
    return {}, {"tokens": tokens[:, :-1]}, tokens[:, 1:], inside


def _glm4_moe_lite(model):
    """ISSUE 30: latent attention's five scopes in every block, the dense
    block, the balance update, the module's ops under `ff.mtp_*`."""
    from dlrm_flexflow_tpu.models.glm4_moe_lite import (
        Glm4MoeLiteConfig, build_glm4_moe_lite, loss_weights, mtp_labels)
    cfg = Glm4MoeLiteConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, q_lora_rank=12, kv_lora_rank=8,
        qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=8,
        intermediate_size=48, moe_intermediate_size=16, n_routed_experts=8,
        num_experts_per_tok=2, experts_held=4)
    build_glm4_moe_lite(model, cfg, 32)
    ids, labels = mtp_labels(
        (np.arange(4 * 33).reshape(4, 33) % 64).astype("int32"))
    mla = ["q_proj", "kv_proj", "rope", "attend", "out_proj"]
    inside = {"l0_mla": mla, "l1_mla": mla, "mtp_mla": mla, "l0_mlp": [],
              "l1_moe": ["router", "dispatch", "experts", "combine",
                         "shared", "balance"],
              "mtp_moe": ["router", "experts", "balance"]}
    return ({"loss_weights": loss_weights(32, cfg.mtp_loss_weight)},
            {"tokens": ids}, labels, inside)


def _nemotron_h(model):
    """ISSUE 32: a block of one part; the state-space mixer's five scopes,
    plain attention's three, the two-matrix experts' walk and balance."""
    from dlrm_flexflow_tpu.models.nemotron_h import (NemotronHConfig,
                                                     build_nemotron_h)
    cfg = NemotronHConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=3,
        hybrid_override_pattern="ME*", mamba_num_heads=4, mamba_head_dim=8,
        n_groups=2, ssm_state_size=8, chunk_size=16, num_attention_heads=2,
        num_key_value_heads=1, head_dim=16, moe_intermediate_size=16,
        moe_shared_expert_intermediate_size=16, n_routed_experts=8,
        num_experts_per_tok=2, experts_held=4)
    build_nemotron_h(model, cfg, 32)
    tokens = (jax.numpy.arange(4 * 33).reshape(4, 33) % 64).astype("int32")
    inside = {"l0_mamba": ["in_proj", "conv", "ssd", "gate_norm",
                           "out_proj"],
              "l1_moe": ["router", "dispatch", "experts", "combine",
                         "shared", "balance"],
              "l2_attn": ["qkv_proj", "attend", "out_proj"]}
    return {}, {"tokens": tokens[:, :-1]}, tokens[:, 1:], inside


@pytest.mark.parametrize("build", [_qwen3_next, _glm4_moe_lite, _nemotron_h],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_every_instruction_of_a_language_model_step_has_a_path(build):
    """The census on the language models (ISSUEs 26, 30): block ops
    recomputed in the backward, the expert walk's loops, Adam; and the
    scopes inside the block ops."""
    model = ff.FFModel(ff.FFConfig(batch_size=2, seed=2))
    compile_kw, x, y, inside = build(model)
    model.compile(ff.AdamOptimizer(alpha=1e-3),
                  "sparse_categorical_crossentropy",
                  ["sparse_categorical_crossentropy"],
                  mesh=make_mesh(devices=jax.devices()[:1]), **compile_kw)
    model.init_layers()
    model.fit(x, y, epochs=1, verbose=False)
    paths = set(_census().values())
    found = {m for p in paths for m in re.findall(r"ff\.[\w.]+", p)}
    wanted = {f"ff.{op.name}" for op in model.ops
              if type(op).__name__ not in ("InputOp", "Reshape")}
    wanted |= {"ff.update.embed", "ff.optimizer", "ff.loss", "ff.metrics"}
    assert wanted <= found, wanted - found
    for op, subs in inside.items():
        for sub in subs:
            assert any(re.search(rf"ff\.{op}\b.*/{sub}\b", p)
                       for p in paths), (op, sub)
        # forward, and the backward through the recomputed block
        assert any(f"transpose(jvp(ff.{op}))" in p for p in paths), op
        assert any("checkpoint" in p or "remat" in p
                   for p in paths if f"ff.{op}" in p), op
    if compile_kw:
        # both loss terms are one weighted sum under `ff.loss`, forward and
        # backward, and the module's ops have names of their own
        assert any("ff.loss" in p and "transpose" in p for p in paths)
        assert {"ff.mtp_enorm", "ff.mtp_hnorm", "ff.mtp_eh_proj",
                "ff.mtp_final_norm", "ff.mtp_rows"} <= found


def test_scopes_are_metadata_only(monkeypatch):
    """No op is added and no fusion changes: the optimized step has as
    many instructions and fusions with the scopes as without."""
    def census():
        ops = _opcodes(_step_text())
        return len(ops), sum(1 for o in ops.values() if o == "fusion")

    _fit("dot")
    with_scopes = census()
    assert any("ff." in p for p in
               trace.program_scopes()["jit_train_step"].values())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _fit("dot")
    assert not any("ff." in p for p in
                   trace.program_scopes()["jit_train_step"].values())
    assert census() == with_scopes


def test_every_pallas_call_is_named_and_scoped():
    """Each `pallas_call` of ops/pallas/ passes a literal `name=` and is
    the body of a `jax.named_scope` of the same name, so the kernel is
    found by either."""
    names = []
    for path in sorted(glob.glob(os.path.join(PKG, "ops", "pallas",
                                              "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        scoped = {}     # id(pallas_call node) -> enclosing scope's name
        for node in ast.walk(tree):
            if not isinstance(node, ast.With):
                continue
            ctx = node.items[0].context_expr
            if (isinstance(ctx, ast.Call)
                    and getattr(ctx.func, "attr", "") == "named_scope"):
                for sub in ast.walk(node):
                    scoped[id(sub)] = ctx.args[0].value
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "attr", "") == "pallas_call"):
                kw = {k.arg: k.value for k in node.keywords}
                where = f"{os.path.basename(path)}:{node.lineno}"
                assert isinstance(kw.get("name"), ast.Constant), where
                assert scoped.get(id(node)) == kw["name"].value, where
                names.append(kw["name"].value)
    assert sorted(names) == ["delta_hand_over_bwd", "delta_hand_over_fwd",
                             "emb_gather", "emb_run_sum", "emb_scatter_add",
                             "emb_scatter_write", "interaction_fused",
                             "lstm_bwd", "lstm_fwd", "moe_experts_bwd",
                             "moe_experts_fwd", "topk"]


def test_a_loaded_executable_gives_the_same_scope_map(tmp_path):
    """The benchmark's runs are warm: the step executable comes from a
    cache. A deserialized executable must answer like the built one."""
    from dlrm_flexflow_tpu.utils.warmcache import CompileCache

    def scopes_of(model_cache):
        dcfg = MODELS["cat"]
        model = ff.FFModel(ff.FFConfig(batch_size=BS, seed=2))
        build_dlrm(model, dcfg)
        model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error",
                      ["mse"], mesh=make_mesh(devices=jax.devices()[:1]))
        model.attach_compile_cache(model_cache)
        model.init_layers()
        x, y = synthetic_batch(dcfg, BS * NB, seed=1)
        model.fit(x, y, epochs=1, verbose=False)
        return trace.program_scopes()["jit_train_step"]

    cold, warm = CompileCache(str(tmp_path)), CompileCache(str(tmp_path))
    built = scopes_of(cold)
    loaded = scopes_of(warm)
    assert (cold.stats()["puts"], warm.stats()["hits"]) == (1, 1)
    assert loaded == built
    assert any("ff.update.emb" in p for p in loaded.values())


def test_hlo_scopes_reads_one_instruction_a_line():
    text = "\n".join([
        "HloModule jit_train_step, is_scheduled=true",
        "%fused_computation (p: f32[4]) -> f32[4] {",
        '  %p = f32[4]{0} parameter(0)',
        '  ROOT %neg.1 = f32[4]{0} negate(%p), metadata={op_name='
        '"jit(train_step)/jit(main)/jvp(ff.top_dense_1)/neg" '
        'source_file="a.py" source_line=3}',
        "}",
        "ENTRY %main.2 (x: f32[4]) -> f32[4] {",
        "  fusion.7 = f32[4]{0} fusion(x), kind=kLoop, calls="
        'fused_computation, metadata={op_name="jit(train_step)/'
        'jit(main)/ff.update.emb/dedup/sort"}',
        "  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%x)",
        "}"])
    assert trace.hlo_scopes(text) == {
        "p": "",
        "neg.1": "jit(train_step)/jit(main)/jvp(ff.top_dense_1)/neg",
        "fusion.7": "jit(train_step)/jit(main)/ff.update.emb/dedup/sort",
        "copy-start.1": ""}


def test_fit_emits_its_spans_on_the_profilers_clock(tmp_path):
    """Under a profiler session, with `--obs off`, every span of fit()'s
    loop is in the profiler's trace, on the thread that ran fit:
    `train/step` inside `train/dispatch`, one of each a step. Nothing
    lands in the ring."""
    from jax.profiler import ProfileData
    epochs, steps = 2, 2 * NB
    trace.clear()
    assert not trace.enabled()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _fit("cat", epochs=epochs)
    finally:
        jax.profiler.stop_trace()
    assert trace.events() == []
    (pb,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = {}          # name -> [(thread, start, end)]
    for plane in ProfileData.from_file(pb).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.split("/")[0] in ("train", "fit", "compile"):
                    spans.setdefault(e.name, []).append(
                        (line.name, e.start_ns, e.start_ns + e.duration_ns))
    counts = {name: len(evs) for name, evs in spans.items()}
    # the CPU's throttle is 1: every step but the first waits on the one
    # before it
    assert counts == {"fit/stage": 1, "compile/train": 1,
                      "train/dispatch": steps, "train/step": steps,
                      "fit/throttle": steps - 1, "fit/epoch_end": epochs,
                      "fit/drain": 1}
    assert len({t for evs in spans.values() for t, _, _ in evs}) == 1
    for (_, a, b), (_, c, d) in zip(sorted(spans["train/dispatch"]),
                                    sorted(spans["train/step"])):
        assert a <= c and d <= b
