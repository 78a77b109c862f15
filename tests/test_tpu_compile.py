"""Compile the main path's Pallas kernels for a described (not attached)
v5e at real widths: Mosaic refuses what interpret mode lets through (a
DMA target that is not aligned to the tiling, a loop it cannot unroll,
too much VMEM). Nothing runs, so these say nothing of results or speed.

All such tests live in this one file, and the topology is described in a
fixture: only the worker that is given the file loads the TPU's library.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from dlrm_flexflow_tpu.ops.pallas import embedding_kernel


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


# (table rows, dim, batch, bag): one chip's share of the terabyte model at
# both benchmark batches, then the shapes that take the planes (k > 1,
# bag > 1) and a batch under one sublane tile
@pytest.mark.parametrize("rows,dim,batch,bag", [
    (11739136, 128, 89856, 1), (11739136, 128, 3328, 1),
    (100000, 256, 1000, 3), (100000, 384, 5, 1), (100000, 128, 300, 4)])
def test_emb_gather_compiles_for_v5e(one_chip, no_compile_cache,
                                     rows, dim, batch, bag):
    table = jax.ShapeDtypeStruct((rows, dim), jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((batch, bag), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda t, i: embedding_kernel.embedding_bag(t, i, "sum", False)
    ).lower(table, idx).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (view rows of the table, dim, lookups): the terabyte cells' table at both
# benchmark batches, k = 2 chunks a row, and fewer lookups than one block
@pytest.mark.parametrize("rows,dim,n", [
    (11739136, 128, 89856), (11739136, 128, 3328), (100000, 256, 1000),
    (11739136, 128, 40)])
def test_emb_scatter_add_compiles_for_v5e(one_chip, no_compile_cache,
                                          rows, dim, n):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(embedding_kernel.scatter_add_rows).lower(
        sds((rows, dim)), sds((n,), jnp.int32), sds((n, dim))).compile()
    assert "emb_scatter_add" in compiled.as_text()
    assert "tpu_custom_call" in compiled.as_text()


# (packed view rows, d, lookups): dlrm_random's eight d=64 tables as one
# packed view at the benchmark batch, under one block, and kaggle's d=16
@pytest.mark.parametrize("vrows,d,n", [
    (4000000, 64, 65536), (4000000, 64, 40), (500000, 16, 1000)])
def test_emb_scatter_write_compiles_for_v5e(one_chip, no_compile_cache,
                                            vrows, d, n):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda v, i, u, t: embedding_kernel.scatter_write_rows_packed(
            v, i, u, t, d)
    ).lower(sds((vrows, 128)), sds((n,), jnp.int32), sds((n, d)),
            sds((n, 128))).compile()
    assert "emb_scatter_write" in compiled.as_text()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def sds(one_chip):
    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


# an HLO scatter instruction (`%scatter.1 = f32[...] scatter(...)`), not the
# kernels' names or the `scatter` scope in an instruction's metadata
_XLA_SCATTER = re.compile(r"\sscatter\(")


# (slots, W): the terabyte cells' two batches (dlrm_random's 65,536 packed
# tiles compile in the guard below: a sort of that length takes the TPU's
# compiler half a minute here), then the stateful update's gradient and
# touch counts side by side, in blocks and in one block of a lane tile
@pytest.mark.parametrize("m,width", [
    (89856, 128), (3328, 128), (1000, 256), (40, 256)])
def test_emb_run_sum_compiles_for_v5e(sds, no_compile_cache, m, width):
    text = jax.jit(embedding_kernel._dedup_tile_updates).lower(
        sds((m,), jnp.int32), sds((m, width))).compile().as_text()
    assert "emb_run_sum" in text and "tpu_custom_call" in text
    assert not _XLA_SCATTER.search(text)


@pytest.mark.parametrize("form", ["add", "write_packed"])
def test_sparse_update_holds_no_xla_scatter(sds, no_compile_cache, form):
    """The guard that the dedup's segment ops do not come back: XLA's
    scatter costs 8-13 ns an element here (PERF.md, PR 29), and the whole
    sparse update is sorts, element-wise passes and three kernels."""
    if form == "add":
        n = 89856
        lowered = jax.jit(embedding_kernel.scatter_add_rows).lower(
            sds((11739136, 128)), sds((n,), jnp.int32), sds((n, 128)))
    else:
        n = 65536
        lowered = jax.jit(
            lambda v, i, u, t: embedding_kernel.scatter_write_rows_packed(
                v, i, u, t, 64)
        ).lower(sds((4000000, 128)), sds((n,), jnp.int32), sds((n, 64)),
                sds((n, 128)))
    text = lowered.compile().as_text()
    assert "emb_run_sum" in text
    assert not _XLA_SCATTER.search(text)
    assert _XLA_SCATTER.search("  %scatter.1 = f32[8,128]{1,0} scatter(%a)")
    # the rows `emb_run_sum` fetches one DMA each stay in HBM: XLA's own
    # choice for a temporary of this size is VMEM (`S(1)` in a layout),
    # from where a row DMA costs 1.7x (PERF.md, PR 29)
    operands = re.search(r"%emb_run_sum\.\d+ = \S+ custom-call\(([^)]*)\)",
                         text).group(1)
    updates = operands.split(", ")[-1]
    made = re.search(rf"^\s*{re.escape(updates)} = (\S+) ", text, re.M)
    assert "S(1)" not in made.group(1), made.group(0)


@pytest.mark.parametrize("route", ["walk", "kernel"])
def test_expert_walk_compiles_for_v5e(one_chip, no_compile_cache, route):
    """The expert op's walk over its sorted pairs at Qwen3-Next's widths,
    forward and backward. As the XLA loop: loops with no static trip
    count, and a chunk's rows, not the worst case's 81,920, set the size of
    the products. As the Pallas kernel's grid (ISSUE 37): no `while` at
    all in the routed path, the two kernels under their names. (The
    two-matrix form walks inside the Nemotron step, below.)"""
    from dlrm_flexflow_tpu.ops import moe
    from dlrm_flexflow_tpu.ops.pallas import moe_kernel
    T, D, F, held, k = 8192, 2048, 512, 32, 10
    assert moe_kernel.shapes_fit(D, F, 3)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def routed(xt, wg, wu, wd, pair_w, order, counts, held_pair):
        if route == "kernel":
            return moe._routed_grid(moe_kernel.ROWS, k, jnp.bfloat16, False,
                                    xt, (wg, wu, wd), pair_w, order, counts,
                                    held_pair)
        return moe._routed(moe.CHUNK_ROWS, k, jnp.bfloat16, "swiglu", xt,
                           (wg, wu, wd), pair_w, order, counts, held_pair)

    # the value too: a gradient alone leaves the forward kernel out
    compiled = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(routed(*a)), argnums=(0, 1, 2, 3, 4))).lower(
        sds((T, D)), sds((held, D, F)), sds((held, D, F)),
        sds((held, F, D)), sds((T * k,)),
        sds((T * k + moe.CHUNK_ROWS,), jnp.int32), sds((held,), jnp.int32),
        sds((T * k,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30
    names = re.findall(r"%(moe_experts_\w+?)[.\d]* = ", text)
    if route == "walk":
        assert "while" in text and not names
        assert "tpu_custom_call" not in text
        return
    assert not re.search(r" while\(", text)
    assert sorted(names) == ["moe_experts_bwd", "moe_experts_fwd"]
    # under the op's `experts` scope, as autodiff wraps it
    assert re.search(r"[(/]experts\)*/moe_experts_fwd/", text)
    assert re.search(r"[(/]experts\)*/moe_experts_bwd/", text)
    # `perfbench/tracereduce.py` files an op under `mosaic` by the first
    # 600 characters after the instruction's name, and the profiler's text
    # spells every operand's type out before its name (the first form of
    # these kernels, twelve and seventeen operands, was filed under `xla`:
    # PERF.md, PR 37): the results' types, then the operands with theirs
    from perfbench import tracereduce
    made = dict(re.findall(r"^\s*%?(\S+) = (\S+) ", text, re.M))
    for line in text.splitlines():
        if re.match(r"\s*%moe_experts_\w+ = ", line):
            detail = tracereduce.split_hlo(line.strip())[1]
            operands = re.search(r"custom-call\(([^)]*)\)", detail).group(1)
            typed = sum(len(made[name.lstrip("%")]) + 1
                        for name in operands.split(", "))
            assert detail.find("tpu_custom_call") + typed < \
                tracereduce.DETAIL_CHARS, line


@pytest.mark.parametrize("resident", [False, True])
def test_chunked_delta_rule_compiles_for_v5e(one_chip, no_compile_cache,
                                             resident):
    """The gated delta rule's chunked scan at Qwen3-Next's head sizes, two
    spans of the sequence, forward and backward; with the hand-over as the
    `lax.scan` and as the Pallas kernel (ISSUE 36). The kernel's route
    holds no `while` but the scan over spans and its reverse: the sixteen
    chunks of a span are one call's grid."""
    from dlrm_flexflow_tpu.ops import delta_net
    b, s, h, d = 1, 2 * delta_net.SPAN, 32, 128

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, g, beta):
        return jnp.sum(delta_net.gated_delta_rule_chunked(
            q, k, v, g, beta, compute_dtype=jnp.bfloat16,
            resident=resident))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        sds((b, s, h, d)), sds((b, s, h, d)), sds((b, s, h, d)),
        sds((b, s, h), jnp.float32), sds((b, s, h), jnp.float32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
    text = compiled.as_text()
    whiles = len(re.findall(r" while\(", text))
    # the forward, the span's forward again with the entering states, and
    # the reverse, each under its own name (what `tracereduce` prints)
    names = re.findall(r"%(delta_hand_over_\w+?)[.\d]* = ", text)
    if resident:
        assert whiles == 2, whiles
        assert sorted(names) == ["delta_hand_over_bwd"] + 2 * [
            "delta_hand_over_fwd"]
        assert "/hand_over/delta_hand_over_fwd/" in text
        assert "/hand_over/delta_hand_over_bwd/" in text
        assert "/prep/" in text
        # `perfbench/tracereduce.py` files an op under `mosaic` by the
        # first 600 characters after the instruction's name, and the
        # backward's eight operands and six results come first
        from perfbench import tracereduce
        for line in text.splitlines():
            if re.match(r"\s*%delta_hand_over_\w+ = ", line):
                detail = tracereduce.split_hlo(line.strip())[1]
                assert "tpu_custom_call" in detail[
                    :tracereduce.DETAIL_CHARS], line
    else:
        assert whiles > 2 and not names
        assert "tpu_custom_call" not in text


# configuration -> (the most the compiler may count for the whole step, in
# bytes: arguments + outputs - aliased + temporaries; flash kernel calls).
# GLM-4.7-Flash (ISSUE 30) counted 13,859,009,024 when it was added: six
# blocks of latent attention through the flash route, forward, recomputed,
# dq and dkv. Qwen3-Next's bound is what its step counted BEFORE the expert
# op learned a second router: an op that does not ask for the sigmoid
# router, the bias or the ungated shared expert compiles to what it did.
# Nemotron-3-Nano (ISSUE 32) counted 13,249,963,008: four Mamba-2 layers
# whose chunked recurrence is all XLA, one attention layer through the flash
# route at heads of 128. The third number is what the step compiled to
# BEFORE that issue touched `ops/moe.py`, `ops/attention.py`, `ops/norm.py`
# and `causal_depthwise_conv` (lines of the optimized text that define an
# instruction; the parent's text, instruction for instruction, but for
# numbering and the source locations inside the Mosaic kernels' bodies): an
# op that does not ask for the two-matrix expert, for attention without its
# gate, norm and rotary, for a grouped norm or a convolution's bias compiles
# to what it did, to the byte and to the instruction. ISSUE 33 changed what
# EVERY step hands back (the counter donated, the loss and the metrics one
# vector): twelve instructions more in both pinned steps (26,863 -> 26,875,
# 30,644 -> 30,656), GLM's bytes +30,720 (13,859,009,024 before), and the
# compiler's schedule of the Qwen3-Next step came out 242 MB of temporaries
# smaller (14,474,101,248 before); PERF.md, PR 33, has the chip's reading.
# ISSUE 36 took the delta rule's hand-over, and the decayed copies of q and
# k that only fed it, out of XLA's hands: the Qwen3-Next step is
# 126,281,216 bytes of temporaries and 3,284 instructions smaller
# (14,231,714,304 and 30,656 before) and holds twelve kernel calls more,
# four a delta layer; GLM's pin did not move. ISSUE 37 made the expert walk
# the grid of a Pallas kernel where a whole expert fits VMEM beside its
# gradient, which of the three only Qwen3-Next's does: its step is
# 2,676,308,992 bytes of temporaries and 1,605 instructions smaller
# (14,105,433,088 and 27,372 before: the walks' zero-filled buffers, their
# fp32 accumulators in the loops' carries and XLA's copies of them; 192 of
# the instructions went when a trip's block and window left the prefetched
# plan for the index maps, 24 a kernel call, the bytes the same), holds
# eight kernel calls more (a layer's forward and backward: the block's
# recomputation needs the router's choice again, not the routed rows) and
# of its 25 `while`s the nine span scans are left; held to the walk
# (`.../walk`, the gate left as the CPU finds it) it is the step it was, to
# the byte and the instruction, as are GLM's (F = 1,536: 183 MB of VMEM)
# and Nemotron's (F = 1,856 is no multiple of 128), whose pins did not move.
LM_STEPS = {"glm_4_7_flash": (13_859_039_744, 24, 26_875),
            "nemotron_3_nano_30b_a3b": (14_000_000_000, 4, None),
            "qwen3_next_80b_a3b": (11_429_124_096, 24, 25_767),
            "qwen3_next_80b_a3b/walk": (14_105_433_088, 16, 27_372)}


@pytest.mark.parametrize("name", sorted(LM_STEPS))
def test_language_model_step_compiles_for_v5e(one_chip, no_compile_cache,
                                              monkeypatch, name):
    """The whole train step at the benchmark's published widths, built as
    the cell's family builds it, lowered on shapes for the described chip
    (the gates that ask the attached backend are told it is the TPU, here
    in the test)."""
    import json
    import numpy as np
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.ops import attention, embedding
    from dlrm_flexflow_tpu.ops.pallas import delta_kernel, moe_kernel
    from dlrm_flexflow_tpu.parallel.mesh import make_mesh
    from perfbench import manifest as mf
    monkeypatch.setattr(
        embedding, "_pallas_common",
        lambda model, op_name, width_ok: bool(width_ok)
        and model.config.use_pallas)
    monkeypatch.setattr(attention, "_hbm_bytes", lambda: 15.75 * 2**30)
    monkeypatch.setattr(
        delta_kernel, "resident_hand_over_ok",
        lambda model, chunk, dk, dv: delta_kernel.shapes_fit(chunk, dk, dv))
    name, _, walk = name.partition("/")
    if not walk:
        monkeypatch.setattr(
            moe_kernel, "grid_walk_ok",
            lambda model, xt, ws, order: xt.dtype == jnp.float32
            and moe_kernel.shapes_fit(*ws[0].shape[1:], len(ws),
                                      entries=order.size,
                                      held=ws[0].shape[0]))
    config = mf.load_config(mf.load(), name)
    family = mf.load_family(config["family"])
    mcfg = family.model_config(config, family.held_table_rows(config, 1)[0])
    seq, opt = int(config["seq_len"]), config["optimizer"]
    model = ff.FFModel(ff.FFConfig.parse_args(
        ["-b", "1", "--compute-dtype", config["compute_dtype"]]))
    kw = {}
    if name == "glm_4_7_flash":
        from dlrm_flexflow_tpu.models.glm4_moe_lite import (
            Glm4MoeLiteConfig, build_glm4_moe_lite, loss_weights)
        build_glm4_moe_lite(model, Glm4MoeLiteConfig.from_dict(mcfg), seq)
        kw["loss_weights"] = loss_weights(seq, config["mtp_loss_weight"])
    elif name == "nemotron_3_nano_30b_a3b":
        from dlrm_flexflow_tpu.models.nemotron_h import (NemotronHConfig,
                                                         build_nemotron_h)
        build_nemotron_h(model, NemotronHConfig.from_dict(mcfg), seq)
    else:
        from dlrm_flexflow_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                         build_qwen3_next)
        build_qwen3_next(model, Qwen3NextConfig.from_dict(mcfg), seq)
    model.compile(
        ff.AdamOptimizer(alpha=opt["alpha"], beta1=opt["beta1"],
                         beta2=opt["beta2"], epsilon=opt["epsilon"]),
        config["loss"], [config["loss"]],
        mesh=make_mesh(devices=[one_chip._device]), **kw)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def of(defs):
        return {n: sds(d.shape, d.dtype) for n, d in defs.items()}

    params = {op.name: of(op.param_defs()) for op in model.ops
              if op.param_defs()}
    assert sum(int(np.prod(a.shape)) for sub in params.values()
               for a in sub.values()) == sum(
        family.parameter_counts(config).values())
    opt_state = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(model.optimizer.init_state, params))
    op_state = {op.name: of(op.state_defs()) for op in model.ops
                if hasattr(op, "state_defs")}
    batch = {t.name: sds(t.shape, t.dtype) for t in model.input_tensors}
    batch["label"] = sds(model.label_tensor.shape, model.label_tensor.dtype)
    compiled = model._train_step.lower(
        params, opt_state, op_state,
        {k: sds((), jnp.float32) for k in model._msums_keys}, batch,
        sds((), jnp.int32)).compile()
    from dlrm_flexflow_tpu.obs import trace as obstrace
    most, kernels, instructions = LM_STEPS[name + ("/walk" if walk else "")]
    counted = obstrace.program_memory(compiled)["counted"]
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    if instructions is None:
        assert counted <= most
    else:
        assert (counted, sum(" = " in line for line in text.splitlines())
                ) == (most, instructions)
    # every carry takes over its input's buffer, the counter too; the one
    # output the runtime allocates a dispatch is the metrics vector
    # (ISSUE 33), by the TPU compiler's own alias table at real widths
    assert obstrace.fresh_outputs(compiled) == 1
    # the balance update is in the step that asked for it, and in no other
    assert ("/balance/" in text) == (name != "qwen3_next_80b_a3b")
    # the blocks the flash kernels run with, as a scope under `attend`
    # (ISSUE 31): at heads of 256 over 8,192 tokens, Nemotron's at 128
    scope = attention._flash_blocks(
        1, seq, seq, mcfg.get("v_head_dim") or mcfg["head_dim"])[1]
    assert f"/attend/{scope}/jit(flash_attention)/pallas_call" in text
    # the expert walk is two kernels a layer where the gate sends it there,
    # and then the only `while`s left are the delta rule's span scans
    walks = len(re.findall(r"%moe_experts_(?:fwd|bwd)[.\d]* = ", text))
    assert walks == (8 if name == "qwen3_next_80b_a3b" and not walk else 0)
    if walks:
        whiles = [line for line in text.splitlines() if " while(" in line]
        assert len(whiles) == 9 and not any("_moe" in w for w in whiles)
    if name == "nemotron_3_nano_30b_a3b":
        # the recurrence is batched products and no loop: the only `while`s
        # of the step are the expert walks'
        assert not re.search(r"ff\.l\d_mamba[^\n]*while", text)
        for sub in ("in_proj", "conv", "ssd", "gate_norm", "out_proj"):
            assert re.search(rf"ff\.l0_mamba\)/{sub}/", text), sub


def test_fused_terabyte_step_compiles_for_v5e(one_chip, no_compile_cache,
                                              monkeypatch):
    """`dlrm_terabyte.b128_local`'s fused program, K = 16 (what fit()'s
    pace probe switches that cell to, ISSUE 35), built as the cell's family
    builds the model: the Pallas kernels run inside the scan's `while`,
    and the loop carries the 6 GB table in place."""
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                               dlrm_strategy)
    from dlrm_flexflow_tpu.obs import trace as obstrace
    from dlrm_flexflow_tpu.ops import embedding
    from dlrm_flexflow_tpu.parallel.mesh import make_mesh
    from perfbench import manifest as mf
    monkeypatch.setattr(
        embedding, "_pallas_common",
        lambda model, op_name, width_ok: bool(width_ok)
        and model.config.use_pallas)
    config = mf.load_config(mf.load(), "dlrm_terabyte")
    family = mf.load_family(config["family"])
    rows = family.held_table_rows(config, 1)
    batch, k = 128, 16
    dcfg = DLRMConfig(
        embedding_size=list(rows),
        embedding_bag_size=int(config["bag_size"]),
        sparse_feature_size=int(config["embedding_dim"]),
        mlp_bot=list(config["mlp_bot"]), mlp_top=list(config["mlp_top"]),
        arch_interaction_op=config["interaction"])
    model = ff.FFModel(ff.FFConfig.parse_args(
        ["-b", str(batch), "--compute-dtype", config["compute_dtype"]]))
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.01), config["loss"], ["mse"],
                  mesh=make_mesh(devices=[one_chip._device]),
                  strategies=dlrm_strategy(model, dcfg, 1, row_shard=False))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = {op.name: {n: sds(d.shape, d.dtype)
                        for n, d in op.param_defs().items()}
              for op in model.ops if op.param_defs()}
    assert (11_739_136, 128) in [tuple(a.shape) for sub in params.values()
                                 for a in sub.values()]
    opt_state = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(model.optimizer.init_state, params))
    sbatch = {t.name: sds((k,) + tuple(t.shape), t.dtype)
              for t in model.input_tensors}
    sbatch["label"] = sds((k,) + tuple(model.label_tensor.shape),
                          model.label_tensor.dtype)
    compiled = model._superstep_fn.lower(
        params, opt_state, {},
        {n: sds((), jnp.float32) for n in model._msums_keys}, sbatch,
        sds((), jnp.int32)).compile()
    memory = obstrace.program_memory(compiled)
    text = compiled.as_text()
    # no second copy of the table: 2,128,896 B of temporaries when this
    # was written, against 2,161,152 for the single step
    assert memory["temp"] < 16e6, memory
    assert memory["alias"] > 6_000_000_000, memory
    assert obstrace.fresh_outputs(compiled) == 1
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert len(re.findall(r" while\(", text)) == 1
    assert not re.search(r"= f32\[11739136,128\]\{[^}]*\} copy\(", text)


# (heads, query length, key length, q/k width, v width, causal): the three
# width pairs of ISSUE 30 at a short sequence; then ISSUE 31's: both
# language-model cells' shapes, a sequence the largest block does not
# divide, one block a sequence (the kernel's single-step form), unequal
# lengths, a non-causal case and three lane tiles of width
@pytest.mark.parametrize("h,sq,sk,hd,vd,causal", [
    (4, 1024, 1024, 192, 128, True), (4, 1024, 1024, 128, 192, True),
    (4, 1024, 1024, 256, 256, True),
    (20, 8192, 8192, 256, 256, True), (16, 8192, 8192, 256, 256, True),
    (4, 1536, 1536, 128, 128, True), (4, 512, 512, 64, 64, True),
    (4, 2048, 4096, 128, 128, False), (4, 4096, 4096, 128, 128, False),
    (4, 4096, 4096, 384, 384, True)])
def test_attend_flash_route_compiles_for_v5e(one_chip, no_compile_cache,
                                             monkeypatch, h, sq, sk, hd, vd,
                                             causal):
    """`attend`'s flash route, forward and gradient, at the blocks
    `_flash_blocks` chooses: Mosaic refuses for the described chip what
    does not fit its scoped VMEM, which interpret mode and the CPU never
    see. With a value head of its own width (latent attention, ISSUE 30)
    the route pads: jax's kernel wants one width for q, k and v, in whole
    lane tiles above 128 (it refuses 192 when it is traced). Forward, dkv
    and dq are three kernels, and the compiled text names their blocks."""
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.ops import attention, embedding
    monkeypatch.setattr(embedding, "_pallas_common",
                        lambda model, op_name, width_ok: bool(width_ok))
    monkeypatch.setattr(attention, "_scores_fit", lambda *a: False)

    class Model:
        ops, optimizer, mesh = [], ff.AdamOptimizer(), None
        config = ff.FFConfig()

    def sds(s, width):
        return jax.ShapeDtypeStruct((1, h, s, width), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        out = attention.attend(Model(), "attn", q, k, v, causal)
        assert out.shape == (1, h, sq, vd)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sds(sq, hd), sds(sk, hd), sds(sk, vd)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    sizes, scope = attention._flash_blocks(
        1, sq, sk, attention._flash_width(hd, vd))
    # the forward under the scope `attend` opens, the backward kernels
    # under jax's own names besides
    assert re.search(rf"jvp\({re.escape(scope)}\)/jit\(flash_attention\)"
                     r"/pallas_call", text)
    assert (f"flash_mha_bwd_dkv_block_q_major={sizes.block_q_major_dkv}"
            f"_block_q={sizes.block_q_dkv}"
            f"_block_k_major={sizes.block_k_major_dkv}"
            f"_block_k={sizes.block_k_dkv}/pallas_call") in text
    assert (f"flash_mha_bwd_dq_block_q_major={sizes.block_q_dq}"
            f"_block_k_major={sizes.block_k_major_dq}"
            f"_block_k={sizes.block_k_dq}/pallas_call") in text
