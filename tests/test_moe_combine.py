"""The expert op's `combine` as a Pallas gather-sum
(`ops/pallas/combine_kernel.py`): the kernel against the XLA gathers it
replaces (`moe._sum_pairs`), bit for bit but for the sign of a zero, over
top-k, widths and routings (the rows bfloat16: fp32 keeps the gathers); through both walks, the value and
all five gradients; the fetches in flight across blocks under the TPU
interpreter's race detector; the rule that routes it and the counter that
reports the route. On the CPU the kernels run in interpret mode
(`tests/test_tpu_compile.py` compiles them for a described v5e)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.ops import moe
from dlrm_flexflow_tpu.ops.pallas import combine_kernel
from dlrm_flexflow_tpu.parallel.mesh import make_mesh

from test_moe_kernel import K, ROWS, _Model, _close, _dense, _layer

# routing -> (tokens in blocks of `token_block`, the held pairs of a
# (tokens, k) grid of pairs, from a seeded generator)
ROUTINGS = {
    "no_pair_held": (2, lambda rng, t, k: np.zeros((t, k), bool)),
    "every_pair_held": (2, lambda rng, t, k: np.ones((t, k), bool)),
    "one_token_holds_all_k": (2, lambda rng, t, k: np.arange(t)[:, None]
                              == np.full((t, k), t // 3)),
    # the block's last token is the last token: it ends the pairs, and its
    # fetches are the last of the grid
    "a_blocks_last_token_ends_the_pairs": (
        2, lambda rng, t, k: (rng.random((t, k)) < 0.3)
        | (np.arange(t)[:, None] == t - 1)),
    # a block of fewer tokens than `token_block`: the tokens are padded
    "a_last_block_of_fewer_tokens": (
        1.5, lambda rng, t, k: rng.random((t, k)) < 0.5),
}


def _pass(top_k, d, dtype, routing, seed=0):
    """(rows_buf, pos, held_pair) of one pass: the rows in sorted order,
    each pair's row anywhere in them (one row a pair), 16 rows spare."""
    rng = np.random.default_rng(seed)
    blocks, held = ROUTINGS[routing]
    tokens = int(blocks * combine_kernel.token_block(1 << 20, top_k))
    held = held(rng, tokens, top_k).reshape(-1)
    r = tokens * top_k + 16
    rows = rng.standard_normal((r, d), np.float32)
    rows[rng.random((r, d)) < 0.05] = 0.0
    rows[rng.random((r, d)) < 0.05] = -0.0
    pos = rng.permutation(r)[:tokens * top_k].astype(np.int32)
    return jnp.asarray(rows, dtype), jnp.asarray(pos), jnp.asarray(held)


@pytest.mark.parametrize("d", [2048, 2688])
@pytest.mark.parametrize("top_k", [4, 6, 10])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_kernel_is_the_xla_sum_bit_for_bit(routing, top_k, d):
    """Each token's fp32 sum over j of its held pairs' bfloat16 rows, every
    bit the XLA gathers give; == takes -0.0 for 0.0, the one difference
    allowed (a pair held elsewhere is skipped, not added as +0.0)."""
    rows, pos, held = _pass(top_k, d, jnp.bfloat16, routing)
    want = moe._sum_pairs(rows, pos, held, top_k)
    got = moe._sum_pairs(rows, pos, held, top_k, kernel=True, interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), np.asarray(want))
    if routing == "no_pair_held":
        assert not np.any(np.asarray(got))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float16],
                         ids=["fp32", "fp16"])
def test_the_kernel_refuses_rows_not_in_bfloat16(dtype):
    """The rule sends only bfloat16 rows to the kernels; called with any
    other it says so rather than sum them some other way."""
    rows, pos, held = _pass(4, 256, dtype, "every_pair_held")
    with pytest.raises(ValueError, match="bfloat16"):
        combine_kernel.gather_sum(rows, pos, held, 4, True)


def test_the_fetches_in_flight_across_blocks_race_nothing():
    """Block b starts block b + 1's fetches before it waits for its own,
    which plain interpret mode runs at once: under the TPU interpreter the
    copies stay asynchronous, the semaphores are counted (a wait for a
    fetch never started hangs here, not on the chip) and a read that races
    a write is reported. Same result, bit for bit."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu
    rows, pos, held = _pass(4, 256, jnp.bfloat16,
                            "a_blocks_last_token_ends_the_pairs")
    got = combine_kernel.gather_sum(
        rows, pos, held, 4, pltpu.InterpretParams(
            detect_races=True, dma_execution_mode="on_wait"))
    assert not interpret_pallas_call.races.races_found
    np.testing.assert_array_equal(
        got, combine_kernel.gather_sum(rows, pos, held, 4, True))


@pytest.fixture
def interpreted(monkeypatch):
    """The gather-sum in interpret mode wherever the op calls it."""
    calls = []

    def gather_sum(rows_buf, pos, held_pair, top_k, interpret=False,
                   orig=combine_kernel.gather_sum):
        calls.append(rows_buf.dtype)
        return orig(rows_buf, pos, held_pair, top_k, interpret or True)

    monkeypatch.setattr(combine_kernel, "gather_sum", gather_sum)
    return calls


@pytest.mark.parametrize("form", sorted(moe.FORMS))
@pytest.mark.parametrize("walk", ["loop", "grid"])
@pytest.mark.parametrize("routing", [
    "none_held", "the_last_trip_reads_into_the_padding"])
def test_both_walks_with_the_kernel_are_the_walks_with_the_gathers(
        interpreted, routing, walk, form):
    """Value and the five gradients (the tokens', the two or three
    matrices', the pair weights') through `_routed` and `_routed_grid` in
    bfloat16, the kernels' one compute dtype: with the kernel as `combine`
    in both passes, every bit what the same walk gives with the gathers,
    and as close to the dense reference as `tests/test_moe_kernel.py`
    holds the walks."""
    cdt = jnp.bfloat16
    xt, ws, pair_w, ct, route, local = _layer(form, routing)

    def value_and_grads(combine):
        def fn(x, w, p):
            if walk == "loop":
                return moe._routed(2 * ROWS, K, cdt, form, x, w, p, *route,
                                   combine)
            return moe._routed_grid(ROWS, K, cdt, True, x, w, p, *route,
                                    combine)
        return jax.value_and_grad(
            lambda x, w, p: jnp.sum(fn(x, w, p) * ct), argnums=(0, 1, 2))(
                xt, ws, pair_w)

    gathers = value_and_grads(False)
    assert not interpreted
    kernel = value_and_grads(True)
    assert interpreted == [jnp.dtype(cdt)] * 2     # forward and backward
    dense = jax.value_and_grad(
        lambda x, w, p: jnp.sum(_dense(form, x, w, p, local) * ct),
        argnums=(0, 1, 2))(xt, ws, pair_w)
    names = ["value", "xt"] + [f"w_{n}" for n in moe.FORMS[form]] + ["pair_w"]
    for name, g, w, d in zip(names, *(jax.tree.leaves(t)
                                      for t in (kernel, gathers, dense))):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert np.array_equal(np.asarray(g), np.asarray(w)), name
        _close(g, d, 4e-2, f"{name} against dense")


# (tokens, top-k, hidden, rows' dtype, fits the v5e): the four
# language-model cells', then the shapes the rule refuses
@pytest.mark.parametrize("name,tokens,k,d,dtype,fits", [
    ("qwen3_next", 8192, 10, 2048, jnp.bfloat16, True),
    ("glm_4_7_flash", 8192, 4, 2048, jnp.bfloat16, True),
    ("lfm2_24b_a2b", 8192, 4, 2048, jnp.bfloat16, True),
    ("nemotron_3_nano", 8192, 6, 2688, jnp.bfloat16, True),
    ("fp32_rows", 8192, 10, 2048, jnp.float32, False),
    ("d_no_whole_lane_tiles", 8192, 4, 2000, jnp.bfloat16, False),
    ("half_precision_rows", 8192, 4, 2048, jnp.float16, False),
    # the plan, a number a pair, in three quarters of 1 MiB of scalar
    # memory: 24,576 tokens at top-10 are 983,040 bytes
    ("qwen3_next_at_16k_tokens", 16384, 10, 2048, jnp.bfloat16, True),
    ("qwen3_next_at_24k_tokens", 24576, 10, 2048, jnp.bfloat16, False)])
def test_the_route_is_chosen_by_backend_mesh_and_shapes(
        monkeypatch, name, tokens, k, d, dtype, fits):
    assert combine_kernel.shapes_fit(tokens, k, d, dtype) is fits
    # this suite's backend is the CPU: the gathers, whatever the shapes
    assert not combine_kernel.combine_ok(_Model(), tokens, k, d, dtype)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert combine_kernel.combine_ok(_Model(), tokens, k, d, dtype) is fits
    one = make_mesh(devices=jax.devices()[:1])
    assert combine_kernel.combine_ok(_Model(one), tokens, k, d, dtype) is fits
    # a direct Pallas call cannot run under GSPMD
    two = make_mesh(devices=jax.devices()[:2])
    assert not combine_kernel.combine_ok(_Model(two), tokens, k, d, dtype)
    # the budgets are the attached chip's: a quarter of the v5e's VMEM
    # holds the landing buffers, a sixty-fourth does not
    from dlrm_flexflow_tpu.search.cost_model import TPUSpec
    for vmem, ok in ((32 << 20, fits), (2 << 20, False)):
        monkeypatch.setattr(TPUSpec, "detect",
                            staticmethod(lambda v=vmem: TPUSpec(vmem_bytes=v)))
        assert combine_kernel.combine_ok(_Model(), tokens, k, d, dtype) is ok


@pytest.mark.parametrize("gate", [False, True], ids=["gathers", "kernel"])
def test_expert_stats_and_the_gauge_report_the_route(interpreted,
                                                      monkeypatch, gate):
    """A model of one expert op trained a step: `combine_kernel` is 1 in
    `expert_stats()` and in `ff_moe_combine_kernel{op}` exactly where the
    rule sent `combine` to the kernel, and the kernel ran there."""
    monkeypatch.setattr(combine_kernel, "combine_ok", lambda *a: gate)
    b, s, d = 2, 16, 256
    model = ff.FFModel(ff.FFConfig(batch_size=b, seed=3,
                                   compute_dtype="bfloat16"))
    x = model.create_tensor((b, s, d), name="x")
    model.moe(x, 16, 4, 128, 0, experts_held=4, expert_offset=8,
              scoring="sigmoid", name="moe")
    model.compile(ff.SGDOptimizer(lr=0.01), "mean_squared_error", ["mse"],
                  mesh=make_mesh(devices=jax.devices()[:1]))
    model.init_layers()
    assert int(model.expert_stats()["moe"]["combine_kernel"]) == 0
    rng = np.random.default_rng(0)
    model.fit({"x": rng.standard_normal((b, s, d), np.float32)},
              rng.standard_normal((b, s, d), np.float32), epochs=1,
              verbose=False)
    st = model.expert_stats()["moe"]
    assert int(st["combine_kernel"]) == int(gate)
    assert bool(interpreted) is gate
    (gauge,) = [v for n, lab, v in model._obs_collect_experts()
                if n == "ff_moe_combine_kernel" and lab == {"op": "moe"}]
    assert gauge == int(gate)
    assert int(st["pairs"].sum()) > 0
