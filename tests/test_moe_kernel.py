"""The expert walk as the grid of a Pallas kernel (ISSUE 37;
`ops/pallas/moe_kernel.py`): the kernel route against the walk and against
a dense per-expert reference, value and all five gradients, both expert
forms, on routings that hit every edge of the plan; the counter that says
the route engaged; where the routing rule sends the op. On the CPU the
kernels run in interpret mode (`tests/test_tpu_compile.py` compiles them
for a described v5e)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.ops import moe
from dlrm_flexflow_tpu.ops.pallas import moe_kernel
from dlrm_flexflow_tpu.parallel.mesh import make_mesh

T, K, D, F, HELD, ROWS = 24, 2, 256, 128, 4, 8
PAIRS = T * K


def _spread(counts):
    """T x K expert choices with `counts[e]` pairs on held expert e and the
    rest on an expert held elsewhere (HELD), in a seeded order."""
    local = np.concatenate([np.full(c, e) for e, c in enumerate(counts)]
                           + [np.full(PAIRS - sum(counts), HELD)])
    return np.random.default_rng(7).permutation(local)


# held expert -> its pairs; the kernel's trips are 8 rows
ROUTINGS = {
    "an_expert_with_no_pair": (13, 0, 5, 9),
    "one_with_three_trips": (3, 20, 1, 6),
    "a_stretch_ending_on_a_trips_last_row": (8, 16, 7, 2),
    "every_pair_held": (10, 14, 11, 13),
    "none_held": (0, 0, 0, 0),
    # the last trip of the last expert reads past the pairs, into the
    # padding of `order`
    "the_last_trip_reads_into_the_padding": (16, 8, 8, 13),
    "the_first_experts_have_no_pair": (0, 0, 17, 4),
}


def _layer(form, routing, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    xt = jax.random.normal(k[0], (T, D))
    ws = tuple(0.3 * jax.random.normal(
        kk, (HELD, F, D) if n == "down" else (HELD, D, F))
        for kk, n in zip(k[1:4], moe.FORMS[form]))
    pair_w = jax.random.uniform(k[4], (PAIRS,), minval=0.1, maxval=1.0)
    ct = jax.random.normal(k[5], (T, D))
    local = jnp.asarray(_spread(ROUTINGS[routing]))
    key = jnp.where(local < HELD, local, HELD)
    order = jnp.pad(jnp.argsort(key).astype(jnp.int32), (0, 2 * ROWS))
    counts = jnp.zeros((HELD + 1,), jnp.int32).at[key].add(1)[:HELD]
    return xt, ws, pair_w, ct, (order, counts, key < HELD), local


def _dense(form, xt, ws, pair_w, local):
    """Every held pair through its expert, a pair at a time: fp32,
    `highest`, no sort and no trip."""
    pw, e = pair_w.reshape(T, K), local.reshape(T, K)
    with jax.default_matmul_precision("highest"):
        out = 0.0
        for held in range(HELD):
            mine = moe._ffn(jnp.float32, form, xt, tuple(w[held] for w in ws))
            out = out + mine * jnp.sum(jnp.where(e == held, pw, 0.0), axis=1,
                                       keepdims=True)
    return out


def _close(got, want, tol, what):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    scale = float(np.max(np.abs(want))) or 1.0
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale,
                               err_msg=what)


@pytest.mark.parametrize("cdt", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("form", sorted(moe.FORMS))
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_kernel_route_is_the_walk_and_the_dense_layer(routing, form, cdt):
    """Value and the five gradients (the tokens', the two or three
    matrices', the pair weights'). fp32: all three agree to rounding. bf16:
    the kernel against the walk to the compute dtype's rounding (on the CPU
    the walk's autodiff multiplies an fp32 cotangent unrounded where the
    MXU, and the kernel, round it), both against fp32 to a few of them."""
    xt, ws, pair_w, ct, route, local = _layer(form, routing)

    def value_and_grads(fn):
        return jax.value_and_grad(
            lambda x, w, p: jnp.sum(fn(x, w, p) * ct), argnums=(0, 1, 2))(
                xt, ws, pair_w)

    walk = value_and_grads(lambda x, w, p: moe._routed(
        2 * ROWS, K, cdt, form, x, w, p, *route))
    grid = value_and_grads(lambda x, w, p: moe._routed_grid(
        ROWS, K, cdt, True, x, w, p, *route))
    dense = value_and_grads(lambda x, w, p: _dense(form, x, w, p, local))
    names = ["value", "xt"] + [f"w_{n}" for n in moe.FORMS[form]] + ["pair_w"]
    exact = cdt == jnp.float32
    for name, g, w, d in zip(names, *(jax.tree.leaves(t)
                                      for t in (grid, walk, dense))):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        _close(g, w, 2e-6 if exact else 2e-2, f"{name} against the walk")
        _close(g, d, 2e-5 if exact else 4e-2, f"{name} against dense")
        if routing == "none_held":
            assert not np.any(np.asarray(g)), name


@pytest.mark.parametrize("form", sorted(moe.FORMS))
def test_the_fetches_in_flight_across_trips_race_nothing(form):
    """Trip j starts trip j + 1's row fetches before it waits for its own,
    which plain interpret mode runs at once: under the TPU interpreter the
    copies stay asynchronous, the semaphores are counted (a wait that
    nothing feeds hangs here, not on the chip) and a read that races a
    write is reported. Same results, bit for bit."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu
    xt, ws, pair_w, ct, route, _ = _layer(form, "one_with_three_trips")

    def run(interpret):
        return jax.value_and_grad(
            lambda x, w, p: jnp.sum(moe._routed_grid(
                ROWS, K, jnp.float32, interpret, x, w, p, *route) * ct),
            argnums=(0, 1, 2))(xt, ws, pair_w)

    got = run(pltpu.InterpretParams(detect_races=True,
                                    dma_execution_mode="on_wait"))
    assert not interpret_pallas_call.races.races_found
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(run(True))):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_plan_is_the_walks_plan_at_the_kernels_rows(routing):
    """A trip's expert, first row and valid rows are what `moe._chunk`
    gives the walk; dead steps repeat the last live trip; the aligned
    buffer gives every held pair a row of its own inside its expert's
    trips; the backward's plan gives an expert with no pair one trip of no
    valid row."""
    _, _, _, _, (order, counts, held_pair), _ = _layer("swiglu", routing)
    walk = moe._walk_plan(ROWS, counts)
    plan = jax.tree.map(np.asarray, moe_kernel.trip_plan(ROWS, counts, PAIRS))
    n = int(plan["n"][0])
    assert n == int(walk[2]) and plan["expert"].shape == (PAIRS // ROWS
                                                           + HELD,)
    for j in range(n):
        e, _, first, valid = moe._chunk(ROWS, j, walk, counts, order)
        assert (plan["expert"][j], plan["src"][j], plan["valid"][j]) == (
            int(e), int(first), int(valid.sum())), j
    for name in ("expert", "src", "valid"):
        assert (plan[name][n:] == plan[name][max(n - 1, 0)]).all(), name
    assert plan["first"][:n].sum() == int((np.asarray(counts) > 0).sum())
    pos = np.asarray(moe_kernel.aligned(plan, moe._sorted_position(
        order, PAIRS)))[np.asarray(held_pair)]
    assert len(set(pos.tolist())) == int(counts.sum())
    trips = plan["expert"][pos // ROWS] if n else pos
    sorted_expert = np.sort(np.asarray(_spread(ROUTINGS[routing])))
    assert (np.sort(trips) == sorted_expert[:len(pos)]).all()
    assert (pos % ROWS < plan["valid"][pos // ROWS]).all()
    back = jax.tree.map(np.asarray, moe_kernel.trip_plan(
        ROWS, counts, PAIRS, every_expert=True))
    nb = int(back["n"][0])
    assert sorted(set(back["expert"][:nb].tolist())) == list(range(HELD))
    assert nb == n + int((np.asarray(counts) == 0).sum())


def _op(form, x):
    model = ff.FFModel(ff.FFConfig(batch_size=x.shape[0], seed=3))
    t = model.create_tensor(x.shape, name="x")
    gated = form == "swiglu"
    model.moe(t, 16, 4, 128, 128, experts_held=4, expert_offset=8,
              scoring="softmax" if gated else "sigmoid", shared_gate=gated,
              activation=form, name="moe")
    op = model.get_layer_by_name("moe")
    params = jax.tree.map(lambda a: 4.0 * a,
                          op.init_params(jax.random.PRNGKey(11)))
    state = {k: jnp.zeros(d.shape, d.dtype)
             for k, d in op.state_defs().items()}
    return op, params, state


@pytest.mark.parametrize("form", sorted(moe.FORMS))
def test_the_op_counts_the_rows_the_kernels_trips_compute(form, monkeypatch):
    """Through `MoE.apply_with_state`: the same result and gradients as the
    walk's route, `pairs` the same, and `rows` the kernel's rows a trip
    times the trips, so the padded-row metrics show the finer trips."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 256))
    op, p, st = _op(form, x)

    def run():
        (out,), new = op.apply_with_state(p, st, [x])
        g = jax.grad(lambda p: jnp.sum(
            op.apply_with_state(p, st, [x])[0][0] ** 2))(p)
        return out, new, g

    walk_out, walk_st, walk_g = run()
    calls = []
    with monkeypatch.context() as m:
        for name in ("experts_fwd", "experts_bwd"):
            def kernel(*a, orig=getattr(moe_kernel, name), name=name):
                calls.append(name)
                return orig(*a[:-1], True)
            m.setattr(moe_kernel, name, kernel)
        m.setattr(moe_kernel, "grid_walk_ok", lambda *a: True)
        m.setattr(moe_kernel, "ROWS", ROWS)
        out, new, g = run()
    assert "experts_fwd" in calls and "experts_bwd" in calls
    _close(out, walk_out, 2e-6, "the op's result")
    for k in g:
        _close(g[k], walk_g[k], 2e-5, k)
    counts = np.asarray(new["pairs"])
    assert counts.tolist() == np.asarray(walk_st["pairs"]).tolist()
    assert int(new["rows"]) == ROWS * int(np.sum(-(-counts // ROWS)))
    assert int(walk_st["rows"]) == op.chunk_rows * int(
        np.sum(-(-counts // op.chunk_rows)))
    assert int(new["rows"]) < int(walk_st["rows"])


class _Model:
    def __init__(self, mesh=None, compute_dtype=jnp.bfloat16):
        self.mesh, self.compute_dtype = mesh, compute_dtype


# (hidden, expert width, held, top-k, matrices, tokens): the three
# language-model cells'
@pytest.mark.parametrize("name,d,f,held,k,n_mats,tokens,fits", [
    ("qwen3_next", 2048, 512, 32, 10, 3, 8192, True),
    # a whole expert beside its gradient passes the v5e's VMEM
    ("glm_4_7_flash", 2048, 1536, 8, 4, 3, 8192, False),
    # F is 14.5 lane tiles, and a token's slab of 21 sublanes is no whole tile
    ("nemotron_3_nano", 2688, 1856, 8, 6, 2, 8192, False),
    ("a_small_relu2_layer", 1024, 256, 4, 2, 2, 64, True),
    # the plan is prefetched whole and grows with the pairs: 24,576 tokens
    # at top-10 are 1,000,996 bytes, which the v5e's compiler takes of its
    # 1 MiB of scalar memory but the gate's three quarters do not; 32,768
    # (1,333,796) the compiler refuses
    ("qwen3_next_at_16k_tokens", 2048, 512, 32, 10, 3, 16384, True),
    ("qwen3_next_at_24k_tokens", 2048, 512, 32, 10, 3, 24576, False),
    ("qwen3_next_at_32k_tokens", 2048, 512, 32, 10, 3, 32768, False)])
def test_the_route_is_chosen_by_backend_mesh_and_shapes(
        monkeypatch, name, d, f, held, k, n_mats, tokens, fits):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    xt = sds((tokens, d))
    ws = (sds((held, d, f)),) * (n_mats - 1) + (sds((held, f, d)),)
    order = sds((tokens * k + moe.CHUNK_ROWS,), jnp.int32)
    assert moe_kernel.shapes_fit(d, f, n_mats, entries=order.size,
                                 held=held) is fits
    # this suite's backend is the CPU: the walk, whatever the shapes
    assert not moe_kernel.grid_walk_ok(_Model(), xt, ws, order)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe_kernel.grid_walk_ok(_Model(), xt, ws, order) is fits
    one = make_mesh(devices=jax.devices()[:1])
    assert moe_kernel.grid_walk_ok(_Model(one), xt, ws, order) is fits
    # a direct Pallas call cannot run under GSPMD
    two = make_mesh(devices=jax.devices()[:2])
    assert not moe_kernel.grid_walk_ok(_Model(two), xt, ws, order)
    # a token's slab is whole tiles only in fp32
    half = sds((tokens, d), jnp.bfloat16)
    assert not moe_kernel.grid_walk_ok(_Model(), half, ws, order)
    # the budgets are the attached chip's, not the v5e's: with half its
    # VMEM, or a quarter of its scalar memory, Qwen3-Next walks in XLA
    from dlrm_flexflow_tpu.search.cost_model import TPUSpec
    for less in (dict(vmem_bytes=64 << 20), dict(smem_bytes=256 << 10)):
        monkeypatch.setattr(TPUSpec, "detect",
                            staticmethod(lambda: TPUSpec(**less)))
        assert moe_kernel.grid_walk_ok(_Model(), xt, ws, order) is (
            name == "a_small_relu2_layer")
