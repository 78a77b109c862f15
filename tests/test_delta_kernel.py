"""The gated delta rule's hand-over as a Pallas kernel with the state
resident in VMEM (ISSUE 36; `ops/pallas/delta_kernel.py`): the kernel pair
against the recurrence a position at a time and against the `lax.scan`
hand-over, output and every gradient; where the routing rule sends the op;
what the cost model is told. On the CPU the kernels run in interpret mode
(`tests/test_tpu_compile.py` compiles them for a described v5e)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.ops import delta_net
from dlrm_flexflow_tpu.ops.pallas import delta_kernel
from dlrm_flexflow_tpu.parallel.mesh import make_mesh
from dlrm_flexflow_tpu.search.cost_model import CostModel, TPUSpec

H, D = 2, 128       # heads of the kernel's tile: 128 x 128


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel in interpret mode wherever `delta_net` calls it; counts
    the calls."""
    orig, calls = delta_kernel.hand_over, []

    def hand_over(*a):
        calls.append(a[0].shape)
        return orig(*a, True)

    monkeypatch.setattr(delta_kernel, "hand_over", hand_over)
    return calls


def _inputs(seq, batch=1, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = delta_net.l2_normalize(jax.random.normal(k[0], (batch, seq, H, D)))
    kk = delta_net.l2_normalize(jax.random.normal(k[1], (batch, seq, H, D)))
    v = jax.random.normal(k[2], (batch, seq, H, D))
    g = -0.3 * jax.nn.softplus(jax.random.normal(k[3], (batch, seq, H)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (batch, seq, H)))
    S0 = 0.5 * jax.random.normal(k[5], (batch, H, D, D))
    return (q * D ** -0.5, kk, v, g, beta), S0


def _stepwise_from(S0, q, k, v, g, beta):
    """`gated_delta_rule_stepwise` from a state: (o, the state after)."""
    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt))
        S = S + kt[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    S, o = lax.scan(step, S0, tuple(jnp.moveaxis(t, 1, 0)
                                    for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def _close(got, want, tol, what):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    scale = float(np.max(np.abs(want))) or 1.0
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale,
                               err_msg=what)


# (batch, sequence, chunk, span, compute dtype, from a state of its own)
CASES = {
    "seq_no_multiple_of_the_chunk": (1, 40, 16, 1024, jnp.float32, False),
    "three_spans_two_samples": (2, 96, 16, 32, jnp.float32, False),
    "bf16_spans_and_a_ragged_end": (1, 72, 16, 32, jnp.bfloat16, False),
    "non_zero_S0": (2, 64, 32, 64, jnp.float32, True),
    "non_zero_S0_bf16": (1, 64, 32, 64, jnp.bfloat16, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_pair_is_the_recurrence_and_the_scan(interpreted, case):
    """Output, the state after, and the gradient of every input (the state
    the span starts from included) agree with the `lax.scan` hand-over to
    rounding, and with the recurrence a position at a time to what the
    chunked form allows (fp32) or bf16 products allow."""
    batch, seq, chunk, span, cdt, from_state = CASES[case]
    xs, S0 = _inputs(seq, batch, seed=seq)
    ct = jax.random.normal(jax.random.PRNGKey(9), (batch, seq, H, D))
    cS = jax.random.normal(jax.random.PRNGKey(8), S0.shape)

    if from_state:      # one span, straight into `_delta_rule_span`
        def chunked(resident):
            def f(S0, *xs):
                o, S = delta_net._delta_rule_span(
                    *(t.astype(cdt) for t in xs[:3]), *xs[3:], S0, chunk,
                    cdt, resident)
                return jnp.sum(o * ct) + jnp.sum(S * cS), (o, S)
            return f

        def stepwise(S0, *xs):
            o, S = _stepwise_from(S0, *xs)
            return jnp.sum(o * ct) + jnp.sum(S * cS), (o, S)
        args = (S0,) + xs
    else:
        def chunked(resident):
            def f(*xs):
                o = delta_net.gated_delta_rule_chunked(
                    *(t.astype(cdt) for t in xs[:3]), *xs[3:], chunk=chunk,
                    compute_dtype=cdt, span=span, resident=resident)
                return jnp.sum(o * ct), (o,)
            return f

        def stepwise(*xs):
            o = delta_net.gated_delta_rule_stepwise(*xs)
            return jnp.sum(o * ct), (o,)
        args = xs

    def run(f):
        (_, outs), grads = jax.value_and_grad(
            f, argnums=tuple(range(len(args))), has_aux=True)(*args)
        return outs + grads

    with jax.default_matmul_precision("highest"):
        kernel, scan, exact = run(chunked(True)), run(chunked(False)), \
            run(stepwise)
    assert interpreted, "the kernel route was not taken"
    fp32 = cdt == jnp.float32
    names = (["o", "S"][:len(kernel) - len(args)]
             + ["dS0"] * from_state + ["dq", "dk", "dv", "dg", "dbeta"])
    for name, a, b, c in zip(names, kernel, scan, exact):
        _close(a, b, 1e-5 if fp32 else 2e-2, f"{name} against the scan")
        _close(a, c, 3e-4 if fp32 else 4e-2, f"{name} against stepwise")
        if not fp32:    # bf16 moves elements, not the direction
            a, c = (np.asarray(t, np.float32).ravel() for t in (a, c))
            assert a @ c / np.sqrt((a @ a) * (c @ c)) > 0.999, name


def test_the_decays_gradient_reads_the_state_in_fp32(interpreted):
    """With q = k = 0 a chunk only decays the state, S <- exp(gc_last) S,
    and the one path to dg is the sum of S dS over the state that entered
    each chunk: no product's operand, so bf16 products must not round it.
    The kernel's dg is the `lax.scan` route's (autodiff through the fp32
    `S * last`) to fp32 rounding; entering states kept in bf16 read 2e-3
    to 1e-2 here."""
    cdt = jnp.bfloat16
    (q, k, v, g, beta), S0 = _inputs(64, seed=5)
    cS = jax.random.normal(jax.random.PRNGKey(8), S0.shape)
    zero = jnp.zeros_like(q, cdt)

    def dg(resident):
        def loss(g):
            _, S = delta_net._delta_rule_span(
                zero, zero, v.astype(cdt), g, beta, S0, 32, cdt, resident)
            return jnp.sum(S * cS)
        return jax.grad(loss)(0.1 * g)

    kernel, scan = dg(True), dg(False)
    assert interpreted and float(jnp.max(jnp.abs(scan))) > 1e-2
    _close(kernel, scan, 1e-5, "dg through the state's decay alone")


def test_no_states_are_written_where_no_gradient_is_asked():
    """The primal call is the kernel without the entering states: one
    output fewer, and the same `o` and final state as the call that keeps
    them."""
    _, S0 = _inputs(64, seed=3)
    tiles = [jax.random.normal(jax.random.PRNGKey(i), (H, 2, 32, D))
             for i in range(4)]
    gc = -jnp.cumsum(jax.random.uniform(jax.random.PRNGKey(4),
                                        (H, 2, 1, 32)), axis=-1)
    plain = delta_kernel._run_fwd(*tiles, gc, S0[0], True, False)
    kept = delta_kernel._run_fwd(*tiles, gc, S0[0], True, True)
    assert plain[1] is None and kept[1].shape == (H, 2, D, D)
    np.testing.assert_array_equal(kept[1][:, 0], S0[0])
    np.testing.assert_array_equal(plain[0], kept[0])
    np.testing.assert_array_equal(plain[2], kept[2])


def _delta_model(devices, dk=D, seq=64):
    model = ff.FFModel(ff.FFConfig(batch_size=len(devices)))
    x = model.create_tensor((len(devices), seq, 64), name="x")
    op_out = delta_net.GatedDeltaNet(model, x, 1, 2, dk, D,
                                     name="delta").outputs[0]
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"],
                  final_tensor=op_out, mesh=make_mesh(devices=devices))
    return model, model.ops[-1]


@pytest.mark.parametrize("backend,devices,dk,kernel", [
    ("cpu", 1, D, False),           # this suite: the `lax.scan`
    ("tpu", 1, D, True),
    ("tpu", 2, D, False),           # GSPMD cannot carry a direct call
    ("tpu", 1, 64, False),          # half a lane tile
])
def test_routing_rule(monkeypatch, interpreted, backend, devices, dk, kernel):
    """The kernel on one TPU with tile-aligned heads, the `lax.scan`
    everywhere else; no flag decides. The op's forward takes the route
    the rule names."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    model, op = _delta_model(jax.devices()[:devices], dk=dk)
    assert delta_kernel.resident_hand_over_ok(
        model, delta_net.CHUNK, dk, D) is kernel
    assert op.scan_weights_resident() is kernel
    model.init_layers(seed=0)
    x = np.random.RandomState(0).randn(devices, 64, 64).astype(np.float32)
    out = np.asarray(model.forward_batch({"x": x}))
    assert np.isfinite(out).all()
    assert bool(interpreted) is kernel


def test_cost_model_is_told_when_the_state_is_resident():
    """The simulator prices a serial scan's re-streams from HBM; what the
    delta rule's `lax.scan` streams is the state, not the layer's
    weights, and the kernel streams nothing (as `scan_weights_resident`
    says for the LSTM)."""
    model, op = _delta_model(jax.devices()[:1], seq=8192)
    one, two = ff.ParallelConfig((1, 1, 1)), ff.ParallelConfig((2, 1, 1))
    assert op.sequential_steps() == 8192 // delta_net.CHUNK
    assert op.scan_param_stream_bytes() == 4 * 2 * D * D * 4
    assert op.scan_weights_resident(one)        # the candidate, on a TPU
    assert not op.scan_weights_resident(two)
    assert not op.scan_weights_resident()       # as compiled: this CPU
    resident = CostModel(TPUSpec()).op_compute_time(op, one)
    # a candidate the kernel cannot carry pays the state's re-streams
    streamed = 2 * CostModel(TPUSpec()).op_compute_time(op, two)
    assert resident <= streamed
    # either way a chunk costs at least the loop's own latency
    assert resident >= op.sequential_steps() * TPUSpec().scan_iter_s
