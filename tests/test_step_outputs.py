"""What a dispatch hands back (ISSUE 33): every carry of the step program
takes over the buffer of the input it replaces, the step counter included,
and everything else (the loss, the metric sums, under a sentinel its flag
and the gradient norm) is ONE fresh float32 vector, split on the host by
`core.metrics.StepMetrics` in one transfer the first time a key is read.

- the compiled `train` and `superstep` programs alias the counter and have
  one fresh output; `ff_step_fresh_outputs` says so (a DLRM and a language
  model, with and without a sentinel);
- the mapping's values are, bit for bit, the scalars the step computed
  (K = 1 and a superstep K = 4, three anomaly policies);
- the vector outlives the dispatches after it (the throttle holds them);
- reading nothing transfers nothing, reading everything transfers once and
  builds no program;
- the donated counter survives save / restore, `reset_metrics`, a rollback.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.core import metrics as metrics_mod
from dlrm_flexflow_tpu.core.metrics import StepMetrics, pack_step_scalars
from dlrm_flexflow_tpu.core.model import AnomalyError, _Throttle
from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                           synthetic_batch)
from dlrm_flexflow_tpu.models.nemotron_h import (NemotronHConfig,
                                                 build_nemotron_h)
from dlrm_flexflow_tpu.obs import metrics as obsmetrics
from dlrm_flexflow_tpu.obs import trace as obstrace
from dlrm_flexflow_tpu.parallel.mesh import make_mesh
from dlrm_flexflow_tpu.utils import faults
from dlrm_flexflow_tpu.utils.checkpoint import CheckpointManager

DCFG = DLRMConfig(embedding_size=[64] * 4, sparse_feature_size=8,
                  mlp_bot=[4, 16, 8], mlp_top=[40, 16, 1])
BS, NB = 16, 8
LM = NemotronHConfig(
    vocab_size=64, hidden_size=32, num_hidden_layers=3,
    hybrid_override_pattern="ME*", mamba_num_heads=2, mamba_head_dim=8,
    n_groups=1, ssm_state_size=8, chunk_size=8, num_attention_heads=2,
    num_key_value_heads=1, head_dim=16, moe_intermediate_size=16,
    moe_shared_expert_intermediate_size=16, n_routed_experts=4,
    num_experts_per_tok=2, experts_held=2, expert_offset=0,
    balance_rate=1e-3)
LM_B, LM_S = 2, 16


def _dlrm(policy="none", superstep=1, ndev=None):
    """On the suite's eight virtual devices, or on `ndev` of them."""
    model = ff.FFModel(ff.FFConfig(batch_size=BS, seed=2, superstep=superstep,
                                   anomaly_policy=policy))
    build_dlrm(model, DCFG)
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"],
                  mesh=ndev and make_mesh(devices=jax.devices()[:ndev]))
    return model.init_layers()


def _dlrm_batches(n=NB, seed=7):
    x, y = synthetic_batch(DCFG, BS * n, seed=seed)
    return [dict({k: v[b * BS:(b + 1) * BS] for k, v in x.items()},
                 label=y[b * BS:(b + 1) * BS]) for b in range(n)]


def _lm(policy="none"):
    model = ff.FFModel(ff.FFConfig(batch_size=LM_B, seed=3,
                                   anomaly_policy=policy))
    build_nemotron_h(model, LM, LM_S)
    model.compile(ff.AdamOptimizer(alpha=1e-3),
                  "sparse_categorical_crossentropy",
                  ["sparse_categorical_crossentropy"],
                  mesh=make_mesh(devices=jax.devices()[:1]))
    return model.init_layers(5)


def _lm_batches(n=4):
    t = np.random.default_rng(0).integers(
        0, LM.vocab_size, size=(n, LM_B, LM_S + 1)).astype(np.int32)
    return [{"tokens": t[i, :, :-1], "label": t[i, :, 1:]} for i in range(n)]


# ---------------------------------------------------------------------
# (a) the compiled programs: the counter aliased, one fresh output
# ---------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["none", "skip_step"])
@pytest.mark.parametrize("family", ["dlrm", "nemotron_h"])
def test_step_programs_alias_the_counter_and_hand_back_one_array(family,
                                                                 policy):
    with obsmetrics.override(True):
        obsmetrics.registry().reset()
        if family == "dlrm":
            model, batches = _dlrm(policy), _dlrm_batches(4)
        else:
            model, batches = _lm(policy), _lm_batches(4)
        counter = model._step_dev
        model.train_batch(batches[0])
        # the donated counter's buffer is the new counter's
        assert counter is None or counter.is_deleted()
        counter = model._step_dev
        model.train_superstep(batches)
        assert counter.is_deleted()
        assert int(np.asarray(model._step_dev)) == model._step == 5
        gauge = obsmetrics.registry().collect()["ff_step_fresh_outputs"]
    assert sorted((s["labels"]["kind"], s["value"])
                  for s in gauge["samples"]) == [("superstep", 1.0),
                                                 ("train", 1.0)]
    n = len(model._step_keys)
    assert n == (5 if policy != "none" else 3)
    for execs, shape in ((model._train_step_execs, (n,)),
                         (model._superstep_execs, (4, n))):
        (executable,) = execs.values()
        assert obstrace.fresh_outputs(executable) == 1
        outs = jax.tree.leaves(executable.out_info)
        aliases = obstrace.aliased_outputs(executable)
        # no argument was pruned, so the counter is the last parameter;
        # its output is the last but one, and the one output no input
        # feeds is the last: the metrics vector
        n_in = len(jax.tree.leaves(executable.in_avals))
        assert aliases[str(len(outs) - 2)] == n_in - 1
        assert set(map(str, range(len(outs)))) - set(aliases) == {
            str(len(outs) - 1)}
        assert (outs[-1].shape, outs[-1].dtype) == (shape, jnp.float32)


def test_fresh_outputs_counts_what_no_input_feeds():
    """The reader itself, on a program with a known answer: of three
    outputs one takes over the donated argument's buffer."""
    f = jax.jit(lambda a, b: (a + 1, b * 2, a.sum() + b.sum()),
                donate_argnums=(0,))
    x = jnp.ones((4,))
    assert obstrace.fresh_outputs(f.lower(x, x).compile()) == 2


# ---------------------------------------------------------------------
# (b) the mapping's values are the step's scalars, bit for bit
# ---------------------------------------------------------------------
@pytest.fixture
def recorded(monkeypatch):
    """Every scalar `pack_step_scalars` is handed, as the running step
    program computed it (a host callback from inside the program, before
    the cast and the stack), in order (which jax keeps on one device
    only)."""
    seen = []

    def spy(scalars, keys):
        jax.debug.callback(lambda s: seen.append(
            {k: np.asarray(v) for k, v in s.items()}), dict(scalars),
            ordered=True)
        return pack_step_scalars(scalars, keys)

    monkeypatch.setattr(metrics_mod, "pack_step_scalars", spy)
    return seen


def _same_bits(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, what
    if want.dtype == np.bool_:
        assert got.dtype == np.bool_ and np.array_equal(got, want), what
    else:
        assert got.dtype == np.float32, what
        assert np.array_equal(got.view(np.uint32),
                              want.astype(np.float32).view(np.uint32)), what


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("policy", ["none", "skip_step", "raise"])
def test_mapping_values_are_the_steps_scalars(recorded, policy, k):
    model, batches = _dlrm(policy, superstep=k, ndev=1), _dlrm_batches()
    keys = {"loss", "mse", "train_all"} | (
        {"anomaly", "grad_norm"} if policy != "none" else set())
    for g in range(0, NB, k):
        mets = (model.train_batch(batches[g]) if k == 1
                else model.train_superstep(batches[g:g + k]))
        assert isinstance(mets, StepMetrics)
        jax.effects_barrier()
        steps = recorded[g:g + k]
        assert len(steps) == k and set(steps[-1]) == keys
        assert set(mets) == keys | ({"per_step", "superstep"}
                                    if k > 1 else set())
        for key in keys:
            _same_bits(mets[key], steps[-1][key], f"{key} at step {g}")
            if k > 1:
                _same_bits(mets["per_step"][key],
                           np.stack([s[key] for s in steps]),
                           f"per_step {key} at step {g}")
        if k > 1:
            assert mets["superstep"] == k
            assert set(mets["per_step"]) == keys
        # they are the quantities they are named for: a mean and its sum
        assert float(mets["train_all"]) == BS
        assert float(mets["loss"]) == pytest.approx(
            float(mets["mse"]) / BS, rel=1e-6)
    assert model._step == NB == len(recorded)


def test_superstep_rows_are_the_single_steps_values():
    """K = 4 against K = 1 on the same batches, every key: the scan packs
    what the single step packs."""
    m1, m4 = _dlrm("skip_step"), _dlrm("skip_step", superstep=4)
    batches = _dlrm_batches(4)
    singles = [dict(m1.train_batch(b)) for b in batches]
    per = m4.train_superstep(batches)["per_step"]
    for key in m1._step_keys:
        _same_bits(per[key], np.stack([s[key] for s in singles]), key)


def test_raise_reads_the_faulting_step_through_the_mapping():
    model, batches = _dlrm("raise", superstep=4), _dlrm_batches(4)
    with faults.active_plan(faults.FaultPlan(nan_grad_steps={2})):
        with pytest.raises(AnomalyError) as err:
            model.train_superstep(batches)
    assert err.value.step == 2 and not np.isfinite(err.value.loss)


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.uint32, jnp.float64,
                                   jnp.int64])
def test_a_scalar_float32_cannot_hold_is_refused_when_traced(dtype):
    with jax.enable_x64(True):
        with pytest.raises(TypeError, match="does not hold it exactly"):
            jax.jit(lambda v: pack_step_scalars(
                {"loss": jnp.float32(0), "count": v}, ("count", "loss"))
                ).lower(jnp.zeros((), dtype))


def test_packing_refuses_a_key_the_model_does_not_carry_and_a_vector():
    with pytest.raises(ValueError, match="the model's step vector"):
        pack_step_scalars({"loss": jnp.float32(0)}, ("loss", "mse"))
    with pytest.raises(TypeError, match="only scalars"):
        pack_step_scalars({"loss": jnp.zeros((2,))}, ("loss",))
    # what a float32 holds exactly goes through, in the keys' order
    vec = pack_step_scalars(
        {"b": jnp.bfloat16(1.5), "a": jnp.asarray(True),
         "c": jnp.int16(-7)}, ("c", "a", "b"))
    assert vec.dtype == jnp.float32 and vec.tolist() == [-7.0, 1.0, 1.5]


# ---------------------------------------------------------------------
# (c) the vector outlives later dispatches
# ---------------------------------------------------------------------
def test_a_kept_mapping_reads_after_forty_later_dispatches():
    model, batches = _dlrm(), _dlrm_batches()
    throttled = _Throttle()
    throttled.bound = 2
    first = throttled(model.train_batch(batches[0]))
    kept = [first]
    for s in range(1, 40):
        kept.append(throttled(model.train_batch(batches[s % NB])))
        assert len(throttled._vectors) <= 2
    assert not first.vector.is_deleted()
    again = _dlrm()
    assert float(first["loss"]) == float(again.train_batch(batches[0])["loss"])
    assert all(np.isfinite(float(m["loss"])) for m in kept)
    throttled.clear()
    assert not throttled._vectors


# ---------------------------------------------------------------------
# (d) one transfer, the first time a key is read; no program
# ---------------------------------------------------------------------
class _CountingNumpy:
    """numpy, with its `asarray` calls noted."""

    def __init__(self):
        self.calls = []

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        self.calls.append(type(a).__name__)
        return np.asarray(a, *args, **kw)


@pytest.fixture
def transfers(monkeypatch):
    """The `np.asarray` calls core/metrics.py makes, by argument type."""
    shim = _CountingNumpy()
    monkeypatch.setattr(metrics_mod, "np", shim)
    return shim.calls


@pytest.fixture(scope="module")
def programs():
    """Counts the programs XLA builds, as the benchmark's window does."""
    from perfbench.run import Programs
    return Programs()


@pytest.mark.parametrize("k", [1, 4])
def test_reading_nothing_transfers_nothing_and_everything_once(transfers,
                                                               programs, k):
    model, batches = _dlrm("skip_step", superstep=k), _dlrm_batches()
    run = (lambda g: model.train_batch(batches[g])) if k == 1 else (
        lambda g: model.train_superstep(batches[g:g + k]))
    run(0)                                  # compiles
    mets = run(k)
    jax.block_until_ready(mets.vector)
    assert transfers == []                  # a step nobody looks at
    built = programs.built
    values = {key: mets[key] for key in model._step_keys}
    if k > 1:
        values.update({f"per_step.{key}": mets["per_step"][key]
                       for key in model._step_keys})
        values["last"] = dict(mets), dict(mets["per_step"])
    float(mets["loss"]), bool(mets["anomaly"])
    assert transfers == ["ArrayImpl"], transfers
    assert programs.built == built          # no indexing program a key
    assert all(not isinstance(v, jax.Array) for v in values.values())


def test_anomaly_policies_that_never_look_never_transfer(transfers):
    """`none` and `skip_step` read nothing at dispatch; `raise` reads its
    flag, once a dispatch."""
    for policy, expect in (("none", 0), ("skip_step", 0), ("raise", 1)):
        model, batches = _dlrm(policy), _dlrm_batches(3)
        del transfers[:]
        for b in batches:
            model.train_batch(b)
        assert len(transfers) == expect * len(batches), policy


# ---------------------------------------------------------------------
# (e) the donated counter through save / restore, reset_metrics, rollback
# ---------------------------------------------------------------------
def _agree(model):
    assert int(np.asarray(model._step_dev)) == model._step


def test_counter_survives_save_restore_and_reset_metrics(tmp_path):
    model, batches = _dlrm(), _dlrm_batches()
    for b in batches[:3]:
        model.train_batch(b)
    _agree(model)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(model, {"epoch": 0, "batch": 3})
    for b in batches[3:6]:
        model.train_batch(b)
    assert model._step == 6
    model.reset_metrics()
    loss6 = float(model.train_batch(batches[6])["loss"])
    _agree(model)
    assert mgr.restore_latest(model)["step"] == 3
    assert model._step == 3 and model._step_dev is None
    for b in batches[3:6]:
        model.train_batch(b)
    # the restored run retraces the first one: same counter, same RNG fold
    assert float(model.train_batch(batches[6])["loss"]) == loss6
    _agree(model)
    assert model._step == 7


def test_counter_survives_a_rollback(tmp_path):
    x, y = synthetic_batch(DCFG, BS * NB, seed=7)
    model = _dlrm("rollback")
    with faults.active_plan(faults.FaultPlan(nan_grad_steps={5})) as plan:
        out = model.fit(x, y, epochs=2, verbose=False,
                        checkpoint_dir=str(tmp_path), save_every=2)
    assert ("nan_grad", 5) in plan.fired and out["rollbacks"] == 1
    assert model._step == 2 * NB
    # one more dispatch rebuilds nothing: the counter the rollback
    # re-seeded from the snapshot's step has been donated ever since
    model.train_batch(_dlrm_batches(1)[0])
    _agree(model)
    clean = _dlrm()
    clean.fit(x, y, epochs=2, verbose=False)
    clean.train_batch(_dlrm_batches(1)[0])
    # the counter feeds the step's RNG fold and nothing else here; the
    # restore's host round trip may cost an ulp (tests/test_superstep.py)
    for name, sub in clean.params.items():
        for pn, w in sub.items():
            np.testing.assert_allclose(
                np.asarray(model.params[name][pn]), np.asarray(w),
                rtol=1e-5, atol=1e-7, err_msg=name)
