"""The step program says what it needs (ISSUE 34): every step executable
`FFModel._cached_compile` builds or loads leaves ONE record in `obs.trace`
(the executable, the seconds lowering and compiling took, built or loaded),
and the record answers, on request only, what the compiler counts for it.

- the store: one record a (kind, key), newest last, bounded; a second
  `fit()` at another batch shape leaves two `train` records;
- `program_memory`: `counted = argument + output - alias + temp`; a donated
  carry shows in `alias`, a materialised (n, n) product in `temp`; None for
  an object that gives no analysis;
- `--obs on`: the three families of samples at scrape; off: the factories
  hand out the NULL instruments and a compile reads neither the program's
  text nor its memory analysis;
- `fit()` of a tiny DLRM and a tiny Nemotron-H leaves its seconds and
  `FFModel.step_memory()` its bytes.
"""

from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                           synthetic_batch)
from dlrm_flexflow_tpu.models.nemotron_h import (NemotronHConfig,
                                                 build_nemotron_h)
from dlrm_flexflow_tpu.obs import metrics as obsmetrics
from dlrm_flexflow_tpu.obs import trace as obstrace
from dlrm_flexflow_tpu.parallel.mesh import make_mesh

DCFG = DLRMConfig(embedding_size=[64] * 4, sparse_feature_size=8,
                  mlp_bot=[4, 16, 8], mlp_top=[40, 16, 1])
BS = 16
LM = NemotronHConfig(
    vocab_size=64, hidden_size=32, num_hidden_layers=3,
    hybrid_override_pattern="ME*", mamba_num_heads=2, mamba_head_dim=8,
    n_groups=1, ssm_state_size=8, chunk_size=8, num_attention_heads=2,
    num_key_value_heads=1, head_dim=16, moe_intermediate_size=16,
    moe_shared_expert_intermediate_size=16, n_routed_experts=4,
    num_experts_per_tok=2, experts_held=2, expert_offset=0,
    balance_rate=1e-3)
LM_B, LM_S = 2, 16


@pytest.fixture
def records():
    """An empty store for the test; what other tests noted comes back."""
    with obstrace._PROGRAMS_LOCK:
        saved = list(obstrace._PROGRAMS.items())
        obstrace._PROGRAMS.clear()
    yield obstrace._PROGRAMS
    with obstrace._PROGRAMS_LOCK:
        obstrace._PROGRAMS.clear()
        obstrace._PROGRAMS.update(saved)


def _dlrm(batch=BS, superstep=1):
    model = ff.FFModel(ff.FFConfig(batch_size=batch, seed=2,
                                   superstep=superstep))
    build_dlrm(model, DCFG)
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"],
                  mesh=make_mesh(devices=jax.devices()[:1]))
    return model.init_layers()


def _dlrm_batches(n, batch=BS):
    x, y = synthetic_batch(DCFG, batch * n, seed=7)
    return [dict({k: v[b * batch:(b + 1) * batch] for k, v in x.items()},
                 label=y[b * batch:(b + 1) * batch]) for b in range(n)]


def _lm():
    model = ff.FFModel(ff.FFConfig(batch_size=LM_B, seed=3))
    build_nemotron_h(model, LM, LM_S)
    model.compile(ff.AdamOptimizer(alpha=1e-3),
                  "sparse_categorical_crossentropy",
                  ["sparse_categorical_crossentropy"],
                  mesh=make_mesh(devices=jax.devices()[:1]))
    return model.init_layers(5)


def _fit(family):
    if family == "dlrm":
        model = _dlrm()
        x, y = synthetic_batch(DCFG, BS * 4, seed=1)
    else:
        model = _lm()
        t = np.random.default_rng(0).integers(
            0, LM.vocab_size, size=(LM_B * 4, LM_S + 1)).astype(np.int32)
        x, y = {"tokens": t[:, :-1]}, t[:, 1:]
    model.fit(x, y, epochs=1, verbose=False)
    return model


class _Executable:
    """Stands where a compiled program would, and counts what is read."""
    out_tree = SimpleNamespace(num_leaves=1)

    def __init__(self, text="HloModule jit_fake, entry\n"):
        self.text, self.reads = text, []

    def as_text(self):
        self.reads.append("as_text")
        return self.text

    def memory_analysis(self):
        self.reads.append("memory_analysis")
        raise RuntimeError("a loaded program keeps no analysis")


class _Lowered:
    def __init__(self, executable):
        self.executable = executable

    def compile(self):
        return self.executable


# ---------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------
def test_one_record_a_kind_and_key_newest_last(records):
    a, b, c, a2 = object(), object(), object(), object()
    obstrace.note_program("train", a, key="k1", lower_s=1.0, compile_s=2.0)
    obstrace.note_program("train", b, key="k2")
    obstrace.note_program("eval", c, key="k1")
    obstrace.note_program("train", a2, key="k1", compile_s=0.5, loaded=True)
    assert [(r.kind, r.key, r.executable) for r in obstrace.programs()] == [
        ("train", "k2", b), ("eval", "k1", c), ("train", "k1", a2)]
    (newest,) = [r for r in obstrace.programs("train") if r.key == "k1"]
    assert (newest.lower_s, newest.compile_s, newest.loaded) == (0.0, 0.5,
                                                                True)
    assert [r.executable for r in obstrace.programs("eval")] == [c]
    assert obstrace.programs("superstep") == []


def test_the_bound_drops_the_oldest(records):
    n = obstrace.PROGRAMS_KEPT
    for i in range(n + 3):
        obstrace.note_program("train", object(), key=i)
    kept = obstrace.programs()
    assert len(kept) == n and 8 <= n <= 64
    assert [r.key for r in kept] == list(range(3, n + 3))
    # a program compiled again is the newest, and the count stays
    obstrace.note_program("train", object(), key=3)
    assert [r.key for r in obstrace.programs()][-1] == 3
    assert len(obstrace.programs()) == n


def test_program_scopes_reads_the_newest_of_each_kind(records):
    old = _Executable("HloModule jit_train_step, entry\n  %a.1 = f32[] "
                      'add(), metadata={op_name="old"}\n')
    new = _Executable("HloModule jit_train_step, entry\n  %a.1 = f32[] "
                      'add(), metadata={op_name="jit(x)/ff.new"}\n')
    obstrace.note_program("train", old, key="k1")
    obstrace.note_program("eval", _Executable(), key="k1")
    obstrace.note_program("train", new, key="k2")
    scopes = obstrace.program_scopes()
    assert scopes["jit_train_step"] == {"a.1": "jit(x)/ff.new"}
    assert set(scopes) == {"jit_train_step", "jit_fake"}
    assert old.reads == []


def test_a_second_fit_at_another_batch_shape_leaves_two_train_records(
        records):
    model = _dlrm()
    x, y = synthetic_batch(DCFG, BS * 4, seed=1)
    model.fit(x, y, epochs=2, verbose=False)
    (first,) = obstrace.programs("train")
    model.fit(x, y, epochs=1, verbose=False)         # the same shape again
    assert [r.executable for r in obstrace.programs("train")] == [
        first.executable]
    model.fit(x, y, epochs=1, batch_size=BS // 2, verbose=False)
    one, two = obstrace.programs("train")
    assert one is first and two.key != one.key
    assert {id(r.executable) for r in (one, two)} == {
        id(e) for e in model._train_step_execs.values()}
    assert all(r.lower_s > 0 and r.compile_s > 0 and not r.loaded
               for r in (one, two))
    # the model answers for its own newest program
    assert model.step_memory()["train"] == obstrace.program_memory(
        two.executable)


# ---------------------------------------------------------------------
# what the compiler counts
# ---------------------------------------------------------------------
def test_counted_is_argument_plus_output_minus_alias_plus_temp():
    n = 256

    def step(carry, x):
        prod = x @ x.T                        # (n, n), kept for two uses
        return carry + prod.sum(axis=0), jnp.tanh(prod).sum() + prod.max()

    carry, x = jnp.zeros((n,)), jnp.ones((n, 8))
    plain = obstrace.program_memory(jax.jit(step).lower(carry, x).compile())
    donated = obstrace.program_memory(
        jax.jit(step, donate_argnums=(0,)).lower(carry, x).compile())
    for m in (plain, donated):
        assert set(m) == set(obstrace.MEMORY_PARTS) | {"counted"}
        assert all(isinstance(v, int) and v >= 0 for v in m.values())
        assert m["counted"] == (m["argument"] + m["output"] - m["alias"]
                                + m["temp"])
        assert m["argument"] == 4 * (n + n * 8)
        # the product is nobody's argument or output: it is a temporary
        assert m["temp"] >= 4 * n * n
    # the donated carry's buffer is the new carry's
    assert plain["alias"] == 0 and donated["alias"] == 4 * n
    assert donated["counted"] == plain["counted"] - 4 * n


def test_none_for_an_object_without_a_memory_analysis():
    assert obstrace.program_memory(object()) is None
    refuses = _Executable()
    assert obstrace.program_memory(refuses) is None
    assert refuses.reads == ["memory_analysis"]

    class Gives:
        def memory_analysis(self):
            return None                        # some backends' answer
    assert obstrace.program_memory(Gives()) is None


# ---------------------------------------------------------------------
# the operator's side, under --obs
# ---------------------------------------------------------------------
def _by_labels(entry, *names):
    return {tuple(s["labels"][n] for n in names): s["value"]
            for s in entry["samples"]}


def test_obs_on_the_three_families_of_samples_at_scrape(records):
    with obsmetrics.override(True):
        obsmetrics.registry().reset()
        model = _dlrm()
        batches = _dlrm_batches(4)
        model.train_batch(batches[0])
        model.train_superstep(batches)
        snap = obsmetrics.registry().collect()
        again = obsmetrics.registry().collect()
    memory = model.step_memory()
    assert set(memory) == {"train", "superstep"}
    hbm = _by_labels(snap["ff_step_hbm_bytes"], "kind", "part")
    assert hbm == {(kind, part): float(n) for kind, m in memory.items()
                   for part, n in m.items()}
    assert hbm[("train", "counted")] > 0 and hbm[("train", "alias")] > 0
    seconds = _by_labels(snap["ff_step_compile_seconds"], "kind", "phase")
    assert set(seconds) == {(k, p) for k in ("train", "superstep")
                            for p in ("lower", "compile")}
    assert all(v > 0 for v in seconds.values())
    for kind in ("train", "superstep"):
        (rec,) = obstrace.programs(kind)
        assert seconds[(kind, "lower")] == rec.lower_s
        assert seconds[(kind, "compile")] == rec.compile_s
    assert _by_labels(snap["ff_step_programs_loaded_total"], "kind") == {
        ("train",): 0.0, ("superstep",): 0.0}
    # the gauge that was there stays as it was
    assert _by_labels(snap["ff_step_fresh_outputs"], "kind") == {
        ("train",): 1.0, ("superstep",): 1.0}
    # one collector however many programs were compiled: no sample twice
    assert again["ff_step_hbm_bytes"] == snap["ff_step_hbm_bytes"]


def test_obs_off_a_compile_reads_nothing_from_the_program(records):
    assert not obsmetrics.enabled()
    obsmetrics.registry().reset()      # no collector an earlier test left
    assert obsmetrics.gauge("ff_step_hbm_bytes") is obsmetrics.NULL_GAUGE
    assert obsmetrics.counter("ff_x_total") is obsmetrics.NULL_COUNTER
    assert obsmetrics.histogram("ff_x_seconds") is obsmetrics.NULL_HISTOGRAM
    model = _dlrm()
    program = _Executable()
    got = model._cached_compile("train", "key", lambda: _Lowered(program))
    assert got is program and program.reads == []
    (rec,) = obstrace.programs()
    assert (rec.kind, rec.key, rec.executable, rec.loaded) == (
        "train", "key", program, False)
    assert rec.lower_s >= 0 and rec.compile_s >= 0
    assert obsmetrics.registry().collect() == {} and program.reads == []
    # on: the text once a compile (`ff_step_fresh_outputs`), the analysis
    # only when someone scrapes
    with obsmetrics.override(True):
        obsmetrics.registry().reset()
        model._cached_compile("train", "key", lambda: _Lowered(program))
        assert program.reads == ["as_text"]
        snap = obsmetrics.registry().collect()
    assert program.reads == ["as_text", "memory_analysis"]
    # a program that gives no analysis still says what it cost to build
    assert "ff_step_hbm_bytes" not in snap
    assert len(snap["ff_step_compile_seconds"]["samples"]) == 2


def test_a_program_the_compile_cache_hands_over_is_noted_as_loaded(records):
    program = _Executable()

    class Cache:
        puts = 0

        def exec_key(self, kind, model, shape_key):
            return f"{kind}-{shape_key}"

        def get(self, key, devices):
            return program

        def put(self, key, executable):
            Cache.puts += 1

    model = _dlrm()
    model._compile_cache = Cache()

    def never():
        raise AssertionError("a loaded program is not lowered")

    assert model._cached_compile("superstep", "k", never) is program
    (rec,) = obstrace.programs("superstep")
    assert rec.loaded and rec.lower_s == 0.0 and rec.compile_s >= 0
    assert Cache.puts == 0
    # `fresh` skips the lookup: built, stored, and the record says so
    built = _Executable()
    assert model._cached_compile("superstep", "k", lambda: _Lowered(built),
                                 fresh=True) is built
    (rec,) = obstrace.programs("superstep")
    assert rec.executable is built and not rec.loaded and Cache.puts == 1
    assert list(obstrace.collect_step_programs())[-1] == (
        "ff_step_programs_loaded_total", {"kind": "superstep"}, 0)


# ---------------------------------------------------------------------
# fit() leaves its seconds and its bytes
# ---------------------------------------------------------------------
@pytest.mark.parametrize("family", ["dlrm", "nemotron_h"])
def test_fit_leaves_the_seconds_and_step_memory_the_bytes(records, family):
    model = _fit(family)
    (rec,) = obstrace.programs("train")
    assert rec.lower_s > 0 and rec.compile_s > 0 and not rec.loaded
    (executable,) = model._train_step_execs.values()
    assert rec.executable is executable
    memory = model.step_memory()
    assert set(memory) == {"train"}
    m = memory["train"]
    assert m["counted"] == (m["argument"] + m["output"] - m["alias"]
                            + m["temp"]) > 0
    # the parameters and the optimizer's state are donated: most of the
    # outputs take over an argument's buffer
    params = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree.leaves(model.params))
    assert m["alias"] >= params and m["argument"] >= m["alias"]
    assert m["temp"] > 0


def test_step_memory_before_any_program_is_empty():
    model = ff.FFModel(ff.FFConfig(batch_size=BS))
    assert model.step_memory() == {}
    assert _dlrm().step_memory() == {}
