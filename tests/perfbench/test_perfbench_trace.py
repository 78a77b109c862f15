"""The reduction from a trace to numbers, on recorded traces: one written
by hand with exact expected numbers, one recorded on the v5e with the names
the chip's trace really gives, and the profiler's own file on the CPU."""

import glob
import gzip
import json
import os

import pytest

from perfbench import tracereduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NS = 1e-9


@pytest.fixture(scope="module")
def handmade():
    with open(os.path.join(DATA, "handmade_trace.json")) as f:
        rec = json.load(f)
    return tr.reduce(rec, rec["steps"])


@pytest.mark.parametrize("key, expected", [
    # chip 0: [1000,1500] [2000,4000] [4500,6500] [9000,11000] = 6500 busy;
    # chip 1: [1000,6000] and 1 ns at 7000 = 5001; averaged over the chips
    ("busy_s", (6500 + 5001) / 2 * NS),
    ("idle_share", 1 - (6500 + 5001) / 2 / 10000),
    ("window_s", 10000 * NS),
    # fusion.1 clipped to 500, fusion.5 1400, fusion.6 clipped to 2000;
    # chip 1: fusion.1 4000 and the ConcatBitcast custom call's 1 ns
    ("xla_s", (3900 + 4001) / 2 * NS),
    # the tpu_custom_call only, overlapping or not: durations, not a union
    ("mosaic_s", (1000 + 2000) / 2 * NS),
    # chip 0: all-to-all [3000,4000], all-reduce start..done [4500,6500];
    # chip 1: the async line's all-gather [5000,8000]
    ("collective_s", (3000 + 3000) / 2 * NS),
    # chip 0: all of the all-to-all, all-reduce minus fusion.5's 1400;
    # chip 1: [5000,8000] minus [5000,6000] and the 1 ns at 7000
    ("collective_exposed_s", (1600 + 1999) / 2 * NS),
    # launches that start inside the slice: 1 (one began before it, one
    # after) and 3
    ("programs", 2.0),
    ("steps", 2), ("devices", 2),
])
def test_handmade_trace_reduces_to_exact_numbers(handmade, key, expected):
    assert handmade[key] == pytest.approx(expected, rel=1e-12)


def test_handmade_breakdown(handmade):
    ops = dict(handmade["breakdown"]["device_ops"])
    assert ops["fusion.1 [xla]"] == pytest.approx((500 + 4000) / 2 * NS)
    assert ops["train_step.2 [mosaic] f32[64,128]{1,0}"] == pytest.approx(
        1500 * NS)
    assert ops["all-to-all.3 [collective]"] == pytest.approx(500 * NS)
    assert list(ops)[0] == "fusion.1 [xla]"            # longest first
    # chip 0 idles in [1500,2000], [4000,4500] and [6500,9000]; the host
    # was in np.asarray at the middle of the long gap, else just in the slice
    assert handmade["breakdown"]["idle_gaps"] == [
        ["np.asarray(jax.Array)", pytest.approx(2500 * NS)],
        ["perfbench/slice", pytest.approx(1000 * NS)]]


def test_interval_arithmetic():
    assert tr.union([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]
    assert tr.measure([(1, 4), (5, 7)]) == 5
    assert tr.subtract([(0, 10)], [(2, 3), (5, 11)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 2), (4, 6)], []) == [(0, 2), (4, 6)]
    assert tr.subtract([(1, 2)], [(0, 3)]) == []


@pytest.mark.parametrize("name, detail, klass", [
    ("all-to-all.4", "", "collective"),
    ("all-reduce-start.1", "", "collective"),
    ("collective-permute-done", "", "collective"),
    ("reduce-scatter.2", "", "collective"),
    ("all-reduce-scatter-fusion", "", "xla"),      # not an op of that name
    ("train_step.3", 'custom-call(...), custom_call_target="tpu_custom_call"',
     "mosaic"),
    ("custom-call.8", 'custom-call(...), custom_call_target="ConcatBitcast"',
     "xla"),
    ("fusion.3", "fusion(...), kind=kLoop", "xla"),
    ("sort.0", "", "xla"),
])
def test_classes_by_the_traces_own_names(name, detail, klass):
    assert tr.classify(name, detail) == klass
    assert tr.split_hlo(f"%{name} = {detail}") == (name, detail)


def test_the_trace_recorded_on_the_v5e():
    """Eight steps of dlrm_terabyte.b3456_local as the chip's profiler
    named them: two Pallas kernels a step, found by `tpu_custom_call`."""
    with gzip.open(os.path.join(DATA, "v5e_b3456_local_8steps.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    out = tr.reduce(rec, rec["steps"])
    ops = rec["devices"]["/device:TPU:0"]["ops"]
    mosaic = [n for n, _, _ in ops
              if tr.classify(n, rec["details"][n]) == "mosaic"]
    assert sorted(set(mosaic)) == ["train_step.2", "train_step.3"]
    assert len(mosaic) == 2 * rec["steps"]
    assert out["programs"] / out["steps"] == 1.0
    assert out["collective_s"] == 0.0 and out["devices"] == 1
    # 7.2 ms of kernels and 3.5 ms of XLA's ops in a 10.7 ms step, a chip
    # that never waits (my chip run, PR 22)
    assert 1e3 * out["mosaic_s"] / out["steps"] == pytest.approx(7.2, rel=.01)
    assert 1e3 * out["xla_s"] / out["steps"] == pytest.approx(3.5, rel=.01)
    assert out["busy_s"] == pytest.approx(out["mosaic_s"] + out["xla_s"])
    assert 0 <= out["idle_share"] < 0.01
    first = out["breakdown"]["device_ops"][0][0]
    assert first.startswith("train_step.2 [mosaic] f32[89856,128]")


def test_the_profilers_file_is_read(tmp_path):
    """A real .xplane.pb, recorded here on the CPU: the slice annotation
    gives the window, XLA's CPU ops stand in for a device's (a rehearsal's
    path; never a device number), and no annotation means nothing to read."""
    import jax
    import jax.numpy as jnp
    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    step(x).block_until_ready()
    recs = []
    for name in (tr.SLICE, "something/else"):
        out = str(tmp_path / name.replace("/", "_"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=opts)
        with jax.profiler.TraceAnnotation(name):
            for _ in range(4):
                step(x).block_until_ready()
        jax.profiler.stop_trace()
        (pb,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        recs.append(tr.load_xplane(pb))
    rec, nothing = recs
    assert nothing is None
    lo, hi = rec["window"]
    assert hi > lo and any(n == tr.SLICE for n, _, _ in rec["host"])
    out = tr.reduce(rec, 4)
    assert out["programs"] / out["steps"] == 1.0
    assert 0 < out["busy_s"] <= out["window_s"]
    assert any("dot" in name for name, _ in out["breakdown"]["device_ops"])
