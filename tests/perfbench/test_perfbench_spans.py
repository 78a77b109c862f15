"""From the program's names to numbers (perfbench/spanreduce.py and the
thirteen metrics of perfbench/spanreport.py on it): a trace written by hand
with exact expected numbers, and steps recorded on the v5e with the names
the chip's trace and the chip's compiler really give."""

import gzip
import json
import math
import os
from types import SimpleNamespace

import pytest

from perfbench import manifest as mf
from perfbench import spanreduce as sr
from perfbench import spanreport as groups
from perfbench import tracereduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NS = 1e-9
TOP = "jit(train_step)/jit(main)/"


def _run(rec):
    """As run.py hands a traced run to the readers that are there."""
    return SimpleNamespace(trace=tr.reduce(rec, rec["steps"]))


@pytest.fixture(scope="module")
def handmade():
    with open(os.path.join(DATA, "handmade_spans.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def chip():
    with gzip.open(os.path.join(DATA, "v5e_b128_local_spans.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_device_time_by_scope(handmade):
    """fusion.1 is clipped to 500, fusion.6 to 2000 and has no path (the
    map that names it belongs to a module the slice never launched); the
    collectives are left to their own metrics."""
    assert sr.device_by_scope(handmade) == pytest.approx({
        TOP + "jvp(ff.top_dense_0)/dot_general": 500 * NS,
        TOP + "ff.emb_concat/emb_gather/pallas_call": 1000 * NS,
        TOP + "transpose(jvp(ff.top_dense_0))/dot_general": 1400 * NS,
        TOP + "ff.update.emb_concat/dedup/sort": 300 * NS,
        "": 2000 * NS})
    by_group = {n: groups.group_ms(handmade, n) for n in
                ("mlp", "interaction", "embedding_fwd", "embedding_update",
                 "dense_update", "unscoped")}
    assert by_group == pytest.approx({
        "mlp": 1900e-6 / 2, "interaction": 0, "embedding_fwd": 1000e-6 / 2,
        "embedding_update": 300e-6 / 2, "dense_update": 0,
        "unscoped": 2000e-6 / 2})
    assert groups.kernel_ms(handmade, "emb_gather") == pytest.approx(500e-6)
    assert groups.kernel_ms(handmade, "emb_scatter_add") == 0


@pytest.mark.parametrize("path, op, family", [
    (TOP + "transpose(jvp(ff.top_dense_1))/mul", "top_dense_1", "mlp"),
    # the outermost `ff.` component decides, whatever JAX puts around it
    ("jit(train_superstep)/while/body/closed_call/ff.update.emb_concat/"
     "dedup/jit(ff.inner)/sort", "update.emb_concat", "embedding_update"),
    (TOP + "jvp(ff.emb_stack)/vmap(gather)/jit(_take)/gather", "emb_stack",
     "embedding_fwd"),
    (TOP + "jvp(ff.emb_flatten)/reshape", "emb_flatten", "interaction"),
    (TOP + "add", "", "unscoped"),
])
def test_a_path_goes_to_its_outermost_op_and_that_to_its_group(path, op,
                                                                family):
    assert sr.group(path) == op
    assert groups.group_of(path) == family


def test_host_time_by_span(handmade):
    """Clipped to the slice (the epoch's end began before it), arguments
    dropped from the name, a nested span under both names, JAX's own
    events left out."""
    assert sr.host_by_span(handmade) == pytest.approx({
        "fit/epoch_end": 100 * NS, "train/dispatch": (1200 + 2180) * NS,
        "train/step": 800 * NS, "train/superstep": 1500 * NS,
        "fit/throttle": 1800 * NS})


def test_idle_time_by_span(handmade):
    """The chip idles in [1500,2000] (middle inside train/step inside
    train/dispatch: the outermost takes it), [4000,4500] (straddles the
    throttle and the next dispatch: its middle decides, whole),
    [6500,6600] (the second dispatch) and [6900,9000] (np.asarray is
    JAX's event, not a span of the program's: in none)."""
    assert sr.idle_by_span(handmade) == pytest.approx({
        "train/dispatch": (500 + 100) * NS, "fit/throttle": 500 * NS,
        "": 2100 * NS})


@pytest.mark.parametrize("fixture", ["handmade", "chip"])
def test_the_two_identities(fixture, request):
    """The new numbers add up to the old, as the readers that are there
    give them: the six `ops` metrics to XLA's plus Mosaic's time, idle by
    span to the host gap."""
    rec = request.getfixturevalue(fixture)
    new = groups.metrics(rec)
    old = {m.NAME: m.read(_run(rec))
           for m in mf.layer_metrics("dlrm_any.cell")
           if m.NAME in ("xla_ms_per_step", "mosaic_ms_per_step",
                         "host_gap_ms_per_step")}
    assert sum(new[n] for n in groups.OPS) == pytest.approx(
        old["xla_ms_per_step"] + old["mosaic_ms_per_step"], rel=1e-9)
    assert 1e3 * sum(sr.idle_by_span(rec).values()) / rec["steps"] == (
        pytest.approx(old["host_gap_ms_per_step"], rel=1e-9))
    out = groups.report(rec)
    assert out["ops_sum_ms"] == pytest.approx(out["xla_plus_mosaic_ms"])
    assert out["idle_by_span_sum_ms"] == pytest.approx(out["host_gap_ms"])


@pytest.mark.parametrize("name", list(groups.METRICS))
def test_every_new_metric_on_the_chips_trace(chip, name):
    """On steps recorded on the v5e (dlrm_terabyte.b128_local, this PR's
    program): a value, finite, not negative; None where the trace carries
    no scope map and no span (a commit before the names). A kernel is still
    found there if the instruction bears its name, as the chip's compiler
    has it (`emb_gather.1`)."""
    layer, source, read = groups.METRICS[name]
    value = read(chip)
    assert value is not None and math.isfinite(value) and value >= 0
    assert groups.metrics(chip)[name] == value
    assert source in mf.SOURCES and layer in {
        m["layer"] for m in mf.load()["per_layer"]}
    unnamed = dict(chip, scopes={}, host=[
        e for e in chip["host"] if not e[0].startswith(sr.SPAN_PREFIXES)])
    if layer == "kernels":
        assert read(unnamed) == pytest.approx(value)
    else:
        assert read(unnamed) is None and name not in groups.metrics(unnamed)
    if name == "embedding_update_ms_per_step":
        assert "ff.update." in groups.table(chip)
        assert "no scope map" in groups.table(unnamed)
