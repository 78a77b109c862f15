"""BENCHMARK.json and the files it names: the lint the driver's contract
implies, run over the committed table and over broken copies of it."""

import copy
import json
import os

import pytest

from perfbench import manifest as mf

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_the_committed_manifest_is_clean():
    assert mf.lint(mf.load()) == []


def test_every_cell_finds_its_files_by_name():
    man = mf.load()
    for cell in man["workloads"]:
        config = mf.load_config(man, cell["config"])
        assert config["name"] == cell["config"]
        family = mf.load_family(config["family"])
        assert callable(family.build) and callable(family.verify)
        readers = mf.layer_metrics(cell["name"])
        assert {r.NAME for r in readers} >= {
            "device_idle_share", "programs_per_step", "setup_init_s"}
        assert all(r.MOVES in {m["name"] for m in man["end_to_end"]}
                   for r in readers)


def _four_chips(man):
    man["workloads"][0]["chips"] = 4
    man["workloads"][1]["chips"] = 4


def _moves_nothing(man):
    man["per_layer"][0]["moves"] = "step_ms"


def _bad_name(man):
    man["workloads"][0]["name"] = "-starts with a dash"


def _pair_twice(man):
    man["workloads"].append(dict(man["workloads"][0], name="again"))


def _no_traffic_file(man):
    man["workloads"][0]["traffic"] = "no_such_mix"


def _loose_bound(man):
    man["end_to_end"][0]["bound"] = 0.2


def _no_setup(man):
    man["end_to_end"] = [m for m in man["end_to_end"]
                         if m["name"] != "setup_s"]
    man["per_layer"] = [m for m in man["per_layer"]
                        if m["moves"] != "setup_s"]


def _unused_config(man):
    man["configs"].append(dict(man["configs"][0], name="spare",
                               file="perfbench/configs/spare.json"))


def _undeclared_reader(man):
    man["per_layer"] = man["per_layer"][1:]


def _long_run(man):
    man["run_seconds"] = 52


def _extra_key(man):
    man["notes"] = "x"


@pytest.mark.parametrize("breakage", [
    _four_chips, _moves_nothing, _bad_name, _pair_twice, _no_traffic_file,
    _loose_bound, _no_setup, _unused_config, _undeclared_reader, _long_run,
    _extra_key], ids=lambda f: f.__name__.lstrip("_"))
def test_lint_refuses(breakage):
    man = copy.deepcopy(mf.load())
    breakage(man)
    assert mf.lint(man) != []


def test_lint_refuses_a_layer_name_with_spaces():
    # the driver refused "graph + compile": a layer is named like a metric
    man = copy.deepcopy(mf.load())
    man["per_layer"][0]["layer"] = "graph + compile"
    assert [m for m in mf.lint(man) if "layer 'graph + compile'" in m]
    assert {m["layer"] for m in mf.load()["per_layer"]} == {
        "graph_compile", "init", "training_loop", "ops", "kernels", "device"}


def test_peaks_are_the_published_ones_and_never_a_default():
    v5e = mf.load_peaks("TPU v5 lite")
    assert (v5e["bf16_flops_per_s"], v5e["int8_ops_per_s"], v5e["hbm_bytes"],
            v5e["hbm_bytes_per_s"], v5e["ici_bits_per_s"]) == (
        197e12, 393e12, 16e9, 819e9, 1600e9)
    assert "cloud" in v5e["source"].lower()
    for kind in ("cpu", "TPU v4", "_doc"):
        with pytest.raises(KeyError):
            mf.load_peaks(kind)


def test_config_files_state_their_cut():
    man = mf.load()
    for entry in man["configs"]:
        with open(os.path.join(REPO, entry["file"])) as f:
            body = json.load(f)
        assert {"source", "family", "reduced", "assumed", "departures",
                "deployment"} <= set(body)
        # no width may ever be listed as cut
        assert not [k for k in body["reduced"]
                    if k.endswith(("_dim", "_rank")) or "mlp" in k]
