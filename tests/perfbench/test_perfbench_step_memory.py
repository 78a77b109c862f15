"""The three readers of the step program's own record (ISSUE 34):
`step_hbm_gib`, `step_temp_hbm_gib`, `step_program_compile_s`. A reader
takes the process's `obs.trace.programs()` and, for its note, the run's
`memory` and `timings`: so a tiny in-process `fit()` of a DLRM and of a
language model through their families' `build` is all it needs (the
rehearsals of `test_perfbench_run.py` and of the three families' files walk
the readers through `run.py` as they stand)."""

from types import SimpleNamespace

import pytest

from dlrm_flexflow_tpu.obs import trace as obstrace
from perfbench import manifest as mf
from perfbench.layer_metrics import (step_hbm_gib, step_program_compile_s,
                                     step_temp_hbm_gib)
from perfbench.traffic import gen

READERS = (step_hbm_gib, step_temp_hbm_gib, step_program_compile_s)
DLRM = {
    "name": "dlrm_tiny", "family": "dlrm", "deployment": {"chips": 1},
    "table_rows": [400, 30, 7, 120], "embedding_dim": 32, "bag_size": 1,
    "mlp_bot": [6, 32, 32], "mlp_top": [42, 64, 1], "interaction": "dot",
    "loss": "mean_squared_error", "optimizer": {"type": "sgd", "lr": 0.01},
    "compute_dtype": "float32"}
NEMOTRON = {
    "name": "nemotron_tiny", "family": "nemotron_h",
    "hidden_size": 64, "num_hidden_layers": 3,
    "hybrid_override_pattern": "ME*", "layer_norm_epsilon": 1e-5,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "use_conv_bias": True, "time_step_min": 0.001, "time_step_max": 0.1,
    "time_step_floor": 0.0001, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "n_routed_experts": 4, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "balance_rate": 0.001,
    "vocab_size": 128, "expert_offset": 8, "seq_len": 40,
    "published": {"num_hidden_layers": 52, "n_routed_experts": 16,
                  "vocab_size": 1024},
    "loss": "sparse_categorical_crossentropy",
    "optimizer": {"type": "adam", "alpha": 1e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8},
    "compute_dtype": "float32", "deployment": {"chips": 4}}
BATCH = 2


@pytest.fixture
def records():
    """An empty store for the test; what other tests noted comes back."""
    with obstrace._PROGRAMS_LOCK:
        saved = list(obstrace._PROGRAMS.items())
        obstrace._PROGRAMS.clear()
    yield
    with obstrace._PROGRAMS_LOCK:
        obstrace._PROGRAMS.clear()
        obstrace._PROGRAMS.update(saved)


def _run(peak_bytes=None):
    return SimpleNamespace(memory={"peak_bytes": peak_bytes},
                           timings={"step_compile_s": 9.5})


def _fit(config, batch=BATCH):
    family = mf.load_family(config["family"])
    rows = family.held_table_rows(config, 1)
    model, _ = family.build(config, rows, batch, 1, seed=3)
    data = gen.generate({"ids": {"distribution": "zipf", "alpha": 1.05}},
                        family.input_fields(config, rows), 4 * batch, seed=3)
    x, y = family.fit_arrays(data)
    model.fit(x, y, epochs=1, verbose=False)
    return model


def test_the_readers_and_the_table_agree():
    man = mf.load()
    assert mf.lint(man) == []
    entries = {m["name"]: m for m in man["per_layer"]}
    # appended, in the issue's order, and reported in every cell
    assert [m["name"] for m in man["per_layer"]][-3:] == [
        r.NAME for r in READERS]
    for reader in READERS:
        entry = entries[reader.NAME]
        assert "workloads" not in entry and reader.CELLS == "*"
        assert reader.LAYER == entry["layer"] == "graph_compile"
        assert (entry["unit"], entry["better"], entry["source"],
                entry["moves"]) == (reader.UNIT, reader.BETTER,
                                    reader.SOURCE, reader.MOVES)
    assert (step_hbm_gib.MOVES, step_temp_hbm_gib.MOVES,
            step_program_compile_s.MOVES) == ("peak_hbm_gib", "peak_hbm_gib",
                                              "setup_s")
    assert step_program_compile_s.SOURCE == "program_span"
    for cell in man["workloads"]:
        found = {mod.NAME for mod in mf.layer_metrics(cell["name"])}
        assert {r.NAME for r in READERS} <= found


def test_no_record_no_value(records):
    run = _run()
    assert [r.read(run) for r in READERS] == [None, None, None]
    # the notes are printed whatever the readers found
    assert "no step program" in step_hbm_gib.note(run)
    assert step_program_compile_s.note(run).startswith("0 step program(s)")
    # a record whose executable gives no analysis: its seconds alone
    obstrace.note_program("train", object(), key="k", lower_s=0.25,
                          compile_s=1.5)
    obstrace.note_program("eval", object(), key="k", lower_s=9.0,
                          compile_s=9.0)          # no step program
    assert step_hbm_gib.read(run) is None
    assert step_temp_hbm_gib.read(run) is None
    assert step_program_compile_s.read(run) == 1.75


def test_a_program_without_the_record_reports_nothing(records, monkeypatch):
    """The parent of this PR has `obs.trace` and no `programs()`: the
    readers, laid over it, return nothing and do not raise."""
    obstrace.note_program("train", object(), key="k", compile_s=1.0)
    monkeypatch.delattr(obstrace, "programs")
    monkeypatch.delattr(obstrace, "program_memory")
    run = _run()
    assert [r.read(run) for r in READERS] == [None, None, None]
    assert isinstance(step_hbm_gib.note(run), str)
    assert isinstance(step_program_compile_s.note(run), str)


@pytest.mark.parametrize("config", [DLRM, NEMOTRON],
                         ids=["dlrm", "nemotron_h"])
def test_the_readers_after_a_tiny_fit(records, config):
    model = _fit(config)
    run = _run(peak_bytes=123_456_789)
    (rec,) = obstrace.programs("train")
    memory = model.step_memory()["train"]
    assert step_hbm_gib.read(run) == memory["counted"] / 2**30
    assert step_temp_hbm_gib.read(run) == memory["temp"] / 2**30
    assert step_hbm_gib.read(run) >= step_temp_hbm_gib.read(run) > 0
    assert step_program_compile_s.read(run) == rec.lower_s + rec.compile_s > 0
    note = step_hbm_gib.note(run)
    for part, n in memory.items():
        assert f"{part} {n:,}" in note
    assert "123,456,789" in note
    note = step_program_compile_s.note(run)
    assert note.startswith("1 step program(s): lower ")
    assert "0 loaded" in note and "9.5" in note


def test_the_largest_program_is_read_and_every_second_counted(records):
    small = _fit(DLRM)
    large = _fit(dict(DLRM, table_rows=[40_000, 30, 7, 120]), batch=4)
    one, two = obstrace.programs("train")
    obstrace.note_program("superstep", two.executable, key="s", lower_s=0.5,
                          compile_s=2.0, loaded=True)
    run = _run()
    counted = large.step_memory()["train"]["counted"]
    assert counted > small.step_memory()["train"]["counted"]
    assert step_hbm_gib.read(run) == counted / 2**30
    assert step_temp_hbm_gib.read(run) == (
        large.step_memory()["train"]["temp"] / 2**30)
    assert step_program_compile_s.read(run) == pytest.approx(
        one.lower_s + one.compile_s + two.lower_s + two.compile_s + 2.5)
    assert "3 step program(s)" in step_program_compile_s.note(run)
    assert "1 loaded" in step_program_compile_s.note(run)
