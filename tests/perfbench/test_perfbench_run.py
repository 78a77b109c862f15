"""`perfbench/run.py` as a command: it refuses the CPU, and a new cell is
new files plus entries, with no existing file of the benchmark touched."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    env.pop("XLA_FLAGS", None)       # the suite's eight virtual devices
    return subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py"), *args],
        cwd=root, env=env, text=True, capture_output=True, timeout=600)


def test_a_cpu_run_exits_nonzero_and_prints_no_metric():
    p = _run(REPO, "--workload", "dlrm_random.b8192", "--seed", "0",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr and "no result" in p.stderr
    assert "platform=cpu" in p.stdout           # it names what it found
    assert '"metrics"' not in p.stdout and "samples_per_s" not in p.stdout


def test_an_unknown_cell_is_an_error():
    p = _run(REPO, "--workload", "no.such.cell", "--rehearse")
    assert p.returncode != 0 and "no.such.cell" in p.stderr
    assert '"metrics"' not in p.stdout


def _digests(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            path = os.path.join(dirpath, f)
            with open(path, "rb") as fh:
                out[path] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_a_cell_is_added_with_new_files_and_entries_only(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(root, "tests", "perfbench"))
    before = _digests(os.path.join(root, "perfbench"))
    pb = os.path.join(root, "perfbench")

    def write(rel, text):
        path = os.path.join(pb, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)

    # one new file of each kind ...
    write("configs/dlrm_small.json", json.dumps({
        "name": "dlrm_small", "family": "dlrm_twin",
        "source": "https://example.org/a-small-dlrm", "reduced": [],
        "assumed": {}, "departures": [], "deployment": {"chips": 4},
        "table_rows": [4000, 30, 7, 1200], "embedding_dim": 32,
        "bag_size": 1, "mlp_bot": [6, 32, 32], "mlp_top": [42, 64, 1],
        "interaction": "dot", "loss": "mean_squared_error",
        "optimizer": {"type": "sgd", "lr": 0.01},
        "compute_dtype": "bfloat16"}))
    write("traffic/b64_uniform.json", json.dumps({
        "name": "b64_uniform", "batch_per_chip": 64,
        "ids": {"distribution": "uniform"}, "dataset_batches": 8,
        "feed": "staged", "why": "a test's mix"}))
    write("models/dlrm_twin.py",
          '"""A second family, here the first under another name."""\n'
          "from perfbench.models.dlrm import *  # noqa: F401,F403\n")
    write("layer_metrics/slice_step_ms.py", '''"""Wall ms a traced step."""

NAME = "slice_step_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "training_loop"
MOVES = "samples_per_s_per_chip"
CELLS = "dlrm_small.*"


def read(run):
    t = run.trace
    return None if t is None else 1e3 * t["window_s"] / t["steps"]
''')
    # ... and entries in the table: a configuration, a cell on four chips,
    # the new metric, and the exchange's metrics, which only such a cell has
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    cell = "dlrm_small.b64_x4"
    man["configs"].append({
        "name": "dlrm_small", "source": "https://example.org/a-small-dlrm",
        "file": "perfbench/configs/dlrm_small.json", "reduced": [],
        "why": "a test's configuration"})
    man["workloads"].append({
        "name": cell, "config": "dlrm_small", "traffic": "b64_uniform",
        "chips": 4, "why": "a test's cell: rows split over four chips"})
    e2e = "samples_per_s_per_chip"
    man["per_layer"] += [
        {"name": "slice_step_ms", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "training_loop", "moves": e2e,
         "workloads": [cell]},
        {"name": "collective_ms_per_step", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "exchange", "moves": e2e,
         "workloads": [cell]},
        {"name": "collective_exposed_ms_per_step", "unit": "ms",
         "better": "lower", "source": "device_trace", "layer": "exchange",
         "moves": e2e, "workloads": [cell]}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    after = _digests(pb)
    assert {k: after[k] for k in before} == before      # nothing edited

    lint = subprocess.run(
        [sys.executable, "-c", "from perfbench import manifest as m; "
         "print(m.lint(m.load()))"], cwd=root, text=True,
        capture_output=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert lint.stdout.strip() == "[]", lint.stdout + lint.stderr

    p = _run(root, "--workload", cell, "--seed", "4", "--seconds", "1",
             "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert lines[-1] == "rehearsal passed"          # and no result line
    (rehearsed,) = [ln for ln in lines if ln.startswith("rehearsed: ")]
    result = json.loads(rehearsed[len("rehearsed: "):])
    assert result["correct"] and result["failed"] == 0
    assert result["device"]["count"] == 4
    metrics = result["metrics"]
    assert metrics["slice_step_ms"]["value"] > 0
    assert metrics["collective_ms_per_step"]["value"] > 0
    assert (metrics["collective_exposed_ms_per_step"]["value"]
            <= metrics["collective_ms_per_step"]["value"])
    assert metrics["programs_per_step"]["value"] >= 1
    assert "samples_per_s_per_chip" not in metrics   # a traced run
    assert result["breakdown"]["device_ops"]
