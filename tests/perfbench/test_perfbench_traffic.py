"""The benchmark's own traffic generator: seeded from --seed only, and
sharing nothing with the program."""

import ast
import os

import numpy as np
import pytest

from perfbench.traffic import gen

FIELDS = [
    {"name": "dense", "kind": "uniform_float", "shape": [13]},
    {"name": "sparse", "kind": "ids", "rows": [1000, 3, 1, 50000], "bag": 2},
    {"name": "label", "kind": "binary", "shape": [1]},
]
UNIFORM = {"ids": {"distribution": "uniform"}}
ZIPF = {"ids": {"distribution": "zipf", "alpha": 1.05}}


@pytest.mark.parametrize("mix", [UNIFORM, ZIPF], ids=["uniform", "zipf"])
def test_same_seed_same_bytes(mix, monkeypatch):
    n = gen.CHUNK + 777          # more than one chunk a field
    a = gen.generate(mix, FIELDS, n, seed=5)
    monkeypatch.setattr(gen, "THREADS", 1)      # bytes, not threads
    b = gen.generate(mix, FIELDS, n, seed=5)
    c = gen.generate(mix, FIELDS, n, seed=6)
    for k in a:
        assert a[k].tobytes() == b[k].tobytes()
        assert a[k].tobytes() != c[k].tobytes()
    assert a["dense"].dtype == np.float32 and a["dense"].shape == (n, 13)
    assert a["sparse"].dtype == np.int32 and a["sparse"].shape == (n, 4, 2)
    assert set(np.unique(a["label"])) == {0.0, 1.0}
    assert 0 <= a["dense"].min() and a["dense"].max() < 1
    for t, rows in enumerate(FIELDS[1]["rows"]):
        assert 0 <= a["sparse"][:, t].min()
        assert a["sparse"][:, t].max() < rows


def test_zipf_is_skewed_towards_id_zero_and_uniform_is_not():
    n = 40000
    z = gen.generate(ZIPF, FIELDS, n, seed=1)["sparse"][:, 3].reshape(-1)
    u = gen.generate(UNIFORM, FIELDS, n, seed=1)["sparse"][:, 3].reshape(-1)
    counts = np.bincount(z, minlength=50000)
    assert counts[0] == counts.max() and counts[0] > 20 * counts[100:].max()
    # P(id = 0) = 1 / sum(k ** -1.05)
    p0 = 1 / np.sum(np.arange(1, 50001, dtype=np.float64) ** -1.05)
    assert abs(counts[0] / z.size - p0) < 0.01
    assert np.bincount(u, minlength=50000).max() < 20


def test_a_new_field_moves_no_other():
    a = gen.generate(UNIFORM, FIELDS, 100, seed=2)
    b = gen.generate(UNIFORM, FIELDS + [
        {"name": "extra", "kind": "binary", "shape": [2]}], 100, seed=2)
    assert all(a[k].tobytes() == b[k].tobytes() for k in a)


def test_mix_files_load_and_bad_ones_are_refused(tmp_path, monkeypatch):
    for name in ("b128_zipf", "b8192_uniform", "b3456_zipf"):
        mix = gen.load_mix(name)
        assert mix["name"] == name and mix["feed"] == "staged"
    with pytest.raises(ValueError):
        gen.generate({"ids": {"distribution": "pareto"}}, FIELDS, 4, 0)
    with pytest.raises(ValueError):
        gen.generate(UNIFORM, [{"name": "x", "kind": "text"}], 4, 0)
    monkeypatch.setattr(gen, "HERE", str(tmp_path))
    (tmp_path / "streamed.json").write_text(
        '{"feed": "streamed", "batch_per_chip": 8, "dataset_batches": 8}')
    with pytest.raises(ValueError):
        gen.load_mix("streamed")


def test_the_generator_imports_nothing_of_the_program():
    with open(os.path.join(gen.HERE, "gen.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0] if not node.level
                         else "<relative>")
    assert imported <= {"__future__", "json", "os", "concurrent", "typing",
                        "numpy"}
