"""`BENCHMARK.json` and the files it names: how the harness finds a cell's
configuration, traffic mix, model family and layer metrics by name alone,
and the lint the tests run over the whole table.

Adding a configuration, a mix, a family, a layer metric or a cell is adding
files and one entry; nothing here is edited for it.
"""

from __future__ import annotations

import fnmatch
import glob
import importlib
import json
import os
import re
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[c['name'] for c in manifest['workloads']]}")


def load_config(manifest: dict, name: str, root: str = ROOT) -> dict:
    (entry,) = [c for c in manifest["configs"] if c["name"] == name]
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def load_family(name: str):
    return importlib.import_module(f"perfbench.models.{name}")


def layer_metrics(cell_name: str) -> list:
    """Every reader under layer_metrics/ whose `CELLS` pattern matches."""
    out = []
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics",
                                              "*.py"))):
        stem = os.path.basename(path)[:-3]
        if stem.startswith("_"):
            continue
        mod = importlib.import_module(f"perfbench.layer_metrics.{stem}")
        if fnmatch.fnmatchcase(cell_name, mod.CELLS):
            out.append(mod)
    return out


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device_kind {device_kind!r} is not in perfbench/peaks.json: "
            f"add its published peaks with their source (never a default)")
    return table[device_kind]


# --------------------------------------------------------------------------
# lint: the parts of the contract a file can break before any run
# --------------------------------------------------------------------------
def lint(manifest: dict, root: str = ROOT) -> List[str]:
    bad: List[str] = []

    def check(ok, msg):
        if not ok:
            bad.append(msg)

    check(set(manifest) == KEYS, f"keys {sorted(manifest)} != {sorted(KEYS)}")
    check(os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024,
          "BENCHMARK.json is over 64 KiB")
    paths = manifest["paths"]
    check(1 <= len(paths) <= 16, "1 to 16 paths")
    for p in paths:
        check(PATH.match(p) and not p.startswith("/")
              and ".." not in p.split("/"), f"path {p!r}")
        check(os.path.isdir(os.path.join(root, p)), f"path {p!r} missing")

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in paths)

    cmd = manifest["command"]
    check(1 <= len(cmd) <= 32 and all(isinstance(c, str) for c in cmd),
          "command is 1 to 32 strings")
    for c in cmd:
        check(not c.startswith("/") and ".." not in c.split("/"),
              f"command part {c!r} leaves the repo")
        if os.path.exists(os.path.join(root, c)):
            check(under_paths(c), f"command names {c!r} outside paths")
    check(isinstance(manifest["run_seconds"], int)
          and 1 <= manifest["run_seconds"] <= 51, "run_seconds in 1..51")

    names: List[str] = []

    def name_ok(n):
        check(bool(NAME.match(n)), f"name {n!r}")
        names.append(n)

    configs = manifest["configs"]
    check(1 <= len(configs) <= 24, "1 to 24 configs")
    files = [c["file"] for c in configs]
    check(len(set(files)) == len(files), "a config file is used twice")
    for c in configs:
        name_ok(c["name"])
        check(set(c) == {"name", "source", "file", "reduced", "why"},
              f"config {c['name']}: keys {sorted(c)}")
        check(under_paths(c["file"]) and PATH.match(c["file"]),
              f"config file {c['file']!r} outside paths")
        check(len(c["why"]) <= 200, f"config {c['name']}: why over 200")
        full = os.path.join(root, c["file"])
        if not os.path.isfile(full):
            bad.append(f"config file {c['file']!r} missing")
            continue
        with open(full) as f:
            body = json.load(f)
        check(body.get("source") == c["source"],
              f"config {c['name']}: source differs from its file's")
        check(body.get("reduced") == c["reduced"],
              f"config {c['name']}: reduced differs from its file's")
        fam = os.path.join(HERE, "models", f"{body.get('family')}.py")
        check(os.path.isfile(fam), f"config {c['name']}: no family file "
                                   f"{fam}")

    e2e = manifest["end_to_end"]
    check(1 <= len(e2e) <= 16, "1 to 16 end-to-end metrics")
    for m in e2e:
        name_ok(m["name"])
        check(m["better"] in ("lower", "higher"), f"{m['name']}: better")
        check(m["source"] in ("host_clock", "device_trace"),
              f"{m['name']}: an end-to-end metric is taken by the benchmark "
              f"itself (host_clock or device_trace)")
        check(0.01 <= m["bound"] <= 0.1, f"{m['name']}: bound in 1%..10%")
    e2e_names = {m["name"] for m in e2e}
    check("setup_s" in e2e_names, "setup_s is an end-to-end metric")

    cells = manifest["workloads"]
    check(2 <= len(cells) <= 24, "2 to 24 workloads")
    cell_names = {c["name"] for c in cells}
    pairs = [(c["config"], c["traffic"]) for c in cells]
    check(len(set(pairs)) == len(pairs), "a (config, traffic) pair twice")
    check({c["config"] for c in cells} == {c["name"] for c in configs},
          "every config is used by a cell, every cell's config exists")
    four = sum(1 for c in cells if c["chips"] == 4)
    check(four <= max(1, len(cells) // 4),
          f"{four} of {len(cells)} cells on four chips: over 25%")
    for c in cells:
        name_ok(c["name"])
        check(c["chips"] in (1, 4), f"cell {c['name']}: chips")
        check(len(c["why"]) <= 200, f"cell {c['name']}: why over 200")
        check(os.path.isfile(os.path.join(HERE, "traffic",
                                          c["traffic"] + ".json")),
              f"cell {c['name']}: no traffic file {c['traffic']}.json")

    per_layer = manifest["per_layer"]
    check(1 <= len(per_layer) <= 128, "1 to 128 per-layer metrics")
    for m in per_layer:
        name_ok(m["name"])
        check(m["better"] in ("lower", "higher"), f"{m['name']}: better")
        check(m["source"] in SOURCES, f"{m['name']}: source")
        check(bool(LAYER.match(m["layer"])),
              f"{m['name']}: layer {m['layer']!r} is not 1 to 64 letters, "
              f"digits, '_', '.' and '-'")
        check(m["moves"] in e2e_names,
              f"{m['name']}: moves {m['moves']!r} is no end-to-end metric")
        check(set(m.get("workloads", cell_names)) <= cell_names,
              f"{m['name']}: workloads name no cell")
        if m["name"].endswith("_roofline"):
            check(m["unit"] == "%", f"{m['name']}: a roofline share is in %")
    check(len(set(names)) == len(names), "a name is used twice")

    # the readers on disk and the table must say the same
    for cell in cells:
        declared = {m["name"]: m for m in per_layer
                    if cell["name"] in m.get("workloads", cell_names)}
        found = {mod.NAME: mod for mod in layer_metrics(cell["name"])}
        check(set(declared) == set(found),
              f"cell {cell['name']}: per_layer {sorted(declared)} != "
              f"readers {sorted(found)}")
        for n in set(declared) & set(found):
            d, mod = declared[n], found[n]
            check((d["unit"], d["better"], d["source"], d["layer"],
                   d["moves"]) == (mod.UNIT, mod.BETTER, mod.SOURCE,
                                   mod.LAYER, mod.MOVES),
                  f"per-layer metric {n}: BENCHMARK.json and its reader "
                  f"disagree")
    for dirpath, _, fs in os.walk(HERE):
        for f in fs:
            rel = os.path.relpath(os.path.join(dirpath, f), root)
            if "__pycache__" not in rel:
                check(bool(PATH.match(rel)), f"file name {rel!r}")
    return bad
