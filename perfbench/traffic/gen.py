"""The benchmark's own traffic generator: one general reader of the mix
files beside it (`<mix>.json`), seeded from `--seed` and nothing else.

A copy, on purpose, of what `dlrm_flexflow_tpu.models.dlrm.synthetic_batch`
and `data.dataloader.zipf_indices` draw (uniform or zipf ids with id 0
hottest, uniform dense features, 0/1 labels): later PRs may change the
program's generators, not the yardstick's. It imports nothing from the
program.

A mix file holds
  batch_per_chip   samples a chip trains per step
  ids              {"distribution": "uniform"} or
                   {"distribution": "zipf", "alpha": a}: how categorical ids
                   are drawn over the rows a table holds in the cell
  dataset_batches  batches per epoch; the set is staged once, as `fit`
                   stages it, and looped for the run
  feed             "staged" (the only feed the harness drives today)

A model family says which fields its model reads (`input_fields`); a field
is {"name", "kind", ...} with kind
  uniform_float    float32 in [0, 1), "shape" per sample
  binary           float32 0/1, "shape" per sample
  ids              int32 (n, tables, bag) from "rows" per table and "bag",
                   drawn as the mix's `ids` says
Each field draws from its own streams of (seed, position in the list,
chunk), so the same seed gives the same bytes and a new field moves no
other.
"""

from __future__ import annotations

import json
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FEEDS = ("staged",)
THREADS = 8


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        mix = json.load(f)
    if mix.get("feed") not in FEEDS:
        raise ValueError(f"mix {name!r}: feed {mix.get('feed')!r} is not "
                         f"one of {FEEDS}")
    if int(mix["batch_per_chip"]) < 1 or int(mix["dataset_batches"]) < 1:
        raise ValueError(f"mix {name!r}: batch_per_chip and dataset_batches "
                         f"must be >= 1")
    return mix


CHUNK = 65536     # samples a stream draws; fixes the bytes, not the threads


def zipf_cdf(rows: int, alpha: float) -> np.ndarray:
    """P(id <= k) for ids 0..rows-1 with P(id = k) ~ (k + 1) ** -alpha."""
    p = np.power(np.arange(1, rows + 1, dtype=np.float64), -alpha)
    cdf = np.cumsum(p)
    cdf /= cdf[-1]
    return cdf


def draw_ids(rng: np.random.Generator, rows: Sequence[int], n: int,
             bag: int, ids: dict, cdfs: Dict[int, np.ndarray]) -> np.ndarray:
    """(n, len(rows), bag) int32 ids, table t's in [0, rows[t]); `cdfs`
    holds the zipf CDF of every distinct table size."""
    out = np.empty((n, len(rows), bag), np.int32)
    for t, r in enumerate(rows):
        if ids["distribution"] == "uniform":
            out[:, t, :] = rng.integers(0, r, size=(n, bag), dtype=np.int32)
        else:
            draws = np.searchsorted(cdfs[r], rng.random(n * bag),
                                    side="right")
            out[:, t, :] = np.minimum(draws, r - 1).reshape(n, bag)
    return out


def generate(mix: dict, fields: List[dict], n: int,
             seed: int) -> Dict[str, np.ndarray]:
    """`n` samples of every field, as {field name: array}. Chunk c of field
    i is drawn from the stream of (seed, i, c), a few chunks at a time on
    threads (numpy draws outside the interpreter lock): a data set of
    gigabytes is part of every run's set-up."""
    kind = mix["ids"]["distribution"]
    if kind not in ("uniform", "zipf"):
        raise ValueError(f"unknown id distribution {kind!r}")
    out, cdfs = {}, {}
    for f in fields:
        if f["kind"] == "ids":
            out[f["name"]] = np.empty((n, len(f["rows"]), int(f["bag"])),
                                      np.int32)
            if kind == "zipf":      # built once per distinct table size
                for r in set(f["rows"]) - set(cdfs):
                    cdfs[r] = zipf_cdf(r, float(mix["ids"]["alpha"]))
        elif f["kind"] in ("uniform_float", "binary"):
            out[f["name"]] = np.empty((n,) + tuple(f["shape"]), np.float32)
        else:
            raise ValueError(f"field {f['name']!r}: unknown kind "
                             f"{f['kind']!r}")

    def draw(i, c):
        f, lo, hi = fields[i], c * CHUNK, min((c + 1) * CHUNK, n)
        rng = np.random.default_rng([int(seed), i, c])
        dst = out[f["name"]][lo:hi]
        if f["kind"] == "uniform_float":
            rng.random(dst.shape, dtype=np.float32, out=dst)
        elif f["kind"] == "binary":
            dst[...] = rng.integers(0, 2, size=dst.shape)
        else:
            dst[...] = draw_ids(rng, f["rows"], hi - lo, int(f["bag"]),
                                mix["ids"], cdfs)

    jobs = [(i, c) for i in range(len(fields))
            for c in range(-(-n // CHUNK))]
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        for done in [pool.submit(draw, i, c) for i, c in jobs]:
            done.result()
    return out
