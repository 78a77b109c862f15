"""Training metrics.

Parity with the reference PerfMetrics (reference:
include/metrics_functions.h:26-40, src/runtime/metrics_functions.cu:57-262):
train_all / train_correct (accuracy), cce, sparse_cce, mse, rmse, mae.

TPU-native redesign: the reference accumulates per-partition metrics with
device atomics into a `PerfMetrics` struct returned as a Legion future, then
folds futures in a CPU task (model.cc:1182-1205) so metrics never block the
train loop. Here metrics are computed inside the jitted train step as sharded
reductions (XLA inserts the cross-chip psum) and returned, with the loss, as
ONE device array a dispatch (`pack_step_scalars`, read through `StepMetrics`);
asynchronous dispatch gives the same never-blocks property — the host only
syncs when it reads a value (fit's loss print, an anomaly policy).
"""

from __future__ import annotations

from collections.abc import Mapping
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from .losses import row_weights

METRICS_ACCURACY = "accuracy"
METRICS_CATEGORICAL_CROSSENTROPY = "categorical_crossentropy"
METRICS_SPARSE_CATEGORICAL_CROSSENTROPY = "sparse_categorical_crossentropy"
METRICS_MEAN_SQUARED_ERROR = "mean_squared_error"
METRICS_ROOT_MEAN_SQUARED_ERROR = "root_mean_squared_error"
METRICS_MEAN_ABSOLUTE_ERROR = "mean_absolute_error"

_ALIASES = {
    "acc": METRICS_ACCURACY,
    "mse": METRICS_MEAN_SQUARED_ERROR,
    "rmse": METRICS_ROOT_MEAN_SQUARED_ERROR,
    "mae": METRICS_MEAN_ABSOLUTE_ERROR,
    "cce": METRICS_CATEGORICAL_CROSSENTROPY,
    "scce": METRICS_SPARSE_CATEGORICAL_CROSSENTROPY,
}

ALL_METRICS = (METRICS_ACCURACY, METRICS_CATEGORICAL_CROSSENTROPY,
               METRICS_SPARSE_CATEGORICAL_CROSSENTROPY,
               METRICS_MEAN_SQUARED_ERROR, METRICS_ROOT_MEAN_SQUARED_ERROR,
               METRICS_MEAN_ABSOLUTE_ERROR)


def canonical_metrics(names: List[str]) -> List[str]:
    out = []
    for n in names:
        n = _ALIASES.get(n.lower(), n.lower())
        if n not in ALL_METRICS:
            raise ValueError(f"unknown metric: {n}")
        out.append(n)
    return out


def compute_metrics(metrics: List[str], loss_type: str, preds, labels,
                    weights=None) -> Dict[str, jnp.ndarray]:
    """Per-batch *sums* (plus count) so epochs accumulate exactly like the
    reference's PerfMetrics::update (metrics_functions.cc). `weights` (one
    a row of a sample, as the loss takes them) weigh `sparse_cce`, so that
    the reported metric is the loss that was trained."""
    out: Dict[str, jnp.ndarray] = {}
    preds32 = preds.astype(jnp.float32)
    labels32 = labels.astype(jnp.float32)
    batch = preds.shape[0]
    out["train_all"] = jnp.asarray(batch, jnp.float32)

    sparse = "sparse" in loss_type
    for m in metrics:
        if m == METRICS_ACCURACY:
            if sparse:
                lab = labels.astype(jnp.int32).reshape(-1)
                correct = (jnp.argmax(preds32.reshape(-1, preds32.shape[-1]),
                                      axis=-1) == lab)
            elif preds32.shape[-1] == 1:
                # regression-style accuracy: rounded prediction (reference
                # metrics_functions.cu accuracy for MSE-style labels)
                correct = jnp.abs(preds32 - labels32).reshape(batch, -1).max(axis=-1) < 0.5
            else:
                correct = (jnp.argmax(preds32, axis=-1)
                           == jnp.argmax(labels32, axis=-1))
            out["train_correct"] = jnp.sum(correct.astype(jnp.float32))
        elif m == METRICS_SPARSE_CATEGORICAL_CROSSENTROPY:
            lab = labels.astype(jnp.int32).reshape(-1)
            logp = jnp.log(jnp.clip(preds32.reshape(-1, preds32.shape[-1]),
                                    1e-12, None))
            picked = jnp.take_along_axis(logp, lab[:, None], axis=-1)
            out["sparse_cce"] = -jnp.sum(
                picked if weights is None
                else picked[:, 0] * row_weights(weights, lab.shape[0]))
        elif m == METRICS_CATEGORICAL_CROSSENTROPY:
            logp = jnp.log(jnp.clip(preds32, 1e-12, None))
            out["cce"] = -jnp.sum(labels32 * logp)
        elif m == METRICS_MEAN_SQUARED_ERROR:
            out["mse"] = jnp.sum(
                jnp.square(preds32 - labels32).reshape(batch, -1).sum(-1))
        elif m == METRICS_ROOT_MEAN_SQUARED_ERROR:
            out["rmse"] = jnp.sum(jnp.sqrt(
                jnp.square(preds32 - labels32).reshape(batch, -1).sum(-1)))
        elif m == METRICS_MEAN_ABSOLUTE_ERROR:
            out["mae"] = jnp.sum(
                jnp.abs(preds32 - labels32).reshape(batch, -1).sum(-1))
    return out


def _exact_in_float32(dtype) -> bool:
    """Whether a float32 holds every value of `dtype` exactly: a bool, a
    float of at most 32 bits, an integer of at most 16."""
    dtype = np.dtype(dtype)
    return (dtype == np.bool_
            or (jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize <= 4)
            or (jnp.issubdtype(dtype, jnp.integer) and dtype.itemsize <= 2))


def pack_step_scalars(scalars: Dict[str, Any],
                      keys: Sequence[str]) -> jnp.ndarray:
    """The step's scalars (the `compute_metrics` sums, the loss, under a
    sentinel its flag and the gradient norm) as ONE float32 vector in the
    order of `keys`: a step program hands back one fresh buffer where it
    handed back one a scalar (each a device allocation the host waits
    for, ROADMAP S1). Traced inside the step; `keys` is the model's, so
    `StepMetrics` splits the vector on the host by the same order.
    Refused here, when the step is traced: a key missing or unasked for,
    a value that is no scalar, a dtype a float32 does not hold exactly
    (an int32 count would come back rounded)."""
    if set(scalars) != set(keys):
        raise ValueError(
            f"the step computed {sorted(scalars)}, the model's step "
            f"vector carries {list(keys)}")
    out = []
    for k in keys:
        v = jnp.asarray(scalars[k])
        if v.shape != ():
            raise TypeError(f"step metric {k!r} has shape {v.shape}: only "
                            f"scalars ride the step vector")
        if not _exact_in_float32(v.dtype):
            raise TypeError(f"step metric {k!r} is {v.dtype}: a float32 "
                            f"does not hold it exactly")
        out.append(v.astype(jnp.float32))
    return jnp.stack(out)


class StepMetrics(Mapping):
    """What one dispatch hands back, under the keys it always had
    (`loss`, the metric sums, under a sentinel `anomaly` and `grad_norm`;
    after a superstep also `per_step` and `superstep`): a read-only
    mapping over the step program's ONE output vector (`[n]`, after a
    superstep `[K, n]`). The vector comes to the host in one transfer the
    first time a value is read, and is kept; a step nobody looks at costs
    nothing. Indexing the device array a key would dispatch a program a
    key. Values are numpy scalars (`per_step`: `[K]` arrays), `anomaly`
    as a bool; `vector` is the device array itself, for whoever has to
    wait for the step without reading it (`_Throttle`)."""

    __slots__ = ("vector", "_index", "_last_of", "_extra", "_host")

    def __init__(self, vector, index: Dict[str, int],
                 last_of: Optional["StepMetrics"] = None, **extra):
        self.vector = vector
        self._index = index
        # a superstep's boundary-facing scalars are its LAST step's: the
        # last row of `last_of`'s host copy, the same one transfer
        self._last_of = last_of
        self._extra = extra
        self._host = None

    def host(self) -> np.ndarray:
        """The vector on the host: the transfer happens here, once."""
        if self._host is None:
            self._host = (np.asarray(self.vector) if self._last_of is None
                          else self._last_of.host()[-1])
        return self._host

    def __getitem__(self, key):
        if key in self._extra:
            return self._extra[key]
        v = self.host()[..., self._index[key]][()]
        return v != 0 if key == "anomaly" else v

    def __iter__(self):
        yield from self._index
        yield from self._extra

    def __len__(self):
        return len(self._index) + len(self._extra)


@dataclass
class PerfMetrics:
    """Host-side accumulator folding per-step metric sums, mirroring the
    reference UPDATE_METRICS_TASK fold (model.cc:1182-1205)."""

    sums: Dict[str, float] = field(default_factory=dict)

    def update(self, step_metrics: Dict[str, jnp.ndarray]):
        # accumulate device arrays without forcing a host sync — additions
        # dispatch asynchronously; only report()/summary_line() sync (the
        # reference's future-chain has the same property, model.cc:1182-1205)
        for k, v in step_metrics.items():
            prev = self.sums.get(k)
            self.sums[k] = v if prev is None else prev + v

    def reset(self):
        self.sums.clear()

    def _host_sums(self) -> Dict[str, float]:
        return {k: float(v) for k, v in self.sums.items()}

    def report(self) -> Dict[str, float]:
        self.sums = dict(self._host_sums())
        n = max(self.sums.get("train_all", 0.0), 1.0)
        out = {}
        for k, v in self.sums.items():
            if k == "train_all":
                out[k] = v
            elif k == "train_correct":
                out["accuracy"] = v / n
            else:
                out[k] = v / n
        return out

    def summary_line(self) -> str:
        rep = self.report()
        parts = []
        if "accuracy" in rep:
            parts.append(f"accuracy={rep['accuracy'] * 100.0:.2f}%"
                         f" ({int(self.sums.get('train_correct', 0))}"
                         f"/{int(self.sums.get('train_all', 0))})")
        for k in ("cce", "sparse_cce", "mse", "rmse", "mae"):
            if k in rep:
                parts.append(f"{k}={rep[k]:.6f}")
        return "[Metrics] " + " ".join(parts)
