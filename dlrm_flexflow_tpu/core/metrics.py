"""Training metrics.

Parity with the reference PerfMetrics (reference:
include/metrics_functions.h:26-40, src/runtime/metrics_functions.cu:57-262):
train_all / train_correct (accuracy), cce, sparse_cce, mse, rmse, mae.

TPU-native redesign: the reference accumulates per-partition metrics with
device atomics into a `PerfMetrics` struct returned as a Legion future, then
folds futures in a CPU task (model.cc:1182-1205) so metrics never block the
train loop. Here metrics are computed inside the jitted train step as sharded
reductions (XLA inserts the cross-chip psum) and returned as device arrays;
asynchronous dispatch gives the same never-blocks property — the host only
syncs when it prints (utils/logging.py).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

import jax.numpy as jnp

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
