"""Loss functions.

Parity with the reference Loss (reference: include/loss_functions.h:39-41,
src/runtime/loss_functions.cu:37-73): sparse categorical cross-entropy,
categorical cross-entropy, and mean-squared-error, all scaled by
1/global_batch (the reference writes logit gradients scaled by
`scale_factor = 1.0f / global_batch`; here the same scaling falls out of
taking `mean` over the batch and letting jax.grad differentiate).

The reference computes loss *gradients* only (backward-only task); the loss
value itself is reported via Metrics. We expose scalar loss values (needed by
jax.grad) and get the identical gradients by construction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LOSS_CATEGORICAL_CROSSENTROPY = "categorical_crossentropy"
LOSS_SPARSE_CATEGORICAL_CROSSENTROPY = "sparse_categorical_crossentropy"
LOSS_MEAN_SQUARED_ERROR = "mean_squared_error"
# aliases accepted by the python frontend of the reference
_ALIASES = {
    "mse": LOSS_MEAN_SQUARED_ERROR,
    "mean_squared_error_avg_reduce": LOSS_MEAN_SQUARED_ERROR,
    "cce": LOSS_CATEGORICAL_CROSSENTROPY,
    "scce": LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
}


def canonical_loss(name: str) -> str:
    name = name.lower()
    name = _ALIASES.get(name, name)
    if name not in (LOSS_CATEGORICAL_CROSSENTROPY,
                    LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                    LOSS_MEAN_SQUARED_ERROR):
        raise ValueError(f"unknown loss type: {name}")
    return name


def row_weights(weights, rows: int):
    """`weights`, one a row of a sample, laid over the `rows` rows of a
    batch: fp32 (rows,)."""
    w = jnp.asarray(weights, jnp.float32).reshape(-1)
    return jnp.tile(w, rows // w.size)


def sparse_categorical_crossentropy(logits, labels, weights=None):
    """labels: any int shape whose element count equals the number of logit
    rows (e.g. [batch], [batch, 1], or [batch, seq] against folded
    [batch*seq, classes] logits as in NMT); logits: float[..., classes].

    Reference kernel sparse_categorical_crossentropy_loss_backward writes
    softmax(logits) - onehot(label); grad of this fn reproduces it.

    `weights`, one a row of a sample (`compile(loss_weights=)`), make the
    loss mean(w_r * nll_r): a sum of terms, each a mean over its own rows
    (0 masks a row). Weights of mean 1 keep an unweighted loss's scale.
    """
    logits2 = logits.reshape(-1, logits.shape[-1])
    labels = labels.astype(jnp.int32).reshape(-1)
    logp = jax.nn.log_softmax(logits2.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    if weights is not None:
        nll = nll * row_weights(weights, nll.shape[0])
    return jnp.mean(nll)


def categorical_crossentropy(logits, labels):
    """Dense one-hot labels float[batch, classes]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.sum(labels * logp, axis=-1))


def mean_squared_error(preds, labels):
    """Reference mseloss_backward: grad = 2*(pred-label)/batch ⇒ loss = mean
    over batch of the summed squared error per sample."""
    d = preds.astype(jnp.float32) - labels.astype(jnp.float32)
    per_sample = jnp.sum(jnp.square(d).reshape(d.shape[0], -1), axis=-1)
    return jnp.mean(per_sample)


def loss_fn(loss_type: str):
    loss_type = canonical_loss(loss_type)
    return {
        LOSS_SPARSE_CATEGORICAL_CROSSENTROPY: sparse_categorical_crossentropy,
        LOSS_CATEGORICAL_CROSSENTROPY: categorical_crossentropy,
        LOSS_MEAN_SQUARED_ERROR: mean_squared_error,
    }[loss_type]
