"""The DLRM family: what every DLRM configuration of the benchmark shares.

Four things live here and nowhere else in the benchmark:

1. how a configuration file becomes the system under test, through the
   program's own front door (`FFConfig` -> `FFModel` -> `build_dlrm` ->
   `dlrm_strategy` -> `compile` -> `init_layers(seed)`);
2. the plain reference: DLRM forward, mean-squared-error loss, gradients and
   one SGD step in straightforward `jax.numpy`, float32, matmul precision
   "highest", no kernel, no sharding, no line shared with the program;
3. which arrays of `model.params` the check reads, and how a (table, id)
   pair is found in the stored table (the one place the benchmark knows the
   program's packed layout);
4. the operations and bytes one training step needs, from the shapes.

Only `build` imports the program; the module itself imports without it.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The system runs its MLP matmuls in bf16 (8 mantissa bits) with fp32
# accumulation and keeps activations in fp32; the reference runs all in fp32.
# The loss is a mean over the batch, so the roundings largely average out:
# at tiny rows on the CPU the two differ by 4e-6 to 4e-5 relative. 2e-3 leaves
# fifty times that and is still a tenth of PR 21's 2e-2, which a loss of
# ~0.25 (a sigmoid against random 0/1 labels) passes whatever the model does.
LOSS_RTOL = 2e-3
# The loss barely moves in three SGD steps, so it cannot tell a scatter that
# wrote nothing from one that wrote the right rows; the stored rows can.
# System and reference apply the same fp32 update `row - lr * g` to the same
# rows, but the system's `g` came back through bf16 MLPs. Against this fp32
# reference that is noise with little bias (tiny rows, CPU, three steps: with
# fp32 compute the rows agree to 5e-4 of the largest update; with bf16 the
# worst element is off by 0.06-0.13 of it, 8% in L2, and the system's update
# projected on the reference's has slope 0.995; 39 runs on the v5e read
# slopes of 0.990-0.999 and a worst element of 0.04-0.20). So two tests:
# - the slope of the system's update on the reference's is 1 within
#   ROWS_SLOPE_TOL: noise averages out of it, fp32 rounding of the stored
#   value included (at batch 8,192 an update is a few ulps of a row), while an
#   update dropped, doubled or mis-scaled on 3% of the rows does not;
# - no element is off by more than ROWS_RTOL of the largest update plus one
#   fp32 rounding of the stored value a step. The worst of millions of
#   elements is an extreme value, so the bound leaves it room (a run that
#   fails the check fails a PR); it still refuses a large row whose update was
#   lost, and a table kept in bf16, fp8 or int8 is off by >= 1e-4 of a row,
#   orders above any update.
ROWS_SLOPE_TOL = 3e-2
ROWS_RTOL = 0.5

EMBEDDING_OPS = ("EmbeddingBagStacked", "EmbeddingBagConcat")
# the key of `fit`'s per-epoch report that is the training loss
LOSS_METRIC = "mse"


# --------------------------------------------------------------------------
# configuration -> sizes
# --------------------------------------------------------------------------
def held_table_rows(config: dict, chips: int) -> List[int]:
    """Rows of every table a cell on `chips` chips holds: the chips' share
    of the stated deployment, `ceil(rows * chips / deployment chips)`, as
    `parallel/alltoall.shard_row_ranges` splits a table in ceil-div blocks.
    A deployment no larger than the cell is held whole."""
    dep = int(config["deployment"]["chips"])
    if chips >= dep:
        return [int(r) for r in config["table_rows"]]
    return [math.ceil(int(r) * chips / dep) for r in config["table_rows"]]


def input_fields(config: dict, rows: List[int]) -> List[dict]:
    """What the traffic generator draws for this model (traffic/gen.py)."""
    return [
        {"name": "dense", "kind": "uniform_float",
         "shape": [int(config["mlp_bot"][0])]},
        {"name": "sparse", "kind": "ids", "rows": rows,
         "bag": int(config["bag_size"])},
        {"name": "label", "kind": "binary", "shape": [1]},
    ]


def fit_arrays(data: Dict[str, np.ndarray]):
    """(inputs, labels) as `FFModel.fit` takes them."""
    return {"dense": data["dense"], "sparse": data["sparse"]}, data["label"]


def build(config: dict, rows: List[int], batch: int, chips: int, seed: int):
    """The system under test, on `chips` devices, weights made on the
    device from the seed. Returns (model, timings) with the seconds of
    graph build + compile() and of init_layers()."""
    import time

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                               dlrm_strategy)

    opt = config["optimizer"]
    if opt["type"] != "sgd" or config["loss"] != "mean_squared_error":
        raise ValueError("the DLRM family's reference knows plain SGD and "
                         "mean_squared_error only")
    t0 = time.time()
    dcfg = DLRMConfig(
        embedding_size=list(rows),
        embedding_bag_size=int(config["bag_size"]),
        sparse_feature_size=int(config["embedding_dim"]),
        mlp_bot=list(config["mlp_bot"]), mlp_top=list(config["mlp_top"]),
        arch_interaction_op=config["interaction"])
    cfg = ff.FFConfig.parse_args(
        ["-b", str(batch), "--lr", str(opt["lr"]),
         "--compute-dtype", config["compute_dtype"]])
    model = ff.FFModel(cfg)
    build_dlrm(model, dcfg)
    # the hand-written plan: one chip holds the tables whole; several chips
    # split every table's rows over all of them and route by all-to-all
    strat = dlrm_strategy(model, dcfg, chips, row_shard=chips > 1)
    model.compile(ff.SGDOptimizer(lr=cfg.learning_rate),
                  config["loss"], ["mse"],
                  mesh=ff.make_mesh(num_devices=chips), strategies=strat)
    t1 = time.time()
    model.init_layers(seed)
    jax.block_until_ready(model.params)
    return model, {"build_s": t1 - t0, "init_s": time.time() - t1}


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------
def _mlp(x, layers, sigmoid_last):
    for i, (w, b) in enumerate(layers):
        x = x @ w + b
        if sigmoid_last and i == len(layers) - 1:
            x = jax.nn.sigmoid(x)
        else:
            x = jax.nn.relu(x)
    return x


def reference_loss(params, dense, inv, label, interaction: str):
    """DLRM forward and loss. `params` = {"bot": [(w, b), ...], "top":
    [(w, b), ...], "rows": (U, d)}; `inv` (batch, tables, bag) indexes the
    U distinct table rows the batch touches."""
    bottom = _mlp(dense, params["bot"], sigmoid_last=False)
    emb = jnp.sum(params["rows"][inv], axis=2)            # (b, T, d)
    if interaction == "cat":
        inter = jnp.concatenate(
            [bottom, emb.reshape(emb.shape[0], -1)], axis=1)
    elif interaction == "dot":
        x = jnp.concatenate([bottom[:, None, :], emb], axis=1)   # (b, F, d)
        z = jnp.einsum("bfd,bgd->bfg", x, x)
        i, j = np.tril_indices(x.shape[1], -1)     # the pairs with f > g
        inter = jnp.concatenate([bottom, z[:, i, j]], axis=1)
    else:
        raise ValueError(f"unknown interaction {interaction!r}")
    pred = _mlp(inter, params["top"], sigmoid_last=True)
    # mean over the batch of the squared error summed over a sample
    return jnp.mean(jnp.sum(jnp.square(pred - label), axis=-1))


@partial(jax.jit, static_argnames=("lr", "steps", "interaction"))
def reference_steps(params, dense, inv, label, *, lr: float, steps: int,
                    interaction: str):
    """`steps` plain SGD steps on one batch. Returns (loss before each
    step, params after the last, the rows' accumulated update). The update
    is carried beside the rows it started from, `rows = rows0 + update`, so
    that it keeps its own fp32 precision: at batch 8,192 one is a few ulps
    of a row, and an update rounded against the row would blur the slope
    the check takes."""
    losses = []
    rows0, update = params["rows"], jnp.zeros_like(params["rows"])
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            loss, grads = jax.value_and_grad(reference_loss)(
                dict(params, rows=rows0 + update), dense, inv, label,
                interaction)
            update = update - lr * grads.pop("rows")
            mlps = {k: params[k] for k in grads}
            params = dict(params, **jax.tree.map(
                lambda p, g: p - lr * g, mlps, grads))
            losses.append(loss)
    return jnp.stack(losses), dict(params, rows=rows0 + update), update


# --------------------------------------------------------------------------
# reading the system's arrays
# --------------------------------------------------------------------------
def _embedding_op(model):
    (op,) = [o for o in model.ops if type(o).__name__ in EMBEDDING_OPS]
    if getattr(op, "_hot_rows", 0) or getattr(op, "_table_order",
                                              None) is not None:
        raise NotImplementedError(
            "the touched-row check does not know the hot/cold split or a "
            "permuted table order")
    return op


def stored_position(op, ids: np.ndarray) -> Tuple[tuple, np.ndarray]:
    """Where the program keeps logical row `ids[n, t, k]` of table t:
    (leading coordinates into `params[op.name]["kernel"]`, sub-row). Narrow
    rows are packed r to a 128-lane stored row (`ops/embedding._pack_factor`):
    stored row = id // r, lanes [sub*d, (sub+1)*d) with sub = id % r."""
    ids = np.asarray(ids, np.int64)
    r = int(op._pack)
    t = np.broadcast_to(np.arange(ids.shape[1])[None, :, None], ids.shape)
    if type(op).__name__ == "EmbeddingBagStacked":
        g = ids % op.num_entries
        return (t.reshape(-1), (g // r).reshape(-1)), (g % r).reshape(-1)
    sizes = np.asarray(op.table_sizes, np.int64)[None, :, None]
    offs = np.asarray(op._offsets, np.int64)[None, :, None]
    g = ids % sizes + offs
    return ((g // r).reshape(-1),), (g % r).reshape(-1)


def read_stored_rows(kernel, coords: tuple) -> np.ndarray:
    """`kernel[coords]` -> (n, lanes) on the host, one small gather per
    shard on the shard's own device, so a 24 GB table is never gathered
    whole and a sharded gather is never compiled."""
    n = len(coords[0])
    out = np.empty((n, kernel.shape[-1]), np.float32)
    seen = np.zeros(n, bool)
    for shard in kernel.addressable_shards:
        mine = ~seen
        local = []
        for c, sl, dim in zip(coords, shard.index, kernel.shape):
            lo, hi, _ = sl.indices(dim)
            mine &= (c >= lo) & (c < hi)
            local.append(c - lo)
        if mine.any():
            out[mine] = np.asarray(
                shard.data[tuple(jnp.asarray(c[mine]) for c in local)])
            seen |= mine
    if not seen.all():
        raise RuntimeError("some touched table rows are on no addressable "
                           "shard")
    return out


class Touched:
    """The distinct logical table rows one batch touches, and how to read
    them out of `model.params`."""

    def __init__(self, model, sparse: np.ndarray):
        self.op = _embedding_op(model)
        self.d = int(self.op.out_dim)
        coords, sub = stored_position(self.op, sparse)
        r = int(self.op._pack)
        # one key per logical row: stored row (flattened) * r + sub-row
        shape = model.params[self.op.name]["kernel"].shape[:len(coords)]
        key = np.ravel_multi_index(coords, shape) * r + sub
        uniq, inv = np.unique(key, return_inverse=True)
        self.inv = inv.reshape(sparse.shape).astype(np.int32)
        self.coords = np.unravel_index(uniq // r, shape)
        self.sub = uniq % r

    def read(self, model) -> np.ndarray:
        """(U, d) current values of the touched rows."""
        stored = read_stored_rows(model.params[self.op.name]["kernel"],
                                  self.coords)
        lanes = self.sub[:, None] * self.d + np.arange(self.d)[None, :]
        return np.take_along_axis(stored, lanes, axis=1)


def read_mlps(model, config: dict) -> Dict[str, list]:
    def stack(prefix, sizes):
        return [(np.asarray(model.params[f"{prefix}_dense_{i}"]["kernel"]),
                 np.asarray(model.params[f"{prefix}_dense_{i}"]["bias"]))
                for i in range(len(sizes) - 1)]
    return {"bot": stack("bot", config["mlp_bot"]),
            "top": stack("top", config["mlp_top"])}


def snapshot(model, config: dict, batch: Dict[str, np.ndarray]) -> dict:
    """Everything the reference needs, read before the checked steps."""
    touched = Touched(model, batch["sparse"])
    params = read_mlps(model, config)
    rows = touched.read(model)
    # padded to one row a lookup, so that the reference's shapes (and its
    # entry in the compile cache) do not depend on how many ids the seed
    # drew twice; no lookup points at the padding, so it never moves
    params["rows"] = np.concatenate(
        [rows, np.zeros((touched.inv.size - len(rows), rows.shape[1]),
                        np.float32)])
    return {"touched": touched, "params": params, "batch": batch,
            "rows": rows}


def verify(snap: dict, rows_after: np.ndarray, system_losses, config: dict
           ) -> dict:
    """Run the reference from the snapshot and compare: the loss before
    every step, and the touched rows after the last."""
    steps = len(system_losses)
    b = snap["batch"]
    ref_losses, ref, ref_update = reference_steps(
        snap["params"], b["dense"], snap["touched"].inv, b["label"],
        lr=float(config["optimizer"]["lr"]), steps=steps,
        interaction=config["interaction"])
    ref_losses = np.asarray(ref_losses, np.float64)
    rows0 = snap["rows"]
    ref_rows = np.asarray(ref["rows"])[:len(rows0)]
    ref_update = np.asarray(ref_update)[:len(rows0)]
    sys_losses = np.asarray(system_losses, np.float64)
    loss_err = float(np.max(np.abs(sys_losses - ref_losses)
                            / np.abs(ref_losses)))
    update = rows_after - rows0
    largest = float(np.max(np.abs(ref_update)))
    slope = float(np.sum(update * ref_update, dtype=np.float64)
                  / np.sum(ref_update * ref_update, dtype=np.float64))
    tol = ROWS_RTOL * largest + steps * np.spacing(np.abs(ref_rows))
    outside = int(np.sum(np.abs(rows_after - ref_rows) > tol))
    out = {
        "steps": steps,
        "loss_system": sys_losses.tolist(),
        "loss_reference": ref_losses.tolist(),
        "loss_rel_err": loss_err, "loss_rtol": LOSS_RTOL,
        "rows_checked": int(rows0.shape[0]),
        "rows_largest_update": largest,
        "rows_update_slope": slope,
        "rows_max_abs_err": float(np.max(np.abs(rows_after - ref_rows))),
        "rows_elements_outside_tolerance": outside,
    }
    out["ok"] = bool(np.all(np.isfinite(sys_losses))
                     and loss_err <= LOSS_RTOL
                     and abs(slope - 1.0) <= ROWS_SLOPE_TOL
                     and outside == 0)
    return out


# --------------------------------------------------------------------------
# operations and bytes from the shapes
# --------------------------------------------------------------------------
def _widths(config: dict) -> Tuple[List[int], List[int]]:
    """Layer widths as built: the bottom MLP's, and the top MLP's with its
    real input width (the interaction's output, not `mlp_top[0]`)."""
    T, d = len(config["table_rows"]), int(config["embedding_dim"])
    F = T + 1
    inter = (d + F * (F - 1) // 2 if config["interaction"] == "dot"
             else d * F)
    return list(config["mlp_bot"]), [inter] + list(config["mlp_top"][1:])


def macs_per_sample(config: dict) -> int:
    """Multiply-accumulates of one forward pass: the two MLPs and, for
    `dot`, the F(F-1)/2 pairwise products of width d the interaction keeps."""
    bot, top = _widths(config)
    macs = sum(a * b for a, b in zip(bot, bot[1:]))
    macs += sum(a * b for a, b in zip(top, top[1:]))
    if config["interaction"] == "dot":
        F = len(config["table_rows"]) + 1
        macs += F * (F - 1) // 2 * int(config["embedding_dim"])
    return macs


def flops_per_sample(config: dict) -> int:
    """Training FLOPs a sample: forward 2 a MAC, backward 4 (dx and dW)."""
    return 6 * macs_per_sample(config)


def bytes_per_step(config: dict, batch_per_chip: int) -> int:
    """The least HBM traffic of one training step on one chip, given fp32
    weights, tables and saved activations: every MLP weight read forward,
    read backward, read and written by the update; every looked-up row
    read by the gather, read and written by the update; every layer's
    output written forward and read backward; the inputs read once."""
    bot, top = _widths(config)
    T, d = len(config["table_rows"]), int(config["embedding_dim"])
    bag = int(config["bag_size"])
    weights = sum(a * b + b for w in (bot, top) for a, b in zip(w, w[1:]))
    acts = sum(bot[1:]) + sum(top)      # top[0] is the interaction's output
    lookups = T * bag
    per_sample = (3 * lookups * d * 4 + 2 * acts * 4
                  + (bot[0] + lookups + 1) * 4)
    return 4 * weights * 4 + batch_per_chip * per_sample
