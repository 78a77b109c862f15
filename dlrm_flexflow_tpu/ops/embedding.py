"""Embedding operators.

Parity with the reference Embedding op (reference: src/ops/embedding.cu, 364
LoC — custom CUDA gather forward / atomicAdd scatter backward,
embedding.cu:173-224; aggregation modes SUM/AVG; partitioned only over the
sample dim, embedding.cu:115-117) and the AVX2 CPU embedding-bag path
(src/ops/embedding_avx2.cc, 296 LoC). In the reference's DLRM strategies each
table is pinned whole to one device = table parallelism
(dlrm_strategy.cc:252-256); the hetero strategy puts tables on CPUs
(dlrm_strategy_hetero.cc:28-36).

TPU-native redesign:
- forward lookup is `jnp.take` (XLA gather, MXU-free, HBM-bandwidth bound);
  backward is XLA scatter-add from jax.grad — no atomics needed. A Pallas
  gather kernel for dim % 128 == 0 (a block of row fetches in flight at
  once) lives in ops/pallas/embedding_kernel.py.
- table ("parameter") parallelism: the table's row or width dim is sharded
  over mesh axes. Width (out_dim) sharding keeps the lookup local and
  concat-compatible. Row sharding (for huge tables) does the lookup under a
  one-hot-free masked gather + psum.
- the stacked EmbeddingBagStacked op (models/dlrm.py uses it) fuses N
  same-shape tables into one (N, rows, dim) parameter sharded on dim 0 —
  the GSPMD expression of "each table whole on one device" with the
  all-to-all the reference got from Legion DMA.
- hetero strategies: `device_type == CPU` host-offloads the COMPUTE
  (compute_on); ZCM memory_types / FFConfig.host_resident_tables store the
  table itself in host RAM with numpy gather + touched-rows scatter around
  the jitted step (host_init/host_lookup/host_sgd_update below) — the
  embedding_avx2.cc capability that lets tables larger than HBM train.
- the sparse-SGD update keeps the forward-gathered tiles as residuals
  (apply_with_fwd) so the scatter WRITES new rows without re-reading them
  (ops/pallas scatter_write_rows_packed) — random HBM rows are the
  latency floor on TPU.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..core.initializers import GlorotUniform
from ..core.op import Op, ParamDef
from ..parallel.pconfig import DEVICE_CPU, ParallelConfig
from ..utils.logging import get_logger

log_emb = get_logger("embedding")

AGGR_MODE_NONE = "none"
AGGR_MODE_SUM = "sum"
AGGR_MODE_AVG = "avg"


def _zcm_candidate(ndims: int) -> ParallelConfig:
    """Host-resident (ZCM) candidate for the strategy search: the table
    stored in CPU RAM, looked up and scatter-updated there (reference
    hetero strategies, dlrm_strategy_hetero.cc:28-49). Offering it as a
    search candidate lets optimize() discover Terabyte-style placements
    (huge tables to host, the rest row-sharded in HBM) instead of only
    executing hand-written hetero .pb files."""
    return ParallelConfig((1,) * ndims, device_type=DEVICE_CPU,
                          memory_types=("ZCM",))


def _pack_factor(dim: int, rows: int) -> int:
    """Rows per 128-lane tile for the packed storage of narrow tables
    (1 when the width is already lane-aligned or doesn't divide 128)."""
    if dim < 128 and 128 % dim == 0:
        r = 128 // dim
        if rows % r == 0:
            return r
    return 1


def _packed_gather_tiles(tbl, ix, r, d):
    """Gather logical rows `ix` from a packed (rows/r, r*d) table.
    Returns (rows `ix.shape + (d,)`, flat tile rows (n,), flat tiles
    (n, r*d)) — THE packed-layout invariant (tile = ix//r, sub-row =
    ix%r, wrap) in one place; the tiles are the forward residuals the
    write-only sparse update reuses."""
    with jax.named_scope("gather"):
        vrow = (ix // r).reshape(-1)
        tiles = jnp.take(tbl, vrow, axis=0, mode="wrap")    # (n, r*d)
        sub = (ix % r).reshape(-1)
        rows = jnp.take_along_axis(
            tiles.reshape(-1, r, d), sub[:, None, None], axis=1)[:, 0, :]
        return rows.reshape(ix.shape + (d,)), vrow, tiles


def _packed_gather(tbl, ix, r, d):
    """Gather logical rows `ix` from a packed (rows/r, r*d) table."""
    return _packed_gather_tiles(tbl, ix, r, d)[0]


def _lookup_count(op) -> float:
    """Rows randomly touched per step by this op's gather: batch × tables
    × bag."""
    t = op.inputs[0]
    batch = t.shape[0]
    bag = t.shape[-1] if t.num_dims > 1 else 1
    tables = getattr(op, "num_tables", 1)
    return float(batch * tables * bag)


# tables at/below this footprint don't pay the random-row latency: their
# whole row space fits a few HBM pages / the chip's caches, so repeated
# lookups behave like streaming. Measured r5: an MLP with 4x64-row tables
# trains its full step in 0.79 ms while pricing its 4k lookups at the
# random-row rate predicted +75%; Criteo-Kaggle's 14 tiny tables (4..13k
# rows) similarly cost ~nothing next to its 12 multi-M-row tables.
_SMALL_TABLE_BYTES = 2 << 20


def _table_sizes(op):
    sizes = getattr(op, "table_sizes", None)
    if sizes is None:
        sizes = [op.num_entries] * getattr(op, "num_tables", 1)
    return sizes


def _table_itemsize(op, pc=None) -> float:
    """Bytes per STORED table element: the op's effective quantized-
    storage policy when one is set (int8 rows stream 1 B/elem against
    the 2 MB threshold), else the actual param dtype — a bf16 table has
    half the fp32 footprint, and hardcoding 4 B would misclassify it as
    large."""
    from ..quant.policy import effective_policy
    pol = effective_policy(op, pc)
    if not pol.is_default:
        return float(pol.itemsize)
    try:
        pd = op.param_defs().get("kernel")
        return float(jnp.dtype(pd.dtype).itemsize)
    except Exception:
        return 4.0


def _has_large_table(op) -> bool:
    row_bytes = op.out_dim * _table_itemsize(op)
    return any(rows * row_bytes > _SMALL_TABLE_BYTES
               for rows in _table_sizes(op))


def _effective_random_rows(op, per_table_lookups: float) -> float:
    """Sum of effective GATHER random-row counts across the op's tables:
    small-table lookups are free (their row space behaves like a
    streamed working set — mlp_heavy's 4k lookups into 64-row tables
    hide entirely inside the step floor, measured r5) and large-table
    counts cap at the table's row count (a gather cannot touch more
    distinct rows than the table has)."""
    row_bytes = op.out_dim * _table_itemsize(op)
    total = 0.0
    for rows in _table_sizes(op):
        if rows * row_bytes <= _SMALL_TABLE_BYTES:
            continue
        total += min(per_table_lookups, float(rows))
    return total


def _is_host_resident(op, pc=None) -> bool:
    return (op.name in getattr(op.model, "_host_resident_ops", set())
            or (pc is not None and "ZCM" in pc.memory_types))


def _embedding_random_rows(op, backward: bool, raw: bool = False) -> float:
    # forward = one random read per lookup into a LARGE table; the
    # sparse-path backward never re-gathers (the train step threads
    # cotangents via overrides). `raw` skips the small-table/dedup
    # gating — the HOST (ZCM) pricing path uses it: the 2 MB streaming
    # heuristic was measured for on-device HBM, not host DRAM over PCIe
    if backward:
        return 0.0
    if raw or _is_host_resident(op):
        return _lookup_count(op)
    t = op.inputs[0]
    batch = t.shape[0]
    bag = t.shape[-1] if t.num_dims > 1 else 1
    return _effective_random_rows(op, float(batch * bag))


def _embedding_update_rows(op, pc=None) -> float:
    # touched-rows scatter: the RMW fallback reads AND writes each row
    # (2.0 accesses/lookup); the write-only path
    # (scatter_write_rows_packed) skips the read but measured step times
    # show random writes amortize only slightly better than reads —
    # 1.6 effective accesses/lookup fits every calibration point within
    # ~16% (benchmarks/calibrate_sim.py). Dense updates stream the table
    # instead (param_bytes_touched_per_step). Stateful sparse updates
    # (lazy momentum/Adam) add one read + one write per state slab per
    # touched row on top of the weight traffic.
    #
    # The choice is STRUCTURAL (op attributes + the CANDIDATE config,
    # never the live process's backend/mesh): write-only needs
    # lane-packed storage and an unsharded table (row-sharded tables take
    # the shard_map RMW path) — the simulator models the target TPU even
    # when the search runs on a CPU host.
    if not _sparse_update_active(op):
        return 0.0
    write_only = (getattr(op, "_pack", 1) > 1
                  and op.aggr in (AGGR_MODE_SUM, AGGR_MODE_AVG)
                  and (pc is None or pc.num_parts == 1))
    accesses = 1.6 if write_only else 2.0
    opt = getattr(op.model, "optimizer", None)
    if opt is not None:
        accesses += 2.0 * len(opt.sparse_slab_names())
    # the update machinery (lane pack + dedup sort + scatter) processes
    # EVERY raw lookup — unlike the gather, tiny-table lookups are not
    # free here unless ALL the op's tables are tiny (then the whole
    # working set streams: mlp_heavy's update hides in the step floor,
    # while Criteo-Kaggle pays ~per-raw-lookup even though 19 of its 26
    # tables are tiny — measured r5, 77-95 ns/lookup update-side on both
    # kaggle and dlrm_random). HOST (ZCM) tables always count raw: the
    # device-cache gating does not describe host DRAM, and a zero here
    # would silently reroute host_update_time to its dense fallback
    if not _is_host_resident(op, pc) and not _has_large_table(op):
        return 0.0
    return accesses * _lookup_count(op)


def _host_init_table(initializer, shape, seed: int):
    """Numpy re-implementation of the common initializers for HOST-resident
    tables (the reference stores hetero tables in CPU RAM and fills them
    there, embedding_avx2.cc / dlrm_strategy_hetero.cc:28-49; jax init on
    the accelerator would defeat the point of host residency)."""
    import numpy as np

    from ..core import initializers as I
    rng = np.random.RandomState(seed & 0x7FFFFFFF)
    if isinstance(initializer, I.ZeroInitializer):
        return np.zeros(shape, np.float32)
    if isinstance(initializer, I.ConstantInitializer):
        return np.full(shape, initializer.value, np.float32)
    if isinstance(initializer, I.UniformInitializer):
        return rng.uniform(initializer.min_val, initializer.max_val,
                           shape).astype(np.float32)
    if isinstance(initializer, I.NormInitializer):
        return rng.normal(initializer.mean, initializer.stddev,
                          shape).astype(np.float32)
    # GlorotUniform over the last two dims (matches initializers.py fans)
    fan_in, fan_out = shape[-2], shape[-1]
    lim = float(np.sqrt(6.0 / (fan_in + fan_out)))
    return rng.uniform(-lim, lim, shape).astype(np.float32)


def _native_emb():
    """The native threaded gather/scatter library, or None (numpy
    fallback). The reference's hetero path is blocked AVX2/FMA C++
    (embedding_avx2.cc); native/ffemb.cc is this build's equivalent."""
    from .. import native
    return native.get_lib()


# gather path chosen by MEASUREMENT per shape (the reference's own trick
# for cuDNN conv algos, conv_2d.cu:217,873): the threaded native gather
# wins on many-core hosts, numpy's fancy-index loop wins on small CPU
# quotas — time both once and keep the faster
_GATHER_CHOICE: Dict[tuple, str] = {}


def _native_gather(lib, table, g, aggr, d):
    import ctypes

    import numpy as np
    batch, T, bag = g.shape
    gf = np.ascontiguousarray(g.reshape(batch * T, bag), np.int64)
    out = np.empty((batch * T, d), np.float32)
    fp = ctypes.POINTER(ctypes.c_float)
    ip = ctypes.POINTER(ctypes.c_int64)
    lib.ffemb_bag_gather(
        table.ctypes.data_as(fp), table.shape[0], d,
        gf.ctypes.data_as(ip), batch * T, bag,
        1 if aggr == AGGR_MODE_AVG else 0, out.ctypes.data_as(fp))
    return out.reshape(batch, T, d)


def _numpy_gather(table, g, aggr, d):
    import numpy as np
    rows = table[g.reshape(-1)].reshape(g.shape + (d,))
    out = rows.mean(axis=2) if aggr == AGGR_MODE_AVG else rows.sum(axis=2)
    return np.ascontiguousarray(out, np.float32)


def _host_bag_lookup(table, g, aggr):
    """table (rows, d) numpy; g (batch, T, bag) global rows -> (batch,T,d)."""
    import time

    import numpy as np
    d = table.shape[-1]
    lib = _native_emb()
    native_ok = (lib is not None and table.dtype == np.float32
                 and table.flags["C_CONTIGUOUS"])
    if not native_ok:
        return _numpy_gather(table, g, aggr, d)
    key = (table.shape, g.shape, aggr)
    choice = _GATHER_CHOICE.get(key)
    if choice is None:
        # warm both paths first (the native side pays one-time pool
        # construction and cold caches; timing it cold would cache the
        # wrong verdict forever), then time each once
        _native_gather(lib, table, g, aggr, d)
        _numpy_gather(table, g, aggr, d)
        t0 = time.perf_counter()
        out_n = _native_gather(lib, table, g, aggr, d)
        t_native = time.perf_counter() - t0
        t0 = time.perf_counter()
        out_p = _numpy_gather(table, g, aggr, d)
        t_numpy = time.perf_counter() - t0
        choice = "native" if t_native <= t_numpy else "numpy"
        _GATHER_CHOICE[key] = choice
        return out_n if choice == "native" else out_p
    if choice == "native":
        return _native_gather(lib, table, g, aggr, d)
    return _numpy_gather(table, g, aggr, d)


def _host_bag_update(table, g, ct, lr, aggr):
    """In-place table[g] -= lr * d(out)/d(rows) · ct (duplicate-safe)."""
    import ctypes

    import numpy as np
    d = table.shape[-1]
    lib = _native_emb()
    if (lib is not None and table.dtype == np.float32
            and table.flags["C_CONTIGUOUS"]):
        batch, T, bag = g.shape
        gf = np.ascontiguousarray(g.reshape(batch * T, bag), np.int64)
        cf = np.ascontiguousarray(ct.reshape(batch * T, d), np.float32)
        fp = ctypes.POINTER(ctypes.c_float)
        ip = ctypes.POINTER(ctypes.c_int64)
        lib.ffemb_bag_scatter(
            table.ctypes.data_as(fp), table.shape[0], d,
            gf.ctypes.data_as(ip), batch * T, bag,
            1 if aggr == AGGR_MODE_AVG else 0,
            cf.ctypes.data_as(fp), float(lr))
        return
    bag = g.shape[-1]
    c = ct / bag if aggr == AGGR_MODE_AVG else ct
    upd = np.broadcast_to(c[..., None, :], g.shape + (d,))
    np.add.at(table, g.reshape(-1), -lr * upd.reshape(-1, d))


def _host_dedup_rows(flat, upd):
    """Numpy duplicate combination for the host stateful update: stateful
    optimizers are nonlinear in the gradient, so duplicate lookups must
    pre-sum into one gradient row (same reason as _dedup_rows)."""
    import numpy as np
    uniq, inv = np.unique(flat, return_inverse=True)
    summed = np.zeros((uniq.shape[0], upd.shape[-1]), np.float32)
    np.add.at(summed, inv, upd)
    return uniq, summed


def _host_stateful_update(table, g, ct, opt, slabs, step, aggr):
    """Lazy stateful touched-rows update on a HOST (numpy) table — the
    host twin of _sparse_opt_update (same semantics as the device tile
    path: state rows update only on touch, decay applies lazily).

    table (rows, d) numpy, mutated in place; g (batch, T, bag) global
    rows; ct (batch, T, d); slabs {name: (rows, d)} mutated in place."""
    d = table.shape[-1]
    import numpy as np
    bag = g.shape[-1]
    c = ct / bag if aggr == AGGR_MODE_AVG else ct
    upd = np.broadcast_to(c[..., None, :],
                          g.shape + (d,)).reshape(-1, d)
    uniq, summed = _host_dedup_rows(g.reshape(-1), upd)
    slab_rows = {k: v[uniq] for k, v in slabs.items()}
    wn, sn = opt.sparse_row_update_np(table[uniq], summed, slab_rows,
                                      step)
    table[uniq] = wn
    for k in slabs:
        slabs[k][uniq] = sn[k]


def _touched_bytes_factor(op) -> float:
    """Bytes-per-touched-element / 4: gather read + update read/write of
    the weights (3 accesses at the table's effective STORED width — an
    int8-policy table streams a quarter of the weight bytes), plus
    read+write per optimizer state slab (always fp32) on the stateful
    sparse path. Returned in fp32-element units so callers keep
    multiplying by ``elements * 4``."""
    opt = getattr(op.model, "optimizer", None)
    nslabs = len(opt.sparse_slab_names()) if opt is not None else 0
    return 3.0 * (_table_itemsize(op) / 4.0) + 2.0 * nslabs


def _sparse_update_active(op) -> bool:
    """Whether a touched-rows-only update will actually run for `op` —
    the state-free plain-SGD path or the stateful lazy momentum/Adam
    path (mirrors FFModel._select_sparse_update_ops; optimizer may be
    unset when the search costs ops pre-compile — assume the common
    plain-SGD case then)."""
    if not getattr(op.model.config, "sparse_embedding_update", True):
        return False
    if not op.supports_sparse_update():
        return False
    if op.name in getattr(op.model, "_host_offload_ops", set()):
        return False   # host-offloaded tables take the dense path
    opt = getattr(op.model, "optimizer", None)
    if opt is None:
        return True
    from ..core.optimizers import AdamOptimizer, SGDOptimizer
    if isinstance(opt, SGDOptimizer):
        return (opt.momentum == 0.0 and opt.weight_decay == 0.0) \
            or hasattr(op, "sparse_opt_update")
    return isinstance(opt, AdamOptimizer) and hasattr(op,
                                                      "sparse_opt_update")


def _dedup_rows(gidx, upd, num_rows: int):
    """Row-granularity duplicate combination: sort + segment-sum in
    UNPACKED row space, on any backend (the Pallas scatters' own dedup,
    `_dedup_tile_updates`, reaches the same result without XLA's segment
    ops; stateful optimizers are nonlinear in the gradient, so duplicate
    lookups MUST be pre-summed into one gradient row — dense semantics).

    gidx (n,) int row ids (duplicates allowed); upd (n, d).
    Returns (target (n,), summed (n, d)): distinct target rows with their
    combined updates; pad slots carry target == num_rows (out of bounds,
    dropped by mode='drop' scatters)."""
    n = gidx.shape[0]
    order = jnp.argsort(gidx)
    si = jnp.take(gidx, order)
    sg = jnp.take(upd, order, axis=0)
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_), si[1:] != si[:-1]])
    seg = jnp.cumsum(first) - 1
    summed = jax.ops.segment_sum(sg, seg, num_segments=n,
                                 indices_are_sorted=True)
    target = jax.ops.segment_max(si, seg, num_segments=n,
                                 indices_are_sorted=True)
    valid = jnp.arange(n) < seg[-1] + 1
    target = jnp.where(valid, target, num_rows).astype(jnp.int32)
    return target, summed


def _stateful_update_rows_xla(logical, gidx, upd, opt, slabs, step):
    """Generic stateful touched-rows update on the LOGICAL (rows, d) view:
    dedup -> gather w/state rows -> optimizer row math -> scatter-set.
    Runs on any backend (the CPU-mesh test oracle and the fallback for
    layouts the Pallas tile path doesn't cover).

    logical (rows, d); slabs {name: (rows, d)} in the same layout.
    Returns (new_logical, new_slabs)."""
    rows = logical.shape[0]
    target, summed = _dedup_rows(gidx, upd, rows)
    safe = jnp.minimum(target, rows - 1)
    w = jnp.take(logical, safe, axis=0).astype(jnp.float32)
    slab_rows = {k: jnp.take(v, safe, axis=0).astype(jnp.float32)
                 for k, v in slabs.items()}
    touched = jnp.ones_like(w, dtype=jnp.bool_)
    wn, sn = opt.sparse_row_update(w, summed.astype(jnp.float32),
                                   slab_rows, touched, step)
    new_logical = logical.at[target].set(wn.astype(logical.dtype),
                                         mode="drop")
    new_slabs = {k: slabs[k].at[target].set(sn[k].astype(slabs[k].dtype),
                                            mode="drop")
                 for k in slabs}
    return new_logical, new_slabs


def _stateful_update_tiles_packed(view, gidx, upd, d, opt, slab_views,
                                  step, fwd_tiles=None, interpret=False):
    """TPU tile path of the stateful touched-rows update, on the lane-
    packed (vrows, 128) view (128 // d logical rows per tile).

    Same structure as the write-only sparse-SGD scatter: dedup at TILE
    granularity, then pure Pallas writes of distinct tiles — but each
    tile's new value comes from the optimizer's row math applied to the
    whole 128-lane tile with a per-lane `touched` mask (a tile holds
    several logical rows; only looked-up rows' lanes may change, or lazy
    momentum/Adam would decay their tile-neighbours). Weight tiles come
    from the forward-gather residuals when available (no re-read); state
    tiles are gathered here (their only read).
    """
    from .pallas.embedding_kernel import (_dedup_tile_updates,
                                          _pack_tile_updates,
                                          scatter_write_tiles)
    tile_rows, tile_upds = _pack_tile_updates(gidx, upd, d, jnp.float32)
    _, tile_ones = _pack_tile_updates(gidx, jnp.ones_like(upd), d,
                                      jnp.float32)
    # one sort/segment pass for both the gradient and the touch counts
    both = jnp.concatenate([tile_upds, tile_ones], axis=1)
    target, summed, rep, _ = _dedup_tile_updates(tile_rows, both, interpret)
    g_tiles, counts = summed[:, :128], summed[:, 128:]
    touched = counts > 0
    safe = jnp.minimum(jnp.maximum(target, 0), view.shape[0] - 1)
    if fwd_tiles is not None:
        # any duplicate's forward tile is the same pre-update value; rep
        # holds one original lookup position per segment (pad slots 0,
        # dropped by target < 0 at the write)
        w = jnp.take(fwd_tiles, rep, axis=0).astype(jnp.float32)
    else:
        w = jnp.take(view, safe, axis=0).astype(jnp.float32)
    slab_tiles = {k: jnp.take(v, safe, axis=0).astype(jnp.float32)
                  for k, v in slab_views.items()}
    wn, sn = opt.sparse_row_update(w, g_tiles, slab_tiles, touched, step)
    new_view = scatter_write_tiles(view, target, wn, interpret=interpret)
    new_slabs = {k: scatter_write_tiles(slab_views[k], target, sn[k],
                                        interpret=interpret)
                 for k in slab_views}
    return new_view, new_slabs


def _norm_slabs(slabs):
    """Accept {slab: arr} (legacy — the kernel's slab rows) or
    {slab: {param: arr}} (the model passes every param's slabs; the
    hybrid placement has two params). Returns
    (kernel_slabs, hot_slabs | None, was_nested)."""
    nested = any(isinstance(v, dict) for v in slabs.values())
    if not nested:
        return dict(slabs), None, False
    k = {n: v["kernel"] for n, v in slabs.items()}
    hot = None
    if any("hot_kernel" in v for v in slabs.values()):
        hot = {n: v["hot_kernel"] for n, v in slabs.items()}
    return k, hot, True


def _finish_opt_update(out, nested):
    """Normalize a stateful-update result back to the caller's slab
    form: hybrid results (4-tuple) always nest (two params); legacy
    flat callers get flat kernel slabs back."""
    if len(out) == 4:
        new_k, new_s, new_h, new_hs = out
        return ({"kernel": new_k, "hot_kernel": new_h},
                {k: {"kernel": new_s[k], "hot_kernel": new_hs[k]}
                 for k in new_s})
    new_k, new_s = out
    if nested:
        new_s = {k: {"kernel": v} for k, v in new_s.items()}
    return {"kernel": new_k}, new_s


def _sparse_opt_update(op, tbl, gidx, upd, opt, slabs, step, total_rows,
                       fwd_tiles=None, hot_tbl=None, hot_slabs=None):
    """Shared stateful-update router for the embedding ops: lane-packed
    Pallas tile path on TPU, logical-row XLA path elsewhere.

    tbl: stored kernel (any layout reshapeable to (total_rows, d));
    slabs {name: same-layout state}; gidx (n,) UNPACKED global rows;
    upd (n, d) RAW gradient rows (not pre-scaled by -lr — stateful
    optimizers are nonlinear in the gradient).
    Returns (new_kernel, new_slabs) in the stored layout — plus
    (new_hot, new_hot_slabs) under the hybrid placement."""
    d = op.out_dim
    plan = _row_plan(op)
    if plan is not None and gidx.shape[0] % plan.ndev == 0:
        # row-sharded: gradient rows + their global positions route to
        # the owning shard; weights AND state slabs update shard-locally
        # (hybrid hot rows apply in lockstep from an all-gather)
        from ..parallel.alltoall import row_sharded_opt_update
        owner, local, gid, hot_id = op._row_route(gidx)
        spec, _ = op._row_spec_block()
        if hot_id is not None:
            return row_sharded_opt_update(
                plan, tbl, slabs, spec, owner, local, upd, opt, step,
                d, gid=gid, hot_table=hot_tbl, hot_slabs=hot_slabs,
                hot_id=hot_id)
        return row_sharded_opt_update(plan, tbl, slabs, spec, owner,
                                      local, upd, opt, step, d, gid=gid)
    r = getattr(op, "_pack", 1)
    use_tiles = (r * d == 128
                 and _pallas_scatter_ok(op.model, 128, op.name)
                 and _row_shard_axes(op, d, total_rows // r) is None)
    if use_tiles:
        view = tbl.reshape(total_rows // r, r * d)
        slab_views = {k: v.reshape(total_rows // r, r * d)
                      for k, v in slabs.items()}
        nv, ns = _stateful_update_tiles_packed(view, gidx, upd, d, opt,
                                               slab_views, step, fwd_tiles)
    else:
        view = tbl.reshape(total_rows, d)
        slab_views = {k: v.reshape(total_rows, d) for k, v in slabs.items()}
        nv, ns = _stateful_update_rows_xla(view, gidx, upd, opt,
                                           slab_views, step)
    return (nv.reshape(tbl.shape),
            {k: ns[k].reshape(slabs[k].shape) for k in slabs})


def _pallas_common(model, op_name: str, width_ok: bool) -> bool:
    """Checks shared by every Pallas routing gate: opted in, TPU backend,
    supported width, not host-offloaded (a Mosaic TPU custom call cannot
    run inside a compute_on("device_host") region)."""
    if not getattr(model.config, "use_pallas", False):
        return False
    if not width_ok:
        return False
    if jax.default_backend() != "tpu":
        return False
    if op_name and op_name in getattr(model, "_host_offload_ops", set()):
        return False
    return True


def _pallas_gate(model, op_name: str, width_ok: bool) -> bool:
    """Single-chip Pallas gate (under a >1-device mesh the op runs inside
    GSPMD where the direct Pallas call cannot; the multi-chip scatter goes
    through _row_shard_axes + shard_map instead)."""
    if not _pallas_common(model, op_name, width_ok):
        return False
    mesh = getattr(model, "mesh", None)
    return mesh is None or mesh.size <= 1


def _row_shard_axes(op, d: int, packed_rows: int):
    """Mesh axes over which `op`'s packed table rows are block-sharded —
    when the multi-chip Pallas scatter can run (TPU, pallas on, not host-
    offloaded, lane-packable width, table actually sharded on dim 0).
    Returns None when the single-chip or XLA path should be used."""
    model = op.model
    mesh = getattr(model, "mesh", None)
    if mesh is None or mesh.size <= 1:
        return None
    width_ok = d <= 128 and 128 % d == 0
    if not _pallas_common(model, op.name, width_ok):
        return None
    # the sharded kernel assumes the LANE-PACKED layout; an unpacked
    # narrow table (rows not divisible by 128//d) must not be routed here
    expected_r = 128 // d
    if getattr(op, "_pack", 1) != expected_r:
        return None
    sh = getattr(model, "_param_sharding", {}).get(op.name, {}).get("kernel")
    if sh is None or not len(sh.spec) or not sh.spec[0]:
        return None
    spec0 = sh.spec[0]
    axes = (spec0,) if isinstance(spec0, str) else tuple(spec0)
    nsh = 1
    for a in axes:
        nsh *= mesh.shape[a]
    if nsh <= 1:
        return None
    # the shard_map kernel needs equal row blocks per shard
    if packed_rows % nsh != 0:
        return None
    return axes


# ---- row/PARAM-axis sharding with explicit all-to-all routing ------------
# The pod-scale mode (ParallelConfig.param_degree > 1): the table's ROW
# space is block-sharded over mesh devices — no single device ever holds a
# whole table — and lookups are routed to owners and back by the dense
# all-to-all exchange in parallel/alltoall.py. Activated per op by
# FFModel._build_shardings via configure_row_shard(); every routed path
# below gates on `op._row_plan`.


# hot-row quantum, in lane-pack units: the hybrid hot count rounds to a
# multiple of HOT_QUANTUM_PACKS x pack so the SAME hot split works for
# every row-shard degree dividing 8 — an elastic clamp 8 -> 4 -> 2 can
# reshard the cold tail without changing the hot block's shape (and the
# checkpoint stays restorable across the clamp)
HOT_QUANTUM_PACKS = 8


def resolve_hot_rows(rows: int, pack: int, param_degree: int,
                     hot_fraction: float) -> int:
    """Per-table replicated hot-row count H for the hybrid placement:
    `hot_fraction` of `rows`, rounded to the hot quantum, such that the
    cold tail (rows - H) still equal-blocks `param_degree` row shards at
    the lane packing. 0 = no hybrid (infeasible requests resolve to 0
    and the caller degrades loudly to plain row sharding)."""
    if hot_fraction <= 0.0 or param_degree <= 1 or rows <= 0:
        return 0
    q = HOT_QUANTUM_PACKS * max(pack, 1)
    if q >= rows:
        return 0
    h = int(round(hot_fraction * rows / q)) * q
    h = max(h, q)
    h = min(h, rows - q)
    if (rows - h) % (param_degree * max(pack, 1)) != 0:
        return 0
    return h


def row_shard_structural_reason(op, raw_pc, axis_sizes) -> Optional[str]:
    """Mesh-free feasibility of `raw_pc.param_degree`-way row sharding
    for `op` over a factorized mesh with `axis_sizes`, or None when the
    request is executable. THE shared rule set: configure_row_shard
    applies it against the live mesh at compile time, and the static
    plan verifier (analysis/shardcheck.py) and elastic clamp
    (search/replan.py) apply it to offline plans — all three must agree
    on what "silently replicates" means."""
    pd = getattr(raw_pc, "param_degree", 1) if raw_pc is not None else 1
    if pd <= 1:
        return None
    if not hasattr(op, "_row_shard_geometry"):
        return ("op has no row-shard support (no configure_row_shard "
                "hook)")
    rows, pack, _tables = op._row_shard_geometry()
    batch = op.inputs[0].shape[0]
    ndev = 1
    for a in axis_sizes:
        ndev *= int(a)
    aggr = getattr(op, "aggr", AGGR_MODE_SUM)
    if aggr not in (AGGR_MODE_SUM, AGGR_MODE_AVG):
        return f"aggr={aggr!r} has no routed bag aggregation"
    if len(raw_pc.degrees) > 1 and any(d > 1 for d in raw_pc.degrees[1:]):
        return (f"degrees {raw_pc.degrees} also request table/width "
                f"sharding — pick one axis for the table")
    from ..parallel.sharding import assignable
    if pd > ndev or not assignable((pd,), list(axis_sizes)):
        return (f"{pd} row shards do not factorize mesh axes "
                f"{[int(a) for a in axis_sizes]}")
    if rows % (pd * max(pack, 1)) != 0:
        return (f"{pd} row shards must divide the {rows} padded rows "
                f"(lane pack {pack})")
    if batch % ndev != 0:
        return (f"batch {batch} does not divide over the {ndev}-device "
                f"mesh (lookups route from batch shards)")
    frac = getattr(raw_pc, "hot_fraction", 0.0)
    if frac > 0 and not getattr(op, "_hot_split_ok", False):
        return (f"hot_fraction={frac:g} requested but this op has no "
                f"per-table hot/cold split (concatenated non-uniform "
                f"tables keep every row routed)")
    return None


def configure_row_shard(op, raw_pc) -> None:
    """Resolve (and validate) the row-shard plan for `op` from its RAW
    strategy's param_degree (+ the skew refinements: exchange mode and
    hot_fraction). Sets ``op._row_plan`` (None = mode off) and
    ``op._hot_rows`` (per-table replicated hot rows; 0 = no hybrid).
    Infeasible requests degrade loudly to replicated rows — a silent
    fallback would OOM exactly the >HBM configs this mode exists for, so
    the warning names the reason."""
    from ..parallel.alltoall import plan_row_shard
    op._row_plan = None
    op._hot_rows = 0
    pd = getattr(raw_pc, "param_degree", 1) if raw_pc is not None else 1
    if pd <= 1:
        return
    model = op.model
    mesh = getattr(model, "mesh", None)
    rows, pack, tables = op._row_shard_geometry()
    dedup = getattr(raw_pc, "exchange", "dense") == "dedup"
    frac = getattr(raw_pc, "hot_fraction", 0.0)
    reason = None
    if mesh is None or mesh.size <= 1:
        reason = "needs a multi-device mesh"
    elif (op.name in getattr(model, "_host_resident_ops", set())
          or op.name in getattr(model, "_host_offload_ops", set())):
        reason = "host-resident/offloaded tables cannot row-shard in HBM"
    else:
        reason = row_shard_structural_reason(
            op, raw_pc, [int(mesh.shape[a]) for a in mesh.axis_names])
    hot = 0
    if reason is None and frac > 0:
        hot = resolve_hot_rows(rows, pack, pd, frac)
        if hot <= 0:
            log_emb.warning(
                "hot_fraction=%g for %r resolves to no replicable hot "
                "block (rows=%d, lane pack %d, %d shards, quantum %d "
                "rows); executing plain row sharding", frac, op.name,
                rows, pack, pd, HOT_QUANTUM_PACKS * max(pack, 1))
    if reason is None:
        plan = plan_row_shard(mesh, pd, rows - hot, pack, tables,
                              dedup=dedup, hot_rows=hot,
                              overlap=bool(getattr(raw_pc, "overlap",
                                                   False)))
        if plan is None:
            sizes = [int(mesh.shape[a]) for a in mesh.axis_names]
            reason = (f"{pd} row shards must factorize mesh axes {sizes} "
                      f"and divide the {rows} padded rows "
                      f"(lane pack {pack})")
        else:
            op._row_plan = plan
            op._hot_rows = hot
            return
    log_emb.warning(
        "row sharding (param_degree=%d) requested for %r but %s; "
        "executing with replicated rows", pd, op.name, reason)


def configure_quant(op, raw_pc) -> None:
    """Resolve the quantized-storage policy for ``op`` from its RAW
    strategy entry (``quant_dtype``/``quant_update``) with the model's
    ``--emb-dtype``/``--emb-update-rule`` as the default. Sets
    ``op._quant_policy`` — THE per-op policy every byte-accounting and
    storage-boundary site reads via ``quant.effective_policy`` — and
    registers it in ``model._quant_policies`` (non-default policies
    only) for the publisher/serving/manifest consumers."""
    from ..quant.policy import FP32, policy_from_config, policy_from_pc
    pol = policy_from_pc(raw_pc) \
        or policy_from_config(op.model.config) or FP32
    op._quant_policy = pol
    reg = getattr(op.model, "_quant_policies", None)
    if reg is None:
        reg = {}
        op.model._quant_policies = reg
    if pol.is_default:
        reg.pop(op.name, None)
        return
    reg[op.name] = pol
    log_emb.info(
        "quantized storage for %r: dtype=%s update_rule=%s "
        "(row-wise scales%s)", op.name, pol.dtype, pol.update_rule,
        "" if pol.is_quantized else " n/a")


def _row_plan(op):
    return getattr(op, "_row_plan", None)


def _id_histogram(op):
    """The op's observed id-frequency sketch (utils/histogram.py),
    attached by FFModel.attach_id_histograms / fit_stream collection, or
    a uniform default — under which dedup ~= dense and the hybrid
    placement never looks attractive, exactly right for unknown
    traffic."""
    from ..utils.histogram import IdFrequencySketch
    hist = getattr(op.model, "_id_histograms", {}).get(op.name)
    if hist is not None:
        return hist
    rows, _pack, tables = op._row_shard_geometry() \
        if hasattr(op, "_row_shard_geometry") else (op.num_entries, 1, 1)
    return IdFrequencySketch(rows * tables)


def expected_routed_lookups(op, pc, per_device_lookups: float) -> float:
    """THE skew term: how many lookup slots one device actually routes
    through the exchange per step under `pc`'s exchange/hot policy,
    from the op's observed id histogram.

    - hybrid (hot_fraction > 0): hot hits are served locally, so only
      the cold fraction routes;
    - dedup: duplicates collapse, so the routed count is the EXPECTED
      DISTINCT (cold) ids among the device's draws — the quantity
      ``IdFrequencySketch.expected_distinct`` computes.

    Uniform (no histogram) traffic makes dedup ~= dense on big tables
    and prices the hot set at its row fraction — so the search only
    reaches for these modes when the observed distribution rewards
    them."""
    rows, pack, tables = op._row_shard_geometry()
    pd = max(getattr(pc, "param_degree", 1), 1)
    hot = resolve_hot_rows(rows, pack, pd,
                           getattr(pc, "hot_fraction", 0.0)) \
        if getattr(op, "_hot_split_ok", False) else 0
    hist = _id_histogram(op)
    if getattr(pc, "exchange", "dense") == "dedup":
        return hist.expected_distinct(per_device_lookups,
                                      hot_rows_per_table=hot,
                                      rows_per_table=rows)
    if hot > 0:
        return per_device_lookups * (1.0 - hist.hot_mass(hot, rows,
                                                         tables))
    return per_device_lookups


def _a2a_payload_bytes(op, ndev: int, itemsize: int, pc=None):
    """Per-device all-to-all payloads for a row-sharded lookup under the
    balanced (production/ragged) exchange, for the simulator: (request
    ids, embedded rows back, gradient rows out). The (P−1)/P exchanged
    fraction is applied by CostModel.alltoall_time_axes per axis. With
    `pc`, the skew-aware exchange policies shrink the routed count
    (expected distinct / cold-only ids from the observed histogram)."""
    n_dev = _lookup_count(op) / max(ndev, 1)
    if pc is not None:
        n_dev = expected_routed_lookups(op, pc, n_dev)
    d = op.out_dim
    req = n_dev * 4.0                      # int32 row ids
    # embedded rows back: at the table's STORED width under a quantized
    # policy (int8/fp8 rows + one fp32 scale each ride the exchange —
    # ids route unchanged, the payload shrinks ~4x), else compute dtype
    from ..quant.policy import effective_policy
    pol = effective_policy(op, pc)
    if pol.is_default:
        rows = n_dev * d * float(itemsize)
    else:
        rows = n_dev * pol.row_bytes(d)
    grad = n_dev * (4.0 + d * 4.0)         # fp32 grad rows + positions
    return req, rows, grad


def expected_hot_distinct(op, pc, per_device_lookups: float) -> float:
    """Expected DISTINCT hot ids one device touches per step under
    `pc`'s hybrid placement — the hot update stream is pre-combined per
    hot id before the all-gather (parallel/alltoall._hot_combine), so
    this, not the raw hot-hit count, is what moves and what every
    replica scatters."""
    rows, pack, tables = op._row_shard_geometry()
    pd = max(getattr(pc, "param_degree", 1), 1)
    hot = resolve_hot_rows(rows, pack, pd,
                           getattr(pc, "hot_fraction", 0.0)) \
        if getattr(op, "_hot_split_ok", False) else 0
    if hot <= 0:
        return 0.0
    hist = _id_histogram(op)
    all_d = hist.expected_distinct(per_device_lookups)
    cold_d = hist.expected_distinct(per_device_lookups,
                                    hot_rows_per_table=hot,
                                    rows_per_table=rows)
    return min(max(all_d - cold_d, 0.0), float(hot * tables))


def hot_update_bytes(op, pc, ndev: int) -> float:
    """Per-device bytes of the hybrid placement's HOT update stream:
    the all-gathered fp32 per-hot-id partial sums (+ id/position) every
    replica applies in lockstep — priced like the replicated-table
    allreduce the simulator already knows, but only over the DISTINCT
    hot ids actually touched."""
    n_dev = _lookup_count(op) / max(ndev, 1)
    hot_d = expected_hot_distinct(op, pc, n_dev)
    return hot_d * (8.0 + op.out_dim * 4.0)


# hot fractions the search samples for the hybrid placement (resolved
# against each table's geometry; unresolvable ones are skipped)
_HOT_FRACTIONS = (1.0 / 64, 1.0 / 16)


def _row_shard_candidates(op, num_devices, feasible_degrees, nd):
    """PARAM-axis candidates for the MCMC search: rows split over pp
    shards, output data-parallel over the whole target mesh (the
    pod-scale shape the cost model trades against pure DP) — in the
    dense exchange, the dedup'd (unique-ids) exchange, and, for ops
    with a per-table hot split, the hot/cold hybrid placement. The
    skew term (expected_routed_lookups) is what lets the walk tell
    them apart: on uniform ids dense wins (dedup pays its sort for
    nothing), on zipfian ids dedup/hybrid win."""
    rows, pack, _ = op._row_shard_geometry()
    batch = op.inputs[0].shape[0]
    if batch % num_devices != 0 or op.aggr not in (AGGR_MODE_SUM,
                                                   AGGR_MODE_AVG):
        return []
    # the skew variants enter the walk ONLY when an observed histogram
    # is attached: without one the cost model assumes uniform ids,
    # under which dedup/hybrid price at best ~dense (minus the sort
    # overhead) — offering them would just dilute the walk. The
    # pipelined-exchange overlap flag is never a candidate here for the
    # same reason: it is a pure schedule toggle over the same bytes, so
    # mcmc.optimize flips it greedily on the annealed winner instead
    skewed = op.name in getattr(op.model, "_id_histograms", {})
    out = []
    for pp in feasible_degrees:
        if 1 < pp <= num_devices and rows % (pp * max(pack, 1)) == 0:
            degs = [1] * nd
            degs[0] = num_devices
            out.append(ParallelConfig(tuple(degs), param_degree=pp))
            if not skewed:
                continue
            out.append(ParallelConfig(tuple(degs), param_degree=pp,
                                      exchange="dedup"))
            if getattr(op, "_hot_split_ok", False):
                for frac in _HOT_FRACTIONS:
                    if resolve_hot_rows(rows, pack, pp, frac) > 0:
                        out.append(ParallelConfig(
                            tuple(degs), param_degree=pp,
                            exchange="dedup", hot_fraction=frac))
    return out


def _pallas_scatter_ok(model, out_dim: int, op_name: str = "") -> bool:
    """Gate for the Pallas RMW scatter kernel: XLA's TPU scatter lowers to
    a serialized loop (~250 ms for 2k rows on an 8M-row table)."""
    from .pallas.embedding_kernel import scatter_supports
    return _pallas_gate(model, op_name, scatter_supports(out_dim))


def _pallas_ok(model, out_dim: int, op_name: str = "") -> bool:
    """Gate for the Pallas row-streaming gather kernel."""
    from .pallas.embedding_kernel import supports
    return _pallas_gate(model, op_name, supports(out_dim))


class Embedding(Op):
    """Embedding bag: int indices (batch, bag) -> (batch, out_dim) with
    SUM/AVG aggregation, or (batch, bag, out_dim) with AGGR_MODE_NONE."""

    type_name = "Embed"
    # per-bag-slot (aggr="none") outputs work on the host-resident path
    host_aggr_none_ok = True

    def __init__(self, model, input_tensor, num_entries: int, out_dim: int,
                 aggr: str = AGGR_MODE_SUM, kernel_initializer=None,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        self.num_entries = int(num_entries)
        self.out_dim = int(out_dim)
        if aggr not in (AGGR_MODE_NONE, AGGR_MODE_SUM, AGGR_MODE_AVG):
            raise ValueError(f"bad aggr mode {aggr}")
        self.aggr = aggr
        self.kernel_initializer = kernel_initializer or GlorotUniform()
        batch = input_tensor.shape[0]
        if aggr == AGGR_MODE_NONE:
            out_shape = tuple(input_tensor.shape) + (self.out_dim,)
        else:
            out_shape = (batch, self.out_dim)
        self.outputs = [self._make_output(out_shape)]

    def param_defs(self) -> Dict[str, ParamDef]:
        H = getattr(self, "_hot_rows", 0)
        if H > 0:
            # hybrid placement (configure_row_shard resolved a hot
            # split): cold tail row-sharded, hot head replicated
            return {"kernel": ParamDef(
                        (self.num_entries - H, self.out_dim),
                        jnp.float32, self.kernel_initializer),
                    "hot_kernel": ParamDef(
                        (H, self.out_dim), jnp.float32,
                        self.kernel_initializer)}
        return {"kernel": ParamDef((self.num_entries, self.out_dim),
                                   jnp.float32, self.kernel_initializer)}

    def init_params(self, key):
        H = getattr(self, "_hot_rows", 0)
        if H <= 0:
            return super().init_params(key)
        # draw at the FULL logical shape with the same key the
        # non-hybrid build would use, then split — the hybrid table's
        # initial values are bitwise the baseline's
        keys = jax.random.split(key, 1)
        logical = self.kernel_initializer(
            keys[0], (self.num_entries, self.out_dim), jnp.float32)
        return {"kernel": logical[H:], "hot_kernel": logical[:H]}

    # ---- row/PARAM-axis sharding hooks (see configure_row_shard) -------
    _row_needs_2d_idx = True
    _hot_split_ok = True    # per-table hot/cold hybrid supported

    def _row_shard_geometry(self):
        return self.num_entries, getattr(self, "_pack", 1), 1

    def _row_route(self, g):
        """Flat global (wrapped) ids t*rows + ix -> the routed-lookup
        arrays (owner, local, gid, hot_id). Shared with
        EmbeddingBagStacked: each shard owns the same COLD row block of
        EVERY table; under the hybrid placement the per-table head
        (ix < hot rows) is served from the replicated hot block — those
        slots carry owner == nshards (excluded from the exchange), a
        gid in a disjoint key range (so the dedup machinery never
        merges them into a cold id's partial sum), and their flat
        hot-block row in hot_id (sentinel on cold slots)."""
        plan = self._row_plan
        rows = self.num_entries
        H = getattr(self, "_hot_rows", 0)
        rl = plan.rows_local
        ix = g % rows
        t = g // rows
        if H <= 0:
            return ((ix // rl).astype(jnp.int32),
                    (t * rl + ix % rl).astype(jnp.int32),
                    g.astype(jnp.int32), None)
        rc = rows - H
        is_hot = ix < H
        cix = jnp.maximum(ix - H, 0)
        owner = jnp.where(is_hot, plan.nshards,
                          cix // rl).astype(jnp.int32)
        local = jnp.where(is_hot, plan.flat_rows_local,
                          t * rl + cix % rl).astype(jnp.int32)
        hid = (t * H + ix).astype(jnp.int32)
        gid = jnp.where(is_hot, plan.tables * rc + hid,
                        t * rc + cix).astype(jnp.int32)
        hot_id = jnp.where(is_hot, hid,
                           plan.hot_rows_flat).astype(jnp.int32)
        return owner, local, gid, hot_id

    def _row_spec_block(self):
        from jax.sharding import PartitionSpec
        plan = self._row_plan
        return (PartitionSpec(plan.row_axes, None),
                (plan.rows_local, self.out_dim))

    def _hot_block_shape(self):
        return (getattr(self, "_hot_rows", 0), self.out_dim)

    def apply(self, params, xs, *, training=False, rng=None):
        (idx,) = xs
        table = params["kernel"]
        plan = _row_plan(self)
        if (plan is not None and idx.ndim == 2
                and idx.shape[0] % plan.ndev == 0):
            from ..parallel.alltoall import row_sharded_bag_lookup
            g = idx.astype(jnp.int32) % self.num_entries
            owner, local, gid, hot_id = self._row_route(g)
            spec, block = self._row_spec_block()
            return [row_sharded_bag_lookup(
                plan, table, spec, owner, local, self.out_dim,
                self.aggr, block, gid=gid,
                hot_table=params.get("hot_kernel"), hot_id=hot_id,
                hot_block_shape=self._hot_block_shape())]
        if (self.aggr in (AGGR_MODE_SUM, AGGR_MODE_AVG) and idx.ndim == 2
                and _pallas_ok(self.model, self.out_dim, self.name)):
            from .pallas.embedding_kernel import embedding_bag
            return [embedding_bag(table, idx, self.aggr)]
        # mode="wrap": modulo-index gather — scalar-only constants, so the
        # trace stays valid under compute_on host offload (the reference's
        # CUDA gather does no bounds handling at all, embedding.cu:173-224)
        rows = jnp.take(table, idx.astype(jnp.int32), axis=0,
                        mode="wrap")  # (..., bag, d)
        if self.aggr == AGGR_MODE_SUM:
            rows = jnp.sum(rows, axis=-2)
        elif self.aggr == AGGR_MODE_AVG:
            rows = jnp.mean(rows, axis=-2)
        return [rows]

    def candidate_parallel_configs(self, num_devices, feasible_degrees):
        """Sample DP × width-sharded table, plus PARAM-axis row sharding
        (DP output over the whole mesh, rows split over pp shards with
        all-to-all lookup routing). (Reference partitions only the
        sample dim, embedding.cu:115-117.)"""
        out = []
        nd = self.outputs[0].num_dims
        for ds in feasible_degrees:
            for dc in feasible_degrees:
                if ds * dc <= num_devices and self.out_dim % max(dc, 1) == 0:
                    degs = [1] * nd
                    degs[0] = ds
                    degs[-1] = dc
                    out.append(ParallelConfig(tuple(degs)))
        out.extend(_row_shard_candidates(self, num_devices,
                                         feasible_degrees, nd))
        out.append(_zcm_candidate(nd))
        return out

    def param_axes(self, pc: ParallelConfig, out_axes,
                   raw_pc=None):
        if _row_plan(self) is not None:
            axes = {"kernel": (self._row_plan.row_axes, ())}
            if getattr(self, "_hot_rows", 0) > 0:
                axes["hot_kernel"] = ((), ())   # replicated hot head
            return axes
        # width sharding follows the output channel axes; rows replicated
        ch = out_axes[-1] if len(out_axes) >= 2 else ()
        return {"kernel": ((), ch)}

    def flops_per_sample(self) -> float:
        bag = self.inputs[0].shape[-1] if self.inputs[0].num_dims > 1 else 1
        return float(bag * self.out_dim)  # bandwidth-bound; count adds

    def param_shard_shapes(self, pc: ParallelConfig, ndev=None):
        pd = max(getattr(pc, "param_degree", 1), 1)
        if pd > 1:
            # row sharding: each shard holds cold_rows/pd full-width
            # rows (+ the whole replicated hot head under the hybrid)
            H = resolve_hot_rows(self.num_entries,
                                 getattr(self, "_pack", 1), pd,
                                 getattr(pc, "hot_fraction", 0.0))
            out = {"kernel": (max((self.num_entries - H) // pd, 1),
                              self.out_dim)}
            if H > 0:
                out["hot_kernel"] = (H, self.out_dim)
            return out
        # width sharding splits out_dim by the last degree
        dc = pc.degrees[-1] if len(pc.degrees) > 1 else 1
        return {"kernel": (self.num_entries, max(self.out_dim // dc, 1))}


    def random_hbm_rows(self, backward: bool = False,
                        raw: bool = False) -> float:
        return _embedding_random_rows(self, backward, raw)

    def update_random_hbm_rows(self, pc=None) -> float:
        return _embedding_update_rows(self, pc)

    def alltoall_payload_bytes(self, ndev: int, itemsize: int, pc=None):
        return _a2a_payload_bytes(self, ndev, itemsize, pc=pc)

    def param_bytes_touched_per_step(self, num_parts: int = 1) -> int:
        if not _sparse_update_active(self):
            return self.param_bytes()   # dense grad+update streams the table
        # gather read + sparse-update read/write of this shard's rows only
        batch = self.inputs[0].shape[0]
        bag = self.inputs[0].shape[-1] if self.inputs[0].num_dims > 1 else 1
        return int(_touched_bytes_factor(self) * batch * bag
                   * self.out_dim * 4 // max(num_parts, 1))

    # ---- sparse (touched-rows-only) SGD update -------------------------
    # The dense path materializes a gradient the size of the whole table
    # (XLA scatter-add of row cotangents into zeros — the functional analog
    # of the reference's table-sized gradient region, embedding.cu:95-105)
    # and then streams the full table through the SGD update. For plain SGD
    # that traffic is avoidable: dense grad rows are zero except gathered
    # rows, so  w -= lr*grad  ==  scatter_add(w, idx, -lr*row_ct)  exactly
    # (duplicate indices accumulate in both). model._build_steps routes
    # eligible embeddings through this method.
    def supports_sparse_update(self) -> bool:
        return self.aggr in (AGGR_MODE_SUM, AGGR_MODE_AVG, AGGR_MODE_NONE)

    def _fwd_residual_ok(self) -> bool:
        """Forward-gather residuals are usable only when a logical row IS
        one 128-lane tile (out_dim == 128, unpacked storage): then the
        rows the XLA-gather forward materializes anyway double as the
        update's weight tiles, sparing the update's random re-read. (The
        lane-packed variants cover narrower widths; see
        EmbeddingBagStacked._fwd_residual_ok.)"""
        return (self.out_dim == 128
                and getattr(self, "_pack", 1) == 1
                and self.aggr in (AGGR_MODE_SUM, AGGR_MODE_AVG)
                and self.inputs[0].num_dims == 2
                and _row_plan(self) is None
                and not _pallas_ok(self.model, self.out_dim, self.name)
                and _pallas_scatter_ok(self.model, 128, self.name)
                and _row_shard_axes(self, self.out_dim, self.num_entries)
                is None)

    def apply_with_fwd(self, params, xs, *, rng=None):
        """apply() plus forward-gather residuals (global rows + tiles);
        None residuals = caller should treat as plain apply."""
        if not self._fwd_residual_ok():
            return self.apply(params, xs, training=True, rng=rng), None
        (idx,) = xs
        table = params["kernel"]
        g = idx.astype(jnp.int32) % self.num_entries   # (batch, bag)
        rows = jnp.take(table, g, axis=0)              # (batch, bag, 128)
        out = (jnp.mean(rows, axis=-2) if self.aggr == AGGR_MODE_AVG
               else jnp.sum(rows, axis=-2))
        return [out], (g.reshape(-1), rows.reshape(-1, 128))

    def sparse_sgd_update(self, params, xs, out_ct, lr,
                          fwd=None):
        """params - lr * d(loss)/d(table), given out_ct = d(loss)/d(output).
        Touches only the gathered rows."""
        (idx,) = xs
        tbl = params["kernel"]
        idx = idx.astype(jnp.int32) % self.num_entries  # match wrap gather
        d = self.out_dim
        ct = out_ct.astype(tbl.dtype)
        if self.aggr == AGGR_MODE_AVG:
            ct = ct / idx.shape[-1]
        if self.aggr == AGGR_MODE_NONE:
            upd = ct.reshape(-1, d)                     # (batch*bag, d)
        else:
            # each row of the bag receives the bag-sum's cotangent
            upd = jnp.broadcast_to(ct[..., None, :],
                                   idx.shape + (d,)).reshape(-1, d)
        plan = _row_plan(self)
        if plan is not None and idx.size % plan.ndev == 0:
            # row-sharded: gradient rows route to their owning shard
            # (all-to-all) and apply there, in canonical order; hybrid
            # hot rows apply in lockstep from an all-gather
            from ..parallel.alltoall import row_sharded_sgd_update
            owner, local, gid, hot_id = self._row_route(idx.reshape(-1))
            spec, _ = self._row_spec_block()
            out = row_sharded_sgd_update(
                plan, tbl, spec, owner, local, upd, lr, d, gid=gid,
                hot_table=params.get("hot_kernel"), hot_id=hot_id)
            if hot_id is None:
                return {"kernel": out}
            new, new_hot = out
            return {"kernel": new, "hot_kernel": new_hot}
        if fwd is not None and self._fwd_residual_ok():
            # write-only path: the forward's gathered rows are the tiles,
            # so new rows land without the RMW read
            from .pallas.embedding_kernel import scatter_write_rows_packed
            g_flat, tiles = fwd
            new = scatter_write_rows_packed(tbl, g_flat, -lr * upd,
                                            tiles, d)
            return {"kernel": new}
        if _pallas_scatter_ok(self.model, d, self.name):
            from .pallas.embedding_kernel import scatter_add_rows
            new = scatter_add_rows(tbl, idx.reshape(-1), -lr * upd)
        else:
            new = tbl.at[idx.reshape(-1)].add(-lr * upd)
        return {"kernel": new}

    def sparse_opt_update(self, params, xs, out_ct, opt, slabs, step,
                          fwd=None):
        """Stateful touched-rows update (lazy momentum / Adam): the dense
        update streams the whole table + state slabs (reference
        optimizer_kernel.cu adam_update world); this touches only the
        gathered rows' weights AND state."""
        (idx,) = xs
        tbl = params["kernel"]
        idx = idx.astype(jnp.int32) % self.num_entries
        d = self.out_dim
        ct = out_ct.astype(jnp.float32)
        if self.aggr == AGGR_MODE_AVG:
            ct = ct / idx.shape[-1]
        if self.aggr == AGGR_MODE_NONE:
            upd = ct.reshape(-1, d)
        else:
            upd = jnp.broadcast_to(ct[..., None, :],
                                   idx.shape + (d,)).reshape(-1, d)
        fwd_tiles = (fwd[1] if fwd is not None and self._fwd_residual_ok()
                     else None)
        kslabs, hslabs, nested = _norm_slabs(slabs)
        out = _sparse_opt_update(self, tbl, idx.reshape(-1), upd,
                                 opt, kslabs, step,
                                 self.num_entries, fwd_tiles,
                                 hot_tbl=params.get("hot_kernel"),
                                 hot_slabs=hslabs)
        return _finish_opt_update(out, nested)

    # ---- delta publication (utils/delta.py) ----------------------------
    # A batch's lookup indices mapped to the rows of the STORED kernel
    # (flattened to 2-D over all-but-the-last axis) that a touched-rows
    # update can change. The continual-learning publisher restricts its
    # publish-time diff to these candidates; serving's EmbeddingCache
    # uses the host variant to invalidate only dirtied samples.
    def delta_touched_rows(self, idx_np) -> "np.ndarray":
        import numpy as np
        g = np.asarray(idx_np).astype(np.int64).reshape(-1) \
            % self.num_entries
        H = getattr(self, "_hot_rows", 0)
        if H > 0:
            # "kernel" stores only the cold tail under the hybrid
            # placement; the (small) replicated hot block stays
            # untracked — the publisher diffs it whole
            g = g[g >= H] - H
        return np.unique(g)

    def host_delta_touched_rows(self, idx_np) -> "np.ndarray":
        # host table is (num_entries, out_dim) — same natural layout
        # (host-resident tables never row-shard, so never hybrid)
        import numpy as np
        g = np.asarray(idx_np).astype(np.int64).reshape(-1) \
            % self.num_entries
        return np.unique(g)

    def flat_lookup_ids(self, idx_np) -> "np.ndarray":
        """Batch indices -> flat lookup-id space, for the id-frequency
        sketch (utils/histogram.py) collected at staging."""
        import numpy as np
        return (np.asarray(idx_np).astype(np.int64).reshape(-1)
                % self.num_entries)

    # ---- host-resident table form (reference embedding_avx2.cc) --------
    def host_init(self, seed: int):
        return {"kernel": _host_init_table(
            self.kernel_initializer, (self.num_entries, self.out_dim), seed)}

    def host_flat_indices(self, idx_np):
        """Per-sample FLAT row ids, shaped (batch, 1, bag) — the shared
        geometry the host lookup and the serving shard tier
        (serve/shardtier.py) route lookups through."""
        import numpy as np
        g = idx_np.astype(np.int64) % self.num_entries
        if g.ndim == 1:
            g = g[:, None]
        return g[:, None, :]

    def host_lookup_rows(self, rows_2d, g3):
        """``host_lookup`` against an arbitrary (rows, d) row matrix
        with already-remapped flat indices: the shard tier assembles
        fetched shard rows through this, so a sharded lookup is
        bit-identical to the local host path (same gather, same bag
        reduction, same order)."""
        import numpy as np
        if self.aggr == AGGR_MODE_NONE:
            # per-bag-slot outputs: no reduction, (batch, bag, d)
            return np.ascontiguousarray(rows_2d[g3[:, 0]], np.float32)
        return _host_bag_lookup(rows_2d, g3, self.aggr)[:, 0]  # (batch,d)

    def host_lookup(self, host_params, idx_np):
        return self.host_lookup_rows(host_params["kernel"],
                                     self.host_flat_indices(idx_np))

    def host_sgd_update(self, host_params, idx_np, ct_np, lr):
        import numpy as np
        g = idx_np.astype(np.int64) % self.num_entries
        if g.ndim == 1:
            g = g[:, None]
        if self.aggr == AGGR_MODE_NONE:
            # ct (batch, bag, d): each slot's cotangent lands on its row
            d = self.out_dim
            np.add.at(host_params["kernel"], g.reshape(-1),
                      -lr * ct_np.reshape(-1, d))
            return
        _host_bag_update(host_params["kernel"], g[:, None, :],
                         ct_np[:, None, :], lr, self.aggr)

    def host_opt_update(self, host_params, idx_np, ct_np, opt, slabs,
                        step):
        """Lazy stateful (momentum/Adam) host update; see
        _host_stateful_update."""
        import numpy as np
        g = idx_np.astype(np.int64) % self.num_entries
        if g.ndim == 1:
            g = g[:, None]
        if self.aggr == AGGR_MODE_NONE:
            uniq, summed = _host_dedup_rows(
                g.reshape(-1), ct_np.reshape(-1, self.out_dim))
            tbl = host_params["kernel"]
            slab_rows = {k: v[uniq] for k, v in slabs.items()}
            wn, sn = opt.sparse_row_update_np(tbl[uniq], summed,
                                              slab_rows, step)
            tbl[uniq] = wn
            for k in slabs:
                slabs[k][uniq] = sn[k]
            return
        _host_stateful_update(host_params["kernel"], g[:, None, :],
                              ct_np[:, None, :], opt, slabs, step,
                              self.aggr)


class EmbeddingBagStacked(Op):
    """N same-shape embedding bags fused into one (N, rows, dim) parameter.

    This is the TPU-native form of the reference DLRM strategy "each table
    whole on one device" (dlrm_strategy.cc:252-256): shard dim 0 (the table
    dim) over mesh axes; each device holds num_tables/parts full tables,
    looks up the *global* batch for its tables, and the downstream
    batch-dim resharding is the all-to-all the reference got implicitly
    from Legion region movement. XLA emits that collective from the
    sharding constraints alone.

    input: int (batch, num_tables, bag)  ->  output (batch, num_tables, dim)
    """

    type_name = "EmbedStack"

    def __init__(self, model, input_tensor, num_tables: int, num_entries: int,
                 out_dim: int, aggr: str = AGGR_MODE_SUM,
                 kernel_initializer=None, name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        assert input_tensor.num_dims == 3, "expect (batch, num_tables, bag)"
        assert input_tensor.shape[1] == num_tables
        self.num_tables = int(num_tables)
        self.num_entries = int(num_entries)
        self.out_dim = int(out_dim)
        self.aggr = aggr
        self.kernel_initializer = kernel_initializer or GlorotUniform()
        # lane packing: narrow rows (d < 128 dividing 128) are stored
        # r-per-128-lane-tile as (T, rows/r, r*d) so the TPU keeps the
        # natural row-major tiled layout — an unpacked (rows, d) table gets
        # a transposed lane-packing layout from XLA, which forces
        # whole-table transpose copies at every Pallas kernel boundary
        self._pack = _pack_factor(self.out_dim, self.num_entries)
        batch = input_tensor.shape[0]
        self.outputs = [self._make_output((batch, self.num_tables, self.out_dim))]
        # storage permutation honoring strategy device_ids: stored slot s
        # holds LOGICAL table _table_order[s], so block-sharding dim 0
        # reproduces the reference's per-table device assignment
        # (dlrm_strategy.cc:242-296 round-robins table i to device i%N;
        # mapper.cc:33-97 places point tasks there). None = identity.
        self._table_order = None
        self._table_inv = None

    def set_table_order(self, order):
        """Storage order for the stacked tables (see __init__)."""
        order = tuple(int(t) for t in order)
        if sorted(order) != list(range(self.num_tables)):
            raise ValueError(f"not a table permutation: {order}")
        if order == tuple(range(self.num_tables)):
            self._table_order = self._table_inv = None
            return
        inv = [0] * self.num_tables
        for s, t in enumerate(order):
            inv[t] = s
        self._table_order = jnp.asarray(order, jnp.int32)
        self._table_inv = jnp.asarray(inv, jnp.int32)

    def param_defs(self):
        r = self._pack
        H = getattr(self, "_hot_rows", 0)
        if H > 0:
            return {"kernel": ParamDef(
                        (self.num_tables, (self.num_entries - H) // r,
                         self.out_dim * r),
                        jnp.float32, self.kernel_initializer),
                    "hot_kernel": ParamDef(
                        (self.num_tables, H // r, self.out_dim * r),
                        jnp.float32, self.kernel_initializer)}
        return {"kernel": ParamDef(
            (self.num_tables, self.num_entries // r, self.out_dim * r),
            jnp.float32, self.kernel_initializer)}

    def init_params(self, key):
        # initialize each table at its LOGICAL (rows, d) shape so
        # shape-dependent initializers (Glorot fans) match the unfused
        # per-table ops, then pack
        keys = jax.random.split(key, self.num_tables)
        tables = jnp.stack([
            self.kernel_initializer(
                k, (self.num_entries, self.out_dim), jnp.float32)
            for k in keys])
        H = getattr(self, "_hot_rows", 0)
        if H <= 0:
            return {"kernel": self.pack_kernel(tables)}
        # hybrid: the SAME draws split into the replicated hot head and
        # the row-sharded cold tail — bitwise the baseline's values
        r, d = self._pack, self.out_dim
        if self._table_order is not None:
            tables = jnp.take(tables, self._table_order, axis=0)
        return {"kernel": tables[:, H:].reshape(
                    self.num_tables, (self.num_entries - H) // r, r * d),
                "hot_kernel": tables[:, :H].reshape(
                    self.num_tables, H // r, r * d)}

    def unpack_kernel(self, kernel):
        """(T, rows/r, r*d) stored form -> logical (T, rows, d)."""
        logical = kernel.reshape(self.num_tables, self.num_entries,
                                 self.out_dim)
        if self._table_order is not None:
            logical = jnp.take(logical, self._table_inv, axis=0)
        return logical

    def pack_kernel(self, logical):
        r = self._pack
        if self._table_order is not None:
            logical = jnp.take(logical, self._table_order, axis=0)
        return logical.reshape(self.num_tables, self.num_entries // r,
                               self.out_dim * r)

    # ---- row/PARAM-axis sharding hooks (see configure_row_shard) -------
    _hot_split_ok = True    # uniform tables: per-table hot/cold split

    def _row_shard_geometry(self):
        return self.num_entries, self._pack, self.num_tables

    _row_route = Embedding._row_route

    def _row_spec_block(self):
        from jax.sharding import PartitionSpec
        plan = self._row_plan
        r = self._pack
        return (PartitionSpec(None, plan.row_axes, None),
                (self.num_tables, plan.rows_local // r,
                 self.out_dim * r))

    def _hot_block_shape(self):
        r = self._pack
        return (self.num_tables, getattr(self, "_hot_rows", 0) // r,
                self.out_dim * r)

    def apply(self, params, xs, *, training=False, rng=None):
        (idx,) = xs  # (batch, T, bag)
        table = params["kernel"]  # (T, rows/r, r*d)
        idx = idx.astype(jnp.int32) % self.num_entries
        if self._table_order is not None:
            idx = jnp.take(idx, self._table_order, axis=1)
        r, d = self._pack, self.out_dim

        plan = _row_plan(self)
        if plan is not None and idx.shape[0] % plan.ndev == 0:
            # row-sharded lookup: indices route to owning shards over
            # the mesh's row axes, embedded rows route back
            from ..parallel.alltoall import row_sharded_bag_lookup
            rows = self.num_entries
            offs = (jnp.arange(self.num_tables, dtype=jnp.int32)
                    * rows)[None, :, None]
            owner, local, gid, hot_id = self._row_route(idx + offs)
            spec, block = self._row_spec_block()
            out = row_sharded_bag_lookup(
                plan, table, spec, owner, local, d, self.aggr, block,
                gid=gid, hot_table=params.get("hot_kernel"),
                hot_id=hot_id, hot_block_shape=self._hot_block_shape())
            if self._table_inv is not None:
                out = jnp.take(out, self._table_inv, axis=1)
            return [out]

        if (self.aggr in (AGGR_MODE_SUM, AGGR_MODE_AVG) and r == 1
                and _pallas_ok(self.model, self.out_dim, self.name)):
            from .pallas.embedding_kernel import stacked_embedding_bag
            out = stacked_embedding_bag(table, idx, self.aggr)
        else:
            # vmap over the table dim: for each table t, gather its own
            # rows for the full batch. With dim-0 sharded params + matching
            # sharding constraints this lowers to per-device local gathers
            # + all-to-all.
            def one_table(tbl, ix):  # tbl (rows/r, r*d), ix (batch, bag)
                if r == 1:
                    rows = jnp.take(tbl, ix, axis=0, mode="wrap")
                else:
                    rows = _packed_gather(tbl, ix, r, d)   # (batch, bag, d)
                if self.aggr == AGGR_MODE_AVG:
                    return jnp.mean(rows, axis=1)
                return jnp.sum(rows, axis=1)

            out = jax.vmap(one_table, in_axes=(0, 1), out_axes=1)(table, idx)
        if self._table_order is not None:
            out = jnp.take(out, self._table_inv, axis=1)
        return [out]  # (batch, T, d) in LOGICAL table order

    def candidate_parallel_configs(self, num_devices, feasible_degrees):
        # partition the table dim (dim 1 of the output) and/or sample
        # dim, plus PARAM-axis row sharding of every table
        out = []
        for ds in feasible_degrees:
            for dt in feasible_degrees:
                if ds * dt <= num_devices and self.num_tables % max(dt, 1) == 0:
                    out.append(ParallelConfig((ds, dt, 1)))
        out.extend(_row_shard_candidates(self, num_devices,
                                         feasible_degrees, 3))
        out.append(_zcm_candidate(3))
        return out

    def param_axes(self, pc: ParallelConfig, out_axes,
                   raw_pc=None):
        if _row_plan(self) is not None:
            # rows of EVERY table block-shard over the row axes; the
            # table dim stays whole on each shard (the hybrid hot head
            # is replicated everywhere)
            axes = {"kernel": ((), self._row_plan.row_axes, ())}
            if getattr(self, "_hot_rows", 0) > 0:
                axes["hot_kernel"] = ((), (), ())
            return axes
        # table dim of the param follows output dim 1's axes
        t_axes = out_axes[1] if len(out_axes) >= 2 else ()
        return {"kernel": (t_axes, (), ())}

    def flops_per_sample(self) -> float:
        bag = self.inputs[0].shape[-1]
        return float(self.num_tables * bag * self.out_dim)

    def input_shard_shapes(self, pc: ParallelConfig):
        # indices follow the output's (sample, table) sharding so measured
        # microbenchmarks trace at consistent per-device shapes
        ds = max(pc.degrees[0] if pc.degrees else 1, 1)
        dt = pc.degrees[1] if len(pc.degrees) > 1 else 1
        batch, T, bag = self.inputs[0].shape
        return [(max(batch // ds, 1), max(T // max(dt, 1), 1), bag)]

    def param_shard_shapes(self, pc: ParallelConfig, ndev=None):
        r = self._pack
        pd = max(getattr(pc, "param_degree", 1), 1)
        if pd > 1:
            # row sharding: all T tables present, cold_rows/pd of each
            # (+ the whole replicated hot head under the hybrid)
            H = resolve_hot_rows(self.num_entries, r, pd,
                                 getattr(pc, "hot_fraction", 0.0))
            out = {"kernel": (self.num_tables,
                              max((self.num_entries - H) // r // pd, 1),
                              self.out_dim * r)}
            if H > 0:
                out["hot_kernel"] = (self.num_tables, H // r,
                                     self.out_dim * r)
            return out
        # table-dim sharding by degrees[1]
        dt = pc.degrees[1] if len(pc.degrees) > 1 else 1
        return {"kernel": (max(self.num_tables // dt, 1),
                           self.num_entries // r, self.out_dim * r)}


    def random_hbm_rows(self, backward: bool = False,
                        raw: bool = False) -> float:
        return _embedding_random_rows(self, backward, raw)

    def update_random_hbm_rows(self, pc=None) -> float:
        return _embedding_update_rows(self, pc)

    def alltoall_payload_bytes(self, ndev: int, itemsize: int, pc=None):
        return _a2a_payload_bytes(self, ndev, itemsize, pc=pc)

    def param_bytes_touched_per_step(self, num_parts: int = 1) -> int:
        if not _sparse_update_active(self):
            return self.param_bytes()
        batch, _, bag = self.inputs[0].shape
        return int(_touched_bytes_factor(self) * batch * self.num_tables
                   * bag * self.out_dim * 4 // max(num_parts, 1))

    # ---- sparse (touched-rows-only) SGD update (see Embedding) ---------
    def supports_sparse_update(self) -> bool:
        return self.aggr in (AGGR_MODE_SUM, AGGR_MODE_AVG)

    def _fwd_residual_ok(self) -> bool:
        """Whether the packed-gather forward can hand its tiles to a
        write-only sparse update (single chip, lane-packed storage, the
        Pallas scatter available, XLA gather path in use)."""
        return (self._pack > 1
                and self.aggr in (AGGR_MODE_SUM, AGGR_MODE_AVG)
                and _row_plan(self) is None
                and not _pallas_ok(self.model, self.out_dim, self.name)
                and _pallas_scatter_ok(self.model, 128, self.name)
                and _row_shard_axes(
                    self, self.out_dim,
                    self.num_tables * self.num_entries // self._pack)
                is None)

    def apply_with_fwd(self, params, xs, *, rng=None):
        """apply() plus forward-gather residuals (global unpacked rows +
        packed tiles): random HBM rows are latency-bound, so keeping the
        1 MB of gathered tiles lets the sparse update WRITE new rows
        without re-reading them — halving the update's random accesses
        vs the RMW kernel. Returns
        (outs, fwd|None); None = caller should treat as plain apply."""
        if not self._fwd_residual_ok():
            return self.apply(params, xs, training=True, rng=rng), None
        (idx,) = xs
        table = params["kernel"]
        r, d = self._pack, self.out_dim
        T, rows = self.num_tables, self.num_entries
        view = table.reshape(T * rows // r, r * d)
        with jax.named_scope("index"):
            idx = idx.astype(jnp.int32) % self.num_entries
            if self._table_order is not None:
                idx = jnp.take(idx, self._table_order, axis=1)
            offs = (jnp.arange(T, dtype=jnp.int32) * rows)[None, :, None]
            g = idx + offs                             # (batch, T, bag)
        rows_g, _, tiles = _packed_gather_tiles(view, g, r, d)
        out = (jnp.mean(rows_g, axis=2) if self.aggr == AGGR_MODE_AVG
               else jnp.sum(rows_g, axis=2))
        if self._table_order is not None:
            out = jnp.take(out, self._table_inv, axis=1)
        return [out], (g.reshape(-1), tiles)

    def sparse_sgd_update(self, params, xs, out_ct, lr, fwd=None):
        (idx,) = xs                       # (batch, T, bag)
        tbl = params["kernel"]            # (T, rows/r, r*d)
        idx = idx.astype(jnp.int32) % self.num_entries
        ct = out_ct.astype(tbl.dtype)     # (batch, T, d)
        if self._table_order is not None:
            # stored slot s holds logical table _table_order[s]
            idx = jnp.take(idx, self._table_order, axis=1)
            ct = jnp.take(ct, self._table_order, axis=1)
        if self.aggr == AGGR_MODE_AVG:
            ct = ct / idx.shape[-1]
        r, d = self._pack, self.out_dim
        T, rows = self.num_tables, self.num_entries

        plan = _row_plan(self)
        if plan is not None and idx.size % plan.ndev == 0:
            from ..parallel.alltoall import row_sharded_sgd_update
            offs = (jnp.arange(T, dtype=jnp.int32) * rows)[None, :, None]
            owner, local, gid, hot_id = self._row_route(
                (idx + offs).reshape(-1))
            upd = jnp.broadcast_to(
                ct[..., None, :], idx.shape + (d,)).reshape(-1, d)
            spec, _ = self._row_spec_block()
            out = row_sharded_sgd_update(
                plan, tbl, spec, owner, local, upd, lr, d, gid=gid,
                hot_table=params.get("hot_kernel"), hot_id=hot_id)
            if hot_id is None:
                return {"kernel": out}
            new, new_hot = out
            return {"kernel": new, "hot_kernel": new_hot}

        if fwd is not None and self._fwd_residual_ok():
            # write-only path: fwd tiles + summed deltas -> pure scatter
            # writes (apply_with_fwd produced g in the SAME permuted
            # (batch, T, bag) order as idx/ct here)
            from .pallas.embedding_kernel import scatter_write_rows_packed
            g_flat, tiles = fwd
            upd = jnp.broadcast_to(
                ct[..., None, :], idx.shape + (d,)).reshape(-1, d)
            new = scatter_write_rows_packed(
                tbl.reshape(T * rows // r, r * d), g_flat, -lr * upd,
                tiles, d)
            return {"kernel": new.reshape(tbl.shape)}

        shard_axes = _row_shard_axes(self, d, T * rows // r)
        if shard_axes is not None:
            # multi-chip: table-dim-sharded packed view; every shard masks
            # the global updates to its row block and runs the local RMW
            # kernel under shard_map
            from .pallas.embedding_kernel import sharded_scatter_add_packed
            offs = (jnp.arange(T, dtype=jnp.int32) * rows)[None, :, None]
            gidx = (idx + offs).reshape(-1)
            upd = jnp.broadcast_to(
                ct[..., None, :], idx.shape + (d,)).reshape(-1, d)
            new = sharded_scatter_add_packed(
                self.model.mesh, shard_axes,
                tbl.reshape(T * rows // r, r * d), gidx, -lr * upd, d)
            return {"kernel": new.reshape(tbl.shape)}
        if _pallas_scatter_ok(self.model, d if r == 1 else 128, self.name):
            # one fused scatter over the packed (T*rows/r, 128|r*d) view;
            # global unpacked row g = t*rows + ix keeps g//r, g%r aligned
            # with the per-table packing because rows % r == 0
            from .pallas.embedding_kernel import (scatter_add_rows,
                                                  scatter_add_rows_packed)
            offs = (jnp.arange(T, dtype=jnp.int32) * rows)[None, :, None]
            gidx = (idx + offs).reshape(-1)
            upd = jnp.broadcast_to(
                ct[..., None, :], idx.shape + (d,)).reshape(-1, d)
            view = tbl.reshape(T * rows // r, r * d)
            if r == 1:
                new = scatter_add_rows(view, gidx, -lr * upd)
            else:
                new = scatter_add_rows_packed(view, gidx, -lr * upd, d)
            return {"kernel": new.reshape(tbl.shape)}

        def one_table(t, ix, c):   # (rows/r, r*d), (batch,bag), (batch,d)
            upd = jnp.broadcast_to(c[:, None, :], ix.shape + (d,))
            tu = t.reshape(rows, d)
            tu = tu.at[ix.reshape(-1)].add(-lr * upd.reshape(-1, d))
            return tu.reshape(t.shape)

        new = jax.vmap(one_table, in_axes=(0, 1, 1))(tbl, idx, ct)
        return {"kernel": new}

    def sparse_opt_update(self, params, xs, out_ct, opt, slabs, step,
                          fwd=None):
        """Stateful touched-rows update (lazy momentum / Adam) on the
        fused stacked tables; see Embedding.sparse_opt_update."""
        (idx,) = xs                       # (batch, T, bag)
        tbl = params["kernel"]            # (T, rows/r, r*d)
        idx = idx.astype(jnp.int32) % self.num_entries
        ct = out_ct.astype(jnp.float32)   # (batch, T, d)
        if self._table_order is not None:
            idx = jnp.take(idx, self._table_order, axis=1)
            ct = jnp.take(ct, self._table_order, axis=1)
        if self.aggr == AGGR_MODE_AVG:
            ct = ct / idx.shape[-1]
        d = self.out_dim
        T, rows = self.num_tables, self.num_entries
        offs = (jnp.arange(T, dtype=jnp.int32) * rows)[None, :, None]
        g = (idx + offs).reshape(-1)
        upd = jnp.broadcast_to(ct[..., None, :],
                               idx.shape + (d,)).reshape(-1, d)
        fwd_tiles = fwd[1] if fwd is not None else None
        kslabs, hslabs, nested = _norm_slabs(slabs)
        out = _sparse_opt_update(self, tbl, g, upd, opt, kslabs,
                                 step, T * rows, fwd_tiles,
                                 hot_tbl=params.get("hot_kernel"),
                                 hot_slabs=hslabs)
        return _finish_opt_update(out, nested)

    # ---- delta publication (utils/delta.py; see Embedding) -------------
    def delta_touched_rows(self, idx_np) -> "np.ndarray":
        # stored kernel (T, rows/r, r*d) flattens to (T*rows/r, r*d);
        # logical table t lives at stored slot _table_inv[t], logical row
        # ix at packed row ix // r of that slot
        import numpy as np
        r, rows = self._pack, self.num_entries
        g = np.asarray(idx_np).astype(np.int64) % rows    # (batch, T, bag)
        slot = np.arange(self.num_tables, dtype=np.int64)
        if self._table_inv is not None:
            slot = np.asarray(self._table_inv, dtype=np.int64)
        H = getattr(self, "_hot_rows", 0)
        if H > 0:
            # hybrid: "kernel" stores only the cold tail; the (small)
            # replicated hot block stays untracked — diffed whole
            flat = slot[None, :, None] * ((rows - H) // r) + (g - H) // r
            return np.unique(flat.reshape(-1)[g.reshape(-1) >= H])
        flat = slot[None, :, None] * (rows // r) + g // r
        return np.unique(flat.reshape(-1))

    def flat_lookup_ids(self, idx_np) -> "np.ndarray":
        """Batch indices -> flat t*rows + ix lookup ids, for the
        id-frequency sketch collected at staging."""
        import numpy as np
        rows = self.num_entries
        g = np.asarray(idx_np).astype(np.int64) % rows
        offs = (np.arange(self.num_tables, dtype=np.int64)
                * rows)[None, :, None]
        return (g + offs).reshape(-1)

    def host_delta_touched_rows(self, idx_np) -> "np.ndarray":
        # host table is (T, rows, d) in LOGICAL table order, unpacked
        import numpy as np
        rows = self.num_entries
        g = np.asarray(idx_np).astype(np.int64) % rows
        offs = (np.arange(self.num_tables, dtype=np.int64)
                * rows)[None, :, None]
        return np.unique((g + offs).reshape(-1))

    # ---- host-resident table form (reference embedding_avx2.cc) --------
    def host_init(self, seed: int):
        return {"kernel": _host_init_table(
            self.kernel_initializer,
            (self.num_tables, self.num_entries, self.out_dim), seed)}

    def host_flat_indices(self, idx_np):
        """Per-sample FLAT row ids, (batch, T, bag), into the (T*rows, d)
        flattened host table — shared with the serving shard tier."""
        import numpy as np
        rows = self.num_entries
        offs = (np.arange(self.num_tables, dtype=np.int64)
                * rows)[None, :, None]
        return idx_np.astype(np.int64) % rows + offs      # (batch, T, bag)

    def host_lookup_rows(self, rows_2d, g3):
        """See :meth:`Embedding.host_lookup_rows`."""
        return _host_bag_lookup(rows_2d, g3, self.aggr)

    def host_lookup(self, host_params, idx_np):
        T, rows, d = host_params["kernel"].shape
        return self.host_lookup_rows(
            host_params["kernel"].reshape(T * rows, d),
            self.host_flat_indices(idx_np))

    def host_sgd_update(self, host_params, idx_np, ct_np, lr):
        import numpy as np
        T, rows, d = host_params["kernel"].shape
        offs = (np.arange(T, dtype=np.int64) * rows)[None, :, None]
        g = idx_np.astype(np.int64) % rows + offs
        _host_bag_update(host_params["kernel"].reshape(T * rows, d), g,
                         ct_np, lr, self.aggr)

    def host_opt_update(self, host_params, idx_np, ct_np, opt, slabs,
                        step):
        import numpy as np
        T, rows, d = host_params["kernel"].shape
        offs = (np.arange(T, dtype=np.int64) * rows)[None, :, None]
        g = idx_np.astype(np.int64) % rows + offs
        _host_stateful_update(
            host_params["kernel"].reshape(T * rows, d), g, ct_np, opt,
            {k: v.reshape(T * rows, d) for k, v in slabs.items()},
            step, self.aggr)


class EmbeddingBagConcat(Op):
    """N embedding bags with a SHARED width but DIFFERENT row counts,
    concatenated row-wise into one (sum_rows_padded, dim) parameter; each
    lookup adds its table's row offset. This is the non-uniform-table form
    of EmbeddingBagStacked and the natural TPU layout for Criteo-Kaggle's
    26 tables (4 … 3.1M rows × 16-d, run_criteo_kaggle.sh): the reference
    places each table whole on one device (dlrm_strategy.cc:252-256); here
    the concatenated rows are block-sharded over the mesh, all 26 gathers
    fuse into ONE gather and the sparse update into ONE scatter.

    input: int (batch, num_tables, bag)  ->  output (batch, num_tables, dim)
    """

    type_name = "EmbedConcat"

    # the table-dim degree is intent ("row-shard the concatenated table"),
    # not an output partitioning — _effective_pc clamping it is expected
    raw_degree_semantics = True

    # row padding so the concatenated row count divides any power-of-two
    # mesh (and most mixed meshes)
    _ROW_PAD = 8192

    def __init__(self, model, input_tensor, table_sizes, out_dim: int,
                 aggr: str = AGGR_MODE_SUM, kernel_initializer=None,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        assert input_tensor.num_dims == 3, "expect (batch, num_tables, bag)"
        self.table_sizes = tuple(int(s) for s in table_sizes)
        self.num_tables = len(self.table_sizes)
        assert input_tensor.shape[1] == self.num_tables
        self.out_dim = int(out_dim)
        self.aggr = aggr
        self.kernel_initializer = kernel_initializer or GlorotUniform()
        total = sum(self.table_sizes)
        self.total_rows = -(-total // self._ROW_PAD) * self._ROW_PAD
        offs = [0]
        for s in self.table_sizes[:-1]:
            offs.append(offs[-1] + s)
        self._offsets = tuple(offs)
        # lane packing (see EmbeddingBagStacked): total_rows is a power-of-
        # two multiple of any pack factor, so narrow rows always pack
        self._pack = _pack_factor(self.out_dim, self.total_rows)
        batch = input_tensor.shape[0]
        self.outputs = [self._make_output(
            (batch, self.num_tables, self.out_dim))]

    def set_device_groups(self, dev_of):
        """Group the concatenated tables by their strategy device: row
        block k holds exactly the tables the strategy places on the k-th
        named device, each block padded to one common size, so GSPMD's
        equal-block row sharding lands every table WHOLE on its intended
        device — the reference's per-table round-robin placement
        (dlrm_strategy.cc:242-296, mapper.cc:33-97) with UNEVEN table
        counts per device. Must be called before init_params (compile-time
        strategy resolution does)."""
        assert len(dev_of) == self.num_tables
        devs = sorted(set(dev_of))
        groups = [[i for i, dg in enumerate(dev_of) if dg == g]
                  for g in devs]
        block = max(sum(self.table_sizes[i] for i in grp)
                    for grp in groups)
        block = -(-block // self._ROW_PAD) * self._ROW_PAD
        offs = [0] * self.num_tables
        for k, grp in enumerate(groups):
            off = k * block
            for i in grp:
                offs[i] = off
                off += self.table_sizes[i]
        self._offsets = tuple(offs)
        self.total_rows = block * len(groups)
        self._pack = _pack_factor(self.out_dim, self.total_rows)
        self._device_groups = tuple(devs)

    def param_defs(self):
        r = self._pack
        return {"kernel": ParamDef(
            (self.total_rows // r, self.out_dim * r), jnp.float32,
            self.kernel_initializer)}

    def init_params(self, key):
        # per-table init at each table's LOGICAL (rows_t, d) shape:
        # one Glorot over the fused multi-million-row shape would collapse
        # small tables' scale to ~0 versus the unfused per-table ops.
        # Tables land at their _offsets (sequential by default; grouped by
        # device under set_device_groups), pad rows stay zero.
        keys = jax.random.split(key, self.num_tables)
        logical = jnp.zeros((self.total_rows, self.out_dim), jnp.float32)
        for i, rows in enumerate(self.table_sizes):
            part = self.kernel_initializer(
                keys[i], (rows, self.out_dim), jnp.float32)
            logical = jax.lax.dynamic_update_slice(
                logical, part, (self._offsets[i], 0))
        return {"kernel": self.pack_kernel(logical)}

    def unpack_kernel(self, kernel):
        """(total_rows/r, r*d) stored form -> logical (total_rows, d)."""
        return kernel.reshape(self.total_rows, self.out_dim)

    def pack_kernel(self, logical):
        r = self._pack
        return logical.reshape(self.total_rows // r, self.out_dim * r)

    def _global_indices(self, idx):
        """Per-table modulo (wrap semantics like the gathers above) then
        offset into the concatenated rows."""
        with jax.named_scope("index"):
            sizes = jnp.asarray(self.table_sizes, jnp.int32)[None, :, None]
            offs = jnp.asarray(self._offsets, jnp.int32)[None, :, None]
            return idx.astype(jnp.int32) % sizes + offs       # (batch, T, bag)

    # ---- row/PARAM-axis sharding hooks (see configure_row_shard) -------
    def _row_shard_geometry(self):
        return self.total_rows, self._pack, 1

    def _row_route(self, g):
        """Concatenated global rows -> (owner, local, gid, hot_id).
        The dedup'd exchange keys on the concatenated row id; the
        hot/cold hybrid does NOT apply here (non-uniform tables have no
        per-table hot split — row_shard_structural_reason says so)."""
        plan = self._row_plan
        rl = plan.rows_local
        return ((g // rl).astype(jnp.int32),
                (g % rl).astype(jnp.int32),
                g.astype(jnp.int32), None)

    def _row_spec_block(self):
        from jax.sharding import PartitionSpec
        plan = self._row_plan
        r = self._pack
        return (PartitionSpec(plan.row_axes, None),
                (self.total_rows // r // plan.nshards, self.out_dim * r))

    def apply(self, params, xs, *, training=False, rng=None):
        (idx,) = xs                        # (batch, T, bag)
        tbl = params["kernel"]             # (total_rows/r, r*d)
        g = self._global_indices(idx)
        batch, T, bag = g.shape
        r, d = self._pack, self.out_dim
        plan = _row_plan(self)
        if plan is not None and batch % plan.ndev == 0:
            from ..parallel.alltoall import row_sharded_bag_lookup
            owner, local, gid, _hot = self._row_route(g)
            spec, block = self._row_spec_block()
            return [row_sharded_bag_lookup(plan, tbl, spec, owner,
                                           local, d, self.aggr, block,
                                           gid=gid)]
        if (self.aggr in (AGGR_MODE_SUM, AGGR_MODE_AVG) and r == 1
                and _pallas_ok(self.model, self.out_dim, self.name)):
            # one Pallas row-stream over the concatenated table; per-table
            # bags become the kernel's bag dim via (batch*T, bag) indices
            from .pallas.embedding_kernel import embedding_bag
            out = embedding_bag(tbl, g.reshape(batch * T, bag), self.aggr)
            return [out.reshape(batch, T, self.out_dim)]
        if r == 1:
            rows = jnp.take(tbl, g.reshape(-1), axis=0,
                            mode="wrap").reshape(g.shape + (d,))
        else:
            rows = _packed_gather(tbl, g, r, d)   # (batch, T, bag, d)
        if self.aggr == AGGR_MODE_AVG:
            return [jnp.mean(rows, axis=2)]
        return [jnp.sum(rows, axis=2)]     # (batch, T, d)

    def candidate_parallel_configs(self, num_devices, feasible_degrees):
        # same divisibility filter as EmbeddingBagStacked so the degrees
        # the search costs are the degrees compile() executes (the clamp in
        # _effective_pc would otherwise silently rewrite them)
        out = []
        for ds in feasible_degrees:
            for dt in feasible_degrees:
                if ds * dt <= num_devices and self.num_tables % max(dt, 1) == 0:
                    out.append(ParallelConfig((ds, dt, 1)))
        out.extend(_row_shard_candidates(self, num_devices,
                                         feasible_degrees, 3))
        out.append(_zcm_candidate(3))
        return out

    def output_axes(self, pc: ParallelConfig, assigner, raw_pc=None):
        # Under table parallelism (RAW degrees[1] > 1 — same trigger as
        # param_axes, surviving the output-shape clamp) the PARAM is
        # row-block sharded over the whole mesh; the fused gather's
        # natural output layout is then batch-sharded over the whole
        # mesh, matching the data-parallel consumers. Constraining the T
        # dim instead (the positional reading of the degrees) forces
        # GSPMD into a full rematerialization per step.
        raw = raw_pc or pc
        if len(raw.degrees) > 1 and raw.degrees[1] > 1:
            batch = self.outputs[0].shape[0]
            full = assigner.mesh.size
            if batch % full == 0:
                return [tuple(assigner.axis_names), (), ()]
        return assigner.assign(pc.degrees)

    def param_axes(self, pc: ParallelConfig, out_axes,
                   raw_pc=None):
        # explicit PARAM-axis row sharding (all-to-all routed lookups)
        # takes precedence over the implicit GSPMD row-block sharding
        if _row_plan(self) is not None:
            return {"kernel": (self._row_plan.row_axes, ())}
        # table parallelism = row-block sharding of the concatenated rows.
        # Keyed off the RAW (unclamped) strategy degrees: the output's
        # table dim often can't split evenly (26 tables on 8 chips), but
        # the padded row count always can — and sharding the rows is the
        # memory-scaling point of placing tables across devices. GSPMD
        # inserts the gather/scatter collectives.
        raw = raw_pc or pc
        if len(raw.degrees) >= 2 and raw.degrees[1] > 1:
            rows_axes = tuple(self.model.mesh.axis_names)
        else:
            rows_axes = ()
        return {"kernel": (rows_axes, ())}

    def flops_per_sample(self) -> float:
        bag = self.inputs[0].shape[-1]
        return float(self.num_tables * bag * self.out_dim)

    def param_shard_shapes(self, pc: ParallelConfig, ndev=None):
        # any table parallelism row-shards the concatenated table over the
        # WHOLE mesh (param_axes), not just pc.num_parts; an explicit
        # PARAM-axis degree shards rows by exactly that many shards
        pd = max(getattr(pc, "param_degree", 1), 1)
        full = ndev or (self.model.mesh.size if self.model.mesh else 1)
        if pd > 1:
            dt = pd
        else:
            dt = full if (len(pc.degrees) > 1 and pc.degrees[1] > 1) else 1
        r = self._pack
        return {"kernel": (max(self.total_rows // r // max(dt, 1), 1),
                           self.out_dim * r)}


    def random_hbm_rows(self, backward: bool = False,
                        raw: bool = False) -> float:
        return _embedding_random_rows(self, backward, raw)

    def update_random_hbm_rows(self, pc=None) -> float:
        return _embedding_update_rows(self, pc)

    def alltoall_payload_bytes(self, ndev: int, itemsize: int, pc=None):
        return _a2a_payload_bytes(self, ndev, itemsize, pc=pc)

    def param_bytes_touched_per_step(self, num_parts: int = 1) -> int:
        if not _sparse_update_active(self):
            return self.param_bytes()
        batch, _, bag = self.inputs[0].shape
        return int(_touched_bytes_factor(self) * batch * self.num_tables
                   * bag * self.out_dim * 4 // max(num_parts, 1))

    # ---- sparse (touched-rows-only) SGD update (see Embedding) ---------
    def supports_sparse_update(self) -> bool:
        return self.aggr in (AGGR_MODE_SUM, AGGR_MODE_AVG)

    def _fwd_residual_ok(self) -> bool:
        """See EmbeddingBagStacked._fwd_residual_ok."""
        return (self._pack > 1
                and self.aggr in (AGGR_MODE_SUM, AGGR_MODE_AVG)
                and _row_plan(self) is None
                and not _pallas_ok(self.model, self.out_dim, self.name)
                and _pallas_scatter_ok(self.model, 128, self.name)
                and _row_shard_axes(self, self.out_dim,
                                    self.total_rows // self._pack) is None)

    def apply_with_fwd(self, params, xs, *, rng=None):
        """apply() plus forward-gather residuals for the write-only sparse
        update (see EmbeddingBagStacked.apply_with_fwd)."""
        if not self._fwd_residual_ok():
            return self.apply(params, xs, training=True, rng=rng), None
        (idx,) = xs
        tbl = params["kernel"]             # (total_rows/r, r*d)
        g = self._global_indices(idx)      # (batch, T, bag) unpacked rows
        r, d = self._pack, self.out_dim
        rows, _, tiles = _packed_gather_tiles(tbl, g, r, d)
        out = (jnp.mean(rows, axis=2) if self.aggr == AGGR_MODE_AVG
               else jnp.sum(rows, axis=2))
        return [out], (g.reshape(-1), tiles)

    def sparse_sgd_update(self, params, xs, out_ct, lr,
                          fwd=None):
        (idx,) = xs                        # (batch, T, bag)
        tbl = params["kernel"]             # (total_rows, d)
        g = self._global_indices(idx)
        ct = out_ct.astype(tbl.dtype)      # (batch, T, d)
        if self.aggr == AGGR_MODE_AVG:
            ct = ct / g.shape[-1]
        r, d = self._pack, self.out_dim
        upd = jnp.broadcast_to(ct[..., None, :], g.shape + (d,))
        upd = upd.reshape(-1, d)
        plan = _row_plan(self)
        if plan is not None and g.size % plan.ndev == 0:
            from ..parallel.alltoall import row_sharded_sgd_update
            owner, local, gid, _hot = self._row_route(g.reshape(-1))
            spec, _ = self._row_spec_block()
            new = row_sharded_sgd_update(plan, tbl, spec, owner, local,
                                         upd, lr, d, gid=gid)
            return {"kernel": new}
        if fwd is not None and self._fwd_residual_ok():
            from .pallas.embedding_kernel import scatter_write_rows_packed
            g_flat, tiles = fwd
            new = scatter_write_rows_packed(tbl, g_flat, -lr * upd,
                                            tiles, d)
            return {"kernel": new}
        shard_axes = _row_shard_axes(self, d, self.total_rows // r)
        if shard_axes is not None:
            from .pallas.embedding_kernel import sharded_scatter_add_packed
            new = sharded_scatter_add_packed(
                self.model.mesh, shard_axes, tbl, g.reshape(-1),
                -lr * upd, d)
        elif _pallas_scatter_ok(self.model, d if r == 1 else 128, self.name):
            from .pallas.embedding_kernel import (scatter_add_rows,
                                                  scatter_add_rows_packed)
            if r == 1:
                new = scatter_add_rows(tbl, g.reshape(-1), -lr * upd)
            else:
                new = scatter_add_rows_packed(tbl, g.reshape(-1),
                                              -lr * upd, d)
        elif r == 1:
            new = tbl.at[g.reshape(-1)].add(-lr * upd)
        else:
            new = self.pack_kernel(
                self.unpack_kernel(tbl).at[g.reshape(-1)].add(-lr * upd))
        return {"kernel": new}

    def sparse_opt_update(self, params, xs, out_ct, opt, slabs, step,
                          fwd=None):
        """Stateful touched-rows update (lazy momentum / Adam) on the
        concatenated non-uniform tables; see Embedding.sparse_opt_update."""
        (idx,) = xs                        # (batch, T, bag)
        tbl = params["kernel"]             # (total_rows/r, r*d)
        g = self._global_indices(idx)
        ct = out_ct.astype(jnp.float32)    # (batch, T, d)
        if self.aggr == AGGR_MODE_AVG:
            ct = ct / g.shape[-1]
        d = self.out_dim
        upd = jnp.broadcast_to(ct[..., None, :],
                               g.shape + (d,)).reshape(-1, d)
        fwd_tiles = fwd[1] if fwd is not None else None
        kslabs, _hslabs, nested = _norm_slabs(slabs)
        out = _sparse_opt_update(self, tbl, g.reshape(-1), upd,
                                 opt, kslabs, step,
                                 self.total_rows, fwd_tiles)
        return _finish_opt_update(out, nested)

    # ---- host-resident table form (reference embedding_avx2.cc) --------
    def host_init(self, seed: int):
        import numpy as np
        logical = np.zeros((self.total_rows, self.out_dim), np.float32)
        for i, rows in enumerate(self.table_sizes):
            logical[self._offsets[i]:self._offsets[i] + rows] = \
                _host_init_table(self.kernel_initializer,
                                 (rows, self.out_dim), seed + i)
        return {"kernel": logical}

    def _host_global_indices(self, idx_np):
        import numpy as np
        sizes = np.asarray(self.table_sizes, np.int64)[None, :, None]
        offs = np.asarray(self._offsets, np.int64)[None, :, None]
        return idx_np.astype(np.int64) % sizes + offs     # (batch, T, bag)

    def host_flat_indices(self, idx_np):
        """Per-sample FLAT row ids, (batch, T, bag), into the
        (total_rows, d) concatenated host table — shared with the
        serving shard tier."""
        return self._host_global_indices(idx_np)

    def host_lookup_rows(self, rows_2d, g3):
        """See :meth:`Embedding.host_lookup_rows`."""
        return _host_bag_lookup(rows_2d, g3, self.aggr)

    def host_lookup(self, host_params, idx_np):
        return self.host_lookup_rows(host_params["kernel"],
                                     self.host_flat_indices(idx_np))

    def host_sgd_update(self, host_params, idx_np, ct_np, lr):
        _host_bag_update(host_params["kernel"],
                         self._host_global_indices(idx_np), ct_np, lr,
                         self.aggr)

    def host_opt_update(self, host_params, idx_np, ct_np, opt, slabs,
                        step):
        _host_stateful_update(host_params["kernel"],
                              self._host_global_indices(idx_np), ct_np,
                              opt, slabs, step, self.aggr)

    # ---- delta publication (utils/delta.py; see Embedding) -------------
    def delta_touched_rows(self, idx_np) -> "np.ndarray":
        # stored kernel is (total_rows/r, r*d): concatenated global rows,
        # r logical rows per packed row
        import numpy as np
        g = self._host_global_indices(idx_np)
        return np.unique(g.reshape(-1) // self._pack)

    def host_delta_touched_rows(self, idx_np) -> "np.ndarray":
        # host table is the unpacked (total_rows, d) concatenation
        import numpy as np
        return np.unique(self._host_global_indices(idx_np).reshape(-1))

    def flat_lookup_ids(self, idx_np) -> "np.ndarray":
        """Batch indices -> concatenated global rows, for the
        id-frequency sketch collected at staging."""
        return self._host_global_indices(idx_np).reshape(-1)

