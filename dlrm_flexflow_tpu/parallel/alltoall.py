"""Row-sharded embedding tables with explicit all-to-all lookup routing.

The pod-scale DLRM shape (Naumov et al. 2019; Mudigere et al., ZionEX
2022): each device owns a ROW block of every embedding table and a slice
of the batch; per-sample lookups are routed to the owning shard and the
embedded rows routed back. The reference got this movement implicitly
from Legion DMA for whole-table placement (dlrm_strategy.cc:252-256);
`EmbeddingBagStacked`'s table-dim sharding reproduces that — but every
table must still fit one device. Row sharding (`ParallelConfig.
param_degree > 1`) is what removes that ceiling.

The exchange, per training step, under one `shard_map` over the mesh:

  forward   bucketize local lookups by owning shard (stable sort by
            owner + rank-in-bucket) → dense all-to-all of request row
            ids over the row axes → local gather on each owner →
            all-to-all of the embedded rows back → unpermute + bag
            aggregation. Output is batch-sharded over the whole mesh.
  backward  the same routing in reverse: gradient rows travel TO their
            owning shard (all-to-all), are put into one canonical
            global order, and scatter-add into the local row block —
            so the table gradient, and therefore the optimizer state,
            stays shard-local. No table-sized dense gradient and no
            cross-replica table all-reduce ever materializes.

Skew-aware refinements (ParallelConfig.exchange / hot_fraction — real
recommendation traffic is zipfian, so a handful of hot ids dominate):

- DEDUP-BEFORE-EXCHANGE (`exchange="dedup"`, Neo/ZionEX): each device
  sort→uniques its local lookup ids, routes only the DISTINCT ids
  through the exchange, scatters the returned rows back through the
  inverse map, and pre-accumulates gradient rows per unique id before
  the return exchange. Exchanged (valid) bytes then scale with distinct
  ids, not batch size; the padded capacity also drops to
  min(n_local, rows a shard owns) — after dedup an owner can never
  receive more requests than it has rows.
- HOT/COLD HYBRID (`hot_fraction > 0`, FAE): the top-H (low-numbered,
  hot) rows of every table are REPLICATED on each device — their
  lookups are purely local and their updates apply in lockstep from an
  all-gather — while the cold tail stays row-sharded. Hot traffic never
  touches the exchange at all.

Exactness contract (tests/test_rowshard.py pins it): forward outputs,
gradients, and optimizer updates are BIT-IDENTICAL across the dense,
dedup'd, and hybrid paths on the same mesh, for any row-shard degree
and any mesh factorization — including duplicate lookups. Three
mechanisms make that hold:

- the request buckets are filled in local flatten order and received in
  peer order, and batch blocks are assigned to devices in mesh order —
  so each row's duplicate updates arrive in global batch order;
- before applying, every receiver puts updates in CANONICAL order:
  combine duplicate rows per (row, source device) — a pos-ordered
  segment sum, exactly what the dedup path pre-computes on the sender —
  then apply the per-device partial sums in ascending first-occurrence
  global position. The accumulation tree is therefore identical whether
  duplicates were combined before or after the exchange, and
  independent of the routing topology (dedup at pd=4 == dedup at pd=8);
- hot (replicated) rows apply the SAME canonical combine from an
  all-gather of every device's updates, so replicas stay bitwise in
  lockstep and match what the owner shard would have computed.

Capacity: the dense exchange reserves `n_local` slots per peer (the
always-exact worst case — one owner could receive every local lookup);
the dedup'd exchange reserves min(n_local, flat_rows_local). A
production TPU kernel would use a ragged exchange
(`lax.ragged_all_to_all`) at the actual distinct-id counts; the
cost model prices that balanced exchange — with the expected distinct
ids from an observed id histogram (utils/histogram.py) when one is
attached — which is also what the padded dense form approaches.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from .mesh import smap as _smap
from .sharding import param_axis_indices

_INT_MAX = np.iinfo(np.int32).max


@dataclass(frozen=True)
class RowShardPlan:
    """Resolved row-shard placement for one embedding op: which mesh
    axes carry the row blocks (`row_axes`, consumed leading-first like
    every other degree), how many shards that makes, and how many
    logical COLD (routed) rows each shard owns. `dedup` selects the
    unique-ids exchange; `hot_rows` > 0 is the hybrid placement's
    per-table replicated-row count (the plan's row geometry then
    describes only the cold tail)."""

    mesh: Mesh
    row_axes: Tuple[str, ...]     # mesh axes the rows shard over
    nshards: int                  # product of row-axis sizes
    rows_local: int               # logical COLD rows per shard (per table)
    flat_rows_local: int          # cold rows per shard of the FLAT view
    dedup: bool = False           # unique-ids exchange
    hot_rows: int = 0             # replicated hot rows per table
    tables: int = 1
    # pipelined exchange: decompose each fused all-to-all into
    # independent rounds (a ppermute ring over a single row axis,
    # capacity-chunked collectives over a factorized one) so XLA's
    # async scheduler can hide them under the step's dense compute.
    # Same blocks, same positions — bit-identical to the fused form.
    overlap: bool = False

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    @property
    def nonrow_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.mesh.axis_names
                     if a not in self.row_axes)

    @property
    def hot_rows_flat(self) -> int:
        """Rows of the FLAT replicated hot block (all tables)."""
        return self.tables * self.hot_rows

    @property
    def ndev(self) -> int:
        n = 1
        for a in self.mesh.axis_names:
            n *= self.mesh.shape[a]
        return n

    def capacity(self, n_local: int) -> int:
        """Per-peer slot capacity of the index/row exchange: the dense
        path reserves the always-exact worst case (one owner receives
        every local lookup); after dedup an owner can receive at most
        as many DISTINCT requests as it has rows."""
        if self.dedup:
            return max(min(int(n_local), self.flat_rows_local), 1)
        return int(n_local)

    def row_ranges(self) -> list:
        """The [lo, hi) flat-row block each shard owns, in shard order —
        the same owner math the exchange body evaluates as
        ``owner = id // rows_local`` (see :func:`shard_row_ranges`)."""
        return shard_row_ranges(self.flat_rows_local * self.nshards,
                                self.nshards)


# ---- shared owner math (training exchange AND the serving shard tier) ----
#
# The exchange body computes `owner = flat_id // rows_local` with equal
# row blocks per shard; these module-level helpers are the host-side
# (numpy) statement of the same placement, generalized to a row count
# that does not divide evenly (the last shard owns the short tail). The
# serving shard tier (serve/shardtier.py) slices lookup shards with
# them, so a serving plan's row ownership is BY CONSTRUCTION the one a
# row-sharded training mesh would use — and shardcheck's FLX507 tiling
# audit verifies any plan against the same functions.


def shard_rows_local(rows: int, nshards: int) -> int:
    """Rows per shard (ceil-division block size)."""
    if nshards < 1:
        raise ValueError(f"nshards must be >= 1, got {nshards}")
    return -(-int(rows) // int(nshards))


def shard_row_ranges(rows: int, nshards: int) -> list:
    """[(lo, hi), ...] per shard, tiling [0, rows) exactly — contiguous
    equal blocks (the last possibly short, possibly empty)."""
    per = shard_rows_local(rows, nshards)
    return [(min(s * per, rows), min((s + 1) * per, rows))
            for s in range(nshards)]


def row_owners(ids, rows: int, nshards: int) -> np.ndarray:
    """Owning shard per flat row id — `id // rows_local`, clamped into
    range (ids are taken mod `rows` first, matching every host lookup's
    wrap semantics)."""
    per = shard_rows_local(rows, nshards)
    g = np.asarray(ids, np.int64) % max(int(rows), 1)
    return np.minimum(g // per, nshards - 1).astype(np.int64)


def plan_row_shard(mesh: Optional[Mesh], param_degree: int,
                   rows: int, pack: int, tables: int = 1,
                   dedup: bool = False, hot_rows: int = 0,
                   overlap: bool = False
                   ) -> Optional[RowShardPlan]:
    """Build the RowShardPlan for `param_degree` row shards of a table
    whose COLD (routed) tail has `rows` logical rows stored
    `pack`-per-lane-tile, or None with the structural reason it cannot
    apply (caller logs it). `hot_rows` records the hybrid placement's
    replicated per-table head (already excluded from `rows`);
    `overlap` selects the pipelined (decomposed) exchange."""
    if mesh is None or param_degree <= 1:
        return None
    sizes = [int(mesh.shape[a]) for a in mesh.axis_names]
    if int(np.prod(sizes)) <= 1:
        return None
    idx = param_axis_indices(param_degree, sizes)
    if idx is None:
        return None
    # equal row blocks per shard, aligned to the lane packing so a
    # shard's packed block reshapes to whole logical rows
    if rows % (param_degree * max(pack, 1)) != 0:
        return None
    axes = tuple(mesh.axis_names[i] for i in idx)
    rows_local = rows // param_degree
    return RowShardPlan(mesh=mesh, row_axes=axes, nshards=param_degree,
                        rows_local=rows_local,
                        flat_rows_local=tables * rows_local,
                        dedup=bool(dedup), hot_rows=int(hot_rows),
                        tables=int(tables), overlap=bool(overlap))


# ---- the exchange collective (inside the shard_map body) -----------------

# capacity-dim chunk count of the pipelined multi-axis exchange: enough
# independent collectives for the scheduler to overlap send k+1 with
# compute consuming chunk k, few enough that per-collective dispatch
# overhead stays under the ~0.5 ms floor the calibration measures
_OVERLAP_CHUNKS = 4


def _ring_a2a(plan: RowShardPlan, x):
    """Pipelined single-axis exchange: decompose the fused all-to-all
    of one (S, C[, d]) buffer into S-1 `ppermute` rounds. Round `s`
    sends block (me+s) mod S one hop of distance s and lands the block
    received from peer (me-s) mod S in its slot; the self block never
    leaves the device. Each round is an independent collective-permute,
    so XLA's async scheduler (collective-permute-start/-done) hoists
    them over whatever dense compute has no data dependence on the
    received blocks — that is the whole overlap. The output buffer is
    position-for-position the one `jax.lax.all_to_all` returns:
    out[j] = x_of_peer_j[me]. No payload arithmetic, so bit-identity
    with the fused exchange is by construction."""
    axis = plan.row_axes[0]
    S = plan.nshards
    me = jax.lax.axis_index(axis)
    out = x                         # keeps the self block at slot `me`
    for s in range(1, S):
        perm = [(i, (i + s) % S) for i in range(S)]
        blk = jax.lax.dynamic_index_in_dim(x, (me + s) % S, axis=0,
                                           keepdims=True)
        recv = jax.lax.ppermute(blk, axis, perm)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, recv, (me + S - s) % S, axis=0)
    return out


def _chunked_a2a(plan: RowShardPlan, x):
    """Pipelined multi-axis exchange: the ring form needs one linear
    peer order, which a factorized row axis does not have — so chunk
    the CAPACITY dim instead and issue one independent all-to-all per
    chunk. Identical bytes, identical slots (the chunks concatenate
    back in order); the scheduler overlaps chunk k+1's exchange with
    compute consuming chunk k. Falls back to the fused collective when
    the capacity has no usable divisor."""
    C = x.shape[1]
    k = 1
    for cand in range(min(_OVERLAP_CHUNKS, C), 1, -1):
        if C % cand == 0:
            k = cand
            break
    if k <= 1:
        return jax.lax.all_to_all(x, plan.row_axes, 0, 0)
    step = C // k
    parts = [jax.lax.all_to_all(
        jax.lax.slice_in_dim(x, i * step, (i + 1) * step, axis=1),
        plan.row_axes, 0, 0) for i in range(k)]
    return jnp.concatenate(parts, axis=1)


def _a2a(plan: RowShardPlan, x):
    """THE row-shard exchange collective on one (S, C[, d]) send buffer
    (block i addressed to shard i; returns the same layout with block j
    received from shard j). Every exchange in this module routes
    through here: serial plans lower the single fused
    `jax.lax.all_to_all` (one blocking collective, reference behavior);
    `plan.overlap` decomposes it into independent rounds the compiler
    can hide under dense compute. All three forms move the same blocks
    to the same slots — the bit-identity contract does not depend on
    which one ran."""
    if not plan.overlap or plan.nshards <= 1:
        return jax.lax.all_to_all(x, plan.row_axes, 0, 0)
    if len(plan.row_axes) == 1:
        return _ring_a2a(plan, x)
    return _chunked_a2a(plan, x)


# ---- routing primitives (inside the shard_map body) ----------------------


def _bucket_ranks(owner_f: jnp.ndarray) -> jnp.ndarray:
    """Rank of each local lookup within its owner's bucket (stable: the
    local flatten order is preserved inside each bucket — the ordering
    half of the bit-identity contract)."""
    n = owner_f.shape[0]
    order = jnp.argsort(owner_f)                       # stable
    so = jnp.take(owner_f, order)
    start = jnp.searchsorted(so, so, side="left")
    ranks_sorted = jnp.arange(n, dtype=jnp.int32) - start.astype(jnp.int32)
    return jnp.zeros((n,), jnp.int32).at[order].set(ranks_sorted)


def _device_linear_index(mesh: Mesh) -> jnp.ndarray:
    """This device's linear index over ALL mesh axes in mesh order —
    the same order input batches block-shard over, so `dev * n + j` is
    the GLOBAL flatten position of local lookup j."""
    dev = jnp.zeros((), jnp.int32)
    for a in mesh.axis_names:
        dev = dev * mesh.shape[a] + jax.lax.axis_index(a)
    return dev


def _dedup_keys(gf: jnp.ndarray):
    """Sort→unique machinery over flat lookup keys `gf` (n,): returns
    (order, seg, rep, inv, nuniq) where `order` is the stable sort
    permutation, `seg` the unique-segment id per SORTED position (within
    a segment, positions ascend — the canonical accumulation order),
    `rep` each unique slot's FIRST-occurrence original position (pads:
    int32 max), `inv` each lookup's unique slot, and `nuniq` the live
    unique count. Slots >= nuniq are padding."""
    n = gf.shape[0]
    order = jnp.argsort(gf)                            # stable
    sg = jnp.take(gf, order)
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                             sg[1:] != sg[:-1]])
    seg = jnp.cumsum(first) - 1
    inv = jnp.zeros((n,), jnp.int32).at[order].set(
        seg.astype(jnp.int32))
    rep = jax.ops.segment_min(order.astype(jnp.int32), seg,
                              num_segments=n, indices_are_sorted=True)
    return order, seg, rep, inv, seg[-1] + 1


def _route_ids(plan: RowShardPlan, owner_f, local_f, C: int):
    """Bucketize + index all-to-all at per-peer capacity `C`. Slots with
    owner >= nshards (hot / dedup padding) are dropped from the send
    buffer and never consume a real peer's capacity. Returns (recv ids
    (S*C,), valid mask, ranks for the return path)."""
    rank = _bucket_ranks(owner_f)
    slot = owner_f * C + rank
    sentinel = jnp.int32(plan.flat_rows_local)
    send = jnp.full((plan.nshards * C,), sentinel, jnp.int32
                    ).at[slot].set(local_f, mode="drop")
    recv = _a2a(plan, send.reshape(plan.nshards, C)).reshape(-1)
    return recv, recv < sentinel, rank


def _combine_received(rid, rpos, rupd, n_local: int, sentinel: int):
    """THE canonical combine: put received update rows in the order
    every path agrees on. Duplicate rows pre-combine per (row id,
    source device) — a segment sum in ascending-position order, which is
    bitwise what the dedup sender already computed locally — and the
    per-device partial sums come back sorted by their first-occurrence
    global position. Padding (rid == sentinel) sorts last and is
    dropped by the appliers' mode="drop" scatters.

    rid (L,) int32 row ids (sentinel pads); rpos (L,) int32 global
    first-occurrence positions (int32-max pads); rupd (L, d) fp32."""
    L = rid.shape[0]
    o1 = jnp.argsort(rpos)                              # stable
    rid1 = jnp.take(rid, o1)
    rpos1 = jnp.take(rpos, o1)
    rupd1 = jnp.take(rupd, o1, axis=0)
    o2 = jnp.argsort(rid1)          # stable → within rid, pos ascending
    rid2 = jnp.take(rid1, o2)
    rpos2 = jnp.take(rpos1, o2)
    rupd2 = jnp.take(rupd1, o2, axis=0)
    dev2 = rpos2 // jnp.int32(max(n_local, 1))
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                             (rid2[1:] != rid2[:-1])
                             | (dev2[1:] != dev2[:-1])])
    seg = jnp.cumsum(first) - 1
    partial = jax.ops.segment_sum(rupd2, seg, num_segments=L,
                                  indices_are_sorted=True)
    ppos = jax.ops.segment_min(rpos2, seg, num_segments=L,
                               indices_are_sorted=True)
    prid = jax.ops.segment_max(rid2, seg, num_segments=L,
                               indices_are_sorted=True)
    valid = jnp.arange(L) < seg[-1] + 1
    prid = jnp.where(valid, prid, sentinel).astype(jnp.int32)
    ppos = jnp.where(valid, ppos, _INT_MAX).astype(jnp.int32)
    o3 = jnp.argsort(ppos)                              # stable
    return jnp.take(prid, o3), jnp.take(partial, o3, axis=0)


def _hot_combine(plan: RowShardPlan, hot_id, pos, upd, n_local: int):
    """Gather every device's hot-row updates (over ALL mesh axes — each
    device group saw a different batch slice AND hot rows are replicated
    on every shard) and put them in canonical order. All replicas apply
    the identical sequence, staying bitwise in lockstep — and matching
    what the owner shard of a non-hybrid plan would have computed.

    The sender pre-combines per hot id first — bitwise the per-(row,
    source-device) partials the canonical combine forms anyway — so the
    gathered buffer holds DISTINCT hot rows, at capacity
    min(n_local, hot rows): hot traffic is the most duplicate-heavy of
    all, and shipping raw per-lookup rows would make the hybrid's
    update gather scale with batch size again."""
    n = hot_id.shape[0]
    sent = int(plan.hot_rows_flat)
    order, seg, rep, _inv, nuniq = _dedup_keys(hot_id)
    partial = jax.ops.segment_sum(jnp.take(upd, order, axis=0), seg,
                                  num_segments=n,
                                  indices_are_sorted=True)
    upos = jax.ops.segment_min(jnp.take(pos, order), seg,
                               num_segments=n, indices_are_sorted=True)
    safe_rep = jnp.minimum(rep, n - 1)
    valid = jnp.arange(n) < nuniq
    uid = jnp.where(valid, jnp.take(hot_id, safe_rep), sent)
    hotv = valid & (uid < sent)
    upos = jnp.where(hotv, upos, _INT_MAX).astype(jnp.int32)
    uid = jnp.where(hotv, uid, sent).astype(jnp.int32)
    # compact: the sentinel (cold/pad) key sorts LAST, so hot uniques
    # occupy segments 0..k-1 with k <= min(n, hot rows) — truncation
    # only ever drops padding
    C = max(min(n, sent), 1)
    uid, upos, partial = uid[:C], upos[:C], partial[:C]
    ids = jax.lax.all_gather(uid, plan.all_axes, axis=0, tiled=True)
    ps = jax.lax.all_gather(upos, plan.all_axes, axis=0, tiled=True)
    us = jax.lax.all_gather(partial, plan.all_axes, axis=0, tiled=True)
    return _combine_received(ids, ps, us, n_local, sent)


# ---- forward lookup ------------------------------------------------------


def _fwd_rows(plan: RowShardPlan, flat, of, lf, gf):
    """Routed per-lookup rows (n, d) from this shard's flat cold block.
    Slots with owner >= nshards (hot slots under the hybrid placement)
    come back as zeros — the caller overlays their locally-gathered hot
    rows. Under `plan.dedup` only distinct ids travel; results scatter
    back through the inverse map (bitwise identical: a gather is a
    gather, whichever duplicate requested it)."""
    n = of.shape[0]
    d = flat.shape[-1]
    C = plan.capacity(n)
    sentinel = jnp.int32(plan.flat_rows_local)
    if plan.dedup:
        _, _, rep, inv, nuniq = _dedup_keys(gf)
        safe_rep = jnp.minimum(rep, n - 1)
        valid_u = jnp.arange(n) < nuniq
        uof = jnp.where(valid_u, jnp.take(of, safe_rep),
                        jnp.int32(plan.nshards))
        ulf = jnp.where(valid_u, jnp.take(lf, safe_rep), sentinel)
    else:
        uof, ulf, inv = of, lf, None
    recv, valid, rank = _route_ids(plan, uof, ulf, C)
    safe = jnp.minimum(recv, plan.flat_rows_local - 1)
    rows = jnp.take(flat, safe, axis=0)
    rows = jnp.where(valid[:, None], rows, 0.0)
    back = _a2a(plan, rows.reshape(plan.nshards, C, d))
    idx = jnp.minimum(uof, plan.nshards - 1) * C + rank
    mine = jnp.take(back.reshape(plan.nshards * C, d),
                    jnp.minimum(idx, plan.nshards * C - 1), axis=0)
    mine = jnp.where((uof < plan.nshards)[:, None], mine, 0.0)
    if inv is not None:
        mine = jnp.take(mine, inv, axis=0)
    return mine


def row_sharded_bag_lookup(plan: RowShardPlan, table, table_spec,
                           owner, local_id, d: int, aggr: str,
                           block_shape, gid=None,
                           hot_table=None, hot_id=None,
                           hot_block_shape=None):
    """Forward lookup with explicit all-to-all routing.

    table     : global packed kernel (COLD rows), row-sharded per
                `table_spec`
    owner     : (batch, T, bag) int32 — owning shard of each lookup;
                >= nshards marks a HOT slot (served locally, excluded
                from the exchange)
    local_id  : (batch, T, bag) int32 — row id within the owner's flat
                local (flat_rows_local, d) view (sentinel on hot slots)
    gid       : (batch, T, bag) int32 flat global cold id — the dedup
                key (required when plan.dedup)
    hot_table : replicated packed hot block (hybrid placement); hot_id
                the flat hot-row id per lookup (sentinel on cold slots)
    returns   : (batch, T, d) aggregated bags, batch-sharded over the
                whole mesh

    Differentiable: a custom VJP routes output cotangent rows back to
    their owning shards (all-to-all) and scatter-adds them there — and,
    under the hybrid placement, applies hot-row cotangents identically
    on every replica from an all-gather — so even the dense-update
    autodiff path never all-reduces a table-sized gradient."""
    mesh = plan.mesh
    batch_spec = PartitionSpec(plan.all_axes)
    hot = hot_table is not None
    if plan.dedup and gid is None:
        raise ValueError("dedup exchange needs the flat global ids")
    if gid is None:
        gid = local_id   # unused key space; keeps one body signature

    def _aggregate(rows_btb):
        # bag is always the last index dim; aggregate it, keep features
        if aggr == "avg":
            return jnp.mean(rows_btb, axis=-2)
        return jnp.sum(rows_btb, axis=-2)

    if not hot:
        def fwd_body(tbl_blk, ow, lo, gi):
            flat = tbl_blk.reshape(-1, d)
            shape = ow.shape
            mine = _fwd_rows(plan, flat, ow.reshape(-1), lo.reshape(-1),
                             gi.reshape(-1))
            return _aggregate(mine.reshape(shape + (d,)))

        lookup = _smap(fwd_body, mesh,
                       in_specs=(table_spec, batch_spec, batch_spec,
                                 batch_spec),
                       out_specs=batch_spec)

        @jax.custom_vjp
        def _call(tbl, ow, lo, gi):
            return lookup(tbl, ow, lo, gi)

        def _call_fwd(tbl, ow, lo, gi):
            return lookup(tbl, ow, lo, gi), (ow, lo, gi)

        def _call_bwd(res, ct):
            ow, lo, gi = res
            upd = _bag_cotangent_rows(ct, ow.shape, d, aggr)
            body = _scatter_body(plan, d, block_shape, mode="grad")
            grad = _smap(body, mesh,
                         in_specs=(batch_spec,) * 4,
                         out_specs=table_spec)(ow, lo, gi, upd)
            f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa
            return (grad, f0(ow), f0(lo), f0(gi))

        _call.defvjp(_call_fwd, _call_bwd)
        return _call(table, owner, local_id, gid)

    # ---- hybrid (hot/cold) form -----------------------------------------
    hot_spec = PartitionSpec()            # replicated on every device

    def fwd_body_h(tbl_blk, hot_blk, ow, lo, gi, hi):
        flat = tbl_blk.reshape(-1, d)
        hflat = hot_blk.reshape(-1, d)
        shape = ow.shape
        of = ow.reshape(-1)
        hf = hi.reshape(-1)
        cold = _fwd_rows(plan, flat, of, lo.reshape(-1), gi.reshape(-1))
        hrows = jnp.take(hflat, jnp.minimum(hf, plan.hot_rows_flat - 1),
                         axis=0)
        mine = jnp.where((of >= plan.nshards)[:, None], hrows, cold)
        return _aggregate(mine.reshape(shape + (d,)))

    lookup = _smap(fwd_body_h, mesh,
                   in_specs=(table_spec, hot_spec) + (batch_spec,) * 4,
                   out_specs=batch_spec)

    @jax.custom_vjp
    def _call(tbl, htbl, ow, lo, gi, hi):
        return lookup(tbl, htbl, ow, lo, gi, hi)

    def _call_fwd(tbl, htbl, ow, lo, gi, hi):
        return lookup(tbl, htbl, ow, lo, gi, hi), (ow, lo, gi, hi)

    def _call_bwd(res, ct):
        ow, lo, gi, hi = res
        upd = _bag_cotangent_rows(ct, ow.shape, d, aggr)
        body = _scatter_body(plan, d, block_shape, mode="grad",
                             hot_block_shape=hot_block_shape)
        grad, hgrad = _smap(body, mesh,
                            in_specs=(batch_spec,) * 5,
                            out_specs=(table_spec, hot_spec))(
            ow, lo, gi, hi, upd)
        f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa
        return (grad, hgrad, f0(ow), f0(lo), f0(gi), f0(hi))

    _call.defvjp(_call_fwd, _call_bwd)
    return _call(table, hot_table, owner, local_id, gid, hot_id)


def _bag_cotangent_rows(ct, idx_shape, d: int, aggr: str):
    """Output cotangent (batch, T, d) -> per-lookup gradient rows
    (batch, T, bag, d): each bag slot receives the bag-sum's cotangent
    (divided by the bag size under AVG)."""
    ct = ct.astype(jnp.float32)
    if aggr == "avg":
        ct = ct / idx_shape[-1]
    return jnp.broadcast_to(ct[..., None, :], tuple(idx_shape) + (d,))


# ---- update routing ------------------------------------------------------


def _route_updates(plan: RowShardPlan, of, lf, gf, uf):
    """-> (rids, rupds) for THIS shard, in canonical order: per-(row,
    source-device) partial sums sorted by first-occurrence global
    position (see _combine_received). Under `plan.dedup` duplicates
    pre-accumulate on the SENDER — bitwise the same segment sums the
    receiver's combine would have formed — so the gradient exchange,
    like the forward one, carries one slot per distinct id."""
    mesh = plan.mesh
    n = of.shape[0]
    d = uf.shape[-1]
    sentinel = jnp.int32(plan.flat_rows_local)
    dev = _device_linear_index(mesh)
    pos = dev * n + jnp.arange(n, dtype=jnp.int32)
    if plan.dedup:
        order, seg, rep, _inv, nuniq = _dedup_keys(gf)
        # per-unique partial sum, accumulated in ascending position —
        # within a segment the stable sort keeps local flatten order
        partial = jax.ops.segment_sum(jnp.take(uf, order, axis=0), seg,
                                      num_segments=n,
                                      indices_are_sorted=True)
        upos = jax.ops.segment_min(jnp.take(pos, order), seg,
                                   num_segments=n,
                                   indices_are_sorted=True)
        safe_rep = jnp.minimum(rep, n - 1)
        valid_u = jnp.arange(n) < nuniq
        s_of = jnp.where(valid_u, jnp.take(of, safe_rep),
                         jnp.int32(plan.nshards))
        s_lf = jnp.where(valid_u, jnp.take(lf, safe_rep), sentinel)
        s_pos = jnp.where(valid_u, upos, _INT_MAX).astype(jnp.int32)
        s_upd = partial
    else:
        s_of, s_lf, s_pos, s_upd = of, lf, pos, uf
    C = plan.capacity(n)
    rank = _bucket_ranks(s_of)
    slot = s_of * C + rank
    send_id = jnp.full((plan.nshards * C,), sentinel, jnp.int32
                       ).at[slot].set(s_lf, mode="drop")
    send_pos = jnp.full((plan.nshards * C,), _INT_MAX, jnp.int32
                        ).at[slot].set(s_pos, mode="drop")
    send_upd = jnp.zeros((plan.nshards * C, d), jnp.float32
                         ).at[slot].set(s_upd.astype(jnp.float32),
                                        mode="drop")
    rid = _a2a(plan, send_id.reshape(plan.nshards, C)).reshape(-1)
    rpos = _a2a(plan, send_pos.reshape(plan.nshards, C)).reshape(-1)
    rupd = _a2a(plan, send_upd.reshape(plan.nshards, C, d)).reshape(-1, d)
    # a row shard is replicated across the non-row axes, whose device
    # groups each saw a different batch slice: gather every group's
    # contributions so all replicas apply the full set (and stay
    # bitwise in lockstep)
    if plan.nonrow_axes:
        rid = jax.lax.all_gather(rid, plan.nonrow_axes, axis=0,
                                 tiled=True)
        rpos = jax.lax.all_gather(rpos, plan.nonrow_axes, axis=0,
                                  tiled=True)
        rupd = jax.lax.all_gather(rupd, plan.nonrow_axes, axis=0,
                                  tiled=True)
    return _combine_received(rid, rpos, rupd, n,
                             int(plan.flat_rows_local))


def _scatter_body(plan: RowShardPlan, d: int, block_shape, mode: str,
                  lr: float = 0.0, opt=None, slab_names=(),
                  hot_block_shape=None):
    """shard_map body routing per-lookup update rows to their owning
    shard and applying them there in canonical order. With a hybrid
    placement (hot_block_shape given), hot slots bypass the exchange:
    their updates all-gather and apply to the replicated hot block
    through the SAME canonical combine.

    mode "grad":  scatter-add combined rows into zeros (the custom-VJP
                  table gradient).
    mode "sgd":   w -= lr * rows, touched rows only (plain-SGD sparse
                  update).
    mode "opt":   stateful touched-rows update (lazy momentum/Adam) via
                  the shared logical-row dedup + optimizer row math.
    """
    mesh = plan.mesh
    sentinel = plan.flat_rows_local
    hot = hot_block_shape is not None
    hot_sent = plan.hot_rows_flat

    def split(ow, lo, gi, hi, upd):
        """Flatten + split one batch's updates into the routed cold
        stream and (hybrid) the gathered hot stream, both in canonical
        combined order."""
        shape = ow.shape
        n = int(np.prod(shape))
        of = ow.reshape(-1)
        lf = lo.reshape(-1)
        gf = gi.reshape(-1)
        uf = upd.reshape(n, d).astype(jnp.float32)
        rid, rupd = _route_updates(plan, of, lf, gf, uf)
        if not hot:
            return rid, rupd, None, None
        dev = _device_linear_index(mesh)
        pos = dev * n + jnp.arange(n, dtype=jnp.int32)
        hf = hi.reshape(-1)
        is_hot = of >= plan.nshards
        hid = jnp.where(is_hot, hf, jnp.int32(hot_sent))
        hpos = jnp.where(is_hot, pos, _INT_MAX).astype(jnp.int32)
        hupd = jnp.where(is_hot[:, None], uf, 0.0)
        hrid, hrupd = _hot_combine(plan, hid, hpos, hupd, n)
        return rid, rupd, hrid, hrupd

    if mode == "grad":
        def body(ow, lo, gi, hi_or_upd, upd=None):
            hi, u = (hi_or_upd, upd) if hot else (None, hi_or_upd)
            rid, rupd, hrid, hrupd = split(ow, lo, gi, hi, u)
            zero = jnp.zeros((sentinel, d), jnp.float32)
            cold = zero.at[rid].add(rupd, mode="drop"
                                    ).reshape(block_shape)
            if not hot:
                return cold
            hzero = jnp.zeros((hot_sent, d), jnp.float32)
            hgrad = hzero.at[hrid].add(hrupd, mode="drop"
                                       ).reshape(hot_block_shape)
            return cold, hgrad
        return body

    if mode == "sgd":
        def body(tbl_blk, *args):
            if hot:
                hot_blk, ow, lo, gi, hi, upd = args
            else:
                (ow, lo, gi, upd), hot_blk, hi = args, None, None
            rid, rupd, hrid, hrupd = split(ow, lo, gi, hi, upd)
            flat = tbl_blk.reshape(-1, d)
            flat = flat.at[rid].add(-lr * rupd.astype(flat.dtype),
                                    mode="drop")
            new = flat.reshape(tbl_blk.shape)
            if not hot:
                return new
            hflat = hot_blk.reshape(-1, d)
            hflat = hflat.at[hrid].add(-lr * hrupd.astype(hflat.dtype),
                                       mode="drop")
            return new, hflat.reshape(hot_blk.shape)
        return body

    if mode == "opt":
        def body(tbl_blk, slab_blks, *args):
            from ..ops.embedding import _stateful_update_rows_xla
            if hot:
                hot_blk, hot_slab_blks, ow, lo, gi, hi, upd, step = args
            else:
                ow, lo, gi, upd, step = args
                hot_blk = hot_slab_blks = hi = None
            rid, rupd, hrid, hrupd = split(ow, lo, gi, hi, upd)
            flat = tbl_blk.reshape(-1, d)
            slabs = {k: v.reshape(-1, d)
                     for k, v in zip(slab_names, slab_blks)}
            new_flat, new_slabs = _stateful_update_rows_xla(
                flat, rid, rupd, opt, slabs, step)
            cold = (new_flat.reshape(tbl_blk.shape),
                    tuple(new_slabs[k].reshape(tbl_blk.shape)
                          for k in slab_names))
            if not hot:
                return cold
            hflat = hot_blk.reshape(-1, d)
            hslabs = {k: v.reshape(-1, d)
                      for k, v in zip(slab_names, hot_slab_blks)}
            nh, nhs = _stateful_update_rows_xla(hflat, hrid, hrupd, opt,
                                                hslabs, step)
            return cold + (nh.reshape(hot_blk.shape),
                           tuple(nhs[k].reshape(hot_blk.shape)
                                 for k in slab_names))
        return body

    raise ValueError(f"unknown scatter mode {mode!r}")


def row_sharded_sgd_update(plan: RowShardPlan, table, table_spec,
                           owner, local_id, upd, lr: float, d: int,
                           gid=None, hot_table=None, hot_id=None):
    """Touched-rows plain-SGD update with all-to-all gradient-row
    routing: each shard applies -lr * (its rows' combined updates), in
    canonical order. `upd` is (batch, T, bag, d) RAW gradient rows.
    With a hybrid placement returns (new_table, new_hot_table)."""
    batch_spec = PartitionSpec(plan.all_axes)
    if gid is None:
        gid = local_id
    hot = hot_table is not None
    body = _scatter_body(plan, d, None, mode="sgd", lr=float(lr),
                         hot_block_shape=(() if hot else None))
    if not hot:
        return _smap(body, plan.mesh,
                     in_specs=(table_spec,) + (batch_spec,) * 4,
                     out_specs=table_spec)(table, owner, local_id, gid,
                                           upd)
    hot_spec = PartitionSpec()
    new, new_hot = _smap(
        body, plan.mesh,
        in_specs=(table_spec, hot_spec) + (batch_spec,) * 5,
        out_specs=(table_spec, hot_spec))(table, hot_table, owner,
                                          local_id, gid, hot_id, upd)
    return new, new_hot


def row_sharded_opt_update(plan: RowShardPlan, table, slabs, table_spec,
                           owner, local_id, upd, opt, step, d: int,
                           gid=None, hot_table=None, hot_slabs=None,
                           hot_id=None):
    """Stateful (lazy momentum/Adam) touched-rows update with
    all-to-all routing; optimizer state slabs are sharded exactly like
    the kernel, so state rows never leave their shard. With a hybrid
    placement the replicated hot block (and its slabs) updates in
    lockstep from the all-gathered hot stream; returns
    (new_tbl, new_slabs[, new_hot, new_hot_slabs])."""
    slab_names = tuple(sorted(slabs))
    batch_spec = PartitionSpec(plan.all_axes)
    if gid is None:
        gid = local_id
    hot = hot_table is not None
    body = _scatter_body(plan, d, None, mode="opt", opt=opt,
                         slab_names=slab_names,
                         hot_block_shape=(() if hot else None))
    if not hot:
        new_tbl, new_slab_vals = _smap(
            body, plan.mesh,
            in_specs=(table_spec, (table_spec,) * len(slab_names),
                      batch_spec, batch_spec, batch_spec, batch_spec,
                      PartitionSpec()),
            out_specs=(table_spec, (table_spec,) * len(slab_names)),
        )(table, tuple(slabs[k] for k in slab_names), owner, local_id,
          gid, upd, step)
        return new_tbl, dict(zip(slab_names, new_slab_vals))
    hot_spec = PartitionSpec()
    new_tbl, new_slab_vals, new_hot, new_hot_vals = _smap(
        body, plan.mesh,
        in_specs=(table_spec, (table_spec,) * len(slab_names),
                  hot_spec, (hot_spec,) * len(slab_names),
                  batch_spec, batch_spec, batch_spec, batch_spec,
                  batch_spec, PartitionSpec()),
        out_specs=(table_spec, (table_spec,) * len(slab_names),
                   hot_spec, (hot_spec,) * len(slab_names)),
    )(table, tuple(slabs[k] for k in slab_names),
      hot_table, tuple(hot_slabs[k] for k in slab_names),
      owner, local_id, gid, hot_id, upd, step)
    return (new_tbl, dict(zip(slab_names, new_slab_vals)),
            new_hot, dict(zip(slab_names, new_hot_vals)))


# ---- accounting ----------------------------------------------------------


def _exchange_buffer_blocks(plan: RowShardPlan) -> int:
    """Per-peer block count of the exchange buffers ONE device actually
    SENDS: the fused all-to-all (and the chunked multi-axis pipelined
    form, which moves identical bytes) ships all S blocks including the
    device's own; the single-axis ppermute ring keeps the self block
    local, so only S-1 blocks travel. The HLO byte predictions below
    must match the lowered program instruction for instruction, so they
    account for which form `_a2a` lowers."""
    if plan.overlap and len(plan.row_axes) == 1 and plan.nshards > 1:
        return plan.nshards - 1
    return plan.nshards


def _hlo_exchange_bytes(plan: RowShardPlan, C: int, d: int,
                        table_itemsize: int) -> int:
    S = _exchange_buffer_blocks(plan)
    fwd = S * C * 4 + S * C * d * table_itemsize
    bwd = S * C * 4 + S * C * 4 + S * C * d * 4
    return int(fwd + bwd)


def dense_exchange_hlo_bytes(plan: RowShardPlan, lookups_global: int,
                             d: int, table_itemsize: int = 4) -> int:
    """Exchange buffer bytes ONE device sends per step under the DENSE
    padded exchange this jax implementation actually lowers — what the
    HLO auditor must find in the partitioned program, instruction for
    instruction: request ids out (S x C int32), embedded rows back
    (S x C x d at the table dtype), then the gradient path's id + global-
    position + fp32 update-row exchanges. C (slot capacity per peer) is
    the full local lookup count n_local — the always-exact worst case —
    so the dense exchange moves S x the BALANCED bytes the cost model
    prices (`exchange_bytes_per_step`); the drift report shows both.
    Under the single-axis pipelined exchange (`plan.overlap`) the self
    block never travels, so S drops to nshards-1 and the bytes land in
    the collective-permute bucket instead of all-to-all — the auditor
    folds the buckets together (analysis/hlo_audit.py)."""
    n_local = int(lookups_global) // max(plan.ndev, 1)
    return _hlo_exchange_bytes(plan, n_local, d, table_itemsize)


def dedup_exchange_hlo_bytes(plan: RowShardPlan, lookups_global: int,
                             d: int, table_itemsize: int = 4) -> int:
    """The dedup'd sibling of :func:`dense_exchange_hlo_bytes`: the
    unique-ids exchange lowers the same four exchanges but at per-peer
    capacity C = min(n_local, flat_rows_local) — after dedup an owner
    can never receive more DISTINCT requests than it has rows, so the
    padded buffers shrink exactly when duplicates are structurally
    guaranteed. Deterministic, so FLX513 can pin predicted == lowered
    on the dedup plan too (overlap-aware like the dense form)."""
    n_local = int(lookups_global) // max(plan.ndev, 1)
    return _hlo_exchange_bytes(plan, plan.capacity(n_local), d,
                               table_itemsize)


def exchange_bytes_per_step(plan: RowShardPlan, lookups_global: int,
                            d: int, itemsize: int = 4,
                            backward: bool = True,
                            distinct_per_device: Optional[float] = None
                            ) -> int:
    """All-to-all bytes ONE device moves per step under the BALANCED
    (ragged / production) exchange: request ids out, embedded rows
    back, and (backward) gradient rows out again — each (P-1)/P of the
    device's routed share. `distinct_per_device` overrides the per-
    device routed count (the dedup'd exchange routes DISTINCT ids —
    pass the measured or expected count so reported bytes scale with
    skew, not batch size). What bench_shard reports and the cost model
    prices."""
    n_dev = lookups_global / max(plan.ndev, 1)
    if distinct_per_device is not None:
        n_dev = float(distinct_per_device)
    frac = (plan.nshards - 1) / plan.nshards
    fwd = n_dev * frac * (4 + d * itemsize)
    bwd = n_dev * frac * (4 + d * 4) if backward else 0.0
    return int(fwd + bwd)
