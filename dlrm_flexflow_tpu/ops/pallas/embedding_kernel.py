"""Pallas TPU embedding-bag kernel.

The reference implements embedding lookup with a custom CUDA gather forward
and an atomicAdd scatter-add backward (reference: src/ops/embedding.cu:173-224)
plus an AVX2 CPU embedding-bag path (src/ops/embedding_avx2.cc). The TPU has
no atomics and gathers are HBM-bandwidth bound, so the design here is:

- forward: a Pallas kernel that keeps the table in HBM and streams exactly
  the needed rows into VMEM with async DMA. Random 512 B reads are
  latency-bound and cost the scalar core a few instructions each, so a
  grid step covers a block of a few hundred row fetches (`_gather_block`),
  starts the NEXT block's fetches before it waits for its own, and sums
  the bag once a block — the TPU analog of the AVX2 embedding-bag blocked
  loads. Indices arrive via scalar prefetch so row addresses are known
  before the body runs.
  Mosaic requires HBM row slices to be exactly one (1, 128) lane tile, so
  a row of width dim = k*128 is streamed as k chunk-DMAs against a
  (rows*k, 128) view of the table; tables whose dim is not a multiple of
  128 fall back to the XLA gather (`embedding_bag_reference`).
- backward: no atomics — sort the flat indices and segment-sum the incoming
  gradients (indices_are_sorted lets XLA lower it as a linear pass), which
  replaces the reference's atomicAdd scatter.

`embedding_bag` is a custom_vjp function usable both standalone and from
ops/embedding.py. On non-TPU backends pass interpret=True (tests do) or use
`embedding_bag_reference`, the plain-XLA equivalent and test oracle.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one float32 sublane tile: the granule of a gather block's sample count
_SUBLANES = 8
_LANES = 128
# gather-kernel block, in (1, 128) chunk fetches: how many the kernel starts
# before it waits (two blocks are in flight, _bag_kernel). Swept on the v5e,
# f32[11739136,128], 89,856 zipf ids, ns a row with the id clamp around the
# kernel: 64 -> 5.64, 128 -> 4.91, 256 -> 4.81, 512 -> 4.75 (PERF.md, PR 25);
# 256 is within 1.3% of 512 at half the VMEM and splits small batches finer
_GATHER_FETCHES = 256
# scatter-kernel block: the update DMA pipeline drains at each grid-step
# boundary, so the block size IS the outstanding-write depth; 64 keeps
# the random-write pipeline full (8 left the update ~3x slower per row
# than the gather, r5 calibration) at a modest 32 KB VMEM cost
_SCATTER_B = 64


def supports(dim: int) -> bool:
    """True if the Pallas path handles this table width."""
    return dim % _LANES == 0


def _gather_block(batch: int, bag: int, k: int) -> int:
    """Samples a grid step of the gather covers.

    A block is g*bag*k chunk fetches, all in flight at once. The rule keeps
    that at or under _GATHER_FETCHES (but a block is one sublane tile of
    samples at the least, so never under 8*bag*k): that bounds each of the
    kernel's two landing buffers at that many 512 B chunks, one wait at
    g*16 <= 4096 DMA granules, and the output block at g*k chunks. A block
    never covers more than the batch rounded up to a sublane tile.
    """
    g_max = max(_SUBLANES,
                _GATHER_FETCHES // (bag * k) // _SUBLANES * _SUBLANES)
    return min(g_max, pl.cdiv(batch, _SUBLANES) * _SUBLANES)


def _bag_kernel(bag: int, k: int, idx_ref, table_ref, out_ref, planes, sems):
    """One grid step = one block of g samples (`_gather_block`).

    table_ref is the (rows*k, 128) chunk view resident in HBM. Chunk c of
    bag slot b of sample s lands in row s of planes[slot, b*k + c], a
    (g, 128) tile column (Mosaic takes a one-sublane DMA target only in a
    buffer one lane tile wide). Step i starts every fetch of block i+1 into
    the other slot BEFORE it waits for block i, whose fetches step i-1
    started: the fetch queue never drains between blocks. Then the planes
    are summed over b in float32 into the output block; for bag == 1 that
    is a copy, bit for bit.
    """
    i, n = pl.program_id(0), pl.num_programs(0)
    g = out_ref.shape[0]

    def start_block(blk):
        slot = blk % 2

        def tile(t, carry):
            # one sublane tile of samples a trip: Mosaic unrolls a fori_loop
            # fully or not at all, and hundreds of bodies would bloat set-up
            for u in range(_SUBLANES):
                s = t * _SUBLANES + u
                for b in range(bag):
                    # chunk c of table row idx[s, b] is view row idx*k + c
                    row = idx_ref[(blk * g + s) * bag + b] * k
                    for c in range(k):
                        pltpu.make_async_copy(
                            table_ref.at[pl.ds(row + c, 1), :],
                            planes.at[slot, b * k + c, pl.ds(s, 1), :],
                            sems.at[slot]).start()
            return carry

        jax.lax.fori_loop(0, g // _SUBLANES, tile, None)

    @pl.when(i == 0)
    def _():
        start_block(0)

    @pl.when(i + 1 < n)
    def _():
        start_block(i + 1)

    slot = i % 2
    for p in range(bag * k):
        # a wait takes the byte count of its target off the semaphore: one
        # wait for a whole plane stands for the g fetches that filled it
        plane = planes.at[slot, p]
        pltpu.make_async_copy(plane, plane, sems.at[slot]).wait()
    for c in range(k):
        acc = planes[slot, c].astype(jnp.float32)
        for b in range(1, bag):
            acc = acc + planes[slot, b * k + c].astype(jnp.float32)
        out_ref[:, c * _LANES:(c + 1) * _LANES] = acc.astype(out_ref.dtype)


def _pallas_forward(table: jax.Array, indices: jax.Array,
                    interpret: bool) -> jax.Array:
    """(rows, dim) × int(batch, bag) -> (batch, dim) sum-aggregated."""
    batch, bag = indices.shape
    rows, dim = table.shape
    if not supports(dim):
        raise ValueError(f"pallas embedding_bag needs dim % {_LANES} == 0, "
                         f"got {dim}; use embedding_bag_reference")
    k = dim // _LANES
    g = _gather_block(batch, bag, k)
    padded = pl.cdiv(batch, g) * g
    # the kernel runs without Mosaic's per-DMA bounds checks (two of them a
    # fetch: 10 of the 15 instruction bundles a row, 14.6 against 5.7 ns a
    # row on the v5e), so an id is clamped into the table here, as XLA's
    # gather clamps. Slots past the batch fetch row 0: under a block more.
    idx_flat = jnp.pad(
        jnp.clip(indices.astype(jnp.int32), 0, rows - 1).reshape(-1),
        (0, (padded - batch) * bag))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(padded // g,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((g, dim), lambda i, idx: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bag * k, g, _LANES), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    with jax.named_scope("emb_gather"):
        out = pl.pallas_call(
            functools.partial(_bag_kernel, bag, k),
            out_shape=jax.ShapeDtypeStruct((padded, dim), table.dtype),
            grid_spec=grid_spec,
            # a step waits for fetches the step before it started
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                disable_bounds_checks=True),
            interpret=interpret,
            name="emb_gather",
        )(idx_flat, table.reshape(rows * k, _LANES))
    return out[:batch]


def embedding_bag_reference(table, indices, aggr: str = "sum"):
    """Plain-XLA oracle/fallback: gather + reduce over the bag dim."""
    rows = jnp.take(table, indices.astype(jnp.int32), axis=0)
    if aggr == "avg":
        return jnp.mean(rows, axis=-2)
    return jnp.sum(rows, axis=-2)


def _primal(table, indices, aggr, interpret):
    out = _pallas_forward(table, indices, interpret)
    if aggr == "avg":
        out = out / indices.shape[-1]
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def embedding_bag(table, indices, aggr: str = "sum",
                  interpret: bool = False):
    """Embedding bag with a Pallas forward and sorted-segment-sum backward.

    table   : (rows, dim) float, dim % 128 == 0
    indices : (batch, bag) int
    returns : (batch, dim), sum or mean over the bag.
    """
    return _primal(table, indices, aggr, interpret)


def _fwd(table, indices, aggr, interpret):
    # zero-size residual whose static shape/dtype carry the table spec
    spec = jnp.zeros((table.shape[0], 0), table.dtype)
    return _primal(table, indices, aggr, interpret), (indices, spec)


def _bwd(aggr, interpret, res, g):
    indices, spec = res
    batch, bag = indices.shape
    gg = jnp.repeat(g, bag, axis=0).astype(jnp.float32)  # (batch*bag, dim)
    if aggr == "avg":
        gg = gg / bag
    flat = indices.astype(jnp.int32).reshape(-1)
    order = jnp.argsort(flat)
    dtable = jax.ops.segment_sum(
        gg[order], flat[order], num_segments=spec.shape[0],
        indices_are_sorted=True).astype(spec.dtype)
    # integer indices get a float0 cotangent
    return dtable, np.zeros(indices.shape, dtype=jax.dtypes.float0)


embedding_bag.defvjp(_fwd, _bwd)


def scatter_supports(dim: int) -> bool:
    """Row widths the scatter-add kernel handles: a whole number of lane
    tiles, or an exact divisor of one tile."""
    return dim % _LANES == 0 or _LANES % dim == 0


def _scatter_unique_kernel(idx_ref, upd_ref, tbl_ref, out_ref, bufs,
                           rsems, wsems):
    """One grid step applies _SCATTER_B tile updates, pipelined.

    PRECONDITION (established by scatter_add_rows' dedup pre-pass): all
    view-row targets with row >= 0 are DISTINCT, so the _SCATTER_B (64)
    RMWs of a block are independent: issue all reads, then add+write-back,
    then drain. row < 0 marks a padding slot and is skipped. The reference
    needed atomicAdd for this (embedding.cu:173-224); here distinctness
    replaces atomicity.
    """
    i = pl.program_id(0)

    def rd(s, row):
        return pltpu.make_async_copy(
            out_ref.at[pl.ds(row, 1), :], bufs.at[s], rsems.at[s])

    def wr(s, row):
        return pltpu.make_async_copy(
            bufs.at[s], out_ref.at[pl.ds(row, 1), :], wsems.at[s])

    for s in range(_SCATTER_B):            # static unroll: issue all reads
        row = idx_ref[i * _SCATTER_B + s]

        @pl.when(row >= 0)
        def _():
            rd(s, row).start()
    for s in range(_SCATTER_B):            # add + async write-back
        row = idx_ref[i * _SCATTER_B + s]

        @pl.when(row >= 0)
        def _():
            rd(s, row).wait()
            bufs[s] = (bufs[s] + upd_ref[pl.ds(s, 1), :]).astype(bufs.dtype)
            wr(s, row).start()
    for s in range(_SCATTER_B):            # drain before the next block
        row = idx_ref[i * _SCATTER_B + s]

        @pl.when(row >= 0)
        def _():
            wr(s, row).wait()


def scatter_add_rows(table: jax.Array, indices: jax.Array,
                     updates: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """table.at[indices].add(updates) for (rows, dim) tables — a Pallas
    in-place RMW kernel with an XLA dedup pre-pass.

    XLA's TPU scatter lowers to a serialized update loop that costs
    hundreds of ms for a few thousand rows on a multi-GB table. Here:
    (1) updates are expressed as (view_row, 128-lane tile) pairs — k
    chunks per row for wide tables, rotated d-wide slices for narrow ones;
    (2) duplicates are combined by sort + segment-sum (the sorted-segment
    trick that replaces the reference's atomicAdd backward); (3) a Pallas
    kernel streams the distinct tiles through a pipelined
    read-modify-write, touching only the updated bytes of HBM.

    table   : (rows, dim) float32
    indices : (n,) int — duplicates allowed
    updates : (n, dim) — same width as the table
    """
    rows, dim = table.shape
    (n,) = indices.shape
    if not scatter_supports(dim):
        return table.at[indices].add(updates.astype(table.dtype))
    indices = indices.astype(jnp.int32)
    updates = updates.astype(table.dtype)
    if dim % _LANES == 0:
        k = dim // _LANES
        view = table.reshape(rows * k, _LANES)
        # (n, dim) -> (n*k, 128) chunk tiles at view rows idx*k + c
        tile_rows = (indices[:, None] * k
                     + jnp.arange(k, dtype=jnp.int32)[None, :]).reshape(-1)
        tile_upds = updates.reshape(n * k, _LANES)
    else:
        r_per_tile = _LANES // dim
        if rows % r_per_tile:
            # padding the view would copy the whole table — not worth it
            return table.at[indices].add(updates)
        view = table.reshape(rows // r_per_tile, _LANES)
        tile_rows, tile_upds = _pack_tile_updates(indices, updates, dim,
                                                  updates.dtype)
    out = _dedup_and_scatter(view, tile_rows, tile_upds, interpret)
    return out.reshape(-1, dim)[:rows]


def scatter_add_rows_packed(view: jax.Array, indices: jax.Array,
                            updates: jax.Array, dim: int,
                            interpret: bool = False) -> jax.Array:
    """Scatter d-wide row updates into an ALREADY-PACKED (vrows, 128) view
    (the lane-packed parameter layout of the fused embedding ops —
    128 // dim unpacked rows per view row). Avoids the whole-table layout
    transposes XLA inserts when a narrow (rows, d) table is reshaped at
    the kernel boundary.

    view    : (vrows, 128) — packed table, 128 % dim == 0
    indices : (n,) int in UNPACKED row space — duplicates allowed
    updates : (n, dim)
    """
    tile_rows, tile_upds = _pack_tile_updates(indices, updates, dim,
                                              view.dtype)
    return _dedup_and_scatter(view, tile_rows, tile_upds, interpret)


def _pack_tile_updates(indices, updates, dim, dtype):
    """(n,) unpacked-row indices + (n, dim) updates -> (tile_rows,
    tile_upds (n, 128)): the packed-layout lane-placement math shared by
    the RMW and write-only scatters (tile = idx // r, lane offset =
    (idx % r)·d).

    The lane placement selects among the r = 128/d STATIC rotations of
    each padded row by a one-hot mask — a dynamic per-row `roll`
    (vmap(jnp.roll)) lowers to a per-row dynamic lane permute that alone
    cost ~8 ms for 8k rows on v5e (measured r5: it was the entire
    DLRM-family sparse-update bottleneck, ~85% of the train step). For
    VERY narrow tables (r > 16, i.e. dim <= 8) the static unroll emits up
    to 128 one-hot selects, inflating the HLO and compile time faster
    than the runtime win pays back — those fall back to the dynamic
    roll."""
    with jax.named_scope("scatter"):
        r_per_tile = _LANES // dim
        indices = indices.astype(jnp.int32)
        tile_rows = indices // r_per_tile
        padded = jnp.pad(updates.astype(dtype), ((0, 0), (0, _LANES - dim)))
        if r_per_tile == 1:
            return tile_rows, padded
        slot = indices % r_per_tile                       # (n,)
        if r_per_tile > 16:
            # low-dim fallback: one dynamic lane roll per row instead of r
            # unrolled one-hot selects (compile-time guard; see docstring)
            shift = (slot * dim).astype(jnp.int32)
            return tile_rows, jax.vmap(jnp.roll)(padded, shift)
        out = None
        for s in range(r_per_tile):
            rolled = jnp.roll(padded, s * dim, axis=1)    # static lane rotate
            # select, not multiply: 0 * NaN would smear a non-finite update
            # into the other unpacked rows sharing this tile
            sel = jnp.where((slot == s)[:, None], rolled,
                            jnp.zeros_like(rolled))
            out = sel if out is None else out + sel
        return tile_rows, out


def _dedup_tile_updates(tile_rows, tile_upds):
    """Combine same-tile updates so a scatter kernel sees DISTINCT rows:
    sort → segment-sum → per-segment target row (-1 marks invalid/pad
    slots) → pad to a _SCATTER_B multiple. Returns
    (target (m,), summed (m, 128), rep (m,), m) where rep[s] is one
    original position whose update landed in segment s (for callers that
    need a representative forward tile)."""
    with jax.named_scope("dedup"):
        m = tile_rows.shape[0]
        order = jnp.argsort(tile_rows)
        srows = tile_rows[order]
        supds = tile_upds[order]
        first = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                 srows[1:] != srows[:-1]])
        seg = jnp.cumsum(first) - 1                      # (m,) segment ids
        summed = jax.ops.segment_sum(supds, seg, num_segments=m,
                                     indices_are_sorted=True)
        target = jax.ops.segment_max(srows, seg, num_segments=m,
                                     indices_are_sorted=True)
        rep = jax.ops.segment_max(order, seg, num_segments=m,
                                  indices_are_sorted=True)
        num_unique = seg[-1] + 1
        valid = jnp.arange(m) < num_unique
        target = jnp.where(valid, target, -1).astype(jnp.int32)
        # empty segments get INT_MIN from segment_max; mask to a safe index so
        # downstream takes never depend on fill behavior (their rows carry
        # target=-1 and are skipped by the kernels regardless)
        rep = jnp.where(valid, rep, 0)

        pad_n = (-m) % _SCATTER_B
        if pad_n:
            target = jnp.pad(target, (0, pad_n), constant_values=-1)
            summed = jnp.pad(summed, ((0, pad_n), (0, 0)))
            rep = jnp.pad(rep, (0, pad_n))
            m += pad_n
        return target, summed, rep, m


def _dedup_and_scatter(view, tile_rows, tile_upds, interpret):
    target, summed, _, m = _dedup_tile_updates(tile_rows, tile_upds)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // _SCATTER_B,),
        in_specs=[
            pl.BlockSpec((_SCATTER_B, _LANES), lambda i, idx: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((_SCATTER_B, 1, _LANES), view.dtype),
            pltpu.SemaphoreType.DMA((_SCATTER_B,)),
            pltpu.SemaphoreType.DMA((_SCATTER_B,)),
        ],
    )
    with jax.named_scope("emb_scatter_add"):
        return pl.pallas_call(
            _scatter_unique_kernel,
            out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
            grid_spec=grid_spec,
            input_output_aliases={2: 0},
            interpret=interpret,
            name="emb_scatter_add",
        )(target, summed.astype(view.dtype), view)


def _scatter_write_kernel(idx_ref, val_ref, tbl_ref, out_ref, wsems):
    """Write-ONLY scatter: out[row] = val for _SCATTER_B distinct rows per
    grid step (row < 0 skipped). No read DMA: callers that kept the
    forward-gathered tiles compute new = fwd_tile + summed_update in XLA
    and this kernel just lands the rows — half the random-HBM traffic of
    the RMW form (the update side of the reference's atomicAdd backward,
    embedding.cu:173-224, with distinctness + precomputed values replacing
    atomicity)."""
    i = pl.program_id(0)
    for s in range(_SCATTER_B):            # static unroll: issue all writes
        row = idx_ref[i * _SCATTER_B + s]

        @pl.when(row >= 0)
        def _():
            pltpu.make_async_copy(
                val_ref.at[pl.ds(s, 1), :], out_ref.at[pl.ds(row, 1), :],
                wsems.at[s]).start()
    for s in range(_SCATTER_B):            # drain before the next block
        row = idx_ref[i * _SCATTER_B + s]

        @pl.when(row >= 0)
        def _():
            pltpu.make_async_copy(
                val_ref.at[pl.ds(s, 1), :], out_ref.at[pl.ds(row, 1), :],
                wsems.at[s]).wait()


def scatter_write_rows_packed(view: jax.Array, indices: jax.Array,
                              updates: jax.Array, fwd_tiles: jax.Array,
                              dim: int,
                              interpret: bool = False) -> jax.Array:
    """Sparse-SGD update WITHOUT the RMW read: the caller passes the
    forward-gathered packed tiles (one per lookup, same order as
    `indices`), so each unique target tile's new value is
    fwd_tile + sum(updates landing in it), computed in XLA, and the
    Pallas kernel performs pure writes.

    view      : (vrows, 128) packed table (donated/aliased)
    indices   : (n,) int in UNPACKED row space — duplicates allowed
    updates   : (n, dim) pre-scaled deltas (e.g. -lr * row_cotangent)
    fwd_tiles : (n, 128) the tile each lookup read in the forward pass
    """
    tile_rows, tile_upds = _pack_tile_updates(indices, updates, dim,
                                              view.dtype)
    target, summed, rep, m = _dedup_tile_updates(tile_rows, tile_upds)
    # any duplicate's forward tile is the same pre-update value, so the
    # representative original position's tile stands in for the segment
    with jax.named_scope("scatter"):
        vals = (jnp.take(fwd_tiles, rep, axis=0).astype(view.dtype)
                + summed.astype(view.dtype))
    return scatter_write_tiles(view, target, vals, interpret=interpret)


def scatter_write_tiles(view: jax.Array, target: jax.Array,
                        vals: jax.Array,
                        interpret: bool = False) -> jax.Array:
    """Pure-write scatter of whole (1, 128) tiles at DISTINCT view rows.

    PRECONDITIONS (the caller establishes them, e.g. via
    _dedup_tile_updates): targets are distinct; target < 0 marks a pad
    slot to skip; len(target) is a _SCATTER_B multiple. Used by the write-
    only sparse-SGD update and by the stateful (momentum/Adam) sparse
    update, which writes the new weight AND state tiles this way.

    view   : (vrows, 128) (donated/aliased)
    target : (m,) int32, m % _SCATTER_B == 0
    vals   : (m, 128) new tile values
    """
    m = target.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // _SCATTER_B,),
        in_specs=[
            pl.BlockSpec((_SCATTER_B, _LANES), lambda i, idx: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((_SCATTER_B,)),
        ],
    )
    with jax.named_scope("emb_scatter_write"):
        return pl.pallas_call(
            _scatter_write_kernel,
            out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
            grid_spec=grid_spec,
            input_output_aliases={2: 0},
            interpret=interpret,
            name="emb_scatter_write",
        )(target, vals.astype(view.dtype), view)


def sharded_scatter_add_packed(mesh, row_axes, view, indices, updates,
                               dim: int, interpret: bool = False):
    """Multi-chip form of scatter_add_rows_packed: the packed (vrows, 128)
    view is row-block sharded over `row_axes` of `mesh`; indices/updates
    are replicated. Under shard_map each device masks the updates to its
    row block (masked slots get row = -1, which the kernel skips) and
    runs the single-chip RMW kernel on its local block — the multi-chip
    analog of the reference's per-device atomicAdd into its own table
    replica partition (embedding.cu:173-224).

    view    : (vrows, 128) global packed table
    indices : (n,) int32 in UNPACKED row space, replicated
    updates : (n, dim), replicated
    """
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import smap

    r_per_tile = _LANES // dim
    vrows = view.shape[0]
    nshards = 1
    for a in row_axes:
        nshards *= mesh.shape[a]
    block = vrows // nshards             # packed rows per shard

    def local_update(tbl_shard, idx, upd):
        # linear shard index over the row axes
        sid = jnp.zeros((), jnp.int32)
        for a in row_axes:
            sid = sid * mesh.shape[a] + jax.lax.axis_index(a)
        lo = sid * block * r_per_tile          # unpacked-row lower bound
        hi = lo + block * r_per_tile
        local = idx - lo
        in_block = (idx >= lo) & (idx < hi)
        local = jnp.where(in_block, local, -(r_per_tile + 1))
        return scatter_add_rows_packed(tbl_shard, local, upd, dim,
                                       interpret=interpret)

    return smap(
        local_update, mesh,
        in_specs=(P(tuple(row_axes)), P(), P()),
        out_specs=P(tuple(row_axes)),
    )(view, indices.astype(jnp.int32), updates)


def stacked_embedding_bag(tables, indices, aggr: str = "sum",
                          interpret: bool = False):
    """Fused multi-table bag on the Pallas kernel.

    tables  : (T, rows, dim)
    indices : (batch, T, bag)
    returns : (batch, T, dim)

    The T tables are viewed as one (T*rows, dim) table and indices are
    offset by t*rows — the fused-table trick that turns the reference's
    per-table kernel launches (one Embedding op per DLRM table) into a
    single streaming kernel.
    """
    T, rows, dim = tables.shape
    batch = indices.shape[0]
    offs = (jnp.arange(T, dtype=jnp.int32) * rows)[None, :, None]
    flat_idx = (indices.astype(jnp.int32) + offs).reshape(batch * T, -1)
    out = embedding_bag(tables.reshape(T * rows, dim), flat_idx, aggr,
                        interpret)
    return out.reshape(batch, T, dim)
