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
- sparse update: no atomics either, and no XLA scatter (8-13 ns an element
  on the v5e). A sort makes duplicates adjacent; a second sort compacts each
  run's target row to the front, valid ones first; one kernel
  (`_run_sum_kernel`) fetches the update rows in sorted order and sums each
  run in VMEM (`_dedup_tile_updates`). That leaves DISTINCT target rows,
  and one kernel (`_scatter_kernel`) applies them in
  blocks of a few hundred (`_scatter_block`), as a read-modify-write
  (`emb_scatter_add`) or, where the caller kept the forward's rows, as pure
  writes (`emb_scatter_write`): the reads of the next block and the writes
  of the last are in flight while a block is added, one vector add a block.
  It runs without Mosaic's bounds checks: the count of valid targets,
  computed in XLA, is what keeps every DMA inside the table.

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
# scatter-kernel block, in (1, 128) tile updates a grid step (`_scatter_block`).
# Swept on the v5e with three landing buffers, kernel time in us (PERF.md,
# PR 27), 128 / 256 / 512 / 1024:
#   read-modify-write, f32[11739136,128], 89,856 slots
#     all valid:                            797 / 750 / 716 / 710
#     zipf 1.05 (18,141 valid, the rest pads): 256 / 204 / 173 / 166
#     3,328 slots, zipf (1,441 valid):      16.6 / 16.1 / 17.7 / 17.2
#   write-only, f32[4000000,128], 65,536 uniform slots: 313 / 281 / 271 / 266
# (8.3 and 4.3 ns a valid row at 256; the parent's blocks of 64: 3825, 2969,
# 121 and 957 us). 256 is within 5% of 512 on full blocks, divides the
# benchmark's three slot counts, so the dedup pads none of them (a pad
# copies the (m, 128) updates: more than 512 would win back), and splits
# small calls finer. What 512 gains under zipf is grid steps past the count
# (0.19 us each, 280 of 351 there)
_SCATTER_ROWS = 256
# landing buffers a scatter rotates through: with three, block i+1's reads
# and block i-1's writes are both in flight while block i is added; two
# (the writes waited before the next reads start) cost 6% and 13% more
_SCATTER_SLOTS = 3
# run-sum kernel block, in sorted slots a grid step (`_run_block`): one row
# fetch a slot, as the gather's block, and a (block, block) one-hot product,
# which grows with the square. Swept on the v5e, kernel time in us (PERF.md,
# PR 29), 128 / 256 (/ 512 with the product at HIGHEST, which costs 0.9 ns a
# slot more at 256): 89,856 zipf slots of 128: 584 / 521 (/ +240);
# 65,536 uniform: 400 / 389 (/ +130)
_RUN_ROWS = 256


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


def _start_fetches(bag: int, k: int, idx_ref, src_ref, planes, sems, blk):
    """Start every fetch of block blk into planes[blk % 2]: chunk c of bag
    slot b of sample s, row idx[(blk*g + s)*bag + b] of the (rows*k, 128)
    chunk view src_ref in HBM, lands in row s of plane b*k + c (Mosaic
    takes a one-sublane DMA target only in a buffer one lane tile wide)."""
    g, slot = planes.shape[2], blk % 2

    def tile(t, carry):
        # one sublane tile of samples a trip: Mosaic unrolls a fori_loop
        # fully or not at all, and hundreds of bodies would bloat set-up
        for u in range(_SUBLANES):
            s = t * _SUBLANES + u
            for b in range(bag):
                # chunk c of source row idx[s, b] is view row idx*k + c
                row = idx_ref[(blk * g + s) * bag + b] * k
                for c in range(k):
                    pltpu.make_async_copy(
                        src_ref.at[pl.ds(row + c, 1), :],
                        planes.at[slot, b * k + c, pl.ds(s, 1), :],
                        sems.at[slot]).start()
        return carry

    jax.lax.fori_loop(0, g // _SUBLANES, tile, None)


def _wait_fetches(planes, sems, slot):
    """Wait for every fetch into planes[slot]: a wait takes the byte count
    of its target off the semaphore, so one wait for a whole plane stands
    for the g fetches that filled it."""
    for p in range(planes.shape[1]):
        plane = planes.at[slot, p]
        pltpu.make_async_copy(plane, plane, sems.at[slot]).wait()


def _bag_kernel(bag: int, k: int, idx_ref, table_ref, out_ref, planes, sems):
    """One grid step = one block of g samples (`_gather_block`).

    table_ref is the (rows*k, 128) chunk view resident in HBM; a sample's
    bag*k chunks land in as many (g, 128) planes (`_start_fetches`). Step i
    starts every fetch of block i+1 into the other slot BEFORE it waits for
    block i, whose fetches step i-1 started: the fetch queue never drains
    between blocks. Then the planes are summed over b in float32 into the
    output block; for bag == 1 that is a copy, bit for bit.
    """
    i, n = pl.program_id(0), pl.num_programs(0)

    @pl.when(i == 0)
    def _():
        _start_fetches(bag, k, idx_ref, table_ref, planes, sems, 0)

    @pl.when(i + 1 < n)
    def _():
        _start_fetches(bag, k, idx_ref, table_ref, planes, sems, i + 1)

    slot = i % 2
    _wait_fetches(planes, sems, slot)
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


def _scatter_block(m: int) -> int:
    """Tile updates a grid step of the scatter kernels covers: _SCATTER_ROWS,
    or all m of a smaller call, rounded up to a sublane tile. That bounds
    each landing buffer at that many 512 B tiles and a call's padding at
    under one block."""
    return min(_SCATTER_ROWS, pl.cdiv(m, _SUBLANES) * _SUBLANES)


def _scatter_kernel(rmw: bool, idx_ref, cnt_ref, upd_ref, tbl_ref, out_ref,
                    bufs, rsems, wsems):
    """One grid step = one block of b tile updates (`_scatter_block`), both
    forms: read-modify-write (out[row] += upd) and write-only
    (out[row] = upd; the same pipeline without the read stage and the add).

    PRECONDITION (established by _dedup_tile_updates, whose second sort
    leaves each run's row once and the valid ones in front, and
    _valid_prefix, which counts them):
    the first cnt_ref[0] targets are DISTINCT rows of the view, so every
    row's read-modify-write is independent of every other's, in its block
    and across blocks. The reference needed atomicAdd for this
    (embedding.cu:173-224); here distinctness replaces atomicity. Slots at
    and past the count are never read: the kernel runs without Mosaic's
    per-DMA bounds checks, and the count is what keeps it inside the view.

    Row s of block i lives in row s of bufs[i % slots]. Step i waits for
    the writes of the block whose buffer block i+1 takes (i-2 with three
    buffers: they had a whole step), starts the reads of block i+1 into
    it, and only then waits for its own reads, which step i-1 started:
    neither DMA queue drains between blocks. It adds the update block in
    one vector operation, starts its writes from the same buffer and
    leaves them in flight; the last step drains. A block under the count
    runs with no branch a row and one wait by byte count; the one block
    the count ends in waits a row at a time; a block past it starts
    nothing.
    """
    i, n = pl.program_id(0), pl.num_programs(0)
    slots, b = bufs.shape[0], bufs.shape[1]

    def live(blk):
        return jnp.clip(cnt_ref[0] - blk * b, 0, b)

    def for_rows(blk, body):
        """body(s, row) for the live rows of block blk: a sublane tile of
        rows a trip (Mosaic unrolls a fori_loop fully or not at all), then
        the rows of a last partial tile one a trip."""
        c = live(blk)

        def one(s, carry):
            body(s, idx_ref[blk * b + s])

        def tile(t, carry):
            for u in range(_SUBLANES):
                one(t * _SUBLANES + u, carry)

        tiles = c // _SUBLANES
        jax.lax.fori_loop(0, tiles, tile, None)
        jax.lax.fori_loop(tiles * _SUBLANES, c, one, None)

    def start_reads(blk):
        slot = blk % slots
        for_rows(blk, lambda s, row: pltpu.make_async_copy(
            out_ref.at[pl.ds(row, 1), :], bufs.at[slot, pl.ds(s, 1), :],
            rsems.at[slot]).start())

    def start_writes(blk):
        slot = blk % slots
        for_rows(blk, lambda s, row: pltpu.make_async_copy(
            bufs.at[slot, pl.ds(s, 1), :], out_ref.at[pl.ds(row, 1), :],
            wsems.at[slot]).start())

    def wait_rows(blk, sems):
        """Take block blk's live rows off its semaphore: a wait takes the
        byte count of its target, so one stands for a whole block."""
        slot, c = blk % slots, live(blk)

        @pl.when(c == b)
        def _():
            whole = bufs.at[slot]
            pltpu.make_async_copy(whole, whole, sems.at[slot]).wait()

        @pl.when(c < b)
        def _():
            row = bufs.at[slot, pl.ds(0, 1), :]

            def wait_one(s, carry):
                pltpu.make_async_copy(row, row, sems.at[slot]).wait()

            jax.lax.fori_loop(0, c, wait_one, None)

    @pl.when(i + 1 >= slots)
    def _():
        wait_rows(i + 1 - slots, wsems)

    slot = i % slots
    if rmw:
        @pl.when(i == 0)
        def _():
            start_reads(0)

        @pl.when(i + 1 < n)
        def _():
            start_reads(i + 1)

        wait_rows(i, rsems)

    @pl.when(live(i) > 0)
    def _():
        upd = upd_ref[...]
        bufs[slot] = bufs[slot] + upd if rmw else upd

    start_writes(i)

    @pl.when(i + 1 == n)
    def _():
        for back in range(slots - 2, 0, -1):
            @pl.when(i >= back)
            def _():
                wait_rows(i - back, wsems)

        wait_rows(i, wsems)


def _valid_prefix(target: jax.Array, view_rows: int) -> jax.Array:
    """(1,) int32: how many leading targets are rows of the view. It is all
    the kernel sees of the rest, so a pad (-1) or an id outside the view is
    dropped wherever it stands, and every slot after it."""
    m = target.shape[0]
    with jax.named_scope("scatter"):
        inside = (target >= 0) & (target < view_rows)
        return jnp.min(jnp.where(inside, m, jnp.arange(m, dtype=jnp.int32)),
                       keepdims=True).astype(jnp.int32)


def _check_prefix(view_rows, target, count):
    """Interpret mode only: no row of the view may stand behind the count,
    where `_valid_prefix` has dropped it with everything after the first
    pad or outside id."""
    late = (target[int(count[0]):] >= 0) & (target[int(count[0]):] < view_rows)
    if late.any():
        raise ValueError(
            f"{int(late.sum())} scatter targets inside the view stand behind "
            f"the first invalid one (slot {int(count[0])}) and would be "
            "dropped; order them first, as _dedup_tile_updates does")


def _scatter_call(view, target, interpret):
    """What the two scatters' pallas_calls share: the count of valid
    targets, and every argument but the kernel and its name. `target` is
    a `_scatter_block` multiple long."""
    m = target.shape[0]
    b = _scatter_block(m)
    if m % b:
        raise ValueError(f"{m} scatter targets are not a multiple of the "
                         f"block of {b}; pad them as _dedup_tile_updates does")
    count = _valid_prefix(target, view.shape[0])
    if interpret:
        # the chip runs unchecked; the interpreter is where a caller that
        # breaks the prefix contract is told, not silently short of updates
        jax.debug.callback(functools.partial(_check_prefix, view.shape[0]),
                           target, count)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // b,),
        in_specs=[
            # a block past the count is never read: name the last live one
            # again, and the pipeline fetches nothing
            pl.BlockSpec((b, _LANES), lambda i, idx, cnt: (
                jnp.minimum(i, jnp.maximum(cnt[0] - 1, 0) // b), 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((_SCATTER_SLOTS, b, _LANES), view.dtype),
            pltpu.SemaphoreType.DMA((_SCATTER_SLOTS,)),
            pltpu.SemaphoreType.DMA((_SCATTER_SLOTS,)),
        ],
    )
    return count, dict(
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        grid_spec=grid_spec,
        input_output_aliases={3: 0},
        # a step waits for DMAs that earlier steps started
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            disable_bounds_checks=True),
        interpret=interpret)


def _scatter_add_tiles(view, target, tiles, interpret=False):
    """view[target] += tiles for the distinct valid targets (the
    read-modify-write twin of `scatter_write_tiles`, same preconditions)."""
    count, shared = _scatter_call(view, target, interpret)
    with jax.named_scope("emb_scatter_add"):
        return pl.pallas_call(
            functools.partial(_scatter_kernel, True),
            name="emb_scatter_add", **shared,
        )(target, count, tiles.astype(view.dtype), view)


def scatter_add_rows(table: jax.Array, indices: jax.Array,
                     updates: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """table.at[indices].add(updates) for (rows, dim) tables — a Pallas
    in-place RMW kernel behind a dedup pre-pass.

    XLA's TPU scatter lowers to a serialized update loop that costs
    hundreds of ms for a few thousand rows on a multi-GB table. Here:
    (1) updates are expressed as (view_row, 128-lane tile) pairs — k
    chunks per row for wide tables, rotated d-wide slices for narrow ones;
    (2) duplicates are combined by `_dedup_tile_updates`: two sorts and a
    kernel pass that sums each run of equal rows (adjacency after the sort
    replaces the reference's atomicAdd backward); (3) a Pallas
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


def _run_block(m: int) -> int:
    """Sorted slots a grid step of the run-sum kernel covers: _RUN_ROWS, or
    one lane tile where that holds all m (a slot is a lane of the block's
    one-hot)."""
    return min(_RUN_ROWS, pl.cdiv(m, _LANES) * _LANES)


def _run_sum_kernel(k: int, order_ref, start_ref, end_ref, seg_ref, upd_ref,
                    out_ref, planes, sums, zeros, carry, fsems, wsem, zsem):
    """One grid step = one block of b slots of the SORTED order
    (`_run_block`): out[c, r] = chunk c of the float32 sum of the update
    rows of run r.

    upd_ref is the (m*k, 128) chunk view of the UNSORTED updates in HBM.
    Slot s of block i fetches the k chunks of row order[i*b + s] into row s
    of planes[i % 2], as `_bag_kernel` fetches table rows: step i starts
    block i+1's fetches before it waits for its own, and the permuted copy
    of the updates never exists in HBM. seg_ref holds the block's run ids,
    ascending, runs adjacent; start_ref[i] and end_ref[i] are the first and
    the last of them. The runs are summed where the rows are: the one-hot
    (b, b) of `seg - start` times a (b, 128) plane is every run's sum,
    already compacted to the front of the block, and rows past the block's
    runs come out 0 (a slot past m carries seg -1 and is in no run). The
    product keeps every bit of an update and accumulates in float32. A run
    that crosses into the next block hands its partial sum on in `carry`.

    Block i's b rows of sums are written at out[c, start[i]:start[i] + b],
    in order: each write waits for the one before it, so where two overlap
    (block i+1 starts inside block i's rows, at the latest on the zeros
    behind its runs) the later block's rows stand. Rows from the number of
    runs on are zeroed, one aligned block a step from a block of zeros, the
    lowest first and landed before step 0 writes: only that one reaches
    below the number of runs.

    Like the kernels beside it this runs without Mosaic's bounds checks:
    order is a permutation of the slots and start[i] + b <= i*b + b. A
    non-finite update reaches every run of its block (0 * NaN): such a
    step is the anomaly sentinel's to drop, not the kernel's.
    """
    i, n = pl.program_id(0), pl.num_programs(0)
    b = planes.shape[2]

    @pl.when(i == 0)
    def _():
        _start_fetches(1, k, order_ref, upd_ref, planes, fsems, 0)
        zeros[...] = jnp.zeros_like(zeros)
        carry[...] = jnp.zeros_like(carry)

    @pl.when(i + 1 < n)
    def _():
        _start_fetches(1, k, order_ref, upd_ref, planes, fsems, i + 1)

    # zero block i of the tail, counted from the lowest: n*b rows, the runs
    # in front, whole blocks of zeros aligned from the end over the rest
    tail_blocks = (n * b - end_ref[n - 1] - 1 + b - 1) // b
    zero_row = pl.multiple_of((n - tail_blocks + i) * b, b)

    def zero_fill(c):
        return pltpu.make_async_copy(
            zeros, out_ref.at[c, pl.ds(zero_row, b), :], zsem)

    @pl.when(i < tail_blocks)
    def _():
        for c in range(k):
            zero_fill(c).start()

    slot = i % 2
    _wait_fetches(planes, fsems, slot)
    run = seg_ref[...] - start_ref[i]                      # (1, b)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
              == run).astype(jnp.bfloat16)
    for c in range(k):
        # a float32 is the sum of three bfloat16 pieces exactly (8 + 8 + 8
        # mantissa bits) and a 0/1 is one, so three single-pass products
        # accumulated in float32 are what HIGHEST's six passes give
        # (5.8 against 6.7 ns a slot on the v5e, PERF.md, PR 29)
        hi = planes[slot, c].astype(jnp.bfloat16)
        rest = planes[slot, c] - hi.astype(jnp.float32)
        mid = rest.astype(jnp.bfloat16)
        low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
        sums[slot, c] = sum(
            jnp.dot(onehot, piece, preferred_element_type=jnp.float32)
            for piece in (hi, mid, low))

    @pl.when((i > 0) & (start_ref[i] == end_ref[jnp.maximum(i - 1, 0)]))
    def _():
        for c in range(k):
            sums[slot, c, 0:1, :] = sums[slot, c, 0:1, :] + carry[c]

    for c in range(k):
        carry[c] = sums[slot, c, pl.ds(end_ref[i] - start_ref[i], 1), :]

    def write(blk, c):
        return pltpu.make_async_copy(
            sums.at[blk % 2, c],
            out_ref.at[c, pl.ds(start_ref[blk], b), :], wsem)

    @pl.when(i < tail_blocks)
    def _():
        for c in range(k):
            zero_fill(c).wait()

    @pl.when(i > 0)
    def _():
        for c in range(k):
            write(i - 1, c).wait()

    for c in range(k):
        write(i, c).start()

    @pl.when(i + 1 == n)
    def _():
        for c in range(k):
            write(i, c).wait()


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def _run_sums(tile_upds, order, seg, rows: int, interpret):
    """(rows, W) float32: row r the sum of the rows of `tile_upds` whose
    sorted slot carries run id r, rows past the last run 0. `order` (m,) is
    the slots' original positions in sorted order, `seg` (m,) their run
    ids; rows >= m. Jitted: a memory space is constrained only in a traced
    program, and callers may run op by op."""
    m, width = tile_upds.shape
    k = width // _LANES
    b = _run_block(rows)
    blocks = pl.cdiv(rows, b)
    pad = blocks * b - m
    # a slot past m fetches row 0 and is in no run (-1); the first and the
    # last run id of a block are those of its real slots
    order = jnp.pad(order, (0, pad))
    edged = jnp.pad(seg, (0, pad), mode="edge").reshape(blocks, b)
    seg = jnp.pad(seg, (0, pad), constant_values=-1)
    chunks = tile_upds.astype(jnp.float32).reshape(m * k, _LANES)
    if not interpret:
        # left to itself XLA keeps a temporary of this size in VMEM, and a
        # row DMA from there costs 1.7x one from HBM (PERF.md, PR 29); the
        # interpreter knows no memory spaces
        chunks = pltpu.with_memory_space_constraint(chunks, pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((None, 1, b), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, k, b, _LANES), jnp.float32),   # rows fetched
            pltpu.VMEM((2, k, b, _LANES), jnp.float32),   # their run sums
            pltpu.VMEM((b, _LANES), jnp.float32),         # zeros
            pltpu.VMEM((k, 1, _LANES), jnp.float32),      # carry
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    with jax.named_scope("emb_run_sum"):
        out = pl.pallas_call(
            functools.partial(_run_sum_kernel, k),
            # chunk planes: Mosaic takes a block of rows at any row of an
            # HBM array one lane tile wide, not of a wider one
            out_shape=jax.ShapeDtypeStruct((k, blocks * b, _LANES),
                                           jnp.float32),
            grid_spec=grid_spec,
            # a step waits for DMAs that the step before it started
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                disable_bounds_checks=True),
            interpret=interpret,
            name="emb_run_sum",
        )(order, edged[:, 0], edged[:, -1], seg.reshape(blocks, 1, b),
          chunks)
    return jnp.concatenate(list(out[:, :rows]), axis=1)


def _dedup_tile_updates(tile_rows, tile_upds, interpret=False):
    """Combine same-tile updates so a scatter kernel sees DISTINCT rows.
    Returns (target (m,), summed (m, W), rep (m,), m), m a `_scatter_block`
    multiple: target[s] is the view row of run s, summed[s] the float32 sum
    of its updates (W a multiple of 128), rep[s] the original position of
    one of its members (for callers that need a representative forward
    tile: every member read the same pre-update tile).

    After a sort the duplicates are adjacent, so nothing here needs a
    scatter. The sort reads a row id as unsigned and carries the original
    positions (in no promised order inside a run: any member represents
    it, and a sum's order is free): ids a view can hold come first, ascending as a signed sort
    puts them, and an id below 0 sorts behind every one of them. A run's
    target is the key at its first slot: a second sort, of the keys with
    every other slot masked to 0xFFFFFFFF (-1, the pad, and already the
    largest key), compacts targets and positions to the front. So the valid
    targets are a prefix of `target`, each once; behind them stand ids
    outside the view, which only the scatter knows (`_valid_prefix` drops
    them), and then the pads (-1). The run sums are one kernel pass over
    the unsorted updates (`_run_sum_kernel`)."""
    with jax.named_scope("dedup"):
        m = tile_rows.shape[0]
        skeys, order = jax.lax.sort(
            (jax.lax.bitcast_convert_type(tile_rows, jnp.uint32),
             jnp.arange(m, dtype=jnp.int32)), num_keys=1, is_stable=False)
        first = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                 skeys[1:] != skeys[:-1]])
        seg = jnp.cumsum(first.astype(jnp.int32)) - 1    # (m,) run ids
        tkeys, rep = jax.lax.sort(
            (jnp.where(first, skeys, jnp.uint32(0xFFFFFFFF)), order),
            num_keys=1, is_stable=False)
        pad_n = (-m) % _scatter_block(m)
        target = jnp.pad(jax.lax.bitcast_convert_type(tkeys, jnp.int32),
                         (0, pad_n), constant_values=-1)
        rep = jnp.pad(rep, (0, pad_n))
        m += pad_n
        return target, _run_sums(tile_upds, order, seg, m, interpret), rep, m


def _dedup_and_scatter(view, tile_rows, tile_upds, interpret):
    target, summed, _, _ = _dedup_tile_updates(tile_rows, tile_upds,
                                               interpret)
    return _scatter_add_tiles(view, target, summed, interpret)


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
    target, summed, rep, _ = _dedup_tile_updates(tile_rows, tile_upds,
                                                 interpret)
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
    _dedup_tile_updates): the targets that are rows of the view are
    distinct and stand first; behind them every slot is dropped, a pad
    (-1) or an id outside the view alike (interpret mode refuses a row of
    the view standing there, `_check_prefix`; the chip checks nothing);
    len(target) is a `_scatter_block` multiple. Used by the write-only sparse-SGD update
    and by the stateful (momentum/Adam) sparse update, which writes the
    new weight AND state tiles this way.

    view   : (vrows, 128) (donated/aliased)
    target : (m,) int32, m % _scatter_block(m) == 0
    vals   : (m, 128) new tile values
    """
    count, shared = _scatter_call(view, target, interpret)
    with jax.named_scope("emb_scatter_write"):
        return pl.pallas_call(
            functools.partial(_scatter_kernel, False),
            name="emb_scatter_write", **shared,
        )(target, count, vals.astype(view.dtype), view)


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
