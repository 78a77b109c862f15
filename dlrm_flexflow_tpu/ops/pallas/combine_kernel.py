"""The expert op's `combine` as a Pallas gather-sum: each token's fp32 sum
over the rows of its held pairs, the rows fetched by DMA, the held ones
alone.

`ops/moe.py` writes a pass's rows (the walk's outputs, or in the backward
the rows' input gradients) into a buffer in sorted order; `combine` sums
each token's k of them, in the order j = 0 .. k - 1, in fp32. As XLA that
is k gathers of all T rows, each masked where the pair is held on another
chip: 26-30 ns a 4 KB row on the v5e, and most of the rows thrown away
(PERF.md §6). Here:

- the grid walks blocks of `bt` tokens (`token_block`: bt k pairs a
  block). The plan (`_plan`) is prefetched into the scalar memory: each
  block's held pairs, together, one number each (its row in the buffer
  and where it lands), behind the first entry of every block; a sort by
  block in XLA puts them there, so a block's loop walks its held pairs
  alone (where it walked all bt k and skipped, the scalar core's ~7
  bundles a pair were half of the kernel's time: PERF.md §6). Block b
  starts the fetches of block b + 1 before it waits for its own
  (`embedding_kernel._bag_kernel` is the precedent); its wait takes as
  many rows off the semaphore as it has entries.
- the rows are bfloat16, the compute dtype of every model that runs an
  expert op (fp32 rows keep the gathers). A fetch is ONE DMA a row:
  Mosaic slices a one-row piece out of an HBM array only where the
  array's tiles are 32-bit (8, 128) and one lane tile wide. A bfloat16 row
  is half of a 32-bit sublane in XLA's layout ((8, 128)(2,1) tiles) and
  cannot be cut out by a DMA, and the (R, D / 128, 128) view is a relayout
  of the whole buffer (XLA copies it: 0.4-0.7 GB a pass at the cells'
  widths), so a first call (`moe_combine_pack`) packs the rows the walk
  wrote, and those alone, into (R / 8, planes, 8, 128) 32-bit words: lane
  l of a row's tiles 2p and 2p + 1 become one word of plane p (`_pack`),
  exact, since a bfloat16 is the high half of the float32 it converts to.
  Row r is then sublane r % 8 of tiles [r // 8, :], a strided copy of
  `planes` 512-byte pieces into the block's landing buffer, where a
  plane's rows are the block's tokens side by side.
- the step sums its k landing planes in fp32 in the order j = 0 .. k - 1,
  a pair held elsewhere adding 0.0 as the XLA form does (its landing slot
  holds whatever an earlier block left there: a select, not a product,
  keeps it out), and writes its (bt, D) block of the result. (A form that
  packed a row's planes side by side, one contiguous DMA a row, and read
  them with strided loads ran no faster on the v5e: PERF.md §6.)

The result is the XLA form's bit for bit (the same conversions, the same
adds in the same order) but for the sign of a zero: the sum starts from
pair 0's term, where the XLA form adds it to +0.0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .moe_kernel import _budgets

_LANES, _SUBLANES = 128, 8
# pairs a block of tokens may hold (`token_block`): bt k rows land in each
# of two buffers. 512 / 1,024 / 2,048 read the same on the v5e with a plan
# of an earlier form (PERF.md §6); a plan entry's landing row (`_ROW_BITS`)
# names 512 at the most
_IN_FLIGHT = 512
# sorted rows a step of the pack covers (1,024 and 2,048 read the same, 512
# slower): steps past the rows the walk wrote fetch nothing and cost a
# grid step each
_PACK_ROWS = 1024
_F32, _I32, _U32 = jnp.float32, jnp.int32, jnp.uint32
_HIGH = 0xFFFF0000
# a plan entry: the pair's row in the buffer in the low bits, its landing
# row (under k bt <= `_IN_FLIGHT`, nine bits) above
_ROW_BITS = 22
_ROW_MASK = (1 << _ROW_BITS) - 1


def token_block(tokens: int, top_k: int) -> int:
    """Tokens a grid step sums: the largest power of two (a sublane tile
    at the least) whose pairs stay within `_IN_FLIGHT` rows, and no more
    than the tokens rounded up to a sublane tile."""
    bt = _SUBLANES
    while 2 * bt * top_k <= _IN_FLIGHT:
        bt *= 2
    return min(bt, pl.cdiv(tokens, _SUBLANES) * _SUBLANES)


def _planes(d: int) -> int:
    """32-bit lane tiles a packed bfloat16 row takes: half its D / 128,
    rounded up."""
    return -(-(d // _LANES) // 2)


def _bits(x):
    """The float32 bits of x: for a bfloat16, its own 16 in the high half
    and zeros below."""
    return lax.bitcast_convert_type(x.astype(_F32), _U32)


def _pack_kernel(live_ref, x_ref, o_ref):
    """Rows of a (bn, D) block of the buffer into (bn / 8, planes, 8, 128)
    words: plane p holds tile 2p in the low half, 2p + 1 in the high."""
    bn, d = x_ref.shape

    @pl.when(pl.program_id(0) * bn < live_ref[0])
    def _():
        for p in range(o_ref.shape[1]):
            lo, hi = 2 * p * _LANES, (2 * p + 1) * _LANES
            word = lax.shift_right_logical(_bits(x_ref[:, lo:hi]),
                                           jnp.uint32(16))
            if hi < d:
                word = word | (_bits(x_ref[:, hi:hi + _LANES])
                               & jnp.uint32(_HIGH))
            o_ref[:, p] = word.reshape(bn // _SUBLANES, _SUBLANES, _LANES)


def _pack(rows_buf, live, interpret):
    """The bfloat16 rows (R, D) as (ceil(R / 8), planes, 8, 128) words, the
    first `live` (1,) of them: blocks past those fetch and write nothing."""
    r, d = rows_buf.shape
    bn = min(_PACK_ROWS, pl.cdiv(r, _SUBLANES) * _SUBLANES)
    planes = _planes(d)

    def block(i, live_ref):
        return jnp.minimum(i, jnp.maximum(pl.cdiv(live_ref[0], bn) - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(pl.cdiv(r, bn),),
        in_specs=[pl.BlockSpec((bn, d), lambda i, lv: (block(i, lv), 0))],
        out_specs=pl.BlockSpec((bn // _SUBLANES, planes, _SUBLANES, _LANES),
                               lambda i, lv: (block(i, lv), 0, 0, 0)))
    with jax.named_scope("moe_combine_pack"):
        return pl.pallas_call(
            _pack_kernel, name="moe_combine_pack", grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (pl.cdiv(r, _SUBLANES), planes, _SUBLANES, _LANES), _U32),
            interpret=interpret,
            # a step past the live rows keeps the last live block's
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_budgets()[0]))(live, rows_buf)


def _gather_kernel(top_k, tiles, plan_ref, flags_ref, src_ref, out_ref, land,
                   sems):
    """One grid step = one block of bt tokens (`token_block`).

    src_ref (R / 8, planes, 8, 128) packed words in HBM; plan_ref (`_plan`): the blocks'
    first entries, then one entry a held pair, a block's together; flags_ref
    (bt, k) whether each pair is held. Pair j of token t lands in row
    j bt + t of land[slot] (planes, k bt, 128); a block's wait takes as
    many rows off the semaphore as it has entries.
    """
    b, n = pl.program_id(0), pl.num_programs(0)
    bt = out_ref.shape[0]

    def start(blk):
        slot = blk % 2

        def fetch(i, carry=None):
            entry = plan_ref[n + 1 + i]
            row = entry & _ROW_MASK
            pltpu.make_async_copy(
                src_ref.at[lax.shift_right_logical(row, 3), :,
                           pl.ds(row & (_SUBLANES - 1), 1), :],
                land.at[slot, :, pl.ds(lax.shift_right_logical(
                    entry, _ROW_BITS), 1), :],
                sems.at[slot]).start()
            return carry

        def eight(g, carry):
            # eight entries a trip: Mosaic unrolls a fori_loop fully or not
            # at all, and a trip of one is a latency chain of ~30 bundles
            for u in range(_SUBLANES):
                fetch(lo + g * _SUBLANES + u)
            return carry

        lo, hi = plan_ref[blk], plan_ref[blk + 1]
        whole = (hi - lo) // _SUBLANES
        lax.fori_loop(0, whole, eight, None)
        lax.fori_loop(lo + whole * _SUBLANES, hi, fetch, None)

    @pl.when(b == 0)
    def _():
        start(0)

    @pl.when(b + 1 < n)
    def _():
        start(b + 1)

    slot = b % 2
    one = land.at[slot, :, pl.ds(0, 1), :]

    def wait(i, carry):
        pltpu.make_async_copy(one, one, sems.at[slot]).wait()
        return carry

    lax.fori_loop(plan_ref[b], plan_ref[b + 1], wait, None)

    flags = flags_ref[...]
    sums = None
    for j in range(top_k):
        # (planes, bt, 128) words, lane tiles 2p and 2p + 1 of each row; a
        # pair held elsewhere adds 0.0
        word = land[slot, :, j * bt:(j + 1) * bt]
        held = jnp.broadcast_to(flags[:, j:j + 1] != 0, word.shape[1:])[None]
        parts = [jnp.where(held, lax.bitcast_convert_type(w, _F32), 0.0)
                 for w in (lax.shift_left(word, jnp.uint32(16)),
                           word & jnp.uint32(_HIGH))]
        sums = parts if sums is None else [s + w for s, w in zip(sums, parts)]
    for c in range(tiles):
        out_ref[:, c * _LANES:(c + 1) * _LANES] = sums[c % 2][c // 2]


def _hbm(x, interpret):
    """Held in HBM: a row DMA from a temporary XLA parked in VMEM costs
    more (PERF.md §6); the interpreter knows no memory spaces."""
    return x if interpret else pltpu.with_memory_space_constraint(
        x, pltpu.HBM)


def _plan(pos, held_pair, top_k, bt):
    """The gather's prefetched plan, one int32 array: the first entry of
    each block of bt tokens and one past the last (blocks + 1), then an
    entry for each held pair, a block's together (the pairs held elsewhere
    behind them): its row in the buffer in the low `_ROW_BITS` bits, its
    landing row j bt + t above. A sort by block; the block walks only its
    own held pairs."""
    assert top_k * bt <= 1 << (31 - _ROW_BITS), "a landing row's bits"
    pairs = pl.cdiv(held_pair.size // top_k, bt) * bt * top_k
    held = jnp.pad(held_pair, (0, pairs - held_pair.size))
    pair = jnp.arange(pairs, dtype=_I32)
    t, j = pair // top_k, pair % top_k
    entry = (lax.shift_left(j * bt + t % bt, _ROW_BITS)
             | jnp.pad(pos.astype(_I32), (0, pairs - pos.size)))
    blocks = pairs // (bt * top_k)
    _, entry = lax.sort((jnp.where(held, t // bt, blocks), entry),
                        num_keys=1, is_stable=False)
    first = jnp.cumsum(jnp.sum(held.reshape(blocks, bt * top_k), axis=1,
                               dtype=_I32))
    return jnp.concatenate([jnp.zeros((1,), _I32), first, entry])


def _gather(plan, flags, src, top_k, d, interpret):
    """The gather-sum's call: (tokens padded to blocks, D) fp32 from the
    prefetched plan (`_plan`), the flags (padded tokens, k) and the source
    view."""
    bt, planes = token_block(flags.shape[0], top_k), src.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(flags.shape[0] // bt,),
        in_specs=[pl.BlockSpec((bt, top_k), lambda b, plan: (b, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((bt, d), lambda b, plan: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, planes, top_k * bt, _LANES), src.dtype),
            pltpu.SemaphoreType.DMA((2,))])
    with jax.named_scope("moe_combine"):
        return pl.pallas_call(
            functools.partial(_gather_kernel, top_k, d // _LANES),
            name="moe_combine", grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((flags.shape[0], d), _F32),
            interpret=interpret,
            # a step waits for fetches the step before it started
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_budgets()[0],
                disable_bounds_checks=True))(plan, flags, src)


@functools.partial(jax.jit, static_argnums=(3, 4))
def gather_sum(rows_buf, pos, held_pair, top_k, interpret=False):
    """`moe._sum_pairs` as kernels: rows_buf (R, D) bfloat16 in sorted
    order, pos and held_pair (T k,) by pair -> (T, D) fp32, each token's
    sum over j of its held pairs' rows."""
    r, d = rows_buf.shape
    if rows_buf.dtype != jnp.bfloat16:
        raise ValueError(f"rows of {rows_buf.dtype}: the kernels take "
                         "bfloat16 rows, the gathers the rest")
    if r > _ROW_MASK + 1:
        raise ValueError(f"{r} sorted rows are more than the plan's "
                         f"{_ROW_BITS} bits name")
    tokens = held_pair.size // top_k
    bt = token_block(tokens, top_k)
    padded = pl.cdiv(tokens, bt) * bt
    flags = jnp.pad(held_pair.reshape(tokens, top_k).astype(_I32),
                    ((0, padded - tokens), (0, 0)))
    live = jnp.max(jnp.where(held_pair, pos, -1)) + 1
    src = _pack(rows_buf, live.reshape(1).astype(_I32), interpret)
    out = _gather(_plan(pos, held_pair, top_k, bt), flags,
                  _hbm(src, interpret), top_k, d, interpret)
    return out if padded == tokens else out[:tokens]


def _vmem_need(tokens: int, top_k: int, d: int) -> int:
    """Bytes the gather holds in VMEM: two landing buffers, the result's
    and the flags' blocks double-buffered, the sums of a plane."""
    bt = token_block(tokens, top_k)
    planes = _planes(d)
    landing = 2 * planes * top_k * bt * _LANES * 4
    blocks = 2 * bt * d * 4 + 2 * bt * _LANES * 4
    return landing + blocks + 4 * planes * bt * _LANES * 4


def _plan_bytes(tokens: int, top_k: int) -> int:
    """Bytes of the prefetched plan (`_plan`), in the scalar memory for the
    whole call: an entry a pair and one a block, and one more."""
    bt = token_block(tokens, top_k)
    blocks = pl.cdiv(tokens, bt)
    return 4 * (blocks * bt * top_k + blocks + 1)


def shapes_fit(tokens: int, top_k: int, d: int, dtype) -> bool:
    """Tile alignment and the chip's two budgets alone: D whole lane
    tiles, rows in bfloat16, the landing buffers in the attached chip's
    VMEM and the plan, a number a pair, in its scalar memory (of the v5e's
    1 MiB, three quarters: 24,576 tokens at top-10 do not fit, 16,384
    do)."""
    vmem, smem = _budgets()
    return (d % _LANES == 0 and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and _vmem_need(tokens, top_k, d) <= vmem
            and _plan_bytes(tokens, top_k) <= smem)


def combine_ok(model, tokens: int, top_k: int, d: int, dtype) -> bool:
    """Whether `combine` sums the T = `tokens` tokens' k rows of width D in
    `dtype` with the kernels: the backend is a TPU, the mesh is one device
    (a direct Pallas call cannot run under GSPMD:
    `lstm_kernel.resident_scan_ok` states the rule) and the shapes fit the
    attached chip. Everywhere else the XLA gathers."""
    if jax.default_backend() != "tpu":
        return False
    mesh = getattr(model, "mesh", None)
    if mesh is not None and mesh.size > 1:
        return False
    return shapes_fit(tokens, top_k, d, dtype)
