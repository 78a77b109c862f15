"""The expert walk as the grid of a Pallas kernel: one call a pass over the
sorted pairs, a grid step a trip.

`ops/moe.py` sorts a step's (token, expert) pairs by held expert and walks
the stretches a trip of `rows` rows at a time. As an XLA `while` a trip is
a dozen device operations (a search, three slices of an expert's slab and
their casts, a row gather, three products, an update of the big buffer, in
the backward three read-modify-writes of 4 MB), each a launch of its own:
~150 us a trip a pass at Qwen3-Next's widths whatever its rows, where the
trip's products are 16 us at the MXU's peak (PERF.md, PR 26 and PR 37).
Here the trips ride the grid (TPU grid steps run in order):

- the plan (`trip_plan`: a trip's expert, its first sorted row, its valid
  rows; the number of trips) is a scalar-prefetch operand. The grid is the
  static worst case, `ceil(pairs / rows) + held` trips; a step past the
  step's own count does nothing and fetches nothing (its block indices are
  the last live trip's).
- a trip fetches its rows' tokens from the fp32 activations by index, one
  DMA a row, into one of two landing buffers: trip j starts trip j + 1's
  fetches before it waits for its own (`embedding_kernel._bag_kernel` is
  the precedent). Mosaic takes a one-sublane DMA target only in a buffer
  one lane tile wide, and a strided load only from one, so the activations
  are viewed as (T, D / 128, 128): a token is one slab of D / 128 sublanes
  (whole tiles where D is a multiple of 1,024), and the kernel reads the
  slabs' sublane s, a strided load, as columns 128 s .. 128 (s + 1).
- the expert's matrices arrive as blocks indexed by the trip's expert, so
  consecutive trips of one expert fetch them once; their cast to the
  compute dtype happens in VMEM, once an expert.
- a trip writes its rows into ITS block of the sorted-rows buffer: an
  expert's stretch starts at a multiple of `rows` there (`aligned`), which
  is what lets the buffer be a blocked output; `combine` gathers from it by
  position as it did from the walk's.
- the backward is the same grid: a trip recomputes its hidden rows, forms
  the rows' input gradients and pair-weight gradients, and adds its
  expert's weight gradients into output blocks that stay in VMEM across
  that expert's trips and are written once when the expert changes. Every
  held expert takes at least one trip there, so an expert with no pair
  leaves zeros.

Every dtype is the walk's: rows and matrices enter the products in the
compute dtype, products accumulate in fp32, the hidden rows are cast
before `down`, a row is scaled by its pair's weight in fp32 and written in
the compute dtype; in the backward the hidden rows' cotangent is rounded
to the compute dtype before the activation's derivative and each half of
the rows' input gradient before their sum, as autodiff of the walk's trip
rounds them. One difference, on the exact side: a trip's weight gradient
is added to its expert's in fp32 as it is, where the walk's autodiff
rounds each trip's to the compute dtype first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows a trip. Inside the kernel a trip costs a grid step and its row
# fetches, not a dozen launches, and its expert's matrices are not fetched
# again, so the rows follow the MXU and the padding, not the launch cost.
# The two kernels alone on the v5e at Qwen3-Next's widths, forward +
# backward in ms, 128 / 256 / 512 rows (`benchmarks/expert_walk.py`,
# PERF.md, PR 37): 5,146 held pairs 5.7 / 6.3 / 10.5; 24,679 (the cell's
# load) 8.9 / 8.9 / 11.0; 81,920 (a deployment's 2,560 an expert) 19.0 /
# 18.6 / 21.1. 512 loses everywhere (its landing buffers and temporaries
# crowd VMEM); 128 and 256 tie at the cell's load, 128 pads less under it
# and 256 makes half the grid steps above it, where the op does its real
# share. What a trip costs there: 13 us forward and 34 backward (the MXU's
# 8 and 25), and every change of expert 40-45 and 70-85 us more: its 12.6
# MB of matrices arrive at ~300 GB/s, one trip ahead at most.
ROWS = 256
_LANES = 128            # a token's slab is (D / 128, 128)
_SUBLANES = 8
# the share of the attached chip's VMEM (`TPUSpec.detect().vmem_bytes`) the
# backward kernel (the larger) may hold: 100 of the v5e's 128 MiB; Mosaic's
# default scope is 16
_VMEM_SHARE = 100 / 128
# and of its scalar memory the prefetched plan (`_plan_bytes`), which grows
# with the pairs: of the v5e's 1 MiB the compiler gave 1,000,996 bytes (T =
# 24,576 at top-10) and refused 1,333,796 (T = 32,768); the cell's are 335,396
_SMEM_SHARE = 3 / 4
_F32 = jnp.float32

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _mm(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=_F32)


def trip_plan(rows: int, counts, n_pairs: int, every_expert: bool = False):
    """The walk over the held experts' stretches of sorted pairs, `rows`
    rows a trip, as int32 arrays over the grid's `ceil(n_pairs / rows) +
    held` steps: `n` (1,) the step's own number of trips; a trip's `expert`,
    `src` (its first sorted row), `valid` (rows that carry a pair of its
    expert) and `first` (1 where its expert differs from the trip before).
    A step past `n` repeats the last live trip's entries. `pad` and `start`
    (held,): rows of padding behind each expert's stretch in the aligned
    buffer, and its first sorted row. Expert e takes ceil(counts[e] / rows)
    trips, as `moe._walk_plan` has it; with `every_expert`, one at the
    least."""
    held = counts.shape[0]
    per = (counts + rows - 1) // rows
    if every_expert:
        per = jnp.maximum(per, 1)
    last = jnp.cumsum(per)
    n = last[-1]
    start = jnp.cumsum(counts) - counts
    steps = -(-n_pairs // rows) + held
    j = jnp.minimum(jnp.arange(steps, dtype=jnp.int32),
                    jnp.maximum(n - 1, 0))
    # a trip's expert is the number of experts whose trips end at or before
    # it; what it reads of its expert's entries, a masked sum: compares of
    # steps x held, no search loop and no gather (the trips left XLA's)
    e = jnp.minimum(jnp.sum(last[None, :] <= j[:, None], axis=1,
                            dtype=jnp.int32), held - 1)
    mine = e[:, None] == jnp.arange(held)[None, :]

    def of(per_expert):
        return jnp.sum(jnp.where(mine, per_expert[None, :], 0), axis=1,
                       dtype=jnp.int32)

    t = j - of(last - per)
    src = of(start) + t * rows
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    return dict(
        n=i32(n).reshape(1), expert=e, src=src,
        valid=jnp.clip(of(counts) - t * rows, 0, rows),
        first=i32(jnp.concatenate([jnp.ones((1,), bool), e[1:] != e[:-1]])),
        pad=i32(per * rows - counts), start=i32(start))


def aligned(plan, pos):
    """Where sorted row `pos` lies in the aligned buffer: behind the padding
    of every expert before its own. A sum of `held - 1` compares, no
    gather."""
    behind = pos[:, None] >= plan["start"][None, 1:]
    return pos + jnp.sum(jnp.where(behind, plan["pad"][None, :-1], 0),
                         axis=1, dtype=jnp.int32)


# the plan's entries in the one prefetched array, in this order: `n` (one
# number), then `steps` numbers each, then each sorted row's token
_FIELDS = ("expert", "src", "first", "valid")


class _Plan:
    """The prefetched plan as the kernel and the index maps read it."""

    def __init__(self, ref, steps):
        self.ref, self.steps = ref, steps

    def n(self):
        return self.ref[0]

    def tok(self, row):
        return self.ref[1 + len(_FIELDS) * self.steps + row]

    def get(self, field, j):
        return self.ref[1 + _FIELDS.index(field) * self.steps + j]

    def block(self, j):
        """Trip j's block of the aligned buffer: its own index; a step past
        the step's count stays on the last live trip's."""
        return jnp.minimum(j, jnp.maximum(self.n() - 1, 0))

    def window(self, j, rows):
        """(the window of `rows` sorted pair weights trip j's first row lies
        in, where in it)."""
        src = self.get("src", j)
        return lax.div(src, jnp.int32(rows)), lax.rem(src, jnp.int32(rows))


def _packed(plan, tok):
    return jnp.concatenate([plan["n"]] + [plan[f] for f in _FIELDS] + [tok])


def _start_rows(plan, row0, hbm_refs, bufs, sems, slot):
    """Start the fetch of every row of a trip whose first sorted row is
    row0: the slab of token tok[row0 + r] of each of `hbm_refs` (T, D/128,
    128) into slab r of its buffer's `slot`. A trip fetches all its rows, the
    ones past its expert's last pair too (the next expert's tokens or the
    padding's token 0): one wait then stands for all of them."""
    slab = hbm_refs[0].shape[1]
    rows = bufs[0].shape[1] // slab

    def tile(t, carry):
        # eight rows a loop trip: Mosaic unrolls a fori_loop fully or not
        # at all
        for u in range(_SUBLANES):
            r = t * _SUBLANES + u
            tok = plan.tok(row0 + r)
            at = pl.multiple_of(r * slab, slab)
            for k, (src, buf) in enumerate(zip(hbm_refs, bufs)):
                pltpu.make_async_copy(
                    src.at[tok], buf.at[slot, pl.ds(at, slab), :],
                    sems.at[k, slot]).start()
        return carry

    lax.fori_loop(0, rows // _SUBLANES, tile, None)


def _wait_rows(bufs, sems, slot):
    """A wait takes its target's byte count off the semaphore: one for a
    whole landing buffer stands for the fetches that filled it."""
    for k, buf in enumerate(bufs):
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sems.at[k, slot]).wait()


def _rows_of(buf, slot, rows, dtype):
    """A landing buffer's `rows` rows (rows, D) in `dtype`: sublane s of
    every slab is the rows' columns 128 s .. 128 (s + 1)."""
    view = buf.at[slot]
    slab = view.shape[0] // rows
    return jnp.concatenate(
        [view[pl.ds(s, rows, stride=slab), :].astype(dtype)
         for s in range(slab)], axis=1)


def _window(pair_ref, at, rows):
    """(rows, 1): the `rows` lanes from lane `at` on of a (1, 2 rows) lane
    vector, as a column, without a rotate or a transpose: the row sums of
    the vector under a shifted diagonal."""
    pair = pair_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, (rows, 2 * rows), 1)
    row = lax.broadcasted_iota(jnp.int32, (rows, 2 * rows), 0)
    return jnp.sum(jnp.where(lane == row + at, pair, 0.0), axis=1,
                   keepdims=True)


def _row(col):
    """(n, 1) -> (1, n) without a transpose: the diagonal's column sums."""
    n = col.shape[0]
    eye = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _cast_matrices(plan, j, w_refs, wc):
    """An expert's matrices in the compute dtype, cast where they landed,
    once an expert."""
    @pl.when(plan.get("first", j) == 1)
    def _():
        for c, w in zip(wc, w_refs):
            c[...] = w[...].astype(c.dtype)


def _fetch_ahead(j, plan, hbm_refs, bufs, sems):
    """Trip j's part in keeping the fetch queue full: trip 0 starts its own
    rows, every trip the next one's, then waits for its own."""
    @pl.when((j == 0) & (plan.n() > 0))
    def _():
        _start_rows(plan, plan.get("src", 0), hbm_refs, bufs, sems, 0)

    @pl.when(j + 1 < plan.n())
    def _():
        _start_rows(plan, plan.get("src", j + 1), hbm_refs, bufs, sems,
                    (j + 1) % 2)


def _fwd_kernel(n_mats, plan_ref, pw_ref, x_ref, *rest):
    w_refs, out_ref = rest[:n_mats], rest[n_mats]
    xbuf, wc, sems = rest[n_mats + 1], rest[n_mats + 2:-1], rest[-1]
    j, plan = pl.program_id(0), _Plan(plan_ref, pl.num_programs(0))
    _fetch_ahead(j, plan, (x_ref,), (xbuf,), sems)

    @pl.when(j < plan.n())
    def _():
        slot, (rows, _), cdt = j % 2, out_ref.shape, out_ref.dtype
        _wait_rows((xbuf,), sems, slot)
        _cast_matrices(plan, j, w_refs, wc)
        xs = _rows_of(xbuf, slot, rows, cdt)
        if n_mats == 3:
            h = jax.nn.silu(_mm(xs, wc[0][...], _NN)) * _mm(xs, wc[1][...],
                                                            _NN)
        else:
            h = jnp.square(jax.nn.relu(_mm(xs, wc[0][...], _NN)))
        w_row = _window(pw_ref, plan.window(j, rows)[1], rows)
        out_ref[...] = (_mm(h.astype(cdt), wc[-1][...], _NN)
                        * w_row).astype(cdt)


def _bwd_kernel(n_mats, plan_ref, pw_ref, x_ref, ct_ref, *rest):
    w_refs, rest = rest[:n_mats], rest[n_mats:]
    dx_ref, dwrow_ref, dw_refs = rest[0], rest[1], rest[2:2 + n_mats]
    xbuf, dybuf = rest[2 + n_mats:4 + n_mats]
    wc, sems = rest[4 + n_mats:-1], rest[-1]
    j, plan = pl.program_id(0), _Plan(plan_ref, pl.num_programs(0))
    _fetch_ahead(j, plan, (x_ref, ct_ref), (xbuf, dybuf), sems)

    @pl.when(j < plan.n())
    def _():
        slot, (rows, _), cdt = j % 2, dx_ref.shape, dx_ref.dtype
        _wait_rows((xbuf, dybuf), sems, slot)
        _cast_matrices(plan, j, w_refs, wc)
        first = plan.get("first", j) == 1

        def add(ref, g):
            """An expert's gradient block stays in VMEM across its trips."""
            @pl.when(first)
            def _():
                ref[...] = g

            @pl.when(jnp.logical_not(first))
            def _():
                ref[...] += g

        xs = _rows_of(xbuf, slot, rows, cdt)
        live = (lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                < plan.get("valid", j))
        dy = jnp.where(live, _rows_of(dybuf, slot, rows, _F32), 0.0)
        # the trip again, as the forward ran it
        if n_mats == 3:
            g, u = _mm(xs, wc[0][...], _NN), _mm(xs, wc[1][...], _NN)
            m = jax.nn.sigmoid(g)
            k = g * m
            h = k * u
        else:
            u = _mm(xs, wc[0][...], _NN)
            r = jax.nn.relu(u)
            h = jnp.square(r)
        hc = h.astype(cdt)
        f = _mm(hc, wc[-1][...], _NN)
        dwrow_ref[...] = _row(jnp.sum(dy * f, axis=1, keepdims=True))
        # and the transposes of its products
        w_row = _window(pw_ref, plan.window(j, rows)[1], rows)
        df = (dy * w_row).astype(cdt)
        add(dw_refs[-1], _mm(hc, df, _TN))
        dh = _mm(df, wc[-1][...], _NT).astype(cdt).astype(_F32)
        if n_mats == 3:
            t = dh * u
            parts = (t * m + (g * t) * (m * (1.0 - m)), k * dh)
        else:
            parts = (jnp.where(u > 0, dh * (2.0 * r), 0.0),)
        dxs = None
        for p, c, dw_ref in zip(parts, wc, dw_refs):
            pc = p.astype(cdt)
            add(dw_ref, _mm(xs, pc, _TN))
            part = _mm(pc, c[...], _NT).astype(cdt)
            dxs = part if dxs is None else dxs + part
        dx_ref[...] = dxs


def _slabs(x, interpret):
    """(T, D) -> (T, D / 128, 128), held in HBM: a row DMA from a temporary
    that XLA parked in VMEM costs more (PERF.md, PR 29); the interpreter
    knows no memory spaces."""
    x = x.reshape(x.shape[0], x.shape[1] // _LANES, _LANES)
    return x if interpret else pltpu.with_memory_space_constraint(
        x, pltpu.HBM)


def _frame(rows, top_k, cdt, ws, pair_w, order, pos, counts, acts, backward,
           interpret):
    """One pass's `pallas_call`: (its keywords, its operands, the plan).
    Prefetched, as ONE array (`perfbench/tracereduce.py` files a call by
    the first 600 characters of its text, and every operand is some fifty
    of them): the plan and each sorted row's token. Operands: the pair
    weights in sorted order as (1, 2 rows) lane vectors, a window and its
    neighbour (a trip's rows start anywhere in the first), the activations
    `acts` as slabs in HBM, the experts' matrices a block an expert, each
    (held, a, b) seen as (held a, b): a bitcast of its tiled layout, whose
    name and type the profiler spells shorter than the parameter's (the
    backward call's text reached the cut with them as they are: PERF.md
    7.6). Results: the trip's (rows, D) block of the aligned buffer and,
    with `backward`, a (1, rows) lane vector a trip and a block an expert
    as the matrices', (held a, b) too; every held expert then has a
    trip."""
    assert rows <= order.size - pair_w.size, "a trip reads into the padding"
    with jax.named_scope("dispatch"):
        plan = trip_plan(rows, counts, pair_w.size, every_expert=backward)
        steps, d = plan["expert"].shape[0], acts[0].shape[1]
        packed = _packed(plan, lax.div(order, jnp.int32(top_k)))
        # the pair weights in sorted order: a sort by the sorted position
        # (a gather of 81,920 scalars costs seven times the sort)
        windows = pair_w.size // rows + 2
        w_sorted = jnp.pad(lax.sort((pos, pair_w), num_keys=1)[1],
                           (0, windows * rows - pair_w.size))
        w_sorted = w_sorted.reshape(windows, 1, rows)
        w_sorted = jnp.concatenate([w_sorted[:-1], w_sorted[1:]], axis=-1)
        slabs = [_slabs(a, interpret) for a in acts]

    def at(index, zeros):
        """A block index: what `index` reads of the plan for the trip, then
        zeros."""
        return lambda j, pre: (index(_Plan(pre, steps), j),) + (0,) * zeros

    def expert(plan, j):
        return plan.get("expert", j)

    mats = [pl.BlockSpec(w.shape[1:], at(expert, 1)) for w in ws]
    flat = [w.reshape(-1, w.shape[-1]) for w in ws]
    out_specs = [pl.BlockSpec((rows, d), at(_Plan.block, 1))]
    out_shape = [jax.ShapeDtypeStruct((steps * rows, d), cdt)]
    if backward:
        out_specs += [pl.BlockSpec((None, 1, rows), at(_Plan.block, 2))] + mats
        out_shape += [jax.ShapeDtypeStruct((steps, 1, rows), _F32)] + [
            jax.ShapeDtypeStruct(w.shape, _F32) for w in flat]
    else:
        # one result, not a tuple of one: what reads a tuple's element reads
        # `%pallas_call.N`, and `tracereduce` files it under `mosaic` too
        (out_specs,), (out_shape,) = out_specs, out_shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(steps,),
        in_specs=[pl.BlockSpec((None, 1, 2 * rows),
                               at(lambda plan, j: plan.window(j, rows)[0], 2))]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(acts) + mats,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((2, rows * d // _LANES, _LANES), a.dtype)
                        for a in acts]
        + [pltpu.VMEM(w.shape[1:], cdt) for w in ws]
        + [pltpu.SemaphoreType.DMA((len(acts), 2))])
    frame = dict(
        out_shape=out_shape, grid_spec=grid_spec, interpret=interpret,
        # a trip waits for fetches the trip before it started
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_budgets()[0], disable_bounds_checks=True))
    return frame, (packed, w_sorted, *slabs, *flat), plan


# (jitted: a model's expert layers of one shape then share one trace and
# one lowering of each kernel, 1.5 s of a step's 6.5 s of lowering with
# four layers)
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 9))
def experts_fwd(rows, top_k, cdt, xt, ws, pair_w, order, pos, counts,
                interpret=False):
    """The held experts on every sorted pair: (the aligned buffer (steps *
    rows, D) in `cdt`, the plan). xt (T, D) fp32; ws the experts' matrices,
    (gate, up, down) or (up, down), each (held, ...) fp32; pos (T k,) each
    pair's sorted position (`moe._sorted_position`); the rest as
    `moe._routed` takes them."""
    frame, operands, plan = _frame(rows, top_k, cdt, ws, pair_w, order, pos,
                                   counts, (xt,), False, interpret)
    with jax.named_scope("experts"):
        with jax.named_scope("moe_experts_fwd"):
            buf = pl.pallas_call(
                functools.partial(_fwd_kernel, len(ws)),
                name="moe_experts_fwd", **frame)(*operands)
    return buf, plan


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 10))
def experts_bwd(rows, top_k, cdt, xt, ws, pair_w, order, pos, counts, ct,
                interpret=False):
    """The same trips in the backward, from the result's cotangent ct (T,
    D): (the rows' input gradients in the aligned buffer's layout (steps *
    rows, D) `cdt`, their pair-weight gradients (steps * rows,) fp32, the
    matrices' gradients as `ws` fp32, the plan: every held expert has a
    trip in it)."""
    frame, operands, plan = _frame(
        rows, top_k, cdt, ws, pair_w, order, pos, counts,
        (xt, ct.astype(_F32)), True, interpret)
    with jax.named_scope("experts"):
        with jax.named_scope("moe_experts_bwd"):
            dx, dw_row, *dws = pl.pallas_call(
                functools.partial(_bwd_kernel, len(ws)),
                name="moe_experts_bwd", **frame)(*operands)
    return (dx, dw_row.reshape(-1),
            tuple(g.reshape(w.shape) for g, w in zip(dws, ws)), plan)


def _budgets():
    """(bytes of VMEM the kernels may ask Mosaic for, bytes of scalar
    memory their plan may take) on the attached chip; off the TPU the v5e's
    stand in, as `lstm_kernel.scan_shape_fits` has it."""
    from ...search.cost_model import TPUSpec
    spec = TPUSpec.detect()
    return int(_VMEM_SHARE * spec.vmem_bytes), int(_SMEM_SHARE
                                                   * spec.smem_bytes)


def _vmem_need(rows: int, d: int, f: int, n_mats: int, itemsize: int) -> int:
    """Bytes the BACKWARD kernel (the larger of the two) holds in VMEM:
    the expert's fp32 matrices in and its gradient blocks out, each
    double-buffered; the matrices in the compute dtype; two landing
    buffers for the rows and two for their cotangents; the trip's own
    (rows, D) and (D, F) temporaries."""
    mats = n_mats * d * f
    landing = 2 * 2 * rows * d * 4
    blocks = 2 * rows * d * itemsize + 4 * rows * 4
    temps = 4 * rows * d * 4 + 2 * d * f * 4 + 6 * rows * f * 4
    return 2 * 2 * mats * 4 + mats * itemsize + landing + blocks + temps


def _plan_bytes(rows: int, entries: int, held: int) -> int:
    """Bytes of the one prefetched array (`_packed`), which lives in the
    scalar memory for the whole call: the number of trips, the plan's
    fields over the grid's steps, and the token of each of `order`'s
    `entries` (the pairs and the walk's padding; counting the padding's
    trips too errs by two steps, on the safe side)."""
    steps = -(-entries // rows) + held
    return 4 * (1 + len(_FIELDS) * steps + entries)


def shapes_fit(d: int, f: int, n_mats: int, itemsize: int = 2,
               rows: int = ROWS, entries: int = 0, held: int = 0) -> bool:
    """Tile alignment and the chip's two budgets alone: a token's slab is
    whole tiles (D a multiple of 8 x 128), F a multiple of 128, a whole
    expert fits VMEM beside its gradient (the kernel does not tile F: a
    tile's partial results would have to leave the chip in fp32), and the
    plan of `order`'s `entries` sorted pairs over `held` experts fits the
    scalar memory."""
    vmem, smem = _budgets()
    return (d % (_SUBLANES * _LANES) == 0 and f % _LANES == 0
            and _vmem_need(rows, d, f, n_mats, itemsize) <= vmem
            and _plan_bytes(rows, entries, held) <= smem)


def grid_walk_ok(model, xt, ws, order) -> bool:
    """Whether the kernel walks the sorted pairs `order` of tokens xt (T, D)
    through the held experts of matrices ws (each (held, ...), the first
    (held, D, F)): the backend is a TPU, the mesh is one device (a direct
    Pallas call cannot run under GSPMD: `lstm_kernel.resident_scan_ok`
    states the rule), the tokens are fp32 (a slab is then whole tiles) and
    the shapes fit the attached chip. Everywhere else the XLA `while`."""
    if jax.default_backend() != "tpu":
        return False
    mesh = getattr(model, "mesh", None)
    if mesh is not None and mesh.size > 1:
        return False
    held, d, f = ws[0].shape
    return xt.dtype == _F32 and shapes_fit(
        d, f, len(ws), jnp.dtype(model.compute_dtype).itemsize,
        entries=order.size, held=held)
