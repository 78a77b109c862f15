"""The gated delta rule's hand-over of state from chunk to chunk, one
kernel a span with the state resident in VMEM.

`ops/delta_net.py:_delta_rule_span` prepares a span's chunks all at once
(the per-chunk solve, the decayed keys and queries); what is left is
sequential: a (dk, dv) state a head walks the span's chunks,

    v_new = value - k_cumdecay S
    o     = q_dec S + qk v_new
    S     = last S + k_dec^T v_new

with q_dec = q exp(gc), k_dec = k exp(gc_last - gc), qk = (q k^T) * decay
and last = exp(gc_last) from the chunk's cumulative log decay gc. As a
`lax.scan` each chunk is a dozen device operations of 3 to 25
microseconds, and the decayed copies of q and k and the masked scores
are written to HBM, read back and differentiated there (PERF.md,
PR 36). Here the chunks ride the grid (TPU grid steps run in order), the
state lives in VMEM scratch from the span's first chunk to its last, and
a grid step loads one chunk's q, k, `k_cumdecay`, `value` and gc for
several heads and forms the decayed tiles where they are. The backward
is the same walk in reverse with the state's cotangent resident: from
`do`, the chunk's tiles and the state that ENTERED the chunk it forms the
transposes of the same products and chains them to q, k and gc. The
state and every accumulation are fp32; the products' operands are cast
to the compute dtype as `_delta_rule_span`'s `mm` casts them, and a
gradient leaves in its operand's dtype, as autodiff's through `astype`
does. The entering states are written out only when a gradient will be
asked for, and in fp32: the backward casts them for its products as the
forward did, and the decay's gradient (the sum of S dS, no product's
operand) reads them as they were.

`_span_frame` is the frame (chunks on the grid forwards or backwards,
per-span states at a constant block index, one scratch state); the delta
rule is one pair of bodies in it, and a scalar-decay recurrence
(`ops/mamba.py`, ROADMAP S12 (c)) would be another.
`ops/pallas/lstm_kernel.py` is the precedent.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# heads a grid step: a step's fixed cost (~0.45 us) is paid once for all
# of them and, unrolled, their products interleave (a layer's three
# kernels alone on the v5e at 2 / 4 / 8 / 16 heads: 8.8 / 7.6 / 6.8 /
# 6.8 ms, 9.1 with a loop over 8; PERF.md, PR 36); a shape whose
# backward blocks, double-buffered, pass the budget at that many heads
# (Mosaic's scoped VMEM is 16 MiB there) stays with the `lax.scan`
_HEADS = 8
_VMEM_BUDGET = 10 << 20
_F32 = jnp.float32


def _mm(a, b, contract):
    return lax.dot_general(a, b, ((contract, ((), ()))),
                           preferred_element_type=_F32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _masks(c):
    """(the diagonal, on and under it) (C, C) and (the last column)
    (1, C): the same for every head of a grid step."""
    ii = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    jj = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return (ii == jj, ii >= jj,
            lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1)


def _chunk_tiles(q, k, gc, masks):
    """What `_delta_rule_span` forms as arrays on the `lax.scan` route,
    from the tiles a grid step has loaded: q, k (C, dk) in the compute dtype, gc
    (1, C) the chunk's cumulative log decay. fp32: `q_dec` = q exp(gc),
    `k_dec` = k exp(gc_last - gc), `qk` = (q k^T) * decay with decay_ij =
    exp(gc_i - gc_j) on and under the diagonal (the exponent masked too:
    above it the difference is positive and may overflow), `last` =
    exp(gc_last) (1, 1); and the factors the backward chains through."""
    eye, tri, is_last = masks
    # (1, C) -> (C, 1) without a transpose: the diagonal's row sums
    gcol = jnp.sum(jnp.where(eye, gc, 0.0), axis=1, keepdims=True)
    g_last = jnp.sum(jnp.where(is_last, gc, 0.0), axis=1, keepdims=True)
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, gcol - gc, 0.0)), 0.0)
    e_q, e_k = jnp.exp(gcol), jnp.exp(g_last - gcol)            # (C, 1)
    return dict(q_dec=q.astype(_F32) * e_q, k_dec=k.astype(_F32) * e_k,
                qk=_mm(q, k, _NT) * decay, last=jnp.exp(g_last),
                decay=decay, e_q=e_q, e_k=e_k)


def _fwd_kernel(heads, keep_states, q_ref, k_ref, kcd_ref, val_ref, gc_ref,
                s0_ref, *rest):
    o_ref, rest = rest[0], rest[1:]
    sin_ref = rest[0] if keep_states else None
    sfin_ref, s_scr = rest[-2:]
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        s_scr[...] = s0_ref[...]

    cdt, masks = q_ref.dtype, _masks(q_ref.shape[1])
    for h in range(heads):      # unrolled: their products interleave
        S = s_scr[h]
        Sc = S.astype(cdt)
        if keep_states:
            sin_ref[h] = S
        t = _chunk_tiles(q_ref[h], k_ref[h], gc_ref[h], masks)
        v_new = val_ref[h] - _mm(kcd_ref[h], Sc, _NN)
        vc = v_new.astype(cdt)
        o_ref[h] = (_mm(t["q_dec"].astype(cdt), Sc, _NN)
                    + _mm(t["qk"].astype(cdt), vc, _NN))
        s_scr[h] = S * t["last"] + _mm(t["k_dec"].astype(cdt), vc, _TN)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        sfin_ref[...] = s_scr[...]


def _bwd_kernel(heads, q_ref, k_ref, kcd_ref, val_ref, gc_ref, sin_ref,
                do_ref, dsfin_ref, dq_ref, dk_ref, dkcd_ref, dval_ref,
                dgc_ref, ds0_ref, ds_scr):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        ds_scr[...] = dsfin_ref[...]

    cdt, masks = q_ref.dtype, _masks(q_ref.shape[1])
    eye, _, is_last = masks
    for h in range(heads):
        q, k, kcd = q_ref[h], k_ref[h], kcd_ref[h]
        t = _chunk_tiles(q, k, gc_ref[h], masks)
        S, dS, doc = sin_ref[h], ds_scr[h], do_ref[h].astype(cdt)
        Sc, dSc = S.astype(cdt), dS.astype(cdt)
        vc = (val_ref[h] - _mm(kcd, Sc, _NN)).astype(cdt)
        # the transposes of the forward's four products
        dvn = (_mm(t["qk"].astype(cdt), doc, _TN)
               + _mm(t["k_dec"].astype(cdt), dSc, _NN))
        dvc = dvn.astype(cdt)
        dqk = _mm(doc, vc, _NT)                                 # (C, C)
        dq_dec = _mm(doc, Sc, _NT)                              # (C, dk)
        dk_dec = _mm(vc, dSc, _NT)
        dlast = jnp.sum(jnp.sum(S * dS, axis=0, keepdims=True),
                        axis=1, keepdims=True)                  # (1, 1)
        dval_ref[h] = dvn
        dkcd_ref[h] = (-_mm(dvc, Sc, _NT)).astype(dkcd_ref.dtype)
        ds_scr[h] = (dS * t["last"]
                     + _mm(t["q_dec"].astype(cdt), doc, _TN)
                     - _mm(kcd, dvc, _TN))
        # and through the chunk's tiles to q, k and the log decay
        draw = (dqk * t["decay"]).astype(cdt)                   # d(q k^T)
        dq_ref[h] = (dq_dec * t["e_q"] + _mm(draw, k, _NN)
                     ).astype(dq_ref.dtype)
        dk_ref[h] = (dk_dec * t["e_k"] + _mm(draw, q, _TN)
                     ).astype(dk_ref.dtype)
        p = dqk * t["qk"]                   # d decay_ij * decay_ij
        from_k = jnp.sum(dk_dec * t["k_dec"], axis=1, keepdims=True)
        col = (jnp.sum(p, axis=1, keepdims=True) - from_k
               + jnp.sum(dq_dec * t["q_dec"], axis=1, keepdims=True))
        d_last = (jnp.sum(from_k, axis=0, keepdims=True)
                  + dlast * t["last"])                          # (1, 1)
        dgc_ref[h] = (jnp.sum(jnp.where(eye, col, 0.0), axis=0,
                              keepdims=True)
                      - jnp.sum(p, axis=0, keepdims=True)
                      + jnp.where(is_last, d_last, 0.0))

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        ds0_ref[...] = ds_scr[...]


def _span_frame(chunk_ins, state_ins, chunk_outs, state_outs, heads,
                reverse):
    """The frame, as the keywords of a `pallas_call`. `chunk_ins` are
    arrays (BH, n, rows, cols), one block a (heads, chunk) grid step, the
    chunks walked in order or, with `reverse`, from the last; `state_ins`
    are (BH, dk, dv), one block a group of heads, fetched once;
    `chunk_outs` / `state_outs` are `ShapeDtypeStruct`s laid out the same
    way, a state written back when its group's last grid step is done.
    The kernel, called with (*chunk_ins, *state_ins), is `kernel(*in_refs,
    *out_refs, scratch)`: the chunk axis squeezed, one fp32 scratch
    state."""
    bh, n = chunk_ins[0].shape[:2]
    at = (lambda i: n - 1 - i) if reverse else (lambda i: i)

    def chunk_spec(a):
        return pl.BlockSpec((heads, None) + tuple(a.shape[2:]),
                            lambda g, i: (g, at(i), 0, 0))

    def state_spec(a):
        return pl.BlockSpec((heads,) + tuple(a.shape[1:]),
                            lambda g, i: (g, 0, 0))

    return dict(
        grid=(bh // heads, n),
        in_specs=([chunk_spec(a) for a in chunk_ins]
                  + [state_spec(a) for a in state_ins]),
        out_specs=([chunk_spec(a) for a in chunk_outs]
                   + [state_spec(a) for a in state_outs]),
        out_shape=list(chunk_outs) + list(state_outs),
        scratch_shapes=[pltpu.VMEM((heads,) + tuple(
            state_outs[0].shape[1:]), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=16 << 20))


def _vmem_need(chunk: int, dk: int, dv: int, itemsize: int) -> int:
    """Bytes the BACKWARD kernel (the larger of the two) holds in VMEM
    with `_HEADS` heads a grid step: every block double-buffered."""
    tiles = 3 * chunk * dk * itemsize       # q, k, k_cumdecay
    chunk_blocks = (2 * tiles               # operands in, gradients out
                    + 3 * chunk * dv * 4    # value, do in; dvalue out
                    + 2 * 8 * 128 * 4       # gc in, dgc out: a tile each
                    + dk * dv * 4)          # the state that entered
    states = 2 * dk * dv * 4                # dS in, dS0 out
    return _HEADS * (2 * (chunk_blocks + states) + dk * dv * 4)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _run_fwd(q, k, k_cumdecay, value, gc, S0, interpret, keep_states):
    bh, n, chunk, dk = q.shape
    dv, heads = value.shape[-1], math.gcd(bh, _HEADS)
    outs = [_sds((bh, n, chunk, dv), _F32)]
    if keep_states:
        outs.append(_sds((bh, n, dk, dv), _F32))
    ins = [q, k, k_cumdecay, value, gc]
    with jax.named_scope("delta_hand_over_fwd"):
        res = pl.pallas_call(
            functools.partial(_fwd_kernel, heads, keep_states),
            name="delta_hand_over_fwd", interpret=interpret,
            **_span_frame(ins, [S0], outs, [_sds(S0.shape, _F32)], heads,
                          reverse=False))(*ins, S0)
    return res if keep_states else (res[0], None, res[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def hand_over(q, k, k_cumdecay, value, gc, S0, interpret=False):
    """A span's chunks from state `S0` (BH, dk, dv) fp32: (o (BH, n, C,
    dv) fp32, the state after the span). `q`, `k`, `k_cumdecay` (BH, n,
    C, dk) in the compute dtype (q, k as the layer made them: the kernel
    applies the decay); `value` (BH, n, C, dv) fp32; `gc` (BH, n, 1, C)
    fp32, the log decay summed from each chunk's start."""
    o, _, S = _run_fwd(q, k, k_cumdecay, value, gc, S0, interpret,
                       keep_states=False)
    return o, S


def _vjp_fwd(q, k, k_cumdecay, value, gc, S0, interpret):
    o, entered, S = _run_fwd(q, k, k_cumdecay, value, gc, S0, interpret,
                             keep_states=True)
    return (o, S), (q, k, k_cumdecay, value, gc, entered)


def _vjp_bwd(interpret, res, cts):
    q, k, k_cumdecay, value, gc, entered = res
    do, dS = cts
    heads = math.gcd(q.shape[0], _HEADS)
    ins = [q, k, k_cumdecay, value, gc, entered, do.astype(_F32)]
    grads = [_sds(a.shape, a.dtype) for a in ins[:5]]
    dS = dS.astype(_F32)
    with jax.named_scope("delta_hand_over_bwd"):
        return tuple(pl.pallas_call(
            functools.partial(_bwd_kernel, heads),
            name="delta_hand_over_bwd", interpret=interpret,
            **_span_frame(ins, [dS], grads, [_sds(dS.shape, _F32)], heads,
                          reverse=True))(*ins, dS))


hand_over.defvjp(_vjp_fwd, _vjp_bwd)


def shapes_fit(chunk: int, dk: int, dv: int, itemsize: int = 2) -> bool:
    """Tile alignment and the VMEM budget alone: what the routing rule
    and the cost model's candidate predicate share."""
    return (dk % 128 == 0 and dv % 128 == 0
            and chunk % (32 // itemsize) == 0       # whole sublane tiles
            and _vmem_need(chunk, dk, dv, itemsize) <= _VMEM_BUDGET)


def resident_hand_over_ok(model, chunk: int, dk: int, dv: int) -> bool:
    """Whether the kernel carries the hand-over: the backend is a TPU,
    the mesh is one device (a direct Pallas call cannot run under GSPMD:
    `lstm_kernel.resident_scan_ok` states the rule) and the tiles are
    aligned. Everywhere else the `lax.scan`."""
    if jax.default_backend() != "tpu":
        return False
    mesh = getattr(model, "mesh", None)
    if mesh is not None and mesh.size > 1:
        return False
    itemsize = jnp.dtype(model.compute_dtype).itemsize
    return shapes_fit(chunk, dk, dv, itemsize)
