"""Pallas TPU fused gather→dot-interaction→top-MLP kernel.

DLRM's "dot" interaction (models/dlrm.py interact_features, reference
dlrm.cc:49-65) lowers as four HLO ops — gather, batched X·Xᵀ, a
strictly-lower-triangle index_select, and the first top-MLP matmul — and
the (B, F, F) pairwise-dot tensor between them round-trips HBM twice even
though only F(F-1)/2 of its F² entries are ever read. This kernel fuses
the whole chain per batch tile so Z = X·Xᵀ lives (F_pad, F_pad) in VMEM
and is consumed by the first top-MLP layer before the next tile starts:
the [B, F, F] buffer never exists in HBM (analysis/hlo_audit.py FLX515
pins that on the lowered HLO).

Structure per grid step (_TILE_B samples):

- gather: the embedding table stays in HBM; the T rows a sample needs
  stream into VMEM with the same deep async-DMA pipeline as
  embedding_kernel._bag_kernel (indices via scalar prefetch, (1, 128)
  chunk DMAs against a (rows*k, 128) view, bag-summed on arrival) and
  land in an (F_pad, d) X buffer under the sample's bottom-MLP row.
- interaction: Z = X·Xᵀ on the MXU, fp32 accumulate, (F_pad, F_pad) in
  registers/VMEM only.
- top-MLP first layer folded in WITHOUT materializing the tril vector:
  y = bottom·W_bot + Σ_f Z[f]·M_f + bias, where M is the tril half of
  the layer weight scattered to (F_pad·F_pad, H) row positions (i·F_pad+j
  for the strictly-lower pairs, zero elsewhere) — a host-side transform
  of the dense weight (`scatter_tril_weight`), so the tril select becomes
  part of the matmul instead of a gather.

`fused_interaction` carries a custom_vjp whose backward is plain XLA
(the backward pass re-materializes g_Z — fusing it is out of scope; the
FLX515 audit targets the forward/serving lowering). On non-TPU backends
pass interpret=True (tests do) or use `fused_interaction_reference`,
the unfused jnp oracle.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# samples per grid step: one float32 sublane tile
_TILE_B = 8
_LANES = 128
# outstanding row DMAs (same latency-bound reasoning as embedding_kernel)
_SLOTS = 8
# fp32 sublane granule: X/Z pad F (= T+1 feature rows) up to this
_SUBLANES = 8


def supports(dim: int) -> bool:
    """True if the fused kernel handles this feature width (the gather
    streams (1, 128) lane tiles, like the embedding-bag kernel)."""
    return dim % _LANES == 0


def _pad_features(F: int) -> int:
    return ((F + _SUBLANES - 1) // _SUBLANES) * _SUBLANES


def tril_pairs(F: int):
    """The strictly-lower-triangle (i, j) pairs in DLRM's interaction
    order (models/dlrm.py: ``for i in range(F) for j in range(i)``)."""
    return [(i, j) for i in range(F) for j in range(i)]


def scatter_tril_weight(w_tril: jax.Array, F: int) -> jax.Array:
    """(P, H) tril half of the first top-MLP weight -> (F_pad², H) matrix
    M with row i·F_pad+j = w_tril[p(i,j)] for strictly-lower pairs and
    zero elsewhere, so tril-select + matmul becomes vec(Z)·M."""
    P, H = w_tril.shape
    pairs = tril_pairs(F)
    if P != len(pairs):
        raise ValueError(f"tril weight has {P} rows, F={F} needs "
                         f"{len(pairs)}")
    Fp = _pad_features(F)
    rows = np.array([i * Fp + j for i, j in pairs], dtype=np.int32)
    return jnp.zeros((Fp * Fp, H), w_tril.dtype).at[rows].set(w_tril)


def _interaction_kernel(T: int, bag: int, k: int, F: int, relu: bool,
                        idx_ref, table_ref, bottom_ref, wbot_ref, m_ref,
                        bias_ref, out_ref, xbuf, row_buf, sems):
    """One grid step = _TILE_B samples through gather→Z=X·Xᵀ→first layer.

    table_ref is the (rows*k, 128) chunk view resident in HBM; xbuf is
    the (F_pad, d) per-sample feature stack (row 0 = bottom-MLP output,
    rows 1..T = bag-summed embedding rows, rows F.. = zero padding);
    row_buf/sems run the deep DMA pipeline, crossing sample boundaries
    freely — fetched chunks land in slots, the accumulate into xbuf
    happens at wait time, before the slot is reused.
    """
    tb = out_ref.shape[0]
    Fp = xbuf.shape[0]
    d = xbuf.shape[1]
    total = tb * T * k * bag
    base = pl.program_id(0) * tb * T * bag

    def dma(j, slot):
        # j enumerates (sample, table, chunk, bag) as (((s*T+t)*k+c)*bag+b)
        stc, b = j // bag, j % bag
        st, c = stc // k, stc % k
        view_row = idx_ref[base + st * bag + b] * k + c
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(view_row, 1), :], row_buf.at[slot],
            sems.at[slot])

    depth = min(_SLOTS - 1, total)
    for j in range(depth):
        dma(j, j % _SLOTS).start()
    for s in range(tb):                # static unroll: all bounds small
        xbuf[pl.ds(0, 1), :] = bottom_ref[pl.ds(s, 1), :]
        for t in range(T):
            for c in range(k):
                acc = jnp.zeros((1, _LANES), jnp.float32)
                for b in range(bag):
                    j = ((s * T + t) * k + c) * bag + b
                    if j + depth < total:
                        dma(j + depth, (j + depth) % _SLOTS).start()
                    dma(j, j % _SLOTS).wait()
                    acc = acc + row_buf[j % _SLOTS].astype(jnp.float32)
                xbuf[pl.ds(1 + t, 1), c * _LANES:(c + 1) * _LANES] = acc
        if Fp > F:
            xbuf[pl.ds(F, Fp - F), :] = jnp.zeros((Fp - F, d), jnp.float32)
        # Z = X·Xᵀ, (F_pad, F_pad) — in VMEM only, never written out
        x = xbuf[:]
        z = lax.dot_general(x, x, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        y = jnp.dot(bottom_ref[pl.ds(s, 1), :], wbot_ref[:],
                    preferred_element_type=jnp.float32)
        # f = 0 (the bottom row) has no strictly-lower pairs; its M rows
        # are zero — skip it statically
        for f in range(1, F):
            y = y + jnp.dot(z[f:f + 1, :],
                            m_ref[f * Fp:(f + 1) * Fp, :],
                            preferred_element_type=jnp.float32)
        y = y + bias_ref[:]
        if relu:
            y = jnp.maximum(y, 0.0)
        out_ref[pl.ds(s, 1), :] = y


def _prep_inputs(indices, bottom, w, d: int, F: int):
    """Shared wrapper plumbing: flatten/pad indices and bottom to a
    whole number of _TILE_B tiles, split the first-layer weight into its
    bottom half and tril-scatter matrix."""
    batch = bottom.shape[0]
    idx = indices.astype(jnp.int32)
    if idx.ndim == 2:
        idx = idx[:, :, None]
    T, bag = idx.shape[1], idx.shape[2]
    if T + 1 != F:
        raise ValueError(f"indices carry {T} tables but F={F}")
    P = len(tril_pairs(F))
    if w.shape[0] != d + P:
        raise ValueError(f"first-layer weight expects {d + P} input "
                         f"features (d={d} + {P} pairs), got {w.shape[0]}")
    padded = ((batch + _TILE_B - 1) // _TILE_B) * _TILE_B
    idx_flat = jnp.zeros((padded * T * bag,), jnp.int32)
    idx_flat = idx_flat.at[: batch * T * bag].set(idx.reshape(-1))
    bot = jnp.zeros((padded, d), jnp.float32)
    bot = bot.at[:batch].set(bottom.astype(jnp.float32))
    w_bot = w[:d].astype(jnp.float32)
    m = scatter_tril_weight(w[d:].astype(jnp.float32), F)
    return idx_flat, bot, w_bot, m, padded, T, bag


def _pallas_fused(table, indices, bottom, w, bias, relu, interpret):
    batch = bottom.shape[0]
    rows, d = table.shape
    if not supports(d):
        raise ValueError(f"pallas fused_interaction needs dim % {_LANES} "
                         f"== 0, got {d}; use fused_interaction_reference")
    F = indices.shape[1] + 1
    Fp = _pad_features(F)
    k = d // _LANES
    H = w.shape[1]
    idx_flat, bot, w_bot, m, padded, T, bag = _prep_inputs(
        indices, bottom, w, d, F)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(padded // _TILE_B,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),             # table (HBM)
            pl.BlockSpec((_TILE_B, d), lambda i, idx: (i, 0)),
            pl.BlockSpec((d, H), lambda i, idx: (0, 0)),   # w_bot
            pl.BlockSpec((Fp * Fp, H), lambda i, idx: (0, 0)),  # M
            pl.BlockSpec((1, H), lambda i, idx: (0, 0)),   # bias
        ],
        out_specs=pl.BlockSpec((_TILE_B, H), lambda i, idx: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((Fp, d), jnp.float32),
            pltpu.VMEM((_SLOTS, 1, _LANES), table.dtype),
            pltpu.SemaphoreType.DMA((_SLOTS,)),
        ],
    )
    with jax.named_scope("interaction_fused"):
        out = pl.pallas_call(
            functools.partial(_interaction_kernel, T, bag, k, F, relu),
            out_shape=jax.ShapeDtypeStruct((padded, H), jnp.float32),
            grid_spec=grid_spec,
            interpret=interpret,
            name="interaction_fused",
        )(idx_flat, table.reshape(rows * k, _LANES), bot, w_bot, m,
          bias.astype(jnp.float32).reshape(1, H))
    return out[:batch]


def fused_interaction_reference(table, indices, bottom, w, bias,
                                relu: bool = True):
    """Unfused jnp oracle/fallback: gather → stack → X·Xᵀ → tril →
    concat → first top-MLP layer, fp32 throughout — the composition the
    kernel must match (and exactly what interact_features + the first
    create_mlp dense build as separate ops)."""
    idx = indices.astype(jnp.int32)
    if idx.ndim == 2:
        idx = idx[:, :, None]
    batch, T, _ = idx.shape
    F = T + 1
    emb = jnp.sum(jnp.take(table, idx, axis=0).astype(jnp.float32), axis=2)
    x = jnp.concatenate(
        [bottom.astype(jnp.float32)[:, None, :], emb], axis=1)  # (b, F, d)
    z = lax.dot_general(x, x, (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32)     # (b, F, F)
    sel = np.array([i * F + j for i, j in tril_pairs(F)], dtype=np.int32)
    zt = z.reshape(batch, F * F)[:, sel]
    cat = jnp.concatenate([bottom.astype(jnp.float32), zt], axis=1)
    y = (jnp.dot(cat, w.astype(jnp.float32),
                 preferred_element_type=jnp.float32)
         + bias.astype(jnp.float32))
    return jnp.maximum(y, 0.0) if relu else y


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def fused_interaction(table, indices, bottom, w, bias,
                      relu: bool = True, interpret: bool = False):
    """Fused gather→dot-interaction→first-top-MLP-layer.

    table   : (rows, d) float, d % 128 == 0 — T tables concatenated
              row-wise, indices pre-offset into the concatenated space
    indices : (batch, T) or (batch, T, bag) int
    bottom  : (batch, d) bottom-MLP output
    w       : (d + F(F-1)/2, H) first top-MLP weight (F = T+1)
    bias    : (H,)
    returns : (batch, H) fp32, optionally relu'd.
    """
    return _pallas_fused(table, indices, bottom, w, bias, relu, interpret)


def _fused_fwd(table, indices, bottom, w, bias, relu, interpret):
    out = _pallas_fused(table, indices, bottom, w, bias, relu, interpret)
    # zero-size spec carries the table's static shape/dtype for backward
    spec = jnp.zeros((table.shape[0], 0), table.dtype)
    idx = indices.astype(jnp.int32)
    if idx.ndim == 2:
        idx = idx[:, :, None]
    emb = jnp.sum(jnp.take(table, idx, axis=0).astype(jnp.float32), axis=2)
    return out, (spec, indices, idx, emb, bottom, w, out)


def _fused_bwd(relu, interpret, res, g):
    """Plain-XLA backward of the fused composition (the forward-only
    fusion is the perf claim; backward re-materializes g_Z)."""
    spec, indices, idx, emb, bottom, w, y = res
    batch, T, bag = idx.shape
    F = T + 1
    d = bottom.shape[1]
    x = jnp.concatenate(
        [bottom.astype(jnp.float32)[:, None, :], emb], axis=1)
    z = lax.dot_general(x, x, (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32)
    sel = np.array([i * F + j for i, j in tril_pairs(F)], dtype=np.int32)
    zt = z.reshape(batch, F * F)[:, sel]
    cat = jnp.concatenate([bottom.astype(jnp.float32), zt], axis=1)

    g = g.astype(jnp.float32)
    if relu:
        g = jnp.where(y > 0.0, g, 0.0)
    dw = jnp.dot(cat.T, g, preferred_element_type=jnp.float32)
    db = jnp.sum(g, axis=0)
    g_cat = jnp.dot(g, w.astype(jnp.float32).T,
                    preferred_element_type=jnp.float32)
    g_bottom = g_cat[:, :d]
    g_z_flat = jnp.zeros((batch, F * F), jnp.float32)
    g_z_flat = g_z_flat.at[:, sel].set(g_cat[:, d:])
    g_z = g_z_flat.reshape(batch, F, F)
    # dX = (g_Z + g_Zᵀ)·X
    dx = lax.dot_general(g_z + jnp.swapaxes(g_z, 1, 2), x,
                         (((2,), (1,)), ((0,), (0,))),
                         preferred_element_type=jnp.float32)
    g_bottom = g_bottom + dx[:, 0, :]
    # rows of one bag share the sample/table gradient (sum aggregation)
    g_rows = jnp.repeat(dx[:, 1:, :].reshape(batch * T, d), bag, axis=0)
    flat = idx.reshape(-1)
    order = jnp.argsort(flat)
    dtable = jax.ops.segment_sum(
        g_rows[order], flat[order], num_segments=spec.shape[0],
        indices_are_sorted=True).astype(spec.dtype)
    return (dtable, np.zeros(indices.shape, dtype=jax.dtypes.float0),
            g_bottom.astype(bottom.dtype), dw.astype(w.dtype), db)


fused_interaction.defvjp(_fused_fwd, _fused_bwd)
