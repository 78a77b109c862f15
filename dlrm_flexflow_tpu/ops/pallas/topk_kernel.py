"""Pallas TPU chunked MIPS scoring kernel: int8 matmul → running top-k.

The retrieval index (retrieve/index.py) stores item-tower output
embeddings as PR-14 ``QuantTable`` codes + per-row fp32 scales, and the
maximum-inner-product search scores queries directly AGAINST THE CODES:

    score[b, r] = int32( q_codes[b] · codes[r] ) * (scales[r] * q_scales[b])

— an int8×int8 dot with a dequant-free int32 accumulate on the MXU and
ONE fp32 rescale at the end, so scoring bandwidth pays quantized bytes
(the same codec already pays for memory, exchange, and publishes; this
is where it pays a fourth time). The kernel streams the item block in
chunks and carries a running top-k (scores + ids) in VMEM across grid
steps; the merged result NEVER materializes the full (B, R) score
matrix in HBM.

Ordering contract (the merge-exactness goldens pin this): top-k is by
score DESCENDING with ties broken by id ASCENDING. The integer dot is
exact and the rescale is one fp32 multiply in a fixed order, so the
same (codes, scales, query) produce bit-identical scores on every
shard, every backend — which is what makes the sharded heap-merge
(retrieve/index.py) provably identical to a single-machine exact scan.

Off-TPU the plain-XLA/numpy oracle (``mips_topk_reference``) is the
fallback — same math, same ordering, bit-identical results; the CPU
tier-1 suite runs that path (or the kernel under ``interpret=True``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# int8 sublane granule: item chunks pad their row count up to this
_INT8_SUBLANES = 32
# sentinel id for empty/padded top-k slots (trimmed by callers)
PAD_ID = np.int32(2 ** 31 - 1)
NEG_INF = np.float32(-np.inf)


def supports(dim: int) -> bool:
    """True if the compiled kernel handles this embedding width (the
    MXU wants whole int8 lane tiles; anything else routes the oracle)."""
    return dim % _LANES == 0


# ---------------------------------------------------------------------
# shared scoring math — the oracle IS the contract
# ---------------------------------------------------------------------
def quantize_query(q: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-row int8 quantization of a query batch (the same
    codec the index rows use, quant/codec.py): (B, d) fp32 ->
    ((B, d) int8 codes, (B,) fp32 scales). A 1-D query is promoted to a
    batch of one."""
    from ...quant.codec import quantize_rows_np
    arr = np.asarray(q, np.float32)
    if arr.ndim == 1:
        arr = arr[None, :]
    codes, scales = quantize_rows_np(arr, "int8")
    return codes, scales


def score_rows_np(q_codes: np.ndarray, q_scales: np.ndarray,
                  codes: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """(B, R) fp32 scores: exact int32 code dot, one fp32 rescale.

    The multiply order (row scale × query scale first, then the dot) is
    part of the exactness contract — the Pallas kernel computes the
    same expression in the same order."""
    dot = q_codes.astype(np.int32) @ codes.astype(np.int32).T    # (B, R)
    comb = (scales.astype(np.float32)[None, :]
            * q_scales.astype(np.float32)[:, None])              # (B, R)
    return dot.astype(np.float32) * comb


def topk_select_np(scores: np.ndarray, ids: np.ndarray, k: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k of each row by (score desc, id asc): (B, k') scores and
    int64 ids, k' = min(k, R). fp32 negation is exact, so the lexsort
    key order matches the kernel's selection order bit-for-bit."""
    scores = np.asarray(scores, np.float32)
    ids = np.asarray(ids, np.int64)
    kk = min(int(k), scores.shape[1])
    out_s = np.empty((scores.shape[0], kk), np.float32)
    out_i = np.empty((scores.shape[0], kk), np.int64)
    for b in range(scores.shape[0]):
        order = np.lexsort((ids, -scores[b]))[:kk]
        out_s[b] = scores[b][order]
        out_i[b] = ids[order]
    return out_s, out_i


def mips_topk_reference(q_codes: np.ndarray, q_scales: np.ndarray,
                        codes: np.ndarray, scales: np.ndarray,
                        k: int, base: int = 0
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """The exact-scan oracle: score every row, sort, take k. ``base``
    offsets the returned ids into a global row space (a shard scoring
    its [lo, hi) slice passes base=lo)."""
    scores = score_rows_np(q_codes, q_scales, codes, scales)
    ids = base + np.arange(codes.shape[0], dtype=np.int64)
    return topk_select_np(scores, ids, k)


# ---------------------------------------------------------------------
# the Pallas kernel
# ---------------------------------------------------------------------
def _topk_kernel(K: int, C: int, n_rows: int,
                 q_ref, qscale_ref, codes_ref, scales_ref,
                 out_s_ref, out_i_ref, run_s, run_i):
    """One grid step scores a (C, d) item chunk against every query and
    folds it into the running (B, K) top-k carried in VMEM scratch.

    The merge is a K-round selection: take the max score (ties to the
    LOWEST id), emit it, deactivate it — exactly the oracle's
    (score desc, id asc) lexsort order, so the compiled path and the
    fallback are bit-identical."""
    step = pl.program_id(0)
    nsteps = pl.num_programs(0)
    B = q_ref.shape[0]

    @pl.when(step == 0)
    def _():
        run_s[:] = jnp.full((B, K), NEG_INF, jnp.float32)
        run_i[:] = jnp.full((B, K), PAD_ID, jnp.int32)

    # int8 × int8 → int32 on the MXU; dequant-free accumulate
    dot = lax.dot_general(q_ref[:], codes_ref[:],
                          (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.int32)      # (B, C)
    comb = scales_ref[:].reshape(1, C) * qscale_ref[:]           # (B, C)
    scores = dot.astype(jnp.float32) * comb
    row_ids = (step * C
               + lax.broadcasted_iota(jnp.int32, (B, C), 1))
    # rows past the real table (chunk padding) never win
    scores = jnp.where(row_ids < n_rows, scores, NEG_INF)

    cand_s = jnp.concatenate([run_s[:], scores], axis=1)         # (B, K+C)
    cand_i = jnp.concatenate([run_i[:], row_ids], axis=1)
    for j in range(K):
        m = jnp.max(cand_s, axis=1, keepdims=True)
        elig = cand_s == m
        pick = jnp.min(jnp.where(elig, cand_i, PAD_ID), axis=1,
                       keepdims=True)
        run_s[:, j:j + 1] = m
        run_i[:, j:j + 1] = pick
        cand_s = jnp.where(elig & (cand_i == pick), NEG_INF, cand_s)

    @pl.when(step == nsteps - 1)
    def _():
        out_s_ref[:] = run_s[:]
        out_i_ref[:] = run_i[:]


def _pallas_topk(q_codes, q_scales, codes, scales, k, chunk, interpret):
    B, d = q_codes.shape
    R = codes.shape[0]
    C = max(_INT8_SUBLANES,
            ((min(chunk, R) + _INT8_SUBLANES - 1)
             // _INT8_SUBLANES) * _INT8_SUBLANES)
    Rp = ((R + C - 1) // C) * C
    codes_p = jnp.zeros((Rp, d), jnp.int8).at[:R].set(
        jnp.asarray(codes, jnp.int8))
    scales_p = jnp.zeros((Rp, 1), jnp.float32).at[:R, 0].set(
        jnp.asarray(scales, jnp.float32))
    with jax.named_scope("topk"):
        out_s, out_i = pl.pallas_call(
            functools.partial(_topk_kernel, int(k), C, R),
            grid=(Rp // C,),
            in_specs=[
                pl.BlockSpec((B, d), lambda i: (0, 0)),              # queries
                pl.BlockSpec((B, 1), lambda i: (0, 0)),              # q scales
                pl.BlockSpec((C, d), lambda i: (i, 0)),              # chunk
                pl.BlockSpec((C, 1), lambda i: (i, 0)),              # scales
            ],
            out_specs=[
                pl.BlockSpec((B, int(k)), lambda i: (0, 0)),
                pl.BlockSpec((B, int(k)), lambda i: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, int(k)), jnp.float32),
                jax.ShapeDtypeStruct((B, int(k)), jnp.int32),
            ],
            scratch_shapes=[
                pltpu.VMEM((B, int(k)), jnp.float32),
                pltpu.VMEM((B, int(k)), jnp.int32),
            ],
            interpret=interpret,
            name="topk",
        )(jnp.asarray(q_codes, jnp.int8),
          jnp.asarray(q_scales, jnp.float32).reshape(B, 1),
          codes_p, scales_p)
    return np.asarray(out_s), np.asarray(out_i)


def mips_topk(q_codes: np.ndarray, q_scales: np.ndarray,
              codes: np.ndarray, scales: np.ndarray, k: int,
              base: int = 0, chunk: int = 512,
              use_pallas: Optional[bool] = None,
              interpret: bool = False
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k MIPS over one quantized row block.

    q_codes  : (B, d) int8 query codes (quantize_query)
    q_scales : (B,) fp32 query row scales
    codes    : (R, d) int8 item codes, scales (R,) fp32 (QuantTable)
    returns  : ((B, k') fp32 scores, (B, k') int64 global ids),
               k' = min(k, R), ordered (score desc, id asc).

    Routing: the compiled Pallas path needs a TPU backend and a lane-
    aligned width (``supports``); everything else — the CPU tier-1
    suite included — runs the bit-identical oracle. ``interpret=True``
    forces the kernel through the Pallas interpreter (kernel-parity
    tests)."""
    q_codes = np.asarray(q_codes, np.int8)
    if q_codes.ndim == 1:
        q_codes = q_codes[None, :]
    q_scales = np.asarray(q_scales, np.float32).reshape(-1)
    R = codes.shape[0]
    if R == 0:
        B = q_codes.shape[0]
        return (np.empty((B, 0), np.float32), np.empty((B, 0), np.int64))
    if use_pallas is None:
        use_pallas = interpret or (jax.default_backend() == "tpu"
                                   and supports(q_codes.shape[1]))
    if not use_pallas:
        return mips_topk_reference(q_codes, q_scales, codes, scales,
                                   k, base)
    kk = min(int(k), R)
    out_s, out_i = _pallas_topk(q_codes, q_scales, codes, scales,
                                kk, chunk, interpret)
    return out_s, base + out_i.astype(np.int64)
