"""Quantized embedding storage: int8/fp8 rows with row-wise scales as a
per-table policy.

Embedding tables dominate DLRM memory AND bytes-moved (Naumov 2019);
low-precision row storage with row-wise scales is the standard
production answer (Guan 2019, post-training 4/8-bit embedding tables).
A :class:`QuantPolicy` is a per-table STORAGE policy — dtype in
{fp32, bf16, int8, fp8}, row-wise symmetric scales (zero-point 0), and
an update rule — threaded through ``ParallelConfig``/``strategy_io`` so
the MCMC search, shardcheck, and the serving tier all price the same
row bytes. One policy multiplies against nearly every subsystem:

- HBM: int8 rows cut per-table residency ~4x
  (``simulator.hbm_footprint_report`` / shardcheck FLX503);
- exchange: the row payloads of the row-sharded all-to-all ship at the
  storage width (``cost_model`` / FLX513 predicted bytes);
- freshness: delta publishes ship ``int8 rows + fp32 scales``
  (``utils/delta.py``), shrinking the measured ~150 KB publish ~4x;
- serving: ``EmbeddingCache`` / the shard tier / the warm cache hold
  ~4x more rows per MB, dequantizing at the RANKER boundary.

Execution model: *master-resident simulated quantization* on every
backend — the trainable parameter remains an fp32 master whose values
are exact dequantizations of the quantized representation, so every
existing update path (replicated / row-sharded / hybrid, SGD / momentum
/ Adam, superstep scan) runs unchanged while storage boundaries
(checkpoints' delta publishes, serving tables, caches) ship true
``q + scale`` payloads bit-exactly. (A Pallas gather that dequantized
int8 rows in VMEM was removed: Mosaic refuses single-row slices of an
int8 HBM table, whose rows are tiled four to a 32-bit word.)

Update rules:

- ``master_weight``: updates apply to the fp32 master — BIT-IDENTICAL
  to the fp32-accumulator reference by construction (pinned by
  tests/test_quant.py across the optimizer x placement matrix). In the
  production TPU layout the master slab lives host-side beside the
  optimizer state; HBM holds the quantized rows.
- ``stochastic_rounding``: no master — the table re-quantizes after
  every update with stochastic rounding (unbiased; deterministic per
  step via the step-folded RNG), trading exactness for the full
  training-time memory win.

Quantize(dequantize(q, s)) == (q, s) for the row-wise symmetric codec
(the row max always maps to the top code), so re-quantizing a
dequantized payload is IDEMPOTENT — the property that lets fp32 arrays
flow between subsystems while quantized storage round-trips bit-exactly
(pinned in tests/test_quant.py).
"""

from .policy import (DTYPES, SCALE_BYTES, UPDATE_RULES, QuantPolicy,
                     effective_policy, policy_from_pc, table_storage_bytes)
from .codec import (decode_q, dequantize_rows_np, encode_q, fake_quant,
                    fake_quant_np, fake_quant_stochastic,
                    fake_quant_stochastic_np, quantize_rows_np,
                    validate_scales)

__all__ = [
    "DTYPES", "UPDATE_RULES", "SCALE_BYTES", "QuantPolicy",
    "policy_from_pc", "effective_policy", "table_storage_bytes",
    "quantize_rows_np", "dequantize_rows_np", "fake_quant_np",
    "fake_quant", "fake_quant_stochastic", "fake_quant_stochastic_np",
    "encode_q", "decode_q", "validate_scales",
]
