"""VMEM-resident LSTM scan kernel.

Round-4 calibration found the LSTM cell WEIGHT-STREAM-BOUND: of the
~32 us/iteration an NMT-sized cell (b64, h1024, bf16) costs under
lax.scan, ~27 us is re-streaming the (h, 4h) recurrent matrix from HBM —
XLA does not keep scan weights resident in VMEM.
This kernel pins them: the grid iterates the time dimension (TPU grid
steps run in order), the recurrent weights use a CONSTANT index_map so
pallas keeps their block in VMEM across all steps, and the (b, h)
hidden/cell carries live in VMEM scratch. Per-iteration HBM traffic
drops to the small x-projection block in and h/c blocks out.

The backward pass is a second reverse-order kernel (same residency
trick, wh AND wh^T resident) that RECOMPUTES the gates from the stored
h/c residuals and emits per-step gate cotangents dz; the weight gradient
is then ONE stacked gemm outside the kernel (exactly how XLA's scan vjp
structures it — r4 calibration's 1.25x-fwd backward finding).

Gate order i, f, g, o (torch convention, matching ops/rnn.py).
Reference analog: the NMT runtime's cuDNN LSTM (nmt/lstm.cu:1) — cuDNN
keeps weights on-chip across the sequence the same way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gates(gates, cprev):
    h4 = gates.shape[-1] // 4
    i = jax.nn.sigmoid(gates[:, :h4])
    f = jax.nn.sigmoid(gates[:, h4:2 * h4])
    g = jnp.tanh(gates[:, 2 * h4:3 * h4])
    o = jax.nn.sigmoid(gates[:, 3 * h4:])
    c = f * cprev + i * g
    return i, f, g, o, c


def _fwd_kernel(xp_ref, wh_ref, ys_ref, cs_ref, h_s, c_s):
    i0 = pl.program_id(0)

    @pl.when(i0 == 0)
    def _():
        h_s[...] = jnp.zeros_like(h_s)
        c_s[...] = jnp.zeros_like(c_s)

    hprev = h_s[...]
    gates = xp_ref[0, :, :] + jnp.dot(
        hprev.astype(wh_ref.dtype), wh_ref[...],
        preferred_element_type=jnp.float32)
    _, _, _, o, c = _gates(gates, c_s[...])
    h = o * jnp.tanh(c)
    h_s[...] = h
    c_s[...] = c
    ys_ref[0, :, :] = h
    if cs_ref is not None:
        cs_ref[0, :, :] = c


# share of VMEM the kernels may plan for; the rest is Mosaic's own temps
_VMEM_SHARE = 0.6


def _scan_vmem_need(batch: int, hidden: int, w_itemsize: int) -> int:
    """Bytes the BACKWARD kernel (the larger of the two) holds in VMEM,
    counted the way Pallas allocates them: every BlockSpec'd operand is
    double-buffered, the constant-index weights included."""
    resident = 2 * (2 * hidden * 4 * hidden * w_itemsize)  # wh + whT
    # per-step fp32 blocks: xp in, dz out (b, 4h); dy/hprev/cprev/c (b, h)
    blocks = 2 * (2 * batch * 4 * hidden + 4 * batch * hidden) * 4
    carries = 2 * batch * hidden * 4
    temps = 3 * batch * 4 * hidden * 4          # gates, dz, concat
    return resident + blocks + carries + temps


def _compiler_params(batch: int, hidden: int, w_itemsize: int):
    """Ask for the need plus the headroom the eligibility gate
    (`scan_shape_fits`) reserved. Under the compiler's default scoped
    limit the NMT shape (h=1024, bf16, b64) still compiles on v5e, but
    h=1280 and h=1408, which the gate admits, run out of VMEM; with this
    limit all three compile. Small shapes keep a 16 MiB allowance."""
    need = _scan_vmem_need(batch, hidden, w_itemsize)
    return pltpu.CompilerParams(
        vmem_limit_bytes=max(int(need / _VMEM_SHARE), 16 << 20))


def _run_fwd(xproj, wh, interpret, with_residuals=True):
    # TIME-MAJOR (T, b, 4h): TPU blocks must keep the last two dims
    # (sublane, lane) aligned — the time dim rides the grid as dim 0.
    # with_residuals=False (the no-gradient primal) skips the (T, b, h)
    # cell-state output nothing would read.
    T, b, h4 = xproj.shape
    h = h4 // 4
    blk = pl.BlockSpec((1, b, h), lambda i: (i, 0, 0))
    shp = jax.ShapeDtypeStruct((T, b, h), jnp.float32)
    kernel = (_fwd_kernel if with_residuals else
              (lambda xp, w, ys, h_s, c_s:
               _fwd_kernel(xp, w, ys, None, h_s, c_s)))
    with jax.named_scope("lstm_fwd"):
        out = pl.pallas_call(
            kernel,
            grid=(T,),
            in_specs=[
                pl.BlockSpec((1, b, h4), lambda i: (i, 0, 0)),
                pl.BlockSpec(wh.shape, lambda i: (0, 0)),   # VMEM-resident
            ],
            out_specs=[blk, blk] if with_residuals else blk,
            out_shape=[shp, shp] if with_residuals else shp,
            scratch_shapes=[
                pltpu.VMEM((b, h), jnp.float32),
                pltpu.VMEM((b, h), jnp.float32),
            ],
            compiler_params=_compiler_params(b, h, wh.dtype.itemsize),
            interpret=interpret,
            name="lstm_fwd",
        )(xproj, wh)
    return out if with_residuals else (out, None)


def _bwd_kernel(xp_ref, wh_ref, whT_ref, dys_ref, hprev_ref, cprev_ref,
                cs_ref, dzs_ref, dh_s, dc_s):
    i0 = pl.program_id(0)

    @pl.when(i0 == 0)
    def _():
        dh_s[...] = jnp.zeros_like(dh_s)
        dc_s[...] = jnp.zeros_like(dc_s)

    hprev = hprev_ref[0, :, :]
    cprev = cprev_ref[0, :, :]
    gates = xp_ref[0, :, :] + jnp.dot(
        hprev.astype(wh_ref.dtype), wh_ref[...],
        preferred_element_type=jnp.float32)
    i, f, g, o, _ = _gates(gates, cprev)
    c = cs_ref[0, :, :]
    tanh_c = jnp.tanh(c)
    dh = dys_ref[0, :, :] + dh_s[...]
    dc = dc_s[...] + dh * o * (1.0 - tanh_c * tanh_c)
    di = dc * g * i * (1.0 - i)
    df = dc * cprev * f * (1.0 - f)
    dg = dc * i * (1.0 - g * g)
    do = dh * tanh_c * o * (1.0 - o)
    dz = jnp.concatenate([di, df, dg, do], axis=1)
    dzs_ref[0, :, :] = dz
    dh_s[...] = jnp.dot(dz.astype(whT_ref.dtype), whT_ref[...],
                        preferred_element_type=jnp.float32)
    dc_s[...] = dc * f


def _run_bwd(xproj, wh, hs_prev, cs_prev, cs, dys, interpret):
    T, b, h4 = xproj.shape
    h = h4 // 4
    whT = jnp.swapaxes(wh, 0, 1)
    rev = lambda i: (T - 1 - i, 0, 0)
    blk_h = pl.BlockSpec((1, b, h), rev)
    with jax.named_scope("lstm_bwd"):
        dzs = pl.pallas_call(
            _bwd_kernel,
            grid=(T,),
            in_specs=[
                pl.BlockSpec((1, b, h4), rev),
                pl.BlockSpec(wh.shape, lambda i: (0, 0)),    # resident
                pl.BlockSpec(whT.shape, lambda i: (0, 0)),   # resident
                blk_h, blk_h, blk_h, blk_h,
            ],
            out_specs=pl.BlockSpec((1, b, h4), rev),
            out_shape=jax.ShapeDtypeStruct((T, b, h4), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((b, h), jnp.float32),
                pltpu.VMEM((b, h), jnp.float32),
            ],
            compiler_params=_compiler_params(b, h, wh.dtype.itemsize),
            interpret=interpret,
            name="lstm_bwd",
        )(xproj, wh, whT, dys, hs_prev, cs_prev, cs)
    return dzs


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def lstm_scan(xproj, wh, interpret=False):
    """ys = LSTM-scan over time of gate pre-activations `xproj`
    (T, b, 4h) float32, TIME-MAJOR (x @ wx + bias, hoisted by the
    caller) with recurrent weights `wh` (h, 4h), zero initial state.
    Returns (T, b, h) float32 hidden states."""
    ys, _ = _run_fwd(xproj, wh, interpret, with_residuals=False)
    return ys


def _vjp_fwd(xproj, wh, interpret):
    ys, cs = _run_fwd(xproj, wh, interpret)
    return ys, (xproj, wh, ys, cs)


def _vjp_bwd(interpret, res, dys):
    xproj, wh, hs, cs = res
    zeros = jnp.zeros_like(hs[:1])
    hs_prev = jnp.concatenate([zeros, hs[:-1]], axis=0)
    cs_prev = jnp.concatenate([zeros, cs[:-1]], axis=0)
    dzs = _run_bwd(xproj, wh, hs_prev, cs_prev, cs,
                   dys.astype(jnp.float32), interpret)
    # dW is ONE stacked gemm over all timesteps (no serial dependence)
    dwh = jnp.einsum("tbh,tbk->hk", hs_prev, dzs,
                     preferred_element_type=jnp.float32)
    return dzs, dwh.astype(wh.dtype)


lstm_scan.defvjp(_vjp_fwd, _vjp_bwd)


def resident_scan_ok(model, batch: int, hidden: int, seq: int,
                     local: bool = False) -> bool:
    """Whether the VMEM-resident kernel path applies: TPU, lane-aligned
    hidden, sublane-aligned batch, and a backward kernel (wh AND whT
    pinned, at the model's compute-dtype width) that fits the ATTACHED
    device's VMEM — an eligible-looking large-hidden config must fall
    back to lax.scan instead of dying in Mosaic compilation.

    `local=False` additionally requires a single-device mesh (a direct
    pallas call cannot run inside GSPMD); `local=True` checks per-SHARD
    eligibility for the shard_map DP route (ops/rnn.py:_dp_shard_axes),
    where `batch` is the per-shard batch."""
    if not getattr(model.config, "pallas_lstm", True):
        return False
    if jax.default_backend() != "tpu":
        return False
    if not local:
        mesh = getattr(model, "mesh", None)
        if mesh is not None and mesh.size > 1:
            return False
    return scan_shape_fits(model, batch, hidden, seq)


def scan_shape_fits(model, batch: int, hidden: int, seq: int,
                    vmem_bytes: int = 0) -> bool:
    """Alignment + VMEM-budget test alone (no backend/mesh gating) —
    shared by the runtime route predicate and the strategy search's
    backend-independent candidate predicate. `vmem_bytes` overrides the
    attached device's VMEM (search prices for the TARGET chip)."""
    if not vmem_bytes:
        from ...search.cost_model import TPUSpec
        vmem_bytes = TPUSpec.detect().vmem_bytes
    itemsize = jnp.dtype(getattr(model.config, "jnp_compute_dtype",
                                 jnp.bfloat16)).itemsize
    return (hidden % 128 == 0 and batch % 8 == 0 and seq >= 2
            and _scan_vmem_need(batch, hidden, itemsize)
            <= _VMEM_SHARE * vmem_bytes)
