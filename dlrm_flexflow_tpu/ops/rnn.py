"""Recurrent ops: LSTM layer.

Parity with the reference NMT mini-framework's LSTM (reference: nmt/lstm.cu,
574 LoC — cuDNN RNN kernels; one op per (layer, word-position) chunk of
LSTM_PER_NODE_LENGTH=10 cells, nmt/rnn.h:23,58-63, placed per-cell by a
hand-written GlobalConfig table).

TPU-native redesign: the whole sequence is ONE op whose time loop is a
`lax.scan` — XLA unrolls nothing, compiles one cell and iterates, keeping
the (batch, 4*hidden) gate matmuls on the MXU. The reference's per-cell
device placement (its only sequence-scaling trick) is subsumed by batch/
hidden sharding; hidden-state TP shards the gate matmul columns. The
sequence dim itself must stay unpartitioned for the scan (degrees[1] == 1);
long-sequence scaling on TPU is the job of sequence-parallel attention
(ops/attention.py), not RNN chunking.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.initializers import DEFAULT_KERNEL_INIT, ZeroInitializer
from ..core.op import Op, ParamDef
from ..parallel.pconfig import ParallelConfig


def _dp_route(model, op, b, hidden, seq):
    """(batch_axes, nsh) when the resident kernel can run PER-SHARD
    under shard_map: pure data parallelism (seq and hidden unsharded,
    recurrent weights replicated) AND per-shard kernel eligibility
    (resident_scan_ok with the local batch — pallas flag, backend,
    alignment, VMEM budget). None otherwise. Same pattern as the
    sharded embedding scatter
    (ops/embedding.py:_row_shard_axes → sharded_scatter_add_packed)."""
    mesh = getattr(model, "mesh", None)
    if mesh is None or mesh.size <= 1 or op is None:
        return None
    sh = getattr(model, "_out_sharding", {}).get(op.outputs[0].guid)
    if sh is None:
        return None
    # PartitionSpec omits trailing unsharded dims: P(('f0','f1'),) means
    # seq/hidden replicated
    spec = tuple(sh.spec) + (None,) * (3 - len(sh.spec))
    if spec[1] is not None or spec[2] is not None:
        return None
    spec0 = spec[0]
    if not spec0:
        return None
    axes = (spec0,) if isinstance(spec0, str) else tuple(spec0)
    # recurrent weights must be replicated (hidden-TP shards the 4h dim)
    wsh = getattr(model, "_param_sharding", {}).get(op.name, {})
    for k, s_ in wsh.items():
        if k.startswith("wh") and any(a is not None for a in s_.spec):
            return None
    nsh = 1
    for a in axes:
        nsh *= mesh.shape[a]
    # global-trace check: under the cost model's standalone measurement
    # the array is already LOCAL-shaped and must not be re-sharded
    if b != op.inputs[0].shape[0] or b % nsh != 0:
        return None
    from .pallas.lstm_kernel import resident_scan_ok
    if not resident_scan_ok(model, b // nsh, hidden, seq, local=True):
        return None
    return axes, nsh


def _resident_route_ok(model, op, b, hidden, seq) -> bool:
    """Single predicate for "the VMEM-resident kernel will carry this
    op's scan" — single-chip direct call OR per-shard DP shard_map.
    Used by apply() routing AND the cost-model hooks so they cannot
    drift."""
    from .pallas.lstm_kernel import resident_scan_ok
    return (resident_scan_ok(model, b, hidden, seq)
            or _dp_route(model, op, b, hidden, seq) is not None)


@functools.lru_cache(maxsize=1)
def _target_vmem_default() -> int:
    """Fallback target VMEM for candidate pricing when the caller does
    not thread a spec through (memoized — this sits in the MCMC inner
    loop)."""
    from ..search.cost_model import TPUSpec
    return TPUSpec.detect().vmem_bytes


def _resident_route_ok_candidate(model, b, hidden, seq, pc,
                                 vmem_bytes: int = 0) -> bool:
    """Residency under a CANDIDATE config, for strategy search: backend-
    independent (an offline CPU search must price the scan the way it
    will run on the TPU target — ADVICE r4) and judged against `pc`
    rather than the currently-compiled sharding. Eligible iff the
    candidate is pure batch-DP (hidden/seq unsharded; hidden-TP shards
    wh, which the resident kernel cannot carry) and the per-shard shape
    passes the same alignment/VMEM test against the TARGET chip
    (`vmem_bytes`, threaded from the cost model's TPUSpec so a
    user-injected spec is honored)."""
    if not getattr(model.config, "pallas_lstm", True):
        return False
    degs = tuple(pc.degrees) + (1,) * (3 - len(pc.degrees))
    if any(d > 1 for d in degs[1:3]):
        return False
    parts = max(degs[0], 1)
    if b % parts:
        return False
    from .pallas.lstm_kernel import scan_shape_fits
    return scan_shape_fits(model, b // parts, hidden, seq,
                           vmem_bytes=vmem_bytes or _target_vmem_default())


def _recurrent_scan(model, xproj, whc, cdt, op=None):
    """The serial part of an LSTM layer: scan gate pre-activations
    `xproj` (b, s, 4h) with recurrent weights `whc`. Routes to the
    VMEM-resident pallas kernel when eligible — round-4 measurement
    found the lax.scan cell WEIGHT-STREAM-BOUND (~27 of ~32 us/iter is
    re-streaming wh from HBM; XLA does not pin scan weights), which the
    kernel removes. Under a >1-device mesh with pure batch DP the
    kernel runs per-shard inside shard_map (each shard's rows are
    independent — exact). Fallback: plain lax.scan (same math, same
    i,f,g,o order)."""
    b, s, h4 = xproj.shape
    h = h4 // 4
    from .pallas.lstm_kernel import lstm_scan, resident_scan_ok
    if resident_scan_ok(model, b, h, s):
        # the kernel is time-major (grid dim 0 = time; TPU block
        # alignment wants (b, 4h) as the trailing dims)
        ys = lstm_scan(jnp.swapaxes(xproj, 0, 1), whc)
        return jnp.swapaxes(ys, 0, 1)
    route = _dp_route(model, op, b, h, s)
    if route is not None:
        axes, _ = route
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import smap

        def local(xp, w):
            ys = lstm_scan(jnp.swapaxes(xp, 0, 1), w)
            return jnp.swapaxes(ys, 0, 1)

        return smap(local, model.mesh,
                    in_specs=(P(axes, None, None), P(None, None)),
                    out_specs=P(axes, None, None))(xproj, whc)

    def cell(carry, xp):
        hprev, cprev = carry
        gates = xp + jnp.dot(hprev.astype(cdt), whc,
                             preferred_element_type=jnp.float32)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c = f * cprev + i * g
        hcur = o * jnp.tanh(c)
        return (hcur, c), hcur

    zeros = jnp.zeros((b, h), jnp.float32)
    (_, _), hs = lax.scan(cell, (zeros, zeros),
                          jnp.swapaxes(xproj, 0, 1))  # (s, b, h)
    return jnp.swapaxes(hs, 0, 1)


def _lstm_candidate_configs(hidden, num_devices, feasible_degrees):
    """batch DP x hidden TP; the seq dim must stay whole for the scan
    (shared by LSTM and LSTMStack so the enumerations cannot drift)."""
    out = []
    for ds in feasible_degrees:
        for dh in feasible_degrees:
            if ds * dh <= num_devices and hidden % max(dh, 1) == 0:
                out.append(ParallelConfig((ds, 1, dh)))
    return out


class LSTM(Op):
    """input (batch, seq, in_dim) -> output (batch, seq, hidden) and the
    final hidden state is discarded (sequence-to-sequence layer form).
    Gate order i,f,g,o (torch convention, for golden tests)."""

    type_name = "LSTM"

    def __init__(self, model, input_tensor, hidden: int,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        if input_tensor.num_dims != 3:
            raise ValueError("LSTM expects (batch, seq, in_dim)")
        b, s, d = input_tensor.shape
        self.in_dim = d
        self.hidden = int(hidden)
        self.outputs = [self._make_output((b, s, self.hidden))]

    def param_defs(self) -> Dict[str, ParamDef]:
        h, d = self.hidden, self.in_dim
        return {
            "wx": ParamDef((d, 4 * h), jnp.float32, DEFAULT_KERNEL_INIT()),
            "wh": ParamDef((h, 4 * h), jnp.float32, DEFAULT_KERNEL_INIT()),
            "bias": ParamDef((4 * h,), jnp.float32, ZeroInitializer()),
        }

    def apply(self, params, xs, *, training=False, rng=None):
        (x,) = xs  # (b, s, d)
        cdt = self.model.compute_dtype
        wx, wh, bias = params["wx"], params["wh"], params["bias"]
        # precompute input projections for the whole sequence in one big
        # MXU matmul, then scan only the recurrent part
        xproj = jnp.einsum("bsd,dk->bsk", x.astype(cdt), wx.astype(cdt),
                           preferred_element_type=jnp.float32) + bias
        # cast the recurrent weights ONCE outside the loop: a cast inside
        # the body would re-stream the (h, 4h) matrix every timestep if
        # XLA declines to hoist it (16 MB/step at reference scale)
        hs = _recurrent_scan(self.model, xproj, wh.astype(cdt), cdt,
                             op=self)
        return [hs.astype(x.dtype)]

    def candidate_parallel_configs(self, num_devices, feasible_degrees):
        return _lstm_candidate_configs(self.hidden, num_devices,
                                       feasible_degrees)

    def param_axes(self, pc: ParallelConfig, out_axes,
                   raw_pc=None):
        ch = out_axes[2] if len(out_axes) >= 3 else ()
        # gate matrices are (.., 4h): sharding 4h on the hidden axes keeps
        # each device's gate slice local (i/f/g/o interleave is fine since
        # split(4) is along the same sharded dim)
        return {"wx": ((), ch), "wh": ((), ch), "bias": (ch,)}

    def param_shard_shapes(self, pc: ParallelConfig, ndev=None):
        dc = pc.degrees[2] if len(pc.degrees) > 2 else 1
        shapes = {n_: list(d.shape) for n_, d in self.param_defs().items()}
        if dc > 1:
            for n_ in shapes:
                shapes[n_][-1] = max(shapes[n_][-1] // dc, 1)
        return {n_: tuple(v) for n_, v in shapes.items()}

    def flops_per_sample(self) -> float:
        s = self.inputs[0].shape[1]
        return 2.0 * s * 4 * self.hidden * (self.in_dim + self.hidden)

    def sequential_steps(self, pc=None, vmem_bytes: int = 0) -> int:
        # the recurrent scan: one serial iteration per sequence position
        return int(self.inputs[0].shape[1])

    def scan_weights_resident(self, pc=None, vmem_bytes: int = 0) -> bool:
        b, s, _ = self.inputs[0].shape
        if pc is not None:
            return _resident_route_ok_candidate(self.model, b, self.hidden,
                                                s, pc, vmem_bytes)
        return _resident_route_ok(self.model, self, b, self.hidden, s)

    def scan_param_stream_bytes(self) -> int:
        # only the recurrent matrix rides inside the loop; wx/bias are
        # hoisted into one sequence-wide projection (apply())
        return self.hidden * 4 * self.hidden * 4


class LSTMStack(Op):
    """N stacked LSTM layers fused into ONE scan.

    Stacking N separate LSTM ops runs N scans of `seq` iterations each —
    N x seq serial steps, each paying the fixed lax.scan iteration
    latency that dominates small-batch RNNs (~300 us/iteration measured
    at NMT scale vs ~15 us of gemm). Fusing the layers into one scan
    body does the SAME math (layer l at time t consumes layer l-1's
    output at time t, computed earlier in the same iteration) in seq
    iterations total — the serial latency is paid once per timestep, not
    once per layer per timestep. The reference reaches for per-cell
    device placement for this (nmt/rnn.h:58-63); on TPU the lever is
    iteration count, not placement.

    input (batch, seq, in_dim) -> output (batch, seq, hidden) of the top
    layer. Gate order i,f,g,o per layer (torch convention).
    """

    type_name = "LSTMStack"

    def __init__(self, model, input_tensor, hidden: int, num_layers: int,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        if input_tensor.num_dims != 3:
            raise ValueError("LSTMStack expects (batch, seq, in_dim)")
        if num_layers < 1:
            raise ValueError("num_layers must be >= 1")
        b, s, d = input_tensor.shape
        self.in_dim = d
        self.hidden = int(hidden)
        self.num_layers = int(num_layers)
        self.outputs = [self._make_output((b, s, self.hidden))]

    def param_defs(self) -> Dict[str, ParamDef]:
        h = self.hidden
        defs = {}
        for layer in range(self.num_layers):
            d = self.in_dim if layer == 0 else h
            defs[f"wx{layer}"] = ParamDef((d, 4 * h), jnp.float32,
                                          DEFAULT_KERNEL_INIT())
            defs[f"wh{layer}"] = ParamDef((h, 4 * h), jnp.float32,
                                          DEFAULT_KERNEL_INIT())
            defs[f"bias{layer}"] = ParamDef((4 * h,), jnp.float32,
                                            ZeroInitializer())
        return defs

    def apply(self, params, xs, *, training=False, rng=None):
        (x,) = xs  # (b, s, d)
        cdt = self.model.compute_dtype
        h, L = self.hidden, self.num_layers
        b, s, _ = x.shape
        if _resident_route_ok(self.model, self, b, h, s):
            # layer-by-layer with the VMEM-resident kernel: EVERY
            # layer's input projection hoists to one big sequence-wide
            # MXU matmul (the fused single-scan form must project deep
            # layers inside the loop, re-streaming their wx every
            # iteration — r4 measurement showed that stream, not the
            # iteration count, is what the scan pays for)
            cur = x
            for l in range(L):
                xp = jnp.einsum(
                    "bsd,dk->bsk", cur.astype(cdt),
                    params[f"wx{l}"].astype(cdt),
                    preferred_element_type=jnp.float32) \
                    + params[f"bias{l}"]
                cur = _recurrent_scan(self.model, xp,
                                      params[f"wh{l}"].astype(cdt), cdt,
                                      op=self)
            return [cur.astype(x.dtype)]
        # layer 0's input projection still happens as ONE big MXU matmul
        # outside the loop; deeper layers' inputs are produced inside the
        # iteration and project there
        xproj0 = jnp.einsum("bsd,dk->bsk", x.astype(cdt),
                            params["wx0"].astype(cdt),
                            preferred_element_type=jnp.float32) \
            + params["bias0"]
        b = x.shape[0]
        whc = [params[f"wh{l}"].astype(cdt) for l in range(L)]
        wxc = [None] + [params[f"wx{l}"].astype(cdt) for l in range(1, L)]
        biases = [None] + [params[f"bias{l}"] for l in range(1, L)]
        zeros = jnp.zeros((b, h), jnp.float32)
        carry0 = tuple((zeros, zeros) for _ in range(L))

        def cell(carry, xp0):
            new_carry = []
            inp = None   # layer l>0 input = layer l-1's fresh h
            for l in range(L):
                hprev, cprev = carry[l]
                if l == 0:
                    gates = xp0
                else:
                    gates = jnp.dot(inp.astype(cdt), wxc[l],
                                    preferred_element_type=jnp.float32) \
                        + biases[l]
                gates = gates + jnp.dot(hprev.astype(cdt), whc[l],
                                        preferred_element_type=jnp.float32)
                i, f, g, o = jnp.split(gates, 4, axis=-1)
                i, f, o = (jax.nn.sigmoid(i), jax.nn.sigmoid(f),
                           jax.nn.sigmoid(o))
                g = jnp.tanh(g)
                c = f * cprev + i * g
                hcur = o * jnp.tanh(c)
                new_carry.append((hcur, c))
                inp = hcur
            return tuple(new_carry), inp

        _, hs = lax.scan(cell, carry0, jnp.swapaxes(xproj0, 0, 1))
        return [jnp.swapaxes(hs, 0, 1).astype(x.dtype)]

    def candidate_parallel_configs(self, num_devices, feasible_degrees):
        return _lstm_candidate_configs(self.hidden, num_devices,
                                       feasible_degrees)

    def param_axes(self, pc: ParallelConfig, out_axes, raw_pc=None):
        ch = out_axes[2] if len(out_axes) >= 3 else ()
        # deep layers' wx contract over the hidden dim, which the TP
        # sharding splits: keep those replicated (only layer 0's input
        # dim is sharding-free); wh/bias shard their gate columns
        axes = {}
        for layer in range(self.num_layers):
            axes[f"wx{layer}"] = ((), ch) if layer == 0 else ((), ())
            axes[f"wh{layer}"] = ((), ch)
            axes[f"bias{layer}"] = (ch,)
        return axes

    def param_shard_shapes(self, pc: ParallelConfig, ndev=None):
        dc = pc.degrees[2] if len(pc.degrees) > 2 else 1
        shapes = {n_: list(d.shape)
                  for n_, d in self.param_defs().items()}
        if dc > 1:
            for n_ in shapes:
                if n_.startswith("wx") and n_ != "wx0":
                    continue
                shapes[n_][-1] = max(shapes[n_][-1] // dc, 1)
        return {n_: tuple(v) for n_, v in shapes.items()}

    def flops_per_sample(self) -> float:
        s = self.inputs[0].shape[1]
        h = self.hidden
        total = 4 * h * (self.in_dim + h)
        total += (self.num_layers - 1) * 4 * h * (h + h)
        return 2.0 * s * total

    def sequential_steps(self, pc=None, vmem_bytes: int = 0) -> int:
        # one fused scan of seq iterations — or, on the resident-kernel
        # path, num_layers scans of seq iterations each (the overhead
        # floor is ~10 us/iteration either way; weight traffic decides)
        s = int(self.inputs[0].shape[1])
        if self.scan_weights_resident(pc, vmem_bytes):
            return s * self.num_layers
        return s

    def scan_weights_resident(self, pc=None, vmem_bytes: int = 0) -> bool:
        b, s, _ = self.inputs[0].shape
        if pc is not None:
            return _resident_route_ok_candidate(self.model, b, self.hidden,
                                                s, pc, vmem_bytes)
        return _resident_route_ok(self.model, self, b, self.hidden, s)

    def scan_param_stream_bytes(self) -> int:
        # fused single-scan form: every layer's wh rides in the loop,
        # plus deep layers' wx (their inputs are produced inside the
        # iteration; only layer 0's projection hoists)
        h = self.hidden
        wh = self.num_layers * h * 4 * h * 4
        wx_deep = (self.num_layers - 1) * h * 4 * h * 4
        return wh + wx_deep
