"""TPU cost model for the strategy-search simulator.

Parity with the reference device model (reference: include/simulator.h:29-129,
src/runtime/simulator.cu:21-76 — per-GPU compute devices plus comm devices
with fixed bandwidths: inter-GPU 20 MB/ms, inter-node 12/numNodes, GPU⇄DRAM
16, simulator.cu:27-29; per-op times measured by running the real kernels,
memoized by (op, config) hash, simulator.cc:235-273).

TPU redesign: per-op compute time is a roofline estimate —
max(FLOPs / MXU_rate, bytes_touched / HBM_bw) — optionally *calibrated* by
timing the op's compiled XLA subgraph on the real chip (cost_model
measure=True), which replaces the reference's cudaEvent microbenchmarks.
XLA fuses ops, so isolated-op timing over-counts; the analytical model is
the default and measured times refine it (SURVEY.md §7 hard-part #3).
Comm time uses ICI/DCN bandwidths instead of the reference's constants.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from ..core.op import InputOp, Op
from ..parallel.pconfig import ParallelConfig
from ..utils.logging import log_sim

# Per-train-step dispatch overhead: round-5 value (500-step pipelined
# windows; the additive share fitting the 12 calibration points — see
# per_step_overhead_s below, which this pins), not re-measured on the
# attached chip (ROADMAP S1/S7). It is not a floor: the driver's own
# round-5 record of dlrm_random b256 is a 0.424 ms step, under this
# value (ROADMAP S1). benchmarks/calibrate_sim.py re-measures it per
# sweep (the K→∞ intercept of the bench_superstep ms/step-vs-1/K line,
# written to benchmarks/dispatch_floor.json); no such record is in the
# tree. On the attached v5e one dispatch of the MLPerf DLRM step at batch
# 128 costs fit()'s host 0.48 ms (`train/dispatch`, PERF.md §5, PR 33's
# chip run: 86 us of Python, 275 of PJRT_LoadedExecutable_Execute, 114
# of jax's argument and result handling) beside 0.116 ms of device work:
# the same order as this value, which stays the calibration's. The
# simulator divides it by the K fit() would run (under the default
# `superstep="auto"`: the K of a host-paced verdict of fit()'s own
# probe, else 1; FFModel.resolve_superstep).
MEASURED_DISPATCH_FLOOR_S = 5.5e-4

# fraction of a PIPELINED (ParallelConfig.overlap) row-shard exchange
# XLA's async collective scheduler actually hides under independent
# dense compute, when such a window exists.
# benchmarks/calibrate_sim.measure_overlap_window can measure it (ratio
# of the step speedup to the exchange time it could have hidden) into
# benchmarks/overlap_calibration.json, which overrides this default at
# load. 0.85 is a pinned default, never measured on more than one real
# chip (the committed overlap_calibration.json says so of itself;
# ROADMAP S4). The reasoning behind it: the last rounds' results feed
# the immediately-following gather and cannot move off the critical path.
OVERLAP_EFFICIENCY_DEFAULT = 0.85

_OVERLAP_CAL_CACHE = {"loaded": False, "data": None}


def load_overlap_calibration() -> Optional[dict]:
    """The committed overlap-window calibration artifact
    (benchmarks/overlap_calibration.json), or None when absent. Cached
    after the first read — the cost model consults it inside the MCMC
    hot loop."""
    if not _OVERLAP_CAL_CACHE["loaded"]:
        import json
        import os
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            "benchmarks", "overlap_calibration.json")
        data = None
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = None
        _OVERLAP_CAL_CACHE["data"] = data
        _OVERLAP_CAL_CACHE["loaded"] = True
    return _OVERLAP_CAL_CACHE["data"]


@dataclass
class TPUSpec:
    """Per-chip hardware model. Defaults are TPU v5e (public numbers:
    197 bf16 TFLOP/s MXU, 819 GB/s HBM, 4 ICI links × ~50 GB/s per
    direction; DCN ~ 25 GB/s per host)."""

    name: str = "v5e"
    mxu_flops: float = 197e12         # bf16 FLOP/s
    mxu_flops_f32: float = 49e12      # fp32 FLOP/s
    hbm_bytes_per_s: float = 819e9
    ici_bytes_per_s: float = 45e9     # per link per direction
    ici_links: int = 4
    dcn_bytes_per_s: float = 25e9
    mxu_utilization: float = 0.55     # achievable fraction on real workloads
    hbm_utilization: float = 0.75
    kernel_launch_s: float = 2e-6     # per-HLO overhead (XLA fused ≈ small)
    hbm_capacity_bytes: float = 16e9  # v5e HBM per chip
    vmem_bytes: int = 128 * 1024 * 1024  # per-core VMEM (v4+ generations)
    # scalar memory, where a kernel's prefetched operands live: what the
    # v5e's compiler says it has when one does not fit (PR 37)
    smem_bytes: int = 1024 * 1024
    # RANDOM HBM row-access model (embedding gather/scatter): fixed setup
    # plus per-row sustained cost. RE-PINNED round 5 (the round-2 numbers
    # were poisoned by the dynamic-roll bottleneck that sat in the same
    # measured path): in-graph XLA gathers of fresh random 512 B rows
    # from a 2 GB table measure 489 µs @ 2k rows, 847 µs @ 8k, 1.06 ms @
    # 32k, 1.58 ms @ 128k — a ~0.5 ms setup plus ~10 ns/row sustained
    # (HBM bank parallelism + deep DMA pipelining; the old 0.3 µs/row
    # figure was off 25x).
    # (the ~0.5 ms setup seen by an ISOLATED in-scan gather is mostly
    # loop artifact — in composed graphs gathers overlap surrounding
    # work, so the modeled fixed cost is far smaller)
    hbm_random_fixed_s: float = 0.5e-4
    hbm_random_row_s: float = 1.2e-8
    # random-row SCATTER (the touched-rows update): per-raw-lookup cost
    # of the whole update machinery — lane pack + dedup sort + the
    # scatter kernel — measured r5 on kaggle (26k lookups, 2.7 ms step)
    # and dlrm_random, when the kernel drained its write DMAs every 64
    # rows; ~2x the pipelined gather rate because the sort/pack passes
    # ride along, not because the writes themselves are slow. Since
    # PR 27 the kernel pipelines blocks of 256 (`_scatter_block`) at
    # 4-8 ns a valid row; the value stays until `sim_step_ratio`
    # (ROADMAP R0.7) judges it against a cell
    hbm_scatter_row_s: float = 2.6e-8
    # per-TRAIN-STEP overhead (dispatch + epilogue) at steady pipelined
    # state. Round-5 value, not re-measured on the attached chip
    # (ROADMAP S1/S7): a one-dense-layer model's full train step took
    # ~820 µs then (500-step windows), while a compute-heavier graph
    # (mlp_heavy, 794 µs total) showed device work partially HIDING
    # under the host-side overhead — ~550 µs was the additive share that
    # fit the 12 calibration points; without it every small-step model
    # under-predicted (the r4 measured-mode DLRM-family bias)
    per_step_overhead_s: float = MEASURED_DISPATCH_FLOOR_S
    # host-resident tables: PCIe host<->device link and host-DRAM random
    # row cost (the reference prices GPU<->DRAM at 16 MB/ms,
    # simulator.cu:27-29; v5e host link ~ PCIe gen3/4)
    pcie_bytes_per_s: float = 16e9
    # host DDR random row access is SLOWER than HBM random access (~60-100
    # ns DRAM latency, no HBM bank parallelism); pricing it cheaper would
    # make the simulator prefer host tables over HBM tables, inverting the
    # measured reality (benchmarks/bench_host_tables.py)
    host_random_row_s: float = 6.0e-7
    host_bytes_per_s: float = 50e9    # host DDR sequential stream
    # per-ROUND overhead of the pipelined (decomposed) row-shard
    # exchange: each ppermute ring hop / capacity chunk is its own
    # collective-start/-done pair, so decomposing a fused all-to-all
    # into k rounds pays k extra launches plus the scheduler's fence
    # bookkeeping. A pinned default, never measured on real ICI
    # (benchmarks/overlap_calibration.json overrides; ROADMAP S4); THE
    # term that
    # makes overlap lose when there is no compute window to hide in —
    # without it the search would flip overlap on everywhere for free
    overlap_round_overhead_s: float = 8e-6
    # fixed OVERHEAD per serial scan iteration (lax.scan bookkeeping +
    # carry round-trip), on top of the cell's own FLOP/bandwidth cost.
    # PINNED by direct measurement (round 4): an NMT-sized cell (b64,
    # h1024, bf16) costs ~32 us/iteration marginal, of which ~27 us is
    # the cell's HBM weight re-stream (priced separately in
    # _roofline_time's scan term) — the residual loop overhead is ~5 us;
    # 10 us keeps a margin for smaller cells where bookkeeping dominates
    scan_iter_s: float = 1.0e-5

    def per_step_overhead_amortized(self, superstep: int = 1) -> float:
        """Dispatch floor per TRAINED step when K steps fuse into one
        dispatch (core/model.py _train_superstep: a lax.scan over K
        pre-staged batches inside one executable). One host→device
        dispatch then trains K steps, so the per-step share of the floor
        is ``per_step_overhead_s / K`` — the simulator must price this
        or it would call every floor-bound small-batch config K× slower
        than the fused runtime actually runs it."""
        return self.per_step_overhead_s / max(int(superstep), 1)

    @staticmethod
    def v4() -> "TPUSpec":
        return TPUSpec(name="v4", mxu_flops=275e12, mxu_flops_f32=69e12,
                       hbm_bytes_per_s=1228e9, ici_bytes_per_s=50e9,
                       ici_links=6, hbm_capacity_bytes=32e9)

    def apply_env_overrides(self) -> "TPUSpec":
        """Honor FF_ICI_GBPS / FF_DCN_GBPS (GB/s, per link / per host):
        pod-pricing knobs so a strategy search for a machine with a
        different interconnect needs no code edit. Strict parsing (the
        FLX401 contract): a malformed value raises naming the variable
        instead of silently running with defaults."""
        import os

        from ..utils.faults import _env_float
        for var, attr in (("FF_ICI_GBPS", "ici_bytes_per_s"),
                          ("FF_DCN_GBPS", "dcn_bytes_per_s")):
            raw = os.environ.get(var)
            if raw is not None and raw != "":
                val = _env_float(var, raw)
                if val <= 0:
                    raise ValueError(
                        f"{var} must be a positive bandwidth in GB/s, "
                        f"got {raw!r}")
                setattr(self, attr, val * 1e9)
        return self

    @staticmethod
    def detect() -> "TPUSpec":
        """The spec of the attached TPU, matched by `device_kind`, with
        the FF_ICI_GBPS/FF_DCN_GBPS overrides applied. A TPU kind the
        table does not know is an error, not a default. Off-TPU (the
        CPU mesh of tests, offline `optimize(ndev=N)` planning) the v5e
        numbers stand in as the documented planning target."""
        import jax
        dev = jax.devices()[0]
        kind = dev.device_kind.lower()
        if dev.platform != "tpu" or "v5 lite" in kind or "v5e" in kind:
            spec = TPUSpec()
        elif "v4" in kind:
            spec = TPUSpec.v4()
        elif "v5p" in kind:
            spec = TPUSpec(name="v5p", mxu_flops=459e12,
                           mxu_flops_f32=115e12, hbm_bytes_per_s=2765e9,
                           ici_bytes_per_s=100e9, ici_links=6,
                           hbm_capacity_bytes=95e9)
        elif "v6" in kind:
            spec = TPUSpec(name="v6e", mxu_flops=918e12,
                           mxu_flops_f32=230e12, hbm_bytes_per_s=1640e9,
                           ici_bytes_per_s=90e9, ici_links=4,
                           hbm_capacity_bytes=32e9)
        else:
            raise ValueError(
                f"no TPUSpec for device_kind {dev.device_kind!r}: add its "
                f"published peaks to TPUSpec.detect before planning for it")
        return spec.apply_env_overrides()


class CostModel:
    """Per-op/per-config compute and comm times, memoized like the
    reference's hash-keyed measurements (simulator.cc:241-249)."""

    def __init__(self, spec: Optional[TPUSpec] = None,
                 compute_dtype=jnp.bfloat16, measure: bool = False):
        self.spec = spec or TPUSpec()
        self.compute_dtype = compute_dtype
        self.measure = measure
        self._cache: Dict[Tuple, float] = {}

    # ---- helpers --------------------------------------------------------
    def _flops_rate(self) -> float:
        rate = (self.spec.mxu_flops
                if jnp.dtype(self.compute_dtype) == jnp.dtype(jnp.bfloat16)
                else self.spec.mxu_flops_f32)
        return rate * self.spec.mxu_utilization

    def _hbm_rate(self) -> float:
        return self.spec.hbm_bytes_per_s * self.spec.hbm_utilization

    @staticmethod
    def _shard_elems(op: Op, pc: ParallelConfig) -> float:
        t = op.outputs[0]
        return math.prod(t.shape) / max(pc.num_parts, 1)

    # ---- per-op compute -------------------------------------------------
    def op_compute_time(self, op: Op, pc: ParallelConfig,
                        backward: bool = False) -> float:
        """Roofline time for one device's shard of `op` (seconds)."""
        # residency/device-type must key the cache: a ZCM config and an
        # HBM config with equal degrees have sharply different costs, and
        # MCMC rewrite proposals compare exactly such pairs (the PARAM-
        # axis row-shard degree — and its skew policies — likewise
        # change the update/comm shape)
        key = (op.name, pc.degrees, getattr(pc, "param_degree", 1),
               getattr(pc, "exchange", "dense"),
               getattr(pc, "hot_fraction", 0.0),
               getattr(pc, "overlap", False),
               pc.device_type, pc.memory_types, backward)
        if key in self._cache:
            return self._cache[key]

        if self.measure:
            # calibrated mode: time the op's compiled subgraph on the real
            # device (reference measures forward AND backward separately,
            # linear.cu:973-1049 / simulator.cc:235-273) — BLENDED with
            # the calibrated roofline: a sub-ms op's measurement can carry
            # multiples of dispatch noise (or
            # run degenerately fast), so a raw reading that strays beyond
            # a 2x band around the roofline is evidence of measurement
            # failure, not of the op's true cost. Clamping to the band
            # keeps measured mode at-least-roofline-grade (validated on
            # benchmarks/sim_calibration.json; round-2's unclamped mode
            # was WORSE than the roofline it was meant to refine).
            t_raw = self.measure_op(op, pc, backward=backward)
            t_roof = self._roofline_time(op, pc, backward)
            # scanned ops keep a somewhat wider band: their roofline is
            # calibrated (r4: scan weight re-stream priced, scan_iter_s
            # pinned by measurement) but serial scans still measure
            # noisier than single kernels on a shared chip
            band = (3.0 if op.sequential_steps(pc, self.spec.vmem_bytes)
                    else 2.0)
            t = min(max(t_raw, t_roof / band), band * t_roof)
            if t != t_raw:
                log_sim.debug(
                    "measured %s %s bwd=%s: %.3es outside the roofline "
                    "band [%.3es, %.3es]; clamped",
                    op.name, pc.degrees, backward, t_raw,
                    t_roof / band, band * t_roof)
        else:
            t = self._roofline_time(op, pc, backward)
        self._cache[key] = t
        return t

    @staticmethod
    def _host_resident(op: Op, pc: ParallelConfig) -> bool:
        """True only for host-RESIDENT tables (ZCM memory). A bare CPU
        device_type without ZCM is compute-offload — its tables still
        live in HBM and MUST count against capacity."""
        if not hasattr(op, "host_lookup"):
            return False
        if op.name in getattr(op.model, "_host_resident_ops", set()):
            return True
        return "ZCM" in pc.memory_types

    def _roofline_time(self, op: Op, pc: ParallelConfig,
                       backward: bool = False) -> float:
        if self._host_resident(op, pc):
            # forward: host gather (DRAM random rows) + rows over PCIe
            # down; backward: cotangents staged host-ward over PCIe — the
            # touched-rows scatter itself is priced on the UPDATE task
            # (simulator._build_tasks), not here, so it isn't charged twice
            out_bytes = self.tensor_bytes(op.outputs[0])
            t = (self.spec.hbm_random_fixed_s
                 + out_bytes / self.spec.pcie_bytes_per_s)
            if not backward:
                t += (op.random_hbm_rows(False, raw=True)
                      * self.spec.host_random_row_s)
            return t
        batch = op.outputs[0].shape[0] if op.outputs[0].num_dims > 0 else 1
        flops = op.flops_per_sample() * batch / max(pc.num_parts, 1)
        # bytes: inputs read + outputs written (+ params read), sharded;
        # dtype-aware (activations stream at compute-dtype width)
        io_bytes = sum(self.tensor_bytes(t) for t in op.inputs)
        io_bytes += self.tensor_bytes(op.outputs[0])
        io_bytes /= max(pc.num_parts, 1)
        # params: bytes this shard actually streams per step (a sparse-
        # update embedding touches only its gathered rows, not the
        # multi-GB table)
        p_touch = op.param_bytes_touched_per_step(max(pc.num_parts, 1))
        io_bytes += p_touch
        steps = op.sequential_steps(pc, self.spec.vmem_bytes)
        if steps > 1 and not op.scan_weights_resident(
                pc, self.spec.vmem_bytes):
            # a serial scan re-streams its IN-LOOP weights from HBM on
            # EVERY iteration (measured round 4: the NMT LSTM cell's
            # marginal per-iteration wall time ≈ its bf16 weight-stream
            # time — XLA does not pin scan weights in VMEM at these
            # sizes; the pallas resident kernel does, and then skips
            # this). Only scan_param_stream_bytes counts — hoisted
            # input projections stream once. (steps - 1) extra passes
            # at compute-dtype width (the 4 B fp32 master read is
            # already counted once above)
            stream = op.scan_param_stream_bytes()
            itemsize = jnp.dtype(self.compute_dtype).itemsize
            io_bytes += (steps - 1) * stream * (itemsize / 4.0)
        io_bytes *= op.hbm_io_factor()
        if backward:
            # bwd ≈ 2x fwd flops (dX and dW gemms), grads written.
            # For scanned ops the dX chain re-streams weights like the
            # forward scan, but dW is ONE stacked gemm over all
            # timesteps (XLA's scan vjp stacks the residuals), so bwd
            # io ≈ 1.25x fwd, not 2x (measured r4: NMT bwd ≈ 1.15x fwd)
            flops *= 2.0
            io_bytes *= 1.25 if steps > 1 else 2.0
        rate = self._flops_rate() * op.mxu_utilization_factor()
        t = max(flops / rate, io_bytes / self._hbm_rate())
        # random-row HBM accesses (embedding gathers) are latency-bound,
        # not bandwidth-bound — the dominant term for sparse ops
        rand_rows = op.random_hbm_rows(backward) / max(pc.num_parts, 1)
        if (not backward and rand_rows > 0
                and getattr(pc, "param_degree", 1) > 1
                and hasattr(op, "_row_shard_geometry")
                and (getattr(pc, "exchange", "dense") == "dedup"
                     or getattr(pc, "hot_fraction", 0.0) > 0)):
            # skew-aware routed gather: owners gather one row per
            # DISTINCT routed id (dedup collapses duplicates before the
            # exchange; hot lookups hit the small replicated hot block,
            # which streams like the tiny tables above)
            from ..ops.embedding import (_lookup_count,
                                         expected_routed_lookups)
            n_dev = _lookup_count(op) / max(pc.num_parts, 1)
            rand_rows = min(rand_rows,
                            expected_routed_lookups(op, pc, n_dev))
        t = max(t, self.random_rows_time(rand_rows))
        # serial scan iterations floor at the per-iteration loop
        # overhead; the vjp of a scan runs its own reverse-order scan
        if steps:
            t = max(t, steps * self.spec.scan_iter_s)
        return t + self.spec.kernel_launch_s

    def host_update_time(self, op: Op, pc: ParallelConfig) -> float:
        """Update cost for a host-RESIDENT (ZCM) table. Pairs with the
        host branch of _roofline_time: the touched-rows scatter is priced
        HERE (on the update task) and nowhere else, so forward/backward
        must not charge it. Host DRAM is one shared resource — rows are
        not divided by num_parts."""
        if op.update_random_hbm_rows(pc) > 0:
            # sparse path: host RMW scatter = 2 accesses per looked-up
            # row (read + write; the 1.6x write-only discount is
            # structural to the Pallas lane-packed TPU path and does not
            # exist on the host), plus read+write per optimizer state
            # slab — mirrors the device path's _embedding_update_rows
            opt = getattr(op.model, "optimizer", None)
            nslabs = len(opt.sparse_slab_names()) if opt is not None else 0
            rows = (2.0 + 2.0 * nslabs) * op.random_hbm_rows(False,
                                                             raw=True)
            return (self.spec.hbm_random_fixed_s
                    + rows * self.spec.host_random_row_s)
        # dense fallback (momentum/Adam without sparse state): stream the
        # FULL table read+write+state through host DDR, at each param's
        # DECLARED dtype (a bf16 table streams half the fp32 bytes —
        # hardcoding 4 B over-billed it)
        full_bytes = sum(
            math.prod(d.shape) * jnp.dtype(d.dtype).itemsize
            for d in op.param_defs().values())
        return full_bytes * 3.0 / self.spec.host_bytes_per_s

    def dedup_overhead_time(self, op, ndev: int) -> float:
        """Sender-side cost of the dedup-before-exchange machinery
        (parallel/alltoall.py): two stable sorts + segment sums over
        the local lookup ids (~8 streaming passes of 4 B each) plus one
        gather/scatter of the returned rows through the inverse map.

        THE term that makes dedup lose on uniform ids: the exchange
        barely shrinks (every id is distinct) but the sort still runs
        every step — so the MCMC search only picks the dedup'd exchange
        when the observed histogram's duplicate mass pays for it
        (README troubleshooting: "dedup slower than dense on uniform
        ids")."""
        from ..ops.embedding import _lookup_count
        n_dev = _lookup_count(op) / max(ndev, 1)
        d = getattr(op, "out_dim", 0)
        isz = jnp.dtype(self.compute_dtype).itemsize
        bytes_ = 8.0 * n_dev * 4.0 + 2.0 * n_dev * d * isz
        return bytes_ / self._hbm_rate()

    def overlap_efficiency(self) -> float:
        """Fraction of a pipelined exchange the async scheduler hides
        under independent compute — the calibrated value
        (benchmarks/overlap_calibration.json, written by
        calibrate_sim.measure_overlap_window) or the pinned default. Clamped to [0, 1): a measured value >= 1 would price
        overlapped exchanges as free and below-zero would price them
        slower than serial, both measurement artifacts."""
        cal = load_overlap_calibration()
        eff = OVERLAP_EFFICIENCY_DEFAULT
        if cal and isinstance(cal.get("overlap_efficiency"), (int, float)):
            eff = float(cal["overlap_efficiency"])
        return min(max(eff, 0.0), 0.99)

    def overlap_round_overhead(self, rounds: int) -> float:
        """Fixed cost of DECOMPOSING one fused exchange into `rounds`
        independent collectives (ppermute ring hops / capacity chunks):
        each round is its own collective-start/-done pair. Charged on
        the participating compute devices — it is host/scheduler work
        that does not hide."""
        cal = load_overlap_calibration()
        per = self.spec.overlap_round_overhead_s
        if cal and isinstance(cal.get("round_overhead_s"), (int, float)):
            per = float(cal["round_overhead_s"])
        return max(int(rounds), 0) * per

    def exposed_exchange_time(self, exchange_s: float,
                              window_s: float,
                              overlap: bool,
                              rounds: int = 0) -> float:
        """THE overlap term (ISSUE 19): the exchange time a step still
        PAYS given an exposed-compute window of `window_s` (compute with
        no data dependence on the exchange, which the async scheduler
        can run under it). Serial exchanges pay everything; pipelined
        ones hide `overlap_efficiency` of the window's worth and pay
        the decomposition overhead. shardcheck's FLX514 and the
        simulator's schedule both derive from this accounting."""
        if not overlap:
            return float(exchange_s)
        eff = self.overlap_efficiency()
        hidden = eff * min(float(window_s), float(exchange_s))
        return (float(exchange_s) - hidden
                + self.overlap_round_overhead(rounds))

    def random_rows_time(self, rows: float) -> float:
        if rows <= 0:
            return 0.0
        return (self.spec.hbm_random_fixed_s
                + rows * self.spec.hbm_random_row_s)

    def scatter_rows_time(self, rows: float) -> float:
        """Touched-rows UPDATE scatter: same fixed setup, slower per-row
        sustained rate (the dedup's sort and segment passes ride along
        with the Pallas kernel's pipelined blocks, `_scatter_block`)."""
        if rows <= 0:
            return 0.0
        return (self.spec.hbm_random_fixed_s
                + rows * self.spec.hbm_scatter_row_s)

    def tensor_bytes(self, t) -> float:
        """Dtype-aware byte size: float activations flow in the model's
        compute dtype (bf16 halves comm/IO vs the old flat 4 B/elem);
        integer tensors (indices) keep their declared dtype."""
        dt = jnp.dtype(t.dtype)
        if jnp.issubdtype(dt, jnp.floating):
            dt = jnp.dtype(self.compute_dtype)
        return float(math.prod(t.shape)) * dt.itemsize

    # ---- comm -----------------------------------------------------------
    # The reference prices inter-GPU and inter-node transfers distinctly
    # (simulator.cu:27-29: 20 MB/ms NVLink, 12/numNodes MB/ms inter-node)
    # and gives each GPU its own comm devices (simulator.cu:21-76). The
    # TPU analog: per-MESH-AXIS channels — a collective over an "ici" axis
    # rides that axis's torus links at ring-allreduce bandwidth, a
    # collective over the "dcn" (multi-slice) axis rides the data-center
    # network. Collectives on different axes use disjoint links and run
    # concurrently; collectives on the same axis contend (the Simulator
    # serializes them on the axis's channel).

    def axis_bw(self, kind: str) -> float:
        if kind == "dcn":
            return self.spec.dcn_bytes_per_s
        # bidirectional ring over ICI: effective algorithm bandwidth
        return self.spec.ici_bytes_per_s * self.spec.ici_links

    def allreduce_time_axes(self, bytes_per_dev: float, axes) -> float:
        """Hierarchical ring all-reduce over `axes` = [(kind, size), ...]:
        phase i moves 2·B·(n−1)/n at its axis's bandwidth, with B shrinking
        by each completed phase's factor (reduce-scatter hierarchy)."""
        t, b = 0.0, float(bytes_per_dev)
        for kind, size in axes:
            if size <= 1:
                continue
            t += 2.0 * b * (size - 1) / size / self.axis_bw(kind)
            b /= size
        return t

    def _ici_allreduce_bw(self) -> float:
        return self.axis_bw("ici")

    def alltoall_time_axes(self, bytes_per_dev: float, axes) -> float:
        """All-to-all over `axes` = [(kind, size), ...]: each device
        exchanges (size−1)/size of its `bytes_per_dev` payload with its
        peers along that axis at the axis's bandwidth — the lookup/row
        exchange of row-sharded embedding tables. Hierarchical like
        allreduce_time_axes: a multi-axis shard group pays each axis's
        phase on that axis's channel."""
        t, b = 0.0, float(bytes_per_dev)
        for kind, size in axes:
            if size <= 1:
                continue
            t += b * (size - 1) / size / self.axis_bw(kind)
        return t

    def resharding_time(self, tensor_bytes: float, src_pc: ParallelConfig,
                        dst_pc: ParallelConfig,
                        kind: str = "ici") -> float:
        """Cost of moving a tensor from the producer's sharding to the
        consumer's (the reference gets this implicitly from Legion region
        intersections, simulator.cc:279-326; GSPMD emits collectives).
        `kind` picks the channel the move rides ("dcn" when the redistri-
        bution crosses the slice axis). PARAM-axis (row-shard) degrees
        count as parts too: resharding a row-sharded table (elastic
        recovery) is an all-to-all of the row blocks."""
        pd_s = max(getattr(src_pc, "param_degree", 1), 1)
        pd_d = max(getattr(dst_pc, "param_degree", 1), 1)
        if src_pc.degrees == dst_pc.degrees and pd_s == pd_d:
            return 0.0
        # approximate: every device re-reads its destination shard from
        # peers — an all-to-all of the full tensor over the channel
        moved = tensor_bytes * (1.0 - 1.0 / max(src_pc.num_parts * pd_s,
                                                dst_pc.num_parts * pd_d,
                                                1))
        return moved / self.axis_bw(kind)

    def grad_sync_time(self, param_bytes: float, replicas: int,
                       kind: str = "ici") -> float:
        """All-reduce of a parameter's gradient across `replicas`
        data-parallel parts (reference: replica regions gathered into the
        optimizer task, optimizer_kernel.cu:98-104; here a psum ring)."""
        if replicas <= 1:
            return 0.0
        moved = 2.0 * param_bytes * (replicas - 1) / replicas
        return moved / self.axis_bw(kind)

    # ---- measured calibration ------------------------------------------
    # in-graph repetitions per measurement: per-op resolution needs an
    # in-graph loop long enough to amortize dispatch jitter below the op
    # times being measured (round-5 value, not re-tuned on the attached
    # chip, ROADMAP S1/S7)
    _REPEATS = 128

    def _dispatch_overhead(self) -> float:
        """One-time estimate of per-dispatch wall overhead (harness
        overhead, not kernel time: it is subtracted)."""
        key = ("dispatch_overhead",)
        if key in self._cache:
            return self._cache[key]
        import time

        import jax
        f = jax.jit(lambda x: x + 1.0)
        x = jnp.zeros((8,), jnp.float32)
        float(f(x)[0])
        # SAME pattern as _time_fn's timed runs — one dispatch + dependent
        # readback per sample — so the full dispatch-and-readback latency
        # is what gets subtracted
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            float(f(x)[0])
            times.append(time.perf_counter() - t0)
        dt = sorted(times)[2]
        self._cache[key] = dt
        return dt

    def _time_fn(self, make_out, params, xs, int_rows: int = 0) -> float:
        """Median-of-3 wall time of ONE application of `make_out`, measured
        as an in-graph lax.scan of N applications inside a single dispatch
        (the XLA analog of the reference's warmup-5/repeat-10 raw kernel
        loops, simulator.cu:25). The scan body perturbs a float input with
        the carry so XLA cannot hoist the op out of the loop. N adapts so
        the loop wall time dwarfs the per-dispatch overhead, which would
        otherwise swamp sub-ms ops.

        `int_rows` > 0 rotates every integer input over [0, int_rows) by a
        per-iteration multiplicative hash: a sparse op re-gathering the
        SAME index set N times sees warm HBM row locality and measures
        well below its fresh-random-rows cost — the round-4 artifact's
        systematic −20…−32% DLRM-family under-prediction. Real steps see
        fresh indices every batch, so the measurement must too."""
        import math as _math
        import time

        import jax

        def loop_fn(n):
            def loop(p, xs_):
                def body(acc, it):
                    # a data dependence the compiler cannot remove, at
                    # negligible cost: float operands get +tiny·acc; int
                    # operands (embedding indices) rotate per-iteration
                    # (or get a data-dependent zero) — NEVER perturb
                    # params (adding eps to a multi-GB table would stream
                    # it every iteration and swamp the op being measured)
                    eps = (acc * 1e-38).astype(jnp.float32)
                    izero = jnp.where(acc > 3e38, 1, 0).astype(jnp.int32)
                    pxs, bumped = [], False
                    for x in xs_:
                        if int_rows > 0 and jnp.issubdtype(x.dtype,
                                                           jnp.integer):
                            # Knuth multiplicative rotation: uniform-ish
                            # fresh rows every iteration, same range
                            x = ((x.astype(jnp.uint32)
                                  + it.astype(jnp.uint32)
                                  * jnp.uint32(2654435761))
                                 % jnp.uint32(int_rows)).astype(x.dtype)
                            bumped = True
                            pxs.append(x)
                            continue
                        if not bumped and jnp.issubdtype(x.dtype,
                                                         jnp.floating):
                            x = x + eps.astype(x.dtype)
                            bumped = True
                        elif not bumped and jnp.issubdtype(x.dtype,
                                                           jnp.integer):
                            x = x + izero.astype(x.dtype)
                            bumped = True
                        pxs.append(x)
                    pp = p
                    if not bumped and p:
                        pp = dict(p)
                        k0 = next(iter(pp))
                        pp[k0] = pp[k0] + eps.astype(pp[k0].dtype)
                    out = make_out(pp, pxs)
                    # consume EVERY output leaf FULLY: reading one element
                    # would let XLA slice the computation down to just
                    # that element (conv/dot shrink to a sliver) and, for
                    # vjp outputs, drop whole cotangents — the op being
                    # measured must fully materialize
                    tot = jnp.zeros((), jnp.float32)
                    for leaf in jax.tree.leaves(out):
                        tot = tot + jnp.sum(leaf).astype(jnp.float32)
                    return acc + tot, None

                acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                      jnp.arange(n, dtype=jnp.int32))
                return acc
            return jax.jit(loop)

        def run(f):
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                float(f(params, xs))
                times.append(time.perf_counter() - t0)
            return sorted(times)[1]

        ovh = self._dispatch_overhead()
        n = self._REPEATS
        f = loop_fn(n)
        float(f(params, xs))  # compile + warmup
        dt = run(f)
        # grow the loop until it costs >= 20x the dispatch overhead (one
        # extra compile at most; scan length doesn't affect compile time)
        target = max(20.0 * ovh, 0.2)
        if dt < target:
            n2 = min(int(n * _math.ceil(target / max(dt, 1e-4))), 8192)
            if n2 > n:
                f = loop_fn(n2)
                float(f(params, xs))
                dt, n = run(f), n2
        return max((dt - ovh) / n, 1e-9)

    def measure_op(self, op: Op, pc: ParallelConfig,
                   backward: bool = False) -> float:
        """Time the op's compiled XLA computation for its shard shape on
        the real device (reference Op::measure_compute_time, e.g.
        linear.cu:973-1049: warmup 5 / repeat 10 — forward and backward
        are measured SEPARATELY there too). Backward is measured as
        (fwd+vjp) − fwd on the op subgraph. Memoized."""
        import jax

        key = ("measured", op.name, pc.degrees, pc.device_type,
               pc.memory_types, backward)
        if key in self._cache:
            return self._cache[key]
        # inputs and params are built at the per-device shapes the op
        # declares for this config (the two hooks stay mutually consistent
        # so apply() traces at the sharded shapes)
        shard_shapes = op.input_shard_shapes(pc)
        params = ({n: jnp.zeros(s, jnp.float32)
                   for n, s in op.param_shard_shapes(pc).items()}
                  if op.param_defs() else {})
        # mirror _forward_env: NHWC-opted-in ops see the producer's NHWC
        # physical form; everything else gets logical NCHW
        accepts_nhwc = getattr(op, "_accepts_nhwc_inputs", False)

        def _phys(s, t):
            if (accepts_nhwc and len(s) == 4
                    and getattr(t, "physical", None) == "nhwc"):
                return (s[0], s[2], s[3], s[1])
            return s
        # integer inputs are lookup indices: zeros would hit row 0 every
        # iteration and hide the random-HBM-row latency that dominates
        # sparse ops — fill them with seeded uniform rows over the table
        # range instead (reference measures with the app's real batches)
        import numpy as _np
        rows = int(getattr(op, "num_entries", 0))
        rng = _np.random.RandomState(0)

        def _fill(s, t):
            if rows > 0 and jnp.issubdtype(jnp.dtype(t.dtype), jnp.integer):
                return jnp.asarray(rng.randint(0, rows, size=s),
                                   dtype=t.dtype)
            return jnp.zeros(_phys(s, t), t.dtype)
        xs = [_fill(s, t) for s, t in zip(shard_shapes, op.inputs)]
        try:
            t_fwd = self._time_fn(
                lambda p, xs_: op.apply(p, xs_, training=False), params, xs,
                int_rows=rows)
            if not backward:
                dt = t_fwd
            else:
                def fwdbwd(p, xs_):
                    y, vjp = jax.vjp(
                        lambda p2, x2: op.apply(p2, x2, training=True),
                        p, xs_)
                    return vjp(jax.tree.map(jnp.ones_like, y))
                t_both = self._time_fn(fwdbwd, params, xs, int_rows=rows)
                # floor at the analytical fwd/bwd ratio's spirit: vjp can't
                # be cheaper than re-running forward
                dt = max(t_both - t_fwd, 0.5 * t_fwd)
        except Exception as e:
            # degrade loudly: a silent fallback would let --measure-ops
            # quietly become the roofline it was meant to replace
            dt = self._roofline_time(op, pc, backward)
            log_sim.warning(
                "measure_op(%s, %s, backward=%s) failed (%r); "
                "using roofline %.3es",
                op.name, pc.degrees, backward, e, dt)
        self._cache[key] = dt
        return dt
