"""Event-driven execution simulator for strategy search.

Port of the reference simulation algorithm (reference:
src/runtime/simulator.cc:275-448 — build a task graph of fwd/bwd/comm/
update/barrier SimTasks, then event-driven priority-queue simulation over
compute and comm devices; weight sync modeled either overlapped with
compute or bulk-synchronous behind a barrier, simulator.cc:327-408).

The algorithm is pure logic (no CUDA) and ports directly; what changes is
the device graph. The reference gives each GPU its own comm devices and
prices inter-node hops separately (simulator.cu:21-76, 27-29:
GPU→DRAM→DRAM→GPU at 12/numNodes MB/ms). The TPU analog here:

- one SPMD compute stream per mesh device, and
- one comm channel PER MESH AXIS: a collective over an "ici" axis rides
  that torus dimension's links, a collective over the "dcn" (multi-slice)
  axis rides the data-center network at TPUSpec.dcn_bytes_per_s.
  Collectives on different axes use disjoint links and run concurrently;
  collectives contending for the same axis serialize on its channel —
  replacing round 1's single shared COMM_DEVICE, which serialized
  everything and priced DCN at ICI rates.

Degrees map to axes exactly as parallel.sharding.AxisAssigner does at
compile time (consume consecutive axes in order), so the simulator prices
the same collectives GSPMD will emit. Costs come from search/cost_model.py.
"""

from __future__ import annotations

import heapq
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from ..core.op import InputOp, Op
from ..parallel.pconfig import ParallelConfig, StrategyMap
from .cost_model import CostModel

COMM_DEVICE = -1  # flat-topology fallback channel (axis 0)
HOST_DEVICE = -1000  # host CPU/DRAM: ONE shared resource for all ZCM ops


def hbm_footprint_report(model, cost: CostModel, strategies: StrategyMap,
                         ndev: int) -> Dict[str, float]:
    """Per-op PEAK per-device HBM residency (bytes) a strategy implies:
    parameters at each op's sharded shapes, optimizer state slabs, dense
    gradients, and LIVE ACTIVATIONS (under reverse-mode autodiff every
    op output is live from its forward until its backward, at its
    sharded shape in compute dtype), plus model inputs under the
    "inputs" key. Host-resident tables (CPU/ZCM strategies) live in host
    RAM and don't count — the capability that lets DLRM-Terabyte run on
    few chips (reference dlrm_strategy_hetero.cc:28-49).

    Shared accounting: Simulator.fits_memory sums it for search
    feasibility; the static plan verifier (analysis/shardcheck.py)
    reports it per-op against an ``--hbm-gb`` cap."""
    opt = getattr(model, "optimizer", None)
    nslabs = len(opt.sparse_slab_names()) if opt is not None else 0
    report: Dict[str, float] = {}
    for op in model.ops:
        pc = strategies.get(op.name)
        if isinstance(op, InputOp):
            # batch inputs are device-resident for the whole step;
            # sharded along the sample dim under DP
            report["inputs"] = (report.get("inputs", 0.0)
                                + cost.tensor_bytes(op.outputs[0])
                                / max(ndev, 1))
            continue
        if pc is None:
            continue
        parts = max(pc.num_parts, 1)
        total = cost.tensor_bytes(op.outputs[0]) / parts
        if op.param_defs() and not cost._host_resident(op, pc):
            shapes = op.param_shard_shapes(pc, ndev)
            # stored params at their EFFECTIVE storage bytes: embedding
            # tables under an int8/fp8 policy hold quantized rows + one
            # fp32 scale per row (quant/policy.py — the ~4x HBM lever);
            # the master_weight fp32 master lives host-side beside the
            # optimizer state, not in HBM. Non-table params price at
            # their declared dtype (bf16 tables stop being billed 4 B)
            from ..quant.policy import param_storage_bytes
            param_bytes = param_storage_bytes(op, pc, shapes)
            # momentum/Adam keep param-shaped fp32 state slabs (lazy
            # sparse state is table-shaped too); a dense-updated param
            # also materializes a param-shaped fp32 gradient before its
            # update, while a touched-rows update's gradient is
            # negligible next to the table
            fp32_bytes = sum(math.prod(shape) * 4.0
                             for shape in shapes.values())
            dense_grad = (op.param_bytes_touched_per_step(parts)
                          >= op.param_bytes())
            total += param_bytes + fp32_bytes * (nslabs + (1.0 if
                                                 dense_grad else 0.0))
        report[op.name] = total
    return report


def _axis_kind(name: str) -> str:
    return "dcn" if str(name).startswith("dcn") else "ici"


@dataclass
class SimTask:
    """reference: SimTask in include/simulator.h:29-60."""

    run_time: float
    device: int
    name: str = ""
    ready_time: float = 0.0
    counter: int = 0                  # unresolved dependencies
    next_tasks: List["SimTask"] = field(default_factory=list)

    def add_next(self, t: "SimTask"):
        self.next_tasks.append(t)
        t.counter += 1


class Simulator:
    """Builds the per-iteration task graph for a model + strategy and
    simulates its makespan (reference Simulator::simulate_runtime).

    `topology` describes the simulated machine as [(axis_name, size), ...]
    in AxisAssigner order; axis names starting with "dcn" are priced at
    DCN bandwidth. Default: the model's mesh axes when the mesh matches
    the simulated device count, else one flat ICI axis.
    """

    def __init__(self, model, cost_model: Optional[CostModel] = None,
                 overlap_weight_sync: bool = True,
                 topology: Optional[Sequence[Tuple[str, int]]] = None):
        self.model = model
        self.cost = cost_model or CostModel(
            compute_dtype=model.config.jnp_compute_dtype)
        self.overlap_weight_sync = overlap_weight_sync
        self.topology = list(topology) if topology is not None else None

    def _effective_superstep(self) -> int:
        """The superstep K this model's fit() would actually run NOW
        (FFModel.resolve_superstep: an explicit K; under "auto" the K of
        a host-paced verdict fit()'s own probe has reached for the
        compile-time batch, else 1; the host-resident-table K=1
        fallback), so the simulated dispatch floor amortizes exactly
        like the runtime's: a model that never trained is priced at the
        whole floor. Models without the resolver (config stubs in older
        tests) price the legacy K=1 floor."""
        resolve = getattr(self.model, "resolve_superstep", None)
        if resolve is None:
            return 1
        try:
            return max(int(resolve()), 1)
        except Exception:
            return 1

    # ---- topology ----------------------------------------------------
    def _topo(self, ndev: int) -> List[Tuple[str, int]]:
        if self.topology is not None:
            return self.topology
        mesh = self.model.mesh
        if mesh is not None and mesh.size == ndev:
            return [(a, int(mesh.shape[a])) for a in mesh.axis_names]
        # offline target: the factorization make_mesh would build for
        # ndev, so per-dim axis assignment (and thus collective pricing)
        # matches what compile() on the target will do
        from ..parallel.mesh import structural_axis_sizes
        return [(f"f{i}", s)
                for i, s in enumerate(structural_axis_sizes(ndev))]

    @staticmethod
    def _assign(degrees: Sequence[int],
                topo: Sequence[Tuple[str, int]]
                ) -> Optional[List[Tuple[int, ...]]]:
        """Per-dim axis-index assignment — the SAME algorithm compile-time
        sharding uses (parallel.sharding.assign_indices), so the simulator
        prices exactly the collectives GSPMD will emit."""
        from ..parallel.sharding import assign_indices
        return assign_indices(degrees, [s for _, s in topo])

    @staticmethod
    def _channel(axis_idx: int) -> int:
        """Comm pseudo-device id for a mesh axis (compute devices are >=0)."""
        return -(axis_idx + 1)

    def _reshard_spec(self, src_pc: ParallelConfig, dst_pc: ParallelConfig,
                      topo) -> Optional[Tuple[str, int]]:
        """(kind, channel) the src→dst redistribution rides: the slowest
        axis whose per-dim assignment changes. None = layouts agree.
        Configs that differ on the PARAM (row-shard) axis ride the axes
        the larger row-shard degree occupies — an all-to-all of row
        blocks, NOT the flat-ICI COMM_DEVICE fallback."""
        pd_s = max(getattr(src_pc, "param_degree", 1), 1)
        pd_d = max(getattr(dst_pc, "param_degree", 1), 1)
        if src_pc.degrees == dst_pc.degrees and pd_s == pd_d:
            return None
        sa = self._assign(src_pc.degrees, topo)
        da = self._assign(dst_pc.degrees, topo)
        if sa is None or da is None:
            return ("ici", COMM_DEVICE)
        nd = max(len(sa), len(da))
        sa += [()] * (nd - len(sa))
        da += [()] * (nd - len(da))
        involved = set()
        for s, d in zip(sa, da):
            involved |= set(s) ^ set(d)
        if pd_s != pd_d:
            from ..parallel.sharding import param_axis_indices
            pidx = param_axis_indices(max(pd_s, pd_d),
                                      [s for _, s in topo])
            involved |= set(pidx or ())
        if not involved:
            return None
        dcn = [i for i in involved if _axis_kind(topo[i][0]) == "dcn"]
        idx = dcn[0] if dcn else min(involved)
        return (_axis_kind(topo[idx][0]), self._channel(idx))

    def build_task_graph(self, strategies: StrategyMap, ndev: int):
        topo = self._topo(ndev)
        ops = [op for op in self.model.ops if not isinstance(op, InputOp)]
        tasks: List[SimTask] = []
        fwd_of: Dict[str, List[SimTask]] = {}
        bwd_of: Dict[str, List[SimTask]] = {}

        def new_task(rt, dev, name):
            t = SimTask(run_time=rt, device=dev, name=name)
            tasks.append(t)
            return t

        def reshard_task(tensor, src_pc, dst_pc, name):
            spec = self._reshard_spec(src_pc, dst_pc, topo)
            if spec is None:
                return None
            kind, chan = spec
            bytes_ = self.cost.tensor_bytes(tensor)
            comm_t = self.cost.resharding_time(bytes_, src_pc, dst_pc,
                                               kind=kind)
            if comm_t <= 0:
                return None
            return new_task(comm_t, chan, name)

        def _a2a_axes(pd):
            """[(axis_idx, kind, size)] the pd-way row shards occupy."""
            from ..parallel.sharding import param_axis_indices
            pidx = param_axis_indices(pd, [s for _, s in topo])
            return [(i, _axis_kind(topo[i][0]), topo[i][1])
                    for i in (pidx or ())]

        def _a2a_chain(parents, bytes_per_dev, pd, label, pc=None,
                       op=None, hide_under=None, tail=False):
            """Chain one exchange task per row axis after `parents`;
            returns the new frontier. The schedule shape depends on the
            strategy's overlap flag — THE semantics that let the MCMC
            walk discover pipelined plans unforced:

            - overlap OFF (the fused `jax.lax.all_to_all`): a blocking
              collective — every participating device sits in it, so
              the exchange occupies the COMPUTE stream and independent
              ops cannot run under it (one task per device, the
              serialized-exchange reality FLX514 flags);
            - overlap ON (the decomposed ppermute/chunked rounds): the
              bytes ride the axis CHANNEL. The rounds interleave with
              the op's OWN chunked compute — round r's ppermute DMA
              flies while round r+1's local gather runs — so the
              channel task starts with `hide_under` (the frontier the
              compute itself starts from) rather than after it, and
              downstream waits on max(compute, exchange). With `tail`
              (the gradient direction) the consumer is the per-chunk
              scatter update, which drains arrivals round by round: the
              channel task gates the makespan (every task end does) but
              not the update's start. The residual (1-efficiency)
              fraction plus the per-round decomposition overhead still
              blocks the compute stream (rounds cannot all leave the
              critical path, and the extra collective launches are
              real)."""
            from ..parallel.alltoall import _OVERLAP_CHUNKS
            overlap = bool(getattr(pc, "overlap", False)) \
                if pc is not None else False
            axes = _a2a_axes(pd)
            devs = (self._participants(pc, ndev, op)
                    if pc is not None else list(range(ndev)))
            for i, kind, size in axes:
                t_ax = self.cost.alltoall_time_axes(bytes_per_dev,
                                                    [(kind, size)])
                if t_ax <= 0:
                    continue
                if not overlap:
                    step = [new_task(t_ax, d, f"{label}[{topo[i][0]}]")
                            for d in devs]
                    for p in parents:
                        for s in step:
                            p.add_next(s)
                    parents = step
                    continue
                rounds = (size - 1) if len(axes) == 1 \
                    else _OVERLAP_CHUNKS
                resid = ((1.0 - self.cost.overlap_efficiency()) * t_ax
                         + self.cost.overlap_round_overhead(rounds))
                c = new_task(t_ax, self._channel(i),
                             f"{label}[{topo[i][0]}]")
                for p in (hide_under if hide_under is not None
                          else parents):
                    p.add_next(c)
                # downstream waits on the compute frontier AND (unless
                # the consumer drains per-round) the channel
                frontier = list(parents) if hide_under is not None \
                    else []
                if not tail:
                    frontier.append(c)
                elif hide_under is None:
                    frontier += list(parents)
                if resid > 0:
                    step = [new_task(resid, d,
                                     f"{label}_resid[{topo[i][0]}]")
                            for d in devs]
                    for p in parents:
                        for s in step:
                            p.add_next(s)
                    frontier += step
                parents = frontier or [c]
                hide_under = None
            return parents

        # forward tasks per op per participating device
        itemsize = jnp.dtype(self.cost.compute_dtype).itemsize
        for op in ops:
            pc = strategies[op.name]
            ct = self.cost.op_compute_time(op, pc, backward=False)
            fwd_of[op.name] = [new_task(ct, d, f"fwd:{op.name}")
                               for d in self._participants(pc, ndev, op)]
            # row-sharded embedding lookups: explicit all-to-alls ride
            # the row axes' channels — request ids to the owning shards
            # before the local gather, embedded rows back after it. The
            # skew-aware policies shrink the routed bytes (dedup /
            # hot/cold hybrid — _a2a_payload_bytes prices the expected
            # routed count from the observed id histogram); dedup also
            # pays its sort/unique machinery as a compute task, which
            # is what makes it LOSE on uniform ids.
            pd = max(getattr(pc, "param_degree", 1), 1)
            if pd > 1 and hasattr(op, "alltoall_payload_bytes"):
                req_b, rows_b, _ = op.alltoall_payload_bytes(
                    ndev, itemsize, pc=pc)
                pre: List[SimTask] = []
                if getattr(pc, "exchange", "dense") == "dedup":
                    t_sort = self.cost.dedup_overhead_time(op, ndev)
                    if t_sort > 0:
                        pre = [new_task(t_sort, d, f"dedup:{op.name}")
                               for d in self._participants(pc, ndev,
                                                           op)]
                req = _a2a_chain(pre, req_b, pd, f"a2a_idx:{op.name}",
                                 pc=pc, op=op)
                for r in req:
                    for ft in fwd_of[op.name]:
                        r.add_next(ft)
                # pipelined plans ship the first rounds' rows while the
                # later rounds still gather: the rows exchange starts
                # where the gather starts (the routed-ids frontier)
                fwd_of[op.name] = _a2a_chain(fwd_of[op.name], rows_b,
                                             pd, f"a2a_rows:{op.name}",
                                             pc=pc, op=op,
                                             hide_under=req)
            # dependency + resharding comm from producers
            for src in op.inputs:
                if src.owner_op is None or isinstance(src.owner_op, InputOp):
                    continue
                src_pc = strategies[src.owner_op.name]
                c = reshard_task(src, src_pc, pc,
                                 f"reshard:{src.owner_op.name}->{op.name}")
                if c is not None:
                    for ft in fwd_of[src.owner_op.name]:
                        ft.add_next(c)
                    for ft in fwd_of[op.name]:
                        c.add_next(ft)
                else:
                    for sft in fwd_of[src.owner_op.name]:
                        for ft in fwd_of[op.name]:
                            sft.add_next(ft)

        # backward tasks (reverse order), mirroring fwd deps
        for op in reversed(ops):
            pc = strategies[op.name]
            ct = self.cost.op_compute_time(op, pc, backward=True)
            bwd_of[op.name] = [new_task(ct, d, f"bwd:{op.name}")
                               for d in self._participants(pc, ndev, op)]
            # bwd of op depends on bwd of its consumers (grad flow) and on
            # its own fwd
            for ft in fwd_of[op.name]:
                for bt in bwd_of[op.name]:
                    ft.add_next(bt)
        consumers: Dict[str, List[Op]] = {}
        for op in ops:
            for src in op.inputs:
                if src.owner_op and not isinstance(src.owner_op, InputOp):
                    consumers.setdefault(src.owner_op.name, []).append(op)
        for op in ops:
            for cons in consumers.get(op.name, []):
                c = reshard_task(op.outputs[0], strategies[cons.name],
                                 strategies[op.name],
                                 f"reshard_grad:{cons.name}->{op.name}")
                if c is not None:
                    for bt in bwd_of[cons.name]:
                        bt.add_next(c)
                    for bt in bwd_of[op.name]:
                        c.add_next(bt)
                else:
                    for cbt in bwd_of[cons.name]:
                        for bt in bwd_of[op.name]:
                            cbt.add_next(bt)

        # weight sync + update per parameter (reference simulator.cc:327-408)
        for op in ops:
            if not op.param_defs():
                continue
            pc = strategies[op.name]
            replicas = pc.degrees[0] if pc.degrees else 1
            # per-device parameter traffic: the op-declared shard shapes
            # (every TP-capable op overrides param_shard_shapes; a config
            # that replicates params — e.g. conv spatial splits — keeps
            # full shapes) or touched-rows sparse updates, whichever is
            # tighter. Params/grads sync in fp32.
            shard_bytes = sum(
                math.prod(shape) * 4.0
                for shape in op.param_shard_shapes(pc, ndev).values())
            touched = op.param_bytes_touched_per_step(max(pc.num_parts, 1))
            dev_bytes = min(shard_bytes, touched)
            # the DP all-reduce rides the axes assigned to the sample dim —
            # a hierarchical chain, one task per axis on that axis's
            # channel (phases over different axes of different ops overlap)
            asn = self._assign(pc.degrees, topo)
            parents: List[SimTask] = list(bwd_of[op.name])
            pd = max(getattr(pc, "param_degree", 1), 1)
            if pd > 1 and hasattr(op, "alltoall_payload_bytes"):
                # row-sharded table: gradient rows route to their owning
                # shard (all-to-all over the row axes) instead of a DP
                # all-reduce — optimizer state stays shard-local
                _, _, grad_b = op.alltoall_payload_bytes(ndev, itemsize,
                                                         pc=pc)
                # pipelined plans scatter each arriving round while the
                # next is in flight: the update drains the exchange
                # per-round instead of waiting for the full buffer
                parents = _a2a_chain(parents, grad_b, pd,
                                     f"a2a_grad:{op.name}", pc=pc,
                                     op=op, tail=True)
                # hybrid placement: the replicated hot head applies its
                # (small) update stream in lockstep from an all-gather —
                # the allreduce-style cost the simulator already prices
                # for replicated tables, but only over the hot hits
                hot_b = 0.0
                if (getattr(pc, "hot_fraction", 0.0) > 0
                        and hasattr(op, "_row_shard_geometry")):
                    from ..ops.embedding import hot_update_bytes
                    hot_b = hot_update_bytes(op, pc, ndev)
                if hot_b > 0:
                    for ax_i, (ax_name, size) in enumerate(topo):
                        if size <= 1:
                            continue
                        ph = self.cost.allreduce_time_axes(
                            float(hot_b), [(_axis_kind(ax_name), size)])
                        if ph <= 0:
                            continue
                        s = new_task(
                            ph, self._channel(ax_i),
                            f"hot_allgather[{ax_name}]:{op.name}")
                        for p in parents:
                            p.add_next(s)
                        parents = [s]
            elif replicas > 1:
                if asn is not None and asn[0]:
                    b = float(dev_bytes)
                    for ax in asn[0]:
                        kind, size = _axis_kind(topo[ax][0]), topo[ax][1]
                        ph = self.cost.allreduce_time_axes(b, [(kind, size)])
                        if ph <= 0:
                            continue
                        s = new_task(ph, self._channel(ax),
                                     f"allreduce[{topo[ax][0]}]:{op.name}")
                        for p in parents:
                            p.add_next(s)
                        parents = [s]
                        b /= size
                else:
                    sync_t = self.cost.grad_sync_time(dev_bytes, replicas)
                    if sync_t > 0:
                        s = new_task(sync_t, COMM_DEVICE,
                                     f"allreduce:{op.name}")
                        for p in parents:
                            p.add_next(s)
                        parents = [s]
            if self.cost._host_resident(op, pc):
                upd_compute = self.cost.host_update_time(op, pc)
            else:
                # the sparse scatter divides by how many shards the
                # TABLE actually splits into (param_shard_shapes:
                # row/table/width sharding), not by the output parts —
                # a REPLICATED table applies the full update set on
                # every replica (GSPMD gathers the updates), which is
                # what makes pure DP lose to row sharding at scale
                full_bytes = sum(
                    math.prod(d.shape) * 4.0
                    for d in op.param_defs().values())
                tshards = max(full_bytes / max(shard_bytes, 1.0), 1.0)
                upd_rows = op.update_random_hbm_rows(pc)
                hot_rows_dev = 0.0
                if (pd > 1 and upd_rows > 0
                        and hasattr(op, "_row_shard_geometry")
                        and (getattr(pc, "exchange", "dense") == "dedup"
                             or getattr(pc, "hot_fraction", 0.0) > 0)):
                    # skew-aware scatter: the routed update stream is
                    # pre-combined per (row, device), so each shard
                    # scatters its share of the ROUTED entries, not the
                    # raw lookups; every replica also applies the hot
                    # partials locally
                    from ..ops.embedding import (_lookup_count,
                                                 expected_hot_distinct,
                                                 expected_routed_lookups)
                    lookups = max(_lookup_count(op), 1.0)
                    acc = upd_rows / lookups
                    n_dev = lookups / max(ndev, 1)
                    upd_rows = acc * ndev * expected_routed_lookups(
                        op, pc, n_dev)
                    hot_rows_dev = acc * expected_hot_distinct(op, pc,
                                                               n_dev)
                upd_compute = max(
                    dev_bytes / self.cost._hbm_rate() * 3.0,  # r/w+momentum
                    # sparse touched-rows scatter is random-access
                    # latency bound (write-pipeline rate, slower than
                    # the gather's)
                    self.cost.scatter_rows_time(
                        upd_rows / tshards + hot_rows_dev))
            for d in self._participants(pc, ndev, op):
                u = new_task(upd_compute, d, f"update:{op.name}")
                for p in parents:
                    p.add_next(u)
        return tasks

    # ------------------------------------------------------------------
    def _participants(self, pc: ParallelConfig, ndev: int,
                      op: Optional[Op] = None) -> List[int]:
        """Devices an op's point tasks run on. The strategy's explicit
        `device_ids` are honored when present (reference builds each op's
        SimTasks on the devices its strategy names,
        simulator.cc:279-326 — what lets operator-placement strategies
        price correctly: ops on disjoint devices overlap). Fallback:
        devices 0..k-1. Host-RESIDENT ops run on the single shared host
        channel instead — host DRAM does not parallelize across tables
        (see CostModel.host_update_time)."""
        if op is not None and self.cost._host_resident(op, pc):
            return [HOST_DEVICE]
        k = min(pc.num_parts, ndev)
        ids = pc.device_ids
        if ids and len(ids) >= k:
            return [int(i) % ndev for i in ids[:k]]
        return list(range(k))

    def _clamp_strategies(self, strategies: StrategyMap,
                          ndev: int) -> StrategyMap:
        """Price what would actually EXECUTE: clamp each op's degrees to
        divide its output dims AND to the target mesh's factorizable
        degrees (the simulator twin of FFModel._effective_pc — both
        checks, or the search selects wins from degrees that silently
        execute as different ones). Without this, 8-way data parallelism
        over a batch of 4 simulates as an impossible 8x speedup. Ops with
        raw_degree_semantics (concatenated-rows embeddings) keep their
        raw degrees — their table dim is intent, not an output
        partitioning."""
        from ..parallel.mesh import structural_axis_sizes
        from ..parallel.sharding import (clamp_param_degree,
                                         feasible_degrees_for)
        if self.model.mesh is not None and self.model.mesh.size == ndev:
            from ..parallel.sharding import AxisAssigner
            asn = AxisAssigner(self.model.mesh)
            feas, axis_sizes = asn.feasible_degrees(), asn.axis_sizes
        else:
            axis_sizes = structural_axis_sizes(ndev)
            feas = feasible_degrees_for(axis_sizes)
        out = {}
        by_name = {op.name: op for op in self.model.ops}

        def _skew(pc, pd):
            """Skew/pipelining policies survive a clamp only while the
            exchange itself does (pd > 1) — a fully-replicated table
            has nothing to dedup, no cold tail to split, and no
            exchange to overlap."""
            if pd > 1:
                return (getattr(pc, "exchange", "dense"),
                        getattr(pc, "hot_fraction", 0.0),
                        bool(getattr(pc, "overlap", False)))
            return "dense", 0.0, False

        for name, pc in strategies.items():
            op = by_name.get(name)
            pd = clamp_param_degree(getattr(pc, "param_degree", 1),
                                    axis_sizes)
            exch, frac, ovl = _skew(pc, pd)
            if (op is None or not op.outputs
                    or getattr(op, "raw_degree_semantics", False)):
                if (pd != getattr(pc, "param_degree", 1)
                        or exch != getattr(pc, "exchange", "dense")
                        or frac != getattr(pc, "hot_fraction", 0.0)
                        or ovl != bool(getattr(pc, "overlap", False))):
                    pc = ParallelConfig(
                        pc.degrees, pc.device_type,
                        pc.device_ids, pc.memory_types,
                        param_degree=pd, exchange=exch,
                        hot_fraction=frac,
                        quant_dtype=getattr(pc, "quant_dtype", ""),
                        quant_update=getattr(pc, "quant_update", ""),
                        overlap=ovl)
                out[name] = pc
                continue
            shape = op.outputs[0].shape
            degs = list(pc.degrees)[:len(shape)]
            degs += [1] * (len(shape) - len(degs))
            changed = (pd != getattr(pc, "param_degree", 1)
                       or exch != getattr(pc, "exchange", "dense")
                       or frac != getattr(pc, "hot_fraction", 0.0)
                       or ovl != bool(getattr(pc, "overlap", False)))
            for i, d in enumerate(degs):
                d = min(d, shape[i])
                while d > 1 and (shape[i] % d != 0 or d not in feas):
                    d -= 1
                if d != degs[i]:
                    changed = True
                degs[i] = max(d, 1)
            out[name] = (ParallelConfig(
                             tuple(degs), pc.device_type,
                             pc.device_ids, pc.memory_types,
                             param_degree=pd, exchange=exch,
                             hot_fraction=frac,
                             quant_dtype=getattr(pc, "quant_dtype", ""),
                             quant_update=getattr(pc, "quant_update", ""),
                             overlap=ovl)
                         if changed else pc)
        return out

    def fits_memory(self, strategies: StrategyMap, ndev: int) -> bool:
        """Per-device residency must fit the chip's HBM with 10%
        headroom for temps and fragmentation. The reference allocates
        real FB scratch on-device and fails oversized configs
        (reference simulator.cu:84-90); the round-3 flat 25% headroom
        ignored activations entirely, so a b256 conv strategy whose
        forward residuals alone exceed HBM could be blessed by the
        search and OOM on the real chip. The accounting itself lives in
        :func:`hbm_footprint_report`, shared with the static plan
        verifier (analysis/shardcheck.py FLX503)."""
        total = sum(hbm_footprint_report(self.model, self.cost,
                                         strategies, ndev).values())
        return total <= 0.9 * self.cost.spec.hbm_capacity_bytes

    def simulate(self, strategies: StrategyMap,
                 ndev: Optional[int] = None,
                 use_native: bool = True) -> float:
        """Event-driven makespan (reference simulator.cc:410-447): pop the
        earliest-ready task whose device is free, run it, release deps.

        The event loop itself runs in the native C++ engine
        (native/ffsim.cc) when available — it sits inside the MCMC search
        hot loop, which is why the reference keeps it native too. The
        Python loop below is the reference semantics and the fallback.
        """
        if ndev is None:
            ndev = int(math.prod(
                [self.model.mesh.shape[a] for a in self.model.mesh.axis_names])
            ) if self.model.mesh else 1
        strategies = self._clamp_strategies(strategies, ndev)
        if not self.fits_memory(strategies, ndev):
            # infeasible placement: params exceed per-chip HBM (pure DP on
            # DLRM-Terabyte replicates ~96 GB of tables, ~6x its HBM); an
            # infinite makespan makes the MCMC reject it like the reference
            # rejects illegal configs
            return float("inf")
        tasks = self.build_task_graph(strategies, ndev)
        # per-step dispatch/epilogue floor (TPUSpec.per_step_overhead_s):
        # constant across strategies, so it never changes WHICH strategy
        # wins, but calibration against real step times needs it. Fused
        # supersteps (FFConfig.superstep) amortize the floor — K steps
        # share ONE dispatch — so the per-step price is overhead / K or
        # the simulator would stay wrong about every floor-bound
        # small-batch config the fusion exists for.
        overhead = self.cost.spec.per_step_overhead_amortized(
            self._effective_superstep())
        if use_native:
            ms = self._simulate_native(tasks)
            if ms is not None:
                return ms + overhead
        device_free: Dict[int, float] = {}
        ready: List = []
        seq = 0
        for t in tasks:
            if t.counter == 0:
                heapq.heappush(ready, (t.ready_time, seq, t))
                seq += 1
        makespan = 0.0
        done = 0
        while ready:
            rt, _, task = heapq.heappop(ready)
            start = max(rt, device_free.get(task.device, 0.0))
            end = start + task.run_time
            device_free[task.device] = end
            makespan = max(makespan, end)
            done += 1
            for nxt in task.next_tasks:
                nxt.counter -= 1
                nxt.ready_time = max(nxt.ready_time, end)
                if nxt.counter == 0:
                    heapq.heappush(ready, (nxt.ready_time, seq, nxt))
                    seq += 1
        if done != len(tasks):
            raise RuntimeError(
                f"simulation deadlock: {done}/{len(tasks)} tasks ran")
        return makespan + overhead

    def _simulate_native(self, tasks: List[SimTask]) -> Optional[float]:
        """Run the event loop in native/ffsim.cc. Returns None when the
        native library is unavailable (caller falls back to Python)."""
        from ..native import get_lib
        lib = get_lib()
        if lib is None:
            return None
        import ctypes

        import numpy as np
        n = len(tasks)
        index = {id(t): i for i, t in enumerate(tasks)}
        run_time = np.empty(n, dtype=np.float64)
        device = np.empty(n, dtype=np.int32)
        src_list: List[int] = []
        dst_list: List[int] = []
        for i, t in enumerate(tasks):
            run_time[i] = t.run_time
            device[i] = t.device
            for nxt in t.next_tasks:
                src_list.append(i)
                dst_list.append(index[id(nxt)])
        edge_src = np.asarray(src_list, dtype=np.int64)
        edge_dst = np.asarray(dst_list, dtype=np.int64)
        ms = lib.ffsim_makespan(
            n, run_time.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            device.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            len(edge_src),
            edge_src.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            edge_dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        if ms < 0:
            raise RuntimeError("simulation deadlock (native engine)")
        return float(ms)
