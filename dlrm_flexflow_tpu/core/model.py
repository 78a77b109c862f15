"""FFModel: graph builder + compile + training verbs.

Parity with the reference FFModel engine (reference: include/model.h:291-517,
src/runtime/model.cc):
- tensor-in/tensor-out builder methods (model.h:291-401) — `dense`,
  `conv2d`, `pool2d`, `batch_norm`, `embedding`, `concat`, `split`, `flat`,
  `softmax`, `dropout`, unary/binary elementwise, `batch_matmul`,
  `transpose`, `reshape`, `reverse`;
- `compile(optimizer, loss_type, metrics)` (model.cc:1003-1080): resolves
  the per-op parallelization strategy (import file / search / default DP),
  builds parameter shardings, and traces+jits the train step;
- training verbs `init_layers/forward/backward/update/zero_gradients`
  (model.cc:942-993, 1146-1149) — provided for API parity; the performant
  path is the fused jitted `train_step` used by `fit()`;
- metrics future-chain (model.cc:1182-1205) — metrics come back as device
  arrays off the async dispatch stream and are folded host-side.

TPU-native redesign: there are no Legion regions/partitions/mappers; the
graph is traced once into XLA, per-op ParallelConfigs lower to GSPMD
shardings (parallel/sharding.py), resharding between ops is XLA collectives,
and Legion trace replay (dlrm.cc:179-185) is subsumed by jit
compile-once/execute-many.
"""

from __future__ import annotations

import contextlib
import functools
import math
import time
from collections import deque
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence)

import jax
import jax.numpy as jnp
import numpy as np

from ..config import FFConfig
from ..parallel.mesh import make_mesh
from ..parallel.pconfig import ParallelConfig, StrategyMap
from ..parallel.sharding import AxisAssigner
from ..parallel.distributed import MeshDegraded, MeshReturned, put_global
from ..analysis import sanitizer as _san
from ..obs import metrics as obsmetrics
from ..obs import trace as obstrace
from ..utils.watchdog import StallReport, WorkerStalled
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from . import losses as losses_mod
from . import metrics as metrics_mod
from .op import InputOp, Op
from .optimizers import Optimizer, SGDOptimizer
from .tensor import Tensor
from ..utils.logging import log_model
from ..utils import faults


# init_layers: an op with at least this many parameter bytes is
# initialized by the sharded SPMD program (a compile per model, no device
# ever holds the whole parameter); smaller ones eagerly on one device and
# then placed (no compile; the whole parameter passes through one device,
# harmless at this size)
_SHARDED_INIT_BYTES = 64 << 20


def _sharding_mismatch(e: Exception) -> bool:
    """True when a cached AOT executable rejected its inputs because
    GSPMD propagated different shardings than it was compiled with (the
    recompile-once fallback)."""
    return "disagree" in str(e)


def _to_memory(v, space: str):
    """Stage a traced value into host or device memory for the hetero
    host-offload path."""
    return jax.device_put(v, jax.memory.Space.Host if space == "host"
                          else jax.memory.Space.Device)


class AnomalyError(RuntimeError):
    """A train step produced a non-finite loss or gradient norm and the
    anomaly policy is "rollback" or "raise" (FFConfig.anomaly_policy).
    Under "rollback", fit(checkpoint_dir=...) catches this, restores the
    last good snapshot, and re-winds; outside fit() it propagates.
    The offending update was already suppressed on device — params/opt
    state keep their pre-step values."""

    def __init__(self, step: int, loss: float, grad_norm: float):
        super().__init__(
            f"non-finite training step {step}: loss={loss}, "
            f"global grad norm={grad_norm}")
        self.step = step
        self.loss = loss
        self.grad_norm = grad_norm
        # anomaly-sentinel fires land in the obs layer at the one choke
        # point every policy passes through (trace instant + counter,
        # no-op when --obs off) — visible even if the recovery path
        # that catches this never reports it
        from ..obs import metrics as _obsm
        from ..obs import trace as _obstrace
        _obsm.counter("ff_anomalies_total",
                      "non-finite training steps the sentinel caught"
                      ).inc()
        _obstrace.instant("anomaly", cat="sentinel", step=int(step),
                          loss=repr(loss), grad_norm=repr(grad_norm))


class StagedStep(NamedTuple):
    """One fully-staged train-step input (`FFModel._stage_step`): the
    device-put batch (host-only inputs already popped) plus the numpy
    indices for host-resident tables (None when there are none). The
    prefetch pipeline stages these ahead of the hot loop.

    `k` > 1 marks a fused-superstep megabatch (`_stage_superstep`):
    `device_batch` holds `[k, batch, ...]` stacked arrays and
    `train_batch_staged` routes it to the K-step scan executable (one
    dispatch trains k steps); host_idx is always None there — host-
    resident-table models fall back to k=1."""

    device_batch: Dict[str, Any]
    host_idx: Optional[Dict[str, Any]]
    k: int = 1


class _Throttle:
    """The bound on async steps in flight: XLA CPU's in-process
    collectives can starve when many multi-device executions queue up on
    few host cores (on TPU the device is the bottleneck; a deep pipeline
    is safe). Bounds the pipeline without draining it: a call blocks on
    the step issued `bound` calls AGO, through that dispatch's metrics
    vector (`StepMetrics.vector`). It holds `bound` of them across later
    dispatches, which is why the vector is a fresh output of the step
    program and rides in no donated carry; it reads none."""

    def __init__(self):
        self.bound = 1 if jax.default_backend() == "cpu" else 32
        self._vectors = deque()

    def clear(self):
        self._vectors.clear()

    def __call__(self, mets):
        self._vectors.append(mets.vector)
        if len(self._vectors) > self.bound:
            with obstrace.span("fit/throttle"):
                jax.block_until_ready(self._vectors.popleft())
        return mets

    @property
    def lag(self) -> int:
        """How many dispatches back fit()'s pace probe asks: a quarter
        of the bound (8 on a TPU, 1 on the CPU)."""
        return max(1, self.bound // 4)

    def behind(self):
        """The metrics vector of the dispatch issued `lag` dispatches
        ago; None while fewer are held."""
        return (self._vectors[-self.lag]
                if len(self._vectors) >= self.lag else None)


# Who sets the pace of a batch shape's steps, as fit() finds out by
# itself under `FFConfig.superstep == "auto"`: at a dispatch it asks,
# without waiting, whether the device had already finished the step it
# issued `_Throttle.lag` dispatches ago (`is_ready()` on that step's
# metrics vector: 0.3 us, 5 us the first time an array is asked). Where
# the device sets the pace the queue is as deep as the throttle allows
# and that step is far from done; where the host does, the device is
# through with everything but the newest few. Not the step before: the
# host learns of a finished step late. On the v5e, a 0.116 ms step under
# a 0.46 ms dispatch (`dlrm_terabyte.b128_local`; PERF.md §6, PR 35) read
# ready at 0% of the dispatches one later, 43-56% two later and 100%
# from three on (0.5-1.4 ms after it was issued), and three device-paced
# loops read 0% at every lag from 1 to 31 once their queue had filled. A
# quarter of the bound leaves room on both sides: eight dispatches for
# the news to arrive, and a queue that has to be a quarter full before a
# loop reads device-paced.
# PROBE_DISPATCHES such answers make a verdict (as many as the throttle
# lets the host run ahead on a TPU), counted from the `lag`-th dispatch
# after an epoch's start, where a callback may have drained the queue,
# and only at dispatches whose input is staged already
# (`BatchFeed.at_hand`): a loop that waits for its input finds the
# device idle as well, and K steps a dispatch buy it nothing.
# A fit that probes waits for its first dispatch, once: a program's
# first run loads it onto the chip (tens of ms), the host meanwhile
# issues all the throttle lets it, and every answer of a probe would be
# about that backlog (2 of 32 read ready on that cell without the wait).
# HOST_PACED_SHARE of them finding the device through say the host sets
# the pace: a host-paced loop reads 100%, a device-paced one reads the
# few dispatches before its queue has filled or after something drained
# it in mid-epoch (a snapshot), and between the two a second step
# program is not worth its compile: fusing K steps buys at most the
# share of the step the device waited.
PROBE_DISPATCHES = 32
HOST_PACED_SHARE = 0.9


class _Pace:
    """The probe's count for one batch shape (`FFModel._pace`, by the
    step's `_exec_key`) and, once PROBE_DISPATCHES answers are in, its
    verdict; kept on the model, so a shape is probed once."""

    __slots__ = ("batch_size", "asked", "idle")

    def __init__(self, batch_size: int):
        self.batch_size, self.asked, self.idle = int(batch_size), 0, 0

    def note(self, idle: bool) -> bool:
        """Count one answer; True when it completed the probe."""
        self.asked += 1
        self.idle += bool(idle)
        return self.asked == PROBE_DISPATCHES

    @property
    def share(self) -> Optional[float]:
        """Share of the dispatches asked that found the device idle."""
        return self.idle / self.asked if self.asked else None

    @property
    def host_paced(self) -> Optional[bool]:
        """The verdict; None while the probe runs."""
        if self.asked < PROBE_DISPATCHES:
            return None
        return self.idle >= HOST_PACED_SHARE * self.asked


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        self.config = config or FFConfig()
        if getattr(self.config, "debug_nans", None) is not None:
            jax.config.update("jax_debug_nans",
                              bool(self.config.debug_nans))
        self._op_guid = 0
        self.ops: List[Op] = []          # topological (construction) order
        self.input_tensors: List[Tensor] = []
        self.compute_dtype = self.config.jnp_compute_dtype
        # set by compile()
        self.optimizer: Optional[Optimizer] = None
        self.loss_type: Optional[str] = None
        self.metrics: List[str] = []
        self.mesh: Optional[Mesh] = None
        self.strategies: StrategyMap = {}
        self.label_tensor: Optional[Tensor] = None
        self._logits_tensor: Optional[Tensor] = None
        self._preds_tensor: Optional[Tensor] = None
        # runtime state (set by init_layers)
        self.params = None
        self.opt_state = None
        self.op_state = None
        self._step = 0
        self.perf = metrics_mod.PerfMetrics()

    # ------------------------------------------------------------------
    # graph construction
    # ------------------------------------------------------------------
    def _next_op_guid(self) -> int:
        self._op_guid += 1
        return self._op_guid

    def _register_op(self, op: Op):
        # the op name keys strategies/params/shardings (reference hashes it
        # into the MappingTagID, strategy.cc:23-26) — collisions corrupt all
        # three maps, so reject them at build time
        if any(o.name == op.name for o in self.ops):
            raise ValueError(
                f"duplicate op name {op.name!r}; op names must be unique "
                f"(they key parallelization strategies and parameters)")
        self.ops.append(op)

    def create_tensor(self, shape: Sequence[int], dtype=jnp.float32,
                      name: Optional[str] = None) -> Tensor:
        """Reference FFModel::create_tensor (model.cc:457-553); sample dim
        first."""
        op = InputOp(self, shape, dtype, name)
        t = op.outputs[0]
        if name:
            t.name = name
        self.input_tensors.append(t)
        return t

    # --- op builders (reference model.h:291-401) -----------------------
    def dense(self, input_tensor, out_dim, activation=None, use_bias=True,
              kernel_initializer=None, bias_initializer=None, name=None):
        from ..ops.linear import Linear
        if activation == "softmax":
            # lower to a separate Softmax op (not a fused epilogue) so the
            # loss's logits-extraction special case in compile() can see it
            t = Linear(self, input_tensor, out_dim, "none", use_bias,
                       kernel_initializer, bias_initializer, name).outputs[0]
            return self.softmax(t, name=f"{name}_softmax" if name else None)
        return Linear(self, input_tensor, out_dim, activation or "none",
                      use_bias, kernel_initializer, bias_initializer,
                      name).outputs[0]

    def conv2d(self, input_tensor, out_channels, kernel_h, kernel_w,
               stride_h, stride_w, padding_h, padding_w, activation=None,
               use_bias=True, groups=1, kernel_initializer=None,
               bias_initializer=None, name=None):
        from ..ops.conv import Conv2D
        return Conv2D(self, input_tensor, out_channels, kernel_h, kernel_w,
                      stride_h, stride_w, padding_h, padding_w,
                      activation or "none", use_bias, groups,
                      kernel_initializer, bias_initializer, name).outputs[0]

    def pool2d(self, input_tensor, kernel_h, kernel_w, stride_h, stride_w,
               padding_h, padding_w, pool_type="max", activation=None,
               name=None):
        from ..ops.conv import Pool2D
        return Pool2D(self, input_tensor, kernel_h, kernel_w, stride_h,
                      stride_w, padding_h, padding_w, pool_type,
                      activation or "none", name).outputs[0]

    def batch_norm(self, input_tensor, relu=True, name=None):
        from ..ops.conv import BatchNorm
        return BatchNorm(self, input_tensor, relu, name).outputs[0]

    def embedding(self, input_tensor, num_entries, out_dim, aggr="sum",
                  kernel_initializer=None, name=None):
        from ..ops.embedding import Embedding
        return Embedding(self, input_tensor, num_entries, out_dim, aggr,
                         kernel_initializer, name).outputs[0]

    def embedding_stacked(self, input_tensor, num_tables, num_entries,
                          out_dim, aggr="sum", kernel_initializer=None,
                          name=None):
        from ..ops.embedding import EmbeddingBagStacked
        return EmbeddingBagStacked(self, input_tensor, num_tables,
                                   num_entries, out_dim, aggr,
                                   kernel_initializer, name).outputs[0]

    def embedding_concat(self, input_tensor, table_sizes, out_dim,
                         aggr="sum", kernel_initializer=None, name=None):
        """Non-uniform tables (shared width, different row counts) fused
        into one concatenated-rows parameter — see ops.embedding
        EmbeddingBagConcat."""
        from ..ops.embedding import EmbeddingBagConcat
        return EmbeddingBagConcat(self, input_tensor, table_sizes, out_dim,
                                  aggr, kernel_initializer, name).outputs[0]

    def concat(self, tensors, axis, name=None):
        from ..ops.tensor_ops import Concat
        return Concat(self, list(tensors), axis, name).outputs[0]

    def split(self, input_tensor, sizes, axis, name=None):
        from ..ops.tensor_ops import Split
        return Split(self, input_tensor, sizes, axis, name).outputs

    def flat(self, input_tensor, name=None):
        from ..ops.tensor_ops import Flat
        return Flat(self, input_tensor, name).outputs[0]

    def reshape(self, input_tensor, shape, name=None):
        from ..ops.tensor_ops import Reshape
        return Reshape(self, input_tensor, shape, name).outputs[0]

    def transpose(self, input_tensor, name=None):
        from ..ops.tensor_ops import Transpose
        return Transpose(self, input_tensor, name).outputs[0]

    def reverse(self, input_tensor, axis, name=None):
        from ..ops.tensor_ops import Reverse
        return Reverse(self, input_tensor, axis, name).outputs[0]

    def index_select(self, input_tensor, indices, axis, name=None):
        from ..ops.tensor_ops import IndexSelect
        return IndexSelect(self, input_tensor, indices, axis, name).outputs[0]

    def softmax(self, input_tensor, name=None):
        from ..ops.elementwise import Softmax
        return Softmax(self, input_tensor, name).outputs[0]

    def dropout(self, input_tensor, rate, seed=0, name=None):
        from ..ops.elementwise import Dropout
        return Dropout(self, input_tensor, rate, seed, name).outputs[0]

    def multihead_attention(self, q, k=None, v=None, embed_dim=None,
                            num_heads=8, causal=False, name=None):
        from ..ops.attention import MultiHeadAttention
        k = q if k is None else k
        v = q if v is None else v
        embed_dim = embed_dim or q.shape[-1]
        return MultiHeadAttention(self, q, k, v, embed_dim, num_heads,
                                  causal, name).outputs[0]

    def rms_norm(self, input_tensor, eps=1e-6, to_compute_dtype=False,
                 zero_centered=True, name=None):
        from ..ops.norm import RMSNorm
        return RMSNorm(self, input_tensor, eps, to_compute_dtype,
                       zero_centered, name).outputs[0]

    def gated_delta_net(self, x, num_k_heads, num_v_heads, head_k_dim,
                        head_v_dim, conv_width=4, eps=1e-6,
                        kernel_initializer=None, name=None):
        """The linear-attention mixer of a hybrid language model (see
        ops/delta_net.GatedDeltaNet)."""
        from ..ops.delta_net import GatedDeltaNet
        return GatedDeltaNet(self, x, num_k_heads, num_v_heads, head_k_dim,
                             head_v_dim, conv_width, eps,
                             kernel_initializer, name).outputs[0]

    def mamba2(self, x, num_heads, head_dim, n_groups, state_size,
               conv_width=4, chunk_size=128, eps=1e-5, dt_min=1e-3,
               dt_max=0.1, dt_floor=1e-4, kernel_initializer=None,
               name=None):
        """The state-space mixer of a hybrid language model (see
        ops/mamba.Mamba2)."""
        from ..ops.mamba import Mamba2
        return Mamba2(self, x, num_heads, head_dim, n_groups, state_size,
                      conv_width, chunk_size, eps, dt_min, dt_max, dt_floor,
                      kernel_initializer, name).outputs[0]

    def gated_attention(self, x, num_heads, num_kv_heads, head_dim,
                        rotary_dim, rope_theta=1e7, eps=1e-6,
                        kernel_initializer=None, name=None, gate=True,
                        qk_norm=True):
        """Causal grouped-query self-attention with, as the model says,
        q/k norm, partial rotary embedding (`rotary_dim` 0: none) and a
        sigmoid output gate (see ops/attention.GatedAttention)."""
        from ..ops.attention import GatedAttention
        return GatedAttention(self, x, num_heads, num_kv_heads, head_dim,
                              rotary_dim, rope_theta, eps,
                              kernel_initializer, name, gate,
                              qk_norm).outputs[0]

    def latent_attention(self, x, num_heads, q_rank, kv_rank, nope_dim,
                         rope_dim, v_dim, rope_theta=1e6, eps=1e-5,
                         kernel_initializer=None, name=None):
        """Causal multi-head latent attention, expanded form (see
        ops/attention.LatentAttention)."""
        from ..ops.attention import LatentAttention
        return LatentAttention(self, x, num_heads, q_rank, kv_rank, nope_dim,
                               rope_dim, v_dim, rope_theta, eps,
                               kernel_initializer, name).outputs[0]

    def gated_mlp(self, x, hidden_dim, kernel_initializer=None, name=None):
        """A dense SwiGLU feed-forward part (see ops/linear.GatedMLP)."""
        from ..ops.linear import GatedMLP
        return GatedMLP(self, x, hidden_dim, kernel_initializer,
                        name).outputs[0]

    def moe(self, x, num_experts, top_k, expert_dim, shared_dim,
            experts_held=None, expert_offset=0, norm_topk=True,
            scoring="softmax", routed_scale=1.0, shared_gate=True,
            balance_rate=0.0, kernel_initializer=None, name=None,
            activation="swiglu"):
        """Sparse experts, the share of them this chip holds (see
        ops/moe.MoE): the router scores all `num_experts`, the op computes
        experts `expert_offset .. expert_offset + experts_held - 1`."""
        from ..ops.moe import MoE
        return MoE(self, x, num_experts, top_k, expert_dim, shared_dim,
                   experts_held, expert_offset, norm_topk, scoring,
                   routed_scale, shared_gate, balance_rate,
                   kernel_initializer, name, activation).outputs[0]

    def lstm_stack(self, input_tensor, hidden, num_layers, name=None):
        """N stacked LSTM layers in ONE scan (see ops/rnn.LSTMStack:
        pays the serial per-iteration latency once per timestep instead
        of once per layer per timestep)."""
        from ..ops.rnn import LSTMStack
        return LSTMStack(self, input_tensor, hidden, num_layers,
                         name).outputs[0]

    def lstm(self, input_tensor, hidden, name=None):
        from ..ops.rnn import LSTM
        return LSTM(self, input_tensor, hidden, name).outputs[0]

    def batch_matmul(self, a, b, trans_a=True, trans_b=False, name=None):
        from ..ops.batch_matmul import BatchMatmul
        return BatchMatmul(self, a, b, trans_a, trans_b, name).outputs[0]

    def fused_dot_interaction(self, sparse_idx, bottom, num_entries,
                              out_dim, activation="relu",
                              emb_initializer=None, kernel_initializer=None,
                              bias_initializer=None, name=None):
        """Fused gather→dot-interaction→first-top-MLP-layer (see
        ops/interaction.FusedDotInteraction): on TPU the whole chain runs
        in one Pallas kernel and the (B, F, F) interaction tensor never
        reaches HBM."""
        from ..ops.interaction import FusedDotInteraction
        return FusedDotInteraction(self, sparse_idx, bottom, num_entries,
                                   out_dim, activation, emb_initializer,
                                   kernel_initializer, bias_initializer,
                                   name).outputs[0]

    def _unary(self, op_type, x, name=None):
        from ..ops.elementwise import ElementUnary
        return ElementUnary(self, x, op_type, name).outputs[0]

    def exp(self, x, name=None):
        return self._unary("exp", x, name)

    def relu(self, x, name=None):
        return self._unary("relu", x, name)

    def sigmoid(self, x, name=None):
        return self._unary("sigmoid", x, name)

    def tanh(self, x, name=None):
        return self._unary("tanh", x, name)

    def elu(self, x, name=None):
        return self._unary("elu", x, name)

    def _binary(self, op_type, a, b, name=None):
        from ..ops.elementwise import ElementBinary
        return ElementBinary(self, a, b, op_type, name).outputs[0]

    def add(self, a, b, name=None):
        return self._binary("add", a, b, name)

    def subtract(self, a, b, name=None):
        return self._binary("subtract", a, b, name)

    def multiply(self, a, b, name=None):
        return self._binary("multiply", a, b, name)

    def divide(self, a, b, name=None):
        return self._binary("divide", a, b, name)

    def get_layer_by_id(self, idx: int) -> Op:
        """Reference flexflow_cbinding.py FFModel.get_layer_by_id — indexes
        non-input ops in construction order."""
        return [op for op in self.ops if not isinstance(op, InputOp)][idx]

    def get_layer_by_name(self, name: str) -> Op:
        for op in self.ops:
            if op.name == name:
                return op
        raise KeyError(name)

    # ------------------------------------------------------------------
    # compile
    # ------------------------------------------------------------------
    def compile(self, optimizer: Optional[Optimizer] = None,
                loss_type: str = "mean_squared_error",
                metrics: Sequence[str] = ("mean_squared_error",),
                mesh: Optional[Mesh] = None,
                strategies: Optional[StrategyMap] = None,
                final_tensor: Optional[Tensor] = None,
                loss_weights=None):
        """Resolve strategy + build the jitted train/eval steps.

        `loss_weights`: one weight a logit row of a sample, for a loss of
        several terms over one logits tensor (`losses.
        sparse_categorical_crossentropy`): a model that predicts through
        one head twice (multi-token prediction) concatenates the two
        passes' rows and weighs them 1 and lambda, 0 where a row has no
        target. The loss and the reported `sparse_cce` are mean(w * nll).

        Mirrors reference FFModel::compile (model.cc:1003-1080): [load or
        search strategies] → per-op partitioning/weights → label tensor →
        optimizer init. Search (--budget) is run by the caller via
        search.mcmc before compile, or lazily here when
        config.search_budget > 0.
        """
        self.optimizer = optimizer or SGDOptimizer(
            lr=self.config.learning_rate,
            weight_decay=self.config.weight_decay)
        self.loss_type = losses_mod.canonical_loss(loss_type)
        self.metrics = metrics_mod.canonical_metrics(list(metrics))
        if loss_weights is not None and self.loss_type != (
                losses_mod.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY):
            raise ValueError("loss_weights weigh the rows of "
                             "sparse_categorical_crossentropy only")
        self.loss_weights = (None if loss_weights is None else
                             np.asarray(loss_weights, np.float32).reshape(-1))
        self.mesh = mesh if mesh is not None else make_mesh(
            num_devices=self.config.num_devices)
        ndev = int(np.prod([self.mesh.shape[a] for a in self.mesh.axis_names]))

        # --- strategies -------------------------------------------------
        self.strategies = dict(strategies or {})
        if not self.strategies and self.config.import_strategy_file:
            from ..parallel.strategy_io import load_strategies
            # load-time validation: degrees must factorize THIS mesh and
            # every entry must reference an op of THIS model (or a
            # reference-style generic key) — a malformed file fails here
            # with file+op+reason, not as a downstream GSPMD error
            self.strategies = load_strategies(
                self.config.import_strategy_file, num_devices=ndev,
                known_ops={op.name for op in self.ops},
                row_shard_ops={op.name for op in self.ops
                               if hasattr(op, "_row_shard_geometry")})
        if self.config.search_budget > 0 and not self.strategies:
            try:
                from ..search.mcmc import optimize
            except ImportError as e:
                raise NotImplementedError(
                    "--budget strategy search requires the search.mcmc "
                    "module (not built yet in this checkout)") from e
            cm = None
            if self.config.search_measure:
                from ..search.cost_model import CostModel
                cm = CostModel(compute_dtype=self.compute_dtype,
                               measure=True)
            self.strategies = optimize(self, budget=self.config.search_budget,
                                       alpha=self.config.search_alpha,
                                       cost_model=cm)
        # reference-style generic keys: the reference's DLRM strategies key
        # ops as "embedding{i}" / "linear" / "concat" / "mse_loss" shared
        # across ops of a type (dlrm_strategy.py, dlrm_strategy_hetero.cc) —
        # resolve those for ops without an exact-name entry
        self._resolve_generic_strategy_keys(ndev)
        # default: data parallelism for every op (reference mapper fallback,
        # mapper.cc:297-311)
        for op in self.ops:
            if isinstance(op, InputOp):
                continue
            if op.name not in self.strategies:
                self.strategies[op.name] = op.default_parallel_config(ndev)
        if self.config.export_strategy_file:
            from ..parallel.strategy_io import save_strategies
            save_strategies(self.config.export_strategy_file, self.strategies)

        # --- final tensors / label -------------------------------------
        from ..ops.elementwise import Softmax
        last_op = [op for op in self.ops if not isinstance(op, InputOp)][-1]
        preds = final_tensor if final_tensor is not None else last_op.outputs[0]
        self._preds_tensor = preds
        # reference applies CCE losses to softmax output; we keep the probs
        # for metrics but feed pre-softmax logits to the loss for stability
        if (isinstance(preds.owner_op, Softmax)
                and "crossentropy" in self.loss_type):
            self._logits_tensor = preds.owner_op.inputs[0]
        else:
            self._logits_tensor = preds
        # label tensor (reference model.cc:1062 creates it sized like the
        # final output, int for sparse labels)
        if self.loss_type == losses_mod.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY:
            lshape, ldtype = (preds.shape[0], 1), jnp.int32
        else:
            lshape, ldtype = preds.shape, jnp.float32
        self.label_tensor = Tensor(lshape, ldtype, name="label")
        if self.loss_weights is not None and (
                preds.shape[0] % (self.config.batch_size
                                  * self.loss_weights.size)):
            raise ValueError(
                f"{self.loss_weights.size} loss weights a sample do not lay "
                f"over {preds.shape[0]} logit rows of a batch of "
                f"{self.config.batch_size}")

        self._build_shardings()
        self._build_steps()
        return self

    def _resolve_generic_strategy_keys(self, ndev: int):
        """Translate reference-keyed strategies onto this graph's ops.

        The reference DLRM strategy files (src/runtime/dlrm_strategy.py,
        dlrm_strategy_hetero.cc:28-49) key per-table embeddings as
        "embedding{i}" (dims (1,1), whole table placed on device
        `device_ids[0]` — model parallelism by placement) and share one
        "linear"/"concat"/"mse_loss" entry across all ops of that type.
        GSPMD translation: N tables round-robined over D distinct devices
        become table-dim sharding of degree D on the stacked embedding (or
        per-op placement for unfused tables); shared type keys apply to every
        op of the type; CPU device_type marks host offload.
        """
        from ..ops.embedding import (Embedding, EmbeddingBagConcat,
                                     EmbeddingBagStacked)
        from ..ops.linear import Linear
        from ..ops.tensor_ops import Concat
        strategies = self.strategies
        if not strategies:
            return
        emb_keys = sorted((k for k in strategies
                           if k.startswith("embedding")
                           and k[len("embedding"):].isdigit()),
                          key=lambda k: int(k[len("embedding"):]))
        fused_types = (EmbeddingBagStacked, EmbeddingBagConcat)
        emb_ops = [op for op in self.ops
                   if isinstance(op, (Embedding,) + fused_types)]
        for i, op in enumerate(emb_ops):
            if op.name in strategies:
                continue
            if isinstance(op, fused_types) and emb_keys:
                pcs = [strategies[k] for k in emb_keys]
                distinct = {pc.device_ids[:1] for pc in pcs if pc.device_ids}
                degree = max(1, min(len(distinct), op.num_tables, ndev))
                dtyp = pcs[0].device_type
                if any(pc.device_type != dtyp for pc in pcs):
                    log_model.warning(
                        "per-table strategies mix device types %s; the "
                        "fused embedding %r uses %r for all tables",
                        sorted({pc.device_type for pc in pcs}), op.name,
                        dtyp)
                # per-table ZCM marks host-RESIDENT storage
                # (strategy.proto:11-14); any table marked ZCM makes the
                # fused op host-resident — dropping it here would silently
                # fall back to HBM tables and OOM the >HBM configs this
                # path exists for
                zcm = ["ZCM" in pc.memory_types for pc in pcs]
                mem = ("ZCM",) if any(zcm) else ()
                if any(zcm) and not all(zcm):
                    log_model.warning(
                        "per-table strategies mark only %d/%d tables ZCM; "
                        "the fused embedding %r stores ALL tables "
                        "host-resident (fusion constraint)",
                        sum(zcm), len(zcm), op.name)
                # per-table PARAM-axis (row-shard) degrees fuse to the
                # largest requested: rows of every table shard over that
                # many devices with all-to-all lookup routing, output
                # data-parallel over the whole mesh
                pd = max((getattr(pc, "param_degree", 1) for pc in pcs),
                         default=1)
                if pd > 1 and not mem:
                    batch = op.inputs[0].shape[0]
                    ds = ndev if batch % max(ndev, 1) == 0 else 1
                    # skew policies fuse like the degree: dedup if any
                    # table asked for it, the largest hot fraction wins
                    exch = ("dedup" if any(
                        getattr(pc, "exchange", "dense") == "dedup"
                        for pc in pcs) else "dense")
                    frac = max((getattr(pc, "hot_fraction", 0.0)
                                for pc in pcs), default=0.0)
                    ovl = any(getattr(pc, "overlap", False)
                              for pc in pcs)
                    strategies[op.name] = ParallelConfig(
                        (ds, 1, 1), device_type=dtyp, param_degree=pd,
                        exchange=exch, hot_fraction=frac, overlap=ovl)
                    continue
                strategies[op.name] = ParallelConfig(
                    (1, degree, 1), device_type=dtyp, memory_types=mem)
                # honor the per-table device assignment, not just its
                # degree: group tables by their strategy device so
                # block-sharding the stacked dim lands table i exactly on
                # device_ids[i] (reference round-robin placement,
                # dlrm_strategy.cc:242-296)
                dev_of = [pc.device_ids[0] if pc.device_ids else None
                          for pc in pcs]
                if len(emb_keys) == op.num_tables and None not in dev_of:
                    devs = sorted(set(dev_of))
                    if hasattr(op, "set_device_groups") and len(devs) > 1:
                        # concatenated-rows form: UNEVEN per-table
                        # placement is honored exactly by grouping the
                        # rows by device with per-group padding
                        before = op.total_rows
                        op.set_device_groups(dev_of)
                        if op.total_rows > 1.25 * before:
                            log_model.warning(
                                "honoring per-table device placement "
                                "pads %r from %d to %d rows (+%d%%): "
                                "groups pad to the LARGEST device's row "
                                "count — skewed placements cost memory",
                                op.name, before, op.total_rows,
                                round(100 * (op.total_rows / before - 1)))
                        if len(devs) != ndev:
                            log_model.warning(
                                "strategy places tables on %d devices "
                                "but the mesh has %d; row blocks land "
                                "in device order, placement is "
                                "approximate", len(devs), ndev)
                    elif hasattr(op, "set_table_order"):
                        per = op.num_tables // max(len(devs), 1)
                        if (len(devs) == degree
                                and all(dev_of.count(g) == per
                                        for g in devs)):
                            op.set_table_order(tuple(
                                i for g in devs
                                for i, dg in enumerate(dev_of)
                                if dg == g))
                        elif len(devs) > 1:
                            log_model.warning(
                                "per-table device_ids place %d tables "
                                "unevenly across %d devices (counts %s); "
                                "the stacked uniform embedding can only "
                                "block-shard equal groups — PLACEMENT "
                                "INTENT DROPPED, executing degree-%d "
                                "table sharding in declaration order",
                                op.num_tables, len(devs),
                                [dev_of.count(g) for g in devs], degree)
            elif not isinstance(op, fused_types) and i < len(emb_keys):
                strategies[op.name] = strategies[emb_keys[i]]
        for op in self.ops:
            if isinstance(op, InputOp) or op.name in strategies:
                continue
            generic = None
            if isinstance(op, Linear):
                generic = "linear"
            elif isinstance(op, Concat):
                generic = "concat"
            if generic and generic in strategies:
                pc = strategies[generic]
                nd = op.outputs[0].num_dims
                degs = tuple(pc.degrees[:nd]) + (1,) * (nd - len(pc.degrees))
                strategies[op.name] = ParallelConfig(
                    degs, device_type=pc.device_type,
                    device_ids=pc.device_ids)

    # --- sharding plumbing --------------------------------------------
    def _effective_pc(self, op: Op) -> ParallelConfig:
        """Clamp strategy degrees to divide the actual tensor dims.

        A rewrite is loud: warn by default, raise under
        FFConfig.strict_strategies — a searched/imported config that does
        not divide the real shapes would otherwise execute as a silently
        different strategy."""
        pc = self.strategies[op.name]
        shape = op.outputs[0].shape
        degs = list(pc.degrees)[:len(shape)]
        degs += [1] * (len(shape) - len(degs))
        asn = AxisAssigner(self.mesh)
        feas = asn.feasible_degrees()
        for i, d in enumerate(degs):
            d = min(d, shape[i])
            while d > 1 and (shape[i] % d != 0 or d not in feas):
                d -= 1
            degs[i] = max(d, 1)
        eff = ParallelConfig(tuple(degs), pc.device_type, pc.device_ids)
        requested = tuple(pc.degrees)[:len(shape)]
        requested += (1,) * (len(shape) - len(requested))
        if tuple(degs) != requested and not op.raw_degree_semantics:
            msg = (f"strategy for {op.name!r} requests degrees {requested} "
                   f"but output shape {shape} / mesh {tuple(self.mesh.shape.values())} "
                   f"only admits {tuple(degs)}; executing the clamped config")
            if getattr(self.config, "strict_strategies", False):
                raise ValueError(msg)
            log_model.warning(msg)
        return eff

    def _build_shardings(self):
        asn = AxisAssigner(self.mesh)
        self._out_sharding: Dict[int, NamedSharding] = {}   # tensor.guid ->
        self._param_sharding: Dict[str, Dict[str, NamedSharding]] = {}
        # ops host-offloaded by a hetero strategy (device_type "CPU",
        # reference dlrm_strategy_hetero.cc:28-36): their compute runs under
        # compute_on("device_host"), with operands staged HBM→host per step —
        # the analog of the reference's zero-copy-memory staging
        # (embedding.cu:280-283).
        self._host_offload_ops: set = set()
        # HOST-RESIDENT tables (reference hetero semantics proper: tables
        # STORED in CPU RAM and looked up there, embedding_avx2.cc +
        # dlrm_strategy_hetero.cc:28-49 — the capability that lets
        # DLRM-Terabyte run on few chips). XLA memory-kind shardings crash
        # this build's partitioner, so residency is explicit instead: the
        # table lives in model.host_params as numpy, the wrapper gathers
        # rows on the host before each step, the jitted step consumes them
        # via the overrides mechanism and returns their cotangents, and
        # the wrapper applies the touched-rows SGD scatter on the host.
        # Selected per op by strategy memory_types ZCM (strategy.proto:
        # 11-14) or globally by FFConfig.host_resident_tables.
        hres: set = set()
        force_host = bool(getattr(self.config, "host_resident_tables",
                                  False))
        for op in self.ops:
            if isinstance(op, InputOp) or not hasattr(op, "host_lookup"):
                continue
            raw = self.strategies.get(op.name)
            if force_host or (raw is not None
                              and "ZCM" in raw.memory_types):
                hres.add(op.name)
        self._host_resident_ops = hres
        # per-op quantized-storage policies (quant/), re-resolved per
        # compile (configure_quant fills it; non-default policies only)
        self._quant_policies = {}

        def spec_from_axes(axes_per_dim):
            return NamedSharding(self.mesh,
                                 AxisAssigner.axes_to_spec(axes_per_dim))

        for op in self.ops:
            if isinstance(op, InputOp):
                continue
            pc = self._effective_pc(op)
            if pc.device_type == "CPU" and op.name not in hres:
                self._host_offload_ops.add(op.name)
            # row/PARAM-axis sharding for embedding tables (strategy
            # param_degree > 1): resolve the all-to-all routing plan
            # BEFORE output/param axes — both consult it
            if hasattr(op, "_row_shard_geometry"):
                from ..ops.embedding import configure_row_shard
                configure_row_shard(op, self.strategies.get(op.name))
            # quantized-storage policy for embedding tables (strategy
            # quant_dtype / --emb-dtype): resolved beside the row-shard
            # plan so search, serving, and the publisher read one policy
            if hasattr(op, "host_lookup"):
                from ..ops.embedding import configure_quant
                configure_quant(op, self.strategies.get(op.name))
            try:
                out_axes = op.output_axes(
                    pc, asn, raw_pc=self.strategies.get(op.name, pc))
            except ValueError:
                msg = (f"strategy for {op.name!r} degrees {pc.degrees} are "
                       f"not jointly assignable on mesh "
                       f"{dict(self.mesh.shape)}; executing replicated")
                if getattr(self.config, "strict_strategies", False):
                    raise ValueError(msg)
                log_model.warning(msg)
                pc = ParallelConfig((1,) * op.outputs[0].num_dims)
                out_axes = asn.assign(pc.degrees)
            self._op_pc = getattr(self, "_op_pc", {})
            self._op_pc[op.name] = pc
            # ops that implement their own collectives (ring attention)
            # need the resolved config + the mesh axes of their seq dim
            op._compiled_pc = pc
            op._seq_axes = tuple(out_axes[1]) if len(out_axes) > 1 else ()
            for t in op.outputs:
                axes = list(out_axes[:t.num_dims])
                axes += [()] * (t.num_dims - len(axes))
                shape = t.shape
                if t.physical == "nhwc" and t.num_dims == 4:
                    # constraints apply to the CONCRETE (NHWC) array:
                    # permute the logical NCHW axis assignment to match
                    axes = [axes[0], axes[2], axes[3], axes[1]]
                    shape = (shape[0], shape[2], shape[3], shape[1])
                # divisibility against the actual axis products (output_axes
                # overrides may differ from the positional degrees)
                sizes = [int(np.prod([self.mesh.shape[a] for a in ax]))
                         if ax else 1 for ax in axes]
                ok = all(shape[i] % s == 0 for i, s in enumerate(sizes))
                self._out_sharding[t.guid] = (
                    spec_from_axes(axes) if ok else
                    NamedSharding(self.mesh, PartitionSpec()))
            if op.param_defs() and op.name not in hres:
                # raw_pc = the UNclamped strategy, for ops whose param
                # sharding keys off the requested (not shape-clamped)
                # degrees — e.g. the concatenated-rows embedding row-shards
                # on ANY requested table parallelism even when the output
                # table dim can't split evenly
                p_axes = op.param_axes(
                    pc, out_axes, raw_pc=self.strategies.get(op.name, pc))
                self._param_sharding[op.name] = {
                    pname: spec_from_axes(axes)
                    for pname, axes in p_axes.items()}

        self._propagate_host_offload_to_views()
        if len(self._host_offload_ops) > 3:
            import jax as _jax
            if _jax.default_backend() == "tpu":
                import warnings
                warnings.warn(
                    f"{len(self._host_offload_ops)} ops are host-offloaded; "
                    "this TPU compiler build is known to crash (SIGABRT) on "
                    "many separate host-compute regions. Prefer the fused "
                    "stacked-embedding form (build_dlrm "
                    "fuse_embeddings=True), which keeps one host region.")

        # model inputs: shard the sample dim over all mesh axes when possible
        flat_axes = tuple(self.mesh.axis_names)
        ndev = int(np.prod([self.mesh.shape[a] for a in flat_axes]))
        for t in self.input_tensors:
            if t.shape[0] % ndev == 0 and ndev > 1:
                self._out_sharding[t.guid] = NamedSharding(
                    self.mesh, PartitionSpec(flat_axes))
            else:
                self._out_sharding[t.guid] = NamedSharding(
                    self.mesh, PartitionSpec())
        # label follows inputs
        lt = self.label_tensor
        if lt.shape[0] % ndev == 0 and ndev > 1:
            self._label_sharding = NamedSharding(self.mesh,
                                                 PartitionSpec(flat_axes))
        else:
            self._label_sharding = NamedSharding(self.mesh, PartitionSpec())

    # --- forward interpreter ------------------------------------------
    def _propagate_host_offload_to_views(self):
        """Pull zero-FLOP view ops (reshape/flat/transpose) into the host
        region when every producer of their inputs is host-offloaded.

        Views are free on either side of the boundary, but leaving them on
        the device puts the host→device transfer *before* the view, and
        this XLA build miscompiles the view's backward at that seam (a
        bitcast between the host buffer and the TPU tiled layout hits
        "Bitcast cannot have different shape sizes"). Running the view on
        the host moves the transfer after it, which compiles and keeps one
        boundary per host subgraph.
        """
        from ..ops.tensor_ops import Flat, Reshape, Transpose
        if not self._host_offload_ops:
            return
        for op in self.ops:  # construction order is topological
            if not isinstance(op, (Reshape, Flat, Transpose)):
                continue
            producers = [t.owner_op for t in op.inputs]
            if producers and all(
                    p is not None and p.name in self._host_offload_ops
                    for p in producers):
                self._host_offload_ops.add(op.name)

    def _forward_env(self, params, op_state, batch: Dict[str, Any],
                     training: bool, rng, overrides: Optional[Dict] = None,
                     only_ops: Optional[set] = None):
        """Run the graph, returning tensor.guid -> value and new op_state.

        `overrides` maps op name -> precomputed output value; the op's
        compute is skipped and the value used instead (the sparse-update
        path threads embedding outputs through here so jax.grad yields
        their cotangents without touching the tables). `only_ops` restricts
        evaluation to a subset of ops (ancestor subgraphs)."""
        import contextlib

        env: Dict[int, Any] = {}
        new_state: Dict[str, Any] = {}
        constrain = jax.lax.with_sharding_constraint
        host_ops = getattr(self, "_host_offload_ops", set())
        # under bf16 compute, float inputs enter the graph in bf16 so the
        # WHOLE activation stream (ops preserve their input dtype) flows at
        # half the HBM bytes; fp32 stats/accumulations inside ops keep
        # their precision. No-op under the default f32 compute dtype.
        cast_bf16 = (jnp.dtype(self.compute_dtype)
                     == jnp.dtype(jnp.bfloat16))
        for t in self.input_tensors:
            if t.name in batch:   # host-only inputs are popped pre-jit
                v = batch[t.name]
                if cast_bf16 and jnp.issubdtype(jnp.dtype(t.dtype),
                                                jnp.floating):
                    v = v.astype(self.compute_dtype)
                env[t.guid] = v
        for op in self.ops:
            if isinstance(op, InputOp):
                continue
            if only_ops is not None and op.name not in only_ops:
                continue
            if overrides and op.name in overrides:
                t = op.outputs[0]
                v = overrides[op.name]
                sh = self._out_sharding.get(t.guid)
                env[t.guid] = constrain(v, sh) if sh is not None else v
                continue
            # physical-layout boundary: ops that didn't opt into NHWC get
            # their conv-stack inputs transposed back to logical NCHW
            # (ops/conv.py module docstring)
            accepts_nhwc = getattr(op, "_accepts_nhwc_inputs", False)
            xs = []
            for t in op.inputs:
                v = env[t.guid]
                if t.physical == "nhwc" and not accepts_nhwc:
                    v = jnp.transpose(v, (0, 3, 1, 2))
                xs.append(v)
            p = params.get(op.name, {})
            host = op.name in host_ops
            if host:
                # hetero host offload (reference CPU device_type +
                # embedding_avx2.cc CPU kernels): run this op's compute on
                # the host; operands are explicitly staged HBM→host→HBM,
                # the analog of the reference's zero-copy-memory staging
                # (embedding.cu:280-283)
                from jax.experimental.compute_on import compute_on
                ctx = compute_on("device_host")
                xs = [_to_memory(x, "host") for x in xs]
                p = {pn: _to_memory(v, "host") for pn, v in p.items()}
            else:
                ctx = contextlib.nullcontext()
            # the op's name in the compiled step's metadata (autodiff makes
            # it jvp(ff.<name>) / transpose(jvp(ff.<name>)) for the backward)
            scope = jax.named_scope(f"ff.{op.name}")
            # a block op keeps its inputs for the backward, not its
            # insides: the backward runs its forward again
            remat = (jax.checkpoint
                     if training and getattr(op, "recompute", False)
                     else lambda f: f)
            if hasattr(op, "apply_with_state"):
                st = op_state.get(op.name, {})
                if host:
                    st = jax.tree.map(lambda v: _to_memory(v, "host"), st)
                with ctx, scope:
                    outs, st2 = remat(
                        lambda p_, st_, xs_, op=op: op.apply_with_state(
                            p_, st_, xs_, training=training, rng=rng))(
                                p, st, xs)
                if host:
                    st2 = jax.tree.map(lambda v: _to_memory(v, "device"),
                                       st2)
                new_state[op.name] = st2
            else:
                with ctx, scope:
                    outs = remat(
                        lambda p_, xs_, op=op: op.apply(
                            p_, xs_, training=training, rng=rng))(p, xs)
            if host:
                outs = [_to_memory(o, "device") for o in outs]
            for t, v in zip(op.outputs, outs):
                sh = self._out_sharding.get(t.guid)
                if sh is not None:
                    v = constrain(v, sh)
                env[t.guid] = v
        return env, new_state

    # --- jitted steps --------------------------------------------------
    def _select_sparse_update_ops(self):
        """Embedding-type ops whose tables take a touched-rows-only
        update: plain SGD goes through the state-free sparse_sgd_update;
        momentum/weight-decay SGD and Adam go through the STATEFUL lazy
        sparse_opt_update (touched-rows state, lazily-applied decay) —
        the reference's Adam world pays a full dense table stream
        otherwise (optimizer_kernel.cu:110+). Disabled by
        config.sparse_embedding_update=False (--dense-embedding-update)."""
        from ..core.optimizers import AdamOptimizer
        from ..ops.embedding import (Embedding, EmbeddingBagConcat,
                                     EmbeddingBagStacked)
        if not getattr(self.config, "sparse_embedding_update", True):
            return []
        opt = self.optimizer
        plain = (isinstance(opt, SGDOptimizer) and opt.momentum == 0.0
                 and opt.weight_decay == 0.0)
        stateful = ((isinstance(opt, SGDOptimizer) and not plain)
                    or isinstance(opt, AdamOptimizer))
        if not (plain or stateful):
            return []
        host = (getattr(self, "_host_offload_ops", set())
                | getattr(self, "_host_resident_ops", set()))
        ops = [op for op in self.ops
               if isinstance(op, (Embedding, EmbeddingBagStacked,
                                  EmbeddingBagConcat))
               and op.supports_sparse_update() and op.name not in host]
        if stateful:
            ops = [op for op in ops if hasattr(op, "sparse_opt_update")]
        return ops

    def _ancestor_op_names(self, targets) -> set:
        out: set = set()

        def visit(op):
            if isinstance(op, InputOp) or op.name in out:
                return
            out.add(op.name)
            for t in op.inputs:
                if t.owner_op is not None:
                    visit(t.owner_op)

        for op in targets:
            visit(op)
        return out

    def _build_steps(self):
        # drop any AOT executables compiled against the previous step
        # function (a re-compile() with a new optimizer/loss/strategies
        # must not keep training with the old one). This also runs on
        # every elastic reshard (recover() re-enters compile()), so
        # old-mesh executables can never serve a post-reshard dispatch.
        from collections import OrderedDict
        self._train_step_execs = {}
        self._superstep_execs = {}
        self._eval_step_execs = OrderedDict()
        # fit()'s pace probe, by the step's shape key: a new step
        # function or a new mesh is asked again
        self._pace: Dict[tuple, _Pace] = {}
        policy = getattr(self.config, "anomaly_policy", "none") or "none"
        if policy not in ("none", "skip_step", "rollback", "raise"):
            raise ValueError(
                f"anomaly_policy must be none|skip_step|rollback|raise, "
                f"got {policy!r}")
        self._anomaly_policy = policy
        sentinel = policy != "none"
        plain_loss = losses_mod.loss_fn(self.loss_type)
        row_w = self.loss_weights
        if row_w is not None:
            plain_loss = functools.partial(plain_loss, weights=row_w)

        def loss_f(logits, labels):
            with jax.named_scope("ff.loss"):
                return plain_loss(logits, labels)

        def opt_update(p, grads, state):
            with jax.named_scope("ff.optimizer"):
                return self.optimizer.update(p, grads, state)
        logits_guid = self._logits_tensor.guid
        preds_guid = self._preds_tensor.guid
        metric_names = self.metrics
        loss_type = self.loss_type
        sparse_ops = self._select_sparse_update_ops()
        self._sparse_update_ops = [op.name for op in sparse_ops]
        anc_names = self._ancestor_op_names(sparse_ops)
        # conv-final models: env values are NHWC-physical; loss/metrics
        # compare against logical-NCHW labels
        logits_nhwc = self._logits_tensor.physical == "nhwc"
        preds_is_nhwc = self._preds_tensor.physical == "nhwc"

        def _env_logits(env):
            v = env[logits_guid]
            return jnp.transpose(v, (0, 3, 1, 2)) if logits_nhwc else v

        def _env_preds(env):
            v = env[preds_guid]
            return jnp.transpose(v, (0, 3, 1, 2)) if preds_is_nhwc else v
        host_ops = [op for op in self.ops
                    if op.name in getattr(self, "_host_resident_ops", set())]
        self._host_resident_list = host_ops
        for op in host_ops:
            for t in op.inputs:
                if t.owner_op is not None and not isinstance(t.owner_op,
                                                             InputOp):
                    raise ValueError(
                        f"host-resident table op {op.name!r} must consume "
                        f"a model input directly (use the fused DLRM "
                        f"embedding layout)")
        from ..core.optimizers import AdamOptimizer
        if host_ops and not isinstance(self.optimizer,
                                       (SGDOptimizer, AdamOptimizer)):
            raise ValueError(
                "host-resident tables support SGD (plain/momentum/"
                "weight-decay) and Adam — stateful optimizers take the "
                "lazy touched-rows host update")
        for op in host_ops:
            if (getattr(op, "aggr", None) == "none"
                    and not getattr(op, "host_aggr_none_ok", False)):
                raise ValueError(
                    f"host-resident table op {op.name!r}: aggr='none' "
                    f"is not implemented on the host path for this op")
        # inputs consumed ONLY by host-resident ops never need to touch the
        # device: the wrapper reads them on the host for the gather/scatter
        # and the jitted step sees only the override values
        consumers_of: Dict[str, List[Op]] = {}
        for op in self.ops:
            if isinstance(op, InputOp):
                continue
            for t in op.inputs:
                if t.owner_op is not None and isinstance(t.owner_op, InputOp):
                    consumers_of.setdefault(t.name, []).append(op)
        hres_names = {op.name for op in host_ops}
        self._host_only_inputs = {
            name for name, cons in consumers_of.items()
            if cons and all(c.name in hres_names for c in cons)}

        def train_step(params, opt_state, op_state, msums, batch, step,
                       host_emb=None):
            rng = jax.random.fold_in(jax.random.PRNGKey(self.config.seed),
                                     step)

            host_cts = None
            if sparse_ops or host_ops:
                sparse_names = {op.name for op in sparse_ops}
                p_dense = {k: v for k, v in params.items()
                           if k not in sparse_names}
                # phase A (no grad): index pipelines, then the embedding
                # lookups evaluated DIRECTLY so ops can hand their
                # forward-gather residuals to the write-only sparse update
                # (apply_with_fwd)
                anc_env, _ = self._forward_env(
                    params, op_state, batch, True, rng,
                    only_ops=set(anc_names) - sparse_names)
                emb_vals, emb_fwd = {}, {}
                for op in sparse_ops:
                    xs_ = [anc_env[t.guid] for t in op.inputs]
                    f = getattr(op, "apply_with_fwd", None)
                    with jax.named_scope(f"ff.{op.name}"):
                        if f is not None:
                            outs, fwd = f(params[op.name], xs_, rng=rng)
                        else:
                            outs, fwd = op.apply(params[op.name], xs_,
                                                 training=True,
                                                 rng=rng), None
                    v = outs[0]
                    sh = self._out_sharding.get(op.outputs[0].guid)
                    if sh is not None:
                        v = jax.lax.with_sharding_constraint(v, sh)
                    emb_vals[op.name] = v
                    anc_env[op.outputs[0].guid] = v
                    if fwd is not None:
                        emb_fwd[op.name] = fwd
                if host_ops:
                    # host-gathered rows enter as plain inputs; their
                    # cotangents leave for the wrapper's host scatter
                    emb_vals = {**emb_vals, **(host_emb or {})}

                # phase B: differentiate the rest of the graph w.r.t. the
                # dense params AND the embedding outputs; the tables never
                # enter the autodiff, so no table-sized dense gradient is
                # ever materialized
                def objective(pd, ev, st):
                    env, st2 = self._forward_env(pd, st, batch, True, rng,
                                                 overrides=dict(ev))
                    loss = loss_f(_env_logits(env), batch["label"])
                    return loss, (_env_preds(env), st2)

                (loss, (preds, st2)), (gd, gev) = jax.value_and_grad(
                    objective, argnums=(0, 1), has_aux=True)(
                        p_dense, emb_vals, op_state)
                grad_leaves = jax.tree.leaves((gd, gev))
                # the optimizer state for sparse tables is NOT part of the
                # dense update: split it out, update it touched-rows-only
                # below, and merge back (keeps one opt_state pytree for
                # checkpoints/sharding)
                slab_names = self.optimizer.sparse_slab_names()
                dense_state = {}
                sparse_state = {}
                for k, sub in opt_state.items():
                    if k in slab_names and isinstance(sub, dict):
                        dense_state[k] = {pk: pv for pk, pv in sub.items()
                                          if pk not in sparse_names}
                        sparse_state[k] = {pk: pv for pk, pv in sub.items()
                                           if pk in sparse_names}
                    else:
                        dense_state[k] = sub
                new_params, new_opt = opt_update(p_dense, gd, dense_state)
                stateful = bool(slab_names) or (
                    isinstance(self.optimizer, SGDOptimizer)
                    and self.optimizer.weight_decay != 0.0)
                pre_step = opt_state.get("step",
                                         jnp.zeros((), jnp.int32))
                for op in sparse_ops:
                    xs = [anc_env[t.guid] for t in op.inputs]
                    with jax.named_scope(f"ff.update.{op.name}"):
                        if stateful:
                            # the whole per-param slab dict goes in (the
                            # hybrid placement splits an embedding into
                            # kernel + hot_kernel, each with its own state)
                            slabs = {k: dict(sparse_state[k][op.name])
                                     for k in slab_names}
                            new_k, new_slabs = op.sparse_opt_update(
                                params[op.name], xs, gev[op.name],
                                self.optimizer, slabs, pre_step,
                                fwd=emb_fwd.get(op.name))
                            new_params[op.name] = new_k
                            for k in slab_names:
                                ns = new_slabs[k]
                                new_opt[k][op.name] = (
                                    ns if isinstance(ns, dict)
                                    else {"kernel": ns})
                        else:
                            new_params[op.name] = op.sparse_sgd_update(
                                params[op.name], xs, gev[op.name],
                                self.optimizer.lr, fwd=emb_fwd.get(op.name))
                if host_ops:
                    host_cts = {op.name: gev[op.name] for op in host_ops}
            else:
                def objective(p, st):
                    env, st2 = self._forward_env(p, st, batch, True, rng)
                    loss = loss_f(_env_logits(env), batch["label"])
                    return loss, (_env_preds(env), st2)

                (loss, (preds, st2)), grads = jax.value_and_grad(
                    objective, has_aux=True)(params, op_state)
                grad_leaves = jax.tree.leaves(grads)
                new_params, new_opt = opt_update(params, grads, opt_state)
            # quantized storage, stochastic_rounding rule: re-quantize
            # the updated tables IN the step (master_weight keeps the
            # exact fp32 master — no requant, bit-identical to fp32
            # training; quantization happens at storage boundaries)
            new_params = self._requant_sr_params(new_params, rng)
            # one scope for the step's bookkeeping: sentinel, metrics, sums
            with jax.named_scope("ff.metrics"):
                # anomaly sentinel: ONE on-device finiteness predicate over the
                # loss and the global gradient norm. Under any active policy
                # the non-finite update is suppressed ON DEVICE (jnp.where
                # against the pre-step values — both live inside the step, so
                # donation costs nothing), keeping params/opt/op-state clean
                # without a host sync; rollback/raise additionally read the
                # flag back at the step boundary (train_batch_device).
                step_ok = None
                if sentinel:
                    gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in grad_leaves)
                    gnorm = jnp.sqrt(gsq)
                    step_ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)

                    def _keep(new, old):
                        return jax.tree.map(
                            lambda n, o: jnp.where(step_ok, n, o), new, old)
                    new_params = _keep(new_params, params)
                    new_opt = _keep(new_opt, opt_state)
                    st2 = _keep(st2, op_state)
                # CCE metrics expect probabilities; when the graph doesn't end
                # in a Softmax op, preds are raw logits — normalize them here
                if "crossentropy" in loss_type and preds_guid == logits_guid:
                    mpreds = jax.nn.softmax(preds.astype(jnp.float32), axis=-1)
                else:
                    mpreds = preds
                mets = metrics_mod.compute_metrics(
                    metric_names, loss_type, mpreds, batch["label"], row_w)
                # accumulate running sums ON DEVICE inside the step (the
                # reference accumulates in device memory with atomics and folds
                # once per epoch, metrics_functions.cu:57-135; host-side
                # accumulation would dispatch extra tiny kernels every step)
                if sentinel:
                    # a skipped step contributes nothing (NaNs would poison
                    # the epoch's running sums irreversibly)
                    new_msums = {k: msums[k]
                                 + jnp.where(step_ok, v, jnp.zeros_like(v))
                                 for k, v in mets.items()}
                else:
                    new_msums = {k: msums[k] + v for k, v in mets.items()}
                # what the host may look at after the step, as ONE fresh
                # output: every other output is a carry that takes over
                # its input's buffer (a fresh 4-byte output is a device
                # allocation the dispatch waits for, ROADMAP S1)
                mets["loss"] = loss
                if sentinel:
                    mets["anomaly"] = ~step_ok
                    mets["grad_norm"] = gnorm
                vec = jax.lax.with_sharding_constraint(
                    metrics_mod.pack_step_scalars(mets, self._step_keys),
                    NamedSharding(self.mesh, PartitionSpec()))
            # the step counter stays device-resident across calls (feeding
            # a fresh host int every step would be one H2D transfer/step)
            # and is donated like the other carries
            outs = (new_params, new_opt, st2, new_msums, step + 1, vec)
            # host-resident tables only: their cotangents leave for the
            # wrapper's host scatter as a seventh output
            return outs if host_cts is None else outs + (host_cts,)

        def eval_step(params, op_state, batch, host_emb=None):
            env, _ = self._forward_env(params, op_state, batch, False, None,
                                       overrides=host_emb)
            # _env_preds exposes the user-facing logical NCHW form
            return _env_preds(env)

        def train_superstep(params, opt_state, op_state, msums, sbatch,
                            step):
            """K fused steps in ONE executable: lax.scan over the
            stacked [K, ...] megabatch with the train-step body,
            donating the carries. One host→device dispatch then trains
            K steps — deleting K-1 of every K per-dispatch overheads
            (ROADMAP S1). The per-step RNG fold,
            on-device sentinel suppression, and metric-sum accumulation
            all run unchanged inside the scan, so K>1 is bit-identical
            to K sequential dispatches of the same batches."""
            def body(carry, bk):
                p, o, st, ms, sp = carry
                p, o, st, ms, sp, vec = train_step(p, o, st, ms, bk, sp)
                return (p, o, st, ms, sp), vec

            # the K steps' vectors stacked [K, n]: the boundary policies
            # read the per-step columns (metrics, anomaly flags), the
            # boundary-facing scalars (fit's loss print) are its LAST row
            # (`StepMetrics`, on the host)
            (p, o, st, ms, sp), stacked = jax.lax.scan(
                body, (params, opt_state, op_state, msums, step), sbatch)
            return p, o, st, ms, sp, stacked

        donate = (0, 1, 2, 3, 5)
        self._train_step = jax.jit(train_step, donate_argnums=donate)
        self._superstep_fn = jax.jit(train_superstep, donate_argnums=donate)
        self._eval_step = jax.jit(eval_step)
        # discover the metric-sum pytree structure with tiny dummies (the
        # keys depend on metric names + loss type only)
        dummy_preds = jnp.zeros((2,) + tuple(self._preds_tensor.shape[1:]),
                                jnp.float32)
        if self.loss_type == losses_mod.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY:
            dummy_labels = jnp.zeros((2, 1), jnp.int32)
        else:
            dummy_labels = jnp.zeros(dummy_preds.shape, jnp.float32)
        self._msums_keys = sorted(metrics_mod.compute_metrics(
            metric_names, loss_type, dummy_preds, dummy_labels).keys())
        # the columns of the step program's metrics vector, fixed here so
        # the host splits what the trace packed (an executable loaded
        # from a cache was never traced in this process)
        self._step_keys = tuple(self._msums_keys) + ("loss",) + (
            ("anomaly", "grad_norm") if sentinel else ())
        self._step_index = {k: i for i, k in enumerate(self._step_keys)}

    def _zero_msums(self):
        # committed replicated: the AOT executable cache requires inputs
        # with deterministic shardings (uncommitted scalars would pin to
        # device 0 and mismatch the executable on the next call)
        rep = NamedSharding(self.mesh, PartitionSpec())
        return {k: put_global(np.zeros((), np.float32), rep)
                for k in self._msums_keys}

    # ------------------------------------------------------------------
    # runtime verbs (reference model.cc:942-993)
    # ------------------------------------------------------------------
    # ------------------------------------------------------------------
    # quantized embedding storage (quant/)
    # ------------------------------------------------------------------
    def quant_policies(self):
        """Per-op NON-DEFAULT quantized-storage policies resolved at
        compile ({op name: QuantPolicy}) — what the delta publisher, the
        serving caches/shard tier, and the checkpoint manifest consume."""
        return dict(getattr(self, "_quant_policies", {}) or {})

    def _sr_quant_ops(self):
        """Ops whose policy re-quantizes in the training step
        (stochastic_rounding with a non-fp32 storage dtype), in
        deterministic order for the per-op RNG fold."""
        return sorted(
            (name, pol) for name, pol in self.quant_policies().items()
            if pol.update_rule == "stochastic_rounding"
            and pol.dtype != "fp32"
            and name not in getattr(self, "_host_resident_ops", set()))

    def _requant_sr_params(self, new_params, rng):
        """The in-step stochastic-rounding hook: re-quantize every
        updated table of an SR-policy op (kernel + hybrid hot_kernel) so
        the stored parameter is always the exact fp32 image of its
        quantized representation. Runs inside the jitted step (and thus
        inside the superstep scan body) with a per-(step, op, param)
        folded key — deterministic per seed."""
        sr = self._sr_quant_ops()
        if not sr:
            return new_params
        from ..quant.codec import fake_quant_stochastic
        for i, (name, pol) in enumerate(sr):
            if name not in new_params:
                continue
            sub = dict(new_params[name])
            with jax.named_scope(f"ff.update.{name}"):   # part of the update
                for j, pname in enumerate(("kernel", "hot_kernel")):
                    if pname in sub:
                        k = jax.random.fold_in(rng, 0x51 + 2 * i + j)
                        sub[pname] = fake_quant_stochastic(
                            sub[pname], pol.dtype, k)
            new_params[name] = sub
        return new_params

    def _sr_policy_of(self, op_name: str):
        pol = self.quant_policies().get(op_name)
        if pol is None or pol.dtype == "fp32" \
                or pol.update_rule != "stochastic_rounding":
            return None
        return pol

    def _quant_init_device(self, op, p):
        """Under stochastic_rounding, training starts FROM the stored
        (quantized) representation: quantize-dequantize the fresh table
        once at init (nearest — SR at init would just add noise).
        master_weight inits stay exact fp32."""
        pol = self._sr_policy_of(op.name)
        if pol is None:
            return p
        from ..quant.codec import fake_quant
        return {n: (fake_quant(v, pol.dtype)
                    if n in ("kernel", "hot_kernel") else v)
                for n, v in p.items()}

    def _quant_init_host(self, op):
        pol = self._sr_policy_of(op.name)
        if pol is None:
            return
        from ..quant.codec import fake_quant_np
        tbl = self.host_params[op.name]
        if "kernel" in tbl:
            k = tbl["kernel"]
            tbl["kernel"] = fake_quant_np(
                k.reshape(-1, k.shape[-1]), pol.dtype).reshape(
                    k.shape).astype(np.float32)

    def init_layers(self, seed: Optional[int] = None):
        """Initialize parameters/optimizer/op state, sharded per strategy
        (reference init_layers launches per-op init tasks; initializer GPU
        tasks run at compile, model.cc:1028-1045)."""
        self._pick_conv_s2d()
        seed = self.config.seed if seed is None else seed
        key = jax.random.PRNGKey(seed)
        params: Dict[str, Any] = {}
        big_keys: Dict[str, Any] = {}    # ops initialized sharded, below
        op_state: Dict[str, Any] = {}
        hres = getattr(self, "_host_resident_ops", set())
        self.host_params: Dict[str, Dict[str, np.ndarray]] = {}
        self.host_opt_state: Dict[str, Dict[str, np.ndarray]] = {}
        multiproc = jax.process_count() > 1
        # init computation runs on a LOCAL device (jax.devices()[0] is not
        # addressable from other ranks of a multi-controller job)
        with jax.default_device(jax.local_devices()[0]):
            for i, op in enumerate(self.ops):
                if isinstance(op, InputOp):
                    continue
                if op.name in hres:
                    # table lives in host RAM, filled there (numpy) —
                    # never device_put (reference embedding_avx2.cc path)
                    self.host_params[op.name] = op.host_init(seed + i)
                    self._quant_init_host(op)
                    # stateful optimizers keep their table-shaped state
                    # slabs on the host too (lazy touched-rows update)
                    for slab in self.optimizer.sparse_slab_names():
                        self.host_opt_state.setdefault(op.name, {})[
                            slab] = np.zeros_like(
                                self.host_params[op.name]["kernel"])
                    continue
                if op.param_defs():
                    key, sub = jax.random.split(key)
                    if op.param_bytes() >= _SHARDED_INIT_BYTES:
                        big_keys[op.name] = sub
                        params[op.name] = None     # keeps the op order
                    else:
                        p = self._quant_init_device(op, op.init_params(sub))
                        shards = self._param_sharding.get(op.name, {})
                        rep = NamedSharding(self.mesh, PartitionSpec())
                        params[op.name] = {
                            n: put_global(v, shards.get(n) or rep)
                            for n, v in p.items()}
                if hasattr(op, "state_defs"):
                    key, sub = jax.random.split(key)
                    defs = op.state_defs()
                    keys = jax.random.split(sub, len(defs))
                    rep = NamedSharding(self.mesh, PartitionSpec())
                    op_state[op.name] = {
                        n: put_global(d.initializer(k, d.shape, d.dtype),
                                      rep)
                        for (n, d), k in zip(sorted(defs.items()), keys)}
            params.update(self._init_params_sharded(big_keys))
        self.params = params
        self.op_state = op_state
        if any("pairs" in st for st in op_state.values()):
            # the registry outlives a model: it gets a weak hold on this one
            import weakref
            from ..obs import metrics as obsm
            me = weakref.ref(self)
            obsm.register_collector(
                lambda: me()._obs_collect_experts() if me() else ())
        # multi-controller: build optimizer state as one SPMD program so
        # every leaf (incl. fresh scalars like Adam's step) is a global
        # array, never a rank-local committed one
        self.opt_state = (jax.jit(self.optimizer.init_state)(params)
                          if multiproc and params
                          else self.optimizer.init_state(params))
        self._step = 0
        self._step_dev = None
        self._msums = None
        return self

    def expert_stats(self) -> Dict[str, Dict[str, np.ndarray]]:
        """{expert op: {"tokens", "pairs" (experts held,), "rows"}}: the
        expert ops' cumulative counters (ops/moe.py), read from the op
        state; for an op with a balance bias also "load" (all experts,)
        and the "bias" itself. The step reads back the bias alone."""
        return {name: {k: np.asarray(v) for k, v in st.items()}
                for name, st in (self.op_state or {}).items()
                if "pairs" in st}

    def _obs_collect_experts(self):
        """Registry collector: the expert counters as scrapeable samples."""
        for name, st in self.expert_stats().items():
            yield "ff_moe_tokens_total", {"op": name}, int(st["tokens"])
            yield "ff_moe_rows_total", {"op": name}, int(st["rows"])
            for e, n in enumerate(st["pairs"]):
                yield ("ff_moe_pairs_total",
                       {"op": name, "expert": str(e)}, int(n))
            for e, n in enumerate(st.get("load", ())):
                yield ("ff_moe_load_total",
                       {"op": name, "expert": str(e)}, int(n))
            if "bias" in st:
                yield ("ff_moe_bias_abs_max", {"op": name},
                       float(np.abs(st["bias"]).max()))

    def _init_params_sharded(self, op_keys):
        """Parameters of the LARGE ops (>= _SHARDED_INIT_BYTES), each born
        under its compiled sharding: ONE SPMD program runs their
        initializers and its outputs carry the shardings, so every device
        draws only its own shard (the random bits do not depend on the
        partitioning) and no device ever holds a whole table — the eager
        init on one device followed by a device_put, which small ops
        keep, peaked at 3x the 2 GB dlrm_random tables on chip 0. Same
        values either way (tests/test_distribution.py pins it)."""
        if not op_keys:
            return {}
        ops = {op.name: op for op in self.ops}
        order = {}     # each op's own param order, noted while tracing

        def init(keys):
            out = {}
            for name, k in keys.items():
                out[name] = self._quant_init_device(
                    ops[name], ops[name].init_params(k))
                order[name] = list(out[name])
            return out

        rep = NamedSharding(self.mesh, PartitionSpec())
        out_sh = {name: {n: self._param_sharding.get(name, {}).get(n) or rep
                         for n in p}
                  for name, p in jax.eval_shape(init, op_keys).items()}
        out = jax.jit(init, out_shardings=out_sh)(op_keys)
        # a jit returns its dicts key-sorted; give back the ops' own order
        return {name: {n: out[name][n] for n in order[name]}
                for name in op_keys}

    def _pick_conv_s2d(self):
        """Choose the conv stem lowering per FFConfig.conv_s2d: "on"
        forces space-to-depth on every eligible conv; "auto" measures
        both lowerings per eligible conv on the attached device and keeps
        the faster (the reference picks its conv algorithm the same way —
        by running candidates, conv_2d.cu:217)."""
        mode = getattr(self.config, "conv_s2d", "off")
        from ..ops.conv import Conv2D, measure_s2d_wins
        for op in self.ops:
            if not isinstance(op, Conv2D) or not op.s2d_eligible():
                continue
            # decisions are cached PER MODE: a re-init after the config
            # changed must not keep the previous mode's lowering
            if getattr(op, "_s2d_mode", None) == mode:
                continue
            op._use_s2d = (False if mode == "off"
                           else True if mode == "on"
                           else measure_s2d_wins(op))
            op._s2d_mode = mode
            op._s2d_decided = True
            if mode != "off":
                log_model.info("conv %s: space-to-depth lowering %s (%s)",
                               op.name, "ON" if op._use_s2d else "off",
                               mode)

    def _stage_input(self, arr, sharding):
        """Host batch -> global device array under the model's sharding.
        Multi-controller: every rank passes the SAME full host batch (the
        loaders keep the whole dataset per host, like the reference's
        per-node zero-copy residency, dlrm.cc:384-484) and jax extracts
        this rank's addressable shards — a plain device_put cannot target
        non-addressable devices."""
        if jax.process_count() > 1:
            arr = np.asarray(arr)
            return jax.make_array_from_process_local_data(
                sharding, arr, arr.shape)
        return jax.device_put(arr, sharding)

    def _device_batch(self, batch: Dict[str, np.ndarray],
                      with_label: bool = True) -> Dict[str, Any]:
        _san.note_jax_dispatch("batch staging device_put")
        out = {}
        puts: Dict[str, tuple] = {}   # name -> (host array, sharding)
        host_only = getattr(self, "_host_only_inputs", set())
        for t in self.input_tensors:
            if t.name in batch:
                if t.name in host_only:
                    # consumed only by host-resident tables: stays numpy
                    # (no H2D; the wrapper reads it for the host gather)
                    out[t.name] = np.asarray(batch[t.name])
                else:
                    puts[t.name] = (batch[t.name],
                                    self._out_sharding[t.guid])
        if with_label:
            lab = batch["label"]
            sh = self._label_sharding
            # the label tensor's shape can be a folded view of what the user
            # passes (e.g. NMT feeds (batch, seq) labels against
            # (batch*seq, 1) logits); re-check divisibility on the real array
            ndev = int(np.prod([self.mesh.shape[a]
                                for a in self.mesh.axis_names]))
            if lab.shape[0] % ndev != 0:
                sh = NamedSharding(self.mesh, PartitionSpec())
            puts["label"] = (lab, sh)
        if puts:
            out.update(self._put(puts))
        return out

    def _put(self, puts: Dict[str, tuple]) -> Dict[str, Any]:
        """{name: (host array, sharding)} on the device."""
        if jax.process_count() > 1:
            return {k: self._stage_input(v, sh)
                    for k, (v, sh) in puts.items()}
        # ONE batched device_put for the whole step input: the per-call
        # dispatch overhead (not the bytes) dominates small H2D puts, and
        # a streamed loop pays it every step — batching the puts measured
        # ~1.6x faster staging on the DLRM input dict
        # (dense+sparse+label)
        names = list(puts)
        return dict(zip(names, jax.device_put(
            [puts[k][0] for k in names], [puts[k][1] for k in names])))

    def train_batch(self, batch: Dict[str, np.ndarray]):
        """One fused train step (forward+backward+update). Returns the
        step's `StepMetrics`: a read-only mapping (`loss`, the metric
        sums, under a sentinel `anomaly` and `grad_norm`) over one device
        vector. The dispatch is async; the first value read waits for the
        step and brings the whole vector to the host, once."""
        return self.train_batch_device(self._device_batch(batch))

    def _ensure_step_state(self):
        """Lazy-init the device-resident step counter and metric sums that
        the jitted step threads through (single definition — warmup and
        hot loop must compile against identically-sharded inputs)."""
        if not getattr(self, "_msums", None):
            self._msums = self._zero_msums()
        if getattr(self, "_step_dev", None) is None:
            self._step_dev = put_global(
                np.asarray(self._step, np.int32),
                NamedSharding(self.mesh, PartitionSpec()))

    def _split_host_idx(self, device_batch: Dict):
        """(device_batch_for_jit, host_idx | None): indices for host-
        resident tables never ride PCIe — host-only inputs are kept numpy
        by _device_batch and popped before the jit call (np.asarray on an
        already-host array is free; on a staged device array it is the one
        unavoidable D2H)."""
        hres = getattr(self, "_host_resident_list", None)
        if not hres:
            return device_batch, None
        device_batch = dict(device_batch)
        host_idx = {}
        host_only = getattr(self, "_host_only_inputs", set())
        for op in hres:
            name = op.inputs[0].name
            host_idx[op.name] = np.asarray(device_batch[name])
            if name in host_only:
                device_batch.pop(name)
        return device_batch, host_idx

    def _exec_key(self, device_batch: Dict):
        """Executable-cache key for a staged batch. Stringifying shardings
        is the slow part, so memoize it by sharding-object identity (the
        model's sharding objects are long-lived)."""
        smemo = getattr(self, "_sharding_str_memo", None)
        if smemo is None:
            smemo = self._sharding_str_memo = {}

        def _shs(v):
            sh = getattr(v, "sharding", None)
            hit = smemo.get(id(sh))
            if hit is not None and hit[0] is sh:
                return hit[1]
            if len(smemo) > 256:
                smemo.clear()
            s = str(sh)
            # pin the sharding object so a GC'd id can't alias a
            # different sharding to a stale string
            smemo[id(sh)] = (sh, s)
            return s

        # numpy's dtype.name property is surprisingly slow (~µs each,
        # 3+ arrays x every step); memoize by the (singleton-ish,
        # hashable) dtype object
        dmemo = getattr(self, "_dtype_name_memo", None)
        if dmemo is None:
            dmemo = self._dtype_name_memo = {}

        def _dname(dt):
            n = dmemo.get(dt)
            if n is None:
                n = dmemo[dt] = dt.name
            return n

        return tuple(sorted(
            (k, v.shape, _dname(v.dtype), _shs(v))
            for k, v in device_batch.items()))

    # --- persistent warm caches (utils/warmcache.py) -------------------
    def attach_compile_cache(self, cache) -> None:
        """Attach a persistent :class:`~..utils.warmcache.CompileCache`
        (or a directory path) so AOT train/eval/superstep executables
        serialize to disk and later boots/recoveries load instead of
        recompiling. Survives ``compile()``/elastic reshards — the
        in-memory exec dicts reset, the disk cache persists."""
        if isinstance(cache, str):
            from ..utils.warmcache import CompileCache
            cache = CompileCache(cache)
        self._compile_cache = cache

    def attach_plan_cache(self, cache) -> None:
        """Attach a persistent :class:`~..utils.warmcache.PlanCache` so
        elastic ``recover()``/``expand()`` re-plans warm-start from disk
        instead of re-running the MCMC search."""
        if isinstance(cache, str):
            from ..utils.warmcache import PlanCache
            cache = PlanCache(cache)
        self._plan_cache = cache

    def compile_cache_stats(self) -> Optional[Dict[str, Any]]:
        cache = getattr(self, "_compile_cache", None)
        return None if cache is None else cache.stats()

    def _cached_compile(self, kind: str, shape_key, lower,
                        fresh: bool = False):
        """lower().compile() through the persistent CompileCache when
        one is attached: a hit deserializes the stored executable (ms)
        instead of recompiling (s); misses and EVERY invalid entry
        (torn, stale code, wrong mesh) compile fresh and re-store.
        `fresh=True` skips the lookup — the GSPMD
        recompile-on-sharding-disagree fallback must not re-load the
        very entry that just disagreed. Every step executable, built or
        loaded, comes from here, so this is where obs.trace gets its
        record of the program: the executable, the seconds lowering and
        compiling (or loading) took and which of the two it was. Nothing
        is read from the executable for it."""
        cache = getattr(self, "_compile_cache", None)
        with obstrace.span(f"compile/{kind}"):
            exec_ = None
            t0 = t1 = time.perf_counter()
            if cache is not None:
                ckey = cache.exec_key(kind, self, shape_key)
                if not fresh:
                    exec_ = cache.get(ckey, self.mesh.devices.flat)
            loaded = exec_ is not None
            if not loaded:
                t0 = time.perf_counter()
                lowered = lower()
                t1 = time.perf_counter()
                exec_ = lowered.compile()
            t2 = time.perf_counter()
            if cache is not None and not loaded:
                cache.put(ckey, exec_)
        obstrace.note_program(kind, exec_, key=shape_key, lower_s=t1 - t0,
                              compile_s=t2 - t1, loaded=loaded)
        if kind in obstrace.STEP_KINDS and obsmetrics.enabled():
            # once a compile, nothing a step: did every carry take over
            # its input's buffer, and is the metrics vector all that is
            # left (1)? Reading the program's text is the cost, so only
            # under --obs on; what the program needs and what it cost to
            # build is read from the records when someone scrapes
            fresh = obstrace.fresh_outputs(exec_)
            if fresh is not None:
                obsmetrics.gauge(
                    "ff_step_fresh_outputs",
                    "outputs of the newest step program that alias no "
                    "input", ("kind",)).set(fresh, kind=kind)
            obsmetrics.register_collector(obstrace.collect_step_programs)
        return exec_

    def step_memory(self) -> Dict[str, Dict[str, int]]:
        """{"train" | "superstep": `obs.trace.program_memory` of this
        model's newest step program of that kind}: the bytes of HBM the
        compiler counts for a step (`counted` = argument + output - alias
        + temp), which the runtime's `peak_bytes_in_use` does not show.
        A kind without a program yet, or whose executable gives no
        analysis, is left out. `ff_step_hbm_bytes` under `--obs on`."""
        out = {}
        for kind, attr in (("train", "_train_step_execs"),
                           ("superstep", "_superstep_execs")):
            execs = getattr(self, attr, None)    # None before compile()
            memory = (obstrace.program_memory(list(execs.values())[-1])
                      if execs else None)
            if memory is not None:
                out[kind] = memory
        return out

    def _executable(self, kind: str, execs: Dict, key, fn, args):
        """The AOT executable of the jitted `fn` for `key`: the one
        `execs` holds, or built against `args` and kept there. Builds,
        never runs: fit()'s warm-up calls it with the keys its loop will
        ask for."""
        exec_ = execs.get(key)
        if exec_ is None:
            exec_ = execs[key] = self._cached_compile(
                kind, key, lambda: fn.lower(*args))
        return exec_

    def _run_executable(self, kind: str, execs: Dict, key, fn, args,
                        span: Optional[str] = None, **span_args):
        """Outputs of `fn(*args)` through its executable for `key`, the
        call alone inside `span`. Calling the AOT executable directly is
        the hot loop's point — the pjit python dispatch re-validates the
        big param pytree every call, which costs more than a fast
        model's step — and the key is the batch signature, so
        alternating shapes (a remainder batch) each compile once. GSPMD
        may give step outputs different shardings than the initial
        inputs; one recompile against the propagated shardings reaches
        the fixed point (the sharding check runs before execution, so
        donated buffers are still intact, and `fresh` keeps the
        persistent cache from handing back the entry that just
        disagreed)."""
        _san.note_jax_dispatch(f"{kind} executable")
        exec_ = execs.get(key) or self._executable(kind, execs, key, fn,
                                                   args)
        with (obstrace.span(span, **span_args) if span
              else contextlib.nullcontext()):
            try:
                return exec_(*args)
            except ValueError as e:
                if not _sharding_mismatch(e):
                    raise
                exec_ = execs[key] = self._cached_compile(
                    kind, key, lambda: fn.lower(*args), fresh=True)
                return exec_(*args)

    def _step_args(self, batch: Dict) -> tuple:
        """What the train and superstep programs take, the host-table
        rows aside."""
        return (self.params, self.opt_state, self.op_state, self._msums,
                batch, self._step_dev)

    def _dispatch_faults(self, batch: Dict, k: int = 1) -> Dict:
        """The fault harness at a dispatch boundary, for the window of
        `k` steps about to be issued as one program. A device loss or
        return scheduled for ANY step of the window raises its typed
        exception BEFORE dispatch, so no state for the window is
        half-applied (a simulated preemption shrinks the runtime's view
        of the mesh by the LAST ndrop devices; they stay physically alive
        on a CPU test mesh — exactly how a lost peer looks from the
        surviving hosts). A scheduled NaN poisons that step's batch —
        only row s of a megabatch, the sibling steps of the scan stay
        clean — so NaNs flow through the REAL autodiff into the
        loss/grad-norm the sentinel watches (same shapes, dtypes and
        shardings: the cached executable holds). Called only under an
        active fault plan: the off path is the caller's one
        `faults.active()`."""
        for s in range(self._step, self._step + k):
            ndrop = faults.take_drop_device(s)
            if ndrop:
                devs = list(self.mesh.devices.flat)
                ndrop = min(ndrop, len(devs) - 1)
                raise MeshDegraded(
                    f"fault-injected loss of {ndrop} device(s) at step "
                    f"{self._step}" + (f" (superstep boundary, K={k})"
                                       if k > 1 else ""),
                    lost=devs[len(devs) - ndrop:],
                    surviving=devs[:len(devs) - ndrop])
        self._maybe_return_devices(k)
        for s in range(k):
            if faults.take_nan_grad(self._step + s):
                batch = faults.poison_batch(batch,
                                            row=s if k > 1 else None)
        return batch

    def _check_anomaly(self, step0: int, mets: Dict) -> None:
        """Under "rollback"/"raise", read back the sentinel's flag for
        the window that began at `step0` (one step's scalars, or a
        superstep's stacked [K] arrays) and raise at its FIRST faulting
        step. The readback is the one host sync these policies cost;
        skip_step never syncs. Every bad update was already suppressed on
        device, so state is clean whichever way the caller (fit's
        rollback loop, or the user) handles this."""
        if self._anomaly_policy not in ("rollback", "raise"):
            return
        flags = np.atleast_1d(np.asarray(mets["anomaly"]))
        if flags.any():
            idx = int(np.argmax(flags))
            raise AnomalyError(
                step=step0 + idx,
                loss=float(np.atleast_1d(np.asarray(mets["loss"]))[idx]),
                grad_norm=float(np.atleast_1d(np.asarray(
                    mets["grad_norm"]))[idx]))

    def _maybe_return_devices(self, k: int = 1) -> None:
        """Scale-UP detection at a dispatch boundary: when elastic
        expansion is enabled and the fault plan (or a registry poll)
        reports devices RETURNED at any of the next `k` steps (a fused
        superstep checks its whole window, like the drop hook), raise
        the typed :class:`MeshReturned` BEFORE dispatch — symmetric with
        the drop-device hook, so no state for this step is half-applied
        and fit()'s expansion resumes exactly where the shrink path
        does."""
        if not getattr(self.config, "elastic_expand", False):
            return
        nret = 0
        for s in range(max(int(k), 1)):
            nret += faults.take_return_device(self._step + s)
        if not nret:
            return
        in_mesh = {id(d) for d in self.mesh.devices.flat}
        avail = [d for d in jax.devices() if id(d) not in in_mesh]
        if not avail:
            log_model.warning(
                "fault-injected device return at step %d ignored: no "
                "device outside the current %d-device mesh", self._step,
                self.mesh.size)
            return
        returned = avail[:nret]
        raise MeshReturned(
            f"fault-injected return of {len(returned)} device(s) at "
            f"step {self._step}", returned=returned)

    def _attach_configured_caches(self, checkpoint_dir=None) -> None:
        """Open the persistent plan/compile caches per
        ``FFConfig.compile_cache_dir`` ("" = off, "auto" = next to the
        checkpoint manifest, else an explicit path) and attach them,
        keeping any caches the caller attached explicitly."""
        configured = getattr(self.config, "compile_cache_dir", "") or ""
        if not configured:
            return
        if (getattr(self, "_plan_cache", None) is not None
                and getattr(self, "_compile_cache", None) is not None):
            return
        from ..utils.warmcache import open_caches
        plan, comp = open_caches(checkpoint_dir, configured)
        if plan is not None and getattr(self, "_plan_cache", None) is None:
            self._plan_cache = plan
        if comp is not None and getattr(self, "_compile_cache",
                                        None) is None:
            self._compile_cache = comp

    def _stage_step(self, batch: Dict[str, np.ndarray],
                    with_label: bool = True) -> "StagedStep":
        """Fully stage one host batch for the jitted step: H2D put against
        the input shardings + the host-index split. Everything here is
        thread-safe jax/numpy, so the prefetch pipeline's staging thread
        runs it for step N+1 while step N executes (data/prefetch.py)."""
        db = self._device_batch(batch, with_label=with_label)
        db, host_idx = self._split_host_idx(db)
        return StagedStep(db, host_idx)

    def train_batch_device(self, device_batch: Dict, next_host_idx=None):
        """train_batch for a batch already staged on device (skips the
        host->device put; used by benchmark loops that pre-stage)."""
        device_batch, host_idx = self._split_host_idx(device_batch)
        return self._train_dispatch(device_batch, host_idx, next_host_idx)

    def train_batch_staged(self, staged: "StagedStep", next_host_idx=None):
        """train step for a StagedStep from `_stage_step` (the prefetch
        pipeline's item type). `next_host_idx` — the NEXT staged batch's
        host-table indices (or a zero-arg callable returning them, eval'd
        at scatter-launch time) — lets the async host-table worker stage
        the gather for step N+1 while step N executes on device (gather
        first, then this step's scatter: deterministic one-step
        staleness, see FFConfig.host_tables_async).

        A `_stage_superstep` megabatch item (`staged.k > 1`) routes to
        the fused K-step scan executable instead — one dispatch, k
        optimizer steps."""
        if getattr(staged, "k", 1) > 1:
            return self.train_superstep_device(staged.device_batch)
        return self._train_dispatch(staged.device_batch, staged.host_idx,
                                    next_host_idx)

    # --- fused supersteps ---------------------------------------------
    def resolve_superstep(self, batch_size: Optional[int] = None) -> int:
        """The superstep K this model's fit() would train with NOW, an
        epoch's length and `save_every` aside (`_auto_superstep`).

        FFConfig.superstep: 1 = the exact per-step dispatch; an int K>1
        fuses K steps per dispatch; "auto" (the default) fuses only a
        batch shape whose pace the host sets, as fit()'s own probe found
        (`_Pace`): the staging rule's K (`_staging_superstep`) once a
        verdict says so, 1 before and otherwise. The search prices the
        dispatch floor over this K (search/simulator.py). Host-resident-
        table models always resolve to 1, with a one-time warning when a
        K was asked for: their per-step host gather/scatter cannot run
        inside the fused scan yet."""
        raw = getattr(self.config, "superstep", 1)
        if raw in (None, "", 1, "1"):
            return 1
        if getattr(self, "_host_resident_list", None):
            if raw != "auto" and not getattr(
                    self, "_superstep_host_warned", False):
                self._superstep_host_warned = True
                log_model.warning(
                    "superstep=%s requested, but ops %s keep their "
                    "tables host-resident: the per-step host gather/"
                    "scatter cannot run inside the fused scan — falling "
                    "back to superstep=1", raw,
                    [op.name for op in self._host_resident_list])
            return 1
        if raw != "auto":
            k = int(raw)
            if k < 1:
                raise ValueError(f"superstep must be >= 1, got {raw!r}")
            return k
        bs = int(batch_size or self.config.batch_size)
        if not any(p.batch_size == bs and p.host_paced
                   for p in getattr(self, "_pace", {}).values()):
            return 1
        return self._staging_superstep(bs)

    def _staging_superstep(self, bs: int) -> int:
        """The largest power-of-two K <= 16 whose stacked megabatch fits
        the staging budget (5% of per-chip HBM on TPU — the megabatch
        lives beside params/opt state/activations — or a 128 MB host-RAM
        cap elsewhere)."""
        scale = bs / max(self.config.batch_size, 1)
        tensors = list(self.input_tensors)
        if self.label_tensor is not None:
            tensors.append(self.label_tensor)
        per_batch = sum(float(np.prod(t.shape))
                        * np.dtype(t.dtype).itemsize * scale
                        for t in tensors)
        if jax.default_backend() == "tpu":
            from ..search.cost_model import TPUSpec
            budget = 0.05 * TPUSpec.detect().hbm_capacity_bytes
        else:
            budget = 128e6
        k = 16
        while k > 1 and k * per_batch > budget:
            k //= 2
        return k

    def _auto_superstep(self, bs: int, num_batches: int, save_every: int,
                        step0: int = 0, epoch_steps: int = 0) -> int:
        """The K a host-paced verdict fuses THIS fit to under "auto": the
        staging rule's, halved until an epoch holds the probe and one
        group of K (a shorter epoch never fuses, probing or not: the same
        shape and epoch give the same K, so a later fit builds nothing).

        With `save_every` set, snapshots land where per-step training
        puts them, all of them: the feed aligns groups on the batch
        index, the snapshot test reads `_step`, and a fused dispatch
        that starts off a multiple of K steps over multiples of
        `save_every`. So K is halved further until it divides
        `save_every`, the step at which this fit's first epoch has its
        batch 0 (`step0`: an earlier fit, a resume) and the steps of an
        epoch (`epoch_steps`: the full batches and the remainder; 0 when
        one epoch is left); 1 where no K does."""
        k = self._staging_superstep(bs)
        while k > 1 and (PROBE_DISPATCHES + k > num_batches
                         or (save_every and (save_every % k or step0 % k
                                             or epoch_steps % k))):
            k //= 2
        return k

    def _idle_at_dispatch(self, vector) -> bool:
        """Had the device already finished the step issued
        `_Throttle.lag` dispatches ago (`vector`: its metrics vector) as
        fit() issues the next? Asked without waiting. The pace probe's
        one reading, and the one seam a test answers in its place."""
        return vector.is_ready()

    def _superstep_sharding(self, sh: NamedSharding) -> NamedSharding:
        """Input sharding for a stacked [K, batch, ...] megabatch: the
        new leading step axis is unsharded, the per-step dims keep the
        model's input specs. Memoized by source-sharding identity (the
        model's sharding objects are long-lived — same trick as
        _exec_key's string memo)."""
        memo = getattr(self, "_super_sharding_memo", None)
        if memo is None:
            memo = self._super_sharding_memo = {}
        hit = memo.get(id(sh))
        if hit is not None and hit[0] is sh:
            return hit[1]
        if len(memo) > 256:
            memo.clear()
        s = NamedSharding(self.mesh,
                          PartitionSpec(*((None,) + tuple(sh.spec))))
        memo[id(sh)] = (sh, s)
        return s

    def _device_superbatch(self, stacked: Dict[str, Any]) -> Dict:
        """Stage a [K, batch, ...] stacked megabatch on device in ONE
        device_put (the K-step extension of _device_batch's single-put
        win): every input rides the model's per-step sharding with the
        leading step axis unsharded, so `sbatch[k]` inside the scan has
        exactly the per-step layout the K=1 executable sees."""
        if getattr(self, "_host_resident_list", None):
            raise ValueError(
                "superstep megabatches do not support host-resident "
                "tables (resolve_superstep falls back to K=1)")
        puts: Dict[str, tuple] = {}
        for t in self.input_tensors:
            if t.name in stacked:
                puts[t.name] = (stacked[t.name], self._superstep_sharding(
                    self._out_sharding[t.guid]))
        lab = np.asarray(stacked["label"])
        sh = self._label_sharding
        ndev = int(np.prod([self.mesh.shape[a]
                            for a in self.mesh.axis_names]))
        # same per-step divisibility re-check as _device_batch, against
        # the PER-STEP sample dim (axis 1 of the stacked array)
        if lab.shape[1] % ndev != 0:
            sh = NamedSharding(self.mesh, PartitionSpec())
        puts["label"] = (lab, self._superstep_sharding(sh))
        return self._put(puts)

    def _stage_superstep(self, stacked: Dict[str, Any]) -> "StagedStep":
        """Fully stage one K-step megabatch (stacked host arrays with
        leading axis K — data.prefetch.stack_batches, or a free reshape
        of a contiguous dataset slice) for the fused-scan executable.
        Thread-safe like _stage_step, so the prefetch ring stages
        megabatch G+1 while the device trains megabatch G."""
        k = int(np.asarray(next(iter(stacked.values()))).shape[0])
        return StagedStep(self._device_superbatch(stacked), None, k)

    def train_superstep(self, batches: Sequence[Dict[str, Any]]):
        """Train K fused steps from a list of same-shaped host batches
        (each including its "label"): one dispatch, len(batches)
        optimizer steps. Returns the LAST step's metrics plus
        `per_step` stacked [K] arrays for every metric."""
        from ..data.prefetch import stack_batches
        return self.train_batch_staged(
            self._stage_superstep(stack_batches(batches)))

    @obstrace.spanned("train/dispatch")
    def train_superstep_device(self, sbatch: Dict):
        """Train step for a staged [K, batch, ...] megabatch: ONE
        host→device dispatch of the AOT-cached fused-scan executable
        trains K steps (step accounting advances by K). Boundary
        semantics match K sequential steps: the anomaly sentinel runs
        per step INSIDE the scan (skip_step suppresses there, with zero
        host syncs); rollback/raise fire here from the stacked flags
        with the faulting step index; fault-injected device loss
        scheduled for ANY step in the window surfaces as MeshDegraded
        BEFORE dispatch (elastic recovery checks at superstep
        boundaries, so no state for the window is half-applied)."""
        k = int(next(iter(sbatch.values())).shape[0])
        self._ensure_step_state()
        if faults.active() is not None:
            sbatch = self._dispatch_faults(sbatch, k)
        # once in K steps, so the span can carry what a trace reader
        # divides a fused span by
        (self.params, self.opt_state, self.op_state, self._msums,
         self._step_dev, stacked) = self._run_executable(
            "superstep", self._superstep_execs,
            (k,) + self._exec_key(sbatch), self._superstep_fn,
            self._step_args(sbatch), "train/superstep",
            step_num=self._step, superstep=k)
        step0 = self._step
        self._step += k
        self.perf.sums = dict(self._msums)
        per_step = metrics_mod.StepMetrics(stacked, self._step_index)
        self._check_anomaly(step0, per_step)
        return metrics_mod.StepMetrics(stacked, self._step_index,
                                       last_of=per_step,
                                       per_step=per_step, superstep=k)

    @obstrace.spanned("train/dispatch")
    def _train_dispatch(self, device_batch: Dict, host_idx,
                        next_host_idx=None):
        self._ensure_step_state()
        if faults.active() is not None:
            device_batch = self._dispatch_faults(device_batch)
        # _step_args, inline: this is the step path of the host-paced cell
        args = (self.params, self.opt_state, self.op_state, self._msums,
                device_batch, self._step_dev)
        hres = host_idx is not None
        if hres:
            args = args + (self._host_emb_input(host_idx),)
        outs = self._run_executable(
            "train", self._train_step_execs,
            self._exec_key(device_batch), self._train_step, args,
            "train/step")
        (self.params, self.opt_state, self.op_state, self._msums,
         self._step_dev, vec) = outs[:6]
        mets = metrics_mod.StepMetrics(vec, self._step_index)
        self._step += 1
        # the sentinel flag guards the host-table scatter on every policy:
        # NaN cotangents scattered into host tables could not be undone
        # by skip_step's on-device suppression. Read where the scatter
        # runs (the worker, after the step), never at dispatch
        sentinel = self._anomaly_policy != "none"
        if hres:
            cts = outs[6]
            if getattr(self.config, "host_tables_async", True):
                # pipelined (double-buffering): the cotangent readback +
                # host scatter run on a worker thread, overlapping the
                # NEXT step's gather/H2D/dispatch and device execution.
                # When the caller knows the next batch (`next_host_idx` —
                # fit's streamed feed does), the worker gathers the
                # NEXT step's rows FIRST (they are ready almost
                # immediately, so the next dispatch never waits on the
                # scatter), then scatters this step's update — the
                # documented bounded ONE-step staleness, made
                # deterministic: the next step always sees updates
                # through step N-1. Table reads and writes serialize on
                # _host_table_lock, so any racing reader sees the table
                # atomically before or after the scatter — never torn
                # rows. Only one worker in flight: join the previous
                # first.
                self._host_drain()
                import threading
                step = self._step - 1   # capture NOW: the thread may run
                # after the next call's increment
                nh = (next_host_idx() if callable(next_host_idx)
                      else next_host_idx)
                gathered = threading.Event()
                self._host_gather_pending = ((nh, gathered)
                                             if nh is not None else None)
                gen = getattr(self, "_host_gen", 0)

                def scatter():
                    try:
                        try:
                            if nh is not None:
                                self._host_gather_next = (
                                    nh, self._host_emb_forward(nh))
                        finally:
                            gathered.set()   # never leave a consumer
                            # parked on the event
                        faults.maybe_stall("scatter")   # wedged-worker
                        # fault: the drain watchdog must catch it
                        if gen != getattr(self, "_host_gen", 0):
                            # elastic recovery abandoned this worker and
                            # replaced the tables underneath it — a late
                            # scatter would corrupt the restored state
                            return
                        if not (sentinel and mets["anomaly"]):
                            self._host_emb_update(host_idx, cts, step)
                    except BaseException as e:   # re-raised at drain
                        self._host_scatter_exc = e
                t = threading.Thread(target=scatter, daemon=True,
                                     name="ff-scatter")
                self._host_scatter_thread = t
                t.start()
            else:
                # exact ordering: the cotangent readback is the step's
                # true completion
                if not (sentinel and mets["anomaly"]):
                    self._host_emb_update(host_idx, cts, self._step - 1)
        # the running sums live on device; PerfMetrics syncs at report().
        # shallow-copy so perf.reset()/report() mutating perf.sums can
        # never corrupt the jit carry
        self.perf.sums = dict(self._msums)
        self._check_anomaly(self._step - 1, mets)
        return mets

    @property
    def _host_lock(self):
        """Serializes host-table reads (gather) against the async scatter
        thread's writes — atomic either-order visibility on EVERY path
        (native, numpy fallback, stateful updates), not just the native
        pool's internal serialization."""
        lk = getattr(self, "_host_table_lock", None)
        if lk is None:
            # no_dispatch: gathers copy rows OUT under the lock and
            # device_put after release; a dispatch in the critical
            # section would stall the scatter worker (FLX203)
            lk = self._host_table_lock = _san.make_lock(
                "FFModel._host_table_lock", no_dispatch=True)
        return lk

    def _worker_deadline_s(self) -> float:
        """Configured background-worker liveness deadline (0 = watchdogs
        off, every wait blocks forever — the pre-elastic behavior)."""
        return float(getattr(self.config, "worker_deadline_s", 0.0)
                     or 0.0)

    def _host_drain(self, deadline_s: Optional[float] = None):
        """Join the in-flight async host scatter (no-op when none) and
        surface any exception it hit — a silently dropped scatter would
        corrupt training. Call before any read of host_params that needs
        the latest update (eval, checkpoint, end of fit).

        With a worker deadline configured (FFConfig.worker_deadline_s or
        the explicit argument), a scatter worker that outlives it raises
        a typed WorkerStalled (structured stall report, worker left
        un-joined) instead of hanging the training loop; the elastic
        layer abandons it via `_host_abandon` and recovers."""
        t = getattr(self, "_host_scatter_thread", None)
        if t is not None and t.is_alive():
            dl = (self._worker_deadline_s() if deadline_s is None
                  else deadline_s)
            if dl > 0:
                t0 = time.perf_counter()
                t.join(dl)
                if t.is_alive():
                    raise WorkerStalled(StallReport(
                        worker=t.name, waiting_for="host-table scatter "
                        "completion", waited_s=time.perf_counter() - t0,
                        deadline_s=dl, detail=f"step {self._step}"))
            else:
                t.join()
        self._host_scatter_thread = None
        exc = getattr(self, "_host_scatter_exc", None)
        if exc is not None:
            self._host_scatter_exc = None
            raise exc

    def _host_abandon(self):
        """Drop (without joining) the in-flight scatter worker and any
        chained gather, bumping the table generation so a late write
        from the abandoned worker is discarded rather than scattered
        into state the elastic recovery is about to replace."""
        self._host_gen = getattr(self, "_host_gen", 0) + 1
        self._host_scatter_thread = None
        self._host_scatter_exc = None
        self._host_prefetch_invalidate()

    def _host_prefetch_invalidate(self):
        """Drop a chained host-table gather (it is stale after anything
        that replaces the tables underneath it — checkpoint restore,
        rollback)."""
        self._host_gather_next = None
        self._host_gather_pending = None

    def _host_emb_input(self, host_idx):
        """Forward rows for the host-resident tables feeding the jitted
        step. Under the async pipeline the previous step's worker gathers
        these rows FIRST (before its scatter — the bounded one-step
        staleness the async mode documents), so by the time this step
        dispatches, the rows are usually staged; the consumer waits only
        on the gather event, never on the scatter, keeping the scatter
        overlapped with this step's device execution. Without a chained
        gather: inline gather (exact when async is off — there is no
        worker; bounded one-step staleness when async is on and a scatter
        is in flight — the table lock makes it atomic either-order)."""
        pending = getattr(self, "_host_gather_pending", None)
        if pending is not None and pending[0] is host_idx:
            self._host_gather_pending = None
            dl = self._worker_deadline_s()
            if dl > 0:
                if not pending[1].wait(dl):
                    t = getattr(self, "_host_scatter_thread", None)
                    raise WorkerStalled(StallReport(
                        worker=getattr(t, "name", "ff-scatter"),
                        waiting_for="chained host-table gather",
                        waited_s=dl, deadline_s=dl,
                        detail=f"step {self._step}",
                        alive=bool(t is not None and t.is_alive())))
            else:
                pending[1].wait()
            got = getattr(self, "_host_gather_next", None)
            self._host_gather_next = None
            if got is not None and got[0] is host_idx:
                return got[1]
            # the worker died before gathering — surface its error here
            # (the step boundary), then fall through to the inline path
            self._host_drain()
        return self._host_emb_forward(host_idx)

    def _host_emb_forward(self, host_idx):
        """Host-side gather for host-resident tables: numpy lookup on the
        already-read-back indices, rows shipped to the device at the op's
        output sharding.

        Only the table READ holds ``_host_lock`` (``host_lookup`` returns
        fresh arrays, never views into the table); the ``device_put`` H2D
        transfer happens after release — flexcheck's blocking-under-lock
        rule (FLX203) pins that a dispatch never stalls the async scatter
        worker contending for the same lock."""
        rows = {}
        with self._host_lock:
            for op in self._host_resident_list:
                rows[op.name] = op.host_lookup(self.host_params[op.name],
                                               host_idx[op.name])
        _san.note_jax_dispatch("host-table row device_put")
        return {op.name: jax.device_put(
                    rows[op.name], self._out_sharding[op.outputs[0].guid])
                for op in self._host_resident_list}

    def _host_emb_update(self, host_idx, cts, step):
        opt = self.optimizer
        stateful = bool(opt.sparse_slab_names()) or (
            isinstance(opt, SGDOptimizer) and opt.weight_decay != 0.0)
        # the device readback happens OUTSIDE the table lock (it is the
        # slow part the async mode overlaps); only the table mutation
        # serializes against concurrent gathers
        cts_np = {op.name: np.asarray(cts[op.name], dtype=np.float32)
                  for op in self._host_resident_list}
        with self._host_lock:
            for op in self._host_resident_list:
                if stateful:
                    # lazy momentum/Adam on the host (same semantics as
                    # the device tile path)
                    op.host_opt_update(
                        self.host_params[op.name], host_idx[op.name],
                        cts_np[op.name], opt,
                        self.host_opt_state.get(op.name, {}), step)
                else:
                    op.host_sgd_update(self.host_params[op.name],
                                       host_idx[op.name],
                                       cts_np[op.name], opt.lr)
                pol = self._sr_policy_of(op.name)
                if pol is not None:
                    # stochastic_rounding: re-quantize exactly the rows
                    # this scatter touched (deterministic per step)
                    from ..quant.codec import fake_quant_stochastic_np
                    rows = np.unique(np.asarray(
                        op.host_delta_touched_rows(host_idx[op.name])))
                    kern = self.host_params[op.name]["kernel"]
                    v = kern.reshape(-1, kern.shape[-1])
                    rng = np.random.RandomState(
                        (self.config.seed ^ (int(step) * 2654435761))
                        & 0x7FFFFFFF)
                    v[rows] = fake_quant_stochastic_np(v[rows], pol.dtype,
                                                       rng)

    @staticmethod
    def to_logical(value, tensor):
        """Bring a raw _forward_env value into the tensor's logical (NCHW)
        dim order — conv-stack tensors are stored NHWC (Tensor.physical)."""
        if tensor.physical == "nhwc":
            return jnp.transpose(value, (0, 3, 1, 2))
        return value

    def forward_batch(self, batch: Dict[str, np.ndarray],
                      host_gather: Optional[Callable] = None):
        """Forward pass for one host batch (no labels). ``host_gather``
        overrides the host-resident-table row gather — the serving
        engine passes its LRU-cached gather (serve/cache.py) so hot rows
        skip the numpy table lookup; the default is the exact
        ``_host_emb_forward`` path."""
        db = self._device_batch(batch, with_label=False)
        db, host_idx = self._split_host_idx(db)
        if host_idx is not None:
            self._host_drain()   # eval must see the last step's scatter
            gather = host_gather or self._host_emb_forward
            return self._eval_dispatch(db, gather(host_idx))
        return self._eval_dispatch(db)

    # --- serving entry points (serve/engine.py) -----------------------
    def bucket_sizes(self, max_batch: int) -> tuple:
        """The power-of-two eval batch buckets this model admits, small
        to large. Serving pads every dynamic batch up to the smallest
        bucket so each dispatch hits one of a FIXED set of pre-compiled
        executables (warmup_buckets). The floor is the mesh size when
        the input shardings split the sample dim — a 3-row device_put
        against an 8-way sharded spec has no even shards."""
        ndev = max(int(self.mesh.size), 1) if self.mesh is not None else 1
        sharded = any(
            bool(self._out_sharding[t.guid].spec)
            for t in self.input_tensors
            if t.guid in getattr(self, "_out_sharding", {}))
        floor = ndev if sharded else 1
        out, b = [], 1
        while b <= max(int(max_batch), 1):
            if b >= floor:
                out.append(b)
            b *= 2
        if not out:
            out = [floor]
        return tuple(out)

    def forward_bucket(self, batch: Dict[str, np.ndarray],
                       bucket: Optional[int] = None,
                       host_gather: Optional[Callable] = None):
        """Bucketed eval entry: zero-pad the batch's rows up to `bucket`
        (default: the smallest admissible power-of-two), dispatch the
        padded batch through the AOT eval cache, and return predictions
        for ONLY the real rows. Row-wise graphs (every model in the zoo
        ends per-sample) make the unpadded rows bit-identical to a
        direct ``forward_batch`` of the same rows — tests/test_serve.py
        pins that contract."""
        from ..data.dataloader import pad_batch_rows
        n = int(next(iter(batch.values())).shape[0])
        if bucket is None:
            # smallest admissible power-of-two >= n
            bucket = self.bucket_sizes(1)[-1]
            while bucket < n:
                bucket *= 2
        if bucket < n:
            raise ValueError(f"bucket {bucket} < batch rows {n}")
        padded = pad_batch_rows(batch, bucket) if bucket > n else batch
        out = self.forward_batch(padded, host_gather=host_gather)
        return out[:n] if bucket > n else out

    def warmup_buckets(self, buckets: Sequence[int],
                       host_gather: Optional[Callable] = None) -> float:
        """AOT-compile the eval executable for every bucket size up
        front (synthetic zero batches from the input specs), so no live
        request ever pays a compile. Returns the warmup seconds."""
        t0 = time.perf_counter()
        for b in buckets:
            batch = {}
            for t in self.input_tensors:
                shape = (int(b),) + tuple(t.shape[1:])
                if jnp.issubdtype(jnp.dtype(t.dtype), jnp.integer):
                    batch[t.name] = np.zeros(shape, np.int32)
                else:
                    batch[t.name] = np.zeros(shape, np.float32)
            jax.block_until_ready(
                self.forward_batch(batch, host_gather=host_gather))
        return time.perf_counter() - t0

    # --- lowering hooks (analysis/hlo_audit.py) -----------------------
    def synthetic_device_batch(self) -> Dict:
        """A zero-filled, fully-staged device batch at the compiled
        shapes — the HLO auditor lowers against it (values never run;
        only shapes/dtypes/shardings reach the compiler)."""
        batch: Dict[str, np.ndarray] = {}
        for t in self.input_tensors:
            batch[t.name] = np.zeros(t.shape, dtype=np.dtype(t.dtype))
        lt = self.label_tensor
        if lt is not None:
            batch["label"] = np.zeros(lt.shape, dtype=np.dtype(lt.dtype))
        return self._device_batch(batch)

    def lowered_train_hlo(self, device_batch: Optional[Dict] = None
                          ) -> str:
        """Post-SPMD-partitioning HLO text of the (K=1) train step —
        the program GSPMD will actually run, with every inserted
        collective visible at its concrete per-device shapes. The HLO
        auditor (analysis/hlo_audit.py FLX511-513) scans this for
        table-scale collectives, missed donation, and cost-model drift;
        callers may also dump it for offline diffing. Requires
        compile() + init_layers(); host-resident-table models are
        rejected (their table traffic runs on the host, outside the
        lowered program)."""
        if getattr(self, "_host_resident_ops", None):
            raise ValueError(
                "host-resident-table models keep their table traffic on "
                "the host — the lowered device HLO has nothing to audit "
                "for them")
        if self.params is None:
            raise ValueError("call compile() + init_layers() first")
        self._ensure_step_state()
        db = device_batch if device_batch is not None \
            else self.synthetic_device_batch()
        return self._train_step.lower(
            *self._step_args(db)).compile().as_text()

    def lowered_eval_hlo(self, device_batch: Optional[Dict] = None
                         ) -> str:
        """Post-SPMD HLO of the eval/serving forward step (see
        lowered_train_hlo); serving-bucket audits lower one batch per
        bucket size."""
        if self.params is None:
            raise ValueError("call compile() + init_layers() first")
        db = device_batch if device_batch is not None \
            else self.synthetic_device_batch()
        db = {k: v for k, v in db.items() if k != "label"}
        args = (self.params, self.op_state, db)
        return self._eval_step.lower(*args).compile().as_text()

    def swap_params(self, params=None, host_params=None, op_state=None):
        """Atomically install new inference state (the hot-reload hook).

        The serving engine calls this under its dispatch lock, BETWEEN
        dispatches: an executable already dispatched keeps computing on
        the old arrays (functional state — nothing is mutated in
        place), so in-flight requests finish on the old weights and the
        next dispatch sees the new ones — never a mix. Tree structures
        must match the compiled model (the cached AOT executables were
        compiled against these shapes/shardings); a mismatch raises
        before anything is replaced."""
        if params is not None:
            old = jax.tree.structure(self.params)
            new = jax.tree.structure(params)
            if old != new:
                raise ValueError(
                    f"swap_params: new params tree {new} does not match "
                    f"the compiled model's {old} — a snapshot from a "
                    f"differently-built model cannot hot-swap")
        self._host_drain()   # land any in-flight training scatter
        self._host_prefetch_invalidate()
        if params is not None:
            self.params = params
        if host_params is not None:
            self.host_params = host_params
        if op_state is not None:
            self.op_state = op_state

    def apply_delta(self, delta: Dict):
        """Incrementally install a delta snapshot (the continual-learning
        hot path; see ``utils/delta.py``).

        ``delta`` is a ``load_delta_file`` payload: ``rows[flat_key] =
        (idx, vals)`` replaces the given flattened-2D stored rows of a
        params/hostparams array, ``full[flat_key]`` replaces whole
        (dense/op-state) arrays, ``step`` becomes the new version. The
        serving engine calls this between dispatches exactly like
        ``swap_params`` — the caller already staged the device-param row
        payloads with ``stage_delta_rows`` OUTSIDE any dispatch lock, so
        the only device work here is the row scatter itself. Device
        params are updated functionally (in-flight executions keep their
        old arrays); host tables are updated in place under
        ``_host_lock`` (between dispatches nothing reads them).

        Everything is validated BEFORE anything is installed: an unknown
        key, an out-of-range row index, or a width mismatch raises with
        the key named and the model untouched — the engine turns that
        into a reject-with-reason and the watcher falls back to a full
        reload."""
        step = int(delta["step"])
        rows = delta.get("rows") or {}
        full = delta.get("full") or {}

        def _leaf(tree, key, what):
            parts = key.split("/")
            node = tree
            for p in parts[1:]:
                if not isinstance(node, dict) or p not in node:
                    raise ValueError(
                        f"delta {what} {key!r} does not exist in this "
                        f"model (differently-built model?)")
                node = node[p]
            return parts[1:], node

        sections = {"params": self.params, "state": self.op_state,
                    "hostparams": self.host_params}
        # ---- validate first, install second ----------------------------
        plan = []
        for key, (idx, vals) in rows.items():
            sec = key.split("/", 1)[0]
            tree = sections.get(sec)
            if tree is None or sec == "state":
                raise ValueError(
                    f"delta row update targets unsupported section "
                    f"{key!r}")
            path, cur = _leaf(tree, key, "row update")
            shape = tuple(np.asarray(cur).shape) if sec == "hostparams" \
                else tuple(cur.shape)
            if len(shape) < 2 or (np.asarray(vals).shape[-1]
                                  != shape[-1]):
                raise ValueError(
                    f"delta rows for {key!r} have width "
                    f"{np.asarray(vals).shape[-1:]} but the stored array "
                    f"is {shape}")
            nrows = int(np.prod(shape[:-1]))
            idx_np = np.asarray(idx)
            if idx_np.size and (int(idx_np.max()) >= nrows
                                or int(idx_np.min()) < 0):
                raise ValueError(
                    f"delta rows for {key!r} index up to "
                    f"{int(idx_np.max())} but the stored array has only "
                    f"{nrows} rows")
            plan.append((sec, key, path, idx, vals))
        for key in full:
            sec = key.split("/", 1)[0]
            tree = sections.get(sec)
            if tree is None:
                raise ValueError(
                    f"delta full update targets unknown section {key!r}")
            _leaf(tree, key, "full update")
        # ---- install ---------------------------------------------------
        self._host_drain()
        self._host_prefetch_invalidate()
        new_params = {op: dict(d) for op, d in self.params.items()}
        new_state = {op: (dict(d) if isinstance(d, dict) else d)
                     for op, d in self.op_state.items()}
        for sec, key, path, idx, vals in plan:
            if sec == "params":
                opname, pname = path[0], path[-1]
                cur = new_params[opname][pname]
                w = cur.shape[-1]
                new2d = jnp.reshape(cur, (-1, w)).at[
                    jnp.asarray(idx)].set(
                        jnp.asarray(vals, dtype=cur.dtype))
                new = jnp.reshape(new2d, cur.shape)
                shard = self._param_sharding.get(opname, {}).get(pname)
                if shard is not None:
                    new = jax.device_put(new, shard)
                new_params[opname][pname] = new
            else:   # hostparams: in-place row writes under the table lock
                opname, pname = path[0], path[-1]
                with self._host_lock:
                    tbl = self.host_params[opname][pname]
                    mi = np.unravel_index(np.asarray(idx),
                                          tbl.shape[:-1])
                    tbl[mi] = np.asarray(vals, dtype=tbl.dtype)
        for key, v in full.items():
            sec = key.split("/", 1)[0]
            parts = key.split("/")
            opname, pname = parts[1], parts[-1]
            if sec == "params":
                shard = self._param_sharding.get(opname, {}).get(pname)
                new_params[opname][pname] = (
                    jax.device_put(v, shard) if shard is not None
                    else jax.device_put(v))
            elif sec == "state":
                new_state[opname][pname] = jax.device_put(v)
            else:
                with self._host_lock:
                    self.host_params[opname][pname] = np.array(v)
        self.params = new_params
        self.op_state = new_state
        self._step = step
        self._step_dev = None
        self._msums = None
        return self

    def _eval_dispatch(self, db: Dict, host_emb=None):
        """Eval through the same AOT executable cache as the train path:
        calling the pjit wrapper re-validates the whole param pytree in
        python on EVERY call, which costs more than a fast model's
        forward itself — the cached `.lower().compile()` executable
        skips that, keyed by the batch signature (alternating shapes
        each compile once; `_run_executable`)."""
        args = (self.params, self.op_state, db)
        key = self._exec_key(db)
        if host_emb is not None:
            args = args + (host_emb,)
            key = key + ("host_emb",) + self._exec_key(host_emb)
        execs = self._eval_step_execs
        if key in execs:
            execs.move_to_end(key)
        else:
            self._executable("eval", execs, key, self._eval_step, args)
            # LRU-bound the cache: a serving engine fed many ad-hoc
            # shapes must not leak one compiled executable per shape
            # forever (config.eval_exec_cache, 0/negative = unbounded)
            cap = int(getattr(self.config, "eval_exec_cache", 0) or 0)
            while cap > 0 and len(execs) > cap:
                execs.popitem(last=False)
                self._eval_exec_evictions = getattr(
                    self, "_eval_exec_evictions", 0) + 1
        return self._run_executable("eval", execs, key, self._eval_step,
                                    args)

    def eval_exec_cache_stats(self) -> Dict[str, int]:
        """Occupancy of the eval-path AOT executable cache plus the
        CUMULATIVE eviction count (across recompiles/reshards) — the
        serving engine surfaces these in ``stats()`` so an executable
        leak or thrash shows up as a number, not an OOM."""
        execs = getattr(self, "_eval_step_execs", None) or {}
        return {"size": len(execs),
                "capacity": int(getattr(self.config, "eval_exec_cache", 0)
                                or 0),
                "evictions": int(getattr(self, "_eval_exec_evictions", 0))}

    def reset_metrics(self):
        """Reference FFModel::reset_metrics (model.cc:934-940)."""
        self.perf.reset()
        self._msums = None

    # --- parity verbs (eager, unfused) --------------------------------
    def forward(self, batch=None):
        if batch is not None:
            self._cur_batch = batch
        if getattr(self, "_cur_batch", None) is None:
            raise ValueError(
                "forward() needs a batch: call forward(batch) once (or use "
                "a DataLoader's next_batch) before parameterless forward()")
        return self.forward_batch(self._cur_batch)

    def zero_gradients(self):
        # gradients are functional values in JAX; nothing to zero
        # (reference model.cc:1146-1149 launches per-op ZERO_INIT tasks)
        pass

    def backward(self, batch=None):
        if batch is not None:
            self._cur_batch = batch
        if getattr(self, "_cur_batch", None) is None:
            raise ValueError("backward() needs a batch: call backward(batch)")
        # fused into train_batch in the perf path; parity verb recomputes
        self._pending_update = self._cur_batch

    def update(self):
        if getattr(self, "_pending_update", None) is not None:
            self.train_batch(self._pending_update)
            self._pending_update = None

    def _staging_room(self):
        """What fit()'s feed may keep on the device: (the bytes one chip
        has for a resident data set, {input name or "label": the chips a
        staged copy of it is spread over}). The room is per-chip HBM
        minus what already lives there (params + optimizer state + op
        state), with 30% headroom for activations/workspace; a staged
        input costs a chip its full size when its sharding is replicated,
        size/ndev when the sample dim is sharded (matches
        _build_shardings' input specs). Off-TPU there is no HBM and all
        virtual "chips" share one host's RAM: a modest cap on the TOTAL
        second copy, so fit() on a CPU mesh never device_puts a huge data
        set a second time."""
        if jax.default_backend() != "tpu":
            return 2e9, {}
        from ..search.cost_model import TPUSpec
        ndev = max(self.mesh.size, 1)
        split = {t.name: ndev for t in self.input_tensors
                 if self._out_sharding[t.guid].spec}
        if self._label_sharding.spec:
            split["label"] = ndev

        def per_chip(leaf) -> float:
            # per-chip bytes of a (possibly sharded) device array —
            # .nbytes alone is the GLOBAL logical size
            try:
                shard = leaf.sharding.shard_shape(leaf.shape)
                return float(math.prod(shard)) * leaf.dtype.itemsize
            except Exception:
                return float(getattr(leaf, "nbytes", 0))

        resident = sum(per_chip(v) for v in jax.tree.leaves(
            (self.params, self.opt_state, self.op_state)))
        return max(0.0, 0.7 * TPUSpec.detect().hbm_capacity_bytes
                   - resident), split

    def _warm_up(self, feed) -> tuple:
        """AOT-compile the train step against the first batch as the
        feed stages it, so the loop starts warm without consuming a real
        optimizer step (the reference warms its Legion trace during epoch
        0 instead, dlrm.cc:178-185). The executable is cached under the
        SAME key the loop's dispatches ask for, so its first step builds
        nothing; returns that key, the step's shape. A fit(batch_size=)
        the graph cannot take fails here, with the reason."""
        def refuse(e, cannot):
            if feed.bs == self.config.batch_size:
                raise e
            raise ValueError(f"fit(batch_size={feed.bs}) cannot "
                             + cannot.format(self.config.batch_size)
                             + f": {e}") from e

        try:
            item = self._stage_step(feed.host_slice(0))
        except Exception as e:
            refuse(e, "stage against this model's input shardings "
                      "(compiled for batch {})")
        self._ensure_step_state()
        args = self._step_args(item.device_batch)
        if item.host_idx is not None:
            args = args + (self._host_emb_forward(item.host_idx),)
        key = self._exec_key(item.device_batch)
        try:
            self._executable("train", self._train_step_execs, key,
                             self._train_step, args)
        except Exception as e:
            refuse(e, "compile against this graph (an op bakes the "
                      "compile-time batch {} into its shape)")
        return key

    def _warm_up_superstep(self, feed, k: int) -> None:
        """The same for the fused scan of `k` steps: before the loop, or
        where fit()'s probe switches to it in mid-epoch."""
        sbatch = self._stage_superstep(feed.host_slice(0, k)).device_batch
        self._executable("superstep", self._superstep_execs,
                         (k,) + self._exec_key(sbatch),
                         self._superstep_fn, self._step_args(sbatch))

    def _drift_monitor(self, name: str):
        """--obs on: process-wide metrics + span tracing + the drift
        monitor comparing measured step time (and lowered collective
        bytes, once) against the simulator's predictions — the runtime
        twin of shardcheck FLX513. Off (default): None, and a loop pays
        one pointer compare per step."""
        from ..obs import configure
        if not configure(self.config):
            return None
        from ..obs.drift import DriftMonitor
        mon = DriftMonitor.from_model(self, name=name)
        mon.audit_collectives()
        return mon

    # ------------------------------------------------------------------
    # fit loop (reference keras base_model.py:367-431 / dlrm.cc:166-198)
    # ------------------------------------------------------------------
    def fit(self, inputs: Dict[str, np.ndarray], labels: np.ndarray,
            epochs: Optional[int] = None, batch_size: Optional[int] = None,
            verbose: bool = True,
            callbacks: Optional[List[Callable]] = None,
            checkpoint_dir: Optional[str] = None,
            save_every: Optional[int] = None,
            keep_last: Optional[int] = None,
            resume: bool = True):
        """Train; with `checkpoint_dir` the run is fault-tolerant:

        - rolling atomic snapshots every `save_every` optimizer steps
          (written on a background thread; keep-last-`keep_last` files
          plus a manifest), and a final one when training completes;
        - `resume=True` scans the manifest first and continues from the
          newest VALID snapshot — params, optimizer state, step counter,
          and the (epoch, batch) dataloader position; corrupt/truncated/
          foreign snapshots are skipped, so a run SIGKILLed mid-save
          restarts from the previous good one;
        - under `FFConfig.anomaly_policy == "rollback"`, a non-finite
          step restores the last good snapshot, re-winds, and continues
          (at most `FFConfig.max_rollbacks` times per fit call).

        All three arguments default from FFConfig (`--checkpoint-dir`,
        `--save-every`, `--keep-last`).

        Every batch reaches the step the same way: the feed
        (data/feed.py) hands out one StagedStep a dispatch, resident or
        streamed, and `train_batch_staged` trains it.
        """
        epochs = epochs or self.config.epochs
        bs = batch_size or self.config.batch_size
        checkpoint_dir = checkpoint_dir or (
            getattr(self.config, "checkpoint_dir", "") or None)
        save_every = (save_every if save_every is not None
                      else getattr(self.config, "save_every", 0))
        keep_last = (keep_last if keep_last is not None
                     else getattr(self.config, "keep_last", 3))
        if bs != self.config.batch_size:
            # the per-shape executable cache compiles the step at the
            # requested shape; ops whose shapes bake the batch dimension
            # (explicit Reshape targets) reject the trace in the warm-up
            # with an actionable error. Reference keras fit() takes
            # whatever batch_size it is given (base_model.py:367-431).
            log_model.warning(
                "fit(batch_size=%d) differs from the compile-time batch "
                "%d; compiling the train step at the new shape",
                bs, self.config.batch_size)
        n = len(labels)
        if n < bs:
            raise ValueError(f"dataset has {n} samples < batch size {bs}")
        num_batches = n // bs
        if self.params is None:
            self.init_layers()

        # --- fused supersteps -------------------------------------------
        # K full batches train as ONE dispatch (lax.scan executable);
        # what cannot align to a K boundary falls back to exact K=1 steps
        # (the feed's schedule). K=1 IS the legacy path, bitwise. Under
        # "auto" a shape starts there and the loop below asks who sets
        # its pace (`_Pace`): `auto_k`, taken once the resume has said
        # where this fit starts, is what a host-paced verdict fuses it
        # to, and it never refuses a `save_every`.
        auto = getattr(self.config, "superstep", 1) == "auto"
        k_super = 1 if auto else self.resolve_superstep(bs)
        if k_super > num_batches:
            log_model.warning(
                "superstep K=%d exceeds the %d batches per epoch; "
                "running per-step (K=1)", k_super, num_batches)
            k_super = 1
        if k_super > 1 and save_every and save_every % k_super != 0:
            raise ValueError(
                f"save_every={save_every} is not a multiple of the "
                f"superstep K={k_super}: snapshots can only land on "
                f"superstep boundaries (the K fused steps commit "
                f"atomically) — pick save_every % K == 0, or "
                f"--superstep 1 for exact per-step checkpointing")

        # --- fault tolerance: rolling checkpoints + auto-resume ---------
        mgr = None
        start_epoch = start_batch = 0
        self._attach_configured_caches(checkpoint_dir)
        if checkpoint_dir:
            from ..utils.checkpoint import CheckpointManager
            mgr = CheckpointManager(checkpoint_dir, keep_last=keep_last)
            cc = getattr(self, "_compile_cache", None)
            if cc is not None:
                # record the warm-cache location in the manifest so a
                # serving host that mounts only the checkpoint dir can
                # find the executables/plans published next to it
                import os as _os
                mgr.set_manifest_extra(
                    "warm_cache_dir",
                    _os.path.relpath(cc.directory, mgr.directory))
            if resume:
                entry = mgr.restore_latest(self)
                if entry is not None:
                    ls = entry.get("loader_state") or {}
                    start_epoch = int(ls.get("epoch", 0))
                    start_batch = min(int(ls.get("batch", 0)), num_batches)
                    if verbose:
                        print(f"resumed from checkpoint step "
                              f"{entry['step']} (epoch {start_epoch}, "
                              f"batch {start_batch})")
            if start_epoch >= epochs:
                log_model.warning(
                    "checkpoint in %s is already at epoch %d >= epochs=%d; "
                    "nothing to train", checkpoint_dir, start_epoch, epochs)
                return {"elapsed": 0.0, "throughput": 0.0,
                        "num_samples": 0, "rollbacks": 0,
                        "recoveries": 0, "expansions": 0,
                        "superstep": 1, "fused_steps": 0,
                        "idle_at_dispatch_share": None,
                        "metrics": self.perf.report()}
            if (self._anomaly_policy == "rollback"
                    or getattr(self.config, "elastic", "off") == "resume") \
                    and mgr.latest_valid() is None:
                # rollback/elastic-resume need a target from step one:
                # seed the directory with the initial state
                mgr.save(self, {"epoch": start_epoch, "batch": start_batch})
        elif self._anomaly_policy == "rollback":
            raise ValueError(
                'anomaly_policy="rollback" needs fit(checkpoint_dir=...) '
                "(or FFConfig.checkpoint_dir) to roll back to")
        elif getattr(self.config, "elastic", "off") == "resume":
            log_model.warning(
                'elastic="resume" without fit(checkpoint_dir=...): a '
                "mesh degradation mid-run will have no snapshot to "
                "resume from and will re-raise")

        # the first epoch's batch 0 is (or would have been) step
        # `_step - start_batch`; more than one epoch from here, and K
        # divides an epoch's steps too
        auto_k = self._auto_superstep(
            bs, num_batches, save_every, self._step - start_batch,
            (num_batches + (n > num_batches * bs)
             if epochs - start_epoch > 1 else 0)) if auto else 1

        # --stage-dataset: "never" forces the streamed feed
        # (bench_pipeline compares the two); "always" trusts the caller
        # on capacity. The feed drains (and re-stages,
        # deterministically) around rollback, recovery and a remainder
        # whose shape cannot train.
        from ..data.feed import BatchFeed
        budget, split = self._staging_room()
        feed = BatchFeed(
            inputs, labels, bs, k_super, epochs, self._stage_step,
            self._stage_superstep,
            mode=getattr(self.config, "stage_dataset", "auto"),
            budget=budget, split=split,
            depth=max(int(getattr(self.config, "prefetch_depth", 2)
                          or 0), 0),
            deadline_s=self._worker_deadline_s() or None,
            on_close=self._host_prefetch_invalidate)
        key = self._warm_up(feed)
        # the probe's count for this shape: kept on the model, so a
        # verdict reached in an earlier fit holds and this one starts
        # fused (both programs cached: nothing is built) or stays per
        # step without asking. Host-resident tables cannot run inside the
        # scan, and several processes would each ask their own device
        # and could disagree on the program to run: neither is probed.
        pace = None
        if (auto and jax.process_count() == 1
                and not getattr(self, "_host_resident_list", None)):
            pace = self._pace.setdefault(key, _Pace(bs))
            if pace.host_paced:
                feed.k = auto_k
        if feed.k > 1:
            self._warm_up_superstep(feed, feed.k)
        if self.config.profiling:
            # per-op timing report (reference --profiling cudaEvent prints,
            # linear.cu:499-531)
            from ..utils.profiling import format_profile, profile_ops
            print(format_profile(profile_ops(self)))
        feed.restage()
        feed.rewind(start_epoch, start_batch)

        from ..utils.profiling import TraceContext
        drift_mon = self._drift_monitor("fit")
        throttled = _Throttle()
        # with async host-resident tables, the scatter worker chains the
        # NEXT step's host gather using the item the feed staged ahead
        peek_idx = (feed.peek_host_idx if getattr(
            self, "_host_resident_list", None) and getattr(
            self.config, "host_tables_async", True) else None)
        start = time.time()
        mets = None
        num_samples = 0
        fused_steps = single_steps = 0
        # the probe runs where this fit can ask it anything; its first
        # dispatch is waited for, once (`settled`)
        probing = (pace is not None and pace.host_paced is None
                   and num_batches > throttled.lag)
        settled = not probing
        rollbacks = 0
        max_rollbacks = getattr(self.config, "max_rollbacks", 3)
        recoveries = 0
        expansions = 0
        max_recoveries = getattr(self.config, "max_recoveries", 3)
        elastic_mode = getattr(self.config, "elastic", "off")

        with TraceContext(self.config.profile_dir or None), feed:
            epoch, b0 = start_epoch, start_batch
            # resume position for the elastic "inplace" path: the batch
            # about to train, plus whether its optimizer step actually
            # applied before the degradation surfaced
            cur, step0 = (epoch, b0), self._step
            while epoch < epochs:
                if b0 == 0:
                    self.reset_metrics()
                try:
                    while (ent := feed.peek(epoch)) is not None:
                        # degradation during the remainder resumes at the
                        # next epoch (the odd-shaped batch is not worth a
                        # dedicated resume position; "resume" mode re-
                        # winds exactly via the snapshot regardless)
                        last = ent.b == num_batches
                        nxt = ((epoch + 1, 0) if last
                               else (epoch, ent.b + ent.k))
                        cur, step0 = ((nxt, None) if last
                                      else ((epoch, ent.b), self._step))
                        t_drift = (time.perf_counter()
                                   if drift_mon is not None else 0.0)
                        if (ent.k > 1 and auto and save_every
                                and self._step % ent.k):
                            # something moved `_step` off the groups'
                            # boundaries (a remainder that was dropped, a
                            # rollback to another run's snapshot): a fused
                            # dispatch would step over a snapshot's place
                            log_model.warning(
                                "step %d is no multiple of the superstep "
                                "K=%d; going on per step, so that a "
                                "snapshot lands every save_every=%d steps",
                                self._step, ent.k, save_every)
                            feed.replan(1, epoch, ent.b)
                            continue
                        # the probe: was the device waiting for this
                        # dispatch? Full batches on the per-step path
                        # whose item the feed has at hand (a loop that
                        # waits for its input reads idle too, and fusing
                        # buys it nothing), until the shape has its
                        # verdict
                        if (probing and not last and feed.at_hand()
                                and ent.b - b0 >= throttled.lag
                                and (behind := throttled.behind())
                                is not None):
                            probing = not pace.note(
                                self._idle_at_dispatch(behind))
                            if (not probing and pace.host_paced
                                    and auto_k > 1):
                                # the host sets this shape's pace: build
                                # the fused program and go on in
                                # supersteps from this batch, or the next
                                # K-aligned one (the schedule keeps the
                                # batches before it, the tail and the
                                # remainder single steps)
                                self._warm_up_superstep(feed, auto_k)
                                feed.replan(auto_k, epoch, ent.b)
                                continue
                        try:
                            mets = throttled(self.train_batch_staged(
                                feed.get(), next_host_idx=peek_idx))
                        except (AnomalyError, MeshDegraded, WorkerStalled,
                                MeshReturned):
                            raise   # recovery, not a shape problem
                        except Exception as e:
                            if not last:
                                raise
                            # the ring may hold later remainders (and a
                            # dead producer, if staging raised): stage
                            # the rest without them
                            feed.drop_remainder(e)
                            feed.rewind(*nxt)
                            break
                        if not settled:
                            # a program's first run loads it onto the
                            # chip, behind whatever staging queued:
                            # tens of ms in which the host would issue
                            # every dispatch of the probe and each would
                            # find the device busy. The probe starts
                            # from an empty queue
                            settled = True
                            jax.block_until_ready(mets.vector)
                        num_samples += feed.rem if last else bs * ent.k
                        if ent.k > 1:
                            fused_steps += ent.k
                        else:
                            single_steps += 1
                        if drift_mon is not None and not last:
                            # per-step wall clock the dispatch loop
                            # observed (async pipelining amortized by
                            # the throttle); a superstep spreads its
                            # window over its K steps
                            drift_mon.observe_step(
                                (time.perf_counter() - t_drift) / ent.k)
                        # position = the NEXT (epoch, batch) to train;
                        # snapshots are written off-thread (the
                        # device→host gather is inline)
                        if mgr is not None and save_every and \
                                self._step % save_every == 0:
                            mgr.save_async(self, {"epoch": nxt[0],
                                                  "batch": nxt[1]})
                except AnomalyError as exc:
                    if (self._anomaly_policy != "rollback" or mgr is None
                            or rollbacks >= max_rollbacks):
                        raise
                    rollbacks += 1
                    throttled.clear()
                    mgr.wait()
                    entry = mgr.restore_latest(self)
                    if entry is None:
                        raise
                    ls = entry.get("loader_state") or {}
                    epoch = int(ls.get("epoch", 0))
                    b0 = min(int(ls.get("batch", 0)), num_batches)
                    log_model.warning(
                        "anomaly at step %d (%s); rolled back to step %d "
                        "(epoch %d, batch %d) — recovery %d/%d",
                        exc.step, exc, entry["step"], epoch, b0,
                        rollbacks, max_rollbacks)
                    feed.rewind(epoch, b0)
                    continue
                except (MeshDegraded, WorkerStalled,
                        MeshReturned) as exc:
                    grow = isinstance(exc, MeshReturned)
                    if elastic_mode == "off" or (
                            expansions if grow else
                            recoveries) >= max_recoveries:
                        raise
                    if grow:
                        expansions += 1
                    else:
                        recoveries += 1
                    throttled.clear()
                    feed.close()
                    if mgr is not None:
                        try:
                            mgr.wait()   # land/flush the in-flight save
                        except Exception as save_exc:
                            log_model.warning(
                                "background checkpoint save failed "
                                "during elastic recovery (%s); older "
                                "snapshots remain usable", save_exc)
                    from ..parallel.elastic import expand, recover
                    if grow:
                        # scale-UP: capacity came back — regrow the mesh
                        # (the inverse of the shrink below; resume
                        # position logic is shared)
                        report = expand(
                            self, returned=getattr(exc, "returned", []),
                            mode=elastic_mode, manager=mgr)
                    else:
                        report = recover(
                            self, lost=getattr(exc, "lost", []),
                            mode=elastic_mode, manager=mgr)
                    if elastic_mode == "resume":
                        ls = (report.entry or {}).get("loader_state") or {}
                        epoch = int(ls.get("epoch", 0))
                        b0 = min(int(ls.get("batch", 0)), num_batches)
                    else:
                        # inplace: continue at the batch about to train;
                        # skip however many optimizer steps actually
                        # applied before the stall surfaced (post-step
                        # drain) — a fused superstep commits its K steps
                        # atomically, so this is 0, 1, or K batches
                        e_, b_ = cur
                        if step0 is not None and self._step > step0:
                            b_ += self._step - step0
                        if b_ >= num_batches:
                            e_, b_ = e_ + 1, 0
                        epoch, b0 = e_, b_
                    log_model.warning(
                        "%s (%s); elastic %s %d/%d (%s) onto %d "
                        "device(s) — resuming at epoch %d, batch %d",
                        "mesh growth" if grow else "mesh degradation",
                        exc, "expansion" if grow else "recovery",
                        expansions if grow else recoveries,
                        max_recoveries, elastic_mode, report.surviving,
                        epoch, b0)
                    # arrays staged on the OLD mesh must not feed the
                    # recompiled executable
                    feed.restage()
                    feed.rewind(epoch, b0)
                    continue
                with obstrace.span("fit/epoch_end"):
                    if verbose and mets is not None:
                        # host sync happens here only (metrics are async)
                        print(f"epoch {epoch}: "
                              f"loss={float(mets['loss']):.6f} "
                              + self.perf.summary_line())
                    if callbacks:
                        for cb in callbacks:
                            cb(self, epoch, self.perf.report())
                epoch += 1
                b0 = 0
            with obstrace.span("fit/drain"):
                if mets is not None:
                    # the loss readback waits for the last step, and so
                    # for the whole timed loop
                    float(mets["loss"])
                self._host_drain()   # land the last async host scatter
        if mgr is not None:
            mgr.wait()        # surface any background-save error
            mgr.save(self, {"epoch": epochs, "batch": 0})  # final snapshot
        elapsed = time.time() - start
        throughput = num_samples / elapsed if elapsed > 0 else float("inf")
        if verbose:
            # same report format intent as reference dlrm.cc:197-198
            print(f"ELAPSED TIME = {elapsed:.4f}s, "
                  f"THROUGHPUT = {throughput:.2f} samples/s")
        share = pace.share if pace is not None else None
        out = {"elapsed": elapsed, "throughput": throughput,
               "num_samples": num_samples, "rollbacks": rollbacks,
               "recoveries": recoveries, "expansions": expansions,
               "superstep": feed.k, "fused_steps": fused_steps,
               "idle_at_dispatch_share": share,
               "metrics": self.perf.report()}
        if obsmetrics.enabled():
            steps = obsmetrics.counter(
                "ff_fit_steps_total", "optimizer steps fit() trained, by "
                "the dispatch that carried them", ("path",))
            steps.inc(fused_steps, path="fused")
            steps.inc(single_steps, path="single")
            obsmetrics.gauge(
                "ff_fit_superstep_k", "steps a dispatch of the newest "
                "fit() of this batch shape", ("shape",)).set(
                    feed.k, shape=str(bs))
            if share is not None:
                obsmetrics.gauge(
                    "ff_fit_idle_at_dispatch_share", "share of the pace "
                    "probe's dispatches that found the device idle",
                    ("shape",)).set(share, shape=str(bs))
        if drift_mon is not None:
            out["drift"] = drift_mon.report()
            obstrace.export_to_dir()   # no-op without --obs-trace-dir
        return out

    # ------------------------------------------------------------------
    # skew statistics (utils/histogram.py)
    # ------------------------------------------------------------------
    def attach_id_histograms(self, sketches) -> None:
        """Attach per-op id-frequency sketches ({op name ->
        IdFrequencySketch}, e.g. loaded from a published
        ``id_histogram.npz``) so the strategy search can price the
        skew-aware exchanges (dedup-before-exchange, hot/cold hybrid —
        ops/embedding.expected_routed_lookups). Without an attached
        histogram the cost model assumes uniform ids, under which
        neither mode looks attractive."""
        self._id_histograms = dict(sketches or {})

    # ------------------------------------------------------------------
    # streaming fit: the continual train->serve loop (utils/delta.py)
    # ------------------------------------------------------------------
    def fit_stream(self, source, steps: Optional[int] = None,
                   publisher=None, publish_every: Optional[int] = None,
                   verbose: bool = True,
                   callbacks: Optional[List[Callable]] = None,
                   resume: bool = False):
        """Train indefinitely off a streaming source, publishing delta
        snapshots for the serving fleet.

        ``source`` is a callable ``source(i) -> batch`` returning the
        i-th host batch as a feature dict INCLUDING ``"label"``
        (:class:`~..data.stream.ArrayStream` wraps in-memory arrays;
        any deterministic callable works). Returning ``None`` or
        raising ``StopIteration``/``IndexError`` ends the stream;
        ``steps`` bounds it explicitly (None = until the source ends).

        Batches ride the same depth-K prefetch ring as ``fit()``'s
        streamed feed, under the same throttle — the staging thread
        slices + device_puts batch N+1 while the device trains batch N —
        and every batch is shown to the publisher's
        :class:`~..utils.delta.TouchedRowTracker` BEFORE staging, so at
        publish time the per-table touched-row candidates cover every
        trained step. Every ``publish_every`` optimizer steps the
        publisher emits a delta snapshot (or a full checkpoint when the
        chain compacts), inline on the training thread — the gather
        must see a quiesced step anyway.

        ``resume=True`` restores the newest valid full checkpoint from
        the publisher's directory first and continues the stream at the
        recorded position (``loader_state["stream_step"]``). The
        restarted publisher always re-anchors on a fresh full base —
        a dead trainer's delta chain is unextendable by design.

        Anomaly policy ``rollback`` is not supported here (there is no
        epoch to re-wind); use ``skip_step`` or ``raise``.
        """
        if getattr(self, "_anomaly_policy", "none") == "rollback":
            raise ValueError(
                'anomaly_policy="rollback" is not supported by '
                "fit_stream (no epoch position to re-wind); use "
                '"skip_step" or "raise"')
        if publish_every is None:
            publish_every = int(getattr(self.config, "publish_every", 0))
        if publisher is not None and publish_every < 1:
            raise ValueError(
                "fit_stream(publisher=...) needs publish_every >= 1 "
                "(--publish-every N)")
        if self.params is None:
            self.init_layers()
        start = 0
        if resume and publisher is not None:
            entry = publisher.mgr.restore_latest(self)
            if entry is not None:
                start = int((entry.get("loader_state") or {})
                            .get("stream_step", 0))
                if verbose:
                    print(f"resumed stream from checkpoint step "
                          f"{entry['step']} (stream position {start})")

        from ..data.prefetch import PrefetchPipeline

        def produce(i):
            try:
                batch = source(start + i)
            except (StopIteration, IndexError):
                raise IndexError("stream exhausted") from None
            if batch is None:
                raise IndexError("stream exhausted")
            if publisher is not None:
                publisher.observe_batch(batch)
            return self._stage_step(batch)

        drift_mon = self._drift_monitor("fit_stream")
        depth = max(int(getattr(self.config, "prefetch_depth", 2) or 0),
                    1)
        pipe = PrefetchPipeline(
            produce, depth=depth, num_items=steps, name="fit_stream",
            deadline_s=self._worker_deadline_s() or None)
        throttled = _Throttle()
        trained = 0
        publishes = 0
        mets = None
        t0 = time.time()
        try:
            while steps is None or trained < steps:
                try:
                    staged = pipe.get()
                except IndexError:
                    break
                _t_drift = (time.perf_counter()
                            if drift_mon is not None else 0.0)
                mets = throttled(self.train_batch_staged(staged))
                if drift_mon is not None:
                    drift_mon.observe_step(
                        time.perf_counter() - _t_drift)
                trained += 1
                if (publisher is not None and publish_every
                        and trained % publish_every == 0):
                    publisher.publish(
                        {"stream_step": start + trained})
                    publishes += 1
                if callbacks and mets is not None:
                    for cb in callbacks:
                        cb(self, trained, mets)
        finally:
            pipe.close()
        self._host_drain()
        if publisher is not None and trained and (
                not publish_every or trained % publish_every):
            # final partial interval: the fleet should not miss the tail
            publisher.publish({"stream_step": start + trained})
            publishes += 1
        elapsed = time.time() - t0
        bs = int(self.config.batch_size)
        if verbose and mets is not None:
            print(f"fit_stream: {trained} steps, "
                  f"loss={float(mets['loss']):.6f}, "
                  f"{trained * bs / max(elapsed, 1e-9):.2f} samples/s, "
                  f"{publishes} publish(es)")
        out = {"steps": trained, "elapsed": elapsed,
               "throughput": trained * bs / max(elapsed, 1e-9),
               "publishes": publishes,
               "publisher": (publisher.stats()
                             if publisher is not None else None)}
        if drift_mon is not None:
            out["drift"] = drift_mon.report()
            obstrace.export_to_dir()   # no-op without --obs-trace-dir
        return out
