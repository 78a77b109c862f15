"""Run configuration and CLI parsing.

Parity with the reference FFConfig (reference: include/config.h:65-103,
src/runtime/model.cc:1273-1381): epochs, batch size, learning rate, weight
decay, search budget/alpha, strategy import/export paths, workers-per-node /
nodes, profiling. The same flag spellings are accepted (`-e/--epochs`,
`-b/--batch-size`, `--lr/--learning-rate`, `--wd/--weight-decay`,
`--budget/--search-budget`, `--alpha/--search-alpha`, `--import`,
`--export`, `--nodes`, `-ll:gpu` → chips per host, `--profiling`), plus
TPU-specific ones (`--compute-dtype`).

Legion low-level flags other than -ll:gpu (-ll:fsize, -ll:zsize, -ll:cpu,
-ll:util, -ll:py, -dm:memorize — reference README.md:44-47) are accepted and
ignored: memory sizing and task-launch memoization are XLA/runtime concerns
on TPU (jit compile-once/execute-many subsumes -dm:memorize and Legion
tracing).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax.numpy as jnp


@dataclass
class FFConfig:
    # DefaultConfig values mirror reference model.cc:1273-1289
    epochs: int = 1
    batch_size: int = 64
    learning_rate: float = 0.01
    weight_decay: float = 0.0001
    workers_per_node: int = 0          # 0 = all local devices
    num_nodes: int = 1
    search_budget: int = 0
    search_alpha: float = 1.2
    # calibrate the search cost model by timing each op's compiled XLA
    # subgraph on the real device (reference Op::measure_compute_time
    # microbenchmarks, simulator.cc:235-273) instead of pure roofline
    search_measure: bool = False
    # jax.debug_nans: fail fast on NaNs (the TPU-native stand-in for the
    # reference's reliance on Legion region privileges + asserts for
    # catching bad numerics, SURVEY.md §5.2). Tri-state: None leaves the
    # process-global jax flag untouched; True/False set it explicitly
    # (it is a PROCESS-global switch — enabling it affects every model
    # in the process until another model sets it False)
    debug_nans: Optional[bool] = None
    # raise instead of warn when a strategy's degrees don't divide the real
    # tensor shapes (Model._effective_pc would otherwise execute a clamped,
    # different config)
    strict_strategies: bool = False
    import_strategy_file: str = ""
    export_strategy_file: str = ""
    profiling: bool = False
    profile_dir: str = ""              # xprof trace output (jax.profiler)
    simulation: bool = False
    seed: int = 0
    compute_dtype: str = "float32"     # or "bfloat16" for MXU-rate matmuls
    # use Pallas kernels for supported ops when running single-chip on TPU
    # (embedding-bag row-streaming; falls back to XLA lowering otherwise)
    use_pallas: bool = True
    # store ALL embedding tables in host RAM (numpy) with host-side
    # gather + touched-rows SGD scatter around the jitted step — the
    # reference hetero-strategy semantics (embedding_avx2.cc), letting
    # tables larger than HBM train on one chip. Per-op form: strategy
    # memory_types ZCM. Enable with --host-tables.
    host_resident_tables: bool = False
    # pipeline the host-table work (double-buffering, ON by default): the
    # previous step's cotangent readback + host scatter run on a worker
    # thread, overlapping the next step's gather + H2D + device dispatch.
    # When the input pipeline knows the next batch (fit's streaming
    # prefetch does), the worker gathers the NEXT step's rows BEFORE its
    # scatter, so the next dispatch never waits on the scatter. Either
    # way the contract is bounded ONE-step staleness: step N+1's forward
    # sees all updates through step N-1, maybe N (deterministically
    # through N-1 under the prefetch chaining); the racing gather sees
    # the table atomically before or after the in-flight scatter (never
    # torn — a model-level lock serializes table access on every path).
    # For bit-exact ordering (each gather sees every prior update),
    # disable with --no-host-tables-async.
    host_tables_async: bool = True
    # input-pipeline lookahead: how many batches the background staging
    # thread may slice + device_put (and host-gather) ahead of the device
    # (data/prefetch.py ring depth). 0 stages synchronously in the hot
    # loop. Set with --prefetch-depth N / --no-prefetch.
    prefetch_depth: int = 2
    # fused supersteps: compile K training steps into ONE executable (a
    # lax.scan over K pre-staged batches, core/model.py _train_superstep)
    # so a single host→device dispatch trains K steps — amortizing the
    # per-step dispatch overhead that dominates small-batch DLRM
    # (ROADMAP S1). "auto" (the default): fit() decides by itself. A
    # batch shape starts on the per-step path; at PROBE_DISPATCHES (32)
    # of its full-batch dispatches fit() asks, without waiting, whether
    # the device had already finished the step issued a quarter of the
    # throttle's bound before (8 dispatches on a TPU: the host learns
    # of a finished step about a millisecond late; core/model.py
    # _Pace); where HOST_PACED_SHARE (90%) of them found it through the
    # host sets the pace, and fit() goes on in
    # supersteps of the largest power of two K <= 16 whose megabatch
    # fits a staging budget (5% of a chip's HBM, 128 MB of host RAM
    # elsewhere). The verdict is kept on the model, once a shape. Auto
    # never fuses: an epoch shorter than the probe plus one K, a loop
    # that waits for its input, host-resident tables, more than one
    # process; with save_every set it halves K until every snapshot of
    # the per-step run is still written, at its step (K divides
    # save_every, the step the fit starts from and an epoch's steps;
    # FFModel._auto_superstep), and never raises. 1 = the exact
    # per-step dispatch, no probe. An integer K is honoured as given, no
    # probe: checkpoints/save_every snap to superstep boundaries (fit()
    # rejects save_every % K != 0); host-resident-table models fall back
    # to K=1 with a one-time warning (their per-step host gather/scatter
    # cannot run inside the scan). Set with --superstep {K,auto}.
    superstep: "int | str" = "auto"
    # fit(): whether to pre-stage the WHOLE dataset on device when it fits
    # the HBM budget ("auto"), always ("always" — trusts the caller on
    # capacity), or never ("never" — forces the streaming/prefetch path;
    # what bench_pipeline uses to compare paths). Set with
    # --stage-dataset {auto,always,never}.
    stage_dataset: str = "auto"
    # run the conv stack (Conv2D/Pool2D/BatchNorm) in NHWC internally —
    # the TPU-native layout (the NCHW API shape is the cuDNN-native
    # choice, reference conv_2d.cu); disable with --no-nhwc
    conv_nhwc: bool = True
    # update only the gathered embedding rows under plain SGD instead of
    # materializing table-sized dense gradients (numerically identical;
    # avoids streaming the full tables through HBM every step). Disable
    # with --dense-embedding-update.
    sparse_embedding_update: bool = True
    # model-wide default QUANTIZED STORAGE policy for embedding tables
    # (quant/: "fp32" | "bf16" | "int8" | "fp8"): int8/fp8 rows store
    # one fp32 scale per row and cut per-table HBM, exchange payloads,
    # delta publishes, and cache bytes ~4x. Per-table overrides ride the
    # strategy file (ParallelConfig.quant_dtype). Set with --emb-dtype.
    emb_dtype: str = "fp32"
    # the quantized update rule: "master_weight" keeps an exact fp32
    # master (updates bit-identical to fp32 training; the quantized
    # representation ships at storage boundaries) — the safe default;
    # "stochastic_rounding" drops the master and re-quantizes after
    # every update (unbiased rounding; full training-memory win, small
    # accuracy tolerance). Set with --emb-update-rule.
    emb_update_rule: str = "master_weight"
    # VMEM-resident pallas LSTM scan kernel (weights pinned in VMEM
    # across the time loop — round 4 found the lax.scan cell
    # weight-stream-bound). Disable with --no-pallas-lstm.
    pallas_lstm: bool = True
    # space-to-depth lowering for strided low-channel convs (the MLPerf
    # ResNet-stem reformulation; a 3-channel stem fills 3/128 MXU lanes).
    # "off" | "on" (every eligible conv) | "auto" (measure both lowerings
    # per eligible conv at init and keep the faster — the TPU analog of
    # the reference's cuDNN find-algorithm pick, conv_2d.cu:217).
    # Set with --conv-s2d {on,off,auto}.
    conv_s2d: str = "off"
    # anomaly sentinel: per-step on-device finiteness check of the loss
    # and global gradient norm, with a policy for non-finite steps.
    # "none" (off, zero overhead) | "skip_step" (suppress the bad update
    # on device — fully async) | "rollback" (restore the last good
    # checkpoint and re-wind the step counter; needs fit(checkpoint_dir))
    # | "raise" (raise AnomalyError at the step boundary). rollback/raise
    # read the flag back every step (one host sync). Set with
    # --anomaly-policy.
    anomaly_policy: str = "none"
    # cap on consecutive-ish rollback recoveries per fit() before the
    # anomaly is re-raised (a persistently-NaN model must not loop)
    max_rollbacks: int = 3
    # rolling-checkpoint defaults for fit(); fit(checkpoint_dir=...)
    # arguments override. save_every counts optimizer steps; 0 = only a
    # final checkpoint. Set with --checkpoint-dir / --save-every /
    # --keep-last.
    checkpoint_dir: str = ""
    save_every: int = 0
    keep_last: int = 3
    # ---- continual learning (FFModel.fit_stream + utils/delta.py) -----
    # optimizer steps between delta-snapshot publishes in fit_stream;
    # 0 = no periodic publication. Set with --publish-every N.
    publish_every: int = 0
    # compaction trigger: when the live delta chain's accumulated bytes
    # exceed this fraction of its base checkpoint's size, the next
    # publish is a fresh full checkpoint. Set with --delta-compact-frac.
    delta_compact_frac: float = 0.5
    # optional hard cadence: a full checkpoint every N delta publishes
    # regardless of size (0 = compaction-only). Set with
    # --delta-full-every N.
    delta_full_every: int = 0
    # elastic-mesh recovery (parallel/elastic.py): what fit() does when
    # the mesh degrades (device loss via MeshDegraded, or a background
    # worker missing its liveness deadline via WorkerStalled).
    # "off" (propagate — legacy) | "resume" (re-plan onto the survivors
    # and restore the newest rolling snapshot; exact, needs
    # checkpoint_dir) | "inplace" (re-plan and reshard the in-memory
    # state; no checkpoint needed, single-controller only). Set with
    # --elastic {off,resume,inplace}.
    elastic: str = "off"
    # liveness deadline (seconds) for background workers — the prefetch
    # ring's staging thread, the async host-table scatter worker — and
    # the collective probe. 0 disables the watchdogs (blocking waits).
    # Set with --worker-deadline SECONDS.
    worker_deadline_s: float = 0.0
    # MCMC budget for the post-degradation strategy re-search; 0 ships
    # the greedy clamped plan without searching. Set with
    # --elastic-budget N.
    elastic_search_budget: int = 100
    # cap on elastic recoveries per fit() call before the degradation is
    # re-raised (a flapping fleet must not loop forever). Set with
    # --max-recoveries N.
    max_recoveries: int = 3
    # elastic scale-UP: when ON, returned devices (ParticipantRegistry
    # heartbeats from a re-admitted host / FF_FAULT_RETURN_DEVICE) raise
    # a typed MeshReturned at a step boundary and fit() grows the mesh
    # back via parallel.elastic.expand — the inverse of the shrink
    # recovery above. Requires elastic != "off". Set with
    # --elastic-expand.
    elastic_expand: bool = False
    # persistent warm caches (utils/warmcache.py): serialize AOT
    # executables + MCMC plans so recoveries, expansions, and serving
    # replica boots warm-start from disk instead of re-searching /
    # recompiling. "" = off; "auto" = <checkpoint_dir>/cache (the caches
    # live next to the manifest); any other value = that directory. Set
    # with --compile-cache-dir {auto,PATH}.
    compile_cache_dir: str = ""
    # ---- online serving (serve/engine.py InferenceEngine) -------------
    # largest dynamic batch per dispatch; requests coalesce up to this
    # and pad to the smallest power-of-two bucket, every bucket AOT-
    # compiled at engine startup. Set with --serve-max-batch N.
    serve_max_batch: int = 64
    # dynamic-batching flush deadline: a batch dispatches when it reaches
    # serve_max_batch OR when its oldest request has waited this long.
    # Set with --serve-max-delay-ms MS.
    serve_max_delay_ms: float = 5.0
    # bounded request queue; a submit against a full queue is rejected
    # immediately with a typed Overloaded (backpressure, not buffering
    # bloat). Set with --serve-queue N.
    serve_queue: int = 256
    # per-request deadline: a request still queued (or in flight) past
    # this budget fails with DeadlineExceeded instead of occupying a
    # batch slot. 0 disables. Set with --serve-deadline-ms MS.
    serve_deadline_ms: float = 0.0
    # LRU embedding-row cache for host-RESIDENT tables on the serving
    # path: per-sample lookup results are cached so hot rows skip the
    # host gather. Capacity in cached samples; 0 disables. Invalidated
    # on every hot reload. Set with --serve-cache-rows N.
    serve_cache_rows: int = 0
    # pre-warm the embedding-row cache at engine start from a published
    # id-frequency histogram (the id_histogram.npz a DeltaPublisher
    # writes next to its snapshots, or the checkpoint dir holding one):
    # zipfian traffic concentrates on few index tuples, so a fresh
    # replica starts with the hot working set already cached. Set with
    # --serve-cache-warm PATH.
    serve_cache_warm: str = ""
    # snapshot-watcher poll interval for zero-downtime hot reload of a
    # CheckpointManager directory. Set with --serve-poll SECONDS.
    serve_poll_s: float = 0.5
    # batch-formation discipline: "continuous" (default) admits
    # whatever queued during the previous dispatch into the next one
    # immediately — iteration-level batching à la Orca, the dispatch IS
    # the coalescing window; "flush" restores the pure size/deadline
    # flush cycle (a partial batch always waits out serve_max_delay_ms).
    # Set with --serve-batching {continuous,flush}.
    serve_batching: str = "continuous"
    # ---- serving fleet (serve/router.py FleetRouter) ------------------
    # replica count for the multi-replica serving fleet (one engine per
    # device/host, data-parallel params); 1 = single engine, no router.
    # Set with --serve-replicas N.
    serve_replicas: int = 1
    # bounded per-request re-dispatches (exponential backoff, different
    # replica) on Overloaded/DeadlineExceeded/replica failure. Set with
    # --serve-retries N.
    serve_retries: int = 2
    # tail-latency hedging: a request unresolved after this long is
    # duplicated to a second replica, first result wins. 0 disables.
    # Set with --serve-hedge-ms MS.
    serve_hedge_ms: float = 0.0
    # share of traffic routed to the canary cohort while a canary
    # deploy is active. Set with --serve-canary-fraction F.
    serve_canary_fraction: float = 0.1
    # ---- SLO-driven autoscaling (serve/autoscale.py Autoscaler) -------
    # serving latency objective in ms: the autoscaler grows the fleet
    # while sustained client-observed p99 exceeds this (0 disables the
    # latency trigger; queue depth still applies). Set with
    # --serve-slo-ms MS.
    serve_slo_ms: float = 0.0
    # fleet size bounds the autoscaler operates within. Set with
    # --serve-min-replicas N / --serve-max-replicas N.
    serve_min_replicas: int = 1
    serve_max_replicas: int = 8
    # sharded serving tier (serve/shardtier.py): split the fleet into
    # stateless rankers + N row-sharded embedding lookup shards so
    # tables live once (divided), not once per replica. 0 = replicated
    # tables (the pre-split fleet). Set with --serve-shards N.
    serve_shards: int = 0
    # per-shard-lookup budget (deadline + bounded retry; exhaustion
    # degrades per --serve-degrade). --serve-lookup-deadline-ms.
    serve_lookup_deadline_ms: float = 50.0
    # what a spent lookup budget does: "cache" answers from cache hits
    # + per-table default rows with degraded=True (the default — answer
    # beats error), "fail" raises so the router retries/sheds. Set with
    # --serve-degrade {cache,fail}.
    serve_degrade: str = "cache"
    # serving-seam transport (serve/transport.py): "inproc" keeps
    # today's method calls (bit-identical fast path), "tcp" carries the
    # wire protocol over real sockets so shards/replicas can run as
    # separate OS processes. Set with --serve-transport {inproc,tcp}.
    serve_transport: str = "inproc"
    # how many lookup shards to run as their OWN OS processes (spawned
    # from the seeded shard warm cache; requires
    # --serve-transport tcp). 0 = all shards in-process. Set with
    # --serve-shard-procs N.
    serve_shard_procs: int = 0
    # ---- retrieval cascade (dlrm_flexflow_tpu/retrieve/) --------------
    # "on" puts the two-tower retrieve stage in front of the ranker:
    # /predict answers USER requests (retrieve top-k, then rank the
    # candidates) and POST /retrieve exposes the index directly. Set
    # with --retrieve {off,on}.
    retrieve: str = "off"
    # candidates out of the retrieve stage per user. --retrieve-k N.
    retrieve_k: int = 100
    # retrieve-stage deadline feeding the per-request budget: the MIPS
    # fan-out gets min(this, what's left of --serve-deadline-ms); the
    # ranker gets the rest. --retrieve-deadline-ms MS.
    retrieve_deadline_ms: float = 25.0
    # how many index shards when the ranker tier is NOT sharded
    # (--serve-shards 0): a standalone index-only shard set. With
    # --serve-shards N the index rides those N shards and this knob
    # must be 0 or equal to N. --retrieve-shards M.
    retrieve_shards: int = 0
    # LRU cap on the eval-path AOT executable cache (_eval_step_execs):
    # serving many ad-hoc shapes must not leak executables. Evictions
    # are counted (FFModel.eval_exec_cache_stats / engine stats()). Set
    # with --eval-exec-cache N.
    eval_exec_cache: int = 32
    # ---- unified observability (dlrm_flexflow_tpu/obs/) ---------------
    # "on" enables the process-wide metrics registry (scrapeable at
    # GET /metrics in serve_dlrm.py), structured span tracing, and the
    # fit()/fit_stream() drift monitor. "off" (default) keeps every
    # instrument a no-op singleton — the hot paths pay nothing (type
    # identity pinned, like FF_SANITIZE=0's plain locks). Set with
    # --obs {off,on}.
    obs: str = "off"
    # directory to export the Chrome-trace/Perfetto JSON span ring into
    # at the end of fit()/fit_stream() (and on serve_dlrm shutdown);
    # "" = keep the ring in memory only. Set with --obs-trace-dir DIR.
    obs_trace_dir: str = ""
    # drift-monitor alarm threshold: a sustained measured/predicted
    # step-time (or collective-bytes) ratio above this emits the loud
    # structured drift warning. Set with --obs-drift-threshold R.
    obs_drift_threshold: float = 1.5
    unparsed: List[str] = field(default_factory=list)

    @property
    def num_devices(self) -> int:
        import jax
        per_node = self.workers_per_node or len(jax.devices())
        return per_node * self.num_nodes

    @property
    def jnp_compute_dtype(self):
        return jnp.bfloat16 if self.compute_dtype == "bfloat16" else jnp.float32

    @staticmethod
    def parse_args(argv: Optional[List[str]] = None) -> "FFConfig":
        import sys
        argv = list(sys.argv[1:] if argv is None else argv)
        cfg = FFConfig()
        i = 0

        def take():
            nonlocal i
            i += 1
            if i >= len(argv):
                raise ValueError(f"flag {argv[i - 1]!r} requires a value")
            return argv[i]

        while i < len(argv):
            a = argv[i]
            if a in ("-e", "--epochs"):
                cfg.epochs = int(take())
            elif a in ("-b", "--batch-size"):
                cfg.batch_size = int(take())
            elif a in ("--lr", "--learning-rate"):
                cfg.learning_rate = float(take())
            elif a in ("--wd", "--weight-decay"):
                cfg.weight_decay = float(take())
            elif a in ("--budget", "--search-budget"):
                cfg.search_budget = int(take())
            elif a in ("--alpha", "--search-alpha"):
                cfg.search_alpha = float(take())
            elif a == "--import":
                cfg.import_strategy_file = take()
            elif a == "--export":
                cfg.export_strategy_file = take()
            elif a == "--nodes":
                cfg.num_nodes = int(take())
            elif a == "-ll:gpu":  # reference flag for devices/node
                cfg.workers_per_node = int(take())
            elif a in ("-ll:fsize", "-ll:zsize", "-ll:cpu", "-ll:util",
                       "-ll:py", "-ll:pysize"):
                take()  # accepted+ignored (Legion memory/processor sizing)
            elif a in ("-dm:memorize", "--simulation"):
                if a == "--simulation":
                    cfg.simulation = True
            elif a == "--profiling":
                cfg.profiling = True
            elif a == "--profile-dir":
                cfg.profile_dir = take()
            elif a == "--seed":
                cfg.seed = int(take())
            elif a == "--compute-dtype":
                cfg.compute_dtype = take()
            elif a == "--dense-embedding-update":
                cfg.sparse_embedding_update = False
            elif a == "--measure-ops":
                cfg.search_measure = True
            elif a == "--debug-nans":
                cfg.debug_nans = True
            elif a == "--strict-strategies":
                cfg.strict_strategies = True
            elif a == "--no-nhwc":
                cfg.conv_nhwc = False
            elif a == "--no-pallas-lstm":
                cfg.pallas_lstm = False
            elif a == "--conv-s2d":
                v = take()
                if v not in ("on", "off", "auto"):
                    raise ValueError(f"--conv-s2d expects on|off|auto, "
                                     f"got {v!r}")
                cfg.conv_s2d = v
            elif a == "--anomaly-policy":
                v = take()
                if v not in ("none", "skip_step", "rollback", "raise"):
                    raise ValueError(
                        f"--anomaly-policy expects "
                        f"none|skip_step|rollback|raise, got {v!r}")
                cfg.anomaly_policy = v
            elif a == "--checkpoint-dir":
                cfg.checkpoint_dir = take()
            elif a == "--save-every":
                cfg.save_every = int(take())
            elif a == "--keep-last":
                cfg.keep_last = int(take())
            elif a == "--publish-every":
                cfg.publish_every = int(take())
            elif a == "--delta-compact-frac":
                cfg.delta_compact_frac = float(take())
            elif a == "--delta-full-every":
                cfg.delta_full_every = int(take())
            elif a == "--elastic":
                v = take()
                if v not in ("off", "resume", "inplace"):
                    raise ValueError(f"--elastic expects "
                                     f"off|resume|inplace, got {v!r}")
                cfg.elastic = v
            elif a == "--worker-deadline":
                cfg.worker_deadline_s = float(take())
            elif a == "--elastic-budget":
                cfg.elastic_search_budget = int(take())
            elif a == "--max-recoveries":
                cfg.max_recoveries = int(take())
            elif a == "--elastic-expand":
                cfg.elastic_expand = True
            elif a == "--compile-cache-dir":
                cfg.compile_cache_dir = take()
            elif a == "--host-tables":
                cfg.host_resident_tables = True
            elif a == "--host-tables-async":
                cfg.host_tables_async = True
            elif a == "--no-host-tables-async":
                cfg.host_tables_async = False
            elif a == "--prefetch-depth":
                cfg.prefetch_depth = int(take())
            elif a == "--no-prefetch":
                cfg.prefetch_depth = 0
            elif a == "--superstep":
                v = take()
                if v == "auto":
                    cfg.superstep = "auto"
                else:
                    try:
                        cfg.superstep = int(v)
                    except ValueError:
                        raise ValueError(
                            f"--superstep expects a positive integer K or "
                            f"'auto', got {v!r}")
                    if cfg.superstep < 1:
                        raise ValueError(
                            f"--superstep expects K >= 1, got {v}")
            elif a == "--emb-dtype":
                v = take()
                if v not in ("fp32", "bf16", "int8", "fp8"):
                    raise ValueError(
                        f"--emb-dtype expects fp32|bf16|int8|fp8, "
                        f"got {v!r}")
                cfg.emb_dtype = v
            elif a == "--emb-update-rule":
                v = take()
                if v not in ("master_weight", "stochastic_rounding"):
                    raise ValueError(
                        f"--emb-update-rule expects "
                        f"master_weight|stochastic_rounding, got {v!r}")
                cfg.emb_update_rule = v
            elif a == "--serve-max-batch":
                cfg.serve_max_batch = int(take())
            elif a == "--serve-max-delay-ms":
                cfg.serve_max_delay_ms = float(take())
            elif a == "--serve-queue":
                cfg.serve_queue = int(take())
            elif a == "--serve-deadline-ms":
                cfg.serve_deadline_ms = float(take())
            elif a == "--serve-cache-rows":
                cfg.serve_cache_rows = int(take())
            elif a == "--serve-cache-warm":
                cfg.serve_cache_warm = take()
            elif a == "--serve-poll":
                cfg.serve_poll_s = float(take())
            elif a == "--serve-batching":
                v = take()
                if v not in ("continuous", "flush"):
                    raise ValueError(f"--serve-batching expects "
                                     f"continuous|flush, got {v!r}")
                cfg.serve_batching = v
            elif a == "--serve-replicas":
                cfg.serve_replicas = int(take())
                if cfg.serve_replicas < 1:
                    raise ValueError(f"--serve-replicas expects N >= 1, "
                                     f"got {cfg.serve_replicas}")
            elif a == "--serve-retries":
                cfg.serve_retries = int(take())
            elif a == "--serve-hedge-ms":
                cfg.serve_hedge_ms = float(take())
            elif a == "--serve-canary-fraction":
                cfg.serve_canary_fraction = float(take())
            elif a == "--serve-slo-ms":
                cfg.serve_slo_ms = float(take())
            elif a == "--serve-min-replicas":
                cfg.serve_min_replicas = int(take())
                if cfg.serve_min_replicas < 1:
                    raise ValueError(
                        f"--serve-min-replicas expects N >= 1, got "
                        f"{cfg.serve_min_replicas}")
            elif a == "--serve-max-replicas":
                cfg.serve_max_replicas = int(take())
                if cfg.serve_max_replicas < 1:
                    raise ValueError(
                        f"--serve-max-replicas expects N >= 1, got "
                        f"{cfg.serve_max_replicas}")
            elif a == "--serve-shards":
                cfg.serve_shards = int(take())
                if cfg.serve_shards < 0:
                    raise ValueError(
                        f"--serve-shards expects N >= 0, got "
                        f"{cfg.serve_shards}")
            elif a == "--serve-lookup-deadline-ms":
                cfg.serve_lookup_deadline_ms = float(take())
            elif a == "--serve-degrade":
                v = take()
                if v not in ("cache", "fail"):
                    raise ValueError(f"--serve-degrade expects "
                                     f"cache|fail, got {v!r}")
                cfg.serve_degrade = v
            elif a == "--serve-transport":
                v = take()
                if v not in ("inproc", "tcp"):
                    raise ValueError(f"--serve-transport expects "
                                     f"inproc|tcp, got {v!r}")
                cfg.serve_transport = v
            elif a == "--serve-shard-procs":
                cfg.serve_shard_procs = int(take())
                if cfg.serve_shard_procs < 0:
                    raise ValueError(
                        f"--serve-shard-procs expects N >= 0, got "
                        f"{cfg.serve_shard_procs}")
            elif a == "--retrieve":
                v = take()
                if v not in ("off", "on"):
                    raise ValueError(f"--retrieve expects off|on, "
                                     f"got {v!r}")
                cfg.retrieve = v
            elif a == "--retrieve-k":
                cfg.retrieve_k = int(take())
                if cfg.retrieve_k < 1:
                    raise ValueError(f"--retrieve-k expects N >= 1, "
                                     f"got {cfg.retrieve_k}")
            elif a == "--retrieve-deadline-ms":
                cfg.retrieve_deadline_ms = float(take())
                if cfg.retrieve_deadline_ms < 0:
                    raise ValueError(
                        f"--retrieve-deadline-ms expects MS >= 0, got "
                        f"{cfg.retrieve_deadline_ms}")
            elif a == "--retrieve-shards":
                cfg.retrieve_shards = int(take())
                if cfg.retrieve_shards < 0:
                    raise ValueError(
                        f"--retrieve-shards expects N >= 0, got "
                        f"{cfg.retrieve_shards}")
            elif a == "--eval-exec-cache":
                cfg.eval_exec_cache = int(take())
            elif a == "--obs":
                v = take()
                if v not in ("off", "on"):
                    raise ValueError(f"--obs expects off|on, got {v!r}")
                cfg.obs = v
            elif a == "--obs-trace-dir":
                cfg.obs_trace_dir = take()
            elif a == "--obs-drift-threshold":
                cfg.obs_drift_threshold = float(take())
                if cfg.obs_drift_threshold <= 0:
                    raise ValueError(
                        f"--obs-drift-threshold expects R > 0, got "
                        f"{cfg.obs_drift_threshold}")
            elif a == "--stage-dataset":
                v = take()
                if v not in ("auto", "always", "never"):
                    raise ValueError(f"--stage-dataset expects "
                                     f"auto|always|never, got {v!r}")
                cfg.stage_dataset = v
            else:
                cfg.unparsed.append(a)
            i += 1
        return cfg
