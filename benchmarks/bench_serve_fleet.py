#!/usr/bin/env python
"""Serving-fleet benchmark: what does the router buy under open-loop load?

Three questions, matching the ISSUE-6 acceptance bar:

- **Scaling**: attained QPS at a p99 SLO for 1/2/4 replicas under
  open-loop Poisson arrivals (open loop so a slow server cannot slow the
  arrival process down and flatter its own tail — the coordinated-
  omission trap of closed-loop drivers). Reported as the highest offered
  rate whose measured p99 stays inside the SLO.
- **Survival**: a 2-replica fleet at a fixed offered rate with one
  replica killed mid-run (`FF_FAULT_REPLICA_DOWN`) — failed requests
  (the bar is ZERO: every request retried to success on the survivor)
  and p99 before/during the outage.
- **Autoscaling under a load spike** (ISSUE 12): a 1-replica fleet with
  the SLO autoscaler attached serves comfortably inside the SLO; the
  offered rate then DOUBLES past single-replica capacity (each dispatch
  carries an injected fixed cost so capacity is dispatch-bound, not
  host-CPU-bound — the accelerator-serving shape, and the only regime
  where in-process CPU replicas scale at all). The autoscaler must grow
  the fleet on the sustained breach and the post-growth p99 must
  RE-ENTER the SLO with zero failed requests across all three phases —
  the ISSUE-12 acceptance bar.
- **Sharded serving tier** (ISSUE 13): a host-table model whose tables
  exceed a per-replica HBM budget is REJECTED by the replicated fleet's
  admission check and served through the row-sharded lookup tier
  instead, at the measured fraction of the replicated engine's
  p99-SLO QPS on a shape that fits both (bar: >= 0.8x) — plus a chaos
  run killing one embedding shard under open-loop traffic (zero failed
  requests; degraded-flagged answers allowed; warm-cache replacement
  probed in; p99 re-enters the SLO).
- **Wire protocol** (ISSUE 16): the same sharded tier served over REAL
  OS-process + socket boundaries — attained QPS at the p99 SLO through
  ``inproc`` vs ``tcp`` transports for 1/2/4 shard processes, the
  per-seam RTT distribution the transport measured while doing it, and
  a chaos run that ``kill -9``s one shard OS process under open-loop
  traffic (zero failed requests; warm-cache replacement probes in; p99
  re-enters the SLO).
- **Continuous vs flush batching**: the same open-loop ladder through
  one engine in continuous (iteration-level) admission vs the
  pre-continuous size/deadline flush cycle. Continuous batching is
  self-clocked — the previous dispatch IS the coalescing window, so the
  batch grows adaptively with load — where flush mode caps a batch at
  whatever ``max_delay`` collects and adds that delay to every partial
  batch; attained QPS at the SLO must be >= for continuous. (A
  closed-loop drive would flatter flush mode: N threads resubmitting in
  lock-step after each batch hand it a perfectly re-formed burst to
  collect — exactly the coordination open loop exists to avoid.)

Prints ONE JSON line; `measure()` is imported by bench.py when
BENCH_SERVE_FLEET=1. Usage:
  python benchmarks/bench_serve_fleet.py [--requests N] [--slo-ms MS]
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _build(dev=None, max_batch=64):
    """One replica's model on its own single-device mesh (replicas must
    not share a mesh — concurrent dispatches would serialize, and on
    CPU can deadlock interleaved collectives)."""
    import jax

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
    from dlrm_flexflow_tpu.parallel.mesh import make_mesh
    dcfg = DLRMConfig(embedding_size=[8192] * 8, sparse_feature_size=16,
                      mlp_bot=[16, 64, 16], mlp_top=[144, 64, 1])
    cfg = ff.FFConfig(batch_size=max_batch, seed=3,
                      serve_max_batch=max_batch)
    model = ff.FFModel(cfg)
    build_dlrm(model, dcfg)
    mesh = None
    if dev is not None:
        devs = jax.devices()
        lo = dev % len(devs)
        mesh = make_mesh(devices=devs[lo:lo + 1])
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"],
                  mesh=mesh)
    model.init_layers()
    return model, dcfg


def _requests(dcfg, n, seed=0):
    from dlrm_flexflow_tpu.models.dlrm import synthetic_batch
    x, _ = synthetic_batch(dcfg, n, seed=seed)
    return [{k: v[i:i + 1] for k, v in x.items()} for i in range(n)]


def _router(n, retries=3):
    import dlrm_flexflow_tpu as ff
    scfg = ff.ServeConfig(max_batch=64, queue_capacity=4096)
    fleet = ff.Fleet.build(lambda i: _build(dev=i)[0], n, scfg)
    rcfg = ff.RouterConfig(retries=retries, backoff_ms=2.0,
                           cooldown_s=0.5, health_interval_s=0.1,
                           probe_deadline_s=30.0)
    return ff.FleetRouter(fleet, rcfg)


def _poisson_drive(submit, reqs, rate_qps, n=None, seed=7):
    """Open-loop Poisson arrivals: submit request i at its scheduled
    arrival time regardless of how the server is doing, measure latency
    FROM THE SCHEDULE (late submission counts against the server).
    ``n`` requests are drawn cyclically from ``reqs``.
    Returns (latencies_ms sorted, failed_count, elapsed_s)."""
    import numpy as np
    n = len(reqs) if n is None else n
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_qps, size=n))
    lat_ms = []
    lat_lock = threading.Lock()
    failed = [0]
    futs = []
    t0 = time.perf_counter()
    for i in range(n):
        now = time.perf_counter() - t0
        wait = arrivals[i] - now
        if wait > 0:
            time.sleep(wait)
        t_sched = t0 + arrivals[i]

        def _done(f, t_sched=t_sched):
            try:
                f.result()
                with lat_lock:
                    lat_ms.append(1e3 * (time.perf_counter() - t_sched))
            except Exception:   # noqa: BLE001 — counted, not raised
                failed[0] += 1

        try:
            fut = submit(reqs[i % len(reqs)])
        except Exception:   # noqa: BLE001 — Overloaded at submit time
            failed[0] += 1  # is a failed request in an open-loop world
            continue
        fut.add_done_callback(_done)
        futs.append(fut)
    for f in futs:
        try:
            f.result(120)
        except Exception:   # noqa: BLE001 — already counted
            pass
    return sorted(lat_ms), failed[0], time.perf_counter() - t0


def _trial_n(reqs, rate_qps, min_s=0.5):
    """Requests per trial: at least the base set, and enough to SUSTAIN
    the offered rate for ``min_s`` — a burst that fits in the queue and
    drains after the last arrival would otherwise report a flawless
    tail at an unsustainable rate (p99-from-schedule of a 30 ms burst
    says nothing about steady state). The absolute cap only bounds the
    trial's memory/runtime; past the driver's own submit ceiling the
    schedule slips, which correctly counts against the server."""
    return int(min(max(len(reqs), rate_qps * min_s), 32768))


def _qps_at_slo(submit, reqs, slo_ms, rates):
    """Highest offered rate whose p99 meets the SLO with zero failures;
    rates are tried in ascending order and the sweep stops at the first
    miss (the attained-QPS knee). A short untimed Poisson pre-run
    absorbs first-dispatch jitter (lazy imports, thread spin-up)."""
    from dlrm_flexflow_tpu.serve import percentile
    _poisson_drive(submit, reqs, rates[0], n=min(64, len(reqs)))
    best = 0.0
    detail = []
    for rate in rates:
        lat, failed, _ = _poisson_drive(submit, reqs, rate,
                                        n=_trial_n(reqs, rate))
        p99 = percentile(lat, 99)
        ok = failed == 0 and p99 is not None and p99 <= slo_ms
        detail.append({"offered_qps": round(rate, 1),
                       "n": _trial_n(reqs, rate),
                       "p99_ms": round(p99, 2) if p99 else None,
                       "failed": failed, "ok": ok})
        if not ok:
            break
        best = rate
    return best, detail


def _measure_autoscale(slo_ms=150.0, dispatch_cost_s=0.02,
                       max_batch=8):
    """Load-doubling chaos: 1 replica inside the SLO -> offered rate
    doubles past its capacity -> the autoscaler grows the fleet -> p99
    re-enters the SLO with zero failed requests.

    Capacity is made dispatch-bound by injecting a fixed per-dispatch
    cost (``FF_FAULT_SERVE_DELAY`` semantics): one replica sustains
    ~max_batch/dispatch_cost rows/s, so doubling the offered rate past
    that backs its queue up — the breach signal — while a second
    replica honestly doubles capacity (pure host-CPU-bound replicas
    would NOT scale in-process; see the module-note)."""
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig
    from dlrm_flexflow_tpu.serve import percentile
    from dlrm_flexflow_tpu.utils import faults

    dcfg = DLRMConfig(embedding_size=[8192] * 8, sparse_feature_size=16,
                      mlp_bot=[16, 64, 16], mlp_top=[144, 64, 1])
    reqs = _requests(dcfg, 128)
    cap_qps = max_batch / dispatch_cost_s        # one replica's ceiling
    rate_lo = 0.6 * cap_qps
    rate_hi = 1.5 * cap_qps                      # the doubled+ spike

    def factory(i):
        return _build(dev=i, max_batch=max_batch)[0]

    fleet = ff.Fleet.build(factory, 1, ff.ServeConfig(
        max_batch=max_batch, queue_capacity=8192))
    router = ff.FleetRouter(fleet, ff.RouterConfig(
        retries=4, backoff_ms=2.0, cooldown_s=0.5,
        health_interval_s=0.1, probe_deadline_s=60.0))
    scaler = ff.Autoscaler(router, ff.AutoscaleConfig(
        slo_ms=slo_ms, min_replicas=1, max_replicas=3,
        interval_s=0.1, sustain=3, queue_hwm=2.0,
        idle_sustain=10 ** 6,                    # no shrink mid-bench
        cooldown_s=1.0))
    router.start()
    scaler.start()
    try:
        for r in reqs[:16]:
            router.predict(r, timeout=120)
        with faults.active_plan(faults.FaultPlan(
                serve_delay_s=dispatch_cost_s)):
            lat_before, failed_before, _ = _poisson_drive(
                router.submit, reqs, rate_lo,
                n=_trial_n(reqs, rate_lo, min_s=2.0))
            # the spike: sustained past one replica's ceiling. Drive
            # long enough for breach detection + replica build/warm.
            lat_spike, failed_spike, _ = _poisson_drive(
                router.submit, reqs, rate_hi,
                n=_trial_n(reqs, rate_hi, min_s=8.0))
            # after growth: same doubled rate, now under capacity
            lat_after, failed_after, _ = _poisson_drive(
                router.submit, reqs, rate_hi,
                n=_trial_n(reqs, rate_hi, min_s=3.0))
        sstats = scaler.stats()
        p99_before = percentile(lat_before, 99)
        p99_spike = percentile(lat_spike, 99)
        p99_after = percentile(lat_after, 99)
        return {
            "slo_ms": slo_ms,
            "single_replica_cap_qps": round(cap_qps, 1),
            "offered_qps_before": round(rate_lo, 1),
            "offered_qps_spike": round(rate_hi, 1),
            "p99_ms_before": round(p99_before or 0, 2),
            "p99_ms_during_spike": round(p99_spike or 0, 2),
            "p99_ms_after_growth": round(p99_after or 0, 2),
            "failed_total": failed_before + failed_spike + failed_after,
            "grows": sstats["grows"],
            "fleet_size_final": sstats["size"],
            "grow_reason": sstats["last_reason"],
            "p99_reenters_slo": bool(p99_after is not None
                                     and p99_after <= slo_ms),
            "zero_failed": (failed_before + failed_spike
                            + failed_after) == 0,
        }
    finally:
        scaler.close()
        router.close()


def _build_host(max_batch=64):
    """A host-resident-table DLRM (the >HBM configuration the sharded
    tier exists for): same shape as ``_build`` but with tables in host
    memory, sliceable into lookup shards."""
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
    dcfg = DLRMConfig(embedding_size=[8192] * 8, sparse_feature_size=16,
                      mlp_bot=[16, 64, 16], mlp_top=[144, 64, 1])
    cfg = ff.FFConfig(batch_size=max_batch, seed=3,
                      serve_max_batch=max_batch,
                      host_resident_tables=True,
                      host_tables_async=False)
    model = ff.FFModel(cfg)
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"])
    model.init_layers()
    return model, dcfg


def _measure_shardtier(slo_ms=50.0, nshards=4, requests=256):
    """ISSUE-13 acceptance measurements for the sharded serving tier:

    - **feasibility** — a model whose tables exceed the per-replica HBM
      budget is REJECTED by the replicated fleet's admission check and
      admitted by the sharded tier (tables stored once, divided);
    - **throughput tax** — attained QPS at the p99 SLO through the
      sharded tier vs the replicated (tables-resident) engine on a
      model that FITS both; the bar is >= 0.8x;
    - **chaos** — one embedding shard killed under open-loop traffic:
      zero failed requests (degraded-flagged answers allowed), the
      replacement shard boots from the warm cache and is probed in, and
      p99 re-enters the SLO afterwards.
    """
    import tempfile

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.serve import percentile
    from dlrm_flexflow_tpu.serve.shardtier import (EmbeddingShardSet,
                                                   ShardTierConfig,
                                                   check_serving_feasible,
                                                   serving_footprint)
    from dlrm_flexflow_tpu.utils import faults
    out = {"nshards": nshards}

    # --- (a) tables-exceed-one-host feasibility sweep -------------------
    model, dcfg = _build_host()
    fp = serving_footprint(model, replicas=2)
    budget = fp["dense_bytes"] + fp["table_bytes"] // 2
    replicated = check_serving_feasible(model, 2, budget, nshards=0)
    sharded = check_serving_feasible(model, 2, budget, nshards=nshards)
    out["feasibility"] = {
        "budget_mb": round(budget / 1e6, 2),
        "table_mb": round(fp["table_bytes"] / 1e6, 2),
        "replicated_feasible": replicated["feasible"],
        "replicated_reason": replicated["reason"],
        "sharded_feasible": sharded["feasible"],
        "sharded_ranker_mb": round(sharded["ranker_bytes"] / 1e6, 3),
        "sharded_shard_mb": round(sharded["shard_bytes"] / 1e6, 3),
    }

    reqs = _requests(dcfg, requests)

    def _qps(engine):
        for r in reqs[:16]:
            engine.predict(r, timeout=60)               # warm
        t0 = time.perf_counter()
        for r in reqs[:64]:
            engine.predict(r, timeout=60)
        base = 64 / (time.perf_counter() - t0)
        rates = [base * f for f in (0.5, 1.0, 2.0, 4.0, 8.0)]
        return _qps_at_slo(engine.submit, reqs, slo_ms, rates)

    # --- (b) replicated (tables-resident) engine baseline ---------------
    eng = ff.InferenceEngine(model, ff.ServeConfig(
        max_batch=64, queue_capacity=4096)).start()
    try:
        best_rep, sweep_rep = _qps(eng)
    finally:
        eng.close()
    out["replicated_qps_at_slo"] = round(best_rep, 1)

    # --- (c) sharded tier on the same shape -----------------------------
    cache_dir = tempfile.mkdtemp(prefix="ff-shard-cache-")
    m2, _ = _build_host()
    tier = ShardTierConfig(nshards=nshards, lookup_deadline_ms=1000.0,
                           cooldown_s=0.0, replace_after=2,
                           eject_after=2)
    sset = EmbeddingShardSet.build(m2, nshards, config=tier,
                                   cache_dir=cache_dir)
    EmbeddingShardSet.release_ranker_tables(m2)
    # cache deliberately smaller than the request pool: the chaos run
    # must keep CONSULTING the shard tier (a pool-sized cache would
    # ride out the outage on hits alone and measure nothing)
    eng = ff.InferenceEngine(m2, ff.ServeConfig(
        max_batch=64, queue_capacity=4096, cache_rows=32),
        shard_set=sset).start()
    try:
        best_shd, sweep_shd = _qps(eng)
        out["sharded_qps_at_slo"] = round(best_shd, 1)
        out["sharded_vs_replicated"] = (
            round(best_shd / best_rep, 3) if best_rep > 0 else None)

        # --- (d) chaos: kill one shard under open-loop traffic ----------
        rate = max(best_shd * 0.5, 8.0)
        half = len(reqs) // 2
        lat_before, failed_before, _ = _poisson_drive(
            eng.submit, reqs[:half], rate)
        stop = threading.Event()

        def _health_loop():
            while not stop.is_set():
                try:
                    sset.health_tick()
                except Exception:   # noqa: BLE001 — keep ticking
                    pass
                time.sleep(0.05)

        ht = threading.Thread(target=_health_loop, daemon=True,
                              name="ff-bench-shard-health")
        ht.start()
        plan = faults.FaultPlan()
        plan.shard_down[0] = -1
        with faults.active_plan(plan):
            lat_during, failed_during, _ = _poisson_drive(
                eng.submit, reqs[half:], rate)
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline and any(
                    r.state != "healthy" for r in sset.shards):
                time.sleep(0.05)
        lat_after, failed_after, _ = _poisson_drive(
            eng.submit, reqs[:half], rate)
        stop.set()
        ht.join(2.0)
        st = eng.stats()
        p99_after = percentile(lat_after, 99)
        out["chaos"] = {
            "offered_qps": round(rate, 1),
            "failed_before": failed_before,
            "failed_during_kill": failed_during,
            "failed_after": failed_after,
            "p99_ms_before": round(percentile(lat_before, 99) or 0, 2),
            "p99_ms_during_kill": round(percentile(lat_during, 99)
                                        or 0, 2),
            "p99_ms_after": round(p99_after or 0, 2),
            "p99_reentered_slo": bool(p99_after is not None
                                      and p99_after <= slo_ms),
            "degraded_responses": st["degraded_responses"],
            "shard_replacements": sset.replacements,
            "all_shards_healthy": all(r.state == "healthy"
                                      for r in sset.shards),
            "version_vector": sset.version_vector(),
        }
    finally:
        eng.close()
        sset.close()
    return out


def _spawn_shard_procs(cache_dir, nshards):
    """One ``shard_server`` OS process per slot; returns
    ``(procs, addresses)`` after every SHARD_SERVER_OK sentinel."""
    import subprocess

    from dlrm_flexflow_tpu.serve import shard_server
    procs = [shard_server.spawn(cache_dir, nshards, slot,
                                stderr=subprocess.STDOUT)
             for slot in range(nshards)]
    addresses = []
    try:
        for p in procs:
            port = None
            for line in p.stdout:
                if line.startswith("SHARD_SERVER_OK"):
                    kv = dict(i.split("=", 1) for i in line.split()[1:])
                    port = int(kv["port"])
                    break
            if port is None:
                raise RuntimeError(
                    f"shard process never booted (exit {p.poll()})")
            addresses.append(("127.0.0.1", port))
    except BaseException:
        for p in procs:
            p.kill()
        raise
    return procs, addresses


def _reap_procs(procs):
    for p in procs:
        if p.poll() is None:
            p.kill()
        try:
            p.wait(5)
        except Exception:   # noqa: BLE001 — best-effort teardown
            pass
        if p.stdout is not None:
            p.stdout.close()


def _measure_wire(slo_ms=50.0, requests=256, proc_counts=(1, 2, 4)):
    """ISSUE-16 acceptance measurements for the wire protocol:

    - **transport tax** — attained QPS at the p99 SLO through the SAME
      sharded tier carried by ``inproc`` method calls vs ``tcp`` real
      sockets to real shard OS processes, for 1/2/4 shard processes;
    - **per-seam RTT** — the lookup seam's p50/p99 RTT the transport's
      own telemetry measured while serving the sweep (what FLX509
      prices the SLO budget against);
    - **proc-kill chaos** — ``kill -9`` (a real SIGKILL to a real pid)
      of one of 3 shard processes under open-loop traffic: zero failed
      requests, warm-cache replacement probes in, p99 re-enters.
    """
    import os as _os
    import signal
    import tempfile

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.serve import percentile
    from dlrm_flexflow_tpu.serve import transport as tp
    from dlrm_flexflow_tpu.serve.shardtier import (EmbeddingShardSet,
                                                   ShardTierConfig)

    model, dcfg = _build_host()
    reqs = _requests(dcfg, requests)
    out = {"slo_ms": slo_ms}

    def _tier_cfg(n, transport):
        return ShardTierConfig(nshards=n, lookup_deadline_ms=1000.0,
                               cooldown_s=0.0, replace_after=2,
                               eject_after=2, transport=transport)

    def _engine(sset):
        return ff.InferenceEngine(model, ff.ServeConfig(
            max_batch=64, queue_capacity=4096, cache_rows=32),
            shard_set=sset).start()

    def _qps(eng, rates):
        for r in reqs[:16]:
            eng.predict(r, timeout=60)                  # warm
        return _qps_at_slo(eng.submit, reqs, slo_ms, rates)

    # rate ladder calibrated off a 1-shard inproc closed-loop probe
    sset = EmbeddingShardSet.build(model, 1, config=_tier_cfg(1, "inproc"))
    eng = _engine(sset)
    try:
        for r in reqs[:16]:
            eng.predict(r, timeout=60)
        t0 = time.perf_counter()
        for r in reqs[:64]:
            eng.predict(r, timeout=60)
        base_qps = 64 / (time.perf_counter() - t0)
    finally:
        eng.close()
        sset.close()
    # wider-than-usual ladder: a 1-process tcp tier pays a socket round
    # trip per lookup, so its knee can sit well under the inproc probe
    rates = [base_qps * f for f in (0.125, 0.25, 0.5, 1.0, 2.0, 4.0)]
    out["closed_loop_qps"] = round(base_qps, 1)

    transports = {}
    for n in proc_counts:
        row = {}
        # inproc twin (same tier geometry, method-call carriage)
        sset = EmbeddingShardSet.build(model, n,
                                       config=_tier_cfg(n, "inproc"))
        eng = _engine(sset)
        try:
            best, _ = _qps(eng, rates)
            row["inproc_qps_at_slo"] = round(best, 1)
        finally:
            eng.close()
            sset.close()
        # tcp: real OS processes behind real sockets
        cache_dir = tempfile.mkdtemp(prefix=f"ff-wire-{n}-")
        cfg = _tier_cfg(n, "tcp")
        EmbeddingShardSet.seed_shard_cache(model, n, cache_dir,
                                           config=cfg)
        procs, addrs = _spawn_shard_procs(cache_dir, n)
        tp.reset_wire_stats()
        try:
            sset = EmbeddingShardSet.connect(addrs, config=cfg,
                                             cache_dir=cache_dir)
            eng = _engine(sset)
            try:
                best, _ = _qps(eng, rates)
                row["tcp_qps_at_slo"] = round(best, 1)
            finally:
                eng.close()
                sset.close()
            seam = tp.wire_stats().get("lookup", {})
            row["lookup_rtt_p50_ms"] = round(
                seam.get("rtt_p50_ms") or 0, 3)
            row["lookup_rtt_p99_ms"] = round(
                seam.get("rtt_p99_ms") or 0, 3)
            row["lookup_frames"] = seam.get("frames_sent", 0)
        finally:
            _reap_procs(procs)
        inp = row.get("inproc_qps_at_slo", 0)
        row["tcp_vs_inproc"] = (round(row["tcp_qps_at_slo"] / inp, 3)
                                if inp else None)
        transports[str(n)] = row
    out["transports"] = transports

    # --- chaos: SIGKILL one of 3 shard OS processes ---------------------
    n = 3
    cache_dir = tempfile.mkdtemp(prefix="ff-wire-chaos-")
    cfg = ShardTierConfig(nshards=n, lookup_deadline_ms=1000.0,
                          cooldown_s=0.0, replace_after=2,
                          eject_after=1, retries=0, transport="tcp")
    EmbeddingShardSet.seed_shard_cache(model, n, cache_dir, config=cfg)
    procs, addrs = _spawn_shard_procs(cache_dir, n)
    try:
        sset = EmbeddingShardSet.connect(addrs, config=cfg,
                                         cache_dir=cache_dir)
        eng = _engine(sset)
        stop = threading.Event()

        def _health_loop():
            while not stop.is_set():
                try:
                    sset.health_tick()
                except Exception:   # noqa: BLE001 — keep ticking
                    pass
                time.sleep(0.05)

        ht = threading.Thread(target=_health_loop, daemon=True,
                              name="ff-bench-wire-health")
        ht.start()
        try:
            rate = max(transports["2"].get("tcp_qps_at_slo", 8.0) * 0.5,
                       8.0)
            half = len(reqs) // 2
            lat_before, failed_before, _ = _poisson_drive(
                eng.submit, reqs[:half], rate)
            _os.kill(procs[0].pid, signal.SIGKILL)     # the real thing
            procs[0].wait(10)
            lat_during, failed_during, _ = _poisson_drive(
                eng.submit, reqs[half:], rate)
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline and any(
                    r.state != "healthy" for r in sset.shards):
                time.sleep(0.05)
            lat_after, failed_after, _ = _poisson_drive(
                eng.submit, reqs[:half], rate)
            st = eng.stats()
            p99_after = percentile(lat_after, 99)
            out["proc_kill"] = {
                "offered_qps": round(rate, 1),
                "failed_before": failed_before,
                "failed_during_kill": failed_during,
                "failed_after": failed_after,
                "p99_ms_before": round(percentile(lat_before, 99)
                                       or 0, 2),
                "p99_ms_during_kill": round(percentile(lat_during, 99)
                                            or 0, 2),
                "p99_ms_after": round(p99_after or 0, 2),
                "p99_reentered_slo": bool(p99_after is not None
                                          and p99_after <= slo_ms),
                "degraded_responses": st["degraded_responses"],
                "shard_replacements": sset.replacements,
                "all_shards_healthy": all(r.state == "healthy"
                                          for r in sset.shards),
            }
        finally:
            stop.set()
            ht.join(2.0)
            eng.close()
            sset.close()
    finally:
        _reap_procs(procs)
    return out


def measure(requests=256, slo_ms=50.0, replica_counts=(1, 2, 4)):
    import jax

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.serve import percentile
    from dlrm_flexflow_tpu.utils import faults

    out = {"requests": requests, "slo_ms": slo_ms,
           "devices": len(jax.devices()),
           # read the scaling section with the platform in mind: on a
           # shared-CPU host, N in-process replicas fight for the same
           # cores AND each sees 1/N of the traffic (smaller batches,
           # worse amortization), so attained QPS can go DOWN with N —
           # per-host replicas on real accelerators share neither
           "note": ("in-process replicas share host cores; scaling "
                    "numbers on CPU reflect batch dilution + core "
                    "contention, not the router")}

    # --- scaling sweep: attained QPS at the p99 SLO ---------------------
    # calibrate the rate ladder off a 1-replica closed-loop probe so the
    # same ladder exercises every fleet size
    scaling = {}
    probe_model, dcfg = _build(dev=0)
    reqs = _requests(dcfg, requests)
    eng = ff.InferenceEngine(probe_model, ff.ServeConfig(
        max_batch=64, queue_capacity=4096))
    with eng:
        for r in reqs[:8]:
            eng.predict(r, timeout=60)
        t0 = time.perf_counter()
        for r in reqs[:64]:
            eng.predict(r, timeout=60)
        base_qps = 64 / (time.perf_counter() - t0)
    rates = [base_qps * f for f in (0.5, 1.0, 2.0, 4.0, 8.0, 16.0)]
    out["single_replica_closed_loop_qps"] = round(base_qps, 1)

    for n in replica_counts:
        router = _router(n).start()
        try:
            for r in reqs[:16]:          # warm every replica's buckets
                router.predict(r, timeout=60)
            best, detail = _qps_at_slo(router.submit, reqs, slo_ms,
                                       rates)
            scaling[str(n)] = {"qps_at_slo": round(best, 1),
                               "sweep": detail}
        finally:
            router.close()
    out["scaling"] = scaling

    # --- survival: kill 1 of 2 replicas mid-run -------------------------
    router = _router(2).start()
    try:
        for r in reqs[:16]:
            router.predict(r, timeout=60)
        rate = max(rates[0], scaling.get("2", {}).get(
            "qps_at_slo", rates[0]) * 0.5)
        half = len(reqs) // 2
        lat_before, failed_before, _ = _poisson_drive(
            router.submit, reqs[:half], rate)
        with faults.active_plan(faults.FaultPlan(replica_down={1: -1})):
            lat_during, failed_during, _ = _poisson_drive(
                router.submit, reqs[half:], rate)
        st = router.stats()
        out["replica_kill"] = {
            "offered_qps": round(rate, 1),
            "failed_before": failed_before,
            "failed_during_kill": failed_during,
            "p99_ms_before": round(percentile(lat_before, 99) or 0, 2),
            "p99_ms_during_kill": round(percentile(lat_during, 99) or 0, 2),
            "retries": st["retries"],
            "ejections": st["fleet"]["replicas"][1]["ejections"],
        }
    finally:
        router.close()

    # --- autoscaler chaos: load doubles, fleet grows, p99 re-enters -----
    out["autoscale"] = _measure_autoscale(slo_ms=150.0)

    # --- sharded serving tier (ISSUE 13) --------------------------------
    out["shardtier"] = _measure_shardtier(slo_ms=slo_ms,
                                          requests=requests)

    # --- wire protocol: process + socket boundaries (ISSUE 16) ----------
    out["wire"] = _measure_wire(slo_ms=slo_ms, requests=requests)

    # --- continuous vs flush batching (open-loop ladder each) -----------
    modes = {}
    for continuous in (False, True):
        model, _ = _build(dev=0)
        eng = ff.InferenceEngine(model, ff.ServeConfig(
            max_batch=64, max_delay_ms=2.0, queue_capacity=4096,
            continuous=continuous))
        with eng:
            for r in reqs[:16]:
                eng.predict(r, timeout=60)              # warm
            best, detail = _qps_at_slo(eng.submit, reqs, slo_ms, rates)
            st = eng.stats()
        modes["continuous" if continuous else "flush"] = {
            "qps_at_slo": round(best, 1),
            "batch_fill": round(st["batch_fill"], 3),
            "flushes": st["flushes"],
            "sweep": detail,
        }
    out["batching"] = modes
    # None (not an astronomical epsilon ratio) when flush attains no
    # rate at all inside the SLO — continuous wins outright
    flush_qps = modes["flush"]["qps_at_slo"]
    out["continuous_vs_flush"] = (
        round(modes["continuous"]["qps_at_slo"] / flush_qps, 2)
        if flush_qps > 0 else None)
    return out


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    n = 256
    slo = 50.0
    if "--requests" in sys.argv:
        n = int(sys.argv[sys.argv.index("--requests") + 1])
    if "--slo-ms" in sys.argv:
        slo = float(sys.argv[sys.argv.index("--slo-ms") + 1])
    if "--wire-only" in sys.argv:
        print(json.dumps({"wire": _measure_wire(slo_ms=slo,
                                                requests=n)}))
    else:
        print(json.dumps(measure(requests=n, slo_ms=slo)))
