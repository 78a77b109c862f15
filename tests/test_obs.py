"""Unified observability layer (ISSUE 15): metrics registry, structured
tracing, and the live drift monitor.

Pinned contracts (the ISSUE-15 acceptance criteria):

- with ``--obs off`` every instrument is a shared NO-OP singleton (type
  identity, like ``make_lock``'s plain Lock) and ``span()`` returns the
  shared null context — the hot paths pay nothing;
- the registry's Counter/Gauge/Histogram respect labels, the Histogram
  reservoir is BOUNDED, and the Prometheus text exposition matches the
  golden format;
- spans nest correctly per thread, the ring overwrites oldest-first
  (``dropped()`` counts the tail), and the Chrome-trace export is valid
  trace-event JSON with thread-name metadata;
- a ``fit_stream`` + serving run traces spans from >= 4 subsystems
  (prefetch, superstep dispatch, delta publish, watcher apply/swap)
  with correct nesting and thread tags;
- the drift monitor stays quiet at calibration, fires on an injected
  ``FF_FAULT_SERVE_DELAY`` slowdown, and reproduces the FLX513
  replicated-plan finding at runtime (measured all-reduce bytes >>
  predicted);
- ``GET /metrics`` round-trips the registry over HTTP;
- the serving stack's ``stats()`` contracts are unchanged (keys pinned
  for engine / router / fleet / shard tier).
"""

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                           synthetic_batch)
from dlrm_flexflow_tpu.obs import configure, metrics, trace
from dlrm_flexflow_tpu.obs.drift import DriftMonitor
from dlrm_flexflow_tpu.parallel.mesh import make_mesh
from dlrm_flexflow_tpu.serve import InferenceEngine, ServeConfig
from dlrm_flexflow_tpu.utils import faults

DCFG = DLRMConfig(embedding_size=[64] * 2, sparse_feature_size=8,
                  mlp_bot=[4, 16, 8], mlp_top=[24, 16, 1])
BS = 16


def _build(seed=2, ndev=None, **cfg_kw):
    model = ff.FFModel(ff.FFConfig(batch_size=BS, seed=seed, **cfg_kw))
    build_dlrm(model, DCFG)
    mesh = make_mesh(devices=jax.devices()[:ndev]) if ndev else None
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"],
                  mesh=mesh)
    model.init_layers()
    return model


def _rows(n, seed=0):
    x, _ = synthetic_batch(DCFG, n, seed=seed)
    return x


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test starts and ends with an empty registry + trace ring
    (obs state is process-global by design)."""
    metrics.registry().reset()
    trace.clear()
    yield
    metrics.registry().reset()
    trace.clear()


# =====================================================================
# obs-off is a true no-op (type identity, like make_lock)
# =====================================================================
class TestObsOff:
    def test_instrument_type_identity(self):
        with metrics.override(False):
            assert metrics.counter("ff_x_total") is metrics.NULL_COUNTER
            assert metrics.gauge("ff_x") is metrics.NULL_GAUGE
            assert metrics.histogram("ff_x_ms") is metrics.NULL_HISTOGRAM
            assert isinstance(metrics.counter("ff_y_total"),
                              metrics.NullCounter)
            # mutators are branch-free no-ops, labels() returns self
            c = metrics.counter("ff_z_total", labelnames=("a",))
            assert c.labels(a="1") is c
            c.inc()
            c.inc(5, a="1")

    def test_span_is_a_profiler_annotation_and_the_ring_stays_empty(self):
        """The one span API: off, a span is just the profiler's
        annotation (inert without a profiler session) and nothing lands
        in the ring; on, the same call also records the event."""
        import jax
        with trace.override(False):
            trace.clear()
            s = trace.span("anything", k=1)
            assert type(s) is jax.profiler.TraceAnnotation
            with s:
                with trace.span("nested"):
                    pass
            trace.instant("marker")
            trace.complete("timed", 0.0)
            assert trace.events() == []
        with trace.override(True):
            trace.clear()
            with trace.span("anything", k=1):
                pass
            (ev,) = trace.events()
            assert (ev["name"], ev["args"]) == ("anything", {"k": 1})
            trace.clear()

    def test_off_latency_reservoir_is_plain_and_unregistered(self):
        with metrics.override(False):
            r = metrics.latency_reservoir("ff_lat_ms", maxlen=8,
                                          replica="0")
            assert type(r) is metrics.Reservoir
            r.observe(1.0)
        assert metrics.registry().collect() == {}

    def test_registry_collector_noop_when_off(self):
        with metrics.override(False):
            metrics.register_collector(lambda: [("ff_a", {}, 1.0)])
        assert metrics.registry().collect() == {}

    def test_config_default_off(self):
        cfg = ff.FFConfig.parse_args([])
        assert cfg.obs == "off"
        with metrics.override(False):
            assert configure(cfg) is False
            assert not metrics.enabled()


# =====================================================================
# registry semantics
# =====================================================================
class TestRegistry:
    def test_counter_labels_and_monotonic(self):
        with metrics.override(True):
            c = metrics.counter("ff_req_total", "requests",
                               labelnames=("replica",))
            c.inc(replica="0")
            c.inc(2, replica="0")
            c.labels(replica="1").inc()
            assert c.value(replica="0") == 3
            assert c.value(replica="1") == 1
            with pytest.raises(TypeError):
                c.labels(replica="0").set(5)

    def test_label_mismatch_rejected(self):
        with metrics.override(True):
            c = metrics.counter("ff_l_total", labelnames=("a",))
            with pytest.raises(ValueError, match="labelnames"):
                c.inc(b="1")
            with pytest.raises(ValueError, match="labelnames"):
                c.inc()

    def test_reregistration_type_conflict(self):
        with metrics.override(True):
            metrics.counter("ff_dup")
            with pytest.raises(ValueError, match="already registered"):
                metrics.gauge("ff_dup")
            with pytest.raises(ValueError, match="already registered"):
                metrics.counter("ff_dup", labelnames=("x",))
            # same spec: get-or-create returns the same instrument
            assert metrics.counter("ff_dup") is metrics.counter("ff_dup")

    def test_invalid_metric_name_rejected(self):
        with metrics.override(True):
            with pytest.raises(ValueError, match="invalid"):
                metrics.counter("bad name!")

    def test_reservoir_is_bounded(self):
        r = metrics.Reservoir(maxlen=100)
        for i in range(10_000):
            r.observe(float(i))
        assert len(r) == 100
        assert r.count == 10_000
        # ring keeps the NEWEST samples
        assert min(r.samples()) >= 9900.0

    def test_reservoir_empty_percentile_is_none(self):
        r = metrics.Reservoir(maxlen=4)
        assert r.percentile(99) is None      # never a flawless p99
        snap = r.snapshot()
        assert snap["p50"] is None and snap["count"] == 0

    def test_percentile_reexport_compat(self):
        # serve.engine re-exports obs.metrics.percentile unchanged
        from dlrm_flexflow_tpu.serve import percentile as p_serve
        from dlrm_flexflow_tpu.serve.engine import percentile as p_eng
        assert p_serve is p_eng is metrics.percentile
        assert p_serve([], 99) is None
        assert p_serve([1.0, 3.0], 50) == pytest.approx(2.0)

    def test_histogram_reservoir_bounded_per_child(self):
        with metrics.override(True):
            h = metrics.histogram("ff_h_ms", labelnames=("k",),
                                  reservoir=16)
            child = h.labels(k="a")
            for i in range(1000):
                child.observe(float(i))
            assert len(child) == 16
            assert child.count == 1000

    def test_prometheus_text_golden(self):
        with metrics.override(True):
            c = metrics.counter("ff_req_total", "requests served",
                               labelnames=("replica",))
            c.inc(3, replica="0")
            g = metrics.gauge("ff_depth", "queue depth")
            g.set(2)
            h = metrics.histogram("ff_lat_ms", "latency", reservoir=8)
            h.observe(1.0)
            h.observe(3.0)
            text = metrics.registry().prometheus_text()
        assert text == (
            "# HELP ff_depth queue depth\n"
            "# TYPE ff_depth gauge\n"
            "ff_depth 2\n"
            "# HELP ff_lat_ms latency\n"
            "# TYPE ff_lat_ms summary\n"
            'ff_lat_ms{quantile="0.5"} 2\n'
            'ff_lat_ms{quantile="0.99"} 2.98\n'
            "ff_lat_ms_count 2\n"
            "ff_lat_ms_sum 4\n"
            "# HELP ff_req_total requests served\n"
            "# TYPE ff_req_total counter\n"
            'ff_req_total{replica="0"} 3\n')

    def test_collector_samples_and_error_isolation(self):
        with metrics.override(True):
            metrics.register_collector(
                lambda: [("ff_coll", {"a": "b"}, 7.0)])

            def bad():
                raise RuntimeError("wedged subsystem")

            metrics.register_collector(bad)
            out = metrics.registry().collect()
        assert out["ff_coll"]["samples"] == [
            {"labels": {"a": "b"}, "value": 7.0}]

    def test_label_value_escaping(self):
        with metrics.override(True):
            g = metrics.gauge("ff_esc", labelnames=("p",))
            g.set(1, p='a"b\nc')
            text = metrics.registry().prometheus_text()
        assert r'p="a\"b\nc"' in text


# =====================================================================
# structured tracing
# =====================================================================
class TestTrace:
    def test_span_nesting_same_thread(self):
        with trace.override(True):
            with trace.span("outer", step=1):
                time.sleep(0.002)
                with trace.span("inner"):
                    time.sleep(0.002)
            evs = trace.events()
        # X events close inner-first
        assert [e["name"] for e in evs] == ["inner", "outer"]
        inner, outer = evs
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] \
            + 1.0  # 1 us slack for float rounding
        assert inner["tid"] == outer["tid"]
        assert outer["args"]["step"] == 1

    def test_thread_tags(self):
        with trace.override(True):
            def work():
                with trace.span("worker-span"):
                    pass

            t = threading.Thread(target=work, daemon=True,
                                 name="ff-obs-test-worker")
            t.start()
            t.join()
            ct = trace.chrome_trace()
        names = {m["args"]["name"] for m in ct["traceEvents"]
                 if m.get("ph") == "M"}
        assert "ff-obs-test-worker" in names
        ev = next(e for e in ct["traceEvents"]
                  if e.get("name") == "worker-span")
        meta = next(m for m in ct["traceEvents"]
                    if m.get("ph") == "M"
                    and m["args"]["name"] == "ff-obs-test-worker")
        assert ev["tid"] == meta["tid"]

    def test_ring_overwrites_oldest(self):
        with trace.override(True, capacity=8):
            for i in range(20):
                trace.instant(f"ev-{i}")
            evs = trace.events()
            assert len(evs) == 8
            assert evs[0]["name"] == "ev-12"   # oldest overwritten
            assert trace.dropped() == 12

    def test_error_span_lands_with_error_tag(self):
        with trace.override(True):
            with pytest.raises(RuntimeError):
                with trace.span("failing"):
                    raise RuntimeError("boom")
            ev = trace.events()[-1]
        assert ev["name"] == "failing"
        assert ev["args"]["error"] == "RuntimeError"

    def test_chrome_trace_schema_and_export(self, tmp_path):
        with trace.override(True):
            with trace.span("a", cat="test"):
                pass
            trace.instant("b")
            path = trace.export(str(tmp_path / "t.json"))
        with open(path) as f:
            doc = json.load(f)
        assert "traceEvents" in doc and doc["displayTimeUnit"] == "ms"
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        insts = [e for e in doc["traceEvents"] if e.get("ph") == "i"]
        assert spans and insts
        for e in spans:
            for key in ("name", "cat", "ts", "dur", "pid", "tid"):
                assert key in e, e
        assert insts[0]["s"] == "t"
        assert doc["otherData"]["dropped_events"] == 0

    def test_export_to_dir_unconfigured_is_none(self):
        with trace.override(True, trace_dir=""):
            assert trace.export_to_dir() is None

    def test_complete_records_explicit_start(self):
        with trace.override(True):
            t0 = time.perf_counter()
            time.sleep(0.002)
            trace.complete("formed", t0, rows=3)
            ev = trace.events()[-1]
        assert ev["name"] == "formed"
        assert ev["dur"] >= 1500   # us
        assert ev["args"]["rows"] == 3


# =====================================================================
# drift monitor
# =====================================================================
class TestDriftMonitor:
    def test_quiet_at_calibration(self):
        mon = DriftMonitor(calibrate_steps=4, sustain=2, threshold=1.5,
                           name="t")
        for _ in range(12):
            mon.observe_step(0.001)
        rep = mon.report()
        assert rep["baseline_source"] == "calibration"
        assert rep["fired"] == 0 and not rep["in_breach"]
        assert rep["last_ratio"] == pytest.approx(1.0, rel=0.5)

    def test_fires_on_injected_serve_delay(self, monkeypatch):
        """The acceptance drill: a run calibrated at ~1 ms/step slows
        to ~30 ms when FF_FAULT_SERVE_DELAY kicks in — the monitor
        fires once per breach episode, loudly."""
        monkeypatch.setenv("FF_FAULT_SERVE_DELAY", "0.03")
        plan = faults.plan_from_env()
        with metrics.override(True), trace.override(True):
            mon = DriftMonitor(predicted_step_s=0.001, sustain=3,
                               threshold=1.5, name="t")
            for _ in range(4):
                mon.observe_step(0.001)      # healthy steps: quiet
            assert mon.fired == 0
            with faults.active_plan(plan):
                for _ in range(6):
                    t0 = time.perf_counter()
                    faults.maybe_serve_delay()   # the injected slowdown
                    mon.observe_step(time.perf_counter() - t0)
            assert mon.fired == 1            # once per episode, not 6x
            assert mon.report()["in_breach"]
            assert mon.last_ratio > 10
            c = metrics.registry().counter(
                "ff_drift_warnings_total",
                labelnames=("kind", "loop"))
            assert c.value(kind="step-time", loop="t") == 1
            assert any(e["name"] == "drift/step-time"
                       for e in trace.events())

    def test_recovers_and_refires_next_episode(self):
        mon = DriftMonitor(predicted_step_s=0.001, sustain=2,
                           threshold=1.5, name="t")
        for _ in range(3):
            mon.observe_step(0.01)
        assert mon.fired == 1
        for _ in range(3):
            mon.observe_step(0.001)          # back under: episode ends
        assert not mon.report()["in_breach"]
        for _ in range(3):
            mon.observe_step(0.01)
        assert mon.fired == 2

    def test_simulator_prediction_preferred(self):
        model = _build(seed=3)
        mon = DriftMonitor.from_model(model, name="t")
        # a compiled model carries strategies -> the simulator prices it
        assert mon.baseline_source == "simulator"
        assert mon.predicted_step_s and mon.predicted_step_s > 0


NDEV, ROWS, TABLES, DIM = 4, 8192, 2, 32


@pytest.mark.slow
class TestDriftCollectiveBytes:
    def test_replicated_plan_reproduced_at_runtime(self):
        """THE FLX513 runtime twin: a replicated-table plan's lowered
        train step moves a full-table gradient all-reduce the cost
        model never priced — measured >> predicted, found at runtime by
        the attached monitor, not by a bench."""
        from dlrm_flexflow_tpu.parallel.pconfig import ParallelConfig
        dcfg = DLRMConfig(embedding_size=[ROWS] * TABLES,
                          sparse_feature_size=DIM,
                          mlp_bot=[DIM, 64, DIM],
                          mlp_top=[DIM * (TABLES + 1), 64, 1])
        model = ff.FFModel(ff.FFConfig(batch_size=64, seed=0))
        build_dlrm(model, dcfg)
        plan = {op.name: ParallelConfig.data_parallel(
                    op.outputs[0].num_dims, NDEV)
                for op in model.ops
                if op.outputs and op.outputs[0].num_dims}
        model.compile(ff.SGDOptimizer(lr=0.05), "mean_squared_error",
                      ["mse"], mesh=make_mesh(devices=jax.devices()[:NDEV]),
                      strategies=plan)
        model.init_layers()
        with metrics.override(True), trace.override(True):
            mon = DriftMonitor.from_model(model, name="t")
            report = mon.audit_collectives()
        assert report, "audit must produce a report on a compiled model"
        ratios = report["ratios"]
        ar = ratios["all-reduce"]
        assert ar == "inf" or float(ar) > 5.0, report
        assert mon.fired >= 1            # the loud warning landed
        assert report["findings"], report
        assert any(e["name"] == "drift/collective-bytes"
                   for e in trace.events())


# =====================================================================
# engine integration: instruments, collectors, /metrics endpoint
# =====================================================================
class _StubServe:
    """stats()/healthz() stand-in so the HTTP handler can be exercised
    without compiling a model."""

    def stats(self):
        return {"ok": True}

    def healthz(self):
        return {"ok": True}


def _http_get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.headers.get("Content-Type"), r.read().decode()


class TestMetricsEndpoint:
    def _serve(self, handler):
        from http.server import ThreadingHTTPServer
        httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
        t = threading.Thread(target=httpd.serve_forever, daemon=True,
                             name="ff-obs-test-httpd")
        t.start()
        return httpd, t

    def test_metrics_roundtrip_on(self):
        sys.path.insert(0, os.path.join(_REPO, "examples", "native"))
        from serve_dlrm import make_handler
        with metrics.override(True):
            metrics.counter("ff_roundtrip_total", "x").inc(3)
            httpd, t = self._serve(make_handler(_StubServe(), []))
            try:
                status, ctype, body = _http_get(
                    httpd.server_address[1], "/metrics")
            finally:
                httpd.shutdown()
                httpd.server_close()
        assert status == 200
        assert ctype.startswith("text/plain")
        assert "ff_roundtrip_total 3" in body
        assert "# TYPE ff_roundtrip_total counter" in body

    def test_metrics_endpoint_off_explains_itself(self):
        sys.path.insert(0, os.path.join(_REPO, "examples", "native"))
        from serve_dlrm import make_handler
        with metrics.override(False):
            httpd, t = self._serve(make_handler(_StubServe(), []))
            try:
                status, _, body = _http_get(
                    httpd.server_address[1], "/metrics")
            finally:
                httpd.shutdown()
                httpd.server_close()
        assert status == 200
        assert "--obs on" in body        # troubleshooting: not silence


@pytest.mark.slow
class TestEngineIntegration:
    def test_engine_scrapes_and_stats_agree(self):
        with metrics.override(True), trace.override(True):
            model = _build(seed=4)
            eng = InferenceEngine(model, ServeConfig(max_batch=8,
                                                     warmup=False))
            with eng:
                for i in range(3):
                    eng.predict(_rows(2, seed=i))
                st = eng.stats()
                text = metrics.registry().prometheus_text()
            assert st["responses"] == 3
            # collector samples == stats values (read-through)
            assert "ff_serve_requests_total" in text
            assert 'ff_serve_responses_total{replica=""} 3' in text
            # the engine latency window doubles as the scrape histogram
            assert "ff_serve_request_latency_ms_count" in text
            # serving pipeline spans landed
            names = {e["name"] for e in trace.events()}
            assert {"serve/enqueue", "serve/batch-form",
                    "serve/dispatch"} <= names
        # after close the collector is unregistered: scrape shrinks
        leftover = metrics.registry().collect()
        assert "ff_serve_requests_total" not in leftover


# =====================================================================
# the end-to-end trace: fit_stream + publish + watcher + swap (+ fit
# superstep + prefetch) — >= 4 subsystems in ONE exported trace
# =====================================================================
@pytest.mark.slow
class TestEndToEndTrace:
    def test_four_subsystem_trace(self, tmp_path):
        from dlrm_flexflow_tpu.data.stream import ArrayStream
        from dlrm_flexflow_tpu.serve import SnapshotWatcher
        from dlrm_flexflow_tpu.utils.delta import DeltaPublisher
        with metrics.override(True), \
                trace.override(True, trace_dir=str(tmp_path / "traces")):
            # --- training side: superstep dispatch + prefetch ring ---
            model = _build(seed=5, superstep=2, stage_dataset="never",
                           obs="on")
            x, y = synthetic_batch(DCFG, BS * 8, seed=1)
            fit_out = model.fit(x, y, epochs=1, verbose=False)
            assert "drift" in fit_out      # --obs on reports drift
            # --- freshness side: publish -> watcher apply -> swap ----
            trainer = _build(seed=6, obs="on")
            pub = DeltaPublisher(trainer, str(tmp_path / "ckpt"))
            xs, ys = synthetic_batch(DCFG, BS * 6, seed=2)
            trainer.fit_stream(ArrayStream(xs, ys, BS), steps=6,
                               publisher=pub, publish_every=2,
                               verbose=False)
            server = _build(seed=6)
            eng = InferenceEngine(model=server,
                                  config=ServeConfig(warmup=False))
            watcher = SnapshotWatcher(eng, str(tmp_path / "ckpt"))
            assert watcher.poll_once()     # install on THIS thread
            path = trace.export_to_dir()
            evs = trace.events()
        assert path and os.path.isfile(path)
        names = [e["name"] for e in evs if e.get("ph") == "X"]
        subsystems = {
            "prefetch": any(n == "prefetch/produce" for n in names),
            "superstep": any(n == "train/superstep" for n in names),
            "publish": any(n in ("publish/delta", "publish/full")
                           for n in names),
            "watcher": any(n == "publish/watcher-apply" for n in names),
            "swap": any(n == "serve/swap" for n in names),
        }
        assert all(subsystems.values()), subsystems
        # thread tags: staging spans ride the ff-prefetch-N threads
        with open(path) as f:
            doc = json.load(f)
        tid_names = {m["tid"]: m["args"]["name"]
                     for m in doc["traceEvents"] if m.get("ph") == "M"}
        pre = next(e for e in evs if e["name"] == "prefetch/produce")
        assert tid_names[pre["tid"]].startswith("ff-prefetch-")
        # nesting: the engine swap applied INSIDE the watcher's apply
        # span, on the same thread
        wa = [e for e in evs if e["name"] == "publish/watcher-apply"]
        sw = [e for e in evs if e["name"] == "serve/swap"]
        assert wa and sw
        nested = [
            (w, s) for w in wa for s in sw
            if s["tid"] == w["tid"] and s["ts"] >= w["ts"]
            and s["ts"] + s["dur"] <= w["ts"] + w["dur"] + 1.0]
        assert nested, (wa, sw)


# =====================================================================
# stats() back-compat: keys pinned for engine / router / fleet / shards
# =====================================================================
ENGINE_KEYS = {"requests", "responses", "overloaded", "timeouts",
               "queue_depth", "batches", "batch_fill", "p50_ms",
               "p99_ms", "version", "reloads", "delta_reloads",
               "reload_rejects", "last_reload_reject", "buckets",
               "warmup_s", "flushes", "continuous", "eval_exec_cache"}
ROUTER_KEYS = {"requests", "responses", "failed", "retries", "hedges",
               "hedge_wins", "p50_ms", "p99_ms", "canary", "cohorts",
               "shadow", "fleet"}
FLEET_KEYS = {"replicas", "size", "healthy", "states", "p50_ms",
              "p99_ms", "totals", "requests_dispatched", "grows",
              "shrinks"}
SHARD_KEYS = {"nshards", "version", "versions", "states",
              "degraded_now", "fetches", "degraded_fetches",
              "defaults_used", "retries", "hedges", "timeouts",
              "failed_fetches", "replacements", "replace_rejects",
              "last_replace_reject", "lagging_slots", "shards",
              "fetch_p50_ms", "fetch_p99_ms"}


@pytest.mark.slow
class TestStatsBackCompat:
    def test_engine_router_fleet_keys(self):
        # obs OFF (the default): the contracts must hold with the plain
        # reservoirs, no registry anywhere
        from dlrm_flexflow_tpu.serve import Fleet, FleetRouter, \
            RouterConfig
        model = _build(seed=7, ndev=1)
        eng = InferenceEngine(model, ServeConfig(max_batch=8,
                                                 warmup=False))
        router = FleetRouter(Fleet([eng]), RouterConfig())
        with router:
            router.predict(_rows(2))
            est = eng.stats()
            rst = router.stats()
        assert ENGINE_KEYS <= set(est), ENGINE_KEYS - set(est)
        assert ROUTER_KEYS <= set(rst), ROUTER_KEYS - set(rst)
        assert FLEET_KEYS <= set(rst["fleet"]), \
            FLEET_KEYS - set(rst["fleet"])
        # empty-window honesty preserved through the Reservoir move
        assert rst["cohorts"]["canary"]["p99_ms"] is None

    def test_empty_engine_p99_is_none(self):
        model = _build(seed=8)
        eng = InferenceEngine(model, ServeConfig(warmup=False))
        st = eng.stats()
        assert st["p50_ms"] is None and st["p99_ms"] is None

    def test_shard_tier_keys(self):
        from dlrm_flexflow_tpu.serve.shardtier import EmbeddingShardSet
        model = _build(seed=9, host_resident_tables=True)
        sset = EmbeddingShardSet.build(model, 2)
        try:
            st = sset.stats()
        finally:
            sset.close()
        assert SHARD_KEYS <= set(st), SHARD_KEYS - set(st)
        assert st["fetch_p99_ms"] is None    # empty window -> None


# =====================================================================
# fleet window merge still works over Reservoirs
# =====================================================================
class TestReservoirFleetCompat:
    def test_extend_and_iterate_like_a_deque(self):
        r = metrics.Reservoir(maxlen=8)
        r.extend([3.0, 1.0, 2.0])
        assert sorted(r) == [1.0, 2.0, 3.0]
        assert len(r) == 3
        merged = []
        merged.extend(r.samples())
        assert sorted(merged) == [1.0, 2.0, 3.0]
