#!/usr/bin/env python
"""DLRM online serving app: dynamic-batched JSON inference over HTTP.

The read-path counterpart of examples/native/dlrm.py — the trainer
publishes rolling snapshots (fit(checkpoint_dir=...)); this app builds
the same graph, restores the newest snapshot params-only, and serves it
with the dynamic-batching engine (power-of-two bucket padding, AOT
warmup, bounded queue backpressure, per-request deadlines) while a
snapshot watcher hot-reloads newer checkpoints with zero downtime.

``--serve-replicas N`` turns the single engine into a FLEET: N replicas
(each compiled on its own slice of the local devices, data-parallel
params) behind a ``FleetRouter`` — queue-depth load balancing, a
circuit breaker that ejects and re-admits crashed replicas, bounded
retry with backoff, optional tail-latency hedging
(``--serve-hedge-ms``), and canary/shadow rollout knobs
(``--serve-canary-fraction``). Each replica follows the trainer's
snapshots independently (cross-mesh reshard is automatic in fleet
mode: per-device replicas consume the multi-device trainer's
checkpoints).

``--serve-shards N`` (host-table models) splits serving into a
SHARDED TIER: the engine/replicas become stateless rankers and the
embedding tables live once, row-sharded over N lookup shards
(``serve/shardtier.py``) — a model whose tables exceed one replica's
memory serves anyway. Responses then carry a per-shard version vector
and, while a shard is out, are served DEGRADED (cache hits + per-table
default rows, ``"degraded": true`` in the response and in /healthz —
still HTTP 200: degraded is not down, and a load balancer that treated
it as down would turn one dark shard into a full outage). Knobs:
``--serve-lookup-deadline-ms`` (per-fetch budget) and
``--serve-degrade {cache,fail}``.

``--serve-transport tcp --serve-shard-procs N`` moves the lookup tier
across a REAL process boundary: the app seeds the warm shard cache,
spawns N ``serve/shard_server.py`` OS processes (one slot each, wire
protocol over loopback TCP — ``serve/wire.py``), and the rankers
resolve ids through ``RemoteShard`` clients with per-request deadlines,
bounded retry/backoff, and CRC-checked frames. ``kill -9`` a shard
process and responses degrade (never fail) until the health loop
replaces it from the warm cache. Fault injection for drills:
``FF_FAULT_NET_DROP/DUP/REORDER/SLOW`` (see utils/faults.py). The
default ``--serve-transport inproc`` keeps today's in-process method
calls bit-for-bit.

``--retrieve on`` puts the RETRIEVAL CASCADE in front of the ranker
(``retrieve/``): a two-tower user encoder feeds a sharded MIPS top-k
index (int8 codes on the embedding-shard substrate — riding the
``--serve-shards`` tier when one exists, or ``--retrieve-shards M``
standalone index shards otherwise), and ``/predict`` answers USER
requests — retrieve ``--retrieve-k`` candidates under
``--retrieve-deadline-ms``, rank them through the engine/fleet with
the remaining ``--serve-deadline-ms`` budget, and return the re-ranked
candidate ids. ``POST /retrieve`` exposes the index stage alone. A
dead index shard DROPS its candidates (``"degraded": true`` — never
fabricated ids, never a failed request). Cascade mode needs the
in-process transport (``--serve-transport tcp`` / ``--serve-shard-procs``
are rejected at startup).

No framework webserver: a stdlib ``http.server`` ThreadingHTTPServer is
all the engine needs — every handler thread just submits into the
engine's queue and blocks on its future, the batcher coalesces across
handler threads.

  # terminal 1: train, publishing snapshots
  python examples/native/dlrm.py --checkpoint-dir /tmp/dlrm-ckpt --save-every 50

  # terminal 2: serve them, hot-reloading as they land (2 replicas)
  python examples/native/serve_dlrm.py --checkpoint-dir /tmp/dlrm-ckpt \\
      --serve-replicas 2 --serve-max-batch 64 --port 8000

  curl -s localhost:8000/healthz
  curl -s localhost:8000/stats
  curl -s -X POST localhost:8000/predict -d \\
      '{"dense": [[0.1, 0.2, 0.3, 0.4]], "sparse": [[[1],[2],[3],[4]]]}'

Endpoints:
  POST /predict  {"dense": [...], "sparse": [...]}  ->
                 {"scores": [...], "version": N, "latency_ms": ...}
                 429 on Overloaded, 504 on DeadlineExceeded,
                 503 when no replica can take the request
                 (--retrieve on: the same request describes a USER;
                 the response adds "candidates" — re-ranked item ids —
                 plus "retrieve_versions", "stage_ms", and the OR'd
                 "degraded" flag)
  POST /retrieve {"dense": [...], "sparse": [...][, "k": N]}  ->
                 {"ids": [[...]], "scores": [[...]], "versions": ...,
                 "degraded": ..., "latency_ms": ...} — the retrieve
                 stage alone (--retrieve on only; 404 otherwise)
  GET  /stats    engine stats() — or fleet-wide router stats() with
                 per-replica circuit-breaker state in fleet mode
  GET  /healthz  200 {"ok": true, ...} while the engine (fleet: at
                 least one healthy replica) is accepting requests;
                 503 {"ok": false, ...} when the queue is saturated,
                 the server is draining, or the batcher died — load
                 balancers must stop sending traffic HERE, not learn
                 it from request errors
  GET  /metrics  Prometheus text exposition of the obs registry
                 (``--obs on``; with obs off the body is a comment
                 saying so) — request/latency/reload series from the
                 engine, router, watcher, and shard tier
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm
from dlrm_flexflow_tpu.serve import (DeadlineExceeded, FleetUnavailable,
                                     Overloaded)
from dlrm_flexflow_tpu.utils.logging import get_logger

log_app = get_logger("serve_dlrm")


def build_server_model(cfg, dcfg, mesh=None):
    """Same graph as the trainer (fingerprints must match for hot
    reload); compiled at the largest serve bucket so every bucket pads
    under the compile batch. ``mesh`` pins a fleet replica to its own
    device slice."""
    model = ff.FFModel(cfg)
    build_dlrm(model, dcfg)
    model.compile(ff.SGDOptimizer(lr=cfg.learning_rate),
                  "mean_squared_error", ["mse"], mesh=mesh)
    model.init_layers()
    return model


def make_handler(serve, input_names, cascade=None):
    """``serve`` is an InferenceEngine or a FleetRouter — both expose
    predict()/stats()/healthz() with the same contract. ``cascade``
    (a retrieve.CascadeEngine) switches /predict into cascade mode and
    opens POST /retrieve."""
    from http.server import BaseHTTPRequestHandler

    class Handler(BaseHTTPRequestHandler):
        def _reply(self, code, payload):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _reply_text(self, code, text,
                        ctype="text/plain; version=0.0.4"):
            body = text.encode()
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, fmt, *args):   # route through our logger
            log_app.debug(fmt, *args)

        def do_GET(self):
            if self.path == "/healthz":
                hz = serve.healthz()
                # 503 tells the balancer to stop routing here while the
                # queue is saturated or the server is draining; a 200
                # with ok:false would keep the traffic coming
                self._reply(200 if hz["ok"] else 503, hz)
            elif self.path == "/stats":
                st = serve.stats()
                if cascade is not None:
                    st = dict(st)
                    st["cascade"] = cascade.stats()
                self._reply(200, st)
            elif self.path == "/metrics":
                # Prometheus text exposition of the obs registry; with
                # --obs off the registry holds no instruments, so the
                # body is a self-explaining comment instead of silence
                from dlrm_flexflow_tpu.obs import metrics as obsm
                if obsm.enabled():
                    self._reply_text(200,
                                     obsm.registry().prometheus_text())
                else:
                    self._reply_text(
                        200, "# observability is off — restart with "
                             "--obs on to populate this endpoint\n")
            else:
                self._reply(404, {"error": f"no route {self.path}"})

        def do_POST(self):
            if self.path not in ("/predict", "/retrieve"):
                self._reply(404, {"error": f"no route {self.path}"})
                return
            if self.path == "/retrieve" and cascade is None:
                self._reply(404, {"error": "retrieval is off — restart "
                                           "with --retrieve on"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n) or b"{}")
                feats = {}
                for name in input_names:
                    if name not in req:
                        raise ValueError(f"missing input {name!r}")
                    arr = np.asarray(req[name])
                    feats[name] = (arr.astype(np.int32)
                                   if name == "sparse"
                                   else arr.astype(np.float32))
            except (ValueError, json.JSONDecodeError) as e:
                self._reply(400, {"error": str(e)})
                return
            try:
                if self.path == "/retrieve":
                    k = int(req.get("k", cascade.config.k))
                    r = cascade.index.topk(
                        cascade.user_encoder(feats), k,
                        deadline_s=cascade.config.retrieve_deadline_ms
                        / 1e3)
                    self._reply(200, {
                        "ids": r.ids.tolist(),
                        "scores": r.scores.tolist(),
                        "versions": {str(s): int(v)
                                     for s, v in r.versions.items()},
                        "degraded": bool(r.degraded),
                        "dropped_slots": list(r.dropped_slots),
                        "latency_ms": round(r.latency_ms, 3)})
                    return
                if cascade is not None:
                    cp = cascade.predict(feats)
                    body = {
                        "candidates": cp.ids.tolist(),
                        "scores": cp.scores.tolist(),
                        "version": cp.rank_version,
                        "retrieve_versions": {
                            str(s): int(v)
                            for s, v in cp.retrieve_versions.items()},
                        "degraded": bool(cp.degraded),
                        "latency_ms": round(cp.latency_ms, 3),
                        "stage_ms": {s: round(v, 3)
                                     for s, v in cp.stage_ms.items()}}
                    if cp.rank_versions is not None:
                        body["versions"] = {
                            str(s): int(v)
                            for s, v in cp.rank_versions.items()}
                    self._reply(200, body)
                    return
                pred = serve.predict(feats)
                body = {
                    "scores": np.asarray(pred.scores).reshape(-1).tolist(),
                    "version": pred.version,
                    "latency_ms": round(pred.latency_ms, 3)}
                versions = getattr(pred, "versions", None)
                if versions is not None:
                    # sharded tier: the per-shard version vector this
                    # answer read, plus the degraded flag (default-row
                    # answers are honest about being approximate)
                    body["versions"] = {str(k): int(v)
                                        for k, v in versions.items()}
                    body["degraded"] = bool(getattr(pred, "degraded",
                                                    False))
                self._reply(200, body)
            except Overloaded as e:
                self._reply(429, {"error": str(e)})
            except FleetUnavailable as e:
                self._reply(503, {"error": str(e)})
            except (DeadlineExceeded, TimeoutError) as e:
                self._reply(504, {"error": str(e)})
            except ValueError as e:
                self._reply(400, {"error": str(e)})
            except Exception as e:   # noqa: BLE001 — e.g. a shape that
                # passed coercion but failed inside the dispatch; an
                # uncaught handler exception would DROP the connection
                # (no status at all) instead of answering 500
                log_app.exception("predict failed")
                self._reply(500, {"error": f"{type(e).__name__}: {e}"})

    return Handler


def _replica_mesh(i, n):
    """Replica i's device slice: the local devices split n ways (each
    replica MUST own its own mesh — replicas sharing devices would
    serialize, and on CPU can deadlock concurrent collectives)."""
    import jax

    from dlrm_flexflow_tpu.parallel.mesh import make_mesh
    devs = jax.devices()
    per = max(1, len(devs) // n)
    lo = (i * per) % len(devs)
    return make_mesh(devices=devs[lo:lo + per])


def _shard_cache_dir(cfg, ckpt_dir):
    from dlrm_flexflow_tpu.utils.warmcache import cache_dir_for
    return cache_dir_for(ckpt_dir,
                         getattr(cfg, "compile_cache_dir", ""))


_SHARD_PROCS = []  # child shard-server processes, reaped in main()


def _wants_shard_tier(cfg):
    return (int(getattr(cfg, "serve_shards", 0)) > 0
            or int(getattr(cfg, "serve_shard_procs", 0)) > 0)


def _spawn_shard_procs(cfg, model, ckpt_dir):
    """The tcp path: seed the warm shard cache from the ranker's model,
    spawn one ``serve/shard_server.py`` OS process per slot, and connect
    ``RemoteShard`` clients over the wire protocol. The child processes
    land in ``_SHARD_PROCS`` for shutdown."""
    from dlrm_flexflow_tpu.serve import shard_server
    n_shards = int(getattr(cfg, "serve_shard_procs", 0))
    tier_cfg = ff.ShardTierConfig.from_config(cfg)
    cache_dir = _shard_cache_dir(cfg, ckpt_dir)
    if not cache_dir:
        raise SystemExit(
            "--serve-shard-procs needs a shard cache directory to boot "
            "the child processes from — set --checkpoint-dir or "
            "--compile-cache-dir")
    ff.EmbeddingShardSet.seed_shard_cache(model, n_shards, cache_dir,
                                          config=tier_cfg)
    addresses = []
    for slot in range(n_shards):
        proc = shard_server.spawn(cache_dir, n_shards, slot)
        _SHARD_PROCS.append(proc)
        line = proc.stdout.readline().strip()
        if not line.startswith("SHARD_SERVER_OK"):
            raise SystemExit(
                f"shard server slot {slot} failed to boot "
                f"(got {line!r}, exit={proc.poll()})")
        port = int(dict(kv.split("=", 1)
                        for kv in line.split()[1:])["port"])
        addresses.append(("127.0.0.1", port))
        log_app.info("shard process slot %d up: pid=%d port=%d",
                     slot, proc.pid, port)
    return ff.EmbeddingShardSet.connect(addresses, config=tier_cfg,
                                        cache_dir=cache_dir)


def _stop_shard_procs():
    for proc in _SHARD_PROCS:
        if proc.poll() is None:
            proc.terminate()
    for proc in _SHARD_PROCS:
        try:
            proc.wait(timeout=5)
        except Exception:
            proc.kill()
    _SHARD_PROCS.clear()


def _build_shard_set(cfg, model, ckpt_dir):
    """Row-shard the model's host tables into the lookup tier and
    release the ranker's own copies (the point of the split)."""
    n_procs = int(getattr(cfg, "serve_shard_procs", 0))
    transport = str(getattr(cfg, "serve_transport", "inproc"))
    if n_procs > 0 and transport != "tcp":
        raise SystemExit(
            "--serve-shard-procs requires --serve-transport tcp "
            "(separate processes cannot share in-process method calls)")
    if n_procs > 0:
        shard_set = _spawn_shard_procs(cfg, model, ckpt_dir)
        n_shards = n_procs
    else:
        n_shards = int(getattr(cfg, "serve_shards", 0))
        shard_set = ff.EmbeddingShardSet.build(
            model, n_shards, config=ff.ShardTierConfig.from_config(cfg),
            cache_dir=_shard_cache_dir(cfg, ckpt_dir))
    freed = ff.EmbeddingShardSet.release_ranker_tables(model)
    log_app.info(
        "sharded serving tier: %d lookup shard(s) [%s], ranker released "
        "%.1f MB of tables", n_shards,
        "tcp, separate processes" if n_procs > 0 else "inproc",
        freed / 1e6)
    return shard_set


def _validate_retrieve(cfg):
    """Reject knob combinations the cascade cannot honor — at startup,
    with the knob names in the message, not as a mid-request surprise."""
    on = str(getattr(cfg, "retrieve", "off")) == "on"
    rshards = int(getattr(cfg, "retrieve_shards", 0))
    if not on:
        if rshards > 0:
            raise SystemExit(
                "--retrieve-shards does nothing without --retrieve on — "
                "refusing to silently ignore it")
        return False
    if str(getattr(cfg, "serve_transport", "inproc")) != "inproc":
        raise SystemExit(
            "--retrieve on requires --serve-transport inproc: the "
            "cascade scores candidates through in-process shard calls "
            "(the wire path for retrieval is not plumbed yet)")
    if int(getattr(cfg, "serve_shard_procs", 0)) > 0:
        raise SystemExit(
            "--retrieve on is incompatible with --serve-shard-procs: "
            "the index attaches to in-process shards")
    nshards = int(getattr(cfg, "serve_shards", 0))
    if nshards > 0 and rshards not in (0, nshards):
        raise SystemExit(
            f"--retrieve-shards {rshards} conflicts with "
            f"--serve-shards {nshards}: with a sharded ranker tier the "
            f"index rides THOSE shards (pass 0, or match the count)")
    return True


def _build_cascade(cfg, dcfg, serve, shard_set):
    """Stand the retrieval stage up in front of the ranker: two-tower
    user/item heads sized to the DLRM's own inputs (so /predict's
    feature dict feeds both stages), the item catalog encoded and
    attached as the MIPS index — to the ranker's shard set when one
    exists, else to ``--retrieve-shards`` standalone index shards.
    Returns ``(CascadeEngine, owned_set_or_None)``."""
    from dlrm_flexflow_tpu.retrieve import (CascadeConfig, CascadeEngine,
                                            ShardedMIPSIndex,
                                            TwoTowerConfig,
                                            build_two_tower,
                                            dlrm_candidate_features,
                                            item_embeddings,
                                            transfer_tower_params)
    tcfg = TwoTowerConfig(
        n_items=int(dcfg.embedding_size[0]),
        dim=32,
        user_dense_dim=int(dcfg.mlp_bot[0]),
        user_embedding_size=list(dcfg.embedding_size),
        user_sparse_dim=8,
        user_bag_size=int(dcfg.embedding_bag_size))

    def build_head(head):
        m = ff.FFModel(cfg)
        build_two_tower(m, tcfg, head=head)
        m.compile(ff.SGDOptimizer(lr=cfg.learning_rate),
                  "mean_squared_error", ["mse"])
        m.init_layers()
        return m

    user_model = build_head("user")
    item_model = build_head("item")
    # keep the untrained heads CONSISTENT: both serve the same init the
    # way both serve the same snapshot after a real transfer (a trained
    # two-tower checkpoint would restore here, then transfer the same
    # way)
    transfer_tower_params(user_model, item_model)

    def encode(feats):
        dense = np.asarray(feats["dense"], np.float32)
        sparse = np.asarray(feats["sparse"], np.int32)
        B = user_model.config.batch_size
        n = dense.shape[0]
        out = np.empty((n, tcfg.dim), np.float32)
        for lo in range(0, n, B):
            hi = min(lo + B, n)
            pad = B - (hi - lo)
            d, s = dense[lo:hi], sparse[lo:hi]
            if pad:
                d = np.concatenate(
                    [d, np.zeros((pad,) + d.shape[1:], np.float32)])
                s = np.concatenate(
                    [s, np.zeros((pad,) + s.shape[1:], np.int32)])
            res = np.asarray(user_model.forward_batch(
                {"user_dense": d, "user_sparse": s}))
            out[lo:hi] = res[:hi - lo]
        return out

    item_emb = item_embeddings(item_model, tcfg)
    owned = None
    if shard_set is not None:
        index = ShardedMIPSIndex.build(shard_set, item_emb)
        where = f"riding the {shard_set.nshards}-shard ranker tier"
    else:
        m = max(1, int(getattr(cfg, "retrieve_shards", 0)))
        owned = ShardedMIPSIndex.standalone_set(m)
        index = ShardedMIPSIndex.build(owned, item_emb)
        where = f"{m} standalone index shard(s)"
    cascade = CascadeEngine(
        index, encode, serve,
        dlrm_candidate_features(len(dcfg.embedding_size),
                                list(dcfg.embedding_size)),
        CascadeConfig.from_config(cfg))
    log_app.info(
        "retrieval cascade on: %d-item index (%s), k=%d, retrieve "
        "deadline %.0f ms", index.n_items, where, cascade.config.k,
        cascade.config.retrieve_deadline_ms)
    return cascade, owned


def _build_fleet(cfg, dcfg, n, ckpt_dir):
    """N replicas on disjoint device slices behind a FleetRouter."""
    scfg = ff.ServeConfig.from_config(cfg)
    shard_holder = {}

    def factory(i):
        model = build_server_model(cfg, dcfg, mesh=_replica_mesh(i, n))
        if _wants_shard_tier(cfg):
            # the FIRST model built seeds the (single, shared) shard
            # set; every ranker — this one included — then releases its
            # own tables and resolves ids through the set
            if "set" not in shard_holder:
                shard_holder["set"] = _build_shard_set(cfg, model,
                                                       ckpt_dir)
            else:
                ff.EmbeddingShardSet.release_ranker_tables(model)
        return model

    fleet = ff.Fleet.build(factory, n, scfg, checkpoint_dir=ckpt_dir,
                           shard_set=None)
    if shard_holder:
        fleet.shard_set = shard_holder["set"]
        for rep in fleet:
            rep.engine.attach_shard_set(fleet.shard_set)
    if ckpt_dir:
        for rep in fleet:
            # initial restore through the watcher's READ-ONLY manifest
            # scan, resharding the trainer's mesh onto the replica's
            if ff.SnapshotWatcher(rep.engine, ckpt_dir,
                                  elastic=True).poll_once():
                log_app.info("replica %d serving snapshot version %d",
                             rep.rid, rep.engine.version)
            else:
                log_app.warning(
                    "replica %d: no restorable snapshot in %s — serving "
                    "fresh init until the trainer publishes one",
                    rep.rid, ckpt_dir)
    return ff.FleetRouter(fleet, ff.RouterConfig.from_config(cfg))


def main(argv=None):
    ff.use_compile_cache()
    cfg = ff.FFConfig.parse_args(argv)
    # --obs on must land BEFORE any engine/fleet is built: instruments
    # resolve at creation time (no-op singletons once off stays off)
    from dlrm_flexflow_tpu import obs
    if obs.configure(cfg):
        log_app.info("observability on: GET /metrics serves the "
                     "registry%s",
                     f", traces export to {cfg.obs_trace_dir}"
                     if cfg.obs_trace_dir else "")
    dcfg = DLRMConfig.parse_args(cfg.unparsed)
    port = 8000
    rest = list(cfg.unparsed)
    if "--port" in rest:
        port = int(rest[rest.index("--port") + 1])

    ckpt_dir = cfg.checkpoint_dir or None
    n = int(getattr(cfg, "serve_replicas", 1))
    retrieve_on = _validate_retrieve(cfg)   # SystemExit on bad combos,
    #                                         BEFORE any model compiles
    shard_set = None
    if n > 1:
        serve = _build_fleet(cfg, dcfg, n, ckpt_dir)
        model = serve.fleet.replicas[0].engine.model
        shard_set = serve.fleet.shard_set
    else:
        model = build_server_model(cfg, dcfg)
        if _wants_shard_tier(cfg):
            shard_set = _build_shard_set(cfg, model, ckpt_dir)
        serve = ff.InferenceEngine(model, checkpoint_dir=ckpt_dir,
                                   shard_set=shard_set)
        if ckpt_dir:
            # initial load through the watcher's READ-ONLY manifest
            # scan (a CheckpointManager here would sweep tmp files
            # under a live trainer) — params_only restore of the newest
            # valid snapshot
            if ff.SnapshotWatcher(serve, ckpt_dir).poll_once():
                log_app.info("serving snapshot version %d", serve.version)
            else:
                log_app.warning(
                    "no restorable snapshot in %s — serving fresh init "
                    "until the trainer publishes one", ckpt_dir)
    input_names = [t.name for t in model.input_tensors]

    cascade = cascade_set = None
    if retrieve_on:
        cascade, cascade_set = _build_cascade(cfg, dcfg, serve,
                                              shard_set)

    # SLO-driven autoscaling over the fleet (--serve-slo-ms + the
    # min/max replica bounds): grows on sustained p99/queue pressure,
    # replaces dead replicas, shrinks when idle. Fleet mode only — a
    # single engine has nothing to grow.
    scaler = None
    if n > 1 and float(getattr(cfg, "serve_slo_ms", 0.0)) > 0:
        scaler = ff.Autoscaler(serve, ff.AutoscaleConfig.from_config(cfg))
        log_app.info(
            "autoscaler on: SLO %.0f ms, %d..%d replicas",
            cfg.serve_slo_ms, cfg.serve_min_replicas,
            cfg.serve_max_replicas)
    if shard_set is not None and scaler is None:
        # no autoscaler to drive shard health ticks — the set runs its
        # own probe/replace loop so an ejected shard still heals
        shard_set.start_health()

    from http.server import ThreadingHTTPServer
    with serve:
        if scaler is not None:
            scaler.start()
        httpd = ThreadingHTTPServer(
            ("0.0.0.0", port),
            make_handler(serve, input_names, cascade=cascade))
        log_app.info(
            "serving DLRM on :%d (%s%s)", port,
            f"{n} replicas" if n > 1 else
            f"buckets {serve.stats()['buckets']}",
            f", hot-reload from {ckpt_dir}" if ckpt_dir else "")
        try:
            httpd.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            if scaler is not None:
                scaler.close()
            if shard_set is not None:
                shard_set.stop_health()
                shard_set.close()
            if cascade_set is not None:
                cascade_set.close()
            _stop_shard_procs()
            httpd.server_close()
            from dlrm_flexflow_tpu.obs import trace as obstrace
            path = obstrace.export_to_dir()
            if path:
                log_app.info("exported serving trace to %s", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
