"""Persistent compile + plan caches: make recovery and cold starts warm.

Every recovery and cold start in this framework used to re-pay work whose
inputs had not changed: elastic ``recover()``/``expand()`` re-ran the MCMC
strategy search and recompiled every step executable, a serving replica
AOT-warmed every bucket from scratch at boot, and ``shardcheck`` re-derived
plans it had already verified. ROADMAP item 4 calls this out: what should
be millisecond failover is seconds of search + XLA compile + bucket warmup.

Two caches, both living in one directory NEXT TO the checkpoint manifest
(``<checkpoint_dir>/cache/`` by convention — the snapshot and the
executables that can serve it travel together):

- :class:`PlanCache` — MCMC strategy maps keyed by (graph fingerprint,
  device count, mesh-axis signature, search budget, seed). The search is
  deterministic for that key, so a hit returns EXACTLY the plan a fresh
  search would produce — the elastic bit-identity contract survives the
  cache. Stored as one human-readable ``plans.json``.
- :class:`CompileCache` — AOT executables (train / eval / superstep /
  serving buckets) serialized via ``jax.experimental.serialize_executable``,
  keyed by (kind, code fingerprint, strategy signature, mesh signature,
  shape signature). One file per entry, written atomically.

A third cache, :class:`ShardCache`, serves the SERVING shard tier
(serve/shardtier.py): per-shard embedding row blocks persisted on every
publish so the autoscaler's replace-dead path can boot a replacement
lookup shard warm (version + chain-CRC validated) instead of re-slicing
a full checkpoint.

Both caches fail OPEN with a named reason: a corrupt, truncated, stale
(code-fingerprint mismatch), or wrong-topology entry is rejected and the
caller falls back to a fresh search/compile — the same
reject-with-reason-then-degrade contract as PR 10's delta chains. A cache
can make a cold start slow again; it can never make it wrong.

Entry validity:

- every compile-cache entry embeds the FULL key string and a CRC-32 of the
  executable payload; a hash-collision, torn write, or bit rot is caught
  before ``deserialize_and_load`` runs;
- the code fingerprint digests the step-builder sources + jax version, so
  an upgraded checkout silently ignores (does not load) executables
  compiled by old code;
- the mesh signature includes the concrete device ids — an executable
  compiled for one replica's device is never handed to another's
  (shardcheck FLX506 audits the same hazard statically for plans).

Fault injection: ``FF_FAULT_CACHE_CORRUPT=n`` truncates the next n cache
entry files at the moment they are read, driving the graceful-degradation
path deterministically (tests/test_elastic.py).
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time
import zlib
from typing import Any, Dict, Optional, Tuple

from .logging import get_logger

log_cache = get_logger("warmcache")


def _obs_cache_event(cache: str, event: str) -> None:
    """Warm-cache hit/miss counter for the obs registry (no-op when
    --obs off): a fleet whose cold boots stopped hitting the compile
    cache shows up as a climbing miss series, not a mystery."""
    from ..obs import metrics as obsm
    obsm.counter("ff_warmcache_events_total",
                 "plan/compile warm-cache lookups by outcome",
                 labelnames=("cache", "event")).inc(cache=cache,
                                                    event=event)


# cache-layout version: bump to orphan every existing entry when the
# on-disk format changes (old files are simply never matched)
_FORMAT = 1

PLANS_FILE = "plans.json"


# ---------------------------------------------------------------------
# fingerprints / signatures
# ---------------------------------------------------------------------
def _sha1(blob: str) -> str:
    return hashlib.sha1(blob.encode()).hexdigest()


def code_fingerprint() -> str:
    """Digest of everything an AOT executable's VALIDITY depends on that a
    shape/strategy key cannot see: the jax/jaxlib versions and the source
    bytes of the step-builder modules. A checkout upgrade makes every old
    entry a clean miss instead of a wrong load."""
    import jax

    import dlrm_flexflow_tpu
    h = hashlib.sha1()
    h.update(jax.__version__.encode())
    h.update(getattr(dlrm_flexflow_tpu, "__version__", "?").encode())
    pkg = os.path.dirname(os.path.abspath(dlrm_flexflow_tpu.__file__))
    for rel in ("core/model.py", "parallel/alltoall.py",
                "parallel/sharding.py", "ops/embedding.py"):
        try:
            with open(os.path.join(pkg, rel), "rb") as f:
                h.update(f.read())
        except OSError:
            h.update(f"missing:{rel}".encode())
    return h.hexdigest()[:16]


def graph_fingerprint(model) -> str:
    """Mesh-independent digest of the op graph: names, types, and tensor
    shapes. Two models with the same fingerprint accept the same strategy
    map — the PlanCache key's first component."""
    desc = [(op.name, type(op).__name__,
             [tuple(int(x) for x in t.shape) for t in op.inputs],
             [tuple(int(x) for x in t.shape) for t in op.outputs])
            for op in model.ops]
    return _sha1(json.dumps(desc, sort_keys=True))[:16]


def mesh_signature(mesh) -> str:
    """Concrete mesh identity: axis names/sizes, platform, AND device ids.
    Device ids matter — a fleet's replicas sit on disjoint single-device
    meshes, and an executable compiled against one device cannot run
    against another's arrays."""
    devs = list(mesh.devices.flat)
    return json.dumps({
        "axes": {a: int(mesh.shape[a]) for a in mesh.axis_names},
        "platform": getattr(devs[0], "platform", "?") if devs else "?",
        "device_ids": [int(getattr(d, "id", -1)) for d in devs],
    }, sort_keys=True)


def strategy_signature(strategies) -> str:
    """Stable digest of a strategy map (every field that changes the
    lowered program)."""
    desc = {name: [list(pc.degrees), pc.device_type,
                   list(pc.memory_types),
                   int(getattr(pc, "param_degree", 1)),
                   getattr(pc, "exchange", "dense"),
                   float(getattr(pc, "hot_fraction", 0.0)),
                   bool(getattr(pc, "overlap", False))]
            for name, pc in (strategies or {}).items()}
    return _sha1(json.dumps(desc, sort_keys=True))[:16]


# ---------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------
def _pc_to_json(pc) -> Dict[str, Any]:
    return {"degrees": list(map(int, pc.degrees)),
            "device_type": pc.device_type,
            "memory_types": list(pc.memory_types),
            "param_degree": int(getattr(pc, "param_degree", 1)),
            "exchange": getattr(pc, "exchange", "dense"),
            "hot_fraction": float(getattr(pc, "hot_fraction", 0.0)),
            "overlap": bool(getattr(pc, "overlap", False))}


def _pc_from_json(d: Dict[str, Any]):
    from ..parallel.pconfig import ParallelConfig
    return ParallelConfig(tuple(d["degrees"]),
                          device_type=d.get("device_type", "TPU"),
                          memory_types=tuple(d.get("memory_types", ())),
                          param_degree=int(d.get("param_degree", 1)),
                          exchange=d.get("exchange", "dense"),
                          hot_fraction=float(d.get("hot_fraction", 0.0)),
                          overlap=bool(d.get("overlap", False)))


class PlanCache:
    """MCMC plans keyed by (graph, topology, budget, seed) in one JSON
    file. Thread-safe for the read-modify-replace write; concurrent
    writers last-win per key (entries are deterministic per key, so a
    lost update rewrites identical content)."""

    def __init__(self, directory: str):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        from ..analysis.sanitizer import make_lock
        self._lock = make_lock("PlanCache._lock")
        self.hits = 0
        self.misses = 0
        self.rejects = 0
        self.last_reject = ""

    def _path(self) -> str:
        return os.path.join(self.directory, PLANS_FILE)

    @staticmethod
    def key(graph_fp: str, ndev: int, axis_sizes, budget: int,
            seed: int) -> str:
        axes = "x".join(str(int(a)) for a in axis_sizes)
        return f"{graph_fp}|ndev={int(ndev)}|axes={axes}|" \
               f"budget={int(budget)}|seed={int(seed)}"

    def _read(self) -> Dict[str, Any]:
        from . import faults
        path = self._path()
        try:
            faults.maybe_corrupt_cache(path)
            with open(path) as f:
                m = json.load(f)
            if isinstance(m, dict) and m.get("format") == _FORMAT:
                return m
            if os.path.exists(path):
                self._reject(f"{PLANS_FILE} has format "
                             f"{m.get('format') if isinstance(m, dict) else '?'}"
                             f" != {_FORMAT}; ignoring")
        except FileNotFoundError:
            pass
        except (json.JSONDecodeError, OSError, ValueError) as e:
            self._reject(f"unreadable {PLANS_FILE} ({e}); treating as empty")
        return {"format": _FORMAT, "plans": {}}

    def _reject(self, reason: str) -> None:
        self.rejects += 1
        self.last_reject = reason
        log_cache.warning("plan cache: %s", reason)

    def get(self, key: str, ndev: int) -> Optional[Dict[str, Any]]:
        """The cached strategy map for `key`, or None. A hit whose
        recorded device count disagrees with `ndev` (a corrupt or
        hand-edited entry — the silent correctness hazard shardcheck
        FLX506 exists for) is rejected, not returned."""
        out = self._get(key, ndev)
        _obs_cache_event("plan", "hit" if out is not None else "miss")
        return out

    def _get(self, key: str, ndev: int) -> Optional[Dict[str, Any]]:
        entry = self._read()["plans"].get(key)
        if entry is None:
            self.misses += 1
            return None
        if int(entry.get("ndev", -1)) != int(ndev):
            self._reject(
                f"entry {key!r} records ndev={entry.get('ndev')} but the "
                f"target mesh has {ndev} device(s) — a plan cached for "
                f"one topology must not ship on another")
            self.misses += 1
            return None
        try:
            strategies = {name: _pc_from_json(d)
                          for name, d in entry["strategies"].items()}
        except (KeyError, TypeError, ValueError) as e:
            self._reject(f"entry {key!r} failed to decode ({e})")
            self.misses += 1
            return None
        self.hits += 1
        return {"strategies": strategies, "ndev": int(entry["ndev"]),
                "searched": bool(entry.get("searched", False))}

    def put(self, key: str, strategies, ndev: int,
            searched: bool = False) -> None:
        entry = {"ndev": int(ndev), "searched": bool(searched),
                 "time": time.time(),
                 "strategies": {name: _pc_to_json(pc)
                                for name, pc in strategies.items()}}
        path = self._path()
        with self._lock:
            m = self._read()
            m["plans"][key] = entry
            tmp = f"{path}.tmp-{os.getpid()}"
            try:
                with open(tmp, "w") as f:
                    json.dump(m, f, indent=1, sort_keys=True)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
            except OSError as e:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                # best-effort: a cache that cannot write costs the next
                # recovery a search, never correctness
                log_cache.warning("plan cache write failed (%s)", e)

    def entries(self) -> Dict[str, Dict[str, Any]]:
        """Raw {key: entry} view (shardcheck's --plan-cache audit reads
        this to re-verify every cached plan against its recorded mesh)."""
        return dict(self._read()["plans"])

    def stats(self) -> Dict[str, Any]:
        return {"hits": self.hits, "misses": self.misses,
                "rejects": self.rejects, "last_reject": self.last_reject}


# ---------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------
class CompileCache:
    """Serialized AOT executables, one file per (kind, fingerprint,
    strategy, mesh, shape) key.

    ``get`` returns a loaded ``jax.stages.Compiled`` or None; EVERY
    failure mode (missing, torn, CRC mismatch, stale code fingerprint,
    key collision, deserialize error, backend without serialization
    support) is a miss with a recorded reason — never an exception on
    the caller's hot path."""

    def __init__(self, directory: str):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._code_fp = code_fingerprint()
        self.hits = 0
        self.misses = 0
        self.rejects = 0
        self.puts = 0
        self.put_errors = 0
        self.last_reject = ""

    # --- keys ----------------------------------------------------------
    def exec_key(self, kind: str, model, shape_key) -> str:
        """Full executable identity: kind (train/eval/superstep/...),
        code fingerprint, strategy signature, mesh signature (device ids
        included), and the caller's shape/sharding signature."""
        return "|".join((
            f"fmt={_FORMAT}", f"kind={kind}", f"code={self._code_fp}",
            f"strat={strategy_signature(getattr(model, 'strategies', None))}",
            f"mesh={mesh_signature(model.mesh)}",
            f"shape={shape_key!r}"))

    def _path(self, key: str) -> str:
        return os.path.join(self.directory, f"exec-{_sha1(key)}.bin")

    def _reject(self, reason: str) -> None:
        self.rejects += 1
        self.last_reject = reason
        log_cache.warning("compile cache: %s — falling back to a fresh "
                          "compile", reason)

    # --- read ----------------------------------------------------------
    def get(self, key: str, devices):
        """`devices`: the mesh's devices, in mesh order — the executable
        loads onto exactly these (a sub-mesh executable must not be
        spread over every device the backend has)."""
        out = self._get(key, devices)
        _obs_cache_event("compile", "hit" if out is not None else "miss")
        return out

    def _get(self, key: str, devices):
        from . import faults
        path = self._path(key)
        if not os.path.isfile(path):
            self.misses += 1
            return None
        name = os.path.basename(path)
        try:
            faults.maybe_corrupt_cache(path)
            with open(path, "rb") as f:
                blob = pickle.load(f)
        except Exception as e:   # noqa: BLE001 — torn pickle, IO error:
            self._reject(f"{name} unreadable ({type(e).__name__}: {e})")
            self.misses += 1
            return None
        try:
            if blob.get("key") != key:
                raise ValueError(
                    f"key mismatch (hash collision or renamed file): "
                    f"cached {blob.get('key')!r:.80}")
            if blob.get("code") != self._code_fp:
                raise ValueError(
                    f"stale code fingerprint {blob.get('code')} != "
                    f"{self._code_fp} (checkout changed since compile)")
            payload = blob["payload"]
            if zlib.crc32(payload) != blob.get("crc32"):
                raise ValueError("payload CRC mismatch (bit rot)")
            from jax.experimental import serialize_executable
            exec_ = serialize_executable.deserialize_and_load(
                payload, blob["in_tree"], blob["out_tree"],
                execution_devices=list(devices))
        except Exception as e:   # noqa: BLE001 — stale/corrupt/unsupported
            self._reject(f"{name}: {e}")
            self.misses += 1
            return None
        self.hits += 1
        return exec_

    # --- write ---------------------------------------------------------
    def put(self, key: str, compiled) -> bool:
        """Best-effort serialize+store; False (with a counted error) when
        the executable does not support serialization or the write
        fails. The caller already holds the compiled executable — a
        failed put costs the NEXT boot a compile, nothing else."""
        try:
            from jax.experimental import serialize_executable
            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
        except Exception as e:   # noqa: BLE001 — backend w/o support
            self.put_errors += 1
            log_cache.info("compile cache: executable not serializable "
                           "(%s); entry skipped", e)
            return False
        blob = {"format": _FORMAT, "key": key, "code": self._code_fp,
                "payload": payload, "crc32": zlib.crc32(payload),
                "in_tree": in_tree, "out_tree": out_tree,
                "time": time.time()}
        path = self._path(key)
        tmp = f"{path}.tmp-{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                pickle.dump(blob, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except Exception as e:   # noqa: BLE001 — full disk, perms
            try:
                os.unlink(tmp)
            except OSError:
                pass
            self.put_errors += 1
            log_cache.warning("compile cache write failed (%s)", e)
            return False
        self.puts += 1
        return True

    def stats(self) -> Dict[str, Any]:
        return {"hits": self.hits, "misses": self.misses,
                "rejects": self.rejects, "puts": self.puts,
                "put_errors": self.put_errors,
                "last_reject": self.last_reject}


# ---------------------------------------------------------------------
# serving shard cache
# ---------------------------------------------------------------------
class ShardCache:
    """Persisted embedding-shard row blocks for the serving shard tier
    (serve/shardtier.py): one npz per (nshards, slot) carrying the
    shard's per-op row blocks, its applied version, and its publish
    chain CRC.

    This is the shard tier's replace-dead warm start: when a lookup
    shard is ejected and replaced, the replacement boots from its slot's
    cached blocks (milliseconds) instead of re-slicing a full checkpoint
    — and is re-admitted only when its version + chain CRC match what
    the live set expects AND its admission probe succeeds. Every failure
    mode (missing, torn, CRC mismatch, foreign fingerprint, wrong slot
    geometry) is a miss with a recorded reason, exactly like the
    plan/compile caches above."""

    def __init__(self, directory: str, fingerprint: str = ""):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.fingerprint = fingerprint
        self.hits = 0
        self.misses = 0
        self.rejects = 0
        self.puts = 0
        self.put_errors = 0
        self.last_reject = ""

    def _path(self, nshards: int, slot: int) -> str:
        return os.path.join(self.directory,
                            f"shard-{nshards}x-{slot}.npz")

    def _reject(self, reason: str) -> None:
        self.rejects += 1
        self.last_reject = reason
        log_cache.warning("shard cache: %s — replacement shard must "
                          "rebuild cold", reason)

    def put(self, nshards: int, slot: int, blocks: Dict[str, "np.ndarray"],
            version: int, chain_crc: int) -> bool:
        """Atomically persist one shard's blocks (temp + fsync +
        os.replace, the checkpoint discipline). Best-effort: a failed
        put costs the next replacement a cold rebuild, nothing else."""
        import numpy as np

        from ..quant.store import QuantTable
        flat = {}
        for k, v in blocks.items():
            if isinstance(v, QuantTable):
                # quantized blocks persist as codes + row scales +
                # dtype — bit-exact round trip at ~1/4 the fp32 bytes;
                # the max-scale bound lets get() reject in-memory
                # scale corruption the file CRC cannot see
                flat[f"block/{k}"] = v.encoded()
                flat[f"scale/{k}"] = v.scales
                flat[f"qdt/{k}"] = np.asarray(v.dtype)
                flat[f"sbd/{k}"] = np.asarray(
                    float(v.scales.max()) if v.scales.size else 0.0,
                    np.float32)
            else:
                flat[f"block/{k}"] = np.ascontiguousarray(v)
        flat["meta/version"] = np.asarray(version, np.int64)
        flat["meta/chain_crc"] = np.asarray(chain_crc & 0xFFFFFFFF,
                                            np.int64)
        flat["meta/nshards"] = np.asarray(nshards, np.int64)
        flat["meta/slot"] = np.asarray(slot, np.int64)
        if self.fingerprint:
            flat["meta/fingerprint"] = np.frombuffer(
                self.fingerprint.encode(), np.uint8)
        crc = 0
        for k in sorted(flat):
            crc = zlib.crc32(k.encode(), crc)
            crc = zlib.crc32(np.ascontiguousarray(flat[k]), crc)
        flat["meta/crc32"] = np.asarray(crc, np.int64)
        path = self._path(nshards, slot)
        tmp = f"{path}.tmp-{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                np.savez(f, **flat)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except Exception as e:   # noqa: BLE001 — full disk, perms
            try:
                os.unlink(tmp)
            except OSError:
                pass
            self.put_errors += 1
            log_cache.warning("shard cache write failed (%s)", e)
            return False
        self.puts += 1
        return True

    def get(self, nshards: int, slot: int):
        """(blocks, version, chain_crc) or None with the reason
        recorded. The corrupt-cache fault hook fires here so chaos tests
        can prove a torn entry degrades to a cold rebuild."""
        import numpy as np

        from . import faults
        path = self._path(nshards, slot)
        if not os.path.isfile(path):
            self.misses += 1
            return None
        name = os.path.basename(path)
        try:
            faults.maybe_corrupt_cache(path)
            data = np.load(path)
            files = set(data.files)
            stored_crc = int(data["meta/crc32"])
            crc = 0
            for k in sorted(files - {"meta/crc32"}):
                crc = zlib.crc32(k.encode(), crc)
                crc = zlib.crc32(np.ascontiguousarray(data[k]), crc)
            if crc != stored_crc:
                raise ValueError("entry CRC mismatch (torn write / "
                                 "bit rot)")
            if self.fingerprint and "meta/fingerprint" in files:
                fp = bytes(data["meta/fingerprint"]).decode()
                if fp != self.fingerprint:
                    raise ValueError(
                        f"foreign fingerprint {fp} != "
                        f"{self.fingerprint} (differently-built model)")
            if (int(data["meta/nshards"]) != nshards
                    or int(data["meta/slot"]) != slot):
                raise ValueError(
                    f"geometry mismatch: entry is shard "
                    f"{int(data['meta/slot'])}/{int(data['meta/nshards'])}"
                    f", wanted {slot}/{nshards}")
            from ..quant.codec import validate_scales
            from ..quant.store import QuantTable
            blocks = {}
            for k in files:
                if not k.startswith("block/"):
                    continue
                op = k[len("block/"):]
                if f"scale/{op}" in files:
                    dt = str(data[f"qdt/{op}"])
                    scales = faults.maybe_corrupt_quant_scale(
                        op, np.array(data[f"scale/{op}"]))
                    # a corrupt scale must reject the ENTRY (cold
                    # rebuild), never boot a shard serving amplified
                    # rows (FF_FAULT_QUANT_SCALE drills this)
                    bound = float(data[f"sbd/{op}"]) \
                        if f"sbd/{op}" in files else None
                    validate_scales(op, scales, bound)
                    blocks[op] = QuantTable.from_encoded(
                        np.array(data[k]), scales, dt)
                else:
                    blocks[op] = np.array(data[k])
            version = int(data["meta/version"])
            chain_crc = int(data["meta/chain_crc"])
        except Exception as e:   # noqa: BLE001 — torn npz, bad meta
            self._reject(f"{name}: {e}")
            self.misses += 1
            return None
        self.hits += 1
        return blocks, version, chain_crc

    # --- the tier-geometry meta sidecar --------------------------------
    # Everything a shard PROCESS (serve/shard_server.py) or a connect()-
    # built set needs that is NOT row blocks: per-op slot ranges, row
    # widths, per-table default rows, quant policies, fingerprint. One
    # JSON per shard count, next to the slot entries.

    def _meta_path(self, nshards: int) -> str:
        return os.path.join(self.directory,
                            f"shard-{nshards}x.meta.json")

    def put_meta(self, nshards: int, meta: Dict[str, Any]) -> bool:
        """Atomically persist the tier geometry (temp + fsync +
        os.replace). Best-effort, like :meth:`put`."""
        doc = dict(meta)
        if self.fingerprint:
            doc.setdefault("fingerprint", self.fingerprint)
        path = self._meta_path(nshards)
        tmp = f"{path}.tmp-{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(doc, f, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except Exception as e:   # noqa: BLE001 — full disk, perms
            try:
                os.unlink(tmp)
            except OSError:
                pass
            self.put_errors += 1
            log_cache.warning("shard meta write failed (%s)", e)
            return False
        self.puts += 1
        return True

    def get_meta(self, nshards: int) -> Optional[Dict[str, Any]]:
        """The tier geometry, or None with the reason recorded (torn
        JSON, foreign fingerprint, wrong shard count — same
        reject-with-reason contract as :meth:`get`)."""
        path = self._meta_path(nshards)
        if not os.path.isfile(path):
            self.misses += 1
            return None
        name = os.path.basename(path)
        try:
            with open(path) as f:
                meta = json.load(f)
            if not isinstance(meta, dict):
                raise ValueError("meta is not a JSON object")
            if int(meta.get("nshards", nshards)) != nshards:
                raise ValueError(
                    f"meta is for {meta.get('nshards')} shard(s), "
                    f"wanted {nshards}")
            fp = str(meta.get("fingerprint", ""))
            if self.fingerprint and fp and fp != self.fingerprint:
                raise ValueError(
                    f"foreign fingerprint {fp} != {self.fingerprint} "
                    f"(differently-built model)")
        except Exception as e:   # noqa: BLE001 — torn/invalid JSON
            self._reject(f"{name}: {e}")
            self.misses += 1
            return None
        self.hits += 1
        return meta

    def stats(self) -> Dict[str, Any]:
        return {"hits": self.hits, "misses": self.misses,
                "rejects": self.rejects, "puts": self.puts,
                "put_errors": self.put_errors,
                "last_reject": self.last_reject}


# ---------------------------------------------------------------------
# directory convention
# ---------------------------------------------------------------------
def cache_dir_for(checkpoint_dir: Optional[str],
                  configured: str = "") -> Optional[str]:
    """Resolve the warm-cache directory from the config knob:

    - ``""`` (default) — caching OFF;
    - ``"auto"`` — ``<checkpoint_dir>/cache`` when a checkpoint dir is in
      play (the caches live next to the manifest), else off;
    - any other string — that path, verbatim.
    """
    if not configured:
        return None
    if configured == "auto":
        if not checkpoint_dir:
            return None
        from .checkpoint import CheckpointManager
        return os.path.join(os.path.abspath(checkpoint_dir),
                            CheckpointManager.CACHE_DIR)
    return os.path.abspath(configured)


def open_caches(checkpoint_dir: Optional[str], configured: str = ""
                ) -> Tuple[Optional[PlanCache], Optional[CompileCache]]:
    """(PlanCache, CompileCache) for the resolved directory, or (None,
    None) when caching is off. Never raises: an unusable directory logs
    and disables caching (cold behavior, not a dead job)."""
    d = cache_dir_for(checkpoint_dir, configured)
    if d is None:
        return None, None
    try:
        return PlanCache(d), CompileCache(d)
    except OSError as e:
        log_cache.warning("cannot open warm cache at %s (%s); running "
                          "cold", d, e)
        return None, None
