"""One lookup shard as an OS process.

::

    python -m dlrm_flexflow_tpu.serve.shard_server \
        --cache-dir /ckpt/cache --nshards 4 --slot 2 --port 0

Boots slot ``--slot`` of an ``--nshards``-way tier from a seeded warm
cache (``EmbeddingShardSet.seed_shard_cache`` wrote the row blocks and
the tier-geometry meta sidecar; no model, no checkpoint, no JAX compile
in this process's serving path) and serves the wire protocol until
killed. ``--port 0`` takes an OS-assigned port; the chosen port is
printed on the ``SHARD_SERVER_OK`` line so a spawner (tests, the
serving example, an init system) can read it from stdout — the same
sentinel contract as ``tests/_mp3_worker.py``.

This is the process boundary ROADMAP item 1 asks for: ``kill -9`` of
this process is a real shard outage — the set's circuit breaker ejects
the slot, responses degrade (flagged), and replace-dead boots a warm
in-process standby from the same cache this process booted from.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spawn(cache_dir: str, nshards: int, slot: int,
          **popen_kw) -> subprocess.Popen:
    """Start slot ``slot`` as a child process on an OS-assigned port
    (read it off the ``SHARD_SERVER_OK`` line of its stdout). A shard is
    a host-RAM lookup process by design, so the child is pinned to the
    CPU platform whatever the parent runs on: an accelerator belongs to
    one process, and a child that reached for the ranker's chip (the
    shard's ``topk`` asks JAX for its backend) would fail or hang."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (_REPO, env.get("PYTHONPATH", "")) if p)
    return subprocess.Popen(
        [sys.executable, "-m", "dlrm_flexflow_tpu.serve.shard_server",
         "--cache-dir", cache_dir, "--nshards", str(nshards),
         "--slot", str(slot), "--port", "0"],
        env=env, text=True, stdout=subprocess.PIPE, **popen_kw)


def build_shard(cache_dir: str, nshards: int, slot: int):
    """The boot path, importable for tests: warm-cache blocks + meta
    sidecar -> a live :class:`~.shardtier.EmbeddingShard`."""
    from ..utils.warmcache import ShardCache
    from .shardtier import EmbeddingShard

    cache = ShardCache(cache_dir)
    meta = cache.get_meta(nshards)
    if meta is None:
        raise SystemExit(
            f"shard_server: no tier meta for {nshards} shard(s) in "
            f"{cache_dir!r} ({cache.last_reject or 'missing'}) — seed "
            f"it with EmbeddingShardSet.seed_shard_cache")
    cache.fingerprint = str(meta.get("fingerprint", ""))
    got = cache.get(nshards, slot)
    if got is None:
        raise SystemExit(
            f"shard_server: no cached blocks for slot {slot} of "
            f"{nshards} in {cache_dir!r} "
            f"({cache.last_reject or 'missing'})")
    blocks, version, chain_crc = got
    ranges = {op: tuple(r[slot]) for op, r in meta["ranges"].items()}
    domains = meta.get("domains") or [""] * nshards
    return EmbeddingShard(
        slot, slot, blocks, ranges, version=version,
        chain_crc=chain_crc, domain=str(domains[slot]),
        quant={str(k): str(v)
               for k, v in (meta.get("quant") or {}).items()})


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="serve one embedding lookup shard over the wire "
                    "protocol (boots from a seeded shard warm cache)")
    ap.add_argument("--cache-dir", required=True,
                    help="seeded ShardCache directory "
                         "(EmbeddingShardSet.seed_shard_cache)")
    ap.add_argument("--nshards", type=int, required=True,
                    help="total shard count of the tier")
    ap.add_argument("--slot", type=int, required=True,
                    help="which row-range slot this process owns")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="listen port; 0 = OS-assigned (printed on "
                         "the SHARD_SERVER_OK line)")
    args = ap.parse_args(argv)
    if not 0 <= args.slot < args.nshards:
        ap.error(f"--slot {args.slot} outside [0, {args.nshards})")

    shard = build_shard(args.cache_dir, args.nshards, args.slot)
    server = shard.serve(host=args.host, port=args.port)
    print(f"SHARD_SERVER_OK slot={args.slot} "
          f"port={server.address[1]} version={shard.version}",
          flush=True)
    try:
        server.serve_forever()   # start() is idempotent; blocks here
    except KeyboardInterrupt:
        pass
    finally:
        server.close()


if __name__ == "__main__":
    main()
