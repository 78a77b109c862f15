"""No fallback that hides the device (ISSUE 21).

On the CPU the chip entry points must REFUSE: `chip_smoke.py` and
`bench.py` exit non-zero and name the platform they found (an opted-in
bench section that raises fails the run too: tests/test_bench_failure.py),
the compile-cache helper never moves the cache between runs, and helper processes are pinned to the CPU
so they cannot reach for the chip their parent holds.
"""

import io
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_entry_points_refuse_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = {name: subprocess.Popen(
        [sys.executable, os.path.join(REPO, name)], env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        for name in ("chip_smoke.py", "bench.py")}
    out = {name: p.communicate(timeout=300) + (p.returncode,)
           for name, p in procs.items()}

    stdout, stderr, rc = out["chip_smoke.py"]
    assert rc != 0
    assert "platform=cpu" in stdout and "'cpu'" in stderr
    assert '"ok"' not in stdout            # no result line

    stdout, stderr, rc = out["bench.py"]
    assert rc != 0
    (line,) = [ln for ln in stdout.splitlines() if ln.strip()]
    rec = json.loads(line)
    assert rec["value"] is None and "'cpu'" in rec["error"]


def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch,
                                                       tmp_path):
    import jax

    from dlrm_flexflow_tpu.utils.compile_cache import use_compile_cache
    updates = []          # record config writes instead of making them
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append(value))

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert updates == []                   # JAX reads the variable itself

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    for cwd in (tmp_path, REPO):
        monkeypatch.chdir(cwd)
        assert use_compile_cache() == fixed
    assert updates == [fixed, fixed]


class _FakeProc:
    """A shard-server child that has already printed its sentinel."""
    pid = 0

    def __init__(self):
        self.stdout = io.StringIO("SHARD_SERVER_OK slot=0 port=1 version=0\n")

    def kill(self):
        pass


@pytest.fixture
def popen_envs(monkeypatch):
    """Capture the environment of every shard-server child instead of
    starting it; the parent pretends to hold a TPU."""
    from dlrm_flexflow_tpu.serve import shard_server
    envs = []

    def fake_popen(cmd, env=None, **kw):
        envs.append(env)
        return _FakeProc()

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(shard_server.subprocess, "Popen", fake_popen)
    return envs


def test_shard_children_are_pinned_to_cpu(popen_envs, monkeypatch):
    import dlrm_flexflow_tpu as ff
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    sys.path.insert(0, os.path.join(REPO, "examples", "native"))
    import bench_serve_fleet
    import serve_dlrm

    bench_serve_fleet._spawn_shard_procs("cache", 1)

    monkeypatch.setattr(ff.EmbeddingShardSet, "seed_shard_cache",
                        lambda *a, **k: None)
    monkeypatch.setattr(ff.EmbeddingShardSet, "connect",
                        lambda *a, **k: None)
    monkeypatch.setattr(serve_dlrm, "_SHARD_PROCS", [])
    monkeypatch.setattr(serve_dlrm, "_shard_cache_dir",
                        lambda cfg, ckpt: "cache")
    cfg = ff.FFConfig()
    cfg.serve_shard_procs = 1
    serve_dlrm._spawn_shard_procs(cfg, None, None)

    assert len(popen_envs) == 2
    for env in popen_envs:
        assert env["JAX_PLATFORMS"] == "cpu"
        assert REPO in env["PYTHONPATH"].split(os.pathsep)
