"""bench.py must stay machine-readable when it cannot reach a TPU.

A run that cannot measure the chip prints ONE JSON error line and exits
non-zero, so the driver can tell an unusable machine from a regression.
"""

import importlib.util
import json
import os
import subprocess
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_chip_health_probe_reports_on_cpu():
    """The probe must produce a NUMBER under benign conditions (CPU
    backend: tiny jitter, slow matmul) — it lengthens the window instead
    of giving up."""
    import jax
    tflops, rt_ms = _load_bench()._chip_health(jax, size=256, iters0=4)
    assert tflops is not None and tflops > 0
    assert rt_ms is not None and rt_ms >= 0


def test_failed_bench_section_fails_the_run(monkeypatch, capsys):
    bench = _load_bench()

    def boom(steps):
        raise ValueError("section exploded")

    monkeypatch.setitem(sys.modules, "bench_resilience",
                        types.SimpleNamespace(measure=boom))
    monkeypatch.setenv("BENCH_RESILIENCE", "1")
    sections = bench._run_sections()
    assert "section exploded" in sections["resilience"]["error"]

    ok = {"metric": bench.METRIC, "chip_bf16_tflops": 100.0}
    assert bench._finish(dict(ok)) == 0
    assert bench._finish(dict(ok, **sections)) == 1
    assert bench._finish(dict(ok, chip_bf16_tflops=None)) == 1
    capsys.readouterr()


def test_bench_emits_json_error_line_when_backend_unavailable():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "nonexistent_backend"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode != 0
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, proc.stdout + proc.stderr
    rec = json.loads(lines[0])
    assert rec["metric"] == "dlrm_random_train_throughput_per_chip"
    assert rec["value"] is None
    assert "error" in rec and "unavailable" in rec["error"]
