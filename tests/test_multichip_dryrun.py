"""The driver's multi-chip dryrun must compile clean: no SPMD
"Involuntary full rematerialization" — each one is a full all-gather per
step on real hardware (the reference moves only region intersections,
src/runtime/simulator.cc:279-326; GSPMD must be given agreeing producer/
consumer shardings to match that)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_8dev_no_spmd_rematerialization():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py"), "8"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    assert "ok, loss=" in out
    # the row-sharded (PARAM-axis, all-to-all routed) config trained
    assert "rowshard ok" in out
    # the SOAP-searched InceptionV3 strategy (.pb) loaded and trained
    pb = os.path.join(REPO, "strategies", "inception_v3_8dev_ici_flat.pb")
    assert os.path.exists(pb), (
        f"missing {pb}: regenerate with benchmarks/search_inception.py")
    assert "searched ok" in out
    # the Terabyte-shape config: optimize() under the capacity model must
    # host-offload the huge table and row-shard the concat tables, then
    # train a real step on the hybrid DCN+ICI mesh
    assert "terabyte ok" in out
    # the north-star v5e-64 topology EXECUTES (8 slices x 8, spawned as
    # a 64-virtual-device child)
    assert "terabyte-64 ok" in out
    assert "rematerialization" not in out, "\n".join(
        l[:200] for l in out.splitlines() if "rematerial" in l)
