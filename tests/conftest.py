"""Test fixture: force an 8-device virtual CPU mesh before JAX init.

The reference can only test multi-GPU behavior on real GPUs via SLURM
(reference: src/ops/tests/test_bootstrap.sh:2); a design goal of this
framework (SURVEY.md §4) is that ALL distribution logic is testable on CPU.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dlrm_flexflow_tpu.utils.testing import ensure_cpu_devices  # noqa: E402

import pytest  # noqa: E402

ensure_cpu_devices(8)


@pytest.fixture(autouse=True)
def _fit_finds_the_device_busy(monkeypatch):
    """`FFConfig.superstep` defaults to "auto": fit() asks at 32
    dispatches of a batch shape whether the device was idle, and fuses
    steps where it mostly was. On the CPU backend that answer is a matter
    of timing, so the suite pins it to "busy" (device-paced: the per-step
    path, one step program); the tests of the probe answer in its place
    themselves (tests/test_superstep.py::TestAuto)."""
    from dlrm_flexflow_tpu.core.model import FFModel
    monkeypatch.setattr(FFModel, "_idle_at_dispatch",
                        lambda self, vector: False)


def pytest_sessionfinish(session, exitstatus):
    """FF_SANITIZE=1 runs report (and fail on) any lock-order cycles /
    held-too-long / dispatch-under-lock violations the suite provoked.
    Tests that seed violations on purpose call ``sanitizer.reset()`` in
    their teardown, so anything left here is a real finding."""
    from dlrm_flexflow_tpu.analysis import sanitizer
    if not sanitizer.enabled():
        return
    leftover = sanitizer.violations()
    if leftover:
        print("\nFF_SANITIZE: %d unexpected sanitizer violation(s):"
              % len(leftover))
        for rep in leftover:
            print(f"  - {rep}")
        session.exitstatus = 1
    else:
        print("\nFF_SANITIZE: no lock-order cycles / held-too-long / "
              "dispatch-under-lock violations recorded")
