"""Host-platform device virtualization for tests and multi-chip dry runs.

The reference can only test multi-GPU behavior on real GPUs grabbed via
SLURM (reference: src/ops/tests/test_bootstrap.sh:2). A design goal here
(SURVEY.md §4) is that distribution logic is testable WITHOUT hardware:
`ensure_cpu_devices(n)` forces the JAX host platform with n virtual CPU
devices so the full GSPMD mesh/collective path compiles and runs anywhere.

Must run before JAX initializes its backends (it mutates XLA_FLAGS and the
platform config); it is a no-op if enough devices already exist.
"""

from __future__ import annotations

import os
import re


def ensure_cpu_devices(n: int) -> None:
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        import warnings
        if len(jax.devices()) < n:
            warnings.warn(
                f"JAX backends already initialized with "
                f"{len(jax.devices())} device(s); cannot virtualize {n} "
                f"CPU devices. Call ensure_cpu_devices() before any JAX "
                f"computation.")
        return

    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n}"
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       flag, flags)
    else:
        flags = (flags + " " + flag).strip()
    os.environ["XLA_FLAGS"] = flags
    # equivalent to JAX_PLATFORMS=cpu, for callers that cannot set the
    # environment before the interpreter starts
    jax.config.update("jax_platforms", "cpu")
