"""Shared plumbing for native-API examples: path shim, flag parsing,
synthetic data, train loop (reference: each examples/cpp app's
top_level_task + DataLoader)."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np  # noqa: E402

import dlrm_flexflow_tpu as ff  # noqa: E402
from dlrm_flexflow_tpu.parallel.mesh import make_mesh  # noqa: E402


def setup(argv, default_batch=64):
    """Parse reference-style flags; returns (FFConfig, mesh). For a CPU
    run set JAX_PLATFORMS=cpu (and
    XLA_FLAGS=--xla_force_host_platform_device_count=N for N devices)."""
    import jax
    ff.use_compile_cache()
    cfg = ff.FFConfig.parse_args(argv)
    if cfg.batch_size <= 0:
        cfg.batch_size = default_batch
    ndev = min(cfg.num_devices, len(jax.devices())) or 1
    return cfg, make_mesh(num_devices=ndev)


def synthetic_classification(inputs, num_classes, n, seed=0):
    """Random images/features + int labels for each named input."""
    r = np.random.RandomState(seed)
    x = {name: r.randn(n, *shape[1:]).astype(np.float32)
         for name, shape in inputs.items()}
    y = r.randint(0, num_classes, size=(n, 1)).astype(np.int32)
    return x, y


def train(model, inputs, labels, cfg, loss="sparse_categorical_crossentropy",
          metrics=("accuracy",), optimizer=None, mesh=None, strategies=None):
    model.compile(optimizer or ff.SGDOptimizer(lr=cfg.learning_rate), loss,
                  list(metrics), mesh=mesh, strategies=strategies)
    model.init_layers(seed=cfg.seed)
    return model.fit(inputs, labels, epochs=cfg.epochs)
