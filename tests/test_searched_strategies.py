"""The committed SEARCHED DLRM strategies must EXECUTE (search ->
export .pb -> load -> compile -> train-step, closed for the DLRM
configs like the InceptionV3 pipeline already is).

Strategies key op NAMES (reference strategy.cc:23-26), which are
table-size-independent — the tests rebuild each config with scaled-down
tables so the virtual CPU mesh can hold them, then train one real step
under the exact searched placement.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm, \
    synthetic_batch
from dlrm_flexflow_tpu.parallel.distributed import make_multihost_mesh
from dlrm_flexflow_tpu.parallel.strategy_io import load_strategies

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scaled(sizes, cap=4096):
    # keep the ragged size profile, bounded for the CPU mesh; multiples
    # of 16 keep row-block sharding and lane packing divisible
    return [max(16, min(int(s), cap) // 16 * 16) for s in sizes]


def _kaggle_model(batch):
    from benchmarks.search_dlrm import KAGGLE_TABLES
    # same LAYER COUNTS as the searched config (op names key strategies),
    # smaller widths
    dcfg = DLRMConfig(embedding_size=_scaled(KAGGLE_TABLES),
                      sparse_feature_size=16,
                      mlp_bot=[13, 64, 64, 32, 16],
                      mlp_top=[432, 64, 32, 1])
    model = ff.FFModel(ff.FFConfig(batch_size=batch))
    build_dlrm(model, dcfg)
    return model, dcfg


@pytest.mark.parametrize("pb", [
    "dlrm_kaggle_8dev_ici_flat_roofline.pb",
    "dlrm_kaggle_8dev_dcn_2host_roofline.pb",
])
def test_searched_kaggle_strategy_executes(pb):
    path = os.path.join(REPO, "strategies", pb)
    assert os.path.exists(path), (
        f"missing {pb}: regenerate with benchmarks/search_dlrm.py")
    strategies = load_strategies(path)
    batch = 64
    model, dcfg = _kaggle_model(batch)
    # every op the search placed must exist in the rebuilt model
    missing = [k for k in strategies if model.get_layer_by_name(k) is None]
    assert not missing, f"searched ops absent from the model: {missing}"
    mesh = (make_multihost_mesh(num_slices=2) if "dcn" in pb
            else make_multihost_mesh(num_slices=1))
    model.compile(ff.SGDOptimizer(lr=0.05), "mean_squared_error", ["mse"],
                  mesh=mesh, strategies=strategies)
    model.init_layers()
    x, y = synthetic_batch(dcfg, batch, seed=0)
    x["label"] = y
    mets = model.train_batch(x)
    assert np.isfinite(float(mets["loss"]))


_TB_RUNNER = r"""
import os, sys
sys.path.insert(0, {repo!r})
from dlrm_flexflow_tpu.utils.testing import ensure_cpu_devices
ensure_cpu_devices(64)
import numpy as np
import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm, \
    synthetic_batch
from dlrm_flexflow_tpu.parallel.distributed import make_multihost_mesh
from dlrm_flexflow_tpu.parallel.strategy_io import load_strategies
from benchmarks.search_dlrm import TB_TABLES

sizes = [max(16, min(int(s), 2048) // 16 * 16) for s in TB_TABLES]
dcfg = DLRMConfig(embedding_size=sizes, sparse_feature_size=64,
                  mlp_bot=[13, 64, 32, 32],
                  mlp_top=[64 * 27, 64, 64, 32, 1])
batch = 128
model = ff.FFModel(ff.FFConfig(batch_size=batch))
build_dlrm(model, dcfg)
strategies = load_strategies(os.path.join(
    {repo!r}, "strategies", "dlrm_terabyte_64dev_dcn8x8_roofline.pb"))
missing = [k for k in strategies if model.get_layer_by_name(k) is None]
assert not missing, f"searched ops absent: {{missing}}"
mesh = make_multihost_mesh(num_slices=8)
model.compile(ff.SGDOptimizer(lr=0.05), "mean_squared_error", ["mse"],
              mesh=mesh, strategies=strategies)
model.init_layers()
x, y = synthetic_batch(dcfg, batch, seed=0)
x["label"] = y
mets = model.train_batch(x)
loss = float(mets["loss"])
assert loss == loss
print(f"TB64_SEARCHED_OK loss={{loss:.6f}}")
"""


def test_searched_terabyte64_strategy_executes():
    """The 64-device searched Criteo-TB placement trains one step on an
    8-slice x 8 virtual mesh (own interpreter: device count is fixed at
    backend init)."""
    path = os.path.join(REPO, "strategies",
                        "dlrm_terabyte_64dev_dcn8x8_roofline.pb")
    assert os.path.exists(path), (
        "missing terabyte .pb: regenerate with benchmarks/search_dlrm.py "
        "--config terabyte")
    proc = subprocess.run(
        [sys.executable, "-c", _TB_RUNNER.format(repo=REPO)],
        capture_output=True, text=True, timeout=900, cwd=REPO)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-4000:]
    assert "TB64_SEARCHED_OK" in proc.stdout
