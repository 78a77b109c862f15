"""The DLRM family: the plain reference against the system at tiny sizes
(1 and 4 virtual devices, `cat` and `dot`), what the check must refuse, and
the shape arithmetic."""

import math

import numpy as np
import pytest

from perfbench import manifest as mf
from perfbench.models import dlrm as family
from perfbench.traffic import gen

BATCH, STEPS = 16, 3
TINY = {
    # uniform tables -> EmbeddingBagStacked, 8 rows packed to a stored row
    "cat": {"table_rows": [64, 64, 64, 64], "embedding_dim": 16,
            "mlp_bot": [8, 32, 16], "mlp_top": [80, 32, 1],
            "interaction": "cat"},
    # uneven tables -> EmbeddingBagConcat, 4 rows packed to a stored row
    "dot": {"table_rows": [64, 3, 1, 40], "embedding_dim": 32,
            "mlp_bot": [5, 16, 32], "mlp_top": [42, 16, 1],
            "interaction": "dot"},
}
COMMON = {"family": "dlrm", "bag_size": 1, "loss": "mean_squared_error",
          "optimizer": {"type": "sgd", "lr": 0.01},
          "compute_dtype": "bfloat16", "deployment": {"chips": 1}}


@pytest.fixture(scope="module", params=[
    ("cat", 1), ("cat", 4), ("dot", 1), ("dot", 4)],
    ids=lambda p: f"{p[0]}-{p[1]}dev")
def checked(request):
    """The harness's own sequence: snapshot, STEPS steps through fit on
    one batch, read the rows back."""
    kind, chips = request.param
    config = dict(COMMON, **TINY[kind])
    rows = family.held_table_rows(config, chips)
    model, timings = family.build(config, rows, BATCH, chips, seed=3)
    assert timings["build_s"] > 0 and timings["init_s"] > 0
    batch = gen.generate({"ids": {"distribution": "zipf", "alpha": 1.05}},
                         family.input_fields(config, rows), BATCH, seed=3)
    snap = family.snapshot(model, config, batch)
    x, y = family.fit_arrays(batch)
    losses = []
    model.fit(x, y, epochs=STEPS, verbose=False, callbacks=[
        lambda m, epoch, report: losses.append(report[family.LOSS_METRIC])])
    return config, snap, snap["touched"].read(model), losses


def test_system_agrees_with_the_plain_reference(checked):
    config, snap, rows_after, losses = checked
    out = family.verify(snap, rows_after, losses, config)
    assert out["ok"], out
    assert out["steps"] == STEPS and out["rows_largest_update"] > 0
    # zipf draws ids twice: fewer distinct rows than lookups, all of them
    # summed into one update
    assert out["rows_checked"] < snap["touched"].inv.size


def _dropped(rows0, rows_after, rng):
    return rows0


def _dropped_on_a_tenth(rows0, rows_after, rng):
    lost = rng.random(len(rows0)) < 0.1
    return np.where(lost[:, None], rows0, rows_after)


def _applied_twice(rows0, rows_after, rng):
    return rows0 + 2 * (rows_after - rows0)


def _kept_in(dtype):
    def kept(rows0, rows_after, rng):
        import jax.numpy as jnp
        return np.asarray(jnp.asarray(rows_after).astype(dtype)
                          .astype(jnp.float32))
    kept.__name__ = f"_kept_in_{dtype}"
    return kept


def _kept_in_int8(rows0, rows_after, rng):
    scale = np.abs(rows_after).max(axis=1, keepdims=True) / 127
    return (np.round(rows_after / scale) * scale).astype(np.float32)


@pytest.mark.parametrize("fault", [
    _dropped, _dropped_on_a_tenth, _applied_twice, _kept_in("bfloat16"),
    _kept_in("float8_e4m3fn"), _kept_in_int8],
    ids=lambda f: f.__name__.lstrip("_"))
def test_the_check_refuses(checked, fault):
    config, snap, rows_after, losses = checked
    broken = fault(snap["rows"], rows_after, np.random.default_rng(0))
    out = family.verify(snap, broken, losses, config)
    assert not out["ok"], out


def test_the_check_refuses_a_wrong_loss(checked):
    config, snap, rows_after, losses = checked
    out = family.verify(snap, rows_after, [1.01 * v for v in losses], config)
    assert not out["ok"]
    out = family.verify(snap, rows_after, [losses[0], float("nan"),
                                           losses[2]], config)
    assert not out["ok"]


def _config(name):
    return mf.load_config(mf.load(), name)


def test_operations_from_the_shapes():
    random, terabyte = _config("dlrm_random"), _config("dlrm_terabyte")
    # bot 64-512-512-64 and top 576-1024-1024-1024-1: 3.02 M MACs a sample
    assert family.macs_per_sample(random) == 3_015_680
    assert family.flops_per_sample(random) == 6 * 3_015_680
    # bot 13-512-256-128, 351 pairs of width 128, MLPerf's five top layers
    # 479-1024-1024-512-256-1: 2.41 M MACs a sample
    assert family.macs_per_sample(terabyte) == 2_410_112 == (
        13 * 512 + 512 * 256 + 256 * 128 + 351 * 128
        + 479 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256)
    # bytes: the weights four times, each looked-up row three times, each
    # activation twice, the inputs once
    weights = sum(a * b + b for a, b in (
        (64, 512), (512, 512), (512, 64), (576, 1024), (1024, 1024),
        (1024, 1024), (1024, 1)))
    acts = 512 + 512 + 64 + 576 + 1024 * 3 + 1
    per_sample = 3 * 8 * 64 * 4 + 2 * acts * 4 + (64 + 8 + 1) * 4
    assert family.bytes_per_step(random, 256) == (
        16 * weights + 256 * per_sample)


def test_a_cell_holds_its_chips_share_of_the_deployment():
    random, terabyte = _config("dlrm_random"), _config("dlrm_terabyte")
    assert family.held_table_rows(random, 1) == [1_000_000] * 8
    assert family.held_table_rows(random, 4) == [1_000_000] * 8
    for chips in (1, 4):
        held = family.held_table_rows(terabyte, chips)
        assert held == [math.ceil(r * chips / 16)
                        for r in terabyte["table_rows"]]
        assert min(held) >= 1
        gb = sum(held) * 128 * 4 / 1e9
        assert abs(gb / chips - 6.0) < 0.02          # 6.0 GB a chip
    assert family.held_table_rows(terabyte, 16) == terabyte["table_rows"]
    assert sum(terabyte["table_rows"]) * 512 / 1e9 == pytest.approx(96.1,
                                                                    abs=0.1)
