"""Plain-Embedding forward-gather residuals (out_dim == 128).

When a logical row is exactly one 128-lane tile, the XLA-gather forward
already materializes every looked-up row — Embedding.apply_with_fwd keeps
them, and both sparse updates (state-free SGD and stateful opt) consume
them instead of re-reading random rows. The residual-fed result must equal
the residual-free path exactly (it is the same math on the same values;
only the memory traffic differs). Gates are monkeypatched so the TPU-only
path runs in Pallas interpret mode on the CPU mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.ops import embedding as emb_mod
from dlrm_flexflow_tpu.ops.pallas import embedding_kernel as ker


@pytest.fixture
def force_tile_path(monkeypatch):
    monkeypatch.setattr(emb_mod, "_pallas_ok", lambda *a, **k: False)
    monkeypatch.setattr(emb_mod, "_pallas_scatter_ok", lambda *a, **k: True)
    orig_write = ker.scatter_write_rows_packed
    monkeypatch.setattr(
        ker, "scatter_write_rows_packed",
        lambda *a, **k: orig_write(*a, **{**k, "interpret": True}))
    orig_tiles = ker.scatter_write_tiles
    monkeypatch.setattr(
        ker, "scatter_write_tiles",
        lambda *a, **k: orig_tiles(*a, **{**k, "interpret": True}))
    orig_dedup = ker._dedup_tile_updates
    monkeypatch.setattr(
        ker, "_dedup_tile_updates",
        lambda rows, upds, interpret=False: orig_dedup(rows, upds, True))
    orig_add = ker.scatter_add_rows
    monkeypatch.setattr(
        ker, "scatter_add_rows",
        lambda *a, **k: orig_add(*a, **{**k, "interpret": True}))


def _make_op(aggr="sum", rows=64, bag=2, batch=8):
    model = ff.FFModel(ff.FFConfig(batch_size=batch))
    idx_t = model.create_tensor((batch, bag), dtype=jnp.int32, name="idx")
    model.embedding(idx_t, rows, 128, aggr=aggr, name="emb")
    (op,) = [o for o in model.ops if o.name == "emb"]
    rng = np.random.RandomState(0)
    params = {"kernel": jnp.asarray(
        rng.randn(rows, 128).astype(np.float32))}
    idx = jnp.asarray(rng.randint(0, rows, (batch, bag)).astype(np.int32))
    return op, params, idx


def test_apply_with_fwd_matches_apply(force_tile_path):
    op, params, idx = _make_op()
    assert op._fwd_residual_ok()
    outs, fwd = op.apply_with_fwd(params, [idx])
    (want,) = op.apply(params, [idx])
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert fwd is not None
    g, tiles = fwd
    np.testing.assert_array_equal(
        np.asarray(tiles), np.asarray(params["kernel"])[np.asarray(g)])


def test_sparse_sgd_update_with_residuals(force_tile_path):
    op, params, idx = _make_op()
    _, fwd = op.apply_with_fwd(params, [idx])
    ct = jnp.asarray(np.random.RandomState(1).randn(
        idx.shape[0], 128).astype(np.float32))
    with_fwd = op.sparse_sgd_update(params, [idx], ct, 0.1, fwd=fwd)
    without = op.sparse_sgd_update(params, [idx], ct, 0.1, fwd=None)
    np.testing.assert_allclose(np.asarray(with_fwd["kernel"]),
                               np.asarray(without["kernel"]),
                               rtol=1e-5, atol=1e-5)


def test_sparse_opt_update_with_residuals(force_tile_path):
    op, params, idx = _make_op(aggr="avg")
    opt = ff.AdamOptimizer(alpha=0.01)
    _, fwd = op.apply_with_fwd(params, [idx])
    rng = np.random.RandomState(2)
    ct = jnp.asarray(rng.randn(idx.shape[0], 128).astype(np.float32))
    slabs = {k: jnp.asarray(rng.rand(*params["kernel"].shape)
                            .astype(np.float32))
             for k in opt.sparse_slab_names()}
    step = jnp.asarray(3, jnp.int32)
    w_fwd, s_fwd = op.sparse_opt_update(params, [idx], ct, opt, slabs,
                                        step, fwd=fwd)
    w_no, s_no = op.sparse_opt_update(params, [idx], ct, opt, slabs,
                                      step, fwd=None)
    np.testing.assert_allclose(np.asarray(w_fwd["kernel"]),
                               np.asarray(w_no["kernel"]),
                               rtol=1e-5, atol=1e-5)
    for k in s_fwd:
        np.testing.assert_allclose(np.asarray(s_fwd[k]),
                                   np.asarray(s_no[k]),
                                   rtol=1e-5, atol=1e-5, err_msg=k)
