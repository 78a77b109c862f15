"""Distribution-correctness tests: the SAME training run on a 1-device and
an 8-device mesh must produce numerically equal parameters.

This is the core upgrade over the reference's multi-GPU testing (reference:
test_harness.py num_gpu=2 variants needing real GPUs): GSPMD guarantees
semantics are placement-independent, and we verify it end-to-end through
forward+backward+optimizer across several strategies, on the virtual CPU
mesh from conftest.py.
"""

import numpy as np
import pytest

import jax

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                           dlrm_strategy, synthetic_batch)
from dlrm_flexflow_tpu.parallel.mesh import make_mesh
from dlrm_flexflow_tpu.parallel.pconfig import ParallelConfig


def _build_dlrm_model(dcfg, ndev, strategies=None, fuse=True, momentum=0.9):
    model = ff.FFModel(ff.FFConfig(batch_size=16, seed=7))
    build_dlrm(model, dcfg, fuse_embeddings=fuse)
    strat = strategies(model, dcfg, ndev) if callable(strategies) else strategies
    model.compile(ff.SGDOptimizer(lr=0.1, momentum=momentum),
                  "mean_squared_error", ["mse"],
                  mesh=make_mesh(num_devices=ndev), strategies=strat)
    model.init_layers()
    return model


def _train_dlrm(ndev, strategies=None, steps=3, fuse=True):
    dcfg = DLRMConfig(embedding_size=[64] * 8, sparse_feature_size=8,
                      mlp_bot=[4, 16, 8], mlp_top=[72, 16, 1])
    model = _build_dlrm_model(dcfg, ndev, strategies, fuse)
    for s in range(steps):
        x, y = synthetic_batch(dcfg, 16, seed=s)
        x["label"] = y
        model.train_batch(x)
    return jax.tree.map(np.asarray, model.params)


def _assert_tree_close(a, b, rtol=2e-4, atol=2e-5):
    la = jax.tree.leaves(a)
    lb = jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(x, y, rtol=rtol, atol=atol)


@pytest.mark.parametrize("row_shard", [False, True])
def test_sharded_init_equals_eager_init(monkeypatch, row_shard):
    """init_layers picks the sharded SPMD init program for large ops and
    the eager init + placement for small ones; both give the same bits
    under the same shardings, in the same dict order."""
    from dlrm_flexflow_tpu.core import model as model_mod
    dcfg = DLRMConfig(embedding_size=[64] * 8, sparse_feature_size=8,
                      mlp_bot=[4, 16, 8], mlp_top=[72, 16, 1])

    def strat(m, d, n):
        return dlrm_strategy(m, d, n, row_shard=row_shard)

    eager = _build_dlrm_model(dcfg, 8, strat).params
    monkeypatch.setattr(model_mod, "_SHARDED_INIT_BYTES", 0)
    sharded = _build_dlrm_model(dcfg, 8, strat).params
    assert list(eager) == list(sharded)
    for name in eager:
        assert list(eager[name]) == list(sharded[name])
        for n, a in eager[name].items():
            b = sharded[name][n]
            assert a.sharding.is_equivalent_to(b.sharding, a.ndim), (name, n)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dp_matches_single_chip():
    single = _train_dlrm(1)
    multi = _train_dlrm(8)  # default: data parallel over 8 devices
    _assert_tree_close(single, multi)


def test_dlrm_strategy_matches_single_chip():
    """Table-parallel embeddings + DP MLPs ≡ single chip."""
    single = _train_dlrm(1)
    multi = _train_dlrm(8, strategies=dlrm_strategy)
    _assert_tree_close(single, multi)


def test_tensor_parallel_linear_matches():
    """channel-TP on an MLP layer ≡ single chip."""
    def strat(model, dcfg, ndev):
        s = dlrm_strategy(model, dcfg, ndev)
        s["top_dense_0"] = ParallelConfig((4, 2))
        s["bot_dense_0"] = ParallelConfig((2, 4))
        return s

    single = _train_dlrm(1)
    multi = _train_dlrm(8, strategies=strat)
    _assert_tree_close(single, multi)


def _sync_params_unfused_to_fused(unfused, fused):
    """Re-key the unfused model's initial params onto the fused model's
    layout: per-table kernels stack/concatenate into the fused op's packed
    kernel (via its pack_kernel), MLP params copy by name."""
    import jax.numpy as jnp
    fop = next(op for op in fused.ops
               if op.name in ("emb_stack", "emb_concat"))
    T = fop.num_tables
    tables = [np.asarray(unfused.params[f"emb_{i}"]["kernel"])
              for i in range(T)]
    if fop.type_name == "EmbedStack":
        logical = jnp.stack([jnp.asarray(t) for t in tables])
    else:
        pad = fop.total_rows - sum(t.shape[0] for t in tables)
        parts = [jnp.asarray(t) for t in tables]
        if pad:
            parts.append(jnp.zeros((pad, fop.out_dim), jnp.float32))
        logical = jnp.concatenate(parts)
    new = {k: dict(v) for k, v in fused.params.items()}
    shards = fused._param_sharding
    new[fop.name] = {"kernel": jax.device_put(
        fop.pack_kernel(logical), shards.get(fop.name, {}).get("kernel"))}
    for name, pdict in unfused.params.items():
        if name.startswith("emb_"):
            continue
        new[name] = {k: jax.device_put(jnp.asarray(np.asarray(v)),
                                       shards.get(name, {}).get(k))
                     for k, v in pdict.items()}
    fused.params = new
    fused.opt_state = fused.optimizer.init_state(new)
    return fop


def _fused_vs_unfused(dcfg, steps=3):
    """Train the unfused per-table and fused forms from IDENTICAL initial
    params on the same data (plain SGD → both take the sparse touched-rows
    path) and assert table-by-table + MLP equality."""
    unfused = _build_dlrm_model(dcfg, 8, dlrm_strategy, fuse=False,
                                momentum=0.0)
    fused = _build_dlrm_model(dcfg, 8, dlrm_strategy, fuse=True,
                              momentum=0.0)
    fop = _sync_params_unfused_to_fused(unfused, fused)
    for s in range(steps):
        x, y = synthetic_batch(dcfg, 16, seed=s)
        x["label"] = y
        unfused.train_batch(dict(x))
        fused.train_batch(dict(x))
    T = fop.num_tables
    logical = np.asarray(fop.unpack_kernel(fused.params[fop.name]["kernel"]))
    off = 0
    for i in range(T):
        rows = dcfg.embedding_size[i]
        if fop.type_name == "EmbedStack":
            ftab = logical[i]
        else:
            ftab = logical[off:off + rows]
            off += rows
        utab = np.asarray(unfused.params[f"emb_{i}"]["kernel"])
        np.testing.assert_allclose(ftab, utab, rtol=2e-4, atol=2e-5,
                                   err_msg=f"table {i}")
    for name, pdict in unfused.params.items():
        if name.startswith("emb_"):
            continue
        for k, v in pdict.items():
            np.testing.assert_allclose(
                np.asarray(fused.params[name][k]), np.asarray(v),
                rtol=2e-4, atol=2e-5, err_msg=f"{name}.{k}")


def test_per_table_embeddings_match_fused_stacked():
    """Unfused per-table ≡ fused stacked embedding, numerically, after
    re-keying initial params onto the packed layout (catches offset /
    lane-packing bugs the old finiteness check could not)."""
    _fused_vs_unfused(DLRMConfig(
        embedding_size=[64] * 8, sparse_feature_size=8,
        mlp_bot=[4, 16, 8], mlp_top=[72, 16, 1]))


def test_per_table_embeddings_match_fused_concat():
    """Unfused per-table ≡ fused concatenated-rows embedding (non-uniform
    table sizes — exercises EmbeddingBagConcat._global_indices offsets)."""
    _fused_vs_unfused(DLRMConfig(
        embedding_size=[40, 7, 300, 12, 64, 5, 128, 9],
        sparse_feature_size=8,
        mlp_bot=[4, 16, 8], mlp_top=[72, 16, 1]))


def test_strategy_search_space_feasibility():
    from dlrm_flexflow_tpu.parallel.sharding import AxisAssigner
    mesh = make_mesh(num_devices=8)
    asn = AxisAssigner(mesh)
    assert asn.feasible_degrees() == [1, 2, 4, 8]
    assert asn.assign([8, 1]) == [("f0", "f1", "f2"), ()]
    assert asn.assign([4, 2]) == [("f0", "f1"), ("f2",)]
    assert asn.assign([2, 4]) == [("f0",), ("f1", "f2")]
    spec = asn.spec([4, 1, 2])
    assert str(spec) == "PartitionSpec(('f0', 'f1'), None, 'f2')"
