"""Fused gather→dot-interaction→top-MLP kernel (ISSUE 19).

The Pallas kernel (ops/pallas/interaction_kernel.py, exercised in
interpreter mode on the CPU backend) must match the unfused jnp oracle
``fused_interaction_reference`` — the exact composition the default
graph builds as five ops — to float32 rounding: forward (relu and
linear heads, 2-D and bagged indices) and the custom-vjp backward for
every differentiable input.

The op wrapper (ops/interaction.py FusedDotInteraction, built by
build_dlrm(fuse_interaction=True)) must train on the fallback path
wherever the kernel cannot run (CPU backend, multi-chip GSPMD) with the
same numbers the kernel path produces, and analysis/hlo_audit FLX515
must flag exactly the lowerings that materialize the [B, F, F]
interaction tensor the fused plan was priced without.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.analysis.hlo_audit import audit_interaction_fusion
from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                           synthetic_batch)
from dlrm_flexflow_tpu.ops.pallas.interaction_kernel import (
    fused_interaction, fused_interaction_reference, scatter_tril_weight,
    supports, tril_pairs)
from dlrm_flexflow_tpu.parallel.mesh import make_mesh

T, ROWS, D, BAG, H, B = 4, 64, 128, 3, 32, 13
F = T + 1
P = len(tril_pairs(F))


def _inputs(seed=0, bag=BAG, d=D, batch=B):
    """Random table/indices/bottom/weights; indices pre-offset into the
    concatenated row space (what the op wrapper feeds the kernel)."""
    rng = np.random.RandomState(seed)
    table = jnp.asarray(rng.randn(T * ROWS, d).astype(np.float32))
    idx = jnp.asarray(np.stack(
        [rng.randint(t * ROWS, (t + 1) * ROWS, size=(batch, bag))
         for t in range(T)], axis=1).astype(np.int32))
    bottom = jnp.asarray(rng.randn(batch, d).astype(np.float32))
    w = jnp.asarray(rng.randn(d + P, H).astype(np.float32) * 0.1)
    bias = jnp.asarray(rng.randn(H).astype(np.float32))
    return table, idx, bottom, w, bias


class TestKernelVsOracle:
    @pytest.mark.parametrize("relu", [True, False])
    def test_forward(self, relu):
        table, idx, bottom, w, bias = _inputs()
        out_k = fused_interaction(table, idx, bottom, w, bias, relu,
                                  True)
        out_r = fused_interaction_reference(table, idx, bottom, w, bias,
                                            relu=relu)
        assert out_k.shape == (B, H)
        np.testing.assert_allclose(out_k, out_r, rtol=1e-5, atol=1e-4)

    def test_forward_2d_indices(self):
        """(batch, T) single-lookup indices take the bag=1 path."""
        table, idx, bottom, w, bias = _inputs(bag=1)
        idx2 = idx[:, :, 0]
        out_k = fused_interaction(table, idx2, bottom, w, bias, False,
                                  True)
        out_r = fused_interaction_reference(table, idx2, bottom, w,
                                            bias, relu=False)
        np.testing.assert_allclose(out_k, out_r, rtol=1e-5, atol=1e-4)

    def test_forward_unaligned_batch(self):
        """batch % _TILE_B != 0: the pad rows must not leak into real
        outputs (B=13 above already covers this; pin B=1 too)."""
        table, idx, bottom, w, bias = _inputs(batch=1)
        out_k = fused_interaction(table, idx, bottom, w, bias, True,
                                  True)
        out_r = fused_interaction_reference(table, idx, bottom, w, bias)
        np.testing.assert_allclose(out_k, out_r, rtol=1e-5, atol=1e-4)

    def test_backward_all_inputs(self):
        """custom_vjp gradients (table scatter, bottom, first-layer
        weight/bias) match autodiff through the unfused oracle."""
        table, idx, bottom, w, bias = _inputs()

        def loss_k(t, b, w_, bi):
            return jnp.sum(
                fused_interaction(t, idx, b, w_, bi, True, True) ** 2)

        def loss_r(t, b, w_, bi):
            return jnp.sum(fused_interaction_reference(
                t, idx, b, w_, bi, relu=True) ** 2)

        g_k = jax.grad(loss_k, argnums=(0, 1, 2, 3))(table, bottom, w,
                                                     bias)
        g_r = jax.grad(loss_r, argnums=(0, 1, 2, 3))(table, bottom, w,
                                                     bias)
        for got, want, name in zip(g_k, g_r,
                                   ("table", "bottom", "w", "bias")):
            np.testing.assert_allclose(
                got, want, rtol=1e-5,
                atol=1e-5 * max(1.0, float(jnp.max(jnp.abs(want)))),
                err_msg=f"grad {name} diverged from the oracle")

    def test_supports_gate(self):
        assert supports(128) and supports(256)
        assert not supports(64) and not supports(130)
        table, idx, bottom, w, bias = _inputs()
        with pytest.raises(ValueError, match="dim % 128"):
            fused_interaction(table[:, :64], idx, bottom[:, :64],
                              w[:P + 64], bias, True, True)

    def test_scatter_tril_weight(self):
        """M's row i*Fp+j carries tril pair p(i, j); everything else is
        zero — vec(Z)·M == Z_tril·w_tril."""
        rng = np.random.RandomState(1)
        w_tril = jnp.asarray(rng.randn(P, H).astype(np.float32))
        m = scatter_tril_weight(w_tril, F)
        Fp = 8   # _pad_features(5)
        assert m.shape == (Fp * Fp, H)
        z = jnp.asarray(rng.randn(Fp, Fp).astype(np.float32))
        sel = np.array([i * Fp + j for i, j in tril_pairs(F)])
        np.testing.assert_allclose(
            z.reshape(-1) @ m, z.reshape(-1)[sel] @ w_tril,
            rtol=1e-5, atol=1e-5)
        with pytest.raises(ValueError, match="tril weight"):
            scatter_tril_weight(w_tril[:-1], F)


# =====================================================================
# the op wrapper + FLX515 (the audit that keeps the fusion honest)
# =====================================================================

OPCFG = DLRMConfig(embedding_size=[64] * 4, sparse_feature_size=128,
                   embedding_bag_size=2, mlp_bot=[8, 128],
                   mlp_top=[0, 32, 1], arch_interaction_op="dot")


def _op_model(ndev, interpret, batch=16):
    m = ff.FFModel(ff.FFConfig(batch_size=batch, seed=0))
    build_dlrm(m, OPCFG, fuse_interaction=True)
    fi = next(op for op in m.ops
              if type(op).__name__ == "FusedDotInteraction")
    fi._interpret = interpret
    m.compile(ff.SGDOptimizer(lr=0.05), "mean_squared_error", ["mse"],
              mesh=make_mesh(devices=jax.devices()[:ndev]))
    m.init_layers()
    return m, fi


class TestFusedDotInteractionOp:
    def test_graph_replaces_five_op_chain(self):
        m, fi = _op_model(1, False)
        names = {type(op).__name__ for op in m.ops}
        assert "FusedDotInteraction" in names
        assert "BatchMatmul" not in names
        assert fi.num_tables == 4 and fi.num_pairs == 10
        assert set(m.params[fi.name]) == {"table", "kernel", "bias"}

    def test_kernel_and_fallback_paths_agree(self):
        """Same seed -> same params: the interpreter-mode Pallas path
        and the unfused fallback produce the same forward (to float
        rounding) and both train."""
        m_ref, _ = _op_model(1, False)
        m_int, _ = _op_model(1, True)
        x, y = synthetic_batch(OPCFG, 16, seed=0)
        a = np.asarray(m_ref.forward_batch(dict(x)))
        b = np.asarray(m_int.forward_batch(dict(x)))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        x["label"] = y
        l_ref = float(m_ref.train_batch(dict(x))["loss"])
        l_int = float(m_int.train_batch(dict(x))["loss"])
        assert np.isfinite(l_ref) and np.isfinite(l_int)
        assert l_ref == pytest.approx(l_int, rel=1e-6)

    def test_multichip_mesh_trains_on_fallback(self):
        """Under an 8-device GSPMD mesh the op cannot call Pallas
        directly — the fallback path shards batch-DP and trains."""
        m, fi = _op_model(8, False)
        assert not fi._use_pallas()
        x, y = synthetic_batch(OPCFG, 16, seed=0)
        x["label"] = y
        assert np.isfinite(float(m.train_batch(dict(x))["loss"]))

    def test_build_dlrm_validation(self):
        with pytest.raises(ValueError, match="arch-interaction-op dot"):
            build_dlrm(ff.FFModel(ff.FFConfig(batch_size=16)),
                       DLRMConfig(embedding_size=[64] * 4,
                                  sparse_feature_size=128,
                                  mlp_bot=[8, 128], mlp_top=[0, 32, 1]),
                       fuse_interaction=True)
        with pytest.raises(ValueError, match="uniform table"):
            build_dlrm(ff.FFModel(ff.FFConfig(batch_size=16)),
                       DLRMConfig(embedding_size=[64, 32, 64, 64],
                                  sparse_feature_size=128,
                                  mlp_bot=[8, 128], mlp_top=[0, 32, 1],
                                  arch_interaction_op="dot"),
                       fuse_interaction=True)
        with pytest.raises(ValueError, match="top-MLP layer"):
            build_dlrm(ff.FFModel(ff.FFConfig(batch_size=16)),
                       DLRMConfig(embedding_size=[64] * 4,
                                  sparse_feature_size=128,
                                  mlp_bot=[8, 128], mlp_top=[0],
                                  arch_interaction_op="dot"),
                       fuse_interaction=True)


class TestFLX515:
    def test_fires_when_interaction_materializes(self):
        """The CPU fallback lowers the unfused chain: a rank-3
        [B, F, F] buffer appears in the serving HLO and the audit names
        the op that silently gave back the fusion."""
        m, fi = _op_model(1, False)
        out = audit_interaction_fusion(m)
        assert [f.rule for f in out] == ["FLX515"]
        assert out[0].scope == fi.name
        assert "pairwise-dot" in out[0].message

    def test_silent_when_fused(self):
        """The Pallas lowering (interpreter mode here) keeps Z in
        kernel scratch — no [B, F, F] buffer, no finding."""
        m, _ = _op_model(1, True)
        assert audit_interaction_fusion(m) == []

    def test_silent_without_fused_ops(self):
        """Models without FusedDotInteraction are out of scope — the
        default unfused graph materializes [B, F, F] BY DESIGN."""
        m = ff.FFModel(ff.FFConfig(batch_size=16, seed=0))
        build_dlrm(m, OPCFG)   # fuse_interaction off
        m.compile(ff.SGDOptimizer(lr=0.05), "mean_squared_error",
                  ["mse"], mesh=make_mesh(devices=jax.devices()[:1]))
        m.init_layers()
        assert audit_interaction_fusion(m) == []
