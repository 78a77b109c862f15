"""Fused supersteps (ISSUE 4): K training steps compiled into ONE
executable (a lax.scan over a stacked megabatch) so one dispatch trains
K steps, amortizing the per-step dispatch floor.

Pinned contracts (the ISSUE-4 acceptance criteria):

- `--superstep K` (K>1) on CPU is BIT-IDENTICAL to K=1 — params, opt
  state, and per-step metrics — for the same data order, including
  across a checkpoint save/resume and an anomaly ``skip_step``;
- checkpoints snap to superstep boundaries (``save_every % K != 0``
  is rejected loudly);
- ``rollback`` re-winds across a mid-superstep NaN; ``raise`` reports
  the faulting step index from the stacked flags;
- ``MeshDegraded`` at a superstep boundary recovers elastically and
  re-stages the megabatch on the shrunken mesh;
- host-resident-table models fall back to K=1 with a one-time warning;
- the SOAP cost model prices the amortized floor as
  ``per_step_overhead / K``.
"""

import logging
import os
import sys

import numpy as np
import pytest

import jax

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.core.model import AnomalyError, StagedStep
from dlrm_flexflow_tpu.data.prefetch import stack_batches
from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                           dlrm_strategy, synthetic_batch)
from dlrm_flexflow_tpu.parallel.mesh import make_mesh
from dlrm_flexflow_tpu.utils import faults

DCFG = DLRMConfig(embedding_size=[64] * 4, sparse_feature_size=8,
                  mlp_bot=[4, 16, 8], mlp_top=[40, 16, 1])
BS, NB = 16, 8


def _build(superstep=1, ndev=None, **cfg_kw):
    model = ff.FFModel(ff.FFConfig(batch_size=BS, seed=2,
                                   superstep=superstep, **cfg_kw))
    build_dlrm(model, DCFG)
    mesh = make_mesh(devices=jax.devices()[:ndev]) if ndev else None
    strat = dlrm_strategy(model, DCFG, ndev) if ndev else None
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"],
                  mesh=mesh, strategies=strat)
    model.init_layers()
    return model


def _dataset(seed=7):
    return synthetic_batch(DCFG, BS * NB, seed=seed)


def _batches(x, y):
    out = []
    for b in range(NB):
        sl = slice(b * BS, (b + 1) * BS)
        bb = {k: v[sl] for k, v in x.items()}
        bb["label"] = y[sl]
        out.append(bb)
    return out


def _params(model):
    return {f"{o}/{p}": np.asarray(v)
            for o, pd in model.params.items() for p, v in pd.items()}


def _opt(model):
    out = {}

    def walk(tree, prefix):
        if isinstance(tree, dict):
            for k, v in tree.items():
                walk(v, f"{prefix}{k}/")
        else:
            out[prefix.rstrip("/")] = np.asarray(tree)
    walk(model.opt_state, "")
    return out


def _assert_same_params(ma, mb, what="params"):
    pa, pb = _params(ma), _params(mb)
    assert set(pa) == set(pb)
    for name in pa:
        np.testing.assert_array_equal(
            pa[name], pb[name],
            err_msg=f"{name}: superstep run diverged ({what})")


def _capture(channel):
    """Handler-based capture (the ff.* loggers don't propagate to root,
    so pytest's caplog never sees them — same as test_resilience)."""
    records = []
    logger = logging.getLogger(f"ff.{channel}")

    class _H(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    h = _H()
    logger.addHandler(h)
    return records, lambda: logger.removeHandler(h)


# ---------------------------------------------------------------------
# bit-identity: K fused steps == K sequential steps
# ---------------------------------------------------------------------
class TestBitIdentical:
    def test_manual_drive_params_opt_and_per_step_metrics(self):
        x, y = _dataset()
        batches = _batches(x, y)
        m1, m4 = _build(1), _build(4)

        losses1 = [float(m1.train_batch(bb)["loss"]) for bb in batches]
        losses4 = []
        for g in range(0, NB, 4):
            mets = m4.train_superstep(batches[g:g + 4])
            assert mets["superstep"] == 4
            per = mets["per_step"]
            assert np.asarray(per["loss"]).shape == (4,)
            # scalar keys are the LAST fused step's values
            assert float(mets["loss"]) == float(np.asarray(per["loss"])[-1])
            losses4.extend(float(v) for v in np.asarray(per["loss"]))
        assert losses1 == losses4
        assert m1._step == m4._step == NB
        assert int(np.asarray(m4._step_dev)) == NB
        _assert_same_params(m1, m4)
        o1, o4 = _opt(m1), _opt(m4)
        assert set(o1) == set(o4)
        for name in o1:
            np.testing.assert_array_equal(o1[name], o4[name],
                                          err_msg=f"opt_state/{name}")
        # epoch metric sums accumulated inside the scan match too
        r1, r4 = m1.perf.report(), m4.perf.report()
        assert r1 == r4

    def test_fit_staged_path_bit_identical(self):
        x, y = _dataset()
        m1, m4 = _build(1), _build(4)
        m1.fit(x, y, epochs=2, verbose=False)
        m4.fit(x, y, epochs=2, verbose=False)
        _assert_same_params(m1, m4, "fit/staged")

    def test_fit_streamed_prefetch_path_bit_identical(self):
        x, y = _dataset()
        m1 = _build(1, stage_dataset="never")
        m4 = _build(4, stage_dataset="never")
        m1.fit(x, y, epochs=2, verbose=False)
        m4.fit(x, y, epochs=2, verbose=False)
        _assert_same_params(m1, m4, "fit/streamed")

    def test_unaligned_tail_falls_back_to_single_steps(self):
        # NB=8 batches with K=3: groups [0..3), [3..6), tail 6,7 at K=1
        x, y = _dataset()
        m1, m3 = _build(1), _build(3)
        m1.fit(x, y, epochs=1, verbose=False)
        m3.fit(x, y, epochs=1, verbose=False)
        assert m3._step == NB
        _assert_same_params(m1, m3, "tail")


# ---------------------------------------------------------------------
# config / resolution
# ---------------------------------------------------------------------
class TestResolve:
    def test_superstep_1_is_exact_legacy_path(self):
        x, y = _dataset()
        m = _build(1)
        assert m.resolve_superstep() == 1
        m.fit(x, y, epochs=1, verbose=False)
        assert not m._superstep_execs   # the fused executable never built

    def test_auto_picks_power_of_two(self):
        m = _build("auto")
        k = m.resolve_superstep()
        assert k in (1, 2, 4, 8, 16)
        # these tiny batches easily fit the host staging budget
        assert k == 16

    def test_auto_fit_shrinks_to_epoch_and_stays_bit_identical(self):
        # auto resolves 16 here but the epoch holds only NB=8 batches:
        # fit shrinks K to the largest power of two that fits
        x, y = _dataset()
        m1, ma = _build(1), _build("auto")
        m1.fit(x, y, epochs=1, verbose=False)
        ma.fit(x, y, epochs=1, verbose=False)
        assert ma._superstep_execs   # the fused path actually ran
        _assert_same_params(m1, ma, "auto")

    def test_cli_flag_parses(self):
        assert ff.FFConfig.parse_args(["--superstep", "8"]).superstep == 8
        assert ff.FFConfig.parse_args(
            ["--superstep", "auto"]).superstep == "auto"
        with pytest.raises(ValueError):
            ff.FFConfig.parse_args(["--superstep", "0"])
        with pytest.raises(ValueError):
            ff.FFConfig.parse_args(["--superstep", "fast"])

    def test_host_tables_fall_back_with_warning(self):
        records, undo = _capture("model")
        try:
            m = _build(4, host_resident_tables=True)
            assert m.resolve_superstep() == 1
            assert m.resolve_superstep() == 1   # warning is one-time
        finally:
            undo()
        warned = [r for r in records if "host-resident" in r
                  and "superstep=1" in r]
        assert len(warned) == 1, records
        # ... and fit still trains (as K=1)
        x, y = _dataset()
        m.fit(x, y, epochs=1, verbose=False)
        assert m._step == NB

    def test_stack_batches_rejects_ragged(self):
        with pytest.raises(ValueError, match="homogeneous"):
            stack_batches([{"x": np.zeros((2, 2))},
                           {"x": np.zeros((3, 2))}])
        with pytest.raises(ValueError, match="keys"):
            stack_batches([{"x": np.zeros(2)}, {"y": np.zeros(2)}])
        out = stack_batches([{"x": np.zeros((2, 2))}] * 3)
        assert out["x"].shape == (3, 2, 2)

    def test_staged_step_marks_megabatch(self):
        m = _build(4)
        x, y = _dataset()
        stacked = stack_batches(_batches(x, y)[:4])
        item = m._stage_superstep(stacked)
        assert isinstance(item, StagedStep) and item.k == 4
        assert item.host_idx is None
        assert item.device_batch["label"].shape[0] == 4


# ---------------------------------------------------------------------
# checkpoint boundaries
# ---------------------------------------------------------------------
class TestCheckpoints:
    def test_save_every_misaligned_rejected_loudly(self, tmp_path):
        x, y = _dataset()
        m = _build(4)
        with pytest.raises(ValueError, match="superstep"):
            m.fit(x, y, epochs=1, verbose=False,
                  checkpoint_dir=str(tmp_path), save_every=3)

    def test_save_resume_at_boundary_bit_identical(self, tmp_path):
        x, y = _dataset()
        ref = _build(1)
        ref.fit(x, y, epochs=2, verbose=False)

        ma = _build(4)
        ma.fit(x, y, epochs=1, verbose=False,
               checkpoint_dir=str(tmp_path), save_every=4)
        # snapshots landed on superstep boundaries only
        snaps = sorted(f for f in os.listdir(str(tmp_path))
                       if f.startswith("ckpt-") and f.endswith(".npz"))
        steps = [int(f[len("ckpt-"):-len(".npz")]) for f in snaps]
        assert steps and all(s % 4 == 0 for s in steps), steps

        mb = _build(4)
        mb.fit(x, y, epochs=2, verbose=False,
               checkpoint_dir=str(tmp_path), save_every=4)
        assert mb._step == 2 * NB
        _assert_same_params(ref, mb, "resume")


# ---------------------------------------------------------------------
# anomaly semantics inside / at the boundary of the scan
# ---------------------------------------------------------------------
class TestAnomalies:
    def test_skip_step_inside_scan_bit_identical(self):
        x, y = _dataset()
        with faults.active_plan(faults.FaultPlan(
                nan_grad_steps={5})) as plan:
            m4 = _build(4, anomaly_policy="skip_step")
            m4.fit(x, y, epochs=1, verbose=False)
        assert ("nan_grad", 5) in plan.fired
        with faults.active_plan(faults.FaultPlan(nan_grad_steps={5})):
            m1 = _build(1, anomaly_policy="skip_step")
            m1.fit(x, y, epochs=1, verbose=False)
        _assert_same_params(m1, m4, "skip_step")
        assert m4._step == NB

    def test_per_step_anomaly_flags_expose_faulting_step(self):
        x, y = _dataset()
        batches = _batches(x, y)
        m = _build(4, anomaly_policy="skip_step")
        with faults.active_plan(faults.FaultPlan(nan_grad_steps={2})):
            mets = m.train_superstep(batches[:4])
        flags = np.asarray(mets["per_step"]["anomaly"])
        assert flags.tolist() == [False, False, True, False]
        # the suppressed step's params stayed clean: the next superstep
        # trains normally with all flags clear
        mets = m.train_superstep(batches[4:8])
        assert not np.asarray(mets["per_step"]["anomaly"]).any()
        assert np.isfinite(np.asarray(mets["per_step"]["loss"])).all()

    def test_raise_reports_first_faulting_step_index(self):
        x, y = _dataset()
        batches = _batches(x, y)
        m = _build(4, anomaly_policy="raise")
        m.train_superstep(batches[:4])          # steps 0..3 clean
        with faults.active_plan(faults.FaultPlan(nan_grad_steps={6})):
            with pytest.raises(AnomalyError) as ei:
                m.train_superstep(batches[4:8])
        assert ei.value.step == 6
        # the K fused steps still committed (bad one suppressed on
        # device) — step accounting is at the boundary
        assert m._step == NB

    def test_rollback_rewinds_across_mid_superstep_nan(self, tmp_path):
        x, y = _dataset()
        clean = _build(1)
        clean.fit(x, y, epochs=1, verbose=False)

        def run_rollback(k, d):
            m = _build(k, anomaly_policy="rollback")
            with faults.active_plan(faults.FaultPlan(
                    nan_grad_steps={6})) as plan:
                res = m.fit(x, y, epochs=1, verbose=False,
                            checkpoint_dir=str(d), save_every=4)
            assert ("nan_grad", 6) in plan.fired
            assert res["rollbacks"] == 1
            assert m._step == NB
            return m

        m4 = run_rollback(4, tmp_path / "k4")
        m1 = run_rollback(1, tmp_path / "k1")
        # the mid-superstep NaN rolled back to the step-4 boundary
        # snapshot and re-trained 4..7 (the fault is consume-once):
        # bit-identical to the SAME recovery at K=1 ...
        _assert_same_params(m1, m4, "rollback")
        # ... and numerically the clean run (the restore's host
        # round-trip + re-put may cost an ulp vs never-restored state)
        pc, p4 = _params(clean), _params(m4)
        for name in pc:
            np.testing.assert_allclose(
                pc[name], p4[name], rtol=1e-5, atol=1e-7,
                err_msg=f"{name}: rollback diverged from the clean run")


# ---------------------------------------------------------------------
# elastic recovery at superstep boundaries
# ---------------------------------------------------------------------
class TestElasticBoundary:
    def test_mesh_degraded_in_window_recovers_and_restages(self):
        x, y = _dataset()
        m = _build(4, ndev=8, elastic="inplace", elastic_search_budget=0)
        # device loss scheduled MID-window (step 5): surfaces at the
        # superstep boundary BEFORE dispatch, recovery re-stages the
        # megabatches on the shrunken mesh and every batch still trains
        # exactly once
        with faults.active_plan(faults.FaultPlan(
                drop_device_steps={5: 6})) as plan:
            res = m.fit(x, y, epochs=1, verbose=False)
        assert ("drop_device", (5, 6)) in plan.fired
        assert res["recoveries"] == 1
        assert m.mesh.size == 2
        assert m._step == NB
        assert np.isfinite(float(res["metrics"].get("mse", 0.0)))

    def test_elastic_off_propagates_from_boundary(self):
        x, y = _dataset()
        m = _build(4, ndev=8)
        from dlrm_flexflow_tpu.parallel.distributed import MeshDegraded
        with faults.active_plan(faults.FaultPlan(
                drop_device_steps={4: 2})):
            with pytest.raises(MeshDegraded):
                m.fit(x, y, epochs=1, verbose=False)


# ---------------------------------------------------------------------
# eval-path AOT executable cache (satellite)
# ---------------------------------------------------------------------
class TestEvalCache:
    def test_forward_batch_caches_one_executable_per_shape(self):
        x, y = _dataset()
        m = _build(1)
        probe = {k: v[:BS] for k, v in x.items()}
        r1 = np.asarray(m.forward_batch(probe))
        r2 = np.asarray(m.forward_batch(probe))
        np.testing.assert_array_equal(r1, r2)
        assert len(m._eval_step_execs) == 1
        # a second shape compiles its own entry, the first stays cached
        # (an MLP graph — the DLRM interaction bakes its batch dim)
        mlp = ff.FFModel(ff.FFConfig(batch_size=8, seed=1))
        xt = mlp.create_tensor((8, 4), name="x")
        mlp.dense(mlp.dense(xt, 8, activation="relu", name="fc1"),
                  1, name="fc2")
        mlp.compile(ff.SGDOptimizer(0.1), "mean_squared_error", ["mse"])
        mlp.init_layers()
        r = np.random.RandomState(0)
        mlp.forward_batch({"x": r.rand(8, 4).astype(np.float32)})
        mlp.forward_batch({"x": r.rand(16, 4).astype(np.float32)})
        mlp.forward_batch({"x": r.rand(8, 4).astype(np.float32)})
        assert len(mlp._eval_step_execs) == 2

    def test_recompile_drops_stale_eval_executables(self):
        x, y = _dataset()
        m = _build(1)
        m.forward_batch({k: v[:BS] for k, v in x.items()})
        assert m._eval_step_execs
        m.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"])
        assert not m._eval_step_execs


# ---------------------------------------------------------------------
# cost model / simulator pricing (satellite)
# ---------------------------------------------------------------------
class TestCostModel:
    def test_amortized_overhead_is_floor_over_k(self):
        from dlrm_flexflow_tpu.search.cost_model import (
            MEASURED_DISPATCH_FLOOR_S, TPUSpec)
        spec = TPUSpec()
        assert spec.per_step_overhead_s == MEASURED_DISPATCH_FLOOR_S
        assert (spec.per_step_overhead_amortized(8)
                == spec.per_step_overhead_s / 8)
        assert (spec.per_step_overhead_amortized(1)
                == spec.per_step_overhead_s)

    def test_simulator_prices_per_step_overhead_over_k(self):
        from dlrm_flexflow_tpu.search.mcmc import default_strategy
        from dlrm_flexflow_tpu.search.simulator import Simulator
        m1, m4 = _build(1), _build(4)
        strat = default_strategy(m1, 1)
        s1 = Simulator(m1).simulate(strat, 1)
        s4 = Simulator(m4).simulate(strat, 1)
        ov = Simulator(m1).cost.spec.per_step_overhead_s
        assert s1 - s4 == pytest.approx(ov * (1 - 1 / 4), rel=1e-9)


# ---------------------------------------------------------------------
# bench + profiling helpers (satellites)
# ---------------------------------------------------------------------
class TestBenchAndProfiling:
    def test_fit_dispatch_floor_recovers_exact_line(self):
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks"))
        from bench_superstep import fit_dispatch_floor
        floor, t_dev = 0.55, 1.1
        per_k = {k: t_dev + floor / k for k in (1, 2, 4, 8, 16)}
        f, t = fit_dispatch_floor(per_k)
        assert f == pytest.approx(floor, rel=1e-6)
        assert t == pytest.approx(t_dev, rel=1e-6)
        with pytest.raises(ValueError):
            fit_dispatch_floor({1: 1.0})

    def test_superstep_span_carries_k(self):
        """One span API (obs.trace.span): the fused dispatch's span says
        which step it starts at and how many it trains, so a trace reader
        can divide it; it runs inside `train/dispatch`; the gated
        StepTraceAnnotation helper is gone."""
        from dlrm_flexflow_tpu.obs import trace
        from dlrm_flexflow_tpu.utils import profiling
        assert not hasattr(profiling, "superstep_annotation")
        model = _build(4)
        x, y = _dataset()
        with trace.override(True):
            trace.clear()
            model.fit(x, y, epochs=1, verbose=False)
            evs = trace.events()
            trace.clear()
        fused = [e for e in evs if e["name"] == "train/superstep"]
        assert [e["args"] for e in fused] == [
            {"step_num": 0, "superstep": 4}, {"step_num": 4, "superstep": 4}]
        outer = [e for e in evs if e["name"] == "train/dispatch"]
        assert len(outer) == len(fused)
        for o, f in zip(outer, fused):
            assert o["ts"] <= f["ts"]
            assert f["ts"] + f["dur"] <= o["ts"] + o["dur"]
