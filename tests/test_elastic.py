"""Elastic-mesh recovery tests (ISSUE 3 acceptance criteria).

Everything runs on the 8-device virtual CPU mesh: device loss is
fault-injected (`FaultPlan.drop_device_steps` — the runtime's view of the
mesh shrinks while the devices stay physically alive, exactly how a TPU
preemption looks from the surviving hosts), worker stalls are injected
sleeps, and collective hangs are a stalled probe thread.

Pinned contracts:

- a CPU-mesh fit() with an injected device drop at step k resumes on the
  shrunken mesh and reaches BIT-IDENTICAL parameters/loss to a
  from-scratch run on that mesh restored from the same snapshot;
- a stalled scatter worker / staging thread is detected within the
  configured deadline and recovery (not a hang) follows;
- a checkpoint written under an 8-device mesh restores onto 4 and 2
  devices with params/opt-state allclose after the round-trip, and is
  rejected-with-reason when elastic mode is off.
"""

import os
import time

import numpy as np
import pytest

import jax

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                           dlrm_strategy, synthetic_batch)
from dlrm_flexflow_tpu.parallel.distributed import (MeshDegraded,
                                                    ParticipantRegistry,
                                                    probe_mesh)
from dlrm_flexflow_tpu.parallel.elastic import recover, surviving_devices
from dlrm_flexflow_tpu.parallel.mesh import make_mesh
from dlrm_flexflow_tpu.search.replan import (clamp_strategies,
                                             replan_strategies)
from dlrm_flexflow_tpu.utils import faults
from dlrm_flexflow_tpu.utils.checkpoint import restore_checkpoint
from dlrm_flexflow_tpu.utils.watchdog import StallReport, WorkerStalled

DCFG = DLRMConfig(embedding_size=[64] * 4, sparse_feature_size=8,
                  mlp_bot=[4, 16, 8], mlp_top=[40, 16, 1])
BS, NB = 16, 8


def _dataset(seed=7):
    return synthetic_batch(DCFG, BS * NB, seed=seed)


def _build(ndev, strategies=None, **cfg_kw):
    cfg = ff.FFConfig(batch_size=BS, seed=2, **cfg_kw)
    model = ff.FFModel(cfg)
    build_dlrm(model, DCFG)
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", ["mse"],
                  mesh=make_mesh(devices=jax.devices()[:ndev]),
                  strategies=strategies or dlrm_strategy(model, DCFG, ndev))
    model.init_layers()
    return model


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


# ---------------------------------------------------------------------
# detection: typed errors instead of hangs
# ---------------------------------------------------------------------
class TestDetection:
    def test_participant_registry_flags_missed_heartbeats(self):
        reg = ParticipantRegistry(["host0", "host1", "host2"],
                                  deadline_s=0.15)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.3:
            reg.heartbeat("host0")
            reg.heartbeat("host1")   # host2 never beats again
            time.sleep(0.02)
        with pytest.raises(MeshDegraded) as ei:
            reg.check()
        assert ei.value.lost == ["host2"]
        assert set(ei.value.surviving) == {"host0", "host1"}

    def test_registry_mark_dead_is_immediate(self):
        reg = ParticipantRegistry(["a", "b"], deadline_s=60.0)
        reg.mark_dead("b")
        assert reg.dead() == ["b"]

    def test_probe_mesh_healthy(self):
        mesh = make_mesh(devices=jax.devices()[:4])
        latency = probe_mesh(mesh, deadline_s=30.0)
        assert 0 <= latency < 30.0

    def test_probe_mesh_stalled_collective_hits_deadline(self):
        mesh = make_mesh(devices=jax.devices()[:2])
        probe_mesh(mesh, deadline_s=30.0)   # warm the jit outside fault
        with faults.active_plan(faults.FaultPlan(
                stall_s={"collective": 30.0})):
            t0 = time.monotonic()
            with pytest.raises(MeshDegraded) as ei:
                probe_mesh(mesh, deadline_s=0.3)
            waited = time.monotonic() - t0
        assert waited < 5.0, "watchdog must fire at the deadline, not " \
            "wait out the stall"
        assert ei.value.report is not None
        assert ei.value.report.worker == "ff-mesh-probe"

    def test_injected_drop_raises_typed_error_before_dispatch(self):
        model = _build(8)
        x, y = _dataset()
        batch = {k: v[:BS] for k, v in x.items()}
        batch["label"] = y[:BS]
        model.train_batch(batch)
        step_before = model._step
        with faults.active_plan(faults.FaultPlan(
                drop_device_steps={step_before: 2})):
            with pytest.raises(MeshDegraded) as ei:
                model.train_batch(batch)
        assert len(ei.value.lost) == 2
        assert len(ei.value.surviving) == 6
        # raised BEFORE dispatch: no optimizer step was applied
        assert model._step == step_before


# ---------------------------------------------------------------------
# re-planning
# ---------------------------------------------------------------------
class TestReplan:
    def test_clamp_projects_degrees_onto_smaller_mesh(self):
        model = _build(8)
        clamped = clamp_strategies(model, model.strategies, 4)
        for name, pc in clamped.items():
            for d in pc.degrees:
                assert d <= 4
        # still covers every non-input op
        from dlrm_flexflow_tpu.core.op import InputOp
        ops = {op.name for op in model.ops
               if not isinstance(op, InputOp)}
        assert ops <= set(clamped)

    def test_clamped_strategies_are_assignable(self):
        from dlrm_flexflow_tpu.parallel.mesh import structural_axis_sizes
        from dlrm_flexflow_tpu.parallel.sharding import assignable
        model = _build(8)
        for ndev in (6, 4, 3, 2, 1):
            axes = structural_axis_sizes(ndev)
            for name, pc in clamp_strategies(
                    model, model.strategies, ndev).items():
                assert assignable(pc.degrees, axes), (name, pc.degrees,
                                                      ndev)

    def test_replan_is_deterministic(self):
        model = _build(8)
        s1, i1 = replan_strategies(model, 4, budget=20, seed=3)
        s2, i2 = replan_strategies(model, 4, budget=20, seed=3)
        assert s1 == s2
        assert i1["searched"] and i2["searched"]

    def test_zero_budget_is_greedy_fallback(self):
        model = _build(8)
        strat, info = replan_strategies(model, 4, budget=0)
        assert info["greedy_fallback"] and not info["searched"]
        assert strat == clamp_strategies(model, model.strategies, 4)


# ---------------------------------------------------------------------
# checkpoint resharding (8 -> 4 -> 2) + reject-with-reason
# ---------------------------------------------------------------------
class TestCheckpointReshard:
    @pytest.fixture(scope="class")
    def snapshot(self, tmp_path_factory):
        d = tmp_path_factory.mktemp("reshard")
        model = _build(8)
        x, y = _dataset()
        for b in range(3):
            batch = {k: v[b * BS:(b + 1) * BS] for k, v in x.items()}
            batch["label"] = y[b * BS:(b + 1) * BS]
            model.train_batch(batch)
        path = str(d / "ck.npz")
        ff.save_checkpoint(model, path)
        return path, _params(model), _opt(model), int(model._step)

    def test_mesh_mismatch_rejected_with_reason_when_elastic_off(
            self, snapshot):
        path, _, _, _ = snapshot
        model4 = _build(4)   # elastic defaults to "off"
        before = _params(model4)
        with pytest.raises(ValueError, match="8-device mesh.*elastic"):
            restore_checkpoint(model4, path)
        # rejected UP FRONT: nothing was half-applied mid-load
        after = _params(model4)
        for k in before:
            np.testing.assert_array_equal(before[k], after[k])

    @pytest.mark.parametrize("ndev", [4, 2])
    def test_restores_onto_smaller_mesh_allclose(self, snapshot, ndev):
        path, ref_p, ref_o, ref_step = snapshot
        model = _build(ndev, elastic="resume")
        restore_checkpoint(model, path)
        assert model._step == ref_step
        got_p, got_o = _params(model), _opt(model)
        assert set(got_p) == set(ref_p)
        for k in ref_p:
            np.testing.assert_allclose(got_p[k], ref_p[k], err_msg=k)
        assert set(got_o) == set(ref_o)
        for k in ref_o:
            np.testing.assert_allclose(got_o[k], ref_o[k], err_msg=k)
        # and the restored model actually trains on the smaller mesh
        x, y = _dataset()
        batch = {k: v[:BS] for k, v in x.items()}
        batch["label"] = y[:BS]
        assert np.isfinite(float(model.train_batch(batch)["loss"]))

    def test_explicit_elastic_argument_overrides_config(self, snapshot):
        path, ref_p, _, _ = snapshot
        model = _build(2)   # config elastic="off"
        restore_checkpoint(model, path, elastic=True)
        got = _params(model)
        for k in ref_p:
            np.testing.assert_allclose(got[k], ref_p[k], err_msg=k)

    def test_manifest_records_mesh_and_degrees(self, tmp_path):
        model = _build(8)
        mgr = ff.CheckpointManager(str(tmp_path), keep_last=2)
        mgr.save(model, {"epoch": 0, "batch": 0})
        entry = mgr.entries()[-1]
        mesh = entry["mesh"]
        assert mesh["num_devices"] == 8
        assert list(mesh["axes"].values()) == [2, 2, 2]
        assert set(mesh["degrees"]) == set(model.strategies)
        for name, degs in mesh["degrees"].items():
            assert degs == list(model.strategies[name].degrees)


# ---------------------------------------------------------------------
# recover(): the orchestrated verb
# ---------------------------------------------------------------------
class TestRecover:
    def test_inplace_recovery_preserves_state_and_trains(self):
        model = _build(8, elastic="inplace", elastic_search_budget=0)
        x, y = _dataset()
        batch = {k: v[:BS] for k, v in x.items()}
        batch["label"] = y[:BS]
        model.train_batch(batch)
        ref = _params(model)
        step = model._step
        devs = list(model.mesh.devices.flat)
        report = recover(model, lost=devs[4:], mode="inplace")
        assert report.surviving == 4
        assert report.mode == "inplace"
        assert model.mesh.size == 4
        assert model._step == step
        got = _params(model)
        for k in ref:
            np.testing.assert_allclose(got[k], ref[k], err_msg=k)
        assert np.isfinite(float(model.train_batch(batch)["loss"]))

    def test_recover_requires_survivors(self):
        model = _build(2, elastic="inplace")
        devs = list(model.mesh.devices.flat)
        with pytest.raises(MeshDegraded, match="no surviving"):
            recover(model, lost=devs, mode="inplace")

    def test_recover_mode_off_rejected(self):
        model = _build(2)
        with pytest.raises(ValueError, match="resume.*inplace"):
            recover(model, lost=[], mode="off")

    def test_resume_without_manager_rejected(self):
        model = _build(2, elastic="resume")
        with pytest.raises(ValueError, match="CheckpointManager"):
            recover(model, lost=[], mode="resume")

    def test_surviving_devices_helper(self):
        mesh = make_mesh(devices=jax.devices()[:4])
        devs = list(mesh.devices.flat)
        assert surviving_devices(mesh, devs[2:]) == devs[:2]
        assert surviving_devices(mesh, []) == devs


# ---------------------------------------------------------------------
# the acceptance run: drop at step k mid-fit -> bit-identical to a
# from-scratch run on the shrunken mesh from the same snapshot
# ---------------------------------------------------------------------
class TestElasticFit:
    def test_drop_mid_fit_bit_identical_to_fresh_run_on_shrunk_mesh(
            self, tmp_path):
        x, y = _dataset()
        k, drop = 4, 4   # lose 4 of 8 devices just before step 4

        # run A: elastic fit; snapshot every 2 steps, drop at step k
        mA = _build(8, elastic="resume", elastic_search_budget=0)
        with faults.active_plan(faults.FaultPlan(
                drop_device_steps={k: drop})) as plan:
            res = mA.fit(x, y, epochs=1, verbose=False,
                         checkpoint_dir=str(tmp_path), save_every=2,
                         keep_last=50)
        assert res["recoveries"] == 1
        assert ("drop_device", (k, drop)) in plan.fired
        assert mA.mesh.size == 8 - drop

        # run B: a FRESH job on the shrunken mesh, restored from the
        # very snapshot recovery used, trained over the same remaining
        # batches. The re-plan is deterministic, so an independent
        # caller reproduces recovery's exact strategy map.
        planner = _build(8)
        stratB, _ = replan_strategies(
            planner, 8 - drop, old=dlrm_strategy(planner, DCFG, 8),
            budget=0)
        mB = _build(8 - drop, strategies=stratB, elastic="resume")
        snap = str(tmp_path / f"ckpt-{k:08d}.npz")
        assert os.path.exists(snap), sorted(os.listdir(str(tmp_path)))
        restore_checkpoint(mB, snap)
        assert mB._step == k
        for b in range(k, NB):
            batch = {kk: v[b * BS:(b + 1) * BS] for kk, v in x.items()}
            batch["label"] = y[b * BS:(b + 1) * BS]
            metsB = mB.train_batch(batch)

        pA, pB = _params(mA), _params(mB)
        assert set(pA) == set(pB)
        for name in pA:
            np.testing.assert_array_equal(
                pA[name], pB[name],
                err_msg=f"{name}: elastic-recovered run diverged from "
                f"the from-scratch shrunken-mesh run")
        # ... and the models compute bit-identical losses/predictions
        assert np.isfinite(float(metsB["loss"]))
        probe = {kk: v[:BS] for kk, v in x.items()}
        np.testing.assert_array_equal(
            np.asarray(mA.forward_batch(probe)),
            np.asarray(mB.forward_batch(probe)))

    def test_elastic_off_propagates(self, tmp_path):
        x, y = _dataset()
        m = _build(8)   # elastic off
        with faults.active_plan(faults.FaultPlan(
                drop_device_steps={2: 4})):
            with pytest.raises(MeshDegraded):
                m.fit(x, y, epochs=1, verbose=False,
                      checkpoint_dir=str(tmp_path), save_every=2)

    def test_inplace_fit_recovers_without_checkpoints(self):
        x, y = _dataset()
        m = _build(8, elastic="inplace", elastic_search_budget=0)
        with faults.active_plan(faults.FaultPlan(
                drop_device_steps={3: 6})):
            res = m.fit(x, y, epochs=1, verbose=False)
        assert res["recoveries"] == 1
        assert m.mesh.size == 2
        # every batch trained exactly once: nothing lost, nothing redone
        assert m._step == NB
        assert np.isfinite(float(res["metrics"].get("mse", 0.0)))

    def test_recovery_cap_re_raises(self, tmp_path):
        x, y = _dataset()
        m = _build(8, elastic="resume", elastic_search_budget=0,
                   max_recoveries=1)
        with faults.active_plan(faults.FaultPlan(
                drop_device_steps={2: 2, 3: 2})):
            with pytest.raises(MeshDegraded):
                m.fit(x, y, epochs=1, verbose=False,
                      checkpoint_dir=str(tmp_path), save_every=1,
                      keep_last=50)


# ---------------------------------------------------------------------
# worker watchdogs: stalls are detected within the deadline and
# recovered from — never a hang
# ---------------------------------------------------------------------
class TestWatchdogs:
    def test_stalled_scatter_worker_detected_and_recovered(self, tmp_path):
        x, y = _dataset()
        deadline = 0.4
        m = _build(8, elastic="resume", elastic_search_budget=0,
                   host_resident_tables=True, host_tables_async=True,
                   worker_deadline_s=deadline)
        t0 = time.monotonic()
        with faults.active_plan(faults.FaultPlan(
                stall_s={"scatter": 30.0})) as plan:
            res = m.fit(x, y, epochs=1, verbose=False,
                        checkpoint_dir=str(tmp_path), save_every=2,
                        keep_last=10)
        elapsed = time.monotonic() - t0
        assert ("stall", ("scatter", 30.0)) in plan.fired
        assert res["recoveries"] >= 1
        # detection within the deadline (+ generous slack for the
        # recovery itself), NOT the 30s the worker is wedged for
        assert elapsed < 20.0
        assert np.isfinite(float(res["metrics"].get("mse", 0.0)))

    def test_host_drain_raises_typed_stall_report(self):
        m = _build(4, host_resident_tables=True, host_tables_async=True,
                   worker_deadline_s=0.2)
        x, y = _dataset()
        batch = {k: v[:BS] for k, v in x.items()}
        batch["label"] = y[:BS]
        with faults.active_plan(faults.FaultPlan(
                stall_s={"scatter": 10.0})):
            m.train_batch(batch)   # launches the (stalling) worker
            with pytest.raises(WorkerStalled) as ei:
                m._host_drain()
        rep = ei.value.report
        assert rep.worker == "ff-scatter"
        assert rep.deadline_s == 0.2
        assert rep.alive
        m._host_abandon()   # leave no wedged worker behind for teardown

    def test_stalled_prefetch_ring_raises_within_deadline(self):
        from dlrm_flexflow_tpu.data.prefetch import PrefetchPipeline
        with faults.active_plan(faults.FaultPlan(
                stall_s={"prefetch": 30.0})):
            pipe = PrefetchPipeline(lambda i: i, depth=2, num_items=4,
                                    deadline_s=0.25)
            t0 = time.monotonic()
            with pytest.raises(WorkerStalled) as ei:
                pipe.get()
            waited = time.monotonic() - t0
            pipe.close(join_timeout_s=0.1)
        assert waited < 5.0
        assert ei.value.report.worker.startswith("ff-prefetch-")
        assert "staged item 0" in ei.value.report.waiting_for

    def test_prefetch_without_deadline_still_blocks_normally(self):
        from dlrm_flexflow_tpu.data.prefetch import PrefetchPipeline
        pipe = PrefetchPipeline(lambda i: i * 10, depth=2, num_items=3)
        assert [pipe.get() for _ in range(3)] == [0, 10, 20]
        pipe.close()

    def test_background_threads_are_named_and_daemon(self, tmp_path):
        import threading
        from dlrm_flexflow_tpu.data.prefetch import PrefetchPipeline
        pipe = PrefetchPipeline(lambda i: i, depth=1, num_items=2)
        names = {t.name for t in threading.enumerate()}
        assert any(n.startswith("ff-prefetch-") for n in names)
        assert pipe._thread.daemon
        pipe.close()
        m = _build(2)
        mgr = ff.CheckpointManager(str(tmp_path), keep_last=1)
        mgr.save_async(m)
        assert mgr._thread.name == "ff-ckpt-writer"
        assert mgr._thread.daemon
        mgr.wait()

    def test_stall_report_format_names_worker_and_deadline(self):
        rep = StallReport(worker="ff-scatter", waiting_for="x",
                          waited_s=1.5, deadline_s=1.0, detail="step 3")
        s = str(rep)
        assert "ff-scatter" in s and "1.5" in s and "step 3" in s


# ---------------------------------------------------------------------
# fault-plan env parsing (satellite: typos warn, new keys parse)
# ---------------------------------------------------------------------
class TestFaultEnv:
    def _with_env(self, monkeypatch, **kv):
        for k, v in kv.items():
            monkeypatch.setenv(k, v)

    def test_unknown_key_warns(self, monkeypatch):
        import logging
        self._with_env(monkeypatch, FF_FAULT_NAN_STEP="3")   # typo'd

        records = []

        class _Capture(logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        h = _Capture()
        faults.log_faults.addHandler(h)   # the ff.* root does not
        # propagate to logging's root, so caplog can't see it
        try:
            plan = faults.plan_from_env()
        finally:
            faults.log_faults.removeHandler(h)
        assert plan is None   # the typo'd key injects nothing...
        assert any("FF_FAULT_NAN_STEP" in m for m in records), \
            "...but it must WARN instead of silently ignoring"

    def test_drop_device_env_forms(self, monkeypatch):
        self._with_env(monkeypatch, FF_FAULT_DROP_DEVICE="5:2,9")
        plan = faults.plan_from_env()
        assert plan.drop_device_steps == {5: 2, 9: 1}

    def test_stall_collective_env(self, monkeypatch):
        self._with_env(monkeypatch, FF_FAULT_STALL_COLLECTIVE="1.5")
        plan = faults.plan_from_env()
        assert plan.stall_s == {"collective": 1.5}

    def test_drop_device_hook_consume_once(self):
        with faults.active_plan(faults.FaultPlan(
                drop_device_steps={3: 2})):
            assert faults.take_drop_device(2) == 0
            assert faults.take_drop_device(3) == 2
            assert faults.take_drop_device(3) == 0   # consumed

    def test_return_device_env_forms(self, monkeypatch):
        self._with_env(monkeypatch, FF_FAULT_RETURN_DEVICE="6:2,9")
        plan = faults.plan_from_env()
        assert plan.return_device_steps == {6: 2, 9: 1}

    def test_return_device_bad_value_names_variable(self, monkeypatch):
        self._with_env(monkeypatch, FF_FAULT_RETURN_DEVICE="6:x")
        with pytest.raises(ValueError, match="FF_FAULT_RETURN_DEVICE"):
            faults.plan_from_env()

    def test_return_device_hook_consume_once(self):
        with faults.active_plan(faults.FaultPlan(
                return_device_steps={4: 2})):
            assert faults.take_return_device(3) == 0
            assert faults.take_return_device(4) == 2
            assert faults.take_return_device(4) == 0   # consumed

    def test_cache_corrupt_env(self, monkeypatch):
        self._with_env(monkeypatch, FF_FAULT_CACHE_CORRUPT="2")
        plan = faults.plan_from_env()
        assert plan.corrupt_cache_entries == 2

    def test_cache_corrupt_bad_value_names_variable(self, monkeypatch):
        self._with_env(monkeypatch, FF_FAULT_CACHE_CORRUPT="two")
        with pytest.raises(ValueError, match="FF_FAULT_CACHE_CORRUPT"):
            faults.plan_from_env()

    def test_cache_corrupt_hook_truncates_budgeted(self, tmp_path):
        p = tmp_path / "entry.bin"
        p.write_bytes(b"x" * 4096)
        with faults.active_plan(faults.FaultPlan(
                corrupt_cache_entries=1)):
            assert faults.maybe_corrupt_cache(str(p)) is True
            assert p.stat().st_size < 4096
            # budget consumed: a second read is untouched
            p.write_bytes(b"y" * 4096)
            assert faults.maybe_corrupt_cache(str(p)) is False
            assert p.stat().st_size == 4096

    def test_cache_corrupt_missing_file_keeps_budget(self, tmp_path):
        with faults.active_plan(faults.FaultPlan(
                corrupt_cache_entries=1)):
            assert faults.maybe_corrupt_cache(
                str(tmp_path / "nope.bin")) is False
            p = tmp_path / "real.bin"
            p.write_bytes(b"x" * 4096)
            assert faults.maybe_corrupt_cache(str(p)) is True


# ---------------------------------------------------------------------
# scale-UP: expand() — the inverse of recover()
# ---------------------------------------------------------------------
class TestExpand:
    def test_expand_grows_mesh_and_preserves_state(self):
        from dlrm_flexflow_tpu.parallel.elastic import expand
        model = _build(8, elastic="inplace", elastic_search_budget=0)
        x, y = _dataset()
        batch = {k: v[:BS] for k, v in x.items()}
        batch["label"] = y[:BS]
        model.train_batch(batch)
        devs = list(model.mesh.devices.flat)
        recover(model, lost=devs[4:], mode="inplace")
        assert model.mesh.size == 4
        ref = _params(model)
        step = model._step
        returned = [d for d in jax.devices() if d.id >= 4][:4]
        report = expand(model, returned=returned, mode="inplace")
        assert report.kind == "expand"
        assert report.surviving == 8
        assert model.mesh.size == 8
        assert model._step == step
        got = _params(model)
        for k in ref:
            np.testing.assert_allclose(got[k], ref[k], err_msg=k)
        assert np.isfinite(float(model.train_batch(batch)["loss"]))

    def test_expand_restores_remembered_pre_shrink_plan(self):
        from dlrm_flexflow_tpu.parallel.elastic import expand
        model = _build(8, elastic="inplace", elastic_search_budget=0)
        before = {k: pc.degrees for k, pc in model.strategies.items()}
        devs = list(model.mesh.devices.flat)
        recover(model, lost=devs[4:], mode="inplace")
        expand(model, returned=devs[4:], mode="inplace")
        after = {k: pc.degrees for k, pc in model.strategies.items()}
        for k in after:   # lowering-relevant intent restored exactly
            assert after[k] == before[k], (k, before[k], after[k])

    def test_expand_requires_fresh_devices(self):
        from dlrm_flexflow_tpu.parallel.elastic import expand
        model = _build(4, elastic="inplace")
        with pytest.raises(ValueError, match="returned device"):
            expand(model, returned=[], mode="inplace")
        with pytest.raises(ValueError, match="returned device"):
            # devices already in the mesh are not growth
            expand(model, returned=list(model.mesh.devices.flat),
                   mode="inplace")

    def test_expand_mode_off_rejected(self):
        from dlrm_flexflow_tpu.parallel.elastic import expand
        model = _build(2)
        with pytest.raises(ValueError, match="resume.*inplace"):
            expand(model, returned=jax.devices()[2:4], mode="off")

    def test_expand_canonical_device_order(self):
        # losing the MIDDLE of the mesh then expanding must rebuild the
        # same device order a fresh full-mesh job would use
        from dlrm_flexflow_tpu.parallel.elastic import expand
        model = _build(8, elastic="inplace", elastic_search_budget=0)
        devs = list(model.mesh.devices.flat)
        recover(model, lost=devs[2:6], mode="inplace")
        expand(model, returned=devs[2:6], mode="inplace")
        got = [d.id for d in model.mesh.devices.flat]
        fresh = [d.id for d in
                 _build(8).mesh.devices.flat]
        assert got == fresh

    def test_fit_drop_then_expand_bit_identical_to_fresh_full_mesh_run(
            self, tmp_path):
        """THE acceptance pin: shrink at step j, expand at step k — the
        post-expansion trajectory is bit-identical to a fresh run on the
        full mesh restored from the same snapshot the expansion used."""
        x, y = _dataset()
        j, k, drop = 2, 5, 4

        mA = _build(8, elastic="resume", elastic_search_budget=0,
                    elastic_expand=True)
        with faults.active_plan(faults.FaultPlan(
                drop_device_steps={j: drop},
                return_device_steps={k: drop})) as plan:
            res = mA.fit(x, y, epochs=1, verbose=False,
                         checkpoint_dir=str(tmp_path), save_every=1,
                         keep_last=50)
        assert res["recoveries"] == 1
        assert res["expansions"] == 1
        assert ("return_device", (k, drop)) in plan.fired
        assert mA.mesh.size == 8

        # run B: fresh 8-device job restored from the very snapshot the
        # expansion resumed from, trained over the same remaining batches
        mB = _build(8, elastic="resume")
        snap = str(tmp_path / f"ckpt-{k:08d}.npz")
        assert os.path.exists(snap), sorted(os.listdir(str(tmp_path)))
        restore_checkpoint(mB, snap)
        assert mB._step == k
        for b in range(k, NB):
            batch = {kk: v[b * BS:(b + 1) * BS] for kk, v in x.items()}
            batch["label"] = y[b * BS:(b + 1) * BS]
            mB.train_batch(batch)

        pA, pB = _params(mA), _params(mB)
        assert set(pA) == set(pB)
        for name in pA:
            np.testing.assert_array_equal(
                pA[name], pB[name],
                err_msg=f"{name}: drop-then-expand run diverged from "
                f"the fresh full-mesh run from the same snapshot")
        probe = {kk: v[:BS] for kk, v in x.items()}
        np.testing.assert_array_equal(
            np.asarray(mA.forward_batch(probe)),
            np.asarray(mB.forward_batch(probe)))

    def test_fit_expand_disabled_ignores_return_hook(self):
        # without --elastic-expand the return hook must not consume or
        # raise: the run completes on the shrunken... full mesh (no drop
        # here), and the budget is still intact afterwards
        x, y = _dataset()
        m = _build(8, elastic="inplace", elastic_search_budget=0)
        with faults.active_plan(faults.FaultPlan(
                return_device_steps={3: 2})) as plan:
            res = m.fit(x, y, epochs=1, verbose=False)
        assert res["expansions"] == 0
        assert plan.return_device_steps == {3: 2}   # not consumed
        assert not any(h == "return_device" for h, _ in plan.fired)


# ---------------------------------------------------------------------
# persistent warm caches: plan + compile (utils/warmcache.py)
# ---------------------------------------------------------------------
class TestWarmCaches:
    def test_recover_plan_cache_hit_reproduces_searched_plan(
            self, tmp_path):
        from dlrm_flexflow_tpu.utils.warmcache import PlanCache
        cache = PlanCache(str(tmp_path))

        def run():
            m = _build(8, elastic="inplace")
            m.attach_plan_cache(cache)
            devs = list(m.mesh.devices.flat)
            return recover(m, lost=devs[4:], mode="inplace", budget=10,
                           seed=3)

        cold = run()
        warm = run()
        assert not cold.plan_cache_hit
        assert warm.plan_cache_hit
        assert warm.searched == cold.searched
        # the cached plan IS the plan the search produced
        assert {k: (pc.degrees, pc.param_degree)
                for k, pc in warm.strategies.items()} \
            == {k: (pc.degrees, pc.param_degree)
                for k, pc in cold.strategies.items()}
        assert cache.stats()["hits"] == 1

    def test_corrupt_plan_cache_degrades_to_fresh_search(self, tmp_path):
        from dlrm_flexflow_tpu.utils.warmcache import PlanCache
        cache = PlanCache(str(tmp_path))
        m = _build(8, elastic="inplace")
        m.attach_plan_cache(cache)
        devs = list(m.mesh.devices.flat)
        recover(m, lost=devs[4:], mode="inplace", budget=0)
        m2 = _build(8, elastic="inplace")
        m2.attach_plan_cache(cache)
        with faults.active_plan(faults.FaultPlan(
                corrupt_cache_entries=1)) as plan:
            rep = recover(m2, lost=list(m2.mesh.devices.flat)[4:],
                          mode="inplace", budget=0)
        assert ("cache_corrupt" in {h for h, _ in plan.fired})
        assert not rep.plan_cache_hit        # torn file = clean miss
        assert m2.mesh.size == 4             # recovery still succeeded
        assert cache.stats()["rejects"] >= 1

    def test_compile_cache_roundtrip_bit_identical(self, tmp_path):
        from dlrm_flexflow_tpu.utils.warmcache import CompileCache
        x, y = _dataset()
        batch = {k: v[:BS] for k, v in x.items()}
        batch["label"] = y[:BS]

        def run(attach):
            m = _build(4)
            if attach:
                m.attach_compile_cache(CompileCache(str(tmp_path)))
            for _ in range(2):
                mets = m.train_batch(batch)
            return _params(m), m

        ref, _ = run(False)
        cold, m_cold = run(True)
        st = m_cold.compile_cache_stats()
        assert st["puts"] >= 1
        warm, m_warm = run(True)
        st = m_warm.compile_cache_stats()
        assert st["hits"] >= 1, st
        for k in ref:   # cached executable computes the same bits
            np.testing.assert_array_equal(ref[k], cold[k], err_msg=k)
            np.testing.assert_array_equal(ref[k], warm[k], err_msg=k)

    def test_corrupt_compile_cache_degrades_to_fresh_compile(
            self, tmp_path):
        from dlrm_flexflow_tpu.utils.warmcache import CompileCache
        x, y = _dataset()
        batch = {k: v[:BS] for k, v in x.items()}
        batch["label"] = y[:BS]
        m = _build(4)
        m.attach_compile_cache(CompileCache(str(tmp_path)))
        ref = np.asarray(m.train_batch(batch)["loss"])
        m2 = _build(4)
        cache2 = CompileCache(str(tmp_path))
        m2.attach_compile_cache(cache2)
        with faults.active_plan(faults.FaultPlan(
                corrupt_cache_entries=16)):
            got = np.asarray(m2.train_batch(batch)["loss"])
        np.testing.assert_array_equal(ref, got)
        st = cache2.stats()
        assert st["rejects"] >= 1 and st["hits"] == 0
        assert "unreadable" in st["last_reject"]

    def test_stale_code_fingerprint_is_a_miss(self, tmp_path):
        from dlrm_flexflow_tpu.utils.warmcache import CompileCache
        x, y = _dataset()
        batch = {k: v[:BS] for k, v in x.items()}
        batch["label"] = y[:BS]
        m = _build(4)
        m.attach_compile_cache(CompileCache(str(tmp_path)))
        m.train_batch(batch)
        # a "new checkout": the code fingerprint is part of the key, so
        # old entries are clean misses — never loaded, never trusted
        stale = CompileCache(str(tmp_path))
        stale._code_fp = "deadbeef00000000"
        m2 = _build(4)
        m2.attach_compile_cache(stale)
        m2.train_batch(batch)
        st = stale.stats()
        assert st["hits"] == 0 and st["misses"] >= 1
        assert st["puts"] >= 1   # re-stored under the new fingerprint

    def test_tampered_entry_code_field_rejected(self, tmp_path):
        # defense in depth: an entry whose FILE claims a different code
        # fingerprint than its key (tampering, a renamed file, a hash
        # collision) is rejected with a reason, not deserialized
        import pickle
        from dlrm_flexflow_tpu.utils.warmcache import CompileCache
        import jax.numpy as jnp
        cache = CompileCache(str(tmp_path))
        co = jax.jit(lambda v: v + 1).lower(jnp.ones((2,))).compile()
        key = "fmt=1|kind=t|code=x|strat=s|mesh=m|shape=(2,)"
        assert cache.put(key, co)
        path = cache._path(key)
        blob = pickle.load(open(path, "rb"))
        blob["code"] = "deadbeef00000000"
        pickle.dump(blob, open(path, "wb"))
        assert cache.get(key, jax.devices()[:1]) is None
        assert "stale code fingerprint" in cache.stats()["last_reject"]

    def test_fit_auto_attaches_caches_next_to_manifest(self, tmp_path):
        x, y = _dataset()
        m = _build(4, compile_cache_dir="auto")
        m.fit(x, y, epochs=1, verbose=False,
              checkpoint_dir=str(tmp_path), save_every=4)
        cache_dir = tmp_path / "cache"
        assert cache_dir.is_dir()
        assert getattr(m, "_compile_cache", None) is not None
        assert getattr(m, "_plan_cache", None) is not None
        assert m.compile_cache_stats()["puts"] >= 1
        assert any(f.startswith("exec-") for f in os.listdir(cache_dir))

    def test_no_cache_dir_configured_stays_cold(self, tmp_path):
        x, y = _dataset()
        m = _build(4)   # compile_cache_dir defaults to "" = off
        m.fit(x, y, epochs=1, verbose=False,
              checkpoint_dir=str(tmp_path), save_every=4)
        assert getattr(m, "_compile_cache", None) is None
        assert not (tmp_path / "cache").exists()
