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

    def test_auto_is_the_default_and_resolves_one_until_a_verdict(self):
        # "auto" means: what fit() would run NOW. Before its probe has
        # a verdict that is the per-step path; with a host-paced one
        # kept for the batch size, the staging rule's power of two
        from dlrm_flexflow_tpu.core.model import PROBE_DISPATCHES, _Pace
        assert ff.FFConfig().superstep == "auto"
        m = _build("auto")
        assert m.resolve_superstep() == 1
        pace = m._pace["a shape"] = _Pace(BS)
        for i in range(PROBE_DISPATCHES):
            assert m.resolve_superstep() == 1     # the probe still runs
            pace.note(True)
        # these tiny batches easily fit the host staging budget
        assert m.resolve_superstep() == 16
        assert m.resolve_superstep(BS * 2) == 1   # another shape's verdict
        busy = _build("auto")
        busy._pace["a shape"] = _Pace(BS)
        for i in range(PROBE_DISPATCHES):
            busy._pace["a shape"].note(i % 2 == 0)
        assert busy._pace["a shape"].host_paced is False
        assert busy.resolve_superstep() == 1

    def test_auto_fit_of_a_short_epoch_stays_per_step_bit_identical(self):
        # the epoch holds NB=8 batches, fewer than the probe: auto is the
        # per-step path, program for program, whatever the device answers
        x, y = _dataset()
        m1, ma = _build(1), _build("auto")
        _answering(ma, True)
        m1.fit(x, y, epochs=1, verbose=False)
        res = ma.fit(x, y, epochs=1, verbose=False)
        assert not ma._superstep_execs   # the fused executable never built
        assert res["superstep"] == 1 and res["fused_steps"] == 0
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
# "auto": fit() asks who sets the pace, and fuses where the host does
# ---------------------------------------------------------------------
NBA = 75            # batches an epoch: the probe, fused groups, a tail


def _long_dataset(rem=0, seed=11):
    return synthetic_batch(DCFG, BS * NBA + rem, seed=seed)


def _answering(model, idle):
    """The probe's one seam: `idle` answers every "was the device idle
    at this dispatch" (a bool, or a callable of the question's number)."""
    asked = []

    def answer(vector):
        asked.append(vector)
        return idle(len(asked) - 1) if callable(idle) else idle

    model._idle_at_dispatch = answer
    return asked


def _snapshot_steps(directory):
    return sorted(int(f[len("ckpt-"):-len(".npz")])
                  for f in os.listdir(str(directory))
                  if f.startswith("ckpt-") and f.endswith(".npz"))


def _mlp(superstep, optimizer):
    m = ff.FFModel(ff.FFConfig(batch_size=BS, seed=3, superstep=superstep))
    xt = m.create_tensor((BS, 6), name="x")
    m.dense(m.dense(xt, 16, activation="relu", name="fc1"), 1, name="fc2")
    m.compile(optimizer, "mean_squared_error", ["mse"])
    m.init_layers()
    return m


class _Compiles:
    """Programs XLA builds while the block runs, as perfbench/run.py
    counts them (jax.monitoring)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        import jax.monitoring as mon
        self.built = 0
        self._on = True
        mon.register_event_duration_secs_listener(self._duration)
        return self

    def _duration(self, event, _secs, **_kw):
        self.built += self._on and event == self.EVENT

    def __exit__(self, *exc):
        self._on = False
        from jax._src import monitoring
        drop = getattr(monitoring,
                       "_unregister_event_duration_listener_by_callback",
                       None)
        if drop is not None:
            drop(self._duration)
        return False


class TestAuto:
    @pytest.mark.parametrize("graph", ["dlrm_sgd", "mlp_adam"])
    def test_host_paced_verdict_in_mid_epoch_is_bit_identical(self, graph):
        """The probe ends inside epoch 0 (its 32nd answer comes as the
        33rd dispatch is about to be issued), fit goes on in supersteps
        of 16 from there: batches 0..31 single, 32..63 fused, the tail
        64..74 and the remainder single; epoch 1 fused from its first
        batch. Same params, optimizer state and metric sums as per-step
        fit."""
        from dlrm_flexflow_tpu.core.model import PROBE_DISPATCHES
        if graph == "dlrm_sgd":
            x, y = _long_dataset()          # its interaction bakes the
            build = _build                  # batch: no remainder trains
        else:
            r = np.random.RandomState(5)
            n = BS * NBA + 5                # a remainder of 5 that trains
            x = {"x": r.rand(n, 6).astype(np.float32)}
            y = r.rand(n, 1).astype(np.float32)

            def build(k):
                return _mlp(k, ff.AdamOptimizer(alpha=0.01))
        m1, ma = build(1), build("auto")
        asked = _answering(ma, True)
        r1 = m1.fit(x, y, epochs=2, verbose=False)
        ra = ma.fit(x, y, epochs=2, verbose=False)
        assert len(asked) == PROBE_DISPATCHES
        assert ra["idle_at_dispatch_share"] == 1.0
        assert ra["superstep"] == 16
        assert ra["fused_steps"] == 2 * 16 + 4 * 16
        assert ma._step == m1._step
        assert r1["superstep"] == 1 and r1["fused_steps"] == 0
        assert r1["idle_at_dispatch_share"] is None
        _assert_same_params(m1, ma, "auto, host-paced")
        o1, oa = _opt(m1), _opt(ma)
        assert set(o1) == set(oa)
        for name in o1:
            np.testing.assert_array_equal(o1[name], oa[name],
                                          err_msg=f"opt_state/{name}")
        assert r1["metrics"] == ra["metrics"]
        assert r1["num_samples"] == ra["num_samples"]

    def test_device_paced_verdict_builds_no_fused_program(self):
        from dlrm_flexflow_tpu.obs import trace
        x, y = _long_dataset()
        m = _build("auto")
        # only the dispatch after a drain finds the chip idle
        asked = _answering(m, lambda i: i % 16 == 0)
        before = [p for p in trace.programs() if p.kind == "superstep"]
        res = m.fit(x, y, epochs=2, verbose=False)
        assert len(asked) == 32 and res["idle_at_dispatch_share"] == 2 / 32
        assert res["superstep"] == 1 and res["fused_steps"] == 0
        assert not m._superstep_execs
        assert [p for p in trace.programs()
                if p.kind == "superstep"] == before
        # the verdict is kept: a later fit asks nothing
        m.fit(x, y, epochs=1, verbose=False)
        assert len(asked) == 32 and not m._superstep_execs

    def test_the_verdict_is_kept_and_a_second_fit_builds_nothing(self):
        x, y = _long_dataset()
        m1, ma = _build(1), _build("auto")
        asked = _answering(ma, True)
        ma.fit(x, y, epochs=1, verbose=False)
        assert len(ma._superstep_execs) == 1
        with _Compiles() as compiles:
            res = ma.fit(x, y, epochs=1, verbose=False)
        assert compiles.built == 0
        assert len(asked) == 32             # no second probe
        assert res["superstep"] == 16 and res["fused_steps"] == 64
        assert res["idle_at_dispatch_share"] == 1.0
        assert len(ma._superstep_execs) == 1
        assert len(ma._train_step_execs) == 1
        m1.fit(x, y, epochs=2, verbose=False)
        _assert_same_params(m1, ma, "a fit that starts fused")

    def test_the_probe_counts_across_epochs_and_fits(self):
        # 8 batches an epoch: seven questions an epoch (none at its first
        # dispatch: whatever ended the epoch before may have drained the
        # queue); the verdict comes in the fifth epoch, over two fits,
        # and such an epoch never fuses
        x, y = _dataset()
        m = _build("auto")
        asked = _answering(m, True)
        res = m.fit(x, y, epochs=2, verbose=False)
        assert len(asked) == 14 and res["idle_at_dispatch_share"] == 1.0
        (pace,) = m._pace.values()
        assert pace.host_paced is None
        res = m.fit(x, y, epochs=4, verbose=False)
        assert len(asked) == 32 and pace.host_paced is True
        assert res["superstep"] == 1 and not m._superstep_execs
        # the simulator is told what a long enough epoch would run
        assert m.resolve_superstep() == 16

    def test_the_probe_asks_a_quarter_of_the_throttle_back(self,
                                                           monkeypatch):
        """On a TPU the throttle lets 32 steps run ahead and the probe
        asks about the step issued 8 dispatches ago (the host learns of
        a finished step late): the first answer comes with an epoch's
        ninth dispatch, the 32nd with the 41st, and fit goes on in
        supersteps from the next boundary of K: batches 0..47 single,
        48..63 fused, the tail single; epoch 1 fused from its start."""
        from dlrm_flexflow_tpu.core import model as model_mod
        assert model_mod._Throttle().lag == 1       # the CPU's bound is 1

        class Deep(model_mod._Throttle):
            """Holds 32 vectors as on a TPU, with none of them in flight:
            XLA's CPU client has aborted under a loaded host with 32
            steps queued (why the CPU's bound is 1)."""
            bound = property(lambda self: 32, lambda self, _: None)

            def __call__(self, mets):
                jax.block_until_ready(mets.vector)
                return super().__call__(mets)

        monkeypatch.setattr(model_mod, "_Throttle", Deep)
        assert Deep().lag == 8
        x, y = _long_dataset()
        m1, ma = _build(1), _build("auto")
        issued = []
        dispatch = ma.train_batch_staged

        def counted(staged, **kw):
            mets = dispatch(staged, **kw)
            issued.append(mets.vector)
            return mets

        ma.train_batch_staged = counted
        asked = []

        def answer(vector):
            # the question is about the step issued 8 dispatches ago
            asked.append(len(issued))
            assert vector is issued[-8]
            return True

        ma._idle_at_dispatch = answer
        res = ma.fit(x, y, epochs=2, verbose=False)
        assert asked == list(range(8, 40))
        assert res["superstep"] == 16 and res["fused_steps"] == 16 + 64
        m1.fit(x, y, epochs=2, verbose=False)
        _assert_same_params(m1, ma, "auto, a lag of 8")

    @pytest.mark.parametrize("save_every,k", [(24, 8), (7, 1), (0, 16)])
    def test_auto_never_refuses_a_save_every(self, tmp_path, save_every,
                                             k):
        x, y = _long_dataset()
        m1, ma = _build(1), _build("auto")
        _answering(ma, True)
        res = ma.fit(x, y, epochs=1, verbose=False,
                     checkpoint_dir=str(tmp_path), save_every=save_every,
                     keep_last=99)
        assert res["superstep"] == k
        assert bool(ma._superstep_execs) == (k > 1)
        if save_every:
            steps = _snapshot_steps(tmp_path)
            assert steps == sorted(
                set(range(save_every, NBA, save_every)) | {NBA})
        m1.fit(x, y, epochs=1, verbose=False)
        _assert_same_params(m1, ma, f"auto, save_every={save_every}")

    @pytest.mark.parametrize("case,nb,k,fused", [
        # 75 steps an epoch: after epoch 0 no group of 8, 4 or 2 starts
        # on a multiple of itself, so two epochs run per step
        ("two_epochs_with_a_tail", 75, 1, 0),
        # 72 steps an epoch: every epoch starts on a multiple of 8
        ("two_epochs_that_align", 72, 8, 40 + 72),
        # an earlier fit left the model at step 75: groups of 8 on the
        # batch index would start at steps 75, 83, ... and pass over 96
        ("after_an_earlier_fit", 75, 1, 0),
        # the same with 76 steps behind it: groups of 4 do start on
        # multiples of 4
        ("after_an_earlier_fit_of_76", 76, 4, 76),
    ])
    def test_auto_loses_no_snapshot(self, tmp_path, case, nb, k, fused):
        """`save_every` 24 under a host-paced verdict: the snapshots are
        those of the per-step run, step for step, whatever step the fit
        starts from and however many epochs it runs."""
        x, y = synthetic_batch(DCFG, BS * nb, seed=11)
        kw = dict(verbose=False, save_every=24, keep_last=99)
        runs = {}
        for name, m in (("per_step", _build(1)), ("auto", _build("auto"))):
            _answering(m, True)
            d = str(tmp_path / name)
            if case.startswith("after"):
                m.fit(x, y, epochs=1, verbose=False)
                if name == "auto":      # the verdict is in: from batch 0
                    assert m._superstep_execs and m._step == nb
                res = m.fit(x, y, epochs=1, checkpoint_dir=d, **kw)
            else:
                res = m.fit(x, y, epochs=2, checkpoint_dir=d, **kw)
            runs[name] = (m, res, _snapshot_steps(d))
        (m1, _, want), (ma, res, got) = runs["per_step"], runs["auto"]
        assert want == sorted(set(range(24, 2 * nb, 24)) - (
            set(range(nb)) if case.startswith("after") else set())
            | {2 * nb})
        assert got == want
        assert res["superstep"] == k and res["fused_steps"] == fused
        _assert_same_params(m1, ma, case)

    @pytest.mark.parametrize("nb,k,fused", [(75, 1, 0), (72, 8, 40)])
    def test_auto_loses_no_snapshot_after_a_resume(self, tmp_path, nb, k,
                                                   fused):
        """A run that ended after one epoch is taken up by a new process
        for a second: at step 75 no K fits; at step 72 groups of 8 do,
        and the probe (batches 1..32 of the epoch) goes on fused from
        batch 32."""
        x, y = synthetic_batch(DCFG, BS * nb, seed=11)
        kw = dict(verbose=False, save_every=24, keep_last=99)
        got = {}
        for name, superstep in (("per_step", 1), ("auto", "auto")):
            d = str(tmp_path / name)
            _build(superstep).fit(x, y, epochs=1, checkpoint_dir=d, **kw)
            m = _build(superstep)
            _answering(m, True)
            res = m.fit(x, y, epochs=2, checkpoint_dir=d, **kw)
            assert m._step == 2 * nb
            got[name] = (m, res, _snapshot_steps(d))
        assert got["auto"][2] == got["per_step"][2] == sorted(
            set(range(24, 2 * nb, 24)) | {nb, 2 * nb})
        assert got["auto"][1]["superstep"] == k
        assert got["auto"][1]["fused_steps"] == fused
        _assert_same_params(got["per_step"][0], got["auto"][0], "resume")

    def test_a_step_off_the_groups_goes_on_per_step(self, tmp_path):
        """What `_auto_superstep` cannot foresee (a remainder dropped in
        mid-run, a rollback to another run's snapshot) the loop sees: a
        group that would start off a multiple of K is not fused."""
        x, y = _long_dataset()
        m1, ma = _build(1), _build("auto")
        _answering(ma, True)
        ma._auto_superstep = lambda *a, **kw: 8     # foresees nothing
        records, undo = _capture("model")
        try:
            res = ma.fit(x, y, epochs=2, verbose=False, save_every=24,
                         checkpoint_dir=str(tmp_path), keep_last=99)
        finally:
            undo()
        # epoch 0: batches 32..71 fused; epoch 1 starts at step 75
        assert res["fused_steps"] == 40 and res["superstep"] == 1
        assert [r for r in records if "no multiple of the superstep" in r]
        assert _snapshot_steps(tmp_path) == [24, 48, 72, 96, 120, 144, 150]
        m1.fit(x, y, epochs=2, verbose=False)
        _assert_same_params(m1, ma, "a step off the groups")

    def test_the_probe_skips_a_dispatch_that_waits_for_its_input(self):
        """A loop that waits for the staging thread finds the device
        idle too; such a dispatch is not asked about."""
        from dlrm_flexflow_tpu.data.feed import BatchFeed
        x, y = _long_dataset()
        m = _build("auto", stage_dataset="never")
        asked = _answering(m, True)
        at_hand = BatchFeed.at_hand
        try:
            BatchFeed.at_hand = lambda self: False
            res = m.fit(x, y, epochs=1, verbose=False)
        finally:
            BatchFeed.at_hand = at_hand
        assert not asked and res["idle_at_dispatch_share"] is None
        assert res["superstep"] == 1 and not m._superstep_execs

    def test_an_explicit_k_is_honoured_without_a_probe(self):
        x, y = _long_dataset()
        m = _build(4)
        asked = _answering(m, True)
        res = m.fit(x, y, epochs=1, verbose=False)
        assert not asked and not m._pace
        assert res["superstep"] == 4 and res["fused_steps"] == 72
        assert res["idle_at_dispatch_share"] is None

    def test_host_resident_tables_stay_per_step(self):
        records, undo = _capture("model")
        try:
            m = _build("auto", host_resident_tables=True)
            asked = _answering(m, True)
            x, y = _long_dataset()
            res = m.fit(x, y, epochs=1, verbose=False)
        finally:
            undo()
        assert not asked and res["superstep"] == 1
        assert res["idle_at_dispatch_share"] is None
        assert not m._superstep_execs and m._step == NBA
        # nobody asked for a K: nothing to warn about
        assert not [r for r in records if "host-resident" in r
                    and "superstep" in r], records

    def test_simulator_prices_the_kept_verdict(self):
        from dlrm_flexflow_tpu.search.mcmc import default_strategy
        from dlrm_flexflow_tpu.search.simulator import Simulator
        x, y = _long_dataset()
        m1, ma = _build(1), _build("auto")
        strat = default_strategy(m1, 1)
        s1 = Simulator(m1).simulate(strat, 1)
        # a model that never trained is priced at the whole floor
        assert Simulator(ma).simulate(strat, 1) == s1
        _answering(ma, True)
        ma.fit(x, y, epochs=1, verbose=False)
        ov = Simulator(m1).cost.spec.per_step_overhead_s
        assert s1 - Simulator(ma).simulate(strat, 1) == pytest.approx(
            ov * (1 - 1 / 16), rel=1e-9)

    def test_obs_gauges_say_what_fit_did(self):
        from dlrm_flexflow_tpu.obs import metrics as obsm
        x, y = _long_dataset()
        m = _build("auto")
        _answering(m, True)
        with obsm.override(True):
            m.fit(x, y, epochs=1, verbose=False)
            text = obsm.registry().prometheus_text()
        assert f'ff_fit_superstep_k{{shape="{BS}"}} 16' in text
        assert f'ff_fit_idle_at_dispatch_share{{shape="{BS}"}} 1' in text
        assert 'ff_fit_steps_total{path="fused"} 32' in text
        assert 'ff_fit_steps_total{path="single"} 43' in text


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
