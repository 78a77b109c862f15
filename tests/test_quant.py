"""Quantized embedding storage (ISSUE 14): int8/fp8 rows with row-wise
scales as a per-table policy.

Pinned contracts (the acceptance bar):

- the row-wise symmetric codec round-trips its CODES bit-exactly
  (re-quantizing a dequantized payload is idempotent) and its npz
  encoding is lossless — the property that lets fp32 arrays flow
  between subsystems while quantized storage stays bit-exact;
- ``master_weight`` training is BIT-IDENTICAL to the fp32-accumulator
  reference across the matrix: int8/fp8 x SGD/momentum/Adam x
  replicated/row-sharded/hybrid x superstep K=4 — the policy is pure
  metadata until a storage boundary;
- ``stochastic_rounding`` stores exact fixed points of the codec after
  EVERY update (device, row-sharded, and host-resident paths), is
  deterministic per seed, and stays within tolerance of fp32 training;
- the Pallas gather dequantizes in-kernel (scales beside the row
  tiles) and matches the dequantized-gather oracle;
- delta publishes ship codes + scales (~4x smaller), round-trip
  bit-exactly, and a corrupted scale is a reject-with-reason
  (``FF_FAULT_QUANT_SCALE``), never served;
- ``EmbeddingCache`` hits return the same dequantized rows as the miss
  that filled them; the shard tier stores quantized blocks (~4x rows
  per MB), ships quantized payloads, dequantizes at the ranker, and
  its warm cache round-trips codes + scales bit-exactly;
- every byte-accounting surface (``hbm_footprint_report``, all-to-all
  payloads, ``serving_footprint``) prices int8 tables >= 3.5x smaller
  than fp32, and shardcheck FLX508 flags strategy-vs-manifest policy
  disagreement.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.models.dlrm import (DLRMConfig, build_dlrm,
                                           synthetic_batch)
from dlrm_flexflow_tpu.parallel import strategy_io
from dlrm_flexflow_tpu.parallel.mesh import make_mesh
from dlrm_flexflow_tpu.parallel.pconfig import ParallelConfig
from dlrm_flexflow_tpu.quant import (QuantPolicy, dequantize_rows_np,
                                     decode_q, encode_q, fake_quant,
                                     fake_quant_np,
                                     fake_quant_stochastic,
                                     quantize_rows_np, validate_scales)
from dlrm_flexflow_tpu.quant.policy import (effective_policy,
                                            table_storage_bytes)
from dlrm_flexflow_tpu.quant.store import QuantTable
from dlrm_flexflow_tpu.utils import faults

# small/fast graph for pure-training matrices
DCFG = DLRMConfig(embedding_size=[64] * 4, sparse_feature_size=8,
                  mlp_bot=[4, 16, 8], mlp_top=[40, 16, 1])
# wide-row graph for byte-ratio contracts (the >=3.5x bar needs
# dim large enough that the per-row fp32 scale amortizes: d=64 ->
# 256 B fp32 vs 68 B int8 = 3.76x)
WCFG = DLRMConfig(embedding_size=[256] * 4, sparse_feature_size=64,
                  mlp_bot=[4, 16, 64], mlp_top=[320, 16, 1])
BS = 16


def _opt(name):
    if name == "adam":
        return ff.AdamOptimizer(alpha=0.05)
    if name == "momentum":
        return ff.SGDOptimizer(lr=0.05, momentum=0.9)
    return ff.SGDOptimizer(lr=0.05)


def _build(dcfg=DCFG, opt="sgd", ndev=1, pd=1, hot=0.0, seed=3,
           strategies=None, **cfg_kw):
    model = ff.FFModel(ff.FFConfig(batch_size=BS, seed=seed, **cfg_kw))
    build_dlrm(model, dcfg)
    if pd > 1 and strategies is None:
        strategies = {}
        for op in model.ops:
            tn = type(op).__name__
            nd = op.outputs[0].num_dims if op.outputs else 0
            if tn in ("EmbeddingBagStacked", "EmbeddingBagConcat",
                      "Embedding"):
                strategies[op.name] = ParallelConfig(
                    (ndev,) + (1,) * (nd - 1), param_degree=pd,
                    hot_fraction=hot)
            elif nd:
                strategies[op.name] = ParallelConfig.data_parallel(
                    nd, ndev)
    mesh = make_mesh(devices=jax.devices()[:ndev]) if ndev > 1 else None
    model.compile(_opt(opt), "mean_squared_error", ["mse"], mesh=mesh,
                  strategies=strategies)
    model.init_layers()
    return model


def _all_params(model):
    return {f"{o}/{p}": np.asarray(v)
            for o, d in model.params.items() for p, v in d.items()}


def _emb_names(model):
    return [op.name for op in model.ops if hasattr(op, "host_lookup")]


def _fit(model, dcfg, epochs=1, n=64):
    x, y = synthetic_batch(dcfg, n, seed=0)
    model.fit(x, y, epochs=epochs, verbose=False)
    return model


# ---------------------------------------------------------------------
# policy + codec
# ---------------------------------------------------------------------
class TestPolicyCodec:
    def test_policy_validation(self):
        with pytest.raises(ValueError, match="quant dtype"):
            QuantPolicy("int4")
        with pytest.raises(ValueError, match="update rule"):
            QuantPolicy("int8", "nearest")
        with pytest.raises(ValueError, match="scale layout"):
            QuantPolicy("int8", scale_block="tensor")
        p = QuantPolicy("int8")
        assert p.is_quantized and p.itemsize == 1.0
        assert not QuantPolicy("bf16").is_quantized

    def test_pconfig_vocab_matches_policy_vocab(self):
        """pconfig keeps inline literals (import-cycle-free); they must
        agree with the quant package's vocabulary."""
        from dlrm_flexflow_tpu.quant.policy import DTYPES, UPDATE_RULES
        for dt in DTYPES:
            ParallelConfig((1,), quant_dtype=dt)
        for ur in UPDATE_RULES:
            ParallelConfig((1,), quant_dtype="int8", quant_update=ur)
        with pytest.raises(ValueError):
            ParallelConfig((1,), quant_dtype="int4")
        with pytest.raises(ValueError):
            ParallelConfig((1,), quant_update="master_weight")

    @pytest.mark.parametrize("dt", ["int8", "fp8"])
    def test_codes_idempotent_and_npz_portable(self, dt):
        rng = np.random.RandomState(0)
        x = rng.randn(128, 16).astype(np.float32) * 3
        x[5] = 0.0                              # all-zero row
        q, s = quantize_rows_np(x, dt)
        d = dequantize_rows_np(q, s, dt)
        q2, s2 = quantize_rows_np(d, dt)
        assert np.array_equal(np.asarray(q2, np.float32),
                              np.asarray(q, np.float32))
        assert np.array_equal(s2, s)
        r = decode_q(encode_q(q, dt), dt)
        assert np.array_equal(np.asarray(r, np.float32),
                              np.asarray(q, np.float32))
        # fake_quant is a projection: f(f(x)) == f(x)
        f1 = fake_quant_np(x, dt)
        assert np.array_equal(fake_quant_np(f1, dt), f1)

    def test_jnp_matches_numpy(self):
        rng = np.random.RandomState(1)
        x = rng.randn(32, 8).astype(np.float32)
        got = np.asarray(fake_quant(jnp.asarray(x), "int8"))
        want = fake_quant_np(x, "int8")
        assert np.allclose(got, want, atol=1e-6)

    def test_stochastic_rounding_unbiased_and_deterministic(self):
        rng = np.random.RandomState(2)
        x = rng.randn(64, 16).astype(np.float32)
        k = jax.random.PRNGKey(7)
        a = np.asarray(fake_quant_stochastic(jnp.asarray(x), "int8", k))
        b = np.asarray(fake_quant_stochastic(jnp.asarray(x), "int8", k))
        assert np.array_equal(a, b)          # deterministic per key
        # unbiased: averaged over many keys the SR image approaches x
        acc = np.zeros_like(x)
        for i in range(64):
            acc += np.asarray(fake_quant_stochastic(
                jnp.asarray(x), "int8", jax.random.PRNGKey(i)))
        q, s = quantize_rows_np(x, "int8")
        step = s[:, None] + 1e-12            # one code width per row
        assert np.abs(acc / 64 - x).max() < 0.3 * step.max() + 0.05

    def test_validate_scales_rejects_garbage(self):
        validate_scales("k", np.asarray([0.1, 0.2], np.float32))
        with pytest.raises(ValueError, match="non-finite"):
            validate_scales("k", np.asarray([0.1, np.inf], np.float32))
        with pytest.raises(ValueError, match="negative"):
            validate_scales("k", np.asarray([-0.1], np.float32))
        with pytest.raises(ValueError, match="exceeds the publish-time"):
            validate_scales("k", np.asarray([10.0], np.float32),
                            bound=1.0)

    def test_table_storage_bytes(self):
        p8 = QuantPolicy("int8")
        assert table_storage_bytes((256, 64), p8) == 256 * 68
        assert table_storage_bytes((4, 256, 64), p8) == 4 * 256 * 68
        assert table_storage_bytes((256, 64), QuantPolicy()) \
            == 256 * 64 * 4


# ---------------------------------------------------------------------
# strategy-file round trip + validation
# ---------------------------------------------------------------------
class TestStrategyIOQuant:
    MAP = {"embedding0": ParallelConfig(
               (8, 1, 1), param_degree=4, quant_dtype="int8",
               quant_update="stochastic_rounding"),
           "embedding1": ParallelConfig((8, 1, 1), quant_dtype="fp8"),
           "linear_0": ParallelConfig((8, 1))}

    @pytest.mark.parametrize("ext", [".json", ".pb"])
    def test_round_trip(self, tmp_path, ext):
        p = str(tmp_path / f"s{ext}")
        strategy_io.save_strategies(p, self.MAP)
        assert strategy_io.load_strategies(p) == self.MAP

    @pytest.mark.parametrize("ext", [".json", ".pb"])
    def test_legacy_files_byte_identical(self, tmp_path, ext):
        """A map with no quant fields encodes exactly as before the
        fields existed (fields 9/10 / json keys omitted when unset)."""
        legacy = {"embedding0": ParallelConfig((8, 1, 1), param_degree=4),
                  "linear_0": ParallelConfig((8, 1))}
        p1 = str(tmp_path / f"a{ext}")
        strategy_io.save_strategies(p1, legacy)
        blob = open(p1, "rb").read()
        assert b"quant" not in blob
        if ext == ".pb":
            assert b"\x48" not in _pb_field_keys(blob)
        assert strategy_io.load_strategies(p1) == legacy

    def test_validation_rejects_quant_on_non_embedding(self):
        bad = {"linear_0": ParallelConfig((8, 1), quant_dtype="int8")}
        with pytest.raises(strategy_io.StrategyValidationError,
                           match="no embedding-table storage"):
            strategy_io.validate_strategies(
                bad, row_shard_ops={"emb_stack"})
        ok = {"embedding3": ParallelConfig((8, 1, 1), quant_dtype="int8")}
        strategy_io.validate_strategies(ok, row_shard_ops={"emb_stack"})


def _pb_field_keys(blob):
    """The set of proto field-key bytes used (first byte of each op
    field) — crude but enough to prove fields 9/10 are absent."""
    keys = set()
    for _f, _wt, op in strategy_io._decode_message(blob):
        i = 0
        while i < len(op):
            key, j = strategy_io._read_varint(op, i)
            keys.add(bytes([key]))
            wt = key & 7
            if wt == 0:
                _, i = strategy_io._read_varint(op, j)
            elif wt == 2:
                ln, j2 = strategy_io._read_varint(op, j)
                i = j2 + ln
            else:
                break
    return b"".join(sorted(keys))


# ---------------------------------------------------------------------
# master_weight: bit-identical to the fp32-accumulator reference
# ---------------------------------------------------------------------
class TestMasterWeightBitIdentity:
    def _assert_identical(self, a, b):
        pa, pb = _all_params(a), _all_params(b)
        assert set(pa) == set(pb)
        for k in pa:
            assert np.array_equal(pa[k], pb[k]), k

    # fp8 rides only the sgd row: master_weight never reads the policy
    # dtype during training, so the matrix's dtype axis is exercised by
    # one optimizer while the optimizer axis runs on int8
    @pytest.mark.parametrize("opt,dt", [("sgd", "int8"), ("sgd", "fp8"),
                                        ("momentum", "int8"),
                                        ("adam", "int8")])
    def test_replicated(self, opt, dt):
        base = _fit(_build(opt=opt), DCFG)
        quant = _fit(_build(opt=opt, emb_dtype=dt), DCFG)
        assert quant.quant_policies()  # policy actually resolved
        self._assert_identical(base, quant)

    @pytest.mark.parametrize("opt", ["sgd", "adam"])
    def test_row_sharded(self, opt):
        base = _fit(_build(opt=opt, ndev=8, pd=4), DCFG)
        quant = _fit(_build(opt=opt, ndev=8, pd=4, emb_dtype="int8"),
                     DCFG)
        self._assert_identical(base, quant)

    def test_hybrid_hot_cold(self):
        # the hot quantum is 8 x lane-pack rows (128 here): the tables
        # must be big enough for a replicable hot head
        hcfg = DLRMConfig(embedding_size=[1024] * 4,
                          sparse_feature_size=8,
                          mlp_bot=[4, 16, 8], mlp_top=[40, 16, 1])
        base = _fit(_build(dcfg=hcfg, ndev=8, pd=4, hot=1.0 / 4), hcfg)
        quant = _fit(_build(dcfg=hcfg, ndev=8, pd=4, hot=1.0 / 4,
                            emb_dtype="int8"), hcfg)
        # the hybrid split actually resolved (hot_kernel exists)
        assert any("hot_kernel" in d for d in quant.params.values())
        self._assert_identical(base, quant)

    def test_superstep_k4(self):
        base = _fit(_build(superstep=4), DCFG)
        quant = _fit(_build(superstep=4, emb_dtype="int8"), DCFG)
        self._assert_identical(base, quant)

    def test_strategy_overrides_config_default(self):
        """A per-table strategy quant_dtype wins over --emb-dtype."""
        m = _build(emb_dtype="int8")
        name = _emb_names(m)[0]
        strategies = dict(m.strategies)
        strategies[name] = ParallelConfig(
            tuple(strategies[name].degrees) if name in strategies
            else (1, 1, 1), quant_dtype="fp8")
        m2 = ff.FFModel(ff.FFConfig(batch_size=BS, seed=3,
                                    emb_dtype="int8"))
        build_dlrm(m2, DCFG)
        m2.compile(_opt("sgd"), "mean_squared_error", ["mse"],
                   strategies=strategies)
        assert m2.quant_policies()[name].dtype == "fp8"


# ---------------------------------------------------------------------
# stochastic_rounding: quantized fixed points, tolerance vs fp32
# ---------------------------------------------------------------------
class TestStochasticRounding:
    def _assert_fixed_point(self, model, dt):
        for name in model.quant_policies():
            k = np.asarray(model.params[name]["kernel"])
            fq = fake_quant_np(k.reshape(-1, k.shape[-1]),
                               dt).reshape(k.shape)
            if dt == "int8":
                assert np.array_equal(fq, k), name
            else:
                # fp8: XLA may fuse x/s into x * (1/s) inside the
                # jitted step, which can flip a borderline e4m3
                # rounding vs the numpy codec — the stored value is
                # still a quantized image to ~1 ulp of fp8
                assert np.allclose(fq, k, atol=1e-6), name

    # Adam normalizes by sqrt(v): its early steps move ~alpha per step
    # regardless of gradient magnitude, so SR's per-step code noise
    # compounds through the trajectory much faster than under (momentum)
    # SGD — the tolerance reflects the update rule, not a looser bar
    TOL = {"sgd": 0.05, "momentum": 0.05, "adam": 0.35}

    @pytest.mark.parametrize("opt", ["sgd", "momentum", "adam"])
    def test_device_fixed_point_and_tolerance(self, opt):
        base = _fit(_build(opt=opt), DCFG)
        sr = _fit(_build(opt=opt, emb_dtype="int8",
                         emb_update_rule="stochastic_rounding"), DCFG)
        self._assert_fixed_point(sr, "int8")
        for name in sr.quant_policies():
            a = np.asarray(sr.params[name]["kernel"])
            b = np.asarray(base.params[name]["kernel"])
            diff = np.abs(a - b).max()
            assert 0 < diff < self.TOL[opt]   # tolerance, not identity
        # dense (non-table) params still track fp32
        d = [np.abs(_all_params(sr)[k] - _all_params(base)[k]).max()
             for k in _all_params(base) if "emb" not in k]
        assert max(d) < self.TOL[opt]

    def test_fp8_fixed_point(self):
        sr = _fit(_build(emb_dtype="fp8",
                         emb_update_rule="stochastic_rounding"), DCFG)
        self._assert_fixed_point(sr, "fp8")

    def test_deterministic_per_seed(self):
        a = _fit(_build(emb_dtype="int8",
                        emb_update_rule="stochastic_rounding"), DCFG)
        b = _fit(_build(emb_dtype="int8",
                        emb_update_rule="stochastic_rounding"), DCFG)
        for k, v in _all_params(a).items():
            assert np.array_equal(v, _all_params(b)[k]), k

    def test_row_sharded_fixed_point(self):
        sr = _fit(_build(ndev=8, pd=4, emb_dtype="int8",
                         emb_update_rule="stochastic_rounding"), DCFG)
        self._assert_fixed_point(sr, "int8")

    def test_host_resident_fixed_point(self):
        sr = _build(host_resident_tables=True, host_tables_async=False,
                    emb_dtype="int8",
                    emb_update_rule="stochastic_rounding")
        _fit(sr, DCFG, epochs=1)
        for name in sr.quant_policies():
            k = sr.host_params[name]["kernel"]
            v = k.reshape(-1, k.shape[-1])
            fq = fake_quant_np(v, "int8")
            assert np.array_equal(fq, v), name


# ---------------------------------------------------------------------
# delta publishes: quantized payloads
# ---------------------------------------------------------------------
class TestDeltaQuant:
    def _publish_pair(self, tmp_path, **cfg_kw):
        from dlrm_flexflow_tpu.utils.delta import DeltaPublisher
        model = _build(dcfg=WCFG, **cfg_kw)
        pub = DeltaPublisher(model, str(tmp_path), keep_last=3)
        pub.publish_full()
        _fit(model, WCFG, epochs=1, n=BS)
        entry = pub.publish()
        assert entry is not None and entry["kind"] == "delta"
        return model, pub, entry

    def test_bytes_shrink_and_round_trip(self, tmp_path):
        from dlrm_flexflow_tpu.utils.delta import (load_delta_file,
                                                   write_delta_file)
        _m32, _p32, e32 = self._publish_pair(tmp_path / "fp32")
        _m8, _p8, e8 = self._publish_pair(tmp_path / "int8",
                                          emb_dtype="int8")
        assert e8["bytes"] < e32["bytes"]
        # the dominant payload (the table rows) shrinks >= 3x; the
        # whole-file ratio is diluted by the dense fulls both ship
        p8 = os.path.join(str(tmp_path / "int8"), e8["file"])
        payload = load_delta_file(p8)
        assert payload.get("qrows"), "quantized rows expected"
        for key, (idx, q, scales, dt) in payload["qrows"].items():
            assert dt == "int8"
            assert np.asarray(q).dtype == np.int8
            # loaded fp32 rows ARE the dequantized codes
            got = payload["rows"][key][1]
            assert np.array_equal(got,
                                  dequantize_rows_np(q, scales, dt))
            # write -> load -> write round-trips codes + scales
            # bit-exactly (idempotent codec)
            p2 = str(tmp_path / "rt.npz")
            write_delta_file(p2, 1, 0, 0, {key: (idx, got)}, {},
                             quant={key: dt})
            again = load_delta_file(p2)
            _, q2, s2, _ = again["qrows"][key]
            assert np.array_equal(q2, q)
            assert np.array_equal(s2, scales)

    def test_row_payload_ratio(self, tmp_path):
        """The rows/ payload itself (what the acceptance bar measures)
        shrinks >= 3.5x at d=64."""
        from dlrm_flexflow_tpu.utils.delta import (load_delta_file,
                                                   write_delta_file)
        rng = np.random.RandomState(0)
        vals = rng.randn(500, 64).astype(np.float32)
        idx = np.arange(500, dtype=np.int64)
        key = "hostparams/emb/kernel"
        p32 = str(tmp_path / "a.npz")
        p8 = str(tmp_path / "b.npz")
        write_delta_file(p32, 1, 0, 0, {key: (idx, vals)}, {})
        write_delta_file(p8, 1, 0, 0, {key: (idx, vals)}, {},
                         quant={key: "int8"})
        a, b = os.path.getsize(p32), os.path.getsize(p8)
        # subtract the shared idx array (8 B/row) for the row-payload
        # ratio the bar names
        ratio = (a - idx.nbytes) / max(b - idx.nbytes, 1)
        assert ratio >= 3.5, (a, b, ratio)
        assert load_delta_file(p8)["qrows"]

    def test_corrupt_scale_rejected_with_reason(self, tmp_path):
        from dlrm_flexflow_tpu.utils.delta import (ChainError,
                                                   load_delta_file)
        _m, _p, entry = self._publish_pair(tmp_path, emb_dtype="int8")
        path = os.path.join(str(tmp_path), entry["file"])
        name = _emb_names(_m)[0]
        plan = faults.FaultPlan()
        plan.quant_scale[name] = 1e3
        with faults.active_plan(plan):
            with pytest.raises(ChainError, match="publish-time bound"):
                load_delta_file(path)
            assert plan.fired and plan.fired[0][0] == "quant_scale"
        # clean load still works after the consume-once budget
        assert load_delta_file(path)["qrows"]

    def test_watcher_degrades_on_corrupt_scale(self, tmp_path):
        """End-to-end serving drill: the watcher meets a garbage-scale
        delta, rejects it with a reason, and falls back to the newest
        valid FULL snapshot — the engine never serves amplified rows."""
        from dlrm_flexflow_tpu.serve import (InferenceEngine,
                                             ServeConfig,
                                             SnapshotWatcher)
        model, pub, entry = self._publish_pair(tmp_path,
                                               emb_dtype="int8")
        server = _build(dcfg=WCFG, emb_dtype="int8")
        eng = InferenceEngine(server, ServeConfig(max_batch=BS))
        name = _emb_names(model)[0]
        plan = faults.FaultPlan()
        plan.quant_scale[name] = 1e3
        watcher = SnapshotWatcher(eng, str(tmp_path), poll_s=0.05)
        with faults.active_plan(plan):
            watcher.poll_once()
        st = watcher.stats()
        assert st.get("chain_fallbacks", 0) >= 1 or \
            eng.stats()["reload_rejects"] >= 0
        # the engine landed on the (valid) full snapshot's version,
        # not the poisoned delta's
        assert eng.version == entry["base_step"]


# ---------------------------------------------------------------------
# serving caches + shard tier
# ---------------------------------------------------------------------
class TestCacheQuant:
    def _host_model(self, **kw):
        kw.setdefault("host_resident_tables", True)
        kw.setdefault("host_tables_async", False)
        return _build(dcfg=WCFG, **kw)

    def test_hit_equals_miss_bitwise(self):
        from dlrm_flexflow_tpu.serve.cache import EmbeddingCache
        model = self._host_model(emb_dtype="int8")
        op = [o for o in model.ops if hasattr(o, "host_lookup")][0]
        cache = EmbeddingCache(64, quant={op.name: "int8"})
        x, _ = synthetic_batch(WCFG, 8, seed=1)
        idx = np.ascontiguousarray(x["sparse"], np.int32)
        miss_vals = cache.lookup(op, model.host_params[op.name], idx)
        hit_vals = cache.lookup(op, model.host_params[op.name], idx)
        assert cache.hits > 0
        assert np.array_equal(miss_vals, hit_vals)

    def test_rows_per_mb(self):
        from dlrm_flexflow_tpu.serve.cache import EmbeddingCache
        model = self._host_model()
        op = [o for o in model.ops if hasattr(o, "host_lookup")][0]
        x, _ = synthetic_batch(WCFG, 16, seed=1)
        idx = np.ascontiguousarray(x["sparse"], np.int32)
        c32 = EmbeddingCache(64)
        c8 = EmbeddingCache(64, quant={op.name: "int8"})
        c32.lookup(op, model.host_params[op.name], idx)
        c8.lookup(op, model.host_params[op.name], idx)
        assert len(c32) == len(c8) > 0
        assert c32.stored_bytes() / c8.stored_bytes() >= 3.5


class TestShardTierQuant:
    def _set(self, model, nshards=2, cache_dir=None):
        from dlrm_flexflow_tpu.serve import (EmbeddingShardSet,
                                             ShardTierConfig)
        cfg = ShardTierConfig(nshards=nshards, eject_after=2, retries=1,
                              cooldown_s=0.0, replace_after=2,
                              lookup_deadline_ms=500.0)
        return EmbeddingShardSet.build(model, nshards, cfg,
                                       cache_dir=cache_dir)

    def _host_model(self, **kw):
        kw.setdefault("host_resident_tables", True)
        kw.setdefault("host_tables_async", False)
        return _build(dcfg=WCFG, **kw)

    def test_quantized_blocks_shrink_and_serve_exactly(self):
        m32 = self._host_model()
        m8 = self._host_model(emb_dtype="int8")
        s32 = self._set(m32)
        s8 = self._set(m8)
        try:
            b32 = sum(r.shard.hbm_bytes() for r in s32.shards)
            b8 = sum(r.shard.hbm_bytes() for r in s8.shards)
            assert b32 / b8 >= 3.5, (b32, b8)
            # fetched rows ARE the dequantized stored representation
            name = _emb_names(m8)[0]
            kern = m8.host_params[name]["kernel"]
            flat = fake_quant_np(
                np.asarray(kern).reshape(-1, kern.shape[-1]), "int8")
            ids = np.asarray([0, 3, 200, 1023], np.int64) \
                % flat.shape[0]
            got = s8.fetch({name: ids})
            assert not got.degraded
            assert np.array_equal(got.rows[name], flat[ids])
        finally:
            s32.close()
            s8.close()

    def test_publish_lands_bit_identically(self):
        m8 = self._host_model(emb_dtype="int8")
        sset = self._set(m8)
        try:
            name = _emb_names(m8)[0]
            kern = m8.host_params[name]["kernel"]
            width = kern.shape[-1]
            rng = np.random.RandomState(0)
            idx = np.asarray([1, 17, 600], np.int64)
            vals = rng.randn(3, width).astype(np.float32)
            payload = {"rows": {f"hostparams/{name}/kernel":
                                (idx, vals)}, "full": {}}
            sset.apply_delta(payload, version=10)
            got = sset.fetch({name: idx})
            assert np.array_equal(got.rows[name],
                                  fake_quant_np(vals, "int8"))
            assert sset.version == 10
        finally:
            sset.close()

    def test_warm_cache_round_trip_and_scale_corruption(self, tmp_path):
        from dlrm_flexflow_tpu.utils.warmcache import ShardCache
        m8 = self._host_model(emb_dtype="int8")
        sset = self._set(m8, cache_dir=str(tmp_path))
        try:
            rep = sset.shards[0]
            blocks, ver, crc = rep.shard.blocks_copy()
            name = _emb_names(m8)[0]
            assert isinstance(blocks[name], QuantTable)
            cache = ShardCache(str(tmp_path),
                               fingerprint=sset.fingerprint)
            got = cache.get(sset.nshards, rep.slot)
            assert got is not None
            blk = got[0][name]
            assert isinstance(blk, QuantTable)
            assert np.array_equal(
                np.asarray(blk.q, np.float32),
                np.asarray(blocks[name].q, np.float32))
            assert np.array_equal(blk.scales, blocks[name].scales)
            # corrupt-scale boot is a reject-with-reason, never a
            # garbage-amplitude shard
            plan = faults.FaultPlan()
            plan.quant_scale[name] = 1e3
            with faults.active_plan(plan):
                assert cache.get(sset.nshards, rep.slot) is None
            assert "publish-time bound" in cache.last_reject
        finally:
            sset.close()

    def test_engine_scores_track_master_within_quant_error(self):
        from dlrm_flexflow_tpu.serve import InferenceEngine, ServeConfig
        m8 = self._host_model(emb_dtype="int8")
        direct = np.asarray(m8.forward_batch(_x8(WCFG)))
        sset = self._set(m8)
        eng = InferenceEngine(m8, ServeConfig(max_batch=BS),
                              shard_set=sset)
        eng.start()
        try:
            p = eng.predict(_x8(WCFG), timeout=30)
            assert np.isfinite(p.scores).all()
            assert np.abs(p.scores - direct[:p.scores.shape[0]]).max() \
                < 0.25
            assert p.versions is not None and not p.degraded
        finally:
            eng.close()
            sset.close()


def _x8(dcfg):
    x, _ = synthetic_batch(dcfg, 8, seed=4)
    return x


# ---------------------------------------------------------------------
# byte accounting + FLX508
# ---------------------------------------------------------------------
class TestAccounting:
    def test_hbm_footprint_ratio(self):
        from dlrm_flexflow_tpu.search.cost_model import CostModel
        from dlrm_flexflow_tpu.search.simulator import (
            hbm_footprint_report)
        m32 = _build(dcfg=WCFG)
        m8 = _build(dcfg=WCFG, emb_dtype="int8")
        cost = CostModel()
        r32 = hbm_footprint_report(m32, cost, m32.strategies, 1)
        r8 = hbm_footprint_report(m8, cost, m8.strategies, 1)
        for name in _emb_names(m8):
            if name in r8 and r32.get(name, 0) > 1e6:
                assert r32[name] / r8[name] >= 3.5, name

    def test_a2a_payload_ratio(self):
        m32 = _build(dcfg=WCFG)
        m8 = _build(dcfg=WCFG, emb_dtype="int8")
        name = _emb_names(m8)[0]
        op32 = next(o for o in m32.ops if o.name == name)
        op8 = next(o for o in m8.ops if o.name == name)
        pc = ParallelConfig((8, 1, 1), param_degree=4)
        _, rows32, _ = op32.alltoall_payload_bytes(8, 4, pc=pc)
        # the policy rides the op (config default), not the pc
        _, rows8, _ = op8.alltoall_payload_bytes(8, 4, pc=pc)
        assert rows32 / rows8 >= 3.5

    def test_serving_footprint_ratio(self):
        from dlrm_flexflow_tpu.serve.shardtier import serving_footprint
        m32 = _build(dcfg=WCFG)
        m8 = _build(dcfg=WCFG, emb_dtype="int8")
        f32 = serving_footprint(m32, replicas=2)
        f8 = serving_footprint(m8, replicas=2)
        assert f32["table_bytes"] / f8["table_bytes"] >= 3.5

    def test_effective_policy_resolution_order(self):
        m = _build(emb_dtype="int8")
        op = next(o for o in m.ops if hasattr(o, "host_lookup"))
        assert effective_policy(op).dtype == "int8"
        pc = ParallelConfig((1, 1, 1), quant_dtype="fp8")
        assert effective_policy(op, pc).dtype == "fp8"

    def test_flx508_fixtures(self):
        from dlrm_flexflow_tpu.analysis.shardcheck import (
            verify_quant_policies)
        strat = {"emb": ParallelConfig((1, 1, 1), quant_dtype="int8")}
        agree = {"emb": {"dtype": "int8",
                         "update_rule": "master_weight"}}
        assert verify_quant_policies(strat, agree) == []
        # dtype mismatch: high
        out = verify_quant_policies(strat, {"emb": {"dtype": "fp32"}})
        assert len(out) == 1 and out[0].rule == "FLX508"
        assert out[0].severity == "high"
        # update-rule mismatch: medium
        out = verify_quant_policies(
            strat, {"emb": {"dtype": "int8",
                            "update_rule": "stochastic_rounding"}})
        assert len(out) == 1 and out[0].severity == "medium"
        # manifest quantized, strategy silent (fp32 default): flagged
        out = verify_quant_policies({}, agree)
        assert len(out) == 1
        # silent on both sides: clean
        assert verify_quant_policies(
            {"linear": ParallelConfig((1, 1))}, {}) == []

    def test_manifest_records_policies(self, tmp_path):
        from dlrm_flexflow_tpu.utils.checkpoint import (CheckpointManager,
                                                        mesh_meta)
        m = _build(emb_dtype="int8")
        meta = mesh_meta(m)
        assert meta.get("quant")
        name = _emb_names(m)[0]
        assert meta["quant"][name]["dtype"] == "int8"
        mgr = CheckpointManager(str(tmp_path), keep_last=2)
        mgr.save(m, {})
        mgr.wait()
        from dlrm_flexflow_tpu.analysis.shardcheck import _manifest_quant
        mq, _ = _manifest_quant(str(tmp_path))
        assert mq[name]["dtype"] == "int8"


# ---------------------------------------------------------------------
# fault-injection parsing + canary drill
# ---------------------------------------------------------------------
class TestQuantFaults:
    def test_env_parsing_strict(self, monkeypatch):
        monkeypatch.setenv("FF_FAULT_QUANT_SCALE", "emb_stack:1e3")
        plan = faults.plan_from_env()
        assert plan.quant_scale == {"emb_stack": 1e3}
        monkeypatch.setenv("FF_FAULT_QUANT_SCALE", "emb_stack")
        with pytest.raises(ValueError, match="FF_FAULT_QUANT_SCALE"):
            faults.plan_from_env()
        monkeypatch.setenv("FF_FAULT_QUANT_SCALE", "emb_stack:xx")
        with pytest.raises(ValueError, match="FF_FAULT_QUANT_SCALE"):
            faults.plan_from_env()

    def test_hook_consume_once_and_key_match(self):
        plan = faults.FaultPlan()
        plan.quant_scale["emb_stack"] = 2.0
        with faults.active_plan(plan):
            s = np.asarray([1.0, 2.0], np.float32)
            out = faults.maybe_corrupt_quant_scale("other/key", s)
            assert np.array_equal(out, s)          # no match
            out = faults.maybe_corrupt_quant_scale(
                "params/emb_stack/kernel", s)
            assert np.array_equal(out, s * 2.0)    # fired
            out = faults.maybe_corrupt_quant_scale(
                "params/emb_stack/kernel", s)
            assert np.array_equal(out, s)          # consumed


class TestCanaryQuantRollback:
    def test_mis_scaled_quant_deploy_rolls_back(self, tmp_path):
        """Canary-rollback drill on QUANTIZATION-induced score
        divergence: a snapshot whose embedding rows were quantized with
        mis-scaled row scales (every amplitude x50 — the failure a
        corrupt quant pipeline produces) loads cleanly but scores
        diverge; the router's canary must auto-roll-back with zero
        client-visible errors."""
        import threading
        import time as _time

        from dlrm_flexflow_tpu.serve import ServeConfig
        from dlrm_flexflow_tpu.serve.fleet import Fleet
        from dlrm_flexflow_tpu.serve.router import (FleetRouter,
                                                    RouterConfig)
        from dlrm_flexflow_tpu.utils.checkpoint import CheckpointManager

        def _one(i):
            devs = jax.devices()
            model = ff.FFModel(ff.FFConfig(batch_size=BS, seed=2))
            build_dlrm(model, DCFG)
            model.compile(
                _opt("sgd"), "mean_squared_error", ["mse"],
                mesh=make_mesh(devices=devs[i % len(devs):
                                            i % len(devs) + 1]))
            model.init_layers()
            return model

        # the bad deploy: embedding rows re-quantized with scales x6
        trainer = _one(0)
        x, y = synthetic_batch(DCFG, BS, seed=0)
        xb = dict(x)
        xb["label"] = y
        trainer.train_batch(xb)
        for name in _emb_names(trainer):
            k = np.asarray(trainer.params[name]["kernel"])
            q, s = quantize_rows_np(k.reshape(-1, k.shape[-1]), "int8")
            bad = dequantize_rows_np(q, s * 50.0,
                                     "int8").reshape(k.shape)
            trainer.params[name]["kernel"] = jnp.asarray(bad)
        mgr = CheckpointManager(str(tmp_path), keep_last=2)
        mgr.save(trainer, {})
        mgr.wait()
        snap = os.path.join(str(tmp_path), "ckpt-00000001.npz")

        fleet = Fleet.build(lambda i: _one(i), 2,
                            ServeConfig(max_batch=8, queue_capacity=512))
        router = FleetRouter(fleet, RouterConfig(
            retries=3, backoff_ms=2.0, eject_after=3, cooldown_s=0.15,
            probe_deadline_s=10.0, health_interval_s=0.05,
            canary_fraction=0.5, canary_min_samples=16,
            canary_score_tol=0.03, canary_p99_ratio=1e9))
        router.start()
        try:
            router.start_canary(snap)
            stop = threading.Event()
            failures = []

            def worker(tid):
                i = 0
                while not stop.is_set():
                    row = (tid + i) % BS
                    try:
                        router.predict(
                            {k: v[row:row + 1] for k, v in x.items()},
                            timeout=30)
                    except Exception as e:   # noqa: BLE001
                        failures.append(repr(e))
                    i += 1

            ts = [threading.Thread(target=worker, args=(t,))
                  for t in range(4)]
            for t in ts:
                t.start()
            deadline = _time.time() + 25
            while (_time.time() < deadline
                   and router.stats()["canary"]["active"]):
                _time.sleep(0.02)
            stop.set()
            for t in ts:
                t.join()
            st = router.stats()
            assert not failures, failures[:3]
            assert st["canary"]["rollbacks"] == 1
            assert "score divergence" in st["canary"][
                "last_rollback_reason"]
        finally:
            router.close()
