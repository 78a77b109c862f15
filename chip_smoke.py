#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the TPU.

Drives the README's own training path once, in ONE process, at the full
width of `DLRMConfig.random_benchmark()` (8 tables x 1M rows x 64-d, bot
64-512-512-64, top 576-1024-1024-1024-1, batch 256 per chip, bf16, SGD):

    FFConfig.parse_args -> FFModel -> build_dlrm -> dlrm_strategy ->
    compile -> init_layers -> fit

and checks, by the repo's own means, that what comes out is right:

1. train: the loss is finite, the default step's lowered HLO contains a
   Mosaic custom call (a gate that quietly fell back to the XLA scatter
   fails the run), and the loss after the steps agrees with the same
   steps under `use_pallas=False`. With more than one device the same
   path is repeated over the full mesh under the table-parallel plan and
   under `row_shard=True`, with the table's shards checked for placement.
2. kernels: every Pallas kernel a gate can route to is compiled by
   Mosaic (never the interpreter) at a shape a listed configuration
   admits and compared with the reference beside it in its own file.

Exit code 0 and a last stdout line
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`
only when every phase passed on a TPU. Any other platform is refused.

`--cpu-dry-run` walks the same control flow on 4 virtual CPU devices at
tiny shapes with the kernels in interpret mode. It proves nothing about
the chip, prints no result line, and exists so the script can be
debugged without spending chip time.
"""

import gc
import json
import os
import sys
import time
import traceback

import numpy as np

STEPS = 8
BATCH_PER_CHIP = 256
# Both routes run the MLPs in bf16 (8 mantissa bits, ~4e-3 relative per
# rounding) and differ in how the touched rows are summed and written
# back; after a few steps the two losses (~0.25, MSE of a sigmoid against
# 0/1 labels) may differ by a few bf16 roundings, not by more.
LOSS_RTOL = 2e-2
# The loss barely moves in a few SGD steps, so it cannot tell a scatter
# that wrote nothing from one that wrote the right rows. The table rows
# the first batch touched can: both routes apply fp32 updates whose
# cotangents came through the same bf16 MLPs, so after STEPS steps the
# rows agree to a few bf16 roundings of the largest update.
ROWS_RTOL = 5e-2
OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chiprun_out", "chip_smoke")


def log(msg):
    print(msg, flush=True)


def device_peaks():
    """Per-device (peak, in-use) bytes; None where the backend does not
    report them (the CPU)."""
    import jax
    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append((st.get("peak_bytes_in_use"), st.get("bytes_in_use")))
    return out


def fmt_gb(n):
    return "n/a" if n is None else f"{n / 2**30:.2f}G"


# --------------------------------------------------------------------------
# phase 1: the trainer
# --------------------------------------------------------------------------
def train_once(ndev, dcfg, *, row_shard=False, use_pallas=True,
               check_hlo=False, check_shards=False):
    """One pass of the README path on an `ndev`-device mesh. Returns
    (record, rows): a dict with the loss after STEPS steps, set-up and
    step times and the per-device memory after init, and the stored table
    rows the first batch touched, read back after the steps."""
    import jax

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.dlrm import (build_dlrm, dlrm_strategy,
                                               synthetic_batch)

    t0 = time.time()
    batch = BATCH_PER_CHIP * ndev
    cfg = ff.FFConfig.parse_args(
        ["-b", str(batch), "-e", "1", "--compute-dtype", "bfloat16"])
    cfg.use_pallas = use_pallas
    model = ff.FFModel(cfg)
    build_dlrm(model, dcfg)
    strat = dlrm_strategy(model, dcfg, ndev, row_shard=row_shard)
    model.compile(ff.SGDOptimizer(lr=cfg.learning_rate),
                  "mean_squared_error", ["mse"],
                  mesh=ff.make_mesh(num_devices=ndev), strategies=strat)
    t_built = time.time()
    model.init_layers()
    jax.block_until_ready(model.params)
    t_init = time.time()
    out = {"ndev": ndev, "row_shard": row_shard, "use_pallas": use_pallas,
           "mem_after_init": device_peaks()}

    (emb,) = [op for op in model.ops
              if type(op).__name__ == "EmbeddingBagStacked"]
    if check_shards:
        out["shards"] = check_table_shards(model.params[emb.name]["kernel"],
                                           ndev, row_shard)

    x, y = synthetic_batch(dcfg, STEPS * batch, seed=0)
    # the stored (packed) rows the first batch touches, by the op's own
    # delta-publication index math
    slot, prow = np.divmod(emb.delta_touched_rows(x["sparse"][:batch]),
                           model.params[emb.name]["kernel"].shape[1])

    def read_rows():
        return np.asarray(model.params[emb.name]["kernel"][slot, prow])

    rows0 = read_rows()
    t_fit = time.time()
    res = model.fit(x, y, epochs=1, verbose=False)
    t_end = time.time()
    # set-up = everything but fit's own timed loop: graph build +
    # compile(), init_layers, and fit's staging + AOT step compile
    out["setup_s"] = {
        "total": (t_end - t0) - res["elapsed"],
        "build": t_built - t0, "init": t_init - t_built,
        "fit_warmup": (t_end - t_fit) - res["elapsed"]}
    out["step_ms"] = 1e3 * res["elapsed"] / STEPS
    rows = read_rows()
    out["rows_moved"] = float(np.max(np.abs(rows - rows0)))
    if not out["rows_moved"] > 0:
        raise AssertionError("training left every touched table row at its "
                             "initial value: the sparse update wrote nothing")

    first = {k: v[:batch] for k, v in x.items()}
    first["label"] = y[:batch]
    loss = float(model.train_batch(first)["loss"])
    if not np.isfinite(loss):
        raise AssertionError(f"non-finite loss {loss}")
    out["loss"] = loss

    if check_hlo:
        hlo = model.lowered_train_hlo()
        out["tpu_custom_calls"] = hlo.count("tpu_custom_call")
        if jax.default_backend() == "tpu" and not out["tpu_custom_calls"]:
            raise AssertionError(
                "the default train step lowered WITHOUT a Mosaic custom "
                "call: a Pallas gate fell back to the XLA scatter")
    return out, rows


def check_table_shards(kernel, ndev, row_shard):
    """The embedding table must sit on `ndev` distinct devices with 1/ndev
    of the tables (table-parallel) or of every table's rows (row_shard)."""
    shards = kernel.addressable_shards
    devs = {s.device.id for s in shards}
    if len(devs) != ndev:
        raise AssertionError(
            f"table shards on {len(devs)} device(s), expected {ndev}")
    want = list(kernel.shape)
    want[1 if row_shard else 0] //= ndev
    for s in shards:
        if list(s.data.shape) != want:
            raise AssertionError(
                f"table shard {s.data.shape} on device {s.device.id}, "
                f"expected {tuple(want)} of {kernel.shape}")
    return {"global": list(kernel.shape), "per_device": want}


def report_train(tag, r):
    peaks = " ".join(f"d{i}:{fmt_gb(p)}/{fmt_gb(u)}"
                     for i, (p, u) in enumerate(r["mem_after_init"]))
    log(f"[train {tag}] loss={r['loss']:.6f} rows_moved="
        f"{r['rows_moved']:.3g} setup={r['setup_s']['total']:.1f}s "
        f"(build {r['setup_s']['build']:.1f} init "
        f"{r['setup_s']['init']:.1f} fit-compile "
        f"{r['setup_s']['fit_warmup']:.1f}) step={r['step_ms']:.3f}ms "
        f"mem after init (peak/in-use) {peaks}"
        + (f" custom_calls={r['tpu_custom_calls']}"
           if "tpu_custom_calls" in r else "")
        + (f" shards={r['shards']['per_device']} of {r['shards']['global']}"
           if "shards" in r else ""))


def phase_train(ndev, dcfg, report):
    """Every check is recorded and the phase goes on, so one chip run
    names everything that is wrong; any problem fails the phase."""
    runs = report["train"] = {}
    problems = []

    def run(key, tag, ndev_, **kw):
        rec, rows = train_once(ndev_, dcfg, **kw)
        runs[key] = rec
        report_train(tag, rec)
        gc.collect()
        return rec, rows

    def close(tag, a, b):
        (ra, rows_a), (rb, rows_b) = a, b
        if abs(ra["loss"] - rb["loss"]) > LOSS_RTOL * abs(rb["loss"]):
            problems.append(
                f"{tag}: losses {ra['loss']:.6f} vs {rb['loss']:.6f} "
                f"differ by more than rtol={LOSS_RTOL}")
        diff = float(np.max(np.abs(rows_a - rows_b)))
        log(f"[train {tag}] touched rows differ by at most {diff:.3g} "
            f"(largest update {rb['rows_moved']:.3g})")
        if diff > ROWS_RTOL * rb["rows_moved"]:
            problems.append(
                f"{tag}: touched table rows differ by {diff:.3g}, more "
                f"than {ROWS_RTOL} of the largest update "
                f"{rb['rows_moved']:.3g}")

    if ndev > 1:
        # full mesh first: peak_bytes_in_use never resets, so only the
        # first model initialized in the process shows its own init peak
        model_bytes = 4 * sum(dcfg.embedding_size) * dcfg.sparse_feature_size
        tp = run("mesh_table_parallel", f"{ndev}dev table-parallel pallas",
                 ndev, check_hlo=True, check_shards=True)
        whole = [i for i, (p, _) in enumerate(tp[0]["mem_after_init"])
                 if p is not None and p >= model_bytes]
        if whole:
            problems.append(
                f"after init device(s) {whole} peaked at or above the whole "
                f"{model_bytes / 2**30:.2f}G of tables: init_layers "
                f"materializes parameters unsharded")
        ref = run("mesh_table_parallel_xla", f"{ndev}dev table-parallel xla",
                  ndev, use_pallas=False)
        close("table-parallel pallas vs xla", tp, ref)
        rs = run("mesh_row_shard", f"{ndev}dev row-shard (all-to-all)",
                 ndev, row_shard=True, check_shards=True)
        close("row-shard vs table-parallel xla", rs, ref)
    one = run("single", "1dev pallas", 1, check_hlo=True)
    ref1 = run("single_xla", "1dev xla", 1, use_pallas=False)
    close("single-chip pallas vs xla", one, ref1)
    if problems:
        raise AssertionError("; ".join(problems))


# --------------------------------------------------------------------------
# phase 2: every shipped kernel through Mosaic, against its reference
# --------------------------------------------------------------------------
def kernel_checks(dry):
    """(name, fn) pairs; fn() returns (max abs error, tolerance). `dry`
    shrinks the shapes and runs the kernels in the Pallas interpreter."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dlrm_flexflow_tpu.ops.pallas import (embedding_kernel as ek,
                                              interaction_kernel as ik,
                                              lstm_kernel as lk,
                                              topk_kernel as tk)
    from dlrm_flexflow_tpu.quant.codec import quantize_rows_np  # top-k

    rng = np.random.RandomState(0)
    rows = 512 if dry else 100_000
    n = 64 if dry else 2048

    def err(a, b):
        return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                     - jnp.asarray(b, jnp.float32))))

    def gather_d128():
        tbl = jnp.asarray(rng.randn(rows, 128), jnp.float32)
        idx = jnp.asarray(rng.randint(0, rows, (n, 2)), jnp.int32)
        return err(jax.jit(lambda t, i: ek.embedding_bag(t, i, "sum", dry)
                           )(tbl, idx),
                   ek.embedding_bag_reference(tbl, idx)), 1e-5

    def _scatter_case(d):
        tbl = jnp.asarray(rng.randn(rows, d), jnp.float32)
        # duplicates on purpose: the dedup pre-pass is part of the route
        idx = jnp.asarray(rng.randint(0, rows // 4, (n,)), jnp.int32)
        upd = jnp.asarray(rng.randn(n, d), jnp.float32)
        return tbl, idx, upd, tbl.at[idx].add(upd)

    def scatter_rmw_packed_d64():
        tbl, idx, upd, want = _scatter_case(64)
        got = jax.jit(lambda v, i, u: ek.scatter_add_rows_packed(
            v, i, u, 64, dry))(tbl.reshape(rows // 2, 128), idx, upd)
        return err(got.reshape(rows, 64), want), 1e-4

    def scatter_write_packed_d64():
        tbl, idx, upd, want = _scatter_case(64)
        view = tbl.reshape(rows // 2, 128)
        tiles = jnp.take(view, idx // 2, axis=0)
        got = jax.jit(lambda v, i, u, t: ek.scatter_write_rows_packed(
            v, i, u, t, 64, dry))(view, idx, upd, tiles)
        return err(got.reshape(rows, 64), want), 1e-4

    def scatter_rmw_d128():
        tbl, idx, upd, want = _scatter_case(128)
        got = jax.jit(lambda t, i, u: ek.scatter_add_rows(t, i, u, dry)
                      )(tbl, idx, upd)
        return err(got, want), 1e-4

    # DLRMConfig.terabyte(): 26 tables, d=128, first top layer H=1024
    T, d, H = (3, 128, 128) if dry else (26, 128, 1024)
    trows = 64 if dry else 4096
    tb = 8 if dry else 256
    P = (T + 1) * T // 2

    # the kernel's fp32 dots and XLA's default-precision fp32 dots may
    # each take bf16 passes on the MXU: compare at bf16 resolution of
    # the output's scale (|y| ~ a few tens)
    def fused_interaction_terabyte():
        tbl = jnp.asarray(rng.randn(T * trows, d), jnp.float32)
        idx = jnp.asarray(rng.randint(0, trows, (tb, T))
                          + np.arange(T)[None, :] * trows, jnp.int32)
        bottom = jnp.asarray(rng.randn(tb, d), jnp.float32)
        w = jnp.asarray(rng.randn(d + P, H) / np.sqrt(d + P), jnp.float32)
        bias = jnp.asarray(rng.randn(H), jnp.float32)
        got = jax.jit(lambda *a: ik.fused_interaction(*a, True, dry)
                      )(tbl, idx, bottom, w, bias)
        with jax.default_matmul_precision("highest"):
            want = ik.fused_interaction_reference(tbl, idx, bottom, w, bias)
        return err(got, want), 2e-2 * float(jnp.max(jnp.abs(want)))

    # the NMT example's cell (benchmarks/run_zoo.py): seq 40, b64, h1024
    seq, b, h = (4, 8, 128) if dry else (40, 64, 1024)

    def _lstm_ref(xproj, wh):
        def cell(carry, xp):
            hp, cp = carry
            g = xp + jnp.dot(hp.astype(wh.dtype), wh,
                             preferred_element_type=jnp.float32)
            i, f, gg, o = jnp.split(g, 4, axis=-1)
            c = jax.nn.sigmoid(f) * cp + jax.nn.sigmoid(i) * jnp.tanh(gg)
            hc = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (hc, c), hc
        z = jnp.zeros((xproj.shape[1], wh.shape[0]), jnp.float32)
        return lax.scan(cell, (z, z), xproj)[1]

    def lstm_resident_fwd_bwd():
        xproj = jnp.asarray(rng.randn(seq, b, 4 * h), jnp.float32)
        wh = jnp.asarray(rng.randn(h, 4 * h) / np.sqrt(h), jnp.bfloat16)
        cot = jnp.asarray(rng.randn(seq, b, h), jnp.float32)

        def run(scan):
            def loss(xp, w):
                ys = scan(xp, w)
                return jnp.sum(ys * cot), ys
            (_, ys), (dx, dw) = jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True))(xproj, wh)
            return ys, dx, dw

        got = run(lambda xp, w: lk.lstm_scan(xp, w, dry))
        want = run(_lstm_ref)
        # normalize each output by its own scale; bf16 recurrent matmuls
        # accumulate differently over `seq` dependent steps
        return max(err(g, w_) / max(float(jnp.max(jnp.abs(
            jnp.asarray(w_, jnp.float32)))), 1e-6)
            for g, w_ in zip(got, want)), 5e-2

    def topk_d128_k100():
        R, B, k = (256, 4, 10) if dry else (50_000, 8, 100)
        codes, scales = quantize_rows_np(
            rng.randn(R, 128).astype(np.float32), "int8")
        qc, qs = tk.quantize_query(rng.randn(B, 128).astype(np.float32))
        gs, gi = tk.mips_topk(qc, qs, codes, scales, k, use_pallas=True,
                              interpret=dry)
        ws, wi = tk.mips_topk_reference(qc, qs, codes, scales, k)
        if not np.array_equal(gi, wi):
            raise AssertionError("top-k ids differ from the exact scan")
        return float(np.max(np.abs(gs - ws))), 0.0   # bit-identical contract

    def _flash_case(hd, vd):
        """`attend`'s flash route at the blocks `_flash_blocks` chooses
        (ISSUE 31) against the dense scores, forward and the three
        gradients, causal, on a model that fills the chip (so the scores
        do not fit and the gate opens). Returns the worse of the forward's
        error over 1.6e-2 (a bf16 ulp of an output past 2; PR 30 read 4e-3)
        and a gradient's, relative to its largest element, over 1.5e-2
        (read 7e-3). The dry run's gate stays shut: it walks the
        blockwise route."""
        import dlrm_flexflow_tpu as ff
        from dlrm_flexflow_tpu.ops import attention as at

        class Full:
            param_bytes = staticmethod(at._hbm_bytes)

        class Model:
            ops, optimizer, mesh = [Full], ff.AdamOptimizer(), None
            config = ff.FFConfig()

        h, s = (2, 2 * at.BLOCK_Q) if dry else (4, 2048)
        q, k, v = (jnp.asarray(rng.randn(1, h, s, d_), jnp.bfloat16)
                   for d_ in (hd, hd, vd))
        if not dry and not at._flash_gate(Model, "attn", q, k):
            raise AssertionError("the flash gate stayed shut on the chip")

        def both(fn):
            def loss(q, k, v):
                out = fn(q, k, v).astype(jnp.float32)
                return jnp.sum(out * jnp.cos(out)), out
            (_, out), grads = jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
            return out, grads

        got, got_g = both(lambda q, k, v: at.attend(Model, "attn", q, k, v,
                                                    True))
        want, want_g = both(lambda q, k, v: at._attention_local(q, k, v,
                                                                True))
        fwd = err(got, want)
        rel = max(err(g, w_) / float(jnp.max(jnp.abs(
            jnp.asarray(w_, jnp.float32)))) for g, w_ in zip(got_g, want_g))
        log(f"[flash {hd}/{vd}] blocks "
            f"{at._flash_blocks(1, s, s, at._flash_width(hd, vd))[1]} "
            f"forward {fwd:.3g} gradients {rel:.3g}")
        return max(fwd / 1.6e-2, rel / 1.5e-2), 1.0

    def flash_attend_256():
        return _flash_case(256, 256)

    def flash_attend_192_128():
        return _flash_case(192, 128)

    return [(f.__name__, f) for f in (
        gather_d128, scatter_rmw_packed_d64, scatter_write_packed_d64,
        scatter_rmw_d128, fused_interaction_terabyte,
        lstm_resident_fwd_bwd, topk_d128_k100, flash_attend_256,
        flash_attend_192_128)]


def phase_kernels(dry, report):
    """Run every kernel check; a refusal by the compiler is recorded and
    the phase goes on, so one run names every kernel that needs repair."""
    results = report["kernels"] = {}
    for name, fn in kernel_checks(dry):
        t0 = time.time()
        try:
            e, tol = fn()
        except Exception as exc:  # noqa: BLE001 — report, go on, fail below
            results[name] = {"ok": False, "error": traceback.format_exc()}
            log(f"[kernel {name}] FAILED {type(exc).__name__}: "
                f"{str(exc)[:1500]}")
            continue
        secs = time.time() - t0
        ok = bool(e <= tol)                 # False for NaN too
        results[name] = {"ok": ok, "max_err": e, "tol": tol,
                         "seconds": round(secs, 2)}
        log(f"[kernel {name}] {'ok' if ok else 'MISMATCH'} max_err={e:.3g} "
            f"tol={tol:.3g} ({secs:.1f}s incl. compile)")
    failed = [name for name, r in results.items() if not r["ok"]]
    if failed:
        raise AssertionError(f"kernels failed: {', '.join(failed)}")


def main(argv):
    dry = argv == ["--cpu-dry-run"]
    if argv and not dry:
        print(__doc__, file=sys.stderr)
        return 2
    if dry:
        from dlrm_flexflow_tpu.utils.testing import ensure_cpu_devices
        ensure_cpu_devices(4)

    import jax

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu import native
    from dlrm_flexflow_tpu.models.dlrm import DLRMConfig

    cache_dir = ff.use_compile_cache()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: platform={device['platform']} "
        f"device_kind={device['kind']!r} count={device['count']}")
    if device["platform"] != "tpu" and not dry:
        print(f"chip_smoke: found platform {device['platform']!r}, not "
              f"'tpu' - refusing to run (no CPU fallback; "
              f"--cpu-dry-run debugs the control flow only)",
              file=sys.stderr)
        return 1
    has_native = native.available()
    log(f"native.available()={has_native} compile cache={cache_dir} "
        f"jax={jax.__version__}")

    if dry:
        dcfg = DLRMConfig(embedding_size=[1024] * 8, sparse_feature_size=64,
                          mlp_bot=[64, 32, 64], mlp_top=[576, 32, 1])
    else:
        dcfg = DLRMConfig.random_benchmark()

    report = {"device": device, "native": has_native,
              "compile_cache": cache_dir}
    failed = []
    t0 = time.time()
    for phase in (lambda: phase_train(len(devs), dcfg, report),
                  lambda: phase_kernels(dry, report)):
        try:
            phase()
        except Exception:  # noqa: BLE001 — every phase reports, then fail
            failed.append(traceback.format_exc())
            log(failed[-1])
    report["failed"] = failed
    report["seconds"] = round(time.time() - t0, 1)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    log(f"total {report['seconds']}s, {len(failed)} phase(s) failed")
    if failed:
        return 1
    if dry:
        log("CPU DRY RUN passed: tiny shapes, interpreted kernels - this "
            "proves nothing about the chip")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
