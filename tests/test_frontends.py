"""Frontend tests: keras capture->fit, torch.fx import with weight
transfer (forward golden vs the torch module), text-graph importer,
dataloader, checkpoint round-trip."""

import numpy as np
import torch

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu import keras as K
from dlrm_flexflow_tpu.data.dataloader import SingleDataLoader
from dlrm_flexflow_tpu.torch_frontend import PyTorchModel, from_torch_module
from dlrm_flexflow_tpu.utils.checkpoint import (get_weights,
                                                restore_checkpoint,
                                                save_checkpoint, set_weights)


def test_keras_sequential_learns():
    r = np.random.RandomState(0)
    x = r.rand(256, 8).astype(np.float32)
    y = (x.sum(axis=1, keepdims=True) > 4).astype(np.float32)
    model = K.Sequential([
        K.Input((8,)),
        K.Dense(32, activation="relu"),
        K.Dense(1, activation="sigmoid"),
    ])
    model.compile(optimizer=K.SGD(learning_rate=0.5),
                  loss="mean_squared_error",
                  metrics=["mse", "accuracy"])
    res = model.fit(x, y, batch_size=32, epochs=15, verbose=False)
    assert res["metrics"]["mse"] < 0.15, res["metrics"]


def test_keras_functional_multi_input():
    r = np.random.RandomState(1)
    a = K.Input((4,))
    b = K.Input((6,))
    ta = K.Dense(8, activation="relu")(a)
    tb = K.Dense(8, activation="relu")(b)
    merged = K.Concatenate(axis=1)([ta, tb])
    out = K.Dense(1)(merged)
    model = K.Model([a, b], out)
    model.compile(optimizer="adam", loss="mean_squared_error",
                  metrics=["mse"])
    xa = r.rand(64, 4).astype(np.float32)
    xb = r.rand(64, 6).astype(np.float32)
    y = r.rand(64, 1).astype(np.float32)
    res = model.fit([xa, xb], y, batch_size=16, epochs=2, verbose=False)
    assert np.isfinite(res["metrics"]["mse"])
    assert "dense" in model.summary()


def test_keras_early_stopping():
    r = np.random.RandomState(2)
    x = r.rand(64, 4).astype(np.float32)
    y = (x[:, :1] > 0.5).astype(np.float32)
    model = K.Sequential([K.Input((4,)), K.Dense(1, activation="sigmoid")])
    model.compile(optimizer=K.SGD(learning_rate=1.0),
                  loss="mean_squared_error", metrics=["accuracy"])
    cb = K.VerifyMetrics(metric="accuracy", threshold=0.5)
    model.fit(x, y, batch_size=16, epochs=50, callbacks=[cb], verbose=False)
    assert cb.reached


class _TorchNet(torch.nn.Module):
    def __init__(self):
        super().__init__()
        self.conv = torch.nn.Conv2d(3, 4, 3, padding=1)
        self.relu = torch.nn.ReLU()
        self.pool = torch.nn.MaxPool2d(2)
        self.flatten = torch.nn.Flatten()
        self.fc = torch.nn.Linear(4 * 4 * 4, 5)

    def forward(self, x):
        return self.fc(self.flatten(self.pool(self.relu(self.conv(x)))))


def test_fx_import_matches_torch_forward():
    net = _TorchNet().eval()
    model = ff.FFModel(ff.FFConfig(batch_size=4))
    names, out, loader = from_torch_module(
        model, net, {"x": (4, 3, 8, 8)})
    model.compile(ff.SGDOptimizer(0.01), "sparse_categorical_crossentropy",
                  ["accuracy"], final_tensor=out)
    model.init_layers()
    loader(model)

    r = np.random.RandomState(3)
    x = r.randn(4, 3, 8, 8).astype(np.float32)
    ours = np.asarray(model.forward_batch({"x": x}))
    with torch.no_grad():
        ref = net(torch.tensor(x)).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)


def test_text_graph_import(tmp_path):
    path = tmp_path / "g.ff"
    path.write_text(
        "x, , x, op_input\n"
        "fc1, x, fc1, op_linear, 16\n"
        "r1, fc1, r1, op_relu\n"
        "fc2, r1, fc2, op_linear, 2\n"
        "sm, fc2, sm, op_softmax\n")
    model = ff.FFModel(ff.FFConfig(batch_size=8))
    t = model.create_tensor((8, 4), name="x")
    out = PyTorchModel(str(path)).apply(model, [t])
    assert out.shape == (8, 2)
    model.compile(ff.SGDOptimizer(0.1), "sparse_categorical_crossentropy",
                  ["accuracy"], final_tensor=out)
    model.init_layers()
    r = np.random.RandomState(4)
    mets = model.train_batch({"x": r.rand(8, 4).astype(np.float32),
                              "label": r.randint(0, 2, (8, 1))})
    assert np.isfinite(float(mets["loss"]))


def test_dataloader_cycles_and_shuffles():
    r = np.random.RandomState(5)
    model = ff.FFModel(ff.FFConfig(batch_size=8))
    x = model.create_tensor((8, 4), name="x")
    model.dense(x, 1, name="fc")
    model.compile(ff.SGDOptimizer(0.1), "mean_squared_error", ["mse"])
    model.init_layers()
    xs = r.rand(40, 4).astype(np.float32)
    ys = r.rand(40, 1).astype(np.float32)
    dl = SingleDataLoader(model, {"x": xs}, ys, shuffle=True, seed=1)
    assert dl.num_batches == 5
    seen = 0
    for batch in dl:
        model.train_batch(batch)
        seen += 1
    assert seen == 5
    b6 = dl.next_batch()  # wraps around
    assert b6["x"].shape == (8, 4)


def test_checkpoint_roundtrip(tmp_path):
    r = np.random.RandomState(6)

    def build():
        m = ff.FFModel(ff.FFConfig(batch_size=8, seed=9))
        x = m.create_tensor((8, 4), name="x")
        m.dense(x, 8, activation="relu", name="fc1")
        m.dense(m.ops[-1].outputs[0], 1, name="fc2")
        m.compile(ff.SGDOptimizer(0.1, momentum=0.9), "mean_squared_error",
                  ["mse"])
        m.init_layers()
        return m

    xs = r.rand(8, 4).astype(np.float32)
    ys = r.rand(8, 1).astype(np.float32)
    m1 = build()
    for _ in range(3):
        m1.train_batch({"x": xs, "label": ys})
    path = str(tmp_path / "ckpt.npz")
    save_checkpoint(m1, path)

    m2 = build()
    restore_checkpoint(m2, path)
    assert m2._step == 3
    np.testing.assert_allclose(np.asarray(m1.params["fc1"]["kernel"]),
                               np.asarray(m2.params["fc1"]["kernel"]))
    # momentum state restored: next steps match exactly
    m1.train_batch({"x": xs, "label": ys})
    m2.train_batch({"x": xs, "label": ys})
    np.testing.assert_allclose(np.asarray(m1.params["fc1"]["kernel"]),
                               np.asarray(m2.params["fc1"]["kernel"]),
                               rtol=1e-6, atol=1e-7)


def test_get_set_weights():
    m = ff.FFModel(ff.FFConfig(batch_size=4))
    x = m.create_tensor((4, 3), name="x")
    m.dense(x, 2, name="fc")
    m.compile(ff.SGDOptimizer(0.1), "mean_squared_error", ["mse"])
    m.init_layers()
    w = get_weights(m, "fc")
    assert w["kernel"].shape == (3, 2)
    new = {"kernel": np.ones((3, 2), np.float32)}
    set_weights(m, "fc", new)
    out = np.asarray(m.forward_batch({"x": np.ones((4, 3), np.float32)}))
    np.testing.assert_allclose(out[:, 0], 3.0 * np.ones(4), rtol=1e-5)


class TestKerasAuxModules:
    """losses/metrics/initializers/preprocessing/np_utils parity
    (reference python/flexflow/keras/{losses,metrics,initializers,
    preprocessing,utils})."""

    def test_loss_metric_objects_in_compile(self):
        import numpy as np

        from dlrm_flexflow_tpu import keras
        model = keras.Sequential([
            keras.Input((4,)),
            keras.Dense(8, activation="relu"),
            keras.Dense(3, activation="softmax"),
        ])
        model.compile(
            optimizer=keras.SGD(learning_rate=0.05),
            loss=keras.losses.SparseCategoricalCrossentropy(),
            metrics=[keras.metrics.Accuracy(),
                     keras.metrics.SparseCategoricalCrossentropy()])
        rng = np.random.RandomState(0)
        x = rng.rand(64, 4).astype(np.float32)
        y = rng.randint(0, 3, (64, 1)).astype(np.int32)
        out = model.fit(x, y, epochs=1, batch_size=32, verbose=False)
        assert out["throughput"] > 0

    def test_pad_sequences_and_tokenizer(self):
        from dlrm_flexflow_tpu.keras.preprocessing.sequence import \
            pad_sequences
        from dlrm_flexflow_tpu.keras.preprocessing.text import (Tokenizer,
                                                                one_hot)
        p = pad_sequences([[1, 2, 3], [4]], maxlen=2)
        assert p.tolist() == [[2, 3], [0, 4]]
        p = pad_sequences([[1], [2, 3]], maxlen=3, padding="post")
        assert p.tolist() == [[1, 0, 0], [2, 3, 0]]
        t = Tokenizer(num_words=10)
        t.fit_on_texts(["the cat sat on the mat", "the dog"])
        seqs = t.texts_to_sequences(["the cat", "the dog"])
        assert seqs[0][0] == seqs[1][0] == t.word_index["the"]
        assert all(0 < i < 10 for s in seqs for i in s)
        oh = one_hot("hello world", 50)
        assert len(oh) == 2 and all(0 < i < 50 for i in oh)

    def test_np_utils(self):
        import numpy as np

        from dlrm_flexflow_tpu.keras.utils import normalize, to_categorical
        cat = to_categorical([1, 0, 2], num_classes=4)
        assert cat.shape == (3, 4) and cat[0, 1] == 1
        n = normalize(np.array([[3.0, 4.0]]))
        np.testing.assert_allclose(n, [[0.6, 0.8]], rtol=1e-6)

    def test_initializer_aliases(self):
        import jax

        from dlrm_flexflow_tpu.keras import initializers
        k = jax.random.PRNGKey(0)
        v = initializers.RandomUniform(minval=-1, maxval=1)(k, (8, 8))
        assert float(v.min()) >= -1 and float(v.max()) <= 1
        z = initializers.Zeros()(k, (4,))
        assert float(abs(z).max()) == 0.0


def test_fx_handler_coverage_vs_reference():
    """Handler-by-handler audit vs the reference torch importer
    (/root/reference/python/flexflow/torch/model.py:45-139: INPUT,
    LINEAR, CONV2D, POOL2D, DROPOUT, FLAT, RELU, SIGMOID, TANH, ELU,
    SOFTMAX, CONCAT, OUTPUT). One traced module drives every op type
    through the fx importer (modules AND functional forms), with
    trained-weight transfer, and the forward matches torch exactly.
    Beyond the reference's set the importer also handles BatchNorm2d,
    Embedding/EmbeddingBag, add/sub/mul, reshape (tested in
    test_fx_import_matches_torch_forward and test_onnx-analog paths)."""
    import torch

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.torch_frontend.fx import from_torch_module

    class Net(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.conv = torch.nn.Conv2d(2, 4, 3, padding=1)
            self.pool = torch.nn.MaxPool2d(2, 2)
            self.apool = torch.nn.AvgPool2d(2, 2)
            self.elu = torch.nn.ELU()
            self.sig = torch.nn.Sigmoid()
            self.tan = torch.nn.Tanh()
            self.drop = torch.nn.Dropout(0.3)   # inference: identity
            self.flat = torch.nn.Flatten()
            self.fc = torch.nn.Linear(8 * 2 * 2, 8)  # cat doubles channels
            self.soft = torch.nn.Softmax(dim=-1)

        def forward(self, x):
            t = self.conv(x)
            t = torch.relu(t)
            t = self.pool(t)
            t = self.apool(t)
            t = self.elu(t)
            t1 = self.sig(t)
            t2 = self.tan(t)
            t = torch.cat([t1, t2], 1)
            t = torch.nn.functional.elu(t)
            t = self.drop(t)
            t = self.flat(t)
            t = self.fc(t)
            return self.soft(t)

    torch.manual_seed(0)
    net = Net().eval()
    x = torch.randn(4, 2, 8, 8)
    with torch.no_grad():
        want = net(x).numpy()

    model = ff.FFModel(ff.FFConfig(batch_size=4))
    _, out, loader = from_torch_module(model, net, {"x": (4, 2, 8, 8)})
    model.compile(ff.SGDOptimizer(0.1), "mean_squared_error", ["mse"],
                  final_tensor=out)
    model.init_layers()
    loader(model)
    got = np.asarray(model.forward_batch({"x": x.numpy()}))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_keras_model_reusable_as_layer():
    """A Model must be callable MORE THAN ONCE (the frozen construction-
    time plan decouples replays from the layers' live wiring) and still
    fit()/summary() afterwards. True weight TYING (one layer at two
    positions of one materialized graph) is not supported — it must
    fail LOUDLY at materialization, never corrupt silently."""
    import pytest as _pytest
    r = np.random.RandomState(7)
    inner_in = K.Input((6,))
    enc = K.Model(inner_in, K.Dense(4, activation="relu")(inner_in))

    # two separate graphs from the same model: both trainable
    for trial in range(2):
        a = K.Input((6,))
        out = K.Dense(1)(enc(a))
        m = K.Model(a, out)
        m.compile(optimizer=K.SGD(learning_rate=0.1),
                  loss="mean_squared_error", metrics=["mse"])
        res = m.fit(r.rand(32, 6).astype(np.float32),
                    r.rand(32, 1).astype(np.float32),
                    batch_size=16, epochs=1, verbose=False)
        assert np.isfinite(res["metrics"]["mse"])

    # the inner model is STILL materializable on its own afterwards
    enc.compile(optimizer=K.SGD(learning_rate=0.1),
                loss="mean_squared_error", metrics=["mse"])
    res2 = enc.fit(r.rand(32, 6).astype(np.float32),
                   r.rand(32, 4).astype(np.float32), batch_size=16,
                   epochs=1, verbose=False)
    assert np.isfinite(res2["metrics"]["mse"])

    # weight tying within ONE graph: loud error, not silent corruption
    a2, b2 = K.Input((6,)), K.Input((6,))
    tied = K.Model([a2, b2],
                   K.Concatenate(axis=1)([enc(a2), enc(b2)]))
    tied.compile(optimizer=K.SGD(learning_rate=0.1),
                 loss="mean_squared_error", metrics=["mse"])
    with _pytest.raises(NotImplementedError, match="multiple graph"):
        tied.fit([r.rand(16, 6).astype(np.float32),
                  r.rand(16, 6).astype(np.float32)],
                 r.rand(16, 8).astype(np.float32), batch_size=16,
                 epochs=1, verbose=False)


def test_fit_trains_remainder_and_off_size_batch():
    """keras fit on 1,000 samples x b64 must train 15
    full batches PLUS the 40-sample remainder (per-shape executable
    cache), and FFModel.fit must accept batch_size != compile-time by
    recompiling instead of raising."""
    r = np.random.RandomState(3)
    x = r.rand(1000, 8).astype(np.float32)
    y = (x.sum(axis=1, keepdims=True) > 4).astype(np.float32)
    model = K.Sequential([
        K.Input((8,)),
        K.Dense(16, activation="relu"),
        K.Dense(1, activation="sigmoid"),
    ])
    model.compile(optimizer=K.SGD(learning_rate=0.1),
                  loss="mean_squared_error", metrics=["mse"])
    res = model.fit(x, y, batch_size=64, epochs=2, verbose=False)
    # 15 full batches + the 40-sample remainder, both epochs
    assert res["num_samples"] == 1000 * 2, res
    # metric running sums reset per epoch; the LAST epoch's count covers
    # all 15 full batches AND the 40-sample remainder
    assert int(res["metrics"]["train_all"]) == 1000, res["metrics"]

    # FFModel.fit with batch_size != compile-time: recompiles, trains
    ff_model = model.ffmodel
    res2 = ff_model.fit({"input_0": x}, y, epochs=1, batch_size=128,
                        verbose=False)
    assert res2["num_samples"] == 1000  # 7 x 128 + 104-sample remainder
