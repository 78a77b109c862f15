"""Generic image data loaders (reference ImgDataLoader4D/2D,
python/flexflow_dataloader.cc: on-disk image datasets resident + per-batch
scatter): .ffbin native-prefetch path and npz/npy fallbacks, feeding the
CNN zoo through the same machinery as the DLRM loader."""

import os
import subprocess
import sys

import numpy as np
import pytest

import dlrm_flexflow_tpu as ff
from dlrm_flexflow_tpu.data import (ImgDataLoader2D, ImgDataLoader4D,
                                    write_img_ffbin)
from dlrm_flexflow_tpu.models.alexnet import build_alexnet
from dlrm_flexflow_tpu.parallel.mesh import make_mesh

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_model(batch=8, hw=32):
    model = ff.FFModel(ff.FFConfig(batch_size=batch))
    build_alexnet(model, num_classes=10, image_hw=hw)
    model.compile(ff.SGDOptimizer(lr=0.01),
                  "sparse_categorical_crossentropy", ["accuracy"],
                  mesh=make_mesh(num_devices=1))
    model.init_layers()
    return model


def _dataset(n=24, hw=32, seed=0):
    rng = np.random.RandomState(seed)
    images = rng.rand(n, 3, hw, hw).astype(np.float32)
    labels = rng.randint(0, 10, size=(n,)).astype(np.int32)
    return images, labels


class TestImgDataLoader:
    def test_ffbin_roundtrip_and_batches(self, tmp_path):
        images, labels = _dataset()
        path = str(tmp_path / "imgs.ffbin")
        write_img_ffbin(path, images, labels)
        model = _tiny_model()
        try:
            loader = ImgDataLoader4D(model, path, image_shape=(3, 32, 32))
        except RuntimeError as e:
            pytest.skip(f"native loader unavailable: {e}")
        assert loader.num_samples == 24 and loader.num_batches == 3
        hb = loader.next_host_batch()
        assert hb["image"].shape == (8, 3, 32, 32)
        assert hb["label"].dtype == np.int32
        np.testing.assert_allclose(hb["image"], images[:8], rtol=0, atol=0)
        mets = model.train_batch_device(loader.next_batch())
        assert np.isfinite(float(mets["loss"]))

    def test_ffbin_requires_image_shape(self, tmp_path):
        images, labels = _dataset()
        path = str(tmp_path / "imgs.ffbin")
        write_img_ffbin(path, images, labels)
        model = _tiny_model()
        with pytest.raises(ValueError, match="image_shape"):
            ImgDataLoader4D(model, path)

    def test_npz_fallback_trains(self, tmp_path):
        images, labels = _dataset()
        path = str(tmp_path / "imgs.npz")
        np.savez(path, images=images, labels=labels)
        model = _tiny_model()
        loader = ImgDataLoader4D(model, path)
        mets = model.train_batch_device(loader.next_batch())
        assert np.isfinite(float(mets["loss"]))

    def test_2d_variant_flattens(self, tmp_path):
        images, labels = _dataset()
        path = str(tmp_path / "imgs.npz")
        np.savez(path, images=images, labels=labels)
        model = ff.FFModel(ff.FFConfig(batch_size=8))
        x = model.create_tensor((8, 3 * 32 * 32), name="image")
        t = model.dense(x, 32, activation="relu")
        model.dense(t, 10, activation="softmax")
        model.compile(ff.SGDOptimizer(lr=0.01),
                      "sparse_categorical_crossentropy", ["accuracy"],
                      mesh=make_mesh(num_devices=1))
        model.init_layers()
        loader = ImgDataLoader2D(model, path)
        hb = loader.next_host_batch()
        assert hb["image"].shape == (8, 3 * 32 * 32)
        mets = model.train_batch_device(loader.next_batch())
        assert np.isfinite(float(mets["loss"]))


def test_alexnet_example_trains_from_disk(tmp_path):
    """The AlexNet example trains from on-disk data, not in-memory
    synthetic."""
    images, labels = _dataset(n=16, hw=32)
    path = str(tmp_path / "imgs.ffbin")
    write_img_ffbin(path, images, labels)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "examples", "native",
                                      "alexnet.py"),
         "-b", "8", "-e", "1", "--image-hw", "32", "--data-path", path],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.join(_REPO, "examples", "native"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "[on-disk]" in proc.stdout and "THROUGHPUT" in proc.stdout
