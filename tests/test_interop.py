"""Serializer, visualization, and Keras-API tests (reference analogues:
utils/serializer specs — per-layer round-trip — visualization
TrainSummarySpec, keras API specs)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu import keras, visualization as viz
from bigdl_tpu.utils.serializer import load_module, save_module


def test_save_load_roundtrip(tmp_path):
    model = nn.Sequential(
        nn.SpatialConvolution(1, 4, 3, 3, pad_w=1, pad_h=1),
        nn.ReLU(),
        nn.Flatten(),
        nn.Linear(4 * 8 * 8, 5),
        nn.LogSoftMax())
    params, state = model.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 8, 1),
                    jnp.float32)
    out1, _ = model.apply(params, state, x)

    path = str(tmp_path / "m.bigdl-tpu")
    save_module(path, model, params, state)
    m2, p2, s2 = load_module(path)
    out2, _ = m2.apply(p2, s2, x)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6)


def test_save_load_bn_state(tmp_path):
    model = nn.Sequential(nn.SpatialConvolution(3, 4, 3, 3),
                          nn.SpatialBatchNormalization(4))
    params, state = model.init(jax.random.PRNGKey(0))
    # run a training step so running stats are non-trivial
    x = jnp.asarray(np.random.RandomState(0).randn(4, 6, 6, 3), jnp.float32)
    _, state = model.apply(params, state, x, training=True)
    path = str(tmp_path / "bn.bigdl-tpu")
    save_module(path, model, params, state)
    _, _, s2 = load_module(path)
    np.testing.assert_allclose(
        np.asarray(state["1"]["running_mean"]),
        np.asarray(s2["1"]["running_mean"]), rtol=1e-6)


def test_format_version_guard(tmp_path):
    import json
    import zipfile
    model = nn.Linear(2, 2)
    params, state = model.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "v.bigdl-tpu")
    save_module(path, model, params, state)
    # bump version in-place
    with zipfile.ZipFile(path) as zf:
        data = {n: zf.read(n) for n in zf.namelist()}
    meta = json.loads(data["meta.json"])
    meta["format_version"] = 999
    data["meta.json"] = json.dumps(meta).encode()
    with zipfile.ZipFile(path, "w") as zf:
        for n, b in data.items():
            zf.writestr(n, b)
    with pytest.raises(ValueError, match="newer"):
        load_module(path)


def test_crc32c_known_values():
    # RFC 3720 test vector: 32 bytes of zeros -> 0x8A9136AA
    assert viz.crc32c(b"\x00" * 32) == 0x8A9136AA
    assert viz.crc32c(b"\xff" * 32) == 0x62A8AB43


def test_event_file_roundtrip(tmp_path):
    s = viz.TrainSummary(str(tmp_path), "app")
    for i in range(5):
        s.add_scalar("Loss", 1.0 / (i + 1), i)
    s.add_scalar("Throughput", 1000.0, 1)
    import time
    time.sleep(0.2)
    got = s.read_scalar("Loss")
    t0 = time.monotonic()
    s.close()
    # an idle writer is woken, not waited for: close() used to take what
    # was left of the writer's 5 s wait on its empty queue
    assert time.monotonic() - t0 < 1.0
    assert [g[0] for g in got] == [0, 1, 2, 3, 4]
    np.testing.assert_allclose([g[1] for g in got],
                               [1.0, 0.5, 1 / 3, 0.25, 0.2], rtol=1e-6)


def test_trainer_writes_summary(tmp_path):
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger
    r = np.random.RandomState(0)
    x = r.randn(32, 4).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    model = nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax())
    ds = ArrayDataSet(x, y, batch_size=8, drop_last=True)
    summary = viz.TrainSummary(str(tmp_path), "t")
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), SGD(0.1))
    opt.set_end_when(Trigger.max_epoch(2)).set_train_summary(summary)
    opt.optimize()
    import time
    time.sleep(0.2)
    losses = summary.read_scalar("Loss")
    summary.close()
    assert len(losses) == 8    # 4 iters/epoch × 2 epochs


def test_keras_fit_evaluate_predict(tmp_path):
    r = np.random.RandomState(0)
    x = r.randn(256, 8).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.int32)
    m = keras.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2),
                         nn.LogSoftMax())
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
              metrics=["accuracy"])
    m.fit(x, y, batch_size=32, nb_epoch=40)
    res = m.evaluate(x, y)
    acc = res["Top1Accuracy"].result
    assert acc > 0.9
    preds = m.predict(x[:10])
    assert preds.shape == (10, 2)
    assert m.predict_classes(x[:10]).shape == (10,)
    # save/load round trip preserves predictions
    path = str(tmp_path / "keras.bigdl-tpu")
    m.save(path)
    m2 = keras.KerasModel.load(path)
    np.testing.assert_allclose(preds, m2.predict(x[:10]), rtol=1e-5)


def test_keras_unknown_names_raise():
    m = keras.Sequential(nn.Linear(2, 2))
    with pytest.raises(ValueError, match="optimizer"):
        m.compile(optimizer="sdg", loss="mse")
    with pytest.raises(ValueError, match="loss"):
        m.compile(optimizer="sgd", loss="msee")


# ----------------------------------------------------- tf.train.Example
def test_tf_example_roundtrip(tmp_path):
    from bigdl_tpu.interop import tf_example as te
    ex = {"image/encoded": b"\x89PNG...",
          "image/class/label": 7,
          "bbox": np.asarray([0.1, 0.2, 0.3, 0.4], np.float32),
          "ids": np.asarray([3, 1, 4], np.int64),
          "name": "sample-1"}
    dec = te.decode_example(te.encode_example(ex))
    assert dec["image/encoded"] == [b"\x89PNG..."]
    np.testing.assert_array_equal(dec["image/class/label"], [7])
    np.testing.assert_allclose(dec["bbox"], ex["bbox"], rtol=1e-6)
    np.testing.assert_array_equal(dec["ids"], ex["ids"])
    assert dec["name"] == [b"sample-1"]

    # file roundtrip through the TFRecord framing
    path = str(tmp_path / "examples.tfrecord")
    n = te.write_example_file(path, [ex, {"x": 1.5}])
    assert n == 2
    back = list(te.read_example_file(path))
    assert len(back) == 2
    np.testing.assert_allclose(back[1]["x"], [1.5])


def test_tf_example_against_torch_free_reference(tmp_path):
    # cross-check the wire format against a hand-built byte layout for a
    # single int64 feature: Example{1:{1:{1:"k",2:{3:{1:[5]}}}}}
    from bigdl_tpu.interop import tf_example as te
    buf = te.encode_example({"k": 5})
    want = bytes([0x0A, 0x0C,           # Example.features, len 12
                  0x0A, 0x0A,           # map entry, len 10
                  0x0A, 0x01, ord("k"),  # key "k"
                  0x12, 0x05,           # Feature, len 5
                  0x1A, 0x03,           # Int64List, len 3
                  0x0A, 0x01, 0x05])    # packed repeated [5]
    assert buf == want


def test_tf_example_negative_int64():
    from bigdl_tpu.interop import tf_example as te
    dec = te.decode_example(te.encode_example(
        {"label": -1, "ids": np.asarray([-5, 3], np.int64)}))
    np.testing.assert_array_equal(dec["label"], [-1])
    np.testing.assert_array_equal(dec["ids"], [-5, 3])


def test_tf_example_bool_array():
    from bigdl_tpu.interop import tf_example as te
    dec = te.decode_example(te.encode_example(
        {"flags": np.asarray([True, False, True])}))
    np.testing.assert_array_equal(dec["flags"], [1, 0, 1])
