"""Model interop tour: author/import ONNX, Keras-HDF5, TF-GraphDef and
Caffe-prototxt models, then fine-tune one of them (reference workflows:
pyspark/bigdl/contrib/onnx/onnx_loader.py, pyspark/bigdl/keras/converter.py,
utils/tf/TensorflowLoader.scala, utils/caffe/CaffeLoader.scala).

    JAX_PLATFORMS=cpu python examples/import_models.py
"""

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import h5py                                                   # noqa: E402
import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402
import bigdl_tpu.nn as nn                                     # noqa: E402


def onnx_roundtrip(tmp):
    """Author an ONNX file with the wire-format helpers, import it back."""
    from bigdl_tpu.interop.onnx import (load_model, make_graph, make_model,
                                        make_node)
    r = np.random.RandomState(0)
    w = (r.randn(8, 3, 3, 3) * 0.2).astype(np.float32)
    b = (r.randn(8) * 0.1).astype(np.float32)
    wfc = (r.randn(8, 10) * 0.3).astype(np.float32)
    graph = make_graph(
        [
            make_node("Conv", ["x", "w", "b"], ["c"], kernel_shape=[3, 3],
                      pads=[1, 1, 1, 1]),
            make_node("Relu", ["c"], ["r"]),
            make_node("GlobalAveragePool", ["r"], ["g"]),
            make_node("Flatten", ["g"], ["f"], axis=1),
            make_node("MatMul", ["f", "wfc"], ["y"]),
        ],
        inputs={"x": [1, 3, 16, 16]}, outputs=["y"],
        initializers={"w": w, "b": b, "wfc": wfc})
    path = os.path.join(tmp, "model.onnx")
    with open(path, "wb") as f:
        f.write(make_model(graph))
    module, params, state, name_map = load_model(path)
    x = jnp.asarray(r.randn(2, 3, 16, 16), jnp.float32)   # NCHW like ONNX
    out, _ = module.apply(params, state, x, training=False)
    print(f"[onnx ] imported {len(name_map)} nodes -> logits {out.shape}")
    return module, params, state


def keras_roundtrip(tmp):
    """Author a Keras model.save()-style HDF5, import, fine-tune briefly."""
    from bigdl_tpu.keras import load_keras
    r = np.random.RandomState(1)
    k = (r.randn(3, 3, 2, 6) * 0.3).astype(np.float32)
    bk = (r.randn(6) * 0.1).astype(np.float32)
    wd = (r.randn(6, 4) * 0.3).astype(np.float32)
    bd = (r.randn(4) * 0.1).astype(np.float32)
    cfg = {"class_name": "Sequential", "config": {"name": "s", "layers": [
        {"class_name": "Conv2D",
         "config": {"name": "c1", "filters": 6, "kernel_size": [3, 3],
                    "padding": "same", "activation": "relu",
                    "batch_input_shape": [None, 8, 8, 2]}},
        {"class_name": "GlobalAveragePooling2D", "config": {"name": "g"}},
        {"class_name": "Dense", "config": {"name": "d", "units": 4}},
    ]}}
    path = os.path.join(tmp, "model.h5")
    with h5py.File(path, "w") as f:
        g = f.create_group("model_weights")
        g.attrs["layer_names"] = [b"c1", b"d"]
        for ln, wts in {"c1": [k, bk], "d": [wd, bd]}.items():
            lg = g.create_group(ln)
            names = [f"{ln}/w{i}:0".encode() for i in range(len(wts))]
            lg.attrs["weight_names"] = names
            for nm, wt in zip(names, wts):
                lg.create_dataset(nm.decode(), data=wt)
        f.attrs["model_config"] = json.dumps(cfg).encode()

    model, params, state = load_keras(hdf5_path=path)
    X = r.randn(64, 8, 8, 2).astype(np.float32)
    Y = (X.mean(axis=(1, 2, 3)) > 0).astype(np.int64)
    model.compile("adam", "sparse_categorical_crossentropy", ["acc"])
    model.fit(X, Y, batch_size=32, nb_epoch=3)
    res = model.evaluate(X, Y, batch_size=32)
    acc = {kk: v.result for kk, v in res.items()}
    print(f"[keras] .h5 import -> 3-epoch fine-tune -> {acc}")


def saved_model_roundtrip(tmp):
    """Save a REAL TF2 module (variables + a tf.while_loop), load it as a
    trainable graph through load_saved_model — the modern-TF entry the
    reference's TF1 checkpoint scripts predate."""
    try:
        import tensorflow as tf
    except ImportError:
        print("[saved_model] tensorflow not importable here - skipped")
        return
    from bigdl_tpu.interop.tf_saved_model import load_saved_model

    class M(tf.Module):
        def __init__(self):
            super().__init__()
            self.w = tf.Variable(
                (0.3 * np.random.RandomState(0).randn(4, 3)
                 ).astype(np.float32))

        @tf.function(input_signature=[
            tf.TensorSpec((None, 4), tf.float32)])
        def __call__(self, x):
            def cond(i, v):
                return i < 3

            def body(i, v):
                return i + 1, tf.nn.relu(v)
            _, x = tf.while_loop(cond, body, [tf.constant(0), x])
            return tf.nn.softmax(x @ self.w)

    m = M()
    x = np.random.RandomState(1).randn(4, 4).astype(np.float32)
    want = m(tf.constant(x)).numpy()
    d = os.path.join(tmp, "saved_model")
    tf.saved_model.save(m, d)
    module, params, state, _ = load_saved_model(d)
    got, _ = module.apply(params, state, jnp.asarray(x))
    err = float(np.abs(np.asarray(got) - want).max())
    print(f"[saved_model] TF2 SavedModel (vars + while loop) round-trip: "
          f"max |err| = {err:.2e}")
    assert err < 1e-5


def main():
    with tempfile.TemporaryDirectory() as tmp:
        onnx_roundtrip(tmp)
        keras_roundtrip(tmp)
        saved_model_roundtrip(tmp)
    print("model interop tour complete "
          "(see examples/quantized_inference.py for the Caffe-prototxt "
          "path and interop/convert.py for the CLI)")


if __name__ == "__main__":
    main()
