"""Int8 quantization tests (reference analogues: nn/quantized specs and the
int8 e2e inference example — quantized output must track the float output
closely and the tree walk must preserve structure)."""

import jax
import jax.numpy as jnp
import numpy as np

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.quantized import (QuantizedLinear,
                                    QuantizedSpatialConvolution, calibrate,
                                    quantize, quantize_weight)


def test_quantize_weight_roundtrip_error():
    r = np.random.RandomState(0)
    w = r.randn(64, 32).astype(np.float32)
    q, s = quantize_weight(w, axis=1)
    assert q.dtype == jnp.int8
    deq = np.asarray(q, np.float32) * np.asarray(s)
    err = np.abs(deq - w).max() / np.abs(w).max()
    assert err < 0.01    # 1/127 per-channel quantization error


def test_quantized_linear_close_to_float():
    r = np.random.RandomState(1)
    layer = nn.Linear(32, 16)
    params, state = layer.init(jax.random.PRNGKey(0))
    x = jnp.asarray(r.randn(8, 32), jnp.float32)
    ref, _ = layer.apply(params, state, x)
    qlayer, qparams = QuantizedLinear.from_float(layer, params)
    out = qlayer.forward(qparams, x)
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.05, rel


def test_quantized_conv_close_to_float():
    r = np.random.RandomState(2)
    layer = nn.SpatialConvolution(3, 8, 3, 3, pad_w=1, pad_h=1)
    params, state = layer.init(jax.random.PRNGKey(0))
    x = jnp.asarray(r.randn(2, 10, 10, 3), jnp.float32)
    ref, _ = layer.apply(params, state, x)
    qlayer, qparams = QuantizedSpatialConvolution.from_float(layer, params)
    out = qlayer.forward(qparams, x)
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.06, rel


def test_quantize_tree_walk_lenet():
    from bigdl_tpu.models import lenet
    model = lenet.build(10)
    params, state = model.init(jax.random.PRNGKey(0))
    qmodel, qparams = quantize(model, params)
    # conv/linear children replaced, others untouched
    kinds = [type(c).__name__ for c in qmodel.children().values()]
    assert "QuantizedSpatialConvolution" in kinds
    assert "QuantizedLinear" in kinds
    assert "SpatialMaxPooling" in kinds
    # original model untouched
    assert type(model.children()["0"]).__name__ == "SpatialConvolution"

    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(4, 28, 28, 1), jnp.float32)
    # (both forwards jitted: one program each, not one per eager op)
    ref, _ = jax.jit(model.apply)(params, state, x)
    out, _ = jax.jit(qmodel.apply)(qparams, state, x)
    # log-probs argmax agreement — the <0.1% top-1 drop claim at model level
    assert (np.argmax(np.asarray(out), 1) ==
            np.argmax(np.asarray(ref), 1)).mean() == 1.0


def test_calibrated_static_scales():
    r = np.random.RandomState(3)
    model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
    params, state = model.init(jax.random.PRNGKey(0))
    batches = [r.randn(8, 16).astype(np.float32) for _ in range(3)]
    scales = calibrate(model, params, state, batches)
    assert set(scales) == {"0", "2"}
    assert all(s > 0 for s in scales.values())
    # forward restored after calibration (no instrumentation left)
    assert "forward" not in model.children()["0"].__dict__

    qmodel, qparams = quantize(model, params, input_scales=scales)
    x = jnp.asarray(batches[0])
    ref, _ = model.apply(params, state, x)
    out, _ = qmodel.apply(qparams, state, x)
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.1, rel


def test_quantized_model_size_shrinks(tmp_path):
    from bigdl_tpu.utils.serializer import load_module, save_module
    model = nn.Sequential(nn.Linear(256, 256), nn.ReLU(),
                          nn.Linear(256, 256))
    params, state = model.init(jax.random.PRNGKey(0))
    qmodel, qparams = quantize(model, params)
    fp = str(tmp_path / "f.bigdl-tpu")
    qp = str(tmp_path / "q.bigdl-tpu")
    save_module(fp, model, params, state)
    save_module(qp, qmodel, qparams, state)
    import os
    ratio = os.path.getsize(fp) / os.path.getsize(qp)
    assert ratio > 3.0, ratio   # ~4x size reduction like the reference claims
    # and it loads + runs
    m2, p2, s2 = load_module(qp)
    out, _ = m2.apply(p2, s2, jnp.zeros((2, 256)))
    assert out.shape == (2, 256)


def test_quantize_graph_model():
    """Graph-based models must execute the quantized modules (regression:
    quantize() used to swap _children while Graph ran node.module)."""
    from bigdl_tpu.models import lenet
    model = lenet.graph(10)
    params, state = model.init(jax.random.PRNGKey(0))
    qmodel, qparams = quantize(model, params)
    kinds = {type(c).__name__ for c in qmodel.children().values()}
    assert "QuantizedSpatialConvolution" in kinds
    assert "QuantizedLinear" in kinds
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(4, 28, 28, 1), jnp.float32)
    ref, _ = model.apply(params, state, x)
    out, _ = qmodel.apply(qparams, state, x)
    assert (np.argmax(np.asarray(out), 1) ==
            np.argmax(np.asarray(ref), 1)).mean() == 1.0


def test_quantize_dilated_conv():
    """Dilated conv quantizes too (reference:
    nn/quantized/SpatialDilatedConvolution.scala) — geometry preserved,
    int8 output tracks the float layer."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.nn.quantized import quantize

    layer = nn.SpatialDilatedConvolution(3, 8, 3, 3, pad_w=2, pad_h=2,
                                         dilation_w=2, dilation_h=2)
    params, state = layer.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 12, 12, 3)
                    .astype(np.float32))
    ref = layer.forward(params, x)
    qm, qp = quantize(layer, params)
    got = qm.forward(qp, x)
    assert got.shape == ref.shape
    err = float(jnp.abs(got - ref).max())
    scale = float(jnp.abs(ref).max())
    assert err < 0.05 * scale, (err, scale)
