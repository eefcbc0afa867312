"""Importer round 2: TF GraphDef → trainable modules, Caffe prototxt
topology import (reference: utils/tf/TensorflowLoader.scala:201-358,
utils/caffe/CaffeLoader.scala:544-561)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.interop import protowire as pw
from bigdl_tpu.interop.tensorflow import load_graphdef, make_node
from bigdl_tpu.interop.tf_convert import to_module


# ------------------------------------------------------------ TF converter
def _demo_graphdef():
    r = np.random.RandomState(0)
    w1 = r.randn(3, 3, 3, 8).astype(np.float32) * 0.2
    b1 = r.randn(8).astype(np.float32) * 0.1
    scale = (r.rand(8) + 0.5).astype(np.float32)
    offset = r.randn(8).astype(np.float32) * 0.1
    mean = r.randn(8).astype(np.float32) * 0.1
    var = (r.rand(8) + 0.5).astype(np.float32)
    wfc = r.randn(8, 5).astype(np.float32) * 0.3
    bfc = r.randn(5).astype(np.float32) * 0.1

    gd = b"".join([
        make_node("x", "Placeholder"),
        make_node("w1", "Const", tensor=w1),
        make_node("conv", "Conv2D", ["x", "w1"],
                  ints={"strides": [1, 1, 1, 1]}, strs={"padding": "SAME"}),
        make_node("b1", "Const", tensor=b1),
        make_node("bias", "BiasAdd", ["conv", "b1"]),
        make_node("scale", "Const", tensor=scale),
        make_node("offset", "Const", tensor=offset),
        make_node("mean", "Const", tensor=mean),
        make_node("var", "Const", tensor=var),
        make_node("bn", "FusedBatchNorm",
                  ["bias", "scale", "offset", "mean", "var"]),
        make_node("relu", "Relu", ["bn"]),
        make_node("pool", "MaxPool", ["relu"],
                  ints={"ksize": [1, 2, 2, 1], "strides": [1, 2, 2, 1]},
                  strs={"padding": "VALID"}),
        make_node("gap", "Mean", ["pool", "axes"]),
        make_node("axes", "Const", tensor=np.asarray([1, 2], np.int32)),
        make_node("wfc", "Const", tensor=wfc),
        make_node("fc", "MatMul", ["gap", "wfc"]),
        make_node("bfc", "Const", tensor=bfc),
        make_node("out", "BiasAdd", ["fc", "bfc"]),
        make_node("prob", "Softmax", ["out"]),
    ])
    return gd


def _topo_fix(gd_bytes):
    """make_node emits in listed order; 'axes' const appears after its
    consumer above — reload and reorder via the parser's own graph."""
    return gd_bytes


def test_tf_convert_matches_interpreter():
    g = load_graphdef(_demo_graphdef())
    # interpreter needs topological order; 'axes' is declared after 'gap' —
    # re-sort by dependencies first
    order = []
    placed = set()

    def place(n):
        if n in placed:
            return
        for i in g.nodes[n].inputs:
            place(i)
        placed.add(n)
        order.append(n)

    for n in g.order:
        place(n)
    g.order = order

    x = np.random.RandomState(1).randn(2, 8, 8, 3).astype(np.float32)
    ref = np.asarray(g.run({"x": x}, outputs=["prob"]))

    module, params, state, name_map = to_module(g, inputs=["x"],
                                                outputs=["prob"])
    out, _ = module.apply(params, state, jnp.asarray(x), training=False)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)
    assert "conv" in name_map and "prob" in name_map


def test_tf_converted_model_is_trainable():
    g = load_graphdef(_demo_graphdef())
    module, params, state, _ = to_module(g, inputs=["x"], outputs=["out"])
    crit = nn.CrossEntropyCriterion()
    x = jnp.asarray(np.random.RandomState(2).randn(4, 8, 8, 3), jnp.float32)
    y = jnp.asarray([0, 1, 2, 3], jnp.int32)

    def loss_fn(p):
        out, _ = module.apply(p, state, x, training=True,
                              rng=jax.random.PRNGKey(0))
        return crit.forward(out, y)

    # (jitted: one program each, not one per eager op of the backward)
    l0, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    # gradients flow to the imported conv weight
    gnorm = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
    assert gnorm > 0
    p2 = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
    assert float(jax.jit(loss_fn)(p2)) < float(l0)


def test_tf_convert_unsupported_op_raises():
    gd = b"".join([
        make_node("x", "Placeholder"),
        make_node("weird", "FancyNewOp", ["x"]),
    ])
    with pytest.raises(NotImplementedError, match="FancyNewOp"):
        to_module(load_graphdef(gd))


# ---------------------------------------------------------- prototxt parser
def test_parse_prototxt_basics():
    from bigdl_tpu.interop.caffe_proto import parse_prototxt
    net = parse_prototxt('''
      name: "demo"  # a comment
      input: "data"
      input_dim: 1 input_dim: 3 input_dim: 8 input_dim: 8
      layer {
        name: "conv1" type: "Convolution"
        bottom: "data" top: "conv1"
        convolution_param { num_output: 4 kernel_size: 3 pad: 1 stride: 1 }
      }
    ''')
    assert net.one("name") == "demo"
    assert [int(d) for d in net.many("input_dim")] == [1, 3, 8, 8]
    layer = net.many("layer")[0]
    assert layer.one("type") == "Convolution"
    assert int(layer.msg("convolution_param").one("num_output")) == 4


# --------------------------------------------------- caffe topology import
_PROTOTXT = '''
name: "MiniVGG"
input: "data"
input_dim: 1
input_dim: 3
input_dim: 8
input_dim: 8
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 4 kernel_size: 3 pad: 1 } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "conv2" type: "Convolution" bottom: "pool1" top: "conv2"
  convolution_param { num_output: 6 kernel_size: 3 pad: 1 } }
layer { name: "relu2" type: "ReLU" bottom: "conv2" top: "conv2" }
layer { name: "pool2" type: "Pooling" bottom: "conv2" top: "pool2"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "fc1" type: "InnerProduct" bottom: "pool2" top: "fc1"
  inner_product_param { num_output: 10 } }
layer { name: "relu3" type: "ReLU" bottom: "fc1" top: "fc1" }
layer { name: "drop1" type: "Dropout" bottom: "fc1" top: "fc1"
  dropout_param { dropout_ratio: 0.5 } }
layer { name: "fc2" type: "InnerProduct" bottom: "fc1" top: "fc2"
  inner_product_param { num_output: 3 } }
layer { name: "prob" type: "Softmax" bottom: "fc2" top: "prob" }
'''


def _write_caffemodel(path, weights):
    """weights: {layer: [arrays in caffe layout]}"""
    body = pw.field_str(1, "MiniVGG")
    for lname, blobs in weights.items():
        layer = pw.field_str(1, lname)
        for b in blobs:
            b = np.asarray(b, np.float32)
            blob = pw.field_bytes(7, pw.field_packed_ints(1, list(b.shape)))
            blob += pw.field_packed_floats(5, b.reshape(-1).tolist())
            layer += pw.field_bytes(7, blob)
        body += pw.field_bytes(100, layer)
    with open(path, "wb") as fh:
        fh.write(body)


def test_caffe_topology_import_matches_torch(tmp_path):
    torch = pytest.importorskip("torch")
    from bigdl_tpu.interop.caffe_proto import load

    r = np.random.RandomState(3)
    w1 = r.randn(4, 3, 3, 3).astype(np.float32) * 0.3   # caffe layout
    b1 = r.randn(4).astype(np.float32) * 0.1
    w2 = r.randn(6, 4, 3, 3).astype(np.float32) * 0.3
    b2 = r.randn(6).astype(np.float32) * 0.1
    wf1 = r.randn(10, 6 * 2 * 2).astype(np.float32) * 0.3  # CHW flatten
    bf1 = r.randn(10).astype(np.float32) * 0.1
    wf2 = r.randn(3, 10).astype(np.float32) * 0.3
    bf2 = r.randn(3).astype(np.float32) * 0.1

    proto = tmp_path / "net.prototxt"
    proto.write_text(_PROTOTXT)
    cm = tmp_path / "net.caffemodel"
    _write_caffemodel(str(cm), {
        "conv1": [w1, b1], "conv2": [w2, b2],
        "fc1": [wf1, bf1], "fc2": [wf2, bf2]})

    cn = load(str(proto), str(cm))
    assert cn.input_shape == (8, 8, 3)
    x = r.randn(2, 8, 8, 3).astype(np.float32)
    out, _ = cn.module.apply(cn.params, cn.state, jnp.asarray(x),
                             training=False)

    # torch replica (NCHW, like caffe)
    t = lambda a: torch.from_numpy(np.asarray(a).copy())
    tx = t(x).permute(0, 3, 1, 2)
    h = torch.conv2d(tx, t(w1), t(b1), padding=1).relu()
    h = torch.nn.functional.max_pool2d(h, 2, 2, ceil_mode=True)
    h = torch.conv2d(h, t(w2), t(b2), padding=1).relu()
    h = torch.nn.functional.max_pool2d(h, 2, 2, ceil_mode=True)
    h = h.flatten(1) @ t(wf1).T + t(bf1)
    h = h.relu()
    h = h @ t(wf2).T + t(bf2)
    ref = torch.softmax(h, -1)
    np.testing.assert_allclose(np.asarray(out), ref.numpy(), atol=1e-5)


def test_caffe_import_then_quantize(tmp_path):
    """BASELINE config 5 shape: import from public format → int8 inference."""
    from bigdl_tpu.interop.caffe_proto import load
    from bigdl_tpu.nn.quantized import quantize

    r = np.random.RandomState(4)
    proto = tmp_path / "net.prototxt"
    proto.write_text(_PROTOTXT)
    cm = tmp_path / "net.caffemodel"
    _write_caffemodel(str(cm), {
        "conv1": [r.randn(4, 3, 3, 3).astype(np.float32) * 0.3,
                  r.randn(4).astype(np.float32) * 0.1],
        "conv2": [r.randn(6, 4, 3, 3).astype(np.float32) * 0.3,
                  r.randn(6).astype(np.float32) * 0.1],
        "fc1": [r.randn(10, 24).astype(np.float32) * 0.3,
                r.randn(10).astype(np.float32) * 0.1],
        "fc2": [r.randn(3, 10).astype(np.float32) * 0.3,
                r.randn(3).astype(np.float32) * 0.1]})
    cn = load(str(proto), str(cm))
    qmodule, qparams = quantize(cn.module, cn.params)
    x = jnp.asarray(r.randn(2, 8, 8, 3), jnp.float32)
    # (both forwards jitted: one program each, not one per eager op)
    fp, _ = jax.jit(lambda p, s, x: cn.module.apply(
        p, s, x, training=False))(cn.params, cn.state, x)
    q8, _ = jax.jit(lambda p, s, x: qmodule.apply(
        p, s, x, training=False))(qparams, cn.state, x)
    # int8 path approximates fp32 within quantization error
    assert np.abs(np.asarray(fp) - np.asarray(q8)).max() < 0.15
    assert np.argmax(fp, -1).tolist() == np.argmax(q8, -1).tolist()


def test_caffe_v1_layers_spelling(tmp_path):
    from bigdl_tpu.interop.caffe_proto import load
    proto = tmp_path / "v1.prototxt"
    proto.write_text('''
      name: "v1net"
      input: "data"
      input_dim: 1 input_dim: 2 input_dim: 6 input_dim: 6
      layers { name: "c" type: CONVOLUTION bottom: "data" top: "c"
        convolution_param { num_output: 3 kernel_size: 3 pad: 1 } }
      layers { name: "r" type: RELU bottom: "c" top: "c" }
      layers { name: "s" type: SOFTMAX bottom: "c" top: "prob" }
    ''')
    cn = load(str(proto))
    x = jnp.asarray(np.random.RandomState(0).randn(1, 6, 6, 2), jnp.float32)
    out, _ = cn.module.apply(cn.params, cn.state, x, training=False)
    assert out.shape == (1, 6, 6, 3)


def test_tf_training_session_fine_tunes_imported_graph():
    """(reference: utils/tf/Session.scala BigDLSessionImpl.train)."""
    import numpy as np
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.interop.tensorflow import make_node
    from bigdl_tpu.interop.tf_session import TFTrainingSession
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger

    r = np.random.RandomState(0)
    w = (0.1 * r.randn(6, 2)).astype(np.float32)
    b = np.zeros(2, np.float32)
    graph = b"".join([
        make_node("x", "Placeholder"),
        make_node("w", "Const", tensor=w),
        make_node("mm", "MatMul", ["x", "w"]),
        make_node("b", "Const", tensor=b),
        make_node("logits", "BiasAdd", ["mm", "b"]),
    ])
    x = r.randn(256, 6).astype(np.float32)
    y = (x[:, 0] - x[:, 1] > 0).astype(np.int32)

    sess = TFTrainingSession(graph, inputs=["x"], outputs=["logits"],
                             criterion=nn.CrossEntropyCriterion())
    before = np.asarray(sess.predict(x))
    acc0 = float((np.argmax(before, 1) == y).mean())
    sess.train(ArrayDataSet(x, y, 32, drop_last=True), SGD(0.5),
               Trigger.max_epoch(10))
    after = np.asarray(sess.predict(x))
    acc1 = float((np.argmax(after, 1) == y).mean())
    assert acc1 > 0.95 and acc1 > acc0


def test_caffe_persister_roundtrip_lenet(tmp_path):
    """VERDICT r2 #8 (missing #3): full CaffePersister parity — export
    prototxt + caffemodel, re-import from the files alone, identical
    outputs (reference: utils/caffe/CaffePersister.scala saveCaffe +
    CaffeLoader round trip)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.core.container import Sequential
    from bigdl_tpu.interop import caffe_proto
    from bigdl_tpu.interop.caffe_saver import save_caffe

    model = Sequential(
        nn.SpatialConvolution(1, 6, 5, 5, pad_w=2, pad_h=2), nn.Tanh(),
        nn.SpatialMaxPooling(2, 2),
        nn.SpatialConvolution(6, 16, 5, 5), nn.Tanh(),
        nn.SpatialMaxPooling(2, 2),
        nn.Flatten(), nn.Linear(16 * 5 * 5, 120), nn.Tanh(),
        nn.Linear(120, 84), nn.Tanh(), nn.Linear(84, 10), nn.LogSoftMax())
    params, state = model.init(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    x = r.randn(3, 28, 28, 1).astype(np.float32)

    proto = str(tmp_path / "lenet.prototxt")
    weights = str(tmp_path / "lenet.caffemodel")
    save_caffe(proto, weights, model, params, state,
               example_input=jnp.asarray(x))

    net = caffe_proto.load(proto, weights)
    got, _ = net.module.apply(net.params, net.state, jnp.asarray(x),
                              training=False)
    want, _ = model.apply(params, state, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_caffe_persister_bn_lrn_globalpool(tmp_path):
    """BatchNorm+Scale pair, LRN, dropout, and global average pooling
    survive the prototxt+caffemodel round trip."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.core.container import Sequential
    from bigdl_tpu.interop import caffe_proto
    from bigdl_tpu.interop.caffe_saver import save_caffe

    model = Sequential(
        nn.SpatialConvolution(3, 8, 3, 3, pad_w=1, pad_h=1),
        nn.SpatialBatchNormalization(8), nn.ReLU(),
        nn.SpatialCrossMapLRN(5, alpha=1e-3, beta=0.75, k=1.0),
        nn.Dropout(0.4),
        nn.GlobalAveragePooling2D(),
        nn.Linear(8, 4), nn.SoftMax())
    params, state = model.init(jax.random.PRNGKey(1))
    r = np.random.RandomState(1)
    x = r.randn(2, 8, 8, 3).astype(np.float32)
    # non-trivial BN stats
    _, state = model.apply(params, state, jnp.asarray(x), training=True,
                           rng=jax.random.PRNGKey(2))

    proto = str(tmp_path / "net.prototxt")
    weights = str(tmp_path / "net.caffemodel")
    save_caffe(proto, weights, model, params, state,
               example_input=jnp.asarray(x))
    net = caffe_proto.load(proto, weights)
    got, _ = net.module.apply(net.params, net.state, jnp.asarray(x),
                              training=False)
    want, _ = model.apply(params, state, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_caffe_persister_unrepresentable_raises(tmp_path):
    import bigdl_tpu.nn as nn
    from bigdl_tpu.core.container import Sequential
    from bigdl_tpu.interop.caffe_saver import save_caffe

    model = Sequential(nn.SpatialConvolution(3, 4, 3, 3, pad_w=-1,
                                             pad_h=-1))
    params, state = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="SAME"):
        save_caffe(str(tmp_path / "a.prototxt"), None, model, params, state)

    model2 = Sequential(nn.SpatialAveragePooling(
        3, 3, 1, 1, pad_w=1, pad_h=1, count_include_pad=False))
    p2, s2 = model2.init(jax.random.PRNGKey(0))
    x = np.zeros((1, 6, 6, 2), np.float32)
    with pytest.raises(NotImplementedError, match="count_include_pad"):
        save_caffe(str(tmp_path / "b.prototxt"), None, model2, p2, s2,
                   example_input=jnp.asarray(x))


def test_convert_cli_any_to_caffe_roundtrip(tmp_path):
    """convert() writes prototxt next to the caffemodel; importing from
    the pair reproduces the source model."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.core.container import Sequential
    from bigdl_tpu.interop import caffe_proto
    from bigdl_tpu.interop.convert import convert
    from bigdl_tpu.utils.serializer import save_module

    model = Sequential(
        nn.SpatialConvolution(1, 4, 3, 3), nn.ReLU(),
        nn.SpatialMaxPooling(2, 2),
        nn.Flatten(), nn.Linear(4 * 5 * 5, 10), nn.SoftMax())
    params, state = model.init(jax.random.PRNGKey(3))
    src = str(tmp_path / "m.bigdl-tpu")
    save_module(src, model, params, state)

    dst = str(tmp_path / "m.caffemodel")
    convert(src, dst, example_shape=(1, 12, 12, 1))
    assert (tmp_path / "m.prototxt").exists()

    net = caffe_proto.load(str(tmp_path / "m.prototxt"), dst)
    r = np.random.RandomState(2)
    x = r.randn(2, 12, 12, 1).astype(np.float32)
    got, _ = net.module.apply(net.params, net.state, jnp.asarray(x),
                              training=False)
    want, _ = model.apply(params, state, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_caffe_persister_bn_eps_and_reverse_cli(tmp_path):
    """Non-default BN eps survives the round trip (batch_norm_param), and
    convert() imports a caffemodel via its sibling prototxt with no
    --module skeleton."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.core.container import Sequential
    from bigdl_tpu.interop.caffe_saver import save_caffe
    from bigdl_tpu.interop.convert import convert
    from bigdl_tpu.utils.serializer import load_module

    model = Sequential(
        nn.SpatialConvolution(1, 4, 3, 3),
        nn.SpatialBatchNormalization(4, eps=1e-2), nn.ReLU(),
        nn.GlobalAveragePooling2D(), nn.Linear(4, 3), nn.SoftMax())
    params, state = model.init(jax.random.PRNGKey(5))
    r = np.random.RandomState(5)
    x = r.randn(2, 9, 9, 1).astype(np.float32)
    _, state = model.apply(params, state, jnp.asarray(x), training=True)

    proto = str(tmp_path / "m.prototxt")
    weights = str(tmp_path / "m.caffemodel")
    save_caffe(proto, weights, model, params, state,
               example_input=jnp.asarray(x))
    assert "batch_norm_param" in open(proto).read()

    out = str(tmp_path / "back.bigdl-tpu")
    convert(weights, out)                # no module_path: sibling prototxt
    mod2, p2, s2 = load_module(out)
    got, _ = mod2.apply(p2, s2, jnp.asarray(x), training=False)
    want, _ = model.apply(params, state, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_caffe_persister_anisotropic_dilation_raises(tmp_path):
    import bigdl_tpu.nn as nn
    from bigdl_tpu.core.container import Sequential
    from bigdl_tpu.interop.caffe_saver import save_caffe
    m = Sequential(nn.SpatialDilatedConvolution(1, 2, 3, 3, dilation_w=2,
                                                dilation_h=1))
    p, s = m.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="anisotropic"):
        save_caffe(str(tmp_path / "d.prototxt"), None, m, p, s)


def test_convert_cli_any_to_any_matrix(tmp_path):
    """The ConvertModel matrix (reference: utils/ConvertModel.scala
    --from X --to Y): one trained model through every export format and
    back, identical outputs each way. Import-only sources (onnx) and the
    t7 weight-table path are covered by their own tests."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.core.container import Sequential
    from bigdl_tpu.interop.convert import convert
    from bigdl_tpu.utils.serializer import load_module, save_module

    model = Sequential(
        nn.SpatialConvolution(1, 4, 3, 3, pad_w=1, pad_h=1), nn.ReLU(),
        nn.SpatialMaxPooling(2, 2),
        nn.Flatten(), nn.Linear(4 * 5 * 5, 10), nn.SoftMax())
    params, state = model.init(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    x = r.randn(2, 10, 10, 1).astype(np.float32)
    want, _ = model.apply(params, state, jnp.asarray(x))
    src = str(tmp_path / "m.bigdl-tpu")
    save_module(src, model, params, state)

    for ext, needs_shape in ((".pb", True), (".caffemodel", True),
                             (".t7", False)):
        out = str(tmp_path / f"m{ext}")
        convert(src, out,
                example_shape=(1, 10, 10, 1) if needs_shape else None)
        back = str(tmp_path / f"back_{ext.lstrip('.')}.bigdl-tpu")
        if ext == ".t7":
            # weight table: reverse path needs the module skeleton
            convert(out, back, module_path=src)
        else:
            convert(out, back)
        m2, p2, s2 = load_module(back)
        got, _ = m2.apply(p2, s2, jnp.asarray(x))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5,
            err_msg=f"round trip via {ext} diverged")
