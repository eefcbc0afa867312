"""Import a Caffe FCN-style segmentation head — the layer vocabulary the
round-5 converter closure added (reference registry:
utils/caffe/Converter.scala:631-669): Deconvolution upsampling, PReLU,
Slice/Eltwise-with-coefficients fusion, Tile, NCHW Reshape — then run it,
quantize the conv trunk to int8, and round-trip the net through our own
prototxt+caffemodel writer.

    JAX_PLATFORMS=cpu python examples/caffe_segmentation_import.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

_PROTOTXT = """
name: "fcn-mini"
input: "data"
input_dim: 1 input_dim: 3 input_dim: 32 input_dim: 32
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 8 kernel_size: 3 pad: 1 stride: 2 } }
layer { name: "act1" type: "PReLU" bottom: "conv1" top: "conv1" }
layer { name: "up" type: "Deconvolution" bottom: "conv1" top: "up"
  convolution_param { num_output: 4 kernel_size: 2 stride: 2 } }
layer { name: "sl" type: "Slice" bottom: "up" top: "fg" top: "bg" }
layer { name: "mix" type: "Eltwise" bottom: "fg" bottom: "bg" top: "mix"
  eltwise_param { operation: SUM coeff: 0.75 coeff: 0.25 } }
layer { name: "probs" type: "Sigmoid" bottom: "mix" top: "probs" }
"""


def write_caffemodel(path, weights):
    from bigdl_tpu.interop import protowire as pw
    body = pw.field_str(1, "fcn-mini")
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


def main():
    from bigdl_tpu.interop import caffe_proto
    from bigdl_tpu.interop.caffe_saver import save_caffe
    from bigdl_tpu.nn.quantized import quantize

    tmp = tempfile.mkdtemp()
    r = np.random.RandomState(0)
    proto = os.path.join(tmp, "fcn.prototxt")
    cm = os.path.join(tmp, "fcn.caffemodel")
    with open(proto, "w") as fh:
        fh.write(_PROTOTXT)
    write_caffemodel(cm, {
        "conv1": [r.randn(8, 3, 3, 3).astype(np.float32) * 0.3,
                  r.randn(8).astype(np.float32) * 0.1],
        "act1": [(r.rand(8).astype(np.float32) * 0.5)],
        "up": [r.randn(8, 4, 2, 2).astype(np.float32) * 0.3,
               r.randn(4).astype(np.float32) * 0.1]})

    net = caffe_proto.load(proto, cm)
    x = jnp.asarray(r.randn(2, 32, 32, 3), jnp.float32)
    probs, _ = net.module.apply(net.params, net.state, x, training=False)
    print(f"[import] {len(net.name_map)} named layers; per-pixel "
          f"foreground probs {probs.shape}, range "
          f"[{float(probs.min()):.3f}, {float(probs.max()):.3f}]")
    assert probs.shape == (2, 32, 32, 2)
    assert 0.0 <= float(probs.min()) and float(probs.max()) <= 1.0

    qmod, qparams = quantize(net.module, net.params)
    q, _ = qmod.apply(qparams, net.state, x, training=False)
    delta = float(jnp.abs(q - probs).max())
    print(f"[int8] dynamic-quantized trunk: max prob delta {delta:.4f}")
    assert delta < 0.05

    proto2 = os.path.join(tmp, "roundtrip.prototxt")
    cm2 = os.path.join(tmp, "roundtrip.caffemodel")
    seq_model, seq_params, seq_state = _as_sequential(r)
    save_caffe(proto2, cm2, seq_model, seq_params, seq_state,
               example_input=x)
    net2 = caffe_proto.load(proto2, cm2)
    want, _ = seq_model.apply(seq_params, seq_state, x, training=False)
    got, _ = net2.module.apply(net2.params, net2.state, x, training=False)
    rt = float(jnp.abs(got - want).max())
    print(f"[roundtrip] save_caffe → load: max delta {rt:.2e}")
    assert rt < 1e-5
    print("caffe segmentation import example OK")


def _as_sequential(r):
    """A PReLU+Deconv chain authored natively, for the save→load leg."""
    import bigdl_tpu.nn as nn
    model = nn.Sequential(
        nn.SpatialConvolution(3, 6, 3, 3, 2, 2, 1, 1),
        nn.PReLU(6),
        nn.SpatialFullConvolution(6, 2, 2, 2, 2, 2),
        nn.Sigmoid())
    params, state = model.init(jax.random.PRNGKey(1))
    params["1"]["weight"] = jnp.asarray(r.rand(6).astype(np.float32) * 0.5)
    return model, params, state


if __name__ == "__main__":
    main()
