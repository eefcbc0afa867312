"""Int8 quantized inference pipeline: train fp32 → calibrate → quantize →
compare accuracy and latency (reference: example/mkldnn int8 DL-Boost
inference; whitepaper claim: <0.1% acc drop, ~4x size reduction).

    JAX_PLATFORMS=cpu python examples/quantized_inference.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import time                                                  # noqa: E402
import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402
import bigdl_tpu.nn as nn                                    # noqa: E402
from bigdl_tpu.dataset import ArrayDataSet, mnist            # noqa: E402
from bigdl_tpu.nn.quantized import calibrate, quantize       # noqa: E402
from bigdl_tpu.optim.local import Optimizer                  # noqa: E402
from bigdl_tpu.optim.method import SGD                       # noqa: E402
from bigdl_tpu.optim.metrics import Top1Accuracy, evaluate   # noqa: E402
from bigdl_tpu.optim.trigger import Trigger                  # noqa: E402


_PROTOTXT = '''
name: "LeNetCaffe"
input: "data"
input_dim: 1 input_dim: 1 input_dim: 28 input_dim: 28
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 6 kernel_size: 5 pad: 2 } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "conv2" type: "Convolution" bottom: "pool1" top: "conv2"
  convolution_param { num_output: 12 kernel_size: 5 } }
layer { name: "relu2" type: "ReLU" bottom: "conv2" top: "conv2" }
layer { name: "pool2" type: "Pooling" bottom: "conv2" top: "pool2"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "fc1" type: "InnerProduct" bottom: "pool2" top: "fc1"
  inner_product_param { num_output: 100 } }
layer { name: "relu3" type: "ReLU" bottom: "fc1" top: "fc1" }
layer { name: "fc2" type: "InnerProduct" bottom: "fc1" top: "fc2"
  inner_product_param { num_output: 10 } }
layer { name: "prob" type: "Softmax" bottom: "fc2" top: "prob" }
'''


def _train_and_export_caffe(tmpdir):
    """Train a LeNet-shaped net, export to Caffe format — the stand-in for
    downloading a public VGG-16 caffemodel (zero-egress environment). The
    int8 pipeline below starts from the IMPORTED model only."""
    from bigdl_tpu.interop.caffe import save_caffemodel

    x, y = mnist.load(None, train=True, n_synthetic=1024)
    x = mnist.normalize(x).reshape(-1, 28, 28, 1)
    model = nn.Sequential(
        nn.SpatialConvolution(1, 6, 5, 5, 1, 1, 2, 2, name="conv1"),
        nn.ReLU(),
        nn.SpatialMaxPooling(2, 2, 2, 2, ceil_mode=True),
        nn.SpatialConvolution(6, 12, 5, 5, name="conv2"), nn.ReLU(),
        nn.SpatialMaxPooling(2, 2, 2, 2, ceil_mode=True),
        nn.Flatten(), nn.Linear(5 * 5 * 12, 100, name="fc1"), nn.ReLU(),
        nn.Linear(100, 10, name="fc2"), nn.LogSoftMax())
    opt = Optimizer(model, ArrayDataSet(x, y, 128, drop_last=True),
                    nn.ClassNLLCriterion(), SGD(0.1, momentum=0.9))
    opt.set_end_when(Trigger.max_epoch(5))
    params, state = opt.optimize()

    # convert our NHWC-flatten fc1 weight to Caffe's NCHW-flatten rows
    p = {k: {kk: np.asarray(vv) for kk, vv in v.items()} if isinstance(v, dict)
         else v for k, v in params.items()}
    fc1 = next(k for k, m in model.children().items()
               if getattr(m, "name", "") == "fc1")
    w = p[fc1]["weight"]                       # (H*W*C, out) NHWC order
    p[fc1]["weight"] = (w.reshape(5, 5, 12, -1).transpose(2, 0, 1, 3)
                        .reshape(5 * 5 * 12, -1))
    proto = f"{tmpdir}/lenet.prototxt"
    with open(proto, "w") as fh:
        fh.write(_PROTOTXT)
    cm = f"{tmpdir}/lenet.caffemodel"
    save_caffemodel(cm, model, p)
    return proto, cm, x, y


def vgg16_leg(tmpdir, width_mult=0.125, spatial=64):
    """The BASELINE config-5 topology end to end: VGG-16 (all 13 convs +
    3 FC, width-scaled for a hermetic CPU run; pass width_mult=1.0 and
    spatial=224 on a chip for the paper model) → export with
    interop.caffe_saver → re-import from the prototxt+caffemodel pair →
    calibrated int8 → top-1 agreement vs fp32 (main() carries the
    timing comparison)."""
    from bigdl_tpu.interop import caffe_proto
    from bigdl_tpu.interop.caffe_saver import save_caffe
    from bigdl_tpu.models import vgg

    model = vgg.build(16, class_num=10, spatial=spatial,
                      width_mult=width_mult)
    params, state = model.init(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    x = r.randn(32, spatial, spatial, 3).astype(np.float32)

    proto = f"{tmpdir}/vgg16.prototxt"
    cm = f"{tmpdir}/vgg16.caffemodel"
    save_caffe(proto, cm, model, params, state,
               example_input=jnp.asarray(x[:1]))
    cn = caffe_proto.load(proto, cm)
    print(f"[vgg16] caffe pair re-imported: input {cn.input_shape}, "
          f"{len(cn.name_map)} named layers")

    ref = np.asarray(cn.module.apply(cn.params, cn.state,
                                     jnp.asarray(x))[0])
    scales = calibrate(cn.module, cn.params, cn.state, [x[:16]])
    qmodel, qparams = quantize(cn.module, cn.params, input_scales=scales)
    got = np.asarray(qmodel.apply(qparams, cn.state, jnp.asarray(x))[0])
    agree = float((ref.argmax(-1) == got.argmax(-1)).mean())
    print(f"[vgg16] int8 vs fp32 top-1 agreement on random inputs: "
          f"{agree:.2f}")
    assert agree >= 0.9, agree


def main():
    import tempfile
    from bigdl_tpu.interop.caffe_proto import load as load_caffe_net

    tmp = tempfile.TemporaryDirectory()
    tmpdir = tmp.name
    vgg16_leg(tmpdir)
    proto, cm, x, y = _train_and_export_caffe(tmpdir)

    # ---- BASELINE config 5: public-format load → int8 inference ----
    cn = load_caffe_net(proto, cm)
    model, params, state = cn.module, cn.params, cn.state
    print(f"imported caffe net: input {cn.input_shape}, "
          f"{len(cn.name_map)} layers")

    val = ArrayDataSet(x, y, 128, shuffle=False)
    facc = evaluate(model, params, state, val,
                    [Top1Accuracy()])["Top1Accuracy"].result

    scales = calibrate(model, params, state, [x[:256]])
    qmodel, qparams = quantize(model, params, input_scales=scales)
    qacc = evaluate(qmodel, qparams, state, val,
                    [Top1Accuracy()])["Top1Accuracy"].result

    from bigdl_tpu.utils.sync import chain_dep, force_completion
    fwd = jax.jit(lambda p, x: model.apply(p, state, x)[0])
    qfwd = jax.jit(lambda p, x: qmodel.apply(p, state, x)[0])
    xb = jnp.asarray(x[:256])

    def timed(f, p):
        # chained dispatches, timed to completion (utils/sync.py)
        out = f(p, xb)
        force_completion(out)
        cur = xb
        t0 = time.perf_counter()
        for _ in range(10):
            out = f(p, cur)
            cur = chain_dep(xb, out)
        force_completion(out)
        return time.perf_counter() - t0

    tf32 = timed(fwd, params)
    ti8 = timed(qfwd, qparams)

    print(f"fp32 acc {facc:.4f} | int8 acc {qacc:.4f} | "
          f"drop {facc - qacc:.4f}")
    print(f"fp32 fwd {tf32 * 100:.1f}ms | int8 fwd {ti8 * 100:.1f}ms")
    assert facc - qacc < 0.01
    return facc, qacc


if __name__ == "__main__":
    main()
