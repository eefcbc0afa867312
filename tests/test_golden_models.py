"""Whole-model import goldens (VERDICT r2 #6): real architectures with
torch-generated weights flow through the ONNX / TF-GraphDef / .t7
importers and must reproduce torch's logits end to end — validating
importer + architecture + numerics in one shot (the analogue of the
reference's whole-model Torch specs, test/.../torch/ResNetSpec.scala,
VggLikeSpec.scala; weights are generated in-test because the environment
ships no pretrained files)."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
import torch.nn as tnn                                        # noqa: E402

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402

from bigdl_tpu.interop.onnx import (load_model as load_onnx,  # noqa: E402
                                    make_graph, make_model, make_node)


def _t(x):
    return x.detach().numpy()


# --------------------------------------------------------- torch ResNet-50
class Bottleneck(tnn.Module):
    expansion = 4

    def __init__(self, cin, width, stride=1):
        super().__init__()
        cout = width * self.expansion
        self.conv1 = tnn.Conv2d(cin, width, 1, bias=False)
        self.bn1 = tnn.BatchNorm2d(width)
        self.conv2 = tnn.Conv2d(width, width, 3, stride, 1, bias=False)
        self.bn2 = tnn.BatchNorm2d(width)
        self.conv3 = tnn.Conv2d(width, cout, 1, bias=False)
        self.bn3 = tnn.BatchNorm2d(cout)
        self.relu = tnn.ReLU()
        self.down = None
        if stride != 1 or cin != cout:
            self.down = tnn.Sequential(
                tnn.Conv2d(cin, cout, 1, stride, bias=False),
                tnn.BatchNorm2d(cout))

    def forward(self, x):
        idt = x if self.down is None else self.down(x)
        y = self.relu(self.bn1(self.conv1(x)))
        y = self.relu(self.bn2(self.conv2(y)))
        y = self.bn3(self.conv3(y))
        return self.relu(y + idt)


class TorchResNet50(tnn.Module):
    """torchvision-equivalent ResNet-50 (layers 3,4,6,3)."""

    def __init__(self, classes=1000):
        super().__init__()
        self.conv1 = tnn.Conv2d(3, 64, 7, 2, 3, bias=False)
        self.bn1 = tnn.BatchNorm2d(64)
        self.relu = tnn.ReLU()
        self.maxpool = tnn.MaxPool2d(3, 2, 1)
        blocks = []
        cin = 64
        for width, n, stride in ((64, 3, 1), (128, 4, 2),
                                 (256, 6, 2), (512, 3, 2)):
            for i in range(n):
                blocks.append(Bottleneck(cin, width,
                                         stride if i == 0 else 1))
                cin = width * Bottleneck.expansion
        self.blocks = tnn.ModuleList(blocks)
        self.fc = tnn.Linear(cin, classes)

    def forward(self, x):
        y = self.maxpool(self.relu(self.bn1(self.conv1(x))))
        for b in self.blocks:
            y = b(y)
        y = y.mean(dim=(2, 3))
        return self.fc(y)


def _randomize_bn_stats(model, rng):
    """BN with non-trivial running stats — identity stats would hide
    mean/var layout bugs in the importers."""
    for m in model.modules():
        if isinstance(m, tnn.BatchNorm2d):
            with torch.no_grad():
                m.running_mean.copy_(torch.from_numpy(
                    (rng.randn(m.num_features) * 0.2).astype(np.float32)))
                m.running_var.copy_(torch.from_numpy(
                    (rng.rand(m.num_features) + 0.5).astype(np.float32)))


class _OnnxEmitter:
    """Walk the in-test torch ResNet and emit its ONNX graph — the shape a
    real exporter would produce (Conv/BN/Relu/MaxPool/Add/
    GlobalAveragePool/Flatten/Gemm, OIHW weights as initializers)."""

    def __init__(self):
        self.nodes, self.inits, self.n = [], {}, 0

    def fresh(self, base):
        self.n += 1
        return f"{base}_{self.n}"

    def conv(self, x, conv: tnn.Conv2d):
        w = self.fresh("w")
        self.inits[w] = _t(conv.weight)
        ins = [x, w]
        if conv.bias is not None:
            b = self.fresh("b")
            self.inits[b] = _t(conv.bias)
            ins.append(b)
        out = self.fresh("conv")
        k = list(conv.kernel_size)
        p = list(conv.padding)
        self.nodes.append(make_node(
            "Conv", ins, [out], kernel_shape=k,
            strides=list(conv.stride), pads=p + p))
        return out

    def bn(self, x, bn: tnn.BatchNorm2d):
        names = [self.fresh(s) for s in ("scale", "beta", "mean", "var")]
        for nm, arr in zip(names, (bn.weight, bn.bias, bn.running_mean,
                                   bn.running_var)):
            self.inits[nm] = _t(arr)
        out = self.fresh("bn")
        self.nodes.append(make_node(
            "BatchNormalization", [x] + names, [out], epsilon=bn.eps))
        return out

    def relu(self, x):
        out = self.fresh("relu")
        self.nodes.append(make_node("Relu", [x], [out]))
        return out

    def bottleneck(self, x, blk: Bottleneck):
        idt = x
        if blk.down is not None:
            idt = self.bn(self.conv(x, blk.down[0]), blk.down[1])
        y = self.relu(self.bn(self.conv(x, blk.conv1), blk.bn1))
        y = self.relu(self.bn(self.conv(y, blk.conv2), blk.bn2))
        y = self.bn(self.conv(y, blk.conv3), blk.bn3)
        out = self.fresh("add")
        self.nodes.append(make_node("Add", [y, idt], [out]))
        return self.relu(out)


def test_resnet50_through_onnx_importer_matches_torch():
    r = np.random.RandomState(0)
    torch.manual_seed(0)
    tm = TorchResNet50(classes=100)
    _randomize_bn_stats(tm, r)
    tm.eval()

    e = _OnnxEmitter()
    x = "x"
    y = e.relu(e.bn(e.conv(x, tm.conv1), tm.bn1))
    out = e.fresh("pool")
    e.nodes.append(make_node("MaxPool", [y], [out], kernel_shape=[3, 3],
                             strides=[2, 2], pads=[1, 1, 1, 1]))
    y = out
    for blk in tm.blocks:
        y = e.bottleneck(y, blk)
    gap = e.fresh("gap")
    e.nodes.append(make_node("GlobalAveragePool", [y], [gap]))
    fl = e.fresh("flat")
    e.nodes.append(make_node("Flatten", [gap], [fl], axis=1))
    wfc, bfc = e.fresh("wfc"), e.fresh("bfc")
    e.inits[wfc] = _t(tm.fc.weight)
    e.inits[bfc] = _t(tm.fc.bias)
    e.nodes.append(make_node("Gemm", [fl, wfc, bfc], ["logits"], transB=1))

    model = make_model(make_graph(
        nodes=e.nodes, inputs={"x": [1, 3, 96, 96]}, outputs=["logits"],
        initializers=e.inits))

    xin = r.randn(1, 3, 96, 96).astype(np.float32) * 0.5
    module, params, state, _ = load_onnx(model)
    # (jitted: one program, where the eager forward compiles each of the
    # imported graph's ~175 nodes on its own)
    got, _ = jax.jit(lambda p, s, x: module.apply(p, s, x, training=False))(
        params, state, jnp.asarray(xin))
    with torch.no_grad():
        want = tm(torch.from_numpy(xin)).numpy()
    assert np.asarray(got).shape == want.shape == (1, 100)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=2e-3)


# ------------------------------------------------------------ TF VGG-16
def test_vgg16_through_tf_graphdef_importer_matches_torch():
    """The 13-conv VGG-16 stack + 3 FC head, hand-emitted as a frozen
    GraphDef (NHWC/HWIO, the layout TF writes), imported via tf_convert."""
    from bigdl_tpu.interop.tensorflow import make_node as tf_node
    from bigdl_tpu.interop.tf_convert import load_model as load_tf

    cfg = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
           512, 512, 512, "M", 512, 512, 512, "M"]
    torch.manual_seed(1)
    layers, cin = [], 3
    for v in cfg:
        if v == "M":
            layers.append(tnn.MaxPool2d(2, 2))
        else:
            layers += [tnn.Conv2d(cin, v, 3, padding=1), tnn.ReLU()]
            cin = v
    # 64x64 input -> 2x2x512 after five pools
    head = [tnn.Flatten(), tnn.Linear(512 * 2 * 2, 256), tnn.ReLU(),
            tnn.Linear(256, 64), tnn.ReLU(), tnn.Linear(64, 10)]
    tm = tnn.Sequential(*(layers + head))
    for p in tm.parameters():           # keep activations in a sane range
        with torch.no_grad():
            p.mul_(0.3)
    tm.eval()

    nodes = [tf_node("input", "Placeholder", types={"dtype": 1})]
    cur = "input"
    i = 0
    for m in tm:
        if isinstance(m, tnn.Conv2d):
            i += 1
            w = _t(m.weight).transpose(2, 3, 1, 0)        # OIHW -> HWIO
            nodes.append(tf_node(f"w{i}", "Const", tensor=w))
            nodes.append(tf_node(f"conv{i}", "Conv2D", [cur, f"w{i}"],
                                 ints={"strides": [1, 1, 1, 1]},
                                 strs={"padding": "SAME"}, types={"T": 1}))
            nodes.append(tf_node(f"cb{i}", "Const", tensor=_t(m.bias)))
            nodes.append(tf_node(f"cbias{i}", "BiasAdd",
                                 [f"conv{i}", f"cb{i}"], types={"T": 1}))
            cur = f"cbias{i}"
        elif isinstance(m, tnn.ReLU):
            i += 1
            nodes.append(tf_node(f"relu{i}", "Relu", [cur], types={"T": 1}))
            cur = f"relu{i}"
        elif isinstance(m, tnn.MaxPool2d):
            i += 1
            nodes.append(tf_node(f"pool{i}", "MaxPool", [cur],
                                 ints={"ksize": [1, 2, 2, 1],
                                       "strides": [1, 2, 2, 1]},
                                 strs={"padding": "VALID"}, types={"T": 1}))
            cur = f"pool{i}"
        elif isinstance(m, tnn.Flatten):
            # NHWC flatten differs from torch's NCHW flatten: transpose
            # the first FC's input features accordingly (below)
            nodes.append(tf_node("shape", "Const",
                                 tensor=np.asarray([-1, 2048], np.int32)))
            nodes.append(tf_node("flat", "Reshape", [cur, "shape"],
                                 types={"T": 1}))
            cur = "flat"
        elif isinstance(m, tnn.Linear):
            i += 1
            w = _t(m.weight).T                              # (in, out)
            if w.shape[0] == 2048:
                # torch flattened C,H,W; the graph flattens H,W,C
                w = (w.reshape(512, 2, 2, -1).transpose(1, 2, 0, 3)
                     .reshape(2048, -1))
            nodes.append(tf_node(f"fw{i}", "Const", tensor=w))
            nodes.append(tf_node(f"mm{i}", "MatMul", [cur, f"fw{i}"],
                                 types={"T": 1}))
            nodes.append(tf_node(f"fb{i}", "Const", tensor=_t(m.bias)))
            nodes.append(tf_node(f"out{i}", "BiasAdd", [f"mm{i}", f"fb{i}"],
                                 types={"T": 1}))
            cur = f"out{i}"

    r = np.random.RandomState(2)
    x_nchw = (r.randn(2, 3, 64, 64) * 0.5).astype(np.float32)
    module, params, state, _ = load_tf(b"".join(nodes))
    got, _ = module.apply(params, state,
                          jnp.asarray(x_nchw.transpose(0, 2, 3, 1)),
                          training=False)
    with torch.no_grad():
        want = tm(torch.from_numpy(x_nchw)).numpy()
    assert np.asarray(got).shape == want.shape == (2, 10)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------ .t7 weights
def test_lenet_through_t7_weight_table_matches_torch(tmp_path):
    """torch weights written as a .t7 weight table and pulled through the
    convert() path onto our LeNet-5 skeleton must reproduce torch's
    forward (the reference's Torch-model load,
    utils/TorchFile.scala + test/.../torch/LeNetSpec)."""
    from bigdl_tpu.interop import torchfile
    from bigdl_tpu.interop.convert import convert
    from bigdl_tpu.utils.serializer import load_module, save_module
    import bigdl_tpu.nn as nn
    from bigdl_tpu.core.container import Sequential

    torch.manual_seed(3)
    tm = tnn.Sequential(
        tnn.Conv2d(1, 6, 5, padding=2), tnn.Tanh(), tnn.MaxPool2d(2),
        tnn.Conv2d(6, 16, 5), tnn.Tanh(), tnn.MaxPool2d(2),
        tnn.Flatten(), tnn.Linear(16 * 5 * 5, 120), tnn.Tanh(),
        tnn.Linear(120, 84), tnn.Tanh(), tnn.Linear(84, 10),
        tnn.LogSoftmax(dim=-1))
    tm.eval()

    ours = Sequential(
        nn.SpatialConvolution(1, 6, 5, 5, pad_w=2, pad_h=2), nn.Tanh(),
        nn.SpatialMaxPooling(2, 2),
        nn.SpatialConvolution(6, 16, 5, 5), nn.Tanh(),
        nn.SpatialMaxPooling(2, 2),
        nn.Flatten(), nn.Linear(16 * 5 * 5, 120), nn.Tanh(),
        nn.Linear(120, 84), nn.Tanh(), nn.Linear(84, 10), nn.LogSoftMax())
    params, state = ours.init(jax.random.PRNGKey(0))
    skel = str(tmp_path / "lenet.bigdl-tpu")
    save_module(skel, ours, params, state)

    # weight table keyed by our param tree, values in OUR layouts
    # (conv HWIO from torch OIHW; linear (in,out) from torch (out,in);
    # torch NCHW-flatten -> our NHWC-flatten for the first FC)
    w_fc1 = _t(tm[7].weight).T
    w_fc1 = (w_fc1.reshape(16, 5, 5, -1).transpose(1, 2, 0, 3)
             .reshape(16 * 5 * 5, -1))
    table = {
        "0.weight": _t(tm[0].weight).transpose(2, 3, 1, 0),
        "0.bias": _t(tm[0].bias),
        "3.weight": _t(tm[3].weight).transpose(2, 3, 1, 0),
        "3.bias": _t(tm[3].bias),
        "7.weight": w_fc1, "7.bias": _t(tm[7].bias),
        "9.weight": _t(tm[9].weight).T, "9.bias": _t(tm[9].bias),
        "11.weight": _t(tm[11].weight).T, "11.bias": _t(tm[11].bias),
    }
    t7 = str(tmp_path / "lenet.t7")
    torchfile.save(t7, table)

    out_path = str(tmp_path / "imported.bigdl-tpu")
    convert(t7, out_path, module_path=skel)
    mod2, p2, s2 = load_module(out_path)

    r = np.random.RandomState(4)
    x_nchw = r.randn(4, 1, 28, 28).astype(np.float32)
    got, _ = mod2.apply(p2, s2, jnp.asarray(x_nchw.transpose(0, 2, 3, 1)),
                        training=False)
    with torch.no_grad():
        want = tm(torch.from_numpy(x_nchw)).numpy()
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
