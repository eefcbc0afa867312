"""Layer-breadth tail (VERDICT round-1 item 10): Maxout, LocallyConnected,
VolumetricFull/AveragePooling, BinaryTreeLSTM, control-flow/TensorArray ops,
criterion tail, histogram summaries (reference: nn/Maxout.scala,
nn/LocallyConnected2D.scala, nn/VolumetricFullConvolution.scala,
nn/BinaryTreeLSTM.scala, nn/tf/, nn/*Criterion*.scala,
optim/AbstractOptimizer.scala:47-91)."""

import numpy as np
import pytest
import torch

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.nn import ops


def _init(m, seed=0):
    return m.init(jax.random.PRNGKey(seed))


def test_maxout_semantics():
    m = nn.Maxout(6, 4, 3)
    p, s = _init(m)
    x = jnp.asarray(np.random.RandomState(0).randn(5, 6), jnp.float32)
    out, _ = m.apply(p, s, x)
    assert out.shape == (5, 4)
    y = np.asarray(x @ p["weight"] + p["bias"]).reshape(5, 3, 4)
    np.testing.assert_allclose(np.asarray(out), y.max(axis=1), atol=1e-5)


def test_locally_connected_2d_matches_untied_loop():
    r = np.random.RandomState(1)
    m = nn.LocallyConnected2D(3, 6, 5, 4, kernel_w=3, kernel_h=2,
                              stride_w=1, stride_h=1)
    p, s = _init(m)
    x = jnp.asarray(r.randn(2, 5, 6, 3), jnp.float32)   # NHWC (h=5, w=6)
    out, _ = m.apply(p, s, x)
    assert out.shape == (2, 4, 4, 4)                    # oh=4, ow=4
    w = np.asarray(p["weight"])                         # (oh, ow, kh*kw*cin, f)
    b = np.asarray(p["bias"])
    xn = np.asarray(x)
    want = np.zeros((2, 4, 4, 4), np.float32)
    for i in range(4):
        for j in range(4):
            patch = xn[:, i:i + 2, j:j + 3, :]          # (B, kh, kw, cin)
            # layer stacks kernel offsets k-major then cin
            flat = patch.transpose(0, 1, 2, 3).reshape(2, -1)
            want[:, i, j, :] = flat @ w[i, j] + b[i, j]
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)


def test_locally_connected_1d():
    r = np.random.RandomState(2)
    m = nn.LocallyConnected1D(7, 3, 5, kernel_w=3, stride_w=2)
    p, s = _init(m)
    x = jnp.asarray(r.randn(2, 7, 3), jnp.float32)
    out, _ = m.apply(p, s, x)
    assert out.shape == (2, 3, 5)
    w, b = np.asarray(p["weight"]), np.asarray(p["bias"])
    xn = np.asarray(x)
    for t in range(3):
        patch = xn[:, t * 2:t * 2 + 3, :].reshape(2, -1)
        np.testing.assert_allclose(np.asarray(out[:, t]),
                                   patch @ w[t] + b[t], atol=1e-4)


def test_volumetric_full_convolution_matches_torch():
    r = np.random.RandomState(3)
    m = nn.VolumetricFullConvolution(3, 5, 2, 3, 3, d_t=2, d_w=2, d_h=2,
                                     pad_t=1, pad_w=1, pad_h=1)
    p, s = _init(m)
    x = jnp.asarray(r.randn(1, 4, 4, 4, 3), jnp.float32)  # NDHWC
    out, _ = m.apply(p, s, x)
    # torch: NCDHW, weight (in, out, kt, kh, kw)
    w = np.asarray(p["weight"]).transpose(3, 4, 0, 1, 2)  # -> (in,out,t,h,w)
    want = torch.nn.functional.conv_transpose3d(
        torch.from_numpy(np.asarray(x).transpose(0, 4, 1, 2, 3)),
        torch.from_numpy(w), torch.from_numpy(np.asarray(p["bias"])),
        stride=2, padding=1).numpy().transpose(0, 2, 3, 4, 1)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)


def test_volumetric_average_pooling():
    r = np.random.RandomState(4)
    m = nn.VolumetricAveragePooling(2, 2, 2)
    p, s = _init(m)
    x = jnp.asarray(r.randn(1, 4, 4, 4, 2), jnp.float32)
    out, _ = m.apply(p, s, x)
    want = torch.nn.functional.avg_pool3d(
        torch.from_numpy(np.asarray(x).transpose(0, 4, 1, 2, 3)),
        2).numpy().transpose(0, 2, 3, 4, 1)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)


def test_binary_tree_lstm_trains():
    """Leaf/composer semantics + gradient flow on a 2-leaf tree."""
    m = nn.BinaryTreeLSTM(4, 8)
    p, s = _init(m)
    r = np.random.RandomState(5)
    x = jnp.asarray(r.randn(3, 2, 4), jnp.float32)
    tree = jnp.asarray(np.tile(np.array([[0, 0, 1], [0, 0, 2],
                                         [1, 2, -1]]), (3, 1, 1)),
                       jnp.int32)
    # (forward and backward jitted: one program each, not one per eager
    # op of the tree's unrolled node loop)
    out, _ = jax.jit(m.apply)(p, s, (x, tree))
    assert out.shape == (3, 3, 8)

    def loss(p):
        o, _ = m.apply(p, s, (x, tree))
        return jnp.sum(o[:, -1] ** 2)      # root states

    g = jax.jit(jax.grad(loss))(p)
    gn = sum(float(jnp.abs(l).sum()) for l in jax.tree.leaves(g))
    assert gn > 0
    # grads reach the leaf projection too (through the composer)
    assert float(jnp.abs(g["leaf_wc"]).sum()) > 0


def test_binary_tree_lstm_padding_rows_are_zero():
    m = nn.BinaryTreeLSTM(4, 8)
    p, s = _init(m)
    x = jnp.asarray(np.random.RandomState(6).randn(1, 2, 4), jnp.float32)
    tree = jnp.asarray([[[0, 0, 1], [0, 0, 2], [1, 2, -1],
                         [0, 0, 0]]], jnp.int32)      # last row = padding
    out, _ = m.apply(p, s, (x, tree))
    assert float(jnp.abs(out[0, 3]).max()) == 0.0


# ------------------------------------------------------------ control flow
def test_cond_op():
    m = ops.Cond(nn.MulConstant(2.0), nn.AddConstant(10.0))
    p, s = _init(m)
    x = jnp.asarray([1.0, 2.0])
    out_t, _ = m.apply(p, s, jnp.asarray(True), x)
    out_f, _ = m.apply(p, s, jnp.asarray(False), x)
    np.testing.assert_allclose(np.asarray(out_t), [2.0, 4.0])
    np.testing.assert_allclose(np.asarray(out_f), [11.0, 12.0])


def test_switch_and_merge():
    sw = ops.Switch()
    p, s = _init(sw)
    x = jnp.asarray([3.0, 4.0])
    f_out, t_out = sw.apply(p, s, x, jnp.asarray(True))[0]
    assert float(jnp.abs(f_out).max()) == 0.0
    np.testing.assert_allclose(np.asarray(t_out), [3.0, 4.0])
    mg = ops.MergeOps()
    pm, sm = _init(mg)
    out, _ = mg.apply(pm, sm, jnp.asarray([1.0]), jnp.asarray([2.0]),
                      jnp.asarray(1))
    np.testing.assert_allclose(np.asarray(out), [2.0])


def test_tensor_array_ops():
    ta = ops.TensorArrayCreate(4, (2,)).forward({})
    ta = ops.TensorArrayWrite().forward({}, ta, 1, jnp.asarray([1.0, 2.0]))
    ta = ops.TensorArrayScatter().forward(
        {}, ta, jnp.asarray([0, 3]), jnp.asarray([[9.0, 9.0], [7.0, 7.0]]))
    got = ops.TensorArrayRead().forward({}, ta, 3)
    np.testing.assert_allclose(np.asarray(got), [7.0, 7.0])
    stacked = ops.TensorArrayStack().forward({}, ta)
    assert stacked.shape == (4, 2)
    gathered = ops.TensorArrayGather().forward({}, ta, jnp.asarray([1, 0]))
    np.testing.assert_allclose(np.asarray(gathered),
                               [[1.0, 2.0], [9.0, 9.0]])
    flat = ops.TensorArrayConcat().forward({}, ta)
    assert flat.shape == (8,)


# -------------------------------------------------------------- criterions
def test_criterion_tail_matches_formulas():
    r = np.random.RandomState(7)
    x = r.rand(4, 6).astype(np.float32) + 0.1
    y = r.rand(4, 6).astype(np.float32) + 0.1
    xj, yj = jnp.asarray(x), jnp.asarray(y)

    # cosine distance / proximity
    cd = float(nn.CosineDistanceCriterion().forward(xj, yj))
    xn = x / np.linalg.norm(x, axis=-1, keepdims=True)
    yn = y / np.linalg.norm(y, axis=-1, keepdims=True)
    np.testing.assert_allclose(cd, np.mean(1 - (xn * yn).sum(-1)),
                               atol=1e-5)
    cp = float(nn.CosineProximityCriterion().forward(xj, yj))
    np.testing.assert_allclose(cp, -np.mean((xn * yn).sum(-1)), atol=1e-5)

    # dot product
    dp = float(nn.DotProductCriterion().forward(xj, yj))
    np.testing.assert_allclose(dp, -np.sum(x * y), rtol=1e-5)

    # keras KLD on distributions
    px = x / x.sum(-1, keepdims=True)
    py = y / y.sum(-1, keepdims=True)
    kl = float(nn.KullbackLeiblerDivergenceCriterion().forward(
        jnp.asarray(px), jnp.asarray(py)))
    np.testing.assert_allclose(kl, np.mean((py * np.log(py / px)).sum(-1)),
                               atol=1e-5)

    # MAPE / MSLE / Poisson vs keras formulas
    mape = float(nn.MeanAbsolutePercentageCriterion().forward(xj, yj))
    np.testing.assert_allclose(
        mape, 100 * np.mean(np.abs(y - x) / np.abs(y)), rtol=1e-4)
    msle = float(nn.MeanSquaredLogarithmicCriterion().forward(xj, yj))
    np.testing.assert_allclose(
        msle, np.mean((np.log1p(x) - np.log1p(y)) ** 2), rtol=1e-4)
    pois = float(nn.PoissonCriterion().forward(xj, yj))
    np.testing.assert_allclose(pois, np.mean(x - y * np.log(x + 1e-7)),
                               rtol=1e-4)


def test_l1_hinge_embedding_criterion():
    x1 = jnp.asarray([[1.0, 2.0], [0.0, 0.0]])
    x2 = jnp.asarray([[1.5, 2.0], [3.0, 4.0]])
    # y=1: loss = L1 distance; y=-1: max(0, margin - d)
    got = float(nn.L1HingeEmbeddingCriterion(margin=8.0).forward(
        (x1, x2), jnp.asarray([1.0, -1.0])))
    np.testing.assert_allclose(got, (0.5 + max(0.0, 8.0 - 7.0)) / 2,
                               atol=1e-6)


def test_softmax_with_criterion_ignore_label():
    r = np.random.RandomState(8)
    logits = r.randn(2, 3, 3, 4).astype(np.float32)     # NHWC, C=4
    labels = r.randint(0, 4, (2, 3, 3))
    labels[0, 0, 0] = 255
    got = float(nn.SoftmaxWithCriterion(ignore_label=255).forward(
        jnp.asarray(logits), jnp.asarray(labels)))
    # torch reference: NCHW cross entropy with ignore_index
    want = torch.nn.functional.cross_entropy(
        torch.from_numpy(logits.transpose(0, 3, 1, 2)),
        torch.from_numpy(labels.astype(np.int64)), ignore_index=255).item()
    np.testing.assert_allclose(got, want, rtol=1e-4)


# ------------------------------------------------------ histogram summaries
def test_histogram_event_roundtrip(tmp_path):
    from bigdl_tpu.visualization import TrainSummary
    ts = TrainSummary(str(tmp_path), "app")
    vals = np.random.RandomState(9).randn(1000)
    ts.add_histogram("params.fc.weight", vals, 7)
    ts.close()
    ts2 = TrainSummary(str(tmp_path), "app")
    hist = ts2.read_histogram("params.fc.weight")
    ts2.close()
    assert len(hist) == 1
    step, stats = hist[0]
    assert step == 7
    np.testing.assert_allclose(stats["num"], 1000)
    np.testing.assert_allclose(stats["sum"], vals.sum(), rtol=1e-6)
    np.testing.assert_allclose(stats["min"], vals.min(), rtol=1e-6)
    assert sum(stats["bucket"]) == 1000


def test_optimizer_writes_parameter_histograms(tmp_path):
    from bigdl_tpu.visualization import TrainSummary
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.dataset import ArrayDataSet

    r = np.random.RandomState(10)
    X = r.randn(32, 4).astype(np.float32)
    Y = (X.sum(1) > 0).astype(np.int32)
    model = nn.Sequential().add(nn.Linear(4, 2)).add(nn.LogSoftMax())
    ds = ArrayDataSet(X, Y, batch_size=16, shuffle=False)
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), SGD(0.1))
    ts = TrainSummary(str(tmp_path), "app")
    ts.set_summary_trigger("Parameters", Trigger.several_iteration(2))
    opt.set_train_summary(ts)
    opt.set_end_when(Trigger.max_iteration(4))
    opt.optimize()
    ts.close()
    ts2 = TrainSummary(str(tmp_path), "app")
    hist = ts2.read_histogram("0.weight")
    ts2.close()
    assert len(hist) >= 1                 # fired on the iteration cadence
    assert hist[0][1]["num"] == 8         # 4*2 weight entries

    # every_epoch trigger fires at epoch end too (regression: the hook was
    # only called inside the batch loop where epoch_finished is False)
    model2 = nn.Sequential().add(nn.Linear(4, 2)).add(nn.LogSoftMax())
    opt2 = Optimizer(model2, ds, nn.ClassNLLCriterion(), SGD(0.1))
    ts3 = TrainSummary(str(tmp_path / "e"), "app")
    ts3.set_summary_trigger("Parameters", Trigger.every_epoch())
    opt2.set_train_summary(ts3)
    opt2.set_end_when(Trigger.max_epoch(2))
    opt2.optimize()
    ts3.close()
    ts4 = TrainSummary(str(tmp_path / "e"), "app")
    assert len(ts4.read_histogram("0.weight")) == 2
    ts4.close()


def test_optimizer_writes_gradient_histograms(tmp_path):
    from bigdl_tpu.visualization import TrainSummary
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.dataset import ArrayDataSet

    r = np.random.RandomState(11)
    X = r.randn(32, 4).astype(np.float32)
    Y = (X.sum(1) > 0).astype(np.int32)
    model = nn.Sequential().add(nn.Linear(4, 2)).add(nn.LogSoftMax())
    ds = ArrayDataSet(X, Y, batch_size=16, shuffle=False)
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), SGD(0.1))
    ts = TrainSummary(str(tmp_path), "app")
    ts.set_summary_trigger("Parameters", Trigger.several_iteration(2))
    opt.set_train_summary(ts)
    opt.set_end_when(Trigger.max_iteration(4))
    opt.optimize()
    ts.close()
    ts2 = TrainSummary(str(tmp_path), "app")
    ghist = ts2.read_histogram("0.weight.grad")
    ts2.close()
    assert len(ghist) >= 1
    assert ghist[0][1]["num"] == 8
