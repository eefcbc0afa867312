"""Golden-model parity tests against PyTorch (CPU) — the analogue of the
reference's 132 Torch7 golden specs (test/.../torch/TH.scala: run torch,
compare within tolerance; SURVEY.md §4 maps this to 'compare vs PyTorch
goldens'). Weights are copied between frameworks with explicit layout
conversion, then outputs AND input-gradients are compared."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402

import bigdl_tpu.nn as nn                                    # noqa: E402


def _j2t(x):
    return torch.from_numpy(np.asarray(x).copy())


def _nhwc_to_torch(x):
    return _j2t(x).permute(0, 3, 1, 2)


def _torch_to_nhwc(t):
    return t.permute(0, 2, 3, 1).detach().numpy()


def _grad_pair(jfn, jx, tfn, tx):
    """Forward outputs + input grads for a scalar-sum objective."""
    jout = jfn(jnp.asarray(jx))
    jgrad = jax.grad(lambda x: jfn(x).sum())(jnp.asarray(jx))
    txt = _j2t(tx).requires_grad_(True)
    tout = tfn(txt)
    tout.sum().backward()
    return (np.asarray(jout), np.asarray(jgrad),
            tout.detach().numpy(), txt.grad.numpy())


def test_linear_matches_torch():
    r = np.random.RandomState(0)
    layer = nn.Linear(16, 8)
    params, state = layer.init(jax.random.PRNGKey(0))
    tl = torch.nn.Linear(16, 8)
    with torch.no_grad():
        tl.weight.copy_(_j2t(params["weight"]).T)     # ours (in,out)
        tl.bias.copy_(_j2t(params["bias"]))
    x = r.randn(4, 16).astype(np.float32)
    jo, jg, to, tg = _grad_pair(
        lambda x: layer.apply(params, state, x)[0], x, tl, x)
    np.testing.assert_allclose(jo, to, atol=1e-5)
    np.testing.assert_allclose(jg, tg, atol=1e-5)


def test_conv2d_matches_torch():
    r = np.random.RandomState(1)
    layer = nn.SpatialConvolution(3, 6, 3, 3, 2, 2, 1, 1)
    params, state = layer.init(jax.random.PRNGKey(0))
    tc = torch.nn.Conv2d(3, 6, 3, stride=2, padding=1)
    with torch.no_grad():
        # ours (kh, kw, cin, cout) -> torch (cout, cin, kh, kw)
        tc.weight.copy_(_j2t(np.transpose(params["weight"], (3, 2, 0, 1))))
        tc.bias.copy_(_j2t(params["bias"]))
    x = r.randn(2, 9, 9, 3).astype(np.float32)        # NHWC

    jo, jg, to, tg = _grad_pair(
        lambda x: layer.apply(params, state, x)[0], x,
        lambda x: tc(x.permute(0, 3, 1, 2)).permute(0, 2, 3, 1),
        x)
    np.testing.assert_allclose(jo, to, atol=1e-4)
    np.testing.assert_allclose(jg, tg, atol=1e-4)


def test_batchnorm_matches_torch_train_and_eval():
    r = np.random.RandomState(2)
    layer = nn.SpatialBatchNormalization(4, eps=1e-5, momentum=0.1)
    params, state = layer.init(jax.random.PRNGKey(0))
    tb = torch.nn.BatchNorm2d(4, eps=1e-5, momentum=0.1)
    x = r.randn(8, 5, 5, 4).astype(np.float32)

    # train step: outputs + updated running stats
    jout, new_state = layer.apply(params, state, jnp.asarray(x),
                                  training=True)
    tb.train()
    tout = tb(_j2t(x).permute(0, 3, 1, 2)).permute(0, 2, 3, 1)
    np.testing.assert_allclose(np.asarray(jout), tout.detach().numpy(),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(new_state["running_mean"]),
                               tb.running_mean.numpy(), atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_state["running_var"]),
                               tb.running_var.numpy(), atol=1e-4)

    # eval with those stats
    jeval, _ = layer.apply(params, new_state, jnp.asarray(x),
                           training=False)
    tb.eval()
    teval = tb(_j2t(x).permute(0, 3, 1, 2)).permute(0, 2, 3, 1)
    np.testing.assert_allclose(np.asarray(jeval), teval.detach().numpy(),
                               atol=1e-4)


def test_maxpool_avgpool_match_torch():
    r = np.random.RandomState(3)
    x = r.randn(2, 8, 8, 3).astype(np.float32)
    jmax = nn.SpatialMaxPooling(2, 2, 2, 2)
    jo, _ = jmax.apply({}, {}, jnp.asarray(x))
    to = torch.nn.functional.max_pool2d(
        _j2t(x).permute(0, 3, 1, 2), 2).permute(0, 2, 3, 1)
    np.testing.assert_allclose(np.asarray(jo), to.numpy(), atol=1e-6)

    javg = nn.SpatialAveragePooling(2, 2, 2, 2)
    jo, _ = javg.apply({}, {}, jnp.asarray(x))
    to = torch.nn.functional.avg_pool2d(
        _j2t(x).permute(0, 3, 1, 2), 2).permute(0, 2, 3, 1)
    np.testing.assert_allclose(np.asarray(jo), to.numpy(), atol=1e-6)


def test_lstm_matches_torch():
    r = np.random.RandomState(4)
    input_size, hidden = 6, 5
    cell = nn.LSTM(input_size, hidden)
    rec = nn.Recurrent(cell, return_sequences=True)
    params, state = rec.init(jax.random.PRNGKey(0))
    cp = params["cell"]

    tl = torch.nn.LSTM(input_size, hidden, batch_first=True)
    # ours: w_i (in, 4H), w_h (H, 4H), bias (4H) in i,f,g,o order?
    # torch: weight_ih (4H, in) in i,f,g,o order
    gates = ["i", "f", "g", "o"]
    if "w_i" in cp:
        wi = np.asarray(cp["w_i"]).T
        wh = np.asarray(cp["w_h"]).T
        b = np.asarray(cp["bias"])
    else:
        wi = np.concatenate([np.asarray(cp[f"w_i{g}"]).T for g in gates], 0)
        wh = np.concatenate([np.asarray(cp[f"w_h{g}"]).T for g in gates], 0)
        b = np.concatenate([np.asarray(cp[f"b_{g}"]) for g in gates], 0)
    with torch.no_grad():
        tl.weight_ih_l0.copy_(_j2t(wi))
        tl.weight_hh_l0.copy_(_j2t(wh))
        tl.bias_ih_l0.copy_(_j2t(b))
        tl.bias_hh_l0.zero_()
    x = r.randn(3, 7, input_size).astype(np.float32)
    jo, _ = rec.apply(params, state, jnp.asarray(x))
    to, _ = tl(_j2t(x))
    np.testing.assert_allclose(np.asarray(jo), to.detach().numpy(),
                               atol=1e-5)


def test_activations_match_torch():
    r = np.random.RandomState(5)
    x = r.randn(4, 10).astype(np.float32) * 3
    pairs = [
        (nn.ReLU(), torch.nn.functional.relu),
        (nn.Tanh(), torch.tanh),
        (nn.Sigmoid(), torch.sigmoid),
        (nn.ELU(), torch.nn.functional.elu),
        (nn.SoftPlus(), torch.nn.functional.softplus),
        (nn.LogSoftMax(), lambda t: torch.log_softmax(t, -1)),
        (nn.SoftMax(), lambda t: torch.softmax(t, -1)),
        (nn.GELU(), torch.nn.functional.gelu),
        (nn.HardTanh(), torch.nn.functional.hardtanh),
        (nn.LeakyReLU(), torch.nn.functional.leaky_relu),
    ]
    for jlayer, tfn in pairs:
        jo, _ = jlayer.apply({}, {}, jnp.asarray(x))
        to = tfn(_j2t(x))
        np.testing.assert_allclose(
            np.asarray(jo), to.numpy(), atol=2e-5,
            err_msg=type(jlayer).__name__)


def test_criterions_match_torch():
    r = np.random.RandomState(6)
    logits = r.randn(8, 5).astype(np.float32)
    target = r.randint(0, 5, 8).astype(np.int64)
    logp = jax.nn.log_softmax(jnp.asarray(logits))

    jl = nn.ClassNLLCriterion().forward(logp, jnp.asarray(target, jnp.int32))
    tl = torch.nn.functional.nll_loss(
        torch.log_softmax(_j2t(logits), -1), _j2t(target))
    np.testing.assert_allclose(float(jl), float(tl), atol=1e-5)

    pred = r.randn(8, 5).astype(np.float32)
    tgt = r.randn(8, 5).astype(np.float32)
    jm = nn.MSECriterion().forward(jnp.asarray(pred), jnp.asarray(tgt))
    tm = torch.nn.functional.mse_loss(_j2t(pred), _j2t(tgt))
    np.testing.assert_allclose(float(jm), float(tm), atol=1e-5)

    p = 1 / (1 + np.exp(-pred))
    t01 = (tgt > 0).astype(np.float32)
    jb = nn.BCECriterion().forward(jnp.asarray(p), jnp.asarray(t01))
    tb = torch.nn.functional.binary_cross_entropy(_j2t(p), _j2t(t01))
    np.testing.assert_allclose(float(jb), float(tb), atol=1e-5)

    js = nn.SmoothL1Criterion().forward(jnp.asarray(pred), jnp.asarray(tgt))
    ts = torch.nn.functional.smooth_l1_loss(_j2t(pred), _j2t(tgt))
    np.testing.assert_allclose(float(js), float(ts), atol=1e-5)


def test_layernorm_matches_torch():
    r = np.random.RandomState(7)
    layer = nn.LayerNormalization(12)
    params, state = layer.init(jax.random.PRNGKey(0))
    tl = torch.nn.LayerNorm(12, eps=layer.eps)
    with torch.no_grad():
        tl.weight.copy_(_j2t(params["weight"]).reshape(-1))
        tl.bias.copy_(_j2t(params["bias"]).reshape(-1))
    x = r.randn(4, 9, 12).astype(np.float32)
    jo, _ = layer.apply(params, state, jnp.asarray(x))
    to = tl(_j2t(x))
    np.testing.assert_allclose(np.asarray(jo), to.detach().numpy(),
                               atol=1e-5)


def test_embedding_matches_torch():
    r = np.random.RandomState(8)
    layer = nn.LookupTable(20, 6)
    params, state = layer.init(jax.random.PRNGKey(0))
    te = torch.nn.Embedding(20, 6)
    with torch.no_grad():
        te.weight.copy_(_j2t(params["weight"]))
    idx = r.randint(0, 20, (3, 5))
    jo, _ = layer.apply(params, state, jnp.asarray(idx, jnp.int32))
    to = te(_j2t(idx.astype(np.int64)))
    np.testing.assert_allclose(np.asarray(jo), to.detach().numpy(),
                               atol=1e-6)


# ======================================================================
# part 2: the 'hard parts' of SURVEY §7(a): ceil-mode pooling, LRN,
# RReLU train/eval, dilated/transposed/separable/1D/3D conv, GRU/vanilla RNN,
# the sizeAverage criterion matrix


# ------------------------------------------------------------------ pooling
@pytest.mark.parametrize("size,k,s,p", [(7, 3, 2, 0), (8, 3, 2, 1),
                                        (9, 2, 3, 0)])
def test_maxpool_ceil_mode(size, k, s, p):
    r = np.random.RandomState(0)
    x = r.randn(2, size, size, 3).astype(np.float32)
    layer = nn.SpatialMaxPooling(k, k, s, s, p, p, ceil_mode=True)
    jo, _ = layer.apply({}, {}, jnp.asarray(x))
    to = torch.nn.functional.max_pool2d(
        _nhwc_to_torch(x), k, s, p, ceil_mode=True)
    np.testing.assert_allclose(np.asarray(jo), _torch_to_nhwc(to), atol=1e-6)


@pytest.mark.parametrize("include_pad", [True, False])
@pytest.mark.parametrize("ceil_mode", [False, True])
def test_avgpool_padding_divisor_rules(include_pad, ceil_mode):
    r = np.random.RandomState(1)
    x = r.randn(2, 9, 9, 2).astype(np.float32)
    layer = nn.SpatialAveragePooling(3, 3, 2, 2, 1, 1, ceil_mode=ceil_mode,
                                     count_include_pad=include_pad)
    jo, _ = layer.apply({}, {}, jnp.asarray(x))
    to = torch.nn.functional.avg_pool2d(
        _nhwc_to_torch(x), 3, 2, 1, ceil_mode=ceil_mode,
        count_include_pad=include_pad)
    np.testing.assert_allclose(np.asarray(jo), _torch_to_nhwc(to), atol=1e-5)


def test_volumetric_maxpool():
    r = np.random.RandomState(2)
    x = r.randn(2, 6, 8, 8, 2).astype(np.float32)     # NDHWC
    layer = nn.VolumetricMaxPooling(2, 2, 2)
    jo, _ = layer.apply({}, {}, jnp.asarray(x))
    to = torch.nn.functional.max_pool3d(
        _j2t(x).permute(0, 4, 1, 2, 3), 2)
    np.testing.assert_allclose(np.asarray(jo),
                               to.permute(0, 2, 3, 4, 1).numpy(), atol=1e-6)


def test_adaptive_maxpool():
    r = np.random.RandomState(3)
    x = r.randn(2, 12, 12, 3).astype(np.float32)
    layer = nn.SpatialAdaptiveMaxPooling(4, 4)
    jo, _ = layer.apply({}, {}, jnp.asarray(x))
    to = torch.nn.functional.adaptive_max_pool2d(_nhwc_to_torch(x), 4)
    np.testing.assert_allclose(np.asarray(jo), _torch_to_nhwc(to), atol=1e-6)


# -------------------------------------------------------------------- norms
@pytest.mark.parametrize("size,alpha,beta,k", [(5, 1e-4, 0.75, 1.0),
                                               (3, 2e-4, 0.6, 2.0)])
def test_lrn_matches_torch(size, alpha, beta, k):
    r = np.random.RandomState(4)
    x = (r.randn(2, 6, 6, 8) * 5).astype(np.float32)
    layer = nn.SpatialCrossMapLRN(size, alpha, beta, k)
    jo, _ = layer.apply({}, {}, jnp.asarray(x))
    to = torch.nn.functional.local_response_norm(
        _nhwc_to_torch(x), size, alpha=alpha, beta=beta, k=k)
    np.testing.assert_allclose(np.asarray(jo), _torch_to_nhwc(to),
                               atol=1e-5, rtol=1e-5)


def test_l2_normalize_matches_torch():
    r = np.random.RandomState(5)
    x = r.randn(4, 10).astype(np.float32)
    jo, _ = nn.Normalize(2.0).apply({}, {}, jnp.asarray(x))
    to = torch.nn.functional.normalize(_j2t(x), p=2.0, dim=-1)
    np.testing.assert_allclose(np.asarray(jo), to.numpy(), atol=1e-6)


# -------------------------------------------------------------- activations
def test_rrelu_eval_matches_torch_and_train_in_bounds():
    r = np.random.RandomState(6)
    x = (r.randn(64, 32) * 2).astype(np.float32)
    lower, upper = 1 / 8, 1 / 3
    layer = nn.RReLU(lower, upper)
    # eval: deterministic mean slope — exact parity
    jo, _ = layer.apply({}, {}, jnp.asarray(x), training=False)
    to = torch.nn.functional.rrelu(_j2t(x), lower, upper, training=False)
    np.testing.assert_allclose(np.asarray(jo), to.numpy(), atol=1e-6)
    # train: slopes random per element, bounded by [lower, upper]
    jt, _ = layer.apply({}, {}, jnp.asarray(x), training=True,
                        rng=jax.random.PRNGKey(0))
    jt = np.asarray(jt)
    neg = x < 0
    slopes = jt[neg] / x[neg]
    assert slopes.min() >= lower - 1e-6 and slopes.max() <= upper + 1e-6
    assert abs(slopes.mean() - (lower + upper) / 2) < 0.02
    np.testing.assert_array_equal(jt[~neg], x[~neg])


def test_more_activations_match_torch():
    r = np.random.RandomState(7)
    x = (r.randn(4, 10) * 3).astype(np.float32)
    pairs = [
        (nn.SELU(), torch.nn.functional.selu),
        (nn.ReLU6(), torch.nn.functional.relu6),
        (nn.SoftSign(), torch.nn.functional.softsign),
        (nn.SoftMin(), lambda t: torch.softmax(-t, -1)),
        (nn.Swish(), torch.nn.functional.silu),
        (nn.Threshold(0.5, -2.0),
         lambda t: torch.nn.functional.threshold(t, 0.5, -2.0)),
        (nn.SoftPlus(beta=2.0),
         lambda t: torch.nn.functional.softplus(t, beta=2.0)),
        (nn.LeakyReLU(0.2),
         lambda t: torch.nn.functional.leaky_relu(t, 0.2)),
        (nn.HardTanh(-2.0, 2.0),
         lambda t: torch.nn.functional.hardtanh(t, -2.0, 2.0)),
    ]
    for jlayer, tfn in pairs:
        jo, _ = jlayer.apply({}, {}, jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(jo), tfn(_j2t(x)).numpy(),
                                   atol=2e-5, err_msg=type(jlayer).__name__)


def test_prelu_matches_torch():
    r = np.random.RandomState(8)
    x = r.randn(4, 6).astype(np.float32)
    layer = nn.PReLU(6)
    params, state = layer.init(jax.random.PRNGKey(0))
    slopes = (r.rand(6) * 0.5).astype(np.float32)
    params = {"weight": jnp.asarray(slopes)}
    jo, _ = layer.apply(params, state, jnp.asarray(x))
    to = torch.nn.functional.prelu(_j2t(x), _j2t(slopes))
    np.testing.assert_allclose(np.asarray(jo), to.numpy(), atol=1e-6)


# ------------------------------------------------------------- convolutions
def test_dilated_conv_matches_torch():
    r = np.random.RandomState(9)
    layer = nn.SpatialDilatedConvolution(3, 5, 3, 3, 1, 1, 2, 2, 2, 2)
    params, state = layer.init(jax.random.PRNGKey(0))
    tc = torch.nn.Conv2d(3, 5, 3, stride=1, padding=2, dilation=2)
    with torch.no_grad():
        tc.weight.copy_(_j2t(np.transpose(params["weight"], (3, 2, 0, 1))))
        tc.bias.copy_(_j2t(params["bias"]))
    x = r.randn(2, 10, 10, 3).astype(np.float32)
    jo, _ = layer.apply(params, state, jnp.asarray(x))
    to = tc(_nhwc_to_torch(x))
    np.testing.assert_allclose(np.asarray(jo), _torch_to_nhwc(to), atol=1e-4)


@pytest.mark.parametrize("stride,pad,adj", [(2, 1, 0), (2, 0, 1), (3, 1, 0)])
def test_transposed_conv_matches_torch(stride, pad, adj):
    r = np.random.RandomState(10)
    layer = nn.SpatialFullConvolution(4, 3, 3, 3, stride, stride, pad, pad,
                                      adj, adj)
    params, state = layer.init(jax.random.PRNGKey(0))
    tc = torch.nn.ConvTranspose2d(4, 3, 3, stride=stride, padding=pad,
                                  output_padding=adj)
    with torch.no_grad():
        # ours (kh, kw, nin, nout) -> torch (nin, nout, kh, kw)
        tc.weight.copy_(_j2t(np.transpose(params["weight"], (2, 3, 0, 1))))
        tc.bias.copy_(_j2t(params["bias"]))
    x = r.randn(2, 6, 6, 4).astype(np.float32)
    jo, _ = layer.apply(params, state, jnp.asarray(x))
    to = tc(_nhwc_to_torch(x))
    np.testing.assert_allclose(np.asarray(jo), _torch_to_nhwc(to), atol=1e-4)


def test_separable_conv_matches_torch():
    r = np.random.RandomState(11)
    nin, nout, mult = 3, 8, 2
    layer = nn.SpatialSeparableConvolution(nin, nout, mult, 3, 3, 1, 1, 1, 1)
    params, state = layer.init(jax.random.PRNGKey(0))
    tdw = torch.nn.Conv2d(nin, nin * mult, 3, padding=1, groups=nin,
                          bias=False)
    tpw = torch.nn.Conv2d(nin * mult, nout, 1)
    with torch.no_grad():
        # ours depth (kh, kw, 1, nin*mult) — feature_group_count=nin means
        # output channel c comes from input group c // mult
        tdw.weight.copy_(_j2t(np.transpose(
            params["depth_weight"], (3, 2, 0, 1))))
        tpw.weight.copy_(_j2t(np.transpose(
            params["point_weight"], (3, 2, 0, 1))))
        tpw.bias.copy_(_j2t(params["bias"]))
    x = r.randn(2, 7, 7, nin).astype(np.float32)
    jo, _ = layer.apply(params, state, jnp.asarray(x))
    to = tpw(tdw(_nhwc_to_torch(x)))
    np.testing.assert_allclose(np.asarray(jo), _torch_to_nhwc(to), atol=1e-4)


def test_temporal_conv_matches_torch():
    r = np.random.RandomState(12)
    layer = nn.TemporalConvolution(6, 4, 3, 2)
    params, state = layer.init(jax.random.PRNGKey(0))
    tc = torch.nn.Conv1d(6, 4, 3, stride=2)
    with torch.no_grad():
        # ours (kw, cin, cout) -> torch (cout, cin, kw)
        tc.weight.copy_(_j2t(np.transpose(params["weight"], (2, 1, 0))))
        tc.bias.copy_(_j2t(params["bias"]))
    x = r.randn(2, 11, 6).astype(np.float32)         # NTC
    jo, _ = layer.apply(params, state, jnp.asarray(x))
    to = tc(_j2t(x).permute(0, 2, 1)).permute(0, 2, 1)
    np.testing.assert_allclose(np.asarray(jo), to.detach().numpy(),
                               atol=1e-5)


def test_volumetric_conv_matches_torch():
    r = np.random.RandomState(13)
    layer = nn.VolumetricConvolution(2, 4, 3, 3, 3, 2, 2, 2, 1, 1, 1)
    params, state = layer.init(jax.random.PRNGKey(0))
    tc = torch.nn.Conv3d(2, 4, 3, stride=2, padding=1)
    with torch.no_grad():
        # ours (kt, kh, kw, cin, cout) -> torch (cout, cin, kt, kh, kw)
        tc.weight.copy_(_j2t(np.transpose(params["weight"], (4, 3, 0, 1, 2))))
        tc.bias.copy_(_j2t(params["bias"]))
    x = r.randn(2, 5, 7, 7, 2).astype(np.float32)    # NDHWC
    jo, _ = layer.apply(params, state, jnp.asarray(x))
    to = tc(_j2t(x).permute(0, 4, 1, 2, 3)).permute(0, 2, 3, 4, 1)
    np.testing.assert_allclose(np.asarray(jo), to.detach().numpy(),
                               atol=1e-4)


# --------------------------------------------------------------- recurrence
def test_gru_matches_torch_autograd():
    """Our GRU is the reference's Cho variant — candidate = tanh(Wx + U(r⊙h))
    (reference: nn/GRU.scala buildModel h2g3(r*h)); torch.nn.GRU is the cudnn
    variant r⊙(Uh). Parity is checked against a torch-autograd replica of the
    same math, incl. input gradients."""
    r = np.random.RandomState(14)
    input_size, hidden = 5, 4
    cell = nn.GRU(input_size, hidden)
    rec = nn.Recurrent(cell, return_sequences=True)
    params, state = rec.init(jax.random.PRNGKey(0))
    cp = params["cell"]
    wi = _j2t(cp["w_i"])
    wh = _j2t(cp["w_h"])
    whc = _j2t(cp["w_hc"])
    b = _j2t(cp["bias"])

    def tgru(x):
        h = torch.zeros(x.shape[0], hidden)
        outs = []
        for t in range(x.shape[1]):
            xi = x[:, t] @ wi + b
            hr_hu = h @ wh
            rg = torch.sigmoid(xi[:, :hidden] + hr_hu[:, :hidden])
            u = torch.sigmoid(xi[:, hidden:2 * hidden] + hr_hu[:, hidden:])
            cand = torch.tanh(xi[:, 2 * hidden:] + (rg * h) @ whc)
            h = u * h + (1.0 - u) * cand
            outs.append(h)
        return torch.stack(outs, 1)

    x = r.randn(3, 6, input_size).astype(np.float32)
    jo, _ = rec.apply(params, state, jnp.asarray(x))
    jg = jax.grad(lambda v: rec.apply(params, state, v)[0].sum())(
        jnp.asarray(x))
    tx = _j2t(x).requires_grad_(True)
    to = tgru(tx)
    to.sum().backward()
    np.testing.assert_allclose(np.asarray(jo), to.detach().numpy(), atol=1e-5)
    np.testing.assert_allclose(np.asarray(jg), tx.grad.numpy(), atol=1e-5)


def test_vanilla_rnn_matches_torch():
    r = np.random.RandomState(15)
    input_size, hidden = 4, 3
    cell = nn.RnnCell(input_size, hidden)
    rec = nn.Recurrent(cell, return_sequences=True)
    params, state = rec.init(jax.random.PRNGKey(0))
    cp = params["cell"]
    tr = torch.nn.RNN(input_size, hidden, batch_first=True)
    with torch.no_grad():
        tr.weight_ih_l0.copy_(_j2t(np.asarray(cp["w_i"]).T))
        tr.weight_hh_l0.copy_(_j2t(np.asarray(cp["w_h"]).T))
        tr.bias_ih_l0.copy_(_j2t(cp["bias"]))
        tr.bias_hh_l0.zero_()
    x = r.randn(2, 5, input_size).astype(np.float32)
    jo, _ = rec.apply(params, state, jnp.asarray(x))
    to, _ = tr(_j2t(x))
    np.testing.assert_allclose(np.asarray(jo), to.detach().numpy(),
                               atol=1e-5)


# --------------------------------------------------- criterions (reductions)
def test_classnll_weights_ignore_and_sum():
    r = np.random.RandomState(16)
    logits = r.randn(8, 5).astype(np.float32)
    target = r.randint(0, 5, 8).astype(np.int64)
    weights = (r.rand(5) + 0.5).astype(np.float32)
    logp_t = torch.log_softmax(_j2t(logits), -1)
    logp_j = jax.nn.log_softmax(jnp.asarray(logits))
    tj = jnp.asarray(target, jnp.int32)

    # weighted mean: torch divides by total weight, like the reference
    jl = nn.ClassNLLCriterion(weights=weights).forward(logp_j, tj)
    tl = torch.nn.functional.nll_loss(logp_t, _j2t(target),
                                      weight=_j2t(weights))
    np.testing.assert_allclose(float(jl), float(tl), atol=1e-5)

    # sum reduction (sizeAverage=false)
    jl = nn.ClassNLLCriterion(size_average=False).forward(logp_j, tj)
    tl = torch.nn.functional.nll_loss(logp_t, _j2t(target), reduction="sum")
    np.testing.assert_allclose(float(jl), float(tl), atol=1e-4)

    # ignore_index
    target[:3] = 2
    tj = jnp.asarray(target, jnp.int32)
    jl = nn.ClassNLLCriterion(ignore_index=2).forward(logp_j, tj)
    tl = torch.nn.functional.nll_loss(logp_t, _j2t(target), ignore_index=2)
    np.testing.assert_allclose(float(jl), float(tl), atol=1e-5)

    # CrossEntropy = fused logits path
    jl = nn.CrossEntropyCriterion().forward(jnp.asarray(logits), tj)
    tl = torch.nn.functional.cross_entropy(_j2t(logits), _j2t(target))
    np.testing.assert_allclose(float(jl), float(tl), atol=1e-5)


def test_criterion_matrix_matches_torch():
    r = np.random.RandomState(17)
    a = r.randn(6, 4).astype(np.float32)
    b = r.randn(6, 4).astype(np.float32)
    y1 = np.sign(r.randn(6)).astype(np.float32)
    ja, jb, jy = jnp.asarray(a), jnp.asarray(b), jnp.asarray(y1)

    cases = [
        (nn.AbsCriterion().forward(ja, jb),
         torch.nn.functional.l1_loss(_j2t(a), _j2t(b))),
        (nn.AbsCriterion(size_average=False).forward(ja, jb),
         torch.nn.functional.l1_loss(_j2t(a), _j2t(b), reduction="sum")),
        (nn.MSECriterion(size_average=False).forward(ja, jb),
         torch.nn.functional.mse_loss(_j2t(a), _j2t(b), reduction="sum")),
        (nn.KLDivCriterion().forward(
            jax.nn.log_softmax(ja), jax.nn.softmax(jb)),
         torch.nn.functional.kl_div(torch.log_softmax(_j2t(a), -1),
                                    torch.softmax(_j2t(b), -1))),
        # ours defaults margin=1.0 (reference/Torch7); torch.nn defaults 0
        (nn.MarginRankingCriterion().forward(
            (ja[:, 0], jb[:, 0]), jy),
         torch.nn.functional.margin_ranking_loss(
             _j2t(a[:, 0]), _j2t(b[:, 0]), _j2t(y1), margin=1.0)),
        (nn.HingeEmbeddingCriterion().forward(jnp.abs(ja[:, 0]), jy),
         torch.nn.functional.hinge_embedding_loss(
             _j2t(np.abs(a[:, 0])), _j2t(y1))),
        (nn.CosineEmbeddingCriterion().forward((ja, jb), jy),
         torch.nn.functional.cosine_embedding_loss(
             _j2t(a), _j2t(b), _j2t(y1))),
        (nn.SoftMarginCriterion().forward(ja[:, 0], jy),
         torch.nn.functional.soft_margin_loss(_j2t(a[:, 0]), _j2t(y1))),
        (nn.BCECriterionWithLogits().forward(
            ja, jnp.asarray((b > 0).astype(np.float32))),
         torch.nn.functional.binary_cross_entropy_with_logits(
             _j2t(a), _j2t((b > 0).astype(np.float32)))),
    ]
    for i, (jl, tl) in enumerate(cases):
        np.testing.assert_allclose(float(jl), float(tl), atol=2e-5,
                                   err_msg=f"case {i}")


def test_multimargin_and_multilabel_soft_margin():
    r = np.random.RandomState(18)
    x = r.randn(5, 4).astype(np.float32)
    t = r.randint(0, 4, 5)
    jl = nn.MultiMarginCriterion().forward(jnp.asarray(x),
                                           jnp.asarray(t, jnp.int32))
    tl = torch.nn.functional.multi_margin_loss(_j2t(x), _j2t(t.astype(np.int64)))
    np.testing.assert_allclose(float(jl), float(tl), atol=1e-5)

    labels = (r.rand(5, 4) > 0.5).astype(np.float32)
    jl = nn.MultiLabelSoftMarginCriterion().forward(jnp.asarray(x),
                                                    jnp.asarray(labels))
    tl = torch.nn.functional.multilabel_soft_margin_loss(_j2t(x), _j2t(labels))
    np.testing.assert_allclose(float(jl), float(tl), atol=1e-5)


# ------------------------------------------------------------- dropout/misc
def test_dropout_eval_identity_train_scales():
    r = np.random.RandomState(19)
    x = r.randn(512, 8).astype(np.float32) + 5.0
    layer = nn.Dropout(0.4)
    jo, _ = layer.apply({}, {}, jnp.asarray(x), training=False)
    np.testing.assert_array_equal(np.asarray(jo), x)   # eval = identity
    jt, _ = layer.apply({}, {}, jnp.asarray(x), training=True,
                        rng=jax.random.PRNGKey(1))
    jt = np.asarray(jt)
    kept = jt != 0
    # inverted dropout: kept values scaled by 1/(1-p); mean preserved
    np.testing.assert_allclose(jt[kept], (x / 0.6)[kept], rtol=1e-5)
    assert abs(kept.mean() - 0.6) < 0.03
    assert abs(jt.mean() - x.mean()) < 0.25


def test_grad_parity_conv_chain():
    """Input-gradient parity through a conv→pool→LRN→fc chain — backward
    semantics of the composition, not just forwards."""
    r = np.random.RandomState(20)
    conv = nn.SpatialConvolution(3, 4, 3, 3, 1, 1, 1, 1)
    model = nn.Sequential(conv, nn.ReLU(),
                          nn.SpatialMaxPooling(2, 2, 2, 2, ceil_mode=True),
                          nn.SpatialCrossMapLRN(3, 1e-3, 0.75, 1.0))
    params, state = model.init(jax.random.PRNGKey(0))
    cp = params[conv.name] if conv.name in params else params
    # locate conv params in the tree
    flat = jax.tree_util.tree_leaves_with_path(params)
    wt = {"/".join(str(k) for k in path): leaf for path, leaf in flat}
    wkey = next(k for k in wt if "weight" in k)
    bkey = next(k for k in wt if "bias" in k)

    tconv = torch.nn.Conv2d(3, 4, 3, padding=1)
    with torch.no_grad():
        tconv.weight.copy_(_j2t(np.transpose(wt[wkey], (3, 2, 0, 1))))
        tconv.bias.copy_(_j2t(wt[bkey]))

    def tmodel(tx):
        h = torch.relu(tconv(tx.permute(0, 3, 1, 2)))
        h = torch.nn.functional.max_pool2d(h, 2, ceil_mode=True)
        h = torch.nn.functional.local_response_norm(h, 3, alpha=1e-3,
                                                    beta=0.75, k=1.0)
        return h.permute(0, 2, 3, 1)

    x = r.randn(2, 7, 7, 3).astype(np.float32)
    jfn = lambda v: model.apply(params, state, v)[0]
    jo = jfn(jnp.asarray(x))
    jg = jax.grad(lambda v: jfn(v).sum())(jnp.asarray(x))
    tx = _j2t(x).requires_grad_(True)
    to = tmodel(tx)
    to.sum().backward()
    np.testing.assert_allclose(np.asarray(jo), to.detach().numpy(), atol=1e-4)
    np.testing.assert_allclose(np.asarray(jg), tx.grad.numpy(), atol=1e-4)


# ======================================================================
# part 3: Bilinear, grouped conv, upsampling, temporal/padding ops,
# bidirectional LSTM, embedding-style criterions


def test_bilinear_matches_torch():
    r = np.random.RandomState(0)
    m = nn.Bilinear(4, 5, 3)
    params, state = m.init(jax.random.PRNGKey(0))
    x1 = r.randn(6, 4).astype(np.float32)
    x2 = r.randn(6, 5).astype(np.float32)
    out = m.forward(params, (jnp.asarray(x1), jnp.asarray(x2)))
    tm = torch.nn.Bilinear(4, 5, 3)
    with torch.no_grad():
        tm.weight.copy_(_j2t(params["weight"]))
        tm.bias.copy_(_j2t(params["bias"]))
    want = tm(_j2t(x1), _j2t(x2)).detach().numpy()
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("groups", [2, 4])
def test_grouped_conv_matches_torch(groups):
    r = np.random.RandomState(1)
    cin, cout = 8, 12
    m = nn.SpatialConvolution(cin, cout, 3, 3, pad_w=1, pad_h=1,
                              n_group=groups)
    params, state = m.init(jax.random.PRNGKey(1))
    x = r.randn(2, 6, 6, cin).astype(np.float32)
    out, _ = m.apply(params, state, jnp.asarray(x))
    tm = torch.nn.Conv2d(cin, cout, 3, padding=1, groups=groups)
    with torch.no_grad():
        # ours (kh, kw, cin/g, cout) -> torch (cout, cin/g, kh, kw)
        tm.weight.copy_(_j2t(params["weight"]).permute(3, 2, 0, 1))
        tm.bias.copy_(_j2t(params["bias"]))
    want = _torch_to_nhwc(tm(_nhwc_to_torch(x)))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-4)


def test_upsampling_matches_torch():
    r = np.random.RandomState(2)
    x = r.randn(2, 3, 4, 5).astype(np.float32)
    out, _ = nn.UpSampling2D((2, 3)).init(jax.random.PRNGKey(0)) and \
        nn.UpSampling2D((2, 3)).apply({}, {}, jnp.asarray(x))
    want = _torch_to_nhwc(torch.nn.Upsample(scale_factor=(2, 3),
                                            mode="nearest")
                          (_nhwc_to_torch(x)))
    np.testing.assert_allclose(np.asarray(out), want)

    x1 = r.randn(2, 5, 3).astype(np.float32)              # (N, T, C)
    out1, _ = nn.UpSampling1D(2).apply({}, {}, jnp.asarray(x1))
    want1 = torch.nn.Upsample(scale_factor=2, mode="nearest")(
        _j2t(x1).permute(0, 2, 1)).permute(0, 2, 1).numpy()
    np.testing.assert_allclose(np.asarray(out1), want1)


def test_resize_bilinear_matches_torch():
    r = np.random.RandomState(3)
    x = r.randn(2, 5, 7, 3).astype(np.float32)
    for align in (False, True):
        m = nn.ResizeBilinear(10, 14, align_corners=align)
        out, _ = m.apply({}, {}, jnp.asarray(x))
        want = _torch_to_nhwc(torch.nn.functional.interpolate(
            _nhwc_to_torch(x), size=(10, 14), mode="bilinear",
            align_corners=align))
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                                   atol=1e-5, err_msg=f"align={align}")


def test_temporal_maxpool_and_zero_padding():
    r = np.random.RandomState(4)
    x = r.randn(2, 9, 4).astype(np.float32)
    out, _ = nn.TemporalMaxPooling(3, 2).apply({}, {}, jnp.asarray(x))
    want = torch.nn.MaxPool1d(3, 2)(_j2t(x).permute(0, 2, 1)) \
        .permute(0, 2, 1).numpy()
    np.testing.assert_allclose(np.asarray(out), want)

    xi = r.randn(1, 3, 4, 2).astype(np.float32)
    out2, _ = nn.SpatialZeroPadding(1, 2, 3, 0).apply({}, {},
                                                      jnp.asarray(xi))
    want2 = _torch_to_nhwc(torch.nn.ZeroPad2d((1, 2, 3, 0))
                           (_nhwc_to_torch(xi)))
    np.testing.assert_allclose(np.asarray(out2), want2)


def test_bidirectional_lstm_matches_torch():
    r = np.random.RandomState(5)
    d, h, t, b = 3, 4, 6, 2
    m = nn.BiRecurrent(nn.LSTM(d, h), nn.LSTM(d, h))
    params, state = m.init(jax.random.PRNGKey(5))
    x = r.randn(b, t, d).astype(np.float32)
    out, _ = m.apply(params, state, jnp.asarray(x))

    tm = torch.nn.LSTM(d, h, batch_first=True, bidirectional=True)

    def set_dir(prefix, p):
        # ours packs gates [i f g o] like torch LSTM; w_i is (in, 4H)
        getattr(tm, f"weight_ih_{prefix}").data.copy_(_j2t(p["w_i"]).T)
        getattr(tm, f"weight_hh_{prefix}").data.copy_(_j2t(p["w_h"]).T)
        getattr(tm, f"bias_ih_{prefix}").data.copy_(_j2t(p["bias"]))
        getattr(tm, f"bias_hh_{prefix}").data.zero_()
    with torch.no_grad():
        set_dir("l0", params["fwd"]["cell"])
        set_dir("l0_reverse", params["bwd"]["cell"])
    want, _ = tm(_j2t(x))
    np.testing.assert_allclose(np.asarray(out), want.detach().numpy(),
                               rtol=1e-4, atol=1e-5)


def test_embedding_criterions_match_torch():
    r = np.random.RandomState(6)
    x1 = r.randn(8, 5).astype(np.float32)
    x2 = r.randn(8, 5).astype(np.float32)
    y = np.sign(r.randn(8)).astype(np.float32)

    ours = nn.CosineEmbeddingCriterion(margin=0.2).forward(
        (jnp.asarray(x1), jnp.asarray(x2)), jnp.asarray(y))
    want = torch.nn.CosineEmbeddingLoss(margin=0.2)(
        _j2t(x1), _j2t(x2), _j2t(y)).item()
    np.testing.assert_allclose(float(ours), want, rtol=1e-5)

    a = r.randn(8).astype(np.float32)
    b = r.randn(8).astype(np.float32)
    ours = nn.MarginRankingCriterion(margin=0.5).forward(
        (jnp.asarray(a), jnp.asarray(b)), jnp.asarray(y))
    want = torch.nn.MarginRankingLoss(margin=0.5)(
        _j2t(a), _j2t(b), _j2t(y)).item()
    np.testing.assert_allclose(float(ours), want, rtol=1e-5)

    xh = np.abs(r.randn(8)).astype(np.float32)
    ours = nn.HingeEmbeddingCriterion(margin=1.0).forward(
        jnp.asarray(xh), jnp.asarray(y))
    want = torch.nn.HingeEmbeddingLoss(margin=1.0)(
        _j2t(xh), _j2t(y)).item()
    np.testing.assert_allclose(float(ours), want, rtol=1e-5)

    xs = r.randn(8, 3).astype(np.float32)
    ys = np.sign(r.randn(8, 3)).astype(np.float32)
    ours = nn.SoftMarginCriterion().forward(jnp.asarray(xs),
                                            jnp.asarray(ys))
    want = torch.nn.SoftMarginLoss()(_j2t(xs), _j2t(ys)).item()
    np.testing.assert_allclose(float(ours), want, rtol=1e-5)


def test_kldiv_matches_torch():
    r = np.random.RandomState(7)
    logp = torch.log_softmax(_j2t(r.randn(6, 4).astype(np.float32)), -1)
    target = torch.softmax(_j2t(r.randn(6, 4).astype(np.float32)), -1)
    ours = nn.KLDivCriterion(size_average=True).forward(
        jnp.asarray(logp.numpy()), jnp.asarray(target.numpy()))
    want = torch.nn.KLDivLoss(reduction="mean")(logp, target).item()
    np.testing.assert_allclose(float(ours), want, rtol=1e-5)


def test_cmul_cadd_match_torch_broadcast():
    r = np.random.RandomState(8)
    x = r.randn(4, 6).astype(np.float32)
    m = nn.CMul((1, 6))
    params, _ = m.init(jax.random.PRNGKey(8))
    out = m.forward(params, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out),
                               x * np.asarray(params["weight"]),
                               rtol=1e-6)
    a = nn.CAdd((1, 6))
    pa, _ = a.init(jax.random.PRNGKey(9))
    np.testing.assert_allclose(np.asarray(a.forward(pa, jnp.asarray(x))),
                               x + np.asarray(pa["bias"]), rtol=1e-6)


# ======================================================================
# part 4: attention vs torch MultiheadAttention (weight-for-weight),
# similarity layers, lookup/shape ops


def test_multihead_attention_matches_torch():
    d, h, t, b = 16, 4, 6, 2
    r = np.random.RandomState(0)
    m = nn.MultiHeadAttention(d, h)
    params, state = m.init(jax.random.PRNGKey(0))
    x = r.randn(b, t, d).astype(np.float32)
    out, _ = m.apply(params, state, jnp.asarray(x))

    tm = torch.nn.MultiheadAttention(d, h, batch_first=True, bias=False)
    with torch.no_grad():
        # torch packs in_proj as rows [q; k; v], each (d, d) with y = W x
        # (left-multiply); ours are (d, d) right-multiply -> transpose
        packed = np.concatenate([np.asarray(params["wq"]).T,
                                 np.asarray(params["wk"]).T,
                                 np.asarray(params["wv"]).T], axis=0)
        tm.in_proj_weight.copy_(_j2t(packed))
        tm.out_proj.weight.copy_(_j2t(np.asarray(params["wo"]).T))
    want, _ = tm(_j2t(x), _j2t(x), _j2t(x), need_weights=False)
    np.testing.assert_allclose(np.asarray(out), want.detach().numpy(),
                               rtol=1e-4, atol=1e-5)


def test_multihead_attention_causal_matches_torch():
    d, h, t = 8, 2, 5
    r = np.random.RandomState(1)
    m = nn.MultiHeadAttention(d, h)
    params, state = m.init(jax.random.PRNGKey(1))
    x = r.randn(1, t, d).astype(np.float32)
    out, _ = m.apply(params, state, jnp.asarray(x), causal=True)

    tm = torch.nn.MultiheadAttention(d, h, batch_first=True, bias=False)
    with torch.no_grad():
        packed = np.concatenate([np.asarray(params["wq"]).T,
                                 np.asarray(params["wk"]).T,
                                 np.asarray(params["wv"]).T], axis=0)
        tm.in_proj_weight.copy_(_j2t(packed))
        tm.out_proj.weight.copy_(_j2t(np.asarray(params["wo"]).T))
    causal = torch.triu(torch.ones(t, t, dtype=torch.bool), diagonal=1)
    want, _ = tm(_j2t(x), _j2t(x), _j2t(x), attn_mask=causal,
                 need_weights=False)
    np.testing.assert_allclose(np.asarray(out), want.detach().numpy(),
                               rtol=1e-4, atol=1e-5)


def test_cosine_and_euclidean_layers():
    # Cosine: per-class cosine similarity to weight rows
    # (reference: nn/Cosine.scala); Euclidean: distances (nn/Euclidean.scala)
    r = np.random.RandomState(2)
    x = r.randn(4, 6).astype(np.float32)
    cos = nn.Cosine(6, 3)
    p, _ = cos.init(jax.random.PRNGKey(2))
    out = np.asarray(cos.forward(p, jnp.asarray(x)))
    wm = np.asarray(p["weight"])
    assert wm.shape == (3, 6)           # (n_out, n_in), misc.py layout
    want = np.stack([
        (x @ wm[k]) / np.maximum(np.linalg.norm(x, axis=1)
                                 * np.linalg.norm(wm[k]), 1e-12)
        for k in range(3)], axis=1)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)

    euc = nn.Euclidean(6, 3)
    p2, _ = euc.init(jax.random.PRNGKey(3))
    out2 = np.asarray(euc.forward(p2, jnp.asarray(x)))
    wm2 = np.asarray(p2["weight"])
    assert wm2.shape == (3, 6)
    want2 = np.stack([np.linalg.norm(x - wm2[k], axis=1) for k in range(3)],
                     axis=1)
    np.testing.assert_allclose(out2, want2, rtol=1e-4, atol=1e-5)


def test_lookup_table_matches_torch_embedding():
    r = np.random.RandomState(3)
    m = nn.LookupTable(10, 5)
    p, _ = m.init(jax.random.PRNGKey(4))
    idx = r.randint(0, 10, (4, 7)).astype(np.int32)
    out = np.asarray(m.forward(p, jnp.asarray(idx)))
    te = torch.nn.Embedding(10, 5)
    with torch.no_grad():
        te.weight.copy_(_j2t(p["weight"]))
    want = te(_j2t(idx).long()).detach().numpy()
    np.testing.assert_allclose(out, want, rtol=1e-6)


def test_mm_mv_dot_match_torch():
    r = np.random.RandomState(4)
    a = r.randn(2, 3, 4).astype(np.float32)
    b = r.randn(2, 4, 5).astype(np.float32)
    out = np.asarray(nn.MM().forward({}, (jnp.asarray(a), jnp.asarray(b))))
    np.testing.assert_allclose(out, np.matmul(a, b), rtol=1e-5)
    v = r.randn(2, 4).astype(np.float32)
    out2 = np.asarray(nn.MV().forward({}, (jnp.asarray(a), jnp.asarray(v))))
    want2 = np.einsum("bij,bj->bi", a, v)
    np.testing.assert_allclose(out2, want2, rtol=1e-5)
    d1 = r.randn(3, 8).astype(np.float32)
    d2 = r.randn(3, 8).astype(np.float32)
    out3 = np.asarray(nn.DotProduct().forward({}, (jnp.asarray(d1),
                                                   jnp.asarray(d2))))
    np.testing.assert_allclose(out3, (d1 * d2).sum(1), rtol=1e-5)


def test_gaussian_noise_and_dropout_statistics():
    r = jax.random.PRNGKey(0)
    x = jnp.ones((2000, 8))
    gn = nn.GaussianNoise(stddev=0.5)
    out, _ = gn.apply({}, {}, x, training=True, rng=r)
    noise = np.asarray(out) - 1.0
    assert abs(float(noise.mean())) < 0.02
    assert abs(float(noise.std()) - 0.5) < 0.02
    # eval mode: identity
    out_eval, _ = gn.apply({}, {}, x, training=False)
    np.testing.assert_allclose(np.asarray(out_eval), np.asarray(x))

    gd = nn.GaussianDropout(rate=0.3)
    out2, _ = gd.apply({}, {}, x, training=True, rng=r)
    mult = np.asarray(out2)
    # multiplicative noise with mean 1, std sqrt(rate/(1-rate))
    assert abs(float(mult.mean()) - 1.0) < 0.03
    assert abs(float(mult.std()) - np.sqrt(0.3 / 0.7)) < 0.05


def test_optimizers_match_torch_step_for_step():
    """Trajectory parity on a quadratic: our Adam/RMSprop/Adagrad/SGD
    match torch.optim step for step (reference oracle pattern,
    test/.../optim/*Spec.scala). Our SGD defaults dampening=momentum like
    the reference (SGD.scala:65) — torch semantics need dampening=0."""
    from bigdl_tpu.optim.method import SGD, Adam, Adagrad, RMSprop

    w0 = np.asarray([1.0, -2.0, 3.0], np.float32)

    def grad(w):
        return 2 * w + 0.5

    cases = [
        (SGD(0.1, momentum=0.9, dampening=0.0, weight_decay=0.01), 0.1,
         lambda p: torch.optim.SGD([p], lr=0.1, momentum=0.9,
                                   weight_decay=0.01), 1e-6),
        (SGD(0.1, momentum=0.9, dampening=0.0, nesterov=True), 0.1,
         lambda p: torch.optim.SGD([p], lr=0.1, momentum=0.9,
                                   nesterov=True), 1e-6),
        (Adam(0.05), 0.05,
         lambda p: torch.optim.Adam([p], lr=0.05), 1e-5),
        (RMSprop(0.05), 0.05,
         lambda p: torch.optim.RMSprop([p], lr=0.05), 1e-6),
        (Adagrad(0.05), 0.05,
         lambda p: torch.optim.Adagrad([p], lr=0.05), 1e-6),
    ]
    for ours, lr, make_torch, tol in cases:
        p = {"w": jnp.asarray(w0)}
        slots = ours.init_slots(p)
        tp = torch.nn.Parameter(torch.from_numpy(w0.copy()))
        topt = make_torch(tp)
        for t in range(10):
            g = {"w": jnp.asarray(grad(np.asarray(p["w"])))}
            p, slots = ours.update(p, g, slots, jnp.float32(lr),
                                   jnp.int32(t))
            topt.zero_grad()
            tp.grad = torch.from_numpy(grad(tp.detach().numpy()))
            topt.step()
        np.testing.assert_allclose(np.asarray(p["w"]),
                                   tp.detach().numpy(), atol=tol,
                                   err_msg=type(ours).__name__)


# ======================================================================
# part 5: volumetric pooling / full convolution, Hard/SoftShrink
# (VolumetricAveragePoolingSpec.scala, VolumetricFullConvolutionSpec.scala)


def test_volumetric_avgpool_matches_torch():
    r = np.random.RandomState(0)
    x = r.randn(2, 6, 8, 8, 3).astype(np.float32)     # NDHWC
    for pads, include in (((0, 0, 0), True), ((1, 1, 1), True),
                          ((1, 1, 1), False)):
        layer = nn.VolumetricAveragePooling(
            2, 2, 2, 2, 2, 2, pad_t=pads[0], pad_w=pads[1], pad_h=pads[2],
            count_include_pad=include)
        ours = layer.forward({}, jnp.asarray(x))
        tl = torch.nn.AvgPool3d(2, 2, padding=pads,
                                count_include_pad=include)
        want = tl(_j2t(x).permute(0, 4, 1, 2, 3)) \
            .permute(0, 2, 3, 4, 1).numpy()
        np.testing.assert_allclose(np.asarray(ours), want, rtol=1e-5,
                                   atol=1e-6)


def test_volumetric_full_convolution_matches_torch():
    r = np.random.RandomState(1)
    x = r.randn(2, 4, 5, 5, 3).astype(np.float32)
    layer = nn.VolumetricFullConvolution(3, 6, 3, 3, 3, 2, 2, 2,
                                         pad_t=1, pad_w=1, pad_h=1,
                                         adj_t=1, adj_w=1, adj_h=1)
    params, state = layer.init(jax.random.PRNGKey(0))
    ours, _ = layer.apply(params, state, jnp.asarray(x))

    tl = torch.nn.ConvTranspose3d(3, 6, 3, stride=2, padding=1,
                                  output_padding=1)
    with torch.no_grad():
        # ours (kt, kh, kw, cin, cout) -> torch (cin, cout, kt, kh, kw)
        tl.weight.copy_(_j2t(params["weight"]).permute(3, 4, 0, 1, 2))
        tl.bias.copy_(_j2t(params["bias"]))
    want = tl(_j2t(x).permute(0, 4, 1, 2, 3)) \
        .permute(0, 2, 3, 4, 1).detach().numpy()
    assert np.asarray(ours).shape == want.shape
    np.testing.assert_allclose(np.asarray(ours), want, rtol=1e-4,
                               atol=1e-4)


def test_shrink_activations_match_torch():
    r = np.random.RandomState(2)
    x = (r.randn(4, 9) * 2).astype(np.float32)
    pairs = [
        (nn.HardShrink(0.5), torch.nn.Hardshrink(0.5)),
        (nn.SoftShrink(0.5), torch.nn.Softshrink(0.5)),
    ]
    for ours_l, torch_l in pairs:
        ours = ours_l.forward({}, jnp.asarray(x))
        want = torch_l(_j2t(x)).numpy()
        np.testing.assert_allclose(np.asarray(ours), want, rtol=1e-6,
                                   atol=1e-7)
        # gradients too
        g = jax.grad(lambda a: ours_l.forward({}, a).sum())(jnp.asarray(x))
        xt = _j2t(x).requires_grad_(True)
        torch_l(xt).sum().backward()
        np.testing.assert_allclose(np.asarray(g), xt.grad.numpy(),
                                   rtol=1e-6, atol=1e-7)


def test_lr_schedules_match_torch_schedulers():
    """Step/MultiStep/Exponential/Poly schedules vs torch.optim's
    schedulers over 30 steps (reference: optim/SGD.scala's
    LearningRateSchedule family; torch is the independent oracle)."""
    from bigdl_tpu.optim import schedule as S

    base = 0.1
    dummy = torch.nn.Parameter(torch.zeros(1))

    def torch_lrs(sched_ctor, n=30):
        opt = torch.optim.SGD([dummy], lr=base)
        sch = sched_ctor(opt)
        out = []
        for _ in range(n):
            out.append(opt.param_groups[0]["lr"])
            opt.step()
            sch.step()
        return out

    def ours_lrs(sched, n=30):
        return [sched(base, {"neval": i, "epoch": 0}) for i in range(n)]

    import torch.optim.lr_scheduler as L
    np.testing.assert_allclose(
        ours_lrs(S.Step(10, 0.5)),
        torch_lrs(lambda o: L.StepLR(o, 10, 0.5)), rtol=1e-6)
    np.testing.assert_allclose(
        ours_lrs(S.MultiStep([5, 12, 20], 0.3)),
        torch_lrs(lambda o: L.MultiStepLR(o, [5, 12, 20], 0.3)),
        rtol=1e-6)
    np.testing.assert_allclose(
        ours_lrs(S.Exponential(100, 0.5)),
        torch_lrs(lambda o: L.ExponentialLR(o, 0.5 ** (1 / 100))),
        rtol=1e-5)
    # Poly against its closed form (torch's PolynomialLR uses a
    # different parameterization, so check the reference formula)
    poly = S.Poly(2.0, 100)
    for i in (0, 10, 50, 99):
        want = base * (1 - i / 100) ** 2.0
        np.testing.assert_allclose(poly(base, {"neval": i, "epoch": 0}),
                                   want, rtol=1e-6)
