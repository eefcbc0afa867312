"""Keras JSON/HDF5 loader goldens (reference:
pyspark/bigdl/keras/converter.py — DefinitionLoader/WeightLoader;
fixtures are hand-authored to_json trees + h5py files, torch supplies
numerics where its conventions coincide with Keras)."""

import json

import h5py
import numpy as np
import pytest
import torch
import torch.nn.functional as F

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.interop.keras_loader import (load_keras, model_from_json)


def _seq_json(layers):
    return json.dumps({"class_name": "Sequential",
                       "config": {"name": "seq", "layers": layers}})


def _write_h5(path, table, model_config=None):
    with h5py.File(path, "w") as f:
        g = f.create_group("model_weights") if model_config else f
        g.attrs["layer_names"] = [n.encode() for n in table]
        for ln, wts in table.items():
            lg = g.create_group(ln)
            names = [f"{ln}/w_{i}:0".encode() for i in range(len(wts))]
            lg.attrs["weight_names"] = names
            for nme, w in zip(names, wts):
                lg.create_dataset(nme.decode(), data=w)
        if model_config:
            f.attrs["model_config"] = json.dumps(model_config).encode()


def test_keras_sequential_cnn_matches_torch(tmp_path):
    r = np.random.RandomState(0)
    k1 = (r.randn(3, 3, 3, 8) * 0.2).astype(np.float32)   # keras HWIO
    b1 = (r.randn(8) * 0.1).astype(np.float32)
    gamma = (r.rand(8) + 0.5).astype(np.float32)
    beta = (r.randn(8) * 0.1).astype(np.float32)
    mean = (r.randn(8) * 0.1).astype(np.float32)
    var = (r.rand(8) + 0.5).astype(np.float32)
    wd = (r.randn(8, 10) * 0.3).astype(np.float32)        # keras (in, out)
    bd = (r.randn(10) * 0.1).astype(np.float32)

    model_json = _seq_json([
        {"class_name": "Conv2D",
         "config": {"name": "c1", "filters": 8, "kernel_size": [3, 3],
                    "strides": [1, 1], "padding": "same",
                    "activation": "relu", "use_bias": True,
                    "batch_input_shape": [None, 8, 8, 3]}},
        {"class_name": "BatchNormalization",
         "config": {"name": "bn1", "epsilon": 1e-5, "momentum": 0.99}},
        {"class_name": "MaxPooling2D",
         "config": {"name": "p1", "pool_size": [2, 2]}},
        {"class_name": "GlobalAveragePooling2D", "config": {"name": "gap"}},
        {"class_name": "Dense",
         "config": {"name": "fc", "units": 10, "activation": "softmax",
                    "use_bias": True}},
    ])
    h5 = tmp_path / "w.h5"
    _write_h5(h5, {"c1": [k1, b1], "bn1": [gamma, beta, mean, var],
                   "fc": [wd, bd]})

    module, params, state = load_keras(json_path=model_json,
                                       hdf5_path=str(h5))
    x = r.randn(2, 8, 8, 3).astype(np.float32)
    got, _ = module.apply(params, state, jnp.asarray(x), training=False)

    tm = torch.nn.Sequential(
        torch.nn.Conv2d(3, 8, 3, padding=1), torch.nn.ReLU(),
        torch.nn.BatchNorm2d(8, eps=1e-5), torch.nn.AdaptiveAvgPool2d(1),
        torch.nn.Flatten(), torch.nn.Linear(8, 10),
        torch.nn.Softmax(dim=-1))
    with torch.no_grad():
        tm[0].weight.copy_(torch.from_numpy(k1.transpose(3, 2, 0, 1)))
        tm[0].bias.copy_(torch.from_numpy(b1))
        tm[2].weight.copy_(torch.from_numpy(gamma))
        tm[2].bias.copy_(torch.from_numpy(beta))
        tm[2].running_mean.copy_(torch.from_numpy(mean))
        tm[2].running_var.copy_(torch.from_numpy(var))
        tm[5].weight.copy_(torch.from_numpy(wd.T))
        tm[5].bias.copy_(torch.from_numpy(bd))
    tm.eval()
    # torch path: conv+relu+bn, then maxpool2d, then gap
    with torch.no_grad():
        t = tm[2](tm[1](tm[0](torch.from_numpy(x.transpose(0, 3, 1, 2)))))
        t = torch.nn.functional.max_pool2d(t, 2)
        t = tm[6](tm[5](tm[4](tm[3](t))))
    np.testing.assert_allclose(np.asarray(got), t.numpy(), atol=2e-5)


def test_keras_functional_branches(tmp_path):
    r = np.random.RandomState(1)
    wa = (r.randn(6, 4) * 0.3).astype(np.float32)
    wb = (r.randn(6, 4) * 0.3).astype(np.float32)
    config = {
        "class_name": "Model",
        "config": {
            "name": "m",
            "layers": [
                {"name": "in1", "class_name": "InputLayer",
                 "config": {"batch_input_shape": [None, 6]},
                 "inbound_nodes": []},
                {"name": "da", "class_name": "Dense",
                 "config": {"name": "da", "units": 4, "use_bias": False},
                 "inbound_nodes": [[["in1", 0, 0, {}]]]},
                {"name": "db", "class_name": "Dense",
                 "config": {"name": "db", "units": 4, "use_bias": False,
                            "activation": "relu"},
                 "inbound_nodes": [[["in1", 0, 0, {}]]]},
                {"name": "addl", "class_name": "Add",
                 "config": {"name": "addl"},
                 "inbound_nodes": [[["da", 0, 0, {}], ["db", 0, 0, {}]]]},
                {"name": "cat", "class_name": "Concatenate",
                 "config": {"name": "cat", "axis": -1},
                 "inbound_nodes": [[["addl", 0, 0, {}],
                                    ["da", 0, 0, {}]]]},
            ],
            "input_layers": [["in1", 0, 0]],
            "output_layers": [["cat", 0, 0]],
        },
    }
    h5 = tmp_path / "w.h5"
    _write_h5(h5, {"da": [wa], "db": [wb]})
    module, params, state = load_keras(json_path=json.dumps(config),
                                       hdf5_path=str(h5))
    x = r.randn(3, 6).astype(np.float32)
    got, _ = module.apply(params, state, jnp.asarray(x), training=False)
    da = x @ wa
    db = np.maximum(x @ wb, 0)
    want = np.concatenate([da + db, da], axis=-1)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_keras_lstm_matches_torch(tmp_path):
    r = np.random.RandomState(2)
    i, h, t, b = 5, 7, 6, 3
    tl = torch.nn.LSTM(i, h, batch_first=True)
    # keras layout from torch: kernel = w_ih.T, recurrent = w_hh.T,
    # bias = b_ih + b_hh (gate order i,f,g,o matches keras i,f,c,o)
    kernel = tl.weight_ih_l0.detach().numpy().T.copy()
    rec = tl.weight_hh_l0.detach().numpy().T.copy()
    bias = (tl.bias_ih_l0 + tl.bias_hh_l0).detach().numpy()

    model_json = _seq_json([
        {"class_name": "LSTM",
         "config": {"name": "l1", "units": h, "return_sequences": True,
                    "batch_input_shape": [None, t, i]}},
    ])
    h5 = tmp_path / "w.h5"
    _write_h5(h5, {"l1": [kernel, rec, bias]})
    module, params, state = load_keras(json_path=model_json,
                                       hdf5_path=str(h5))
    x = r.randn(b, t, i).astype(np.float32)
    got, _ = module.apply(params, state, jnp.asarray(x), training=False)
    with torch.no_grad():
        want, _ = tl(torch.from_numpy(x))
    np.testing.assert_allclose(np.asarray(got), want.numpy(), atol=1e-5)


def test_keras_gru_matches_reference_math(tmp_path):
    r = np.random.RandomState(3)
    i, h, t, b = 4, 5, 3, 2
    kernel = (r.randn(i, 3 * h) * 0.4).astype(np.float32)   # [z|r|h]
    rec = (r.randn(h, 3 * h) * 0.4).astype(np.float32)
    bias = (r.randn(3 * h) * 0.1).astype(np.float32)

    model_json = _seq_json([
        {"class_name": "GRU",
         "config": {"name": "g1", "units": h, "return_sequences": False,
                    "reset_after": False,
                    "batch_input_shape": [None, t, i]}},
    ])
    h5 = tmp_path / "w.h5"
    _write_h5(h5, {"g1": [kernel, rec, bias]})
    module, params, state = load_keras(json_path=model_json,
                                       hdf5_path=str(h5))
    x = r.randn(b, t, i).astype(np.float32)
    got, _ = module.apply(params, state, jnp.asarray(x), training=False)

    # keras GRU (reset_after=False):
    # z = sig(x Wz + h Uz + bz); r_ = sig(x Wr + h Ur + br)
    # hh = tanh(x Wh + (r_*h) Uh + bh); h' = z*h + (1-z)*hh
    def sig(v):
        return 1 / (1 + np.exp(-v))
    hs = np.zeros((b, h), np.float32)
    for step in range(t):
        xt = x[:, step]
        z = sig(xt @ kernel[:, :h] + hs @ rec[:, :h] + bias[:h])
        r_ = sig(xt @ kernel[:, h:2 * h] + hs @ rec[:, h:2 * h]
                 + bias[h:2 * h])
        hh = np.tanh(xt @ kernel[:, 2 * h:] + (r_ * hs) @ rec[:, 2 * h:]
                     + bias[2 * h:])
        hs = z * hs + (1 - z) * hh
    np.testing.assert_allclose(np.asarray(got), hs, atol=1e-5)


def test_keras_single_file_model_save(tmp_path):
    r = np.random.RandomState(4)
    w = (r.randn(4, 3) * 0.4).astype(np.float32)
    b = (r.randn(3) * 0.1).astype(np.float32)
    config = json.loads(_seq_json([
        {"class_name": "Dense",
         "config": {"name": "d1", "units": 3, "activation": "tanh",
                    "batch_input_shape": [None, 4]}},
    ]))
    h5 = tmp_path / "model.h5"
    _write_h5(h5, {"d1": [w, b]}, model_config=config)
    module, params, state = load_keras(hdf5_path=str(h5))
    x = r.randn(5, 4).astype(np.float32)
    got, _ = module.apply(params, state, jnp.asarray(x), training=False)
    np.testing.assert_allclose(np.asarray(got), np.tanh(x @ w + b),
                               atol=1e-5)


def test_keras_definition_only_shape_inference_and_training():
    model_json = _seq_json([
        {"class_name": "Conv2D",
         "config": {"name": "c", "filters": 4, "kernel_size": [3, 3],
                    "padding": "same", "activation": "relu",
                    "batch_input_shape": [None, 6, 6, 2]}},
        {"class_name": "Flatten", "config": {"name": "f"}},
        {"class_name": "Dense", "config": {"name": "d", "units": 3}},
    ])
    module, params, state, loaded = model_from_json(model_json)
    # Dense input dim inferred: 6*6*4 = 144
    assert params["2"]["weight"].shape == (144, 3)
    x = jnp.asarray(np.random.RandomState(5).randn(4, 6, 6, 2), jnp.float32)
    y = jnp.asarray([0, 1, 2, 0], jnp.int32)
    crit = nn.CrossEntropyCriterion()

    def loss_fn(p):
        out, _ = module.apply(p, state, x, training=True,
                              rng=jax.random.PRNGKey(0))
        return crit.forward(out, y)

    l0, grads = jax.value_and_grad(loss_fn)(params)
    p2 = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
    assert float(loss_fn(p2)) < float(l0)


def test_keras_embedding_and_depthwise(tmp_path):
    r = np.random.RandomState(6)
    emb = r.randn(30, 8).astype(np.float32)
    model_json = _seq_json([
        {"class_name": "Embedding",
         "config": {"name": "e", "input_dim": 30, "output_dim": 8,
                    "batch_input_shape": [None, 5]}},
        {"class_name": "GlobalAveragePooling1D", "config": {"name": "g"}},
    ])
    h5 = tmp_path / "w.h5"
    _write_h5(h5, {"e": [emb]})
    module, params, state = load_keras(json_path=model_json,
                                       hdf5_path=str(h5))
    idx = np.array([[0, 3, 7, 29, 1]], np.int32)
    got, _ = module.apply(params, state, jnp.asarray(idx), training=False)
    np.testing.assert_allclose(np.asarray(got), emb[idx[0]].mean(0)[None],
                               atol=1e-5)

    dw = (r.randn(3, 3, 2, 2) * 0.3).astype(np.float32)  # (kh,kw,cin,mult)
    model_json = _seq_json([
        {"class_name": "DepthwiseConv2D",
         "config": {"name": "dw", "kernel_size": [3, 3], "padding": "same",
                    "depth_multiplier": 2, "use_bias": False,
                    "batch_input_shape": [None, 5, 5, 2]}},
    ])
    h5b = tmp_path / "w2.h5"
    _write_h5(h5b, {"dw": [dw]})
    module, params, state = load_keras(json_path=model_json,
                                       hdf5_path=str(h5b))
    x = r.randn(1, 5, 5, 2).astype(np.float32)
    got, _ = module.apply(params, state, jnp.asarray(x), training=False)
    want = torch.nn.functional.conv2d(
        torch.from_numpy(x.transpose(0, 3, 1, 2)),
        torch.from_numpy(dw.transpose(2, 3, 0, 1).reshape(4, 1, 3, 3)),
        padding=1, groups=2).numpy().transpose(0, 2, 3, 1)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_keras_missing_weights_and_unsupported():
    model_json = _seq_json([
        {"class_name": "Dense",
         "config": {"name": "d", "units": 3,
                    "batch_input_shape": [None, 4]}},
    ])
    module, params, state, loaded = model_from_json(model_json)
    with pytest.raises(ValueError, match="missing weights"):
        loaded.apply_weights(params, state, {}, by_name=False)
    # by_name=True skips silently
    loaded.apply_weights(params, state, {}, by_name=True)

    bad = _seq_json([
        {"class_name": "FancyKerasLayer",
         "config": {"name": "x", "batch_input_shape": [None, 4]}},
    ])
    with pytest.raises(NotImplementedError, match="FancyKerasLayer"):
        model_from_json(bad)


def test_keras1_highway_maxout_srelu(tmp_path):
    """Keras-1 layer converters (reference: converter.py convert_highway/
    convert_maxoutdense/convert_srelu)."""
    r = np.random.RandomState(20)
    d = 5
    W = (r.randn(d, d) * 0.4).astype(np.float32)
    Wc = (r.randn(d, d) * 0.4).astype(np.float32)
    b = (r.randn(d) * 0.1).astype(np.float32)
    bc = (r.randn(d) * 0.1).astype(np.float32)
    k = (r.randn(3, d, 4) * 0.4).astype(np.float32)   # maxout (maxN,in,out)
    kb = (r.randn(3, 4) * 0.1).astype(np.float32)
    sr = [(r.randn(4) * 0.3).astype(np.float32),          # t_left != 0
          np.ones(4, np.float32),
          (r.randn(4) * 0.5).astype(np.float32),          # may be negative
          np.ones(4, np.float32)]

    model_json = _seq_json([
        {"class_name": "Highway",
         "config": {"name": "hw", "activation": "tanh",
                    "batch_input_shape": [None, d]}},
        {"class_name": "MaxoutDense",
         "config": {"name": "mx", "output_dim": 4, "nb_feature": 3}},
        {"class_name": "SReLU", "config": {"name": "sr"}},
    ])
    h5 = tmp_path / "w.h5"
    _write_h5(h5, {"hw": [W, Wc, b, bc], "mx": [k, kb], "sr": sr})
    module, params, state = load_keras(json_path=model_json,
                                       hdf5_path=str(h5))
    x = r.randn(3, d).astype(np.float32)
    got, _ = module.apply(params, state, jnp.asarray(x), training=False)

    # reference math
    def sig(v):
        return 1 / (1 + np.exp(-v))
    h = np.tanh(x @ W + b)
    t = sig(x @ Wc + bc)
    hw = t * h + (1 - t) * x
    mx = np.stack([hw @ k[i] + kb[i] for i in range(3)], 1).max(1)
    tl, al, tr_raw, ar = sr
    tr = tl + np.abs(tr_raw)            # keras-1 reparameterization
    y = np.where(mx < tl, tl + al * (mx - tl), mx)
    y = np.where(mx > tr, tr + ar * (mx - tr), y)
    np.testing.assert_allclose(np.asarray(got), y, atol=1e-5)


def test_keras1_tail_guardrails():
    """Unsupported configs raise; weightless use works; None time dims
    propagate (reference policy: raise, never silently-wrong numerics)."""
    import pytest
    from bigdl_tpu.interop.keras_loader import _build_layer

    # None time dim propagates through the shape pass
    _, out, _ = _build_layer("UpSampling1D", {"size": 3},
                             [(None, None, 4)])
    assert out == (None, None, 4)
    _, out2, _ = _build_layer("ZeroPadding1D", {"padding": 2},
                              [(None, None, 4)])
    assert out2 == (None, None, 4)
    with pytest.raises(NotImplementedError, match="Cropping1D"):
        _build_layer("Cropping1D", {"cropping": (1, 1)}, [(None, None, 4)])

    # int cropping normalizes
    _, out3, _ = _build_layer("Cropping2D", {"cropping": 2},
                              [(None, 10, 10, 3)])
    assert out3 == (None, 6, 6, 3)

    # ConvLSTM2D refuses architecture it cannot honor
    with pytest.raises(NotImplementedError, match="padding"):
        _build_layer("ConvLSTM2D", {"filters": 2, "kernel_size": 3,
                                    "padding": "valid"},
                     [(None, 4, 6, 6, 2)])
    # LocallyConnected2D imports impl-1 weights (round 4; real-keras
    # golden in test_golden_keras_real.py); impl 2/3 layouts refuse
    import numpy as np
    _, _, adapter = _build_layer(
        "LocallyConnected2D",
        {"filters": 2, "kernel_size": (3, 3)}, [(None, 8, 8, 2)])
    p, _ = adapter([np.zeros((36, 18, 2), np.float32)])
    assert p["weight"].shape == (6, 6, 18, 2)
    assert adapter([]) == ({}, {})
    # impl 2/3 kernel layouts refuse only when WEIGHTS arrive — the
    # constructor-API (no-weights) path builds fine since forward math is
    # identical across keras implementations
    _, _, ad2 = _build_layer("LocallyConnected2D",
                             {"filters": 2, "kernel_size": (3, 3),
                              "implementation": 2}, [(None, 8, 8, 2)])
    assert ad2([]) == ({}, {})
    with pytest.raises(NotImplementedError, match="implementation"):
        ad2([np.zeros((6, 6, 3, 3, 2, 2), np.float32)])
    _, _, ad1 = _build_layer("LocallyConnected1D",
                             {"filters": 2, "kernel_size": 3,
                              "implementation": 3}, [(None, 8, 2)])
    assert ad1([]) == ({}, {})
    with pytest.raises(NotImplementedError, match="implementation"):
        ad1([np.zeros((6, 6, 2), np.float32)])


# ======================================================================
# the paths that once raised NotImplementedError: SAME-padded 1D/3D pooling
# and Conv3D, dilated grouped Conv2D, strided ConvLSTM2D, partial shared_axes
# PReLU/SReLU, each against torch numerics (or direct numpy window math
# where torch has no SAME mode)

R = np.random.RandomState(3)


def _load(tmp_path, layers, weights):
    _write_h5(str(tmp_path / "w.h5"), weights)
    mod, params, state = load_keras(_seq_json(layers),
                                    str(tmp_path / "w.h5"))
    return mod, params, state


def _same_pad_1d(n, k, s):
    total = max((-(-n // s) - 1) * s + k - n, 0)
    return total // 2, total - total // 2


def test_dilated_grouped_conv2d_matches_torch(tmp_path):
    cin, cout, g, d = 4, 6, 2, 2
    k = (R.randn(3, 3, cin // g, cout) * 0.3).astype(np.float32)
    b = (R.randn(cout) * 0.1).astype(np.float32)
    mod, params, state = _load(tmp_path, [
        {"class_name": "Conv2D",
         "config": {"name": "c", "filters": cout, "kernel_size": [3, 3],
                    "dilation_rate": [d, d], "groups": g,
                    "padding": "valid", "use_bias": True,
                    "batch_input_shape": [None, 10, 10, cin]}},
    ], {"c": [k, b]})
    x = R.randn(2, 10, 10, cin).astype(np.float32)
    got, _ = mod.apply(params, state, jnp.asarray(x))
    want = F.conv2d(torch.from_numpy(x).permute(0, 3, 1, 2),
                    torch.from_numpy(k).permute(3, 2, 0, 1),
                    torch.from_numpy(b), dilation=d, groups=g)
    np.testing.assert_allclose(np.asarray(got),
                               want.permute(0, 2, 3, 1).numpy(),
                               rtol=1e-4, atol=1e-5)


def test_conv3d_same_matches_torch(tmp_path):
    cin, cout = 2, 3
    k = (R.randn(3, 3, 3, cin, cout) * 0.3).astype(np.float32)
    b = (R.randn(cout) * 0.1).astype(np.float32)
    mod, params, state = _load(tmp_path, [
        {"class_name": "Conv3D",
         "config": {"name": "c", "filters": cout,
                    "kernel_size": [3, 3, 3], "strides": [2, 2, 2],
                    "padding": "same", "use_bias": True,
                    "batch_input_shape": [None, 7, 7, 7, cin]}},
    ], {"c": [k, b]})
    x = R.randn(1, 7, 7, 7, cin).astype(np.float32)
    got, _ = mod.apply(params, state, jnp.asarray(x))
    assert got.shape == (1, 4, 4, 4, cout)
    # torch: explicit asymmetric SAME pad then VALID conv
    pads = [_same_pad_1d(7, 3, 2)] * 3
    xt = torch.from_numpy(x).permute(0, 4, 1, 2, 3)
    # F.pad takes (w_lo, w_hi, h_lo, h_hi, d_lo, d_hi)
    xt = F.pad(xt, (pads[2][0], pads[2][1], pads[1][0], pads[1][1],
                    pads[0][0], pads[0][1]))
    want = F.conv3d(xt, torch.from_numpy(k).permute(4, 3, 0, 1, 2),
                    torch.from_numpy(b), stride=2)
    np.testing.assert_allclose(np.asarray(got),
                               want.permute(0, 2, 3, 4, 1).numpy(),
                               rtol=1e-4, atol=1e-5)


def test_maxpool1d_same_matches_torch(tmp_path):
    mod, params, state = _load(tmp_path, [
        {"class_name": "MaxPooling1D",
         "config": {"name": "p", "pool_size": [3], "strides": [2],
                    "padding": "same",
                    "batch_input_shape": [None, 9, 2]}},
    ], {})
    x = R.randn(2, 9, 2).astype(np.float32)
    got, _ = mod.apply(params, state, jnp.asarray(x))
    assert got.shape == (2, 5, 2)
    lo, hi = _same_pad_1d(9, 3, 2)
    xt = F.pad(torch.from_numpy(x).permute(0, 2, 1), (lo, hi),
               value=float("-inf"))
    want = F.max_pool1d(xt, 3, 2).permute(0, 2, 1).numpy()
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_avgpool1d_same_matches_manual_windows(tmp_path):
    """keras/TF SAME avg pooling divides by the VALID element count per
    window — no torch mode matches, so compare against direct window
    math."""
    mod, params, state = _load(tmp_path, [
        {"class_name": "AveragePooling1D",
         "config": {"name": "p", "pool_size": [3], "strides": [2],
                    "padding": "same",
                    "batch_input_shape": [None, 8, 2]}},
    ], {})
    x = R.randn(1, 8, 2).astype(np.float32)
    got, _ = mod.apply(params, state, jnp.asarray(x))
    lo, _hi = _same_pad_1d(8, 3, 2)
    want = np.zeros((1, 4, 2))
    for i in range(4):
        s, e = max(i * 2 - lo, 0), min(i * 2 - lo + 3, 8)
        want[:, i] = x[:, s:e].mean(axis=1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_pool3d_same_matches_manual_windows(tmp_path):
    for cls in ("MaxPooling3D", "AveragePooling3D"):
        mod, params, state = _load(tmp_path, [
            {"class_name": cls,
             "config": {"name": "p", "pool_size": [2, 2, 2],
                        "strides": [2, 2, 2], "padding": "same",
                        "batch_input_shape": [None, 5, 5, 5, 1]}},
        ], {})
        x = R.randn(1, 5, 5, 5, 1).astype(np.float32)
        got, _ = mod.apply(params, state, jnp.asarray(x))
        assert got.shape == (1, 3, 3, 3, 1)
        agg = np.max if cls.startswith("Max") else np.mean
        want = np.zeros((1, 3, 3, 3, 1))
        for i in range(3):
            for j in range(3):
                for l in range(3):
                    want[0, i, j, l, 0] = agg(
                        x[0, i * 2:i * 2 + 2, j * 2:j * 2 + 2,
                          l * 2:l * 2 + 2, 0])
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-6, err_msg=cls)


def test_convlstm2d_strided_matches_torch_recurrence(tmp_path):
    """Strided ConvLSTM2D vs an independent torch implementation of the
    keras recurrence (gate order i,f,c,o; input conv stride 2 SAME;
    recurrent conv stride 1 SAME at the downsampled resolution)."""
    cin, f, kk, T = 2, 3, 3, 3
    kern = (R.randn(kk, kk, cin, 4 * f) * 0.2).astype(np.float32)
    rec = (R.randn(kk, kk, f, 4 * f) * 0.2).astype(np.float32)
    bias = (R.randn(4 * f) * 0.1).astype(np.float32)
    mod, params, state = _load(tmp_path, [
        {"class_name": "ConvLSTM2D",
         "config": {"name": "cl", "filters": f, "kernel_size": [kk, kk],
                    "strides": [2, 2], "padding": "same",
                    "recurrent_activation": "sigmoid",
                    "return_sequences": True,
                    "batch_input_shape": [None, T, 8, 8, cin]}},
    ], {"cl": [kern, rec, bias]})
    x = R.randn(1, T, 8, 8, cin).astype(np.float32)
    got, _ = mod.apply(params, state, jnp.asarray(x))
    assert got.shape == (1, T, 4, 4, f)

    # independent torch recurrence
    def tconv(inp, w, stride):
        # SAME pad for k=3: (1,1) at stride 1; TF SAME at stride 2 on even
        # input: total pad = k - stride = 1 → (0,1)
        n = inp.shape[-1]
        lo, hi = _same_pad_1d(n, kk, stride)
        inp = F.pad(inp, (lo, hi, lo, hi))
        return F.conv2d(inp, w, stride=stride)

    wk = torch.from_numpy(kern).permute(3, 2, 0, 1)
    wr = torch.from_numpy(rec).permute(3, 2, 0, 1)
    bt = torch.from_numpy(bias)
    h = torch.zeros(1, f, 4, 4)
    c = torch.zeros(1, f, 4, 4)
    outs = []
    for t in range(T):
        xt = torch.from_numpy(x[:, t]).permute(0, 3, 1, 2)
        gates = tconv(xt, wk, 2) + tconv(h, wr, 1) + bt[None, :, None, None]
        i, fg, g, o = torch.split(gates, f, dim=1)
        i, fg, o = torch.sigmoid(i), torch.sigmoid(fg), torch.sigmoid(o)
        c = fg * c + i * torch.tanh(g)
        h = o * torch.tanh(c)
        outs.append(h.permute(0, 2, 3, 1).numpy())
    want = np.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_convlstm2d_default_hard_sigmoid_matches_torch(tmp_path):
    """keras defaults recurrent_activation='hard_sigmoid' — verify the
    gates use clip(0.2x+0.5, 0, 1), not sigmoid (review finding r4)."""
    cin, f, T = 1, 2, 2
    kern = (R.randn(3, 3, cin, 4 * f) * 0.4).astype(np.float32)
    rec = (R.randn(3, 3, f, 4 * f) * 0.4).astype(np.float32)
    bias = (R.randn(4 * f) * 0.2).astype(np.float32)
    mod, params, state = _load(tmp_path, [
        {"class_name": "ConvLSTM2D",
         "config": {"name": "cl", "filters": f, "kernel_size": [3, 3],
                    "padding": "same", "return_sequences": True,
                    "batch_input_shape": [None, T, 5, 5, cin]}},
    ], {"cl": [kern, rec, bias]})
    x = R.randn(1, T, 5, 5, cin).astype(np.float32)
    got, _ = mod.apply(params, state, jnp.asarray(x))

    def hsig(v):
        return torch.clamp(0.2 * v + 0.5, 0.0, 1.0)

    wk = torch.from_numpy(kern).permute(3, 2, 0, 1)
    wr = torch.from_numpy(rec).permute(3, 2, 0, 1)
    bt = torch.from_numpy(bias)
    h = torch.zeros(1, f, 5, 5)
    c = torch.zeros(1, f, 5, 5)
    outs = []
    for t in range(T):
        xt = torch.from_numpy(x[:, t]).permute(0, 3, 1, 2)
        gates = (F.conv2d(F.pad(xt, (1, 1, 1, 1)), wk)
                 + F.conv2d(F.pad(h, (1, 1, 1, 1)), wr)
                 + bt[None, :, None, None])
        i, fg, g, o = torch.split(gates, f, dim=1)
        c = hsig(fg) * c + hsig(i) * torch.tanh(g)
        h = hsig(o) * torch.tanh(c)
        outs.append(h.permute(0, 2, 3, 1).numpy())
    np.testing.assert_allclose(np.asarray(got), np.stack(outs, 1),
                               rtol=1e-4, atol=1e-5)


def test_prelu_shared_axes_on_2d_input(tmp_path):
    """PReLU(shared_axes=[1]) on (None, F): keras stores a single-element
    alpha — must load as a broadcastable (1,) map (review finding r4)."""
    alpha = np.asarray([0.31], np.float32)
    mod, params, state = _load(tmp_path, [
        {"class_name": "PReLU",
         "config": {"name": "pr", "shared_axes": [1],
                    "batch_input_shape": [None, 6]}},
    ], {"pr": [alpha]})
    x = R.randn(4, 6).astype(np.float32)
    got, _ = mod.apply(params, state, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got),
                               np.where(x >= 0, x, 0.31 * x), rtol=1e-6)


def test_apply_update_honors_default_lr_decay():
    """Default-schedule lr_decay must not be short-circuited by the
    constant-LR fast path (review finding r4): trajectory must equal
    manually computed lr/(1+neval*decay) SGD steps."""
    from bigdl_tpu.optim.method import SGD, apply_update, init_update_slots
    from bigdl_tpu.optim.schedule import Default
    m = SGD(learning_rate=0.1, learning_rate_schedule=Default(0.5))
    p = {"w": jnp.ones((3,))}
    g = {"w": jnp.full((3,), 1.0)}
    slots = init_update_slots(m, p)
    want = 1.0
    for step in range(3):
        p, slots = apply_update(m, p, g, slots)
        want -= 0.1 / (1 + step * 0.5)
    np.testing.assert_allclose(np.asarray(p["w"]),
                               np.full(3, want, np.float32), rtol=1e-6)


def test_prelu_partial_shared_axes(tmp_path):
    alpha = (R.rand(1, 5, 2).astype(np.float32)) * 0.5   # share H only
    mod, params, state = _load(tmp_path, [
        {"class_name": "PReLU",
         "config": {"name": "pr", "shared_axes": [1],
                    "batch_input_shape": [None, 4, 5, 2]}},
    ], {"pr": [alpha]})
    x = R.randn(3, 4, 5, 2).astype(np.float32)
    got, _ = mod.apply(params, state, jnp.asarray(x))
    want = np.where(x >= 0, x, x * alpha[None])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    # and vs torch on the fully-shared-per-channel formulation
    alpha_c = (R.rand(2).astype(np.float32)) * 0.5
    mod2, p2, s2 = _load(tmp_path, [
        {"class_name": "PReLU",
         "config": {"name": "pr2", "shared_axes": [1, 2],
                    "batch_input_shape": [None, 4, 5, 2]}},
    ], {"pr2": [alpha_c.reshape(1, 1, 2)]})
    got2, _ = mod2.apply(p2, s2, jnp.asarray(x))
    want2 = F.prelu(torch.from_numpy(x).permute(0, 3, 1, 2),
                    torch.from_numpy(alpha_c)).permute(0, 2, 3, 1).numpy()
    np.testing.assert_allclose(np.asarray(got2), want2, rtol=1e-6)


def test_srelu_partial_shared_axes(tmp_path):
    shape = (4, 1, 2)                       # share W only
    tl = (R.randn(*shape) * 0.1).astype(np.float32)
    al = (R.rand(*shape).astype(np.float32))
    tr = (R.rand(*shape).astype(np.float32))
    ar = (R.rand(*shape).astype(np.float32))
    mod, params, state = _load(tmp_path, [
        {"class_name": "SReLU",
         "config": {"name": "sr", "shared_axes": [2],
                    "batch_input_shape": [None, 4, 5, 2]}},
    ], {"sr": [tl, al, tr, ar]})
    x = R.randn(3, 4, 5, 2).astype(np.float32)
    got, _ = mod.apply(params, state, jnp.asarray(x))
    # keras-1 reparameterization: t_right_actual = t_left + |t_right|
    tra = tl + np.abs(tr)
    y = np.where(x < tl, tl + al * (x - tl), x)
    want = np.where(x > tra, tra + ar * (x - tra), y)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
