"""Int8 accuracy evidence (VERDICT r3 next #7; reference:
whitepaper.md:192-196 "<0.1% accuracy drop" and
nn/MklInt8Convertible.scala:29-134 calibration): a TRAINED ResNet-20 on
the CIFAR fixture set, quantized three ways (dynamic, calibrated,
calibrated+per-window blocked weights), with the top-1 delta, argmax
agreement, and per-granularity weight reconstruction error all measured
and floored. The numbers recorded in docs/int8.md come from this setup.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import cifar
from bigdl_tpu.models import resnet
from bigdl_tpu.nn.quantized import (QuantizedLinear, calibrate, quantize,
                                    quantize_weight,
                                    quantize_weight_blocked)
from bigdl_tpu.optim.method import Adam, apply_update, init_update_slots


@pytest.fixture(scope="module")
def trained_resnet20():
    xtr, ytr = cifar.load(train=True, n_synthetic=768)
    xte, yte = cifar.load(train=False, n_synthetic=768)
    mean = np.asarray(cifar.TRAIN_MEAN)
    std = np.asarray(cifar.TRAIN_STD)
    xtr = ((xtr - mean) / std).astype(np.float32)
    xte = ((xte[:256] - mean) / std).astype(np.float32)
    yte = yte[:256]

    model = resnet.build_cifar(depth=20, class_num=10)
    params, state = model.init(jax.random.PRNGKey(0))
    crit = nn.ClassNLLCriterion()
    method = Adam(learning_rate=2e-3)
    slots = init_update_slots(method, params)

    @jax.jit
    def step(p, s, sl, x, y):
        def loss_fn(p):
            out, ns = model.apply(p, s, x, training=True)
            return crit.forward(out, y), ns
        (l, ns), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
        p2, sl2 = apply_update(method, p, g, sl)
        return p2, ns, sl2, l

    # 6 epochs reaches the 0.95 fp32 floor with margin on the fixture
    # set; 8 made this the #3 tier-1 offender
    r = np.random.RandomState(0)
    for _ in range(6):
        order = r.permutation(len(xtr))
        for i in range(0, len(xtr) - 63, 64):
            idx = order[i:i + 64]
            params, state, slots, _ = step(
                params, state, slots, jnp.asarray(xtr[idx]),
                jnp.asarray(ytr[idx]))
    return model, params, state, xtr, xte, yte


def _logits(mod, p, s, xte):
    outs = []
    for i in range(0, len(xte), 64):
        out, _ = mod.apply(p, s, jnp.asarray(xte[i:i + 64]),
                           training=False)
        outs.append(np.asarray(out))
    return np.concatenate(outs)


@pytest.mark.slow        # trains ResNet-20 to a 0.95 floor first (23 + 31 s)
def test_int8_top1_delta_on_trained_model(trained_resnet20):
    model, params, state, xtr, xte, yte = trained_resnet20
    lf = _logits(model, params, state, xte)
    acc_fp32 = float((lf.argmax(-1) == yte).mean())
    assert acc_fp32 >= 0.95, acc_fp32      # the fixture task is learnable

    scales = calibrate(model, params, state,
                       [xtr[i:i + 64] for i in range(0, 256, 64)],
                       percentile=99.9)
    variants = {
        "dynamic": quantize(model, params),
        "calibrated": quantize(model, params, input_scales=scales),
        "blocked": quantize(model, params, input_scales=scales,
                            weight_block=16),
    }
    for name, (qm, qp) in variants.items():
        lq = _logits(qm, qp, state, xte)
        acc = float((lq.argmax(-1) == yte).mean())
        delta = acc_fp32 - acc
        agree = float((lf.argmax(-1) == lq.argmax(-1)).mean())
        # the reference's capability claim is <0.1% drop
        # (whitepaper.md:192-196); measured here: 0.0 for all variants
        assert delta <= 0.01, (name, delta)
        assert agree >= 0.99, (name, agree)
        rel = float(np.abs(lq - lf).max() / np.abs(lf).max())
        assert rel < 0.05, (name, rel)     # logits stay close, not just argmax


@pytest.mark.slow        # needs the same trained weights
def test_blocked_scales_reduce_weight_error(trained_resnet20):
    """Granularity ladder: per-tensor > per-channel > per-window RMS
    reconstruction error (BigQuant's motivation for windowed min/max)."""
    model, params, _, _, _, _ = trained_resnet20

    def find_fc(p):
        for k, v in p.items():
            if isinstance(v, dict):
                r = find_fc(v)
                if r is not None:
                    return r
            elif k == "weight" and hasattr(v, "ndim") and v.ndim == 2:
                return v
        return None

    w = np.asarray(find_fc(params))
    s0 = np.abs(w).max() / 127.0
    rec0 = np.round(np.clip(w / s0, -127, 127)) * s0
    q1, s1 = quantize_weight(w, axis=1)
    rec1 = np.asarray(q1, np.float32) * np.asarray(s1)
    qb, sb = quantize_weight_blocked(w, 16)
    recb = (np.asarray(qb, np.float32) * np.asarray(sb)) \
        .reshape(-1, w.shape[1])[:w.shape[0]]

    def err(rec):
        return float(np.sqrt(((rec - w) ** 2).mean())
                     / np.sqrt((w ** 2).mean()))

    e0, e1, eb = err(rec0), err(rec1), err(recb)
    assert eb < e1 <= e0, (e0, e1, eb)


def test_blocked_linear_matches_float_closely():
    """Unit check incl. the non-divisible in_features padding path."""
    r = np.random.RandomState(0)
    lin = nn.Linear(37, 11)                # 37 % 16 != 0 → padded block
    params, _ = lin.init(jax.random.PRNGKey(1))
    x = jnp.asarray(r.randn(5, 37).astype(np.float32))
    want = np.asarray(lin.forward(params, x))
    qm, qp = QuantizedLinear.from_float(lin, params, weight_block=16)
    got = np.asarray(qm.forward(qp, x))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=0.03 * scale)


def test_blocked_linear_survives_serialization(tmp_path):
    from bigdl_tpu.utils.serializer import load_module, save_module
    lin = nn.Linear(32, 8)
    params, _ = lin.init(jax.random.PRNGKey(2))
    qm, qp = QuantizedLinear.from_float(lin, params, weight_block=8)
    x = jnp.asarray(np.random.RandomState(3).randn(4, 32)
                    .astype(np.float32))
    want = np.asarray(qm.forward(qp, x))
    save_module(str(tmp_path / "q.bigdl-tpu"), qm, qp, {})
    qm2, qp2, _ = load_module(str(tmp_path / "q.bigdl-tpu"))
    np.testing.assert_allclose(np.asarray(qm2.forward(qp2, x)), want,
                               rtol=1e-6)
