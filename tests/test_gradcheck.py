"""Numeric gradient checks (reference: test/.../nn/GradientChecker.scala,
GradientCheckerRNN.scala) — central differences vs autodiff across a
sweep of layers whose gradients are NOT trivially right: custom-VJP
kernels, piecewise/masked activations, window selections, normalization
statistics, recurrence."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.utils.gradcheck import check_gradients, \
    check_module_gradients

def _x(*shape, seed=0):
    # fresh RandomState per call: inputs must not depend on which tests
    # ran before (discontinuous ops near a kink would flake under -k)
    r = np.random.RandomState(hash(shape) % (2**31) + seed)
    return jnp.asarray(r.randn(*shape).astype(np.float32))


SWEEP = [
    ("conv_pad", lambda: nn.SpatialConvolution(2, 3, 3, 3, pad_w=1,
                                               pad_h=1), (2, 6, 6, 2)),
    ("dilated_conv", lambda: nn.SpatialDilatedConvolution(
        2, 3, 3, 3, dilation_w=2, dilation_h=2, pad_w=2, pad_h=2),
     (1, 8, 8, 2)),
    ("transposed_conv", lambda: nn.SpatialFullConvolution(
        2, 3, 3, 3, 2, 2, 1, 1), (1, 5, 5, 2)),
    ("maxpool_ceil", lambda: nn.SpatialMaxPooling(3, 3, 2, 2,
                                                  ceil_mode=True),
     (1, 6, 6, 2)),
    ("avgpool_expad", lambda: nn.SpatialAveragePooling(
        3, 3, 2, 2, pad_w=1, pad_h=1, count_include_pad=False),
     (1, 7, 7, 2)),
    ("lrn", lambda: nn.SpatialCrossMapLRN(3, alpha=1e-2, beta=0.75),
     (1, 4, 4, 6)),
    ("batchnorm_eval", lambda: nn.BatchNormalization(4), (6, 4)),
    ("layernorm", lambda: nn.LayerNormalization(6), (4, 6)),
    ("prelu", lambda: nn.PReLU(3), (3, 5, 5, 3)),
    ("hardshrink", lambda: nn.HardShrink(0.4), (4, 7)),
    ("softshrink", lambda: nn.SoftShrink(0.4), (4, 7)),
    ("bilinear_resize", lambda: nn.ResizeBilinear(7, 9), (1, 4, 5, 2)),
    ("linear", lambda: nn.Linear(6, 4), (5, 6)),
]


@pytest.mark.parametrize("name,build,shape",
                         [(n, b, s) for n, b, s in SWEEP],
                         ids=[n for n, _, _ in SWEEP])
def test_layer_gradients_match_numeric(name, build, shape):
    module = build()
    check_module_gradients(module, _x(*shape), max_entries=24)


def test_flash_attention_custom_vjp_gradcheck():
    """The Pallas flash kernel carries a hand-written backward — exactly
    what the reference's GradientChecker exists for."""
    from bigdl_tpu.kernels.flash_attention import flash_attention
    q = _x(1, 1, 8, 4)
    k = _x(1, 1, 8, 4)
    v = _x(1, 1, 8, 4)

    def obj_q(a):
        return jnp.sum(flash_attention(a, k, v, block_q=8, block_k=8,
                                       causal=True, interpret=True) ** 2)

    def obj_k(a):
        return jnp.sum(flash_attention(q, a, v, block_q=8, block_k=8,
                                       causal=True, interpret=True) ** 2)

    def obj_v(a):
        return jnp.sum(flash_attention(q, k, a, block_q=8, block_k=8,
                                       causal=True, interpret=True) ** 2)

    # jitted: the checker calls each objective 33 times, and the
    # interpreted kernel is traced anew on every call that is not
    check_gradients(jax.jit(obj_q), q, max_entries=16)
    check_gradients(jax.jit(obj_k), k, max_entries=16)
    check_gradients(jax.jit(obj_v), v, max_entries=16)


def test_lstm_recurrence_gradcheck():
    """GradientCheckerRNN analogue: grads through the scan recurrence."""
    rnn = nn.Recurrent(nn.LSTM(4, 5))
    params, state = rnn.init(jax.random.PRNGKey(0))
    x = _x(2, 6, 4)

    def obj(a):
        out, _ = rnn.apply(params, state, a)
        out = out[0] if isinstance(out, tuple) else out
        return jnp.sum(out ** 2)

    check_gradients(jax.jit(obj), x, max_entries=24)


def test_nms_selection_gradient_flows_to_selected_boxes():
    """Selections (top-k/NMS) must pass gradients to the chosen slots."""
    from bigdl_tpu.nn.detection import nms
    boxes = jnp.asarray([[0, 0, 10, 10], [1, 1, 11, 11],
                         [30, 30, 40, 40]], jnp.float32)
    scores = jnp.asarray([0.9, 0.8, 0.7])

    def obj(b):
        idx, valid = nms(b, scores, 0.5, 2)
        return jnp.sum(jnp.where(valid[:, None], b[idx], 0.0) ** 2)

    check_gradients(jax.jit(obj), boxes, max_entries=12, eps=1e-2,
                    rtol=8e-2)
