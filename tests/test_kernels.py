"""Pallas kernel tests — run in interpreter mode on CPU; the driver's real
chip runs the compiled path (reference analogue: BigDL-core kernels are
validated against the scala BLAS path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.kernels.flash_attention import (PallasFlashAttention,
                                               flash_attention)
from bigdl_tpu.nn.attention import dot_product_attention, causal_mask

# How far a chip run of these kernels may sit from the fp32 reference —
# DERIVED, not fitted: the MXU truncates fp32 dot operands to bf16 (one
# pass, fp32 accumulation); the bf16-emulated references in
# kernels/mxu_ref.py reproduce that envelope on CPU, and
# test_real_chip_tolerances_derived_from_mxu_emulation pins each constant
# to it (≥ the envelope, ≤ 4× its max-abs delta). The kernels' lowering
# for the chip is compile-tested in tests/test_chip_compile.py.
MXU_FLASH_TOL = 2e-2
MXU_CCE_TOL = 5e-3


def _qkv(b=2, h=2, tq=64, tk=64, d=32, seed=0):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, h, tq, d), jnp.float32),
            jnp.asarray(r.randn(b, h, tk, d), jnp.float32),
            jnp.asarray(r.randn(b, h, tk, d), jnp.float32))


def test_flash_matches_dense():
    q, k, v = _qkv()
    out = flash_attention(q, k, v, 32, 32, False, None, True)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_causal_matches_dense():
    q, k, v = _qkv(tq=64, tk=64)
    out = flash_attention(q, k, v, 32, 32, True, None, True)
    ref = dot_product_attention(q, k, v, causal_mask(64, 64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_cross_attention_lengths():
    q, k, v = _qkv(tq=32, tk=128)
    out = flash_attention(q, k, v, 32, 64, False, None, True)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_causal_offset():
    """Tq < Tk causal: queries are the LAST rows (KV-cache decode)."""
    q, k, v = _qkv(tq=32, tk=64)
    out = flash_attention(q, k, v, 32, 32, True, None, True)
    full_mask = causal_mask(64, 64)[..., 32:, :]   # last 32 query rows
    ref = dot_product_attention(q, k, v, full_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_gradients_match_dense():
    q, k, v = _qkv(tq=32, tk=32, d=16)

    def f_flash(q, k, v):
        return flash_attention(q, k, v, 16, 16, True, None, True).sum()

    def f_ref(q, k, v):
        return dot_product_attention(q, k, v, causal_mask(32, 32)).sum()

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


def test_flash_ragged_lengths_padded_and_masked():
    """Tq/Tk that do NOT divide the blocks pad internally and mask the
    K tail (the valid-mask trick) — callers never pre-pad."""
    for tq, tk, causal in ((60, 60, False), (60, 60, True), (37, 91, False),
                           (50, 77, True), (64, 60, False)):
        q, k, v = _qkv(tq=tq, tk=tk)
        out = flash_attention(q, k, v, 32, 32, causal, None, True)
        mask = causal_mask(tk, tk)[..., tk - tq:, :] if causal else None
        ref = dot_product_attention(q, k, v, mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"tq={tq} tk={tk} causal={causal}")


def test_flash_ragged_gradients_match_dense():
    """The recompute-backward handles ragged Tk (largest-divisor block)."""
    q, k, v = _qkv(tq=24, tk=33, d=16)

    def f_flash(q, k, v):
        return flash_attention(q, k, v, 16, 16, False, None, True).sum()

    def f_ref(q, k, v):
        return dot_product_attention(q, k, v).sum()

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


def test_flash_lane_alignment_enforced():
    """The ONE remaining hard error (compiled path only — the
    interpreter has no tiling constraint): a head dim off the sublane
    grid that Mosaic could not tile."""
    q, k, v = _qkv(tq=32, tk=32, d=12)
    with pytest.raises(ValueError, match="lane-aligned"):
        flash_attention(q, k, v, 32, 32, False, None, False)


def test_flash_as_mha_backend():
    from bigdl_tpu.nn.attention import MultiHeadAttention
    mha = MultiHeadAttention(32, 4,
                             attn_impl=PallasFlashAttention(16, 16,
                                                            interpret=True))
    ref_mha = MultiHeadAttention(32, 4)
    params, state = mha.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 32, 32), jnp.float32)
    out, _ = mha.apply(params, state, x, causal=True)
    ref, _ = ref_mha.apply(params, state, x, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------ int8 matmul kernel
def test_int8_matmul_matches_dot_general():
    from bigdl_tpu.kernels.quantized_matmul import int8_matmul
    r = np.random.RandomState(0)
    m, k, n = 70, 96, 50                    # deliberately non-block-multiple
    xq = r.randint(-127, 128, (m, k)).astype(np.int8)
    wq = r.randint(-127, 128, (k, n)).astype(np.int8)
    sx = (r.rand(m, 1).astype(np.float32) + 0.5) / 100
    sw = (r.rand(1, n).astype(np.float32) + 0.5) / 100
    got = int8_matmul(jnp.asarray(xq), jnp.asarray(wq), jnp.asarray(sx),
                      jnp.asarray(sw), block_m=32, block_n=32, block_k=32,
                      interpret=True)
    want = (xq.astype(np.int64) @ wq.astype(np.int64)).astype(np.float32) \
        * sx * sw
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_quantized_linear_pallas_matches_xla_path():
    from bigdl_tpu.nn.quantized import QuantizedLinear
    from bigdl_tpu.nn.linear import Linear
    import jax
    r = np.random.RandomState(1)
    lin = Linear(40, 24)
    params, _ = lin.init(jax.random.PRNGKey(0))
    x = jnp.asarray(r.randn(6, 40).astype(np.float32))

    qlin, qp = QuantizedLinear.from_float(lin, params)
    qlin.use_pallas = False
    ref = qlin.forward(qp, x)

    from bigdl_tpu.kernels.quantized_matmul import quantized_linear_forward
    got = quantized_linear_forward(x, qp["weight_q"], qp["weight_scale"],
                                   bias=qp["bias"], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_quantized_linear_forward_3d_batch():
    from bigdl_tpu.kernels.quantized_matmul import quantized_linear_forward
    r = np.random.RandomState(2)
    x = jnp.asarray(r.randn(2, 5, 16).astype(np.float32))
    wq = jnp.asarray(r.randint(-127, 128, (16, 8)).astype(np.int8))
    sw = jnp.asarray((r.rand(1, 8).astype(np.float32) + 0.5) / 50)
    out = quantized_linear_forward(x, wq, sw, interpret=True)
    assert out.shape == (2, 5, 8)
    # leading dims flatten correctly: row 0 of batch 1 == flat row 5
    flat = quantized_linear_forward(x.reshape(10, 16), wq, sw,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(out).reshape(10, 8),
                               np.asarray(flat), rtol=1e-6)


def test_int8_matmul_unaligned_shapes_tile_padded():
    """ADVICE r2: K=40/N=24 must produce tile-aligned Pallas blocks
    ((32,128) for int8), not raw-dim blocks that Mosaic rejects on TPU.
    interpret=True checks numerics; the block-shape assertion is static."""
    from bigdl_tpu.kernels import quantized_matmul as qmm
    assert qmm._round_up(40, 128) == 128
    assert qmm._round_up(24, 128) == 128
    assert qmm._round_up(6, 32) == 32
    r = np.random.RandomState(3)
    xq = jnp.asarray(r.randint(-127, 128, (6, 40)).astype(np.int8))
    wq = jnp.asarray(r.randint(-127, 128, (40, 24)).astype(np.int8))
    sx = jnp.asarray((r.rand(6, 1).astype(np.float32) + 0.5) / 60)
    sw = jnp.asarray((r.rand(1, 24).astype(np.float32) + 0.5) / 60)
    got = qmm.int8_matmul(xq, wq, sx, sw, interpret=True)
    ref = (np.asarray(xq, np.int32) @ np.asarray(wq, np.int32)
           ).astype(np.float32) * np.asarray(sx) * np.asarray(sw)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-5)


def test_int8_matmul_scalar_per_tensor_scales():
    """Scalar (per-tensor) scales stay accepted — the docstring's
    'broadcastable' contract."""
    from bigdl_tpu.kernels.quantized_matmul import int8_matmul
    r = np.random.RandomState(5)
    xq = jnp.asarray(r.randint(-127, 128, (4, 16)).astype(np.int8))
    wq = jnp.asarray(r.randint(-127, 128, (16, 8)).astype(np.int8))
    got = int8_matmul(xq, wq, 0.02, 0.01, interpret=True)
    ref = (np.asarray(xq, np.int32) @ np.asarray(wq, np.int32)
           ).astype(np.float32) * 0.02 * 0.01
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-6)


def _cce_ref(h, w, labels):
    logits = h @ w.T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def test_cut_cross_entropy_matches_dense():
    """Fused head-matmul + online logsumexp == dense log_softmax NLL,
    including a vocab size that does not divide the block."""
    import jax
    from bigdl_tpu.kernels.cut_cross_entropy import cut_cross_entropy
    r = np.random.RandomState(0)
    n, d, v = 16, 32, 37                  # v deliberately unaligned
    h = jnp.asarray(r.randn(n, d).astype(np.float32))
    w = jnp.asarray(r.randn(v, d).astype(np.float32) * 0.3)
    labels = jnp.asarray(r.randint(0, v, n), jnp.int32)
    got = cut_cross_entropy(h, w, labels, block_n=8, block_v=16,
                            interpret=True)
    want = _cce_ref(h, w, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_cut_cross_entropy_gradients_match_dense():
    """Blockwise-recomputed backward == autodiff of the dense loss for
    BOTH h and the tied head w (the scatter-add one-hot term included)."""
    import jax
    from bigdl_tpu.kernels.cut_cross_entropy import cut_cross_entropy
    r = np.random.RandomState(1)
    n, d, v = 16, 24, 29
    h = jnp.asarray(r.randn(n, d).astype(np.float32))
    w = jnp.asarray(r.randn(v, d).astype(np.float32) * 0.3)
    labels = jnp.asarray(r.randint(0, v, n), jnp.int32)
    # non-uniform upstream gradient exercises the g scaling
    gvec = jnp.asarray(r.rand(n).astype(np.float32) + 0.5)

    def fused(h, w):
        return jnp.sum(cut_cross_entropy(h, w, labels, block_n=8,
                                         block_v=8, interpret=True) * gvec)

    def dense(h, w):
        return jnp.sum(_cce_ref(h, w, labels) * gvec)

    (dh_f, dw_f) = jax.grad(fused, argnums=(0, 1))(h, w)
    (dh_d, dw_d) = jax.grad(dense, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(dh_f), np.asarray(dh_d),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw_f), np.asarray(dw_d),
                               rtol=1e-4, atol=1e-5)


def test_cut_cross_entropy_trains_a_tied_lm_head():
    """End-to-end: a tiny tied-embedding LM trained with the fused loss
    reaches the same ballpark loss as the dense-loss twin."""
    import jax
    from bigdl_tpu.kernels.cut_cross_entropy import cut_cross_entropy
    r = np.random.RandomState(2)
    n, d, v = 32, 16, 21
    x = jnp.asarray(r.randn(n, d).astype(np.float32))
    labels = jnp.asarray(np.arange(n) % v, jnp.int32)

    def train(loss_kind):
        w = jnp.asarray(r.randn(v, d).astype(np.float32) * 0.1)
        proj = jnp.eye(d, dtype=jnp.float32)

        @jax.jit
        def step(w, proj):
            def loss_fn(w, proj):
                hh = x @ proj
                if loss_kind == "fused":
                    return cut_cross_entropy(hh, w, labels, block_n=8,
                                             block_v=8,
                                             interpret=True).mean()
                return _cce_ref(hh, w, labels).mean()
            l, (gw, gp) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
                w, proj)
            return w - 0.5 * gw, proj - 0.5 * gp, l

        for _ in range(60):
            w, proj, l = step(w, proj)
        return float(l)

    r = np.random.RandomState(2)
    lf = train("fused")
    r = np.random.RandomState(2)
    ld = train("dense")
    assert abs(lf - ld) < 1e-3, (lf, ld)
    assert lf < 1.0


def test_real_chip_tolerances_derived_from_mxu_emulation():
    """The chip tolerance constants must bracket the bf16-operand-
    truncation envelope computed on CPU (kernels/mxu_ref.py): each
    constant passes against the emulated delta (≥ envelope) AND stays
    within 4× the emulation's max-abs delta (not vacuously loose) — a
    physically derived bound, not one fitted to an observation."""
    from bigdl_tpu.kernels.mxu_ref import attention_mxu_ref, cce_mxu_ref

    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(2, 4, 256, 64).astype(np.float32))
    k = jnp.asarray(r.randn(2, 4, 256, 64).astype(np.float32))
    v = jnp.asarray(r.randn(2, 4, 256, 64).astype(np.float32))
    ref = np.asarray(dot_product_attention(q, k, v, causal_mask(256)))
    emu = np.asarray(attention_mxu_ref(q, k, v, causal=True))
    flash_env = np.abs(emu - ref).max()
    assert flash_env <= MXU_FLASH_TOL, (
        f"bf16 envelope {flash_env:.2e} exceeds the chip flash "
        f"tolerance {MXU_FLASH_TOL}")
    assert MXU_FLASH_TOL <= 4 * flash_env, (
        f"flash tolerance {MXU_FLASH_TOL} is >4x the bf16 "
        f"envelope {flash_env:.2e} — tighten it")

    r = np.random.RandomState(3)
    n, d, vv = 256, 128, 1000
    h = jnp.asarray(r.randn(n, d).astype(np.float32))
    w = jnp.asarray(r.randn(vv, d).astype(np.float32) * 0.1)
    labels = jnp.asarray(r.randint(0, vv, n), jnp.int32)
    ref2 = np.asarray(_cce_ref(h, w, labels))
    emu2 = np.asarray(cce_mxu_ref(h, w, labels))
    # NLL values are O(log V) ≈ 7, so the smoke's rtol dominates — the
    # envelope bound must use the same allclose criterion
    cce_allowed = MXU_CCE_TOL * (1.0 + np.abs(ref2))
    cce_delta = np.abs(emu2 - ref2)
    assert (cce_delta <= cce_allowed).all(), (
        f"bf16 envelope {cce_delta.max():.2e} exceeds the chip CCE "
        f"criterion")
    cce_env = cce_delta.max()
    assert MXU_CCE_TOL <= 4 * cce_env, (
        f"CCE tolerance {MXU_CCE_TOL} is >4x the bf16 envelope "
        f"{cce_env:.2e} — tighten it")


