"""Attention/Transformer tests: dense vs blockwise vs ring equivalence
(the long-context kernels must be numerically identical to dense attention),
transformer LM/enc-dec shapes, causal-mask leakage checks, and training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.nn.attention import (
    FeedForwardNetwork, MultiHeadAttention, Transformer, TransformerLayer,
    blockwise_attention, causal_mask, dot_product_attention, padding_mask,
    positional_encoding)
from bigdl_tpu.parallel.mesh import create_mesh
from bigdl_tpu.parallel.ring import ring_attention, ring_self_attention


def _qkv(b=2, h=3, t=16, d=8, seed=0):
    r = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(r.randn(b, h, t, d).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_dense(causal):
    q, k, v = _qkv()
    mask = causal_mask(q.shape[2]) if causal else None
    ref = dot_product_attention(q, k, v, mask)
    out = blockwise_attention(q, k, v, block_size=4, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense(causal):
    mesh = create_mesh(jax.devices()[:4], seq=4, data=1,
                       drop_trivial_axes=False)
    q, k, v = _qkv(t=16)
    ref = dot_product_attention(
        q, k, v, causal_mask(q.shape[2]) if causal else None)
    out = ring_self_attention(mesh, q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_long_sequence_under_jit():
    """Ring attention jitted over an 8-device seq mesh on a longer
    sequence — the multi-chip long-context path end to end."""
    mesh = create_mesh(jax.devices(), seq=8, data=1, drop_trivial_axes=False)
    q, k, v = _qkv(b=1, h=2, t=256, d=4, seed=1)
    out = jax.jit(lambda q, k, v: ring_self_attention(
        mesh, q, k, v, causal=True))(q, k, v)
    ref = dot_product_attention(q, k, v, causal_mask(256))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_mha_shapes_and_cross():
    mha = MultiHeadAttention(16, 4)
    p, s = mha.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 6, 16), jnp.float32)
    mem = jnp.asarray(np.random.RandomState(1).randn(2, 9, 16), jnp.float32)
    out, _ = mha.apply(p, s, x)
    assert out.shape == (2, 6, 16)
    out, _ = mha.apply(p, s, x, mem)          # cross attention
    assert out.shape == (2, 6, 16)


def test_causal_no_leakage():
    """Changing future tokens must not change past outputs."""
    mha = MultiHeadAttention(8, 2)
    p, s = mha.init(jax.random.PRNGKey(1))
    r = np.random.RandomState(2)
    x1 = r.randn(1, 8, 8).astype(np.float32)
    x2 = x1.copy()
    x2[:, 5:] += 10.0
    o1, _ = mha.apply(p, s, jnp.asarray(x1), causal=True)
    o2, _ = mha.apply(p, s, jnp.asarray(x2), causal=True)
    np.testing.assert_allclose(np.asarray(o1[:, :5]), np.asarray(o2[:, :5]),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(o1[:, 5:]) - np.asarray(o2[:, 5:])).max() > 1e-3


def test_padding_mask():
    mha = MultiHeadAttention(8, 2)
    p, s = mha.init(jax.random.PRNGKey(3))
    r = np.random.RandomState(4)
    x = r.randn(2, 6, 8).astype(np.float32)
    lengths = jnp.asarray([4, 6])
    m = padding_mask(lengths, 6)
    o1, _ = mha.apply(p, s, jnp.asarray(x), mask=m)
    x2 = x.copy()
    x2[0, 4:] = 99.0          # padded region of row 0
    o2, _ = mha.apply(p, s, jnp.asarray(x2), mask=m)
    np.testing.assert_allclose(np.asarray(o1[0, :4]), np.asarray(o2[0, :4]),
                               rtol=1e-4, atol=1e-4)


def test_transformer_lm_forward_and_train():
    model = Transformer(vocab_size=50, d_model=32, num_heads=4, d_ff=64,
                        num_layers=2, mode="lm")
    params, state = model.init(jax.random.PRNGKey(5))
    tokens = jnp.asarray(np.random.RandomState(6).randint(0, 50, (4, 12)))
    # (forward and loss jitted: one program each, not one per eager op)
    logits, _ = jax.jit(model.apply)(params, state, tokens)
    assert logits.shape == (4, 12, 50)

    # a couple of steps of next-token training must reduce loss
    targets = jnp.roll(tokens, -1, axis=1)

    def loss_fn(p):
        lg, _ = model.apply(p, state, tokens, training=True,
                            rng=jax.random.PRNGKey(0))
        lp = jax.nn.log_softmax(lg[:, :-1])
        return -jnp.mean(jnp.take_along_axis(
            lp, targets[:, :-1, None], axis=-1))

    loss_fn = jax.jit(loss_fn)
    l0 = float(loss_fn(params))
    opt_step = jax.jit(lambda p: jax.tree.map(
        lambda a, g: a - 0.1 * g, p, jax.grad(loss_fn)(p)))
    for _ in range(12):
        params = opt_step(params)
    assert float(loss_fn(params)) < l0 * 0.7


def test_transformer_encdec():
    model = Transformer(vocab_size=30, d_model=16, num_heads=2, d_ff=32,
                        num_layers=1, mode="encdec")
    params, state = model.init(jax.random.PRNGKey(7))
    src = jnp.asarray(np.random.RandomState(8).randint(0, 30, (2, 7)))
    tgt = jnp.asarray(np.random.RandomState(9).randint(0, 30, (2, 5)))
    logits, _ = jax.jit(model.apply)(params, state, (src, tgt))
    assert logits.shape == (2, 5, 30)


def test_transformer_blockwise_impl_matches_dense():
    kw = dict(vocab_size=40, d_model=16, num_heads=2, d_ff=32, num_layers=2,
              mode="lm", max_len=64)
    dense = Transformer(**kw)
    blockw = Transformer(**kw, attn_impl="blockwise", block_size=8)
    params, state = dense.init(jax.random.PRNGKey(10))
    tokens = jnp.asarray(np.random.RandomState(11).randint(0, 40, (2, 32)))
    ld, _ = jax.jit(dense.apply)(params, state, tokens)
    lb, _ = jax.jit(blockw.apply)(params, state, tokens)
    np.testing.assert_allclose(np.asarray(lb), np.asarray(ld),
                               rtol=3e-5, atol=3e-5)


def test_positional_encoding_odd_dim():
    enc = positional_encoding(10, 7)
    assert enc.shape == (10, 7)
    assert np.all(np.isfinite(np.asarray(enc)))


def test_causal_cross_attention_kv_cache_shapes():
    """Causal decode against longer memory (KV-cache convention): queries
    occupy the LAST Tq positions of the Tk key sequence."""
    mha = MultiHeadAttention(8, 2)
    p, s = mha.init(jax.random.PRNGKey(20))
    r = np.random.RandomState(21)
    x = jnp.asarray(r.randn(1, 3, 8), jnp.float32)      # 3 queries
    mem = jnp.asarray(r.randn(1, 7, 8), jnp.float32)    # 7 keys
    out, _ = mha.apply(p, s, x, mem, causal=True)
    assert out.shape == (1, 3, 8)
    # last query sees all 7 keys -> equals non-causal cross attention row
    full, _ = mha.apply(p, s, x, mem)
    np.testing.assert_allclose(np.asarray(out[:, -1]), np.asarray(full[:, -1]),
                               rtol=1e-5, atol=1e-5)
    # numeric (0/1 float) user mask composes with causal
    m = jnp.ones((1, 1, 3, 7), jnp.float32)
    out2, _ = mha.apply(p, s, x, mem, mask=m, causal=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


def test_blockwise_q_offset_matches_dense():
    q, k, v = _qkv(t=16)
    qs = q[:, :, -4:]      # last 4 queries against all 16 keys
    ref = dot_product_attention(qs, k, v, causal_mask(4, 16))
    out = blockwise_attention(qs, k, v, block_size=4, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_transformer_max_len_enforced():
    model = Transformer(vocab_size=10, d_model=8, num_heads=2, d_ff=16,
                        num_layers=1, mode="lm", max_len=8)
    params, state = model.init(jax.random.PRNGKey(22))
    with pytest.raises(ValueError):
        model.apply(params, state, jnp.zeros((1, 9), jnp.int32))


def test_transformer_lm_cached_generate_matches_full_forward():
    """Transformer.generate (KV-cached incremental decode) at beam 1 ==
    greedy rollout through the ordinary full forward — cached_step is an
    exact program transform of the block."""
    vocab = 37
    model = Transformer(vocab, d_model=24, num_heads=2, d_ff=48,
                        num_layers=2, mode="lm", max_len=64)
    params, state = model.init(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    prompt = jnp.asarray(r.randint(1, vocab, (2, 5)), jnp.int32)
    n_new = 6

    seqs, scores = model.generate(params, state, prompt, n_new,
                                  beam_size=1, eos_id=0)
    assert seqs.shape == (2, 1, 5 + n_new)

    # (the rollout's forward jitted: one program per length, where the
    # eager forward compiles every op anew at each of the six lengths)
    forward = jax.jit(lambda toks: model.apply(params, state, toks))
    cur = np.asarray(prompt)
    for _ in range(n_new):
        logits, _ = forward(jnp.asarray(cur))
        nxt = np.asarray(jnp.argmax(logits[:, -1, :], -1), np.int32)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    assert not (cur[:, 5:] == 0).any()        # pin: no eos in rollout
    np.testing.assert_array_equal(np.asarray(seqs[:, 0]), cur)

    # beams reorder the cache correctly (finite scores, right shapes)
    seqs3, scores3 = model.generate(params, state, prompt, n_new,
                                    beam_size=3, eos_id=0)
    assert seqs3.shape == (2, 3, 5 + n_new)
    assert np.isfinite(np.asarray(scores3)).all()
    # best beam scores at least as well as greedy
    assert float(scores3[:, 0].min()) >= float(scores[:, 0].min()) - 1e-4


def test_gqa_rope_composes_with_blockwise_and_flash():
    """GQA repeat + rotary happen BEFORE the attend, so every attn_impl
    sees full-head q/k/v: dense, blockwise, and the Pallas flash kernel
    (interpret mode) must agree bit-for-bit-ish."""
    import numpy as np
    from bigdl_tpu.nn.attention import MultiHeadAttention
    from bigdl_tpu.kernels.flash_attention import PallasFlashAttention

    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(2, 64, 32).astype(np.float32))
    outs = {}
    for impl in ("dense", "blockwise", "flash"):
        kw = {"attn_impl": "dense" if impl == "dense" else
              ("blockwise" if impl == "blockwise" else
               PallasFlashAttention(block_q=32, block_k=32,
                                    interpret=True))}
        m = MultiHeadAttention(32, 8, num_kv_heads=2, rope_theta=10000.0,
                               block_size=32, **kw)
        p, s = m.init(jax.random.PRNGKey(0))
        out, _ = jax.jit(lambda p, s, x: m.apply(p, s, x, causal=True))(
            p, s, x)
        outs[impl] = np.asarray(out)
    np.testing.assert_allclose(outs["blockwise"], outs["dense"],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(outs["flash"], outs["dense"],
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("block", [1, 7, 16])
@pytest.mark.parametrize("heads", [(4, 4), (4, 2)], ids=["mha", "gqa"])
@pytest.mark.parametrize("chunk", [1, 8])
def test_paged_attend_over_poisoned_pool_matches_dense_rows(chunk, heads,
                                                            block):
    """`paged_slot_cached_attend` alone against `slot_cached_attend` on the
    equivalent dense rows. The pool is 1e30 wherever a slot must not look:
    in blocks no slot owns, in blocks the inactive slot once owned, and in
    the lanes of owned blocks at and past a slot's frontier. Slots 0 and 1
    share their first pool block (a prefix-cache hit), slot 1's chunk ends
    in a padded tail, slot 2 is inactive. The attended lanes are the dense
    path's, summed in pool order, hence the tolerance; the write is exact
    and touches the chunk's valid lanes only."""
    from bigdl_tpu.nn.attention import (make_paged_kv_pool,
                                        paged_slot_cached_attend,
                                        slot_cached_attend)
    T, (H, Hc), B = chunk, heads, block
    N, hd = 3, 8
    M = -(-(2 * B + T + 6) // B)              # blocks a slot: room for all
    L, P = M * B, N * M + 3
    r = np.random.RandomState(100 * T + 10 * Hc + B)
    starts = np.array([B + 2, B + 5, 0])      # both past the shared block
    lengths = np.array([T, max(T - 3, 1), 0], np.int32)
    free = list(r.permutation(P))
    table = -np.ones((N, M), np.int32)
    for n in range(2):
        for m in range((starts[n] + T - 1) // B + 1):
            table[n, m] = table[0, 0] if (n, m) == (1, 0) else free.pop()
    ck = r.randn(N, L, Hc, hd).astype(np.float32)
    cv = r.randn(N, L, Hc, hd).astype(np.float32)
    ck[1, :B], cv[1, :B] = ck[0, :B], cv[0, :B]       # the shared prefix
    pool = np.asarray(make_paged_kv_pool(P, B, Hc, hd, np.float32)) + 1e30
    for n in range(2):
        for pos in range(starts[n]):
            pool[:, table[n, pos // B], pos % B] = np.concatenate(
                [ck[n, pos], cv[n, pos]], -1)
        ck[n, starts[n]:] = cv[n, starts[n]:] = 1e30  # stale past frontier
    q = r.randn(N, H, T, hd).astype(np.float32)
    kc = r.randn(N, T, Hc, hd).astype(np.float32)
    vc = r.randn(N, T, Hc, hd).astype(np.float32)
    positions = (starts[:, None] + np.arange(T)).astype(np.int32)
    want, _, _ = slot_cached_attend(q, kc, vc, ck, cv, positions)
    got, new_pool = jax.jit(paged_slot_cached_attend)(
        q, kc, vc, pool, positions, table, lengths)
    got, new_pool = np.asarray(got), np.asarray(new_pool)
    written = 0
    for n in range(N):
        v = lengths[n]
        np.testing.assert_allclose(got[n, :v], np.asarray(want)[n, :v],
                                   rtol=2e-5, atol=2e-5)
        for t in range(v):
            pos = starts[n] + t
            np.testing.assert_array_equal(
                new_pool[:, table[n, pos // B], pos % B],
                np.concatenate([kc[n, t], vc[n, t]], -1))
            written += 1
    changed = (new_pool != pool).any(axis=(0, 3))     # (P, B) lanes
    assert changed.sum() == written == lengths.sum()
