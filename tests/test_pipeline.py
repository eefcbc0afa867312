"""Pipeline and Ulysses sequence-parallel tests on the fake 8-device CPU
mesh (same trick as DistriOptimizerSpec's simulated cluster): the uniform
`pipeline_apply`, then heterogeneous stages, streamed input and 1F1B
training (no reference equivalent — SURVEY.md §2.13 parity-plus;
scheduling follows the classic 1F1B literature, memory model per the
scaling-book)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import bigdl_tpu.nn as nn
from bigdl_tpu.parallel.pipeline import (Pipeline, pipeline_apply,
                                         stack_stage_params)
from bigdl_tpu.parallel.ulysses import ulysses_self_attention
from bigdl_tpu.nn.attention import causal_mask, dot_product_attention


def _mesh(n, axis="pipe"):
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n), (axis,))


def test_pipeline_matches_sequential():
    """4-stage pipeline output == running the 4 stages back-to-back."""
    n_stages, mb = 4, 2
    r = np.random.RandomState(0)
    ws = [jnp.asarray(r.randn(8, 8) * 0.5, jnp.float32)
          for _ in range(n_stages)]
    bs = [jnp.asarray(r.randn(8) * 0.1, jnp.float32)
          for _ in range(n_stages)]
    stage_params = [{"w": w, "b": b} for w, b in zip(ws, bs)]
    stacked = stack_stage_params(stage_params)

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    x = jnp.asarray(r.randn(8, 8), jnp.float32)
    mesh = _mesh(n_stages)
    out = pipeline_apply(stage_fn, stacked, x, mesh, n_microbatches=4)

    ref = x
    for p in stage_params:
        ref = stage_fn(p, ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_differentiable():
    n_stages = 2
    r = np.random.RandomState(1)
    stage_params = [{"w": jnp.asarray(r.randn(4, 4) * 0.5, jnp.float32)}
                    for _ in range(n_stages)]
    stacked = stack_stage_params(stage_params)
    x = jnp.asarray(r.randn(4, 4), jnp.float32)
    mesh = _mesh(n_stages)

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    def loss(stacked):
        return pipeline_apply(stage_fn, stacked, x, mesh,
                              n_microbatches=2).sum()

    g = jax.jit(jax.grad(loss))(stacked)

    def ref_loss(stacked):
        h = x
        for i in range(n_stages):
            h = stage_fn(jax.tree.map(lambda a: a[i], stacked), h)
        return h.sum()

    gr = jax.jit(jax.grad(ref_loss))(stacked)
    np.testing.assert_allclose(np.asarray(g["w"]), np.asarray(gr["w"]),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_module_facade():
    block = nn.Linear(6, 6)
    pipe = Pipeline(block, n_stages=2, n_microbatches=2)
    stacked = pipe.init(jax.random.PRNGKey(0))
    mesh = _mesh(2)
    stacked = pipe.shard(stacked, mesh)
    x = jnp.asarray(np.random.RandomState(0).randn(4, 6), jnp.float32)
    out = pipe.apply(stacked, x, mesh)
    assert out.shape == (4, 6)
    # stage axis is sharded over pipe devices
    assert "pipe" in str(jax.tree.leaves(stacked)[0].sharding.spec)


def test_pipeline_batch_divisibility():
    mesh = _mesh(2)
    stacked = stack_stage_params([{"w": jnp.eye(2)}] * 2)
    with pytest.raises(ValueError, match="divide"):
        pipeline_apply(lambda p, h: h, stacked, jnp.zeros((5, 2)), mesh, 3)


@pytest.mark.parametrize("n,shape,causal", [
    (4, (2, 4, 32, 8), False),          # H=4 divides n=4
    (2, (1, 2, 16, 8), True),
], ids=["dense", "causal"])
def test_ulysses_matches_dense(n, shape, causal):
    mesh = _mesh(n, "seq")
    r = np.random.RandomState(int(causal))
    q = jnp.asarray(r.randn(*shape), jnp.float32)
    k = jnp.asarray(r.randn(*shape), jnp.float32)
    v = jnp.asarray(r.randn(*shape), jnp.float32)
    out = ulysses_self_attention(mesh, q, k, v, causal=causal)
    mask = causal_mask(shape[2], shape[2]) if causal else None
    ref = dot_product_attention(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def _seq_reference(pipe, pv, x, training=False):
    """Run the stages back-to-back without the pipeline machinery."""
    h = jnp.asarray(x)
    for i, stage in enumerate(pipe.stages):
        p = pipe._p_meta[i].unflatten(pv["flat"][i])
        s = pipe._s_meta[i].unflatten(pv["state"][i])
        h, _ = stage.apply(p, s, h, training=training,
                           rng=jax.random.PRNGKey(0))
    return h


def test_hetero_pipeline_matches_sequential():
    r = np.random.RandomState(0)
    stages = [
        nn.Linear(8, 8),
        nn.Sequential().add(nn.Linear(8, 16)).add(nn.ReLU())
                       .add(nn.Linear(16, 8)),         # different structure
        nn.Sequential().add(nn.LayerNormalization(8)).add(nn.Tanh()),
        nn.Linear(8, 8, bias=False),
    ]
    pipe = Pipeline(stages, n_microbatches=4)
    pv = pipe.init(jax.random.PRNGKey(0))
    mesh = _mesh(4)
    pv = pipe.shard(pv, mesh)
    x = jnp.asarray(r.randn(8, 8), jnp.float32)
    got = pipe.apply(pv, x, mesh)
    want = _seq_reference(pipe, pv, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_1f1b_grads_match_autodiff():
    r = np.random.RandomState(1)
    stages = [nn.Linear(6, 6), nn.Sequential().add(nn.Linear(6, 12))
              .add(nn.Tanh()).add(nn.Linear(12, 6)), nn.Linear(6, 6),
              nn.Linear(6, 6)]
    M = 8
    pipe = Pipeline(stages, n_microbatches=M)
    pv = pipe.init(jax.random.PRNGKey(1))
    mesh = _mesh(4)
    pv = pipe.shard(pv, mesh)
    x = jnp.asarray(r.randn(16, 6), jnp.float32)
    y = jnp.asarray(r.randn(16, 6), jnp.float32)

    def loss_fn(h, t):
        return jnp.mean((h - t) ** 2)

    loss, grads, _ = pipe.train_step(pv, x, y, loss_fn, mesh)

    # reference: same loss via plain autodiff over the flat rows,
    # averaged per microbatch exactly like the schedule does
    def ref_loss(flat):
        mb = x.shape[0] // M
        total = 0.0
        for m in range(M):
            h = x[m * mb:(m + 1) * mb]
            for i, stage in enumerate(pipe.stages):
                p = pipe._p_meta[i].unflatten(flat[i])
                s = pipe._s_meta[i].unflatten(pv["state"][i])
                h, _ = stage.apply(p, s, h, training=True,
                                   rng=jax.random.PRNGKey(0))
            total = total + loss_fn(h, y[m * mb:(m + 1) * mb])
        return total / M

    # (the dense reference jitted: one program, not one per eager op)
    want_loss, want_grads = jax.jit(
        jax.value_and_grad(ref_loss))(pv["flat"])
    np.testing.assert_allclose(float(loss), float(want_loss), atol=1e-5)
    np.testing.assert_allclose(np.asarray(grads), np.asarray(want_grads),
                               atol=1e-4, rtol=1e-4)


def test_pipeline_batchnorm_state_threads():
    """BatchNorm stages are now supported: running stats update across
    microbatches in schedule order (round-1 raised NotImplementedError)."""
    stages = [nn.Sequential().add(nn.Linear(4, 4))
              .add(nn.BatchNormalization(4, momentum=0.5)),
              nn.Linear(4, 4)]
    pipe = Pipeline(stages, n_microbatches=4)
    pv = pipe.init(jax.random.PRNGKey(0))
    mesh = _mesh(2)
    pv = pipe.shard(pv, mesh)
    x = jnp.asarray(np.random.RandomState(0).randn(8, 4) * 3 + 1,
                    jnp.float32)
    out, pv2 = pipe.apply(pv, x, mesh, training=True)
    s0_before = pipe._s_meta[0].unflatten(pv["state"][0])
    s0_after = pipe._s_meta[0].unflatten(pv2["state"][0])
    rm_b = jax.tree.leaves(s0_before)[0]
    rm_a = jax.tree.leaves(s0_after)[0]
    assert float(jnp.abs(rm_a - rm_b).max()) > 1e-3  # stats moved


def test_shape_changing_stage_rejected():
    pipe = Pipeline([nn.Linear(6, 8), nn.Linear(8, 6)], n_microbatches=2)
    pv = pipe.init(jax.random.PRNGKey(0))
    mesh = _mesh(2)
    x = jnp.zeros((4, 6), jnp.float32)
    with pytest.raises(ValueError, match="preserve"):
        pipe.apply(pipe.shard(pv, mesh), x, mesh)


def test_pipelined_transformer_lm_converges():
    """8-device: embed outside, 4 pipelined transformer blocks, head
    outside; 1F1B train steps drive the LM loss down (VERDICT item 7)."""
    vocab, d, T, B, M = 17, 16, 8, 16, 8
    r = np.random.RandomState(0)
    mesh = _mesh(4)

    blocks = [nn.TransformerLayer(d, 2, 2 * d, dropout=0.0)
              for _ in range(4)]
    pipe = Pipeline(blocks, n_microbatches=M)
    pv = pipe.init(jax.random.PRNGKey(0))
    pv = pipe.shard(pv, mesh)

    emb = jnp.asarray(r.randn(vocab, d) * 0.1, jnp.float32)
    head = jnp.asarray(r.randn(d, vocab) * 0.1, jnp.float32)

    # data: repeating token pattern → next-token prediction is learnable
    toks = np.stack([(np.arange(T) + i) % vocab for i in range(B)])
    xt = jnp.asarray(toks[:, :-1])
    yt = jnp.asarray(toks[:, 1:])

    def lm_loss(h_mb, y_mb):
        logits = h_mb @ head
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y_mb[..., None],
                                             axis=-1))

    losses = []
    flat = pv["flat"]
    for step in range(30):
        pv_step = {"flat": flat, "state": pv["state"]}
        h_in = emb[xt]                       # embed outside the pipe
        loss, grads, pv_step = pipe.train_step(pv_step, h_in, yt,
                                               lm_loss, mesh)
        flat = flat - 0.5 * grads
        losses.append(float(loss))
    assert losses[-1] < 0.5 * losses[0], losses


def test_train_step_full_matches_unpipelined_grads():
    """train_step_full's boundary gradients (d_x -> embedding, head/ln
    grads) and stage grads must equal the same math computed without the
    pipeline — 1F1B end to end is an exact program transform."""
    from bigdl_tpu.models.pipelined_lm import PipelinedLM
    vocab, dm, T, B, M, S = 13, 8, 6, 8, 4, 2
    mesh = _mesh(S)
    lm = PipelinedLM(vocab, d_model=dm, num_heads=2, num_layers=2,
                     n_stages=S, n_microbatches=M)
    st = lm.init(jax.random.PRNGKey(0), mesh)
    r = np.random.RandomState(0)
    xt = jnp.asarray(r.randint(0, vocab, (B, T)))
    yt = jnp.asarray(r.randint(0, vocab, (B, T)))

    pv = st["pv"]
    h, pull = jax.vjp(lambda e: lm._embed(e, xt), st["emb"])
    lp = {"emb": st["emb"], "ln": st["ln"]}
    loss, g_stage, d_x, d_lp, _ = lm.pipe.train_step_full(
        pv, h, yt, lm._loss_fn(), mesh, loss_params=lp)

    def ref(flat, emb, ln):
        hh = lm._embed(emb, xt)
        for i, stage in enumerate(lm.pipe.stages):
            p = lm.pipe._p_meta[i].unflatten(flat[i])
            s = lm.pipe._s_meta[i].unflatten(pv["state"][i])
            hh, _ = stage.apply(p, s, hh, training=True)
        hh, _ = lm.final_ln.apply(ln, {}, hh)
        logp = jax.nn.log_softmax(hh @ emb.T, -1)
        return -jnp.mean(jnp.take_along_axis(logp, yt[..., None], -1))

    # (the dense reference jitted: one program, not one per eager op)
    ref_loss, (g_flat, g_emb, g_ln) = jax.jit(jax.value_and_grad(
        ref, argnums=(0, 1, 2)))(pv["flat"], st["emb"], st["ln"])
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    np.testing.assert_allclose(np.asarray(g_stage), np.asarray(g_flat),
                               rtol=1e-4, atol=1e-5)
    (d_emb_in,) = pull(d_x)
    np.testing.assert_allclose(np.asarray(d_emb_in + d_lp["emb"]),
                               np.asarray(g_emb), rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(d_lp["ln"]), jax.tree.leaves(g_ln)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pipelined_lm_zoo_model_converges():
    """The zoo PipelinedLM (VERDICT r2 #9): embedding+head train together
    with the pipelined body; next-token loss drops on learnable data."""
    from bigdl_tpu.models.pipelined_lm import PipelinedLM
    vocab, T, B = 17, 8, 16
    mesh = _mesh(4)
    lm = PipelinedLM(vocab, d_model=32, num_heads=2, num_layers=4,
                     n_stages=4, n_microbatches=8)
    st = lm.init(jax.random.PRNGKey(1), mesh)
    toks = np.stack([(np.arange(T + 1) + i) % vocab for i in range(B)])
    xt, yt = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
    losses = []
    for i in range(40):
        st, loss = lm.train_step(st, xt, yt, mesh, lr=0.05)
        losses.append(loss)
    assert losses[-1] < 0.4 * losses[0], (losses[0], losses[-1])
    # inference path agrees with what training optimized
    logits = lm.apply(st, xt, mesh)
    acc = float((jnp.argmax(logits, -1) == yt).mean())
    assert acc > 0.5, acc


def test_pipelined_lm_fused_loss_matches_dense():
    """fused_loss (cut cross-entropy on the last stage) must produce the
    same loss and train the same as the dense tied-softmax loss."""
    from bigdl_tpu.models.pipelined_lm import PipelinedLM
    vocab, T, B = 19, 8, 8
    mesh = _mesh(2)
    toks = np.stack([(np.arange(T + 1) + i) % vocab for i in range(B)])
    xt, yt = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])

    def run(fused):
        lm = PipelinedLM(vocab, d_model=16, num_heads=2, num_layers=2,
                         n_stages=2, n_microbatches=4, fused_loss=fused,
                         fused_interpret=True)
        st = lm.init(jax.random.PRNGKey(3), mesh)
        losses = []
        for _ in range(6):
            st, loss = lm.train_step(st, xt, yt, mesh, lr=0.05)
            losses.append(loss)
        return losses, st

    l_dense, st_d = run(False)
    l_fused, st_f = run(True)
    np.testing.assert_allclose(l_fused, l_dense, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_f["emb"]),
                               np.asarray(st_d["emb"]),
                               rtol=1e-4, atol=1e-5)


def test_pipelined_lm_fused_loss_unaligned_rows():
    """Regression: microbatch rows not a multiple of 128 (e.g. 2x96=192)
    must pad through the kernel, not raise."""
    from bigdl_tpu.models.pipelined_lm import PipelinedLM
    vocab, T, B = 13, 96, 8               # rows/microbatch = 2*96 = 192
    mesh = _mesh(2)
    r = np.random.RandomState(0)
    xt = jnp.asarray(r.randint(0, vocab, (B, T)))
    yt = jnp.asarray(r.randint(0, vocab, (B, T)))
    lm = PipelinedLM(vocab, d_model=16, num_heads=2, num_layers=2,
                     n_stages=2, n_microbatches=4, fused_loss=True,
                     fused_interpret=True)
    st = lm.init(jax.random.PRNGKey(0), mesh)
    st, loss = lm.train_step(st, xt, yt, mesh, lr=0.05)
    assert np.isfinite(loss)
