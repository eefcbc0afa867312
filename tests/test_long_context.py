"""Sequence-parallel zoo LM (models/long_context_lm.py): ring-attention
training over the 'seq' mesh must be EXACTLY the single-device dense
computation (loss and every gradient), and must converge."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from bigdl_tpu.models.long_context_lm import (SeqParallelLM,
                                              positional_encoding_at)


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n), ("seq",))


def test_positional_encoding_at_matches_prefix():
    from bigdl_tpu.nn.attention import positional_encoding
    full = positional_encoding(16, 12)
    at = positional_encoding_at(jnp.arange(8, 16), 12)
    np.testing.assert_allclose(np.asarray(at), np.asarray(full[8:]),
                               rtol=1e-6)


def test_seq_parallel_matches_dense_loss_and_grads():
    vocab, d, T, B = 23, 16, 32, 2
    mesh = _mesh(4)
    lm = SeqParallelLM(vocab, d_model=d, num_heads=2, num_layers=2)
    params = lm.init(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    xt = jnp.asarray(r.randint(0, vocab, (B, T)))
    yt = jnp.asarray(r.randint(0, vocab, (B, T)))

    loss, grads = lm.loss_and_grads(params, xt, yt, mesh)

    # dense single-device reference: same params, same math, no mesh
    from bigdl_tpu.nn.attention import positional_encoding

    def dense_loss(p):
        x = p["emb"][xt] * np.sqrt(d) + positional_encoding(T, d)
        for i, blk in enumerate(lm.blocks):
            # dense attention (the blocks' RingAttention needs the mesh,
            # so clone the computation through the dense kernel)
            from bigdl_tpu.nn.attention import TransformerLayer
            dense_blk = TransformerLayer(d, 2, 4 * d)
            x, _ = dense_blk.apply(p[f"h{i}"], {}, x, causal=True)
        x, _ = lm.final_ln.apply(p["ln"], {}, x)
        logp = jax.nn.log_softmax(x @ p["emb"].T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, yt[..., None], -1))

    # (the dense reference jitted: one program, not one per eager op)
    want_loss, want_grads = jax.jit(
        jax.value_and_grad(dense_loss))(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_seq_parallel_lm_converges_and_infers():
    vocab, T, B = 17, 32, 4
    mesh = _mesh(8)
    lm = SeqParallelLM(vocab, d_model=32, num_heads=2, num_layers=2)
    params = lm.init(jax.random.PRNGKey(1))
    toks = np.stack([(np.arange(T + 1) + i) % vocab for i in range(B)])
    xt, yt = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
    losses = []
    for _ in range(60):
        params, loss = lm.train_step(params, xt, yt, mesh, lr=0.1)
        losses.append(loss)
    assert losses[-1] < 0.2 * losses[0], (losses[0], losses[-1])
    logits = lm.apply(params, xt, mesh)
    assert logits.shape == (B, T, vocab)
    acc = float((jnp.argmax(logits, -1) == yt).mean())
    assert acc > 0.7, acc


def test_seq_parallel_composes_with_data_parallel():
    """dp x sp: batch over 'data', sequence over 'seq' on a 2x4 mesh —
    loss and gradients still exactly match the dense computation."""
    from bigdl_tpu.parallel.mesh import create_mesh
    vocab, d, T, B = 13, 16, 16, 4
    mesh = create_mesh(jax.devices(), seq=4)       # data=2 x seq=4
    assert mesh.shape["data"] == 2 and mesh.shape["seq"] == 4
    lm = SeqParallelLM(vocab, d_model=d, num_heads=2, num_layers=1)
    params = lm.init(jax.random.PRNGKey(2))
    r = np.random.RandomState(2)
    xt = jnp.asarray(r.randint(0, vocab, (B, T)))
    yt = jnp.asarray(r.randint(0, vocab, (B, T)))
    loss, grads = lm.loss_and_grads(params, xt, yt, mesh)

    from bigdl_tpu.nn.attention import TransformerLayer, \
        positional_encoding

    def dense_loss(p):
        x = p["emb"][xt] * np.sqrt(d) + positional_encoding(T, d)
        blk = TransformerLayer(d, 2, 4 * d)
        x, _ = blk.apply(p["h0"], {}, x, causal=True)
        x, _ = lm.final_ln.apply(p["ln"], {}, x)
        logp = jax.nn.log_softmax(x @ p["emb"].T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, yt[..., None], -1))

    # (the dense reference jitted: one program, not one per eager op)
    want_loss, want_grads = jax.jit(
        jax.value_and_grad(dense_loss))(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    out = lm.apply(params, xt, mesh)
    assert out.shape == (B, T, vocab)


def test_parallel_zoo_states_checkpoint_roundtrip(tmp_path):
    """Every custom-parallelism zoo model's training state rides the
    standard checkpoint format — sharded leaves (pipe-sharded stage rows,
    expert-sharded FFNs) gather on save and restore bit-exact."""
    from bigdl_tpu.models.moe_lm import MoELM
    from bigdl_tpu.models.pipelined_lm import PipelinedLM
    from bigdl_tpu.utils import checkpoint as ckpt
    from bigdl_tpu.parallel.mesh import create_mesh

    # seq-parallel (replicated params)
    smesh = _mesh(4)
    slm = SeqParallelLM(13, d_model=16, num_heads=2, num_layers=1)
    sp = slm.init(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    xt = jnp.asarray(r.randint(0, 13, (4, 8)))
    yt = jnp.asarray(r.randint(0, 13, (4, 8)))
    sp, _ = slm.train_step(sp, xt, yt, smesh, lr=0.1)

    # pipelined (stage-sharded flat rows)
    from jax.sharding import Mesh
    pmesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("pipe",))
    plm = PipelinedLM(13, d_model=16, num_heads=2, num_layers=2,
                      n_stages=2, n_microbatches=4)
    pst = plm.init(jax.random.PRNGKey(1), pmesh)
    pst, _ = plm.train_step(pst, xt, yt, pmesh, lr=0.1)

    # moe (expert-sharded FFNs)
    emesh = create_mesh(jax.devices()[:4], expert=4,
                        drop_trivial_axes=True)
    mlm = MoELM(13, d_model=16, num_heads=2, num_layers=1, n_experts=4,
                dropless=True)
    mp = mlm.init(jax.random.PRNGKey(2))
    mp, _, _ = mlm.train_step(mp, xt, yt, emesh, lr=0.1)

    trees = {"seq": sp, "pipe": pst, "moe": mp}
    path = str(tmp_path / "parallel-snap")
    ckpt.save_checkpoint(path, trees, {"neval": 3})
    loaded, meta = ckpt.load_checkpoint(path)
    assert meta["neval"] == 3
    for name in trees:
        for a, b in zip(jax.tree.leaves(trees[name]),
                        jax.tree.leaves(loaded[name])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # a restored pipeline state keeps training after re-sharding
    pst3 = {"emb": jnp.asarray(loaded["pipe"]["emb"]),
            "ln": loaded["pipe"]["ln"],
            "pv": plm.pipe.shard(
                {"flat": np.asarray(loaded["pipe"]["pv"]["flat"]),
                 "state": np.asarray(loaded["pipe"]["pv"]["state"])},
                pmesh)}
    pst3, loss = plm.train_step(pst3, xt, yt, pmesh, lr=0.1)
    assert np.isfinite(loss)


@pytest.mark.slow        # trains three parallel LMs, 25 Adam steps each, to
#                          half their first loss (27 s at the parent, 23 s now)
def test_parallel_zoo_models_train_with_optim_methods():
    """Every parallel zoo model accepts a stateful OptimMethod (Adam here;
    OptaxMethod works identically) and converges faster than where it
    started — slots shard alongside their params."""
    from bigdl_tpu.models.moe_lm import MoELM
    from bigdl_tpu.models.pipelined_lm import PipelinedLM
    from bigdl_tpu.optim.method import Adam, init_update_slots
    from bigdl_tpu.parallel.mesh import create_mesh
    from jax.sharding import Mesh

    vocab, T, B = 17, 8, 8
    toks = np.stack([(np.arange(T + 1) + i) % vocab for i in range(B)])
    xt, yt = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])

    # seq-parallel + Adam
    smesh = _mesh(4)
    slm = SeqParallelLM(vocab, d_model=16, num_heads=2, num_layers=1)
    sp = slm.init(jax.random.PRNGKey(0))
    adam = Adam(5e-2)
    slots = init_update_slots(adam, sp)
    first = last = None
    for i in range(25):
        sp, loss, slots = slm.train_step(sp, xt, yt, smesh,
                                         method=adam, slots=slots)
        first = loss if first is None else first
        last = loss
    assert last < 0.5 * first, (first, last)

    # pipelined + Adam (slots cover emb/ln/stage-rows)
    pmesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("pipe",))
    plm = PipelinedLM(vocab, d_model=16, num_heads=2, num_layers=2,
                      n_stages=2, n_microbatches=4)
    pst = plm.init(jax.random.PRNGKey(1), pmesh)
    padam = Adam(5e-2)
    pslots = init_update_slots(padam, {"emb": pst["emb"],
                                       "ln": pst["ln"],
                                       "flat": pst["pv"]["flat"]})
    first = last = None
    for i in range(25):
        pst, loss, pslots = plm.train_step(pst, xt, yt, pmesh,
                                           method=padam, slots=pslots)
        first = loss if first is None else first
        last = loss
    assert last < 0.5 * first, (first, last)

    # moe + Adam
    emesh = create_mesh(jax.devices()[:4], expert=4,
                        drop_trivial_axes=True)
    mlm = MoELM(vocab, d_model=16, num_heads=2, num_layers=1,
                n_experts=4, dropless=True)
    mp = mlm.init(jax.random.PRNGKey(2))
    madam = Adam(5e-2)
    mslots = init_update_slots(madam, mp)
    first = last = None
    for i in range(25):
        mp, ce, _, mslots = mlm.train_step(mp, xt, yt, emesh,
                                           method=madam, slots=mslots)
        first = ce if first is None else first
        last = ce
    assert last < 0.5 * first, (first, last)
