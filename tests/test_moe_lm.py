"""Expert-parallel zoo MoE LM (models/moe_lm.py): training over the
'expert' mesh must match the unsharded MoE computation and converge."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from bigdl_tpu.models.moe_lm import MoELM


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n), ("expert",))


def test_moe_lm_matches_dense_loss_and_grads():
    """Dropless routing ⇒ the expert-parallel all_to_all path computes
    EXACTLY the unsharded layer: CE loss and every gradient agree.
    (lb_coef=0: the load-balance stat is per-shard by design; the z-loss
    pmean IS the global mean, so it stays in the objective.)"""
    vocab, T, B = 19, 8, 8
    mesh = _mesh(4)
    lm = MoELM(vocab, d_model=16, num_heads=2, num_layers=2, n_experts=4,
               dropless=True, lb_coef=0.0, z_coef=1e-3)
    params = lm.init(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    xt = jnp.asarray(r.randint(0, vocab, (B, T)))
    yt = jnp.asarray(r.randint(0, vocab, (B, T)))

    loss, ce, aux, grads = lm.loss_and_grads(params, xt, yt, mesh)

    def dense(p):
        total, (ce, aux) = lm.dense_objective(p, xt, yt)
        return total
    # (the dense reference jitted: one program, not one per eager op)
    want_loss, want_grads = jax.jit(jax.value_and_grad(dense))(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


def test_moe_lm_converges_with_balanced_experts():
    vocab, T, B = 17, 8, 16
    mesh = _mesh(8)
    lm = MoELM(vocab, d_model=32, num_heads=2, num_layers=2, n_experts=8,
               capacity_factor=2.0)
    params = lm.init(jax.random.PRNGKey(1))
    toks = np.stack([(np.arange(T + 1) + i) % vocab for i in range(B)])
    xt, yt = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
    ces = []
    for _ in range(80):
        params, ce, aux = lm.train_step(params, xt, yt, mesh, lr=0.1)
        ces.append(ce)
    assert ces[-1] < 0.3 * ces[0], (ces[0], ces[-1])
    # router stays usable (uniform optimum is 1.0; a collapsed router on
    # E=8 would read ~8) — tiny toy batches route unevenly, so the bound
    # is loose
    assert np.isfinite(aux["load_balance"]) and aux["load_balance"] < 5.0