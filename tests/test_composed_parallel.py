"""Composed parallelism meshes (VERDICT r3 next #6): dp×pp and dp×ep on a
2×4 mesh must be EXACTLY the dense / single-axis computation — batch
shards over 'data' while stages/experts shard over their own axis
(the hierarchical layout real slices use: dp over DCN, pp/ep over ICI).
dp×sp parity lives in test_long_context.py; the 4-process cross-host run
of all three is tests/test_multihost.py::test_four_process_composed."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import bigdl_tpu.nn as nn
from bigdl_tpu.models.moe_lm import MoELM
from bigdl_tpu.parallel.pipeline import Pipeline


def _mesh2x4():
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    return Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "pipe"))


def test_dp_pp_matches_pure_pipeline():
    mesh = _mesh2x4()
    mesh1 = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("pipe",))
    pipe = Pipeline(nn.Linear(6, 6), n_stages=4, n_microbatches=4)
    pv = pipe.shard(pipe.init(jax.random.PRNGKey(2)), mesh)
    pv1 = pipe.shard(pipe.init(jax.random.PRNGKey(2)), mesh1)
    x = jnp.asarray(np.random.RandomState(2).randn(8, 6), jnp.float32)
    y = jnp.asarray(np.random.RandomState(3).randn(8, 6), jnp.float32)

    def mse(h, t):
        return jnp.mean((h - t) ** 2)

    loss, grads, _ = pipe.train_step(pv, x, y, mse, mesh)
    loss1, grads1, _ = pipe.train_step(pv1, x, y, mse, mesh1)
    np.testing.assert_allclose(float(loss), float(loss1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grads), np.asarray(grads1),
                               rtol=1e-4, atol=1e-6)

    out = pipe.apply(pv, x, mesh)
    out1 = pipe.apply(pv1, x, mesh1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out1),
                               rtol=1e-5, atol=1e-6)


def test_dp_pp_full_boundary_gradients_match():
    """train_step_full under dp×pp: dL/dx rows stay with their data group
    but carry the GLOBAL-mean scale; head grads average across groups."""
    mesh = _mesh2x4()
    mesh1 = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("pipe",))
    pipe = Pipeline(nn.Linear(6, 6), n_stages=4, n_microbatches=4)
    pv = pipe.shard(pipe.init(jax.random.PRNGKey(2)), mesh)
    pv1 = pipe.shard(pipe.init(jax.random.PRNGKey(2)), mesh1)
    x = jnp.asarray(np.random.RandomState(2).randn(8, 6), jnp.float32)
    y = jnp.asarray(np.random.RandomState(3).randn(8, 6), jnp.float32)
    head = {"w": jnp.asarray(np.random.RandomState(5).randn(6, 6),
                             jnp.float32)}

    def loss_full(h, t, lp):
        return jnp.mean((h @ lp["w"] - t) ** 2)

    lf, g, dx, dlp, _ = pipe.train_step_full(pv, x, y, loss_full, mesh,
                                             loss_params=head)
    lf1, g1, dx1, dlp1, _ = pipe.train_step_full(
        pv1, x, y, loss_full, mesh1, loss_params=head)
    np.testing.assert_allclose(float(lf), float(lf1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g1), rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx1),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dlp["w"]),
                               np.asarray(dlp1["w"]), rtol=1e-4,
                               atol=1e-6)


def test_dp_ep_matches_dense_and_pure_ep():
    """Every gradient leaf of the dp×ep MoE-LM equals the dense and the
    pure-ep computation (regularizers off: the load-balance/z statistics
    are per-shard by design, so only CE is partition-invariant)."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    lm = MoELM(13, d_model=16, num_heads=2, num_layers=1, n_experts=4,
               dropless=True, lb_coef=0.0, z_coef=0.0)
    params = lm.init(jax.random.PRNGKey(6))
    toks = np.random.RandomState(6).randint(0, 13, (8, 6))
    xt = jnp.asarray(toks)
    yt = jnp.asarray(np.roll(toks, -1, axis=1))

    # (the dense reference jitted: one program, not one per eager op)
    dense_loss, g_dense = jax.jit(jax.value_and_grad(
        lambda p: lm.dense_objective(p, xt, yt)[0]))(params)
    mesh_ep = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("expert",))
    l1, ce1, _, g1 = lm.loss_and_grads(params, xt, yt, mesh_ep)
    mesh2 = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                 ("data", "expert"))
    l2, ce2, _, g2 = lm.loss_and_grads(params, xt, yt, mesh2)

    np.testing.assert_allclose(float(l1), float(dense_loss), rtol=1e-5)
    np.testing.assert_allclose(float(l2), float(dense_loss), rtol=1e-5)
    for a, b, c in zip(jax.tree.leaves(g_dense), jax.tree.leaves(g1),
                       jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                                   rtol=1e-4, atol=1e-6)


def test_dp_ep_ce_is_partition_invariant_with_regularizers():
    """With the regularizers ON, CE (linear in the batch partition) still
    matches exactly; the total loss only approximately (per-shard lb/z
    stats — the reference's per-worker statistics behave the same)."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    lm = MoELM(13, d_model=16, num_heads=2, num_layers=1, n_experts=4,
               dropless=True)
    params = lm.init(jax.random.PRNGKey(6))
    toks = np.random.RandomState(6).randint(0, 13, (8, 6))
    xt = jnp.asarray(toks)
    yt = jnp.asarray(np.roll(toks, -1, axis=1))
    _, (dense_ce, _) = lm.dense_objective(params, xt, yt)
    mesh2 = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                 ("data", "expert"))
    _, ce2, _, _ = lm.loss_and_grads(params, xt, yt, mesh2)
    np.testing.assert_allclose(float(ce2), float(dense_ce), rtol=1e-5)


def test_dp_ep_trains():
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    lm = MoELM(13, d_model=16, num_heads=2, num_layers=1, n_experts=4,
               dropless=True)
    params = lm.init(jax.random.PRNGKey(0))
    toks = np.stack([(np.arange(7) + i) % 13 for i in range(8)])
    xt = jnp.asarray(toks[:, :-1])
    yt = jnp.asarray(toks[:, 1:])
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "expert"))
    ces = []
    for _ in range(25):
        params, ce, _ = lm.train_step(params, xt, yt, mesh, lr=0.05)
        ces.append(ce)
    assert ces[-1] < 0.5 * ces[0], (ces[0], ces[-1])
