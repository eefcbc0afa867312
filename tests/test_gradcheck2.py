"""Catalog-wide numeric gradient sweep (reference:
test/.../nn/GradientChecker.scala — every layer's backward checked against
central differences; here autodiff replaces hand-written backwards, so the
sweep guards the places autodiff CAN silently diverge: custom VJPs, where()
gates, selection ops, scan recurrences, normalization statistics).

Every catalog entry with grad=True gets: all float leaves of
(params, inputs) raveled into one vector, sum-of-squares objective over the
float output leaves, a sampled central-difference comparison against
jax.grad. Criterions use their scalar loss directly.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

import bigdl_tpu.nn as nn
from layer_catalog import CRITERIA, MODULES, x


def _is_float(leaf):
    return hasattr(leaf, "dtype") and jnp.issubdtype(
        jnp.asarray(leaf).dtype, jnp.floating)


def _split(tree):
    """Flatten `tree`; return (flat float vector, rebuild fn)."""
    leaves, treedef = jax.tree.flatten(tree)
    is_diff = [_is_float(l) for l in leaves]
    diff = [jnp.asarray(l) for l, d in zip(leaves, is_diff) if d]
    flat, unravel = ravel_pytree(diff)

    def rebuild(vec):
        dl = iter(unravel(vec))
        full = [next(dl) if d else l for l, d in zip(leaves, is_diff)]
        return jax.tree.unflatten(treedef, full)

    return flat, rebuild


def _loss_of(out):
    total = 0.0
    for leaf in jax.tree.leaves(out):
        if _is_float(leaf):
            total = total + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
    return total


def _sampled_check(f, flat, *, eps=1e-3, rtol=5e-2, atol=5e-3,
                   max_entries=12, seed=0):
    # the differenced objective keeps the backend's optimisations, which
    # conftest.py turns off: unoptimised, its fp32 sums run one element
    # at a time and their rounding alone exceeds `atol` for one layer
    fj = jax.jit(f, compiler_options={"xla_backend_optimization_level": 2})
    auto = np.asarray(jax.jit(jax.grad(f))(flat), np.float64)
    n = flat.size
    idx = np.arange(n)
    if n > max_entries:
        idx = np.random.RandomState(seed).choice(n, max_entries,
                                                 replace=False)
    base = np.asarray(flat, np.float64)
    num = np.zeros(len(idx))
    for j, i in enumerate(idx):
        bump = np.zeros_like(base)
        bump[i] = eps
        hi = float(fj(jnp.asarray(base + bump, jnp.float32)))
        lo = float(fj(jnp.asarray(base - bump, jnp.float32)))
        num[j] = (hi - lo) / (2 * eps)
    # scale-aware atol, same rationale as utils.gradcheck.check_gradients:
    # fp32 central differences cannot resolve entries tiny next to the
    # largest gradient magnitude
    scale = float(np.max(np.abs(auto))) if auto.size else 0.0
    atol_eff = max(atol, 2e-3 * scale)
    np.testing.assert_allclose(auto[idx], num, rtol=rtol, atol=atol_eff)


_GRAD_MODULES = [n for n, e in MODULES.items() if e.grad]
_GRAD_CRITERIA = [n for n, e in CRITERIA.items() if e.grad]


@pytest.mark.parametrize("name", _GRAD_MODULES)
def test_module_gradients(name):
    e = MODULES[name]
    mod = e.build()
    params, state = mod.init(jax.random.PRNGKey(0))
    inputs = e.inputs()
    kw = dict(e.kwargs)
    if e.train_rng:
        kw.update(training=True, rng=jax.random.PRNGKey(42))
    flat, rebuild = _split((params, inputs))
    if flat.size == 0:
        pytest.skip("no float leaves to differentiate")

    def f(vec):
        p2, in2 = rebuild(vec)
        out, _ = mod.apply(p2, state, *in2, **kw)
        if e.post:
            out = e.post(out)
        return _loss_of(out)

    _sampled_check(f, flat)


@pytest.mark.parametrize("name", _GRAD_CRITERIA)
def test_criterion_gradients(name):
    e = CRITERIA[name]
    crit = e.build()
    inp, tgt = e.inputs()
    flat, rebuild = _split(inp)
    if flat.size == 0:
        pytest.skip("no float leaves to differentiate")

    def f(vec):
        return crit.forward(rebuild(vec), tgt)

    _sampled_check(f, flat)


def test_gradient_reversal_semantics():
    """GradientReversal is EXCLUDED from the numeric sweep on purpose: its
    backward (-λ·g) intentionally disagrees with its forward (identity) —
    reference: nn/GradientReversal.scala. Check the defining contract."""
    m = nn.GradientReversal(0.7)
    params, state = m.init(jax.random.PRNGKey(0))
    v = x(3, 4)

    g = jax.grad(lambda a: jnp.sum(m.apply(params, state, a)[0] * 2.0))(v)
    np.testing.assert_allclose(np.asarray(g),
                               -0.7 * 2.0 * np.ones_like(v), rtol=1e-6)


def test_dense_to_sparse_gradcheck_is_na():
    """DenseToSparse runs on the host (data-dependent shapes) — its grad
    path is the documented propagate_back flag, not autodiff; covered by
    the sparse round-trip in the serializer sweep."""
    from bigdl_tpu.nn.sparse import SparseCOO
    out = nn.DenseToSparse(4).forward({}, x(3, 8))
    assert isinstance(out, SparseCOO)
