"""Catalog-wide serialization round-trips — closes the gap between the
original 26-layer sweep (test_serializer_sweep.py) and the reference's
per-layer ModuleSerializationTests (every layer must survive the durable
format and reproduce its outputs bit-for-bit).

Modules go through save_module/load_module; criterions (stateless pure
loss objects that ride checkpoints via pickle) through pickle. Stochastic
layers replay with the same rng; sparse outputs compare densified.
"""

import pickle

import numpy as np
import pytest

import jax

import bigdl_tpu.nn as nn  # noqa: F401  (builders resolve through nn)
from bigdl_tpu.utils.serializer import load_module, save_module
from layer_catalog import CRITERIA, MODULES

_SER_MODULES = [n for n, e in MODULES.items() if e.ser]
_SER_CRITERIA = [n for n, e in CRITERIA.items() if e.ser]


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        np.testing.assert_allclose(np.asarray(xa), np.asarray(xb),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("name", _SER_MODULES)
def test_module_roundtrip(name, tmp_path):
    e = MODULES[name]
    mod = e.build()
    params, state = mod.init(jax.random.PRNGKey(0))
    inputs = e.inputs()
    kw = dict(e.kwargs)
    if e.train_rng:
        kw.update(training=True, rng=jax.random.PRNGKey(42))

    def forward(m, p, s):
        """One program a module, not one per eager op (the detection
        heads' NMS loops alone were 25 s of this file)."""
        def run(p, s, *xs):
            return m.apply(p, s, *xs, **kw)[0]
        return (run if e.host else jax.jit(run))(p, s, *inputs)

    want = forward(mod, params, state)
    path = str(tmp_path / f"{name}.bigdl-tpu")
    save_module(path, mod, params, state)
    got = forward(*load_module(path))
    if e.post:
        want, got = e.post(want), e.post(got)
    _assert_tree_equal(want, got)


@pytest.mark.parametrize("name", _SER_CRITERIA)
def test_criterion_roundtrip(name):
    e = CRITERIA[name]
    crit = e.build()
    inp, tgt = e.inputs()
    want = float(crit.forward(inp, tgt))
    crit2 = pickle.loads(pickle.dumps(crit))
    got = float(crit2.forward(inp, tgt))
    np.testing.assert_allclose(got, want, rtol=1e-7)
