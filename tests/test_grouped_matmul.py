"""The grouped matrix product of the expert layer (`kernels/grouped_matmul`)
in the Pallas interpreter against `jax.lax.ragged_dot`, at a few dozen rows:
every pattern of group sizes the layer makes and every row tile the rule
picks; then `GatedExperts.mixed` through the kernel and through
`ragged_dot`, and the arrays laid over several devices that keep
`ragged_dot`."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bigdl_tpu.kernels import grouped_matmul as gmm
from bigdl_tpu.nn import experts
from bigdl_tpu.nn.experts import GatedExperts

K, N = 16, 24

# name: (group sizes, rows, the row tile the rule picks)
CASES = {
    "equal": ([8, 8, 8, 8], 32, 32),
    "empty_first": ([0, 5, 7, 4], 16, 16),
    "empty_middle": ([6, 0, 0, 10], 16, 16),
    "empty_last": ([9, 7, 0, 0], 16, 16),
    "one_group": ([0, 40, 0], 40, 64),
    "rows_past_the_last_group": ([3, 0, 4, 2], 30, 32),
    "rows_not_a_multiple_of_the_tile": ([50, 27, 23], 100, 128),
    "groups_across_row_tiles": ([70, 1, 90, 39, 0], 260, 128),
    "chunk_tile": ([300, 212], 512, 256),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ragged_dot(case, dtype):
    sizes, m, tm = CASES[case]
    G, dtype = len(sizes), jnp.dtype(dtype)
    assert gmm.tiling(m, K, N, G)[0] == tm
    kx, kw = jax.random.split(jax.random.PRNGKey(len(case)))
    x = jax.random.normal(kx, (m, K)).astype(dtype)
    w = jax.random.normal(kw, (G, K, N)).astype(dtype)
    s = jnp.asarray(sizes, jnp.int32)
    got = gmm.grouped_matmul(x, w, s, interpret=True)
    assert got.shape == (m, N) and got.dtype == dtype
    rows = sum(sizes)                    # rows past the last group: as they may
    want = jax.lax.ragged_dot(x.astype(jnp.float32), w.astype(jnp.float32), s)
    # float32 to its rounding; bfloat16 to one rounding of the result
    rtol, atol = (1e-5, 1e-4) if dtype == jnp.float32 else (2 ** -7, 2e-2)
    np.testing.assert_allclose(np.asarray(got[:rows], np.float32),
                               np.asarray(want[:rows]), rtol=rtol, atol=atol)
    same = jax.lax.ragged_dot(x, w, s)
    np.testing.assert_allclose(np.asarray(got[:rows], np.float32),
                               np.asarray(same[:rows], np.float32),
                               rtol=rtol, atol=atol)


def test_no_row_in_any_group_writes_nothing_and_runs():
    x = jnp.ones((8, K))
    out = gmm.grouped_matmul(x, jnp.ones((3, K, N)),
                             jnp.zeros((3,), jnp.int32), interpret=True)
    assert out.shape == (8, N)


def _layer(seed=0):
    layer = GatedExperts(16, 12, 8, 2, expert_share=(2, 4), scaling=2.5)
    params, _ = GatedExperts(16, 12, 8, 2, scaling=2.5).init(
        jax.random.PRNGKey(seed))
    params = jax.tree.map(lambda a: a + 0.3 * jax.random.normal(
        jax.random.PRNGKey(a.size), a.shape), params)
    return layer, dict(params, **{k: params[k][2:6]
                                  for k in ("gate", "up", "down")})


def test_mixed_through_the_kernel_and_through_ragged_dot(monkeypatch):
    """The same `y` and counts whichever product the layer's three
    projections go through; a padded tail (`valid`) sorts behind the last
    group with the absent experts' pairs."""
    layer, params = _layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 13, 16))
    valid = jnp.arange(13)[None, :] < jnp.asarray([[13], [9]])
    y, counts = layer.mixed(params, x, valid)
    monkeypatch.setattr(experts, "grouped_matmul", functools.partial(
        gmm.grouped_matmul, interpret=True))
    y2, counts2 = layer.mixed(params, x, valid)
    assert counts2.tolist() == counts.tolist() and int(counts[0]) > 0
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y), rtol=1e-5,
                               atol=1e-5)


def test_the_kernel_is_the_tpu_path_of_unsharded_arrays():
    x, w = jnp.ones((16, K)), jnp.ones((4, K, N))
    text = str(jax.make_jaxpr(gmm.grouped_matmul)(
        x, w, jnp.full((4,), 4, jnp.int32)))
    assert "pallas_call" in text and "ragged_dot" in text


def test_experts_laid_over_devices_keep_ragged_dot(monkeypatch):
    """GSPMD cannot partition a Pallas call: expert arrays split over a
    mesh go through `ragged_dot`, placed or traced under the mesh; a
    replicated copy does not count as split."""
    mesh = jax.make_mesh((4,), ("expert",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:4])
    layer, params = _layer()
    x = jax.random.normal(jax.random.PRNGKey(2), (11, 16))
    y, counts = layer.mixed(params, x)
    split = dict(params, **{k: jax.device_put(
        params[k], NamedSharding(mesh, P("expert"))) for k in
        ("gate", "up", "down")})
    assert gmm._spread(split["gate"])
    assert not gmm._spread(jax.device_put(params["gate"],
                                          NamedSharding(mesh, P())))
    assert not gmm._spread(params["gate"])

    def refused(*_, **__):
        raise AssertionError("the Pallas call was traced for split experts")
    monkeypatch.setattr(gmm, "_pallas", refused)
    y2, counts2 = layer.mixed(split, x)
    assert counts2.tolist() == counts.tolist()
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y), rtol=1e-5,
                               atol=1e-5)
    with jax.set_mesh(mesh):
        y3 = jax.jit(lambda p, a: layer.mixed(p, a)[0])(split, x)
    np.testing.assert_allclose(np.asarray(y3), np.asarray(y), rtol=1e-5,
                               atol=1e-5)
