"""DCN-tier gradient exchange: accumulate-locally / exchange-every-T
with error-feedback wire compression (ISSUE 13; parallel/dcn.py,
mesh.cross_slice_accumulated_exchange, docs/parallelism.md).

Acceptance (2 slices × 4 devices CPU mesh):
  * T=1 with compression off is BIT-IDENTICAL to the pre-DCN every-step
    exchange (params + slots + rng), K∈{1,4}, ZeRO-1 and replicated;
  * the T-window semantics match a hand-rolled per-slice accumulate
    oracle, and no param/slot moves before a window boundary (T > K
    threads the accumulator across jitted calls);
  * int8/bf16 compression is error-feedback exact at the primitive
    level (dequantized mean + residual reconstruct the accumulator);
  * kill-and-resume mid-window is exact (accumulator + outer state ride
    the snapshot);
  * a slice loss mid-window preserves survivor accumulators and
    explicitly drops + counts the lost slice's contribution.
"""

import os
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu import observe
from bigdl_tpu.dataset import ArrayDataSet
from bigdl_tpu.optim.method import SGD, Adam
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.parallel import DistriOptimizer, create_mesh
from bigdl_tpu.parallel import dcn
from bigdl_tpu.parallel.mesh import cross_slice_accumulated_exchange
from bigdl_tpu.resilience import faults
from bigdl_tpu.resilience.failover import remap_accumulator_rows

_KNOBS = ("BIGDL_TPU_SLICE_EXCHANGE_EVERY", "BIGDL_TPU_SLICE_GRAD_COMPRESS",
          "BIGDL_TPU_SLICE_OUTER", "BIGDL_TPU_SLICE_GRAD_DTYPE")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for k in _KNOBS:
        monkeypatch.delenv(k, raising=False)
    faults.configure("")
    faults.clear_preempt()
    faults.clear_slice_loss()
    faults.clear_slice_gain()
    yield
    faults.configure("")
    faults.clear_preempt()
    faults.clear_slice_loss()
    faults.clear_slice_gain()


def _data(n=192, d=4, seed=7):
    r = np.random.RandomState(seed)
    x = r.randn(n, d).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    return x, y


def _mlp(d=4):
    return nn.Sequential(nn.Linear(d, 8), nn.Tanh(), nn.Linear(8, 2),
                         nn.LogSoftMax())


def _two_tier():
    return create_mesh(jax.devices(), slices=2, drop_trivial_axes=True)


def _trainer(mesh, *, method=None, k=1, end=12, zero1=True, seed=5,
             ckpt_dir=None, ckpt_every=100):
    x, y = _data()
    ds = ArrayDataSet(x, y, 16, drop_last=True, shuffle=False)
    opt = DistriOptimizer(_mlp(), ds, nn.ClassNLLCriterion(),
                          method or Adam(1e-2), mesh=mesh, zero1=zero1,
                          seed=seed, steps_per_call=k)
    if ckpt_dir is not None:
        opt.set_checkpoint(str(ckpt_dir),
                           Trigger.several_iteration(ckpt_every))
    opt.set_end_when(Trigger.max_iteration(end))
    return opt


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree.leaves(jax.device_get(tree))]


def _assert_same(a, b, exact=True, msg=""):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for i, (x, y) in enumerate(zip(la, lb)):
        if exact:
            np.testing.assert_array_equal(x, y, err_msg=f"{msg}[{i}]")
        else:
            np.testing.assert_allclose(x, y, atol=2e-5, rtol=2e-5,
                                       err_msg=f"{msg}[{i}]")


# --------------------------------------------------- arming / bit-parity
def test_dcn_config_default_off_and_t1_off(monkeypatch):
    opt = _trainer(_two_tier())
    assert opt._dcn_config() is None
    monkeypatch.setenv("BIGDL_TPU_SLICE_EXCHANGE_EVERY", "1")
    monkeypatch.setenv("BIGDL_TPU_SLICE_GRAD_COMPRESS", "")
    assert opt._dcn_config() is None       # T=1 + no compress = pre-DCN
    monkeypatch.setenv("BIGDL_TPU_SLICE_GRAD_COMPRESS", "int8")
    cfg = opt._dcn_config()                # int8 EF arms even at T=1
    assert cfg is not None and cfg.every == 1 and cfg.compress == "int8"
    monkeypatch.setenv("BIGDL_TPU_SLICE_GRAD_COMPRESS", "bogus")
    with pytest.raises(ValueError):
        opt._dcn_config()


def test_dcn_needs_two_tier_mesh(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_SLICE_EXCHANGE_EVERY", "4")
    flat = create_mesh(jax.devices(), drop_trivial_axes=True)
    opt = _trainer(flat)
    assert opt._dcn_config() is None       # warns once, stays off
    p, _ = opt.optimize()                  # trains on the flat path
    assert np.isfinite(opt.state["loss"])


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("zero1", [True, False])
def test_t1_compress_off_bit_identical(monkeypatch, k, zero1):
    """Explicitly setting T=1 (and compression off) must take the exact
    pre-DCN code path: params + slots + rng bit-identical to a run with
    the knobs unset."""
    mesh = _two_tier()
    ref = _trainer(mesh, k=k, zero1=zero1)
    p_ref, _ = ref.optimize()
    monkeypatch.setenv("BIGDL_TPU_SLICE_EXCHANGE_EVERY", "1")
    monkeypatch.setenv("BIGDL_TPU_SLICE_GRAD_COMPRESS", "")
    monkeypatch.setenv("BIGDL_TPU_SLICE_OUTER", "")
    opt = _trainer(mesh, k=k, zero1=zero1)
    p, _ = opt.optimize()
    assert opt._dcn_state is None          # machinery never armed
    _assert_same(p_ref, p, msg="params")
    _assert_same(ref.slots, opt.slots, msg="slots")
    np.testing.assert_array_equal(np.asarray(ref._step_rng),
                                  np.asarray(opt._step_rng))
    assert ref.state["loss"] == opt.state["loss"]


# ------------------------------------------------------ window semantics
def test_exchange_matches_per_slice_accumulate_oracle(monkeypatch):
    """T=2 SGD vs a hand-rolled oracle: per-slice mean grads on the
    batch halves, accumulated 2 steps, one update with the cross-slice
    window mean."""
    monkeypatch.setenv("BIGDL_TPU_SLICE_EXCHANGE_EVERY", "2")
    x, y = _data()
    opt = _trainer(_two_tier(), method=SGD(0.1), end=4, zero1=False)
    p_got, _ = opt.optimize()

    model = _mlp()
    params, ms = model.init(
        jax.random.fold_in(jax.random.PRNGKey(5), 0xBD1))
    crit = nn.ClassNLLCriterion()
    step_rng = jax.random.fold_in(jax.random.PRNGKey(5), 0x57E9)

    def grad_of(p, xb, yb, rng):
        def lf(pp):
            out, _ = model.apply(pp, ms, xb, training=True, rng=rng)
            return crit.forward(out, yb)
        return jax.grad(lf)(p)

    acc = [jax.tree.map(jnp.zeros_like, params) for _ in range(2)]
    for i in range(4):
        xb, yb = x[i * 16:(i + 1) * 16], y[i * 16:(i + 1) * 16]
        rng = jax.random.fold_in(step_rng, i)
        for s in range(2):
            g = grad_of(params, xb[s * 8:(s + 1) * 8],
                        yb[s * 8:(s + 1) * 8],
                        jax.random.fold_in(rng, s))
            acc[s] = jax.tree.map(jnp.add, acc[s], g)
        if (i + 1) % 2 == 0:
            mean = jax.tree.map(lambda a, b: (a + b) / 2.0 / 2.0,
                                acc[0], acc[1])
            params = jax.tree.map(lambda p_, g_: p_ - 0.1 * g_,
                                  params, mean)
            acc = [jax.tree.map(jnp.zeros_like, params)
                   for _ in range(2)]
    _assert_same(p_got, params, exact=False, msg="oracle")


def test_no_update_before_boundary_t_gt_k(monkeypatch):
    """T=8 with K=4: the accumulator spans two jitted calls; params and
    slots must not move until step 8's exchange."""
    monkeypatch.setenv("BIGDL_TPU_SLICE_EXCHANGE_EVERY", "8")
    mesh = _two_tier()
    opt4 = _trainer(mesh, k=4, end=4)
    p4, _ = opt4.optimize()
    model = _mlp()
    p_init, _ = model.init(
        jax.random.fold_in(jax.random.PRNGKey(5), 0xBD1))
    _assert_same(p4, p_init, msg="pre-boundary params")
    # slots untouched too (Adam moments still zero)
    for leaf in _leaves(opt4.slots):
        assert not np.any(leaf)
    observe.registry().reset()
    opt8 = _trainer(mesh, k=4, end=8)
    p8, _ = opt8.optimize()
    moved = any(not np.array_equal(a, b)
                for a, b in zip(_leaves(p8), _leaves(p_init)))
    assert moved                           # boundary update happened
    # the flushed telemetry counted exactly one exchange, 7 skips
    snap = observe.registry().snapshot()
    assert snap["counters"]["exchange/count"] == 1
    assert snap["counters"]["exchange/skipped_steps"] == 7
    assert snap["counters"]["exchange/wire_bytes"] > 0


# ------------------------------------------------ compression primitives
@pytest.mark.parametrize("compress", ["", "bfloat16", "int8"])
def test_exchange_primitive_error_feedback_exact(compress):
    """dequant(acc_s) = acc_s - residual_s, and the returned mean is the
    cross-slice mean of the dequantized accumulators — error feedback
    reconstructs the accumulator exactly at the primitive level."""
    mesh = _two_tier()
    r = np.random.RandomState(3)
    acc = {"w": jnp.asarray(r.randn(2, 8, 4).astype(np.float32)),
           "b": jnp.asarray(r.randn(2, 5).astype(np.float32) * 1e-3)}

    @jax.jit
    def run(a):
        return cross_slice_accumulated_exchange(a, mesh,
                                                compress=compress)

    mean, resid, norm = run(acc)
    mean, resid = jax.device_get(mean), jax.device_get(resid)
    for key in acc:
        deq = np.asarray(acc[key]) - resid[key]        # per-slice dequant
        np.testing.assert_allclose(mean[key], deq.mean(0), atol=1e-6,
                                   rtol=1e-6, err_msg=key)
    if compress == "":
        for key in resid:
            assert not np.any(resid[key])
        assert float(norm) == 0.0
    else:
        assert float(norm) > 0.0
        if compress == "bfloat16":
            got = np.asarray(acc["w"]) - resid["w"]
            want = np.asarray(acc["w"]).astype(jnp.bfloat16).astype(
                np.float32)
            np.testing.assert_array_equal(got, want)


def test_int8_ef_training_tracks_uncompressed(monkeypatch):
    """Error feedback keeps int8-compressed training close to the exact
    exchange at equal step count, and it is the feedback that does it.

    Under SGD, because that is where the promise holds on any host: what
    the quantiser drops re-enters the next window, so the updates applied
    so far differ from the exact ones by one window's residual, however
    many windows went by. (Under Adam a quantised gradient near zero
    moves its weight by a whole `lr` per exchange whichever way it
    rounds: 3 of 32 weights came out 0.023 apart on some hosts.) The
    control drops the residual and walks away from the exact run."""
    from bigdl_tpu.parallel import mesh as mesh_mod

    def train(compress):
        monkeypatch.setenv("BIGDL_TPU_SLICE_EXCHANGE_EVERY", "4")
        monkeypatch.setenv("BIGDL_TPU_SLICE_GRAD_COMPRESS", compress)
        opt = _trainer(_two_tier(), method=SGD(0.5), end=32)
        params, _ = opt.optimize()
        return _leaves(params), opt.state["loss"]

    def gap(a, b):
        return max(float(np.abs(x - y).max()) for x, y in zip(a, b))

    p_exact, loss_exact = train("")
    p_comp, loss_comp = train("int8")

    exchange = mesh_mod.cross_slice_accumulated_exchange

    def without_feedback(acc, mesh, **kw):
        mean, resid, rnorm = exchange(acc, mesh, **kw)
        return mean, jax.tree.map(jnp.zeros_like, resid), rnorm

    monkeypatch.setattr(mesh_mod, "cross_slice_accumulated_exchange",
                        without_feedback)
    p_open, _ = train("int8")

    # measured in PR 24: 1.7e-4 with the feedback, 7.5e-4 without
    assert abs(loss_exact - loss_comp) < 5e-3
    assert gap(p_exact, p_comp) < 4e-4
    assert gap(p_exact, p_open) > 4e-4


def test_wire_bytes_accounting():
    params = {"w": np.zeros((100, 10), np.float32),
              "b": np.zeros((10,), np.float32)}
    raw = dcn.wire_bytes_per_exchange(params, "")
    bf16 = dcn.wire_bytes_per_exchange(params, "bfloat16")
    int8 = dcn.wire_bytes_per_exchange(params, "int8")
    assert raw == 4 * 1010
    assert bf16 == 2 * 1010
    # int8: 1 byte/elem padded to 256 blocks + 4B scale per block
    assert int8 < bf16 < raw


# ----------------------------------------------------- outer optimizer
def test_nesterov_outer_differs_and_trains(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_SLICE_EXCHANGE_EVERY", "2")
    mesh = _two_tier()
    plain = _trainer(mesh, end=8)
    p_plain, _ = plain.optimize()
    monkeypatch.setenv("BIGDL_TPU_SLICE_OUTER", "nesterov")
    nest = _trainer(mesh, end=8)
    p_nest, _ = nest.optimize()
    assert np.isfinite(nest.state["loss"])
    assert "m" in jax.device_get(nest._dcn_state)["outer"]
    diff = any(not np.array_equal(a, b)
               for a, b in zip(_leaves(p_plain), _leaves(p_nest)))
    assert diff


# -------------------------------------------------- resume / failover
def test_mid_window_crash_resume_exact(monkeypatch, tmp_path):
    """Snapshot at step 6 inside a T=4 window (pending=2), crash at 8,
    resume, finish — bit-identical params AND accumulator vs control
    (int8 on, so the residual round-trips too)."""
    monkeypatch.setenv("BIGDL_TPU_SLICE_EXCHANGE_EVERY", "4")
    monkeypatch.setenv("BIGDL_TPU_SLICE_GRAD_COMPRESS", "int8")
    mesh = _two_tier()
    ctrl = _trainer(mesh, k=2, end=10, ckpt_dir=tmp_path / "c",
                    ckpt_every=6)
    p_ctrl, _ = ctrl.optimize()
    faults.configure("step:8")
    crash = _trainer(mesh, k=2, end=10, ckpt_dir=tmp_path / "x",
                     ckpt_every=6)
    p_crash, _ = crash.optimize_with_retry()
    faults.configure("")
    _assert_same(p_ctrl, p_crash, msg="params")
    _assert_same(ctrl.slots, crash.slots, msg="slots")
    _assert_same(jax.device_get(ctrl._dcn_state)["acc"],
                 jax.device_get(crash._dcn_state)["acc"], msg="acc")


def test_slice_loss_mid_window_drops_and_counts(monkeypatch):
    """Losing slice 1 inside a T=4 window keeps the survivor's
    accumulator, drops the lost contribution (counted), and training
    finishes within the run; grow-back restores a fresh zero row."""
    monkeypatch.setenv("BIGDL_TPU_SLICE_EXCHANGE_EVERY", "4")
    observe.registry().reset()
    faults.configure("slice:1@step:5,grow@step:9")
    opt = _trainer(_two_tier(), k=1, end=12)
    p, _ = opt.optimize()
    faults.configure("")
    assert opt.state["neval"] == 12
    assert np.isfinite(opt.state["loss"])
    snap = observe.registry().snapshot()
    assert snap["counters"]["exchange/dropped_contributions"] == 1
    assert snap["gauges"]["exchange/last_dropped_norm"] > 0
    assert snap["counters"]["failover/slice_losses"] == 1
    assert snap["counters"]["failover/grow_backs"] == 1
    # grown back: accumulator carries 2 rows again
    assert _leaves(opt._dcn_state["acc"])[0].shape[0] == 2


def test_remap_accumulator_rows_unit():
    ex = {"acc": {"w": np.arange(12, dtype=np.float32).reshape(3, 4)},
          "outer": {}, "residual_norm": np.float32(0)}
    out = remap_accumulator_rows(ex, [0, 1, 2], [0, 2])
    np.testing.assert_array_equal(out["acc"]["w"],
                                  ex["acc"]["w"][[0, 2]])
    back = remap_accumulator_rows(out, [0, 2], [0, 1, 2])
    np.testing.assert_array_equal(back["acc"]["w"][0], ex["acc"]["w"][0])
    assert not np.any(back["acc"]["w"][1])            # fresh window
    np.testing.assert_array_equal(back["acc"]["w"][2], ex["acc"]["w"][2])


# --------------------------------------------------------- telemetry
def test_statusz_exchange_section_and_fleet_row(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_SLICE_EXCHANGE_EVERY", "4")
    observe.registry().reset()
    opt = _trainer(_two_tier(), k=2, end=10)
    opt.optimize()
    from bigdl_tpu.observe.statusz import status_payload
    pl = status_payload()
    ex = pl["exchange"]
    assert ex["window"] == 4
    assert ex["pending_steps"] == 10 % 4
    assert ex["count"] == 2 and ex["skipped_steps"] == 8
    assert ex["wire_bytes"] > 0
    assert ex["loss_spread"] is not None and ex["loss_spread"] >= 0
    # the fleet plane mirrors the window position per peer
    from bigdl_tpu.observe import fleet as obs_fleet
    agg = obs_fleet.FleetAggregator(
        ["h:1"], poll_s=1.0, start_thread=False,
        fetch=lambda addr, path, timeout: {**pl, "varz": {
            "counters": {}, "gauges": {}, "histograms": {}}})
    agg.poll_once()
    row = agg.fleet_payload()["peers"][0]
    assert row["exchange_pending"] == 10 % 4
    assert row["slice_loss_spread"] == ex["loss_spread"]
    agg.close()


def test_knobs_registered():
    from bigdl_tpu.utils import config
    knobs = config.knobs()
    for name in ("SLICE_EXCHANGE_EVERY", "SLICE_GRAD_COMPRESS",
                 "SLICE_OUTER"):
        assert name in knobs and knobs[name].doc
    assert config.get("SLICE_EXCHANGE_EVERY") >= 1
