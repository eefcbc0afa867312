"""Optim method / schedule / trigger tests (analogue of
test/.../optim/{SGD,Adam,...}Spec.scala — convergence on synthetic problems)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import optim
from bigdl_tpu.core.module import flatten_params


def quadratic_problem(method, steps=150, lr_state=None):
    """Minimize ||x - t||^2 from a fixed start; returns final distance."""
    t = jnp.array([1.0, -2.0, 3.0])
    params = {"x": jnp.zeros(3)}
    slots = method.init_slots(params)

    @jax.jit
    def step(params, slots, lr, i):
        grads = jax.grad(lambda p: jnp.sum(jnp.square(p["x"] - t)))(params)
        return method.update(params, grads, slots, lr, i)

    state = {"neval": 0, "epoch": 0}
    for i in range(steps):
        lr = method.current_lr(state)
        params, slots = step(params, slots, jnp.float32(lr), jnp.int32(i))
        state["neval"] += 1
    return float(jnp.max(jnp.abs(params["x"] - t)))


@pytest.mark.parametrize("method", [
    optim.SGD(0.1),
    optim.SGD(0.05, momentum=0.9),
    optim.SGD(0.05, momentum=0.9, nesterov=True),
    optim.Adam(0.1),
    optim.AdamW(0.1, weight_decay=1e-4),
    optim.Adamax(0.2),
    optim.Adadelta(0.9, epsilon=1e-2),  # default 1e-10 needs ~1e4 steps here
    optim.Adagrad(0.5),
    optim.RMSprop(0.05),
    optim.Ftrl(0.5),
    optim.LarsSGD(0.2, momentum=0.5, trust=0.1),
], ids=lambda m: type(m).__name__ + str(id(m) % 97))
def test_methods_converge(method):
    assert quadratic_problem(method, steps=300) < 0.15


def test_lbfgs_rosenbrock():
    # reference: test/.../optim/LBFGSSpec uses Rosenbrock
    def rosen(x):
        return jnp.sum(100.0 * (x[1:] - x[:-1] ** 2) ** 2 + (1 - x[:-1]) ** 2)

    feval = jax.jit(jax.value_and_grad(rosen))
    lbfgs = optim.LBFGS(max_iter=120, learning_rate=0.5)
    x, losses = lbfgs.step(lambda x: feval(x), jnp.zeros(4))
    assert losses[-1] < losses[0] * 0.01


def test_schedules():
    st = {"neval": 0, "epoch": 0}
    assert optim.Poly(2, 100)(1.0, {"neval": 50}) == pytest.approx(0.25)
    assert optim.Step(10, 0.5)(1.0, {"neval": 25}) == pytest.approx(0.25)
    assert optim.MultiStep([10, 20], 0.1)(1.0, {"neval": 15}) == pytest.approx(0.1)
    assert optim.EpochStep(2, 0.1)(1.0, {"epoch": 4}) == pytest.approx(0.01)
    assert optim.Exponential(10, 0.5, staircase=True)(1.0, {"neval": 25}) == \
        pytest.approx(0.25)
    assert optim.Warmup(0.01)(0.1, {"neval": 10}) == pytest.approx(0.2)
    w = optim.CosineDecay(100, warmup_steps=10)
    assert w(1.0, {"neval": 0}) == pytest.approx(0.1)
    assert w(1.0, {"neval": 100}) == pytest.approx(0.0, abs=1e-6)


def test_sequential_schedule():
    s = optim.SequentialSchedule(10)
    s.add(optim.Warmup(0.1), 5).add(optim.Default(), 100)
    assert s(0.5, {"neval": 3}) == pytest.approx(0.8)
    assert s(0.5, {"neval": 50}) == pytest.approx(0.5)


def test_plateau():
    p = optim.Plateau(factor=0.1, patience=2, mode="min")
    for v in [1.0, 0.9, 0.9, 0.9]:   # no improvement for 2 after 0.9
        p.record(v)
    assert p(1.0, {}) == pytest.approx(0.1)


def test_triggers():
    T = optim.Trigger
    assert T.max_epoch(3)({"epoch": 3})
    assert not T.max_epoch(3)({"epoch": 2})
    assert T.several_iteration(5)({"neval": 10})
    assert not T.several_iteration(5)({"neval": 11})
    assert T.min_loss(0.1)({"loss": 0.05})
    assert T.and_(T.max_epoch(1), T.min_loss(1.0))({"epoch": 1, "loss": 0.5})
    ev = T.every_epoch()
    assert not ev({"epoch": 1, "epoch_finished": False})
    assert ev({"epoch": 1, "epoch_finished": True})
    assert not ev({"epoch": 1, "epoch_finished": True})  # fires once per epoch


def test_validation_methods():
    out = jnp.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]])
    tgt = jnp.array([1, 0, 0])
    top1 = optim.Top1Accuracy().batch(out, tgt)
    assert top1.result == pytest.approx(2 / 3)
    top5 = optim.Top5Accuracy().batch(out, tgt)
    assert top5.result == pytest.approx(1.0)
    r = top1 + optim.Top1Accuracy().batch(out, tgt)
    assert r.result == pytest.approx(2 / 3)
    mae = optim.MAE().batch(jnp.ones(4), jnp.zeros(4))
    assert mae.result == pytest.approx(1.0)


def test_hit_ratio_ndcg():
    scores = jnp.array([[0.1, 0.9, 0.5, 0.2]])
    hr = optim.HitRatio(k=2).batch(scores, jnp.array([2]))
    assert hr.result == pytest.approx(1.0)
    nd = optim.NDCG(k=2).batch(scores, jnp.array([2]))
    assert nd.result == pytest.approx(1 / np.log2(3), rel=1e-4)


def test_clipping():
    grads = {"a": jnp.array([3.0, 4.0])}
    clipped = optim.L2NormClipping(1.0)(grads, grads)
    np.testing.assert_allclose(jnp.linalg.norm(clipped["a"]), 1.0, rtol=1e-5)
    c2 = optim.ConstantClipping(-0.5, 0.5)(grads, grads)
    assert float(jnp.max(c2["a"])) == 0.5


def test_frozen_layer_immovable_with_weight_decay(rng=None):
    """freeze() must win over weight decay (regression for masking order)."""
    import jax
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset.core import ArrayDataSet
    m = optim  # noqa  (keep namespace clear)
    model = nn.Sequential(nn.Linear(4, 4), nn.Linear(4, 2))
    model[0].freeze()
    x = np.random.RandomState(0).randn(64, 4).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.int32)
    ds = ArrayDataSet(x, y, batch_size=32)
    opt = optim.Optimizer(model, ds, __import__("bigdl_tpu.nn", fromlist=["x"]).CrossEntropyCriterion(),
                          optim.SGD(0.1, weight_decay=0.1))
    opt.set_end_when(optim.Trigger.max_epoch(2))
    params, _ = opt.optimize()
    # same rng path the Optimizer uses for initialization
    init_params, _ = model.init(jax.random.fold_in(jax.random.PRNGKey(1), 0xBD1))
    np.testing.assert_allclose(params["0"]["weight"],
                               init_params["0"]["weight"], rtol=1e-6)
    assert not np.allclose(params["1"]["weight"], init_params["1"]["weight"])


def test_mid_epoch_stop_does_not_advance_epoch():
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset.core import ArrayDataSet
    x = np.random.RandomState(0).randn(640, 4).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.int32)
    ds = ArrayDataSet(x, y, batch_size=32)  # 20 batches/epoch
    model = nn.Sequential(nn.Linear(4, 2))
    opt = optim.Optimizer(model, ds, nn.CrossEntropyCriterion(), optim.SGD(0.1))
    opt.set_end_when(optim.Trigger.max_iteration(5))
    opt.optimize()
    assert opt.state["neval"] == 5
    assert opt.state["epoch"] == 0  # partial epoch is not counted


def test_prauc_resets_between_runs():
    m = optim.PrecisionRecallAUC()
    out = jnp.array([0.9, 0.1, 0.8, 0.3])
    tgt = jnp.array([1, 0, 1, 0])
    m.batch(out, tgt)
    auc1 = m.batch(out, tgt).result
    m.reset()
    m.batch(out, tgt)
    assert len(m.scores) == 1


def test_predictor_and_service():
    import jax
    import numpy as np
    from bigdl_tpu.nn import Linear, Sequential, SoftMax
    from bigdl_tpu.optim.predictor import Predictor, PredictionService, Evaluator
    from bigdl_tpu.optim.metrics import Top1Accuracy

    model = Sequential(Linear(4, 3))
    params, state = model.init(jax.random.PRNGKey(0))
    x = np.random.RandomState(0).randn(10, 4).astype(np.float32)

    pred = Predictor(model, params, state, batch_size=4)
    out = pred.predict(x)
    assert out.shape == (10, 3)
    ref, _ = model.apply(params, state, x)
    np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-5, atol=1e-5)
    labels = pred.predict_class(x)
    assert labels.shape == (10,)

    svc = PredictionService(model, params, state, max_batch=8)
    out2 = svc.predict(x)
    np.testing.assert_allclose(out2, out, rtol=1e-5, atol=1e-5)

    y = labels.astype(np.int32)   # evaluate against own predictions => acc 1
    res = Evaluator(model).test(params, state, [(x, y)], [Top1Accuracy()])
    assert res["Top1Accuracy"].result == 1.0


def test_predictor_empty_and_bucket():
    import jax
    import numpy as np
    from bigdl_tpu.nn import Linear, Sequential
    from bigdl_tpu.optim.predictor import Predictor, PredictionService

    model = Sequential(Linear(4, 3))
    params, state = model.init(jax.random.PRNGKey(0))
    pred = Predictor(model, params, state, batch_size=4)
    out = pred.predict(np.zeros((0, 4), np.float32))
    assert out.shape == (0, 3)
    svc = PredictionService(model, params, state, max_batch=100)
    assert svc._bucket(5) == 8
    assert svc._bucket(100) == 100
    assert svc._bucket(200) == 100
    # PR 8 contract: the serving path zero-pads rows into buckets and
    # REJECTS empty requests as a client error (there is no bucket for
    # 0 rows) — only the offline Predictor returns an empty result
    with pytest.raises(ValueError, match="empty request"):
        svc.predict(np.zeros((0, 4), np.float32))
    svc.close()


def test_set_initial_survives_donation_and_retry(tmp_path):
    """set_initial trees must survive the donating step and a pre-snapshot
    retry (fine-tuning must never silently restart from scratch)."""
    import numpy as np
    import jax
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger

    r = np.random.RandomState(0)
    x = r.randn(32, 4).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    model = nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax())
    init_p, init_s = model.init(jax.random.PRNGKey(7))
    # make the supplied trees unmistakable: huge weights that one epoch of
    # lr-0.1 SGD cannot move anywhere near a random re-init (~0.x scale)
    init_p = {"0": {"weight": init_p["0"]["weight"] + 5.0,
                    "bias": init_p["0"]["bias"]}, "1": {}}
    marker = float(np.asarray(init_p["0"]["weight"])[0, 0])

    ds = ArrayDataSet(x, y, 8, drop_last=True)
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), SGD(0.1))
    opt.set_initial(init_p, init_s)
    opt.set_end_when(Trigger.max_epoch(1))
    opt.set_checkpoint(str(tmp_path), Trigger.every_epoch())

    # inject a failure on the FIRST validate call (before any snapshot)
    calls = {"n": 0}
    real = opt._maybe_validate

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected pre-snapshot fault")
        return real(*a, **kw)
    opt._maybe_validate = flaky
    opt.optimize_with_retry(retries=2, window_s=60)
    # caller's trees are intact (not donated away)
    assert float(np.asarray(init_p["0"]["weight"])[0, 0]) == marker
    # the retry restarted from the supplied trees, not a random re-init:
    # weights remain at the "huge" scale of the initial trees
    assert float(np.abs(np.asarray(opt.params["0"]["weight"])).mean()) > 2.0


def test_set_initial_without_state_builds_skeleton():
    import numpy as np
    import jax
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import ArrayDataSet
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger
    r = np.random.RandomState(0)
    x = r.randn(16, 4).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    model = nn.Sequential(nn.Linear(4, 8), nn.BatchNormalization(8),
                          nn.ReLU(), nn.Linear(8, 2), nn.LogSoftMax())
    p, _ = model.init(jax.random.PRNGKey(0))
    opt = Optimizer(model, ArrayDataSet(x, y, 8, drop_last=True),
                    nn.ClassNLLCriterion(), SGD(0.1))
    opt.set_initial(p)               # no model_state: skeleton generated
    opt.set_end_when(Trigger.max_epoch(1))
    params, state = opt.optimize()   # must not KeyError on container state
    assert "1" in state and "running_mean" in state["1"]


def test_optax_method_adapter_matches_optax_and_trains():
    """OptaxMethod: any optax GradientTransformation drives the trainer;
    trajectory matches raw optax step for step, and ZeRO-1 sharding on
    the distributed trainer accepts the optax slot tree."""
    import optax
    import bigdl_tpu.nn as nn
    from bigdl_tpu.core.container import Sequential
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import OptaxMethod

    r = np.random.RandomState(0)
    x = r.randn(64, 6).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)

    def model():
        return Sequential(nn.Linear(6, 16), nn.ReLU(), nn.Linear(16, 2),
                          nn.LogSoftMax())

    m = model()
    params, state = m.init(jax.random.PRNGKey(3))
    crit = nn.ClassNLLCriterion()

    # raw optax trajectory
    tx = optax.adam(1e-2)
    p_ref = params
    opt_state = tx.init(p_ref)

    @jax.jit            # one program, not one per eager op of five steps
    def raw_step(p, opt_state):
        g = jax.grad(lambda p: crit.forward(
            m.apply(p, state, jnp.asarray(x))[0], jnp.asarray(y)))(p)
        upd, opt_state = tx.update(g, opt_state, p)
        return jax.tree.map(lambda a, b: a + b, p, upd), opt_state

    for i in range(5):
        p_ref, opt_state = raw_step(p_ref, opt_state)

    # the adapter inside the trainer (same data, one batch per iter)
    opt = (Optimizer(model(), [(x, y)], crit,
                     OptaxMethod(optax.adam(1e-2), 1e-2), seed=9)
           .set_initial(params, state)
           .set_end_when(optim.Trigger.max_iteration(5)))
    p_got, _ = opt.optimize()
    for a, b in zip(jax.tree.leaves(p_got), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    # distributed: optax slots ride ZeRO-1 without complaint
    from bigdl_tpu.parallel import DistriOptimizer, create_mesh
    mesh = create_mesh(drop_trivial_axes=True)
    do = DistriOptimizer(model(), [(x, y)], crit,
                         OptaxMethod(optax.adamw(1e-2), 1e-2),
                         mesh=mesh, zero1=True, seed=9)
    do.set_end_when(optim.Trigger.max_iteration(2))
    do.optimize()
    assert np.isfinite(do.state["loss"])
