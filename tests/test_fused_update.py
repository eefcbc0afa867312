"""Fused optimizer-update kernel + shape-keyed autotuner tests
(kernels/fused_update.py, kernels/autotune.py).

Acceptance contracts (ISSUE 7):
  * interpret-mode equivalence vs the `OptimMethod.update` oracle for
    Adam / AdamW / SGD-momentum — params, slots, and lr-schedule
    threading — within the mxu_ref envelope (the fp32 elementwise math
    is in fact bitwise);
  * a distri ZeRO-1 run with BIGDL_TPU_FUSED_UPDATE=1 allclose to the
    unfused run; BIT-identical training with the flag off;
  * the autotune table survives concurrent writers (atomic publish, no
    torn reads) and warm-starts a fresh process with zero searches;
  * autotune/hits|misses|search_seconds ride the observe registry with
    no new per-step host syncs.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu import observe
from bigdl_tpu.dataset import ArrayDataSet
from bigdl_tpu.kernels import autotune, fused_update as fu
from bigdl_tpu.optim.local import Optimizer
from bigdl_tpu.optim.method import SGD, Adam, AdamW, RMSprop
from bigdl_tpu.optim.schedule import Default
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.parallel import DistriOptimizer, create_mesh


@pytest.fixture
def clean_autotune(monkeypatch):
    """Detached autotuner + fresh metrics before/after each test."""
    autotune.detach()
    from bigdl_tpu.observe import metrics as obs_metrics
    obs_metrics.registry().reset()
    yield
    autotune.detach()
    obs_metrics.registry().reset()


def _tree(seed=0):
    r = np.random.RandomState(seed)
    params = {"w1": jnp.asarray(r.randn(33, 7), jnp.float32),
              "blk": {"w2": jnp.asarray(r.randn(129), jnp.float32),
                      "b": jnp.asarray(r.randn(1, 5), jnp.float32)}}
    grads = jax.tree.map(
        lambda p: jnp.asarray(r.randn(*p.shape), jnp.float32), params)
    return params, grads


METHODS = [
    Adam(1e-3, weight_decay=0.01),
    AdamW(1e-3, weight_decay=0.05),
    SGD(0.1, momentum=0.9),
    SGD(0.1, momentum=0.9, nesterov=True),
    SGD(0.1, momentum=0.5, dampening=0.1, weight_decay=0.02),
    SGD(0.1),                            # stateless
]


@pytest.mark.parametrize("method", METHODS,
                         ids=lambda m: f"{type(m).__name__}-mu"
                         f"{getattr(m, 'momentum', '')}")
@pytest.mark.parametrize("layout", ["flat", "leaf"])
def test_fused_update_matches_oracle_bitwise(method, layout):
    """XLA-engine fused update == method.update EXACTLY (same
    elementwise expressions; flattening does not change per-element
    math), for several steps so slot threading and Adam bias
    correction are exercised."""
    params, grads = _tree()
    slots = method.init_slots(params)
    upd = fu.make_update_fn(method, layout=layout)
    assert upd is not None
    p_a, s_a = params, slots
    p_b, s_b = params, slots
    for step in range(3):
        p_a, s_a = method.update(p_a, grads, s_a, jnp.float32(1e-2),
                                 jnp.int32(step))
        p_b, s_b = upd(p_b, grads, s_b, jnp.float32(1e-2),
                       jnp.int32(step))
    for a, b in zip(jax.tree.leaves(p_a), jax.tree.leaves(p_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_a), jax.tree.leaves(s_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("method", METHODS[:4],
                         ids=["adam", "adamw", "sgd-mom", "sgd-nesterov"])
def test_fused_update_pallas_interpret_matches_oracle(method):
    """The Pallas engine (interpret mode, forced on CPU) against the
    oracle — the real-kernel numerics contract, held to a bound far
    inside the mxu_ref envelope (this is fp32 elementwise math, no
    matmul truncation in play)."""
    params, grads = _tree(1)
    slots = method.init_slots(params)
    upd = fu.make_update_fn(method, layout="flat", use_pallas=True,
                            interpret=True, block_rows=8)
    p_a, s_a = method.update(params, grads, slots, jnp.float32(5e-3),
                             jnp.int32(7))
    p_b, s_b = upd(params, grads, slots, jnp.float32(5e-3), jnp.int32(7))
    for a, b in zip(jax.tree.leaves(p_a), jax.tree.leaves(p_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=2e-7)
    for a, b in zip(jax.tree.leaves(s_a), jax.tree.leaves(s_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=2e-7)


def test_fused_update_jits_and_threads_step(clean_autotune):
    """Under jit with a TRACED step number the Adam bias correction must
    track the step — frozen-at-t=0 correction mis-scales every update."""
    method = Adam(1e-3)
    params, grads = _tree(2)
    slots = method.init_slots(params)
    upd = jax.jit(fu.make_update_fn(method, layout="flat"))
    oracle = jax.jit(method.update)      # jit both: same compiled pow/rsqrt
    for step in (0, 5, 50):
        p_o, s_o = oracle(params, grads, slots, jnp.float32(1e-2),
                          jnp.int32(step))
        p_f, s_f = upd(params, grads, slots, jnp.float32(1e-2),
                       jnp.int32(step))
        for a, b in zip(jax.tree.leaves(p_o), jax.tree.leaves(p_f)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)


def test_unsupported_method_returns_none():
    assert fu.make_update_fn(RMSprop(1e-3)) is None

    class MyAdam(Adam):                  # user subclass overriding update
        def update(self, params, grads, slots, lr, step):
            return params, slots

    assert fu.make_update_fn(MyAdam(1e-3)) is None
    assert fu.supports(Adam(1e-3))


# ------------------------------------------------------------ trainer wiring
def _train(cls, fused, monkeypatch, *, method=None, k=4, schedule=None,
           **kw):
    monkeypatch.setenv("BIGDL_TPU_FUSED_UPDATE", "1" if fused else "0")
    r = np.random.RandomState(0)
    x = r.randn(256, 16).astype(np.float32)
    y = r.randint(0, 2, 256).astype(np.int32)
    model = nn.Sequential(nn.Linear(16, 8), nn.ReLU(), nn.Linear(8, 2),
                          nn.LogSoftMax())
    ds = ArrayDataSet(x, y, 32, drop_last=True, shuffle=False)
    meth = method or Adam(1e-2, learning_rate_schedule=schedule)
    opt = cls(model, ds, nn.ClassNLLCriterion(), meth, seed=0,
              steps_per_call=k, **kw)
    opt.set_end_when(Trigger.max_iteration(16))
    opt.optimize()
    return opt.params, opt.slots


@pytest.mark.parametrize("k", [1, 4])
def test_local_trainer_fused_flag_allclose(k, monkeypatch):
    p0, s0 = _train(Optimizer, False, monkeypatch, k=k)
    p1, s1 = _train(Optimizer, True, monkeypatch, k=k)
    for a, b in zip(jax.tree.leaves((p0, s0)), jax.tree.leaves((p1, s1))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_local_trainer_fused_with_lr_schedule(monkeypatch):
    """Host-side LR schedule threading: per-step lrs differ across the
    fused K-stride; the fused kernel must consume each step's lr."""
    sched = Default(lr_decay=0.05)
    p0, s0 = _train(Optimizer, False, monkeypatch, schedule=sched)
    p1, s1 = _train(Optimizer, True, monkeypatch, schedule=sched)
    for a, b in zip(jax.tree.leaves((p0, s0)), jax.tree.leaves((p1, s1))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_flag_off_is_bit_identical_to_oracle_loop(monkeypatch):
    """BIGDL_TPU_FUSED_UPDATE off MUST be today's tree-map path bit for
    bit: two flag-off runs agree exactly, and so does a run with the
    flag never set at all (the env-default path)."""
    monkeypatch.delenv("BIGDL_TPU_FUSED_UPDATE", raising=False)
    p_default, s_default = _train(Optimizer, False, monkeypatch)
    monkeypatch.delenv("BIGDL_TPU_FUSED_UPDATE", raising=False)
    r = np.random.RandomState(0)
    x = r.randn(256, 16).astype(np.float32)
    y = r.randint(0, 2, 256).astype(np.int32)
    model = nn.Sequential(nn.Linear(16, 8), nn.ReLU(), nn.Linear(8, 2),
                          nn.LogSoftMax())
    ds = ArrayDataSet(x, y, 32, drop_last=True, shuffle=False)
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), Adam(1e-2), seed=0,
                    steps_per_call=4)
    opt.set_end_when(Trigger.max_iteration(16))
    opt.optimize()
    for a, b in zip(jax.tree.leaves((p_default, s_default)),
                    jax.tree.leaves((opt.params, opt.slots))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("zero1", [True, False], ids=["zero1", "replslots"])
def test_distri_fused_flag_allclose(zero1, monkeypatch):
    """The ZeRO-1 sharded-slot path (leaf layout) and the replicated
    path (flat layout) both train allclose to the unfused oracle on the
    8-virtual-device mesh."""
    mesh = create_mesh(drop_trivial_axes=True)
    p0, s0 = _train(DistriOptimizer, False, monkeypatch, mesh=mesh,
                    zero1=zero1)
    p1, s1 = _train(DistriOptimizer, True, monkeypatch, mesh=mesh,
                    zero1=zero1)
    for a, b in zip(jax.tree.leaves((p0, s0)), jax.tree.leaves((p1, s1))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_unsupported_method_falls_back_in_trainer(monkeypatch, caplog):
    """Flag on + RMSprop: trains through the tree-map path (bitwise to
    flag-off) and warns once instead of failing."""
    import logging
    with caplog.at_level(logging.WARNING, logger="bigdl_tpu"):
        p1, s1 = _train(Optimizer, True, monkeypatch,
                        method=RMSprop(1e-3))
    p0, s0 = _train(Optimizer, False, monkeypatch, method=RMSprop(1e-3))
    for a, b in zip(jax.tree.leaves((p0, s0)), jax.tree.leaves((p1, s1))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert any("no fused kernel" in r.message for r in caplog.records)


def test_fused_update_no_extra_host_syncs(monkeypatch):
    """The fused-update path adds ZERO host syncs to the train loop —
    lookups/counters happen at trace time only (the test_observe.py
    device_get-counting probe)."""
    counts = {}
    for fused in (False, True):
        monkeypatch.setenv("BIGDL_TPU_FUSED_UPDATE",
                           "1" if fused else "0")
        r = np.random.RandomState(0)
        x = r.randn(128, 16).astype(np.float32)
        y = r.randint(0, 2, 128).astype(np.int32)
        model = nn.Sequential(nn.Linear(16, 2), nn.LogSoftMax())
        ds = ArrayDataSet(x, y, 16, drop_last=True, shuffle=False)
        opt = Optimizer(model, ds, nn.ClassNLLCriterion(), Adam(1e-2),
                        seed=0, steps_per_call=4)
        opt._log_every = 4
        opt.set_end_when(Trigger.max_iteration(8))
        real_get = jax.device_get
        n = {"v": 0}

        def counting_get(v):
            n["v"] += 1
            return real_get(v)
        monkeypatch.setattr(jax, "device_get", counting_get)
        opt.optimize()
        monkeypatch.setattr(jax, "device_get", real_get)
        counts[fused] = n["v"]
    assert counts[True] == counts[False]


# ----------------------------------------------------------------- autotune
def test_autotune_off_returns_defaults(clean_autotune, monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_AUTOTUNE", raising=False)
    cfg = autotune.lookup("flash_attention", {"tq": 64, "tk": 64},
                          {"block_q": 128, "block_k": 128})
    assert cfg == {"block_q": 128, "block_k": 128}
    snap = observe.registry().snapshot()
    assert not any("autotune" in k for k in snap["counters"])


def test_autotune_miss_search_hit_counters(clean_autotune, monkeypatch,
                                           tmp_path):
    monkeypatch.setenv("BIGDL_TPU_AUTOTUNE", "1")
    monkeypatch.setenv("BIGDL_TPU_AUTOTUNE_CACHE", str(tmp_path / "at"))
    shape = {"kind": "adam", "n": 2048, "dtype": "float32"}
    cfg1 = autotune.lookup("fused_update", shape, {"block_rows": 512})
    assert autotune.process_search_count() == 1
    cfg2 = autotune.lookup("fused_update", shape, {"block_rows": 512})
    assert cfg2 == cfg1
    assert autotune.process_search_count() == 1     # hit, no re-search
    snap = observe.registry().snapshot()
    assert snap["counters"]["autotune/misses"] == 1
    assert snap["counters"]["autotune/hits"] == 1
    assert snap["counters"]["autotune/search_seconds"] > 0
    # the search span rode the phase histogram (flush-cadence metrics)
    assert any(k.startswith("phase/autotune/search/")
               for k in snap["histograms"])
    # committed entry on disk, atomic name discipline
    files = [f for f in os.listdir(tmp_path / "at")
             if f.startswith("tune_") and f.endswith(".json")]
    assert len(files) == 1
    rec = json.load(open(tmp_path / "at" / files[0]))
    assert rec["kernel"] == "fused_update" and "block_rows" in rec["config"]


def test_autotune_fresh_process_warm_start_zero_searches(
        clean_autotune, monkeypatch, tmp_path):
    """The fleet contract: a second process on the same table resolves
    every tuned shape with ZERO searches (100% warm-start hit rate)."""
    root = str(tmp_path / "at")
    monkeypatch.setenv("BIGDL_TPU_AUTOTUNE", "1")
    monkeypatch.setenv("BIGDL_TPU_AUTOTUNE_CACHE", root)
    autotune.tune("fused_update", {"kind": "adam", "n": 1024,
                                   "dtype": "float32"})
    autotune.tune("int8_matmul", {"m": 32, "k": 64, "n": 32})
    autotune.sync()
    child = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "from bigdl_tpu.kernels import autotune\n"
        "a = autotune.lookup('fused_update', {'kind': 'adam', 'n': 1024,"
        " 'dtype': 'float32'}, {'block_rows': 512})\n"
        "b = autotune.lookup('int8_matmul', {'m': 32, 'k': 64, 'n': 32},"
        " autotune._DEFAULTS['int8_matmul'])\n"
        "print('SEARCHES', autotune.process_search_count())\n")
    env = {**os.environ, "XLA_FLAGS": "", "BIGDL_TPU_AUTOTUNE": "1",
           "BIGDL_TPU_AUTOTUNE_CACHE": root}
    r = subprocess.run([sys.executable, "-c", child], env=env,
                       capture_output=True, text=True, timeout=100)
    assert r.returncode == 0, r.stderr
    assert "SEARCHES 0" in r.stdout


def test_autotune_concurrent_writers_no_torn_reads(clean_autotune,
                                                   tmp_path):
    """Writers hammering one key with fat records while readers parse
    the committed file in a loop: every read is a complete JSON doc
    (atomic os.replace publish) and the table stays loadable."""
    root = str(tmp_path / "at")
    autotune._attach(root)
    key = autotune.canonical_key("fused_update", {"n": 7})
    name = autotune._entry_name(key)
    stop = threading.Event()
    errors = []

    def writer(wid):
        i = 0
        while not stop.is_set():
            rec = {"key": key, "kernel": "fused_update",
                   "shape": {"n": 7}, "config": {"block_rows": 8 * wid},
                   "pad": "x" * 20000, "i": i}
            autotune._record(key, rec)
            i += 1

    def reader():
        path = os.path.join(root, name)
        while not stop.is_set():
            if not os.path.exists(path):
                continue
            try:
                with open(path) as fh:
                    rec = json.load(fh)
                assert rec["key"] == key and len(rec["pad"]) == 20000
            except (ValueError, AssertionError) as e:   # torn read
                errors.append(repr(e))
                stop.set()

    threads = ([threading.Thread(target=writer, args=(w,))
                for w in (1, 2)] + [threading.Thread(target=reader)
                                    for _ in range(2)])
    for t in threads:
        t.start()
    import time
    time.sleep(1.5)
    stop.set()
    for t in threads:
        t.join(10)
    assert not errors, errors
    assert autotune._load(root) >= 1     # table still loads cleanly


def test_autotune_dead_staging_swept_and_adopted(clean_autotune, tmp_path):
    root = str(tmp_path / "at")
    os.makedirs(root)
    dead = os.path.join(root, f"{autotune._STAGING_PREFIX}0-999999999")
    os.makedirs(dead)
    key = autotune.canonical_key("int8_matmul", {"m": 8})
    rec = {"key": key, "kernel": "int8_matmul", "shape": {"m": 8},
           "config": {"block_m": 32}}
    with open(os.path.join(dead, autotune._entry_name(key)), "w") as fh:
        json.dump(rec, fh)
    autotune._attach(root)
    assert not os.path.isdir(dead)                   # swept
    assert autotune._state["table"][key]["config"] == {"block_m": 32}


def test_kernels_cli_tune_stats_clear(clean_autotune, tmp_path, capsys,
                                      monkeypatch):
    from bigdl_tpu.kernels.__main__ import main
    root = str(tmp_path / "at")
    monkeypatch.setenv("BIGDL_TPU_AUTOTUNE_CACHE", root)
    # record one entry cheaply instead of sweeping the full smoke set
    autotune._attach(root)
    autotune.tune("int8_matmul", {"m": 16, "k": 32, "n": 16})
    autotune.sync()
    assert main(["stats", root]) == 0
    out = capsys.readouterr().out
    assert "autotune root:" in out and "int8_matmul" in out
    assert main(["stats", root, "--json"]) == 0
    s = json.loads(capsys.readouterr().out)
    assert s["entries"] == 1 and s["kernels"]["int8_matmul"] == 1
    assert main(["clear", root]) == 0
    assert "cleared 1" in capsys.readouterr().out
    assert autotune.stats(root)["entries"] == 0


@pytest.mark.slow
def test_kernels_cli_full_smoke_sweep(clean_autotune, tmp_path, capsys,
                                      monkeypatch):
    """The heavy offline sweep: every kernel of the 'smoke' shape set
    searched end-to-end through the CLI (interpret-mode Pallas on CPU)."""
    from bigdl_tpu.kernels.__main__ import main
    root = str(tmp_path / "at")
    monkeypatch.setenv("BIGDL_TPU_AUTOTUNE_CACHE", root)
    assert main(["tune", "smoke", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert len(doc["records"]) == len(autotune.SHAPE_SETS["smoke"])
    assert all(r["candidates_tried"] >= 1 for r in doc["records"])
    assert autotune.stats(root)["entries"] == len(doc["records"])


def test_flash_attention_consults_autotuned_blocks(clean_autotune,
                                                   monkeypatch, tmp_path):
    """A pre-seeded table entry steers the call site's block choice (and
    the tuned kernel still matches dense numerics)."""
    from bigdl_tpu.kernels.flash_attention import flash_attention
    from bigdl_tpu.nn.attention import dot_product_attention
    monkeypatch.setenv("BIGDL_TPU_AUTOTUNE", "1")
    monkeypatch.setenv("BIGDL_TPU_AUTOTUNE_CACHE", str(tmp_path / "at"))
    autotune._attach(str(tmp_path / "at"))
    shape = {"b": 2, "h": 2, "tq": 64, "tk": 64, "d": 32, "causal": 0,
             "dtype": "float32", "device": autotune.device_signature()}
    key = autotune.canonical_key("flash_attention", shape)
    autotune._record(key, {"key": key, "kernel": "flash_attention",
                           "shape": shape,
                           "config": {"block_q": 16, "block_k": 16}})
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(2, 2, 64, 32), jnp.float32)
    out = flash_attention(q, q, q, None, None, False, None, True)
    ref = dot_product_attention(q, q, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert autotune.process_search_count() == 0      # hit, no search
    snap = observe.registry().snapshot()
    assert snap["counters"]["autotune/hits"] == 1
