"""Compile-latency subsystem (bigdl_tpu/compilecache/ —
docs/compile_cache.md): where the persistent cache lives (the
environment's JAX_COMPILATION_CACHE_DIR, else the fixed
<checkout>/.jax_cache) + CLI, AOT precompile() on both trainers,
single-variant shape bucketing (padded valid-mask tails, epoch lengths
% K in {0, 1, K-1}), and the retrace-hygiene contract that resume/retry
reuses built step programs (compile count stays flat across a
crash-at-step-7 resume)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu import compilecache, observe
from bigdl_tpu.dataset import ArrayDataSet
from bigdl_tpu.optim.local import Optimizer
from bigdl_tpu.optim.method import SGD, Adam
from bigdl_tpu.optim.metrics import Top1Accuracy
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.resilience import faults

R = np.random.RandomState(0)
X = R.randn(128, 6).astype(np.float32)
Y = (X[:, 0] > 0).astype(np.int32)
ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIN_SECS = "jax_persistent_cache_min_compile_time_secs"


@pytest.fixture
def clean_cache(monkeypatch):
    """Detach any process-wide cache state before AND after each test;
    inside, even the tiniest program is persisted."""
    compilecache.disable()
    faults.configure("")
    monkeypatch.delenv(ENV, raising=False)
    min_secs = getattr(jax.config, MIN_SECS)
    jax.config.update(MIN_SECS, 0.0)
    yield
    compilecache.disable()
    jax.config.update(MIN_SECS, min_secs)
    faults.configure("")


@pytest.fixture
def env_cache(tmp_path, monkeypatch, clean_cache):
    """The cache placed from outside, the way a caller places it."""
    root = str(tmp_path / "cc")
    monkeypatch.setenv(ENV, root)
    assert compilecache.enable() == root
    return root


def _model():
    return nn.Sequential(nn.Linear(6, 16), nn.ReLU(),
                         nn.Linear(16, 2), nn.LogSoftMax())


def _opt(n_rows=96, bs=16, K=1, method=None, seed=5, val=False):
    ds = ArrayDataSet(X[:n_rows], Y[:n_rows], bs, drop_last=True,
                      shuffle=False)
    opt = Optimizer(_model(), ds, nn.ClassNLLCriterion(),
                    method or SGD(0.05, momentum=0.9), seed=seed,
                    steps_per_call=K)
    if val:
        opt.set_validation(Trigger.several_iteration(5),
                           ArrayDataSet(X[:n_rows], Y[:n_rows], bs,
                                        shuffle=False),
                           [Top1Accuracy()])
    return opt


def _assert_trees_close(a, b, rtol=2e-6, atol=2e-7):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=rtol, atol=atol)


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _entries(d):
    return sorted(n for n in os.listdir(d) if n.endswith("-cache")) \
        if os.path.isdir(d) else []


# ------------------------------------------------------ cache mechanics
def test_env_dir_is_the_only_dir_ever_set(tmp_path, monkeypatch,
                                          clean_cache):
    """JAX_COMPILATION_CACHE_DIR set: the cache is that directory, every
    entry lands there, and nothing points jax anywhere else."""
    root = str(tmp_path / "cc")
    monkeypatch.setenv(ENV, root)
    before = _entries(os.path.join(CHECKOUT, ".jax_cache"))
    seen = []
    real_update = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            seen.append(value)
        return real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    assert compilecache.cache_dir() == root
    assert compilecache.enable() == root
    assert compilecache.enable() == root    # idempotent: no second update
    assert jax.config.jax_compilation_cache_dir == root
    assert compilecache.enabled()
    jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones((17,)))
    s = compilecache.stats()
    assert s["root"] == root and s["entries"] >= 1
    assert s["entries"] == len(_entries(root))
    assert seen == [root]
    assert _entries(os.path.join(CHECKOUT, ".jax_cache")) == before
    assert os.listdir(tmp_path) == ["cc"]   # no sibling staging dir


def test_unset_env_is_the_fixed_checkout_dir(clean_cache):
    """No environment variable: <checkout>/.jax_cache, a path with no
    process id, temporary name or time in it — and entries land there."""
    want = os.path.join(CHECKOUT, ".jax_cache")
    assert compilecache.cache_dir() == want
    assert compilecache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert str(os.getpid()) not in want and "tmp" not in want.lower()
    before = set(_entries(want))

    def unique_fn_5519(x):
        return (x * 1.75 - 0.5).sum()
    jax.jit(unique_fn_5519)(jnp.arange(33, dtype=jnp.float32))
    new = [n for n in _entries(want) if n not in before]
    for n in new:                           # leave the checkout as found
        os.unlink(os.path.join(want, n))
    assert any("unique_fn_5519" in n for n in new), new


def test_disable_detaches_and_reenable_hits(env_cache):
    """disable() leaves the entries on disk; a later enable() — a
    restarted process, here a fresh jit of the same program — reads
    them back instead of compiling."""
    observe.ensure_started()
    jax.jit(lambda x: x - 3.5)(jnp.ones((11,)))
    n = compilecache.stats(env_cache)["entries"]
    assert n >= 1
    compilecache.disable()
    assert not compilecache.enabled()
    assert jax.config.jax_compilation_cache_dir is None
    assert compilecache.stats(env_cache)["entries"] == n
    assert compilecache.enable() == env_cache
    hits = observe.counter("jit/cache_hits").value
    jax.jit(lambda x: x - 3.5)(jnp.ones((11,)))
    assert observe.counter("jit/cache_hits").value == hits + 1
    assert compilecache.stats(env_cache)["entries"] == n


def test_torn_entry_warns_and_recompiles(env_cache):
    """What the deleted per-process staging guarded against: a reader
    meets a half-written entry. jax refuses it, warns, and compiles —
    a lost hit, never a wrong program."""
    def make():                 # a fresh function object = a fresh jit
        def unique_fn_9142(x):
            return x * 0.25 + 9.0
        return unique_fn_9142
    want = np.asarray(jax.jit(make())(jnp.ones((13,))))
    (name,) = [n for n in _entries(env_cache) if "unique_fn_9142" in n]
    path = os.path.join(env_cache, name)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2])
    with pytest.warns(UserWarning, match="Error reading persistent "
                                         "compilation cache entry"):
        got = np.asarray(jax.jit(make())(jnp.ones((13,))))
    np.testing.assert_array_equal(got, want)


def test_stats_and_clear_cli(env_cache, capsys):
    from bigdl_tpu.compilecache.__main__ import main
    jax.jit(lambda x: x / 7.0)(jnp.ones((5,)))
    compilecache.disable()
    assert main(["stats", env_cache]) == 0
    out = capsys.readouterr().out
    assert "cache dir:" in out and "entries:" in out
    assert main(["stats", "--json"]) == 0   # DIR defaults to the env's
    import json
    s = json.loads(capsys.readouterr().out)
    assert s["root"] == env_cache and s["entries"] >= 1
    assert main(["clear", env_cache]) == 0
    assert "cleared" in capsys.readouterr().out
    assert compilecache.stats(env_cache)["entries"] == 0
    assert [n for n in os.listdir(env_cache)] == []


def test_warm_process_hits_persistent_cache(tmp_path, clean_cache):
    """Two processes given the same directory from outside: jax itself
    reads the variable, the second process deserializes instead of
    compiling (the jit/cache_hit_compiles counter observe keeps), and
    neither writes anywhere else."""
    child = (
        "import os, sys\n"
        "import jax, jax.numpy as jnp\n"
        "from bigdl_tpu import compilecache, observe\n"
        "observe.ensure_started()\n"
        "assert compilecache.enable() == sys.argv[1]\n"
        "assert jax.config.jax_compilation_cache_dir == sys.argv[1]\n"
        "def unique_fn_7731(x):\n"
        "    return (x * 3.25 + 17.0).sum() - 0.125\n"
        "jax.jit(unique_fn_7731)(jnp.arange(4096, dtype=jnp.float32))\n"
        "print('HITS', int(observe.counter('jit/cache_hit_compiles')"
        ".value))\n")
    root = str(tmp_path / "cc")
    before = _entries(os.path.join(CHECKOUT, ".jax_cache"))
    env = {**os.environ, "XLA_FLAGS": "", "JAX_PLATFORMS": "cpu",
           ENV: root, "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", child, root],
                           capture_output=True, text=True, env=env,
                           cwd=CHECKOUT, timeout=50)
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout)
    assert compilecache.stats(root)["programs"].get("jit_unique_fn_7731") == 1
    assert "HITS 0" in outs[0]
    hits = int(outs[1].split("HITS")[1].strip().split()[0])
    assert hits >= 1, outs[1]
    assert _entries(os.path.join(CHECKOUT, ".jax_cache")) == before


# ------------------------------------------------------------ precompile
def test_precompile_unfused_attaches_aot_and_costs(clean_cache):
    opt = _opt(K=1, val=True)
    res = opt.precompile()
    assert "train_step" in res and "eval_step" in res
    assert res["train_step"]["compile_seconds"] > 0
    entry = opt._built_steps[opt._step_key("step")]
    assert entry.aot is not None
    assert observe.gauge("compile/train_step/compile_seconds").value > 0
    opt.set_end_when(Trigger.max_iteration(4))
    params, _ = opt.optimize()             # runs through the AOT program
    assert opt.state["neval"] == 4
    # the AOT executable matches the live inputs: no fallback happened
    assert entry.aot is not None


def test_precompile_matches_plain_run_bit_identical(clean_cache):
    """Training through the AOT executable is the SAME program as the
    jitted path — results bit-identical with and without warmup."""
    o0 = _opt(K=4)
    o0.set_end_when(Trigger.max_iteration(4))
    p0, _ = o0.optimize()
    o1 = _opt(K=4)
    o1.precompile()
    o1.set_end_when(Trigger.max_iteration(4))
    p1, _ = o1.optimize()
    _assert_trees_equal(p0, p1)
    _assert_trees_equal(o0.slots, o1.slots)


def test_precompile_knob_runs_automatically(clean_cache, monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_PRECOMPILE", "1")
    opt = _opt(K=4)
    opt.set_end_when(Trigger.max_iteration(4))
    opt.optimize()
    assert getattr(opt, "_precompiled", False)


def test_single_variant_per_config_including_tail(env_cache):
    """Acceptance: a fused run whose epochs END IN A TAIL (5 batches,
    K=4) compiles exactly ONE train-step program — the padded valid-mask
    super-batch serves full groups and tails alike. The persistent cache
    counts program variants by name."""
    root = env_cache
    opt = _opt(n_rows=80, K=4)             # 5 batches/epoch: 4 + tail(1)
    opt.set_end_when(Trigger.max_epoch(2))
    opt.optimize()
    assert opt.state["neval"] == 10        # tails never dropped
    progs = compilecache.stats(root)["programs"]
    assert progs.get("jit_bigdl_fused_train_step") == 1, progs


def test_precompile_distri_sharded_specs(env_cache):
    """DistriOptimizer precompile: the AOT specs carry mesh shardings
    (TP params, ZeRO-1 slots, data-sharded super-batch), so the
    precompiled executable accepts the live sharded trees — and the run
    still compiles exactly one fused train-step variant."""
    from bigdl_tpu.parallel import DistriOptimizer, create_mesh
    root = env_cache
    mesh = create_mesh(drop_trivial_axes=True)
    ds = ArrayDataSet(X[:80], Y[:80], 16, drop_last=True, shuffle=False)
    opt = DistriOptimizer(_model(), ds, nn.ClassNLLCriterion(),
                          SGD(0.05, momentum=0.9), mesh=mesh, zero1=True,
                          seed=5, steps_per_call=4)
    opt.set_validation(Trigger.every_epoch(),
                       ArrayDataSet(X[:80], Y[:80], 16, shuffle=False),
                       [Top1Accuracy()])
    res = opt.precompile()
    assert "train_step" in res and "eval_step" in res
    opt.set_end_when(Trigger.max_epoch(1))
    opt.optimize()
    assert opt.state["neval"] == 5
    progs = compilecache.stats(root)["programs"]
    assert progs.get("jit_bigdl_fused_train_step") == 1, progs


# ------------------------------------------- valid-mask tail equivalence
# epoch lengths chosen so len % K covers {0, 1, K-1} for K=4 (and the
# K=1 degenerate bucket where every group is "full")
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("n_batches", [8, 5, 7])
def test_tail_epochs_match_unfused_oracle(k, n_batches, clean_cache):
    """Two epochs with tails of len % K in {0, 1, K-1}: params, slots,
    and counters match the unfused per-step oracle — the masked pad
    steps contribute nothing and advance nothing."""
    iters = 2 * n_batches
    oracle = _opt(n_rows=16 * n_batches, K=1)
    oracle.set_end_when(Trigger.max_iteration(iters))
    p_o, _ = oracle.optimize()

    fused = _opt(n_rows=16 * n_batches, K=k)
    fused.set_end_when(Trigger.max_iteration(iters))
    p_f, _ = fused.optimize()
    _assert_trees_close(p_o, p_f)
    _assert_trees_close(oracle.slots, fused.slots)
    assert fused.state["neval"] == oracle.state["neval"] == iters
    assert fused.state["records"] == oracle.state["records"]
    # end_when fires on the epoch's final stride -> mid-epoch stop
    # semantics for BOTH paths (epoch counter agrees, whatever it is)
    assert fused.state["epoch"] == oracle.state["epoch"]


def test_pad_rows_fully_masked_bit_identical(clean_cache, monkeypatch):
    """The mask — not the zero padding — is what isolates pad steps:
    poisoning the pad rows with garbage leaves every output bit
    identical (zero gradient, no lr/neval/rng advance, no counters)."""
    ref = _opt(n_rows=80, K=4)             # tail of 1 every epoch
    ref.set_end_when(Trigger.max_epoch(2))
    p_ref, _ = ref.optimize()

    from bigdl_tpu.dataset import prefetch as pf
    orig = pf.stack_batches

    def poisoned(it, kk):
        for xs, ys, n in orig(it, kk):
            if n < xs.shape[0]:
                xs[n:] = 999.0             # garbage where zeros were
                ys[n:] = 1
            yield xs, ys, n

    monkeypatch.setattr(pf, "stack_batches", poisoned)
    poi = _opt(n_rows=80, K=4)
    poi.set_end_when(Trigger.max_epoch(2))
    p_poi, _ = poi.optimize()
    _assert_trees_equal(p_ref, p_poi)
    _assert_trees_equal(ref.slots, poi.slots)
    assert ref.state == poi.state


def test_tail_trigger_firings_match_unfused(tmp_path, clean_cache):
    """several_iteration(5) with a 5-batch epoch and K=4: the nominal
    firing iteration lands INSIDE the tail stride — it must fire at the
    tail boundary (neval 5), exactly where the unfused run fires, and
    exactly once (no skip, no double-fire)."""
    ck1, ck4 = str(tmp_path / "k1"), str(tmp_path / "k4")
    runs = {}
    for k, ck in ((1, ck1), (4, ck4)):
        opt = _opt(n_rows=80, K=k, val=True)
        opt.set_checkpoint(ck, Trigger.several_iteration(5))
        opt.set_end_when(Trigger.max_iteration(10))
        opt.optimize()
        runs[k] = opt
    assert runs[1]._last_val_neval == runs[4]._last_val_neval == 10
    for ck in (ck1, ck4):
        snaps = sorted(d for d in os.listdir(ck)
                       if d.startswith("snapshot-"))
        assert snaps == ["snapshot-10", "snapshot-5"], (ck, snaps)


def test_distri_tail_matches_local_oracle(clean_cache):
    """DistriOptimizer (ZeRO-1 on) through a 7-batch epoch (K=4 ->
    tail of 3 = K-1): same trajectory as the local unfused oracle."""
    from bigdl_tpu.parallel import DistriOptimizer, create_mesh
    oracle = _opt(n_rows=112, K=1)         # 7 batches/epoch
    oracle.set_end_when(Trigger.max_iteration(14))
    p_o, _ = oracle.optimize()

    mesh = create_mesh(drop_trivial_axes=True)
    ds = ArrayDataSet(X[:112], Y[:112], 16, drop_last=True, shuffle=False)
    opt = DistriOptimizer(_model(), ds, nn.ClassNLLCriterion(),
                          SGD(0.05, momentum=0.9), mesh=mesh, zero1=True,
                          seed=5, steps_per_call=4)
    opt.set_end_when(Trigger.max_iteration(14))
    p_d, _ = opt.optimize()
    _assert_trees_close(p_o, p_d, rtol=2e-5, atol=1e-6)
    assert opt.state["neval"] == 14
    assert opt.state["records"] == oracle.state["records"]


# --------------------------------------------------- retrace hygiene
def test_resume_retry_compile_count_stays_flat(tmp_path, clean_cache):
    """Satellite acceptance: a crash-at-step-7 auto-resume must NOT
    rebuild the jitted step programs — the fused builder runs exactly
    once across both attempts, and the re-entered optimize() performs
    zero fresh XLA compiles (everything it needs was compiled by the
    first attempt and reused from the built-step cache)."""
    observe.ensure_started()
    opt = _opt(n_rows=96, K=4)
    opt.set_checkpoint(str(tmp_path / "ck"), Trigger.several_iteration(4))
    opt.set_end_when(Trigger.max_iteration(12))
    builds = []
    orig_build = opt._build_fused_step
    opt._build_fused_step = lambda: (builds.append(1), orig_build())[1]
    compiles_at_retry = []
    orig_resume = opt.resume

    def spying_resume(path):
        compiles_at_retry.append(observe.counter("jit/compiles").value)
        return orig_resume(path)

    opt.resume = spying_resume
    faults.configure("step:7:crash")
    opt.optimize_with_retry(retries=3, window_s=600)
    assert opt.state["neval"] == 12
    assert builds == [1]                   # built once, reused on resume
    assert len(compiles_at_retry) == 1     # exactly one recovery
    after = observe.counter("jit/compiles").value
    assert after == compiles_at_retry[0], (
        f"resume recompiled {after - compiles_at_retry[0]} programs")


def test_repeat_optimize_reuses_built_steps(clean_cache):
    """A second optimize() on the same trainer (the resume() + continue
    pattern) reuses every built program: no fresh compiles at all."""
    observe.ensure_started()
    opt = _opt(K=4)
    opt.set_end_when(Trigger.max_iteration(4))
    opt.optimize()
    n_built = len(opt._built_steps)
    before = observe.counter("jit/compiles").value
    # 12 is K-boundary-aligned from neval=4 (strides 6, 10, 12 with the
    # 6-batch epochs re-grouping after the mid-epoch stop)
    opt.set_end_when(Trigger.max_iteration(12))
    opt.optimize()
    assert opt.state["neval"] == 12
    assert len(opt._built_steps) == n_built
    assert observe.counter("jit/compiles").value == before


def test_builder_setters_invalidate_built_cache(clean_cache):
    """Setters that change a closure capture must drop the built
    programs (stale captures would silently train with the old
    config)."""
    opt = _opt(K=1)
    opt._get_built("step")
    assert opt._built_steps
    opt.set_gradient_clipping_by_l2_norm(1.0)
    assert not opt._built_steps
    opt._get_built("step")
    opt.set_optim_method(Adam(1e-3))
    assert not opt._built_steps
