"""Tier-1 static-analysis suite: the tracing-safety AST lint (TPU-LINT001..
007) and the ahead-of-trace graph checker (GRAPH-*), plus the catalog-wide
property test that every registered layer passes Module.check() clean.

This file IS the CI wiring for both prongs (no extra infra): it fails the
fast tier when (a) non-baseline lint violations land anywhere in
bigdl_tpu/, or (b) any layer in tests/layer_catalog.py stops passing the
graph checker at its canonical input shape.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.analysis import rules as lint
from bigdl_tpu.analysis.graphcheck import (GraphCheckError, check_module,
                                           summarize)
from bigdl_tpu.core import init as initializers
from bigdl_tpu.core.module import Module, ParamSpec, StateSpec

from layer_catalog import MODULES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(violations):
    return {v.rule for v in violations}


# =========================================================== lint fixtures
# Every rule: one purpose-built bad snippet caught, one good twin clean.
# The fake path places snippets inside the framework (not test-exempt).

HOT_PATH = "bigdl_tpu/nn/fake_layer.py"

LINT_CASES = {
    "TPU-LINT001": (
        "import math\n"
        "class L:\n"
        "    def forward(self, params, x, **_):\n"
        "        return x * math.sqrt(2.0)\n",
        "import jax.numpy as jnp\n"
        "class L:\n"
        "    def forward(self, params, x, **_):\n"
        "        return x * 2.0 ** 0.5\n",
    ),
    "TPU-LINT002": (
        "class L:\n"
        "    def forward(self, params, x, **_):\n"
        "        return float(x.sum())\n",
        "class L:\n"
        "    def forward(self, params, x, **_):\n"
        "        return float(self.scale) * x\n",
    ),
    "TPU-LINT003": (
        "class L:\n"
        "    def forward(self, params, x, **_):\n"
        "        if x > 0:\n"
        "            return x\n"
        "        return -x\n",
        "class L:\n"
        "    def forward(self, params, x, **_):\n"
        "        if x.ndim > 2:\n"
        "            return x\n"
        "        return -x\n",
    ),
    "TPU-LINT004": (
        "import jax\n"
        "def init_model(model):\n"
        "    return model.init(jax.random.PRNGKey(0))\n",
        "import jax\n"
        "def init_model(model, seed):\n"
        "    return model.init(jax.random.PRNGKey(seed))\n",
    ),
    "TPU-LINT005": (
        "import jax.numpy as jnp\n"
        "ACC_DTYPE = jnp.float64\n",
        "import jax.numpy as jnp\n"
        "ACC_DTYPE = jnp.float32\n",
    ),
    "TPU-LINT006": (
        "class L:\n"
        "    def _apply(self, params, state, x, training=False, rng=None):\n"
        "        self.cache = x\n"
        "        return x, state\n",
        "class L:\n"
        "    def _apply(self, params, state, x, training=False, rng=None):\n"
        "        return x, {'cache': x}\n",
    ),
    "TPU-LINT007": (
        "import jax\n"
        "def make(train_step):\n"
        "    return jax.jit(train_step)\n",
        "import jax\n"
        "def make(train_step):\n"
        "    return jax.jit(train_step, donate_argnums=(0, 1))\n",
    ),
    "TPU-LINT101": (
        "import threading\n"
        "def go(fn):\n"
        "    threading.Thread(target=fn, daemon=True).start()\n",
        "from bigdl_tpu.utils.threads import spawn\n"
        "def go(fn):\n"
        "    spawn(fn, name='worker')\n",
    ),
    "TPU-LINT102": (
        "import threading, time\n"
        "_lock = threading.Lock()\n"
        "def poll():\n"
        "    with _lock:\n"
        "        time.sleep(0.5)\n",
        "import threading, time\n"
        "_lock = threading.Lock()\n"
        "def poll():\n"
        "    with _lock:\n"
        "        pass\n"
        "    time.sleep(0.5)\n",
    ),
    "TPU-LINT103": (
        "import threading\n"
        "def go(fn):\n"
        "    threading.Thread(target=fn).start()\n",
        "import threading\n"
        "def go(fn):\n"
        "    threading.Thread(target=fn, daemon=True).start()\n",
    ),
    "TPU-LINT104": (
        "import threading, os\n"
        "_lock = threading.Lock()\n"
        "def publish(tmp, dst):\n"
        "    with _lock:\n"
        "        os.replace(tmp, dst)\n",
        "import threading, os\n"
        "_lock = threading.Lock()\n"
        "def publish(tmp, dst):\n"
        "    os.replace(tmp, dst)\n"
        "    with _lock:\n"
        "        pass\n",
    ),
    "TPU-LINT105": (
        "import threading\n"
        "_lock = threading.Lock()\n"
        "_cache = {}\n"
        "def put(k, v):\n"
        "    _cache[k] = v\n",
        "import threading\n"
        "_lock = threading.Lock()\n"
        "_cache = {}\n"
        "def put(k, v):\n"
        "    with _lock:\n"
        "        _cache[k] = v\n",
    ),
}


@pytest.mark.parametrize("rule", sorted(LINT_CASES))
def test_lint_rule_catches_bad_and_passes_good_twin(rule):
    bad_src, good_src = LINT_CASES[rule]
    bad = lint.lint_source(bad_src, HOT_PATH)
    assert rule in rules_of(bad), f"{rule} missed its bad fixture: {bad}"
    good = lint.lint_source(good_src, HOT_PATH)
    assert rule not in rules_of(good), \
        f"{rule} false-positived on its good twin: {good}"


def test_lint_pragma_suppresses():
    src = ("import math\n"
           "class L:\n"
           "    def forward(self, params, x, **_):\n"
           "        return x * math.sqrt(2.0)  # tpu-lint: disable=001\n")
    assert lint.lint_source(src, HOT_PATH) == []
    # full rule id and 'all' spellings work too
    src2 = src.replace("disable=001", "disable=TPU-LINT001")
    assert lint.lint_source(src2, HOT_PATH) == []
    src3 = src.replace("disable=001", "disable=all")
    assert lint.lint_source(src3, HOT_PATH) == []


def test_lint_static_probes_are_exempt():
    """Structure probes on traced values must not trip 002/003."""
    src = ("class L:\n"
           "    def forward(self, params, x, *rest, mask=None, **_):\n"
           "        if mask is not None and x.ndim == 3 and len(rest) > 1:\n"
           "            return x\n"
           "        if rest:\n"               # vararg tuple truthiness
           "            return rest[0]\n"
           "        if 'bias' in params:\n"   # structure membership
           "            return x + params['bias']\n"
           "        return x\n")
    assert lint.lint_source(src, HOT_PATH) == []


def test_lint_prngkey_exempt_in_tests():
    src = "import jax\nKEY = jax.random.PRNGKey(0)\n"
    assert lint.lint_source(src, "tests/test_foo.py") == []
    assert "TPU-LINT004" in rules_of(lint.lint_source(
        src, "bigdl_tpu/optim/foo.py"))


def test_lint_thread_rule_scoping():
    """101 is framework-scoped: the sanctioned wrapper itself and code
    outside bigdl_tpu/ may construct raw Threads (103's daemon check
    still applies everywhere)."""
    src = ("import threading\n"
           "def go(fn):\n"
           "    threading.Thread(target=fn).start()\n")
    assert "TPU-LINT101" not in rules_of(lint.lint_source(
        src, "bigdl_tpu/utils/threads.py"))
    outside = rules_of(lint.lint_source(src, "tools/some_tool.py"))
    assert "TPU-LINT101" not in outside and "TPU-LINT103" in outside


def test_lint_global_mutation_needs_module_lock():
    """105 only fires in modules that DECLARE locked concurrency — a
    lock-free module's globals are not its business."""
    src = ("_cache = {}\n"
           "def put(k, v):\n"
           "    _cache[k] = v\n")
    assert lint.lint_source(src, HOT_PATH) == []


def test_lint_baseline_is_burned_to_zero():
    """ISSUE 11 acceptance: the ratchet baseline carries NO debt — new
    violations fail immediately, everywhere."""
    baseline = lint.load_baseline(
        os.path.join(ROOT, "tools", "tpu_lint_baseline.json"))
    assert baseline == {}, baseline


def test_lint_float64_scoped_to_hot_dirs():
    src = "import numpy as np\nD = np.float64\n"
    assert "TPU-LINT005" in rules_of(lint.lint_source(
        src, "bigdl_tpu/optim/foo.py"))
    assert lint.lint_source(src, "bigdl_tpu/interop/foo.py") == []


# ================================================= repo scan + ratchet CI

def test_repo_is_lint_clean_vs_baseline():
    """THE ratchet gate: no new error-severity violations anywhere in
    bigdl_tpu/ beyond the checked-in baseline counts."""
    violations = lint.lint_paths(["bigdl_tpu"], ROOT)
    baseline = lint.load_baseline(
        os.path.join(ROOT, "tools", "tpu_lint_baseline.json"))
    new = lint.apply_baseline(violations, baseline)
    assert not new, "new tpu_lint violations (fix or pragma them):\n" + \
        "\n".join(str(v) for v in new)


def test_lint_cli_exit_codes(tmp_path):
    """tools/tpu_lint.py semantics: non-zero on violations, zero clean."""
    bad = tmp_path / "bad.py"
    bad.write_text("import math\n"
                   "class L:\n"
                   "    def forward(self, params, x, **_):\n"
                   "        return math.sin(x)\n")
    clean = tmp_path / "clean.py"
    clean.write_text("import jax.numpy as jnp\n"
                     "class L:\n"
                     "    def forward(self, params, x, **_):\n"
                     "        return jnp.sin(x)\n")
    assert lint.main([str(bad), "--no-baseline", "-q"]) == 1
    assert lint.main([str(clean), "--no-baseline", "-q"]) == 0
    # the checked-in tree passes against the checked-in baseline
    assert lint.main(["bigdl_tpu", "--root", ROOT, "-q", "--stats"]) == 0


# ======================================================= graph checker

X24 = jax.ShapeDtypeStruct((2, 4), jnp.float32)


def issues_for(model, *inputs, **kw):
    return check_module(model, inputs, raise_on_error=False, **kw)


def test_graphcheck_shape_mismatch_with_provenance():
    import analysis_fixtures as fx
    with pytest.raises(GraphCheckError) as ei:
        fx.broken_shapes().check(X24)
    issues = ei.value.issues
    assert any(i.rule == "GRAPH-SHAPE" and i.path == "model/1" and
               "Linear" in i.module for i in issues), issues
    # provenance (module path) must be in the rendered error message
    assert "model/1" in str(ei.value)


def test_graphcheck_dead_param():
    import analysis_fixtures as fx
    m = nn.Sequential(fx.DeadParamLayer(), name="model")
    issues = issues_for(m, X24)
    assert any(i.rule == "GRAPH-DEADPARAM" and
               i.path == "model/0/unused" for i in issues), issues


def test_graphcheck_stale_state_training_only():
    import analysis_fixtures as fx
    m = nn.Sequential(fx.StaleStateLayer(), name="model")
    issues = issues_for(m, X24, training=True)
    assert any(i.rule == "GRAPH-STALESTATE" and
               i.path == "model/0/counter" for i in issues), issues
    # eval mode: returning state untouched is correct
    assert not issues_for(m, X24, training=False)


def test_graphcheck_dtype_drift_f64():
    import analysis_fixtures as fx
    m = nn.Sequential(fx.Float64Layer(), name="model")
    issues = issues_for(m, X24)
    assert any(i.rule == "GRAPH-DTYPE" and i.path == "model/0/w"
               for i in issues), issues


def test_graphcheck_rogue_dequant():
    import analysis_fixtures as fx
    m = nn.Sequential(fx.RogueDequantLayer(), name="model")
    issues = issues_for(m, X24)
    assert any(i.rule == "GRAPH-QUANT" and i.path == "model/0"
               for i in issues), issues


def test_graphcheck_sanctioned_dequant_is_clean():
    """QuantizedLinear IS the dequant point — no GRAPH-QUANT for it."""
    from bigdl_tpu.nn.quantized import QuantizedLinear
    lin = nn.Linear(4, 3)
    params, _ = lin.init(jax.random.PRNGKey(0))
    qmod, qparams = QuantizedLinear.from_float(lin, params)
    qmod.use_pallas = False          # keep the walk on the XLA path
    issues = [i for i in issues_for(qmod, X24) if i.severity == "error"]
    # abstract walk can't rebuild converted params from specs; drive the
    # instrumented trace through apply directly instead
    from bigdl_tpu.analysis import graphcheck as gc
    ctx = gc._Ctx(qmod, training=False)
    with gc._instrumented(ctx):
        jax.eval_shape(lambda p, x: qmod.apply(p, {}, x), qparams,
                       jnp.zeros((2, 4), jnp.float32))
    assert not [i for i in ctx.issues if i.rule == "GRAPH-QUANT"], ctx.issues


def test_graphcheck_partition_spec_vs_mesh():
    from jax.sharding import PartitionSpec as P
    from bigdl_tpu.parallel.mesh import create_mesh
    from bigdl_tpu.parallel.sharding import ShardingRules
    mesh = create_mesh(model=2)
    m = nn.Sequential(nn.Linear(4, 4), name="model")
    bad = ShardingRules([(r".*weight", P(None, "modelx"))])
    issues = issues_for(m, X24, mesh=mesh, rules=bad)
    assert any(i.rule == "GRAPH-MESH" and "modelx" in i.message
               for i in issues), issues
    good = ShardingRules([(r".*weight", P(None, "model"))])
    assert not issues_for(m, X24, mesh=mesh, rules=good)


def test_graphcheck_dead_sharding_rule_warns():
    from jax.sharding import PartitionSpec as P
    from bigdl_tpu.parallel.mesh import create_mesh
    from bigdl_tpu.parallel.sharding import ShardingRules
    mesh = create_mesh(model=2)
    m = nn.Sequential(nn.Linear(4, 4), name="model")
    rules = ShardingRules([(r"no/such/param", P("model"))])
    issues = issues_for(m, X24, mesh=mesh, rules=rules)
    assert any(i.rule == "GRAPH-MESH" and i.severity == "warning"
               for i in issues), issues


def test_graphcheck_fold_name_collision_warns():
    """zlib.crc32('plumless') == crc32('buckeroo') — as sibling names they
    alias the same rng stream; Module.check() must warn (satellite: the
    silent-aliasing gap in core/module.py's _fold_name)."""
    m = nn.Sequential(name="model")
    m.add_child("plumless", nn.Linear(4, 4))
    m.add_child("buckeroo", nn.Linear(4, 4))
    issues = issues_for(m, X24)
    coll = [i for i in issues if i.rule == "GRAPH-RNGFOLD"]
    assert coll and coll[0].severity == "warning", issues
    assert "plumless" in coll[0].message and "buckeroo" in coll[0].message
    # distinct names don't warn
    assert not issues_for(nn.Sequential(nn.Linear(4, 4), nn.ReLU(),
                                        name="m"), X24)


def test_graphcheck_clean_model_and_summary():
    import analysis_fixtures as fx
    m = fx.clean_mlp()
    assert m.check(X24) == []
    out = m.summary(X24)
    assert "mlp/0" in out and "Linear" in out
    assert "total params:" in out
    # 4*8+8 + 8*2+2 = 58
    assert "58" in out.rsplit("total params:", 1)[1]


def test_graphcheck_cli_exit_codes():
    from bigdl_tpu.analysis.__main__ import main
    assert main(["bigdl_tpu.models.lenet:build",
                 "--input", "2,28,28,1"]) == 0
    assert main(["analysis_fixtures:broken_shapes",
                 "--input", "2,4"]) == 1


# ============================== catalog-wide property test (regression net)

@pytest.mark.parametrize("name", sorted(MODULES))
def test_catalog_layer_passes_check(name):
    """Every registered layer passes Module.check() clean at its canonical
    input shape — the regression net for all future layer PRs."""
    entry = MODULES[name]
    mod = entry.build()
    issues = check_module(mod, entry.inputs(), training=True,
                          rng=jax.random.PRNGKey(3), raise_on_error=False,
                          apply_kwargs=entry.kwargs or None)
    errors = [i for i in issues if i.severity == "error"]
    assert not errors, "\n".join(str(i) for i in errors)


# ===================================================== the tier's own shape

def test_tier_has_one_marker_put_in_the_tests_own_file():
    """`slow` is the only marker that selects (docs/testing.md), and a
    test gets it in the file that defines it: conftest.py marks nothing,
    so no table of module names can drift from what the tier runs."""
    import glob
    import re
    allowed = {"slow", "parametrize", "xfail", "skip", "skipif"}
    used = {}
    for path in glob.glob(os.path.join(ROOT, "tests", "*.py")):
        with open(path) as f:
            for mark in re.findall(r"\bmark\.(\w+)", f.read()):
                used.setdefault(mark, os.path.basename(path))
    assert set(used) <= allowed, {m: used[m] for m in set(used) - allowed}
    with open(os.path.join(ROOT, "tests", "conftest.py")) as f:
        conftest = f.read()
    assert "add_marker" not in conftest
    assert "pytest_collection_modifyitems" not in conftest
    assert re.findall(r'"markers",\s*"(\w+):', conftest) == ["slow"]
