"""Config and profiling utils tests (reference analogues: EngineSpec config
checks, Metrics accumulator behavior)."""

import os

import jax
import jax.numpy as jnp
import numpy as np

import bigdl_tpu.nn as nn
from bigdl_tpu.utils import config
from bigdl_tpu.utils.profile import (IterationMetrics, format_times,
                                     module_times)


def test_config_defaults_and_env_override(monkeypatch):
    assert config.get("SEED") == 1
    monkeypatch.setenv("BIGDL_TPU_SEED", "42")
    assert config.get("SEED") == 42
    monkeypatch.setenv("BIGDL_TPU_CHECK_SINGLETON", "true")
    assert config.get("CHECK_SINGLETON") is True
    out = config.print_config()
    assert "BIGDL_TPU_SEED = 42 (set)" in out
    assert "BIGDL_TPU_FAILURE_RETRY_TIMES" in out


def test_module_times_orders_by_cost():
    model = nn.Sequential(
        nn.Linear(64, 512, name="big"),
        nn.ReLU(),
        nn.Linear(512, 4, name="small"))
    params, state = model.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).randn(32, 64), jnp.float32)
    times = module_times(model, params, state, x, repeats=2)
    assert len(times) == 3
    names = [n for n, _ in times]
    assert any("big" in n for n in names)
    table = format_times(times)
    assert "module" in table and "%" in table


def test_iteration_metrics_summary():
    m = IterationMetrics()
    with m.time("forward"):
        pass
    with m.time("forward"):
        pass
    m.add("comm", 0.5)
    s = m.summary()
    assert "comm: total 0.500s over 1" in s
    assert "forward" in s


def test_config_knobs_are_wired(monkeypatch):
    """Every documented knob must have a real consumer."""
    import numpy as np
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.dataset import ArrayDataSet

    # SEED
    monkeypatch.setenv("BIGDL_TPU_SEED", "123")
    ds = ArrayDataSet(np.zeros((4, 2), np.float32),
                      np.zeros(4, np.int32), 2)
    opt = Optimizer(nn.Linear(2, 2), ds, nn.MSECriterion())
    assert opt.seed == 123
    # LOG_THROUGHPUT_EVERY
    monkeypatch.setenv("BIGDL_TPU_LOG_THROUGHPUT_EVERY", "5")
    opt2 = Optimizer(nn.Linear(2, 2), ds, nn.MSECriterion())
    assert opt2._log_every == 5
    # a bool knob honors false
    monkeypatch.setenv("BIGDL_TPU_CHECK_SINGLETON", "false")
    assert config.get("CHECK_SINGLETON") is False
    monkeypatch.setenv("BIGDL_TPU_CHECK_SINGLETON", "1")
    assert config.get("CHECK_SINGLETON") is True


def test_optimize_with_retry_recovers(tmp_path, monkeypatch):
    """A transient failure mid-training resumes from checkpoint."""
    import numpy as np
    from bigdl_tpu.optim.local import Optimizer
    from bigdl_tpu.optim.method import SGD
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.dataset import ArrayDataSet

    r = np.random.RandomState(0)
    x = r.randn(32, 4).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    model = nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax())
    ds = ArrayDataSet(x, y, 8, drop_last=True)
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), SGD(0.1))
    opt.set_end_when(Trigger.max_epoch(4))
    opt.set_checkpoint(str(tmp_path), Trigger.every_epoch())

    calls = {"n": 0}
    real = opt._maybe_validate

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 6:          # blow up once mid-epoch-2
            raise RuntimeError("injected fault")
        return real(*a, **kw)

    opt._maybe_validate = flaky
    params, state = opt.optimize_with_retry(retries=2, window_s=60)
    assert opt.state["epoch"] >= 3   # completed after recovery


def test_dl_image_reader_and_transformer(tmp_path):
    from PIL import Image
    import numpy as np
    from bigdl_tpu.dlframes import DLImageReader, DLImageTransformer
    from bigdl_tpu.dataset.vision import ChannelNormalize, Resize
    d = tmp_path / "imgs"
    d.mkdir()
    for i in range(3):
        arr = np.random.RandomState(i).randint(
            0, 255, (8 + i, 10, 3), np.uint8)
        Image.fromarray(arr).save(str(d / f"im{i}.png"))
    frame = DLImageReader.read_images(str(d))
    assert len(frame["origin"]) == 3
    assert frame["height"] == [8, 9, 10]
    assert frame["n_channels"] == [3, 3, 3]
    tr = DLImageTransformer([Resize(4, 4),
                             ChannelNormalize((127.5,) * 3, (127.5,) * 3)])
    out = tr.transform(frame)
    assert len(out["features"]) == 3
    assert all(f.shape == (4, 4, 3) for f in out["features"])
    assert max(max(abs(float(f.max())), abs(float(f.min())))
               for f in out["features"]) <= 1.0 + 1e-5


def test_dl_image_transformer_randomness_varies_per_image(tmp_path):
    from PIL import Image
    import numpy as np
    from bigdl_tpu.dlframes import DLImageReader, DLImageTransformer
    from bigdl_tpu.dataset.vision import RandomCrop
    d = tmp_path / "imgs2"
    d.mkdir()
    arr = np.arange(20 * 20 * 3, dtype=np.uint8).reshape(20, 20, 3)
    for i in range(6):
        Image.fromarray(arr).save(str(d / f"a{i}.png"))
    tr = DLImageTransformer(RandomCrop(8, 8), seed=0)
    out = tr.transform(DLImageReader.read_images(str(d)))
    crops = [f.tobytes() for f in out["features"]]
    # identical inputs + random crop: offsets must differ across images
    assert len(set(crops)) > 1


def test_device_memory_summary_and_profile(tmp_path):
    """Memory observability helpers: stats dict (possibly empty on host
    CPU) and a pprof device-memory profile that actually lands on
    disk."""
    from bigdl_tpu.utils.profile import (device_memory_summary,
                                         memory_profile)
    import jax.numpy as jnp
    x = jnp.ones((128, 128)) @ jnp.ones((128, 128))
    x.block_until_ready()
    stats = device_memory_summary()
    assert isinstance(stats, dict)
    for v in stats.values():
        assert isinstance(v, int)
    p = memory_profile(str(tmp_path / "mem.pprof"))
    import os
    assert os.path.getsize(p) > 0


def test_a_test_past_its_limit_fails_and_the_next_still_runs(tmp_path):
    """tests/conftest.py's limit, set to 1 s through the same hooks: the
    test that sleeps past it fails, the test after it runs and passes."""
    import subprocess
    import sys
    tier_conftest = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "conftest.py")
    (tmp_path / "conftest.py").write_text(
        "import importlib.util\n"
        f"spec = importlib.util.spec_from_file_location('tier', "
        f"{tier_conftest!r})\n"
        "tier = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(tier)\n"
        "tier.TEST_LIMIT_S = 1.0\n"
        "from_tier = ('pytest_runtest_setup', 'pytest_runtest_call',\n"
        "             'pytest_runtest_teardown', 'pytest_configure')\n"
        "globals().update({n: getattr(tier, n) for n in from_tier})\n")
    (tmp_path / "test_two.py").write_text(
        "import time\n"
        "import pytest\n"
        "def test_hangs():\n"
        "    time.sleep(30)\n"
        "def test_next():\n"
        "    pass\n"
        "@pytest.mark.slow\n"
        "def test_slow_keeps_its_time():\n"
        "    time.sleep(1.5)\n")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "--rootdir", str(tmp_path), str(tmp_path)],
        capture_output=True, text=True, timeout=60, cwd=str(tmp_path))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "1 failed, 2 passed" in r.stdout, r.stdout
    assert "test_hangs: call took more than 1 s" in r.stdout, r.stdout
