"""CI-enforce the examples (VERDICT r2 weak #2 / next #3).

The reference compiles its examples as part of the build
(spark/dl/src/main/scala/com/intel/analytics/bigdl/example/ ships in the
same module as the library, so `mvn test` breaks if an example rots);
the analogue here is to actually *run* each `examples/*.py` hermetically
in a subprocess and assert a clean exit.

The runs are `slow`: each is a subprocess that trains a model (6 to 85 s,
342 s together in PR 24's sequential run), so the tier every PR is held to
does not run them (`-m slow` does).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES_DIR = os.path.join(REPO, "examples")

EXAMPLES = sorted(
    f for f in os.listdir(EXAMPLES_DIR) if f.endswith(".py")
)


def test_all_examples_enumerated():
    # if an example is added, it is auto-collected; this guards deletion
    assert len(EXAMPLES) >= 10


@pytest.mark.slow        # a subprocess that trains (6-85 s each)
@pytest.mark.parametrize("name", EXAMPLES)
def test_example_runs_clean(name, tmp_path):
    env = dict(os.environ)          # conftest's JAX_PLATFORMS=cpu rides along
    # hermetic: examples that write (checkpoints, exports) go to tmp
    env.setdefault("TMPDIR", str(tmp_path))
    r = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES_DIR, name)],
        cwd=str(tmp_path), env=env,
        # examples run ~30-250s alone; the margin absorbs a loaded
        # machine (a full-suite run alongside other jobs has tripped 600)
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, (
        f"{name} exited rc={r.returncode}\n"
        f"--- stdout tail ---\n{r.stdout[-2000:]}\n"
        f"--- stderr tail ---\n{r.stderr[-2000:]}"
    )
