"""Concurrent model serving (reference: example/udfpredictor +
optim/PredictionService.scala:56-66 — a blocking-queue pool of model
instances serving concurrent requests).

    JAX_PLATFORMS=cpu python examples/serving.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from concurrent.futures import ThreadPoolExecutor            # noqa: E402
import jax                                                   # noqa: E402
import numpy as np                                           # noqa: E402
import bigdl_tpu.nn as nn                                    # noqa: E402
from bigdl_tpu.models import lenet                           # noqa: E402
from bigdl_tpu.optim.predictor import PredictionService      # noqa: E402


def main():
    model = lenet.build(10)
    params, state = model.init(jax.random.PRNGKey(0))
    service = PredictionService(model, params, state, instance_num=4)

    r = np.random.RandomState(0)
    requests = [r.randn(1, 28, 28, 1).astype(np.float32)
                for _ in range(32)]

    with ThreadPoolExecutor(8) as pool:
        outs = list(pool.map(service.predict, requests))

    assert len(outs) == 32
    assert all(np.asarray(o).shape == (1, 10) for o in outs)
    print(f"served {len(outs)} concurrent requests; "
          f"sample prediction class: {int(np.argmax(outs[0]))}")


if __name__ == "__main__":
    main()
