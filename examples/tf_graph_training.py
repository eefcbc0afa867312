"""Train a TensorFlow graph (reference: example/tensorflow — load a TF
model definition and train it with the distributed optimizer;
utils/tf/Session.scala).

A frozen GraphDef (here produced by our own exporter standing in for a
TF-authored .pb — zero-egress image) is loaded by TFTrainingSession and
fine-tuned end-to-end.

    JAX_PLATFORMS=cpu python examples/tf_graph_training.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np                                           # noqa: E402
import jax                                                   # noqa: E402
import bigdl_tpu.nn as nn                                    # noqa: E402
from bigdl_tpu.core.container import Sequential              # noqa: E402
from bigdl_tpu.dataset import ArrayDataSet                   # noqa: E402
from bigdl_tpu.interop.tf_saver import save_model            # noqa: E402
from bigdl_tpu.interop.tf_session import TFTrainingSession   # noqa: E402
from bigdl_tpu.optim.method import Adam                      # noqa: E402
from bigdl_tpu.optim.trigger import Trigger                  # noqa: E402


def main():
    # stand-in "TF-authored" graph: an untrained CNN exported to .pb
    model = Sequential(
        nn.SpatialConvolution(1, 8, 3, 3, pad_w=-1, pad_h=-1), nn.ReLU(),
        nn.SpatialMaxPooling(2, 2),
        nn.Reshape((8 * 7 * 7,)), nn.Linear(8 * 7 * 7, 10))
    params, state = model.init(jax.random.PRNGKey(0))
    pb = os.path.join(tempfile.mkdtemp(), "mnist_net.pb")
    save_model(pb, model, params, state)
    print(f"wrote {pb} ({os.path.getsize(pb)} bytes)")

    # synthetic MNIST-shaped task: label = brightest quadrant row
    r = np.random.RandomState(0)
    x = r.rand(4096, 14, 14, 1).astype(np.float32)
    q = x.reshape(-1, 2, 7, 2, 7).mean((2, 4)).reshape(-1, 4)
    srt = np.sort(q, axis=1)
    keep = (srt[:, -1] - srt[:, -2]) > 0.01   # drop near-tied quadrants
    x, q = x[keep][:2048], q[keep][:2048]
    y = np.argmax(q, axis=1).astype(np.int32)

    sess = TFTrainingSession(pb, criterion=nn.CrossEntropyCriterion())
    acc0 = float((np.argmax(np.asarray(sess.predict(x)), 1) == y).mean())
    sess.train(ArrayDataSet(x, y, 128, drop_last=True), Adam(2e-3),
               Trigger.max_epoch(40))
    acc1 = float((np.argmax(np.asarray(sess.predict(x)), 1) == y).mean())
    print(f"imported-graph training: accuracy {acc0:.3f} -> {acc1:.3f}")
    assert acc1 > 0.9


if __name__ == "__main__":
    main()
