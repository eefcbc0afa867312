"""Spark-ML-style pipeline: image folder → frame → transform → classifier
(reference: example/MLPipeline + example/dlframes — DLImageReader,
DLImageTransformer, DLClassifier over Spark DataFrames; here columnar
frames, no Spark).

    JAX_PLATFORMS=cpu python examples/ml_pipeline.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np                                           # noqa: E402
import bigdl_tpu.nn as nn                                    # noqa: E402
from bigdl_tpu.dataset.vision import (ChannelNormalize,      # noqa: E402
                                      Resize)
from bigdl_tpu.dlframes import (DLClassifier, DLImageReader,  # noqa: E402
                                DLImageTransformer)


def make_image_folder(root, n=96, seed=0):
    """Class = dominant color channel; varied sizes exercise the reader."""
    from PIL import Image
    r = np.random.RandomState(seed)
    labels = []
    for i in range(n):
        cls = i % 3
        arr = r.randint(0, 70, (24 + (i % 5), 28, 3), np.uint8)
        arr[..., cls] += 160
        Image.fromarray(arr).save(os.path.join(root, f"img{i:03d}.png"))
        labels.append(cls)
    return np.asarray(labels, np.int64)


def main():
    d = tempfile.mkdtemp()
    labels = make_image_folder(d)

    frame = DLImageReader.read_images(d)
    print(f"read {len(frame['origin'])} images, "
          f"heights {min(frame['height'])}..{max(frame['height'])}")

    transformer = DLImageTransformer(
        [Resize(16, 16), ChannelNormalize((127.5,) * 3, (127.5,) * 3)])
    frame = transformer.transform(frame)
    frame["features"] = np.stack(frame["features"])
    frame["label"] = labels

    estimator = DLClassifier(
        nn.Sequential(nn.Flatten(), nn.Linear(16 * 16 * 3, 32), nn.ReLU(),
                      nn.Linear(32, 3), nn.LogSoftMax()),
        nn.ClassNLLCriterion(), feature_size=(16, 16, 3),
        batch_size=32, max_epoch=20, learning_rate=0.1)
    model = estimator.fit(frame)

    out = model.transform(frame)
    acc = float((np.asarray(out["prediction"]) == labels).mean())
    print(f"pipeline train accuracy: {acc:.3f}")
    assert acc > 0.95


if __name__ == "__main__":
    main()
