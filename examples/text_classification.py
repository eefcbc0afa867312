"""Text classification: embedding + temporal CNN + max-pool
(reference: example/textclassification — GloVe embeddings + CNN; here
hermetic synthetic data + trained embeddings).

    JAX_PLATFORMS=cpu python examples/text_classification.py
"""

import numpy as np

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                   # noqa: E402
import bigdl_tpu.nn as nn                                    # noqa: E402
from bigdl_tpu.dataset import ArrayDataSet, text             # noqa: E402
from bigdl_tpu.optim.local import Optimizer                  # noqa: E402
from bigdl_tpu.optim.method import Adam                      # noqa: E402
from bigdl_tpu.optim.metrics import Top1Accuracy, evaluate   # noqa: E402
from bigdl_tpu.optim.trigger import Trigger                  # noqa: E402


def make_corpus(n=512, seq_len=20, seed=0):
    """Two 'topics' with distinct vocabulary distributions."""
    rng = np.random.RandomState(seed)
    topic_words = [np.arange(2, 52), np.arange(52, 102)]
    xs, ys = [], []
    for i in range(n):
        label = i % 2
        words = rng.choice(topic_words[label], seq_len)
        noise = rng.choice(np.arange(2, 102), seq_len // 4)
        words[: len(noise)] = noise
        xs.append(words)
        ys.append(label)
    return np.stack(xs).astype(np.int32), np.asarray(ys, np.int32)


def build_model(vocab=102, embed=32, seq_len=20, classes=2):
    return nn.Sequential(
        nn.LookupTable(vocab, embed),
        nn.TemporalConvolution(embed, 64, 5),
        nn.ReLU(),
        nn.TemporalMaxPooling(seq_len - 4),
        nn.Flatten(),
        nn.Linear(64, classes),
        nn.LogSoftMax(),
        name="TextCNN")


def main():
    x, y = make_corpus()
    ds = ArrayDataSet(x, y, 64, drop_last=True)
    model = build_model()
    opt = Optimizer(model, ds, nn.ClassNLLCriterion(), Adam(1e-3))
    opt.set_end_when(Trigger.max_epoch(5))
    params, state = opt.optimize()
    res = evaluate(model, params, state,
                   ArrayDataSet(x, y, 64, shuffle=False), [Top1Accuracy()])
    acc = res["Top1Accuracy"].result
    print(f"text-classification train accuracy: {acc:.3f}")
    assert acc > 0.9
    return acc


if __name__ == "__main__":
    main()
