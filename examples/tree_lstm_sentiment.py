"""Tree-LSTM sentiment classification over constituency trees
(reference: example/treeLSTMSentiment/ — BinaryTreeLSTM over SST parse
trees with GloVe embeddings; here synthetic trees + learned embeddings so
the example runs hermetically).

    JAX_PLATFORMS=cpu python examples/tree_lstm_sentiment.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402
import bigdl_tpu.nn as nn                                     # noqa: E402


def make_batch(rng, batch, n_leaves, vocab):
    """Random right-branching parse trees over token sequences; label =
    whether 'positive' tokens (< vocab/2) outnumber negative ones."""
    toks = rng.randint(0, vocab, (batch, n_leaves))
    labels = (2 * (toks < vocab // 2).sum(1) > n_leaves).astype(np.int32)
    # nodes: leaves 1..L, then internal combining (prev, leaf) left-to-right
    n_nodes = 2 * n_leaves - 1
    tree = np.zeros((batch, n_nodes, 3), np.int32)
    for i in range(n_leaves):
        tree[:, i] = (0, 0, i + 1)                 # leaf i+1 (1-based)
    prev = 1
    for j in range(n_leaves, n_nodes):
        leaf = j - n_leaves + 2                    # next leaf node id
        tree[:, j] = (prev, leaf, 0)
        prev = j + 1
    tree[:, n_nodes - 1, 2] = -1                   # mark root
    return toks, tree, labels


def main():
    vocab, dim, hidden, n_leaves, batch = 40, 16, 32, 6, 64
    rng = np.random.RandomState(0)
    toks, tree, labels = make_batch(rng, batch, n_leaves, vocab)

    embed = nn.LookupTable(vocab, dim)
    tlstm = nn.BinaryTreeLSTM(dim, hidden)
    head = nn.Linear(hidden, 2)
    ep, es = embed.init(jax.random.PRNGKey(0))
    tp, ts = tlstm.init(jax.random.PRNGKey(1))
    hp, hs = head.init(jax.random.PRNGKey(2))
    params = {"embed": ep, "tree": tp, "head": hp}
    crit = nn.CrossEntropyCriterion()
    tk = jnp.asarray(toks)
    tr = jnp.asarray(tree)
    y = jnp.asarray(labels)

    @jax.jit
    def step(params):
        def loss_fn(params):
            emb, _ = embed.apply(params["embed"], es, tk)
            states, _ = tlstm.apply(params["tree"], ts, (emb, tr))
            logits, _ = head.apply(params["head"], hs, states[:, -1])
            return crit.forward(logits, y), logits
        (l, logits), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return l, logits, jax.tree.map(lambda a, b: a - 0.1 * b, params, g)

    for it in range(200):
        loss, logits, params = step(params)
        if it % 50 == 0:
            acc = float((jnp.argmax(logits, -1) == y).mean())
            print(f"iter {it:3d}  loss {float(loss):.4f}  acc {acc:.3f}")
    acc = float((jnp.argmax(logits, -1) == y).mean())
    print(f"final: loss {float(loss):.4f}  acc {acc:.3f}")
    assert acc > 0.9, "tree-LSTM failed to fit the sentiment toy task"


if __name__ == "__main__":
    main()
