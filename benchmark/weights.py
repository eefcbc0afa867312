"""What every family's weights and training tokens are made with.

A family (`benchmark/families/<name>`) makes its own leaves from `--seed`;
the key it starts from, the spreads a configuration may state and the
training tokens are the same for all of them and live here.
"""

import jax
import numpy as np

STD = 0.02


def key_of(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def init_std(cfg):
    """(spread of the matrices, spread of the token table) as stated under
    `"init": {"std": m, "embedding_std": s}`; 0.02 where nothing is."""
    init = cfg.get("init", {})
    std = float(init.get("std", STD))
    return std, float(init.get("embedding_std", std))


def train_tokens(seed, rows, seq, vocab):
    """(rows, seq + 1) int32 token ids, every row different: inputs are
    `[:, :-1]` and next-token targets `[:, 1:]`."""
    rng = np.random.default_rng([int(seed), 0x7041])
    return rng.integers(0, vocab, size=(rows, seq + 1), dtype=np.int32)
