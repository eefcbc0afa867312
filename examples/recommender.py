"""Neural collaborative filtering on MovieLens (reference: the movielens
dataset helper pyspark/bigdl/dataset/movielens.py scored with the
HitRatio/NDCG validation methods, optim/ValidationMethod.scala:660,700).

Hermetic: synthetic MovieLens-shaped ratings with latent block structure;
the NCF tower must learn the user-group x item-group preference and rank
held-out positives above sampled negatives.

    JAX_PLATFORMS=cpu python examples/recommender.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np                                           # noqa: E402
import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import bigdl_tpu.nn as nn                                    # noqa: E402
from bigdl_tpu.core.container import Graph, Input            # noqa: E402
from bigdl_tpu.dataset import movielens                      # noqa: E402
from bigdl_tpu.optim.metrics import NDCG, HitRatio           # noqa: E402

N_USERS, N_ITEMS, DIM = 400, 200, 16


def build_ncf():
    """Two-tower embedding + MLP scorer: score(user, item) in R."""
    u = Input()
    i = Input()
    ue = nn.LookupTable(N_USERS + 1, DIM)(u)
    ie = nn.LookupTable(N_ITEMS + 1, DIM)(i)
    h = nn.JoinTable(1)(ue, ie)
    h = nn.Linear(2 * DIM, 32)(h)
    h = nn.ReLU()(h)
    h = nn.Linear(32, 1)(h)
    return Graph([u, i], [h])


def main():
    data = movielens.get_id_ratings(n_users=N_USERS, n_items=N_ITEMS,
                                    n_synthetic=30000)
    users, items = data[:, 0], data[:, 1]
    pos = (data[:, 2] >= 4).astype(np.float32)   # implicit feedback
    model = build_ncf()
    params, state = model.init(jax.random.PRNGKey(0))
    crit = nn.BCECriterion()

    ub = jnp.asarray(users, jnp.int32)
    ib = jnp.asarray(items, jnp.int32)
    yb = jnp.asarray(pos)

    from bigdl_tpu.optim.method import Adam
    method = Adam(5e-3)
    slots = method.init_slots(params)

    @jax.jit
    def step(p, sl, t):
        def loss(p):
            out, _ = model.apply(p, state, ub, ib)
            return crit.forward(jax.nn.sigmoid(out[:, 0]), yb)
        l, g = jax.value_and_grad(loss)(p)
        np_, nsl = method.update(p, g, sl, jnp.float32(5e-3), t)
        return l, np_, nsl

    first = None
    for t in range(300):
        l, params, slots = step(params, slots, jnp.int32(t))
        if first is None:
            first = float(l)
    print(f"NCF training loss: {first:.3f} -> {float(l):.3f}")

    # HR@10 / NDCG@10: for each eval user, 1 held-out liked item vs 50
    # sampled negatives (the reference's NCF evaluation protocol)
    r = np.random.RandomState(1)
    neg = 50
    eval_users, cand_items = [], []
    for u in range(1, 101):
        liked = (u - 1) % 4
        liked_items = np.arange(1, N_ITEMS + 1)[(np.arange(N_ITEMS)) % 4
                                                == liked]
        disliked = np.arange(1, N_ITEMS + 1)[(np.arange(N_ITEMS)) % 4
                                             != liked]
        cands = np.concatenate([[r.choice(liked_items)],
                                r.choice(disliked, neg, replace=False)])
        eval_users.append(np.full(neg + 1, u))
        cand_items.append(cands)
    ue = jnp.asarray(np.concatenate(eval_users), jnp.int32)
    ie = jnp.asarray(np.concatenate(cand_items), jnp.int32)
    scores, _ = model.apply(params, state, ue, ie)
    labels = np.zeros((100, neg + 1), np.float32)
    labels[:, 0] = 1.0

    hr = HitRatio(k=10, neg_num=neg).batch(scores[:, 0],
                                           jnp.asarray(labels.reshape(-1)))
    ndcg = NDCG(k=10, neg_num=neg).batch(scores[:, 0],
                                         jnp.asarray(labels.reshape(-1)))
    print(f"HR@10 = {hr.result:.3f}   NDCG@10 = {ndcg.result:.3f} "
          f"(chance HR@10 ~ {10 / (neg + 1):.3f})")
    assert hr.result > 0.6 and ndcg.result > 0.3


if __name__ == "__main__":
    main()
