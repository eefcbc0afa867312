"""Weights and training tokens from `--seed`, made by the benchmark.

The program is handed these weights (`program_params`, in the layout of
`GPT2LM`'s parameter tree, which is the program's interface as a checkpoint
format is); the plain reference makes the same values again for itself
(`stacked`, layers stacked on a leading axis for `lax.scan`). Neither takes
anything the other has made: both derive every leaf from the seed with the
same per-layer keys, and a test holds the two layouts equal bit for bit.

GPT-2's own initialisation (Radford et al. 2019): normal(0, 0.02) matrices,
the two residual projections scaled by 1/sqrt(2 * layers). Biases and the
LayerNorm offsets are small random numbers rather than GPT-2's zeros, so a
path that drops one is caught by the comparison with the reference.

A configuration may state `"init": {"std": m, "embedding_std": s}`: every
matrix, bias and offset gets the spread `m` in place of 0.02 (for a narrow
model of a test, whose products would else be too small to matter), and the
token table gets `s` and the position table `s / 2` in place of `m` and
`m / 2`. Under the tied head, a token's own row in the residual stream raises
its own logit at the next position by |row|^2 / rms(stream); at 0.02 that
is more than one spread of the logits, greedy decoding repeats one token
with a wide margin, and the served positions hold a twelfth of the near-ties
that tell one precision from the next (PERF.md, section 2).
"""

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02


def key_of(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _layer(key, d, n_layer, dtype, std=STD):
    """One block's leaves, named as the reference uses them."""
    ks = jax.random.split(key, 16)
    n = lambda k, shape, std=std: (                        # noqa: E731
        std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    proj = std / np.sqrt(2.0 * n_layer)
    return {
        "ln1_w": (1.0 + n(ks[0], (d,))).astype(dtype), "ln1_b": n(ks[1], (d,)),
        "wq": n(ks[2], (d, d)), "bq": n(ks[3], (d,)),
        "wk": n(ks[4], (d, d)), "bk": n(ks[5], (d,)),
        "wv": n(ks[6], (d, d)), "bv": n(ks[7], (d,)),
        "wo": n(ks[8], (d, d), proj), "bo": n(ks[9], (d,)),
        "ln2_w": (1.0 + n(ks[10], (d,))).astype(dtype),
        "ln2_b": n(ks[11], (d,)),
        "w1": n(ks[12], (d, 4 * d)), "b1": n(ks[13], (4 * d,)),
        "w2": n(ks[14], (4 * d, d), proj), "b2": n(ks[15], (d,)),
    }


def init_std(cfg):
    """(spread of the matrices, spread of the token table) as stated."""
    init = cfg.get("init", {})
    std = float(init.get("std", STD))
    return std, float(init.get("embedding_std", std))


def _top(key, cfg, dtype):
    d = cfg["n_embd"]
    std, emb = init_std(cfg)
    ks = jax.random.split(key, 4)
    n = lambda k, shape, std: (                            # noqa: E731
        std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    return {"wte": n(ks[0], (cfg["vocab_size"], d), emb),
            "wpe": n(ks[1], (cfg["n_positions"], d), emb / 2),
            "lnf_w": (1.0 + n(ks[2], (d,), std)).astype(dtype),
            "lnf_b": n(ks[3], (d,), std)}


def _keys(seed, n_layer):
    key = key_of(seed)
    top = jax.random.fold_in(key, 0x70F)
    layers = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(n_layer))
    return top, layers


def _shape_key(cfg):
    return (cfg["vocab_size"], cfg["n_positions"], cfg["n_embd"],
            cfg["n_layer"]) + init_std(cfg)


_BUILT = {}


def stacked(seed, cfg, dtype=jnp.float32):
    """The reference's layout: top-level leaves plus `layers`, a dict of
    arrays with a leading axis of `n_layer`. One jitted call."""
    sk = ("stacked", _shape_key(cfg), jnp.dtype(dtype).name)
    if sk not in _BUILT:
        d, L, std = cfg["n_embd"], cfg["n_layer"], init_std(cfg)[0]

        def bench_weights_stacked(top, layer_keys):
            w = _top(top, cfg, dtype)
            w["layers"] = jax.vmap(
                lambda k: _layer(k, d, L, dtype, std))(layer_keys)
            return w
        _BUILT[sk] = jax.jit(bench_weights_stacked)
    return _BUILT[sk](*_keys(seed, cfg["n_layer"]))


def to_program(layer):
    """One block of the reference's names -> one `h<i>` of `GPT2LM`."""
    return {
        "ln1": {"weight": layer["ln1_w"], "bias": layer["ln1_b"]},
        "attn": {k: layer[k] for k in
                 ("wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo")},
        "ln2": {"weight": layer["ln2_w"], "bias": layer["ln2_b"]},
        "ffn": {"w1": {"weight": layer["w1"], "bias": layer["b1"]},
                "w2": {"weight": layer["w2"], "bias": layer["b2"]}},
    }


def program_tree(w):
    """A stacked tree (weights, gradients or Adam slots of the reference)
    in the program's layout, for comparing leaf by leaf."""
    L = next(iter(w["layers"].values())).shape[0]
    out = {"wte": w["wte"], "wpe": w["wpe"],
           "ln_f": {"weight": w["lnf_w"], "bias": w["lnf_b"]}}
    for i in range(L):
        out[f"h{i}"] = to_program({k: v[i] for k, v in w["layers"].items()})
    return out


def program_params(seed, cfg, dtype=jnp.float32):
    """The same values in `GPT2LM`'s parameter tree, made on the device in
    one jitted call, leaf by leaf so that no stacked copy is ever alive."""
    sk = ("program", _shape_key(cfg), jnp.dtype(dtype).name)
    if sk not in _BUILT:
        d, L, std = cfg["n_embd"], cfg["n_layer"], init_std(cfg)[0]

        def bench_weights_program(top, layer_keys):
            t = _top(top, cfg, dtype)
            out = {"wte": t["wte"], "wpe": t["wpe"],
                   "ln_f": {"weight": t["lnf_w"], "bias": t["lnf_b"]}}
            for i in range(L):
                out[f"h{i}"] = to_program(_layer(layer_keys[i], d, L, dtype, std))
            return out
        _BUILT[sk] = jax.jit(bench_weights_program)
    return _BUILT[sk](*_keys(seed, cfg["n_layer"]))


def train_tokens(seed, rows, seq, vocab):
    """(rows, seq + 1) int32 token ids, every row different: inputs are
    `[:, :-1]` and next-token targets `[:, 1:]`."""
    rng = np.random.default_rng([int(seed), 0x7041])
    return rng.integers(0, vocab, size=(rows, seq + 1), dtype=np.int32)
