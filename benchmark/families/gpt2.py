"""The GPT-2 family: everything of the benchmark that knows GPT-2.

A family is one module of `benchmark/families/`, found by the name a
configuration states under `family` (PERF.md, section 3, has the
interface). This one holds: the check of what it implements and the
program's model for a configuration; the weights from the seed in the
program's layout and in the reference's; the plain reference's forward
pass, written from the published equations (Radford et al. 2019) with
nothing imported from the program; and the shape functions that count
parameters and the FLOPs a token needs. What is the same for every family
(the arithmetic of a mode, the served gaps, the loss and its gradients,
Adam, the norms, the statistics) is in `reference.py`, `weights.py` and
`reduce.py`, and is imported from there.

The weights are GPT-2's own initialisation: normal(0, 0.02) matrices, the
two residual projections scaled by 1/sqrt(2 * layers). Biases and the
LayerNorm offsets are small random numbers rather than GPT-2's zeros, so a
path that drops one is caught by the comparison with the reference. The
program is handed `program_params` (the layout of `GPT2LM`'s parameter
tree, which is the program's interface as a checkpoint format is); the
reference makes the same values again for itself (`stacked`, layers on a
leading axis for `lax.scan`). Neither takes anything the other has made:
both derive every leaf from the seed with the same per-layer keys, and a
test holds the two layouts equal bit for bit.

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

import math

import jax
import jax.numpy as jnp
import numpy as np

import reference
from weights import init_std, key_of

# What the builder, the weights and the reference below implement. A
# configuration that states anything else is refused: another activation or
# an untied head is code here, not data.
IMPLEMENTED = {"activation_function": "gelu_new", "tie_word_embeddings": True}
# The arithmetic in which `logits` can be computed (`reference.mm`).
MODES = ("float32", "bfloat16", "fp8")


def check(cfg):
    for key, have in IMPLEMENTED.items():
        if cfg.get(key) != have:
            raise ValueError(f"configuration states {key}={cfg.get(key)!r}; "
                             f"the family gpt2 implements {have!r} only")


def sizes(cfg):
    """What the general files need to know of a configuration's sizes."""
    return {"vocab": cfg["vocab_size"], "positions": cfg["n_positions"]}


def build_model(cfg):
    """The program's model for a configuration's sizes, and its eos id."""
    from bigdl_tpu.interop.huggingface import GPT2LM
    check(cfg)
    eos = cfg["vocab_size"] - 1
    return GPT2LM(cfg["vocab_size"], cfg["n_positions"], cfg["n_embd"],
                  cfg["n_head"], cfg["n_layer"],
                  ln_eps=cfg["layer_norm_epsilon"], eos_id=eos), eos


# ------------------------------------------------------------- weights
def _layer(key, d, n_layer, dtype, std):
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


# ----------------------------------------------------------- reference
def _ln(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, lw, n_head, eps, mode):
    mm = reference.mm(mode)
    B, T, d = x.shape
    hd = d // n_head
    h = _ln(x, lw["ln1_w"], lw["ln1_b"], eps)
    split = lambda a: a.reshape(B, T, n_head, hd).transpose(0, 2, 1, 3)  # noqa: E731
    q = split(mm(h, lw["wq"]) + lw["bq"])
    k = split(mm(h, lw["wk"]) + lw["bk"])
    v = split(mm(h, lw["wv"]) + lw["bv"])
    if mode == "fp8":
        q, k, v = (reference.round_fp8(a) for a in (q, k, v))
    prec = None if mode == "bfloat16" else jax.lax.Precision.HIGHEST
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=prec) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    if mode == "fp8":
        p = reference.round_fp8(p)
    a = jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=prec)
    a = a.transpose(0, 2, 1, 3).reshape(B, T, d)
    x = x + mm(a, lw["wo"]) + lw["bo"]
    h = _ln(x, lw["ln2_w"], lw["ln2_b"], eps)
    f = _gelu_new(mm(h, lw["w1"]) + lw["b1"])
    return x + mm(f, lw["w2"]) + lw["b2"]


def logits(w, cfg, tokens, mode="float32"):
    """tokens (B, T) int32 -> (B, T, vocab) logits, head tied to `wte`.
    Layers are stacked and scanned so that the 48-layer model compiles in
    seconds, and each block is rematerialised in the backward pass so that
    the float32 reference fits beside nothing else on one chip."""
    n_head, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    dtype = jnp.bfloat16 if mode == "bfloat16" else jnp.float32
    w = jax.tree.map(lambda a: a.astype(dtype), w)
    T = tokens.shape[1]
    x = w["wte"][tokens] + w["wpe"][:T]
    body = jax.checkpoint(
        lambda x, lw: (_block(x, lw, n_head, eps, mode), None))
    x, _ = jax.lax.scan(body, x, w["layers"])
    x = _ln(x, w["lnf_w"], w["lnf_b"], eps)
    return reference.mm(mode)(x, w["wte"].T)


# --------------------------------------------------------------- FLOPs
def parameters(cfg):
    """Parameter counts of a GPT-2 from its sizes alone."""
    d, L = cfg["n_embd"], cfg["n_layer"]
    per_layer = 12 * d * d + 13 * d
    non_embedding = L * per_layer + 2 * d
    embedding = cfg["vocab_size"] * d + cfg["n_positions"] * d
    return {"non_embedding": non_embedding, "embedding": embedding,
            "head": cfg["vocab_size"] * d,
            "total": non_embedding + embedding}


def serve_token_flops(cfg, position, logits):
    """FLOPs the model needs to process one token at `position` (counting
    from 0) through the cache: 2 a parameter outside the embeddings, the
    head where logits are taken, and 4 d per layer per live position."""
    p = parameters(cfg)
    live = position + 1
    return (2.0 * p["non_embedding"] + (2.0 * p["head"] if logits else 0.0)
            + 4.0 * cfg["n_embd"] * live * cfg["n_layer"])


def train_token_flops(cfg, seq):
    """FLOPs a trained token needs, forward and backward, recomputation not
    counted: 6 a parameter outside the embeddings, 6 for the head, and the
    causal half of attention (6 L d seq)."""
    p = parameters(cfg)
    return (6.0 * p["non_embedding"] + 6.0 * p["head"]
            + 6.0 * cfg["n_layer"] * cfg["n_embd"] * seq)
