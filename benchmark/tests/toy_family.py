"""A family the tree does not hold, for `test_family_seam.py`, which copies
this file into a temporary directory as `families/toyllama.py`: a small
LLaMA-style decoder (RMSNorm, rotary positions in the rotate-half
convention, grouped key/value heads, SwiGLU, an untied head; Touvron et al.
2023) behind the program's `LlamaLM`, with its weights from the seed in
both layouts and its plain reference. It is what a `model_config` PR
writes: this interface and nothing of the harness."""

import math

import jax
import jax.numpy as jnp

import reference
from weights import init_std, key_of

MODES = ("float32", "bfloat16")


def check(cfg):
    if cfg.get("hidden_act") != "silu" or cfg.get("tie_word_embeddings"):
        raise ValueError("the family toyllama implements hidden_act='silu' "
                         "and an untied head only")


def sizes(cfg):
    return {"vocab": cfg["vocab_size"],
            "positions": cfg["max_position_embeddings"]}


def build_model(cfg):
    from bigdl_tpu.interop.huggingface import LlamaLM
    check(cfg)
    eos = cfg["vocab_size"] - 1
    return LlamaLM(cfg["vocab_size"], cfg["hidden_size"],
                   cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["intermediate_size"], cfg["num_hidden_layers"],
                   eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
                   tied=False, eos_id=eos), eos


def _dims(cfg):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, H, cfg["num_key_value_heads"], d // H, cfg["intermediate_size"]


def _layer(key, cfg, dtype):
    d, H, KV, hd, ff = _dims(cfg)
    std = init_std(cfg)[0]
    ks = jax.random.split(key, 9)
    n = lambda k, shape: (std * jax.random.normal(                # noqa: E731
        k, shape, jnp.float32)).astype(dtype)
    return {"ln1": (1.0 + n(ks[0], (d,))).astype(dtype),
            "wq": n(ks[1], (d, d)), "wk": n(ks[2], (d, KV * hd)),
            "wv": n(ks[3], (d, KV * hd)), "wo": n(ks[4], (d, d)),
            "ln2": (1.0 + n(ks[5], (d,))).astype(dtype),
            "gate": n(ks[6], (d, ff)), "up": n(ks[7], (d, ff)),
            "down": n(ks[8], (ff, d))}


def stacked(seed, cfg, dtype=jnp.float32):
    @jax.jit
    def make(key):
        std, emb = init_std(cfg)
        d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
        ks = jax.random.split(jax.random.fold_in(key, 0x70F), 3)
        n = lambda k, shape, s: (s * jax.random.normal(           # noqa: E731
            k, shape, jnp.float32)).astype(dtype)
        return {"embed": n(ks[0], (cfg["vocab_size"], d), emb),
                "lm_head": n(ks[1], (cfg["vocab_size"], d), emb),
                "norm": (1.0 + n(ks[2], (d,), std)).astype(dtype),
                "layers": jax.vmap(lambda i: _layer(
                    jax.random.fold_in(key, i), cfg, dtype))(jnp.arange(L))}
    return make(key_of(seed))


def program_tree(w):
    """The reference's layout -> `LlamaLM`'s parameter tree."""
    out = {"embed": w["embed"], "lm_head": w["lm_head"],
           "norm": {"weight": w["norm"]}}
    for i in range(w["layers"]["wq"].shape[0]):
        lw = {k: v[i] for k, v in w["layers"].items()}
        out[f"l{i}"] = {
            "ln1": {"weight": lw["ln1"]}, "ln2": {"weight": lw["ln2"]},
            "attn": {k: lw[k] for k in ("wq", "wk", "wv", "wo")},
            "gate": {"weight": lw["gate"]}, "up": {"weight": lw["up"]},
            "down": {"weight": lw["down"]}}
    return out


def program_params(seed, cfg, dtype=jnp.float32):
    return jax.jit(program_tree)(stacked(seed, cfg, dtype))


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (B, H, T, hd): the two halves of a head rotated by position."""
    hd, T = x.shape[-1], x.shape[-2]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _block(x, lw, cfg, mode):
    mm = reference.mm(mode)
    d, H, KV, hd, _ = _dims(cfg)
    B, T, _ = x.shape
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    prec = None if mode == "bfloat16" else jax.lax.Precision.HIGHEST
    h = _rms(x, lw["ln1"], eps)
    heads = lambda a, n: a.reshape(B, T, n, hd).transpose(0, 2, 1, 3)  # noqa: E731
    q = _rope(heads(mm(h, lw["wq"]), H), theta)
    k = _rope(heads(mm(h, lw["wk"]), KV), theta)
    v = heads(mm(h, lw["wv"]), KV)
    k, v = (jnp.repeat(a, H // KV, axis=1) for a in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=prec) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    a = jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=prec)
    x = x + mm(a.transpose(0, 2, 1, 3).reshape(B, T, d), lw["wo"])
    h = _rms(x, lw["ln2"], eps)
    return x + mm(jax.nn.silu(mm(h, lw["gate"])) * mm(h, lw["up"]),
                  lw["down"])


def logits(w, cfg, tokens, mode="float32"):
    dtype = jnp.bfloat16 if mode == "bfloat16" else jnp.float32
    w = jax.tree.map(lambda a: a.astype(dtype), w)
    x = w["embed"][tokens]
    x, _ = jax.lax.scan(lambda x, lw: (_block(x, lw, cfg, mode), None), x,
                        w["layers"])
    return reference.mm(mode)(_rms(x, w["norm"], cfg["rms_norm_eps"]),
                              w["lm_head"].T)


def _matrix_parameters(cfg):
    d, H, KV, hd, ff = _dims(cfg)
    return cfg["num_hidden_layers"] * (2 * d * d + 2 * d * KV * hd
                                       + 3 * d * ff)


def serve_token_flops(cfg, position, logits):
    d = cfg["hidden_size"]
    return (2.0 * _matrix_parameters(cfg)
            + (2.0 * cfg["vocab_size"] * d if logits else 0.0)
            + 4.0 * d * (position + 1) * cfg["num_hidden_layers"])


def train_token_flops(cfg, seq):
    d = cfg["hidden_size"]
    return (6.0 * _matrix_parameters(cfg) + 6.0 * cfg["vocab_size"] * d
            + 6.0 * cfg["num_hidden_layers"] * d * seq)
