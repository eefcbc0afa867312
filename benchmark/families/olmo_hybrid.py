"""The Olmo-Hybrid family (`model_type` `olmo_hybrid`): everything of the
benchmark that knows it.

A decoder whose blocks alternate, as `layer_types` says, between a gated
delta-rule linear-attention mixer and full softmax attention. The equations
below are written from the sources, with nothing imported from the program;
what the published `config.json` does not state is the family's convention
and is listed under `assumed` in the configuration file.

* Linear mixer (Yang, Kautz, Hatamizadeh, "Gated Delta Networks",
  arXiv:2412.06464). For the block's input `x_t`: `q~, k~, v~ = W_q x, W_k x,
  W_v x` (H heads of `dk`, `dk`, `dv`); each channel passes a causal
  depthwise convolution of width K over time (zeros before position 0;
  `out[t] = sum_i conv[c, i] in[t - (K-1) + i]`) and SiLU; `q_t`, `k_t` are
  L2-normalised per head (`x / sqrt(sum x^2 + 1e-6)`) and `q_t` scaled by
  `dk^-1/2`; `beta_t = 2 sigmoid(W_b x_t)` per head (the 2 is
  `linear_allow_neg_eigval`); `g_t = -exp(A_log) softplus(W_a x_t +
  dt_bias)`. Per head, from `S_0 = 0` in R^(dk x dv), token by token:
  `S' = exp(g_t) S_(t-1)`; `u_t = beta_t (v_t - S'^T k_t)`; `S_t = S' + k_t
  u_t^T`; `o_t = S_t^T q_t`. Output `W_o [RMSNorm_dv(o_t) * silu(W_g x_t)]`,
  the norm per head with one learned weight of `dv`.
* Full mixer: `q, k, v = W_q x, W_k x, W_v x`, an RMSNorm over the whole
  width of `q` and of `k`, no rotary embedding (`rope_theta` null), causal
  softmax attention over `num_attention_heads` heads scaled by `hd^-1/2`,
  `W_o`.
* Block: `h = x + RMSNorm(mixer(x))`, `out = h + RMSNorm(W_down(silu(W_gate
  h) * W_up h))`; a final RMSNorm; an untied head.

The weights: normal(0, `init.std` or 0.02) matrices and tables; the
convolution uniform in +-K^-1/2; `A_log = log(a)`, `a` uniform in [1, 16);
`dt_bias` the inverse softplus of a `dt` log-uniform in [0.001, 0.1) (the
publication's initialisation of the layer); every norm weight 1 plus a small
random number, so that a dropped one is caught. Program and reference derive
every leaf from the seed with the same per-layer keys (`program_params`,
`stacked`); a test holds the two layouts equal bit for bit. `stacked` holds
the configuration's `weights_dtype` values (bfloat16 values are exact in
float32) and `logits` upcasts one period of layers at a time inside its
scan, so that the float32 reference of an 8 GB model fits one chip.
"""

import math

import jax
import jax.numpy as jnp

import reference
from weights import init_std, key_of

LINEAR, FULL = "linear_attention", "full_attention"
IMPLEMENTED = {"model_type": "olmo_hybrid", "hidden_act": "silu",
               "tie_word_embeddings": False, "attention_bias": False,
               "linear_allow_neg_eigval": True}
# The arithmetic in which `logits` can be computed (`reference.mm`).
MODES = ("float32", "fp8")
L2_EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST


def check(cfg):
    for key, have in IMPLEMENTED.items():
        if cfg.get(key) != have:
            raise ValueError(f"configuration states {key}={cfg.get(key)!r}; "
                             f"the family olmo_hybrid implements {have!r} "
                             f"only")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("the family olmo_hybrid implements as many key "
                         "heads as query (or value) heads only")
    if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("the family olmo_hybrid implements no rotary "
                         "embedding (rope_theta null) only")
    if len(cfg["layer_types"]) < cfg["num_hidden_layers"] \
            or set(kinds(cfg)) - {LINEAR, FULL}:
        raise ValueError(f"layer_types must name {LINEAR!r} or {FULL!r} "
                         f"for each of num_hidden_layers")


def sizes(cfg):
    """What the general files need to know of a configuration's sizes."""
    return {"vocab": cfg["vocab_size"],
            "positions": cfg["max_position_embeddings"]}


def kinds(cfg):
    """The mixer of each layer that is held: the first `num_hidden_layers`
    of the published `layer_types`."""
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def period(cfg):
    """The shortest pattern of layer kinds that the held layers repeat."""
    ks = kinds(cfg)
    return next(p for p in range(1, len(ks) + 1)
                if len(ks) % p == 0 and ks == ks[:p] * (len(ks) // p))


def _dims(cfg):
    return {"d": cfg["hidden_size"], "ff": cfg["intermediate_size"],
            "H": cfg["linear_num_value_heads"],
            "dk": cfg["linear_key_head_dim"],
            "dv": cfg["linear_value_head_dim"],
            "K": cfg["linear_conv_kernel_dim"],
            "heads": cfg["num_attention_heads"]}


def build_model(cfg):
    """The program's model for a configuration's sizes, and its eos id."""
    from bigdl_tpu.interop.olmo_hybrid import OlmoHybridLM
    check(cfg)
    m, eos = _dims(cfg), cfg["vocab_size"] - 1
    linear = {"num_heads": m["H"], "key_dim": m["dk"], "value_dim": m["dv"],
              "conv_kernel": m["K"],
              "allow_neg_eigval": cfg["linear_allow_neg_eigval"]}
    return OlmoHybridLM(
        cfg["vocab_size"], m["d"], m["heads"], m["ff"], kinds(cfg), linear,
        cfg["max_position_embeddings"], eps=cfg["rms_norm_eps"], eos_id=eos,
        param_dtype=jnp.dtype(cfg["weights_dtype"])), eos


# ------------------------------------------------------------- weights
def _layer(key, kind, cfg, dtype):
    """One block's leaves, named as the reference uses them."""
    m, std = _dims(cfg), init_std(cfg)[0]
    d, ff, H, dk, dv, K = (m[k] for k in ("d", "ff", "H", "dk", "dv", "K"))
    ks = iter(jax.random.split(key, 20))
    n = lambda shape: (std * jax.random.normal(                # noqa: E731
        next(ks), shape, jnp.float32)).astype(dtype)
    one = lambda shape: (1.0 + std * jax.random.normal(        # noqa: E731
        next(ks), shape, jnp.float32)).astype(dtype)
    out = {"mixer_norm": one((d,)), "mlp_norm": one((d,)),
           "gate": n((d, ff)), "up": n((d, ff)), "down": n((ff, d))}
    if kind == FULL:
        out.update({"wq": n((d, d)), "wk": n((d, d)), "wv": n((d, d)),
                    "wo": n((d, d)), "q_norm": one((d,)),
                    "k_norm": one((d,))})
        return out
    bound = 1.0 / math.sqrt(K)
    a = jax.random.uniform(next(ks), (H,), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(next(ks), (H,), jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    out.update({
        "wq": n((d, H * dk)), "wk": n((d, H * dk)), "wv": n((d, H * dv)),
        "wg": n((d, H * dv)), "wa": n((d, H)), "wb": n((d, H)),
        "wo": n((H * dv, d)), "o_norm": one((dv,)),
        "conv": jax.random.uniform(next(ks), (2 * H * dk + H * dv, K),
                                   jnp.float32, -bound, bound).astype(dtype),
        "A_log": jnp.log(a).astype(dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)})
    return out


def _top(key, cfg, dtype):
    d, (std, emb) = cfg["hidden_size"], init_std(cfg)
    ks = jax.random.split(jax.random.fold_in(key, 0x70F), 3)
    n = lambda k, shape, s: (s * jax.random.normal(            # noqa: E731
        k, shape, jnp.float32)).astype(dtype)
    return {"embed": n(ks[0], (cfg["vocab_size"], d), emb),
            "lm_head": n(ks[1], (cfg["vocab_size"], d), emb),
            "norm": (1.0 + n(ks[2], (d,), std)).astype(dtype)}


def _dtype(cfg, dtype):
    return jnp.dtype(cfg["weights_dtype"] if dtype is None else dtype)


def _shape_key(cfg, dtype):
    return (cfg["vocab_size"], kinds(cfg), tuple(sorted(_dims(cfg).items())),
            init_std(cfg), _dtype(cfg, dtype).name)


_BUILT = {}


def stacked(seed, cfg, dtype=None):
    """The reference's layout: the top-level leaves plus `periods`, one
    dict for each place in the period of layer kinds, its arrays with a
    leading axis of `num_hidden_layers / period` (layer `r * period + j`
    is row `r` of place `j`). In the configuration's `weights_dtype` unless
    told otherwise. One jitted call."""
    sk = ("stacked",) + _shape_key(cfg, dtype)
    if sk not in _BUILT:
        dt, ks, p = _dtype(cfg, dtype), kinds(cfg), period(cfg)

        def bench_weights_stacked(key):
            w = _top(key, cfg, dt)
            rows = jnp.arange(len(ks) // p)
            w["periods"] = [jax.vmap(lambda r, j=j: _layer(
                jax.random.fold_in(key, r * p + j), ks[j], cfg, dt))(rows)
                for j in range(p)]
            return w
        _BUILT[sk] = jax.jit(bench_weights_stacked)
    return _BUILT[sk](key_of(seed))


def to_program(layer, kind):
    """One block of the reference's names -> one `l<i>` of `OlmoHybridLM`."""
    lin = lambda name: {"weight": layer[name]}                 # noqa: E731
    if kind == FULL:
        mixer = {"q": lin("wq"), "k": lin("wk"), "v": lin("wv"),
                 "o": lin("wo"), "q_norm": lin("q_norm"),
                 "k_norm": lin("k_norm")}
    else:
        mixer = {"q": lin("wq"), "k": lin("wk"), "v": lin("wv"),
                 "g": lin("wg"), "a": lin("wa"), "b": lin("wb"),
                 "o": lin("wo"), "norm": lin("o_norm"),
                 "conv": layer["conv"], "A_log": layer["A_log"],
                 "dt_bias": layer["dt_bias"]}
    return {"mixer": mixer, "mixer_norm": lin("mixer_norm"),
            "gate": lin("gate"), "up": lin("up"), "down": lin("down"),
            "mlp_norm": lin("mlp_norm")}


def program_tree(w):
    """A stacked tree in the program's layout, for comparing leaf by
    leaf."""
    out = {"embed": w["embed"], "lm_head": w["lm_head"],
           "norm": {"weight": w["norm"]}}
    p = len(w["periods"])
    for j, place in enumerate(w["periods"]):
        kind = FULL if "q_norm" in place else LINEAR
        for r in range(place["gate"].shape[0]):
            out[f"l{r * p + j}"] = to_program(
                {k: v[r] for k, v in place.items()}, kind)
    return out


def program_params(seed, cfg, dtype=None):
    """The same values in `OlmoHybridLM`'s parameter tree, made on the
    device in one jitted call, leaf by leaf so that no stacked copy is ever
    alive."""
    sk = ("program",) + _shape_key(cfg, dtype)
    if sk not in _BUILT:
        dt, ks = _dtype(cfg, dtype), kinds(cfg)

        def bench_weights_program(key):
            t = _top(key, cfg, dt)
            out = {"embed": t["embed"], "lm_head": t["lm_head"],
                   "norm": {"weight": t["norm"]}}
            for i, kind in enumerate(ks):
                out[f"l{i}"] = to_program(_layer(
                    jax.random.fold_in(key, i), kind, cfg, dt), kind)
            return out
        _BUILT[sk] = jax.jit(bench_weights_program)
    return _BUILT[sk](key_of(seed))


# ----------------------------------------------------------- reference
def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _l2(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


def _linear_mixer(x, lw, cfg, mode):
    """The gated delta rule, the recurrence token by token."""
    mm, m = reference.mm(mode), _dims(cfg)
    H, dk, dv, K = m["H"], m["dk"], m["dv"], m["K"]
    B, T, _ = x.shape
    pre = jnp.concatenate([mm(x, lw["wq"]), mm(x, lw["wk"]),
                           mm(x, lw["wv"])], axis=-1)
    padded = jnp.pad(pre, ((0, 0), (K - 1, 0), (0, 0)))
    y = jax.nn.silu(sum(padded[:, i:i + T] * lw["conv"][:, i]
                        for i in range(K)))
    q = _l2(y[..., :H * dk].reshape(B, T, H, dk)) / math.sqrt(dk)
    k = _l2(y[..., H * dk:2 * H * dk].reshape(B, T, H, dk))
    v = y[..., 2 * H * dk:].reshape(B, T, H, dv)
    if mode == "fp8":
        q, k, v = (reference.round_fp8(a) for a in (q, k, v))
    beta = 2.0 * jax.nn.sigmoid(mm(x, lw["wb"]))
    g = -jnp.exp(lw["A_log"]) * jax.nn.softplus(mm(x, lw["wa"])
                                                + lw["dt_bias"])

    def token(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        S = jnp.exp(g_t)[..., None, None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t,
                                               precision=_HI))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HI)

    time_major = lambda a: jnp.moveaxis(a, 1, 0)               # noqa: E731
    _, o = jax.lax.scan(token, jnp.zeros((B, H, dk, dv), x.dtype),
                        tuple(time_major(a) for a in (q, k, v, g, beta)))
    o = _rms(time_major(o), lw["o_norm"], cfg["rms_norm_eps"])
    gated = o.reshape(B, T, H * dv) * jax.nn.silu(mm(x, lw["wg"]))
    return mm(gated, lw["wo"])


def _query_block(T):
    return next((b for b in (256, 128, 64) if T % b == 0 and T > b), T)


def _full_mixer(x, lw, cfg, mode):
    """Causal softmax attention with the QK-norm, in blocks of queries so
    that the scores of a long sequence fit."""
    mm, heads, eps = reference.mm(mode), cfg["num_attention_heads"], \
        cfg["rms_norm_eps"]
    B, T, d = x.shape
    hd = d // heads
    split = lambda a: a.reshape(B, T, heads, hd).transpose(0, 2, 1, 3)  # noqa: E731
    q = split(_rms(mm(x, lw["wq"]), lw["q_norm"], eps))
    k = split(_rms(mm(x, lw["wk"]), lw["k_norm"], eps))
    v = split(mm(x, lw["wv"]))
    if mode == "fp8":
        q, k, v = (reference.round_fp8(a) for a in (q, k, v))
    blk = _query_block(T)

    def attend(at):
        start, qb = at
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k, precision=_HI) \
            / math.sqrt(hd)
        seen = (start + jnp.arange(blk))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        if mode == "fp8":
            p = reference.round_fp8(p)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=_HI)

    qs = jnp.moveaxis(q.reshape(B, heads, T // blk, blk, hd), 2, 0)
    a = jax.lax.map(attend, (jnp.arange(T // blk) * blk, qs))
    a = jnp.moveaxis(a, 0, 2).reshape(B, heads, T, hd)
    return mm(a.transpose(0, 2, 1, 3).reshape(B, T, d), lw["wo"])


def _block(x, lw, kind, cfg, mode):
    mm, eps = reference.mm(mode), cfg["rms_norm_eps"]
    mixer = _full_mixer if kind == FULL else _linear_mixer
    h = x + _rms(mixer(x, lw, cfg, mode), lw["mixer_norm"], eps)
    f = mm(jax.nn.silu(mm(h, lw["gate"])) * mm(h, lw["up"]), lw["down"])
    return h + _rms(f, lw["mlp_norm"], eps)


def logits(w, cfg, tokens, mode="float32"):
    """tokens (B, T) int32 -> (B, T, vocab) float32 logits. The periods of
    layers are scanned and each is upcast to float32 only while it runs."""
    f32 = lambda t: jax.tree.map(                              # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    ks = kinds(cfg)[:len(w["periods"])]

    def one_period(x, places):
        for kind, lw in zip(ks, places):
            x = _block(x, f32(lw), kind, cfg, mode)
        return x, None

    x, _ = jax.lax.scan(one_period, f32(w["embed"][tokens]), w["periods"])
    x = _rms(x, f32(w["norm"]), cfg["rms_norm_eps"])
    return reference.mm(mode)(x, f32(w["lm_head"]).T)


# --------------------------------------------------------------- FLOPs
def parameters(cfg, layer_kinds=None):
    """Parameter counts from the sizes alone, of the layers held (or of
    `layer_kinds`, as for the whole published `layer_types`)."""
    m = _dims(cfg)
    d, ff, H, dk, dv, K = (m[k] for k in ("d", "ff", "H", "dk", "dv", "K"))
    mlp = 3 * d * ff + 2 * d
    per = {FULL: 4 * d * d + 2 * d + mlp,
           LINEAR: (2 * d * H * dk + 2 * d * H * dv + H * dv * d + 2 * d * H
                    + (2 * H * dk + H * dv) * K + 2 * H + dv + mlp)}
    ks = kinds(cfg) if layer_kinds is None else layer_kinds
    layers = sum(per[k] for k in ks) + d
    table = cfg["vocab_size"] * d
    return {"linear_layer": per[LINEAR], "full_layer": per[FULL],
            "layers": layers, "embedding": table, "head": table,
            "total": layers + 2 * table}


def serve_token_flops(cfg, position, logits):
    """FLOPs the model needs to process one token at `position` (counting
    from 0) through the cache: 2 a parameter outside the two tables, the
    head where logits are taken, `4 d live` for each full layer held (its
    scores and its weighted sum over the live positions) and `7 H dk dv` for
    each linear layer held (the recurrence: the decay, `S^T k`, the rank-one
    update, `S^T q`)."""
    p, m, ks = parameters(cfg), _dims(cfg), kinds(cfg)
    return (2.0 * p["layers"] + (2.0 * p["head"] if logits else 0.0)
            + 4.0 * m["d"] * (position + 1) * ks.count(FULL)
            + 7.0 * m["H"] * m["dk"] * m["dv"] * ks.count(LINEAR))


def kernel_work(cfg, kernel, tokens=(), **_):
    """The least work the model asks of a mechanism for what a traced span
    processed, from shapes. `linear_state`: the recurrence's FLOPs for each
    token (`7 H dk dv` a linear layer) and the float32 state of its
    sequence read and written once a decode token (one with logits) and
    once a prefill chunk of `register.prefill_chunk` tokens, in each linear
    layer."""
    if kernel != "linear_state":
        return None
    m, n_linear = _dims(cfg), kinds(cfg).count(LINEAR)
    state = m["H"] * m["dk"] * m["dv"]
    decode = sum(1 for _, with_logits in tokens if with_logits)
    prefill = len(tokens) - decode
    passes = decode + prefill / float(cfg["register"]["prefill_chunk"])
    return {"flops": 7.0 * state * n_linear * (decode + prefill),
            "bytes": 2.0 * 4 * state * n_linear * passes}
