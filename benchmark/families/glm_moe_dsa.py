"""The GLM-5 family (`model_type` `glm_moe_dsa`): everything of the
benchmark that knows it.

A pre-norm decoder, `h = x + Attn(RMSNorm(x))`, `out = h + MLP(RMSNorm(h))`,
a final RMSNorm, an untied head, eps `rms_norm_eps`, no bias. The equations
below are written from the published `config.json` and the publications it
follows, with nothing imported from the program; what the config does not
state is listed under `assumed` in the configuration file.

* Attention (multi-head latent attention, DeepSeek-V2, arXiv:2405.04434):
  `cQ = RMSNorm(W_dq x)`; `q_i = W_uq,i cQ = [qC_i (nope) ; qR_i (rope)]`;
  `[cKV ; kR] = W_dkv x`, `cKV <- RMSNorm(cKV)`; rotary embedding (theta
  `rope_parameters.rope_theta`, interleaved pairs) on every `qR_i` and on
  `kR`, which all heads share; `[kC_i ; v_i] = W_ukv,i cKV`; score of head i,
  query t, key s: `(qC_i . kC_i,s + qR_i . kR_s) / sqrt(nope + rope)`;
  softmax over the admitted `s <= t`; output `W_o concat_i(sum_s p v_i,s)`.
* Indexer of a `full` layer (DeepSeek-V3.2's sparse attention): `qI = W_iq
  cQ` in `index_n_heads` heads of `index_head_dim`, `kI_s = LayerNorm(W_ik
  x_s)`, rotary embedding on the first `qk_rope_head_dim` dims of both,
  `w_t = W_iw x_t * index_n_heads^-1/2 * index_head_dim^-1/2`; `I(t, s) =
  sum_j w_t,j relu(qI_t,j . kI_s)`; admitted to query t: the `index_topk`
  largest `I(t, s)` over `s <= t`, all of them while there are no more. A
  `shared` layer has no indexer and admits what the nearest `full` layer
  before it admitted.
* MLP of a `dense` layer: `W_down(silu(W_gate x) * W_up x)`. Of a `sparse`
  one (DeepSeek-V3, arXiv:2412.19437): `s = sigmoid(W_g x)` over all routed
  experts; chosen = the `num_experts_per_tok` largest of `s + b` (`b` the
  `noaux_tc` correction bias; `n_group` 1: no group limit); weights
  `s[chosen] / sum s[chosen] * routed_scaling_factor`; `y = sum_chosen w_e
  Expert_e(x) + Expert_shared(x)`, every expert the same SwiGLU. This chip
  holds experts `[share.experts_first, + n_routed_experts)` of
  `published.n_routed_experts`: the router is as wide as published, only
  the held experts' terms (and the shared expert's) are added, here and in
  the program alike, and that partial sum goes on to the next layer.

How `logits` is computed so that 29,696 positions fit one chip, each an
exact re-association of the products above and none an approximation:
queries go in blocks of `QUERY_BLOCK` (the MLPs in blocks of `TOKEN_BLOCK`
tokens); a block's `I(t, .)` is taken against
every key, its `index_topk` positions by `lax.top_k`, and only those rows
of `[cKV ; kR]` are gathered and attended, with `W_uk,i` applied to the
query (`qC_i . W_uk,i cKV = (W_uk,i^T qC_i) . cKV`) and `W_uv,i` after the
weighted sum, since keys and values a head for 2,048 rows a query would
not fit; a layer's weights are upcast to float32 while it runs, an expert at
a time. benchmark/tests/test_glm_moe_dsa.py holds this forward pass to the
program's `apply`, which expands keys and values a head and masks.

The weights: normal(0, `init.std` or 0.02) matrices and tables; every norm
weight 1 plus such a number, the LayerNorm's bias such a number, so that a
dropped one is caught; the router's correction bias normal(0,
`ROUTER_BIAS_STD`): with the routers' logits of spread `0.02 sqrt(6144) =
1.6` that makes the most chosen expert 2.5 times as busy as the mean one and
the least chosen nearly idle (uneven routing, as a trained router's is).
The biases are drawn from `BIAS_KEY` and a layer's index, the SAME for every
seed: which experts are popular decides how many of the held ones get a
token in a step, that is how many matrices the grouped product reads, and
with a bias from the seed the decode step took 20.7 to 21.5 ms by the seed
(PERF.md section 6, PR 31). As with the traffic's sizes, two seeds differ
in every other weight and in which request meets which, not in how much
work there is. Program and reference derive every leaf from the seed with
the same per-layer keys (`program_params`, `stacked`).
"""

import math

import jax
import jax.numpy as jnp

import reference
from weights import init_std, key_of

FULL, SHARED, DENSE, SPARSE = "full", "shared", "dense", "sparse"
IMPLEMENTED = {"model_type": "glm_moe_dsa", "hidden_act": "silu",
               "tie_word_embeddings": False, "attention_bias": False,
               "scoring_func": "sigmoid", "topk_method": "noaux_tc",
               "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
               "rope_interleave": True, "indexer_rope_interleave": True}
# The arithmetic in which `logits` can be computed (`reference.mm`).
MODES = ("float32", "fp8")
ROUTER_BIAS_STD = 0.02
BIAS_KEY = 0xB1A5           # of every seed's correction biases
QUERY_BLOCK = 64
TOKEN_BLOCK = 512        # of the MLPs; whole blocks of queries
_HI = jax.lax.Precision.HIGHEST


def check(cfg):
    for key, have in IMPLEMENTED.items():
        if cfg.get(key) != have:
            raise ValueError(f"configuration states {key}={cfg.get(key)!r}; "
                             f"the family glm_moe_dsa implements {have!r} "
                             f"only")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] \
            + cfg["qk_rope_head_dim"]:
        raise ValueError("the family glm_moe_dsa implements as many key "
                         "heads as query heads, of qk_nope_head_dim + "
                         "qk_rope_head_dim, only")
    ks = kinds(cfg)
    if len(ks) != cfg["num_hidden_layers"] or ks[0][0] != FULL \
            or {k for k, _ in ks} - {FULL, SHARED} \
            or {m for _, m in ks} - {DENSE, SPARSE}:
        raise ValueError(
            f"indexer_types and mlp_layer_types must name {FULL!r} or "
            f"{SHARED!r} and {DENSE!r} or {SPARSE!r} for each of the "
            f"num_hidden_layers held from first_hidden_layer on, the first "
            f"of them {FULL!r}")
    m = _dims(cfg)
    if m["first"] + m["n"] > m["E"] or m["k"] > m["E"]:
        raise ValueError("the experts held are no share of the published "
                         "n_routed_experts")


def sizes(cfg):
    """What the general files need to know of a configuration's sizes. The
    vocabulary is the slice this chip holds: the traffic draws its ids from
    it."""
    return {"vocab": cfg["vocab_size"],
            "positions": cfg["max_position_embeddings"]}


def kinds(cfg):
    """(indexer type, MLP type) of each layer that is held: the published
    lists from `first_hidden_layer` on."""
    a = cfg.get("first_hidden_layer", 0)
    b = a + cfg["num_hidden_layers"]
    return tuple(zip(cfg["indexer_types"][a:b], cfg["mlp_layer_types"][a:b]))


def _dims(cfg):
    share, published = cfg.get("share", {}), cfg.get("published", {})
    return {"d": cfg["hidden_size"], "ff": cfg["intermediate_size"],
            "H": cfg["num_attention_heads"], "qr": cfg["q_lora_rank"],
            "kvr": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "Hi": cfg["index_n_heads"], "Di": cfg["index_head_dim"],
            "topk": cfg["index_topk"], "f": cfg["moe_intermediate_size"],
            # the router's width, the experts held and the first of them
            "E": published.get("n_routed_experts", cfg["n_routed_experts"]),
            "n": cfg["n_routed_experts"],
            "first": share.get("experts_first", 0),
            "k": cfg["num_experts_per_tok"], "ns": cfg["n_shared_experts"],
            "V": published.get("vocab_size", cfg["vocab_size"]),
            "v_first": share.get("vocab_first", 0),
            "theta": float(cfg["rope_parameters"]["rope_theta"]),
            "scaling": float(cfg["routed_scaling_factor"])}


def build_model(cfg):
    """The program's model for a configuration's sizes, and its eos id."""
    from bigdl_tpu.interop.glm_moe_dsa import GlmMoeDsaLM
    check(cfg)
    m, eos = _dims(cfg), cfg["vocab_size"] - 1
    attention = {"num_heads": m["H"], "q_lora_rank": m["qr"],
                 "kv_lora_rank": m["kvr"], "qk_nope_head_dim": m["dn"],
                 "qk_rope_head_dim": m["dr"], "v_head_dim": m["dv"],
                 "index_topk": m["topk"], "rope_theta": m["theta"],
                 "indexer": {"heads": m["Hi"], "head_dim": m["Di"]}}
    experts = {"d_expert": m["f"], "num_experts": m["E"], "top_k": m["k"],
               "num_shared": m["ns"], "scaling": m["scaling"]}
    ks = kinds(cfg)
    return GlmMoeDsaLM(
        m["V"], m["d"], m["ff"], [k for k, _ in ks], [p for _, p in ks],
        attention, experts, cfg["max_position_embeddings"],
        eps=cfg["rms_norm_eps"], expert_share=(m["first"], m["n"]),
        vocab_share=(m["v_first"], cfg["vocab_size"]), eos_id=eos,
        param_dtype=jnp.dtype(cfg["weights_dtype"])), eos


# ------------------------------------------------------------- weights
def _layer(key, kind, cfg, dtype, index=0):
    """One block's leaves, named as the reference uses them; `index`
    counts the layers held."""
    m, std = _dims(cfg), init_std(cfg)[0]
    d, H = m["d"], m["H"]
    ks = iter(jax.random.split(key, 32))
    n = lambda shape, s=std: (s * jax.random.normal(           # noqa: E731
        next(ks), shape, jnp.float32)).astype(dtype)
    one = lambda shape: (1.0 + std * jax.random.normal(        # noqa: E731
        next(ks), shape, jnp.float32)).astype(dtype)
    out = {"attn_norm": one((d,)), "mlp_norm": one((d,)),
           "wq_a": n((d, m["qr"])), "q_norm": one((m["qr"],)),
           "wq_b": n((m["qr"], H * (m["dn"] + m["dr"]))),
           "wkv_a": n((d, m["kvr"] + m["dr"])), "kv_norm": one((m["kvr"],)),
           "wkv_b": n((m["kvr"], H * (m["dn"] + m["dv"]))),
           "wo": n((H * m["dv"], d))}
    if kind[0] == FULL:
        out.update({"iq": n((m["qr"], m["Hi"] * m["Di"])),
                    "ik": n((d, m["Di"])), "ik_norm_w": one((m["Di"],)),
                    "ik_norm_b": n((m["Di"],)), "iw": n((d, m["Hi"]))})
    if kind[1] == DENSE:
        out.update({"gate": n((d, m["ff"])), "up": n((d, m["ff"])),
                    "down": n((m["ff"], d))})
    else:
        f, fs = m["f"], m["f"] * m["ns"]
        out.update({"router": n((d, m["E"])),
                    "router_bias": (ROUTER_BIAS_STD * jax.random.normal(
                        jax.random.fold_in(jax.random.PRNGKey(BIAS_KEY),
                                           index),
                        (m["E"],), jnp.float32)).astype(dtype),
                    "e_gate": n((m["n"], d, f)), "e_up": n((m["n"], d, f)),
                    "e_down": n((m["n"], f, d)), "s_gate": n((d, fs)),
                    "s_up": n((d, fs)), "s_down": n((fs, d))})
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


_BUILT = {}


def _built(which, cfg, dtype, make):
    """One jitted maker for each layout, sizes and dtype."""
    sk = (which, cfg["vocab_size"], kinds(cfg),
          tuple(sorted(_dims(cfg).items())), init_std(cfg),
          _dtype(cfg, dtype).name)
    if sk not in _BUILT:
        _BUILT[sk] = jax.jit(make)
    return _BUILT[sk]


def _stacked_of(key, cfg, dtype):
    dt = _dtype(cfg, dtype)
    return dict(_top(key, cfg, dt), layers=[
        _layer(jax.random.fold_in(key, i), kind, cfg, dt, i)
        for i, kind in enumerate(kinds(cfg))])


def stacked(seed, cfg, dtype=None):
    """The reference's layout: the top-level leaves plus `layers`, one dict
    of the reference's names a layer held (three kinds of layer, so nothing
    is stacked). In the configuration's `weights_dtype` unless told
    otherwise. One jitted call."""
    return _built("stacked", cfg, dtype,
                  lambda key: _stacked_of(key, cfg, dtype))(key_of(seed))


def to_program(layer):
    """One block of the reference's names -> one `l<i>` of `GlmMoeDsaLM`."""
    lin = lambda name: {"weight": layer[name]}                 # noqa: E731
    attn = {"q_a": lin("wq_a"), "q_norm": lin("q_norm"), "q_b": lin("wq_b"),
            "kv_a": lin("wkv_a"), "kv_norm": lin("kv_norm"),
            "kv_b": lin("wkv_b"), "o": lin("wo")}
    if "iq" in layer:
        attn.update({"iq": lin("iq"), "ik": lin("ik"), "iw": lin("iw"),
                     "ik_norm": {"weight": layer["ik_norm_w"],
                                 "bias": layer["ik_norm_b"]}})
    out = {"attn": attn, "attn_norm": lin("attn_norm"),
           "mlp_norm": lin("mlp_norm")}
    if "gate" in layer:
        out.update({"gate": lin("gate"), "up": lin("up"),
                    "down": lin("down")})
    else:
        out["experts"] = {
            "router": layer["router"], "router_bias": layer["router_bias"],
            "gate": layer["e_gate"], "up": layer["e_up"],
            "down": layer["e_down"], "shared_gate": lin("s_gate"),
            "shared_up": lin("s_up"), "shared_down": lin("s_down")}
    return out


def program_tree(w):
    """A stacked tree in the program's layout, for comparing leaf by
    leaf."""
    out = {"embed": w["embed"], "lm_head": w["lm_head"],
           "norm": {"weight": w["norm"]}}
    for i, layer in enumerate(w["layers"]):
        out[f"l{i}"] = to_program(layer)
    return out


def program_params(seed, cfg, dtype=None):
    """The same values in `GlmMoeDsaLM`'s parameter tree, made on the
    device in one jitted call."""
    return _built(
        "program", cfg, dtype,
        lambda key: program_tree(_stacked_of(key, cfg, dtype)))(key_of(seed))


# ----------------------------------------------------------- reference
def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _rope(x, positions, theta):
    """Interleaved pairs (x[2i], x[2i+1]) turned by positions *
    theta^(-2i/r). x (T, r) or (T, H, r); positions (T,)."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = positions.astype(jnp.float32)[:, None] * inv
    if x.ndim == 3:
        ang = ang[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def _in_blocks(f, block, *arrays):
    """`f` over blocks of `block` rows of the arrays (all of T rows), a
    block after another; T is `block` times a whole number, or one block."""
    T = arrays[0].shape[0]
    if T <= block:
        return f(jnp.zeros((), jnp.int32), *arrays)
    n = T // block
    out = jax.lax.map(lambda at: f(*at), (jnp.arange(n) * block,) + tuple(
        a.reshape((n, block) + a.shape[1:]) for a in arrays))
    return jax.tree.map(lambda a: a.reshape((T,) + a.shape[2:]), out)


def _attention(x, lw, cfg, mode, selection):
    """One sequence's normed x (T, d) -> (W_o attention (T, d), selection
    (T, K) int32): the positions admitted to each query, made here by a
    `full` layer and handed on to the `shared` ones."""
    mm, m, eps = reference.mm(mode), _dims(cfg), cfg["rms_norm_eps"]
    T, H, dn, dr, dv, kvr = (x.shape[0], m["H"], m["dn"], m["dr"], m["dv"],
                             m["kvr"])
    r8 = reference.round_fp8 if mode == "fp8" else (lambda a: a)
    pos = jnp.arange(T)
    # what every key leaves behind, all of them before any query
    kv = mm(x, lw["wkv_a"])
    rows = r8(jnp.concatenate([_rms(kv[:, :kvr], lw["kv_norm"], eps),
                               _rope(kv[:, kvr:], pos, m["theta"])], -1))
    w_ukv = r8(lw["wkv_b"].reshape(kvr, H, dn + dv))
    full = "iq" in lw
    if full:
        k_idx = _layer_norm(mm(x, lw["ik"]), lw["ik_norm_w"],
                            lw["ik_norm_b"], eps)
        k_idx = r8(jnp.concatenate([_rope(k_idx[:, :dr], pos, m["theta"]),
                                    k_idx[:, dr:]], -1))
    K = min(m["topk"], T)

    def block(start, x_b, sel):
        blk = x_b.shape[0]
        p_b = start + jnp.arange(blk)
        cq = _rms(mm(x_b, lw["wq_a"]), lw["q_norm"], eps)
        if full:
            q_idx = mm(cq, lw["iq"]).reshape(blk, m["Hi"], m["Di"])
            q_idx = r8(jnp.concatenate([
                _rope(q_idx[..., :dr], p_b, m["theta"]), q_idx[..., dr:]],
                -1))
            w_b = mm(x_b, lw["iw"]) * (m["Hi"] ** -0.5 * m["Di"] ** -0.5)
            s = jnp.einsum("qhd,kd->qhk", q_idx, k_idx, precision=_HI)
            score = jnp.einsum("qhk,qh->qk", jax.nn.relu(s), w_b,
                               precision=_HI)
            seen = pos[None, :] <= p_b[:, None]
            sel = jax.lax.top_k(jnp.where(seen, score, -jnp.inf), K)[1] \
                if K < T else jnp.broadcast_to(pos, (blk, T))
        q = mm(cq, lw["wq_b"]).reshape(blk, H, dn + dr)
        q_abs = r8(jnp.concatenate([
            jnp.einsum("qhc,lhc->qhl", r8(q[..., :dn]), w_ukv[..., :dn],
                       precision=_HI),
            _rope(q[..., dn:], p_b, m["theta"])], -1))
        got = rows[sel]                                     # (blk, K, W)
        s = jnp.einsum("qhw,qkw->qhk", q_abs, got, precision=_HI) \
            / math.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where((sel <= p_b[:, None])[:, None, :], s,
                                     -jnp.inf), axis=-1)
        o = jnp.einsum("qhk,qkc->qhc", r8(p), got[..., :kvr], precision=_HI)
        v = jnp.einsum("qhl,lhv->qhv", r8(o), w_ukv[..., dn:],
                       precision=_HI)
        return mm(v.reshape(blk, H * dv), lw["wo"]), sel

    if selection is None:
        selection = jnp.zeros((T, K), jnp.int32)
    return _in_blocks(block, QUERY_BLOCK, x, selection)


def _mlp(y, lw, cfg, mode):
    """The MLP of normed y (blk, d). Sparse: the held experts' part, every
    held expert over every token with its term weighted by 0 where the
    router did not choose it (an expert upcast to float32 at a time), and
    the shared expert's."""
    mm, m = reference.mm(mode), _dims(cfg)

    def swiglu(gate, up, down):
        return mm(jax.nn.silu(mm(y, gate)) * mm(y, up), down)
    if "gate" in lw:
        return swiglu(lw["gate"], lw["up"], lw["down"])
    s = jax.nn.sigmoid(jnp.matmul(y, lw["router"], precision=_HI))
    chosen = jax.lax.top_k(s + lw["router_bias"], m["k"])[1]
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / jnp.sum(w, -1, keepdims=True) * m["scaling"]
    # (blk, E) weights, zero off the chosen ones; this chip's columns
    dense = jnp.zeros_like(s).at[jnp.arange(y.shape[0])[:, None],
                                 chosen].set(w)
    mine = jax.lax.dynamic_slice_in_dim(dense, m["first"], m["n"], axis=1)
    f32 = lambda a: a.astype(jnp.float32)                      # noqa: E731

    def one(total, at):
        gate, up, down, w_e = at
        return total + w_e[:, None] * swiglu(f32(gate), f32(up),
                                             f32(down)), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (lw["e_gate"], lw["e_up"], lw["e_down"], mine.T))
    return routed + swiglu(lw["s_gate"], lw["s_up"], lw["s_down"])


def _block(x, lw, cfg, mode, selection):
    eps = cfg["rms_norm_eps"]
    f32 = lambda t: jax.tree.map(                              # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    routed = {k: lw[k] for k in ("e_gate", "e_up", "e_down") if k in lw}
    lw = dict(f32({k: v for k, v in lw.items() if k not in routed}),
              **routed)
    a, selection = _attention(_rms(x, lw["attn_norm"], eps), lw, cfg, mode,
                              selection)
    h = x + a
    return _in_blocks(
        lambda _, h_b: h_b + _mlp(_rms(h_b, lw["mlp_norm"], eps), lw, cfg,
                                  mode), TOKEN_BLOCK, h), selection


def logits(w, cfg, tokens, mode="float32"):
    """tokens (B, T) int32 -> (B, T, vocab) float32 logits over the
    vocabulary rows held, a sequence after another. A sequence longer than
    one block of queries is padded to whole blocks of TOKEN_BLOCK tokens
    behind its end, where no token before sees it."""
    f32 = lambda a: a.astype(jnp.float32)                      # noqa: E731
    T = tokens.shape[1]
    pad = -T % TOKEN_BLOCK if T > QUERY_BLOCK else 0

    def one(row):
        x, selection = f32(w["embed"][jnp.pad(row, (0, pad))]), None
        for lw in w["layers"]:
            x, selection = _block(x, lw, cfg, mode, selection)
        x = _rms(x[:T], f32(w["norm"]), cfg["rms_norm_eps"])
        return reference.mm(mode)(x, f32(w["lm_head"]).T)
    return jax.lax.map(one, tokens)


# --------------------------------------------------------------- FLOPs
def parameters(cfg, layer_kinds=None, experts=None, vocab=None):
    """Parameter counts from the sizes alone, of the layers held with the
    experts and vocabulary rows held (or of `layer_kinds`, `experts` routed
    experts a layer and `vocab` rows, as for the whole published model)."""
    m = _dims(cfg)
    d, H = m["d"], m["H"]
    n = m["n"] if experts is None else experts
    V = cfg["vocab_size"] if vocab is None else vocab
    mla = (d * m["qr"] + m["qr"] + m["qr"] * H * (m["dn"] + m["dr"])
           + d * (m["kvr"] + m["dr"]) + m["kvr"]
           + m["kvr"] * H * (m["dn"] + m["dv"]) + H * m["dv"] * d)
    indexer = m["qr"] * m["Hi"] * m["Di"] + d * m["Di"] + 2 * m["Di"] \
        + d * m["Hi"]
    expert = 3 * d * m["f"]
    router = d * m["E"] + m["E"]
    per = {"mla": mla, "indexer": indexer, "expert": expert,
           "shared_expert": expert * m["ns"], "router": router,
           "dense_mlp": 3 * d * m["ff"]}
    ks = kinds(cfg) if layer_kinds is None else layer_kinds
    layers = d + sum(
        2 * d + mla + (indexer if k == FULL else 0)
        + (per["dense_mlp"] if p == DENSE
           else router + per["shared_expert"] + n * expert)
        for k, p in ks)
    return dict(per, layers=layers, embedding=V * d, head=V * d,
                total=layers + 2 * V * d)


_FLOPS = {}


def _flop_terms(cfg):
    """What a computed token costs, in the terms the counters count."""
    key = id(cfg)
    if key not in _FLOPS:
        m, p, ks = _dims(cfg), parameters(cfg, experts=0), kinds(cfg)
        _FLOPS[key] = {
            # every matrix a token meets whatever it is routed to
            "token": 2.0 * p["layers"], "logits": 2.0 * p["head"],
            "pair": 2.0 * p["expert"],
            # absorbed attention over one admitted row, all heads and layers
            "attended": 2.0 * m["H"] * (2 * m["kvr"] + m["dr"]) * len(ks),
            # the indexer's score of one earlier token, all full layers
            "context": 2.0 * m["Hi"] * (m["Di"] + 1)
            * sum(k == FULL for k, _ in ks),
            # token-expert pairs a token makes here at the expectation
            "pairs": sum(p_ == SPARSE for _, p_ in ks) * m["k"] * m["n"]
            / m["E"],
            "topk": m["topk"]}
    return _FLOPS[key]


def serve_token_flops(cfg, position, logits):
    """FLOPs the model needs to process one token at `position` (counting
    from 0) through the cache, the experts' load at its expectation
    (`num_experts_per_tok` x held / published pairs a sparse layer)."""
    t = _flop_terms(cfg)        # (called once a token of a window)
    seen = position + 1
    return (t["token"] + (t["logits"] if logits else 0.0)
            + t["pair"] * t["pairs"]
            + t["attended"] * min(seen, t["topk"]) + t["context"] * seen)


def counted_flops(cfg, counted):
    """FLOPs the model needs for the tokens the program COMPUTED, from what
    the decode scheduler counted (`counted`: `prefill_tokens`, `step_tokens`
    = rows of decode steps, `attended_tokens`, `context_tokens`,
    `expert_pairs`): a prompt token that came from the prefix cache was not
    computed and is not in them."""
    t = _flop_terms(cfg)
    return (t["token"] * (counted["prefill_tokens"] + counted["step_tokens"])
            + t["logits"] * counted["step_tokens"]
            + t["pair"] * counted["expert_pairs"]
            + t["attended"] * counted["attended_tokens"]
            + t["context"] * counted["context_tokens"])


def kernel_work(cfg, kernel, counted=None, **_):
    """The least work the model asks of a mechanism for what the scheduler
    counted over a span (`counted`, as for `counted_flops`, with
    `step_context_tokens` and `expert_loads`), from shapes.
    `sparse_attend`: the indexer's scores and the attention over the
    admitted rows of every computed token; the bytes are each admitted row
    of `[cKV ; kR]` read once a query and layer, and the index keys of a
    decode step's context read once a row and full layer (a prompt chunk
    reads them once for all its queries: left out). `expert_ffn`: the
    grouped product's FLOPs from the pairs, and the matrices of every expert
    that got a token in a call, read once."""
    if not counted:
        return None
    t, m, ks = _flop_terms(cfg), _dims(cfg), kinds(cfg)
    itemsize = jnp.dtype(cfg["weights_dtype"]).itemsize
    if kernel == "sparse_attend":
        n_full = sum(k == FULL for k, _ in ks)
        return {"flops": t["attended"] * counted["attended_tokens"]
                + t["context"] * counted["context_tokens"],
                "bytes": float(itemsize) * (
                    (m["kvr"] + m["dr"]) * len(ks)
                    * counted["attended_tokens"]
                    + m["Di"] * n_full * counted["step_context_tokens"])}
    if kernel == "expert_ffn":
        return {"flops": t["pair"] * counted["expert_pairs"],
                "bytes": float(itemsize) * 3 * m["d"] * m["f"]
                * counted["expert_loads"]}
    return None
