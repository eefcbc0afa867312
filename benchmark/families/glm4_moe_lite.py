"""The GLM-4.7-Flash family (`model_type` `glm4_moe_lite`): everything of
the benchmark that knows it.

A pre-norm decoder, `h = x + Attn(RMSNorm(x))`, `out = h + MLP(RMSNorm(h))`,
a final RMSNorm, an untied head, eps `rms_norm_eps`, no bias. The equations
are written from the published `config.json` and the publications it
follows, with nothing imported from the program; what the config does not
state is listed under `assumed` in the configuration file.

* Attention (multi-head latent attention, DeepSeek-V2, arXiv:2405.04434),
  with no indexer: `cQ = RMSNorm(W_dq x)`; `q_i = W_uq,i cQ = [qC_i (nope) ;
  qR_i (rope)]`; `[cKV ; kR] = W_dkv x`, `cKV <- RMSNorm(cKV)`; rotary
  embedding (theta `rope_theta`, interleaved pairs) on every `qR_i` and on
  `kR`, which all heads share; `[kC_i ; v_i] = W_ukv,i cKV`; score of head i,
  query t, key s: `(qC_i . kC_i,s + qR_i . kR_s) / sqrt(nope + rope)`;
  softmax over every `s <= t`; output `W_o concat_i(sum_s p v_i,s)`. Keys
  and values are expanded a head here, as the equations state them.
* MLP of the first `first_k_dense_replace` layers: `W_down(silu(W_gate x) *
  W_up x)`. Of the others (DeepSeek-V3, arXiv:2412.19437): `s = sigmoid(W_g
  x)` over the `n_routed_experts`; chosen = the `num_experts_per_tok`
  largest of `s + b` (`b` the `noaux_tc` correction bias; `n_group` 1: no
  group limit); weights `s[chosen] / sum s[chosen] * routed_scaling_factor`;
  `y = sum_chosen w_e Expert_e(x) + Expert_shared(x)`, every expert the same
  SwiGLU of `moe_intermediate_size`. Every expert is held.
* The vocabulary: rows `[share.vocab_first, + vocab_size)` of
  `published.vocab_size` are held, of both tables: a sliced vocabulary is a
  smaller one (ids, logits and the greedy choice are over the slice).
* The multi-token prediction module (`num_nextn_predict_layers` 1; ibid.
  section 2.2), teacher-forced in `mtp_logits`: with `h_j` the main model's
  last hidden state at position j after its final norm, `u_j = W_eh
  [RMSNorm_e(Emb(t_j+1)) ; RMSNorm_h(h_j)]`, `g = Block(u)` (a routed block,
  causal over `u_0..u_j`, rope position j), logits of `t_j+2` = `Head(
  RMSNorm_s(g_j))`, the embedding and the head the main model's.

`logits` and `mtp_logits` take queries in blocks of `QUERY_BLOCK` and the
MLPs in blocks of `TOKEN_BLOCK` tokens, so that 18k positions fit one chip;
a layer's weights are upcast to float32 while it runs, an expert at a time,
each expert over every token of a block weighted by 0 where the router did
not choose it.

The weights: normal(0, `init.std` or 0.02) matrices and tables; every norm
weight 1 plus such a number, so that a dropped one is caught; the routers'
correction biases normal(0, `ROUTER_BIAS_STD`) from `BIAS_KEY` and a
layer's index, the same for every seed (as in the GLM-5 family: seeds
differ in every other weight, not in how busy the experts are). Program
and reference derive every leaf from the seed with the same keys
(`program_params`, `stacked`).
"""

import math

import jax
import jax.numpy as jnp

import reference
from weights import init_std, key_of

IMPLEMENTED = {"model_type": "glm4_moe_lite", "hidden_act": "silu",
               "tie_word_embeddings": False, "attention_bias": False,
               "topk_method": "noaux_tc", "norm_topk_prob": True,
               "n_group": 1, "topk_group": 1, "partial_rotary_factor": 1,
               "rope_scaling": None, "num_nextn_predict_layers": 1}
MODES = ("float32", "fp8")
ROUTER_BIAS_STD = 0.02
BIAS_KEY = 0xB1A5
MTP_INDEX = 1000            # the MTP block's index for its correction bias
QUERY_BLOCK = 64
TOKEN_BLOCK = 512
_HI = jax.lax.Precision.HIGHEST


def check(cfg):
    for key, have in IMPLEMENTED.items():
        if cfg.get(key, None) != have:
            raise ValueError(f"configuration states {key}={cfg.get(key)!r}; "
                             f"the family glm4_moe_lite implements {have!r} "
                             f"only")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("the family glm4_moe_lite implements as many key "
                         "heads as query heads only")
    if not 0 <= cfg["first_k_dense_replace"] <= cfg["num_hidden_layers"]:
        raise ValueError("first_k_dense_replace is no count of the layers")
    m = _dims(cfg)
    if m["v_first"] < 0 or m["v_first"] + cfg["vocab_size"] > m["V"]:
        raise ValueError("the vocabulary rows held are no slice of the "
                         "published vocab_size")


def sizes(cfg):
    return {"vocab": cfg["vocab_size"],
            "positions": cfg["max_position_embeddings"]}


def kinds(cfg):
    """`dense` or `sparse`, the MLP of each main layer."""
    k = cfg["first_k_dense_replace"]
    return ("dense",) * k + ("sparse",) * (cfg["num_hidden_layers"] - k)


def _dims(cfg):
    return {"d": cfg["hidden_size"], "ff": cfg["intermediate_size"],
            "H": cfg["num_attention_heads"], "qr": cfg["q_lora_rank"],
            "kvr": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "f": cfg["moe_intermediate_size"], "E": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"], "ns": cfg["n_shared_experts"],
            # the published vocabulary, the first of its rows held here
            "V": cfg.get("published", {}).get("vocab_size",
                                              cfg["vocab_size"]),
            "v_first": cfg.get("share", {}).get("vocab_first", 0),
            "theta": float(cfg["rope_theta"]),
            "scaling": float(cfg["routed_scaling_factor"])}


def build_model(cfg):
    """The program's model for a configuration's sizes, and its eos id."""
    from bigdl_tpu.interop.glm4_moe_lite import Glm4MoeLiteLM
    check(cfg)
    m, eos = _dims(cfg), cfg["vocab_size"] - 1
    attention = {"num_heads": m["H"], "q_lora_rank": m["qr"],
                 "kv_lora_rank": m["kvr"], "qk_nope_head_dim": m["dn"],
                 "qk_rope_head_dim": m["dr"], "v_head_dim": m["dv"],
                 "rope_theta": m["theta"]}
    experts = {"d_expert": m["f"], "num_experts": m["E"], "top_k": m["k"],
               "num_shared": m["ns"], "scaling": m["scaling"]}
    return Glm4MoeLiteLM(
        m["V"], m["d"], m["ff"], kinds(cfg), attention, experts,
        cfg["max_position_embeddings"], eps=cfg["rms_norm_eps"],
        vocab_share=(m["v_first"], cfg["vocab_size"]), eos_id=eos,
        param_dtype=jnp.dtype(cfg["weights_dtype"])), eos


# ------------------------------------------------------------- weights
def _layer(key, kind, cfg, dtype, index):
    """One block's leaves, named as the reference uses them."""
    m, std = _dims(cfg), init_std(cfg)[0]
    d, H = m["d"], m["H"]
    ks = iter(jax.random.split(key, 24))
    n = lambda shape: (std * jax.random.normal(                # noqa: E731
        next(ks), shape, jnp.float32)).astype(dtype)
    one = lambda shape: (1.0 + std * jax.random.normal(        # noqa: E731
        next(ks), shape, jnp.float32)).astype(dtype)
    out = {"attn_norm": one((d,)), "mlp_norm": one((d,)),
           "wq_a": n((d, m["qr"])), "q_norm": one((m["qr"],)),
           "wq_b": n((m["qr"], H * (m["dn"] + m["dr"]))),
           "wkv_a": n((d, m["kvr"] + m["dr"])), "kv_norm": one((m["kvr"],)),
           "wkv_b": n((m["kvr"], H * (m["dn"] + m["dv"]))),
           "wo": n((H * m["dv"], d))}
    if kind == "dense":
        out.update({"gate": n((d, m["ff"])), "up": n((d, m["ff"])),
                    "down": n((m["ff"], d))})
    else:
        f, fs = m["f"], m["f"] * m["ns"]
        out.update({"router": n((d, m["E"])),
                    "router_bias": (ROUTER_BIAS_STD * jax.random.normal(
                        jax.random.fold_in(jax.random.PRNGKey(BIAS_KEY),
                                           index),
                        (m["E"],), jnp.float32)).astype(dtype),
                    "e_gate": n((m["E"], d, f)), "e_up": n((m["E"], d, f)),
                    "e_down": n((m["E"], f, d)), "s_gate": n((d, fs)),
                    "s_up": n((d, fs)), "s_down": n((fs, d))})
    return out


def _stacked_of(key, cfg, dtype):
    dt = jnp.dtype(cfg["weights_dtype"] if dtype is None else dtype)
    d, (std, emb) = cfg["hidden_size"], init_std(cfg)
    ks = jax.random.split(jax.random.fold_in(key, 0x70F), 8)
    n = lambda k, shape, s: (s * jax.random.normal(            # noqa: E731
        k, shape, jnp.float32)).astype(dt)
    one = lambda k: (1.0 + n(k, (d,), std)).astype(dt)         # noqa: E731
    out = {"embed": n(ks[0], (cfg["vocab_size"], d), emb),
           "lm_head": n(ks[1], (cfg["vocab_size"], d), emb),
           "norm": one(ks[2]),
           "layers": [_layer(jax.random.fold_in(key, i), kind, cfg, dt, i)
                      for i, kind in enumerate(kinds(cfg))],
           "mtp": {"enorm": one(ks[3]), "hnorm": one(ks[4]),
                   "eh": n(ks[5], (2 * d, d), std), "norm": one(ks[6]),
                   "block": _layer(jax.random.fold_in(key, MTP_INDEX),
                                   "sparse", cfg, dt, MTP_INDEX)}}
    return out


_BUILT = {}


def _built(which, cfg, dtype, make):
    """One jitted maker for each layout, sizes and dtype."""
    sk = (which, kinds(cfg), tuple(sorted(_dims(cfg).items())), init_std(cfg),
          jnp.dtype(cfg["weights_dtype"] if dtype is None else dtype).name)
    if sk not in _BUILT:
        _BUILT[sk] = jax.jit(make)
    return _BUILT[sk]


def stacked(seed, cfg, dtype=None):
    """The reference's layout: `embed`, `lm_head`, `norm`, `layers` (one
    dict of the reference's names a layer) and `mtp`. In the
    configuration's `weights_dtype` unless told otherwise."""
    return _built("stacked", cfg, dtype,
                  lambda key: _stacked_of(key, cfg, dtype))(key_of(seed))


def to_program(layer):
    """One block of the reference's names -> one block of the program."""
    lin = lambda name: {"weight": layer[name]}                 # noqa: E731
    out = {"attn": {"q_a": lin("wq_a"), "q_norm": lin("q_norm"),
                    "q_b": lin("wq_b"), "kv_a": lin("wkv_a"),
                    "kv_norm": lin("kv_norm"), "kv_b": lin("wkv_b"),
                    "o": lin("wo")},
           "attn_norm": lin("attn_norm"), "mlp_norm": lin("mlp_norm")}
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
    """A stacked tree in the program's layout (`Glm4MoeLiteLM`)."""
    out = {"embed": w["embed"], "lm_head": w["lm_head"],
           "norm": {"weight": w["norm"]}}
    for i, layer in enumerate(w["layers"]):
        out[f"l{i}"] = to_program(layer)
    t = w["mtp"]
    out.update({"mtp_enorm": {"weight": t["enorm"]},
                "mtp_hnorm": {"weight": t["hnorm"]},
                "mtp_eh": {"weight": t["eh"]}, "mtp": to_program(t["block"]),
                "mtp_norm": {"weight": t["norm"]}})
    return out


def program_params(seed, cfg, dtype=None):
    """The same values in the program's parameter tree, made on the device
    in one jitted call."""
    return _built(
        "program", cfg, dtype,
        lambda key: program_tree(_stacked_of(key, cfg, dtype)))(key_of(seed))


# ----------------------------------------------------------- reference
def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


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
    """`f(start, *blocks)` over blocks of `block` rows of the arrays (all of
    T rows), a block after another; T is `block` times a whole number, or
    one block."""
    T = arrays[0].shape[0]
    if T <= block:
        return f(jnp.zeros((), jnp.int32), *arrays)
    n = T // block
    out = jax.lax.map(lambda at: f(*at), (jnp.arange(n) * block,) + tuple(
        a.reshape((n, block) + a.shape[1:]) for a in arrays))
    return out.reshape((T,) + out.shape[2:])


def _attention(x, lw, cfg, mode):
    """One sequence's normed x (T, d) -> W_o attention (T, d): keys and
    values of every head for every position, each query block over all of
    them under the causal mask."""
    mm, m, eps = reference.mm(mode), _dims(cfg), cfg["rms_norm_eps"]
    T, H, dn, dr, dv, kvr = (x.shape[0], m["H"], m["dn"], m["dr"], m["dv"],
                             m["kvr"])
    r8 = reference.round_fp8 if mode == "fp8" else (lambda a: a)
    pos = jnp.arange(T)
    kv = mm(x, lw["wkv_a"])
    c = _rms(kv[:, :kvr], lw["kv_norm"], eps)
    k_r = r8(_rope(kv[:, kvr:], pos, m["theta"]))            # (T, dr)
    kv_up = mm(c, lw["wkv_b"]).reshape(T, H, dn + dv)
    k_c, v = r8(kv_up[..., :dn]), r8(kv_up[..., dn:])

    def block(start, x_b):
        blk = x_b.shape[0]
        p_b = start + jnp.arange(blk)
        cq = _rms(mm(x_b, lw["wq_a"]), lw["q_norm"], eps)
        q = mm(cq, lw["wq_b"]).reshape(blk, H, dn + dr)
        q_c, q_r = r8(q[..., :dn]), r8(_rope(q[..., dn:], p_b, m["theta"]))
        s = (jnp.einsum("qhc,khc->qhk", q_c, k_c, precision=_HI)
             + jnp.einsum("qhr,kr->qhk", q_r, k_r, precision=_HI)) \
            / math.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where((pos[None, :] <= p_b[:, None])[:, None],
                                     s, -jnp.inf), axis=-1)
        o = jnp.einsum("qhk,khv->qhv", r8(p), v, precision=_HI)
        return mm(o.reshape(blk, H * dv), lw["wo"])

    return _in_blocks(block, QUERY_BLOCK, x)


def _mlp(y, lw, cfg, mode):
    """The MLP of normed y (blk, d): a dense layer's SwiGLU, or every
    expert over every token weighted by 0 where the router did not choose
    it (an expert upcast to float32 at a time) and the shared expert."""
    mm, m = reference.mm(mode), _dims(cfg)

    def swiglu(gate, up, down):
        return mm(jax.nn.silu(mm(y, gate)) * mm(y, up), down)
    if "gate" in lw:
        return swiglu(lw["gate"], lw["up"], lw["down"])
    s = jax.nn.sigmoid(jnp.matmul(y, lw["router"], precision=_HI))
    chosen = jax.lax.top_k(s + lw["router_bias"], m["k"])[1]
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / jnp.sum(w, -1, keepdims=True) * m["scaling"]
    dense = jnp.zeros_like(s).at[jnp.arange(y.shape[0])[:, None],
                                 chosen].set(w)
    f32 = lambda a: a.astype(jnp.float32)                      # noqa: E731

    def one(total, at):
        gate, up, down, w_e = at
        return total + w_e[:, None] * swiglu(f32(gate), f32(up),
                                             f32(down)), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (lw["e_gate"], lw["e_up"], lw["e_down"], dense.T))
    return routed + swiglu(lw["s_gate"], lw["s_up"], lw["s_down"])


def _block(x, lw, cfg, mode):
    eps = cfg["rms_norm_eps"]
    routed = {k: lw[k] for k in ("e_gate", "e_up", "e_down") if k in lw}
    lw = dict({k: v.astype(jnp.float32) for k, v in lw.items()
               if k not in routed}, **routed)
    h = x + _attention(_rms(x, lw["attn_norm"], eps), lw, cfg, mode)
    return _in_blocks(
        lambda _, h_b: h_b + _mlp(_rms(h_b, lw["mlp_norm"], eps), lw, cfg,
                                  mode), TOKEN_BLOCK, h)


def _padded(T):
    return -T % TOKEN_BLOCK if T > QUERY_BLOCK else 0


def _hidden(w, cfg, row, mode):
    """One sequence (T,) -> the last block's output (T + pad, d) float32,
    padded behind its end to whole blocks (no token before sees them)."""
    x = w["embed"][jnp.pad(row, (0, _padded(row.shape[0])))].astype(
        jnp.float32)
    for lw in w["layers"]:
        x = _block(x, lw, cfg, mode)
    return x


def _head(x, table, norm, cfg, mode):
    x = _rms(x, norm.astype(jnp.float32), cfg["rms_norm_eps"])
    return reference.mm(mode)(x, table.astype(jnp.float32).T)


def logits(w, cfg, tokens, mode="float32"):
    """tokens (B, T) int32 -> (B, T, vocab) float32 logits of the main
    model over the vocabulary rows held, a sequence after another."""
    T = tokens.shape[1]
    return jax.lax.map(
        lambda row: _head(_hidden(w, cfg, row, mode)[:T], w["lm_head"],
                          w["norm"], cfg, mode), tokens)


def mtp_logits(w, cfg, tokens, mode="float32"):
    """tokens (B, T) int32 -> (B, T - 1, vocab) float32: row j the MTP
    module's logits of token j + 2 from the pair (h_j, t_j+1), teacher-
    forced along the sequence."""
    T, eps, t = tokens.shape[1], cfg["rms_norm_eps"], w["mtp"]
    f32 = lambda a: a.astype(jnp.float32)                      # noqa: E731

    def one(row):
        h = _rms(_hidden(w, cfg, row, mode)[:T - 1], f32(w["norm"]), eps)
        e = _rms(f32(w["embed"][row[1:]]), f32(t["enorm"]), eps)
        u = reference.mm(mode)(jnp.concatenate(
            [e, _rms(h, f32(t["hnorm"]), eps)], -1), f32(t["eh"]))
        u = jnp.pad(u, ((0, _padded(T - 1)), (0, 0)))
        g = _block(u, t["block"], cfg, mode)[:T - 1]
        return _head(g, w["lm_head"], t["norm"], cfg, mode)
    return jax.lax.map(one, tokens)


# --------------------------------------------------------------- FLOPs
def parameters(cfg, layers=None, vocab=None):
    """Parameter counts from the sizes alone, of the main layers held (or
    of `layers` of them, as for the published depth), the MTP module
    beside them, and the vocabulary rows held (or `vocab` rows)."""
    m = _dims(cfg)
    d, H = m["d"], m["H"]
    mla = (d * m["qr"] + m["qr"] + m["qr"] * H * (m["dn"] + m["dr"])
           + d * (m["kvr"] + m["dr"]) + m["kvr"]
           + m["kvr"] * H * (m["dn"] + m["dv"]) + H * m["dv"] * d)
    expert = 3 * d * m["f"]
    router = d * m["E"] + m["E"]
    routed = 2 * d + mla + router + expert * (m["ns"] + m["E"])
    dense = 2 * d + mla + 3 * d * m["ff"]
    ks = kinds(cfg) if layers is None else \
        kinds(dict(cfg, num_hidden_layers=layers))
    main = d + sum(dense if k == "dense" else routed for k in ks)
    mtp = routed + 2 * d * d + 3 * d
    table = (cfg["vocab_size"] if vocab is None else vocab) * d
    return {"mla": mla, "expert": expert, "shared_expert": expert * m["ns"],
            "router": router, "routed_layer": routed, "dense_layer": dense,
            "layers": main, "mtp": mtp, "embedding": table, "head": table,
            "total": main + mtp + 2 * table}


_FLOPS = {}


def _flop_terms(cfg):
    """What a computed token of the main model costs, in the terms the
    counters count (the MTP module's work is the price of drafting, not
    the served tokens' work: it is left out)."""
    key = id(cfg)
    if key not in _FLOPS:
        m, ks = _dims(cfg), kinds(cfg)
        p = parameters(cfg)
        n_sparse = ks.count("sparse")
        _FLOPS[key] = {
            # every matrix a token meets whatever it is routed to
            "token": 2.0 * (p["layers"] - n_sparse * m["E"] * p["expert"]),
            "logits": 2.0 * p["head"],
            # every expert is held: a token makes top-k pairs a layer
            "pairs": 2.0 * p["expert"] * m["k"] * n_sparse,
            # absorbed attention over one earlier row, all heads and layers
            "attended": 2.0 * m["H"] * (2 * m["kvr"] + m["dr"]) * len(ks)}
    return _FLOPS[key]


def serve_token_flops(cfg, position, logits):
    """FLOPs the main model needs to process one token at `position`
    (counting from 0) through the cache."""
    t = _flop_terms(cfg)
    return (t["token"] + t["pairs"] + (t["logits"] if logits else 0.0)
            + t["attended"] * (position + 1))


def counted_flops(cfg, counted):
    """FLOPs the main model needs for the tokens the program computed and
    delivered, from what the decode scheduler counted (`prefill_tokens`,
    `step_tokens`, `attended_tokens`): a prompt token from the prefix cache
    is not in them, nor is a rejected draft's row."""
    t = _flop_terms(cfg)
    return ((t["token"] + t["pairs"])
            * (counted["prefill_tokens"] + counted["step_tokens"])
            + t["logits"] * counted["step_tokens"]
            + t["attended"] * counted["attended_tokens"])


def kernel_work(cfg, kernel, counted=None, **_):
    """The least work the model asks of a mechanism for what the scheduler
    counted over a span. `latent_attend`: the decode steps' reads of their
    slots' contexts, each latent row (`kv_lora_rank + qk_rope_head_dim`
    values) read once a step row, a main layer and the MTP block, and one
    query a row against each (a draft's row shares the read and is left
    out). `expert_ffn`: the grouped products of the main layers, FLOPs from
    the token-expert pairs they computed and bytes from the matrices of
    every expert that got a token in a call, read once (the MTP block's are
    left out of both)."""
    if not counted:
        return None
    m, itemsize = _dims(cfg), jnp.dtype(cfg["weights_dtype"]).itemsize
    if kernel == "latent_attend":
        layers = len(kinds(cfg)) + 1
        rows = counted["step_context_tokens"] * layers
        return {"flops": 2.0 * m["H"] * (2 * m["kvr"] + m["dr"]) * rows,
                "bytes": float(itemsize) * (m["kvr"] + m["dr"]) * rows}
    if kernel == "expert_ffn":
        per = 3 * m["d"] * m["f"]
        return {"flops": 2.0 * per * counted["expert_pairs"],
                "bytes": float(itemsize) * per * counted["expert_loads"]}
    return None
