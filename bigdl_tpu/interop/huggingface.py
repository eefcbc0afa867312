"""HuggingFace `transformers` bridge (parity-plus: the reference predates
the HF ecosystem; its closest analogue is the Keras/TF importer surface,
§2.8). Converts a torch `transformers` model's weights onto this
framework's own primitives — no torch at inference time.

Bridges: `from_gpt2` (decoder, pre-LN + tanh-gelu, beam/KV-cache
generate), `from_bert` (post-LN encoder with padding masks + token
types), `from_llama` (modern decoder: RMSNorm + rotary + grouped-query
attention + SwiGLU, grouped-KV cached generate), `from_vit` (vision
encoder: patchify conv + CLS + learned positions). Each is logits/
hidden-state exact vs the torch forward and returns a trainable,
serializable module on nn.* primitives.

    from transformers import GPT2LMHeadModel
    from bigdl_tpu.interop.huggingface import from_gpt2
    module, params, state = from_gpt2(GPT2LMHeadModel(config))
    logits, _ = module.apply(params, state, tokens)   # (B, T, vocab)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.core.module import Module
from bigdl_tpu.nn.attention import (FeedForwardNetwork,
                                    MultiHeadAttention, TransformerLayer)
from bigdl_tpu.nn.normalization import LayerNormalization


def _gelu_tanh(x):
    """GPT-2's `gelu_new` (tanh approximation) — module-level so the
    converted model stays picklable for the durable format."""
    return jax.nn.gelu(x, approximate=True)


def _beam_generate(lm, params, state, prompt, max_new_tokens, beam_size,
                   eos_id, alpha, kv_cache, *, kv_shape, dtype,
                   n_positions=None):
    """Shared beam-search decode used by GPT2LM and LlamaLM. The lm must
    provide `_hidden(params, state, tokens)`, `_head(params)`, and
    `_cached_forward(params, tokens, caches, start)`; `kv_shape` =
    (cache heads, head_dim) — grouped-KV models pass the grouped width.

    Recompute path: fixed-shape buffer, only the decode position's
    hidden row hits the LM head. kv_cache path: per-layer (N, L, H, hd)
    caches through cached_beam_generate."""
    from bigdl_tpu.nn.recurrent import (beam_search, cached_beam_generate,
                                        tile_beam)
    if eos_id is None:
        eos_id = lm.eos_id
    if eos_id is None:
        raise ValueError("generate: pass eos_id (the model carries none "
                         "— config eos_token_id was absent or out of "
                         "vocabulary)")
    B, P = prompt.shape
    L = P + max_new_tokens
    if n_positions is not None and L > n_positions:
        raise ValueError(f"prompt+new = {L} > n_positions {n_positions}")
    if kv_cache:
        H, hd = kv_shape

        def make_caches():
            zeros = lambda: jnp.zeros((B, L, H, hd), dtype)  # noqa: E731
            return (tuple(zeros() for _ in range(lm.num_layers)),
                    tuple(zeros() for _ in range(lm.num_layers)))

        return cached_beam_generate(
            functools.partial(lm._cached_forward, params), make_caches,
            prompt, max_new_tokens=max_new_tokens, beam_size=beam_size,
            vocab_size=lm.vocab_size, eos_id=eos_id, alpha=alpha)

    buf0 = jnp.zeros((B, L), jnp.int32).at[:, :P - 1].set(prompt[:, :-1])
    # beam_search reorders state leaves along the beam dim, so `pos`
    # rides as a per-row vector (identical entries)
    st0 = tile_beam((buf0, jnp.full((B,), P - 1, jnp.int32)), beam_size)

    def step_fn(tokens_last, st):
        buf, pos = st
        p = pos[0]
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, tokens_last[:, None], p, axis=1)
        h, _ = lm._hidden(params, state, buf)
        h_p = jax.lax.dynamic_index_in_dim(h, p, axis=1, keepdims=False)
        return h_p @ lm._head(params).T, (buf, pos + 1)

    seqs, scores = beam_search(
        step_fn, st0, prompt[:, -1], beam_size=beam_size,
        vocab_size=lm.vocab_size, max_len=max_new_tokens, eos_id=eos_id,
        alpha=alpha)
    full = jnp.concatenate(
        [jnp.repeat(prompt[:, None], beam_size, axis=1), seqs], -1)
    return full, scores


class GPT2LM(Module):
    """GPT-2 rebuilt on this framework's primitives. apply(params, state,
    tokens (B, T) int32) → (B, T, vocab) logits (head tied to the token
    embedding unless `tied=False`, which adds an `lm_head` param)."""

    def __init__(self, vocab_size: int, n_positions: int, d_model: int,
                 num_heads: int, num_layers: int, ln_eps: float = 1e-5,
                 dropout: float = 0.0, tied: bool = True,
                 eos_id=None, name=None):
        super().__init__(name or "GPT2LM")
        self.vocab_size, self.n_positions = vocab_size, n_positions
        self.d_model, self.num_layers = d_model, num_layers
        self.tied = tied
        self.eos_id = eos_id          # generate()'s default stop token
        for i in range(num_layers):
            self.add_child(f"h{i}", TransformerLayer(
                d_model, num_heads, 4 * d_model, bias=True,
                activation=_gelu_tanh, ln_eps=ln_eps, dropout=dropout))
        self.add_child("ln_f", LayerNormalization(d_model, eps=ln_eps))

    def param_specs(self):
        from bigdl_tpu.core.module import ParamSpec
        from bigdl_tpu.core import init as initializers
        specs = {
            "wte": ParamSpec((self.vocab_size, self.d_model),
                             initializers.random_normal(0.0, 0.02)),
            "wpe": ParamSpec((self.n_positions, self.d_model),
                             initializers.random_normal(0.0, 0.01)),
        }
        if not self.tied:
            specs["lm_head"] = ParamSpec(
                (self.vocab_size, self.d_model),
                initializers.random_normal(0.0, 0.02))
        return specs

    def _hidden(self, params, state, tokens, training=False, rng=None):
        t = tokens.shape[1]
        if t > self.n_positions:
            raise ValueError(f"sequence {t} > n_positions "
                             f"{self.n_positions}")
        x = params["wte"][tokens] + params["wpe"][jnp.arange(t)]
        new_state = dict(state)
        rngs = (jax.random.split(rng, self.num_layers)
                if rng is not None else (None,) * self.num_layers)
        for i in range(self.num_layers):
            x, new_state[f"h{i}"] = self.children()[f"h{i}"].apply(
                params[f"h{i}"], state.get(f"h{i}", {}), x, causal=True,
                training=training, rng=rngs[i])
        x, new_state["ln_f"] = self.children()["ln_f"].apply(
            params["ln_f"], state.get("ln_f", {}), x)
        return x, new_state

    def _head(self, params):
        return params["wte"] if self.tied else params["lm_head"]

    def _apply(self, params, state, tokens, *, training=False, rng=None):
        x, new_state = self._hidden(params, state, tokens, training, rng)
        return x @ self._head(params).T, new_state

    # ------------------------------------------------- KV-cached decoding
    def _cached_forward(self, params, tokens, caches, start):
        """tokens (N, T) at absolute positions [start, start+T); caches =
        (cks, cvs) per-layer tuples of (N, L, H, hd) — N leading so
        beam_search's per-beam state reorder maps over the leaves.
        Returns (logits at the LAST position (N, V), new caches)."""
        cks, cvs = caches
        x = params["wte"][tokens] + params["wpe"][start + jnp.arange(
            tokens.shape[1])]
        new_ck, new_cv = [], []
        for i in range(self.num_layers):
            blk = self.children()[f"h{i}"]
            x, ck_i, cv_i = blk.cached_step(
                params[f"h{i}"], x, cks[i], cvs[i], start)
            new_ck.append(ck_i)
            new_cv.append(cv_i)
        x, _ = self.children()["ln_f"].apply(params["ln_f"], {}, x)
        return (x[:, -1] @ self._head(params).T,
                (tuple(new_ck), tuple(new_cv)))

    def generate(self, params, state, prompt, max_new_tokens: int,
                 beam_size: int = 4, eos_id=None, alpha: float = 0.0,
                 kv_cache: bool = False):
        """Beam-search continuation of `prompt` (B, P) int32 →
        (sequences (B, K, P+max_new), scores (B, K)).

        Default path: full-prefix recompute per step (fixed-shape scan
        buffer; the causal mask hides the zero tail — same recipe as
        examples/language_model.py), with only the decode position's
        hidden row hitting the LM head. `kv_cache=True` switches to
        incremental decoding: one token's QKV per step attending over
        per-layer caches — O(L) per step instead of O(L²), identical
        outputs (asserted). `eos_id` defaults to the converted config's
        eos_token_id."""
        H = self.children()["h0"].attn.num_heads
        return _beam_generate(
            self, params, state, prompt, max_new_tokens, beam_size,
            eos_id, alpha, kv_cache, kv_shape=(H, self.d_model // H),
            dtype=params["wte"].dtype, n_positions=self.n_positions)

    # ------------------------------------------- iteration-level decoding
    # What the decode engine composes its programs from (serve/decode.py
    # DecodeEntry._build): the cache pytree, the hidden states of a SLOT
    # batch where each row is an independent sequence at its own absolute
    # positions, and the final norm and head. K/V live in a shared pool of
    # fixed-size blocks addressed through a per-slot block table and
    # attention reads the pool where it lies
    # (nn/attention.paged_slot_cached_attend). Per row the same lanes are
    # attended as by _cached_forward with the matching scalar start,
    # summed in pool order (the oracle of tests/test_decode.py). Inactive
    # rows and padded prefill tails are left out of the write - they never
    # touch the pool.
    def make_paged_slot_caches(self, params, num_blocks: int, block: int):
        """One zero KV pool per layer (nn/attention.make_paged_kv_pool) -
        the shared block pool the decode engine's BlockPool allocates
        out of."""
        from bigdl_tpu.nn.attention import make_paged_kv_pool
        H = self.children()["h0"].attn.num_heads
        return tuple(make_paged_kv_pool(num_blocks, block, H,
                                        self.d_model // H,
                                        params["wte"].dtype)
                     for _ in range(self.num_layers))

    def paged_hidden(self, params, caches, tokens, positions,
                     block_table, lengths, decode=False, chunk=None):
        """Hidden states of one chunk a slot, its K/V written into the
        pool: tokens/positions (S, C) int32, block_table (S, M) int32
        (-1 = unacquired), lengths (S,) int32 = VALID leading tokens per
        row (0 = inactive; padded tail tokens of a rounded-up bucket are
        dropped, not written). `decode` (the one-token step, not a
        prompt chunk) changes nothing here. `chunk` (the optional carrying
        form, nn/attention.carried_rows) is a prompt chunk of streaming slots
        that the same pass writes: every product reads its weights once
        for both. Returns (x (S, C, d), the new pool caches)."""
        from bigdl_tpu.nn.attention import carried_rows
        S = tokens.shape[0]
        tokens, positions, parts = carried_rows(
            tokens, positions, block_table, lengths, decode, chunk)
        pos = jnp.clip(positions, 0, self.n_positions - 1)
        x = params["wte"][tokens] + params["wpe"][pos]
        pools = []
        for i in range(self.num_layers):
            x, pool = self.children()[f"h{i}"].paged_slot_cached_step(
                params[f"h{i}"], x, caches[i], pos, block_table, lengths,
                parts)
            pools.append(pool)
        if parts:
            x = x[0, -S:, None]         # the step's rows, one token each
        return x, tuple(pools)

    def head_logits(self, params, x):
        """The final norm and the head on a step's hidden state:
        x (S, 1, d) -> logits (S, V)."""
        x, _ = self.children()["ln_f"].apply(params["ln_f"], {}, x)
        return x[:, -1] @ self._head(params).T


def _gelu_exact(x):
    """BERT's exact erf gelu — module-level for picklability."""
    return jax.nn.gelu(x, approximate=False)


class BertEncoder(Module):
    """BERT rebuilt on this framework's primitives — post-LN blocks
    (x = LN(x + attn(x)); x = LN(x + ffn(x)), the original-Transformer
    wiring, vs GPT-2's pre-LN), learned word/position/type embeddings
    with an embedding LayerNorm. apply(params, state, tokens,
    attention_mask=None, token_type_ids=None) → (B, T, D) last hidden
    state."""

    def __init__(self, vocab_size: int, n_positions: int, type_vocab: int,
                 d_model: int, num_heads: int, num_layers: int,
                 d_ff: int, ln_eps: float = 1e-12, dropout: float = 0.0,
                 name=None):
        super().__init__(name or "BertEncoder")
        self.vocab_size, self.n_positions = vocab_size, n_positions
        self.type_vocab, self.d_model = type_vocab, d_model
        self.num_layers, self.num_heads = num_layers, num_heads
        # hidden_dropout_prob: applied to each sublayer output before the
        # residual add (HF BertSelfOutput/BertOutput); the attention-
        # probability dropout is not replicated
        self.dropout = dropout
        self.add_child("emb_ln", LayerNormalization(d_model, eps=ln_eps))
        for i in range(num_layers):
            self.add_child(f"attn{i}", MultiHeadAttention(
                d_model, num_heads, bias=True))
            self.add_child(f"attn_ln{i}", LayerNormalization(d_model,
                                                             eps=ln_eps))
            self.add_child(f"ffn{i}", FeedForwardNetwork(
                d_model, d_ff, activation=_gelu_exact))
            self.add_child(f"ffn_ln{i}", LayerNormalization(d_model,
                                                            eps=ln_eps))

    def param_specs(self):
        from bigdl_tpu.core.module import ParamSpec
        from bigdl_tpu.core import init as initializers
        n = initializers.random_normal(0.0, 0.02)
        return {"word": ParamSpec((self.vocab_size, self.d_model), n),
                "pos": ParamSpec((self.n_positions, self.d_model), n),
                "type": ParamSpec((self.type_vocab, self.d_model), n)}

    def _apply(self, params, state, tokens, attention_mask=None,
               token_type_ids=None, *, training=False, rng=None):
        t = tokens.shape[1]
        if t > self.n_positions:
            raise ValueError(f"sequence {t} > max_position_embeddings "
                             f"{self.n_positions} (a clamped gather would "
                             f"silently reuse the last position row)")
        x = params["word"][tokens] + params["pos"][jnp.arange(t)]
        tt = (jnp.zeros_like(tokens) if token_type_ids is None
              else token_type_ids)
        x = x + params["type"][tt]
        ch = self.children()
        x, _ = ch["emb_ln"].apply(params["emb_ln"], {}, x)
        mask = None
        if attention_mask is not None:
            # (B, T) 1/0 padding mask → (B, 1, 1, T) broadcast over heads
            mask = attention_mask[:, None, None, :] != 0

        def drop(h, key):
            if not training or self.dropout <= 0.0 or key is None:
                return h
            keep = jax.random.bernoulli(key, 1.0 - self.dropout, h.shape)
            return jnp.where(keep, h / (1.0 - self.dropout), 0.0)

        rngs = (jax.random.split(rng, 2 * self.num_layers)
                if rng is not None else (None,) * (2 * self.num_layers))
        for i in range(self.num_layers):
            a, _ = ch[f"attn{i}"].apply(params[f"attn{i}"], {}, x,
                                        mask=mask)
            x, _ = ch[f"attn_ln{i}"].apply(params[f"attn_ln{i}"], {},
                                           x + drop(a, rngs[2 * i]))
            f, _ = ch[f"ffn{i}"].apply(params[f"ffn{i}"], {}, x)
            x, _ = ch[f"ffn_ln{i}"].apply(params[f"ffn_ln{i}"], {},
                                          x + drop(f, rngs[2 * i + 1]))
        return x, state


def _t(x) -> np.ndarray:
    return np.asarray(x.detach().cpu().numpy(), np.float32)


def _torch_attn_params(query, key, value, out_dense):
    """torch Linear q/k/v/out modules -> our packed attn param dict
    (shared by from_bert and from_vit — HF encoders store separate
    (out, in) Linears; ours is x @ w)."""
    return {
        "wq": jnp.asarray(_t(query.weight).T),
        "bq": jnp.asarray(_t(query.bias)),
        "wk": jnp.asarray(_t(key.weight).T),
        "bk": jnp.asarray(_t(key.bias)),
        "wv": jnp.asarray(_t(value.weight).T),
        "bv": jnp.asarray(_t(value.bias)),
        "wo": jnp.asarray(_t(out_dense.weight).T),
        "bo": jnp.asarray(_t(out_dense.bias)),
    }


def _torch_ffn_params(inter_dense, out_dense):
    """torch intermediate/output Linears -> FeedForwardNetwork params."""
    return {
        "w1": {"weight": jnp.asarray(_t(inter_dense.weight).T),
               "bias": jnp.asarray(_t(inter_dense.bias))},
        "w2": {"weight": jnp.asarray(_t(out_dense.weight).T),
               "bias": jnp.asarray(_t(out_dense.bias))},
    }


def _zero_skeleton(model):
    """Shaped zero trees for (params, state) — every leaf is overwritten
    with checkpoint weights, so skip the random init entirely."""
    p_shape, s_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))  # tpu-lint: disable=004
    zeros = lambda s: jnp.zeros(s.shape, s.dtype)
    return jax.tree.map(zeros, p_shape), jax.tree.map(zeros, s_shape)


def from_gpt2(hf_model):
    """`transformers` GPT2Model / GPT2LMHeadModel → (module, params,
    state). Weight layout notes: HF Conv1D stores (in, out) — the same
    orientation as our `x @ w` projections, so c_attn's (D, 3D) splits
    column-wise into wq|wk|wv. Untied LM heads are carried as their own
    param. Fine-tuning caveat: `resid_pdrop` maps onto the block's
    sublayer dropout; HF's separate attention-probability and embedding
    dropouts are not replicated (inference is exact either way)."""
    tf = getattr(hf_model, "transformer", hf_model)   # LMHead wraps it
    cfg = hf_model.config
    d = cfg.n_embd
    lm_head = getattr(hf_model, "lm_head", None)
    tied = (lm_head is None
            or lm_head.weight.data_ptr() == tf.wte.weight.data_ptr())
    eos = getattr(cfg, "eos_token_id", None)
    if eos is not None and not (0 <= eos < cfg.vocab_size):
        eos = None                       # e.g. tiny test vocabs
    model = GPT2LM(cfg.vocab_size, cfg.n_positions, d, cfg.n_head,
                   cfg.n_layer, ln_eps=cfg.layer_norm_epsilon,
                   dropout=float(getattr(cfg, "resid_pdrop", 0.0)),
                   tied=tied, eos_id=eos)
    params, state = _zero_skeleton(model)
    if not tied:
        params["lm_head"] = jnp.asarray(_t(lm_head.weight))
    params["wte"] = jnp.asarray(_t(tf.wte.weight))
    params["wpe"] = jnp.asarray(_t(tf.wpe.weight))
    for i, block in enumerate(tf.h):
        p = params[f"h{i}"]
        p["ln1"] = {"weight": jnp.asarray(_t(block.ln_1.weight)),
                    "bias": jnp.asarray(_t(block.ln_1.bias))}
        p["ln2"] = {"weight": jnp.asarray(_t(block.ln_2.weight)),
                    "bias": jnp.asarray(_t(block.ln_2.bias))}
        ca_w = _t(block.attn.c_attn.weight)           # (D, 3D)
        ca_b = _t(block.attn.c_attn.bias)             # (3D,)
        p["attn"] = {
            "wq": jnp.asarray(ca_w[:, :d]),
            "wk": jnp.asarray(ca_w[:, d:2 * d]),
            "wv": jnp.asarray(ca_w[:, 2 * d:]),
            "bq": jnp.asarray(ca_b[:d]),
            "bk": jnp.asarray(ca_b[d:2 * d]),
            "bv": jnp.asarray(ca_b[2 * d:]),
            "wo": jnp.asarray(_t(block.attn.c_proj.weight)),
            "bo": jnp.asarray(_t(block.attn.c_proj.bias)),
        }
        p["ffn"] = {
            "w1": {"weight": jnp.asarray(_t(block.mlp.c_fc.weight)),
                   "bias": jnp.asarray(_t(block.mlp.c_fc.bias))},
            "w2": {"weight": jnp.asarray(_t(block.mlp.c_proj.weight)),
                   "bias": jnp.asarray(_t(block.mlp.c_proj.bias))},
        }
    params["ln_f"] = {"weight": jnp.asarray(_t(tf.ln_f.weight)),
                      "bias": jnp.asarray(_t(tf.ln_f.bias))}
    return model, params, state


def from_bert(hf_model):
    """`transformers` BertModel → (module, params, state). HF's
    torch.nn.Linear stores (out, in) — transposed into our `x @ w`
    orientation. Pooler/task heads are not converted (the encoder's last
    hidden state is the output)."""
    bert = getattr(hf_model, "bert", hf_model)        # task heads wrap it
    cfg = hf_model.config
    pet = getattr(cfg, "position_embedding_type", "absolute")
    if pet != "absolute":
        raise ValueError(
            f"from_bert: position_embedding_type={pet!r} is not "
            f"representable (only absolute learned positions)")
    if getattr(cfg, "is_decoder", False) or getattr(
            cfg, "add_cross_attention", False):
        raise ValueError("from_bert: decoder/cross-attention BERT "
                         "variants are not supported")
    model = BertEncoder(cfg.vocab_size, cfg.max_position_embeddings,
                        cfg.type_vocab_size, cfg.hidden_size,
                        cfg.num_attention_heads, cfg.num_hidden_layers,
                        cfg.intermediate_size,
                        ln_eps=cfg.layer_norm_eps,
                        dropout=float(getattr(cfg, "hidden_dropout_prob",
                                              0.0)))
    params, state = _zero_skeleton(model)

    emb = bert.embeddings
    params["word"] = jnp.asarray(_t(emb.word_embeddings.weight))
    params["pos"] = jnp.asarray(_t(emb.position_embeddings.weight))
    params["type"] = jnp.asarray(_t(emb.token_type_embeddings.weight))
    params["emb_ln"] = {"weight": jnp.asarray(_t(emb.LayerNorm.weight)),
                        "bias": jnp.asarray(_t(emb.LayerNorm.bias))}
    for i, layer in enumerate(bert.encoder.layer):
        att = layer.attention
        params[f"attn{i}"] = _torch_attn_params(
            att.self.query, att.self.key, att.self.value,
            att.output.dense)
        params[f"attn_ln{i}"] = {
            "weight": jnp.asarray(_t(att.output.LayerNorm.weight)),
            "bias": jnp.asarray(_t(att.output.LayerNorm.bias))}
        params[f"ffn{i}"] = _torch_ffn_params(layer.intermediate.dense,
                                              layer.output.dense)
        params[f"ffn_ln{i}"] = {
            "weight": jnp.asarray(_t(layer.output.LayerNorm.weight)),
            "bias": jnp.asarray(_t(layer.output.LayerNorm.bias))}
    return model, params, state


class LlamaBlock(Module):
    """One LLaMA decoder block on this framework's primitives: pre-RMSNorm
    grouped-query attention with rotary embeddings, then pre-RMSNorm
    SwiGLU MLP, both residual."""

    def __init__(self, d_model, num_heads, num_kv_heads, d_ff, eps,
                 rope_theta, attn_impl="dense", block_size=512,
                 name=None):
        super().__init__(name or "LlamaBlock")
        from bigdl_tpu.nn.linear import Linear
        from bigdl_tpu.nn.normalization import RMSNorm
        self.add_child("ln1", RMSNorm(d_model, eps=eps))
        self.add_child("attn", MultiHeadAttention(
            d_model, num_heads, bias=False, num_kv_heads=num_kv_heads,
            rope_theta=rope_theta, attn_impl=attn_impl,
            block_size=block_size))
        self.add_child("ln2", RMSNorm(d_model, eps=eps))
        self.add_child("gate", Linear(d_model, d_ff, bias=False))
        self.add_child("up", Linear(d_model, d_ff, bias=False))
        self.add_child("down", Linear(d_ff, d_model, bias=False))

    def _apply(self, params, state, x, *, positions=None, training=False,
               rng=None):
        c = self.children()
        h, _ = c["ln1"].apply(params["ln1"], {}, x)
        a, _ = c["attn"].apply(params["attn"], {}, h, causal=True,
                               positions=positions, training=training,
                               rng=rng)
        x = x + a
        h, _ = c["ln2"].apply(params["ln2"], {}, x)
        g, _ = c["gate"].apply(params["gate"], {}, h)
        u, _ = c["up"].apply(params["up"], {}, h)
        dn, _ = c["down"].apply(params["down"], {}, jax.nn.silu(g) * u)
        return x + dn, state

    def cached_step(self, params, x, ck, cv, start):
        """Incremental decode (see TransformerLayer.cached_step): x
        (N, T, d) at absolute positions [start, start+T); ck/cv hold the
        GROUPED kv heads (N, L, KV, hd) — the repeat to query heads
        happens at the attend, exactly like apply(). RoPE uses absolute
        positions, so cached entries never shift. Returns
        (out, new_ck, new_cv)."""
        from bigdl_tpu.nn.attention import cached_attend, rotary_embedding
        c = self.children()
        attn = c["attn"]
        if callable(attn.attn_impl):
            # decoding runs the dense core; a custom kernel's numerics
            # would silently diverge from apply() (same refusal as
            # TransformerLayer.cached_step)
            raise ValueError(
                "cached_step decodes through the dense attention core; "
                "this block was built with a custom attn_impl whose "
                "numerics it cannot reproduce")
        N, T, d = x.shape
        H, hd = attn.num_heads, attn.head_dim
        KV = attn.num_kv_heads or H
        at = params["attn"]
        h, _ = c["ln1"].apply(params["ln1"], {}, x)
        pos = start + jnp.arange(T)
        q = (h @ at["wq"]).reshape(N, T, H, hd)
        k = (h @ at["wk"]).reshape(N, T, KV, hd)
        v = (h @ at["wv"]).reshape(N, T, KV, hd)
        q = rotary_embedding(q.transpose(0, 2, 1, 3), attn.rope_theta,
                             pos)
        k = rotary_embedding(k.transpose(0, 2, 1, 3), attn.rope_theta,
                             pos).transpose(0, 2, 1, 3)
        a, ck, cv = cached_attend(q, k, v, ck, cv, start)
        x = x + a @ at["wo"]
        h, _ = c["ln2"].apply(params["ln2"], {}, x)
        g, _ = c["gate"].apply(params["gate"], {}, h)
        u, _ = c["up"].apply(params["up"], {}, h)
        dn, _ = c["down"].apply(params["down"], {}, jax.nn.silu(g) * u)
        return x + dn, ck, cv

    def paged_slot_cached_step(self, params, x, kv_pool, positions,
                               block_table, lengths, parts=None):
        """`cached_step` over a slot batch with PER-ROW positions (N, T)
        int32 against a PAGED grouped-KV pool
        (nn/attention.paged_slot_cached_attend): RoPE angles and the
        causal-over-cache mask are computed per row, so each slot decodes
        at its own offset; K/V are written into pool blocks through the
        slot's block table, the grouped query heads attending to the pool
        where it lies. Per row the same lanes as cached_step with the
        matching scalar start. With `parts` (nn/attention.carried_rows) x
        and positions are their joined tokens, attention part by part."""
        from bigdl_tpu.nn.attention import (rotary_embedding,
                                            paged_parts_attend,
                                            paged_slot_cached_attend)
        c = self.children()
        attn = c["attn"]
        if callable(attn.attn_impl):
            raise ValueError(
                "paged_slot_cached_step decodes through the dense "
                "attention core; this block was built with a custom "
                "attn_impl whose numerics it cannot reproduce")
        N, T, d = x.shape
        H, hd = attn.num_heads, attn.head_dim
        KV = attn.num_kv_heads or H
        at = params["attn"]
        h, _ = c["ln1"].apply(params["ln1"], {}, x)
        q = (h @ at["wq"]).reshape(N, T, H, hd)
        k = (h @ at["wk"]).reshape(N, T, KV, hd)
        v = (h @ at["wv"]).reshape(N, T, KV, hd)
        q = rotary_embedding(q.transpose(0, 2, 1, 3), attn.rope_theta,
                             positions)
        k = rotary_embedding(k.transpose(0, 2, 1, 3), attn.rope_theta,
                             positions).transpose(0, 2, 1, 3)
        if parts is None:
            a, kv_pool = paged_slot_cached_attend(
                q, k, v, kv_pool, positions, block_table, lengths)
        else:
            a, kv_pool = paged_parts_attend(q.transpose(0, 2, 1, 3), k, v,
                                            kv_pool, parts)
        x = x + a @ at["wo"]
        h, _ = c["ln2"].apply(params["ln2"], {}, x)
        g, _ = c["gate"].apply(params["gate"], {}, h)
        u, _ = c["up"].apply(params["up"], {}, h)
        dn, _ = c["down"].apply(params["down"], {}, jax.nn.silu(g) * u)
        return x + dn, kv_pool


class LlamaLM(Module):
    """LLaMA-architecture causal LM (RMSNorm + RoPE + GQA + SwiGLU) on
    this framework's primitives — the modern-decoder counterpart of
    GPT2LM. apply(params, state, tokens (B, T) int32) -> (B, T, vocab)
    logits."""

    def __init__(self, vocab_size, d_model, num_heads, num_kv_heads,
                 d_ff, num_layers, eps=1e-6, rope_theta=10000.0,
                 tied=False, eos_id=None, attn_impl="dense",
                 block_size=512, remat=False, name=None):
        super().__init__(name or "LlamaLM")
        from bigdl_tpu.nn.normalization import RMSNorm
        self.vocab_size, self.d_model = vocab_size, d_model
        self.num_layers, self.tied, self.eos_id = num_layers, tied, eos_id
        self.remat = remat
        for i in range(num_layers):
            self.add_child(f"l{i}", LlamaBlock(
                d_model, num_heads, num_kv_heads, d_ff, eps, rope_theta,
                attn_impl=attn_impl, block_size=block_size))
        self.add_child("norm", RMSNorm(d_model, eps=eps))

    def param_specs(self):
        from bigdl_tpu.core.module import ParamSpec
        from bigdl_tpu.core import init as initializers
        specs = {"embed": ParamSpec((self.vocab_size, self.d_model),
                                    initializers.random_normal(0.0, 0.02))}
        if not self.tied:
            specs["lm_head"] = ParamSpec(
                (self.vocab_size, self.d_model),
                initializers.random_normal(0.0, 0.02))
        return specs

    remat = False     # class default keeps older pickles loading

    def _hidden(self, params, state, tokens, training=False, rng=None,
                positions=None):
        x = params["embed"][tokens]
        rngs = (jax.random.split(rng, self.num_layers)
                if rng is not None else (None,) * self.num_layers)
        for i in range(self.num_layers):
            blk = self.children()[f"l{i}"]

            def run(p, h, blk=blk, st=state.get(f"l{i}", {}), rng=rngs[i]):
                return blk.apply(p, st, h, positions=positions,
                                 training=training, rng=rng)[0]
            if self.remat:
                # recompute each block's activations in the backward —
                # the TPU-standard HBM-for-FLOPs trade (jax.checkpoint)
                run = jax.checkpoint(run)
            x = run(params[f"l{i}"], x)
        x, _ = self.children()["norm"].apply(params["norm"], {}, x)
        return x, state

    def _head(self, params):
        return params["embed"] if self.tied else params["lm_head"]

    def _apply(self, params, state, tokens, *, positions=None,
               training=False, rng=None):
        x, _ = self._hidden(params, state, tokens, training, rng,
                            positions=positions)
        return x @ self._head(params).T, state

    def _cached_forward(self, params, tokens, caches, start):
        """tokens (N, T) at absolute positions [start, start+T) →
        (last-position logits (N, V), new caches); caches = per-layer
        (cks, cvs) of (N, L, KV, hd)."""
        cks, cvs = caches
        x = params["embed"][tokens]
        new_ck, new_cv = [], []
        for i in range(self.num_layers):
            x, ck_i, cv_i = self.children()[f"l{i}"].cached_step(
                params[f"l{i}"], x, cks[i], cvs[i], start)
            new_ck.append(ck_i)
            new_cv.append(cv_i)
        x, _ = self.children()["norm"].apply(params["norm"], {}, x)
        head = params["embed"] if self.tied else params["lm_head"]
        return x[:, -1] @ head.T, (tuple(new_ck), tuple(new_cv))

    def generate(self, params, state, prompt, max_new_tokens: int,
                 beam_size: int = 4, eos_id=None, alpha: float = 0.0,
                 kv_cache: bool = False):
        """Beam-search continuation (shared _beam_generate recipe — the
        causal mask hides the zero tail, and RoPE positions are absolute
        so the prefix's embeddings never shift; only the decode row hits
        the LM head). `kv_cache=True` decodes incrementally over
        grouped-KV caches — identical outputs, O(L) per step. Returns
        (sequences (B, K, P+new), scores (B, K))."""
        attn0 = self.children()["l0"].children()["attn"]
        KV = attn0.num_kv_heads or attn0.num_heads
        return _beam_generate(
            self, params, state, prompt, max_new_tokens, beam_size,
            eos_id, alpha, kv_cache, kv_shape=(KV, attn0.head_dim),
            dtype=params["embed"].dtype)

    # ------------------------------------------- iteration-level decoding
    # Same decode-serving contract as GPT2LM (serve/decode.py): grouped
    # KV pools, per-row RoPE offsets, inactive rows and padded tails left
    # out of the write.
    def make_paged_slot_caches(self, params, num_blocks: int, block: int):
        """One zero grouped-KV pool per layer
        (nn/attention.make_paged_kv_pool)."""
        from bigdl_tpu.nn.attention import make_paged_kv_pool
        attn0 = self.children()["l0"].children()["attn"]
        KV = attn0.num_kv_heads or attn0.num_heads
        return tuple(make_paged_kv_pool(num_blocks, block, KV,
                                        attn0.head_dim,
                                        params["embed"].dtype)
                     for _ in range(self.num_layers))

    def paged_hidden(self, params, caches, tokens, positions,
                     block_table, lengths, decode=False, chunk=None):
        """Hidden states of one chunk a slot against the paged grouped-KV
        pool (see GPT2LM.paged_hidden — same contract, `chunk` too)."""
        from bigdl_tpu.nn.attention import carried_rows
        S = tokens.shape[0]
        tokens, positions, parts = carried_rows(
            tokens, positions, block_table, lengths, decode, chunk)
        x = params["embed"][tokens]
        pools = []
        for i in range(self.num_layers):
            x, pool = self.children()[f"l{i}"].paged_slot_cached_step(
                params[f"l{i}"], x, caches[i], positions, block_table,
                lengths, parts)
            pools.append(pool)
        if parts:
            x = x[0, -S:, None]         # the step's rows, one token each
        return x, tuple(pools)

    def head_logits(self, params, x):
        """x (S, 1, d) -> logits (S, V): the final norm and the head."""
        x, _ = self.children()["norm"].apply(params["norm"], {}, x)
        return x[:, -1] @ self._head(params).T


def from_llama(hf_model, attn_impl="dense", block_size=512,
               remat=False):
    """`transformers` LlamaModel / LlamaForCausalLM → (module, params,
    state). `attn_impl` selects the attention backend for the converted
    blocks ('dense', 'blockwise', or a callable like
    kernels.flash_attention.PallasFlashAttention — GQA repeat and RoPE
    happen before the attend, so every backend sees full-head q/k/v).
    torch Linear weights are (out, in) — transposed into the
    `x @ w` orientation; k/v projections keep their grouped
    (num_key_value_heads) width. Non-default rope_scaling and explicit
    head_dim ≠ hidden/heads refuse (rotary math would silently
    diverge)."""
    m = getattr(hf_model, "model", hf_model)
    cfg = hf_model.config
    d, H = cfg.hidden_size, cfg.num_attention_heads
    kv = getattr(cfg, "num_key_value_heads", H)
    hd = getattr(cfg, "head_dim", None)
    if hd is not None and hd != d // H:
        raise NotImplementedError(
            f"from_llama: head_dim {hd} != hidden/heads {d // H}")
    scaling = getattr(cfg, "rope_scaling", None)
    if scaling:
        raise NotImplementedError(
            f"from_llama: rope_scaling {scaling!r} is not supported")
    # refuse-loudly for config fields the block doesn't model (Qwen-style
    # exports set these on LlamaForCausalLM)
    if getattr(cfg, "attention_bias", False):
        raise NotImplementedError("from_llama: attention_bias=True")
    if getattr(cfg, "mlp_bias", False):
        raise NotImplementedError("from_llama: mlp_bias=True")
    act = getattr(cfg, "hidden_act", "silu")
    if act not in ("silu", "swish"):
        raise NotImplementedError(f"from_llama: hidden_act={act!r} "
                                  "(only silu/swish)")
    lm_head = getattr(hf_model, "lm_head", None)
    tied = (lm_head is None or bool(getattr(
        cfg, "tie_word_embeddings", False)))
    eos = getattr(cfg, "eos_token_id", None)
    if not isinstance(eos, int) or not 0 <= eos < cfg.vocab_size:
        eos = None
    model = LlamaLM(cfg.vocab_size, d, H, kv, cfg.intermediate_size,
                    cfg.num_hidden_layers, eps=cfg.rms_norm_eps,
                    rope_theta=float(getattr(cfg, "rope_theta", 10000.0)),
                    tied=tied, eos_id=eos, attn_impl=attn_impl,
                    block_size=block_size, remat=remat)
    params, state = _zero_skeleton(model)
    params["embed"] = jnp.asarray(_t(m.embed_tokens.weight))
    if not tied:
        params["lm_head"] = jnp.asarray(_t(lm_head.weight))
    for i, layer in enumerate(m.layers):
        p = params[f"l{i}"]
        p["ln1"] = {"weight": jnp.asarray(_t(layer.input_layernorm.weight))}
        p["ln2"] = {"weight": jnp.asarray(
            _t(layer.post_attention_layernorm.weight))}
        att = layer.self_attn
        p["attn"] = {
            "wq": jnp.asarray(_t(att.q_proj.weight).T),
            "wk": jnp.asarray(_t(att.k_proj.weight).T),
            "wv": jnp.asarray(_t(att.v_proj.weight).T),
            "wo": jnp.asarray(_t(att.o_proj.weight).T),
        }
        p["gate"] = {"weight": jnp.asarray(_t(layer.mlp.gate_proj.weight).T)}
        p["up"] = {"weight": jnp.asarray(_t(layer.mlp.up_proj.weight).T)}
        p["down"] = {"weight": jnp.asarray(_t(layer.mlp.down_proj.weight).T)}
    params["norm"] = {"weight": jnp.asarray(_t(m.norm.weight))}
    return model, params, state


class ViTEncoder(Module):
    """Vision Transformer rebuilt on this framework's primitives —
    patchify conv + CLS token + learned position embeddings + pre-LN
    TransformerLayer stack + final LN (+ tanh pooler on CLS).
    apply(params, state, images (B, H, W, C) NHWC) -> last hidden
    (B, 1+N, d); `pool=True` returns the pooled CLS vector (B, d)."""

    def __init__(self, image_size, patch_size, channels, d_model,
                 num_heads, d_ff, num_layers, ln_eps=1e-12,
                 has_pooler=True, name=None):
        super().__init__(name or "ViTEncoder")
        from bigdl_tpu.nn.conv import SpatialConvolution
        from bigdl_tpu.nn.linear import Linear
        if image_size % patch_size:
            raise ValueError(f"image {image_size} % patch {patch_size}")
        self.d_model = d_model
        self.num_layers = num_layers
        self.n_patches = (image_size // patch_size) ** 2
        self.has_pooler = has_pooler
        self.add_child("patch", SpatialConvolution(
            channels, d_model, patch_size, patch_size, patch_size,
            patch_size, 0, 0))
        for i in range(num_layers):
            self.add_child(f"h{i}", TransformerLayer(
                d_model, num_heads, d_ff, bias=True,
                activation=_gelu_exact, ln_eps=ln_eps))
        self.add_child("ln", LayerNormalization(d_model, eps=ln_eps))
        if has_pooler:
            self.add_child("pooler", Linear(d_model, d_model))

    def param_specs(self):
        from bigdl_tpu.core.module import ParamSpec
        from bigdl_tpu.core import init as initializers
        return {
            "cls": ParamSpec((1, 1, self.d_model),
                             initializers.random_normal(0.0, 0.02)),
            "pos": ParamSpec((1, 1 + self.n_patches, self.d_model),
                             initializers.random_normal(0.0, 0.02)),
        }

    def _apply(self, params, state, images, *, pool=False, training=False,
               rng=None):
        c = self.children()
        x, _ = c["patch"].apply(params["patch"], state.get("patch", {}),
                                images)
        B = x.shape[0]
        x = x.reshape(B, -1, self.d_model)            # (B, N, d), row-major
        cls = jnp.broadcast_to(params["cls"], (B, 1, self.d_model))
        x = jnp.concatenate([cls, x], axis=1) + params["pos"]
        rngs = (jax.random.split(rng, self.num_layers)
                if rng is not None else (None,) * self.num_layers)
        for i in range(self.num_layers):
            x, _ = c[f"h{i}"].apply(params[f"h{i}"],
                                    state.get(f"h{i}", {}), x,
                                    training=training, rng=rngs[i])
        x, _ = c["ln"].apply(params["ln"], {}, x)
        if pool:
            if not self.has_pooler:
                raise ValueError(
                    "pool=True, but the source model had no pooler "
                    "(e.g. ViTForImageClassification's inner ViTModel) "
                    "— use the last hidden state's CLS row instead")
            p, _ = c["pooler"].apply(params["pooler"], {}, x[:, 0])
            return jnp.tanh(p), state
        return x, state


def from_vit(hf_model):
    """`transformers` ViTModel → (module, params, state). Inputs here are
    NHWC (TPU layout); the patch conv's torch OIHW weight transposes to
    HWIO. Interpolated position embeddings (image sizes other than the
    config's) are not replicated."""
    vit = getattr(hf_model, "vit", hf_model)          # task heads wrap it
    cfg = hf_model.config
    act = getattr(cfg, "hidden_act", "gelu")
    if act != "gelu":
        raise NotImplementedError(
            f"from_vit: hidden_act={act!r} (only exact-erf 'gelu')")
    if not getattr(cfg, "qkv_bias", True):
        raise NotImplementedError("from_vit: qkv_bias=False")
    pooler = getattr(vit, "pooler", None)
    model = ViTEncoder(cfg.image_size, cfg.patch_size, cfg.num_channels,
                       cfg.hidden_size, cfg.num_attention_heads,
                       cfg.intermediate_size, cfg.num_hidden_layers,
                       ln_eps=cfg.layer_norm_eps,
                       has_pooler=pooler is not None)
    params, state = _zero_skeleton(model)
    emb = vit.embeddings
    params["cls"] = jnp.asarray(_t(emb.cls_token))            # (1, 1, d)
    params["pos"] = jnp.asarray(_t(emb.position_embeddings))  # (1, 1+N, d)
    pw_ = _t(emb.patch_embeddings.projection.weight)          # (d, C, p, p)
    params["patch"] = {
        "weight": jnp.asarray(np.transpose(pw_, (2, 3, 1, 0))),  # HWIO
        "bias": jnp.asarray(_t(emb.patch_embeddings.projection.bias)),
    }
    for i, layer in enumerate(vit.encoder.layer):
        p = params[f"h{i}"]
        att = layer.attention
        p["ln1"] = {"weight": jnp.asarray(_t(layer.layernorm_before.weight)),
                    "bias": jnp.asarray(_t(layer.layernorm_before.bias))}
        p["ln2"] = {"weight": jnp.asarray(_t(layer.layernorm_after.weight)),
                    "bias": jnp.asarray(_t(layer.layernorm_after.bias))}
        p["attn"] = _torch_attn_params(
            att.attention.query, att.attention.key, att.attention.value,
            att.output.dense)
        p["ffn"] = _torch_ffn_params(layer.intermediate.dense,
                                     layer.output.dense)
    params["ln"] = {"weight": jnp.asarray(_t(vit.layernorm.weight)),
                    "bias": jnp.asarray(_t(vit.layernorm.bias))}
    if pooler is not None:
        params["pooler"] = {
            "weight": jnp.asarray(_t(pooler.dense.weight).T),
            "bias": jnp.asarray(_t(pooler.dense.bias))}
    return model, params, state


def llama_tp_rules():
    """Megatron-style tensor-parallel ShardingRules for LlamaLM param
    paths: q/k/v and gate/up split output columns over the 'model' axis,
    o and down split input rows (XLA GSPMD inserts the collectives).
    Constraint: the model-axis size must divide num_heads AND
    num_kv_heads (grouped K/V shard by kv head)."""
    from jax.sharding import PartitionSpec as P
    from bigdl_tpu.parallel.sharding import ShardingRules
    return ShardingRules([
        (r"l\d+/attn/w[qkv]", P(None, "model")),
        (r"l\d+/attn/wo", P("model", None)),
        (r"l\d+/(gate|up)/weight", P(None, "model")),
        (r"l\d+/down/weight", P("model", None)),
    ])


def llama_sp_apply(module, params, tokens, mesh, seq_axis="seq"):
    """Sequence-parallel LLaMA forward: run a
    `from_llama(attn_impl=RingAttention(seq_axis))` module inside
    shard_map with the sequence dim sharded over `seq_axis` — each shard
    computes RoPE with its GLOBAL position offsets (axis_index) and K/V
    blocks rotate the ring, so the logits are exactly the dense
    full-sequence forward's. Composes with a 'data' batch axis when the
    mesh carries one. tokens (B, T) with T % mesh.shape[seq_axis] == 0;
    returns (B, T, vocab) logits sharded over the sequence dim."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from bigdl_tpu.parallel.mesh import composed_data_axis
    from bigdl_tpu.parallel.ring import RingAttention

    # a non-ring backend inside shard_map would attend only within each
    # shard's slice and return plausible-shaped but WRONG logits
    for i in range(module.num_layers):
        impl = module.children()[f"l{i}"].children()["attn"].attn_impl
        if not (isinstance(impl, RingAttention)
                and impl.axis_name == seq_axis):
            raise ValueError(
                f"llama_sp_apply: layer l{i} attn_impl is {impl!r}; "
                f"build the module with from_llama(hf, attn_impl="
                f"RingAttention(axis_name={seq_axis!r}))")

    cache = module.__dict__.setdefault("_sp_compiled", {})
    key = (mesh, seq_axis)
    if key not in cache:
        batch_axis = composed_data_axis(mesh)
        tok_spec = P(batch_axis, seq_axis)

        def fwd(p, xt):
            t_local = xt.shape[1]
            idx = jax.lax.axis_index(seq_axis)
            pos = idx * t_local + jnp.arange(t_local)
            logits, _ = module.apply(p, {}, xt, positions=pos)
            return logits

        cache[key] = jax.jit(shard_map(
            fwd, mesh=mesh, in_specs=(P(), tok_spec),
            out_specs=P(batch_axis, seq_axis, None),
            check_vma=False))
    return cache[key](params, tokens)


def gpt2_tp_rules():
    """Megatron-style tensor-parallel rules for GPT2LM param paths
    (h<i>/attn + h<i>/ffn) — the same split as encoder_tp_rules, whose
    alternation already covers the GPT-2 paths; kept as a named entry
    point. The model-axis size must divide num_heads."""
    return encoder_tp_rules()


def encoder_tp_rules():
    """Tensor-parallel rules for the BERT/ViT encoder param paths
    (attn<i>/..., ffn<i>/... for BERT; h<i>/... for ViT — both match).
    Same Megatron split as gpt2_tp_rules."""
    from jax.sharding import PartitionSpec as P
    from bigdl_tpu.parallel.sharding import ShardingRules
    return ShardingRules([
        (r"(attn\d+|h\d+/attn)/w[qkv]", P(None, "model")),
        (r"(attn\d+|h\d+/attn)/b[qkv]", P("model")),
        (r"(attn\d+|h\d+/attn)/wo", P("model", None)),
        (r"(ffn\d+|h\d+/ffn)/w1/weight", P(None, "model")),
        (r"(ffn\d+|h\d+/ffn)/w1/bias", P("model")),
        (r"(ffn\d+|h\d+/ffn)/w2/weight", P("model", None)),
    ])
