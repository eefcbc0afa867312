"""Olmo-Hybrid (`model_type` `olmo_hybrid`): a causal LM whose blocks
alternate between gated delta-rule linear attention (nn/linear_attention.py)
and full softmax attention, as `layer_types` says, on this framework's
primitives, with the paged decode contract of serve/decode.py.

A block is `h = x + RMSNorm(mixer(x))`, `out = h + RMSNorm(mlp(h))` (the
OLMo family's norm placement since OLMo 2: the norm sits on each branch's
output), the MLP SwiGLU, a final RMSNorm and an untied head. A full block's
mixer is softmax attention over `num_heads` heads with an RMSNorm over the
whole width of q and of k and no rotary embedding; a linear block's is
`GatedDeltaNet`. Every norm's statistics are float32.

What a sequence leaves behind is of two kinds, and the cache pytree holds
both: a full block's keys and values, in the block pool that
nn/attention.make_paged_kv_pool lays out and `BlockPool` allots; a linear
block's `{"S", "conv"}`, resident by slot (leading axis `num_slots`), which
no block holds. `slot_resident` tells the decode engine which leaf is
which.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from bigdl_tpu.core import init as initializers
from bigdl_tpu.core.module import Module, ParamSpec
from bigdl_tpu.nn.attention import (carried_rows, causal_mask,
                                    dot_product_attention,
                                    make_paged_kv_pool, paged_parts_attend,
                                    paged_slot_cached_attend)
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.linear_attention import GatedDeltaNet
from bigdl_tpu.nn.normalization import RMSNorm

LINEAR, FULL = "linear_attention", "full_attention"


def _rms(norm, params, x):
    """`RMSNorm` with float32 statistics whatever `x` is."""
    return norm.apply(params, {}, x.astype(jnp.float32))[0].astype(x.dtype)


class QKNormAttention(Module):
    """Softmax attention with an RMSNorm over the whole width of q and of
    k, no bias, no rotary embedding: the full-attention mixer."""

    def __init__(self, d_model: int, num_heads: int, eps: float,
                 name: Optional[str] = None):
        super().__init__(name or "QKNormAttention")
        self.num_heads, self.head_dim = num_heads, d_model // num_heads
        for n in ("q", "k", "v", "o"):
            self.add_child(n, Linear(d_model, d_model, bias=False))
        self.add_child("q_norm", RMSNorm(d_model, eps=eps))
        self.add_child("k_norm", RMSNorm(d_model, eps=eps))

    def _qkv(self, params, x):
        """x (N, T, d) -> q (N, H, T, hd); k, v (N, T, H, hd)."""
        c = self.children()
        N, T, _ = x.shape
        run = lambda n: c[n].apply(params[n], {}, x)[0]        # noqa: E731
        heads = lambda a: a.reshape(N, T, self.num_heads,      # noqa: E731
                                    self.head_dim)
        q = heads(_rms(c["q_norm"], params["q_norm"], run("q")))
        k = heads(_rms(c["k_norm"], params["k_norm"], run("k")))
        return q.transpose(0, 2, 1, 3), k, heads(run("v"))

    def _apply(self, params, state, x, *, training=False, rng=None):
        N, T, d = x.shape
        q, k, v = self._qkv(params, x)
        a = dot_product_attention(q, k.transpose(0, 2, 1, 3),
                                  v.transpose(0, 2, 1, 3), causal_mask(T))
        a = a.transpose(0, 2, 1, 3).reshape(N, T, d)
        return self.children()["o"].apply(params["o"], {}, a)[0], state

    def paged_step(self, params, x, kv_pool, positions, block_table,
                   lengths, parts=None):
        """The chunk's K/V into the slot's pool blocks and attention over
        the pool where it lies (nn/attention.paged_slot_cached_attend).
        With `parts` (nn/attention.carried_rows) x is their joined tokens
        and attention goes part by part. Returns (out (N, T, d), new
        pool)."""
        q, k, v = self._qkv(params, x)
        if parts is None:
            a, kv_pool = paged_slot_cached_attend(
                q, k, v, kv_pool, positions, block_table, lengths)
        else:
            a, kv_pool = paged_parts_attend(q.transpose(0, 2, 1, 3), k, v,
                                            kv_pool, parts)
        return self.children()["o"].apply(params["o"], {}, a)[0], kv_pool


class OlmoHybridBlock(Module):
    """One block: the mixer its `kind` names, then SwiGLU, each branch
    normed on its way back into the residual stream."""

    def __init__(self, kind: str, d_model: int, num_heads: int, d_ff: int,
                 eps: float, linear: dict, name: Optional[str] = None):
        super().__init__(name or "OlmoHybridBlock")
        if kind not in (LINEAR, FULL):
            raise ValueError(f"layer type {kind!r} is neither {LINEAR!r} "
                             f"nor {FULL!r}")
        self.kind = kind
        self.add_child("mixer", GatedDeltaNet(d_model, eps=eps, **linear)
                       if kind == LINEAR
                       else QKNormAttention(d_model, num_heads, eps))
        self.add_child("mixer_norm", RMSNorm(d_model, eps=eps))
        self.add_child("gate", Linear(d_model, d_ff, bias=False))
        self.add_child("up", Linear(d_model, d_ff, bias=False))
        self.add_child("down", Linear(d_ff, d_model, bias=False))
        self.add_child("mlp_norm", RMSNorm(d_model, eps=eps))

    def _rest(self, params, x, mixed):
        """The block after its mixer: both residual branches."""
        c = self.children()
        h = x + _rms(c["mixer_norm"], params["mixer_norm"], mixed)
        g, _ = c["gate"].apply(params["gate"], {}, h)
        u, _ = c["up"].apply(params["up"], {}, h)
        dn, _ = c["down"].apply(params["down"], {}, jax.nn.silu(g) * u)
        return h + _rms(c["mlp_norm"], params["mlp_norm"], dn)

    def _apply(self, params, state, x, *, training=False, rng=None):
        mixed, _ = self.children()["mixer"].apply(params["mixer"], {}, x)
        return self._rest(params, x, mixed), state


class OlmoHybridLM(Module):
    """apply(params, state, tokens (B, T) int32) -> (B, T, vocab) logits.
    `layer_types` gives each block's mixer; `linear` the sizes of the
    gated delta-rule layers (`num_heads`, `key_dim`, `value_dim`,
    `conv_kernel`, `allow_neg_eigval`). `param_dtype` is the dtype `init`
    makes the parameters in (and the activations follow it)."""

    def __init__(self, vocab_size: int, d_model: int, num_heads: int,
                 d_ff: int, layer_types: Sequence[str], linear: dict,
                 max_positions: int, eps: float = 1e-6, eos_id=None,
                 param_dtype=jnp.float32, name: Optional[str] = None):
        super().__init__(name or "OlmoHybridLM")
        self.vocab_size, self.d_model = vocab_size, d_model
        self.num_heads = num_heads
        self.layer_types = tuple(layer_types)
        self.num_layers = len(self.layer_types)
        # the decode engine's name for the longest sequence a slot holds
        self.n_positions = max_positions
        self.eos_id, self.param_dtype = eos_id, jnp.dtype(param_dtype)
        for i, kind in enumerate(self.layer_types):
            self.add_child(f"l{i}", OlmoHybridBlock(
                kind, d_model, num_heads, d_ff, eps, linear))
        self.add_child("norm", RMSNorm(d_model, eps=eps))

    def param_specs(self):
        table = lambda: ParamSpec(                             # noqa: E731
            (self.vocab_size, self.d_model),
            initializers.random_normal(0.0, 0.02))
        return {"embed": table(), "lm_head": table()}

    def init(self, rng, dtype=None):
        return super().init(rng, dtype if dtype is not None
                            else self.param_dtype)

    def _blocks(self):
        c = self.children()
        return [(f"l{i}", c[f"l{i}"]) for i in range(self.num_layers)]

    def _logits(self, params, x):
        x = _rms(self.children()["norm"], params["norm"], x)
        return x @ params["lm_head"].T

    def _apply(self, params, state, tokens, *, training=False, rng=None):
        x = params["embed"][tokens]
        for name, blk in self._blocks():
            x, _ = blk.apply(params[name], {}, x)
        return self._logits(params, x), state

    # -------------------------------------------------- paged decoding
    # What serve/decode.py composes its programs from, with two kinds of
    # cache leaf: a full block's pool of KV blocks, a linear block's state
    # by slot.
    def make_paged_slot_caches(self, params, num_blocks: int, block: int,
                               num_slots: Optional[int] = None):
        """One entry a block: a zero KV pool (full), or a zero `{"S",
        "conv"}` for `num_slots` sequences (linear). Where `num_slots` is
        not given it is as many as the pool holds sequences of
        `max_positions` tokens."""
        if num_slots is None:
            num_slots = max(1, num_blocks * block // self.n_positions)
        dtype = params["embed"].dtype
        hd = self.d_model // self.num_heads
        return tuple(
            blk.children()["mixer"].make_state(num_slots, dtype)
            if blk.kind == LINEAR
            else make_paged_kv_pool(num_blocks, block, self.num_heads, hd,
                                    dtype)
            for _, blk in self._blocks())

    def slot_resident(self, caches):
        """`caches`' structure with True at each leaf that is resident by
        slot (leading axis `num_slots`) and False at each block pool."""
        return tuple(jax.tree.map(lambda _: blk.kind == LINEAR, c)
                     for (_, blk), c in zip(self._blocks(), caches))

    def paged_hidden(self, params, caches, tokens, positions, block_table,
                     lengths, decode=False, chunk=None):
        """Hidden states of one chunk a slot: tokens/positions (S, C)
        int32, block_table (S, M) int32, lengths (S,) int32 = valid
        leading tokens a row (0 = inactive). `decode` says the chunk is a
        step's one token: the linear layers then take their recurrence and
        not the chunk form, which a one-token prompt chunk keeps. `chunk`
        (the optional carrying form, nn/attention.carried_rows) is a prompt
        chunk of streaming slots that the same pass computes: every product
        reads its weights once for both, each linear layer runs both of its
        forms. Returns (x (S, C, d), the new caches)."""
        S = tokens.shape[0]
        tokens, positions, parts = carried_rows(
            tokens, positions, block_table, lengths, decode, chunk)
        x = params["embed"][tokens]
        new = []
        for (name, blk), cache in zip(self._blocks(), caches):
            mixer, p = blk.children()["mixer"], params[name]["mixer"]
            if blk.kind == FULL:
                mixed, cache = mixer.paged_step(
                    p, x, cache, positions, block_table, lengths, parts)
            elif parts:
                mixed, cache = mixer.parts_step(p, x, cache, parts)
            elif decode:
                mixed, cache = mixer.decode_step(
                    p, x, cache, positions[:, 0], lengths > 0)
            else:
                mixed, cache = mixer.prefill_step(
                    p, x, cache, positions, lengths)
            x = blk._rest(params[name], x, mixed)
            new.append(cache)
        if parts:
            x = x[0, -S:, None]         # the step's rows, one token each
        return x, tuple(new)

    def head_logits(self, params, x):
        """x (S, 1, d) -> logits (S, V): the final norm and the head."""
        return self._logits(params, x[:, -1])
