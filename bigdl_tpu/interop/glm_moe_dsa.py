"""GLM-5 (`model_type` `glm_moe_dsa`): a causal LM of latent (MLA)
attention with a learned sparse indexer (nn/latent_attention.py) and of
shared-and-routed gated experts (nn/experts.py), on this framework's
primitives, with the paged decode contract of serve/decode.py.

A block is pre-norm, `h = x + Attn(RMSNorm(x))`, `out = h + MLP(RMSNorm(h))`,
with a final RMSNorm and an untied head, no bias anywhere. `indexer_types`
says of each block whether its attention scores and selects (`full`) or is
handed the selection of the last `full` block before it (`shared`);
`mlp_layer_types` whether its MLP is one SwiGLU (`dense`) or the expert
layer (`sparse`). Every norm's statistics, the router's and the indexer's
scores and every softmax are float32.

What a chip holds may be a share: `expert_share=(first, held)` of the
routed experts (the router stays as wide as published; what the absent
experts would add is left out, nn/experts.py) and `vocab_share=(first,
held)` rows of both tables (a sliced vocabulary is a smaller one: ids,
logits and sampling are over the slice, row 0 of the tables being id
`first` of the published vocabulary).

What a sequence leaves behind is pooled by block under one block table,
every leaf of it: a `{"latent"}` pool a block, with an `{"index"}` pool
beside it in a `full` block, so `BlockPool` and the prefix cache serve the
model as they serve keys and values. The last entry of the cache pytree
counts what the expert layers did (`counter_names`: token-expert pairs the
held experts computed, tokens that went through an expert layer, held
experts that got a token in a call), which `step_counters` hands the decode
step to send back with its tokens.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.core import init as initializers
from bigdl_tpu.core.module import Module, ParamSpec
from bigdl_tpu.nn.attention import carried_rows, join_rows
from bigdl_tpu.nn.experts import GatedExperts
from bigdl_tpu.nn.latent_attention import SparseLatentAttention
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.normalization import RMSNorm

FULL, SHARED = "full", "shared"
DENSE, SPARSE = "dense", "sparse"


def _rms(norm, params, x):
    """`RMSNorm` with float32 statistics whatever `x` is."""
    return norm.apply(params, {}, x.astype(jnp.float32))[0].astype(x.dtype)


class GlmMoeDsaBlock(Module):
    """One block: the mixer its `indexer` kind names, then the MLP its
    `mlp` kind names. `attention` holds `SparseLatentAttention`'s sizes
    (with `indexer`, which a `shared` block drops), `experts`
    `GatedExperts`'."""

    def __init__(self, indexer: str, mlp: str, d_model: int, d_ff: int,
                 eps: float, attention: dict, experts: dict,
                 name: Optional[str] = None):
        super().__init__(name or "GlmMoeDsaBlock")
        if indexer not in (FULL, SHARED) or mlp not in (DENSE, SPARSE):
            raise ValueError(f"a block is {FULL!r} or {SHARED!r} and "
                             f"{DENSE!r} or {SPARSE!r}, not {indexer!r} "
                             f"and {mlp!r}")
        self.indexer, self.mlp = indexer, mlp
        attention = dict(attention)
        if indexer == SHARED:
            attention.pop("indexer", None)
        self.add_child("attn_norm", RMSNorm(d_model, eps=eps))
        self.add_child("attn", SparseLatentAttention(d_model, eps=eps,
                                                     **attention))
        self.add_child("mlp_norm", RMSNorm(d_model, eps=eps))
        if mlp == SPARSE:
            self.add_child("experts", GatedExperts(d_model, **experts))
        else:
            self.add_child("gate", Linear(d_model, d_ff, bias=False))
            self.add_child("up", Linear(d_model, d_ff, bias=False))
            self.add_child("down", Linear(d_ff, d_model, bias=False))

    def normed(self, params, x):
        return _rms(self.children()["attn_norm"], params["attn_norm"], x)

    def rest(self, params, x, mixed, valid=None):
        """The block after its mixer -> (out, the expert layer's counts (2,)
        int32, `GatedExperts.mixed`'s)."""
        c = self.children()
        h = x + mixed
        y = _rms(c["mlp_norm"], params["mlp_norm"], h)
        if self.mlp == SPARSE:
            f, counts = c["experts"].mixed(params["experts"], y, valid)
            return h + f, counts
        g, _ = c["gate"].apply(params["gate"], {}, y)
        u, _ = c["up"].apply(params["up"], {}, y)
        f, _ = c["down"].apply(params["down"], {}, jax.nn.silu(g) * u)
        return h + f, jnp.zeros((2,), jnp.int32)


class GlmMoeDsaLM(Module):
    """apply(params, state, tokens (B, T) int32) -> (B, T, vocab) logits
    over the vocabulary rows held. `indexer_types` and `mlp_layer_types`
    name each block held; `attention` the sizes of
    `SparseLatentAttention` (`num_heads`, `q_lora_rank`, `kv_lora_rank`,
    `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`, `index_topk`,
    `rope_theta`, `indexer` = `{"heads", "head_dim"}`); `experts` those of
    `GatedExperts` (`d_expert`, `num_experts`, `top_k`, `num_shared`,
    `scaling`). `param_dtype` is the dtype `init` makes the parameters in
    (and the activations follow it)."""

    # what the cache's last entry counts, running totals (`step_counters`)
    counter_names = ("expert_pairs", "expert_loads", "expert_tokens")

    def __init__(self, vocab_size: int, d_model: int, d_ff: int,
                 indexer_types: Sequence[str],
                 mlp_layer_types: Sequence[str], attention: dict,
                 experts: dict, max_positions: int, eps: float = 1e-5,
                 expert_share: Optional[Tuple[int, int]] = None,
                 vocab_share: Optional[Tuple[int, int]] = None,
                 eos_id=None, param_dtype=jnp.float32,
                 name: Optional[str] = None):
        super().__init__(name or "GlmMoeDsaLM")
        self.vocab_share = tuple(vocab_share or (0, vocab_size))
        if not (0 <= self.vocab_share[0] and self.vocab_share[1] >= 1
                and sum(self.vocab_share) <= vocab_size):
            raise ValueError(f"vocab_share {self.vocab_share} is no slice "
                             f"of {vocab_size} rows")
        # the vocabulary as this model sees it: the rows it holds
        self.vocab_size, self.d_model = self.vocab_share[1], d_model
        self.indexer_types = tuple(indexer_types)
        self.mlp_layer_types = tuple(mlp_layer_types)
        if len(self.indexer_types) != len(self.mlp_layer_types) \
                or self.indexer_types[:1] != (FULL,):
            raise ValueError(
                "indexer_types and mlp_layer_types name the same blocks, "
                f"the first of them {FULL!r}: a {SHARED!r} block needs the "
                "selection of one before it")
        self.num_layers = len(self.indexer_types)
        self.index_topk = attention["index_topk"]
        # the decode engine's name for the longest sequence a slot holds
        self.n_positions = max_positions
        self.eos_id, self.param_dtype = eos_id, jnp.dtype(param_dtype)
        experts = dict(experts, expert_share=expert_share)
        for i, (kind, mlp) in enumerate(zip(self.indexer_types,
                                            self.mlp_layer_types)):
            self.add_child(f"l{i}", GlmMoeDsaBlock(
                kind, mlp, d_model, d_ff, eps, attention, experts))
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
        admitted = None
        for name, blk in self._blocks():
            mixed, admitted = blk.children()["attn"].dense(
                params[name]["attn"], blk.normed(params[name], x), admitted)
            x, _ = blk.rest(params[name], x, mixed)
        return self._logits(params, x), state

    # -------------------------------------------------- paged decoding
    # What serve/decode.py composes its programs from. Every leaf of the
    # cache is pooled by block but the last, which counts.
    def make_paged_slot_caches(self, params, num_blocks: int, block: int):
        """A block's zero pools each, then the expert layers' counts."""
        dtype = params["embed"].dtype
        return tuple(blk.children()["attn"].make_pools(num_blocks, block,
                                                       dtype)
                     for _, blk in self._blocks()) \
            + (jnp.zeros((len(self.counter_names),), jnp.int32),)

    def step_counters(self, caches):
        """`counter_names`' running totals (int32, they wrap), as the cache
        carries them: a decode step sends them back beside its tokens."""
        return caches[-1]

    def paged_hidden(self, params, caches, tokens, positions, block_table,
                     lengths, decode=False, chunk=None):
        """Hidden states of one chunk a slot: tokens/positions (S, C)
        int32, block_table (S, M) int32, lengths (S,) int32 = valid
        leading tokens a row (0 = inactive). `decode` says the chunk is a
        step's one token: attention then gathers the rows it selects, and
        else attends a slot's context under a mask (nn/latent_attention.py).
        `chunk` (the optional carrying form, nn/attention.carried_rows) is a
        prompt chunk of streaming slots that the same pass computes: every
        product and the experts read their weights once for both, attention
        takes each in its own form. Returns (x (S, C, d), the new caches)."""
        def valid_of(tokens, lengths):
            return jnp.arange(tokens.shape[1])[None, :] < lengths[:, None]
        S = tokens.shape[0]
        valid = valid_of(tokens, lengths)
        if chunk is not None:
            valid = join_rows([valid_of(chunk[0], chunk[3]), valid])
        tokens, positions, parts = carried_rows(
            tokens, positions, block_table, lengths, decode, chunk)
        x = params["embed"][tokens]
        new, selection = [], None
        counts = jnp.zeros((2,), jnp.int32)
        for (name, blk), pools in zip(self._blocks(), caches):
            mixed, pools, selection = blk.children()["attn"].paged_step(
                params[name]["attn"], blk.normed(params[name], x), pools,
                positions, block_table, lengths, selection, decode, parts)
            x, n = blk.rest(params[name], x, mixed, valid)
            counts += n
            new.append(pools)
        if parts:
            x = x[0, -S:, None]         # the step's rows, one token each
        through = jnp.sum(valid, dtype=jnp.int32) \
            * self.mlp_layer_types.count(SPARSE)
        return x, tuple(new) + (
            caches[-1] + jnp.append(counts, through),)

    def head_logits(self, params, x):
        """x (S, 1, d) -> logits (S, V): the final norm and the head."""
        return self._logits(params, x[:, -1])
