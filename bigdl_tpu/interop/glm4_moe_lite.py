"""GLM-4.7-Flash (`model_type` `glm4_moe_lite`): GLM-5's block
(interop/glm_moe_dsa.py) with latent (MLA) attention that admits every
earlier position (no indexer), routed and shared gated experts, and a
multi-token prediction (MTP) module that drafts the token after next.

The MTP module of depth 1 (DeepSeek-V3, arXiv:2412.19437, section 2.2):
with `h_j` the main model's last hidden state at position `j` after its
final norm,

    u_j = W_eh [RMSNorm_e(Emb(t_{j+1})) ; RMSNorm_h(h_j)]      (2d -> d)
    g_j = Block_MTP(u_j)        a routed block with its own latent pool,
                                attending u_0..u_j, rope position j
    draft_{j+2} = argmax Head(RMSNorm_s(g_j))

the embedding and the head being the main model's. The row the pair
`(h_j, t_{j+1})` leaves in the module's pool lies at position j, under the
slots' one block table beside the main layers' pools; since it depends on
`t_{j+1}`, a block's last row depends on the token after the block, which
the prefix cache covers by keying a block with that token too
(serve/decode.py `PrefixCache.chain_keys`, `lookahead`).

The decode contract's optional draft piece (docs/serving.md "What a served
model provides"), which serve/decode.py composes into a step that verifies
one drafted token a slot:

  * `paged_hidden(..., follow=)`: with a prompt chunk, the token after each
    of its tokens; the module's rows of those pairs are written and nothing
    else of it is computed (no later prompt token reads its output);
  * `paged_draft(params, caches, x, follow, positions, block_table,
    lengths)`: the module over a step's pairs (x the main model's hidden
    states of the step's rows, `follow` the tokens verified after them),
    their rows written and attended -> (g, caches);
  * `draft_logits(params, g)`: its norm and the shared head.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp

from bigdl_tpu.interop.glm_moe_dsa import (SPARSE, GlmMoeDsaBlock,
                                           GlmMoeDsaLM, _rms)
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.normalization import RMSNorm


class Glm4MoeLiteLM(GlmMoeDsaLM):
    """`GlmMoeDsaLM` with no indexer (`attention` without `index_topk` or
    `indexer`), every expert held, the vocabulary rows `vocab_share` holds
    (as GlmMoeDsaLM's), and the MTP module, whose embedding and head are
    those rows too."""

    def __init__(self, vocab_size: int, d_model: int, d_ff: int,
                 mlp_layer_types: Sequence[str], attention: dict,
                 experts: dict, max_positions: int, eps: float = 1e-5,
                 vocab_share=None, eos_id=None,
                 param_dtype=jnp.float32, name: Optional[str] = None):
        attention = dict(attention, index_topk=None, indexer=None)
        super().__init__(vocab_size, d_model, d_ff, None, mlp_layer_types,
                         attention, experts, max_positions, eps=eps,
                         vocab_share=vocab_share, eos_id=eos_id,
                         param_dtype=param_dtype,
                         name=name or "Glm4MoeLiteLM")
        self.add_child("mtp_enorm", RMSNorm(d_model, eps=eps))
        self.add_child("mtp_hnorm", RMSNorm(d_model, eps=eps))
        self.add_child("mtp_eh", Linear(2 * d_model, d_model, bias=False))
        self.add_child("mtp", GlmMoeDsaBlock(None, SPARSE, d_model, d_ff, eps,
                                             attention, experts))
        self.add_child("mtp_norm", RMSNorm(d_model, eps=eps))

    # ------------------------------------------------------------- dense
    def mtp_apply(self, params, tokens):
        """Teacher-forced draft logits over whole sequences: tokens (B, T)
        -> (B, T - 1, vocab), row j from the pair (h_j, t_{j+1}), the
        logits of t_{j+2}."""
        x = self._hidden(params, tokens)
        u = self._mtp_input(params, x[:, :-1], tokens[:, 1:])
        blk = self.children()["mtp"]
        mixed, _ = blk.children()["attn"].dense(
            params["mtp"]["attn"], blk.normed(params["mtp"], u))
        g, _ = blk.rest(params["mtp"], u, mixed)
        return self._draft(params, g)

    def _mtp_input(self, params, x, follow):
        """u = W_eh [RMSNorm_e(Emb(follow)) ; RMSNorm_h(final norm(x))]."""
        c = self.children()
        h = _rms(c["mtp_hnorm"], params["mtp_hnorm"],
                 _rms(c["norm"], params["norm"], x))
        e = _rms(c["mtp_enorm"], params["mtp_enorm"],
                 params["embed"][follow])
        return c["mtp_eh"].apply(params["mtp_eh"], {},
                                 jnp.concatenate([e, h], axis=-1))[0]

    def _draft(self, params, g):
        g = _rms(self.children()["mtp_norm"], params["mtp_norm"], g)
        return g @ params["lm_head"].T

    # -------------------------------------------------- paged decoding
    def make_paged_slot_caches(self, params, num_blocks: int, block: int):
        """The main layers' pools, the MTP module's, then the counts."""
        caches = super().make_paged_slot_caches(params, num_blocks, block)
        pools = self.children()["mtp"].children()["attn"].make_pools(
            num_blocks, block, params["embed"].dtype)
        return caches[:-1] + (pools,) + caches[-1:]

    def paged_hidden(self, params, caches, tokens, positions, block_table,
                     lengths, decode=False, chunk=None, follow=None):
        """`GlmMoeDsaLM.paged_hidden`; with `follow` (R, C) int32, the
        token after each prompt token of the carried chunk (or, without
        one, of the rows, a prompt chunk), whose pairs' rows go into the
        MTP module's pool."""
        x, caches, joined = self._paged_pass(
            params, caches, tokens, positions, block_table, lengths, decode,
            chunk)
        if follow is None:
            return x, caches
        if chunk is not None:
            c_tokens, positions, block_table, lengths, _ = chunk
            R, C = c_tokens.shape
            h = joined[0, :R * C].reshape(R, C, -1)
        else:
            h = x
        blk, L = self.children()["mtp"], self.num_layers
        u = self._mtp_input(params, h, follow)
        pools = blk.children()["attn"].paged_write(
            params["mtp"]["attn"], blk.normed(params["mtp"], u), caches[L],
            positions, block_table, lengths)
        return x, caches[:L] + (pools,) + caches[L + 1:]

    def paged_draft(self, params, caches, x, follow, positions, block_table,
                    lengths):
        """The MTP module over a step's pairs: x (S, T, d) the main model's
        hidden states of the step's rows (`paged_hidden`'s), follow (S, T)
        the token verified after each, lengths (S,) the valid pairs a row.
        Their rows are written and attended. -> (g (S, T, d), caches). Its
        experts add nothing to the model's counts (`step_counters`)."""
        blk, L = self.children()["mtp"], self.num_layers
        u = self._mtp_input(params, x, follow)
        mixed, pools, _ = blk.children()["attn"].paged_step(
            params["mtp"]["attn"], blk.normed(params["mtp"], u), caches[L],
            positions, block_table, lengths, decode=True)
        valid = jnp.arange(x.shape[1])[None, :] < lengths[:, None]
        g, _ = blk.rest(params["mtp"], u, mixed, valid)
        return g, caches[:L] + (pools,) + caches[L + 1:]

    def draft_logits(self, params, g):
        """g (S, 1, d) -> the draft's logits (S, V)."""
        return self._draft(params, g[:, -1])
