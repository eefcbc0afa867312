"""Latent (MLA) attention with a learned sparse indexer, over paged pools
(no reference analogue; DeepSeek-V2's multi-head latent attention,
arXiv:2405.04434, with the "lightning indexer" of DeepSeek-V3.2's sparse
attention, as the `glm_moe_dsa` configurations state them).

What a token leaves behind is ONE row a layer, not keys and values a head:

    cQ = RMSNorm(W_dq x)                       the query's latent
    q_i = W_uq,i cQ = [qC_i ; qR_i]            heads of nope + rope dims
    [cKV ; kR] = W_dkv x ; cKV <- RMSNorm(cKV) ; rope on kR (one for all
                                               heads) and on every qR_i
    [kC_i,s ; v_i,s] = W_ukv,i cKV_s           never stored
    score_i(t, s) = (qC_i . kC_i,s + qR_i . kR_s) / sqrt(nope + rope)

so the cache holds `[cKV ; kR]`, `kv_lora_rank + qk_rope_head_dim` values a
token. A `full` layer also keeps an indexer key `kI_s = rope(LayerNorm(W_ik
x_s))` a token and scores every earlier token for each query,

    I(t, s) = sum_j w_t,j relu(qI_t,j . kI_s),   qI = rope(W_iq cQ),
    w_t = W_iw x_t * (heads^-1/2 * head_dim^-1/2),              in float32

and attention admits only the `index_topk` largest `I(t, .)` among `s <= t`
(all of them while there are no more). A `shared` layer has no indexer and
admits what the last `full` layer before it admitted.

The same function is computed two ways:

  * `SparseLatentAttention.dense` - whole sequences with keys and values
    expanded a head, scores over every pair under the mask of what was
    admitted: `apply`, the form that is easy to read;
  * `SparseLatentAttention.paged_step` - a slot batch against the pools,
    for the decode engine (serve/decode.py), ABSORBED: `W_uk,i` goes into
    the query (`qC_i . W_uk,i cKV = (W_uk,i^T qC_i) . cKV`) and `W_uv,i`
    comes after the weighted sum, so every head reads the same
    `[cKV ; kR]` row and nothing a head wide is ever made of the context.
    The indexer scores a slot's context where the block table says it
    lies. Then, by what is cheaper on the chip (PERF.md section 5):
    a DECODE step, one query a slot, takes the `index_topk` best positions
    (`lax.top_k`), turns them into pool rows through the block table and
    gathers and attends those rows alone, all slots at once, so its work
    does not grow with the context; a PROMPT CHUNK, many queries a slot
    whose selections differ, finds each query's `index_topk`-th best score
    (a binary search over the scores' bits) and attends the slot's context
    under the mask of what scored at least that, a slot after another and
    in blocks of `KEY_BLOCK` keys with an online softmax, as far as the
    chunk's last token and no further. Both admit the same set (ties at the
    threshold go to the earlier position, as `top_k` breaks them). No array
    over the whole pool is made, nor one of queries x heads x context.

The pools' layout is decided here: `(pool blocks, tokens a block, width)`,
written a whole block at a time through the block table as
nn/attention.paged_slot_cached_attend writes its K/V pool, the width
rounded up to whole tiles of `LANES` values (a latent row of 512 + 64
lies in 640, its tail zero): for a width that is not, the device keeps the
pool in another order than the programs read it in and copies the whole
pool on the way in and on the way out of every program (2.8 ms a copy at
4 GB of pools, 56 of a decode step's 88 ms: PERF.md section 6, PR 31).
Rotary position embedding is over interleaved pairs (`rope_interleave`).
Indexer scores, every softmax and every norm's statistics are float32
whatever the weights are.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import Module
from bigdl_tpu.nn.attention import SlotRows, join_rows, split_rows
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.normalization import LayerNormalization, RMSNorm

NEG_INF = -1e30
# queries of a prompt chunk that the indexer scores together (queries x
# index heads x context, float32, is alive for one such block), and keys a
# prompt chunk attends together
QUERY_BLOCK = 64
KEY_BLOCK = 1024
# a pool's rows are whole tiles of so many values
LANES = 128
_NEEDS_SELECTION = ("a layer without an indexer needs the selection of the "
                    "full layer before it")


def rotary_interleaved(x, positions, theta: float):
    """Rotary position embedding over interleaved pairs `(x[2i], x[2i+1])`,
    pair `i` turned by `positions * theta^(-2i/r)`. x (N, T, r) or
    (N, T, H, r); positions (N, T). Computed in float32."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = positions.astype(jnp.float32)[..., None] * inv    # (N, T, r/2)
    if x.ndim == 4:
        ang = ang[:, :, None, :]
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------- pools
def pool_width(width: int) -> int:
    """The width of a pool whose rows hold `width` values: whole tiles."""
    return -(-width // LANES) * LANES


def make_paged_pool(num_blocks: int, block: int, width: int, dtype):
    """A zero pool of one row a token, `(P, B, pool_width(width))`."""
    return jnp.zeros((num_blocks, block, pool_width(width)), dtype)


def _widened(rows, width: int):
    """`rows` (..., w) with zeros behind them up to `width`."""
    pad = width - rows.shape[-1]
    return rows if not pad else jnp.concatenate(
        [rows, jnp.zeros(rows.shape[:-1] + (pad,), rows.dtype)], axis=-1)


def paged_pool_write(pool, chunk, positions, block_table, lengths):
    """A chunk's rows into the blocks they straddle, a whole block a
    window, as nn/attention.paged_slot_cached_attend writes K and V. pool
    (P, B, W); chunk (N, T, W); positions (N, T) consecutive a row;
    block_table (N, M), -1 = not acquired; lengths (N,) = valid leading
    tokens a row: the padded tail, inactive rows and unacquired blocks are
    left out of the write."""
    P, B, W = pool.shape
    N, T, _ = chunk.shape
    M = block_table.shape[1]
    nb = -(-(T - 1) // B) + 1
    start = positions[:, 0]
    m = start[:, None] // B + jnp.arange(nb)                    # (N, nb)
    blk = jnp.take_along_axis(block_table, jnp.clip(m, 0, M - 1), axis=1)
    t = (m * B - start[:, None])[:, :, None] + jnp.arange(B)    # (N, nb, B)
    live = ((t >= 0) & (t < lengths[:, None, None])
            & ((m < M) & (blk >= 0))[:, :, None])
    ids = jnp.where(live.any(-1), blk, P).reshape(-1)
    new = jnp.take_along_axis(
        chunk, jnp.clip(t, 0, T - 1).reshape(N, nb * B, 1), axis=1)
    old = pool[jnp.clip(ids, 0, P - 1)]                     # (N*nb, B, W)
    return pool.at[ids].set(
        jnp.where(live.reshape(N * nb, B, 1), new.reshape(N * nb, B, W),
                  old), mode="drop")


def paged_pool_write_parts(pool, parts, chunks):
    """`paged_pool_write` of every part's chunk (nn/attention.SlotRows),
    one after another: a later part may write into a block an earlier one
    has just written (the same slot's next token)."""
    for part, chunk in zip(parts, chunks):
        pool = paged_pool_write(pool, chunk, part.positions,
                                part.block_table, part.lengths)
    return pool


def gather_context(pool, block_table):
    """A slot's rows in the order of its positions, (N, M*B, W): logical
    block m of row n is pool block `block_table[n, m]`. A block that is not
    acquired reads block 0; the caller masks by position."""
    N, M = block_table.shape
    _, B, W = pool.shape
    return pool[jnp.clip(block_table, 0)].reshape(N, M * B, W)


def pool_rows(block_table, idx, block: int):
    """Positions `idx` (G, Q, K) of the slots whose block-table rows are
    `block_table` (G, M) -> their rows of the pool laid flat, (G, Q, K)."""
    m = jnp.take_along_axis(block_table[:, None, :], idx // block, axis=2)
    return jnp.clip(m, 0) * block + idx % block


# ----------------------------------------------------- indexer, selection
def index_scores(q_idx, w, keys):
    """I(t, s) of a group of queries over its slot's context. q_idx (G, Q,
    Hi, Di); w (G, Q, Hi) float32; keys (G, L, Di). -> (G, Q, L) float32."""
    s = jnp.einsum("gqhd,gld->gqhl", q_idx, keys,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("gqhl,gqh->gql", jax.nn.relu(s), w)


def select_topk(scores, positions, k: int):
    """The `k` best-scored positions `s <= t` of every query: scores (...,
    L) float32, positions (...) int32 -> (..., min(k, L)) int32. Where a
    query has fewer than `k` tokens behind it the rest of its row names
    positions after it, which `idx <= position` tells apart."""
    L = scores.shape[-1]
    if L <= k:
        return jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                                scores.shape)
    seen = jnp.arange(L) <= positions[..., None]
    # over a matrix: the TPU has a top-k for that, and sorts anything else
    best = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf).reshape(-1, L),
                         k)[1]
    return best.reshape(scores.shape[:-1] + (k,))


def admitted_mask(scores, positions, k: int):
    """`select_topk`'s set as a mask: scores (Q, L) float32, positions (Q,)
    -> (Q, L) bool, True at the `k` best-scored `s <= t` (at all of them
    where there are no more). The `k`-th best score of a query is found bit
    by bit, 32 counts over its row; of the scores equal to it the earliest
    positions are admitted, so that exactly `k` are."""
    Q, L = scores.shape
    seen = jnp.arange(L) <= positions[:, None]
    if L <= k:
        return seen
    # float32 -> uint32 keys of the same order
    u = jax.lax.bitcast_convert_type(
        jnp.where(seen, scores, -jnp.inf), jnp.uint32)
    keys = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))

    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= cand[:, None], axis=-1) >= k
        return jnp.where(enough, cand, prefix)
    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((Q,), jnp.uint32))[:, None]
    above, ties = keys > kth, keys == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return seen & (above | (ties & (jnp.cumsum(ties, axis=-1) <= room)))


def attend_masked(q_abs, context, admitted, n_keys, scale, v_width: int):
    """Absorbed attention of one slot's queries over its context under a
    mask, `KEY_BLOCK` keys at a time with an online softmax. q_abs (Q, H,
    W); context (L, W); admitted (Q, L) bool; `n_keys` () int32: keys from
    there on are admitted to no query and are not read. -> (Q, H,
    v_width)."""
    Q, H, _ = q_abs.shape
    L = context.shape[0]
    kb = min(KEY_BLOCK, L)
    pad = -L % kb
    if pad:
        context = jnp.pad(context, ((0, pad), (0, 0)))
        admitted = jnp.pad(admitted, ((0, 0), (0, pad)))

    def block(j, carry):
        o, m, l = carry
        keys = jax.lax.dynamic_slice_in_dim(context, j * kb, kb)
        ok = jax.lax.dynamic_slice_in_dim(admitted, j * kb, kb,
                                          axis=1)[:, None, :]
        s = jnp.einsum("qhw,kw->qhk", q_abs, keys,
                       preferred_element_type=jnp.float32) * scale
        m_new = jnp.maximum(m, jnp.max(jnp.where(ok, s, NEG_INF), axis=-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        o = o * alpha[..., None] + jnp.einsum(
            "qhk,kc->qhc", p.astype(keys.dtype), keys[:, :v_width],
            preferred_element_type=jnp.float32)
        return o, m_new, l * alpha + jnp.sum(p, axis=-1)

    o, _, l = jax.lax.fori_loop(
        0, (n_keys + kb - 1) // kb, block,
        (jnp.zeros((Q, H, v_width), jnp.float32),
         jnp.full((Q, H), NEG_INF, jnp.float32),
         jnp.zeros((Q, H), jnp.float32)))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q_abs.dtype)


def attend_rows(q_abs, pool_flat, rows, valid, scale, v_width: int):
    """Absorbed attention over gathered rows. q_abs (G, Q, H, W); pool_flat
    (P*B, W); rows, valid (G, Q, K). -> (G, Q, H, v_width), the weighted
    sum of the rows' leading `v_width` values (the latent)."""
    got = pool_flat[rows]                                   # (G, Q, K, W)
    s = jnp.einsum("gqhw,gqkw->gqhk", q_abs, got,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(valid[:, :, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("gqhk,gqkc->gqhc", p.astype(got.dtype),
                      got[..., :v_width])


def _rms(norm, params, x):
    return norm.apply(params, {}, x.astype(jnp.float32))[0].astype(x.dtype)


class SparseLatentAttention(Module):
    """One layer's mixer: x (N, T, d_model) -> (N, T, d_model). `indexer`
    (`{"heads", "head_dim"}`) makes it a `full` layer, which scores and
    selects; None a `shared` one, which is handed a selection."""

    def __init__(self, d_model: int, num_heads: int, q_lora_rank: int,
                 kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int, index_topk: int,
                 rope_theta: float, eps: float,
                 indexer: Optional[dict] = None,
                 name: Optional[str] = None):
        super().__init__(name or "SparseLatentAttention")
        self.num_heads, self.kv_rank = num_heads, kv_lora_rank
        self.nope, self.rope, self.v_dim = (qk_nope_head_dim,
                                            qk_rope_head_dim, v_head_dim)
        self.index_topk, self.theta = index_topk, rope_theta
        self.scale = 1.0 / math.sqrt(qk_nope_head_dim + qk_rope_head_dim)
        self.indexer = dict(indexer) if indexer else None
        H = num_heads
        lin = lambda a, b: Linear(a, b, bias=False)            # noqa: E731
        self.add_child("q_a", lin(d_model, q_lora_rank))
        self.add_child("q_norm", RMSNorm(q_lora_rank, eps=eps))
        self.add_child("q_b", lin(q_lora_rank, H * (self.nope + self.rope)))
        self.add_child("kv_a", lin(d_model, kv_lora_rank + self.rope))
        self.add_child("kv_norm", RMSNorm(kv_lora_rank, eps=eps))
        self.add_child("kv_b", lin(kv_lora_rank, H * (self.nope + v_head_dim)))
        self.add_child("o", lin(H * v_head_dim, d_model))
        if self.indexer:
            hi, di = self.indexer["heads"], self.indexer["head_dim"]
            self.add_child("iq", lin(q_lora_rank, hi * di))
            self.add_child("ik", lin(d_model, di))
            self.add_child("ik_norm", LayerNormalization(di, eps=eps))
            self.add_child("iw", lin(d_model, hi))

    @property
    def row_width(self) -> int:
        """Of a latent row as the pool holds it: `[cKV ; kR ; zeros]`."""
        return pool_width(self.kv_rank + self.rope)

    # -------------------------------------------------------- projections
    def _run(self, params, name, x):
        return self.children()[name].apply(params[name], {}, x)[0]

    def _queries(self, params, x, positions):
        """-> (cQ (N, T, qr), qC (N, T, H, nope), qR (N, T, H, rope))."""
        N, T, _ = x.shape
        cq = _rms(self.children()["q_norm"], params["q_norm"],
                  self._run(params, "q_a", x))
        q = self._run(params, "q_b", cq).reshape(
            N, T, self.num_heads, self.nope + self.rope)
        return cq, q[..., :self.nope], rotary_interleaved(
            q[..., self.nope:], positions, self.theta)

    def _latent(self, params, x, positions):
        """The row a token leaves behind: [RMSNorm(cKV) ; rope(kR)]."""
        kv = self._run(params, "kv_a", x)
        c = _rms(self.children()["kv_norm"], params["kv_norm"],
                 kv[..., :self.kv_rank])
        return jnp.concatenate([c, rotary_interleaved(
            kv[..., self.kv_rank:], positions, self.theta)], axis=-1)

    def _index(self, params, x, cq, positions):
        """-> (qI (N, T, Hi, Di), w (N, T, Hi) float32, kI (N, T, Di))."""
        hi, di = self.indexer["heads"], self.indexer["head_dim"]
        N, T, _ = x.shape
        r = self.rope

        def roped(a):
            return jnp.concatenate([rotary_interleaved(
                a[..., :r], positions, self.theta), a[..., r:]], axis=-1)
        q_idx = roped(self._run(params, "iq", cq).reshape(N, T, hi, di))
        k = self.children()["ik_norm"].apply(
            params["ik_norm"], {},
            self._run(params, "ik", x).astype(jnp.float32))[0]
        w = self._run(params, "iw", x).astype(jnp.float32) \
            * (hi ** -0.5 * di ** -0.5)
        return q_idx, w, roped(k.astype(x.dtype))

    def _up(self, params):
        """W_ukv as (kv_rank, H, nope + v): keys' part, values' part."""
        w = params["kv_b"]["weight"].reshape(
            self.kv_rank, self.num_heads, self.nope + self.v_dim)
        return w[..., :self.nope], w[..., self.nope:]

    # -------------------------------------------------------------- dense
    def dense(self, params, x, admitted=None):
        """Whole sequences from position 0, keys and values expanded a
        head. `admitted` (N, T, T) bool is a `shared` layer's selection; a
        `full` layer makes its own. Returns (out, admitted)."""
        N, T, _ = x.shape
        H = self.num_heads
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (N, T))
        cq, q_c, q_r = self._queries(params, x, positions)
        row = self._latent(params, x, positions)
        if self.indexer:
            q_idx, w, k_idx = self._index(params, x, cq, positions)
            idx = select_topk(index_scores(q_idx, w, k_idx), positions,
                              self.index_topk)
            admitted = jnp.zeros((N, T, T), bool).at[
                jnp.arange(N)[:, None, None], jnp.arange(T)[None, :, None],
                idx].set(True) & (jnp.arange(T)[None, :] <=
                                  jnp.arange(T)[:, None])
        elif admitted is None:
            raise ValueError(_NEEDS_SELECTION)
        w_uk, w_uv = self._up(params)
        c = row[..., :self.kv_rank]
        k_c = jnp.einsum("nsl,lhc->nshc", c, w_uk)
        v = jnp.einsum("nsl,lhv->nshv", c, w_uv)
        s = (jnp.einsum("nthc,nshc->nhts", q_c, k_c,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("nthr,nsr->nhts", q_r, row[..., self.kv_rank:],
                          preferred_element_type=jnp.float32)) * self.scale
        p = jax.nn.softmax(jnp.where(admitted[:, None], s, NEG_INF), axis=-1)
        a = jnp.einsum("nhts,nshv->nthv", p.astype(v.dtype), v)
        return self._run(params, "o", a.reshape(N, T, H * self.v_dim)), \
            admitted

    def _apply(self, params, state, x, *, training=False, rng=None):
        return self.dense(params, x)[0], state

    # -------------------------------------------------------------- paged
    def make_pools(self, num_blocks: int, block: int, dtype):
        """The layer's zero pools: `latent`, and `index` in a full layer."""
        pools = {"latent": make_paged_pool(num_blocks, block,
                                           self.row_width, dtype)}
        if self.indexer:
            pools["index"] = make_paged_pool(
                num_blocks, block, self.indexer["head_dim"], dtype)
        return pools

    def paged_step(self, params, x, pools, positions, block_table, lengths,
                   selection=None, decode=False, parts=None):
        """A chunk a slot against the pools: the chunk's rows are written
        through the block table, then every query attends what is admitted
        to it. `decode` says the chunk is a step's one token: the selection
        is then positions, whose rows are gathered, and else a mask over the
        slot's context. `selection` is a `shared` layer's, of the `full`
        layer before it (which makes its own). With `parts`
        (nn/attention.carried_rows) x and positions are their joined
        tokens: the projections are one product over all of them, the pools
        are written once, and each part selects and attends in its own
        form, `selection` being a tuple of theirs. Returns (out, pools,
        selection)."""
        N, T, _ = x.shape
        H = self.num_heads
        joined = parts is not None
        if joined:
            share = lambda *a: split_rows(parts, *a)           # noqa: E731
            selection = selection or (None,) * len(parts)
        else:
            share = lambda *a: [a]                             # noqa: E731
            parts = (SlotRows(positions, block_table, lengths, decode),)
            selection = (selection,)
        cq, q_c, q_r = self._queries(params, x, positions)
        width = self.row_width
        rows = _widened(self._latent(params, x, positions), width)
        latent = paged_pool_write_parts(
            pools["latent"], parts, [r for r, in share(rows)])
        new = {"latent": latent}
        w_uk, w_uv = self._up(params)
        q_abs = _widened(jnp.concatenate(
            [jnp.einsum("nthc,lhc->nthl", q_c, w_uk), q_r], axis=-1), width)
        mine = share(q_abs)
        if self.indexer:
            # the chunk's keys into their pool, then the slots' keys in the
            # order of their positions
            q_idx, w, k_idx = self._index(params, x, cq, positions)
            lanes = pools["index"].shape[-1]
            new["index"] = paged_pool_write_parts(
                pools["index"], parts,
                [k for k, in share(_widened(k_idx, lanes))])
            mine = share(q_abs, _widened(q_idx, lanes), w)
        elif None in selection:
            raise ValueError(_NEEDS_SELECTION)
        outs, selections = [], []
        for part, given, (q_abs_, *scored) in zip(parts, selection, mine):
            index = (*scored, gather_context(
                new["index"], part.block_table)) if scored else None
            if part.decode:
                out, given = self._step_rows(
                    q_abs_, latent, index, part.positions,
                    part.block_table, given)
            else:
                out, given = self._chunk_rows(
                    q_abs_, latent, index, part.positions,
                    part.block_table, part.lengths, given)
            outs.append(out)
            selections.append(given)
        out = join_rows(outs) if joined else outs[0]
        a = jnp.einsum("nthl,lhv->nthv", out, w_uv)
        return self._run(params, "o", a.reshape(N, T, H * self.v_dim)), \
            new, tuple(selections) if joined else selections[0]

    def _step_rows(self, q_abs, latent, index, positions, block_table,
                   selection):
        """Few queries a slot (a decode step's one): the selection is
        (positions (N, T, K), their rows of the pool laid flat), the rows
        are gathered, all slots at once. Every layer's pool is indexed
        alike, so a `shared` layer is handed the rows too."""
        if index is not None:
            idx = select_topk(index_scores(*index), positions,
                              self.index_topk)
            selection = (idx, pool_rows(block_table, idx, latent.shape[1]))
        idx, rows = selection
        return attend_rows(q_abs, latent.reshape(-1, self.row_width), rows,
                           idx <= positions[..., None], self.scale,
                           self.kv_rank), selection

    def _chunk_rows(self, q_abs, latent, index, positions, block_table,
                    lengths, selection):
        """Many queries a slot (a prompt chunk): the selection is a mask
        (N, T, L) over the slot's context, which is attended under it, a
        slot after another."""
        N, T = positions.shape
        if index is not None:
            q_idx, w, keys = index
            Q = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

            def select(at):
                n, q, w_q, pos = at         # a block of one slot's queries
                return admitted_mask(
                    index_scores(q[None], w_q[None], keys[n][None])[0], pos,
                    self.index_topk)
            blocks = lambda a: a.reshape((N * (T // Q), Q)    # noqa: E731
                                         + a.shape[2:])
            selection = jax.lax.map(select, (
                jnp.repeat(jnp.arange(N), T // Q), blocks(q_idx), blocks(w),
                blocks(positions))).reshape(N, T, -1)
        out = jax.lax.map(
            lambda at: attend_masked(*at, self.scale, self.kv_rank),
            (q_abs, gather_context(latent, block_table), selection,
             positions[:, 0] + lengths))
        return out, selection
