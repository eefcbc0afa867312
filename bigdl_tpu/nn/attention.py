"""Attention / Transformer stack — the TPU-native analogue of the
reference's transformer LM (reference: nn/Transformer.scala:53-105,
nn/Attention.scala, nn/FeedForwardNetwork.scala, nn/LayerNormalization.scala,
nn/TransformerOperation.scala).

TPU-first design:
  * attention is one fused softmax(QK^T/sqrt(d))V expression — XLA fuses the
    scale/mask/softmax chain into the two MXU matmuls (the reference builds
    it from ~10 separate modules);
  * heads live in one packed (d_model, d_model) projection per Q/K/V so each
    step is a single large gemm;
  * long-context paths: `blockwise_attention` (lax.scan over KV blocks —
    O(block) memory on one chip) and `parallel.ring.ring_attention`
    (sequence-parallel ring over the 'seq' mesh axis). The reference has no
    long-context machinery at all (SURVEY §5 "Long-context: Absent") — this
    is parity-plus, designed in from the start.
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.core import init as initializers
from bigdl_tpu.core.module import Module, ParamSpec
from bigdl_tpu.nn.normalization import LayerNormalization
from bigdl_tpu.nn.linear import Linear

NEG_INF = -1e9


def _inline_dropout(x, rate, training, rng, layer):
    """Inverted dropout for layers that fold dropout into a fused block.
    Same contract as nn.Dropout: training with a nonzero rate requires rng."""
    if not training or rate <= 0.0:
        return x
    if rng is None:
        raise ValueError(
            f"{layer.name}: dropout={rate} in training mode needs rng= "
            f"(pass rng to apply, or set dropout=0)")
    keep = 1.0 - rate
    return x * jax.random.bernoulli(rng, keep, x.shape) / keep


def dot_product_attention(q, k, v, mask=None, *, scale: Optional[float] = None):
    """softmax(q k^T * scale + mask) v over the last two dims.

    q: (..., Tq, d), k/v: (..., Tk, d); mask broadcastable to (..., Tq, Tk)
    with 1/True = attend. Softmax runs in fp32 for bf16 inputs (TPU-safe)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("...qk,...kd->...qd", weights, v)


def online_softmax_step(q, kb, vb, o, m, l, scale, pos_mask=None):
    """One online-softmax accumulation step over a KV block — the shared
    numerical core of :func:`blockwise_attention` and
    `parallel.ring.ring_attention`. Carries (o, m, l) in fp32; `pos_mask`
    broadcastable to the (…, Tq, Tk_block) logits, True = attend."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kb).astype(jnp.float32) * scale
    if pos_mask is not None:
        s = jnp.where(pos_mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb).astype(jnp.float32)
    return o_new, m_new, l_new


def online_softmax_finish(o, l, dtype):
    """Normalize the accumulated output; fully-masked rows (l == 0) yield 0."""
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def blockwise_attention(q, k, v, *, block_size: int, causal: bool = False,
                        scale: Optional[float] = None,
                        q_offset: Optional[int] = None):
    """Memory-efficient attention: lax.scan over KV blocks with online
    softmax (max/sum carried in fp32) — peak memory O(Tq*block) instead of
    O(Tq*Tk). Numerically identical to dense attention.

    q: (B, H, Tq, d), k/v: (B, H, Tk, d). Tk must divide by block_size.
    `q_offset` positions the queries within the key sequence for causal
    masking (default Tk - Tq: queries are the LAST rows, the KV-cache
    decode convention)."""
    B, H, Tq, d = q.shape
    Tk = k.shape[2]
    if Tk % block_size != 0:
        raise ValueError(f"Tk={Tk} must divide by block_size={block_size}")
    nblk = Tk // block_size
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if q_offset is None:
        q_offset = Tk - Tq

    kb = k.reshape(B, H, nblk, block_size, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, nblk, block_size, d).transpose(2, 0, 1, 3, 4)
    q_pos = q_offset + jnp.arange(Tq)

    def body(carry, inp):
        o, m, l = carry            # o:(B,H,Tq,d) m,l:(B,H,Tq)
        blk_idx, kblk, vblk = inp
        pos_mask = None
        if causal:
            k_pos = blk_idx * block_size + jnp.arange(block_size)
            pos_mask = q_pos[:, None] >= k_pos[None, :]
        return online_softmax_step(q, kblk, vblk, o, m, l, scale,
                                   pos_mask), None

    o0 = jnp.zeros((B, H, Tq, d), jnp.float32)
    m0 = jnp.full((B, H, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    (o, m, l), _ = jax.lax.scan(
        body, (o0, m0, l0), (jnp.arange(nblk), kb, vb))
    return online_softmax_finish(o, l, q.dtype)


def causal_mask(tq: int, tk: Optional[int] = None, dtype=bool):
    """Lower-triangular (1, 1, Tq, Tk) mask. With tk > tq, queries sit at
    the END of the key sequence (KV-cache decode convention)."""
    tk = tk if tk is not None else tq
    q_pos = (tk - tq) + jnp.arange(tq)
    return (q_pos[:, None] >= jnp.arange(tk)[None, :]).astype(dtype)[None, None]


def padding_mask(lengths, t: int):
    """(B, 1, 1, T) mask from per-row valid lengths."""
    return (jnp.arange(t)[None, :] < lengths[:, None])[:, None, None, :]


def rotary_embedding(x, theta: float = 10000.0, positions=None):
    """Rotary position embedding, rotate-half convention (LLaMA/HF
    layout: the head dim splits into two contiguous halves, not
    interleaved pairs). x: (B, H, T, hd). `positions` is either a (T,)
    vector shared by every row or a (B, T) matrix of PER-ROW absolute
    positions (the slot-decode path, where each KV slot sits at its own
    sequence offset). No reference analogue — RoPE postdates it;
    standard for modern LMs."""
    B, H, T, hd = x.shape
    if positions is None:
        positions = jnp.arange(T)
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2) / hd))       # (hd/2,)
    ang = positions[..., :, None] * inv                # (..., T, hd/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)   # (..., T, hd)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    if cos.ndim == 3:          # (B, T, hd) -> broadcast over the head dim
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x * cos + rotated * sin).astype(x.dtype)


def cached_attend(q_heads, k_chunk, v_chunk, ck, cv, start):
    """Shared incremental-decode attention core (used by
    TransformerLayer.cached_step and the HF bridge's LlamaBlock): write
    this chunk's K/V into the caches at [start, start+T), build the
    causal-over-cache mask, and attend. q_heads (N, H, T, hd);
    k_chunk/v_chunk (N, T, Hc, hd) with Hc == H or a grouped divisor
    (GQA — repeated up to H here). Returns ((N, T, H*hd), new_ck,
    new_cv)."""
    ck = jax.lax.dynamic_update_slice(ck, k_chunk, (0, start, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v_chunk, (0, start, 0, 0))
    N, H, T, hd = q_heads.shape
    L, Hc = ck.shape[1], ck.shape[2]
    fk = ck.transpose(0, 2, 1, 3)
    fv = cv.transpose(0, 2, 1, 3)
    if Hc != H:
        fk = jnp.repeat(fk, H // Hc, axis=1)
        fv = jnp.repeat(fv, H // Hc, axis=1)
    mask = (jnp.arange(L)[None, :] <=
            (start + jnp.arange(T))[:, None])   # causal + cache tail
    a = dot_product_attention(q_heads, fk, fv, mask)
    return a.transpose(0, 2, 1, 3).reshape(N, T, H * hd), ck, cv


def slot_cached_attend(q_heads, k_chunk, v_chunk, ck, cv, positions):
    """`cached_attend` batched over a SLOT dimension with per-row start
    offsets over one dense cache row a slot — the reference that
    `paged_slot_cached_attend`, which serve/decode.py serves from, is
    tested against (tests/test_attention.py, tests/test_decode.py): row n
    of the batch is an independent sequence sitting at its own absolute
    positions `positions[n]` (N, T) int32, so its chunk is written at
    `[positions[n, 0], positions[n, 0] + T)` of ITS cache row and
    attends causally over its own prefix only.

    Per-row numerics are bit-identical to `cached_attend` with the same
    scalar start (same write, same mask values, same softmax chain) —
    the iteration-level parity oracle in tests/test_decode.py depends on
    this. Entries past a row's frontier are masked to NEG_INF *before*
    the softmax, so stale/poisoned cache content beyond the frontier
    contributes exactly zero (the PR 5/8 valid-mask discipline applied
    along the sequence axis). Masking INACTIVE rows entirely is the
    caller's job.

    q_heads (N, H, T, hd); k_chunk/v_chunk (N, T, Hc, hd) with Hc == H
    or a grouped divisor (GQA). Returns ((N, T, H*hd), new_ck, new_cv).
    """
    starts = positions[:, 0]
    upd = jax.vmap(
        lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (s, 0, 0)))
    ck = upd(ck, k_chunk, starts)
    cv = upd(cv, v_chunk, starts)
    N, H, T, hd = q_heads.shape
    L, Hc = ck.shape[1], ck.shape[2]
    fk = ck.transpose(0, 2, 1, 3)
    fv = cv.transpose(0, 2, 1, 3)
    if Hc != H:
        fk = jnp.repeat(fk, H // Hc, axis=1)
        fv = jnp.repeat(fv, H // Hc, axis=1)
    # (N, 1, T, L): per-row causal-over-cache frontier
    mask = (jnp.arange(L)[None, None, :] <= positions[:, :, None])[:, None]
    a = dot_product_attention(q_heads, fk, fv, mask)
    return a.transpose(0, 2, 1, 3).reshape(N, T, H * hd), ck, cv


# The paged KV pool's layout is decided here and nowhere else: one array
# per layer, (Hc, P, B, 2*hd) = (KV heads, pool blocks, tokens a block,
# K's head_dim lanes then V's). Heads lead because they are the batch
# dimension of both attention matmuls; the block dimension comes next so
# that a whole block is one contiguous (B, 2*hd) window per head, which the
# TPU compiler scatters in place; K and V share the minor dimension so that
# a 64-wide head fills the 128 lanes of a tile and the device keeps the
# array row-major (a (…, 64) minor dimension makes it pick another layout
# and relay the pool out around every write and read). serve/decode.py
# shards PAGED_POOL_BLOCK_AXIS under kv_shard.
PAGED_POOL_BLOCK_AXIS = 1


def make_paged_kv_pool(num_blocks: int, block: int, kv_heads: int,
                       head_dim: int, dtype):
    """One layer's zero paged KV pool, (Hc, P, B, 2*hd)."""
    return jnp.zeros((kv_heads, num_blocks, block, 2 * head_dim), dtype)


def paged_slot_cached_attend(q_heads, k_chunk, v_chunk, kv_pool, positions,
                             block_table, lengths):
    """`slot_cached_attend` over a PAGED KV pool (vLLM's PagedAttention
    discipline), served from the pool where it lies: instead of one dense
    (N, L, Hc, hd) cache row per slot, K/V live in a shared pool of
    fixed-size blocks (`make_paged_kv_pool`) and each slot owns an int32
    `block_table` row (N, M) mapping its m-th logical block to a pool
    block (-1 = not acquired). No per-slot copy of K or V is made:

    * **write** - the chunk's K/V go into the (at most
      ceil((T-1)/B)+1) blocks a row's run of T positions straddles, one
      whole block a window: the touched blocks are read, the chunk's
      lanes put over them, and the blocks scattered back along the block
      dimension, which the donated pool takes in place.
    * **read** - every query attends over the WHOLE pool under an
      ownership mask: lane (p, b) is absolute position m*B+b of slot n
      iff `block_table[n, m] == p` (a block shared through the prefix
      cache is owned by several slots), and is visible to the query at
      position q iff it is owned and m*B+b <= q. Unowned, stale and
      future lanes are masked to NEG_INF *before* the softmax and their
      exp underflows to exactly 0.0, as in the dense path; only the
      order of summation differs from it (pool order, not position
      order).

    `positions` (N, T) int32 are a row's absolute positions, consecutive
    from `positions[:, 0]`; `lengths` (N,) int32 is the count of VALID
    leading tokens in this chunk per row (0 for inactive rows): padded
    tail tokens of a rounded-up prefill bucket, inactive rows and
    unacquired blocks are left out of the write, because a padded write
    could land past the slot's reserved blocks.

    q_heads (N, H, T, hd); k_chunk/v_chunk (N, T, Hc, hd), Hc == H or a
    grouped divisor (GQA: the H/Hc query heads of a group attend to its
    one KV head without a repeated copy of it). Returns ((N, T, H*hd),
    new_kv_pool)."""
    kv_pool = _paged_write(k_chunk, v_chunk, kv_pool, positions, block_table,
                           lengths)
    return _paged_attend(q_heads, kv_pool, positions, block_table), kv_pool


# (jitted by themselves: a model calls them once a layer with the same
# shapes, and a traced call of a jitted function is one equation of the
# caller's program, found again in the cache, where the index arithmetic
# written out is a few hundred; the compiler inlines the calls. A 48-layer
# program traces in half the time: set-up pays for that a program.)
@jax.jit
def _paged_write(k_chunk, v_chunk, kv_pool, positions, block_table, lengths):
    """The write of `paged_slot_cached_attend`: -> the new pool."""
    N, T, Hc, hd = k_chunk.shape
    _, P, B, _ = kv_pool.shape
    M = block_table.shape[1]
    nb = -(-(T - 1) // B) + 1
    start = positions[:, 0]
    m = start[:, None] // B + jnp.arange(nb)                    # (N, nb)
    blk = jnp.take_along_axis(block_table, jnp.clip(m, 0, M - 1), axis=1)
    # chunk index of lane b of the j-th touched block
    t = (m * B - start[:, None])[:, :, None] + jnp.arange(B)    # (N, nb, B)
    live = ((t >= 0) & (t < lengths[:, None, None])
            & ((m < M) & (blk >= 0))[:, :, None])
    # windows with nothing to write are pointed out of range and dropped
    ids = jnp.where(live.any(-1), blk, P).reshape(-1)
    kv = jnp.concatenate([k_chunk, v_chunk], axis=-1)       # (N, T, Hc, 2hd)
    new = jnp.take_along_axis(
        kv, jnp.clip(t, 0, T - 1).reshape(N, nb * B, 1, 1), axis=1)
    new = new.reshape(N * nb, B, Hc, 2 * hd).transpose(2, 0, 1, 3)
    old = kv_pool[:, jnp.clip(ids, 0, P - 1)]             # (Hc, N*nb, B, 2hd)
    return kv_pool.at[:, ids].set(
        jnp.where(live.reshape(N * nb, B)[None, :, :, None], new, old),
        mode="drop")


@jax.jit
def _paged_attend(q_heads, kv_pool, positions, block_table):
    """The read of `paged_slot_cached_attend`: every query over the whole
    pool under the ownership mask. -> (N, T, H*hd)."""
    N, H, T, hd = q_heads.shape
    Hc, P, B, _ = kv_pool.shape
    M = block_table.shape[1]
    owns = block_table[:, :, None] == jnp.arange(P)             # (N, M, P)
    logical = jnp.max(jnp.where(owns, jnp.arange(M)[None, :, None], -1),
                      axis=1)                                   # (N, P)
    lane_pos = jnp.where(logical[:, :, None] >= 0,
                         logical[:, :, None] * B + jnp.arange(B),
                         jnp.iinfo(jnp.int32).max).reshape(N, P * B)
    mask = lane_pos[:, None, None, None, :] <= \
        positions[:, None, None, :, None]                   # (N,1,1,T,P*B)
    # K and V broadcast over the slots and over a group's query heads
    lanes = kv_pool.reshape(Hc, 1, P * B, 2 * hd)
    a = dot_product_attention(q_heads.reshape(N, Hc, H // Hc, T, hd),
                              lanes[..., :hd], lanes[..., hd:], mask)
    return a.reshape(N, H, T, hd).transpose(0, 2, 1, 3).reshape(N, T, H * hd)


class SlotRows(NamedTuple):
    """The rows of one shape in a pass over a slot batch: `positions` (N, T)
    and `lengths` (N,) as `paged_slot_cached_attend` takes them,
    `block_table` (N, M) their slots' rows of it, `decode` whether they are
    a step's one token, `slots` (N,) the slots they belong to where that is
    not "row i is slot i" (a leaf resident by slot is read and written at
    those rows alone)."""
    positions: Any
    block_table: Any
    lengths: Any
    decode: bool = False
    slots: Optional[Any] = None


def carried_rows(tokens, positions, block_table, lengths, decode, chunk):
    """A step that carries a prompt chunk (serve/decode.py): the chunk's
    `chunk = (tokens (R, C), positions (R, C), block_table (R, M), lengths
    (R,), slots (R,))` then the step's rows (S, 1), their tokens one after
    another as ONE row, so that everything a model computes token by token
    (norms, projections, the MLP or the experts) is one product over both
    and reads its weights once; what is computed row by row (attention over
    the pool, a recurrent state) takes its tokens back out part by part
    (`split_rows`), in this order: a part sees what the parts before it
    wrote, so a step's row may be the token that follows the chunk in the
    same slot (a prompt's last token behind its last chunk). Returns
    (tokens (1, n), positions (1, n), parts); the step's rows are the last
    S of the n. Without a chunk: (tokens, positions, None)."""
    if chunk is None:
        return tokens, positions, None
    c_tokens, c_positions, c_table, c_lengths, c_slots = chunk
    parts = (SlotRows(c_positions, c_table, c_lengths, False, c_slots),
             SlotRows(positions, block_table, lengths, decode))
    return (join_rows([c_tokens, tokens]),
            join_rows([c_positions, positions]), parts)


def join_rows(arrays):
    """[(N_i, T_i, ...)] -> (1, sum N_i T_i, ...): the parts' tokens one
    after another."""
    return jnp.concatenate(
        [a.reshape((1, -1) + a.shape[2:]) for a in arrays], axis=1)


def split_rows(parts, *joined):
    """The inverse of `join_rows`, array by array: -> for each part the
    tuple of its (N, T, ...) share of every `joined` (1, n, ...)."""
    out, at = [], 0
    for part in parts:
        N, T = part.positions.shape
        out.append(tuple(a[0, at:at + N * T].reshape((N, T) + a.shape[2:])
                         for a in joined))
        at += N * T
    return out


def paged_parts_attend(q, k, v, kv_pool, parts):
    """`paged_slot_cached_attend` over joined rows: q (1, n, H, hd), k, v
    (1, n, Hc, hd) -> ((1, n, H*hd), new_kv_pool). The parts write one
    after another (a later part's window may be a block an earlier part
    has just written: the same slot's next token), nothing reading the
    pool in between, so it stays where it lies. Then every token attends
    the pool as a row of its own (its position, its slot's row of the
    block table), all parts in one pass over the pool, in which another
    slot's new lanes stay masked."""
    for part, (k_, v_) in zip(parts, split_rows(parts, k, v)):
        kv_pool = _paged_write(k_, v_, kv_pool, part.positions,
                               part.block_table, part.lengths)
    positions = jnp.concatenate([p.positions.reshape(-1, 1) for p in parts])
    tables = jnp.concatenate([
        jnp.repeat(p.block_table, p.positions.shape[1], axis=0)
        for p in parts])
    a = _paged_attend(q[0][:, :, None, :], kv_pool, positions, tables)
    return a.reshape((1,) + a.shape[:1] + a.shape[2:]), kv_pool


class MultiHeadAttention(Module):
    """Multi-head attention (reference: nn/Attention.scala). Packed QKV
    projections; inputs (B, T, d_model). `attn_impl` picks the kernel:
    'dense' (default), or 'blockwise' with `block_size` for long sequences.

    Modern-LM options (no reference analogue): `num_kv_heads` < num_heads
    enables grouped-query attention — K/V project to num_kv_heads and
    repeat up to the query heads before the attend, so every attn_impl
    (dense/blockwise/flash) works unchanged; `rope_theta` applies rotary
    position embeddings to q and k.
    """

    bias = False          # class default: pickles from before the bias
                          # option existed must keep loading
    num_kv_heads = None   # class defaults: old pickles keep loading
    rope_theta = None

    def __init__(self, d_model: int, num_heads: int, *,
                 dropout: float = 0.0, attn_impl="dense",
                 block_size: int = 512, bias: bool = False,
                 num_kv_heads=None, rope_theta=None, name=None):
        super().__init__(name)
        if d_model % num_heads:
            raise ValueError(f"d_model {d_model} % heads {num_heads} != 0")
        if attn_impl not in ("dense", "blockwise") and not callable(attn_impl):
            raise ValueError(
                f"attn_impl must be 'dense', 'blockwise', or a callable "
                f"(q, k, v, mask=..., causal=...) -> out; got {attn_impl!r}")
        if num_kv_heads is not None and num_heads % num_kv_heads:
            raise ValueError(f"num_heads {num_heads} % num_kv_heads "
                             f"{num_kv_heads} != 0")
        self.d_model, self.num_heads = d_model, num_heads
        self.head_dim = d_model // num_heads
        self.dropout = dropout
        self.attn_impl, self.block_size = attn_impl, block_size
        # bias=True adds projection biases (GPT-family checkpoints carry
        # them; the reference's Attention.scala denses are bias-free)
        self.bias = bias
        self.num_kv_heads = num_kv_heads
        self.rope_theta = rope_theta

    def param_specs(self):
        d = self.d_model
        kv = (self.num_kv_heads or self.num_heads) * self.head_dim
        spec = lambda n: ParamSpec((d, n), initializers.xavier, fan_in=d,
                                   fan_out=n)
        specs = {"wq": spec(d), "wk": spec(kv), "wv": spec(kv),
                 "wo": spec(d)}
        if self.bias:
            specs["bq"] = ParamSpec((d,), initializers.zeros)
            specs["bk"] = ParamSpec((kv,), initializers.zeros)
            specs["bv"] = ParamSpec((kv,), initializers.zeros)
            specs["bo"] = ParamSpec((d,), initializers.zeros)
        return specs

    def _split(self, x, heads=None):
        B, T, _ = x.shape
        return x.reshape(B, T, heads or self.num_heads,
                         self.head_dim).transpose(0, 2, 1, 3)

    def _attend(self, q, k, v, mask, causal):
        if callable(self.attn_impl):
            return self.attn_impl(q, k, v, mask=mask, causal=causal)
        if self.attn_impl == "blockwise":
            if mask is not None:
                raise ValueError("blockwise path supports causal= only; "
                                 "use attn_impl='dense' with a mask")
            return blockwise_attention(q, k, v, block_size=self.block_size,
                                       causal=causal)
        if causal:
            cm = causal_mask(q.shape[2], k.shape[2])
            # accept numeric 0/1 masks as the docstring promises
            mask = cm if mask is None else ((mask != 0) & cm)
        return dot_product_attention(q, k, v, mask)

    def _apply(self, params, state, x, memory=None, *, mask=None,
               causal: bool = False, positions=None, training=False,
               rng=None):
        kv_src = memory if memory is not None else x
        q = x @ params["wq"]
        k = kv_src @ params["wk"]
        v = kv_src @ params["wv"]
        if self.bias:
            q, k, v = (q + params["bq"], k + params["bk"],
                       v + params["bv"])
        kv_heads = self.num_kv_heads or self.num_heads
        q = self._split(q)
        k = self._split(k, kv_heads)
        v = self._split(v, kv_heads)
        if self.rope_theta:
            # `positions` carries ABSOLUTE token positions (sequence-
            # parallel shards pass their global offsets); default 0..T-1
            q = rotary_embedding(q, self.rope_theta, positions)
            k = rotary_embedding(k, self.rope_theta, positions)
        if kv_heads != self.num_heads:      # GQA: repeat kv to q heads
            rep = self.num_heads // kv_heads
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        out = self._attend(q, k, v, mask, causal)
        B, H, T, hd = out.shape
        out = out.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
        out = out @ params["wo"]
        if self.bias:
            out = out + params["bo"]
        out = _inline_dropout(out, self.dropout, training, rng, self)
        return out, state


def _ffn_relu(x):
    """Module-level default activation — `jax.nn.relu` itself does not
    pickle (its qualname points inside jax._src), which would break the
    durable model format for every Transformer."""
    return jax.nn.relu(x)


class FeedForwardNetwork(Module):
    """Position-wise FFN (reference: nn/FeedForwardNetwork.scala):
    Linear(d, d_ff) -> activation -> Linear(d_ff, d). A custom
    `activation` must be picklable (a module-level function or a class
    instance) for save_module."""

    def __init__(self, d_model: int, d_ff: int, activation=_ffn_relu,
                 dropout: float = 0.0, name=None):
        super().__init__(name)
        self.w1 = self.add_child("w1", Linear(d_model, d_ff))
        self.w2 = self.add_child("w2", Linear(d_ff, d_model))
        self.activation, self.dropout = activation, dropout

    def _apply(self, params, state, x, *, training=False, rng=None):
        h, s1 = self.w1.apply(params["w1"], state.get("w1", {}), x)
        h = self.activation(h)
        h = _inline_dropout(h, self.dropout, training, rng, self)
        out, s2 = self.w2.apply(params["w2"], state.get("w2", {}), h)
        return out, {**state, "w1": s1, "w2": s2}


class TransformerLayer(Module):
    """One pre-norm transformer block: x + attn(ln(x)), x + ffn(ln(x)) —
    the reference's layer_preprocess=layer_norm / postprocess=dropout+add
    wiring (nn/Transformer.scala prePostProcessing* ). With `cross=True`
    a decoder block adds ln->cross-attn->add between self-attn and FFN."""

    def __init__(self, d_model: int, num_heads: int, d_ff: int, *,
                 dropout: float = 0.0, cross: bool = False,
                 attn_impl: str = "dense", block_size: int = 512,
                 bias: bool = False, activation=None, ln_eps: float = 1e-6,
                 name=None):
        super().__init__(name)
        self.cross = cross
        self.dropout = dropout
        self.ln1 = self.add_child("ln1", LayerNormalization(d_model,
                                                            eps=ln_eps))
        self.attn = self.add_child("attn", MultiHeadAttention(
            d_model, num_heads, dropout=dropout, attn_impl=attn_impl,
            block_size=block_size, bias=bias))
        if cross:
            self.ln_x = self.add_child("ln_x", LayerNormalization(
                d_model, eps=ln_eps))
            self.xattn = self.add_child("xattn", MultiHeadAttention(
                d_model, num_heads, dropout=dropout, bias=bias))
        self.ln2 = self.add_child("ln2", LayerNormalization(d_model,
                                                            eps=ln_eps))
        ffn_kw = {} if activation is None else {"activation": activation}
        self.ffn = self.add_child("ffn", FeedForwardNetwork(
            d_model, d_ff, dropout=dropout, **ffn_kw))

    def cached_step(self, params, x, ck, cv, start):
        """Incremental-decode forward: run this block over `x` (N, T, d)
        attending to the KV cache, writing this chunk's K/V at
        [start, start+T). LayerNorms/FFN run through the child modules;
        the attention is hand-rolled because the cache IS the point.
        Numerically identical to the full forward with causal=True over
        the prefix (asserted by the generation parity tests). `start`
        may be traced. Self-attention blocks only (cross=False).

        ck/cv (N, L, H, hd) → returns (out, new_ck, new_cv)."""
        if self.cross:
            raise ValueError("cached_step supports self-attention "
                             "decoder blocks only")
        if callable(self.attn.attn_impl):
            # a custom kernel computes logits its own way; decoding
            # through the dense core here would silently diverge from
            # apply() — refuse instead
            raise ValueError(
                "cached_step decodes through the dense attention core; "
                "this layer was built with a custom attn_impl whose "
                "numerics it cannot reproduce")
        N, T, d = x.shape
        H = self.attn.num_heads
        hd = d // H
        at = params["attn"]
        h, _ = self.ln1.apply(params["ln1"], {}, x)
        q = h @ at["wq"]
        k = h @ at["wk"]
        v = h @ at["wv"]
        if self.attn.bias:
            q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
        q = q.reshape(N, T, H, hd).transpose(0, 2, 1, 3)
        k = k.reshape(N, T, H, hd)
        v = v.reshape(N, T, H, hd)
        # one numerical core: the same scale/mask/softmax chain apply()
        # uses ((N, H, T, hd) layout; mask broadcasts over N, H)
        a, ck, cv = cached_attend(q, k, v, ck, cv, start)
        a = a @ at["wo"]
        if self.attn.bias:
            a = a + at["bo"]
        x = x + a
        f, _ = self.ffn.apply(params["ffn"], {},
                              self.ln2.apply(params["ln2"], {}, x)[0])
        return x + f, ck, cv

    def paged_slot_cached_step(self, params, x, kv_pool, positions,
                               block_table, lengths, parts=None):
        """`cached_step` over a slot batch with PER-ROW positions (N, T)
        int32 against a PAGED KV pool: each row is an independent
        sequence at its own offset, the chunk's K/V are written into the
        pool's blocks through the slot's block table and attention reads
        the pool where it lies (paged_slot_cached_attend). Per row the
        same lanes are attended as by `cached_step` with the matching
        scalar start, summed in pool order. With `parts` (`carried_rows`)
        x is their joined tokens (1, n, d) and attention goes part by
        part. Self-attention blocks only; same custom-attn_impl refusal
        as cached_step."""
        if self.cross:
            raise ValueError("paged_slot_cached_step supports self-"
                             "attention decoder blocks only")
        if callable(self.attn.attn_impl):
            raise ValueError(
                "paged_slot_cached_step decodes through the dense "
                "attention core; this layer was built with a custom "
                "attn_impl whose numerics it cannot reproduce")
        N, T, d = x.shape
        H = self.attn.num_heads
        hd = d // H
        at = params["attn"]
        h, _ = self.ln1.apply(params["ln1"], {}, x)
        q = h @ at["wq"]
        k = h @ at["wk"]
        v = h @ at["wv"]
        if self.attn.bias:
            q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
        q = q.reshape(N, T, H, hd)
        k = k.reshape(N, T, H, hd)
        v = v.reshape(N, T, H, hd)
        if parts is None:
            a, kv_pool = paged_slot_cached_attend(
                q.transpose(0, 2, 1, 3), k, v, kv_pool, positions,
                block_table, lengths)
        else:
            a, kv_pool = paged_parts_attend(q, k, v, kv_pool, parts)
        a = a @ at["wo"]
        if self.attn.bias:
            a = a + at["bo"]
        x = x + a
        f, _ = self.ffn.apply(params["ffn"], {},
                              self.ln2.apply(params["ln2"], {}, x)[0])
        return x + f, kv_pool

    def _apply(self, params, state, x, memory=None, *, mask=None,
               memory_mask=None, causal=False, training=False, rng=None):
        rngs = jax.random.split(rng, 3) if rng is not None else (None,) * 3
        new_state = dict(state)

        def run(name, *args, **kw):
            out, ns = self.children()[name].apply(
                params[name], state.get(name, {}), *args, **kw)
            new_state[name] = ns
            return out

        h = run("ln1", x)
        a = run("attn", h, mask=mask, causal=causal, training=training,
                rng=rngs[0])
        x = x + a
        if self.cross:
            if memory is None:
                raise ValueError("decoder block needs encoder memory")
            h = run("ln_x", x)
            a = run("xattn", h, memory, mask=memory_mask, training=training,
                    rng=rngs[1])
            x = x + a
        h = run("ln2", x)
        f = run("ffn", h, training=training, rng=rngs[2])
        return x + f, new_state


def positional_encoding_at(positions, d: int, dtype=jnp.float32):
    """Sinusoidal signal at arbitrary (possibly traced / shard-offset)
    positions — used by sequence-parallel shards and KV-cached decoding."""
    pos = positions.astype(jnp.float32)[:, None]
    half = d // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half) / max(1, half - 1))
    angles = pos * freq[None, :]
    enc = jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)
    if enc.shape[-1] < d:
        enc = jnp.pad(enc, ((0, 0), (0, d - enc.shape[-1])))
    return enc.astype(dtype)


def positional_encoding(t: int, d: int, dtype=jnp.float32):
    """Sinusoidal position signal (reference: TransformerOperation.scala
    addTimingSignal)."""
    return positional_encoding_at(jnp.arange(t), d, dtype)


class Transformer(Module):
    """Transformer (reference: nn/Transformer.scala:53-105 — supports a
    decoder-only `TransformerType.LanguageModel` and an encoder-decoder
    `Translation` mode).

    mode='lm':      apply(params, state, tokens) -> (B, T, vocab) logits,
                    causal self-attention, tied input/output embedding.
    mode='encdec':  apply(params, state, (src_tokens, tgt_tokens)).
    """

    def __init__(self, vocab_size: int, d_model: int, num_heads: int,
                 d_ff: int, num_layers: int, *, mode: str = "lm",
                 dropout: float = 0.0, max_len: int = 2048,
                 attn_impl: str = "dense", block_size: int = 512, name=None):
        super().__init__(name)
        if mode not in ("lm", "encdec"):
            raise ValueError(f"mode must be lm|encdec, got {mode}")
        self.vocab_size, self.d_model, self.mode = vocab_size, d_model, mode
        self.max_len, self.dropout = max_len, dropout
        self.num_layers = num_layers
        dec_layers = num_layers
        if mode == "encdec":
            for i in range(num_layers):
                self.add_child(f"enc{i}", TransformerLayer(
                    d_model, num_heads, d_ff, dropout=dropout,
                    attn_impl=attn_impl, block_size=block_size))
            self.add_child("enc_ln", LayerNormalization(d_model))
        for i in range(dec_layers):
            self.add_child(f"dec{i}", TransformerLayer(
                d_model, num_heads, d_ff, dropout=dropout,
                cross=(mode == "encdec"), attn_impl=attn_impl,
                block_size=block_size))
        self.add_child("dec_ln", LayerNormalization(d_model))

    def param_specs(self):
        v, d = self.vocab_size, self.d_model
        return {"embedding": ParamSpec(
            (v, d), initializers.random_normal(0.0, d ** -0.5))}

    def _embed(self, params, tokens):
        t = tokens.shape[1]
        if t > self.max_len:
            raise ValueError(
                f"sequence length {t} exceeds max_len={self.max_len}")
        x = params["embedding"][tokens] * self.d_model ** 0.5
        return x + positional_encoding(t, self.d_model, x.dtype)

    def _apply(self, params, state, inputs, *, training=False, rng=None):
        n_rng = 2 * self.num_layers + 1
        rngs = (jax.random.split(rng, n_rng) if rng is not None
                else (None,) * n_rng)
        new_state = dict(state)

        def run(name, *args, **kw):
            out, ns = self.children()[name].apply(
                params[name], state.get(name, {}), *args, **kw)
            new_state[name] = ns
            return out

        if self.mode == "lm":
            tokens = inputs
            x = self._embed(params, tokens)
            for i in range(self.num_layers):
                x = run(f"dec{i}", x, causal=True, training=training,
                        rng=rngs[i])
            x = run("dec_ln", x)
            logits = x @ params["embedding"].T     # tied softmax weights
            return logits, new_state
        src_tokens, tgt_tokens = inputs
        h = self._embed(params, src_tokens)
        for i in range(self.num_layers):
            h = run(f"enc{i}", h, training=training, rng=rngs[i])
        memory = run("enc_ln", h)
        x = self._embed(params, tgt_tokens)
        for i in range(self.num_layers):
            x = run(f"dec{i}", x, memory, causal=True, training=training,
                    rng=rngs[self.num_layers + i])
        x = run("dec_ln", x)
        return x @ params["embedding"].T, new_state


    def generate(self, params, state, prompt, max_new_tokens: int,
                 beam_size: int = 4, eos_id=None, alpha: float = 0.0):
        """KV-cached beam-search continuation for the LM mode: one
        token's QKV per step attending over per-layer caches
        (`TransformerLayer.cached_step`), prompt prefill once per batch
        row. prompt (B, P) int32 → (sequences (B, K, P+max_new),
        scores (B, K)). The reference pairs its Transformer with
        SequenceBeamSearch (nn/SequenceBeamSearch.scala); this is that
        wiring with incremental decode. `eos_id` is required — guessing
        a stop token would silently freeze beams that emit it."""
        from bigdl_tpu.nn.recurrent import cached_beam_generate
        if self.mode != "lm":
            raise ValueError("generate() requires mode='lm'")
        if eos_id is None:
            raise ValueError("generate: pass eos_id (your vocabulary's "
                             "end-of-sequence token)")
        B, P = prompt.shape
        L = P + max_new_tokens
        if L > self.max_len:
            raise ValueError(f"prompt+new = {L} > max_len {self.max_len}")
        d = self.d_model
        H = self.children()["dec0"].attn.num_heads
        hd = d // H
        scale = d ** 0.5
        dtype = params["embedding"].dtype      # bf16 params → bf16 caches

        def fwd(tokens, caches, start):
            cks, cvs = caches
            x = (params["embedding"][tokens] * scale
                 + positional_encoding_at(
                     start + jnp.arange(tokens.shape[1]), d, dtype))
            new_ck, new_cv = [], []
            for i in range(self.num_layers):
                blk = self.children()[f"dec{i}"]
                x, ck_i, cv_i = blk.cached_step(
                    params[f"dec{i}"], x, cks[i], cvs[i], start)
                new_ck.append(ck_i)
                new_cv.append(cv_i)
            x, _ = self.children()["dec_ln"].apply(
                params["dec_ln"], {}, x)
            logits = x[:, -1] @ params["embedding"].T
            return logits, (tuple(new_ck), tuple(new_cv))

        def make_caches():
            zeros = lambda: jnp.zeros((B, L, H, hd), dtype)  # noqa: E731
            return (tuple(zeros() for _ in range(self.num_layers)),
                    tuple(zeros() for _ in range(self.num_layers)))

        return cached_beam_generate(
            fwd, make_caches, prompt, max_new_tokens=max_new_tokens,
            beam_size=beam_size, vocab_size=self.vocab_size,
            eos_id=eos_id, alpha=alpha)


class Attention(MultiHeadAttention):
    """Alias matching the reference's layer name (nn/Attention.scala)."""
