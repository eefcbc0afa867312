"""Decode-step latent attention over a paged pool, as a Pallas TPU kernel.

Absorbed latent (MLA) attention is multi-query attention: every query head
of a slot reads the same pool row a token, `[cKV ; kR ; zeros]`, whose
leading `v_width` values are also the value (nn/latent_attention.py). A
decode step has a few query rows a slot (the verified token and its draft)
and must read each slot's WHOLE context, wherever its block table puts it,
once a layer. XLA can only gather the context into a copy and read that
copy twice; this kernel walks the block table itself: the grid is (slot,
run of `pages` pool blocks), the block table and the lengths are prefetched
into scalar memory, and each of `pages` inputs (the same pool) fetches one
block of the run by its index, so the pipeline streams every block of a
slot's context from HBM into VMEM once while the previous run is computed.
Past a slot's end the index stays on its last block, which is then not
fetched again, and nothing is computed. An online softmax keeps the running
max and sum; the result is unnormalised, with the max and the sum beside
it, so that the caller can fold in the keys a step writes after the
context (a draft's own row) before it divides.

`paged_latent_attend` is the function; on the TPU it is the kernel and on
any other backend (the CPU of the tests) the same arithmetic in XLA, over
the gathered context (`lax.platform_dependent` chooses when the program is
lowered, so a program compiled here for a described TPU holds the kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:                                    # pltpu only imports on TPU builds
    from jax.experimental.pallas import tpu as pltpu
except Exception:                       # pragma: no cover
    pltpu = None

NEG_INF = -1e30
# pool blocks a grid step reads: 16 blocks of 64 rows of 640 bfloat16 values
# are 1.3 MB, which the pipeline moves in ~1.6 us at 819 GB/s, several times
# the ~0.35 us a grid step costs
PAGES = 16
_STATS = 128                            # lanes of the max and the sum


def _kernel(lens_ref, table_ref, q_ref, *refs, pages: int, block: int,
            v_width: int, scale: float):
    pools = refs[:pages]
    o_ref, m_ref, l_ref = refs[pages:]
    s, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    n = lens_ref[s]
    start = j * pages * block

    @pl.when(start < n)
    def _compute():
        q = q_ref[0]                                        # (Q, W)
        Q = q.shape[0]
        scores, rows = [], []
        for i in range(pages):
            k = pools[i][0]                                 # (B, W)
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            at = start + i * block + jax.lax.broadcasted_iota(
                jnp.int32, (Q, block), 1)
            scores.append(jnp.where(at < n, sc, NEG_INF))
            rows.append(k)
        m_prev = m_ref[0][:, :1]
        m_new = m_prev
        for sc in scores:
            m_new = jnp.maximum(m_new, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        o = o_ref[0] * alpha
        total = l_ref[0][:, :1] * alpha
        for sc, k in zip(scores, rows):
            p = jnp.exp(sc - m_new)
            total = total + jnp.sum(p, axis=1, keepdims=True)
            o = o + jax.lax.dot_general(
                p.astype(k.dtype), k[:, :v_width], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        o_ref[0] = o
        m_ref[0] = jnp.broadcast_to(m_new, (Q, _STATS))
        l_ref[0] = jnp.broadcast_to(total, (Q, _STATS))


def _pallas(q, pool, lengths, block_table, scale, v_width, pages,
            interpret=False):
    S, Q, W = q.shape
    P, B, _ = pool.shape
    M = block_table.shape[1]
    steps = -(-M // pages)

    def page_of(i):
        def index(s, j, lens, table):
            last = jnp.maximum(lens[s] - 1, 0) // B
            m = jnp.minimum(jnp.minimum(j * pages + i, last), M - 1)
            return jnp.maximum(table[s * M + m], 0), 0, 0
        return index

    kernel = functools.partial(_kernel, pages=pages, block=B,
                               v_width=v_width, scale=scale)
    stats = pl.BlockSpec((1, Q, _STATS), lambda s, j, *_: (s, 0, 0))
    o, m, l = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((S, Q, v_width), jnp.float32),
                   jax.ShapeDtypeStruct((S, Q, _STATS), jnp.float32),
                   jax.ShapeDtypeStruct((S, Q, _STATS), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, steps),
            in_specs=[pl.BlockSpec((1, Q, W), lambda s, j, *_: (s, 0, 0))]
            + [pl.BlockSpec((1, B, W), page_of(i)) for i in range(pages)],
            out_specs=(pl.BlockSpec((1, Q, v_width),
                                    lambda s, j, *_: (s, 0, 0)),
                       stats, stats)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="paged_latent_attend",
    )(lengths.astype(jnp.int32), block_table.reshape(-1).astype(jnp.int32),
      q, *([pool] * pages))
    return o, m[..., 0], l[..., 0]


def _xla(q, pool, lengths, block_table, scale, v_width):
    """The same sums over the gathered context (N, M*B, W)."""
    S, M = block_table.shape
    _, B, W = pool.shape
    ctx = pool[jnp.clip(block_table, 0)].reshape(S, M * B, W)
    s = jnp.einsum("sqw,skw->sqk", q, ctx,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where((jnp.arange(M * B) < lengths[:, None])[:, None, :], s,
                  NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m[..., None]), 0.0)
    o = jnp.einsum("sqk,skc->sqc", p.astype(ctx.dtype), ctx[..., :v_width],
                   preferred_element_type=jnp.float32)
    return o, m, jnp.sum(p, axis=-1)


def paged_latent_attend(q, pool, lengths, block_table, *, scale: float,
                        v_width: int, pages: int = PAGES,
                        interpret: bool = False):
    """Every query row of a slot over the first `lengths[s]` positions of
    its context. q (S, Q, W) (a slot's query rows and heads, absorbed);
    pool (P, B, W); lengths (S,) int32, 0 = nothing to read; block_table
    (S, M) int32, -1 = not acquired (never read below a slot's length).
    Returns (o (S, Q, v_width) float32 = sum_s e^(score - m) row_s[:v_width],
    m (S, Q) float32 the largest score, l (S, Q) float32 = sum_s e^(score -
    m)); a slot with nothing to read has o = l = 0 and m = NEG_INF.
    `interpret` runs the kernel in the Pallas interpreter (tests)."""
    pages = max(1, min(pages, block_table.shape[1]))
    if interpret:
        return _pallas(q, pool, lengths, block_table, scale, v_width, pages,
                       interpret=True)
    return jax.lax.platform_dependent(
        q, pool, lengths, block_table,
        tpu=functools.partial(_pallas, scale=scale, v_width=v_width,
                              pages=pages),
        default=functools.partial(_xla, scale=scale, v_width=v_width))
