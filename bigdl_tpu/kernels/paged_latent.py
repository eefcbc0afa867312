"""Decode-step latent attention over a paged pool, as a Pallas TPU kernel.

Absorbed latent (MLA) attention is multi-query attention: every query head
of a slot reads the same pool row a token, `[cKV ; kR ; zeros]`, whose
leading `v_width` values are also the value (nn/latent_attention.py). A
decode step has a few query rows a slot (the verified token and its draft)
and must read each slot's WHOLE context, wherever its block table puts it,
once a layer. XLA can only gather the context into a copy and read that
copy twice; this kernel walks the block table itself.

A grid step is one slot. Its context is read in runs of `pages` pool
blocks, laid one after another in a key tile of `pages x B` rows in fast
memory by the kernel's own copies (one a block, the pool left in HBM), two
tiles deep: the copies of the next run, or of the next slot's first run
after a slot's last, start before the current run is computed, so the
stream never waits on the grid. A run is one product of all the slot's
query rows against the whole tile for the scores, one online-softmax
update of the running max and sum, and one product of the probabilities
with the tile's values; the position mask is applied on a slot's last run
alone, the one that crosses its length, and no copy or product is spent
past it. The MXU then meets each key row once with every query row (a few
dozen), so a run costs about what its bytes take to arrive. The result is
unnormalised, with the max and the sum beside it, so that the caller can
fold in the keys a step writes after the context (a draft's own row)
before it divides.

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
# bytes of pool rows a run copies into its key tile: 16 blocks of 64 rows of
# 640 bfloat16 values at GLM's widths, 1.3 MB. On a v5e, runs of 8 blocks
# cost 15 % more a call (a run's fixed cost paid twice as often), runs of 32
# no less, and two tiles of 64 blocks no longer fit the scoped fast memory
RUN_BYTES = 1280 * 1024
_STATS = 128                            # lanes of the max and the sum


def run_pages(block: int, width: int, itemsize: int, blocks: int) -> int:
    """Pool blocks a run copies: `RUN_BYTES` of rows, at most a slot's
    table."""
    return max(1, min(blocks, RUN_BYTES // (block * width * itemsize)))


def _attend(q, k, carry, *, scale, v_width, valid=None):
    """One key tile k (T, W) into the running (o, m, l) of q (Q, W)."""
    o, m, l = carry
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if valid is not None:
        s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    if valid is not None:               # a slot with nothing to read adds 0
        p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m - m_new)
    o = o * alpha + jax.lax.dot_general(
        p.astype(k.dtype), k[:, :v_width], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return o, m_new, l * alpha + jnp.sum(p, axis=1, keepdims=True)


def _kernel(lens_ref, table_ref, first_ref, q_ref, pool_ref, o_ref, m_ref,
            l_ref, keys, sems, *, pages: int, block: int, table_width: int,
            attend):
    s, S = pl.program_id(0), pl.num_programs(0)
    tile = pages * block

    def count(t, j):                    # blocks run j of slot t copies
        n_blocks = (lens_ref[t] + block - 1) // block
        return jnp.clip(n_blocks - j * pages, 0, pages)

    def copies(t, j, buf, action):
        def one(i, c):
            page = jnp.maximum(table_ref[t * table_width + j * pages + i], 0)
            dst = keys.at[buf, pl.ds(pl.multiple_of(i * block, block), block)]
            action(pltpu.make_async_copy(pool_ref.at[page], dst,
                                         sems.at[buf]))
            return c
        jax.lax.fori_loop(0, count(t, j), one, 0)

    @pl.when(s == 0)
    def _first():
        # rows past a slot's last block keep an earlier run's pool rows,
        # finite, so that a masked row's 0 probability times its value is 0
        keys[...] = jnp.zeros_like(keys)
        copies(0, 0, 0, lambda c: c.start())

    n = lens_ref[s]
    runs = jnp.maximum((n + tile - 1) // tile, 1)
    q = q_ref[0]
    Q = q.shape[0]

    def run(j, carry, last):
        buf = (first_ref[s] + j) % 2

        @pl.when(j + 1 < runs)
        def _next_run():
            copies(s, j + 1, 1 - buf, lambda c: c.start())

        @pl.when(jnp.logical_and(j + 1 == runs, s + 1 < S))
        def _next_slot():
            copies(s + 1, 0, 1 - buf, lambda c: c.start())

        copies(s, j, buf, lambda c: c.wait())
        valid = None
        if last:
            at = j * tile + jax.lax.broadcasted_iota(jnp.int32, (Q, tile), 1)
            valid = at < n
        return attend(q, keys[buf], carry, valid=valid)

    carry = (jnp.zeros(o_ref.shape[1:], jnp.float32),
             jnp.full((Q, 1), NEG_INF, jnp.float32),
             jnp.zeros((Q, 1), jnp.float32))
    carry = jax.lax.fori_loop(0, runs - 1,
                              lambda j, c: run(j, c, last=False), carry)
    o, m, l = run(runs - 1, carry, last=True)
    o_ref[0] = o
    m_ref[0] = jnp.broadcast_to(m, (Q, _STATS))
    l_ref[0] = jnp.broadcast_to(l, (Q, _STATS))


def _pallas(q, pool, lengths, block_table, scale, v_width, pages,
            interpret=False, attend=_attend):
    S, Q, W = q.shape
    _, B, _ = pool.shape
    M = block_table.shape[1]
    lengths = lengths.astype(jnp.int32)
    runs = jnp.maximum(-(-lengths // (pages * B)), 1)
    kernel = functools.partial(
        _kernel, pages=pages, block=B, table_width=M,
        attend=functools.partial(attend, scale=scale, v_width=v_width))
    per_slot = lambda s, *_: (s, 0, 0)  # noqa: E731
    stats = pl.BlockSpec((1, Q, _STATS), per_slot)
    o, m, l = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((S, Q, v_width), jnp.float32),
                   jax.ShapeDtypeStruct((S, Q, _STATS), jnp.float32),
                   jax.ShapeDtypeStruct((S, Q, _STATS), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S,),
            in_specs=[pl.BlockSpec((1, Q, W), per_slot),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec((1, Q, v_width), per_slot), stats, stats),
            scratch_shapes=[pltpu.VMEM((2, pages * B, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        # a slot's last run starts the next slot's copies: one core, in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_latent_attend",
    )(lengths, block_table.reshape(-1).astype(jnp.int32),
      (jnp.cumsum(runs) - runs).astype(jnp.int32), q, pool)
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
                        v_width: int, pages: int | None = None,
                        interpret: bool = False):
    """Every query row of a slot over the first `lengths[s]` positions of
    its context. q (S, Q, W) (a slot's query rows and heads, absorbed);
    pool (P, B, W); lengths (S,) int32, 0 = nothing to read; block_table
    (S, M) int32, -1 = not acquired (never read below a slot's length).
    Returns (o (S, Q, v_width) float32 = sum_s e^(score - m) row_s[:v_width],
    m (S, Q) float32 the largest score, l (S, Q) float32 = sum_s e^(score -
    m)); a slot with nothing to read has o = l = 0 and m = NEG_INF.
    `pages`, the pool blocks of a run, comes from the shapes (`run_pages`)
    unless given; `interpret` runs the kernel in the Pallas interpreter
    (tests)."""
    _, B, W = pool.shape
    M = block_table.shape[1]
    pages = max(1, min(pages or run_pages(B, W, pool.dtype.itemsize, M), M))
    if interpret:
        return _pallas(q, pool, lengths, block_table, scale, v_width, pages,
                       interpret=True)
    return jax.lax.platform_dependent(
        q, pool, lengths, block_table,
        tpu=functools.partial(_pallas, scale=scale, v_width=v_width,
                              pages=pages),
        default=functools.partial(_xla, scale=scale, v_width=v_width))
