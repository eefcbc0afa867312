#!/usr/bin/env python
"""Times the dense latent attention kernel (`kernels/paged_latent.py`) at
GLM-4.7-Flash's decode shapes on one TPU chip, beside the variants it was
chosen from, and prints one JSON line a variant:

- `blocks16`: the kernel's first form, a grid of (slot, run of 16 pool
  blocks) whose 16 blocks arrive as 16 pipelined inputs and are computed
  as 16 products of 64 keys; `blocks16_dma` the same with its body skipped
  (the stream alone);
- `pages<N>_qk`: the kernel as it is, one key tile of N pool blocks a run,
  the scores as q . K^T; `pages<N>_kq` the scores as (K . q^T)^T, the keys
  streamed through the query rows held; `pages<N>_dma` its copies alone.

Each line gives the median time of a call over `--calls` back-to-back
calls (of three such readings), the time of a run (or of a computing grid
step), the context bytes a second, the share of the least time of the
bytes `latent_attend_roofline_pct` counts (576 values a row), and how far
o / l lies from the gathered XLA form's. Nothing of the benchmark runs.

    python tools/time_paged_latent.py                  # on the chip
    JAX_PLATFORMS=cpu python tools/time_paged_latent.py --tiny   # rehearsal
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
from jax.experimental import pallas as pl           # noqa: E402
from jax.experimental.pallas import tpu as pltpu    # noqa: E402

from bigdl_tpu.kernels import paged_latent as PL    # noqa: E402

HBM_BYTES_PER_S = 819e9                  # TPU v5e, Google Cloud "TPU v5e"


# ------------------------------------------- the kernel's first form
def _blocks_kernel(lens_ref, table_ref, q_ref, *refs, pages, block, v_width,
                   scale, body):
    pools = refs[:pages]
    o_ref, m_ref, l_ref = refs[pages:]
    s, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, PL.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    n = lens_ref[s]
    start = j * pages * block

    @pl.when(jnp.logical_and(start < n, body))
    def _compute():
        q = q_ref[0]
        Q = q.shape[0]
        scores, rows = [], []
        for i in range(pages):
            k = pools[i][0]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            at = start + i * block + jax.lax.broadcasted_iota(
                jnp.int32, (Q, block), 1)
            scores.append(jnp.where(at < n, sc, PL.NEG_INF))
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
        m_ref[0] = jnp.broadcast_to(m_new, (Q, 128))
        l_ref[0] = jnp.broadcast_to(total, (Q, 128))


def blocks_form(q, pool, lengths, block_table, scale, v_width, pages=16,
                body=True, interpret=False):
    S, Q, W = q.shape
    _, B, _ = pool.shape
    M = block_table.shape[1]

    def page_of(i):
        def index(s, j, lens, table):
            last = jnp.maximum(lens[s] - 1, 0) // B
            m = jnp.minimum(jnp.minimum(j * pages + i, last), M - 1)
            return jnp.maximum(table[s * M + m], 0), 0, 0
        return index

    kernel = functools.partial(_blocks_kernel, pages=pages, block=B,
                               v_width=v_width, scale=scale, body=body)
    stats = pl.BlockSpec((1, Q, 128), lambda s, j, *_: (s, 0, 0))
    o, m, l = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((S, Q, v_width), jnp.float32),
                   jax.ShapeDtypeStruct((S, Q, 128), jnp.float32),
                   jax.ShapeDtypeStruct((S, Q, 128), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, -(-M // pages)),
            in_specs=[pl.BlockSpec((1, Q, W), lambda s, j, *_: (s, 0, 0))]
            + [pl.BlockSpec((1, B, W), page_of(i)) for i in range(pages)],
            out_specs=(pl.BlockSpec((1, Q, v_width),
                                    lambda s, j, *_: (s, 0, 0)),
                       stats, stats)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="paged_latent_blocks",
    )(lengths.astype(jnp.int32), block_table.reshape(-1).astype(jnp.int32),
      q, *([pool] * pages))
    return o, m[..., 0], l[..., 0]


# ------------------------------------------- bodies of the tile form
def attend_kq(q, k, carry, *, scale, v_width, valid=None):
    """`PL._attend` with the scores computed as (K . q^T)^T."""
    o, m, l = carry
    s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32).T * scale
    if valid is not None:
        s = jnp.where(valid, s, PL.NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m - m_new)
    o = o * alpha + jax.lax.dot_general(
        p.astype(k.dtype), k[:, :v_width], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return o, m_new, l * alpha + jnp.sum(p, axis=1, keepdims=True)


def attend_none(q, k, carry, **_):
    """The copies alone: the tile is never read."""
    return carry


def variants(pages_tried, block_pages):
    out = {f"blocks{block_pages}": functools.partial(
               blocks_form, pages=block_pages),
           f"blocks{block_pages}_dma": functools.partial(
               blocks_form, pages=block_pages, body=False)}
    for pages in pages_tried:
        for tag, attend in (("qk", PL._attend), ("kq", attend_kq),
                            ("dma", attend_none)):
            out[f"pages{pages}_{tag}"] = functools.partial(
                PL._pallas, pages=pages, attend=attend)
    return out


def inputs(seed, S, Q, W, B, P, M, lo, hi):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.standard_normal((S, Q, W)) * 0.5, jnp.bfloat16)
    pool = jnp.asarray(r.standard_normal((P, B, W)), jnp.bfloat16)
    table = jnp.asarray(r.integers(0, P, (S, M)), jnp.int32)
    lens = jnp.asarray(r.integers(lo, hi + 1, S), jnp.int32)
    return q, pool, lens, table


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="tiny shapes in the Pallas interpreter (no chip)")
    ap.add_argument("--rows", default="40,20",
                    help="query rows a slot (2 rows x 20 heads, 1 x 20)")
    ap.add_argument("--pages", default="8,16,32",
                    help="pool blocks a run of the tile form")
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--out", default="benchmark_out/time_paged_latent.jsonl")
    a = ap.parse_args(argv)
    if a.tiny:
        S, W, B, P, M, lo, hi, v = 3, 128, 8, 40, 12, 0, 90, 64
        pages, block_pages, interpret = (2, 4), 4, True
    else:
        S, W, B, P, M, lo, hi, v = 64, 640, 64, 3712, 281, 8192, 17984, 512
        pages = tuple(int(x) for x in a.pages.split(","))
        block_pages, interpret = 16, False
    scale = 1 / 16
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    dev = jax.devices()[0]
    with open(a.out, "a") as fh:
        for Q in (int(x) for x in a.rows.split(",")):
            q, pool, lens, table = inputs(a.seed, S, Q, W, B, P, M, lo, hi)
            ref = jax.jit(functools.partial(PL._xla, scale=scale,
                                            v_width=v))(q, pool, lens, table)
            want = np.asarray(ref[0] / ref[2][..., None])
            n = np.asarray(lens)
            ctx_bytes = float(n.sum()) * W * pool.dtype.itemsize
            least_s = float(n.sum()) * 576 * 2 / HBM_BYTES_PER_S
            for name, fn in variants(pages, block_pages).items():
                f = jax.jit(functools.partial(fn, scale=scale, v_width=v,
                                              interpret=interpret))
                got = jax.block_until_ready(f(q, pool, lens, table))
                o = np.asarray(got[0] / np.maximum(got[2], 1e-30)[..., None])
                times = []
                for _ in range(1 if a.tiny else 3):
                    t0 = time.perf_counter()
                    for _ in range(1 if a.tiny else a.calls):
                        got = f(q, pool, lens, table)
                    jax.block_until_ready(got)
                    times.append((time.perf_counter() - t0)
                                 / (1 if a.tiny else a.calls))
                call_s = statistics.median(times)
                per = (block_pages if name.startswith("blocks")
                       else int(name[5:].split("_")[0])) * B
                steps = int(np.sum(np.maximum(-(-n // per), 1)))
                line = {"variant": name, "rows": Q, "device": dev.device_kind,
                        "call_ms": call_s * 1e3,
                        "run_us": call_s * 1e6 / steps, "runs": steps,
                        "context_GBps": ctx_bytes / call_s / 1e9,
                        "roofline_pct": 100 * least_s / call_s,
                        "max_err": (None if name.endswith("dma") else
                                    float(np.abs(o - want).max()))}
                print(json.dumps(line), flush=True)
                fh.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
