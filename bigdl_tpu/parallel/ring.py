"""Ring attention — sequence/context parallelism over the 'seq' mesh axis.

The reference has NO long-context machinery (SURVEY §5 "Long-context:
Absent"); this is the parity-plus subsystem the TPU build treats as
first-class. Design follows the ring-attention recipe (blockwise attention
+ online softmax, KV blocks rotating around the ring one hop per step so
each device only ever holds 1/N of K/V, and the permute overlaps with the
block computation):

  * the sequence dim of Q/K/V is sharded over `axis_name` (mesh 'seq');
  * each of N ring steps computes one blockwise-attention partial and
    `lax.ppermute`s the KV block to the next neighbor (ICI hop);
  * online softmax (fp32 running max / sum / weighted output) makes the
    result numerically identical to full dense attention;
  * causal masking uses global positions derived from each block's device
    of origin, so the rotated blocks mask correctly.

`ring_attention` is written to run inside `shard_map` (it needs the axis
name bound); `ring_self_attention` is the host-level wrapper that builds
the shard_map over a mesh.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.nn.attention import (NEG_INF, online_softmax_finish,
                                    online_softmax_step)
from bigdl_tpu.parallel.mesh import SEQ_AXIS


def ring_attention(q, k, v, *, axis_name: str = SEQ_AXIS,
                   causal: bool = False, scale: Optional[float] = None):
    """Attention over a sequence-sharded (B, H, T_local, d) q/k/v.

    Must run inside `shard_map` (or `pmap`) with `axis_name` bound. Returns
    the (B, H, T_local, d) output shard. Peak memory per device is
    O(T_local^2) logits for one block pair instead of O(T_global^2)."""
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, H, t_local, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    q_pos = my_idx * t_local + jnp.arange(t_local)
    # send each device's KV to its LOWER neighbor: after s steps we hold
    # the block that originated at (my_idx + s) mod n
    perm = [(i, (i - 1) % n) for i in range(n)]

    def body(s, carry):
        o, m, l, kb, vb = carry
        src = (my_idx + s) % n
        pos_mask = None
        if causal:
            k_pos = src * t_local + jnp.arange(t_local)
            pos_mask = q_pos[:, None] >= k_pos[None, :]
        o, m, l = online_softmax_step(q, kb, vb, o, m, l, scale, pos_mask)
        # rotate KV for the next step (XLA overlaps this with compute)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return o, m, l, kb, vb

    # derive initial carries from q so they inherit q's varying manual axes
    # (shard_map type system: plain zeros would be unvarying and mismatch
    # the loop-carry types)
    zero = (q * 0).astype(jnp.float32)
    o0 = zero
    m0 = zero[..., 0] + NEG_INF
    l0 = zero[..., 0]
    o, m, l, _, _ = jax.lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    return online_softmax_finish(o, l, q.dtype)


def ring_self_attention(mesh: Mesh, q, k, v, *, causal: bool = False,
                        seq_axis: str = SEQ_AXIS):
    """Host-level entry: shards (B, H, T, d) q/k/v over `seq_axis` along T
    (and batch over 'data' when present) and runs :func:`ring_attention`.
    """
    from jax import shard_map
    from bigdl_tpu.parallel.mesh import DATA_AXIS

    batch = DATA_AXIS if DATA_AXIS in mesh.axis_names else None
    spec = P(batch, None, seq_axis, None)

    fn = shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    sh = NamedSharding(mesh, spec)
    return fn(jax.device_put(q, sh), jax.device_put(k, sh),
              jax.device_put(v, sh))


class RingAttention:
    """Callable `attn_impl` backend for MultiHeadAttention: use when the
    model body runs inside shard_map with the sequence dimension sharded
    over `axis_name` — e.g.
    `MultiHeadAttention(d, h, attn_impl=RingAttention())`. Masks beyond
    `causal=` are not supported (mask tensors would need to be sequence-
    sharded alongside q/k/v)."""

    def __init__(self, axis_name: str = SEQ_AXIS):
        self.axis_name = axis_name

    def __call__(self, q, k, v, *, mask=None, causal=False):
        if mask is not None:
            raise ValueError("RingAttention supports causal= only")
        return ring_attention(q, k, v, axis_name=self.axis_name,
                              causal=causal)
