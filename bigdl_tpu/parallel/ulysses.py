"""Ulysses-style sequence parallelism — all-to-all head/sequence reshard
(no reference equivalent: SURVEY.md §2.13/§5 mark sequence parallelism as
absent in BigDL; built TPU-native alongside ring attention in ring.py).

Scheme (DeepSpeed-Ulysses): activations arrive sharded on the SEQUENCE dim.
For attention, `all_to_all` re-shards to the HEAD dim (each device then
holds ALL positions for H/N heads — attention is exact and local), and a
second all_to_all restores sequence sharding. Two all-to-alls ride ICI;
communication volume per device is O(T·d/N), vs ring attention's O(T·d)
streamed — Ulysses wins when heads divide evenly and ICI all-to-all
bandwidth is good; ring wins at very long T with few heads.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map
from jax.lax import axis_size

from bigdl_tpu.parallel.ring import SEQ_AXIS


def ulysses_attention(q, k, v, *, axis_name: str = SEQ_AXIS,
                      causal: bool = False,
                      scale: Optional[float] = None):
    """Call INSIDE shard_map with q/k/v (B, H, T_local, d) sequence-sharded
    on `axis_name`. Returns (B, H, T_local, d), sequence-sharded again.
    The axis size must divide the head count H (each device takes H/N
    heads after the all-to-all)."""
    n = axis_size(axis_name)
    h = q.shape[1]
    if h % n:
        raise ValueError(f"seq-axis size {n} must divide head count {h}")

    def to_heads(x):
        # (B, H, T/N, d) -> (B, H/N, T, d): split heads, concat sequence
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    from bigdl_tpu.nn.attention import dot_product_attention, causal_mask
    mask = causal_mask(qh.shape[2], kh.shape[2]) if causal else None
    out = dot_product_attention(qh, kh, vh, mask, scale=scale)
    return to_seq(out)


def ulysses_self_attention(mesh: Mesh, q, k, v, *, causal: bool = False,
                           seq_axis: str = SEQ_AXIS):
    """Convenience wrapper: shards (B, H, T, d) inputs on T over `seq_axis`
    and runs ulysses_attention under shard_map (mirrors
    ring.ring_self_attention)."""
    from bigdl_tpu.parallel.mesh import DATA_AXIS
    batch = DATA_AXIS if DATA_AXIS in mesh.axis_names else None
    spec = P(batch, None, seq_axis, None)
    fn = shard_map(
        functools.partial(ulysses_attention, axis_name=seq_axis,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    sh = NamedSharding(mesh, spec)
    return fn(jax.device_put(q, sh), jax.device_put(k, sh),
              jax.device_put(v, sh))
