"""Runtime bring-up and device-mesh construction — the analogue of the
reference's `Engine` singleton (reference: utils/Engine.scala:106-242).

The reference discovers nodes/cores from SparkConf per cluster-manager type
(utils/Engine.scala:485-567) and sizes thread pools; here the "cluster" is a
`jax.sharding.Mesh` over the device grid, and multi-host bring-up is
`jax.distributed.initialize` (the analogue of the reference's per-executor
singleton check + py4j gateway bootstrap, utils/Engine.scala:146-186,266).

Mesh axes (superset of the reference's parallelism inventory, SURVEY §2.13 —
the reference only has data parallelism; tensor/pipeline/sequence/expert axes
are the parity-plus TPU extensions):
  slice  — slice-level data parallelism (two-tier: DCN across slices)
  data   — batch sharding (sync data-parallel SGD)
  model  — tensor parallelism (megatron-style param sharding)
  pipe   — pipeline stages
  seq    — sequence/context parallelism (ring attention)
  expert — MoE expert parallelism

Two-tier topology (BIGDL_TPU_SLICES > 1): the batch axis splits into
`('slice', 'data')` — gradients reduce over ICI inside a slice and the
cross-slice half of the exchange is factored into its own labeled scope
(`cross_slice_exchange`) so it can later be lowered to DCN-friendly
(lower-frequency or compressed) exchange. Params stay replicated across
slices; ZeRO-1 slots default to the composed ('slice', 'data') windows
(bit-identical to the flat mesh at equal global batch — the failover
equivalence tests rely on it) with BIGDL_TPU_ZERO1_SLICE_LOCAL opting
into slice-redundant slots instead. In-run slice failover lives in
resilience/failover.py.
"""

from __future__ import annotations

import logging
import math
import os
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

log = logging.getLogger("bigdl_tpu")

SLICE_AXIS = "slice"
DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"

# Canonical axis order: slice outermost (pure DCN), then data, then pipe,
# then the ICI-heavy axes innermost so tensor/sequence collectives ride
# the fastest links (scaling-book recipe: keep high-traffic axes on ICI).
AXIS_ORDER = (SLICE_AXIS, DATA_AXIS, PIPE_AXIS, EXPERT_AXIS, SEQ_AXIS,
              MODEL_AXIS)


def mesh_shape_for(n_devices: int, *, slices: int = 1, model: int = 1,
                   pipe: int = 1, seq: int = 1, expert: int = 1,
                   data: Optional[int] = None) -> Dict[str, int]:
    """Resolve a full axis->size dict; `data` auto-fills remaining devices."""
    fixed = slices * model * pipe * seq * expert
    if n_devices % fixed != 0:
        raise ValueError(
            f"{n_devices} devices not divisible by "
            f"slices*model*pipe*seq*expert={fixed}")
    if data is None:
        data = n_devices // fixed
    if data * fixed != n_devices:
        raise ValueError(
            f"mesh {data}x{fixed} != {n_devices} devices")
    return {SLICE_AXIS: slices, DATA_AXIS: data, PIPE_AXIS: pipe,
            EXPERT_AXIS: expert, SEQ_AXIS: seq, MODEL_AXIS: model}


def create_mesh(devices: Optional[Sequence[jax.Device]] = None, *,
                slices: Optional[int] = None,
                model: int = 1, pipe: int = 1, seq: int = 1,
                expert: int = 1, data: Optional[int] = None,
                drop_trivial_axes: bool = False) -> Mesh:
    """Build a named mesh over `devices` (default: all).

    `slices` (default: BIGDL_TPU_SLICES) splits the batch dimension into
    a two-tier `('slice', 'data')` topology — one 'slice' row per TPU
    slice, devices_per_slice along 'data'. The 'slice' axis only appears
    in the mesh when slices > 1, so single-slice jobs keep today's axis
    names exactly (a survivor mesh built by resilience/failover.py DOES
    keep a size-1 'slice' axis: its specs must stay valid mid-run).

    With `drop_trivial_axes`, size-1 axes are omitted — useful for tests
    that want a pure-DP mesh named ('data',).
    """
    if slices is None:
        from bigdl_tpu.utils import config
        slices = config.get("SLICES")
    devices = list(devices if devices is not None else jax.devices())
    shape = mesh_shape_for(len(devices), slices=slices, model=model,
                           pipe=pipe, seq=seq, expert=expert, data=data)
    names = tuple(a for a in AXIS_ORDER
                  if not (a == SLICE_AXIS and shape[a] == 1)
                  and not (drop_trivial_axes and shape[a] == 1))
    if not names:
        names = (DATA_AXIS,)
    dims = tuple(shape[a] for a in names)
    grid = np.asarray(devices).reshape(dims)
    return Mesh(grid, names)


def composed_data_axis(mesh) -> "Optional[str]":
    """The composed batch axis, when the mesh carries one — the dp×pp /
    dp×ep / dp×sp composition rule shared by Pipeline, MoELM and
    SeqParallelLM: batch shards over DATA_AXIS while the subsystem's own
    axis carries its collectives."""
    return DATA_AXIS if DATA_AXIS in mesh.axis_names else None


def data_axis_size(mesh) -> int:
    """Total batch-sharding ways: the product of the 'slice' and 'data'
    axis sizes present on the mesh (1 when it carries neither). A global
    batch must divide by this — on a two-tier 2×4 mesh that is 8, same
    as the flat 8-device mesh it is numerically equivalent to."""
    n = 1
    for ax in (SLICE_AXIS, DATA_AXIS):
        if ax in mesh.axis_names:
            n *= mesh.shape[ax]
    return n


def slice_axis_size(mesh) -> int:
    """Number of slice rows (1 on a flat mesh)."""
    return mesh.shape[SLICE_AXIS] if SLICE_AXIS in mesh.axis_names else 1


def cross_slice_exchange(grads, mesh, compress_dtype=None):
    """The cross-slice half of the gradient reduction, factored into its
    own labeled scope. Under GSPMD jit the all-reduce over the composed
    ('slice', 'data') batch axes is inserted by the partitioner; this
    seam marks where the cross-slice leg belongs so a later lowering can
    make it DCN-friendly — lower-frequency, or compressed on the wire:
    with `compress_dtype` (BIGDL_TPU_SLICE_GRAD_DTYPE, e.g. bfloat16)
    every floating gradient leaf round-trips through that dtype inside
    the `cross_slice_grad_exchange` scope, so the converts (and the
    collectives sharing their fusion) carry the label in HLO metadata.
    Identity on a mesh without a >1 'slice' axis, and bit-identical to
    no-op when compression is off — the flat-mesh ≡ two-tier-mesh
    equivalence tests rely on that."""
    if (mesh is None or SLICE_AXIS not in mesh.axis_names
            or mesh.shape[SLICE_AXIS] <= 1):
        return grads
    if compress_dtype is None:
        return grads
    import jax.numpy as jnp

    def one(g):
        if hasattr(g, "dtype") and jnp.issubdtype(g.dtype, jnp.floating):
            return g.astype(compress_dtype).astype(g.dtype)
        return g

    with jax.named_scope("cross_slice_grad_exchange"):
        return jax.tree.map(one, grads)


def _quantize_int8_blocks(x, block: int):
    """Symmetric per-block int8 for a gradient leaf (the traced mirror of
    nn/quantized.quantize_weight_blocked's window recipe): flatten, pad
    to a block multiple, one fp32 scale = max|x|/127 per block. Returns
    (q (nb, block) int8, scale (nb, 1) fp32)."""
    import jax.numpy as jnp
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    xb = flat.reshape(-1, block)
    amax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _unblock(blocks, shape):
    """Undo _quantize_int8_blocks' flatten+pad: (nb, block) -> shape."""
    n = 1
    for d in shape:
        n *= int(d)
    return blocks.reshape(-1)[:n].reshape(shape)


def cross_slice_accumulated_exchange(acc, mesh, *, compress: str = "",
                                     block: int = 256):
    """The REAL lowering of the `cross_slice_grad_exchange` seam: the
    exchange-every-T leg of the DCN-tier gradient exchange
    (parallel/dcn.py; docs/parallelism.md "DCN-tier exchange").

    `acc` is a pytree of per-slice accumulators with leaf shape
    `(S, *shape)` — row s holds slice s's locally-accumulated gradient
    contribution, laid out `P('slice', ...)`. A shard_map over the mesh
    gives each slice its own row; the cross-slice reduction is an
    EXPLICIT collective over ('slice',) — `psum`/`pmean` uncompressed,
    or an `all_gather` of the int8 blocks + per-block scales (the actual
    DCN payload) followed by a local dequantize+mean when compressed.

    Error feedback: the per-slice compression residual
    `acc_s - dequant(quant(acc_s))` is returned for the caller to seed
    the NEXT window's accumulator with, so quantization error re-enters
    the pipeline instead of biasing the outer step (zero when
    compress='').

    Returns `(mean_tree, residual_tree, residual_norm)`:
      * mean_tree — cross-slice mean of the (de)compressed accumulators,
        leaf shape `*shape`, replicated;
      * residual_tree — per-slice residuals, leaf shape `(S, *shape)`;
      * residual_norm — scalar: slice-mean L2 norm of the residuals.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    S = slice_axis_size(mesh)
    in_specs = jax.tree.map(lambda _: P(SLICE_AXIS), acc)

    def body(acc_blk):
        sq = jnp.float32(0.0)
        leaves, treedef = jax.tree_util.tree_flatten(acc_blk)
        means, resids = [], []
        for a in leaves:
            x = a[0]                       # this slice's accumulator row
            if not jnp.issubdtype(a.dtype, jnp.floating):
                means.append(x)
                resids.append(jnp.zeros_like(a))
                continue
            if compress == "int8":
                q, scale = _quantize_int8_blocks(x, block)
                # the wire payload: int8 blocks + fp32 per-block scales
                allq = jax.lax.all_gather(q, SLICE_AXIS)
                allsc = jax.lax.all_gather(scale, SLICE_AXIS)
                deq_all = allq.astype(jnp.float32) * allsc   # (S, nb, B)
                mean = _unblock(deq_all.mean(0),
                                x.shape).astype(x.dtype)
                resid = x - _unblock(q.astype(jnp.float32) * scale,
                                     x.shape).astype(x.dtype)
            elif compress in ("bfloat16", "bf16"):
                deq = x.astype(jnp.bfloat16).astype(x.dtype)
                mean = jax.lax.pmean(deq, SLICE_AXIS)
                resid = x - deq
            else:
                mean = jax.lax.pmean(x, SLICE_AXIS)
                resid = jnp.zeros_like(x)
            sq = sq + jnp.sum(jnp.square(resid).astype(jnp.float32))
            means.append(mean)
            resids.append(resid[None])
        norm = jnp.sqrt(jax.lax.pmean(sq, SLICE_AXIS))
        return (jax.tree_util.tree_unflatten(treedef, means),
                jax.tree_util.tree_unflatten(treedef, resids), norm)

    out_specs = (jax.tree.map(lambda _: P(), acc),
                 jax.tree.map(lambda _: P(SLICE_AXIS), acc), P())
    with jax.named_scope("cross_slice_grad_exchange"):
        return shard_map(body, mesh=mesh, in_specs=(in_specs,),
                         out_specs=out_specs, check_vma=False)(acc)


def round_up_to_data_multiple(n: int, mesh) -> int:
    """Smallest multiple of the data-axis size ≥ n — the padding rule
    batch-sharded inference uses so every padded batch shards evenly."""
    k = data_axis_size(mesh)
    return -(-n // k) * k


def host_array_to_global(arr, mesh, spec):
    """Place a host array (identical on every process) as a global array
    sharded by `spec` over `mesh` — multi-host safe for ANY mesh rank:
    under one process this is a device_put; across processes each feeds
    its addressable shards via `jax.make_array_from_callback` (device_put
    cannot address remote shards). Arrays ALREADY carrying the target
    sharding pass through untouched (so a train loop's second step does
    not round-trip every param through the host)."""
    import numpy as np
    from jax.sharding import NamedSharding
    sh = NamedSharding(mesh, spec)
    if isinstance(arr, jax.Array) and hasattr(arr, "sharding"):
        if arr.sharding.is_equivalent_to(sh, arr.ndim):
            return arr
        if not arr.is_fully_addressable:
            raise ValueError(
                f"cannot re-place a cross-host array from sharding "
                f"{arr.sharding} to {sh} on the host — reshard it inside "
                f"a jitted computation instead")
    arr = np.asarray(arr)
    if jax.process_count() == 1:
        return jax.device_put(arr, sh)
    return jax.make_array_from_callback(arr.shape, sh,
                                        lambda idx: arr[idx])


def host_rows_to_global(arr, mesh, axis_name: str):
    """Place a host array whose LEADING dim shards over `axis_name`;
    other mesh axes (if any) replicate. Every process must hold identical
    host values. Shared by Pipeline.shard/_globalize and
    expert_parallel_apply."""
    import numpy as np
    from jax.sharding import PartitionSpec as P
    arr = np.asarray(arr)
    spec = P(axis_name, *([None] * (arr.ndim - 1)))
    return host_array_to_global(arr, mesh, spec)


class Engine:
    """Process-level runtime singleton (reference: utils/Engine.scala).

    `Engine.init()` is the one call a program makes before training:
      * multi-host: wires up the JAX distributed runtime (analogue of the
        reference's executor bootstrap, utils/Engine.scala:146-186);
      * builds the global mesh from env/config;
      * enforces the reference's one-Engine-per-process singleton check
        (utils/Engine.scala:266).
    """

    _mesh: Optional[Mesh] = None
    _initialized = False

    @classmethod
    def init(cls, *, coordinator_address: Optional[str] = None,
             num_processes: Optional[int] = None,
             process_id: Optional[int] = None,
             model: int = 1, pipe: int = 1, seq: int = 1, expert: int = 1,
             data: Optional[int] = None) -> Mesh:
        if cls._initialized:
            raise RuntimeError(
                "Engine.init called twice in one process (reference enforces "
                "a per-executor singleton, utils/Engine.scala:266); call "
                "Engine.reset() first if you really mean it")
        if coordinator_address is not None:
            jax.distributed.initialize(coordinator_address=coordinator_address,
                                       num_processes=num_processes,
                                       process_id=process_id)
        cls._mesh = create_mesh(model=model, pipe=pipe, seq=seq,
                                expert=expert, data=data)
        cls._initialized = True
        log.info("Engine: %d devices, mesh %s", len(jax.devices()),
                 dict(zip(cls._mesh.axis_names,
                          cls._mesh.devices.shape)))
        return cls._mesh

    @classmethod
    def mesh(cls) -> Mesh:
        if cls._mesh is None:
            cls._mesh = create_mesh()
        return cls._mesh

    @classmethod
    def node_number(cls) -> int:
        return jax.process_count()

    @classmethod
    def core_number(cls) -> int:
        return jax.local_device_count()

    @classmethod
    def reset(cls):
        cls._mesh = None
        cls._initialized = False
