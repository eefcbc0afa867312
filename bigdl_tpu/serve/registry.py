"""Multi-model registry: named models, each with its own params/mesh/
dtype, a valid-mask bucket forward, and per-bucket AOT executables.

The reference's serving surface loads one model per `PredictionService`
(PredictionService.scala:56-66); production serving multiplexes MANY
models behind one process, so the registry owns the per-model state the
engine schedules over:

  * **forward** — ONE jitted `fn(params, state, x, valid)` shared by
    every bucket: the model's inference apply on a zero-padded batch,
    with the `[B]` bool valid mask zeroing the padded rows' outputs so
    pad content can never leak to a client (PR 5's padded valid-mask
    trick, applied to serving). Under a mesh the batch shards over the
    composed batch axes and params/state replicate (the GSPMD
    NamedSharding idiom — SNIPPETS [3]).
  * **buckets** — powers-of-two × `data_axis_size(mesh)` capped at
    `max_batch`, exactly `PredictionService._bucket`'s rule, so the
    model compiles O(log max_batch) programs total and every padded
    batch shards evenly.
  * **int8** — behind BIGDL_TPU_SERVE_INT8 (or `int8=True` per model)
    the registered float model is quantized on registration
    (nn/quantized.quantize); on a TPU backend QuantizedLinear routes
    through the fused Pallas `kernels/quantized_matmul.py` epilogue
    automatically.
  * **AOT** — `precompile()` lowers + compiles the forward for every
    bucket ahead of traffic (compilecache.precompile_buckets), so a
    warm-started server with the persistent compile cache enabled
    compiles ZERO fresh programs; dispatch prefers the AOT executable
    with a one-shot fallback to the jit path (the trainers' _StepEntry
    discipline).
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional, Tuple

import numpy as np

from bigdl_tpu import observe
from bigdl_tpu.analysis.sancov import sanctioned_sync
from bigdl_tpu.utils.threads import make_lock

log = logging.getLogger("bigdl_tpu")


def serve_buckets(max_batch: int, mesh=None) -> Tuple[int, ...]:
    """The bucket ladder: min_bucket × {1, 2, 4, ...} up to max_batch
    (max_batch itself rounded up to a data-axis multiple). min_bucket is
    the mesh's data-axis size (1 without a mesh) so every bucket shards
    evenly."""
    lo = 1
    if mesh is not None:
        from bigdl_tpu.parallel.mesh import (data_axis_size,
                                             round_up_to_data_multiple)
        lo = data_axis_size(mesh)
        max_batch = round_up_to_data_multiple(max_batch, mesh)
    buckets: List[int] = []
    b = lo
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch)
    return tuple(sorted(set(buckets)))


def _serve_forward(model, mesh=None):
    """Build the jitted serving forward `fn(params, state, x, valid)`:
    the inference apply on the padded batch, with the padded rows'
    outputs zeroed via the valid mask. Under a mesh, params/state are
    pinned replicated and the (pre-placed) batch keeps its composed
    batch-axis sharding."""
    import jax
    import jax.numpy as jnp

    def fn(p, s, x, valid):
        out = model.apply(p, s, x, training=False)[0]
        mask = valid.reshape((valid.shape[0],) + (1,) * (out.ndim - 1))
        return jnp.where(mask, out, jnp.zeros((), out.dtype))

    if mesh is None:
        return jax.jit(fn)
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    return jax.jit(fn, in_shardings=(rep, rep, None, None),
                   out_shardings=rep)


class ModelEntry:
    """One served model: params/state/mesh, the valid-mask forward, the
    bucket ladder, and (after `precompile()`) per-bucket AOT
    executables."""

    def __init__(self, name: str, model, params, state, *,
                 mesh=None, max_batch: int = 256,
                 int8: Optional[bool] = None,
                 decode: bool = False,
                 num_slots: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 paged: Optional[bool] = None,
                 kv_block: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_cache_blocks: Optional[int] = None,
                 sampling: Optional[bool] = None,
                 kv_shard: Optional[bool] = None,
                 speculative_tokens: Optional[int] = None):
        from bigdl_tpu.utils import config
        self.name = name
        self.mesh = mesh
        if int8 is None:
            int8 = config.get("SERVE_INT8")
        self.int8 = bool(int8)
        if self.int8 and decode:
            raise ValueError(
                f"serve[{name}]: decode=True is incompatible with the "
                f"int8 registration path (the quantized module does not "
                f"carry the slot-decode contract)")
        if self.int8:
            from bigdl_tpu.nn.quantized import quantize
            model, params = quantize(model, params)
            log.info("serve[%s]: registered int8-quantized forward", name)
        self.model = model
        self.params = params
        self.state = state
        # memory plane (observe/memz.py): refuse a registration that
        # cannot fit the remaining headroom (a loud CapacityError with
        # the per-owner report beats an OOM mid-traffic), then account
        # the model's resident trees under `serve/<name>/params` —
        # weakref-finalized so a dropped entry releases its bytes
        from bigdl_tpu.observe import memz as _memz
        need = _memz.tree_nbytes(params) + _memz.tree_nbytes(state)
        if not decode:
            # the decode path admission-checks params + the KV pool
            # together (DecodeEntry, closed form, before any allocation)
            _memz.admission_check(need, f"serve model {name!r}")
        self._mem_handle = _memz.ledger().register(
            f"serve/{name}/params", anchor=self, nbytes=need,
            kind="params", note=type(model).__name__)
        self.buckets = serve_buckets(max_batch, mesh)
        self.max_batch = self.buckets[-1]
        self._jitted = _serve_forward(model, mesh)
        self._aot: Dict[int, object] = {}
        self._placed_params = None     # mesh: replicate params/state once
        # decode=True: the iteration-level autoregressive path — paged
        # KV pool + AOT prefill/decode programs (serve/decode.py); the
        # engine drives it through a DecodeScheduler instead of a
        # ContinuousBatcher
        self.decode = None
        if decode:
            from bigdl_tpu.serve.decode import DecodeEntry
            self.decode = DecodeEntry(
                name, model, params, mesh=mesh, num_slots=num_slots,
                max_seq_len=max_seq_len, prefill_chunk=prefill_chunk,
                eos_id=eos_id, paged=paged, kv_block=kv_block,
                kv_pool_blocks=kv_pool_blocks, prefix_cache=prefix_cache,
                prefix_cache_blocks=prefix_cache_blocks,
                sampling=sampling, kv_shard=kv_shard,
                speculative_tokens=speculative_tokens)

    def precompile_decode(self) -> Dict[str, Dict]:
        """AOT-compile the decode step + every prefill-chunk bucket
        (decode registrations only; see DecodeEntry.precompile)."""
        if self.decode is None:
            raise ValueError(f"model {self.name!r} was not registered "
                             f"with decode=True")
        return self.decode.precompile()

    # ------------------------------------------------------------ forward
    def _trees(self):
        """Params/state, replicated onto the mesh once (first dispatch)
        so steady-state serving never re-places them."""
        if self.mesh is None:
            return self.params, self.state
        if self._placed_params is None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from bigdl_tpu.parallel.mesh import host_array_to_global
            rep = P()
            place = lambda t: jax.tree.map(          # noqa: E731
                lambda a: host_array_to_global(a, self.mesh, rep), t)
            self._placed_params = (place(self.params), place(self.state))
        return self._placed_params

    def forward(self, xs: np.ndarray, valid: np.ndarray):
        """Device forward for one padded bucket batch (no host fetch).
        Prefers the bucket's AOT executable (under a mesh the batch is
        mesh-placed first, so the executable sees the sharded layout it
        was pinned for); a live-layout mismatch falls back to the jit
        path once and drops the executable."""
        p, s = self._trees()
        if self.mesh is not None:
            from bigdl_tpu.parallel.mesh import host_array_to_global
            from bigdl_tpu.parallel.sharding import batch_spec
            xs = host_array_to_global(xs, self.mesh,
                                      batch_spec(self.mesh, xs.ndim))
            valid = host_array_to_global(valid, self.mesh,
                                         batch_spec(self.mesh, 1))
        aot = self._aot.get(xs.shape[0])
        if aot is not None:
            try:
                return aot(p, s, xs, valid)
            except (TypeError, ValueError):  # spec mismatch, not a device error
                log.warning("serve[%s]: AOT executable for bucket %d "
                            "rejected live inputs; falling back to jit",
                            self.name, xs.shape[0])
                self._aot.pop(xs.shape[0], None)
        return self._jitted(p, s, xs, valid)

    def dispatch(self, xs: np.ndarray, n_valid: int) -> np.ndarray:
        """The batcher's downstream: forward the padded pack and fetch
        the result to host — ONE device_get per batch, the only host
        sync serving performs (asserted by tests/test_serve.py)."""
        import jax
        valid = np.zeros((xs.shape[0],), bool)
        valid[:n_valid] = True
        with sanctioned_sync("serve dispatch result fetch"):
            return jax.device_get(self.forward(xs, valid))

    # --------------------------------------------------------------- AOT
    def precompile_for(self, feature_shape: Tuple[int, ...],
                       dtype="float32") -> Dict[int, Dict]:
        """AOT-compile the forward for EVERY bucket before traffic
        arrives (compilecache.precompile_buckets): per-row
        `feature_shape` (no batch dim) + input dtype define the specs;
        with the persistent compile cache warm this costs only
        deserialization, so a restarted server compiles zero fresh
        programs."""
        from bigdl_tpu.compilecache import precompile_buckets
        results, executables = precompile_buckets(
            self._jitted, self.params, self.state, tuple(feature_shape),
            dtype, self.buckets, name=f"serve/{self.name}", mesh=self.mesh)
        self._aot.update(executables)
        return results


class ModelRegistry:
    """Name -> ModelEntry map (register / get / unregister / names)."""

    def __init__(self):
        self._entries: Dict[str, ModelEntry] = {}
        self._lock = make_lock("serve.registry")

    def register(self, name: str, model, params, state, *, mesh=None,
                 max_batch: int = 256,
                 int8: Optional[bool] = None,
                 decode: bool = False,
                 num_slots: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 **decode_opts) -> ModelEntry:
        entry = ModelEntry(name, model, params, state, mesh=mesh,
                           max_batch=max_batch, int8=int8, decode=decode,
                           num_slots=num_slots, max_seq_len=max_seq_len,
                           prefill_chunk=prefill_chunk, eos_id=eos_id,
                           **decode_opts)
        with self._lock:
            if name in self._entries:
                raise ValueError(f"model {name!r} already registered")
            self._entries[name] = entry
        observe.gauge("serve/models").set(len(self._entries))
        return entry

    def get(self, name: str) -> ModelEntry:
        with self._lock:
            try:
                return self._entries[name]
            except KeyError:
                raise KeyError(
                    f"no model {name!r} registered "
                    f"(have: {sorted(self._entries) or 'none'})") from None

    def unregister(self, name: str) -> None:
        with self._lock:
            entry = self._entries.pop(name, None)
        if entry is not None:
            # release the ledger accounting NOW (the weakref finalizer
            # is the backstop for entries dropped without unregister)
            handle = getattr(entry, "_mem_handle", None)
            if handle is not None:
                handle.close()
        observe.gauge("serve/models").set(len(self._entries))

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._entries)
