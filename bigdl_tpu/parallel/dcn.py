"""DCN-tier gradient exchange — accumulate locally, exchange every T.

The two-tier ('slice', 'data') mesh (parallel/mesh.py) reduces gradients
over ICI inside a slice, but the reference path still all-reduces across
slices EVERY step — the pattern that dies over a data-center network.
Following Local SGD (Stich, 2019) and DiLoCo (Douillard et al., 2023),
this module makes the cross-slice leg a low-frequency exchange:

  * each slice ACCUMULATES its own gradient contribution locally for T
    steps (BIGDL_TPU_SLICE_EXCHANGE_EVERY) in a per-slice accumulator —
    leaf shape `(S, *param_shape)`, laid out `P('slice', ...)` so row s
    lives on slice s's devices;
  * every T-th step a shard_map'd exchange does an EXPLICIT psum over
    ('slice',) — `mesh.cross_slice_accumulated_exchange` — and applies
    an outer correction: plain averaging by default, or a DiLoCo-style
    outer Nesterov momentum (BIGDL_TPU_SLICE_OUTER=nesterov);
  * on the wire, BIGDL_TPU_SLICE_GRAD_COMPRESS=int8 sends per-256-block
    int8 + fp32 scales (the nn/quantized window recipe) with ERROR
    FEEDBACK: the quantization residual seeds the next window's
    accumulator, so compression error never biases the outer step;
  * the accumulator is threaded through the fused K-scan as part of the
    carry AND as a program input/output, so T > steps_per_call spans
    jitted calls without extra host syncs (optim/local.py).

T=1 with compression off is the pre-DCN path — the machinery never arms
and training is bit-identical (tests/test_dcn_exchange.py). Failover
semantics: on a slice loss at a K-boundary the SURVIVORS' accumulator
rows are preserved and the lost slice's in-window contribution is
explicitly dropped and counted (resilience/failover.py
remap_accumulator_rows); the accumulator and outer state ride the
checkpoint next to params/slots, so kill-and-resume mid-window is
exact (resilience/snapshot.py).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass

import numpy as np

log = logging.getLogger("bigdl_tpu")

# per-block scale granularity of the int8 wire format — mirrors the
# BigQuant-style windows in nn/quantized.quantize_weight_blocked
INT8_BLOCK = 256

_COMPRESS_ALIASES = {"": "", "0": "", "off": "", "none": "",
                     "bf16": "bfloat16", "bfloat16": "bfloat16",
                     "int8": "int8"}


def normalize_compress(name: str) -> str:
    """Canonical SLICE_GRAD_COMPRESS value ('' | 'bfloat16' | 'int8')."""
    key = (name or "").strip().lower()
    if key not in _COMPRESS_ALIASES:
        raise ValueError(
            f"BIGDL_TPU_SLICE_GRAD_COMPRESS={name!r} — expected '', "
            f"'bfloat16' or 'int8'")
    return _COMPRESS_ALIASES[key]


@dataclass(frozen=True)
class DcnConfig:
    """Armed DCN-exchange configuration, captured at step-build time
    (a failover rebuild re-derives it from the survivor mesh)."""

    every: int          # T — steps accumulated per exchange window
    compress: str       # '' | 'bfloat16' | 'int8'
    outer: str          # '' (plain averaging) | 'nesterov'
    slices: int         # live slice rows S on the CURRENT mesh
    momentum: float = 0.9

    @property
    def key(self):
        """The _step_key component: everything that shapes the program."""
        return (self.every, self.compress, self.outer, self.slices,
                self.momentum)


def init_exchange_state(params_like, cfg: DcnConfig):
    """Fresh host-side exchange state: zero per-slice accumulators
    (fp32 — accumulation should not inherit a bf16 param dtype), zero
    outer-momentum state when armed, zero residual norm."""
    def acc_leaf(leaf):
        dt = (np.float32 if np.issubdtype(np.dtype(leaf.dtype), np.floating)
              else leaf.dtype)
        return np.zeros((cfg.slices,) + tuple(leaf.shape), dt)

    import jax
    acc = jax.tree.map(acc_leaf, params_like)
    outer = ({"m": jax.tree.map(
        lambda leaf: np.zeros(tuple(leaf.shape), np.float32), params_like)}
        if cfg.outer == "nesterov" else {})
    return {"acc": acc, "outer": outer,
            "residual_norm": np.float32(0.0)}


def wire_bytes_per_exchange(params_like, compress: str,
                            block: int = INT8_BLOCK) -> int:
    """Bytes ONE slice puts on the DCN per exchange — the all-gather /
    all-reduce payload for every floating gradient leaf: fp32 raw, bf16
    halves it, int8 sends one byte per element (padded to the block
    size) plus one fp32 scale per block. Feeds the exchange/wire_bytes
    counter."""
    import jax
    total = 0
    for leaf in jax.tree.leaves(params_like):
        if not np.issubdtype(np.dtype(leaf.dtype), np.floating):
            continue
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        if compress == "int8":
            nb = -(-n // block)
            total += nb * block + 4 * nb
        elif compress == "bfloat16":
            total += 2 * n
        else:
            total += 4 * n
    return total
