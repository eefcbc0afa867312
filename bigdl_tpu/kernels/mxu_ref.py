"""MXU-emulated references: bound the EXPECTED fp32-vs-TPU delta.

The TPU MXU computes fp32 matmuls at JAX's DEFAULT precision by
truncating multiplier inputs to bf16 (one pass) while accumulating in
fp32. These references are the same math with every dot's operands
rounded to bf16 and fp32 accumulation, so the envelope a chip run of the
flash/CCE kernels may sit from the fp32 reference is derived on the CPU:
it pins the tolerances in tests/test_kernels.py (MXU_*_TOL).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def bf16_round(x):
    """Round-trip through bf16 — the MXU's one-pass input truncation."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def attention_mxu_ref(q, k, v, causal: bool = False,
                      scale: Optional[float] = None):
    """Dense attention with bf16-truncated dot operands + fp32 softmax/
    accumulation — the expected on-chip numerics for the flash kernel."""
    from bigdl_tpu.nn.attention import NEG_INF, causal_mask
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", bf16_round(q), bf16_round(k),
                   preferred_element_type=jnp.float32) * scale
    if causal:
        s = jnp.where(causal_mask(s.shape[-2], s.shape[-1]), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", bf16_round(p), bf16_round(v),
                      preferred_element_type=jnp.float32)


def cce_mxu_ref(h, w, labels):
    """Cut-cross-entropy NLL with bf16-truncated head matmul — the
    expected on-chip numerics for the CCE kernel."""
    logits = jnp.einsum("nd,vd->nv", bf16_round(h), bf16_round(w),
                        preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
