"""Device-completion helpers for timing code.

JAX returns from a dispatch before the device has finished, so a timed
region must end by waiting for the work it timed. On the TPU v5e
`jax.block_until_ready` is that wait. Observed (my chip run, PR 21, one
v5e chip, JAX 0.9 / libtpu 0.0.34): a chain of 64 data-dependent 4096^2
bf16 matmuls was dispatched in 25 ms and took 49.6 ms to
`block_until_ready` (177 TFLOP/s of the chip's 197 peak) against 49.9 ms
when completion was forced by fetching one element to the host; a host
fetch issued after `block_until_ready` returned added 1.5-1.8 ms, which is
the round trip of the fetch itself (a tiny op plus its fetch: 1.7 ms). The
two agree, so the helpers below wait with `block_until_ready`: it needs no
extra device program and no transfer.

These helpers are shared by models/perf.py and utils/profile.py so the
timing protocol lives in exactly one place."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _first_elem(leaf):
    """One element of `leaf` without materializing a full copy."""
    return leaf[(0,) * leaf.ndim] if getattr(leaf, "ndim", 0) else leaf


def _array_leaves(tree):
    return [l for l in jax.tree.leaves(tree)
            if hasattr(l, "ndim") and getattr(l, "size", 0)]


def force_completion(tree) -> None:
    """Block until every array leaf of `tree` has been computed."""
    jax.block_until_ready(tree)


def time_steps(step, carry, warmup: int, iters: int):
    """Time `carry, observed = step(carry)` chains: steps are
    data-dependent through `carry`, and the timed region ends when
    `observed` is complete. The single home for the timing loop used by
    models/perf.py.

    Returns (seconds_per_step, final_carry). warmup=0 measures cold
    (compile included) — that is the caller's explicit choice."""
    import time as _time
    observed = carry
    for _ in range(warmup):
        carry, observed = step(carry)
    force_completion(observed)
    t0 = _time.perf_counter()
    for _ in range(iters):
        carry, observed = step(carry)
    force_completion(observed)
    return (_time.perf_counter() - t0) / max(1, iters), carry


def chain_dep(x, out):
    """Return `x` unchanged in value but data-dependent on EVERY array leaf
    of `out`, so a timing loop that re-runs one call on the same input
    still forms a chain: call i+1 cannot start before call i is fully
    computed. Non-finite leaf values are masked so the contract holds
    even for overflowing/diverging outputs. It costs a few tiny eager ops
    per link, which a loop over sub-millisecond calls will see."""
    leaves = _array_leaves(out)
    if not leaves:
        return x
    z = sum(_first_elem(l).astype(jnp.float32) for l in leaves) * 0.0
    z = jnp.where(jnp.isfinite(z), z, 0.0)
    return x + z.astype(x.dtype)
