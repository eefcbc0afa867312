"""Mixture-of-Experts with expert parallelism (no reference equivalent:
SURVEY.md §2.13 marks EP absent in BigDL — TPU-native extension over the
'expert' mesh axis; closest reference precedent is MixtureTable,
nn/MixtureTable.scala, a non-distributed dense mixture).

Design (switch-style, capacity-bounded, XLA-friendly):
  * top-1 router with jitter-free softmax gating and a static
    `capacity = ceil(tokens/experts * capacity_factor)` — fixed shapes, no
    retrace, dropped tokens pass through the residual path;
  * dispatch/combine are one-hot matmuls (MXU) — the standard TPU MoE trick;
  * under `expert_parallel_apply`, experts live one-per-device on the
    'expert' mesh axis and tokens ride `lax.all_to_all` there and back.
Aux losses: load-balancing (Switch eq. 4) + router z-loss.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.core.module import Module, ParamSpec
from bigdl_tpu.core import init as initializers
from bigdl_tpu.parallel.mesh import EXPERT_AXIS


def router_probs(x, w_gate):
    """(tokens, d) @ (d, E) -> softmax probs, plus z-loss ingredients."""
    logits = x @ w_gate
    return jax.nn.softmax(logits, axis=-1), logits


def topk_dispatch(probs, k: int, capacity: int):
    """Top-k routing (generalizes Switch top-1): each token is sent to its
    k best experts with gates renormalized over the chosen k. Returns
    (dispatch (T, E, C), combine (T, E, C), aux_load_balance).

    Queue positions account for earlier choices so a token's i-th choice
    lands after all previous choices' assignments to that expert; tokens
    past capacity are dropped choice-wise (their other choices survive)."""
    t, e = probs.shape
    topv, topi = lax.top_k(probs, k)                          # (T, k)
    gates = topv / jnp.maximum(topv.sum(axis=-1, keepdims=True), 1e-9)
    dispatch = jnp.zeros((t, e, capacity), probs.dtype)
    combine = jnp.zeros((t, e, capacity), probs.dtype)
    counts = jnp.zeros((e,), probs.dtype)
    frac_acc = jnp.zeros((e,), probs.dtype)
    for i in range(k):                                        # k is static
        oh = jax.nn.one_hot(topi[:, i], e, dtype=probs.dtype)
        pos = jnp.cumsum(oh, axis=0) * oh + counts * oh
        slot = (pos.sum(axis=1) - 1).astype(jnp.int32)
        keep = slot < capacity
        slot_oh = jax.nn.one_hot(jnp.where(keep, slot, capacity),
                                 capacity + 1,
                                 dtype=probs.dtype)[:, :capacity]
        disp_i = oh[:, :, None] * slot_oh[:, None, :]
        dispatch = dispatch + disp_i
        combine = combine + disp_i * (gates[:, i] * keep)[:, None, None]
        counts = counts + oh.sum(axis=0)
        frac_acc = frac_acc + oh.mean(axis=0)
    # Switch eq. 4 generalized: E * sum_e (assignments_e / k) * mean_prob_e
    aux = e * jnp.sum(frac_acc / k * probs.mean(axis=0))
    return dispatch, combine, aux


def top1_dispatch(probs, capacity: int):
    """Switch routing: returns (dispatch (T, E, C) bool-ish float,
    combine (T, E, C) float, aux_load_balance_loss).

    Token t goes to expert e = argmax probs[t]; its slot is its position
    among tokens routed to e; tokens past capacity are dropped (combine=0)."""
    t, e = probs.shape
    expert_idx = jnp.argmax(probs, axis=-1)                  # (T,)
    onehot = jax.nn.one_hot(expert_idx, e, dtype=probs.dtype)  # (T, E)
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot                # (T, E)
    slot = (pos.sum(axis=1) - 1).astype(jnp.int32)           # (T,)
    keep = slot < capacity
    gate = (probs * onehot).sum(axis=1) * keep               # (T,)
    slot_oh = jax.nn.one_hot(jnp.where(keep, slot, capacity),
                             capacity + 1, dtype=probs.dtype)[:, :capacity]
    dispatch = onehot[:, :, None] * slot_oh[:, None, :]      # (T, E, C)
    combine = dispatch * gate[:, None, None]
    # Switch load-balancing loss: E * sum_e fraction_e * mean_prob_e
    frac = onehot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = e * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


class MoE(Module):
    """Switch-style MoE layer: top-1 routed expert FFNs + residual
    passthrough for dropped tokens.

    apply(params, state, x:(B, T, d)) -> ((B, T, d), aux_losses dict in
    state['aux']). Use `expert_parallel_apply` to run the expert FFNs
    sharded over the 'expert' mesh axis."""

    def __init__(self, d_model: int, d_ff: int, n_experts: int,
                 capacity_factor: float = 1.25, top_k: int = 1,
                 dropless: bool = False, name=None):
        super().__init__(name)
        self.d_model, self.d_ff, self.n_experts = d_model, d_ff, n_experts
        self.capacity_factor = capacity_factor
        self.top_k = top_k
        # dropless: capacity = worst-case tokens-per-expert (T), so no token
        # is ever dropped. Exact but memory ∝ T·E·C — the block-sparse
        # MegaBlocks-style path is the production answer; this is the
        # correctness-first one.
        self.dropless = dropless

    def param_specs(self):
        d, f, e = self.d_model, self.d_ff, self.n_experts
        return {
            "gate": ParamSpec((d, e), initializers.xavier, fan_in=d,
                              fan_out=e),
            # experts stacked on a leading E axis — shard it over 'expert'
            "w_up": ParamSpec((e, d, f), initializers.xavier, fan_in=d,
                              fan_out=f),
            "w_down": ParamSpec((e, f, d), initializers.xavier, fan_in=f,
                                fan_out=d),
        }

    def capacity(self, n_tokens: int) -> int:
        import math
        if self.dropless:
            return n_tokens
        return max(1, int(math.ceil(
            n_tokens * self.top_k / self.n_experts * self.capacity_factor)))

    def _dispatch(self, probs, cap):
        if self.top_k == 1:
            return top1_dispatch(probs, cap)
        return topk_dispatch(probs, self.top_k, cap)

    def _experts(self, params, xe):
        """xe (E, C', d) -> (E, C', d): per-expert FFN via batched matmul."""
        h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", xe, params["w_up"]))
        return jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    def _apply(self, params, state, x, *, training=False, rng=None):
        b, t, d = x.shape
        tokens = x.reshape(b * t, d)
        probs, logits = router_probs(tokens, params["gate"])
        cap = self.capacity(b * t)
        dispatch, combine, aux = self._dispatch(probs, cap)
        xe = jnp.einsum("td,tec->ecd", tokens, dispatch)     # (E, C, d)
        ye = self._experts(params, xe)
        y = jnp.einsum("ecd,tec->td", ye, combine)
        z_loss = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        new_state = {**state,
                     "aux": {"load_balance": aux, "z_loss": z_loss}}
        # dropped tokens (combine all-zero) fall through as identity
        return (tokens + y).reshape(b, t, d), new_state


def expert_parallel_forward(moe: MoE, params_local, x_local,
                            axis_name: str = EXPERT_AXIS):
    """The shard-level expert-parallel MoE forward — runs INSIDE a
    shard_map with `axis_name` bound (expert_parallel_apply wraps it; a
    model whose whole train step lives in one shard_map, e.g.
    models/moe_lm.py, calls it directly). x_local (B_local, T, d) with
    batch sharded over `axis_name`; expert params sharded on their
    leading E axis; gate replicated. Returns (out_local, aux) with aux
    pmean'd over the axis. Differentiable end to end (the all_to_alls
    transpose to all_to_alls)."""
    b, t, d = x_local.shape
    tokens = x_local.reshape(b * t, d)
    probs, logits = router_probs(tokens, params_local["gate"])
    cap = moe.capacity(b * t)
    dispatch, combine, aux = moe._dispatch(probs, cap)
    xe = jnp.einsum("td,tec->ecd", tokens, dispatch)     # (E, C, d)
    # (E, C, d) -> (E/n, n*C, d): this device's expert group's queues
    # from every device
    xe = lax.all_to_all(xe, axis_name, split_axis=0, concat_axis=1,
                        tiled=True)
    ye = moe._experts(params_local, xe)
    ye = lax.all_to_all(ye, axis_name, split_axis=1, concat_axis=0,
                        tiled=True)
    y = jnp.einsum("ecd,tec->td", ye, combine)
    z_loss = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    aux_out = {
        "load_balance": lax.pmean(aux, axis_name),
        "z_loss": lax.pmean(z_loss, axis_name),
    }
    return (tokens + y).reshape(b, t, d), aux_out


def expert_parallel_apply(moe: MoE, params, x, mesh: Mesh,
                          axis_name: str = EXPERT_AXIS):
    """Run the MoE layer with BOTH tokens and experts sharded over
    `axis_name`: each device routes its local batch shard (so router +
    dispatch FLOPs scale 1/n), an all_to_all hands every device the queues
    for its E/n experts from ALL devices (per-device expert FLOPs:
    (E/n)·(n·C_local) = E·C_local — 1/n of the global expert work), and the
    reverse all_to_all brings results home. Capacity is enforced per device
    shard, which with the usual capacity_factor slack matches the global
    behavior; a token's expert assignment is identical to the unsharded
    layer's.

    Returns (out, aux) where aux = {'load_balance', 'z_loss'} psum-averaged
    over the axis — feed them into the loss exactly as with `MoE.apply`.
    Requires: axis size divides both n_experts and the batch dim."""
    n = mesh.shape[axis_name]
    if moe.n_experts % n:
        raise ValueError(f"expert-axis size {n} must divide expert count "
                         f"{moe.n_experts}")
    if x.shape[0] % n:
        raise ValueError(f"expert-axis size {n} must divide batch "
                         f"{x.shape[0]}")

    p_spec = {"gate": P(), "w_up": P(axis_name), "w_down": P(axis_name)}

    def shard_fn(params_local, x_local):
        return expert_parallel_forward(moe, params_local, x_local,
                                       axis_name)

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(p_spec, P(axis_name)),
                   out_specs=(P(axis_name), P()),
                   check_vma=False)

    from bigdl_tpu.parallel.mesh import host_rows_to_global

    def place(v, spec):
        if spec == P():
            return jax.device_put(v, NamedSharding(mesh, spec))
        return host_rows_to_global(np.asarray(v), mesh, axis_name)

    sharded_params = {k: place(v, p_spec[k]) for k, v in params.items()}
    xs = place(x, P(axis_name, *([None] * (x.ndim - 1))))
    return fn(sharded_params, xs)
