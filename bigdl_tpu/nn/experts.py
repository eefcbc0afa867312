"""Gated sparse experts of which a chip holds a share (no reference
analogue; the routed-and-shared expert layer of DeepSeek-V3,
arXiv:2412.19437, as the `glm_moe_dsa` configurations state it).

    s = sigmoid(W_g x)                         over ALL routed experts, float32
    chosen = top_k(s + b)                      b: the `noaux_tc` correction
                                               bias, used to choose only
    w_e = s_e / sum_chosen s * scaling         (`norm_topk_prob`)
    y = sum_chosen w_e Expert_e(x) + Expert_shared(x)
    Expert(x) = W_down (silu(W_gate x) * W_up x)

A layer is told which experts it holds, `[first, first + held)` of
`num_experts`: it routes over all of them, as every chip of an
expert-parallel group does, and adds its own experts' terms and the shared
expert's; what the absent experts would add is left out (on one chip there
is no exchange, and nothing stands in for one). No token is dropped
whatever the routing: the token-expert pairs are sorted by expert, every
pair has its row, and the held experts' part is ONE grouped matrix product a
projection (`jax.lax.ragged_dot`: rows of a group meet that group's
matrix); pairs of absent experts sort behind the last group, where no
matrix meets them. `parallel/moe.py` `MoE` (ReLU, top-1, capacity) is
another layer and stays as it is.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.core import init as initializers
from bigdl_tpu.core.module import Module, ParamSpec
from bigdl_tpu.nn.linear import Linear


def route(x, router, bias, top_k: int, scaling: float):
    """x (T, d) -> (chosen (T, k) int32 of all experts, weights (T, k)
    float32). Scores and their normalisation are float32."""
    s = jax.nn.sigmoid(jnp.dot(x, router,
                               preferred_element_type=jnp.float32))
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, w / jnp.sum(w, axis=-1, keepdims=True) * scaling


def grouped_ffn(x, gate, up, down, group_sizes):
    """SwiGLU over rows sorted by group: rows `[sum(sizes[:g]),
    sum(sizes[:g+1]))` of x (m, d) meet expert g of gate/up (n, d, f) and
    down (n, f, d). Rows past the last group come out as they may."""
    h = jax.nn.silu(jax.lax.ragged_dot(x, gate, group_sizes)) \
        * jax.lax.ragged_dot(x, up, group_sizes)
    return jax.lax.ragged_dot(h, down, group_sizes)


class GatedExperts(Module):
    """x (..., d_model) -> y (..., d_model). `mixed` also counts: the
    token-expert pairs the held experts computed and the held experts that
    got a token; it takes `valid` (...) bool, which leaves tokens (a padded
    tail, an inactive row) out of the routed part and of the counts."""

    def __init__(self, d_model: int, d_expert: int, num_experts: int,
                 top_k: int, expert_share: Optional[Tuple[int, int]] = None,
                 num_shared: int = 1, scaling: float = 1.0,
                 name: Optional[str] = None):
        super().__init__(name or "GatedExperts")
        first, held = expert_share or (0, num_experts)
        if not (0 <= first and held >= 1
                and first + held <= num_experts and top_k <= num_experts):
            raise ValueError(
                f"expert_share {(first, held)} is no share of "
                f"{num_experts} experts (top {top_k})")
        self.d_model, self.d_expert = d_model, d_expert
        self.num_experts, self.top_k = num_experts, top_k
        self.first, self.held, self.scaling = first, held, scaling
        for n in ("shared_gate", "shared_up"):
            self.add_child(n, Linear(d_model, d_expert * num_shared,
                                     bias=False))
        self.add_child("shared_down", Linear(d_expert * num_shared, d_model,
                                             bias=False))

    def param_specs(self):
        d, f, n = self.d_model, self.d_expert, self.held
        w = initializers.random_normal(0.0, 0.02)
        return {"router": ParamSpec((d, self.num_experts), w),
                "router_bias": ParamSpec((self.num_experts,),
                                         initializers.zeros),
                "gate": ParamSpec((n, d, f), w), "up": ParamSpec((n, d, f), w),
                "down": ParamSpec((n, f, d), w)}

    def _apply(self, params, state, x, *, training=False, rng=None):
        return self.mixed(params, x)[0], state

    def mixed(self, params, x, valid=None):
        """-> (y, counts (2,) int32 = [pairs, experts with a pair])."""
        shape, k, n = x.shape, self.top_k, self.held
        x = x.reshape(-1, shape[-1])
        T = x.shape[0]
        chosen, w = route(x, params["router"], params["router_bias"], k,
                          self.scaling)
        local = chosen - self.first
        mine = (local >= 0) & (local < n)
        if valid is not None:
            mine &= valid.reshape(T, 1)
        # the pairs by expert, those of absent experts behind the last
        # group; a stable order keeps a token's pairs in token order
        group = jnp.where(mine, local, n).reshape(-1)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.sum(group[:, None] == jnp.arange(n), axis=0,
                        dtype=jnp.int32)
        y = grouped_ffn(x[order // k], params["gate"], params["up"],
                        params["down"], sizes)
        # back to (token, choice); a row no group owned counts for nothing
        y = y[jnp.argsort(order)].reshape(T, k, -1)
        y = jnp.einsum("tkd,tk->td", jnp.where(mine[..., None], y, 0), w,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        c = self.children()

        def run(name, a):
            return c[name].apply(params[name], {}, a)[0]
        shared = run("shared_down", jax.nn.silu(run("shared_gate", x))
                     * run("shared_up", x))
        return (y + shared).reshape(shape), jnp.stack(
            [jnp.sum(sizes), jnp.sum(sizes > 0, dtype=jnp.int32)])
