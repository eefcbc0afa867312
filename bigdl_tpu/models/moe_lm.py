"""Expert-parallel MoE Transformer LM — the zoo config that trains with
experts sharded over the 'expert' mesh axis (parity-plus: no MoE in the
reference; completes the zoo-level parallelism set alongside
DistriOptimizer dp/tp, PipelinedLM pp, and SeqParallelLM sp).

Batch is sharded over the same axis (each device routes its own token
shard — router FLOPs scale 1/N), expert FFN queues travel via
all_to_all, and the whole train step — embedding, attention blocks, MoE
FFNs, tied head, CE + load-balance + router-z losses, gradients — runs
inside one shard_map. Loss and gradients exactly match the unsharded
MoE computation (tests/test_moe_lm.py).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.nn.attention import (MultiHeadAttention,
                                    positional_encoding)
from bigdl_tpu.nn.normalization import LayerNormalization
from bigdl_tpu.parallel.mesh import EXPERT_AXIS
from bigdl_tpu.parallel.moe import MoE, expert_parallel_forward


class MoELM:
    """Decoder-only LM with Switch-style MoE FFNs, expert-parallel.

        mesh = Mesh(devices, ('expert',))
        lm = MoELM(vocab, n_experts=8)
        params = lm.init(jax.random.PRNGKey(0))
        params, loss, aux = lm.train_step(params, x_tok, y_tok, mesh)
    """

    def __init__(self, vocab_size: int, d_model: int = 128,
                 num_heads: int = 4, d_ff: Optional[int] = None,
                 num_layers: int = 2, n_experts: int = 8,
                 capacity_factor: float = 2.0, top_k: int = 1,
                 dropless: bool = False,
                 lb_coef: float = 1e-2, z_coef: float = 1e-3,
                 expert_axis: str = EXPERT_AXIS):
        self.vocab_size, self.d_model = vocab_size, d_model
        self.num_layers, self.expert_axis = num_layers, expert_axis
        self.lb_coef, self.z_coef = lb_coef, z_coef
        d_ff = d_ff or 4 * d_model
        self.attns = [MultiHeadAttention(d_model, num_heads)
                      for _ in range(num_layers)]
        self.ln1s = [LayerNormalization(d_model)
                     for _ in range(num_layers)]
        self.ln2s = [LayerNormalization(d_model)
                     for _ in range(num_layers)]
        self.moes = [MoE(d_model, d_ff, n_experts,
                         capacity_factor=capacity_factor, top_k=top_k,
                         dropless=dropless)
                     for _ in range(num_layers)]
        self.final_ln = LayerNormalization(d_model)
        self._compiled = {}

    def init(self, rng):
        params = {}
        keys = jax.random.split(rng, 4 * self.num_layers + 2)
        params["emb"] = (jax.random.normal(
            keys[0], (self.vocab_size, self.d_model))
            * self.d_model ** -0.5)
        for i in range(self.num_layers):
            params[f"ln1_{i}"], _ = self.ln1s[i].init(keys[4 * i + 1])
            params[f"attn{i}"], _ = self.attns[i].init(keys[4 * i + 2])
            params[f"ln2_{i}"], _ = self.ln2s[i].init(keys[4 * i + 3])
            params[f"moe{i}"], _ = self.moes[i].init(keys[4 * i + 4])
        params["ln"], _ = self.final_ln.init(keys[-1])
        return params

    # ---------------------------------------------------------- internals
    def _hidden(self, params, tokens, sharded: bool):
        """Blocks over one batch shard. `sharded=True` routes the MoE FFN
        through the expert-parallel all_to_all path (must be inside
        shard_map); False runs the plain MoE layer (dense reference)."""
        t = tokens.shape[1]
        x = params["emb"][tokens] * math.sqrt(self.d_model)
        x = x + positional_encoding(t, self.d_model, x.dtype)
        aux_sum = {"load_balance": 0.0, "z_loss": 0.0}
        for i in range(self.num_layers):
            h, _ = self.ln1s[i].apply(params[f"ln1_{i}"], {}, x)
            a, _ = self.attns[i].apply(params[f"attn{i}"], {}, h,
                                       causal=True)
            x = x + a
            h, _ = self.ln2s[i].apply(params[f"ln2_{i}"], {}, x)
            if sharded:
                y, aux = expert_parallel_forward(
                    self.moes[i], params[f"moe{i}"], h, self.expert_axis)
            else:
                y, st = self.moes[i].apply(params[f"moe{i}"], {}, h)
                aux = st["aux"]
            # MoE returns tokens+delta (residual included)
            x = x + (y - h)
            aux_sum = {k: aux_sum[k] + aux[k] for k in aux_sum}
        x, _ = self.final_ln.apply(params["ln"], {}, x)
        return x, aux_sum

    def _objective(self, params, xt, yt, sharded, world):
        h, aux = self._hidden(params, xt, sharded)
        logp = jax.nn.log_softmax(h @ params["emb"].T, axis=-1)
        nll = -jnp.take_along_axis(logp, yt[..., None], axis=-1)
        ce = jnp.sum(nll) / (nll.size * world)
        reg = (self.lb_coef * aux["load_balance"]
               + self.z_coef * aux["z_loss"]) / world
        return ce + reg, (ce, aux)

    # -------------------------------------------------------------- steps
    def _dp(self, mesh: Mesh):
        """Composed data axis: batch shards over (data, expert) together
        (dp×ep — each data group runs its own all_to_all expert exchange
        over 'expert'; experts replicate across 'data')."""
        from bigdl_tpu.parallel.mesh import composed_data_axis
        return composed_data_axis(mesh)

    def _batch_axes(self, mesh: Mesh):
        dp = self._dp(mesh)
        return (self.expert_axis,) if dp is None \
            else (dp, self.expert_axis)

    def _world(self, mesh: Mesh) -> int:
        world = 1
        for a in self._batch_axes(mesh):
            world *= mesh.shape[a]
        return world

    def _build_step(self, mesh: Mesh):
        from jax import shard_map
        ax = self.expert_axis
        dp = self._dp(mesh)
        baxes = self._batch_axes(mesh)
        world = self._world(mesh)
        batch_spec = P(baxes, None)

        specs = self._param_specs()

        def step(params, xt, yt):
            def loss_fn(p):
                # local contribution (see long_context_lm.py on why the
                # psum happens after differentiation)
                return self._objective(p, xt, yt, True, world)
            (local_loss, (ce, aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            loss = jax.lax.psum(local_loss, baxes)
            ce = jax.lax.psum(ce, baxes)
            # REPLICATED params' grads all-reduce over every batch axis;
            # expert-SHARDED leaves (w_up/w_down) all-reduce only over
            # 'data' (replicated there) — a psum over 'expert' would add
            # different experts' grads into each other's slots
            out = {}
            for k, g in grads.items():
                s = specs[k]
                if isinstance(s, dict):
                    out[k] = {}
                    for kk, gg in g.items():
                        if s[kk] == P():
                            out[k][kk] = jax.lax.psum(gg, baxes)
                        elif dp is not None:
                            out[k][kk] = jax.lax.psum(gg, dp)
                        else:
                            out[k][kk] = gg
                else:
                    out[k] = jax.tree.map(
                        lambda a: jax.lax.psum(a, baxes), g)
            return loss, ce, aux, out
        return jax.jit(shard_map(
            step, mesh=mesh,
            in_specs=(self._param_specs(), batch_spec, batch_spec),
            out_specs=(P(), P(), P(), self._param_specs()),
            check_vma=False))

    def _param_specs(self):
        ax = self.expert_axis
        specs = {"emb": P(), "ln": P()}
        for i in range(self.num_layers):
            specs[f"ln1_{i}"] = P()
            specs[f"attn{i}"] = P()
            specs[f"ln2_{i}"] = P()
            specs[f"moe{i}"] = {"gate": P(), "w_up": P(ax),
                                "w_down": P(ax)}
        return specs

    def _place(self, params, mesh):
        from bigdl_tpu.parallel.mesh import host_array_to_global
        specs = self._param_specs()
        out = {}
        for k, v in params.items():
            s = specs[k]
            if isinstance(s, dict):
                out[k] = {kk: host_array_to_global(vv, mesh, s[kk])
                          for kk, vv in v.items()}
            else:
                out[k] = jax.tree.map(
                    lambda a, sh=s: host_array_to_global(a, mesh, sh), v)
        return out

    def loss_and_grads(self, params, x_tokens, y_tokens, mesh: Mesh):
        from bigdl_tpu.parallel.mesh import host_array_to_global
        n = mesh.shape[self.expert_axis]
        world = self._world(mesh)
        if self.moes[0].n_experts % n:
            raise ValueError(f"expert-axis size {n} must divide expert "
                             f"count {self.moes[0].n_experts}")
        if x_tokens.shape[0] % world:
            raise ValueError(f"batch axes size {world} must divide batch "
                             f"{x_tokens.shape[0]}")
        key = mesh
        if key not in self._compiled:
            self._compiled[key] = self._build_step(mesh)
        params = self._place(params, mesh)
        spec = P(self._batch_axes(mesh), None)
        return self._compiled[key](
            params, host_array_to_global(x_tokens, mesh, spec),
            host_array_to_global(y_tokens, mesh, spec))

    def train_step(self, params, x_tokens, y_tokens, mesh: Mesh,
                   lr: float = 1e-3, method=None, slots=None):
        """One step. Default plain SGD at `lr`; pass any
        `optim.OptimMethod` with `slots` from
        `optim.method.init_update_slots(method, params)` (expert-sharded
        leaves' slots shard alongside them via sharding propagation; the
        method's own lr/schedule and step counter apply). Returns
        (params, ce, aux) or (params, ce, aux, slots)."""
        from bigdl_tpu.optim.method import apply_update
        loss, ce, aux, grads = self.loss_and_grads(params, x_tokens,
                                                   y_tokens, mesh)
        aux_f = {k: float(v) for k, v in aux.items()}
        new_p, new_slots = apply_update(method, params, grads, slots,
                                        sgd_lr=lr)
        if method is None:
            return new_p, float(ce), aux_f
        return new_p, float(ce), aux_f, new_slots

    def dense_objective(self, params, x_tokens, y_tokens):
        """Single-device reference (same math, no mesh) for tests."""
        return self._objective(params, x_tokens, y_tokens, False, 1)
