"""Sequence-parallel (long-context) Transformer LM — the zoo config that
trains with ring attention over a 'seq' mesh axis (parity-plus: SURVEY §5
marks long-context "Absent" in the reference; here it is first-class).

Every device holds 1/N of the sequence: tokens, activations, and the
attention working set are all sequence-sharded, with K/V blocks rotating
around the ring (parallel/ring.py) so full-sequence causal attention is
computed without any device ever materializing the global T. The whole
train step — embedding, blocks, tied head, loss, gradients — runs inside
one shard_map; parameters are replicated and their gradients psum over
the ring, so the update is identical to the single-device computation
(asserted exactly in tests/test_long_context.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.core.module import Module
from bigdl_tpu.nn.attention import TransformerLayer
from bigdl_tpu.nn.normalization import LayerNormalization
from bigdl_tpu.parallel.mesh import SEQ_AXIS
from bigdl_tpu.parallel.ring import RingAttention


# canonical home moved to nn.attention; re-exported for compatibility
from bigdl_tpu.nn.attention import positional_encoding_at  # noqa: E402,F401


class SeqParallelLM:
    """Decoder-only LM, sequence-parallel end to end.

        mesh = Mesh(devices, ('seq',))
        lm = SeqParallelLM(vocab, n_layers=4)
        st = lm.init(jax.random.PRNGKey(0))
        st, loss = lm.train_step(st, tokens_x, tokens_y, mesh, lr=1e-3)
        logits = lm.apply(st, tokens_x, mesh)     # (B, T, vocab)
    """

    def __init__(self, vocab_size: int, d_model: int = 128,
                 num_heads: int = 4, d_ff: Optional[int] = None,
                 num_layers: int = 4, seq_axis: str = SEQ_AXIS):
        self.vocab_size, self.d_model = vocab_size, d_model
        self.num_layers, self.seq_axis = num_layers, seq_axis
        d_ff = d_ff or 4 * d_model
        self.blocks = [TransformerLayer(
            d_model, num_heads, d_ff,
            attn_impl=RingAttention(axis_name=seq_axis))
            for _ in range(num_layers)]
        self.final_ln = LayerNormalization(d_model)
        self._compiled = {}

    # --------------------------------------------------------------- state
    def init(self, rng):
        params = {}
        k_emb, *keys = jax.random.split(rng, self.num_layers + 2)
        params["emb"] = (jax.random.normal(
            k_emb, (self.vocab_size, self.d_model))
            * self.d_model ** -0.5)
        for i, blk in enumerate(self.blocks):
            params[f"h{i}"], _ = blk.init(keys[i])
        params["ln"], _ = self.final_ln.init(keys[-1])
        return params

    # ------------------------------------------------------- local forward
    def _local_hidden(self, params, tokens_local):
        """Forward of one sequence shard (runs inside shard_map)."""
        t_local = tokens_local.shape[1]
        idx = jax.lax.axis_index(self.seq_axis)
        positions = idx * t_local + jnp.arange(t_local)
        x = params["emb"][tokens_local] * math.sqrt(self.d_model)
        x = x + positional_encoding_at(positions, self.d_model, x.dtype)
        for i, blk in enumerate(self.blocks):
            x, _ = blk.apply(params[f"h{i}"], {}, x, causal=True)
        x, _ = self.final_ln.apply(params["ln"], {}, x)
        return x

    # --------------------------------------------------------------- steps
    def _build(self, mesh: Mesh, what: str):
        from jax import shard_map
        from bigdl_tpu.parallel.mesh import DATA_AXIS
        n = mesh.shape[self.seq_axis]
        # compose with data parallelism when the mesh carries a 'data'
        # axis: batch over 'data', sequence over 'seq'
        batch_axis = DATA_AXIS if DATA_AXIS in mesh.axis_names else None
        tok_spec = P(batch_axis, self.seq_axis)

        if what == "apply":
            def fwd(params, xt):
                h = self._local_hidden(params, xt)
                return h @ params["emb"].T
            return jax.jit(shard_map(
                fwd, mesh=mesh, in_specs=(P(), tok_spec),
                out_specs=P(batch_axis, self.seq_axis, None),
                check_vma=False))

        axes = tuple(a for a in (batch_axis, self.seq_axis)
                     if a is not None)
        world = 1
        for a in axes:
            world *= mesh.shape[a]

        def step(params, xt, yt):
            def loss_fn(p):
                h = self._local_hidden(p, xt)
                logits = h @ p["emb"].T
                logp = jax.nn.log_softmax(logits, axis=-1)
                nll = -jnp.take_along_axis(logp, yt[..., None], axis=-1)
                # this shard's CONTRIBUTION to the global token mean —
                # differentiating a psum'd value instead would scale every
                # cotangent by N (psum's VJP is itself a psum)
                return jnp.sum(nll) / (nll.size * world)
            local_loss, grads = jax.value_and_grad(loss_fn)(params)
            loss = jax.lax.psum(local_loss, axes)
            # replicated params ← psum over every shard's gradient (the
            # dp all-reduce and the sp gradient reduction in one)
            grads = jax.tree.map(lambda g: jax.lax.psum(g, axes), grads)
            return loss, grads
        return jax.jit(shard_map(
            step, mesh=mesh, in_specs=(P(), tok_spec, tok_spec),
            out_specs=(P(), P()), check_vma=False))

    def _fn(self, mesh, what):
        key = (what, mesh)
        if key not in self._compiled:
            self._compiled[key] = self._build(mesh, what)
        return self._compiled[key]

    @staticmethod
    def _placed(arr, sh):
        """device_put host arrays; pass through already-global jax.Arrays
        (multi-host callers assemble them with
        jax.make_array_from_process_local_data — a device_put of those
        would try to materialize remote shards locally)."""
        if isinstance(arr, jax.Array) and arr.sharding == sh:
            return arr
        return jax.device_put(arr, sh)

    def loss_and_grads(self, params, x_tokens, y_tokens, mesh: Mesh):
        sh = NamedSharding(mesh, P(None, self.seq_axis))
        xt = self._placed(x_tokens, sh)
        yt = self._placed(y_tokens, sh)
        return self._fn(mesh, "step")(params, xt, yt)

    def train_step(self, params, x_tokens, y_tokens, mesh: Mesh,
                   lr: float = 1e-3, method=None, slots=None):
        """One step. Default plain SGD at `lr`; pass any
        `optim.OptimMethod` (Adam, OptaxMethod, ...) with `slots` from
        `optim.method.init_update_slots(method, params)` — the method's
        own learning_rate/schedule then drive the rate and the step
        counter advances inside the slots. Returns (params, loss) or
        (params, loss, slots)."""
        from bigdl_tpu.optim.method import apply_update
        loss, grads = self.loss_and_grads(params, x_tokens, y_tokens, mesh)
        new_p, new_slots = apply_update(method, params, grads, slots,
                                        sgd_lr=lr)
        if method is None:
            return new_p, float(loss)
        return new_p, float(loss), new_slots

    def apply(self, params, tokens, mesh: Mesh):
        sh = NamedSharding(mesh, P(None, self.seq_axis))
        return self._fn(mesh, "apply")(params, self._placed(tokens, sh))
