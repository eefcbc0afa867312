"""Long-context tour: the same causal LM trained three ways —
sequence-parallel ring attention over a 'seq' mesh (every device holds
T/N of the sequence), pipeline-parallel 1F1B with the cut-cross-entropy
fused head, and the flash-attention kernel as a drop-in MHA backend.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/long_context.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np                                            # noqa: E402
import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
from jax.sharding import Mesh                                 # noqa: E402


def data(vocab, T, B):
    toks = np.stack([(np.arange(T + 1) * 5 + i) % vocab for i in range(B)])
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def ring_leg():
    from bigdl_tpu.models.long_context_lm import SeqParallelLM
    n = min(4, len(jax.devices()))
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(n), ("seq",))
    vocab, T, B = 211, 64, 4
    lm = SeqParallelLM(vocab, d_model=32, num_heads=2, num_layers=2)
    params = lm.init(jax.random.PRNGKey(0))
    xt, yt = data(vocab, T, B)
    first = last = None
    for _ in range(60):
        params, loss = lm.train_step(params, xt, yt, mesh, lr=0.1)
        first = loss if first is None else first
        last = loss
    print(f"[ring x{n}] seq-parallel LM: loss {first:.3f} -> {last:.3f}")
    assert last < 0.65 * first


def pipeline_fused_leg():
    from bigdl_tpu.models.pipelined_lm import PipelinedLM
    n = min(2, len(jax.devices()))
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(n), ("pipe",))
    vocab, T, B = 211, 32, 8
    lm = PipelinedLM(vocab, d_model=32, num_heads=2, num_layers=2,
                     n_stages=n, n_microbatches=2 * n, fused_loss=True,
                     fused_interpret=True)
    st = lm.init(jax.random.PRNGKey(1), mesh)
    xt, yt = data(vocab, T, B)
    first = last = None
    for _ in range(40):
        st, loss = lm.train_step(st, xt, yt, mesh, lr=0.05)
        first = loss if first is None else first
        last = loss
    print(f"[1f1b x{n} + cut-xent] pipelined LM: loss {first:.3f} -> "
          f"{last:.3f} (logits never materialized on the last stage)")
    assert last < 0.85 * first


def flash_leg():
    from bigdl_tpu.kernels.flash_attention import PallasFlashAttention
    from bigdl_tpu.nn.attention import (MultiHeadAttention,
                                        dot_product_attention)
    mha = MultiHeadAttention(32, 2,
                             attn_impl=PallasFlashAttention(
                                 block_q=64, block_k=64, interpret=True))
    params, state = mha.init(jax.random.PRNGKey(2))
    x = jnp.asarray(np.random.RandomState(2).randn(2, 128, 32),
                    jnp.float32)
    out, _ = mha.apply(params, state, x, causal=True)
    dense = MultiHeadAttention(32, 2)
    ref, _ = dense.apply(params, state, x, causal=True)
    err = float(jnp.abs(out - ref).max())
    print(f"[flash] Pallas kernel as MHA backend: max |err| vs dense = "
          f"{err:.2e}")
    assert err < 1e-3


def main():
    ring_leg()
    pipeline_fused_leg()
    flash_leg()
    print("long-context tour complete (ring / 1F1B+cut-xent / flash)")


if __name__ == "__main__":
    main()
