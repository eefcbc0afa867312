"""LLaMA-architecture tour: convert a `transformers` LlamaForCausalLM
(RMSNorm + rotary embeddings + grouped-query attention + SwiGLU) onto
this framework's primitives, verify logits parity against the torch
forward, beam-generate with and without the grouped-KV cache (identical
outputs, O(L) vs O(L^2) per step), and fine-tune through the imported
weights.

    JAX_PLATFORMS=cpu python examples/llama_generation.py

(Random-init weights — no network in this environment; with downloads,
`LlamaForCausalLM.from_pretrained(...)` drops in unchanged.)"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np                                            # noqa: E402
import torch                                                  # noqa: E402
import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
from transformers import LlamaConfig, LlamaForCausalLM        # noqa: E402

from bigdl_tpu.interop.huggingface import from_llama          # noqa: E402


def main():
    torch.manual_seed(0)
    cfg = LlamaConfig(vocab_size=160, hidden_size=64,
                      intermediate_size=96, num_hidden_layers=2,
                      num_attention_heads=8, num_key_value_heads=2,
                      max_position_embeddings=64,
                      attn_implementation="eager")
    hf = LlamaForCausalLM(cfg).eval()
    module, params, state = from_llama(hf)

    toks = np.random.RandomState(0).randint(0, 160, (2, 12))
    with torch.no_grad():
        want = hf(torch.from_numpy(toks)).logits.numpy()
    got, _ = module.apply(params, state, jnp.asarray(toks))
    err = float(np.abs(np.asarray(got) - want).max())
    print(f"[convert] LLaMA logits parity vs torch (GQA 8q/2kv): "
          f"max |err| = {err:.2e}")
    assert err < 1e-3

    prompt = jnp.asarray(
        np.random.RandomState(1).randint(1, 150, (2, 6)), jnp.int32)
    seq_a, _ = module.generate(params, state, prompt, 10, beam_size=2,
                               eos_id=159, kv_cache=False)
    seq_b, _ = module.generate(params, state, prompt, 10, beam_size=2,
                               eos_id=159, kv_cache=True)
    assert (np.asarray(seq_a) == np.asarray(seq_b)).all()
    print(f"[generate] beam-2, grouped-KV cache == recompute; "
          f"continuation: {np.asarray(seq_b)[0, 0, 6:].tolist()}")

    # fine-tune through RoPE/GQA/SwiGLU to memorize a toy sequence
    seq = jnp.asarray(
        np.random.RandomState(2).randint(0, 160, (1, 20)), jnp.int32)

    @jax.jit
    def loss_fn(p):
        logits, _ = module.apply(p, state, seq[:, :-1])
        lp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(lp, seq[:, 1:, None], -1).mean()

    l0 = float(loss_fn(params))
    grad = jax.jit(jax.grad(loss_fn))
    p = params
    for _ in range(120):
        p = jax.tree.map(lambda a, b: a - 0.3 * b, p, grad(p))
    l1 = float(loss_fn(p))
    print(f"[finetune] memorization loss {l0:.3f} -> {l1:.4f}")
    assert l1 < 0.1
    print("llama tour complete")


if __name__ == "__main__":
    main()
