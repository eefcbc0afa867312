"""Compile a cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py train 16 8 4 2
    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py serve

Nothing runs: the TPU compiler installed beside JAX compiles for a device
that is described and not attached, and `memory_analysis()` says whether
the program fits the chip's 16 GB. `train` sizes the training batch of
`gpt2-medium-train` (the largest of the given batches whose
`bigdl_train_step` fits); `serve` compiles the paged decode step and the
largest prefill bucket of `gpt2-xl-serve`. The figures are copied into the
configuration files under `assumed` and into PERF.md by hand: a compile
that passes is not a chip run.
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

HBM = 16 * 2 ** 30


def _cfg(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _report(what, compiled, seconds):
    m = compiled.memory_analysis()
    need = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes - m.alias_size_in_bytes
    print(json.dumps({
        "program": what, "compile_s": round(seconds, 1),
        "argument_bytes": m.argument_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "alias_bytes": m.alias_size_in_bytes,
        "needs_bytes": need, "fits_16GiB": bool(need <= HBM)}), flush=True)
    return need <= HBM


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    from bigdl_tpu.interop.huggingface import GPT2LM
    if argv[0] == "train":
        import bigdl_tpu.nn as nn
        from bigdl_tpu.optim.local import Optimizer
        from bigdl_tpu.optim.method import Adam
        c = _cfg("gpt2-medium-train")
        model = GPT2LM(c["vocab_size"], c["n_positions"], c["n_embd"],
                       c["n_head"], c["n_layer"], eos_id=c["vocab_size"] - 1)
        t = c["trainer"]
        seq = t["sequence"]
        params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        method = Adam(t["learning_rate"])
        slots = jax.eval_shape(method.init_slots, params)
        for batch in [int(b) for b in argv[1:]] or [16, 8, 4, 2]:
            opt = Optimizer(model, [], nn.TimeDistributedMaskCriterion(
                nn.CrossEntropyCriterion(), padding_value=-1), method,
                seed=0, compute_dtype=jnp.bfloat16)
            step = jax.jit(opt._make_step(jnp.bfloat16),
                           donate_argnums=(0, 1, 2))
            t0 = time.perf_counter()
            try:
                compiled = step.lower(
                    on_chip(params), on_chip(state), on_chip(slots),
                    sds((batch, seq), np.int32), sds((batch, seq), np.int32),
                    sds((), jnp.float32), sds((), jnp.int32),
                    on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
                ).compile()
            except Exception as e:      # noqa: BLE001 — the compiler's refusal is the result
                print(json.dumps({"program": f"bigdl_train_step batch {batch}",
                                  "refused": str(e)[:400]}), flush=True)
                continue
            _report(f"bigdl_train_step batch {batch} x {seq}", compiled,
                    time.perf_counter() - t0)
    else:
        from bigdl_tpu.serve.decode import DecodeEntry
        c = _cfg("gpt2-xl-serve")
        model = GPT2LM(c["vocab_size"], c["n_positions"], c["n_embd"],
                       c["n_head"], c["n_layer"], eos_id=c["vocab_size"] - 1)
        params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        params = on_chip(params)
        real = jax.default_backend
        jax.default_backend = lambda: "tpu"     # the one question _build asks
        try:
            entry = DecodeEntry("m", model, params, **c["register"])
        finally:
            jax.default_backend = real
        S = entry.num_slots
        caches = on_chip(jax.eval_shape(
            lambda p: model.make_paged_slot_caches(
                p, entry.pool_blocks, entry.kv_block), params))
        vec = sds((S,), np.int32)
        table = sds((S, entry.blocks_per_slot), np.int32)
        t0 = time.perf_counter()
        compiled = entry._jit_decode.lower(
            params, caches, vec, vec, sds((S,), np.bool_), table).compile()
        _report("paged decode step", compiled, time.perf_counter() - t0)
        C = entry.buckets[-1]
        t0 = time.perf_counter()
        compiled = entry._jit_prefill.lower(
            params, caches, sds((S, C), np.int32), sds((S, C), np.int32),
            table, vec).compile()
        _report(f"paged prefill chunk {C}", compiled,
                time.perf_counter() - t0)


if __name__ == "__main__":
    main(sys.argv[1:] or ["train"])
