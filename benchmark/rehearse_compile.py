"""Compile a cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py <config> [batch ...]

Nothing runs: the TPU compiler installed beside JAX compiles for a device
that is described and not attached, and `memory_analysis()` says whether
the program fits the chip's 16 GB. The argument is a configuration's name
(`benchmark/configs/<name>.json`); the model is built by the
configuration's family, as a cell builds it. For a configuration of the kind `train` the given batches (or 16, 8,
4, 2) are tried and `bigdl_train_step` is compiled at each; for one of the
kind `serve`, the paged decode step and the largest prefill bucket. The
figures are copied into the configuration files under `assumed` and into
PERF.md by hand: a compile that passes is not a chip run.
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

HBM = 16 * 2 ** 30


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

    import common as C
    import train_cell
    try:
        c = C.load_json(C.found([HERE], "configs", argv[0] + ".json",
                                "configuration"))
    except C.Refused as e:
        raise SystemExit(f"rehearse_compile: {e}")
    model, _ = C.family_of(c).build_model(c)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = on_chip(jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, jnp.dtype(c["weights_dtype"])), params))
    if c["kind"] == "train":
        t = c["trainer"]
        seq, compute = t["sequence"], jnp.dtype(t["compute_dtype"])
        trainer, method, criterion = train_cell.trainer_parts(t)
        slots = jax.eval_shape(method.init_slots, params)
        for batch in [int(b) for b in argv[1:]] or [16, 8, 4, 2]:
            opt = trainer(model, [], criterion, method, seed=0,
                          compute_dtype=compute)
            step = jax.jit(opt._make_step(compute),
                           donate_argnums=(0, 1, 2))
            t0 = time.perf_counter()
            try:
                compiled = step.lower(
                    params, on_chip(state), on_chip(slots),
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
        chunk = entry.buckets[-1]
        t0 = time.perf_counter()
        compiled = entry._jit_prefill.lower(
            params, caches, sds((S, chunk), np.int32),
            sds((S, chunk), np.int32), table, vec).compile()
        _report(f"paged prefill chunk {chunk}", compiled,
                time.perf_counter() - t0)


if __name__ == "__main__":
    if len(sys.argv) < 2:
        raise SystemExit(__doc__)
    main(sys.argv[1:])
