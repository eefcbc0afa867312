"""Compile-only tests for the chip: the TPU's compiler is installed here
and compiles for a v5e that is DESCRIBED, not attached
(`jax.experimental.topologies`). The kernels and the decode step of the
main path are lowered at the widths chip_smoke.py runs them at, so a
slice off the tiling, a kernel over its fast-memory budget or a program
over 16 GB of HBM fails here, on the CPU, before it costs chip time.
Nothing runs: a compile that passes is not a chip run.

The topology is described inside a fixture of THIS file (never at import,
in a skipif, a parametrize or conftest.py): only one process may hold the
TPU library, and every xdist worker imports every test file."""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 1024 ** 3        # one v5e chip

# GPT-2 XL (Radford et al. 2019): the width chip_smoke.py serves
XL = dict(vocab=50257, positions=1024, d=1600, heads=25, layers=48)
RESNET50_PARAMS = 25_557_032


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # read by the TPU library as it loads: no compiler logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # noqa: BLE001 — no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described chip, with the persistent compile
    cache off for the module: an entry compiled for a described device
    cannot be read back without one, and would only warn."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used < HBM_BYTES, m
    return used


_KEPT = {}


def _program(one_chip, entry, params, caches, which):
    """`entry`'s decode step ("step"), its full chunk's one-row prefill
    program ("row") or the step that carries that row ("carry"), compiled
    once for the module: several tests read each."""
    key = (entry.name, which)
    if key not in _KEPT:
        S, M = entry.num_slots, entry.blocks_per_slot
        vec = _sds(one_chip, (S,), np.int32)
        step = (vec, vec, _sds(one_chip, (S,), np.bool_),
                _sds(one_chip, (S, M), np.int32))
        chunk = _sds(one_chip, (1, entry.buckets[-1]), np.int32)
        row = _sds(one_chip, (1,), np.int32)
        one_row = (chunk, chunk, _sds(one_chip, (1, M), np.int32), row, row)
        jitted, args = {"step": (entry._jit_decode, step),
                        "row": (entry._jit_prefill_rows, one_row),
                        "carry": (entry._jit_carry, step + one_row)}[which]
        _KEPT[key] = jitted.lower(params, caches, *args).compile()
    return _KEPT[key]


def test_flash_attention_fwd_bwd_at_gpt2_xl_heads(one_chip):
    from bigdl_tpu.kernels.flash_attention import flash_attention
    qkv = _sds(one_chip, (4, XL["heads"], 1024, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True)
                  ).lower(qkv, qkv, qkv).compile()
    assert "tpu_custom_call" in fwd.as_text()       # the Pallas kernel
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2))
                  ).lower(qkv, qkv, qkv).compile()
    _fits(fwd)
    _fits(bwd)


def test_cut_cross_entropy_fwd_bwd_at_gpt2_vocab(one_chip):
    from bigdl_tpu.kernels.cut_cross_entropy import cut_cross_entropy
    h = _sds(one_chip, (4096, XL["d"]), jnp.bfloat16)
    w = _sds(one_chip, (XL["vocab"], XL["d"]), jnp.bfloat16)
    labels = _sds(one_chip, (4096,), jnp.int32)

    def loss(h, w, labels):
        return cut_cross_entropy(h, w, labels).mean()

    fwd = jax.jit(loss).lower(h, w, labels).compile()
    assert "tpu_custom_call" in fwd.as_text()
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1))
                  ).lower(h, w, labels).compile()
    assert "tpu_custom_call" in bwd.as_text()
    _fits(fwd)
    _fits(bwd)


def test_int8_matmul_at_gpt2_xl_ffn(one_chip):
    from bigdl_tpu.kernels.quantized_matmul import int8_matmul
    m, k, n = 8, XL["d"], 4 * XL["d"]
    c = jax.jit(int8_matmul).lower(
        _sds(one_chip, (m, k), jnp.int8), _sds(one_chip, (k, n), jnp.int8),
        _sds(one_chip, (m, 1), jnp.float32),
        _sds(one_chip, (1, n), jnp.float32)).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


def test_fused_update_at_resnet50_parameter_count(one_chip):
    """SGD+momentum, the trainer of chip_smoke.py, through the flat
    Pallas engine — the branch `layout='auto'` takes on a TPU and no CPU
    test reaches without interpret mode."""
    from bigdl_tpu.kernels import fused_update
    from bigdl_tpu.optim.method import SGD
    kind, hyper = fused_update.describe(SGD(0.1, momentum=0.9))
    vec = _sds(one_chip, (RESNET50_PARAMS,), jnp.float32)
    scalar = _sds(one_chip, (), jnp.float32)

    def update(p, g, velocity, lr):
        return fused_update.flat_update(kind, hyper, p, g, (velocity,),
                                        lr, jnp.int32(3), use_pallas=True)

    c = jax.jit(update, donate_argnums=(0, 2)).lower(
        vec, vec, vec, scalar).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


@pytest.fixture(scope="module")
def xl_entry(one_chip):
    """The DecodeEntry chip_smoke.py registers for GPT-2 XL, all 48
    layers, with the shapes of its arguments on the described chip - and
    with the cache donation that `_build` turns on only off the CPU."""
    import chip_smoke
    from bigdl_tpu.interop.huggingface import GPT2LM
    from bigdl_tpu.serve.decode import DecodeEntry
    model = GPT2LM(XL["vocab"], XL["positions"], XL["d"], XL["heads"],
                   XL["layers"], eos_id=XL["vocab"] - 1)
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), params)
    # steer the one platform question _build asks; nothing else is patched
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        entry = DecodeEntry("xl", model, params, **chip_smoke.SERVE_KV)
    caches = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda p: model.make_paged_slot_caches(
            p, entry.pool_blocks, entry.kv_block), params))
    return entry, params, caches


_MOVES = re.compile(r"= \w+\[([\d,]*)\]\S* (copy|gather|transpose)\(")


def _serves_from_the_pool(compiled, entry, caches):
    """What `paged_slot_cached_attend` promises of the compiled program:
    the pool is donated and updated in place, no pool is copied around the
    write of the new tokens, and attention reads the pool's own buffer
    (no per-slot copy of K or V, which was slots x blocks-a-slot x block
    token lanes where the pool holds pool_blocks x block). The one array
    larger than a layer's pool that either program still copies is the
    tied token table (PERF.md section 7: the model head's, not this
    layer's)."""
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= entry.kv_cache_bytes   # pool donated
    _fits(compiled)
    # temporaries were 4.67 GB (decode) and 2.33 GB (prefill 64) while the
    # pool was relaid and gathered in every layer; the table's copy is
    # 0.32 GB of what is left
    assert m.temp_size_in_bytes < entry.kv_cache_bytes, m
    pool = int(np.prod(caches[0].shape))
    table = XL["vocab"] * XL["d"]
    sizes = [(int(np.prod([int(d) for d in dims.split(",") if d])), op)
             for dims, op in _MOVES.findall(compiled.as_text())]
    assert len(sizes) > 50            # the pattern still reads this compiler
    assert [s for s in sizes if s[0] > pool and s[0] != table] == []
    # 0 pool-sized copies a layer: the write (a scatter of whole blocks
    # along the block dimension) and the read (two matmuls batched over the
    # leading head dimension) both take the pool row-major as it arrives
    assert [s for s in sizes if s == (pool, "copy")] == []


def test_gpt2_xl_paged_decode_step_fits_one_chip(one_chip, xl_entry):
    """The decode program DecodeEntry jits, at the slots and KV pool
    chip_smoke.py registers. The compiler counts arguments + temporaries
    against the chip's HBM and refuses what does not fit."""
    entry, params, caches = xl_entry
    _serves_from_the_pool(_program(one_chip, *xl_entry, "step"), entry,
                          caches)


def test_gpt2_xl_paged_prefill_chunk_fits_one_chip(one_chip, xl_entry):
    """The largest prefill bucket of the same entry: the program that
    writes the most blocks a call."""
    entry, params, caches = xl_entry
    S, C = entry.num_slots, entry.buckets[-1]
    chunk = _sds(one_chip, (S, C), np.int32)
    c = entry._jit_prefill.lower(
        params, caches, chunk, chunk,
        _sds(one_chip, (S, entry.blocks_per_slot), np.int32),
        _sds(one_chip, (S,), np.int32)).compile()
    _serves_from_the_pool(c, entry, caches)


def test_gpt2_xl_one_row_prefill_chunk_serves_from_the_pool(one_chip,
                                                            xl_entry):
    """The chunk-64 program over ONE row, the call a streaming slot costs:
    the pool as in the `num_slots`-row program (donated, no pool-sized
    copy), and temporaries below that program's 416,298,496 bytes (its
    scores are an eighth; the tied table's copy is most of what is left)."""
    entry, params, caches = xl_entry
    c = _program(one_chip, *xl_entry, "row")
    _serves_from_the_pool(c, entry, caches)
    assert c.memory_analysis().temp_size_in_bytes < 416_298_496


# Olmo-Hybrid-7B (allenai/Olmo-Hybrid-7B config.json): one period of its
# layer pattern at the published widths, as benchmark/configs/
# olmo-hybrid-7b-serve.json registers it (the cell holds four periods)
HYBRID = dict(vocab=100352, d=3840, heads=30, ff=11008, positions=1280,
              linear=dict(num_heads=30, key_dim=96, value_dim=192,
                          conv_kernel=4, allow_neg_eigval=True),
              register=dict(num_slots=8, max_seq_len=1280, kv_block=16,
                            kv_pool_blocks=640, prefill_chunk=64,
                            paged=True))


@pytest.fixture(scope="module")
def hybrid_entry(one_chip):
    from bigdl_tpu.interop.olmo_hybrid import FULL, LINEAR, OlmoHybridLM
    from bigdl_tpu.serve.decode import DecodeEntry
    model = OlmoHybridLM(
        HYBRID["vocab"], HYBRID["d"], HYBRID["heads"], HYBRID["ff"],
        [LINEAR] * 3 + [FULL], HYBRID["linear"], HYBRID["positions"],
        eos_id=HYBRID["vocab"] - 1, param_dtype=jnp.bfloat16)
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), params)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        entry = DecodeEntry("hybrid", model, params, **HYBRID["register"])
    assert entry.slot_state and not entry.prefix_cache
    caches = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                          jax.eval_shape(entry._raw_caches, params))
    return entry, params, caches


_TYPED_MOVES = re.compile(r"= (\w+)\[([\d,]*)\]\S* (copy|gather|transpose)\(")


def _keeps_both_kinds_of_state_in_place(compiled, entry, caches):
    """The cache pytree (three linear layers' state by slot, one full
    layer's block pool) is donated and rewritten where it lies: the program
    moves no array with the state's or the pool's dimensions, and no
    float32 array larger than one layer's state."""
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= entry.kv_cache_bytes
    _fits(compiled)
    state, pool = caches[0]["S"].shape, caches[3].shape
    assert state == (8, 30, 96, 192) and pool == (30, 640, 16, 256)
    moves = [(dtype, tuple(int(d) for d in dims.split(",") if d))
             for dtype, dims, _ in _TYPED_MOVES.findall(compiled.as_text())]
    assert len(moves) > 5             # the pattern still reads this compiler
    of_a_cache = [mv for mv in moves
                  if sorted(mv[1]) in (sorted(state), sorted(pool))]
    assert of_a_cache == []
    assert [mv for mv in moves if mv[0] == "f32"
            and int(np.prod(mv[1])) > int(np.prod(state))] == []
    return m


def test_olmo_hybrid_decode_step_keeps_its_state_in_place(one_chip,
                                                          hybrid_entry):
    """The decode program of one period at the published widths: the
    recurrence as written, on a state of 8 x 30 x 96 x 192 float32 a
    layer. Its temporaries stay under one layer's state: nothing of the
    size of a state or a pool is made beside them."""
    entry, params, caches = hybrid_entry
    m = _keeps_both_kinds_of_state_in_place(
        _program(one_chip, *hybrid_entry, "step"), entry, caches)
    assert m.temp_size_in_bytes < 4 * int(np.prod(caches[0]["S"].shape)), m


def test_olmo_hybrid_prefill_chunk_keeps_its_state_in_place(one_chip,
                                                            hybrid_entry):
    """The chunk-64 prefill of the same entry: the chunkwise form (one
    triangular solve a head) and the full layer's attention over the pool."""
    entry, params, caches = hybrid_entry
    S, C = entry.num_slots, entry.buckets[-1]
    chunk = _sds(one_chip, (S, C), np.int32)
    c = entry._jit_prefill.lower(
        params, caches, chunk, chunk,
        _sds(one_chip, (S, entry.blocks_per_slot), np.int32),
        _sds(one_chip, (S,), np.int32)).compile()
    m = _keeps_both_kinds_of_state_in_place(c, entry, caches)
    assert m.temp_size_in_bytes < entry.kv_cache_bytes, m


def test_olmo_hybrid_one_row_prefill_puts_its_row_back_in_place(
        one_chip, hybrid_entry):
    """The chunk-64 program over ONE row: the streaming slot's state is
    taken out of the eight, and the new row written back into the donated
    buffer (a dynamic-update-slice of each linear layer's `S` and `conv`,
    no copy of either, no scatter); the chunk form works on one slot's
    state, so its temporaries (17.8 MB) are a sixth of the 8-row program's."""
    entry, params, caches = hybrid_entry
    c = _program(one_chip, *hybrid_entry, "row")
    m = _keeps_both_kinds_of_state_in_place(c, entry, caches)
    text = c.as_text()
    put_back = re.findall(
        r"= (f32\[8,30,96,192\]|bf16\[8,3,11520\])\S* "
        r"(dynamic-update-slice|scatter)\(", text)
    assert sorted(op for _, op in put_back) == ["dynamic-update-slice"] * 6
    # the 8-row program of this period takes 113,926,144 bytes
    assert m.temp_size_in_bytes < 113_926_144 // 4, m



# ------------------------------------------- the step that carries a chunk
@pytest.mark.parametrize("which", ["xl_entry", "hybrid_entry"])
def test_the_carrying_step_reads_the_weights_once(one_chip, request, which):
    """The decode step that carries the full chunk of one streaming slot
    (`DecodeEntry._jit_carry`): by the compiler's own count of `bytes
    accessed` it reads the step's bytes and the chunk's own activations,
    scores and pool windows, not the step's plus the one-row call's: under
    the step's + 25 % of the call's (19.39 GB against 15.77 and 17.09 at
    GPT-2 XL's widths, + 21 %; for a period of Olmo-Hybrid against 18.76 and
    16.02). Two `paged_hidden` calls in one program read 27.33 GB at the
    first's widths, and two attends threaded through one pool 25.91, with
    two copies of the pool a layer. The caches stay where they lie, as in
    the two programs it stands for."""
    entry, params, caches = request.getfixturevalue(which)
    assert entry.carried == (64,)
    step, row, carry = (_program(one_chip, entry, params, caches, w)
                        for w in ("step", "row", "carry"))
    read = lambda c: c.cost_analysis()["bytes accessed"]       # noqa: E731
    assert read(carry) < read(step) + 0.25 * read(row), \
        [read(c) for c in (step, row, carry)]
    if which == "xl_entry":
        _serves_from_the_pool(carry, entry, caches)
    else:
        m = _keeps_both_kinds_of_state_in_place(carry, entry, caches)
        # the step's temporaries and the one-row chunk's, not a state more
        assert m.temp_size_in_bytes < 113_926_144, m


def test_glm_carrying_step_fits_beside_its_weights_and_pools(one_chip):
    """`glm-5.2-serve` as its cell registers it (benchmark/configs): 7.76 GB
    of weights and 4.98 GB of pools leave 3.2 GB, of which the cell's peak
    already stands at 80.5 %. The step that carries a question's chunk of
    256 has to fit its temporaries in what is left (0.28 GB read here; the
    step alone 0.26), with the pools donated and rewritten in place."""
    import importlib.util
    import json
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    cfg = json.load(open(os.path.join(bench, "configs",
                                      "glm-5.2-serve.json")))
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            "glm_family_for_compile",
            os.path.join(bench, "families", "glm_moe_dsa.py"))
        family = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(family)
        model, _ = family.build_model(cfg)
    finally:
        sys.path.remove(bench)
    from bigdl_tpu.serve.decode import DecodeEntry
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, jnp.dtype(cfg["weights_dtype"])),
        params)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        entry = DecodeEntry("glm", model, params, **cfg["register"])
    assert entry.carried == (64, 128, 256)
    caches = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                          jax.eval_shape(entry._raw_caches, params))
    m = _program(one_chip, entry, params, caches, "carry").memory_analysis()
    assert m.alias_size_in_bytes >= entry.kv_pool_bytes
    needs = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert needs < HBM_BYTES and m.temp_size_in_bytes < 2 ** 29, m


def test_paged_latent_kernel_at_glm47_flash_widths(one_chip):
    """GLM-4.7-Flash's decode-step attention (`glm-4.7-flash-serve`): 64
    slots of 2 query rows x 20 heads over rows of 640 bfloat16 values, 281
    blocks of 64 a slot, a pool of 3,712 blocks: the block-table walk
    compiles as the Pallas kernel, its two key tiles of 16 blocks within
    its fast memory, and stays the call `latent_attend_roofline_pct`'s
    match finds: named `paged_latent_attend`, its first output the
    unnormalised f32[64,40,512]."""
    import json
    from bigdl_tpu.kernels.paged_latent import paged_latent_attend, run_pages
    assert run_pages(64, 640, 2, 281) == 16
    f = jax.jit(lambda q, pool, n, table: paged_latent_attend(
        q, pool, n, table, scale=1 / 16, v_width=512))
    compiled = f.lower(_sds(one_chip, (64, 40, 640), jnp.bfloat16),
                       _sds(one_chip, (3712, 64, 640), jnp.bfloat16),
                       _sds(one_chip, (64,), np.int32),
                       _sds(one_chip, (64, 281), np.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%paged_latent_attend(\.\d+)? = \(f32\[64,40,512\]",
                     text)
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "layer_metrics",
                           "latent_attend_roofline_pct.json")) as fh:
        match = json.load(fh)["args"]["match"]
    assert len(re.findall(match, text)) == 1
    _fits(compiled)


def _pallas_blocks(jaxpr):
    """The block shapes of every Pallas call in `jaxpr` and the jaxprs
    nested in it (`platform_dependent`'s branches among them)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append([tuple(getattr(b, "block_size", None)
                                for b in bm.block_shape)
                          for bm in eqn.params["grid_mapping"].block_mappings])
        for v in eqn.params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(j, "jaxpr") and hasattr(j.jaxpr, "eqns"):
                    found += _pallas_blocks(j.jaxpr)
    return found


def test_expert_layer_at_glm47_flash_decode_widths(one_chip):
    """GLM-4.7-Flash's routed layer in its verifying step
    (`glm-4.7-flash-serve`): 64 slots x 2 rows, top-4 of 64 experts of
    2048 -> 1536, so the grouped products see (512, 2048) x (64, 2048,
    1536). They compile as three `expert_gmm` Pallas calls and no
    `ragged-dot` (whose row tile of 512 multiplies every pair by every
    expert), with the rule's decode tile of 128 rows and the depth in one
    block, and small temporaries."""
    from bigdl_tpu.kernels.grouped_matmul import tiling
    from bigdl_tpu.nn.experts import GatedExperts
    layer = GatedExperts(2048, 1536, 64, 4, scaling=1.8)
    params, _ = jax.eval_shape(layer.init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _sds(one_chip, a.shape, jnp.bfloat16),
                          params)
    x = _sds(one_chip, (128, 2048), jnp.bfloat16)
    f = jax.jit(lambda p, x: layer.mixed(p, x))
    compiled = f.lower(params, x).compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert len(re.findall(r"%expert_gmm(\.\d+)? = ", text)) == 3
    assert tiling(512, 2048, 1536, 64) == (128, 2048, 1536)
    blocks = _pallas_blocks(jax.make_jaxpr(f)(params, x).jaxpr)
    assert sorted(b[0] for b in blocks) == [(128, 1536), (128, 2048),
                                            (128, 2048)]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20
    _fits(compiled)
