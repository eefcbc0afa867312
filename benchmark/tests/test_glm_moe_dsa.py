"""The GLM-5 family and its cell: the `tiny` block through a whole run and
its `fp8` control not `correct`; the reference's forward pass against the
program's `apply`; the two layouts of the weights equal leaf by leaf; the
parameter arithmetic at the published sizes; the FLOPs and the kernels' work
from made-up counts; the new readers silent (None, never 0) without a trace
or counters.

The cell's traffic cannot be rehearsed on the CPU (32 documents of 12k-29k
tokens are 655,360 tokens of prefill), so the runs here take the cell as
`BENCHMARK.json` has it and its mix with shorter lengths and fewer callers,
as the configuration's `tiny.traffic_scale` says: prefix hits, a
context several times the tiny `index_topk`, prefill chunks and decode
steps are all still there."""

import json

import numpy as np
import pytest

import common as C
import run
from test_correct import drive

FAMILY = C.plug_in((C.HERE,), "families", "glm_moe_dsa", "family")
CELL = ("--workload", "glm52_docqa", "--seconds", "2")


def config(tiny=False):
    cfg = C.load_json(C.HERE, "configs", "glm-5.2-serve.json")
    return dict(cfg, **cfg["tiny"]) if tiny else cfg


@pytest.fixture
def scaled_traffic(monkeypatch, tmp_path):
    """`run.load_cell` with the cell's mix under `tiny.traffic_scale`'s
    entries, in a file of its own (the client process reads the file)."""
    load_cell = run.load_cell

    def scaled(workload, rehearse):
        cell = load_cell(workload, rehearse)
        mix = dict(cell["mix"], **cell["sizes"]["traffic_scale"]["mix"])
        path = tmp_path / "docqa_scaled.json"
        path.write_text(json.dumps(mix))
        return dict(cell, mix=mix, mix_file=str(path))
    monkeypatch.setattr(run, "load_cell", scaled)


def test_tiny_run_is_correct(capsys, scaled_traffic):
    notes, result = drive(capsys, *CELL, "--seed", str(2 ** 31 + 31))
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["failed"] == 0 and notes["checked"]["tokens"] > 60
    # the end-to-end metrics whose spread over seeds stays under half
    # their bounds here (PERF.md section 2); the rest are notes
    assert set(result["metrics"]) == {"rehearsal.setup_s",
                                      "rehearsal.serve_tokens_per_s"}
    assert notes["n_gaps"] > 200


def test_fp8_control_in_the_programs_place_is_not_correct(capsys,
                                                          scaled_traffic):
    notes, result = drive(capsys, *CELL, "--seed", str(2 ** 31 + 32),
                          "--control", "fp8")
    assert result["correct"] is False
    held, limit = result["checks"]["served_gap_sq_mean"]
    # tiny sizes: 5 seeds read 0.005-0.049 for the program and 0.27-0.69
    # for the control, and the tiny limit lies between (0.1)
    assert held == notes["checked"]["control"]["gap_sq_mean"] > 2 * limit
    assert notes["checked"]["gap_sq_mean"] < limit


def test_reference_is_the_programs_plain_forward():
    """The reference gathers the admitted rows and applies W_uk to the
    query; the program's `apply` expands keys and values a head and masks.
    In float32 on one set of weights they are one function, over a context
    several times `index_topk`, with a share of the experts."""
    import jax
    import jax.numpy as jnp
    cfg = dict(config(tiny=True), weights_dtype="float32")
    w = FAMILY.stacked(7, cfg)
    model, _ = FAMILY.build_model(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"] - 1, (2, 128)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ours, _ = model.apply(FAMILY.program_params(7, cfg), {}, tokens)
    ref = FAMILY.logits(w, cfg, tokens)
    assert cfg["index_topk"] * 4 <= tokens.shape[1]
    # float32 sums in another order: 1e-5 of logits of spread ~1
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5])
def test_both_layouts_hold_the_same_values(seed):
    import jax
    cfg = config(tiny=True)
    program = FAMILY.program_params(seed, cfg)
    tree = FAMILY.program_tree(FAMILY.stacked(seed, cfg))
    assert jax.tree.structure(tree) == jax.tree.structure(program)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(program)):
        assert str(a.dtype) == str(b.dtype) == cfg["weights_dtype"]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    model, _ = FAMILY.build_model(cfg)
    C.layout_matches(model, program)
    # another seed: other weights, the same correction biases (how busy
    # the held experts are is not the seed's to decide), which differ by
    # layer
    other = FAMILY.program_params(seed + 1, cfg)["l1"]["experts"]
    mine = program["l1"]["experts"]
    assert not np.array_equal(np.asarray(other["router"]),
                              np.asarray(mine["router"]))
    np.testing.assert_array_equal(np.asarray(other["router_bias"]),
                                  np.asarray(mine["router_bias"]))
    assert not np.array_equal(
        np.asarray(program["l2"]["experts"]["router_bias"]),
        np.asarray(mine["router_bias"]))


def test_parameter_arithmetic_at_the_published_sizes():
    cfg = config()
    p = FAMILY.parameters(cfg)
    M = lambda n: round(n / 1e6, 1)                            # noqa: E731
    assert FAMILY.kinds(cfg) == (
        ("full", "dense"), ("shared", "sparse"), ("shared", "sparse"),
        ("shared", "sparse"), ("full", "sparse"))
    assert (M(p["mla"]), M(p["indexer"]), M(p["shared_expert"]),
            M(p["router"]), M(p["expert"]), M(p["dense_mlp"])) == \
        (165.0, 9.4, 37.7, 1.6, 37.7, 226.5)
    norms = 2 * 6144
    assert M(norms + p["mla"] + p["indexer"] + p["dense_mlp"]) == 400.9
    shared = norms + p["mla"] + p["router"] + p["shared_expert"] \
        + 16 * p["expert"]
    assert M(shared) == 808.3 and M(shared + p["indexer"]) == 817.7
    assert M(p["embedding"] + p["head"]) == 237.9
    assert round(p["total"] / 1e9, 2) == 3.88
    # cache: 5 latent rows of 576 and 2 indexer keys of 128, bfloat16
    assert (5 * 576 + 2 * 128) * 2 == 6272
    whole = FAMILY.parameters(
        cfg, tuple(zip(cfg["indexer_types"], cfg["mlp_layer_types"])),
        experts=256, vocab=154880)
    assert 740e9 < whole["total"] < 750e9       # "~750B"
    import jax
    tiny = config(tiny=True)
    model, _ = FAMILY.build_model(tiny)
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == \
        FAMILY.parameters(tiny)["total"]


COUNTED = {"prefill_tokens": 1000.0, "step_tokens": 3200.0,
           "attended_tokens": 4200.0 * 2048, "context_tokens": 4200.0 * 20000,
           "step_context_tokens": 3200.0 * 20000, "expert_pairs": 8400.0,
           "expert_loads": 900.0, "expert_tokens": 16800.0}


def test_flops_and_kernel_work_from_made_up_counts():
    cfg = config()
    p = FAMILY.parameters(cfg, experts=0)
    per_row = 2.0 * 64 * (2 * 512 + 64) * 5
    per_key = 2.0 * 32 * 129 * 2
    assert FAMILY.counted_flops(cfg, COUNTED) == (
        2.0 * p["layers"] * 4200 + 2.0 * p["head"] * 3200
        + 2.0 * p["expert"] * 8400 + per_row * COUNTED["attended_tokens"]
        + per_key * COUNTED["context_tokens"])
    # a token at position 19,999 with logits, the experts at their
    # expectation of half a pair a sparse layer
    assert FAMILY.serve_token_flops(cfg, 19999, True) == (
        2.0 * p["layers"] + 2.0 * p["head"] + 2.0 * p["expert"] * 4 * 0.5
        + per_row * 2048 + per_key * 20000)
    work = FAMILY.kernel_work(cfg, "sparse_attend", counted=COUNTED)
    assert work["flops"] == per_row * COUNTED["attended_tokens"] \
        + per_key * COUNTED["context_tokens"]
    assert work["bytes"] == 2.0 * (576 * 5 * COUNTED["attended_tokens"]
                                   + 128 * 2 * COUNTED["step_context_tokens"])
    work = FAMILY.kernel_work(cfg, "expert_ffn", counted=COUNTED)
    assert work == {"flops": 2.0 * p["expert"] * 8400,
                    "bytes": 2.0 * p["expert"] * 900}
    assert FAMILY.kernel_work(cfg, "another_kernel", counted=COUNTED) is None
    assert FAMILY.kernel_work(cfg, "sparse_attend") is None


def _ctx(counters, trace=None):
    prefix = "serve/model/decode/"
    return {"before": {"counters": {}, "histograms": {}},
            "after": {"counters": {prefix + k: v
                                   for k, v in counters.items()},
                      "histograms": {}},
            "trace": trace, "window_s": 51.0, "model_name": "model",
            "peaks": C.load_json(C.HERE, "peaks", "tpu-v5e.json"),
            "cfg": config(), "family": FAMILY, "dirs": (C.HERE,), "chips": 1}


NEW_METRICS = ("serve_mfu_computed_pct", "sparse_attend_roofline_pct",
               "expert_ffn_roofline_pct", "sparse_attended_pct",
               "expert_pairs_per_token", "prefix_miss_per_100_hits")


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_are_silent_without_counters_or_trace(metric):
    """What the parent commit gives them: None, never 0 and no error."""
    import readers
    assert readers.read_metric(metric, _ctx({})) is None
    old = {"prefill_tokens": 10.0, "tokens": 5.0, "prefix_hits": 0.0,
           "prefix_misses": 3.0}
    assert readers.read_metric(metric, _ctx(old)) is None


def test_new_readers_read_the_counters():
    import readers
    counters = {"prefill_tokens": 1000.0, "tokens": 3190.0,
                "rows_dropped": 10.0, "prefix_hits": 32000.0,
                "prefix_misses": 320.0,
                **{k: v for k, v in COUNTED.items()
                   if k not in ("prefill_tokens", "step_tokens")}}
    ctx = _ctx(counters)
    assert readers.read_metric("sparse_attended_pct", ctx) == \
        pytest.approx(10.24)
    assert readers.read_metric("expert_pairs_per_token", ctx) == 0.5
    assert readers.read_metric("prefix_miss_per_100_hits", ctx) == 1.0
    share = readers.read_metric("serve_mfu_computed_pct", ctx)
    assert share == pytest.approx(
        100.0 * FAMILY.counted_flops(config(), COUNTED) / (51.0 * 197e12))
    # a roofline share needs the device trace too
    assert readers.read_metric("sparse_attend_roofline_pct", ctx) is None
