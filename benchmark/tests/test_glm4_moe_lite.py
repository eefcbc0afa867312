"""The GLM-4.7-Flash family and its cell: the `tiny` block through a whole
run and its `fp8` control not `correct`; the reference's forward pass and
its MTP module against the program's dense forms; the two layouts of the
weights equal leaf by leaf; the parameter arithmetic at the published
sizes; the FLOPs and the kernels' work from made-up counts; the new
metrics silent (None, never 0) without their counters or trace; the
traffic file as `trafficgen` reads it.

The cell's traffic cannot be rehearsed on the CPU (64 callers over shared
prompts of 8k-16k tokens), so the runs here take the cell as
`BENCHMARK.json` has it and its mix with shorter lengths and fewer callers,
as the configuration's `tiny.traffic_scale` says: prefix hits, prompt
chunks carried by steps, and steps that verify drafts are all still
there."""

import json

import numpy as np
import pytest

import common as C
import run
import trafficgen
from test_correct import drive

FAMILY = C.plug_in((C.HERE,), "families", "glm4_moe_lite", "family")
CELL = ("--workload", "glm47flash_agent", "--seconds", "2")


def config(tiny=False):
    cfg = C.load_json(C.HERE, "configs", "glm-4.7-flash-serve.json")
    return dict(cfg, **cfg["tiny"]) if tiny else cfg


@pytest.fixture
def scaled_traffic(monkeypatch, tmp_path):
    """`run.load_cell` with the cell's mix under `tiny.traffic_scale`'s
    entries, in a file of its own (the client process reads the file)."""
    load_cell = run.load_cell

    def scaled(workload, rehearse):
        cell = load_cell(workload, rehearse)
        mix = dict(cell["mix"], **cell["sizes"]["traffic_scale"]["mix"])
        path = tmp_path / "agent_scaled.json"
        path.write_text(json.dumps(mix))
        return dict(cell, mix=mix, mix_file=str(path))
    monkeypatch.setattr(run, "load_cell", scaled)


def test_tiny_run_is_correct(capsys, scaled_traffic):
    notes, result = drive(capsys, *CELL, "--seed", str(2 ** 31 + 41))
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["failed"] == 0 and notes["checked"]["tokens"] > 60
    assert set(result["metrics"]) == {"rehearsal.setup_s",
                                      "rehearsal.serve_tokens_per_s"}


def test_fp8_control_in_the_programs_place_is_not_correct(capsys,
                                                          scaled_traffic):
    notes, result = drive(capsys, *CELL, "--seed", str(2 ** 31 + 42),
                          "--control", "fp8")
    assert result["correct"] is False
    held, limit = result["checks"]["served_gap_sq_mean"]
    # tiny sizes: 3 seeds read 3e-5 to 3e-3 for the program and 0.027 to
    # 0.075 for the control, and the tiny limit lies between (0.01)
    assert held == notes["checked"]["control"]["gap_sq_mean"] > limit
    assert notes["checked"]["gap_sq_mean"] < limit


@pytest.fixture(scope="module")
def tiny32():
    import jax.numpy as jnp
    cfg = dict(config(tiny=True), weights_dtype="float32")
    model, _ = FAMILY.build_model(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"] - 1, (2, 80)), jnp.int32)
    return cfg, model, tokens


def test_reference_is_the_programs_plain_forward(tiny32):
    """The reference expands keys and values a head in blocks of queries,
    the program's `apply` too but over whole sequences: in float32 on one
    set of weights they are one function, past one block of queries."""
    import jax
    cfg, model, tokens = tiny32
    with jax.default_matmul_precision("highest"):
        ours, _ = model.apply(FAMILY.program_params(7, cfg), {}, tokens)
    ref = FAMILY.logits(FAMILY.stacked(7, cfg), cfg, tokens)
    # float32 sums in another order: 1e-5 of logits of spread ~1
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=2e-5)


def test_mtp_reference_is_the_programs_teacher_forced_draft(tiny32):
    import jax
    cfg, model, tokens = tiny32
    with jax.default_matmul_precision("highest"):
        ours = model.mtp_apply(FAMILY.program_params(7, cfg), tokens)
    ref = FAMILY.mtp_logits(FAMILY.stacked(7, cfg), cfg, tokens)
    assert ours.shape == ref.shape == (2, 79, cfg["vocab_size"])
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
    # the correction biases are the seed's to keep, not to choose
    other = FAMILY.program_params(seed + 1, cfg)
    np.testing.assert_array_equal(
        np.asarray(other["l1"]["experts"]["router_bias"]),
        np.asarray(program["l1"]["experts"]["router_bias"]))
    assert not np.array_equal(np.asarray(other["mtp_eh"]["weight"]),
                              np.asarray(program["mtp_eh"]["weight"]))


def test_parameter_arithmetic_at_the_published_sizes():
    """The issue's table: per part, in millions, and the cut's sum."""
    import jax
    cfg = config()
    p = FAMILY.parameters(cfg)
    M = lambda n: round(n / 1e6, 2)                            # noqa: E731
    assert FAMILY.kinds(cfg) == ("dense",) + ("sparse",) * 5
    assert (M(p["mla"]), M(p["shared_expert"]), M(p["router"]),
            M(p["expert"])) == (21.76, 9.44, 0.13, 9.44)
    assert round(p["routed_layer"] / 1e6, 1) == 635.3
    assert round(p["dense_layer"] / 1e6, 1) == 84.7
    assert round(p["mtp"] / 1e6, 1) == 643.7
    # both tables: 634.4 M published, an eighth of the rows held
    whole_tables = FAMILY.parameters(cfg, vocab=154880)
    assert round((whole_tables["embedding"] + whole_tables["head"]) / 1e6,
                 1) == 634.4
    assert round((p["embedding"] + p["head"]) / 1e6, 1) == 79.3
    assert round(whole_tables["total"] / 1e6) == 4539   # the issue's 9.08 GB
    assert round(p["total"] / 1e6) == 3984              # 7.97 GB bfloat16
    # the whole published model: "30B-A3B"
    whole = FAMILY.parameters(cfg, layers=47, vocab=154880)
    assert 29e9 < whole["total"] < 31e9
    # the pool: 7 latent rows of 576 values in rows of 640, bfloat16
    assert 7 * 640 * 2 == 8960
    tiny = config(tiny=True)
    model, _ = FAMILY.build_model(tiny)
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == \
        FAMILY.parameters(tiny)["total"]


COUNTED = {"prefill_tokens": 1000.0, "step_tokens": 3200.0,
           "attended_tokens": 4200.0 * 12000, "context_tokens": 4200.0 * 12000,
           "step_context_tokens": 3100.0 * 12000, "expert_pairs": 8400.0 * 5,
           "expert_loads": 900.0, "expert_tokens": 4200.0 * 5}


def test_flops_and_kernel_work_from_made_up_counts():
    cfg = config()
    p = FAMILY.parameters(cfg)
    outside = p["layers"] - 5 * 64 * p["expert"]
    per_row = 2.0 * 20 * (2 * 512 + 64) * 6
    assert FAMILY.counted_flops(cfg, COUNTED) == pytest.approx(
        (2.0 * outside + 2.0 * p["expert"] * 4 * 5) * 4200
        + 2.0 * p["head"] * 3200 + per_row * COUNTED["attended_tokens"])
    assert FAMILY.serve_token_flops(cfg, 11999, True) == pytest.approx(
        2.0 * outside + 2.0 * p["expert"] * 4 * 5 + 2.0 * p["head"]
        + per_row * 12000)
    work = FAMILY.kernel_work(cfg, "latent_attend", counted=COUNTED)
    rows = COUNTED["step_context_tokens"] * 7       # 6 layers and the MTP
    assert work == {"flops": 2.0 * 20 * 1088 * rows,
                    "bytes": 2.0 * 576 * rows}
    # bound by bandwidth: ~38 FLOPs a byte against the v5e's ~240
    assert work["flops"] / work["bytes"] < 40
    work = FAMILY.kernel_work(cfg, "expert_ffn", counted=COUNTED)
    assert work == {"flops": 2.0 * p["expert"] * 8400 * 5,
                    "bytes": 2.0 * p["expert"] * 900}
    assert FAMILY.kernel_work(cfg, "sparse_attend", counted=COUNTED) is None
    assert FAMILY.kernel_work(cfg, "latent_attend") is None


def _ctx(counters, trace=None):
    prefix = "serve/model/decode/"
    return {"before": {"counters": {}, "histograms": {}},
            "after": {"counters": {prefix + k: v
                                   for k, v in counters.items()},
                      "histograms": {}},
            "trace": trace, "window_s": 51.0, "model_name": "model",
            "peaks": C.load_json(C.HERE, "peaks", "tpu-v5e.json"),
            "cfg": config(), "family": FAMILY, "dirs": (C.HERE,), "chips": 1}


@pytest.mark.parametrize("metric", ["mtp_accepted_pct",
                                    "latent_attend_roofline_pct"])
def test_new_metrics_are_silent_without_counters_or_trace(metric):
    """What the parent commit gives them: None, never 0 and no error."""
    import readers
    assert readers.read_metric(metric, _ctx({})) is None
    old = {"prefill_tokens": 10.0, "tokens": 5.0}
    assert readers.read_metric(metric, _ctx(old)) is None


def test_mtp_accepted_pct_reads_the_draft_counters():
    import readers
    ctx = _ctx({"draft_rows": 400.0, "draft_accepted": 3.0})
    assert readers.read_metric("mtp_accepted_pct", ctx) == 0.75
    # a roofline share needs the device trace too
    counters = {"tokens": 3190.0, "rows_dropped": 10.0,
                **{k: v for k, v in COUNTED.items() if k != "step_tokens"}}
    assert readers.read_metric("latent_attend_roofline_pct",
                               _ctx(counters)) is None


def test_the_match_finds_the_kernel_and_no_other_op():
    """The roofline share's `match` against HLO text of the kind a trace
    names its ops with: the kernel's call (both attention rows of a slot's
    20 heads, main layers and MTP block alike) and nothing else."""
    import re
    pattern = re.compile(C.load_json(
        C.HERE, "layer_metrics",
        "latent_attend_roofline_pct.json")["args"]["match"])
    kernel = ("%branch_0_fun.1 = (f32[64,40,512]{2,1,0:T(8,128)}, "
              "f32[64,40,128]{2,1,0:T(8,128)S(1)}, f32[64,40,128]{2,1,0:"
              "T(8,128)S(1)}) custom-call(%l.1, %reshape.1, %q.1, %pool.1), "
              "custom_call_target=\"tpu_custom_call\"")
    assert pattern.search(kernel)
    for other in ("%fusion.3 = f32[64,40,512]{2,1,0} fusion(%a), kind=kLoop",
                  "%ragged-dot.1 = bf16[2048,1536]{1,0} ragged-dot(%x, %w)",
                  "%fusion.9 = (f32[64,40,512]{2,1,0}, f32[64,40]{1,0}) "
                  "fusion(%p), kind=kLoop"):
        assert not pattern.search(other)


def test_the_traffic_file_reads_through_trafficgen():
    mix = trafficgen.load_mix("agent")
    cfg = config()
    reqs = trafficgen.Requests(mix, 2 ** 31 + 3, cfg["vocab_size"],
                               cfg["vocab_size"] - 1,
                               cfg["register"]["max_seq_len"])
    assert len(reqs.prefixes) == 8
    assert all(8192 <= len(p) <= 16384 for p in reqs.prefixes)
    for i in range(40):
        r = reqs[i]
        assert 8192 + 32 <= len(r["prompt"]) <= 16384 + 512
        assert 64 <= r["max_new_tokens"] <= 1024
        # with the draft's row, every request fits a slot
        assert len(r["prompt"]) - 1 + r["max_new_tokens"] + 1 <= \
            cfg["register"]["max_seq_len"]
    assert mix["clients"] == cfg["register"]["num_slots"] == 64
