"""The Olmo-Hybrid family and its cell: the `tiny` block through a whole
run and its `fp8` control not `correct`; the two layouts of the weights
equal leaf by leaf; the parameter and FLOP counts at the published sizes;
the linear-attention state's least work on made-up traced work."""

import numpy as np
import pytest

import common as C
from test_correct import drive

FAMILY = C.plug_in((C.HERE,), "families", "olmo_hybrid", "family")
CELL = ("--workload", "olmohybrid_ragchat", "--seconds", "2")


def config(tiny=False):
    cfg = C.load_json(C.HERE, "configs", "olmo-hybrid-7b-serve.json")
    return dict(cfg, **cfg["tiny"]) if tiny else cfg


def test_tiny_run_is_correct(capsys):
    notes, result = drive(capsys, *CELL, "--seed", str(2 ** 31 + 21))
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["failed"] == 0 and notes["checked"]["tokens"] > 200
    # the end-to-end metrics whose spread the cell's 94 requests a window
    # keep under half their bounds (PERF.md section 2); the rest are notes
    assert set(result["metrics"]) == {"rehearsal.setup_s",
                                      "rehearsal.itl_p95_ms"}
    assert notes["n_gaps"] > 200


def test_fp8_control_in_the_programs_place_is_not_correct(capsys):
    notes, result = drive(capsys, *CELL, "--seed", str(2 ** 31 + 22),
                          "--control", "fp8")
    assert result["correct"] is False
    held, limit = result["checks"]["served_gap_sq_mean"]
    assert held == notes["checked"]["control"]["gap_sq_mean"] > 4 * limit
    assert notes["checked"]["gap_sq_mean"] < limit / 2


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
    other = FAMILY.program_params(seed + 1, cfg)
    assert not np.array_equal(np.asarray(other["l0"]["mixer"]["conv"]),
                              np.asarray(program["l0"]["mixer"]["conv"]))


def test_parameter_counts_at_the_published_sizes():
    cfg = config()
    cut = FAMILY.parameters(cfg)
    assert FAMILY.kinds(cfg).count("linear_attention") == 12
    assert FAMILY.period(cfg) == 4
    assert round(cut["linear_layer"] / 1e6, 1) == 215.6
    assert round(cut["full_layer"] / 1e6, 1) == 185.8
    assert round(cut["total"] / 1e9, 2) == 4.10
    whole = FAMILY.parameters(cfg, tuple(cfg["layer_types"]))
    assert len(cfg["layer_types"]) == 32 == cfg["published"][
        "num_hidden_layers"]
    assert round(whole["total"] / 1e9, 2) == 7.43
    model, _ = FAMILY.build_model(config(tiny=True))
    import jax
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == \
        FAMILY.parameters(config(tiny=True))["total"]


def test_serve_token_flops_at_the_published_sizes():
    cfg = config()
    p = FAMILY.parameters(cfg)
    state = 30 * 96 * 192
    base = 2.0 * p["layers"] + 7.0 * state * 12
    assert FAMILY.serve_token_flops(cfg, 0, False) == base + 4.0 * 3840 * 4
    assert FAMILY.serve_token_flops(cfg, 499, True) == \
        base + 2.0 * 100352 * 3840 + 4.0 * 3840 * 500 * 4
    # 6.7 GFLOP of matrices a token, as PERF.md reckons
    assert 6.6e9 < FAMILY.serve_token_flops(cfg, 0, False) < 6.8e9


def test_kernel_work_on_made_up_traced_work():
    cfg = config()
    state = 30 * 96 * 192
    tokens = [(p, False) for p in range(128)] + [(200 + k, True)
                                                 for k in range(10)]
    work = FAMILY.kernel_work(cfg, "linear_state", tokens=tokens)
    assert work["flops"] == 7.0 * state * 12 * 138
    # 10 decode tokens and two chunks of 64, a read and a write each
    assert work["bytes"] == 2.0 * 4 * state * 12 * (10 + 128 / 64)
    assert FAMILY.kernel_work(cfg, "another_kernel", tokens=tokens) is None
    assert FAMILY.kernel_work(cfg, "linear_state", tokens=[]) == \
        {"flops": 0.0, "bytes": 0.0}
