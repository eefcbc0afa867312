"""The yardstick's arithmetic, checked on the CPU: percentiles and pooled
gaps, the busy union and idle share of a trace, per-module device time (on
hand-made events and on a recorded TPU trace), and the FLOP shape functions
against the parameter counts the program's own models have."""

import functools
import json
import os
import re

import numpy as np
import pytest

import common as C
import reduce as R
import readers

HERE = os.path.dirname(os.path.abspath(__file__))
GPT2 = C.plug_in((C.HERE,), "families", "gpt2", "family")


def test_percentile_is_linear_between_order_statistics():
    v = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert R.percentile(v, 0) == 10.0
    assert R.percentile(v, 100) == 50.0
    assert R.percentile(v, 50) == 30.0
    assert R.percentile(v, 95) == pytest.approx(48.0)
    assert R.percentile(v, 95) == pytest.approx(np.percentile(v, 95))
    assert R.percentile([], 95) is None
    assert R.percentile([7.0], 95) == 7.0


def test_pooled_gaps_pool_every_request_and_skip_single_tokens():
    gaps = R.pooled_gaps([[0.0, 0.1, 0.4], [5.0], [1.0, 1.5]])
    assert gaps == pytest.approx([0.1, 0.3, 0.5])


def _rec(sent, stamps, prompt_len=4, error=None, due=None):
    return {"sent": sent, "stamps": stamps, "tokens": [1] * len(stamps),
            "error": error, "prompt_len": prompt_len, "asked": len(stamps),
            "due": due, "index": 0}


def test_serve_metrics_count_the_window_and_nothing_else():
    t0, seconds = 100.0, 10.0
    records = [
        _rec(99.0, [99.5, 100.5]),              # sent before: one token in
        _rec(101.0, [101.2, 101.3, 101.5]),     # whole
        _rec(109.0, [109.9, 110.4]),            # ends after: one token in
        _rec(105.0, [], error="HTTP 500"),      # failed, counted as failed
        _rec(111.0, [111.5]),                   # sent after: not attempted
    ]
    m = R.serve_metrics(records, t0, seconds)
    assert m["attempted"] == 3 and m["failed"] == 1
    assert m["serve_tokens_per_s"] == pytest.approx(5 / 10.0)
    assert m["n_ttft"] == 2 and m["n_gaps"] == 3
    assert m["ttft_p95_ms"] == pytest.approx(
        np.percentile([200.0, 900.0], 95))
    assert m["ttft_mean_ms"] == pytest.approx(550.0)    # every request
    assert m["itl_p95_ms"] == pytest.approx(
        np.percentile([100.0, 200.0, 500.0], 95))


def test_open_loop_requests_are_timed_from_when_they_were_due():
    m = R.serve_metrics([_rec(101.0, [101.5], due=100.2)], 100.0, 10.0)
    assert m["ttft_p95_ms"] == pytest.approx(1300.0)
    assert m["ttft_mean_ms"] == pytest.approx(1300.0)
    assert m["generator_late_p95_ms"] == pytest.approx(800.0)


def test_busy_union_merges_overlaps_and_idle_gaps_name_what_ended_them():
    ev = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 30.0, 5.0),
          ("d", 31.0, 1.0), ("e", 50.0, 0.0)]
    assert R.busy_union_ns(ev) == pytest.approx(20.0)
    gaps = R.idle_gaps(ev)
    assert gaps[0] == ["before c", pytest.approx(15e-9)]
    assert gaps[1] == ["before e", pytest.approx(15e-9)]
    assert R.busy_union_ns([]) == 0.0


def test_reduce_trace_module_time_and_idle_reader():
    planes = {"/device:TPU:0": {
        R.OPS_LINE: [("fusion.1", 0.0, 4e6), ("fusion.2", 6e6, 2e6)],
        R.MODULES_LINE: [("jit_bigdl_train_step(1)", 0.0, 8e6),
                         ("jit_other(2)", 9e6, 1e6),
                         ("jit_bigdl_train_step(1)", 10e6, 10e6)]}}
    t = R.reduce_trace(planes)
    assert t["busy_s"] == pytest.approx(6e-3)
    assert t["window_s"] == pytest.approx(20e-3)
    assert R.module_ms(t, "bigdl_train_step") == pytest.approx(9.0)
    assert R.module_ms(t, "absent") is None
    assert readers.trace_idle({"trace": t}) == pytest.approx(70.0)
    assert readers.trace_idle({"trace": None}) is None
    assert R.reduce_trace({"/device:TPU:0": {R.OPS_LINE: []}}) is None
    # a device idle at either end of the traced span is idle: the window is
    # the span the host timed, unless the device's events reach further
    spanned = R.reduce_trace(planes, span_s=30e-3)
    assert spanned["window_s"] == pytest.approx(30e-3)
    assert readers.trace_idle({"trace": spanned}) == pytest.approx(80.0)
    assert R.reduce_trace(planes, span_s=5e-3)["window_s"] == \
        pytest.approx(20e-3)


def test_recorded_tpu_trace_reduces_to_its_known_numbers():
    """A trimmed `.xplane.pb` of the first traced run of `gpt2xl_chat` on the
    v5e (PR 23): the reduction reads it with nothing but JAX."""
    path = os.path.join(HERE, "recorded_v5e.xplane.pb")
    want = json.load(open(os.path.join(HERE, "recorded_v5e.expected.json")))
    t = R.reduce_trace(R.read_device_lines(path))
    assert len(t["chips"]) == want["chips"]
    assert len(t["chips"][0]["ops"]) == want["ops"]
    assert t["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert t["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0.0 < t["busy_s"] <= t["window_s"]
    assert t["device_ops"][0][0] == want["top_op"]
    assert [n for n, _, _ in t["chips"][0]["modules"]] == want["modules"]
    # two whole runs of the anonymous decode program, 38 ms each
    assert R.module_ms(t, "jit__lambda") == pytest.approx(37.89, abs=0.01)
    assert all(len(n) <= 130 for n, _ in t["device_ops"] + t["idle_gaps"])


def test_hist_readers_take_differences_over_the_window():
    before = {"histograms": {"serve/gpt2/decode/step_ms":
                             {"sum": 100.0, "count": 4},
                             "a": {"sum": 1.0, "count": 1},
                             "b": {"sum": 10.0, "count": 1}}}
    after = {"histograms": {"serve/gpt2/decode/step_ms":
                            {"sum": 400.0, "count": 10},
                            "a": {"sum": 3.0, "count": 5},
                            "b": {"sum": 50.0, "count": 9}}}
    ctx = {"before": before, "after": after, "model_name": "gpt2"}
    assert readers.hist_mean(
        ctx, "serve/{model}/decode/step_ms") == pytest.approx(50.0)
    assert readers.hist_mean(ctx, "never/recorded") is None
    assert readers.ratio_of_sums(ctx, "a", "b", 100.0) == pytest.approx(5.0)
    same = {"before": before, "after": before, "model_name": "gpt2"}
    assert readers.hist_mean(same, "a") is None      # nothing in the window


def test_client_stat_reads_the_clients_own_statistics_or_nothing():
    m = R.serve_metrics([_rec(101.0, [101.2, 101.3, 101.5])], 100.0, 10.0)
    assert readers.client_stat({"client": m}, "ttft_p50_ms") == \
        pytest.approx(200.0)
    assert set(m) >= {f"{k}_p{q}_ms" for k in ("ttft", "itl")
                      for q in R.CLIENT_PERCENTILES}
    assert readers.client_stat({}, "ttft_p50_ms") is None   # a training cell


def test_flops_share_is_needed_flops_over_window_times_peak():
    ctx = {"needed_flops": 197e12, "window_s": 10.0,
           "peaks": {"bf16_flops_per_s": 197e12}}
    assert readers.flops_share(ctx) == pytest.approx(10.0)
    assert readers.flops_share(dict(ctx, needed_flops=0.0)) is None
    assert readers.flops_share(dict(ctx, peaks={})) is None


@pytest.mark.parametrize("config,published", [
    ("gpt2-xl-serve", 1_557_611_200), ("gpt2-medium-train", 354_823_168)])
def test_parameter_counts_match_the_programs_model_and_the_paper(
        config, published):
    """The shape function, the program's `model.init` (by `eval_shape`, no
    memory) and GPT-2's published counts agree."""
    import jax
    from bigdl_tpu.interop.huggingface import GPT2LM
    cfg = json.load(open(os.path.join(
        os.path.dirname(HERE), "configs", config + ".json")))
    p = GPT2.parameters(cfg)
    assert p["total"] == published
    model = GPT2LM(cfg["vocab_size"], cfg["n_positions"], cfg["n_embd"],
                   cfg["n_head"], cfg["n_layer"], eos_id=0)
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(params)) == published


def test_token_flops_follow_the_shape_functions():
    cfg = {"n_embd": 8, "n_layer": 2, "vocab_size": 11, "n_positions": 16}
    p = GPT2.parameters(cfg)
    token_flops = functools.partial(GPT2.serve_token_flops, cfg)
    assert p["non_embedding"] == 2 * (12 * 64 + 13 * 8) + 16
    assert token_flops(0, False) == 2 * p["non_embedding"] + 4 * 8 * 1 * 2
    assert token_flops(9, True) == 2 * p["non_embedding"] \
        + 2 * 88 + 4 * 8 * 10 * 2
    assert GPT2.train_token_flops(cfg, 16) == 6 * p["non_embedding"] \
        + 6 * 88 + 6 * 2 * 8 * 16
    # a request of 3 prompt tokens and 2 outputs, all inside the window:
    # positions 0, 1 by prefill, 2 and 3 by decode steps with logits
    rec = _rec(1.0, [1.5, 1.6], prompt_len=3)
    want = sum(token_flops(i, False) for i in (0, 1)) \
        + sum(token_flops(i, True) for i in (2, 3))
    assert R.window_tokens([rec], 0.0, 10.0) == [
        (0, False), (1, False), (2, True), (3, True)]
    assert R.serve_window_flops(token_flops, [rec], 0.0, 10.0) == want
    # its second token outside the window: that token alone is left out
    assert R.serve_window_flops(token_flops, [rec], 0.0, 1.55) == \
        want - token_flops(3, True)


def test_counter_delta_reads_what_a_counter_gained_in_the_window():
    before = {"counters": {"hits": 10.0, "lookups": 40.0}}
    after = {"counters": {"hits": 25.0, "lookups": 100.0, "born_inside": 7.0,
                          "serve/m/evictions": 3.0}}
    ctx = {"before": before, "after": after, "window_s": 5.0,
           "model_name": "m"}
    assert readers.counter_delta(ctx, "hits") == pytest.approx(15.0)
    assert readers.counter_delta(ctx, "born_inside") == pytest.approx(7.0)
    assert readers.counter_delta(ctx, "serve/{model}/evictions") == 3.0
    assert readers.counter_delta(ctx, "hits", over="lookups",
                                 scale=100.0) == pytest.approx(25.0)
    assert readers.counter_delta(ctx, "hits", per_second=True) == \
        pytest.approx(3.0)
    assert readers.counter_delta(ctx, "never_counted") is None
    assert readers.counter_delta(ctx, "hits", over="never_counted") is None
    still = dict(ctx, after=dict(after, counters=dict(after["counters"],
                                                      lookups=40.0)))
    assert readers.counter_delta(still, "hits", over="lookups") is None


class _Family:
    """Reckons a stated amount of work for one kernel and none for others."""

    @staticmethod
    def kernel_work(cfg, kernel, tokens):
        if kernel != "table_copy":
            return None
        return {"flops": 2.0e9 * len(tokens), "bytes": cfg["bytes_a_token"]
                * len(tokens)}


def test_kernel_roofline_on_the_recorded_trace():
    """The `copy` ops of the recorded trace against a stated amount of work:
    the share is the least time the chip could take over their device time,
    bound here by bytes; events are matched on their name, the HLO text."""
    t = R.reduce_trace(R.read_device_lines(
        os.path.join(HERE, "recorded_v5e.xplane.pb")))
    copy_s = dict(t["device_ops"])["copy"]
    assert copy_s == pytest.approx(0.019975915, rel=1e-6)     # 402 events
    assert R.matching_op_seconds(t, re.compile(r"^%copy(\.\d+)? = ")) == \
        pytest.approx(copy_s, rel=1e-9)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"trace": t, "family": _Family, "peaks": peaks,
           "cfg": {"bytes_a_token": 1.0e9},
           "traced_work": {"tokens": [(5, True), (6, True)]}}
    args = {"kernel": "table_copy", "match": r"^%copy(\.\d+)? = "}
    least = max(4.0e9 / 197e12, 2.0e9 / 819e9)          # the bytes bound it
    assert readers.kernel_roofline(ctx, **args) == pytest.approx(
        100.0 * least / copy_s)
    assert 0.0 < readers.kernel_roofline(ctx, **args) < 100.0
    # nothing to read is None, never 0: no event matches, the family reckons
    # no such kernel or none at all, the run has no trace or no traced work
    assert readers.kernel_roofline(ctx, "table_copy", r"no_such_op") is None
    assert readers.kernel_roofline(ctx, "other", args["match"]) is None
    assert readers.kernel_roofline(dict(ctx, family=object()), **args) is None
    assert readers.kernel_roofline(dict(ctx, trace=None), **args) is None
    assert readers.kernel_roofline(dict(ctx, traced_work=None), **args) is None
    # a Pallas call's `name` is its instruction's name on the chip (PERF.md)
    planes = {"/device:TPU:0": {R.OPS_LINE: [
        ("%fusion.7 = f32[8,30,96,192]{3,2,1,0} fusion(...)", 0.0, 4e6),
        ("%probe_kernel.1 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} "
         "%fusion.7), custom_call_target=\"tpu_custom_call\"", 5e6, 6e6)]}}
    small = R.reduce_trace(planes)
    assert R.matching_op_seconds(
        small, re.compile(r"^%probe_kernel(\.\d+)? = ")) == pytest.approx(6e-3)
    assert R.matching_op_seconds(
        small, re.compile(r"\[\d+,30,96,192\]")) == pytest.approx(4e-3)


def test_an_unknown_reader_is_refused_by_name(tmp_path):
    (tmp_path / "layer_metrics").mkdir()
    (tmp_path / "layer_metrics" / "m.json").write_text(
        '{"reader": "no_such_reader", "args": {}}')
    (tmp_path / "layer_metrics" / "mine.json").write_text(
        '{"reader": "twice", "args": {"stat": "ttft_p50_ms"}}')
    ctx = {"dirs": [str(tmp_path)], "client": {"ttft_p50_ms": 21.0}}
    with pytest.raises(C.Refused, match=r"'no_such_reader'.*counter_delta"):
        readers.read_metric("m", ctx)
    with pytest.raises(C.Refused, match="'absent'"):
        readers.read_metric("absent", ctx)
    # a reader of a later PR's own is a file found by name
    (tmp_path / "layer_readers").mkdir()
    (tmp_path / "layer_readers" / "twice.py").write_text(
        "def read(ctx, stat):\n    return 2 * ctx['client'][stat]\n")
    assert readers.read_metric("mine", ctx) == 42.0
