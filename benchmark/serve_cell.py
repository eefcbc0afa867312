"""A serving cell: the model behind `ServeFront`, asked over real sockets by
the client process, for `--seconds`; then the served tokens against the
plain reference."""

import functools
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

import common as C
import reduce as R
import trafficgen

# one word for every family: the histograms are found through `{model}`
MODEL_NAME = "model"


def _reader_thread(stream, lines, event):
    for line in stream:
        lines.append(line.strip())
        event.set()
    event.set()


def _wait_line(lines, event, prefix, child, timeout):
    deadline = time.monotonic() + timeout
    seen = 0
    while time.monotonic() < deadline:
        for line in lines[seen:]:
            seen += 1
            if line.startswith(prefix):
                return line
            if line.startswith("FAILED"):
                raise RuntimeError(f"client: {line}")
        if child.poll() is not None and seen >= len(lines):
            raise RuntimeError(f"client ended with {child.returncode} "
                               f"before {prefix!r}: {lines[-3:]}")
        event.wait(0.05)
        event.clear()
    raise RuntimeError(f"client said no {prefix!r} within {timeout} s")


def sample_for_check(records, rng, n):
    """`n` finished requests drawn from the seed, the longest among them."""
    done = [r for r in records if not r["error"]]
    if not done:
        return []
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in pick]


def gap_numbers(gaps):
    """What is said of a set of per-token gaps: `gap_sq_mean` is compared,
    the rest is printed beside it."""
    gaps = gaps.astype(np.float64)
    return {"tokens": int(gaps.size),
            "gap_sq_mean": float(np.square(gaps).mean()),
            "gap_mean": float(gaps.mean()), "gap_max": float(gaps.max()),
            "off_argmax_share": float((gaps > 0).mean())}


def check_outputs(env, family, cfg, mix, requests, records, control=None):
    """The served tokens against the family's reference
    (`reference.served_gaps_of`):
    by how much a served token's logit lies below the reference's best, as
    the mean of its square over every served token of every request
    compared (the widest gap and the plain mean keep the program and its
    control 1.8 and 3.1 times apart, the mean square 7 times: PERF.md,
    section 2). With `control`, also what the lower precision reads at the
    same positions of the same requests, under `"control"`."""
    import reference
    sample = sample_for_check(
        records, np.random.default_rng([env["seed"], 9]),
        int(mix.get("check_requests", 12)))
    if not sample:
        return None
    longest = max(r["prompt_len"] + r["asked"] for r in sample)
    pad_to = min(family.sizes(cfg)["positions"], -(-longest // 64) * 64)
    w = family.stacked(env["seed"], cfg)
    served_gaps = reference.served_gaps_of(family, cfg, control)
    gaps, control_gaps, finite = [], [], True
    for r in sample:
        prompt = requests[r["index"]]["prompt"]
        g = served_gaps(w, prompt, r["tokens"], pad_to)
        finite &= g["finite"]
        gaps.append(g["gap"])
        if control:
            control_gaps.append(g["control_gap"])
    gaps = np.concatenate(gaps)
    out = dict(gap_numbers(gaps), requests=len(sample), finite=bool(finite))
    if control:
        control_gaps = np.concatenate(control_gaps)
        out["control"] = gap_numbers(control_gaps)
    keep = os.environ.get("BENCH_KEEP_GAPS")
    if keep:            # for setting limits: every gap, and the control's
        os.makedirs(keep, exist_ok=True)
        with open(os.path.join(keep, f"gaps.{env['seed']}.json"), "w") as f:
            json.dump({"gap": gaps.tolist(), "control_gap":
                       control_gaps.tolist() if control else None}, f)
    return out


def run(env, cell):
    import jax
    import jax.numpy as jnp
    from bigdl_tpu import observe
    from bigdl_tpu.serve.engine import ServeEngine
    from bigdl_tpu.serve.net import LocalBackend, ServeFront

    cfg, mix, seconds = cell["sizes"], cell["mix"], env["seconds"]
    dev = env["device"]
    family = C.family_of(cfg, cell["dirs"])
    control = C.control_of(family, env.get("control"))
    model, eos = family.build_model(cfg)
    vocab = family.sizes(cfg)["vocab"]
    reg = dict(cfg["register"])
    requests = trafficgen.Requests(mix, env["seed"], vocab, eos,
                                   reg["max_seq_len"])
    params = family.program_params(env["seed"], cfg,
                                   jnp.dtype(cfg["weights_dtype"]))
    C.layout_matches(model, params)
    _, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))  # no leaves
    compiles = env["compiles"]
    records_path = os.path.join(C.OUT, f"records.{cell['name']}.jsonl")
    os.makedirs(C.OUT, exist_ok=True)

    engine = ServeEngine()
    front = child = None
    lines, got = [], threading.Event()
    trace = None
    try:
        engine.register(MODEL_NAME, model, params, state, decode=True, **reg)
        front = ServeFront(LocalBackend(engine), port=0)
        child_env = {k: v for k, v in os.environ.items()
                     if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        child = subprocess.Popen(
            [sys.executable, os.path.join(C.HERE, "client.py"),
             "--host", front.host, "--port", str(front.port),
             "--model", MODEL_NAME, "--traffic", cell["mix_file"],
             "--seed", str(env["seed"]), "--seconds", str(seconds),
             "--vocab", str(vocab), "--eos", str(eos),
             "--max-seq-len", str(reg["max_seq_len"]),
             "--out", records_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=child_env, cwd=C.HERE)
        threading.Thread(target=_reader_thread, daemon=True,
                         args=(child.stdout, lines, got)).start()
        _wait_line(lines, got, "READY", child, 900.0)

        # ---- the window: nothing above is measured, everything below is
        before = observe.metrics.registry().snapshot()
        compiled0 = compiles.n
        setup_s = C.now() - env["t_start"]
        child.stdin.write("GO\n")
        child.stdin.flush()
        t_go = C.now()
        if env["trace"]:
            time.sleep(max(0.0, min(0.25 * seconds,
                                    seconds - C.TRACE_SECONDS - 1.0)))
            path = C.start_trace(cell["name"])
            time.sleep(min(C.TRACE_SECONDS, 0.5 * seconds))
            trace = C.stop_trace(path)
        time.sleep(max(0.0, t_go + seconds - C.now()))
        after = observe.metrics.registry().snapshot()
        compiled_in_window = compiles.n - compiled0
        done = _wait_line(lines, got, "DONE", child, seconds + 180.0)
        child.wait(30.0)
        t0, hung = float(done.split()[1]), int(done.split()[2])
        device = C.device_block(dev, env["count"], trace)
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait(30.0)
        if front is not None:
            front.close()
        engine.shutdown(drain=False, timeout=10.0)
    del engine, front, params, model
    C.free_device_memory()

    with open(records_path) as f:
        records = [json.loads(line) for line in f]
    keep = os.environ.get("BENCH_KEEP_RECORDS")
    if keep:            # every request's stamps, for a look at the tails
        os.makedirs(keep, exist_ok=True)
        os.replace(records_path,
                   os.path.join(keep, f"records.{env['seed']}.jsonl"))
    else:
        os.remove(records_path)
    m = R.serve_metrics(records, t0, seconds)
    in_window = [r for r in records if t0 <= r["sent"] < t0 + seconds]
    t_check = C.now()
    checked = check_outputs(env, family, cfg, mix, requests, in_window,
                            control=control)
    check_s = C.now() - t_check

    checks = C.Checks()
    limits = cfg["limits"]
    # under `--control` the lower precision stands in the program's place
    held = checked["control"] if checked and control else checked
    checks.at_most("served_gap_sq_mean",
                   held["gap_sq_mean"] if checked and checked["finite"]
                   else float("nan"), limits["served_gap_sq_mean"])
    checks.at_most("requests_unanswered", hung + m["failed"], 0)
    checks.at_most("compiles_in_window", compiled_in_window, 0)
    ctx = {"before": before, "after": after, "trace": trace, "client": m,
           "model_name": MODEL_NAME, "window_s": seconds, "peaks": env["peaks"],
           "cfg": cfg, "family": family, "dirs": cell["dirs"],
           "needed_flops": R.serve_window_flops(
               functools.partial(family.serve_token_flops, cfg), records, t0,
               seconds)}
    if trace:       # what the traced span processed (the client's stamps
        #             are on the same clock), for a kernel's roofline share
        ctx["traced_work"] = {"tokens": R.window_tokens(
            records, trace["span_at"], trace["span_s"])}
    # every statistic of the client's stamps: BENCHMARK.json names the ones
    # that are end-to-end metrics, the rest stay on the notes line
    end_to_end = dict({k: v for k, v in m.items()
                       if k.endswith(("_ms", "_per_s"))}, setup_s=setup_s)
    notes = {k: m[k] for k in ("n_ttft", "n_gaps")}
    notes["checked"] = checked
    notes["check_s"] = check_s
    return {"attempted": m["attempted"] + hung, "failed": m["failed"] + hung,
            "end_to_end": end_to_end, "ctx": ctx, "device": device,
            "checks": checks, "notes": notes}
