"""The benchmark: one cell, one seed, one window, one line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One process, the only one to touch JAX (the load generator of a serving
cell is a child that never imports it). The cell is looked up in
`BENCHMARK.json` at the root of the checkout; its configuration, traffic
mix, per-layer metrics and the chip's peaks are data files, and its model
family and the readers of its metrics are modules, all found by name in the
directories that `BENCHMARK.json` lists under `paths` (PERF.md, section 3,
says how to add one). Set-up makes the weights on the device from
the seed and warms exactly the cell's programs; the window measures; then
the timed path's output is compared with the plain reference. The last line
of standard output is the result. Without a TPU whose `device_kind` is in
`benchmark/peaks/`, the run fails and prints no result.

`--rehearse` runs the configuration's `tiny` block on whatever backend JAX
finds, to rehearse the control flow on the CPU: its line says `"rehearsal":
true` and `cpu`, and is never a measurement. `--control <mode>` puts the
reference, computed in a lower precision or with a fault planted, in the
program's place: one of the modes that the configuration states under
`controls` and that its family's reference (or, for a fault such as half of
each batch, its kind of cell) implements. The run's checks hold what the
control reads, so `correct` has to come out false; what the program itself
read is on the notes line. It is for setting limits and for showing that
they bite, and the driver never passes it.
"""

import time

T_START = time.monotonic()          # process start, as near as Python gets

import argparse                     # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)
# the compile cache at a fixed place inside the checkout, unless the
# environment names one: the path is part of every key
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def load_cell(workload, rehearse):
    import common as C
    import trafficgen
    bench = C.load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = C.load_json(ROOT, entry["file"])
    sizes = dict(cfg)
    if rehearse:
        sizes.update(cfg["tiny"])
    dirs = [os.path.join(ROOT, p) for p in bench["paths"]]
    mix_file = C.found(dirs, "traffic", cell["traffic"] + ".json", "traffic")
    cell = dict(cell, sizes=sizes, dirs=dirs, mix_file=mix_file,
                mix=trafficgen.read_mix(mix_file))

    def reported(metric):
        return workload in metric.get("workloads", [workload])
    cell["end_to_end"] = [m for m in bench["end_to_end"] if reported(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if reported(m)]
    return cell


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)

    import common as C
    try:
        return run_cell(args)
    except C.Refused as e:          # a name or a mode the benchmark lacks
        raise SystemExit(f"benchmark: {e}")


def run_cell(args):
    import common as C
    cell = load_cell(args.workload, args.rehearse)
    if args.control and args.control not in cell["sizes"]["controls"]:
        raise SystemExit(f"benchmark: {cell['config']} states the controls "
                         f"{cell['sizes']['controls']}, not {args.control!r}")

    import jax
    import bigdl_tpu        # noqa: F401 — fails here where the program is absent
    devices = jax.devices()
    dev = devices[0]
    if args.rehearse:
        peaks = {"device_kind": dev.device_kind}      # no peak, no share
    else:
        if dev.platform != "tpu":
            raise SystemExit(f"benchmark: no TPU — JAX found {len(devices)} "
                             f"x {dev.platform} ({dev.device_kind})")
        try:
            peaks = C.peaks_for(dev.device_kind, cell["dirs"])
        except C.NoChip as e:
            raise SystemExit(f"benchmark: {e}")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"benchmark: {args.workload} needs {cell['chips']} "
                         f"chips, JAX found {len(devices)}")

    env = {"seed": args.seed, "seconds": args.seconds,
           "trace": bool(args.trace), "t_start": T_START, "device": dev,
           "count": cell["chips"], "peaks": peaks, "control": args.control,
           "compiles": C.Compiles(), "rehearse": args.rehearse}
    kind = cell["sizes"]["kind"]
    if kind == "serve":
        import serve_cell as runner
    elif kind == "train":
        import train_cell as runner
    else:
        raise SystemExit(f"benchmark: configuration kind {kind!r}")
    return report(args, cell, runner.run(env, cell))


def report(args, cell, out):
    import readers
    wanted = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        if args.trace:
            value = readers.read_metric(m["name"], dict(
                out["ctx"], chips=cell["chips"]))
        else:
            value = out["end_to_end"].get(m["name"])
        if value is not None:
            # a number from a rehearsal never goes under a metric's name
            name = ("rehearsal." if args.rehearse else "") + m["name"]
            metrics[name] = {"value": value, "unit": m["unit"]}
    checks = out["checks"]
    result = {"correct": checks.correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": out["device"]}
    if args.rehearse:
        result["rehearsal"] = True
    trace = out["ctx"]["trace"]
    if args.trace and trace is not None:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["checks"] = {k: [r["value"], r["limit"]]
                        for k, r in checks.rows.items()}
    print(json.dumps({"notes": out["notes"],
                      "end_to_end": out["end_to_end"]}), flush=True)
    sys.stderr.flush()
    for name, r in checks.rows.items():
        print(f"check {name}: {r['value']} (limit {r['limit']}) "
              f"{'ok' if r['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
