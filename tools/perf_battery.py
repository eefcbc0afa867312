"""Assemble PERF_r{N}.json: the scaling + loader battery on the virtual
8-device CPU mesh (re-run when trainer/parallelism changes need refreshed
plumbing-overhead numbers).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/perf_battery.py --round 5

Measures SPMD plumbing overhead only on host CPU (the 8 'devices' share
one host's cores); the JSON says so. Host provenance (core count, load)
is recorded so cross-round deltas can be attributed (the r3→r4 bench
'regression' was a 1-core host, not code — ROUND5_NOTES.md)."""

import argparse
import json
import os
import sys

_FLAG = "--xla_force_host_platform_device_count=8"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--round", type=int, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from bench import _host_provenance
    from bigdl_tpu.models.perf import run_loader, run_scaling

    rec = {
        "round": args.round,
        "note": ("Virtual 8-device CPU mesh; scaling numbers measure SPMD "
                 "plumbing overhead only — the 8 'devices' share one "
                 "host's cores, so per-device FLOPs shrink with N and "
                 "efficiency is NOT an ICI statement. Loader number is a "
                 "real host-side measurement (224px JPEG decode+augment)."),
        "scaling": {},
    }
    for model, bpd in (("resnet20-cifar", 16), ("ptb-transformer", 4)):
        rec["scaling"][model] = run_scaling(
            model, batch_per_device=bpd, iters=3, warmup=1, dtype="bf16",
            class_num=10 if "cifar" in model else 1000)
        print(f"scaling[{model}] done", file=sys.stderr)
    rec["loader"] = run_loader(batch_size=32)
    rec["host"] = _host_provenance()
    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        f"PERF_r{args.round:02d}.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec)[:400])


if __name__ == "__main__":
    main()
