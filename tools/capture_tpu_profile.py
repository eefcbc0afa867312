"""Capture an XLA profile + HLO cost breakdown of the ResNet-50 train
step on the chip. One process, which must be the only one holding the
chip; it refuses to run on any other backend:

    python tools/capture_tpu_profile.py [outdir]

Writes into outdir (default chiprun_out/tpu_profile/):
  * profile/       — jax.profiler trace (TensorBoard-loadable)
  * hlo_stats.json — model FLOPs/step, step timing at several batch
    sizes, and the implied MFU (updated incrementally, so a timeout
    keeps every completed measurement)
"""

import json
import os
import sys


def main():
    outdir = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/tpu_profile"
    os.makedirs(outdir, exist_ok=True)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print(json.dumps({"error": "no TPU backend; refusing to profile "
                                   "the CPU"}))
        return 1
    from bench import _bench_resnet50, _peak_flops

    kind = getattr(dev, "device_kind", "unknown")
    peak = _peak_flops(kind)
    report = {"device_kind": kind, "peak_bf16_flops": peak,
              "batches": {}}
    stats_path = os.path.join(outdir, "hlo_stats.json")

    def dump():
        # incremental: a timeout mid-run keeps completed measurements
        with open(stats_path, "w") as fh:
            json.dump(report, fh, indent=1)

    # batch-size sensitivity sweep (bf16) — the MFU tuning data. bs=128
    # runs inside the profiler trace so its compile+steps are captured
    # once instead of paying a second compile later.
    for bs in (64, 128, 256):
        try:
            if bs == 128:
                with jax.profiler.trace(os.path.join(outdir, "profile")):
                    ips, flops, sec, _runs = _bench_resnet50(
                        compute_dtype=jnp.bfloat16, batch_size=bs,
                        spatial=224, warmup=3, iters=10)
                report["profile_dir"] = os.path.join(outdir, "profile")
            else:
                ips, flops, sec, _runs = _bench_resnet50(
                    compute_dtype=jnp.bfloat16, batch_size=bs,
                    spatial=224, warmup=3, iters=10)
            rec = {"imgs_per_sec": round(ips, 1),
                   "model_flops_per_step": flops,
                   "sec_per_step": round(sec, 5)}
            rec["mfu_bf16"] = round(flops / sec / peak, 4)
            report["batches"][str(bs)] = rec
            print(f"bs={bs}: {ips:.1f} imgs/s, MFU {rec['mfu_bf16']}")
        except Exception as e:                      # OOM at big batches
            report["batches"][str(bs)] = {"error": str(e)[:300]}
        dump()

    print(json.dumps({"ok": True, "outdir": outdir}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
