"""Full-scale int8 accuracy evidence (reference claim:
whitepaper.md:192-196 "<0.1% accuracy drop on SSD/VGG16/VGG19"):
VGG-16 at width_mult=1.0 / spatial=224 and ResNet-50 at 224, random-init
+ calibrated — the measurement is about QUANTIZATION error (fp32-vs-int8
top-1 agreement and logit deltas), not task accuracy, so zero-egress
synthetic inputs are sufficient. Results feed the table in docs/int8.md
and the floors in tests/test_int8_accuracy.py.

    python tools/int8_fullscale.py [--n 32] [--calib 16] [--out JSON]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(model, params, state, x, calib_x, weight_block=64):
    """fp32 vs {dynamic, calibrated, calibrated+blocked} int8:
    top-1 agreement + max/mean relative logit delta. Forwards are jitted
    — eager VGG-16 at 224² is ~10× slower on host CPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.nn.quantized import calibrate, quantize

    fwd = jax.jit(lambda p, s, xx: model.apply(p, s, xx,
                                               training=False)[0])
    ref = np.asarray(fwd(params, state, jnp.asarray(x)))
    scale = np.abs(ref).max() + 1e-9
    rows = {}
    scales = calibrate(model, params, state, [calib_x])
    for mode, kw in (("dynamic", {}),
                     ("calibrated", {"input_scales": scales}),
                     ("blocked", {"input_scales": scales,
                                  "weight_block": weight_block})):
        qmod, qparams = quantize(model, params, **kw)
        qfwd = jax.jit(lambda p, s, xx, _q=qmod: _q.apply(
            p, s, xx, training=False)[0])
        got = np.asarray(qfwd(qparams, state, jnp.asarray(x)))
        delta = np.abs(got - ref) / scale
        rows[mode] = {
            "top1_agree": float((ref.argmax(-1) == got.argmax(-1)).mean()),
            "max_rel_logit_delta": float(delta.max()),
            "mean_rel_logit_delta": float(delta.mean()),
        }
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=32)
    ap.add_argument("--calib", type=int, default=16)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import time

    import jax
    import numpy as np

    from bigdl_tpu.models import resnet, vgg

    r = np.random.RandomState(0)
    report = {"n_eval": args.n, "n_calib": args.calib,
              "host_ncpu": os.cpu_count()}
    for name, build in (
            ("vgg16_w1.0_224", lambda: vgg.build(16, class_num=1000,
                                                 spatial=224,
                                                 width_mult=1.0)),
            ("resnet50_224", lambda: resnet.build(50, class_num=1000))):
        model = build()
        params, state = model.init(jax.random.PRNGKey(0))
        x = r.randn(args.n, 224, 224, 3).astype(np.float32)
        t0 = time.time()
        report[name] = measure(model, params, state, x, x[:args.calib])
        report[name]["measure_sec"] = round(time.time() - t0, 1)
        print(name, json.dumps(report[name]), flush=True)
    out = args.out or "/tmp/int8_fullscale.json"
    with open(out, "w") as fh:
        json.dump(report, fh, indent=1)
    print("wrote", out)


if __name__ == "__main__":
    main()
