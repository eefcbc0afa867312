"""CLI: manage the shape-keyed kernel autotune table.

    python -m bigdl_tpu.kernels tune [SET] [--force] [--dir DIR] [--json]
    python -m bigdl_tpu.kernels stats [DIR] [--json]
    python -m bigdl_tpu.kernels clear [DIR]

`tune` sweeps every (kernel, shape) of a named shape set (see
`autotune.SHAPE_SETS`; default "smoke" — CPU-interpreter-sized; "bench"
holds shapes of real-hardware size) and publishes the winners; `stats`
prints the committed table grouped by kernel plus staging dirs; `clear`
removes everything under the root. DIR defaults to
BIGDL_TPU_AUTOTUNE_CACHE (falling back to `<compile cache dir>/autotune`
when the persistent compile cache is on) — docs/kernels.md."""

from __future__ import annotations

import argparse
import json
import sys

from bigdl_tpu.kernels import autotune


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bigdl_tpu.kernels")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("tune", help="offline block-size sweep")
    p.add_argument("set", nargs="?", default="smoke",
                   choices=sorted(autotune.SHAPE_SETS),
                   help="named shape set to sweep (default: smoke)")
    p.add_argument("--force", action="store_true",
                   help="re-search keys the table already has")
    p.add_argument("--dir", default=None,
                   help="table root (default BIGDL_TPU_AUTOTUNE_CACHE)")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON object instead of the table")
    p = sub.add_parser("stats", help="inventory the table root")
    p.add_argument("dir", nargs="?", default=None)
    p.add_argument("--json", action="store_true")
    p = sub.add_parser("clear", help="remove every entry + staging dir")
    p.add_argument("dir", nargs="?", default=None)
    args = ap.parse_args(argv)

    if args.cmd == "clear":
        removed = autotune.clear(args.dir)
        print(f"cleared {removed} autotune entr"
              f"{'y' if removed == 1 else 'ies'}")
        return 0

    if args.cmd == "tune":
        if args.dir:
            autotune._attach(args.dir)
        recs = autotune.tune_set(args.set, force=args.force)
        autotune.sync()
        if args.json:
            print(json.dumps({"set": args.set, "records": recs}))
            return 0
        for rec in recs:
            print(f"{rec['key']}\n  -> {rec['config']} "
                  f"({rec['candidates_tried']} candidates, "
                  f"{rec['search_seconds']}s)")
        return 0

    s = autotune.stats(args.dir)
    if getattr(args, "json", False):
        print(json.dumps(s))
        return 0
    if not s["root"]:
        print("no autotune dir (set BIGDL_TPU_AUTOTUNE_CACHE / "
              "JAX_COMPILATION_CACHE_DIR or pass DIR)")
        return 1
    print(f"autotune root: {s['root']}")
    print(f"committed:     {s['entries']} entries")
    for kern, n in sorted(s["kernels"].items()):
        print(f"  {kern}: {n} shape{'s' if n != 1 else ''}")
    for dev, n in sorted(s["device_signatures"].items()):
        print(f"  device {dev}: {n}")
    for st in s["staging"]:
        state = "live" if st["alive"] else "dead"
        print(f"staging {st['dir']} ({state} pid {st['pid']}): "
              f"{st['pending']} unpublished")
    return 0


if __name__ == "__main__":
    import signal
    # die quietly when the consumer closes the pipe (stats | head)
    signal.signal(signal.SIGPIPE, signal.SIG_DFL)
    sys.exit(main())
