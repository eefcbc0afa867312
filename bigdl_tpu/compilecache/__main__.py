"""CLI: inspect / clear the persistent compilation cache.

    python -m bigdl_tpu.compilecache stats [DIR]
    python -m bigdl_tpu.compilecache clear [DIR]

DIR defaults to JAX_COMPILATION_CACHE_DIR, else `<checkout>/.jax_cache`.
`stats` prints the entries grouped by program (cache keys embed the
jitted function name); `clear` removes every entry — the recovery move
when a jax/jaxlib upgrade leaves stale entries behind
(docs/compile_cache.md)."""

from __future__ import annotations

import argparse
import json
import sys

from bigdl_tpu.compilecache import cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bigdl_tpu.compilecache")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("stats", help="inventory the cache directory")
    p.add_argument("dir", nargs="?", default=None,
                   help="cache directory (default: cache_dir())")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON object instead of the table")
    p = sub.add_parser("clear", help="remove every entry")
    p.add_argument("dir", nargs="?", default=None)
    args = ap.parse_args(argv)

    if args.cmd == "clear":
        removed = cache.clear(args.dir)
        print(f"cleared {removed} cache entr"
              f"{'y' if removed == 1 else 'ies'}")
        return 0

    s = cache.stats(args.dir)
    if getattr(args, "json", False):
        print(json.dumps(s))
        return 0
    print(f"cache dir: {s['root']}")
    print(f"entries:   {s['entries']}, {s['bytes']} bytes")
    for prog, n in sorted(s["programs"].items()):
        print(f"  {prog}: {n} variant{'s' if n != 1 else ''}")
    return 0


if __name__ == "__main__":
    import signal
    # die quietly when the consumer closes the pipe (stats | head)
    signal.signal(signal.SIGPIPE, signal.SIG_DFL)
    sys.exit(main())
