"""Input-pipeline CLI — `python -m bigdl_tpu.dataset {stat,throughput}`
(the compilecache/kernels CLI convention): debug feed problems without a
trainer.

  stat        — shard inventory: per-shard record counts, bytes, CRC
                frame validation, and the per-host assignment preview
                for a simulated host count.
  throughput  — host-pipeline-only probe: drive the SAME
                read-ahead/echo/stack stages the trainers consume
                (dataset/service.py InputService) with placement
                replaced by a no-op, and report the feed rate plus the
                pipeline-stage phase table. If the rec/s here is below
                what the device demands, the feed — not the chip — is
                the wall.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional


def _stat(args) -> int:
    from bigdl_tpu.dataset import service
    from bigdl_tpu.dataset.sharded import ShardedRecordDataset
    from bigdl_tpu.utils import recordio
    ds = ShardedRecordDataset(args.shards, batch_size=1, shuffle=False,
                              num_workers=1)
    rows = []
    total_records = 0
    total_bytes = 0
    bad = 0
    for path in ds.shards:
        size = os.path.getsize(path)
        row = {"shard": os.path.basename(path), "bytes": size}
        try:
            with open(path, "rb") as fh:
                payloads = recordio.parse_records(fh.read())
            row["records"] = len(payloads)
            row["crc"] = "ok"              # parse validates frame CRCs
            total_records += len(payloads)
        except ValueError as e:
            row["records"] = 0
            row["crc"] = f"CORRUPT: {e}"
            bad += 1
        total_bytes += size
        rows.append(row)
    hosts = None
    if args.hosts > 1:
        hosts = []
        for h in range(args.hosts):
            mine = service.host_shard_order(ds.shards, args.seed,
                                            args.epoch, h, args.hosts)
            hosts.append({"host": h, "shards": len(mine),
                          "records": sum(ds._shard_count(p)
                                         for p in mine)})
    if args.json:
        print(json.dumps({"shards": rows, "total_records": total_records,
                          "total_bytes": total_bytes, "corrupt": bad,
                          "hosts": hosts}))
    else:
        w = max(len(r["shard"]) for r in rows)
        print(f"{'shard':<{w}} {'records':>9} {'bytes':>12}  crc")
        for r in rows:
            print(f"{r['shard']:<{w}} {r['records']:>9} "
                  f"{r['bytes']:>12,}  {r['crc']}")
        print(f"{len(rows)} shards · {total_records} records · "
              f"{total_bytes:,} bytes · {bad} corrupt")
        if hosts:
            print(f"\nper-host assignment (seed={args.seed} "
                  f"epoch={args.epoch}, {args.hosts} hosts):")
            for h in hosts:
                print(f"  host {h['host']}: {h['shards']} shards, "
                      f"{h['records']} records")
    return 1 if bad else 0


def _throughput(args) -> int:
    import tempfile
    from bigdl_tpu import observe
    from bigdl_tpu.dataset import service
    from bigdl_tpu.dataset.sharded import (ShardedRecordDataset,
                                           generate_synthetic,
                                           imagenet_train_transform)
    from bigdl_tpu.observe.metrics import phase_table
    shards = args.shards
    if shards is None:
        tmp = tempfile.mkdtemp(prefix="bigdl_tpu_input_probe_")
        generate_synthetic(tmp, args.synthetic, num_shards=8,
                           height=args.size, width=args.size)
        shards = tmp
        print(f"(synthetic: {args.synthetic} {args.size}x{args.size} "
              f"records under {tmp})", file=sys.stderr)
    transform = imagenet_train_transform(args.crop) if args.crop else None
    ds = ShardedRecordDataset(shards, args.batch_size,
                              transform=transform, exact=args.exact,
                              num_workers=args.workers)
    svc = service.InputService(ds, workers=args.workers, echo=args.echo)
    observe.registry().reset()
    out = svc.throughput_probe(batches=args.batches,
                               seconds=args.seconds, k=args.k)
    stages = [r for r in phase_table(observe.registry().snapshot())
              if r["phase"].startswith("data/")]
    if args.json:
        print(json.dumps({**out, "stages": stages}))
    else:
        print(f"{out['records_per_sec']:.1f} records/sec "
              f"({out['batches_per_sec']:.2f} batches/sec) — "
              f"{out['records']} records in {out['seconds']}s, "
              f"{out['workers']} workers, echo x{out['echo']}, "
              f"k={args.k}")
        for r in stages:
            print(f"  stage {r['phase']:<18} {r['count']:>7}x "
                  f"avg {r['avg_ms']:.2f} ms  total {r['total_s']:.2f} s")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="bigdl_tpu.dataset",
        description="input-pipeline tools: shard inventory + host-"
                    "pipeline throughput probe (docs/data.md)")
    sub = ap.add_subparsers(dest="cmd", required=True)

    s = sub.add_parser("stat", help="shard inventory + CRC validation")
    s.add_argument("--shards", required=True,
                   help="shard glob or directory")
    s.add_argument("--hosts", type=int, default=1,
                   help="preview the per-host shard assignment for N "
                        "simulated hosts")
    s.add_argument("--seed", type=int, default=0)
    s.add_argument("--epoch", type=int, default=0)
    s.add_argument("--json", action="store_true")

    t = sub.add_parser("throughput",
                       help="host-pipeline-only feed-rate probe")
    t.add_argument("--shards", default=None,
                   help="shard glob or directory (default: generate "
                        "synthetic shards)")
    t.add_argument("--synthetic", type=int, default=2048,
                   help="synthetic record count when --shards is absent")
    t.add_argument("--size", type=int, default=64,
                   help="synthetic record height/width")
    t.add_argument("--batch-size", type=int, default=32)
    t.add_argument("--crop", type=int, default=0,
                   help="apply the imagenet train transform at this "
                        "crop size (0 = raw decode only)")
    t.add_argument("--workers", type=int, default=None)
    t.add_argument("--echo", type=int, default=None)
    t.add_argument("--k", type=int, default=1,
                   help="stack K batches per super-batch like the fused "
                        "dispatch path")
    t.add_argument("--exact", action="store_true",
                   help="use the deterministic (sample-exact-resume) "
                        "pipeline mode")
    t.add_argument("--batches", type=int, default=None,
                   help="stop after this many batches (default: one "
                        "epoch or --seconds)")
    t.add_argument("--seconds", type=float, default=None)
    t.add_argument("--json", action="store_true")

    args = ap.parse_args(argv)
    return _stat(args) if args.cmd == "stat" else _throughput(args)


if __name__ == "__main__":
    sys.exit(main())
