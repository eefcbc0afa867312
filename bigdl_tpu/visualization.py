"""TensorBoard-compatible training summaries
(reference: visualization/TrainSummary.scala:32, ValidationSummary.scala:29,
visualization/tensorboard/{EventWriter,RecordWriter}.scala,
src/main/java/netty/Crc32c.java).

Writes real TensorBoard event files with no TF dependency: the Event proto is
hand-encoded (wire format below), records are framed TFRecord-style with
masked CRC32C — byte-compatible with `tensorboard --logdir`.

Event proto (tensorflow/core/util/event.proto):
    double wall_time = 1; int64 step = 2; string file_version = 3;
    Summary summary = 5;
Summary.Value: tag = 1 (string), simple_value = 2 (float).
"""

from __future__ import annotations

import os
import queue
import struct
import threading
import time
from typing import Dict, List, Optional, Tuple

# CRC32C lives in utils/crc.py (shared with resilience/manifest.py, C
# -accelerated when the google_crc32c wheel is present — record framing
# used to run the per-byte pure-Python loop on every event). `crc32c` is
# re-exported here for the pre-existing import sites.
from bigdl_tpu.utils.crc import crc32c  # noqa: F401 — public re-export
from bigdl_tpu.utils.crc import masked_crc32c as _masked_crc


# -------------------------------------------------------- proto encoding
def _varint(n: int) -> bytes:
    out = b""
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _tag(field: int, wire: int) -> bytes:
    return _varint(field << 3 | wire)


def _pb_double(field: int, v: float) -> bytes:
    return _tag(field, 1) + struct.pack("<d", v)


def _pb_float(field: int, v: float) -> bytes:
    return _tag(field, 5) + struct.pack("<f", v)


def _pb_int64(field: int, v: int) -> bytes:
    return _tag(field, 0) + _varint(v & 0xFFFFFFFFFFFFFFFF)


def _pb_bytes(field: int, v: bytes) -> bytes:
    return _tag(field, 2) + _varint(len(v)) + v


def _pb_string(field: int, v: str) -> bytes:
    return _pb_bytes(field, v.encode())


def encode_scalar_event(tag: str, value: float, step: int,
                        wall_time: Optional[float] = None) -> bytes:
    sv = _pb_string(1, tag) + _pb_float(2, value)
    summary = _pb_bytes(1, sv)
    return (_pb_double(1, wall_time if wall_time is not None else time.time())
            + _pb_int64(2, step) + _pb_bytes(5, summary))


def _pb_packed_doubles(field: int, vals) -> bytes:
    payload = struct.pack(f"<{len(vals)}d", *vals)
    return _tag(field, 2) + _varint(len(payload)) + payload


def encode_histogram_stats_event(tag: str, stats: dict, step: int,
                                 wall_time: Optional[float] = None) -> bytes:
    """HistogramProto event from PRECOMPUTED stats — min/max/num/sum/
    sum_squares/bucket_limit/bucket (the same keys parse_histogram_event
    returns). Lets the flight recorder's log-bucket histograms
    (observe/metrics.py) export natively without retaining raw samples."""
    histo = (_pb_double(1, float(stats["min"]))
             + _pb_double(2, float(stats["max"]))
             + _pb_double(3, float(stats["num"]))
             + _pb_double(4, float(stats["sum"]))
             + _pb_double(5, float(stats["sum_squares"]))
             + _pb_packed_doubles(6, [float(e)
                                      for e in stats["bucket_limit"]])
             + _pb_packed_doubles(7, [float(c) for c in stats["bucket"]]))
    sv = _pb_string(1, tag) + _pb_bytes(5, histo)
    summary = _pb_bytes(1, sv)
    return (_pb_double(1, wall_time if wall_time is not None else time.time())
            + _pb_int64(2, step) + _pb_bytes(5, summary))


def encode_histogram_event(tag: str, values, step: int,
                           bins: int = 30,
                           wall_time: Optional[float] = None) -> bytes:
    """Per-parameter distribution summary (reference:
    optim/AbstractOptimizer.scala:47-91 writes `Parameters` histograms via
    visualization/Summary.scala histogram; proto: HistogramProto)."""
    import numpy as _np
    v = _np.asarray(values, _np.float64).reshape(-1)
    if v.size == 0:
        v = _np.zeros(1)
    counts, edges = _np.histogram(v, bins=bins)
    return encode_histogram_stats_event(
        tag,
        {"min": float(v.min()), "max": float(v.max()),
         "num": float(v.size), "sum": float(v.sum()),
         "sum_squares": float((v * v).sum()),
         "bucket_limit": [float(e) for e in edges[1:]],
         "bucket": [float(c) for c in counts]},
        step, wall_time=wall_time)


def encode_file_version_event() -> bytes:
    return _pb_double(1, time.time()) + _pb_string(3, "brain.Event:2")


def frame_record(data: bytes) -> bytes:
    """TFRecord framing (reference: RecordWriter.scala)."""
    header = struct.pack("<Q", len(data))
    return (header + struct.pack("<I", _masked_crc(header)) + data
            + struct.pack("<I", _masked_crc(data)))


def parse_records(blob: bytes) -> List[bytes]:
    """Inverse of frame_record, with CRC verification (reference:
    visualization/tensorboard/FileReader.scala)."""
    out, off = [], 0
    while off < len(blob):
        (length,) = struct.unpack_from("<Q", blob, off)
        (hcrc,) = struct.unpack_from("<I", blob, off + 8)
        if _masked_crc(blob[off:off + 8]) != hcrc:
            raise ValueError(f"corrupt record header at {off}")
        data = blob[off + 12:off + 12 + length]
        (dcrc,) = struct.unpack_from("<I", blob, off + 12 + length)
        if _masked_crc(data) != dcrc:
            raise ValueError(f"corrupt record body at {off}")
        out.append(data)
        off += 16 + length
    return out


def parse_histogram_event(data: bytes):
    """Decoder for histogram events: returns (tag, stats, step) where stats
    has min/max/num/sum/sum_squares/bucket_limit/bucket, or None."""
    from bigdl_tpu.interop.protowire import Msg
    ev = Msg(data)
    if not ev.has(5):
        return None
    step = ev.int(2, 0)
    val = ev.msg(5).msg(1)                  # Summary.value[0]
    if not val.has(5):
        return None                         # not a histogram event
    tag = val.str(1)
    h = val.msg(5)
    stats = {"min": h.doubles(1)[0], "max": h.doubles(2)[0],
             "num": h.doubles(3)[0], "sum": h.doubles(4)[0],
             "sum_squares": h.doubles(5)[0],
             "bucket_limit": h.doubles(6), "bucket": h.doubles(7)}
    return tag, stats, step


def parse_scalar_event(data: bytes) -> Optional[Tuple[str, float, int]]:
    """Minimal decoder for round-trip tests/readers: returns
    (tag, value, step) for scalar events, None otherwise."""
    off, step, tag, value = 0, 0, None, None
    while off < len(data):
        key = data[off]
        field, wire = key >> 3, key & 7
        off += 1
        if wire == 0:
            v = 0
            shift = 0
            while True:
                b = data[off]
                off += 1
                v |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            if field == 2:
                step = v
        elif wire == 1:
            off += 8
        elif wire == 5:
            off += 4
        elif wire == 2:
            ln = 0
            shift = 0
            while True:
                b = data[off]
                off += 1
                ln |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            sub = data[off:off + ln]
            off += ln
            if field == 5:          # Summary
                soff = 0
                while soff < len(sub):
                    skey = sub[soff]
                    soff += 1
                    sln = sub[soff]
                    soff += 1
                    val = sub[soff:soff + sln]
                    soff += sln
                    if skey >> 3 == 1:   # Value message
                        voff = 0
                        while voff < len(val):
                            vkey = val[voff]
                            vfield, vwire = vkey >> 3, vkey & 7
                            voff += 1
                            if vwire == 2:
                                vln = val[voff]
                                voff += 1
                                if vfield == 1:
                                    tag = val[voff:voff + vln].decode()
                                voff += vln
                            elif vwire == 5:
                                if vfield == 2:
                                    (value,) = struct.unpack_from(
                                        "<f", val, voff)
                                voff += 4
                            elif vwire == 1:
                                voff += 8
                            else:
                                return None
        else:
            return None
    if tag is None or value is None:
        return None
    return tag, value, step


class EventWriter:
    """Dedicated writer thread draining a queue to an event file
    (reference: visualization/tensorboard/EventWriter.scala:31-66)."""

    def __init__(self, log_dir: str, flush_secs: float = 5.0):
        os.makedirs(log_dir, exist_ok=True)
        self.path = os.path.join(
            log_dir, f"events.out.tfevents.{int(time.time())}.bigdl-tpu")
        self._q: "queue.Queue" = queue.Queue()
        self._stop = threading.Event()
        self.flush_secs = flush_secs
        self._fh = open(self.path, "ab")
        self._fh.write(frame_record(encode_file_version_event()))
        from bigdl_tpu.utils.threads import spawn
        self._thread = spawn(self._run, name="tb-event-writer")

    def add_scalar(self, tag: str, value: float, step: int):
        self._q.put(encode_scalar_event(tag, float(value), int(step)))

    def add_histogram(self, tag: str, values, step: int):
        self._q.put(encode_histogram_event(tag, values, int(step)))

    def add_event(self, event_bytes: bytes):
        """Queue an already-encoded Event proto (the flight recorder's
        histogram-stats events — observe/export.py)."""
        self._q.put(event_bytes)

    def flush(self):
        """Block until the queue is drained and bytes hit the file —
        readers must not race the writer thread."""
        import time as _time
        while not self._q.empty():
            _time.sleep(0.01)
        self._fh.flush()

    def _run(self):
        while not self._stop.is_set() or not self._q.empty():
            try:
                ev = self._q.get(timeout=self.flush_secs)
                if ev is not None:
                    self._fh.write(frame_record(ev))
            except queue.Empty:
                pass
            if self._q.empty():
                self._fh.flush()

    def close(self):
        self._stop.set()
        self._q.put(None)       # wake the writer: it may be `flush_secs`
        #                         into a wait on an empty queue
        self._thread.join(timeout=10)
        self._fh.flush()
        self._fh.close()


class _NullEventWriter:
    """Accepts the EventWriter API and writes nothing — what every
    process except 0 gets in a multihost job, so `dryrun_multichip` /
    multi-process training never interleaves duplicate event dirs
    (reference: the driver alone writes TrainSummary)."""

    path = None

    def add_scalar(self, tag, value, step):
        pass

    def add_histogram(self, tag, values, step):
        pass

    def add_event(self, event_bytes):
        pass

    def flush(self):
        pass

    def close(self):
        pass


class Summary:
    """Base summary bound to logdir/<app_name>/<tag> like the reference.

    Multihost: only process 0 opens an event file; the other processes
    get a null writer (their scalars are identical replicas — the
    reference's driver-writes-alone contract). `read_scalar` on a
    non-writing process returns what process 0 has flushed (shared
    filesystem) or []."""

    tag = "summary"

    def __init__(self, log_dir: str, app_name: str):
        from bigdl_tpu.utils.runtime import process_index
        self.log_dir = os.path.join(log_dir, app_name, self.tag)
        self._writer = (EventWriter(self.log_dir) if process_index() == 0
                        else _NullEventWriter())
        self._triggers = {}

    def set_summary_trigger(self, name: str, trigger) -> "Summary":
        """(reference: visualization/TrainSummary.scala:57
        setSummaryTrigger — e.g. ('Parameters', Trigger.several_iteration(n))
        turns on per-parameter histogram dumps in the optimizer)."""
        self._triggers[name] = trigger
        return self

    def get_summary_trigger(self, name: str):
        return self._triggers.get(name)

    def add_scalar(self, tag: str, value: float, step: int):
        self._writer.add_scalar(tag, value, step)
        return self

    def add_histogram(self, tag: str, values, step: int):
        self._writer.add_histogram(tag, values, step)
        return self

    def _read_events(self, parse_fn, tag: str):
        self._writer.flush()
        out = []
        if not os.path.isdir(self.log_dir):   # non-writing process, no dir
            return out
        for name in sorted(os.listdir(self.log_dir)):
            with open(os.path.join(self.log_dir, name), "rb") as fh:
                for rec in parse_records(fh.read()):
                    parsed = parse_fn(rec)
                    if parsed and parsed[0] == tag:
                        out.append((parsed[2], parsed[1]))
        return out

    def read_histogram(self, tag: str):
        """List of (step, stats) for a histogram tag."""
        return self._read_events(parse_histogram_event, tag)

    def read_scalar(self, tag: str) -> List[Tuple[int, float]]:
        """(reference: TrainSummary.readScalar via FileReader)."""
        return self._read_events(parse_scalar_event, tag)

    def close(self):
        self._writer.close()


class TrainSummary(Summary):
    """(reference: visualization/TrainSummary.scala:32 — Loss/Throughput/
    LearningRate written per iteration by the trainer)."""
    tag = "train"


class ValidationSummary(Summary):
    """(reference: visualization/ValidationSummary.scala:29)."""
    tag = "validation"
