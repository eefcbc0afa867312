"""`layer_readers/idle_by_span.py` on a hand-made device trace and span
ring whose idle gaps and host spans are known: the shares it returns, and
the None it returns where its mapping fails or the ring is too short."""

import pytest

import common as C
import reduce as R
import readers

K = 1_000_000_000_000 - 2_000_000     # the capture began 2 ms before
SPAN_AT = 1000.0                      # start_trace returned (host, s)
MS = 1_000_000


def _trace():
    """Three runs of `jit__step` on the device (ns from the capture's
    start): [10, 20), [20.01, 30), [35, 45) ms; a merge in between; a
    traced span of 50 ms from 2 ms on."""
    mods = [("jit__step(7)", 10 * MS, 10 * MS),
            ("jit__step(7)", 20.01 * MS, 9.99 * MS),
            ("jit__lambda(3)", 30 * MS, 0),
            ("jit__step(7)", 35 * MS, 10 * MS)]
    ops = [("%fusion.1 = f32[8]", m[1], m[2]) for m in mods if m[2]]
    trace = R.reduce_trace({"/device:TPU:0": {"XLA Ops": ops,
                                              "XLA Modules": mods}}, 0.05)
    trace["span_at"], trace["span_s"] = SPAN_AT, 0.05
    return trace


def _ring(oldest=True, late_dispatch=False):
    """The host's spans (perf_counter ns = device ns + K): thread 1 drives
    the device, thread 2 runs a collection during the gap before the
    third run. A fetch carries its dispatch's args (the same dict)."""
    def at(a, b):
        return K + int(a * MS), int((b - a) * MS)

    def X(name, a, b, args=None, tid=1):
        return ("X", name, "serve", tid) + at(a, b) + (args,)
    d = [{"program": "jit__step"} for _ in range(3)]
    c = (36, 36.5) if late_dispatch else (34.5, 35)
    ev = [X("serve/decode/deliver", 0.5, 0.9)] if oldest else []
    ev += [X("serve/decode/admit", 1, 9.5),
           X("serve/decode/dispatch", 9.5, 10, d[0]),
           X("serve/decode/dispatch", 15, 15.5, d[1]),
           X("serve/decode/fetch", 15.6, 20.05, d[0]),
           X("serve/decode/fetch", 28, 30.05, d[1]),
           X("serve/decode/deliver", 30.05, 30.9),
           X("serve/decode/admit", 31, 34.5),
           X("serve/decode/dispatch", c[0], c[1], d[2]),
           X("serve/decode/iteration", 27.5, 35.5),
           ("X", "process/gc", "process", 2) + at(31, 33) + (
               {"generation": 2, "collected": 0},),
           X("serve/decode/fetch", 40, 45.05, d[2]),
           X("serve/decode/deliver", 45.05, 46),
           ("i", "serve/decode/retire", "serve", 1, K + 46 * MS, 0, None)]
    return ev


@pytest.fixture
def reader(monkeypatch):
    mod = C.plug_in((C.HERE,), "layer_readers", "idle_by_span", "reader")

    def use(events):
        monkeypatch.setattr(mod, "ring_events", lambda: events)
        return lambda name: readers.read_metric(name, {"trace": _trace()})
    return mod, use


def test_known_gaps_go_to_the_spans_the_host_was_in(reader):
    mod, use = reader
    read = use(_ring())
    found = mod.attribute({"trace": _trace()}, "serve/decode/dispatch",
                          "serve/decode/fetch")
    got = {k: v / MS for k, v in found["by_span"].items()}
    # head 2-10 ms: admit, then the first dispatch; the 10 us between the
    # first two runs: the host waited in the fetch; 30-35 ms: the fetch's
    # last 50 us, deliver, the iteration alone, the collection (over
    # admit), admit, the third dispatch; tail 45-52 ms: the fetch's last
    # 50 us, deliver, then no span
    assert got == pytest.approx({
        "serve/decode/admit": 7.5 + 1.5, "serve/decode/dispatch": 1.0,
        "serve/decode/fetch": 0.01 + 0.05 + 0.05,
        "serve/decode/deliver": 0.85 + 0.95,
        "serve/decode/iteration": 0.1, "process/gc": 2.0, "none": 6.0},
        abs=1e-5)                       # the ring's whole nanoseconds
    chip = found["chips"][0]
    assert chip["pairs"] == 3
    # the anchor puts the first event (10 ms) at the span's start (2 ms)
    assert chip["first_event_ms"] == pytest.approx(10.0)
    assert chip["anchor_ms"] == pytest.approx(8.0)
    assert found["idle_ns"] == pytest.approx(20.01 * MS)
    assert found["window_ns"] == pytest.approx(50 * MS)
    # the third run waited for its dispatch and began as it ended
    assert chip["late_after_gap_us"]["n"] == 1
    assert chip["late_after_gap_us"]["median_us"] == pytest.approx(0.0)

    host = read("device_idle_host_pct.serve")
    fetch = read("device_idle_fetch_pct.serve")
    assert host == pytest.approx(100 * (9.0 + 1.0 + 1.8 + 2.0) / 50,
                                 abs=1e-4)
    assert fetch == pytest.approx(100 * 0.11 / 50, abs=1e-4)
    idle = readers.trace_idle({"trace": _trace()})
    assert idle == pytest.approx(100 * 20.01 / 50)
    assert host + fetch <= idle


def test_none_where_a_module_starts_before_its_dispatch(reader):
    _, use = reader
    read = use(_ring(late_dispatch=True))
    assert read("device_idle_host_pct.serve") is None
    assert read("device_idle_fetch_pct.serve") is None


def test_none_where_the_ring_starts_after_the_traced_span(reader):
    _, use = reader
    assert use(_ring(oldest=False))("device_idle_host_pct.serve") is None


def test_none_where_the_program_names_no_dispatched_program(reader):
    """A program older than these spans: the ring is empty, or its
    dispatches carry no `program`."""
    _, use = reader
    assert use([])("device_idle_host_pct.serve") is None
    bare = [e[:6] + (None,) if e[1] == "serve/decode/dispatch" else e
            for e in _ring()]
    assert use(bare)("device_idle_host_pct.serve") is None


def _steps(span_at, chunks):
    """Three runs of one program, 10 ms apart from 10 ms on, and four
    `train/dispatch` spans 0.4 ms long ending 0.5 ms before each run (the
    fourth's run is past the trace); a dispatch with a `chunk` enqueued
    the second executable, `jit__verify(2)`."""
    def host(ms):
        return K + int(ms * MS)
    mods = [("jit__verify(2)" if chunks[i] else "jit__verify(1)",
             (10 + 10 * i) * MS, 9 * MS) for i in range(3)]
    ops = [("%fusion.1 = f32[8]", m[1], m[2]) for m in mods]
    trace = R.reduce_trace({"/device:TPU:0": {"XLA Ops": ops,
                                              "XLA Modules": mods}}, 0.04)
    trace["span_at"], trace["span_s"] = span_at, 0.04
    ring = [("X", "train/data_wait", "train", 1, host(-5), MS, None)]
    for i, c in enumerate(chunks):
        args = {"program": "jit__verify"}
        if c:
            args["chunk"] = c
        ring.append(("X", "train/dispatch", "train", 1,
                     host(9.1 + 10 * i), int(0.4 * MS), args))
    return {"trace": trace}, ring


@pytest.mark.parametrize("span_at,chunks,mapped", [
    (1000.0, [0, 0, 0, 0], True),         # K 8 ms off the anchor, next 18
    (1000.0125, [0, 0, 0, 0], False),     # two pairings 5 ms off it
    (1000.0125, [0, 64, 0, 0], True),     # one pairing keeps the kinds
])
def test_none_where_two_pairings_fit_about_as_well(reader, monkeypatch,
                                                   span_at, chunks, mapped):
    mod, _ = reader
    ctx, ring = _steps(span_at, chunks)
    monkeypatch.setattr(mod, "ring_events", lambda: ring)
    found = mod.attribute(ctx, "train/dispatch")
    assert (found is not None) == mapped
    if mapped:   # module i with dispatch i: each ran 0.5 ms after its end
        k = K - 0.5 * MS
        assert found["chips"][0]["anchor_ms"] == pytest.approx(
            (k - (span_at * 1e9 - 10 * MS)) / MS, abs=1e-3)
