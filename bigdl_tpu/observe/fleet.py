"""Fleet brain — cross-process telemetry aggregation.

PR 10's telemetry plane is strictly per-process: in a multihost or
multi-replica deployment every process serves its own /statusz and
nobody sees the whole fleet. This module is the driver-side aggregation
point the reference keeps at the Spark driver (`TrainSummary` /
`ValidationSummary` collected per-node into one dashboard — SURVEY §2),
rebuilt for the HTTP plane:

  * **Discovery** — peer /statusz endpoints come from
    ``BIGDL_TPU_FLEET_PEERS`` (explicit ``host:port`` list — the
    real-topology override) or are DERIVED from the distributed process
    table (``utils/runtime.fleet_peer_candidates``: process *i* serves
    at ``STATUSZ_PORT + i``; observe/statusz.py offsets the bind on
    non-zero processes when ``BIGDL_TPU_FLEET`` is on).

  * **Polling** — process 0's :class:`FleetAggregator` polls every
    peer's ``/statusz`` (operator headline) and ``/varz`` (raw registry
    snapshot) on the export-flush cadence from a sanctioned
    ``utils/threads.PeriodicWorker``. A peer that stops answering is
    marked **stale, never dropped**: its last-known state and failure
    count stay on the pane (``fleet/peer_unreachable`` counts every
    miss, ``fleet/peers_stale`` gauges the current count) — a dead
    process disappearing from the dashboard is how outages hide.

  * **Serving** — the same statusz HTTP thread grows two endpoints:
    ``/fleetz`` (merged per-peer health, step skew, loss/throughput
    spread, failover + sanitizer findings rolled up, merged incident
    list; ``?full=1`` embeds each peer's raw snapshot for the
    ``observe report --fleet`` CLI) and ``/fleetz/metrics`` (every
    peer's registry in Prometheus exposition format, peer-labeled
    through the shared ``export.render_prometheus``).

Cadence contract unchanged: aggregation reads HTTP + host-side state
only — polling the fleet adds zero device syncs to any train loop.
"""

from __future__ import annotations

import json
import logging
import time
import urllib.request
from typing import Callable, Dict, List, Optional

from bigdl_tpu.observe import metrics as _metrics
from bigdl_tpu.observe.export import render_prometheus
from bigdl_tpu.utils.threads import PeriodicWorker, make_lock

log = logging.getLogger("bigdl_tpu")


def enabled() -> bool:
    """Fleet mode is armed by BIGDL_TPU_FLEET=1 or a non-empty
    BIGDL_TPU_FLEET_PEERS list (statusz.py consults this to offset
    non-zero processes' bind ports)."""
    from bigdl_tpu.utils import config
    return bool(config.get("FLEET") or config.get("FLEET_PEERS").strip())


def resolve_peers() -> List[str]:
    """The peer address list: explicit knob first, then the derivation
    from the distributed process table."""
    from bigdl_tpu.utils import config
    raw = config.get("FLEET_PEERS").strip()
    if raw:
        return [p.strip() for p in raw.split(",") if p.strip()]
    from bigdl_tpu.utils.runtime import fleet_peer_candidates
    return fleet_peer_candidates(config.get("STATUSZ_PORT"))


def _http_get_json(addr: str, path: str, timeout: float) -> dict:
    with urllib.request.urlopen(f"http://{addr}{path}",
                                timeout=timeout) as r:
        return json.loads(r.read().decode())


class PeerState:
    """One peer's rolling view: last-known payloads + reachability."""

    __slots__ = ("index", "addr", "ok", "stale", "payload", "snapshot",
                 "last_ok_t", "failures", "polls", "misses",
                 "last_error")

    def __init__(self, index: int, addr: str):
        self.index = index
        self.addr = addr
        self.ok = False
        self.stale = False
        self.payload: dict = {}
        self.snapshot: dict = {}
        self.last_ok_t = 0.0
        self.failures = 0        # consecutive
        self.polls = 0
        self.misses = 0          # lifetime
        self.last_error = ""


class FleetAggregator:
    """Polls every peer plane and serves the merged view. Built by
    :func:`ensure_started` on process 0; tests build private ones with
    an injected `fetch` (no HTTP)."""

    def __init__(self, peers: List[str], *, poll_s: float = 5.0,
                 stale_after: Optional[int] = None,
                 fetch: Optional[Callable[[str, str, float], dict]] = None,
                 start_thread: bool = True):
        from bigdl_tpu.utils import config
        if not peers:
            raise ValueError("fleet aggregation needs at least one peer")
        self.poll_s = max(0.1, float(poll_s))
        self.stale_after = (config.get("FLEET_STALE_POLLS")
                            if stale_after is None else stale_after)
        self.timeout_s = min(2.0, self.poll_s)
        self._fetch = fetch or _http_get_json
        self._lock = make_lock("fleet.aggregator")
        self._peers = [PeerState(i, a) for i, a in enumerate(peers)]
        self._last_poll_t = 0.0
        self._worker: Optional[PeriodicWorker] = None
        _metrics.gauge("fleet/peers").set(len(self._peers))
        if start_thread:
            self.start()

    def start(self) -> "FleetAggregator":
        if self._worker is None:
            self._worker = PeriodicWorker(self.poll_once, self.poll_s,
                                          name="fleet-poller")
        return self

    # ------------------------------------------------------------- polling
    def poll_once(self) -> None:
        """One scrape of every peer. Failures mark the peer unreachable
        (stale after `stale_after` consecutive misses) — the aggregator
        itself never raises out of a poll."""
        for peer in self._peers:
            try:
                # one request per peer per sweep: /statusz?varz=1
                # carries the registry snapshot inline (falls back to a
                # second /varz fetch against a peer that predates it)
                payload = self._fetch(peer.addr, "/statusz?varz=1",
                                      self.timeout_s)
                snapshot = payload.pop("varz", None)
                if snapshot is None:
                    snapshot = self._fetch(peer.addr, "/varz",
                                           self.timeout_s)
            except Exception as e:       # noqa: BLE001 — peer down
                with self._lock:
                    peer.polls += 1
                    peer.misses += 1
                    peer.failures += 1
                    peer.ok = False
                    peer.last_error = str(e)
                    newly_stale = (not peer.stale
                                   and peer.failures >= self.stale_after)
                    if newly_stale:
                        peer.stale = True
                _metrics.counter("fleet/peer_unreachable").inc()
                if newly_stale:
                    log.warning(
                        "fleet: peer %d (%s) unreachable for %d polls — "
                        "marked STALE (kept on the pane): %s",
                        peer.index, peer.addr, peer.failures, e)
                continue
            with self._lock:
                peer.polls += 1
                was_stale = peer.stale
                peer.ok = True
                peer.stale = False
                peer.failures = 0
                peer.payload = payload
                peer.snapshot = snapshot
                peer.last_ok_t = time.time()
                peer.last_error = ""
            if was_stale:
                log.warning("fleet: peer %d (%s) is back — stale flag "
                            "cleared", peer.index, peer.addr)
        with self._lock:
            self._last_poll_t = time.time()
            stale = sum(1 for p in self._peers if p.stale)
        _metrics.counter("fleet/polls").inc()
        _metrics.gauge("fleet/peers_stale").set(stale)
        _metrics.gauge("fleet/last_poll_unix").set(time.time())

    # ------------------------------------------------------------- merging
    def _peer_rows(self) -> List[dict]:
        now = time.time()
        rows = []
        with self._lock:
            peers = list(self._peers)
            for p in peers:
                t = (p.payload.get("train") or {})
                wd = (p.payload.get("watchdog") or {})
                rows.append({
                    "index": p.index,
                    "addr": p.addr,
                    "ok": p.ok,
                    "stale": p.stale,
                    "last_ok_age_s": (round(now - p.last_ok_t, 3)
                                      if p.last_ok_t else None),
                    "consecutive_failures": p.failures,
                    "misses": p.misses,
                    "last_error": p.last_error or None,
                    "run_id": p.payload.get("run_id"),
                    "process_index": p.payload.get("process_index"),
                    "step": t.get("step"),
                    "epoch": t.get("epoch"),
                    "loss": t.get("loss"),
                    "throughput_rec_s": t.get("throughput_rec_s"),
                    "nonfinite_steps": t.get("nonfinite_steps"),
                    "last_step_age_s": p.payload.get("last_step_age_s"),
                    "data_wait": (p.payload.get("data_wait") or {}
                                  ).get("fraction"),
                    "alert_active": wd.get("alert_active"),
                    # DCN exchange: where the peer sits inside its
                    # T-window + its per-slice loss spread (statusz
                    # `exchange` section; None off-mode)
                    "exchange_pending": (p.payload.get("exchange")
                                         or {}).get("pending_steps"),
                    "slice_loss_spread": (p.payload.get("exchange")
                                          or {}).get("loss_spread"),
                    # iteration-level decode (statusz `decode` section):
                    # the peer's aggregate decode rate + live slots
                    "decode_tokens_per_s": self._peer_decode_rate(
                        p.payload),
                    # device-memory plane (statusz `memory` section,
                    # observe/memz.py): utilization, headroom, and the
                    # biggest ledger owner per peer — STALE peers keep
                    # their last-known rows like every other signal
                    "mem_utilization_pct": (p.payload.get("memory")
                                            or {}).get("utilization_pct"),
                    "mem_headroom_bytes": (p.payload.get("memory")
                                           or {}).get("headroom_bytes"),
                    "mem_ledger_bytes": (p.payload.get("memory")
                                         or {}).get("ledger_bytes"),
                    "mem_top_owner": (p.payload.get("memory")
                                      or {}).get("top_owner"),
                })
        return rows

    @staticmethod
    def _peer_decode_rate(payload: dict) -> Optional[float]:
        """Sum of a peer's per-model decode tokens/s (None when the
        peer serves no decode models)."""
        dec = payload.get("decode") or {}
        rates = [float(s.get("tokens_per_s", 0) or 0)
                 for s in dec.values() if isinstance(s, dict)]
        return round(sum(rates), 2) if rates else None

    @staticmethod
    def _spread(vals: List[float]) -> Optional[dict]:
        vs = [float(v) for v in vals if v is not None]
        if not vs:
            return None
        return {"min": round(min(vs), 6), "max": round(max(vs), 6),
                "mean": round(sum(vs) / len(vs), 6),
                "spread": round(max(vs) - min(vs), 6)}

    def fleet_payload(self, full: bool = False) -> dict:
        """The merged /fleetz JSON. `full=True` embeds each reachable
        peer's raw registry snapshot (the report CLI's --fleet input)."""
        from bigdl_tpu.utils.runtime import run_id
        rows = self._peer_rows()
        live = [r for r in rows if r["ok"]]
        steps = [r["step"] for r in live if r["step"] is not None]
        alerts: List[dict] = []
        serve: Dict[str, dict] = {}
        failover: Dict[str, float] = {}
        san_reports = 0
        san_by_peer: Dict[str, int] = {}
        with self._lock:
            peers = list(self._peers)
        for p in peers:
            for a in ((p.payload.get("watchdog") or {}).get("alerts")
                      or []):
                alerts.append({"peer": p.index, **a})
            swd = ((p.payload.get("watchdog") or {}).get("serve")
                   or {})
            for a in swd.get("alerts") or []:
                alerts.append({"peer": p.index, **a})
            sv = p.payload.get("serve") or {}
            for model, s in sv.items():
                if model.startswith("_") or not isinstance(s, dict):
                    continue
                agg = serve.setdefault(
                    model, {"requests": 0, "p99_ms_max": 0.0,
                            "queued_rows": 0, "peers": 0})
                agg["requests"] += int(s.get("requests", 0) or 0)
                agg["p99_ms_max"] = max(agg["p99_ms_max"],
                                        float(s.get("p99_ms", 0) or 0))
                agg["queued_rows"] += int(s.get("queued_rows", 0) or 0)
                agg["peers"] += 1
                d = s.get("decode")
                if isinstance(d, dict):
                    # per-model decode aggregates: fleet tokens/s is
                    # additive; slot occupancy averages across peers
                    dec = agg.setdefault("decode", {
                        "tokens": 0, "tokens_per_s": 0.0,
                        "active_slots": 0, "slots": 0,
                        "_occ_sum": 0.0, "_occ_n": 0, "peers": 0})
                    dec["tokens"] += int(d.get("tokens", 0) or 0)
                    dec["tokens_per_s"] = round(
                        dec["tokens_per_s"]
                        + float(d.get("tokens_per_s", 0) or 0), 2)
                    dec["active_slots"] += int(
                        d.get("active_slots", 0) or 0)
                    dec["slots"] += int(d.get("slots", 0) or 0)
                    occ = d.get("slot_occupancy_mean")
                    if occ is not None:
                        dec["_occ_sum"] += float(occ)
                        dec["_occ_n"] += 1
                    dec["peers"] += 1
                    # KV pool economics: block counts are additive
                    # across peers; prefix hit rate is re-derived from
                    # the summed hit/miss counters
                    for k in ("kv_blocks_total", "kv_blocks_free",
                              "kv_blocks_cached", "prefix_hits",
                              "prefix_misses"):
                        dec[k] = dec.get(k, 0) + int(d.get(k, 0) or 0)
                    seen = (dec.get("prefix_hits", 0)
                            + dec.get("prefix_misses", 0))
                    dec["prefix_hit_rate"] = (
                        round(dec["prefix_hits"] / seen, 4)
                        if seen else 0.0)
            fo = p.payload.get("failover") or {}
            for k in ("slice_losses", "grow_backs", "lost_slices"):
                if k in fo:
                    failover[k] = failover.get(k, 0) + fo[k]
            if "live_slices" in fo:
                failover["min_live_slices"] = min(
                    failover.get("min_live_slices", fo["live_slices"]),
                    fo["live_slices"])
            san = p.payload.get("sanitizer") or {}
            n = len(san.get("reports") or [])
            if n:
                san_reports += n
                san_by_peer[str(p.index)] = n
        for agg in serve.values():
            dec = agg.get("decode")
            if dec is not None:
                n = dec.pop("_occ_n")
                occ_sum = dec.pop("_occ_sum")
                dec["slot_occupancy_mean"] = (round(occ_sum / n, 4)
                                              if n else None)
        alerts.sort(key=lambda a: a.get("opened_at", 0.0))
        payload = {
            "run_id": run_id(),
            "ts": time.time(),
            "poll_s": self.poll_s,
            "stale_after": self.stale_after,
            "peers": rows,
            "fleet": {
                "peers_total": len(rows),
                "peers_live": len(live),
                "peers_stale": sum(1 for r in rows if r["stale"]),
                "unreachable_polls": int(_metrics.counter(
                    "fleet/peer_unreachable").value),
                "step": ({"min": min(steps), "max": max(steps),
                          "skew": max(steps) - min(steps)}
                         if steps else None),
                "loss": self._spread([r["loss"] for r in live]),
                "throughput_rec_s": self._spread(
                    [r["throughput_rec_s"] for r in live]),
                "data_wait_max": max(
                    [r["data_wait"] for r in live
                     if r["data_wait"] is not None], default=None),
                # fleet memory headline: the hottest peer's device
                # utilization + the tightest headroom (capacity
                # questions are answered by the WORST peer)
                "mem_utilization_max": max(
                    [r["mem_utilization_pct"] for r in live
                     if r["mem_utilization_pct"] is not None],
                    default=None),
                "mem_headroom_min_bytes": min(
                    [r["mem_headroom_bytes"] for r in live
                     if r["mem_headroom_bytes"] is not None],
                    default=None),
                "alerts_active": sum(1 for r in rows
                                     if r.get("alert_active")),
            },
            "alerts": alerts,
            "serve": serve or None,
            "failover": failover or None,
            "sanitizer": ({"reports": san_reports,
                           "by_peer": san_by_peer}
                          if san_reports else None),
        }
        if steps:
            _metrics.gauge("fleet/step_skew").set(
                payload["fleet"]["step"]["skew"])
        if full:
            with self._lock:
                payload["snapshots"] = {
                    str(p.index): p.snapshot for p in self._peers
                    if p.snapshot}
                payload["statusz"] = {
                    str(p.index): p.payload for p in self._peers
                    if p.payload}
        return payload

    def fleet_metrics(self) -> str:
        """Peer-labeled Prometheus exposition: every peer's snapshot
        rendered through the shared `export.render_prometheus` with a
        `peer` label, TYPE headers deduped across peers, plus per-peer
        `bigdl_tpu_fleet_peer_up`/`_stale` reachability series."""
        out: List[str] = []
        seen: set = set()
        with self._lock:
            peers = [(p.index, p.addr, p.ok, p.stale, dict(p.snapshot))
                     for p in self._peers]
        for idx, addr, ok, stale, snap in peers:
            out.append(f'bigdl_tpu_fleet_peer_up{{peer="{idx}",'
                       f'addr="{addr}"}} {1 if ok else 0}')
            out.append(f'bigdl_tpu_fleet_peer_stale{{peer="{idx}",'
                       f'addr="{addr}"}} {1 if stale else 0}')
            if not snap:
                continue
            for line in render_prometheus(
                    snap, labels={"peer": str(idx)}).splitlines():
                if line.startswith("# TYPE"):
                    if line in seen:
                        continue
                    seen.add(line)
                if line:
                    out.append(line)
        return "\n".join(out) + "\n"

    # ----------------------------------------------------------- lifecycle
    def close(self) -> None:
        w, self._worker = self._worker, None
        if w is not None:
            w.stop()


_agg: Optional[FleetAggregator] = None
_agg_lock = make_lock("fleet.singleton")


def ensure_started() -> Optional[FleetAggregator]:
    """Start (or return) the process-wide aggregator. No-op (None) when
    fleet mode is off, this is not process 0, or no peers resolve —
    observe.ensure_started() calls this unconditionally."""
    global _agg
    with _agg_lock:
        if _agg is not None:
            return _agg
        if not enabled():
            return None
        from bigdl_tpu.utils.runtime import process_index
        if process_index() != 0:
            return None
        peers = resolve_peers()
        if not peers:
            log.warning("fleet: aggregation armed but no peers resolve "
                        "(set BIGDL_TPU_FLEET_PEERS or STATUSZ_PORT)")
            return None
        from bigdl_tpu.utils import config
        poll = (config.get("FLEET_POLL_S")
                or config.get("METRICS_FLUSH_S"))
        _agg = FleetAggregator(peers, poll_s=poll)
        log.info("fleet: aggregating %d peer plane%s every %.1fs "
                 "(/fleetz, /fleetz/metrics): %s", len(peers),
                 "s" if len(peers) != 1 else "", _agg.poll_s,
                 ", ".join(peers))
        return _agg


def aggregator() -> Optional[FleetAggregator]:
    return _agg


def stop() -> None:
    """Join the poller and drop the singleton (shutdown path; swap
    under the lock, join outside it — docs/concurrency.md)."""
    global _agg
    with _agg_lock:
        agg, _agg = _agg, None
    if agg is not None:
        agg.close()


# ----------------------------------------------------------------- smoke
def smoke_main(argv: Optional[List[str]] = None) -> int:
    """`python -m bigdl_tpu.observe fleet` — the fleet-plane smoke:
    spins TWO in-process statusz planes on ephemeral ports, aggregates
    them, asserts the merged payload shows both peers live, then kills
    one and asserts it goes stale (not dropped). Exits nonzero on any
    missing peer — the CI canary for the whole aggregation path."""
    import argparse
    ap = argparse.ArgumentParser(
        prog="bigdl_tpu.observe fleet",
        description="Fleet aggregation smoke: two in-process planes, "
                    "one aggregator, merged /fleetz asserted")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    from bigdl_tpu.observe.statusz import StatuszServer
    _metrics.gauge("train/neval").set(42)
    _metrics.gauge("train/loss").set(0.5)
    _metrics.gauge("train/last_flush_unix").set(time.time())
    a = StatuszServer(0)
    b = StatuszServer(0)
    agg = FleetAggregator(
        [f"127.0.0.1:{a.port}", f"127.0.0.1:{b.port}"],
        poll_s=0.5, stale_after=2, start_thread=False)
    problems: List[str] = []
    try:
        agg.poll_once()
        payload = agg.fleet_payload()
        if payload["fleet"]["peers_live"] != 2:
            problems.append(
                f"expected 2 live peers, got "
                f"{payload['fleet']['peers_live']}: "
                f"{[p['last_error'] for p in payload['peers']]}")
        for p in payload["peers"]:
            if p["step"] != 42:
                problems.append(f"peer {p['index']} payload missing "
                                f"train state: step={p['step']}")
        text = agg.fleet_metrics()
        if 'bigdl_tpu_train_neval{peer="1"} 42' not in text:
            problems.append("/fleetz/metrics missing peer-labeled "
                            "series for peer 1")
        # peer death: must go STALE, never dropped, and the aggregator
        # must keep serving
        b.close()
        for _ in range(agg.stale_after):
            agg.poll_once()
        payload = agg.fleet_payload()
        rows = payload["peers"]
        if len(rows) != 2:
            problems.append(f"dead peer was dropped: {len(rows)} rows")
        elif not rows[1]["stale"]:
            problems.append("dead peer not marked stale after "
                            f"{agg.stale_after} failed polls")
        if payload["fleet"]["peers_live"] != 1:
            problems.append("live count wrong after peer death")
    finally:
        agg.close()
        a.close()
        try:
            b.close()
        except Exception:                # noqa: BLE001 — already closed
            pass
    summary = {"ok": not problems, "problems": problems,
               "peers": payload["fleet"]["peers_total"],
               "live": payload["fleet"]["peers_live"],
               "stale": payload["fleet"]["peers_stale"],
               "unreachable_polls": payload["fleet"]["unreachable_polls"]}
    print(json.dumps(summary) if args.json
          else "fleet smoke: " + ("OK " if not problems else "FAIL ")
          + json.dumps(summary))
    return 0 if not problems else 1
