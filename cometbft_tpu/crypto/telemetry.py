"""Verify-path capacity telemetry — who is loading this node, how hard,
and how much headroom is left.

PR 4's spans answer "why was THIS verify slow"; raw counters answer "how
many". Neither answers the capacity questions the roadmap's multi-tenant
verify sidecar (item 4) and live single-chip-vs-mesh routing (item 1)
need: utilization, service attribution, and SLO burn. This module is
that layer, one ``TelemetryHub`` threaded through the existing pipeline:

* **per-device utilization** — the supervisor reports every completed
  device call as a busy interval (``note_device_busy``); the hub keeps a
  bounded window of intervals per fault domain and computes a windowed
  duty cycle (busy seconds over wall seconds, overlap-clipped). "Busy"
  is host-clock dispatch occupancy, the time a device call was
  outstanding, pack and issue included: not device busy share (that is
  ``device.busy_s`` of a traced benchmark run).
* **lane-fill efficiency** — the mesh chunk loop reports real signature
  lanes vs the padded pow2-bucket capacity it dispatched
  (``note_chunk``), so the lanes wasted to AOT shape buckets become a
  measured ratio instead of folklore.
* **per-subsystem RED metering** — the scheduler reports every demuxed
  request (``note_request``) keyed by its existing origin tags
  (consensus / blocksync / light / evidence + height): request and
  error rates, signature counts, and a rolling latency distribution per
  tenant — the accounting primitive sidecar fairness/metering sits on.
* an **SLO engine** — rolling-window p50/p99 end-to-end verify latency
  against ``[instrumentation] slo_commit_ms`` (default 100, the ZKP
  runtime study's p50 commit-verify bar), an error-budget burn rate
  (violation fraction over the unavailability budget of a 99% objective;
  burn 1.0 = spending the budget exactly as fast as it accrues), and a
  **headroom estimator**: observed throughput scaled by the inverse of
  the bottleneck device's utilization and the supervisor's healthy
  ``capacity_fraction()`` — projected sigs/sec still available.
* a **health/capacity plane** — ``snapshot()`` aggregates all of the
  above plus every registered source (supervisor breaker states and
  chunk caps, scheduler queue, device topology) into ONE JSON document,
  served as ``/debug/verify`` by MetricsServer and rendered live by
  ``tools/verify_top.py``.

The hub is also exported as Prometheus families (``verify_telemetry_*``
gauges/counters/µs-bucket histograms and ``verify_slo_*`` gauges) when
built over the node's registry; gauges derived from rolling windows are
refreshed on ``snapshot()`` — i.e. on every scrape of ``/debug/verify``.

A module default (``default_hub`` / ``set_default_hub``) mirrors
``trace.default_tracer`` so the mesh chunk loop — which predates any
node object — reaches the hub without plumbing; no default installed
means the hot path pays one attribute read.
"""

from __future__ import annotations

import math
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

from cometbft_tpu.libs.metrics import MICRO_BUCKETS, Registry

SUBSYSTEM = "verify_telemetry"
SLO_SUBSYSTEM = "verify_slo"

DEFAULT_SLO_COMMIT_MS = 100
DEFAULT_WINDOW_S = 60.0
DEFAULT_OBJECTIVE = 0.99
# Bound per-window sample retention (requests, busy intervals, chunks).
_MAX_SAMPLES = 4096
# Incident-timeline ring capacity (discrete control-plane events).
_TIMELINE_EVENTS = 256
# Requests with no origin tag meter under this tenant.
UNTAGGED = "untagged"


def slo_commit_ms_default(config_value: Optional[int] = None) -> int:
    """Resolve the SLO latency target: CBFT_SLO_COMMIT_MS env >
    [instrumentation] slo_commit_ms > built-in 100ms."""
    raw = os.environ.get("CBFT_SLO_COMMIT_MS")
    if raw is not None:
        try:
            return max(1, int(raw))
        except ValueError:
            pass
    if config_value is not None:
        return max(1, int(config_value))
    return DEFAULT_SLO_COMMIT_MS


class Metrics:
    """Capacity-telemetry export (libs/metrics.py instruments), wired
    into the node's Prometheus registry when [instrumentation] enables
    it. Latency families use MICRO_BUCKETS — verify-path stages live at
    µs-to-ms scale, far below DEFAULT_BUCKETS' 5ms first rung."""

    def __init__(self, registry: Optional[Registry] = None):
        r = registry if registry is not None else Registry()
        self.device_utilization = r.gauge(
            SUBSYSTEM, "device_utilization",
            "Windowed host-clock dispatch occupancy per fault domain: "
            "seconds a device call was outstanding over wall seconds in "
            "the rolling window, by device label. Not device busy share "
            "(that is device.busy_s of a traced benchmark run).",
        )
        self.device_busy_seconds = r.counter(
            SUBSYSTEM, "device_busy_seconds",
            "Cumulative host-clock wall time of device calls, by device "
            "label.",
        )
        self.device_sigs = r.counter(
            SUBSYSTEM, "device_sigs",
            "Signatures served by completed device calls, by device "
            "label.",
        )
        self.lane_fill_efficiency = r.gauge(
            SUBSYSTEM, "lane_fill_efficiency",
            "Windowed real signature lanes over padded pow2-bucket "
            "lanes dispatched — 1.0 means no lanes wasted to shape "
            "buckets.",
        )
        self.lanes_real = r.counter(
            SUBSYSTEM, "lanes_real",
            "Real signature lanes dispatched to the device plane.",
        )
        self.lanes_padded = r.counter(
            SUBSYSTEM, "lanes_padded",
            "Padded pow2-bucket lanes dispatched (real + zero-filled).",
        )
        self.red_requests = r.counter(
            SUBSYSTEM, "red_requests",
            "Verify requests metered, by submitting subsystem.",
        )
        self.red_errors = r.counter(
            SUBSYSTEM, "red_errors",
            "Verify requests whose verdict mask contained at least one "
            "rejected signature, by submitting subsystem.",
        )
        self.red_sigs = r.counter(
            SUBSYSTEM, "red_sigs",
            "Signatures metered, by submitting subsystem.",
        )
        self.red_latency_seconds = r.histogram(
            SUBSYSTEM, "red_latency_seconds",
            "End-to-end per-request verify latency (queue wait + "
            "service), by submitting subsystem.",
            buckets=MICRO_BUCKETS,
        )
        self.red_disconnects = r.counter(
            SUBSYSTEM, "red_disconnects",
            "Verify-service requests whose client connection died before "
            "the verdict could be delivered, by tenant.",
        )
        self.red_fallbacks = r.counter(
            SUBSYSTEM, "red_fallbacks",
            "Client-side verify fallback ladder events, by tenant and "
            "reason (disconnected / timeout / draining / stale / error / "
            "unauthorized hit the local-CPU rung; failover = absorbed by "
            "a healthy secondary instead).",
        )
        self.slo_target_ms = r.gauge(
            SLO_SUBSYSTEM, "target_ms",
            "Configured commit-verify latency target "
            "([instrumentation] slo_commit_ms).",
        )
        self.slo_p50_ms = r.gauge(
            SLO_SUBSYSTEM, "p50_ms",
            "Rolling-window median end-to-end verify latency.",
        )
        self.slo_p99_ms = r.gauge(
            SLO_SUBSYSTEM, "p99_ms",
            "Rolling-window p99 end-to-end verify latency.",
        )
        self.slo_burn_rate = r.gauge(
            SLO_SUBSYSTEM, "burn_rate",
            "Error-budget burn rate: window violation fraction over the "
            "unavailability budget (1 - objective); 1.0 spends the "
            "budget exactly as fast as it accrues.",
        )
        self.slo_headroom_sigs_per_sec = r.gauge(
            SLO_SUBSYSTEM, "headroom_sigs_per_sec",
            "Projected additional signatures/sec available given "
            "current utilization and healthy capacity fraction "
            "(-1 while cold: no utilization observed yet).",
        )
        self.slo_window_requests = r.gauge(
            SLO_SUBSYSTEM, "window_requests",
            "Requests currently inside the SLO rolling window.",
        )

    @classmethod
    def nop(cls) -> "Metrics":
        return cls(None)


def _percentile(sorted_vals: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile over an ascending list; None when empty."""
    if not sorted_vals:
        return None
    rank = int(math.ceil(q * len(sorted_vals)))
    return sorted_vals[min(len(sorted_vals) - 1, max(0, rank - 1))]


class _IntervalWindow:
    """Bounded record of (t0, t1, n_sigs) busy intervals for ONE device.

    ``busy_in(now, window)`` clips every interval to [now - window, now]
    and sums — the windowed duty cycle numerator. Intervals may overlap
    (a hedged dispatch racing a retry); the duty cycle is capped at 1.0
    by the caller, so overlap reads as "saturated", never >100%.
    """

    __slots__ = ("_iv",)

    def __init__(self) -> None:
        self._iv: Deque[Tuple[float, float, int]] = deque(maxlen=_MAX_SAMPLES)

    def add(self, t0: float, t1: float, n_sigs: int) -> None:
        self._iv.append((t0, t1, n_sigs))

    def busy_in(self, now: float, window_s: float) -> Tuple[float, int]:
        cutoff = now - window_s
        busy = 0.0
        sigs = 0
        for t0, t1, n in self._iv:
            if t1 <= cutoff:
                continue
            busy += min(t1, now) - max(t0, cutoff)
            sigs += n
        return max(0.0, busy), sigs


class SLOEngine:
    """Rolling-window latency objective tracker for the verify path.

    Feeds on every metered request's end-to-end latency; reports p50/p99
    vs the configured target and the error-budget burn rate: with a
    ``objective`` fraction of requests allowed to miss the target, burn
    = (violating fraction in window) / (1 - objective). Burn 1.0 spends
    the budget exactly at the sustainable rate; >1 exhausts it early.
    """

    def __init__(
        self,
        target_ms: Optional[int] = None,
        objective: float = DEFAULT_OBJECTIVE,
        window_s: float = DEFAULT_WINDOW_S,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.target_ms = slo_commit_ms_default(target_ms)
        self.objective = min(0.9999, max(0.0, float(objective)))
        self.window_s = max(1e-3, float(window_s))
        self._clock = clock
        self._mtx = threading.Lock()
        # (t_observed, latency_s, n_sigs)
        self._samples: Deque[Tuple[float, float, int]] = deque(
            maxlen=_MAX_SAMPLES
        )
        self._born = clock()

    def observe(self, latency_s: float, n_sigs: int = 1) -> None:
        with self._mtx:
            self._samples.append((self._clock(), latency_s, n_sigs))

    def snapshot(self, now: Optional[float] = None) -> Dict[str, Any]:
        if now is None:
            now = self._clock()
        cutoff = now - self.window_s
        with self._mtx:
            live = [(lat, n) for t, lat, n in self._samples if t > cutoff]
            born = self._born
        lats = sorted(lat for lat, _ in live)
        target_s = self.target_ms / 1e3
        violations = sum(1 for lat in lats if lat > target_s)
        budget = 1.0 - self.objective
        burn = (violations / len(lats)) / budget if lats else 0.0
        # throughput over the time the window actually covers (a node
        # younger than the window divides by its age, not the window)
        elapsed = max(1e-3, min(self.window_s, now - born))
        p50 = _percentile(lats, 0.50)
        p99 = _percentile(lats, 0.99)
        return {
            "target_ms": self.target_ms,
            "objective": self.objective,
            "window_s": self.window_s,
            "requests": len(lats),
            "violations": violations,
            "p50_ms": None if p50 is None else round(p50 * 1e3, 3),
            "p99_ms": None if p99 is None else round(p99 * 1e3, 3),
            "burn_rate": round(burn, 4),
            "throughput_sigs_per_sec": round(
                sum(n for _, n in live) / elapsed, 2
            ),
        }


class TelemetryHub:
    """The verify path's capacity accountant: one instance per node,
    fed by the scheduler (requests), supervisor (device busy intervals),
    and mesh (chunk lane fill); drained by ``snapshot()``.

    Note methods are hot-path: bounded deque appends plus counter
    bumps, no aggregation. All aggregation (duty cycles, percentiles,
    headroom) happens in ``snapshot()`` — scrape-time work.
    """

    def __init__(
        self,
        metrics: Optional[Metrics] = None,
        slo_target_ms: Optional[int] = None,
        window_s: float = DEFAULT_WINDOW_S,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.metrics = metrics if metrics is not None else Metrics.nop()
        self.window_s = max(1e-3, float(window_s))
        self._clock = clock
        self.slo = SLOEngine(
            target_ms=slo_target_ms, window_s=self.window_s, clock=clock
        )
        self.metrics.slo_target_ms.set(self.slo.target_ms)
        self._mtx = threading.Lock()
        self._devices: Dict[str, _IntervalWindow] = {}
        # windowed lane-fill samples: (t, real, padded)
        self._chunks: Deque[Tuple[float, int, int]] = deque(
            maxlen=_MAX_SAMPLES
        )
        # subsystem -> [requests, errors, sigs, last_height,
        #               deque[(t, latency_s)]]
        self._subsystems: Dict[str, List[Any]] = {}
        # tenant -> requests abandoned by a mid-flight disconnect; kept
        # beside the positional RED recs, not inside them, so existing
        # rec indexing stays untouched
        self._disconnects: Dict[str, int] = {}
        # tenant -> {reason: count} of client-side fallback ladder
        # events (disconnected/timeout/draining/... plus HA failovers)
        self._fallbacks: Dict[str, Dict[str, int]] = {}
        self._sources: Dict[str, Callable[[], Any]] = {}
        self._capacity_fn: Optional[Callable[[], float]] = None
        self._burn_watchers: List[Callable[[float], None]] = []
        # incident timeline: a bounded ring of discrete control-plane
        # events (breaker motion, brownout steps, watchdog trips,
        # keystore churn, disconnects) stamped on ONE wall clock so
        # client- and server-side incidents order against each other
        self._timeline: Deque[Dict[str, Any]] = deque(
            maxlen=_TIMELINE_EVENTS
        )
        self._event_listeners: List[Callable[[Dict[str, Any]], None]] = []

    # -- feeders (hot path) --------------------------------------------------

    def note_request(
        self,
        n_sigs: int,
        wait_s: float,
        service_s: float,
        ok: bool,
        subsystem: Optional[str] = None,
        height: Optional[int] = None,
    ) -> None:
        """One demuxed scheduler request: RED metering under its origin
        tag plus an SLO sample (end-to-end = queue wait + service)."""
        name = subsystem or UNTAGGED
        latency_s = max(0.0, wait_s) + max(0.0, service_s)
        with self._mtx:
            rec = self._subsystems.get(name)
            if rec is None:
                rec = self._subsystems[name] = [
                    0, 0, 0, None, deque(maxlen=_MAX_SAMPLES)
                ]
            rec[0] += 1
            if not ok:
                rec[1] += 1
            rec[2] += int(n_sigs)
            if height is not None:
                rec[3] = int(height)
            rec[4].append((self._clock(), latency_s))
        self.slo.observe(latency_s, int(n_sigs))
        m = self.metrics
        m.red_requests.with_labels(subsystem=name).add()
        if not ok:
            m.red_errors.with_labels(subsystem=name).add()
        m.red_sigs.with_labels(subsystem=name).add(int(n_sigs))
        m.red_latency_seconds.with_labels(subsystem=name).observe(latency_s)

    def note_disconnect(self, tenant: Optional[str], n: int = 1) -> None:
        """``n`` verify-service requests orphaned by ``tenant``'s
        connection dying mid-flight. RED-metered per tenant (a flapping
        client must look flappy in /debug/verify) and surfaced in
        ``subsystems()`` beside the tenant's request/error rates."""
        name = tenant or UNTAGGED
        with self._mtx:
            self._disconnects[name] = (
                self._disconnects.get(name, 0) + int(n)
            )
            if name not in self._subsystems:
                # make the tenant visible in the RED view even if every
                # one of its requests died before a verdict was metered
                self._subsystems[name] = [
                    0, 0, 0, None, deque(maxlen=_MAX_SAMPLES)
                ]
        self.metrics.red_disconnects.with_labels(tenant=name).add(int(n))
        self.note_event("disconnect", {"tenant": name, "pending": int(n)})

    def note_fallback(
        self,
        tenant: Optional[str],
        reason: str,
        kind: str = "client_fallback",
        detail: Optional[Dict[str, Any]] = None,
    ) -> None:
        """One client-side fallback ladder event: RED-metered per
        (tenant, reason) and stamped on the incident timeline. The
        reason taxonomy keeps an intentional drain (``draining``), a
        crash (``disconnected``), and an HA-absorbed resubmit
        (``failover``, kind ``client_failover``) distinguishable in
        every panel."""
        name = tenant or UNTAGGED
        with self._mtx:
            per = self._fallbacks.setdefault(name, {})
            per[reason] = per.get(reason, 0) + 1
            if name not in self._subsystems:
                # keep the tenant visible in the RED view even when its
                # every request resolved on the fallback ladder
                self._subsystems[name] = [
                    0, 0, 0, None, deque(maxlen=_MAX_SAMPLES)
                ]
        self.metrics.red_fallbacks.with_labels(
            tenant=name, reason=reason
        ).add()
        ev: Dict[str, Any] = {"tenant": name, "reason": reason}
        if detail:
            ev.update(detail)
        self.note_event(kind, ev, source="client")

    def note_event(
        self,
        kind: str,
        detail: Optional[Dict[str, Any]] = None,
        source: str = "server",
    ) -> None:
        """Append one discrete event to the incident timeline.

        ``kind`` names the event (brownout_trip, breaker_open,
        watchdog_trip, valset_registered, disconnect, client_fallback…),
        ``source`` says which side of the wire saw it ("server" /
        "client"), and the stamp is ``time.time()`` — WALL clock, not the
        hub's monotonic clock, so rings exported from two processes
        merge onto one axis."""
        ev: Dict[str, Any] = {"t": time.time(), "kind": kind,
                              "source": source}
        if detail:
            ev.update(detail)
        with self._mtx:
            self._timeline.append(ev)
            listeners = list(self._event_listeners)
        for fn in listeners:
            try:
                fn(ev)
            except Exception:  # noqa: BLE001 - listener is advisory
                pass

    def add_event_listener(
        self, fn: Callable[[Dict[str, Any]], None]
    ) -> None:
        """Observe every timeline event as it lands (outside the hub
        lock). verifyd wires its incident-dump trigger here — a
        brownout trip or breaker open flushes the flight recorder with
        the service panel embedded."""
        with self._mtx:
            self._event_listeners.append(fn)

    def timeline(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """The incident timeline, oldest first; ``limit`` keeps the
        newest N."""
        with self._mtx:
            events = list(self._timeline)
        if limit is not None:
            events = events[-max(0, int(limit)):]
        return events

    def note_device_busy(
        self, device: str, t0: float, t1: float, n_sigs: int
    ) -> None:
        """One completed device call on fault domain ``device``:
        [t0, t1] on the hub's clock (time.monotonic in production) joins
        that device's busy-interval window."""
        if t1 < t0:
            t0, t1 = t1, t0
        with self._mtx:
            win = self._devices.get(device)
            if win is None:
                win = self._devices[device] = _IntervalWindow()
            win.add(t0, t1, int(n_sigs))
        self.metrics.device_busy_seconds.with_labels(device=device).add(
            t1 - t0
        )
        self.metrics.device_sigs.with_labels(device=device).add(int(n_sigs))

    def note_chunk(self, device: str, real: int, padded: int) -> None:
        """One mesh chunk dispatch: ``real`` signature lanes inside a
        ``padded`` pow2-bucket dispatch on ``device``."""
        real = max(0, int(real))
        padded = max(real, int(padded))
        with self._mtx:
            self._chunks.append((self._clock(), real, padded))
        self.metrics.lanes_real.add(real)
        self.metrics.lanes_padded.add(padded)

    # -- plane assembly ------------------------------------------------------

    def register_source(self, name: str, fn: Callable[[], Any]) -> None:
        """Add a named snapshot contributor (supervisor, scheduler,
        topology…); its return value embeds under ``sources.<name>``. A
        raising source reports its error instead of killing the plane."""
        with self._mtx:
            self._sources[str(name)] = fn

    def set_capacity_fraction(self, fn: Optional[Callable[[], float]]) -> None:
        """Install the healthy-capacity oracle (the supervisor's
        ``healthy_capacity_fraction``) the headroom estimator scales by."""
        self._capacity_fn = fn

    def set_burn_watcher(self, fn: Optional[Callable[[float], None]]) -> None:
        """Install a callable invoked with the SLO burn rate on every
        ``snapshot()`` — the incident profiler's auto-capture trigger
        (libs/profiling.py ``on_burn``). Best-effort: a raising watcher
        never breaks the plane. Replaces any previously installed
        watchers; use ``add_burn_watcher`` to stack several (profiler
        capture + QoS brownout ride the same signal)."""
        with self._mtx:
            self._burn_watchers = [fn] if fn is not None else []

    def add_burn_watcher(self, fn: Callable[[float], None]) -> None:
        """Append a burn watcher without displacing the ones already
        installed — every watcher sees every ``snapshot()``'s burn rate,
        each isolated in its own try/except."""
        with self._mtx:
            self._burn_watchers.append(fn)

    def utilization(self, now: Optional[float] = None) -> Dict[str, Any]:
        """Windowed per-device duty cycle + served signature counts."""
        if now is None:
            now = self._clock()
        window = self.window_s
        with self._mtx:
            devices = list(self._devices.items())
        out = {}
        for label, win in devices:
            busy, sigs = win.busy_in(now, window)
            out[label] = {
                "utilization": round(min(1.0, busy / window), 4),
                "busy_s": round(busy, 4),
                "window_sigs": sigs,
            }
        return out

    def lane_fill(self, now: Optional[float] = None) -> Dict[str, Any]:
        """Windowed lane-fill efficiency: real vs padded lanes."""
        if now is None:
            now = self._clock()
        cutoff = now - self.window_s
        with self._mtx:
            live = [(r, p) for t, r, p in self._chunks if t > cutoff]
        real = sum(r for r, _ in live)
        padded = sum(p for _, p in live)
        return {
            "chunks": len(live),
            "real_lanes": real,
            "padded_lanes": padded,
            "efficiency": round(real / padded, 4) if padded else None,
        }

    def headroom(
        self,
        slo: Optional[Dict[str, Any]] = None,
        util: Optional[Dict[str, Any]] = None,
        now: Optional[float] = None,
    ) -> Dict[str, Any]:
        """Projected sigs/sec remaining: observed throughput scaled to
        100% of the BOTTLENECK device's duty cycle, then to the healthy
        capacity fraction, minus what is already being served. None
        while cold (no device utilization observed in the window) — a
        projection from zero load would be fiction."""
        if now is None:
            now = self._clock()
        if slo is None:
            slo = self.slo.snapshot(now)
        if util is None:
            util = self.utilization(now)
        throughput = float(slo.get("throughput_sigs_per_sec") or 0.0)
        peak = max(
            (d["utilization"] for d in util.values()), default=0.0
        )
        frac = 1.0
        fn = self._capacity_fn
        if fn is not None:
            try:
                frac = min(1.0, max(0.0, float(fn())))
            except Exception:  # noqa: BLE001 - oracle is advisory
                frac = 1.0
        if peak <= 0.0 or throughput <= 0.0:
            projected = None
            headroom = None
        else:
            projected = round(throughput / peak * frac, 2)
            headroom = round(max(0.0, projected - throughput), 2)
        return {
            "throughput_sigs_per_sec": round(throughput, 2),
            "peak_device_utilization": round(peak, 4),
            "healthy_capacity_fraction": round(frac, 4),
            "projected_capacity_sigs_per_sec": projected,
            "headroom_sigs_per_sec": headroom,
        }

    def subsystems(self, now: Optional[float] = None) -> Dict[str, Any]:
        """Per-tenant RED view: totals plus windowed rate and latency
        percentiles, keyed by the scheduler's origin tags."""
        if now is None:
            now = self._clock()
        cutoff = now - self.window_s
        with self._mtx:
            rows = {
                name: (rec[0], rec[1], rec[2], rec[3], list(rec[4]))
                for name, rec in self._subsystems.items()
            }
            disconnects = dict(self._disconnects)
            fallbacks = {
                name: dict(per) for name, per in self._fallbacks.items()
            }
        out = {}
        for name, (reqs, errs, sigs, height, samples) in rows.items():
            live = sorted(lat for t, lat in samples if t > cutoff)
            p50 = _percentile(live, 0.50)
            p99 = _percentile(live, 0.99)
            out[name] = {
                "requests": reqs,
                "errors": errs,
                "sigs": sigs,
                "last_height": height,
                "disconnects": disconnects.get(name, 0),
                "fallbacks": fallbacks.get(name, {}),
                "window_requests": len(live),
                "rate_per_sec": round(len(live) / self.window_s, 3),
                "p50_ms": None if p50 is None else round(p50 * 1e3, 3),
                "p99_ms": None if p99 is None else round(p99 * 1e3, 3),
            }
        return out

    def snapshot(self) -> Dict[str, Any]:
        """The health/capacity plane: ONE JSON-ready document. Also
        refreshes the window-derived gauges (utilization, lane fill,
        SLO, headroom) so a Prometheus scrape adjacent to a
        /debug/verify poll sees the same numbers."""
        now = self._clock()
        util = self.utilization(now)
        fill = self.lane_fill(now)
        slo = self.slo.snapshot(now)
        with self._mtx:
            watchers = list(self._burn_watchers)
        burn = float(slo.get("burn_rate") or 0.0)
        for watcher in watchers:
            try:
                watcher(burn)
            except Exception:  # noqa: BLE001 - watcher is advisory
                pass
        head = self.headroom(slo=slo, util=util, now=now)
        subs = self.subsystems(now)
        sources: Dict[str, Any] = {}
        with self._mtx:
            src_fns = list(self._sources.items())
        for name, fn in src_fns:
            try:
                sources[name] = fn()
            except Exception as exc:  # noqa: BLE001 - plane must render
                sources[name] = {"error": repr(exc)}
        m = self.metrics
        for label, d in util.items():
            m.device_utilization.with_labels(device=label).set(
                d["utilization"]
            )
        if fill["efficiency"] is not None:
            m.lane_fill_efficiency.set(fill["efficiency"])
        if slo["p50_ms"] is not None:
            m.slo_p50_ms.set(slo["p50_ms"])
        if slo["p99_ms"] is not None:
            m.slo_p99_ms.set(slo["p99_ms"])
        m.slo_burn_rate.set(slo["burn_rate"])
        m.slo_window_requests.set(slo["requests"])
        m.slo_headroom_sigs_per_sec.set(
            -1.0
            if head["headroom_sigs_per_sec"] is None
            else head["headroom_sigs_per_sec"]
        )
        return {
            "ts": time.time(),
            "window_s": self.window_s,
            "devices": util,
            "lane_fill": fill,
            "subsystems": subs,
            "slo": slo,
            "headroom": head,
            "sources": sources,
            "timeline": self.timeline(),
        }


# --------------------------------------------------------------------------
# Default (process-wide) hub — the deep-layer entry point, mirroring
# trace.default_tracer: the mesh chunk loop has no node to hand it a
# hub, so it reads the default. Unlike the tracer there is NO lazy
# construction: no node installed one means telemetry is off and the
# hot path pays a single attribute read.

_default: Optional[TelemetryHub] = None
_default_mtx = threading.Lock()


def default_hub() -> Optional[TelemetryHub]:
    return _default


def set_default_hub(hub: Optional[TelemetryHub]) -> Optional[TelemetryHub]:
    global _default
    with _default_mtx:
        prev, _default = _default, hub
    return prev
