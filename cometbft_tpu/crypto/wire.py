"""Wire ledger — continuous per-phase attribution of every live
device dispatch (ROADMAP item 1, "attack the wire", made measurable).

The bench anecdote this plane replaces: per 16k batch the kernel runs
~0.1 ms while host prepare takes ~15 ms and H2D transfer ~181 ms
(MAXCHUNK16K.jsonl) — yet until now the live path was blind to where
dispatch wall-time goes. The mesh chunk loop (crypto/tpu/mesh.py)
timestamps five phases on every chunk and feeds them here:

* ``pack``    — host chunk materialization + pow2 zero-pad;
* ``h2d``     — the explicit ``jax.device_put`` issue wall (on a
  blocking backend this is the transfer; on an async device plane it
  is the issue cost, with the remainder surfacing in d2h);
* ``compute`` — the kernel dispatch call (async backends: issue cost;
  the CPU fallback platform executes here);
* ``d2h``     — the retire wait (``np.asarray`` on the verdict mask
  blocks until the device finishes and the mask is copied back);
* ``demux``   — scheduler-side verdict demultiplex into rider futures
  (crypto/scheduler.py notes it at flush level).

compute and d2h split differently per backend; their SUM is the
device-side residency either way, and fetch + pack + h2d + compute +
d2h reconciles with the dispatch wall time (the ledger records coverage
= phase sum / wall per dispatch — the acceptance bound is within 10%).
``fetch`` is a launch's sixth number: what the stream's caller gathered
for the launch before it was packed (a commit's sign-bytes on the
resident path), 0 where the stream has no such callback.

A flush's whole life (PR 34). Those phases start at the first launch's
pack and end at the last retire; what lies in front of and behind them
is booked by a :class:`FlushRecord`, opened where a flush is born (the
scheduler's ``_dispatch``; ``verify_commit*`` on the resident path,
which has no scheduler), carried to whichever thread runs the stream
(``flush_scope`` / ``current_flush``, re-installed by the supervisor's
workers beside ``mesh.route_scope``) and closed where the last future
is set. It holds stamps on ``time.perf_counter_ns`` — each one the
reading a ``libs/trace.stage`` took anyway, handed on — and the stages'
own seconds, and closes into the same ``phase_seconds`` histogram
through ``note_phase`` and into a bounded deque (``flushes``):

* ``queue``    — the oldest rider's submit → ``_dispatch`` entry (the
  scheduler's own reading of the oldest wait);
* ``assemble``, ``route`` — the two scheduler stages' own seconds;
* ``lead``     — ``_verify`` entry (resident: ``verify_commit*``
  entry) → the first launch's kernel call returned: supervise, the
  thread hop, ``columns`` (its own phase, inside lead), the first
  launch's fetch, pack and issue;
* ``stream``   — the first launch's call returned → the last retire;
  the launches inside it stay booked as pack / h2d / compute / d2h;
* ``build_exposed`` — fetch + pack seconds of launches issued with no
  launch in flight: build time the device waited for;
* ``tail``     — the last retire → ``_verify`` returned (resident:
  ``verify_commit*`` returned, the tally included);
* ``demux``    — as before.

queue + assemble + route + lead + stream + tail + demux is the flush's
life, last future set less oldest submit, but for the statements
between two stages. Several streams under one record (a hedge's, a
retry's, per-domain shards) stamp the earliest issue and the latest
retire. A flush that never reached a launch (the floor kept it on the
host) closes with queue, assemble, route and demux alone. A thread that
is ``tracelib.in_background()`` (canary, probe, audit) opens no record
and books none of these: nobody waits for its work. There is no switch
but the ledger's own: no ledger, nothing booked.

Overlap accounting: under the double-buffered pipeline
(mesh.pipeline_depth) the host packs/transfers chunk N+1 while the
device still owes chunk N's verdict. Transfer time spent while ≥1
earlier chunk was in flight is HIDDEN — it costs no wall time.
Overlap efficiency = hidden transfer seconds / total transfer seconds
(1.0 = the pipe is fully saturated, 0 = every byte was paid serially).

The ledger maintains EWMA cost profiles keyed by (route, pow2 bucket,
device): per-phase p50/p99, bytes-on-wire per lane, effective link
bandwidth, and the pipeline overlap ratio. It registers as a
TelemetryHub source ("wire" in /debug/verify), exports the
``verify_wire_*`` metric family, and answers cost queries through
:class:`CostProfile` — the exact interface ROADMAP item 5b's learned
router consumes. Cold profiles are seeded from the persisted link
probe (tools/tpu_link_probe.py --merge → calibrate.load_link_profile).

Hot-path contract (bench_micro's wire section bounds it under 1%):
note_* methods are deque appends, EWMA folds, and counter bumps under
one short lock; all percentile math happens at snapshot time.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from cometbft_tpu.libs import trace as tracelib
from cometbft_tpu.libs.metrics import MICRO_BUCKETS, Registry

SUBSYSTEM = "verify_wire"

# Chunk-level phases (measured in the mesh dispatch loop). demux is the
# fifth phase, measured at flush level by the scheduler.
CHUNK_PHASES = ("pack", "h2d", "compute", "d2h")
PHASES = CHUNK_PHASES + ("demux",)
# What a FlushRecord holds when it closes and books, one observation a
# flush each (but demux: note_demux's, with its EWMA rows). Histogram
# only: no cost profile, prediction or price reads them.
FLUSH_PHASES = (
    "queue", "assemble", "route", "lead", "columns", "stream",
    "build_exposed", "tail", "demux",
)

DEFAULT_WINDOW = 64     # EWMA window (samples); alpha = 2 / (window + 1)
_MAX_SAMPLES = 512      # per-phase percentile retention per profile
_MAX_DISPATCHES = 128   # recent dispatch records kept for reconciliation
# ed25519 verify wire: 32 B pubkey + 64 B sig + 32 B SHA-512 digest per
# lane — the cold-boot bytes/lane guess before any chunk is observed.
# Holds for both the compact uint8 wire (128 rows × 1 B) and the legacy
# u32 word wire (32 rows × 4 B); the indexed key-store route (100
# B/lane) and the device-hash route (96 B + message block) diverge from
# it, which the live bytes_per_lane gauge then reflects.
DEFAULT_BYTES_PER_LANE = 128.0
# Per-route cold-boot bytes/lane where the wire format is known to
# diverge from the compact baseline: the indexed key-store route ships
# 96 B compact R ‖ S ‖ h plus a 4 B int32 table index, the device-hash
# route ships the 96 B rows without the precomputed digest. Used by
# the cold link-probe seed so a never-observed indexed candidate is
# priced with its real (smaller) transfer leg.
ROUTE_BYTES_PER_LANE = {
    "indexed": 100.0,
    "device_hash": 96.0,
    # verify-as-a-service row flushes: the socket payload IS the compact
    # wire (128 B/lane on the frame, re-used verbatim for device_put)
    "service": 128.0,
}


def wire_ledger_default(config_value: bool = True) -> bool:
    """Resolve the wire-ledger enable knob: an explicitly-set
    CBFT_WIRE_LEDGER env var wins over [instrumentation] wire_ledger."""
    raw = os.environ.get("CBFT_WIRE_LEDGER")
    if raw is not None:
        return raw.strip().lower() not in ("0", "false", "no", "off", "")
    return bool(config_value)


def wire_window_default(config_value: Optional[int] = None) -> int:
    """Resolve the EWMA window (samples): CBFT_WIRE_WINDOW env >
    [instrumentation] wire_window > DEFAULT_WINDOW."""
    raw = os.environ.get("CBFT_WIRE_WINDOW")
    if raw is not None:
        try:
            return max(1, int(raw))
        except ValueError:
            pass
    if config_value is not None:
        return max(1, int(config_value))
    return DEFAULT_WINDOW


class Metrics:
    """verify_wire_* export (libs/metrics.py instruments), wired into
    the node's Prometheus registry when [instrumentation] enables it.
    Phase latencies use MICRO_BUCKETS — the wire phases live at µs-to-ms
    scale on a healthy link."""

    def __init__(self, registry: Optional[Registry] = None):
        r = registry if registry is not None else Registry()
        self.phase_seconds = r.histogram(
            SUBSYSTEM, "phase_seconds",
            "Per-dispatch-phase wall seconds (fetch / pack / h2d / "
            "compute / d2h per chunk; queue / assemble / route / lead / "
            "columns / stream / build_exposed / tail / demux per "
            "flush), by phase and route.",
            buckets=MICRO_BUCKETS,
        )
        self.chunks = r.counter(
            SUBSYSTEM, "chunks",
            "Chunk dispatches attributed by the wire ledger, by route.",
        )
        self.dispatches = r.counter(
            SUBSYSTEM, "dispatches",
            "Whole batch dispatches attributed by the wire ledger, by "
            "route.",
        )
        self.bytes_on_wire = r.counter(
            SUBSYSTEM, "bytes",
            "Bytes shipped H2D by attributed dispatches (padded wire "
            "bytes), by device label.",
        )
        self.lanes = r.counter(
            SUBSYSTEM, "lanes",
            "Real signature lanes carried by attributed chunks, by "
            "route.",
        )
        self.padded_lanes = r.counter(
            SUBSYSTEM, "padded_lanes",
            "Lanes the attributed chunks were padded to (what the "
            "device ran, every shard's added up), by route; beside "
            "verify_wire_lanes it says what padding costs.",
        )
        self.overlap_ratio = r.gauge(
            SUBSYSTEM, "overlap_ratio",
            "Pipeline overlap efficiency of the latest attributed "
            "dispatch: hidden transfer seconds / total transfer "
            "seconds, by route (1.0 = transfer fully hidden behind "
            "compute).",
        )
        self.effective_mbps = r.gauge(
            SUBSYSTEM, "effective_mbps",
            "Effective H2D link bandwidth of the latest attributed "
            "chunk (wire bytes / h2d seconds, MB/s), by device label.",
        )
        self.coverage = r.gauge(
            SUBSYSTEM, "coverage",
            "Phase-sum / dispatch-wall reconciliation of the latest "
            "attributed dispatch, by route (1.0 = fetch, pack, h2d, "
            "compute and d2h account for the whole dispatch).",
        )
        self.bytes_per_lane = r.gauge(
            SUBSYSTEM, "bytes_per_lane",
            "Wire bytes per real signature lane of the latest "
            "attributed chunk, by route — the compact-format win "
            "(uint8 rows / indexed key store) reads directly off this "
            "gauge vs the 128 B/lane word-wire baseline.",
        )

    @classmethod
    def nop(cls) -> "Metrics":
        return cls(None)


def _percentile(sorted_vals: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile over an ascending list; None when empty."""
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def _pow2(n: int, floor: int = 1) -> int:
    size = max(1, int(floor))
    n = max(1, int(n))
    while size < n:
        size *= 2
    return size


class _Profile:
    """EWMA cost profile for one (route, bucket, device) key."""

    __slots__ = (
        "n", "ewma_s", "samples", "bytes_ewma", "lanes_ewma",
        "bw_ewma", "hidden_s", "h2d_s",
    )

    def __init__(self):
        self.n = 0
        self.ewma_s = {p: 0.0 for p in CHUNK_PHASES}
        self.samples = {
            p: deque(maxlen=_MAX_SAMPLES) for p in CHUNK_PHASES
        }
        self.bytes_ewma = 0.0   # padded wire bytes per chunk
        self.lanes_ewma = 0.0   # real lanes per chunk
        self.bw_ewma = 0.0      # MB/s over the h2d window
        self.hidden_s = 0.0     # cumulative hidden transfer seconds
        self.h2d_s = 0.0        # cumulative total transfer seconds

    def overlap(self) -> Optional[float]:
        if self.h2d_s <= 0.0:
            return None
        return max(0.0, min(1.0, self.hidden_s / self.h2d_s))

    def per_chunk_ms(self) -> float:
        return sum(self.ewma_s[p] for p in CHUNK_PHASES) * 1e3


class _DemuxStat:
    """EWMA + samples for the scheduler-side demux phase, keyed by
    (route, pow2 bucket of the flush)."""

    __slots__ = ("n", "ewma_s", "samples")

    def __init__(self):
        self.n = 0
        self.ewma_s = 0.0
        self.samples: deque = deque(maxlen=_MAX_SAMPLES)


# Every record's lock: a stamp comes once a stream and an add is three
# dictionary operations, so records do not wait for each other, and a
# flush every few milliseconds allocates no lock of its own.
_record_lock = threading.Lock()


class FlushRecord:
    """One flush's life on ``time.perf_counter_ns``: the stamps of its
    edges and the lexical seconds inside them (module docstring). Made
    by ``WireLedger.open_flush``; the thread that opened it closes it,
    any thread under its ``flush_scope`` may ``add`` seconds and stamp a
    stream, and nothing said after ``close`` is kept (a worker the
    watchdog abandoned). What a flush pays for it is on its verdict's
    path (the riders wake when the flush thread next blocks), so close
    computes three differences, observes the phases and keeps the record
    itself; ``entry()`` formats it when somebody asks."""

    __slots__ = (
        "_ledger", "_closed", "lanes", "route", "streams", "launches",
        "stream_lanes", "t_born_ns", "t_lead_ns", "t_issue_ns",
        "t_retire_ns", "t_done_ns", "t_close_ns", "seconds",
    )

    def __init__(self, ledger: "WireLedger", lanes: int, t_born_ns: int,
                 t_lead_ns: int, seconds: Dict[str, float]):
        self._ledger = ledger
        self._closed = False
        self.lanes = lanes
        self.route: Optional[str] = None  # of the first stream, if any
        self.streams = 0
        self.launches = 0
        self.stream_lanes = 0
        self.t_born_ns = t_born_ns
        self.t_lead_ns = t_lead_ns
        self.t_issue_ns: Optional[int] = None
        self.t_retire_ns: Optional[int] = None
        self.t_done_ns = self.t_close_ns = t_lead_ns
        self.seconds = seconds

    def add(self, phase: str, seconds: float) -> None:
        """``seconds`` more of a lexical phase (a stage's own reading)."""
        with _record_lock:
            if not self._closed:
                self.seconds[phase] = self.seconds.get(phase, 0.0) + seconds

    def note_issue(self, t_ns: int, route: str) -> None:
        """A stream's first launch was issued (its call returned) at
        ``t_ns``; the earliest of several streams stands."""
        with _record_lock:
            if self._closed:
                return
            self.streams += 1
            if self.route is None:
                self.route = route
            if self.t_issue_ns is None or t_ns < self.t_issue_ns:
                self.t_issue_ns = t_ns

    def note_retire(self, t_ns: int, launches: int, lanes: int) -> None:
        """A stream of ``launches`` over ``lanes`` real lanes retired its
        last launch at ``t_ns``; the latest of several streams stands."""
        with _record_lock:
            if self._closed:
                return
            self.launches += launches
            self.stream_lanes += lanes
            if self.t_retire_ns is None or t_ns > self.t_retire_ns:
                self.t_retire_ns = t_ns

    def close(self, route: Optional[str] = None,
              t_done_ns: Optional[int] = None,
              t_close_ns: Optional[int] = None,
              **seconds: float) -> Optional["FlushRecord"]:
        """Books the record's phases under ``route`` (left out: the
        route of the stream that ran; none ran: nothing is booked).
        ``t_done_ns`` is where the verify returned, ``t_close_ns`` where
        the last future was set: the demux stage's two readings, or one
        reading of the clock here for a caller that has no demux.
        ``seconds`` are last lexical phases (``demux=``). → the record,
        or None where nothing was booked."""
        with _record_lock:
            if self._closed:
                return None
            self._closed = True
        if route is None:
            route = self.route
            if route is None:
                return None
        if t_done_ns is None:
            t_done_ns = t_close_ns = time.perf_counter_ns()
        elif t_close_ns is None:
            t_close_ns = t_done_ns
        booked = self.seconds
        if seconds:
            booked.update(seconds)
        t_issue, t_retire = self.t_issue_ns, self.t_retire_ns
        if t_issue is not None:
            # a stream the answer did not wait for (a hedge the host
            # won) ends, for this flush, where the verify returned
            if t_retire is None or t_retire > t_done_ns:
                t_retire = t_done_ns
            if t_issue > t_retire:
                t_issue = t_retire
            booked["lead"] = max(0, t_issue - self.t_lead_ns) / 1e9
            booked["stream"] = (t_retire - t_issue) / 1e9
            booked["tail"] = (t_done_ns - t_retire) / 1e9
        self.route = route
        self.t_done_ns, self.t_close_ns = t_done_ns, t_close_ns
        self._ledger._note_flush(self)
        return self

    def entry(self) -> Dict[str, Any]:
        """The closed record as ``flushes`` serves it, in ms."""
        return {
            "route": self.route,
            # the riders' lanes; a record that has no riders (the
            # resident path's own): the lanes its streams carried
            "lanes": int(self.lanes or self.stream_lanes),
            "streams": self.streams,
            "launches": int(self.launches),
            "life_ms": round(
                max(0, self.t_close_ns - self.t_born_ns) / 1e6, 4
            ),
            "verify_ms": round(
                max(0, self.t_done_ns - self.t_lead_ns) / 1e6, 4
            ),
            "phases_ms": {
                ph: round(self.seconds[ph] * 1e3, 4)
                for ph in FLUSH_PHASES if ph in self.seconds
            },
        }


class WireLedger:
    """Continuous per-phase dispatch attribution with EWMA cost
    profiles keyed by (route, pow2 bucket, device). Thread-safe; the
    note_* feeders are the hot path, snapshot()/predict_ms() do the
    aggregation."""

    def __init__(
        self,
        metrics: Optional[Metrics] = None,
        window: Optional[int] = None,
        link: Optional[dict] = None,
    ):
        self.metrics = metrics if metrics is not None else Metrics.nop()
        self.window = max(1, int(window)) if window else DEFAULT_WINDOW
        self._alpha = 2.0 / (self.window + 1.0)
        self._lock = threading.Lock()
        self._profiles: Dict[Tuple[str, int, str], _Profile] = {}
        self._demux: Dict[Tuple[str, int], _DemuxStat] = {}
        self._recent: deque = deque(maxlen=_MAX_DISPATCHES)
        self._flushes: deque = deque(maxlen=self.window)
        self._phase_series: Dict[str, Dict[str, Any]] = {}
        self.chunks = 0
        self.n_dispatches = 0
        self.demux_notes = 0
        self.flush_notes = 0
        self._lanes: Dict[str, int] = {}
        self._padded: Dict[str, int] = {}
        self._link = dict(link) if link else None
        # the challenge call of a launch's pack (ed25519_batch
        # _challenge_scalars): calls, lanes by path, threads summed
        self.challenge_calls = 0
        self.challenge_threads = 0
        self._challenge_lanes: Dict[str, int] = {}
        # the lanes of each curve by where they ran ("device": a launch
        # booked by note_chunk; "host": a partition the tpu backend kept
        # on the host pool) and each curve's pack seconds
        self._curve_lanes: Dict[Tuple[str, str], int] = {}
        self._curve_pack_s: Dict[str, float] = {}

    # --- cold-boot link seed -------------------------------------------------

    def seed_link(self, probe: dict) -> None:
        """Install a measured link curve (tools/tpu_link_probe.py
        output shape) as the cold-boot prediction seed and the
        verify_top bandwidth ceiling."""
        if isinstance(probe, dict) and probe:
            with self._lock:
                self._link = dict(probe)

    def link(self) -> Optional[dict]:
        with self._lock:
            return dict(self._link) if self._link else None

    # --- hot-path feeders ----------------------------------------------------

    def note_chunk(
        self,
        route: str,
        device: str,
        bucket: int,
        lanes: int,
        wire_bytes: int,
        pack_s: float,
        h2d_s: float,
        compute_s: float,
        d2h_s: float,
        hidden_s: float = 0.0,
        padded_lanes: Optional[int] = None,
        fetch_s: float = 0.0,
        curve: str = "ed25519",
    ) -> None:
        """One chunk's phase attribution from the mesh dispatch loop.
        ``hidden_s`` is the portion of ``h2d_s`` spent while an earlier
        chunk was still in flight (paid no wall time). ``padded_lanes``
        is what the chunk was padded to over all its shards; left out,
        it is ``bucket`` (a loop that keys its profiles by the per-shard
        bucket says the total). ``fetch_s`` is what the stream's caller
        gathered for this launch before it was packed (launch_stream's
        ``fetch``): a histogram phase of its own, in no cost profile.
        ``curve`` is whose lanes the chunk carried: its lanes and pack
        seconds go to that curve's book (``curve_books``) as well."""
        a = self._alpha
        bucket = int(bucket)
        padded = max(0, int(bucket if padded_lanes is None else padded_lanes))
        phases = (
            ("pack", max(0.0, pack_s)),
            ("h2d", max(0.0, h2d_s)),
            ("compute", max(0.0, compute_s)),
            ("d2h", max(0.0, d2h_s)),
        )
        bw = 0.0
        if h2d_s > 0.0 and wire_bytes > 0:
            bw = wire_bytes / h2d_s / 1e6
        with self._lock:
            self.chunks += 1
            self._lanes[route] = self._lanes.get(route, 0) + max(
                0, int(lanes)
            )
            self._padded[route] = self._padded.get(route, 0) + padded
            key = (curve, "device")
            self._curve_lanes[key] = self._curve_lanes.get(key, 0) + max(
                0, int(lanes)
            )
            self._curve_pack_s[curve] = (
                self._curve_pack_s.get(curve, 0.0) + max(0.0, pack_s)
            )
            key = (route, bucket, device)
            p = self._profiles.get(key)
            if p is None:
                p = self._profiles[key] = _Profile()
            first = p.n == 0
            p.n += 1
            for name, v in phases:
                p.ewma_s[name] = (
                    v if first else p.ewma_s[name] + a * (v - p.ewma_s[name])
                )
                p.samples[name].append(v)
            p.bytes_ewma = (
                float(wire_bytes) if first
                else p.bytes_ewma + a * (wire_bytes - p.bytes_ewma)
            )
            p.lanes_ewma = (
                float(lanes) if first
                else p.lanes_ewma + a * (lanes - p.lanes_ewma)
            )
            if bw > 0.0:
                p.bw_ewma = (
                    bw if p.bw_ewma <= 0.0
                    else p.bw_ewma + a * (bw - p.bw_ewma)
                )
            p.hidden_s += max(0.0, min(hidden_s, h2d_s))
            p.h2d_s += max(0.0, h2d_s)
        m = self.metrics
        for name, v in phases:
            m.phase_seconds.with_labels(phase=name, route=route).observe(v)
        if fetch_s > 0.0:
            self.note_phase(route, "fetch", fetch_s)
        m.chunks.with_labels(route=route).add()
        m.lanes.with_labels(route=route).add(max(0, int(lanes)))
        m.padded_lanes.with_labels(route=route).add(padded)
        m.bytes_on_wire.with_labels(device=device).add(
            max(0, int(wire_bytes))
        )
        if bw > 0.0:
            m.effective_mbps.with_labels(device=device).set(round(bw, 2))
        if lanes > 0 and wire_bytes > 0:
            m.bytes_per_lane.with_labels(route=route).set(
                round(wire_bytes / lanes, 2)
            )

    def note_dispatch(
        self,
        route: str,
        device: str,
        n: int,
        wall_s: float,
        pack_s: float,
        h2d_s: float,
        compute_s: float,
        d2h_s: float,
        hidden_s: float,
        wire_bytes: int,
        chunks: int,
        fetch_s: float = 0.0,
    ) -> None:
        """One whole dispatch_batch/dispatch_sharded call: summed phase
        seconds vs the observed wall — the reconciliation record the
        acceptance bound (within 10%) is judged on. ``fetch_s`` (the
        stream's totals carry it) runs inside the wall, so it counts."""
        phase_s = fetch_s + pack_s + h2d_s + compute_s + d2h_s
        coverage = (phase_s / wall_s) if wall_s > 0.0 else None
        overlap = (
            max(0.0, min(1.0, hidden_s / h2d_s)) if h2d_s > 0.0 else None
        )
        rec = {
            "route": route,
            "device": device,
            "n": int(n),
            "chunks": int(chunks),
            "wall_ms": round(wall_s * 1e3, 3),
            "fetch_ms": round(fetch_s * 1e3, 3),
            "pack_ms": round(pack_s * 1e3, 3),
            "h2d_ms": round(h2d_s * 1e3, 3),
            "compute_ms": round(compute_s * 1e3, 3),
            "d2h_ms": round(d2h_s * 1e3, 3),
            "hidden_ms": round(hidden_s * 1e3, 3),
            "bytes": int(wire_bytes),
            "coverage": round(coverage, 4) if coverage is not None else None,
            "overlap": round(overlap, 4) if overlap is not None else None,
        }
        with self._lock:
            self.n_dispatches += 1
            self._recent.append(rec)
        m = self.metrics
        m.dispatches.with_labels(route=route).add()
        if overlap is not None:
            m.overlap_ratio.with_labels(route=route).set(round(overlap, 4))
        if coverage is not None:
            m.coverage.with_labels(route=route).set(round(coverage, 4))

    def note_demux(self, route: str, n_sigs: int, demux_s: float) -> None:
        """The scheduler's verdict-demux wall for one coalesced flush."""
        a = self._alpha
        bucket = _pow2(n_sigs)
        demux_s = max(0.0, demux_s)
        with self._lock:
            self.demux_notes += 1
            key = (route, bucket)
            d = self._demux.get(key)
            if d is None:
                d = self._demux[key] = _DemuxStat()
            d.ewma_s = (
                demux_s if d.n == 0 else d.ewma_s + a * (demux_s - d.ewma_s)
            )
            d.n += 1
            d.samples.append(demux_s)
        self.metrics.phase_seconds.with_labels(
            phase="demux", route=route
        ).observe(demux_s)

    def note_challenges(self, path: str, lanes: int, threads: int) -> None:
        """One challenge call of a pack: ``lanes`` hashed by ``path``
        (``native``: the C call; ``python``: the hashlib loop, where a
        launch is under the native gate or the library is missing or
        stale) on ``threads`` threads."""
        with self._lock:
            self.challenge_calls += 1
            self.challenge_threads += threads
            self._challenge_lanes[path] = (
                self._challenge_lanes.get(path, 0) + lanes
            )

    def note_host_lanes(self, curve: str, lanes: int) -> None:
        """``lanes`` of ``curve`` that the tpu backend verified on the host
        pool: a partition under its curve's routing floor."""
        key = (curve, "host")
        with self._lock:
            self._curve_lanes[key] = self._curve_lanes.get(key, 0) + lanes

    def curve_books(self) -> Dict[str, Dict[str, float]]:
        """{curve: {"device_lanes", "host_lanes", "pack_s"}}, monotonic."""
        with self._lock:
            lanes = dict(self._curve_lanes)
            pack = dict(self._curve_pack_s)
        out: Dict[str, Dict[str, float]] = {}
        for (curve, where), n in lanes.items():
            book = out.setdefault(curve, {
                "device_lanes": 0, "host_lanes": 0, "pack_s": 0.0,
            })
            book[where + "_lanes"] = n
        for curve, secs in pack.items():
            out.setdefault(curve, {
                "device_lanes": 0, "host_lanes": 0, "pack_s": 0.0,
            })["pack_s"] = secs
        return out

    def _series_of(self, route: str) -> Dict[str, Any]:
        """{phase: its ``phase_seconds`` series} for ``route``, made once
        (a series nothing was observed in is not exposed)."""
        series = self._phase_series.get(route)
        if series is None:
            series = self._phase_series[route] = {
                phase: self.metrics.phase_seconds.with_labels(
                    phase=phase, route=route
                )
                for phase in FLUSH_PHASES + ("fetch",)
            }
        return series

    def note_phase(self, route: str, phase: str, seconds: float) -> None:
        """``seconds`` of a flush phase or of ``fetch`` into
        ``phase_seconds{phase, route}``: the histogram and nothing else."""
        self._series_of(route)[phase].observe(max(0.0, seconds))

    def open_flush(
        self,
        lanes: int = 0,
        t_born_ns: Optional[int] = None,
        t_lead_ns: Optional[int] = None,
        **seconds: float,
    ) -> Optional[FlushRecord]:
        """A record for the flush that is being dispatched on this
        thread, or None on a background thread (canary, probe, audit:
        nobody waits for it). ``t_lead_ns`` is where the verify starts
        (left out: the clock is read here), ``t_born_ns`` where the
        flush's life does: the oldest rider's submit (left out: where
        the verify starts; no queue, the resident path). ``seconds`` are
        the lexical phases that are over already (``queue=``,
        ``assemble=``, ``route=``)."""
        if tracelib.in_background():
            return None
        if t_lead_ns is None:
            t_lead_ns = time.perf_counter_ns()
        return FlushRecord(
            self, lanes, t_lead_ns if t_born_ns is None else t_born_ns,
            t_lead_ns, seconds,
        )

    def _note_flush(self, rec: FlushRecord) -> None:
        series = self._series_of(rec.route)
        for phase, seconds in rec.seconds.items():
            # demux is in the record and booked by note_demux, which the
            # scheduler calls as it always did
            if phase != "demux":
                series[phase].observe(seconds)
        with self._lock:
            self.flush_notes += 1
            self._flushes.append(rec)

    def flushes(self) -> List[dict]:
        """The last ``window`` closed flush records, oldest first: what
        ``snapshot()`` serves under ``flushes`` and an incident dump
        carries."""
        with self._lock:
            recs = list(self._flushes)
        return [rec.entry() for rec in recs]

    # --- cost queries --------------------------------------------------------

    def predict_ms(
        self, route: str, bucket: int, device: Optional[str] = None
    ) -> Optional[float]:
        """Predicted wall ms for a hypothetical dispatch of ``bucket``
        lanes on ``route`` — warm profiles first (exact bucket, then
        the nearest measured bucket scaled around the link's fixed
        latency), then the cold link-probe seed; None when neither
        exists. This is the CostProfile interface the learned router
        (ROADMAP item 5b) consumes.

        Pinned edge behavior (the decision plane queries this for
        every candidate on every flush, so it must NEVER raise):
        an unknown route or a cold ledger falls down the ladder to the
        link-probe seed, then None; a bucket below the smallest
        observed scales only the size-dependent part down (never below
        the link's fixed latency, never negative); a malformed bucket
        (None, non-numeric) answers None."""
        try:
            bucket = _pow2(bucket)
        except (TypeError, ValueError):
            return None
        with self._lock:
            cands = [
                (k[1], p) for k, p in self._profiles.items()
                if k[0] == route and p.n > 0
                and (device is None or k[2] == device)
            ]
            link = dict(self._link) if self._link else {}
        if cands:
            exact = [(b, p) for b, p in cands if b == bucket]
            if exact:
                # multiple devices at this bucket: trust the most seen
                _, p = max(exact, key=lambda bp: bp[1].n)
                return p.per_chunk_ms()
            # nearest measured bucket in log space, best-observed first
            b0, p = min(
                cands,
                key=lambda bp: (abs(bp[0].bit_length() - bucket.bit_length()),
                                -bp[1].n),
            )
            per_chunk = p.per_chunk_ms()
            fixed = min(self._link_fixed_ms_from(link), per_chunk)
            if bucket <= b0:
                # scale only the size-dependent part down
                return fixed + (per_chunk - fixed) * (bucket / b0)
            # bigger than any measured chunk: the dispatcher would split
            # into ceil(bucket / b0) chunks; pipelining hides the
            # observed overlap fraction of each follow-up chunk's
            # transfer
            n_chunks = -(-bucket // b0)
            hidden_ms = (p.overlap() or 0.0) * p.ewma_s["h2d"] * 1e3
            return max(
                per_chunk,
                per_chunk * n_chunks - hidden_ms * (n_chunks - 1),
            )
        # cold: the probed link curve
        if link:
            try:
                mbps = float(link.get("effective_MBps", 0.0))
            except (TypeError, ValueError):
                mbps = 0.0
            fixed = self._link_fixed_ms_from(link)
            if mbps > 0.0 or fixed > 0.0:
                bpl = ROUTE_BYTES_PER_LANE.get(route, DEFAULT_BYTES_PER_LANE)
                xfer = (
                    bucket * bpl / (mbps * 1e6) * 1e3
                    if mbps > 0.0 else 0.0
                )
                return fixed + xfer
        return None

    @staticmethod
    def _link_fixed_ms_from(link: dict) -> float:
        fixed = 0.0
        for k in ("fixed_latency_ms_est", "kernel_roundtrip_ms"):
            try:
                fixed += float(link.get(k, 0.0))
            except (TypeError, ValueError):
                pass
        return fixed

    def observations(
        self, route: str, bucket: int, device: Optional[str] = None
    ) -> int:
        """How many chunks back the (route, bucket) profile — the ≥5
        warm-up bound callers gate predictions on."""
        bucket = _pow2(bucket)
        with self._lock:
            return sum(
                p.n for k, p in self._profiles.items()
                if k[0] == route and k[1] == bucket
                and (device is None or k[2] == device)
            )

    def lanes_by_route(self) -> Dict[str, int]:
        """Real signature lanes that reached the device, per wire route
        — every chunk any dispatch loop attributed. The decision
        ledger's per-route lanes reconcile against this."""
        with self._lock:
            return dict(self._lanes)

    def padded_lanes_by_route(self) -> Dict[str, int]:
        """Lanes the device ran for them, padding included, per wire
        route: lanes_by_route's counterpart."""
        with self._lock:
            return dict(self._padded)

    def bytes_per_lane(self, route: str) -> Optional[float]:
        """Steady-state wire bytes per real signature lane for
        ``route`` — the EWMA over every attributed chunk, weighted
        toward the best-observed profile. None until the route has
        been observed. The bench routing stage and the indexed-route
        acceptance check (≤ 100 B/lane) read this."""
        with self._lock:
            cands = [
                p for k, p in self._profiles.items()
                if k[0] == route and p.n > 0 and p.lanes_ewma > 0.0
            ]
            if not cands:
                return None
            p = max(cands, key=lambda p: p.n)
            return p.bytes_ewma / p.lanes_ewma

    def cost_profile(self) -> "CostProfile":
        return CostProfile(self)

    # --- snapshot (TelemetryHub source "wire") -------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """The /debug/verify wire section: per-(route, bucket, device)
        phase EWMAs + p50/p99, bytes/lane, effective bandwidth, overlap
        ratio, demux stats, the probed link ceiling, the most recent
        dispatch reconciliation records, and the last ``window`` flush
        records (a flush's life by phase, ms)."""
        with self._lock:
            profiles = [
                (k, p.n, dict(p.ewma_s),
                 {ph: sorted(p.samples[ph]) for ph in CHUNK_PHASES},
                 p.bytes_ewma, p.lanes_ewma, p.bw_ewma, p.overlap())
                for k, p in self._profiles.items()
            ]
            demux = [
                (k, d.n, d.ewma_s, sorted(d.samples))
                for k, d in self._demux.items()
            ]
            recent = list(self._recent)[-8:]
            link = dict(self._link) if self._link else None
            counters = (self.chunks, self.n_dispatches, self.demux_notes)
            challenges = (self.challenge_calls, dict(self._challenge_lanes),
                          self.challenge_threads)
        prof_rows = []
        for (route, bucket, device), n, ewma, samples, b_ewma, l_ewma, \
                bw, overlap in sorted(profiles, key=lambda t: t[0]):
            phases_ms = {}
            for ph in CHUNK_PHASES:
                vals = samples[ph]
                phases_ms[ph] = {
                    "ewma": round(ewma[ph] * 1e3, 3),
                    "p50": round((_percentile(vals, 0.50) or 0.0) * 1e3, 3),
                    "p99": round((_percentile(vals, 0.99) or 0.0) * 1e3, 3),
                }
            bpl = (b_ewma / l_ewma) if l_ewma > 0 else None
            prof_rows.append({
                "route": route,
                "bucket": bucket,
                "device": device,
                "n": n,
                "phases_ms": phases_ms,
                "bytes_per_lane": round(bpl, 1) if bpl else None,
                "effective_MBps": round(bw, 2) if bw > 0 else None,
                "overlap": round(overlap, 4) if overlap is not None else None,
                "predicted_ms": (
                    round(pred, 3) if (pred := self.predict_ms(
                        route, bucket, device
                    )) is not None else None
                ),
            })
        demux_rows = [
            {
                "route": route,
                "bucket": bucket,
                "n": n,
                "ewma_ms": round(ewma * 1e3, 4),
                "p50_ms": round((_percentile(vals, 0.50) or 0.0) * 1e3, 4),
                "p99_ms": round((_percentile(vals, 0.99) or 0.0) * 1e3, 4),
            }
            for (route, bucket), n, ewma, vals in sorted(
                demux, key=lambda t: t[0]
            )
        ]
        return {
            "window": self.window,
            "chunks": counters[0],
            "lanes": self.lanes_by_route(),
            "padded_lanes": self.padded_lanes_by_route(),
            "dispatches": counters[1],
            "demux_notes": counters[2],
            "link": link,
            "profiles": prof_rows,
            "demux": demux_rows,
            "recent": recent,
            "flush_notes": self.flush_notes,
            "flushes": self.flushes(),
            "challenge_calls": challenges[0],
            "challenge_lanes": challenges[1],
            "challenge_threads": challenges[2],
            "curves": self.curve_books(),
        }


class CostProfile:
    """Queryable dispatch-cost prediction over a WireLedger — the
    interface the learned cost-model router (ROADMAP item 5b) will
    consume. predict_ms answers for a hypothetical (route, pow2
    bucket); observations() reports how warm that key is."""

    def __init__(self, ledger: WireLedger):
        self._ledger = ledger

    def predict_ms(
        self, route: str, bucket: int, device: Optional[str] = None
    ) -> Optional[float]:
        return self._ledger.predict_ms(route, bucket, device=device)

    def observations(
        self, route: str, bucket: int, device: Optional[str] = None
    ) -> int:
        return self._ledger.observations(route, bucket, device=device)


# --- process default ---------------------------------------------------------
# Installed by node start (gated by [instrumentation] wire_ledger /
# CBFT_WIRE_LEDGER); the mesh dispatch loop and the scheduler consult
# it with one attribute read, same pattern as telemetry.default_hub.

_default_mtx = threading.Lock()
_default_ledger: Optional[WireLedger] = None


def default_ledger() -> Optional[WireLedger]:
    """The process-default wire ledger, or None (attribution off)."""
    return _default_ledger


def set_default_ledger(
    ledger: Optional[WireLedger],
) -> Optional[WireLedger]:
    """Install ``ledger`` as the process default; returns the previous
    default so callers can restore it (tests, benches)."""
    global _default_ledger
    with _default_mtx:
        prev = _default_ledger
        _default_ledger = ledger
        return prev


# --- the flush a thread is working for ---------------------------------------
# The scheduler's flush thread installs its record around the verify; a
# thread that takes the work over (the supervisor's dispatch worker, a
# per-domain shard) re-installs what its spawner had, the pattern of
# mesh.route_scope and decisions.use. The mesh's stream and the
# verifier's columns pass read it with one attribute lookup.

_flush_local = threading.local()


def current_flush() -> Optional[FlushRecord]:
    """The flush record THIS thread works for, if any; none while the
    thread's work is background (the one place the rule is kept: a
    worker that re-applies its spawner's ``tracelib.background`` reads
    None here whatever it was handed)."""
    if tracelib.in_background():
        return None
    return getattr(_flush_local, "rec", None)


class flush_scope:
    """Context manager installing ``rec`` (None: explicitly none) as
    this thread's flush record; nests."""

    __slots__ = ("_rec", "_prev")

    def __init__(self, rec: Optional[FlushRecord]):
        self._rec = rec
        self._prev = None

    def __enter__(self) -> Optional[FlushRecord]:
        self._prev = getattr(_flush_local, "rec", None)
        _flush_local.rec = self._rec
        return self._rec

    def __exit__(self, *exc_info) -> bool:
        _flush_local.rec = self._prev
        return False


def open_flush(*stamps: Any, **seconds: float) -> Optional[FlushRecord]:
    """The process-default ledger's ``open_flush``; None without a
    ledger or on a background thread."""
    ledger = _default_ledger
    if ledger is None:
        return None
    return ledger.open_flush(*stamps, **seconds)


def add_phase(phase: str, seconds: float) -> None:
    """``seconds`` of a lexical phase to this thread's flush record;
    nothing where there is none."""
    rec = current_flush()
    if rec is not None:
        rec.add(phase, seconds)


class own_flush:
    """For an entry point that may run with no scheduler above it
    (``verify_commit*``): opens a record where the thread has none and
    closes it under the route of the stream that ran inside; a call that
    reached no stream (a commit under the floor, a commit that went
    through the scheduler, whose flush thread keeps its own record)
    books nothing."""

    __slots__ = ("_rec",)

    def __init__(self) -> None:
        self._rec: Optional[FlushRecord] = None

    def __enter__(self) -> Optional[FlushRecord]:
        if current_flush() is None:
            rec = self._rec = open_flush()
            if rec is not None:
                _flush_local.rec = rec
        return self._rec

    def __exit__(self, *exc_info) -> bool:
        rec = self._rec
        if rec is not None:
            _flush_local.rec = None
            rec.close()
        return False


def seed_from_calibration(ledger: Optional[WireLedger] = None) -> bool:
    """Seed ``ledger`` (default: the process default) with the link
    curve persisted by ``tools/tpu_link_probe.py --merge``
    (calibrate.load_link_profile). → True when a curve was installed."""
    target = ledger if ledger is not None else default_ledger()
    if target is None:
        return False
    try:
        from cometbft_tpu.crypto.tpu import calibrate

        profile = calibrate.load_link_profile()
    except Exception:  # noqa: BLE001 - seeding is best-effort
        return False
    if not profile:
        return False
    target.seed_link(profile)
    return True
