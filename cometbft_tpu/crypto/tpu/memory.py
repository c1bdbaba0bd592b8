"""Device-memory plane — HBM observability + the PROACTIVE chunk guard.

The degradation ladder's OOM rung (crypto/supervisor.py) is reactive: a
RESOURCE_EXHAUSTED must first cost a dispatch before the chunk cap
shrinks. Yet the footprint is predictable — a 16384-lane ed25519 chunk's
Straus tables are ~70 MB (crypto/tpu/ed25519_batch.py), linear in the
lane count — so the right time to shrink is BEFORE the allocator fails,
the way the FPGA-ECDSA engine literature sizes its batch engine from a
static per-batch resource model (PAPERS.md, arXiv:2112.02229).

This module is the third observability plane (after PR 4 traces and
PR 8 telemetry): **memory + footprint model + pre-dispatch guard**.

* ``MemoryPlane`` polls each fault domain's ``device.memory_stats()``
  (bytes_in_use / peak / limit). Backends without stats — the CPU
  platform, virtual test domains — degrade to MODEL-ONLY mode: the
  modeled limit (``CBFT_MEM_LIMIT_BYTES``, default 16 GiB of HBM) and a
  zero in-use floor stand in, so the guard math still runs everywhere
  and tests can drive it by shrinking the modeled limit.

* A per-(kernel, pow2-bucket) **footprint model** seeded from the
  static Straus estimate (~4480 bytes/lane) and corrected by observed
  allocation peaks (EWMA) — persisted across runs through the
  calibration table (crypto/tpu/calibrate.py ``memory`` section).

* ``refresh_guard`` is the pre-dispatch guard: projected footprint
  (modeled bytes/lane × padded lanes × pipeline depth) above the free
  headroom (limit × headroom_fraction − in_use) halves the effective
  chunk cap BEFORE dispatch, clamped onto the device handle
  (topology.DeviceHandle.set_memory_guard_cap) so every cap consumer —
  the mesh chunk loop, the supervisor's capacity snapshot, fault
  injection — sees the guarded value. The reactive OOM rung stays as
  the last resort.

Everything is observable: ``verify_memory_*`` metrics (per-device
bytes gauges, guard caps, shrink/poll counters) and a TelemetryHub
snapshot source so /debug/verify and tools/verify_top.py show memory
pressure next to duty cycle.

Polling is LAZY and rate-limited (``[instrumentation] mem_poll_ms``,
env ``CBFT_MEM_POLL_MS``): there is no background thread — stats are
read at most once per poll window, on access, from whichever dispatch
or scheduler thread touches the plane first. Off the poll edge the
plane is one monotonic-clock compare, which is what keeps the measured
scheduler overhead under the bench_micro 1% bound.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

from cometbft_tpu.libs.metrics import Registry

SUBSYSTEM = "verify_memory"

DEFAULT_POLL_MS = 500
DEFAULT_HEADROOM_FRACTION = 0.9
# the static seed: a 16384-lane ed25519 chunk's Straus tables are ~70 MB
# (crypto/tpu/ed25519_batch.py) → ~4480 bytes per lane
STRAUS_BYTES_16384 = 70 * 1024 * 1024
SEED_BYTES_PER_LANE = STRAUS_BYTES_16384 / 16384.0
# model-only fallback limit: one TPU v2/v3 core's HBM
DEFAULT_MODEL_LIMIT_BYTES = 16 * 1024 ** 3

_EWMA_ALPHA = 0.2


def mem_poll_ms_default(config_value: Optional[int] = None) -> int:
    """[instrumentation] mem_poll_ms resolution: CBFT_MEM_POLL_MS env >
    config > 500 ms."""
    raw = os.environ.get("CBFT_MEM_POLL_MS")
    if raw is not None:
        return int(raw)
    if config_value is not None:
        return int(config_value)
    return DEFAULT_POLL_MS


def headroom_fraction_default() -> float:
    """Fraction of the device limit the guard is allowed to plan into
    (CBFT_MEM_HEADROOM, default 0.9 — the last 10% is the allocator's
    fragmentation slack)."""
    raw = os.environ.get("CBFT_MEM_HEADROOM")
    if raw is not None:
        return float(raw)
    return DEFAULT_HEADROOM_FRACTION


def model_limit_bytes_default() -> int:
    """The per-device byte limit assumed in model-only mode
    (CBFT_MEM_LIMIT_BYTES, default 16 GiB). Tests and chaos harnesses
    shrink this to drive the guard without real device stats."""
    raw = os.environ.get("CBFT_MEM_LIMIT_BYTES")
    if raw is not None:
        return int(raw)
    return DEFAULT_MODEL_LIMIT_BYTES


def _pow2_bucket(n: int, floor: int = 1) -> int:
    size = max(1, int(floor))
    while size < n:
        size *= 2
    return size


class Metrics:
    """Memory-plane observability (libs/metrics.py instruments),
    exported as verify_memory_* through the node's registry."""

    def __init__(self, registry: Optional[Registry] = None):
        r = registry if registry is not None else Registry()
        self.bytes_in_use = r.gauge(
            SUBSYSTEM, "bytes_in_use",
            "Device bytes currently allocated, by device (model-only "
            "domains report 0).",
        )
        self.bytes_peak = r.gauge(
            SUBSYSTEM, "bytes_peak",
            "Peak device bytes observed since the last peak reset, by "
            "device.",
        )
        self.bytes_limit = r.gauge(
            SUBSYSTEM, "bytes_limit",
            "Device byte capacity, by device (the modeled limit when the "
            "backend exposes no memory stats).",
        )
        self.headroom_bytes = r.gauge(
            SUBSYSTEM, "headroom_bytes",
            "Free bytes the pre-dispatch guard may plan into: "
            "limit x headroom_fraction - bytes_in_use, by device.",
        )
        self.guard_cap = r.gauge(
            SUBSYSTEM, "guard_cap",
            "Chunk cap imposed by the pre-dispatch memory guard, by "
            "device (0 = unconstrained).",
        )
        self.guard_shrinks = r.counter(
            SUBSYSTEM, "guard_shrinks",
            "Pre-dispatch chunk-cap halvings because projected footprint "
            "exceeded free headroom, by device — each one is an OOM that "
            "never happened.",
        )
        self.polls = r.counter(
            SUBSYSTEM, "polls",
            "Device memory_stats() polls (rate-limited by mem_poll_ms).",
        )
        self.model_updates = r.counter(
            SUBSYSTEM, "model_updates",
            "Footprint-model EWMA corrections from observed allocation "
            "peaks.",
        )

    @classmethod
    def nop(cls) -> "Metrics":
        return cls(None)


class MemoryPlane:
    """Per-device HBM stats + calibrated footprint model + the
    pre-dispatch chunk guard. Thread-safe; all hot-path entries are a
    clock compare unless the poll window elapsed."""

    def __init__(
        self,
        topology=None,
        poll_ms: Optional[int] = None,
        headroom_fraction: Optional[float] = None,
        model_limit_bytes: Optional[int] = None,
        metrics: Optional[Metrics] = None,
        stats: Optional[bool] = None,
    ):
        if topology is None:
            from cometbft_tpu.crypto.tpu import topology as topolib

            topology = topolib.default_topology()
        self.topology = topology
        self._poll_s = max(1, mem_poll_ms_default(poll_ms)) / 1e3
        self._headroom = (
            headroom_fraction if headroom_fraction is not None
            else headroom_fraction_default()
        )
        self._model_limit = (
            int(model_limit_bytes) if model_limit_bytes is not None
            else model_limit_bytes_default()
        )
        self.metrics = metrics if metrics is not None else Metrics.nop()
        # stats: None = try the jax device plane once, fall back to
        # model-only on any failure; False = model-only from the start
        # (unit tests, CPU nodes — no jax import ever happens).
        self._stats_enabled = stats is not False
        self._lock = threading.Lock()
        self._last_poll = 0.0
        # label -> {"bytes_in_use", "bytes_peak", "bytes_limit", "mode"}
        self._devices: Dict[str, Dict[str, object]] = {}
        # kernel -> pow2 bucket -> EWMA bytes per lane
        self._model: Dict[str, Dict[int, float]] = {}
        self._model_dirty = False
        self._seed_from_calibration()

    # -- footprint model -----------------------------------------------------

    def _seed_from_calibration(self) -> None:
        """Warm-start the footprint model from the calibration table's
        ``memory`` section (crypto/tpu/calibrate.py) when one exists —
        a restarted node keeps what earlier runs learned."""
        try:
            from cometbft_tpu.crypto.tpu import calibrate

            stored = calibrate.load_memory_footprints()
        except Exception:  # noqa: BLE001 - seeding is best-effort
            return
        for kernel, buckets in (stored or {}).items():
            dst = self._model.setdefault(kernel, {})
            for bucket, bpl in buckets.items():
                try:
                    dst[int(bucket)] = float(bpl)
                except (TypeError, ValueError):
                    continue

    def bytes_per_lane(self, kernel: str, lanes: int) -> float:
        """Modeled footprint per lane for a ``lanes``-wide padded chunk
        of ``kernel`` — the calibrated EWMA when the bucket (or any
        neighbor) is warm, else the static Straus seed."""
        bucket = _pow2_bucket(lanes)
        with self._lock:
            buckets = self._model.get(kernel)
            if buckets:
                if bucket in buckets:
                    return buckets[bucket]
                key = min(buckets, key=lambda k: abs(k - bucket))
                return buckets[key]
        return SEED_BYTES_PER_LANE

    def projected_bytes(self, kernel: str, chunk_cap: int) -> int:
        """Projected allocation for one dispatch at ``chunk_cap``:
        modeled bytes/lane × padded lanes × pipeline depth (that many
        chunks are in flight at once, mesh.pipeline_depth)."""
        from cometbft_tpu.crypto.tpu import mesh

        bucket = _pow2_bucket(chunk_cap)
        try:
            depth = mesh.pipeline_depth()
        except ValueError:
            depth = 2
        return int(self.bytes_per_lane(kernel, bucket) * bucket * depth)

    def observe_footprint(
        self, kernel: str, lanes: int, observed_bytes: int
    ) -> None:
        """Fold one observed allocation peak delta into the model:
        EWMA-correct the (kernel, bucket) bytes/lane toward
        ``observed_bytes / lanes``. Non-positive observations are
        ignored (a poll raced the allocator's release)."""
        if lanes <= 0 or observed_bytes <= 0:
            return
        bucket = _pow2_bucket(lanes)
        bpl = observed_bytes / float(bucket)
        with self._lock:
            buckets = self._model.setdefault(kernel, {})
            prev = buckets.get(bucket)
            if prev is None:
                buckets[bucket] = bpl
            else:
                buckets[bucket] = prev + _EWMA_ALPHA * (bpl - prev)
            self._model_dirty = True
        self.metrics.model_updates.add()

    def export_footprints(self) -> Dict[str, Dict[int, float]]:
        """The learned model, for calibration-table persistence
        (calibrate.merge_memory_footprints). Empty when nothing was
        observed beyond the static seed."""
        with self._lock:
            if not self._model_dirty:
                return {}
            return {k: dict(v) for k, v in self._model.items()}

    # -- device stats --------------------------------------------------------

    def _read_device_stats(self, handle) -> Optional[Dict[str, int]]:
        """One device's memory_stats(), or None when the backend (or
        this handle) has none. The first hard failure disables the
        stats path for good — model-only from then on."""
        if not self._stats_enabled:
            return None
        try:
            from cometbft_tpu.crypto.tpu import mesh

            # only devices this process already holds: polling must
            # never be what takes the accelerator (one process per chip)
            devs = mesh.live_devices()
            if devs is None or handle.index >= len(devs):
                return None  # not dispatching yet / virtual domain
            stats = devs[handle.index].memory_stats()
        except Exception:  # noqa: BLE001 - no backend / no stats support
            self._stats_enabled = False
            return None
        if not stats:
            return None
        in_use = stats.get("bytes_in_use")
        if in_use is None:
            return None
        return {
            "bytes_in_use": int(in_use),
            "bytes_peak": int(
                stats.get("peak_bytes_in_use", in_use)
            ),
            "bytes_limit": int(
                stats.get("bytes_limit", self._model_limit)
            ),
        }

    def poll(self, force: bool = False) -> None:
        """Refresh every device's memory view, at most once per poll
        window (``force`` bypasses the limiter). Cheap when the window
        has not elapsed: one clock read + one compare."""
        now = time.monotonic()
        with self._lock:
            if not force and now - self._last_poll < self._poll_s:
                return
            self._last_poll = now
        self.metrics.polls.add()
        for handle in self.topology:
            stats = self._read_device_stats(handle)
            if stats is None:
                doc = {
                    "mode": "model",
                    "bytes_in_use": 0,
                    "bytes_peak": 0,
                    "bytes_limit": self._model_limit,
                }
            else:
                doc = {"mode": "device", **stats}
            with self._lock:
                self._devices[handle.label] = doc
            m = self.metrics
            lbl = handle.label
            m.bytes_in_use.with_labels(device=lbl).set(doc["bytes_in_use"])
            m.bytes_peak.with_labels(device=lbl).set(doc["bytes_peak"])
            m.bytes_limit.with_labels(device=lbl).set(doc["bytes_limit"])
            m.headroom_bytes.with_labels(device=lbl).set(
                self._free_bytes(doc)
            )

    def _free_bytes(self, doc: Dict[str, object]) -> int:
        limit = int(doc.get("bytes_limit", self._model_limit))
        in_use = int(doc.get("bytes_in_use", 0))
        return max(0, int(limit * self._headroom) - in_use)

    def device_view(self, handle) -> Dict[str, object]:
        """This device's current memory doc (polling as needed)."""
        self.poll()
        with self._lock:
            doc = self._devices.get(handle.label)
        if doc is None:
            doc = {
                "mode": "model",
                "bytes_in_use": 0,
                "bytes_peak": 0,
                "bytes_limit": self._model_limit,
            }
        return doc

    def free_headroom_bytes(self, handle) -> int:
        """Bytes the guard may plan into on this device right now."""
        return self._free_bytes(self.device_view(handle))

    # -- the pre-dispatch guard ----------------------------------------------

    def refresh_guard(
        self, handle, default_cap: int, min_pad: int,
        kernel: str = "ed25519", launch: Optional[int] = None,
    ) -> int:
        """The proactive rung: recompute this device's memory-guard
        chunk cap from fresh(ish) stats and the footprint model, clamp
        it onto the handle (DeviceHandle.set_memory_guard_cap) so every
        cap consumer sees it, and return the guarded cap. Halves until
        the projected footprint fits free headroom, floored at
        ``min_pad`` — at the floor the dispatch proceeds and the
        reactive OOM rung remains the backstop. ``launch`` is the
        dispatch's launch size where it has one below the cap
        (mesh.dispatch_batch): the projection starts from the largest
        launch the dispatch can issue, not from a cap it never reaches."""
        from cometbft_tpu.crypto.tpu import mesh

        try:
            base = max(
                min_pad,
                mesh.resolve_chunk_cap(default_cap, min_pad)
                >> handle.chunk_shrink_levels(),
            )
        except ValueError:
            # malformed CBFT_TPU_MAX_CHUNK surfaces at dispatch, not here
            handle.set_memory_guard_cap(None)
            return default_cap
        if launch is not None:
            base = min(base, max(min_pad, launch))
        free = self.free_headroom_bytes(handle)
        cap = base
        while cap > min_pad and self.projected_bytes(kernel, cap) > free:
            cap >>= 1
        cap = max(cap, min_pad)
        lbl = handle.label
        if cap < base:
            self.metrics.guard_shrinks.with_labels(device=lbl).add(
                (base // max(1, cap)).bit_length() - 1
            )
            self.metrics.guard_cap.with_labels(device=lbl).set(cap)
            handle.set_memory_guard_cap(cap)
        else:
            self.metrics.guard_cap.with_labels(device=lbl).set(0)
            handle.set_memory_guard_cap(None)
        return cap

    def observe_dispatch(
        self, handle, kernel: str, lanes: int,
        baseline_in_use: Optional[int] = None,
    ) -> None:
        """Post-dispatch model correction: compare the device's peak
        against the pre-dispatch baseline and fold the delta into the
        footprint model. No stats → no correction (the static seed
        stands)."""
        stats = self._read_device_stats(handle)
        if stats is None:
            return
        base = baseline_in_use
        if base is None:
            with self._lock:
                prev = self._devices.get(handle.label)
            base = int(prev.get("bytes_in_use", 0)) if prev else 0
        self.observe_footprint(
            kernel, lanes, int(stats["bytes_peak"]) - int(base)
        )

    # -- snapshot (TelemetryHub source) --------------------------------------

    def snapshot(self) -> Dict[str, object]:
        """JSON-ready memory picture for /debug/verify (registered as
        the hub's ``memory`` source) and the flight-recorder dump."""
        self.poll()
        with self._lock:
            devices = {
                lbl: dict(doc) for lbl, doc in self._devices.items()
            }
            model = {
                kernel: {
                    str(bucket): round(bpl, 1)
                    for bucket, bpl in sorted(buckets.items())
                }
                for kernel, buckets in self._model.items()
            }
        for handle in self.topology:
            doc = devices.setdefault(handle.label, {
                "mode": "model",
                "bytes_in_use": 0,
                "bytes_peak": 0,
                "bytes_limit": self._model_limit,
            })
            doc["headroom_bytes"] = self._free_bytes(doc)
            doc["guard_cap"] = handle.memory_guard_cap()
        return {
            "poll_ms": int(self._poll_s * 1e3),
            "headroom_fraction": self._headroom,
            "seed_bytes_per_lane": round(SEED_BYTES_PER_LANE, 1),
            "devices": devices,
            "model_bytes_per_lane": model,
        }


# --- default plane (process-wide, like telemetry.default_hub) ---------------

_default_mtx = threading.Lock()
_default_plane: Optional[MemoryPlane] = None


def default_plane() -> Optional[MemoryPlane]:
    """The process-default memory plane, or None when none is installed
    (the mesh/scheduler hot paths pay one attribute read)."""
    return _default_plane


def set_default_plane(plane: Optional[MemoryPlane]) -> Optional[MemoryPlane]:
    """Install ``plane`` as the process default (None uninstalls).
    Returns the previous default."""
    global _default_plane
    with _default_mtx:
        prev, _default_plane = _default_plane, plane
    return prev
