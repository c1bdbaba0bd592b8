"""The program's own counters, read as they are.

The benchmark's copy of chip_smoke.py's ``Books`` (PR 21), extended to
one ``snapshot()`` whose difference over the measured window every
per-layer reader takes. Nothing here counts anything itself: decision
ledger, wire ledger, scheduler, supervisor and AOT registry are the
program's, read through their public accessors, and histograms through
the text exposition an operator scrapes.
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

DEVICE_ROUTES = ("single", "sharded", "indexed")

# supervisor counters; the first group means "the device path broke
# under this flush": a request served while one of them moved counts as
# failed. A hedge the host pool won is not among them: the dispatch was
# late, the verdict correct and the request served (its latency counts;
# layers/hedge_cpu_win_share.py reports how often). Counted as failed,
# 2 and 4 of ~1,555 windows failed in the two sets of the driver's check
# of PR 22, and a cell whose failures differ between runs of one code is
# refused: the traffic itself never fails.
FALLBACK_COUNTERS = (
    "failures", "watchdog_kills", "sharded_fallbacks", "indexed_fallbacks",
    "triage_cpu_fallbacks",
)
OTHER_COUNTERS = (
    "cpu_routed", "triage_runs", "triage_passes", "triage_divergence",
    "audits", "audit_lanes", "audit_mismatches", "audit_drops",
    "hedge_fires", "hedge_divergence", "indexed_dispatches", "host_lanes",
    "device_dispatches", "sharded_dispatches", "chunk_shrinks",
    "redistributions",
)

_SAMPLE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_exposition(lines: List[str]) -> Dict[Tuple[str, tuple], float]:
    """Prometheus text lines → {(name, sorted label pairs): value}."""
    out: Dict[Tuple[str, tuple], float] = {}
    for line in lines:
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line.strip())
        if m is None:
            continue
        name, labels, value = m.groups()
        key = tuple(sorted(_LABEL.findall(labels or "")))
        try:
            out[(name, key)] = float(value)
        except ValueError:
            continue
    return out


def histogram_totals(hist) -> Dict[tuple, Dict[str, float]]:
    """{label pairs: {"sum": s, "count": n}} for one histogram family."""
    out: Dict[tuple, Dict[str, float]] = {}
    for (name, labels), v in parse_exposition(hist.expose()).items():
        for suffix in ("_sum", "_count"):
            if name.endswith(suffix):
                out.setdefault(labels, {})[suffix[1:]] = v
    return out


class Books:
    """Reads one node's ledgers and metrics."""

    def __init__(self, node):
        self.node = node

    def fallbacks(self) -> float:
        """Cheap enough to read after every request: the sum of every
        counter that moves when the routed path failed and something
        else answered (a flush sent to the host by the floor is routing,
        a hedge won by the host pool is a late dispatch: neither moves
        any of them)."""
        m = self.node.verify_supervisor.metrics
        total = sum(getattr(m, name).value() for name in FALLBACK_COUNTERS)
        total += self.node.verify_scheduler.metrics.cpu_fallbacks.value()
        return total

    def breakers(self) -> Dict[str, str]:
        return dict(self.node.verify_supervisor.device_states())

    def supervisor(self) -> Dict[str, float]:
        m = self.node.verify_supervisor.metrics
        out = {
            name: getattr(m, name).value()
            for name in FALLBACK_COUNTERS + OTHER_COUNTERS
        }
        out["retries"] = sum(
            m.retries.with_labels(cls=c).value() for c in ("transient", "oom")
        )
        out["hedge_wins_cpu"] = m.hedge_wins.with_labels(winner="cpu").value()
        for oc in ("ok", "fail"):
            out[f"probes_{oc}"] = m.probes.with_labels(outcome=oc).value()
        return out

    def scheduler(self) -> Dict[str, object]:
        sched = self.node.verify_scheduler
        m = sched.metrics
        wait = histogram_totals(m.request_wait_seconds).get((), {})
        snap = sched.queue_snapshot()
        return {
            "requests": m.requests.value(),
            "signatures": m.signatures.value(),
            "cpu_fallbacks": m.cpu_fallbacks.value(),
            "wait_sum_s": wait.get("sum", 0.0),
            "wait_count": wait.get("count", 0.0),
            "dispatches": snap["dispatches"],
            "flush_reasons": dict(snap["flush_reasons"]),
            "routes": dict(snap["routes"]),
        }

    def wire(self) -> Dict[str, object]:
        ledger = self.node.wire_ledger
        phase_s: Dict[str, Dict[str, float]] = {}
        for labels, tot in histogram_totals(
            ledger.metrics.phase_seconds
        ).items():
            lab = dict(labels)
            phase_s.setdefault(lab.get("route", ""), {})[
                lab.get("phase", "")
            ] = tot.get("sum", 0.0)
        return {"lanes": ledger.lanes_by_route(), "phase_s": phase_s}

    def decisions(self) -> Dict[str, object]:
        ledger = self.node.decision_ledger
        return {"lanes": ledger.lanes(), "counts": ledger.counts()}

    def cpu_pool_lanes(self) -> float:
        """Every lane the host pool verified, whoever sent it there: the
        floor's routing, the corruption audit, triage confirmations."""
        return self.node.telemetry_hub.metrics.device_sigs.with_labels(
            device="cpu"
        ).value()

    def aot_builds(self) -> list:
        """One record per executable the AOT registry compiled or loaded
        from its store so far: {kernel, bucket, sharded, source, seconds}."""
        from cometbft_tpu.crypto.tpu import aot

        return aot.default_registry().stats()["builds"]

    def snapshot(self) -> Dict[str, object]:
        return {
            "sched": self.scheduler(),
            "decisions": self.decisions(),
            "wire": self.wire(),
            "supervisor": self.supervisor(),
            "cpu_pool_lanes": self.cpu_pool_lanes(),
            "aot_builds": len(self.aot_builds()),
            "breakers": self.breakers(),
        }


def device_lanes(before: dict, after: dict) -> float:
    """Caller lanes that took a device route in between: the decision
    ledger's device routes, and the resident commit path, which runs
    beside the scheduler and is on record in the wire ledger alone."""
    routed = delta_map(before, after, "decisions", "lanes")
    return (
        sum(routed.get(r, 0.0) for r in DEVICE_ROUTES)
        + delta(before, after, "wire", "lanes", "resident")
    )


def wire_lanes(before: dict, after: dict) -> float:
    """Lanes that reached the device in between, on any wire route."""
    return sum(delta_map(before, after, "wire", "lanes").values())


def wire_phase_s(before: dict, after: dict, *phases: str) -> float:
    """Seconds the wire ledger booked to ``phases`` in between, all
    routes together."""
    total = 0.0
    routes = set(after["wire"]["phase_s"]) | set(before["wire"]["phase_s"])
    for route in routes:
        per = delta_map(before, after, "wire", "phase_s", route)
        total += sum(per.get(ph, 0.0) for ph in phases)
    return total


def delta(before: dict, after: dict, *path: str) -> float:
    """after[path] - before[path]; a key missing on a side counts 0."""
    a, b = after, before
    for key in path:
        a = a.get(key, {}) if isinstance(a, dict) else {}
        b = b.get(key, {}) if isinstance(b, dict) else {}
    return float(a or 0) - float(b or 0)


def delta_map(before: dict, after: dict, *path: str) -> Dict[str, float]:
    """Per-key differences of two {key: number} maps under ``path``."""
    a, b = after, before
    for key in path:
        a = a.get(key) or {}
        b = b.get(key) or {}
    return {k: float(a.get(k, 0)) - float(b.get(k, 0)) for k in set(a) | set(b)}
