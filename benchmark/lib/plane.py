"""The system under test, reached the way an operator reaches it.

The benchmark's copy of chip_smoke.py's ``leg_node`` (PR 21): ``init`` a
home, ``[crypto] backend = "tpu"``, ``default_new_node``, ``start``, wait
for the supervisor's canary on the device. ``[crypto]`` stays at its
defaults but for what the configuration file states under ``crypto``.
"""

from __future__ import annotations

import contextlib
import sys
import time
from typing import Callable, Dict

from benchmark.lib.books import DEVICE_ROUTES, Books

# the node's own one-validator chain; the traffic's chain id is the
# configuration's
CHAIN_ID = "perf-bench"


class PlaneError(RuntimeError):
    """The plane did not come up, or is not the one the cell asks for."""


def wait_for(cond: Callable[[], object], timeout_s: float, what: str,
             poll_s: float = 0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        val = cond()
        if val:
            return val
        time.sleep(poll_s)
    raise PlaneError(f"timed out after {timeout_s:.0f}s waiting for {what}")


class Plane:
    """What a traffic generator is handed: the node's served entry point
    (``backend``), the counters' fallback probe, and the two hooks of a
    traced run (``span`` writes a host span into the profiler's trace,
    ``tick`` lets the harness start and stop the trace between
    requests). Outside a traced run both hooks do nothing."""

    def __init__(self, node):
        self.node = node
        self.backend = node.crypto_backend
        self.books = Books(node)
        self.span: Callable[[str], contextlib.AbstractContextManager] = (
            lambda name: contextlib.nullcontext()
        )
        self.tick: Callable[[], None] = lambda: None
        self.started_s: Dict[str, float] = {}

    def fallbacks(self) -> float:
        return self.books.fallbacks()

    @staticmethod
    def note(msg: str) -> None:
        """Progress and oddities go to stderr; stdout carries the result."""
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    def stop(self) -> None:
        self.node.stop()


def start(home: str, crypto: Dict[str, object],
          expect_platform: str = "tpu",
          canary_timeout_s: float = 900.0) -> Plane:
    """Build the home, start the node, wait for the canary. ``crypto``
    holds the configuration's ``[crypto]`` overrides, key for key."""
    from cometbft_tpu.cmd.commands import _load_config
    from cometbft_tpu.cmd.commands import main as cli_main
    from cometbft_tpu.crypto.tpu import mesh as tpu_mesh
    from cometbft_tpu.libs.net import free_ports
    from cometbft_tpu.node import default_new_node

    t0 = time.monotonic()
    if cli_main(["--home", home, "init", "--chain-id", CHAIN_ID]) != 0:
        raise PlaneError("init failed")
    cfg = _load_config(home)
    rpc_port, p2p_port = free_ports(2)
    cfg.base.proxy_app = "kvstore"
    cfg.base.db_backend = "memdb"
    cfg.rpc.laddr = f"tcp://127.0.0.1:{rpc_port}"
    cfg.p2p.laddr = f"tcp://127.0.0.1:{p2p_port}"
    cfg.crypto.backend = "tpu"
    for key, value in crypto.items():
        if not hasattr(cfg.crypto, key):
            raise PlaneError(f"[crypto] has no key {key!r}")
        setattr(cfg.crypto, key, value)
    cfg.validate_basic()
    node = default_new_node(cfg)
    resolved = tpu_mesh.device_plane()
    if resolved["platform"] != expect_platform:
        raise PlaneError(
            f"the tpu backend resolved platform {resolved['platform']!r}, "
            f"not {expect_platform!r}"
        )
    node.start()
    plane = Plane(node)
    plane.started_s["node_start"] = time.monotonic() - t0
    try:
        _await_canary(node, canary_timeout_s)
    except BaseException:
        node.stop()
        raise
    plane.started_s["canary"] = time.monotonic() - t0
    return plane


def _await_canary(node, timeout_s: float) -> None:
    m = node.verify_supervisor.metrics
    n_domains = len(node.verify_topology)

    def canary_done() -> bool:
        if m.probes.with_labels(outcome="fail").value():
            raise PlaneError("the warmup canary failed on the device")
        return m.probes.with_labels(outcome="ok").value() >= n_domains

    wait_for(canary_done, timeout_s, "the warmup canary", poll_s=0.1)
    routed = Books(node).decisions()["lanes"]
    if any(routed.get(r) for r in DEVICE_ROUTES):
        raise PlaneError(
            f"a flush took a device route before any traffic: {routed}"
        )


def device_record() -> Dict[str, object]:
    """The device as jax reports it (the contract's last-line shape)."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def memory_peak_bytes() -> int:
    """The peak on the fullest chip, where the backend reports it."""
    import jax

    peak = 0
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
