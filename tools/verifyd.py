"""verifyd — standalone verify-as-a-service daemon.

Runs ONE VerifyScheduler + VerifyService pair and listens on a Unix
socket (default) or TCP address so many nodes / light clients can share
one device pool. Client frames carry the compact wire format directly
(crypto/service.py), so the daemon's only per-request work is
device_put + the coalesced kernel dispatch; verdicts fan back out as
one status byte + a packed verdict bitmap per request.

Ops surface (--metrics-addr): the daemon serves the node's
MetricsServer routes — ``/metrics`` (Prometheus text), ``/debug/verify``
(one JSON snapshot: SLO, devices, per-tenant service panel, incident
timeline), ``/debug/traces`` (+ ``/chrome``) off the daemon's flight
recorder. Incident dumps fire on breaker opens and brownout trips and
embed the service view (which tenants were riding the failing flush).

Usage:
    python tools/verifyd.py                              # unix socket
    python tools/verifyd.py --address tcp://0.0.0.0:26670
    python tools/verifyd.py --backend tpu --flush-us 500 --qos on
    python tools/verifyd.py --no-coalesce                # bench baseline
    python tools/verifyd.py --stats 5                    # JSON snapshots
    python tools/verifyd.py --metrics-addr 127.0.0.1:26670

Point nodes at it with ``[crypto] verify_service = "unix:///..."`` or
``CBFT_VERIFY_SERVICE``; they fall back to local CPU verification on
disconnect/timeout, so the daemon is never a liveness dependency.
"""

import argparse
import json
import os
import signal
import sys
import threading
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# incidents whose timeline event flushes the flight recorder to disk
_DUMP_EVENTS = ("brownout_trip", "breaker_open")
_STATS_JOIN_S = 2.0


class Daemon:
    """The verifyd component graph, constructed without being started —
    tests (and the chaos harness) drive it in-process; ``main`` drives
    it from the CLI. One scheduler, one service, one telemetry hub, one
    tracer, and (optionally) one MetricsServer."""

    def __init__(
        self,
        address: str,
        *,
        backend: Optional[str] = None,
        flush_us: Optional[int] = None,
        max_chunk: Optional[int] = None,
        qos: str = "default",
        tenant_rate: Optional[int] = None,
        coalesce: bool = True,
        auth_key: Optional[bytes] = None,
        drain_timeout_ms: int = 10_000,
        metrics_addr: Optional[str] = None,
        trace_sample: Optional[float] = None,
        dump_dir: Optional[str] = None,
        advertise_trace: bool = True,
        row_verifier=None,
        logger=None,
    ):
        from cometbft_tpu.crypto import service as servicelib
        from cometbft_tpu.crypto.scheduler import VerifyScheduler
        from cometbft_tpu.crypto.telemetry import Metrics, TelemetryHub
        from cometbft_tpu.libs import trace as tracelib
        from cometbft_tpu.libs.log import new_tm_logger
        from cometbft_tpu.libs.metrics import MetricsServer, Registry

        self.logger = logger if logger is not None else new_tm_logger()
        if (backend or os.environ.get("CMT_CRYPTO_BACKEND")) == "tpu":
            # the daemon is the process that will dispatch: refuse to
            # serve behind a device name when jax found no TPU (unless
            # JAX_PLATFORMS asks for the CPU platform on purpose)
            from cometbft_tpu.crypto.tpu import mesh as tpu_mesh

            tpu_mesh.require_accelerator("verifyd --backend tpu")
        self.registry = Registry(namespace="cometbft")
        self.tracer = tracelib.Tracer(sample=trace_sample, dump_dir=dump_dir)
        tracelib.attach_stage_metrics(self.tracer, self.registry)
        self.hub = TelemetryHub(metrics=Metrics(self.registry))
        self.scheduler = VerifyScheduler(
            spec=backend,
            flush_us=flush_us,
            lane_budget=max_chunk,
            logger=self.logger.with_(module="scheduler"),
            telemetry=self.hub,
            tracer=self.tracer,
            qos=qos,
            tenant_rate=tenant_rate,
            row_verifier=row_verifier,
        )
        self.hub.add_burn_watcher(self.scheduler.on_burn)
        self.service = servicelib.VerifyService(
            self.scheduler,
            address,
            coalesce=coalesce,
            row_verifier=row_verifier,
            metrics=servicelib.ServiceMetrics(self.registry),
            telemetry=self.hub,
            advertise_trace=advertise_trace,
            auth_key=auth_key,
            logger=self.logger.with_(module="verifyd"),
        )
        self.drain_timeout_ms = int(drain_timeout_ms)
        # every incident dump carries the service view: which tenants
        # were riding the failing flush, and the event ring around it
        self.tracer.set_dump_context(lambda: {
            "service": self.service.snapshot(),
            "timeline": self.hub.timeline(),
        })
        self.hub.add_event_listener(self._on_event)
        self._metrics_addr = metrics_addr
        self._metrics_server: Optional[MetricsServer] = MetricsServer(
            self.registry, tracer=self.tracer, telemetry=self.hub,
            extra_routes={"/drain": self._drain_route},
        ) if metrics_addr is not None else None
        self.metrics_port: Optional[int] = None
        self.last_dump: Optional[str] = None

    def _on_event(self, ev: dict) -> None:
        if ev.get("kind") not in _DUMP_EVENTS:
            return
        path = self.tracer.dump(str(ev["kind"]), extra={"event": ev})
        if path:
            self.last_dump = path
            self.logger.error(
                "verifyd incident: flight recorder dumped",
                kind=ev["kind"], path=path,
            )

    def _drain_route(self, _q):
        """``/drain`` ops route: flip the service into draining (idempotent
        — new REQs get typed ST_DRAINING, in-flight work still answers)
        and report what is left in flight. Process exit stays with the
        supervisor's SIGTERM; this route only initiates the drain so a
        rolling restart can stop the bleeding before the kill."""
        import json

        already = self.service.draining
        self.service.drain()
        return (200, "application/json", json.dumps({
            "draining": True,
            "already_draining": already,
            "pending_requests": self.service.pending_requests(),
        }).encode())

    def drain(self, timeout_ms: Optional[int] = None) -> int:
        """Graceful drain bounded by --drain-timeout-ms: stop accepting
        new frames, wait for in-flight work to answer, and return the
        count of frames abandoned at the deadline (0 = clean drain).
        SIGTERM can never hang a supervised daemon forever."""
        import time

        bound_ms = self.drain_timeout_ms if timeout_ms is None else timeout_ms
        self.service.drain()
        deadline = time.monotonic() + max(0, bound_ms) / 1e3
        while self.service.pending_requests() > 0:
            if time.monotonic() >= deadline:
                break
            time.sleep(0.01)
        abandoned = self.service.pending_requests()
        if abandoned:
            self.logger.error(
                "drain timeout: abandoning in-flight frames",
                abandoned=abandoned, bound_ms=bound_ms,
            )
        return abandoned

    def start(self) -> None:
        self.scheduler.start()
        try:
            self.service.start()
        except Exception:
            self.scheduler.stop()
            raise
        if self._metrics_server is not None:
            host, _, port = self._metrics_addr.rpartition(":")
            self.metrics_port = self._metrics_server.serve(
                host or "127.0.0.1", int(port or 0)
            )

    def stop(self) -> None:
        if self._metrics_server is not None:
            self._metrics_server.stop()
        self.service.stop()
        self.scheduler.stop()


def main(argv: Optional[List[str]] = None) -> int:
    from cometbft_tpu.crypto import service as servicelib
    from cometbft_tpu.crypto.batch import resolved_device_plane

    ap = argparse.ArgumentParser(
        description="Shared verify-as-a-service daemon (one device pool, "
                    "N clients, cross-client megabatch coalescing)."
    )
    ap.add_argument(
        "--address", default=servicelib.DEFAULT_ADDRESS,
        help="listen address: unix:///path.sock or tcp://host:port "
             f"(default {servicelib.DEFAULT_ADDRESS})",
    )
    ap.add_argument(
        "--backend", default=None,
        help="verify backend name (cpu | tpu | ...; default: "
             "CMT_CRYPTO_BACKEND or cpu)",
    )
    ap.add_argument(
        "--flush-us", type=int, default=None,
        help="coalescing window in microseconds (default: scheduler "
             "default / CBFT_VERIFY_FLUSH_US)",
    )
    ap.add_argument(
        "--max-chunk", type=int, default=None,
        help="lane budget per coalesced flush (default: backend "
             "max_chunk / CBFT_TPU_MAX_CHUNK)",
    )
    ap.add_argument(
        "--qos", default="default",
        help="QoS class spec for the merged queue — 'default' (the five "
             "built-in classes), 'off', or an explicit "
             "'name:policy:weight,...' list (default: default)",
    )
    ap.add_argument(
        "--tenant-rate", type=int, default=None,
        help="per-tenant lanes/sec quota (tenant = client connection "
             "name); 0/unset = unlimited",
    )
    ap.add_argument(
        "--no-coalesce", action="store_true",
        help="dispatch each client frame isolated (the bench baseline "
             "— proves what cross-client coalescing buys)",
    )
    ap.add_argument(
        "--auth-key", default=None, metavar="PATH",
        help="per-node key file for HMAC session auth: clients must "
             "answer the HELLO challenge with this key or are refused "
             "typed ERR_UNAUTHORIZED (default: open, v1 interop)",
    )
    ap.add_argument(
        "--drain-timeout-ms", type=int, default=10_000,
        help="bound on the SIGTERM graceful-drain phase; at the "
             "deadline the daemon hard-exits and logs the count of "
             "abandoned in-flight frames (default: 10000)",
    )
    ap.add_argument(
        "--stats", type=float, default=0.0, metavar="SECONDS",
        help="print a JSON service snapshot every N seconds",
    )
    ap.add_argument(
        "--metrics-addr", default=None, metavar="HOST:PORT",
        help="serve /metrics, /debug/verify, /debug/traces on this "
             "address (port 0 picks a free port)",
    )
    ap.add_argument(
        "--trace-sample", type=float, default=None,
        help="flight-recorder sampling fraction for daemon-rooted "
             "traces (client-propagated sampled traces always record; "
             "default: CBFT_TRACE_SAMPLE or 0)",
    )
    ap.add_argument(
        "--dump-dir", default=None,
        help="directory for incident trace dumps (breaker open / "
             "brownout trip; default: CBFT_TRACE_DUMP_DIR)",
    )
    args = ap.parse_args(argv)

    try:
        servicelib.parse_address(args.address)
    except ValueError as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2

    auth_key = None
    if args.auth_key is not None:
        try:
            auth_key = servicelib.load_auth_key(args.auth_key)
        except (OSError, ValueError) as exc:
            print(f"error: cannot load --auth-key: {exc}", file=sys.stderr)
            return 2

    try:
        daemon = Daemon(
            args.address,
            backend=args.backend,
            flush_us=args.flush_us,
            max_chunk=args.max_chunk,
            qos=args.qos,
            tenant_rate=args.tenant_rate,
            coalesce=not args.no_coalesce,
            auth_key=auth_key,
            drain_timeout_ms=args.drain_timeout_ms,
            metrics_addr=args.metrics_addr,
            trace_sample=args.trace_sample,
            dump_dir=args.dump_dir,
        )
    except RuntimeError as exc:  # --backend tpu and jax found no TPU
        print(f"error: {exc}", file=sys.stderr)
        return 1
    try:
        daemon.start()
    except Exception as exc:  # noqa: BLE001 - CLI surface
        print(f"error: cannot listen on {args.address}: {exc}",
              file=sys.stderr)
        return 1

    line = (
        f"verifyd listening on {daemon.service.address()}  "
        f"backend={daemon.scheduler.spec.name}  "
        f"platform={(resolved_device_plane() or {}).get('platform', 'host')}  "
        f"coalesce={'on' if not args.no_coalesce else 'OFF'}  "
        f"qos={args.qos}  "
        f"auth={'on' if auth_key else 'off'}"
    )
    if daemon.metrics_port is not None:
        line += f"  metrics=http://127.0.0.1:{daemon.metrics_port}/metrics"
    print(line, flush=True)

    done = threading.Event()
    # SIGTERM drains first (rolling-restart contract: answer in-flight
    # work, refuse new frames typed so clients fail over, exit bounded
    # by --drain-timeout-ms); SIGINT stays the immediate stop.
    graceful = {"drain": False}

    def _stop(signum, frame):  # noqa: ARG001 - signal signature
        done.set()

    def _term(signum, frame):  # noqa: ARG001 - signal signature
        graceful["drain"] = True
        done.set()

    signal.signal(signal.SIGINT, _stop)
    signal.signal(signal.SIGTERM, _term)

    # The stats printer gets its own thread so the idle path (no
    # --stats) blocks straight on the shutdown event instead of waking
    # every second just to loop; teardown joins it bounded.
    stats_thread: Optional[threading.Thread] = None
    if args.stats > 0:

        def _stats_loop() -> None:
            while not done.wait(args.stats):
                print(
                    json.dumps(daemon.service.snapshot(), sort_keys=True,
                               default=str),
                    flush=True,
                )

        stats_thread = threading.Thread(
            target=_stats_loop, daemon=True, name="verifyd-stats"
        )
        stats_thread.start()

    try:
        done.wait()
    finally:
        done.set()
        if stats_thread is not None:
            stats_thread.join(timeout=_STATS_JOIN_S)
        if graceful["drain"]:
            abandoned = daemon.drain()
            print(
                f"verifyd drained  abandoned={abandoned}", flush=True
            )
        daemon.stop()
        print("verifyd stopped", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
