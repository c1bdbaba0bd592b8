"""The Node — full dependency-ordered assembly of a running validator.

Reference: node/node.go:708 NewNode / :100 DefaultNewNode / :943 OnStart.
Every subsystem the tests hand-assemble is wired here from a Config:
stores, ABCI proxy conns, handshake replay, mempool, evidence, blocksync,
consensus (with WAL + FilePV), p2p transport/switch/PEX, and the JSON-RPC
server.
"""

from __future__ import annotations

import os
from typing import List, Optional

from cometbft_tpu.abci.client import Client, LocalClient, SocketClient
from cometbft_tpu.abci.kvstore import (
    KVStoreApplication,
    PersistentKVStoreApplication,
)
from cometbft_tpu.blocksync import BLOCKSYNC_CHANNEL, BlocksyncReactor
from cometbft_tpu.config import Config
from cometbft_tpu.consensus.reactor import (
    DATA_CHANNEL,
    STATE_CHANNEL,
    VOTE_CHANNEL,
    VOTE_SET_BITS_CHANNEL,
    ConsensusReactor,
)
from cometbft_tpu.consensus.replay import Handshaker
from cometbft_tpu.consensus.state import ConsensusState
from cometbft_tpu.consensus.wal import WAL, NilWAL
from cometbft_tpu.evidence.pool import Pool as EvidencePool
from cometbft_tpu.evidence.reactor import EVIDENCE_CHANNEL, EvidenceReactor
from cometbft_tpu.libs.db import DB, MemDB, SQLiteDB
from cometbft_tpu.libs.log import Logger, new_nop_logger
from cometbft_tpu.libs.service import BaseService
from cometbft_tpu.mempool.clist_mempool import CListMempool
from cometbft_tpu.mempool.reactor import MEMPOOL_CHANNEL, MempoolReactor
from cometbft_tpu.p2p import (
    MultiplexTransport,
    NetAddress,
    NodeInfo,
    NodeKey,
    ProtocolVersion,
    Switch,
)
from cometbft_tpu.p2p.conn.connection import MConnConfig
from cometbft_tpu.p2p.pex.addrbook import AddrBook
from cometbft_tpu.p2p.pex.reactor import PEX_CHANNEL, PEXReactor
from cometbft_tpu.privval import load_or_gen_file_pv
from cometbft_tpu.proxy import AppConns, new_app_conns
from cometbft_tpu.state import State, make_genesis_state
from cometbft_tpu.statesync.messages import CHUNK_CHANNEL, SNAPSHOT_CHANNEL
from cometbft_tpu.statesync.reactor import StateSyncReactor
from cometbft_tpu.state.execution import BlockExecutor
from cometbft_tpu.state.store import Store as StateStore
from cometbft_tpu.store import BlockStore
from cometbft_tpu.types.event_bus import EventBus
from cometbft_tpu.types.genesis import GenesisDoc
from cometbft_tpu.types.params import default_consensus_params


def _parse_laddr(laddr: str):
    """tcp://host:port → (host, port)."""
    addr = laddr.split("://", 1)[-1]
    host, _, port = addr.rpartition(":")
    return host or "127.0.0.1", int(port)


def default_client_creator(
    proxy_app: str, app_db: Optional[DB] = None, transport: str = "socket"
):
    """Reference: proxy.DefaultClientCreator — builtin names or a remote
    address ([base] abci = "socket" | "grpc" picks the wire). Builtin apps
    share ONE application instance across the four logical connections
    (LocalClient takes a shared mutex)."""
    import threading

    if proxy_app == "kvstore":
        app = KVStoreApplication(app_db)
        mtx = threading.Lock()
        return lambda: LocalClient(app, mtx)
    if proxy_app == "persistent_kvstore":
        app = PersistentKVStoreApplication(app_db)
        mtx = threading.Lock()
        return lambda: LocalClient(app, mtx)
    if proxy_app == "snapshot_kvstore":
        from cometbft_tpu.abci.kvstore import SnapshotKVStoreApplication

        app = SnapshotKVStoreApplication(app_db, snapshot_interval=10)
        mtx = threading.Lock()
        return lambda: LocalClient(app, mtx)
    if proxy_app == "noop":
        from cometbft_tpu.abci.application import BaseApplication

        app = BaseApplication()
        mtx = threading.Lock()
        return lambda: LocalClient(app, mtx)
    if transport == "grpc":
        from cometbft_tpu.abci.grpc import GRPCClient

        return lambda: GRPCClient(proxy_app)
    addr = proxy_app.split("://", 1)[-1]
    return lambda: SocketClient(addr, must_connect=False)


class Node(BaseService):
    """node/node.go:708 NewNode."""

    def __init__(
        self,
        config: Config,
        priv_validator,
        node_key: NodeKey,
        client_creator,
        genesis_doc: GenesisDoc,
        db_provider=None,  # (name, config) -> DB
        state_provider=None,  # statesync.StateProvider (when statesync on)
        logger: Optional[Logger] = None,
        genesis_hash: Optional[bytes] = None,  # sha256 of the RAW file
    ):
        super().__init__("Node", logger or new_nop_logger())
        self.config = config
        self.genesis_doc = genesis_doc
        self.node_key = node_key
        self._dbs: List[DB] = []
        # any failure while assembling must release the services already
        # started (threads, sockets, DB file locks), not leak a half-node
        try:
            self._setup(
                config, priv_validator, node_key, client_creator,
                genesis_doc, db_provider, state_provider, genesis_hash,
            )
        except Exception:
            self._abort_init()
            raise

    def _setup(
        self,
        config: Config,
        priv_validator,
        node_key: NodeKey,
        client_creator,
        genesis_doc: GenesisDoc,
        db_provider,
        state_provider,
        genesis_hash: Optional[bytes] = None,
    ) -> None:
        _provider = db_provider or default_db_provider

        def db_provider(name: str, cfg: Config) -> DB:
            db = _provider(name, cfg)
            self._dbs.append(db)
            return db

        # [crypto] backend AND its tuning are threaded explicitly to
        # every consumer below as one BackendSpec — never set
        # process-globally here, so in-process multi-node setups (tests,
        # localnet runners) can mix backends and min_batch values. The
        # CLI entrypoint (default_new_node) additionally sets the
        # process default backend name.
        from cometbft_tpu.crypto import service as verify_servicelib
        from cometbft_tpu.crypto.batch import BackendSpec

        self.crypto_spec = BackendSpec(
            name=config.crypto.backend,
            min_batch=config.crypto.min_batch,
            max_chunk=config.crypto.max_chunk,
        )

        # 0. metrics provider (node.go:122-152 DefaultMetricsProvider —
        # Prometheus-backed when [instrumentation] enables it, no-ops
        # otherwise so instrumentation points stay free)
        from cometbft_tpu.consensus.metrics import Metrics as ConsMetrics
        from cometbft_tpu.crypto.scheduler import Metrics as SchedMetrics
        from cometbft_tpu.crypto.supervisor import Metrics as SupMetrics
        from cometbft_tpu.crypto.telemetry import Metrics as TelMetrics
        from cometbft_tpu.libs.metrics import Registry
        from cometbft_tpu.mempool.metrics import Metrics as MemMetrics
        from cometbft_tpu.p2p.metrics import Metrics as P2PMetrics
        from cometbft_tpu.state.metrics import Metrics as SMMetrics

        from cometbft_tpu.crypto.decisions import Metrics as DecisionMetrics
        from cometbft_tpu.crypto.qos import QoSMetrics
        from cometbft_tpu.crypto.tpu.aot import Metrics as AotMetrics
        from cometbft_tpu.crypto.tpu.memory import Metrics as MemPlaneMetrics
        from cometbft_tpu.crypto.wire import Metrics as WireMetrics

        if config.instrumentation.prometheus:
            self.metrics_registry = Registry(
                namespace=config.instrumentation.namespace
            )
            cons_metrics = ConsMetrics(self.metrics_registry)
            p2p_metrics = P2PMetrics(self.metrics_registry)
            mem_metrics = MemMetrics(self.metrics_registry)
            sm_metrics = SMMetrics(self.metrics_registry)
            sched_metrics = SchedMetrics(self.metrics_registry)
            qos_metrics = QoSMetrics(self.metrics_registry)
            sup_metrics = SupMetrics(self.metrics_registry)
            aot_metrics = AotMetrics(self.metrics_registry)
            tel_metrics = TelMetrics(self.metrics_registry)
            memplane_metrics = MemPlaneMetrics(self.metrics_registry)
            wire_metrics = WireMetrics(self.metrics_registry)
            decision_metrics = DecisionMetrics(self.metrics_registry)
        else:
            self.metrics_registry = None
            cons_metrics = ConsMetrics.nop()
            p2p_metrics = P2PMetrics.nop()
            mem_metrics = MemMetrics.nop()
            sm_metrics = SMMetrics.nop()
            sched_metrics = SchedMetrics.nop()
            qos_metrics = QoSMetrics.nop()
            sup_metrics = SupMetrics.nop()
            aot_metrics = AotMetrics.nop()
            tel_metrics = TelMetrics.nop()
            memplane_metrics = MemPlaneMetrics.nop()
            wire_metrics = WireMetrics.nop()
            decision_metrics = DecisionMetrics.nop()
        # the AOT executable registry is process-global (it backs the
        # mesh dispatch layer, which predates any Node); the node only
        # lends it an exporter, exactly like the topology default above
        from cometbft_tpu.crypto.tpu import aot as aotlib

        aotlib.default_registry().set_metrics(aot_metrics)

        # 0c. verify-path tracer (libs/trace.py): per-node flight
        # recorder over the verify pipeline (request → dispatch →
        # supervise → device → chunk). Sampling/buffer knobs resolve
        # env > [instrumentation] config > default; disabled (sample 0)
        # the hot path sees only a no-op span object. Incident dumps
        # (watchdog trip / circuit-break) land in the node's data dir.
        from cometbft_tpu.libs import trace as tracelib

        self.tracer = tracelib.Tracer(
            sample=tracelib.trace_sample_default(
                config.instrumentation.trace_sample
            ),
            buffer=tracelib.trace_buffer_default(
                config.instrumentation.trace_buffer
            ),
            dump_keep=tracelib.trace_dump_keep_default(
                config.instrumentation.trace_dump_keep
            ),
        )
        if config.root_dir:
            self.tracer.set_dump_dir(os.path.join(config.root_dir, "data"))
        if self.metrics_registry is not None:
            tracelib.attach_stage_metrics(self.tracer, self.metrics_registry)

        # 0d. the capacity-telemetry hub (crypto/telemetry.py): per-
        # device utilization, lane-fill efficiency, per-subsystem RED
        # metering, and the SLO engine — the health/capacity plane
        # served as /debug/verify. Installed as the process default so
        # the mesh chunk loop (which predates any node) reports lane
        # fill without plumbing; supervisor and scheduler are handed it
        # explicitly below.
        from cometbft_tpu.crypto import telemetry as telemetrylib

        self.telemetry_hub = telemetrylib.TelemetryHub(
            metrics=tel_metrics,
            slo_target_ms=telemetrylib.slo_commit_ms_default(
                config.instrumentation.slo_commit_ms
            ),
        )
        telemetrylib.set_default_hub(self.telemetry_hub)

        # 0b. the node-wide verification scheduler: ONE coalescer every
        # verification-carrying subsystem submits through, so concurrent
        # sub-floor batches (a commit check racing a vote drain) share a
        # single padded dispatch and clear the TPU routing floor
        # together. It travels the same parameter the BackendSpec did —
        # crypto/batch.py unwraps it — so standalone new_batch_verifier
        # users keep working unchanged.
        from cometbft_tpu.crypto.scheduler import VerifyScheduler
        from cometbft_tpu.crypto.supervisor import BackendSupervisor

        # 0a'. the device topology the supervisor shards its fault
        # state over: [crypto] fault_domains (CBFT_FAULT_DOMAINS wins)
        # selects single-domain (1, default), an N-domain virtual mesh
        # (N > 1), or auto-detection from the visible device plane (0).
        # Installed as the process default so the mesh dispatch layer's
        # single-device shim and any standalone verifier resolve the
        # same registry (crypto/tpu/topology.py).
        from cometbft_tpu.crypto.tpu import topology as topolib

        n_domains = topolib.fault_domains_default(
            config.crypto.fault_domains
        )
        if n_domains <= 0:
            verify_topology = topolib.DeviceTopology.detect()
        elif n_domains == 1:
            verify_topology = topolib.DeviceTopology.single()
        else:
            verify_topology = topolib.DeviceTopology.virtual(n_domains)
        topolib.set_default_topology(verify_topology)
        self.verify_topology = verify_topology

        # 0e. the device-memory plane (crypto/tpu/memory.py): per-device
        # HBM occupancy polled lazily from device.memory_stats() plus a
        # calibrated per-(kernel, bucket) footprint model. Installed as
        # the process default so the mesh dispatch layer consults the
        # pre-dispatch guard — projected footprint vs free headroom
        # shrinks the chunk cap BEFORE an allocation can fail, demoting
        # the reactive RESOURCE_EXHAUSTED shrink rung to a last resort.
        from cometbft_tpu.crypto.tpu import memory as memlib

        self.memory_plane = memlib.MemoryPlane(
            topology=verify_topology,
            poll_ms=memlib.mem_poll_ms_default(
                config.instrumentation.mem_poll_ms
            ),
            metrics=memplane_metrics,
        )
        memlib.set_default_plane(self.memory_plane)
        self.telemetry_hub.register_source(
            "memory", self.memory_plane.snapshot
        )

        # 0g. the wire ledger (crypto/wire.py): continuous per-phase
        # dispatch attribution (pack / h2d / compute / d2h / demux) with
        # EWMA cost profiles per (route, bucket, device). Installed as
        # the process default so the mesh chunk loop and the scheduler's
        # demux loop feed it without plumbing; seeded cold from the
        # calibration store's link profile (tools/tpu_link_probe.py
        # --merge) so CostProfile.predict_ms answers before the first
        # live dispatch lands.
        from cometbft_tpu.crypto import wire as wirelib

        if wirelib.wire_ledger_default(config.instrumentation.wire_ledger):
            self.wire_ledger = wirelib.WireLedger(
                metrics=wire_metrics,
                window=wirelib.wire_window_default(
                    config.instrumentation.wire_window
                ),
            )
            wirelib.seed_from_calibration(self.wire_ledger)
            wirelib.set_default_ledger(self.wire_ledger)
            self.telemetry_hub.register_source(
                "wire", self.wire_ledger.snapshot
            )
        else:
            self.wire_ledger = None

        # 0f. the incident profiler (libs/profiling.py): bounded one-shot
        # jax.profiler captures into NODE_HOME/data/profiles — on demand
        # (/debug/profile), on SLO burn ([instrumentation]
        # profile_on_burn via the hub's burn watcher), and on breaker
        # trip (the supervisor is handed it below). The flight recorder
        # tags the newest capture into its incident dumps.
        from cometbft_tpu.libs import profiling as proflib

        self.profiler = proflib.ProfilerCapture(
            profile_dir=(
                os.path.join(config.root_dir, "data", "profiles")
                if config.root_dir
                else None
            ),
            keep=proflib.profile_keep_default(
                config.instrumentation.profile_keep
            ),
            on_burn_threshold=proflib.profile_on_burn_default(
                config.instrumentation.profile_on_burn
            ),
            logger=self.logger,
        )
        self.telemetry_hub.set_burn_watcher(self.profiler.on_burn)
        # every incident dump — whoever triggers it — carries the memory
        # plane's view of the device; the post-mortem reads HBM pressure
        # next to the breaker states instead of guessing
        # ... and the wire ledger's last flush records: where the flushes
        # before the incident spent their lives, by phase
        _mem_plane, _wire = self.memory_plane, self.wire_ledger

        def _dump_context() -> dict:
            doc = {"memory": _mem_plane.snapshot()}
            if _wire is not None:
                doc["flushes"] = _wire.flushes()
            return doc

        self.tracer.set_dump_context(_dump_context)

        # 0h. the decision ledger (crypto/decisions.py): one
        # RouteDecision per coalesced flush — inputs, per-candidate
        # predicted cost (over the wire ledger's CostProfile), taken vs
        # final route, prediction error, counterfactual regret — plus
        # the time-series ring and the anomaly watchdog. The watchdog
        # fires the same incident-capture path a breaker trip does:
        # flight-recorder dump + profiler one-shot, tagged with the
        # anomaly cause.
        from cometbft_tpu.crypto import decisions as declib

        if declib.decision_ledger_default(
            config.instrumentation.decision_ledger
        ):
            _tracer, _profiler = self.tracer, self.profiler

            def _on_route_anomaly(cause: str, value: float) -> None:
                _tracer.dump(
                    f"decision_{cause}",
                    extra={"decision_anomaly": {
                        "cause": cause, "value": value,
                    }},
                )
                _profiler.on_breaker_trip(f"decision_{cause}")

            self.decision_ledger = declib.DecisionLedger(
                window=declib.decision_window_default(
                    config.instrumentation.decision_window
                ),
                mape_trip=declib.decision_mape_trip_default(
                    config.instrumentation.decision_mape_trip
                ),
                cost_profile=(
                    self.wire_ledger.cost_profile()
                    if self.wire_ledger is not None else None
                ),
                metrics=decision_metrics,
                on_anomaly=_on_route_anomaly,
                # third prediction rung: the persisted calibration sweep
                # prices routes the wire ledger never observes live
                # (notably cpu on a device node), which is what lets the
                # priced router engage before any route has been walked
                seed=declib.calibration_seed_ms,
            )
            declib.set_default_ledger(self.decision_ledger)
            self.telemetry_hub.register_source(
                "decisions", self.decision_ledger.snapshot
            )
        else:
            self.decision_ledger = None

        # 0i. the device key store as its own telemetry source: decision
        # records cite residency from the same plane /debug/verify
        # serves. The sys.modules guard keeps CPU-only nodes from ever
        # importing the TPU package for it.
        def _keystore_source():
            import sys as _sys

            kslib = _sys.modules.get("cometbft_tpu.crypto.tpu.keystore")
            if kslib is None:
                return {"resident": False}
            snap = kslib.default_store().snapshot()
            snap["resident"] = bool(snap.get("entries"))
            return snap

        self.telemetry_hub.register_source("keystore", _keystore_source)

        # 0j. what jax gave this process (platform, device kind, count,
        # runtime versions, compile cache): {} until the tpu backend has
        # resolved it — a cpu-backend node never starts jax for a snapshot
        from cometbft_tpu.crypto.batch import resolved_device_plane

        self.telemetry_hub.register_source(
            "device_plane", lambda: resolved_device_plane() or {}
        )

        # 0a. the backend supervisor: every coalesced dispatch runs
        # under its watchdog / circuit breaker / corruption audit, so a
        # wedged, dying, or silently-wrong device plane degrades to the
        # CPU ground truth instead of stalling consensus or releasing
        # wrong verdicts (crypto/supervisor.py)
        self.verify_supervisor = BackendSupervisor(
            spec=self.crypto_spec,
            dispatch_timeout_ms=config.crypto.dispatch_timeout_ms,
            breaker_threshold=config.crypto.breaker_threshold,
            audit_pct=config.crypto.audit_pct,
            hedge_pct=config.crypto.hedge_pct,
            retry_ms=config.crypto.retry_ms,
            chunk_recover_n=config.crypto.chunk_recover_n,
            metrics=sup_metrics,
            logger=self.logger,
            tracer=self.tracer,
            topology=verify_topology,
            telemetry=self.telemetry_hub,
            memory_plane=self.memory_plane,
            profiler=self.profiler,
        )
        self.verify_scheduler = VerifyScheduler(
            spec=self.crypto_spec,
            flush_us=config.crypto.flush_us,
            metrics=sched_metrics,
            logger=self.logger,
            supervisor=self.verify_supervisor,
            max_queue=config.crypto.max_queue,
            tracer=self.tracer,
            telemetry=self.telemetry_hub,
            shard_min_batch=config.crypto.shard_min_batch,
            qos=config.crypto.qos_classes,
            qos_metrics=qos_metrics,
            tenant_rate=config.crypto.qos_tenant_rate,
            router=config.crypto.router,
        )
        self.telemetry_hub.register_source(
            "scheduler", self.verify_scheduler.queue_snapshot
        )
        # overload signals → QoS brownout: the hub's SLO burn rate on
        # every snapshot (the same hook the profiler rides) and the
        # supervisor's aggregate-state transitions
        self.telemetry_hub.add_burn_watcher(self.verify_scheduler.on_burn)
        self.verify_supervisor.add_state_listener(
            self.verify_scheduler.on_supervisor_state
        )
        self.telemetry_hub.register_source(
            "topology", verify_topology.snapshot
        )
        # shared verify daemon ([crypto] verify_service /
        # CBFT_VERIFY_SERVICE): when set, every verification-carrying
        # subsystem below points at a RemoteVerifier over the daemon —
        # cross-client megabatch coalescing on one device pool, with
        # local-CPU fallback on disconnect/timeout — instead of the
        # in-process scheduler (which still exists for standalone use
        # and as the local fallback's spec donor)
        self.remote_verifier = None
        self.crypto_backend = self.verify_scheduler
        vs_addr = verify_servicelib.verify_service_default(
            config.crypto.verify_service
        )
        if vs_addr:
            endpoints = verify_servicelib.parse_address_list(vs_addr)
            auth_path = verify_servicelib.verify_auth_key_default(
                config.crypto.verify_auth_key
            )
            auth_key = (
                verify_servicelib.load_auth_key(auth_path)
                if auth_path else None
            )
            if len(endpoints) > 1:
                # comma list = HA replica set (crypto/ha.py): breakers,
                # health probes, failover rung above local CPU; it
                # registers its own "ha" telemetry source with the
                # per-endpoint panel
                from cometbft_tpu.crypto import ha as halib

                self.remote_verifier = halib.HAVerifier(
                    endpoints,
                    tenant=config.base.moniker,
                    spec=self.crypto_spec,
                    timeout_ms=config.crypto.verify_service_timeout_ms,
                    retry_cap_s=config.crypto.verify_retry_cap_ms / 1e3,
                    probe_base_s=config.crypto.verify_probe_ms / 1e3,
                    auth_key=auth_key,
                    node_id=config.base.moniker,
                    tracer=self.tracer,
                    telemetry=self.telemetry_hub,
                    logger=self.logger,
                )
            else:
                self.remote_verifier = verify_servicelib.RemoteVerifier(
                    endpoints[0],
                    tenant=config.base.moniker,
                    spec=self.crypto_spec,
                    timeout_ms=config.crypto.verify_service_timeout_ms,
                    retry_cap_s=config.crypto.verify_retry_cap_ms / 1e3,
                    auth_key=auth_key,
                    node_id=config.base.moniker,
                    tracer=self.tracer,
                    telemetry=self.telemetry_hub,
                    logger=self.logger,
                )
                self.telemetry_hub.register_source(
                    "service", self.remote_verifier.snapshot
                )
            self.crypto_backend = self.remote_verifier

        # 1. stores
        self.block_store = BlockStore(db_provider("blockstore", config))
        self.state_store = StateStore(db_provider("state", config))

        # 2. state from DB or genesis — with the genesis doc's hash
        # pinned in the state DB on first boot (node.go:1394-1449
        # LoadStateFromDBOrGenesisDocProvider): booting existing data
        # against a DIFFERENT genesis must fail loudly up front, not
        # surface later as app-hash divergence. Only file-based boots
        # (default_new_node) pin: they hash the RAW file, which is
        # stable across boots. Direct embedders pass no hash and skip
        # the guard — the completed doc re-stamps a zero genesis_time
        # on every load, so a canonical-JSON fallback would refuse
        # perfectly valid reboots.
        if genesis_hash is not None:
            stored = self.state_store.load_genesis_doc_hash()
            if stored is None:
                self.state_store.save_genesis_doc_hash(genesis_hash)
            elif stored != genesis_hash:
                raise ValueError(
                    "genesis doc hash in db does not match loaded genesis doc"
                )
        state = self.state_store.load()
        if state is None:
            state = make_genesis_state(genesis_doc)
            self.state_store.save(state)

        # 3. proxy app + handshake
        self.proxy_app: AppConns = new_app_conns(client_creator)
        self.proxy_app.start()

        # 4. event bus (started before replay so indexers see replayed events)
        self.event_bus = EventBus()
        self.event_bus.start()

        # 4b. indexers + indexer service (node.go:742-747 — started before
        # the handshake on purpose so replayed blocks get indexed)
        from cometbft_tpu.state.indexer import (
            IndexerService,
            KVBlockIndexer,
            KVTxIndexer,
            NullTxIndexer,
        )

        if config.tx_index.indexer == "kv":
            self.tx_indexer = KVTxIndexer(db_provider("tx_index", config))
        else:
            self.tx_indexer = NullTxIndexer()
        self.block_indexer = KVBlockIndexer(
            db_provider("block_index", config)
        )
        self.indexer_service = IndexerService(
            self.tx_indexer, self.block_indexer, self.event_bus,
            logger=self.logger,
        )
        self.indexer_service.start()

        self._privval_endpoint = None
        Handshaker(
            self.state_store, state, self.block_store, genesis_doc,
            event_bus=self.event_bus, logger=self.logger,
        ).handshake(self.proxy_app)
        state = self.state_store.load() or state

        # 5. privval — a remote signer replaces the file-backed one
        # when priv_validator_laddr is set (node.go:755-761,1451)
        if config.base.priv_validator_laddr:
            from cometbft_tpu.privval.socket import (
                SignerClient,
                SignerListenerEndpoint,
            )

            endpoint = SignerListenerEndpoint(
                config.base.priv_validator_laddr, logger=self.logger
            )
            self._privval_endpoint = endpoint
            endpoint.wait_for_connection(30.0)
            priv_validator = SignerClient(endpoint, genesis_doc.chain_id)
        self.priv_validator = priv_validator
        pub_key = priv_validator.get_pub_key() if priv_validator else None

        fast_sync = config.base.fast_sync_mode and not _only_validator_is_us(
            state, pub_key
        )
        # state sync only makes sense from an empty chain (node.go:791-799)
        self.state_sync_enabled = (
            config.statesync.enable and state.last_block_height == 0
        )
        self.state_provider = state_provider

        # 6. mempool
        if config.mempool.version == "v1":
            from cometbft_tpu.mempool.priority_mempool import PriorityMempool

            mempool_cls = PriorityMempool
        else:
            mempool_cls = CListMempool
        self.mempool = mempool_cls(
            config.mempool, self.proxy_app.mempool(),
            height=state.last_block_height, metrics=mem_metrics,
        )
        self.mempool_reactor = MempoolReactor(config.mempool, self.mempool)

        # 7. evidence
        self.evidence_pool = EvidencePool(
            db_provider("evidence", config), self.state_store,
            self.block_store, crypto_backend=self.crypto_backend,
        )
        self.evidence_reactor = EvidenceReactor(self.evidence_pool)

        # 8. executor
        self.block_executor = BlockExecutor(
            self.state_store,
            self.proxy_app.consensus(),
            mempool=self.mempool,
            evidence_pool=self.evidence_pool,
            event_bus=self.event_bus,
            crypto_backend=self.crypto_backend,
            metrics=sm_metrics,
            logger=self.logger,
        )

        # 9. blocksync — held back when statesync will bootstrap first
        # (node.go:820: fastSync && !stateSync)
        self.blocksync_reactor = BlocksyncReactor(
            state, self.block_executor, self.block_store,
            fast_sync=fast_sync and not self.state_sync_enabled,
            crypto_backend=self.crypto_backend,
            logger=self.logger,
        )
        self._fast_sync_after_statesync = fast_sync
        if fast_sync and not self.state_sync_enabled:
            cons_metrics.fast_syncing.set(1)

        # 9b. statesync (serving side always on; restore when enabled)
        self.statesync_reactor = StateSyncReactor(
            config.statesync,
            self.proxy_app.snapshot(),
            self.proxy_app.query(),
            temp_dir=config.statesync.temp_dir or None,
            logger=self.logger,
        )

        # 10. consensus
        wal = (
            WAL(config.consensus.wal_file())
            if config.consensus.wal_path
            else NilWAL()
        )
        self.consensus_state = ConsensusState(
            config.consensus, state, self.block_executor, self.block_store,
            tx_notifier=self.mempool, evpool=self.evidence_pool, wal=wal,
            event_bus=self.event_bus,
            crypto_backend=self.crypto_backend, metrics=cons_metrics,
            logger=self.logger,
        )
        if priv_validator is not None:
            self.consensus_state.set_priv_validator(priv_validator)
        if (
            not config.consensus.create_empty_blocks
            or config.consensus.create_empty_blocks_interval_ns > 0
        ):
            # reference node.go WaitForTxs(): TxsAvailable is enabled
            # when empty blocks are off OR rate-limited by interval,
            # plus the push side the reference implements as consensus's
            # TxsAvailable-channel goroutine — without BOTH,
            # enterNewRound waits for a poke that never comes and the
            # chain stalls until the interval timeout (or forever, when
            # none is configured)
            self.mempool.enable_txs_available()
            self.mempool.on_txs_available = (
                self.consensus_state.notify_txs_available
            )
        self.consensus_reactor = ConsensusReactor(
            self.consensus_state,
            wait_sync=fast_sync or self.state_sync_enabled,
            gossip_sleep=config.consensus.peer_gossip_sleep_duration_ns / 1e9,
            query_maj23_sleep=(
                config.consensus.peer_query_maj23_sleep_duration_ns / 1e9
            ),
            logger=self.logger,
        )

        # 11. p2p
        adv_host, adv_port = _parse_laddr(
            config.p2p.external_address or config.p2p.laddr
        )
        node_info = NodeInfo(
            protocol_version=ProtocolVersion(),
            node_id=node_key.id(),
            listen_addr=f"{adv_host}:{adv_port}",
            network=genesis_doc.chain_id,
            channels=bytes(
                [
                    BLOCKSYNC_CHANNEL,
                    STATE_CHANNEL,
                    DATA_CHANNEL,
                    VOTE_CHANNEL,
                    VOTE_SET_BITS_CHANNEL,
                    MEMPOOL_CHANNEL,
                    EVIDENCE_CHANNEL,
                    SNAPSHOT_CHANNEL,
                    CHUNK_CHANNEL,
                ]
                + ([PEX_CHANNEL] if config.p2p.pex else [])
            ),
            moniker=config.base.moniker,
        )
        self.transport = MultiplexTransport(
            node_info, node_key,
            handshake_timeout=config.p2p.handshake_timeout_ns / 1e9,
            dial_timeout=config.p2p.dial_timeout_ns / 1e9,
            logger=self.logger,
        )
        mconfig = MConnConfig(
            send_rate=config.p2p.send_rate,
            recv_rate=config.p2p.recv_rate,
            max_packet_msg_payload_size=config.p2p.max_packet_msg_payload_size,
            flush_throttle=config.p2p.flush_throttle_timeout_ns / 1e9,
        )
        self.switch = Switch(
            self.transport,
            max_inbound_peers=config.p2p.max_num_inbound_peers,
            max_outbound_peers=config.p2p.max_num_outbound_peers,
            mconfig=mconfig,
            metrics=p2p_metrics,
            logger=self.logger,
        )
        self.switch.add_reactor("MEMPOOL", self.mempool_reactor)
        self.switch.add_reactor("BLOCKSYNC", self.blocksync_reactor)
        self.switch.add_reactor("CONSENSUS", self.consensus_reactor)
        self.switch.add_reactor("EVIDENCE", self.evidence_reactor)
        self.switch.add_reactor("STATESYNC", self.statesync_reactor)
        from cometbft_tpu.p2p.key import validate_id as _validate_id

        uncond = set()
        for p in config.p2p.unconditional_peer_ids.split(","):
            p = p.strip().lower()
            if not p:
                continue
            _validate_id(p)  # a malformed ID must fail config, not be inert
            uncond.add(p)
        self.switch.unconditional_peer_ids = uncond

        if not config.p2p.allow_duplicate_ip:
            # reference ConnDuplicateIPFilter: a second inbound conn from
            # an IP we already hold a peer on is refused at accept
            def _dup_ip_filter(sock) -> None:
                rip = sock.getpeername()[0]
                for p in self.switch.peers.list():
                    sa = p.socket_addr
                    if sa is not None and sa.ip == rip:
                        raise ValueError(f"duplicate IP {rip}")

            self.transport.conn_filters.append(_dup_ip_filter)

        if config.base.filter_peers:
            # reference createTransport (node.go:500): vet every conn by
            # address and every peer by ID through the app's Query conn;
            # non-OK code rejects — the knob was previously inert
            import concurrent.futures as _futures

            from cometbft_tpu.abci import types as _abci

            _query_conn = self.proxy_app.query()
            _filter_pool = _futures.ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="abci-peer-filter"
            )

            def _bounded_query(path: str) -> None:
                # reference filterTimeout (5s): a hung app Query must
                # drop ONE conn, not wedge the accept loop forever
                fut = _filter_pool.submit(
                    _query_conn.query_sync, _abci.RequestQuery(path=path)
                )
                try:
                    res = fut.result(timeout=5.0)
                except _futures.TimeoutError:
                    raise ValueError("abci peer filter timed out") from None
                if res.code != _abci.CODE_TYPE_OK:
                    raise ValueError(f"rejected by app: {res.code}")

            def _abci_addr_filter(sock) -> None:
                host, port = sock.getpeername()[:2]
                _bounded_query(f"/p2p/filter/addr/{host}:{port}")

            def _abci_id_filter(peer_id: str) -> None:
                _bounded_query(f"/p2p/filter/id/{peer_id}")

            self.transport.conn_filters.append(_abci_addr_filter)
            self.switch.peer_filters.append(_abci_id_filter)

        if config.p2p.test_fuzz:
            # fault injection for nets (reference p2p/fuzz.go + config
            # :663-684): every raw conn gets random delay/drop under the
            # secret connection — the knob was previously inert
            from cometbft_tpu.p2p.fuzz import FuzzConnConfig, FuzzedSocket

            fuzz_cfg = FuzzConnConfig()
            # grace period before fuzzing starts, "so we have time to do
            # peer handshakes and get set up" (reference testPeerConn
            # uses FuzzConnAfter with 10s) — fuzzing from byte 0 would
            # kill nearly every handshake and degenerate into no peering
            self.transport.conn_wrapper = (
                lambda c: FuzzedSocket(c, fuzz_cfg, start_after=10.0)
            )

        # 12. PEX + addrbook
        self.pex_reactor = None
        self.addr_book = None
        if config.p2p.pex:
            self.addr_book = AddrBook(
                file_path=os.path.join(
                    config.root_dir, config.p2p.addr_book_file
                )
                if config.root_dir
                else "",
                routability_strict=config.p2p.addr_book_strict,
            )
            seeds = [
                s.strip() for s in config.p2p.seeds.split(",") if s.strip()
            ]
            # reference node.go createAddrBookAndSetOnSwitch: our own
            # advertised address never re-enters the book (self-dial /
            # self-gossip guard), and operator-marked private peers are
            # excluded from PEX gossip — without these the
            # private_peer_ids knob is inert and sentry-protected
            # validators leak
            # BOTH the advertised (external) and listen addresses are
            # ours, resolved the way peers would record them — a
            # hostname external_address re-gossiped in resolved-IP form
            # must still match the guard
            for raw_addr in {config.p2p.external_address, config.p2p.laddr}:
                if not raw_addr:
                    continue
                own_host, own_port = _parse_laddr(raw_addr)
                try:
                    own = NetAddress.from_string(
                        f"{node_key.id()}@{own_host}:{own_port}"
                    )
                except (ValueError, OSError):
                    own = NetAddress(node_key.id(), own_host, own_port)
                self.addr_book.add_our_address(own)
            private_ids = [
                p.strip()
                for p in config.p2p.private_peer_ids.split(",")
                if p.strip()
            ]
            if private_ids:
                self.addr_book.add_private_ids(private_ids)
            self.pex_reactor = PEXReactor(
                self.addr_book,
                seeds=seeds,
                seed_mode=config.p2p.seed_mode,
            )
            self.switch.add_reactor("PEX", self.pex_reactor)
            self.switch.addr_book = self.addr_book

        # 13. RPC
        self.rpc_server = None
        if config.rpc.laddr:
            from cometbft_tpu.rpc.core import Environment
            from cometbft_tpu.rpc.server import RPCServer

            env = Environment(self)
            self.rpc_server = RPCServer(env, logger=self.logger)
        self.grpc_broadcast_server = None
        if config.rpc.grpc_laddr:
            from cometbft_tpu.rpc.grpc_api import BroadcastAPIServer

            self.grpc_broadcast_server = BroadcastAPIServer(
                config.rpc.grpc_laddr, self
            )

    # -- lifecycle ----------------------------------------------------------

    def _abort_init(self) -> None:
        """Best-effort teardown of the services __init__ already started."""
        for svc in (
            getattr(self, "_privval_endpoint", None),
            getattr(self, "indexer_service", None),
            getattr(self, "event_bus", None),
            getattr(self, "proxy_app", None),
        ):
            if svc is None:
                continue
            try:
                if hasattr(svc, "is_running") and not svc.is_running():
                    continue
                (svc.stop if hasattr(svc, "stop") else svc.close)()
            except Exception:
                pass
        for db in getattr(self, "_dbs", ()):
            try:
                db.close()
            except Exception:
                pass

    def on_start(self) -> None:
        # the verification coalescer goes live before any reactor that
        # can carry signatures (blocksync starts verifying immediately
        # after switch.start); submit() degrades to inline dispatch when
        # the service is down, so ordering is a perf matter, not safety
        self.verify_scheduler.start()
        if self.crypto_spec.name == "tpu":
            # prove the device plane end-to-end (known-good signed batch)
            # off the startup path; a failure trips the breaker before
            # the first real commit instead of during it
            self.verify_supervisor.warmup_canary()
        host, port = _parse_laddr(self.config.p2p.laddr)
        self.transport.listen(NetAddress(self.node_key.id(), host, port))
        if self.addr_book is not None:
            self.addr_book.start()
        self.switch.start()
        persistent = [
            p.strip()
            for p in self.config.p2p.persistent_peers.split(",")
            if p.strip()
        ]
        if persistent:
            addrs = self.switch.add_persistent_peers(persistent)
            self.switch.dial_peers_async(addrs)
        if self.rpc_server is not None:
            host, port = _parse_laddr(self.config.rpc.laddr)
            self.rpc_server.serve(host, port)
        if self.grpc_broadcast_server is not None:
            self.grpc_broadcast_server.start()
        if self.config.rpc.pprof_laddr:
            from cometbft_tpu.libs.debug import PprofServer

            host, port = _parse_laddr(self.config.rpc.pprof_laddr)
            self.pprof_server = PprofServer()
            self.pprof_server.serve(host, port)
        if self.metrics_registry is not None:
            from cometbft_tpu.libs.metrics import MetricsServer

            host, port = _parse_laddr(
                self.config.instrumentation.prometheus_listen_addr
            )
            self.metrics_server = MetricsServer(
                self.metrics_registry,
                tracer=self.tracer,
                telemetry=self.telemetry_hub,
                profiler=self.profiler,
            )
            self.metrics_server.serve(host, port)
        if self.state_sync_enabled:
            self._start_state_sync()

    def _start_state_sync(self) -> None:
        """node.go:651 startStateSync — restore a snapshot asynchronously,
        bootstrap the stores, then hand off to blocksync/consensus."""
        if self.state_provider is None:
            ss_cfg = self.config.statesync
            if len(ss_cfg.rpc_servers) >= 2 and ss_cfg.trust_hash:
                # build the light-client provider from [statesync]
                # rpc_servers + trust root (node.go:655-672)
                from cometbft_tpu.light.client import TrustOptions
                from cometbft_tpu.light.provider import HTTPProvider
                from cometbft_tpu.statesync import LightClientStateProvider

                providers = [
                    HTTPProvider(self.genesis_doc.chain_id, s)
                    for s in ss_cfg.rpc_servers
                ]
                from cometbft_tpu.state import StateVersion

                # only .software is taken from this; the consensus/app
                # versions come from the verified light-block headers
                self.state_provider = LightClientStateProvider(
                    self.genesis_doc.chain_id,
                    StateVersion(),
                    self.genesis_doc.initial_height,
                    providers,
                    TrustOptions(
                        period_ns=ss_cfg.trust_period_ns,
                        height=ss_cfg.trust_height,
                        hash=bytes.fromhex(ss_cfg.trust_hash),
                    ),
                    crypto_backend=self.crypto_backend,
                    logger=self.logger,
                )
            else:
                raise RuntimeError(
                    "statesync enabled but no state provider: set "
                    "[statesync] rpc_servers (>=2) + trust_height/"
                    "trust_hash, or construct the Node with "
                    "state_provider=LightClientStateProvider(...)"
                )
        import threading

        metrics = self.consensus_state.metrics
        metrics.state_syncing.set(1)

        def fail_over(msg: str, exc: Exception):
            # A dead statesync must not wedge the node in wait-sync
            # forever (the reference treats startStateSync failure as
            # fatal): clear the gauge and fall back to blocksync /
            # consensus from the untouched pre-sync state, loudly.
            self.logger.error(
                msg + " — falling back to block sync", err=str(exc)
            )
            metrics.state_syncing.set(0)
            try:
                state = self.state_store.load()
                if self._fast_sync_after_statesync:
                    metrics.fast_syncing.set(1)
                    self.blocksync_reactor.switch_to_fast_sync(state)
                else:
                    self.consensus_reactor.switch_to_consensus(state, True)
            except Exception as exc2:  # noqa: BLE001
                self.logger.error(
                    "statesync fail-over itself failed — stopping node",
                    err=str(exc2),
                )
                threading.Thread(target=self.stop, daemon=True).start()

        def run():
            try:
                state, commit = self.statesync_reactor.sync(
                    self.state_provider,
                    self.config.statesync.discovery_time_ns / 1e9,
                )
            except Exception as exc:
                fail_over("state sync failed", exc)
                return
            try:
                self.state_store.bootstrap(state)
                self.block_store.save_seen_commit(
                    state.last_block_height, commit
                )
            except Exception as exc:
                # the stores may be half-bootstrapped; resuming consensus
                # from them is unsafe — treat as fatal like the reference
                self.logger.error(
                    "FATAL: failed to bootstrap node with new state — "
                    "stopping node",
                    err=str(exc),
                )
                metrics.state_syncing.set(0)
                threading.Thread(target=self.stop, daemon=True).start()
                return
            metrics.state_syncing.set(0)
            if self._fast_sync_after_statesync:
                metrics.fast_syncing.set(1)
                self.blocksync_reactor.switch_to_fast_sync(state)
            else:
                self.consensus_reactor.switch_to_consensus(state, True)

        threading.Thread(
            target=run, name="statesync", daemon=True
        ).start()

    def on_stop(self) -> None:
        for svc in (
            getattr(self, "pprof_server", None),
            getattr(self, "metrics_server", None),
            getattr(self, "grpc_broadcast_server", None),
            self.rpc_server,
            self.switch,
            self.addr_book,
            self.indexer_service,
            self.event_bus,
            self.proxy_app,
        ):
            if svc is None:
                continue
            try:
                if hasattr(svc, "is_running") and not svc.is_running():
                    continue
                svc.stop()
            except Exception as exc:
                self.logger.error("error stopping service", err=str(exc))
        if self.consensus_state.is_running():
            self.consensus_state.stop()
        # the remote verifier first: close() fails any still-pending
        # requests over to the local-CPU fallback before the scheduler
        # (its spec donor) drains
        if self.remote_verifier is not None:
            try:
                self.remote_verifier.close()
            except Exception as exc:
                self.logger.error(
                    "error closing remote verifier", err=str(exc)
                )
        # after every verification-carrying service: stop() drains the
        # queue (dispatching, not abandoning), so no future hangs
        if self.verify_scheduler.is_running():
            try:
                self.verify_scheduler.stop()
            except Exception as exc:
                self.logger.error(
                    "error stopping verify scheduler", err=str(exc)
                )
        try:
            self.verify_supervisor.stop()
        except Exception as exc:
            self.logger.error(
                "error stopping verify supervisor", err=str(exc)
            )
        # uninstall OUR telemetry hub from the process default so a
        # later node (or test) never feeds a stopped node's plane; a
        # hub another owner installed meanwhile is left alone
        try:
            from cometbft_tpu.crypto import telemetry as telemetrylib

            if telemetrylib.default_hub() is self.telemetry_hub:
                telemetrylib.set_default_hub(None)
        except Exception:  # noqa: BLE001 - teardown is best-effort
            pass
        # same for the wire ledger — a later node's dispatches must not
        # fold into a stopped node's cost profiles
        try:
            from cometbft_tpu.crypto import wire as wirelib

            ledger = getattr(self, "wire_ledger", None)
            if ledger is not None and wirelib.default_ledger() is ledger:
                wirelib.set_default_ledger(None)
        except Exception:  # noqa: BLE001 - teardown is best-effort
            pass
        # same for the decision ledger — a later node's flushes must
        # not fold into a stopped node's accuracy profiles
        try:
            from cometbft_tpu.crypto import decisions as declib

            dledger = getattr(self, "decision_ledger", None)
            if dledger is not None and declib.default_ledger() is dledger:
                declib.set_default_ledger(None)
        except Exception:  # noqa: BLE001 - teardown is best-effort
            pass
        # same for the memory plane — and fold what it LEARNED (observed
        # per-bucket footprints) into the calibration table first, so
        # the next boot's pre-dispatch guard starts from measured peaks
        # instead of the static Straus estimate
        try:
            from cometbft_tpu.crypto.tpu import calibrate as caliblib
            from cometbft_tpu.crypto.tpu import memory as memlib

            plane = getattr(self, "memory_plane", None)
            if plane is not None:
                footprints = plane.export_footprints()
                if footprints:
                    caliblib.merge_memory_footprints(footprints)
                if memlib.default_plane() is plane:
                    memlib.set_default_plane(None)
        except Exception:  # noqa: BLE001 - teardown is best-effort
            pass
        # the AOT warm boot checks its stop event between compiles, so
        # this join is bounded by one in-flight compile (or the
        # calibration sweep after it — the thread is a daemon either way)
        try:
            from cometbft_tpu.crypto.tpu import aot as aotlib

            if not aotlib.stop_warm_boot(timeout=10.0):
                self.logger.info("warm boot still compiling at stop; "
                                 "abandoned as daemon")
        except Exception as exc:
            self.logger.error("error stopping warm boot", err=str(exc))
        if self._privval_endpoint is not None:
            self._privval_endpoint.close()
        # release DB file locks so maintenance commands (rollback,
        # reindex-event) can open the same files from another process
        for db in self._dbs:
            try:
                db.close()
            except Exception:
                pass

    # -- introspection (used by RPC) -----------------------------------------

    def listen_addr(self) -> Optional[NetAddress]:
        return self.transport.listen_addr

    def is_syncing(self) -> bool:
        return self.consensus_reactor.wait_sync()


def _only_validator_is_us(state: State, pub_key) -> bool:
    """node.go onlyValidatorIsUs — no point fast-syncing a 1-validator
    chain where we're the validator."""
    if pub_key is None:
        return False
    if state.validators.size() != 1:
        return False
    return state.validators.validators[0].address == pub_key.address()


def default_db_provider(name: str, config: Config) -> DB:
    if config.base.db_backend == "memdb":
        return MemDB()
    data_dir = os.path.join(config.root_dir, config.base.db_dir)
    os.makedirs(data_dir, exist_ok=True)
    return SQLiteDB(os.path.join(data_dir, f"{name}.db"))


def _warm_tpu_kernels(config: Config) -> None:
    """Arm the device plane at node start (the AOT warm boot,
    crypto/tpu/aot.py), in the node's OWN process: a chip belongs to one
    process at a time, so a helper process that compiles or calibrates
    on the device would either take the chip from the node or fail
    against the node holding it.

    The warm boot pre-compiles the buckets a routed flush can pad to —
    [crypto] min_batch's bucket up to max_chunk, the canary's bucket
    first (single-device + sharded variants) — into the process's
    executable registry, so the first real commit is a registry hit;
    then ``calibrate.record`` times device vs host from the largest size
    down and writes the routing table (a size it sweeps that the ladder
    did not cover compiles before it is timed, never while), and the
    per-bucket compile seconds are folded in beside it. Compiled
    programs persist in the compile cache (aot.compile_cache_dir) and
    the executable store under it, so a restart loads instead of
    compiling. A failure is logged and leaves ``WarmBoot.error`` set;
    dispatch then compiles on demand, outside its watchdog's clock.

    The supervisor's warmup canary (on_start) joins the warm boot
    before declaring HEALTHY; on_stop stops it with a bounded join.
    [crypto] warm_boot = eager|background|off (CBFT_WARM_BOOT env wins)
    selects blocking/threaded/disabled. A curve's kernels are warmed
    only where the genesis consensus params' ``validator.pub_key_types``
    admit its keys."""
    from cometbft_tpu.crypto.tpu import aot, calibrate

    calib_path = calibrate.table_path()
    floor = int(config.crypto.min_batch)
    key_types = tuple(_genesis_key_types(config))

    def body(stop_event):
        obs = aot.run_warm_boot(
            floor=floor, stop_event=stop_event, key_types=key_types
        )
        if stop_event.is_set():
            return obs
        # routing reads the table lazily by mtime
        calibrate.record(calib_path)
        calibrate.merge_compile_times(obs, calib_path)
        return obs

    aot.start_warm_boot(
        aot.warm_boot_mode(config.crypto.warm_boot), body=body
    )


def _genesis_key_types(config: Config):
    """The validator key types the genesis consensus params admit."""
    with open(config.base.genesis_path(), "rb") as f:
        doc = GenesisDoc.from_json(f.read().decode())
    params = doc.consensus_params or default_consensus_params()
    return params.validator.pub_key_types


def default_new_node(config: Config, logger: Optional[Logger] = None) -> Node:
    """Reference: node/node.go:100 DefaultNewNode — everything from files
    under the config root."""
    # one node per process here, so the process-wide default backend can
    # follow [crypto] — programmatic multi-node embedders get per-node
    # threading through the constructors instead
    from cometbft_tpu.crypto import batch as cryptobatch

    cryptobatch.set_default_backend(config.crypto.backend)
    # [crypto] min_batch reaches the batch plane through the BackendSpec
    # the Node threads to every consumer (crypto/batch.py) — NOT through
    # os.environ.setdefault, which made in-process multi-node setups
    # silently share the first node's value. max_chunk tunes the shared
    # dispatch layer (a link property — one value per process).
    if config.crypto.backend == "tpu":
        from cometbft_tpu.crypto.tpu import calibrate
        from cometbft_tpu.crypto.tpu import mesh as tpu_mesh

        # the node is the process that will dispatch: resolve what jax
        # gave it now, and refuse to start behind a device name when it
        # is not a TPU (unless JAX_PLATFORMS asks for cpu on purpose)
        tpu_mesh.require_accelerator('[crypto] backend = "tpu"')
        tpu_mesh.configure_chunk_cap(config.crypto.max_chunk)
        calibrate.set_table_path(
            os.path.join(config.root_dir, "data", "tpu_calibration.json")
        )
        _warm_tpu_kernels(config)

    node_key = NodeKey.load_or_gen(
        os.path.join(config.root_dir, config.base.node_key_file)
    )
    priv_validator = load_or_gen_file_pv(
        config.base.priv_validator_key_path(),
        config.base.priv_validator_state_path(),
    )
    with open(config.base.genesis_path(), "rb") as f:
        raw_genesis = f.read()
    import hashlib as _hashlib

    genesis_doc = GenesisDoc.from_json(raw_genesis.decode())
    app_db = default_db_provider("app", config)
    try:
        node = Node(
            config,
            priv_validator,
            node_key,
            default_client_creator(
                config.base.proxy_app, app_db, transport=config.base.abci
            ),
            genesis_doc,
            logger=logger,
            genesis_hash=_hashlib.sha256(raw_genesis).digest(),
        )
    except Exception:
        # Node's own abort path closes provider-tracked DBs; the app DB
        # opened above is ours to release
        try:
            app_db.close()
        except Exception:
            pass
        raise
    # the app DB is created outside Node's tracking provider; register it
    # so on_stop releases its file locks too
    node._dbs.append(app_db)
    return node
