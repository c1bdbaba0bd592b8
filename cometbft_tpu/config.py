"""Node configuration — 9 sections, TOML-serialized.

Reference: config/config.go:66-81 (master Config), defaults per section
(Base :228, RPC :440, P2P :563, Mempool :697, StateSync :771, FastSync
:844, Consensus :969-1037, TxIndex :1112, Instrumentation :1141) and the
TOML writer config/toml.go. Durations are stored in nanoseconds like Go's
time.Duration; TOML round-trips them as "300ms"/"10s" strings.

New in this framework: the [crypto] section selecting the signature-
verification backend ("cpu" | "tpu") — SURVEY.md §7's plugin boundary.
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass, field
from typing import List, Optional

_SECOND = 1_000_000_000
_MS = 1_000_000


def duration_to_str(ns: int) -> str:
    if ns % _SECOND == 0:
        return f"{ns // _SECOND}s"
    if ns % _MS == 0:
        return f"{ns // _MS}ms"
    return f"{ns}ns"


def parse_duration(s: str) -> int:
    """Go-style duration string → nanoseconds."""
    if isinstance(s, (int, float)):
        return int(s)
    units = {
        "ns": 1, "us": 1_000, "µs": 1_000, "ms": _MS, "s": _SECOND,
        "m": 60 * _SECOND, "h": 3600 * _SECOND,
    }
    total = 0
    pos = 0
    token = re.compile(r"([\d.]+)(ns|us|µs|ms|s|m|h)")
    while pos < len(s):
        m = token.match(s, pos)
        if m is None:
            raise ValueError(f"invalid duration {s!r}")
        total += int(float(m.group(1)) * units[m.group(2)])
        pos = m.end()
    return total


@dataclass
class BaseConfig:
    """[top-level] (config/config.go:145-226)."""

    root_dir: str = ""
    proxy_app: str = "tcp://127.0.0.1:26658"
    moniker: str = "anonymous"
    fast_sync_mode: bool = True
    db_backend: str = "sqlite"
    db_dir: str = "data"
    log_level: str = "info"
    log_format: str = "plain"
    genesis_file: str = "config/genesis.json"
    priv_validator_key_file: str = "config/priv_validator_key.json"
    priv_validator_state_file: str = "data/priv_validator_state.json"
    priv_validator_laddr: str = ""
    node_key_file: str = "config/node_key.json"
    abci: str = "socket"  # "socket" | "grpc" | "builtin"
    filter_peers: bool = False

    def genesis_path(self) -> str:
        return os.path.join(self.root_dir, self.genesis_file)

    def priv_validator_key_path(self) -> str:
        return os.path.join(self.root_dir, self.priv_validator_key_file)

    def priv_validator_state_path(self) -> str:
        return os.path.join(self.root_dir, self.priv_validator_state_file)

    def node_key_path(self) -> str:
        return os.path.join(self.root_dir, self.node_key_file)

    def db_path(self) -> str:
        return os.path.join(self.root_dir, self.db_dir)


@dataclass
class RPCConfig:
    laddr: str = "tcp://127.0.0.1:26657"
    cors_allowed_origins: List[str] = field(default_factory=list)
    grpc_laddr: str = ""
    unsafe: bool = False
    max_open_connections: int = 900
    max_subscription_clients: int = 100
    max_subscriptions_per_client: int = 5
    timeout_broadcast_tx_commit_ns: int = 10 * _SECOND
    max_body_bytes: int = 1000000
    max_header_bytes: int = 1 << 20
    pprof_laddr: str = ""


@dataclass
class P2PConfig:
    laddr: str = "tcp://0.0.0.0:26656"
    external_address: str = ""
    seeds: str = ""
    persistent_peers: str = ""
    upnp: bool = False
    addr_book_file: str = "config/addrbook.json"
    addr_book_strict: bool = True
    max_num_inbound_peers: int = 40
    max_num_outbound_peers: int = 10
    unconditional_peer_ids: str = ""
    persistent_peers_max_dial_period_ns: int = 0
    flush_throttle_timeout_ns: int = 100 * _MS
    max_packet_msg_payload_size: int = 1024
    send_rate: int = 5120000  # 5 MB/s
    recv_rate: int = 5120000
    pex: bool = True
    seed_mode: bool = False
    private_peer_ids: str = ""
    allow_duplicate_ip: bool = False
    handshake_timeout_ns: int = 20 * _SECOND
    dial_timeout_ns: int = 3 * _SECOND
    test_fuzz: bool = False


@dataclass
class MempoolConfig:
    version: str = "v0"
    recheck: bool = True
    broadcast: bool = True
    wal_dir: str = ""
    size: int = 5000
    max_txs_bytes: int = 1073741824  # 1GB
    cache_size: int = 10000
    keep_invalid_txs_in_cache: bool = False
    max_tx_bytes: int = 1048576  # 1MB
    max_batch_bytes: int = 0
    ttl_duration_ns: int = 0
    ttl_num_blocks: int = 0


@dataclass
class StateSyncConfig:
    enable: bool = False
    rpc_servers: List[str] = field(default_factory=list)
    trust_height: int = 0
    trust_hash: str = ""
    trust_period_ns: int = 168 * 3600 * _SECOND  # 168h0m0s
    discovery_time_ns: int = 15 * _SECOND
    temp_dir: str = ""
    chunk_request_timeout_ns: int = 10 * _SECOND
    chunk_fetchers: int = 4


@dataclass
class FastSyncConfig:
    version: str = "v0"


@dataclass
class ConsensusConfig:
    """[consensus] (config/config.go:969-1037). Round-scaled accessors
    mirror the reference's Propose(round)/Prevote(round)/Precommit(round)."""

    wal_path: str = "data/cs.wal/wal"
    root_dir: str = ""
    timeout_propose_ns: int = 3 * _SECOND
    timeout_propose_delta_ns: int = 500 * _MS
    timeout_prevote_ns: int = 1 * _SECOND
    timeout_prevote_delta_ns: int = 500 * _MS
    timeout_precommit_ns: int = 1 * _SECOND
    timeout_precommit_delta_ns: int = 500 * _MS
    timeout_commit_ns: int = 1 * _SECOND
    skip_timeout_commit: bool = False
    create_empty_blocks: bool = True
    create_empty_blocks_interval_ns: int = 0
    peer_gossip_sleep_duration_ns: int = 100 * _MS
    peer_query_maj23_sleep_duration_ns: int = 2 * _SECOND
    double_sign_check_height: int = 0

    def propose_timeout(self, round_: int) -> float:
        return (
            self.timeout_propose_ns + self.timeout_propose_delta_ns * round_
        ) / _SECOND

    def prevote_timeout(self, round_: int) -> float:
        return (
            self.timeout_prevote_ns + self.timeout_prevote_delta_ns * round_
        ) / _SECOND

    def precommit_timeout(self, round_: int) -> float:
        return (
            self.timeout_precommit_ns + self.timeout_precommit_delta_ns * round_
        ) / _SECOND

    def commit_time(self) -> float:
        return self.timeout_commit_ns / _SECOND

    def wal_file(self) -> str:
        return os.path.join(self.root_dir, self.wal_path)


@dataclass
class TxIndexConfig:
    indexer: str = "kv"  # "kv" | "null"
    psql_conn: str = ""


@dataclass
class InstrumentationConfig:
    prometheus: bool = False
    prometheus_listen_addr: str = ":26660"
    max_open_connections: int = 3
    namespace: str = "cometbft"
    # Verify-path tracing (libs/trace.py): fraction of verify requests
    # that open a sampled trace (0 disables tracing entirely — the hot
    # path then costs one attribute check; 1 traces everything). An
    # explicitly-set CBFT_TRACE_SAMPLE env var wins.
    trace_sample: float = 0.0
    # Flight-recorder capacity: how many COMPLETED traces the in-memory
    # ring buffer retains for /debug/traces and incident dumps.
    # CBFT_TRACE_BUFFER env wins.
    trace_buffer: int = 256
    # SLO engine (crypto/telemetry.py): rolling-window p50/p99 commit-
    # verify latency is judged against this target; the burn-rate gauge
    # reads how fast the error budget is being spent. Default = the ZKP
    # runtime study's p50 commit-verify bar. CBFT_SLO_COMMIT_MS wins.
    slo_commit_ms: int = 100
    # Incident dump retention: trace_dump_*.json files kept in
    # NODE_HOME/data (newest N; older dumps deleted at write time).
    # CBFT_TRACE_DUMP_KEEP env wins.
    trace_dump_keep: int = 20
    # Memory-plane poll period (crypto/tpu/memory.py): device
    # memory_stats() is read at most once per this many milliseconds,
    # lazily from whichever dispatch touches the plane first — no
    # background thread. CBFT_MEM_POLL_MS env wins.
    mem_poll_ms: int = 500
    # Incident profiler auto-capture threshold (libs/profiling.py): a
    # bounded one-shot jax.profiler capture fires when the SLO
    # error-budget burn rate crosses this value. 0 disables
    # auto-capture (the /debug/profile endpoint still works).
    # CBFT_PROFILE_ON_BURN env wins.
    profile_on_burn: float = 0.0
    # Profiler capture retention: profile_* capture dirs kept in
    # NODE_HOME/data/profiles (newest N — captures are an order of
    # magnitude bigger than trace dumps). CBFT_PROFILE_KEEP env wins.
    profile_keep: int = 4
    # Wire ledger (crypto/wire.py): continuous per-phase dispatch
    # attribution (pack / h2d / compute / d2h / demux) with EWMA cost
    # profiles per (route, bucket, device) — feeds /debug/verify,
    # verify_wire_* metrics, and the CostProfile API. Off = the mesh
    # hot path pays one module-attribute read per dispatch.
    # CBFT_WIRE_LEDGER env wins.
    wire_ledger: bool = True
    # EWMA window (in chunk observations) for the wire ledger's cost
    # profiles: alpha = 2/(window+1). CBFT_WIRE_WINDOW env wins.
    wire_window: int = 64
    # Decision ledger (crypto/decisions.py): per-flush RouteDecision
    # records with per-candidate predicted cost, prediction error,
    # counterfactual regret, the time-series ring, and the anomaly
    # watchdog. Off = one module-attribute read per flush.
    # CBFT_DECISION_LEDGER env wins.
    decision_ledger: bool = True
    # Rolling decision window (in finished decisions) behind the
    # windowed MAPE / regret rate and the EWMA accuracy profiles.
    # CBFT_DECISION_WINDOW env wins.
    decision_window: int = 64
    # Anomaly-watchdog trip level: windowed prediction MAPE above this
    # marks the router's world-model stale and fires one incident
    # capture (hysteretic: re-arms after clean windows below half).
    # CBFT_DECISION_MAPE_TRIP env wins.
    decision_mape_trip: float = 2.0


@dataclass
class CryptoConfig:
    """[crypto] — NEW: signature-verification backend selection
    (SURVEY.md §7; no reference counterpart — v0.34 has no batch plane)."""

    backend: str = "cpu"  # "cpu" | "tpu"
    # Below min_batch ed25519 signatures, a batch routes to the CPU
    # plane (the device dispatch round-trip dominates small batches).
    # Default = the measured on-chip crossover under the slower
    # observed link floor (SMALLBATCH_onchip.jsonl; crypto/batch.py).
    # Threaded per-node via BackendSpec (crypto/batch.py) — an
    # explicitly-set CBFT_TPU_MIN_BATCH env var still wins for
    # operator A/B overrides.
    min_batch: int = 1024
    # Dispatch chunk cap for the double-buffered pipeline (crypto/tpu/
    # mesh.py): batches larger than this split into chunks whose host
    # packing + async H2D overlaps the previous chunk's device compute.
    # Default = the measured 8k sweet spot (two pipelined 8k chunks beat
    # one 16k dispatch ~1.8× on the round-5 shared chip — MAXCHUNK16K.jsonl).
    # Rounded up to a power of two at the dispatch layer; an
    # explicitly-set CBFT_TPU_MAX_CHUNK env var wins.
    max_chunk: int = 8192
    # Deadline (µs) the node-wide verification scheduler
    # (crypto/scheduler.py) holds a pending request open for the chance
    # of coalescing with other subsystems' submissions before flushing
    # a partial dispatch. Bounds the extra latency a lone request pays;
    # an explicitly-set CBFT_VERIFY_FLUSH_US env var wins.
    flush_us: int = 500
    # --- BackendSupervisor knobs (crypto/supervisor.py) ---
    # Watchdog budget (ms) per device dispatch: past it the dispatch is
    # abandoned to a zombie thread, the batch re-verifies on CPU, and
    # the incident counts against the breaker. CBFT_DISPATCH_TIMEOUT_MS
    # env wins. Time the dispatch spends building an executable (a cold
    # bucket compiles for 45-85 s on a v5e) is host work and is not
    # counted against it (crypto/tpu/aot.py BuildClock).
    dispatch_timeout_ms: int = 60000
    # Consecutive dispatch failures that open the circuit breaker
    # (HEALTHY → BROKEN; watchdog trips and audit mismatches open it
    # immediately regardless). CBFT_BREAKER_THRESHOLD env wins.
    breaker_threshold: int = 3
    # Percentage of healthy device batches re-verified on CPU in the
    # background to catch silent verdict corruption (a miscompiled
    # kernel that accepts bad signatures without raising). 0 disables;
    # 100 audits every batch. CBFT_AUDIT_PCT env wins.
    audit_pct: int = 5
    # Pending-signature bound on the scheduler's submission queue:
    # past it submit() blocks (bounded by CBFT_SUBMIT_TIMEOUT_MS)
    # instead of growing without limit while the device plane stalls.
    # CBFT_MAX_QUEUE env wins.
    max_queue: int = 65536
    # Hedged verification: when a device dispatch overruns predicted
    # p99 × hedge_pct/100, the supervisor races the CPU verifier in
    # parallel and releases whichever mask finishes first (the loser is
    # audited for divergence). 0 disables hedging; dispatch_timeout_ms
    # stays the last-resort bound. CBFT_HEDGE_PCT env wins.
    hedge_pct: int = 200
    # Base backoff before retrying a transient-classified device error
    # (UNAVAILABLE/DEADLINE_EXCEEDED/runtime flaps); actual delay is
    # jittered in [0.5x, 1.5x). One retry, then the breaker ladder.
    # CBFT_RETRY_MS env wins.
    retry_ms: int = 25
    # Chunk-cap recovery hysteresis: after an OOM halves the effective
    # dispatch chunk cap, the cap recovers one doubling per this many
    # consecutive clean device dispatches. CBFT_CHUNK_RECOVER_N env wins.
    chunk_recover_n: int = 32
    # Fault domains the supervisor shards its breaker/retry/shrink state
    # over (crypto/tpu/topology.py). 1 = single-device behavior
    # (default); N > 1 = N virtual domains sharing the batch axis;
    # 0 = auto-detect from the visible device plane at startup.
    # CBFT_FAULT_DOMAINS env wins.
    fault_domains: int = 1
    # Coalesced-flush size at which the scheduler routes a dispatch to
    # the multi-device sharded mesh (ONE program sharded over every
    # healthy fault domain) instead of a single chip. 0 = auto: use the
    # per-topology crossover learned by calibrate.py's sharded sweep,
    # falling back to 4096. CBFT_SHARD_MIN_BATCH env wins;
    # CBFT_MESH_ROUTE=single|sharded overrides the decision entirely.
    shard_min_batch: int = 0
    # Live router for the verification scheduler (crypto/scheduler.py):
    # "priced" (default) takes the cheapest decision-ledger-priced
    # feasible candidate per coalesced flush (falling back to the
    # threshold ladder while cold, and rolling back hysteretically when
    # the anomaly watchdog says the cost model is stale); "threshold"
    # keeps the legacy comparison pile as the only router. CBFT_ROUTER
    # env wins; CBFT_MESH_ROUTE pins beat either router.
    router: str = "priced"
    # AOT warm-boot phase (crypto/tpu/aot.py): pre-lower and compile the
    # pow2 shape-bucket ladder before traffic arrives so no dispatch
    # ever pays trace+compile. "background" (default) warms on a thread
    # the supervisor's warmup canary joins before declaring HEALTHY;
    # "eager" blocks node start until warm; "off" disables. CBFT_WARM_BOOT
    # env wins; CBFT_TPU_WARMUP=0 (legacy kill switch) still forces off.
    warm_boot: str = "background"
    # QoS admission control for the verification scheduler
    # (crypto/qos.py): "default" = the built-in priority ladder
    # (consensus > evidence > blocksync > light > mempool, each with its
    # own overload policy), "off" = the legacy single FIFO, or an
    # explicit comma-separated "name[:policy[:max_queue[:weight]]]"
    # spec whose order is the priority order. CBFT_QOS_CLASSES env wins.
    qos_classes: str = "default"
    # Per-tenant token-bucket quota (signatures/sec refill; burst = 2×)
    # keyed by the subsystem origin tag. 0 = quotas off. Block-policy
    # classes are never throttled — over-quota submits there are only
    # counted. CBFT_QOS_TENANT_RATE env wins.
    qos_tenant_rate: int = 0
    # Shared verify daemon (crypto/service.py / tools/verifyd.py):
    # "unix:///path.sock" or "tcp://host:port" points consensus
    # preverify, blocksync, light, and mempool verification at a remote
    # VerifyService (cross-client megabatch coalescing over one device
    # pool) instead of the in-process scheduler, with local-CPU fallback
    # on disconnect/timeout. A COMMA list of addresses turns the client
    # into the HA replica-set verifier (crypto/ha.py): per-endpoint
    # breakers + health probes, failover to a healthy secondary above
    # the local-CPU rung. "" (default) = in-process.
    # CBFT_VERIFY_SERVICE env wins.
    verify_service: str = ""
    # Per-request deadline before the remote verifier gives up on the
    # daemon and falls back to local CPU.
    # CBFT_VERIFY_SERVICE_TIMEOUT_MS env wins.
    verify_service_timeout_ms: int = 2000
    # Per-node key file for the verify service's HMAC session auth:
    # when set, the client answers the daemon's HELLO challenge with
    # HMAC(key, challenge ‖ node_id) and the authenticated node id
    # becomes the tenant identity (quotas/RED survive reconnects and
    # NAT). "" = no auth (v1 interop). CBFT_VERIFY_AUTH_KEY env wins.
    verify_auth_key: str = ""
    # Reconnect backoff ceiling for the verify-service client: retries
    # back off exponentially with jitter from 1s up to this cap, so a
    # dead daemon is not hammered by every node in lockstep.
    verify_retry_cap_ms: int = 30_000
    # HA fleet probe cadence base: a breaker-quarantined or draining
    # endpoint is probed with capped exponential backoff starting here.
    verify_probe_ms: int = 250


@dataclass
class Config:
    base: BaseConfig = field(default_factory=BaseConfig)
    rpc: RPCConfig = field(default_factory=RPCConfig)
    p2p: P2PConfig = field(default_factory=P2PConfig)
    mempool: MempoolConfig = field(default_factory=MempoolConfig)
    statesync: StateSyncConfig = field(default_factory=StateSyncConfig)
    fastsync: FastSyncConfig = field(default_factory=FastSyncConfig)
    consensus: ConsensusConfig = field(default_factory=ConsensusConfig)
    tx_index: TxIndexConfig = field(default_factory=TxIndexConfig)
    instrumentation: InstrumentationConfig = field(default_factory=InstrumentationConfig)
    crypto: CryptoConfig = field(default_factory=CryptoConfig)

    def set_root(self, root: str) -> "Config":
        self.base.root_dir = root
        self.consensus.root_dir = root
        return self

    @property
    def root_dir(self) -> str:
        return self.base.root_dir

    def validate_basic(self) -> None:
        if self.base.abci not in ("socket", "grpc", "builtin"):
            raise ValueError(f"unknown abci transport {self.base.abci!r}")
        if self.mempool.size < 0:
            raise ValueError("mempool.size can't be negative")
        if self.consensus.timeout_propose_ns < 0:
            raise ValueError("consensus.timeout_propose can't be negative")
        if self.crypto.backend not in ("cpu", "tpu"):
            raise ValueError(f"unknown crypto backend {self.crypto.backend!r}")
        # min_batch/max_chunk are load-bearing (they drive the batch
        # plane's routing and chunking): reject malformed TOML at
        # startup, not at the first commit
        for knob in (
            "min_batch", "max_chunk", "flush_us",
            "dispatch_timeout_ms", "breaker_threshold", "max_queue",
            "retry_ms", "chunk_recover_n",
        ):
            v = getattr(self.crypto, knob)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(
                    f"crypto.{knob} must be a positive integer, got {v!r}"
                )
        ap = self.crypto.audit_pct
        if not isinstance(ap, int) or isinstance(ap, bool) or not 0 <= ap <= 100:
            raise ValueError(
                f"crypto.audit_pct must be an integer in [0, 100], got {ap!r}"
            )
        fd = self.crypto.fault_domains
        if not isinstance(fd, int) or isinstance(fd, bool) or fd < 0:
            # 0 is a valid value: auto-detect from the device plane
            raise ValueError(
                "crypto.fault_domains must be a non-negative integer, "
                f"got {fd!r}"
            )
        smb = self.crypto.shard_min_batch
        if not isinstance(smb, int) or isinstance(smb, bool) or smb < 0:
            # 0 is a valid value: use the calibrated crossover
            raise ValueError(
                "crypto.shard_min_batch must be a non-negative integer, "
                f"got {smb!r}"
            )
        # qos_classes is load-bearing the moment overload hits: reject
        # unknown class names / policies / non-positive bounds at
        # startup, not at the first flood. The parser raises ValueError
        # in the same crypto.<knob> style as the checks above.
        from cometbft_tpu.crypto import qos as qoslib

        qoslib.parse_qos_classes(self.crypto.qos_classes)
        qtr = self.crypto.qos_tenant_rate
        if not isinstance(qtr, int) or isinstance(qtr, bool) or qtr < 0:
            # 0 is a valid value: per-tenant quotas disabled
            raise ValueError(
                "crypto.qos_tenant_rate must be a non-negative integer, "
                f"got {qtr!r}"
            )
        vs = self.crypto.verify_service
        if vs:
            # parse_address_list raises ValueError in the crypto.<knob>
            # style for each element (a comma list selects the HA
            # replica-set client)
            from cometbft_tpu.crypto import service as servicelib

            servicelib.parse_address_list(vs)
        vst = self.crypto.verify_service_timeout_ms
        if not isinstance(vst, int) or isinstance(vst, bool) or vst < 1:
            raise ValueError(
                "crypto.verify_service_timeout_ms must be a positive "
                f"integer, got {vst!r}"
            )
        for knob in ("verify_retry_cap_ms", "verify_probe_ms"):
            v = getattr(self.crypto, knob)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(
                    f"crypto.{knob} must be a positive integer, got {v!r}"
                )
        rt = self.crypto.router
        if rt not in ("priced", "threshold"):
            raise ValueError(
                "crypto.router must be one of ['priced', 'threshold'], "
                f"got {rt!r}"
            )
        wb = self.crypto.warm_boot
        if wb not in ("eager", "background", "off"):
            raise ValueError(
                "crypto.warm_boot must be one of "
                f"['eager', 'background', 'off'], got {wb!r}"
            )
        hp = self.crypto.hedge_pct
        if not isinstance(hp, int) or isinstance(hp, bool) or hp < 0:
            # 0 is a valid value: it disables hedging entirely
            raise ValueError(
                f"crypto.hedge_pct must be a non-negative integer, got {hp!r}"
            )
        ts = self.instrumentation.trace_sample
        if (
            not isinstance(ts, (int, float))
            or isinstance(ts, bool)
            or not 0.0 <= float(ts) <= 1.0
        ):
            raise ValueError(
                "instrumentation.trace_sample must be a number in "
                f"[0, 1], got {ts!r}"
            )
        tb = self.instrumentation.trace_buffer
        if not isinstance(tb, int) or isinstance(tb, bool) or tb < 1:
            raise ValueError(
                "instrumentation.trace_buffer must be a positive "
                f"integer, got {tb!r}"
            )
        slo = self.instrumentation.slo_commit_ms
        if not isinstance(slo, int) or isinstance(slo, bool) or slo < 1:
            raise ValueError(
                "instrumentation.slo_commit_ms must be a positive "
                f"integer, got {slo!r}"
            )
        tdk = self.instrumentation.trace_dump_keep
        if not isinstance(tdk, int) or isinstance(tdk, bool) or tdk < 1:
            raise ValueError(
                "instrumentation.trace_dump_keep must be a positive "
                f"integer, got {tdk!r}"
            )
        for knob in (
            "mem_poll_ms", "profile_keep", "wire_window",
            "decision_window",
        ):
            v = getattr(self.instrumentation, knob)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(
                    f"instrumentation.{knob} must be a positive "
                    f"integer, got {v!r}"
                )
        for knob in ("wire_ledger", "decision_ledger"):
            v = getattr(self.instrumentation, knob)
            if not isinstance(v, bool):
                raise ValueError(
                    f"instrumentation.{knob} must be a boolean, "
                    f"got {v!r}"
                )
        mt = self.instrumentation.decision_mape_trip
        if (
            not isinstance(mt, (int, float))
            or isinstance(mt, bool)
            or float(mt) <= 0.0
        ):
            raise ValueError(
                "instrumentation.decision_mape_trip must be a "
                f"positive number, got {mt!r}"
            )
        pb = self.instrumentation.profile_on_burn
        if (
            not isinstance(pb, (int, float))
            or isinstance(pb, bool)
            or float(pb) < 0.0
        ):
            # 0 is a valid value: auto-capture disabled. No upper
            # bound — burn rate is an unbounded ratio.
            raise ValueError(
                "instrumentation.profile_on_burn must be a "
                f"non-negative number, got {pb!r}"
            )


def default_config() -> Config:
    return Config()


def test_config() -> Config:
    """Reference: config.TestConfig — aggressive timeouts for tests."""
    cfg = Config()
    c = cfg.consensus
    c.timeout_propose_ns = 40 * _MS
    c.timeout_propose_delta_ns = 1 * _MS
    c.timeout_prevote_ns = 10 * _MS
    c.timeout_prevote_delta_ns = 1 * _MS
    c.timeout_precommit_ns = 10 * _MS
    c.timeout_precommit_delta_ns = 1 * _MS
    c.timeout_commit_ns = 10 * _MS
    c.skip_timeout_commit = True
    cfg.p2p.flush_throttle_timeout_ns = 10 * _MS
    cfg.base.fast_sync_mode = False
    return cfg


# --- TOML ------------------------------------------------------------------

_DURATION_FIELDS = re.compile(r"_ns$")


def _to_toml_value(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, int):
        return str(v)
    if isinstance(v, float):
        # repr always keeps a "." or exponent for finite floats, which
        # is what TOML requires; without this branch floats fell through
        # to the string case and came back as strings on reload
        return repr(v)
    if isinstance(v, list):
        return "[" + ", ".join(f'"{x}"' for x in v) + "]"
    return f'"{v}"'


_SECTIONS = [
    ("", "base"),
    ("rpc", "rpc"),
    ("p2p", "p2p"),
    ("mempool", "mempool"),
    ("statesync", "statesync"),
    ("fastsync", "fastsync"),
    ("consensus", "consensus"),
    ("tx_index", "tx_index"),
    ("instrumentation", "instrumentation"),
    ("crypto", "crypto"),
]


def write_config_file(path: str, cfg: Config) -> None:
    lines = ["# This is a TOML config file generated by cometbft_tpu.", ""]
    for section, attr in _SECTIONS:
        obj = getattr(cfg, attr)
        if section:
            lines.append(f"[{section}]")
        for name, value in vars(obj).items():
            if name == "root_dir":
                continue
            if _DURATION_FIELDS.search(name):
                key = name[: -len("_ns")]
                lines.append(f'{key} = "{duration_to_str(value)}"')
            else:
                lines.append(f"{name} = {_to_toml_value(value)}")
        lines.append("")
    with open(path, "w") as f:
        f.write("\n".join(lines))


def _parse_toml_min(text: str) -> dict:
    """Minimal TOML-subset reader for the dialect save_config_file
    emits (flat [section] tables; string / bool / int / string-list
    values, all JSON-compatible tokens) — the fallback on Python 3.10
    where stdlib tomllib (3.11+) does not exist."""
    import json as _json

    root: dict = {}
    cur = root
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        if line.startswith("[") and line.endswith("]"):
            cur = root.setdefault(line[1:-1].strip(), {})
            continue
        if "=" not in line:
            raise ValueError(f"unparseable config line: {raw!r}")
        key, tok = (s.strip() for s in line.split("=", 1))
        try:
            cur[key] = _json.loads(tok)
        except ValueError:
            # trailing comment after the value, then one more try
            tok = tok.split("#", 1)[0].strip()
            cur[key] = _json.loads(tok)
    return root


def load_config_file(path: str, cfg: Optional[Config] = None) -> Config:
    try:
        import tomllib
    except ImportError:
        tomllib = None
    if tomllib is not None:
        with open(path, "rb") as f:
            data = tomllib.load(f)
    else:
        with open(path, "r", encoding="utf-8") as f:
            data = _parse_toml_min(f.read())
    cfg = cfg or Config()
    for section, attr in _SECTIONS:
        obj = getattr(cfg, attr)
        src = data if section == "" else data.get(section, {})
        for name in list(vars(obj)):
            if name == "root_dir":
                continue
            if _DURATION_FIELDS.search(name):
                key = name[: -len("_ns")]
                if isinstance(src, dict) and key in src:
                    setattr(obj, name, parse_duration(src[key]))
            elif isinstance(src, dict) and name in src:
                setattr(obj, name, src[name])
    return cfg
