"""Verify-as-a-service — the VerifyScheduler behind a real network
boundary, with cross-client megabatch coalescing over the compact wire
format.

The QoS plane (crypto/qos.py), per-tenant RED metering (telemetry.py),
and the compact 128 B / indexed 100 B wire rows (PR 13) made the
scheduler multi-tenant in everything but transport: "tenants" were
threads in one process. This module adds the transport. A
``VerifyService`` listens on a Unix domain socket (TCP optional) and
feeds frames from N client connections into ONE ``VerifyScheduler`` —
cross-client coalescing: the batch sweep says a lone 1024-lane flush
earns ~25k sigs/sec while a 16384-lane megabatch earns ~75k, so merging
many small client flushes raises fleet throughput AND each client's
latency. A ``RemoteVerifier`` duck-types the crypto Backend contract
(``spec`` + ``submit``, like ScheduledBatchVerifier) so every existing
call site — consensus preverify, blocksync, light, mempool — points at
a shared daemon the moment the node sets ``[crypto] verify_service`` /
``CBFT_VERIFY_SERVICE``.

Zero double-marshalling is the design invariant: the RPC payload IS the
PR 13 wire format. The client packs compact u8[128,B] rows (or 100 B
indexed rows when its cached keystore generation matches the server's)
exactly once via ``ed25519_batch.prepare_batch_compact`` /
``_prepare_rsh_compact`` — the same ``pack_compact_rows`` plane layout
the kernels consume — and the server ``device_put``s those same rows:
a compact frame's payload as it is, an indexed frame's behind its key
rows (gathered where the frame is admitted, on its reader thread), the
requests of a flush interleaved row by row. Nothing is ever
re-marshalled into triples on the server.

Frame protocol (length-prefixed binary, no external deps):

    u32 LE frame length (header + payload)
    40-byte header:  <4sBBBBQII16s
        magic      b"CBVS"
        version    1
        ftype      HELLO | CLIENT_HELLO | REQ | RESP | ERR |
                   REGISTER | REGISTERED | AUTH | AUTH_OK | DRAINING
        qclass     QoS class code (qos.class_code; 0xFF = untagged)
        kind       0 = compact 128 B rows, 1 = indexed 100 B rows
        req_id     u64, client-assigned, echoed on RESP/ERR
        n_lanes    u32 lanes in this frame (HELLO: server max_lanes)
        generation u32 keystore generation (the indexed handshake)
        valset_id  16 bytes (sha256(pubkey rows)[:16]; REGISTER/indexed)
    payload:
        REQ compact   u8[128, n] C-order — exactly 128 B/lane
        REQ indexed   u8[96, n] R ‖ S ‖ h rows + n × i32 LE table
                      indices — exactly 100 B/lane
        RESP          1 status byte (0 ok, 1 rejected) + bitmask
                      (np.packbits little) of per-lane verdicts
        ERR           u16 LE code + utf8 message
        REGISTER      n × 32-byte pubkey rows
        CLIENT_HELLO  utf8 tenant name
        AUTH          32-byte HMAC-SHA256(key, challenge ‖ node_id)
                      + utf8 node id (client answer to the HELLO
                      challenge when the server requires auth)
        AUTH_OK       empty (session authenticated)
        DRAINING      empty (server entered graceful drain; pick
                      another endpoint for NEW work — in-flight
                      requests are still answered)

The HELLO payload is [proto_version u8, flags u8, 16-byte challenge?]:
flags bit0 = the server is draining, bit1 = the server requires the
HMAC challenge-response (the challenge bytes follow). v1 servers send
an empty payload and v1 clients ignore HELLO payload bytes entirely, so
both extensions ride the existing version negotiation unchanged.

Tenant identity is the connection (CLIENT_HELLO), the QoS class rides
in the frame header, and ``qos.resolve_class`` / ``TenantQuotas`` /
brownout apply unchanged inside the scheduler. Refused row requests
(shed/drop/backpressure) are answered ``rejected`` — the remote client
holds the original triples and its own CPU, so IT pays the fallback
verify, never the shared device plane's host.

Fallback ladder, client side: indexed frame → (stale generation,
unknown valset) re-register + compact frame → (disconnect, timeout,
draining) FAILOVER to a healthy secondary when an HA hook is installed
(crypto/ha.py) → (rejected, any error, all endpoints down) local CPU
ground truth, with the verdict reason kept distinct (``future.reason``)
and counted per cause.
"""

from __future__ import annotations

import collections
import hashlib
import hmac
import os
import random
import socket
import struct
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from cometbft_tpu.crypto import qos as qoslib
from cometbft_tpu.crypto.batch import (
    BackendSpec,
    CPUBatchVerifier,
    resolved_device_plane,
)
from cometbft_tpu.crypto.scheduler import Item, VerifyFuture
from cometbft_tpu.libs import trace as tracelib
from cometbft_tpu.libs.log import Logger
from cometbft_tpu.libs.metrics import Registry
from cometbft_tpu.libs.service import BaseService

SUBSYSTEM = "verify_service"

# -- frame protocol ----------------------------------------------------------

MAGIC = b"CBVS"
# v2 adds an optional extension block between header and payload on REQ
# frames (currently: trace context). Frames WITHOUT extensions are still
# emitted with version=1 headers, byte-identical to the v1 wire, so a v1
# peer interops unchanged; the version byte is parsed per frame.
VERSION = 2
MIN_VERSION = 1

FT_HELLO = 0
FT_CLIENT_HELLO = 1
FT_REQ = 2
FT_RESP = 3
FT_ERR = 4
FT_REGISTER = 5
FT_REGISTERED = 6
FT_AUTH = 7
FT_AUTH_OK = 8
FT_DRAINING = 9
_FT_NAMES = {
    FT_HELLO: "hello",
    FT_CLIENT_HELLO: "client_hello",
    FT_REQ: "req",
    FT_RESP: "resp",
    FT_ERR: "err",
    FT_REGISTER: "register",
    FT_REGISTERED: "registered",
    FT_AUTH: "auth",
    FT_AUTH_OK: "auth_ok",
    FT_DRAINING: "draining",
}

KIND_COMPACT = 0
KIND_INDEXED = 1
_KIND_NAMES = {KIND_COMPACT: "compact", KIND_INDEXED: "indexed"}

COMPACT_ROW_BYTES = 128
RSH_ROW_BYTES = 96
INDEX_BYTES = 4
INDEXED_ROW_BYTES = RSH_ROW_BYTES + INDEX_BYTES  # 100 B/lane

_LEN = struct.Struct("<I")
_HEADER = struct.Struct("<4sBBBBQII16s")
HEADER_BYTES = _HEADER.size
VALSET_ID_BYTES = 16
_ERR_HEAD = struct.Struct("<H")

# v2 extension block: u8 ext_len (TLV bytes that follow), then TLV
# entries of (u8 type, u8 len, len value bytes). Unknown types are
# skipped per spec; a TLV running past ext_len is malformed.
EXT_TRACE = 1
_EXT_TRACE = struct.Struct("<QQB")  # trace_id, span_id, flags
TRACE_FLAG_SAMPLED = 0x01
_MAX_EXT_BYTES = 255  # ext_len is a u8

# typed error codes (satellite: malformed/truncated/oversized frames get
# a typed error frame and the accept loop survives)
ERR_MALFORMED = 1
ERR_OVERSIZE = 2
ERR_STALE_GENERATION = 3
ERR_UNKNOWN_VALSET = 4
ERR_BAD_CLASS = 5
ERR_BAD_VERSION = 6
ERR_INTERNAL = 7
ERR_UNAUTHORIZED = 8
ERR_NAMES = {
    ERR_MALFORMED: "malformed",
    ERR_OVERSIZE: "oversize",
    ERR_STALE_GENERATION: "stale_generation",
    ERR_UNKNOWN_VALSET: "unknown_valset",
    ERR_BAD_CLASS: "bad_class",
    ERR_BAD_VERSION: "bad_version",
    ERR_INTERNAL: "internal",
    ERR_UNAUTHORIZED: "unauthorized",
}

# RESP status byte. ST_DRAINING is the graceful-drain refusal: the
# request was NOT admitted (the server stopped accepting new work) and
# the client should fail over to another endpoint immediately instead
# of burning its timeout — unlike ST_REJECTED it is a transport-shaped
# signal, not an admission verdict, so the HA rung may retry it.
ST_OK = 0
ST_REJECTED = 1
ST_DRAINING = 2

# HELLO payload flags (second byte; absent = 0 for older servers)
HELLO_FLAG_DRAINING = 0x01
HELLO_FLAG_AUTH = 0x02

# authenticated sessions: HMAC-SHA256 challenge-response riding HELLO
AUTH_CHALLENGE_BYTES = 16
AUTH_MAC_BYTES = 32
# a wrong-key client gets this many typed refusals before the server
# hangs up the connection (its reconnects are then backoff-bounded)
MAX_AUTH_ATTEMPTS = 3

# transport-shaped failure reasons the HA failover rung may resubmit to
# a secondary (verify is idempotent). "rejected" (admission verdict),
# "error", and "unauthorized" (the whole fleet shares the key) are NOT
# failover-eligible.
FAILOVER_REASONS = ("disconnected", "timeout", "draining")

DEFAULT_ADDRESS = "unix:///tmp/cbft-verifyd.sock"
DEFAULT_TIMEOUT_MS = 2_000
# registration frames carry raw 32-byte key rows; bound them the same
# way REQ lanes are bounded so one garbage client cannot OOM the server
MAX_REGISTER_KEYS = 16_384
_DRAIN_CHUNK = 65_536


def verify_service_default(config_value: Optional[str] = None) -> str:
    """Shared-daemon address: CBFT_VERIFY_SERVICE env > [crypto]
    verify_service > "" (in-process scheduler, the default)."""
    raw = os.environ.get("CBFT_VERIFY_SERVICE")
    if raw is not None:
        return raw.strip()
    if config_value:
        return str(config_value).strip()
    return ""


def verify_auth_key_default(config_value: Optional[str] = None) -> str:
    """Path of the shared HMAC key file: CBFT_VERIFY_AUTH_KEY env >
    [crypto] verify_auth_key > "" (unauthenticated, the v1 default)."""
    raw = os.environ.get("CBFT_VERIFY_AUTH_KEY")
    if raw is not None:
        return raw.strip()
    if config_value:
        return str(config_value).strip()
    return ""


def load_auth_key(path: str) -> bytes:
    """Read the shared HMAC key from a per-node key file (surrounding
    whitespace stripped so `openssl rand -hex 32 > key` round-trips)."""
    with open(path, "rb") as fh:
        key = fh.read().strip()
    if not key:
        raise ValueError(f"auth key file {path!r} is empty")
    return key


def auth_mac(key: bytes, challenge: bytes, node_id: str) -> bytes:
    """The AUTH frame's proof: HMAC-SHA256(key, challenge ‖ node_id).
    Binding the node id into the MAC makes the authenticated identity
    unforgeable — the server adopts it as the tenant, so quotas/RED
    follow the key holder across reconnects and NAT."""
    return hmac.new(
        bytes(key), bytes(challenge) + node_id.encode("utf-8"),
        hashlib.sha256,
    ).digest()


def service_timeout_default(config_timeout_ms: Optional[int] = None) -> int:
    """Per-request deadline (ms) before the client falls back to local
    CPU: CBFT_VERIFY_SERVICE_TIMEOUT_MS env > configured > 2000."""
    raw = os.environ.get("CBFT_VERIFY_SERVICE_TIMEOUT_MS")
    if raw is not None:
        return int(raw)
    if config_timeout_ms is not None:
        return int(config_timeout_ms)
    return DEFAULT_TIMEOUT_MS


def parse_address(addr: str) -> Tuple[str, Any]:
    """("unix", path) or ("tcp", (host, port)). A bare filesystem path
    is accepted as a unix address; anything else raises ValueError in
    config.validate_basic's style."""
    a = str(addr).strip()
    if a.startswith("unix://"):
        path = a[len("unix://"):]
        if not path:
            raise ValueError("verify_service unix:// address needs a path")
        return "unix", path
    if a.startswith("tcp://"):
        rest = a[len("tcp://"):]
        host, sep, port = rest.rpartition(":")
        if not sep or not host or not port.isdigit():
            raise ValueError(
                f"verify_service tcp:// address must be tcp://HOST:PORT, "
                f"got {addr!r}"
            )
        return "tcp", (host, int(port))
    if "://" not in a and (a.startswith(("/", ".")) or os.sep in a):
        # a bare filesystem path; an unrecognized scheme must NOT fall
        # through here (ftp://x contains os.sep and would silently
        # become a unix path)
        return "unix", a
    raise ValueError(
        f"verify_service address must be unix://PATH or tcp://HOST:PORT, "
        f"got {addr!r}"
    )


def parse_address_list(addr: str) -> List[str]:
    """``verify_service`` accepts a comma-separated endpoint list (the
    HA replica set). Each element validates via parse_address; a single
    address yields a one-element list."""
    out: List[str] = []
    for part in str(addr).split(","):
        part = part.strip()
        if not part:
            continue
        parse_address(part)
        out.append(part)
    if not out:
        raise ValueError("verify_service endpoint list is empty")
    return out


def max_frame_bytes(max_lanes: int) -> int:
    """Frame-length bound derived from the lane budget (itself
    max_chunk-derived): the largest legal frame is a full compact REQ or
    a full REGISTER, whichever is bigger, plus the header and the v2
    extension allowance (1 length byte + up to 255 TLV bytes)."""
    lanes = max(1, int(max_lanes))
    body = max(lanes * COMPACT_ROW_BYTES, MAX_REGISTER_KEYS * 32)
    return HEADER_BYTES + 1 + _MAX_EXT_BYTES + body


class FrameError(Exception):
    """Typed protocol error; ``code`` is one of the ERR_* constants and
    is what travels in the error frame."""

    def __init__(self, code: int, msg: str):
        super().__init__(msg)
        self.code = code


class _FatalFrameError(FrameError):
    """A typed refusal after which the server hangs up the connection
    (repeated auth failures): the error frame still goes out first, but
    the read loop breaks instead of serving more frames."""


class AuthError(ConnectionError):
    """The server required authentication and refused ours (wrong key /
    refused node id). NOT failover-eligible — the whole fleet shares the
    key, so a secondary would refuse the same credentials."""


class Frame:
    __slots__ = ("ftype", "qclass", "kind", "req_id", "n_lanes",
                 "generation", "valset_id", "payload", "trace_ctx")

    def __init__(self, ftype, qclass, kind, req_id, n_lanes, generation,
                 valset_id, payload, trace_ctx=None):
        self.ftype = ftype
        self.qclass = qclass
        self.kind = kind
        self.req_id = req_id
        self.n_lanes = n_lanes
        self.generation = generation
        self.valset_id = valset_id
        self.payload = payload
        # (trace_id, span_id, sampled) off the v2 extension block, or None
        self.trace_ctx = trace_ctx


def encode_frame(
    ftype: int,
    *,
    qclass: int = qoslib.CLASS_CODE_UNTAGGED,
    kind: int = KIND_COMPACT,
    req_id: int = 0,
    n_lanes: int = 0,
    generation: int = 0,
    valset_id: bytes = b"",
    payload: bytes = b"",
    trace_ctx: Optional[Tuple[int, int, bool]] = None,
) -> bytes:
    """Encode one frame. Without ``trace_ctx`` the frame is the exact v1
    wire (version byte 1, no extension block) — a v2 sender talking to a
    v1 peer never trips its version check. With ``trace_ctx``
    (trace_id, span_id, sampled) the header says version 2 and an
    extension block rides between header and payload."""
    vid = bytes(valset_id)[:VALSET_ID_BYTES].ljust(VALSET_ID_BYTES, b"\x00")
    if trace_ctx is None:
        version, ext = MIN_VERSION, b""
    else:
        tid, sid, sampled = trace_ctx
        tlv_val = _EXT_TRACE.pack(
            tid & 0xFFFFFFFFFFFFFFFF, sid & 0xFFFFFFFFFFFFFFFF,
            TRACE_FLAG_SAMPLED if sampled else 0,
        )
        tlv = bytes((EXT_TRACE, len(tlv_val))) + tlv_val
        version, ext = VERSION, bytes((len(tlv),)) + tlv
    header = _HEADER.pack(
        MAGIC, version, ftype & 0xFF, qclass & 0xFF, kind & 0xFF,
        req_id & 0xFFFFFFFFFFFFFFFF, n_lanes & 0xFFFFFFFF,
        generation & 0xFFFFFFFF, vid,
    )
    return (
        _LEN.pack(HEADER_BYTES + len(ext) + len(payload))
        + header + ext + payload
    )


def _decode_extensions(
    buf: bytes,
) -> Tuple[Optional[Tuple[int, int, bool]], int]:
    """Parse the v2 extension block starting at HEADER_BYTES. Returns
    (trace_ctx or None, payload offset). Unknown TLV types are skipped;
    a block overrunning the frame or a TLV overrunning the block is
    malformed."""
    if len(buf) < HEADER_BYTES + 1:
        raise FrameError(ERR_MALFORMED, "v2 frame missing extension length")
    ext_len = buf[HEADER_BYTES]
    pos = HEADER_BYTES + 1
    end = pos + ext_len
    if len(buf) < end:
        raise FrameError(
            ERR_MALFORMED,
            f"extension block of {ext_len} bytes overruns the frame",
        )
    trace_ctx = None
    while pos < end:
        if pos + 2 > end:
            raise FrameError(ERR_MALFORMED, "truncated extension TLV head")
        etype, elen = buf[pos], buf[pos + 1]
        pos += 2
        if pos + elen > end:
            raise FrameError(
                ERR_MALFORMED,
                f"extension {etype} of {elen} bytes overruns the block",
            )
        if etype == EXT_TRACE and elen == _EXT_TRACE.size:
            tid, sid, flags = _EXT_TRACE.unpack_from(buf, pos)
            trace_ctx = (tid, sid, bool(flags & TRACE_FLAG_SAMPLED))
        # any other type (or a differently-sized trace TLV from a newer
        # minor revision) is skipped per spec
        pos += elen
    return trace_ctx, end


def decode_frame(buf: bytes) -> Frame:
    """Parse one length-stripped frame. Raises FrameError — MALFORMED
    for a short/garbled header, BAD_VERSION for a future protocol.
    Versions 1 and 2 are both accepted; v2 frames may carry an
    extension block (unknown extension types are ignored)."""
    if len(buf) < HEADER_BYTES:
        raise FrameError(
            ERR_MALFORMED, f"frame shorter than header ({len(buf)} bytes)"
        )
    magic, version, ftype, qclass, kind, req_id, n_lanes, generation, vid = (
        _HEADER.unpack_from(buf)
    )
    if magic != MAGIC:
        raise FrameError(ERR_MALFORMED, f"bad magic {magic!r}")
    if not (MIN_VERSION <= version <= VERSION):
        raise FrameError(ERR_BAD_VERSION, f"unsupported version {version}")
    trace_ctx: Optional[Tuple[int, int, bool]] = None
    body_at = HEADER_BYTES
    if version >= 2:
        trace_ctx, body_at = _decode_extensions(buf)
    return Frame(
        ftype, qclass, kind, req_id, n_lanes, generation, vid,
        buf[body_at:], trace_ctx,
    )


def req_payload_bytes(kind: int, n_lanes: int) -> int:
    if kind == KIND_COMPACT:
        return COMPACT_ROW_BYTES * n_lanes
    if kind == KIND_INDEXED:
        return INDEXED_ROW_BYTES * n_lanes
    raise FrameError(ERR_MALFORMED, f"unknown row kind {kind}")


def encode_error(code: int, msg: str) -> bytes:
    return _ERR_HEAD.pack(code & 0xFFFF) + msg.encode(
        "utf-8", errors="replace"
    )


def decode_error(payload: bytes) -> Tuple[int, str]:
    if len(payload) < _ERR_HEAD.size:
        return ERR_INTERNAL, "truncated error frame"
    (code,) = _ERR_HEAD.unpack_from(payload)
    return code, payload[_ERR_HEAD.size:].decode("utf-8", errors="replace")


# -- socket helpers ----------------------------------------------------------


def _recv_exact(sock, n: int, tick: Optional[Callable[[], bool]] = None
                ) -> Optional[bytes]:
    """Read exactly n bytes. None on EOF or socket error (the caller
    treats both as disconnect — a mid-frame EOF IS a truncated frame).
    Socket timeouts loop, calling ``tick()`` between slices when given
    (the client's pending-expiry hook); tick() returning False aborts."""
    buf = bytearray()
    while len(buf) < n:
        try:
            chunk = sock.recv(n - len(buf))
        except socket.timeout:
            if tick is not None and not tick():
                return None
            continue
        except OSError:
            return None
        if not chunk:
            return None
        buf += chunk
    return bytes(buf)


def _drain(sock, n: int) -> bool:
    """Discard n bytes in bounded chunks (the oversize-frame recovery:
    the typed error already went out; the stream stays framed)."""
    left = n
    while left > 0:
        got = _recv_exact(sock, min(left, _DRAIN_CHUNK))
        if got is None:
            return False
        left -= len(got)
    return True


def _pk_bytes(pk) -> bytes:
    """Normalize one pubkey to raw bytes (same contract as
    keystore._key_bytes: PubKey objects and raw bytes both travel)."""
    if isinstance(pk, (bytes, bytearray, memoryview)):
        return bytes(pk)
    b = getattr(pk, "bytes", None)
    if callable(b):
        return b()
    return bytes(pk)


# -- packing (client side, and server-side triples riding a row flush) -------


def pack_items_compact(
    items: Sequence[Item],
) -> Tuple[np.ndarray, np.ndarray]:
    """(wire u8[128, n], valid bool[n]) for (pk, msg, sig) triples —
    the exact ed25519_batch.prepare_batch_compact plane layout
    (A ‖ R ‖ S ‖ h rows via pack_compact_rows), packed ONCE. Lanes with
    malformed inputs or s ≥ L come back valid=False (their rows are
    zero-filled); the client strips them before framing, the server
    masks them after the kernel."""
    from cometbft_tpu.crypto.tpu import ed25519_batch as ed

    pks = [_pk_bytes(pk) for pk, _, _ in items]
    msgs = [m for _, m, _ in items]
    sigs = [s for _, _, s in items]
    wire, valid = ed.prepare_batch_compact(pks, msgs, sigs)
    return wire, np.asarray(valid, dtype=bool)


def pack_items_indexed(
    items: Sequence[Item], index: Dict[bytes, int],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(rsh u8[96, n], idx i32[n], valid bool[n]) for triples whose
    pubkeys are ALL in ``index`` (the caller's coverage check) — the
    100 B/lane indexed wire."""
    from cometbft_tpu.crypto.tpu import ed25519_batch as ed

    pk_arr = np.stack([
        np.frombuffer(_pk_bytes(pk), np.uint8) for pk, _, _ in items
    ])
    msgs = [m for _, m, _ in items]
    sigs = [s for _, _, s in items]
    rsh, valid = ed._prepare_rsh_compact(pk_arr, msgs, sigs)
    idx = np.fromiter(
        (index[_pk_bytes(pk)] for pk, _, _ in items),
        dtype=np.int32, count=len(items),
    )
    return rsh, idx, np.asarray(valid, dtype=bool)


class RowPayload:
    """One client frame's rows as the scheduler carries them: the
    request's FINISHED compact block and its valid mask, built where the
    frame was admitted (the connection's reader thread, stage
    ``svc.gather``) and not touched again until a flush joins them.

    ``rows`` is the u8[128, n] block A‖R‖S‖h as ``128 * n`` bytes in
    the compact wire's own C order: a compact frame's payload (the
    stale resend's too) IS it, untouched; an indexed frame's is its key
    rows, gathered and transposed, in front of its R‖S‖h rows as they
    crossed the socket. ``valid`` is ``n`` bytes, one 0/1 a lane: 0
    where the registered key the lane's index addresses is malformed
    (``pk_ok`` false), which the flush refuses whatever the kernel says.

    An indexed frame's key rows are COPIED out of the key-store entry
    when the frame is accepted, so an entry evicted or invalidated
    between admission and flush cannot change the keys a request is
    verified against: the guarantee the entry object riding along used
    to give, by simpler means (a copy needs no reference). The
    generation check is a frame-accept-time freshness protocol only."""

    __slots__ = ("rows", "valid", "n")

    def __init__(self, rows: bytes, valid: bytes):
        self.rows = rows
        self.valid = valid
        self.n = len(valid)

    @classmethod
    def from_compact(cls, wire: bytes) -> "RowPayload":
        """A compact frame's payload: no gather and no copy."""
        return cls(wire, b"\x01" * (len(wire) // COMPACT_ROW_BYTES))

    @classmethod
    def from_indexed(cls, rsh: bytes, idx: np.ndarray, entry
                     ) -> "RowPayload":
        """An indexed frame's u8[96, n] R‖S‖h rows (their bytes) and its
        bounds-checked table indices into ``entry``: the host gather of
        the key rows, 32 bytes a lane, and of their ``pk_ok`` bits. At a
        light client's size (~100 lanes) none of these calls gives the
        GIL up, so a reader builds its block without waiting its turn
        again (PERF.md, PR 31)."""
        return cls(
            entry.pk_arr[idx].T.tobytes() + rsh,
            np.asarray(entry.pk_ok[idx], dtype=bool).tobytes(),
        )

    def as_compact(self) -> Tuple[np.ndarray, np.ndarray]:
        """(u8[128, n] compact rows, valid mask): read-only views of what
        admission built; nothing is gathered or copied here."""
        return _block_views(self.rows, self.valid)


def _block_views(rows: bytes, valid: bytes) -> Tuple[np.ndarray, np.ndarray]:
    """C-order block bytes as the u8[128, N] array the row verifiers
    take, and the bool[N] mask beside it."""
    return (
        np.frombuffer(rows, np.uint8).reshape(COMPACT_ROW_BYTES, len(valid)),
        np.frombuffer(valid, dtype=bool),
    )


# -- row verification (host ground truth + device dispatch) ------------------


def _verify_row(col: bytes) -> bool:
    """Ground-truth verify of ONE compact wire column (A‖R‖S‖h, 128 B):
    cofactorless [s]B + [h](−A) == R over the pure-Python group — the
    same check the kernel runs, minus the batching. ~2.6 ms/lane; the
    CachingRowVerifier amortizes it."""
    from cometbft_tpu.crypto import purepy as pp

    a = pp._pt_decode(bytes(col[0:32]))
    if a is None:
        return False
    s = int.from_bytes(col[64:96], "little")
    if s >= pp._L:
        return False
    h = int.from_bytes(col[96:128], "little")
    na = (pp._P - a[0], a[1], a[2], pp._P - a[3])
    q = pp._IDENT
    add = pp._pt_add
    b = pp._B
    for i in range(max(s.bit_length(), h.bit_length()) - 1, -1, -1):
        q = add(q, q)
        if (s >> i) & 1:
            q = add(q, b)
        if (h >> i) & 1:
            q = add(q, na)
    return pp._pt_encode(q) == bytes(col[32:64])


class CachingRowVerifier:
    """Host row verifier over compact wire columns with a bounded
    memoization LRU keyed by the full 128-byte lane. Every DISTINCT lane
    is truly verified (Shamir double-scalar, exact kernel semantics);
    repeats are a dict hit — which is what makes the chaos/bench soaks
    honest AND fast, and is the last rung of the service fallback ladder
    when no device plane exists."""

    def __init__(self, max_entries: int = 65_536):
        self._cache: "collections.OrderedDict[bytes, bool]" = (
            collections.OrderedDict()
        )
        self._max = max(1, int(max_entries))
        self._mtx = threading.Lock()
        self.hits = 0
        self.misses = 0

    def __call__(self, rows: np.ndarray) -> np.ndarray:
        cols = np.ascontiguousarray(rows.T)
        out = np.zeros(cols.shape[0], dtype=bool)
        for i in range(cols.shape[0]):
            key = cols[i].tobytes()
            with self._mtx:
                v = self._cache.get(key)
                if v is not None:
                    self._cache.move_to_end(key)
                    self.hits += 1
            if v is None:
                v = _verify_row(key)  # slow — outside the lock
                with self._mtx:
                    self.misses += 1
                    self._cache[key] = v
                    while len(self._cache) > self._max:
                        self._cache.popitem(last=False)
            out[i] = v
        return out


def dispatch_rows(rows: np.ndarray) -> np.ndarray:
    """Device dispatch of a flush's compact wire columns — the
    zero-double-marshalling half of the tentpole: the u8[128, B] block
    is the requests' rows as admission left them (a compact frame's
    socket bytes; an indexed frame's behind its key rows, gathered on
    the reader thread), interleaved by verify_mixed_flush into one
    contiguous array. One launch_stream on jax's default chip, cut as
    the keyed flush is (ed25519_batch.verify_batch through
    mesh.dispatch_batch): launches of _LAUNCH_LANES with the one short
    launch first and padded to at least half of one, so a flush of any
    length reaches the shapes that route warms plus the small buckets,
    and the slices of launch k+1 are padded and sent while launch k
    runs. Every launch is attributed into the wire ledger under the
    "service" route so bytes-per-lane is provable from /debug/verify."""
    from cometbft_tpu.crypto.tpu import ed25519_batch as ed
    from cometbft_tpu.crypto.tpu import mesh as mesh_mod

    n = int(rows.shape[1])
    cap = min(mesh_mod.chunk_cap(ed._MAX_CHUNK, ed._MIN_PAD), ed._LAUNCH_LANES)
    out, _ = mesh_mod.launch_stream(
        ed.verify_kernel_compact,
        mesh_mod.shard_chunks(n, 1, cap, ed._MIN_PAD, short_floor=cap // 2),
        lambda start, end: [rows[:, start:end]], n, where=None,
        prefix="mesh", route="service", device_label="dev0",
    )
    return out


_host_verifier: Optional[CachingRowVerifier] = None
_host_mtx = threading.Lock()


def host_row_verifier() -> CachingRowVerifier:
    """Process-shared host verifier so memoized verdicts span every
    scheduler/service in the process (tests spin up several)."""
    global _host_verifier
    with _host_mtx:
        if _host_verifier is None:
            _host_verifier = CachingRowVerifier()
        return _host_verifier


def resolve_row_verifier(spec=None) -> Callable[[np.ndarray], np.ndarray]:
    """Pick the row verifier for a scheduler that received row payloads:
    the device kernel when the backend spec asks for one and jax runs an
    accelerator, the host ground truth otherwise. (The CPU-jax compact
    kernel pays a multi-second compile for no batching win — the host
    path is both faster and exact on the CPU platform.) A non-cpu spec
    on a machine where jax cannot start RAISES here: handing a ``tpu``
    daemon the host verifier would be a CPU service behind a device
    name."""
    name = getattr(spec, "name", None) or os.environ.get(
        "CMT_CRYPTO_BACKEND", "cpu"
    )
    if name != "cpu":
        import jax

        if jax.default_backend() != "cpu":
            return dispatch_rows
    return host_row_verifier()


def assemble_flush(batch) -> Tuple[np.ndarray, np.ndarray, int]:
    """(u8[128, N] block, bool[N] valid, row requests) of one flush's
    requests, in request order. A row request contributes the block its
    admission built; a triple rider is packed here, once, into the same
    layout. Row ``i`` of the megabatch is row ``i`` of every block end
    to end, so the blocks interleave: ``128 x requests`` byte slices and
    one ``bytes.join``, all under the GIL (a join of 1 MiB or more gives
    it up once). The flush thread makes no numpy call per request, and
    gives the GIL up a number of times that does not grow with the
    requests it carries; a one-request flush copies nothing."""
    blocks: List[Tuple[bytes, int]] = []
    valids: List[bytes] = []
    prebuilt = 0
    for req in batch:
        rows = getattr(req, "rows", None)
        if rows is not None:
            blocks.append((rows.rows, rows.n))
            valids.append(rows.valid)
            prebuilt += 1
        else:
            w, v = pack_items_compact(req.items)
            blocks.append((w.tobytes(), len(v)))
            valids.append(v.tobytes())
    if len(blocks) == 1:
        wire = blocks[0][0]
    else:
        wire = b"".join([
            blk[i * n:(i + 1) * n]
            for i in range(COMPACT_ROW_BYTES) for blk, n in blocks
        ])
    full, valid = _block_views(wire, b"".join(valids))
    return full, valid, prebuilt


def verify_mixed_flush(batch, row_verifier, on_fallback=None) -> List[bool]:
    """Verdict mask for one coalesced flush that contains at least one
    row-payload request. Row requests arrive FINISHED (RowPayload: their
    lanes and valid bits were built where their frames were admitted, an
    indexed frame's key rows gathered there; no flush gathers, and none
    keeps an on-device gather); triple requests pack ONCE into the same
    layout; ``sched.rows`` interleaves them (assemble_flush) and the
    u8[128, N] block verifies in one call of the row verifier — this is
    the cross-client megabatch. A verifier that raises (the device died
    mid-flight) is reported through ``on_fallback(exc, n_lanes)`` — the
    scheduler counts it under cpu_fallbacks — before the host rung
    re-verifies the block."""
    # recorder tags: what the flush carried, and how many of its blocks
    # came from admission
    with tracelib.stage("sched.rows") as span:
        full, valid, prebuilt = assemble_flush(batch)
        span.set_tag("requests", len(batch))
        span.set_tag("lanes", int(full.shape[1]))
        span.set_tag("prebuilt", prebuilt)
    try:
        mask = np.asarray(row_verifier(full), dtype=bool)[: full.shape[1]]
    except Exception as exc:  # noqa: BLE001 - device died mid-flight: host rung
        if on_fallback is not None:
            on_fallback(exc, int(full.shape[1]))
        mask = np.asarray(
            host_row_verifier()(full), dtype=bool
        )[: full.shape[1]]
    mask = mask & valid
    return [bool(b) for b in mask]


# -- metrics -----------------------------------------------------------------


class ServiceMetrics:
    """verify_service_* instruments (libs/metrics.py), wired into the
    node registry alongside the scheduler's."""

    def __init__(self, registry: Optional[Registry] = None):
        r = registry if registry is not None else Registry()
        self.frames = r.counter(
            SUBSYSTEM, "frames", "Frames received, by type."
        )
        self.lanes = r.counter(
            SUBSYSTEM, "lanes", "Request lanes received, by wire kind."
        )
        self.bytes_rx = r.counter(
            SUBSYSTEM, "bytes_rx", "Payload bytes received."
        )
        self.bytes_tx = r.counter(
            SUBSYSTEM, "bytes_tx", "Frame bytes sent."
        )
        self.bytes_per_lane = r.gauge(
            SUBSYSTEM, "bytes_per_lane",
            "Socket payload bytes per lane of the last request frame, by "
            "wire kind — the zero-double-marshalling proof "
            "(compact ≤ 128, indexed ≤ 100).",
        )
        self.disconnects = r.counter(
            SUBSYSTEM, "disconnects",
            "Connections that died with requests in flight, by tenant.",
        )
        self.errors = r.counter(
            SUBSYSTEM, "errors", "Typed error frames sent, by code."
        )
        self.stale_drops = r.counter(
            SUBSYSTEM, "stale_drops",
            "Indexed frames refused for a stale keystore generation.",
        )
        self.pending = r.gauge(
            SUBSYSTEM, "pending",
            "Requests accepted from clients and not yet answered.",
        )
        self.refusals = r.counter(
            SUBSYSTEM, "refusals",
            "Typed per-request refusals, by tenant and code.",
        )
        self.registrations = r.counter(
            SUBSYSTEM, "registrations",
            "Valset registrations accepted, by tenant.",
        )
        self.rows_prebuilt = r.counter(
            SUBSYSTEM, "rows_prebuilt",
            "Row requests whose compact block (key rows gathered, valid "
            "bits set) was built where the frame was admitted.",
        )

    @classmethod
    def nop(cls) -> "ServiceMetrics":
        return cls(None)


# -- server ------------------------------------------------------------------


class _Conn:
    __slots__ = ("sock", "tenant", "alive", "pending", "outq", "cv",
                 "reader", "writer", "mtx", "authenticated", "challenge",
                 "auth_fails")

    def __init__(self, sock):
        self.sock = sock
        self.tenant: Optional[str] = None
        self.alive = True
        self.authenticated = False
        self.challenge: Optional[bytes] = None
        self.auth_fails = 0
        # req_id -> (n_lanes, t0), for the leak check on disconnect/stop
        # and the per-tenant service latency (t0 = accept time)
        self.pending: Dict[int, Tuple[int, float]] = {}
        self.outq: "collections.deque[bytes]" = collections.deque()
        self.mtx = threading.Lock()
        self.cv = threading.Condition(self.mtx)
        self.reader: Optional[threading.Thread] = None
        self.writer: Optional[threading.Thread] = None


class VerifyService(BaseService):
    """The server half: accept loop + per-connection reader/writer
    threads feeding one VerifyScheduler. Frames from N connections merge
    into the scheduler's coalesced flushes (deadline / lane-budget /
    QoS semantics preserved — ``submit_rows`` runs the same admission
    ladder as ``submit``), and per-request verdicts fan back out per
    connection via future done-callbacks, so the flush worker never
    blocks on a slow client socket.

    ``coalesce=False`` dispatches each frame isolated in its reader
    thread — the bench head-to-head baseline proving what cross-client
    coalescing buys."""

    def __init__(
        self,
        scheduler,
        address: str = DEFAULT_ADDRESS,
        *,
        coalesce: bool = True,
        max_lanes: Optional[int] = None,
        row_verifier: Optional[Callable] = None,
        metrics: Optional[ServiceMetrics] = None,
        telemetry=None,
        advertise_trace: bool = True,
        auth_key: Optional[bytes] = None,
        logger: Optional[Logger] = None,
    ):
        super().__init__("VerifyService", logger)
        self._sched = scheduler
        self._family, self._target = parse_address(address)
        self._coalesce = bool(coalesce)
        self._auth_key = bytes(auth_key) if auth_key else None
        if self._auth_key is not None and not advertise_trace:
            # the challenge rides the HELLO payload; a server simulating
            # the v1 empty-payload HELLO cannot also demand auth
            raise ValueError("auth_key requires advertise_trace=True")
        self._draining = False
        # advertise_trace=False simulates a v1 server (no capability byte
        # in the HELLO payload, so v2 clients stay on the pure v1 wire)
        self._advertise_trace = bool(advertise_trace)
        if max_lanes is None:
            max_lanes = getattr(scheduler, "_lane_budget", None) or 8192
        self._max_lanes = max(1, int(max_lanes))
        self._max_frame = max_frame_bytes(self._max_lanes)
        self._row_verifier = row_verifier
        self.metrics = metrics if metrics is not None else ServiceMetrics.nop()
        self._telemetry = telemetry
        self._listener: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._conns: set = set()
        self._cmtx = threading.Lock()
        self._bound: Optional[Any] = None
        # snapshot source-of-truth counters (the instruments may be nop)
        self._smtx = threading.Lock()
        self._frames: Dict[str, int] = {}
        self._lanes: Dict[str, int] = {}
        self._payload_bytes: Dict[str, int] = {}
        self._errors: Dict[str, int] = {}
        self._disconnects: Dict[str, int] = {}
        self._stale_drops = 0
        self._drain_refusals = 0
        self._auth_ok = 0
        self._auth_rejects = 0
        self._inline_dispatches = 0
        # row requests whose compact block was built at admission (every
        # one admitted: beside ``served`` it says no flush built any)
        self._rows_prebuilt = 0
        # every tenant together: responses enqueued, and the seconds from
        # their frames decoded to then (the socket's legs are the
        # client's round trip less this)
        self._served = 0
        self._served_s = 0.0
        # per-tenant service panel: RED + wire shape + refusal taxonomy
        self._tenant_stats: Dict[str, Dict[str, Any]] = {}
        if telemetry is not None:
            telemetry.register_source("service", self.snapshot)

    def _tenant(self, tenant: Optional[str]) -> Dict[str, Any]:
        """The per-tenant stats record (callers hold _smtx)."""
        rec = self._tenant_stats.get(tenant or "unknown")
        if rec is None:
            rec = self._tenant_stats[tenant or "unknown"] = {
                "requests": 0,
                "responses": 0,
                "rejected": 0,
                "dur_total_s": 0.0,
                "lanes": {},
                "payload_bytes": 0,
                "refusals": {},
                "disconnects": 0,
                "registrations": 0,
                "generations_seen": 0,
                "last_generation": None,
            }
        return rec

    # -- lifecycle ---------------------------------------------------------

    def on_start(self) -> None:
        if self._family == "unix":
            path = self._target
            try:
                os.unlink(path)
            except OSError:
                pass
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            sock.bind(path)
            self._bound = path
        else:
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            sock.bind(self._target)
            self._bound = sock.getsockname()
        sock.listen(128)
        self._listener = sock
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True, name="verify-service"
        )
        self._accept_thread.start()
        self.logger.info(
            "verify service listening", address=self.address(),
            max_lanes=self._max_lanes, coalesce=self._coalesce,
        )

    def on_stop(self) -> None:
        listener = self._listener
        if listener is not None:
            # shutdown() first: close() alone does not wake a thread
            # blocked in accept() on the same fd, and the join below
            # would eat its full timeout on every daemon stop
            try:
                listener.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                listener.close()
            except OSError:
                pass
        t = self._accept_thread
        if t is not None:
            t.join(timeout=5.0)
        with self._cmtx:
            conns = list(self._conns)
        for conn in conns:
            self._teardown(conn)
        for conn in conns:
            for t in (conn.reader, conn.writer):
                if t is not None and t is not threading.current_thread():
                    t.join(timeout=5.0)
        if self._family == "unix":
            try:
                os.unlink(self._target)
            except OSError:
                pass

    def address(self) -> str:
        """The actual bound address (tcp port 0 resolves here)."""
        if self._family == "unix":
            return f"unix://{self._bound or self._target}"
        host, port = self._bound or self._target
        return f"tcp://{host}:{port}"

    def pending_requests(self) -> int:
        """Accepted-but-unanswered requests across live connections —
        the never-leak-past-stop invariant's observable (0 after
        stop())."""
        with self._cmtx:
            conns = list(self._conns)
        total = 0
        for conn in conns:
            with conn.mtx:
                total += len(conn.pending)
        return total

    # -- accept + per-connection threads -----------------------------------

    def _accept_loop(self) -> None:
        while not self._quit.is_set():
            try:
                sock, _ = self._listener.accept()
            except OSError:
                return  # listener closed by stop()
            conn = _Conn(sock)
            with self._cmtx:
                self._conns.add(conn)
            # Capability advertisement rides the HELLO *payload*
            # ([version, flags, challenge?]). The header stays version 1
            # so v1 clients decode it, and v1 clients provably ignore
            # HELLO payload bytes — only a v2 client reads them and
            # starts shipping extended frames / answering the challenge.
            if self._advertise_trace:
                flags = 0
                challenge = b""
                if self._draining:
                    flags |= HELLO_FLAG_DRAINING
                if self._auth_key is not None:
                    conn.challenge = os.urandom(AUTH_CHALLENGE_BYTES)
                    flags |= HELLO_FLAG_AUTH
                    challenge = conn.challenge
                hello_payload = bytes((VERSION, flags)) + challenge
            else:
                hello_payload = b""
            self._enqueue(conn, encode_frame(
                FT_HELLO, n_lanes=self._max_lanes,
                generation=self._generation(),
                payload=hello_payload,
            ))
            conn.writer = threading.Thread(
                target=self._write_loop, args=(conn,), daemon=True,
                name="verify-service-w",
            )
            conn.reader = threading.Thread(
                target=self._read_loop, args=(conn,), daemon=True,
                name="verify-service-r",
            )
            conn.writer.start()
            conn.reader.start()

    def _read_loop(self, conn: _Conn) -> None:
        try:
            while conn.alive and not self._quit.is_set():
                head = _recv_exact(conn.sock, _LEN.size)
                if head is None:
                    break
                (length,) = _LEN.unpack(head)
                if length > self._max_frame:
                    # typed refusal, then discard the body: the stream
                    # stays framed, the connection survives
                    self._send_err(conn, 0, ERR_OVERSIZE, (
                        f"frame of {length} bytes exceeds the "
                        f"{self._max_frame}-byte bound"
                    ))
                    if not _drain(conn.sock, length):
                        break
                    continue
                if length < HEADER_BYTES:
                    # the stream cannot be re-framed after a short
                    # header — refuse and hang up
                    self._send_err(conn, 0, ERR_MALFORMED, (
                        f"frame of {length} bytes is shorter than the "
                        f"{HEADER_BYTES}-byte header"
                    ))
                    break
                buf = _recv_exact(conn.sock, length)
                if buf is None:
                    break  # truncated mid-frame: disconnect path
                with self._smtx:
                    self._payload_bytes["rx"] = (
                        self._payload_bytes.get("rx", 0) + length
                    )
                self.metrics.bytes_rx.add(length)
                try:
                    frame = decode_frame(buf)
                except FrameError as fe:
                    # bad magic / future version: framing is untrusted
                    self._send_err(conn, 0, fe.code, str(fe))
                    break
                try:
                    self._handle(conn, frame)
                except _FatalFrameError as fe:
                    # typed refusal, then hang up (repeated auth
                    # failures): the drain window in _teardown flushes
                    # the error frame to the refused client first
                    self._send_err(conn, frame.req_id, fe.code, str(fe))
                    break
                except FrameError as fe:
                    # per-request refusal (bad class, stale generation,
                    # unknown valset, size mismatch): typed error, the
                    # connection and its other requests survive
                    self._send_err(conn, frame.req_id, fe.code, str(fe))
        except Exception as exc:  # noqa: BLE001 - one conn never kills accept
            self.logger.error(
                "verify service connection failed", err=repr(exc),
                tenant=conn.tenant,
            )
        finally:
            self._teardown(conn, drain=True)

    def _write_loop(self, conn: _Conn) -> None:
        while True:
            with conn.cv:
                while conn.alive and not conn.outq:
                    conn.cv.wait(0.5)
                if not conn.alive and not conn.outq:
                    return
                data = conn.outq.popleft()
            try:
                conn.sock.sendall(data)
            except OSError:
                self._teardown(conn)
                return
            self.metrics.bytes_tx.add(len(data))

    def _teardown(self, conn: _Conn, drain: bool = False) -> None:
        """Idempotent connection teardown. Pending futures stay with the
        scheduler (they complete inside their coalesced flush — other
        tenants' riders are untouched); THIS tenant's in-flight requests
        are metered as disconnected and their responses dropped.

        ``drain`` (the reader's hangup path only) gives the writer a
        bounded window to flush queued frames first — a header-level
        refusal enqueues its typed error right before the reader breaks,
        and closing the socket immediately would race that error frame
        away from the very client it refuses. The writer's own failure
        path must NOT drain: its queue can never send again."""
        if drain:
            deadline = time.monotonic() + 0.5
            while time.monotonic() < deadline:
                with conn.mtx:
                    if not conn.outq or not conn.alive:
                        break
                time.sleep(0.005)
        with conn.mtx:
            if not conn.alive:
                return
            conn.alive = False
            n_pending = len(conn.pending)
            conn.pending.clear()
            conn.cv.notify_all()
        tenant = conn.tenant or "unknown"
        if n_pending:
            with self._smtx:
                self._disconnects[tenant] = (
                    self._disconnects.get(tenant, 0) + n_pending
                )
                self._tenant(tenant)["disconnects"] += n_pending
            self.metrics.disconnects.with_labels(tenant=tenant).add(
                n_pending
            )
            if self._telemetry is not None:
                self._telemetry.note_disconnect(tenant, n_pending)
            self.logger.info(
                "client disconnected mid-flight", tenant=tenant,
                pending=n_pending,
            )
        with self._cmtx:
            self._conns.discard(conn)
        # shutdown() before close(): the reader may be blocked in
        # recv() on this fd, and close() alone does not wake it — the
        # stop path would then burn its full join timeout per conn
        try:
            conn.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            conn.sock.close()
        except OSError:
            pass
        self.metrics.pending.set(self.pending_requests())

    # -- frame handling ----------------------------------------------------

    def _handle(self, conn: _Conn, frame: Frame) -> None:
        name = _FT_NAMES.get(frame.ftype)
        if name is None:
            raise FrameError(ERR_MALFORMED, f"unknown frame type {frame.ftype}")
        with self._smtx:
            self._frames[name] = self._frames.get(name, 0) + 1
        self.metrics.frames.with_labels(type=name).add()
        if frame.ftype == FT_CLIENT_HELLO:
            # a tenant HINT only: under auth the authenticated node id
            # wins (set in _handle_auth), so a client cannot ride
            # another tenant's quota by renaming its connection
            if not (self._auth_key is not None and conn.authenticated):
                conn.tenant = frame.payload.decode(
                    "utf-8", errors="replace"
                ) or None
            return
        if frame.ftype == FT_AUTH:
            self._handle_auth(conn, frame)
            return
        if self._auth_key is not None and not conn.authenticated and \
                frame.ftype in (FT_REQ, FT_REGISTER):
            # unauthenticated work NEVER reaches the scheduler
            raise FrameError(
                ERR_UNAUTHORIZED, "session not authenticated"
            )
        if frame.ftype == FT_REGISTER:
            self._handle_register(conn, frame)
            return
        if frame.ftype == FT_REQ:
            self._handle_req(conn, frame)
            return
        # HELLO/RESP/ERR/REGISTERED/AUTH_OK/DRAINING are
        # server-to-client only
        raise FrameError(
            ERR_MALFORMED, f"unexpected client frame type {name}"
        )

    def _handle_auth(self, conn: _Conn, frame: Frame) -> None:
        if self._auth_key is None:
            # no key configured: acknowledge so a keyed client pointed
            # at an open server still completes its handshake
            conn.authenticated = True
            self._enqueue(conn, encode_frame(
                FT_AUTH_OK, req_id=frame.req_id,
                generation=self._generation(),
            ))
            return
        payload = frame.payload
        ok = False
        node_id = ""
        if len(payload) > AUTH_MAC_BYTES and conn.challenge is not None:
            mac = payload[:AUTH_MAC_BYTES]
            node_id = payload[AUTH_MAC_BYTES:].decode(
                "utf-8", errors="replace"
            )
            want = auth_mac(self._auth_key, conn.challenge, node_id)
            ok = bool(node_id) and hmac.compare_digest(mac, want)
        if not ok:
            conn.auth_fails += 1
            with self._smtx:
                self._auth_rejects += 1
            if conn.auth_fails >= MAX_AUTH_ATTEMPTS:
                raise _FatalFrameError(
                    ERR_UNAUTHORIZED,
                    f"auth refused {conn.auth_fails} times; disconnecting",
                )
            raise FrameError(ERR_UNAUTHORIZED, "bad auth response")
        conn.authenticated = True
        # tenant identity = the authenticated node id: quotas and RED
        # metering follow the key holder across reconnects and NAT
        conn.tenant = node_id
        with self._smtx:
            self._auth_ok += 1
        if self._telemetry is not None:
            self._telemetry.note_event(
                "session_authenticated", {"tenant": node_id}
            )
        self._enqueue(conn, encode_frame(
            FT_AUTH_OK, req_id=frame.req_id,
            generation=self._generation(),
        ))

    def _handle_register(self, conn: _Conn, frame: Frame) -> None:
        payload = frame.payload
        if not payload or len(payload) % 32:
            raise FrameError(
                ERR_MALFORMED,
                f"register payload of {len(payload)} bytes is not a "
                f"multiple of 32",
            )
        n = len(payload) // 32
        if n > MAX_REGISTER_KEYS:
            raise FrameError(
                ERR_OVERSIZE, f"{n} keys exceeds the register bound "
                f"{MAX_REGISTER_KEYS}",
            )
        valset_id = hashlib.sha256(payload).digest()[:VALSET_ID_BYTES]
        keys = [payload[i * 32:(i + 1) * 32] for i in range(n)]
        store = self._keystore()
        store.register(valset_id, keys)
        gen = store.generation()
        tenant = conn.tenant or "unknown"
        with self._smtx:
            self._tenant(conn.tenant)["registrations"] += 1
        self.metrics.registrations.with_labels(tenant=tenant).add()
        if self._telemetry is not None:
            self._telemetry.note_event("valset_registered", {
                "tenant": tenant, "keys": n, "generation": gen,
            })
        self._enqueue(conn, encode_frame(
            FT_REGISTERED, req_id=frame.req_id, n_lanes=n,
            generation=gen, valset_id=valset_id,
        ))

    def _handle_req(self, conn: _Conn, frame: Frame) -> None:
        with tracelib.stage("svc.admit"):
            self._admit(conn, frame, time.monotonic())

    def _admit(self, conn: _Conn, frame: Frame, t0: float) -> None:
        """A decoded REQ frame to ``submit_rows`` returned: bounds, the
        freshness rule, the request's compact block built (``svc.gather``
        inside this stage), counters, QoS admission."""
        if self._draining:
            # graceful drain: new work is refused with a typed
            # ST_DRAINING response (clients fail over immediately
            # instead of eating a timeout); in-flight work still answers
            tenant = conn.tenant or "unknown"
            with self._smtx:
                self._drain_refusals += 1
                rec = self._tenant(conn.tenant)
                rec["refusals"]["draining"] = (
                    rec["refusals"].get("draining", 0) + 1
                )
            self.metrics.refusals.with_labels(
                tenant=tenant, code="draining"
            ).add()
            self._enqueue(conn, encode_frame(
                FT_RESP, req_id=frame.req_id, n_lanes=0,
                generation=self._generation(),
                payload=bytes((ST_DRAINING,)),
            ))
            return
        n = frame.n_lanes
        if n < 1 or n > self._max_lanes:
            raise FrameError(
                ERR_MALFORMED,
                f"{n} lanes outside the [1, {self._max_lanes}] bound",
            )
        expect = req_payload_bytes(frame.kind, n)
        if len(frame.payload) != expect:
            raise FrameError(
                ERR_MALFORMED,
                f"{_KIND_NAMES[frame.kind]} payload of "
                f"{len(frame.payload)} bytes for {n} lanes "
                f"(expected {expect})",
            )
        try:
            qname = qoslib.class_name(frame.qclass)
        except ValueError as exc:
            raise FrameError(ERR_BAD_CLASS, str(exc)) from None
        kind_name = _KIND_NAMES[frame.kind]
        if frame.kind == KIND_COMPACT:
            payload = RowPayload.from_compact(frame.payload)
        else:
            store = self._keystore()
            entry = store.entry_for(frame.valset_id, frame.generation)
            if entry is None:
                if frame.generation != store.generation():
                    with self._smtx:
                        self._stale_drops += 1
                    self.metrics.stale_drops.add()
                    raise FrameError(
                        ERR_STALE_GENERATION,
                        f"client generation {frame.generation} != "
                        f"{store.generation()}",
                    )
                raise FrameError(
                    ERR_UNKNOWN_VALSET,
                    f"valset {frame.valset_id.hex()} is not registered",
                )
            idx = np.frombuffer(frame.payload[RSH_ROW_BYTES * n:], "<i4")
            if idx.size and (idx.min() < 0 or idx.max() >= entry.n):
                raise FrameError(
                    ERR_MALFORMED,
                    f"table index outside [0, {entry.n})",
                )
            # the key rows leave the entry HERE, on this connection's
            # reader thread: the flush joins finished blocks, and an
            # entry that leaves the store from now on changes nothing
            with tracelib.stage("svc.gather"):
                payload = RowPayload.from_indexed(
                    frame.payload[: RSH_ROW_BYTES * n], idx, entry
                )
        with self._smtx:
            self._rows_prebuilt += 1
            self._lanes[kind_name] = self._lanes.get(kind_name, 0) + n
            self._payload_bytes[kind_name] = (
                self._payload_bytes.get(kind_name, 0) + len(frame.payload)
            )
            rec = self._tenant(conn.tenant)
            rec["requests"] += 1
            rec["lanes"][kind_name] = rec["lanes"].get(kind_name, 0) + n
            rec["payload_bytes"] += len(frame.payload)
            if rec["last_generation"] != frame.generation:
                rec["last_generation"] = frame.generation
                rec["generations_seen"] += 1
        self.metrics.lanes.with_labels(kind=kind_name).add(n)
        self.metrics.rows_prebuilt.add()
        self.metrics.bytes_per_lane.with_labels(kind=kind_name).set(
            len(frame.payload) / n
        )
        if not self._coalesce:
            self._dispatch_isolated(conn, frame, payload, t0)
            return
        fut = self._sched.submit_rows(
            payload, tenant=conn.tenant, qclass=qname,
            trace_ctx=frame.trace_ctx,
        )
        with conn.mtx:
            if not conn.alive:
                return  # raced teardown: disconnect already metered
            conn.pending[frame.req_id] = (n, t0)
        self.metrics.pending.set(self.pending_requests())
        # the callback keeps the request's id and size, not its frame: a
        # pending request holds its 128 B a lane once, in the payload
        fut.add_done_callback(
            lambda f, c=conn, rid=frame.req_id, n=n: self._complete(
                c, rid, n, f
            )
        )

    def _dispatch_isolated(
        self, conn: _Conn, frame: Frame, payload: RowPayload, t0: float
    ) -> None:
        """coalesce=False: verify this frame alone, in this reader
        thread — the per-client-isolated baseline the bench stage
        measures the coalescing gain against."""
        verifier = self._row_verifier
        if verifier is None:
            verifier = self._row_verifier = resolve_row_verifier(
                getattr(self._sched, "spec", None)
            )
        rows, valid = payload.as_compact()
        mask = np.asarray(verifier(rows), dtype=bool)[: payload.n] & valid
        self._respond(conn, frame.req_id, ST_OK, mask)
        with self._smtx:
            self._inline_dispatches += 1
            self._served += 1
            self._served_s += time.monotonic() - t0

    def _complete(self, conn: _Conn, req_id: int, n_lanes: int,
                  fut: VerifyFuture) -> None:
        """Done-callback on the scheduler's worker (or an inline-dispatch
        submitter): encode the verdict and hand it to the connection's
        writer — never block the flush loop on a client socket."""
        with tracelib.stage("svc.respond"):
            with conn.mtx:
                known = conn.pending.pop(req_id, None)
            self.metrics.pending.set(self.pending_requests())
            if known is None or not conn.alive:
                return  # disconnected mid-flight: metered in _teardown
            try:
                _, sub = fut.result(timeout=0)
                mask = np.asarray(sub, dtype=bool)
                status = ST_REJECTED if fut.rejected else ST_OK
            except Exception:  # noqa: BLE001 - failed flush = rejected verdict
                mask = np.zeros(n_lanes, dtype=bool)
                status = ST_REJECTED
            self._respond(conn, req_id, status, mask)
            _, t0 = known
            dur = time.monotonic() - t0
            with self._smtx:
                self._served += 1
                self._served_s += dur
                rec = self._tenant(conn.tenant)
                rec["responses"] += 1
                rec["dur_total_s"] += dur
                if status == ST_REJECTED:
                    rec["rejected"] += 1

    def _respond(self, conn: _Conn, req_id: int, status: int,
                 mask: np.ndarray) -> None:
        payload = bytes([status]) + np.packbits(
            mask, bitorder="little"
        ).tobytes()
        self._enqueue(conn, encode_frame(
            FT_RESP, req_id=req_id, n_lanes=int(mask.size),
            generation=self._generation(), payload=payload,
        ))

    def _send_err(self, conn: _Conn, req_id: int, code: int, msg: str
                  ) -> None:
        name = ERR_NAMES.get(code, str(code))
        tenant = conn.tenant or "unknown"
        with self._smtx:
            self._errors[name] = self._errors.get(name, 0) + 1
            rec = self._tenant(conn.tenant)
            rec["refusals"][name] = rec["refusals"].get(name, 0) + 1
        self.metrics.errors.with_labels(code=name).add()
        self.metrics.refusals.with_labels(tenant=tenant, code=name).add()
        self._enqueue(conn, encode_frame(
            FT_ERR, req_id=req_id, generation=self._generation(),
            payload=encode_error(code, msg),
        ))

    def _enqueue(self, conn: _Conn, data: bytes) -> None:
        with conn.cv:
            if not conn.alive:
                return
            conn.outq.append(data)
            conn.cv.notify_all()

    # -- graceful drain ----------------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self, broadcast: bool = True) -> None:
        """Enter graceful drain: stop admitting new REQ frames (typed
        ST_DRAINING refusals), keep answering in-flight work, and
        broadcast FT_DRAINING so connected clients stop picking this
        endpoint for new submits. Idempotent; the listener keeps
        accepting (new connections see the draining HELLO flag).
        ``broadcast=False`` sets the flag without notifying — the chaos
        harness uses it to exercise the per-request ST_DRAINING path
        deterministically."""
        with self._smtx:
            first = not self._draining
            self._draining = True
        if first:
            self.logger.info(
                "verify service draining",
                pending=self.pending_requests(),
            )
            if self._telemetry is not None:
                self._telemetry.note_event("drain_started", {
                    "pending": self.pending_requests(),
                })
        if not broadcast:
            return
        with self._cmtx:
            conns = list(self._conns)
        for conn in conns:
            self._enqueue(conn, encode_frame(
                FT_DRAINING, generation=self._generation(),
            ))

    # -- keystore (generation handshake) -----------------------------------

    def _keystore(self):
        from cometbft_tpu.crypto.tpu import keystore

        return keystore.default_store()

    def _generation(self) -> int:
        # same sys.modules guard as the scheduler's decision inputs: a
        # compact-only CPU service never imports the TPU package just to
        # stamp generation 0 on its frames
        ks = sys.modules.get("cometbft_tpu.crypto.tpu.keystore")
        if ks is None:
            return 0
        try:
            return ks.default_store().generation()
        except Exception:  # noqa: BLE001 - advisory header field
            return 0

    def _keystore_residency(self) -> Optional[dict]:
        """The key store's entries, generation, evictions and thrash;
        None while nothing has imported it (a compact-only service)."""
        ks = sys.modules.get("cometbft_tpu.crypto.tpu.keystore")
        if ks is None:
            return None
        try:
            return ks.default_store().residency()
        except Exception:  # noqa: BLE001 - a panel, never a failure
            return None

    # -- observability -----------------------------------------------------

    def snapshot(self) -> dict:
        """The "service" TelemetryHub source: connection/tenant counts,
        frame/lane/byte counters, and the bytes-per-lane proof."""
        with self._cmtx:
            conns = list(self._conns)
        tenants = sorted({c.tenant for c in conns if c.tenant})
        with self._smtx:
            lanes = dict(self._lanes)
            payload_bytes = dict(self._payload_bytes)
            panel = {}
            for name, rec in self._tenant_stats.items():
                row = dict(rec)
                row["lanes"] = dict(rec["lanes"])
                row["refusals"] = dict(rec["refusals"])
                resp = rec["responses"]
                row["mean_ms"] = (
                    rec["dur_total_s"] / resp * 1e3 if resp else 0.0
                )
                lane_total = sum(rec["lanes"].values())
                row["bytes_per_lane"] = (
                    rec["payload_bytes"] / lane_total if lane_total else 0.0
                )
                panel[name] = row
            out = {
                "address": self.address() if self._bound else None,
                "protocol_version": VERSION,
                "coalesce": self._coalesce,
                "max_lanes": self._max_lanes,
                "connections": len(conns),
                "tenants": tenants,
                "frames": dict(self._frames),
                "lanes": lanes,
                "errors": dict(self._errors),
                "disconnects": dict(self._disconnects),
                "stale_drops": self._stale_drops,
                "draining": self._draining,
                "drain_refusals": self._drain_refusals,
                "auth_required": self._auth_key is not None,
                "auth_ok": self._auth_ok,
                "auth_rejects": self._auth_rejects,
                "inline_dispatches": self._inline_dispatches,
                "rows_prebuilt": self._rows_prebuilt,
                "served": self._served,
                "served_s": self._served_s,
                "tenants_panel": panel,
            }
        out["pending"] = self.pending_requests()
        out["backend"] = getattr(self._sched.spec, "name", None)
        out["device_plane"] = resolved_device_plane()
        out["keystore"] = self._keystore_residency()
        out["bytes_per_lane"] = {
            kind: payload_bytes[kind] / lanes[kind]
            for kind in ("compact", "indexed")
            if lanes.get(kind)
        }
        return out


# -- client ------------------------------------------------------------------


class _ClientValset:
    __slots__ = ("valset_id", "index", "pub_keys", "registered_gen")

    def __init__(self, valset_id, index, pub_keys, registered_gen):
        self.valset_id = valset_id
        self.index = index
        self.pub_keys = pub_keys
        self.registered_gen = registered_gen


class _Agg:
    """One submit()'s state across its frame parts (requests larger than
    the server's max_lanes split into several frames). Any part failing
    — rejected, typed error, timeout, disconnect — flips the whole
    request to the local CPU ground truth exactly once."""

    __slots__ = ("items", "future", "mask", "remaining", "failed",
                 "req_ids", "mtx", "span", "wire_span", "ctx")

    def __init__(self, items, future, n_parts):
        self.items = items
        self.future = future
        self.mask = np.zeros(len(items), dtype=bool)
        self.remaining = n_parts
        self.failed = False
        self.req_ids: List[int] = []
        self.mtx = threading.Lock()
        # opaque HA-failover context (crypto/ha.py), handed back to the
        # failover hook so the fleet layer can resubmit these items to a
        # secondary even when submit() fails before returning
        self.ctx = None
        # client-side trace spans (NOOP_SPAN when unsampled): the submit
        # root whose id ships in the v2 extension, and the wire_wait
        # child covering send -> final verdict
        self.span = None
        self.wire_span = None


class _PendingPart:
    __slots__ = ("agg", "base", "sent_idx", "deadline", "qcode", "t_sent")

    def __init__(self, agg, base, sent_idx, deadline, qcode):
        self.agg = agg
        self.base = base
        self.sent_idx = sent_idx
        self.deadline = deadline
        self.qcode = qcode
        self.t_sent = 0.0  # when its frame went to the socket


class RemoteVerifier:
    """Client half: duck-types the crypto Backend contract the way the
    scheduler does (``spec`` + ``submit(items, subsystem=, height=) ->
    VerifyFuture``), so ``new_batch_verifier`` adapts it for every call
    site unchanged. Packs each request ONCE into compact (or indexed,
    when a registered valset covers it at the server's current keystore
    generation) wire rows, demuxes verdicts by req_id on a receiver
    thread, and falls back to the LOCAL CPU ground truth — with the
    verdict reason kept distinct — on disconnect, timeout, rejection, or
    stale generation. No caller ever hangs on a dead daemon."""

    def __init__(
        self,
        address: str,
        tenant: Optional[str] = None,
        spec=None,
        timeout_ms: Optional[int] = None,
        connect_timeout_s: float = 1.0,
        retry_s: float = 1.0,
        retry_cap_s: float = 30.0,
        auth_key: Optional[bytes] = None,
        node_id: Optional[str] = None,
        failover: Optional[Callable] = None,
        tracer=None,
        telemetry=None,
        logger: Optional[Logger] = None,
    ):
        if isinstance(spec, BackendSpec):
            self.spec = spec
        else:
            self.spec = BackendSpec(name=spec) if spec else BackendSpec(
                name="cpu"
            )
        self._address = address
        self._family, self._target = parse_address(address)
        self._tenant = tenant or "remote"
        self._timeout_s = service_timeout_default(timeout_ms) / 1e3
        self._connect_timeout_s = connect_timeout_s
        self._retry_s = retry_s
        self._retry_cap_s = max(retry_cap_s, retry_s)
        self._auth_key = bytes(auth_key) if auth_key else None
        self._node_id = node_id or self._tenant
        # failover(items, reason, future, ctx) -> bool: the HA rung
        # (crypto/ha.py). True = it owns completing the future on a
        # secondary; False/raise = fall through to the local CPU rung.
        self._failover = failover
        self._tracer = tracer
        self._telemetry = telemetry
        # highest protocol version the server advertised (HELLO payload
        # byte); trace extensions ship only when it is >= 2
        self._server_proto = 1
        self._server_flags = 0
        self._server_draining = False
        self._challenge: Optional[bytes] = None
        self._hello_evt: Optional[threading.Event] = None
        # [done Event, ok bool] for the in-flight AUTH round-trip
        self._auth_waiter: Optional[list] = None
        self.logger = logger
        self._mtx = threading.Lock()
        # serializes the connect+handshake so a concurrent submit can
        # never race a half-authenticated socket with an FT_REQ (the
        # server would refuse it ERR_UNAUTHORIZED despite a good key)
        self._conn_lock = threading.Lock()
        self._session_ready = False
        self._sock: Optional[socket.socket] = None
        self._recv_thread: Optional[threading.Thread] = None
        self._pending: Dict[int, _PendingPart] = {}
        self._reg_waiters: Dict[int, list] = {}
        self._req_id = 0
        self._server_gen: Optional[int] = None
        self._max_lanes = 8192
        self._valsets: Dict[bytes, _ClientValset] = {}
        self._stats: Dict[str, int] = {}
        # seconds from a REQ frame handed to the socket to its RESP
        # decoded, over the ``rtts`` in _stats: with the server's
        # served_s, what the two socket legs and the hand-offs cost
        self._rtt_s = 0.0
        self._next_retry = 0.0
        self._connect_fails = 0
        self._auth_fails = 0
        self._last_backoff_s = 0.0
        self._rng = random.Random()
        self._closed = False

    # -- Backend contract --------------------------------------------------

    def submit(
        self,
        items: Sequence[Item],
        subsystem: Optional[str] = None,
        height: Optional[int] = None,
        failover_ctx=None,
    ) -> VerifyFuture:
        triples = [(pk, bytes(m), bytes(s)) for pk, m, s in items]
        fut = VerifyFuture()
        if not triples:
            fut._set((True, []))
            return fut
        agg = _Agg(triples, fut, 0)
        agg.ctx = failover_ctx
        if self._tracer is not None:
            agg.span = self._tracer.start_remote_root(
                "submit", n_sigs=len(triples), tenant=self._tenant,
                subsystem=subsystem or "?", transport="remote",
            )
        try:
            self._submit_remote(agg, subsystem)
        except AuthError:
            # the fleet shares the key — a secondary would refuse the
            # same credentials, so never failover, go straight to CPU
            self._fail_agg(agg, "unauthorized")
        except Exception:  # noqa: BLE001 - daemon down: local ground truth
            self._fail_agg(agg, "disconnected")
        return fut

    def close(self) -> None:
        with self._mtx:
            self._closed = True
            sock = self._sock
            self._sock = None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
        self._drop_pending("disconnected")

    def kill_connection(self) -> None:
        """Chaos hook: sever the transport abruptly (no close frame, no
        draining) as if the client process died mid-flight. In-flight
        futures resolve via the local-CPU fallback with
        ``reason="disconnected"``; the next submit reconnects."""
        with self._mtx:
            sock = self._sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass

    # -- request path ------------------------------------------------------

    def _submit_remote(self, agg: _Agg, subsystem: Optional[str]) -> None:
        self._ensure_connected()
        qcode = qoslib.class_code(
            qoslib.SUBSYSTEM_ALIASES.get(subsystem, subsystem)
        )
        root = agg.span
        traced = root is not None and not root.noop
        # ship the trace context only when the server advertised v2 — a
        # v1 server would refuse the extended frame outright
        ctx = (
            (root.trace_id, root.span_id, True)
            if traced and self._server_proto >= 2 else None
        )
        valset = self._covering_valset(agg.items)
        deadline = time.monotonic() + self._timeout_s
        pack_span = root.child("pack") if traced else None
        parts: List[Tuple[bytes, _PendingPart]] = []
        base = 0
        step = max(1, self._max_lanes)
        while base < len(agg.items):
            part_items = agg.items[base:base + step]
            if valset is not None:
                rsh, idx, valid = pack_items_indexed(
                    part_items, valset.index
                )
                sent = np.nonzero(valid)[0]
                payload = (
                    np.ascontiguousarray(rsh[:, sent]).tobytes()
                    + np.ascontiguousarray(idx[sent]).tobytes()
                )
                kind = KIND_INDEXED
            else:
                wire, valid = pack_items_compact(part_items)
                sent = np.nonzero(valid)[0]
                # all-valid is the common case and ships the packed
                # buffer as-is — pack once, send those bytes
                if sent.size == len(part_items):
                    payload = wire.tobytes()
                else:
                    payload = np.ascontiguousarray(
                        wire[:, sent]
                    ).tobytes()
                kind = KIND_COMPACT
            if sent.size:
                with self._mtx:
                    self._req_id += 1
                    rid = self._req_id
                    pend = _PendingPart(agg, base, sent, deadline, qcode)
                    self._pending[rid] = pend
                    self._stats["req_frames"] = (
                        self._stats.get("req_frames", 0) + 1
                    )
                agg.req_ids.append(rid)
                agg.remaining += 1
                frame = encode_frame(
                    FT_REQ, qclass=qcode, kind=kind, req_id=rid,
                    n_lanes=int(sent.size),
                    generation=(valset.registered_gen if valset else 0),
                    valset_id=(valset.valset_id if valset else b""),
                    payload=payload, trace_ctx=ctx,
                )
                parts.append((frame, pend))
            base += step
        if pack_span is not None:
            pack_span.end(
                parts=len(parts),
                kind=_KIND_NAMES[KIND_INDEXED if valset else KIND_COMPACT],
            )
        if not parts:
            # every lane was locally known-invalid: exact verdict, no
            # frame, no fallback
            agg.future._set((False, [False] * len(agg.items)))
            self._finish_spans(agg, "local_invalid")
            return
        if traced:
            agg.wire_span = root.child("wire_wait", parts=len(parts))
        for frame, pend in parts:
            pend.t_sent = time.monotonic()
            try:
                self._send(frame)
            except OSError as exc:
                self._on_disconnect()
                raise ConnectionError(str(exc)) from exc

    def _covering_valset(self, items) -> Optional[_ClientValset]:
        """A registered valset covering every pubkey of the request, at
        the server's CURRENT generation — re-registering first when the
        cached one went stale (the resync half of the handshake). None
        means ship full 128 B compact rows."""
        with self._mtx:
            valsets = list(self._valsets.values())
            server_gen = self._server_gen
        for vs in valsets:
            try:
                covered = all(
                    _pk_bytes(pk) in vs.index for pk, _, _ in items
                )
            except Exception:  # noqa: BLE001 - unhashable key: compact
                continue
            if not covered:
                continue
            if vs.registered_gen == server_gen and server_gen is not None:
                return vs
            try:
                self._register(vs.pub_keys)
                return self._valsets.get(vs.valset_id)
            except Exception:  # noqa: BLE001 - resync failed: compact
                self._count("resync_failed")
                return None
        return None

    def register_valset(self, pub_keys: Sequence[bytes]) -> bytes:
        """Register a valset with the server's keystore so later
        submits covered by it ship 100 B indexed frames. Returns the
        16-byte valset id. Raises on a dead daemon (callers treat
        registration as an optimization)."""
        self._ensure_connected()
        return self._register(pub_keys)

    def _register(self, pub_keys: Sequence[bytes]) -> bytes:
        keys = [_pk_bytes(pk) for pk in pub_keys]
        if not keys or any(len(k) != 32 for k in keys):
            raise ValueError("register_valset needs 32-byte ed25519 keys")
        if len(keys) > MAX_REGISTER_KEYS:
            raise ValueError(
                f"{len(keys)} keys exceeds the register bound "
                f"{MAX_REGISTER_KEYS}"
            )
        payload = b"".join(keys)
        valset_id = hashlib.sha256(payload).digest()[:VALSET_ID_BYTES]
        waiter = [threading.Event(), None]
        with self._mtx:
            self._req_id += 1
            rid = self._req_id
            self._reg_waiters[rid] = waiter
            self._stats["register_frames"] = (
                self._stats.get("register_frames", 0) + 1
            )
        try:
            self._send(encode_frame(
                FT_REGISTER, req_id=rid, n_lanes=len(keys),
                payload=payload,
            ))
            if not waiter[0].wait(self._timeout_s):
                raise TimeoutError("valset registration timed out")
        finally:
            with self._mtx:
                self._reg_waiters.pop(rid, None)
        gen = waiter[1]
        index = {k: i for i, k in enumerate(keys)}
        with self._mtx:
            self._server_gen = gen
            self._valsets[valset_id] = _ClientValset(
                valset_id, index, list(keys), gen
            )
        self._count("registrations")
        return valset_id

    # -- connection --------------------------------------------------------

    def _note_retry(self, auth: bool = False) -> None:
        """Capped exponential backoff with full jitter before the next
        connect attempt — a dead daemon is not hammered in lockstep by
        every node whose socket it dropped. Auth refusals back off the
        same way (equal jitter, so the bounded-attempts property is
        deterministic) without resetting on mere TCP success."""
        with self._mtx:
            if auth:
                self._auth_fails += 1
                fails = self._auth_fails
            else:
                self._connect_fails += 1
                fails = self._connect_fails
            window = min(
                self._retry_cap_s,
                max(self._retry_s, 1e-3) * (2 ** min(fails - 1, 16)),
            )
            lo = window / 2 if auth else 0.0
            self._last_backoff_s = window
            # max(): the teardown path also notes a retry, and its
            # fresh (small) window must not shrink an auth backoff
            self._next_retry = max(
                self._next_retry,
                time.monotonic() + self._rng.uniform(lo, window),
            )

    def _ensure_connected(self) -> None:
        with self._mtx:
            if self._closed:
                raise ConnectionError("remote verifier closed")
            if self._sock is not None and self._session_ready:
                return
        # one thread runs the handshake; the rest block here and re-check
        # (the holder either finished — ready — or tore the socket down)
        with self._conn_lock:
            self._connect_locked()

    def _connect_locked(self) -> None:
        with self._mtx:
            if self._closed:
                raise ConnectionError("remote verifier closed")
            if self._sock is not None and self._session_ready:
                return
            if time.monotonic() < self._next_retry:
                # attribution survives the backoff window: a client the
                # server REFUSED stays "unauthorized" (CPU rung, never
                # failover) until its next real attempt says otherwise
                if self._auth_fails > 0:
                    raise AuthError(
                        "verify service refused authentication (backoff)"
                    )
                raise ConnectionError("verify service unreachable (backoff)")
        self._count("connect_attempts")
        if self._family == "unix":
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        else:
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.settimeout(self._connect_timeout_s)
        try:
            sock.connect(self._target)
        except OSError:
            self._note_retry()
            try:
                sock.close()
            except OSError:
                pass
            raise
        sock.settimeout(0.2)
        hello_evt = threading.Event()
        with self._mtx:
            self._sock = sock
            self._session_ready = False
            self._server_draining = False
            self._server_flags = 0
            self._challenge = None
            self._hello_evt = hello_evt
            self._auth_waiter = None
            self._recv_thread = threading.Thread(
                target=self._recv_loop, args=(sock,), daemon=True,
                name="verify-remote",
            )
            self._recv_thread.start()
        self._send(encode_frame(
            FT_CLIENT_HELLO, payload=self._tenant.encode("utf-8"),
        ))
        self._count("connects")
        with self._mtx:
            self._connect_fails = 0
        if self._auth_key is None:
            with self._mtx:
                self._session_ready = True
            return
        # authenticated session: the HELLO carries the challenge; answer
        # it and hold this submit until the server acknowledges. Against
        # a no-auth server the flag is simply absent (v1 interop).
        if not hello_evt.wait(self._connect_timeout_s):
            self._on_disconnect()
            raise ConnectionError("no HELLO from verify service")
        with self._mtx:
            challenge = self._challenge
            required = bool(self._server_flags & HELLO_FLAG_AUTH)
            if not required:
                self._session_ready = True
                return
            waiter = [threading.Event(), False]
            self._auth_waiter = waiter
        mac = auth_mac(self._auth_key, challenge or b"", self._node_id)
        try:
            self._send(encode_frame(
                FT_AUTH,
                payload=mac + self._node_id.encode("utf-8"),
            ))
        except OSError as exc:
            self._on_disconnect()
            raise ConnectionError(str(exc)) from exc
        answered = waiter[0].wait(self._timeout_s)
        if answered and not waiter[1]:
            # a typed verdict: the server LOOKED at our credentials and
            # refused them — not failover-eligible (shared fleet key)
            self._count("unauthorized")
            self._note_retry(auth=True)
            self._on_disconnect()
            raise AuthError("verify service refused authentication")
        if not answered:
            # no verdict at all: the server died or stalled
            # mid-handshake (rolling restart, blackhole). That is a
            # transport failure — a secondary may well accept the same
            # key, so it must stay failover-eligible
            self._on_disconnect()
            raise ConnectionError("no auth verdict from verify service")
        with self._mtx:
            self._auth_fails = 0
            self._session_ready = True

    def _send(self, data: bytes) -> None:
        with self._mtx:
            sock = self._sock
        if sock is None:
            raise ConnectionError("verify service not connected")
        sock.sendall(data)

    def _recv_loop(self, sock: socket.socket) -> None:
        def tick() -> bool:
            self._expire_pending()
            with self._mtx:
                return self._sock is sock and not self._closed
        while True:
            head = _recv_exact(sock, _LEN.size, tick=tick)
            if head is None:
                break
            (length,) = _LEN.unpack(head)
            if length < HEADER_BYTES or length > max_frame_bytes(
                self._max_lanes
            ):
                break
            buf = _recv_exact(sock, length, tick=tick)
            if buf is None:
                break
            try:
                frame = decode_frame(buf)
                self._on_frame(frame)
            except FrameError:
                break
        with self._mtx:
            stale = self._sock is not sock
        if not stale:
            self._on_disconnect()

    # -- response demux ----------------------------------------------------

    def _on_frame(self, frame: Frame) -> None:
        if frame.ftype == FT_HELLO:
            payload = frame.payload
            with self._mtx:
                self._server_gen = frame.generation
                if frame.n_lanes:
                    self._max_lanes = frame.n_lanes
                # capability bytes: [version, flags, challenge?]
                # (absent/empty payload = a v1 server)
                self._server_proto = payload[0] if payload else 1
                self._server_flags = (
                    payload[1] if len(payload) >= 2 else 0
                )
                self._server_draining = bool(
                    self._server_flags & HELLO_FLAG_DRAINING
                )
                if (self._server_flags & HELLO_FLAG_AUTH) and \
                        len(payload) >= 2 + AUTH_CHALLENGE_BYTES:
                    self._challenge = bytes(
                        payload[2:2 + AUTH_CHALLENGE_BYTES]
                    )
                evt = self._hello_evt
            if evt is not None:
                evt.set()
            return
        if frame.ftype == FT_AUTH_OK:
            with self._mtx:
                waiter = self._auth_waiter
            if waiter is not None:
                waiter[1] = True
                waiter[0].set()
            self._count("auth_ok")
            return
        if frame.ftype == FT_DRAINING:
            # the server entered graceful drain: stop sending NEW work
            # there (the HA layer skips draining endpoints); in-flight
            # requests are still answered, so pendings stay put
            with self._mtx:
                already = self._server_draining
                self._server_draining = True
            if not already:
                self._count("server_draining")
                if self._telemetry is not None:
                    self._telemetry.note_event("server_draining", {
                        "tenant": self._tenant,
                        "address": self._address,
                    }, source="client")
            return
        if frame.ftype == FT_REGISTERED:
            with self._mtx:
                self._server_gen = frame.generation
                waiter = self._reg_waiters.get(frame.req_id)
            if waiter is not None:
                waiter[1] = frame.generation
                waiter[0].set()
            return
        if frame.ftype == FT_RESP:
            with self._mtx:
                self._server_gen = frame.generation
                pend = self._pending.pop(frame.req_id, None)
                if pend is not None:
                    self._rtt_s += time.monotonic() - pend.t_sent
                    self._stats["rtts"] = self._stats.get("rtts", 0) + 1
            if pend is None:
                return
            status = frame.payload[0] if frame.payload else ST_REJECTED
            if status == ST_DRAINING:
                # typed drain refusal: transport-shaped, so the HA rung
                # fails this over to a secondary immediately instead of
                # eating a timeout; solo clients take the CPU rung with
                # the reason kept distinct from a crash
                with self._mtx:
                    self._server_draining = True
                self._fail_agg(pend.agg, "draining")
                return
            if status != ST_OK:
                # a server-side ADMISSION verdict (QoS shed/drop/quota),
                # not a transport failure: propagate the rejection like
                # the local scheduler would. CPU-fallback-verifying here
                # would defeat the shed — the overloaded server's load
                # would bounce to every client's CPU instead
                self._reject_agg(pend.agg)
                return
            bits = np.unpackbits(
                np.frombuffer(frame.payload[1:], np.uint8),
                bitorder="little",
            )[: frame.n_lanes].astype(bool)
            self._complete_part(pend, bits)
            return
        if frame.ftype == FT_ERR:
            code, msg = decode_error(frame.payload)
            with self._mtx:
                pend = self._pending.pop(frame.req_id, None)
                if code == ERR_STALE_GENERATION:
                    self._server_gen = frame.generation
            if code == ERR_STALE_GENERATION:
                # every cached valset registered under an older
                # generation is now suspect; the next submit
                # re-registers (resync) before going indexed again, and
                # the refused lanes go again now, as compact rows
                self._count("stale")
                if pend is not None and not self._resend_compact(pend):
                    self._fail_agg(pend.agg, "stale")
                return
            if code == ERR_UNAUTHORIZED:
                # typed auth refusal: wake the handshake waiter (wrong
                # key) and resolve any refused request on the CPU rung
                # under its own reason — never the failover rung
                with self._mtx:
                    waiter = self._auth_waiter
                if waiter is not None and not waiter[0].is_set():
                    waiter[1] = False
                    waiter[0].set()
                self._count("err_unauthorized")
                if pend is not None:
                    self._fail_agg(pend.agg, "unauthorized")
                return
            if code == ERR_UNKNOWN_VALSET and pend is not None:
                with self._mtx:
                    for vid in list(self._valsets):
                        self._valsets.pop(vid, None)
            self._count(f"err_{ERR_NAMES.get(code, code)}")
            if pend is not None:
                self._fail_agg(pend.agg, "error")

    def _resend_compact(self, pend: _PendingPart) -> bool:
        """The lanes of an indexed frame the server refused as stale,
        sent again as 128 B compact rows under a new req_id and the same
        deadline: the server then needs no registration of ours. False
        where a lane does not pack (the caller's local rung decides
        then); True once the frame is sent or the connection's failure
        path owns the request."""
        agg = pend.agg
        items = [agg.items[pend.base + int(i)] for i in pend.sent_idx]
        wire, valid = pack_items_compact(items)
        if not valid.all():
            return False
        with agg.mtx:
            if agg.failed or agg.future.done():
                return True
            with self._mtx:
                self._req_id += 1
                rid = self._req_id
                self._pending[rid] = pend
                for key in ("stale_resends", "req_frames"):
                    self._stats[key] = self._stats.get(key, 0) + 1
            agg.req_ids.append(rid)
        pend.t_sent = time.monotonic()
        try:
            self._send(encode_frame(
                FT_REQ, qclass=pend.qcode, kind=KIND_COMPACT, req_id=rid,
                n_lanes=len(items), payload=wire.tobytes(),
            ))
        except OSError:
            self._on_disconnect()
        return True

    def _complete_part(self, pend: _PendingPart, bits: np.ndarray) -> None:
        agg = pend.agg
        with agg.mtx:
            if agg.failed or agg.future.done():
                return
            if bits.size >= pend.sent_idx.size:
                agg.mask[pend.base + pend.sent_idx] = (
                    bits[: pend.sent_idx.size]
                )
            agg.remaining -= 1
            done = agg.remaining == 0
        if done:
            mask = [bool(b) for b in agg.mask]
            agg.future._set((all(mask), mask))
            self._count("remote_ok")
            self._finish_spans(agg, "ok")

    def _finish_spans(self, agg: _Agg, outcome: str) -> None:
        """End the submit root (and its wire_wait child) exactly once;
        Span.end is idempotent so racing completion paths are safe."""
        if agg.wire_span is not None:
            agg.wire_span.end(outcome=outcome)
        if agg.span is not None:
            agg.span.end(outcome=outcome)

    def _reject_agg(self, agg: _Agg) -> None:
        """Mirror the local scheduler's shed/drop verdict: rejected=True,
        not-ok, all-False — callers already handle rejected futures
        (retry later / treat as unverified), and the admission layer's
        load-shedding decision survives the network boundary."""
        with agg.mtx:
            if agg.failed:
                return
            agg.failed = True
        with self._mtx:
            for rid in agg.req_ids:
                self._pending.pop(rid, None)
        self._count("rejected")
        if self._telemetry is not None:
            self._telemetry.note_event(
                "client_rejected", {"tenant": self._tenant},
                source="client",
            )
        agg.future.rejected = True
        agg.future.reason = "rejected"
        agg.future._set((False, [False] * len(agg.mask)))
        self._finish_spans(agg, "rejected")

    def _fail_agg(self, agg: _Agg, reason: str) -> None:
        """Fallback ladder for the WHOLE request, exactly once. With an
        HA hook installed, transport-shaped failures (disconnect /
        timeout / draining) first offer the items to a healthy secondary
        — verify is idempotent and req_ids are per-connection, so the
        resubmit is safe; only when the hook declines (all endpoints
        down) does the local CPU rung run. The reason stays distinct on
        the future (``disconnected`` for a dead daemon is the contract
        the node's health checks key on)."""
        with agg.mtx:
            if agg.failed:
                return
            agg.failed = True
        with self._mtx:
            for rid in agg.req_ids:
                self._pending.pop(rid, None)
        self._count(reason)
        if self._failover is not None and reason in FAILOVER_REASONS:
            try:
                took = self._failover(
                    agg.items, reason, agg.future, agg.ctx
                )
            except Exception:  # noqa: BLE001 - broken HA layer: CPU rung
                took = False
            if took:
                # the HA layer owns completion now; this agg's future is
                # never set here, and `failover` is metered distinctly
                # from the transport reason that triggered it
                self._count("failed_over")
                if self._telemetry is not None:
                    self._telemetry.note_fallback(
                        self._tenant, "failover",
                        kind="client_failover", detail={"via": reason},
                    )
                self._finish_spans(agg, "failover")
                return
        if self._telemetry is not None:
            self._telemetry.note_fallback(self._tenant, reason)
        bv = CPUBatchVerifier()
        for pk, m, s in agg.items:
            bv.add(pk, m, s)
        _, mask = bv.verify()
        agg.future.reason = reason
        agg.future._set((all(mask), mask))
        self._finish_spans(agg, reason)

    def _expire_pending(self) -> None:
        now = time.monotonic()
        with self._mtx:
            expired = [
                p for p in self._pending.values() if now > p.deadline
            ]
        for pend in expired:
            self._fail_agg(pend.agg, "timeout")

    def _on_disconnect(self) -> None:
        with self._mtx:
            sock = self._sock
            self._sock = None
            self._session_ready = False
        self._note_retry()
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
        self._drop_pending("disconnected")

    def _drop_pending(self, reason: str) -> None:
        with self._mtx:
            pending = list(self._pending.values())
            self._pending.clear()
        seen = set()
        for pend in pending:
            if id(pend.agg) in seen:
                continue
            seen.add(id(pend.agg))
            self._fail_agg(pend.agg, reason)

    def _count(self, key: str) -> None:
        with self._mtx:
            self._stats[key] = self._stats.get(key, 0) + 1

    # -- observability -----------------------------------------------------

    @property
    def server_draining(self) -> bool:
        """True once the current endpoint signalled graceful drain (the
        FT_DRAINING broadcast, a draining HELLO flag, or an ST_DRAINING
        refusal) — the HA layer skips such endpoints for new work."""
        with self._mtx:
            return self._server_draining

    def clear_draining(self) -> None:
        """HA probe hook: the endpoint restarted and its HELLO no longer
        carries the draining flag, so new work may route here again."""
        with self._mtx:
            self._server_draining = False

    @property
    def connected(self) -> bool:
        with self._mtx:
            return self._sock is not None

    @property
    def address(self) -> str:
        return self._address

    def stats(self) -> Dict[str, int]:
        with self._mtx:
            return dict(self._stats)

    def snapshot(self) -> dict:
        """The client-side "service" TelemetryHub source a node
        registers when it points its backends at a shared daemon."""
        with self._mtx:
            return {
                "address": self._address,
                "tenant": self._tenant,
                "connected": self._sock is not None,
                "server_generation": self._server_gen,
                "server_proto": self._server_proto,
                "server_draining": self._server_draining,
                "auth": self._auth_key is not None,
                "max_lanes": self._max_lanes,
                "valsets": len(self._valsets),
                "pending": len(self._pending),
                "reconnect": {
                    "connect_fails": self._connect_fails,
                    "auth_fails": self._auth_fails,
                    "last_backoff_s": round(self._last_backoff_s, 4),
                    "next_retry_in_s": round(
                        max(0.0, self._next_retry - time.monotonic()), 4
                    ),
                    "retry_base_s": self._retry_s,
                    "retry_cap_s": self._retry_cap_s,
                },
                "stats": dict(self._stats),
                "rtt_s": self._rtt_s,
            }
