"""Native (C) runtime pieces of the framework.

The compute plane is JAX/XLA/Pallas (cometbft_tpu.crypto.tpu); this
package holds the native CPU runtime the reference implements in Go +
assembly — today the batched ed25519 fallback verifier
(`ed25519_batch.c`), built on demand with the system toolchain and
loaded via ctypes (which releases the GIL around calls).

Everything here degrades gracefully: if the toolchain or libcrypto is
unavailable the loader returns None and callers use the pure-Python
path.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "ed25519_batch.c")
_SO = os.path.join(_HERE, "build", "libcbft_ed25519.so")

# size_t, as the C entry points read offsets and lengths
_SIZE_T = np.dtype(f"=u{ctypes.sizeof(ctypes.c_size_t)}")
_U8_ARG = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_SIZE_T_ARG = np.ctypeslib.ndpointer(_SIZE_T, flags="C_CONTIGUOUS")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _build() -> bool:
    try:
        return _build_inner()
    except OSError:
        # read-only package dir, missing source, fs races — all mean
        # "no native path"; the caller degrades to pure Python
        return False


def _build_inner() -> bool:
    os.makedirs(os.path.dirname(_SO), exist_ok=True)
    # rebuild only when the source is newer than the cached .so
    if os.path.exists(_SO) and os.path.getmtime(_SO) >= os.path.getmtime(_SRC):
        return True
    cc = os.environ.get("CC", "cc")
    # build images ship a runtime libcrypto (.so.3 or .so.1.1) without
    # dev symlink/headers: try the dev-style -lcrypto first, then link
    # the runtime .so by path (the EVP ABI used is stable since 1.1.1)
    candidates = [
        ["-lcrypto"],
        ["/usr/lib/x86_64-linux-gnu/libcrypto.so.3"],
        ["/lib/x86_64-linux-gnu/libcrypto.so.3"],
        ["/usr/lib/x86_64-linux-gnu/libcrypto.so.1.1"],
        ["/lib/x86_64-linux-gnu/libcrypto.so.1.1"],
    ]
    for libargs in candidates:
        cmd = [
            cc, "-O2", "-shared", "-fPIC", "-o", _SO + ".tmp", _SRC,
            "-pthread", *libargs,
        ]
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=120
            )
        except (OSError, subprocess.TimeoutExpired):
            return False
        if proc.returncode == 0:
            os.replace(_SO + ".tmp", _SO)
            return True
    return False


def load_ed25519() -> Optional[ctypes.CDLL]:
    """Build (if needed) and load the native verifier; None on failure."""
    global _lib, _load_failed
    if _lib is not None:
        return _lib
    if _load_failed:
        return None
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if os.environ.get("CBFT_NATIVE_ED25519", "1") == "0" or not _build():
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            _load_failed = True
            return None
        lib.cbft_ed25519_verify_batch.restype = ctypes.c_int
        lib.cbft_ed25519_verify_batch.argtypes = [
            ctypes.c_char_p,                  # pubs
            ctypes.c_char_p,                  # msgs
            _SIZE_T_ARG,                      # msg_off
            _SIZE_T_ARG,                      # msg_len
            ctypes.c_char_p,                  # sigs
            ctypes.POINTER(ctypes.c_ubyte),   # out
            ctypes.c_size_t,                  # n
            ctypes.c_int,                     # nthreads
        ]
        _lib = lib
        return _lib


def _pack_msgs(msgs: Sequence[bytes]):
    """Concatenate variable-length messages into one buffer with
    per-entry offset and length ``size_t`` arrays — the shared
    marshalling of both batch entry points: one join and one pass of
    ``len``, no per-lane store. A None message raises TypeError."""
    buf = b"".join(msgs)
    lens = np.fromiter(map(len, msgs), _SIZE_T, len(msgs))
    offs = np.zeros_like(lens)
    np.cumsum(lens[:-1], out=offs[1:])
    return buf, offs, lens


def ed25519_verify_batch(
    pubs: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    nthreads: Optional[int] = None,
) -> Optional[List[bool]]:
    """One native call for the whole batch; None if the lib is unavailable.

    Entries with malformed lengths are rejected (False) without being
    passed to OpenSSL, matching PubKeyEd25519.verify_signature.
    """
    lib = load_ed25519()
    if lib is None:
        return None
    n = len(pubs)
    if n == 0:
        return []
    ok_shape = [
        len(pubs[i]) == 32 and len(sigs[i]) == 64 for i in range(n)
    ]
    # malformed entries get zeroed slots so indices stay aligned
    pub_buf = b"".join(
        pubs[i] if ok_shape[i] else b"\x00" * 32 for i in range(n)
    )
    sig_buf = b"".join(
        sigs[i] if ok_shape[i] else b"\x00" * 64 for i in range(n)
    )
    msg_buf, offs, lens = _pack_msgs(msgs)
    out = (ctypes.c_ubyte * n)()
    if nthreads is None:
        nthreads = min(os.cpu_count() or 1, 16)
    rc = lib.cbft_ed25519_verify_batch(
        pub_buf, msg_buf, offs, lens, sig_buf, out, n, nthreads
    )
    if rc != 0:
        return None
    return [bool(out[i]) and ok_shape[i] for i in range(n)]


def _load_single():
    """ctypes bindings for the single-key sign/keygen entry points
    (same .so); None on any load failure."""
    lib = load_ed25519()
    if lib is None:
        return None
    sign = getattr(lib, "cbft_ed25519_sign", None)
    pub = getattr(lib, "cbft_ed25519_pub_from_seed", None)
    if sign is None or pub is None:
        return None  # stale cached .so predating these entry points
    if not getattr(sign, "_cbft_typed", False):
        sign.restype = ctypes.c_int
        sign.argtypes = [
            ctypes.c_char_p,  # seed (32)
            ctypes.c_char_p,  # msg
            ctypes.c_size_t,  # msglen
            ctypes.c_char_p,  # sig out (64)
        ]
        sign._cbft_typed = True
        pub.restype = ctypes.c_int
        pub.argtypes = [
            ctypes.c_char_p,  # seed (32)
            ctypes.c_char_p,  # pub out (32)
        ]
    return sign, pub


def ed25519_sign(seed: bytes, msg: bytes) -> Optional[bytes]:
    """OpenSSL ed25519 signature over msg; None if the lib is unavailable."""
    fns = _load_single()
    if fns is None or len(seed) != 32:
        return None
    out = ctypes.create_string_buffer(64)
    if fns[0](seed, msg, len(msg), out) != 0:
        return None
    return out.raw


def ed25519_pub_from_seed(seed: bytes) -> Optional[bytes]:
    """seed → 32-byte public key; None if the lib is unavailable."""
    fns = _load_single()
    if fns is None or len(seed) != 32:
        return None
    out = ctypes.create_string_buffer(32)
    if fns[1](seed, out) != 0:
        return None
    return out.raw


# Lanes a thread of the challenge call hashes: an n-lane call runs on
# n // _CHALLENGE_GRAIN threads, at most the cores this process may run
# on, and on the caller's thread below two grains. Not a knob. Fixed on
# the v5e's one-chip host (13 cores; PR 37's probe, PERF.md section 7;
# `python3 bench_micro.py challenges` re-reads the whole call by lanes
# and threads), where a lane costs 0.60 us on one thread and a thread
# started costs ~0.2 ms: the C loop took 1,024 lanes 0.62 ms on
# 1 thread / 0.55 on 3; 2,048 lanes 1.23 / 0.96 on 2 / 0.73 on 4; 8,192
# lanes 4.91 / 1.73 on 4 / 1.52 on 5 / 2.15 on 8 / 3.63 on 13. 1,536
# puts 8,192 lanes (a four-chip commit's first launch) on 5 threads and
# a one-chip launch of 2,048 on 1.
_CHALLENGE_GRAIN = 1536


def challenge_threads(n: int) -> int:
    """Threads an n-lane ``ed25519_challenges`` call runs on."""
    return max(1, min(n // _CHALLENGE_GRAIN, len(os.sched_getaffinity(0))))


def load_challenges():
    """ctypes binding for cbft_ed25519_challenges (same .so); None on
    any load failure."""
    lib = load_ed25519()
    if lib is None:
        return None
    fn = getattr(lib, "cbft_ed25519_challenges", None)
    if fn is None:
        return None
    if not getattr(fn, "_cbft_typed", False):
        fn.restype = ctypes.c_int
        fn.argtypes = [
            _U8_ARG,          # pubs (A), n*32
            _U8_ARG,          # rs (R), n*32
            ctypes.c_char_p,  # msgs
            _SIZE_T_ARG,      # msg_off
            _SIZE_T_ARG,      # msg_len
            _U8_ARG,          # valid, n
            _U8_ARG,          # out, n*32 LE
            ctypes.c_size_t,  # n
            ctypes.c_int,     # nthreads
        ]
        fn._cbft_typed = True
    return fn


def ed25519_challenges(
    pubs: np.ndarray,
    rs: np.ndarray,
    msgs: Sequence[Optional[bytes]],
    valid: np.ndarray,
    nthreads: Optional[int] = None,
) -> Optional[np.ndarray]:
    """h = SHA-512(R ‖ A ‖ M) mod L per valid lane, one native call.

    pubs / rs are the u8[n,32] rows of A and R; ``valid`` is a bool or
    uint8 array, passed as its buffer: lanes with valid[i] False are
    skipped (zeros in the output) and their message may be None. A
    valid lane with msgs[i] None is a caller bug and returns None (the
    Python oracle would raise — silent empty-message hashing would be a
    parity break). ``nthreads`` None: challenge_threads(n). Returns the
    u8[n,32] little-endian scalars, or None when the native path is
    unavailable (callers fall back to the Python loop)."""
    fn = load_challenges()
    if fn is None:
        return None
    n = len(msgs)
    pk = np.ascontiguousarray(pubs, np.uint8)
    r = np.ascontiguousarray(rs, np.uint8)
    ok = np.ascontiguousarray(valid)
    if ok.dtype != np.uint8:
        ok = ok.astype(bool, copy=False).view(np.uint8)
    if pk.shape != (n, 32) or r.shape != (n, 32) or ok.shape != (n,):
        return None  # shape mismatch must not reach the C reader
    out = np.zeros((n, 32), np.uint8)
    if n == 0:
        return out
    try:
        buf, offs, lens = _pack_msgs(msgs)
    except TypeError:  # a None message: fine only on a skipped lane
        absent = np.fromiter((m is None for m in msgs), bool, n)
        if (absent & (ok != 0)).any():
            return None
        buf, offs, lens = _pack_msgs(
            [b"" if m is None else m for m in msgs]
        )
    if nthreads is None:
        nthreads = challenge_threads(n)
    if fn(pk, r, buf, offs, lens, ok, out, n, nthreads) != 0:
        return None
    return out
