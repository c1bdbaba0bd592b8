"""Batched secp256k1 ECDSA verification as one XLA tensor program.

SURVEY.md §2.1 names the secp256k1 batch kernel as the stretch companion
to the ed25519 north star; §7 stage 10 calls for mixed-key batches
partitioned by curve. Same architecture as ed25519_batch: every
signature is a lane, limb-major [19, B] field elements (secp_field), a
joint radix-4 Straus double-scalar multiplication u1·G + u2·Q over 128
2-bit digit rows, one-hot table selection, no data-dependent control
flow. The wire format is compact (one u32[32,B] buffer of raw LE words
plus an int32[B] flag vector — 132 bytes/sig); limb splitting and digit
extraction run on device, mirroring ed25519_batch.unpack_wire.

Point arithmetic uses the Renes–Costello–Batina COMPLETE addition
formulas for a = 0 curves (Algorithm 7; b3 = 3·7 = 21) in homogeneous
projective coordinates — one branch-free formula covers add, double,
inverses, and the identity (0:1:0), exactly what SIMD lanes need. Cost
12M + 2 small muls per add; doubling reuses the same formula.

Semantics contract — bit-identical accept/reject with the CPU verifier
(crypto/secp256k1.py PubKeySecp256k1.verify_signature):
  * sig is r ‖ s (32+32 big-endian); r, s ∈ [1, n) required;
  * HIGH-S REJECTED (s > n/2 — the btcec/low-S malleability rule);
  * pubkey is 33-byte compressed; prefix ∈ {2,3} and x < p required
    (host-checked), y recovered on device (decompress failure rejects);
  * e = SHA-256(msg) mod n (host, hashlib);
  * accept iff R' = u1·G + u2·Q is not infinity and R'.x ≡ r (mod n),
    i.e. affine x == r or x == r + n (when r + n < p).

u1 = e·s⁻¹, u2 = r·s⁻¹ mod n are the host's (prepare_batch: one native
call a launch, one inversion for all its lanes); the ~6,550 field
multiplications of decompression, the table, the ladder and the final
inversion are the device's work (benchmark/secp_opcount.py).
"""

from __future__ import annotations

import hashlib
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from cometbft_tpu.crypto.tpu import secp_field as fe
from cometbft_tpu.crypto.tpu.secp_field import N, P

NUM_DIGITS = 128  # 256 bits, 2-bit windows
_GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
_GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8

Point = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]  # homogeneous (X:Y:Z)

_B3_FE = fe.const_fe(fe.B3)
_ONE = fe.const_fe(1)
_ZERO = fe.const_fe(0)
_SEVEN = fe.const_fe(7)
_ID_POINT: Point = (_ZERO, _ONE, _ZERO)  # the point at infinity


def point_add(p: Point, q: Point) -> Point:
    """RCB 2015 Algorithm 7 (a = 0): complete — valid for every input
    pair including doubling, inverses, and infinity."""
    x1, y1, z1 = p
    x2, y2, z2 = q
    t0 = fe.mul(x1, x2)
    t1 = fe.mul(y1, y2)
    t2 = fe.mul(z1, z2)
    t3 = fe.mul(fe.add(x1, y1), fe.add(x2, y2))
    t3 = fe.sub(t3, fe.add(t0, t1))
    t4 = fe.mul(fe.add(y1, z1), fe.add(y2, z2))
    t4 = fe.sub(t4, fe.add(t1, t2))
    x3 = fe.mul(fe.add(x1, z1), fe.add(x2, z2))
    y3 = fe.sub(x3, fe.add(t0, t2))
    x3 = fe.add(fe.add(t0, t0), t0)  # 3·X1X2
    t2 = fe.mul(t2, _B3_FE)
    z3 = fe.add(t1, t2)
    t1 = fe.sub(t1, t2)
    y3 = fe.mul(y3, _B3_FE)
    x3_out = fe.sub(fe.mul(t3, t1), fe.mul(t4, y3))
    y3_out = fe.add(fe.mul(y3, x3), fe.mul(t1, z3))
    z3_out = fe.add(fe.mul(z3, t4), fe.mul(x3, t3))
    return (x3_out, y3_out, z3_out)


def point_dbl(p: Point) -> Point:
    return point_add(p, p)


def _const_point(x: int, y: int) -> Point:
    return (fe.const_fe(x), fe.const_fe(y), fe.const_fe(1))


def _addp(a, b):
    """Host-side affine add for building the G multiples."""
    if a is None:
        return b
    (x1, y1), (x2, y2) = a, b
    if x1 == x2 and (y1 + y2) % P == 0:
        return None
    if a == b:
        lam = (3 * x1 * x1) * pow(2 * y1, P - 2, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, P - 2, P) % P
    x3 = (lam * lam - x1 - x2) % P
    y3 = (lam * (x1 - x3) - y1) % P
    return (x3, y3)


_G1 = (_GX, _GY)
_G2 = _addp(_G1, _G1)
_G3 = _addp(_G2, _G1)
_G_POINTS = [
    _ID_POINT,
    _const_point(*_G1),
    _const_point(*_G2),
    _const_point(*_G3),
]


def decompress(
    qx: jnp.ndarray, parity: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x limbs [19,B] (< p, host-checked), parity int32[B] (prefix & 1)
    → (y, on_curve). y = sqrt(x³+7) with the parity of the prefix."""
    rhs = fe.add(fe.mul(fe.sq(qx), qx), _SEVEN)
    y = fe.sqrt_candidate(rhs)
    ok = fe.eq(fe.sq(y), rhs)
    yc = fe.to_canonical(y)
    flip = (yc[0] & 1) != parity
    y = fe.select(flip, fe.neg(y), y)
    return y, ok


def _select_point(entries: List[Point], idx: jnp.ndarray) -> Point:
    """One-hot select over the 16-entry Straus table (branch-free, no
    gathers — the TPU-friendly form proven out in ed25519_batch)."""
    oh = idx[None, :] == jnp.arange(len(entries), dtype=jnp.int32)[:, None]
    out = []
    for k in range(3):
        acc = None
        for e_i, entry in enumerate(entries):
            term = jnp.where(oh[e_i][None, :], entry[k], 0)
            acc = term if acc is None else acc + term
        out.append(acc)
    return tuple(out)


def unpack_fe_limbs(words: jnp.ndarray) -> jnp.ndarray:
    """u32[8,B] little-endian words → int32[19,B] radix-14 limbs of the
    full 256-bit value (limb 18 holds bits 252..255). Device-side
    equivalent of fe.bytes_be_to_limbs_np so the wire ships 32 raw bytes
    per field element instead of 76 bytes of pre-split limbs (same
    link-bandwidth rationale as ed25519_batch.unpack_fe_limbs)."""
    limbs = []
    for i in range(fe.NUM_LIMBS):
        bit = fe.RADIX * i
        j, k = bit // 32, bit % 32
        w = words[j] >> k
        if k > 32 - fe.RADIX and j + 1 < 8:  # limb spans into next word
            w = w | (words[j + 1] << (32 - k))
        limbs.append((w & jnp.uint32(0x3FFF)).astype(jnp.int32))
    return jnp.stack(limbs, axis=0)


def unpack_digits(words: jnp.ndarray) -> jnp.ndarray:
    """u32[8,B] little-endian scalar words → int32[128,B] 2-bit digits,
    MSB first (a digit at an even bit offset never crosses a word)."""
    digs = []
    for d in range(NUM_DIGITS):
        bit = 2 * (NUM_DIGITS - 1 - d)
        j, k = bit // 32, bit % 32
        digs.append(((words[j] >> k) & jnp.uint32(3)).astype(jnp.int32))
    return jnp.stack(digs, axis=0)


_N_FE = fe.const_fe(N)


def _secp256k1_verify_core(
    wire: jnp.ndarray, flags: jnp.ndarray
) -> jnp.ndarray:
    """bool[B] from the compact wire — u32[32,B] (rows 0:8 qx, 8:16 r,
    16:24 u1, 24:32 u2, all LE words) + int32[B] flags (bit 0 = pubkey
    prefix parity, bit 1 = r + n < p). 132 bytes/sig on the link instead
    of the ~1,257 bytes/sig the pre-split limb+digit arrays cost; limb
    split, digit extraction, and the r + n second x-candidate all happen
    on device."""
    qx = unpack_fe_limbs(wire[0:8])
    r_fe = unpack_fe_limbs(wire[8:16])
    rn_fe = fe.add(r_fe, jnp.asarray(_N_FE))
    u1_digits = unpack_digits(wire[16:24])
    u2_digits = unpack_digits(wire[24:32])
    q_parity = (flags & 1).astype(jnp.int32)
    rn_ok = (flags & 2) != 0
    return _verify_math(
        qx, q_parity, r_fe, rn_fe, rn_ok, u1_digits, u2_digits
    )


# the jitted program is named for its curve (the trace's module line reads
# jit__secp256k1_verify_core), so a device trace tells its seconds from
# the ed25519 programs' (benchmark/layers/secp256k1_kernel_us_per_lane.py)
verify_kernel = jax.jit(_secp256k1_verify_core)


def _verify_math(
    qx: jnp.ndarray,  # int32[19,B]  pubkey x limbs
    q_parity: jnp.ndarray,  # int32[B]  compressed-prefix parity
    r_fe: jnp.ndarray,  # int32[19,B]  r as a field element
    rn_fe: jnp.ndarray,  # int32[19,B]  r + n (second x-candidate)
    rn_ok: jnp.ndarray,  # bool[B]  r + n < p (second candidate valid)
    u1_digits: jnp.ndarray,  # int32[128,B]  u1 2-bit digits, MSB first
    u2_digits: jnp.ndarray,  # int32[128,B]  u2 2-bit digits, MSB first
) -> jnp.ndarray:
    """bool[B]: R' = u1·G + u2·Q exists, is finite, and R'.x ≡ r mod n."""
    qy, on_curve = decompress(qx, q_parity)
    q: Point = (qx, qy, jnp.broadcast_to(_ONE, qx.shape))

    q2 = point_dbl(q)
    q3 = point_add(q2, q)
    q_pts = [None, q, q2, q3]
    entries: List[Point] = []
    for dh in range(4):
        for ds in range(4):
            if dh == 0:
                pt = _G_POINTS[ds]
            elif ds == 0:
                pt = q_pts[dh]
            else:
                pt = point_add(_G_POINTS[ds], q_pts[dh])
            entries.append(pt)

    batch = qx.shape[1:]
    ident: Point = tuple(
        jnp.broadcast_to(c, (fe.NUM_LIMBS,) + batch) for c in _ID_POINT
    )

    def body(i, acc: Point) -> Point:
        acc = point_dbl(point_dbl(acc))
        idx = u1_digits[i] + 4 * u2_digits[i]
        return point_add(acc, _select_point(entries, idx))

    rx, ry, rz = lax.fori_loop(0, NUM_DIGITS, body, ident)

    finite = ~fe.is_zero(rz)
    x_aff = fe.mul(rx, fe.invert(rz))
    match = fe.eq(x_aff, r_fe) | (rn_ok & fe.eq(x_aff, rn_fe))
    return on_curve & finite & match


# --- host glue -------------------------------------------------------------

_MIN_PAD = 64
# The CEILING of a launch's lanes in total (mesh.chunk_cap may lower it).
_MAX_CHUNK = 4096
# The SIZE of one launch in lanes a chip (register_kernel's ``launch``):
# a batch of more lanes is a stream of such launches, the short one
# first, padded to at least half a launch (mesh.shard_chunks), so the
# executables a batch of any size needs are @1024 and @2048 a chip. The
# ed25519 entries' size (ed25519_batch._LAUNCH_LANES), and the one this
# kernel was measured at on the v5e in the mixed commit (PERF.md, PR 38).
_LAUNCH_LANES = 2048

KEY_TYPE = "secp256k1"


def _be(v: int) -> np.ndarray:
    return np.frombuffer(v.to_bytes(32, "big"), np.uint8)


_P_BE = _be(P)
_N_BE = _be(N)
_N_HALF_PLUS1_BE = _be(N // 2 + 1)  # s <= n/2: s < n/2 + 1
_P_MINUS_N_BE = _be(P - N)  # r + n < p: r < p - n


def _le_words(arr_u8: np.ndarray) -> np.ndarray:
    """u8[B,32] → u32[8,B] little-endian words."""
    return np.ascontiguousarray(np.ascontiguousarray(arr_u8).view("<u4").T)


def _below(rows: np.ndarray, bound: np.ndarray) -> np.ndarray:
    """bool[B]: each big-endian u8[B,32] row < ``bound`` (u8[32])."""
    diff = rows.astype(np.int16) - bound.astype(np.int16)
    nz = diff != 0
    first = nz.argmax(axis=1)
    return nz.any(axis=1) & (diff[np.arange(rows.shape[0]), first] < 0)


def _parse_inputs(pub_keys, sigs):
    """→ (pk_arr u8[B,33], sig_arr u8[B,64], valid) with every check the
    CPU verifier makes before any curve math, over the launch at once:
    lengths (a None signature, an absent lane, is malformed), prefix 2
    or 3, x < p, r and s in [1, n), s ≤ n/2 (high-S refused). Malformed
    lanes hold zeros."""
    n = len(pub_keys)
    valid = np.ones(n, bool)
    try:
        whole = (set(map(len, pub_keys)) <= {33}
                 and set(map(len, sigs)) <= {64})
    except TypeError:  # an absent lane's None signature
        whole = False
    if whole:
        pk_parts, sig_parts = pub_keys, sigs
    else:
        pk_parts, sig_parts = [], []
        for i in range(n):
            pk, sig = pub_keys[i], sigs[i]
            if sig is None or len(pk) != 33 or len(sig) != 64:
                valid[i] = False
                pk_parts.append(b"\x02" + bytes(32))
                sig_parts.append(bytes(64))
            else:
                pk_parts.append(pk)
                sig_parts.append(sig)
    pk_arr = np.frombuffer(b"".join(pk_parts), np.uint8).reshape(n, 33)
    sig_arr = np.frombuffer(b"".join(sig_parts), np.uint8).reshape(n, 64)
    r_be, s_be = sig_arr[:, :32], sig_arr[:, 32:]
    valid &= (pk_arr[:, 0] == 2) | (pk_arr[:, 0] == 3)
    valid &= _below(pk_arr[:, 1:], _P_BE)
    valid &= r_be.any(axis=1) & _below(r_be, _N_BE)
    valid &= s_be.any(axis=1) & _below(s_be, _N_HALF_PLUS1_BE)
    return pk_arr, sig_arr, valid


# Lanes from which a pack's scalars are one native call
# (native.secp256k1_scalars) rather than the hashlib + big-int loop below:
# ed25519_batch._NATIVE_CHALLENGE_MIN's gate, for the same per-call cost.
_NATIVE_SCALARS_MIN = 16


def _scalars(sig_arr: np.ndarray, msgs, valid: np.ndarray):
    """u1 = e·w, u2 = r·w (w = s⁻¹, e = SHA-256(msg) mod n) of each valid
    lane → (u1, u2) as u8[B,32] little-endian; zeros elsewhere. The
    launch's inversions are one (Montgomery's trick: one inversion and
    three products a lane): one native C call from _NATIVE_SCALARS_MIN
    lanes up (native/ed25519_batch.c cbft_secp256k1_scalars), else, or
    where the native library is missing or stale, _scalars_py."""
    from cometbft_tpu import native

    if len(msgs) >= _NATIVE_SCALARS_MIN:
        out = native.secp256k1_scalars(sig_arr, msgs, valid)
        if out is not None:
            return out
    return _scalars_py(sig_arr.tobytes(), msgs, valid)


def _scalars_py(sig_buf: bytes, msgs, valid: np.ndarray):
    """_scalars with hashlib and CPython's big ints: the small launches'
    path and the parity oracle."""
    n = len(msgs)
    lanes = np.flatnonzero(valid).tolist()
    sha, from_bytes, mv = hashlib.sha256, int.from_bytes, memoryview(sig_buf)
    r_of = [from_bytes(mv[64 * i:64 * i + 32], "big") for i in lanes]
    s_of = [from_bytes(mv[64 * i + 32:64 * i + 64], "big") for i in lanes]
    e_of = [from_bytes(sha(msgs[i]).digest(), "big") for i in lanes]
    prefix, acc = [], 1
    for s in s_of:
        prefix.append(acc)
        acc = acc * s % N
    inv = pow(acc, -1, N) if lanes else 1
    u1_of, u2_of = [0] * len(lanes), [0] * len(lanes)
    for k in range(len(lanes) - 1, -1, -1):
        w = inv * prefix[k] % N
        inv = inv * s_of[k] % N
        u1_of[k] = e_of[k] * w % N
        u2_of[k] = r_of[k] * w % N
    u1 = np.zeros((n, 32), np.uint8)
    u2 = np.zeros((n, 32), np.uint8)
    if lanes:
        u1[lanes] = np.frombuffer(
            b"".join(u.to_bytes(32, "little") for u in u1_of), np.uint8
        ).reshape(-1, 32)
        u2[lanes] = np.frombuffer(
            b"".join(u.to_bytes(32, "little") for u in u2_of), np.uint8
        ).reshape(-1, 32)
    return u1, u2


def prepare_batch(
    pub_keys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
):
    """Host packing of one launch + the structural checks the CPU
    verifier applies before any curve math (_parse_inputs). → (wire
    u32[32,B], flags int32[B], valid): raw little-endian words of qx, r,
    u1, u2 (the limb/digit splits run on device: unpack_fe_limbs /
    unpack_digits), 132 bytes/sig. Bulk: the checks, the byte order and
    the word layout are array operations, the scalars one native call
    (_scalars)."""
    pk_arr, sig_arr, valid = _parse_inputs(pub_keys, sigs)
    u1, u2 = _scalars(sig_arr, msgs, valid)
    r_be = sig_arr[:, :32]
    flags = (pk_arr[:, 0] & 1).astype(np.int32)
    flags |= np.where(_below(r_be, _P_MINUS_N_BE), 2, 0).astype(np.int32)
    flags[~valid] = 0
    keep = valid[:, None]
    wire = np.concatenate(
        [
            _le_words(np.where(keep, pk_arr[:, :0:-1], 0).astype(np.uint8)),
            _le_words(np.where(keep, r_be[:, ::-1], 0).astype(np.uint8)),
            _le_words(u1),
            _le_words(u2),
        ],
        axis=0,
    )
    return wire, flags, valid


def verify_batch(
    pub_keys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
) -> List[bool]:
    """Public entry used by crypto.batch.TPUBatchVerifier for secp keys:
    a stream of launches of _LAUNCH_LANES a chip (mesh.dispatch_batch),
    each packed while the ones before it run."""
    from cometbft_tpu.crypto.tpu import mesh as mesh_mod

    n = len(pub_keys)
    if n == 0:
        return []
    valid_full = np.ones(n, bool)

    def chunk_pack(start: int, end: int):
        (*packed, valid) = prepare_batch(
            pub_keys[start:end], msgs[start:end], sigs[start:end]
        )
        valid_full[start:end] = valid
        return packed

    out = mesh_mod.dispatch_batch(
        verify_kernel, chunk_pack, n, _MAX_CHUNK, _MIN_PAD,
        launch=_LAUNCH_LANES, curve=KEY_TYPE,
    )
    return (out & valid_full).tolist()


def stream_part(pub_keys: Sequence[bytes], msgs, sigs, n_shards: int):
    """The secp256k1 seats of a commit as one part of a mixed stream
    (mesh.launch_parts): launches of _LAUNCH_LANES a chip over
    ``n_shards`` chips, the short one first (mesh.shard_chunks). ``msgs``
    is ``(start, end) -> msgs[start:end]``, asked when the launch is next
    (the commit's sign-bytes, built behind the device's work); ``sigs``
    None marks an absent lane (False)."""
    from cometbft_tpu.crypto.tpu import mesh as mesh_mod
    from cometbft_tpu.libs import trace as tracelib

    n = len(pub_keys)
    cap = min(mesh_mod.chunk_cap(_MAX_CHUNK, _MIN_PAD),
              _LAUNCH_LANES * n_shards)
    launches = mesh_mod.shard_chunks(n, n_shards, cap, _MIN_PAD,
                                     short_floor=cap // 2)
    valid = np.ones(n, bool)
    fetched = []

    def fetch(chunk, start, end, inflight):
        built = tracelib.stage("commit.msgs_chunk", chunk=chunk,
                               lanes=end - start, inflight=inflight)
        with built:
            fetched.append(msgs(start, end))
        return built.seconds

    def build(start, end):
        wire, flags, valid[start:end] = prepare_batch(
            pub_keys[start:end], fetched.pop(), sigs[start:end]
        )
        return [wire, flags]

    return mesh_mod.StreamPart(
        curve=KEY_TYPE, kernel=verify_kernel, donate_from=0,
        launches=launches, build=build, fetch=fetch, n=n, valid=valid,
    )


def _register_aot_kernels():
    from cometbft_tpu.crypto.tpu import aot

    # curve "secp256k1" (the name's prefix): a warm boot builds it only
    # for a node whose consensus params admit secp256k1 validator keys
    # (aot.warmup_plan's key_types)
    aot.register_kernel(
        "secp256k1.verify",
        verify_kernel,
        bucket_shapes=lambda b: [((32, b), np.uint32), ((b,), np.int32)],
        launch=_LAUNCH_LANES,
    )


_register_aot_kernels()
