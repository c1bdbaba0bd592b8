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

u1 = e·s⁻¹, u2 = r·s⁻¹ mod n are host-side CPython big-int (~3 µs/sig,
like the ed25519 host-hash mode); the ~4600 field muls of the scalar
multiplication are the device's work.
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


def _verify_core(wire: jnp.ndarray, flags: jnp.ndarray) -> jnp.ndarray:
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


verify_kernel = jax.jit(_verify_core)


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
_MAX_CHUNK = 4096




def _le_words(arr_u8: np.ndarray) -> np.ndarray:
    """u8[B,32] → u32[8,B] little-endian words."""
    return np.ascontiguousarray(np.ascontiguousarray(arr_u8).view("<u4").T)


def prepare_batch(
    pub_keys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
):
    """Host packing + the structural checks the CPU verifier applies
    before any curve math (lengths, prefix, x < p, r/s ranges, low-S).
    → (wire u32[32,B], flags int32[B], valid): raw little-endian words
    of qx, r, u1, u2 — the limb/digit splits run on device
    (unpack_fe_limbs / unpack_digits), so the link carries 132 bytes/sig
    instead of ~1,257."""
    n = len(pub_keys)
    valid = np.ones(n, bool)
    qx_b = np.zeros((n, 32), np.uint8)
    r_b = np.zeros((n, 32), np.uint8)
    u1_b = np.zeros((n, 32), np.uint8)
    u2_b = np.zeros((n, 32), np.uint8)
    flags = np.zeros(n, np.int32)
    for i in range(n):
        pk, sig = pub_keys[i], sigs[i]
        if len(pk) != 33 or pk[0] not in (2, 3) or len(sig) != 64:
            valid[i] = False
            continue
        x = int.from_bytes(pk[1:], "big")
        r = int.from_bytes(sig[:32], "big")
        s = int.from_bytes(sig[32:], "big")
        if x >= P or not (1 <= r < N) or not (1 <= s < N) or s > N // 2:
            valid[i] = False
            continue
        e = int.from_bytes(hashlib.sha256(bytes(msgs[i])).digest(), "big") % N
        w = pow(s, -1, N)
        u1_b[i] = np.frombuffer((e * w % N).to_bytes(32, "little"), np.uint8)
        u2_b[i] = np.frombuffer((r * w % N).to_bytes(32, "little"), np.uint8)
        qx_b[i] = np.frombuffer(x.to_bytes(32, "little"), np.uint8)
        r_b[i] = np.frombuffer(r.to_bytes(32, "little"), np.uint8)
        flags[i] = (pk[0] & 1) | (2 if r + N < P else 0)

    wire = np.concatenate(
        [
            _le_words(qx_b),
            _le_words(r_b),
            _le_words(u1_b),
            _le_words(u2_b),
        ],
        axis=0,
    )
    return wire, flags, valid


def verify_batch(
    pub_keys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
) -> List[bool]:
    """Public entry used by crypto.batch.TPUBatchVerifier for secp keys."""
    from cometbft_tpu.crypto.tpu import mesh as mesh_mod

    n = len(pub_keys)
    if n == 0:
        return []
    valid_full = np.ones(n, bool)

    def chunk_pack(start: int, end: int):
        # per-chunk packing: the host's scalar inversions for chunk i+1
        # overlap the device's work on chunk i (dispatch is async)
        (*packed, valid) = prepare_batch(
            pub_keys[start:end], msgs[start:end], sigs[start:end]
        )
        valid_full[start:end] = valid
        return packed

    out = mesh_mod.dispatch_batch(
        verify_kernel, chunk_pack, n, _MAX_CHUNK, _MIN_PAD
    )
    return (out & valid_full).tolist()
