"""Batched sr25519 (schnorrkel) verification as one XLA tensor program.

The third curve kernel (SURVEY.md §2.1 stretch set). sr25519 rides the
SAME edwards25519 curve as ed25519, so the entire field and point
machinery (field.py limb-major arithmetic, the joint radix-4 Straus
loop, cached-point tables, one-hot selects) is reused from
ed25519_batch; what differs is the wrapping:

  * A and R arrive as ristretto255 encodings — decoded on device per
    RFC 9496 §4.3.1 (SQRT_RATIO_M1 built from the existing pow_p58);
  * the challenge k comes from a merlin transcript (host-side — the
    from-scratch merlin/STROBE the SecretConnection already uses);
  * the check is s·B == R + k·A, verified as
    P := s·B + k·(−A) ≟ R under RISTRETTO equality
    (X_P·y_R == Y_P·x_R  or  Y_P·y_R == X_P·x_R — RFC 9496 §4.5,
    a = −1 form, NO negation) — projective cross-multiplication, no
    inversion needed.

Semantics contract — bit-identical accept/reject with the CPU verifier
(crypto/sr25519.py PubKeySr25519.verify_signature): the schnorrkel
"new" format bit (sig[63] & 0x80) must be set, s < L after unmasking,
A/R encodings must be canonical, non-negative, and decodable.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from cometbft_tpu.crypto.tpu import ed25519_batch as eb
from cometbft_tpu.crypto.tpu import field as fe
from cometbft_tpu.crypto.tpu.field import L, P

_ONE = fe.const_fe(1)
_D_FE = fe.const_fe(fe.D)
_SQRT_M1_FE = fe.const_fe(fe.SQRT_M1)


def _is_neg(x: jnp.ndarray) -> jnp.ndarray:
    """Ristretto 'negative' = odd canonical representative."""
    return (fe.to_canonical(x)[0] & 1) == 1


def _sqrt_ratio_m1(
    u: jnp.ndarray, v: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RFC 9496 SQRT_RATIO_M1 → (was_square, nonneg root of u/v or
    i·u/v)."""
    v3 = fe.mul(fe.sq(v), v)
    v7 = fe.mul(fe.sq(v3), v)
    r = fe.mul(fe.mul(u, v3), fe.pow_p58(fe.mul(u, v7)))
    check = fe.mul(v, fe.sq(r))
    correct = fe.eq(check, u)
    flipped = fe.eq(check, fe.neg(u))
    flipped_i = fe.eq(check, fe.mul(fe.neg(u), _SQRT_M1_FE))
    r = fe.select(flipped | flipped_i, fe.mul(r, _SQRT_M1_FE), r)
    r = fe.select(_is_neg(r), fe.neg(r), r)
    return correct | flipped, r


def ristretto_decode(
    s: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """s: fe[17,B] (canonical, even — host-checked) → (x, y, ok) on the
    edwards curve (RFC 9496 §4.3.1)."""
    ss = fe.sq(s)
    u1 = fe.sub(_ONE, ss)
    u2 = fe.add(_ONE, ss)
    u2_sqr = fe.sq(u2)
    v = fe.sub(fe.neg(fe.mul(fe.mul(_D_FE, u1), u1)), u2_sqr)
    was_square, invsqrt = _sqrt_ratio_m1(
        jnp.broadcast_to(_ONE, s.shape), fe.mul(v, u2_sqr)
    )
    den_x = fe.mul(invsqrt, u2)
    den_y = fe.mul(fe.mul(invsqrt, den_x), v)
    x = fe.mul(fe.mul_small(s, 2), den_x)
    x = fe.select(_is_neg(x), fe.neg(x), x)
    y = fe.mul(u1, den_y)
    t = fe.mul(x, y)
    ok = was_square & ~_is_neg(t) & ~fe.is_zero(y)
    return x, y, ok


def _verify_core(wire: jnp.ndarray) -> jnp.ndarray:
    """bool[B] from the u32[32,B] wire (rows 0:8 A, 8:16 R, 16:24 S,
    24:32 merlin challenge k, LE words): s·B + k·(−A) ≟ R (ristretto
    equality), decodes valid. Raw encodings on the link + device unpack,
    same rationale as ed25519_batch.unpack_wire (ristretto encodings are
    canonical < p with bit 255 clear, so the low-255-bit limb unpack is
    lossless)."""
    a_s = eb.unpack_fe_limbs(wire[0:8])
    r_s = eb.unpack_fe_limbs(wire[8:16])
    s_digits = eb.unpack_digits(wire[16:24])
    k_digits = eb.unpack_digits(wire[24:32])
    ax, ay, ok_a = ristretto_decode(a_s)
    rx, ry, ok_r = ristretto_decode(r_s)

    nx = fe.neg(ax)
    neg_a = (nx, ay, jnp.broadcast_to(_ONE, ay.shape), fe.mul(nx, ay))

    # the ed25519 joint-Straus table over B and −A, reused verbatim
    a2 = eb.point_dbl(neg_a)
    a3 = eb.point_add(a2, neg_a)
    s_pts = [eb._ID_POINT, eb._B_POINT, eb._B2_POINT, eb._B3_POINT]
    h_pts = [None, neg_a, a2, a3]
    entries = []
    for dh in range(4):
        for ds in range(4):
            if dh == 0:
                pt = s_pts[ds]
            elif ds == 0:
                pt = h_pts[dh]
            else:
                pt = eb.point_add(s_pts[ds], h_pts[dh])
            entries.append(eb.cache_point(pt))

    batch = a_s.shape[1:]
    ident = tuple(
        jnp.broadcast_to(c, (fe.NUM_LIMBS,) + batch) for c in eb._ID_POINT
    )

    def body(i, acc):
        acc = eb.point_dbl(eb.point_dbl(acc))
        idx = s_digits[i] + 4 * k_digits[i]
        return eb.add_cached(acc, eb._select_cached(entries, idx))

    px, py, pz, _ = lax.fori_loop(0, eb.NUM_DIGITS, body, ident)

    # ristretto equality against affine R (RFC 9496 §4.5, a = −1):
    # X·y_R == Y·x_R  or  Y·y_R == X·x_R (cross-multiplied; Z_R = 1)
    eq1 = fe.eq(fe.mul(px, ry), fe.mul(py, rx))
    eq2 = fe.eq(fe.mul(py, ry), fe.mul(px, rx))
    return (eq1 | eq2) & ok_a & ok_r


verify_kernel = jax.jit(_verify_core)


# --- host glue -------------------------------------------------------------

_MIN_PAD = 64
_MAX_CHUNK = 8192

_P_INT = P


def prepare_batch(
    pub_keys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
):
    """Host packing: structural checks + the merlin transcript challenge
    per signature (the schnorrkel protocol binds pk and R into the
    transcript, so k must be computed host-side per sig)."""
    from cometbft_tpu.crypto.sr25519 import (
        _challenge_scalar,
        _signing_transcript,
    )

    n = len(pub_keys)
    valid = np.ones(n, bool)
    a_b = np.zeros((n, 32), np.uint8)
    r_b = np.zeros((n, 32), np.uint8)
    s_arr = np.zeros((n, 32), np.uint8)
    k_arr = np.zeros((n, 32), np.uint8)
    for i in range(n):
        pk, sig = pub_keys[i], sigs[i]
        if len(pk) != 32 or len(sig) != 64 or not sig[63] & 0x80:
            valid[i] = False
            continue
        s_bytes = bytearray(sig[32:])
        s_bytes[31] &= 0x7F
        s = int.from_bytes(bytes(s_bytes), "little")
        a_int = int.from_bytes(pk, "little")
        r_int = int.from_bytes(sig[:32], "little")
        # canonical + even ("non-negative") ristretto encodings
        if (
            s >= L
            or a_int >= _P_INT
            or r_int >= _P_INT
            or a_int & 1
            or r_int & 1
        ):
            valid[i] = False
            continue
        t = _signing_transcript(bytes(msgs[i]))
        t.append_message(b"proto-name", b"Schnorr-sig")
        t.append_message(b"sign:pk", bytes(pk))
        t.append_message(b"sign:R", bytes(sig[:32]))
        k = _challenge_scalar(t, b"sign:c")
        a_b[i] = np.frombuffer(bytes(pk), np.uint8)
        r_b[i] = np.frombuffer(bytes(sig[:32]), np.uint8)
        s_arr[i] = np.frombuffer(s.to_bytes(32, "little"), np.uint8)
        k_arr[i] = np.frombuffer(k.to_bytes(32, "little"), np.uint8)

    wire = np.concatenate(
        [
            eb._le_words(a_b),
            eb._le_words(r_b),
            eb._le_words(s_arr),
            eb._le_words(k_arr),
        ],
        axis=0,
    )
    return wire, valid


def verify_batch(
    pub_keys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
) -> List[bool]:
    """Public entry used by crypto.batch.TPUBatchVerifier for sr25519."""
    from cometbft_tpu.crypto.tpu import mesh as mesh_mod

    n = len(pub_keys)
    if n == 0:
        return []
    valid_full = np.ones(n, bool)

    def chunk_pack(start: int, end: int):
        # per-chunk packing: the merlin transcripts (the expensive host
        # step — pure-Python STROBE) for chunk i+1 overlap the device's
        # work on chunk i (dispatch is async)
        (*packed, valid) = prepare_batch(
            pub_keys[start:end], msgs[start:end], sigs[start:end]
        )
        valid_full[start:end] = valid
        return packed

    out = mesh_mod.dispatch_batch(
        verify_kernel, chunk_pack, n, _MAX_CHUNK, _MIN_PAD
    )
    return (out & valid_full).tolist()
