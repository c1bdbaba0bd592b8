"""The plain reference for secp256k1: ECDSA verification independent of
the program.

Beside ``reference.py`` (ed25519), the verdict a mixed commit's
secp256k1 lanes are compared with. It shares no code with
``cometbft_tpu``: SEC 1 section 4.1.4 in plain Python integers
(``verify_py``), and, where the ``cryptography`` wheel is importable,
OpenSSL's ECDSA(SHA-256) for bulk speed (``verify_openssl``), wrapped in
the same explicit checks, because OpenSSL alone accepts a high S. The
test suite holds the two to each other on every kind of lane the
benchmark plants.

Semantics (CometBFT v0.34's crypto/secp256k1): a 33-byte compressed key
with prefix 2 or 3 whose x decodes to a curve point; a 64-byte
signature r || s, big-endian; r and s in [1, n) and s <= n/2 (a high S
is refused: v0.34 signs low-S and its verifier refuses the other);
e = SHA-256(msg) mod n; accept iff R' = u1*G + u2*Q, u1 = e/s,
u2 = r/s mod n, is not the point at infinity and R'.x = r (mod n).
"""

from __future__ import annotations

import hashlib
from typing import List, Optional, Sequence, Tuple

P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
G = (
    0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
    0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8,
)


def _add(p, q):
    """Affine addition on y^2 = x^3 + 7; None is the point at infinity."""
    if p is None:
        return q
    if q is None:
        return p
    (x1, y1), (x2, y2) = p, q
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        lam = 3 * x1 * x1 * pow(2 * y1, -1, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    return (x3, (lam * (x1 - x3) - y1) % P)


def _mul(k: int, p):
    out = None
    while k:
        if k & 1:
            out = _add(out, p)
        p = _add(p, p)
        k >>= 1
    return out


def decode_key(pk: bytes):
    """The point of a compressed key, or None where it is none."""
    if len(pk) != 33 or pk[0] not in (2, 3):
        return None
    x = int.from_bytes(pk[1:], "big")
    if x >= P:
        return None
    rhs = (x * x * x + 7) % P
    y = pow(rhs, (P + 1) // 4, P)
    if y * y % P != rhs:
        return None
    if y & 1 != pk[0] & 1:
        y = P - y
    return (x, y)


def _scalars(sig: bytes) -> Optional[Tuple[int, int]]:
    """(r, s) of a well-formed low-S signature, else None."""
    if len(sig) != 64:
        return None
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:], "big")
    if not (1 <= r < N and 1 <= s < N) or s > N // 2:
        return None
    return r, s


def verify_py(pk: bytes, msg: bytes, sig: bytes) -> bool:
    """SEC 1 4.1.4 in Python integers (a few ms)."""
    q = decode_key(pk)
    rs = _scalars(sig)
    if q is None or rs is None:
        return False
    r, s = rs
    e = int.from_bytes(hashlib.sha256(msg).digest(), "big") % N
    w = pow(s, -1, N)
    pt = _add(_mul(e * w % N, G), _mul(r * w % N, q))
    return pt is not None and pt[0] % N == r


def _openssl():
    try:
        from cryptography.exceptions import InvalidSignature
        from cryptography.hazmat.primitives import hashes
        from cryptography.hazmat.primitives.asymmetric import ec
        from cryptography.hazmat.primitives.asymmetric.utils import (
            encode_dss_signature,
        )
    except ImportError:
        return None
    return ec, hashes, encode_dss_signature, InvalidSignature


def verify_openssl(pk: bytes, msg: bytes, sig: bytes) -> Optional[bool]:
    """The same check by OpenSSL, or None where the wheel is missing."""
    return verify_many([(pk, msg, sig)])[0] if _openssl() else None


def verify_many(items: Sequence[Tuple[bytes, bytes, bytes]]) -> List[bool]:
    """One verdict per (pub_key bytes, message, signature): OpenSSL where
    the wheel is importable (a key object made once per key), else
    verify_py."""
    lib = _openssl()
    if lib is None:
        return [verify_py(pk, m, s) for pk, m, s in items]
    ec, hashes, encode_dss, invalid = lib
    algo = ec.ECDSA(hashes.SHA256())
    keys: dict = {}
    out = []
    for pk, msg, sig in items:
        rs = _scalars(sig)
        if rs is None or len(pk) != 33 or pk[0] not in (2, 3):
            out.append(False)
            continue
        key = keys.get(pk)
        if key is None:
            try:
                key = keys[pk] = ec.EllipticCurvePublicKey.from_encoded_point(
                    ec.SECP256K1(), pk
                )
            except ValueError:
                out.append(False)
                continue
        try:
            key.verify(encode_dss(*rs), msg, algo)
            out.append(True)
        except invalid:
            out.append(False)
    return out
