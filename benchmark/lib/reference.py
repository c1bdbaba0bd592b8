"""The plain reference: ed25519 verification independent of the program.

``verify_many`` decides what every verdict of a run is compared with. It
shares no code with ``cometbft_tpu``: RFC 8032 section 5.1.7 in plain
Python integers (``verify_py``), and, where the ``cryptography`` wheel is
importable, OpenSSL's implementation of the same check for bulk speed
(``verify_openssl``). The test suite holds the two to each other on every
kind of spoiled lane the benchmark plants.

Semantics (the ones CometBFT v0.34's crypto/ed25519 has): a 32-byte key,
a 64-byte signature, S < L, A and R decode to curve points, and
[S]B == R + [k]A with k = SHA-512(R || A || M) mod L, checked without the
cofactor. The benchmark's traffic holds honest signatures and plain
forgeries only, on which every ed25519 verifier agrees.
"""

from __future__ import annotations

import hashlib
from typing import List, Optional, Sequence, Tuple

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
_SQRT_M1 = pow(2, (P - 1) // 4, P)


def _recover_x(y: int, sign: int) -> Optional[int]:
    if y >= P:
        return None
    x2 = (y * y - 1) * pow(D * y * y + 1, P - 2, P) % P
    if x2 == 0:
        return None if sign else 0
    x = pow(x2, (P + 3) // 8, P)
    if (x * x - x2) % P != 0:
        x = x * _SQRT_M1 % P
    if (x * x - x2) % P != 0:
        return None
    if (x & 1) != sign:
        x = P - x
    return x


def first_off_curve_y() -> int:
    """The smallest y >= 2 with no x on the curve."""
    return next(y for y in range(2, 64) if _recover_x(y, 0) is None)


def _decode_point(b: bytes):
    y = int.from_bytes(b, "little")
    sign = y >> 255
    y &= (1 << 255) - 1
    x = _recover_x(y, sign)
    if x is None:
        return None
    return (x, y, 1, x * y % P)


def _add(p, q):
    a = (p[1] - p[0]) * (q[1] - q[0]) % P
    b = (p[1] + p[0]) * (q[1] + q[0]) % P
    c = 2 * p[3] * q[3] * D % P
    d = 2 * p[2] * q[2] % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def _mul(s: int, p):
    q = (0, 1, 1, 0)
    while s > 0:
        if s & 1:
            q = _add(q, p)
        p = _add(p, p)
        s >>= 1
    return q


def _equal(p, q) -> bool:
    return ((p[0] * q[2] - q[0] * p[2]) % P == 0
            and (p[1] * q[2] - q[1] * p[2]) % P == 0)


_BY = 4 * pow(5, P - 2, P) % P
_B = (_recover_x(_BY, 0), _BY, 1, _recover_x(_BY, 0) * _BY % P)


def verify_py(pk: bytes, msg: bytes, sig: bytes) -> bool:
    """RFC 8032 5.1.7, cofactorless, in Python integers (~5 ms)."""
    if len(pk) != 32 or len(sig) != 64:
        return False
    a = _decode_point(pk)
    r = _decode_point(sig[:32])
    s = int.from_bytes(sig[32:], "little")
    if a is None or r is None or s >= L:
        return False
    k = int.from_bytes(
        hashlib.sha512(sig[:32] + pk + msg).digest(), "little"
    ) % L
    return _equal(_mul(s, _B), _add(r, _mul(k, a)))


def _openssl():
    try:
        from cryptography.exceptions import InvalidSignature
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PublicKey,
        )
    except ImportError:
        return None
    return Ed25519PublicKey, InvalidSignature


def verify_openssl(pk: bytes, msg: bytes, sig: bytes) -> Optional[bool]:
    """The same check by OpenSSL, or None where the wheel is missing."""
    lib = _openssl()
    if lib is None:
        return None
    key_cls, invalid = lib
    if len(pk) != 32 or len(sig) != 64:
        return False
    try:
        key_cls.from_public_bytes(pk).verify(sig, msg)
    except (invalid, ValueError):
        return False
    return True


def verify_many(items: Sequence[Tuple[bytes, bytes, bytes]]) -> List[bool]:
    """One verdict per (pub_key bytes, message, signature)."""
    lib = _openssl()
    if lib is None:
        return [verify_py(pk, m, s) for pk, m, s in items]
    key_cls, invalid = lib
    keys: dict = {}
    out = []
    for pk, msg, sig in items:
        if len(pk) != 32 or len(sig) != 64:
            out.append(False)
            continue
        key = keys.get(pk)
        if key is None:
            try:
                key = keys[pk] = key_cls.from_public_bytes(pk)
            except ValueError:
                out.append(False)
                continue
        try:
            key.verify(sig, msg)
            out.append(True)
        except invalid:
            out.append(False)
    return out
