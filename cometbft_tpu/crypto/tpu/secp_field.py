"""GF(p) arithmetic for secp256k1 on TPU limb vectors,
p = 2^256 - 2^32 - 977.

Same design language as field.py (the ed25519 field): little-endian
radix-2^14 limbs in int32 lanes, limb axis 0, batch on the trailing
(lane) axis, no data-dependent control flow. Differences forced by the
prime: 19 limbs × 14 bits (266 ≥ 256), and the top-carry fold constant is
V = 2^266 mod p = 2^42 + 977·2^10 whose radix-2^14 limbs are
[1024, 61, 0, 1] — all tiny, which is what keeps fold-back carries from
inflating limbs past the int32 product bound (a radix-15 layout was
tried first: its fold limb 16384 is HALF the radix, and identity-heavy
op chains overflowed).

A multiply is three stages. (1) The 38 columns of a·b: every one of the
361 limb products split into a 14-bit lo and a signed hi part, lo_ij on
column i + j, hi_ij on i + j + 1. Their form follows the platform, as
field.default_mul_impl() decides ed25519's: on the CPU platform two
{0,1}-matrix products over the flattened outer product (a small graph,
which is what XLA:CPU's compile time follows); on every other platform
the outer product's rows padded into place, stacked and summed: slices
and adds. The same integers either way (exact int32 sums,
|col| < 2^21). (2) Two V-folds of columns 19..37 with lo/hi product
splits, on [19, B] / [5, B] slices. (3) Four vectorized carry rounds.

Verification-only: no constant-time requirements. Exactness is pinned
by randomized chained-composition parity tests against CPython big-int
(tests/test_tpu_secp.py) — every op keeps limbs inside the invariant
|limb| small enough that limb products stay in int32.
"""

from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np
from jax import lax

from cometbft_tpu.crypto.tpu.field import default_mul_impl

P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
B3 = 21  # 3·b for the complete-addition formulas (b = 7)

NUM_LIMBS = 19
RADIX = 14
_MASK = 0x3FFF

_V = (1 << (RADIX * NUM_LIMBS)) % P  # 2^266 mod p = 2^42 + 977·2^10
_V_LIMBS = [(_V >> (RADIX * i)) & _MASK for i in range(4)]
_V_VEC = np.array(_V_LIMBS, np.int32)


def int_to_limbs(n: int) -> List[int]:
    return [(n >> (RADIX * i)) & _MASK for i in range(NUM_LIMBS)]


def limbs_to_int(limbs) -> int:
    total = 0
    for i, limb in enumerate(limbs):
        total += int(limb) << (RADIX * i)
    return total


def const_fe(n: int) -> np.ndarray:
    # host array: importing this module must not init a jax backend
    # (see field.const_fe)
    return np.array(int_to_limbs(n % P), np.int32)[:, None]


_P_LIMBS = np.array(int_to_limbs(P), np.int32)[:, None]


def _cols_of(n: int) -> np.ndarray:
    cols = [(n >> (RADIX * i)) & _MASK for i in range(NUM_LIMBS - 1)]
    cols.append(n >> (RADIX * (NUM_LIMBS - 1)))  # top keeps the rest
    return np.array(cols, np.int32)[:, None]


_FOUR_P_COLS = _cols_of(4 * P)  # top column < 2^18


def _carry_round(x: jnp.ndarray) -> jnp.ndarray:
    """One vectorized carry round: each limb keeps its low 14 bits and
    passes the signed carry one limb up; the top carry (callers keep it
    < 2^14) folds back through V's limbs [1024, 61, 0, 1] as one [4, B]
    product (< 2^24) onto limbs 0..3."""
    c = x >> RADIX
    fold = c[NUM_LIMBS - 1 :] * _V_VEC.reshape((4,) + (1,) * (x.ndim - 1))
    return (x & _MASK) + jnp.concatenate(
        [fold[:1], c[:3] + fold[1:], c[3 : NUM_LIMBS - 1]], axis=0
    )


def _reduce(cols: jnp.ndarray) -> jnp.ndarray:
    """Signed columns |col| < 2^25 → invariant limbs, value mod p.

    Round 1: carries ≤ 2^11, V-fold adds < 2^21 to limbs 0..3.
    Round 2: carries ≤ 2^7, top carry ≤ 2 → fold < 2^12. Rounds 3-4
    converge: limbs end in [-4, 2^14 + small] — products of two
    invariant limbs stay far inside int32 (< 2^29)."""
    for _ in range(4):
        cols = _carry_round(cols)
    return cols


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _carry_round(a + b)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _carry_round(_carry_round(a - b + _FOUR_P_COLS))


def neg(a: jnp.ndarray) -> jnp.ndarray:
    return _carry_round(_carry_round(_FOUR_P_COLS - a))


def mul_small(a: jnp.ndarray, c: int) -> jnp.ndarray:
    return _reduce(a * c)


def _scatter_matrices():
    """{0,1} matrices [38, 361]: position of each outer-product part."""
    width = 2 * NUM_LIMBS
    m_lo = np.zeros((width, NUM_LIMBS * NUM_LIMBS), np.int32)
    m_hi = np.zeros((width, NUM_LIMBS * NUM_LIMBS), np.int32)
    for i in range(NUM_LIMBS):
        for j in range(NUM_LIMBS):
            idx = i * NUM_LIMBS + j
            m_lo[i + j, idx] = 1
            m_hi[i + j + 1, idx] = 1
    return m_lo, m_hi


_M_LO, _M_HI = _scatter_matrices()


def _cols_matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """The 38 columns of a·b by two [38, 361] @ [361, B] int32 matrix
    products over the flattened outer product's lo / hi parts: the
    small graph the CPU platform compiles quickly."""
    flat = NUM_LIMBS * NUM_LIMBS
    prod = a[:, None] * b[None, :]  # [19, 19, B]
    lo = (prod & _MASK).reshape((flat,) + prod.shape[2:])
    hi = (prod >> RADIX).reshape((flat,) + prod.shape[2:])
    return jnp.asarray(_M_LO) @ lo + jnp.asarray(_M_HI) @ hi


def _cols_stack(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """The same 38 columns in field._mul_stack's form: row i of the
    outer product, lo parts padded onto columns i..i+18 and hi parts
    onto i+1..i+19, the 38 rows stacked and summed. No matrix. Chosen
    on the v5e over field._mul_shift_add's form by the whole kernel in
    the mixed commit: 55.3 us a secp256k1 lane against 78.6, though one
    mul alone in a loop at [19, 2048] read 13.18 us against 11.76 (the
    matrix products 20.32; PERF.md, section 6)."""
    prod = a[:, None] * b[None, :]  # [19, 19, B]
    lo = prod & _MASK
    hi = prod >> RADIX
    width = 2 * NUM_LIMBS
    tail_pad = [(0, 0)] * (prod.ndim - 2)
    rows = []
    for i in range(NUM_LIMBS):
        rows.append(jnp.pad(lo[i], [(i, width - NUM_LIMBS - i)] + tail_pad))
        rows.append(
            jnp.pad(hi[i], [(i + 1, width - NUM_LIMBS - i - 1)] + tail_pad)
        )
    return jnp.sum(jnp.stack(rows, axis=0), axis=0)


def _normalize(x: jnp.ndarray) -> jnp.ndarray:
    """Signed columns [n, B] → the same value as n - 1 14-bit limbs and
    a signed top that keeps the rest: one sequential carry, the one
    exact form of that representation."""
    out = []
    carry = jnp.zeros_like(x[0])
    for i in range(x.shape[0] - 1):
        t = x[i] + carry
        out.append(t & _MASK)
        carry = t >> RADIX
    out.append(x[-1] + carry)
    return jnp.stack(out, axis=0)


def _times_v(h: jnp.ndarray, width: int) -> jnp.ndarray:
    """h·V as [width, B] columns for limbs h [n, B]: for each nonzero
    limb v_j of V one [n, B] product, its 14-bit lo parts padded onto
    columns j..j+n-1 and its signed hi parts onto j+1..j+n (|h| ≤
    2^15ish → |h·v_j| < 2^30)."""
    n = h.shape[0]
    tail_pad = [(0, 0)] * (h.ndim - 1)
    acc = None
    for j, v in enumerate(_V_LIMBS):
        if v:
            p = h * v
            term = jnp.pad(p & _MASK, [(j, width - n - j)] + tail_pad)
            term = term + jnp.pad(
                p >> RADIX, [(j + 1, width - n - j - 1)] + tail_pad
            )
            acc = term if acc is None else acc + term
    return acc


def _fold_v(cols38: jnp.ndarray) -> jnp.ndarray:
    """38 signed columns (|col| < 2^22) → 19 columns, value mod p.

    hi := columns 19..37 normalized to 14-bit limbs (+ signed top);
    acc := lo + hi·V with every product split into 14-bit lo / signed
    hi parts (products < 2^26, column sums < 2^24). The fold spills
    into columns 19..23 — one second, tiny fold of those 5 brings them
    home (spill ≤ 5 limbs → columns ≤ 4 + 3 + 1 < 19)."""
    spill_cols = 5
    width = NUM_LIMBS + spill_cols
    tail_pad = [(0, 0)] * (cols38.ndim - 1)
    hi = _normalize(cols38[NUM_LIMBS:])
    acc = jnp.pad(cols38[:NUM_LIMBS], [(0, spill_cols)] + tail_pad)
    acc = acc + _times_v(hi, width)
    spill = _normalize(acc[NUM_LIMBS:])
    return acc[:NUM_LIMBS] + _times_v(spill, NUM_LIMBS)


# The two forms of a product's columns; one reduction follows either.
_MUL_IMPLS = {
    "matmul": _cols_matmul,
    "stack": _cols_stack,
}


def _mul_form() -> str:
    """The platform's form, by field.default_mul_impl()'s platform test:
    the matrix products where ed25519 takes its matmul (the CPU
    platform), slices and adds on every other."""
    return "matmul" if default_mul_impl() == "matmul" else "stack"


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _reduce(_fold_v(_MUL_IMPLS[_mul_form()](a, b)))


def sq(a: jnp.ndarray) -> jnp.ndarray:
    return mul(a, a)


def _carry_seq(x: jnp.ndarray):
    out = []
    carry = jnp.zeros(x.shape[1:], jnp.int32)
    for i in range(NUM_LIMBS):
        t = x[i] + carry
        out.append(t & _MASK)
        carry = t >> RADIX
    return jnp.stack(out, axis=0), carry


def to_canonical(x: jnp.ndarray) -> jnp.ndarray:
    """Invariant fe → unique representative in [0, p).

    Unlike the ed25519 field (17 limbs = 255 bits ≈ log2 p), the 19-limb
    span holds values up to ~2^10·p, so canonicalization folds at bit
    256 (2^256 ≡ 2^32 + 977, i.e. hi·16 into limb 2 + hi·977 into limb
    0), twice, before the final conditional subtracts."""
    # resolve carries; the 2^270 overflow folds through V
    for _ in range(2):
        x, c = _carry_seq(x)
        for i, v in enumerate(_V_LIMBS):
            if v:
                x = x.at[i].add(c * jnp.int32(v))
    x, _ = _carry_seq(x)
    # fold bits ≥ 256: 256 = 18·14 + 4 → hi = limb18 >> 4 (< 2^10)
    for _ in range(2):
        hi = x[18] >> 4
        x = x.at[18].set(x[18] & 0xF)
        x = x.at[2].add(hi * 16)  # 2^32 = 2^(2·14+4)
        x = x.at[0].add(hi * 977)
        x, _ = _carry_seq(x)  # no 2^270 overflow: value < 2^257
    for _ in range(2):  # value < 2p after the folds
        diff, borrow = _borrow_sub(x, _P_LIMBS)
        x = jnp.where((borrow == 0)[None], diff, x)
    return x


def _borrow_sub(a: jnp.ndarray, b: jnp.ndarray):
    out = []
    borrow = jnp.zeros(a.shape[1:], jnp.int32)
    for i in range(NUM_LIMBS):
        t = a[i] - b[i] - borrow
        out.append(t & _MASK)
        borrow = (t >> RADIX) & 1
    return jnp.stack(out, axis=0), borrow


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(to_canonical(a) == to_canonical(b), axis=0)


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(to_canonical(a) == 0, axis=0)


def select(pred: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(pred[None], a, b)


def _pow_const(x: jnp.ndarray, e: int) -> jnp.ndarray:
    """Fixed-exponent pow: square-and-multiply over the constant bit
    string under a fori_loop (~2 muls/bit — only used outside the main
    Straus loop, for decompression and the final inversion)."""
    bits = jnp.array([int(b) for b in bin(e)[2:]], jnp.int32)
    one = const_fe(1)
    acc0 = jnp.broadcast_to(one, x.shape)

    def body(i, acc):
        acc = sq(acc)
        return jnp.where(bits[i] == 1, mul(acc, x), acc)

    return lax.fori_loop(0, bits.shape[0], body, acc0)


def invert(x: jnp.ndarray) -> jnp.ndarray:
    """x^(p-2); invert(0) = 0."""
    return _pow_const(x, P - 2)


def sqrt_candidate(x: jnp.ndarray) -> jnp.ndarray:
    """x^((p+1)/4) — a square root when x is a QR (p ≡ 3 mod 4);
    callers must verify candidate² == x."""
    return _pow_const(x, (P + 1) // 4)


def bytes_be_to_limbs_np(data):
    """numpy uint8[..., 32] BIG-endian field elements → int32[..., 19]
    limbs. Host-side; transpose to limb-major before the kernel."""
    import numpy as np

    b = np.asarray(data, dtype=np.uint8)[..., ::-1]  # → little-endian
    bits = np.unpackbits(b, axis=-1, bitorder="little")
    pad = NUM_LIMBS * RADIX - 256
    bits = np.concatenate(
        [bits, np.zeros(bits.shape[:-1] + (pad,), bits.dtype)], axis=-1
    )
    weights = (1 << np.arange(RADIX, dtype=np.int32)).astype(np.int32)
    shaped = bits.reshape(b.shape[:-1] + (NUM_LIMBS, RADIX)).astype(np.int32)
    return shaped @ weights
