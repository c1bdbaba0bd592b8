"""Operations and bytes of one secp256k1 verification lane, from shapes.

``opcount.py``'s counterpart for the secp256k1 kernel, counted the same
way so that a change to the kernel cannot change its own yardstick. The
formulation counted is the one the program runs today
(crypto/tpu/secp256k1_batch.py ``_verify_math``, crypto/tpu/secp_field.py):
field elements of 19 limbs of 14 bits, schoolbook multiplication (19 x 19
limb multiply-adds, a squaring is a full multiplication), Renes-Costello-
Batina complete addition (a = 0, Algorithm 7: 12 products and 2 by
b3 = 21, both a full multiplication by a constant field element) for
additions and doublings alike, a joint 2-bit Straus ladder of 128 steps
(two doublings and one addition a step) over a 16-entry table, and the
fixed-exponent powers of ``secp_field._pow_const``: one squaring and one
multiplication per bit of the exponent, the product computed on every
bit and selected (branch-free), for the square root of decompression
((p + 1) / 4, 254 bits) and the final inversion (p - 2, 256 bits).

A 14 x 14-bit product is 2 x 2 byte products, each one multiply and one
add: 8 int8 operations, over the int8 MXU peak of peaks.json (the only
integer peak published), as opcount.py counts ed25519. Additions,
carries, selects and the table's masked sums are left out: a floor of
the work.
"""

from __future__ import annotations

from typing import Dict

# the jitted program this count is for, as the trace's "XLA Modules"
# line names it (jit__secp256k1_verify_core)
PROGRAMS = r"secp256k1_verify"

P = 2**256 - 2**32 - 977
LIMBS = 19
LADDER_STEPS = 128
INT8_OPS_PER_LIMB_MAC = 8

POINT_ADD = 12 + 2  # RCB Algorithm 7, doubling included
# _pow_const: a squaring and a product a bit of the exponent
SQRT = 2 * ((P + 1) // 4).bit_length()
INVERT = 2 * (P - 2).bit_length()


def field_muls() -> Dict[str, int]:
    """Field multiplications per lane, by phase of the kernel."""
    decompress = (
        1            # qx^2
        + 1          # qx^3
        + SQRT       # y = (qx^3 + 7)^((p+1)/4)
        + 1          # y^2 == rhs
    )
    table = (
        2 * POINT_ADD        # 2Q = Q + Q, 3Q = 2Q + Q
        + 9 * POINT_ADD      # ds*G + dh*Q, ds, dh in 1..3
    )
    ladder = LADDER_STEPS * 3 * POINT_ADD
    final = INVERT + 1       # Z^-1, x = X * Z^-1
    return {
        "decompress": decompress,
        "table": table,
        "ladder": ladder,
        "final": final,
    }


def limb_macs_per_lane() -> int:
    return sum(field_muls().values()) * LIMBS * LIMBS


def int8_ops_per_lane() -> int:
    return limb_macs_per_lane() * INT8_OPS_PER_LIMB_MAC


def hbm_bytes_per_lane(wire_bytes: int = 132) -> int:
    """The lane's wire in (u32[32] words of qx, r, u1, u2 and an int32
    flag: 132 B) and its verdict byte out."""
    return wire_bytes + 1


def least_seconds_per_lane(peaks: Dict[str, float]) -> Dict[str, object]:
    """The roofline: the larger of operations over the integer peak and
    bytes over the memory peak. → {"seconds", "bound"}"""
    compute = int8_ops_per_lane() / peaks["int8_ops_per_s"]
    memory = hbm_bytes_per_lane() / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(compute, memory),
        "bound": "compute" if compute >= memory else "memory",
    }
