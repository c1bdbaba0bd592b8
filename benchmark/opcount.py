"""Operations and bytes of one ed25519 verification lane, from shapes.

What the algorithm needs, whatever a later PR makes of it, so that a
change to the kernel cannot change its own yardstick. The formulation
counted is the one the program runs today (crypto/tpu/ed25519_batch.py,
crypto/tpu/field.py): field elements of 17 limbs of 15 bits, schoolbook
multiplication (17 x 17 limb multiply-adds, squaring not special-cased),
a joint 2-bit Straus ladder of 127 steps over a 16-entry table of cached
points, decompression of A by the (p-5)/8 power, one inversion to encode.

A limb multiply-add is counted as what it costs on the only unit whose
integer peak is published (the int8 MXU, peaks.json): a 15 x 15-bit
product is 2 x 2 byte products, each one multiply and one add, so 8 int8
operations. Additions, carries, selects and the table's masked sums are
left out: the count is a floor of the work, and the share computed from
it is an upper bound of how close the kernel is to that unit.
"""

from __future__ import annotations

from typing import Dict

# the jitted programs this count is for, as the trace's "XLA Modules"
# line names them (jit__verify_core_compact, _resident, _indexed)
PROGRAMS = r"verify"

LIMBS = 17
LADDER_STEPS = 127
INT8_OPS_PER_LIMB_MAC = 8

# field multiplications (squarings included), by phase
POW_P58 = 251 + 11          # ref10 fe_pow22523: 251 squarings, 11 products
INVERT = 254 + 11           # ref10 fe_invert: 254 squarings, 11 products
POINT_DBL = 4 + 4           # dbl-2008-hwcd: 4 squarings, 4 products
ADD_CACHED = 4 + 4          # add against a cached point
CACHE_POINT = 1             # 2d * T
POINT_ADD = CACHE_POINT + ADD_CACHED


def field_muls() -> Dict[str, int]:
    """Field multiplications per lane, by phase of the kernel."""
    decompress = (
        1            # y^2
        + 1          # d * y^2
        + 2 + 2      # v^3, v^7
        + 1          # u * v^7
        + POW_P58
        + 2          # x = u * v^3 * t
        + 2          # v * x^2
        + 1          # x * sqrt(-1), computed for every lane, then selected
    )
    table = (
        1                       # T of -A
        + POINT_DBL             # 2(-A)
        + POINT_ADD             # 3(-A)
        + 9 * POINT_ADD         # ds*B + dh*(-A), ds, dh in 1..3
        + 12 * CACHE_POINT      # the 12 per-lane entries (4 are constants)
    )
    ladder = LADDER_STEPS * (2 * POINT_DBL + ADD_CACHED)
    encode = INVERT + 2
    return {
        "decompress": decompress,
        "table": table,
        "ladder": ladder,
        "encode": encode,
    }


def limb_macs_per_lane() -> int:
    return sum(field_muls().values()) * LIMBS * LIMBS


def int8_ops_per_lane() -> int:
    return limb_macs_per_lane() * INT8_OPS_PER_LIMB_MAC


def hbm_bytes_per_lane(wire_bytes: int = 128) -> int:
    """The lane's wire in (128 B keyed, 100 B indexed, 96 B resident)
    and its verdict byte out; everything between can stay on the chip."""
    return wire_bytes + 1


def least_seconds_per_lane(peaks: Dict[str, float],
                           wire_bytes: int = 128) -> Dict[str, object]:
    """The roofline: the larger of operations over the integer peak and
    bytes over the memory peak. → {"seconds", "bound"}"""
    compute = int8_ops_per_lane() / peaks["int8_ops_per_s"]
    memory = hbm_bytes_per_lane(wire_bytes) / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(compute, memory),
        "bound": "compute" if compute >= memory else "memory",
    }
