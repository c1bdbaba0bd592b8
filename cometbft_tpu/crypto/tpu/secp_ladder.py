"""The secp256k1 Straus ladder as one Pallas kernel.

secp256k1_batch._verify_math's 128 digit steps (two doublings, a one-hot
select over the 16-entry table, one addition; RCB's complete addition
for all three) run as ONE pallas_call on every platform but the CPU.
Each grid step owns a tile of lanes and runs all 128 steps with its
accumulator, table and digit rows in VMEM: the table and the digit rows
come in from HBM once, the accumulator's (X:Y:Z) goes out once; as XLA
fusions, each point operation's temporaries would round-trip HBM.

A field element in the kernel is a list of 19 arrays, one limb each, of
shape [S, 128]: at S = 8 sublanes a limb is one vreg and a tile 1,024
lanes. Every operation mirrors secp_field's step for step (the same 38
columns, ``_fold_v``, ``_normalize``, carry rounds, ``add``, ``sub``
with ``_FOUR_P_COLS``, the product by b3 as a product by constant limbs),
so the kernel's limbs EQUAL the XLA ladder's, not only mod p, and it
makes the same 3 x 14 field products a step. None stands for a limb
known to be zero. The arithmetic uses ``*``, ``&``, ``>>``, ``+`` and
``-`` alone: it runs unchanged on numpy arrays, the CPU tests' eager
oracle, and in the kernel on ``_Limb``s, whose operators bind lax's
primitives (jnp's operators dispatch a jitted wrapper each, and an
unrolled step is ~100k of them). Constants are Python ints: a kernel
body may not capture arrays.
"""

from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cometbft_tpu.crypto.tpu import secp_field as fe

NUM_DIGITS = 128  # 256 bits, 2-bit windows
TABLE_ENTRIES = 16  # u1 digit + 4 * u2 digit
_NL = fe.NUM_LIMBS
_R = fe.RADIX
_M = fe._MASK
_V_TERMS = [(j, v) for j, v in enumerate(fe._V_LIMBS) if v]
_FOUR_P = [int(c) for c in fe._FOUR_P_COLS[:, 0]]
_B3 = [int(c) for c in fe.const_fe(fe.B3)[:, 0]]
_SPILL_COLS = 5  # secp_field._fold_v's
_LANES = 128
_TILE_SUBLANES = 8  # a limb is one vreg: 1,024 lanes a tile

Limbs = List  # 19 arrays [S, 128] (None: a known zero), or Python ints
Point = Tuple[Limbs, Limbs, Limbs]


class _Limb:
    """One limb in the kernel: ``+ - * & >>`` bind lax's primitives
    directly, with a Python int as a scalar literal."""

    __slots__ = ("x",)

    def __init__(self, x):
        self.x = x

    def __add__(self, o):
        return _Limb(lax.add(self.x, _arg(o)))

    def __sub__(self, o):
        return _Limb(lax.sub(self.x, _arg(o)))

    def __mul__(self, o):
        return _Limb(lax.mul(self.x, _arg(o)))

    def __and__(self, o):
        return _Limb(lax.bitwise_and(self.x, _arg(o)))

    def __rshift__(self, o):
        return _Limb(lax.shift_right_arithmetic(self.x, _arg(o)))


def _arg(o):
    return o.x if isinstance(o, _Limb) else o


def _plus(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a + b


def _carry_round(x: Limbs) -> Limbs:
    """secp_field._carry_round: each limb keeps 14 bits, its carry goes
    one limb up, the top carry folds back through V's limbs."""
    c = [v >> _R for v in x]
    top = c[_NL - 1]
    inc = [None] + c[:_NL - 1]
    for j, v in _V_TERMS:
        inc[j] = _plus(inc[j], top if v == 1 else top * v)
    return [(v & _M) + i for v, i in zip(x, inc)]


def _normalize(x: Limbs) -> Limbs:
    """secp_field._normalize: one sequential carry, a signed top."""
    out, carry = [], None
    for v in x[:-1]:
        t = _plus(v, carry)
        if t is None:
            out.append(None)
            continue
        out.append(t & _M)
        carry = t >> _R
    out.append(_plus(x[-1], carry))
    return out


def _times_v(h: Limbs, width: int) -> Limbs:
    """secp_field._times_v: h·V as ``width`` columns, each product split
    into its 14-bit lo part and its signed hi part one column up."""
    acc = [None] * width
    for j, v in _V_TERMS:
        for r, limb in enumerate(h):
            if limb is None:
                continue
            p = limb if v == 1 else limb * v
            acc[j + r] = _plus(acc[j + r], p & _M)
            acc[j + r + 1] = _plus(acc[j + r + 1], p >> _R)
    return acc


def _fold_v(cols: Limbs) -> Limbs:
    """secp_field._fold_v: 38 columns -> 19, value mod p."""
    hi = _normalize(cols[_NL:])
    acc = [_plus(a, b) for a, b in zip(
        list(cols[:_NL]) + [None] * _SPILL_COLS,
        _times_v(hi, _NL + _SPILL_COLS))]
    spill = _normalize(acc[_NL:])
    return [_plus(a, b) for a, b in zip(acc[:_NL], _times_v(spill, _NL))]


def _columns(a: Limbs, b: Limbs) -> Limbs:
    """The 38 columns of a·b (secp_field._cols_stack's integers): lo_ij
    on column i + j, hi_ij on i + j + 1. A limb of ``b`` may be a Python
    int (a constant operand): its zero limbs add nothing and are
    skipped."""
    cols = [None] * (2 * _NL)
    for i, ai in enumerate(a):
        for j, bj in enumerate(b):
            if isinstance(bj, int) and bj == 0:
                continue
            p = ai * bj
            cols[i + j] = _plus(cols[i + j], p & _M)
            cols[i + j + 1] = _plus(cols[i + j + 1], p >> _R)
    return cols


def mul(a: Limbs, b: Limbs) -> Limbs:
    """secp_field.mul, limb for limb."""
    x = _fold_v(_columns(a, b))
    for _ in range(4):  # secp_field._reduce
        x = _carry_round(x)
    return x


def add(a: Limbs, b: Limbs) -> Limbs:
    return _carry_round([x + y for x, y in zip(a, b)])


def sub(a: Limbs, b: Limbs) -> Limbs:
    return _carry_round(_carry_round(
        [x - y + k for x, y, k in zip(a, b, _FOUR_P)]))


def point_add(p: Point, q: Point) -> Point:
    """secp256k1_batch.point_add (RCB 2015 Algorithm 7, a = 0), limb for
    limb: the same 14 products in the same order."""
    x1, y1, z1 = p
    x2, y2, z2 = q
    t0 = mul(x1, x2)
    t1 = mul(y1, y2)
    t2 = mul(z1, z2)
    t3 = mul(add(x1, y1), add(x2, y2))
    t3 = sub(t3, add(t0, t1))
    t4 = mul(add(y1, z1), add(y2, z2))
    t4 = sub(t4, add(t1, t2))
    x3 = mul(add(x1, z1), add(x2, z2))
    y3 = sub(x3, add(t0, t2))
    x3 = add(add(t0, t0), t0)
    t2 = mul(t2, _B3)
    z3 = add(t1, t2)
    t1 = sub(t1, t2)
    y3 = mul(y3, _B3)
    x3_out = sub(mul(t3, t1), mul(t4, y3))
    y3_out = add(mul(y3, x3), mul(t1, z3))
    z3_out = add(mul(z3, t4), mul(x3, t3))
    return (x3_out, y3_out, z3_out)


def select(load: Callable[[int, int, int], object], idx) -> Point:
    """Table entry ``idx`` of each lane (secp256k1_batch._select_point's
    one-hot sum: exactly one entry matches an idx in [0, 16)).
    ``load(e, k, l)`` is limb l of coordinate k of entry e, an array."""
    hits = [lax.eq(idx, e) for e in range(1, TABLE_ENTRIES)]
    out = []
    for k in range(3):
        coord = []
        for limb in range(_NL):
            v = load(0, k, limb)
            for e, hit in enumerate(hits, start=1):
                v = lax.select(hit, load(e, k, limb), v)
            coord.append(v)
        out.append(coord)
    return tuple(out)


def ladder_step(acc: Point, entry: Point, add=point_add) -> Point:
    """One of the ladder's 128 steps: acc <- 4·acc + entry, the entry
    select() picked. ``add`` is point_add, or in the kernel
    _traced_point_add."""
    acc = add(acc, acc)
    acc = add(acc, acc)
    return add(acc, entry)


@jax.jit
def _traced_point_add(p: Point, q: Point) -> Point:
    """point_add on the kernel's raw limbs, as _Limbs. Jitted so that its
    ~33k primitives are traced once a limb shape: the step's three
    additions and every launch of the same tile share one trace, which
    Mosaic inlines at each call as if it were unrolled there."""
    def limbs(pt):
        return tuple([_Limb(v) for v in c] for c in pt)

    return tuple([v.x for v in c] for c in point_add(limbs(p), limbs(q)))


def _ladder_kernel(idx_ref, table_ref, out_ref):
    """One tile: idx_ref [128, S, 128], table_ref [16, 3, 19, S, 128],
    out_ref [3, 19, S, 128] (the accumulator, from the identity)."""
    shape = out_ref.shape[2:]
    for k in range(3):
        for limb in range(_NL):
            one = k == 1 and limb == 0  # (0:1:0)
            out_ref[k, limb] = jnp.full(shape, int(one), jnp.int32)

    def body(i, carry):
        acc = tuple([out_ref[k, limb] for limb in range(_NL)]
                    for k in range(3))
        entry = select(lambda e, k, limb: table_ref[e, k, limb], idx_ref[i])
        acc = ladder_step(acc, entry, _traced_point_add)
        for k in range(3):
            for limb in range(_NL):
                out_ref[k, limb] = acc[k][limb]
        return carry

    lax.fori_loop(0, NUM_DIGITS, body, 0)


def tile_plan(batch: int) -> Tuple[int, int]:
    """(padded lanes, sublanes a tile) of a launch of ``batch`` lanes:
    tiles of 8 x 128 from 1,024 lanes up, else ONE tile of the batch
    rounded up to 128 lanes (a narrow launch is not padded to 1,024)."""
    lanes = -(-batch // _LANES) * _LANES
    tile = _TILE_SUBLANES * _LANES
    if lanes >= tile:
        return -(-lanes // tile) * tile, _TILE_SUBLANES
    return lanes, lanes // _LANES


def _vmem_limit(sub: int) -> int:
    """The tile's blocks, double-buffered by the pipeline, and as much
    again for a point addition's temporaries and spills."""
    vreg = sub * _LANES * 4
    blocks = (NUM_DIGITS + TABLE_ENTRIES * 3 * _NL + 3 * _NL) * vreg
    return 4 * blocks


def ladder(idx: jnp.ndarray, entries: Sequence[Sequence[jnp.ndarray]],
           interpret: bool = False):
    """(X, Y, Z) int32[19, B] = sum over the 128 digit rows, MSB first,
    of acc <- 4·acc + entries[idx[i]], from the identity: the Straus
    ladder of secp256k1_batch._verify_math. ``idx`` int32[128, B] is the
    combined digit row u1 + 4·u2; ``entries`` the 16 table points, each
    coordinate [19, B] or a constant [19, 1]."""
    batch = idx.shape[1]
    lanes, sub = tile_plan(batch)
    pad = [(0, 0), (0, lanes - batch)]
    table = jnp.stack([
        jnp.pad(jnp.broadcast_to(c, (_NL, batch)).astype(jnp.int32), pad)
        for pt in entries for c in pt
    ]).reshape(TABLE_ENTRIES, 3, _NL, lanes // _LANES, _LANES)
    rows = jnp.pad(idx, pad).reshape(NUM_DIGITS, lanes // _LANES, _LANES)
    out = pl.pallas_call(
        _ladder_kernel,
        grid=(lanes // (sub * _LANES),),
        in_specs=[
            pl.BlockSpec((NUM_DIGITS, sub, _LANES), lambda t: (0, t, 0)),
            pl.BlockSpec((TABLE_ENTRIES, 3, _NL, sub, _LANES),
                         lambda t: (0, 0, 0, t, 0)),
        ],
        out_specs=pl.BlockSpec((3, _NL, sub, _LANES),
                               lambda t: (0, 0, t, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (3, _NL, lanes // _LANES, _LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(sub)),
        interpret=interpret,
    )(rows, table)
    out = out.reshape(3, _NL, lanes)[:, :, :batch]
    return out[0], out[1], out[2]
