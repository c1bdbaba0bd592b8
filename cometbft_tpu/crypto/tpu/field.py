"""GF(2^255-19) arithmetic on TPU-friendly limb vectors.

Design notes (tpu-first, not a port — the reference delegates all field math
to assembly in golang.org/x/crypto; there is no Go source to mirror):

* A field element is ``int32[17, B]`` — seventeen little-endian
  radix-2^15 limbs in a *redundant signed* representation: limbs live in
  [-4, 2^15 + 127] rather than strictly [0, 2^15). The slack is what makes
  the representation SIMD-friendly: carries are resolved by 1-3
  *vectorized* rounds over the whole limb axis (`_carry_round`) instead of
  a sequential 17-step scan, so every op is a handful of wide [17, B]
  VPU instructions. Exact bounds are proven per-op below; limb products
  (2^15+127)^2 < 2^31 stay inside native int32 multiplies.
* A squaring is a squaring: `sq` takes a² from its 153 distinct limb
  products (17 diagonal, 136 off-diagonal taken once at weight 2)
  where `mul` takes 289 — 1,529 of a verification lane's 3,700 field
  multiplications. The same columns as `mul(a, a)`, integer for
  integer, so the same limbs; each multiplication form has its square
  (`_SQ_IMPLS` beside `_MUL_IMPLS`, one name selects both).
* **Limb-major layout**: the limb axis is axis 0 and the batch axis is
  the trailing (minor-most) axis. XLA's TPU layout maps the minor-most
  dimension onto the 128-wide vector lanes — with the batch there, every
  elementwise op runs at full lane occupancy. (The previous [B, 17]
  layout put the 17 limbs on the lanes: a ≤13% utilization ceiling on
  every instruction of the kernel.)
* 17 × 15 = 255 bits exactly, so the carry out of the top limb has weight
  2^255 ≡ 19 (mod p) — the cheapest possible fold.
* The batch axis is explicit (and trailing) so pjit/shard_map can shard
  it over an ICI mesh: the whole point is to verify thousands of
  signatures as one SPMD tensor program.
* Only `to_canonical` produces the unique representative mod p, and only
  where encoding/comparison semantics require it (matching the ref10
  fe_frombytes convention the CPU backend's OpenSSL inherits:
  non-canonical encodings are reduced mod p, not rejected —
  crypto/ed25519/ed25519.go:148 parity contract).
* No data-dependent control flow: selections are jnp.where, loops are
  lax.fori_loop with static trip counts — everything stays inside one XLA
  computation.
"""

from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np
from jax import lax

P = 2**255 - 19
# group order of the prime-order subgroup
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
D2 = (2 * D) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)

NUM_LIMBS = 17
RADIX = 15
_MASK = 0x7FFF

LIMB_AXIS = 0  # documented contract: fe = int32[NUM_LIMBS, *batch]


def int_to_limbs(n: int) -> List[int]:
    return [(n >> (RADIX * i)) & _MASK for i in range(NUM_LIMBS)]


def limbs_to_int(limbs) -> int:
    total = 0
    for i, limb in enumerate(limbs):
        total += int(limb) << (RADIX * i)
    return total


def const_fe(n: int) -> np.ndarray:
    """A field-element constant: int32[17, 1] — broadcasts against the
    trailing batch axis of any [17, B] element. Returned as a HOST
    (numpy) array: jax lifts it to a device constant at trace time, and
    building it must not initialize a backend — kernel modules are
    imported by TPUBatchVerifier.__init__ on the consensus thread, and
    an import must never be what takes the chip."""
    return np.array(int_to_limbs(n % P), np.int32)[:, None]


# 4p = 2^257 - 76 as signed radix-2^15 columns (2^257 = 2^17 · 2^(15·16)).
# Host arrays (see const_fe): module import must not init a jax backend.
_FOUR_P_COLS = np.zeros(NUM_LIMBS, np.int32)
_FOUR_P_COLS[0] = -76
_FOUR_P_COLS[16] = 0x20000
_FOUR_P_COLS = _FOUR_P_COLS[:, None]
_P_LIMBS = np.array(int_to_limbs(P), np.int32)[:, None]


def _carry_round(x: jnp.ndarray) -> jnp.ndarray:
    """One vectorized carry round: each limb keeps its low 15 bits and
    passes the (signed, arithmetic-shift) carry one limb up; the top carry
    wraps to limb 0 multiplied by 19 (2^255 ≡ 19). Value-preserving mod p.
    """
    c = x >> RADIX
    return (x & _MASK) + jnp.concatenate(
        [19 * c[NUM_LIMBS - 1 :], c[: NUM_LIMBS - 1]], axis=0
    )


def _reduce(cols: jnp.ndarray) -> jnp.ndarray:
    """Signed columns with |col| < 2^25 → invariant representation.

    Round 1: carries ≤ 2^10, limbs ≤ 2^15 + 2^10, limb0 ≤ 2^15 + 19·2^10
    (< 46340, safe: never multiplied before round 2 tightens it).
    Round 2: carries ≤ 1, limbs ≤ 2^15, limb0 ≤ 2^15 + 19 — inside the
    [-4, 2^15+127] invariant. Limbs ≥ -1 throughout (carries ≥ -1).
    """
    return _carry_round(_carry_round(cols))


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    # inputs ≤ 2^15+127 → sum ≤ 2^16+254, carries ≤ 2; one round suffices:
    # limbs ≤ 2^15-1+2, limb0 ≤ 2^15+37. Inputs ≥ -4 → limbs ≥ -1.
    return _carry_round(a + b)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    # a - b + 4p keeps the value non-negative for any invariant a, b.
    # Columns ∈ [-2^15-131, 2^17+2^15+131]: carries ∈ [-1, 5], so limbs
    # ≥ -1 and limb0 ≤ 2^15-1+19·5 = 2^15+94 — inside the invariant.
    return _carry_round(a - b + _FOUR_P_COLS)


def neg(a: jnp.ndarray) -> jnp.ndarray:
    return _carry_round(_FOUR_P_COLS - a)


def _mul_stack(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Outer-product form: materializes a [17, 17, B] (and a stacked
    [34, B]-column) intermediate per multiply — compact trace; at large
    batch each mul round-trips the outer product through HBM, making the
    point operations bandwidth-bound. Kept as a CBFT_TPU_MUL variant for
    on-chip A/B timing."""
    prod = a[:, None] * b[None, :]  # [17, 17, B]
    lo = prod & _MASK
    hi = prod >> RADIX
    width = 2 * NUM_LIMBS  # 34 columns: lo_i spans i..i+16, hi_i spans i+1..i+17
    tail_pad = [(0, 0)] * (a.ndim - 1)
    rows = []
    for i in range(NUM_LIMBS):
        rows.append(jnp.pad(lo[i], [(i, width - NUM_LIMBS - i)] + tail_pad))
        rows.append(
            jnp.pad(hi[i], [(i + 1, width - NUM_LIMBS - i - 1)] + tail_pad)
        )
    cols = jnp.sum(jnp.stack(rows, axis=0), axis=0)
    folded = cols[:NUM_LIMBS] + 19 * cols[NUM_LIMBS:]
    return _reduce(folded)


def _mul_shift_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Shift-accumulate form: 17 × (one [17, B] vector product padded
    into a [34, B] accumulator). Largest live tensor is the accumulator
    itself — the whole multiply stays fusable in registers/VMEM lanes, no
    big HBM intermediates."""
    width = 2 * NUM_LIMBS
    tail_pad = [(0, 0)] * (a.ndim - 1)
    acc = None
    for i in range(NUM_LIMBS):
        p = a[i : i + 1] * b  # [17, B]
        term = jnp.pad(p & _MASK, [(i, width - NUM_LIMBS - i)] + tail_pad)
        term = term + jnp.pad(
            p >> RADIX, [(i + 1, width - NUM_LIMBS - i - 1)] + tail_pad
        )
        acc = term if acc is None else acc + term
    folded = acc[:NUM_LIMBS] + 19 * acc[NUM_LIMBS:]
    return _reduce(folded)


def _fold_matrices(i_idx, j_idx, weight):
    """Constant [17, n] int32 matrices folding the lo and hi 15-bit
    parts of the n limb products a_{i_idx}·b_{j_idx} straight into the
    17 output columns: entry (k, idx) is the weight of product idx's
    part in column k — weight[idx] on its own column c, 19 times that on
    c-17 (2^255 ≡ 19). Precomposing the column-fold into the scatter
    matrix turns the whole schoolbook multiply into two matmuls. Host
    arrays, built once."""
    m_lo = np.zeros((NUM_LIMBS, len(i_idx)), np.int32)
    m_hi = np.zeros((NUM_LIMBS, len(i_idx)), np.int32)
    for idx, (i, j, w) in enumerate(zip(i_idx, j_idx, weight)):
        for m, c in ((m_lo, i + j), (m_hi, i + j + 1)):
            if c < NUM_LIMBS:
                m[c, idx] = w
            else:
                m[c - NUM_LIMBS, idx] = 19 * w
    return m_lo, m_hi


# the flattened outer product: product 17i+j is a_i·b_j, weight 1
_M_LO, _M_HI = _fold_matrices(
    *np.indices((NUM_LIMBS, NUM_LIMBS)).reshape(2, -1),
    np.ones(NUM_LIMBS * NUM_LIMBS, np.int32),
)


def _mul_matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Constant-matrix form: outer product → two [17, 289] × [289, B]
    int32 matmuls against precomposed fold matrices. ~8× fewer HLO ops
    than the unrolled forms — the XLA CPU backend compiles the full
    verify kernel super-linearly in graph size (measured 909 s with
    shift_add), so this is the compile-friendly variant; on TPU the int32
    dots bypass the MXU, so runtime there must be A/B-timed on chip
    (CBFT_TPU_MUL) against shift_add.

    Column bound: per output limb ≤ 17 unit + 17 ×19 contributions of
    |part| < 2^16 → < 2^25, inside the _reduce precondition and exact in
    int32 accumulation."""
    flat = NUM_LIMBS * NUM_LIMBS
    prod = a[:, None] * b[None, :]  # [17, 17, B]
    lo = (prod & _MASK).reshape((flat,) + prod.shape[2:])
    hi = (prod >> RADIX).reshape((flat,) + prod.shape[2:])
    folded = jnp.asarray(_M_LO) @ lo + jnp.asarray(_M_HI) @ hi
    return _reduce(folded)


def _f32_matrices():
    """Constant {0,1} f32 matrices [3·34, 1156]: row (q·34 + c) collects
    the half-limb products a_m1·b_m2 with limb-sum i1+i2 = c and
    sub-shift k1+k2 = q (halves: m = 2i+k, k=0 → low 7 bits, k=1 → the
    ≤8-bit top; weight 2^(15i + 7k))."""
    import numpy as np

    h = 2 * NUM_LIMBS
    m = np.zeros((3 * h, h * h), np.float32)
    for m1 in range(h):
        for m2 in range(h):
            i_sum = m1 // 2 + m2 // 2
            q = m1 % 2 + m2 % 2
            m[q * h + i_sum, m1 * h + m2] = 1.0
    return m


_F32_SCATTER = _f32_matrices()


def _mul_f32(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Exact-float form: each 15-bit limb splits into (≤8-bit hi, 7-bit
    lo) halves; the 34×34 half-limb products run in f32 and fold through
    one constant {0,1} matmul. Every product (≤ 2^16) and every matmul
    row sum (≤ 34·2^16 < 2^21.1) stays inside the 24-bit mantissa —
    bit-exact by construction, pinned by the same parity suite as the
    int32 forms.

    Why it exists: TPU VPUs issue f32 FMAs at full rate while int32
    multiplies decompose into multi-op sequences, and the f32 constant
    matmul can ride the MXU outright. Whether that beats shift_add is an
    on-chip CBFT_TPU_MUL A/B question, not a paper one."""
    h = 2 * NUM_LIMBS
    # interleaved halves [34, B]: row 2i = a_i & 0x7F, row 2i+1 = a_i >> 7
    # (arithmetic shift keeps the identity for the invariant's small
    # negative limbs; f32 exactness bounds are on magnitudes)
    ha = jnp.stack([a & 0x7F, a >> 7], axis=1).reshape((h,) + a.shape[1:])
    hb = jnp.stack([b & 0x7F, b >> 7], axis=1).reshape((h,) + b.shape[1:])
    prod = ha.astype(jnp.float32)[:, None] * hb.astype(jnp.float32)[None, :]
    prod = prod.reshape((h * h,) + prod.shape[2:])
    # precision=HIGHEST is load-bearing: the TPU MXU's default f32
    # matmul truncates inputs to bf16 (8-bit mantissa), which silently
    # breaks the ≤2^21 exactness bound — caught on chip by the r5 bench
    # ("benchmark batch must verify" under CBFT_TPU_MUL=f32). HIGHEST
    # selects the multi-pass f32 algorithm, restoring the full 24-bit
    # mantissa the proof needs.
    grouped = jnp.matmul(
        jnp.asarray(_F32_SCATTER),
        prod,
        precision=lax.Precision.HIGHEST,
    )  # [3·34, B], exact
    gi = grouped.astype(jnp.int32)
    c0, c1, c2 = gi[:h], gi[h : 2 * h], gi[2 * h :]
    # recombine the three sub-shift groups into radix-2^15 columns:
    # col[i] += c0[i] + (c1[i] low 8)·2^7 + (c2[i] bit 0)·2^14,
    # col[i+1] += c1[i] >> 8 + c2[i] >> 1 — every piece < 2^21
    cols = (
        c0
        + ((c1 & 0xFF) << 7)
        + ((c2 & 1) << 14)
    )
    spill = (c1 >> 8) + (c2 >> 1)
    cols = cols.at[1:].add(spill[:-1])
    top_spill = spill[h - 1]  # weight 2^(15·34) ≡ 19·19
    folded = cols[:NUM_LIMBS] + 19 * cols[NUM_LIMBS:]
    folded = folded.at[0].add(361 * top_spill)
    return _reduce(folded)


# Limb products ≤ (2^15+127)^2 < 2^31 are exact in int32. Each product
# splits into a 15-bit low part and a signed high part before column
# accumulation, keeping columns ≤ 34·(2^15+2^8) < 2^21; the fold of
# columns 17..33 (weight 2^255 ≡ 19) brings them to < 2^25 — the
# _reduce precondition. All implementations share this bound analysis
# (the f32 form documents its own).
#
# A square takes each off-diagonal product a_i·a_j (i < j) ONCE and
# weights it 2. The weight goes on the PARTS, never on an operand or on
# the product: 2·a_i·a_j reaches 2·(2^15+127)^2 > 2^31 (already
# 32,768 × 65,536 = 2^31), so a pre-doubled limb or a product doubled
# before the split wraps. Split first (p & 0x7FFF, p >> 15), then double
# the off-diagonal parts' column sums: 2·lo(p) + 2·hi(p)·2^15 is what
# mul(a, a) adds for p = a_i·a_j and again for a_j·a_i, so every column
# is the exact integer mul(a, a) gives it and the bounds above hold
# unchanged (over all 2^17 vectors of the invariant's corners, limbs -4
# or 2^15+127: worst column 834,807 < 2^21, worst folded column
# 15,585,451 < 2^25).
_MUL_IMPLS = {
    "stack": _mul_stack,
    "shift_add": _mul_shift_add,
    "matmul": _mul_matmul,
    "f32": _mul_f32,
}


def default_mul_impl() -> str:
    """Platform-sensitive default: the matmul form on the CPU platform
    (fast XLA compile — that path exists for the tests), stack on TPU
    per the on-chip A/B (BENCH_onchip_probe.json tpu_variants: stack
    17,014 sigs/s vs shift_add 12,901 vs matmul 10,750 at batch 4096).
    A backend that cannot start raises here, at trace time."""
    import jax

    return "matmul" if jax.default_backend() == "cpu" else "stack"


def _impl(impls: dict):
    """The form CBFT_TPU_MUL (or the platform) names, of a product or of
    a square: one name selects both."""
    import os

    name = os.environ.get("CBFT_TPU_MUL") or default_mul_impl()
    impl = impls.get(name)
    if impl is None:
        raise ValueError(
            f"unknown CBFT_TPU_MUL={name!r}; choose from "
            f"{sorted(impls)}"
        )
    return impl


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook 17×15-bit-limb multiply in native int32 lanes."""
    return _impl(_MUL_IMPLS)(a, b)


# The 153 distinct limb products of a square, (i, j) with i ≤ j in row
# order, and their fold matrices: a diagonal product at weight 1, an
# off-diagonal one (taken once, standing for a_i·a_j and a_j·a_i) at 2.
_SQ_I, _SQ_J = np.triu_indices(NUM_LIMBS)
_SQ_M_LO, _SQ_M_HI = _fold_matrices(_SQ_I, _SQ_J, 1 + (_SQ_I != _SQ_J))


def _sq_diagonals(a: jnp.ndarray):
    """The 136 off-diagonal products, by diagonals: diagonal k (1..16) is
    ``a[:17-k] * a[k:]``, the products a_i·a_{i+k}, two plain slices of
    the operand and no broadcast. Their lo parts fall on columns 2i+k,
    every OTHER column, so the 34 columns are kept as two [17, B] halves
    — E, columns 0, 2, .., 32, and O, columns 1, 3, .., 33 — in each of
    which a diagonal is contiguous: for k = 2t the lo parts are
    E[t..t+16-k] and the hi parts (one column up) O[t..]; for k = 2t+1
    the lo parts are O[t..] and the hi parts E[t+1..]. → (the padded
    [17, B] terms of E, those of O), weight not yet applied.

    Chosen on the v5e over the triangle's rows (``a[i] * a[i+1:]`` into
    34 columns) and over one concatenated product: 2.48 us a square at
    [17, 2048] against 2.97 and 12.3, mul(a, a) 4.25; the whole
    verify_compact@2048 12.77 ms against 13.63, and 15.80 with
    mul(a, a) (PERF.md, PR 35)."""
    tail_pad = [(0, 0)] * (a.ndim - 1)
    even, odd = [], []
    for k in range(1, NUM_LIMBS):
        p = a[: NUM_LIMBS - k] * a[k:]  # [17-k, B]
        lo_to, hi_to = (even, odd) if k % 2 == 0 else (odd, even)
        lo_at = k // 2
        hi_at = lo_at + k % 2
        lo_to.append(jnp.pad(p & _MASK, [(lo_at, k - lo_at)] + tail_pad))
        hi_to.append(jnp.pad(p >> RADIX, [(hi_at, k - hi_at)] + tail_pad))
    return even, odd


def _sq_fold(a: jnp.ndarray, even_off: jnp.ndarray,
             odd_off: jnp.ndarray) -> jnp.ndarray:
    """The diagonal a_i² (lo on column 2i = E[i], hi on O[i]) plus the
    summed off-diagonal parts at weight 2, folded (column c + 17 onto c
    at weight 19: O[m+8] onto E[m], E[m+9] onto O[m]) and interleaved
    ONCE back into limb order."""
    d = a * a
    e = (d & _MASK) + 2 * even_off  # the weight, on the summed PARTS
    o = (d >> RADIX) + 2 * odd_off
    half = (NUM_LIMBS + 1) // 2  # 9 even limbs 0, 2, .., 16; 8 odd ones
    f_even = e[:half] + 19 * o[half - 1 :]
    f_odd = o[: half - 1] + 19 * e[half:]
    f_odd = jnp.pad(f_odd, [(0, 1)] + [(0, 0)] * (a.ndim - 1))
    folded = jnp.stack([f_even, f_odd], axis=1).reshape(
        (2 * half,) + a.shape[1:]
    )[:NUM_LIMBS]
    return _reduce(folded)


def _sq_stack(a: jnp.ndarray) -> jnp.ndarray:
    """_mul_stack's square: each half's terms stacked and summed."""
    even, odd = _sq_diagonals(a)
    return _sq_fold(
        a,
        jnp.sum(jnp.stack(even, axis=0), axis=0),
        jnp.sum(jnp.stack(odd, axis=0), axis=0),
    )


def _sq_shift_add(a: jnp.ndarray) -> jnp.ndarray:
    """_mul_shift_add's square: the same terms into two running
    accumulators."""
    even, odd = _sq_diagonals(a)
    return _sq_fold(a, sum(even[1:], even[0]), sum(odd[1:], odd[0]))


def _sq_matmul(a: jnp.ndarray) -> jnp.ndarray:
    """_mul_matmul's square: ONE [153, B] product from two static index
    vectors, folded by two [17, 153] matmuls that carry the weights —
    a smaller graph than the [289, B] product's, which is what the CPU
    backend's compile time follows."""
    prod = a[_SQ_I] * a[_SQ_J]  # [153, B]
    folded = jnp.asarray(_SQ_M_LO) @ (prod & _MASK) + jnp.asarray(
        _SQ_M_HI
    ) @ (prod >> RADIX)
    return _reduce(folded)


# One square per multiplication form, under the same names: CBFT_TPU_MUL
# selects both. f32 squares through its product (it lost 3.3× on the
# last chip that ran it; ROADMAP C1).
_SQ_IMPLS = {
    "stack": _sq_stack,
    "shift_add": _sq_shift_add,
    "matmul": _sq_matmul,
    "f32": lambda a: _mul_f32(a, a),
}


def sq(a: jnp.ndarray) -> jnp.ndarray:
    """a² from its 153 distinct limb products (17 diagonal, 136
    off-diagonal taken once at weight 2) instead of mul's 289: the same
    columns, so the same limbs as mul(a, a)."""
    return _impl(_SQ_IMPLS)(a)


def mul_small(a: jnp.ndarray, c: int) -> jnp.ndarray:
    """Multiply by a small constant (|c| ≤ 16)."""
    return _reduce(a * c)


def _carry_seq(x: jnp.ndarray):
    """Exact sequential carry pass (only used by to_canonical — the rare
    encode/compare path). Returns (limbs in [0, 2^15), carry_out)."""
    out = []
    carry = jnp.zeros(x.shape[1:], jnp.int32)
    for i in range(NUM_LIMBS):
        t = x[i] + carry
        out.append(t & _MASK)
        carry = t >> RADIX
    return jnp.stack(out, axis=0), carry


def to_canonical(x: jnp.ndarray) -> jnp.ndarray:
    """Invariant fe (value in [0, ~2^255.01)) → unique representative in [0, p)."""
    # Two fold+propagate iterations: first brings value < 2^255 + 19,
    # second < 2^255 (the +19 can set bit 255 only for values < 2^255+19).
    for _ in range(2):
        x, c = _carry_seq(x)
        x = x.at[0].add(19 * c)
        x, _ = _carry_seq(x)
    # Conditionally subtract p (value < 2^255 < 2p ⇒ at most once).
    diff, borrow = _carry_seq(x - _P_LIMBS)
    return jnp.where((borrow == 0)[None], diff, x)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Constant-shape equality in the field → bool[batch]."""
    return jnp.all(to_canonical(a) == to_canonical(b), axis=0)


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(to_canonical(a) == 0, axis=0)


def select(pred: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """pred: bool[batch] → element-wise fe select (a where pred)."""
    return jnp.where(pred[None], a, b)


def _sq_n(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """n squarings in a row. Rolled into a fori_loop so the long runs in
    the inversion addition chains (up to 100) stay one compiled body."""
    if n <= 2:
        for _ in range(n):
            x = sq(x)
        return x
    return lax.fori_loop(0, n, lambda i, v: sq(v), x)


def invert(x: jnp.ndarray) -> jnp.ndarray:
    """x^(p-2) = x^(2^255-21) by the ref10 addition chain: 254 squarings
    + 11 multiplies — versus ~254 squarings + 254 always-computed
    conditional multiplies for generic square-and-multiply. invert(0) = 0
    (harmless: used only on Z ≠ 0)."""
    t0 = sq(x)  # 2
    t1 = mul(x, _sq_n(t0, 2))  # 9
    t2 = mul(t0, t1)  # 11
    t3 = sq(t2)  # 22
    t3 = mul(t1, t3)  # 31 = 2^5-1
    t4 = mul(_sq_n(t3, 5), t3)  # 2^10-1
    t5 = mul(_sq_n(t4, 10), t4)  # 2^20-1
    t6 = mul(_sq_n(t5, 20), t5)  # 2^40-1
    t5 = mul(_sq_n(t6, 10), t4)  # 2^50-1
    t6 = mul(_sq_n(t5, 50), t5)  # 2^100-1
    t7 = mul(_sq_n(t6, 100), t6)  # 2^200-1
    t6 = mul(_sq_n(t7, 50), t5)  # 2^250-1
    return mul(_sq_n(t6, 5), t2)  # (2^250-1)·2^5 + 11 = 2^255-21


def pow_p58(x: jnp.ndarray) -> jnp.ndarray:
    """x^((p-5)/8) = x^(2^252-3) — the square-root-ratio exponent for
    decompression, by the ref10 fe_pow22523 addition chain."""
    t0 = sq(x)  # 2
    t1 = mul(x, _sq_n(t0, 2))  # 9
    t0 = mul(t0, t1)  # 11
    t0 = sq(t0)  # 22
    t0 = mul(t1, t0)  # 31 = 2^5-1
    t1 = mul(_sq_n(t0, 5), t0)  # 2^10-1
    t2 = mul(_sq_n(t1, 10), t1)  # 2^20-1
    t3 = mul(_sq_n(t2, 20), t2)  # 2^40-1
    t2 = mul(_sq_n(t3, 10), t1)  # 2^50-1
    t3 = mul(_sq_n(t2, 50), t2)  # 2^100-1
    t4 = mul(_sq_n(t3, 100), t3)  # 2^200-1
    t3 = mul(_sq_n(t4, 50), t2)  # 2^250-1
    return mul(_sq_n(t3, 2), x)  # (2^250-1)·4 + 3 = 2^252-3


def bytes_to_limbs_np(data):
    """numpy uint8[..., 32] → int32[..., 17] limbs of the low 255 bits
    (bit 255 — the ed25519 sign bit — is excluded; handle it separately).
    NOTE: host-side helper; the limb axis lands LAST here — transpose to
    limb-major before feeding the kernel."""
    import numpy as np

    b = np.asarray(data, dtype=np.uint8)
    bits = np.unpackbits(b, axis=-1, bitorder="little")[..., : NUM_LIMBS * RADIX]
    weights = (1 << np.arange(RADIX, dtype=np.int32)).astype(np.int32)
    shaped = bits.reshape(b.shape[:-1] + (NUM_LIMBS, RADIX)).astype(np.int32)
    return shaped @ weights
