"""Batched Ed25519 verification as one XLA tensor program.

This is the north-star kernel (BASELINE.json): the reference verifies
commits one signature at a time on a single goroutine
(types/validator_set.go:685-823 → crypto/ed25519/ed25519.go:148). Here the
whole batch is verified at once: every signature is a lane of a fixed-shape
SPMD computation — point decompression, a joint windowed Straus
double-scalar multiplication [s]B + [h](-A), and an encode-and-compare
against R — built from the limb arithmetic in `field`. The batch axis is
explicit (and minor-most, i.e. on the TPU vector lanes — see field.py's
limb-major layout notes) so pjit/shard_map can spread a 10k-validator
mega-commit across an ICI mesh.

Algorithm: radix-4 joint Straus. Both 253-bit scalars are split into 127
2-bit digits; one 16-entry table ds·B + dh·(-A) (ds, dh ∈ 0..3) is built
per signature, entries kept in "cached" form (Y+X, Y−X, 2d·T, 2Z) so the
main-loop addition costs 8 field muls. Loop: 127 × (2 doublings + 1
branch-free table select + 1 cached add). The table select is a one-hot
multiply-accumulate over the 16 entries — a handful of full-width VPU
ops — rather than a per-lane gather, which XLA lowers to a (slow,
serializing) dynamic-gather on TPU. Everything is uniform across the
batch — no data-dependent control flow, ideal for SIMD lanes.

Two hashing modes (CBFT_TPU_HASH):
  * ``host`` — h = SHA-512(R ‖ A ‖ M) mod L per signature on the host
    while packing (one native call a launch, _challenge_scalars); the
    device runs only the group math.
  * ``device`` — the full pipeline is ONE dispatch: batched SHA-512
    (sha512.py, 64-bit lanes in 2×u32), exact mod-L reduction
    (scalar.sc_reduce — ref10 sc_reduce semantics, required for parity on
    torsioned keys), 2-bit digit extraction, then the Straus loop. The
    host's per-signature work drops to pure byte packing.

Semantics contract: accept/reject is bit-identical to the CPU backend
(OpenSSL via `cryptography`, itself matching ref10):
  * cofactorless check: encode([s]B + [h](-A)) must equal R byte-for-byte;
  * s is rejected unless s < L (RFC 8032 / modern OpenSSL);
  * A's y-coordinate is decoded mod p — non-canonical encodings are NOT
    rejected (ref10 fe_frombytes convention);
  * decompression failure (no square root) rejects;
  * x = 0 with sign bit set yields -0 = 0 (no special rejection), as ref10;
  * non-canonical R never matches (raw-limb compare = byte compare).

SHA-512(R ‖ A ‖ M) mod L runs host-side (C): messages are short and
variable-length, hashing is ~1% of the work; the 253-doubling scalar
multiplication — >99% of the FLOPs — is what the TPU executes.
"""

from __future__ import annotations

import hashlib
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from cometbft_tpu.crypto.tpu import field as fe
from cometbft_tpu.crypto.tpu.field import L, P

SCALAR_BITS = 253  # both s < L < 2^253 and h < L
NUM_DIGITS = 127  # 2-bit windows

# --- curve constants (host-side Python-int math) ---------------------------


def _sqrt_ratio_py(u: int, v: int) -> Optional[int]:
    x = (u * pow(v, 3, P) * pow(u * pow(v, 7, P) % P, (P - 5) // 8, P)) % P
    vxx = v * x * x % P
    if vxx == u % P:
        return x
    if vxx == (-u) % P:
        return x * fe.SQRT_M1 % P
    return None


def _edwards_add_py(p, q):
    (x1, y1), (x2, y2) = p, q
    den = fe.D * x1 * x2 * y1 * y2 % P
    x3 = (x1 * y2 + x2 * y1) * pow(1 + den, P - 2, P) % P
    y3 = (y1 * y2 + x1 * x2) * pow(1 - den, P - 2, P) % P
    return (x3, y3)


_BY = 4 * pow(5, P - 2, P) % P
_BX = _sqrt_ratio_py((_BY * _BY - 1) % P, (fe.D * _BY * _BY + 1) % P)
assert _BX is not None
if _BX & 1:  # base point encoding has sign bit 0 → even x
    _BX = P - _BX

_B_AFFINE = (_BX, _BY)
_B2_AFFINE = _edwards_add_py(_B_AFFINE, _B_AFFINE)
_B3_AFFINE = _edwards_add_py(_B2_AFFINE, _B_AFFINE)

_D_FE = fe.const_fe(fe.D)
_D2_FE = fe.const_fe(fe.D2)
_SQRT_M1_FE = fe.const_fe(fe.SQRT_M1)
_ONE_FE = fe.const_fe(1)
_ZERO_FE = fe.const_fe(0)


def _const_point(affine) -> "Point":
    x, y = affine
    return (fe.const_fe(x), fe.const_fe(y), fe.const_fe(1), fe.const_fe(x * y % P))


_B_POINT = _const_point(_B_AFFINE)
_B2_POINT = _const_point(_B2_AFFINE)
_B3_POINT = _const_point(_B3_AFFINE)
_ID_POINT = (_ZERO_FE, _ONE_FE, _ONE_FE, _ZERO_FE)

Point = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]
CachedPoint = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]


# --- point arithmetic (a = -1 extended coordinates) ------------------------


def point_dbl(p: Point) -> Point:
    """dbl-2008-hwcd, a = -1. Valid for every input including identity."""
    x1, y1, z1, _ = p
    a = fe.sq(x1)
    b = fe.sq(y1)
    c = fe.mul_small(fe.sq(z1), 2)
    d = fe.neg(a)
    e = fe.sub(fe.sub(fe.sq(fe.add(x1, y1)), a), b)
    g = fe.add(d, b)
    f = fe.sub(g, c)
    h = fe.sub(d, b)
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def point_add(p: Point, q: Point) -> Point:
    """add-2008-hwcd-3 (unified, k = 2d). Complete on this curve: a = -1 is
    a square mod p and d is not, so no exceptional cases — identical code
    for add/double/identity, exactly what a branch-free SIMD batch needs."""
    return add_cached(p, cache_point(q))


def cache_point(q: Point) -> CachedPoint:
    """(Y+X, Y−X, 2d·T, 2Z) — the ref10 'cached' form: one-time cost per
    table entry, saves one mul per main-loop addition."""
    x2, y2, z2, t2 = q
    return (
        fe.add(y2, x2),
        fe.sub(y2, x2),
        fe.mul(t2, _D2_FE),
        fe.mul_small(z2, 2),
    )


def add_cached(p: Point, qc: CachedPoint) -> Point:
    x1, y1, z1, t1 = p
    yp, ym, t2d, z2 = qc
    a = fe.mul(fe.sub(y1, x1), ym)
    b = fe.mul(fe.add(y1, x1), yp)
    c = fe.mul(t1, t2d)
    d = fe.mul(z1, z2)
    e = fe.sub(b, a)
    f = fe.sub(d, c)
    g = fe.add(d, c)
    h = fe.add(b, a)
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


# --- decompression ---------------------------------------------------------


def decompress(y: jnp.ndarray, sign: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """y: fe[17,B] (low 255 bits), sign: int32[B].

    Returns (x, ok). ref10 semantics: y is taken mod p; the candidate root
    x = (u/v)^((p+3)/8) is validated by v·x² ∈ {u, -u}; parity is adjusted
    to the sign bit (negating 0 keeps 0).
    """
    yy = fe.sq(y)
    u = fe.sub(yy, _ONE_FE)
    v = fe.add(fe.mul(yy, _D_FE), _ONE_FE)
    v3 = fe.mul(fe.sq(v), v)
    v7 = fe.mul(fe.sq(v3), v)
    t = fe.pow_p58(fe.mul(u, v7))
    x = fe.mul(fe.mul(u, v3), t)
    vxx = fe.mul(v, fe.sq(x))
    ok_direct = fe.eq(vxx, u)
    ok_flip = fe.eq(vxx, fe.neg(u))
    x = fe.select(ok_flip, fe.mul(x, _SQRT_M1_FE), x)
    ok = ok_direct | ok_flip
    xc = fe.to_canonical(x)
    flip = (xc[0] & 1) != sign
    x = fe.select(flip, fe.neg(x), x)
    return x, ok


# --- the verification kernel ----------------------------------------------


def _select_cached(entries: List[CachedPoint], idx: jnp.ndarray) -> CachedPoint:
    """Branch-free table lookup as one-hot multiply-accumulate:
    idx int32[B] ∈ [0, 16) → the idx-th cached point per lane.

    A per-lane gather (take_along_axis) lowers to TPU dynamic-gather —
    slow and serializing. The one-hot form is 16 masked adds per
    coordinate: plain full-lane VPU work that XLA fuses into the loop."""
    oh = idx[None, :] == jnp.arange(len(entries), dtype=jnp.int32)[:, None]
    out = []
    for k in range(4):
        acc = None
        for e_i, entry in enumerate(entries):
            term = jnp.where(oh[e_i][None, :], entry[k], 0)
            acc = term if acc is None else acc + term
        out.append(acc)
    return tuple(out)


def unpack_fe_limbs(words: jnp.ndarray) -> jnp.ndarray:
    """u32[8,B] little-endian words → int32[17,B] 15-bit limbs of the low
    255 bits (bit 255 — the sign bit — is naturally excluded: limb 16
    covers bits 240..254). Runs ON DEVICE: the wire format ships the raw
    32-byte encodings and pays a few shifts per limb instead of 68 bytes
    of pre-split limbs per field element (the round-5 shared chip's link
    was bandwidth-bound — BENCH_onchip_probe.json: 299 ms transfer vs
    0.22 ms compute at batch 4096)."""
    limbs = []
    for i in range(fe.NUM_LIMBS):
        bit = 15 * i
        j, k = bit // 32, bit % 32
        w = words[j] >> k
        if k > 17 and j + 1 < 8:  # limb spans into the next word
            w = w | (words[j + 1] << (32 - k))
        limbs.append((w & jnp.uint32(0x7FFF)).astype(jnp.int32))
    return jnp.stack(limbs, axis=0)


def unpack_digits(words: jnp.ndarray) -> jnp.ndarray:
    """u32[8,B] little-endian scalar words → int32[127,B] radix-4 digits,
    MSB first (device-side equivalent of the old host _digits_msb_first;
    a 2-bit digit at even bit offset never crosses a word boundary)."""
    digs = []
    for d in range(NUM_DIGITS):
        bit = 2 * (NUM_DIGITS - 1 - d)
        j, k = bit // 32, bit % 32
        digs.append(((words[j] >> k) & jnp.uint32(3)).astype(jnp.int32))
    return jnp.stack(digs, axis=0)


def bytes_to_words(rows: jnp.ndarray) -> jnp.ndarray:
    """u8[4k,B] raw little-endian byte rows → u32[k,B] LE words, ON
    DEVICE. The compact wire ships the 32-byte encodings exactly as they
    appear in blocks (uint8), so the host never touches a word view; the
    device pays three shifts and three ORs per word — noise next to the
    253-doubling Straus loop."""
    r = rows.astype(jnp.uint32)
    return r[0::4] | (r[1::4] << 8) | (r[2::4] << 16) | (r[3::4] << 24)


def _unpack_points_scalar(wire: jnp.ndarray):
    """Rows 0:24 of the wire (A, R, S — shared between the host-hash and
    device-hash layouts) → (ay, a_sign, r_y, r_sign, s_digits)."""
    pk_w, r_w = wire[0:8], wire[8:16]
    ay = unpack_fe_limbs(pk_w)
    a_sign = (pk_w[7] >> 31).astype(jnp.int32)
    r_y = unpack_fe_limbs(r_w)
    r_sign = (r_w[7] >> 31).astype(jnp.int32)
    s_digits = unpack_digits(wire[16:24])
    return ay, a_sign, r_y, r_sign, s_digits


def unpack_wire(wire: jnp.ndarray):
    """u32[32,B] wire (rows 0:8 A, 8:16 R, 16:24 S, 24:32 h, all LE
    words) → the six unpacked kernel inputs."""
    return _unpack_points_scalar(wire) + (unpack_digits(wire[24:32]),)


def _verify_unpacked(
    ay: jnp.ndarray,  # int32[17,B]  A's y limbs (low 255 bits)
    a_sign: jnp.ndarray,  # int32[B]  A's sign bit
    r_y: jnp.ndarray,  # int32[17,B]  R's y limbs (low 255 bits)
    r_sign: jnp.ndarray,  # int32[B]  R's sign bit
    s_digits: jnp.ndarray,  # int32[127,B]  s 2-bit digits, MSB first
    h_digits: jnp.ndarray,  # int32[127,B]  h 2-bit digits, MSB first
) -> jnp.ndarray:
    """bool[B]: encode([s]B + [h](-A)) == R and A decompressed OK."""
    batch = ay.shape[1:]
    x, ok = decompress(ay, a_sign)
    nx = fe.neg(x)
    neg_a: Point = (nx, ay, jnp.broadcast_to(_ONE_FE, ay.shape), fe.mul(nx, ay))

    # Table: entry[ds + 4·dh] = ds·B + dh·(-A), in cached form. Constant
    # (dh=0) entries stay [17,1] and broadcast inside the one-hot select.
    a2 = point_dbl(neg_a)
    a3 = point_add(a2, neg_a)
    s_pts = [_ID_POINT, _B_POINT, _B2_POINT, _B3_POINT]
    h_pts = [None, neg_a, a2, a3]
    entries: List[CachedPoint] = []
    for dh in range(4):
        for ds in range(4):
            if dh == 0:
                pt = s_pts[ds]
            elif ds == 0:
                pt = h_pts[dh]
            else:
                pt = point_add(s_pts[ds], h_pts[dh])
            entries.append(cache_point(pt))

    ident: Point = tuple(
        jnp.broadcast_to(c, (fe.NUM_LIMBS,) + batch) for c in _ID_POINT
    )

    def body(i, acc: Point) -> Point:
        acc = point_dbl(point_dbl(acc))
        idx = s_digits[i] + 4 * h_digits[i]
        return add_cached(acc, _select_cached(entries, idx))

    rx, ry, rz, _ = lax.fori_loop(0, NUM_DIGITS, body, ident)

    zinv = fe.invert(rz)
    ex = fe.to_canonical(fe.mul(rx, zinv))
    ey = fe.to_canonical(fe.mul(ry, zinv))
    # Encode-and-compare, split into (255-bit y, sign bit) — equivalent to
    # the ref10 byte-compare of the full 32-byte encoding. r_y is compared
    # RAW (not canonicalized): a non-canonical R encoding must never match,
    # exactly as a byte-compare behaves.
    y_eq = jnp.all(ey == r_y, axis=0)
    sign_eq = (ex[0] & 1) == r_sign
    return y_eq & sign_eq & ok


def _verify_core(wire: jnp.ndarray) -> jnp.ndarray:
    """bool[B] from the u32[32,B] wire buffer (host-hash mode). ONE input
    array per dispatch: 128 bytes/sig on the link instead of the 1,160
    bytes/sig the pre-split limb+digit arrays cost."""
    return _verify_unpacked(*unpack_wire(wire))


verify_kernel = jax.jit(_verify_core)


def _verify_core_compact(wire: jnp.ndarray) -> jnp.ndarray:
    """bool[B] from the COMPACT u8[128,B] wire (rows 0:32 A, 32:64 R,
    64:96 S, 96:128 h — raw little-endian bytes). The whole decompress
    prologue — byte→word packing, limb unpacking, sign extraction,
    2-bit scalar windowing — runs fused in front of the Straus loop, so
    the host pack is a byte transpose and nothing else."""
    return _verify_unpacked(*unpack_wire(bytes_to_words(wire)))


verify_kernel_compact = jax.jit(_verify_core_compact)


@jax.jit
def verify_full_kernel(
    wire: jnp.ndarray,  # u32[24,B]  rows 0:8 A, 8:16 R, 16:24 S (LE words)
    msg_hi: jnp.ndarray,  # u32[n_blocks,16,B]  padded R‖A‖M, BE word hi
    msg_lo: jnp.ndarray,  # u32[n_blocks,16,B]
    msg_nblocks: jnp.ndarray,  # int32[B]  live block count per lane
) -> jnp.ndarray:
    """The whole verification — SHA-512, mod-L, digits, Straus — as one
    device program: no host work between hash and group math, no extra
    dispatches (CBFT_TPU_HASH=device path)."""
    from cometbft_tpu.crypto.tpu import scalar, sha512

    ay, a_sign, r_y, r_sign, s_digits = _unpack_points_scalar(wire)
    dig_hi, dig_lo = sha512.sha512_blocks(msg_hi, msg_lo, msg_nblocks)
    h = scalar.sc_reduce(scalar.digest_to_limbs(dig_hi, dig_lo))
    h_digits = scalar.digits_msb_first(h)
    return _verify_unpacked(ay, a_sign, r_y, r_sign, s_digits, h_digits)


@jax.jit
def verify_full_kernel_compact(
    wire: jnp.ndarray,  # u8[96,B]  rows 0:32 A, 32:64 R, 64:96 S (raw bytes)
    msg: jnp.ndarray,  # u8[MP,B]  raw message bytes, zero-filled past mlen
    mlen: jnp.ndarray,  # int32[B]  live message bytes per lane
) -> jnp.ndarray:
    """The compact device-hash pipeline: SHA-512 PADDING and
    compression, mod-L, digit windowing, decompress, and the Straus
    loop — one fused program from raw bytes. The 64-byte hash prefix
    R ‖ A is reassembled from the wire on device, so the link never
    ships those bytes twice and the message plane carries padded raw
    uint8 instead of pre-split u32 block words (128 B per block per
    lane → the actual message length rounded to the block grid)."""
    from cometbft_tpu.crypto.tpu import scalar, sha512

    words = bytes_to_words(wire)  # u32[24,B]
    ay, a_sign, r_y, r_sign, s_digits = _unpack_points_scalar(words)
    prefix = jnp.concatenate([wire[32:64], wire[0:32]], axis=0)  # R ‖ A
    max_blocks = (64 + msg.shape[0]) // 128  # staging keeps this exact
    hi, lo, n_live = sha512.blocks_from_bytes(prefix, msg, mlen, max_blocks)
    dig_hi, dig_lo = sha512.sha512_blocks(hi, lo, n_live)
    h = scalar.sc_reduce(scalar.digest_to_limbs(dig_hi, dig_lo))
    h_digits = scalar.digits_msb_first(h)
    return _verify_unpacked(ay, a_sign, r_y, r_sign, s_digits, h_digits)


def _verify_core_indexed(
    table: jnp.ndarray,  # u8[N,32]  resident pubkey encodings (keystore)
    idx: jnp.ndarray,  # int32[B]  table row per lane
    rsh: jnp.ndarray,  # u8[96,B]  rows 0:32 R, 32:64 S, 64:96 h (raw bytes)
) -> jnp.ndarray:
    """bool[B] against a device-resident pubkey table: steady-state
    consensus traffic ships sigs, challenge scalars, and a 4-byte index
    per lane — the pubkey bytes never cross the link again after the
    key-store upload. The gather is per-lane but runs ONCE per dispatch
    (32 bytes/lane), not inside the Straus loop."""
    rows = jnp.take(table, idx, axis=0)  # u8[B,32]; clipped for pad lanes
    a_words = bytes_to_words(rows.T)
    ay = unpack_fe_limbs(a_words)
    a_sign = (a_words[7] >> 31).astype(jnp.int32)
    w = bytes_to_words(rsh)  # u32[24,B]
    r_y = unpack_fe_limbs(w[0:8])
    r_sign = (w[0:8][7] >> 31).astype(jnp.int32)
    s_digits = unpack_digits(w[8:16])
    h_digits = unpack_digits(w[16:24])
    return _verify_unpacked(ay, a_sign, r_y, r_sign, s_digits, h_digits)


verify_kernel_indexed = jax.jit(_verify_core_indexed)


# --- host glue -------------------------------------------------------------

_MIN_PAD = 64
# The CEILING of a launch's lanes IN TOTAL (per-curve default);
# [crypto] max_chunk and CBFT_TPU_MAX_CHUNK override it for ALL curve
# kernels at the shared dispatch layer (mesh.chunk_cap), the OOM-shrink
# ladder and the memory guard halve it, and the scheduler budgets a
# flush's lanes by it. The ed25519 entries launch BELOW it, at
# _LAUNCH_LANES a chip; it is a launch's size only once it has been
# shrunk under that. On the v5e the program runs at 6.2-6.5 us a lane
# (7.3-8.5 while a squaring cost a full multiplication; PERF.md, PR 35)
# whatever the bucket from 512 up and a launch costs 0.75 ms to issue
# (1.75 ms over four chips), so a larger launch buys no device time: two
# launches of 8,192 + 2,048 padded lanes beat one of 16,384 by 19 ms a
# 10,000-lane commit on four chips (PERF.md, PR 26). Device memory is no
# bound: HBM peaks at 211 MB of 16 GB.
_MAX_CHUNK = 8192
# The SIZE of one launch of the ed25519 entries, in lanes a chip, within
# the ceiling above: the resident commit (verify_valset_resident) and the
# keyed flush (verify_batch) are streams of such launches whose lanes are
# built while the launches before run, so the size trades the host work
# in front of the first launch against the fixed cost a launch (0.75 ms
# to issue, ~3.5 ms of each pack call). Fixed on the v5e from a
# 10,000-lane verify_commit's median: 4,096 a chip 115.9 ms, 2,048
# 100.5, 1,024 102.1, against 128.1 with every lane built first
# (PERF.md, PR 27), and re-read on a 6,464-lane blocksync window's
# median: one launch of 8,192 107.9 ms, 4,096 102.4, 2,048 83.1, 1,024
# 77.8 with 512 fewer padded lanes (79.8 for 2,048 at the same padding;
# PERF.md, PR 29): one size for both routes. A bucket of the warm ladder
# (aot.bucket_ladder) at the defaults, and where that ladder ends for
# these kernels on one chip (``launch`` of aot.register_kernel).
_LAUNCH_LANES = 2048


def _le_words(arr_u8: np.ndarray) -> np.ndarray:
    """u8[B,32] → u32[8,B] little-endian words."""
    return np.ascontiguousarray(np.ascontiguousarray(arr_u8).view("<u4").T)


_L_BYTES_LE = np.frombuffer(L.to_bytes(32, "little"), np.uint8)


def _s_below_l(s_arr: np.ndarray) -> np.ndarray:
    """bool[B]: s < L, compared little-endian from the most significant
    byte down (u8[B,32] in)."""
    n = s_arr.shape[0]
    diff = s_arr.astype(np.int16) - _L_BYTES_LE.astype(np.int16)
    nz_mask = diff != 0
    has_diff = nz_mask.any(axis=1)
    msb_idx = 31 - nz_mask[:, ::-1].argmax(axis=1)
    return has_diff & (diff[np.arange(n), msb_idx] < 0)


def _parse_inputs(pub_keys, sigs):
    """→ (pk_arr u8[B,32], sig_arr u8[B,64], valid) with wrong-length and
    s ≥ L entries masked out (zero-filled placeholders keep the shapes).
    THE place a keyed lane's key and signature bytes are copied: one
    join a column into the launch's arrays. The lengths are read as a
    set first, so the placeholder loop runs only for a launch that
    holds a malformed lane."""
    n = len(pub_keys)
    valid = np.ones(n, bool)
    if set(map(len, pub_keys)) <= {32} and set(map(len, sigs)) <= {64}:
        pk_parts, sig_parts = pub_keys, sigs
    else:
        pk_parts, sig_parts = [], []
        for i in range(n):
            pk, sig = pub_keys[i], sigs[i]
            if len(pk) != 32 or len(sig) != 64:
                valid[i] = False
                pk_parts.append(b"\x00" * 32)
                sig_parts.append(b"\x00" * 64)
            else:
                pk_parts.append(pk)
                sig_parts.append(sig)
    pk_arr = np.frombuffer(b"".join(pk_parts), np.uint8).reshape(n, 32)
    sig_arr = np.frombuffer(b"".join(sig_parts), np.uint8).reshape(n, 64)
    valid &= _s_below_l(sig_arr[:, 32:])
    return pk_arr, sig_arr, valid


# Lanes from which a pack's challenge scalars are one native call
# (native.ed25519_challenges) rather than the hashlib + big-int loop
# below, whatever the cores: on the v5e's host (PR 37) the loop took 8
# lanes 29 us and 16 lanes 57, the native call on one thread 38 and 44.
_NATIVE_CHALLENGE_MIN = 16


def _challenge_scalars(
    pk_arr: np.ndarray, sig_arr: np.ndarray, msgs, valid: np.ndarray
) -> np.ndarray:
    """h = SHA-512(R ‖ A ‖ M) mod L per valid lane → u8[B,32]
    little-endian. From _NATIVE_CHALLENGE_MIN lanes up one native C
    call hashes the launch on native.challenge_threads(B) threads
    (native/ed25519_batch.c cbft_ed25519_challenges); below it, or
    where the native library is missing or stale, the hashlib loop
    (_challenge_scalars_py). The wire ledger books each call's lanes
    by path and its threads."""
    from cometbft_tpu import native
    from cometbft_tpu.crypto import wire

    n = len(msgs)
    h_arr = None
    if n >= _NATIVE_CHALLENGE_MIN:
        threads = native.challenge_threads(n)
        h_arr = native.ed25519_challenges(
            pk_arr, sig_arr[:, :32], msgs, valid, threads
        )
    path = "native"
    if h_arr is None:
        path, threads = "python", 1
        h_arr = _challenge_scalars_py(pk_arr, sig_arr, msgs, valid)
    ledger = wire.default_ledger()
    if ledger is not None:
        ledger.note_challenges(path, n, threads)
    return h_arr


def _challenge_scalars_py(pk_arr, sig_arr, msgs, valid) -> np.ndarray:
    """_challenge_scalars lane by lane with hashlib and CPython's big
    ints: the small launches' path and the parity oracle."""
    n = len(msgs)
    h_arr = np.zeros((n, 32), np.uint8)
    sha = hashlib.sha512
    for i in range(n):
        if not valid[i]:
            continue
        h_int = (
            int.from_bytes(
                sha(
                    sig_arr[i, :32].tobytes()
                    + pk_arr[i].tobytes()
                    + bytes(msgs[i])
                ).digest(),
                "little",
            )
            % L
        )
        h_arr[i] = np.frombuffer(h_int.to_bytes(32, "little"), np.uint8)
    return h_arr


def prepare_batch(
    pub_keys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
):
    """Host-side packing for the host-hash mode → (wire u32[32,B], valid).

    The wire buffer carries the raw little-endian words of A, R, S and
    h = SHA-512(R ‖ A ‖ M) mod L; limb splitting and digit extraction
    moved on-device (unpack_wire) so the link carries 128 bytes/sig,
    not 1,160."""
    pk_arr, sig_arr, valid = _parse_inputs(pub_keys, sigs)
    h_arr = _challenge_scalars(pk_arr, sig_arr, msgs, valid)

    wire = np.concatenate(
        [
            _le_words(pk_arr),
            _le_words(sig_arr[:, :32]),
            _le_words(sig_arr[:, 32:]),
            _le_words(h_arr),
        ],
        axis=0,
    )
    return wire, valid


def pack_compact_rows(*row_arrs: np.ndarray) -> np.ndarray:
    """Stack u8[B,k] byte arrays into the compact byte-major wire
    u8[Σk,B]: one preallocated buffer and one transposed copy per
    plane — no word views, no concatenate — which is why the compact
    pack can never cost more host time than the word pack it replaces
    (bench_micro `pack` asserts this on CPU CI)."""
    n = row_arrs[0].shape[0]
    rows = sum(a.shape[1] for a in row_arrs)
    wire = np.empty((rows, n), np.uint8)
    at = 0
    for a in row_arrs:
        wire[at : at + a.shape[1]] = a.T
        at += a.shape[1]
    return wire


def prepare_batch_compact(
    pub_keys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
):
    """Host-side packing for the compact host-hash wire →
    (wire u8[128,B], valid): rows 0:32 A, 32:64 R, 64:96 S,
    96:128 h, raw little-endian bytes. Bit-identical inputs to
    prepare_batch's u32 wire (the kernel's bytes_to_words prologue
    reproduces the exact words), shipped without any host word
    packing."""
    pk_arr, sig_arr, valid = _parse_inputs(pub_keys, sigs)
    h_arr = _challenge_scalars(pk_arr, sig_arr, msgs, valid)
    wire = pack_compact_rows(
        pk_arr, sig_arr[:, :32], sig_arr[:, 32:], h_arr
    )
    return wire, valid


def prepare_batch_device_hash(
    pub_keys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
):
    """Host-side packing for the device-hash mode: no hashing at all on
    the host — R ‖ A ‖ M is padded into SHA-512 blocks (bulk numpy) and
    the kernel does the rest. → (wire u32[24,B], msg_hi, msg_lo,
    nblocks, valid)."""
    from cometbft_tpu.crypto.tpu import sha512

    pk_arr, sig_arr, valid = _parse_inputs(pub_keys, sigs)
    hash_msgs = [
        sig_arr[i, :32].tobytes() + pk_arr[i].tobytes() + bytes(msgs[i])
        for i in range(len(pub_keys))
    ]
    msg_hi, msg_lo, nblocks = sha512.pad_ragged_np(hash_msgs)
    wire = np.concatenate(
        [
            _le_words(pk_arr),
            _le_words(sig_arr[:, :32]),
            _le_words(sig_arr[:, 32:]),
        ],
        axis=0,
    )
    return wire, msg_hi, msg_lo, nblocks, valid


def prepare_batch_device_hash_compact(
    pub_keys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
):
    """Compact device-hash packing → (wire u8[96,B], msg u8[MP,B],
    mlen int32[B], valid). Three wins over prepare_batch_device_hash:
    the wire is raw bytes (no word packing), the 64-byte R ‖ A hash
    prefix is NOT re-shipped with the message (the kernel rebuilds it
    from the wire), and SHA padding happens on device — the message
    plane is one bulk-scattered uint8 block instead of per-lane padded
    u32 hi/lo word planes, with no per-message Python concatenation."""
    from cometbft_tpu.crypto.tpu import sha512

    pk_arr, sig_arr, valid = _parse_inputs(pub_keys, sigs)
    wire = pack_compact_rows(pk_arr, sig_arr[:, :32], sig_arr[:, 32:])
    msg, mlen = sha512.stage_ragged_np(msgs, prefix_len=64)
    return wire, msg, mlen, valid


def hash_mode() -> str:
    """CBFT_TPU_HASH resolution: ``host`` and ``device`` pin the hash
    placement for A/B runs; ``auto`` (the default) lets the calibration
    crossover measured at warmup decide per dispatch size
    (hash_route)."""
    import os

    mode = os.environ.get("CBFT_TPU_HASH", "auto")
    if mode not in ("host", "device", "auto"):
        raise ValueError(
            f"unknown CBFT_TPU_HASH={mode!r}; choose from "
            "['auto', 'device', 'host']"
        )
    return mode


def hash_route(n: int) -> str:
    """Where h = SHA-512(R ‖ A ‖ M) runs for an n-lane dispatch:
    the env pin when set, else the measured crossover
    (calibrate.hash_device_min_batch — recorded by the warmup
    calibration sweep). Unmeasured (fresh node, CPU CI) → host: the
    round-5 probe showed the old device-hash path LOSING (38.8k vs
    75.8k sigs/s at 16k), so unproven means the safe side."""
    mode = hash_mode()
    if mode != "auto":
        return mode
    from cometbft_tpu.crypto.tpu import calibrate

    floor = calibrate.hash_device_min_batch()
    return "device" if floor is not None and n >= floor else "host"


def wire_format() -> str:
    """CBFT_TPU_WIRE: ``compact`` (default — raw uint8 rows, decompress
    prologue on device) or ``words`` (the pre-PR-13 u32 word wire, kept
    as the A/B and parity reference)."""
    import os

    fmt = os.environ.get("CBFT_TPU_WIRE", "compact")
    if fmt not in ("compact", "words"):
        raise ValueError(
            f"unknown CBFT_TPU_WIRE={fmt!r}; choose from "
            "['compact', 'words']"
        )
    return fmt


def verify_batch(
    pub_keys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    hash: Optional[str] = None,
) -> List[bool]:
    """Public entry used by crypto.batch.TPUBatchVerifier. Packing runs
    per dispatch chunk (the callable form of dispatch_batch) so the host
    hashing of chunk i+1 overlaps the device's work on chunk i. The
    three columns are sequences of bytes-like lanes, taken as they are
    and only sliced here: a lane's bytes are copied once, by its
    launch's pack (``_parse_inputs``; the messages by the hash), and the
    length / s < L checks are made there.

    Route selection is two-dimensional: wire_format() picks compact
    (raw uint8 rows, on-device decompress — the default) vs the legacy
    u32 word wire, and hash_route(n) picks where SHA-512 runs (env pin
    or the measured calibration crossover) unless the caller names the
    placement (``hash`` — the calibration sweep times both)."""
    n = len(pub_keys)
    if n == 0:
        return []
    compact = wire_format() == "compact"
    if (hash or hash_route(n)) == "device":
        prepare = (
            prepare_batch_device_hash_compact
            if compact else prepare_batch_device_hash
        )
        kernel = (
            verify_full_kernel_compact if compact else verify_full_kernel
        )
    else:
        prepare = prepare_batch_compact if compact else prepare_batch
        kernel = verify_kernel_compact if compact else verify_kernel
    valid_full = np.ones(n, bool)

    def chunk_pack(start: int, end: int):
        (*packed, valid) = prepare(
            pub_keys[start:end], msgs[start:end], sigs[start:end]
        )
        valid_full[start:end] = valid
        return packed

    from cometbft_tpu.crypto.tpu import mesh as mesh_mod

    out = mesh_mod.dispatch_batch(
        kernel, chunk_pack, n, _MAX_CHUNK, _MIN_PAD, launch=_LAUNCH_LANES
    )
    return (out & valid_full).tolist()


# --- valset-resident commit verification ------------------------------------
# The validator set's pubkeys are identical height after height (the
# reference re-verifies the SAME valset every commit —
# types/validator_set.go:685-707), so their wire rows live on device
# across calls: the per-commit link traffic drops to R ‖ S ‖ h
# (96 B/sig, 25% less than the full wire) and every height dispatches
# the same fixed shapes, hitting the same compiled executable. Absent
# lanes (nil/missing votes) ship zeros and are masked out host-side —
# full-lane dispatch is what keeps the resident layout stable while
# the set of signers varies per commit.


# The cache itself now lives in the generational DeviceKeyStore
# (crypto/tpu/keystore.py): same LRU + adopt-the-race-winner contract,
# plus generation tagging (store generation, topology generation) and
# the indexed-dispatch pubkey table. The module-level names below are
# aliases onto the store's own state so existing callers (warmup, tests
# that evict synthetic valsets) keep working unchanged.
from cometbft_tpu.crypto.tpu import keystore as _keystore_mod

_keystore = _keystore_mod.default_store()
_ResidentValset = _keystore_mod.KeyStoreEntry
_RESIDENT_CACHE_MAX = _keystore_mod.CACHE_MAX
_resident_cache = _keystore._entries
_resident_mtx = _keystore._mtx


def _get_resident(valset_id: bytes, pub_keys) -> _ResidentValset:
    return _keystore.get(valset_id, pub_keys, _build_resident)


def _verify_core_resident(a_words: jnp.ndarray, rsh: jnp.ndarray) -> jnp.ndarray:
    """bool[B] from resident pubkey rows (u32[8,B]) + the per-commit
    wire (u32[24,B]: rows 0:8 R, 8:16 S, 16:24 h, LE words)."""
    ay = unpack_fe_limbs(a_words)
    a_sign = (a_words[7] >> 31).astype(jnp.int32)
    r_w = rsh[0:8]
    r_y = unpack_fe_limbs(r_w)
    r_sign = (r_w[7] >> 31).astype(jnp.int32)
    s_digits = unpack_digits(rsh[8:16])
    h_digits = unpack_digits(rsh[16:24])
    return _verify_unpacked(ay, a_sign, r_y, r_sign, s_digits, h_digits)


verify_kernel_resident = jax.jit(_verify_core_resident)


# AOT registration: stable names (never id()-keyed) plus the per-bucket
# arg shape templates warm boot pre-compiles (crypto/tpu/aot.py).
# verify_full_kernel has no template — its msg-block axis is ragged per
# commit, so it cannot be bucket-warmed; it still gets a stable name.
def _device_hash_reachable() -> bool:
    """Can hash_route() send a dispatch to the device-hash kernel: the
    env pin, or a calibration sweep in which on-device SHA-512 won."""
    mode = hash_mode()
    if mode != "auto":
        return mode == "device"
    from cometbft_tpu.crypto.tpu import calibrate

    return calibrate.hash_device_min_batch() is not None


def _register_aot_kernels():
    from cometbft_tpu.crypto.tpu import aot

    # the u32 word wire and the device-hash pipeline are dispatched only
    # when CBFT_TPU_WIRE / hash_route select them: warm boot skips what
    # the routing in force cannot reach (``reachable``)
    aot.register_kernel(
        "ed25519.verify",
        verify_kernel,
        bucket_shapes=lambda b: [((32, b), np.uint32)],
        reachable=lambda: wire_format() == "words",
        forced=True,
        launch=_LAUNCH_LANES,
    )
    aot.register_kernel(
        "ed25519.verify_resident",
        verify_kernel_resident,
        bucket_shapes=lambda b: [((8, b), np.uint32), ((24, b), np.uint32)],
        donate_from=1,
        launch=_LAUNCH_LANES,
    )
    aot.register_kernel("ed25519.verify_full", verify_full_kernel)
    # compact-wire kernels (PR 13): the host-hash compact wire is the
    # default dispatch route, so it gets the same bucket warm plan as
    # the word wire it replaces. The device-hash compact kernel warms
    # the 2-block message bucket (MP = 2·128 − 64 = 192 — every
    # prevote/precommit lands there); other message paddings compile on
    # first use. The indexed kernel's table axis tracks valset size, so
    # it has no static template either.
    aot.register_kernel(
        "ed25519.verify_compact",
        verify_kernel_compact,
        bucket_shapes=lambda b: [((128, b), np.uint8)],
        reachable=lambda: wire_format() == "compact",
        forced=True,
        launch=_LAUNCH_LANES,
    )
    aot.register_kernel(
        "ed25519.verify_full_compact",
        verify_full_kernel_compact,
        bucket_shapes=lambda b: [
            ((96, b), np.uint8), ((192, b), np.uint8), ((b,), np.int32)
        ],
        reachable=lambda: (
            wire_format() == "compact" and _device_hash_reachable()
        ),
        launch=_LAUNCH_LANES,
    )
    aot.register_kernel(
        "ed25519.verify_indexed", verify_kernel_indexed, donate_from=1
    )


_register_aot_kernels()


def _build_resident(pub_keys: Sequence[bytes]) -> _ResidentValset:
    """Pad the valset's pubkey rows into the dispatch chunk layout and
    place them on device: sharded over the current shard plan's mesh
    (mesh.shard_plan: the healthy fault domains that own a chip) where
    there is one, else on the default chip. Chunks and padding are the
    one rounding rule's (mesh.shard_chunks), a chunk a launch of at most
    _LAUNCH_LANES lanes a chip within the chunk cap. Also builds the
    indexed-dispatch view (single-device only): a u8[n_pad, 32] gather
    table plus a pubkey→row index, so steady-state flushes against this
    valset ship an index vector instead of the keys."""
    from cometbft_tpu.crypto.tpu import mesh as mesh_mod
    from jax.sharding import NamedSharding, PartitionSpec as PS

    n = len(pub_keys)
    pk_ok = np.ones(n, bool)
    parts = []
    for i, pk in enumerate(pub_keys):
        if len(pk) != 32:
            pk_ok[i] = False
            parts.append(b"\x00" * 32)
        else:
            parts.append(bytes(pk))
    pk_arr = np.frombuffer(b"".join(parts), np.uint8).reshape(n, 32)

    plan = mesh_mod.shard_plan()
    nsh = plan.n_shards if plan is not None else 1
    cap = min(mesh_mod.chunk_cap(_MAX_CHUNK, _MIN_PAD), _LAUNCH_LANES * nsh)
    chunks = []
    for start, end, size in mesh_mod.shard_chunks(n, nsh, cap, _MIN_PAD):
        a_words = np.zeros((8, size), np.uint32)
        a_words[:, : end - start] = _le_words(pk_arr[start:end])
        if plan is not None:
            sh = NamedSharding(plan.mesh, PS(None, "batch"))
            a_dev = jax.device_put(a_words, sh)
        else:
            a_dev = jax.device_put(jnp.asarray(a_words))
        chunks.append((start, end, size, a_dev))

    rv = _ResidentValset()
    rv.chunks = chunks
    rv.plan = plan
    rv.pk_arr = pk_arr
    rv.pk_ok = pk_ok
    rv.n = n
    if plan is None and n > 0:
        # indexed gather table: pow2-padded rows so successive valsets
        # of similar size reuse the compiled executable. Multi-device
        # meshes skip it — the gather would need the full table
        # replicated per shard, so the sharded route keeps shipping keys.
        n_pad = 64
        while n_pad < n:
            n_pad *= 2
        table = np.zeros((n_pad, 32), np.uint8)
        table[:n] = pk_arr
        rv.table_dev = jax.device_put(jnp.asarray(table))
        rv.index = {
            pk_arr[i].tobytes(): i for i in range(n) if pk_ok[i]
        }
    else:
        rv.table_dev = None
        rv.index = {}
    return rv


def _parse_sigs(msgs, sigs):
    """→ (sig_arr u8[B,64], valid) of a resident or indexed launch:
    a lane is present where its signature is 64 bytes and its message
    is there (None = absent: zeros, masked), and valid where s < L
    besides. One join; the lengths are read as a set first, so the
    placeholder loop runs only for a launch that holds an absent or
    malformed lane."""
    n = len(msgs)
    valid = np.ones(n, bool)
    try:
        whole = set(map(len, sigs)) <= {64} and None not in msgs
    except TypeError:  # an absent lane's None signature
        whole = False
    if whole:
        sig_parts = sigs
    else:
        sig_parts = []
        for i in range(n):
            s = sigs[i]
            if s is None or msgs[i] is None or len(s) != 64:
                valid[i] = False
                sig_parts.append(b"\x00" * 64)
            else:
                sig_parts.append(s)
    sig_arr = np.frombuffer(b"".join(sig_parts), np.uint8).reshape(n, 64)
    valid &= _s_below_l(sig_arr[:, 32:])
    return sig_arr, valid


def _prepare_rsh(pk_arr: np.ndarray, msgs, sigs):
    """Per-commit host packing for one resident chunk: msgs[i]/sigs[i]
    None = absent lane (zeros, masked). → (rsh u32[24,B], valid)."""
    sig_arr, valid = _parse_sigs(msgs, sigs)
    h_arr = _challenge_scalars(pk_arr, sig_arr, msgs, valid)

    rsh = np.concatenate(
        [
            _le_words(sig_arr[:, :32]),
            _le_words(sig_arr[:, 32:]),
            _le_words(h_arr),
        ],
        axis=0,
    )
    return rsh, valid


def _prepare_rsh_compact(pk_arr: np.ndarray, msgs, sigs):
    """Compact per-flush staging for the indexed key-store path: same
    parse/hash as _prepare_rsh but packed as raw byte rows →
    (rsh u8[96,B]: rows 0:32 R, 32:64 S, 64:96 h, valid)."""
    sig_arr, valid = _parse_sigs(msgs, sigs)
    h_arr = _challenge_scalars(pk_arr, sig_arr, msgs, valid)
    rsh = pack_compact_rows(sig_arr[:, :32], sig_arr[:, 32:], h_arr)
    return rsh, valid


def resident_part(valset_id: bytes, pub_keys: Sequence[bytes], msgs, sigs):
    """The commit's lanes against the device-resident rows of
    ``pub_keys`` as one part of a stream (mesh.launch_parts): the rows'
    chunks are its launches, ``_prepare_rsh`` its build. ``msgs`` as
    verify_valset_resident takes it. → (part, the rows' shard plan or
    None: where the launches must run)."""
    from cometbft_tpu.crypto.tpu import mesh as mesh_mod
    from cometbft_tpu.libs import trace as tracelib

    n = len(pub_keys)
    streamed = callable(msgs)
    if len(sigs) != n or not (streamed or len(msgs) == n):
        raise ValueError("msgs/sigs must have one entry per validator")
    with tracelib.stage("commit.valset_id"):
        rv = _get_resident(valset_id, pub_keys)
    valid = np.ones(n, bool)
    fetched = []  # the messages of the launch that is next

    def fetch(chunk, start, end, inflight):
        built = tracelib.stage("commit.msgs_chunk", chunk=chunk,
                               lanes=end - start, inflight=inflight)
        with built:
            fetched.append(msgs(start, end))
        return built.seconds  # the wire ledger's fetch phase

    def build(start, end):
        rsh, ok = _prepare_rsh(
            rv.pk_arr[start:end],
            fetched.pop() if streamed else msgs[start:end],
            sigs[start:end],
        )
        valid[start:end] = ok & rv.pk_ok[start:end]
        return [rsh]

    # only the per-commit rsh staging is donated: the resident pubkey
    # rows lead the call and must survive across commits
    part = mesh_mod.StreamPart(
        curve="ed25519", kernel=verify_kernel_resident, donate_from=1,
        launches=rv.chunks, build=build, fetch=fetch if streamed else None,
        n=n, valid=valid,
    )
    return part, rv.plan


def verify_valset_resident(
    valset_id: bytes,
    pub_keys: Sequence[bytes],
    msgs,
    sigs: Sequence[Optional[bytes]],
) -> List[bool]:
    """Full-lane commit verification against a device-resident valset.

    pub_keys: EVERY validator key, in valset order; msgs/sigs: one entry
    per validator, None = absent (False in the result — callers skip
    absent lanes). ``msgs`` is that list, or a callable
    ``(start, end) -> msgs[start:end]`` that BUILDS the slice: it is
    asked once a launch, in order, when that launch is next, so the
    build runs while the device works on the launches before it.
    valset_id must be a collision-resistant digest of the ordered
    pub_keys (the caller computes sha256 over their concatenation); the
    resident rows are trusted to match it. Accept/reject per present
    lane is bit-identical to verify_batch."""
    if not pub_keys:
        return []
    part, plan = resident_part(valset_id, pub_keys, msgs, sigs)
    (mask,), _ = run_commit_parts([part], plan)
    return list(mask)


def run_commit_parts(parts, plan):
    """A commit's parts (resident_part, secp256k1_batch.stream_part) as
    one stream on the mesh the resident rows were placed on (``plan``,
    a mesh.ShardPlan, or None: the default chip). This path runs beside
    the scheduler (no flush, no supervisor), so the wire ledger is the
    only place its device lanes are on record, under the route
    ``resident`` (its flush record is verify_commit*'s own:
    wire.own_flush). → (a mask a part, the stream's phase totals)."""
    from cometbft_tpu.crypto.tpu import mesh as mesh_mod

    nsh = plan.n_shards if plan is not None else 1
    return mesh_mod.launch_parts(
        parts, where=plan.mesh if plan is not None else None,
        prefix="resident", route="resident",
        device_label=f"mesh:{nsh}" if nsh > 1 else "dev0",
    )
