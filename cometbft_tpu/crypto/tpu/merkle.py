"""TPU-parallel RFC-6962 Merkle root — the whole tree in ONE device call.

Reference: crypto/merkle/tree.go:9 HashFromByteSlices — recursive,
one stdlib SHA-256 call per node. Here the full reduction runs as a
single jitted program: leaf hashing (0x00 ‖ item, ragged lengths padded
host-side into per-lane block counts) AND every inner level — pairwise
SHA-256 over fixed 65-byte messages (0x01 ‖ left ‖ right) — happen
on-device with no host↔device round-trips anywhere. Level counts are
carried as a traced scalar over a fixed log2(P) level loop, with the odd
tail carried up unhashed, which reproduces the reference's
largest-power-of-two-split tree shape exactly for every n.

One compilation per (power-of-two padded size, leaf block count); lanes
beyond the live count compute garbage that is masked out, which costs
nothing on the VPU's fixed-width lanes. CBFT_TPU_MERKLE_LEAVES=host
falls back to hashlib leaf hashing (the round-3 design) for A/B timing.

Bit-identical to crypto.merkle.hash_from_byte_slices for every n
(tests/test_tpu_merkle.py parity suite).
"""

from __future__ import annotations

import hashlib
import os
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from cometbft_tpu.crypto.tpu import sha256 as tpu_sha

_LEAF_PREFIX = b"\x00"
_INNER_LEN = 65  # 0x01 || left32 || right32

# device becomes worth the round-trip above this many leaves. Round-5
# on-chip measurement: on the round-5 shared chip the device tree
# LOST at every size tried (10k leaves: 93.2 ms device vs 17.3 ms
# host — BENCH_onchip_probe.json tpu_p50) because the link's transfer
# cost dwarfed the compute; the routing stays opt-in
# (crypto.merkle.enable_parallel) and this floor is env-tunable for
# locally-attached TPUs where the round-trip is microseconds.
# legacy floor, superseded by device_wins() for routing — kept only as
# the documented default of the env knob (device_wins re-reads the env
# per call, so monkeypatched tests see changes immediately)
MIN_DEVICE_LEAVES = int(os.environ.get("CBFT_TPU_MERKLE_MIN_LEAVES", "128"))


def device_wins(n: int) -> bool:
    """Measurement-driven routing verdict for an n-leaf root: True only
    when the crossover table recorded at node warmup (tpu/calibrate.py)
    PROVED the device tree beats the host tree at this size on this
    machine. No table (fresh node, CPU-only CI) → False: the round-5
    measurement is that the shared chip LOST at every size, so
    unproven means host. An explicitly-set
    CBFT_TPU_MERKLE_MIN_LEAVES keeps operator precedence (e.g. a
    locally-attached TPU whose round-trip is microseconds)."""
    raw = os.environ.get("CBFT_TPU_MERKLE_MIN_LEAVES")
    if raw is not None:
        return n >= int(raw)
    from cometbft_tpu.crypto.tpu import calibrate

    floor = calibrate.merkle_min_leaves()
    return floor is not None and n >= floor
# device leaf hashing caps the per-item size (16 SHA blocks ≈ 1 KiB);
# larger items fall back to host-hashed leaves + device tree. The SHA
# message is prefix ‖ item ‖ 0x80-pad ‖ 8-byte length, so the prefix
# byte counts against the 16-block budget too
_MAX_DEVICE_LEAF_BYTES = 16 * 64 - 9 - len(_LEAF_PREFIX)


def _pad_pow2(n: int) -> int:
    size = 1
    while size < n:
        size *= 2
    return size


def _inner_blocks(left: jnp.ndarray, right: jnp.ndarray) -> jnp.ndarray:
    """left/right u32[B,8] digest words → u32[B,2,16] SHA-padded blocks of
    the 65-byte message 0x01 ‖ left ‖ right (big-endian packing shifted by
    the single prefix byte)."""
    u8 = np.uint32(0xFF)
    words = []
    # block 0: 0x01 then the first 63 message bytes
    words.append((jnp.uint32(0x01) << 24) | (left[..., 0] >> 8))
    for i in range(1, 8):
        words.append(((left[..., i - 1] & u8) << 24) | (left[..., i] >> 8))
    words.append(((left[..., 7] & u8) << 24) | (right[..., 0] >> 8))
    for i in range(1, 8):
        words.append(((right[..., i - 1] & u8) << 24) | (right[..., i] >> 8))
    block0 = jnp.stack(words, axis=-1)
    # block 1: last message byte, 0x80 terminator, zeros, 520-bit length
    zero = jnp.zeros_like(left[..., 0])
    w16 = ((right[..., 7] & u8) << 24) | jnp.uint32(0x80 << 16)
    tail = [w16] + [zero] * 14 + [jnp.full_like(zero, _INNER_LEN * 8)]
    block1 = jnp.stack(tail, axis=-1)
    return jnp.stack([block0, block1], axis=-2)


def _tree_reduce(a: jnp.ndarray, m0: jnp.ndarray, levels: int):
    """a u32[P,8] leaf digests (first m0 live), P = 2^levels → root u32[8].

    Each iteration halves the live count: hash the even/odd pairs, carry
    an odd tail unhashed. Runs exactly `levels` iterations; once the live
    count reaches 1 further iterations are identity (pairs = 0, the
    single root carries itself), so over-running is harmless."""
    m = m0.astype(jnp.int32)
    for _ in range(levels):
        # the array SHRINKS each level (static shapes, loop is unrolled):
        # total SHA work stays O(P) instead of O(P log P). The live count
        # m never exceeds the current width: m' = ceil(m/2) <= w/2.
        width = a.shape[0] // 2
        pairs = m - (m & 1)
        half = pairs // 2
        hashed = tpu_sha.sha256_blocks(
            _inner_blocks(a[0::2], a[1::2])
        )  # [w/2, 8]
        carried = jax.lax.dynamic_index_in_dim(
            a, jnp.maximum(m - 1, 0), axis=0, keepdims=False
        )
        idx = jnp.arange(width, dtype=jnp.int32)[:, None]
        a = jnp.where(
            idx < half,
            hashed,
            jnp.where(idx == half, carried[None, :], 0),
        )
        m = half + (m & 1)
    return a[0]


@partial(jax.jit, static_argnames=("levels",))
def _tree_kernel(digests: jnp.ndarray, m0: jnp.ndarray, levels: int):
    """Host-hashed-leaves path: digests u32[P,8] → root u32[8]."""
    return _tree_reduce(digests, m0, levels)


@partial(jax.jit, static_argnames=("levels",))
def _leaves_and_tree_kernel(
    blocks: jnp.ndarray,  # u32[P, n_blocks, 16] — padded 0x00‖item messages
    n_live: jnp.ndarray,  # int32[P] — per-lane live block counts
    m0: jnp.ndarray,
    levels: int,
):
    """The full root in one dispatch: ragged leaf SHA-256, then the
    tree reduction, with no host round-trip between them."""
    digests = tpu_sha.sha256_blocks_ragged(blocks, n_live)  # [P, 8]
    return _tree_reduce(digests, m0, levels)


def hash_from_byte_slices(
    items: Sequence[bytes], force_device: bool = False
) -> bytes:
    """Drop-in parallel replacement for
    crypto.merkle.hash_from_byte_slices (tree.go:9)."""
    import os

    n = len(items)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return hashlib.sha256(_LEAF_PREFIX + bytes(items[0])).digest()
    # routing goes through the measured verdict, not a constant: at the
    # round-5 sizes (10k leaves: 81.2 ms device vs 18.1 ms host) the
    # device path must LOSE the decision even when a caller reaches this
    # entry directly — only force_device (calibration's own sweep, A/B
    # probes) bypasses it. device_wins keeps operator precedence for an
    # explicitly-set CBFT_TPU_MERKLE_MIN_LEAVES.
    if not force_device and not device_wins(n):
        return _host_tree(
            [
                hashlib.sha256(_LEAF_PREFIX + bytes(item)).digest()
                for item in items
            ]
        )
    p = max(2, _pad_pow2(n))
    levels = p.bit_length() - 1
    device_leaves = (
        os.environ.get("CBFT_TPU_MERKLE_LEAVES", "device") == "device"
        # one oversized item would pad EVERY lane to its block count
        # (O(n·max_len) buffers + a fresh compile per max_blocks): leave
        # rare big-item sets — app-controlled DeliverTx results, say —
        # on the fixed-cost host-leaf path
        and max(len(it) for it in items) <= _MAX_DEVICE_LEAF_BYTES
    )
    if device_leaves:
        blocks, n_live = tpu_sha.pad_ragged_np(items, prefix=_LEAF_PREFIX)
        padded = np.zeros((p,) + blocks.shape[1:], np.uint32)
        padded[:n] = blocks
        live = np.zeros(p, np.int32)
        live[:n] = n_live
        root = _leaves_and_tree_kernel(padded, live, np.int32(n), levels)
    else:
        leaves = [
            hashlib.sha256(_LEAF_PREFIX + bytes(item)).digest()
            for item in items
        ]
        raw = np.frombuffer(b"".join(leaves), np.uint8).reshape(n, 8, 4)
        w = raw.astype(np.uint32)
        words = (
            (w[..., 0] << 24) | (w[..., 1] << 16) | (w[..., 2] << 8) | w[..., 3]
        )
        padded = np.zeros((p, 8), np.uint32)
        padded[:n] = words
        root = _tree_kernel(padded, np.int32(n), levels)
    return tpu_sha.digests_to_bytes_np(np.asarray(root)[None, :])[0].tobytes()


def _host_tree(level: list) -> bytes:
    """Small-n fallback: same reduction shape, hashlib on the host."""
    while len(level) > 1:
        nxt = [
            hashlib.sha256(b"\x01" + level[i] + level[i + 1]).digest()
            for i in range(0, len(level) - 1, 2)
        ]
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    return level[0]
