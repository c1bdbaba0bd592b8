"""Batched SHA-256 compression in JAX — uint32 lanes, no data-dependent
control flow; the whole batch is one fused XLA program.

Used by the TPU Merkle kernel (crypto/tpu/merkle.py): Merkle inner nodes
are fixed 65-byte messages (0x01 ‖ left ‖ right → two padded blocks), so
a batch of N node hashes is a [N, 32]-word tensor pushed through 128
rounds of uint32 arithmetic — ideal VPU shape, no MXU needed.

Reference baseline being replaced: crypto/tmhash (stdlib SHA-256, one
call at a time) under crypto/merkle/tree.go.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_K = np.array(
    [
        0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5,
        0x3956C25B, 0x59F111F1, 0x923F82A4, 0xAB1C5ED5,
        0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
        0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174,
        0xE49B69C1, 0xEFBE4786, 0x0FC19DC6, 0x240CA1CC,
        0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
        0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7,
        0xC6E00BF3, 0xD5A79147, 0x06CA6351, 0x14292967,
        0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
        0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85,
        0xA2BFE8A1, 0xA81A664B, 0xC24B8B70, 0xC76C51A3,
        0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
        0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5,
        0x391C0CB3, 0x4ED8AA4A, 0x5B9CCA4F, 0x682E6FF3,
        0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
        0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
    ],
    dtype=np.uint32,
)

_IV = np.array(
    [
        0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
        0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
    ],
    dtype=np.uint32,
)


def _rotr(x: jnp.ndarray, n: int) -> jnp.ndarray:
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _compress(
    state: jnp.ndarray, block: jnp.ndarray, k_arr: jnp.ndarray = None
) -> jnp.ndarray:
    """state u32[...,8], block u32[...,16] → u32[...,8].

    One fori_loop over the 64 rounds with the message schedule computed
    in-loop from a 16-word circular window. Unrolling the schedule (the
    textbook form) builds a deep × wide expression DAG that sends an XLA
    pass super-linear — a 64-entry unrolled schedule costs minutes of
    compile (measured: the fused Merkle kernel went 125 s → seconds with
    the windowed form); the loop form is the same arithmetic.
    """
    from jax import lax

    if k_arr is None:
        k_arr = jnp.asarray(_K)
    # window layout: [..., 16] so lanes stay on the batch axis
    win0 = block

    def round_fn(i, carry):
        vals, win = carry
        a, b, c, d, e, f, g, h = vals
        idx = i % 16
        # schedule word: for i < 16 the window still holds the block
        # word at idx; for i >= 16 extend the recurrence (writing the
        # selected word back is a value-level no-op for i < 16)
        w16 = win[..., idx]
        wm15 = win[..., (i - 15) % 16]
        wm7 = win[..., (i - 7) % 16]
        wm2 = win[..., (i - 2) % 16]
        s0 = _rotr(wm15, 7) ^ _rotr(wm15, 18) ^ (wm15 >> np.uint32(3))
        s1 = _rotr(wm2, 17) ^ _rotr(wm2, 19) ^ (wm2 >> np.uint32(10))
        ext = w16 + s0 + wm7 + s1
        w = jnp.where(i < 16, w16, ext)
        win = _set_last_axis(win, idx, w)

        s1e = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1e + ch + k_arr[i] + w
        s0a = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0a + maj
        return ((t1 + t2, a, b, c, d + t1, e, f, g), win)

    init = tuple(state[..., i] for i in range(8))
    (a, b, c, d, e, f, g, h), _ = lax.fori_loop(
        0, 64, round_fn, (init, win0)
    )
    return jnp.stack(
        [
            state[..., 0] + a, state[..., 1] + b, state[..., 2] + c,
            state[..., 3] + d, state[..., 4] + e, state[..., 5] + f,
            state[..., 6] + g, state[..., 7] + h,
        ],
        axis=-1,
    )


def _set_last_axis(arr: jnp.ndarray, idx, value: jnp.ndarray) -> jnp.ndarray:
    """arr[..., idx] = value with a traced idx (dynamic_update_slice on
    the minor axis)."""
    from jax import lax

    return lax.dynamic_update_index_in_dim(arr, value, idx, axis=-1)


@jax.jit
def _sha256_blocks_xla(blocks: jnp.ndarray) -> jnp.ndarray:
    state = jnp.broadcast_to(
        jnp.asarray(_IV), blocks.shape[:-2] + (8,)
    )
    for i in range(blocks.shape[-2]):  # fixed small count — unrolled
        state = _compress(state, blocks[..., i, :])
    return state


def sha256_blocks(blocks: jnp.ndarray) -> jnp.ndarray:
    """blocks u32[B, n_blocks, 16] (BE words of pre-padded messages)
    → digests u32[B, 8], as one fused XLA program."""
    return _sha256_blocks_xla(blocks)


def sha256_blocks_ragged(
    blocks: jnp.ndarray, n_live: jnp.ndarray
) -> jnp.ndarray:
    """blocks u32[B, n_blocks, 16], n_live int32[B] → digests u32[B, 8].

    Mixed-length batch: every lane runs all n_blocks compressions but
    keeps its state unchanged past its own live count — the branch-free
    way to hash ragged messages."""
    state = jnp.broadcast_to(jnp.asarray(_IV), blocks.shape[:-2] + (8,))
    for i in range(blocks.shape[-2]):  # small static count — unrolled
        new = _compress(state, blocks[..., i, :])
        live = (i < n_live)[..., None]
        state = jnp.where(live, new, state)
    return state


def pad_ragged_np(items, prefix: bytes = b""):
    """Variable-length messages (each prefixed) → one fixed-shape batch:
    (blocks u32[B, max_blocks, 16], n_live int32[B]). SHA-256 padding is
    baked in per message at its own length."""
    n = len(items)
    plen = len(prefix)
    lens = np.array([plen + len(m) for m in items], np.int64)
    nblocks = np.maximum((lens + 1 + 8 + 63) // 64, 1).astype(np.int32)
    max_blocks = int(nblocks.max()) if n else 1
    buf = np.zeros((n, max_blocks * 64), np.uint8)
    pre = np.frombuffer(prefix, np.uint8)
    for i, m in enumerate(items):
        ln = int(lens[i])
        if plen:
            buf[i, :plen] = pre
        buf[i, plen:ln] = np.frombuffer(bytes(m), np.uint8)
        buf[i, ln] = 0x80
        end = int(nblocks[i]) * 64
        buf[i, end - 8 : end] = np.frombuffer(
            (ln * 8).to_bytes(8, "big"), np.uint8
        )
    words = buf.reshape(n, max_blocks, 16, 4).astype(np.uint32)
    packed = (
        (words[..., 0] << 24) | (words[..., 1] << 16)
        | (words[..., 2] << 8) | words[..., 3]
    )
    return packed, nblocks


def pad_messages_np(msgs: np.ndarray, msg_len: int) -> np.ndarray:
    """uint8[B, msg_len] → u32[B, n_blocks, 16] with SHA-256 padding."""
    n = msgs.shape[0]
    total = ((msg_len + 8) // 64 + 1) * 64
    buf = np.zeros((n, total), np.uint8)
    buf[:, :msg_len] = msgs
    buf[:, msg_len] = 0x80
    bit_len = msg_len * 8
    buf[:, -8:] = np.frombuffer(
        bit_len.to_bytes(8, "big"), np.uint8
    )
    words = buf.reshape(n, total // 64, 16, 4)
    return (
        (words[..., 0].astype(np.uint32) << 24)
        | (words[..., 1].astype(np.uint32) << 16)
        | (words[..., 2].astype(np.uint32) << 8)
        | words[..., 3].astype(np.uint32)
    )


def digests_to_bytes_np(digests: np.ndarray) -> np.ndarray:
    """u32[B, 8] → uint8[B, 32] big-endian."""
    d = np.asarray(digests, np.uint32)
    out = np.zeros(d.shape[:-1] + (32,), np.uint8)
    for i in range(8):
        out[..., 4 * i] = d[..., i] >> 24
        out[..., 4 * i + 1] = (d[..., i] >> 16) & 0xFF
        out[..., 4 * i + 2] = (d[..., i] >> 8) & 0xFF
        out[..., 4 * i + 3] = d[..., i] & 0xFF
    return out
