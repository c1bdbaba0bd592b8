"""The compact uint8 wire — the one program of a keyed ed25519 flush.

The compact format ships raw 32-byte little-endian encodings as uint8
rows and reconstructs u32 words on device (bytes_to_words) before the
limb-unpack / sign-extract / digit-window prologue. These tests pin the
property the whole hot path rests on: for any batch — across chunk
boundaries, non-canonical s, all-zero and all-ones rows — the on-device
words equal a host little-endian word view of the same bytes, and the
verdicts equal the CPU verifier's. Runs on the virtual CPU mesh
(conftest.py).
"""

import hashlib

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto.tpu import ed25519_batch as eb

# group order L: the canonical-s boundary
_L = 2**252 + 27742317777372353535851937790883648493


def _batch(n, tag=b"wf", corrupt_every=0):
    keys = [ed.gen_priv_key_from_secret(tag + b"-%d" % i) for i in range(n)]
    msgs = [b"wire format msg %d" % i for i in range(n)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    if corrupt_every:
        for i in range(0, n, corrupt_every):
            b = bytearray(sigs[i])
            b[7] ^= 1
            sigs[i] = bytes(b)
    return [k.pub_key().bytes() for k in keys], msgs, sigs


def _cpu(pks, msgs, sigs):
    return [
        ed.PubKeyEd25519(p).verify_signature(m, s)
        for p, m, s in zip(pks, msgs, sigs)
    ]


def _words_from_compact(wire_c):
    import jax.numpy as jnp

    return np.asarray(eb.bytes_to_words(jnp.asarray(wire_c)))


def _word_oracle(wire_c):
    """u32[rows/4, B]: each lane's byte rows read as little-endian u32
    words on the host (a ``"<u4"`` view), independent of the kernel."""
    lanes = np.ascontiguousarray(wire_c.T)  # u8[B, rows]
    return np.ascontiguousarray(lanes.view("<u4").T)


def _kernel_verdicts(pks, msgs, sigs):
    """(compact-kernel mask, valid) for one un-chunked dispatch, after
    checking that the device's words are the host oracle's."""
    import jax.numpy as jnp

    wire_c, valid_c = eb.prepare_batch_compact(pks, msgs, sigs)
    np.testing.assert_array_equal(
        _words_from_compact(wire_c), _word_oracle(wire_c)
    )
    got_c = np.asarray(eb.verify_kernel_compact(jnp.asarray(wire_c)))
    return got_c, valid_c


class TestWordReconstruction:
    """bytes_to_words(compact rows) must equal a host little-endian word
    view of the same bytes — the limb planes downstream are then the
    ones the words define."""

    def test_bit_identical_words(self):
        pks, msgs, sigs = _batch(17, corrupt_every=5)
        wire_c, valid_c = eb.prepare_batch_compact(pks, msgs, sigs)
        assert wire_c.dtype == np.uint8
        assert wire_c.shape == (128, 17)
        words = _words_from_compact(wire_c)
        assert words.shape == (32, 17)
        np.testing.assert_array_equal(words, _word_oracle(wire_c))
        # A's first word, from the key bytes themselves
        for lane in range(17):
            assert int(words[0, lane]) == int.from_bytes(
                pks[lane][:4], "little"
            )
        assert valid_c.all()

    def test_row_layout(self):
        # rows 0:32 A, 32:64 R, 64:96 S, 96:128 h — raw bytes,
        # lane-minor; h = SHA-512(R ‖ A ‖ M) mod L
        pks, msgs, sigs = _batch(3)
        wire_c, _ = eb.prepare_batch_compact(pks, msgs, sigs)
        for lane in range(3):
            assert wire_c[0:32, lane].tobytes() == pks[lane]
            assert wire_c[32:64, lane].tobytes() == sigs[lane][:32]
            assert wire_c[64:96, lane].tobytes() == sigs[lane][32:]
            h = int.from_bytes(
                hashlib.sha512(
                    sigs[lane][:32] + pks[lane] + msgs[lane]
                ).digest(),
                "little",
            ) % _L
            assert wire_c[96:128, lane].tobytes() == h.to_bytes(32, "little")


class TestVerdictParity:
    """The compact kernel's accept/reject mask (& valid) is the serial
    CPU verifier's."""

    def test_mixed_valid_invalid(self):
        pks, msgs, sigs = _batch(13, corrupt_every=4)
        got_c, valid = _kernel_verdicts(pks, msgs, sigs)
        want = _cpu(pks, msgs, sigs)
        assert list(got_c & valid) == want

    def test_non_canonical_s(self):
        # s' = s + L encodes the same residue but MUST reject (the CPU
        # path enforces canonical s); the wire carries the raw bytes
        pks, msgs, sigs = _batch(4, tag=b"noncanon")
        bad = list(sigs)
        for i in (1, 3):
            s_int = int.from_bytes(sigs[i][32:], "little")
            bad[i] = sigs[i][:32] + (s_int + _L).to_bytes(32, "little")
        got_c, valid = _kernel_verdicts(pks, msgs, bad)
        assert list(valid) == [True, False, True, False]
        assert list(got_c & valid) == _cpu(pks, msgs, bad)
        assert _cpu(pks, msgs, bad) == [True, False, True, False]

    def test_all_zero_and_all_ones_rows(self):
        # degenerate encodings: zero A (identity-adjacent y=0), zero
        # R/S, and 0xFF everywhere (y ≥ p, s ≥ L). No semantics asserted
        # beyond: the device's words are the host view of the bytes, and
        # nothing accepts that the CPU path rejects.
        pks = [b"\x00" * 32, b"\xff" * 32, b"\x00" * 32, b"\xff" * 32]
        sigs = [b"\x00" * 64, b"\xff" * 64, b"\xff" * 64, b"\x00" * 64]
        msgs = [b"z", b"o", b"zo", b"oz"]
        got_c, valid = _kernel_verdicts(pks, msgs, sigs)
        assert list(got_c & valid) == _cpu(pks, msgs, sigs)


class TestChunkedCompactDispatch:
    """verify_batch across chunk boundaries: the staged-prefetch
    reassembly must keep lane order and never smear a verdict onto a
    neighbor chunk."""

    @pytest.mark.parametrize("size", [63, 64, 65, 129])
    def test_boundary_sizes(self, size, monkeypatch):
        monkeypatch.setenv("CBFT_TPU_MAX_CHUNK", "64")
        pks, msgs, sigs = _batch(size, tag=b"chunk", corrupt_every=9)
        got = eb.verify_batch(pks, msgs, sigs)
        assert got == _cpu(pks, msgs, sigs)

    def test_chunked_agrees_with_one_dispatch(self, monkeypatch):
        pks, msgs, sigs = _batch(100, tag=b"agree", corrupt_every=7)
        got_one, valid = _kernel_verdicts(pks, msgs, sigs)
        monkeypatch.setenv("CBFT_TPU_MAX_CHUNK", "64")
        got_c = eb.verify_batch(pks, msgs, sigs)
        assert got_c == list(got_one & valid) == _cpu(pks, msgs, sigs)

    @pytest.mark.parametrize(
        "knob", ["CBFT_TPU_WIRE=words", "CBFT_TPU_HASH=device"]
    )
    def test_retired_knobs_leave_the_one_program(self, knob, monkeypatch):
        # a setting of the retired wire and hash knobs, left in an
        # operator's environment, changes nothing: verify_batch
        # dispatches the compact kernel and answers the CPU's verdicts
        from cometbft_tpu.crypto.tpu import mesh

        name, value = knob.split("=")
        monkeypatch.setenv(name, value)
        kernels = []
        real = mesh.dispatch_batch

        def spy(kernel, *a, **kw):
            kernels.append(kernel)
            return real(kernel, *a, **kw)

        monkeypatch.setattr(mesh, "dispatch_batch", spy)
        pks, msgs, sigs = _batch(9, tag=b"retired", corrupt_every=4)
        assert eb.verify_batch(pks, msgs, sigs) == _cpu(pks, msgs, sigs)
        assert kernels == [eb.verify_kernel_compact]
