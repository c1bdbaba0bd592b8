"""TPU Merkle kernel: bit-identical parity with the recursive CPU tree.

Model: reference crypto/merkle/tree_test.go (known-shape roots) plus the
CPU/TPU golden-parity discipline used for the ed25519 kernel.
"""

import hashlib

import numpy as np
import pytest

from cometbft_tpu.crypto import merkle as cpu_merkle
from cometbft_tpu.crypto.tpu import merkle as tpu_merkle
from cometbft_tpu.crypto.tpu import sha256 as tpu_sha


class TestJaxSha256:
    @pytest.mark.parametrize("msg_len", [0, 1, 32, 55, 56, 64, 65, 100, 119])
    def test_matches_hashlib(self, msg_len):
        rng = np.random.default_rng(msg_len)
        msgs = rng.integers(0, 256, (8, msg_len), dtype=np.uint8)
        blocks = tpu_sha.pad_messages_np(msgs, msg_len)
        digests = tpu_sha.digests_to_bytes_np(
            np.asarray(tpu_sha.sha256_blocks(blocks))
        )
        for i in range(8):
            want = hashlib.sha256(msgs[i].tobytes()).digest()
            assert digests[i].tobytes() == want, f"len={msg_len} row={i}"


class TestMerkleParity:
    def _leaves(self, n, seed=7):
        rng = np.random.default_rng(seed)
        # variable-length leaves like SimpleValidator encodings
        return [rng.bytes(int(rng.integers(1, 90))) for _ in range(n)]

    @pytest.mark.parametrize("n", list(range(0, 40)) + [63, 64, 65, 127, 128, 129, 400])
    def test_root_parity_all_shapes(self, n):
        leaves = self._leaves(n)
        want = cpu_merkle.hash_from_byte_slices(leaves)
        got = tpu_merkle.hash_from_byte_slices(leaves, force_device=True)
        assert got == want, f"n={n}"

    def test_mega_set_parity(self):
        """10k-leaf root (the mega-commit ValidatorSet.Hash case)."""
        leaves = self._leaves(10_000, seed=11)
        want = cpu_merkle.hash_from_byte_slices(leaves)
        got = tpu_merkle.hash_from_byte_slices(leaves, force_device=True)
        assert got == want

    def test_enable_parallel_routes_large_calls(self):
        leaves = self._leaves(300, seed=3)
        want = cpu_merkle.hash_from_byte_slices(leaves)
        cpu_merkle.enable_parallel(True)
        try:
            got = cpu_merkle.hash_from_byte_slices(leaves)
        finally:
            cpu_merkle.enable_parallel(False)
        assert got == want

    def test_validator_set_hash_parity(self):
        from cometbft_tpu.types import test_util

        vals, _ = test_util.deterministic_validator_set(150, 10)
        want = vals.hash()
        cpu_merkle.enable_parallel(True)
        try:
            got = vals.hash()
        finally:
            cpu_merkle.enable_parallel(False)
        assert got == want
