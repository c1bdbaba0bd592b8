"""Crypto layer tests (reference models: crypto/*/..._test.go)."""

import hashlib

import pytest

from cometbft_tpu.crypto import ed25519, secp256k1, sha256, tmhash
from cometbft_tpu.crypto.batch import CPUBatchVerifier, new_batch_verifier
from cometbft_tpu.crypto import merkle
from cometbft_tpu.crypto.ripemd160 import ripemd160


class TestEd25519:
    def test_sign_verify(self):
        priv = ed25519.gen_priv_key()
        pub = priv.pub_key()
        msg = b"sign me please"
        sig = priv.sign(msg)
        assert len(sig) == 64
        assert pub.verify_signature(msg, sig)
        assert not pub.verify_signature(b"other msg", sig)
        bad = bytearray(sig)
        bad[0] ^= 1
        assert not pub.verify_signature(msg, bytes(bad))

    def test_rfc8032_vector(self):
        # RFC 8032 §7.1 TEST 3
        seed = bytes.fromhex(
            "c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7"
        )
        pub = bytes.fromhex(
            "fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025"
        )
        msg = bytes.fromhex("af82")
        sig = bytes.fromhex(
            "6291d657deec24024827e69c3abe01a30ce548a284743a445e3680d7db5ac3ac"
            "18ff9b538d16f290ae67f760984dc6594a7c15e9716ed28dc027beceea1ec40a"
        )
        priv = ed25519.PrivKeyEd25519(seed)
        assert priv.pub_key().bytes() == pub
        assert priv.sign(msg) == sig
        assert priv.pub_key().verify_signature(msg, sig)

    def test_deterministic_keygen(self):
        a = ed25519.gen_priv_key_from_secret(b"secret")
        b = ed25519.gen_priv_key_from_secret(b"secret")
        assert a.bytes() == b.bytes()
        assert a.pub_key() == b.pub_key()

    def test_address_is_truncated_sha(self):
        priv = ed25519.gen_priv_key_from_secret(b"addr")
        pub = priv.pub_key()
        assert pub.address() == hashlib.sha256(pub.bytes()).digest()[:20]
        assert len(pub.address()) == 20

    def test_malformed_sig_len(self):
        priv = ed25519.gen_priv_key()
        assert not priv.pub_key().verify_signature(b"m", b"short")


class TestSecp256k1:
    def test_sign_verify(self):
        priv = secp256k1.gen_priv_key_from_secret(b"sec")
        pub = priv.pub_key()
        assert len(pub.bytes()) == 33
        msg = b"hello secp"
        sig = priv.sign(msg)
        assert len(sig) == 64
        assert pub.verify_signature(msg, sig)
        assert not pub.verify_signature(b"tampered", sig)

    def test_deterministic_signature(self):
        priv = secp256k1.gen_priv_key_from_secret(b"rfc6979")
        assert priv.sign(b"m") == priv.sign(b"m")

    def test_low_s_enforced(self):
        priv = secp256k1.gen_priv_key_from_secret(b"lows")
        sig = priv.sign(b"m")
        s = int.from_bytes(sig[32:], "big")
        n = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
        assert s <= n // 2
        # the high-S form of a valid sig must be rejected
        high = sig[:32] + (n - s).to_bytes(32, "big")
        assert not priv.pub_key().verify_signature(b"m", high)

    def test_address_len(self):
        pub = secp256k1.gen_priv_key_from_secret(b"a").pub_key()
        assert len(pub.address()) == 20


class TestRipemd160:
    def test_vectors(self):
        # standard RIPEMD-160 test vectors (Dobbertin et al.)
        assert ripemd160(b"").hex() == "9c1185a5c5e9fc54612808977ee8f548b2258d31"
        assert (
            ripemd160(b"abc").hex() == "8eb208f7e05d987a9b044a8e98c6b087f15a0bfc"
        )
        assert (
            ripemd160(b"message digest").hex()
            == "5d0689ef49d2fae572b881b123a85ffa21595f36"
        )
        assert (
            ripemd160(b"a" * 1000000).hex()
            == "52783243c1697bdbe16d37f97f68f08325dc1528"
        )


class TestMerkle:
    def test_rfc6962_empty_and_leaf(self):
        # RFC 6962 test vectors (same layout as reference tree.go)
        assert merkle.hash_from_byte_slices([]) == hashlib.sha256(b"").digest()
        assert (
            merkle.leaf_hash(b"").hex()
            == "6e340b9cffb37a989ca544e6bb780a2c78901d3fb33738768511a30617afa01d"
        )
        assert (
            merkle.hash_from_byte_slices([b"L123456"]).hex()
            == "395aa064aa4c29f7010acfe3f25db9485bbd4b91897b6ad7ad547639252b4d56"
        )

    def test_inner_split(self):
        items = [b"a", b"b", b"c"]
        root = merkle.hash_from_byte_slices(items)
        l = merkle.inner_hash(merkle.leaf_hash(b"a"), merkle.leaf_hash(b"b"))
        expect = merkle.inner_hash(l, merkle.leaf_hash(b"c"))
        assert root == expect

    @pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 7, 8, 9, 33, 100])
    def test_proofs(self, n):
        items = [bytes([i]) * 3 for i in range(n)]
        root, proofs = merkle.proofs_from_byte_slices(items)
        assert root == merkle.hash_from_byte_slices(items)
        for i, p in enumerate(proofs):
            p.verify(root, items[i])
            with pytest.raises(ValueError):
                p.verify(root, b"wrong leaf")
        # cross-proof misuse: proof i must not verify item j
        if n >= 2:
            with pytest.raises(ValueError):
                proofs[0].verify(root, items[1])

    def test_split_point(self):
        assert merkle.get_split_point(2) == 1
        assert merkle.get_split_point(3) == 2
        assert merkle.get_split_point(8) == 4
        assert merkle.get_split_point(9) == 8


class TestBatchVerifier:
    def _mk(self, n, bad=()):
        triples = []
        for i in range(n):
            priv = ed25519.gen_priv_key_from_secret(f"k{i}".encode())
            msg = f"msg {i}".encode()
            sig = priv.sign(msg)
            if i in bad:
                sig = sig[:-1] + bytes([sig[-1] ^ 1])
            triples.append((priv.pub_key(), msg, sig))
        return triples

    def test_cpu_all_valid(self):
        bv = CPUBatchVerifier()
        for pk, m, s in self._mk(16):
            bv.add(pk, m, s)
        assert bv.count() == 16
        ok, mask = bv.verify()
        assert ok and mask == [True] * 16
        assert bv.count() == 0  # reset

    def test_cpu_mixed_validity(self):
        bv = CPUBatchVerifier()
        for pk, m, s in self._mk(8, bad={2, 5}):
            bv.add(pk, m, s)
        ok, mask = bv.verify()
        assert not ok
        assert [i for i, v in enumerate(mask) if not v] == [2, 5]

    def test_empty_batch(self):
        ok, mask = CPUBatchVerifier().verify()
        assert not ok and mask == []

    def test_mixed_key_types(self):
        bv = CPUBatchVerifier()
        e = ed25519.gen_priv_key_from_secret(b"e")
        s = secp256k1.gen_priv_key_from_secret(b"s")
        bv.add(e.pub_key(), b"m1", e.sign(b"m1"))
        bv.add(s.pub_key(), b"m2", s.sign(b"m2"))
        ok, mask = bv.verify()
        assert ok and mask == [True, True]

    def test_registry(self):
        assert isinstance(new_batch_verifier("cpu"), CPUBatchVerifier)
        with pytest.raises(ValueError):
            new_batch_verifier("quantum")

    def test_verify_many_parity_with_serial(self):
        # fast loop / native call must be bit-identical to verify_signature,
        # including malformed sig and pubkey shapes
        triples = self._mk(100, bad={3, 71})
        pk0, m0, s0 = triples[0]
        triples[10] = (pk0, m0, s0[:40])           # short sig
        triples[11] = (ed25519.PubKeyEd25519(b"\xff" * 32), m0, s0)
        expected = [pk.verify_signature(m, s) for pk, m, s in triples]
        assert ed25519.verify_many(triples) == expected

    def test_native_verify_batch_parity(self):
        from cometbft_tpu import native

        triples = self._mk(80, bad={1, 40})
        mask = native.ed25519_verify_batch(
            [pk.bytes() for pk, _, _ in triples],
            [m for _, m, _ in triples],
            [s for _, _, s in triples],
            nthreads=4,
        )
        if mask is None:
            pytest.skip("native verifier unavailable (no toolchain/libcrypto)")
        expected = [pk.verify_signature(m, s) for pk, m, s in triples]
        assert mask == expected



    # (n, seed, longest message, share of absent lanes): the first is
    # the original case; the others cross the old 256-lane gate, a
    # thread's grain and an odd count; the message lengths include
    # 64 + len = 111, 112, 127, 128, 239, 240 (SHA-512's padding edges)
    @pytest.mark.parametrize(
        "n,seed,max_len,absent",
        [
            (120, 5, 150, 0.15),
            (1, 11, 300, 0.0),
            (255, 12, 300, 0.1),
            (256, 13, 300, 0.1),
            (2048, 14, 300, 0.1),
            (8193, 15, 300, 0.05),
            (64, 16, 300, 1.0),
        ],
    )
    def test_native_challenges_parity(self, n, seed, max_len, absent):
        """cbft_ed25519_challenges vs the hashlib + big-int oracle,
        including skipped (absent) lanes, all lanes absent and empty
        messages, on every thread count from 1 to the usable cores."""
        import hashlib
        import os
        import random

        import numpy as np

        from cometbft_tpu import native

        L = 2**252 + 27742317777372353535851937790883648493
        rng = random.Random(seed)
        edges = [47, 48, 63, 64, 175, 176, 0]
        pk = np.frombuffer(rng.randbytes(n * 32), np.uint8).reshape(n, 32)
        r = np.frombuffer(rng.randbytes(n * 32), np.uint8).reshape(n, 32)
        valid = np.array([rng.random() >= absent for _ in range(n)])
        msgs = [
            rng.randbytes(
                edges[i] if i < len(edges) else rng.randrange(0, max_len + 1)
            )
            if v
            else None
            for i, v in enumerate(valid)
        ]
        want = np.zeros((n, 32), np.uint8)
        for i in range(n):
            if valid[i]:
                h = int.from_bytes(
                    hashlib.sha512(
                        r[i].tobytes() + pk[i].tobytes() + msgs[i]
                    ).digest(),
                    "little",
                ) % L
                want[i] = np.frombuffer(h.to_bytes(32, "little"), np.uint8)
        for threads in range(1, len(os.sched_getaffinity(0)) + 1):
            got = native.ed25519_challenges(pk, r, msgs, valid, threads)
            if got is None:
                pytest.skip("native challenges unavailable")
            assert got.tobytes() == want.tobytes(), threads

    def test_native_challenges_refuse_a_valid_lane_without_message(self):
        import numpy as np

        from cometbft_tpu import native

        if native.load_challenges() is None:
            pytest.skip("native challenges unavailable")
        rows = np.zeros((3, 32), np.uint8)
        msgs = [b"a", None, b"c"]
        assert native.ed25519_challenges(
            rows, rows, msgs, np.array([1, 1, 1], np.uint8)
        ) is None
        got = native.ed25519_challenges(
            rows, rows, msgs, np.array([True, False, True])
        )
        assert got is not None and not got[1].any()

    @pytest.mark.parametrize(
        "digest",
        ["0", "L-1", "L", "L+1", "2L", "2^512-1", "2^252", "random"],
    )
    def test_native_sc_reduce64(self, digest):
        """The C reduction alone (test-only entry cbft_sc_reduce64)
        against Python's big-int mod L."""
        import ctypes
        import random

        from cometbft_tpu import native

        L = 2**252 + 27742317777372353535851937790883648493
        lib = native.load_ed25519()
        fn = getattr(lib, "cbft_sc_reduce64", None) if lib else None
        if fn is None:
            pytest.skip("native library unavailable")
        fn.restype = None
        fn.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t]
        named = {
            "0": 0, "L-1": L - 1, "L": L, "L+1": L + 1, "2L": 2 * L,
            "2^512-1": 2**512 - 1, "2^252": 2**252,
        }
        if digest == "random":
            rng = random.Random(37)
            vals = [rng.getrandbits(512) for _ in range(20000)] + [
                k * L + d for k in range(1, 64) for d in (-1, 0, 1)
            ]
        else:
            vals = [named[digest]]
        out = ctypes.create_string_buffer(32 * len(vals))
        fn(b"".join(v.to_bytes(64, "little") for v in vals), out, len(vals))
        want = b"".join((v % L).to_bytes(32, "little") for v in vals)
        assert out.raw == want

    def test_floor_says_where_lanes_ran(self):
        """The tpu backend reports where a verify()'s lanes actually ran,
        so nothing the floor keeps on the host is counted as a device
        dispatch; verdicts are the same either side of the floor."""
        from cometbft_tpu.crypto import batch as cryptobatch

        for floor, host, device in ((1, 0, 8), (9, 8, 0)):
            bv = cryptobatch.TPUBatchVerifier(
                min_batch=floor, slow_curve_min_batch=1, secp_min_batch=1
            )
            items = self._mk(8, bad={2})
            for pk, m, s in items:
                bv.add(pk, m, s)
            ok, mask = bv.verify()
            assert not ok
            assert [i for i, v in enumerate(mask) if not v] == [2]
            assert (bv.host_lanes, bv.device_lanes) == (host, device)
            pks = [pk for pk, _, _ in items]
            spec = cryptobatch.BackendSpec("tpu", min_batch=floor)
            assert cryptobatch.clears_device_floor(pks, spec) == (device > 0)

    def test_tpu_backend_without_tpu_is_a_startup_error(self, monkeypatch):
        """[crypto] backend = "tpu" where jax found no TPU fails start-up
        with a message naming the platform found, unless JAX_PLATFORMS
        asks for cpu FIRST (how these tests get the virtual mesh). A
        ``tpu,cpu`` list that resolved to cpu is jax falling back — the
        chip is held or libtpu is missing — and is refused like no
        variable at all."""
        from cometbft_tpu.crypto.tpu import mesh

        assert mesh.require_accelerator("test")["platform"] == "cpu"
        monkeypatch.setenv("JAX_PLATFORMS", "cpu,tpu")
        assert mesh.require_accelerator("test")["n_devices"] >= 1
        for fell_back in (None, "tpu,cpu", "tpu"):
            if fell_back is None:
                monkeypatch.delenv("JAX_PLATFORMS")
            else:
                monkeypatch.setenv("JAX_PLATFORMS", fell_back)
            with pytest.raises(RuntimeError, match="found platform 'cpu'"):
                mesh.require_accelerator('[crypto] backend = "tpu"')


class TestHashers:
    def test_tmhash(self):
        assert tmhash.sum(b"x") == hashlib.sha256(b"x").digest()
        assert tmhash.sum_truncated(b"x") == hashlib.sha256(b"x").digest()[:20]
        assert sha256(b"") == hashlib.sha256(b"").digest()

