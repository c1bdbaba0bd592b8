"""TPU batched secp256k1 — bit-identical parity with the CPU verifier.

The stretch companion to the ed25519 north-star kernel (SURVEY.md §2.1):
accept/reject from the JAX batch kernel must match
crypto/secp256k1.py's PubKeySecp256k1.verify_signature on valid,
corrupted, and adversarial edge-case signatures, including the low-S
malleability rule. Runs on the virtual CPU platform (conftest.py).
"""

import random

import numpy as np
import pytest

from cometbft_tpu.crypto import secp256k1 as secp
from cometbft_tpu.crypto.tpu import secp256k1_batch, secp_field as F


def _cpu_verify(pk: bytes, msg: bytes, sig: bytes) -> bool:
    return secp.PubKeySecp256k1(pk).verify_signature(msg, sig)


def _assert_parity(pks, msgs, sigs):
    got = secp256k1_batch.verify_batch(pks, msgs, sigs)
    want = [_cpu_verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    assert got == want, f"mismatch: tpu={got} cpu={want}"
    return got


class TestSecpField:
    def _fe1(self, n):
        import jax.numpy as jnp

        return jnp.array(F.int_to_limbs(n % F.P), jnp.int32)[:, None]

    def _val(self, x):
        return F.limbs_to_int(np.asarray(F.to_canonical(x))[:, 0])

    def test_ops_parity(self):
        rng = random.Random(7)
        for _ in range(15):
            a, b = rng.randrange(F.P), rng.randrange(F.P)
            fa, fb = self._fe1(a), self._fe1(b)
            assert self._val(F.add(fa, fb)) == (a + b) % F.P
            assert self._val(F.sub(fa, fb)) == (a - b) % F.P
            assert self._val(F.mul(fa, fb)) == (a * b) % F.P

    def test_chained_compositions_preserve_invariant(self):
        rng = random.Random(11)
        for trial in range(6):
            ints = [rng.randrange(F.P) for _ in range(6)]
            fes = [self._fe1(v) for v in ints]
            x, xi = fes[0], ints[0]
            for i in range(1, 6):
                op = (trial + i) % 3
                if op == 0:
                    x, xi = F.mul(x, fes[i]), xi * ints[i] % F.P
                elif op == 1:
                    x, xi = F.add(x, fes[i]), (xi + ints[i]) % F.P
                else:
                    x, xi = F.sub(x, fes[i]), (xi - ints[i]) % F.P
            assert self._val(x) == xi, trial

    def test_invert_and_sqrt(self):
        inv = F.invert(self._fe1(987654321))
        assert self._val(inv) * 987654321 % F.P == 1
        s = self._val(F.sqrt_candidate(self._fe1(9)))
        assert pow(s, 2, F.P) == 9

    def test_identity_chain_stays_bounded(self):
        """The radix-14 redesign exists exactly for this: long identity-
        doubling chains must not inflate limbs past the invariant."""
        import jax.numpy as jnp

        ident = tuple(
            jnp.broadcast_to(c, (F.NUM_LIMBS, 1))
            for c in (F.const_fe(0), F.const_fe(1), F.const_fe(0))
        )
        acc = ident
        for i in range(64):
            acc = secp256k1_batch.point_dbl(acc)
            assert self._val(acc[0]) == 0 and self._val(acc[2]) == 0, i
            m = max(int(np.abs(np.asarray(c)).max()) for c in acc)
            assert m < (1 << F.RADIX) + 4096, (i, m)


class TestSecpVerifyParity:
    @pytest.fixture(scope="class")
    def keys(self):
        return [secp.gen_priv_key() for _ in range(6)]

    def test_valid_and_corrupted(self, keys):
        pks, msgs, sigs = [], [], []
        for i, k in enumerate(keys):
            m = b"secp vote %d" % i
            s = bytearray(k.sign(m))
            if i % 3 == 1:
                s[10] ^= 1
            pks.append(k.pub_key().bytes())
            msgs.append(m)
            sigs.append(bytes(s))
        got = _assert_parity(pks, msgs, sigs)
        assert got[0] and not got[1]

    def test_wrong_key_and_message(self, keys):
        k1, k2 = keys[0], keys[1]
        m = b"proposal"
        sig = k1.sign(m)
        _assert_parity(
            [k2.pub_key().bytes(), k1.pub_key().bytes()],
            [m, b"other message"],
            [sig, sig],
        )

    def test_high_s_rejected(self, keys):
        """The low-S rule: flipping s to n - s keeps the curve equation
        satisfied but MUST be rejected (malleability)."""
        k = keys[0]
        m = b"malleable"
        sig = k.sign(m)
        r = sig[:32]
        s = int.from_bytes(sig[32:], "big")
        high = r + (F.N - s).to_bytes(32, "big")
        got = _assert_parity(
            [k.pub_key().bytes()] * 2, [m, m], [sig, high]
        )
        assert got == [True, False]

    def test_structural_garbage(self, keys):
        k = keys[0]
        m = b"m"
        good = k.sign(m)
        zero_r = bytes(32) + good[32:]
        zero_s = good[:32] + bytes(32)
        big_r = F.N.to_bytes(32, "big") + good[32:]
        bad_prefix = b"\x05" + k.pub_key().bytes()[1:]
        x_too_big = bytes([2]) + F.P.to_bytes(32, "big")
        not_on_curve = bytes([2]) + (5).to_bytes(32, "big")
        pks = [k.pub_key().bytes()] * 3 + [bad_prefix, x_too_big, not_on_curve]
        sigs = [zero_r, zero_s, big_r, good, good, good]
        got = _assert_parity(pks, [m] * 6, sigs)
        assert not any(got)

    def test_wrong_lengths_and_empty(self, keys):
        got = secp256k1_batch.verify_batch(
            [b"short", keys[0].pub_key().bytes()],
            [b"m", b"m"],
            [b"\x01" * 64, b"\x01" * 63],
        )
        assert got == [False, False]
        assert secp256k1_batch.verify_batch([], [], []) == []


class TestMixedCurveBatch:
    def test_partitioned_by_curve_through_boundary(self):
        """SURVEY §7 stage 10: one batch holding ed25519 AND secp keys,
        each partition on its own kernel, per-sig mask exact."""
        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.crypto.batch import TPUBatchVerifier

        bv = TPUBatchVerifier(min_batch=1, secp_min_batch=1)
        expect = []
        for i in range(4):
            k = ed.gen_priv_key_from_secret(bytes([i, 31]))
            m = b"ed %d" % i
            sig = k.sign(m) if i != 1 else b"\x0a" * 64
            bv.add(k.pub_key(), m, sig)
            expect.append(i != 1)
        for i in range(4):
            k = secp.gen_priv_key()
            m = b"secp %d" % i
            s = bytearray(k.sign(m))
            if i == 2:
                s[5] ^= 1
            bv.add(k.pub_key(), m, bytes(s))
            expect.append(
                secp.PubKeySecp256k1(k.pub_key().bytes()).verify_signature(
                    m, bytes(s)
                )
            )
        ok, mask = bv.verify()
        assert mask == expect
        assert not ok


class TestCompactWireUnpack:
    """Device-side unpack of the compact secp wire vs independent
    oracles — the wire is the dispatch ABI; a bit-slip corrupts every
    lane (same contract as the ed25519 unpack tests)."""

    def test_fe_limbs_match_int_oracle(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(17)
        raw = rng.integers(0, 256, size=(9, 32)).astype(np.uint8)
        words = jnp.asarray(secp256k1_batch._le_words(raw))
        got = np.asarray(secp256k1_batch.unpack_fe_limbs(words))
        for b in range(raw.shape[0]):
            val = int.from_bytes(raw[b].tobytes(), "little")
            assert F.limbs_to_int(got[:, b]) == val, b
            assert all(0 <= int(v) < 2**F.RADIX for v in got[:, b])
        # cross-check against the host limb oracle (expects BE bytes)
        want = F.bytes_be_to_limbs_np(raw[:, ::-1]).T
        assert (got == want).all()

    def test_digits_match_bit_oracle(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(19)
        raw = rng.integers(0, 256, size=(7, 32)).astype(np.uint8)
        words = jnp.asarray(secp256k1_batch._le_words(raw))
        got = np.asarray(secp256k1_batch.unpack_digits(words))
        bits = np.unpackbits(raw, axis=-1, bitorder="little")
        digits = bits[:, 0:256:2] + 2 * bits[:, 1:256:2]  # LSB-first pairs
        want = np.ascontiguousarray(digits[:, ::-1].astype(np.int32).T)
        assert (got == want).all()

    def test_flags_encode_parity_and_rn(self):
        k = secp.gen_priv_key()
        m = b"wire flags"
        sig = k.sign(m)
        pk = k.pub_key().bytes()
        wire, flags, valid = secp256k1_batch.prepare_batch(
            [pk], [m], [sig]
        )
        assert valid[0]
        assert wire.shape == (32, 1) and wire.dtype == np.uint32
        assert int(flags[0]) & 1 == pk[0] & 1
        r = int.from_bytes(sig[:32], "big")
        assert bool(int(flags[0]) & 2) == (r + F.N < F.P)
        # wire rows carry qx, r, u1, u2 as raw LE words
        qx = int.from_bytes(
            np.asarray(wire[0:8, 0]).astype("<u4").tobytes(), "little"
        )
        assert qx == int.from_bytes(pk[1:], "big")
        r_w = int.from_bytes(
            np.asarray(wire[8:16, 0]).astype("<u4").tobytes(), "little"
        )
        assert r_w == r


class TestBulkPack:
    """prepare_batch packs a launch in bulk (array checks, one native
    call for the scalars with one inversion): held to the per-lane pack
    it replaced, kept here as the oracle, on a launch with every kind of
    malformed lane, and the native scalars to the Python loop."""

    @staticmethod
    def _per_lane_pack(pub_keys, msgs, sigs):
        import hashlib

        n = len(pub_keys)
        valid = np.ones(n, bool)
        rows = np.zeros((4, n, 32), np.uint8)
        flags = np.zeros(n, np.int32)
        for i in range(n):
            pk, sig = pub_keys[i], sigs[i]
            if (sig is None or len(pk) != 33 or pk[0] not in (2, 3)
                    or len(sig) != 64):
                valid[i] = False
                continue
            x = int.from_bytes(pk[1:], "big")
            r = int.from_bytes(sig[:32], "big")
            s = int.from_bytes(sig[32:], "big")
            if x >= F.P or not (1 <= r < F.N) or not (1 <= s < F.N) \
                    or s > F.N // 2:
                valid[i] = False
                continue
            e = int.from_bytes(hashlib.sha256(msgs[i]).digest(), "big") % F.N
            w = pow(s, -1, F.N)
            for k, v in enumerate((x, r, e * w % F.N, r * w % F.N)):
                rows[k, i] = np.frombuffer(v.to_bytes(32, "little"), np.uint8)
            flags[i] = (pk[0] & 1) | (2 if r + F.N < F.P else 0)
        wire = np.concatenate(
            [secp256k1_batch._le_words(rows[k]) for k in range(4)], axis=0)
        return wire, flags, valid

    @pytest.fixture(scope="class")
    def launch(self):
        rng = random.Random(5)
        keys = [secp.gen_priv_key_from_secret(bytes([i])) for i in range(4)]
        pks, msgs, sigs = [], [], []
        for i in range(96):
            k = keys[i % 4]
            m = bytes(rng.randrange(256) for _ in range(rng.randrange(140)))
            sig = k.sign(m) if i < 24 else None
            if sig is None:  # signing is slow: random in-range scalars
                r = rng.randrange(1, F.N)
                s = rng.randrange(1, F.N // 2 + 1)
                sig = r.to_bytes(32, "big") + s.to_bytes(32, "big")
            pks.append(k.pub_key().bytes())
            msgs.append(m)
            sigs.append(sig)
        r0 = sigs[0][:32]
        plant = {
            30: (pks[30], b"\x05" + pks[30][1:], sigs[30]),
            31: (pks[31], b"\x02" + F.P.to_bytes(32, "big"), sigs[31]),
            32: (pks[32], pks[32], bytes(32) + sigs[32][32:]),
            33: (pks[33], pks[33], sigs[33][:32] + bytes(32)),
            34: (pks[34], pks[34], F.N.to_bytes(32, "big") + sigs[34][32:]),
            35: (pks[35], pks[35], r0 + (F.N // 2 + 1).to_bytes(32, "big")),
            36: (pks[36], pks[36], r0 + (F.N // 2).to_bytes(32, "big")),
            37: (pks[37], pks[37], r0 + (1).to_bytes(32, "big")),
            38: (pks[38], pks[38], (F.N - 1).to_bytes(32, "big") + sigs[38][32:]),
            39: (pks[39], pks[39], sigs[39][:63]),
            40: (pks[40], pks[40][:32], sigs[40]),
            41: (pks[41], pks[41], None),
        }
        for i, (_, pk, sig) in plant.items():
            pks[i], sigs[i] = pk, sig
        msgs[41] = None  # an absent lane: no message, no signature
        return pks, msgs, sigs

    def test_the_bulk_pack_is_the_per_lane_pack(self, launch):
        got = secp256k1_batch.prepare_batch(*launch)
        want = self._per_lane_pack(*launch)
        for g, w in zip(got, want):
            assert (np.asarray(g) == w).all()
        assert list(np.flatnonzero(~got[2])) == [30, 31, 32, 33, 34, 35,
                                                 39, 40, 41]

    def test_the_native_scalars_are_the_python_loops(self, launch):
        from cometbft_tpu import native

        pks, msgs, sigs = launch
        pk_arr, sig_arr, valid = secp256k1_batch._parse_inputs(pks, sigs)
        got = native.secp256k1_scalars(sig_arr, msgs, valid)
        if got is None:
            pytest.skip("no native library here")
        want = secp256k1_batch._scalars_py(sig_arr.tobytes(), msgs, valid)
        assert all((g == w).all() for g, w in zip(got, want))
