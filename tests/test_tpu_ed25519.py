"""TPU batched ed25519 — bit-identical parity with the CPU verifier.

The north-star contract (BASELINE.json): accept/reject from the JAX batch
kernel must match the serial CPU path (crypto/ed25519/ed25519.go:148
semantics) on valid, corrupted, and adversarial edge-case signatures.
Runs on the virtual 8-device CPU mesh (conftest.py).
"""

import numpy as np
import pytest

from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto.tpu import ed25519_batch, field as fe, keystore


def _cpu_verify(pk: bytes, msg: bytes, sig: bytes) -> bool:
    return ed.PubKeyEd25519(pk).verify_signature(msg, sig)


def _assert_parity(pks, msgs, sigs):
    got = ed25519_batch.verify_batch(pks, msgs, sigs)
    want = [_cpu_verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    assert got == want, f"mismatch: tpu={got} cpu={want}"
    return got


def _fe1(n: int):
    """One field element in the kernel's limb-major [17, 1] layout."""
    import jax.numpy as jnp

    return jnp.array(fe.int_to_limbs(n), jnp.int32)[:, None]


def _fe_int(x) -> int:
    return fe.limbs_to_int(np.asarray(fe.to_canonical(x))[:, 0])


class TestField:
    def test_roundtrip_and_ops(self):
        rng = np.random.default_rng(7)

        for _ in range(20):
            a = int(rng.integers(0, 2**63)) * int(rng.integers(0, 2**63)) % fe.P
            b = int(rng.integers(0, 2**63)) ** 3 % fe.P
            fa, fb = _fe1(a), _fe1(b)
            assert _fe_int(fe.add(fa, fb)) == (a + b) % fe.P
            assert _fe_int(fe.sub(fa, fb)) == (a - b) % fe.P
            assert _fe_int(fe.mul(fa, fb)) == (a * b) % fe.P

    def test_invert(self):
        a = 0xDEADBEEFCAFEBABE1234567890ABCDEF
        inv = _fe_int(fe.invert(_fe1(a)))
        assert a * inv % fe.P == 1

    def test_pow_p58(self):
        a = 0x1234567890ABCDEF ** 3 % fe.P
        got = _fe_int(fe.pow_p58(_fe1(a)))
        assert got == pow(a, (fe.P - 5) // 8, fe.P)

    def test_weak_input_canonicalized(self):
        # value p + 5 in limbs (non-canonical but weakly reduced)
        assert _fe_int(_fe1(fe.P + 5)) == 5

    @pytest.mark.parametrize("impl", sorted(fe._MUL_IMPLS))
    def test_every_mul_impl_matches_oracle(self, impl):
        """All CBFT_TPU_MUL forms must agree with the big-int oracle —
        the TPU default (stack) and the f32 form otherwise run only on
        hardware, never under CI's CPU-platform default (matmul)."""
        mul = fe._MUL_IMPLS[impl]
        rng = np.random.default_rng(impl.encode()[0])
        for _ in range(8):
            a = int(rng.integers(0, 2**63)) ** 5 % fe.P
            b = int(rng.integers(0, 2**63)) ** 7 % fe.P
            got = _fe_int(mul(_fe1(a), _fe1(b)))
            assert got == a * b % fe.P, impl
        # chained squarings push the weakly-reduced (non-canonical)
        # intermediate representation through each impl's bound analysis
        x = _fe1(fe.P - 2)
        for _ in range(6):
            x = mul(x, x)
        assert _fe_int(x) == pow(fe.P - 2, 2**6, fe.P), impl


_TOP = 2**15 + 127  # the invariant's upper edge; -4 is its lower

# raw limb vectors at the invariant's corners, which _fe1 never produces
# (it builds canonical 15-bit limbs)
_SQ_CORNERS = {
    "all_top": [_TOP] * 17,
    "all_bottom": [-4] * 17,
    "alternating": [_TOP, -4] * 8 + [_TOP],
    "alternating_from_bottom": [-4, _TOP] * 8 + [-4],
    "limb0_top_rest_2^15": [_TOP] + [2**15] * 16,
}


def _raw(limbs):
    import jax.numpy as jnp

    return jnp.array(limbs, jnp.int32)[:, None]


def _raw_int(x) -> int:
    """The integer a (possibly redundant, signed) limb vector stands for."""
    return fe.limbs_to_int(np.asarray(x)[:, 0])


@pytest.mark.parametrize("impl", sorted(fe._SQ_IMPLS))
class TestSquareForms:
    """field.sq takes a square from its 153 distinct limb products; every
    form CBFT_TPU_MUL can select has one, and each must give what the
    product of that form gives — the TPU's default (stack) otherwise
    runs only on hardware."""

    def test_matches_oracle_on_random_elements(self, impl):
        sq = fe._SQ_IMPLS[impl]
        rng = np.random.default_rng(impl.encode()[0] + 1)
        for _ in range(8):
            a = int(rng.integers(0, 2**63)) ** 5 % fe.P
            assert _fe_int(sq(_fe1(a))) == a * a % fe.P, impl

    @pytest.mark.parametrize("corner", sorted(_SQ_CORNERS))
    def test_invariant_corners(self, impl, corner):
        """Limbs at the edges of [-4, 2^15 + 127]. A square that doubles
        an OPERAND (2·a_i) or a PRODUCT before the split fails here:
        2 · (2^15 + 127)^2 > 2^31 wraps int32; the weight 2 belongs on
        the 15-bit parts."""
        a = _raw(_SQ_CORNERS[corner])
        got = fe._SQ_IMPLS[impl](a)
        assert _raw_int(got) % fe.P == _raw_int(a) ** 2 % fe.P, (impl, corner)
        limbs = np.asarray(got)
        assert limbs.min() >= -4 and limbs.max() <= _TOP, (impl, corner)

    def test_equals_the_forms_own_product(self, impl):
        """sq(a) and mul(a, a) of one form: the same columns, so the same
        limbs after to_canonical (and before: the sums are one integer)."""
        sq, mul = fe._SQ_IMPLS[impl], fe._MUL_IMPLS[impl]
        rng = np.random.default_rng(17)
        cases = [_raw(c) for c in _SQ_CORNERS.values()] + [
            _raw(rng.integers(-4, _TOP + 1, 17)) for _ in range(8)
        ]
        for a in cases:
            s, m = sq(a), mul(a, a)
            assert np.array_equal(np.asarray(s), np.asarray(m)), impl
            assert np.array_equal(
                np.asarray(fe.to_canonical(s)), np.asarray(fe.to_canonical(m))
            ), impl

    def test_chained_squarings(self, impl):
        sq = fe._SQ_IMPLS[impl]
        x = _fe1(fe.P - 2)
        for _ in range(20):
            x = sq(x)
        assert _fe_int(x) == pow(fe.P - 2, 2**20, fe.P), impl

    def test_invert_and_pow_p58(self, impl, monkeypatch):
        monkeypatch.setenv("CBFT_TPU_MUL", impl)
        a = 0xDEADBEEFCAFEBABE1234567890ABCDEF ** 2 % fe.P
        assert a * _fe_int(fe.invert(_fe1(a))) % fe.P == 1, impl
        assert _fe_int(fe.pow_p58(_fe1(a))) == pow(
            a, (fe.P - 5) // 8, fe.P
        ), impl


def test_sq_and_mul_dispatch_on_one_name(monkeypatch):
    """CBFT_TPU_MUL names the form of the product AND of the square; a
    form has both or the name is refused."""
    assert sorted(fe._SQ_IMPLS) == sorted(fe._MUL_IMPLS)
    seen = []
    monkeypatch.setitem(fe._SQ_IMPLS, "shift_add", lambda a: seen.append("sq") or a)
    monkeypatch.setitem(fe._MUL_IMPLS, "shift_add", lambda a, b: seen.append("mul") or a)
    monkeypatch.setenv("CBFT_TPU_MUL", "shift_add")
    fe.sq(_fe1(3))
    fe.mul(_fe1(3), _fe1(5))
    assert seen == ["sq", "mul"]
    monkeypatch.setenv("CBFT_TPU_MUL", "no_such_form")
    for call in (lambda: fe.sq(_fe1(3)), lambda: fe.mul(_fe1(3), _fe1(5))):
        with pytest.raises(ValueError, match="no_such_form"):
            call()


class _FieldOpCount:
    """Counts field.sq / field.mul call sites while a kernel is traced,
    with the loop trip counts applied: lax.fori_loop runs its body once,
    in Python, under the loop's trip count as a multiplier. Phases as
    benchmark/opcount.py has them: decompress() is decompress, the
    127-step loop the ladder, invert() and what follows it encode, the
    rest the table."""

    def __init__(self, monkeypatch, refuse_mul_of_one_operand=False):
        from jax import lax

        self.counts = {}
        self._mult = 1
        self._phase = []
        self._after_invert = False
        self._refuse = refuse_mul_of_one_operand
        real_sq, real_mul = fe.sq, fe.mul
        real_invert, real_decompress = fe.invert, ed25519_batch.decompress

        def per_lane(*operands):
            # [17, 1] operands are constants (the four table entries
            # that hold multiples of B alone): XLA folds them, no lane
            # runs them, and opcount.py leaves them out
            return any(x.shape[-1] != 1 for x in operands)

        def sq(a):
            if per_lane(a):
                self._note("sq")
            return real_sq(a)

        def mul(a, b):
            if self._refuse:
                assert a is not b, "a call site squares through field.mul"
            if per_lane(a, b):
                self._note("mul")
            return real_mul(a, b)

        def fori_loop(lower, upper, body, init):
            ladder = upper - lower == ed25519_batch.NUM_DIGITS
            if ladder:
                self._phase.append("ladder")
            self._mult *= upper - lower
            try:
                return body(lower, init)
            finally:
                self._mult //= upper - lower
                if ladder:
                    self._phase.pop()

        def phased(name, fn):
            def wrapped(*args):
                self._phase.append(name)
                try:
                    return fn(*args)
                finally:
                    self._phase.pop()
                    self._after_invert |= name == "encode"
            return wrapped

        monkeypatch.setattr(fe, "sq", sq)
        monkeypatch.setattr(fe, "mul", mul)
        monkeypatch.setattr(lax, "fori_loop", fori_loop)
        monkeypatch.setattr(fe, "invert", phased("encode", real_invert))
        monkeypatch.setattr(
            ed25519_batch, "decompress", phased("decompress", real_decompress)
        )

    def _note(self, op):
        phase = self._phase[-1] if self._phase else (
            "encode" if self._after_invert else "table"
        )
        key = (phase, op)
        self.counts[key] = self.counts.get(key, 0) + self._mult

    def total(self, op):
        return sum(n for (_, o), n in self.counts.items() if o == op)

    def phase(self, name):
        return sum(n for (p, _), n in self.counts.items() if p == name)


def _trace_ed25519_lane_program():
    import jax
    import jax.numpy as jnp

    fe_shape = jax.ShapeDtypeStruct((fe.NUM_LIMBS, 8), jnp.int32)
    bit = jax.ShapeDtypeStruct((8,), jnp.int32)
    digits = jax.ShapeDtypeStruct((ed25519_batch.NUM_DIGITS, 8), jnp.int32)
    # a fresh callable: eval_shape caches a function's trace, and the
    # count is taken while tracing
    jax.eval_shape(
        lambda *args: ed25519_batch._verify_unpacked(*args),
        fe_shape, bit, fe_shape, bit, digits, digits,
    )


class TestSquareEngagement:
    """There is no path beside field.sq to count at run time: every
    ed25519 lane runs it wherever the program squares. What can be held
    is that no call site squares through field.mul, and how many of a
    lane's field multiplications are squarings."""

    def test_no_ed25519_call_site_squares_through_mul(self, monkeypatch):
        counter = _FieldOpCount(monkeypatch, refuse_mul_of_one_operand=True)
        _trace_ed25519_lane_program()
        assert counter.total("sq") and counter.total("mul")

    def test_no_sr25519_call_site_squares_through_mul(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        from cometbft_tpu.crypto.tpu import sr25519_batch

        counter = _FieldOpCount(monkeypatch, refuse_mul_of_one_operand=True)
        jax.eval_shape(
            lambda wire: sr25519_batch._verify_core(wire),
            jax.ShapeDtypeStruct((32, 8), jnp.uint32),
        )
        assert counter.total("sq") and counter.total("mul")

    def test_a_lanes_squarings_and_products_are_opcounts(self, monkeypatch):
        """1,529 squarings + 2,171 products = the 3,700 field
        multiplications benchmark/opcount.py counts a lane, phase by
        phase (that file counts a squaring as a full multiplication, by
        design: it is the roofline's yardstick, read here, not edited)."""
        from benchmark import opcount

        counter = _FieldOpCount(monkeypatch)
        _trace_ed25519_lane_program()
        want = opcount.field_muls()
        assert {p: counter.phase(p) for p in want} == want
        squarings = {
            p: counter.counts.get((p, "sq"), 0) for p in want
        }
        assert squarings == {
            "decompress": 251 + 4,
            "table": 4,
            "ladder": opcount.LADDER_STEPS * 2 * 4,
            "encode": 254,
        }
        assert counter.total("sq") == 1529
        assert counter.total("mul") == 2171
        assert counter.total("sq") + counter.total("mul") == sum(want.values())


class TestWireUnpack:
    """Device-side unpack of the compact u32 wire vs independent numpy
    oracles — the wire format is the dispatch ABI, so a silent bit-slip
    here would corrupt every lane."""

    def test_fe_limbs_match_int_oracle(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(11)
        raw = rng.integers(0, 256, size=(9, 32)).astype(np.uint8)
        words = jnp.asarray(ed25519_batch._le_words(raw))
        got = np.asarray(ed25519_batch.unpack_fe_limbs(words))
        for b in range(raw.shape[0]):
            val = int.from_bytes(raw[b].tobytes(), "little") & ((1 << 255) - 1)
            assert fe.limbs_to_int(got[:, b]) == val, b
            assert all(0 <= int(v) < 2**15 for v in got[:, b])

    def test_digits_match_bit_oracle(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(13)
        raw = rng.integers(0, 256, size=(7, 32)).astype(np.uint8)
        words = jnp.asarray(ed25519_batch._le_words(raw))
        got = np.asarray(ed25519_batch.unpack_digits(words))
        bits = np.unpackbits(raw, axis=-1, bitorder="little")
        digits = bits[:, 0:254:2] + 2 * bits[:, 1:254:2]
        want = np.ascontiguousarray(digits[:, ::-1].astype(np.int32).T)
        assert (got == want).all()

    def test_sign_bits_through_production_unpack(self):
        import jax.numpy as jnp

        pk = np.zeros((2, 32), np.uint8)
        pk[1, 31] = 0x80  # A sign bit set on lane 1
        r = np.zeros((2, 32), np.uint8)
        r[0, 31] = 0x80  # R sign bit set on lane 0
        zero = np.zeros((2, 32), np.uint8)
        wire = jnp.asarray(
            np.concatenate(
                [ed25519_batch._le_words(a) for a in (pk, r, zero, zero)],
                axis=0,
            )
        )
        ay, a_sign, r_y, r_sign, s_dig, h_dig = ed25519_batch.unpack_wire(wire)
        assert list(np.asarray(a_sign)) == [0, 1]
        assert list(np.asarray(r_sign)) == [1, 0]
        # and the sign bit never leaks into the limbs
        assert fe.limbs_to_int(np.asarray(ay)[:, 1]) == 0
        assert fe.limbs_to_int(np.asarray(r_y)[:, 0]) == 0


class TestVerifyBatchParity:
    def test_valid_signatures(self):
        keys = [ed.gen_priv_key_from_secret(bytes([i])) for i in range(8)]
        msgs = [b"vote %d" % i for i in range(8)]
        sigs = [k.sign(m) for k, m in zip(keys, msgs)]
        pks = [k.pub_key().bytes() for k in keys]
        got = _assert_parity(pks, msgs, sigs)
        assert all(got)

    def test_corrupted_signature_rejected(self):
        k = ed.gen_priv_key_from_secret(b"x")
        msg = b"block part"
        sig = bytearray(k.sign(msg))
        pks, msgs, sigs = [], [], []
        # flip a bit in R, in S, and in the message
        for variant in range(3):
            s = bytearray(sig)
            m = msg
            if variant == 0:
                s[0] ^= 1
            elif variant == 1:
                s[40] ^= 0x80
            else:
                m = b"other msg"
            pks.append(k.pub_key().bytes())
            msgs.append(m)
            sigs.append(bytes(s))
        got = _assert_parity(pks, msgs, sigs)
        assert not any(got)

    def test_wrong_pubkey_rejected(self):
        k1 = ed.gen_priv_key_from_secret(b"a")
        k2 = ed.gen_priv_key_from_secret(b"b")
        msg = b"proposal"
        got = _assert_parity([k2.pub_key().bytes()], [msg], [k1.sign(msg)])
        assert got == [False]

    def test_noncanonical_s_rejected(self):
        k = ed.gen_priv_key_from_secret(b"s")
        msg = b"m"
        sig = bytearray(k.sign(msg))
        s_int = int.from_bytes(sig[32:], "little") + fe.L
        sig[32:] = s_int.to_bytes(32, "little")
        got = _assert_parity([k.pub_key().bytes()], [msg], [bytes(sig)])
        assert got == [False]

    def test_mixed_batch(self):
        rng = np.random.default_rng(3)
        pks, msgs, sigs, expect = [], [], [], []
        for i in range(33):  # odd size → exercises padding
            k = ed.gen_priv_key_from_secret(bytes([i, 1]))
            m = rng.bytes(rng.integers(0, 200))
            s = bytearray(k.sign(m))
            good = i % 3 != 0
            if not good:
                s[rng.integers(0, 64)] ^= 1 << rng.integers(0, 8)
            pks.append(k.pub_key().bytes())
            msgs.append(bytes(m))
            sigs.append(bytes(s))
            expect.append(good)
        got = _assert_parity(pks, msgs, sigs)
        # corrupt sigs could theoretically still verify; parity is the real
        # assertion — but sanity-check the good ones accepted
        for i, e in enumerate(expect):
            if e:
                assert got[i]

    def test_garbage_pubkey(self):
        # all-0xff y is not on the curve → decompression failure path
        pks = [b"\xff" * 32, b"\x00" * 32]
        msgs = [b"m1", b"m2"]
        k = ed.gen_priv_key_from_secret(b"g")
        sigs = [k.sign(b"m1"), k.sign(b"m2")]
        _assert_parity(pks, msgs, sigs)

    def test_identity_pubkey_parity(self):
        # A = neutral element (y=1, x=0): [h]A vanishes, check degenerates
        # to [s]B == R. Craft an "accepting" signature without any secret:
        # pick s, set R = encode([s]B). Parity with OpenSSL matters most.
        import jax.numpy as jnp

        ident_pk = (1).to_bytes(32, "little")
        s = 12345
        s_bytes = s.to_bytes(32, "little")
        # compute [s]B via the kernel's own point ops on host python ints
        bx, by = ed25519_batch._BX, ed25519_batch._BY

        def edwards_add(p, q):
            (x1, y1), (x2, y2) = p, q
            den = fe.D * x1 * x2 * y1 * y2 % fe.P
            x3 = (x1 * y2 + x2 * y1) * pow(1 + den, fe.P - 2, fe.P) % fe.P
            y3 = (y1 * y2 + x1 * x2) * pow(1 - den, fe.P - 2, fe.P) % fe.P
            return (x3, y3)

        acc = (0, 1)
        base = (bx, by)
        for bit in bin(s)[2:]:
            acc = edwards_add(acc, acc)
            if bit == "1":
                acc = edwards_add(acc, base)
        r_enc = bytearray(acc[1].to_bytes(32, "little"))
        r_enc[31] |= (acc[0] & 1) << 7
        sig = bytes(r_enc) + s_bytes
        _assert_parity([ident_pk], [b"any message"], [sig])

    def test_wrong_length_inputs(self):
        k = ed.gen_priv_key_from_secret(b"l")
        got = ed25519_batch.verify_batch(
            [k.pub_key().bytes()], [b"m"], [b"\x01" * 63]
        )
        assert got == [False]

    def test_empty_batch(self):
        assert ed25519_batch.verify_batch([], [], []) == []


def edge_case_batch(lanes: int = 64):
    """(pks, msgs, sigs) of ``lanes`` lanes: valid ones around a forged
    signature (R, S, message), a forged key, a non-canonical R (y = p + 1
    for the identity, with its canonical twin), an R of all ones,
    s >= L, a small-order key (y = -1, order 4) and a key off the curve.
    Also what the builder's chip script feeds the TPU's own form."""
    keys = [ed.gen_priv_key_from_secret(bytes([i, 35])) for i in range(lanes)]
    msgs = [b"height %d round 0 precommit" % i for i in range(lanes)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    pks = [k.pub_key().bytes() for k in keys]

    def flip(i, byte, bit=1):
        sig = bytearray(sigs[i])
        sig[byte] ^= bit
        sigs[i] = bytes(sig)

    flip(3, 0)  # R
    flip(9, 40, 0x80)  # S
    msgs[17] = b"another message"
    pks[21] = pks[22]  # forged key
    identity = (1).to_bytes(32, "little")
    pks[30], sigs[30] = identity, (fe.P + 1).to_bytes(32, "little") + bytes(32)
    pks[31], sigs[31] = identity, identity + bytes(32)  # the canonical twin
    sigs[36] = b"\xff" * 32 + sigs[36][32:]
    s_int = int.from_bytes(sigs[41][32:], "little") + fe.L
    sigs[41] = sigs[41][:32] + s_int.to_bytes(32, "little")
    order4 = (fe.P - 1).to_bytes(32, "little")
    pks[50] = order4
    pks[51], sigs[51] = order4, b"\x01" * 64
    pks[58] = b"\xff" * 32
    return pks, msgs, sigs


@pytest.mark.slow
def test_whole_kernel_parity_under_the_chips_form(monkeypatch):
    """Tier-1 compiles the kernel under ``matmul`` (the CPU platform's
    default); this is the whole program under ``stack``, the form the
    chip runs, against the CPU verifier. Slow: XLA's CPU backend takes
    ~2.5 min over the unrolled form's graph (field.py, _mul_matmul)."""
    import jax

    from cometbft_tpu.crypto.tpu import aot

    monkeypatch.setenv("CBFT_TPU_MUL", "stack")
    # the registry's and the store's keys hold nothing of the program's
    # form: both would serve the matmul executable of the same shape
    registry = aot.default_registry()
    aot.reset_default_registry()
    aot.configure_exec_store("")
    jax.clear_caches()
    compiled = registry.compile_count
    try:
        pks, msgs, sigs = edge_case_batch()
        got = _assert_parity(pks, msgs, sigs)
        assert registry.compile_count > compiled
        assert sum(got) == 64 - 10 or sum(got) == 64 - 11, got
        assert not any(got[i] for i in (3, 9, 17, 21, 30, 36, 41, 50, 51, 58))
    finally:
        aot.reset_default_registry()
        aot.configure_exec_store(None)
        jax.clear_caches()


class TestHostChallengeEdges:
    """verify_batch's host challenge h = SHA-512(R ‖ A ‖ M) mod L on the
    edges the other batches do not reach: ragged messages of 0-300
    bytes through the native call, a small-order key, where an inexact
    mod-L would change [h](-A), and wrong lengths. Accept/reject must
    stay bit-identical to the CPU verifier."""

    def test_valid_and_corrupted(self):
        rng = np.random.default_rng(5)
        pks, msgs, sigs = [], [], []
        for i in range(9):
            k = ed.gen_priv_key_from_secret(bytes([i, 21]))
            m = rng.bytes(int(rng.integers(0, 300)))  # ragged block counts
            s = bytearray(k.sign(m))
            if i % 3 == 0:
                s[rng.integers(0, 64)] ^= 1
            pks.append(k.pub_key().bytes())
            msgs.append(bytes(m))
            sigs.append(bytes(s))
        _assert_parity(pks, msgs, sigs)

    def test_small_order_pubkey(self):
        # identity A: [h]A = 0 for h ≡ 0 mod ord(A)=1 — any h works, but
        # torsion points of order 8 make the result depend on h mod 8·L,
        # so the device mod-L must be exact. y = -1 has order 4.
        order4 = ((fe.P - 1) % fe.P).to_bytes(32, "little")
        k = ed.gen_priv_key_from_secret(b"t")
        msgs = [b"torsion", b"torsion2"]
        sigs = [k.sign(msgs[0]), b"\x01" * 64]
        _assert_parity([order4, order4], msgs, sigs)

    def test_wrong_lengths_and_empty(self):
        k = ed.gen_priv_key_from_secret(b"l2")
        got = ed25519_batch.verify_batch(
            [k.pub_key().bytes(), b"short"], [b"m", b"m"], [b"\x01" * 63, b"\x02" * 64]
        )
        assert got == [False, False]
        assert ed25519_batch.verify_batch([], [], []) == []


class TestTPUBatchVerifier:
    def test_small_batches_route_to_cpu_kernel_above_threshold(self):
        """The tpu boundary verifies small batches on CPU (measured
        crossover ~1k sigs) but MUST still drive the device kernel when
        forced below threshold — guards the hybrid routing both ways."""
        from cometbft_tpu.crypto.batch import TPUBatchVerifier

        keys = [ed.gen_priv_key_from_secret(bytes([i, 11])) for i in range(4)]
        bv = TPUBatchVerifier(min_batch=2)  # force the kernel path
        for i, k in enumerate(keys):
            msg = b"kernel path %d" % i
            sig = k.sign(msg) if i != 1 else b"\x11" * 64
            bv.add(k.pub_key(), msg, sig)
        ok, mask = bv.verify()
        assert not ok
        assert mask == [True, False, True, True]

    def test_default_threshold_keeps_small_batches_off_device(self, monkeypatch):
        from cometbft_tpu.crypto.tpu import ed25519_batch

        def boom(*a, **k):
            raise AssertionError("kernel dispatched for a small batch")

        monkeypatch.setattr(ed25519_batch, "verify_batch", boom)
        bv = cbatch.new_batch_verifier("tpu")  # default min_batch
        keys = [ed.gen_priv_key_from_secret(bytes([i, 13])) for i in range(6)]
        for i, k in enumerate(keys):
            m = b"cpu route %d" % i
            bv.add(k.pub_key(), m, k.sign(m))
        ok, mask = bv.verify()
        assert ok and all(mask)

    def test_backend_routing(self):
        bv = cbatch.new_batch_verifier("tpu")
        keys = [ed.gen_priv_key_from_secret(bytes([i, 9])) for i in range(5)]
        for i, k in enumerate(keys):
            msg = b"height %d" % i
            sig = k.sign(msg) if i != 2 else b"\x00" * 64
            bv.add(k.pub_key(), msg, sig)
        ok, mask = bv.verify()
        assert not ok
        assert mask == [True, True, False, True, True]
        assert bv.count() == 0

    def test_matches_cpu_backend(self):
        keys = [ed.gen_priv_key_from_secret(bytes([i, 7])) for i in range(6)]
        entries = []
        for i, k in enumerate(keys):
            msg = b"commit sig %d" % i
            sig = bytearray(k.sign(msg))
            if i % 2:
                sig[10] ^= 4
            entries.append((k.pub_key(), msg, bytes(sig)))
        results = []
        for backend in ("cpu", "tpu"):
            bv = cbatch.new_batch_verifier(backend)
            for pk, msg, sig in entries:
                bv.add(pk, msg, sig)
            results.append(bv.verify())
        assert results[0] == results[1]


class TestValsetResident:
    """Device-resident valset verification (verify_valset_resident):
    per-lane accept/reject must be bit-identical to verify_batch, with
    absent lanes masked False, across multiple resident chunks, and the
    cache must be reused by valset_id."""

    def _valset(self, n, tag=77):
        keys = [ed.gen_priv_key_from_secret(bytes([i, tag])) for i in range(n)]
        return keys, [k.pub_key().bytes() for k in keys]

    def test_parity_with_absent_and_invalid_lanes(self, monkeypatch):
        # chunk cap 64 (= the kernel's min pad) + 100 lanes → 2 resident
        # chunks, with absent/corrupt lanes in BOTH chunks
        monkeypatch.setenv("CBFT_TPU_MAX_CHUNK", "64")
        store = keystore.default_store()
        store.invalidate()
        n = 100
        keys, pks = self._valset(n)
        msgs, sigs = [], []
        for i, k in enumerate(keys):
            if i in (3, 70):  # absent lanes (nil votes)
                msgs.append(None)
                sigs.append(None)
                continue
            m = b"resident vote %d" % i
            s = bytearray(k.sign(m))
            if i in (5, 90):
                s[9] ^= 1  # corrupt
            if i == 65:
                s[32:] = ed25519_batch.L.to_bytes(32, "little")  # s = L
            msgs.append(m)
            sigs.append(bytes(s))
        import hashlib as h

        vid = h.sha256(b"".join(pks)).digest()
        got = ed25519_batch.verify_valset_resident(vid, pks, msgs, sigs)
        assert len(store.entry_for(vid).chunks) == 2
        want = []
        for i in range(n):
            if msgs[i] is None:
                want.append(False)
            else:
                want.append(
                    ed.PubKeyEd25519(pks[i]).verify_signature(
                        msgs[i], sigs[i]
                    )
                )
        assert got == want
        for i in (3, 5, 65, 70, 90):
            assert not got[i]
        assert sum(got) == n - 5

    def test_cache_reused_across_commits_and_evicted_by_lru(self, monkeypatch):
        monkeypatch.delenv("CBFT_TPU_MAX_CHUNK", raising=False)
        store = keystore.default_store()
        store.invalidate()
        import hashlib as h

        keys, pks = self._valset(8, tag=78)
        vid = h.sha256(b"".join(pks)).digest()
        for height in range(2):
            msgs = [b"h%d vote %d" % (height, i) for i in range(8)]
            sigs = [k.sign(m) for k, m in zip(keys, msgs)]
            assert all(
                ed25519_batch.verify_valset_resident(vid, pks, msgs, sigs)
            )
        assert store.residency()["entries"] == 1  # one set, reused
        # rotate through >MAX distinct valsets: LRU bounds the cache
        for tag in range(100, 100 + keystore.CACHE_MAX + 2):
            ks, ps = self._valset(4, tag=tag)
            v = h.sha256(b"".join(ps)).digest()
            m = [b"x"] * 4
            s = [k.sign(b"x") for k in ks]
            assert all(ed25519_batch.verify_valset_resident(v, ps, m, s))
        assert store.residency()["entries"] == keystore.CACHE_MAX

    def test_verify_commit_routes_resident(self, monkeypatch):
        """End-to-end: ValidatorSet.verify_commit under the tpu backend
        takes the resident path when the floor allows, with behavior
        identical to the cpu backend."""
        monkeypatch.setenv("CBFT_TPU_MIN_BATCH", "1")
        monkeypatch.delenv("CBFT_TPU_MAX_CHUNK", raising=False)
        store = keystore.default_store()
        store.invalidate()
        from cometbft_tpu.types.test_util import (
            deterministic_validator_set,
            make_block_id,
            make_commit,
        )

        vset, privs = deterministic_validator_set(6)
        bid = make_block_id()
        commit = make_commit(bid, 5, 1, vset, privs, "res-chain")
        vset.verify_commit("res-chain", bid, 5, commit, backend="cpu")
        vset.verify_commit("res-chain", bid, 5, commit, backend="tpu")
        assert store.residency()["entries"] == 1  # resident path ran
        # corrupt one signature: both backends must reject identically
        bad = bytearray(commit.signatures[2].signature)
        bad[6] ^= 1
        commit.signatures[2].signature = bytes(bad)
        import pytest as _pytest

        with _pytest.raises(ValueError):
            vset.verify_commit("res-chain", bid, 5, commit, backend="cpu")
        with _pytest.raises(ValueError):
            vset.verify_commit("res-chain", bid, 5, commit, backend="tpu")


class TestChunkedPipelineParity:
    """The double-buffered chunked dispatch (the DEFAULT verify_batch
    path) must be bit-identical to a single dispatch and to the CPU
    serial verifier on adversarial batches: one corrupt signature
    walked across every chunk position, at sizes straddling the chunk
    boundary (cap 64 = the kernel's min pad, so 63/64/65/127/128/129
    cover last-lane-of-chunk, exact-fill, and one-lane-overflow)."""

    _POOL = {}

    def _pool(self, n):
        """n deterministic (pk, msg, sig) lanes, memoized — signing 129
        keys once keeps the walk over positions cheap."""
        if n not in self._POOL:
            keys = [
                ed.gen_priv_key_from_secret(b"chunk-%d" % i) for i in range(n)
            ]
            msgs = [b"pipelined vote %d" % i for i in range(n)]
            self._POOL[n] = (
                [k.pub_key().bytes() for k in keys],
                msgs,
                [k.sign(m) for k, m in zip(keys, msgs)],
            )
        pks, msgs, sigs = self._POOL[n]
        return list(pks), list(msgs), list(sigs)

    @pytest.mark.parametrize("size", [63, 64, 65, 127, 128, 129])
    def test_one_bad_lane_per_chunk_position(self, size, monkeypatch):
        monkeypatch.setenv("CBFT_TPU_MAX_CHUNK", "64")
        pks, msgs, sigs = self._pool(size)
        # positions that matter for chunk reassembly: first/last lane of
        # each chunk, the boundary straddle, and the final ragged lane
        positions = sorted(
            {0, size - 1}
            | {p for p in (63, 64, 65, 127, 128) if p < size}
        )
        for bad in positions:
            s = list(sigs)
            corrupted = bytearray(s[bad])
            corrupted[8] ^= 1
            s[bad] = bytes(corrupted)
            got = ed25519_batch.verify_batch(pks, msgs, s)
            want = [i != bad for i in range(size)]
            # the corrupt lane must reject and, critically, reassembly
            # must not smear the verdict onto any neighbor lane
            assert got == want, f"size={size} bad={bad}: {got}"

    def test_pipelined_matches_single_dispatch_and_cpu(self, monkeypatch):
        """Same adversarial batch through three dispatch shapes — chunked
        double-buffered (depth 2), chunked serial (depth 1), and one
        unchunked dispatch — all equal to the CPU reference."""
        n = 129
        pks, msgs, sigs = self._pool(n)
        for i in range(0, n, 7):  # corrupt every 7th lane
            b = bytearray(sigs[i])
            b[40] ^= 0x80
            sigs[i] = bytes(b)
        want = [
            ed.PubKeyEd25519(p).verify_signature(m, s)
            for p, m, s in zip(pks, msgs, sigs)
        ]

        monkeypatch.setenv("CBFT_TPU_MAX_CHUNK", "64")
        monkeypatch.delenv("CBFT_TPU_PIPELINE_DEPTH", raising=False)
        assert ed25519_batch.verify_batch(pks, msgs, sigs) == want

        monkeypatch.setenv("CBFT_TPU_PIPELINE_DEPTH", "1")
        assert ed25519_batch.verify_batch(pks, msgs, sigs) == want

        monkeypatch.delenv("CBFT_TPU_PIPELINE_DEPTH", raising=False)
        monkeypatch.delenv("CBFT_TPU_MAX_CHUNK", raising=False)
        assert ed25519_batch.verify_batch(pks, msgs, sigs) == want
