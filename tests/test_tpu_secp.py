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


@pytest.fixture(params=sorted(F._MUL_IMPLS))
def form(request, monkeypatch):
    """F.mul (and everything built on it) in the named form of its
    columns, whatever the platform: the TPU's slice form otherwise runs
    only on hardware."""
    monkeypatch.setattr(F, "_mul_form", lambda: request.param)
    return request.param


class TestSecpField:
    def _fe1(self, n):
        import jax.numpy as jnp

        return jnp.array(F.int_to_limbs(n % F.P), jnp.int32)[:, None]

    def _val(self, x):
        return F.limbs_to_int(np.asarray(F.to_canonical(x))[:, 0])

    def test_ops_parity(self, form):
        rng = random.Random(7)
        for _ in range(15):
            a, b = rng.randrange(F.P), rng.randrange(F.P)
            fa, fb = self._fe1(a), self._fe1(b)
            assert self._val(F.add(fa, fb)) == (a + b) % F.P
            assert self._val(F.sub(fa, fb)) == (a - b) % F.P
            assert self._val(F.mul(fa, fb)) == (a * b) % F.P

    def test_chained_compositions_preserve_invariant(self, form):
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

    def test_invert_and_sqrt(self, form):
        inv = F.invert(self._fe1(987654321))
        assert self._val(inv) * 987654321 % F.P == 1
        s = self._val(F.sqrt_candidate(self._fe1(9)))
        assert pow(s, 2, F.P) == 9

    def test_identity_chain_stays_bounded(self, form):
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


# the invariant's edges, as test_identity_chain_stays_bounded holds them:
# limbs in [-4, 2^14 + 4096)
_LOW, _TOP = -4, (1 << F.RADIX) + 4095
_CORNERS = {
    "all_top": [_TOP] * F.NUM_LIMBS,
    "all_bottom": [_LOW] * F.NUM_LIMBS,
    "alternating": [_TOP, _LOW] * 9 + [_TOP],
    "alternating_from_bottom": [_LOW, _TOP] * 9 + [_LOW],
    "limb0_top_rest_2^14": [_TOP] + [1 << F.RADIX] * (F.NUM_LIMBS - 1),
}


def _limbs(rows):
    import jax.numpy as jnp

    return jnp.array(np.asarray(rows, np.int64).T, jnp.int32)


class TestProductForms:
    """The platforms' two forms of a product's columns are one set of
    integers: every verdict, bound and test of one holds for the other."""

    @pytest.fixture(scope="class")
    def operands(self):
        rng = np.random.default_rng(39)
        corners = list(_CORNERS.values())
        a = corners + corners + rng.integers(
            _LOW, _TOP + 1, (64, F.NUM_LIMBS)).tolist()
        b = corners + corners[::-1] + rng.integers(
            _LOW, _TOP + 1, (64, F.NUM_LIMBS)).tolist()
        return _limbs(a), _limbs(b)

    def test_columns_are_integer_identical(self, operands):
        a, b = operands
        want = np.asarray(F._cols_matmul(a, b))
        got = np.asarray(F._cols_stack(a, b))
        assert got.shape == (2 * F.NUM_LIMBS, a.shape[1])
        assert np.array_equal(got, want)
        assert np.abs(want).max() < 1 << 21  # _fold_v wants < 2^22

    def test_limbs_are_integer_identical_and_in_the_invariant(
            self, operands, monkeypatch):
        a, b = operands
        out = {}
        for name in sorted(F._MUL_IMPLS):
            monkeypatch.setattr(F, "_mul_form", lambda n=name: n)
            out[name] = np.asarray(F.mul(a, b))
        assert np.array_equal(out["stack"], out["matmul"])
        limbs = out["stack"]
        assert limbs.min() >= _LOW and limbs.max() <= _TOP
        an, bn, got = np.asarray(a), np.asarray(b), limbs
        for k in range(a.shape[1]):
            x = F.limbs_to_int(an[:, k])
            y = F.limbs_to_int(bn[:, k])
            assert F.limbs_to_int(got[:, k]) % F.P == x * y % F.P, k

    def test_a_constant_operand_broadcasts(self, operands):
        a, _ = operands
        c = F.const_fe(F.B3)
        for x, y in ((a, c), (c, a)):
            assert np.array_equal(np.asarray(F._cols_stack(x, y)),
                                  np.asarray(F._cols_matmul(x, y)))


def test_the_platform_names_the_form(monkeypatch):
    """The CPU platform keeps the matrix products (XLA:CPU compiles the
    whole kernel for the tests); every other platform takes the slices.
    No option chooses it."""
    import jax

    seen = []
    for name in sorted(F._MUL_IMPLS):
        monkeypatch.setitem(
            F._MUL_IMPLS, name,
            lambda a, b, n=name: seen.append(n) or F._cols_matmul(a, b))
    one = F.const_fe(1)
    for backend, form in (("cpu", "matmul"), ("tpu", "stack")):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        monkeypatch.setenv("CBFT_TPU_MUL", "f32")  # ed25519's alone
        assert F._mul_form() == form
        F.mul(one, one)
        assert seen[-1] == form


# secp_field's reduction as it was written first, one limb row at a time:
# the oracle the vector form is held to, integer for integer
def _per_row_carry_round(x):
    import jax.numpy as jnp

    c = x >> F.RADIX
    kept = x & F._MASK
    shifted = jnp.concatenate([jnp.zeros_like(c[:1]), c[:-1]], axis=0)
    out = kept + shifted
    top = c[F.NUM_LIMBS - 1]
    for i, v in enumerate(F._V_LIMBS):
        if v:
            out = out.at[i].add(top * jnp.int32(v))
    return out


def _per_row_carry_signed_list(cols):
    import jax.numpy as jnp

    out = []
    carry = jnp.zeros_like(cols[0])
    for c in cols[:-1]:
        t = c + carry
        out.append(t & F._MASK)
        carry = t >> F.RADIX
    out.append(cols[-1] + carry)
    return out


def _per_row_fold_v(cols36):
    import jax.numpy as jnp

    lo = [cols36[i] for i in range(F.NUM_LIMBS)]
    hi = _per_row_carry_signed_list(
        [cols36[F.NUM_LIMBS + i] for i in range(F.NUM_LIMBS)])
    acc = lo + [jnp.zeros_like(lo[0]) for _ in range(5)]

    def fold_into(acc, limbs):
        for i, h in enumerate(limbs):
            for j, v in enumerate(F._V_LIMBS):
                if v:
                    p = h * jnp.int32(v)
                    acc[i + j] = acc[i + j] + (p & F._MASK)
                    acc[i + j + 1] = acc[i + j + 1] + (p >> F.RADIX)
        return acc

    acc = fold_into(acc, hi)
    spill = _per_row_carry_signed_list(acc[F.NUM_LIMBS:])
    acc = acc[:F.NUM_LIMBS] + [jnp.zeros_like(lo[0])] * 5
    acc = fold_into(acc, spill)
    return jnp.stack(acc[:F.NUM_LIMBS], axis=0)


class TestVectorReduction:
    """_carry_round and _fold_v as [19, B] / [5, B] slices give the per-row
    forms' integers on every input inside their preconditions."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_carry_round_is_the_per_row_round(self, seed):
        import jax.numpy as jnp

        rng = np.random.default_rng(seed)
        bound = 1 << 25  # _reduce's precondition
        x = rng.integers(-bound + 1, bound, (F.NUM_LIMBS, 256))
        x[:, 0], x[:, 1] = bound - 1, -bound + 1
        x = jnp.asarray(x, jnp.int32)
        for _ in range(4):
            want = _per_row_carry_round(x)
            assert np.array_equal(np.asarray(F._carry_round(x)),
                                  np.asarray(want))
            x = want

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_fold_v_is_the_per_row_fold(self, seed):
        import jax.numpy as jnp

        rng = np.random.default_rng(seed + 100)
        bound = 1 << 22  # _fold_v's precondition
        cols = rng.integers(-bound + 1, bound, (2 * F.NUM_LIMBS, 256))
        cols[:, 0], cols[:, 1] = bound - 1, -bound + 1
        # carry ripples: hi columns of 0 / 2^14 - 1 under a +-1 carry
        cols[F.NUM_LIMBS:, 2] = (1 << F.RADIX) - 1
        cols[F.NUM_LIMBS, 2] = 1 << F.RADIX
        cols[F.NUM_LIMBS:, 3] = 0
        cols[F.NUM_LIMBS, 3] = -1
        cols = jnp.asarray(cols, jnp.int32)
        assert np.array_equal(np.asarray(F._fold_v(cols)),
                              np.asarray(_per_row_fold_v(cols)))


def _tiled(x, sub=8):
    """[19, B] -> the kernel's field element: 19 numpy limbs [sub, 128]."""
    x = np.asarray(x, np.int32)
    return [x[i].reshape(sub, -1) for i in range(F.NUM_LIMBS)]


def _untiled(limbs):
    return np.stack([np.asarray(v).reshape(-1) for v in limbs])


def _multiples_of_g(count, rng):
    """k·G for k = 1..count in homogeneous (X:Y:Z) with a random Z each,
    as three [19, count] limb arrays."""
    pts, pt = [], None
    for _ in range(count):
        pt = secp256k1_batch._addp(pt, secp256k1_batch._G1)
        pts.append(pt)
    cols = []
    for x, y in pts:
        lam = int(rng.integers(1, 1 << 62)) * 0x9E3779B97F4A7C15 % F.P
        cols.append([F.int_to_limbs(v * lam % F.P) for v in (x, y, 1)])
    arr = np.asarray(cols, np.int32)  # [count, 3, 19]
    return tuple(arr[:, k].T for k in range(3))


_IDENTITY = tuple(np.asarray(F.const_fe(v)) for v in (0, 1, 0))


class TestPallasLadder:
    """secp_ladder, the ladder the chip runs: its product, point addition
    and steps give secp_field's and secp256k1_batch's limbs EXACTLY. The
    kernel body's functions run eagerly on numpy limbs (the same integer
    operations; a whole addition under interpret mode or jitted on XLA:CPU
    is too large a graph to compile); the product alone and the tiling
    run as pallas_calls in interpret mode. The whole kernel's verdicts
    are the mixed cell's on the chip."""

    LANES = 1024  # one tile: 8 x 128

    def _operands(self):
        """[19, 1024] a, b: the representation's bounds, 0, 1, p - 1 and
        values near 2^256 against each other, then random limbs."""
        rng = np.random.default_rng(41)
        special = list(_CORNERS.values()) + [
            F.int_to_limbs(v) for v in (0, 1, F.P - 1, F.P, 2**256 - 1,
                                        2**256 - 2**32, 2**255)]
        a = rng.integers(_LOW, _TOP + 1, (self.LANES, F.NUM_LIMBS))
        b = rng.integers(_LOW, _TOP + 1, (self.LANES, F.NUM_LIMBS))
        k = len(special)
        for i in range(k):
            for j in range(k):
                a[i * k + j], b[i * k + j] = special[i], special[j]
        return a.T.astype(np.int32), b.T.astype(np.int32)

    def test_the_product_kernel_is_secp_field_mul(self):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        from cometbft_tpu.crypto.tpu import secp_ladder as L

        a, b = self._operands()

        def kernel(a_ref, b_ref, out_ref):  # on _Limbs, as the ladder's
            out = L.mul([L._Limb(a_ref[i]) for i in range(F.NUM_LIMBS)],
                        [L._Limb(b_ref[i]) for i in range(F.NUM_LIMBS)])
            for i, limb in enumerate(out):
                out_ref[i] = limb.x

        shape = (F.NUM_LIMBS, 8, 128)
        got = pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(shape, jnp.int32),
            interpret=True,
        )(a.reshape(shape), b.reshape(shape))
        want = np.asarray(F.mul(jnp.asarray(a), jnp.asarray(b)))
        assert np.array_equal(np.asarray(got).reshape(want.shape), want)
        # the product by b3, as the addition makes it: constant limbs
        want = np.asarray(F.mul(jnp.asarray(a), F.const_fe(F.B3)))
        assert np.array_equal(_untiled(L.mul(_tiled(a), L._B3)), want)

    def test_the_point_addition_is_point_add(self):
        import jax
        import jax.numpy as jnp

        from cometbft_tpu.crypto.tpu import secp_ladder as L

        rng = np.random.default_rng(42)
        group = self.LANES // 8
        real = _multiples_of_g(2 * group, rng)
        p = [np.empty((F.NUM_LIMBS, self.LANES), np.int32) for _ in range(3)]
        q = [np.empty_like(c) for c in p]

        def put(lanes, pt, qt):
            for k in range(3):
                p[k][:, lanes] = pt[k]
                q[k][:, lanes] = qt[k]

        rand = [rng.integers(_LOW, _TOP + 1, (F.NUM_LIMBS, group))
                for _ in range(6)]
        put(slice(0, group), rand[:3], rand[3:])
        first = tuple(c[:, :group] for c in real)
        second = tuple(c[:, group:] for c in real)
        neg = (first[0], np.asarray(F.neg(jnp.asarray(first[1]))), first[2])
        put(slice(group, 2 * group), first, second)
        put(slice(2 * group, 3 * group), first, first)  # a doubling
        put(slice(3 * group, 4 * group), first, neg)  # p + (-p)
        put(slice(4 * group, 5 * group), _IDENTITY, first)
        put(slice(5 * group, 6 * group), first, _IDENTITY)
        put(slice(6 * group, 7 * group), _IDENTITY, _IDENTITY)
        put(slice(7 * group, 8 * group), second, first)
        got = L.point_add(tuple(_tiled(c) for c in p),
                          tuple(_tiled(c) for c in q))
        want = secp256k1_batch.point_add(tuple(map(jnp.asarray, p)),
                                         tuple(map(jnp.asarray, q)))
        for g, w in zip(got, want):
            assert np.array_equal(_untiled(g), np.asarray(w))
        # the kernel's form: on _Limbs, lax primitives, run op by op
        with jax.disable_jit():
            traced = L._traced_point_add(
                tuple(list(map(jnp.asarray, _tiled(c))) for c in p),
                tuple(list(map(jnp.asarray, _tiled(c))) for c in q))
        for t, g in zip(traced, got):
            assert np.array_equal(_untiled(t), _untiled(g))
        z = np.asarray(F.to_canonical(jnp.asarray(_untiled(got[2]))))
        assert not z[:, 3 * group:4 * group].any()  # the identity's Z
        assert z[:, group:3 * group].any(axis=0).all()

    def test_ladder_steps_are_the_xla_steps(self):
        import jax.numpy as jnp

        from cometbft_tpu.crypto.tpu import secp_ladder as L

        rng = np.random.default_rng(43)
        pool = _multiples_of_g(64, rng)
        pick = rng.integers(0, 64, (17, self.LANES))
        entries = [tuple(c[:, pick[e]] for c in pool) for e in range(16)]
        acc = tuple(c[:, pick[16]] for c in pool)
        digits = rng.integers(0, 16, (3, self.LANES)).astype(np.int32)
        digits[:, :16] = np.arange(16)[None]  # every entry chosen
        table = [[_tiled(c) for c in pt] for pt in entries]
        xla_entries = [tuple(map(jnp.asarray, pt)) for pt in entries]
        got, want = tuple(_tiled(c) for c in acc), tuple(map(jnp.asarray, acc))
        for i in range(digits.shape[0]):
            entry = L.select(lambda e, k, limb: table[e][k][limb],
                             digits[i].reshape(8, 128))
            got = L.ladder_step(
                got, tuple([np.asarray(v) for v in c] for c in entry))
            got = tuple([np.asarray(v) for v in c] for c in got)
            want = secp256k1_batch.point_dbl(secp256k1_batch.point_dbl(want))
            want = secp256k1_batch.point_add(
                want, secp256k1_batch._select_point(
                    xla_entries, jnp.asarray(digits[i])))
            for g, w in zip(got, want):
                assert np.array_equal(_untiled(g), np.asarray(w)), i

    @pytest.mark.parametrize("lanes", [200, 1500])
    def test_the_tiles_cover_every_lane(self, lanes, monkeypatch):
        """The pallas_call's tiles, index maps, padding and slicing, with
        the point arithmetic stood in for by a cheap limb-wise map (the
        select stays): one narrow tile of the batch rounded up to 128
        lanes (200: 256 lanes, 2 sublanes), or tiles of 1,024 (1,500: two,
        the second padded)."""
        from cometbft_tpu.crypto.tpu import secp_ladder as L

        def stand_in(p, q):
            return tuple([(a * 3 + b) & 0xFFFFF for a, b in zip(x, y)]
                         for x, y in zip(p, q))

        monkeypatch.setattr(L, "_traced_point_add", stand_in)
        rng = np.random.default_rng(lanes)
        idx = rng.integers(0, 16, (L.NUM_DIGITS, lanes)).astype(np.int32)
        entries = [
            tuple(np.asarray(F.const_fe(e * 3 + k + 1)) if e % 5 == 0 else
                  rng.integers(0, 1 << 14, (F.NUM_LIMBS, lanes)).astype(
                      np.int32) for k in range(3))
            for e in range(16)]
        got = L.ladder(idx, entries, interpret=True)
        table = np.stack([np.stack([np.broadcast_to(c, (F.NUM_LIMBS, lanes))
                                    for c in pt]) for pt in entries])
        acc = np.stack([np.broadcast_to(c, (F.NUM_LIMBS, lanes))
                        for c in _IDENTITY]).astype(np.int64)
        for i in range(L.NUM_DIGITS):
            acc = (acc * 3 + acc) & 0xFFFFF
            acc = (acc * 3 + acc) & 0xFFFFF
            acc = (acc * 3 + table[idx[i], :, :, np.arange(lanes)].transpose(
                1, 2, 0)) & 0xFFFFF
        for k in range(3):
            assert np.array_equal(np.asarray(got[k]), acc[k]), k
        assert L.tile_plan(lanes) == {200: (256, 2), 1500: (2048, 8)}[lanes]
        assert L.tile_plan(64) == (128, 1) and L.tile_plan(1024) == (1024, 8)


def test_the_platform_names_the_ladder(monkeypatch):
    """Off the CPU platform the ladder is secp_ladder's pallas_call, fed
    the combined digit row u1 + 4·u2 and the 16 table points; the CPU
    platform's program keeps the XLA fori_loop of 128 steps. No option
    chooses it. (The point and field arithmetic around the ladder is
    stood in for: the choice is what is under test.)"""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from cometbft_tpu.crypto.tpu import secp_ladder

    ladders, loops = [], []

    def spy_ladder(idx, entries):
        ladders.append((np.asarray(idx), entries))
        return tuple(jnp.zeros_like(c) for c in entries[5])

    def spy_loop(lo, hi, body, init):
        loops.append(hi - lo)
        return init

    monkeypatch.setattr(secp_ladder, "ladder", spy_ladder)
    monkeypatch.setattr(secp256k1_batch, "lax",
                        SimpleNamespace(fori_loop=spy_loop))
    monkeypatch.setattr(secp256k1_batch, "decompress",
                        lambda qx, parity: (qx, parity == parity))
    monkeypatch.setattr(secp256k1_batch, "point_add", lambda p, q: p)
    monkeypatch.setattr(F, "invert", lambda x: x)
    monkeypatch.setattr(F, "mul", lambda a, b: a)
    rng = np.random.default_rng(44)
    b = 8
    fe_in = jnp.asarray(rng.integers(0, 1 << 14, (F.NUM_LIMBS, b)), jnp.int32)
    u1, u2 = (jnp.asarray(rng.integers(0, 4, (secp256k1_batch.NUM_DIGITS, b)),
                          jnp.int32) for _ in range(2))
    args = (fe_in, jnp.zeros(b, jnp.int32), fe_in, fe_in,
            jnp.zeros(b, bool), u1, u2)
    for backend, pallas in (("cpu", False), ("tpu", True)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        monkeypatch.setenv("CBFT_TPU_MUL", "f32")  # ed25519's alone
        assert (F._mul_form() == "stack") is pallas
        secp256k1_batch._verify_math(*args)
        assert (len(ladders), loops) == (int(pallas),
                                         [secp256k1_batch.NUM_DIGITS])
    idx, entries = ladders[0]
    assert np.array_equal(idx, np.asarray(u1) + 4 * np.asarray(u2))
    assert len(entries) == 16 and all(len(pt) == 3 for pt in entries)


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
