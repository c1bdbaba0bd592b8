"""The secp256k1 plain reference and count: ``verify_py`` (Python
integers) equals ``verify_openssl`` (the wheel, wrapped in the range and
low-S checks) on every kind of lane the benchmark plants and more, each
against its stated verdict; ``secp_opcount`` equals a count of
``_verify_math``'s own field multiplications, taken by tracing it."""

import hashlib

import pytest

from benchmark import secp_opcount
from benchmark.lib import secp_reference as ref
from benchmark.traffic import mixed_commit as gen

SEED = 2_150_000_999


@pytest.fixture(scope="module")
def lanes():
    """(kind, (pk, msg, sig), want) for every planted kind."""
    vals, signers = gen.make_mixed_valset(4, 4, SEED, "ref")
    keys = [v.pub_key.bytes() for v in vals.validators]
    msg = b"a canonical vote's sign-bytes stand-in" * 3
    sig = gen._sign(signers[0][0], signers[0][1], msg)
    pk = keys[0]
    r, s = int.from_bytes(sig[:32], "big"), int.from_bytes(sig[32:], "big")
    be = lambda v: v.to_bytes(32, "big")  # noqa: E731
    off_curve = next(x for x in range(5, 99)
                     if ref.decode_key(b"\x02" + be(x)) is None)
    flip = lambda b, i: b[:i] + bytes([b[i] ^ 0x10]) + b[i + 1:]  # noqa: E731
    return [
        ("honest", (pk, msg, sig), True),
        ("r_flipped", (pk, msg, flip(sig, 7)), False),
        ("s_flipped", (pk, msg, flip(sig, 40)), False),
        ("high_s", (pk, msg, be(r) + be(ref.N - s)), False),
        ("r_zero", (pk, msg, be(0) + be(s)), False),
        ("s_zero", (pk, msg, be(r) + be(0)), False),
        ("r_n", (pk, msg, be(ref.N) + be(s)), False),
        ("s_n", (pk, msg, be(r) + be(ref.N)), False),
        ("short_sig", (pk, msg, sig[:63]), False),
        ("wrong_prefix", (b"\x05" + pk[1:], msg, sig), False),
        ("other_parity", (bytes([pk[0] ^ 1]) + pk[1:], msg, sig), False),
        ("x_not_below_p", (b"\x02" + be(ref.P), msg, sig), False),
        ("off_curve_x", (b"\x02" + be(off_curve), msg, sig), False),
        ("short_key", (pk[:32], msg, sig), False),
        ("other_message", (pk, msg + b"!", sig), False),
        ("other_key", (keys[1], msg, sig), False),
    ]


def test_the_two_references_agree_on_every_planted_lane(lanes):
    if ref.verify_openssl(*lanes[0][1]) is None:
        pytest.skip("no cryptography wheel")
    for kind, lane, want in lanes:
        assert ref.verify_py(*lane) is want, kind
        assert ref.verify_openssl(*lane) is want, kind
    assert ref.verify_many([lane for _, lane, _ in lanes]) == [
        want for _, _, want in lanes]


def test_the_signer_makes_low_s_deterministic_signatures():
    vals, signers = gen.make_mixed_valset(3, 3, SEED, "sign")
    for (curve, key), v in zip(signers, vals.validators):
        msg = hashlib.sha256(v.address).digest()
        sig = gen._sign(curve, key, msg)
        assert sig == gen._sign(curve, key, msg)
        assert int.from_bytes(sig[32:], "big") <= ref.N // 2
        assert ref.verify_py(v.pub_key.bytes(), msg, sig)


def test_the_count_is_verify_maths_own(monkeypatch):
    """Each field multiplication ``_verify_math`` makes, counted while
    jax traces it, a loop's body once and times its trip count."""
    import jax
    import jax.numpy as jnp
    from types import SimpleNamespace

    from cometbft_tpu.crypto.tpu import secp256k1_batch as sb
    from cometbft_tpu.crypto.tpu import secp_field as fe

    count = {"n": 0}
    real_mul = fe.mul

    def mul(a, b):
        count["n"] += 1
        return real_mul(a, b)

    def fori_loop(lo, hi, body, init):
        before = count["n"]
        out = body(lo, init)
        count["n"] += (count["n"] - before) * (hi - lo - 1)
        return out

    phases = {}

    def timed(name, fn):
        def wrapper(*a):
            before = count["n"]
            out = fn(*a)
            phases[name] = phases.get(name, 0) + count["n"] - before
            return out
        return wrapper

    counting_lax = SimpleNamespace(fori_loop=fori_loop)
    monkeypatch.setattr(fe, "mul", mul)
    monkeypatch.setattr(fe, "lax", counting_lax)
    monkeypatch.setattr(sb, "lax", counting_lax)
    monkeypatch.setattr(sb, "decompress", timed("decompress", sb.decompress))
    monkeypatch.setattr(fe, "invert", timed("invert", fe.invert))
    real_loop = counting_lax.fori_loop
    counting_lax.fori_loop = lambda lo, hi, body, init: (
        timed("ladder", real_loop)(lo, hi, body, init)
        if hi - lo == sb.NUM_DIGITS else real_loop(lo, hi, body, init)
    )
    b = 8
    fe_shape = jax.ShapeDtypeStruct((fe.NUM_LIMBS, b), jnp.int32)
    lane = jax.ShapeDtypeStruct((b,), jnp.int32)
    digits = jax.ShapeDtypeStruct((sb.NUM_DIGITS, b), jnp.int32)
    jax.eval_shape(
        sb._verify_math, fe_shape, lane, fe_shape, fe_shape,
        jax.ShapeDtypeStruct((b,), jnp.bool_), digits, digits,
    )
    want = secp_opcount.field_muls()
    assert count["n"] == sum(want.values()) == 6554
    assert phases["decompress"] == want["decompress"]
    assert phases["ladder"] == want["ladder"]
    assert phases["invert"] + 1 == want["final"]
    assert count["n"] - sum(phases.values()) - 1 == want["table"]
    assert secp_opcount.int8_ops_per_lane() == 6554 * 19 * 19 * 8


def test_the_roofline_is_compute_bound_on_the_v5e():
    import json
    import os

    with open(os.path.join(os.path.dirname(secp_opcount.__file__),
                           "peaks.json")) as fh:
        peaks = json.load(fh)["TPU v5 lite"]
    least = secp_opcount.least_seconds_per_lane(peaks)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(6554 * 19 * 19 * 8 / 393e12)
