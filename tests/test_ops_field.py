"""JAX limb field ops vs the scalar oracle — must agree exactly."""

import random

import numpy as np
import pytest

from janus_tpu.fields import Field64, Field128, Field255
from janus_tpu.ops.field_jax import JField

FIELDS = [Field64, Field128]


def _edge_values(field):
    p = field.MODULUS
    vals = [0, 1, 2, p - 1, p - 2, (1 << 32) - 1, 1 << 32, (1 << 32) + 1]
    if field.ENCODED_SIZE == 16:
        vals += [(1 << 64) - 1, 1 << 64, (1 << 96) + 5, p - (1 << 66)]
    return [v % p for v in vals]


def _pairs(field, count=200, seed=0):
    rng = random.Random(seed)
    edges = _edge_values(field)
    a = edges + [rng.randrange(field.MODULUS) for _ in range(count)]
    b = list(reversed(edges)) + [rng.randrange(field.MODULUS) for _ in range(count)]
    return a, b


@pytest.mark.parametrize("field", FIELDS)
def test_limb_roundtrip(field):
    jf = JField(field)
    vals = _edge_values(field) + [12345678901234567890 % field.MODULUS]
    limbs = jf.to_limbs(vals)
    assert jf.from_limbs(limbs) == vals


def _loop_to_limbs(values, n):
    """The per-limb loops the byte-buffer conversion replaced: the reference."""
    flat = np.empty((len(values), n), dtype=np.uint32)
    for i, v in enumerate(values):
        for j in range(n):
            flat[i, j] = (v >> (32 * j)) & 0xFFFFFFFF
    return flat


def _loop_from_limbs(limbs, n):
    out = []
    for row in np.asarray(limbs, dtype=np.uint32).reshape(-1, n):
        v = 0
        for j in range(n):
            v |= int(row[j]) << (32 * j)
        out.append(v)
    return out


@pytest.mark.parametrize("field", FIELDS + [Field255])
def test_limb_conversion_matches_per_limb_loops(field):
    jf = JField(field)
    n, p = jf.n, field.MODULUS
    assert n == field.ENCODED_SIZE // 4
    rng = random.Random(30)
    bounds = [(1 << (32 * j)) + d for j in range(1, n) for d in (-1, 0, 1)]
    vals = [0, 1, p - 1] + [b for b in bounds if b < p]
    vals += [rng.randrange(p) for _ in range(1000)]
    limbs = jf.to_limbs(vals)
    assert limbs.dtype == np.uint32 and limbs.flags.c_contiguous
    assert limbs.shape == (len(vals), n)
    assert np.array_equal(limbs, _loop_to_limbs(vals, n))
    assert jf.from_limbs(limbs) == vals == _loop_from_limbs(limbs, n)
    # what the callers do next: write into it, or hand it to the device
    limbs[0, 0] = 7
    assert jf.from_limbs(jf.add(limbs[1:3], limbs[1:3])) == [2, (2 * (p - 1)) % p]

    empty = jf.to_limbs([])
    assert empty.shape == (0, n) and empty.dtype == np.uint32
    assert jf.from_limbs(empty) == []

    # a (B, L, n) matrix flattens row-major; a strided view reads its own rows
    cube = _loop_to_limbs(vals[:24], n).reshape(4, 6, n)
    assert jf.from_limbs(cube) == vals[:24]
    strided = cube[::2, 1:4]
    assert not strided.flags.c_contiguous
    assert jf.from_limbs(strided) == _loop_from_limbs(
        np.ascontiguousarray(strided), n
    ) == [vals[r * 6 + c] for r in (0, 2) for c in (1, 2, 3)]
    with pytest.raises(ValueError):
        jf.from_limbs(np.zeros(n + 1, dtype=np.uint32))


@pytest.mark.parametrize("field", FIELDS + [Field255])
def test_to_limbs_refuses_what_it_used_to_mask(field):
    """A value of 32 n bits or more, or a negative one, is no field element:
    the per-limb loop masked the one and two's-complemented the other."""
    jf = JField(field)
    top = 1 << (32 * jf.n)
    assert jf.from_limbs(jf.to_limbs([top - 1])) == [top - 1]
    for bad in (top, -1):
        with pytest.raises(OverflowError):
            jf.to_limbs([0, bad])


@pytest.mark.parametrize("field", FIELDS)
def test_add_sub(field):
    jf = JField(field)
    a, b = _pairs(field)
    la, lb = jf.to_limbs(a), jf.to_limbs(b)
    got_add = jf.from_limbs(np.asarray(jf.add(la, lb)))
    got_sub = jf.from_limbs(np.asarray(jf.sub(la, lb)))
    for i, (x, y) in enumerate(zip(a, b)):
        assert got_add[i] == field.add(x, y), (i, x, y)
        assert got_sub[i] == field.sub(x, y), (i, x, y)


@pytest.mark.parametrize("field", FIELDS)
def test_mont_mul(field):
    jf = JField(field)
    a, b = _pairs(field)
    la, lb = jf.to_limbs(a), jf.to_limbs(b)
    ma, mb = jf.to_mont(la), jf.to_mont(lb)
    got = jf.from_limbs(np.asarray(jf.from_mont(jf.mont_mul(ma, mb))))
    for i, (x, y) in enumerate(zip(a, b)):
        assert got[i] == field.mul(x, y), (i, x, y)


@pytest.mark.parametrize("field", FIELDS)
def test_mont_roundtrip(field):
    jf = JField(field)
    vals = _edge_values(field)
    limbs = jf.to_limbs(vals)
    back = jf.from_limbs(np.asarray(jf.from_mont(jf.to_mont(limbs))))
    assert back == vals


@pytest.mark.parametrize(
    "field",
    [
        Field64,
        # Field128 Fermat chain = 127 sequential CIOS muls in one scan:
        # ~400 s cold compile; batch_inv[Field128] covers the same math.
        pytest.param(Field128, marks=pytest.mark.slow),
    ],
)
def test_inv(field):
    jf = JField(field)
    rng = random.Random(3)
    vals = [1, 2, field.MODULUS - 1] + [rng.randrange(1, field.MODULUS) for _ in range(20)]
    m = jf.to_mont(jf.to_limbs(vals))
    got = jf.from_limbs(np.asarray(jf.from_mont(jf.inv_mont(m))))
    for i, v in enumerate(vals):
        assert got[i] == field.inv(v), (i, v)


@pytest.mark.parametrize("field", FIELDS)
def test_batch_inv(field):
    jf = JField(field)
    rng = random.Random(4)
    vals = [rng.randrange(1, field.MODULUS) for _ in range(13)]
    m = jf.to_mont(jf.to_limbs(vals))
    got = jf.from_limbs(np.asarray(jf.from_mont(jf.batch_inv_mont(m, axis=0))))
    for i, v in enumerate(vals):
        assert got[i] == field.inv(v), (i, v)


@pytest.mark.parametrize("field", FIELDS)
def test_sum_and_cumprod(field):
    jf = JField(field)
    rng = random.Random(5)
    vals = [rng.randrange(field.MODULUS) for _ in range(11)]
    limbs = jf.to_limbs(vals)
    got = jf.from_limbs(np.asarray(jf.sum(limbs, axis=0)))
    want = 0
    for v in vals:
        want = field.add(want, v)
    assert got == [want]

    m = jf.to_mont(limbs)
    got_cp = jf.from_limbs(np.asarray(jf.from_mont(jf.cumprod_mont(m, axis=0))))
    acc = 1
    for i, v in enumerate(vals):
        acc = field.mul(acc, v)
        assert got_cp[i] == acc


@pytest.mark.parametrize("field", FIELDS)
def test_horner(field):
    from janus_tpu.fields import poly_eval

    jf = JField(field)
    rng = random.Random(6)
    coeffs = [rng.randrange(field.MODULUS) for _ in range(9)]
    xs = [rng.randrange(field.MODULUS) for _ in range(4)]
    mc = jf.to_mont(jf.to_limbs(coeffs))  # (9, n)
    mx = jf.to_mont(jf.to_limbs(xs))  # (4, n)
    mc_b = np.broadcast_to(np.asarray(mc), (4, 9, jf.n))
    got = jf.from_limbs(np.asarray(jf.from_mont(jf.horner_mont(mc_b, mx))))
    for i, x in enumerate(xs):
        assert got[i] == poly_eval(field, coeffs, x), i


@pytest.mark.parametrize("field", FIELDS)
def test_ntt_eval_matches_per_point(field):
    """ntt_eval_mont at all P-th roots == oracle per-point evaluation.

    Exercises the full bit-reversal + per-stage twiddle construction used by
    BatchedPrio3 for wide-vector gadget evaluation (prepare.py), at P large
    enough for multiple butterfly stages.
    """
    from janus_tpu.fields import poly_eval

    import jax.numpy as jnp

    P = 16
    p = field.MODULUS
    w = field.root(P)
    jf = JField(field)
    rng = random.Random(11)
    B = 3
    coeffs = [[rng.randrange(p) for _ in range(P)] for _ in range(B)]
    logp = P.bit_length() - 1
    bitrev = np.array([int(format(i, f"0{logp}b")[::-1], 2) for i in range(P)], dtype=np.int32)

    def mont_np(x):
        return jf._int_to_limbs_np((x % p) * (1 << (32 * jf.n)) % p)

    tw_stages = []
    m = 2
    while m <= P:
        w_m = pow(w, P // m, p)
        tw_stages.append(jnp.asarray(np.stack([mont_np(pow(w_m, j, p)) for j in range(m // 2)])))
        m *= 2
    carr = jnp.asarray(jf.to_limbs([x for row in coeffs for x in row]).reshape(B, P, jf.n))
    got = jf.from_limbs(np.asarray(jf.ntt_eval_mont(carr, bitrev, tw_stages)).reshape(B * P, jf.n))
    for b in range(B):
        for j in range(P):
            expect = poly_eval(field, coeffs[b], pow(w, j, p))
            assert got[b * P + j] == expect, (b, j)


@pytest.mark.parametrize("field", FIELDS)
def test_batched_shapes(field):
    """Ops broadcast over leading axes (the report axis)."""
    jf = JField(field)
    rng = np.random.default_rng(0)
    a = rng.integers(0, 1 << 32, size=(3, 5, jf.n), dtype=np.uint32)
    # force canonical: zero the top limb to stay < p
    a[..., -1] = 0
    b = np.array(a[::-1])
    s = np.asarray(jf.add(a, b))
    assert s.shape == (3, 5, jf.n)
    m = np.asarray(jf.mont_mul(jf.to_mont(a), jf.to_mont(b)))
    assert m.shape == (3, 5, jf.n)


@pytest.mark.slow
@pytest.mark.parametrize(
    "fields,widths",
    [
        pytest.param(("Field64",), (5, 64), id="narrow"),
        pytest.param(("Field64", "Field128"), (1, 100, 1023), id="wide"),
    ],
)
def test_poly_eval_bsgs_matches_horner_wide(fields, widths):
    # Slow tier: each (field, C) shape cold-compiles for minutes under the
    # 8-virtual-device CPU conftest; the identity also holds on the real
    # chip via bench parity.
    """poly_eval_mont (baby-step/giant-step) is limb-identical to Horner —
    _gpoly_at routes every glen >= 64 circuit through it."""
    import random

    import jax.numpy as jnp

    from janus_tpu import fields as fmod

    random.seed(11)
    for fname in fields:
        F = getattr(fmod, fname)
        jf = JField(F)
        for C in widths:
            B = 2
            coeffs = jnp.asarray(
                jf.to_limbs([random.randrange(F.MODULUS) for _ in range(B * C)]).reshape(
                    B, C, jf.n
                )
            )
            xs = [0, 1] + [random.randrange(F.MODULUS) for _ in range(B - 2)]
            x = jf.to_mont(jnp.asarray(jf.to_limbs(xs[:B]).reshape(B, jf.n)))
            a = np.asarray(jf.horner_mont(coeffs, x))
            b = np.asarray(jf.poly_eval_mont(coeffs, x))
            assert np.array_equal(a, b), (F.__name__, C)


@pytest.mark.parametrize("field", FIELDS)
@pytest.mark.parametrize("count", [1, 2, 7, 16, 316])
def test_pow_range_matches_cumprod(field, count):
    """pow_range_mont (baby-step/giant-step power table) is limb-identical
    to the cumulative-product form it replaces in the planar coefficient
    generation (histogram r_ch, SumVec klu slabs)."""
    import jax.numpy as jnp

    jf = JField(field)
    random.seed(17)
    xs = [1, field.MODULUS - 1] + [random.randrange(field.MODULUS) for _ in range(3)]
    x = jf.to_mont(jnp.asarray(jf.to_limbs(xs).reshape(len(xs), jf.n)))
    via_cum = jf.cumprod_mont(
        jnp.broadcast_to(x[:, None, :], (len(xs), count, jf.n)), axis=1
    )
    via_bsgs = jf.pow_range_mont(x, count)
    assert np.array_equal(np.asarray(via_cum), np.asarray(via_bsgs))
