"""MXU limb-plane contraction layer (JField.mat_mul_mont) vs the oracle field.

Property/fuzz coverage for ISSUE 7: the dot_general-based modular matmul
primitives must be EXACT — limb-identical to arbitrary-precision integer
arithmetic — for random operands and for the adversarial ones the lazy-carry
bound analysis (README "MXU field arithmetic") names: 0, 1, p-1, R-boundary
values, and carry-saturating all-0xFF digit rows at the DOT_MAX_K contraction
cap.  Both fields, matvec and matmul shapes, shared-constant and per-batch
right-hand sides, plus the chunked >DOT_MAX_K split and the batched
Montgomery inversion that replaced tensor-wide Fermat chains.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from janus_tpu.fields import Field64, Field128
from janus_tpu.ops import field_jax
from janus_tpu.ops.field_jax import DOT_MAX_K, JField

FIELDS = [Field64, Field128]


def _adversarial(field, jf):
    """Edge operands: identity/boundary residues + R-boundary + max-digit."""
    p = field.MODULUS
    R = 1 << (32 * jf.n)
    vals = [0, 1, 2, p - 1, p - 2, (R - 1) % p, R % p, (R + 1) % p]
    # carry-saturating digit patterns: long runs of 0xFF bytes
    vals += [((1 << b) - 1) % p for b in (8, 16, 32, 32 * jf.n - 1, 32 * jf.n)]
    return [v % p for v in vals]


def _fill(field, jf, shape, seed):
    """Int tensor mixing adversarial values with random residues."""
    rng = random.Random(seed)
    adv = _adversarial(field, jf)
    total = int(np.prod(shape))
    vals = [
        adv[i] if i < len(adv) else rng.randrange(field.MODULUS)
        for i in range(total)
    ]
    rng.shuffle(vals)
    return np.array(vals, dtype=object).reshape(shape)

def _limbs(jf, ints):
    flat = [int(v) for v in ints.reshape(-1)]
    return jf.to_limbs(flat).reshape(ints.shape + (jf.n,))


def _ints(jf, limbs):
    arr = np.asarray(limbs)
    flat = jf.from_limbs(arr.reshape(-1, jf.n))
    return np.array(flat, dtype=object).reshape(arr.shape[:-1])


def _oracle_mat_mul_mont(field, jf, a, b):
    """sum_k a[.., k, m] * b[.., k, v] * R^-1 mod p via python ints."""
    p = field.MODULUS
    r_inv = pow(1 << (32 * jf.n), p - 2, p)
    *batch, K, M = a.shape
    N = b.shape[-1]
    out = np.empty(tuple(batch) + (M, N), dtype=object)
    for idx in np.ndindex(*batch):
        for m in range(M):
            for v in range(N):
                acc = sum(int(a[idx + (k, m)]) * int(b[idx + (k, v)]) for k in range(K))
                out[idx + (m, v)] = acc * r_inv % p
    return out


@pytest.mark.parametrize("field", FIELDS)
@pytest.mark.parametrize("shape", [(2, 5, 3, 2), (1, 11, 2, 4)], ids=["b2", "b1"])
def test_mat_mul_mont_fuzz(field, shape):
    """Batched matmul vs arbitrary-precision ints, adversarial + random."""
    jf = JField(field)
    B, K, M, N = shape
    a = _fill(field, jf, (B, K, M), seed=hash((field.MODULUS, shape, 0)) & 0xFFFF)
    b = _fill(field, jf, (B, K, N), seed=hash((field.MODULUS, shape, 1)) & 0xFFFF)
    got = _ints(jf, jf.mat_mul_mont(_limbs(jf, a), _limbs(jf, b)))
    want = _oracle_mat_mul_mont(field, jf, a, b)
    assert (got == want).all()


@pytest.mark.parametrize("field", FIELDS)
def test_mat_mul_mont_shared_rhs(field):
    """(K, N, n) rhs without batch dims — the host-constant matrix form
    used for the gadget Vandermonde table — broadcasts over the batch."""
    jf = JField(field)
    B, K, M, N = 3, 6, 2, 3
    a = _fill(field, jf, (B, K, M), seed=21)
    b = _fill(field, jf, (K, N), seed=22)
    got = _ints(jf, jf.mat_mul_mont(_limbs(jf, a), _limbs(jf, b)))
    want = np.empty((B, M, N), dtype=object)
    for bi in range(B):
        want[bi] = _oracle_mat_mul_mont(field, jf, a[bi], b)
    assert (got == want).all()


@pytest.mark.parametrize("field", FIELDS)
def test_dot_mont_matches_mont_mul_sum(field):
    """dot_mont is limb-identical to the sum(mont_mul(...)) tree it
    replaces in the wire-evaluation hot loop (matvec shape)."""
    jf = JField(field)
    B, K, A = 4, 7, 3
    wires = _fill(field, jf, (B, K, A), seed=31)
    lag = _fill(field, jf, (B, K), seed=32)
    lw, ll = _limbs(jf, wires), _limbs(jf, lag)
    got = np.asarray(jf.dot_mont(lw, ll))
    want = np.asarray(jf.sum(jf.mont_mul(lw, ll[:, :, None, :]), axis=1))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("field", FIELDS)
def test_mat_mul_mont_carry_saturation(field):
    """K == DOT_MAX_K rows of all-0xFF digits: every per-digit-pair dot
    accumulates K * 255^2 — the documented u32 ceiling.  The result must
    still be exact, proving the lazy-carry bound is not merely probable."""
    jf = JField(field)
    K = DOT_MAX_K
    maxv = (1 << (32 * jf.n)) - 1  # all digits 255 (deliberately non-canonical)
    a = np.full((K, 1), maxv, dtype=object)
    got = _ints(jf, jf.mat_mul_mont(_limbs(jf, a), _limbs(jf, a)))
    p = field.MODULUS
    r_inv = pow(1 << (32 * jf.n), p - 2, p)
    want = K * maxv * maxv * r_inv % p
    assert got[0][0] == want


def test_mat_mul_mont_chunked_long_k(monkeypatch):
    """Contractions longer than DOT_MAX_K split into modular-added chunks
    (shrunk cap so the split runs at test size), including a ragged tail."""
    field = Field64
    jf = JField(field)
    monkeypatch.setattr(field_jax, "DOT_MAX_K", 4)
    B, K, M, N = 2, 11, 2, 2  # 4 + 4 + 3: two full chunks + ragged tail
    a = _fill(field, jf, (B, K, M), seed=41)
    b = _fill(field, jf, (B, K, N), seed=42)
    got = _ints(jf, jf.mat_mul_mont(_limbs(jf, a), _limbs(jf, b)))
    want = _oracle_mat_mul_mont(field, jf, a, b)
    assert (got == want).all()


@pytest.mark.parametrize("field", FIELDS)
def test_poly_eval_dot_matches_horner(field):
    """The bsgs-as-matmul polynomial evaluation (gadget poly at t under
    mxu) is limb-identical to Horner for narrow and non-square widths."""
    import jax.numpy as jnp

    jf = JField(field)
    rng = random.Random(51)
    for C in (1, 2, 5, 9):
        B = 3
        coeffs = _fill(field, jf, (B, C), seed=50 + C)
        xs = [0, 1] + [rng.randrange(field.MODULUS)]
        x = jf.to_mont(jnp.asarray(jf.to_limbs(xs).reshape(B, jf.n)))
        lc = jnp.asarray(_limbs(jf, coeffs))
        got = np.asarray(jf.poly_eval_dot(lc, x))
        want = np.asarray(jf.horner_mont(lc, x))
        assert np.array_equal(got, want), (field.__name__, C)


@pytest.mark.parametrize(
    "field",
    [
        Field64,
        # the one-element Fermat chain still cold-compiles the 127-step
        # scan on XLA:CPU — same budget note as test_ops_field.test_inv
        pytest.param(Field128, marks=pytest.mark.slow),
    ],
)
def test_inv_mont_batched_matches_fermat(field):
    """Vector inv_mont now routes through Montgomery batch inversion (one
    Fermat chain total); results stay limb-identical to the per-element
    chain, inv(0) == 0 included, and leading batch shape is preserved."""
    jf = JField(field)
    rng = random.Random(61)
    vals = [0, 1, 2, field.MODULUS - 1, 0] + [
        rng.randrange(1, field.MODULUS) for _ in range(7)
    ]
    m = jf.to_mont(jf.to_limbs(vals))
    got = jf.from_limbs(np.asarray(jf.from_mont(jf.inv_mont(m))))
    for i, v in enumerate(vals):
        assert got[i] == (field.inv(v) if v else 0), (i, v)
    # 2-D batch shape round-trips
    m2 = np.asarray(m).reshape(3, 4, jf.n)
    got2 = np.asarray(jf.inv_mont(m2))
    assert got2.shape == (3, 4, jf.n)
    assert np.array_equal(got2.reshape(12, jf.n), np.asarray(jf.inv_mont(m)))


def test_inv_mont_scalar_path_unchanged():
    """A single element (no batch) still runs the plain Fermat chain."""
    field = Field64
    jf = JField(field)
    v = 123456789
    m = jf.to_mont(jf.to_limbs([v]))[0]
    got = jf.from_limbs(np.asarray(jf.from_mont(jf.inv_mont(m)))[None])
    assert got == [field.inv(v)]


# -- toggle plumbing -------------------------------------------------------


def test_field_backend_plumbing(monkeypatch):
    """The config toggle threads make_backend -> TpuBackend/MeshBackend ->
    BatchedPrio3, honors the JANUS_TPU_FIELD_BACKEND env default, rejects
    unknown values, and survives the executor's mesh upgrade."""
    from janus_tpu.vdaf.backend import (
        MeshBackend,
        OracleBackend,
        VdafError,
        default_field_backend,
        make_backend,
    )
    from janus_tpu.vdaf.instances import prio3_count

    vdaf = prio3_count()
    be = make_backend(vdaf, "tpu", field_backend="mxu")
    assert be.field_backend == "mxu" and be.bp.field_backend == "mxu"
    assert make_backend(vdaf, "tpu").field_backend == "vpu"
    monkeypatch.setenv("JANUS_TPU_FIELD_BACKEND", "mxu")
    assert default_field_backend() == "mxu"
    assert make_backend(vdaf, "tpu").field_backend == "mxu"
    monkeypatch.delenv("JANUS_TPU_FIELD_BACKEND")
    with pytest.raises(VdafError):
        make_backend(vdaf, "tpu", field_backend="tensor-cores")
    with pytest.raises(ValueError):
        from janus_tpu.ops.prepare import BatchedPrio3

        BatchedPrio3(vdaf, field_backend="simd")
    # the oracle has no device field layer and ignores the toggle
    assert isinstance(make_backend(vdaf, "oracle", field_backend="mxu"), OracleBackend)
    # the executor's mesh upgrade preserves the layout choice
    import jax

    mesh = MeshBackend(vdaf, devices=jax.devices("cpu"), field_backend="mxu")
    assert mesh.field_backend == "mxu" and mesh.bp.field_backend == "mxu"


def test_executor_meshify_preserves_field_backend():
    """DeviceExecutor._meshify rebuilds a TpuBackend as MeshBackend with
    the producer's field_backend intact (the transparent-cache criterion)."""
    from janus_tpu.executor.service import DeviceExecutor, ExecutorConfig
    from janus_tpu.vdaf.backend import MeshBackend, TpuBackend
    from janus_tpu.vdaf.instances import prio3_count

    ex = DeviceExecutor(ExecutorConfig(enabled=False))
    try:
        up = ex._meshify(TpuBackend(prio3_count(), field_backend="mxu"))
        assert isinstance(up, MeshBackend)
        assert up.field_backend == "mxu" and up.bp.field_backend == "mxu"
    finally:
        ex.shutdown()


# -- compiled-HLO evidence -------------------------------------------------


def _prep_hlo_text(vdaf, field_backend, B=4):
    """Optimized HLO for the helper-side prep_init graph of ``vdaf``."""
    import jax
    import jax.numpy as jnp

    from janus_tpu.ops.prepare import BatchedPrio3

    bp = BatchedPrio3(vdaf, field_backend=field_backend)
    vk = b"\x2a" * vdaf.VERIFY_KEY_SIZE
    kwargs = dict(
        nonces_u8=jnp.zeros((B, vdaf.NONCE_SIZE), dtype=jnp.uint8),
        share_seeds_u8=jnp.zeros((B, vdaf.xof.SEED_SIZE), dtype=jnp.uint8),
    )
    if vdaf.flp.JOINT_RAND_LEN > 0:
        kwargs["blinds_u8"] = jnp.zeros((B, vdaf.xof.SEED_SIZE), dtype=jnp.uint8)
        kwargs["public_parts_u8"] = jnp.zeros(
            (B, vdaf.num_shares, vdaf.xof.SEED_SIZE), dtype=jnp.uint8
        )
    fn = jax.jit(lambda kw: bp.prep_init(1, verify_key=vk, **kw))
    return fn.lower(kwargs).compile().as_text()


def _count_dots(txt):
    """Lines of the HLO that ARE a dot op or were lowered from one.  The
    text also carries a table of Python function names, and the tests
    below have "dot_general" in theirs, so a bare substring count finds
    one in every module."""
    return sum(
        " dot(" in line or ("dot_general" in line and "op_name=" in line)
        for line in txt.splitlines()
    )


def test_prep_hlo_contains_dot_general_small_hist():
    """Under field_backend=mxu the compiled prepare graph carries the wire
    and gadget contractions as dot ops; under vpu it carries none.  Small
    histogram so the check rides the default suite (the full histogram1024
    twin below is slow-tier)."""
    from janus_tpu.vdaf.instances import prio3_histogram

    vdaf = prio3_histogram(length=2, chunk_length=1)
    assert _count_dots(_prep_hlo_text(vdaf, "mxu")) > 0
    assert _count_dots(_prep_hlo_text(vdaf, "vpu")) == 0


@pytest.mark.slow
def test_prep_hlo_contains_dot_general_histogram1024():
    """ISSUE 7 acceptance: the compiled prepare HLO for histogram1024 under
    field_backend=mxu contains dot ops for the wire/gadget contractions
    (XLA:CPU cold-compiles this graph for ~5 minutes; RUN_SLOW tier)."""
    from janus_tpu.vdaf.instances import prio3_histogram

    vdaf = prio3_histogram(length=1024, chunk_length=316)
    assert _count_dots(_prep_hlo_text(vdaf, "mxu")) > 0
