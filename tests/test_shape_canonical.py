"""Pow2 shape canonicalization (vdaf/canonical.py, ISSUE 8).

Plan math and fallback preconditions are pure Python (free).  The parity
sweep drives the CANONICAL backend with reports sharded by the task's
ACTUAL vdaf and asserts byte equality with the task's own oracle — for
every prepare output (out share, corrected seed, verifier share,
joint-rand part), both aggregator sides, mixed-task mega-batches, and
both field_backend layouts.  One small always-on case guards the fast
tier; the full matrix is slow-marked and runs in ``./ci.sh coldstart``.
"""

import numpy as np
import pytest

from janus_tpu.fields import next_power_of_2
from janus_tpu.utils.test_util import det_rng
from janus_tpu.vdaf.backend import OracleBackend, TpuBackend, vdaf_shape_key
from janus_tpu.vdaf.canonical import (
    canonical_vdaf_for,
    canonicalization_reason,
    clip_agg_vector,
    executor_shape,
)
from janus_tpu.vdaf.instances import (
    prio3_count,
    prio3_histogram,
    prio3_sum,
    prio3_sum_vec,
    prio3_sum_vec_field64_multiproof_hmacsha256_aes128,
)

# ---------------------------------------------------------------------------
# plan math + fallback preconditions (pure Python)


def test_histogram_lengths_bucket_by_pow2_calls():
    # chunk 2: calls 3 (P=4) is its own ceiling; length 5 rounds to 6
    c5 = canonical_vdaf_for(prio3_histogram(5, 2))
    assert c5.flp.valid.length == 6
    assert canonical_vdaf_for(prio3_histogram(6, 2)) is None  # already canonical
    # non-ceiling lengths in one bucket share the TAGGED canonical key;
    # the ceiling shape keeps its exact (maskless, planar-capable) key —
    # which must never collide with the canonical entry, or first-resolver
    # order would decide the backend mode for the whole bucket
    k7, c7 = executor_shape(prio3_histogram(7, 3))
    k8, c8 = executor_shape(prio3_histogram(8, 3))
    assert k7 == k8 and c7.flp.valid.length == c8.flp.valid.length == 9
    k9, c9 = executor_shape(prio3_histogram(9, 3))
    assert c9 is None and k9 == vdaf_shape_key(prio3_histogram(9, 3))
    assert k9 != k7
    # calls 5 (P=8) rounds to the class ceiling 7 -> length 14
    assert canonical_vdaf_for(prio3_histogram(9, 2)).flp.valid.length == 14
    # bucket count over a wide length range is O(log): every canonical
    # call count is a power of two or P-1, and P never changes
    for length in range(1, 200):
        vdaf = prio3_histogram(length, 4)
        canon = canonical_vdaf_for(vdaf) or vdaf
        calls = canon.flp.valid.GADGET_CALLS[0]
        P = next_power_of_2(1 + vdaf.flp.valid.GADGET_CALLS[0])
        assert next_power_of_2(1 + calls) == P, length
        assert calls in (P - 1, next_power_of_2(calls)), length


def test_canonical_twin_is_a_fixpoint():
    for vdaf in (
        prio3_histogram(9, 2),
        prio3_sum(5),
        prio3_sum_vec(3, 3, 2),
    ):
        canon = canonical_vdaf_for(vdaf)
        assert canon is not None
        assert canonical_vdaf_for(canon) is None  # twin of twin = itself
        assert executor_shape(vdaf)[0] == ("canon",) + vdaf_shape_key(canon)


def test_sum_and_sumvec_plans():
    assert canonical_vdaf_for(prio3_sum(5)).flp.valid.bits == 7
    assert canonical_vdaf_for(prio3_sum(8)) is None  # 8 = pow2: own bucket
    csv = canonical_vdaf_for(prio3_sum_vec(3, 3, 2))
    assert (csv.flp.valid.length, csv.flp.valid.bits) == (4, 3)
    # canonical JR stream is a superset of the actual (prefix-stable)
    assert csv.flp.JOINT_RAND_LEN >= prio3_sum_vec(3, 3, 2).flp.JOINT_RAND_LEN


def test_unsupported_shapes_fall_back_to_exact_compile():
    # Count has no parameter axis; multiproof rand streams are not
    # prefix-stable; Poplar1 is not Prio3.  Each keeps its exact key.
    for vdaf in (
        prio3_count(),
        prio3_sum_vec_field64_multiproof_hmacsha256_aes128(2, 4, 1, 2),
    ):
        assert canonical_vdaf_for(vdaf) is None
        assert canonicalization_reason(vdaf) != ""
        key, canon = executor_shape(vdaf)
        assert canon is None and key == vdaf_shape_key(vdaf)
    # the disabled switch also keeps exact keys for canonicalizable shapes
    h = prio3_histogram(5, 2)
    key, canon = executor_shape(h, enabled=False)
    assert canon is None and key == vdaf_shape_key(h)


def test_clip_agg_vector_requires_zero_tail():
    h5 = prio3_histogram(5, 2)
    assert clip_agg_vector(h5, [1, 2, 3, 4, 5, 0]) == [1, 2, 3, 4, 5]
    assert clip_agg_vector(h5, [1, 2, 3, 4, 5]) == [1, 2, 3, 4, 5]
    from janus_tpu.vdaf.prio3 import VdafError

    with pytest.raises(VdafError):
        clip_agg_vector(h5, [1, 2, 3, 4, 5, 9])  # broken parity must be LOUD


# ---------------------------------------------------------------------------
# length-selected TurboSHAKE absorb (the joint-rand binder mechanism)


def test_select_absorb_matches_host_oracle():
    from janus_tpu.ops.keccak_jax import xof_turboshake128_batch_select
    from janus_tpu.xof import XofTurboShake128

    rng = np.random.default_rng(8)
    dst = b"\x01\x00\x00\x00\x00\x03\x00\x07"
    lens = np.array([0, 5, 144, 145, 168, 200, 299, 300], dtype=np.int32)
    B, Bmax = len(lens), 300
    seed = rng.integers(0, 256, (B, 16), dtype=np.uint8)
    binder = np.zeros((B, Bmax), dtype=np.uint8)
    for i, L in enumerate(lens):
        binder[i, :L] = rng.integers(0, 256, L, dtype=np.uint8)
    got = np.asarray(
        xof_turboshake128_batch_select(seed, dst, binder, 16, lens)
    )
    for i, L in enumerate(lens):
        want = XofTurboShake128(bytes(seed[i]), dst, bytes(binder[i, :L])).next(16)
        assert bytes(got[i]) == want, (i, L)


# ---------------------------------------------------------------------------
# oracle-parity sweep (device tier)


def _reports(vdaf, meas_list, seed, agg_id):
    rng = det_rng(seed)
    rows = []
    for m in meas_list:
        nonce = rng(vdaf.NONCE_SIZE)
        ps, shares = vdaf.shard(m, nonce, rng(vdaf.RAND_SIZE))
        rows.append((nonce, ps, shares[agg_id]))
    return rows


def _assert_parity(backend, vdaf, meas_list, agg_id, seed="p"):
    vk = b"\x07" * vdaf.VERIFY_KEY_SIZE
    rows = _reports(vdaf, meas_list, seed + str(agg_id), agg_id)
    reqs = [(vk, rows, vdaf)]
    got = backend.launch_prep_init_multi(
        backend.stage_prep_init_multi(agg_id, reqs), reqs
    )[0]
    want = OracleBackend(vdaf).prep_init_batch(vk, agg_id, rows)
    _assert_same_outcomes(got, want, agg_id)
    return got, want


def _assert_same_outcomes(got, want, agg_id):
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g[0].out_share == w[0].out_share, (agg_id, i)
        assert g[0].corrected_joint_rand_seed == w[0].corrected_joint_rand_seed
        assert g[1].verifiers_share == w[1].verifiers_share, (agg_id, i)
        assert g[1].joint_rand_part == w[1].joint_rand_part, (agg_id, i)


@pytest.fixture(scope="module")
def hist_canonical_backend():
    """ONE canonical backend for the Histogram(*, chunk=2, P=4) bucket —
    shared by every case in this module so the fast tier pays its two
    compiles (one per agg side) once."""
    return TpuBackend(canonical_vdaf_for(prio3_histogram(5, 2)), canonical=True)


def test_histogram_padded_parity_and_mixed_batch(hist_canonical_backend):
    """Always-on representative: meas-column padding + the length-selected
    joint-rand binder, leader AND helper, with two different-length tasks
    riding ONE staged mega-batch."""
    backend = hist_canonical_backend
    h5, h6 = prio3_histogram(5, 2), prio3_histogram(6, 2)
    _assert_parity(backend, h5, [0, 4, 2], 0)
    _assert_parity(backend, h5, [0, 4, 2], 1)
    for agg_id in (0, 1):
        vk5, vk6 = b"\x05" * 16, b"\x06" * 16
        r5 = _reports(h5, [0, 4], "mix5", agg_id)
        r6 = _reports(h6, [5, 1, 3], "mix6", agg_id)
        reqs = [(vk5, r5, h5), (vk6, r6, h6)]
        got5, got6 = backend.launch_prep_init_multi(
            backend.stage_prep_init_multi(agg_id, reqs), reqs
        )
        for vdaf, vk, rows, got in ((h5, vk5, r5, got5), (h6, vk6, r6, got6)):
            want = OracleBackend(vdaf).prep_init_batch(vk, agg_id, rows)
            for g, w in zip(got, want):
                assert g[0].out_share == w[0].out_share
                assert g[1].verifiers_share == w[1].verifiers_share
                assert g[1].joint_rand_part == w[1].joint_rand_part
            # out shares come back at the TASK's length, not the bucket's
            assert all(len(g[0].out_share) == vdaf.flp.OUTPUT_LEN for g in got)


@pytest.mark.parametrize("agg_id", [0, 1])
def test_unmarshal_slices_each_row_from_the_flush(hist_canonical_backend, agg_id):
    """The out-share and verifier matrices become ints once a flush and are
    sliced per row: with a bucket wider than the task and a row sent to the
    oracle in the middle, every row still gets ITS OWN slice, in order."""
    backend = hist_canonical_backend
    h5 = prio3_histogram(5, 2)
    assert backend.vdaf.flp.OUTPUT_LEN > h5.flp.OUTPUT_LEN
    vk = b"\x07" * h5.VERIFY_KEY_SIZE
    rows = _reports(h5, [0, 4, 2, 1, 3], "slice" + str(agg_id), agg_id)
    staged = backend.stage_prep_init_multi(agg_id, [(vk, rows, h5)])
    out = {
        k: np.array(v)[: len(rows)]
        for k, v in backend._prep_fn(agg_id)(staged.placed).items()
    }
    assert out["ok"].all()
    # row 2 overflowed the device margin, says the device: its limbs are
    # junk and the oracle serves it
    out["ok"][2] = False
    out["out_share"][2] = 0xFFFFFFFF
    out["verifiers"][2] = 0xFFFFFFFF
    got = backend._unmarshal_prep(vk, agg_id, rows, out, actual_vdaf=h5)
    _assert_same_outcomes(
        got, OracleBackend(h5).prep_init_batch(vk, agg_id, rows), agg_id
    )
    # distinct measurements: a row's share is no neighbour's
    assert len({tuple(g[0].out_share) for g in got}) == len(rows)


def test_combine_through_canonical_backend(hist_canonical_backend):
    """prep_shares_to_prep is length-independent across a bucket: actual
    tasks' share rows combine bit-exactly on the canonical backend."""
    h5 = prio3_histogram(5, 2)
    vk = b"\x07" * 16
    o = OracleBackend(h5)
    p0 = o.prep_init_batch(vk, 0, _reports(h5, [0, 4, 2], "c0", 0))
    p1 = o.prep_init_batch(vk, 1, _reports(h5, [0, 4, 2], "c0", 1))
    pairs = [[a[1], b[1]] for a, b in zip(p0, p1)]
    assert hist_canonical_backend.prep_shares_to_prep_batch(
        pairs
    ) == o.prep_shares_to_prep_batch(pairs)


def test_tampered_report_rejected_identically(hist_canonical_backend):
    """Adversarial content: a corrupted gadget polynomial must fail the
    decide identically through the canonical combine (the gk mask is what
    keeps padded evaluation points out of an attacker's reach)."""
    h5 = prio3_histogram(5, 2)
    vk = b"\x07" * 16
    rows0 = _reports(h5, [2], "t", 0)
    bad = rows0[0][2]
    tampered = type(bad)(
        meas_share=list(bad.meas_share),
        proofs_share=[(x + 1) % h5.flp.field.MODULUS for x in bad.proofs_share],
        joint_rand_blind=bad.joint_rand_blind,
        share_seed=None,
    )
    rows0 = [(rows0[0][0], rows0[0][1], tampered)]
    # prepare both sides (tampered leader share), then combine must reject
    req0 = [(vk, rows0, h5)]
    g0 = hist_canonical_backend.launch_prep_init_multi(
        hist_canonical_backend.stage_prep_init_multi(0, req0), req0
    )[0]
    w0 = OracleBackend(h5).prep_init_batch(vk, 0, rows0)
    rows1 = _reports(h5, [2], "t", 1)
    w1 = OracleBackend(h5).prep_init_batch(vk, 1, rows1)
    pairs = [[g0[0][1], w1[0][1]]]
    got_c = hist_canonical_backend.prep_shares_to_prep_batch(pairs)
    want_c = OracleBackend(h5).prep_shares_to_prep_batch(
        [[w0[0][1], w1[0][1]]]
    )
    assert type(got_c[0]) is type(want_c[0])  # both VdafError (rejected)
    assert g0[0][1].verifiers_share == w0[0][1].verifiers_share


def _sumvec64():
    """Single-proof TurboSHAKE SumVec over Field64: the Field64 leg of the
    parity sweep (the stock Field64 instance is multiproof, which falls
    back by precondition — this direct construction is canonicalizable)."""
    from janus_tpu.fields import Field64
    from janus_tpu.flp import FlpGeneric, SumVec
    from janus_tpu.vdaf.prio3 import ALG_PRIO3_SUMVEC, Prio3

    return Prio3(
        FlpGeneric(SumVec(3, 3, 2, field=Field64)), ALG_PRIO3_SUMVEC
    )


@pytest.mark.slow
@pytest.mark.parametrize("field_backend", ["vpu", "mxu"])
@pytest.mark.parametrize(
    "name,vdaf,meas",
    [
        ("hist9/2", prio3_histogram(9, 2), [0, 8, 3]),  # calls 5 -> 7 (masked)
        ("sum5", prio3_sum(5), [0, 31, 7]),  # bits 5 -> 7
        ("sumvec3x3", prio3_sum_vec(3, 3, 2), [[0, 0, 0], [7, 1, 5], [3, 3, 3]]),
        ("sumvec3x3-f64", _sumvec64(), [[0, 0, 0], [7, 1, 5], [3, 3, 3]]),
    ],
)
def test_canonical_parity_sweep(name, vdaf, meas, field_backend):
    """Full matrix: every canonicalizable circuit family with ACTIVE call
    masking (calls < bucket ceiling), both aggregator sides, both field
    layouts.  Slow tier; ./ci.sh coldstart runs it."""
    canon = canonical_vdaf_for(vdaf)
    assert canon is not None, name
    backend = TpuBackend(canon, field_backend=field_backend, canonical=True)
    for agg_id in (0, 1):
        _assert_parity(backend, vdaf, meas, agg_id, seed=name)


def test_oracle_config_never_caches_under_canonical_key():
    """Regression (review-found): with ``vdaf_backend: oracle`` the driver
    must resolve a canonicalizable task under its EXACT key — an oracle
    backend cached under the shared canonical bucket key would serve every
    other bucket member a wrong-shaped circuit."""
    from janus_tpu.aggregator import AggregationJobDriver, DriverConfig
    from janus_tpu.executor import ExecutorConfig, reset_global_executor
    from janus_tpu.vdaf.backend import OracleBackend

    reset_global_executor()
    try:
        driver = AggregationJobDriver(
            None,
            None,
            DriverConfig(
                vdaf_backend="oracle",
                device_executor=ExecutorConfig(enabled=True),
            ),
        )
        h5 = prio3_histogram(5, 2)
        canon_key, canon = executor_shape(h5)
        assert canon is not None

        class _Task:
            task_id = "t-oracle"

        b = driver._backend_for(_Task(), h5)
        assert isinstance(b, OracleBackend) and b.vdaf is h5
        assert canon_key not in driver._backends
        assert vdaf_shape_key(h5) in driver._backends
        assert driver._executor.cached_backend(canon_key) is None
    finally:
        reset_global_executor()


# ---------------------------------------------------------------------------
# executor integration: one cached backend per bucket


def test_two_lengths_share_one_cached_backend_and_executable():
    """The ISSUE 8 satellite assertion: two tasks with different histogram
    lengths in the same pow2 bucket (7 and 8, chunk 3 — both NON-ceiling,
    twin length 9) resolve to ONE cached backend in the executor, their
    mega-batches share ONE bucket/flush, and the results stay per-task
    oracle-exact."""
    import asyncio

    from janus_tpu.executor import DeviceExecutor, ExecutorConfig
    from janus_tpu.vdaf.backend import make_backend

    h7, h8 = prio3_histogram(7, 3), prio3_histogram(8, 3)
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.05, flush_max_rows=1024))
    k7, c7 = executor_shape(h7)
    k8, c8 = executor_shape(h8)
    assert k7 == k8
    b7 = ex.backend_for(k7, lambda: make_backend(c7, "tpu", canonical=True))
    b8 = ex.backend_for(
        k8, lambda: pytest.fail("second resolver must hit the cache")
    )
    assert b7 is b8, "one bucket -> ONE cached backend (and compiled graphs)"

    vk7, vk8 = b"\x05" * 16, b"\x06" * 16
    r7 = _reports(h7, [0, 6], "ex7", 0)
    r8 = _reports(h8, [7, 1, 3], "ex8", 0)

    async def go():
        return await asyncio.gather(
            ex.submit(k7, "prep_init", (vk7, r7, h7), backend=b7),
            ex.submit(k8, "prep_init", (vk8, r8, h8), backend=b8),
        )

    loop = asyncio.new_event_loop()
    try:
        got7, got8 = loop.run_until_complete(asyncio.wait_for(go(), 300.0))
    finally:
        loop.close()
    ex.shutdown()
    stats = next(iter(ex.stats().values()))
    assert stats["flushes"] == 1 and stats["flushed_jobs"] == 2
    for vdaf, vk, rows, got in ((h7, vk7, r7, got7), (h8, vk8, r8, got8)):
        want = OracleBackend(vdaf).prep_init_batch(vk, 0, rows)
        for g, w in zip(got, want):
            assert g[0].out_share == w[0].out_share
            assert g[1].verifiers_share == w[1].verifiers_share
