"""Compile the main path's kernels for a described TPU v5e, without a chip.

The TPU compiler is installed in the sandbox and compiles for a topology
that is described, not attached (``jax.experimental.topologies``).  Each
test lowers one kernel — or one whole small step — at the widths the
served Prio3Histogram(1024, chunk 316) deployment uses and 1,024 rows,
and asks Mosaic/XLA:TPU to compile it: a block that breaks the (8, 128)
tiling rule, a kernel over its VMEM budget or a program that does not fit
HBM fails here, at no chip time.  Nothing runs, so nothing here says a
result is right or fast; the interpret-mode parity tests
(tests/test_prepare.py, tests/test_ops_keccak.py) and ``chip_smoke.py``
do that.

Only one process may hold the TPU library, and it keeps it until it exits,
so every such compile lives in THIS file, in the test's own process, behind
a module-scoped fixture that skips when no topology can be described.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from janus_tpu.ops.field_jax import JField
from janus_tpu.vdaf.instances import prio3_count, prio3_histogram

ROWS = 1024
R = ROWS // 128
U32 = jnp.uint32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the chip; keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def hist():
    """(vdaf, JField) of the served deployment: BASELINE.json configs[2]."""
    vdaf = prio3_histogram(length=1024, chunk_length=316)
    return vdaf, JField(vdaf.flp.field)


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def test_keccak_squeeze_kernel(one_chip, hist):
    from janus_tpu.ops.keccak_pallas import RATE_WORDS, _squeeze_call

    vdaf, jf = hist
    nb = -(-vdaf.flp.MEAS_LEN * jf.n // RATE_WORDS)  # the meas-share stream
    text = _compile(
        lambda p: _squeeze_call(p, nb, False), one_chip, ((RATE_WORDS, R, 128), U32)
    )
    assert "tpu_custom_call" in text


def test_keccak_absorb_kernel(one_chip, hist):
    from janus_tpu.ops.keccak_pallas import RATE, RATE_WORDS, _absorb_call

    vdaf, jf = hist
    # joint-rand part: a 42-byte head, then enc(meas)
    na = (42 + 4 * jf.n * vdaf.flp.MEAS_LEN) // RATE + 1
    text = _compile(
        lambda p: _absorb_call(p, na, False),
        one_chip,
        ((na * RATE_WORDS, R, 128), U32),
    )
    assert "tpu_custom_call" in text


def test_histogram_wire_kernel(one_chip, hist):
    from janus_tpu.ops.flp_pallas import _grid_chunk, wire_evals_planar

    vdaf, jf = hist
    flp, valid = vdaf.flp, vdaf.flp.valid
    chunk, calls = valid.chunk_length, valid.GADGET_CALLS[0]
    nj, uc = _grid_chunk(chunk)
    n = jf.n
    text = _compile(
        lambda *a: wire_evals_planar(jf, flp.MEAS_LEN, chunk, *a),
        one_chip,
        ((R, n, flp.MEAS_LEN, 128), U32),
        ((R, n, flp.PROOF_LEN, 128), U32),
        ((R, n, nj * uc, 128), U32),
        ((R, n, calls, 128), U32),
        ((R, n, calls, 128), U32),
        ((R, n, 128), U32),
        ((R, n, 128), U32),
    )
    assert "tpu_custom_call" in text


def test_histogram_combine_decide_kernel(one_chip, hist):
    from janus_tpu.ops.flp_pallas import _grid_chunk, combine_decide_planar

    vdaf, jf = hist
    chunk = vdaf.flp.valid.chunk_length
    nj, uc = _grid_chunk(chunk)
    n = jf.n
    text = _compile(
        lambda he, ho, pv: combine_decide_planar(jf, chunk, he, ho, pv),
        one_chip,
        ((R, n, nj * uc, 128), U32),
        ((R, n, nj * uc, 128), U32),
        ((R, n, vdaf.flp.VERIFIER_LEN, 128), U32),
    )
    assert "tpu_custom_call" in text


def test_sumvec_partial_kernel(one_chip, hist):
    """SumVec(1024, bits 1, chunk 316): the call-slab contraction at the
    same chunk width (4 calls, one slab)."""
    from janus_tpu.ops.flp_pallas import pad_chunk, sumvec_partial_planar

    _vdaf, jf = hist
    n, kc, cp = jf.n, 4, pad_chunk(316)
    text = _compile(
        lambda m, klu, lagk: sumvec_partial_planar(jf, m, klu, lagk),
        one_chip,
        ((R, n, kc, cp, 128), U32),
        ((R, n, kc, cp, 128), U32),
        ((R, n, kc, 128), U32),
    )
    assert "tpu_custom_call" in text


def test_count_planar_step(one_chip, monkeypatch):
    """The whole helper prepare step of Prio3Count in the planar layout —
    what TpuBackend launches on the chip for a 1,024-row batch.  The
    backend election sees the CPU here, so the test steers the kernels on."""
    from janus_tpu.vdaf.backend import TpuBackend

    monkeypatch.setenv("JANUS_TPU_PALLAS", "on")
    backend = TpuBackend(prio3_count())
    assert backend.bp.planar_eligible(1, ROWS)
    u8 = jnp.uint8
    kw = {
        "nonces_u8": jax.ShapeDtypeStruct((ROWS, 16), u8, sharding=one_chip),
        "verify_key_u8": jax.ShapeDtypeStruct((ROWS, 16), u8, sharding=one_chip),
        "share_seeds_u8": jax.ShapeDtypeStruct((ROWS, 16), u8, sharding=one_chip),
    }
    compiled = backend._prep_fn(1).lower(kw).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    assert "optimization_barrier" not in compiled.as_text()


def test_frontdoor_aes_kernel(one_chip, hist):
    """The multikey AES kernel at the block count of one Histogram(1024)
    leader share (pow2-padded: 2,048 blocks), eight reports wide.  The
    compile grows with rows x blocks — the u8 table gathers unroll — and
    a full 64-report open batch takes 77 s here and 51 s on the chip
    (PERF.md, PR 21), which is why the front door no longer elects this
    kernel where `cryptography` works; the Poplar1 walk still uses it."""
    from janus_tpu.ops.aes_jax import _next_pow2, encrypt_blocks_multikey

    vdaf, jf = hist
    share_bytes = 4 * jf.n * (vdaf.flp.MEAS_LEN + vdaf.flp.PROOF_LEN) + 16
    blocks = _next_pow2(2 + -(-share_bytes // 16))
    assert blocks == 2048
    u8 = jnp.uint8
    rks = jax.ShapeDtypeStruct((8, 11, 16), u8, sharding=one_chip)
    blk = jax.ShapeDtypeStruct((8, blocks, 16), u8, sharding=one_chip)
    compiled = encrypt_blocks_multikey.lower(rks, blk).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0
