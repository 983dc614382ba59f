"""Pallas TPU kernels for batched TurboSHAKE128 (Keccak-p[1600,12]).

The XLA graph version (keccak_jax.py) runs the permutation as ~5k scalar u32
HLOs on (B, 50)-shaped tensors and reaches ~2% of VPU peak.  These kernels
hold the sponge state in VMEM scratch as 100 u32 lane-words of shape (8, 128)
— one full VPU tile of 1024 reports per lane-word — so every xor/rot/and in
the permutation is a single full-width VPU op, and the squeeze/absorb block
loop rides the Pallas grid, overlapping the per-block HBM DMA with the next
permutation.

Layout convention ("planar"): a batch of B reports (B % 1024 == 0) is carried
as u32 word-planes of shape (W, B // 128, 128); plane w holds stream word w
of every report.  Lane l of the Keccak state is planes (2l, 2l+1) =
(lo, hi) of the 64-bit lane, identical to keccak_jax.

Replaces the rayon-parallel scalar Keccak of the reference's prio crate
(reference: aggregator/src/aggregator.rs:2101 ships the per-report scalar
loops to rayon; SURVEY.md §2.3 P1).  Bit-exact vs janus_tpu.xof.turboshake128
(tests/test_ops_keccak.py, interpret mode on CPU + real kernels on TPU).
"""

from __future__ import annotations

from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..xof import ROUND_CONSTANTS, _RHO
from . import pallas_mode as _pallas_mode

RATE = 168
RATE_WORDS = 42
_ROUNDS = 12
_RC = [(rc & 0xFFFFFFFF, rc >> 32) for rc in ROUND_CONSTANTS[24 - _ROUNDS :]]


def pallas_enabled(batch: int) -> bool:
    """True when the planar kernels apply: TPU (or interpret) and full tiles."""
    return batch % 1024 == 0 and _pallas_mode() != "off"


# -- the permutation on (lo, hi) u32 tile pairs -----------------------------

def _rotl(lo, hi, r: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    r %= 64
    if r == 0:
        return lo, hi
    if r == 32:
        return hi, lo
    if r < 32:
        return (lo << r) | (hi >> (32 - r)), (hi << r) | (lo >> (32 - r))
    s = r - 32
    return (hi << s) | (lo >> (32 - s)), (lo << s) | (hi >> (32 - s))


def _permute_pingpong(a_ref, b_ref):
    """Keccak-p[1600,12] on a (100, 8, 128) VMEM state, result in a_ref.

    Register-pressure-aware schedule: holding all 25 lanes of a 1024-report
    tile in registers (50 live (8,128) tiles + temporaries) overflows the
    VPU register file and Mosaic spills every round.  Instead each round
    streams through VMEM — theta columns, then rho+pi+chi fused per output
    row — reading the round input from one buffer and writing the round
    output to the other (ping-pong, so sources are never clobbered).  At
    most ~25 tiles are live and every state word is loaded twice / stored
    once per round.  Measured ~6x faster than the all-lanes-in-registers
    form on v5e.  12 rounds = even count, so the result lands back in a_ref.
    """
    for rnd, (rc_lo, rc_hi) in enumerate(_RC):
        src_ref, dst_ref = (a_ref, b_ref) if rnd % 2 == 0 else (b_ref, a_ref)
        # theta: column xors c[x], then d[x] = c[x-1] ^ rotl(c[x+1], 1)
        c = []
        for x in range(5):
            lo = src_ref[2 * x] ^ src_ref[2 * (x + 5)] ^ src_ref[2 * (x + 10)] ^ src_ref[2 * (x + 15)] ^ src_ref[2 * (x + 20)]
            hi = src_ref[2 * x + 1] ^ src_ref[2 * (x + 5) + 1] ^ src_ref[2 * (x + 10) + 1] ^ src_ref[2 * (x + 15) + 1] ^ src_ref[2 * (x + 20) + 1]
            c.append((lo, hi))
        d = []
        for x in range(5):
            rl, rh = _rotl(*c[(x + 1) % 5], 1)
            d.append((c[(x - 1) % 5][0] ^ rl, c[(x - 1) % 5][1] ^ rh))
        # rho+pi+chi fused per output row: b[x_b + 5*y_b] = rotl(a[src] ^
        # d[x_src], RHO[src]) with src = x_src + 5*x_b, x_src = (3*y_b +
        # x_b) % 5 (inverse of the b-index map y + 5*((2x + 3y) % 5)); the
        # chi row needs only the 5 freshly built b lanes.
        for y_b in range(5):
            row = []
            for x_b in range(5):
                x_src = (3 * y_b + x_b) % 5
                src = x_src + 5 * x_b
                lo = src_ref[2 * src] ^ d[x_src][0]
                hi = src_ref[2 * src + 1] ^ d[x_src][1]
                row.append(_rotl(lo, hi, _RHO[src]))
            for x_b in range(5):
                lo = row[x_b][0] ^ (~row[(x_b + 1) % 5][0] & row[(x_b + 2) % 5][0])
                hi = row[x_b][1] ^ (~row[(x_b + 1) % 5][1] & row[(x_b + 2) % 5][1])
                if x_b == 0 and y_b == 0:
                    lo = lo ^ jnp.uint32(rc_lo)
                    hi = hi ^ jnp.uint32(rc_hi)
                dst_ref[2 * (5 * y_b + x_b)] = lo
                dst_ref[2 * (5 * y_b + x_b) + 1] = hi


# -- squeeze kernel: one absorbed block -> NB output blocks -----------------

def _squeeze_kernel(in_ref, out_ref, state_ref, tmp_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        for w in range(RATE_WORDS):
            state_ref[w] = in_ref[w]
        zero = jnp.zeros((8, 128), dtype=jnp.uint32)
        for w in range(RATE_WORDS, 100):
            state_ref[w] = zero

    _permute_pingpong(state_ref, tmp_ref)
    for w in range(RATE_WORDS):
        out_ref[0, w] = state_ref[w]


def _squeeze_call(planar: jnp.ndarray, nb: int, interpret: bool) -> jnp.ndarray:
    """(42, R, 128) padded single-block messages -> (nb, 42, R, 128) stream."""
    R = planar.shape[1]
    grid = (R // 8, nb)
    return pl.pallas_call(
        _squeeze_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((RATE_WORDS, 8, 128), lambda i, j: (0, i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec(
            (1, RATE_WORDS, 8, 128), lambda i, j: (j, 0, i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((nb, RATE_WORDS, R, 128), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((100, 8, 128), jnp.uint32),
            pltpu.VMEM((100, 8, 128), jnp.uint32),
        ],
        interpret=interpret,
    )(planar)


# -- absorb kernel: NA message blocks -> 42-word (one block) output ---------

def _absorb_kernel(in_ref, out_ref, state_ref, tmp_ref):
    j = pl.program_id(1)
    first = j == 0
    zero = jnp.zeros((8, 128), dtype=jnp.uint32)

    @pl.when(first)
    def _():
        for w in range(RATE_WORDS, 100):
            state_ref[w] = zero

    # xor the message block into the rate words (state is zero at j==0).
    for w in range(RATE_WORDS):
        prev = jnp.where(first, zero, state_ref[w])
        state_ref[w] = prev ^ in_ref[w]

    _permute_pingpong(state_ref, tmp_ref)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for w in range(RATE_WORDS):
            out_ref[w] = state_ref[w]


def _absorb_call(planar: jnp.ndarray, na: int, interpret: bool) -> jnp.ndarray:
    """(na*42, R, 128) padded message blocks -> (42, R, 128) first out block."""
    R = planar.shape[1]
    grid = (R // 8, na)
    return pl.pallas_call(
        _absorb_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((RATE_WORDS, 8, 128), lambda i, j: (j, i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec(
            (RATE_WORDS, 8, 128), lambda i, j: (0, i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((RATE_WORDS, R, 128), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((100, 8, 128), jnp.uint32),
            pltpu.VMEM((100, 8, 128), jnp.uint32),
        ],
        interpret=interpret,
    )(planar)


# -- host/XLA-side planar plumbing ------------------------------------------

def _to_planar(words: jnp.ndarray) -> jnp.ndarray:
    """(B, W) u32 -> (W, B//128, 128) word planes."""
    B, W = words.shape
    return words.reshape(B // 128, 128, W).transpose(2, 0, 1)


def _pad_words(msg_u8: jnp.ndarray, domain: int) -> jnp.ndarray:
    """(B, L) u8 message -> (B, nblocks*42) u32 padded stream words."""
    from .keccak_jax import bytes_to_words

    B, L = msg_u8.shape
    nblocks = L // RATE + 1
    pad_len = nblocks * RATE - L
    pad = np.zeros(pad_len, dtype=np.uint8)
    pad[0] = domain
    pad[-1] ^= 0x80
    padded = jnp.concatenate(
        [msg_u8, jnp.broadcast_to(jnp.asarray(pad), (B, pad_len))], axis=-1
    )
    return bytes_to_words(padded)


def xof_planes_pallas(
    seed: jnp.ndarray, dst: bytes, binder: jnp.ndarray, out_words: int
) -> jnp.ndarray:
    """Batched XofTurboShake128 -> PLANE-ordered stream words (W, B//128, 128).

    Same computation as xof_words_pallas for a single-block message, but the
    result stays in the kernels' native planar layout (plane w = stream word
    w of every report) — the limb-planar FLP pipeline consumes this directly,
    skipping the 100+ MB lane transpose that (B, W) row-major output costs.
    """
    interpret = _pallas_mode() == "interpret"
    prefix = np.frombuffer(bytes([len(dst)]) + dst, dtype=np.uint8)
    B = seed.shape[0]
    parts = [jnp.broadcast_to(jnp.asarray(prefix), (B, len(prefix))), seed]
    if binder.shape[-1]:
        parts.append(binder)
    msg = jnp.concatenate(parts, axis=-1)
    words = _pad_words(msg, 0x01)
    if words.shape[1] != RATE_WORDS:
        raise NotImplementedError("xof_planes_pallas requires a single-block message")
    nb = -(-out_words // RATE_WORDS)
    planes = _squeeze_call(_to_planar(words), nb, interpret)  # (nb, 42, R, 128)
    R = planes.shape[2]
    return planes.reshape(nb * RATE_WORDS, R, 128)[:out_words]


def absorb_planes_pallas(msg_planes: jnp.ndarray, out_words: int) -> jnp.ndarray:
    """Absorb a pre-built planar padded message -> (out_words, R, 128).

    msg_planes: (na*42, R, 128) plane-ordered padded message words (the
    caller applies TurboSHAKE padding).  Used by the joint-rand-part XOF,
    whose 16 KB-per-report binder is assembled by funnel-shifting the
    measurement-share planes instead of a byte-level concat + transpose.
    """
    interpret = _pallas_mode() == "interpret"
    if out_words > RATE_WORDS:
        raise NotImplementedError("multi-block squeeze after absorb")
    na = msg_planes.shape[0] // RATE_WORDS
    planes = _absorb_call(msg_planes, na, interpret)  # (42, R, 128)
    return planes[:out_words]


def planes_to_rows(planes: jnp.ndarray) -> jnp.ndarray:
    """(W, R, 128) planar words -> (B, W) row-major words (small W only)."""
    W, R, _ = planes.shape
    return planes.transpose(1, 2, 0).reshape(R * 128, W)


def rows_to_planes(words: jnp.ndarray) -> jnp.ndarray:
    """(B, W) row-major words -> (W, B//128, 128) planes (small W only)."""
    return _to_planar(words)


def xof_words_pallas(
    seed: jnp.ndarray, dst: bytes, binder: jnp.ndarray, out_words: int
) -> jnp.ndarray:
    """Batched XofTurboShake128 via the planar kernels -> (B, out_words) u32.

    Chooses the squeeze kernel (single-block message) or absorb kernel
    (multi-block message, out_words <= 42) based on static shapes; the caller
    must have checked pallas_enabled(B).
    """
    interpret = _pallas_mode() == "interpret"
    prefix = np.frombuffer(bytes([len(dst)]) + dst, dtype=np.uint8)
    B = seed.shape[0]
    parts = [jnp.broadcast_to(jnp.asarray(prefix), (B, len(prefix))), seed]
    if binder.shape[-1]:
        parts.append(binder)
    msg = jnp.concatenate(parts, axis=-1)
    words = _pad_words(msg, 0x01)
    nblocks = words.shape[1] // RATE_WORDS
    if nblocks == 1:
        nb = -(-out_words // RATE_WORDS)
        planes = _squeeze_call(_to_planar(words), nb, interpret)
        # (nb, 42, R, 128) -> (B, nb*42): batch-major stream words.
        R = planes.shape[2]
        stream = planes.transpose(2, 3, 0, 1).reshape(B, nb * RATE_WORDS)
        return stream[:, :out_words]
    if out_words > RATE_WORDS:
        raise NotImplementedError("multi-block absorb + multi-block squeeze")
    planes = _absorb_call(_to_planar(words), nblocks, interpret)
    stream = planes.transpose(1, 2, 0).reshape(B, RATE_WORDS)
    return stream[:, :out_words]
