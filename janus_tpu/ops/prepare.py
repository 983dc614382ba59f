"""Batched Prio3 prepare on device — the north-star hot loop.

This composes the leaf kernels (``field_jax`` limb arithmetic, ``keccak_jax``
batched TurboSHAKE, ``xof_jax`` rejection sampling) into the full per-report
prepare pipeline, vmapped over an aggregation job:

    seeds/nonces → XOF expand (meas + proof shares, query/joint rands)
                 → FLP query (gadget wires, Lagrange eval, gadget poly)
                 → verifier shares + out shares,
    then ``prep_shares_to_prep``: combine verifiers, decide, joint-rand seed.

The reference runs the scalar equivalent per report on a rayon pool
(reference: aggregator/src/aggregator/aggregation_job_driver.rs:397-428 leader,
aggregator/src/aggregator.rs:2101 helper).  Here one XLA launch handles the
whole batch; every output is byte-identical to the CPU oracle
(janus_tpu.vdaf.prio3) — asserted in tests/test_prepare.py.

Montgomery domain convention: the BULK tensors (meas, proofs, wires, gadget
outputs, verifiers, out shares) stay CANONICAL end to end; only the handful
of per-report scalars that multiply them — joint-rand r, query point t, the
precomputed alpha powers / barycentric weights — are held in Montgomery
form.  ``mont_mul(x_canonical, y_montgomery) = x*y canonical`` makes every
product land back in canonical form for free, which eliminates the
full-width to_mont/from_mont passes over meas (MEAS_LEN muls), proofs
(PROOF_LEN), and the verifier (VERIFIER_LEN) that an all-Montgomery circuit
needs — ~26% of the field multiplies in the histogram1024 pipeline.  The
gadget check in prep_shares_to_prep compares g*R^-1 against y*R^-1 (R is
invertible, so equality is unchanged).  All arithmetic is exact integer
math mod p, so there is no reassociation hazard.

Wire-polynomial evaluation avoids a device NTT: the verifier needs each wire
polynomial only *evaluated at t*, and the wire values live on the P-th roots
of unity, so barycentric Lagrange applies:

    poly(t) = (t^P - 1)/P * sum_k  val_k * w^k / (t - w^k)

with one batched Montgomery inversion over the k axis (field_jax.batch_inv_mont).
Values at unused points are zero, so only calls+1 terms are needed.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..fields import next_power_of_2
from ..flp.circuits import (
    Count,
    FixedPointBoundedL2VecSum,
    Histogram,
    Sum,
    SumVec,
)
from ..vdaf.prio3 import (
    USAGE_JOINT_RAND_PART,
    USAGE_JOINT_RAND_SEED,
    USAGE_JOINT_RANDOMNESS,
    USAGE_MEAS_SHARE,
    USAGE_PROOF_SHARE,
    USAGE_QUERY_RANDOMNESS,
    Prio3,
)
from ..xof import XofTurboShake128
from .field_jax import JField, _scan_fence
from .keccak_jax import bytes_to_words, words_to_bytes, xof_turboshake128_batch
from .xof_jax import xof_next_vec_batch

_U32 = jnp.uint32


#: Names on the device ops of the prepare programs: a profiler trace reads
#: ``xof.query_rand/while.26`` where it read ``while.26``.  Metadata only:
#: the lowered program is op for op what it is without them
#: (tests/test_phases.py compares the text).
_scope = jax.named_scope


def limbs_to_bytes(limbs: jnp.ndarray) -> jnp.ndarray:
    """Canonical (..., L, n) u32 limbs -> (..., L*4n) u8 little-endian wire bytes."""
    flat = limbs.reshape(limbs.shape[:-2] + (limbs.shape[-2] * limbs.shape[-1],))
    return words_to_bytes(flat)


def bytes_to_limbs(jf: JField, data: jnp.ndarray, num_elems: int) -> jnp.ndarray:
    """(..., num_elems*4n) u8 wire bytes -> (..., num_elems, n) u32 limbs."""
    words = bytes_to_words(data)
    return words.reshape(words.shape[:-1] + (num_elems, jf.n))


class _GadgetPlan:
    """Static shape of ONE gadget inside a device circuit: its call count,
    wire arity/degree, interpolation modulus P = next_pow2(1 + calls), and
    gadget-polynomial length.  The proof and verifier wire formats are the
    concatenation of per-gadget segments in declaration order — exactly
    the scalar ``flp/generic.py`` layout."""

    __slots__ = ("calls", "arity", "degree", "P", "glen")

    def __init__(self, calls: int, arity: int, degree: int):
        self.calls = calls
        self.arity = arity
        self.degree = degree
        self.P = next_power_of_2(1 + calls)
        self.glen = degree * (self.P - 1) + 1


class _DeviceCircuit:
    """Device twin of one FLP validity circuit.

    Circuits hold a PER-GADGET plan list (``self.plans``); the original
    single-gadget families are the trivial 1-plan case and keep their
    gadget-0 attribute aliases (``calls``/``arity``/``P``/``glen``) so the
    planar Pallas paths — which only serve single-gadget circuits — read
    them unchanged.  Multi-gadget circuits (FixedPointBoundedL2VecSum)
    override the ``*_g`` per-gadget hooks.

    ``mxu=True`` routes the K-axis field contractions (wire Lagrange
    evaluation, weighted truncates, joint-rand verifier folds) through the
    limb-plane dot_general layer (JField.mat_mul_mont/dot_mont) instead of
    mont_mul/sum trees — identical canonical limbs, MXU-shaped compute.
    """

    def __init__(self, valid, mxu: bool = False):
        self.valid = valid
        self.mxu = mxu
        self.plans = [
            _GadgetPlan(calls, g.ARITY, g.DEGREE)
            for g, calls in zip(valid.new_gadgets(), valid.GADGET_CALLS)
        ]
        p0 = self.plans[0]
        self.calls = p0.calls
        self.arity = p0.arity
        self.degree = p0.degree
        self.P = p0.P
        self.glen = p0.glen

    # subclasses: inputs(), v(), truncate(), gadget_eval_scaled().
    # Convention: meas/gk/wires canonical; jr_m Montgomery; consts as noted.

    def calls_from_meas_len(self, meas_len):
        """Per-row LIVE gadget-call count for a (possibly canonical-padded)
        measurement length — the mask boundary for the barycentric
        coefficients and the gadget-output fold (vdaf/canonical.py).
        Chunked circuits: ceil(meas_len / chunk)."""
        chunk = getattr(self.valid, "chunk_length", 1)
        return (meas_len + (chunk - 1)) // chunk

    # -- per-gadget hooks (multi-gadget circuits override) ---------------
    def calls_live_list(self, meas_len):
        """Per-GADGET live-call counts for a per-row measurement length
        (canonical masking, vdaf/canonical.py) — one entry per plan."""
        return [self.calls_from_meas_len(meas_len)]

    def wire_evals_g(self, gi, jf, meas_m, jr_m, lag, seeds, consts, ml=None):
        """Wire evaluations for gadget ``gi``; the single-gadget default
        delegates to the circuit's ``wire_evals``.  ``ml`` (B,) i32 is the
        per-row true measurement length under canonical padding (None on
        exact-shape graphs) — only length-dependent gadget inputs (the
        fixed-point entry recomposition) consume it."""
        assert gi == 0
        return self.wire_evals(jf, meas_m, jr_m, lag, seeds, consts)

    def gadget_eval_scaled_g(self, gi, jf, x):
        """Direct gadget evaluation (scaled by R^-1) for gadget ``gi`` on
        its combined wire evaluations — the decide-side check."""
        return self.gadget_eval_scaled(jf, x)

    def v_multi(self, jf, gks, meas_m, jr_m, consts, ml=None):
        """Circuit output from the per-gadget output lists (``gks`` has
        one (B, calls_g, n) tensor per plan).  Single-gadget default
        delegates to ``v``."""
        return self.v(jf, gks[0], meas_m, jr_m, consts)

    def wire_evals(self, jf, meas_m, jr_m, lag, seeds, consts):
        """Wire-polynomial evaluations at t: (B, arity, n) canonical.

        lag (B, K, n) Montgomery barycentric coefficients, seeds (B, arity, n)
        canonical.  Default path materializes the gadget-input tensor; the
        chunked circuits override with a fused form (the input tensor is
        (B, calls, arity, n) — ~165 MB/launch for histogram1024 at B=4096 —
        and this device is HBM-bandwidth-bound, so never writing it is the
        win)."""
        inp = self.inputs(jf, meas_m, jr_m, consts)  # (B, calls, arity, n)
        wires = jnp.concatenate([seeds[:, None], inp], axis=1)  # (B, K, arity, n)
        if self.mxu:
            return jf.dot_mont(wires, lag)
        return jf.sum(jf.mont_mul(wires, lag[:, :, None, :]), axis=1)


class _DCount(_DeviceCircuit):
    def inputs(self, jf, meas_m, jr_m, consts):
        # Single call: [meas0, meas0].
        m0 = meas_m[:, 0:1]  # (B, 1, n)
        return jnp.stack([m0, m0], axis=2)  # (B, 1, 2, n)

    def v(self, jf, gk, meas_m, jr_m, consts):
        return jf.sub(gk[:, 0], meas_m[:, 0])

    def truncate(self, jf, meas_m, consts, ml=None):
        return meas_m

    def gadget_eval_scaled(self, jf, x):
        """Gadget output scaled by R^-1, from canonical wire inputs."""
        return jf.mont_mul(x[:, 0], x[:, 1])


class _DSum(_DeviceCircuit):
    def inputs(self, jf, meas_m, jr_m, consts):
        return meas_m[:, :, None, :]  # (B, bits, 1, n)

    def v(self, jf, gk, meas_m, jr_m, consts):
        r = jr_m[:, 0]  # (B, n) Montgomery
        r_b = jnp.broadcast_to(r[:, None, :], gk.shape)
        r_pows = jf.cumprod_mont(r_b, axis=1)  # r^(k+1)*R at call k
        if self.mxu:
            # joint-rand verifier fold as a (1 x calls) x (calls x 1) dot
            return jnp.squeeze(jf.dot_mont(gk[:, :, None, :], r_pows), axis=1)
        return jf.sum(jf.mont_mul(r_pows, gk), axis=1)  # canonical

    def truncate(self, jf, meas_m, consts, ml=None):
        w = consts["pow2_m"]  # (bits, n) Montgomery constants 2^b*R
        if self.mxu:
            # bit-weight contraction against the shared constant vector
            return jf.dot_mont(meas_m[:, :, None, :], w)
        return jf.sum(jf.mont_mul(meas_m, w[None]), axis=1)[:, None, :]

    def gadget_eval_scaled(self, jf, x):
        x0 = x[:, 0]
        # (x^2 - x)*R^-1 from canonical x: x*x*R^-1 - x*1*R^-1.
        return jf.sub(jf.mont_mul(x0, x0), jf.from_mont(x0))


class _DChunked(_DeviceCircuit):
    """Shared machinery for the ParallelSum(Mul, chunk) circuits."""

    def __init__(self, valid, mxu: bool = False):
        super().__init__(valid, mxu)
        self.chunk = valid.chunk_length
        self.pad_len = self.calls * self.chunk - valid.MEAS_LEN

    def _pad(self, jf, meas_m):
        if self.pad_len == 0:
            return meas_m
        B = meas_m.shape[0]
        zeros = jnp.zeros((B, self.pad_len, jf.n), dtype=_U32)
        return jnp.concatenate([meas_m, zeros], axis=1)

    def _interleave(self, a, b):
        # wire order per call: [a_0, b_0, a_1, b_1, ...]
        B, calls, chunk, n = a.shape
        return jnp.stack([a, b], axis=3).reshape(B, calls, 2 * chunk, n)

    def gadget_eval_scaled(self, jf, x):
        B, arity, n = x.shape
        pairs = x.reshape(B, arity // 2, 2, n)
        prod = jf.mont_mul(pairs[:, :, 0], pairs[:, :, 1])  # (a*b)*R^-1
        return jf.sum(prod, axis=1)

    def _odds_and_seed(self, jf, m, lagk, lag0, seeds, consts):
        """Shared pieces of the fused wire evaluation.

        odds[u] = sum_k lag_{k+1}*(m[k,u] - 1/shares)
                = sum_k mont_mul(m[k,u], lag_{k+1}) - mont_mul(1/shares, sum_k lag_{k+1})
        (exact: mont_mul distributes over mod-p addition; canonical limbs are
        unique, so the rearranged form is byte-identical to the oracle's).
        """
        if self.mxu:
            s2 = jf.dot_mont(m, lagk)  # (B, chunk, n) via one dot_general
        else:
            s2 = jf.sum(jf.mont_mul(m, lagk[:, :, None, :]), axis=1)  # (B, chunk, n)
        lag_sum = jf.sum(lagk, axis=1)  # (B, n) Montgomery
        c = jnp.broadcast_to(consts["shares_inv_c"], lag_sum.shape)
        ccorr = jf.mont_mul(c, lag_sum)  # (B, n) canonical
        odds = jf.sub(s2, ccorr[:, None, :])
        se = jf.mont_mul(seeds, lag0[:, None, :])  # (B, arity, n)
        return odds, se

    def _zip_wires(self, jf, evens, odds, se):
        B = evens.shape[0]
        pair = jnp.stack([evens, odds], axis=2).reshape(B, 2 * self.chunk, jf.n)
        return jf.add(se, pair)


class _DSumVec(_DChunked):
    def inputs(self, jf, meas_m, jr_m, consts):
        B = meas_m.shape[0]
        m = self._pad(jf, meas_m).reshape(B, self.calls, self.chunk, jf.n)
        # r_power resets per call: jr[i]^(j+1)
        jr_b = jnp.broadcast_to(jr_m[:, :, None, :], m.shape)
        r_pows = jf.cumprod_mont(jr_b, axis=2)
        a = jf.mont_mul(m, r_pows)
        b = jf.sub(m, jnp.broadcast_to(consts["shares_inv_c"], m.shape))
        return self._interleave(a, b)

    def wire_evals(self, jf, meas_m, jr_m, lag, seeds, consts):
        """Fused: evens[u] = sum_k lag_{k+1} * m[k,u] * jr_k^(u+1).

        jr differs per call, so lag folds into the per-(k,u) Montgomery
        power table; no (B, calls, arity, n) tensor is ever written.  (The
        evens coefficient varies over BOTH contraction axes, so unlike the
        histogram it is not a matmul — under mxu only the odds/seed halves
        ride the dot layer, via _odds_and_seed.)"""
        B = meas_m.shape[0]
        m = self._pad(jf, meas_m).reshape(B, self.calls, self.chunk, jf.n)
        lag0, lagk = lag[:, 0], lag[:, 1:]
        jr_b = jnp.broadcast_to(jr_m[:, :, None, :], m.shape)
        r_pows = jf.cumprod_mont(jr_b, axis=2)  # jr_k^(u+1) * R
        rl = jf.mont_mul(r_pows, jnp.broadcast_to(lagk[:, :, None, :], m.shape))
        evens = jf.sum(jf.mont_mul(m, rl), axis=1)  # (B, chunk, n)
        odds, se = self._odds_and_seed(jf, m, lagk, lag0, seeds, consts)
        return self._zip_wires(jf, evens, odds, se)

    def v(self, jf, gk, meas_m, jr_m, consts):
        return jf.sum(gk, axis=1)

    def truncate(self, jf, meas_m, consts, ml=None):
        if self.valid.bits == 1:
            # sum over a single bit weighted 2^0 is the identity; skip the
            # MEAS_LEN-wide multiply (len=100k circuits pay for it).
            return meas_m
        B = meas_m.shape[0]
        w = consts["pow2_m"]  # (bits, n)
        m = meas_m.reshape(B, self.valid.length, self.valid.bits, jf.n)
        if self.mxu:
            return jf.dot_mont(jnp.swapaxes(m, 1, 2), w)  # (B, length, n)
        return jf.sum(jf.mont_mul(m, w[None, None]), axis=2)


class _DHistogram(_DChunked):
    def inputs(self, jf, meas_m, jr_m, consts):
        B = meas_m.shape[0]
        m = self._pad(jf, meas_m).reshape(B, self.calls, self.chunk, jf.n)
        # r_power is global: r^(index+1) over the padded, flattened axis.
        r = jr_m[:, 0]  # (B, n)
        r_flat = jnp.broadcast_to(r[:, None, :], (B, self.calls * self.chunk, jf.n))
        r_pows = jf.cumprod_mont(r_flat, axis=1).reshape(m.shape)
        a = jf.mont_mul(m, r_pows)
        b = jf.sub(m, jnp.broadcast_to(consts["shares_inv_c"], m.shape))
        return self._interleave(a, b)

    def wire_evals(self, jf, meas_m, jr_m, lag, seeds, consts):
        """Fused with the global r-power pulled apart as an outer product.

        r^(k*chunk + u + 1) = r^(k*chunk) * r^(u+1), so
        evens[u] = mont_mul( sum_k mont_mul(m[k,u], kl[k]),  r_ch[u] )
        with kl[k] = mont_mul(r_call[k], lag_{k+1}) a TINY (B, calls, n)
        table — the k-contraction happens before the chunk-wide multiply,
        reading meas once and writing only (B, chunk, n).  Every
        rearrangement is an exact mod-p identity, so the canonical output
        limbs are byte-identical to the unfused form.  The coefficient
        tensors come from planar_coeffs — the SAME code that feeds the
        limb-planar Pallas kernel, so the two paths cannot drift.
        """
        B = meas_m.shape[0]
        m = self._pad(jf, meas_m).reshape(B, self.calls, self.chunk, jf.n)
        kl, lagk, lag0, ccorr, r_ch = self.planar_coeffs(jf, jr_m, lag, consts)
        if self.mxu:
            # Both k-contractions share the measurement operand, so the kl
            # and lagk coefficient columns stack into ONE (B, calls, 2, n)
            # rhs and a single dot_general produces s1 and s2 together.
            s12 = jf.mat_mul_mont(m, jnp.stack([kl, lagk], axis=2))
            s1, s2 = s12[:, :, 0], s12[:, :, 1]
        else:
            s1 = jf.sum(jf.mont_mul(m, kl[:, :, None, :]), axis=1)  # (B, chunk, n)
            s2 = jf.sum(jf.mont_mul(m, lagk[:, :, None, :]), axis=1)
        evens = jf.mont_mul(s1, r_ch)
        odds = jf.sub(s2, ccorr[:, None, :])
        se = jf.mont_mul(seeds, lag0[:, None, :])  # (B, arity, n)
        return self._zip_wires(jf, evens, odds, se)

    def v(self, jf, gk, meas_m, jr_m, consts):
        meas_sum = jf.sum(meas_m, axis=1)  # (B, n)
        return self.v_from_meas_sum(jf, gk, meas_sum, jr_m, consts)

    def v_from_meas_sum(self, jf, gk, meas_sum, jr_m, consts):
        """v given a precomputed meas sum (planar path computes it lazily)."""
        range_check = jf.sum(gk, axis=1)
        sum_check = jf.sub(
            meas_sum, jnp.broadcast_to(consts["shares_inv_c"], meas_sum.shape)
        )
        jr1 = jr_m[:, 1]
        return jf.add(
            jf.mont_mul(jr1, range_check),
            jf.mont_mul(jf.mont_mul(jr1, jr1), sum_check),
        )

    def planar_coeffs(self, jf, jr_m, lag, consts):
        """Per-report coefficient tensors for the planar wire kernel.

        Exactly the scalars wire_evals folds into its fused contraction:
        (kl (B,calls,n), lagk (B,calls,n), lag0 (B,n), ccorr (B,n),
        r_ch (B,chunk,n)) — same formulas, so kernel output limbs are
        byte-identical to the row-major path.
        """
        B = jr_m.shape[0]
        lag0, lagk = lag[:, 0], lag[:, 1:]
        r = jr_m[:, 0]
        r_ch = jf.pow_range_mont(r, self.chunk)  # r^(u+1), u < chunk
        rc = r_ch[:, -1]
        ones = jf.mont_one()[None, None, :]
        if self.calls > 1:
            tail = jf.cumprod_mont(
                jnp.broadcast_to(rc[:, None, :], (B, self.calls - 1, jf.n)), axis=1
            )
            r_call = jnp.concatenate(
                [jnp.broadcast_to(ones, (B, 1, jf.n)), tail], axis=1
            )
        else:
            r_call = jnp.broadcast_to(ones, (B, 1, jf.n))
        kl = jf.mont_mul(r_call, lagk)
        lag_sum = jf.sum(lagk, axis=1)
        c = jnp.broadcast_to(consts["shares_inv_c"], lag_sum.shape)
        ccorr = jf.mont_mul(c, lag_sum)
        return kl, lagk, lag0, ccorr, r_ch

    def truncate(self, jf, meas_m, consts, ml=None):
        return meas_m


class _DFixedPointL2(_DChunked):
    """Device twin of FixedPointBoundedL2VecSum — the first TWO-gadget
    circuit on the device plane (the jax_graft gradient-sum workload).

    Gadget 0 is the SumVec-pattern bit-range check over all MEAS_LEN
    positions (per-call joint-rand weights, power resetting each call);
    gadget 1 is the entry-squares ParallelSum(Mul) whose inputs are the
    fixed-point entries RECOMPOSED IN-GRAPH from the bit planes
    (X_i = sum_b 2^b * meas[i*n + b]) — no entry tensor ever crosses the
    host boundary.  The norm-equality affine combination and the
    Schwartz-Zippel fold live in ``v_multi``.  Under canonical padding
    (vdaf/canonical.py) every length-dependent site is per-row: the entry
    count d derives from ``ml``, padded entries mask to zero (the columns
    past a row's entry region hold its NORM bits — live data), the
    claimed-norm bits gather at the row's own offset d*n, and the
    Schwartz-Zippel combiner r_n selects joint_rand[bit_calls(row)].
    """

    def __init__(self, valid, mxu: bool = False):
        super().__init__(valid, mxu)  # chunk + gadget-0 pad over MEAS_LEN
        self.nbits = valid.bits_per_entry
        self.entries = valid.entries
        self.norm_bits = valid.bits_for_norm
        self.pad_len1 = self.plans[1].calls * self.chunk - valid.entries

    # -- canonical-shape helpers ----------------------------------------
    def entries_from_meas_len(self, ml):
        return (ml - self.norm_bits) // self.nbits

    def calls_live_list(self, ml):
        chunk = self.chunk
        return [
            (ml + chunk - 1) // chunk,
            (self.entries_from_meas_len(ml) + chunk - 1) // chunk,
        ]

    def _entries_from_meas(self, jf, meas_m, consts, entries_live=None):
        """(B, entries, n) canonical X_i = sum_b 2^b * meas[i*n + b].

        ``entries_live`` (B,) zeroes entries at/past the row's own count:
        a canonical-padded row's columns past its entry region hold its
        norm bits, so the recomposition there is garbage that must not
        reach the squares gadget, the norm sums, or the out share."""
        B = meas_m.shape[0]
        m = meas_m[:, : self.entries * self.nbits].reshape(
            B, self.entries, self.nbits, jf.n
        )
        w = consts["pow2_m"]  # (nbits, n) Montgomery
        if self.mxu:
            x = jf.dot_mont(jnp.swapaxes(m, 1, 2), w)  # (B, entries, n)
        else:
            x = jf.sum(jf.mont_mul(m, w[None, None]), axis=2)
        if entries_live is not None:
            e = jnp.arange(self.entries, dtype=jnp.int32)[None, :]
            x = jnp.where((e < entries_live[:, None])[:, :, None], x, 0)
        return x

    # -- per-gadget wire evaluations ------------------------------------
    def wire_evals_g(self, gi, jf, meas_m, jr_m, lag, seeds, consts, ml=None):
        if gi == 0:
            return self._wire_evals_bits(jf, meas_m, jr_m, lag, seeds, consts)
        return self._wire_evals_squares(
            jf, meas_m, lag, seeds, consts, ml=ml
        )

    def _wire_evals_bits(self, jf, meas_m, jr_m, lag, seeds, consts):
        """Fused SumVec-pattern wires: evens[u] = sum_k lag_{k+1} * m[k,u]
        * jr_k^(u+1) (jr slice: one weight per bit chunk), odds/seed via
        the shared _DChunked machinery.  Identical math to _DSumVec."""
        B = meas_m.shape[0]
        calls0 = self.plans[0].calls
        m = self._pad(jf, meas_m).reshape(B, calls0, self.chunk, jf.n)
        lag0, lagk = lag[:, 0], lag[:, 1:]
        jr_b = jnp.broadcast_to(jr_m[:, :calls0, None, :], m.shape)
        r_pows = jf.cumprod_mont(jr_b, axis=2)  # jr_k^(u+1) * R
        rl = jf.mont_mul(r_pows, jnp.broadcast_to(lagk[:, :, None, :], m.shape))
        evens = jf.sum(jf.mont_mul(m, rl), axis=1)  # (B, chunk, n)
        odds, se = self._odds_and_seed(jf, m, lagk, lag0, seeds, consts)
        return self._zip_wires(jf, evens, odds, se)

    def _wire_evals_squares(self, jf, meas_m, lag, seeds, consts, ml=None):
        """Gadget-1 wires: both wires of pair u evaluate to
        seed*lag_0 + sum_k X[k,u]*lag_{k+1} — the (X_i, X_i) input pairs
        share one contraction, emitted to the even AND odd slots."""
        B = meas_m.shape[0]
        calls1 = self.plans[1].calls
        el = self.entries_from_meas_len(ml) if ml is not None else None
        x = self._entries_from_meas(jf, meas_m, consts, entries_live=el)
        if self.pad_len1:
            x = jnp.concatenate(
                [x, jnp.zeros((B, self.pad_len1, jf.n), dtype=_U32)], axis=1
            )
        xm = x.reshape(B, calls1, self.chunk, jf.n)
        lag0, lagk = lag[:, 0], lag[:, 1:]
        if self.mxu:
            s = jf.dot_mont(xm, lagk)  # (B, chunk, n)
        else:
            s = jf.sum(jf.mont_mul(xm, lagk[:, :, None, :]), axis=1)
        se = jf.mont_mul(seeds, lag0[:, None, :])  # (B, arity, n)
        pair = jnp.stack([s, s], axis=2).reshape(B, 2 * self.chunk, jf.n)
        return jf.add(se, pair)

    # -- circuit output ---------------------------------------------------
    def v_multi(self, jf, gks, meas_m, jr_m, consts, ml=None):
        gk_bits, gk_sq = gks
        B = meas_m.shape[0]
        bit_check = jf.sum(gk_bits, axis=1)  # (B, n) canonical
        sumsq = jf.sum(gk_sq, axis=1)
        el = self.entries_from_meas_len(ml) if ml is not None else None
        x = self._entries_from_meas(jf, meas_m, consts, entries_live=el)
        sum_x = jf.sum(x, axis=1)
        # claimed norm: the (2n-2)-bit decomposition at the row's offset.
        w = consts["pow2_norm_m"]  # (norm_bits, n) Montgomery
        if ml is None:
            norm_m = meas_m[:, self.entries * self.nbits :]
        else:
            cols = (el * self.nbits)[:, None] + jnp.arange(
                self.norm_bits, dtype=jnp.int32
            )[None, :]
            norm_m = jnp.take_along_axis(meas_m, cols[:, :, None], axis=1)
        if self.mxu:
            claimed = jnp.squeeze(jf.dot_mont(norm_m[:, :, None, :], w), axis=1)
        else:
            claimed = jf.sum(jf.mont_mul(norm_m, w[None]), axis=1)
        # computed = sumsq - 2^n * sum_x + shares_inv * d * 2^(2n-2)
        two_n = jnp.broadcast_to(consts["pow2n_m"], sum_x.shape)
        if ml is None:
            off = jnp.broadcast_to(consts["offset_sq_c"], sum_x.shape)
        else:
            d_limbs = jnp.concatenate(
                [
                    el.astype(_U32)[:, None],
                    jnp.zeros((B, jf.n - 1), dtype=_U32),
                ],
                axis=1,
            )
            off = jf.mont_mul(d_limbs, jnp.broadcast_to(consts["offsq_m"], d_limbs.shape))
        computed = jf.add(jf.sub(sumsq, jf.mont_mul(sum_x, two_n)), off)
        norm_check = jf.sub(computed, claimed)
        # Schwartz-Zippel: r_n = joint_rand[bit_calls] (per-row index under
        # canonical padding — the row's OWN stream position).
        if ml is None:
            rn = jr_m[:, self.plans[0].calls]
        else:
            cl0 = (ml + self.chunk - 1) // self.chunk
            rn = jnp.squeeze(
                jnp.take_along_axis(jr_m, cl0[:, None, None], axis=1), axis=1
            )
        return jf.add(
            jf.mont_mul(rn, bit_check),
            jf.mont_mul(jf.mont_mul(rn, rn), norm_check),
        )

    def truncate(self, jf, meas_m, consts, ml=None):
        el = self.entries_from_meas_len(ml) if ml is not None else None
        return self._entries_from_meas(jf, meas_m, consts, entries_live=el)


def _device_circuit(valid, mxu: bool = False) -> _DeviceCircuit:
    if isinstance(valid, Count):
        return _DCount(valid, mxu)
    if isinstance(valid, Sum):
        return _DSum(valid, mxu)
    if isinstance(valid, SumVec):
        return _DSumVec(valid, mxu)
    if isinstance(valid, Histogram):
        return _DHistogram(valid, mxu)
    if isinstance(valid, FixedPointBoundedL2VecSum):
        return _DFixedPointL2(valid, mxu)
    raise NotImplementedError(f"no device circuit for {type(valid).__name__}")


class BatchedPrio3:
    """Device-batched prepare for one Prio3 instance (TurboSHAKE XOF only).

    All shapes are static per instance; the batch axis is the report axis.
    Outputs are canonical u32 limb tensors / u8 byte tensors that are
    byte-identical to the CPU oracle.
    """

    def __init__(
        self,
        prio3: Prio3,
        ntt_min_p: int = 64,
        require_device_xof: bool = True,
        field_backend: str = "vpu",
    ):
        #: TurboSHAKE has device (Pallas) kernels; other XOFs (the HMAC
        #: multiproof variant) run on the HOST and feed query_batch — the
        #: hybrid split in vdaf/backend.py HybridXofBackend.
        self.device_xof = prio3.xof is XofTurboShake128
        if require_device_xof and not self.device_xof:
            raise NotImplementedError("device path requires XofTurboShake128")
        if field_backend not in ("vpu", "mxu"):
            raise ValueError(f"unknown field_backend {field_backend!r}")
        #: "vpu" (default): scalar-lane CIOS mont_mul chains, limb-planar
        #: Pallas fast paths.  "mxu": the K-axis field contractions (wire
        #: Lagrange evaluation, gadget Vandermonde evaluation, weighted
        #: truncates, joint-rand folds) run as limb-plane dot_generals
        #: (JField.mat_mul_mont) on the row-major path — identical limbs,
        #: matmul-shaped compute for the matrix units.
        self.field_backend = field_backend
        self.prio3 = prio3
        self.flp = prio3.flp
        self.jf = JField(self.flp.field)
        self.circ = _device_circuit(self.flp.valid, mxu=field_backend == "mxu")
        jf, circ, field = self.jf, self.circ, self.flp.field
        p = field.MODULUS

        def mont_np(x: int) -> np.ndarray:
            return jf._int_to_limbs_np((x % p) * (1 << (32 * jf.n)) % p)

        self.consts: Dict[str, jnp.ndarray] = {}
        # Canonical: subtracted from / compared with canonical tensors.
        self.consts["shares_inv_c"] = jnp.asarray(
            jf._int_to_limbs_np(pow(prio3.num_shares, p - 2, p))
        )
        # Host-precomputed PER-GADGET Montgomery constants: each gadget g
        # has its own interpolation modulus P_g, hence its own root of
        # unity, alpha powers, barycentric weights, and (optionally) NTT
        # twiddles.  Single-gadget circuits see exactly the constants the
        # pre-multi-gadget code built.
        #
        # Gadget-poly evaluation strategy per gadget: the verifier needs
        # gpoly(alpha^k) for k=1..calls, alpha a P-th root of unity.  For
        # small P a Horner scan over the glen coefficients is cheapest;
        # for the wide-vector circuits (P >= 64, e.g. SumVec len=100k
        # chunk=316 -> P=512, glen=1023) Horner costs calls*glen
        # multiplies per report while a fold to P coefficients + P-point
        # NTT costs P*log2(P)/2 — ~70x fewer.  Both produce identical
        # limbs (exact integer math).  ``ntt_min_p`` exists so parity
        # tests can force this branch at tiny P and check it
        # byte-for-byte against the oracle.
        self._gc: List[Dict[str, object]] = []
        for plan in circ.plans:
            w = field.root(plan.P)
            p_inv = pow(plan.P, p - 2, p)
            gc: Dict[str, object] = {
                # alpha^k for k=1..calls (gadget poly eval points).
                "alpha_pows_m": jnp.asarray(
                    np.stack(
                        [mont_np(pow(w, k, p)) for k in range(1, plan.calls + 1)]
                    )
                ),
                # Barycentric constants w^k / P for k=0..calls.
                "bary_c_m": jnp.asarray(
                    np.stack(
                        [
                            mont_np(pow(w, k, p) * p_inv % p)
                            for k in range(plan.calls + 1)
                        ]
                    )
                ),
                "roots_m": jnp.asarray(
                    np.stack([mont_np(pow(w, k, p)) for k in range(plan.calls + 1)])
                ),
                # ALL P root differences feed the inversion-free
                # barycentric weights (prod over j != k of (t - w^k)
                # spans every P-th root, used or not).
                "roots_all_m": jnp.asarray(
                    np.stack([mont_np(pow(w, k, p)) for k in range(plan.P)])
                ),
                "log2_P": plan.P.bit_length() - 1,
                "ntt": None,
            }
            if plan.P >= ntt_min_p:
                P = plan.P
                logp = P.bit_length() - 1
                bitrev = np.zeros(P, dtype=np.int32)
                for i in range(P):
                    bitrev[i] = int(format(i, f"0{logp}b")[::-1], 2)
                tw_stages = []
                m = 2
                while m <= P:
                    w_m = pow(w, P // m, p)
                    tw_stages.append(
                        jnp.asarray(
                            np.stack(
                                [mont_np(pow(w_m, j, p)) for j in range(m // 2)]
                            )
                        )
                    )
                    m *= 2
                gc["ntt"] = (bitrev, tw_stages)
            self._gc.append(gc)
        # Gadget-0 aliases: the planar Pallas paths (single-gadget
        # circuits only) read these under the historical names.
        gc0 = self._gc[0]
        self.alpha_pows_m = gc0["alpha_pows_m"]
        self.bary_c_m = gc0["bary_c_m"]
        self.roots_m = gc0["roots_m"]
        self.roots_all_m = gc0["roots_all_m"]
        self._log2_P = gc0["log2_P"]
        self._ntt = gc0["ntt"]
        self._alpha_mat_cache: Dict[int, np.ndarray] = {}

        valid = self.flp.valid
        if hasattr(valid, "bits"):
            bits = valid.bits
            self.consts["pow2_m"] = jnp.asarray(
                np.stack([mont_np(1 << b) for b in range(bits)])
            )
        if isinstance(valid, FixedPointBoundedL2VecSum):
            nb = valid.bits_per_entry
            shares_inv = pow(prio3.num_shares, p - 2, p)
            # entry-bit recomposition weights 2^b (b < bits_per_entry)
            self.consts["pow2_m"] = jnp.asarray(
                np.stack([mont_np(1 << b) for b in range(nb)])
            )
            # claimed-norm decomposition weights 2^b (b < 2n-2)
            self.consts["pow2_norm_m"] = jnp.asarray(
                np.stack([mont_np(1 << b) for b in range(valid.bits_for_norm)])
            )
            # 2^n (the cross-term weight of the norm expansion)
            self.consts["pow2n_m"] = jnp.asarray(mont_np(1 << nb))
            # shares_inv * 2^(2n-2): multiplied by the per-row entry count
            # d on canonical graphs (offset term of the norm identity)
            self.consts["offsq_m"] = jnp.asarray(
                mont_np(shares_inv * (1 << (2 * nb - 2)))
            )
            # the exact-shape constant offset shares_inv * d * 2^(2n-2)
            self.consts["offset_sq_c"] = jnp.asarray(
                jf._int_to_limbs_np(
                    shares_inv * (valid.entries % p) * (1 << (2 * nb - 2)) % p
                )
            )

    # -- XOF helpers ----------------------------------------------------
    def _dst(self, usage: int) -> bytes:
        return self.prio3._dst(usage)

    def _expand_vec(self, seed_u8, dst, binder_u8, length) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """XOF -> (canonical limbs (B, length, n), ok (B,))."""
        return xof_next_vec_batch(self.jf, seed_u8, dst, binder_u8, length)

    def _xof_seed(self, seed_u8, dst, binder_u8) -> jnp.ndarray:
        """XOF -> one seed-sized output (B, SEED)."""
        from .keccak_pallas import pallas_enabled, xof_words_pallas

        seed_size = self.prio3.xof.SEED_SIZE
        if seed_u8.ndim == 2 and pallas_enabled(seed_u8.shape[0]) and seed_size % 4 == 0:
            words = xof_words_pallas(seed_u8, dst, binder_u8, seed_size // 4)
            return words_to_bytes(words)
        return xof_turboshake128_batch(seed_u8, dst, binder_u8, seed_size)

    # -- share expansion (helper side) ----------------------------------
    def helper_shares(self, agg_id: int, share_seeds_u8: jnp.ndarray):
        """Expand a helper's (meas, proofs) shares from its seed.

        Oracle twin: Prio3._helper_meas_share / _helper_proofs_share.
        Returns (meas (B,MEAS_LEN,n), proofs (B,num_proofs*PROOF_LEN,n), ok (B,)).
        """
        B = share_seeds_u8.shape[0]
        binder = jnp.broadcast_to(
            jnp.asarray(np.array([agg_id], dtype=np.uint8)), (B, 1)
        )
        with _scope("xof.expand_meas"):
            meas, ok1 = self._expand_vec(
                share_seeds_u8, self._dst(USAGE_MEAS_SHARE), binder, self.flp.MEAS_LEN
            )
        with _scope("xof.expand_proof"):
            proofs, ok2 = self._expand_vec(
                share_seeds_u8,
                self._dst(USAGE_PROOF_SHARE),
                binder,
                self.flp.PROOF_LEN * self.prio3.num_proofs,
            )
        return meas, proofs, ok1 & ok2

    def _lagrange_coeffs(self, t_m, gi: int = 0):
        """Barycentric Lagrange coefficients at t over gadget ``gi``'s
        P-th roots.

        Inversion-free form: z/(t - w^k) = prod_{j != k} (t - w^j) exactly
        (t^P - 1 factors over ALL P roots), so the coefficients need only
        exclusive prefix/suffix products — this removes a Fermat inversion
        whose 2x(32n)-step sequential scan dominated the query's serial
        sections.  Rows with t on a root have z == 0 and are flagged via
        t_ok for host recompute, as before.
        Returns (lag (B, calls+1, n) Montgomery, t_ok (B,)).
        """
        jf = self.jf
        plan, gc = self.circ.plans[gi], self._gc[gi]
        t_pow = t_m
        for _ in range(gc["log2_P"]):
            t_pow = jf.mont_mul(t_pow, t_pow)
        z = jf.sub(t_pow, jnp.broadcast_to(jf.mont_one(), t_pow.shape))  # t^P - 1
        t_ok = ~jf.is_zero(z)
        K = plan.calls + 1
        denom_all = jf.sub(t_m[:, None, :], gc["roots_all_m"][None])  # (B, P, n)
        others = jf.mutual_products_mont(denom_all, axis=1)
        lag = jf.mont_mul(others[:, :K], gc["bary_c_m"][None])  # (B, K, n)
        return lag, t_ok

    def _gpoly_at(self, gpoly, t_m):
        """Gadget polynomial at t.  Wide polynomials (the 100k-element
        SumVec has glen=1023) use baby-step/giant-step evaluation —
        Horner's glen-step serial chain is the launch's critical path.
        Under mxu both bsgs contractions run as dot_generals."""
        jf = self.jf
        if self.field_backend == "mxu":
            return jf.poly_eval_dot(gpoly, t_m)
        if gpoly.shape[1] >= 64:
            return jf.poly_eval_mont(gpoly, t_m)
        return jf.horner_mont(gpoly, t_m)

    def _gadget_outputs(self, gpoly, B, gi: int = 0):
        """gk (B, calls, n): gadget ``gi``'s polynomial at alpha^1..alpha^calls."""
        jf = self.jf
        plan, gc = self.circ.plans[gi], self._gc[gi]
        if self.field_backend == "mxu":
            # Vandermonde-style matmul: gk[b, k] = sum_j gpoly[b, j] * w^(kj)
            # with the alpha-power table a host-precomputed Montgomery
            # constant shared by every report — ONE dot_general across calls
            # replaces the NTT butterfly stages / the Horner scan, and the
            # canonical residues are identical (exact integer math).
            amat = self._alpha_mat_m(gi)  # (calls, glen, n) Montgomery, host
            w = jnp.asarray(np.ascontiguousarray(amat.transpose(1, 0, 2)))
            return jnp.squeeze(jf.mat_mul_mont(gpoly[:, :, None, :], w), axis=1)
        if gc["ntt"] is not None:
            P = plan.P
            hi = gpoly[:, P:]
            hi = jnp.concatenate(
                [hi, jnp.zeros((B, P - hi.shape[1], jf.n), dtype=_U32)], axis=1
            )
            folded = jf.add(gpoly[:, :P], hi)
            evals = jf.ntt_eval_mont(folded, *gc["ntt"])
            return evals[:, 1 : plan.calls + 1]

        def horner_step(acc, c):
            return (
                jf.add(
                    jf.mont_mul(acc, gc["alpha_pows_m"][None]), c[:, None, :]
                ),
                None,
            )

        coeffs_rev = jnp.moveaxis(jnp.flip(gpoly, axis=1), 1, 0)
        acc0 = jnp.zeros((B, plan.calls, jf.n), dtype=_U32)
        gk, _ = lax.scan(horner_step, acc0, coeffs_rev)
        return _scan_fence(gk)

    # -- FLP query (one proof) ------------------------------------------
    def _query_one(self, meas_m, proof_m, jr_m, t_m, calls_live=None, ml=None):
        """Device FLP query for one proof, over EVERY gadget.

        meas_m (B,MEAS_LEN,n) CANONICAL, proof_m (B,PROOF_LEN,n) CANONICAL,
        jr_m (B,JR_LEN,n) Montgomery, t_m (B,QUERY_RAND_LEN,n) Montgomery
        (one query point per gadget) ->
        (verifier (B,VERIFIER_LEN,n) CANONICAL, t_ok (B,)).
        Every mont_mul pairs one canonical bulk tensor with one Montgomery
        scalar/constant, so products stay canonical (see module docstring).
        The proof splits into per-gadget segments (wire seeds + gadget
        polynomial) and the verifier concatenates [v] + per-gadget
        [wire evals, gpoly(t)] — exactly the scalar FlpGeneric.query
        layout.  Oracle twin: FlpGeneric.query.

        ``calls_live`` (canonical masking, vdaf/canonical.py) is a
        PER-GADGET list of (B,) i32 mask boundaries: this graph is
        compiled for the BUCKET's call counts, and rows from a shorter
        task zero their padded calls out of (a) each gadget-output fold —
        an adversarial gadget polynomial is NOT zero at unused evaluation
        points, so gk must be masked before v — and (b) each barycentric
        coefficient vector, which reproduces the actual circuit's wire
        polynomial exactly (its values at unused P-th roots are zero BY
        DEFINITION, and every fused wire path consumes lag downstream of
        this mask).  ``ml`` (B,) i32 is the row's true measurement length
        for length-dependent gadget inputs (the fixed-point entry
        recomposition and norm fold).
        """
        jf, circ = self.jf, self.circ
        B = meas_m.shape[0]
        ok = jnp.ones((B,), dtype=bool)
        gks = []
        segs = []
        idx = 0
        for gi, plan in enumerate(circ.plans):
            seeds = proof_m[:, idx : idx + plan.arity]  # (B, arity_g, n)
            gpoly = proof_m[:, idx + plan.arity : idx + plan.arity + plan.glen]
            idx += plan.arity + plan.glen

            with _scope("flp.gadget_eval"):
                gk = self._gadget_outputs(gpoly, B, gi=gi)  # (B, calls_g, n)
            cl = calls_live[gi] if calls_live is not None else None
            if cl is not None:
                k = jnp.arange(plan.calls, dtype=jnp.int32)[None, :]
                gk = jnp.where((k < cl[:, None])[:, :, None], gk, 0)
            gks.append(gk)

            # Wire evaluations at t_g via barycentric Lagrange on the
            # gadget's own P-th roots.
            t_g = t_m[:, gi]
            with _scope("flp.wire_evals"):
                lag, t_ok = self._lagrange_coeffs(t_g, gi=gi)
            ok = ok & t_ok
            if cl is not None:
                k = jnp.arange(plan.calls + 1, dtype=jnp.int32)[None, :]
                lag = jnp.where((k <= cl[:, None])[:, :, None], lag, 0)
            with _scope("flp.wire_evals"):
                wire_evals = circ.wire_evals_g(
                    gi, jf, meas_m, jr_m, lag, seeds, self.consts, ml=ml
                )
            with _scope("flp.gadget_eval"):
                gp_t = self._gpoly_at(gpoly, t_g)  # (B, n)
            segs.append((wire_evals, gp_t))

        with _scope("flp.gadget_eval"):
            v = circ.v_multi(jf, gks, meas_m, jr_m, self.consts, ml=ml)  # (B, n)
        parts = [v[:, None]]
        for wire_evals, gp_t in segs:
            parts.extend([wire_evals, gp_t[:, None]])
        with _scope("verifier.pack"):
            verifier = jnp.concatenate(parts, axis=1)  # (B, VERIFIER_LEN, n)
        return verifier, ok

    # -- prep init ------------------------------------------------------
    def prep_init(
        self,
        agg_id: int,
        verify_key,  # bytes, or (SEED,) u8 array (traced — per-task data)
        nonces_u8: jnp.ndarray,
        *,
        share_seeds_u8: Optional[jnp.ndarray] = None,
        meas_limbs: Optional[jnp.ndarray] = None,
        proofs_limbs: Optional[jnp.ndarray] = None,
        blinds_u8: Optional[jnp.ndarray] = None,
        public_parts_u8: Optional[jnp.ndarray] = None,
        meas_len_u32: Optional[jnp.ndarray] = None,
    ) -> Dict[str, jnp.ndarray]:
        """Batched Prio3.prep_init for one aggregator.

        Leader (agg_id=0) passes canonical ``meas_limbs``/``proofs_limbs``;
        helpers pass ``share_seeds_u8``.  ``public_parts_u8`` is (B, S, SEED)
        when the circuit uses joint randomness.  Returns canonical tensors:
        out_share (B,OUT,n), verifiers (B,num_proofs*VER,n),
        joint_rand_part/corrected_seed (B,SEED) u8 (if applicable), and
        ok (B,) flagging rows needing host fallback.

        ``meas_len_u32`` (B,) engages canonical-shape masking
        (vdaf/canonical.py): this instance is the BUCKET's padded twin and
        each row carries its task's true MEAS_LEN.  Measurement columns at
        or past it are zeroed (the helper XOF expands the bucket width —
        its stream is prefix-stable, but the tail is live data that must
        not reach the wires), the joint-rand-part XOF absorbs the row's
        true ``enc(meas)`` byte length via the length-selected sponge, and
        the gadget-call masks flow into _query_one.  Outputs are
        byte-identical to the row's own unpadded oracle.

        Oracle twin: Prio3.prep_init (janus_tpu/vdaf/prio3.py).
        """
        prio3, flp, jf = self.prio3, self.flp, self.jf
        B = nonces_u8.shape[0]
        ok = jnp.ones((B,), dtype=bool)
        if agg_id == 0:
            meas, proofs = meas_limbs, proofs_limbs
        else:
            meas, proofs, ok_h = self.helper_shares(agg_id, share_seeds_u8)
            ok = ok & ok_h
        ml = calls_live = None
        if meas_len_u32 is not None:
            ml = meas_len_u32.astype(jnp.int32)
            calls_live = self.circ.calls_live_list(ml)
            col = jnp.arange(flp.MEAS_LEN, dtype=jnp.int32)[None, :]
            meas = jnp.where((col < ml[:, None])[:, :, None], meas, 0)

        if isinstance(verify_key, (bytes, bytearray)):
            verify_key = jnp.asarray(np.frombuffer(bytes(verify_key), dtype=np.uint8))
        vk = jnp.broadcast_to(verify_key, (B, verify_key.shape[-1]))
        with _scope("xof.query_rand"):
            qr, ok_q = self._expand_vec(
                vk,
                self._dst(USAGE_QUERY_RANDOMNESS),
                nonces_u8,
                flp.QUERY_RAND_LEN * prio3.num_proofs,
            )
        ok = ok & ok_q

        out: Dict[str, jnp.ndarray] = {}
        jr = None
        if flp.JOINT_RAND_LEN > 0:
            with _scope("xof.joint_rand"):
                # joint_rand_part = XOF(blind, dst, agg_id || nonce || enc(meas))
                agg_b = jnp.broadcast_to(
                    jnp.asarray(np.array([agg_id], dtype=np.uint8)), (B, 1)
                )
                meas_bytes = limbs_to_bytes(meas)
                part_binder = jnp.concatenate([agg_b, nonces_u8, meas_bytes], axis=-1)
                if ml is None:
                    part = self._xof_seed(
                        blinds_u8, self._dst(USAGE_JOINT_RAND_PART), part_binder
                    )
                else:
                    # Canonical padding: the binder embeds enc(meas), whose true
                    # byte length is per-row — absorb with the length-selected
                    # sponge (the padded tail bytes are zero by the mask above,
                    # which the select absorb's pad construction requires).
                    from .keccak_jax import xof_turboshake128_batch_select

                    binder_len = 1 + nonces_u8.shape[-1] + ml * (4 * jf.n)
                    part = xof_turboshake128_batch_select(
                        blinds_u8,
                        self._dst(USAGE_JOINT_RAND_PART),
                        part_binder,
                        prio3.xof.SEED_SIZE,
                        binder_len,
                    )
                # corrected joint rand seed over parts with ours substituted.
                S = prio3.num_shares
                pieces = []
                if agg_id > 0:
                    pieces.append(public_parts_u8[:, :agg_id].reshape(B, -1))
                pieces.append(part)
                if agg_id < S - 1:
                    pieces.append(public_parts_u8[:, agg_id + 1 :].reshape(B, -1))
                seed_binder = jnp.concatenate(pieces, axis=-1)
                zero_seed = jnp.zeros((B, prio3.xof.SEED_SIZE), dtype=jnp.uint8)
                corrected = self._xof_seed(zero_seed, self._dst(USAGE_JOINT_RAND_SEED), seed_binder)
                jr_vec, ok_j = self._expand_vec(
                    corrected,
                    self._dst(USAGE_JOINT_RANDOMNESS),
                    jnp.zeros((B, 0), dtype=jnp.uint8),
                    flp.JOINT_RAND_LEN * prio3.num_proofs,
                )
            ok = ok & ok_j
            jr = jr_vec
            out["joint_rand_part"] = part
            out["corrected_seed"] = corrected

        # Bulk tensors stay canonical; only the per-report multipliers (joint
        # rand, query point t) go to Montgomery form — a handful of elements
        # vs MEAS_LEN + PROOF_LEN full-width conversion passes.
        jr_m = jf.to_mont(jr) if jr is not None else None

        verifiers = []
        for i in range(prio3.num_proofs):
            pm = proofs[:, i * flp.PROOF_LEN : (i + 1) * flp.PROOF_LEN]
            # one query point per gadget: the full QUERY_RAND_LEN segment
            ti = jf.to_mont(
                qr[:, i * flp.QUERY_RAND_LEN : (i + 1) * flp.QUERY_RAND_LEN]
            )
            ji = (
                jr_m[:, i * flp.JOINT_RAND_LEN : (i + 1) * flp.JOINT_RAND_LEN]
                if jr_m is not None
                else jnp.zeros((B, 0, jf.n), dtype=_U32)
            )
            ver, t_ok = self._query_one(
                meas, pm, ji, ti, calls_live=calls_live, ml=ml
            )
            ok = ok & t_ok
            verifiers.append(ver)

        with _scope("verifier.pack"):
            out["verifiers"] = jnp.concatenate(verifiers, axis=1)
        with _scope("flp.truncate"):
            out["out_share"] = self.circ.truncate(jf, meas, self.consts, ml=ml)
        out["ok"] = ok
        return out

    def query_batch(
        self,
        meas_limbs: jnp.ndarray,
        proofs_limbs: jnp.ndarray,
        jr_limbs: Optional[jnp.ndarray],
        qr_limbs: jnp.ndarray,
    ) -> Dict[str, jnp.ndarray]:
        """FLP query ONLY — every XOF output precomputed by the caller.

        The device half of the hybrid path for host-XOF VDAFs (the
        HMAC-SHA256-AES128 multiproof variant, reference:
        core/src/vdaf.rs:178-195): meas (B, MEAS_LEN, n), proofs
        (B, num_proofs*PROOF_LEN, n), jr (B, num_proofs*JR_LEN, n) or None,
        qr (B, num_proofs*QUERY_RAND_LEN, n), all canonical.  Returns
        verifiers (B, num_proofs*VER, n), out_share (B, OUT, n), and ok
        (rows whose query point hit an interpolation root).  Identical
        field math to prep_init's verifier loop — byte parity with the
        oracle's FlpGeneric.query per proof.
        """
        prio3, flp, jf = self.prio3, self.flp, self.jf
        B = meas_limbs.shape[0]
        ok = jnp.ones((B,), dtype=bool)
        jr_m = jf.to_mont(jr_limbs) if jr_limbs is not None else None
        verifiers = []
        for i in range(prio3.num_proofs):
            pm = proofs_limbs[:, i * flp.PROOF_LEN : (i + 1) * flp.PROOF_LEN]
            ti = jf.to_mont(
                qr_limbs[:, i * flp.QUERY_RAND_LEN : (i + 1) * flp.QUERY_RAND_LEN]
            )
            ji = (
                jr_m[:, i * flp.JOINT_RAND_LEN : (i + 1) * flp.JOINT_RAND_LEN]
                if jr_m is not None
                else jnp.zeros((B, 0, jf.n), dtype=_U32)
            )
            ver, t_ok = self._query_one(meas_limbs, pm, ji, ti)
            ok = ok & t_ok
            verifiers.append(ver)
        return {
            "verifiers": jnp.concatenate(verifiers, axis=1),
            "out_share": self.circ.truncate(jf, meas_limbs, self.consts),
            "ok": ok,
        }

    def decide_batch(self, combined_verifiers: jnp.ndarray) -> jnp.ndarray:
        """Decide from the COMBINED (summed) verifier tensor — the field
        half of prep_shares_to_prep, XOF-free for the hybrid backend."""
        prio3, flp, jf, circ = self.prio3, self.flp, self.jf, self.circ
        B = combined_verifiers.shape[0]
        decide = jnp.ones((B,), dtype=bool)
        for i in range(prio3.num_proofs):
            ver = combined_verifiers[
                :, i * flp.VERIFIER_LEN : (i + 1) * flp.VERIFIER_LEN
            ]
            decide = decide & jf.is_zero(ver[:, 0])
            idx = 1
            for gi, plan in enumerate(circ.plans):
                x = ver[:, idx : idx + plan.arity]
                y_scaled = jf.from_mont(ver[:, idx + plan.arity])
                g = circ.gadget_eval_scaled_g(gi, jf, x)
                decide = decide & jf.eq(g, y_scaled)
                idx += plan.arity + 1
        return decide

    # -- planar (limb-plane) helper prep --------------------------------
    def planar_eligible(self, agg_id: int, batch: int) -> bool:
        """True when the limb-planar Pallas fast path serves this prep."""
        from .keccak_pallas import pallas_enabled

        if self.field_backend == "mxu":
            # The MXU layer lives on the row-major path: its contractions
            # want (batch x K) matrices feeding dot_general, not lane-planar
            # tensors feeding the VPU Pallas kernels.  field_backend is the
            # A/B seam between the two accelerated layouts.
            return False
        if isinstance(self.circ, _DHistogram):
            # u16-half lazy meas_sum is exact only up to 65535 terms.
            circuit_ok = self.flp.MEAS_LEN <= 65535
        elif isinstance(self.circ, _DSumVec):
            # bits > 1 would need a planar truncate (out_share != meas).
            circuit_ok = self.flp.valid.bits == 1
        else:
            # Count/Sum ride the all-planes small-circuit path.
            circuit_ok = isinstance(self.circ, (_DCount, _DSum))
        return (
            circuit_ok
            and self.prio3.num_proofs == 1
            # planar aggregate's lazy batch sum is exact to 65535 terms.
            and batch <= 65535
            and pallas_enabled(batch)
        )

    def _planar_ok(self, stream, num_elems):
        """Canonicality of stream-ordered element words -> ok (B,) row-major."""
        jf = self.jf
        el = stream[: num_elems * jf.n].reshape(num_elems, jf.n, *stream.shape[1:])
        borrow = jnp.zeros(el.shape[0:1] + el.shape[2:], dtype=_U32)
        from .field_jax import _sbb

        for i in range(jf.n):
            _, borrow = _sbb(el[:, i], jnp.asarray(np.uint32(jf.p_np[i])), borrow)
        valid = jnp.all(borrow == 1, axis=0)  # (R, 128)
        return valid.reshape(-1)

    def _rows_to_planes_small(self, rows3):
        """(B, L, n) row-major limbs -> (R, n, L, 128) planes (narrow L)."""
        B, L, n = rows3.shape
        return rows3.reshape(B // 128, 128, L, n).transpose(0, 3, 2, 1)

    def _ones_planes(self, R):
        jf = self.jf
        return [jnp.broadcast_to(jf.mont_one()[l], (R, 128)) for l in range(jf.n)]

    def _geom_planes(self, first, ratio, count):
        """first * ratio^i for i < count on limb-list planes: n arrays
        (R, 128) Montgomery -> n arrays (R, count, 128).  Planar twin of
        JField.geom_mont: a sequential chain run as a scan, so the graph
        holds one multiply however long the chain is."""
        jf = self.jf

        def step(acc, _):
            return tuple(jf.mont_mul_limbs(list(acc), ratio)), acc

        _, out = lax.scan(step, tuple(first), None, length=count)
        return [jnp.moveaxis(o, 0, 1) for o in _scan_fence(out)]

    def _sum_planes(self, x, axis: int = 1):
        """Modular sum of limb-list planes over one (short) axis."""
        acc = [lax.index_in_dim(l_, 0, axis, keepdims=False) for l_ in x]
        for j in range(1, x[0].shape[axis]):
            acc = self.jf.add_limbs(
                acc, [lax.index_in_dim(l_, j, axis, keepdims=False) for l_ in x]
            )
        return acc

    def _pow_range_planes(self, x_pl, count):
        """x^1..x^count on limb-list planes via baby-step/giant-step.

        x_pl: n arrays (R, 128) Montgomery -> n arrays (R, count, 128).
        Exact Montgomery identities (byte parity with cumprod)."""
        import math

        jf = self.jf
        R = x_pl[0].shape[0]
        bs = max(1, math.isqrt(count))
        gs = -(-count // bs)
        baby_t = self._geom_planes(x_pl, x_pl, bs)  # x^(i+1)
        giant_t = self._geom_planes(
            self._ones_planes(R), [b[:, -1] for b in baby_t], gs
        )  # x^(bs*g)
        outer = jf.mont_mul_limbs(
            [g[:, :, None, :] for g in giant_t], [b[:, None, :, :] for b in baby_t]
        )
        return [o.reshape(R, gs * bs, 128)[:, :count] for o in outer]

    def _gpoly_at_planes(self, gp, t_pl):
        """gpoly(t) on limb-list planes (baby-step/giant-step).

        gp: n arrays (R, glen, 128) canonical coefficients, t_pl: n arrays
        (R, 128) Montgomery -> n arrays (R, 128) canonical."""
        import math

        jf = self.jf
        glen = gp[0].shape[1]
        R = gp[0].shape[0]
        bs = max(1, math.isqrt(glen))
        gs = -(-glen // bs)
        one = self._ones_planes(R)
        baby_t = self._geom_planes(one, t_pl, bs)  # t^j, j < bs
        tbs = jf.mont_mul_limbs([b[:, -1] for b in baby_t], t_pl)  # t^bs
        giant_t = self._geom_planes(one, tbs, gs)  # t^(bs*g)
        cg = [
            jnp.pad(c, ((0, 0), (0, gs * bs - glen), (0, 0))).reshape(R, gs, bs, 128)
            for c in gp
        ]
        # c_j * t^(j % bs), summed over the baby axis, then * t^(bs*g)
        terms = jf.mont_mul_limbs(cg, [b[:, None] for b in baby_t])
        inner = self._sum_planes(terms, axis=2)  # (R, gs, 128)
        return self._sum_planes(jf.mont_mul_limbs(inner, giant_t))

    def _lagrange_planes(self, t_pl):
        """Planar twin of _lagrange_coeffs.

        t_pl: limb list of (R, 128) Montgomery -> (lag_pl (R, n, K, 128)
        Montgomery, t_ok (R, 128) bool).  Same inversion-free barycentric
        construction (z/(t - w^k) = prod_{j != k} (t - w^j)); the exclusive
        prefix and suffix products are two scans of lane-wide multiplies
        instead of T(1,128) row passes.  Byte parity follows from exact
        Montgomery identities.
        """
        jf, circ = self.jf, self.circ
        n = jf.n
        R = t_pl[0].shape[0]
        P = circ.P
        K = circ.calls + 1
        one = self._ones_planes(R)

        tp = t_pl
        for _ in range(self._log2_P):
            tp = jf.mont_mul_limbs(tp, tp)
        z = jf.sub_limbs(tp, one)  # t^P - 1
        nz = z[0]
        for l in range(1, n):
            nz = nz | z[l]
        t_ok = nz != 0

        roots = self.roots_all_m  # (P, n) Montgomery
        denom = jf.sub_limbs(
            [jnp.broadcast_to(t[None], (P, R, 128)) for t in t_pl],
            [jnp.broadcast_to(roots[:, l][:, None, None], (P, R, 128)) for l in range(n)],
        )  # t - w^k, stacked on the leading axis

        def step(acc, d):
            return tuple(jf.mont_mul_limbs(list(acc), list(d))), acc

        _, prefix = lax.scan(step, tuple(one), tuple(denom))
        _, suffix = lax.scan(step, tuple(one), tuple(denom), reverse=True)
        prefix, suffix = _scan_fence((prefix, suffix))
        others = jf.mont_mul_limbs([x[:K] for x in prefix], [x[:K] for x in suffix])
        bary = self.bary_c_m  # (K, n) Montgomery
        lag = jf.mont_mul_limbs(
            others,
            [jnp.broadcast_to(bary[:, l][:, None, None], (K, R, 128)) for l in range(n)],
        )
        lag_pl = jnp.stack(lag, axis=0).transpose(2, 0, 1, 3)  # (R, n, K, 128)
        return lag_pl, t_ok

    def _alpha_mat_m(self, gi: int = 0):
        """Constant w^{k*j} Montgomery table (calls, glen, n) per gadget for
        the direct-sum / Vandermonde gadget evaluation (lazy)."""
        mat = self._alpha_mat_cache.get(gi)
        if mat is None:
            field, jf = self.flp.field, self.jf
            plan = self.circ.plans[gi]
            p = field.MODULUS
            w = field.root(plan.P)

            def mont_np(x: int) -> np.ndarray:
                return jf._int_to_limbs_np((x % p) * (1 << (32 * jf.n)) % p)

            # Cached as a HOST array: a jnp constant created inside one jit
            # trace must not be cached across traces (tracer leak).
            mat = np.stack(
                [
                    np.stack(
                        [mont_np(pow(w, k * j, p)) for j in range(plan.glen)]
                    )
                    for k in range(1, plan.calls + 1)
                ]
            )  # (calls, glen, n)
            self._alpha_mat_cache[gi] = mat
        return mat

    def _gadget_outputs_planes(self, gp):
        """gk[k] = gpoly(alpha^k), k = 1..calls, on limb-list planes.

        gp: n arrays (R, glen, 128) canonical coefficients -> n arrays
        (R, calls, 128) canonical.  The DIRECT sum over coefficients times
        constant w^{kj} powers — the same residue the row path's Horner
        chain produces, and canonical limbs are unique, so byte parity
        holds while the glen-step serial chain over T(1,128) row tensors
        disappears.  One wide multiply serves every call.
        """
        jf = self.jf
        amat = self._alpha_mat_m()  # (calls, glen, n)
        terms = jf.mont_mul_limbs(
            [c[:, None] for c in gp],
            [jnp.asarray(amat[:, :, l])[None, :, :, None] for l in range(jf.n)],
        )  # (R, calls, glen, 128)
        return self._sum_planes(terms, axis=2)

    def _histogram_coeff_planes(self, jr_m, lag_pl, cp):
        """Planar twin of _DHistogram.planar_coeffs.

        Generates every wire-kernel coefficient tensor DIRECTLY in plane
        layout with limb-list Montgomery ops (lanes = reports), so no
        full-width row-major (B, chunk, n) pass exists — XLA lays those out
        T(1,128) (batch minor) at several times the planar cost.  The chunk
        power table r^(u+1) uses baby-step/giant-step (two ~sqrt(cp)
        sequential chains of lane-wide multiplies + one wide outer product).
        Every step is an exact Montgomery identity, so the values are
        byte-identical to planar_coeffs (tests/test_prepare.py planar
        parity).  Returns (rch_pl (R,n,cp,128), kl_pl (R,n,calls,128),
        lagk_pl, lag0_pl (R,n,128), ccorr_pl (R,n,128)).

        Pad columns u in [chunk, cp) get REAL powers r^(u+1) rather than
        planar_coeffs' zero padding — sound because the measurement pad
        columns are zero, so those wire outputs are garbage either way and
        the consumers mask/slice them.
        """
        jf, circ = self.jf, self.circ
        n = jf.n
        calls = circ.calls
        jr_pl = self._rows_to_planes_small(jr_m)  # (R, n, JR, 128)
        R = jr_pl.shape[0]
        one = self._ones_planes(R)
        r = [jr_pl[:, l, 0] for l in range(n)]
        rch = self._pow_range_planes(r, cp)
        rc = [l_[:, circ.chunk - 1] for l_ in rch]  # r^chunk
        r_call_t = self._geom_planes(one, rc, calls)  # r^(chunk*k)
        lagk_t = [lag_pl[:, l, 1 : 1 + calls] for l in range(n)]
        kl = jf.mont_mul_limbs(r_call_t, lagk_t)

        lag_sum = self._sum_planes(lagk_t)
        c = self.consts["shares_inv_c"]
        c_pl = [jnp.broadcast_to(c[l], (R, 128)) for l in range(n)]
        ccorr = jf.mont_mul_limbs(c_pl, lag_sum)

        return (
            jnp.stack(rch, axis=1),  # (R, n, cp, 128)
            jnp.stack(kl, axis=1),  # (R, n, calls, 128)
            jnp.stack(lagk_t, axis=1),  # (R, n, calls, 128)
            lag_pl[:, :, 0],  # (R, n, 128)
            jnp.stack(ccorr, axis=1),  # (R, n, 128)
        )

    def _jr_part_planes(self, agg_id, blinds_u8, nonces_u8, meas_stream):
        """Joint-rand-part XOF with the 16 KB meas binder built in-plane.

        The message is  len(dst) || dst || blind || agg_id || nonce ||
        meas_bytes || padding.  meas_bytes already exist as the XOF squeeze
        planes; a 16/8/24-bit funnel shift aligns them into message words,
        replacing a byte-level concat plus a full-batch lane transpose.
        Byte-identical to the row-major absorb (tests/test_prepare.py).
        """
        from .keccak_pallas import (
            RATE,
            RATE_WORDS,
            absorb_planes_pallas,
            rows_to_planes,
        )
        from .keccak_jax import bytes_to_words

        jf = self.jf
        B = nonces_u8.shape[0]
        R = B // 128
        dst = self._dst(USAGE_JOINT_RAND_PART)
        W_m = meas_stream.shape[0]
        hb_len = 1 + len(dst) + blinds_u8.shape[-1] + 1 + nonces_u8.shape[-1]
        q, rm = divmod(hb_len, 4)
        msg_len = hb_len + 4 * W_m
        nblocks = msg_len // RATE + 1
        msg_words = nblocks * RATE_WORDS

        # Head: constant prefix + per-report blind/agg_id/nonce, padded to a
        # word boundary, as (ceil(hb_len/4), R, 128) planes.
        prefix = np.frombuffer(bytes([len(dst)]) + dst, dtype=np.uint8)
        agg_b = jnp.broadcast_to(jnp.asarray(np.array([agg_id], dtype=np.uint8)), (B, 1))
        head_pad = (-hb_len) % 4
        head_parts = [
            jnp.broadcast_to(jnp.asarray(prefix), (B, len(prefix))),
            blinds_u8,
            agg_b,
            nonces_u8,
        ]
        if head_pad:
            head_parts.append(jnp.zeros((B, head_pad), dtype=jnp.uint8))
        head_words = bytes_to_words(jnp.concatenate(head_parts, axis=-1))
        head_planes = rows_to_planes(head_words)  # (q or q+1, R, 128)

        # Tail: TurboSHAKE padding bytes (constant), as extension words so
        # the funnel below can treat meas+pad as one stream.  The funnel
        # consumes msg_words - q extension words total; the meas stream
        # provides W_m, so (rm + pad_len)/4 constant words complete it
        # (exact: 4*msg_words = 4*q + rm + 4*W_m + pad_len).
        pad_len = nblocks * RATE - msg_len
        pad_words_needed = (rm + pad_len) // 4
        pad = np.zeros(pad_words_needed * 4, dtype=np.uint8)
        pad[0] = 0x01
        pad[pad_len - 1] ^= 0x80
        pad_words_np = pad.view("<u4").astype(np.uint32)
        ext_const = jnp.broadcast_to(
            jnp.asarray(pad_words_np)[:, None, None], (pad_words_needed, R, 128)
        )
        ext = jnp.concatenate([meas_stream, ext_const], axis=0)

        if rm == 0:
            body = ext[: msg_words - q]
            msg = jnp.concatenate([head_planes[:q], body], axis=0)
        else:
            sh = 8 * rm
            boundary = head_planes[q] | (ext[0] << sh)
            nbody = msg_words - q - 1
            body = (ext[:nbody] >> (32 - sh)) | (ext[1 : nbody + 1] << sh)
            msg = jnp.concatenate([head_planes[:q], boundary[None], body], axis=0)

        seed_words = self.prio3.xof.SEED_SIZE // 4
        return absorb_planes_pallas(msg, seed_words)  # (seed_words, R, 128)

    def prep_init_planar(
        self,
        agg_id: int,
        verify_key,
        nonces_u8: jnp.ndarray,
        *,
        share_seeds_u8: Optional[jnp.ndarray] = None,
        meas_limbs: Optional[jnp.ndarray] = None,
        proofs_limbs: Optional[jnp.ndarray] = None,
        blinds_u8: Optional[jnp.ndarray] = None,
        public_parts_u8: Optional[jnp.ndarray] = None,
        keep_planar: bool = False,
    ) -> Dict[str, jnp.ndarray]:
        """Prep in the limb-planar layout (histogram family), either side.

        Helpers (agg_id > 0) pass ``share_seeds_u8`` and the meas/proof
        streams come from the planar XOF squeeze; the leader (agg_id == 0)
        passes its explicit ``meas_limbs``/``proofs_limbs`` row-major and
        they are lane-transposed into the same stream planes (no XOF
        expansion and no canonicality recheck — reference leader prep:
        aggregator/src/aggregator/aggregation_job_driver.rs:397-449).

        Same outputs as prep_init except ``out_share`` stays limb-planar
        (R, n, OUTPUT_LEN, 128) — ``aggregate`` consumes either layout.  The
        stream planes feed the Pallas wire kernel directly; nothing
        batch-wide is lane-transposed except the (small) verifier tensor.
        """
        if isinstance(self.circ, (_DCount, _DSum)):
            return self.prep_init_planar_small(
                agg_id,
                verify_key,
                nonces_u8,
                share_seeds_u8=share_seeds_u8,
                meas_limbs=meas_limbs,
                proofs_limbs=proofs_limbs,
                blinds_u8=blinds_u8,
                public_parts_u8=public_parts_u8,
            )
        from .keccak_jax import words_to_bytes
        from .keccak_pallas import rows_to_planes, xof_planes_pallas
        from .flp_pallas import pad_chunk, wire_evals_planar, _pallas_interpret

        prio3, flp, jf, circ = self.prio3, self.flp, self.jf, self.circ
        B = nonces_u8.shape[0]
        R = B // 128
        n = jf.n

        if agg_id == 0:
            # Leader: explicit shares -> stream planes (word w of element e,
            # limb l at stream position e*n + l, little-endian — the same
            # order the XOF squeeze emits).
            meas_st = rows_to_planes(meas_limbs.reshape(B, flp.MEAS_LEN * n))
            proofs_st = rows_to_planes(
                proofs_limbs.reshape(B, flp.PROOF_LEN * n)
            )
            ok = jnp.ones((B,), dtype=bool)
        else:
            binder = jnp.broadcast_to(
                jnp.asarray(np.array([agg_id], dtype=np.uint8)), (B, 1)
            )
            with _scope("xof.expand_meas"):
                meas_st = xof_planes_pallas(
                    share_seeds_u8, self._dst(USAGE_MEAS_SHARE), binder, flp.MEAS_LEN * n
                )  # (MEAS_LEN*n, R, 128)
            with _scope("xof.expand_proof"):
                proofs_st = xof_planes_pallas(
                    share_seeds_u8, self._dst(USAGE_PROOF_SHARE), binder, flp.PROOF_LEN * n
                )
            ok = self._planar_ok(meas_st, flp.MEAS_LEN) & self._planar_ok(
                proofs_st, flp.PROOF_LEN
            )

        # Limb-planar views: lanes stay report-indexed throughout.  The
        # histogram wire kernel reads the RAW streams (one transpose each —
        # circuit padding / per-call splitting / seed de-interleaving happen
        # in-register); only the SumVec slab path still builds the padded
        # chunk layout.
        cp = pad_chunk(circ.chunk)
        m_el = meas_st.reshape(flp.MEAS_LEN, n, R, 128)
        m_lp = m_el.transpose(2, 1, 0, 3)  # (R, n, MEAS_LEN, 128)
        p_el = proofs_st.reshape(flp.PROOF_LEN, n, R, 128)
        p_lp = p_el.transpose(2, 1, 0, 3)  # (R, n, PROOF_LEN, 128)
        gpoly = (
            p_el[circ.arity :].transpose(2, 3, 0, 1).reshape(B, circ.glen, n)
        )  # small row-major

        with _scope("xof.joint_rand"):
            # Joint randomness: part from the in-plane absorb, the rest row-major.
            part_planes = self._jr_part_planes(agg_id, blinds_u8, nonces_u8, meas_st)
            from .keccak_pallas import planes_to_rows

            part = words_to_bytes(planes_to_rows(part_planes))  # (B, SEED)
            S = prio3.num_shares
            pieces = []
            if agg_id > 0:
                pieces.append(public_parts_u8[:, :agg_id].reshape(B, -1))
            pieces.append(part)
            if agg_id < S - 1:
                pieces.append(public_parts_u8[:, agg_id + 1 :].reshape(B, -1))
            seed_binder = jnp.concatenate(pieces, axis=-1)
            zero_seed = jnp.zeros((B, prio3.xof.SEED_SIZE), dtype=jnp.uint8)
            corrected = self._xof_seed(zero_seed, self._dst(USAGE_JOINT_RAND_SEED), seed_binder)
            jr_vec, ok_j = self._expand_vec(
                corrected,
                self._dst(USAGE_JOINT_RANDOMNESS),
                jnp.zeros((B, 0), dtype=jnp.uint8),
                flp.JOINT_RAND_LEN,
            )
        if isinstance(verify_key, (bytes, bytearray)):
            verify_key = jnp.asarray(np.frombuffer(bytes(verify_key), dtype=np.uint8))
        vk = jnp.broadcast_to(verify_key, (B, verify_key.shape[-1]))
        with _scope("xof.query_rand"):
            qr, ok_q = self._expand_vec(
                vk, self._dst(USAGE_QUERY_RANDOMNESS), nonces_u8, flp.QUERY_RAND_LEN
            )
        ok = ok & ok_j & ok_q

        jr_m = jf.to_mont(jr_vec)
        t_m = jf.to_mont(qr[:, 0])

        ev_pl = od_pl = None
        if isinstance(circ, _DHistogram):
            from .flp_pallas import _grid_chunk

            t_planes_a = self._rows_to_planes_small(t_m[:, None, :])[:, :, 0]
            t_pl = [t_planes_a[:, l] for l in range(n)]
            lag_pl, t_ok_pl = self._lagrange_planes(t_pl)
            ok = ok & t_ok_pl.reshape(B)
            NJc, UCc = _grid_chunk(circ.chunk)
            rch_pl, kl_pl, lagk_pl, lag0_pl, ccorr_pl = self._histogram_coeff_planes(
                jr_m, lag_pl, NJc * UCc
            )
            with _scope("flp.wire_evals"):
                ev_pl, od_pl = wire_evals_planar(
                    jf,
                    flp.MEAS_LEN,
                    circ.chunk,
                    m_lp,
                    p_lp,
                    rch_pl,
                    kl_pl,
                    lagk_pl,
                    lag0_pl,
                    ccorr_pl,
                    interpret=_pallas_interpret(),
                )  # each (R, n, chunk, 128)
            # Gadget polynomial: planar direct-sum evaluation (no glen-step
            # row-major Horner chain); gk back to rows only for the tiny
            # (B, calls, n) v computation.
            gp = [p_lp[:, l, circ.arity :] for l in range(n)]  # (R, glen, 128)
            with _scope("flp.gadget_eval"):
                gk = (
                    jnp.stack(self._gadget_outputs_planes(gp), axis=1)
                    .transpose(0, 3, 2, 1)
                    .reshape(B, circ.calls, n)
                )
                gpt_limbs = self._gpoly_at_planes(gp, t_pl)
            gp_t = (
                jnp.stack(gpt_limbs, axis=1).transpose(0, 2, 1).reshape(B, n)
            )
            # v from the lazily-summed measurement (see JField._sum_lazy).
            slo = jnp.sum(m_lp & np.uint32(0xFFFF), axis=2)  # (R, n, 128)
            shi = jnp.sum(m_lp >> 16, axis=2)
            meas_sum = jf.lazy_fold(
                slo.transpose(0, 2, 1).reshape(B, n),
                shi.transpose(0, 2, 1).reshape(B, n),
            )
            v = circ.v_from_meas_sum(jf, gk, meas_sum, jr_m, self.consts)
        else:  # _DSumVec: padded chunk layout for the call-slab kernels
            lag, t_ok = self._lagrange_coeffs(t_m)
            ok = ok & t_ok
            if circ.pad_len:
                m_pad = jnp.concatenate(
                    [m_lp, jnp.zeros((R, n, circ.pad_len, 128), dtype=_U32)],
                    axis=2,
                )
            else:
                m_pad = m_lp
            m_pl = m_pad.reshape(R, n, circ.calls, circ.chunk, 128)
            if cp != circ.chunk:
                m_pl = jnp.pad(
                    m_pl, ((0, 0), (0, 0), (0, 0), (0, cp - circ.chunk), (0, 0))
                )
            swe_pl = p_lp[:, :, 0 : circ.arity : 2]
            swo_pl = p_lp[:, :, 1 : circ.arity : 2]
            if cp != circ.chunk:
                hpad = ((0, 0), (0, 0), (0, cp - circ.chunk), (0, 0))
                swe_pl = jnp.pad(swe_pl, hpad)
                swo_pl = jnp.pad(swo_pl, hpad)
            wire = self._sumvec_wires_planar(m_pl, swe_pl, swo_pl, jr_m, lag, cp)
            gk = self._gadget_outputs(gpoly, B)
            v = jf.sum(gk, axis=1)
            gp_t = self._gpoly_at(gpoly, t_m)

        out = {
            "out_share": m_lp,  # planar; aggregate() accepts this layout
            "ok": ok,
            "joint_rand_part": part,
            "corrected_seed": corrected,
        }
        if ev_pl is not None and keep_planar:
            # Planar-combine consumers: wires stay in plane layout; only the
            # tiny v / gpoly(t) rows leave it.  No row-major verifier is
            # materialized (prep_shares_to_prep_planar pairs the planes
            # directly).
            out.update(wire_ev_pl=ev_pl, wire_od_pl=od_pl, v_row=v, gpt_row=gp_t)
            return out
        if ev_pl is not None:
            wire = self._zip_planes_to_rows(ev_pl, od_pl)[:, : circ.arity]
        with _scope("verifier.pack"):
            out["verifiers"] = jnp.concatenate([v[:, None], wire, gp_t[:, None]], axis=1)
        return out

    def _stream_to_limb_planes(self, stream, num_elems):
        """(L*n, R, 128) stream words -> limb list of n arrays (R, L, 128)."""
        jf = self.jf
        el = stream[: num_elems * jf.n].reshape(num_elems, jf.n, -1, 128)
        return [el[:, l].transpose(1, 0, 2) for l in range(jf.n)]

    def prep_init_planar_small(
        self,
        agg_id: int,
        verify_key,
        nonces_u8: jnp.ndarray,
        *,
        share_seeds_u8: Optional[jnp.ndarray] = None,
        meas_limbs: Optional[jnp.ndarray] = None,
        proofs_limbs: Optional[jnp.ndarray] = None,
        blinds_u8: Optional[jnp.ndarray] = None,
        public_parts_u8: Optional[jnp.ndarray] = None,
    ) -> Dict[str, jnp.ndarray]:
        """Count/Sum prep entirely in plane layout (no wire Pallas kernel).

        These circuits move a few dozen field elements per report, so the
        whole FLP query fits in limb-list ops over (R, L, 128) planes —
        which XLA tiles (8, 128) and fuses well, unlike the row-major
        T(1,128) emission this path replaces.  The XOF expansion/absorb
        still runs in the planar Keccak kernels.  Outputs and byte parity
        match prep_init exactly (tests/test_prepare.py); out_share stays
        planar (R, n, OUTPUT_LEN, 128) like prep_init_planar's.

        Reference twins: leader aggregation_job_driver.rs:397-449, helper
        aggregator.rs:2101 — both sides of the small-circuit VDAFs ride the
        same accelerated path as the histogram family.
        """
        from .keccak_jax import words_to_bytes
        from .keccak_pallas import rows_to_planes, xof_planes_pallas

        prio3, flp, jf, circ = self.prio3, self.flp, self.jf, self.circ
        B = nonces_u8.shape[0]
        R = B // 128
        n = jf.n
        has_jr = flp.JOINT_RAND_LEN > 0

        if agg_id == 0:
            meas_st = rows_to_planes(meas_limbs.reshape(B, flp.MEAS_LEN * n))
            proofs_st = rows_to_planes(proofs_limbs.reshape(B, flp.PROOF_LEN * n))
            ok = jnp.ones((B,), dtype=bool)
        else:
            binder = jnp.broadcast_to(
                jnp.asarray(np.array([agg_id], dtype=np.uint8)), (B, 1)
            )
            with _scope("xof.expand_meas"):
                meas_st = xof_planes_pallas(
                    share_seeds_u8, self._dst(USAGE_MEAS_SHARE), binder, flp.MEAS_LEN * n
                )
            with _scope("xof.expand_proof"):
                proofs_st = xof_planes_pallas(
                    share_seeds_u8, self._dst(USAGE_PROOF_SHARE), binder, flp.PROOF_LEN * n
                )
            ok = self._planar_ok(meas_st, flp.MEAS_LEN) & self._planar_ok(
                proofs_st, flp.PROOF_LEN
            )

        m = self._stream_to_limb_planes(meas_st, flp.MEAS_LEN)  # n x (R, MEAS, 128)
        p = self._stream_to_limb_planes(proofs_st, flp.PROOF_LEN)
        sw = [x[:, : circ.arity] for x in p]
        gp = [x[:, circ.arity :] for x in p]

        out: Dict[str, jnp.ndarray] = {}
        if has_jr:
            part_planes = self._jr_part_planes(agg_id, blinds_u8, nonces_u8, meas_st)
            from .keccak_pallas import planes_to_rows

            part = words_to_bytes(planes_to_rows(part_planes))
            S = prio3.num_shares
            pieces = []
            if agg_id > 0:
                pieces.append(public_parts_u8[:, :agg_id].reshape(B, -1))
            pieces.append(part)
            if agg_id < S - 1:
                pieces.append(public_parts_u8[:, agg_id + 1 :].reshape(B, -1))
            seed_binder = jnp.concatenate(pieces, axis=-1)
            zero_seed = jnp.zeros((B, prio3.xof.SEED_SIZE), dtype=jnp.uint8)
            corrected = self._xof_seed(
                zero_seed, self._dst(USAGE_JOINT_RAND_SEED), seed_binder
            )
            jr_vec, ok_j = self._expand_vec(
                corrected,
                self._dst(USAGE_JOINT_RANDOMNESS),
                jnp.zeros((B, 0), dtype=jnp.uint8),
                flp.JOINT_RAND_LEN,
            )
            ok = ok & ok_j
            out["joint_rand_part"] = part
            out["corrected_seed"] = corrected
            jr_m = jf.to_mont(jr_vec)
            jr_planes = self._rows_to_planes_small(jr_m)
            jr_pl = [jr_planes[:, l, 0] for l in range(n)]  # (R, 128) limbs

        if isinstance(verify_key, (bytes, bytearray)):
            verify_key = jnp.asarray(np.frombuffer(bytes(verify_key), dtype=np.uint8))
        vk = jnp.broadcast_to(verify_key, (B, verify_key.shape[-1]))
        with _scope("xof.query_rand"):
            qr, ok_q = self._expand_vec(
                vk, self._dst(USAGE_QUERY_RANDOMNESS), nonces_u8, flp.QUERY_RAND_LEN
            )
        ok = ok & ok_q
        t_m = jf.to_mont(qr[:, 0])
        t_planes = self._rows_to_planes_small(t_m[:, None, :])[:, :, 0]
        t_pl = [t_planes[:, l] for l in range(n)]
        lag_pl, t_ok_pl = self._lagrange_planes(t_pl)
        ok = ok & t_ok_pl.reshape(B)
        lag0 = [lag_pl[:, l, 0] for l in range(n)]
        lagk = [lag_pl[:, l, 1:] for l in range(n)]  # (R, calls, 128)

        with _scope("flp.gadget_eval"):
            # gadget outputs gk at alpha^1..alpha^calls
            if self._ntt is not None:
                P = circ.P
                folded = [
                    jf.add_limbs(
                        [x[:, :P] for x in gp],
                        [
                            jnp.concatenate(
                                [
                                    x[:, P:],
                                    jnp.zeros(
                                        (R, 2 * P - circ.glen, 128), dtype=_U32
                                    ),
                                ],
                                axis=1,
                            )
                            for x in gp
                        ],
                    )[l]
                    for l in range(n)
                ]
                evals = jf.ntt_eval_mont_limbs(folded, *self._ntt)
                gk = [e[:, 1 : circ.calls + 1] for e in evals]
            else:
                gk = self._gadget_outputs_planes(gp)  # (R, calls, 128)

        with _scope("flp.wire_evals"):
            if isinstance(circ, _DCount):
                # v = gk[0] - m[0]; wires w0 = w1 = sw_i*lag0 + m0*lag1
                v = jf.sub_limbs(
                    [g[:, 0] for g in gk], [x[:, 0] for x in m]
                )
                m0lag1 = jf.mont_mul_limbs(
                    [x[:, 0] for x in m], [lk[:, 0] for lk in lagk]
                )
                wires = []
                for i in range(2):
                    se = jf.mont_mul_limbs([x[:, i] for x in sw], lag0)
                    wires.append(jf.add_limbs(se, m0lag1))
            else:  # _DSum
                # v = sum_k r^(k+1) * gk[k]
                r_pows = self._pow_range_planes(jr_pl, circ.calls)  # (R, calls, 128)
                v = self._sum_planes(jf.mont_mul_limbs(r_pows, gk))
                # single wire: sw0*lag0 + sum_k m[k]*lag_{k+1}
                se = jf.mont_mul_limbs([x[:, 0] for x in sw], lag0)
                wires = [
                    jf.add_limbs(se, self._sum_planes(jf.mont_mul_limbs(m, lagk)))
                ]

        with _scope("flp.gadget_eval"):
            gpt = self._gpoly_at_planes(gp, t_pl)

        with _scope("verifier.pack"):
            # verifier rows (B, VERIFIER_LEN, n): tiny stack + transpose
            cols = [v] + wires + [gpt]  # each: n x (R, 128)
            ver_pl = jnp.stack(
                [jnp.stack([col[l] for col in cols], axis=1) for l in range(n)],
                axis=1,
            )  # (R, n, VER, 128)
            out["verifiers"] = ver_pl.transpose(0, 3, 2, 1).reshape(B, len(cols), n)

        with _scope("flp.truncate"):
            # out_share planar (R, n, OUTPUT_LEN, 128)
            if isinstance(circ, _DCount):
                osh = [x[:, 0:1] for x in m]
            else:
                w = self.consts["pow2_m"]  # (bits, n) Montgomery
                terms = jf.mont_mul_limbs(
                    m,
                    [
                        jnp.broadcast_to(w[:, l][None, :, None], (R, circ.calls, 128))
                        for l in range(n)
                    ],
                )
                osh = [a[:, None, :] for a in self._sum_planes(terms)]
            out["out_share"] = jnp.stack(osh, axis=1)  # (R, n, OUT, 128)
        out["ok"] = ok
        return out

    @staticmethod
    def _zip_planes_to_rows(ev_pl, od_pl):
        """Interleave even/odd wire planes -> row-major (B, 2*cp, n)."""
        R, n, cp, _ = ev_pl.shape
        zipped = jnp.stack([ev_pl, od_pl], axis=3)  # (R, n, cp, 2, 128)
        return zipped.transpose(0, 4, 2, 3, 1).reshape(R * 128, 2 * cp, n)

    @staticmethod
    def planar_out_share_to_rows(osp):
        """(R, n, L, 128) planar out shares -> row-major (B, L, n).

        The single place that knows the planar out_share layout outside the
        planar pipeline itself (report b lives at (b // 128, ..., b % 128)).
        """
        R, n, L, _ = osp.shape
        return osp.transpose(0, 3, 2, 1).reshape(R * 128, L, n)

    def _planar_add(self, a, b):
        """Modular add on (R, n, ..., 128) planar tensors (limb axis 1)."""
        jf = self.jf
        return jnp.stack(
            jf.add_limbs([a[:, l] for l in range(jf.n)], [b[:, l] for l in range(jf.n)]),
            axis=1,
        )

    def _sumvec_wires_planar(self, m_pl, swe_pl, swo_pl, jr_m, lag, cp):
        """SumVec wire evaluations via per-call-slab Pallas contractions.

        evens[u] = sum_k m[k,u] * jr_k^(u+1) * lag_{k+1};
        odds[u]  = sum_k m[k,u] * lag_{k+1}  -  ccorr;
        wire     = seeds * lag_0 + zip(evens, odds).

        The evens coefficient klu = jr_k^(u+1) * lag_{k+1} varies over BOTH
        axes (per-call joint rand, power resetting each call), so unlike the
        histogram it cannot fold into a per-call scalar.  It is generated
        and consumed slab-by-slab over the calls axis (lax.scan) so the
        wide-vector circuits — calls=317 for the 100k-element SumVec —
        never materialize a meas-sized coefficient tensor, and each slab's
        contraction runs in the limb-planar kernel.  Exact mod-p identities
        throughout: limbs match the row path (tests/test_prepare.py).
        """
        from .flp_pallas import _pallas_interpret, sumvec_partial_planar

        jf, circ = self.jf, self.circ
        R, n, calls, _, _ = m_pl.shape
        B = R * 128
        lag0, lagk = lag[:, 0], lag[:, 1:]
        lag_sum = jf.sum(lagk, axis=1)
        c = jnp.broadcast_to(self.consts["shares_inv_c"], lag_sum.shape)
        ccorr = jf.mont_mul(c, lag_sum)

        KC = min(calls, 8)
        calls_pad = -(-calls // KC) * KC
        if calls_pad != calls:
            pad = calls_pad - calls
            # zero meas + zero lagk make pad calls contribute exactly 0.
            m_pl = jnp.pad(m_pl, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            lagk = jnp.pad(lagk, ((0, 0), (0, pad), (0, 0)))
            jr_m = jnp.pad(jr_m, ((0, 0), (0, pad), (0, 0)))
        NS = calls_pad // KC
        interpret = _pallas_interpret()

        def slab(s):
            m_slab = lax.dynamic_slice_in_dim(m_pl, s * KC, KC, axis=2)
            jr_s = lax.dynamic_slice_in_dim(jr_m, s * KC, KC, axis=1)
            lagk_s = lax.dynamic_slice_in_dim(lagk, s * KC, KC, axis=1)
            r_pows = jf.pow_range_mont(jr_s, circ.chunk)  # jr_k^(u+1) * R
            klu = jf.mont_mul(
                r_pows, jnp.broadcast_to(lagk_s[:, :, None, :], r_pows.shape)
            )
            if cp != circ.chunk:
                klu = jnp.pad(klu, ((0, 0), (0, 0), (0, cp - circ.chunk), (0, 0)))
            klu_pl = klu.reshape(R, 128, KC, cp, jf.n).transpose(0, 4, 2, 3, 1)
            lagk_pl = self._rows_to_planes_small(lagk_s)
            return sumvec_partial_planar(
                jf, m_slab, klu_pl, lagk_pl, interpret=interpret
            )

        ev, od = slab(0)
        if NS > 1:
            def body(carry, s):
                ev_c, od_c = carry
                ev_p, od_p = slab(s)
                return (
                    self._planar_add(ev_c, ev_p),
                    self._planar_add(od_c, od_p),
                ), None

            (ev, od), _ = lax.scan(body, (ev, od), jnp.arange(1, NS))

        evens_row = ev.transpose(0, 3, 2, 1).reshape(B, cp, n)[:, : circ.chunk]
        odds_row = od.transpose(0, 3, 2, 1).reshape(B, cp, n)[:, : circ.chunk]
        odds_row = jf.sub(odds_row, jnp.broadcast_to(ccorr[:, None, :], odds_row.shape))
        swe_row = swe_pl.transpose(0, 3, 2, 1).reshape(B, cp, n)[:, : circ.chunk]
        swo_row = swo_pl.transpose(0, 3, 2, 1).reshape(B, cp, n)[:, : circ.chunk]
        sw_row = jnp.stack([swe_row, swo_row], axis=2).reshape(B, circ.arity, n)
        se = jf.mont_mul(sw_row, jnp.broadcast_to(lag0[:, None, :], sw_row.shape))
        pair = jnp.stack([evens_row, odds_row], axis=2).reshape(B, circ.arity, n)
        return jf.add(se, pair)

    # -- prep shares -> prep message ------------------------------------
    def prep_shares_to_prep(
        self,
        verifier_shares: List[jnp.ndarray],
        joint_rand_parts_u8: Optional[List[jnp.ndarray]] = None,
    ) -> Dict[str, jnp.ndarray]:
        """Combine verifier shares and decide; derive the joint-rand seed.

        verifier_shares: num_shares tensors (B, num_proofs*VER_LEN, n) canonical.
        Returns {"decide": (B,) bool, "prep_msg_seed": (B,SEED) u8 (if joint rand)}.
        Oracle twin: Prio3.prep_shares_to_prep.
        """
        prio3, flp, jf, circ = self.prio3, self.flp, self.jf, self.circ
        with _scope("combine.decide"):
            combined = verifier_shares[0]
            for vs in verifier_shares[1:]:
                combined = jf.add(combined, vs)
            B = combined.shape[0]
            decide = jnp.ones((B,), dtype=bool)
            for i in range(prio3.num_proofs):
                ver = combined[:, i * flp.VERIFIER_LEN : (i + 1) * flp.VERIFIER_LEN]
                decide = decide & jf.is_zero(ver[:, 0])
                idx = 1
                for gi, plan in enumerate(circ.plans):
                    x = ver[:, idx : idx + plan.arity]  # canonical wire evals
                    # Compare g*R^-1 == y*R^-1 (R invertible => same predicate
                    # as g == y) to skip the to_mont pass over the arity wires.
                    y_scaled = jf.from_mont(ver[:, idx + plan.arity])
                    g = circ.gadget_eval_scaled_g(gi, jf, x)
                    decide = decide & jf.eq(g, y_scaled)
                    idx += plan.arity + 1
        out: Dict[str, jnp.ndarray] = {"decide": decide}
        if flp.JOINT_RAND_LEN > 0:
            binder = jnp.concatenate(list(joint_rand_parts_u8), axis=-1)
            zero_seed = jnp.zeros((B, prio3.xof.SEED_SIZE), dtype=jnp.uint8)
            with _scope("xof.joint_rand"):
                out["prep_msg_seed"] = self._xof_seed(
                    zero_seed, self._dst(USAGE_JOINT_RAND_SEED), binder
                )
        return out

    def prep_shares_to_prep_planar(
        self,
        own: Dict[str, jnp.ndarray],
        peer_verifiers: jnp.ndarray,
        joint_rand_parts_u8: Optional[List[jnp.ndarray]] = None,
    ) -> Dict[str, jnp.ndarray]:
        """Combine + decide with OUR verifier still in plane layout.

        ``own`` is a prep_init_planar(keep_planar=True) result (wire_ev_pl /
        wire_od_pl planes + v_row / gpt_row); ``peer_verifiers`` is the other
        aggregator's share, row-major (B, VERIFIER_LEN, n) canonical as it
        arrives off the wire.  The gadget contraction over the combined
        wires runs in the planar Pallas kernel (combine_decide_planar);
        only v / gpoly(t) / the folded gadget sum touch row layout (tiny).
        Exact mod-p identities throughout — ``decide`` and the derived
        prep-message seed are bit-identical to prep_shares_to_prep
        (tests/test_prepare.py).  num_proofs == 1 (planar_eligible).
        """
        from .flp_pallas import _pallas_interpret, combine_decide_planar

        prio3, flp, jf, circ = self.prio3, self.flp, self.jf, self.circ
        ev_pl, od_pl = own["wire_ev_pl"], own["wire_od_pl"]
        B = peer_verifiers.shape[0]
        # One transpose puts the peer's whole verifier in plane layout; the
        # kernel de-interleaves its zipped wires in-register.
        pv_pl = self._rows_to_planes_small(peer_verifiers)
        with _scope("combine.decide"):
            g_parts = combine_decide_planar(
                jf, circ.chunk, ev_pl, od_pl, pv_pl,
                interpret=_pallas_interpret(),
            )  # (R, n, 8, 128) partial sums
        R, n, S8, _ = g_parts.shape
        g = jf.sum(g_parts.transpose(0, 3, 2, 1).reshape(B, S8, n), axis=1)

        v = jf.add(own["v_row"], peer_verifiers[:, 0])
        y = jf.add(own["gpt_row"], peer_verifiers[:, 1 + circ.arity])
        # g is (a*b)*R^-1-scaled (gadget_eval_scaled); compare against
        # y*R^-1 — R invertible, so the predicate equals g == y.
        decide = jf.is_zero(v) & jf.eq(g, jf.from_mont(y))
        out: Dict[str, jnp.ndarray] = {"decide": decide}
        if flp.JOINT_RAND_LEN > 0:
            binder = jnp.concatenate(list(joint_rand_parts_u8), axis=-1)
            zero_seed = jnp.zeros((B, prio3.xof.SEED_SIZE), dtype=jnp.uint8)
            with _scope("xof.joint_rand"):
                out["prep_msg_seed"] = self._xof_seed(
                    zero_seed, self._dst(USAGE_JOINT_RAND_SEED), binder
                )
        return out

    # -- aggregation -----------------------------------------------------
    def aggregate(self, out_shares: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
        """Masked modular sum of out shares over the batch axis.

        out_shares (B, OUTPUT_LEN, n) canonical — or limb-planar
        (n, OUTPUT_LEN, R, 128) from prep_init_planar — with mask (B,) bool
        -> (OUTPUT_LEN, n).  TPU analog of sharded batch-aggregation
        accumulation (reference:
        aggregator/src/aggregator/aggregation_job_writer.rs:591-698).
        """
        if out_shares.ndim == 4:  # planar (R, n, L, 128): lazy u16 lane reduce
            with _scope("aggregate.sum"):
                R, n, L, _ = out_shares.shape
                maskp = mask.reshape(R, 128)
                masked = jnp.where(
                    maskp[:, None, None], out_shares, jnp.zeros_like(out_shares)
                )
                slo = jnp.sum(masked & np.uint32(0xFFFF), axis=(0, 3))  # (n, L)
                shi = jnp.sum(masked >> 16, axis=(0, 3))
                return self.jf.lazy_fold(slo.T, shi.T)
        with _scope("aggregate.sum"):
            masked = jnp.where(mask[:, None, None], out_shares, jnp.zeros_like(out_shares))
            return self.jf.sum(masked, axis=0)
