"""Batched prime-field arithmetic on u32 limb tensors (JAX, TPU-friendly).

A field element is a little-endian vector of u32 limbs along the trailing
axis: shape (..., n_limbs).  Canonical form = integer < MODULUS; Montgomery
form = x * R mod p with R = 2^(32 n).  ``mont_mul`` is CIOS Montgomery
multiplication built from 16-bit half-limb products (TPU has no 64-bit
integer multiply; 16x16->32 products are exact in u32).

Bit-exactness: all ops are exact integer arithmetic mod p — there is no
rounding or reassociation hazard — so any algebraically-equal formula yields
identical limbs.  Tests compare against janus_tpu.fields on random and edge
values.
"""

from __future__ import annotations

import functools
import math
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_U32 = jnp.uint32
_MASK16 = np.uint32(0xFFFF)
_MASK8 = np.uint32(0xFF)

#: Max contraction length for one ``mat_mul_mont`` dot pass.  The layer
#: contracts base-2^8 digit planes, so every per-digit-pair partial sum
#: P[d,e] = sum_k a_d[k] * b_e[k] is bounded by K * 255^2 and must stay
#: exact in the u32 dot accumulator: K <= floor((2^32-1)/255^2) = 66051.
#: 65536 keeps a round power of two and matches the u16-half lazy-sum cap
#: (JField.sum / planar aggregate) used across the prepare pipeline.
#: Longer contractions split into exact modular-added chunks.
DOT_MAX_K = 65536


def _eager_jit(static_argnums=(0,)):
    """Jit for EAGER callers only; inline when already under a trace.

    Wrapping these methods in plain jax.jit made eager tests fast but
    embedded hundreds of nested pjit calls into every prepare trace, which
    blew XLA CPU compile times from tens of seconds to tens of minutes.
    Tracing callers get the original inlined body; eager callers (tests,
    oracle fallbacks) get a cached compiled version.
    """

    def deco(fn):
        jitted = partial(jax.jit, static_argnums=static_argnums)(fn)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if any(isinstance(a, jax.core.Tracer) for a in args) or any(
                isinstance(v, jax.core.Tracer) for v in kwargs.values()
            ):
                return fn(*args, **kwargs)
            return jitted(*args, **kwargs)

        return wrapper

    return deco


def _u32(x: int):
    return jnp.asarray(np.uint32(x), dtype=_U32)


def _scan_fence(x):
    """Fence a scan's output from its consumers on XLA:CPU.

    XLA:CPU fuses cheap consumers *into* a while-loop body; once the body
    spans multiple fusions the thunk runtime pays a per-iteration
    scheduling penalty that grows with executable size (measured: a
    127-iteration Fermat-inversion scan inside the histogram prepare graph
    went from milliseconds standalone to minutes composed).  An
    optimization_barrier on the scan output keeps the loop body a single
    fused kernel.  TPU keeps the fusion (it's profitable there), so the
    barrier is chosen by the platform the computation is LOWERED for — a
    program compiled for a TPU from a CPU-only process is the program the
    chip runs.
    """
    return lax.platform_dependent(
        x, cpu=lax.optimization_barrier, default=lambda y: y
    )


def _mul32(a, b) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact 32x32 -> 64 multiply as (hi, lo) u32 pairs via 16-bit halves."""
    al = a & _MASK16
    ah = a >> 16
    bl = b & _MASK16
    bh = b >> 16
    ll = al * bl
    lh = al * bh
    hl = ah * bl
    hh = ah * bh
    mid = (ll >> 16) + (lh & _MASK16) + (hl & _MASK16)  # < 2^18, no overflow
    lo = (ll & _MASK16) | ((mid & _MASK16) << 16)
    hi = hh + (lh >> 16) + (hl >> 16) + (mid >> 16)
    return hi, lo


def _adc(a, b, carry_in):
    """a + b + carry_in (carry_in in {0,1}) -> (sum, carry_out in {0,1})."""
    s = a + b
    c1 = (s < a).astype(_U32)
    s2 = s + carry_in
    c2 = (s2 < s).astype(_U32)
    return s2, c1 | c2


def _sbb(a, b, borrow_in):
    """a - b - borrow_in -> (diff, borrow_out in {0,1})."""
    d = a - b
    b1 = (a < b).astype(_U32)
    d2 = d - borrow_in
    b2 = (d < borrow_in).astype(_U32)
    return d2, b1 | b2


def _mac(a, b, acc, carry):
    """a*b + acc + carry -> (hi, lo); fits exactly in 64 bits."""
    hi, lo = _mul32(a, b)
    lo, c = _adc(lo, acc, _u32(0))
    hi = hi + c
    lo, c = _adc(lo, carry, _u32(0))
    hi = hi + c
    return hi, lo


class JField:
    """JAX batched ops for one of the oracle fields (janus_tpu.fields).

    Instances are hashable/equal by their oracle field so the jitted method
    wrappers below share one compilation cache across instances (tests and
    pipelines construct JField freely).
    """

    def __hash__(self):
        return hash(self.oracle)

    def __eq__(self, other):
        return isinstance(other, JField) and other.oracle is self.oracle

    def __init__(self, oracle_field: type):
        self.oracle = oracle_field
        p = oracle_field.MODULUS
        self.p = p
        self.n = oracle_field.ENCODED_SIZE // 4  # u32 limbs per element
        bits = 32 * self.n
        r = (1 << bits) % p
        self.p_np = self._int_to_limbs_np(p)
        self.r2_np = self._int_to_limbs_np(r * r % p)
        self.one_np = self._int_to_limbs_np(1)
        self.n_prime = np.uint32((-pow(p, -1, 1 << 32)) % (1 << 32))
        # p - 2 bits (MSB first) for Fermat inversion.
        self._inv_exp_bits = np.array(
            [int(b) for b in bin(p - 2)[2:]], dtype=np.uint32
        )

    # --- host-side conversions ----------------------------------------
    def _int_to_limbs_np(self, x: int) -> np.ndarray:
        return np.array(
            [(x >> (32 * i)) & 0xFFFFFFFF for i in range(self.n)], dtype=np.uint32
        )

    def to_limbs(self, values: Sequence[int]) -> np.ndarray:
        """Host: python ints -> (len, n) u32 canonical limbs.

        An element's ``4 n`` little-endian bytes ARE its limbs, so the whole
        vector crosses in one byte buffer.  A value outside ``[0, 2**(32 n))``
        is no field element and raises ``OverflowError``; it is not masked."""
        size = 4 * self.n
        buf = bytearray().join([v.to_bytes(size, "little") for v in values])
        flat = np.frombuffer(buf, dtype="<u4").astype(np.uint32, copy=False)
        return flat.reshape(len(values), self.n)

    def from_limbs(self, limbs: np.ndarray) -> List[int]:
        """Host: (..., n) u32 canonical limbs -> python ints (flattened)."""
        arr = np.ascontiguousarray(limbs, dtype="<u4").reshape(-1, self.n)
        # numpy cuts out each element's bytes (a void cell's tolist())
        cells = arr.view(f"V{4 * self.n}").ravel().tolist()
        from_bytes = int.from_bytes  # looked up once, not once an element
        return [from_bytes(c, "little") for c in cells]

    def const(self, value: int) -> jnp.ndarray:
        """Canonical constant as a device limb vector."""
        return jnp.asarray(self._int_to_limbs_np(value % self.p))

    def mont_const(self, value: int) -> jnp.ndarray:
        """Constant already converted to Montgomery form (host-side)."""
        bits = 32 * self.n
        return jnp.asarray(self._int_to_limbs_np((value % self.p) * (1 << bits) % self.p))

    # --- device ops (operate on (..., n) u32; canonical in, canonical out
    #     for add/sub; Montgomery domain for mont_mul chains) -----------
    def zeros(self, shape) -> jnp.ndarray:
        return jnp.zeros(tuple(shape) + (self.n,), dtype=_U32)

    def _split(self, a):
        return [a[..., i] for i in range(self.n)]

    def _join(self, limbs):
        return jnp.stack(limbs, axis=-1)

    def _cond_sub_p(self, limbs, extra_bit):
        """limbs (list of n) + extra_bit*2^(32n); subtract p if >= p."""
        p = [ _u32(int(x)) for x in self.p_np ]
        d = []
        borrow = _u32(0)
        for i in range(self.n):
            di, borrow = _sbb(limbs[i], p[i], borrow)
            d.append(di)
        # subtract if extra_bit set or no borrow (value >= p)
        take = (extra_bit | (1 - borrow)).astype(jnp.bool_)
        return [jnp.where(take, d[i], limbs[i]) for i in range(self.n)]

    def add_limbs(self, aa: List, bb: List) -> List:
        """Canonical modular addition on limb lists (shared XLA/Pallas core)."""
        s = []
        carry = _u32(0)
        for i in range(self.n):
            si, carry = _adc(aa[i], bb[i], carry)
            s.append(si)
        return self._cond_sub_p(s, carry)

    @_eager_jit(static_argnums=(0,))
    def add(self, a, b):
        """Canonical modular addition."""
        return self._join(self.add_limbs(self._split(a), self._split(b)))

    def sub_limbs(self, aa: List, bb: List) -> List:
        """Canonical modular subtraction on limb lists (shared XLA/Pallas core)."""
        d = []
        borrow = _u32(0)
        for i in range(self.n):
            di, borrow = _sbb(aa[i], bb[i], borrow)
            d.append(di)
        # add p back when we borrowed
        p = [ _u32(int(x)) for x in self.p_np ]
        s = []
        carry = _u32(0)
        for i in range(self.n):
            si, carry = _adc(d[i], p[i], carry)
            s.append(si)
        use_add = borrow.astype(jnp.bool_)
        return [jnp.where(use_add, s[i], d[i]) for i in range(self.n)]

    @_eager_jit(static_argnums=(0,))
    def sub(self, a, b):
        """Canonical modular subtraction."""
        return self._join(self.sub_limbs(self._split(a), self._split(b)))

    def neg(self, a):
        return self.sub(self.zeros(a.shape[:-1]), a)

    def _mont_m(self, t0):
        """m = t0 * n_prime mod 2^32; free negation when n_prime == -1.

        Every field whose modulus is 1 mod 2^32 (Field64 = 2^64-2^32+1,
        Field128 = 2^128-7*2^66+1) has n_prime = 0xFFFFFFFF.
        """
        if int(self.n_prime) == 0xFFFFFFFF:
            return jnp.zeros_like(t0) - t0
        return t0 * _u32(int(self.n_prime))

    def _mac_p(self, j: int, m, acc, carry):
        """(hi, lo) of m * p[j] + acc + carry, specialized on the host-known
        limb value of the modulus.  The VDAF fields' moduli have limbs drawn
        from {0, 1, 0xFFFFFFFF, <one odd limb>}, which turns most of the
        CIOS reduction multiplies into adds/negations (~1.4x fewer VPU ops
        per mont_mul; exact same integer result)."""
        pj = int(self.p_np[j])
        zero = jnp.zeros_like(m)
        if pj == 0:
            lo, c = _adc(acc, carry, zero)
            return c, lo
        if pj == 1:
            lo, c1 = _adc(m, acc, zero)
            lo, c2 = _adc(lo, carry, zero)
            return c1 + c2, lo
        if pj == 0xFFFFFFFF:
            # m*(2^32-1) + acc + carry = m*2^32 + (acc + carry - m)
            s1, c1 = _adc(acc, carry, zero)
            d, borrow = _sbb(s1, m, zero)
            return m + c1 - borrow, d
        return _mac(m, _u32(pj), acc, carry)

    def mont_mul_limbs(self, aa: List, bb: List) -> List:
        """CIOS core on limb lists: a*b*R^-1 mod p (shared XLA/Pallas)."""
        n = self.n
        zero = jnp.zeros_like(aa[0] | bb[0])
        t = [zero] * (n + 2)
        for i in range(n):
            carry = zero
            for j in range(n):
                hi, lo = _mac(aa[i], bb[j], t[j], carry)
                t[j] = lo
                carry = hi
            s, c = _adc(t[n], carry, zero)
            t[n] = s
            t[n + 1] = t[n + 1] + c
            m = self._mont_m(t[0])
            hi, _lo = self._mac_p(0, m, t[0], zero)
            carry = hi
            for j in range(1, n):
                hi, lo = self._mac_p(j, m, t[j], carry)
                t[j - 1] = lo
                carry = hi
            s, c = _adc(t[n], carry, zero)
            t[n - 1] = s
            t[n] = t[n + 1] + c
            t[n + 1] = zero
        return self._cond_sub_p(t[:n], t[n])

    @_eager_jit(static_argnums=(0,))
    def mont_mul(self, a, b):
        """CIOS Montgomery multiplication: returns a*b*R^-1 mod p, canonical."""
        return self._join(self.mont_mul_limbs(self._split(a), self._split(b)))

    @_eager_jit(static_argnums=(0,))
    def to_mont(self, a):
        r2 = jnp.asarray(self.r2_np)
        return self.mont_mul(a, jnp.broadcast_to(r2, a.shape))

    @_eager_jit(static_argnums=(0,))
    def from_mont(self, a):
        one = jnp.asarray(self.one_np)
        return self.mont_mul(a, jnp.broadcast_to(one, a.shape))

    def mont_one(self):
        bits = 32 * self.n
        return jnp.asarray(self._int_to_limbs_np((1 << bits) % self.p))

    def _fermat_inv_mont(self, a):
        """Fermat inversion in Montgomery domain: a^(p-2).  inv(0) = 0.

        Two single-multiply scans instead of one square-and-multiply scan:
        phase 1 stacks the squares chain a^(2^i); phase 2 multiplies the
        squares selected by the bits of p-2.  Same exact integer result
        (modular multiplication is associative/commutative), but each scan
        body stays one fused kernel — XLA:CPU's while-loop runtime pays a
        ~0.3 s/iteration scheduling penalty the moment a body spans more
        than one fusion, which turned the old 2-multiply body into a
        63 s dispatch for a (4,) batch (observed; 35 ms this way).
        """
        bits = jnp.asarray(self._inv_exp_bits[::-1].copy())  # LSB-first

        def sq(acc, _):
            return self.mont_mul(acc, acc), acc

        _, squares = lax.scan(sq, a, None, length=bits.shape[0])
        squares = _scan_fence(squares)

        one = jnp.broadcast_to(self.mont_one(), a.shape)

        def mulsel(acc, si_b):
            si, bit = si_b
            return self.mont_mul(acc, jnp.where(bit == 1, si, one)), None

        acc, _ = lax.scan(mulsel, one, (squares, bits))
        return _scan_fence(acc)

    @_eager_jit(static_argnums=(0,))
    def inv_mont(self, a):
        """Inversion in Montgomery domain; inv(0) = 0.

        A single element runs the Fermat square-and-multiply chain
        (``_fermat_inv_mont``).  Any BATCHED input runs Montgomery batch
        inversion instead: the whole batch collapses through one prefix
        product, ONE Fermat chain inverts the single total, and two
        prefix/suffix passes fan the inverse back out — so the
        127-iteration sequential scan (the thing ``_scan_fence`` exists to
        protect on XLA:CPU) runs over ONE field element instead of the
        full tensor, and the deepest sequential chain a vector call site
        pays drops from 2*127 tensor-wide multiplies to one scalar chain
        plus log-depth prefix scans.  Zero entries are substituted with 1
        before the product (a zero would annihilate it) and masked back to
        0 after, preserving inv(0) = 0 exactly.  The inverse of a nonzero
        element is unique and canonical limbs are unique, so the result is
        limb-identical to the per-element Fermat chain.
        """
        batch_elems = 1
        for d in a.shape[:-1]:
            batch_elems *= d
        if batch_elems <= 1:
            return self._fermat_inv_mont(a)
        flat = a.reshape((-1, self.n))
        z = jnp.all(flat == 0, axis=-1)
        one = jnp.broadcast_to(self.mont_one(), flat.shape)
        safe = jnp.where(z[:, None], one, flat)
        inv = self._batch_inv_nonzero(safe, 0)
        inv = jnp.where(z[:, None], jnp.zeros_like(inv), inv)
        return inv.reshape(a.shape)

    @_eager_jit(static_argnums=(0,))
    def eq(self, a, b):
        """Elementwise equality of canonical limb vectors -> bool (...)."""
        return jnp.all(a == b, axis=-1)

    @_eager_jit(static_argnums=(0,))
    def is_zero(self, a):
        return jnp.all(a == 0, axis=-1)

    @_eager_jit(static_argnums=(0, 2))
    def sum(self, a, axis: int):
        """Exact modular reduction along an element axis.

        Long axes use a lazy 16-bit-half accumulation: limbs are split into
        u16 halves, summed with plain (exact, < 2^32) integer reduces, and
        reduced mod p ONCE at the end — replacing length-1 full modular adds
        (carry chain + conditional subtract each) with plain adds.  Exact
        integer math, so the result is limb-identical to the add tree, which
        short axes still use (the lazy path's fixed cost: a digit
        carry-propagation plus one tiny mont_mul).
        """
        axis = axis % (a.ndim - 1)  # never the limb axis
        length = a.shape[axis]
        if 16 <= length <= 65535:
            return self._sum_lazy(a, axis)
        while length > 1:
            half = length // 2
            lo = lax.slice_in_dim(a, 0, half, axis=axis)
            hi = lax.slice_in_dim(a, half, 2 * half, axis=axis)
            rest = lax.slice_in_dim(a, 2 * half, length, axis=axis)
            a = jnp.concatenate([self.add(lo, hi), rest], axis=axis)
            length = half + (length - 2 * half)
        return jnp.squeeze(a, axis=axis)

    def _sum_lazy(self, a, axis: int):
        """Lazy-reduction sum: u16-half accumulate, one mod-p fold at the end.

        Requires a.shape[axis] <= 65535 so each half-column sum stays below
        2^16 * 65535 < 2^32 (exact in u32).
        """
        slo = jnp.sum(a & _MASK16, axis=axis)  # (..., n) each < 2^32
        shi = jnp.sum(a >> 16, axis=axis)
        return self.lazy_fold(slo, shi)

    def lazy_fold(self, slo, shi):
        """(..., n) u16-half column sums -> canonical limbs (..., n).

        Base-2^16 digit stream D[2i] = slo_i, D[2i+1] = shi_i is carry-
        normalized; the overflow beyond 2^(32n) (carry < 2^17) folds back
        via one tiny mont_mul with R^2 (= 2^(32n)*R mod p).  Exact integer
        math — shared by the row-major and limb-planar lazy sums.
        """
        n = self.n
        carry = jnp.zeros_like(slo[..., 0])
        digits = []
        for i in range(n):
            t = slo[..., i] + carry
            digits.append(t & _MASK16)
            carry = t >> 16
            t = shi[..., i] + carry
            digits.append(t & _MASK16)
            carry = t >> 16
        limbs = self._join(
            [digits[2 * j] | (digits[2 * j + 1] << 16) for j in range(n)]
        )
        r2 = jnp.asarray(self.r2_np)
        hi_limbs = self._join([carry] + [jnp.zeros_like(carry)] * (n - 1))
        corr = self.mont_mul(hi_limbs, jnp.broadcast_to(r2, hi_limbs.shape))
        # limbs < 2^(32n) < 2p but may exceed p: add(x, 0) canonicalizes.
        limbs = self.add(limbs, jnp.zeros_like(limbs))
        return self.add(limbs, corr)

    @_eager_jit(static_argnums=(0, 2))
    def mutual_products_mont(self, a, axis: int):
        """For each k along the axis: prod_{j != k} a_j (Montgomery domain).

        Exclusive prefix x exclusive suffix products — the inversion-free
        core of barycentric Lagrange on roots of unity, where
        (t^P - 1)/(t - w^k) = prod_{j != k} (t - w^j) exactly.
        """
        axis = axis % (a.ndim - 1)
        L = a.shape[axis]
        prefix = self.cumprod_mont(a, axis)
        ones = jnp.broadcast_to(
            self.mont_one(), lax.slice_in_dim(a, 0, 1, axis=axis).shape
        )
        prefix_excl = jnp.concatenate(
            [ones, lax.slice_in_dim(prefix, 0, L - 1, axis=axis)], axis=axis
        )
        rev = jnp.flip(a, axis=axis)
        suffix_incl_rev = self.cumprod_mont(rev, axis)
        suffix_excl = jnp.concatenate(
            [
                jnp.flip(
                    lax.slice_in_dim(suffix_incl_rev, 0, L - 1, axis=axis), axis=axis
                ),
                ones,
            ],
            axis=axis,
        )
        return self.mont_mul(prefix_excl, suffix_excl)

    @_eager_jit(static_argnums=(0, 2))
    def cumprod_mont(self, a, axis: int):
        """Inclusive cumulative product (Montgomery domain) along an axis."""
        axis = axis % (a.ndim - 1)
        return _scan_fence(lax.associative_scan(self.mont_mul, a, axis=axis))

    def geom_mont(self, first, ratio, count: int):
        """first * ratio^i for i < count as (..., count, n); Montgomery in,
        Montgomery out.  The chain is sequential by construction, so it
        runs as a scan: the graph holds ONE multiply however long the
        chain is.  Unrolled, these chains were most of the histogram
        prepare graph (36 multiplies for a 316-wide chunk) and most of its
        compile time."""

        def step(acc, _):
            return self.mont_mul(acc, ratio), acc

        _, out = lax.scan(step, first, None, length=count)
        return jnp.moveaxis(_scan_fence(out), 0, -2)

    @_eager_jit(static_argnums=(0, 2))
    def pow_range_mont(self, x, count: int):
        """x^1..x^count as (..., count, n), x Montgomery -> Montgomery.

        Baby-step/giant-step: two short sequential chains (~2*sqrt(count)
        tiny multiplies) plus ONE wide multiply — where cumprod_mont's
        associative scan costs log2(count) full-width passes over the
        (batch, count, n) tensor.  Exact Montgomery identities
        (mont_mul(aR, bR) = abR), so the limbs are byte-identical to the
        cumulative-product form (tests/test_ops_field.py)."""
        bs = max(1, math.isqrt(count))
        gs = -(-count // bs)
        baby_t = self.geom_mont(x, x, bs)  # x^(i+1) * R, i < bs
        one = jnp.broadcast_to(self.mont_one(), x.shape)
        giant_t = self.geom_mont(one, baby_t[..., -1, :], gs)  # x^(bs*g) * R
        out = self.mont_mul(giant_t[..., :, None, :], baby_t[..., None, :, :])
        return out.reshape(x.shape[:-1] + (gs * bs, self.n))[..., :count, :]

    def _bsgs_powers(self, x, count: int):
        """(baby (..., bs, n) = x^i, giant (..., gs, n) = x^(bs*g)), both
        Montgomery, with bs*gs >= count — the two power tables of a
        baby-step/giant-step polynomial evaluation."""
        bs = max(1, math.isqrt(count))
        gs = -(-count // bs)
        one = jnp.broadcast_to(self.mont_one(), x.shape)
        baby_t = self.geom_mont(one, x, bs)
        xbs = self.mont_mul(baby_t[..., -1, :], x)  # x^bs * R
        return baby_t, self.geom_mont(one, xbs, gs)

    @_eager_jit(static_argnums=(0,))
    def poly_eval_mont(self, coeffs, x):
        """Polynomial evaluation via baby-step/giant-step powers.

        coeffs (..., C, n) canonical low-order-first, x (..., n) Montgomery
        -> (..., n) canonical.  Horner's C sequential tiny multiplies become
        ~2*sqrt(C) sequential ones plus C wide parallel ones — the serial
        depth is what dominates wide gadget polynomials (C = 1023 for the
        100k-element SumVec).  Exact integer math: limb-identical to
        horner_mont (tests/test_ops_field.py
        test_poly_eval_bsgs_matches_horner_wide, slow tier).
        """
        C = coeffs.shape[-2]
        bs = max(1, math.isqrt(C))
        gs = -(-C // bs)
        pad = bs * gs - C
        if pad:
            coeffs = jnp.concatenate(
                [coeffs, self.zeros(coeffs.shape[:-2] + (pad,))], axis=-2
            )
        baby_t, giant_t = self._bsgs_powers(x, C)  # (..., bs, n), (..., gs, n)
        cg = coeffs.reshape(coeffs.shape[:-2] + (gs, bs, self.n))
        # c_j * x^(j%bs): canonical; sum over the baby axis, then * giant.
        t = self.mont_mul(cg, baby_t[..., None, :, :])
        inner = self.sum(t, axis=t.ndim - 2)  # (..., gs, n)
        outer = self.mont_mul(inner, giant_t)
        return self.sum(outer, axis=outer.ndim - 2)

    @_eager_jit(static_argnums=(0,))
    def horner_mont(self, coeffs, x):
        """Evaluate poly with coeff tensor (..., n_coeffs, n_limbs) at x (..., n_limbs).

        Low-order-first coefficients (matching the oracle); Montgomery domain.
        """
        rev = jnp.flip(coeffs, axis=-2)
        # scan over coefficient axis
        cs = jnp.moveaxis(rev, -2, 0)

        def body(acc, c):
            return self.add(self.mont_mul(acc, x), c), None

        acc0 = jnp.zeros_like(x)
        acc, _ = lax.scan(body, acc0, cs)
        return _scan_fence(acc)

    def ntt_eval_mont(self, coeffs, bitrev_idx, tw_stages):
        """Evaluate a polynomial at ALL P-th roots of unity (iterative NTT).

        coeffs (..., P, n) canonical -> values (..., P, n) canonical, value
        j = poly(w^j) in natural order.  ``bitrev_idx`` (P,) host-precomputed
        bit-reversal permutation; ``tw_stages`` list of per-stage twiddle
        tables (m/2, n) in Montgomery form (w^(P/m)^j).  Cooley-Tukey DIT:
        log2(P) stages of m/2 butterflies; each butterfly is one
        mont_mul(odd_canonical, twiddle_montgomery) -> canonical plus an
        add/sub, so the whole tensor stays canonical.  Exact integer math —
        identical limbs to per-point Horner evaluation, at O(P log P) cost
        instead of O(P * deg) (the wide-vector FLP evaluates a ~2P-coeff
        gadget polynomial at ~P points; reference circuit params
        core/src/vdaf.rs:220-236).
        """
        P = coeffs.shape[-2]
        x = jnp.take(coeffs, jnp.asarray(bitrev_idx), axis=-2)
        m = 2
        for tw in tw_stages:
            xr = x.reshape(x.shape[:-2] + (P // m, m, self.n))
            even = xr[..., : m // 2, :]
            odd = xr[..., m // 2 :, :]
            t = self.mont_mul(odd, jnp.broadcast_to(tw, odd.shape))
            xr = jnp.concatenate([self.add(even, t), self.sub(even, t)], axis=-2)
            x = xr.reshape(x.shape)
            m *= 2
        return x

    def ntt_eval_mont_limbs(self, coeffs: List, bitrev_idx, tw_stages) -> List:
        """Planar twin of ntt_eval_mont on limb lists.

        coeffs: n arrays (R, P, 128) canonical -> values, same shapes.  The
        butterfly schedule is identical op-for-op (one mont_mul + add/sub
        per butterfly, same order), so outputs are byte-identical to the
        row form — the lanes just hold reports instead of T(1,128) rows.
        """
        P = coeffs[0].shape[1]
        idx = jnp.asarray(bitrev_idx)
        x = [jnp.take(c, idx, axis=1) for c in coeffs]
        R = x[0].shape[0]
        m = 2
        for tw in tw_stages:  # (m/2, n) Montgomery twiddles
            xr = [c.reshape(R, P // m, m, 128) for c in x]
            even = [c[:, :, : m // 2] for c in xr]
            odd = [c[:, :, m // 2 :] for c in xr]
            twl = [
                jnp.broadcast_to(tw[:, l][None, None, :, None], odd[0].shape)
                for l in range(self.n)
            ]
            t = self.mont_mul_limbs(odd, twl)
            hi = self.add_limbs(even, t)
            lo = self.sub_limbs(even, t)
            x = [
                jnp.concatenate([h, l_], axis=2).reshape(R, P, 128)
                for h, l_ in zip(hi, lo)
            ]
            m *= 2
        return x

    def _batch_inv_nonzero(self, a, axis: int):
        """Montgomery-trick core: inv(a_k) = inv(prod_j a_j) * prod_{j != k}
        a_j — one Fermat inversion of the single total plus the exclusive
        mutual products.  All entries along the axis must be nonzero."""
        total = jnp.squeeze(
            lax.slice_in_dim(
                self.cumprod_mont(a, axis), a.shape[axis] - 1, a.shape[axis], axis=axis
            ),
            axis=axis,
        )
        inv_total = self._fermat_inv_mont(total)
        others = self.mutual_products_mont(a, axis)
        inv_b = jnp.expand_dims(inv_total, axis=axis)
        return _scan_fence(self.mont_mul(others, jnp.broadcast_to(inv_b, a.shape)))

    @_eager_jit(static_argnums=(0, 2))
    def batch_inv_mont(self, a, axis: int):
        """Montgomery-trick batched inversion along an axis (all nonzero)."""
        return self._batch_inv_nonzero(a, axis % (a.ndim - 1))

    # -- MXU contraction layer (limb-plane dot_general) -----------------
    def _digits8(self, x):
        """(..., n) u32 limbs -> (..., 4n) u32 base-2^8 digit planes.

        Little-endian, limb-major: digit d of an element has weight
        2^(8d).  Digits are held in u32 (not u8) so the contraction's
        dot_general accumulates in u32 — on TPU, XLA decomposes the
        integer matmul into MXU-native narrow passes; on CPU it stays one
        exact integer ``dot``.
        """
        parts = jnp.stack([(x >> (8 * i)) & _MASK8 for i in range(4)], axis=-1)
        return parts.reshape(x.shape[:-1] + (4 * self.n,))

    @_eager_jit(static_argnums=(0,))
    def mat_mul_mont(self, a, b):
        """Modular matmul with ONE Montgomery reduction per output element.

        a (*B, K, M, n) x b (*B, K, N, n) -> (*B, M, N, n) with
        out[m, v] = sum_k a[k, m] * b[k, v] * R^-1 mod p — exactly
        sum_k mont_mul(a_k, b_k), so it composes with the prepare
        pipeline's domain convention (one canonical operand times one
        Montgomery operand yields a canonical result) the same way a
        mont_mul/sum chain does.  ``b`` may omit the batch dims
        ((K, N, n)): a host-constant matrix (e.g. the gadget Vandermonde
        table) shared by every batch element.

        The contraction runs on base-2^8 digit planes as a single batched
        ``lax.dot_general`` with u32 accumulation (the MXU path named by
        the multi-precision-systolic-NTT recipe in PAPERS.md): all 4n x 4n
        cross-digit partial products for a whole output tile come out of
        one integer matmul, and carry propagation + modular reduction are
        DEFERRED to a single pass per output tile (``_lazy_reduce_digits``).
        Contractions longer than DOT_MAX_K split into exact modular-added
        chunks.  Every step is exact integer arithmetic, so outputs are
        limb-identical to the mont_mul/sum form (tests/test_mxu_field.py
        fuzzes random and adversarial operands against the oracle field).
        """
        K = a.shape[-3]
        if K <= DOT_MAX_K:
            return self._mat_mul_dot(a, b)
        out = None
        for s in range(0, K, DOT_MAX_K):
            part = self._mat_mul_dot(
                a[..., s : s + DOT_MAX_K, :, :], b[..., s : s + DOT_MAX_K, :, :]
            )
            out = part if out is None else self.add(out, part)
        return out

    def _mat_mul_dot(self, a, b):
        """Single-chunk core of mat_mul_mont (K <= DOT_MAX_K)."""
        n = self.n
        D = 4 * n
        K, M = a.shape[-3], a.shape[-2]
        N = b.shape[-2]
        batch = a.shape[:-3]
        nb = len(batch)
        shared_rhs = b.ndim == 3 and nb > 0
        lhs = jnp.moveaxis(self._digits8(a), -3, -1).reshape(batch + (M * D, K))
        if shared_rhs:
            rhs = self._digits8(b).reshape(K, N * D)
            dn = (((nb + 1,), (0,)), ((), ()))
        else:
            rhs = self._digits8(b).reshape(batch + (K, N * D))
            dn = (((nb + 1,), (nb,)), (tuple(range(nb)), tuple(range(nb))))
        prod = lax.dot_general(lhs, rhs, dn, preferred_element_type=_U32)
        return self._lazy_reduce_digits(
            prod.reshape(batch + (M, D, N, D)), batch + (M, N)
        )

    def _lazy_reduce_digits(self, P, out_shape):
        """(..., M, D, N, D) digit-pair partial sums -> canonical (..., M, N, n).

        The deferred half of the MXU contraction — one pass per output
        tile.  Lazy-carry bounds (all exact in u32):

        * each partial sum P[d, e] <= K * 255^2 < 2^32 for K <= DOT_MAX_K;
        * P splits into u16 halves before the diagonal fold, so a base-2^8
          digit column S[g] accumulates at most 2D addends each < 2^16 —
          S[g] < 2^21 regardless of K (the same trick as JField._sum_lazy);
        * the sequential carry pass keeps carry < 2^14.

        The normalized integer U < K * 2^(64n) <= 2^(64n+16) packs into
        2n+1 u32 limbs U = U0 + R*U1 + R^2*U2 (R = 2^(32n)), and
        U*R^-1 mod p folds with the existing primitives:
        from_mont(U0) + canonicalize(U1) + mont_mul(U2, R^2).  Each piece
        is the unique canonical residue of the same value mod p, so the
        result is limb-identical to the multiply/add tree it replaces.
        """
        n = self.n
        D = 4 * n
        lo = P & _MASK16
        hi = P >> 16
        zero = jnp.zeros(out_shape, dtype=_U32)
        # S[g]: base-2^8 digit column g — lo[d,e] lands at d+e, hi at d+e+2.
        S = [zero] * (2 * D + 1)
        for d in range(D):
            for e in range(D):
                f = d + e
                S[f] = S[f] + lo[..., d, :, e]
                S[f + 2] = S[f + 2] + hi[..., d, :, e]
        L = 2 * n + 1
        digits = []
        carry = zero
        for g in range(4 * L):
            t = (S[g] if g < len(S) else zero) + carry
            digits.append(t & _MASK8)
            carry = t >> 8
        # carry == 0 here: U < 2^(64n+16) and 4L digits span 2^(64n+32).
        U = jnp.stack(
            [
                digits[4 * j]
                | (digits[4 * j + 1] << 8)
                | (digits[4 * j + 2] << 16)
                | (digits[4 * j + 3] << 24)
                for j in range(L)
            ],
            axis=-1,
        )  # (..., M, N, L)
        U0 = U[..., :n]
        U1 = U[..., n : 2 * n]
        U2 = jnp.concatenate(
            [U[..., 2 * n :], jnp.zeros(U.shape[:-1] + (n - 1,), dtype=_U32)],
            axis=-1,
        )
        r2 = jnp.asarray(self.r2_np)
        res = self.add(self.from_mont(U0), self.add(U1, jnp.zeros_like(U1)))
        return self.add(res, self.mont_mul(U2, jnp.broadcast_to(r2, U2.shape)))

    @_eager_jit(static_argnums=(0,))
    def dot_mont(self, a, b):
        """Contraction form of mat_mul_mont: sum_k mont_mul(a_k, b_k).

        a (*B, K, M, n) x b (*B, K, n) -> (*B, M, n): the wire-evaluation
        shape (per-report Lagrange coefficients contracted against a
        per-report wire tensor).  One batched dot_general under the hood.
        """
        return jnp.squeeze(self.mat_mul_mont(a, b[..., :, None, :]), axis=-2)

    @_eager_jit(static_argnums=(0,))
    def poly_eval_dot(self, coeffs, x):
        """MXU twin of poly_eval_mont: baby-step/giant-step powers with
        BOTH contractions (per-giant coefficient fold, giant fold) run as
        mat_mul_mont dot_generals instead of mont_mul/sum trees.

        coeffs (..., C, n) canonical low-order-first, x (..., n) Montgomery
        -> (..., n) canonical.  Same residues stage for stage as
        poly_eval_mont (exact integer math), so limbs are identical.
        """
        C = coeffs.shape[-2]
        bs = max(1, math.isqrt(C))
        gs = -(-C // bs)
        pad = bs * gs - C
        if pad:
            coeffs = jnp.concatenate(
                [coeffs, self.zeros(coeffs.shape[:-2] + (pad,))], axis=-2
            )
        baby_t, giant_t = self._bsgs_powers(x, C)  # (..., bs, n), (..., gs, n)
        cg = coeffs.reshape(coeffs.shape[:-2] + (gs, bs, self.n))
        inner = self.dot_mont(jnp.swapaxes(cg, -3, -2), baby_t)  # (..., gs, n)
        return jnp.squeeze(
            self.dot_mont(inner[..., :, None, :], giant_t), axis=-2
        )
