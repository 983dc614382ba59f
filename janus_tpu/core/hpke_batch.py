"""Batched HPKE open: the upload front door's crypto as ONE wide kernel.

``Aggregator.handle_upload`` used to HPKE-open every report inline, one
at a time, on the handler's event loop.  The AEAD body of an HPKE open
is exactly the batch-crypto shape this repo accelerates — per-report
keys, a handful of blocks each, huge N — so this module re-expresses a
BATCH of concurrent uploads' opens as:

1. per-report KEM decap + HKDF key schedule (X25519 / P-256 DH — serial
   math, host territory, run off the event loop by the caller's thread
   pool), then
2. ONE vectorized AES-GCM pass over every AES-128-GCM body in the batch:
   the AES-CTR keystream (plus each report's GHASH key H = E(K, 0) and
   tag mask E(K, J0)) via the existing multikey AES kernel
   (``ops/aes_jax.encrypt_blocks_multikey_padded`` — per-report round
   keys, both axes pow2-padded), and GHASH as a vectorized carryless
   GF(2^128) multiply over u64 half-words (numpy), LEFT-zero-padding
   each report's block sequence so one unmasked Horner loop serves
   ragged lengths (leading zero blocks are GHASH no-ops).

Suites the wide kernel does not cover (AES-256-GCM, ChaCha20-Poly1305)
open per-report through core/hpke.py inside the same batch call, so the
caller's contract is uniform.  Robustness contract: a malformed
ciphertext rejects ONLY its own report (per-item error slots).  Which of
the two AES-128-GCM paths serves a process is decided ONCE
(``vector_pass_preferred``) and logged; a failure of the elected path
RAISES out of ``open_batch`` — the callers' bisection (which isolates
poison rows, loudly, into the quarantine ledger) is the only net, and
nothing here quietly drops to another implementation.
Bit-exactness is anchored by running the vendored RFC 9180 vectors and a
batched-vs-inline fuzz (tests/test_upload_frontdoor.py) through this path.
"""

from __future__ import annotations

import logging
import struct
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..messages import HpkeAeadId
from .hpke import (
    _AEAD_PARAMS,
    _KEMS,
    HpkeApplicationInfo,
    HpkeError,
    HpkeKeypair,
    _key_schedule,
    is_hpke_config_supported,
)

__all__ = ["OpenRequest", "open_batch", "aesgcm_open_batch", "vector_pass_preferred"]

logger = logging.getLogger("janus_tpu.hpke_batch")

#: An open request: (recipient keypair, application info, ciphertext, aad).
OpenRequest = Tuple[HpkeKeypair, HpkeApplicationInfo, object, bytes]

#: Below this many AES-128-GCM bodies the vectorized pass is dispatch
#: overhead, not a win — open per-report instead.
MIN_VECTOR_BATCH = 2

#: memoized backend probe for vector_pass_preferred (None = unprobed)
_VECTOR_PREFERRED: Optional[bool] = None


def vector_pass_preferred() -> bool:
    """Should AES-128-GCM bodies take the wide table-AES kernel?

    Only on a host with NO functional `cryptography`, where nothing
    constant-time exists to prefer.  With a working `cryptography`,
    per-report AES-NI is constant-time and is the path — on a CPU host
    and on a TPU host alike.  The kernel was once also elected whenever
    the JAX backend was an accelerator; that did not survive the chip
    (PERF.md, PR 21).  On a v5e one open batch of 64 Histogram(1024)
    leader shares (64 x 2,048 AES blocks) took 51 s on its first call —
    the u8 S-box gathers compile to tens of MB of code, once per pow2
    (rows, blocks) shape, against a 2 s shed deadline — and 0.47 s on
    every later one, holding the chip that prepare needs; `cryptography`
    opened all 2,304 uploads of the same run in 0.66 s.  The choice is
    made once per process and logged.
    ``JANUS_TPU_UPLOAD_VECTOR_GCM=1|0`` overrides (tests force both
    paths)."""
    global _VECTOR_PREFERRED
    import os

    force = os.environ.get("JANUS_TPU_UPLOAD_VECTOR_GCM", "")
    if force in ("0", "1"):
        return force == "1"
    if _VECTOR_PREFERRED is None:
        from ..utils.gcm import HAVE_FUNCTIONAL_CRYPTOGRAPHY

        _VECTOR_PREFERRED = not HAVE_FUNCTIONAL_CRYPTOGRAPHY
        logger.info(
            "AES-128-GCM open path: %s",
            "vectorized table-AES kernel (no functional `cryptography`)"
            if _VECTOR_PREFERRED
            else "per-report AES-GCM (`cryptography`)",
        )
    return _VECTOR_PREFERRED


_R_HI = np.uint64(0xE100000000000000)  # GCM reduction poly, high u64


# -- vectorized GHASH ---------------------------------------------------------


def _ghash_batch(h_blocks: np.ndarray, datas: Sequence[bytes]) -> np.ndarray:
    """GHASH_H(data) per report, vectorized across the batch.

    ``h_blocks`` is (B, 16) u8 (each report's H = E(K, 0)); each
    ``datas[i]`` must already be a block multiple (the caller appends the
    GCM length block).  Ragged lengths are LEFT-padded with zero blocks
    to the common maximum — a leading zero block leaves the Horner
    accumulator at 0, so padding changes nothing.  Returns (B, 16) u8.

    Field elements ride as (hi, lo) u64 pairs in string order (bit 0 of
    the GCM spec = the integer's MSB); multiply-by-H is the SP 800-38D
    right-shift construction: per report, precompute V_t = H * x^t for
    t in [0, 128), then each Horner step XOR-selects the V_t rows whose
    corresponding bit of (Y ^ X_j) is set."""
    b = len(h_blocks)
    # H as u64 halves
    h = h_blocks.reshape(b, 2, 8).astype(np.uint64)
    weights = (np.uint64(256) ** np.arange(7, -1, -1, dtype=np.uint64)).reshape(1, 1, 8)
    h64 = (h * weights).sum(axis=2, dtype=np.uint64)  # (B, 2): hi, lo
    # Vpow[:, t] = H * x^t (128 sequential shift-reduce steps, vectorized
    # over the batch)
    vhi = np.empty((b, 128), dtype=np.uint64)
    vlo = np.empty((b, 128), dtype=np.uint64)
    chi, clo = h64[:, 0].copy(), h64[:, 1].copy()
    one = np.uint64(1)
    s63 = np.uint64(63)
    for t in range(128):
        vhi[:, t] = chi
        vlo[:, t] = clo
        lsb = clo & one
        clo = (clo >> one) | ((chi & one) << s63)
        chi = (chi >> one) ^ (lsb * _R_HI)
    # left-pad block streams to the common length
    nblocks = [len(d) // 16 for d in datas]
    m = max(nblocks) if nblocks else 0
    padded = np.zeros((b, m * 16), dtype=np.uint8)
    for i, d in enumerate(datas):
        if d:
            padded[i, (m - nblocks[i]) * 16 :] = np.frombuffer(d, dtype=np.uint8)
    blocks = padded.reshape(b, m, 2, 8).astype(np.uint64)
    blocks64 = (blocks * weights.reshape(1, 1, 1, 8)).sum(axis=3, dtype=np.uint64)
    # Horner: Y <- (Y ^ X_j) * H per block position
    yhi = np.zeros(b, dtype=np.uint64)
    ylo = np.zeros(b, dtype=np.uint64)
    shifts = np.arange(63, -1, -1, dtype=np.uint64)
    for j in range(m):
        xhi = yhi ^ blocks64[:, j, 0]
        xlo = ylo ^ blocks64[:, j, 1]
        # bit t of the STRING order = integer bit (127 - t): hi's MSB first
        bits_hi = ((xhi[:, None] >> shifts) & one).astype(bool)  # t = 0..63
        bits_lo = ((xlo[:, None] >> shifts) & one).astype(bool)  # t = 64..127
        bits = np.concatenate([bits_hi, bits_lo], axis=1)  # (B, 128)
        yhi = np.bitwise_xor.reduce(np.where(bits, vhi, np.uint64(0)), axis=1)
        ylo = np.bitwise_xor.reduce(np.where(bits, vlo, np.uint64(0)), axis=1)
    out = np.empty((b, 16), dtype=np.uint8)
    for k in range(8):
        sh = np.uint64(8 * (7 - k))
        out[:, k] = (yhi >> sh).astype(np.uint8)
        out[:, 8 + k] = (ylo >> sh).astype(np.uint8)
    return out


# -- vectorized AES-128-GCM open ---------------------------------------------


def aesgcm_open_batch(
    keys: Sequence[bytes],
    nonces: Sequence[bytes],
    ciphertexts: Sequence[bytes],
    aads: Sequence[bytes],
) -> List[Optional[bytes]]:
    """Open B AES-128-GCM one-shot messages as one vectorized pass.

    Returns a plaintext per slot, or None where authentication failed
    (tag mismatch / truncated input) — per-report isolation is the
    contract.  All nonces must be 12 bytes (the only length RFC 9180
    produces)."""
    from ..ops.aes_jax import encrypt_blocks_multikey_padded
    from ..utils.softaes import _expand_key

    b = len(keys)
    cts, tags, ok = [], [], []
    for ct in ciphertexts:
        if len(ct) < 16:
            cts.append(b"")
            tags.append(b"")
            ok.append(False)
        else:
            cts.append(ct[:-16])
            tags.append(ct[-16:])
            ok.append(True)
    nblocks = [(len(c) + 15) // 16 for c in cts]
    kmax = 2 + max(nblocks, default=0)
    round_keys = np.stack([_expand_key(bytes(k)) for k in keys])
    blocks = np.zeros((b, kmax, 16), dtype=np.uint8)
    for i in range(b):
        j0 = nonces[i] + b"\x00\x00\x00\x01"
        blocks[i, 1] = np.frombuffer(j0, dtype=np.uint8)
        for c in range(nblocks[i]):
            ctr = nonces[i] + struct.pack(">I", 2 + c)
            blocks[i, 2 + c] = np.frombuffer(ctr, dtype=np.uint8)
    out = np.asarray(encrypt_blocks_multikey_padded(round_keys, blocks))
    h = np.ascontiguousarray(out[:, 0])  # E(K, 0): the GHASH key
    tag_mask = out[:, 1]  # E(K, J0)
    ghash_in = [
        aad
        + b"\x00" * (-len(aad) % 16)
        + ct
        + b"\x00" * (-len(ct) % 16)
        + struct.pack(">QQ", 8 * len(aad), 8 * len(ct))
        for aad, ct in zip(aads, cts)
    ]
    s = _ghash_batch(h, ghash_in)
    tags_got = s ^ tag_mask
    results: List[Optional[bytes]] = []
    for i in range(b):
        if not ok[i] or tags_got[i].tobytes() != tags[i]:
            results.append(None)
            continue
        stream = out[i, 2 : 2 + nblocks[i]].tobytes()
        ct = cts[i]
        pt = np.frombuffer(ct, dtype=np.uint8) ^ np.frombuffer(
            stream[: len(ct)], dtype=np.uint8
        )
        results.append(pt.tobytes())
    return results


# -- the batch face -----------------------------------------------------------


def _open_one(keypair, info, ciphertext, aad):
    """Per-report inline open, errors as values."""
    from .hpke import open_

    try:
        return open_(keypair, info, ciphertext, aad)
    except HpkeError as e:
        return e
    except Exception as e:  # pragma: no cover - defensive
        return HpkeError(f"HPKE open failed: {type(e).__name__}")


def open_batch(requests: Sequence[OpenRequest]) -> List[object]:
    """Open a batch of HPKE ciphertexts; one result slot per request —
    plaintext bytes on success, an :class:`HpkeError` value on failure
    (never raised: a malformed row must reject only itself).

    Per-report KEM decap + key schedule run here (the caller is expected
    to be on a worker thread); all AES-128-GCM bodies then open on the
    elected path (``vector_pass_preferred``) — ONE vectorized pass, or
    per-report AEAD with the already-derived keys — other suites
    per-report.  A failure of the vectorized pass itself raises."""
    results: List[object] = [None] * len(requests)
    gcm_idx: List[int] = []
    gcm_keys: List[bytes] = []
    gcm_nonces: List[bytes] = []
    gcm_cts: List[bytes] = []
    gcm_aads: List[bytes] = []
    for i, (keypair, info, ciphertext, aad) in enumerate(requests):
        config = keypair.config
        if not is_hpke_config_supported(config):
            results[i] = HpkeError("unsupported HPKE configuration")
            continue
        kem = _KEMS[config.kem_id]
        try:
            shared_secret = kem.decap(
                ciphertext.encapsulated_key,
                keypair.private_key,
                pk_r=config.public_key.raw,
            )
            key, base_nonce = _key_schedule(
                config.kem_id, config.kdf_id, config.aead_id, shared_secret, info.raw
            )
        except Exception as e:
            results[i] = HpkeError(f"HPKE open failed: {type(e).__name__}")
            continue
        if config.aead_id == HpkeAeadId.AES_128_GCM:
            gcm_idx.append(i)
            gcm_keys.append(key)
            gcm_nonces.append(base_nonce)
            gcm_cts.append(ciphertext.payload)
            gcm_aads.append(aad)
        else:
            _nk, _nn, aead_factory = _AEAD_PARAMS[config.aead_id]
            try:
                results[i] = aead_factory(key).decrypt(
                    base_nonce, ciphertext.payload, aad
                )
            except Exception as e:
                results[i] = HpkeError(f"HPKE open failed: {type(e).__name__}")
    if gcm_idx:
        if len(gcm_idx) < MIN_VECTOR_BATCH or not vector_pass_preferred():
            # per-report AEAD with the ALREADY-derived keys (the KEM work
            # above is never repeated): the path for tiny batches and for
            # CPU hosts where `cryptography`'s constant-time AES-NI beats
            # — and must be preferred over — the table kernels
            _nk, _nn, aead_factory = _AEAD_PARAMS[HpkeAeadId.AES_128_GCM]
            for i, key, nonce, ct, aad in zip(
                gcm_idx, gcm_keys, gcm_nonces, gcm_cts, gcm_aads
            ):
                try:
                    results[i] = aead_factory(key).decrypt(nonce, ct, aad)
                except Exception as e:
                    results[i] = HpkeError(f"HPKE open failed: {type(e).__name__}")
        else:
            opened = aesgcm_open_batch(gcm_keys, gcm_nonces, gcm_cts, gcm_aads)
            for i, pt in zip(gcm_idx, opened):
                results[i] = (
                    pt if pt is not None else HpkeError("HPKE open failed: InvalidTag")
                )
    return results
