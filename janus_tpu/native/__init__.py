"""Native host kernel loader (ctypes).

Builds and loads ``native/turboshake.cpp`` — the C++ TurboSHAKE128 sponge
and VDAF XOF field expansion the CPU oracle uses for its hot loops.  The
build is one ``g++ -O3 -shared`` invocation, cached next to the source
(the ``.so`` is git-ignored: a fresh checkout builds it on first use); if
the toolchain or the build is unavailable, callers fall back to the pure
Python sponge (bit-exact either way, asserted in tests/test_native.py) and
a warning says so.

Disable explicitly with JANUS_TPU_NATIVE=0.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from typing import List, Optional

logger = logging.getLogger("janus_tpu.native")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "turboshake.cpp")
_LIB = os.path.join(_REPO_ROOT, "native", "libjanusts.so")

_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    # build to a temp path and rename: concurrent cold processes must never
    # CDLL a partially written library
    tmp = _LIB + f".tmp.{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, _LIB)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        # Loud: without the library every XOF expansion of the oracle and
        # of clients runs the pure-Python sponge, ~100x slower, and
        # nothing else would say why.
        detail = getattr(e, "stderr", b"") or b""
        logger.warning(
            "native build of %s failed (%s); the pure-Python sponge serves: %s",
            _SRC,
            e,
            detail.decode(errors="replace")[-2000:],
        )
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def load() -> Optional[ctypes.CDLL]:
    """The loaded library, building it on first use; None if unavailable."""
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("JANUS_TPU_NATIVE", "1") == "0":
        return None
    if not os.path.exists(_SRC):
        return None
    if not os.path.exists(_LIB) or os.path.getmtime(_LIB) < os.path.getmtime(_SRC):
        if not _build():
            return None
    try:
        lib = ctypes.CDLL(_LIB)
    except OSError as e:
        logger.warning("native library %s did not load: %s", _LIB, e)
        return None
    lib.ts128_hash.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint8,
        ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.ts128_expand_vdaf.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.ts128_next_vec.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t,
    ]
    lib.ts128_next_vec.restype = ctypes.c_int
    _lib = lib
    return _lib


def _validate(seed: Optional[bytes], dst: bytes) -> None:
    """Mirror the Python XOF's input contract — the C ABI reads exactly 16
    seed bytes and truncates the dst length prefix to one byte."""
    if seed is not None and len(seed) != 16:
        raise ValueError("bad seed size")
    if len(dst) > 255:
        raise ValueError("dst too long")


def turboshake128(message: bytes, domain: int, length: int) -> Optional[bytes]:
    lib = load()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(length)
    lib.ts128_hash(message, len(message), domain, out, length)
    return out.raw


def xof_stream(seed: bytes, dst: bytes, binder: bytes, length: int) -> Optional[bytes]:
    """Full XofTurboShake128 stream of ``length`` bytes."""
    lib = load()
    if lib is None:
        return None
    _validate(seed, dst)
    out = ctypes.create_string_buffer(length)
    lib.ts128_expand_vdaf(seed, dst, len(dst), binder, len(binder), out, length)
    return out.raw


def next_vec(
    seed: bytes, dst: bytes, binder: bytes, field_encoded_size: int, length: int
) -> Optional[List[int]]:
    """Rejection-sampled field elements (Field64 or Field128)."""
    lib = load()
    if lib is None or field_encoded_size not in (8, 16):
        return None
    _validate(seed, dst)
    out = (ctypes.c_uint64 * (2 * length))()
    rc = lib.ts128_next_vec(
        seed, dst, len(dst), binder, len(binder),
        0 if field_encoded_size == 8 else 1, out, length,
    )
    if rc != 0:
        return None
    return [out[2 * i] | (out[2 * i + 1] << 64) for i in range(length)]
