"""A store of compiled device programs: a restarted replica loads its
executables instead of tracing them again.

XLA's persistent cache (utils/jax_setup.py) spares a restarted process the
*compile*; it still pays Python tracing the circuit, lowering it, keying
the module and loading the executable — 56 s a Histogram prepare program
on a run that compiles nothing (PERF.md, PR 32).  This store sits in front
of that: ``TpuBackend``'s four program kinds (``prep_init``, ``combine``,
``aggregate``, ``accumulate``: the ``program`` label of
``janus_program_store_total``) ask it before they trace.

* in memory, one dict a store (``active_store`` is process-wide): a second
  backend of a shape this process already holds gets the loaded executable;
* on disk, ``<compile cache dir>/programs/<source digest>/<key>.bin``:
  ``jax.experimental.serialize_executable`` of the compiled object, read
  back with ``deserialize_and_load``;
* else ``build()`` — ``jit(fn).lower(*args).compile()``, which still goes
  through XLA's cache — serialized and written for the next process.

The KEY names everything the traced function reads besides its arguments
(``program_key``), so a hit never has to trace to know it is right.  The
SOURCE DIGEST is one SHA-256 over every ``.py`` of the installed package:
any edit anywhere is a miss and takes the build path.  That is the whole
invalidation rule; a tree's first write removes the other digests'
directories.

Where it lives and whether it is on is observed, not configured: a
subdirectory of what ``enable_compile_cache`` returned, off where that
returned None (XLA:CPU, or nobody called it).  The files are PICKLES: the
directory has to be as trusted as the code's own — written 0600 under a
0700 directory, never a shared world-writable volume.
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import shutil
import tempfile
import threading
from typing import Callable, Dict, Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

_PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_digests: Dict[str, str] = {}
_active: Optional["ProgramStore"] = None
_active_lock = threading.Lock()


def source_digest(root: str = _PACKAGE_ROOT) -> str:
    """SHA-256 over the sorted relative paths and contents of every ``.py``
    under ``root`` (the installed package: milliseconds), once a process."""
    digest = _digests.get(root)
    if digest is None:
        h = hashlib.sha256()
        for directory, dirs, files in os.walk(root):
            dirs.sort()
            for name in sorted(f for f in files if f.endswith(".py")):
                path = os.path.join(directory, name)
                h.update(os.path.relpath(path, root).encode() + b"\0")
                with open(path, "rb") as f:
                    h.update(f.read() + b"\0")
        digest = _digests[root] = h.hexdigest()
    return digest


def runtime_facts() -> tuple:
    """What of the installation a compiled executable depends on: the JAX
    and jaxlib versions, the PjRt client's ``platform_version`` (the libtpu
    build) and the device kind."""
    import jax
    import jaxlib

    device = jax.devices()[0]
    return (
        jax.__version__,
        jaxlib.__version__,
        device.client.platform_version,
        device.device_kind,
    )


def signature(args) -> tuple:
    """The argument pytree's structure with every leaf's shape and dtype."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten(args)
    return tree, tuple(
        (np.shape(x), str(x.dtype if hasattr(x, "dtype") else np.asarray(x).dtype))
        for x in leaves
    )


def program_key(kind: str, agg_id: Optional[int], backend, sig: tuple) -> str:
    """Everything ``backend``'s traced ``kind`` program reads besides its
    arguments, as one line of text: the file's name is its hash, and the
    file carries the text itself."""
    import jax

    from ..ops import pallas_mode
    from .backend import vdaf_shape_key

    tree, leaves = sig
    return repr(
        (
            kind,
            agg_id,
            vdaf_shape_key(backend.vdaf),
            bool(backend.canonical),
            backend.field_backend,
            pallas_mode(),
            bool(jax.config.jax_enable_x64),
            str(tree),
            leaves,
            runtime_facts(),
        )
    )


def _count(kind: str, outcome: str) -> None:
    from ..core.metrics import GLOBAL_METRICS

    if GLOBAL_METRICS.registry is not None:
        GLOBAL_METRICS.program_store.labels(program=kind, outcome=outcome).inc()


def _single_device(compiled) -> bool:
    import jax

    shardings = jax.tree_util.tree_leaves(
        (compiled.input_shardings, compiled.output_shardings)
    )
    return len({d for s in shardings for d in s.device_set}) == 1


class ProgramStore:
    """Compiled executables by key: memory, then disk, then ``build()``."""

    def __init__(self, directory: str, digest: Optional[str] = None):
        self.directory = os.path.join(directory, digest or source_digest())
        self._memory: Dict[str, object] = {}
        self._locks: Dict[str, threading.Lock] = {}
        self._lock = threading.Lock()

    def path(self, kind: str, key: str) -> str:
        name = f"{kind}-{hashlib.sha256(key.encode()).hexdigest()[:32]}.bin"
        return os.path.join(self.directory, name)

    def get(self, kind: str, key: str, build: Callable[[], object]) -> Tuple[object, str]:
        """``(executable, outcome)``, outcome ``memory`` | ``disk`` |
        ``built``.  One build a key, whoever asks meanwhile waits for it."""
        with self._lock:
            lock = self._locks.setdefault(key, threading.Lock())
        with lock:
            exe = self._memory.get(key)
            outcome = "memory"
            if exe is None:
                exe = self._load(kind, key)
                outcome = "disk"
            if exe is None:
                exe = build()
                outcome = "built"
                self._save(kind, key, exe)
            self._memory[key] = exe
        _count(kind, outcome)
        return exe, outcome

    def reject(self, kind: str, key: str) -> None:
        """Forget an entry that failed to load or to prove itself (counted
        ``rejected``): the next ``get`` builds and writes it anew."""
        with self._lock:
            self._memory.pop(key, None)
        try:
            os.unlink(self.path(kind, key))
        except OSError:
            pass
        _count(kind, "rejected")

    def _load(self, kind: str, key: str):
        import jax
        from jax.experimental.serialize_executable import deserialize_and_load

        path = self.path(kind, key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None
        try:
            entry = pickle.loads(blob)
            if entry["key"] != key:
                raise ValueError("the file holds another key")
            # single-device programs of the default device, as built
            return deserialize_and_load(
                entry["executable"],
                entry["in_tree"],
                entry["out_tree"],
                execution_devices=jax.local_devices()[:1],
            )
        except Exception as e:
            logger.warning("program store: %s does not load (%r); building", path, e)
            self.reject(kind, key)
            return None

    def _save(self, kind: str, key: str, compiled) -> None:
        """Write-to-temp + ``os.replace``, 0600.  A program that cannot be
        stored (several devices, an executable XLA will not serialize, a
        full disk) is served from memory and built again next time."""
        from jax.experimental.serialize_executable import serialize

        try:
            if not _single_device(compiled):
                return
            executable, in_tree, out_tree = serialize(compiled)
            blob = pickle.dumps(
                {"key": key, "executable": executable, "in_tree": in_tree, "out_tree": out_tree},
                protocol=pickle.HIGHEST_PROTOCOL,
            )
            if not os.path.isdir(self.directory):
                self._start_directory()
            fd, tmp = tempfile.mkstemp(dir=self.directory, suffix=".tmp")  # 0600
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, self.path(kind, key))
            except BaseException:
                os.unlink(tmp)
                raise
        except Exception as e:
            logger.warning("program store: %s program not stored (%r)", kind, e)

    def _start_directory(self) -> None:
        """A tree's first entry: make its directory and remove those of
        other digests, so the store does not grow with every checkout."""
        parent = os.path.dirname(self.directory)
        os.makedirs(self.directory, mode=0o700, exist_ok=True)
        for name in os.listdir(parent):
            other = os.path.join(parent, name)
            if other != self.directory and os.path.isdir(other):
                shutil.rmtree(other, ignore_errors=True)


def active_store() -> Optional[ProgramStore]:
    """The process's store, beside XLA's cache: ``<dir>/programs`` of the
    directory ``enable_compile_cache`` returned; None where it returned
    None or was never called."""
    from ..utils.jax_setup import compile_cache_dir

    global _active
    directory = compile_cache_dir()
    if directory is None:
        return None
    directory = os.path.join(directory, "programs")
    with _active_lock:
        if _active is None or os.path.dirname(_active.directory) != directory:
            _active = ProgramStore(directory)
        return _active


class StoredProgram:
    """One jitted function of a backend, called as the jitted function is,
    whose executable for each argument signature comes from the store."""

    def __init__(self, store: ProgramStore, kind: str, agg_id: Optional[int], backend, jitted):
        self._store, self._kind, self._agg_id = store, kind, agg_id
        self._backend, self._jitted = backend, jitted
        self._loaded: Dict[tuple, object] = {}
        #: signature -> "memory" | "disk" | "built"
        self._sources: Dict[tuple, str] = {}

    def _key(self, sig: tuple) -> str:
        return program_key(self._kind, self._agg_id, self._backend, sig)

    def __call__(self, *args):
        sig = signature(args)
        exe = self._loaded.get(sig)
        if exe is None:
            exe, self._sources[sig] = self._store.get(
                self._kind, self._key(sig), lambda: self._jitted.lower(*args).compile()
            )
            self._loaded[sig] = exe
        return exe(*args)

    def sources(self) -> Dict[tuple, str]:
        """signature -> where its executable came from."""
        return dict(self._sources)

    def source(self, *args) -> Optional[str]:
        """Where the executable for these arguments came from; None before
        their first call."""
        return self._sources.get(signature(args))

    def reject(self, *args) -> None:
        """The executable for these arguments gave a wrong answer: out of
        the store, and the next call builds."""
        sig = signature(args)
        self._loaded.pop(sig, None)
        self._sources.pop(sig, None)
        self._store.reject(self._kind, self._key(sig))
