"""The program store (vdaf/program_store.py): compiled executables kept
beside XLA's cache, so a restarted replica loads its device programs
instead of tracing them again.

All on the CPU with a temporary directory handed to the store directly
(``active_store`` patched): on XLA:CPU ``enable_compile_cache`` returns
None and the store is off, which the last tests pin.  A "fresh process"
is a new ``ProgramStore`` on the same directory with a new backend: the
memory front is the store object's."""

import os
import threading

import numpy as np
import pytest

from janus_tpu.core.metrics import GLOBAL_METRICS
from janus_tpu.executor import DeviceExecutor, ExecutorConfig
from janus_tpu.vdaf import program_store
from janus_tpu.vdaf.backend import MeshBackend, TpuBackend
from janus_tpu.vdaf.instances import prio3_count, prio3_histogram

ROWS = 8


def _counted(kind, outcome):
    return (
        GLOBAL_METRICS.get_sample_value(
            "janus_program_store_total", {"program": kind, "outcome": outcome}
        )
        or 0.0
    )


def _fresh(monkeypatch, directory, vdaf=None, **kw):
    """A backend as a process that has just started would build it."""
    store = program_store.ProgramStore(str(directory))
    monkeypatch.setattr(program_store, "active_store", lambda: store)
    return TpuBackend(vdaf or prio3_count(), **kw), store


def _report(vdaf, agg_id):
    nonce = b"\x01" * vdaf.NONCE_SIZE
    public, shares = vdaf.shard(1, nonce, b"\x02" * vdaf.RAND_SIZE)
    return (nonce, public, shares[agg_id])


def _staged(backend, agg_id):
    vk = b"\x03" * backend.vdaf.VERIFY_KEY_SIZE
    reports = [_report(backend.vdaf, agg_id)]
    return backend.stage_prep_init_multi(agg_id, [(vk, reports)], pad_to=ROWS)


def _combine_args(backend):
    jf, flp = backend.bp.jf, backend.vdaf.flp
    rng = np.random.default_rng(7)
    vs = [
        rng.integers(0, 2**16, (ROWS, flp.VERIFIER_LEN, jf.n), dtype=np.uint32)
        for _ in range(2)
    ]
    return vs, []


def _aggregate_args(backend):
    jf, flp = backend.bp.jf, backend.vdaf.flp
    rng = np.random.default_rng(9)
    shares = rng.integers(0, 2**16, (ROWS, flp.OUTPUT_LEN, jf.n), dtype=np.uint32)
    return shares, np.arange(ROWS) % 2 == 0


def _aggregate_program(backend):
    backend.aggregate_batch(*_aggregate_args(backend))  # made on first use
    return backend._agg_fn


#: program -> (the backend's getter, its arguments)
PROGRAMS = {
    "prep_init.a0": (lambda b: b._prep_fn(0), lambda b: (_staged(b, 0).placed,)),
    "prep_init.a1": (lambda b: b._prep_fn(1), lambda b: (_staged(b, 1).placed,)),
    "combine": (lambda b: b._combine(), _combine_args),
    "aggregate": (lambda b: _aggregate_program(b), _aggregate_args),
}


def _leaves(out):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(out)]


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_disk_hit_is_bit_equal_to_the_traced_program(monkeypatch, tmp_path, program):
    getter, make_args = PROGRAMS[program]
    kind = program.split(".")[0]
    plain = TpuBackend(prio3_count(), canonical=True)  # no store: plain jit
    want = _leaves(getter(plain)(*make_args(plain)))

    first, _ = _fresh(monkeypatch, tmp_path, canonical=True)
    fn = getter(first)
    args = make_args(first)
    assert [a.tolist() for a in _leaves(fn(*args))] == [a.tolist() for a in want]
    assert fn.source(*args) == "built"

    disk_before = _counted(kind, "disk")
    second, store = _fresh(monkeypatch, tmp_path, canonical=True)
    fn = getter(second)
    got = _leaves(fn(*make_args(second)))
    assert fn.source(*make_args(second)) == "disk"
    assert _counted(kind, "disk") == disk_before + 1
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    (name,) = [n for n in os.listdir(store.directory) if n.startswith(kind)]
    assert os.stat(os.path.join(store.directory, name)).st_mode & 0o777 == 0o600


def _key(monkeypatch, **changed):
    """The key of one program with one of its parts changed."""
    facts = ("0.9.0", "0.9.0", changed.pop("platform_version", "libtpu 1"), "TPU v5 lite")
    if "jax_version" in changed:
        facts = (changed.pop("jax_version"),) + facts[1:]
    monkeypatch.setattr(program_store, "runtime_facts", lambda: facts)
    monkeypatch.setenv("JANUS_TPU_PALLAS", changed.pop("pallas", "off"))
    backend = TpuBackend(
        changed.pop("vdaf", prio3_histogram(8, 3)),
        canonical=changed.pop("canonical", False),
        field_backend=changed.pop("field_backend", "vpu"),
    )
    rows = changed.pop("rows", ROWS)
    sig = program_store.signature(({"nonces_u8": np.zeros((rows, 16), np.uint8)},))
    key = program_store.program_key(
        changed.pop("kind", "prep_init"), changed.pop("agg_id", 0), backend, sig
    )
    assert not changed
    return key


@pytest.mark.parametrize(
    "part",
    [
        {"kind": "combine"},
        {"agg_id": 1},
        {"vdaf": prio3_histogram(8, 2)},
        {"rows": 2 * ROWS},
        {"canonical": True},
        {"field_backend": "mxu"},
        {"pallas": "interpret"},
        {"jax_version": "0.9.1"},
        {"platform_version": "libtpu 2"},
    ],
    ids=lambda part: next(iter(part)),
)
def test_key_differs_when_any_one_part_differs(monkeypatch, part):
    assert _key(monkeypatch) == _key(monkeypatch)
    assert _key(monkeypatch, **part) != _key(monkeypatch)


def test_digest_differs_when_one_byte_of_one_file_differs(tmp_path):
    for root in ("a", "b"):
        (tmp_path / root / "ops").mkdir(parents=True)
        (tmp_path / root / "__init__.py").write_bytes(b"")
        (tmp_path / root / "ops" / "prepare.py").write_bytes(b"x = 1\n")
        (tmp_path / root / "notes.txt").write_bytes(root.encode())  # not source
    a, b = (program_store.source_digest(str(tmp_path / r)) for r in ("a", "b"))
    assert a == b
    (tmp_path / "b" / "ops" / "prepare.py").write_bytes(b"x = 2\n")
    program_store._digests.pop(str(tmp_path / "b"))
    assert program_store.source_digest(str(tmp_path / "b")) != a
    # the package's own: computed once, a directory a digest
    assert program_store.source_digest() == program_store.source_digest()
    assert program_store.ProgramStore(str(tmp_path)).directory == str(
        tmp_path / program_store.source_digest()
    )


def test_first_entry_of_a_tree_removes_other_digests(monkeypatch, tmp_path):
    stale = tmp_path / ("0" * 64)
    stale.mkdir()
    (stale / "prep_init-x.bin").write_bytes(b"old tree")
    backend, store = _fresh(monkeypatch, tmp_path)
    backend.aggregate_batch(*_aggregate_args(backend))
    assert os.listdir(tmp_path) == [os.path.basename(store.directory)]


def test_truncated_file_is_rejected_rebuilt_and_rewritten(monkeypatch, tmp_path):
    first, store = _fresh(monkeypatch, tmp_path)
    args = _aggregate_args(first)
    want = first.aggregate_batch(*args)
    (name,) = os.listdir(store.directory)
    path = os.path.join(store.directory, name)
    whole = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(whole // 2)

    rejected, built = _counted("aggregate", "rejected"), _counted("aggregate", "built")
    second, _ = _fresh(monkeypatch, tmp_path)
    assert second.aggregate_batch(*args) == want
    assert second._agg_fn.sources() and set(second._agg_fn.sources().values()) == {"built"}
    assert _counted("aggregate", "rejected") == rejected + 1
    assert _counted("aggregate", "built") == built + 1
    assert os.path.getsize(path) == whole  # written anew

    third, _ = _fresh(monkeypatch, tmp_path)
    assert third.aggregate_batch(*args) == want
    assert set(third._agg_fn.sources().values()) == {"disk"}


def test_program_that_fails_warmups_check_is_rejected_and_rebuilt(monkeypatch, tmp_path):
    """A stored prepare executable that loads and runs but answers wrongly
    (here: the right program with one verifier limb flipped, written under
    the right key) is caught by warm-up's comparison with the plain
    ``Prio3.prep_init``, rejected, built and rewritten."""
    import jax

    ex = DeviceExecutor(ExecutorConfig(warmup_rows=ROWS))
    writer, store = _fresh(monkeypatch, tmp_path)
    assert ex.warmup_backend(writer, pad_to=ROWS) == 2
    assert writer.prep_program_sources() == {"built"}

    def wrong(kw):
        out = writer._prep(0, dict(kw))
        return dict(out, verifiers=out["verifiers"] ^ 1)

    placed = _staged(writer, 0).placed
    key = program_store.program_key(
        "prep_init", 0, writer, program_store.signature((placed,))
    )
    store._save("prep_init", key, jax.jit(wrong).lower(placed).compile())

    rejected = _counted("prep_init", "rejected")
    oracle_rows = GLOBAL_METRICS.get_sample_value(
        "janus_vdaf_prepare_reports_total", {"backend": "oracle", "phase": "init"}
    )
    loaded, _ = _fresh(monkeypatch, tmp_path)
    assert ex.warmup_backend(loaded, pad_to=ROWS) == 2
    assert _counted("prep_init", "rejected") == rejected + 1
    sources = {agg: loaded.prep_program_source(_staged(loaded, agg)) for agg in (0, 1)}
    assert sources == {0: "built", 1: "disk"}
    # warm-up's comparison is set-up, not a served row of the oracle
    assert oracle_rows == GLOBAL_METRICS.get_sample_value(
        "janus_vdaf_prepare_reports_total", {"backend": "oracle", "phase": "init"}
    )

    again, _ = _fresh(monkeypatch, tmp_path)
    assert ex.warmup_backend(again, pad_to=ROWS) == 2
    assert again.prep_program_sources() == {"disk"}
    assert _counted("prep_init", "rejected") == rejected + 1
    ex.shutdown()


def test_two_threads_asking_for_one_key_build_once(tmp_path):
    store = program_store.ProgramStore(str(tmp_path))
    builds, started, release = [], threading.Event(), threading.Event()

    class _Unstorable:  # _save gives up on it; memory serves it
        pass

    def build():
        builds.append(1)
        started.set()
        assert release.wait(10)
        return _Unstorable()

    results = []
    threads = [
        threading.Thread(target=lambda: results.append(store.get("combine", "k", build)))
        for _ in range(2)
    ]
    for t in threads:
        t.start()
    assert started.wait(10)
    release.set()
    for t in threads:
        t.join(10)
    assert len(builds) == 1
    assert sorted(outcome for _exe, outcome in results) == ["built", "memory"]
    assert results[0][0] is results[1][0]


def test_many_threads_over_a_few_keys_build_each_once(tmp_path):
    """More threads than cores, a short switch interval: a lost update in
    the store's dicts would show as a second build or a second object."""
    import sys
    import time

    store = program_store.ProgramStore(str(tmp_path))
    keys = [f"k{i}" for i in range(4)]
    builds = {k: 0 for k in keys}
    results = {k: [] for k in keys}

    def ask(key):
        def build():
            builds[key] += 1
            time.sleep(0.01)
            return object()  # unstorable: memory serves it

        results[key].append(store.get("aggregate", key, build)[0])

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=ask, args=(keys[i % 4],)) for i in range(64)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    assert builds == {k: 1 for k in keys}
    assert all(len(results[k]) == 16 and len(set(map(id, results[k]))) == 1 for k in keys)


def test_store_is_off_where_enable_compile_cache_returns_none(monkeypatch):
    import jax

    from janus_tpu.utils import jax_setup

    assert jax_setup.enable_compile_cache() is None  # XLA:CPU
    assert jax_setup.compile_cache_dir() is None
    assert program_store.active_store() is None
    backend = TpuBackend(prio3_count())
    assert type(backend._prep_fn(0)) is type(jax.jit(lambda x: x))  # today's jit
    assert backend.prep_program_sources() == set()


def test_store_lives_under_the_compile_cache_directory(monkeypatch, tmp_path):
    from janus_tpu.utils import jax_setup

    monkeypatch.setattr(jax_setup, "_cache_dir_in_use", str(tmp_path))
    store = program_store.active_store()
    assert store.directory == str(tmp_path / "programs" / program_store.source_digest())
    assert program_store.active_store() is store  # one a process
    assert isinstance(TpuBackend(prio3_count())._prep_fn(0), program_store.StoredProgram)
    # the mesh backend's shard_maps stay on plain jit
    assert not isinstance(
        MeshBackend(prio3_count())._prep_fn(0), program_store.StoredProgram
    )
