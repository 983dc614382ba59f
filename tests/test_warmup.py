"""Background AOT warmup + persistent compile cache (ISSUE 8).

Scheduling/ledger/metric machinery runs against stubbed warmup compiles
(no jax); one real-backend case proves the background thread actually
compiles executables.  The compile-cache tests pin enable_compile_cache's
contract — JAX_COMPILATION_CACHE_DIR wins and sets no directory in code,
a fixed <repo>/.jax_cache otherwise, and the no-cache-on-CPU guard decided
from the elected backend — against a recording stand-in for jax.config
(this container has no TPU)."""

import asyncio
import threading
import time
from types import SimpleNamespace

import pytest

from janus_tpu.executor import DeviceExecutor, ExecutorConfig
from janus_tpu.fields import next_power_of_2


def _run(coro, timeout=60.0):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


class _FakeBackend:
    def __init__(self):
        self.vdaf = SimpleNamespace()
        self.launches = []

    def stage_prep_init_multi(self, agg_id, requests, pad_to=None):
        rows = sum(len(r[1]) for r in requests)
        if rows == 0:
            return None
        return SimpleNamespace(
            agg_id=agg_id,
            placed=None,
            pad_to=max(pad_to or 0, next_power_of_2(rows)),
            rows=rows,
        )

    def launch_prep_init_multi(self, staged, requests):
        self.launches.append([len(r[1]) for r in requests])
        return [["out"] * len(r[1]) for r in requests]


# ---------------------------------------------------------------------------
# background warmup scheduling + ledger


def test_backend_for_returns_before_background_warmup_finishes(monkeypatch):
    ex = DeviceExecutor(ExecutorConfig(warmup_rows=4, warmup_async=True))
    gate = threading.Event()

    def slow_warmup(backend, agg_ids=(0, 1), pad_to=None):
        assert gate.wait(10)
        return 2

    monkeypatch.setattr(ex, "warmup_backend", slow_warmup)
    t0 = time.monotonic()
    b = ex.backend_for(("shape",), _FakeBackend)
    assert time.monotonic() - t0 < 1.0, "backend_for must not block on compile"
    assert ex.warming(("shape",))
    gate.set()
    assert ex.wait_warm(("shape",), timeout=10)
    assert not ex.warming(("shape",))
    st = ex.compile_stats()
    (entry,) = st.values()
    assert entry["state"] == "warm" and entry["compile_s"] is not None
    # resolving again neither re-warms nor blocks
    assert ex.backend_for(("shape",), _FakeBackend) is b
    ex.shutdown()


def test_failed_warmup_neither_wedges_bucket_nor_trips_breaker(monkeypatch):
    """ISSUE 8 satellite: a warmup failure clears the warming flag, counts
    janus_executor_warmup_total{outcome=error}, leaves the circuit CLOSED,
    and the bucket still serves (first live flush pays the compile)."""
    from janus_tpu.core.metrics import GLOBAL_METRICS

    ex = DeviceExecutor(
        ExecutorConfig(
            warmup_rows=4,
            warmup_async=True,
            flush_window_s=0.01,
            breaker_failure_threshold=3,
        )
    )

    def broken_warmup(backend, agg_ids=(0, 1), pad_to=None):
        raise RuntimeError("XLA compile exploded")

    monkeypatch.setattr(ex, "warmup_backend", broken_warmup)
    before = (
        GLOBAL_METRICS.get_sample_value(
            "janus_executor_warmup_total", {"outcome": "error"}
        )
        or 0
    )
    backend = ex.backend_for(("shape",), _FakeBackend)
    assert ex.wait_warm(("shape",), timeout=10) is False
    assert not ex.warming(("shape",))  # failed != warming: submits flow
    (entry,) = ex.compile_stats().values()
    assert entry["state"] == "failed" and "exploded" in entry["error"]
    after = GLOBAL_METRICS.get_sample_value(
        "janus_executor_warmup_total", {"outcome": "error"}
    )
    assert after == before + 1

    # the bucket is NOT wedged: a live submission flushes normally...
    out = _run(
        ex.submit(("shape",), "prep_init", (b"k", [1, 2]), backend=backend)
    )
    assert len(out) == 2
    # ...and the breaker never counted the compile failure
    assert all(c["state"] == "closed" for c in ex.circuit_stats().values())
    assert all(c["consecutive_failures"] == 0 for c in ex.circuit_stats().values())
    ex.shutdown()


def test_warmup_sync_mode_preserves_legacy_inline_behavior(monkeypatch):
    ex = DeviceExecutor(ExecutorConfig(warmup_rows=4, warmup_async=False))
    calls = []
    monkeypatch.setattr(
        ex, "warmup_backend", lambda b, agg_ids=(0, 1), pad_to=None: calls.append(b) or 2
    )
    ex.backend_for(("shape",), _FakeBackend)
    assert len(calls) == 1  # compiled inline, before backend_for returned
    assert not ex.warming(("shape",))
    (entry,) = ex.compile_stats().values()
    assert entry["state"] == "warm"
    ex.shutdown()


def test_warm_shape_pads_every_flush_up_to_the_warmed_executable(monkeypatch):
    """Once a shape is warm, a flush that fits pads up to warmup_rows and
    runs on the executable warmup compiled; a smaller pow2 pad would be a
    new shape — on the chip, minutes of compile on the launch thread.  A
    larger flush, and a shape that was never warmed, keep the pow2 pad;
    the flight record says which layout the backend chose."""
    ex = DeviceExecutor(
        ExecutorConfig(warmup_rows=16, warmup_async=False, flush_window_s=0.01)
    )
    monkeypatch.setattr(
        ex, "warmup_backend", lambda b, agg_ids=(0, 1), pad_to=None: 2
    )
    staged_pads = []

    class _Backend(_FakeBackend):
        def stage_prep_init_multi(self, agg_id, requests, pad_to=None):
            staged = super().stage_prep_init_multi(agg_id, requests, pad_to=pad_to)
            staged_pads.append(staged.pad_to)
            return staged

        def launch_layout(self, agg_id, pad_to):
            return "planar" if pad_to % 16 == 0 else "row-major"

    warm = ex.backend_for(("warm-shape",), _Backend)
    assert ex.compile_stats() and not ex.warming(("warm-shape",))
    _run(ex.submit(("warm-shape",), "prep_init", (b"k", [1, 2, 3]), backend=warm))
    _run(ex.submit(("warm-shape",), "prep_init", (b"k", list(range(20))), backend=warm))
    assert staged_pads == [16, 32]  # padded up to the warm size; above it, pow2
    flights = ex.flight_recorder.snapshot(2)
    assert [f["padded_rows"] for f in reversed(flights)] == [13, 12]
    assert [f["layout"] for f in reversed(flights)] == ["planar", "planar"]

    # never warmed (warmup of THIS shape failed): plain pow2 pad
    monkeypatch.setattr(
        ex,
        "warmup_backend",
        lambda b, agg_ids=(0, 1), pad_to=None: (_ for _ in ()).throw(RuntimeError("x")),
    )
    cold = ex.backend_for(("cold-shape",), _Backend)
    _run(ex.submit(("cold-shape",), "prep_init", (b"k", [1, 2, 3]), backend=cold))
    assert staged_pads[-1] == 4
    assert ex.flight_recorder.snapshot(1)[0]["layout"] == "row-major"
    ex.shutdown()


def test_cold_state_tracked_without_warmup():
    ex = DeviceExecutor(ExecutorConfig(warmup_rows=0))
    ex.backend_for(("shape",), _FakeBackend)
    (entry,) = ex.compile_stats().values()
    assert entry["state"] == "cold"
    assert not ex.warming(("shape",))
    ex.shutdown()


def test_statusz_surfaces_compile_states(monkeypatch):
    from janus_tpu.core.statusz import runtime_status
    from janus_tpu.executor import service as svc

    ex = DeviceExecutor(ExecutorConfig(warmup_rows=4, warmup_async=True))
    monkeypatch.setattr(
        ex, "warmup_backend", lambda b, agg_ids=(0, 1), pad_to=None: 2
    )
    monkeypatch.setattr(svc, "_GLOBAL", ex)
    ex.backend_for(("shape",), _FakeBackend)
    ex.wait_warm(("shape",), timeout=10)
    doc = runtime_status()
    (entry,) = doc["executor"]["compile"].values()
    assert entry["state"] == "warm" and entry["compile_s"] is not None
    # ledger AGE (ISSUE 9 gap fix): time in the current state, so a
    # minutes-old "warming" entry is visible as the stall it is
    assert entry["age_s"] >= 0.0
    # canonicalization-plan outcomes ride the compile neighborhood
    canon = doc["executor"]["canonicalization"]
    assert set(canon) == {"planned", "canonicalized", "exact_reasons"}
    ex.shutdown()


def test_statusz_canonicalization_reason_counts(monkeypatch):
    """The /statusz compile section counts WHY shapes kept exact-shape
    compiles (ISSUE 9 satellite): plan outcomes per reason."""
    from janus_tpu.core.statusz import runtime_status
    from janus_tpu.executor import service as svc
    from janus_tpu.vdaf import canonical
    from janus_tpu.vdaf.instances import prio3_count, prio3_histogram

    ex = DeviceExecutor(ExecutorConfig(warmup_rows=0))
    monkeypatch.setattr(svc, "_GLOBAL", ex)
    before = canonical.plan_stats()
    # Count has no parameter axis -> exact-shape reason; Histogram(20, 4)
    # pads to a pow2 twin -> canonicalized
    assert canonical.canonicalization_reason(prio3_count())
    assert canonical.canonicalization_reason(prio3_histogram(20, 4)) == ""
    stats = runtime_status()["executor"]["canonicalization"]
    assert stats["planned"] >= before["planned"]
    assert stats["canonicalized"] >= 1
    assert any(stats["exact_reasons"].values())
    ex.shutdown()


def test_real_backend_background_warmup_compiles_executables():
    from janus_tpu.vdaf.backend import TpuBackend
    from janus_tpu.vdaf.instances import prio3_count

    backend = TpuBackend(prio3_count())
    ex = DeviceExecutor(ExecutorConfig(warmup_rows=4, warmup_async=True))
    ex.backend_for(("count",), lambda: backend)
    assert ex.wait_warm(("count",), timeout=300)
    assert set(backend._prep_fns) == {0, 1}  # both agg sides precompiled
    st = ex.compile_stats()
    assert next(iter(st.values()))["state"] == "warm"
    # no compile cache directory on XLA:CPU, so no program store: plain jit
    assert next(iter(st.values()))["source"] == "built"
    ex.shutdown()


# ---------------------------------------------------------------------------
# the program store behind the warm-up (ISSUE 32; tests/test_program_store.py
# has the store itself)


def _with_store(monkeypatch, directory):
    from janus_tpu.vdaf import program_store

    store = program_store.ProgramStore(str(directory))
    monkeypatch.setattr(program_store, "active_store", lambda: store)
    return store


@pytest.mark.parametrize("restarts, source", [(0, "built"), (1, "disk")])
def test_compile_stats_carry_the_programs_source(monkeypatch, tmp_path, restarts, source):
    """A process's first warm-up of a shape builds its prepare programs; a
    restarted one (a new store object on the same directory) loads them,
    and the ledger, the log line and the ``compile`` span say which."""
    from janus_tpu.vdaf.backend import TpuBackend
    from janus_tpu.vdaf.instances import prio3_count

    spans = []
    monkeypatch.setattr(
        "janus_tpu.executor.service.emit_span",
        lambda name, cat, t0, dt, **kw: spans.append((name, kw)),
    )
    for _ in range(restarts + 1):
        _with_store(monkeypatch, tmp_path)
        ex = DeviceExecutor(ExecutorConfig(warmup_rows=4, warmup_async=False))
        ex.backend_for(("count",), lambda: TpuBackend(prio3_count()))
        (entry,) = ex.compile_stats().values()
        ex.shutdown()
    assert entry["state"] == "warm" and entry["source"] == source
    assert spans[-1][0] == "compile" and spans[-1][1]["source"] == source


def test_cold_and_failed_shapes_have_no_source(monkeypatch):
    ex = DeviceExecutor(ExecutorConfig(warmup_rows=0))
    ex.backend_for(("shape",), _FakeBackend)
    (entry,) = ex.compile_stats().values()
    assert entry["state"] == "cold" and entry["source"] is None
    ex.shutdown()


def test_second_backend_of_a_loaded_shape_gets_aggregate_from_memory(monkeypatch, tmp_path):
    """The helper's per-task backend is rebuilt when ``task_cache_ttl``
    expires (ROADMAP S8): the memory front is keyed by shape, not by
    backend object, so the rebuilt one does not trace ``aggregate`` again."""
    import numpy as np

    from janus_tpu.core.metrics import GLOBAL_METRICS
    from janus_tpu.vdaf.backend import TpuBackend
    from janus_tpu.vdaf.instances import prio3_count

    store = _with_store(monkeypatch, tmp_path)
    builds = []
    inner = store.get

    def spying_get(kind, key, build):
        def spied():
            builds.append(kind)
            return build()

        return inner(kind, key, spied)

    monkeypatch.setattr(store, "get", spying_get)

    def memory_hits():
        return GLOBAL_METRICS.get_sample_value(
            "janus_program_store_total", {"program": "aggregate", "outcome": "memory"}
        ) or 0.0

    shares = np.ones((4, 1, 2), dtype=np.uint32)
    mask = np.ones(4, dtype=bool)
    first, second = TpuBackend(prio3_count()), TpuBackend(prio3_count())
    want = first.aggregate_batch(shares, mask)
    assert builds == ["aggregate"]
    before = memory_hits()
    assert second.aggregate_batch(shares, mask) == want
    assert builds == ["aggregate"]  # no second trace
    assert memory_hits() == before + 1
    assert set(second._agg_fn.sources().values()) == {"memory"}
    # another job size is another program: built once, then shared too
    second.aggregate_batch(np.ones((8, 1, 2), dtype=np.uint32), np.ones(8, dtype=bool))
    first.aggregate_batch(np.ones((8, 1, 2), dtype=np.uint32), np.ones(8, dtype=bool))
    assert builds == ["aggregate", "aggregate"]


# ---------------------------------------------------------------------------
# driver routing: oracle-drain while warming


def test_driver_serves_on_oracle_while_shape_warms(monkeypatch):
    from janus_tpu.aggregator import AggregationJobDriver, DriverConfig
    from janus_tpu.executor import reset_global_executor
    from janus_tpu.utils.test_util import det_rng
    from janus_tpu.vdaf.backend import OracleBackend, TpuBackend
    from janus_tpu.vdaf.instances import prio3_count

    reset_global_executor()
    try:
        driver = AggregationJobDriver(
            None,
            None,
            DriverConfig(
                vdaf_backend="tpu",
                device_executor=ExecutorConfig(enabled=True),
            ),
        )
        ex = driver._executor
        vdaf = prio3_count()
        backend = TpuBackend(vdaf)
        key = driver._vdaf_shape_key(vdaf)
        monkeypatch.setattr(ex, "warming", lambda sk: sk == key)

        async def no_submit(*a, **kw):
            raise AssertionError("submit must not run while the shape warms")

        monkeypatch.setattr(ex, "submit", no_submit)
        rng = det_rng("warmroute")
        rows = []
        for i in range(3):
            nonce = rng(vdaf.NONCE_SIZE)
            ps, shares = vdaf.shard(i % 2, nonce, rng(vdaf.RAND_SIZE))
            rows.append((nonce, ps, shares[0]))
        vk = b"\x01" * vdaf.VERIFY_KEY_SIZE
        got = _run(
            driver._coalesced_prep_init(backend, vk, rows, vdaf=vdaf)
        )
        want = OracleBackend(vdaf).prep_init_batch(vk, 0, rows)
        for g, w in zip(got, want):
            assert g[0].out_share == w[0].out_share
            assert g[1].verifiers_share == w[1].verifiers_share
        # compile-wait never reached the breaker
        assert all(c["state"] == "closed" for c in ex.circuit_stats().values())
    finally:
        reset_global_executor()


# ---------------------------------------------------------------------------
# persistent compile cache wiring


class _RecordingConfig:
    """Stand-in for jax.config: records update() calls."""

    def __init__(self):
        self.updates = {}

    def update(self, key, value):
        self.updates[key] = value

    @property
    def jax_compilation_cache_dir(self):
        return self.updates.get("jax_compilation_cache_dir")


def _patched_enable(monkeypatch, backend, env_dir=None, cache_dir=None):
    """enable_compile_cache() with ``backend`` as the ELECTED JAX backend
    and JAX_COMPILATION_CACHE_DIR set to ``env_dir`` (None = unset)."""
    import jax

    from janus_tpu.utils import jax_setup

    rec = _RecordingConfig()
    monkeypatch.setattr(jax, "config", rec)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    return jax_setup.enable_compile_cache(cache_dir), rec


def test_compile_cache_env_dir_wins_and_sets_no_directory_in_code(
    monkeypatch, tmp_path
):
    """JAX_COMPILATION_CACHE_DIR places the cache from outside: JAX reads
    it itself, so the function sets NO directory — not the default and
    not common.compile_cache_dir either."""
    used, rec = _patched_enable(
        monkeypatch,
        "tpu",
        env_dir=str(tmp_path / "outside"),
        cache_dir=str(tmp_path / "fleet-cache"),
    )
    assert used == str(tmp_path / "outside")
    assert "jax_compilation_cache_dir" not in rec.updates
    assert rec.updates["jax_persistent_cache_min_entry_size_bytes"] == 0
    assert rec.updates["jax_persistent_cache_min_compile_time_secs"] == 0


def test_compile_cache_default_is_fixed_path_in_checkout(monkeypatch):
    """Unset, the cache is <repo>/.jax_cache with nothing derived from the
    host, the platform string or XLA_FLAGS in it: two processes — on two
    machines — resolve the same directory, so the second one loads what
    the first compiled."""
    import os

    from janus_tpu.utils import jax_setup

    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    used1, rec1 = _patched_enable(monkeypatch, "tpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_something_else")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    used2, rec2 = _patched_enable(monkeypatch, "tpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert used1 == used2 == os.path.join(repo, ".jax_cache")
    assert jax_setup.DEFAULT_CACHE_DIR == used1
    assert rec1.updates["jax_compilation_cache_dir"] == used1
    assert rec2.updates["jax_compilation_cache_dir"] == used1


def test_compile_cache_config_dir_used_as_given(monkeypatch, tmp_path):
    """common.compile_cache_dir (a fleet-shared volume) is used as given —
    no per-host sub-directory, so every replica that mounts it shares it."""
    used, rec = _patched_enable(
        monkeypatch, "tpu", cache_dir=str(tmp_path / "fleet-cache")
    )
    assert used == str(tmp_path / "fleet-cache")
    assert rec.updates["jax_compilation_cache_dir"] == used


def test_compile_cache_cpu_guard_follows_elected_backend(monkeypatch, tmp_path):
    """XLA:CPU AOT loads are poisoned (see enable_compile_cache): the
    guard must win even over an explicitly placed cache — and it decides
    from the backend JAX elected, not from JAX_PLATFORMS being empty (the
    chip machine leaves it unset)."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    used, rec = _patched_enable(
        monkeypatch, "cpu", env_dir=str(tmp_path), cache_dir=str(tmp_path)
    )
    assert used is None
    assert rec.updates == {}
    used, rec = _patched_enable(monkeypatch, "tpu")
    assert used is not None  # same empty JAX_PLATFORMS, a chip was elected


def test_bootstrap_wires_compile_cache_for_device_binaries(monkeypatch, tmp_path):
    from janus_tpu.binaries import main as binmain

    calls = []
    monkeypatch.setattr(
        "janus_tpu.utils.jax_setup.enable_compile_cache",
        lambda d=None: calls.append(d) or "/somewhere",
    )
    monkeypatch.setenv(
        "DATASTORE_KEYS", "AAAAAAAAAAAAAAAAAAAAAA"
    )
    from janus_tpu.binaries.config import CommonConfig, DbConfig

    cfg = CommonConfig(
        database=DbConfig(path=str(tmp_path / "db.sqlite3")),
        compile_cache_dir=str(tmp_path / "cache"),
    )
    clock, datastore = binmain._bootstrap(cfg, device=True)
    assert calls == [str(tmp_path / "cache")]
    # absent config -> still on, at the function's own default
    calls.clear()
    cfg2 = CommonConfig(database=DbConfig(path=str(tmp_path / "db2.sqlite3")))
    binmain._bootstrap(cfg2, device=True)
    assert calls == [None]
    # a binary that never launches on the device (creator, collection
    # driver, oracle-backed aggregator) must not touch JAX at all
    calls.clear()
    binmain._bootstrap(cfg2)
    assert calls == []


def test_executor_config_plumbs_warmup_and_canonical_knobs():
    from janus_tpu.binaries.config import DeviceExecutorConfig

    cfg = DeviceExecutorConfig(
        enabled=True, warmup_rows=64, warmup_async=False, canonical_shapes=False
    )
    ec = cfg.to_executor_config()
    assert ec.warmup_rows == 64
    assert ec.warmup_async is False
    assert ec.canonical_shapes is False
    # defaults: background warmup + canonicalization on
    ec2 = DeviceExecutorConfig(enabled=True).to_executor_config()
    assert ec2.warmup_async is True and ec2.canonical_shapes is True
