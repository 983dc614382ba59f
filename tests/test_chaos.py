"""Fault-injection harness + failure-domain hardening (ISSUE 2).

Layers, cheapest first:

* registry semantics: default-off, seeded determinism, every KNOWN_POINT
  actually wired into the tree;
* ``retry_http_request`` exhaustion contract (raises, counts request
  duration against ``max_elapsed``);
* executor circuit breaker: trip after K consecutive launch failures,
  half-open probe, recovery; driver degradation to the CPU oracle;
* retryable-failure budget: exponential lease-backoff, abandon at
  ``max_step_attempts``;
* the CHAOS SOAK: a 2-replica, 2-task leader+helper run with every
  injection point firing at p~=0.2, asserting every job reaches a
  terminal state, the breaker trip+recovery is observable in the
  /metrics payload, and aggregates are byte-identical to what the CPU
  oracle computes (Prio3 aggregation is exact, so equality with the
  true sums IS oracle parity).

Seeded via JANUS_CHAOS_SEED (./ci.sh chaos pins it) so CI replays the
same per-point fault sequences.
"""

import asyncio
import os
import pathlib
import sqlite3
import time

import pytest

from janus_tpu.core import faults
from janus_tpu.core.faults import FaultInjectedError, FaultSpec, SkewedClock
from janus_tpu.core.retries import HttpRetryPolicy, retry_http_request
from janus_tpu.core.time import MockClock
from janus_tpu.executor import (
    CircuitOpenError,
    DeviceExecutor,
    ExecutorConfig,
    ExecutorOverloadedError,
    reset_global_executor,
)
from janus_tpu.messages import Duration, Time

SEED = int(os.environ.get("JANUS_CHAOS_SEED", "7"))
REPO = pathlib.Path(__file__).resolve().parents[1]

# (The lease SQL's RETURNING requirement — and the skipif gate it needed
# on pre-3.35 SQLite — is gone: the datastore carries select-then-mutate
# fallbacks, backend_sql.SqliteBackend.supports_returning.)


@pytest.fixture(autouse=True)
def _clean_faults():
    """Never leak an armed registry (or a tripped global executor, or a
    suspect peer verdict) into the rest of the suite."""
    from janus_tpu.core import peer_health

    faults.clear()
    peer_health.reset_peer_health()
    yield
    faults.clear()
    peer_health.reset_peer_health()
    peer_health.tracker().configure(failure_threshold=3, suspect_dwell_s=10.0)
    reset_global_executor()


def _run(coro, timeout=300.0):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


# -- registry ----------------------------------------------------------------


def test_faults_default_off_and_cleared():
    assert not faults.active()
    faults.fire("http.request")  # no-op, no raise
    faults.configure([FaultSpec("http.request", "error", 1.0)], seed=SEED)
    assert faults.active()
    with pytest.raises(FaultInjectedError):
        faults.fire("http.request")
    faults.clear()
    faults.fire("http.request")  # off again
    assert faults.registry().hits["http.request"] == 1


def test_fault_decisions_are_seeded_deterministic():
    """Two identically-seeded registries make identical per-point decision
    sequences; a different seed diverges."""

    def sequence(seed):
        r = faults.FaultRegistry()
        r.configure([FaultSpec("backend.launch", "error", 0.5)], seed=seed)
        out = []
        for _ in range(64):
            try:
                r.fire("backend.launch")
                out.append(0)
            except FaultInjectedError:
                out.append(1)
        return out

    a, b, c = sequence(SEED), sequence(SEED), sequence(SEED + 1)
    assert a == b
    assert a != c
    assert sum(a) > 0 and sum(a) < 64  # p=0.5 actually fires sometimes


def test_every_known_point_is_wired():
    """The KNOWN_POINTS contract: each name appears at its call site (a
    renamed point must fail here, not silently stop injecting)."""
    wiring = {
        "datastore.tx.begin": "janus_tpu/datastore/datastore.py",
        "datastore.tx.commit": "janus_tpu/datastore/datastore.py",
        "http.request": "janus_tpu/core/retries.py",
        "executor.flush": "janus_tpu/executor/service.py",
        "backend.launch": "janus_tpu/vdaf/backend.py",
        "backend.device_lost": "janus_tpu/vdaf/backend.py",
        "backend.combine": "janus_tpu/vdaf/backend.py",
        "clock.skew": "janus_tpu/core/faults.py",
        "upload.open": "janus_tpu/aggregator/report_writer.py",
        "report_writer.flush": "janus_tpu/aggregator/report_writer.py",
        "gc.run": "janus_tpu/aggregator/garbage_collector.py",
        "key_rotator.run": "janus_tpu/aggregator/key_rotator.py",
        "accumulator.spill": "janus_tpu/executor/accumulator.py",
        "accumulator.evict": "janus_tpu/executor/accumulator.py",
        "accumulator.replay": "janus_tpu/aggregator/collection_job_driver.py",
        "collection.aggregate_share": "janus_tpu/aggregator/collection_job_driver.py",
        "ingest.journal": "janus_tpu/core/ingest.py",
        "journal.corrupt": "janus_tpu/datastore/datastore.py",
    }
    assert set(wiring) == set(faults.KNOWN_POINTS)
    for point, rel in wiring.items():
        assert f'"{point}"' in (REPO / rel).read_text(), (point, rel)


def test_skewed_clock_applies_registry_offsets():
    base = MockClock(Time(1_600_000_000))
    clock = SkewedClock(base)
    assert clock.now().seconds == 1_600_000_000  # faults off: no skew
    faults.configure([FaultSpec("clock.skew", "skew", 1.0, skew_s=30)], seed=SEED)
    seen = {clock.now().seconds - base.now().seconds for _ in range(32)}
    assert seen - {0}, "skew must fire at p=1"
    assert all(-30 <= s <= 30 for s in seen)
    clock.advance(Duration(60))  # delegation to the wrapped MockClock
    assert base.now().seconds == 1_600_000_060


def test_fault_injection_config_yaml_round_trip():
    from janus_tpu.binaries.config import JobDriverBinaryConfig, load_config

    cfg = load_config(
        JobDriverBinaryConfig,
        text="""
common:
  fault_injection:
    enabled: true
    seed: 3
    points:
      http.request: {mode: error, probability: 1.0}
      clock.skew: [{mode: skew, probability: 0.5, skew_s: 10}]
""",
    )
    assert cfg.common.fault_injection.enabled
    cfg.common.fault_injection.install()
    try:
        assert faults.active()
        with pytest.raises(FaultInjectedError):
            faults.fire("http.request")
    finally:
        faults.clear()


# -- retry_http_request (satellite fix) --------------------------------------


class _FailingSession:
    """Every attempt fails at the transport layer after ``delay_s``."""

    def __init__(self, delay_s=0.0):
        self.calls = 0
        self.delay_s = delay_s

    def request(self, method, url, data=None, headers=None):
        self.calls += 1
        sess = self

        class _Ctx:
            async def __aenter__(self):
                import aiohttp

                if sess.delay_s:
                    await asyncio.sleep(sess.delay_s)
                raise aiohttp.ClientConnectionError("connection refused")

            async def __aexit__(self, *exc):
                return False

        return _Ctx()


def test_retry_exhaustion_after_transport_failure_raises():
    """Exhausting attempts on transport errors must RAISE the last error,
    never return None (the old code's max_elapsed path did)."""
    import aiohttp

    session = _FailingSession()
    with pytest.raises(aiohttp.ClientConnectionError):
        _run(
            retry_http_request(
                session,
                "GET",
                "http://unreachable.invalid/",
                policy=HttpRetryPolicy(0.001, 0.002, 2.0, 10.0, 3),
            )
        )
    assert session.calls == 3


def test_retry_max_elapsed_counts_request_duration():
    """A peer that burns wall time per hung attempt exhausts max_elapsed
    even though almost nothing is spent sleeping between attempts."""
    import aiohttp

    session = _FailingSession(delay_s=0.05)
    with pytest.raises(aiohttp.ClientConnectionError):
        _run(
            retry_http_request(
                session,
                "GET",
                "http://unreachable.invalid/",
                policy=HttpRetryPolicy(0.001, 0.002, 2.0, 0.06, 10),
            )
        )
    assert session.calls <= 3, "request duration must count against max_elapsed"


def test_injected_http_faults_are_retried_then_surfaced():
    class _NeverCalled:
        def request(self, *a, **kw):  # pragma: no cover
            raise AssertionError("transport reached despite injected fault")

    faults.configure([FaultSpec("http.request", "error", 1.0)], seed=SEED)
    with pytest.raises(FaultInjectedError):
        _run(
            retry_http_request(
                _NeverCalled(),
                "GET",
                "http://x.invalid/",
                policy=HttpRetryPolicy(0.001, 0.002, 2.0, 1.0, 2),
            )
        )
    assert faults.registry().hits["http.request"] == 2, "each attempt re-rolls"


# -- circuit breaker ---------------------------------------------------------


class _FlakyBackend:
    """Launches fail while .fail is True; minimal stage/launch seam."""

    class _V:
        pass

    def __init__(self, fail=True):
        self.vdaf = self._V()
        self.fail = fail
        self.launches = 0

    def stage_prep_init_multi(self, agg_id, requests, pad_to=None):
        from types import SimpleNamespace

        rows = sum(len(r) for _, r in requests)
        return SimpleNamespace(agg_id=agg_id, placed=None, pad_to=rows, rows=rows)

    def launch_prep_init_multi(self, staged, requests):
        self.launches += 1
        if self.fail:
            raise RuntimeError("device on fire")
        return [[("ok", i) for i in range(len(r))] for _, r in requests]


def _breaker_config(**kw):
    base = dict(
        flush_window_s=0.005,
        flush_max_rows=10_000,
        breaker_failure_threshold=2,
        breaker_reset_timeout_s=0.15,
    )
    base.update(kw)
    return ExecutorConfig(**base)


def test_breaker_trips_after_k_failures_and_half_open_probe_recovers():
    backend = _FlakyBackend(fail=True)
    ex = DeviceExecutor(_breaker_config())

    async def go():
        for _ in range(2):  # K=2 consecutive launch failures
            with pytest.raises(RuntimeError):
                await ex.submit(("sh",), "prep_init", (b"k", [0]), backend=backend)
        # open: fail fast without touching the device
        launches = backend.launches
        with pytest.raises(CircuitOpenError):
            await ex.submit(("sh",), "prep_init", (b"k", [0]), backend=backend)
        assert backend.launches == launches
        (st,) = ex.circuit_stats().values()
        assert st["state"] == "open" and st["trips"] == 1
        # past the reset timeout the single half-open probe goes through
        await asyncio.sleep(0.2)
        backend.fail = False
        out = await ex.submit(("sh",), "prep_init", (b"k", [0]), backend=backend)
        assert out == [("ok", 0)]
        (st,) = ex.circuit_stats().values()
        assert st["state"] == "closed" and st["consecutive_failures"] == 0

    _run(go())
    ex.shutdown()


def test_failed_half_open_probe_reopens():
    backend = _FlakyBackend(fail=True)
    ex = DeviceExecutor(_breaker_config())

    async def go():
        for _ in range(2):
            with pytest.raises(RuntimeError):
                await ex.submit(("sh",), "prep_init", (b"k", [0]), backend=backend)
        await asyncio.sleep(0.2)
        with pytest.raises(RuntimeError):  # the probe itself fails...
            await ex.submit(("sh",), "prep_init", (b"k", [0]), backend=backend)
        with pytest.raises(CircuitOpenError):  # ...and the circuit re-opens
            await ex.submit(("sh",), "prep_init", (b"k", [0]), backend=backend)
        (st,) = ex.circuit_stats().values()
        assert st["state"] == "open" and st["trips"] == 2

    _run(go())
    ex.shutdown()


def test_injected_flush_faults_count_toward_breaker():
    backend = _FlakyBackend(fail=False)
    ex = DeviceExecutor(_breaker_config())
    faults.configure([FaultSpec("executor.flush", "error", 1.0)], seed=SEED)

    async def go():
        for _ in range(2):
            with pytest.raises(FaultInjectedError):
                await ex.submit(("sh",), "prep_init", (b"k", [0]), backend=backend)
        with pytest.raises(CircuitOpenError):
            await ex.submit(("sh",), "prep_init", (b"k", [0]), backend=backend)

    _run(go())
    ex.shutdown()
    assert backend.launches == 0, "flush fault fires before the device"


def test_driver_degrades_to_oracle_while_circuit_open():
    """The graceful-degradation contract: CircuitOpenError -> the job is
    served by the backend's bit-exact CPU oracle, not failed."""
    from janus_tpu.aggregator.aggregation_job_driver import (
        AggregationJobDriver,
        DriverConfig,
        JobStepError,
    )

    reset_global_executor()
    backend = _FlakyBackend(fail=True)

    class _Oracle:
        def prep_init_batch(self, vk, agg_id, rows):
            return [("oracle", vk, i) for i in range(len(rows))]

    backend.oracle = _Oracle()
    driver = AggregationJobDriver(
        datastore=None,
        session_factory=None,
        config=DriverConfig(
            vdaf_backend="tpu",
            device_executor=_breaker_config(
                enabled=True, breaker_failure_threshold=1, breaker_reset_timeout_s=60.0
            ),
        ),
    )

    async def go():
        # first delivery: launch fails -> retryable (breaker counts it)
        with pytest.raises(JobStepError) as exc_info:
            await driver._coalesced_prep_init(backend, b"vk", [0, 1])
        assert exc_info.value.retryable
        # redelivery: circuit open -> oracle serves the job
        out = await driver._coalesced_prep_init(backend, b"vk", [0, 1])
        assert out == [("oracle", b"vk", 0), ("oracle", b"vk", 1)]

    _run(go())


# -- retryable-failure budget ------------------------------------------------


def test_step_retry_delay_curve():
    from janus_tpu.aggregator.job_driver import step_retry_delay

    delays = [step_retry_delay(a, 1.0, 300.0).seconds for a in range(1, 12)]
    assert delays[:5] == [1, 2, 4, 8, 16]
    assert delays[-1] == 300  # capped


def test_retryable_budget_releases_with_backoff_then_abandons():
    """JobStepError(retryable=True) counts against max_step_attempts via
    lease.lease_attempts: under budget -> release (redeliver later); at
    budget -> abandon."""
    from janus_tpu.aggregator.aggregation_job_driver import (
        AggregationJobDriver,
        DriverConfig,
        JobStepError,
    )
    from janus_tpu.datastore.models import AcquiredAggregationJob, Lease, LeaseToken
    from janus_tpu.messages import AggregationJobId, TaskId

    class _StubDatastore:
        def __init__(self):
            self.tx_names = []

        async def run_tx_async(self, name, fn):
            self.tx_names.append(name)
            return None

    def make_lease(attempts):
        return Lease(
            leased=AcquiredAggregationJob(
                task_id=TaskId.random(),
                aggregation_job_id=AggregationJobId.random(),
                query_type="TimeInterval",
                vdaf={"type": "Prio3Count"},
            ),
            lease_expiry=Time(1_600_000_600),
            lease_token=LeaseToken(b"\x01" * 16),
            lease_attempts=attempts,
        )

    ds = _StubDatastore()
    driver = AggregationJobDriver(ds, None, DriverConfig(max_step_attempts=3))

    async def failing_step(lease):
        raise JobStepError("injected", retryable=True)

    driver._step = failing_step

    _run(driver.step_aggregation_job(make_lease(attempts=1)))
    assert ds.tx_names == ["release_agg_job"], "under budget: released"

    ds.tx_names.clear()
    _run(driver.step_aggregation_job(make_lease(attempts=3)))
    assert ds.tx_names == ["abandon_agg_job"], "budget spent: abandoned"


def test_collection_budget_releases_with_backoff_then_abandons():
    from janus_tpu.aggregator.collection_job_driver import (
        CollectionDriverConfig,
        CollectionJobDriver,
    )
    from janus_tpu.datastore.models import AcquiredCollectionJob, Lease, LeaseToken
    from janus_tpu.messages import CollectionJobId, TaskId

    class _StubDatastore:
        def __init__(self):
            self.tx_names = []

        async def run_tx_async(self, name, fn):
            self.tx_names.append(name)
            return None

    def make_lease(attempts):
        return Lease(
            leased=AcquiredCollectionJob(
                task_id=TaskId.random(),
                collection_job_id=CollectionJobId.random(),
                query_type="TimeInterval",
                vdaf={"type": "Prio3Count"},
                step_attempts=0,
            ),
            lease_expiry=Time(1_600_000_600),
            lease_token=LeaseToken(b"\x02" * 16),
            lease_attempts=attempts,
        )

    ds = _StubDatastore()
    driver = CollectionJobDriver(ds, None, CollectionDriverConfig(max_step_attempts=3))

    _run(driver._release_retryable(make_lease(attempts=1)))
    assert ds.tx_names == ["release_coll_job"]

    ds.tx_names.clear()
    _run(driver._release_retryable(make_lease(attempts=3)))
    assert ds.tx_names == ["abandon_collection_job"]


def test_injected_tx_faults_are_absorbed_by_run_tx():
    """Transaction-boundary faults at p=0.5 look like lock contention:
    every transaction still commits (run_tx's retry loop absorbs them)."""
    from janus_tpu.datastore.test_util import EphemeralDatastore

    eph = EphemeralDatastore()
    try:
        faults.configure(
            [
                FaultSpec("datastore.tx.begin", "error", 0.5),
                FaultSpec("datastore.tx.commit", "error", 0.5),
            ],
            seed=SEED,
        )
        for i in range(20):
            got = eph.datastore.run_tx("chaos_tx", lambda tx, i=i: i)
            assert got == i
        hits = faults.registry().hits
        assert hits.get("datastore.tx.begin", 0) + hits.get(
            "datastore.tx.commit", 0
        ) > 0
    finally:
        faults.clear()
        eph.cleanup()


# -- the soak ----------------------------------------------------------------

NOW = Time(1_600_002_000)
TIME_PRECISION = Duration(3600)


class ChaosHarness:
    """Leader + helper aggregators over real HTTP, N Prio3Count tasks,
    stepped by TWO driver replicas sharing the process-wide executor —
    tests/test_integration_pair.py's InProcessPair generalized to
    multi-task + chaos."""

    N_REPORTS = 4

    def __init__(
        self,
        n_tasks=2,
        mesh=False,
        deferred=False,
        driver_overrides=None,
        vdaf=None,
    ):
        import aiohttp

        from janus_tpu.aggregator import Aggregator, Config
        from janus_tpu.aggregator.aggregation_job_driver import (
            AggregationJobDriver,
            DriverConfig,
        )
        from janus_tpu.core.auth_tokens import AuthenticationToken
        from janus_tpu.core.hpke import HpkeKeypair
        from janus_tpu.datastore.test_util import EphemeralDatastore

        self.n_tasks = n_tasks
        #: serialized VDAF instance for every task (default Prio3Count —
        #: the fpvec chaos case passes the gradient family)
        self.vdaf_dict = vdaf or {"type": "Prio3Count"}
        self.clock = MockClock(NOW)
        # clock-skew failure domain: the leader datastore's view drifts
        self.leader_ds = EphemeralDatastore(SkewedClock(self.clock))
        self.helper_ds = EphemeralDatastore(self.clock)
        from janus_tpu.executor import AccumulatorConfig

        self.exec_cfg = ExecutorConfig(
            enabled=True,
            # mesh-enabled chaos (ISSUE 6): every single-chip backend the
            # executor caches upgrades to the SPMD MeshBackend over the
            # 8 virtual CPU devices, so the soak exercises sharded
            # launches, the per-MESH breaker, and sharded accumulation
            # under the same fault schedule
            mesh=mesh,
            flush_window_s=0.02,
            flush_max_rows=4096,
            breaker_failure_threshold=2,
            breaker_reset_timeout_s=0.3,
            # ISSUE 3 acceptance: the soak runs with device-resident
            # accumulation ON and a byte budget tiny enough that LRU
            # evictions fire constantly — aggregates must still be exact.
            # ``deferred`` switches to cross-job residency + journal rows
            # (ISSUE 11's collection-replica SIGKILL case orphans them).
            accumulator=AccumulatorConfig(
                enabled=True,
                byte_budget=256,
                drain_interval_s=3600.0 if deferred else 0.0,
            ),
        )
        cfg = Config(vdaf_backend="oracle", max_upload_batch_write_delay=0.02)
        # Helper-side chaos parity (ISSUE 4 satellite / ROADMAP): the
        # HELPER serves prepare on the device backend THROUGH the shared
        # executor (and, with the store enabled, retains its out shares on
        # device) — the same failure domains the leader drivers face.
        helper_cfg = Config(
            vdaf_backend="tpu",
            max_upload_batch_write_delay=0.02,
            device_executor=self.exec_cfg,
        )
        self.leader_agg = Aggregator(self.leader_ds.datastore, self.clock, cfg)
        self.helper_agg = Aggregator(self.helper_ds.datastore, self.clock, helper_cfg)
        self.agg_token = AuthenticationToken.new_bearer("agg-token-chaos")
        self.col_token = AuthenticationToken.new_bearer("col-token-chaos")
        self.collector_keys = HpkeKeypair.generate(9)
        self.tasks = []  # (task_id, leader_task, helper_task)
        # 2 replicas: distinct driver instances, one shared global executor
        driver_kwargs = dict(
            vdaf_backend="tpu",
            device_executor=self.exec_cfg,
            http_retry=HttpRetryPolicy(0.001, 0.01, 2.0, 0.5, 3),
            # parity soak: jobs must survive chaos, never abandon
            maximum_attempts_before_failure=10_000,
            max_step_attempts=10_000,
            retry_initial_delay_s=1.0,
            retry_max_delay_s=8.0,
            # the soak's rounds spin in mock time while the peer-health
            # dwell runs in REAL time: keep it short so a suspect helper
            # (phase 1 drives http.request at p=1) probes again within a
            # couple of rounds instead of gating for 10 wall seconds
            peer_suspect_dwell_s=0.2,
            peer_failure_threshold=3,
        )
        driver_kwargs.update(driver_overrides or {})
        # peer-health thresholds go to the PROCESS-WIDE tracker (what a
        # binary does once at startup), not onto DriverConfig
        from janus_tpu.core import peer_health

        peer_health.tracker().configure(
            failure_threshold=driver_kwargs.pop("peer_failure_threshold"),
            suspect_dwell_s=driver_kwargs.pop("peer_suspect_dwell_s"),
        )
        self.drivers = [
            AggregationJobDriver(
                self.leader_ds.datastore,
                aiohttp.ClientSession,
                DriverConfig(**driver_kwargs),
            )
            for _ in range(2)
        ]

    async def start(self):
        from aiohttp.test_utils import TestClient, TestServer

        from janus_tpu.aggregator import aggregator_app
        from janus_tpu.datastore import AggregatorTask, TaskQueryType
        from janus_tpu.messages import Role, TaskId

        self.leader_client = TestClient(TestServer(aggregator_app(self.leader_agg)))
        self.helper_client = TestClient(TestServer(aggregator_app(self.helper_agg)))
        await self.leader_client.start_server()
        await self.helper_client.start_server()
        self.leader_url = str(self.leader_client.make_url("/"))
        helper_url = str(self.helper_client.make_url("/"))
        from janus_tpu.core.hpke import HpkeKeypair

        for t in range(self.n_tasks):
            task_id = TaskId.random()
            common = dict(
                task_id=task_id,
                query_type=TaskQueryType.time_interval(),
                vdaf=dict(self.vdaf_dict),
                vdaf_verify_key=bytes([0x30 + t]) * 16,
                min_batch_size=3,
                time_precision=TIME_PRECISION,
                collector_hpke_config=self.collector_keys.config,
            )
            leader_task = AggregatorTask(
                peer_aggregator_endpoint=helper_url,
                role=Role.LEADER,
                aggregator_auth_token=self.agg_token,
                collector_auth_token_hash=self.col_token.hash(),
                hpke_keys=[HpkeKeypair.generate(1)],
                **common,
            )
            helper_task = AggregatorTask(
                peer_aggregator_endpoint=self.leader_url,
                role=Role.HELPER,
                aggregator_auth_token_hash=self.agg_token.hash(),
                hpke_keys=[HpkeKeypair.generate(2)],
                **common,
            )
            self.leader_ds.datastore.run_tx(
                "put", lambda tx, lt=leader_task: tx.put_aggregator_task(lt)
            )
            self.helper_ds.datastore.run_tx(
                "put", lambda tx, ht=helper_task: tx.put_aggregator_task(ht)
            )
            self.tasks.append((task_id, leader_task, helper_task))

    async def stop(self):
        for d in self.drivers:
            await d.close()
        await self.leader_agg.shutdown()
        await self.helper_agg.shutdown()
        await self.leader_client.close()
        await self.helper_client.close()
        self.leader_ds.cleanup()
        self.helper_ds.cleanup()

    async def upload(self, task_idx, measurement):
        from janus_tpu.client import prepare_report

        task_id, leader_task, helper_task = self.tasks[task_idx]
        report = prepare_report(
            leader_task.vdaf_instance(),
            task_id,
            leader_task.hpke_keys[0].config,
            helper_task.hpke_keys[0].config,
            TIME_PRECISION,
            measurement,
            time=NOW,
        )
        resp = await self.leader_client.put(
            f"/tasks/{task_id}/reports", data=report.get_encoded()
        )
        assert resp.status == 201, await resp.text()

    async def create_jobs(self):
        from janus_tpu.aggregator import AggregationJobCreator, CreatorConfig

        creator = AggregationJobCreator(
            self.leader_ds.datastore,
            CreatorConfig(min_aggregation_job_size=1, max_aggregation_job_size=100),
        )
        await creator.run_once()

    async def drive_round(self):
        """One discovery+step round on BOTH replicas concurrently; raw
        stepper escapes are tolerated mid-chaos (the lease machinery owns
        recovery) but counted."""

        async def replica(driver):
            leases = await self.leader_ds.datastore.run_tx_async(
                "acquire",
                lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(60), 4),
            )
            for lease in leases:
                try:
                    await driver.step_aggregation_job(lease)
                except Exception:
                    pass  # lease expires; redelivered next round

        await asyncio.gather(*(replica(d) for d in self.drivers))
        self.clock.advance(Duration(61))

    def agg_job_states(self):
        states = []
        for task_id, _, _ in self.tasks:
            jobs = self.leader_ds.datastore.run_tx(
                "jobs", lambda tx, t=task_id: tx.get_aggregation_jobs_for_task(t)
            )
            states.extend(j.state.value for j in jobs)
        return states

    async def collect_task(self, task_idx):
        import aiohttp

        from janus_tpu.aggregator.collection_job_driver import CollectionJobDriver
        from janus_tpu.collector import Collector
        from janus_tpu.messages import Interval, Query

        task_id, leader_task, _ = self.tasks[task_idx]
        collector = Collector(
            task_id=task_id,
            leader_endpoint=self.leader_url,
            vdaf=leader_task.vdaf_instance(),
            auth_token=self.col_token,
            hpke_keypair=self.collector_keys,
            poll_interval=0.05,
            max_poll_time=20.0,
        )
        driver = CollectionJobDriver(self.leader_ds.datastore, aiohttp.ClientSession)

        async def drive():
            for _ in range(20):
                await asyncio.sleep(0.1)
                leases = await self.leader_ds.datastore.run_tx_async(
                    "acquire_coll",
                    lambda tx: tx.acquire_incomplete_collection_jobs(Duration(600), 4),
                )
                for lease in leases:
                    await driver.step_collection_job(lease)
                self.clock.advance(Duration(61))

        result, _ = await asyncio.gather(
            collector.collect(
                Query.new_time_interval(Interval(NOW, TIME_PRECISION)), session=None
            ),
            drive(),
        )
        await driver.close()
        return result


def _soak_fault_specs():
    """Every injection point firing at p~=0.2 (the ISSUE 2 acceptance
    shape); delays/hangs sized against the soak's timeout guards."""
    return [
        FaultSpec("datastore.tx.begin", "error", 0.2),
        FaultSpec("datastore.tx.commit", "error", 0.1),
        FaultSpec("http.request", "error", 0.2),
        FaultSpec("http.request", "delay", 0.1, delay_s=0.01),
        FaultSpec("http.request", "hang", 0.05, hang_s=0.1),
        FaultSpec("executor.flush", "error", 0.2),
        FaultSpec("backend.launch", "error", 0.2),
        # the mesh-flavored twin of backend.launch: a chip dropping out of
        # the mesh mid-launch (fires on single-chip launches too — the
        # failure answer is the same breaker + oracle fallback)
        FaultSpec("backend.device_lost", "error", 0.1),
        FaultSpec("backend.combine", "error", 0.2),
        FaultSpec("clock.skew", "skew", 0.2, skew_s=5),
        # mid-spill failures: drains fall back to the CPU-oracle replay,
        # evictions abort the flush (breaker counts it) — aggregates must
        # come out exact either way (ISSUE 3 acceptance)
        FaultSpec("accumulator.spill", "error", 0.2),
        FaultSpec("accumulator.evict", "error", 0.2),
    ]


def test_chaos_soak_two_replicas_multitask():
    """THE ACCEPTANCE SOAK: all injection points at p~=0.2 over a
    2-replica 2-task run; every job terminal, breaker trip AND recovery
    observable in the /metrics payload, aggregates exactly the oracle's."""
    from janus_tpu.core.metrics import GLOBAL_METRICS

    reset_global_executor()
    harness = ChaosHarness(n_tasks=2)
    measurements = {0: [1, 0, 1, 1], 1: [1, 1, 0, 1]}

    async def flow():
        await harness.start()
        try:
            for t, ms in measurements.items():
                for m in ms:
                    await harness.upload(t, m)
            await asyncio.sleep(0.1)  # report batcher flush
            await harness.create_jobs()

            # Phase 1 — guaranteed breaker trip: every executor flush AND
            # every peer request fails, so the circuit opens while no job
            # can slip through to Finished before the steady-state phase.
            faults.configure(
                [
                    FaultSpec("executor.flush", "error", 1.0),
                    FaultSpec("http.request", "error", 1.0),
                ],
                seed=SEED,
            )
            ex = harness.drivers[0]._executor
            for _ in range(8):
                await harness.drive_round()
                if any(
                    s["state"] == "open" for s in ex.circuit_stats().values()
                ):
                    break
            # with the circuit open, prepare degrades to the oracle and
            # the step reaches the helper over HTTP — where the request
            # fault fires (a fast trip would otherwise end phase 1 before
            # any HTTP attempt)
            for _ in range(8):
                if faults.registry().hits.get("http.request", 0) > 0:
                    break
                await harness.drive_round()
            circuits = ex.circuit_stats()
            assert any(s["trips"] >= 1 for s in circuits.values()), circuits
            phase1_hits = dict(faults.registry().hits)
            assert phase1_hits.get("executor.flush", 0) > 0
            assert phase1_hits.get("http.request", 0) > 0

            # Phase 2 — steady-state chaos: every point at p~=0.2.
            faults.configure(_soak_fault_specs(), seed=SEED)
            for _ in range(60):
                await harness.drive_round()
                states = harness.agg_job_states()
                if states and all(s == "Finished" for s in states):
                    break
            states = harness.agg_job_states()
            assert len(states) >= 2, "both tasks must have aggregation jobs"
            assert all(s == "Finished" for s in states), states

            phase2_hits = dict(faults.registry().hits)
            faults.clear()
            assert phase2_hits.get("datastore.tx.begin", 0) > 0, phase2_hits

            # Phase 3 — recovery: with faults off, a probe submit closes
            # any still-open circuit (half-open -> success -> closed).
            if any(s["state"] != "closed" for s in ex.circuit_stats().values()):
                await asyncio.sleep(0.35)  # past breaker_reset_timeout_s
                driver = next(d for d in harness.drivers if d._backends)
                (shape_key, backend), = list(driver._backends.items())
                vdaf = harness.tasks[0][1].vdaf_instance()
                nonce = b"\x00" * vdaf.NONCE_SIZE
                public, shares = vdaf.shard(0, nonce, b"\x00" * vdaf.RAND_SIZE)
                await ex.submit(
                    shape_key,
                    "prep_init",
                    (b"\x2a" * 16, [(nonce, public, shares[0])]),
                    backend=backend,
                )
            circuits = ex.circuit_stats()
            assert all(s["state"] == "closed" for s in circuits.values()), circuits

            # trip AND recovery observable on the /metrics payload
            metrics_text = GLOBAL_METRICS.export().decode()
            assert 'janus_executor_circuit_transitions_total' in metrics_text
            assert 'state="open"' in metrics_text
            assert 'state="closed"' in metrics_text
            assert "janus_faults_injected_total" in metrics_text

            # Collection under a quiet sky: aggregates == the oracle's
            # exact sums, with every report accounted for.
            for t, ms in measurements.items():
                result = await harness.collect_task(t)
                assert result.report_count == len(ms), (t, result)
                assert result.aggregate_result == sum(ms), (t, result)
        finally:
            faults.clear()
            await harness.stop()

    _run(flow(), timeout=280.0)
    reset_global_executor()


def test_poplar1_chaos_device_lost_oracle_fallback_exactly_once():
    """ISSUE 10 acceptance: Poplar1 heavy hitters share the Prio3 failure
    domains end to end.  With every Poplar1 walk/sketch losing the device
    (``backend.device_lost`` at p=1), the per-shape breaker opens, BOTH
    protocol sides degrade to the per-report CPU oracle (the fault point
    stays armed — the oracle path must never consult it), each job's
    level-keyed deltas journal in its commit tx (deferred store), the
    owning store "crashes" before draining, and the collection-time
    replay re-derives the level's shares from the datastore: heavy-hitter
    counts bit-exact, journal empty, nothing double-merged."""
    from test_poplar_executor import NOW_S, _PoplarPair

    from janus_tpu.executor import AccumulatorConfig
    from janus_tpu.vdaf.poplar1 import Poplar1AggregationParam

    reset_global_executor()
    exec_cfg = ExecutorConfig(
        enabled=True,
        flush_window_s=0.05,
        flush_max_rows=4096,
        breaker_failure_threshold=2,
        breaker_reset_timeout_s=60.0,  # stays open for the whole run
        accumulator=AccumulatorConfig(enabled=True, drain_interval_s=3600.0),
    )
    pair = _PoplarPair(exec_cfg, bits=4, job_size=2)
    measurements = [0b1011, 0b1011, 0b0100, 0b1111]

    async def flow():
        from janus_tpu.messages import Duration

        await pair.start()
        try:
            for m in measurements:
                await pair.upload(m)
            await asyncio.sleep(0.1)
            driver = pair.make_driver()
            ap1 = Poplar1AggregationParam(1, (0, 1, 2, 3))

            # every device walk loses a chip — the per-shape breaker must
            # open, then the oracle serves the rest of the run
            faults.configure(
                [FaultSpec("backend.device_lost", "error", 1.0)], seed=SEED
            )
            result = await pair.collect_level(ap1, driver, max_rounds=40)

            ex = driver._executor
            circuits = ex.circuit_stats()
            assert any(
                label.startswith("Poplar1") and s["trips"] >= 1
                for label, s in circuits.items()
            ), circuits
            assert faults.registry().hits.get("backend.device_lost", 0) > 0

            expect = [0, 0, 0, 0]
            for m in measurements:
                expect[m >> 2] += 1
            assert result.aggregate_result == expect, (
                result.aggregate_result, expect,
            )
            assert result.report_count == len(measurements)

            # the level's deltas journaled (deferred) and were consumed
            # exactly once by drain or replay — none outstanding now
            ds = pair.leader_ds.datastore
            assert (
                ds.run_tx(
                    "count",
                    lambda tx: tx.count_accumulator_journal_entries(pair.task_id),
                )
                == 0
            )
            await driver.close()
        finally:
            faults.clear()
            await pair.stop()

    _run(flow(), timeout=280.0)
    reset_global_executor()


def test_fpvec_chaos_device_lost_oracle_fallback_exactly_once():
    """ISSUE 15 acceptance: the gradient family shares the Prio3 failure
    domains end to end.  A Prio3FixedPointBoundedL2VecSum task rides the
    standard prep_init executor plane; with every device launch losing
    the chip (``backend.device_lost`` at p=1) the per-shape breaker opens
    and BOTH protocol sides degrade to the per-report CPU oracle — the
    multi-gadget scalar circuit — then collection decodes the fixed-point
    aggregate exactly once, elementwise-equal to the expected vector sum.
    (The fault fires BEFORE the launch's compile, so this case never pays
    XLA for the fpvec graphs — the bit-exact device-vs-oracle fuzz lives
    in tests/test_fpvec_device.py.)"""
    reset_global_executor()
    harness = ChaosHarness(
        n_tasks=1,
        vdaf={
            "type": "Prio3FixedPointBoundedL2VecSum",
            "bitsize": 16,
            "length": 2,
        },
    )
    # exactly representable at 2^-15 granularity: decoded sums are exact
    measurements = [[0.5, -0.25], [0.25, 0.25], [-0.5, 0.125]]

    async def flow():
        await harness.start()
        try:
            for m in measurements:
                await harness.upload(0, m)
            await asyncio.sleep(0.1)
            await harness.create_jobs()

            # every device launch loses a chip — the per-shape breaker
            # must open, then the oracle serves the rest of the run
            faults.configure(
                [FaultSpec("backend.device_lost", "error", 1.0)], seed=SEED
            )
            ex = harness.drivers[0]._executor
            for _ in range(40):
                await harness.drive_round()
                states = harness.agg_job_states()
                if states and all(s == "Finished" for s in states):
                    break
            states = harness.agg_job_states()
            assert states and all(s == "Finished" for s in states), states
            circuits = ex.circuit_stats()
            assert any(
                label.startswith("FixedPointBoundedL2VecSum")
                and s["trips"] >= 1
                for label, s in circuits.items()
            ), circuits
            assert faults.registry().hits.get("backend.device_lost", 0) > 0

            faults.clear()
            result = await harness.collect_task(0)
            assert result.report_count == len(measurements)
            expect = [
                sum(m[i] for m in measurements) for i in range(2)
            ]
            assert result.aggregate_result == expect, (
                result.aggregate_result,
                expect,
            )
        finally:
            faults.clear()
            await harness.stop()

    _run(flow(), timeout=280.0)
    reset_global_executor()


# -- connectivity fault modes (ISSUE 11) -------------------------------------


def test_reset_mode_raises_transport_shaped_error():
    """``reset`` impersonates a mid-exchange socket reset: the error is a
    ConnectionResetError (the peer-health tracker and retry loop classify
    it transport) AND a FaultInjectedError (chaos harnesses catch it)."""
    from janus_tpu.core.faults import FaultInjectedTransportError
    from janus_tpu.core.retries import is_transport_error

    faults.configure([FaultSpec("http.request", "reset", 1.0)], seed=SEED)
    with pytest.raises(FaultInjectedTransportError) as exc_info:
        faults.fire("http.request", target="http://peer:1/x")
    assert isinstance(exc_info.value, ConnectionResetError)
    assert is_transport_error(exc_info.value)


def test_target_scoped_specs_partition_one_direction():
    """The asymmetric-partition primitive: a spec targeting the helper's
    host:port fires ONLY for leader->helper traffic; helper->leader (a
    different target) and untargeted points flow — and the scoped spec's
    RNG is rolled only for matching calls, so the partitioned direction's
    decision sequence is independent of the healthy one's traffic."""
    from janus_tpu.core.faults import FaultInjectedTransportError

    faults.configure(
        [FaultSpec("http.request", "reset", 1.0, target="helper-host:81")],
        seed=SEED,
    )
    # leader -> helper: partitioned
    with pytest.raises(FaultInjectedTransportError):
        faults.fire("http.request", target="http://helper-host:81/tasks/t/x")
    # helper -> leader: flows
    faults.fire("http.request", target="http://leader-host:80/tasks/t/x")
    # a call site that passes no target never matches a scoped spec
    faults.fire("http.request")
    assert faults.registry().hits["http.request"] == 1
    # datastore tx points stay healthy during an http-scoped partition
    faults.fire("datastore.tx.begin")


def test_flap_schedule_determinism_under_seed():
    """Two schedules with one (seed, point) agree at every sample; a
    different seed diverges — a flapping-link chaos run replays."""
    from janus_tpu.core.faults import FlapSchedule

    grid = [i * 0.173 for i in range(200)]
    a = FlapSchedule(SEED, "http.request", 1.0)
    b = FlapSchedule(SEED, "http.request", 1.0)
    c = FlapSchedule(SEED + 1, "http.request", 1.0)
    sa = [a.up(t) for t in grid]
    assert sa == [b.up(t) for t in grid]
    assert sa != [c.up(t) for t in grid]
    # distinct specs on ONE point (salt = spec index) flap INDEPENDENTLY
    # — two target-scoped directions must not partition in lockstep
    d = FlapSchedule(SEED, "http.request", 1.0, salt=1)
    assert sa != [d.up(t) for t in grid]
    assert sa[0] is False, "phase 0 is DOWN: arming must not partition t=0"
    assert any(sa) and not all(sa), "both phases must occur"
    # transitions alternate (a schedule, not noise)
    flips = sum(1 for x, y in zip(sa, sa[1:]) if x != y)
    assert flips >= 2


def test_flap_spec_alternates_connectivity():
    """An armed flap spec produces BOTH outcomes over a few periods —
    injected resets while up, clean passes while down."""
    from janus_tpu.core.faults import FaultInjectedTransportError

    faults.configure(
        [FaultSpec("http.request", "flap", 1.0, flap_period_s=0.03)], seed=SEED
    )
    outcomes = set()
    deadline = _now() + 2.0
    while len(outcomes) < 2 and _now() < deadline:
        try:
            faults.fire("http.request", target="http://flappy:1/")
            outcomes.add("pass")
        except FaultInjectedTransportError:
            outcomes.add("reset")
        import time as _t

        _t.sleep(0.005)
    assert outcomes == {"pass", "reset"}, outcomes


def _now():
    import time as _t

    return _t.monotonic()


def test_snapshot_renders_target_scope_and_flap_period():
    faults.configure(
        [
            FaultSpec("http.request", "blackhole", 0.5, target="helper:99"),
            FaultSpec("http.request", "flap", 1.0, flap_period_s=2.5),
        ],
        seed=SEED,
    )
    snap = faults.snapshot()
    specs = snap["points"]["http.request"]
    assert specs[0] == {
        "mode": "blackhole",
        "probability": 0.5,
        "target": "helper:99",
    }
    assert specs[1] == {
        "mode": "flap",
        "probability": 1.0,
        "flap_period_s": 2.5,
    }


# -- helper-side split-brain: datastore down, HTTP up (ISSUE 11) --------------


def test_helper_datastore_unreachable_returns_503_with_retry_after():
    """A helper whose datastore is unreachable must answer DAP-retryable
    503 (+ Retry-After) — not 500 — so the leader's lease machinery
    redelivers instead of burning failure budget on the split-brain
    window."""
    from janus_tpu.aggregator import Aggregator, Config, aggregator_app
    from janus_tpu.datastore.test_util import EphemeralDatastore
    from janus_tpu.messages import TaskId

    eph = EphemeralDatastore()
    # exhaust the tx retry loop quickly: the 503 path is DatastoreError
    # escaping run_tx, and 30 retries of a p=1 begin fault take ~10s
    eph.datastore.max_transaction_retries = 2
    agg = Aggregator(eph.datastore, eph.clock, Config(vdaf_backend="oracle"))
    task_id = TaskId.random()

    async def flow():
        from aiohttp.test_utils import TestClient, TestServer

        client = TestClient(TestServer(aggregator_app(agg)))
        await client.start_server()
        try:
            faults.configure(
                [FaultSpec("datastore.tx.begin", "error", 1.0)], seed=SEED
            )
            resp = await client.get(f"/hpke_config?task_id={task_id}")
            assert resp.status == 503, await resp.text()
            assert resp.headers.get("Retry-After") == "5"
            # heal: the same request now reaches the handler (404 — the
            # task does not exist — proves the datastore answered)
            faults.clear()
            resp = await client.get(f"/hpke_config?task_id={task_id}")
            assert resp.status == 404, await resp.text()
        finally:
            faults.clear()
            await client.close()
            await agg.shutdown()
            eph.cleanup()

    _run(flow())


def test_helper_redelivery_after_503_is_exactly_once():
    """Post-heal duplicate redeliveries are FENCED, not assumed: an init
    request that 503s (datastore down mid-request, nothing committed)
    succeeds on redelivery, and a SECOND redelivery of the same body (the
    partition ate the leader's response) returns the stored response
    without double-accumulating — report counts stay exactly-once."""
    from test_aggregator_handlers import (
        AGG_TOKEN,
        NOW as HANDLER_NOW,
        TIME_PRECISION as HANDLER_PRECISION,
        leader_prep_inits,
        make_pair_tasks,
    )

    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.datastore.datastore import DatastoreError
    from janus_tpu.datastore.test_util import EphemeralDatastore
    from janus_tpu.messages import (
        AggregationJobId,
        AggregationJobInitializeReq,
        Interval,
        PartialBatchSelector,
    )

    eph = EphemeralDatastore(MockClock(HANDLER_NOW))
    eph.datastore.max_transaction_retries = 2
    agg = Aggregator(eph.datastore, eph.clock, Config(vdaf_backend="oracle"))
    leader, helper, _collector = make_pair_tasks({"type": "Prio3Count"})
    eph.datastore.run_tx("put", lambda tx: tx.put_aggregator_task(helper))
    vdaf = helper.vdaf_instance()
    measurements = (1, 0, 1)
    inits, _states, _reports = leader_prep_inits(vdaf, leader, helper, measurements)
    body = AggregationJobInitializeReq(
        aggregation_parameter=b"",
        partial_batch_selector=PartialBatchSelector.new_time_interval(),
        prepare_inits=inits,
    ).get_encoded()
    job_id = AggregationJobId.random()

    async def flow():
        # attempt 1: datastore down -> DatastoreError (503 at the HTTP
        # layer, test above) with NOTHING committed
        faults.configure([FaultSpec("datastore.tx.begin", "error", 1.0)], seed=SEED)
        with pytest.raises(DatastoreError):
            await agg.handle_aggregate_init(helper.task_id, job_id, body, AGG_TOKEN)
        faults.clear()
        # heal -> redelivery commits once
        resp = await agg.handle_aggregate_init(
            helper.task_id, job_id, body, AGG_TOKEN
        )
        # the response was lost to the partition -> the leader redelivers
        # the SAME body; the request-hash fence returns the stored resp
        resp2 = await agg.handle_aggregate_init(
            helper.task_id, job_id, body, AGG_TOKEN
        )
        assert resp2 == resp
        return resp

    try:
        resp = _run(flow())
        assert len(resp.prepare_resps) == len(measurements)
        ident = Interval(HANDLER_NOW, HANDLER_PRECISION).get_encoded()
        bas = eph.datastore.run_tx(
            "get",
            lambda tx: tx.get_batch_aggregations_for_batch(
                helper.task_id, ident, b""
            ),
        )
        assert sum(ba.report_count for ba in bas) == len(measurements), (
            "redelivery double-accumulated"
        )
    finally:
        faults.clear()
        _run(agg.shutdown())
        eph.cleanup()


# -- THE PARTITION SOAK (ISSUE 11 acceptance) ---------------------------------


@pytest.mark.slow
def test_partition_soak_asymmetric_heal_exactly_once():
    """./ci.sh chaos partition: mid-aggregation, the leader->helper
    direction is BLACKHOLED (target-scoped http.request spec — the
    helper's own datastore and the leader's local points stay healthy).
    During the partition: jobs quiesce by releasing with retryable
    jittered backoff (tiny max_step_attempts budget NOT consumed — zero
    abandonments), the executor breaker never trips (HTTP failure is not
    device sickness), and the deadline budget releases every lease
    in-band (zero expired-lease reaps; janus_job_leases_expired_total
    stays zero).  After the heal: every job finishes, collection counts
    are exactly-once against the oracle sums, and the soak's own SLO
    evaluation shows zero false breaches."""
    from urllib.parse import urlsplit

    from janus_tpu.core import peer_health
    from janus_tpu.core.metrics import GLOBAL_METRICS
    from janus_tpu.core.slo import SloEvaluator, targets_from_config

    reset_global_executor()
    harness = ChaosHarness(
        n_tasks=2,
        driver_overrides=dict(
            # a SMALL retryable budget is the teeth: the partition lasts
            # more deliveries than this, and zero jobs may abandon
            max_step_attempts=2,
            retry_initial_delay_s=1.0,
            retry_max_delay_s=4.0,
            peer_failure_threshold=2,
            peer_suspect_dwell_s=0.25,
            # per-attempt timeout: a blackholed attempt costs 1s, the
            # whole exchange <= ~3s — far inside the 60s lease.  The
            # budgets are deliberately LOAD-TOLERANT (the PR 14
            # concurrent-suite flake): on a saturated 2-core host a
            # HEALTHY in-process helper exchange can take >0.5s, and a
            # too-tight budget turns host load into transport failures
            # that keep the tracker suspect forever — the heal phase then
            # can never heal.
            http_retry=HttpRetryPolicy(
                0.001, 0.01, 2.0, 3.0, 3, attempt_timeout=1.0
            ),
        ),
    )
    measurements = {0: [1, 0, 1, 1], 1: [1, 1, 0, 1]}
    slo_eval = SloEvaluator(
        targets_from_config(
            {
                "commit_age": {"objective": 0.99, "threshold_s": 3600},
                "collection_e2e": {"objective": 0.95, "threshold_s": 21600},
            }
        )
    )
    slo_eval.tick()  # baseline before any traffic

    leases_expired_before = sum(
        GLOBAL_METRICS.get_sample_value(
            "janus_job_leases_expired_total", {"job_type": jt}
        )
        or 0
        for jt in ("aggregation", "collection")
    )

    async def flow():
        await harness.start()
        try:
            helper_netloc = urlsplit(
                harness.tasks[0][1].peer_aggregator_endpoint
            ).netloc
            for t, ms in measurements.items():
                for m in ms:
                    await harness.upload(t, m)
            await asyncio.sleep(0.1)
            await harness.create_jobs()

            # partition BEFORE the first helper exchange: Prio3Count's
            # init+continue completes in one step, so a "healthy round"
            # would finish every job — the jobs are created and
            # IN_PROGRESS (mid-aggregation) when the link goes dark
            # -- asymmetric partition: leader->helper blackholed --------
            faults.configure(
                [
                    FaultSpec(
                        "http.request",
                        "blackhole",
                        1.0,
                        target=helper_netloc,
                        hang_s=3600.0,
                    )
                ],
                seed=SEED,
            )
            ex = harness.drivers[0]._executor

            def reap():
                return harness.leader_ds.datastore.run_tx(
                    "reap", lambda tx: tx.reap_expired_aggregation_job_leases()
                )

            # EVIDENCE-DRIVEN partition phase (the PR 14 concurrent-suite
            # flake fix): a FIXED round count raced the wall-clock
            # machinery it depends on — the REAL-time suspect dwell gates
            # job acquisition, so on a loaded 2-core host six quick rounds
            # could all land inside one dwell window and leave
            # lease_attempts at the budget (or the tracker one failure
            # short of a suspect transition).  Drive rounds until the
            # budget-bypass evidence exists — deliveries PAST
            # max_step_attempts=2 AND an observed suspect transition — or
            # a generous real-time cap expires (the assertions below then
            # fail with the same diagnostics as before).  The
            # load-independent invariants (zero abandons, zero reaps) are
            # asserted every round regardless of pacing.
            reaped_total = 0
            min_rounds, rounds = 6, 0

            def partition_evidence():
                stats = peer_health.tracker().stats().get(helper_netloc, {})
                if stats.get("suspect_transitions", 0) < 1:
                    return False
                got = _sql_scalar(
                    harness.leader_ds.path,
                    "SELECT MAX(lease_attempts) FROM aggregation_jobs",
                )
                return (got or 0) > 2

            partition_deadline = time.monotonic() + 120.0
            while True:
                await harness.drive_round()
                rounds += 1
                # the deadline budget must have released every lease
                # in-band: nothing is ever left for the reaper
                reaped_total += reap()
                states = harness.agg_job_states()
                assert "Abandoned" not in states, (
                    "partition pressure consumed the attempt budget",
                    states,
                )
                assert reaped_total == 0, (
                    f"{reaped_total} lease(s) expired under partition — "
                    "the deadline budget failed to release first"
                )
                if rounds >= min_rounds and partition_evidence():
                    break
                if time.monotonic() > partition_deadline:
                    break
                # real time between rounds: the suspect dwell (0.25s) must
                # be able to elapse so probing re-acquisitions happen even
                # when the rounds themselves run fast
                await asyncio.sleep(0.05)
            states = harness.agg_job_states()
            assert states, "jobs must exist"
            assert not all(s == "Finished" for s in states), (
                "partition had no effect?",
                states,
            )
            # the breaker is a DEVICE verdict: HTTP partition must not trip it
            assert all(
                s["trips"] == 0 for s in ex.circuit_stats().values()
            ), ex.circuit_stats()
            # the tracker saw the partition
            stats = peer_health.tracker().stats()
            assert stats[helper_netloc]["suspect_transitions"] >= 1, stats
            assert (
                GLOBAL_METRICS.get_sample_value(
                    "janus_peer_transport_failures_total",
                    {"peer": helper_netloc},
                )
                > 0
            )
            # the budget bypass was genuinely exercised: deliveries went
            # PAST max_step_attempts=2 without abandoning
            max_attempts = _sql_scalar(
                harness.leader_ds.path,
                "SELECT MAX(lease_attempts) FROM aggregation_jobs",
            )
            assert max_attempts > 2, (
                "partition too short to prove the budget bypass",
                max_attempts,
            )

            # -- heal ---------------------------------------------------
            faults.clear()
            await asyncio.sleep(0.3)  # past the suspect dwell
            # deadline-driven like the partition phase: rounds are cheap
            # once the peer is healthy, but the suspect->probing dwell is
            # REAL time — a fast round that lands inside the dwell window
            # acquires nothing, so give the loop wall-clock room instead
            # of a fixed round count
            heal_deadline = time.monotonic() + 90.0
            while True:
                await harness.drive_round()
                reaped_total += reap()
                states = harness.agg_job_states()
                if states and all(s == "Finished" for s in states):
                    break
                if time.monotonic() > heal_deadline:
                    break
                await asyncio.sleep(0.05)
            states = harness.agg_job_states()
            assert states and all(s == "Finished" for s in states), states
            assert reaped_total == 0
            # peer healed: the probe's success restored healthy
            assert (
                peer_health.tracker().stats()[helper_netloc]["state"]
                == "healthy"
            )

            # -- exactly-once collection --------------------------------
            for t, ms in measurements.items():
                result = await harness.collect_task(t)
                assert result.report_count == len(ms), (t, result)
                assert result.aggregate_result == sum(ms), (t, result)
        finally:
            faults.clear()
            await harness.stop()

    try:
        # generous guard: the evidence-driven partition phase may spend up
        # to its own 120s real-time cap on a loaded host before healing
        _run(flow(), timeout=420.0)

        # zero expired leases observable on the metric too (the soak's
        # replicas never left a lease to the reaper)
        leases_expired_after = sum(
            GLOBAL_METRICS.get_sample_value(
                "janus_job_leases_expired_total", {"job_type": jt}
            )
            or 0
            for jt in ("aggregation", "collection")
        )
        assert leases_expired_after == leases_expired_before

        # zero SLO false breaches from the partition
        verdict = slo_eval.tick()
        for slo in ("commit_age", "collection_e2e"):
            st = verdict[slo]
            assert st["events_total"] > 0, (slo, st)
            assert st["breaches"] == 0, (slo, st)
            for window in ("fast", "slow"):
                sample = GLOBAL_METRICS.get_sample_value(
                    "janus_slo_burn_rate", {"slo": slo, "window": window}
                )
                assert sample == 0.0, (slo, window, sample)
    finally:
        reset_global_executor()


@pytest.mark.slow
def test_partition_flap_soak_suspect_dwell_restart_exactly_once():
    """./ci.sh chaos partition, FLAPPING-LINK stage (ISSUE 13 satellite):
    instead of a clean blackhole, the leader->helper direction flaps on a
    deterministic schedule — while "up" (partitioned) exchanges RESET
    mid-flight, while "down" they flow.  Half-open probes land in both
    phases: a probe in an up phase fails and RESTARTS the suspect dwell,
    a probe in a down phase succeeds and heals — the tracker must ride
    the churn (several suspect transitions) without a single abandoned
    job or expired lease.  Once the link settles: every job finishes and
    collection counts are exactly-once."""
    from urllib.parse import urlsplit

    from janus_tpu.core import peer_health
    from janus_tpu.core.metrics import GLOBAL_METRICS

    reset_global_executor()
    harness = ChaosHarness(
        n_tasks=2,
        driver_overrides=dict(
            max_step_attempts=2,
            retry_initial_delay_s=1.0,
            retry_max_delay_s=2.0,
            peer_failure_threshold=1,
            peer_suspect_dwell_s=0.15,
            http_retry=HttpRetryPolicy(
                0.001, 0.01, 2.0, 0.2, 2, attempt_timeout=0.1
            ),
        ),
    )
    measurements = {0: [1, 0, 1, 1], 1: [1, 1, 0, 1]}
    leases_expired_before = sum(
        GLOBAL_METRICS.get_sample_value(
            "janus_job_leases_expired_total", {"job_type": jt}
        )
        or 0
        for jt in ("aggregation", "collection")
    )

    async def flow():
        await harness.start()
        try:
            helper_netloc = urlsplit(
                harness.tasks[0][1].peer_aggregator_endpoint
            ).netloc
            for t, ms in measurements.items():
                for m in ms:
                    await harness.upload(t, m)
            await asyncio.sleep(0.1)
            await harness.create_jobs()

            # -- flapping link: short phases, mid-exchange resets -------
            faults.configure(
                [
                    FaultSpec(
                        "http.request",
                        "flap",
                        1.0,
                        target=helper_netloc,
                        # phases of ~0.2-0.6s: wide enough that the >=1s
                        # redelivery cadence (step_retry_delay's floor)
                        # lands probes in BOTH phases over the churn window
                        flap_period_s=0.4,
                    )
                ],
                seed=SEED,
            )

            def reap():
                return harness.leader_ds.datastore.run_tx(
                    "reap", lambda tx: tx.reap_expired_aggregation_job_leases()
                )

            reaped_total = 0
            # churn window: up to ~8s of flapping (a dozen-plus up/down
            # phases) under SUSTAINED delivery pressure — fresh reports
            # keep arriving, so a down-phase heal is always followed by
            # up-phase traffic that re-suspects the peer (the dwell
            # restart this soak exists to exercise)
            for i in range(28):
                if i % 4 == 3:
                    for t in measurements:
                        await harness.upload(t, 1)
                        measurements[t].append(1)
                    await harness.create_jobs()
                await harness.drive_round()
                reaped_total += reap()
                await asyncio.sleep(0.25)
                stats = peer_health.tracker().stats()
                if (
                    stats.get(helper_netloc, {}).get("suspect_transitions", 0)
                    >= 2
                ):
                    break  # churn proven; don't stretch the soak
            states = harness.agg_job_states()
            assert states, "jobs must exist"
            assert "Abandoned" not in states, (
                "flap churn consumed the attempt budget",
                states,
            )
            assert reaped_total == 0, (
                f"{reaped_total} lease(s) expired under the flapping link"
            )
            stats = peer_health.tracker().stats()
            # the dwell-restart path under churn: the peer went suspect
            # MORE than once (fail -> dwell -> probe/heal -> fail again)
            assert stats[helper_netloc]["suspect_transitions"] >= 2, stats
            ex = harness.drivers[0]._executor
            assert all(
                s["trips"] == 0 for s in ex.circuit_stats().values()
            ), "a flapping HTTP link must never trip the DEVICE breaker"

            # -- link settles -------------------------------------------
            faults.clear()
            await asyncio.sleep(0.3)  # past the suspect dwell
            for _ in range(40):
                await harness.drive_round()
                reaped_total += reap()
                states = harness.agg_job_states()
                if states and all(s == "Finished" for s in states):
                    break
            states = harness.agg_job_states()
            assert states and all(s == "Finished" for s in states), states
            assert reaped_total == 0
            assert (
                peer_health.tracker().stats()[helper_netloc]["state"]
                == "healthy"
            )

            # -- exactly-once collection --------------------------------
            for t, ms in measurements.items():
                result = await harness.collect_task(t)
                assert result.report_count == len(ms), (t, result)
                assert result.aggregate_result == sum(ms), (t, result)
        finally:
            faults.clear()
            await harness.stop()

    try:
        _run(flow(), timeout=280.0)
        leases_expired_after = sum(
            GLOBAL_METRICS.get_sample_value(
                "janus_job_leases_expired_total", {"job_type": jt}
            )
            or 0
            for jt in ("aggregation", "collection")
        )
        assert leases_expired_after == leases_expired_before
    finally:
        reset_global_executor()


def _sql_scalar(path, query):
    conn = sqlite3.connect(path, timeout=10.0)
    try:
        return conn.execute(query).fetchone()[0]
    finally:
        conn.close()


def test_mesh_chaos_device_lost_opens_per_mesh_breaker_oracle_exact():
    """ISSUE 6 acceptance: with the MESH backend enabled
    (``device_executor.mesh: true`` — every cached backend upgraded to the
    SPMD MeshBackend over the 8 virtual CPU devices), a
    ``backend.device_lost`` injection (a chip dropping out of the mesh
    mid-launch) opens the PER-MESH circuit breaker, jobs degrade to the
    bit-exact CPU oracle, and collection still returns exactly-once
    counts."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device mesh conftest provisions")

    reset_global_executor()
    harness = ChaosHarness(n_tasks=1, mesh=True)
    measurements = [1, 0, 1, 1]

    async def flow():
        await harness.start()
        try:
            for m in measurements:
                await harness.upload(0, m)
            await asyncio.sleep(0.1)
            await harness.create_jobs()

            # Every mesh launch loses a device: the per-MESH breaker must
            # open (label carries the mesh device set, not a VDAF shape).
            faults.configure(
                [FaultSpec("backend.device_lost", "error", 1.0)], seed=SEED
            )
            ex = harness.drivers[0]._executor
            for _ in range(10):
                await harness.drive_round()
                if any(
                    s["state"] == "open" for s in ex.circuit_stats().values()
                ):
                    break
            circuits = ex.circuit_stats()
            assert any(
                label.startswith("mesh[") and s["trips"] >= 1
                for label, s in circuits.items()
            ), circuits
            assert faults.registry().hits.get("backend.device_lost", 0) > 0

            # With the circuit open (fault still armed — the mesh stays
            # "sick"), every job finishes on the CPU oracle: driver-side
            # via the breaker peek / CircuitOpenError fallback, helper-side
            # via the executor-path oracle re-entry.
            for _ in range(40):
                await harness.drive_round()
                states = harness.agg_job_states()
                if states and all(s == "Finished" for s in states):
                    break
            states = harness.agg_job_states()
            assert states and all(s == "Finished" for s in states), states

            # Exactly-once: the collected aggregate equals the true sum
            # with every report counted once, despite retries + fallback.
            faults.clear()
            result = await harness.collect_task(0)
            assert result.report_count == len(measurements), result
            assert result.aggregate_result == sum(measurements), result
        finally:
            faults.clear()
            await harness.stop()

    _run(flow(), timeout=280.0)
    reset_global_executor()
