"""Process-level crash/restart chaos (ISSUE 4 tentpole).

PR 2/3's chaos harness injects faults *in-process*; these tests kill and
restart whole replica PROCESSES, exercising the recovery machinery the
in-process soak cannot reach: lease expiry under real process death, the
lease reaper's prompt redelivery (``janus_job_leases_expired_total``),
graceful SIGTERM teardown (accumulator spill through the journal
transaction), and the datastore-persisted accumulator journal's
collection-time oracle replay for deltas that died resident on a
SIGKILLed replica's device.

Layers:

* ``test_killed_lease_holder_redelivers_with_attempts_preserved`` — a
  worker process acquires a lease and dies without releasing; after
  expiry the reaper counts it and a survivor reacquires with the
  ``lease_attempts`` accounting intact (the ``max_step_attempts`` budget
  survives holder death).
* ``test_collection_replica_sigkill_mid_replay_exactly_once`` (slow) —
  the COLLECTION driver's crash case (ISSUE 11, carried from the
  ROADMAP): aggregation runs in-process with the accumulator store in
  deferred mode, the executor is torn down drain-less (orphaning every
  job's journal rows), and a real ``collection_job_driver`` BINARY picks
  the collection job up with an ``accumulator.replay`` delay fault armed
  — it is SIGKILLed mid-journal-replay (zero rows consumed), and a
  clean replacement binary replays every orphan exactly once: journal
  drains to empty, the survivor's replay-consumed metric delta equals
  the orphaned row count, the collected result is unchanged, and the
  survivor's trace carries the collection_finish span.
* ``test_crash_restart_soak_exactly_once`` (slow) — THE ACCEPTANCE SOAK:
  a helper aggregator binary plus two aggregation-job-driver binaries
  (device executor + accumulator store in DEFERRED drain mode, device
  backend on a pinned CPU platform) share one datastore; replicas are
  SIGKILLed at seeded random points mid-step and restarted (>= 3
  cycles, ending with a double kill that guarantees a stranded lease);
  after convergence one replica exits via SIGTERM (graceful spill, exit
  code 0) and the other is SIGKILLed (orphaning journal rows), then the
  collection driver replays the orphans from the datastore and every
  seeded report is counted exactly once with aggregates bit-exact
  against the CPU oracle's sums.

Seeded via JANUS_CHAOS_SEED (./ci.sh chaos crash pins it).  The process
soak runs wherever ``cryptography`` is importable — the datastore's
pre-3.35-SQLite fallback paths (backend_sql.py) removed the RETURNING
requirement.
"""

from __future__ import annotations

import base64
import json
import multiprocessing as mp
import os
import pathlib
import random
import signal
import socket
import sqlite3
import subprocess
import sys
import time
import urllib.request

import pytest


from janus_tpu.core.hpke import HpkeApplicationInfo, HpkeKeypair, Label, open_
from janus_tpu.core.time import RealClock
from janus_tpu.datastore import (
    AggregatorTask,
    CollectionJob,
    CollectionJobState,
    Crypter,
    Datastore,
    LeaderStoredReport,
    TaskQueryType,
    generate_key,
)
from janus_tpu.core.auth_tokens import AuthenticationToken
from janus_tpu.messages import (
    AggregationJobId,
    AggregationJobStep,
    BatchSelector,
    CollectionJobId,
    Duration,
    Interval,
    PlaintextInputShare,
    Query,
    Role,
    TaskId,
    Time,
)

SEED = int(os.environ.get("JANUS_CHAOS_SEED", "7"))
REPO = pathlib.Path(__file__).resolve().parents[1]
TIME_PRECISION = Duration(3600)

#: -c bootstrap for replica binaries: pin jax to the CPU the way
#: conftest.py does, then enter the real multi-call entry point.  A chip
#: belongs to one process — these tests start several — and CPU-vs-device
#: parity is the backend contract anyway.
_BOOT = (
    "import os, sys;"
    "os.environ['JAX_PLATFORMS'] = 'cpu';"
    "from janus_tpu.binaries.main import main;"
    "sys.exit(main(sys.argv[1:]))"
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# lease-expiry redelivery across process death (ISSUE 4 satellite)


def _hold_lease_and_die(path: str, key: bytes) -> None:
    """Acquire a short lease, then die WITHOUT releasing (SIGKILL shape:
    os._exit skips every finally/atexit, like a kill -9 mid-step)."""
    ds = Datastore(path, Crypter([key]), RealClock())
    leases = ds.run_tx(
        "acquire",
        lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(2), 1),
    )
    os._exit(0 if len(leases) == 1 else 3)


def test_killed_lease_holder_redelivers_with_attempts_preserved(tmp_path):
    from tests.test_datastore import make_task

    key = generate_key()
    path = str(tmp_path / "lease.sqlite3")
    ds = Datastore(path, Crypter([key]), RealClock())
    task = make_task()
    ds.run_tx("put-task", lambda tx: tx.put_aggregator_task(task))
    from janus_tpu.datastore import AggregationJob, AggregationJobState

    job = AggregationJob(
        task_id=task.task_id,
        aggregation_job_id=AggregationJobId.random(),
        aggregation_parameter=b"",
        partial_batch_identifier=None,
        client_timestamp_interval=Interval(Time(0), Duration(1)),
        state=AggregationJobState.IN_PROGRESS,
        step=AggregationJobStep(0),
    )
    ds.run_tx("put-job", lambda tx: tx.put_aggregation_job(job))

    ctx = mp.get_context("spawn")
    p = ctx.Process(target=_hold_lease_and_die, args=(path, key))
    p.start()
    p.join(timeout=60)
    assert p.exitcode == 0

    # while the dead holder's lease is still valid, nothing to reap or acquire
    assert ds.run_tx("reap0", lambda tx: tx.reap_expired_aggregation_job_leases()) == 0
    assert (
        ds.run_tx(
            "acq0", lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(2), 1)
        )
        == []
    )
    time.sleep(2.5)  # past the 2s lease
    # the survivor's reaper counts exactly the expired-without-release lease
    assert ds.run_tx("reap1", lambda tx: tx.reap_expired_aggregation_job_leases()) == 1
    (lease,) = ds.run_tx(
        "acq1", lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 1)
    )
    # delivery accounting survives the holder's death: this is attempt 2,
    # so the max_step_attempts budget keeps counting across the crash
    assert lease.lease_attempts == 2
    assert lease.leased.aggregation_job_id == job.aggregation_job_id
    ds.close()


# ---------------------------------------------------------------------------
# THE SOAK


class _Replicas:
    """Spawn/kill/restart the replica binaries of one soak run."""

    def __init__(self, env, driver_cfgs, helper_cfg, log_dir):
        self.env = env
        self.driver_cfgs = driver_cfgs
        self.helper_cfg = helper_cfg
        self.log_dir = log_dir
        self.drivers = [None, None]
        self.helper = None
        self._log_seq = 0

    def _spawn(self, binary, cfg_path, tag):
        self._log_seq += 1
        log = open(self.log_dir / f"{tag}-{self._log_seq}.log", "wb")
        return subprocess.Popen(
            [sys.executable, "-c", _BOOT, binary, "--config-file", str(cfg_path)],
            env=self.env,
            cwd=str(REPO),
            stdout=log,
            stderr=subprocess.STDOUT,
        )

    def start_helper(self):
        self.helper = self._spawn("aggregator", self.helper_cfg, "helper")

    def start_driver(self, i):
        self.drivers[i] = self._spawn(
            "aggregation_job_driver", self.driver_cfgs[i], f"driver{i}"
        )

    def kill_driver(self, i):
        p = self.drivers[i]
        p.send_signal(signal.SIGKILL)
        p.wait(timeout=30)

    def terminate_all(self):
        for p in self.drivers + [self.helper]:
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=30)


def _wait_http(url: str, deadline_s: float) -> None:
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=2):
                return
        except Exception:
            time.sleep(0.25)
    raise TimeoutError(f"{url} never came up")


def _scrape(port: int) -> str:
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ) as r:
            return r.read().decode()
    except Exception:
        return ""


def _metric_total(text: str, name: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            try:
                total += float(line.rsplit(" ", 1)[1])
            except ValueError:
                pass
    return total


def _sql(path: str, query: str):
    conn = sqlite3.connect(path, timeout=10.0)
    try:
        return conn.execute(query).fetchall()
    finally:
        conn.close()


@pytest.mark.slow
def test_collection_replica_sigkill_mid_replay_exactly_once(tmp_path):
    """SIGKILL a collection replica MID-JOURNAL-REPLAY (ISSUE 11): the
    replay's exactly-once fence is the row DELETE inside the merge tx,
    so a replica killed between recompute start and commit must consume
    nothing — and the replacement replica must then consume EVERY
    orphaned row exactly once.  Asserted via the journal gauges (the
    dying replica's /statusz shows the orphans, the survivor's /metrics
    replay counter moves by exactly the orphan count, the table drains
    to empty), the collected result (bit-exact Prio3Count sums), and the
    survivor's merged trace carrying the collection_finish span."""
    import asyncio
    import urllib.parse

    from test_chaos import NOW, TIME_PRECISION, ChaosHarness

    from janus_tpu.core import faults
    from janus_tpu.executor import reset_global_executor

    faults.clear()
    reset_global_executor()
    harness = ChaosHarness(n_tasks=2, deferred=True)
    measurements = {0: [1, 0, 1, 1], 1: [1, 1, 0, 1]}
    coll_health = [_free_port(), _free_port()]

    def _replica_yaml(i, with_fault):
        fault = (
            """
  fault_injection:
    enabled: true
    seed: %d
    points:
      accumulator.replay: {mode: delay, probability: 1.0, delay_s: 600}
"""
            % SEED
        )
        return f"""
common:
  database: {{path: {harness.leader_ds.path}}}
  health_check_listen_address: 127.0.0.1:{coll_health[i]}
  chrome_trace_path: {tmp_path}/trace-coll{i}.json
  status_sample_interval_s: 0.5{fault if with_fault else ''}
job_driver:
  job_discovery_interval_s: 0.2
  max_concurrent_job_workers: 2
  worker_lease_duration_s: 5
  worker_lease_clock_skew_allowance_s: 1
  maximum_attempts_before_failure: 100000
  max_step_attempts: 100000
  lease_reap_interval_s: 0.1
"""

    cfg_paths = []
    for i, with_fault in enumerate((True, False)):
        p = tmp_path / f"coll{i}.yaml"
        p.write_text(_replica_yaml(i, with_fault))
        cfg_paths.append(p)

    env = dict(os.environ)
    env["DATASTORE_KEYS"] = (
        base64.urlsafe_b64encode(harness.leader_ds.key).decode().rstrip("=")
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")

    def _spawn_coll(i):
        log = open(tmp_path / f"coll{i}.log", "wb")
        return subprocess.Popen(
            [
                sys.executable,
                "-c",
                _BOOT,
                "collection_job_driver",
                "--config-file",
                str(cfg_paths[i]),
            ],
            env=env,
            cwd=str(REPO),
            stdout=log,
            stderr=subprocess.STDOUT,
        )

    def _journal_rows():
        return _sql(
            harness.leader_ds.path, "SELECT COUNT(*) FROM accumulator_journal"
        )[0][0]

    async def _statusz(port):
        def get():
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/statusz", timeout=5
            ) as r:
                return json.loads(r.read().decode())

        return await asyncio.get_running_loop().run_in_executor(None, get)

    procs = [None, None]

    async def flow():
        from janus_tpu.messages import Interval, Query

        await harness.start()
        results = {}
        try:
            # -- in-process aggregation, deferred store -> journal rows -
            for t, ms in measurements.items():
                for m in ms:
                    await harness.upload(t, m)
            await asyncio.sleep(0.1)
            await harness.create_jobs()
            for _ in range(30):
                await harness.drive_round()
                states = harness.agg_job_states()
                if states and all(s == "Finished" for s in states):
                    break
            states = harness.agg_job_states()
            assert states and all(s == "Finished" for s in states), states

            orphans = _journal_rows()
            assert orphans > 0, "deferred store journaled nothing to orphan"
            # CRASH: the executor (and the resident deltas) die drain-less
            # — the journal rows are now recoverable ONLY by replay
            reset_global_executor()

            # -- collection jobs for both tasks -------------------------
            interval = Interval(NOW, TIME_PRECISION)
            jobs = {}
            for t, (task_id, _lt, _ht) in enumerate(harness.tasks):
                job = CollectionJob(
                    task_id=task_id,
                    collection_job_id=CollectionJobId.random(),
                    query=Query.new_time_interval(interval),
                    aggregation_parameter=b"",
                    batch_identifier=interval.get_encoded(),
                    state=CollectionJobState.START,
                )
                harness.leader_ds.datastore.run_tx(
                    "putc", lambda tx, j=job: tx.put_collection_job(j)
                )
                jobs[t] = job

            # -- replica 1: wedged mid-replay, then SIGKILLed -----------
            procs[0] = _spawn_coll(0)
            await asyncio.get_running_loop().run_in_executor(
                None,
                lambda: _wait_http(
                    f"http://127.0.0.1:{coll_health[0]}/healthz", 120
                ),
            )
            deadline = time.monotonic() + 120
            while True:
                doc = await _statusz(coll_health[0])
                if doc["faults"]["hits"].get("accumulator.replay", 0) >= 1:
                    break
                assert time.monotonic() < deadline, "replay fault never fired"
                await asyncio.sleep(0.2)
            # the dying replica's own gauge SEES the orphans (journal
            # section is served straight off the shared datastore)
            assert doc["journal"]["outstanding_rows"] == orphans, doc["journal"]
            # give one step-timeout cycle so the replica completes (and
            # traces) at least one wedged job_step before dying
            await asyncio.sleep(5.0)
            procs[0].send_signal(signal.SIGKILL)
            await asyncio.get_running_loop().run_in_executor(
                None, lambda: procs[0].wait(timeout=30)
            )
            assert _journal_rows() == orphans, (
                "a replica killed mid-replay must consume NOTHING"
            )

            # -- replica 2: clean replay, exactly once ------------------
            procs[1] = _spawn_coll(1)
            await asyncio.get_running_loop().run_in_executor(
                None,
                lambda: _wait_http(
                    f"http://127.0.0.1:{coll_health[1]}/healthz", 120
                ),
            )
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                done = {}
                for t, job in jobs.items():
                    got = await harness.leader_ds.datastore.run_tx_async(
                        "getc",
                        lambda tx, j=job: tx.get_collection_job(
                            j.task_id, j.collection_job_id, "TimeInterval"
                        ),
                    )
                    if got is not None and got.state == CollectionJobState.FINISHED:
                        done[t] = got
                if len(done) == len(jobs):
                    results = done
                    break
                await asyncio.sleep(0.5)
            assert len(results) == len(jobs), "collection never finished"

            # journal drained to empty; the survivor's replay-consumed
            # metric delta equals the orphaned row count
            assert _journal_rows() == 0
            scraped = await asyncio.get_running_loop().run_in_executor(
                None, lambda: _scrape(coll_health[1])
            )
            replayed = _metric_total(
                scraped, 'janus_accumulator_journal_consumed_total{path="replay"}'
            )
            assert replayed == orphans, (replayed, orphans)
            # and the survivor's sampled gauge agrees once a tick lands
            deadline = time.monotonic() + 30
            while True:
                doc = await _statusz(coll_health[1])
                if doc["journal"]["outstanding_rows"] == 0:
                    break
                assert time.monotonic() < deadline, doc["journal"]
                await asyncio.sleep(0.3)
            # graceful SIGTERM for the survivor: _close_tracing flushes
            # its chrome trace (the collection_finish span asserted below)
            procs[1].send_signal(signal.SIGTERM)
            assert (
                await asyncio.get_running_loop().run_in_executor(
                    None, lambda: procs[1].wait(timeout=120)
                )
                == 0
            ), "survivor SIGTERM exit must be clean"
        finally:
            for p in procs:
                if p is not None and p.poll() is None:
                    p.kill()
                    p.wait(timeout=30)
            await harness.stop()
        return results

    loop = asyncio.new_event_loop()
    try:
        results = loop.run_until_complete(asyncio.wait_for(flow(), 600))
    finally:
        loop.close()
        reset_global_executor()

    # -- collection results unchanged by the crash/replay dance ---------
    from janus_tpu.messages import AggregateShareAad, Interval as _Interval

    interval = _Interval(NOW, TIME_PRECISION)
    for t, (task_id, leader_task, _h) in enumerate(harness.tasks):
        got = results[t]
        vdaf = leader_task.vdaf_instance()
        field = vdaf.field_for_agg_param(vdaf.decode_agg_param(b""))
        leader_share = field.decode_vec(got.leader_aggregate_share)
        aad = AggregateShareAad(
            task_id, b"", BatchSelector.new_time_interval(interval)
        ).get_encoded()
        info = HpkeApplicationInfo.new(
            Label.AGGREGATE_SHARE, Role.HELPER, Role.COLLECTOR
        )
        helper_share = field.decode_vec(
            open_(harness.collector_keys, info, got.helper_aggregate_share, aad)
        )
        result = vdaf.unshard([leader_share, helper_share], got.report_count)
        assert got.report_count == len(measurements[t]), (t, got.report_count)
        assert result == sum(measurements[t]), (t, result, measurements[t])

    # -- the survivor's trace carries the collection close-out ----------
    from tools.trace_merge import load_events, merge_trace_files

    survivor_trace = str(tmp_path / "trace-coll1.json")
    assert os.path.exists(survivor_trace)
    events = load_events(survivor_trace)
    finishes = [
        e for e in events if e.get("ph") == "X" and e["name"] == "collection_finish"
    ]
    assert len(finishes) == len(harness.tasks), (
        "one collection_finish per task expected",
        [e.get("name") for e in events],
    )
    # each close-out links the collected reports' upload-minted trace ids
    assert all(e["args"].get("links") for e in finishes), finishes
    # both incarnations' files merge onto one timeline (the SIGKILLed
    # replica's partial file must not poison the merge)
    summary = merge_trace_files(
        [str(tmp_path / "trace-coll0.json"), survivor_trace],
        str(tmp_path / "merged-coll-trace.json"),
    )
    assert os.path.exists(tmp_path / "merged-coll-trace.json"), summary


@pytest.mark.slow
def test_crash_restart_soak_exactly_once(tmp_path):
    from janus_tpu.aggregator import AggregationJobCreator, CreatorConfig
    from janus_tpu.client import prepare_report
    from janus_tpu.messages import InputShareAad

    rng = random.Random(SEED)
    key = generate_key()
    leader_db = str(tmp_path / "leader.sqlite3")
    helper_db = str(tmp_path / "helper.sqlite3")
    helper_port = _free_port()
    helper_health = _free_port()
    driver_health = [_free_port(), _free_port()]

    # -- seed both stores ---------------------------------------------------
    clock = RealClock()
    leader_ds = Datastore(leader_db, Crypter([key]), clock)
    helper_ds = Datastore(helper_db, Crypter([key]), clock)
    agg_token = AuthenticationToken.new_bearer("agg-token-crash")
    collector_keys = HpkeKeypair.generate(9)
    now = clock.now()
    report_time = Time(now.seconds - now.seconds % TIME_PRECISION.seconds)
    interval = Interval(report_time, TIME_PRECISION)

    n_tasks = 2
    measurements = {t: [(i + t) % 2 for i in range(12)] for t in range(n_tasks)}
    #: field sum of every seeded report's LEADER out share, straight off
    #: the CPU oracle — the collection's leader aggregate share must be
    #: bit-exact against this no matter which recovery paths fired
    expected_leader_shares = {}
    tasks = []
    keypairs = []
    for t in range(n_tasks):
        task_id = TaskId.random()
        common = dict(
            task_id=task_id,
            query_type=TaskQueryType.time_interval(),
            vdaf={"type": "Prio3Count"},
            vdaf_verify_key=bytes([0x40 + t]) * 16,
            min_batch_size=3,
            time_precision=TIME_PRECISION,
            collector_hpke_config=collector_keys.config,
        )
        leader_kp, helper_kp = HpkeKeypair.generate(1), HpkeKeypair.generate(2)
        leader_task = AggregatorTask(
            peer_aggregator_endpoint=f"http://127.0.0.1:{helper_port}/",
            role=Role.LEADER,
            aggregator_auth_token=agg_token,
            hpke_keys=[leader_kp],
            **common,
        )
        helper_task = AggregatorTask(
            peer_aggregator_endpoint="http://127.0.0.1:1/",  # never called
            role=Role.HELPER,
            aggregator_auth_token_hash=agg_token.hash(),
            hpke_keys=[helper_kp],
            **common,
        )
        leader_ds.run_tx("putl", lambda tx, lt=leader_task: tx.put_aggregator_task(lt))
        helper_ds.run_tx("puth", lambda tx, ht=helper_task: tx.put_aggregator_task(ht))
        tasks.append((task_id, leader_task, helper_task))
        keypairs.append((leader_kp, helper_kp))
        expected_leader_shares[t] = None

    # -- Poplar1 traffic in the soak (ISSUE 10): a heavy-hitters task rides
    # the same kill/restart schedule — its two-round jobs step through the
    # driver binaries' executor-routed poplar_init path, its level-keyed
    # deltas journal in the deferred store, and the SIGKILL orphans replay
    # at collection exactly like Prio3's.
    from janus_tpu.vdaf.poplar1 import Poplar1AggregationParam

    POPLAR_T = n_tasks  # tasks[2]
    poplar_param = Poplar1AggregationParam(1, (0, 1, 2, 3))
    poplar_task_id = TaskId.random()
    poplar_common = dict(
        task_id=poplar_task_id,
        query_type=TaskQueryType.time_interval(),
        vdaf={"type": "Poplar1", "bits": 4},
        vdaf_verify_key=bytes([0x40 + POPLAR_T]) * 16,
        min_batch_size=3,
        time_precision=TIME_PRECISION,
        collector_hpke_config=collector_keys.config,
    )
    poplar_leader_kp, poplar_helper_kp = HpkeKeypair.generate(1), HpkeKeypair.generate(2)
    poplar_leader_task = AggregatorTask(
        peer_aggregator_endpoint=f"http://127.0.0.1:{helper_port}/",
        role=Role.LEADER,
        aggregator_auth_token=agg_token,
        hpke_keys=[poplar_leader_kp],
        **poplar_common,
    )
    poplar_helper_task = AggregatorTask(
        peer_aggregator_endpoint="http://127.0.0.1:1/",
        role=Role.HELPER,
        aggregator_auth_token_hash=agg_token.hash(),
        hpke_keys=[poplar_helper_kp],
        **poplar_common,
    )
    leader_ds.run_tx("putl", lambda tx: tx.put_aggregator_task(poplar_leader_task))
    helper_ds.run_tx("puth", lambda tx: tx.put_aggregator_task(poplar_helper_task))
    tasks.append((poplar_task_id, poplar_leader_task, poplar_helper_task))
    keypairs.append((poplar_leader_kp, poplar_helper_kp))
    expected_leader_shares[POPLAR_T] = None
    measurements[POPLAR_T] = [0b1011, 0b1011, 0b0100, 0b1111, 0b0000, 0b0100]

    def agg_param_enc(t):
        if t == POPLAR_T:
            return tasks[t][1].vdaf_instance().encode_agg_param(poplar_param)
        return b""

    from janus_tpu.core.metrics import GLOBAL_METRICS
    from janus_tpu.core.trace import close_chrome_trace, configure_chrome_trace
    from janus_tpu.vdaf.backend import OracleBackend

    commit_age_count_before = (
        GLOBAL_METRICS.get_sample_value("janus_report_commit_age_seconds_count")
        or 0
    )

    # This process is the soak's CLIENT-INGRESS + COLLECTION replica: the
    # real upload writer and the collection driver both run here, so its
    # trace file carries the upload_commit spans (upload-minted trace
    # ids), the creator's job_create LINK spans, and collection_finish —
    # the pieces trace_merge --stats stitches onto the driver/helper
    # binaries' timelines (ISSUE 9 acceptance).
    client_trace = str(tmp_path / "trace-client.json")
    configure_chrome_trace(client_trace)

    # SLO evaluation plane (ISSUE 9): judge the soak's own traffic.  The
    # commit-age and collection-e2e histograms live in THIS process (the
    # writer and collection driver run here); targets are generous enough
    # that chaos must produce ZERO false breaches.
    from janus_tpu.core.slo import SloEvaluator, targets_from_config

    slo_eval = SloEvaluator(
        targets_from_config(
            {
                "commit_age": {"objective": 0.99, "threshold_s": 3600},
                "collection_e2e": {"objective": 0.95, "threshold_s": 21600},
            }
        )
    )
    slo_eval.tick()  # baseline snapshot before any traffic

    def seed_report(t, m):
        task_id, leader_task, _h = tasks[t]
        leader_kp, helper_kp = keypairs[t]
        vdaf = leader_task.vdaf_instance()
        report = prepare_report(
            vdaf,
            task_id,
            leader_kp.config,
            helper_kp.config,
            TIME_PRECISION,
            m,
            time=report_time,
        )
        # store the leader share the way handle_upload does: HPKE-open
        # our own ciphertext, keep the helper's sealed
        aad = InputShareAad(task_id, report.metadata, report.public_share).get_encoded()
        info = HpkeApplicationInfo.new(Label.INPUT_SHARE, Role.CLIENT, Role.LEADER)
        plain = PlaintextInputShare.get_decoded(
            open_(leader_kp, info, report.leader_encrypted_input_share, aad)
        )
        stored = LeaderStoredReport(
            task_id=task_id,
            metadata=report.metadata,
            public_share=report.public_share,
            leader_extensions=[],
            leader_input_share=plain.payload,
            helper_encrypted_input_share=report.helper_encrypted_input_share,
        )
        # commit through the REAL upload writer (not a bare put): the
        # batch-commit path is what populates the freshness histogram
        # (janus_report_commit_age_seconds) the acceptance asserts on
        import asyncio as _asyncio

        from janus_tpu.aggregator.report_writer import ReportWriteBatcher

        _asyncio.run(
            ReportWriteBatcher(leader_ds, max_batch_size=1).write_report(stored)
        )
        prep_row = (
            report.metadata.report_id.data,
            vdaf.decode_public_share(report.public_share),
            vdaf.decode_input_share(0, plain.payload),
        )
        if t == POPLAR_T:
            # heavy hitters: the leader out share at the collection level
            # is the prefix-value vector (state.y_flat)
            state, _sh = vdaf.prep_init(
                leader_task.vdaf_verify_key, 0, poplar_param, *prep_row
            )
            out_share = list(state.y_flat)
            field = vdaf.field_for_agg_param(poplar_param)
        else:
            (outcome,) = OracleBackend(vdaf).prep_init_batch(
                leader_task.vdaf_verify_key, 0, [prep_row]
            )
            out_share = list(outcome[0].out_share)
            field = vdaf.field_for_agg_param(vdaf.decode_agg_param(b""))
        prev = expected_leader_shares[t]
        expected_leader_shares[t] = (
            out_share if prev is None else field.vec_add(prev, out_share)
        )

    for t in measurements:
        for m in measurements[t]:
            seed_report(t, m)

    import asyncio

    creator = AggregationJobCreator(
        leader_ds,
        CreatorConfig(min_aggregation_job_size=1, max_aggregation_job_size=3),
    )

    def create_poplar_jobs():
        """Agg-param jobs come from collection requests, not the periodic
        creator — drive the production path (_create_agg_param_jobs, job
        size 3) directly so the soak's Poplar1 jobs are created exactly
        the way handle_create_collection_job creates them."""
        from janus_tpu.aggregator import Aggregator, Config
        from janus_tpu.aggregator.aggregator import TaskAggregator

        agg = Aggregator(
            leader_ds, clock, Config(vdaf_backend="oracle", max_agg_param_job_size=3)
        )
        ta = TaskAggregator(poplar_leader_task, "oracle")
        before = len(
            leader_ds.run_tx(
                "jobs",
                lambda tx: tx.get_aggregation_jobs_for_task(poplar_task_id),
            )
        )
        leader_ds.run_tx(
            "poplar_jobs",
            lambda tx: agg._create_agg_param_jobs(
                tx, ta, interval.get_encoded(), agg_param_enc(POPLAR_T)
            ),
        )
        return (
            len(
                leader_ds.run_tx(
                    "jobs",
                    lambda tx: tx.get_aggregation_jobs_for_task(poplar_task_id),
                )
            )
            - before
        )

    n_jobs = asyncio.run(creator.run_once())
    assert n_jobs >= 2 * n_tasks, n_jobs
    n_poplar_jobs = create_poplar_jobs()
    assert n_poplar_jobs == 2, n_poplar_jobs  # 6 reports / job size 3
    n_jobs += n_poplar_jobs

    # -- replica configs ----------------------------------------------------
    def driver_yaml(i):
        return f"""
common:
  database: {{path: {leader_db}}}
  health_check_listen_address: 127.0.0.1:{driver_health[i]}
  chrome_trace_path: {tmp_path}/trace-driver{i}.json
  status_sample_interval_s: 0.5
  otlp_endpoint: http://127.0.0.1:1
  slos:
    job_age_at_acquire: {{objective: 0.9, threshold_s: 1800}}
  # fleet mode ON in the crash soak (ISSUE 16 acceptance): stable
  # per-slot replica ids so a SIGKILL/restart re-owns its tasks (and its
  # warm caches) instead of reshuffling; a short TTL so the kill windows
  # exercise real migrations; routing must never cost exactly-once or
  # convergence
  fleet:
    enabled: true
    replica_id: crash-r{i}
    heartbeat_interval_s: 0.5
    heartbeat_ttl_s: 3.0
    takeover_grace_s: 0.5
job_driver:
  job_discovery_interval_s: 0.2
  max_concurrent_job_workers: 4
  worker_lease_duration_s: 5
  worker_lease_clock_skew_allowance_s: 1
  maximum_attempts_before_failure: 100000
  max_step_attempts: 100000
  retry_initial_delay_s: 1.0
  retry_max_delay_s: 2.0
  lease_reap_interval_s: 0.1
vdaf_backend: tpu
# the drivers walk Poplar1 on the jitted device kernel with DEFERRED
# drains: sketch refs are minted on device, cross the WAITING_LEADER
# persistence hop, and DIE with every SIGKILL — the soak then proves the
# dead-ref recovery story end to end (retained payloads -> per-report
# oracle replay; journal rows -> collection-time replay, exactly once)
poplar_backend: jax
device_executor:
  enabled: true
  flush_window_ms: 20
  flush_max_rows: 4096
  breaker_failure_threshold: 0
  accumulator:
    enabled: true
    byte_budget: 256
    drain_interval_s: 3600
"""

    helper_yaml = f"""
common:
  database: {{path: {helper_db}}}
  health_check_listen_address: 127.0.0.1:{helper_health}
  chrome_trace_path: {tmp_path}/trace-helper.json
  status_sample_interval_s: 0.5
listen_address: 127.0.0.1:{helper_port}
vdaf_backend: tpu
device_executor:
  enabled: true
  flush_window_ms: 20
  flush_max_rows: 4096
  breaker_failure_threshold: 0
  accumulator:
    enabled: true
    byte_budget: 256
"""
    cfg_paths = []
    for i in range(2):
        p = tmp_path / f"driver{i}.yaml"
        p.write_text(driver_yaml(i))
        cfg_paths.append(p)
    helper_cfg = tmp_path / "helper.yaml"
    helper_cfg.write_text(helper_yaml)

    env = dict(os.environ)
    env["DATASTORE_KEYS"] = base64.urlsafe_b64encode(key).decode().rstrip("=")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")

    reps = _Replicas(env, cfg_paths, helper_cfg, tmp_path)
    try:
        reps.start_helper()
        _wait_http(f"http://127.0.0.1:{helper_health}/healthz", 120)
        for i in range(2):
            reps.start_driver(i)
        for i in range(2):
            _wait_http(f"http://127.0.0.1:{driver_health[i]}/healthz", 120)

        def leased_count():
            return _sql(
                leader_db,
                "SELECT COUNT(*) FROM aggregation_jobs"
                " WHERE lease_token IS NOT NULL AND state = 'InProgress'",
            )[0][0]

        def unfinished_count():
            return _sql(
                leader_db,
                "SELECT COUNT(*) FROM aggregation_jobs WHERE state = 'InProgress'",
            )[0][0]

        def wait_for_lease(deadline_s=120):
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                if leased_count() > 0:
                    return True
                if unfinished_count() == 0:
                    return False  # converged before a lease appeared
                time.sleep(0.05)
            raise TimeoutError("no lease ever appeared")

        # -- >= 3 seeded SIGKILL/restart cycles mid-step --------------------
        kills = 0
        for cycle in range(2):
            time.sleep(rng.uniform(0.3, 1.2))
            if not wait_for_lease():
                break
            victim = rng.randrange(2)
            reps.kill_driver(victim)
            kills += 1
            reps.start_driver(victim)
        # final cycle: a DOUBLE kill with a lease outstanding guarantees
        # the holder died mid-step — the restarted replicas' reaper must
        # observe at least one expired-without-release lease
        if wait_for_lease():
            reps.kill_driver(0)
            reps.kill_driver(1)
            kills += 2
            reps.start_driver(0)
            reps.start_driver(1)
        assert kills >= 3, f"only {kills} kill/restart cycles ran"
        for i in range(2):
            _wait_http(f"http://127.0.0.1:{driver_health[i]}/healthz", 120)

        # /statusz consistent after recovery: a freshly restarted replica
        # serves every introspection section (ISSUE 5 acceptance).  The
        # health server comes up a beat before the sampler's first tick,
        # so poll briefly until the SLO evaluator has ticked (0.5s cadence).
        deadline = time.monotonic() + 30
        while True:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{driver_health[0]}/statusz", timeout=10
            ) as r:
                statusz = json.loads(r.read().decode())
            if (
                statusz.get("slo", {}).get("ticks", 0) >= 1
                or time.monotonic() > deadline
            ):
                break
            time.sleep(0.2)
        for section in (
            "executor",
            "accumulator",
            "journal",
            "leases",
            "faults",
            "otlp",
            "slo",
        ):
            assert section in statusz, (section, statusz)
        assert statusz["executor"]["enabled"] is True
        assert statusz["leases"]["aggregation"]["active"] >= 0
        # OTLP configured but the SDK is absent on this container: the
        # replica started cleanly and says exactly why it exports nothing
        # (ISSUE 9 acceptance: the no-op path is first-class)
        assert statusz["otlp"]["state"] == "unavailable", statusz["otlp"]
        assert statusz["otlp"]["endpoint"] == "http://127.0.0.1:1"
        # the declarative SLO target from the replica config is armed and
        # its sampler-driven evaluator has ticked
        assert statusz["slo"]["targets"] == 1
        assert statusz["slo"]["ticks"] >= 1, statusz["slo"]
        assert "job_age_at_acquire" in statusz["slo"]["slos"]

        # -- convergence: every job terminal --------------------------------
        deadline = time.monotonic() + 420
        while time.monotonic() < deadline:
            if unfinished_count() == 0:
                break
            time.sleep(0.5)
        states = _sql(leader_db, "SELECT state, COUNT(*) FROM aggregation_jobs GROUP BY state")
        assert dict(states).get("InProgress", 0) == 0, states
        assert dict(states).get("Finished", 0) == n_jobs, (states, n_jobs)

        # acceptance: at least one expired-lease reacquisition observed
        expired = sum(
            _metric_total(_scrape(driver_health[i]), "janus_job_leases_expired_total")
            for i in range(2)
        )
        assert expired > 0, "no expired-lease reacquisition observed"

        # deferred drains (interval 1h) never fired: the journal must hold
        # outstanding rows for the committed-but-unspilled resident deltas
        journal_before = _sql(leader_db, "SELECT COUNT(*) FROM accumulator_journal")[0][0]
        assert journal_before > 0, "no outstanding journal rows to replay"

        # the live replica's /statusz journal section agrees with the
        # datastore (nothing is committing post-convergence, so the
        # outstanding-row count is stable)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{driver_health[1]}/statusz", timeout=10
        ) as r:
            statusz = json.loads(r.read().decode())
        assert statusz["journal"]["outstanding_rows"] == journal_before, statusz[
            "journal"
        ]

        # -- teardown: graceful SIGTERM (spill), then a GUARANTEED orphan ---
        reps.drivers[0].send_signal(signal.SIGTERM)
        assert reps.drivers[0].wait(timeout=120) == 0, "SIGTERM exit must be clean"

        # second wave: only driver1 remains, so every wave-2 job's journal
        # row is owned by driver1's live store — SIGKILLing it afterwards
        # deterministically orphans rows for the collection replay
        for t in range(n_tasks):
            for m in [1, 1, 0]:
                measurements[t].append(m)
                seed_report(t, m)
        # wave-2 Poplar1 reports: _create_agg_param_jobs' conflict-key
        # dedup must pick up ONLY the fresh reports for the new level job
        for m in [0b0100, 0b1111, 0b1011]:
            measurements[POPLAR_T].append(m)
            seed_report(POPLAR_T, m)
        n_jobs += asyncio.run(creator.run_once())
        wave2_poplar = create_poplar_jobs()
        assert wave2_poplar == 1, wave2_poplar
        n_jobs += wave2_poplar
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if unfinished_count() == 0:
                break
            time.sleep(0.5)
        assert unfinished_count() == 0, "wave-2 jobs never converged"
        reps.kill_driver(1)
        journal_after = _sql(leader_db, "SELECT COUNT(*) FROM accumulator_journal")[0][0]
        assert journal_after > 0, "the SIGKILLed replica must orphan journal rows"
    except BaseException:
        reps.terminate_all()
        configure_chrome_trace(None)
        raise

    # -- collection: replay the orphans, then exactness ---------------------
    import aiohttp

    from janus_tpu.aggregator.collection_job_driver import (
        CollectionDriverConfig,
        CollectionJobDriver,
    )

    async def collect():
        results = {}
        driver = CollectionJobDriver(
            leader_ds,
            aiohttp.ClientSession,
            CollectionDriverConfig(retry_initial_delay=Duration(1)),
        )
        try:
            for t, (task_id, leader_task, _h) in enumerate(tasks):
                job = CollectionJob(
                    task_id=task_id,
                    collection_job_id=CollectionJobId.random(),
                    query=Query.new_time_interval(interval),
                    aggregation_parameter=agg_param_enc(t),
                    batch_identifier=interval.get_encoded(),
                    state=CollectionJobState.START,
                )
                leader_ds.run_tx("putc", lambda tx, j=job: tx.put_collection_job(j))
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline:
                    leases = await leader_ds.run_tx_async(
                        "acqc",
                        lambda tx: tx.acquire_incomplete_collection_jobs(
                            Duration(600), 4
                        ),
                    )
                    for lease in leases:
                        await driver.step_collection_job(lease)
                    got = leader_ds.run_tx(
                        "getc",
                        lambda tx, j=job: tx.get_collection_job(
                            j.task_id, j.collection_job_id, "TimeInterval"
                        ),
                    )
                    if got.state == CollectionJobState.FINISHED:
                        results[t] = got
                        break
                    await asyncio.sleep(0.3)
                else:
                    raise TimeoutError(f"collection for task {t} never finished")
        finally:
            await driver.close()
        return results

    replay_before = (
        GLOBAL_METRICS.get_sample_value(
            "janus_accumulator_journal_consumed_total", {"path": "replay"}
        )
        or 0
    )
    e2e_before = (
        GLOBAL_METRICS.get_sample_value("janus_collection_e2e_seconds_count") or 0
    )

    try:
        results = asyncio.run(collect())

        from janus_tpu.messages import AggregateShareAad

        for t, (task_id, leader_task, _h) in enumerate(tasks):
            got = results[t]
            vdaf = leader_task.vdaf_instance()
            agg_param = vdaf.decode_agg_param(agg_param_enc(t))
            field = vdaf.field_for_agg_param(agg_param)
            leader_share = field.decode_vec(got.leader_aggregate_share)
            aad = AggregateShareAad(
                task_id, agg_param_enc(t), BatchSelector.new_time_interval(interval)
            ).get_encoded()
            info = HpkeApplicationInfo.new(
                Label.AGGREGATE_SHARE, Role.HELPER, Role.COLLECTOR
            )
            helper_share = field.decode_vec(
                open_(collector_keys, info, got.helper_aggregate_share, aad)
            )
            result = vdaf.unshard_with_param(
                agg_param, [leader_share, helper_share], got.report_count
            )
            # exactly-once: Prio3Count aggregation is exact, so equality
            # with the true count and sum IS the no-double/no-drop proof;
            # the leader share is additionally checked BIT-EXACT against
            # the CPU oracle's field sum (splits a leader-side recovery
            # bug from a helper-side one on failure)
            assert got.report_count == len(measurements[t]), (t, got.report_count)
            assert leader_share == expected_leader_shares[t], (
                t,
                "leader share deviates from the CPU oracle sum",
                leader_share,
                expected_leader_shares[t],
            )
            if t == POPLAR_T:
                # heavy-hitter counts: per-prefix totals at level 1
                expect = [0, 0, 0, 0]
                for m in measurements[t]:
                    expect[m >> 2] += 1
            else:
                expect = sum(measurements[t])
            assert result == expect, (t, result, expect, "helper side")

        # every orphaned journal row was consumed by the replay
        assert _sql(leader_db, "SELECT COUNT(*) FROM accumulator_journal")[0][0] == 0

        # -- ISSUE 5 acceptance: metric invariants + the merged trace -------
        # journal written == consumed, from metrics: the rows the SIGKILLed
        # replica wrote and never drained (journal_after of them) were each
        # consumed via the replay path — the replay counter moved by exactly
        # the orphan count, and with the table empty above, every row any
        # incarnation ever wrote was consumed by its drain or this replay.
        replay_delta = (
            GLOBAL_METRICS.get_sample_value(
                "janus_accumulator_journal_consumed_total", {"path": "replay"}
            )
            or 0
        ) - replay_before
        assert replay_delta == journal_after, (replay_delta, journal_after)

        # freshness histograms populated: one commit-age sample per seeded
        # report (the soak uploads through the real writer), and an
        # upload->collectable end-to-end sample per finished collection
        commit_age_delta = (
            GLOBAL_METRICS.get_sample_value("janus_report_commit_age_seconds_count")
            or 0
        ) - commit_age_count_before
        total_reports = sum(len(m) for m in measurements.values())
        assert commit_age_delta == total_reports, (commit_age_delta, total_reports)
        e2e_delta = (
            GLOBAL_METRICS.get_sample_value("janus_collection_e2e_seconds_count")
            or 0
        ) - e2e_before
        assert e2e_delta >= n_tasks, (e2e_delta, n_tasks)

        # -- ISSUE 9 acceptance: SLO self-evaluation over the soak ----------
        # The evaluator ticked a baseline before traffic; this tick sees
        # every commit-age and collection-e2e sample the soak produced.
        # Burn-rate samples must EXIST for both SLOs (the evaluator is
        # live) and read 0.0 — at these targets, chaos must not cost SLO
        # budget, so any breach is a false positive.
        slo_verdict = slo_eval.tick()
        for slo in ("commit_age", "collection_e2e"):
            st = slo_verdict[slo]
            assert st["events_total"] > 0, (slo, st)
            for window in ("fast", "slow"):
                sample = GLOBAL_METRICS.get_sample_value(
                    "janus_slo_burn_rate", {"slo": slo, "window": window}
                )
                assert sample is not None, (slo, window)
                assert sample == 0.0, (slo, window, sample)
            assert st["breaches"] == 0, (slo, st)
            assert (
                GLOBAL_METRICS.get_sample_value(
                    "janus_slo_breach_total", {"slo": slo}
                )
                or 0
            ) == 0
        # the evaluator saw every sample the soak committed (events_total
        # is the histogram's absolute count; the soak added exactly
        # commit_age_delta of them)
        assert slo_verdict["commit_age"]["events_total"] >= commit_age_delta

        # upload->commit latency recorded for every seeded report
        assert (
            GLOBAL_METRICS.get_sample_value(
                "janus_report_upload_to_commit_seconds_count"
            )
            or 0
        ) >= total_reports

        # merged chrome trace: one aggregation job's spans visible from >= 2
        # processes (a leader driver binary AND the helper binary) under a
        # single trace id — the cross-process correlation the trace ids
        # persisted on job rows + the traceparent header exist to provide
        from tools.trace_merge import load_events, merge_trace_files, trace_stats

        close_chrome_trace()  # flush this process's client/collection spans
        helper_trace = str(tmp_path / "trace-helper.json")
        trace_files = [
            str(tmp_path / f"trace-driver{i}.json") for i in range(2)
        ] + [helper_trace, client_trace]
        for f in trace_files:
            assert os.path.exists(f), f"replica never wrote its trace: {f}"
        summary = merge_trace_files(
            trace_files, str(tmp_path / "merged-trace.json")
        )
        helper_pids = {
            e.get("pid") for e in load_events(helper_trace) if e.get("ph") == "X"
        }
        cross_process = {
            t: pids
            for t, pids in summary["traces"].items()
            if set(pids) & helper_pids and set(pids) - helper_pids
        }
        assert cross_process, (
            "no trace id spans both a driver and the helper",
            summary["traces"],
        )

        # -- ISSUE 9 acceptance: the MERGED timeline runs client ingress ->
        # collection.  Upload-minted trace ids (this process's writer) are
        # linked to job trace ids by job_create spans and closed out by
        # collection_finish, so trace_merge --stats must report >= 1 merged
        # trace whose critical path is COMPLETE (upload span -> batch
        # commit -> a driver binary's flush -> collection) and whose spans
        # come from an upload process, a driver binary, AND the helper.
        driver_pids = set()
        for i in range(2):
            driver_pids |= {
                e.get("pid")
                for e in load_events(str(tmp_path / f"trace-driver{i}.json"))
                if e.get("ph") == "X"
            }
        stats = trace_stats(trace_files)
        assert stats["complete_paths"] >= 1, stats
        end_to_end = [
            g
            for g in stats["merged_traces"]
            if g["complete"]
            and set(g["pids"]) & driver_pids
            and set(g["pids"]) & helper_pids
        ]
        assert end_to_end, (
            "no complete upload->collection path crosses a driver binary "
            "and the helper",
            stats,
        )
        durations = end_to_end[0]["durations_s"]
        assert durations["upload_to_collection"] > 0, durations
    finally:
        reps.terminate_all()
        leader_ds.close()
        helper_ds.close()
        configure_chrome_trace(None)


# ---------------------------------------------------------------------------
# zero-copy ingest: SIGKILL between ACK and materialization (ISSUE 18), with
# the GC loop live through the whole replay window (ROADMAP direction 4)


@pytest.mark.slow
def test_journaled_ingest_sigkill_replay_exactly_once_with_gc(tmp_path):
    """THE INGEST CRASH CASE (ISSUE 18 acceptance): an aggregator binary
    in journaled mode ACKs uploads off the report-journal write alone
    (materializer and staged consumer are parked far out, so every
    admitted report sits in the replay window), is SIGKILLed there, and
    the restarted incarnation's startup replay materializes every row —
    zero admitted-then-lost.  The GC loop runs at 0.2s the WHOLE time
    (ROADMAP direction 4's GC-mid-SIGKILL case): it provably executes
    deletions (an aged decoy report is reaped) yet never touches a
    journal row inside the replay window.  Re-uploading every ACKed
    report after recovery changes nothing (cross-crash, cross-path
    dedup), the upload-success counter reads exactly N, and the creator
    then packs each report into exactly one aggregation job."""
    import asyncio

    from janus_tpu.aggregator import AggregationJobCreator, CreatorConfig
    from janus_tpu.aggregator.report_writer import ReportWriteBatcher
    from janus_tpu.client import prepare_report
    from janus_tpu.messages import InputShareAad

    key = generate_key()
    leader_db = str(tmp_path / "leader.sqlite3")
    agg_port, agg_health = _free_port(), _free_port()

    clock = RealClock()
    leader_ds = Datastore(leader_db, Crypter([key]), clock)
    agg_token = AuthenticationToken.new_bearer("agg-token-ingest")
    collector_keys = HpkeKeypair.generate(9)
    now = clock.now()
    report_time = Time(now.seconds - now.seconds % TIME_PRECISION.seconds)

    task_id = TaskId.random()
    leader_kp, helper_kp = HpkeKeypair.generate(1), HpkeKeypair.generate(2)
    leader_task = AggregatorTask(
        task_id=task_id,
        peer_aggregator_endpoint="http://127.0.0.1:1/",  # never called
        role=Role.LEADER,
        aggregator_auth_token=agg_token,
        hpke_keys=[leader_kp],
        query_type=TaskQueryType.time_interval(),
        vdaf={"type": "Prio3Count"},
        vdaf_verify_key=bytes([0x60]) * 16,
        min_batch_size=1,
        time_precision=TIME_PRECISION,
        collector_hpke_config=collector_keys.config,
        report_expiry_age=Duration(2 * 3600),
    )
    leader_ds.run_tx("putl", lambda tx: tx.put_aggregator_task(leader_task))

    vdaf = leader_task.vdaf_instance()

    def _sealed(m, time):
        return prepare_report(
            vdaf,
            task_id,
            leader_kp.config,
            helper_kp.config,
            TIME_PRECISION,
            m,
            time=time,
        )

    # the GC BAIT: an aged report written straight into client_reports
    # (the upload path would reject it as expired) — its disappearance is
    # the proof that the 0.2s GC loop is executing real deletions while
    # the journal rows sit in the replay window beside it
    decoy = _sealed(1, Time(report_time.seconds - 3 * 3600))
    aad = InputShareAad(task_id, decoy.metadata, decoy.public_share).get_encoded()
    info = HpkeApplicationInfo.new(Label.INPUT_SHARE, Role.CLIENT, Role.LEADER)
    plain = PlaintextInputShare.get_decoded(
        open_(leader_kp, info, decoy.leader_encrypted_input_share, aad)
    )
    asyncio.run(
        ReportWriteBatcher(leader_ds, max_batch_size=1).write_report(
            LeaderStoredReport(
                task_id=task_id,
                metadata=decoy.metadata,
                public_share=decoy.public_share,
                leader_extensions=[],
                leader_input_share=plain.payload,
                helper_encrypted_input_share=decoy.helper_encrypted_input_share,
            )
        )
    )

    measurements = [1, 0, 1, 1, 0, 1, 1, 1]
    N = len(measurements)
    encodeds = [_sealed(m, report_time).get_encoded() for m in measurements]

    def _success_total():
        return _sql(
            leader_db,
            "SELECT COALESCE(SUM(report_success), 0) FROM task_upload_counters",
        )[0][0]

    success_before = _success_total()  # the decoy's seed write counted one

    cfg = tmp_path / "ingest-agg.yaml"
    cfg.write_text(
        f"""
common:
  database: {{path: {leader_db}}}
  health_check_listen_address: 127.0.0.1:{agg_health}
  status_sample_interval_s: 0.5
listen_address: 127.0.0.1:{agg_port}
vdaf_backend: oracle
upload_open_batch_delay_ms: 2
garbage_collection_interval_s: 0.2
ingest:
  mode: journaled
  journal_write_delay_ms: 5
  materialize_interval_ms: 600000
  staged_consume_interval_ms: 600000
"""
    )

    env = dict(os.environ)
    env["DATASTORE_KEYS"] = base64.urlsafe_b64encode(key).decode().rstrip("=")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")

    def _spawn(tag):
        log = open(tmp_path / f"{tag}.log", "wb")
        return subprocess.Popen(
            [sys.executable, "-c", _BOOT, "aggregator", "--config-file", str(cfg)],
            env=env,
            cwd=str(REPO),
            stdout=log,
            stderr=subprocess.STDOUT,
        )

    def _put_report(encoded):
        req = urllib.request.Request(
            f"http://127.0.0.1:{agg_port}/tasks/{task_id}/reports",
            data=encoded,
            method="PUT",
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status

    def _journal():
        return _sql(leader_db, "SELECT COUNT(*) FROM report_journal")[0][0]

    def _reports_rows():
        return _sql(leader_db, "SELECT COUNT(*) FROM client_reports")[0][0]

    proc = _spawn("ingest-agg-1")
    try:
        _wait_http(f"http://127.0.0.1:{agg_health}/healthz", 120)
        for enc in encodeds:
            assert _put_report(enc) == 201
        # ACK semantics: every 201 above returned only after its journal
        # row committed — and with the materializer parked, the journal
        # IS the only durable home of the admitted reports
        assert _journal() == N
        # GC provably executes during the window: the aged decoy goes...
        deadline = time.monotonic() + 60
        while _reports_rows() > 0:
            assert time.monotonic() < deadline, "GC never reaped the aged decoy"
            time.sleep(0.2)
        # ...while several more GC passes never touch a journal row
        time.sleep(1.0)
        assert _journal() == N
        # the replica's own /statusz sees the replay window (shared
        # datastore section) and reports the journaled ingest plane
        with urllib.request.urlopen(
            f"http://127.0.0.1:{agg_health}/statusz", timeout=10
        ) as r:
            doc = json.loads(r.read().decode())
        assert doc["report_journal"]["outstanding_rows"] == N, doc["report_journal"]
        assert doc["ingest"]["mode"] == "journaled", doc["ingest"]

        # -- SIGKILL between ACK and materialization ------------------------
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        assert _journal() == N, "journal rows must survive the SIGKILL"
        assert _reports_rows() == 0, "nothing materialized before the crash"

        # -- restart: startup replay drains the journal, GC still live ------
        proc = _spawn("ingest-agg-2")
        _wait_http(f"http://127.0.0.1:{agg_health}/healthz", 120)
        deadline = time.monotonic() + 120
        while _journal() > 0:
            assert time.monotonic() < deadline, "startup replay never drained"
            time.sleep(0.2)
        assert _reports_rows() == N, "zero admitted-then-lost after replay"
        # several GC cycles post-replay: fresh reports stay put
        time.sleep(1.0)
        assert _reports_rows() == N

        # -- duplicate re-uploads after the crash change NOTHING ------------
        for enc in encodeds:
            assert _put_report(enc) == 201
        assert _journal() == 0
        assert _reports_rows() == N
        # exactly-once admission accounting across crash + duplicates
        assert _success_total() - success_before == N
        # the survivor's replay counter moved by exactly the orphan count
        scraped = _scrape(agg_health)
        assert (
            _metric_total(scraped, "janus_ingest_journal_replayed_total") == N
        ), scraped

        # graceful close-out: SIGTERM drains the (empty) plane cleanly
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0, "SIGTERM exit must be clean"
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    # -- exactly-once collection: each report lands in ONE job --------------
    creator = AggregationJobCreator(
        leader_ds,
        CreatorConfig(min_aggregation_job_size=1, max_aggregation_job_size=3),
    )
    n_jobs = asyncio.run(creator.run_once())
    assert n_jobs >= 1, n_jobs
    total, distinct = _sql(
        leader_db,
        "SELECT COUNT(*), COUNT(DISTINCT report_id) FROM report_aggregations",
    )[0]
    assert total == N and distinct == N, (total, distinct)
    leader_ds.close()


# ---------------------------------------------------------------------------
# flight recorder SIGKILL semantics + per-task cost attribution (ISSUE 12)


@pytest.mark.slow
def test_flight_recorder_sigkill_semantics_and_per_task_cost(tmp_path):
    """The flight recorder is deliberately in-memory: a fresh binary
    starts an EMPTY ring (probed on a just-started driver before any job
    exists), a SIGKILLed binary's records die with it (the survivor's
    ring carries only its OWN flushes), and the survivor's breaker trip
    dumps the ring EXACTLY ONCE into its log.  After recovery, the
    per-task cost series prove the failure-domain shift: every seeded
    task has device-seconds > 0, attributed on the oracle path the open
    breaker degraded it to."""
    import asyncio

    from janus_tpu.aggregator import AggregationJobCreator, CreatorConfig
    from janus_tpu.aggregator.report_writer import ReportWriteBatcher
    from janus_tpu.client import prepare_report
    from janus_tpu.executor.flight_recorder import DUMP_MARKER
    from janus_tpu.messages import InputShareAad

    key = generate_key()
    leader_db = str(tmp_path / "leader.sqlite3")
    helper_db = str(tmp_path / "helper.sqlite3")
    helper_port, helper_health = _free_port(), _free_port()
    driver_health = [_free_port(), _free_port()]

    clock = RealClock()
    leader_ds = Datastore(leader_db, Crypter([key]), clock)
    helper_ds = Datastore(helper_db, Crypter([key]), clock)
    agg_token = AuthenticationToken.new_bearer("agg-token-flights")
    collector_keys = HpkeKeypair.generate(9)
    now = clock.now()
    report_time = Time(now.seconds - now.seconds % TIME_PRECISION.seconds)

    n_tasks = 2
    tasks = []
    for t in range(n_tasks):
        task_id = TaskId.random()
        common = dict(
            task_id=task_id,
            query_type=TaskQueryType.time_interval(),
            vdaf={"type": "Prio3Count"},
            vdaf_verify_key=bytes([0x50 + t]) * 16,
            min_batch_size=3,
            time_precision=TIME_PRECISION,
            collector_hpke_config=collector_keys.config,
        )
        leader_kp, helper_kp = HpkeKeypair.generate(1), HpkeKeypair.generate(2)
        leader_task = AggregatorTask(
            peer_aggregator_endpoint=f"http://127.0.0.1:{helper_port}/",
            role=Role.LEADER,
            aggregator_auth_token=agg_token,
            hpke_keys=[leader_kp],
            **common,
        )
        helper_task = AggregatorTask(
            peer_aggregator_endpoint="http://127.0.0.1:1/",
            role=Role.HELPER,
            aggregator_auth_token_hash=agg_token.hash(),
            hpke_keys=[helper_kp],
            **common,
        )
        leader_ds.run_tx("putl", lambda tx, lt=leader_task: tx.put_aggregator_task(lt))
        helper_ds.run_tx("puth", lambda tx, ht=helper_task: tx.put_aggregator_task(ht))
        tasks.append((task_id, leader_task, leader_kp, helper_kp))

    def seed_report(t, m):
        task_id, leader_task, leader_kp, helper_kp = tasks[t]
        vdaf = leader_task.vdaf_instance()
        report = prepare_report(
            vdaf,
            task_id,
            leader_kp.config,
            helper_kp.config,
            TIME_PRECISION,
            m,
            time=report_time,
        )
        aad = InputShareAad(
            task_id, report.metadata, report.public_share
        ).get_encoded()
        info = HpkeApplicationInfo.new(Label.INPUT_SHARE, Role.CLIENT, Role.LEADER)
        plain = PlaintextInputShare.get_decoded(
            open_(leader_kp, info, report.leader_encrypted_input_share, aad)
        )
        stored = LeaderStoredReport(
            task_id=task_id,
            metadata=report.metadata,
            public_share=report.public_share,
            leader_extensions=[],
            leader_input_share=plain.payload,
            helper_encrypted_input_share=report.helper_encrypted_input_share,
        )
        asyncio.run(
            ReportWriteBatcher(leader_ds, max_batch_size=1).write_report(stored)
        )

    for t in range(n_tasks):
        for m in (1, 0, 1):
            seed_report(t, m)

    # -- replica configs ----------------------------------------------------
    def driver_yaml(i):
        if i == 0:  # the WEDGER: every flush parks for 600s mid-step
            fault_point = "executor.flush: {mode: delay, probability: 1.0, delay_s: 600}"
        else:  # the SURVIVOR: every device launch fails -> breaker trip
            fault_point = "backend.launch: {mode: error, probability: 1.0}"
        return f"""
common:
  database: {{path: {leader_db}}}
  health_check_listen_address: 127.0.0.1:{driver_health[i]}
  status_sample_interval_s: 0.5
  fault_injection:
    enabled: true
    seed: {SEED}
    points:
      {fault_point}
job_driver:
  job_discovery_interval_s: 0.2
  max_concurrent_job_workers: 2
  worker_lease_duration_s: 5
  worker_lease_clock_skew_allowance_s: 1
  maximum_attempts_before_failure: 100000
  max_step_attempts: 100000
  retry_initial_delay_s: 0.5
  retry_max_delay_s: 1.0
  lease_reap_interval_s: 0.1
vdaf_backend: tpu
device_executor:
  enabled: true
  flush_window_ms: 20
  flush_max_rows: 4096
  breaker_failure_threshold: 1
  breaker_reset_timeout_s: 3600
"""

    helper_yaml = f"""
common:
  database: {{path: {helper_db}}}
  health_check_listen_address: 127.0.0.1:{helper_health}
listen_address: 127.0.0.1:{helper_port}
vdaf_backend: oracle
"""
    cfg_paths = []
    for i in range(2):
        p = tmp_path / f"driver{i}.yaml"
        p.write_text(driver_yaml(i))
        cfg_paths.append(p)
    helper_cfg = tmp_path / "helper.yaml"
    helper_cfg.write_text(helper_yaml)

    env = dict(os.environ)
    env["DATASTORE_KEYS"] = base64.urlsafe_b64encode(key).decode().rstrip("=")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")

    def _statusz(port):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/statusz", timeout=10
        ) as r:
            return json.loads(r.read().decode())

    def _unfinished():
        return _sql(
            leader_db,
            "SELECT COUNT(*) FROM aggregation_jobs WHERE state = 'InProgress'",
        )[0][0]

    def _task_seconds_from_scrape(text, label):
        total = 0.0
        for line in text.splitlines():
            if line.startswith("janus_task_device_seconds_total{") and (
                f'task="{label}"' in line
            ):
                total += float(line.rsplit(" ", 1)[1])
        return total

    reps = _Replicas(env, cfg_paths, helper_cfg, tmp_path)
    try:
        reps.start_helper()
        _wait_http(f"http://127.0.0.1:{helper_health}/healthz", 120)

        # -- binary #1 starts BEFORE any job exists: a fresh binary's
        # flight ring is EMPTY (deterministic probe, nothing to flush yet)
        reps.start_driver(0)
        _wait_http(f"http://127.0.0.1:{driver_health[0]}/healthz", 120)
        doc = _statusz(driver_health[0])
        flights = doc["executor"]["flights"]
        assert flights["recorded"] == 0 and flights["records"] == [], flights
        assert doc["executor"]["cost_attribution"]["tracked"] == 0

        # jobs appear; the wedger acquires and parks mid-flush (the
        # injected 600s executor.flush delay) — a wedged flush never
        # COMPLETES, so its ring stays empty right up to the SIGKILL
        creator = AggregationJobCreator(
            leader_ds,
            CreatorConfig(min_aggregation_job_size=1, max_aggregation_job_size=3),
        )
        n_jobs = asyncio.run(creator.run_once())
        assert n_jobs == n_tasks, n_jobs

        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if _statusz(driver_health[0])["faults"]["hits"].get("executor.flush", 0):
                break
            time.sleep(0.2)
        else:
            raise TimeoutError("the wedger never reached its flush fault")
        assert _statusz(driver_health[0])["executor"]["flights"]["recorded"] == 0

        # -- SIGKILL the wedger; its in-memory ring dies with it --------
        reps.kill_driver(0)

        # -- binary #2 (the survivor): launch faults trip the breaker,
        # jobs degrade to the per-task-attributed oracle, and converge
        reps.start_driver(1)
        _wait_http(f"http://127.0.0.1:{driver_health[1]}/healthz", 120)
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if _unfinished() == 0:
                break
            time.sleep(0.3)
        assert _unfinished() == 0, "survivor never converged on the oracle path"

        # the survivor's ring carries ONLY its own flushes (SIGKILL
        # semantics: nothing leaked over from binary #1's incarnation),
        # and each is the error-outcome record of its own launch faults
        doc = _statusz(driver_health[1])
        records = doc["executor"]["flights"]["records"]
        assert records, "survivor must have recorded its failing flushes"
        assert all(r["outcome"] == "error" and r["fault"] for r in records), records
        assert doc["executor"]["flights"]["dumps"] == {"breaker_trip": 1}, doc[
            "executor"
        ]["flights"]

        # per-task device-seconds > 0 for EVERY seeded task after
        # recovery — and specifically on the ORACLE path (the breaker
        # cost shift the series exist to show)
        scraped = _scrape(driver_health[1])
        for task_id, _lt, _lk, _hk in tasks:
            label = str(task_id)
            assert _task_seconds_from_scrape(scraped, label) > 0, label
            oracle_line = [
                line
                for line in scraped.splitlines()
                if line.startswith("janus_task_device_seconds_total{")
                and f'task="{label}"' in line
                and 'path="oracle"' in line
            ]
            assert oracle_line, f"task {label} has no oracle-path attribution"
    finally:
        reps.terminate_all()

    # -- the dump appears EXACTLY ONCE in the survivor's log ------------
    def _dump_lines(tag):
        lines = []
        for log in sorted(tmp_path.glob(f"{tag}-*.log")):
            lines += [
                line
                for line in log.read_text(errors="replace").splitlines()
                if DUMP_MARKER in line
            ]
        return lines

    survivor_dumps = _dump_lines("driver1")
    assert len(survivor_dumps) == 1, survivor_dumps
    payload = json.loads(survivor_dumps[0].split(DUMP_MARKER, 1)[1])
    assert payload["reason"] == "breaker_trip"
    assert payload["flights"], "the dump must carry the ring that led to the trip"
    assert all(f["outcome"] == "error" for f in payload["flights"])
    # the wedger never completed a flush, never tripped: zero dumps
    assert _dump_lines("driver0") == []
    leader_ds.close()
    helper_ds.close()
