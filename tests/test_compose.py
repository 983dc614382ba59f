"""The shipped wiring: ``janus_tpu/binaries/compose.py`` and the creator's loop.

``main.py`` runs these functions only in child processes (the slow soaks);
here they run in-process.  Every field of a binary's config either arrives
in the role's config under the value it was given, or is named below as one
the process itself consumes — a field added to ``config.py`` and mapped
nowhere fails the census.
"""

import asyncio
import dataclasses
import logging
import random
import time

import pytest

from janus_tpu.aggregator import AggregationJobCreator
from janus_tpu.binaries import compose
from janus_tpu.binaries.config import (
    AggregatorConfig,
    JobCreatorConfig,
    JobDriverBinaryConfig,
)
from janus_tpu.core.time import RealClock
from janus_tpu.executor import ExecutorConfig, reset_global_executor
from janus_tpu.messages import Duration


def _get(obj, path):
    for name in path.split("."):
        obj = getattr(obj, name)
    return obj


def _set(obj, path, value):
    head, _, leaf = path.rpartition(".")
    setattr(_get(obj, head) if head else obj, leaf, value)


def _leaves(cfg, nested=()):
    """The config's field paths, one level into the sections of ``nested``."""
    out = set()
    for f in dataclasses.fields(cfg):
        if f.name in nested:
            out |= {f"{f.name}.{g.name}" for g in dataclasses.fields(getattr(cfg, f.name))}
        else:
            out.add(f.name)
    return out


def _driver_roles(cfg):
    agg, coll = compose.aggregation_driver(cfg, None), compose.collection_driver(cfg, None)
    return {
        "aggregation": agg.config,
        "collection": coll.config,
        "loop": compose.job_driver("aggregation", cfg, None, RealClock(), agg),
    }


#: binary -> (config class, sections mapped field by field, what builds the
#: role configs, {field: (value, [(role, attribute, expected)])}, the fields
#: the process consumes itself and where)
CASES = {
    "aggregator": (
        AggregatorConfig,
        ("ingest",),
        lambda cfg: {"role": compose.aggregator_config(cfg)},
        {
            "max_upload_batch_size": (7, [("role", "max_upload_batch_size", 7)]),
            "max_upload_batch_write_delay_ms": (
                125, [("role", "max_upload_batch_write_delay", 0.125)],
            ),
            "upload_open_backend": ("inline", [("role", "upload_open_backend", "inline")]),
            "upload_open_batch_size": (9, [("role", "upload_open_batch_size", 9)]),
            "upload_open_batch_delay_ms": (40, [("role", "upload_open_batch_delay", 0.04)]),
            "upload_queue_max": (11, [("role", "upload_queue_max", 11)]),
            "upload_shed_delay_s": (0.75, [("role", "upload_shed_delay_s", 0.75)]),
            "ingest.mode": ("journaled", [("role", "ingest_mode", "journaled")]),
            "ingest.journal_batch_size": (13, [("role", "ingest_journal_batch_size", 13)]),
            "ingest.journal_write_delay_ms": (
                20, [("role", "ingest_journal_write_delay", 0.02)],
            ),
            "ingest.journal_queue_max": (17, [("role", "ingest_journal_queue_max", 17)]),
            "ingest.stage_direct": (False, [("role", "ingest_stage_direct", False)]),
            "ingest.stage_max_reports": (19, [("role", "ingest_stage_max_reports", 19)]),
            "batch_aggregation_shard_count": (
                3, [("role", "batch_aggregation_shard_count", 3)],
            ),
            "task_counter_shard_count": (5, [("role", "task_counter_shard_count", 5)]),
            "vdaf_backend": ("oracle", [("role", "vdaf_backend", "oracle")]),
            "field_backend": ("mxu", [("role", "field_backend", "mxu")]),
            "poplar_backend": ("jax", [("role", "poplar_backend", "jax")]),
            "max_agg_param_job_size": (23, [("role", "max_agg_param_job_size", 23)]),
        },
        {
            # listeners, janitor loops and the embedded staged consumer of
            # run_aggregator; device_executor has its own test below
            "common", "listen_address", "device_executor",
            "garbage_collection_interval_s", "task_api_listen_address",
            "task_api_auth_tokens", "key_rotator_interval_s",
            "key_rotator_pending_duration_s", "key_rotator_active_duration_s",
            "key_rotator_expired_duration_s", "ingest.staged_consume_interval_ms",
            "ingest.materialize_interval_ms", "ingest.materialize_batch_size",
            "ingest.staged_min_job_size", "ingest.staged_max_job_size",
        },
    ),
    "creator": (
        JobCreatorConfig,
        (),
        lambda cfg: {"role": compose.creator_config(cfg)},
        {
            "min_aggregation_job_size": (4, [("role", "min_aggregation_job_size", 4)]),
            "max_aggregation_job_size": (44, [("role", "max_aggregation_job_size", 44)]),
            "batch_aggregation_shard_count": (
                3, [("role", "batch_aggregation_shard_count", 3)],
            ),
            "journal_replay_min_age_s": (1.5, [("role", "journal_replay_min_age_s", 1.5)]),
        },
        # the interval is AggregationJobCreator.run's argument
        {"common", "aggregation_job_creation_interval_s"},
    ),
    "job_driver": (
        JobDriverBinaryConfig,
        ("job_driver",),
        _driver_roles,
        {
            "batch_aggregation_shard_count": (
                3,
                [
                    ("aggregation", "batch_aggregation_shard_count", 3),
                    ("collection", "batch_aggregation_shard_count", 3),
                ],
            ),
            "vdaf_backend": ("oracle", [("aggregation", "vdaf_backend", "oracle")]),
            "field_backend": ("mxu", [("aggregation", "field_backend", "mxu")]),
            "poplar_backend": ("jax", [("aggregation", "poplar_backend", "jax")]),
            "warmup_wait_s": (2.5, [("aggregation", "warmup_wait_s", 2.5)]),
            "job_driver.job_discovery_interval_s": (
                0.3, [("loop", "job_discovery_interval", 0.3)],
            ),
            "job_driver.max_concurrent_job_workers": (
                3, [("loop", "max_concurrent_job_workers", 3)],
            ),
            "job_driver.worker_lease_duration_s": (
                77, [("loop", "worker_lease_duration", Duration(77))],
            ),
            "job_driver.worker_lease_clock_skew_allowance_s": (
                9, [("loop", "worker_lease_clock_skew_allowance", Duration(9))],
            ),
            "job_driver.lease_reap_interval_s": (4.0, [("loop", "lease_reap_interval", 4.0)]),
            "job_driver.maximum_attempts_before_failure": (
                6,
                [
                    ("aggregation", "maximum_attempts_before_failure", 6),
                    ("collection", "maximum_attempts_before_failure", 6),
                ],
            ),
            "job_driver.max_step_attempts": (
                7,
                [("aggregation", "max_step_attempts", 7), ("collection", "max_step_attempts", 7)],
            ),
            "job_driver.retry_initial_delay_s": (
                2.5,
                [
                    ("aggregation", "retry_initial_delay_s", 2.5),
                    ("collection", "step_retry_initial_delay", Duration(2)),
                ],
            ),
            "job_driver.retry_max_delay_s": (
                90.0,
                [
                    ("aggregation", "retry_max_delay_s", 90.0),
                    ("collection", "step_retry_max_delay", Duration(90)),
                ],
            ),
            "job_driver.http_attempt_timeout_s": (
                12.0,
                [
                    ("aggregation", "http_retry.attempt_timeout", 12.0),
                    ("collection", "http_retry.attempt_timeout", 12.0),
                ],
            ),
        },
        {
            # the process-wide peer-health tracker, configured once by
            # _run_job_driver_binary; device_executor has its own test below
            "common", "device_executor",
            "job_driver.peer_failure_threshold", "job_driver.peer_suspect_dwell_s",
        },
    ),
}


@pytest.mark.parametrize("binary", sorted(CASES))
def test_every_field_of_a_binary_config_reaches_its_role(binary):
    cls, nested, build, fields, process_only = CASES[binary]
    cfg = cls()
    assert _leaves(cfg, nested) == set(fields) | process_only
    for path, (value, _arrivals) in fields.items():
        assert _get(cfg, path) != value, f"{path}: {value!r} is the default"
        _set(cfg, path, value)
    roles = build(cfg)
    for path, (_value, arrivals) in fields.items():
        for role, attribute, expected in arrivals:
            assert _get(roles[role], attribute) == expected, (path, role, attribute)


def test_collection_retry_delay_is_at_least_a_second():
    cfg = JobDriverBinaryConfig()
    cfg.job_driver.retry_initial_delay_s = 0.2
    assert compose.collection_driver(cfg, None).config.step_retry_initial_delay == Duration(1)


@pytest.mark.parametrize("role", ["aggregator", "aggregation_driver"])
@pytest.mark.parametrize("enabled", [False, True])
def test_device_executor_only_where_enabled(role, enabled):
    cfg = AggregatorConfig() if role == "aggregator" else JobDriverBinaryConfig()
    cfg.device_executor.enabled = enabled
    cfg.device_executor.flush_window_ms = 3000.0
    try:
        if role == "aggregator":
            got = compose.aggregator_config(cfg).device_executor
        else:
            got = compose.aggregation_driver(cfg, None).config.device_executor
    finally:
        reset_global_executor()
    if enabled:
        assert isinstance(got, ExecutorConfig) and got.enabled
        assert got.flush_window_s == 3.0
    else:
        assert got is None


class _Tx:
    def __init__(self):
        self.calls = []

    def __getattr__(self, name):
        return lambda *args, **kwargs: self.calls.append((name, args, kwargs)) or 5


class _Datastore:
    def __init__(self):
        self.tx = _Tx()
        self.names = []

    async def run_tx_async(self, name, fn):
        self.names.append(name)
        return fn(self.tx)


class _Stepper:
    async def step_aggregation_job(self, lease):
        pass

    async def step_collection_job(self, lease):
        pass


KINDS = {
    "aggregation": (
        "acquire_incomplete_aggregation_jobs",
        "reap_expired_aggregation_job_leases",
        "step_aggregation_job",
    ),
    "collection": (
        "acquire_incomplete_collection_jobs",
        "reap_expired_collection_job_leases",
        "step_collection_job",
    ),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_job_driver_acquires_reaps_and_steps_its_kind(kind, monkeypatch):
    acquire, reap, step = KINDS[kind]
    asked = []
    monkeypatch.setattr(
        compose, "acquisition_exclusions", lambda tx, k: asked.append(k) or [b"suspect"]
    )
    ds, stepper = _Datastore(), _Stepper()
    driver = compose.job_driver(kind, JobDriverBinaryConfig(), ds, RealClock(), stepper)
    assert driver.job_type == kind
    assert driver.stepper == getattr(stepper, step)

    assert asyncio.run(driver.acquirer(Duration(600), 4)) == 5
    assert asyncio.run(driver.reaper()) == 5
    assert asked == [kind]
    assert ds.tx.calls == [
        (acquire, (Duration(600), 4), {"exclude_task_ids": [b"suspect"]}),
        (reap, (), {}),
    ]
    assert len(set(ds.names)) == 2


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("interval_s", [0, -1.0])
def test_job_driver_has_no_reaper_without_an_interval(kind, interval_s):
    cfg = JobDriverBinaryConfig()
    cfg.job_driver.lease_reap_interval_s = interval_s
    driver = compose.job_driver(kind, cfg, _Datastore(), RealClock(), _Stepper())
    assert driver.reaper is None


# -- AggregationJobCreator.run ------------------------------------------------


class _Passes(AggregationJobCreator):
    """A creator whose passes take ``pass_s`` and report what ``results``
    says (an exception is raised); records when each began and ended."""

    def __init__(self, results, pass_s=0.0):
        super().__init__(None)
        self.results, self.pass_s = list(results), pass_s
        self.began, self.ended = [], []
        self.after_pass = None

    async def run_once(self):
        self.began.append(time.monotonic())
        await asyncio.sleep(self.pass_s)
        self.ended.append(time.monotonic())
        if self.after_pass is not None:
            self.after_pass(len(self.ended))
        result = self.results.pop(0) if self.results else 0
        if isinstance(result, Exception):
            raise result
        return result


def _run(creator, interval_s, stop_after_passes, timeout_s=20.0):
    """Run the loop until ``stop_after_passes`` passes have ended; returns
    (when it started, when it returned)."""

    async def flow():
        stop = asyncio.Event()
        creator.after_pass = lambda n: n >= stop_after_passes and stop.set()
        t0 = time.monotonic()
        await asyncio.wait_for(creator.run(stop, interval_s), timeout_s)
        return t0, time.monotonic()

    return asyncio.run(flow())


def test_creator_run_passes_at_once_then_an_interval_after_each_pass_has_ended():
    creator = _Passes([0, 0, 0], pass_s=0.15)
    t0, _t1 = _run(creator, 0.3, stop_after_passes=3)
    assert len(creator.began) == 3
    assert creator.began[0] - t0 < 0.1
    for ended, began in zip(creator.ended, creator.began[1:]):
        # the whole interval after the pass, not the interval less the pass
        assert 0.29 <= began - ended < 5.0


def test_creator_run_returns_promptly_when_stopped_mid_wait():
    creator = _Passes([2])

    async def flow():
        stop = asyncio.Event()
        running = asyncio.ensure_future(creator.run(stop, 3600.0))
        await asyncio.sleep(0.2)
        assert len(creator.ended) == 1 and not running.done()
        t0 = time.monotonic()
        stop.set()
        await asyncio.wait_for(running, 5.0)
        return time.monotonic() - t0

    assert asyncio.run(flow()) < 1.0
    assert len(creator.began) == 1


def test_creator_run_makes_no_pass_once_stop_is_set():
    creator = _Passes([1])

    async def flow():
        stop = asyncio.Event()
        stop.set()
        await asyncio.wait_for(creator.run(stop, 3600.0), 5.0)

    asyncio.run(flow())
    assert creator.began == []


def test_creator_run_logs_a_failing_pass_and_goes_on(caplog):
    creator = _Passes([RuntimeError("datastore away"), 3])
    with caplog.at_level(logging.INFO, logger="janus_tpu.aggregation_job_creator"):
        _run(creator, 0.05, stop_after_passes=2)
    assert len(creator.began) == 2
    failed = [r for r in caplog.records if r.getMessage() == "creation pass failed"]
    assert len(failed) == 1 and failed[0].exc_info[1].args == ("datastore away",)
    assert "created 3 aggregation jobs" in [r.getMessage() for r in caplog.records]


# -- the composed pair ----------------------------------------------------------


def test_composed_pair_on_the_oracle_collects_the_plain_sum(tmp_path):
    """``chip_smoke.Fleet`` builds everything through ``compose``; with the
    configs turned to the CPU oracle it is the shipped pair in one process."""
    import chip_smoke

    rng = random.Random(31)
    measurements = [rng.randrange(2) for _ in range(24)]
    time_s = (int(time.time()) // chip_smoke.TIME_PRECISION_S - 1) * chip_smoke.TIME_PRECISION_S

    async def flow():
        fleet = chip_smoke.Fleet(str(tmp_path))
        for cfg in (fleet.agg_cfg, fleet.drv_cfg):
            cfg.vdaf_backend = "oracle"
            cfg.device_executor.enabled = False
        fleet.drv_cfg.job_driver.job_discovery_interval_s = 0.1
        await fleet.start()
        try:
            task_id, leader_cfg, helper_cfg = fleet.add_task("count", chip_smoke.COUNT)
            reports = chip_smoke._make_reports(
                (
                    chip_smoke.COUNT, task_id.data, leader_cfg.get_encoded(),
                    helper_cfg.get_encoded(), time_s, measurements,
                )
            )
            accepted, _sheds = await fleet.upload(task_id, reports)
            jobs, finished = await fleet.aggregate(task_id, timeout_s=60.0)
            result = await fleet.collect("count", task_id, time_s)
        finally:
            await fleet.stop()
        return accepted, jobs, finished, result

    accepted, jobs, finished, result = asyncio.run(asyncio.wait_for(flow(), 120.0))
    assert accepted == finished == len(measurements)
    assert jobs >= 1
    assert result.report_count == len(measurements)
    assert result.aggregate_result == sum(measurements)
