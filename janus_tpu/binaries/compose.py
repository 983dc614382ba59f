"""How a binary's config becomes a running role.

The one place that maps the YAML-facing config classes of ``config.py``
onto the roles' own config dataclasses and builds the role objects from
them.  ``main.py`` calls these for the four daemons; ``chip_smoke.py``
calls them to run the same pair in one process.  What belongs to a
process (signals, health listener, samplers, teardown order) stays in
``main.py``.
"""

from __future__ import annotations

import aiohttp

from ..aggregator import (
    AggregationJobDriver,
    CollectionJobDriver,
    Config,
    CreatorConfig,
    DriverConfig,
    JobDriver,
)
from ..aggregator.collection_job_driver import CollectionDriverConfig
from ..aggregator.job_driver import acquisition_exclusions
from ..core.retries import HttpRetryPolicy
from ..core.time import Clock
from ..datastore import Datastore
from ..messages import Duration
from .config import AggregatorConfig, JobCreatorConfig, JobDriverBinaryConfig


def _executor_config(cfg):
    """The runtime ExecutorConfig, or None where the executor is off."""
    return (
        cfg.device_executor.to_executor_config()
        if cfg.device_executor.enabled
        else None
    )


def aggregator_config(cfg: AggregatorConfig) -> Config:
    return Config(
        max_upload_batch_size=cfg.max_upload_batch_size,
        max_upload_batch_write_delay=cfg.max_upload_batch_write_delay_ms / 1000.0,
        upload_open_backend=cfg.upload_open_backend,
        upload_open_batch_size=cfg.upload_open_batch_size,
        upload_open_batch_delay=cfg.upload_open_batch_delay_ms / 1000.0,
        upload_queue_max=cfg.upload_queue_max,
        upload_shed_delay_s=cfg.upload_shed_delay_s,
        ingest_mode=cfg.ingest.mode,
        ingest_journal_batch_size=cfg.ingest.journal_batch_size,
        ingest_journal_write_delay=cfg.ingest.journal_write_delay_ms / 1000.0,
        ingest_journal_queue_max=cfg.ingest.journal_queue_max,
        ingest_stage_direct=cfg.ingest.stage_direct,
        ingest_stage_max_reports=cfg.ingest.stage_max_reports,
        batch_aggregation_shard_count=cfg.batch_aggregation_shard_count,
        task_counter_shard_count=cfg.task_counter_shard_count,
        vdaf_backend=cfg.vdaf_backend,
        field_backend=cfg.field_backend,
        poplar_backend=cfg.poplar_backend,
        max_agg_param_job_size=cfg.max_agg_param_job_size,
        device_executor=_executor_config(cfg),
    )


def creator_config(cfg: JobCreatorConfig) -> CreatorConfig:
    return CreatorConfig(
        min_aggregation_job_size=cfg.min_aggregation_job_size,
        max_aggregation_job_size=cfg.max_aggregation_job_size,
        batch_aggregation_shard_count=cfg.batch_aggregation_shard_count,
        journal_replay_min_age_s=cfg.journal_replay_min_age_s,
    )


def aggregation_driver(
    cfg: JobDriverBinaryConfig, datastore: Datastore
) -> AggregationJobDriver:
    jd = cfg.job_driver
    return AggregationJobDriver(
        datastore,
        aiohttp.ClientSession,
        DriverConfig(
            batch_aggregation_shard_count=cfg.batch_aggregation_shard_count,
            maximum_attempts_before_failure=jd.maximum_attempts_before_failure,
            max_step_attempts=jd.max_step_attempts,
            retry_initial_delay_s=jd.retry_initial_delay_s,
            retry_max_delay_s=jd.retry_max_delay_s,
            vdaf_backend=cfg.vdaf_backend,
            field_backend=cfg.field_backend,
            poplar_backend=cfg.poplar_backend,
            device_executor=_executor_config(cfg),
            warmup_wait_s=cfg.warmup_wait_s,
            http_retry=HttpRetryPolicy(attempt_timeout=jd.http_attempt_timeout_s),
        ),
    )


def collection_driver(
    cfg: JobDriverBinaryConfig, datastore: Datastore
) -> CollectionJobDriver:
    jd = cfg.job_driver
    return CollectionJobDriver(
        datastore,
        aiohttp.ClientSession,
        CollectionDriverConfig(
            maximum_attempts_before_failure=jd.maximum_attempts_before_failure,
            max_step_attempts=jd.max_step_attempts,
            batch_aggregation_shard_count=cfg.batch_aggregation_shard_count,
            # the shared retry knobs configure the FAILURE backoff; the
            # readiness-poll curve keeps its own (reference) defaults
            step_retry_initial_delay=Duration(max(1, int(jd.retry_initial_delay_s))),
            step_retry_max_delay=Duration(int(jd.retry_max_delay_s)),
            http_retry=HttpRetryPolicy(attempt_timeout=jd.http_attempt_timeout_s),
        ),
    )


#: kind -> (transaction names, the Transaction's acquire and reap methods,
#: the stepper's step method)
_KINDS = {
    "aggregation": (
        "acquire_agg",
        "acquire_incomplete_aggregation_jobs",
        "reap_agg_leases",
        "reap_expired_aggregation_job_leases",
        "step_aggregation_job",
    ),
    "collection": (
        "acquire_coll",
        "acquire_incomplete_collection_jobs",
        "reap_coll_leases",
        "reap_expired_collection_job_leases",
        "step_collection_job",
    ),
}


def job_driver(
    kind: str,
    cfg: JobDriverBinaryConfig,
    datastore: Datastore,
    clock: Clock,
    stepper,
) -> JobDriver:
    """The lease loop of one driver binary.  ``kind`` is "aggregation" or
    "collection"; ``stepper`` is what ``aggregation_driver`` or
    ``collection_driver`` returned."""
    acquire_tx, acquire, reap_tx, reap, step = _KINDS[kind]
    jd = cfg.job_driver

    async def acquirer(duration, limit):
        return await datastore.run_tx_async(
            acquire_tx,
            # suspect-peer and fleet-routed tasks filter at the query
            # (task -> peer index, same tx) instead of
            # acquire-then-release churn
            lambda tx: getattr(tx, acquire)(
                duration,
                limit,
                exclude_task_ids=acquisition_exclusions(tx, kind),
            ),
        )

    async def reaper():
        return await datastore.run_tx_async(reap_tx, lambda tx: getattr(tx, reap)())

    return JobDriver(
        clock,
        acquirer,
        getattr(stepper, step),
        job_discovery_interval=jd.job_discovery_interval_s,
        max_concurrent_job_workers=jd.max_concurrent_job_workers,
        worker_lease_duration=Duration(jd.worker_lease_duration_s),
        worker_lease_clock_skew_allowance=Duration(
            jd.worker_lease_clock_skew_allowance_s
        ),
        reaper=reaper if jd.lease_reap_interval_s > 0 else None,
        lease_reap_interval=jd.lease_reap_interval_s,
        job_type=kind,
    )
