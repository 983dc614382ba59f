"""The system under test: one leader and one helper in this process.

Copied from ``chip_smoke.py`` (proven on the chip, PR 21) and composed the
way ``janus_tpu/binaries/main.py`` composes ``run_aggregator``,
``run_aggregation_job_creator`` and the job-driver binaries: both
``aggregator_app``s on loopback ports, the creator on its interval, the
aggregation and collection ``JobDriver`` loops, real clock, real HTTP, sqlite
datastores, ``vdaf_backend="tpu"`` and the process-wide device executor.
Everything that a deployment sets comes from the configuration's file; the
benchmark edits nothing of the program.
"""

from __future__ import annotations

import asyncio
import os
import random
import sqlite3
import threading
import time


class FleetFailure(Exception):
    """The fleet could not be brought to serve; the message is the reason."""


class Fleet:
    def __init__(self, workdir, config):
        from janus_tpu.binaries.config import (
            AggregatorConfig,
            JobCreatorConfig,
            JobDriverBinaryConfig,
        )
        from janus_tpu.core.auth_tokens import AuthenticationToken
        from janus_tpu.core.hpke import HpkeKeypair
        from janus_tpu.core.time import RealClock
        from janus_tpu.datastore import Crypter, Datastore
        from janus_tpu.datastore.crypter import generate_key

        self.config = config
        self.clock = RealClock()
        backend = config.get("vdaf_backend", "tpu")
        self.agg_cfg = AggregatorConfig(vdaf_backend=backend)
        self.drv_cfg = JobDriverBinaryConfig(vdaf_backend=backend)
        self.creator_cfg = JobCreatorConfig()
        for key, value in config["job_creator"].items():
            setattr(self.creator_cfg, key, value)
        for key, value in config["job_driver"].items():
            setattr(self.drv_cfg.job_driver, key, value)
        for cfg in (self.agg_cfg, self.drv_cfg):
            for key, value in config["device_executor"].items():
                setattr(cfg.device_executor, key, value)
        self.exec_cfg = self.drv_cfg.device_executor.to_executor_config()
        self.datastores = {
            role: Datastore(
                os.path.join(workdir, f"{role}.sqlite3"),
                Crypter([generate_key()]),
                self.clock,
            )
            for role in ("leader", "helper")
        }
        self.agg_token = AuthenticationToken.new_bearer("bench-aggregator-token")
        self.col_token = AuthenticationToken.new_bearer("bench-collector-token")
        self.collector_keys = HpkeKeypair.generate(9)
        self.urls = {}
        self._runners = []
        self._stop = None
        self._loops = []
        self.tasks = {}

    async def start(self):
        import aiohttp
        from aiohttp import web

        from janus_tpu.aggregator import (
            Aggregator,
            AggregationJobCreator,
            AggregationJobDriver,
            CollectionJobDriver,
            Config,
            CreatorConfig,
            DriverConfig,
            JobDriver,
            aggregator_app,
        )
        from janus_tpu.aggregator.collection_job_driver import CollectionDriverConfig
        from janus_tpu.aggregator.job_driver import acquisition_exclusions
        from janus_tpu.core import peer_health
        from janus_tpu.core.retries import HttpRetryPolicy
        from janus_tpu.messages import Duration

        a, d = self.agg_cfg, self.drv_cfg
        self.aggregators = {}
        for role, ds in self.datastores.items():
            agg = Aggregator(
                ds,
                self.clock,
                Config(
                    max_upload_batch_size=a.max_upload_batch_size,
                    max_upload_batch_write_delay=a.max_upload_batch_write_delay_ms / 1000.0,
                    upload_open_backend=a.upload_open_backend,
                    upload_open_batch_size=a.upload_open_batch_size,
                    upload_open_batch_delay=a.upload_open_batch_delay_ms / 1000.0,
                    upload_queue_max=a.upload_queue_max,
                    upload_shed_delay_s=a.upload_shed_delay_s,
                    batch_aggregation_shard_count=a.batch_aggregation_shard_count,
                    task_counter_shard_count=a.task_counter_shard_count,
                    vdaf_backend=a.vdaf_backend,
                    field_backend=a.field_backend,
                    device_executor=self.exec_cfg,
                    task_cache_ttl=self.config["aggregator"]["task_cache_ttl_s"],
                ),
            )
            runner = web.AppRunner(aggregator_app(agg))
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = runner.addresses[0][1]
            self.urls[role] = f"http://127.0.0.1:{port}/"
            self.aggregators[role] = agg
            self._runners.append(runner)

        leader_ds = self.datastores["leader"]
        jd = d.job_driver
        peer_health.tracker().configure(
            failure_threshold=jd.peer_failure_threshold,
            suspect_dwell_s=jd.peer_suspect_dwell_s,
        )
        self.creator = AggregationJobCreator(
            leader_ds,
            CreatorConfig(
                min_aggregation_job_size=self.creator_cfg.min_aggregation_job_size,
                max_aggregation_job_size=self.creator_cfg.max_aggregation_job_size,
                batch_aggregation_shard_count=self.creator_cfg.batch_aggregation_shard_count,
                journal_replay_min_age_s=self.creator_cfg.journal_replay_min_age_s,
            ),
        )
        retry = HttpRetryPolicy(attempt_timeout=jd.http_attempt_timeout_s)
        self.agg_driver = AggregationJobDriver(
            leader_ds,
            aiohttp.ClientSession,
            DriverConfig(
                batch_aggregation_shard_count=d.batch_aggregation_shard_count,
                maximum_attempts_before_failure=jd.maximum_attempts_before_failure,
                max_step_attempts=jd.max_step_attempts,
                retry_initial_delay_s=jd.retry_initial_delay_s,
                retry_max_delay_s=jd.retry_max_delay_s,
                vdaf_backend=d.vdaf_backend,
                field_backend=d.field_backend,
                device_executor=self.exec_cfg,
                warmup_wait_s=d.warmup_wait_s,
                http_retry=retry,
            ),
        )
        self.col_driver = CollectionJobDriver(
            leader_ds,
            aiohttp.ClientSession,
            CollectionDriverConfig(
                maximum_attempts_before_failure=jd.maximum_attempts_before_failure,
                max_step_attempts=jd.max_step_attempts,
                batch_aggregation_shard_count=d.batch_aggregation_shard_count,
                http_retry=retry,
            ),
        )

        def job_driver(kind, stepper):
            acquire = {
                "aggregation": lambda tx, *a, **kw: tx.acquire_incomplete_aggregation_jobs(*a, **kw),
                "collection": lambda tx, *a, **kw: tx.acquire_incomplete_collection_jobs(*a, **kw),
            }[kind]

            async def acquirer(duration, limit):
                return await leader_ds.run_tx_async(
                    f"acquire_{kind}",
                    lambda tx: acquire(
                        tx,
                        duration,
                        limit,
                        exclude_task_ids=acquisition_exclusions(tx, kind),
                    ),
                )

            return JobDriver(
                self.clock,
                acquirer,
                stepper,
                job_discovery_interval=jd.job_discovery_interval_s,
                max_concurrent_job_workers=jd.max_concurrent_job_workers,
                worker_lease_duration=Duration(jd.worker_lease_duration_s),
                worker_lease_clock_skew_allowance=Duration(
                    jd.worker_lease_clock_skew_allowance_s
                ),
                job_type=kind,
            )

        self._stop = asyncio.Event()
        self._creator_task = asyncio.ensure_future(self._creator_loop())
        self._loops = [
            asyncio.ensure_future(
                job_driver("aggregation", self.agg_driver.step_aggregation_job).run(
                    self._stop
                )
            ),
            asyncio.ensure_future(
                job_driver("collection", self.col_driver.step_collection_job).run(
                    self._stop
                )
            ),
        ]

    async def _creator_loop(self):
        # run_aggregation_job_creator's loop: a pass every interval
        interval = self.creator_cfg.aggregation_job_creation_interval_s
        while not self._stop.is_set():
            t0 = time.monotonic()
            await self.creator.run_once()
            rest = interval - (time.monotonic() - t0)
            if rest > 0:
                try:
                    await asyncio.wait_for(self._stop.wait(), rest)
                except asyncio.TimeoutError:
                    pass

    async def restart_creator(self):
        """Start the creator's interval anew, with a pass now: the harness
        calls this when the traffic starts, so that every run sees the
        creator's passes at the same offsets from its first upload."""
        self._creator_task.cancel()
        await asyncio.gather(self._creator_task, return_exceptions=True)
        self._creator_task = asyncio.ensure_future(self._creator_loop())

    def add_task(self, name):
        """Provision one task of the configuration's VDAF on both
        aggregators; returns what a client and a collector need."""
        from janus_tpu.core.hpke import HpkeKeypair
        from janus_tpu.datastore import AggregatorTask, TaskQueryType
        from janus_tpu.messages import Duration, Role, TaskId

        seed = random.Random(name)
        task_id = TaskId(seed.randbytes(32))
        keys = {"leader": HpkeKeypair.generate(1), "helper": HpkeKeypair.generate(2)}
        common = dict(
            task_id=task_id,
            query_type=TaskQueryType.time_interval(),
            vdaf=self.config["vdaf"],
            vdaf_verify_key=seed.randbytes(16),
            min_batch_size=self.config["min_batch_size"],
            time_precision=Duration(self.config["time_precision_s"]),
            collector_hpke_config=self.collector_keys.config,
        )
        leader = AggregatorTask(
            peer_aggregator_endpoint=self.urls["helper"],
            role=Role.LEADER,
            aggregator_auth_token=self.agg_token,
            collector_auth_token_hash=self.col_token.hash(),
            hpke_keys=[keys["leader"]],
            **common,
        )
        helper = AggregatorTask(
            peer_aggregator_endpoint=self.urls["leader"],
            role=Role.HELPER,
            aggregator_auth_token_hash=self.agg_token.hash(),
            hpke_keys=[keys["helper"]],
            **common,
        )
        self.datastores["leader"].run_tx("put", lambda tx: tx.put_aggregator_task(leader))
        self.datastores["helper"].run_tx("put", lambda tx: tx.put_aggregator_task(helper))
        self.tasks[name] = (leader, helper)
        return task_id, keys["leader"].config, keys["helper"].config

    def backend(self, name):
        """The one backend of the task's shape, which the process-wide
        executor shares between the leader's driver and the helper."""
        leader, _helper = self.tasks[name]
        return self.agg_driver._backend_for(leader, leader.vdaf_instance())

    async def warm(self, name):
        """Compile the task's prepare executables before traffic, through
        the executor's own warmup (what the aggregation-driver binary's
        registry walk does at startup with ``warmup_rows`` set)."""
        from janus_tpu.executor import peek_global_executor
        from janus_tpu.vdaf.canonical import backend_shape_key

        backend = self.backend(name)
        if not hasattr(backend, "stage_prep_init_multi"):
            # no device path (the oracle): the executor schedules no warmup
            return {"backend": type(backend).__name__, "ledger": {}}
        ex = peek_global_executor()
        loop, key = asyncio.get_running_loop(), backend_shape_key(backend)
        # waited for a second at a time: a run that gives up in set-up then
        # leaves no thread of the loop's pool waiting out a compile
        while True:
            warm = await loop.run_in_executor(None, ex.wait_warm, key, 1.0)
            if warm or not any(s["state"] == "warming" for s in ex.compile_stats().values()):
                break
        if not warm:
            raise FleetFailure(f"warmup of {name} failed: {ex.compile_stats()}")
        return {
            "canonical_twin": bool(getattr(backend, "canonical", False)),
            "backend": type(backend).__name__,
            "ledger": ex.compile_stats(),
        }

    def collector(self, name, task_id):
        from janus_tpu.collector import Collector

        leader, _helper = self.tasks[name]
        return Collector(
            task_id=task_id,
            leader_endpoint=self.urls["leader"],
            vdaf=leader.vdaf_instance(),
            auth_token=self.col_token,
            hpke_keypair=self.collector_keys,
            poll_interval=0.5,
            max_poll_time=120.0,
        )

    async def collect(self, name, task_id, time_s):
        from janus_tpu.messages import Duration, Interval, Query, Time

        return await self.collector(name, task_id).collect(
            Query.new_time_interval(
                Interval(Time(time_s), Duration(self.config["time_precision_s"]))
            )
        )

    async def stop(self):
        from janus_tpu.executor import peek_global_executor

        if self._stop is not None:
            self._stop.set()
            await asyncio.gather(self._creator_task, *self._loops, return_exceptions=True)
        await self.agg_driver.shutdown()
        await self.col_driver.close()
        for agg in self.aggregators.values():
            await agg.shutdown()
        ex = peek_global_executor()
        if ex is not None:
            await ex.drain()
            ex.shutdown(drain=True)
        for runner in self._runners:
            await runner.cleanup()
        for ds in self.datastores.values():
            ds.close()


class JobWatch:
    """Stamps, on the monotonic clock, when each aggregation job of one task
    is first seen FINISHED, with the reports it finished.

    A thread of its own reads the leader's sqlite file four times a second
    over a read-only connection (WAL: a reader blocks no writer), so the
    watch makes no transaction that the program counts, takes no lock of
    ``Datastore`` and no thread of the fleet's pools."""

    def __init__(self, fleet, task_id, interval_s=0.25):
        self.path = fleet.datastores["leader"].path
        self.task_id, self.interval_s = task_id.data, interval_s
        self.finished_at = {}  # report id bytes -> monotonic seconds
        self.failed_reports = 0
        self.jobs_seen = 0
        self.jobs_abandoned = 0
        self._done_jobs = set()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-watch", daemon=True)

    def _scan(self, conn):
        jobs = conn.execute(
            """SELECT aj.id, aj.state FROM aggregation_jobs aj
               JOIN tasks t ON aj.task_id = t.id WHERE t.task_id = ?""",
            (self.task_id,),
        ).fetchall()
        new = [
            (
                pk,
                conn.execute(
                    "SELECT report_id, state FROM report_aggregations WHERE aggregation_job_id = ?",
                    (pk,),
                ).fetchall(),
            )
            for pk, state in jobs
            if state == "Finished" and pk not in self._done_jobs
        ]
        now = time.monotonic()
        self.jobs_seen = len(jobs)
        self.jobs_abandoned = sum(1 for _pk, state in jobs if state == "Abandoned")
        for pk, reports in new:
            self._done_jobs.add(pk)
            for rid, state in reports:
                if state == "Finished":
                    self.finished_at.setdefault(bytes(rid), now)
                else:
                    self.failed_reports += 1

    def _run(self):
        conn = sqlite3.connect(f"file:{self.path}?mode=ro", uri=True, timeout=5.0)
        try:
            last = False
            while not last:
                last = self._stop.wait(self.interval_s)  # one more scan after the stop
                try:
                    self._scan(conn)
                except sqlite3.OperationalError:
                    pass  # busy for longer than the timeout: the next scan sees it
        finally:
            conn.close()

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join()
