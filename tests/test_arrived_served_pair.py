"""The served pair under the deployments' 3 s flush window, on the CPU.

One leader and one helper as the benchmark composes them (``benchmark/
fleet.py``, the ``count_pair`` deployment at its rehearsal size but with
``flush_window_ms`` left at 3,000): a creator pass of two jobs is leased in
one discovery pass, and each of its three flushes — leader ``prep_init``,
helper ``prep_init``, helper ``combine`` — goes when the last announced job
has joined its bucket.  At the parent each waited its window out, 9 s a step.
"""

import asyncio
import json
import os
import sys
import time

import pytest

from janus_tpu.executor import reset_global_executor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")

JOB_SIZE = 16
JOBS = 2


@pytest.fixture
def bench_modules():
    """``benchmark/`` is not a package: its modules import each other by
    bare name, as ``python3 benchmark/run.py`` finds them."""
    sys.path.insert(0, BENCH)
    try:
        import fleet
        import prom
        import reference

        yield fleet, prom, reference
    finally:
        sys.path.remove(BENCH)
        reset_global_executor()


def _config():
    config = json.load(open(os.path.join(BENCH, "configs", "count_pair.json")))
    window_ms = config["device_executor"]["flush_window_ms"]
    for group, values in config["rehearse"].items():
        config[group].update(values)
    # the rehearsal's size, the deployment's window
    config["device_executor"]["flush_window_ms"] = window_ms
    config["job_creator"]["max_aggregation_job_size"] = JOB_SIZE
    # no creator pass but the one this test makes, once every report is in
    config["job_creator"]["aggregation_job_creation_interval_s"] = 3600.0
    return config


async def _upload(url, vdaf, task_id, leader_cfg, helper_cfg, precision, when, measurements):
    import aiohttp

    from janus_tpu.client import prepare_report

    bodies = [
        prepare_report(
            vdaf, task_id, leader_cfg, helper_cfg, precision, m, time=when
        ).get_encoded()
        for m in measurements
    ]
    async with aiohttp.ClientSession() as session:
        for body in bodies:
            async with session.put(url, data=body) as resp:
                assert resp.status == 201, await resp.text()


def test_two_job_pass_steps_under_the_window_and_collects_the_plain_count(
    tmp_path, bench_modules
):
    fleet_mod, prom, reference = bench_modules
    from janus_tpu.messages import Time

    config = _config()
    assert config["device_executor"]["flush_window_ms"] == 3000.0
    # two passes of two jobs: the first compiles what the executor's warmup
    # does not (combine, aggregate), the second is the one that is timed
    measurements = [i % 3 == 0 for i in range(2 * JOB_SIZE * JOBS)]
    half = len(measurements) // 2

    async def flow():
        reset_global_executor()
        fleet = fleet_mod.Fleet(str(tmp_path), config)
        await fleet.start()
        try:
            task_id, leader_cfg, helper_cfg = fleet.add_task("pair")
            await fleet.warm("pair")
            leader, _helper = fleet.tasks["pair"]
            precision = leader.time_precision
            when = Time(int(time.time()) // precision.seconds * precision.seconds)
            watch = fleet_mod.JobWatch(fleet, task_id, interval_s=0.05)
            watch.start()

            async def one_pass(batch, done):
                await _upload(
                    fleet.urls["leader"] + f"tasks/{task_id}/reports",
                    leader.vdaf_instance(), task_id, leader_cfg, helper_cfg,
                    precision, when, batch,
                )
                await fleet.creator.run_once()
                deadline = time.monotonic() + 60.0
                while len(watch.finished_at) < done:
                    assert time.monotonic() < deadline, "the pass never finished"
                    await asyncio.sleep(0.05)

            await one_pass(measurements[:half], half)
            before = prom.snapshot()
            await one_pass(measurements[half:], len(measurements))
            watch.stop()
            after = prom.snapshot()
            collected = await fleet.collect("pair", task_id, when.seconds)
        finally:
            await fleet.stop()
        return before, after, collected

    loop = asyncio.new_event_loop()
    try:
        before, after, collected = loop.run_until_complete(
            asyncio.wait_for(flow(), 240.0)
        )
    finally:
        loop.close()

    step = {"job_type": "aggregation"}
    family = "janus_job_step_duration_seconds"
    assert prom.delta(before, after, family + "_count", step) == JOBS
    # at the parent each step took three windows, 9 s
    assert prom.mean(before, after, family, step) < 1.5
    assert prom.delta(before, after, family + "_bucket", {**step, "le": "2.5"}) == JOBS

    flushes = "janus_executor_flushes_total"
    assert prom.delta(before, after, flushes, {"trigger": "arrived"}) == 3
    assert prom.delta(before, after, flushes) == 3
    for bucket in ("Count/a0/prep_init*", "Count/a1/prep_init*", "Count/a1/combine*"):
        assert prom.delta(before, after, flushes, {"bucket": bucket, "trigger": "arrived"}) == 1
    # one flush of both jobs each, every report on the device in both roles
    assert prom.delta(before, after, "janus_device_prepare_reports_total") == 2 * half
    assert prom.delta(before, after, "janus_device_prepare_launches_total") == 2

    assert collected.report_count == len(measurements)
    want = reference.plain_aggregate(config["vdaf"], measurements)
    assert reference.mismatched_positions(collected.aggregate_result, want) == 0
