#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that janus_tpu still starts on the chip.

Drives the served two-party aggregation path once, on one TPU chip, through
the composition ``janus_tpu/binaries/main.py`` serves
(``janus_tpu/binaries/compose.py``): a leader and a helper ``Aggregator``
behind ``aggregator_app`` on loopback ports, the ``AggregationJobCreator``,
and the aggregation and collection drivers under the real ``JobDriver``
loop — real clock, real HTTP, sqlite datastores,
``vdaf_backend="tpu"`` and the process-wide device executor.  Clients are
``janus_tpu.client.prepare_report`` PUT to ``/tasks/<id>/reports``; the
result comes back through ``janus_tpu.collector.Collector``.

Phases (no arguments, one chip):

1. ``histogram``: Prio3Histogram(length 1024, chunk 316) — BASELINE.json
   configs[2] at full width — 2,048 uploads, time-interval query.  The
   executor serves it from the CANONICAL row-major graphs.
2. ``count``: Prio3Count, 256 uploads, through the same fleet: a second
   shape bucket and a second set of executables.
3. ``planar``: the same histogram VDAF through ``TpuBackend`` with
   ``canonical=False`` at 1,024 rows on both sides (``prep_init_multi``,
   then combine) — the limb-planar Pallas layout, which the default
   executor path does not reach — compared limb for limb with
   ``OracleBackend`` on 16 rows.

``--chips 4`` runs instead, and only, the four-chip phase: one 4,096-row
histogram mega-batch through ``MeshBackend`` over the host's four chips on
both sides, plus ``aggregate_batch`` (the cross-chip sum), compared with the
one-device ``TpuBackend`` on the same rows.

Every line of standard output is one JSON object.  The LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`` and
the exit code 0 only if every phase ran on the device and every collected
aggregate equals the plain count of the measurements, which come from
``--seed``.  With no accelerator — or outside the repository — it prints no
result and exits non-zero.  Timings printed here are smoke timings (one cold
run, compilation included), not rates.

One process holds the chip: report generation runs in worker processes
that never import JAX, and nothing here starts a child that does.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import sys
import tempfile
import time
import traceback

HISTOGRAM = {"type": "Prio3Histogram", "length": 1024, "chunk_length": 316}
COUNT = {"type": "Prio3Count"}
#: Set-up of the device executor that differs from its defaults, and why.
#: On the chip a cold prepare shape compiles for minutes on the launch
#: thread — longer than the executor's 30 s submit deadline and the
#: drivers' 30 s HTTP attempt timeout — so the shapes are compiled before
#: traffic by the executor's own warmup (as README "Running on a TPU host"
#: tells operators to), and every flush pads up to the warmed executable.
#: The default 5 ms flush window never coalesces 256-report jobs whose host
#: side decode takes longer than that; 3 s does, so one launch carries the
#: whole mega-batch.
WARMUP_ROWS = 2048
FLUSH_WINDOW_MS = 3000.0
TIME_PRECISION_S = 3600


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


class SmokeFailure(Exception):
    """A check of the smoke did not hold; the message is the reason."""


# -- report generation (worker processes; never imports JAX) -----------------


def _make_reports(job):
    """Shard and seal one slice of measurements into encoded DAP Reports."""
    vdaf_desc, task_id, leader_cfg, helper_cfg, time_s, measurements = job
    from janus_tpu.client import prepare_report
    from janus_tpu.messages import Duration, HpkeConfig, TaskId, Time
    from janus_tpu.vdaf import vdaf_from_instance

    vdaf = vdaf_from_instance(vdaf_desc)
    leader, helper = HpkeConfig.get_decoded(leader_cfg), HpkeConfig.get_decoded(helper_cfg)
    return [
        prepare_report(
            vdaf,
            TaskId(task_id),
            leader,
            helper,
            Duration(TIME_PRECISION_S),
            m,
            time=Time(time_s),
        ).get_encoded()
        for m in measurements
    ]


def _make_shards(job):
    """Shard one slice of measurements without sealing (the launches that
    bypass HTTP): (nonce, public share, leader share, helper share) bytes."""
    vdaf_desc, seed, measurements = job
    from janus_tpu.vdaf import vdaf_from_instance

    vdaf = vdaf_from_instance(vdaf_desc)
    rng = random.Random(seed)
    out = []
    for m in measurements:
        nonce = rng.randbytes(vdaf.NONCE_SIZE)
        public, shares = vdaf.shard(m, nonce, rng.randbytes(vdaf.RAND_SIZE))
        out.append(
            (
                nonce,
                vdaf.encode_public_share(public),
                shares[0].encode(vdaf),
                shares[1].encode(vdaf),
            )
        )
    return out


def _slices(items, n):
    k = max(1, -(-len(items) // n))
    return [items[i : i + k] for i in range(0, len(items), k)]


def _measurements(vdaf_desc, n, rng):
    if vdaf_desc["type"] == "Prio3Histogram":
        return [rng.randrange(vdaf_desc["length"]) for _ in range(n)]
    return [rng.randrange(2) for _ in range(n)]


def _plain(vdaf_desc, measurements):
    """The plain reference: count the measurements."""
    if vdaf_desc["type"] == "Prio3Histogram":
        out = [0] * vdaf_desc["length"]
        for m in measurements:
            out[m] += 1
        return out
    return sum(measurements)


# -- metrics -----------------------------------------------------------------


def _metric(name, **labels):
    from janus_tpu.core.metrics import GLOBAL_METRICS

    return GLOBAL_METRICS.get_sample_value(name, labels) or 0.0


def _metric_total(name):
    """Sum of one sample name over every label set."""
    from janus_tpu.core.metrics import GLOBAL_METRICS

    total = 0.0
    for line in GLOBAL_METRICS.export().decode().splitlines():
        if line.startswith(name) and line[len(name) : len(name) + 1] in ("{", " "):
            total += float(line.rsplit(" ", 1)[1])
    return total


def _device_counters():
    return {
        "device_prepare_reports_tpu": _metric(
            "janus_device_prepare_reports_total", backend="tpu"
        ),
        "device_prepare_launches_tpu": _metric(
            "janus_device_prepare_launches_total", backend="tpu"
        ),
        "oracle_prepare_reports": sum(
            _metric("janus_vdaf_prepare_reports_total", backend="oracle", phase=p)
            for p in ("init", "combine")
        ),
        "backend_fallbacks": _metric_total("janus_vdaf_backend_fallback_total"),
        "circuit_transitions": _metric_total(
            "janus_executor_circuit_transitions_total"
        ),
        "executor_rejections": _metric_total("janus_executor_rejections_total"),
        "executor_pad_rows": _metric_total("janus_executor_pad_rows_total"),
    }


# -- the fleet ---------------------------------------------------------------


class Fleet:
    """One leader and one helper, built by the functions of
    binaries/compose.py that run_aggregator, run_aggregation_job_creator
    and _run_job_driver_binary of binaries/main.py call — in THIS process,
    which holds the chip."""

    def __init__(self, workdir):
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

        self.clock = RealClock()
        # A smoke's departures from the binaries' defaults, set on the
        # binaries' own config objects; compose.* maps them as for main.py.
        self.agg_cfg = AggregatorConfig(vdaf_backend="tpu")
        self.drv_cfg = JobDriverBinaryConfig(vdaf_backend="tpu")
        self.creator_cfg = JobCreatorConfig()
        for cfg in (self.agg_cfg, self.drv_cfg):
            cfg.device_executor.enabled = True
            cfg.device_executor.warmup_rows = WARMUP_ROWS
            cfg.device_executor.flush_window_ms = FLUSH_WINDOW_MS
        # the binaries' default is 10 s; a smoke has no idle fleet to spare,
        # so it looks for work every second
        self.drv_cfg.job_driver.job_discovery_interval_s = 1.0
        self.datastores = {
            role: Datastore(
                os.path.join(workdir, f"{role}.sqlite3"),
                Crypter([generate_key()]),
                self.clock,
            )
            for role in ("leader", "helper")
        }
        self.agg_token = AuthenticationToken.new_bearer("smoke-aggregator-token")
        self.col_token = AuthenticationToken.new_bearer("smoke-collector-token")
        self.collector_keys = HpkeKeypair.generate(9)
        self.urls = {}
        self._runners = []
        self._stop = None
        self._loops = []
        self.tasks = {}

    async def start(self):
        from aiohttp import web

        from janus_tpu.aggregator import (
            Aggregator,
            AggregationJobCreator,
            aggregator_app,
        )
        from janus_tpu.binaries import compose
        from janus_tpu.core import peer_health

        self.aggregators = {}
        for role, ds in self.datastores.items():
            agg = Aggregator(ds, self.clock, compose.aggregator_config(self.agg_cfg))
            runner = web.AppRunner(aggregator_app(agg))
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = runner.addresses[0][1]
            self.urls[role] = f"http://127.0.0.1:{port}/"
            self.aggregators[role] = agg
            self._runners.append(runner)

        leader_ds = self.datastores["leader"]
        jd = self.drv_cfg.job_driver
        peer_health.tracker().configure(
            failure_threshold=jd.peer_failure_threshold,
            suspect_dwell_s=jd.peer_suspect_dwell_s,
        )
        self.creator = AggregationJobCreator(
            leader_ds, compose.creator_config(self.creator_cfg)
        )
        self.agg_driver = compose.aggregation_driver(self.drv_cfg, leader_ds)
        self.col_driver = compose.collection_driver(self.drv_cfg, leader_ds)
        self._stop = asyncio.Event()
        self._loops = [
            asyncio.ensure_future(
                compose.job_driver(
                    kind, self.drv_cfg, leader_ds, self.clock, stepper
                ).run(self._stop)
            )
            for kind, stepper in (
                ("aggregation", self.agg_driver),
                ("collection", self.col_driver),
            )
        ]

    def add_task(self, name, vdaf_desc):
        """Provision one task on both aggregators; returns what a client
        and a collector need."""
        from janus_tpu.core.hpke import HpkeKeypair
        from janus_tpu.datastore import AggregatorTask, TaskQueryType
        from janus_tpu.messages import Duration, Role, TaskId

        task_id = TaskId.random()
        keys = {"leader": HpkeKeypair.generate(1), "helper": HpkeKeypair.generate(2)}
        common = dict(
            task_id=task_id,
            query_type=TaskQueryType.time_interval(),
            vdaf=vdaf_desc,
            vdaf_verify_key=random.Random(name).randbytes(16),
            min_batch_size=10,
            time_precision=Duration(TIME_PRECISION_S),
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

    async def warm(self, name):
        """Compile the task's prepare executables before traffic, through
        the executor's own warmup (what the aggregation-driver binary's
        registry walk does at startup with ``warmup_rows`` set).  Both
        roles share the process-wide executor, so one warmup serves the
        leader's and the helper's side.  Returns the ledger entry."""
        from janus_tpu.executor import peek_global_executor
        from janus_tpu.vdaf.canonical import backend_shape_key

        leader, _helper = self.tasks[name]
        backend = self.agg_driver._backend_for(leader, leader.vdaf_instance())
        shape_key = backend_shape_key(backend)
        ex = peek_global_executor()
        warm = await asyncio.get_running_loop().run_in_executor(
            None, lambda: ex.wait_warm(shape_key)
        )
        if not warm:
            raise SmokeFailure(f"warmup of {name} failed: {ex.compile_stats()}")
        return {
            "canonical_twin": bool(getattr(backend, "canonical", False)),
            "backend": type(backend).__name__,
            "ledger": ex.compile_stats(),
        }

    async def upload(self, task_id, reports):
        """PUT every report to the leader; returns (accepted, shed
        retries).  A 503 is the front door's admission control asking the
        client to come back, as a DAP client would."""
        import aiohttp

        url = f"{self.urls['leader']}tasks/{task_id}/reports"
        sem = asyncio.Semaphore(64)
        sheds = 0

        async def put(session, body):
            nonlocal sheds
            async with sem:
                for _ in range(50):
                    async with session.put(url, data=body) as resp:
                        if resp.status == 201:
                            return 1
                        if resp.status != 503:
                            raise SmokeFailure(
                                f"upload refused: {resp.status} {await resp.text()}"
                            )
                    sheds += 1
                    await asyncio.sleep(0.2)
                raise SmokeFailure("upload shed 50 times in a row")

        async with aiohttp.ClientSession() as session:
            done = await asyncio.gather(*(put(session, r) for r in reports))
        return sum(done), sheds

    async def aggregate(self, task_id, timeout_s=600.0):
        """Cut the uploaded reports into jobs with the creator's defaults
        and wait for the JobDriver loop to finish them; returns
        (jobs, finished report aggregations)."""
        from janus_tpu.datastore.models import (
            AggregationJobState,
            ReportAggregationState,
        )

        while await self.creator.run_once():
            pass
        ds = self.datastores["leader"]
        deadline = time.monotonic() + timeout_s
        while True:
            jobs = await ds.run_tx_async(
                "smoke_jobs", lambda tx: tx.get_aggregation_jobs_for_task(task_id)
            )
            states = [j.state for j in jobs]
            if any(s == AggregationJobState.ABANDONED for s in states):
                raise SmokeFailure("an aggregation job was abandoned")
            if jobs and all(s == AggregationJobState.FINISHED for s in states):
                break
            if time.monotonic() > deadline:
                raise SmokeFailure(f"aggregation not finished after {timeout_s}s: {states}")
            await asyncio.sleep(0.5)

        def finished(tx):
            return sum(
                ra.state == ReportAggregationState.FINISHED
                for j in jobs
                for ra in tx.get_report_aggregations_for_aggregation_job(
                    task_id, j.aggregation_job_id
                )
            )

        return len(jobs), await ds.run_tx_async("smoke_ras", finished)

    async def collect(self, name, task_id, time_s):
        from janus_tpu.collector import Collector
        from janus_tpu.messages import Duration, Interval, Query, Time

        leader, _helper = self.tasks[name]
        collector = Collector(
            task_id=task_id,
            leader_endpoint=self.urls["leader"],
            vdaf=leader.vdaf_instance(),
            auth_token=self.col_token,
            hpke_keypair=self.collector_keys,
            poll_interval=0.5,
            max_poll_time=300.0,
        )
        return await collector.collect(
            Query.new_time_interval(Interval(Time(time_s), Duration(TIME_PRECISION_S)))
        )

    async def stop(self):
        from janus_tpu.executor import peek_global_executor

        if self._stop is not None:
            self._stop.set()
            await asyncio.gather(*self._loops)
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


async def fleet_phase(fleet, name, vdaf_desc, task, reports, measurements, time_s, gen_s):
    """Upload -> create -> drive -> collect one task; checks the collected
    aggregate against the plain count.  Returns the phase's report line."""
    task_id = task[0]
    before = _device_counters()
    flights_before = _flight_count()
    t0 = time.monotonic()
    accepted, sheds = await fleet.upload(task_id, reports)
    t1 = time.monotonic()
    jobs, finished = await fleet.aggregate(task_id)
    t2 = time.monotonic()
    result = await fleet.collect(name, task_id, time_s)
    t3 = time.monotonic()
    after = _device_counters()
    expected = _plain(vdaf_desc, measurements)
    equal = result.aggregate_result == expected and result.report_count == len(reports)
    flights = _flights_since(flights_before)
    first_launch_s = sum(f["launch_ms"] for f in _first_of_each_shape(flights)) / 1000.0
    line = {
        "phase": name,
        "vdaf": vdaf_desc,
        "uploads_accepted": accepted,
        "upload_shed_retries": sheds,
        "aggregation_jobs": jobs,
        "reports_aggregated": finished,
        "report_count_collected": result.report_count,
        "collected_equals_plain_count": equal,
        "delta": {k: after[k] - before[k] for k in after},
        "flushes": [
            {
                k: f.get(k)
                for k in (
                    "bucket", "outcome", "layout", "rows", "padded_rows", "launch_ms", "trigger"
                )
            }
            for f in flights
        ],
        "smoke_timing_s": {
            "set_up_report_generation": round(gen_s, 1),
            "upload": round(t1 - t0, 1),
            "aggregate": round(t2 - t1, 1),
            "collect": round(t3 - t2, 1),
            "first_launch_of_each_shape": round(first_launch_s, 1),
        },
    }
    emit(line)
    if accepted != len(reports):
        raise SmokeFailure(f"{name}: {accepted} of {len(reports)} uploads accepted")
    if finished != len(reports):
        raise SmokeFailure(f"{name}: {finished} of {len(reports)} reports aggregated")
    if not equal:
        raise SmokeFailure(f"{name}: the collected aggregate differs from the plain count")
    # both roles prepared every report on the device
    if line["delta"]["device_prepare_reports_tpu"] < 2 * len(reports):
        raise SmokeFailure(
            f"{name}: janus_device_prepare_reports{{backend=tpu}} covers "
            f"{line['delta']['device_prepare_reports_tpu']} rows, not both roles' "
            f"{2 * len(reports)}"
        )
    return line


def check_fleet(min_flush_rows):
    """After the fleet phases: the device — not the CPU oracle — served
    them, from the layout the deployment is meant to run, in mega-batches."""
    flights = _flights_since(0)
    largest = max((f["rows"] for f in flights if f["outcome"] == "ok"), default=0)
    hist_layouts = {
        f["layout"]
        for f in flights
        if f["bucket"].startswith("Histogram/") and f.get("layout")
    }
    totals = _device_counters()
    emit(
        {
            "executor": {
                "largest_flush_rows": largest,
                "histogram_prep_layouts": sorted(hist_layouts),
                "totals": totals,
            }
        }
    )
    if totals["backend_fallbacks"] or totals["circuit_transitions"]:
        raise SmokeFailure(
            f"the CPU oracle served the fleet: {totals['backend_fallbacks']} "
            f"backend fallbacks, {totals['circuit_transitions']} breaker transitions"
        )
    if totals["oracle_prepare_reports"] > 1:
        raise SmokeFailure(
            f"the CPU oracle prepared {totals['oracle_prepare_reports']} rows "
            "of the fleet phases (device said ok == False)"
        )
    if hist_layouts != {"canonical-row-major"}:
        raise SmokeFailure(
            f"histogram launches ran as {hist_layouts}: a canonical twin failed to build"
        )
    if largest < min_flush_rows:
        raise SmokeFailure(f"no flush reached {min_flush_rows} rows (largest {largest})")


def _flight_count():
    from janus_tpu.executor import peek_global_executor

    ex = peek_global_executor()
    return ex.flight_recorder.recorded_total if ex is not None else 0


def _flights_since(count):
    from janus_tpu.executor import peek_global_executor

    ex = peek_global_executor()
    new = ex.flight_recorder.recorded_total - count
    return list(reversed(ex.flight_recorder.snapshot(new))) if new else []


def _first_of_each_shape(flights):
    seen, out = set(), []
    for f in flights:
        key = (f["bucket"], f["rows"] + f["padded_rows"])
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


# -- launches that bypass HTTP (planar phase, four-chip phase) ---------------


def _decode_shards(vdaf, shards, agg_id):
    """Worker output -> the (nonce, public share, input share) rows the
    backends take."""
    return [
        (
            nonce,
            vdaf.decode_public_share(public),
            vdaf.decode_input_share(agg_id, leader if agg_id == 0 else helper),
        )
        for nonce, public, leader, helper in shards
    ]


def planar_phase(shards, measurements, gen_s):
    """One launch per side in the limb-planar Pallas layout, then combine,
    against the plain-Python oracle on a 16-row slice."""
    from janus_tpu.ops.keccak_pallas import _pallas_mode
    from janus_tpu.vdaf import vdaf_from_instance
    from janus_tpu.vdaf.backend import OracleBackend, TpuBackend

    vdaf = vdaf_from_instance(HISTOGRAM)
    backend = TpuBackend(vdaf, canonical=False)
    oracle = OracleBackend(vdaf)
    rows = len(shards)
    vk = random.Random("planar").randbytes(vdaf.VERIFY_KEY_SIZE)
    mode = _pallas_mode()
    layouts = [backend.launch_layout(a, rows) for a in (0, 1)]
    line = {
        "phase": "planar",
        "vdaf": HISTOGRAM,
        "rows": rows,
        "pallas_mode": mode,
        "planar_eligible": [backend.bp.planar_eligible(a, rows) for a in (0, 1)],
        "layout": layouts,
    }
    if mode != "on" or layouts != ["planar", "planar"]:
        emit(line)
        raise SmokeFailure(f"planar phase: pallas mode {mode!r}, layouts {layouts}")
    timing = {"set_up_shard_generation": round(gen_s, 1)}
    outs = []
    for agg_id in (0, 1):
        t0 = time.monotonic()
        outs.append(backend.prep_init_multi(agg_id, [(vk, _decode_shards(vdaf, shards, agg_id))])[0])
        timing[f"prep_init_a{agg_id}_first_launch"] = round(time.monotonic() - t0, 1)
    bad = [o for side in outs for o in side if isinstance(o, Exception)]
    if bad:
        raise SmokeFailure(f"planar phase: {len(bad)} rows rejected: {bad[0]}")
    t0 = time.monotonic()
    prep_msgs = backend.prep_shares_to_prep_batch(
        [[outs[0][i][1], outs[1][i][1]] for i in range(rows)]
    )
    timing["combine_first_launch"] = round(time.monotonic() - t0, 1)
    rejected = sum(isinstance(m, Exception) for m in prep_msgs)

    # limb for limb against the oracle, 16 rows spread over the batch
    sample = list(range(0, rows, rows // 16))[:16]
    t0 = time.monotonic()
    mismatches = 0
    for agg_id in (0, 1):
        want = oracle.prep_init_batch(
            vk, agg_id, [_decode_shards(vdaf, [shards[i]], agg_id)[0] for i in sample]
        )
        for i, (state, share) in zip(sample, want):
            got_state, got_share = outs[agg_id][i]
            mismatches += (
                list(got_state.out_share) != list(state.out_share)
                or got_state.corrected_joint_rand_seed != state.corrected_joint_rand_seed
                or list(got_share.verifiers_share) != list(share.verifiers_share)
                or got_share.joint_rand_part != share.joint_rand_part
            )
    want_msgs = oracle.prep_shares_to_prep_batch(
        [[outs[0][i][1], outs[1][i][1]] for i in sample]
    )
    mismatches += sum(prep_msgs[i] != w for i, w in zip(sample, want_msgs))
    timing["oracle_16_rows"] = round(time.monotonic() - t0, 1)

    # and the whole batch end to end: both sides' out shares sum to the
    # plain histogram
    agg = [
        backend.aggregate_batch(
            backend.bp.jf.to_limbs(
                [x for state, _share in outs[a] for x in state.out_share]
            ).reshape(rows, vdaf.flp.OUTPUT_LEN, backend.bp.jf.n),
            [True] * rows,
        )
        for a in (0, 1)
    ]
    equal = vdaf.unshard(agg, rows) == _plain(HISTOGRAM, measurements)
    line.update(
        rows_rejected=rejected,
        oracle_rows_compared=2 * len(sample),
        oracle_mismatches=mismatches,
        aggregate_equals_plain_count=equal,
        smoke_timing_s=timing,
    )
    emit(line)
    if rejected or mismatches or not equal:
        raise SmokeFailure(
            f"planar phase: {rejected} rows rejected, {mismatches} oracle "
            f"mismatches, aggregate equal: {equal}"
        )


def mesh_phase(shards, measurements, gen_s):
    """--chips 4: one 4,096-row mega-batch through MeshBackend on both
    sides, and the cross-chip aggregate, against the one-device backend."""
    import jax
    import numpy as np

    from janus_tpu.vdaf import vdaf_from_instance
    from janus_tpu.vdaf.backend import MeshBackend, TpuBackend

    vdaf = vdaf_from_instance(HISTOGRAM)
    devices = jax.local_devices()
    mesh = MeshBackend(vdaf, devices=devices)
    single = TpuBackend(vdaf)
    rows = len(shards)
    vk = random.Random("mesh").randbytes(vdaf.VERIFY_KEY_SIZE)
    timing = {"set_up_shard_generation": round(gen_s, 1)}
    line = {
        "phase": "mesh",
        "vdaf": HISTOGRAM,
        "rows": rows,
        "devices": [str(d) for d in devices],
        "layout": [mesh.launch_layout(a, rows) for a in (0, 1)],
    }
    outputs = {"mesh": [], "single": []}
    for agg_id in (0, 1):
        # marshal once (host Python, the slow part), place per backend
        t0 = time.monotonic()
        staged = single.stage_prep_init_multi(
            agg_id, [(vk, _decode_shards(vdaf, shards, agg_id))]
        )
        timing[f"marshal_a{agg_id}"] = round(time.monotonic() - t0, 1)
        for backend, label in ((mesh, "mesh"), (single, "single")):
            placed = backend._place(dict(staged.placed))
            t0 = time.monotonic()
            out = backend._prep_fn(agg_id)(placed)
            jax.block_until_ready(out)
            timing[f"{label}_prep_init_a{agg_id}_first_launch"] = round(
                time.monotonic() - t0, 1
            )
            outputs[label].append(out)
    def where(array):
        """(partition spec, rows held by each device in mesh order)."""
        held = {s.device: int(s.data.shape[0]) for s in array.addressable_shards}
        return str(array.sharding.spec), [held.get(d, 0) for d in devices]

    shardings, per_device = {}, {}
    for agg_id, out in enumerate(outputs["mesh"]):
        for k, v in out.items():
            shardings[f"a{agg_id}.{k}"], per_device[f"a{agg_id}.{k}"] = where(v)
    mismatched = [
        f"a{agg_id}.{k}"
        for agg_id in (0, 1)
        for k in outputs["single"][agg_id]
        if not np.array_equal(
            np.asarray(outputs["mesh"][agg_id][k]), np.asarray(outputs["single"][agg_id][k])
        )
    ]
    # combine on the mesh, then the cross-chip masked sum of both sides
    has_jr = vdaf.flp.JOINT_RAND_LEN > 0
    results = {}
    for backend, label in ((mesh, "mesh"), (single, "single")):
        leader, helper = outputs[label]
        t0 = time.monotonic()
        comb = backend._combine()(
            [leader["verifiers"], helper["verifiers"]],
            [leader["joint_rand_part"], helper["joint_rand_part"]] if has_jr else [],
        )
        decide = np.asarray(comb["decide"]) & np.asarray(leader["ok"]) & np.asarray(helper["ok"])
        timing[f"{label}_combine_first_launch"] = round(time.monotonic() - t0, 1)
        if label == "mesh":
            shardings["combine.decide"], per_device["combine.decide"] = where(comb["decide"])
        t0 = time.monotonic()
        aggs = [
            backend.aggregate_batch(np.asarray(side["out_share"]), decide)
            for side in (leader, helper)
        ]
        timing[f"{label}_aggregate_first_launch"] = round(time.monotonic() - t0, 1)
        results[label] = (int(decide.sum()), vdaf.unshard(aggs, rows))
    # every output must put rows on every device: code that never met more
    # than one real chip may have put everything on the first
    holding = min(sum(1 for n in held if n) for held in per_device.values())
    line.update(
        mesh_shape=dict(mesh.mesh.shape),
        output_partition_spec=shardings,
        rows_per_device=per_device,
        devices_holding_rows=holding,
        mesh_vs_single_mismatched_outputs=mismatched,
        rows_decided_valid={k: v[0] for k, v in results.items()},
        mesh_aggregate_equals_single=results["mesh"][1] == results["single"][1],
        mesh_aggregate_equals_plain_count=results["mesh"][1] == _plain(HISTOGRAM, measurements),
        smoke_timing_s=timing,
    )
    emit(line)
    if (
        mismatched
        or holding != len(devices)
        or results["mesh"][0] != rows
        or not line["mesh_aggregate_equals_single"]
        or not line["mesh_aggregate_equals_plain_count"]
    ):
        raise SmokeFailure(
            f"mesh phase: mismatched outputs {mismatched}, {holding} of "
            f"{len(devices)} devices hold rows, {results['mesh'][0]} of {rows} rows "
            f"valid, aggregate equal single/plain: "
            f"{line['mesh_aggregate_equals_single']}/{line['mesh_aggregate_equals_plain_count']}"
        )


# -- the run -----------------------------------------------------------------


def _environment(device, chips, cache_dir):
    import importlib.metadata as md

    def version(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return None

    return {
        "device": device,
        "chips_asked": chips,
        "jax": version("jax"),
        "jaxlib": version("jaxlib"),
        "libtpu": version("libtpu"),
        "compile_cache_dir": cache_dir,
        "compile_cache_from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
    }


def _native_status():
    """Load (or build) native/libjanusts.so before the workers race to."""
    from janus_tpu import native

    existed = os.path.exists(native._LIB)
    lib = native.load()
    return {
        "native_xof": "unavailable"
        if lib is None
        else ("loaded" if existed else "built"),
        "path": native._LIB,
    }


def _frontdoor_line():
    from janus_tpu.core.hpke_batch import vector_pass_preferred

    aes = sys.modules.get("janus_tpu.ops.aes_jax")
    return {
        "front_door": {
            "aes_gcm_open_path": "vectorized table-AES kernel (jax)"
            if vector_pass_preferred()
            else "per-report AES-GCM (cryptography)",
            "vector_pass_preferred": vector_pass_preferred(),
            "aes_kernel_shapes_compiled": aes.encrypt_blocks_multikey._cache_size()
            if aes is not None
            else 0,
            "open_batches": _metric(
                "janus_upload_open_duration_seconds_count", backend="batched"
            ),
            "open_batch_seconds_total": round(
                _metric("janus_upload_open_duration_seconds_sum", backend="batched"), 3
            ),
            "upload_sheds": _metric_total("janus_upload_shed_total"),
        }
    }


def _count_cache_events():
    """Count JAX's persistent-cache hits and misses from here on."""
    from jax._src import monitoring

    events = {"hits": 0, "misses": 0}

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    monitoring.register_event_listener(on_event)
    return events


def _cache_line(cache_dir, events):
    entries = len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0
    return {"compile_cache": {"dir": cache_dir, "entries_now": entries, **events}}


async def one_chip(args, pool):
    rng = random.Random(args.seed)
    time_s = (int(time.time()) // TIME_PRECISION_S - 1) * TIME_PRECISION_S
    plans = [
        ("histogram", HISTOGRAM, args.histogram_uploads),
        ("count", COUNT, args.count_uploads),
    ]
    planar_meas = _measurements(HISTOGRAM, args.planar_rows, rng)
    with tempfile.TemporaryDirectory(prefix="janus-smoke-") as workdir:
        fleet = Fleet(workdir)
        await fleet.start()
        try:
            pending = []
            t_gen = time.monotonic()
            gen_s = {}  # name -> seconds from start until its reports were made

            def made(name):
                return lambda _result: gen_s.__setitem__(name, time.monotonic() - t_gen)

            for name, desc, n in plans:
                task = fleet.add_task(name, desc)
                meas = _measurements(desc, n, rng)
                jobs = [
                    (desc, task[0].data, task[1].get_encoded(), task[2].get_encoded(), time_s, s)
                    for s in _slices(meas, 4 * args.workers)
                ]
                pending.append(
                    (name, desc, task, meas, pool.map_async(_make_reports, jobs, callback=made(name)))
                )
            planar_async = pool.map_async(
                _make_shards,
                [
                    (HISTOGRAM, args.seed * 1000 + i, s)
                    for i, s in enumerate(_slices(planar_meas, 4 * args.workers))
                ],
                callback=made("planar"),
            )
            # the chip compiles while the workers shard
            for name, _desc, _task, _meas, _res in pending:
                t0 = time.monotonic()
                emit({"warmup": name, **await fleet.warm(name),
                      "smoke_timing_s": {"compile_warmup": round(time.monotonic() - t0, 1)}})
            loop = asyncio.get_running_loop()
            for name, desc, task, meas, res in pending:
                reports = [
                    r for chunk in await loop.run_in_executor(None, res.get) for r in chunk
                ]
                await fleet_phase(fleet, name, desc, task, reports, meas, time_s, gen_s[name])
            emit(_frontdoor_line())
            check_fleet(args.min_flush_rows)
        finally:
            await fleet.stop()
    shards = [s for chunk in planar_async.get() for s in chunk]
    planar_phase(shards, planar_meas, gen_s["planar"])


def four_chips(args, pool):
    rng = random.Random(args.seed)
    meas = _measurements(HISTOGRAM, args.mesh_rows, rng)
    t0 = time.monotonic()
    chunks = pool.map(
        _make_shards,
        [
            (HISTOGRAM, args.seed * 1000 + i, s)
            for i, s in enumerate(_slices(meas, 4 * args.workers))
        ],
    )
    mesh_phase([s for c in chunks for s in c], meas, time.monotonic() - t0)


def run(args) -> dict:
    """Run the smoke; returns the device dict of the last line.  Raises on
    any failure — nothing here turns one into exit code 0."""
    import multiprocessing

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu" and not args.allow_cpu:
        raise SmokeFailure(
            f"JAX found no accelerator: jax.devices()[0].platform is "
            f"{device['platform']!r}, not 'tpu'"
        )
    if device["count"] != args.chips:
        raise SmokeFailure(f"--chips {args.chips} but JAX reports {device['count']} devices")

    from janus_tpu.utils.jax_setup import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_events = _count_cache_events()
    emit(_environment(device, args.chips, cache_dir))
    emit(_native_status())
    # spawn, not fork: this process has threads and holds the chip; the
    # workers import nothing of JAX
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(args.workers) as pool:
        if args.chips == 4:
            four_chips(args, pool)
        else:
            asyncio.run(one_chip(args, pool))
    emit(_cache_line(cache_dir, cache_events))
    return device


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=21, help="measurements come from it")
    parser.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4: run the four-chip MeshBackend phase and nothing else",
    )
    args = parser.parse_args(argv)
    # sizes are the deployment's, not options; a rehearsal on the CPU (a
    # scratch script, never the driver) overrides them on the namespace
    args.histogram_uploads = 2048
    args.count_uploads = 256
    args.planar_rows = 1024
    args.mesh_rows = 4096
    args.min_flush_rows = 1024
    args.allow_cpu = False
    args.workers = max(2, min(12, (os.cpu_count() or 4) - 2))
    return finish(args)


def finish(args) -> int:
    t0 = time.monotonic()
    try:
        device = run(args)
    except BaseException as e:
        traceback.print_exc()
        reason = str(e) if isinstance(e, SmokeFailure) else f"{type(e).__name__}: {e}"
        emit({"ok": False, "reason": reason, "seconds": round(time.monotonic() - t0, 1)})
        return 1
    emit({"smoke_seconds": round(time.monotonic() - t0, 1)})
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    try:
        import janus_tpu  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke.py runs from the root of the repository: {e}", file=sys.stderr)
        sys.exit(2)
    sys.exit(main())
