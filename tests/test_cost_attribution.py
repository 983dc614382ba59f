"""Device-plane cost attribution + flight recorder (ISSUE 12).

Fast by design: scheduling/attribution logic runs against fake backends
(no jax, no compiles); the only real-VDAF piece is the pure-Python CPU
oracle (prio3_count), so the whole module stays inside the tier-1 budget.

Covers the acceptance criteria directly:
* attribution is CONSERVATIVE — per-task seconds sum to the measured
  flush totals within 1e-6 for multi-task mega-batches, the
  oracle-fallback path, and mesh-padded tails (11%8-style uneven flush);
* attribution is BOUNDED — task-label cardinality capped with the
  ``other`` overflow label, series retired on the sampler-tick pattern;
* the flight-recorder ring is O(N) bounded, records every flush shape,
  and dumps exactly once per breaker trip (+ rate-limited slow-flush
  anomalies);
* ``tools/cost_report.py`` renders the per-task rollup from a /statusz +
  /metrics pair.
"""

import asyncio
import base64
import json
import logging
import threading
import time
from types import SimpleNamespace

import pytest

from janus_tpu.core import costs
from janus_tpu.core.metrics import GLOBAL_METRICS
from janus_tpu.executor import (
    DeviceExecutor,
    ExecutorConfig,
    ExecutorOverloadedError,
    reset_global_executor,
)
from janus_tpu.executor.flight_recorder import DUMP_MARKER, FlightRecorder
from janus_tpu.fields import next_power_of_2


@pytest.fixture(autouse=True)
def _clean_cost_model():
    costs.reset_cost_model()
    yield
    costs.reset_cost_model()


def _run(coro, timeout=30.0):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


def _label(ident: bytes) -> str:
    return base64.urlsafe_b64encode(ident).rstrip(b"=").decode()


def _task_seconds(label, phase, path):
    return (
        GLOBAL_METRICS.get_sample_value(
            "janus_task_device_seconds_total",
            {"task": label, "phase": phase, "path": path},
        )
        or 0.0
    )


def _task_rows(label, outcome):
    return (
        GLOBAL_METRICS.get_sample_value(
            "janus_task_rows_total", {"task": label, "outcome": outcome}
        )
        or 0.0
    )


class _FakeVdaf:
    pass


class _FakeBackend:
    """Stage/launch seam double with controllable padding + latency."""

    def __init__(self, pad_multiple=None, stage_sleep=0.0, launch_sleep=0.0):
        self.vdaf = _FakeVdaf()
        self.pad_multiple = pad_multiple
        self.stage_sleep = stage_sleep
        self.launch_sleep = launch_sleep
        self.launches = []

    def _pad(self, rows):
        pad = next_power_of_2(rows)
        if self.pad_multiple:
            pad = max(pad, -(-rows // self.pad_multiple) * self.pad_multiple)
        return pad

    def stage_prep_init_multi(self, agg_id, requests, pad_to=None):
        rows = sum(len(r) for _, r in requests)
        if rows == 0:
            return None
        if self.stage_sleep:
            time.sleep(self.stage_sleep)
        return SimpleNamespace(
            agg_id=agg_id, placed=None, pad_to=pad_to or self._pad(rows), rows=rows
        )

    def launch_prep_init_multi(self, staged, requests):
        if self.launch_sleep:
            time.sleep(self.launch_sleep)
        self.launches.append([len(r) for _, r in requests])
        return [
            [("prep", vk, i) for i in range(len(reports))]
            for vk, reports in requests
        ]


# ---------------------------------------------------------------------------
# the model itself: cardinality bound + retirement


def test_label_rendering_matches_taskid_b64url():
    ident = bytes(range(32))
    assert costs.task_label(ident) == _label(ident)
    assert costs.task_label(None) == costs.UNATTRIBUTED_LABEL
    assert costs.task_label("already-a-string") == "already-a-string"


def test_cardinality_cap_overflows_to_other_and_retires():
    model = costs.TaskCostModel(max_tasks=2)
    a, b, c = b"A" * 32, b"B" * 32, b"C" * 32
    assert model.label_for(a) == _label(a)
    assert model.label_for(b) == _label(b)
    # beyond the cap: the newcomer lands on the overflow label, counted
    assert model.label_for(c) == costs.OVERFLOW_LABEL
    assert model.overflowed == 1
    assert model.stats() == {"tracked": 2, "cap": 2, "overflowed": 1}
    # a known task keeps its label (and refreshes recency)
    assert model.label_for(a) == _label(a)
    # retirement frees idle slots AND removes their series
    model.attribute_direct(b, "launch", "device", 1.0)
    assert _task_seconds(_label(b), "launch", "device") == 1.0
    with model._lock:
        for e in model._entries.values():
            e.last_used -= 10_000
    assert model.retire_idle(600) == 2
    assert model.stats()["tracked"] == 0
    assert (
        GLOBAL_METRICS.get_sample_value(
            "janus_task_device_seconds_total",
            {"task": _label(b), "phase": "launch", "path": "device"},
        )
        is None
    ), "retirement must remove the retired task's series"
    # the slot freed: C is admitted under its own label now
    assert model.label_for(c) == _label(c)


def test_attribute_flush_is_conservative_and_proportional():
    model = costs.TaskCostModel(max_tasks=8)
    a, b = b"\x01" * 32, b"\x02" * 32
    before = {
        t: _task_seconds(_label(t), "launch", "device") for t in (a, b)
    }
    model.attribute_flush([(a, 30), (b, 10)], {"launch": 4.0}, path="device")
    da = _task_seconds(_label(a), "launch", "device") - before[a]
    db = _task_seconds(_label(b), "launch", "device") - before[b]
    assert abs(da - 3.0) < 1e-9 and abs(db - 1.0) < 1e-9
    assert abs((da + db) - 4.0) < 1e-9


# ---------------------------------------------------------------------------
# conservation through the REAL flush path


def test_multi_task_mega_batch_attribution_conserves_measured_totals():
    """ISSUE 12 acceptance: sum over tasks of attributed seconds == the
    measured flush totals (to 1e-6) for a multi-task mega-batch."""
    backend = _FakeBackend(stage_sleep=0.01, launch_sleep=0.02)
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.01, flush_max_rows=10_000))
    idents = [b"\x11" * 32, b"\x22" * 32, b"\x33" * 32]
    labels = [_label(i) for i in idents]
    before = {
        (t, ph): _task_seconds(t, ph, "device")
        for t in labels
        for ph in ("stage", "launch")
    }

    async def go():
        return await asyncio.gather(
            *(
                ex.submit(
                    ("s",),
                    "prep_init",
                    (b"k%d" % n, [0] * rows),
                    backend=backend,
                    task_ident=ident,
                )
                for n, (ident, rows) in enumerate(zip(idents, (7, 5, 3)))
            )
        )

    _run(go())
    ex.shutdown()
    (rec,) = ex.flight_stats(1)["records"]
    assert rec["outcome"] == "ok" and rec["rows"] == 15
    assert sorted(rec["tasks"]) == sorted(labels)
    for phase, measured_ms in (("stage", rec["stage_ms"]), ("launch", rec["launch_ms"])):
        attributed = sum(
            _task_seconds(t, phase, "device") - before[(t, phase)] for t in labels
        )
        assert abs(attributed - measured_ms / 1000.0) < 1e-6, (phase, attributed)
    # rows land per task with outcome=ok
    assert _task_rows(labels[0], "ok") >= 7
    # per-submission queue delay fed the task histogram
    for t in labels:
        assert (
            GLOBAL_METRICS.get_sample_value(
                "janus_task_queue_delay_seconds_count", {"task": t}
            )
            or 0
        ) >= 1


def test_padded_tail_flush_counts_pad_rows_and_conserves():
    """Mesh-tail shape (11 rows padded to 16, the 11%8 uneven flush):
    pad waste is counted per bucket and attribution still sums to the
    measured totals — padding overhead rides with the rows that caused
    it, never on a phantom task."""
    backend = _FakeBackend(pad_multiple=8, launch_sleep=0.02)
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.01, flush_max_rows=10_000))
    a, b = b"\x44" * 32, b"\x55" * 32
    la, lb = _label(a), _label(b)
    before = {t: _task_seconds(t, "launch", "device") for t in (la, lb)}

    async def go():
        return await asyncio.gather(
            ex.submit(("m",), "prep_init", (b"k1", [0] * 6), backend=backend, task_ident=a),
            ex.submit(("m",), "prep_init", (b"k2", [0] * 5), backend=backend, task_ident=b),
        )

    _run(go())
    ex.shutdown()
    (rec,) = ex.flight_stats(1)["records"]
    assert rec["rows"] == 11 and rec["padded_rows"] == 5
    bucket = rec["bucket"]
    assert (
        GLOBAL_METRICS.get_sample_value(
            "janus_executor_pad_rows_total", {"bucket": bucket}
        )
        == 5.0
    )
    attributed = sum(_task_seconds(t, "launch", "device") - before[t] for t in (la, lb))
    assert abs(attributed - rec["launch_ms"] / 1000.0) < 1e-6
    # proportionality: task A carried 6/11 of the flush
    da = _task_seconds(la, "launch", "device") - before[la]
    assert abs(da - (rec["launch_ms"] / 1000.0) * 6 / 11) < 1e-6


def test_oracle_path_attribution_conserves_measured_batch_time():
    """The oracle-fallback side of conservation: the thread-scope hook
    attributes exactly the duration _observe_prepare measured, so the
    task's path="oracle" delta equals the oracle histogram's sum delta."""
    from janus_tpu.vdaf.backend import OracleBackend
    from janus_tpu.vdaf.instances import prio3_count

    vdaf = prio3_count()
    oracle = OracleBackend(vdaf)
    ident = b"\x66" * 32
    label = _label(ident)
    rows = []
    for i in range(3):
        nonce = bytes([i]) * vdaf.NONCE_SIZE
        ps, shares = vdaf.shard(i % 2, nonce, bytes([i + 1]) * vdaf.RAND_SIZE)
        rows.append((nonce, ps, shares[0]))
    vk = b"\x00" * vdaf.VERIFY_KEY_SIZE
    secs_before = _task_seconds(label, "init", "oracle")
    hist_before = (
        GLOBAL_METRICS.get_sample_value(
            "janus_vdaf_prepare_duration_seconds_sum",
            {"backend": "oracle", "phase": "init"},
        )
        or 0.0
    )
    out = costs.run_in_task_scope(
        ident, lambda: oracle.prep_init_batch(vk, 0, rows)
    )
    assert len(out) == 3
    attributed = _task_seconds(label, "init", "oracle") - secs_before
    measured = (
        GLOBAL_METRICS.get_sample_value(
            "janus_vdaf_prepare_duration_seconds_sum",
            {"backend": "oracle", "phase": "init"},
        )
        or 0.0
    ) - hist_before
    assert measured > 0
    assert abs(attributed - measured) < 1e-6
    # outside a scope the hook is a no-op (no double counting for
    # executor flushes, which attribute via attribute_flush)
    assert costs.current_task() is None
    before = _task_seconds(label, "init", "oracle")
    oracle.prep_init_batch(vk, 0, rows)
    assert _task_seconds(label, "init", "oracle") == before


def test_driver_oracle_fallback_attributes_with_task_scope():
    """An open circuit degrades the job to the oracle AND moves its cost
    to path="oracle" on the task's series (the breaker cost shift the
    label exists to show)."""
    from janus_tpu.aggregator.aggregation_job_driver import (
        AggregationJobDriver,
        DriverConfig,
    )
    from janus_tpu.vdaf.backend import OracleBackend
    from janus_tpu.vdaf.instances import prio3_count

    reset_global_executor()
    try:
        driver = AggregationJobDriver(
            datastore=None,
            session_factory=None,
            config=DriverConfig(
                vdaf_backend="tpu",
                device_executor=ExecutorConfig(
                    enabled=True, breaker_failure_threshold=1
                ),
            ),
        )
        vdaf = prio3_count()
        backend = OracleBackend(vdaf)  # .oracle-less: oracle_backend_for -> .oracle? uses getattr
        backend.oracle = backend  # its own oracle (fallback chokepoint)
        ident = b"\x77" * 32
        label = _label(ident)
        nonce = b"\x01" * vdaf.NONCE_SIZE
        ps, shares = vdaf.shard(1, nonce, b"\x02" * vdaf.RAND_SIZE)
        prep_in = [(nonce, ps, shares[0])]
        before = _task_seconds(label, "init", "oracle")
        out = _run(
            driver._oracle_fallback(
                backend,
                b"\x00" * vdaf.VERIFY_KEY_SIZE,
                prep_in,
                "circuit open (test)",
                task_ident=ident,
            )
        )
        assert len(out) == 1
        assert _task_seconds(label, "init", "oracle") > before
    finally:
        reset_global_executor()


# ---------------------------------------------------------------------------
# rows outcomes: rejected + error


def test_rejected_and_error_rows_are_attributed():
    class _Exploding(_FakeBackend):
        def launch_prep_init_multi(self, staged, requests):
            raise RuntimeError("device on fire")

    ident = b"\x88" * 32
    label = _label(ident)
    rej_before = _task_rows(label, "rejected")
    err_before = _task_rows(label, "error")

    # deadline rejection
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.05, flush_max_rows=10_000))

    async def rejected():
        with pytest.raises(ExecutorOverloadedError):
            await ex.submit(
                ("s",),
                "prep_init",
                (b"k", [0, 0]),
                backend=_FakeBackend(),
                deadline_s=1e-4,
                task_ident=ident,
            )

    _run(rejected())
    ex.shutdown()
    assert _task_rows(label, "rejected") - rej_before == 2

    # launch failure
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.01, flush_max_rows=10_000))

    async def errored():
        with pytest.raises(RuntimeError):
            await ex.submit(
                ("s",),
                "prep_init",
                (b"k", [0, 0, 0]),
                backend=_Exploding(),
                task_ident=ident,
            )

    _run(errored())
    (rec,) = ex.flight_stats(1)["records"]
    ex.shutdown()
    assert _task_rows(label, "error") - err_before == 3
    assert rec["outcome"] == "error" and "device on fire" in rec["error"]
    assert rec["fault"] is False


# ---------------------------------------------------------------------------
# flight recorder


def test_flight_ring_is_bounded():
    fr = FlightRecorder(size=4)
    for i in range(10):
        fr.record(
            bucket="b",
            trigger="size",
            rows=i,
            padded_rows=0,
            tasks=[],
            queue_delay_max_s=0.0,
            stage_s=0.0,
            launch_s=0.001,
            outcome="ok",
            breaker_state="closed",
            fault=False,
        )
    snap = fr.snapshot(100)
    assert len(snap) == 4, "ring must stay O(size) bounded"
    assert [r["rows"] for r in snap] == [9, 8, 7, 6]  # newest first
    assert fr.stats()["recorded"] == 10


def test_breaker_trip_dumps_ring_exactly_once(caplog):
    class _Exploding(_FakeBackend):
        def launch_prep_init_multi(self, staged, requests):
            raise RuntimeError("boom")

    ex = DeviceExecutor(
        ExecutorConfig(
            flush_window_s=0.01,
            flush_max_rows=10_000,
            breaker_failure_threshold=2,
            breaker_reset_timeout_s=3600.0,
        )
    )
    backend = _Exploding()

    async def one(n):
        with pytest.raises(RuntimeError):
            await ex.submit(
                ("s",),
                "prep_init",
                (b"k%d" % n, [0]),
                backend=backend,
                task_ident=b"\x99" * 32,
            )

    with caplog.at_level(logging.WARNING, logger="janus_tpu.executor.flights"):
        _run(one(0))  # failure 1: no trip yet
        assert DUMP_MARKER not in caplog.text
        _run(one(1))  # failure 2: trips -> exactly one dump
    ex.shutdown()
    dumps = [r for r in caplog.records if DUMP_MARKER in r.getMessage()]
    assert len(dumps) == 1, "one trip, one dump"
    payload = json.loads(dumps[0].getMessage().split(DUMP_MARKER, 1)[1])
    assert payload["reason"] == "breaker_trip"
    assert payload["detail"]["consecutive_failures"] == 2
    # the ring inside the dump carries BOTH failing flushes (the second
    # was recorded before the breaker verdict fired the dump)
    assert [r["outcome"] for r in payload["flights"]] == ["error", "error"]
    assert ex.flight_stats()["dumps"] == {"breaker_trip": 1}


def test_slow_flush_anomaly_dumps_and_rate_limits(caplog):
    fr = FlightRecorder(size=64, slow_flush_p95_factor=4.0)

    def rec(launch_s):
        fr.record(
            bucket="b",
            trigger="size",
            rows=1,
            padded_rows=0,
            tasks=["t"],
            queue_delay_max_s=0.0,
            stage_s=0.0,
            launch_s=launch_s,
            outcome="ok",
            breaker_state=None,
            fault=False,
        )

    with caplog.at_level(logging.WARNING, logger="janus_tpu.executor.flights"):
        for _ in range(FlightRecorder.MIN_P95_SAMPLES):
            rec(0.010)
        assert DUMP_MARKER not in caplog.text, "baseline must not dump"
        rec(0.100)  # 10x the rolling p95 -> anomaly
        assert caplog.text.count(DUMP_MARKER) == 1
        rec(0.100)  # within the rate floor: suppressed
        assert caplog.text.count(DUMP_MARKER) == 1
    assert fr.stats()["dumps"] == {"slow_flush": 1}
    # the detector never fires when disabled
    fr2 = FlightRecorder(size=16, slow_flush_p95_factor=0.0)
    for _ in range(FlightRecorder.MIN_P95_SAMPLES):
        fr2.record(
            bucket="b", trigger="size", rows=1, padded_rows=0, tasks=[],
            queue_delay_max_s=0.0, stage_s=0.0, launch_s=0.001,
            outcome="ok", breaker_state=None, fault=False,
        )
    fr2.record(
        bucket="b", trigger="size", rows=1, padded_rows=0, tasks=[],
        queue_delay_max_s=0.0, stage_s=0.0, launch_s=5.0,
        outcome="ok", breaker_state=None, fault=False,
    )
    assert fr2.stats()["dumps"] == {}


def test_statusz_carries_flights_and_cost_sections():
    from janus_tpu.core.statusz import runtime_status
    from janus_tpu.executor import get_global_executor

    reset_global_executor()
    try:
        ex = get_global_executor(
            ExecutorConfig(flush_window_s=0.01, flush_max_rows=10_000)
        )

        async def go():
            await ex.submit(
                ("s",),
                "prep_init",
                (b"k", [0, 0]),
                backend=_FakeBackend(),
                task_ident=b"\xaa" * 32,
            )

        _run(go())
        doc = runtime_status()
        flights = doc["executor"]["flights"]
        assert flights["ring_size"] == ex.config.flight_recorder_size
        assert flights["recorded"] >= 1
        assert flights["records"][0]["outcome"] == "ok"
        cost = doc["executor"]["cost_attribution"]
        assert cost["tracked"] >= 1 and cost["cap"] >= 1
    finally:
        reset_global_executor()


def test_executor_config_threads_flight_recorder_knobs():
    from janus_tpu.binaries.config import DeviceExecutorConfig

    cfg = DeviceExecutorConfig(
        enabled=True, flight_recorder_size=7, slow_flush_p95_factor=2.5
    )
    ec = cfg.to_executor_config()
    assert ec.flight_recorder_size == 7
    assert ec.slow_flush_p95_factor == 2.5
    ex = DeviceExecutor(ec)
    assert ex.flight_recorder.size == 7
    assert ex.flight_recorder.slow_flush_p95_factor == 2.5
    ex.shutdown()


# ---------------------------------------------------------------------------
# tools: cost_report


def test_cost_report_builds_rollup_from_statusz_and_metrics():
    from tools.cost_report import build_report, parse_metrics

    metrics_text = "\n".join(
        [
            'janus_task_device_seconds_total{task="tA",phase="stage",path="device"} 1.0',
            'janus_task_device_seconds_total{task="tA",phase="launch",path="device"} 3.0',
            'janus_task_device_seconds_total{task="tA",phase="init",path="oracle"} 1.0',
            'janus_task_rows_total{task="tA",outcome="ok"} 500',
            'janus_task_rows_total{task="tA",outcome="rejected"} 20',
            'janus_task_queue_delay_seconds_sum{task="tA"} 0.5',
            'janus_task_queue_delay_seconds_count{task="tA"} 100',
            'janus_executor_pad_rows_total{bucket="Count/a0/prep_init#abc"} 100',
            'janus_executor_flush_rows_sum{bucket="Count/a0/prep_init#abc"} 400',
        ]
    )
    samples = parse_metrics(metrics_text)
    assert samples["janus_task_rows_total"][
        (("outcome", "ok"), ("task", "tA"))
    ] == 500.0
    statusz = {
        "pid": 42,
        "uptime_s": 100.0,
        "executor": {
            "flights": {"ring_size": 256, "recorded": 7, "dumps": {}, "records": []},
            "cost_attribution": {"tracked": 1, "cap": 64, "overflowed": 0},
        },
    }
    report = build_report(statusz, metrics_text)
    t = report["tasks"]["tA"]
    assert t["device_s"] == 4.0 and t["oracle_s"] == 1.0
    assert t["oracle_share"] == 0.2
    assert t["rows"] == {"ok": 500, "rejected": 20}
    assert t["reports_per_s"] == 5.0  # 500 ok rows / 100s uptime
    assert t["queue_delay_mean_ms"] == 5.0
    b = report["buckets"]["Count/a0/prep_init#abc"]
    assert b["pad_rows"] == 100 and b["rows"] == 400
    assert b["pad_waste"] == 0.2  # 100 / (400 + 100)
    assert report["flights"]["recorded"] == 7
    from tools.cost_report import render

    text = render(report)
    assert "tA" in text and "pad" in text


def test_cost_report_live_roundtrip_through_global_metrics():
    """End-to-end: drive a real flush, render the report from the real
    /statusz document + /metrics exposition."""
    from janus_tpu.core.statusz import runtime_status
    from janus_tpu.executor import get_global_executor
    from tools.cost_report import build_report

    reset_global_executor()
    try:
        ex = get_global_executor(
            ExecutorConfig(flush_window_s=0.01, flush_max_rows=10_000)
        )
        ident = b"\xbb" * 32

        async def go():
            await ex.submit(
                ("s",),
                "prep_init",
                (b"k", [0] * 3),
                backend=_FakeBackend(pad_multiple=8),
                task_ident=ident,
            )

        _run(go())
        report = build_report(
            runtime_status(), GLOBAL_METRICS.export().decode()
        )
        task = report["tasks"][_label(ident)]
        assert task["rows"]["ok"] >= 3
        assert task["device_s"] >= 0
        assert report["cost_attribution"]["tracked"] >= 1
        # 3 rows padded to 8: THIS flush's bucket (by its flight-record
        # label — the global registry may carry other suites' buckets)
        label = ex.flight_stats(1)["records"][0]["bucket"]
        assert report["buckets"][label]["pad_rows"] >= 5
    finally:
        reset_global_executor()


def test_accumulator_drain_attributes_to_the_bucket_key_task():
    """Spill/drain cost rows (ISSUE 12): the per-bucket drain readback is
    device time spent FOR one task — attributed under phase="drain" from
    the bucket key's task slot (keys are (role, task, shape, ident, ...))."""
    import numpy as np

    from janus_tpu.executor.accumulator import (
        AccumulatorConfig,
        DeviceAccumulatorStore,
    )

    class _Field:
        @staticmethod
        def vec_add(a, b):
            return [x + y for x, y in zip(a, b)]

    class _Flp:
        OUTPUT_LEN = 2
        field = _Field

    class _Vdaf:
        flp = _Flp

    class _Backend:
        supports_resident_out_shares = True

        def __init__(self):
            self.vdaf = _Vdaf()

        def accumulate_rows(self, buffer, matrix, mask):
            delta = np.asarray(matrix)[mask].sum(axis=0)
            return delta if buffer is None else buffer + delta

        def read_accum_buffer(self, buffer):
            return [int(x) for x in np.asarray(buffer)]

    ident = b"\xcc" * 32
    label = _label(ident)
    store = DeviceAccumulatorStore(AccumulatorConfig(enabled=True))
    backend = _Backend()
    matrix = np.array([[1, 10], [2, 20]], dtype=np.int64)
    fid = store.retain_flush(backend, matrix, rows=2, nbytes=64)
    from janus_tpu.executor.accumulator import ResidentRef

    key = ("leader", ident, ("shape",), b"ident", b"")
    before = _task_seconds(label, "drain", "device")
    store.commit_rows(
        key,
        backend,
        [ResidentRef(fid, 0), ResidentRef(fid, 1)],
        job_token="j1",
        report_ids=[b"r1", b"r2"],
    )
    vector, rids = store.drain(key, _Field)
    assert vector == [3, 30] and rids == {b"r1", b"r2"}
    assert _task_seconds(label, "drain", "device") > before


def test_launch_dequeue_rejection_not_double_counted_as_error():
    """Review regression: a submission that expires at the LAUNCH dequeue
    is counted outcome="rejected" there; when the subsequent backend
    launch then raises, the error sweep must skip it — per-task row
    totals across outcomes must never exceed rows submitted."""

    class _SlowStageExplodingLaunch(_FakeBackend):
        def __init__(self):
            super().__init__(stage_sleep=0.15)

        def launch_prep_init_multi(self, staged, requests):
            raise RuntimeError("boom after stage")

    a, b = b"\xdd" * 32, b"\xee" * 32
    la, lb = _label(a), _label(b)
    before = {
        (t, o): _task_rows(t, o)
        for t in (la, lb)
        for o in ("rejected", "error", "ok")
    }
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.01, flush_max_rows=10_000))
    backend = _SlowStageExplodingLaunch()

    async def go():
        # A: no deadline — survives to the launch, which raises.
        # B: expires DURING the 0.15s stage, so the launch-side
        # _reject_expired rejects it before the backend raises.
        ra = asyncio.ensure_future(
            ex.submit(("s",), "prep_init", (b"ka", [0] * 2), backend=backend, task_ident=a)
        )
        rb = asyncio.ensure_future(
            ex.submit(
                ("s",),
                "prep_init",
                (b"kb", [0] * 3),
                backend=backend,
                task_ident=b,
                deadline_s=0.05,
            )
        )
        return await asyncio.gather(ra, rb, return_exceptions=True)

    out_a, out_b = _run(go())
    ex.shutdown()
    assert isinstance(out_a, RuntimeError)
    assert isinstance(out_b, (ExecutorOverloadedError, RuntimeError))
    da = {o: _task_rows(la, o) - before[(la, o)] for o in ("rejected", "error", "ok")}
    db = {o: _task_rows(lb, o) - before[(lb, o)] for o in ("rejected", "error", "ok")}
    # every submitted row is accounted EXACTLY once
    assert sum(da.values()) == 2, da
    assert sum(db.values()) == 3, db
    assert da == {"rejected": 0, "error": 2, "ok": 0}, da
    if isinstance(out_b, ExecutorOverloadedError):  # B expired at dequeue
        assert db == {"rejected": 3, "error": 0, "ok": 0}, db


def test_poplar_oracle_backend_name_lands_on_oracle_path():
    """Review regression: the CPU fallbacks are named "oracle" (Prio3)
    AND "poplar1-oracle" — both must attribute path="oracle", or the
    heavy-hitters breaker cost shift is invisible."""
    ident = b"\xff" * 32
    label = _label(ident)
    before = {
        p: _task_seconds(label, "init", p) for p in ("oracle", "device")
    }
    costs.run_in_task_scope(
        ident, lambda: costs.attribute_prepare("poplar1-oracle", "init", 0.25)
    )
    costs.run_in_task_scope(
        ident, lambda: costs.attribute_prepare("tpu-hybrid", "init", 0.25)
    )
    assert _task_seconds(label, "init", "oracle") - before["oracle"] == 0.25
    assert _task_seconds(label, "init", "device") - before["device"] == 0.25


def test_hybrid_per_row_oracle_rescue_does_not_double_attribute():
    """Review regression: tpu-hybrid's per-row oracle rescue runs INSIDE
    the enclosing device measurement — within a task scope its nested
    oracle batch must not attribute a second time (conservation: one
    measurement, attributed once).  Modeled at the costs layer: the
    rescue clears the scope, so only the outer device total lands."""
    ident = b"\xab" * 32
    label = _label(ident)
    before_o = _task_seconds(label, "init", "oracle")

    def hybrid_batch():
        # what HybridXofBackend.prep_init_batch now does for a bad row
        costs.run_in_task_scope(
            None, lambda: costs.attribute_prepare("oracle", "init", 0.1)
        )
        costs.attribute_prepare("tpu-hybrid", "init", 0.3)  # outer total

    before_d = _task_seconds(label, "init", "device")
    costs.run_in_task_scope(ident, hybrid_batch)
    assert _task_seconds(label, "init", "oracle") - before_o == 0.0
    assert _task_seconds(label, "init", "device") - before_d == 0.3
