"""Device executor: continuous cross-job batching (janus_tpu/executor/).

Scheduling-logic tests (bucketing, flush triggers, backpressure, deadline
rejection) run against a fake backend — no jax, no compiles.  Parity
tests (results byte-identical to the oracle under coalescing) use the
real TpuBackend on the cheapest shape; the heavier multi-shape
integration lives in tests/test_multitask.py.
"""

import asyncio
import threading
import time
from types import SimpleNamespace

import pytest

from janus_tpu.executor import (
    DeviceExecutor,
    ExecutorConfig,
    ExecutorOverloadedError,
    bucket_label,
    reset_global_executor,
)
from janus_tpu.fields import next_power_of_2
from janus_tpu.utils.test_util import det_rng
from janus_tpu.vdaf.instances import prio3_count


def _run(coro, timeout=30.0):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


class _FakeVdaf:
    pass


class _FakeBackend:
    """Stage/launch seam double: records mega-batches, touches no device."""

    def __init__(self, launch_gate: threading.Event = None):
        self.vdaf = _FakeVdaf()
        self.launches = []  # rows-per-request of each mega-batch
        self.staged_pads = []
        self.combine_batches = []
        self._gate = launch_gate

    def stage_prep_init_multi(self, agg_id, requests, pad_to=None):
        rows = sum(len(r) for _, r in requests)
        if rows == 0:
            return None
        self.staged_pads.append(max(pad_to or 0, next_power_of_2(rows)))
        return SimpleNamespace(
            agg_id=agg_id, placed=None, pad_to=self.staged_pads[-1], rows=rows
        )

    def launch_prep_init_multi(self, staged, requests):
        if self._gate is not None:
            assert self._gate.wait(10), "test launch gate never opened"
        self.launches.append([len(r) for _, r in requests])
        return [
            [("prep", vk, i) for i in range(len(reports))]
            for vk, reports in requests
        ]

    def prep_shares_to_prep_batch(self, rows):
        self.combine_batches.append(len(rows))
        return [("combined", i) for i in range(len(rows))]


# -- bucketing / padding -----------------------------------------------------


def test_distinct_shape_kind_aggid_get_distinct_buckets():
    backend = _FakeBackend()
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.005, flush_max_rows=1024))

    async def go():
        await asyncio.gather(
            ex.submit(("shapeA",), "prep_init", (b"k1", [1, 2]), backend=backend),
            ex.submit(("shapeA",), "prep_init", (b"k2", [3]), backend=backend),
            ex.submit(("shapeB",), "prep_init", (b"k3", [4]), backend=backend),
            ex.submit(("shapeA",), "combine", [[1], [2]], backend=backend),
            ex.submit(("shapeA",), "prep_init", (b"k4", [5]), backend=backend, agg_id=1),
        )

    _run(go())
    ex.shutdown()
    # same (shape, kind, agg_id) coalesce; anything else separates
    assert len(ex._buckets) == 4
    assert [sorted(l) for l in backend.launches].count([1, 2]) == 1
    # pow2 padding: the 4-row shapeA/a0 mega-batch staged at pad 4
    assert 4 in backend.staged_pads


def test_pow2_padding_and_warmup_override():
    backend = _FakeBackend()
    backend.stage_prep_init_multi(0, [(b"k", [1, 2, 3])])
    assert backend.staged_pads[-1] == 4
    backend.stage_prep_init_multi(0, [(b"k", [1, 2, 3])], pad_to=16)
    assert backend.staged_pads[-1] == 16


def test_empty_submission_short_circuits():
    backend = _FakeBackend()
    ex = DeviceExecutor(ExecutorConfig())

    async def go():
        return await ex.submit(("s",), "prep_init", (b"k", []), backend=backend)

    assert _run(go()) == []
    ex.shutdown()
    assert backend.launches == []


# -- flush triggers ----------------------------------------------------------


def test_deadline_flush_coalesces_concurrent_jobs():
    backend = _FakeBackend()
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.01, flush_max_rows=10_000))

    async def go():
        return await asyncio.gather(
            ex.submit(("s",), "prep_init", (b"k1", [0, 1]), backend=backend),
            ex.submit(("s",), "prep_init", (b"k2", [0, 1, 2]), backend=backend),
        )

    a, b = _run(go())
    ex.shutdown()
    assert backend.launches == [[2, 3]], "both jobs must ride ONE deadline flush"
    assert len(a) == 2 and len(b) == 3
    stats = next(iter(ex.stats().values()))
    assert stats["flushes"] == 1 and stats["flushed_jobs"] == 2


def test_size_flush_fires_without_waiting_for_window():
    backend = _FakeBackend()
    # window absurdly long: only the size trigger can flush in time
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=60.0, flush_max_rows=4))

    async def go():
        return await asyncio.gather(
            ex.submit(("s",), "prep_init", (b"k1", [0, 1]), backend=backend),
            ex.submit(("s",), "prep_init", (b"k2", [0, 1]), backend=backend),
        )

    t0 = time.monotonic()
    a, b = _run(go(), timeout=10.0)
    elapsed = time.monotonic() - t0
    ex.shutdown()
    assert backend.launches == [[2, 2]]
    assert elapsed < 5.0, "size-triggered flush must not wait for the window"
    assert len(a) == 2 and len(b) == 2


def test_combine_kind_coalesces_and_slices():
    backend = _FakeBackend()
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.01, flush_max_rows=10_000))

    async def go():
        return await asyncio.gather(
            ex.submit(("s",), "combine", [[1], [2]], backend=backend),
            ex.submit(("s",), "combine", [[3]], backend=backend),
        )

    a, b = _run(go())
    ex.shutdown()
    assert backend.combine_batches == [3], "one concatenated combine launch"
    assert a == [("combined", 0), ("combined", 1)] and b == [("combined", 2)]


# -- backpressure ------------------------------------------------------------


def test_oversized_submission_admitted_on_empty_bucket():
    """A job larger than max_queue_rows must still run when nothing is
    queued ahead of it — the legacy per-job path handled any size, so a
    deterministic rejection would permanently fail the job."""
    backend = _FakeBackend()
    ex = DeviceExecutor(
        ExecutorConfig(flush_window_s=0.01, flush_max_rows=10_000, max_queue_rows=2)
    )

    async def go():
        return await ex.submit(
            ("s",), "prep_init", (b"k1", [0, 1, 2, 3, 4]), backend=backend
        )

    out = _run(go())
    ex.shutdown()
    assert len(out) == 5


def test_backpressure_rejects_when_queue_bound_exceeded():
    backend = _FakeBackend()
    ex = DeviceExecutor(
        ExecutorConfig(flush_window_s=60.0, flush_max_rows=10_000, max_queue_rows=4)
    )

    async def go():
        t1 = asyncio.ensure_future(
            ex.submit(("s",), "prep_init", (b"k1", [0, 1, 2]), backend=backend)
        )
        await asyncio.sleep(0)  # let the first submission enqueue
        with pytest.raises(ExecutorOverloadedError):
            await ex.submit(("s",), "prep_init", (b"k2", [0, 1]), backend=backend)
        t1.cancel()

    _run(go())
    ex.shutdown()
    stats = next(iter(ex.stats().values()))
    assert stats["rejections"] == 1


def test_inflight_rows_count_against_the_bound():
    gate = threading.Event()
    backend = _FakeBackend(launch_gate=gate)
    ex = DeviceExecutor(
        ExecutorConfig(flush_window_s=60.0, flush_max_rows=3, max_queue_rows=4)
    )

    async def go():
        # 3 rows: size-flush immediately, launch blocks on the gate
        t1 = asyncio.ensure_future(
            ex.submit(("s",), "prep_init", (b"k1", [0, 1, 2]), backend=backend)
        )
        await asyncio.sleep(0.05)  # flush happened; rows now in flight
        with pytest.raises(ExecutorOverloadedError):
            await ex.submit(("s",), "prep_init", (b"k2", [0, 1]), backend=backend)
        gate.set()
        return await t1

    out = _run(go())
    ex.shutdown()
    assert len(out) == 3


def test_deadline_expired_submission_rejected_at_flush():
    backend = _FakeBackend()
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.05, flush_max_rows=10_000))

    async def go():
        # deadline far shorter than the flush window: expires while queued
        with pytest.raises(ExecutorOverloadedError):
            await ex.submit(
                ("s",),
                "prep_init",
                (b"k1", [0]),
                backend=backend,
                deadline_s=1e-4,
            )

    _run(go())
    ex.shutdown()
    stats = next(iter(ex.stats().values()))
    assert stats["rejections"] == 1 and stats["flushes"] == 0


def test_deadline_expiry_between_take_pending_and_flush_rejects_retryably():
    """RACE (ISSUE 2 satellite): a submission whose deadline expires AFTER
    the size-trigger detached it from the bucket (_take_pending) but BEFORE
    its flush coroutine runs must be retryably rejected — never silently
    dropped (future unresolved) and never launched past its deadline."""
    backend = _FakeBackend()
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=60.0, flush_max_rows=10_000))

    async def go():
        fut = asyncio.ensure_future(
            ex.submit(
                ("s",), "prep_init", (b"k1", [0]), backend=backend, deadline_s=0.02
            )
        )
        await asyncio.sleep(0)  # submission enqueued; window timer armed
        with ex._lock:
            bucket = next(iter(ex._buckets.values()))
            subs = ex._take_pending(bucket)  # the size-flush side of the race
        assert subs, "submission must have been detached"
        await asyncio.sleep(0.05)  # deadline passes while the flush is queued
        await ex._run_flush(bucket, subs, trigger="size")
        with pytest.raises(ExecutorOverloadedError):
            await fut

    _run(go())
    ex.shutdown()
    stats = next(iter(ex.stats().values()))
    # retryable rejection, accounted (queue drains), and nothing launched
    assert stats["rejections"] == 1
    assert stats["depth_rows"] == 0
    assert backend.launches == []


def test_driver_surfaces_overload_as_retryable_jobsteperror():
    """The driver contract: executor backpressure -> JobStepError(retryable)
    so the lease machinery redelivers the job."""
    from janus_tpu.aggregator.aggregation_job_driver import (
        AggregationJobDriver,
        DriverConfig,
        JobStepError,
    )

    reset_global_executor()
    try:
        driver = AggregationJobDriver(
            datastore=None,
            session_factory=None,
            config=DriverConfig(
                vdaf_backend="tpu",
                device_executor=ExecutorConfig(
                    enabled=True, max_queue_rows=2, flush_window_s=60.0
                ),
            ),
        )
        assert driver._executor is not None
        backend = _FakeBackend()
        # pre-fill the bucket (oversized jobs on an EMPTY bucket are
        # admitted, so backpressure needs something queued ahead)
        key = AggregationJobDriver._vdaf_shape_key(backend.vdaf)

        async def go():
            filler = asyncio.ensure_future(
                driver._executor.submit(
                    key, "prep_init", (b"vk0", [0, 1]), backend=backend
                )
            )
            await asyncio.sleep(0)
            with pytest.raises(JobStepError) as exc_info:
                await driver._coalesced_prep_init(backend, b"vk", [0, 1, 2])
            assert exc_info.value.retryable
            filler.cancel()

        _run(go())
    finally:
        reset_global_executor()


# -- error propagation -------------------------------------------------------


def test_launch_failure_propagates_to_every_job_in_the_flush():
    class _ExplodingBackend(_FakeBackend):
        def launch_prep_init_multi(self, staged, requests):
            raise RuntimeError("device on fire")

    backend = _ExplodingBackend()
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.01, flush_max_rows=10_000))

    async def go():
        futs = await asyncio.gather(
            ex.submit(("s",), "prep_init", (b"k1", [0]), backend=backend),
            ex.submit(("s",), "prep_init", (b"k2", [0]), backend=backend),
            return_exceptions=True,
        )
        return futs

    a, b = _run(go())
    ex.shutdown()
    assert isinstance(a, RuntimeError) and isinstance(b, RuntimeError)


# -- real-backend parity + warmup -------------------------------------------


@pytest.fixture(scope="module")
def count_backend():
    from janus_tpu.vdaf.backend import TpuBackend

    return TpuBackend(prio3_count())


def _count_reports(vdaf, n, seed):
    rng = det_rng(seed)
    rows = []
    for i in range(n):
        nonce = rng(vdaf.NONCE_SIZE)
        ps, shares = vdaf.shard(i % 2, nonce, rng(vdaf.RAND_SIZE))
        rows.append((nonce, ps, shares[0]))
    return rows


def test_coalesced_results_byte_identical_to_oracle(count_backend):
    from janus_tpu.vdaf.backend import OracleBackend

    vdaf = count_backend.vdaf
    oracle = OracleBackend(vdaf)
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.02, flush_max_rows=1024))
    vk1, vk2 = b"\x01" * vdaf.VERIFY_KEY_SIZE, b"\x02" * vdaf.VERIFY_KEY_SIZE
    r1 = _count_reports(vdaf, 3, "par1")
    r2 = _count_reports(vdaf, 2, "par2")

    async def go():
        return await asyncio.gather(
            ex.submit(("count",), "prep_init", (vk1, r1), backend=count_backend),
            ex.submit(("count",), "prep_init", (vk2, r2), backend=count_backend),
        )

    a, b = _run(go(), timeout=120.0)
    ex.shutdown()
    stats = next(iter(ex.stats().values()))
    assert stats["flushes"] == 1 and stats["flushed_jobs"] == 2
    for got, (vk, rows) in zip((a, b), ((vk1, r1), (vk2, r2))):
        want = oracle.prep_init_batch(vk, 0, rows)
        for (gs, gsh), (ws, wsh) in zip(got, want):
            assert gs.out_share == ws.out_share
            assert gsh.verifiers_share == wsh.verifiers_share


def test_warmup_compiles_prep_executables(count_backend):
    ex = DeviceExecutor(ExecutorConfig(warmup_rows=4))
    compiled = ex.warmup_backend(count_backend, agg_ids=(0, 1))
    ex.shutdown()
    assert compiled == 2
    assert set(count_backend._prep_fns) == {0, 1}


def test_bucket_label_is_compact():
    from janus_tpu.vdaf.backend import OracleBackend

    assert (
        bucket_label(OracleBackend(prio3_count()), "prep_init", 0)
        == "Count/a0/prep_init"
    )


# -- announced arrivals: the "arrived" flush trigger --------------------------

#: the served deployments' window (README "Running on a TPU host"): a test
#: that passes well under it was not flushed by the timer
WINDOW_S = 3.0
#: for the tests that must see the window run out
SHORT_WINDOW_S = 0.4


def _triggers(ex):
    """(bucket kind, trigger, rows) of every flush, oldest first."""
    return [
        (r["bucket"].split("/")[2].split("#")[0], r["trigger"], r["rows"])
        for r in reversed(ex.flight_recorder.snapshot(64))
    ]


def _jobs(ex):
    """Submissions in every flush of each bucket kind."""
    return {
        label.split("/")[2].split("#")[0]: (st["flushes"], st["flushed_jobs"])
        for label, st in ex.stats().items()
    }


def _announcing_executor(window_s=WINDOW_S):
    return DeviceExecutor(ExecutorConfig(flush_window_s=window_s, flush_max_rows=10_000))


async def _gathered(*coros, return_exceptions=False):
    return await asyncio.gather(*coros, return_exceptions=return_exceptions)


def test_announced_submitters_flush_once_on_the_last_arrival():
    backend = _FakeBackend()
    ex = _announcing_executor()
    delays = [0.02, 0.08, 0.2]

    async def job(i):
        # announced first, as a discovery pass's leases are, then the
        # load and the decode take each job its own time
        with ex.announce("prep_init"):
            await asyncio.sleep(delays[i])
            return await ex.submit(
                ("s",), "prep_init", (b"k%d" % i, list(range(i + 1))), backend=backend
            )

    t0 = time.monotonic()
    outs = _run(_gathered(*(job(i) for i in range(3))))
    elapsed = time.monotonic() - t0
    ex.shutdown()
    assert [len(o) for o in outs] == [1, 2, 3]
    assert backend.launches == [[1, 2, 3]], "one flush, of every job's rows"
    assert _triggers(ex) == [("prep_init", "arrived", 6)]
    assert _jobs(ex) == {"prep_init": (1, 3)}
    assert max(delays) <= elapsed < 1.5, "on the last arrival, not on the timer"
    assert not ex._arrivals


@pytest.mark.parametrize("how", ["exception", "cancelled", "zero_rows", "oracle"])
def test_arrival_closed_without_rows_releases_the_bucket(how):
    from janus_tpu.executor import withdraw_arrival

    backend = _FakeBackend()
    ex = _announcing_executor()

    async def stayer():
        with ex.announce("prep_init"):
            return await ex.submit(("s",), "prep_init", (b"k", [0, 1]), backend=backend)

    async def leaver():
        with ex.announce("prep_init"):
            await asyncio.sleep(0.1)
            if how == "exception":
                raise RuntimeError("the step failed before it had rows")
            if how == "zero_rows":
                return await ex.submit(("s",), "prep_init", (b"k", []), backend=backend)
            if how == "oracle":
                withdraw_arrival()  # serves on the CPU oracle, for long
            await asyncio.sleep(WINDOW_S * 2)

    async def go():
        left = asyncio.ensure_future(leaver())
        stay = asyncio.ensure_future(stayer())
        if how == "cancelled":
            await asyncio.sleep(0.1)
            left.cancel()
        out = await stay
        done_at = time.monotonic()
        left.cancel()
        await asyncio.gather(left, return_exceptions=True)
        return out, done_at

    t0 = time.monotonic()
    out, done_at = _run(go())
    ex.shutdown()
    assert len(out) == 2
    assert _triggers(ex) == [("prep_init", "arrived", 2)]
    assert 0.1 <= done_at - t0 < 1.5
    assert not ex._arrivals


def test_arrival_never_closed_falls_to_the_deadline_with_every_row_resolved():
    backend = _FakeBackend()
    ex = _announcing_executor(SHORT_WINDOW_S)
    wedged = ex.announce("prep_init")  # announced, and then nothing

    async def job(i):
        with ex.announce("prep_init"):
            await asyncio.sleep(0.01)  # the load: both are announced by then
            return await ex.submit(("s",), "prep_init", (b"k", [0] * (i + 1)), backend=backend)

    async def go():
        t0 = time.monotonic()
        outs = await asyncio.gather(job(0), job(1))
        t1 = time.monotonic()
        # it held the bucket for its one window; the next cohort does not wait
        again = await asyncio.gather(job(0), job(1))
        return outs, t1 - t0, again, time.monotonic() - t1

    outs, first, again, second = _run(go())
    ex.shutdown()
    assert [len(o) for o in outs] == [len(o) for o in again] == [1, 2]
    assert _triggers(ex) == [("prep_init", "deadline", 3), ("prep_init", "arrived", 3)]
    assert SHORT_WINDOW_S <= first < SHORT_WINDOW_S + 1.5
    assert second < SHORT_WINDOW_S / 2
    assert wedged in ex._arrivals


def test_unannounced_submission_waits_the_window_as_before():
    backend = _FakeBackend()
    ex = _announcing_executor(SHORT_WINDOW_S)

    async def go():
        fut = asyncio.ensure_future(
            ex.submit(("s",), "prep_init", (b"k", [0, 1]), backend=backend)
        )
        await asyncio.sleep(0.05)
        # somebody else's announcement coming and going is not its business
        with ex.announce("prep_init"):
            pass
        return await fut

    t0 = time.monotonic()
    out = _run(go())
    elapsed = time.monotonic() - t0
    ex.shutdown()
    assert len(out) == 2
    assert _triggers(ex) == [("prep_init", "deadline", 2)]
    assert elapsed >= SHORT_WINDOW_S


@pytest.mark.parametrize("one_drops_out", [False, True])
def test_helper_cohort_combines_in_one_flush_without_a_window(one_drops_out):
    """combine after an N-submission prep_init flush is one flush of N (of
    those still there), not N of one: the executor announces it for every
    submission before the first of them runs again."""
    backend = _FakeBackend()
    ex = _announcing_executor()
    n = 3

    async def request(i):
        with ex.announce("prep_init", agg_id=1, then="combine"):
            await asyncio.sleep(0.03 * i)  # decode, replay lookups, HPKE open
            prep = await ex.submit(
                ("s",), "prep_init", (b"k", [0] * (i + 1)), backend=backend, agg_id=1
            )
            await asyncio.sleep(0.03 * (n - i))  # last to prepare, first to combine
            if one_drops_out and i == 1:
                raise RuntimeError("every row of this request failed")
            return await ex.submit(
                ("s",), "combine", [[p, p] for p in prep], backend=backend, agg_id=1
            )

    t0 = time.monotonic()
    outs = _run(_gathered(*(request(i) for i in range(n)), return_exceptions=True))
    elapsed = time.monotonic() - t0
    ex.shutdown()
    assert backend.launches == [[1, 2, 3]]
    left = [(1, 1), (3, 2)] if one_drops_out else [(1, 1), (2, 1), (3, 1)]
    rows = sum(r for r, _ in left)
    assert backend.combine_batches == [rows], "one combine launch for the cohort"
    assert _triggers(ex) == [("prep_init", "arrived", 6), ("combine", "arrived", rows)]
    assert _jobs(ex) == {"prep_init": (1, 3), "combine": (1, len(left))}
    assert [len(o) for o in outs if not isinstance(o, Exception)] == [r for r, _ in left]
    assert elapsed < 1.5
    assert not ex._arrivals


@pytest.mark.parametrize("closer", ["thread", "loop"])
def test_arrival_closed_elsewhere_fires_on_the_buckets_own_loop(closer):
    backend = _FakeBackend()
    ex = _announcing_executor()
    fired_on = []
    arrived_flush = ex._arrived_flush

    def spy(bucket):
        fired_on.append((threading.current_thread(), asyncio.get_running_loop()))
        arrived_flush(bucket)

    ex._arrived_flush = spy

    def close_from_thread(arrival):
        time.sleep(0.1)
        arrival.close()

    def close_from_loop():
        async def other():
            with ex.announce("prep_init"):
                await asyncio.sleep(0.1)

        asyncio.run(other())

    async def go():
        if closer == "thread":
            worker = threading.Thread(
                target=close_from_thread, args=(ex.announce("prep_init"),)
            )
        else:
            worker = threading.Thread(target=close_from_loop)
        worker.start()
        await asyncio.sleep(0.03)  # the other one is announced by now
        with ex.announce("prep_init"):
            out = await ex.submit(("s",), "prep_init", (b"k", [0, 1]), backend=backend)
        worker.join(5)
        assert not worker.is_alive()
        return out, threading.current_thread(), asyncio.get_running_loop()

    t0 = time.monotonic()
    out, thread, loop = _run(go())
    elapsed = time.monotonic() - t0
    ex.shutdown()
    assert len(out) == 2
    assert _triggers(ex) == [("prep_init", "arrived", 2)]
    assert fired_on == [(thread, loop)]
    assert elapsed < 1.5


def test_narrowed_arrival_holds_only_buckets_of_its_shape():
    from janus_tpu.executor import narrow_arrival

    backend = _FakeBackend()
    ex = _announcing_executor()

    async def slow_other_shape():
        with ex.announce("prep_init"):
            narrow_arrival(("other",))
            await asyncio.sleep(0.5)
            return await ex.submit(("other",), "prep_init", (b"k", [0]), backend=backend)

    async def go():
        other = asyncio.ensure_future(slow_other_shape())
        await asyncio.sleep(0)
        t0 = time.monotonic()
        with ex.announce("prep_init", shape_key=("s",)):
            out = await ex.submit(("s",), "prep_init", (b"k", [0, 1]), backend=backend)
        mine = time.monotonic() - t0
        return out, mine, await other

    out, mine, theirs = _run(go())
    ex.shutdown()
    assert len(out) == 2 and len(theirs) == 1
    assert mine < 0.4, "a bucket of another shape does not wait for that caller"
    assert [t[1] for t in _triggers(ex)] == ["arrived", "arrived"]


def test_arrivals_closed_from_many_threads_strand_no_submission():
    """More closers than cores, a short switch interval: every submission
    resolves long before the window, and no arrival stays on the books."""
    import sys

    backend = _FakeBackend()
    ex = _announcing_executor()
    n = 32

    async def go():
        strangers = [ex.announce("prep_init") for _ in range(n)]
        workers = [threading.Thread(target=a.close) for a in strangers]

        async def job(i):
            with ex.announce("prep_init"):
                await asyncio.sleep(0.001 * i)
                return await ex.submit(("s",), "prep_init", (b"k", [i]), backend=backend)

        jobs = [asyncio.ensure_future(job(i)) for i in range(n)]
        for w in workers:
            w.start()
        outs = await asyncio.wait_for(asyncio.gather(*jobs), WINDOW_S - 0.5)
        for w in workers:
            w.join(5)
            assert not w.is_alive()
        return outs

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        outs = _run(go())
    finally:
        sys.setswitchinterval(interval)
    ex.shutdown()
    assert [len(o) for o in outs] == [1] * n
    assert sum(len(l) for l in backend.launches) == n
    assert {t[1] for t in _triggers(ex)} == {"arrived"}
    assert not ex._arrivals


def test_flush_trigger_counter_carries_the_arrived_label():
    from janus_tpu.core.metrics import GLOBAL_METRICS

    if GLOBAL_METRICS.registry is None:
        pytest.skip("no metrics registry in this process")
    backend = _FakeBackend()
    ex = _announcing_executor()

    async def go():
        with ex.announce("prep_init"):
            return await ex.submit(("s",), "prep_init", (b"k", [0]), backend=backend)

    label = bucket_label(backend, "prep_init", 0, ("s",))
    counter = GLOBAL_METRICS.executor_flushes.labels(bucket=label, trigger="arrived")
    before = counter._value.get()
    _run(go())
    ex.shutdown()
    assert counter._value.get() == before + 1
    assert 'janus_executor_flushes_total{bucket="%s",trigger="arrived"}' % label in (
        GLOBAL_METRICS.export().decode()
    )
