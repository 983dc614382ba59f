#!/usr/bin/env python3
"""DAP upload load generator: the traffic half of the load-soak subsystem.

Drives REAL HTTP uploads (PUT /tasks/{id}/reports, wire-exact sealed
reports) against a leader aggregator at a target rate for a duration,
and reports what the front door did with them — the measurement the SLO
evaluator then judges (ISSUE 14; ``./ci.sh load`` is the harness).

Traffic model — closed+open loop:

* OPEN loop: arrivals are scheduled on a fixed cadence derived from
  ``--rate`` (with a linear ``--ramp-s`` ramp-in), independent of
  response latency — the client population does not slow down because
  the server is slow, which is exactly what makes overload real.
* CLOSED bound: at most ``--concurrency`` requests in flight.  When the
  server falls behind, arrivals past the bound are not dropped but
  DELAYED (counted as ``behind_schedule``) — the generator degrades like
  a finite client population instead of growing an unbounded task pile.

Report production (VDAF shard + two HPKE seals per report) runs on a
thread pool ahead of the schedule into a bounded buffer, so crypto cost
never gates the arrival cadence.

Outcomes are classified per response: ``accepted`` (201), ``shed``
(503 — the front door's Retry-After pressure; the header's presence is
counted separately), ``rejected`` (other 4xx), ``error`` (transport).
``--trace-sample N`` mints a W3C ``traceparent`` for every Nth upload
(bounded sampling: a soak must not emit millions of spans) and lists the
sampled ids in the summary so a harness can stitch them through
``tools/trace_merge.py --stats``.

Usage:

    python tools/loadgen.py --leader http://127.0.0.1:8080 \
        --task-id <b64url> --vdaf '{"type": "Prio3Count"}' \
        --rate 100 --duration 30 --json

Requires the task's HPKE configs to be fetchable from ``--leader`` and
``--helper`` (or pass ``--helper-config-from-leader`` for a pair that
shares one process).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import queue
import secrets
import sys
import threading
import time
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from janus_tpu.client import prepare_report  # noqa: E402
from janus_tpu.core.hpke import is_hpke_config_supported  # noqa: E402
from janus_tpu.messages import (  # noqa: E402
    Duration,
    HpkeConfigList,
    Report,
    TaskId,
    Time,
)
from janus_tpu.vdaf.instances import vdaf_from_instance  # noqa: E402


def _percentile(sorted_vals: List[float], q: float) -> Optional[float]:
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[idx]


class ReportFactory:
    """Thread-pool producer of sealed wire reports into a bounded buffer.

    Timestamps are rounded to the task's time precision by
    prepare_report; a measurement is drawn per report from
    ``measurement`` (a constant for Count/Sum-style VDAFs)."""

    def __init__(self, vdaf, task_id, leader_config, helper_config,
                 time_precision, measurement, workers: int, depth: int,
                 now_fn=None):
        self._vdaf = vdaf
        self._task_id = task_id
        self._leader = leader_config
        self._helper = helper_config
        self._precision = time_precision
        self._measurement = measurement
        self._now_fn = now_fn or (lambda: Time(int(time.time())))
        self._buf: "queue.Queue[bytes]" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        #: first seal failure (a dying worker must fail the run loudly,
        #: never leave next() polling an empty buffer forever)
        self._error: Optional[BaseException] = None
        self._threads = [
            threading.Thread(target=self._run, name=f"loadgen-seal-{i}", daemon=True)
            for i in range(workers)
        ]
        for t in self._threads:
            t.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                report = prepare_report(
                    self._vdaf,
                    self._task_id,
                    self._leader,
                    self._helper,
                    self._precision,
                    self._measurement,
                    time=self._now_fn(),
                ).get_encoded()
            except BaseException as e:
                self._error = e
                self._stop.set()
                return
            while not self._stop.is_set():
                try:
                    self._buf.put(report, timeout=0.2)
                    break
                except queue.Full:
                    continue

    async def next(self) -> bytes:
        loop = asyncio.get_running_loop()
        while True:
            try:
                return self._buf.get_nowait()
            except queue.Empty:
                if self._error is not None:
                    raise RuntimeError(
                        f"report sealing failed: {type(self._error).__name__}: "
                        f"{self._error}"
                    ) from self._error
                await loop.run_in_executor(None, time.sleep, 0.005)

    def stop(self) -> None:
        self._stop.set()


class LoadStats:
    def __init__(self):
        self.outcomes = {"accepted": 0, "shed": 0, "rejected": 0, "error": 0}
        self.latencies_ms: List[float] = []
        self.retry_after_seen = 0
        self.behind_schedule = 0
        self.trace_ids: List[str] = []
        self.first_t: Optional[float] = None
        self.last_t: Optional[float] = None

    def record(self, status: Optional[int], latency_s: float, retry_after) -> None:
        now = time.monotonic()
        self.first_t = self.first_t if self.first_t is not None else now
        self.last_t = now
        if status == 201:
            self.outcomes["accepted"] += 1
        elif status == 503:
            self.outcomes["shed"] += 1
            if retry_after is not None:
                self.retry_after_seen += 1
        elif status is not None and 400 <= status < 500:
            self.outcomes["rejected"] += 1
        else:
            self.outcomes["error"] += 1
        self.latencies_ms.append(latency_s * 1e3)

    def summary(self, target_rate: float, duration_s: float) -> dict:
        lat = sorted(self.latencies_ms)
        sent = sum(self.outcomes.values())
        wall = (
            (self.last_t - self.first_t)
            if (self.first_t is not None and self.last_t and self.last_t > self.first_t)
            else duration_s
        )
        return {
            "target_rate": target_rate,
            "duration_s": round(duration_s, 2),
            "sent": sent,
            "achieved_rate": round(sent / wall, 2) if wall > 0 else 0.0,
            "accepted_rate": round(self.outcomes["accepted"] / wall, 2)
            if wall > 0
            else 0.0,
            "outcomes": dict(self.outcomes),
            "behind_schedule": self.behind_schedule,
            "retry_after_seen": self.retry_after_seen,
            "latency_ms": {
                "p50": _percentile(lat, 0.50),
                "p90": _percentile(lat, 0.90),
                "p99": _percentile(lat, 0.99),
                "max": lat[-1] if lat else None,
            },
            "trace_ids": self.trace_ids,
        }


def first_prepare_percentiles(trace_paths: List[str], sampled_ids: List[str]) -> dict:
    """Upload -> first-prepare percentiles for the SAMPLED uploads (the
    ISSUE 18 ingest unit): per sampled trace id, the wall time from its
    upload span's start to the first device-prepare span (flush_share /
    executor_flush / prep_launch) anywhere in its merged trace — the
    handoff's moment of truth, read straight off the replicas' chrome
    trace files (incrementally flushed, so they are live-readable).
    ``trace_paths`` may contain globs.  Returns ``{"samples", "p50",
    "p90", "p99"}`` in milliseconds (None when nothing resolved)."""
    import glob as globmod

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from trace_merge import merge_events, trace_stats

    paths: List[str] = []
    for pat in trace_paths:
        hits = sorted(globmod.glob(pat))
        paths.extend(hits if hits else ([pat] if os.path.exists(pat) else []))
    sampled = set(sampled_ids)
    out = {"samples": 0, "p50": None, "p90": None, "p99": None}
    if not paths or not sampled:
        return out
    events = merge_events(paths)
    # each sampled id's OWN earliest upload-span start (a merged group may
    # carry many sampled uploads; the group minimum would skew them all)
    upload_ts = {}
    for ev in events:
        if ev.get("ph") == "X" and ev.get("name") == "upload":
            tid = ev.get("args", {}).get("trace_id")
            if tid in sampled:
                ts = ev.get("ts", 0)
                if tid not in upload_ts or ts < upload_ts[tid]:
                    upload_ts[tid] = ts
    vals: List[float] = []
    for g in trace_stats(events)["merged_traces"]:
        flush_ts = g["stages_ts_us"].get("first_flush")
        if flush_ts is None:
            continue
        for tid in set(g["trace_ids"]) & sampled:
            t0 = upload_ts.get(tid)
            if t0 is not None and flush_ts >= t0:
                vals.append((flush_ts - t0) / 1e3)
    vals.sort()
    if vals:
        out = {
            "samples": len(vals),
            "p50": round(_percentile(vals, 0.50), 3),
            "p90": round(_percentile(vals, 0.90), 3),
            "p99": round(_percentile(vals, 0.99), 3),
        }
    return out


async def fetch_hpke_config(session, endpoint: str, task_id: TaskId):
    url = endpoint.rstrip("/") + "/hpke_config?task_id=" + str(task_id)
    async with session.get(url) as resp:
        if resp.status != 200:
            raise RuntimeError(f"hpke_config fetch failed ({url}): {resp.status}")
        body = await resp.read()
    for config in HpkeConfigList.get_decoded(body).hpke_configs:
        if is_hpke_config_supported(config):
            return config
    raise RuntimeError(f"no supported HPKE config at {url}")


async def run_load(
    leader: str,
    task_id: TaskId,
    vdaf_desc: dict,
    *,
    helper: Optional[str] = None,
    helper_config=None,
    rate: float = 50.0,
    duration_s: float = 10.0,
    ramp_s: float = 0.0,
    concurrency: int = 64,
    measurement=1,
    time_precision_s: int = 3600,
    trace_sample: int = 0,
    seal_workers: int = 2,
    now_fn=None,
) -> dict:
    """The programmatic face (the soak tests call this)."""
    import aiohttp

    vdaf = vdaf_from_instance(vdaf_desc)
    stats = LoadStats()
    url = leader.rstrip("/") + f"/tasks/{task_id}/reports"
    connector = aiohttp.TCPConnector(limit=concurrency + 8)
    async with aiohttp.ClientSession(connector=connector) as session:
        leader_config = await fetch_hpke_config(session, leader, task_id)
        if helper_config is None:
            helper_config = await fetch_hpke_config(session, helper or leader, task_id)
        factory = ReportFactory(
            vdaf,
            task_id,
            leader_config,
            helper_config,
            Duration(time_precision_s),
            measurement,
            workers=seal_workers,
            depth=max(32, int(rate)),
            now_fn=now_fn,
        )
        sem = asyncio.Semaphore(concurrency)
        inflight: set = set()
        n_sent = 0

        async def one_upload(body: bytes, traceparent: Optional[str]) -> None:
            headers = {"Content-Type": Report.MEDIA_TYPE}
            if traceparent:
                headers["traceparent"] = traceparent
            t0 = time.monotonic()
            try:
                async with session.put(url, data=body, headers=headers) as resp:
                    await resp.read()
                    stats.record(
                        resp.status,
                        time.monotonic() - t0,
                        resp.headers.get("Retry-After"),
                    )
            except Exception:
                stats.record(None, time.monotonic() - t0, None)
            finally:
                sem.release()

        try:
            start = time.monotonic()
            next_at = start
            while True:
                now = time.monotonic()
                if now - start >= duration_s:
                    break
                # open-loop cadence with ramp-in (floored at 20% of the
                # target so t=0 schedules a real arrival, not a stall)
                frac = 1.0 if ramp_s <= 0 else min(1.0, (now - start) / ramp_s)
                current_rate = max(rate * frac, rate * 0.2, 0.5)
                if now < next_at:
                    await asyncio.sleep(min(next_at - now, 0.05))
                    continue
                next_at += 1.0 / current_rate
                if next_at < now - 1.0:
                    next_at = now  # never build unbounded schedule debt
                # closed-loop bound: wait (counted) when at max in-flight
                if sem.locked():
                    stats.behind_schedule += 1
                await sem.acquire()
                body = await factory.next()
                n_sent += 1
                traceparent = None
                if trace_sample > 0 and (n_sent - 1) % trace_sample == 0:
                    tid = secrets.token_hex(16)
                    traceparent = f"00-{tid}-{secrets.token_hex(8)}-01"
                    stats.trace_ids.append(tid)
                t = asyncio.ensure_future(one_upload(body, traceparent))
                inflight.add(t)
                t.add_done_callback(inflight.discard)
            if inflight:
                await asyncio.gather(*inflight, return_exceptions=True)
        finally:
            factory.stop()
    return stats.summary(rate, duration_s)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--leader", required=True, help="leader base URL")
    p.add_argument("--helper", help="helper base URL (for its HPKE config); "
                   "defaults to --leader (taskprov-style shared serving)")
    p.add_argument("--task-id", required=True)
    p.add_argument("--vdaf", default='{"type": "Prio3Count"}',
                   help="VDAF instance JSON")
    p.add_argument("--measurement", default="1",
                   help="measurement JSON per report (default 1)")
    p.add_argument("--rate", type=float, default=50.0, help="target reports/s")
    p.add_argument("--duration", type=float, default=10.0, help="seconds")
    p.add_argument("--ramp-s", type=float, default=0.0,
                   help="linear rate ramp-in seconds")
    p.add_argument("--concurrency", type=int, default=64,
                   help="max in-flight uploads (closed-loop bound)")
    p.add_argument("--time-precision", type=int, default=3600)
    p.add_argument("--trace-sample", type=int, default=0,
                   help="mint a traceparent for every Nth upload (0 = off)")
    p.add_argument("--seal-workers", type=int, default=2,
                   help="report-sealing threads")
    p.add_argument("--now", type=int, default=0,
                   help="fixed report timestamp (0 = wall clock); harnesses "
                   "with MockClock-seeded tasks pin this")
    p.add_argument("--trace-files", nargs="+", default=None,
                   help="replica chrome-trace files/globs; with "
                   "--trace-sample, the --json summary gains "
                   "upload_to_first_prepare_ms percentiles for the "
                   "sampled uploads (ISSUE 18)")
    p.add_argument("--json", action="store_true", help="print the summary JSON")
    args = p.parse_args(argv)

    now_fn = (lambda: Time(args.now)) if args.now else None
    summary = asyncio.run(
        run_load(
            args.leader,
            TaskId.from_str(args.task_id),
            json.loads(args.vdaf),
            helper=args.helper,
            rate=args.rate,
            duration_s=args.duration,
            ramp_s=args.ramp_s,
            concurrency=args.concurrency,
            measurement=json.loads(args.measurement),
            time_precision_s=args.time_precision,
            trace_sample=args.trace_sample,
            seal_workers=args.seal_workers,
            now_fn=now_fn,
        )
    )
    if args.trace_files:
        summary["upload_to_first_prepare_ms"] = first_prepare_percentiles(
            args.trace_files, summary["trace_ids"]
        )
    if args.json:
        print(json.dumps(summary))
    else:
        o = summary["outcomes"]
        print(
            f"sent={summary['sent']} ({summary['achieved_rate']}/s of "
            f"{summary['target_rate']}/s target)  accepted={o['accepted']} "
            f"shed={o['shed']} rejected={o['rejected']} error={o['error']}  "
            f"p50={summary['latency_ms']['p50']}ms "
            f"p99={summary['latency_ms']['p99']}ms"
        )
    # exit 0 when traffic flowed at all; judging is the harness's job
    return 0 if summary["sent"] > 0 else 1


if __name__ == "__main__":
    sys.exit(main())
