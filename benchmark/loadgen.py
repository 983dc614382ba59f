"""Open-loop load: the schedule from the seed, and the sender processes.

The schedule and the arithmetic follow ``tools/loadgen.py`` (open loop,
nearest-rank percentile); what differs is where the reports are made and
sent.  Here each worker process makes its slice of the reports in set-up
and later sends that slice itself at the due times, so a 19 KB report never
crosses a pipe and neither sealing nor sending shares the fleet's GIL.  (Only
the bodies of set-up's probe batch, one a worker, go back to the parent, which
sends them.)  The workers never import JAX: the chip belongs to the parent.

Every upload is timed from its DUE time (``CLOCK_MONOTONIC`` is one clock
for all processes of a host), not from when it was sent, so a stall of the
generator or of the server counts against every request it delayed.
"""

from __future__ import annotations

import asyncio
import multiprocessing
import os
import random
import sys
import time

from vdafs import family

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- arithmetic --------------------------------------------------------------


def percentile(values, q):
    """Nearest-rank percentile (``tools/loadgen.py`` ``_percentile``) of an
    unsorted sequence; ``None`` when there is nothing to rank."""
    ordered = sorted(values)
    if not ordered:
        return None
    return ordered[min(len(ordered) - 1, int(q * (len(ordered) - 1) + 0.5))]


def schedule(traffic, seconds, seed):
    """Due times, in seconds from the start of the lead-in, of every upload
    of one run: ``traffic["lead_in_s"]`` and then ``seconds`` of arrivals at
    ``traffic["rate"]`` a second.

    ``poisson``: exponential gaps.  Every seed gets the SAME number of
    arrivals in the lead-in and in the window, and the same multiset of gaps
    in each — drawn once from the traffic file's ``schedule_seed`` and scaled
    to fill the span — in an order of its own, so the seed moves when each
    report arrives and never how much work a run holds."""
    rate, kind = float(traffic["rate"]), traffic["arrivals"]
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    base = random.Random(int(traffic.get("schedule_seed", 0)))
    order = random.Random(seed)
    out, start = [], 0.0
    for span in (float(traffic["lead_in_s"]), float(seconds)):
        n = round(rate * span)
        gaps = [base.expovariate(1.0) for _ in range(n + 1)]
        order.shuffle(gaps)
        scale = span / sum(gaps) if n else 0.0
        t = start
        for g in gaps[:n]:
            t += g * scale
            out.append(t)
        start += span
    return out


def measurements(vdaf_desc, n, rng):
    """Measurements a client of this VDAF would send, drawn from ``rng``."""
    return family(vdaf_desc).measurements(vdaf_desc, n, rng)


# -- worker process ----------------------------------------------------------


def _make_reports(job):
    """Shard and seal one slice: ``janus_tpu.client.prepare_report`` with the
    report id and the sharding randomness drawn from the item's own seed
    (HPKE's ephemeral key stays the operating system's)."""
    from janus_tpu.core.hpke import HpkeApplicationInfo, Label, seal
    from janus_tpu.messages import (
        HpkeConfig,
        InputShareAad,
        PlaintextInputShare,
        Report,
        ReportId,
        ReportMetadata,
        Role,
        TaskId,
        Time,
    )
    from janus_tpu.vdaf import vdaf_from_instance

    vdaf = vdaf_from_instance(job["vdaf"])
    task_id = TaskId(job["task_id"])
    configs = (
        (Role.LEADER, HpkeConfig.get_decoded(job["leader_cfg"])),
        (Role.HELPER, HpkeConfig.get_decoded(job["helper_cfg"])),
    )
    when = Time(job["time_s"])
    out = []
    for idx, measurement, item_seed, due in job["items"]:
        rng = random.Random(item_seed)
        report_id = ReportId(rng.randbytes(16))
        public, shares = vdaf.shard(
            measurement, report_id.data, rng.randbytes(vdaf.RAND_SIZE)
        )
        public_bytes = vdaf.encode_public_share(public)
        metadata = ReportMetadata(report_id, when)
        aad = InputShareAad(task_id, metadata, public_bytes).get_encoded()
        sealed = [
            seal(
                config,
                HpkeApplicationInfo.new(Label.INPUT_SHARE, Role.CLIENT, role),
                PlaintextInputShare([], share.encode(vdaf)).get_encoded(),
                aad,
            )
            for (role, config), share in zip(configs, shares)
        ]
        body = Report(metadata, public_bytes, sealed[0], sealed[1]).get_encoded()
        out.append((idx, due, report_id.data, body))
    return out


async def _send_all(url, reports, t0, timeout_s):
    """PUT each report at ``t0 + due``; one record per report:
    (idx, report id, seconds late sent, seconds due->answer, status)."""
    import aiohttp

    records = []

    async def put(session, idx, due, rid, body):
        at = t0 + due
        delay = at - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        sent = time.monotonic()
        try:
            async with session.put(url, data=body) as resp:
                await resp.read()
                status = resp.status
        except Exception:
            status = 0
        records.append((idx, rid, sent - at, time.monotonic() - at, status))

    connector = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=timeout_s)
    async with aiohttp.ClientSession(connector=connector, timeout=timeout) as session:
        await asyncio.gather(*(put(session, *r) for r in reports))
    return records


def _worker(conn):
    sys.path.insert(0, REPO_ROOT)
    reports, url = [], None
    try:
        while True:
            msg = conn.recv()
            if msg[0] == "stop":
                return
            if msg[0] == "probe":
                # set-up's probe: made here, sent by the parent, so the
                # bodies go back (one report a worker, made before any other)
                t0 = time.monotonic()
                made = _make_reports(msg[1])
                conn.send(("probe_made", [m[3] for m in made], time.monotonic() - t0, os.getpid()))
            elif msg[0] == "make":
                t0 = time.monotonic()
                job, url = msg[1], msg[1]["url"]
                # the first report alone, and word of it: what one report
                # costs here, long before the slice is done
                reports = _make_reports({**job, "items": job["items"][:1]})
                conn.send(("first", time.monotonic() - t0))
                reports += _make_reports({**job, "items": job["items"][1:]})
                conn.send(("made", len(reports), time.monotonic() - t0))
            elif msg[0] == "go":
                records = asyncio.run(_send_all(url, reports, msg[1], msg[2]))
                reports = []
                conn.send(("sent", records))
    except (EOFError, KeyboardInterrupt):
        return


class Senders:
    """A pool of sender processes.  ``make_probe`` hands the first workers
    one report each of set-up's probe batch, whose bodies ``wait_probe``
    brings back; ``make`` hands each worker its slice (round robin, so every
    worker sends at an even share of the rate) and returns at once;
    ``wait_first`` blocks until every worker has made one report of its
    slice; ``wait_made`` blocks until every report exists; ``go`` fixes the
    start of the lead-in on the shared monotonic clock; ``results`` blocks
    until every upload was answered or timed out.  A worker answers in the
    order it was asked, so the waits are called in that order."""

    def __init__(self, workers):
        # spawn, not fork: the parent has threads and holds the chip
        ctx = multiprocessing.get_context("spawn")
        self._procs, self._conns = [], []
        for _ in range(workers):
            parent, child = ctx.Pipe()
            proc = ctx.Process(target=_worker, args=(child,), daemon=True)
            proc.start()
            child.close()
            self._procs.append(proc)
            self._conns.append(parent)
        self._busy, self._probing = [], []
        self.slice_sizes = []

    def __len__(self):
        return len(self._conns)

    @staticmethod
    def _next(conn, kind):
        """The worker's next message of this kind.  An earlier "first" that
        nobody waited for is passed over."""
        while True:
            msg = conn.recv()
            if msg[0] == kind:
                return msg
            if msg[0] != "first":
                raise RuntimeError(f"a sender answered {msg[0]!r} where {kind!r} was due")

    def make_probe(self, job, items):
        """One item a worker, to as many workers as there are items."""
        self._probing = self._conns[: len(items)]
        for conn, item in zip(self._probing, items):
            conn.send(("probe", {**job, "items": [item]}))

    def wait_probe(self):
        """(the probe's report bodies, the slowest worker's seconds, the
        pids that made them)."""
        made = [self._next(conn, "probe_made") for conn in self._probing]
        self._probing = []
        return (
            [body for m in made for body in m[1]],
            max(m[2] for m in made),
            [m[3] for m in made],
        )

    def make(self, job, items):
        self._busy, self.slice_sizes = [], []
        for w, conn in enumerate(self._conns):
            mine = items[w :: len(self._conns)]
            if mine:
                conn.send(("make", {**job, "items": mine}))
                self._busy.append(conn)
                self.slice_sizes.append(len(mine))

    def wait_first(self):
        """Seconds each worker took to its slice's first report, in the
        order of ``slice_sizes``."""
        return [self._next(conn, "first")[1] for conn in self._busy]

    def wait_made(self):
        """(reports made, the slowest worker's seconds)."""
        made = [self._next(conn, "made") for conn in self._busy]
        return sum(m[1] for m in made), max(m[2] for m in made)

    def go(self, t0, timeout_s=60.0):
        for conn in self._busy:
            conn.send(("go", t0, timeout_s))

    def results(self):
        return [rec for conn in self._busy for rec in conn.recv()[1]]

    def stop(self):
        for conn in self._conns:
            try:
                conn.send(("stop",))
            except (BrokenPipeError, OSError):
                pass
        # a worker in the middle of its slice reads no "stop": one deadline
        # for all of them, then the rest are killed
        deadline = time.monotonic() + 10.0
        for proc in self._procs:
            proc.join(timeout=max(0.0, deadline - time.monotonic()))
            if proc.is_alive():
                proc.kill()
                proc.join()
