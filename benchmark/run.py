#!/usr/bin/env python3
"""One run of one cell of the benchmark, on the chip it is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Every cell is the same run: set-up (fleet, warm shapes, reports made in
worker processes from the seed), open-loop uploads at the cell's rate from
``lead_in_s`` before the window to its close, the window, the drain, the
collection, and the comparison with the plain reference.  The cell, its
configuration, its traffic and every metric are data (``BENCHMARK.json`` and
the files it names under ``benchmark/``); no name of any of them is in code.

The last line of standard output is the result; ``README.md`` beside this
file has the phases and how to add a cell.  With no accelerator the command
fails.  ``--rehearse`` (the harness's own, never the driver's) runs the
configuration's ``rehearse`` size on the CPU to find wrong paths, says
``platform: cpu`` and reports no metric.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

#: the traced slice: at most this many seconds from the middle of the window
TRACE_SLICE_S = 20.0
#: the drain gives up after this long; what is left then is ``failed``
DRAIN_LIMIT_S = 120.0
#: a run ends this long after its process started, or the driver stops it ...
RUN_LIMIT_S = 360.0
#: ... and this long where it compiled: the first run of a cell in a checkout
RUN_LIMIT_COMPILED_S = 1200.0
#: what the drain and the collection take after the window, as the fit rule counts them
AFTER_WINDOW_S = 30.0
#: an upload with no answer in this long has failed
UPLOAD_TIMEOUT_S = 60.0
#: set-up's phases, as the record carries their seconds (``setup_phases``)
SETUP_PHASES = ("reports_made", "prep_warm", "probe", "programs_warm", "wait_made")
#: idle gaps shorter than this lie between the ops of one device program
SHORT_GAP_NS = 100_000.0
MARK = "bench_slice_mark"


class RunFailure(Exception):
    """The run cannot report a result; the message is the reason."""


def log(obj):
    """One JSON object on an earlier line of standard output."""
    print(json.dumps(obj), flush=True)


def fits(elapsed_s, lead_in_s, seconds, compiled, phases, projected=False):
    """``None`` where a run whose set-up takes ``elapsed_s`` ends inside the
    limit the driver holds it to, else the reason why it cannot: the lead-in,
    the window and ``AFTER_WINDOW_S`` of drain and collection still follow.
    ``phases`` are the seconds that the reason names."""
    limit = RUN_LIMIT_COMPILED_S if compiled else RUN_LIMIT_S
    end = elapsed_s + lead_in_s + seconds + AFTER_WINDOW_S
    if end <= limit:
        return None
    split = ", ".join(f"{name.replace('_', ' ')} {s:.0f}" for name, s in phases.items())
    return (
        f"set-up {'is projected to take' if projected else 'took'} {elapsed_s:.0f} s ({split}); "
        f"lead-in, window and drain would end at {end:.0f} s, over the {limit:.0f} s a run"
        f"{' that compiled' if compiled else ''} may last"
    )


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def by_name(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise RunFailure(f"BENCHMARK.json has no {what} named {name!r}")


def read_plan(workload):
    """The cell with its configuration, traffic and metric files."""
    manifest = load_json("BENCHMARK.json")
    cell = by_name(manifest["workloads"], workload, "workload")
    config = load_json(by_name(manifest["configs"], cell["config"], "config")["file"])
    traffic = load_json("benchmark", "traffic", cell["traffic"] + ".json")

    every = {
        group: [
            {**m, **load_json("benchmark", "metrics", m["name"] + ".json")}
            for m in manifest[group]
        ]
        for group in ("end_to_end", "per_layer")
    }

    def mine(group):
        return [m for m in every[group] if "workloads" not in m or workload in m["workloads"]]

    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": mine("end_to_end"),
        "per_layer": mine("per_layer"),
        # every metric of the benchmark, for the earlier "leg" line
        "every_metric": every["end_to_end"] + every["per_layer"],
    }


# -- the device --------------------------------------------------------------


def find_device(chips, rehearse):
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearse:
        return device
    if device["platform"] == "cpu":
        raise RunFailure("JAX found no accelerator; there is no CPU fallback")
    if device["count"] < chips:
        raise RunFailure(f"the cell asks for {chips} chips, JAX reports {device['count']}")
    return device


def count_cache_events():
    """Count, from here on, the programs JAX lowers and takes to its
    persistent cache (hit or miss): each is a shape nobody had compiled in
    this process.  (``chip_smoke.py`` ``_count_cache_events``.)"""
    from jax._src import monitoring

    events = {"hits": 0, "misses": 0}

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    monitoring.register_event_listener(on_event)
    return events


class CompileLog(logging.Handler):
    """Keeps what JAX says it compiles ("Compiling <name> with global
    shapes ..."), so that a compile inside the window can be named."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.seen = []  # (monotonic seconds, message)
        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
            logger = logging.getLogger(name)
            logger.addHandler(self)
            if logger.getEffectiveLevel() > logging.DEBUG:
                logger.setLevel(logging.DEBUG)
                logger.propagate = False

    def emit(self, record):
        message = record.getMessage()
        if message.startswith("Compiling"):
            self.seen.append((time.monotonic(), message[:240]))

    def between(self, lo, hi):
        return [m for t, m in self.seen if lo <= t < hi]


def memory_peak_bytes():
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()
    ]
    return int(max(peaks))


def served_counters(snap):
    """Who prepared the rows: the device or the CPU oracle
    (``chip_smoke.py`` ``_device_counters``)."""
    import prom

    return {
        "device_rows": prom.total(snap, "janus_device_prepare_reports_total", {"backend": "tpu"}),
        "oracle_rows": prom.total(snap, "janus_vdaf_prepare_reports_total", {"backend": "oracle"}),
        "backend_fallbacks": prom.total(snap, "janus_vdaf_backend_fallback_total"),
        "circuit_transitions": prom.total(snap, "janus_executor_circuit_transitions_total"),
        "executor_rejections": prom.total(snap, "janus_executor_rejections_total"),
    }


# -- set-up: warm every shape the window will use ----------------------------


def pow2_up_to(lo, hi):
    n, out = 1, []
    while n <= hi:
        if n >= lo:
            out.append(n)
        n *= 2
    return out


class ShapeProbe:
    """Records the arguments of the combine and aggregate programs while one
    small real batch goes through the fleet, so that set-up can compile the
    same programs at every batch size the window can reach.  The program has
    no warmup hook for them (``PERF.md``, Open questions); only ``prep_init``
    is warmed by the executor itself.

    ``shared`` is the one backend of the shape that the executor shares
    between the leader's driver and the helper's prepare; ``helper_own`` is
    the helper's per-task backend, whose ``aggregate_batch`` the helper's
    writer calls — a new jitted program for every task."""

    def __init__(self, shared, helper_own):
        self.shared, self.helper_own = shared, helper_own
        self.combine_args = None
        self.aggregate_args = {}
        self._combine_fn = shared._combine()

        def combine(vs, parts):
            self.combine_args = (vs, parts)
            return self._combine_fn(vs, parts)

        shared._combine_fn = combine
        for key, backend in (("shared", shared), ("helper_own", helper_own)):
            backend.aggregate_batch = self._recording(key, backend.aggregate_batch)

    def _recording(self, key, inner):
        def aggregate(shares, mask):
            import numpy as np

            self.aggregate_args[key] = np.asarray(shares)
            return inner(shares, mask)

        return aggregate

    def complete(self):
        return self.combine_args is not None and len(self.aggregate_args) == 2

    def remove(self):
        self.shared._combine_fn = self._combine_fn
        for backend in (self.shared, self.helper_own):
            del backend.aggregate_batch

    def warm_combine(self, rows):
        import jax

        vs, parts = self.combine_args
        done = []
        for n in rows:
            t0 = time.monotonic()
            jax.block_until_ready(
                self._combine_fn([resized(v, n) for v in vs], [resized(p, n) for p in parts])
            )
            done.append([n, round(time.monotonic() - t0, 2)])
        return done

    def warm_aggregate(self, key, backend, rows):
        import numpy as np

        done = []
        for n in rows:
            t0 = time.monotonic()
            backend.aggregate_batch(resized(self.aggregate_args[key], n), np.ones(n, dtype=bool))
            done.append([n, round(time.monotonic() - t0, 2)])
        return done


def resized(arr, n):
    """Zeros of ``arr``'s shape and type with ``n`` rows."""
    import numpy as np

    arr = np.asarray(arr)
    return np.zeros((n,) + arr.shape[1:], arr.dtype)


async def put_reports(url, bodies):
    import aiohttp

    async def put(session, body):
        async with session.put(url, data=body) as resp:
            if resp.status != 201:
                raise RunFailure(f"probe upload refused: {resp.status} {await resp.text()}")

    async with aiohttp.ClientSession() as session:
        await asyncio.gather(*(put(session, body) for body in bodies))


async def helper_backend(fleet, task_id):
    return (await fleet.aggregators["helper"].task_aggregator_for(task_id)).backend


def start_probe(ctx):
    """Set-up's probe batch: a throw-away task, and one report of it asked of
    every sender before anything else, so that it costs one client call's
    time and the main process makes no report.  Two reports or more: one
    finished job of that many is enough (the creator may cut the batch)."""
    from loadgen import measurements

    fleet, config, senders = ctx["fleet"], ctx["config"], ctx["senders"]
    task_id, leader_cfg, helper_cfg = fleet.add_task("probe")
    rng = random.Random("probe")
    job = {
        "vdaf": config["vdaf"],
        "task_id": task_id.data,
        "leader_cfg": leader_cfg.get_encoded(),
        "helper_cfg": helper_cfg.get_encoded(),
        "time_s": ctx["time_s"],
    }
    items = [
        (i, m, rng.getrandbits(64), 0.0)
        for i, m in enumerate(measurements(config["vdaf"], len(senders), rng))
    ]
    senders.make_probe(job, items)
    made = asyncio.get_running_loop().run_in_executor(None, senders.wait_probe)
    return {"task_id": task_id, "rows": len(items), "made": made}


async def first_reports_fit(ctx, batch, t_make, lead_in, seconds):
    """As soon as every sender has made one report of its slice: reports a
    worker x that time is how long the making will take, and a run that this
    alone carries past its limit ends here, minutes sooner.  No program taken
    from the cache yet means the run may still be one that compiles."""
    senders, events = ctx["senders"], ctx["cache_events"]
    await asyncio.wait([batch["made"]])  # the senders answer in the order asked
    firsts = await asyncio.get_running_loop().run_in_executor(None, senders.wait_first)
    projected = max(n * s for n, s in zip(senders.slice_sizes, firsts))
    log({"first_reports_s": [round(s, 3) for s in firsts], "reports_a_worker":
         max(senders.slice_sizes), "reports_made_projected_s": round(projected, 1)})
    reason = fits(t_make - T0 + projected, lead_in, seconds,
                  events["misses"] > 0 or not events["hits"],
                  {"reports_made": projected}, projected=True)
    if reason:
        raise RunFailure(reason)


async def warm_everything(ctx, name, batch, phases):
    """The executor's own warmup of ``prep_init`` (running since the
    backend was made), then the probe batch that the senders made through its
    throw-away task with the shape probe on, then combine and the leader's
    aggregate at every size."""
    from fleet import JobWatch

    fleet, config = ctx["fleet"], ctx["config"]
    loop = asyncio.get_running_loop()
    t0 = time.monotonic()
    info = await fleet.warm(name)
    t1 = time.monotonic()
    phases["prep_warm"] = t1 - t0
    log({"warmup": "prep_init", "seconds": round(t1 - t0, 1), **info})
    shared = fleet.backend(name)
    if not hasattr(shared, "_combine"):
        return
    bodies, made_s, pids = await batch["made"]
    task_id, rows = batch["task_id"], batch["rows"]
    probe = ShapeProbe(shared, await helper_backend(fleet, task_id))
    watch = JobWatch(fleet, task_id)
    watch.start()
    try:
        await put_reports(f"{fleet.urls['leader']}tasks/{task_id}/reports", bodies)
        deadline = time.monotonic() + 300.0
        while not (watch.finished_at and probe.complete()):
            if time.monotonic() > deadline or watch.jobs_abandoned:
                raise RunFailure(
                    f"the probe batch did not finish: {len(watch.finished_at)}/{rows}, "
                    f"recorded {sorted(probe.aggregate_args)}"
                )
            await asyncio.sleep(0.25)
    finally:
        watch.stop()
        probe.remove()
    t2 = time.monotonic()
    phases["probe"] = t2 - t1
    log({"warmup": "probe", "seconds": round(t2 - t1, 1), "rows": rows,
         "slowest_worker_s": round(made_s, 2), "made_by": pids, "main_pid": os.getpid()})
    ctx["probe"] = probe
    ctx["combine_rows"] = pow2_up_to(1, config["device_executor"]["warmup_rows"])
    ctx["aggregate_rows"] = pow2_up_to(2, config["job_creator"]["max_aggregation_job_size"])
    done = {
        "combine": await loop.run_in_executor(None, probe.warm_combine, ctx["combine_rows"]),
        "aggregate": await loop.run_in_executor(
            None, probe.warm_aggregate, "shared", shared, ctx["aggregate_rows"]
        ),
    }
    phases["programs_warm"] = time.monotonic() - t2
    log({"warmup": "combine+aggregate", "seconds": round(phases["programs_warm"], 1), **done})


async def warm_task(ctx, task_id, phases):
    """The helper's aggregate program is jitted per task: compile this
    task's at every job size."""
    probe = ctx.get("probe")
    if probe is None:
        return
    t0 = time.monotonic()
    own = await helper_backend(ctx["fleet"], task_id)
    done = await asyncio.get_running_loop().run_in_executor(
        None, probe.warm_aggregate, "helper_own", own, ctx["aggregate_rows"]
    )
    phases["programs_warm"] += time.monotonic() - t0
    log({"warmup": "helper aggregate", "seconds": round(time.monotonic() - t0, 1), "rows": done})


def plant_fault(kind, backend):
    """The harness's own faults, for the control and the tests: the timed
    path broken underneath, to see ``correct`` come out false."""
    import numpy as np

    inner = backend.aggregate_batch
    state = {"calls": 0}

    if kind == "alter":
        # one answer altered where it is produced: the first aggregate share
        def aggregate(shares, mask):
            out = list(inner(shares, mask))
            state["calls"] += 1
            if state["calls"] == 1:
                out[0] = out[0] - 1 if out[0] > 0 else 1
            return out

    elif kind == "half_batch":
        # half of every batch left out of the sum
        def aggregate(shares, mask):
            mask = np.array(mask, dtype=bool)
            mask[len(mask) // 2 :] = False
            return inner(shares, mask)

    else:
        raise RunFailure(f"unknown fault {kind!r}")
    backend.aggregate_batch = aggregate
    return state


# -- the traced slice --------------------------------------------------------


def start_trace(trace_dir):
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    mark = {"mono": time.monotonic(), "wall": time.time()}
    with jax.profiler.TraceAnnotation(MARK):
        pass
    return mark


def stop_trace():
    import jax

    jax.profiler.stop_trace()


def flight_spans(mark, mark_ns):
    """Host spans of the executor's flushes, in trace nanoseconds, from the
    flight recorder (wall clock, stamped when a flush ends): launch, stage,
    and the wait in the flush window before it."""
    from janus_tpu.executor import peek_global_executor

    ex = peek_global_executor()
    launches, stages, waits = [], [], []
    if ex is None:
        return launches, stages, waits
    for f in ex.flight_recorder.snapshot(ex.flight_recorder.size):
        end = mark_ns + (f["t"] - mark["wall"]) * 1e9
        launch = end - f["launch_ms"] * 1e6
        stage = launch - f["stage_ms"] * 1e6
        wait = stage - f["queue_delay_max_ms"] * 1e6
        bucket = f["bucket"].split("#")[0]
        launches.append((f"host side of launch {bucket}", launch, end))
        stages.append((f"staging {bucket}", stage, launch))
        waits.append((f"flush-window wait {bucket}", wait, stage))
    return launches, stages, waits


def reduce_trace(ctx, trace_dir, mark, stop_mono, snaps):
    """The traced slice's named quantities, the device dict's ``busy_s`` and
    ``window_s``, and the breakdown."""
    import prom
    import trace_reduce as tr
    from protocol_bytes import prepare_bytes_per_report

    planes = tr.load(trace_dir)
    lo = tr.find_mark(planes, MARK)
    if lo is None:
        raise RunFailure("the trace does not hold the harness's mark")
    hi = lo + (stop_mono - mark["mono"]) * 1e9
    devices = tr.device_planes(planes, *ctx["trace_planes"])
    if not devices or not any(devices):
        raise RunFailure(
            f"no device operation in the trace; planes: {[p['name'] for p in planes]}"
        )
    window_s = (hi - lo) / 1e9
    busy_s = sum(tr.busy_ns(ev, lo, hi) for ev in devices) / len(devices) / 1e9
    first = devices[0]
    launches, stages, waits = flight_spans(mark, lo)
    launch_union = tr.merged([(n, s, e - s) for n, s, e in launches], lo, hi)
    rows = prom.delta(
        snaps["open"], snaps["close"], "janus_device_prepare_reports_total", {"backend": "tpu"}
    )
    quantities = {
        "window_s": window_s,
        "busy_s": busy_s,
        "launch_s": sum(e - s for s, e in launch_union) / 1e9,
        "launch_busy_s": tr.overlap_ns(launch_union, tr.merged(first, lo, hi)) / 1e9,
        "rows": rows,
        "reports": rows / 2.0,
        "protocol_bytes": prepare_bytes_per_report(ctx["config"]["vdaf"]) * rows / 2.0,
        **ctx["peaks"],
    }
    idle = tr.gaps(first, lo, hi)
    short = sum(e - s for s, e in idle if e - s < SHORT_GAP_NS) / 1e9
    gaps = tr.attribute_gaps(
        [g for g in idle if g[1] - g[0] >= SHORT_GAP_NS], launches + stages + waits
    )
    if short > 0:
        gaps = sorted(gaps + [("between ops of one device program", short)], key=lambda g: -g[1])
    ops = {}
    for ev in devices:
        for name, seconds in tr.op_seconds(ev, lo, hi):
            ops[name] = ops.get(name, 0.0) + seconds
    breakdown = {
        "device_ops": [list(x) for x in sorted(ops.items(), key=lambda x: -x[1])[:10]],
        "idle_gaps": [list(x) for x in gaps[:10]],
    }
    return {"quantities": quantities, "prom": snaps, "breakdown": breakdown,
            "planes": [[p["name"], [l["name"] for l in p["lines"]][:12]] for p in planes]}


# -- one leg: lead-in, window, drain, collect, compare -----------------------


async def sleep_until(t):
    delay = t - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)


async def run_leg(ctx, index, seed, rate, seconds):
    import prom
    import readers
    from fleet import JobWatch
    from loadgen import measurements, schedule
    from reference import mismatched_positions, plain_aggregate

    fleet, senders, config, args = ctx["fleet"], ctx["senders"], ctx["config"], ctx["args"]
    loop = asyncio.get_running_loop()
    traffic = {**ctx["traffic"], "rate": rate}
    lead_in = float(traffic["lead_in_s"])
    name = f"seed{seed}-leg{index}"
    task_id, leader_cfg, helper_cfg = fleet.add_task(name)
    backend = fleet.backend(name)  # made here, so its warmup starts now

    rng = random.Random(seed)
    dues = schedule(traffic, seconds, seed)
    meas = measurements(config["vdaf"], len(dues), rng)
    job = {
        "vdaf": config["vdaf"],
        "task_id": task_id.data,
        "leader_cfg": leader_cfg.get_encoded(),
        "helper_cfg": helper_cfg.get_encoded(),
        "time_s": ctx["time_s"],
        "url": f"{fleet.urls['leader']}tasks/{task_id}/reports",
    }
    phases = dict.fromkeys(SETUP_PHASES, 0.0)
    # the first leg's probe goes to the senders before their slices
    batch = start_probe(ctx) if index == 0 else None
    t_make = time.monotonic()
    senders.make(job, [(i, meas[i], rng.getrandbits(64), dues[i]) for i in range(len(dues))])

    async def main_path():
        if index == 0:
            await warm_everything(ctx, name, batch, phases)
            if args.fault in ("alter", "half_batch"):
                ctx["fault"] = plant_fault(args.fault, backend)
        if "fault" in ctx:
            ctx["fault"]["calls"] = 0  # every leg gets its fault
        await warm_task(ctx, task_id, phases)

    tasks = [asyncio.ensure_future(main_path())]
    if index == 0:  # the run's limit is on its first leg; later ones are the harness's own
        tasks.append(asyncio.ensure_future(first_reports_fit(ctx, batch, t_make, lead_in, seconds)))
    try:
        await asyncio.gather(*tasks)  # whichever fails first ends the leg
    except BaseException:
        for task in tasks:
            task.cancel()
        raise
    t0 = time.monotonic()
    made, phases["reports_made"] = await loop.run_in_executor(None, senders.wait_made)
    phases["wait_made"] = time.monotonic() - t0
    log({"reports_made": made, "slowest_worker_s": round(phases["reports_made"], 1), "rate": rate})

    snaps_run = {"start": prom.snapshot()}
    served0 = served_counters(snaps_run["start"])
    t_start = time.monotonic() + 0.5
    setup_s = t_start - T0
    if index == 0:
        reason = fits(setup_s, lead_in, seconds, ctx["cache_events"]["misses"] > 0, phases)
        if reason:
            raise RunFailure(reason)
    senders.go(t_start, UPLOAD_TIMEOUT_S)
    log({"setup_s": round(setup_s, 2), "setup_phases": {k: round(v, 2) for k, v in phases.items()},
         "before_warm_s": round(t_make - T0, 2), "cache_events": dict(ctx["cache_events"])})
    t_open, t_close = t_start + lead_in, t_start + lead_in + seconds
    watch = JobWatch(fleet, task_id)
    watch.start()
    await sleep_until(t_start)
    await fleet.restart_creator()

    await sleep_until(t_open)
    snaps = {"open": prom.snapshot()}
    events_open = dict(ctx["cache_events"])
    trace = None
    if args.trace:
        slice_s = min(TRACE_SLICE_S, seconds)
        trace_dir = os.path.join(ctx["out_dir"], "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        await sleep_until(t_open + (seconds - slice_s) / 2.0)
        mark = await loop.run_in_executor(None, start_trace, trace_dir)
        trace_snaps = {"open": prom.snapshot()}
        await sleep_until(mark["mono"] + slice_s)
        trace_snaps["close"] = prom.snapshot()
        stop_mono = time.monotonic()
        await loop.run_in_executor(None, stop_trace)
        trace = (trace_dir, mark, stop_mono, trace_snaps)
    await sleep_until(t_close)
    snaps["close"] = prom.snapshot()
    compiled = ctx["compile_log"].between(t_open, t_close)
    compiles = max(len(compiled), sum(ctx["cache_events"].values()) - sum(events_open.values()))

    records = await loop.run_in_executor(None, senders.results)
    acked = {rid for _i, rid, _late, _lat, status in records if status == 201}
    deadline = time.monotonic() + DRAIN_LIMIT_S
    while not acked <= watch.finished_at.keys():
        if time.monotonic() > deadline or watch.jobs_abandoned:
            break
        await asyncio.sleep(0.25)
    t_drained = time.monotonic()
    watch.stop()
    snaps_run["drained"] = prom.snapshot()
    memory_peak = memory_peak_bytes()

    # the answer, and the reference over the reports the leader finished
    by_rid = {rid: i for i, rid, *_ in records}
    finished = set(watch.finished_at)
    collected, collect_error, t0 = None, None, time.monotonic()
    try:
        collected = await asyncio.wait_for(fleet.collect(name, task_id, ctx["time_s"]), 150.0)
    except Exception as e:  # a collection that fails is a wrong answer, not a crash
        collect_error = f"{type(e).__name__}: {e}"
    collect_s = time.monotonic() - t0
    want = plain_aggregate(config["vdaf"], [meas[by_rid[r]] for r in finished if r in by_rid])
    served1 = served_counters(prom.snapshot())
    served = {k: served1[k] - served0[k] for k in served1}

    def check(value, limit=0):
        return {"value": value, "limit": limit}

    checks = {
        "aggregate_mismatched_positions": check(
            mismatched_positions(collected.aggregate_result, want)
            if collected is not None
            else -1
        ),
        "report_count_off": check(
            abs(collected.report_count - len(finished)) if collected is not None else -1
        ),
        "acked_not_aggregated": check(len(acked - finished)),
        "aggregated_unknown_reports": check(len(finished - set(by_rid))),
        "reports_failed_in_jobs": check(watch.failed_reports),
        "jobs_abandoned": check(watch.jobs_abandoned),
        "oracle_rows": check(served["oracle_rows"]),
        "backend_fallbacks": check(served["backend_fallbacks"]),
        "circuit_transitions": check(served["circuit_transitions"]),
        "device_rows_short": check(max(0.0, 2 * len(finished) - served["device_rows"])),
        "compiles_in_window": check(compiles),
    }
    correct = all(c["value"] == c["limit"] for c in checks.values())

    # what the window saw
    in_window = [r for r in records if lead_in <= dues[r[0]] < lead_in + seconds]
    uploads = []
    for i, rid, late, latency, status in in_window:
        done = watch.finished_at.get(rid)
        uploads.append(
            {
                "late_s": late,
                "ack_s": latency if status == 201 else None,
                "lag_s": done - (t_start + dues[i]) if done is not None else None,
            }
        )
    failed = sum(1 for u in uploads if u["ack_s"] is None or u["lag_s"] is None)
    done = [watch.finished_at[r[1]] for r in in_window if r[1] in watch.finished_at]
    rec = {
        "seconds": float(seconds),
        "setup_s": setup_s,
        "setup_phases": phases,
        "uploads": uploads,
        # every report, of the lead-in's backlog too, in a job first seen
        # FINISHED inside the window
        "finished_in_window": sum(1 for t in watch.finished_at.values() if t_open <= t < t_close),
        "reports_aggregated": len(finished),
        "prom": snaps,
        "prom_run": {"open": snaps_run["start"], "close": snaps_run["drained"]},
    }
    device = {**ctx["device"], "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace is not None:
        t0 = time.monotonic()
        rec["trace"] = reduce_trace(ctx, *trace)
        q = rec["trace"]["quantities"]
        device.update(busy_s=q["busy_s"], window_s=q["window_s"])
        breakdown = rec["trace"]["breakdown"]
        log({"trace_planes": rec["trace"]["planes"], "reduce_s": round(time.monotonic() - t0, 1),
             "quantities": q})
        shutil.rmtree(trace[0], ignore_errors=True)

    def read(metrics):
        out = {}
        for m in metrics:
            value = readers.KINDS[m["reader"]](rec, m.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    both = read(ctx["plan"]["every_metric"])
    log(
        {
            "leg": index, "seed": seed, "rate": rate, "seconds": seconds,
            "uploads_sent": len(records), "acked": len(acked),
            "aggregated": len(finished), "jobs": watch.jobs_seen,
            "aggregated_in_window": rec["finished_in_window"],
            "window_uploads_done_s": round(max(done) - t_open, 2) if done else None,
            "first_finished_s": round(min(watch.finished_at.values()) - t_start, 1)
            if watch.finished_at else None,
            "finished_timeline": finished_timeline(watch, t_open),
            "backlog_at_close": len(acked) - sum(1 for t in watch.finished_at.values() if t < t_close),
            "backlog_slope_per_s": backlog_slope(records, watch, dues, t_start, t_open, t_close),
            "drain_s": round(t_drained - t_close, 1), "collect_s": round(collect_s, 2),
            "collect_error": collect_error, "compiles_in_window": compiles,
            "compiled_in_window": compiled,
            "served": served,
            "every_metric": {k: v["value"] for k, v in both.items()},
        }
    )
    if compiles and not (args.rehearse or args.sweep or args.seeds):
        print_checks(checks)
        raise RunFailure(
            f"{compiles} programs were lowered inside the window: a shape was missed in set-up"
        )
    metrics = read(ctx["plan"]["per_layer"] if args.trace else ctx["plan"]["end_to_end"])
    result = {
        "correct": correct,
        "attempted": len(uploads),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    # set-up's split needs no trace: on the line of either kind of run
    result["setup_phases"] = phases
    if args.rehearse:
        result["rehearsal_values"] = {k: v["value"] for k, v in metrics.items()}
        result["metrics"] = {}
    result["checks"] = checks
    return result


def finished_timeline(watch, t_open):
    """[seconds from the window's opening, reports finished so far] at every
    instant the watch saw jobs finish: the completions' quanta, for reading
    what a count inside any window would have been."""
    steps, total = [], 0
    for t in sorted(watch.finished_at.values()):
        total += 1
        if steps and steps[-1][0] == round(t - t_open, 2):
            steps[-1][1] = total
        else:
            steps.append([round(t - t_open, 2), total])
    return steps


def backlog_slope(records, watch, dues, t_start, t_open, t_close):
    """Reports a second by which the un-aggregated backlog (ACKed, not yet
    in a finished job) grew over the second half of the window."""

    def backlog(t):
        acked = sum(1 for i, _r, _l, lat, st in records if st == 201 and t_start + dues[i] + lat <= t)
        return acked - sum(1 for f in watch.finished_at.values() if f <= t)

    mid = (t_open + t_close) / 2.0
    return round((backlog(t_close) - backlog(mid)) / (t_close - mid), 2)


def print_checks(checks):
    """Each number compared beside its limit, as the last lines of
    standard error."""
    for name, c in checks.items():
        print(f"check {name}: value {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()


# -- the run -----------------------------------------------------------------


async def run(args, plan, device):
    from fleet import Fleet
    from loadgen import Senders

    config = dict(plan["config"])
    if args.rehearse:
        # the file's "rehearse" block: another vdaf whole, other groups merged
        for key, value in config.get("rehearse", {}).items():
            config[key] = value if key == "vdaf" else {**config[key], **value}
    if args.fault == "oracle":
        config["vdaf_backend"] = "oracle"
    peaks = load_json("benchmark", "peaks.json").get(device["kind"])
    if peaks is None and not args.rehearse:
        raise RunFailure(f"benchmark/peaks.json has no entry for {device['kind']!r}")
    seeds = [int(s) for s in args.seeds.split(",")] if args.seeds else [args.seed]
    rates = [float(r) for r in args.sweep.split(",")] if args.sweep else [plan["traffic"]["rate"]]
    legs = [(s, r) for s in seeds for r in rates]
    out_dir = os.path.join(ROOT, "benchmark_out")
    os.makedirs(out_dir, exist_ok=True)
    # load (or build) native/libjanusts.so here, before the workers race to
    from janus_tpu import native

    log({"native_xof": "unavailable" if native.load() is None else "loaded"})
    workers = max(2, min(10, (os.cpu_count() or 4) - 3))
    senders = Senders(workers)
    result = None
    with tempfile.TemporaryDirectory(prefix="janus-bench-") as workdir:
        fleet = Fleet(workdir, config)
        await fleet.start()
        ctx = {
            "args": args, "plan": plan, "config": config, "traffic": plan["traffic"],
            "fleet": fleet, "senders": senders, "device": device, "out_dir": out_dir,
            "cache_events": count_cache_events(), "compile_log": CompileLog(),
            "peaks": {k: v for k, v in (peaks or {}).items() if isinstance(v, (int, float))},
            "trace_planes": ("/device:TPU:", "") if device["platform"] == "tpu"
            else ("/host:CPU", "tf_XLAPjRtCpuClient"),
            # one batch interval, the hour before this one, for every report
            "time_s": (int(time.time()) // config["time_precision_s"] - 1)
            * config["time_precision_s"],
        }
        try:
            for index, (seed, rate) in enumerate(legs):
                result = await run_leg(ctx, index, seed, rate, args.seconds)
                if index + 1 < len(legs):
                    log(result)
        finally:
            senders.stop()
            await fleet.stop()
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the harness's own switches; the driver never passes them
    parser.add_argument("--rehearse", action="store_true", help="tiny size on the CPU; no metric")
    parser.add_argument("--sweep", help="rates in turn after one set-up, comma separated")
    parser.add_argument("--seeds", help="seeds in turn after one set-up, comma separated")
    parser.add_argument("--fault", choices=("alter", "half_batch", "oracle"),
                        help="break the timed path underneath (control, tests)")
    args = parser.parse_args(argv)
    try:
        import janus_tpu  # noqa: F401

        plan = read_plan(args.workload)
        # the compile cache: a fixed directory inside the checkout, which the
        # program takes from the environment and sets no other
        if not args.rehearse:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
        device = find_device(plan["cell"]["chips"], args.rehearse)
        from janus_tpu.utils.jax_setup import enable_compile_cache

        log({"device": device, "compile_cache_dir": enable_compile_cache(),
             "rehearse": args.rehearse, "workload": args.workload, "seed": args.seed})
        result = asyncio.run(run(args, plan, device))
    except BaseException as e:
        traceback.print_exc()
        reason = str(e) if isinstance(e, RunFailure) else f"{type(e).__name__}: {e}"
        print(f"no result: {reason}", file=sys.stderr)
        return 1
    print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


def leave(code):
    """Exit.  A run that gave up in set-up has stopped what it started, but
    the program's warm-up may still compile on a thread that nobody can
    stop, and an orderly exit would wait minutes for it: a failed run goes
    at once."""
    sys.stdout.flush()
    sys.stderr.flush()
    if code:
        os._exit(code)
    sys.exit(0)


if __name__ == "__main__":
    leave(main())
