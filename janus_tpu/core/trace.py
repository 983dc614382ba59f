"""Tracing/logging configuration.

The analog of the reference's ``TraceConfiguration`` (reference:
aggregator/src/trace.rs:36-236): pretty or JSON structured stdout logging
with a runtime-reloadable level filter (the reference exposes this as PUT
``/traceconfigz`` on the health port; our health server does the same).
On-device profiling is the separate ``jax.profiler`` session the bench
harness can enable — host tracing stays here.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import os
import secrets
import sys
import threading
import time
from dataclasses import dataclass
from typing import Optional


# -- cross-process trace context ---------------------------------------------
# The fleet-wide correlation layer (reference: trace.rs OTel trace layer +
# the W3C traceparent the OTLP exporter propagates): a trace id is minted
# once per pipeline entity (upload batch / aggregation job / collection
# job), persisted on the job row, carried leader->helper in DAP HTTP
# headers, and bound here — a contextvar, so it follows the asyncio task —
# for every log line and ChromeTracer span to pick up.  That is what makes
# one aggregation job's timeline joinable across replica processes.

#: fields: trace_id (32 hex chars), task_id, job_id — all optional strings
_TRACE_CTX: contextvars.ContextVar[dict] = contextvars.ContextVar(
    "janus_trace_ctx", default={}
)

#: ctx keys stamped onto log records and chrome-trace span args
TRACE_CTX_KEYS = ("trace_id", "task_id", "job_id")


def new_trace_id() -> str:
    """A W3C-traceparent-style 16-byte random trace id (32 hex chars)."""
    return secrets.token_hex(16)


def current_trace() -> dict:
    """The bound trace context ({} when none)."""
    return _TRACE_CTX.get()


def bind_trace(**fields) -> contextvars.Token:
    """Merge ``fields`` (trace_id/task_id/job_id) into the bound context;
    returns a token for :func:`unbind_trace`.  None values are dropped so
    an unset field inherits the enclosing scope's."""
    merged = dict(_TRACE_CTX.get())
    for k, v in fields.items():
        if v is not None:
            merged[k] = str(v)
    return _TRACE_CTX.set(merged)


def unbind_trace(token: contextvars.Token) -> None:
    _TRACE_CTX.reset(token)


@contextlib.contextmanager
def trace_scope(**fields):
    """``with trace_scope(trace_id=..., task_id=..., job_id=...):`` — the
    scoped form of bind/unbind used by job steppers and HTTP handlers."""
    token = bind_trace(**fields)
    try:
        yield
    finally:
        unbind_trace(token)


def current_traceparent() -> Optional[str]:
    """The bound context as a W3C ``traceparent`` header value
    (``00-<trace-id>-<span-id>-01``), or None when no trace id is bound.
    The span id is minted per call: each outbound hop is its own span."""
    trace_id = _TRACE_CTX.get().get("trace_id")
    if not trace_id:
        return None
    return f"00-{trace_id}-{secrets.token_hex(8)}-01"


def inject_traceparent(headers: dict) -> None:
    """Stamp the bound context's ``traceparent`` onto outbound request
    ``headers`` (no-op when no trace id is bound) — the one place every
    peer-HTTP path calls so cross-process correlation cannot be forgotten
    by a new client."""
    traceparent = current_traceparent()
    if traceparent:
        headers["traceparent"] = traceparent


def parse_traceparent(value: Optional[str]) -> Optional[str]:
    """Extract the trace id from a ``traceparent`` header (None on any
    malformation — a bad peer header must never break request handling)."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) < 4 or len(parts[1]) != 32:
        return None
    trace_id = parts[1].lower()
    # strict per-char hex: int(x, 16) would accept '+'/'-'/'_' and
    # whitespace, adopting ids W3C-strict peers will drop downstream
    if any(c not in "0123456789abcdef" for c in trace_id):
        return None
    if trace_id == "0" * 32:
        return None
    return trace_id


@dataclass
class TraceConfiguration:
    """reference: trace.rs:36"""

    use_json: bool = False
    level: str = "INFO"


class TraceContextFilter(logging.Filter):
    """Stamps the bound trace context onto every log record, so formatters
    (and ad-hoc ``%(trace_id)s`` format strings) can render it."""

    def filter(self, record: logging.LogRecord) -> bool:
        ctx = _TRACE_CTX.get()
        for key in TRACE_CTX_KEYS:
            setattr(record, key, ctx.get(key))
        return True


class JsonFormatter(logging.Formatter):
    """One JSON object per line (reference: trace.rs json/stackdriver
    stdout modes), carrying the bound trace context when present."""

    def format(self, record: logging.LogRecord) -> str:
        doc = {
            "ts": round(time.time(), 3),
            "level": record.levelname,
            "logger": record.name,
            "message": record.getMessage(),
        }
        for key in TRACE_CTX_KEYS:
            value = getattr(record, key, None)
            if value is not None:
                doc[key] = value
        if record.exc_info:
            doc["exception"] = self.formatException(record.exc_info)
        return json.dumps(doc)


def install_trace_subscriber(config: Optional[TraceConfiguration] = None) -> None:
    """reference: trace.rs:119 install_trace_subscriber"""
    config = config or TraceConfiguration()
    root = logging.getLogger()
    for h in list(root.handlers):
        root.removeHandler(h)
    handler = logging.StreamHandler(sys.stdout)
    handler.addFilter(TraceContextFilter())
    if config.use_json:
        handler.setFormatter(JsonFormatter())
    else:
        handler.setFormatter(
            logging.Formatter("%(asctime)s %(levelname)s %(name)s %(message)s")
        )
    root.addHandler(handler)
    root.setLevel(getattr(logging, config.level.upper(), logging.INFO))


def reload_trace_filter(level: str) -> None:
    """Runtime log-level reload (reference: binary_utils.rs:422-456
    /traceconfigz)."""
    logging.getLogger().setLevel(getattr(logging, level.upper(), logging.INFO))


# -- span sinks --------------------------------------------------------------
# Secondary consumers of closed spans (the OTLP exporter, core/otlp.py):
# callables ``sink(name, cat, epoch_start_s, dur_s, args)``.  Spans reach
# sinks whether or not chrome tracing is configured — the ChromeTracer
# forwards from emit(), and the module-level span helpers forward directly
# when no tracer exists.  Sink errors are swallowed: an export problem must
# never break the traced code path.

_SPAN_SINKS: list = []


def register_span_sink(sink) -> None:
    if sink not in _SPAN_SINKS:
        _SPAN_SINKS.append(sink)


def unregister_span_sink(sink) -> None:
    try:
        _SPAN_SINKS.remove(sink)
    except ValueError:
        pass


def _forward_span(name: str, cat: str, epoch_start_s: float, dur_s: float, args: dict) -> None:
    for sink in list(_SPAN_SINKS):
        try:
            sink(name, cat, epoch_start_s, dur_s, args)
        except Exception:
            pass


# -- chrome-trace export -----------------------------------------------------
# The analog of the reference's chrome tracing layer (trace.rs:145-156
# ChromeLayer): spans around job steps / device launches, written in the
# Trace Event Format chrome://tracing and Perfetto load directly.


class ChromeTracer:
    """Incremental Trace-Event-Format writer (JSON array of "X" events).

    Thread-safe; events are appended as they close, so a crash loses at most
    the open spans (the format tolerates a missing closing bracket).

    Cross-process merging (tools/trace_merge.py): events carry the real OS
    pid, every span inherits the bound trace context (trace_id/task_id/
    job_id) into its args, and a ``clock_sync`` metadata event records the
    wall-clock epoch of this process's monotonic t0 so per-replica files
    can be rebased onto one shared timeline.  A restarted replica pointed
    at the same path APPENDS (its new pid gets its own clock_sync) instead
    of truncating the dead incarnation's events.
    """

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._closed = False
        append = os.path.exists(path) and os.path.getsize(path) > 0
        self._f = open(path, "a" if append else "w")
        if not append:
            self._f.write("[\n")
        else:
            # the dead incarnation may have been SIGKILLed mid-write: start
            # on a fresh line so its partial trailing line cannot swallow
            # our clock_sync event (trace_merge needs it to rebase us)
            self._f.write("\n")
        self.pid = os.getpid()
        self._t0 = time.monotonic()
        self._epoch_t0 = time.time()
        self._write_event(
            {
                "name": "clock_sync",
                "ph": "M",
                "pid": self.pid,
                "tid": 0,
                "args": {"epoch_t0": self._epoch_t0},
            }
        )
        self._write_event(
            {
                "name": "process_name",
                "ph": "M",
                "pid": self.pid,
                "tid": 0,
                "args": {"name": f"{os.path.basename(sys.argv[0] or 'python')}:{self.pid}"},
            }
        )

    def _write_event(self, ev: dict) -> None:
        line = json.dumps(ev) + ",\n"
        with self._lock:
            if self._closed:
                return
            self._f.write(line)
            self._f.flush()

    def emit(self, name: str, cat: str, start_s: float, dur_s: float, **args) -> None:
        # Concurrent spans must land on distinct tracks: same-track
        # overlapping "X" events render as bogus nesting in trace viewers.
        # Thread identity separates executor/launch spans; same-thread
        # asyncio concurrency (job steps) additionally keys on the running
        # task so parallel steps get their own rows.
        tid = threading.get_ident() % 100000
        try:
            import asyncio

            task = asyncio.current_task()
            if task is not None:
                tid = 100000 + id(task) % 100000
        except RuntimeError:
            pass
        ctx = _TRACE_CTX.get()
        for key in TRACE_CTX_KEYS:
            if key not in args and ctx.get(key) is not None:
                args[key] = ctx[key]
        ev = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "pid": self.pid,
            "tid": tid,
            "ts": round((start_s - self._t0) * 1e6, 1),
            "dur": round(dur_s * 1e6, 1),
        }
        if args:
            ev["args"] = args
        self._write_event(ev)
        if _SPAN_SINKS:
            _forward_span(
                name, cat, self._epoch_t0 + (start_s - self._t0), dur_s, dict(args)
            )

    def span(self, name: str, cat: str = "job", **args):
        return _Span(self, name, cat, args)

    def close(self) -> None:
        """Flush and close; idempotent (the graceful-shutdown path and an
        atexit/teardown race may both call it)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._f.write("{}]\n")  # sentinel keeps the array valid JSON
            self._f.close()


class _Span:
    def __init__(self, tracer: ChromeTracer, name: str, cat: str, args):
        self.tracer, self.name, self.cat, self.args = tracer, name, cat, args

    def __enter__(self):
        self.start = time.monotonic()
        return self

    def __exit__(self, exc_type, *_):
        self.tracer.emit(
            self.name,
            self.cat,
            self.start,
            time.monotonic() - self.start,
            ok=exc_type is None,
            **self.args,
        )
        return False


_GLOBAL_TRACER: Optional[ChromeTracer] = None


def configure_chrome_trace(path: Optional[str]) -> Optional[ChromeTracer]:
    """Enable (or disable with None) process-wide chrome-trace output."""
    global _GLOBAL_TRACER
    if _GLOBAL_TRACER is not None:
        _GLOBAL_TRACER.close()
        _GLOBAL_TRACER = None
    if path:
        _GLOBAL_TRACER = ChromeTracer(path)
    return _GLOBAL_TRACER


def close_chrome_trace() -> None:
    """Flush/close the global tracer WITHOUT dropping the configuration
    handle — the binaries' graceful-shutdown (SIGTERM) hook, so soak traces
    are never truncated mid-event.  Safe to call when tracing is off."""
    if _GLOBAL_TRACER is not None:
        _GLOBAL_TRACER.close()


def chrome_trace_path() -> Optional[str]:
    """The active chrome-trace output path (None when tracing is off) —
    surfaced by /statusz."""
    return _GLOBAL_TRACER.path if _GLOBAL_TRACER is not None else None


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _SinkSpan:
    """Span measured for the registered sinks only (OTLP configured while
    chrome tracing is off) — mirrors _Span's context inheritance."""

    def __init__(self, name: str, cat: str, args: dict):
        self.name, self.cat, self.args = name, cat, args

    def __enter__(self):
        self.start = time.monotonic()
        return self

    def __exit__(self, exc_type, *_):
        _sink_emit(
            self.name,
            self.cat,
            self.start,
            time.monotonic() - self.start,
            dict(self.args, ok=exc_type is None),
        )
        return False


def _sink_emit(name: str, cat: str, start_mono_s: float, dur_s: float, args: dict) -> None:
    """Forward a monotonic-timed span to the sinks with the bound trace
    context merged in (the ChromeTracer-less twin of ChromeTracer.emit)."""
    ctx = _TRACE_CTX.get()
    for key in TRACE_CTX_KEYS:
        if key not in args and ctx.get(key) is not None:
            args[key] = ctx[key]
    epoch_start = time.time() - (time.monotonic() - start_mono_s)
    _forward_span(name, cat, epoch_start, dur_s, args)


def tracing_active() -> bool:
    """True when SOME span consumer exists (chrome tracer or a sink) —
    the cheap guard for span producers whose data gathering is itself
    expensive (e.g. a datastore query feeding a link span)."""
    return _GLOBAL_TRACER is not None or bool(_SPAN_SINKS)


def trace_span(name: str, cat: str = "job", **args):
    """Span against the global tracer (and any registered span sinks);
    free no-op when both are off."""
    t = _GLOBAL_TRACER
    if t is not None:
        return t.span(name, cat, **args)
    if _SPAN_SINKS:
        return _SinkSpan(name, cat, args)
    return _NULL_SPAN


def emit_span(name: str, cat: str, start_s: float, dur_s: float, **args) -> None:
    """Emit an already-measured span directly (no context manager) —
    retroactive CHILD spans whose interval is known only after the parent
    closed, e.g. the per-submission shares of one executor mega-batch
    flush.  Explicit trace_id/task_id/job_id args override the calling
    context's, so a flush running on the executor's loop can stamp each
    child with ITS submitter's identity.  Free no-op when tracing is off."""
    t = _GLOBAL_TRACER
    if t is not None:
        t.emit(name, cat, start_s, dur_s, **args)
    elif _SPAN_SINKS:
        _sink_emit(name, cat, start_s, dur_s, dict(args))


# -- the phase clock ----------------------------------------------------------
# One measurement per boundary inside the served path, three outputs of it:
# the ``janus_phase_*`` families (always), a ``jax.profiler.TraceAnnotation``
# in the profiler's own clock (processes that already hold jax; recorded
# only while a profiler session runs, in the same ``.xplane.pb`` as the
# device ops), and the ``trace_span`` it would be anyway (when chrome/OTLP
# tracing is configured).  Granularity: per flush, per job step, per helper
# request — never per report.

#: what a phase's thread is doing: ``python`` (a synchronous body: no
#: ``await``, no device or socket wait inside — so wall minus thread CPU is
#: time it waited for the GIL or the scheduler), ``device`` (blocked on the
#: chip or a transfer), ``queue`` (waiting for a window, a thread, a lock),
#: ``io`` (datastore tx, peer HTTP).  ``python`` and ``device`` bodies are
#: synchronous by definition and get the profiler annotation; ``queue`` and
#: ``io`` phases hold an ``await`` or span threads, and do not.
PHASE_KINDS = ("python", "device", "queue", "io")

#: THE table: group -> phase -> kind.  ``leader_step`` and ``helper_init``
#: are their own ``scope`` label; an ``executor`` or ``backend`` phase
#: carries the executor bucket's label (``Histogram/a0/prep_init#1a2b3c``:
#: leader/helper and prep_init/combine stay apart), or the backend's own
#: ``<circuit>/aggregate`` / ``<circuit>/accumulate`` outside a flush.  A
#: phase that is not here raises.
PHASES = {
    "executor": {
        "window_wait": "queue",  # oldest live submission's enqueue -> dispatch
        "stage_queue": "queue",  # dispatch -> the stage body starts on janus-exec-stage
        "stage_wake": "queue",  # stage body done -> the flush's task runs again
        "launch_queue": "queue",  # stage done -> the launch body starts on janus-exec-launch
        "launch_wake": "queue",  # launch body done -> the flush's task runs again
        "resolve": "python",  # bookkeeping, cost attribution, every _resolve
    },
    "backend": {
        "marshal": "python",  # rows -> numpy (limb packing, verify-key stack)
        "place": "device",  # _place: commit the inputs to the device(s)
        "dispatch": "python",  # the compiled program's call returns
        "readback": "device",  # np.asarray of the outputs: device time + D2H
        "unmarshal": "python",  # numpy -> per-row protocol objects
        "launch": "device",  # a launch the backend does not split (Poplar1 sketch)
    },
    "leader_step": {
        "load_tx": "io",
        "decode_rows": "python",
        "prep_init": "queue",  # executor submit -> result
        "wrap_outcomes": "python",
        "encode_req": "python",
        "helper_http": "io",
        "process_resp": "python",
        "commit_shares": "queue",  # resident out shares -> accumulators
        "write_tx": "io",
    },
    "helper_init": {
        "decode_req": "python",
        "replay_tx": "io",
        "conflicts_tx": "io",
        "validate": "python",
        "hpke_open": "python",  # the open_batch worker-thread body
        "decode_shares": "python",
        "decode_leader_shares": "python",
        "prep_init": "queue",
        "combine": "queue",
        "finish": "python",
        "assemble": "python",
        "commit_shares": "queue",
        "write_tx": "io",
        "encode_resp": "python",
    },
}

_PHASE_LOCAL = threading.local()
#: (scope, phase) -> seconds of thread CPU read beyond a phase's wall time,
#: owed to the next observations.  Where the kernel accounts a thread's CPU
#: by the scheduler's tick (milliseconds: the v5e hosts do), a phase shorter
#: than a tick reads no CPU at all most of the time and a whole tick now and
#: then; carrying the excess keeps the counter's long-run sum true, where
#: clamping each observation at 0 would count short phases as all waiting.
_OFFCPU_OWED: dict = {}
_OFFCPU_LOCK = threading.Lock()


def phase_group(scope: str, phase: str, kind: str) -> str:
    """The ``PHASES`` group of one (scope, phase, kind); raises for a
    triple the table does not hold."""
    if scope in PHASES:
        group = scope
    else:
        group = "executor" if phase in PHASES["executor"] else "backend"
    if PHASES[group].get(phase) != kind:
        raise ValueError(
            f"phase ({scope!r}, {phase!r}, {kind!r}) is not in core.trace.PHASES"
        )
    return group


@contextlib.contextmanager
def phase_scope(scope: str, **args):
    """Until exit, every phase of THIS thread takes ``scope`` (and carries
    ``args``) in place of its caller's own, and its seconds add up by
    phase in the yielded dict.  The executor binds its bucket label — and
    the flush's ``seq`` — around the backend calls on its stage and launch
    threads: the backend cannot know either."""
    prev = getattr(_PHASE_LOCAL, "bound", None)
    seconds: dict = {}
    _PHASE_LOCAL.bound = (scope, args, seconds)
    try:
        yield seconds
    finally:
        _PHASE_LOCAL.bound = prev


def _record_phase(group, scope, phase, kind, start_s, end_s, offcpu_s, args, ok=True):
    """The outputs of one measured phase that are not the annotation."""
    from .metrics import GLOBAL_METRICS

    seconds = max(0.0, end_s - start_s)
    if GLOBAL_METRICS.registry is not None:
        GLOBAL_METRICS.phase_seconds.labels(scope=scope, phase=phase, kind=kind).observe(
            seconds
        )
        if offcpu_s is not None:
            with _OFFCPU_LOCK:
                off = _OFFCPU_OWED.get((scope, phase), 0.0) + min(seconds, offcpu_s)
                _OFFCPU_OWED[scope, phase] = min(off, 0.0)
            if off > 0.0:
                GLOBAL_METRICS.phase_offcpu_seconds.labels(
                    scope=scope, phase=phase, kind=kind
                ).inc(off)
    if tracing_active():
        emit_span(
            f"janus.{group}.{phase}", "phase", start_s, seconds,
            scope=scope, kind=kind, ok=ok, **args,
        )
    return seconds


class _Phase:
    """One timed phase: ``start``/``end`` (monotonic seconds) and
    ``seconds`` are readable after exit, so a caller that owes the same
    interval to an older metric or span feeds it from these stamps."""

    __slots__ = ("scope", "phase", "kind", "args", "group", "start", "end",
                 "seconds", "_cpu0", "_ann", "_into")

    def __init__(self, scope, phase, kind, args):
        bound = getattr(_PHASE_LOCAL, "bound", None)
        self._into = None
        if bound is not None:
            scope, args, self._into = bound[0], {**bound[1], **args}, bound[2]
        self.scope, self.phase, self.kind, self.args = scope, phase, kind, args
        self.group = phase_group(scope, phase, kind)
        self.start = self.end = self.seconds = 0.0

    def __enter__(self):
        self._ann = None
        if self.kind in ("python", "device"):
            jax = sys.modules.get("jax")
            if jax is not None:
                # ("#" ends the metadata of a profiler event's name, and a
                # bucket's label holds one before its digest)
                self._ann = jax.profiler.TraceAnnotation(
                    f"janus.{self.group}.{self.phase}",
                    scope=self.scope.replace("#", "@"),
                    **self.args,
                )
                self._ann.__enter__()
        # the CPU pair lies inside the wall pair: wall >= CPU by construction
        self.start = time.monotonic()
        self._cpu0 = time.thread_time() if self.kind == "python" else None
        return self

    def __exit__(self, exc_type, exc, tb):
        cpu = time.thread_time() - self._cpu0 if self._cpu0 is not None else None
        self.end = time.monotonic()
        offcpu = None if cpu is None else (self.end - self.start) - cpu
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        self.seconds = _record_phase(
            self.group, self.scope, self.phase, self.kind, self.start, self.end,
            offcpu, self.args, ok=exc_type is None,
        )
        if self._into is not None:
            self._into[self.phase] = self._into.get(self.phase, 0.0) + self.seconds
        return False


def trace_phase(scope: str, phase: str, kind: str, **args) -> _Phase:
    """``with trace_phase(scope, phase, kind, rows=...):`` — time one phase
    of ``PHASES`` on the thread that does the work: one ``time.monotonic()``
    pair (for ``kind="python"`` one ``time.thread_time()`` pair besides),
    observed once, also when the body raises.  ``args`` go to the
    annotation and the span, never to a metric label."""
    return _Phase(scope, phase, kind, args)


def emit_phase(scope: str, phase: str, kind: str, start_s: float, end_s: float, **args) -> float:
    """A phase whose two stamps were taken apart (a wait that starts on
    the event loop and ends on a worker thread): the :func:`emit_span` of
    phases.  Returns its seconds.  No annotation — the profiler takes no
    interval after the fact; a reader places a wait from the start of the
    annotation that follows it."""
    return _record_phase(
        phase_group(scope, phase, kind), scope, phase, kind, start_s, end_s, None, args
    )


def retire_phase_scope(scope: str) -> None:
    """Drop every ``janus_phase_*`` series of one retired scope (an idle
    executor bucket): cardinality follows live traffic, not history."""
    from .metrics import GLOBAL_METRICS

    if GLOBAL_METRICS.registry is None:
        return
    for group in ("executor", "backend"):
        for phase, kind in PHASES[group].items():
            _OFFCPU_OWED.pop((scope, phase), None)
            for metric in (GLOBAL_METRICS.phase_seconds, GLOBAL_METRICS.phase_offcpu_seconds):
                GLOBAL_METRICS.remove_series(metric, scope, phase, kind)


def start_profiler_server(port: int) -> bool:
    """Opt-in on-device profiling: a jax.profiler server an operator can
    capture from at any time (the analog of the reference's tokio-console /
    OTLP always-on observability sockets, trace.rs:158-236).  Returns False
    when jax is unavailable in this process (control-plane binaries — the
    GATE PROBE, logged quietly: a jax-less process is a deployment shape,
    not an error) or when the server fails to start (logged with the
    traceback; the binary continues — a dead profiler socket must never
    take a replica down)."""
    log = logging.getLogger("janus_tpu.trace")
    try:
        import jax
    except ImportError:
        log.info(
            "jax unavailable in this process; profiler server not started"
        )
        return False
    except Exception:
        # import jax can die with RuntimeError/OSError on a broken device
        # runtime (libtpu init) — still logs-and-continues, never fatal
        log.exception("jax import failed; profiler server not started")
        return False
    try:
        jax.profiler.start_server(port)
        return True
    except Exception:
        log.exception("could not start jax profiler server")
        return False
