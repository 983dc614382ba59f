"""The phase clock (core/trace.py ``trace_phase`` / ``PHASES``): one
measurement per boundary inside the served path, in the ``janus_phase_*``
families, as profiler annotations, as spans — and the name scopes on the
prepare programs' device ops."""

import ast
import asyncio
import contextlib
import pathlib
import subprocess
import sys
import time

import pytest

from janus_tpu.core import trace
from janus_tpu.core.metrics import GLOBAL_METRICS
from janus_tpu.core.trace import PHASES, emit_phase, phase_scope, trace_phase

REPO = pathlib.Path(__file__).resolve().parent.parent


def _sample(name, **labels):
    return GLOBAL_METRICS.registry.get_sample_value(name, labels) or 0.0


def _count(scope, phase, kind):
    return _sample("janus_phase_seconds_count", scope=scope, phase=phase, kind=kind)


def _sum(scope, phase, kind):
    return _sample("janus_phase_seconds_sum", scope=scope, phase=phase, kind=kind)


def _offcpu(scope, phase, kind):
    return _sample(
        "janus_phase_offcpu_seconds_total", scope=scope, phase=phase, kind=kind
    )


# ---------------------------------------------------------------------------
# the primitive


@pytest.mark.parametrize(
    "scope,phase,kind",
    [
        (group if group in ("leader_step", "helper_init") else "T/a0/prep_init#t", p, k)
        for group, table in PHASES.items()
        for p, k in table.items()
    ],
)
def test_every_phase_of_the_table_observes_once_with_its_labels(scope, phase, kind):
    before = _count(scope, phase, kind)
    with trace_phase(scope, phase, kind, rows=3) as ph:
        pass
    assert _count(scope, phase, kind) == before + 1
    assert ph.end >= ph.start and ph.seconds == ph.end - ph.start


def test_phase_is_observed_once_when_the_body_raises():
    key = ("leader_step", "decode_rows", "python")
    before = _count(*key)
    with pytest.raises(RuntimeError):
        with trace_phase(*key):
            raise RuntimeError("boom")
    assert _count(*key) == before + 1


@pytest.mark.parametrize(
    "scope,phase,kind",
    [
        ("leader_step", "no_such_phase", "python"),
        ("leader_step", "load_tx", "python"),  # right phase, wrong kind
        ("T/a0/prep_init#t", "load_tx", "io"),  # a step's phase in a bucket's scope
        ("helper_init", "marshal", "python"),  # a backend phase in a request's scope
    ],
)
def test_a_phase_the_table_does_not_hold_raises(scope, phase, kind):
    with pytest.raises(ValueError, match="PHASES"):
        trace_phase(scope, phase, kind)
    with pytest.raises(ValueError, match="PHASES"):
        emit_phase(scope, phase, kind, 0.0, 1.0)


def test_off_cpu_never_exceeds_wall_and_sleep_is_off_cpu():
    sleepy = ("helper_init", "finish", "python")
    busy = ("helper_init", "assemble", "python")
    s0, o0 = _sum(*sleepy), _offcpu(*sleepy)
    with trace_phase(*sleepy):
        time.sleep(0.05)  # stands for a wait for the GIL: wall without CPU
    wall, off = _sum(*sleepy) - s0, _offcpu(*sleepy) - o0
    assert 0.04 <= off <= wall
    s0, o0 = _sum(*busy), _offcpu(*busy)
    with trace_phase(*busy):
        t_end = time.thread_time() + 0.05
        while time.thread_time() < t_end:
            pass
    wall, off = _sum(*busy) - s0, _offcpu(*busy) - o0
    assert 0.0 <= off <= wall and off < 0.5 * wall
    # only python phases count off-CPU time
    with trace_phase("leader_step", "load_tx", "io"):
        time.sleep(0.01)
    assert _offcpu("leader_step", "load_tx", "io") == 0.0


def test_cpu_read_beyond_the_wall_is_carried_not_dropped():
    """A CPU clock that ticks reads 0 for most short phases and a whole
    tick for a few: the excess is owed to the next observations, so the
    sum stays true (two of three 1 ms phases read no CPU, one reads 4 ms:
    by the counter 0 of 3 ms were off the CPU, not 2)."""
    key = ("leader_step", "encode_req", "python")
    before = _offcpu(*key)
    for wall, cpu in ((0.001, 0.004), (0.001, 0.0), (0.001, 0.0), (0.002, 0.0)):
        trace._record_phase("leader_step", *key, 10.0, 10.0 + wall, wall - cpu, {})
    assert _offcpu(*key) - before == pytest.approx(0.001)  # 5 ms of wall, 4 of CPU


def test_emit_phase_observes_a_wait_whose_stamps_were_taken_apart():
    key = ("T/a1/combine#t", "launch_queue", "queue")
    c0, s0 = _count(*key), _sum(*key)
    assert emit_phase(*key, 10.0, 10.25, seq=7) == pytest.approx(0.25)
    assert _count(*key) == c0 + 1
    assert _sum(*key) - s0 == pytest.approx(0.25)
    assert emit_phase(*key, 10.0, 9.0) == 0.0  # never negative


def test_phase_scope_binds_the_scope_and_collects_seconds_on_this_thread():
    bound = "T/a0/prep_init#bound"
    c0 = _count(bound, "marshal", "python")
    with phase_scope(bound, seq=4) as seconds:
        with trace_phase("T/a0/prep_init", "marshal", "python", rows=1) as ph:
            pass
        with trace_phase("T/a0/prep_init", "marshal", "python", rows=1):
            pass
    assert ph.scope == bound and ph.args == {"seq": 4, "rows": 1}
    assert _count(bound, "marshal", "python") == c0 + 2
    assert set(seconds) == {"marshal"} and seconds["marshal"] >= ph.seconds
    # unbound again
    with trace_phase("T/a0/prep_init", "place", "device") as ph:
        pass
    assert ph.scope == "T/a0/prep_init"


def test_retire_phase_scope_drops_the_scopes_series():
    scope = "T/a0/prep_init#retired"
    with trace_phase(scope, "marshal", "python"):
        pass
    emit_phase(scope, "window_wait", "queue", 0.0, 1.0)
    assert _count(scope, "marshal", "python") == 1
    trace.retire_phase_scope(scope)
    assert _count(scope, "marshal", "python") == 0
    assert _count(scope, "window_wait", "queue") == 0
    assert _offcpu(scope, "marshal", "python") == 0


# ---------------------------------------------------------------------------
# the profiler's annotation, and the span


class _Annotations:
    def __init__(self):
        self.seen = []

    def __call__(self, name, **kwargs):
        self.seen.append((name, kwargs))
        return contextlib.nullcontext()


def test_python_and_device_phases_are_annotated_in_a_process_that_holds_jax(monkeypatch):
    import jax

    rec = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    with trace_phase("H/a1/prep_init#x", "marshal", "python", rows=8, seq=2):
        pass
    with trace_phase("H/a1/prep_init#x", "readback", "device", rows=8):
        pass
    with trace_phase("leader_step", "decode_rows", "python"):
        pass
    with trace_phase("helper_init", "hpke_open", "python"):
        pass
    # waits hold an await or span threads: no annotation
    with trace_phase("leader_step", "helper_http", "io"):
        pass
    with trace_phase("helper_init", "prep_init", "queue"):
        pass
    emit_phase("H/a1/prep_init#x", "stage_queue", "queue", 0.0, 1.0)
    assert rec.seen == [
        # ("#" would end the event's metadata: the label's digest follows "@")
        ("janus.backend.marshal", {"scope": "H/a1/prep_init@x", "rows": 8, "seq": 2}),
        ("janus.backend.readback", {"scope": "H/a1/prep_init@x", "rows": 8}),
        ("janus.leader_step.decode_rows", {"scope": "leader_step"}),
        ("janus.helper_init.hpke_open", {"scope": "helper_init"}),
    ]


def test_no_annotation_and_no_jax_import_in_a_process_without_jax():
    """A control-plane binary never imports jax for a phase."""
    code = (
        "import sys\n"
        "from janus_tpu.core.trace import trace_phase, emit_phase\n"
        "from janus_tpu.core.metrics import GLOBAL_METRICS as g\n"
        "with trace_phase('leader_step', 'decode_rows', 'python', rows=1) as ph:\n"
        "    pass\n"
        "emit_phase('leader_step', 'load_tx', 'io', 0.0, 0.5)\n"
        "assert ph._ann is None\n"
        "assert 'jax' not in sys.modules, 'a phase imported jax'\n"
        "v = g.registry.get_sample_value('janus_phase_seconds_count',\n"
        "    {'scope': 'leader_step', 'phase': 'decode_rows', 'kind': 'python'})\n"
        "assert v == 1.0, v\n"
        "print('ok')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True, timeout=120
    )
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_a_phase_is_the_span_it_would_be_when_chrome_tracing_is_on(tmp_path):
    import json

    path = tmp_path / "trace.json"
    trace.configure_chrome_trace(str(path))
    try:
        with trace.trace_scope(trace_id="ab" * 16, job_id="j1"):
            with trace_phase("leader_step", "load_tx", "io"):
                pass
            emit_phase("H/a0/prep_init#x", "window_wait", "queue", 1.0, 3.0, seq=9)
    finally:
        trace.configure_chrome_trace(None)
    events = [e for e in json.loads(path.read_text()) if e.get("cat") == "phase"]
    assert [e["name"] for e in events] == [
        "janus.leader_step.load_tx",
        "janus.executor.window_wait",
    ]
    assert events[0]["args"]["trace_id"] == "ab" * 16
    assert events[0]["args"]["kind"] == "io" and events[0]["args"]["ok"] is True
    assert events[1]["dur"] == 2e6 and events[1]["args"]["seq"] == 9
    assert events[1]["args"]["scope"] == "H/a0/prep_init#x"


# ---------------------------------------------------------------------------
# the table is the contract: what the code times is in it, and all of it is timed

_TIMED = (
    "janus_tpu/executor/service.py",
    "janus_tpu/vdaf/backend.py",
    "janus_tpu/aggregator/aggregation_job_driver.py",
    "janus_tpu/aggregator/aggregator.py",
    "janus_tpu/aggregator/http_handlers.py",
)


def _phase_calls():
    """(file, scope or None, phase, kind) of every trace_phase/emit_phase
    call whose phase and kind are literals."""
    out = []
    for rel in _TIMED:
        for node in ast.walk(ast.parse((REPO / rel).read_text())):
            if not (
                isinstance(node, ast.Call)
                and getattr(node.func, "id", getattr(node.func, "attr", None))
                in ("trace_phase", "emit_phase")
            ):
                continue
            scope, phase, kind = node.args[:3]
            if isinstance(phase, ast.Constant) and isinstance(kind, ast.Constant):
                out.append(
                    (
                        rel,
                        scope.value if isinstance(scope, ast.Constant) else None,
                        phase.value,
                        kind.value,
                    )
                )
    return out


def test_every_timed_phase_is_in_the_table_and_every_row_of_the_table_is_timed():
    calls = _phase_calls()
    assert len(calls) >= 35
    timed = {g: set() for g in PHASES}
    for rel, scope, phase, kind in calls:
        group = trace.phase_group(scope or "Circuit/a0/prep_init#0", phase, kind)
        timed[group].add(phase)
    # the executor's thread waits are named from the half of the flush
    timed["executor"] |= {h + s for h in ("stage", "launch") for s in ("_queue", "_wake")}
    assert timed == {g: set(table) for g, table in PHASES.items()}
    for table in PHASES.values():
        assert set(table.values()) <= set(trace.PHASE_KINDS)


def test_the_two_families_are_in_the_metric_manifest():
    manifest = (REPO / "tests" / "metric_manifest.txt").read_text().split()
    assert "janus_phase_seconds|histogram|scope,phase,kind" in manifest
    assert "janus_phase_offcpu_seconds|counter|scope,phase,kind" in manifest


# ---------------------------------------------------------------------------
# one executor flush on the CPU backend


def _count_reports(vdaf, n):
    import secrets

    out = []
    for i in range(n):
        nonce = secrets.token_bytes(vdaf.NONCE_SIZE)
        public, shares = vdaf.shard(i % 2, nonce, secrets.token_bytes(vdaf.RAND_SIZE))
        out.append((nonce, public, shares))
    return out


def test_a_flush_on_the_cpu_backend_records_phases_that_cover_stage_and_launch():
    from janus_tpu.executor import DeviceExecutor, ExecutorConfig
    from janus_tpu.vdaf import vdaf_from_instance
    from janus_tpu.vdaf.backend import TpuBackend, vdaf_shape_key

    vdaf = vdaf_from_instance({"type": "Prio3Count"})
    backend = TpuBackend(vdaf)
    shape = vdaf_shape_key(vdaf)
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.02, warmup_rows=0))
    verify_key = b"\x07" * vdaf.VERIFY_KEY_SIZE
    reports = _count_reports(vdaf, 24)

    async def both_sides():
        leader = await ex.submit(
            shape, "prep_init",
            (verify_key, [(n, p, s[0]) for n, p, s in reports]),
            backend=backend, agg_id=0,
        )
        helper = await ex.submit(
            shape, "prep_init",
            (verify_key, [(n, p, s[1]) for n, p, s in reports]),
            backend=backend, agg_id=1,
        )
        combined = await ex.submit(
            shape, "combine",
            [[l[1], h[1]] for l, h in zip(leader, helper)],
            backend=backend, agg_id=1,
        )
        return combined

    try:
        assert asyncio.run(both_sides()) == [None] * 24
        flights = list(reversed(ex.flight_recorder.snapshot(8)))
    finally:
        ex.shutdown()
    assert [f["bucket"].split("#")[0] for f in flights] == [
        "Count/a0/prep_init", "Count/a1/prep_init", "Count/a1/combine",
    ]
    assert [f["seq"] for f in flights] == [1, 2, 3]
    for f in flights:
        # every field the benchmark reads keeps its name and meaning
        assert {"t", "bucket", "launch_ms", "stage_ms", "queue_delay_max_ms"} <= set(f)
        assert f["t_dispatch_mono_ns"] <= time.monotonic() * 1e9
        phases = f["phases"]
        inside = ["launch_queue", "dispatch", "readback", "unmarshal", "launch_wake"]
        if f["bucket"].split("#")[0].endswith("prep_init"):
            inside += ["stage_queue", "marshal", "place", "stage_wake"]
        else:
            inside += ["marshal"]
        assert set(phases) == set(inside) | {"window_wait", "resolve"}
        assert phases["window_wait"] == f["queue_delay_max_ms"]
        covered = sum(phases[p] for p in inside)
        total = f["stage_ms"] + f["launch_ms"]
        unexplained_ms = total - covered
        print(f"{f['bucket']}: stage+launch {total:.3f} ms, unexplained_ms {unexplained_ms:.3f}")
        assert -0.01 <= unexplained_ms <= 0.10 * total
        # the families carry the same numbers under the bucket's label
        for p in phases:
            kind = PHASES["executor"].get(p) or PHASES["backend"][p]
            assert _count(f["bucket"], p, kind) == 1
            assert _sum(f["bucket"], p, kind) * 1e3 == pytest.approx(phases[p], abs=2e-3)
    # one measurement per boundary: the launch histogram and the prepare
    # histogram read the stamps the phases read
    a0 = flights[0]
    assert _sample(
        "janus_executor_launch_duration_seconds_sum", bucket=a0["bucket"]
    ) * 1e3 == pytest.approx(a0["launch_ms"], abs=2e-3)


# ---------------------------------------------------------------------------
# name scopes on the device ops: metadata only


def _lowered_prep_init(agg_id):
    import jax
    import numpy as np

    from janus_tpu.ops.prepare import BatchedPrio3
    from janus_tpu.vdaf import vdaf_from_instance

    vdaf = vdaf_from_instance({"type": "Prio3Histogram", "length": 8, "chunk_length": 3})
    bp = BatchedPrio3(vdaf)
    B, seed = 4, vdaf.xof.SEED_SIZE
    kw = {
        "nonces_u8": np.zeros((B, vdaf.NONCE_SIZE), np.uint8),
        "blinds_u8": np.zeros((B, seed), np.uint8),
        "public_parts_u8": np.zeros((B, vdaf.num_shares, seed), np.uint8),
    }
    if agg_id == 0:
        kw["meas_limbs"] = np.zeros((B, vdaf.flp.MEAS_LEN, bp.jf.n), np.uint32)
        kw["proofs_limbs"] = np.zeros((B, vdaf.flp.PROOF_LEN, bp.jf.n), np.uint32)
    else:
        kw["share_seeds_u8"] = np.zeros((B, seed), np.uint8)
    vk = np.zeros((seed,), np.uint8)
    prep = jax.jit(lambda vk, kw: bp.prep_init(agg_id, vk, kw.pop("nonces_u8"), **kw)).lower(vk, kw)
    ver = np.zeros((B, vdaf.flp.VERIFIER_LEN, bp.jf.n), np.uint32)
    part = np.zeros((B, seed), np.uint8)
    combine = jax.jit(bp.prep_shares_to_prep).lower([ver, ver], [part, part])
    agg = jax.jit(bp.aggregate).lower(
        np.zeros((B, vdaf.flp.OUTPUT_LEN, bp.jf.n), np.uint32), np.ones((B,), bool)
    )
    return prep, combine, agg


@pytest.mark.parametrize("agg_id", [0, 1])
def test_name_scopes_leave_the_lowered_programs_op_for_op_what_they_were(agg_id, monkeypatch):
    from janus_tpu.ops import prepare

    scoped = _lowered_prep_init(agg_id)
    monkeypatch.setattr(prepare, "_scope", lambda name: contextlib.nullcontext())
    bare = _lowered_prep_init(agg_id)
    for with_scopes, without in zip(scoped, bare):
        # debug info stripped: the programs are the same text
        assert with_scopes.as_text() == without.as_text()
    prep, combine, agg = (low.as_text(debug_info=True) for low in scoped)
    # (a Histogram's truncate is the identity: no op carries flp.truncate)
    want = ["xof.query_rand", "xof.joint_rand", "flp.wire_evals", "flp.gadget_eval",
            "verifier.pack"]
    if agg_id == 1:
        want += ["xof.expand_meas", "xof.expand_proof"]
    for name in want:
        assert name in prep, name
    assert "combine.decide" in combine and "xof.joint_rand" in combine
    assert "aggregate.sum" in agg
    assert "xof.query_rand" not in bare[0].as_text(debug_info=True)


# ---------------------------------------------------------------------------
# a capture holds the phases on the threads that did the work


def test_a_profiler_capture_holds_the_annotations_of_the_stage_and_launch_threads(tmp_path):
    """``tools/phase_trace.py`` on a CPU capture of two flushes: the
    backend's phases sit on the lines of the executor's stage and launch
    threads (named for the OS, which keeps 15 bytes), their count per name
    is the family's ``_count`` delta, and each carries its flush's ``seq``."""
    import jax

    sys.path.insert(0, str(REPO / "tools"))
    try:
        import phase_trace
    finally:
        sys.path.pop(0)
    from janus_tpu.executor import DeviceExecutor, ExecutorConfig
    from janus_tpu.vdaf import vdaf_from_instance
    from janus_tpu.vdaf.backend import TpuBackend, vdaf_shape_key

    vdaf = vdaf_from_instance({"type": "Prio3Count"})
    backend, shape = TpuBackend(vdaf), vdaf_shape_key(vdaf)
    ex = DeviceExecutor(ExecutorConfig(flush_window_s=0.01, warmup_rows=0))
    reports = _count_reports(vdaf, 8)
    payload = (b"\x07" * vdaf.VERIFY_KEY_SIZE, [(n, p, s[1]) for n, p, s in reports])

    def flush():
        return asyncio.run(ex.submit(shape, "prep_init", payload, backend=backend, agg_id=1))

    try:
        flush()  # compiles, outside the capture
        label = ex.flight_recorder.snapshot(1)[0]["bucket"]
        before = {p: _count(label, p, k) for p, k in PHASES["backend"].items()}
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            flush()
            flush()
        finally:
            jax.profiler.stop_trace()
        seqs = [f["seq"] for f in ex.flight_recorder.snapshot(2)]
    finally:
        ex.shutdown()
    out = phase_trace.report(str(tmp_path), plane_prefix="/host:CPU")
    stage, launch = out["threads"]["janus-exec-stag"], out["threads"]["janus-exec-laun"]
    assert set(stage) == {"janus.backend.marshal", "janus.backend.place"}
    assert set(launch) == {
        "janus.backend.dispatch", "janus.backend.readback", "janus.backend.unmarshal"
    }
    for name, row in {**stage, **launch}.items():
        phase = name.rsplit(".", 1)[1]
        kind = PHASES["backend"][phase]
        assert row["count"] == _count(label, phase, kind) - before[phase] == 2, name
    assert out["readback"]["count"] == 2
    data = phase_trace.load(str(tmp_path))
    spans = phase_trace.annotations(data)["janus-exec-stag"]["janus.backend.marshal"]["spans"]
    assert sorted(int(st["seq"]) for _s, _e, st in spans) == sorted(seqs)
    assert all(st["scope"] == label.replace("#", "@") for _s, _e, st in spans)
