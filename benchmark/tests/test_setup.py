"""Set-up: who makes the probe batch, the phases in the record and their
metrics, and the rule that ends a run which cannot fit its limit.
"""

import json
import os
import random
import subprocess
import sys
import textwrap
import time

import pytest

import loadgen
import readers
import run
from test_run_rehearsal import ROOT, cheapest_cell

# -- the fit rule, a pure function -------------------------------------------

PHASES = {"reports_made": 140.2, "prep_warm": 95.0, "probe": 29.1, "programs_warm": 60.4,
          "wait_made": 141.9}


@pytest.mark.parametrize(
    "elapsed, compiled, over",
    [
        # warm: 360 s; 171 + 20 + 51 + 30 = 272 is today's widest cell
        (171.0, False, False),
        (466.0, False, True),
        # a run that compiled: 1,200 s; 334 + 101 = 435 is today's cold run
        (334.0, True, False),
        (1100.0, True, True),
    ],
)
def test_fits_at_the_four_corners(elapsed, compiled, over):
    reason = run.fits(elapsed, 20.0, 51.0, compiled, PHASES)
    if not over:
        assert reason is None
        return
    limit = run.RUN_LIMIT_COMPILED_S if compiled else run.RUN_LIMIT_S
    end = elapsed + 20.0 + 51.0 + run.AFTER_WINDOW_S
    assert f"set-up took {elapsed:.0f} s" in reason
    assert f"would end at {end:.0f} s, over the {limit:.0f} s" in reason
    assert ("that compiled" in reason) == compiled
    # every phase by name, with its seconds
    assert "(reports made 140, prep warm 95, probe 29, programs warm 60, wait made 142)" in reason


def test_fits_edge_and_the_projections_wording():
    room = run.RUN_LIMIT_S - 20.0 - 51.0 - run.AFTER_WINDOW_S
    assert run.fits(room, 20.0, 51.0, False, PHASES) is None
    assert run.fits(room + 0.01, 20.0, 51.0, False, PHASES) is not None
    reason = run.fits(1800.0, 9.0, 51.0, True, {"reports_made": 1790.0}, projected=True)
    assert reason.startswith("set-up is projected to take 1800 s (reports made 1790)")
    assert (run.RUN_LIMIT_S, run.RUN_LIMIT_COMPILED_S) == (360.0, 1200.0)


# -- the phases: record, reader, metric files, manifest ----------------------


def test_setup_phase_reader_and_its_silence():
    rec = {"setup_phases": {"probe": 29.5, "wait_made": 0.0004}}
    assert readers.KINDS["setup_phase"](rec, {"phase": "probe"}) == 29.5
    assert readers.KINDS["setup_phase"](rec, {"phase": "wait_made"}) == 0.0004
    assert readers.KINDS["setup_phase"](rec, {"phase": "prep_warm"}) is None
    assert readers.KINDS["setup_phase"]({}, {"phase": "probe"}) is None


def test_every_phase_has_one_metric_that_moves_the_set_up_time():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = [w["name"] for w in manifest["workloads"]]
    seen = {}
    for m in manifest["per_layer"]:
        data = json.load(open(os.path.join(ROOT, "benchmark", "metrics", m["name"] + ".json")))
        if data["reader"] != "setup_phase":
            continue  # test_manifest.py: no other metric moves the set-up time
        assert set(data) == {"reader", "args", "definition"} and set(data["args"]) == {"phase"}
        assert data["args"]["phase"] in run.SETUP_PHASES, m["name"]
        assert data["args"]["phase"] not in seen, m["name"]
        seen[data["args"]["phase"]] = m["name"]
        assert (m["layer"], m["source"], m["better"], m["unit"], m["moves"]) == (
            "set-up", "host_clock", "lower", "s", "setup_s"
        ), m["name"]
        # every cell sets up, so every cell reads it
        assert m["workloads"] == cells, m["name"]
    assert set(seen) == set(run.SETUP_PHASES)


# -- the probe batch is made by the senders, one report a worker -------------

SLOW_CLIENT = """
import time
import loadgen

def worker(conn):
    real = loadgen._make_reports

    def slow(job):
        time.sleep({seconds} * len(job["items"]))
        return real(job)

    loadgen._make_reports = slow
    loadgen._worker(conn)
"""


def test_the_probe_costs_one_client_call_not_one_a_report(tmp_path, monkeypatch):
    """With a client that takes a second a report, a probe batch of four
    reports is there after about one second (one round of the pool), each
    made by another worker and none by this process; and the slices follow
    in the order asked."""
    from janus_tpu.core.hpke import HpkeKeypair

    seconds, workers = 1.0, 4
    (tmp_path / "slow_client.py").write_text(SLOW_CLIENT.format(seconds=seconds))
    monkeypatch.syspath_prepend(str(tmp_path))
    import slow_client

    monkeypatch.setattr(loadgen, "_worker", slow_client.worker)
    monkeypatch.setattr(
        loadgen, "_make_reports", lambda job: pytest.fail("the main process made a report")
    )
    vdaf = {"type": "Prio3Count"}
    job = {
        "vdaf": vdaf,
        "task_id": bytes(32),
        "leader_cfg": HpkeKeypair.generate(1).config.get_encoded(),
        "helper_cfg": HpkeKeypair.generate(2).config.get_encoded(),
        "time_s": 3600,
        "url": "http://127.0.0.1:9/",
    }
    rng = random.Random("probe")
    senders = loadgen.Senders(workers)
    try:
        assert len(senders) == workers
        items = [(i, m, rng.getrandbits(64), 0.0)
                 for i, m in enumerate(loadgen.measurements(vdaf, workers, rng))]
        senders.make_probe(job, items)
        senders.make(job, [(i, 1, i, 0.0) for i in range(3 * workers)])
        bodies, slowest_s, pids = senders.wait_probe()
        assert len(bodies) == workers and all(isinstance(b, bytes) and b for b in bodies)
        assert len(set(pids)) == workers and os.getpid() not in pids
        # one call's time a worker; sixteen in a row would be 16 s
        assert seconds <= slowest_s < 2.5 * seconds + 3.0  # the first call also imports
        t0 = time.monotonic()
        firsts = senders.wait_first()
        assert len(firsts) == workers == len(senders.slice_sizes)
        assert all(seconds <= s < seconds + 1.0 for s in firsts)
        made, made_s = senders.wait_made()
        assert made == 3 * workers and 3 * seconds <= made_s < 3 * seconds + 2.0
        assert time.monotonic() - t0 < 3 * seconds + 3.0  # the slices in parallel too
    finally:
        senders.stop()


# -- a run that cannot fit says so, before the first upload ------------------

PATCHED = """
import sys
sys.path[:0] = [{bench!r}, {root!r}]
import run
run.RUN_LIMIT_S = run.RUN_LIMIT_COMPILED_S = {limit}
if {final_only}:
    real = run.fits
    run.fits = lambda *a, projected=False, **kw: None if projected else real(*a, **kw)
run.leave(run.main({argv!r}))
"""


@pytest.mark.parametrize(
    "final_only, said",
    [
        # the word comes as soon as every sender has made one report
        (False, "no result: set-up is projected to take"),
        # ... and at the end of set-up, with every phase in the reason
        (True, "no result: set-up took"),
    ],
)
def test_a_run_that_cannot_fit_ends_with_the_reason_and_no_upload(final_only, said):
    argv = ["--workload", cheapest_cell(), "--seed", str(2**31 + 79), "--seconds", "5",
            "--trace", "0", "--rehearse", "--sweep", "10"]
    script = textwrap.dedent(PATCHED).format(
        bench=os.path.join(ROOT, "benchmark"), root=ROOT, limit=1.0, final_only=final_only,
        argv=argv,
    )
    proc = subprocess.run(
        [sys.executable, "-c", script], cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 1, proc.stderr[-3000:]
    last = proc.stderr.strip().splitlines()[-1]
    assert last.startswith(said), last
    assert "over the 1 s a run" in last
    if final_only:
        for phase in run.SETUP_PHASES:
            assert phase.replace("_", " ") in last, last
    else:
        assert "reports made" in last
    # no result line, and the traffic never started
    assert '"correct"' not in proc.stdout and '{"leg"' not in proc.stdout
