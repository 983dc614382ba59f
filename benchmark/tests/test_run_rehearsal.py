"""The rest of a run, with the harness's look for a chip skipped
(``--rehearse``: the configuration's tiny size, on the CPU): once sound, and
once for each fault a cell can have, with the timed path broken underneath.
``correct`` has to come out true for the first and false for the others.

Each case is a whole run (a minute or so): ``python -m pytest benchmark/tests``.
"""

import json
import os
import subprocess
import sys

import pytest

import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def rehearse(workload, *extra, rate=10):
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, *manifest["command"][1:], "--workload", workload, "--seed",
         str(2**31 + 77), "--seconds", "5", "--trace", "0", "--rehearse", "--sweep", str(rate),
         *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    checks = {k: v["value"] for k, v in result["checks"].items()}
    for name, value in checks.items():
        assert f"check {name}: value {value} limit 0" in proc.stderr
    result["log"] = [json.loads(l) for l in proc.stdout.splitlines()[:-1] if l.startswith("{")]
    return result, checks


def assert_set_up_as_it_should_be(result):
    """The senders made the probe batch, one report a worker and none in the
    main process; the record has set-up's five phases, which are part of the
    set-up time."""
    probe = next(l for l in result["log"] if l.get("warmup") == "probe")
    assert probe["rows"] == len(probe["made_by"]) >= 2
    assert len(set(probe["made_by"])) == probe["rows"]
    assert probe["main_pid"] not in probe["made_by"]
    phases = result["setup_phases"]
    assert set(phases) == set(run.SETUP_PHASES) and len(phases) == 5
    assert all(seconds >= 0 for seconds in phases.values()), phases
    on_the_main_path = sum(s for name, s in phases.items() if name != "reports_made")
    assert 0 < on_the_main_path <= result["rehearsal_values"]["setup_s"]
    assert phases["reports_made"] <= result["rehearsal_values"]["setup_s"]


def cell_sizes():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    sizes = {}
    for w in manifest["workloads"]:
        config = next(c for c in manifest["configs"] if c["name"] == w["config"])
        vdaf = json.load(open(os.path.join(ROOT, config["file"])))["vdaf"]
        sizes[w["name"]] = vdaf.get("length", 1)
    return sizes


def cheapest_cell():
    sizes = cell_sizes()
    return min(sizes, key=sizes.get)


def widest_cell():
    sizes = cell_sizes()
    return max(sizes, key=sizes.get)


@pytest.mark.parametrize(
    "cell, rate",
    [
        (cheapest_cell, 10),
        # joint randomness, a share of several elements and the bucket twin:
        # the probe path as a wide-vector deployment takes it.  The CPU
        # compiles the two prepare programs for 100 s and steps a job of four
        # in a second, so two uploads a second are what it serves
        (widest_cell, 2),
    ],
)
def test_a_sound_run_is_correct(cell, rate):
    result, checks = rehearse(cell(), rate=rate)
    assert result["correct"] is True, checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(checks.values()) == {0}
    assert_set_up_as_it_should_be(result)


@pytest.mark.parametrize(
    "fault, failing",
    [
        # an answer altered where it is produced: one aggregate share, by one
        ("alter", "aggregate_mismatched_positions"),
        # half of every batch left out of the sum
        ("half_batch", "aggregate_mismatched_positions"),
        # the CPU oracle serves the rows in the device's place
        ("oracle", "device_rows_short"),
    ],
)
def test_a_broken_timed_path_is_not_correct(fault, failing):
    result, checks = rehearse(cheapest_cell(), "--fault", fault)
    assert result["correct"] is False
    assert checks[failing] > 0, checks
