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

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def rehearse(workload, *extra):
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, *manifest["command"][1:], "--workload", workload, "--seed",
         str(2**31 + 77), "--seconds", "5", "--trace", "0", "--rehearse", "--sweep", "10", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    checks = {k: v["value"] for k, v in result["checks"].items()}
    for name, value in checks.items():
        assert f"check {name}: value {value} limit 0" in proc.stderr
    return result, checks


def cheapest_cell():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    sizes = {}
    for w in manifest["workloads"]:
        config = next(c for c in manifest["configs"] if c["name"] == w["config"])
        vdaf = json.load(open(os.path.join(ROOT, config["file"])))["vdaf"]
        sizes[w["name"]] = vdaf.get("length", 1)
    return min(sizes, key=sizes.get)


def test_a_sound_run_is_correct():
    result, checks = rehearse(cheapest_cell())
    assert result["correct"] is True, checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(checks.values()) == {0}


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
