"""The metrics that read the program's phase clock (``janus_phase_*``,
``janus_tpu.core.trace.PHASES``): every phase, scope and kind that a metric's
file names is one the program has, so that a rename there fails here and not
a metric in silence; and a rehearsal reports all eight.
"""

import fnmatch
import glob
import json
import os
import subprocess
import sys

from test_run_rehearsal import ROOT, cheapest_cell

PHASE_METRICS = {
    "window_wait_ms", "launch_queue_ms", "marshal_ms_per_krow", "unmarshal_ms_per_krow",
    "readback_ms_per_krow", "python_offcpu_pct", "leader_step_python_ms",
    "helper_hpke_open_ms",
}
#: what a scope label of the bucket-scoped groups looks like (executor/service.py
#: ``bucket_label``; the backend's own outside a flush)
BUCKET_SCOPES = ["Histogram/a0/prep_init#1a2b3c", "Count/a1/combine#1a2b3c",
                 "Histogram/aggregate", "Poplar1/a0/poplar_init/L3#1a2b3c"]


def phase_label_sets():
    """(file, labels) of every label set a metric file applies to a
    ``janus_phase_*`` sample."""
    out = []
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark", "metrics", "*.json"))):
        args = json.load(open(path)).get("args", {})
        for sample, labels in (
            (args.get("family"), args.get("labels")),
            (args.get("num"), args.get("num_labels")),
            (args.get("den"), args.get("den_labels")),
        ):
            if isinstance(sample, str) and sample.startswith("janus_phase_"):
                out.append((os.path.basename(path)[:-5], labels or {}))
    return out


def test_every_phase_scope_and_kind_a_metric_names_is_in_the_programs_table():
    from janus_tpu.core.trace import PHASE_KINDS, PHASES

    sets = phase_label_sets()
    assert {name for name, _ in sets} == PHASE_METRICS
    rows = []  # (scope, phase, kind) the program can emit
    for group, table in PHASES.items():
        scopes = [group] if group in ("leader_step", "helper_init") else BUCKET_SCOPES
        rows += [(s, p, k) for s in scopes for p, k in table.items()]
    for name, labels in sets:
        assert set(labels) <= {"scope", "phase", "kind"}, name
        assert labels.get("kind", PHASE_KINDS[0]) in PHASE_KINDS, name
        matching = [
            row for row in rows
            if all(
                fnmatch.fnmatchcase(got, labels[key]) if "*" in labels.get(key, "") else
                labels.get(key, got) == got
                for key, got in zip(("scope", "phase", "kind"), row)
            )
        ]
        assert matching, f"{name}: no phase of core.trace.PHASES carries {labels}"


def test_a_rehearsal_reports_all_eight_phase_metrics():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    # looked up by name: later PRs append their own metrics after these
    assert PHASE_METRICS <= {m["name"] for m in manifest["per_layer"]}
    # the rehearsal as test_run_rehearsal.py runs it, read for its "leg" line;
    # 8 s, so that one whole pass of the creator (every 5 s) and its jobs'
    # steps lie inside the window wherever the pass falls
    proc = subprocess.run(
        [sys.executable, *manifest["command"][1:], "--workload", cheapest_cell(), "--seed",
         str(2**31 + 78), "--seconds", "8", "--trace", "0", "--rehearse", "--sweep", "10"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1])["correct"] is True
    every = json.loads(next(l for l in lines if l.startswith('{"leg"')))["every_metric"]
    assert PHASE_METRICS <= set(every), sorted(PHASE_METRICS - set(every))
    for name in PHASE_METRICS:
        assert every[name] > 0, (name, every[name])
    assert every["python_offcpu_pct"] <= 100.0
