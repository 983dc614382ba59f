#!/usr/bin/env python
"""Bench-trajectory regression gate (ISSUE 12 tentpole).

The repo accumulates one ``BENCH_rNN.json`` per recorded bench run, but
until now nothing GATED on them — r05's environmental failure (no TPU in
the runner) sat unnoticed because the trajectory was a graveyard, not a
signal.  This tool turns it into one:

* the NEWEST run's per-config rows are compared against the **best prior
  value for the same config** across every older run, with a tolerance
  band (default 10%): ``new < best_prior × (1 - tolerance)`` is a
  REGRESSION (exit 1);
* structured skip rows — ``{"skipped": "platform unavailable"}``, the
  shape bench.py emits since PR 7 when the device tier cannot run — are
  NEUTRAL: they neither regress nor advance the trajectory;
* runs that failed outright (``rc != 0`` / no parsed payload — the r05
  failure mode predating structured skips) are NEUTRAL with a loud
  warning, so an environmental failure can never read as either "fine"
  or "20% slower";
* configs with no prior datapoint are BASELINES (recorded, not judged).

Exit codes: 0 = pass (or fully neutral), 1 = regression, 2 = usage/IO.
(The repo no longer checks record files in — PR 21 removed the ones taken
on a retired installation, and the ``./ci.sh benchdiff`` stage that read
them; ``PERF_LEDGER.jsonl`` is the record now.  Point ``--dir`` at any
directory of ``BENCH_rNN.json`` files.)
"""

from __future__ import annotations

import argparse
import glob as globmod
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple


def load_runs(paths: List[str]) -> List[dict]:
    """Parse BENCH files into ``{n, path, rc, rows}`` sorted by run
    number; ``rows`` maps config key -> row dict (value/unit or
    skipped/error), None when the run has no usable payload."""
    runs = []
    for path in paths:
        with open(path) as f:
            doc = json.load(f)
        m = re.search(r"r(\d+)", os.path.basename(path))
        n = doc.get("n", int(m.group(1)) if m else 0)
        runs.append(
            {
                "n": n,
                "path": path,
                "rc": doc.get("rc"),
                "rows": extract_rows(doc),
            }
        )
    runs.sort(key=lambda r: r["n"])
    return runs


def extract_rows(doc: dict) -> Optional[Dict[str, dict]]:
    """Per-config rows of one run document.  ``parsed.configs`` when
    present (the multi-config bench shape since r04), else the headline
    metric as a single pseudo-config; None when nothing parsed."""
    parsed = doc.get("parsed")
    if not isinstance(parsed, dict):
        return None
    configs = parsed.get("configs")
    if isinstance(configs, dict) and configs:
        return {str(k): v for k, v in configs.items() if isinstance(v, dict)}
    if parsed.get("metric"):
        key = str(parsed["metric"])
        return {key: parsed}
    return None


def row_value(row: dict) -> Optional[Tuple[float, str]]:
    """``(value, unit)`` of a comparable row; None for neutral rows
    (structured skips, recorded errors, value-less shapes)."""
    if not isinstance(row, dict) or "skipped" in row or "error" in row:
        return None
    v = row.get("value")
    if not isinstance(v, (int, float)):
        return None
    return float(v), str(row.get("unit", ""))


def compare(runs: List[dict], tolerance: float) -> dict:
    """The verdict over a chronological run list.  Pure — tests and the
    CLI share it."""
    verdict = {
        "tolerance": tolerance,
        "newest": None,
        "results": [],
        "neutral": [],
        "regressions": [],
        "ok": True,
    }
    if not runs:
        verdict["neutral"].append("no bench runs found")
        return verdict
    newest = runs[-1]
    prior = runs[:-1]
    verdict["newest"] = {"n": newest["n"], "path": newest["path"]}
    if newest["rows"] is None:
        verdict["neutral"].append(
            f"newest run r{newest['n']:02d} has no parsed rows "
            f"(rc={newest['rc']}) — environmental failure, NEUTRAL; "
            "the trajectory still ends at the last good run"
        )
        return verdict

    # best prior value per (config, unit) across every older run
    best: Dict[Tuple[str, str], Tuple[float, int]] = {}
    for run in prior:
        for key, row in (run["rows"] or {}).items():
            vu = row_value(row)
            if vu is None:
                continue
            value, unit = vu
            k = (key, unit)
            if k not in best or value > best[k][0]:
                best[k] = (value, run["n"])

    for key, row in sorted(newest["rows"].items()):
        vu = row_value(row)
        if vu is None:
            reason = row.get("skipped") or row.get("error") or "no value"
            verdict["neutral"].append(f"{key}: {reason} (neutral)")
            continue
        value, unit = vu
        prior_best = best.get((key, unit))
        if prior_best is None:
            verdict["results"].append(
                {"config": key, "value": value, "unit": unit, "status": "baseline"}
            )
            continue
        best_value, best_n = prior_best
        floor = best_value * (1.0 - tolerance)
        entry = {
            "config": key,
            "value": value,
            "unit": unit,
            "best_prior": best_value,
            "best_prior_run": best_n,
            "floor": round(floor, 3),
            "ratio": round(value / best_value, 4) if best_value else None,
        }
        if value < floor:
            entry["status"] = "regression"
            verdict["regressions"].append(entry)
            verdict["ok"] = False
        else:
            entry["status"] = "ok"
        verdict["results"].append(entry)
    return verdict


def render(verdict: dict) -> str:
    lines = []
    newest = verdict.get("newest")
    if newest:
        lines.append(
            f"bench_compare: newest run r{newest['n']:02d} "
            f"({os.path.basename(newest['path'])}), "
            f"tolerance {verdict['tolerance']:.0%}"
        )
    for n in verdict["neutral"]:
        lines.append(f"  NEUTRAL  {n}")
    for e in verdict["results"]:
        if e["status"] == "baseline":
            lines.append(
                f"  BASELINE {e['config']}: {e['value']} {e['unit']} "
                "(no prior datapoint)"
            )
        else:
            tag = "REGRESS " if e["status"] == "regression" else "OK      "
            lines.append(
                f"  {tag} {e['config']}: {e['value']} {e['unit']} vs best "
                f"prior {e['best_prior']} (r{e['best_prior_run']:02d}), "
                f"ratio {e['ratio']}"
            )
    lines.append(
        "bench_compare: "
        + ("PASS" if verdict["ok"] else "REGRESSION — trajectory fell below the band")
    )
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--dir", default=".", help="directory holding the BENCH_r*.json rows"
    )
    p.add_argument("--glob", default="BENCH_r*.json")
    p.add_argument(
        "--tolerance",
        type=float,
        default=0.10,
        help="allowed fractional drop vs the best prior value (default 0.10)",
    )
    p.add_argument("--json", action="store_true", help="emit the verdict as JSON")
    args = p.parse_args(argv)
    paths = sorted(globmod.glob(os.path.join(args.dir, args.glob)))
    if not paths:
        print(f"no files match {args.glob} under {args.dir}", file=sys.stderr)
        return 2
    try:
        runs = load_runs(paths)
    except (OSError, json.JSONDecodeError) as e:
        print(f"cannot load bench rows: {e}", file=sys.stderr)
        return 2
    verdict = compare(runs, args.tolerance)
    print(json.dumps(verdict, indent=2) if args.json else render(verdict))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
