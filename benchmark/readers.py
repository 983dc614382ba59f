"""Reader kinds: how a metric's file turns what a run recorded into a number.

A metric is data (``benchmark/metrics/<name>.json``: a reader kind and its
arguments); the kinds are here.  Each takes the run's record ``rec`` and the
arguments, and returns a number or ``None`` where it finds nothing to read —
the harness then leaves the metric out of the line.  None returns 0 for a
share of a peak.

``rec`` holds: ``seconds`` (the window), ``setup_s``, ``setup_phases`` (seconds
of set-up's phases on the harness's monotonic clock, ``run.py``
``SETUP_PHASES``), ``uploads`` (one dict
per upload due in the window: ``late_s``, ``ack_s`` or ``None``, ``lag_s`` or
``None``), ``finished_in_window`` (reports in leader jobs first seen FINISHED
inside the window), ``reports_aggregated`` (all of the run), ``prom`` (snapshots at the window's
``open`` and ``close``), ``prom_run`` (the same pair around the whole of the
traffic and the drain) and, in a traced run, ``trace`` (named quantities of
the traced slice, see ``run.py`` ``reduce_trace``) with its own ``prom`` pair.
"""

from __future__ import annotations

import prom
from loadgen import percentile


def setup(rec, args):
    return rec["setup_s"]


def setup_phase(rec, args):
    """Seconds of one phase of set-up; nothing where the run had no such
    phase."""
    return rec.get("setup_phases", {}).get(args["phase"])


def window_rate(rec, args):
    """Work finished inside the window over the window's seconds: all the
    work and all the time of the window, nothing of the drain."""
    if not rec["finished_in_window"]:
        return None
    return rec["finished_in_window"] / rec["seconds"]


def upload_percentile(rec, args):
    """A percentile over EVERY upload due in the window of one of its
    times; an upload without that time (never answered, never aggregated)
    counts with ``missing_s``, so it can only raise the tail."""
    values = [
        u[args["field"]] if u[args["field"]] is not None else args["missing_s"]
        for u in rec["uploads"]
    ]
    value = percentile(values, args["q"])
    return None if value is None else value * args.get("scale", 1.0)


def sender(rec, args):
    """How late the generator itself ran: send time minus due time."""
    value = percentile([u["late_s"] for u in rec["uploads"]], args["q"])
    return None if value is None else value * args.get("scale", 1.0)


def _snapshots(rec, args):
    """The pair of snapshots a metric is taken ``over``: the window (the
    default), the traced slice, or the whole ``run`` (traffic and drain)."""
    over = args.get("over", "window")
    if over == "run":
        return rec.get("prom_run")
    src = rec.get("trace") if over == "trace" else rec
    return (src or {}).get("prom")


def prom_mean(rec, args):
    snaps = _snapshots(rec, args)
    if not snaps:
        return None
    value = prom.mean(snaps["open"], snaps["close"], args["family"], args.get("labels"))
    return None if value is None else value * args.get("scale", 1.0)


def prom_ratio(rec, args):
    """Delta of one sample over delta of another (or over a named count
    of the run, ``den_count``)."""
    snaps = _snapshots(rec, args)
    if not snaps:
        return None
    num = prom.delta(snaps["open"], snaps["close"], args["num"], args.get("num_labels"))
    if "den_count" in args:
        den = rec.get(args["den_count"], 0)
    else:
        den = prom.delta(snaps["open"], snaps["close"], args["den"], args.get("den_labels"))
    if den <= 0:
        return None
    return num / den * args.get("scale", 1.0)


def trace_ratio(rec, args):
    """``scale * (sum of signed named quantities) / (product of named
    quantities)`` of the traced slice; a name with a leading ``-`` is
    subtracted.  Nothing where there is no trace, a quantity is missing or
    the denominator is not above 0."""
    trace = rec.get("trace")
    if not trace:
        return None
    q = trace["quantities"]
    try:
        num = sum(-q[n[1:]] if n.startswith("-") else q[n] for n in args["num"])
        den = 1.0
        for n in args["den"]:
            den *= q[n]
    except KeyError:
        return None
    if den <= 0 or num <= 0:
        return None
    return args.get("scale", 1.0) * num / den


KINDS = {
    "setup": setup,
    "setup_phase": setup_phase,
    "window_rate": window_rate,
    "upload_percentile": upload_percentile,
    "sender": sender,
    "prom_mean": prom_mean,
    "prom_ratio": prom_ratio,
    # device busy time per unit of work is the same arithmetic as a ratio
    "trace_busy": trace_ratio,
    "trace_ratio": trace_ratio,
}
