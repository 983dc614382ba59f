"""From a profiler trace to device busy time, time per op and idle gaps.

The reduction of ``tools/profile_planar.py`` (device track -> time per op),
moved from the chrome trace to the ``.xplane.pb`` that ``jax.profiler``
writes and ``jax.profiler.ProfileData`` reads with nothing but JAX.  Times in
a trace are nanoseconds from the start of the profile.

Everything below ``load`` works on plain lists, so the tests check it on a
recorded event list by hand-worked values.
"""

from __future__ import annotations

import collections
import glob
import os

#: lines of a device plane that are not single operations: umbrella spans of
#: whole modules and steps, and the host's own annotations
NOT_OPS = ("XLA Modules", "Steps", "XLA TraceMe", "Framework Ops", "Source code")


def load(trace_dir):
    """The newest ``.xplane.pb`` under ``trace_dir`` as
    ``[{"name": plane, "lines": [{"name": line, "events": [(name, start_ns,
    duration_ns), ...]}]}]``."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    return [
        {
            "name": plane.name,
            "lines": [
                {
                    "name": line.name,
                    "events": [
                        (op_name(e.name), float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    ],
                }
                for line in plane.lines
            ],
        }
        for plane in data.planes
    ]


def op_name(name):
    """The device track names an op by its whole HLO line (``%fusion.3 =
    u32[...] fusion(...)``); keep the name in front of the ``=``."""
    return name.split(" = ", 1)[0].lstrip("%")


def device_planes(planes, plane_prefix, line_prefix=""):
    """One list of op events per device: the ``XLA Ops`` line of each plane
    whose name starts with ``plane_prefix`` where there is one, else every
    line that is not an umbrella (and, with ``line_prefix``, only lines that
    start with it: the CPU rehearsal's executor threads)."""
    out = []
    for plane in planes:
        if not plane["name"].startswith(plane_prefix):
            continue
        lines = [l for l in plane["lines"] if l["name"] == "XLA Ops"] or [
            l
            for l in plane["lines"]
            if l["name"] not in NOT_OPS and l["name"].startswith(line_prefix)
        ]
        out.append([e for l in lines for e in l["events"] if e[2] > 0])
    return out


def find_mark(planes, name):
    """Start, in trace nanoseconds, of the first host event called
    ``name`` (the harness's own ``TraceAnnotation``), or ``None``."""
    starts = [
        e[1]
        for plane in planes
        for line in plane["lines"]
        for e in line["events"]
        if e[0] == name
    ]
    return min(starts) if starts else None


def merged(events, lo, hi):
    """Union of the events' intervals clipped to [lo, hi]: sorted, disjoint
    (start, end) pairs."""
    spans = sorted(
        (max(s, lo), min(s + d, hi)) for _n, s, d in events if s < hi and s + d > lo
    )
    out = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events, lo, hi):
    return sum(e - s for s, e in merged(events, lo, hi))


def op_seconds(events, lo, hi):
    """Seconds per op name inside [lo, hi], largest first."""
    totals = collections.Counter()
    for name, s, d in events:
        part = min(s + d, hi) - max(s, lo)
        if part > 0:
            totals[name] += part / 1e9
    return totals.most_common()


def gaps(events, lo, hi):
    """The idle intervals of [lo, hi]: what the union leaves over."""
    out, at = [], lo
    for s, e in merged(events, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlap_ns(spans_a, spans_b):
    """Total overlap of two sorted lists of disjoint intervals."""
    total, j = 0.0, 0
    for s, e in spans_a:
        while j < len(spans_b) and spans_b[j][1] <= s:
            j += 1
        k = j
        while k < len(spans_b) and spans_b[k][0] < e:
            total += max(0.0, min(e, spans_b[k][1]) - max(s, spans_b[k][0]))
            k += 1
    return total


def attribute_gaps(idle, host_spans):
    """Seconds of idle time by what the host was doing, largest first.

    ``host_spans`` is ``[(label, start_ns, end_ns), ...]`` in order of
    priority: time that several cover goes to the first.  What none covers
    is ``nothing queued``."""
    totals = collections.Counter()
    left = list(idle)
    for label, s, e in host_spans:
        rest = []
        for gs, ge in left:
            os_, oe = max(gs, s), min(ge, e)
            if oe <= os_:
                rest.append((gs, ge))
                continue
            totals[label] += (oe - os_) / 1e9
            if gs < os_:
                rest.append((gs, os_))
            if oe < ge:
                rest.append((oe, ge))
        left = rest
    uncovered = sum(e - s for s, e in left) / 1e9
    if uncovered > 0:
        totals["nothing queued"] += uncovered
    return totals.most_common()
