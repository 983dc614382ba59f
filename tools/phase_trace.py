#!/usr/bin/env python3
"""List what the phase clock and the name scopes left in a profiler trace.

    python tools/phase_trace.py <trace dir or .xplane.pb> [--lo NS --hi NS] [--json]

A ``jax.profiler`` capture of a process that serves (``profiler_port``, or a
benchmark run with ``--trace 1``) holds, beside the device ops, one host
event per ``kind=python`` / ``kind=device`` phase of
``janus_tpu.core.trace.PHASES``, named ``janus.<group>.<phase>`` on the line
of the thread that did the work, and on each device op the name scope that
``ops/prepare.py`` gave it.  This prints, from the ``.xplane.pb`` alone:

* per host thread, the annotations by name: count, total and mean ms;
* per device, time by name scope (``xof.query_rand`` ...; ``-`` for an op
  outside every scope), the scope of each op read from the HLO protos that
  the capture carries;
* for every ``janus.backend.readback`` annotation, the device time that
  ran inside it — the host waits there for the program it dispatched.

Times in a trace are nanoseconds from the start of the profile.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import glob
import json
import os
import re
import sys

#: the scopes of ops/prepare.py, wherever they stand in an op's path
SCOPE = re.compile(r"\b(?:xof|flp|verifier|combine|aggregate)\.[a-z_]+")
PREFIX = "janus."


def find(path):
    """The newest ``.xplane.pb`` under a directory; a file as it is."""
    if not os.path.isdir(path):
        return path
    found = sorted(
        glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not found:
        raise SystemExit(f"no .xplane.pb under {path}")
    return found[-1]


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find(path))


def _stats(event):
    return {str(k): v for k, v in event.stats}


# -- the name scopes: from the HLO protos the capture carries ----------------
# A device op's event names its HLO instruction (``%while.26 = ...``) and
# nothing of where it came from.  The scope path (``jit(..)/xof.query_rand/
# while``) is the instruction's ``metadata.op_name`` in the module's
# ``HloProto``, which the profiler stores in the ``/host:metadata`` plane.
# ``ProfileData`` does not show that plane's contents, so the few fields on
# the way are read from the protobuf wire format here (field numbers of
# tsl/profiler/protobuf/xplane.proto and xla/service/hlo.proto).


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: a varint as an int, a
    length-delimited field as bytes; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i : i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, value


def _first(buf, number, default=b""):
    return next((v for n, v in _fields(buf) if n == number), default)


def hlo_op_names(path):
    """``{module name as the device plane has it: {instruction name:
    metadata.op_name}}`` from the capture's ``/host:metadata`` plane."""
    out = {}
    for n, plane in _fields(memoryview(open(path, "rb").read())):
        if n != 1 or bytes(_first(plane, 2)) != b"/host:metadata":  # XSpace.planes, XPlane.name
            continue
        for n, entry in _fields(plane):
            if n != 4:  # XPlane.event_metadata (a map entry: key 1, value 2)
                continue
            event = _first(entry, 2)
            names = out.setdefault(bytes(_first(event, 2)).decode(), {})  # XEventMetadata.name
            for n, stat in _fields(event):
                if n != 5:  # XEventMetadata.stats; the proto is its bytes_value
                    continue
                module = _first(_first(stat, 6), 1)  # HloProto.hlo_module
                for n, computation in _fields(module):
                    if n != 3:  # HloModuleProto.computations
                        continue
                    for n, instruction in _fields(computation):
                        if n == 2:  # HloComputationProto.instructions: name 1, metadata 7
                            op_name = _first(_first(instruction, 7), 2)  # OpMetadata.op_name
                            names[bytes(_first(instruction, 1)).decode()] = bytes(op_name).decode()
    return out


def annotations(data, lo=None, hi=None):
    """``{thread: {name: {"count", "total_ms", "spans": [(start_ns, end_ns,
    stats)]}}}`` of the ``janus.*`` host events that start in [lo, hi)."""
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                if (lo is not None and e.start_ns < lo) or (hi is not None and e.start_ns >= hi):
                    continue
                row = out.setdefault(line.name, {}).setdefault(
                    e.name, {"count": 0, "total_ms": 0.0, "spans": []}
                )
                row["count"] += 1
                row["total_ms"] += e.duration_ns / 1e6
                row["spans"].append((e.start_ns, e.start_ns + e.duration_ns, _stats(e)))
    return out


def device_ops(data, op_names, plane_prefix="/device:"):
    """``{plane: [(start_ns, end_ns, scope or "-", op name)]}``: the single
    ops of each device plane (its ``XLA Ops`` line; on the CPU the host
    plane's XLA client threads), each with the name scope of its
    instruction in the module that was running (``XLA Modules``) — or of
    its own name: a Pallas kernel's custom call is named for its scope."""
    out = {}
    for plane in data.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        modules = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, e.name)
            for line in plane.lines if line.name == "XLA Modules" for e in line.events
        )
        starts = [m[0] for m in modules]
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops" and not line.name.startswith("tf_XLA"):
                continue
            for e in line.events:
                if e.duration_ns <= 0:
                    continue
                name = e.name.split(" = ", 1)[0].lstrip("%")
                at = bisect.bisect_right(starts, e.start_ns) - 1
                module = modules[at][2] if at >= 0 and e.start_ns < modules[at][1] else (
                    "jit_" + str(_stats(e).get("hlo_module", "")).removeprefix("jit_")
                )
                known = op_names.get(module)
                if known is None:  # the CPU's events name the module without its id
                    known = next((v for k, v in op_names.items() if k.startswith(module + "(")), {})
                found = SCOPE.search(known.get(name, "")) or SCOPE.search(name)
                ops.append(
                    (e.start_ns, e.start_ns + e.duration_ns, found.group(0) if found else "-", name)
                )
        if ops:
            out[plane.name] = sorted(ops)
    return out


def outermost(events):
    """The events that lie in no earlier one: a ``while`` holds the ops of
    its body, and the time is the loop's."""
    end = float("-inf")
    for event in events:
        if event[0] >= end:
            end = event[1]
            yield event


def _union(spans):
    merged = []
    for s, e in sorted(spans):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _overlap(lo, hi, merged):
    return sum(max(0.0, min(hi, e) - max(lo, s)) for s, e in merged if s < hi and e > lo)


def report(path, lo=None, hi=None, plane_prefix="/device:"):
    path = find(path)
    data = load(path)
    ann = annotations(data, lo, hi)
    ops = device_ops(data, hlo_op_names(path), plane_prefix)
    clip = lambda s, e: (max(s, lo if lo is not None else s), min(e, hi if hi is not None else e))
    scopes = {}
    for plane, events in ops.items():
        by = collections.Counter()
        for s, e, scope, _name in outermost(events):
            cs, ce = clip(s, e)
            if ce > cs:
                by[scope] += (ce - cs) / 1e6
        scopes[plane] = dict(by.most_common())
    busy = _union([(s, e) for events in ops.values() for s, e, *_ in events])
    readbacks = [
        (s, e, st)
        for rows in ann.values()
        for name, row in rows.items()
        if name == PREFIX + "backend.readback"
        for s, e, st in row["spans"]
    ]
    inside = [(e - s, _overlap(s, e, busy), st) for s, e, st in readbacks]
    return {
        "threads": {
            thread: {
                name: {
                    "count": row["count"],
                    "total_ms": round(row["total_ms"], 3),
                    "mean_ms": round(row["total_ms"] / row["count"], 3),
                }
                for name, row in sorted(rows.items())
            }
            for thread, rows in sorted(ann.items())
        },
        "device_scope_ms": scopes,
        "readback": {
            "count": len(inside),
            "with_device_ops_inside": sum(1 for _d, busy_ns, _s in inside if busy_ns > 0),
            "annotation_ms": round(sum(d for d, _b, _s in inside) / 1e6, 3),
            "device_ms_inside": round(sum(b for _d, b, _s in inside) / 1e6, 3),
        },
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("trace", help="a trace directory or an .xplane.pb")
    parser.add_argument("--lo", type=float, help="only what starts at or after this ns")
    parser.add_argument("--hi", type=float, help="only what starts before this ns")
    parser.add_argument("--plane", default="/device:", help="prefix of the device planes")
    parser.add_argument("--json", action="store_true")
    args = parser.parse_args(argv)
    out = report(args.trace, args.lo, args.hi, args.plane)
    if args.json:
        print(json.dumps(out))
        return 0
    for thread, rows in out["threads"].items():
        print(f"thread {thread}")
        for name, row in rows.items():
            print(f"  {name:36s} n={row['count']:<5d} total {row['total_ms']:>10.3f} ms"
                  f"  mean {row['mean_ms']:>9.3f} ms")
    for plane, by in out["device_scope_ms"].items():
        print(f"device {plane}")
        for scope, ms in by.items():
            print(f"  {scope:36s} {ms:>10.3f} ms")
    r = out["readback"]
    print(f"readback annotations: {r['count']}, with device ops inside {r['with_device_ops_inside']};"
          f" {r['annotation_ms']} ms of annotation hold {r['device_ms_inside']} ms of device time")
    return 0


if __name__ == "__main__":
    sys.exit(main())
