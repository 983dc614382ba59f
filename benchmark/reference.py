"""The plain reference: count the measurements.

It imports nothing of the program and takes nothing that the program made.
A Prio3Histogram aggregate is how many clients reported each bucket; a
Prio3Count aggregate is how many reported 1.
"""

from __future__ import annotations


def plain_aggregate(vdaf, measurements):
    kind = vdaf["type"]
    if kind == "Prio3Histogram":
        out = [0] * vdaf["length"]
        for m in measurements:
            out[m] += 1
        return out
    if kind == "Prio3Count":
        return sum(measurements)
    raise ValueError(f"no plain reference for {kind}")


def mismatched_positions(got, want):
    """How many positions of the collected aggregate differ from the
    reference (a scalar is one position; a wrong length counts every
    position of the longer)."""
    if isinstance(want, list):
        if not isinstance(got, (list, tuple)):
            return len(want)
        n = max(len(got), len(want))
        return sum(
            1
            for i in range(n)
            if i >= len(got) or i >= len(want) or got[i] != want[i]
        )
    return int(got != want)
