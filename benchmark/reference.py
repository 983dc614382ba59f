"""The plain reference and the comparison.

It imports nothing of the program and takes nothing that the program made.
What an aggregate of the measurements is, the VDAF's family says
(``vdafs/<type>.py``).
"""

from __future__ import annotations

from vdafs import family


def plain_aggregate(vdaf, measurements):
    return family(vdaf).plain_aggregate(vdaf, measurements)


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
