"""Prio3Count (VDAF-08 section 7.4.1): a client reports 0 or 1; the aggregate
is how many reported 1."""

from __future__ import annotations

from . import gadget_lengths


def measurements(vdaf, n, rng):
    return [rng.randrange(2) for _ in range(n)]


def plain_aggregate(vdaf, measurements):
    return sum(measurements)


def flp_lengths(vdaf):
    # Field64; Mul gadget, arity 2, degree 2, one call
    proof, verifier = gadget_lengths(2, 2, 1)
    return 8, 1, 1, 0, proof, verifier
