"""Prio3Histogram (VDAF-08 section 7.4.4): a client reports one bucket; the
aggregate is how many clients reported each."""

from __future__ import annotations

from . import gadget_lengths


def measurements(vdaf, n, rng):
    return [rng.randrange(vdaf["length"]) for _ in range(n)]


def plain_aggregate(vdaf, measurements):
    out = [0] * vdaf["length"]
    for m in measurements:
        out[m] += 1
    return out


def flp_lengths(vdaf):
    # Field128; ParallelSum(Mul, chunk): arity 2*chunk, degree 2
    length, chunk = vdaf["length"], vdaf["chunk_length"]
    proof, verifier = gadget_lengths(2 * chunk, 2, -(-length // chunk))
    return 16, length, length, 2, proof, verifier
