"""The least bytes the protocol moves per report through prepare.

Worked out from the VDAF's lengths (draft-irtf-cfrg-vdaf-08, which DAP-09
uses) and from nothing of the program: the same whatever implements the
kernels.  Padded rows are not counted; what a kernel re-reads, spills or
expands from a seed on the device is not counted either, so this is a floor
on traffic and the roofline share built on it is a floor on efficiency.

Per report, both aggregators' ``prep_init`` and the combine:

- in:  nonce and public share (read by both sides), the leader's input share
  (measurement share, proof share, joint-rand blind), the helper's input share
  (two seeds when there is joint randomness, else one);
- out: each side's output share and prepare share (verifier share and
  joint-rand part);
- combine: both prepare shares in, the prepare message (joint-rand seed) and
  one decision byte out.
"""

from __future__ import annotations

from vdafs import family

NONCE = 16
SEED = 16  # XofTurboShake128.SEED_SIZE


def flp_lengths(vdaf):
    """(field bytes, MEAS_LEN, OUTPUT_LEN, JOINT_RAND_LEN, PROOF_LEN,
    VERIFIER_LEN) of the instance described as the task's ``vdaf``."""
    return family(vdaf).flp_lengths(vdaf)


def prepare_bytes_per_report(vdaf):
    field, meas, out, jr, proof, verifier = flp_lengths(vdaf)
    has_jr = jr > 0
    public_share = 2 * SEED if has_jr else 0
    leader_share = (meas + proof) * field + (SEED if has_jr else 0)
    helper_share = SEED + (SEED if has_jr else 0)
    prep_share = verifier * field + (SEED if has_jr else 0)
    bytes_in = 2 * (NONCE + public_share) + leader_share + helper_share
    bytes_out = 2 * (out * field + prep_share)
    combine = 2 * prep_share + (SEED if has_jr else 0) + 1
    return bytes_in + bytes_out + combine
