"""One file per VDAF family, found by its name.

``family(vdaf)`` answers the module ``<vdaf["type"], lower-cased>.py`` of
this package: nothing is registered by hand, so a new family is a new file.
Each file gives the three things the harness asks of a family:

- ``measurements(vdaf, n, rng)``: what ``n`` clients of the family send,
  drawn from ``rng``;
- ``plain_aggregate(vdaf, measurements)``: the plain reference;
- ``flp_lengths(vdaf)``: (field bytes, MEAS_LEN, OUTPUT_LEN, JOINT_RAND_LEN,
  PROOF_LEN, VERIFIER_LEN) from the gadget table of draft-irtf-cfrg-vdaf-08.

No file here imports anything of the program, nor JAX or numpy, and none
takes anything that the program made: the sender processes stay JAX-free and
the reference stays independent of what it judges.
"""

from __future__ import annotations

import importlib


def family(vdaf):
    """The module of ``vdaf``'s family; ``ValueError`` names the file that a
    type nobody wrote a family for is missing."""
    kind = vdaf["type"]
    name = kind.lower()
    if name.isidentifier() and not name.startswith("_"):
        try:
            return importlib.import_module(f"{__name__}.{name}")
        except ModuleNotFoundError as e:
            if e.name != f"{__name__}.{name}":
                raise
    raise ValueError(
        f"no VDAF family for {kind}: {name}.py (measurements, plain_aggregate, "
        f"flp_lengths) is missing from {list(__path__)}"
    )


def gadget_lengths(arity, degree, calls):
    """(PROOF_LEN, VERIFIER_LEN) of a circuit with one gadget (VDAF-08
    section 7.3.2): the gadget's wire seeds, its polynomial's coefficients
    over the next power of two above the calls, and one element more."""
    p = _next_pow2(1 + calls)
    return arity + degree * (p - 1) + 1, 1 + arity + 1


def _next_pow2(n):
    return 1 << (n - 1).bit_length()
