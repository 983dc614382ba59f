"""JAX/TPU kernels: u32-limb field arithmetic, vmapped Keccak, batched prepare.

These are the TPU-native re-expression of the reference's CPU-bound VDAF hot
loop (reference: aggregator/src/aggregator/aggregation_job_driver.rs:449,
aggregator/src/aggregator.rs:2101 — per-report serial loops on a rayon pool).
Every kernel must agree bit-for-bit with the oracle in janus_tpu.{fields,xof,
flp,vdaf}; tests enforce byte equality.

TPU notes: there is no native 64-bit integer path on TPU, so field elements are
little-endian u32 limb vectors (2 limbs for Field64, 4 for Field128) and
multiplication uses 16-bit half-limb products that fit exactly in u32
multiplies.  Field multiplication is Montgomery (CIOS); values are kept in
Montgomery form between boundary conversions.  All shapes are static per VDAF
configuration; batching over reports is jax.vmap-style broadcasting over the
leading axis.
"""

import os


def pallas_mode() -> str:
    """'on' | 'off' | 'interpret' — resolved at trace time.

    auto: real kernels when the default backend is TPU, else off (the CPU
    test mesh and the oracle paths use the XLA graph version).  Here, not
    beside the kernels, so that asking it (the program store's key does)
    imports no Pallas.
    """
    import jax

    mode = os.environ.get("JANUS_TPU_PALLAS", "auto")
    if mode in ("0", "off"):
        return "off"
    if mode == "interpret":
        return "interpret"
    if mode in ("1", "on"):
        return "on"
    return "on" if jax.default_backend() == "tpu" else "off"
