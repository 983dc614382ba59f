"""Stage-by-stage timing of the batched prepare pipeline on the current chip.

Times each component of the helper prepare (BASELINE.md configs[2] shape) in
isolation so optimization effort lands where the milliseconds are:

  xof_meas      — TurboSHAKE expansion of the measurement share (98 squeezes)
  xof_proof     — proof-share expansion (62 squeezes)
  reject_only   — the rejection-sampling compaction (argsort) alone
  jr_part       — joint-rand part (16 KB binder absorb)
  flp_query     — FLP query with precomputed limb inputs
  combine       — prep_shares_to_prep
  full          — the whole helper step (bench.py pipeline)

Usage: python tools/profile_stages.py [--batch 1024] [--iters 5]
"""

from __future__ import annotations

import argparse
import time


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch", type=int, default=1024)
    parser.add_argument("--iters", type=int, default=5)
    parser.add_argument("--stages", default="")
    args = parser.parse_args()

    import jax
    import numpy as np

    from janus_tpu.utils.jax_setup import enable_compile_cache

    enable_compile_cache()

    from janus_tpu.ops.keccak_jax import xof_turboshake128_batch
    from janus_tpu.ops.prepare import BatchedPrio3
    from janus_tpu.ops.xof_jax import xof_next_vec_batch
    from janus_tpu.vdaf.instances import prio3_histogram
    from janus_tpu.vdaf.prio3 import (
        USAGE_JOINT_RAND_PART,
        USAGE_MEAS_SHARE,
        USAGE_PROOF_SHARE,
    )

    vdaf = prio3_histogram(1024, 316)
    bp = BatchedPrio3(vdaf)
    jf, flp = bp.jf, vdaf.flp
    B = args.batch
    rng = np.random.default_rng(0)
    seeds = jax.device_put(rng.integers(0, 256, (B, 16), dtype=np.uint8))
    nonces = jax.device_put(rng.integers(0, 256, (B, 16), dtype=np.uint8))
    binder1 = jax.device_put(rng.integers(0, 256, (B, 1), dtype=np.uint8))
    meas_limbs = jax.device_put(
        rng.integers(0, 1 << 16, (B, flp.MEAS_LEN, jf.n), dtype=np.uint32)
    )
    proof_limbs = jax.device_put(
        rng.integers(0, 1 << 16, (B, flp.PROOF_LEN, jf.n), dtype=np.uint32)
    )
    jr_limbs = jax.device_put(
        rng.integers(0, 1 << 16, (B, flp.JOINT_RAND_LEN, jf.n), dtype=np.uint32)
    )
    t_limbs = jax.device_put(rng.integers(0, 1 << 16, (B, jf.n), dtype=np.uint32))
    big_binder = jax.device_put(
        rng.integers(0, 256, (B, 1 + 16 + 16 * flp.MEAS_LEN), dtype=np.uint8)
    )
    verifiers = jax.device_put(
        rng.integers(0, 1 << 16, (B, flp.VERIFIER_LEN, jf.n), dtype=np.uint32)
    )

    def stage_xof_meas():
        out, ok = xof_next_vec_batch(
            jf, seeds, bp._dst(USAGE_MEAS_SHARE), binder1, flp.MEAS_LEN
        )
        return out

    def stage_xof_raw_meas():
        # The raw XOF stream for the meas share, no rejection handling.
        return xof_turboshake128_batch(
            seeds, bp._dst(USAGE_MEAS_SHARE), binder1, flp.MEAS_LEN * 4 * jf.n
        )

    def stage_xof_proof():
        out, ok = xof_next_vec_batch(
            jf, seeds, bp._dst(USAGE_PROOF_SHARE), binder1, flp.PROOF_LEN
        )
        return out

    def stage_jr_part():
        return xof_turboshake128_batch(
            seeds, bp._dst(USAGE_JOINT_RAND_PART), big_binder, 16
        )

    def stage_flp_query():
        meas_m = jf.to_mont(meas_limbs)
        proof_m = jf.to_mont(proof_limbs)
        jr_m = jf.to_mont(jr_limbs)
        t_m = jf.to_mont(t_limbs)
        ver, ok = bp._query_one(meas_m, proof_m, jr_m, t_m)
        return jf.from_mont(ver)

    def stage_combine():
        parts = [seeds, seeds]
        out = bp.prep_shares_to_prep([verifiers, verifiers], parts)
        return out["decide"]

    def stage_to_mont():
        return jf.to_mont(meas_limbs)

    stages = {
        "xof_raw_meas": stage_xof_raw_meas,
        "xof_meas": stage_xof_meas,
        "xof_proof": stage_xof_proof,
        "jr_part": stage_jr_part,
        "to_mont": stage_to_mont,
        "flp_query": stage_flp_query,
        "combine": stage_combine,
    }
    pick = [s for s in args.stages.split(",") if s] or list(stages)

    import jax.numpy as jnp

    print(f"platform={jax.devices()[0].platform} batch={B}")
    DEPTH = 8
    for name in pick:
        f = stages[name]
        jitted = jax.jit(f)
        t0 = time.monotonic()
        out = jitted()
        jax.block_until_ready(out)
        compile_s = time.monotonic() - t0

        # Pipelined marginal cost: DEPTH launches in flight, one readback
        # — the metric bench.py reports.
        rounds = []
        for _ in range(args.iters):
            t0 = time.monotonic()
            outs = [jitted() for _ in range(DEPTH)]
            jax.block_until_ready(outs)
            np.asarray(jnp.ravel(outs[-1])[:4])
            rounds.append((time.monotonic() - t0) / DEPTH)
        best = min(rounds) * 1e3
        med = sorted(rounds)[len(rounds) // 2] * 1e3
        print(f"{name:14s} pipelined p50={med:9.2f}ms best={best:9.2f}ms compile={compile_s:6.1f}s")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
