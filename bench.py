"""Benchmark: batched Prio3 prepare throughput on the current JAX backend.

Measures the north-star metric (BASELINE.md configs[2]): reports prepared per
second for Prio3Histogram{length=1024, chunk_length=316} — the helper-side
prepare pipeline (XOF share expansion -> FLP query -> decide -> masked
aggregation), which the reference runs as a per-report scalar loop on rayon
(reference: aggregator/src/aggregator.rs:2101).

Two numbers are reported:

* ``value`` (headline): steady-state PIPELINED throughput — K batches are
  enqueued back-to-back and timed to a final readback.  This is the
  production regime: the aggregation job driver overlaps device launches
  across jobs (janus_tpu/vdaf/backend.py), exactly as the reference keeps
  every rayon worker busy across jobs.
* ``sync_p50_ms``: per-batch latency when each launch is dispatched and
  awaited alone (the round-2 methodology).

Each timed round ends with an np.asarray readback of the decide mask — an
output that depends on the whole pipeline — so neither number can be
flattered by a launch that returned before the device finished.

With no accelerator the run fails: nothing here falls back to the CPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "reports/s", "vs_baseline": N/1e6, ...}
vs_baseline is measured against the 1M reports/s north-star target.

Inputs are random seeds/nonces: the prepare computation is input-oblivious
(identical op sequence for valid and invalid shares), so throughput on random
inputs equals throughput on real jobs; bit-exact correctness is asserted
separately in tests/test_prepare.py and tests/test_backend.py.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time


def build_pipeline(
    vdaf, batch: int, multi_task: int = 0, side: str = "helper",
    field_backend: str = "vpu",
):
    """``multi_task`` > 0 benches the BASELINE configs[4] launch shape: the
    batch carries reports from that many tasks, so the verify key becomes a
    per-ROW traced input (exactly what TpuBackend.prep_init_multi passes).

    ``side`` selects which aggregator's prepare is measured: "helper"
    expands share seeds through the XOF; "leader" preps its explicit
    meas/proof limbs (reference: the leader prepares every report too,
    aggregation_job_driver.rs:397-449).

    ``field_backend`` is the MXU-vs-VPU A/B knob (ops/field_jax.py): "mxu"
    runs the FLP contractions as limb-plane dot_generals on the row-major
    path (planar_eligible turns itself off), "vpu" the limb-planar Pallas
    pipeline."""
    import jax
    import jax.numpy as jnp

    from janus_tpu.ops.prepare import BatchedPrio3

    bp = BatchedPrio3(vdaf, field_backend=field_backend)
    has_jr = vdaf.flp.JOINT_RAND_LEN > 0
    verify_key = b"\x2a" * vdaf.VERIFY_KEY_SIZE
    agg_id = 0 if side == "leader" else 1
    use_planar = bp.planar_eligible(agg_id, batch)

    def prep_step(kw):
        """One aggregate-init step over a whole job: prep + decide against
        the peer's verifier share + masked aggregate."""
        vk = kw.get("verify_keys_u8", verify_key)
        if use_planar:
            out = bp.prep_init_planar(
                agg_id,
                vk,
                kw["nonces_u8"],
                share_seeds_u8=kw.get("share_seeds_u8"),
                meas_limbs=kw.get("meas_limbs"),
                proofs_limbs=kw.get("proofs_limbs"),
                blinds_u8=kw.get("blinds_u8"),
                public_parts_u8=kw.get("public_parts_u8"),
                keep_planar=True,
            )
        else:
            out = bp.prep_init(agg_id, verify_key=vk, **{
                k: v for k, v in kw.items()
                if k not in ("peer_verifiers", "verify_keys_u8")
            })
        parts = (
            [out["joint_rand_part"], out["joint_rand_part"]] if has_jr else None
        )
        if "wire_ev_pl" in out:
            # Verifier planes never leave plane layout: the combined-wire
            # gadget contraction runs in the planar Pallas kernel.
            comb = bp.prep_shares_to_prep_planar(out, kw["peer_verifiers"], parts)
        else:
            comb = bp.prep_shares_to_prep(
                [kw["peer_verifiers"], out["verifiers"]], parts
            )
        agg = bp.aggregate(out["out_share"], comb["decide"])
        return agg, comb["decide"], out["ok"]

    fn = jax.jit(prep_step)

    def make_inputs(seed: int):
        import numpy as np

        rng = np.random.default_rng(seed)
        kw = {
            "nonces_u8": rng.integers(0, 256, (batch, 16), dtype=np.uint8),
            "peer_verifiers": rng.integers(
                0,
                1 << 16,
                (batch, vdaf.flp.VERIFIER_LEN * vdaf.num_proofs, bp.jf.n),
                dtype=np.uint32,
            ),
        }
        if agg_id == 0:
            # Explicit leader shares: random canonical limbs (every limb
            # < 2^16 keeps the value far below the modulus; the prepare
            # op sequence is input-oblivious, so throughput matches real
            # shares).
            kw["meas_limbs"] = rng.integers(
                0, 1 << 16, (batch, vdaf.flp.MEAS_LEN, bp.jf.n), dtype=np.uint32
            )
            kw["proofs_limbs"] = rng.integers(
                0,
                1 << 16,
                (batch, vdaf.flp.PROOF_LEN * vdaf.num_proofs, bp.jf.n),
                dtype=np.uint32,
            )
        else:
            kw["share_seeds_u8"] = rng.integers(0, 256, (batch, 16), dtype=np.uint8)
        if has_jr:
            kw["blinds_u8"] = rng.integers(0, 256, (batch, 16), dtype=np.uint8)
            kw["public_parts_u8"] = rng.integers(
                0, 256, (batch, vdaf.num_shares, 16), dtype=np.uint8
            )
        if multi_task:
            # per-row verify keys: `multi_task` distinct tasks interleaved
            task_keys = rng.integers(
                0, 256, (multi_task, vdaf.VERIFY_KEY_SIZE), dtype=np.uint8
            )
            kw["verify_keys_u8"] = task_keys[np.arange(batch) % multi_task]
        return {k: jax.device_put(v) for k, v in kw.items()}

    return fn, make_inputs


def measure(fn, staged, iters: int, pipeline_depth: int):
    """(sync latencies, pipelined per-batch seconds)."""
    import jax
    import numpy as np

    # Sync latency: dispatch, wait, and read back the decide mask each time.
    sync = []
    for i in range(iters):
        inp = staged[i % len(staged)]
        t0 = time.monotonic()
        out = fn(inp)
        jax.block_until_ready(out)
        np.asarray(out[1][:4])  # decide-mask readback: forces real completion
        sync.append(time.monotonic() - t0)

    # Pipelined throughput: K launches in flight, one readback at the end.
    rounds = []
    for r in range(max(3, iters // 2)):
        t0 = time.monotonic()
        outs = [fn(staged[(r + k) % len(staged)]) for k in range(pipeline_depth)]
        jax.block_until_ready(outs)
        np.asarray(outs[-1][1][:4])
        rounds.append((time.monotonic() - t0) / pipeline_depth)
    return sync, rounds


def run_executor_config(args, scaled: bool) -> dict:
    """BASELINE configs[5] local proxy: N concurrent tasks through the
    DEVICE EXECUTOR (janus_tpu/executor/), the continuous cross-job
    batcher.  16 async submitters — one per task, each with its own verify
    key — submit small per-job batches concurrently; the executor
    coalesces them into pow2-padded mega-batches.  Reported: aggregate
    reports/s end-to-end (submit -> unmarshaled oracle-level outcomes) and
    the mean flush mega-batch size, which must exceed the per-submitter
    batch size for cross-job coalescing to have actually happened.

    ``scaled`` (CPU-only machines): a small histogram shape keeps the
    XLA:CPU compile in seconds; the coalescing measurement is shape-
    independent.
    """
    import asyncio

    import numpy as np

    from janus_tpu.executor import DeviceExecutor, ExecutorConfig
    from janus_tpu.vdaf.backend import TpuBackend
    from janus_tpu.vdaf.instances import prio3_histogram

    n_tasks = 16
    if scaled:
        vdaf = prio3_histogram(length=4, chunk_length=2)
        per, rounds = 8, 2
        desc = "16 concurrent tasks x Prio3Histogram len=4 (executor, scaled)"
    else:
        vdaf = prio3_histogram(length=1024, chunk_length=316)
        per, rounds = 32, 4
        desc = "16 concurrent tasks x Prio3Histogram len=1024 (executor)"

    backend = TpuBackend(vdaf)
    executor = DeviceExecutor(
        ExecutorConfig(
            enabled=True,
            flush_max_rows=n_tasks * per,
            flush_window_s=0.005,
        )
    )
    shape_key = ("bench-executor", type(vdaf.flp.valid).__name__)

    # One shard per task, repeated per row: prepare is input-oblivious, so
    # identical rows measure real throughput without paying n_tasks*per*
    # rounds host-side shards.
    rng = np.random.default_rng(7)
    tasks = []
    for t in range(n_tasks):
        vk = rng.integers(0, 256, vdaf.VERIFY_KEY_SIZE, dtype=np.uint8).tobytes()
        nonce = rng.integers(0, 256, vdaf.NONCE_SIZE, dtype=np.uint8).tobytes()
        rand = rng.integers(0, 256, vdaf.RAND_SIZE, dtype=np.uint8).tobytes()
        public, shares = vdaf.shard(t % vdaf.flp.valid.length, nonce, rand)
        tasks.append((vk, [(nonce, public, shares[0])] * per))

    async def submitter(t, vk, reports):
        for _ in range(rounds):
            out = await executor.submit(
                shape_key,
                "prep_init",
                (vk, reports),
                backend=backend,
                agg_id=0,
                # per-task cost attribution (ISSUE 12): the row proves the
                # ledger splits one shared mega-batch across its tenants
                task_ident=f"bench/{t}",
            )
            assert len(out) == len(reports)

    async def drive():
        await asyncio.gather(
            *[submitter(t, vk, reports) for t, (vk, reports) in enumerate(tasks)]
        )
        await executor.drain()

    from janus_tpu.core.metrics import GLOBAL_METRICS

    def _task_seconds():
        out = {}
        for t in range(n_tasks):
            out[t] = sum(
                GLOBAL_METRICS.get_sample_value(
                    "janus_task_device_seconds_total",
                    {"task": f"bench/{t}", "phase": phase, "path": "device"},
                )
                or 0.0
                for phase in ("stage", "launch")
            )
        return out

    def _pad_rows(label):
        return (
            GLOBAL_METRICS.get_sample_value(
                "janus_executor_pad_rows_total", {"bucket": label}
            )
            or 0.0
        )

    # Warmup pass compiles the mega-batch executable outside the timing;
    # stats are diffed against this snapshot so flushes/mean_flush_rows
    # describe ONLY the timed pass.
    asyncio.run(drive())
    bucket = next(iter(executor.stats().keys()), "")
    warm = next(iter(executor.stats().values()), {})
    warm_seconds = _task_seconds()
    warm_pad = _pad_rows(bucket)
    t0 = time.monotonic()
    asyncio.run(drive())
    elapsed = time.monotonic() - t0
    executor.shutdown()

    stats = next(iter(executor.stats().values()), {})
    total = n_tasks * per * rounds
    flushes = stats.get("flushes", 0) - warm.get("flushes", 0)
    flushed_rows = stats.get("flushed_rows", 0) - warm.get("flushed_rows", 0)
    mean_flush = round(flushed_rows / flushes, 2) if flushes else 0.0
    task_seconds = {
        t: s - warm_seconds[t] for t, s in _task_seconds().items()
    }
    attributed = sum(task_seconds.values())
    pad_rows = _pad_rows(bucket) - warm_pad
    return {
        "config": desc,
        "value": round(total / elapsed, 1),
        "unit": "reports/s",
        "submitters": n_tasks,
        "per_submitter_rows": per,
        "mean_flush_rows": mean_flush,
        "flushes": flushes,
        "cross_job_coalesced": bool(mean_flush > per),
        # cost-attribution proof rows (ISSUE 12): the 16 tenants split the
        # shared flushes' device seconds ~evenly (identical row counts),
        # and pad waste is the pow2-rounding overhead of this flush mix
        "attributed_device_s": round(attributed, 4),
        "task_device_s_min": round(min(task_seconds.values()), 4),
        "task_device_s_max": round(max(task_seconds.values()), 4),
        "pad_rows": int(pad_rows),
        "pad_waste": round(pad_rows / (pad_rows + flushed_rows), 4)
        if (pad_rows + flushed_rows) > 0
        else 0.0,
    }


def run_accumulator_config(args, scaled: bool) -> dict:
    """The ``accum16`` row: the executor16 shape with the DEVICE-RESIDENT
    ACCUMULATOR STORE attached (janus_tpu/executor/accumulator.py).  Every
    flush keeps its out-share mega-batch on device (ResidentRefs back to
    the submitters, zero out-share readback — asserted), each submitter
    commits its rows into a per-task bucket, and one commit-time drain per
    bucket spills a single field vector.  Reported: aggregate reports/s
    plus the flush-readback bytes the resident path avoided vs what the
    legacy readback path would have moved.
    """
    import asyncio

    import numpy as np

    from janus_tpu.executor import (
        AccumulatorConfig,
        DeviceAccumulatorStore,
        DeviceExecutor,
        ExecutorConfig,
        ResidentRef,
    )
    from janus_tpu.vdaf.backend import OracleBackend, TpuBackend
    from janus_tpu.vdaf.instances import prio3_histogram

    n_tasks = 16
    if scaled:
        vdaf = prio3_histogram(length=4, chunk_length=2)
        per, rounds = 8, 2
        desc = "16 tasks x Prio3Histogram len=4 (resident accumulator, scaled)"
    else:
        vdaf = prio3_histogram(length=1024, chunk_length=316)
        per, rounds = 32, 4
        desc = "16 tasks x Prio3Histogram len=1024 (resident accumulator)"

    backend = TpuBackend(vdaf)
    store = DeviceAccumulatorStore(AccumulatorConfig(enabled=True))
    executor = DeviceExecutor(
        ExecutorConfig(
            enabled=True, flush_max_rows=n_tasks * per, flush_window_s=0.005
        )
    )
    executor.accumulator = store
    shape_key = ("bench-accum", type(vdaf.flp.valid).__name__)
    field = vdaf.flp.field

    rng = np.random.default_rng(7)
    tasks = []
    for t in range(n_tasks):
        vk = rng.integers(0, 256, vdaf.VERIFY_KEY_SIZE, dtype=np.uint8).tobytes()
        nonce = rng.integers(0, 256, vdaf.NONCE_SIZE, dtype=np.uint8).tobytes()
        rand = rng.integers(0, 256, vdaf.RAND_SIZE, dtype=np.uint8).tobytes()
        public, shares = vdaf.shard(t % vdaf.flp.valid.length, nonce, rand)
        tasks.append((t, vk, [(nonce, public, shares[0])] * per))

    drained = {}

    async def submitter(t, vk, reports):
        for r in range(rounds):
            out = await executor.submit(
                shape_key,
                "prep_init",
                (vk, reports),
                backend=backend,
                agg_id=0,
                retain_out_shares=True,
            )
            refs = [state.out_share for state, _ in out]
            assert all(isinstance(x, ResidentRef) for x in refs)
            # commit-time spill: one device psum + one O(OUT) readback
            bucket = ("task", t)
            store.commit_rows(
                bucket,
                backend,
                refs,
                job_token=b"job%d-%d" % (t, r),
                report_ids=[b"%d-%d-%d" % (t, r, i) for i in range(len(refs))],
            )
            vec, _rids = store.drain(bucket, field)
            prev = drained.get(t)
            drained[t] = vec if prev is None else field.vec_add(prev, vec)

    async def drive():
        await asyncio.gather(*[submitter(*task) for task in tasks])
        await executor.drain()

    asyncio.run(drive())  # warmup compile pass
    drained.clear()
    backend.outshare_readback_rows = 0
    spills_before = store.spills
    t0 = time.monotonic()
    asyncio.run(drive())
    elapsed = time.monotonic() - t0
    executor.shutdown()

    # parity spot-check: task 0's accumulated vector == the oracle's sum
    t0_, vk0, reports0 = tasks[0]
    want = vdaf.aggregate(
        [
            state.out_share
            for state, _ in OracleBackend(vdaf).prep_init_batch(vk0, 0, reports0)
        ]
        * rounds
    )
    assert drained[t0_] == want, "resident accumulation must match the oracle"

    total = n_tasks * per * rounds
    out_len, nlimbs = vdaf.flp.OUTPUT_LEN, backend.bp.jf.n
    legacy_bytes = total * out_len * nlimbs * 4
    resident_bytes = (store.spills - spills_before) * out_len * nlimbs * 4
    return {
        "config": desc,
        "value": round(total / elapsed, 1),
        "unit": "reports/s",
        "submitters": n_tasks,
        "per_submitter_rows": per,
        "flush_readback_rows": backend.outshare_readback_rows,
        "legacy_readback_bytes": legacy_bytes,
        "resident_readback_bytes": resident_bytes,
        "readback_reduction": round(legacy_bytes / max(1, resident_bytes), 1),
    }


def run_coldtask_config(args, scaled: bool) -> dict:
    """The ``coldtask`` row (ISSUE 8): a COLD task joins a busy 16-task
    fleet.  Phase A runs the shape-churn machinery — pow2 canonical shape
    keys + registry-driven background warmup — so the cold task either
    lands in an already-warm bucket (shared executable, zero compile) or
    drains through the CPU oracle while its bucket compiles OFF the
    submit path; phase B (the before) gives the same cold task an
    exact-shape backend with no warmup, so its first flush pays the XLA
    compile inline.  Recorded: p99 first-flush latency across repeated
    cold joins (A), the compile-inline first flush (B), whether the
    compile overlapped service, and the warmup ledger's compile seconds.
    On TPU platforms with ``common.compile_cache_dir`` set, re-running
    this row replays the cache and B's compile collapses too — the
    cache-hit compile seconds are whatever the ledger then reports."""
    import asyncio

    import numpy as np

    from janus_tpu.executor import DeviceExecutor, ExecutorConfig
    from janus_tpu.vdaf.backend import make_backend
    from janus_tpu.vdaf.canonical import executor_shape
    from janus_tpu.vdaf.instances import prio3_histogram

    # 16 tasks, 8 per canonical bucket; per-submitter batches are sized
    # so each bucket's busy flush hits the warmed mega-batch pad exactly.
    n_tasks, per = 16, 16
    mega = (n_tasks // 2) * per  # 128-row mega-batches (the warmed shape)
    if scaled:
        # chunk 3: fleet length 7 is a NON-ceiling bucket member (twin
        # len 9, TAGGED canonical key) and length 9 the bucket ceiling
        # (exact key, planar-capable maskless graphs) — two warm
        # backends; the UNSEEN cold length 8 lands in the warm canonical
        # bucket.  Small shapes keep the XLA:CPU compiles in tens of
        # seconds.
        chunk, fleet_lengths, cold_length, new_bucket_length = (
            3,
            [7, 9],
            8,
            13,  # calls 5 -> bucket ceiling 7 (a genuinely cold bucket)
        )
        desc = "cold task joins 16-task fleet (Histogram chunk=3, scaled)"
    else:
        # chunk 316: non-ceiling length 1000 (twin len 1264) + the
        # ceiling itself; the unseen cold 1100 shares the warm twin.
        chunk, fleet_lengths, cold_length, new_bucket_length = (
            316,
            [1000, 1264],
            1100,
            1400,  # calls 5 -> bucket ceiling 7
        )
        desc = "cold task joins 16-task fleet (Histogram chunk=316)"

    def build(vdaf_length, canonical_on):
        vdaf = prio3_histogram(vdaf_length, chunk)
        key, canon = executor_shape(vdaf, enabled=canonical_on)
        if canon is not None:
            return vdaf, key, lambda: make_backend(canon, "tpu", canonical=True)
        return vdaf, key, lambda: make_backend(vdaf, "tpu")

    def shard_rows(vdaf, seed, rows=None):
        rng = np.random.default_rng(seed)
        nonce = rng.integers(0, 256, vdaf.NONCE_SIZE, dtype=np.uint8).tobytes()
        rand = rng.integers(0, 256, vdaf.RAND_SIZE, dtype=np.uint8).tobytes()
        public, shares = vdaf.shard(0, nonce, rand)
        return [(nonce, public, shares[1])] * (rows or per)

    async def first_flush(ex, key, backend, vdaf, rows, vk):
        """One cold task's first submission, routed the way the driver
        routes it: oracle-drain while the shape warms, device otherwise.
        Returns (latency_s, served_on_oracle)."""
        t0 = time.monotonic()
        if ex.warming(key):
            out = backend.oracle_for(vdaf).prep_init_batch(vk, 1, rows)
            assert len(out) == len(rows)
            return time.monotonic() - t0, True
        payload = (
            (vk, rows, vdaf) if getattr(backend, "canonical", False) else (vk, rows)
        )
        out = await ex.submit(key, "prep_init", payload, backend=backend, agg_id=1)
        assert len(out) == len(rows)
        return time.monotonic() - t0, False

    # ---- phase A: warmup + canonicalization ON -------------------------
    ex = DeviceExecutor(
        ExecutorConfig(
            enabled=True,
            flush_max_rows=mega,
            flush_window_s=0.005,
            warmup_rows=mega,
            warmup_async=True,
            canonical_shapes=True,
            submit_timeout_s=600.0,
        )
    )
    rng = np.random.default_rng(11)
    fleet = []
    for t in range(n_tasks):
        vdaf, key, factory = build(fleet_lengths[t % len(fleet_lengths)], True)
        backend = ex.backend_for(key, factory)
        vk = rng.integers(0, 256, 16, dtype=np.uint8).tobytes()
        fleet.append((vdaf, key, backend, vk, shard_rows(vdaf, 100 + t)))
    # registry warmup: wait for the fleet's (one) bucket to finish
    # compiling in the background, then run busy traffic through it
    for _, key, *_ in fleet:
        ex.wait_warm(key, timeout=3600)

    async def busy_pass():
        await asyncio.gather(
            *[
                first_flush(ex, key, backend, vdaf, rows, vk)
                for vdaf, key, backend, vk, rows in fleet
            ]
        )
        await ex.drain()

    asyncio.run(busy_pass())

    # Repeated cold joins into the busy fleet's bucket: each join is the
    # cold task's FIRST MEGA-BATCH (flush_max_rows rows — the shape
    # warmup precompiled), exactly what a driver flushes for a busy new
    # task.  p99 across the joins is the headline.
    cold_lat, cold_oracle = [], 0
    vdaf, key, factory = build(cold_length, True)
    for trial in range(12):
        backend = ex.backend_for(key, factory)
        vk = rng.integers(0, 256, 16, dtype=np.uint8).tobytes()
        rows = shard_rows(vdaf, 500 + trial, rows=mega)

        async def one():
            lat, on_oracle = await first_flush(ex, key, backend, vdaf, rows, vk)
            await ex.drain()
            return lat, on_oracle

        lat, on_oracle = asyncio.run(one())
        cold_lat.append(lat)
        cold_oracle += int(on_oracle)
    fleet_same_bucket = next(
        (b for v, k, b, _vk, _r in fleet if k == key), None
    )
    shared_bucket = fleet_same_bucket is ex.backend_for(key, factory)

    # a genuinely new bucket: background warmup + oracle-drain until warm
    vdaf_nb, key_nb, factory_nb = build(new_bucket_length, True)
    backend_nb = ex.backend_for(key_nb, factory_nb)
    vk_nb = rng.integers(0, 256, 16, dtype=np.uint8).tobytes()
    rows_nb = shard_rows(vdaf_nb, 999, rows=mega)

    async def new_bucket_join():
        lat, on_oracle = await first_flush(
            ex, key_nb, backend_nb, vdaf_nb, rows_nb, vk_nb
        )
        return lat, on_oracle

    nb_lat, nb_oracle = asyncio.run(new_bucket_join())
    warmed = ex.wait_warm(key_nb, timeout=3600)

    async def warm_flush():
        lat, on_oracle = await first_flush(
            ex, key_nb, backend_nb, vdaf_nb, rows_nb, vk_nb
        )
        await ex.drain()
        assert not on_oracle
        return lat

    nb_warm_lat = asyncio.run(warm_flush()) if warmed else None
    compile_ledger = {
        k: v
        for k, v in ex.compile_stats().items()
        if v["compile_s"] is not None
    }
    ex.shutdown()

    # ---- phase B: before (exact shapes, no warmup) ---------------------
    ex_b = DeviceExecutor(
        ExecutorConfig(
            enabled=True,
            flush_max_rows=mega,
            flush_window_s=0.005,
            warmup_rows=0,
            canonical_shapes=False,
            submit_timeout_s=3600.0,
        )
    )
    vdaf_b, key_b, factory_b = build(cold_length, False)  # exact, unwarmed
    backend_b = ex_b.backend_for(key_b, factory_b)
    vk_b = rng.integers(0, 256, 16, dtype=np.uint8).tobytes()
    rows_b = shard_rows(vdaf_b, 1234, rows=mega)

    async def before_join():
        lat, _ = await first_flush(ex_b, key_b, backend_b, vdaf_b, rows_b, vk_b)
        await ex_b.drain()
        return lat

    before_lat = asyncio.run(before_join())
    ex_b.shutdown()

    cold_sorted = sorted(cold_lat)
    p99 = cold_sorted[min(len(cold_sorted) - 1, int(len(cold_sorted) * 0.99))]
    return {
        "config": desc,
        "value": round(p99 * 1000.0, 2),
        "unit": "ms p99 cold-task first flush (warm+canonical)",
        "cold_trials": len(cold_lat),
        "cold_first_flush_p50_ms": round(cold_sorted[len(cold_sorted) // 2] * 1e3, 2),
        "cold_served_on_oracle": cold_oracle,
        "cold_bucket_shared_with_fleet": bool(shared_bucket),
        "new_bucket_first_flush_ms": round(nb_lat * 1e3, 2),
        "new_bucket_served_on_oracle": bool(nb_oracle),
        "new_bucket_warm_flush_ms": (
            round(nb_warm_lat * 1e3, 2) if nb_warm_lat is not None else None
        ),
        "compile_overlapped_service": bool(nb_oracle or warmed),
        "before_exact_cold_first_flush_ms": round(before_lat * 1e3, 2),
        "compile_ledger": compile_ledger,
        "speedup_first_flush": (
            round(before_lat / p99, 1) if p99 > 0 else None
        ),
    }


def run_poplar_config(args, scaled: bool) -> dict:
    """The ``poplar1_hh`` row (ISSUE 10): heavy-hitters reports/s with the
    device executor's agg-param-keyed poplar_init plane vs the legacy
    per-job path.

    Four concurrent jobs at ONE IDPF tree level — the multi-round
    collection steady state — submit through the executor; their bulk-AES
    walks + device sketches coalesce into level-keyed mega-batches.  The
    legacy number serializes the same jobs through per-job
    ``prep_init_batch_poplar`` calls (what every pre-executor round did).
    A per-row oracle-parity assert (batched walk vs per-report
    ``Poplar1.prep_init``) gates the number; parity drift records an
    error, never a throughput value."""
    import asyncio
    import random as _random

    from janus_tpu.executor import DeviceExecutor, ExecutorConfig, KIND_POPLAR_INIT
    from janus_tpu.vdaf.backend import make_backend, vdaf_shape_key
    from janus_tpu.vdaf.poplar1 import Poplar1, Poplar1AggregationParam

    n_jobs = 4
    if scaled:
        bits, level, n_prefixes, per, rounds = 8, 4, 8, 16, 2
        desc = "4 concurrent jobs x Poplar1 bits=8 level=4 (executor, scaled)"
    else:
        bits, level, n_prefixes, per, rounds = 16, 8, 64, 64, 4
        desc = "4 concurrent jobs x Poplar1 bits=16 level=8 (executor)"
    vdaf = Poplar1(bits=bits)
    agg_param = Poplar1AggregationParam(
        level, tuple(range(n_prefixes))
    )
    backend = make_backend(vdaf, "tpu")
    shape_key = vdaf_shape_key(vdaf)

    rng = _random.Random(7)
    jobs = []
    for j in range(n_jobs):
        vk = rng.randbytes(vdaf.VERIFY_KEY_SIZE)
        rows = []
        for i in range(per):
            nonce = rng.randbytes(vdaf.NONCE_SIZE)
            public, shares = vdaf.shard(
                (j * per + i) % (1 << bits), nonce, rng.randbytes(vdaf.RAND_SIZE)
            )
            rows.append((nonce, public, shares[1]))
        jobs.append((vk, rows))

    # oracle-parity fence on a tiny real slice, both aggregator sides
    vk0 = jobs[0][0]
    for agg_id in (0, 1):
        sub = []
        for i in range(2):
            nonce = rng.randbytes(vdaf.NONCE_SIZE)
            public, shares = vdaf.shard(1, nonce, rng.randbytes(vdaf.RAND_SIZE))
            sub.append((nonce, public, shares[agg_id]))
        got = backend.prep_init_batch_poplar(vk0, agg_id, agg_param, sub)
        want = backend.oracle.prep_init_batch_poplar(vk0, agg_id, agg_param, sub)
        for (gs, gsh), (ws, wsh) in zip(got, want):
            assert gsh.encode() == wsh.encode(), "poplar sketch-share parity broke"
            assert gs.y_flat == ws.y_flat, "poplar prefix-value parity broke"

    # legacy per-job path: each job pays its own walk + sketch launch.
    # One untimed pass first so the timed loop excludes sketch-shape JIT
    # compilation exactly like the executor path's warmup run below —
    # the A/B ratio must compare steady states, not compile luck.
    for vk, rows in jobs:
        backend.prep_init_batch_poplar(vk, 1, agg_param, rows)
    t0 = time.monotonic()
    for _ in range(rounds):
        for vk, rows in jobs:
            out = backend.prep_init_batch_poplar(vk, 1, agg_param, rows)
            assert len(out) == len(rows)
    legacy_elapsed = time.monotonic() - t0
    total = n_jobs * per * rounds
    legacy_rate = total / legacy_elapsed

    # executor path: the 4 jobs' submissions coalesce per level bucket
    executor = DeviceExecutor(
        ExecutorConfig(
            enabled=True, flush_max_rows=n_jobs * per, flush_window_s=0.01
        )
    )

    async def submitter(vk, rows):
        for _ in range(rounds):
            out = await executor.submit(
                shape_key,
                KIND_POPLAR_INIT,
                (vk, agg_param, rows),
                backend=backend,
                agg_id=1,
                agg_param_key=agg_param.level,
            )
            assert len(out) == len(rows)

    async def drive():
        await asyncio.gather(*[submitter(vk, rows) for vk, rows in jobs])
        await executor.drain()

    asyncio.run(drive())  # warmup (jits the sketch launch shapes)
    warm = next(iter(executor.stats().values()), {})
    t0 = time.monotonic()
    asyncio.run(drive())
    elapsed = time.monotonic() - t0
    executor.shutdown()

    stats = next(iter(executor.stats().values()), {})
    flushes = stats.get("flushes", 0) - warm.get("flushes", 0)
    flushed_jobs = stats.get("flushed_jobs", 0) - warm.get("flushed_jobs", 0)
    flushed_rows = stats.get("flushed_rows", 0) - warm.get("flushed_rows", 0)
    mean_flush = round(flushed_rows / flushes, 2) if flushes else 0.0
    host_rate = total / elapsed

    # -- jax-walk A/B (device-resident IDPF, ISSUE 13) --------------------
    # Same jobs through the jitted AES walk with the resident store:
    # states carry ResidentRefs, the timed refs commit/psum on device and
    # drain as ONE vector (bit-exact vs the host walk's sum), and the
    # sketch-readback counter must stay at ZERO.
    from janus_tpu.executor import AccumulatorConfig
    from janus_tpu.executor.accumulator import ResidentRef

    jax_backend = make_backend(vdaf, "tpu", poplar_backend="jax")
    field = vdaf.field_for_agg_param(agg_param)
    # per-row oracle parity for the jax walk, both aggregator sides
    for agg_id in (0, 1):
        sub = []
        for i in range(2):
            nonce = rng.randbytes(vdaf.NONCE_SIZE)
            public, shares = vdaf.shard(1, nonce, rng.randbytes(vdaf.RAND_SIZE))
            sub.append((nonce, public, shares[agg_id]))
        got = jax_backend.prep_init_batch_poplar(vk0, agg_id, agg_param, sub)
        want = jax_backend.oracle.prep_init_batch_poplar(vk0, agg_id, agg_param, sub)
        for (gs, gsh), (ws, wsh) in zip(got, want):
            assert gsh.encode() == wsh.encode(), "jax sketch-share parity broke"
            assert gs.y_flat == ws.y_flat, "jax prefix-value parity broke"

    jax_exec = DeviceExecutor(
        ExecutorConfig(
            enabled=True,
            flush_max_rows=n_jobs * per,
            flush_window_s=0.01,
            accumulator=AccumulatorConfig(enabled=True, drain_interval_s=3600.0),
        )
    )
    store = jax_exec.accumulator

    async def submitter_jax(vk, rows, sink):
        for _ in range(rounds):
            out = await jax_exec.submit(
                shape_key,
                KIND_POPLAR_INIT,
                (vk, agg_param, rows),
                backend=jax_backend,
                agg_id=1,
                retain_out_shares=True,
                agg_param_key=agg_param.level,
            )
            assert len(out) == len(rows)
            sink.extend(st.y_flat for st, _sh in out)

    async def drive_jax(sink):
        await asyncio.gather(*[submitter_jax(vk, rows, sink) for vk, rows in jobs])
        await jax_exec.drain()

    # the parity fence above ran WITHOUT retention (its rows legitimately
    # materialize); the resident-path assertion below is on the DELTA
    readback_base = jax_backend.sketch_readback_rows
    warm_refs = []
    asyncio.run(drive_jax(warm_refs))  # warmup (jits the walk + sketch shapes)
    store.release_refs([r for r in warm_refs if isinstance(r, ResidentRef)])
    refs = []
    t0 = time.monotonic()
    asyncio.run(drive_jax(refs))
    jax_elapsed = time.monotonic() - t0
    jax_rate = total / jax_elapsed

    refs = [r for r in refs if isinstance(r, ResidentRef)]
    jax_resident = {"available": bool(refs)}
    if refs:
        # the deferred-leader contract in miniature: commit every timed
        # ref (device psum, no readback) and drain ONE vector — equal to
        # the host walk's sum over the same rows
        bucket_key = (
            "bench", b"task", shape_key, b"ident", vdaf.encode_agg_param(agg_param)
        )
        store.commit_rows(
            bucket_key,
            jax_backend,
            refs,
            job_token=b"bench",
            report_ids=[b"%d" % i for i in range(len(refs))],
        )
        vec, _journal = store.drain_with_journal(bucket_key, field)
        expect = None
        for vk, rows in jobs:
            for st, _sh in backend.prep_init_batch_poplar(vk, 1, agg_param, rows):
                y = list(st.y_flat)
                expect = y if expect is None else field.vec_add(expect, y)
        expect = [field.mul(rounds, v) for v in expect]
        assert vec == expect, "device-resident drain diverged from the host walk"
        jax_resident.update(
            refs_committed=len(refs),
            drain_vector_ok=True,
        )
    readback = jax_backend.sketch_readback_rows - readback_base
    assert readback == 0, (
        f"device-resident path read {readback} sketch row(s) back to host"
    )
    jax_resident["sketch_readback_rows"] = readback
    jax_exec.shutdown()

    return {
        "config": desc,
        "value": round(host_rate, 1),
        "unit": "reports/s",
        "bits": bits,
        "level": level,
        "prefixes": n_prefixes,
        "jobs": n_jobs,
        "per_job_rows": per,
        "legacy_per_job_reports_s": round(legacy_rate, 1),
        "executor_vs_legacy": round(host_rate / legacy_rate, 3)
        if legacy_rate
        else None,
        "mean_flush_rows": mean_flush,
        "flushes": flushes,
        "cross_job_coalesced": bool(
            flushes and flushed_jobs / flushes > 1.0
        ),
        # the ISSUE 13 A/B: same jobs, jitted AES walk + device-resident
        # sketches (this container's host walk is numpy soft-AES; a real
        # host pits the kernel against AES-NI — TPU-runner row)
        "host_walk_reports_s": round(host_rate, 1),
        "jax_walk_reports_s": round(jax_rate, 1),
        "jax_vs_host_walk": round(jax_rate / host_rate, 3) if host_rate else None,
        "jax_resident": jax_resident,
    }


def run_mesh_config(args, scaled: bool) -> dict:
    """The ``mesh8`` row (ISSUE 6): the north-star histogram1024 prepare
    SPMD over every local device via MeshBackend — the production
    multi-chip path (``vdaf_backend: mesh`` / ``device_executor.mesh``),
    not a kernel microbench.  Both halves run exactly as the executor
    drives them (stage: marshal + shard-per-device placement; launch:
    shard_map prepare with DEVICE-RESIDENT out shares — zero out-share
    readback, asserted) and finished rows psum into a SHARDED accumulator
    buffer whose one cross-chip all-reduce happens at the final drain.
    Reported: aggregate reports/s, per-chip efficiency vs a single-chip
    TpuBackend pass measured in the same process, and the drained
    leader-aggregate's bit-exact parity vs the CPU oracle.

    ``scaled`` (CPU-only machines): the len=4 shape over however many
    virtual devices exist — the sharding/correctness path is identical,
    only the throughput is meaningless there (tests assert correctness on
    the 8-virtual-device mesh; the TPU runner produces the real number).
    """
    import jax
    import numpy as np

    from janus_tpu.executor import AccumulatorConfig, DeviceAccumulatorStore
    from janus_tpu.vdaf.backend import MeshBackend, OracleBackend, TpuBackend
    from janus_tpu.vdaf.instances import prio3_histogram

    devices = jax.local_devices()
    n = len(devices)
    if scaled:
        vdaf = prio3_histogram(length=4, chunk_length=2)
        batch, rounds = max(64, 8 * n), 2
        desc = f"Prio3Histogram len=4 SPMD mesh over {n} device(s) (scaled)"
    else:
        vdaf = prio3_histogram(length=1024, chunk_length=316)
        batch, rounds = args.batch, 3
        desc = f"Prio3Histogram len=1024 chunk=316 SPMD mesh over {n} device(s)"

    rng = np.random.default_rng(7)
    vk = rng.integers(0, 256, vdaf.VERIFY_KEY_SIZE, dtype=np.uint8).tobytes()
    nonce = rng.integers(0, 256, vdaf.NONCE_SIZE, dtype=np.uint8).tobytes()
    rand = rng.integers(0, 256, vdaf.RAND_SIZE, dtype=np.uint8).tobytes()
    public, shares = vdaf.shard(1, nonce, rand)
    # helper-side rows (seed expansion through the XOF); identical rows
    # measure real throughput — prepare is input-oblivious
    reports = [(nonce, public, shares[1])] * batch
    store = DeviceAccumulatorStore(AccumulatorConfig(enabled=True))

    def timed_rate(backend, commit_bucket=None):
        """Best-round reports/s through stage+launch with device-resident
        out shares; round 0 pays the compile, untimed.  ``commit_bucket``
        additionally psums each round's rows into the (sharded, on a
        mesh) accumulator buffer — the production steady state."""
        best = float("inf")
        for r in range(rounds + 1):
            t0 = time.monotonic()
            staged = backend.stage_prep_init_multi(1, [(vk, reports)])
            (out,) = backend.launch_prep_init_multi(
                staged, [(vk, reports)], retain_store=store
            )
            refs = [state.out_share for state, _ in out]
            if r == 0:
                store.release_refs(refs)
                continue
            if commit_bucket is not None:
                store.commit_rows(
                    commit_bucket,
                    backend,
                    refs,
                    job_token=b"bench-%d" % r,
                    report_ids=[b"%d-%d" % (r, i) for i in range(len(refs))],
                )
            else:
                store.release_refs(refs)
            best = min(best, time.monotonic() - t0)
        return batch / best

    # Same work on both sides of the efficiency ratio: the single-chip
    # baseline also commits each round into the accumulator (its own
    # bucket), so per_chip_efficiency compares stage+launch+accumulate
    # like for like instead of charging the accumulate launch to the
    # mesh alone.
    single = TpuBackend(vdaf)
    single.outshare_readback_rows = 0
    single_rate = timed_rate(single, commit_bucket=("single-bench",))
    store.discard(("single-bench",))

    mesh = MeshBackend(vdaf, devices=devices)
    mesh.outshare_readback_rows = 0
    mesh_rate = timed_rate(mesh, commit_bucket=("mesh-bench",))
    assert mesh.outshare_readback_rows == 0, (
        "mesh flushes must keep out shares device-resident"
    )

    # The drain: ONE cross-chip all-reduce over the sharded buffer + one
    # O(OUT) readback.  Identical rows make the oracle check exact and
    # cheap: the aggregate is (batch * rounds) x one report's out share.
    vector, _rids = store.drain(("mesh-bench",), vdaf.flp.field)
    ((state, _share),) = OracleBackend(vdaf).prep_init_batch(vk, 1, reports[:1])
    total = batch * rounds
    modulus = vdaf.flp.field.MODULUS
    want = [(x * total) % modulus for x in state.out_share]
    assert vector == want, "mesh leader aggregate must be bit-exact vs the oracle"

    return {
        "config": desc,
        "value": round(mesh_rate, 1),
        "unit": "reports/s",
        "devices": n,
        "batch": batch,
        "single_chip_reports_s": round(single_rate, 1),
        "speedup_vs_single_chip": round(mesh_rate / single_rate, 2)
        if single_rate
        else None,
        "per_chip_efficiency": round(mesh_rate / (n * single_rate), 3)
        if single_rate and n
        else None,
        "flush_readback_rows": mesh.outshare_readback_rows,
        "oracle_parity": True,
    }


def run_fpvec_config(args, scaled: bool) -> dict:
    """The ``fpvec`` row (ISSUE 15): Prio3FixedPointBoundedL2VecSum —
    the federated-learning gradient-sum workload — through the
    multi-gadget device plane vs the scalar CPU oracle.

    The real regime is big-vector/few-shapes (bits=16, entries >= 1000:
    exactly the chunked-ParallelSum shape the MXU limb-plane matmul path
    was built for); the CPU-scaled variant shrinks to a shape XLA:CPU can
    compile in minutes.  A per-row parity fence (both aggregator sides,
    every prepare artifact, device combine verdicts) gates the number —
    parity drift records an error, never a throughput value.  The oracle
    rate is measured over a small report slice (the scalar two-gadget
    query is seconds/report at full size) — same-unit reports/s either
    way, so the device_vs_oracle ratio is direct."""
    import jax

    from janus_tpu.flp import FixedPointBoundedL2VecSum, FlpGeneric
    from janus_tpu.vdaf.backend import OracleBackend, make_backend
    from janus_tpu.vdaf.prio3 import (
        ALG_PRIO3_FIXEDPOINT_BOUNDED_L2_VEC_SUM,
        Prio3,
    )

    if scaled:
        bits, entries, chunk = 2, 2, 2
        batch, iters, oracle_rows = 64, 2, 8
        desc = "Prio3FixedPointBoundedL2VecSum bits=2 entries=2 (cpu-scaled)"
    else:
        bits, entries, chunk = 16, 1000, 127
        batch, iters, oracle_rows = min(args.batch, 2048), args.iters, 8
        desc = "Prio3FixedPointBoundedL2VecSum bits=16 entries=1000 chunk=127"
    vdaf = Prio3(
        FlpGeneric(
            FixedPointBoundedL2VecSum(
                bits_per_entry=bits, entries=entries, chunk_length=chunk
            )
        ),
        ALG_PRIO3_FIXEDPOINT_BOUNDED_L2_VEC_SUM,
    )
    import random as _random

    rng = _random.Random(15)
    vk = rng.randbytes(vdaf.VERIFY_KEY_SIZE)

    def shard_rows(n):
        rows = []
        scale = 1 << (bits - 1)
        for _ in range(n):
            vec = [
                rng.randrange(-scale // 2, scale // 2) / scale
                for _ in range(entries)
            ]
            nonce = rng.randbytes(vdaf.NONCE_SIZE)
            public, shares = vdaf.shard(vec, nonce, rng.randbytes(vdaf.RAND_SIZE))
            rows.append((nonce, public, shares))
        return rows

    backend = make_backend(vdaf, "tpu")
    oracle = OracleBackend(vdaf)

    # parity fence: BOTH aggregator sides + device combine on real rows
    fence = shard_rows(2)
    got_sides = []
    for agg_id in (0, 1):
        sub = [(n, p, sh[agg_id]) for (n, p, sh) in fence]
        got = backend.prep_init_batch(vk, agg_id, sub)
        want = oracle.prep_init_batch(vk, agg_id, sub)
        for (gs, gsh), (ws, wsh) in zip(got, want):
            assert gs.out_share == ws.out_share, "fpvec out-share parity broke"
            assert (
                gsh.verifiers_share == wsh.verifiers_share
            ), "fpvec verifier parity broke"
            assert gsh.joint_rand_part == wsh.joint_rand_part
            assert gs.corrected_joint_rand_seed == ws.corrected_joint_rand_seed
        got_sides.append(got)
    pairs = [
        [got_sides[0][b][1], got_sides[1][b][1]] for b in range(len(fence))
    ]
    assert backend.prep_shares_to_prep_batch(pairs) == oracle.prep_shares_to_prep_batch(
        pairs
    ), "fpvec prepare-message parity broke"

    # timed helper-side prepare: `oracle_rows` sharded reports tiled to
    # the batch (throughput is content-independent; distinct nonces per
    # slot keep the XOF work honest)
    base = shard_rows(oracle_rows)
    tiled = []
    for i in range(batch):
        n, p, sh = base[i % len(base)]
        tiled.append((rng.randbytes(vdaf.NONCE_SIZE), p, sh[1]))
    t0 = time.monotonic()
    out = backend.prep_init_batch(vk, 1, tiled)
    compile_s = time.monotonic() - t0
    assert len(out) == batch
    t0 = time.monotonic()
    for _ in range(iters):
        backend.prep_init_batch(vk, 1, tiled)
    device_elapsed = time.monotonic() - t0
    device_rate = batch * iters / device_elapsed

    # oracle rate over the small slice (scalar two-gadget query)
    osub = [(n, p, sh[1]) for (n, p, sh) in base]
    t0 = time.monotonic()
    oracle.prep_init_batch(vk, 1, osub)
    oracle_elapsed = time.monotonic() - t0
    oracle_rate = len(osub) / oracle_elapsed

    return {
        "config": desc,
        "side": "helper",
        "value": round(device_rate, 1),
        "unit": "reports/s",
        "batch": batch,
        "iters": iters,
        "compile_s": round(compile_s, 1),
        "oracle_reports_s": round(oracle_rate, 1),
        "device_vs_oracle": round(device_rate / oracle_rate, 2)
        if oracle_rate
        else None,
        "platform": jax.devices()[0].platform,
    }


CONFIGS = {
    # BASELINE.md rows; histogram1024 is the north-star config.
    "count": ("Prio3Count", "prio3_count", {}),
    "sum32": ("Prio3Sum bits=32", "prio3_sum", {"bits": 32}),
    "histogram1024": (
        "Prio3Histogram len=1024 chunk=316",
        "prio3_histogram",
        {"length": 1024, "chunk_length": 316},
    ),
    "sumvec": (
        "Prio3SumVec len=1024 bits=1 chunk=316",
        "prio3_sum_vec",
        {"length": 1024, "bits": 1, "chunk_length": 316},
    ),
    "sumvec100k": (
        # BASELINE.md configs[3]: the wide-vector FLP
        # (reference circuit params: core/src/vdaf.rs:220-236).
        "Prio3SumVec len=100000 bits=1 chunk=316",
        "prio3_sum_vec",
        {"length": 100000, "bits": 1, "chunk_length": 316},
    ),
    "multitask16": (
        # BASELINE.md configs[4], single-chip form: one launch carrying
        # 16 concurrent histogram tasks (per-row verify keys).
        "16x Prio3Histogram len=1024 chunk=316, one launch",
        "prio3_histogram",
        {"length": 1024, "chunk_length": 316},
    ),
}

# All five BASELINE.md rows, benched on every default run so BENCH_r{N}.json
# stays comparable round over round (VERDICT r3 weak #9).
DEFAULT_SET = ["count", "sum32", "histogram1024", "sumvec100k", "multitask16"]

#: Rows tracked under BOTH field-arithmetic layouts (ISSUE 7): each gets a
#: sibling ``<name>_mxu`` row so the MXU-vs-VPU delta is recorded per shape
#: in BENCH_r{N}.json, with a per-row oracle-parity assert on each side.
MXU_AB_ROWS = ("sum32", "histogram1024", "sumvec100k")


def _record_row_failure(results: dict, key: str, e: BaseException) -> None:
    """A row that fails — a bench bug, a compile error, or the device going
    away mid-run — is an error; nothing here turns it into a skip."""
    sys.stderr.write(f"{key} failed: {type(e).__name__}: {e}\n")
    results[key] = {"error": f"{type(e).__name__}: {e}"}


def _bench_measurement(vdaf):
    """A valid measurement for this VDAF's circuit (parity spot checks)."""
    valid = vdaf.flp.valid
    kind = type(valid).__name__
    if kind == "SumVec":
        return [1] * valid.length
    if kind == "Histogram":
        return 1  # bucket index
    if kind == "Count":
        return 1
    return 1  # Sum: any value < 2^bits


def _assert_oracle_parity(vdaf, field_backend: str) -> None:
    """Bit-exact fence for the benched row's backend: a tiny batch of REAL
    sharded reports through the device path under ``field_backend`` (both
    aggregator sides) must match the CPU oracle limb-for-limb (prep shares,
    out shares, joint-rand parts, prepare messages).  Raises AssertionError
    on drift — a throughput number with broken parity must never be
    recorded."""
    import numpy as np

    from janus_tpu.vdaf.backend import OracleBackend, make_backend

    rng = np.random.default_rng(1234)
    verify_key = rng.integers(0, 256, vdaf.VERIFY_KEY_SIZE, dtype=np.uint8).tobytes()
    meas = _bench_measurement(vdaf)
    rows = []
    for _ in range(2):
        nonce = rng.integers(0, 256, vdaf.NONCE_SIZE, dtype=np.uint8).tobytes()
        rand = rng.integers(0, 256, vdaf.RAND_SIZE, dtype=np.uint8).tobytes()
        public, shares = vdaf.shard(meas, nonce, rand)
        rows.append((nonce, public, shares))
    backend = make_backend(vdaf, "tpu", field_backend=field_backend)
    oracle = OracleBackend(vdaf)
    got_shares = []
    for a in range(vdaf.num_shares):
        sub = [(n, p, sh[a]) for (n, p, sh) in rows]
        got = backend.prep_init_batch(verify_key, a, sub)
        want = oracle.prep_init_batch(verify_key, a, sub)
        for (gs, gsh), (ws, wsh) in zip(got, want):
            assert gs.out_share == ws.out_share, "out-share parity broke"
            assert gsh.verifiers_share == wsh.verifiers_share, "verifier parity broke"
            assert gsh.joint_rand_part == wsh.joint_rand_part
            assert gs.corrected_joint_rand_seed == ws.corrected_joint_rand_seed
        got_shares.append(got)
    combined = [[got_shares[a][b][1] for a in range(vdaf.num_shares)] for b in range(len(rows))]
    assert backend.prep_shares_to_prep_batch(combined) == oracle.prep_shares_to_prep_batch(
        combined
    ), "prepare-message parity broke"


def run_config(
    name: str, args, side: str = "helper", field_backend: str = "vpu"
) -> dict:
    """Measure one config; returns the result dict (or an error record)."""
    import jax

    from janus_tpu.vdaf import instances

    desc, ctor_name, ctor_kw = CONFIGS[name]
    vdaf = getattr(instances, ctor_name)(**ctor_kw)

    batch = args.batch
    depth = args.pipeline_depth
    if name == "sumvec100k":
        # 100k Field128 elements/report: bound the batch and the number of
        # in-flight launches (each holds a multi-GB XLA workspace).  1024 is
        # the minimum batch that engages the planar Pallas XOF kernels
        # (keccak_pallas.pallas_enabled) and fits HBM.
        batch = min(batch, 1024)
        depth = min(depth, 3)
    fn = make_inputs = None
    while batch >= 64:
        try:
            fn, make_inputs = build_pipeline(
                vdaf, batch, multi_task=16 if name == "multitask16" else 0,
                side=side, field_backend=field_backend,
            )
            inputs = make_inputs(0)
            t0 = time.monotonic()
            out = fn(inputs)
            jax.block_until_ready(out)
            compile_s = time.monotonic() - t0
            break
        except Exception as e:  # OOM etc: halve the batch and retry
            sys.stderr.write(f"{name}: batch {batch} failed ({type(e).__name__}: {e}); halving\n")
            batch //= 2
            fn = None
    if fn is None:
        return {"config": desc, "error": "no batch size succeeded"}

    staged = [make_inputs(i + 1) for i in range(min(args.iters, 4))]
    sync, rounds = measure(fn, staged, args.iters, depth)

    sync_p50 = statistics.median(sync)
    pipelined = min(rounds)  # least-contended round: this chip is shared
    reports_per_sec = batch / pipelined
    if (name in MXU_AB_ROWS and side == "helper") or field_backend != "vpu":
        # A throughput number with broken parity must never be recorded:
        # re-derive a tiny batch of real reports through the device path
        # under this row's field_backend and diff it against the CPU
        # oracle.  An AssertionError here turns the row into an error
        # record in main()'s per-row handler.
        _assert_oracle_parity(vdaf, field_backend)
    result = {
        "config": desc,
        "side": side,
        "field_backend": field_backend,
        "value": round(reports_per_sec, 1),
        "unit": "reports/s",
        "batch": batch,
        "pipelined_ms_per_batch": round(pipelined * 1e3, 3),
        "pipeline_depth": depth,
        "sync_p50_ms": round(sync_p50 * 1e3, 3),
        "compile_s": round(compile_s, 1),
    }
    if name == "sumvec100k" and side == "helper":
        # VERDICT r4 weak #2: prove (or disprove) the XOF bound with
        # recorded numbers, not prose — the protocol-mandated Keccak volume
        # per report vs the standalone squeeze kernel's ceiling on this
        # same device at this same batch.
        try:
            result.update(_sumvec_xof_evidence(vdaf, batch))
            ceiling = result.get("keccak_ceiling_reports_s")
            if ceiling:
                result["xof_bound_fraction"] = round(reports_per_sec / ceiling, 3)
        except Exception as e:  # pragma: no cover - evidence is best-effort
            sys.stderr.write(f"sumvec xof evidence failed: {e}\n")
    return result


def _sumvec_xof_evidence(vdaf, batch: int) -> dict:
    """Measured Keccak ceiling for the sumvec100k shape.

    Counts the TurboSHAKE permutations the prepare pipeline MUST run per
    report (meas + proof squeeze, joint-rand binder absorb), then times the
    standalone planar squeeze kernel producing that much stream at this
    batch.  ceiling_reports_s = achievable reports/s if the pipeline were
    nothing but its XOF — the recorded upper bound the throughput row is
    judged against.
    """
    import jax
    import numpy as np

    from janus_tpu.ops.keccak_pallas import RATE_WORDS, xof_planes_pallas

    flp = vdaf.flp
    n = flp.field.ENCODED_SIZE // 4
    meas_words = flp.MEAS_LEN * n
    proof_words = flp.PROOF_LEN * n
    squeeze_perms = -(-meas_words // RATE_WORDS) + (-(-proof_words // RATE_WORDS))
    # joint-rand part binder: head + meas bytes + padding, one absorb
    # permutation per rate block (prepare.py _jr_part_planes)
    absorb_perms = (1 + 16 + 16 + 1 + 4 * meas_words) // (RATE_WORDS * 4) + 1
    perms_per_report = squeeze_perms + absorb_perms

    rng = np.random.default_rng(0)
    seeds = jax.device_put(rng.integers(0, 256, (batch, 16), dtype=np.uint8))
    binder = jax.device_put(np.ones((batch, 1), dtype=np.uint8))

    def squeeze_only(s, b):
        # same kernel, same words as the pipeline's meas expansion
        return xof_planes_pallas(s, b"\x01\x02", b, meas_words)[-1]

    fn = jax.jit(squeeze_only)
    out = fn(seeds, binder)
    jax.block_until_ready(out)
    best = float("inf")
    DEPTH = 4
    for _ in range(3):
        t0 = time.monotonic()
        outs = [fn(seeds, binder) for _ in range(DEPTH)]
        jax.block_until_ready(outs)
        np.asarray(outs[-1][:1, :4])
        best = min(best, (time.monotonic() - t0) / DEPTH)
    meas_perms = -(-meas_words // RATE_WORDS)
    perm_per_sec = batch * meas_perms / best
    return {
        "xof_permutations_per_report": perms_per_report,
        "xof_bytes_per_report": 4 * (meas_words + proof_words),
        "keccak_standalone_perm_per_s": round(perm_per_sec, 0),
        "keccak_ceiling_reports_s": round(perm_per_sec / perms_per_report, 1),
    }


def run_upload_frontdoor_config(args, scaled: bool = False) -> dict:
    """Upload front-door row (ISSUE 14): batched vs inline HPKE opens/s
    (the DAP default suite, X25519 / AES-128-GCM) with a parity fence,
    plus a short in-process loadgen pass recording the reports/s the
    full upload pipeline sustains with its SLO burn below the
    sustainable pace and zero sheds."""
    import asyncio
    import secrets

    from janus_tpu.core.hpke import (
        HpkeApplicationInfo,
        HpkeKeypair,
        Label,
        open_,
        seal,
    )
    from janus_tpu.core.hpke_batch import open_batch
    from janus_tpu.messages import Role

    B = 128 if scaled else 512
    info = HpkeApplicationInfo.new(Label.INPUT_SHARE, Role.CLIENT, Role.LEADER)
    kp = HpkeKeypair.generate(1)
    batch = []
    for _ in range(B):
        pt = secrets.token_bytes(120)
        aad = secrets.token_bytes(48)
        batch.append((kp, info, seal(kp.config, info, pt, aad), aad))

    # parity fence BEFORE timing: a throughput number with broken parity
    # must never be recorded
    got = open_batch(batch)
    want = [open_(k, i, c, a) for (k, i, c, a) in batch]
    assert got == want, "batched open parity broke"

    def best_of(fn, n=3):
        best = float("inf")
        for _ in range(n):
            t0 = time.monotonic()
            fn()
            best = min(best, time.monotonic() - t0)
        return best

    t_batched = best_of(lambda: open_batch(batch))
    t_inline = best_of(lambda: [open_(k, i, c, a) for (k, i, c, a) in batch])
    result = {
        "config": f"upload front door: {B} HPKE opens, batched vs inline",
        "value": round(B / t_batched, 1),
        "unit": "opens/s",
        "batch": B,
        "inline_opens_s": round(B / t_inline, 1),
        "batched_vs_inline": round(t_inline / t_batched, 2),
    }

    # -- loadgen reports/s at SLO (in-process leader, real HTTP) ---------
    try:
        from aiohttp.test_utils import TestClient, TestServer

        from janus_tpu.aggregator import Aggregator, Config
        from janus_tpu.aggregator.http_handlers import aggregator_app
        from janus_tpu.core.metrics import GLOBAL_METRICS
        from janus_tpu.core.slo import SloEvaluator, targets_from_config
        from janus_tpu.core.time import MockClock
        from janus_tpu.datastore.test_util import EphemeralDatastore
        from janus_tpu.messages import Time

        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))
        from loadgen import run_load

        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests"))
        from test_aggregator_handlers import make_pair_tasks

        NOW = Time(1_600_002_000)
        leader, _helper, _ = make_pair_tasks({"type": "Prio3Count"})
        eds = EphemeralDatastore(MockClock(NOW))
        eds.datastore.run_tx("put", lambda tx: tx.put_aggregator_task(leader))
        agg = Aggregator(
            eds.datastore,
            eds.clock,
            Config(vdaf_backend="oracle", upload_open_backend="batched"),
        )
        evaluator = SloEvaluator(
            targets_from_config(
                {"upload_to_commit": {"objective": 0.95, "threshold_s": 10}}
            ),
            metrics=GLOBAL_METRICS,
        )
        evaluator.tick()
        rate = 25 if scaled else 200

        async def flow():
            client = TestClient(TestServer(aggregator_app(agg)))
            await client.start_server()
            try:
                return await run_load(
                    str(client.make_url("/")).rstrip("/"),
                    leader.task_id,
                    {"type": "Prio3Count"},
                    rate=rate,
                    duration_s=4.0,
                    ramp_s=0.5,
                    concurrency=64,
                    now_fn=lambda: NOW,
                )
            finally:
                await client.close()

        loop = asyncio.new_event_loop()
        try:
            summary = loop.run_until_complete(flow())
        finally:
            loop.close()
            eds.cleanup()
        verdict = evaluator.tick()["upload_to_commit"]
        slo_green = (
            summary["outcomes"]["shed"] == 0
            and verdict["burn_rate"]["fast"] < 1.0
            and verdict["breaches"] == 0
        )
        result["loadgen_reports_s"] = summary["accepted_rate"]
        result["loadgen_target_rate"] = rate
        result["loadgen_slo_green"] = slo_green
        result["loadgen_outcomes"] = summary["outcomes"]
        if not slo_green:
            result["error"] = "loadgen pass breached its SLO or shed"
    except Exception as e:  # the opens/s halves still record
        result["loadgen_skipped"] = f"{type(e).__name__}: {str(e)[:200]}"

    # -- ISSUE 18: upload -> first-prepare A/B (journaled vs synchronous)
    # The zero-copy ingest unit: the SAME sealed reports through both
    # ingest modes, measuring upload-start -> first prepare-ready
    # aggregation job.  Parity-fenced first: journaled materialization
    # must store byte-identical rows before any latency is recorded.
    try:
        import sqlite3 as _sqlite3

        from janus_tpu.aggregator import (
            AggregationJobCreator,
            Aggregator,
            Config,
            CreatorConfig,
        )
        from janus_tpu.core.time import MockClock
        from janus_tpu.datastore.test_util import EphemeralDatastore

        from test_aggregator_handlers import NOW as _NOW
        from test_aggregator_handlers import make_pair_tasks as _make_pair
        from test_upload_frontdoor import _reports, _stored_rows

        B2 = 32 if scaled else 128
        leader2, helper2, _ = _make_pair({"type": "Prio3Count"})
        sealed = _reports(leader2, helper2, B2)

        def _agg(mode, stage_direct):
            eds = EphemeralDatastore(MockClock(_NOW))
            eds.datastore.run_tx("put", lambda tx: tx.put_aggregator_task(leader2))
            agg = Aggregator(
                eds.datastore,
                eds.clock,
                Config(
                    vdaf_backend="oracle",
                    upload_open_backend="batched",
                    upload_open_batch_delay=0.002,
                    ingest_mode=mode,
                    ingest_journal_write_delay=0.002,
                    ingest_stage_direct=stage_direct,
                ),
            )
            return eds, agg

        async def _upload_all(agg):
            await asyncio.gather(
                *(agg.handle_upload(leader2.task_id, r) for r in sealed)
            )

        # parity fence (stage off so journaled rows MATERIALIZE instead
        # of scrubbing): decrypted stored rows must match bit-for-bit
        rows = {}
        for mode in ("synchronous", "journaled"):
            eds, agg = _agg(mode, stage_direct=False)
            loop = asyncio.new_event_loop()
            try:
                loop.run_until_complete(_upload_all(agg))
                loop.run_until_complete(agg.shutdown())
                if agg.ingest is not None:
                    loop.run_until_complete(agg.ingest.drain())
                rows[mode] = _stored_rows(eds.datastore, leader2.task_id)
            finally:
                loop.close()
                eds.cleanup()
        if rows["journaled"] != rows["synchronous"] or len(rows["journaled"]) != B2:
            result["error"] = "journaled materialization parity broke"
            return result

        def _packed(path):
            conn = _sqlite3.connect(path)
            try:
                return conn.execute(
                    "SELECT COUNT(*) FROM report_aggregations"
                ).fetchone()[0]
            finally:
                conn.close()

        async def _first_prepare_ms(mode):
            eds, agg = _agg(mode, stage_direct=True)
            creator = AggregationJobCreator(
                eds.datastore,
                CreatorConfig(
                    min_aggregation_job_size=1,
                    max_aggregation_job_size=256,
                    journal_replay_min_age_s=0.0,
                ),
            )
            try:
                t0 = time.monotonic()
                await _upload_all(agg)
                first = None
                for _ in range(200):
                    if agg.ingest is not None:
                        # the zero-copy handoff: staged cohorts pack with
                        # no client_reports read-back
                        await creator.run_staged_once(agg.ingest)
                    else:
                        await creator.run_once()
                    n = _packed(eds.path)
                    if first is None and n > 0:
                        first = time.monotonic()
                    if n >= B2:
                        break
                    if agg.ingest is not None:
                        await agg.ingest.materialize_once(1024)
                        await creator.run_once()
                assert _packed(eds.path) >= B2, "A/B never packed every report"
                await agg.shutdown()
                if agg.ingest is not None:
                    await agg.ingest.drain()
                return round((first - t0) * 1000, 2)
            finally:
                eds.cleanup()

        ab = {}
        for mode in ("synchronous", "journaled"):
            loop = asyncio.new_event_loop()
            try:
                ab[mode] = loop.run_until_complete(_first_prepare_ms(mode))
            finally:
                loop.close()
        result["upload_to_first_prepare_ms"] = ab
        result["first_prepare_ab_reports"] = B2
        result["first_prepare_journaled_vs_synchronous"] = round(
            ab["synchronous"] / ab["journaled"], 2
        )
    except Exception as e:  # the opens/s + loadgen halves still record
        result["ingest_ab_skipped"] = f"{type(e).__name__}: {str(e)[:200]}"
    return result


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch", type=int, default=16384)
    parser.add_argument("--iters", type=int, default=8)
    parser.add_argument("--pipeline-depth", type=int, default=96)
    parser.add_argument(
        "--config",
        default="all",
        choices=["all"]
        + list(CONFIGS)
        + [
            "executor16",
            "accum16",
            "mesh8",
            "coldtask",
            "poplar1_hh",
            "upload_frontdoor",
            "fpvec",
        ],
        help="one config, or 'all' for every BASELINE.md row (default); "
        "executor16 is the device-executor concurrent-task row, accum16 "
        "the same shape with the device-resident accumulator store, "
        "mesh8 the SPMD multi-chip prepare over every local device, "
        "coldtask the shape-churn row (cold task joins a busy fleet: "
        "canonical buckets + background warmup vs exact-shape compile), "
        "poplar1_hh the heavy-hitters row (Poplar1 jobs coalescing at one "
        "IDPF level through the executor vs the legacy per-job path), "
        "upload_frontdoor the front-door row (batched vs inline HPKE "
        "opens/s + an in-process loadgen pass at SLO), "
        "fpvec the gradient-aggregation row (fixed-point bounded-L2 "
        "vector sum through the multi-gadget device plane vs the CPU "
        "oracle, parity-fenced)",
    )
    parser.add_argument(
        "--side",
        default="both",
        choices=["helper", "leader", "both"],
        help="which aggregator's prepare to measure (default: both — the "
        "reference accelerates both halves of the protocol)",
    )
    args = parser.parse_args()

    import jax

    from janus_tpu.utils.jax_setup import enable_compile_cache

    enable_compile_cache()

    # This benchmark measures the accelerator.  With no chip it fails: a
    # timing of XLA:CPU is not a speed of this system, and is never
    # printed under a device's name.
    platform = jax.devices()[0].platform
    if platform == "cpu":
        sys.stderr.write(
            "bench.py: JAX found no accelerator (jax.devices()[0].platform == "
            "'cpu'); nothing measured\n"
        )
        return 2

    names = DEFAULT_SET if args.config == "all" else [args.config]
    results = {}
    run_executor_row = args.config in ("all", "executor16")
    run_accum_row = args.config in ("all", "accum16")
    run_mesh_row = args.config in ("all", "mesh8")
    run_coldtask_row = args.config in ("all", "coldtask")
    run_poplar_row = args.config in ("all", "poplar1_hh")
    run_frontdoor_row = args.config in ("all", "upload_frontdoor")
    run_fpvec_row = args.config in ("all", "fpvec")
    names = [
        n
        for n in names
        if n
        not in (
            "executor16",
            "accum16",
            "mesh8",
            "coldtask",
            "poplar1_hh",
            "upload_frontdoor",
            "fpvec",
        )
    ]
    # Leader-side rows for the configs whose explicit-share inputs are
    # modest; sumvec100k's leader would stage ~1.6 GB of host limbs per
    # input, and multitask16's leader is histogram1024's.
    leader_ok = {"count", "sum32", "histogram1024", "sumvec"}
    for name in names:
        sides = ("helper",)
        if args.side == "leader":
            sides = ("leader",)
        elif args.side == "both":
            sides = ("helper", "leader") if name in leader_ok else ("helper",)
        for side in sides:
            key = name if side == "helper" else f"{name}_leader"
            try:
                results[key] = run_config(name, args, side=side)
            except Exception as e:  # never lose completed configs to one failure
                _record_row_failure(results, key, e)
        if name in MXU_AB_ROWS:
            # Sibling row under the MXU field layout (ISSUE 7): same shape,
            # same methodology, field_backend="mxu", per-row parity assert —
            # the recorded MXU-vs-VPU delta.
            key = f"{name}_mxu"
            try:
                results[key] = run_config(
                    name, args, side="helper", field_backend="mxu"
                )
            except Exception as e:
                _record_row_failure(results, key, e)

    if run_executor_row:
        # The device-executor concurrent-task row (BASELINE configs[5]
        # proxy): cross-job coalescing measured end-to-end.
        try:
            results["executor16"] = run_executor_config(args, scaled=False)
        except Exception as e:
            _record_row_failure(results, "executor16", e)
    if run_accum_row:
        # Same shape with device-resident accumulation: aggregate
        # reports/s + resident-vs-readback flush bytes (ISSUE 3).
        try:
            results["accum16"] = run_accumulator_config(args, scaled=False)
        except Exception as e:
            _record_row_failure(results, "accum16", e)
    if run_mesh_row:
        # SPMD multi-chip prepare (ISSUE 6): histogram1024 sharded over
        # every local device, per-chip efficiency vs single chip, sharded
        # accumulation drained through ONE all-reduce, oracle parity.
        try:
            results["mesh8"] = run_mesh_config(args, scaled=False)
        except Exception as e:
            _record_row_failure(results, "mesh8", e)
    if run_coldtask_row:
        # Shape-churn survival (ISSUE 8): a cold task joining a busy
        # fleet — p99 first-flush under canonical buckets + background
        # warmup vs the exact-shape compile-inline before.
        try:
            results["coldtask"] = run_coldtask_config(args, scaled=False)
        except Exception as e:
            _record_row_failure(results, "coldtask", e)
    if run_poplar_row:
        # Heavy hitters through the executor (ISSUE 10): level-coalesced
        # Poplar1 prep vs the legacy per-job path, oracle-parity gated;
        # a mid-run platform loss records the structured skip like every
        # other row (the sketch launch is the row's only device work).
        try:
            results["poplar1_hh"] = run_poplar_config(args, scaled=False)
        except Exception as e:
            _record_row_failure(results, "poplar1_hh", e)
    if run_frontdoor_row:
        # Upload front door (ISSUE 14): batched vs inline HPKE opens/s
        # (parity-fenced) + loadgen reports/s with the SLO judge green;
        # environmental failures record the structured skip like every
        # other row.
        try:
            results["upload_frontdoor"] = run_upload_frontdoor_config(
                args, scaled=False
            )
        except Exception as e:
            _record_row_failure(results, "upload_frontdoor", e)
    if run_fpvec_row:
        # Gradient aggregation (ISSUE 15): fpvec device-vs-oracle
        # reports/s, parity-fenced; platform loss records the structured
        # skip like every other row.
        try:
            results["fpvec"] = run_fpvec_config(args, scaled=False)
        except Exception as e:
            _record_row_failure(results, "fpvec", e)

    # Headline: the north-star config when measured, else the first row
    # that produced a number (a skipped/errored headline must not zero out
    # an otherwise-valid run).
    candidates = ["histogram1024", "histogram1024_leader", "count", "executor16"]
    candidates += [k for k in results if k not in candidates]
    headline = next(
        (k for k in candidates if "value" in results.get(k, {})), None
    )
    if headline is None:
        headline = next(iter(results))
    head = results[headline]
    reports_per_sec = head.get("value", 0.0)

    # Device calibration: effective HBM bandwidth via a pure elementwise
    # pass (read + write = 2 x 64 MB moved, negligible compute), to read
    # vs_baseline against (a v5e's HBM is specified at 819 GB/s).
    import numpy as np

    device_gbps = None
    try:  # never lose the completed measurement to a probe failure
        x = jax.device_put(np.zeros((4096, 4096), dtype=np.uint32))
        xor1 = jax.jit(lambda a: a ^ np.uint32(1))
        jax.block_until_ready(xor1(x))
        best = float("inf")
        for _ in range(3):
            t0 = time.monotonic()
            outs = [xor1(x) for _ in range(8)]
            jax.block_until_ready(outs)
            np.asarray(outs[-1][:1, :4])
            best = min(best, (time.monotonic() - t0) / 8)
        device_gbps = (2 * x.nbytes) / best / 1e9
    except Exception as e:  # pragma: no cover - probe is best-effort
        sys.stderr.write(f"bandwidth probe failed: {e}\n")
    print(
        json.dumps(
            {
                "metric": f"prepare_throughput_{headline}",
                "value": round(reports_per_sec, 1),
                "unit": head.get("unit", "reports/s"),
                "vs_baseline": round(reports_per_sec / 1_000_000, 4),
                "config": head.get("config"),
                "batch": head.get("batch"),
                "pipelined_ms_per_batch": head.get("pipelined_ms_per_batch"),
                "pipeline_depth": head.get("pipeline_depth"),
                "sync_p50_ms": head.get("sync_p50_ms"),
                "compile_s": head.get("compile_s"),
                "platform": platform,
                "device_eff_gbps": round(device_gbps, 2) if device_gbps else None,
                "iters": args.iters,
                "configs": results,
            }
        )
    )
    # Nonzero exit when the headline config produced no measurement or any
    # row failed (a row that loses the device is an error), so a harness
    # gating on the exit code cannot publish an errored run.
    failed = [k for k, v in results.items() if "error" in v]
    return 0 if "value" in head and not failed else 1


if __name__ == "__main__":
    sys.exit(main())
