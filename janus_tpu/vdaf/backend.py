"""Execution backends for Prio3 preparation: CPU oracle vs batched TPU.

This is the real dispatch seam the reference expresses as ``vdaf_dispatch!`` /
``VdafOps`` (reference: core/src/vdaf.rs:516-532,
aggregator/src/aggregator.rs:1168-1340): one switch routes a whole aggregation
job's prepare work either through the scalar oracle (janus_tpu.vdaf.prio3) or
through one jitted device launch (janus_tpu.ops.prepare), with identical
results — the agreement is asserted in tests/test_backend.py.

Both backends speak oracle-level types (Prio3InputShare / Prio3PrepareShare /
Prio3PrepareState), so role logic above the seam is backend-agnostic.  The
device backend pads batches to power-of-two buckets to bound recompilation,
and falls back to the oracle for any row whose XOF rejection-sampling margin
overflowed (``ok`` mask — astronomically rare, but exact).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..core import faults
from ..core.trace import emit_span, trace_phase
from ..fields import next_power_of_2
from ..xof import XofTurboShake128
from .prio3 import (
    Prio3,
    Prio3InputShare,
    Prio3PrepareShare,
    Prio3PrepareState,
    VdafError,
)

#: A per-report prepare outcome: either a result or the error that rejected it.
PrepOutcome = Union[Tuple[Prio3PrepareState, Prio3PrepareShare], VdafError]


@dataclass
class StagedPrepInit:
    """Device-resident half of a prepare launch.

    Produced by ``TpuBackend.stage_prep_init_multi`` (host marshal +
    device_put), consumed by ``launch_prep_init_multi`` (compiled launch +
    readback).  The split lets the device executor double-buffer: batch
    k+1 stages on the host while batch k's launch occupies the chip.
    """

    agg_id: int
    placed: Dict[str, object]
    #: padded batch size the compiled executable was (or will be) built for
    pad_to: int
    #: real rows in the batch (readbacks slice to this)
    rows: int


def _observe_prepare(backend: str, phase: str, reports: int, seconds: float) -> None:
    """Per-backend steady-state throughput/latency metrics (VERDICT r4 #6).

    Also the oracle-path COST ATTRIBUTION hook (ISSUE 12): when the
    calling thread carries a task scope (core/costs.run_in_task_scope —
    the drivers and the helper bind it around oracle fallbacks and direct
    backend batches), the same measured duration lands on
    ``janus_task_device_seconds_total{task,phase,path}``, path derived
    from the backend name — so an open breaker's cost shift to the CPU
    oracle is visible per task.  Conservation is exact by construction:
    one measurement, observed once here and attributed once there."""
    from ..core import costs
    from ..core.metrics import GLOBAL_METRICS

    if GLOBAL_METRICS.registry is not None:
        GLOBAL_METRICS.observe_prepare(backend, phase, reports, seconds)
    costs.attribute_prepare(backend, phase, seconds)


def _observe_launch(backend: str, phase: str, reports: int, first, last) -> None:
    """One device launch, from the stamps of the phases that timed it
    (``first.start`` .. ``last.end``): the ``prep_launch`` span (a stage
    of ``tools/trace_merge.py --stats``) and :func:`_observe_prepare`."""
    seconds = last.end - first.start
    emit_span(
        "prep_launch", "device", first.start, seconds,
        backend=backend, batch=reports, ok=True,
    )
    _observe_prepare(backend, phase, reports, seconds)


class OracleBackend:
    """Scalar per-report loop — the analog of the reference's rayon hop
    (reference: aggregator/src/aggregator.rs:2101)."""

    name = "oracle"

    def __init__(self, vdaf: Prio3):
        self.vdaf = vdaf

    def prep_init_batch(
        self,
        verify_key: bytes,
        agg_id: int,
        reports: Sequence[Tuple[bytes, Optional[List[bytes]], Prio3InputShare]],
    ) -> List[PrepOutcome]:
        t0 = time.monotonic()
        out: List[PrepOutcome] = []
        for nonce, public_share, input_share in reports:
            try:
                out.append(
                    self.vdaf.prep_init(verify_key, agg_id, nonce, public_share, input_share)
                )
            except VdafError as e:
                out.append(e)
        _observe_prepare(self.name, "init", len(out), time.monotonic() - t0)
        return out

    def prep_shares_to_prep_batch(
        self, prep_shares: Sequence[Sequence[Prio3PrepareShare]]
    ) -> List[Union[Optional[bytes], VdafError]]:
        t0 = time.monotonic()
        out: List[Union[Optional[bytes], VdafError]] = []
        for shares in prep_shares:
            try:
                out.append(self.vdaf.prep_shares_to_prep(shares))
            except VdafError as e:
                out.append(e)
        _observe_prepare(self.name, "combine", len(out), time.monotonic() - t0)
        return out


#: Field-arithmetic layouts for the device backends (ops/prepare.py):
#: "vpu" = scalar-lane CIOS multiply chains + limb-planar Pallas kernels;
#: "mxu" = limb-plane dot_general contractions (JField.mat_mul_mont) so the
#: FLP wire/gadget math runs on the matrix units.  Bit-exact either way —
#: the CPU oracle stays the correctness fence for both.
FIELD_BACKENDS = ("vpu", "mxu")


def default_field_backend() -> str:
    """Process default, overridable via JANUS_TPU_FIELD_BACKEND (the A/B
    knob for bench runs that don't thread a config file)."""
    import os

    return os.environ.get("JANUS_TPU_FIELD_BACKEND", "vpu")


def _resolve_field_backend(field_backend: Optional[str]) -> str:
    fb = field_backend or default_field_backend()
    if fb not in FIELD_BACKENDS:
        raise VdafError(f"unknown field_backend {fb!r}")
    return fb


def _req_parts(req):
    """A prepare request is ``(verify_key, reports)`` or — on a CANONICAL
    backend (vdaf/canonical.py) — ``(verify_key, reports, actual_vdaf)``,
    the third element naming the task's true (unpadded) VDAF so marshal
    can pad its rows to the bucket shape and unmarshal can slice back."""
    return req[0], req[1], (req[2] if len(req) > 2 else None)


def oracle_backend_for(backend, vdaf):
    """The bit-exact CPU oracle for serving ``vdaf``'s reports when
    ``backend`` cannot (circuit open, executable warming, replay).  The
    single chokepoint for canonical routing: a canonical backend's own
    ``.oracle`` computes the bucket twin's padded circuit, so it must
    resolve through ``oracle_for(vdaf)``; plain backends fall back to
    their ``.oracle`` (or None when there is none)."""
    if hasattr(backend, "oracle_for"):
        return backend.oracle_for(vdaf)
    return getattr(backend, "oracle", None)


class TpuBackend:
    """Batched device prepare: one XLA launch per aggregation job."""

    name = "tpu"
    #: this backend can keep a flush's out shares resident on device and
    #: hand back ResidentRefs (executor/accumulator.py) instead of limbs
    supports_resident_out_shares = True
    #: leading-axis rows of an accumulator buffer (accumulate_rows):
    #: 1 on a single chip; the mesh backend keeps one partial-sum row PER
    #: DEVICE so the accumulator store can account resident bytes honestly
    accum_buffer_rows = 1

    def __init__(
        self,
        vdaf: Prio3,
        field_backend: Optional[str] = None,
        canonical: bool = False,
    ):
        if vdaf.xof is not XofTurboShake128:
            raise VdafError("TPU backend requires the TurboSHAKE XOF")
        import jax

        from ..ops.prepare import BatchedPrio3

        self.vdaf = vdaf
        #: CANONICAL mode (vdaf/canonical.py): ``vdaf`` is a bucket's
        #: padded twin shared by every task in the bucket.  Requests carry
        #: the task's actual vdaf (3-tuples), marshal pads measurement
        #: columns and emits the per-row ``meas_len_u32`` mask input, and
        #: the graphs run row-major (the planar Pallas kernels take no
        #: masks).  The graph SIGNATURE is mode-fixed, so one executable
        #: serves every task mix.
        self.canonical = canonical
        #: "vpu" | "mxu" — see FIELD_BACKENDS; carried so the executor's
        #: mesh upgrade (_meshify) preserves the layout choice.
        self.field_backend = _resolve_field_backend(field_backend)
        self.bp = BatchedPrio3(vdaf, field_backend=self.field_backend)
        self.oracle = OracleBackend(vdaf)
        #: actual-shape oracles for canonical-mode fallback rows, keyed by
        #: vdaf_shape_key (a row that overflowed the device margin must be
        #: recomputed by ITS task's oracle, not the bucket twin's)
        self._oracles: Dict[tuple, OracleBackend] = {}
        self._jax = jax
        self._prep_fns: Dict[int, object] = {}
        self._combine_fn = None
        self._agg_fn = None
        self._accum_fn = None
        #: out-share rows transferred device->host by prepare launches —
        #: the flush-readback counter the accumulator acceptance tests
        #: assert stays 0 in the device-resident steady state
        self.outshare_readback_rows = 0

    def oracle_for(self, vdaf=None) -> OracleBackend:
        """The bit-exact CPU oracle for ``vdaf`` (None/own = this
        backend's).  Canonical-mode callers MUST route fallbacks through
        this — the bucket twin's oracle computes a different circuit."""
        if vdaf is None or vdaf is self.vdaf:
            return self.oracle
        key = vdaf_shape_key(vdaf)
        o = self._oracles.get(key)
        if o is None:
            o = self._oracles[key] = OracleBackend(vdaf)
        return o

    def _scope(self, tail: str) -> str:
        """This backend's own phase scope (``Histogram/a0/prep_init``,
        ``Histogram/aggregate``); inside an executor flush the bucket's
        label is bound on the thread and wins (core.trace.phase_scope)."""
        return f"{type(self.vdaf.flp.valid).__name__}/{tail}"

    # -- jit caches ------------------------------------------------------
    #: Gate for the limb-planar fast path.  Pallas custom calls do not
    #: partition under SHARDED jit, but MeshBackend routes its launches
    #: through shard_map (manual partitioning), where each chip runs the
    #: planar kernels on its own shard — so both backends keep this True;
    #: it remains a seam for environments whose compiler lacks the kernels.
    _planar_capable = True

    def _layout(self, agg_id: int, rows: int) -> str:
        """The device layout one device's ``rows``-row launch runs in."""
        if self.canonical:
            # canonical batches carry the per-row mask input and run
            # row-major only (the planar kernels take no masks)
            return "canonical-row-major"
        if self._planar_capable and self.bp.planar_eligible(agg_id, rows):
            return "planar"
        return "row-major"

    def launch_layout(self, agg_id: int, pad_to: int) -> str:
        """"planar" | "row-major" | "canonical-row-major": the layout a
        launch padded to ``pad_to`` rows runs in — what the flight
        recorder (and chip_smoke.py) report per flush."""
        return self._layout(agg_id, pad_to)

    def _prep(self, agg_id: int, kw):
        """One device's prepare program (traced).  verify_key flows as a
        traced input (it is per-task data), so one compilation per agg_id
        serves every task."""
        vk = kw.pop("verify_key_u8")
        B = kw["nonces_u8"].shape[0]
        if self._layout(agg_id, B) != "planar":
            return self.bp.prep_init(agg_id, verify_key=vk, **kw)
        # Limb-planar fast path (the bench pipeline), both sides: helpers
        # expand share seeds through the planar XOF, the leader transposes
        # its explicit shares in.  Outputs are identical; out_share
        # transposes back to row-major for the unmarshal/aggregate
        # interfaces.
        out = self.bp.prep_init_planar(
            agg_id,
            vk,
            kw["nonces_u8"],
            share_seeds_u8=kw.get("share_seeds_u8"),
            meas_limbs=kw.get("meas_limbs"),
            proofs_limbs=kw.get("proofs_limbs"),
            blinds_u8=kw.get("blinds_u8"),
            public_parts_u8=kw.get("public_parts_u8"),
        )
        return dict(
            out, out_share=self.bp.planar_out_share_to_rows(out["out_share"])
        )

    def _jit_per_device(self, per_device):
        """Compile a per-device program; the mesh backend shard_maps it."""
        return self._jax.jit(per_device)

    #: Whether this backend's programs go through the program store
    #: (vdaf/program_store.py).  The store takes single-device executables
    #: only: the mesh backend's shard_maps stay on plain jit.
    _stores_programs = True

    def _program(self, kind: str, agg_id: Optional[int], jitted):
        """One of the four program kinds (``prep_init``, ``combine``,
        ``aggregate``, ``accumulate``), called as ``jitted`` is: its executables come from the program store where
        the process has one (a compile cache directory, so never on
        XLA:CPU), else ``jitted`` itself, which traces on first call."""
        from . import program_store

        store = program_store.active_store() if self._stores_programs else None
        if store is None:
            return jitted
        return program_store.StoredProgram(store, kind, agg_id, self, jitted)

    def _prep_fn(self, agg_id: int):
        fn = self._prep_fns.get(agg_id)
        if fn is None:
            fn = self._program(
                "prep_init", agg_id, self._jit_per_device(partial(self._prep, agg_id))
            )
            self._prep_fns[agg_id] = fn
        return fn

    def prep_program_source(self, staged: StagedPrepInit) -> Optional[str]:
        """Where the prepare executable that ran ``staged`` came from:
        "disk" | "memory" | "built"; None for a plain jit."""
        fn = self._prep_fn(staged.agg_id)
        return fn.source(staged.placed) if hasattr(fn, "source") else None

    def prep_program_sources(self) -> set:
        """The sources of every prepare executable this backend has run
        (empty on plain jit)."""
        return {
            source
            for fn in self._prep_fns.values()
            for source in getattr(fn, "sources", dict)().values()
        }

    def reject_prep_program(self, staged: StagedPrepInit) -> None:
        """The stored prepare executable for ``staged``'s shape gave a
        wrong answer: out of the store, and the next launch builds."""
        self._prep_fn(staged.agg_id).reject(staged.placed)

    def _combine(self):
        if self._combine_fn is None:
            has_jr = self.vdaf.flp.JOINT_RAND_LEN > 0
            if has_jr:
                jitted = self._jax.jit(
                    lambda vs, parts: self.bp.prep_shares_to_prep(vs, parts)
                )
            else:
                jitted = self._jax.jit(
                    lambda vs, parts: self.bp.prep_shares_to_prep(vs)
                )
            self._combine_fn = self._program("combine", None, jitted)
        return self._combine_fn

    # -- marshaling ------------------------------------------------------
    def _marshal(
        self, agg_id, reports, pad_to: int, segments=None
    ) -> Dict[str, np.ndarray]:
        """``segments`` (canonical mode): ``[(rows, actual_meas_len)]``
        per contiguous same-task run of ``reports`` — leader measurement
        limbs land in the leading ``actual_meas_len`` columns of the
        bucket-width matrix (the pad columns STAY ZERO; the graph's mask
        and the select-absorb's pad construction both require it) and
        every row gets its ``meas_len_u32`` mask input."""
        vdaf, flp, jf = self.vdaf, self.vdaf.flp, self.bp.jf
        B = len(reports)
        seed_size = vdaf.xof.SEED_SIZE

        def stack_bytes(rows, width) -> np.ndarray:
            arr = np.frombuffer(b"".join(rows), dtype=np.uint8).reshape(B, width)
            return np.concatenate([arr, np.repeat(arr[-1:], pad_to - B, axis=0)])

        kw: Dict[str, np.ndarray] = {
            "nonces_u8": stack_bytes([r[0] for r in reports], vdaf.NONCE_SIZE)
        }
        if flp.JOINT_RAND_LEN > 0:
            kw["public_parts_u8"] = stack_bytes(
                [b"".join(r[1]) for r in reports], vdaf.num_shares * seed_size
            ).reshape(pad_to, vdaf.num_shares, seed_size)
            kw["blinds_u8"] = stack_bytes(
                [r[2].joint_rand_blind for r in reports], seed_size
            )
        if agg_id == 0:
            if segments is None:
                meas = jf.to_limbs(
                    [x for r in reports for x in r[2].meas_share]
                ).reshape(B, flp.MEAS_LEN, jf.n)
            else:
                meas = np.zeros((B, flp.MEAS_LEN, jf.n), dtype=np.uint32)
                limbs = jf.to_limbs([x for r in reports for x in r[2].meas_share])
                row = off = 0
                for rows, mlen in segments:
                    meas[row : row + rows, :mlen] = limbs[
                        off : off + rows * mlen
                    ].reshape(rows, mlen, jf.n)
                    row += rows
                    off += rows * mlen
            proofs = jf.to_limbs(
                [x for r in reports for x in r[2].proofs_share]
            ).reshape(B, flp.PROOF_LEN * vdaf.num_proofs, jf.n)
            kw["meas_limbs"] = np.concatenate(
                [meas, np.repeat(meas[-1:], pad_to - B, axis=0)]
            )
            kw["proofs_limbs"] = np.concatenate(
                [proofs, np.repeat(proofs[-1:], pad_to - B, axis=0)]
            )
        else:
            kw["share_seeds_u8"] = stack_bytes([r[2].share_seed for r in reports], seed_size)
        if segments is not None:
            lens = np.concatenate(
                [np.full(rows, mlen, dtype=np.uint32) for rows, mlen in segments]
            )
            kw["meas_len_u32"] = np.concatenate(
                [lens, np.repeat(lens[-1:], pad_to - B, axis=0)]
            )
        return kw

    # -- placement hooks (MeshBackend shards these over the device mesh) --
    def _pad_to(self, B: int) -> int:
        """Power-of-two bucketing bounds recompiles to log2 distinct shapes."""
        return next_power_of_2(B)

    def _align_pad(self, pad_to: int) -> int:
        """Final alignment applied to an explicitly requested pad (warmup's
        target mega-batch shape); the mesh backend rounds it up so the
        batch axis divides evenly across the mesh."""
        return pad_to

    def _place(self, kw: Dict[str, np.ndarray]) -> Dict[str, object]:
        """Commit marshaled inputs to device(s); identity on a single chip."""
        return kw

    def _place_batch(self, arr: np.ndarray):
        """Commit one batch-axis array to device(s)."""
        return arr

    # -- batch APIs ------------------------------------------------------
    def prep_init_batch(
        self,
        verify_key: bytes,
        agg_id: int,
        reports: Sequence[Tuple[bytes, Optional[List[bytes]], Prio3InputShare]],
    ) -> List[PrepOutcome]:
        """Single-task launch: the one-request form of prep_init_multi
        (same compiled graph — the verify key is a per-row traced input
        either way)."""
        if not reports:
            return []
        return self.prep_init_multi(agg_id, [(verify_key, reports)])[0]

    def _unmarshal_prep(
        self, verify_key, agg_id, reports, out, resident=None, actual_vdaf=None
    ) -> List[PrepOutcome]:
        """``resident=(flush_id, start_row)`` means the out-share matrix
        stayed on device (accumulator store): states carry ResidentRefs
        instead of limb vectors and no out-share bytes cross the PCIe.
        ``actual_vdaf`` (canonical mode) slices the bucket-width out share
        back to the task's OUTPUT_LEN — the pad tail is provably zero —
        and routes margin-overflow fallback rows to the TASK's oracle."""
        flp, jf = self.vdaf.flp, self.bp.jf
        out_len = (actual_vdaf or self.vdaf).flp.OUTPUT_LEN
        oracle = self.oracle_for(actual_vdaf)
        B = len(reports)
        ok = np.asarray(out["ok"])[:B]
        verifiers = jf.from_limbs(np.asarray(out["verifiers"])[:B])  # once a flush
        if resident is None:
            out_shares = jf.from_limbs(np.asarray(out["out_share"])[:B, :out_len])
        else:
            from ..executor.accumulator import ResidentRef

            flush_id, start_row = resident
        has_jr, ver_len = flp.JOINT_RAND_LEN > 0, flp.VERIFIER_LEN * self.vdaf.num_proofs
        if has_jr:
            parts = np.asarray(out["joint_rand_part"])[:B]
            corrected = np.asarray(out["corrected_seed"])[:B]

        results: List[PrepOutcome] = []
        for b in range(B):
            if not ok[b]:
                # Exact-path fallback: the device margin overflowed for this row.
                results.extend(
                    oracle.prep_init_batch(verify_key, agg_id, [reports[b]])
                )
                continue
            state = Prio3PrepareState(
                out_share=out_shares[b * out_len : (b + 1) * out_len]
                if resident is None
                else ResidentRef(flush_id, start_row + b),
                corrected_joint_rand_seed=corrected[b].tobytes() if has_jr else None,
            )
            share = Prio3PrepareShare(
                verifiers_share=verifiers[b * ver_len : (b + 1) * ver_len],
                joint_rand_part=parts[b].tobytes() if has_jr else None,
            )
            results.append((state, share))
        return results

    def prep_shares_to_prep_batch(
        self, prep_shares: Sequence[Sequence[Prio3PrepareShare]]
    ) -> List[Union[Optional[bytes], VdafError]]:
        if not prep_shares:
            return []
        faults.fire("backend.combine")
        vdaf, flp, jf = self.vdaf, self.vdaf.flp, self.bp.jf
        S = vdaf.num_shares
        # Rows with the wrong share count must fail exactly like the oracle
        # ("wrong number of prepare shares"), not be truncated or crash.
        bad_rows = {i for i, row in enumerate(prep_shares) if len(row) != S}
        if bad_rows:
            results = []
            good = [row for i, row in enumerate(prep_shares) if i not in bad_rows]
            good_iter = iter(self.prep_shares_to_prep_batch(good))
            for i in range(len(prep_shares)):
                if i in bad_rows:
                    results.append(VdafError("wrong number of prepare shares"))
                else:
                    results.append(next(good_iter))
            return results
        B = len(prep_shares)
        pad_to = self._pad_to(B)
        has_jr = flp.JOINT_RAND_LEN > 0
        scope = self._scope("combine")

        with trace_phase(scope, "marshal", "python", rows=B):
            ver_len = flp.VERIFIER_LEN * vdaf.num_proofs
            vs = []
            parts = []
            for a in range(S):
                limbs = jf.to_limbs(
                    [x for row in prep_shares for x in row[a].verifiers_share]
                ).reshape(B, ver_len, jf.n)
                vs.append(
                    self._place_batch(
                        np.concatenate(
                            [limbs, np.repeat(limbs[-1:], pad_to - B, axis=0)]
                        )
                    )
                )
                if has_jr:
                    arr = np.frombuffer(
                        b"".join(row[a].joint_rand_part for row in prep_shares),
                        dtype=np.uint8,
                    ).reshape(B, vdaf.xof.SEED_SIZE)
                    parts.append(
                        self._place_batch(
                            np.concatenate(
                                [arr, np.repeat(arr[-1:], pad_to - B, axis=0)]
                            )
                        )
                    )

        with trace_phase(scope, "dispatch", "python", rows=B) as dispatched:
            out = self._combine()(vs, parts)
        with trace_phase(scope, "readback", "device", rows=B) as read:
            decide = np.asarray(out["decide"])[:B]
            seeds = np.asarray(out["prep_msg_seed"])[:B] if has_jr else None
        _observe_prepare(self.name, "combine", B, read.end - dispatched.start)

        with trace_phase(scope, "unmarshal", "python", rows=B):
            results: List[Union[Optional[bytes], VdafError]] = []
            for b in range(B):
                if not decide[b]:
                    results.append(VdafError("proof verification failed"))
                elif has_jr:
                    results.append(seeds[b].tobytes())
                else:
                    results.append(None)
        return results

    def stage_prep_init_multi(
        self,
        agg_id: int,
        requests: Sequence[
            Tuple[bytes, Sequence[Tuple[bytes, Optional[List[bytes]], Prio3InputShare]]]
        ],
        pad_to: Optional[int] = None,
    ) -> Optional[StagedPrepInit]:
        """Host half of a multi-request launch: flatten, marshal, pow2-pad,
        and commit to device.  Returns None when no request carries rows.

        ``pad_to`` overrides the power-of-two bucket (the executor's warmup
        uses it to compile a target mega-batch shape from a handful of
        synthetic rows)."""
        B = sum(len(_req_parts(req)[1]) for req in requests)
        if not B:
            return None
        scope = self._scope(f"a{agg_id}/prep_init")
        pad_to = self._align_pad(max(pad_to or 0, self._pad_to(B)))
        with trace_phase(scope, "marshal", "python", rows=B):
            flat: List = []
            vk_rows: List[np.ndarray] = []
            segments: Optional[List] = [] if self.canonical else None
            for req in requests:
                verify_key, reports, actual = _req_parts(req)
                flat.extend(reports)
                vk = np.frombuffer(verify_key, dtype=np.uint8)
                vk_rows.extend([vk] * len(reports))
                if segments is not None and reports:
                    # a 2-tuple request (warmup's synthetic rows) is shaped
                    # for the canonical twin itself: its mask is the full
                    # width
                    mlen = (actual or self.vdaf).flp.MEAS_LEN
                    segments.append((len(reports), mlen))
            kw = self._marshal(agg_id, flat, pad_to, segments=segments)
            vk_mat = np.stack(vk_rows)
            kw["verify_key_u8"] = np.concatenate(
                [vk_mat, np.repeat(vk_mat[-1:], pad_to - B, axis=0)]
            )
        with trace_phase(scope, "place", "device", rows=B):
            placed = self._place(kw)
        return StagedPrepInit(agg_id=agg_id, placed=placed, pad_to=pad_to, rows=B)

    def launch_prep_init_multi(
        self,
        staged: StagedPrepInit,
        requests: Sequence[
            Tuple[bytes, Sequence[Tuple[bytes, Optional[List[bytes]], Prio3InputShare]]]
        ],
        retain_store=None,
    ) -> List[List[PrepOutcome]]:
        """Device half: run the compiled prepare on a staged batch, read
        back once, and slice results per request.

        ``retain_store`` (a DeviceAccumulatorStore) is the accumulate-into-
        buffer variant: the (pad, OUT, n) out-share matrix stays RESIDENT on
        device (adopted by the store) and each ok row's state carries a
        ResidentRef; only the small verdict outputs (ok / verifiers /
        joint-rand) are read back, so the flush pays zero out-share
        readback."""
        # Failure-domain boundary: an injected launch fault impersonates
        # XLA OOM / plugin loss; callers (executor breaker, driver retry
        # budget) must degrade gracefully.  The oracle has no such point —
        # it is the fallback truth.  backend.device_lost is the mesh-
        # flavored twin: a chip dropping out of the mesh mid-launch, which
        # the executor's per-MESH breaker must answer by opening the
        # circuit for EVERY mesh-backed shape (./ci.sh chaos exercises it).
        faults.fire("backend.launch")
        faults.fire("backend.device_lost")
        agg_id, B = staged.agg_id, staged.rows
        from ..core.metrics import GLOBAL_METRICS

        if GLOBAL_METRICS.registry is not None:
            GLOBAL_METRICS.device_launches.labels(backend=self.name).inc()
            GLOBAL_METRICS.device_reports.labels(backend=self.name).inc(B)
        scope = self._scope(f"a{agg_id}/prep_init")
        resident = None
        try:
            with trace_phase(scope, "dispatch", "python", rows=B) as dispatched:
                out = dict(self._prep_fn(agg_id)(staged.placed))
                if retain_store is not None:
                    matrix = out.pop("out_share")
                    nbytes = int(np.prod(matrix.shape)) * 4
                    flush_id = retain_store.retain_flush(self, matrix, B, nbytes)
                    resident = (flush_id, 0)
                else:
                    self.outshare_readback_rows += B
            # One readback for the whole launch, then slice per request.
            with trace_phase(scope, "readback", "device", rows=B) as read:
                outputs = {k: np.asarray(v)[:B] for k, v in out.items()}
            _observe_launch(self.name, "init", B, dispatched, read)
            with trace_phase(scope, "unmarshal", "python", rows=B):
                start = 0
                results: List[List[PrepOutcome]] = []
                for req in requests:
                    verify_key, reports, actual = _req_parts(req)
                    n = len(reports)
                    view = {k: v[start : start + n] for k, v in outputs.items()}
                    results.append(
                        self._unmarshal_prep(
                            verify_key,
                            agg_id,
                            reports,
                            view,
                            resident=None
                            if resident is None
                            else (resident[0], start),
                            actual_vdaf=actual,
                        )
                    )
                    start += n
        except Exception:
            if resident is not None:
                # a failure after the store adopted the matrix (verdict
                # readback, unmarshal) must not strand the flush: release
                # every row so it frees (release is idempotent)
                from ..executor.accumulator import ResidentRef

                retain_store.release_refs(
                    [ResidentRef(resident[0], r) for r in range(B)]
                )
            raise
        if resident is not None:
            # rows the oracle fallback served (device margin overflow)
            # never minted a ref; release them so the flush can free
            from ..executor.accumulator import ResidentRef

            ok_all = np.asarray(outputs["ok"])
            dead = [ResidentRef(resident[0], r) for r in range(B) if not ok_all[r]]
            if dead:
                retain_store.release_refs(dead)
        return results

    def prep_init_multi(
        self,
        agg_id: int,
        requests: Sequence[
            Tuple[bytes, Sequence[Tuple[bytes, Optional[List[bytes]], Prio3InputShare]]]
        ],
    ) -> List[List[PrepOutcome]]:
        """ONE device launch preparing reports from MULTIPLE tasks.

        ``requests``: (verify_key, reports) per task, all sharing this
        backend's VDAF shape.  The verify key is a traced per-ROW input, so
        the same compiled graph serves any task mix (BASELINE configs[4]'s
        16-concurrent-task shape on a single chip; the mesh backend shards
        the concatenated batch across chips).  Results are returned
        per-request, byte-identical to separate launches.
        """
        if not requests:
            return []
        staged = self.stage_prep_init_multi(agg_id, requests)
        if staged is None:
            return [[] for _ in requests]
        return self.launch_prep_init_multi(staged, requests)

    # -- device-resident accumulation (executor/accumulator.py) ----------
    def accumulate_rows(self, buffer, matrix, mask: np.ndarray):
        """Accumulate-into-buffer launch: psum the ``mask``-selected rows
        of a resident (pad, OUT, n) out-share matrix into ``buffer`` (an
        (OUT, n) limb accumulator; None starts one).  Pure device work —
        no readback; the result is the new resident buffer."""
        if self._accum_fn is None:
            jnp = self._jax.numpy
            jf = self.bp.jf

            def accum(buf, m, msk):
                masked = jnp.where(msk[:, None, None], m, jnp.zeros_like(m))
                delta = jf.sum(masked, axis=0)
                return jf.add(buf, delta)

            self._accum_fn = self._program("accumulate", None, self._jax.jit(accum))
        if buffer is None:
            jf = self.bp.jf
            buffer = np.zeros((self.vdaf.flp.OUTPUT_LEN, jf.n), dtype=np.uint32)
        with trace_phase(self._scope("accumulate"), "dispatch", "python"):
            return self._accum_fn(buffer, matrix, mask)

    def read_accum_buffer(self, buffer) -> List[int]:
        """Spill readback: ONE (OUT,) field vector — the commit-time drain."""
        with trace_phase(self._scope("accumulate"), "readback", "device"):
            limbs = np.asarray(buffer)
        return self.bp.jf.from_limbs(limbs)

    def aggregate_batch(self, out_shares_limbs, mask) -> List[int]:
        """Masked out-share aggregation on-device.

        out_shares_limbs (B, OUT, n) canonical, mask (B,) bool -> aggregate
        share as field integers.  On MeshBackend the inputs are sharded over
        the batch axis and the reduction crosses shard boundaries, so XLA
        lowers it to per-device partial sums + an all-reduce over the mesh —
        the collective replacing the reference's DB shard merge
        (reference: aggregator/src/aggregator/aggregation_job_writer.rs:591-698).
        """
        if self._agg_fn is None:
            self._agg_fn = self._program(
                "aggregate", None, self._jax.jit(self.bp.aggregate)
            )
        shares = np.asarray(out_shares_limbs)
        m = np.asarray(mask)
        B = shares.shape[0]
        pad_to = self._pad_to(B)
        if pad_to != B:  # zero rows masked False: no effect on the sum
            shares = np.concatenate(
                [shares, np.zeros((pad_to - B,) + shares.shape[1:], shares.dtype)]
            )
            m = np.concatenate([m, np.zeros(pad_to - B, dtype=bool)])
        scope = self._scope("aggregate")
        with trace_phase(scope, "dispatch", "python", rows=B):
            out = self._agg_fn(self._place_batch(shares), self._place_batch(m))
        with trace_phase(scope, "readback", "device", rows=B):
            out = np.asarray(out)
        return self.bp.jf.from_limbs(out)


class MeshBackend(TpuBackend):
    """SPMD batched prepare over a ``jax.sharding.Mesh``.

    The product form of the multi-chip path (not just the dryrun): every
    prepare / combine launch is sharded over the mesh's ``batch`` axis, so
    on a v5e-8 slice each chip prepares 1/8 of the job's reports, and
    ``aggregate_batch`` reduces out shares ACROSS chips on-device — XLA
    inserts the all-reduce over ICI for the sum along the sharded axis.
    This replaces the reference's write-contention DB shard merge
    (reference: aggregator/src/aggregator/aggregation_job_writer.rs:591-698)
    with a collective, exactly the psum re-design named in SURVEY §2.3 P4.

    Selected via the service config ``vdaf_backend: mesh``.  On a single
    device it degrades to TpuBackend behavior (mesh of 1).
    """

    name = "mesh"
    _stores_programs = False

    def __init__(
        self,
        vdaf: Prio3,
        devices=None,
        field_backend: Optional[str] = None,
        canonical: bool = False,
    ):
        super().__init__(vdaf, field_backend=field_backend, canonical=canonical)
        import os

        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        if devices is not None:
            devs = list(devices)
        elif os.environ.get("JANUS_TPU_MESH_SPAN", "local") == "global":
            # Multi-controller SPMD: ONLY sound when every process runs the
            # same launch sequence in lockstep (gang-scheduled deployments;
            # a lease-driven daemon must NOT set this — its launches are
            # per-replica and a cross-host collective would deadlock).
            devs = jax.devices()
        else:
            # Per-replica mesh over this host's chips (ICI); cross-host
            # scale-out is the N-replica shared-datastore model, exactly
            # the reference's deployment shape (docs/DEPLOYING.md:29-31).
            devs = jax.local_devices()
        self.mesh = Mesh(np.array(devs), ("batch",))
        self._batch_sharding = NamedSharding(self.mesh, PartitionSpec("batch"))
        self._replicated = NamedSharding(self.mesh, PartitionSpec())
        #: accumulator buffers keep one (OUT, n) partial-sum row per device
        self.accum_buffer_rows = len(devs)
        self._accum_read_fn = None

    # -- sharded launches -------------------------------------------------
    # prepare/combine run under shard_map (manual partitioning): each chip
    # executes the SAME per-shard program TpuBackend runs — including the
    # limb-planar Pallas kernels, which do not partition under sharded jit
    # but run fine per-shard — on its 1/N of the batch.  No cross-shard
    # dataflow exists in prepare, so out_specs are batch-sharded
    # everywhere; the cross-chip psum stays in aggregate_batch (sharded
    # jit, XLA inserts the all-reduce).  planar_eligible is evaluated on
    # the LOCAL (per-shard) batch during tracing, so planar engages exactly
    # when each chip's shard satisfies the kernels' tiling.

    def _jit_per_device(self, per_shard, n_args: int = 1):
        import jax
        from jax.sharding import PartitionSpec

        return jax.jit(
            jax.shard_map(
                per_shard,
                mesh=self.mesh,
                in_specs=(PartitionSpec("batch"),) * n_args,
                out_specs=PartitionSpec("batch"),
                check_vma=False,
            )
        )

    def launch_layout(self, agg_id: int, pad_to: int) -> str:
        return self._layout(agg_id, pad_to // len(self.mesh.devices))

    def _combine(self):
        if self._combine_fn is None:
            has_jr = self.vdaf.flp.JOINT_RAND_LEN > 0

            def per_shard(args):
                vs, parts = args
                return self.bp.prep_shares_to_prep(vs, parts if has_jr else None)

            wrapped = self._jit_per_device(per_shard)
            self._combine_fn = lambda vs, parts: wrapped((vs, parts))
        return self._combine_fn

    # The batch APIs are inherited: only padding and placement differ.
    def _pad_to(self, B: int) -> int:
        # Power-of-two bucketing (bounds recompiles) rounded up to a
        # MULTIPLE of the mesh size, so the batch axis divides evenly and
        # every shard sees the same local batch — the flush-tail guarantee
        # planar_eligible's per-shard tiling check relies on.  (For a
        # power-of-two mesh the pow2 pad is already a multiple; the
        # rounding matters on odd-sized meshes, e.g. after a chip is
        # cordoned out.)
        n = len(self.mesh.devices)
        return self._align_pad(max(next_power_of_2(B), n))

    def _align_pad(self, pad_to: int) -> int:
        n = len(self.mesh.devices)
        return -(-pad_to // n) * n

    def _place(self, kw: Dict[str, np.ndarray]) -> Dict[str, object]:
        """Commit per-report arrays shard-per-device.

        Every marshaled array — including verify_key_u8, which
        prep_init_multi expands to one row per report — has the batch as
        its leading axis, matching _jit_per_device's in_specs."""
        return {
            k: self._jax.device_put(v, self._batch_sharding) for k, v in kw.items()
        }

    def _place_batch(self, arr: np.ndarray):
        return self._jax.device_put(arr, self._batch_sharding)

    # -- sharded device-resident accumulation -----------------------------
    # The accumulator store's per-bucket buffers stay SHARDED: one
    # (OUT, n) partial-sum row per device, batch-sharded over the mesh.
    # accumulate_rows is pure per-shard work (each chip psums the
    # mask-selected rows of ITS shard of the retained out-share matrix
    # into ITS partial row — no collective, no readback), and the ONE
    # cross-chip reduction happens at drain/spill time in
    # read_accum_buffer, where XLA lowers the sum over the device-sharded
    # axis to an all-reduce.  Bucket placement decision: one bucket spans
    # the LOCAL mesh (the same ICI domain its flush matrices live on);
    # hashing buckets across meshes on multi-slice hosts stays a ROADMAP
    # item.

    def accumulate_rows(self, buffer, matrix, mask: np.ndarray):
        """Per-shard psum of the mask-selected rows of a batch-sharded
        (pad, OUT, n) out-share matrix into a (n_dev, OUT, n) sharded
        buffer (None starts one).  Zero cross-chip traffic."""
        if self._accum_fn is None:
            jnp = self._jax.numpy
            jf = self.bp.jf

            def per_shard(buf, m, msk):
                masked = jnp.where(msk[:, None, None], m, jnp.zeros_like(m))
                delta = jf.sum(masked, axis=0)
                return jf.add(buf, delta[None])

            self._accum_fn = self._jit_per_device(per_shard, n_args=3)
        if buffer is None:
            jf = self.bp.jf
            buffer = self._jax.device_put(
                np.zeros(
                    (len(self.mesh.devices), self.vdaf.flp.OUTPUT_LEN, jf.n),
                    dtype=np.uint32,
                ),
                self._batch_sharding,
            )
        with trace_phase(self._scope("accumulate"), "dispatch", "python"):
            return self._accum_fn(buffer, matrix, np.asarray(mask))

    def read_accum_buffer(self, buffer) -> List[int]:
        """Spill readback: the one point where the accumulated shards
        cross chips — a modular tree-sum over the device-sharded leading
        axis (XLA inserts the all-reduce), then ONE (OUT,) vector to the
        host.  (A raw integer psum over u32 limb arrays would be wrong —
        the carry chain must run inside the modular sum.)"""
        if self._accum_read_fn is None:
            jf = self.bp.jf
            self._accum_read_fn = self._jax.jit(lambda b: jf.sum(b, axis=0))
        with trace_phase(self._scope("accumulate"), "readback", "device"):
            limbs = np.asarray(self._accum_read_fn(buffer))
        return self.bp.jf.from_limbs(limbs)


class HybridXofBackend:
    """Host-XOF + device-FLP hybrid for non-TurboSHAKE Prio3 instances.

    The HMAC-SHA256-AES128 multiproof VDAF (reference:
    core/src/vdaf.rs:178-195) keeps its XOF on the host — HMAC/AES have no
    TPU kernels worth writing, and the multiproof circuits' XOF volume is
    tiny — while the FLP queries (num_proofs of them) and the decide run
    as one batched device launch (BatchedPrio3.query_batch/decide_batch).
    Byte parity with the oracle is the same contract as TpuBackend's
    (tests/test_backend.py)."""

    name = "tpu-hybrid"

    def __init__(self, vdaf: Prio3, field_backend: Optional[str] = None):
        import jax

        from ..ops.prepare import BatchedPrio3

        self.vdaf = vdaf
        self.field_backend = _resolve_field_backend(field_backend)
        self.bp = BatchedPrio3(
            vdaf, require_device_xof=False, field_backend=self.field_backend
        )
        self.oracle = OracleBackend(vdaf)
        self._jax = jax
        self._query_fn = None
        self._decide_fn = None

    def _pad_to(self, B: int) -> int:
        return next_power_of_2(B)

    def prep_init_batch(self, verify_key, agg_id, reports):
        if not reports:
            return []
        vdaf, flp, jf = self.vdaf, self.vdaf.flp, self.bp.jf
        t0 = time.monotonic()
        B = len(reports)
        has_jr = flp.JOINT_RAND_LEN > 0
        meas_rows: List[int] = []
        proof_rows: List[int] = []
        qr_rows: List[int] = []
        jr_rows: List[int] = []
        parts: List[Optional[bytes]] = []
        corrected: List[Optional[bytes]] = []
        for nonce, public_share, input_share in reports:
            # host XOF stage — mirrors Prio3.prep_init element for element
            if agg_id == 0:
                meas = input_share.meas_share
                proofs = input_share.proofs_share
            else:
                meas = vdaf._helper_meas_share(agg_id, input_share.share_seed)
                proofs = vdaf._helper_proofs_share(agg_id, input_share.share_seed)
            meas_rows.extend(meas)
            proof_rows.extend(proofs)
            qr_rows.extend(vdaf._query_rands(verify_key, nonce))
            if has_jr:
                part = vdaf._joint_rand_part(
                    agg_id, input_share.joint_rand_blind, meas, nonce
                )
                ps = list(public_share)
                ps[agg_id] = part
                cs = vdaf._joint_rand_seed(ps)
                jr_rows.extend(vdaf._joint_rands(cs))
                parts.append(part)
                corrected.append(cs)
            else:
                parts.append(None)
                corrected.append(None)

        pad_to = self._pad_to(B)

        def limb_mat(vals, width):
            arr = jf.to_limbs(vals).reshape(B, width, jf.n)
            return np.concatenate([arr, np.repeat(arr[-1:], pad_to - B, axis=0)])

        meas_l = limb_mat(meas_rows, flp.MEAS_LEN)
        proofs_l = limb_mat(proof_rows, flp.PROOF_LEN * vdaf.num_proofs)
        qr_l = limb_mat(qr_rows, flp.QUERY_RAND_LEN * vdaf.num_proofs)
        jr_l = (
            limb_mat(jr_rows, flp.JOINT_RAND_LEN * vdaf.num_proofs)
            if has_jr
            else None
        )
        if self._query_fn is None:
            self._query_fn = self._jax.jit(self.bp.query_batch)
        out = self._query_fn(meas_l, proofs_l, jr_l, qr_l)
        ok = np.asarray(out["ok"])[:B]
        verifiers = jf.from_limbs(np.asarray(out["verifiers"])[:B])
        out_shares = jf.from_limbs(np.asarray(out["out_share"])[:B])
        ver_len, out_len = flp.VERIFIER_LEN * vdaf.num_proofs, flp.OUTPUT_LEN
        results: List[PrepOutcome] = []
        for b in range(B):
            if not ok[b]:
                # Per-row oracle rescue is an INTERNAL detail of this
                # device batch: the enclosing _observe_prepare below
                # already spans it, so the nested oracle call must not
                # ALSO attribute its slice to the task's cost scope (the
                # conservation invariant is one measurement, attributed
                # once) — clear the scope around the rescue.
                from ..core import costs

                results.extend(
                    costs.run_in_task_scope(
                        None,
                        lambda b=b: self.oracle.prep_init_batch(
                            verify_key, agg_id, [reports[b]]
                        ),
                    )
                )
                continue
            state = Prio3PrepareState(
                out_share=out_shares[b * out_len : (b + 1) * out_len],
                corrected_joint_rand_seed=corrected[b],
            )
            share = Prio3PrepareShare(
                verifiers_share=verifiers[b * ver_len : (b + 1) * ver_len],
                joint_rand_part=parts[b],
            )
            results.append((state, share))
        _observe_prepare(self.name, "init", B, time.monotonic() - t0)
        return results

    def prep_shares_to_prep_batch(self, prep_shares):
        if not prep_shares:
            return []
        vdaf, flp, jf = self.vdaf, self.vdaf.flp, self.bp.jf
        t0 = time.monotonic()
        S = vdaf.num_shares
        bad_rows = {i for i, row in enumerate(prep_shares) if len(row) != S}
        if bad_rows:
            results = []
            good = [row for i, row in enumerate(prep_shares) if i not in bad_rows]
            good_iter = iter(self.prep_shares_to_prep_batch(good))
            for i in range(len(prep_shares)):
                results.append(
                    VdafError("wrong number of prepare shares")
                    if i in bad_rows
                    else next(good_iter)
                )
            return results
        B = len(prep_shares)
        pad_to = self._pad_to(B)
        ver_len = flp.VERIFIER_LEN * vdaf.num_proofs
        acc_rows = [row[0].verifiers_share for row in prep_shares]
        for a in range(1, S):
            acc_rows = [
                flp.field.vec_add(prev, row[a].verifiers_share)
                for prev, row in zip(acc_rows, prep_shares)
            ]
        comb_l = jf.to_limbs([x for row in acc_rows for x in row]).reshape(
            B, ver_len, jf.n
        )
        comb_l = np.concatenate(
            [comb_l, np.repeat(comb_l[-1:], pad_to - B, axis=0)]
        )
        if self._decide_fn is None:
            self._decide_fn = self._jax.jit(self.bp.decide_batch)
        decide = np.asarray(self._decide_fn(comb_l))[:B]
        results = []
        has_jr = flp.JOINT_RAND_LEN > 0
        for b in range(B):
            if not decide[b]:
                results.append(VdafError("proof verification failed"))
            elif has_jr:
                results.append(
                    vdaf._joint_rand_seed(
                        [row.joint_rand_part for row in prep_shares[b]]
                    )
                )
            else:
                results.append(None)
        _observe_prepare(self.name, "combine", B, time.monotonic() - t0)
        return results


class Poplar1Oracle:
    """Scalar per-report Poplar1 prepare — the bit-exact CPU fallback the
    executor-routed heavy-hitters path degrades to (circuit open, journal
    replay), mirroring OracleBackend's role for Prio3."""

    name = "poplar1-oracle"

    def __init__(self, vdaf):
        self.vdaf = vdaf

    def prep_init_batch_poplar(self, verify_key, agg_id, agg_param, reports):
        t0 = time.monotonic()
        out = []
        for nonce, public_share, input_share in reports:
            try:
                out.append(
                    self.vdaf.prep_init(
                        verify_key, agg_id, agg_param, nonce, public_share, input_share
                    )
                )
            except VdafError as e:
                out.append(e)
        _observe_prepare(self.name, "init", len(out), time.monotonic() - t0)
        return out


class Poplar1Backend:
    """Batched prepare for Poplar1 (heavy hitters): bulk-AES IDPF tree walk
    on the host (AES-NI territory) + JField sketch inner products on the
    accelerator — see ops/poplar1_batch.py.  Exposed through the same
    dispatch seam as the Prio3 backends so the role logic stays
    VDAF-agnostic (reference: core/src/vdaf.rs:96 — Poplar1 rides the same
    accelerated dispatch as Prio3).  Through the device executor this
    backend serves the ``poplar_init`` submission kind: mega-batches whose
    bucket identity carries the aggregation parameter's tree LEVEL, so
    ping-pong rounds from different jobs at one IDPF level coalesce into
    one walk + one sketch launch (``prep_init_multi_poplar``)."""

    name = "poplar1-batch"

    def __init__(self, vdaf, poplar_backend: Optional[str] = None):
        from ..ops.poplar1_batch import BatchedPoplar1

        self.vdaf = vdaf
        #: AES-walk backend seam ("host" | "jax"; None = process default)
        self.bp = BatchedPoplar1(vdaf, poplar_backend=poplar_backend)
        #: bit-exact per-report CPU fallback (breaker open / replay), the
        #: same contract as the Prio3 backends' .oracle
        self.oracle = Poplar1Oracle(vdaf)

    @property
    def poplar_backend(self) -> str:
        return self.bp.walk_backend

    @property
    def supports_resident_sketch(self) -> bool:
        """Whether flushes may retain the sketch y matrices on device and
        hand back ResidentRefs: requires the jax walk (host-walked values
        are born in host memory — retaining them would be a readback in
        reverse)."""
        return self.bp.walk_backend == "jax"

    @property
    def sketch_readback_rows(self) -> int:
        """Device-walked rows whose y vectors were materialized to host
        (the acceptance counter: 0 on the device-resident path)."""
        return self.bp.sketch_readback_rows

    def oracle_for(self, vdaf=None) -> "Poplar1Oracle":
        """Uniform fallback-resolution face (oracle_backend_for): Poplar1
        backends are never canonicalized, so the answer is always this
        backend's own oracle."""
        return self.oracle

    def prep_init_batch_poplar(self, verify_key, agg_id, agg_param, reports):
        """Batched round-0 prep: per-report (state, share), oracle parity."""
        return self.prep_init_multi_poplar(
            agg_id, [(verify_key, agg_param, reports)]
        )[0]

    def stage_poplar_init_multi(self, agg_id, requests):
        """The WALK half of a poplar flush: bulk-AES IDPF eval per
        agg-param group, value shares staged (device-resident under the
        jax walk).  Runs on the executor's STAGING thread so walk k+1
        overlaps sketch launch k (the stage/launch double buffering).  A
        walk failure surfaces through the flush like a stage failure on
        the Prio3 path — the breaker counts it."""
        return self.bp.stage_init_multi(agg_id, requests)

    def launch_poplar_init_multi(self, staged, retain_store=None):
        """The SKETCH half: device inner products + state assembly over a
        staged walk.  The named fault points fire here so the per-shape
        circuit breaker (and chaos coverage) treats a sick sketch/walk
        path exactly like a sick XLA launch.  ``retain_store`` (the
        device accumulator store) adopts device-walked y matrices: states
        then carry ResidentRefs and the flush pays zero sketch readback."""
        faults.fire("backend.launch")
        faults.fire("backend.device_lost")
        rows = sum(len(r) for _p, _i, _c, _v, r, _w in staged.groups)
        from ..core.metrics import GLOBAL_METRICS

        if GLOBAL_METRICS.registry is not None:
            GLOBAL_METRICS.device_launches.labels(backend=self.name).inc()
            GLOBAL_METRICS.device_reports.labels(backend=self.name).inc(rows)
        with trace_phase("Poplar1/poplar_init", "launch", "device", rows=rows) as launched:
            out = self.bp.launch_init_multi(staged, retain_store=retain_store)
        _observe_launch(self.name, "init", rows, launched, launched)
        return out

    def prep_init_multi_poplar(self, agg_id, requests, retain_store=None):
        """ONE bulk-AES walk + sketch launch for rows from MULTIPLE jobs
        (``requests``: (verify_key, agg_param, reports) per submission —
        the executor's poplar_init flush form).  Composed from the
        stage/launch halves; direct (non-executor) callers pay them
        back-to-back."""
        return self.launch_poplar_init_multi(
            self.stage_poplar_init_multi(agg_id, requests),
            retain_store=retain_store,
        )


BACKENDS = {"oracle": OracleBackend, "tpu": TpuBackend, "mesh": MeshBackend}


def vdaf_shape_key(vdaf) -> tuple:
    """Key a VDAF by its FULL parameterization: tasks sharing it share one
    backend instance — and therefore one set of compiled device graphs
    (verify_key is a traced input, so one compilation serves every task).
    Every scalar circuit parameter participates — derived lengths alone
    are ambiguous (SumVec(length=100, bits=2) and SumVec(length=200,
    bits=1) share MEAS_LEN but not truncate/OUTPUT_LEN).  Shared by the
    driver and the helper aggregator so both sides of the protocol land in
    the same executor buckets and breaker domains."""
    flp = getattr(vdaf, "flp", None)
    valid = getattr(flp, "valid", None)
    circuit_params = None
    if valid is not None:
        circuit_params = tuple(
            sorted(
                (k, v if isinstance(v, (int, str, bool)) else getattr(v, "__name__", str(v)))
                for k, v in vars(valid).items()
                if not k.startswith("_") and not isinstance(v, (list, dict))
            )
        )
    return (
        type(vdaf).__name__,
        type(valid).__name__ if valid is not None else None,
        circuit_params,
        getattr(vdaf, "algorithm_id", None),
        getattr(vdaf, "num_shares", None),
        getattr(vdaf, "num_proofs", None),
        getattr(getattr(vdaf, "xof", None), "__name__", None),
        # FLP-less VDAFs parameterize outside a `valid` circuit: Poplar1's
        # whole shape is its input bit width (two Poplar1 tasks with
        # different `bits` must never share a backend, bucket, or breaker)
        getattr(vdaf, "bits", None) if valid is None else None,
    )


# Circuits with a device twin in ops/prepare.py _device_circuit.  Kept as a
# name set so capability checks (driver dispatch, provisioning warnings) do
# NOT import the jax-backed kernels — a control-plane process must be able
# to classify a VDAF without pulling in jax.  tests/test_backend_fallback.py
# asserts this set matches _device_circuit's dispatch table.
# FixedPointBoundedL2VecSum (ISSUE 15) rides the multi-gadget device plane:
# every TurboSHAKE Prio3 family now has a device arm — there is no
# oracle-only Prio3 family left.
DEVICE_CIRCUITS = {"Count", "Sum", "SumVec", "Histogram", "FixedPointBoundedL2VecSum"}


def device_supported(vdaf) -> Tuple[bool, str]:
    """Whether the device (tpu/mesh) prepare path serves this VDAF.

    Returns (ok, reason).  Used to make oracle fallback LOUD: a task whose
    VDAF silently ran ~100x slower than the flagship path was VERDICT r3
    weak #3 (reference analog: every VdafInstance monomorphizes onto the
    same rayon path, core/src/vdaf.rs:178-195 — there is no silent tier
    split to begin with).  jax-free by design.
    """
    if not isinstance(vdaf, Prio3):
        if type(vdaf).__name__ == "Poplar1":
            return True, ""  # batched host-AES + device-sketch path
        return False, f"{type(vdaf).__name__} is not a Prio3 VDAF"
    circuit = type(vdaf.flp.valid).__name__
    if circuit not in DEVICE_CIRCUITS:
        return False, f"no device circuit for {circuit}"
    # Non-TurboSHAKE XOFs (HMAC multiproof) ride the hybrid backend: host
    # XOF, device FLP query/decide (HybridXofBackend).
    return True, ""


def device_path_label(vdaf) -> str:
    """Human-readable routing status for provisioning surfaces (task-API
    responses, startup logs): WHICH accelerated path serves this VDAF and
    which executor submission plane it batches through.  Poplar1 used to
    read as a silent "supported" while actually riding a per-job path
    outside the executor — this label makes the tier explicit, and names
    the oracle reason when there is no device path at all.  jax-free."""
    ok, reason = device_supported(vdaf)
    if not ok:
        return f"cpu-oracle ({reason})"
    if type(vdaf).__name__ == "Poplar1":
        return (
            "poplar1-batch: bulk-AES IDPF walk + device sketch, "
            "executor kind=poplar_init (agg-param/level-keyed buckets)"
        )
    if isinstance(vdaf, Prio3) and vdaf.xof is not XofTurboShake128:
        return "tpu-hybrid: host XOF + device FLP, executor kind=prep_init/combine"
    if type(getattr(vdaf.flp, "valid", None)).__name__ == "FixedPointBoundedL2VecSum":
        return (
            "tpu: multi-gadget batched device prepare (gradient "
            "aggregation), executor kind=prep_init/combine"
        )
    return "tpu: batched device prepare, executor kind=prep_init/combine"


def make_backend(
    vdaf,
    backend: str = "oracle",
    field_backend: Optional[str] = None,
    canonical: bool = False,
    poplar_backend: Optional[str] = None,
):
    """Backend factory — the dispatch gate named in the north star.

    ``field_backend`` ("vpu" | "mxu", None = JANUS_TPU_FIELD_BACKEND or
    "vpu") selects the device backends' field-arithmetic layout; the
    oracle and Poplar1 paths have no device field layer and ignore it.
    ``poplar_backend`` ("host" | "jax", None = JANUS_TPU_POPLAR_BACKEND
    or "host") selects the Poplar1 AES-walk backend; only the Poplar1
    path reads it.  ``canonical`` marks ``vdaf`` as a bucket's padded
    twin (vdaf/canonical.py) — device backends then expect 3-tuple
    requests and emit the per-row mask input; only device Prio3 backends
    honor it (the oracle/hybrid/Poplar1 paths are never canonicalized).
    """
    try:
        cls = BACKENDS[backend]
    except KeyError:
        raise VdafError(f"unknown backend {backend!r}")
    if backend != "oracle" and type(vdaf).__name__ == "Poplar1":
        # Heavy hitters: the device configs route Poplar1 through the
        # batched AES/sketch path instead of the Prio3-shaped backends.
        return Poplar1Backend(vdaf, poplar_backend=poplar_backend)
    if (
        backend != "oracle"
        and isinstance(vdaf, Prio3)
        and vdaf.xof is not XofTurboShake128
    ):
        # Host-XOF VDAFs (HMAC multiproof): device FLP, host XOF.
        return HybridXofBackend(vdaf, field_backend=field_backend)
    if cls is OracleBackend:
        return cls(vdaf)
    return cls(vdaf, field_backend=field_backend, canonical=canonical)
