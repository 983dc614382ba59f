"""Test configuration: tests run on the CPU with eight virtual devices.

The sandbox that runs them has no accelerator, and the mesh tests need
several devices (mirrors how the reference tests multi-node without a
cluster — SURVEY.md §4).  What runs on the chip is ``chip_smoke.py``; what
is compiled for it without one is tests/test_chip_compile.py.
"""

import os

# Pin the CPU before JAX is imported: a test run must never take the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import pytest


#: XLA-compile-bound modules — the heavy tier.  ci.sh runs the fast tier
#: (everything else, <2 min warm) on every change and this tier separately,
#: so red artifacts can't ship because the full suite "didn't fit" in a
#: budget (VERDICT r3 weak #7).
DEVICE_TIER_MODULES = {
    "test_prepare",
    "test_ops_field",
    "test_ops_keccak",
    "test_mesh",
    "test_mxu_field",
    "test_integration_pair",
    "test_backend",
    "test_poplar1_batch",
    "test_shape_canonical",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy device-parity cases; run with RUN_SLOW=1 "
        "(one representative per family stays in the default suite)",
    )
    config.addinivalue_line(
        "markers",
        "device: XLA-compile-bound device-path tests (heavy CI tier; "
        "select with -m device, deselect with -m 'not device')",
    )


@pytest.fixture(autouse=True)
def _clean_db_health():
    """The datastore health tracker is process-wide (core/db_health.py)
    and fed by EVERY run_tx: a test that storms tx faults (p=1 begin
    errors) would otherwise leak a suspect verdict into the next test's
    fleet router / upload front door.  Resetting is just zeroing a
    struct — cheap enough to do around every test."""
    from janus_tpu.core.db_health import reset_db_health, tracker

    reset_db_health()
    tracker().configure(failure_threshold=3, suspect_dwell_s=5.0)
    yield
    reset_db_health()


def pytest_collection_modifyitems(config, items):
    run_slow = os.environ.get("RUN_SLOW")
    skip = pytest.mark.skip(reason="slow; set RUN_SLOW=1 to run")
    for item in items:
        if item.module.__name__.rpartition(".")[2] in DEVICE_TIER_MODULES:
            item.add_marker(pytest.mark.device)
        if not run_slow and "slow" in item.keywords:
            item.add_marker(skip)
