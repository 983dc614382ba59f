"""The SLO-judged load soak (ISSUE 14 tentpole, ``./ci.sh load``).

System throughput under traffic, not kernel throughput: a REAL fleet of
``_BOOT`` binaries (leader aggregator, helper aggregator, aggregation
job creator, aggregation job driver) serves sustained HTTP uploads from
``tools/loadgen.py`` running as its own process, and the PASS/FAIL judge
is the PR 9 SLO evaluator running inside the leader:

* phase 1 (target rate): every upload accepted, zero sheds, burn rates
  for ``upload_to_commit`` / ``commit_age`` published and breach-free;
* phase 2 (past the shed threshold): a second leader replica with a
  deliberately tiny front-door queue and a wedged open stage
  (``upload.open`` delay fault) sheds visibly — 503 + Retry-After,
  ``janus_upload_shed_total`` moving — while ADMITTED reports keep their
  commit-age SLO green;
* settlement: every admitted report (and nothing else) aggregates and
  collects exactly once, and the loadgen-minted sampled upload traces
  stitch a COMPLETE upload -> commit -> flush -> collection critical
  path across the binaries via ``tools/trace_merge.py --stats``.

The fast variant (not slow-marked) runs the loadgen loop programmatically
against an in-process aggregator app — the scaled-down smoke that rides
the fast tier.
"""

from __future__ import annotations

import asyncio
import base64
import json
import os
import pathlib
import signal
import socket
import sqlite3
import subprocess
import sys
import time
import urllib.request

import pytest

from janus_tpu.core.auth_tokens import AuthenticationToken
from janus_tpu.core.hpke import HpkeApplicationInfo, HpkeKeypair, Label, open_
from janus_tpu.core.time import RealClock
from janus_tpu.datastore import (
    AggregatorTask,
    Crypter,
    Datastore,
    TaskQueryType,
    generate_key,
)
from janus_tpu.messages import Duration, Interval, Role, TaskId, Time

REPO = pathlib.Path(__file__).resolve().parents[1]
TIME_PRECISION = Duration(3600)

_BOOT = (
    "import os, sys;"
    "os.environ['JAX_PLATFORMS'] = 'cpu';"
    "from janus_tpu.binaries.main import main;"
    "sys.exit(main(sys.argv[1:]))"
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_http(url: str, deadline_s: float) -> None:
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=2):
                return
        except Exception:
            time.sleep(0.25)
    raise TimeoutError(f"{url} never came up")


def _scrape(port: int) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
        return r.read().decode()


def _metric_value(text: str, prefix: str):
    for line in text.splitlines():
        if line.startswith(prefix) and not line.startswith("#"):
            try:
                return float(line.rsplit(" ", 1)[1])
            except ValueError:
                pass
    return None


def _metric_total(text: str, name: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            try:
                total += float(line.rsplit(" ", 1)[1])
            except ValueError:
                pass
    return total


def _sql(path: str, query: str):
    conn = sqlite3.connect(path, timeout=10.0)
    try:
        return conn.execute(query).fetchall()
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# fast variant: the loadgen loop against an in-process app


def test_loadgen_fast_smoke():
    """Scaled-down load pass (the ``./ci.sh load fast`` shape): the
    programmatic loadgen sustains a small open-loop rate against an
    in-process leader and classifies every outcome."""
    from aiohttp.test_utils import TestClient, TestServer

    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.http_handlers import aggregator_app
    from janus_tpu.core.time import MockClock
    from janus_tpu.datastore.test_util import EphemeralDatastore

    sys.path.insert(0, str(REPO / "tools"))
    from loadgen import run_load

    from test_aggregator_handlers import NOW, make_pair_tasks

    leader, _helper, _ = make_pair_tasks({"type": "Prio3Count"})
    eds = EphemeralDatastore(MockClock(NOW))
    eds.datastore.run_tx("put", lambda tx: tx.put_aggregator_task(leader))
    agg = Aggregator(
        eds.datastore,
        eds.clock,
        Config(vdaf_backend="oracle", upload_open_backend="batched"),
    )

    async def flow():
        client = TestClient(TestServer(aggregator_app(agg)))
        await client.start_server()
        try:
            url = str(client.make_url("/")).rstrip("/")
            return await run_load(
                url,
                leader.task_id,
                {"type": "Prio3Count"},
                rate=30,
                duration_s=3.0,
                ramp_s=0.5,
                concurrency=16,
                trace_sample=5,
                now_fn=lambda: NOW,
            )
        finally:
            await client.close()

    summary = asyncio.new_event_loop().run_until_complete(flow())
    # floors sized for a STARVED host (tier-1 runs this beside device
    # compiles on shared cores): the open loop must still have flowed
    assert summary["sent"] >= 8, summary
    assert summary["outcomes"]["accepted"] == summary["sent"], summary
    assert summary["outcomes"]["shed"] == 0
    assert summary["achieved_rate"] > 2
    assert summary["latency_ms"]["p50"] is not None
    # bounded trace sampling: every 5th upload minted a traceparent
    assert 1 <= len(summary["trace_ids"]) <= summary["sent"] // 5 + 1
    # the sampled ids were ADOPTED by the leader (stored on the reports)
    whole = Interval(Time(0), Duration(NOW.seconds * 2))
    stored_traces = {
        r.trace_id
        for r in eds.datastore.run_tx(
            "rows",
            lambda tx: tx.get_client_reports_for_interval(
                leader.task_id, whole, 10_000
            ),
        )
    }
    assert set(summary["trace_ids"]) <= stored_traces
    eds.cleanup()


# ---------------------------------------------------------------------------
# THE SOAK


@pytest.mark.slow
def test_load_soak_slo_judged(tmp_path):
    from janus_tpu.core.trace import close_chrome_trace, configure_chrome_trace

    key = generate_key()
    leader_db = str(tmp_path / "leader.sqlite3")
    helper_db = str(tmp_path / "helper.sqlite3")
    clock = RealClock()
    leader_ds = Datastore(leader_db, Crypter([key]), clock)
    helper_ds = Datastore(helper_db, Crypter([key]), clock)

    helper_port = _free_port()
    leader_port = [_free_port(), _free_port()]  # serving + shed-tuned replica
    health = {
        "helper": _free_port(),
        "leader0": _free_port(),
        "leader1": _free_port(),
        "creator": _free_port(),
        "driver": _free_port(),
    }

    agg_token = AuthenticationToken.new_bearer("agg-token-load")
    col_token = AuthenticationToken.new_bearer("col-token-load")
    collector_keys = HpkeKeypair.generate(9)
    task_id = TaskId.random()
    now = clock.now()
    bucket_start = Time(now.seconds - now.seconds % TIME_PRECISION.seconds)
    #: collection window: this bucket and the next (the soak may cross an
    #: hour boundary)
    interval = Interval(bucket_start, Duration(2 * TIME_PRECISION.seconds))

    common = dict(
        task_id=task_id,
        query_type=TaskQueryType.time_interval(),
        vdaf={"type": "Prio3Count"},
        vdaf_verify_key=b"\x51" * 16,
        min_batch_size=1,
        time_precision=TIME_PRECISION,
        collector_hpke_config=collector_keys.config,
    )
    leader_task = AggregatorTask(
        peer_aggregator_endpoint=f"http://127.0.0.1:{helper_port}/",
        role=Role.LEADER,
        aggregator_auth_token=agg_token,
        collector_auth_token_hash=col_token.hash(),
        hpke_keys=[HpkeKeypair.generate(1)],
        **common,
    )
    helper_task = AggregatorTask(
        peer_aggregator_endpoint=f"http://127.0.0.1:{leader_port[0]}/",
        role=Role.HELPER,
        aggregator_auth_token_hash=agg_token.hash(),
        hpke_keys=[HpkeKeypair.generate(2)],
        **common,
    )
    leader_ds.run_tx("putl", lambda tx: tx.put_aggregator_task(leader_task))
    helper_ds.run_tx("puth", lambda tx: tx.put_aggregator_task(helper_task))

    slo_block = """
  slos:
    upload_to_commit: {objective: 0.95, threshold_s: 10}
    commit_age: {objective: 0.99, threshold_s: 3600}
"""

    def leader_yaml(i, shed_tuned):
        shed = (
            """
  fault_injection:
    enabled: true
    seed: 7
    points:
      upload.open: {mode: delay, probability: 1.0, delay_s: 1.0}
"""
            if shed_tuned
            else ""
        )
        queue = (
            "upload_queue_max: 4\nupload_shed_delay_s: 1.0\n"
            if shed_tuned
            else "upload_queue_max: 4096\n"
        )
        # the serving replica runs the ISSUE 18 zero-copy ingest plane in
        # journaled mode under real load: ACK off the write-behind journal,
        # direct staged handoff, materializer draining the rest.  The
        # shed-tuned replica stays synchronous so its shed assertions keep
        # judging the legacy front door.
        ingest = "" if shed_tuned else "ingest:\n  mode: journaled\n"
        return f"""
common:
  database: {{path: {leader_db}}}
  health_check_listen_address: 127.0.0.1:{health[f'leader{i}']}
  chrome_trace_path: {tmp_path}/trace-leader{i}.json
  status_sample_interval_s: 0.5{slo_block}{shed}
listen_address: 127.0.0.1:{leader_port[i]}
vdaf_backend: oracle
upload_open_backend: batched
upload_open_batch_size: 64
upload_open_batch_delay_ms: 5
{queue}max_upload_batch_write_delay_ms: 50
{ingest}"""

    helper_yaml = f"""
common:
  database: {{path: {helper_db}}}
  health_check_listen_address: 127.0.0.1:{health['helper']}
  chrome_trace_path: {tmp_path}/trace-helper.json
  status_sample_interval_s: 0.5
listen_address: 127.0.0.1:{helper_port}
vdaf_backend: oracle
"""
    creator_yaml = f"""
common:
  database: {{path: {leader_db}}}
  health_check_listen_address: 127.0.0.1:{health['creator']}
  chrome_trace_path: {tmp_path}/trace-creator.json
aggregation_job_creation_interval_s: 0.5
min_aggregation_job_size: 1
max_aggregation_job_size: 200
"""
    driver_yaml = f"""
common:
  database: {{path: {leader_db}}}
  health_check_listen_address: 127.0.0.1:{health['driver']}
  chrome_trace_path: {tmp_path}/trace-driver.json
  status_sample_interval_s: 0.5
job_driver:
  job_discovery_interval_s: 0.3
  max_concurrent_job_workers: 4
  worker_lease_duration_s: 60
  worker_lease_clock_skew_allowance_s: 1
  lease_reap_interval_s: 1.0
vdaf_backend: tpu
device_executor:
  enabled: true
  flush_window_ms: 20
  flush_max_rows: 4096
"""
    cfgs = {}
    for name, text in (
        ("leader0", leader_yaml(0, False)),
        ("leader1", leader_yaml(1, True)),
        ("helper", helper_yaml),
        ("creator", creator_yaml),
        ("driver", driver_yaml),
    ):
        p = tmp_path / f"{name}.yaml"
        p.write_text(text)
        cfgs[name] = p

    env = dict(os.environ)
    env["DATASTORE_KEYS"] = base64.urlsafe_b64encode(key).decode().rstrip("=")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")

    def spawn(binary, cfg, tag):
        log = open(tmp_path / f"{tag}.log", "wb")
        return subprocess.Popen(
            [sys.executable, "-c", _BOOT, binary, "--config-file", str(cfg)],
            env=env,
            cwd=str(REPO),
            stdout=log,
            stderr=subprocess.STDOUT,
        )

    def loadgen(leader_url, rate, duration, extra=()):
        out = subprocess.run(
            [
                sys.executable,
                str(REPO / "tools" / "loadgen.py"),
                "--leader",
                leader_url,
                "--helper",
                f"http://127.0.0.1:{helper_port}",
                "--task-id",
                str(task_id),
                "--vdaf",
                '{"type": "Prio3Count"}',
                "--rate",
                str(rate),
                "--duration",
                str(duration),
                "--json",
                *extra,
            ],
            env=env,
            cwd=str(REPO),
            capture_output=True,
            text=True,
            timeout=duration + 120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    procs = {}
    try:
        procs["helper"] = spawn("aggregator", cfgs["helper"], "helper")
        procs["leader0"] = spawn("aggregator", cfgs["leader0"], "leader0")
        procs["creator"] = spawn(
            "aggregation_job_creator", cfgs["creator"], "creator"
        )
        procs["driver"] = spawn("aggregation_job_driver", cfgs["driver"], "driver")
        for tag in ("helper", "leader0", "creator", "driver"):
            _wait_http(f"http://127.0.0.1:{health[tag]}/healthz", 120)

        # -- phase 1: sustained traffic at target rate ------------------
        # Scaled to the host: with a functional `cryptography` (AES-NI,
        # C curves) the whole pipeline runs ~50-100x faster than on the
        # pure-Python fallback a dev container uses; the judge (SLO burn,
        # zero sheds, exactly-once) is the same either way.
        from janus_tpu.utils.gcm import HAVE_FUNCTIONAL_CRYPTOGRAPHY

        cores = os.cpu_count() or 1
        default_rate = 60 if (HAVE_FUNCTIONAL_CRYPTOGRAPHY and cores >= 4) else 12
        target = float(os.environ.get("JANUS_LOAD_RATE", default_rate))
        duration = float(os.environ.get("JANUS_LOAD_DURATION", "30"))
        p1 = loadgen(
            f"http://127.0.0.1:{leader_port[0]}",
            target,
            duration,
            extra=["--ramp-s", "3", "--concurrency", "64", "--trace-sample", "25"],
        )
        assert p1["outcomes"]["accepted"] == p1["sent"] > 0, p1
        assert p1["outcomes"]["shed"] == 0, p1
        assert p1["achieved_rate"] >= 0.4 * target, p1

        # breach-free SLO burn at target rate, judged by the LEADER's own
        # evaluator (give a sampler tick time to land)
        time.sleep(1.2)
        m0 = _scrape(health["leader0"])
        burn_fast = _metric_value(
            m0, 'janus_slo_burn_rate{slo="upload_to_commit",window="fast"}'
        )
        assert burn_fast is not None, "burn rate never published"
        # breach-free at target rate: the fast burn must sit below the
        # SUSTAINABLE pace (1.0 = spending budget exactly on schedule),
        # nowhere near the page threshold (14) — and no breach counted
        assert burn_fast < 1.0, f"upload_to_commit burning: {burn_fast}"
        assert (
            _metric_value(m0, 'janus_slo_burn_rate{slo="commit_age",window="fast"}')
            == 0.0
        )
        assert _metric_total(m0, "janus_slo_breach_total") == 0.0
        assert _metric_total(m0, "janus_upload_shed_total") == 0.0
        # the batched open actually batched (amortization observable)
        assert _metric_value(m0, "janus_upload_open_batch_rows_count") > 0
        batch_sum = _metric_value(m0, "janus_upload_open_batch_rows_sum")
        batch_cnt = _metric_value(m0, "janus_upload_open_batch_rows_count")
        assert batch_sum >= p1["outcomes"]["accepted"]
        assert batch_sum / batch_cnt > 1.0, "opens never coalesced"

        # -- phase 2: past the shed threshold ---------------------------
        procs["leader1"] = spawn("aggregator", cfgs["leader1"], "leader1")
        _wait_http(f"http://127.0.0.1:{health['leader1']}/healthz", 120)
        p2 = loadgen(
            f"http://127.0.0.1:{leader_port[1]}",
            max(120.0, 3 * target),
            10,
            extra=["--concurrency", "128"],
        )
        assert p2["outcomes"]["shed"] > 0, p2  # overload sheds...
        assert p2["outcomes"]["accepted"] > 0, p2  # ...but bounded
        assert p2["retry_after_seen"] > 0, p2  # with Retry-After attached
        time.sleep(1.2)
        m1 = _scrape(health["leader1"])
        assert _metric_total(m1, "janus_upload_shed_total") >= p2["outcomes"]["shed"]
        # admitted reports kept their commit SLOs green through overload
        assert _metric_total(m1, "janus_slo_breach_total") == 0.0
        assert (
            _metric_value(m1, 'janus_slo_burn_rate{slo="commit_age",window="fast"}')
            == 0.0
        )

        accepted_total = p1["outcomes"]["accepted"] + p2["outcomes"]["accepted"]
        transport_errors = p1["outcomes"]["error"] + p2["outcomes"]["error"]
        # journaled ingest (ISSUE 18): leader0's ACKed reports may still
        # sit in the write-behind journal; let the staged consumer /
        # materializer drain it before judging durability by table counts
        deadline = time.monotonic() + 60
        while _sql(leader_db, "SELECT COUNT(*) FROM report_journal")[0][0] > 0:
            assert time.monotonic() < deadline, "report journal never drained"
            time.sleep(0.3)
        stored = _sql(leader_db, "SELECT COUNT(*) FROM client_reports")[0][0]
        # every accepted upload is durable; only a transport error AFTER
        # the server committed could make stored exceed accepted
        assert accepted_total <= stored <= accepted_total + transport_errors

        # -- settle: everything admitted aggregates ---------------------
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            unpacked = _sql(
                leader_db,
                "SELECT COUNT(*) FROM client_reports WHERE aggregation_started = 0",
            )[0][0]
            in_progress = _sql(
                leader_db,
                "SELECT COUNT(*) FROM aggregation_jobs WHERE state = 'InProgress'",
            )[0][0]
            n_jobs = _sql(leader_db, "SELECT COUNT(*) FROM aggregation_jobs")[0][0]
            if unpacked == 0 and in_progress == 0 and n_jobs > 0:
                break
            time.sleep(0.5)
        else:
            pytest.fail(
                f"aggregation never settled: unpacked={unpacked} "
                f"in_progress={in_progress} jobs={n_jobs}"
            )

        # -- collect (in-process driver + real collector HTTP flow) -----
        client_trace = str(tmp_path / "trace-client.json")
        configure_chrome_trace(client_trace)

        async def collect():
            import aiohttp

            from janus_tpu.aggregator.collection_job_driver import (
                CollectionJobDriver,
            )
            from janus_tpu.collector import Collector
            from janus_tpu.messages import Query

            collector = Collector(
                task_id=task_id,
                leader_endpoint=f"http://127.0.0.1:{leader_port[0]}",
                vdaf=leader_task.vdaf_instance(),
                auth_token=col_token,
                hpke_keypair=collector_keys,
                poll_interval=0.2,
                max_poll_time=120.0,
            )
            driver = CollectionJobDriver(leader_ds, aiohttp.ClientSession)
            done = asyncio.Event()

            async def drive():
                while not done.is_set():
                    leases = await leader_ds.run_tx_async(
                        "acquire_coll",
                        lambda tx: tx.acquire_incomplete_collection_jobs(
                            Duration(600), 4
                        ),
                    )
                    for lease in leases:
                        await driver.step_collection_job(lease)
                    try:
                        await asyncio.wait_for(done.wait(), timeout=0.3)
                    except asyncio.TimeoutError:
                        pass

            async def run_collect():
                try:
                    return await collector.collect(
                        Query.new_time_interval(interval), session=None
                    )
                finally:
                    done.set()

            result, _ = await asyncio.gather(run_collect(), drive())
            await driver.close()
            return result

        collection = asyncio.new_event_loop().run_until_complete(collect())
        # exactly-once: the collected count and sum are the admitted
        # uploads, no more, no less (measurement == 1 per report)
        assert accepted_total <= collection.report_count <= stored
        assert collection.aggregate_result == collection.report_count

        # -- graceful teardown so every binary flushes its trace --------
        for tag in ("leader0", "leader1", "creator", "driver", "helper"):
            p = procs.get(tag)
            if p is not None and p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for tag, p in procs.items():
            if p is not None:
                assert p.wait(timeout=60) == 0, f"{tag} dirty exit"
        close_chrome_trace()

        # -- loadgen-minted traces stitch client -> collection ----------
        from tools.trace_merge import trace_stats

        trace_files = [
            str(tmp_path / f)
            for f in (
                "trace-leader0.json",
                "trace-leader1.json",
                "trace-creator.json",
                "trace-driver.json",
                "trace-helper.json",
                "trace-client.json",
            )
            if (tmp_path / f).exists()
        ]
        stats = trace_stats(trace_files)
        assert stats["complete_paths"] >= 1, {
            "files": trace_files,
            "groups": [
                {k: g[k] for k in ("trace_ids", "spans", "complete")}
                for g in stats["merged_traces"][:5]
            ],
        }
        # the sampled loadgen trace ids are IN the merged timeline
        merged_ids = set().union(
            *(set(g["trace_ids"]) for g in stats["merged_traces"])
        ) if stats["merged_traces"] else set()
        sampled = set(p1["trace_ids"])
        assert sampled & merged_ids, "no sampled upload trace reached the timeline"

        # ISSUE 18: upload->first-prepare percentiles for the sampled
        # uploads, computed the way `loadgen --json --trace-files` reports
        # them — the client-side view of the ingest handoff's latency
        sys.path.insert(0, str(REPO / "tools"))
        from loadgen import first_prepare_percentiles

        fp = first_prepare_percentiles(trace_files, p1["trace_ids"])
        assert fp["samples"] >= 1, fp
        assert fp["p50"] is not None and fp["p50"] >= 0, fp
        assert fp["p99"] >= fp["p50"], fp
    finally:
        for p in procs.values():
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=30)
        leader_ds.close()
        helper_ds.close()
