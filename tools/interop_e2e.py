#!/usr/bin/env python3
"""Multi-process interop end-to-end: 4 real processes over localhost HTTP.

Spawns janus_interop_client / two janus_interop_aggregator (leader+helper) /
janus_interop_collector as SEPARATE OS processes (the containerized topology
of the reference's interop harness — reference:
interop_binaries/tests/end_to_end.rs:40-60 over a Docker network), then
drives the draft-dvcs-ppm-dap interop test API end to end:

    ready -> add_task (collector, leader, helper) -> upload xN
          -> collection_start -> collection_poll until success

The aggregator processes run their own job-driver loops, so aggregation and
collection happen entirely inside the spawned processes; this script only
speaks HTTP.  Exit code 0 iff the collection completes with the expected
aggregate.

Usage:
    python tools/interop_e2e.py [--backend oracle|tpu|mesh] [--measurements N]

With --backend mesh the aggregators run SPMD over a virtual 8-device CPU
mesh (JAX_PLATFORMS=cpu is forced in the children), exercising the product
multi-chip path across process boundaries.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import secrets
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def b64u(b: bytes) -> str:
    return base64.urlsafe_b64encode(b).rstrip(b"=").decode()


def post(url: str, body: dict, timeout: float = 30.0) -> dict:
    req = urllib.request.Request(
        url,
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read() or b"{}")


def wait_ready(url: str, deadline: float) -> None:
    while time.time() < deadline:
        try:
            post(url + "/internal/test/ready", {}, timeout=2)
            return
        except (urllib.error.URLError, ConnectionError, OSError):
            time.sleep(0.3)
    raise SystemExit(f"process at {url} never became ready")


def spawn(role: str, port: int, backend: str, logdir: str) -> subprocess.Popen:
    env = dict(os.environ)
    env["JANUS_TPU_VDAF_BACKEND"] = backend
    # Interop processes always run on the host CPU (virtual mesh for
    # backend=mesh): a chip belongs to ONE process, and this harness
    # starts four.  The parent itself never imports JAX.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    log = open(os.path.join(logdir, f"{role}-{port}.log"), "w")
    return subprocess.Popen(
        [
            sys.executable,
            "-m",
            "janus_tpu.binaries.main",
            f"janus_interop_{role}",
            "--port",
            str(port),
        ],
        env=env,
        stdout=log,
        stderr=subprocess.STDOUT,
        cwd=REPO,
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="oracle", choices=["oracle", "tpu", "mesh"])
    ap.add_argument("--measurements", type=int, default=6)
    ap.add_argument("--base-port", type=int, default=18080)
    ap.add_argument("--logdir", default="/tmp/janus-interop-e2e")
    args = ap.parse_args()

    os.makedirs(args.logdir, exist_ok=True)
    ports = {
        "client": args.base_port,
        "leader": args.base_port + 1,
        "helper": args.base_port + 2,
        "collector": args.base_port + 3,
    }
    roles = {"client": "client", "leader": "aggregator", "helper": "aggregator", "collector": "collector"}
    procs = {}
    try:
        for name, role in roles.items():
            procs[name] = spawn(role, ports[name], args.backend, args.logdir)
        urls = {n: f"http://127.0.0.1:{p}" for n, p in ports.items()}
        deadline = time.time() + 120
        for n in urls:
            wait_ready(urls[n], deadline)
        print(f"all 4 processes ready (backend={args.backend})")

        task_id = secrets.token_bytes(32)
        vdaf = {"type": "Prio3Count"}
        leader_url = urls["leader"] + "/dap/"
        helper_url = urls["helper"] + "/dap/"
        now = int(time.time())
        start = now - now % 3600

        doc = post(
            urls["collector"] + "/internal/test/add_task",
            {
                "task_id": b64u(task_id),
                "leader": leader_url,
                "vdaf": vdaf,
                "collector_authentication_token": "col-tok",
                "query_type": 1,
            },
        )
        assert doc["status"] == "success", doc
        collector_hpke = doc["collector_hpke_config"]

        common = {
            "task_id": b64u(task_id),
            "leader": leader_url,
            "helper": helper_url,
            "vdaf": vdaf,
            "leader_authentication_token": "agg-tok",
            "vdaf_verify_key": b64u(secrets.token_bytes(16)),
            "min_batch_size": 1,
            "time_precision": 3600,
            "query_type": 1,
            "collector_hpke_config": collector_hpke,
        }
        doc = post(
            urls["leader"] + "/internal/test/add_task",
            {**common, "role": "Leader", "collector_authentication_token": "col-tok"},
        )
        assert doc["status"] == "success", doc
        doc = post(urls["helper"] + "/internal/test/add_task", {**common, "role": "Helper"})
        assert doc["status"] == "success", doc

        measurements = [i % 2 for i in range(args.measurements)]
        for m in measurements:
            doc = post(
                urls["client"] + "/internal/test/upload",
                {
                    "task_id": b64u(task_id),
                    "leader": leader_url,
                    "helper": helper_url,
                    "vdaf": vdaf,
                    "measurement": str(m),
                    "time_precision": 3600,
                },
            )
            assert doc["status"] == "success", doc
        print(f"uploaded {len(measurements)} reports")

        doc = post(
            urls["collector"] + "/internal/test/collection_start",
            {
                "task_id": b64u(task_id),
                "agg_param": "",
                "query": {
                    "type": 1,
                    "batch_interval_start": start,
                    "batch_interval_duration": 7200,
                },
            },
        )
        assert doc["status"] == "success", doc
        handle = doc["handle"]

        result = None
        poll_deadline = time.time() + 180
        while time.time() < poll_deadline:
            doc = post(urls["collector"] + "/internal/test/collection_poll", {"handle": handle})
            if doc["status"] == "success":
                result = doc
                break
            assert doc["status"] == "in progress", doc
            time.sleep(1.0)
        assert result is not None, "collection never completed (see logs in %s)" % args.logdir
        expect = sum(measurements)
        assert result["result"] == str(expect), result
        assert result["report_count"] == len(measurements), result
        print(
            json.dumps(
                {
                    "interop_e2e": "ok",
                    "backend": args.backend,
                    "processes": 4,
                    "reports": len(measurements),
                    "aggregate": result["result"],
                }
            )
        )
        return 0
    finally:
        for p in procs.values():
            p.send_signal(signal.SIGTERM)
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


if __name__ == "__main__":
    sys.exit(main())
