"""The yardstick's arithmetic, on hand-worked values."""

import json
import os

import pytest

import prom
import protocol_bytes
import readers
import trace_reduce as tr
from loadgen import measurements, percentile, schedule
from reference import mismatched_positions, plain_aggregate

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def planes():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        data = json.load(f)["planes"]
    for plane in data:
        for line in plane["lines"]:
            line["events"] = [tuple(e) for e in line["events"]]
    return data


# -- trace reduction: slice [1000, 11000) ns of the recorded trace -----------


def test_device_planes_takes_the_ops_line_and_not_the_umbrella(planes):
    (events,) = tr.device_planes(planes, "/device:TPU:")
    assert len(events) == 6
    assert all(not name.startswith("jit_") for name, _s, _d in events)


def test_mark_busy_union_idle_share_and_op_sums(planes):
    lo = tr.find_mark(planes, "bench_slice_mark")
    assert lo == 1000.0
    hi = lo + 10000.0
    (events,) = tr.device_planes(planes, "/device:TPU:")
    # union: [2000,3500] [4000,4500] [6000,7000] [10500,11000 clipped]; the
    # op at 500 ends before the slice
    assert tr.merged(events, lo, hi) == [
        (2000.0, 3500.0), (4000.0, 4500.0), (6000.0, 7000.0), (10500.0, 11000.0)
    ]
    assert tr.busy_ns(events, lo, hi) == 3500.0
    assert 1 - tr.busy_ns(events, lo, hi) / (hi - lo) == pytest.approx(0.65)
    ops = dict(tr.op_seconds(events, lo, hi))
    assert ops == pytest.approx(
        {"fusion.1": 2000e-9, "fusion.2": 1000e-9, "copy.3": 500e-9, "fusion.9": 500e-9}
    )
    assert tr.op_seconds(events, lo, hi)[0][0] == "fusion.1"


def test_gaps_and_their_attribution(planes):
    lo, hi = 1000.0, 11000.0
    (events,) = tr.device_planes(planes, "/device:TPU:")
    idle = tr.gaps(events, lo, hi)
    assert idle == [(1000.0, 2000.0), (3500.0, 4000.0), (4500.0, 6000.0), (7000.0, 10500.0)]
    assert sum(e - s for s, e in idle) == 6500.0
    spans = [
        ("launch", 1500.0, 5000.0),  # covers 500 + 500 + 500 of idle
        ("wait", 0.0, 9000.0),  # lower priority: 500 + 1000 + 2000 more
    ]
    got = dict(tr.attribute_gaps(idle, spans))
    assert got == pytest.approx(
        {"launch": 1500e-9, "wait": 3500e-9, "nothing queued": 1500e-9}
    )
    assert sum(got.values()) == pytest.approx(6500e-9)


def test_overlap_of_two_interval_lists():
    a = [(0.0, 10.0), (20.0, 30.0)]
    b = [(5.0, 25.0), (28.0, 40.0)]
    assert tr.overlap_ns(a, b) == 5.0 + 5.0 + 2.0


def test_trace_ratio_reader_and_its_silences():
    rec = {"trace": {"quantities": {"window_s": 10.0, "busy_s": 3.5, "rows": 0.0,
                                    "protocol_bytes": 7e9, "hbm_bytes_per_s": 1e10}}}
    idle = {"num": ["window_s", "-busy_s"], "den": ["window_s"], "scale": 100.0}
    assert readers.trace_ratio(rec, idle) == pytest.approx(65.0)
    roof = {"num": ["protocol_bytes"], "den": ["hbm_bytes_per_s", "busy_s"], "scale": 100.0}
    assert readers.trace_ratio(rec, roof) == pytest.approx(20.0)
    # nothing to read: no trace, a missing quantity, a zero denominator
    assert readers.trace_ratio({}, idle) is None
    assert readers.trace_ratio(rec, {"num": ["busy_s"], "den": ["launch_s"]}) is None
    assert readers.trace_ratio(rec, {"num": ["busy_s"], "den": ["rows"]}) is None


# -- percentiles and the schedule --------------------------------------------


def test_percentile_is_nearest_rank():
    assert percentile([], 0.95) is None
    assert percentile([7.0], 0.95) == 7.0
    values = list(range(1, 101))  # 1..100
    assert percentile(values, 0.5) == 51  # int(0.5 * 99 + 0.5) = 50 -> 51
    assert percentile(values, 0.95) == 95  # int(0.95 * 99 + 0.5) = 94, the 95th value
    assert percentile(reversed(values), 0.95) == 95
    assert percentile([1, 2, 3, 4], 1.0) == 4


def test_upload_percentile_counts_a_missing_answer_as_the_limit():
    rec = {"uploads": [{"ack_s": 0.1}] * 9 + [{"ack_s": None}]}
    args = {"field": "ack_s", "q": 0.95, "scale": 1000.0, "missing_s": 60.0}
    assert readers.upload_percentile(rec, args) == 60000.0
    args["q"] = 0.5
    assert readers.upload_percentile(rec, args) == pytest.approx(100.0)


def test_schedule_gives_every_seed_the_same_gaps_in_another_order():
    traffic = {"arrivals": "poisson", "rate": 50.0, "lead_in_s": 4.0, "schedule_seed": 3}
    a, b = schedule(traffic, 6.0, 1), schedule(traffic, 6.0, 2**31 + 5)
    assert len(a) == len(b) == 500
    assert a != b and a == schedule(traffic, 6.0, 1)
    assert a == sorted(a) and 0 < a[0] and a[-1] < 10.0
    # the same number in the lead-in and in the window, whatever the seed
    for times in (a, b):
        assert sum(1 for t in times if t < 4.0) == 200
        assert sum(1 for t in times if 4.0 <= t < 10.0) == 300

    def gaps(times):
        return sorted(round(y - x, 9) for x, y in zip([0.0] + times, times))

    # the same multiset of gaps, but for those at the seam and the ends
    assert len(set(gaps(a)) ^ set(gaps(b))) <= 12
    with pytest.raises(ValueError):
        schedule({**traffic, "arrivals": "bursts"}, 6.0, 1)


def test_measurements_come_from_the_seed():
    import random

    desc = {"type": "Prio3Histogram", "length": 8, "chunk_length": 3}
    a = measurements(desc, 50, random.Random(9))
    assert a == measurements(desc, 50, random.Random(9))
    assert a != measurements(desc, 50, random.Random(10))
    assert set(measurements({"type": "Prio3Count"}, 50, random.Random(9))) == {0, 1}


# -- Prometheus deltas ---------------------------------------------------------

BEFORE = """
# HELP janus_job_step_duration_seconds step
# TYPE janus_job_step_duration_seconds histogram
janus_job_step_duration_seconds_sum{job_type="aggregation",outcome="ok"} 2.0
janus_job_step_duration_seconds_count{job_type="aggregation",outcome="ok"} 4
janus_job_step_duration_seconds_sum{job_type="collection",outcome="ok"} 9.0
janus_job_step_duration_seconds_count{job_type="collection",outcome="ok"} 1
janus_device_prepare_reports_total{backend="tpu"} 100
janus_device_prepare_launches_total{backend="tpu"} 2
janus_http_request_duration_seconds_count{route="/tasks/{task_id}/aggregation_jobs/{aggregation_job_id}"} 3
janus_database_transactions_total{name="a,b",status="ok"} 10
"""
AFTER = """
janus_job_step_duration_seconds_sum{job_type="aggregation",outcome="ok"} 5.0
janus_job_step_duration_seconds_count{job_type="aggregation",outcome="ok"} 8
janus_job_step_duration_seconds_sum{job_type="aggregation",outcome="retry"} 1.0
janus_job_step_duration_seconds_count{job_type="aggregation",outcome="retry"} 2
janus_job_step_duration_seconds_sum{job_type="collection",outcome="ok"} 19.0
janus_job_step_duration_seconds_count{job_type="collection",outcome="ok"} 2
janus_device_prepare_reports_total{backend="tpu"} 4196
janus_device_prepare_launches_total{backend="tpu"} 4
janus_http_request_duration_seconds_count{route="/tasks/{task_id}/aggregation_jobs/{aggregation_job_id}"} 7
janus_http_request_duration_seconds_count{route="/tasks/{task_id}/reports"} 50
janus_database_transactions_total{name="a,b",status="ok"} 25
janus_database_transactions_total{name="c",status="ok"} 5
"""


def test_prometheus_deltas_means_and_ratios():
    before, after = prom.parse(BEFORE), prom.parse(AFTER)
    # (5 - 2 + 1 - 0) / (8 - 4 + 2 - 0) over both outcomes of one job type
    assert prom.mean(
        before, after, "janus_job_step_duration_seconds", {"job_type": "aggregation"}
    ) == pytest.approx(4.0 / 6.0)
    assert prom.mean(before, after, "janus_executor_wait_duration_seconds") is None
    assert prom.delta(
        before, after, "janus_http_request_duration_seconds_count", {"route": "*aggregation_jobs*"}
    ) == 4
    assert prom.delta(before, after, "janus_database_transactions_total") == 20
    rec = {"prom": {"open": before, "close": after}, "reports_aggregated": 40}
    flush = {"num": "janus_device_prepare_reports_total", "num_labels": {"backend": "tpu"},
             "den": "janus_device_prepare_launches_total", "den_labels": {"backend": "tpu"}}
    assert readers.prom_ratio(rec, flush) == 2048.0
    tx = {"num": "janus_database_transactions_total", "den_count": "reports_aggregated"}
    assert readers.prom_ratio(rec, tx) == 0.5
    assert readers.prom_ratio({**rec, "reports_aggregated": 0}, tx) is None
    # over the whole run: another pair of snapshots, and nothing where there is none
    assert readers.prom_ratio(rec, {**tx, "over": "run"}) is None
    run = {**rec, "prom_run": {"open": prom.parse(""), "close": after}}
    assert readers.prom_ratio(run, {**tx, "over": "run"}) == 30 / 40
    step = {"family": "janus_job_step_duration_seconds", "labels": {"job_type": "aggregation"},
            "scale": 1000.0}
    assert readers.prom_mean(rec, step) == pytest.approx(4000.0 / 6.0)


def test_window_rate_is_the_work_finished_in_the_window_over_its_seconds():
    assert readers.window_rate({"finished_in_window": 4080, "seconds": 51.0}, {}) == 80.0
    assert readers.window_rate({"finished_in_window": 0, "seconds": 51.0}, {}) is None


# -- protocol bytes, by hand from the VDAF's share lengths ---------------------


def test_protocol_bytes_histogram_1024_34():
    desc = {"type": "Prio3Histogram", "length": 1024, "chunk_length": 34}
    # Field128; ParallelSum(Mul, 34): arity 68, ceil(1024 / 34) = 31 calls -> P = 32
    # proof = 68 + 2 * 31 + 1 = 131; verifier = 1 + 68 + 1 = 70
    assert protocol_bytes.flp_lengths(desc) == (16, 1024, 1024, 2, 131, 70)
    leader_share = (1024 + 131) * 16 + 16  # 18,496: "a report is about 19 KB"
    helper_share = 32
    per_side_in = 16 + 32  # nonce, public share (two joint-rand parts)
    prep_share = 70 * 16 + 16  # 1,136
    out = 2 * (1024 * 16 + prep_share)  # 35,040
    combine = 2 * prep_share + 16 + 1  # 2,289
    want = leader_share + helper_share + 2 * per_side_in + out + combine
    assert want == 55953
    assert protocol_bytes.prepare_bytes_per_report(desc) == want


def test_the_chunk_length_is_the_one_libprio_would_choose():
    """libprio-rs ``optimal_chunk_length``: over gadget-call counts 2^k - 1,
    the chunk whose proof, 2 * chunk + 2 * (next_pow2(1 + calls) - 1)
    elements, is shortest.  The configuration files name it as their source."""

    def optimal_chunk_length(length):
        if length <= 1:
            return 1
        best = None
        for log2 in range(length.bit_length(), 0, -1):
            calls = (1 << log2) - 1
            chunk = -(-length // calls)
            proof = 2 * chunk + 2 * ((1 << (1 + calls - 1).bit_length()) - 1)
            if best is None or proof < best[0]:
                best = (proof, chunk)
        return best[1]

    assert optimal_chunk_length(1024) == 34
    bench = os.path.dirname(HERE)
    for name in os.listdir(os.path.join(bench, "configs")):
        with open(os.path.join(bench, "configs", name)) as f:
            vdaf = json.load(f)["vdaf"]
        if vdaf["type"] == "Prio3Histogram":
            assert vdaf["chunk_length"] == optimal_chunk_length(vdaf["length"]), name


def test_protocol_bytes_count():
    desc = {"type": "Prio3Count"}
    # Field64; Mul: arity 2, 1 call -> P = 2; proof = 2 + 2 + 1; verifier = 4
    assert protocol_bytes.flp_lengths(desc) == (8, 1, 1, 0, 5, 4)
    # in: 2 nonces, leader (1 + 5) * 8, helper one seed; out: 2 * (8 + 32);
    # combine: 2 * 32 in, one decision byte out
    want = 2 * 16 + 48 + 16 + 2 * (8 + 32) + 2 * 32 + 1
    assert want == 241
    assert protocol_bytes.prepare_bytes_per_report(desc) == want


def test_protocol_lengths_agree_with_the_program():
    """Every family file, at every ``vdaf`` a committed configuration or its
    ``rehearse`` block names: a new configuration is covered by being there."""
    from janus_tpu.vdaf import vdaf_from_instance
    from test_vdafs import committed_vdafs

    descs = committed_vdafs()
    assert {"type": "Prio3Histogram", "length": 3, "chunk_length": 2} in descs  # a rehearsal's
    for desc in descs:
        flp = vdaf_from_instance(desc).flp
        assert protocol_bytes.flp_lengths(desc) == (
            flp.field.ENCODED_SIZE, flp.MEAS_LEN, flp.OUTPUT_LEN, flp.JOINT_RAND_LEN,
            flp.PROOF_LEN, flp.VERIFIER_LEN,
        )


# -- the plain reference --------------------------------------------------------


def test_plain_reference_and_the_comparison():
    hist = {"type": "Prio3Histogram", "length": 4, "chunk_length": 2}
    assert plain_aggregate(hist, [0, 3, 3, 1]) == [1, 1, 0, 2]
    assert plain_aggregate({"type": "Prio3Count"}, [1, 0, 1, 1]) == 3
    assert mismatched_positions([1, 1, 0, 2], [1, 1, 0, 2]) == 0
    assert mismatched_positions([1, 2, 0, 1], [1, 1, 0, 2]) == 2
    assert mismatched_positions([1, 1, 0], [1, 1, 0, 2]) == 1
    assert mismatched_positions(3, 3) == 0 and mismatched_positions(4, 3) == 1
    assert mismatched_positions(None, [0, 0]) == 2
