"""The reduction from a profiler trace to busy time, idle share, time per
operation and gap attribution: on a hand-made trace whose answers are known,
and on a small trace recorded on the chip."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def hand_made():
    ms = 1e6  # the trace's times are nanoseconds
    return {
        "devices": {"/device:TPU:0": [
            [10 * ms, 20 * ms, "%fusion.1 = f32[8]{0} fusion(...)"],
            [25 * ms, 10 * ms, "%fusion.2 = f32[8]{0} fusion(...)"],  # overlaps
            [50 * ms, 10 * ms, '%k.3 = bf16[4,4]{1,0} custom-call(...), '
                               'custom_call_target="tpu_custom_call"'],
            [95 * ms, 20 * ms, "%copy.4 = f32[8]{0} copy(...)"],  # runs past
        ]},
        "host": [[0.0, 40 * ms, "dispatch"], [40 * ms, 60 * ms, "block"],
                 [42 * ms, 5 * ms, "batch"]],
    }


def test_busy_union_idle_share_and_per_op_time():
    r = trace.reduce(hand_made())
    assert abs(r["window_s"] - 0.100) < 1e-12          # first to last span
    # union: [10,35] + [50,60] + [95,100] (clipped) = 40 ms
    assert abs(r["busy_s"] - 0.040) < 1e-12
    assert abs(sum(r["per_op"].values()) - 0.045) < 1e-12  # overlap counted
    assert abs(trace.op_seconds(r, "tpu_custom_call") - 0.010) < 1e-12
    idle = 100.0 * (1 - r["busy_s"] / r["window_s"])
    assert abs(idle - 60.0) < 1e-9


def test_gaps_go_to_the_host_span_that_covers_them():
    r = trace.reduce(hand_made())
    gaps = {(name, round(sec * 1e3, 6)) for name, sec in r["gaps"]}
    # [0,10] under dispatch; [35,50]: 5 ms dispatch, 10 ms block (5 of them
    # also under the shorter batch span, which covers less) -> block;
    # [60,95] under block
    assert gaps == {("dispatch", 10.0), ("block", 15.0), ("block", 35.0)}
    assert abs(r["idle_by_span"]["block"] - 0.050) < 1e-12
    b = trace.breakdown(r)
    assert b["device_ops"][0] == ["fusion fusion f32[8]", 0.030]
    assert ["k custom-call bf16[4,4] tpu_custom_call", 0.010] in \
        b["device_ops"]
    assert b["idle_gaps"][0][0] == "block"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_host_spans_means_first_to_last_operation():
    t = hand_made()
    t["host"] = []
    r = trace.reduce(t)
    assert abs(r["window_s"] - 0.105) < 1e-12
    assert abs(r["busy_s"] - 0.055) < 1e-12
    assert r["gaps"] and all(name == "outside_spans" for name, _ in r["gaps"])


def test_a_trace_without_device_operations_reads_nothing():
    r = trace.reduce({"devices": {"/device:TPU:0": []}, "host": []})
    assert r["busy_s"] == 0.0 and r["per_op"] == {}


def test_recorded_trace():
    with open(os.path.join(HERE, "data", "trace_small.json")) as fh:
        t = json.load(fh)
    r = trace.reduce(t)
    assert 0 < r["busy_s"] < r["window_s"]
    assert abs(r["busy_s"] - 0.100565925) < 1e-6       # as first reduced
    # one operation at a time on a TensorCore: the union is the sum
    assert abs(sum(r["per_op"].values()) - r["busy_s"]) < 1e-6
    flash = trace.op_seconds(r, "tpu_custom_call")
    assert 0.4 < flash / r["busy_s"] < 0.5   # the flash kernels' share
    assert set(r["idle_by_span"]) <= {"batch", "dispatch", "block",
                                      "outside_spans"}
    top = trace.breakdown(r)["device_ops"][0]
    assert top[0] == "branch_0_fun custom-call bf16[192,1024,64] " \
                     "tpu_custom_call"
