"""tools/profile_step.py's trace aggregation, against a synthetic perfetto
trace — the tool backs BASELINE.md's where-the-step-goes claims, so its
track selection (XLA Ops only, no double-counting of module/step slices)
and its grouping by the step's anatomy (phase and part of each instruction,
from the table the compiled step gives) are pinned here."""

import gzip
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import profile_step  # noqa: E402

sys.path.pop(0)


def _trace(tmp_path, events):
    d = tmp_path / "plugins" / "perfetto"
    d.mkdir(parents=True)
    with gzip.open(d / "x.perfetto_trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    return str(tmp_path)


def _meta(pid, name, tid=None):
    ev = {"ph": "M", "pid": pid,
          "name": "thread_name" if tid is not None else "process_name",
          "args": {"name": name}}
    if tid is not None:
        ev["tid"] = tid
    return ev


@pytest.mark.core
def test_summarize_uses_only_the_ops_track(tmp_path):
    events = [
        _meta(1, "/device:TPU:0"),
        _meta(1, "XLA Modules", tid=1),
        _meta(1, "XLA Ops", tid=2),
        _meta(2, "python host", ),
        _meta(2, "main", tid=1),
        # Module-level slice spanning everything — must NOT be counted.
        {"ph": "X", "pid": 1, "tid": 1, "name": "jit_step_fn", "dur": 9000},
        # Leaf ops (microseconds).
        {"ph": "X", "pid": 1, "tid": 2, "name": "fusion.1", "dur": 3000},
        {"ph": "X", "pid": 1, "tid": 2, "name": "convert_reduce_fusion.2",
         "dur": 2000},
        {"ph": "X", "pid": 1, "tid": 2, "name": "copy.5", "dur": 1000},
        {"ph": "X", "pid": 1, "tid": 2, "name": "bn_stem.7", "dur": 500},
        # Host-side slice — wrong pid, must not be counted.
        {"ph": "X", "pid": 2, "tid": 1, "name": "fusion.1", "dur": 77777},
    ]
    table = {
        "fusion.1": "jit(step_fn)/grads/jvp(ResNet)/head/dot_general",
        "convert_reduce_fusion.2": "jit(step_fn)/loss_scale/reduce_sum",
        "copy.5": "jit(step_fn)/optimizer/mul",
    }
    out = profile_step.summarize(_trace(tmp_path, events), steps=2, top=10,
                                 table=table)
    # 6.5 ms of ops over 2 steps = 3.25 ms/step; the 9 ms module slice and
    # the 77 ms host slice are excluded.
    assert out["device_ms_per_step"] == pytest.approx(3.25)
    parts = out["by_part_ms"]
    assert parts["forward/head"] == pytest.approx(1.5)       # fusion.1
    assert parts["update/loss_scale"] == pytest.approx(1.0)  # convert_reduce
    assert parts["update/optimizer"] == pytest.approx(0.5)   # copy.5
    assert parts["-/unattributed"] == pytest.approx(0.25)    # not in table
    assert list(parts) == ["forward/head", "update/loss_scale",
                           "update/optimizer", "-/unattributed"]
    assert out["top_ops_ms"]["fusion.1"] == pytest.approx(1.5)
    assert "jit_step_fn" not in out["top_ops_ms"]


def test_summarize_missing_trace_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        profile_step.summarize(str(tmp_path), steps=1, top=5, table={})
