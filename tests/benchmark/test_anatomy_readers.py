"""`benchmark/anatomy.py` and the `device_ms.*` readers, on a reduction
recorded on the chip: the operations that hold 99.5 % of the device time of
14 traced steps of `gpt2_small.train_b16_s1024`, with the rows of the
program's anatomy table for them (`data/anatomy_small.json`)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

sys.path.insert(0, tiny.REPO)

from benchmark import anatomy, harness, trace  # noqa: E402
from distributeddeeplearning_tpu.perf import aot  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "anatomy_small.json")

# device ms per step, as first read from the recording
READERS = {
    "device_ms.flash_fwd": 35.724807357,
    "device_ms.flash_dq": 44.730067071,
    "device_ms.flash_dkv": 52.973680786,
    "device_ms.head_loss": 28.524829071,
    "device_ms.blocks_other": 83.229267929,
    "device_ms.update": 5.1468435,
    "device_ms.unattributed": 0.0018191428571,
}


@pytest.fixture
def recorded(monkeypatch):
    """(the recording, the `ctx` a traced run of it hands the readers); the
    program answers `aot.anatomy` with the recorded table."""
    with open(DATA) as fh:
        data = json.load(fh)
    monkeypatch.setattr(
        aot, "anatomy",
        lambda name: data["table"] if name == "gspmd_train_step" else None)
    reduced = {k: data[k] for k in ("per_op", "per_module", "busy_s",
                                    "window_s", "chips")}
    ctx = {"trace": reduced, "chips": data["chips"],
           "traffic": {"batch": data["batch"]},
           "traced_units": data["steps"] * data["batch"] * data["chips"]}
    return data, ctx


def read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_reader_gives_its_number(recorded, name):
    _, ctx = recorded
    assert read(name, ctx) == pytest.approx(READERS[name], rel=1e-7)


def check_the_device_ms_entries(spec):
    entries = {m["name"]: m for m in spec["per_layer"]
               if m["name"].startswith("device_ms.")}
    assert set(entries) == set(READERS)
    for m in entries.values():
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("ms", "lower", "device_trace", "train_examples_per_s")
    # the two that every train cell reports list no cells
    assert {n for n, m in entries.items() if "workloads" not in m} == \
        {"device_ms.update", "device_ms.unattributed"}


def test_the_readers_are_the_benchmarks_device_ms_metrics():
    check_the_device_ms_entries(harness.read_json("BENCHMARK.json"))


def test_the_seven_parts_sum_to_the_step_modules_device_time(recorded):
    data, ctx = recorded
    total = sum(read(name, ctx) for name in READERS) * data["steps"] * 1e-3
    assert total == pytest.approx(sum(data["per_op"].values()), rel=1e-9)
    step_module = max(data["per_module"].values())
    # the recording keeps 99.5 % of the time; a module's own span also
    # counts the gaps between its operations
    assert 0.994 * step_module < total <= step_module


def test_the_flash_parts_are_the_tpu_custom_calls(recorded):
    data, ctx = recorded
    flash = sum(read(f"device_ms.flash_{k}", ctx) for k in ("fwd", "dq",
                                                            "dkv"))
    kernels = trace.op_seconds(ctx["trace"], "tpu_custom_call")
    # the parts also hold the copies of a kernel's operands and results
    assert flash * data["steps"] * 1e-3 == pytest.approx(kernels, rel=5e-3)
    assert flash * data["steps"] * 1e-3 >= kernels


def test_the_table_by_part_and_phase(recorded, capsys):
    _, ctx = recorded
    table = anatomy.by_part(ctx)
    assert table["backward", "flash_dkv"] == pytest.approx(52.973680786)
    assert table["forward", "head"] == pytest.approx(7.399245143)
    assert ("forward", "flash_dq") not in table
    assert table["-", "unattributed"] == pytest.approx(0.001819143)
    err = capsys.readouterr().err.splitlines()
    assert err[0].startswith("anatomy: device ms per step over 14 traced")
    assert any(ln.startswith("anatomy: flash_dkv") for ln in err)
    anatomy.by_part(ctx)  # read once, printed once
    assert capsys.readouterr().err == ""


def test_nothing_is_read_when_another_program_holds_over_1_percent(recorded):
    data, ctx = recorded
    ctx["trace"]["per_module"]["jit_other(1)"] = 0.0101 * data["busy_s"]
    for name in READERS:
        assert read(name, ctx) is None


def test_just_under_1_percent_is_read(recorded):
    data, ctx = recorded
    ctx["trace"]["per_module"]["jit_other(1)"] = 0.0099 * data["busy_s"]
    assert read("device_ms.update", ctx) is not None


@pytest.mark.parametrize("case", ["no_table", "older_program", "no_trace",
                                  "no_modules", "cpu_trace"])
def test_nothing_to_read_gives_none_and_does_not_raise(recorded, monkeypatch,
                                                       case):
    """The parent of the PR that brought the readers has no table to give;
    a CPU rehearsal has no device operations."""
    _, ctx = recorded
    if case == "no_table":
        monkeypatch.setattr(aot, "anatomy", lambda name: None)
    elif case == "older_program":  # no analysis/anatomy.py to import
        import distributeddeeplearning_tpu.analysis as package
        monkeypatch.delattr(package, "anatomy")
        monkeypatch.setitem(
            sys.modules, "distributeddeeplearning_tpu.analysis.anatomy", None)
    elif case == "no_trace":
        ctx["trace"] = None
    elif case == "no_modules":
        ctx["trace"]["per_module"] = {}
    else:
        ctx["trace"] = trace.reduce({"devices": {}, "host": []})
    for name in READERS:
        assert read(name, ctx) is None
