"""Integration smokes (SURVEY.md §4): config-1 loop via the public API, CLI
surface, graft entry points, bench harness contract."""

import json
from pathlib import Path
import subprocess
import sys

import jax
import pytest

from distributeddeeplearning_tpu.config import (
    DataConfig, ParallelConfig, TrainConfig, preset, PRESETS)
from distributeddeeplearning_tpu.train import loop
from distributeddeeplearning_tpu.utils.logging import MetricLogger


def test_presets_construct():
    for name in PRESETS:
        cfg = preset(name)
        assert cfg.global_batch_size > 0
        assert cfg.parallel.num_devices >= 1


@pytest.mark.slow
def test_loop_smoke_resnet():
    cfg = TrainConfig(model="resnet18", global_batch_size=16, dtype="float32",
                      log_every=10**9, parallel=ParallelConfig(data=8),
                      data=DataConfig(image_size=32, num_classes=10))
    summary = loop.run(cfg, total_steps=3, warmup_steps=1,
                       logger=MetricLogger(enabled=False))
    assert summary["final_step"] == 3
    assert "examples_per_sec" in summary
    assert summary["final_metrics"]["loss"] > 0


def test_graft_entry_forward():
    import __graft_entry__ as ge
    fn, args = ge.entry()
    out = jax.eval_shape(fn, *args)
    assert out.shape == (8, 1000)


@pytest.mark.slow
def test_graft_dryrun_multichip():
    import __graft_entry__ as ge
    ge.dryrun_multichip(8)


def test_metric_logger_jsonl(tmp_path):
    path = tmp_path / "metrics.jsonl"
    lg = MetricLogger(file_path=str(path), enabled=True,
                      stream=open("/dev/null", "w"))
    lg.log(1, {"loss": 2.5}, examples_per_step=32)
    lg.log(2, {"loss": 2.4}, examples_per_step=32)
    lg.close()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert lines[0]["step"] == 1 and lines[0]["loss"] == 2.5
    assert "examples_per_sec" in lines[1]


def test_tensorboard_metric_mirror(tmp_path):
    """tensorboard_dir mirrors scalars into TF event files (SURVEY.md §5.5)."""
    tb = tmp_path / "tb"
    lg = MetricLogger(tensorboard_dir=str(tb), enabled=True,
                      stream=open("/dev/null", "w"))
    lg.log(1, {"loss": 3.0}, examples_per_step=8)
    lg.log(2, {"loss": 2.0}, examples_per_step=8)
    lg.close()
    events = list(tb.glob("events.out.tfevents.*"))
    assert events and events[0].stat().st_size > 0


@pytest.mark.slow
def test_profiler_trace_capture(tmp_path):
    """profile_steps=(1,2) writes a jax.profiler trace dir (SURVEY.md §5.1)."""
    cfg = TrainConfig(model="resnet18", global_batch_size=8, dtype="float32",
                      log_every=10**9,
                      profile_steps=(1, 2), profile_dir=str(tmp_path / "prof"),
                      data=DataConfig(image_size=32, num_classes=10))
    loop.run(cfg, total_steps=3, logger=MetricLogger(enabled=False))
    produced = list((tmp_path / "prof").rglob("*"))
    assert any(p.is_file() for p in produced), produced


@pytest.mark.slow
def test_train_cli_smoke():
    """End-to-end CLI run on the CPU backend (subprocess, tiny workload)."""
    out = subprocess.run(
        [sys.executable, "train.py", "--model", "resnet18",
         "--batch-size", "8", "--steps", "2", "--backend", "cpu",
         "--synthetic", "--dtype", "float32", "--dp", "1"],
        capture_output=True, text=True, timeout=600, cwd=".")
    assert out.returncode == 0, out.stderr[-2000:]
    last = out.stdout.strip().splitlines()[-1]
    rec = json.loads(last)
    assert rec["summary"]["final_step"] == 2


def test_bench_error_path_emits_parseable_json(tmp_path):
    """A child that cannot even build its model must still produce exactly
    one parseable JSON line with an error record — and a non-zero exit: no
    fresh measurement landed (the driver contract)."""
    proc = subprocess.run(
        [sys.executable, "bench.py", "--platform", "cpu",
         "--model", "no_such_model", "--attempts", "1",
         "--attempt-timeout", "120", "--budget", "180"],
        capture_output=True, text=True, timeout=300,
        cwd=str(Path(__file__).resolve().parent.parent))
    assert proc.returncode == 1, proc.stderr
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert rec["value"] is None
    assert "no_such_model" in rec["error"] or "unknown model" in rec["error"]
