"""Launcher tests (SURVEY.md §2 #9-#10, §5.3): host planning, fail-whole
monitoring, multi-process rendezvous, and fault-injection → resume.

Real pod-slice runs are manual/benchmark-time (SURVEY.md §4); here the
process-management layer is tested with local subprocesses, exactly how the
launcher simulates a multi-host job on one machine.
"""

import os
import subprocess
import sys

import pytest

from distributeddeeplearning_tpu import launch


@pytest.mark.core
def test_plan_local():
    specs = launch.plan_local(4, port=9100)
    assert [s.process_id for s in specs] == [0, 1, 2, 3]
    assert all(s.num_processes == 4 for s in specs)
    assert all(s.coordinator == "127.0.0.1:9100" for s in specs)
    env = specs[2].env()
    assert env[launch.ENV_PROCESS_ID] == "2"
    assert env[launch.ENV_NUM_PROCESSES] == "4"


@pytest.mark.core
def test_plan_from_hostfile(tmp_path):
    hf = tmp_path / "hosts"
    hf.write_text("# slice hosts\nworker0\nworker1\n\nworker2\n")
    specs = launch.plan_from_hostfile(str(hf), port=9200)
    assert len(specs) == 3
    assert specs[0].coordinator == "worker0:9200"  # first host coordinates
    assert specs[2].process_id == 2
    empty = tmp_path / "empty"
    empty.write_text("# comments only\n")
    with pytest.raises(ValueError):
        launch.plan_from_hostfile(str(empty))


def _spawn_py(code: str) -> subprocess.Popen:
    return subprocess.Popen([sys.executable, "-c", code])


@pytest.mark.core
def test_monitor_all_succeed():
    children = [_spawn_py("import sys; sys.exit(0)") for _ in range(3)]
    assert launch.monitor(children) == 0


@pytest.mark.core
def test_monitor_fail_whole():
    """First nonzero exit kills the survivors (mpirun semantics)."""
    slow = _spawn_py("import time; time.sleep(60)")
    bad = _spawn_py("import sys; sys.exit(3)")
    rc = launch.monitor([slow, bad], poll_interval_s=0.05, grace_s=5.0)
    assert rc == 3
    assert slow.poll() is not None  # terminated, not left running


@pytest.mark.slow
def test_two_process_rendezvous():
    """launch.run_local really wires jax.distributed: both processes must see
    num_processes=2 and the global device count."""
    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "from distributeddeeplearning_tpu import launch\n"
        "pid = launch.maybe_initialize_distributed()\n"
        "import jax\n"
        "assert pid == jax.process_index(), (pid, jax.process_index())\n"
        "assert jax.process_count() == 2, jax.process_count()\n"
        "assert jax.device_count() == 2 * jax.local_device_count()\n"
    )
    specs = launch.plan_local(2, port=9310)
    # XLA_FLAGS="" overrides the suite's 8-fake-device flag: 1 local CPU
    # device per process.
    children = [launch.spawn(s, [sys.executable, "-c", code],
                             extra_env={"XLA_FLAGS": ""}) for s in specs]
    assert launch.monitor(children, poll_interval_s=0.1) == 0


@pytest.mark.slow
def test_fault_injection_then_resume(tmp_path):
    """End-to-end §5.3 story: a run killed at step 3 exits nonzero through
    the launcher; the relaunch resumes from the step-2 checkpoint."""
    ckpt = str(tmp_path / "ckpt")
    base = [sys.executable, "train.py", "--backend", "cpu", "--model",
            "resnet18", "--batch-size", "8", "--dp", "1", "--synthetic",
            "--dtype", "float32", "--steps", "5", "--checkpoint-dir", ckpt,
            "--checkpoint-every", "2", "--log-every", "1000000"]
    env = dict(os.environ)

    crash = subprocess.run(
        [sys.executable, "launch.py", "--num-processes", "1", "--"]
        + base + ["--fail-at-step", "3"],
        capture_output=True, text=True, timeout=600, env=env)
    assert crash.returncode != 0
    assert "fault injection" in crash.stderr

    resume = subprocess.run(base, capture_output=True, text=True,
                            timeout=600, env=env)
    assert resume.returncode == 0, resume.stderr[-2000:]
    import json
    summary = json.loads(resume.stdout.strip().splitlines()[-1])["summary"]
    assert summary["start_step"] == 2  # resumed from the step-2 checkpoint
    assert summary["final_step"] == 5


@pytest.mark.slow
def test_multihost_checkpoint_save_restore_elastic(tmp_path):
    """SURVEY §5.4 under a REAL 2-process jax.distributed job (VERDICT r3
    Next #7 — the one checkpoint path that was only single-process-tested):

    1. two processes train and SAVE (every process writes its own orbax
       shards; the stream-meta agreement runs its collective fingerprint
       compare at process_count=2);
    2. the same 2-process topology RESUMES from that checkpoint;
    3. a single process resumes the 2-process checkpoint (process-count
       change — the elastic-restore claim, now proven against shards
       written by a genuinely multi-process save).

    Steps stay tiny: the XLA:CPU in-process collective watchdog aborts
    long dp>1 runs on this box (documented in conftest notes).
    """
    import json

    ckpt = str(tmp_path / "ckpt")

    def train_cmd(steps: int, dp: int) -> list:
        return [sys.executable, "train.py", "--backend", "cpu", "--model",
                "resnet18", "--batch-size", "8", "--dp", str(dp),
                "--synthetic", "--dtype", "float32", "--steps", str(steps),
                "--checkpoint-dir", ckpt, "--checkpoint-every", "2",
                "--log-every", "1000000"]

    env = dict(os.environ)
    env["XLA_FLAGS"] = ""  # 1 CPU device per process -> dp=2 spans procs
    env["JAX_PLATFORMS"] = "cpu"

    def run2(steps: int):
        return subprocess.run(
            [sys.executable, "launch.py", "--num-processes", "2", "--"]
            + train_cmd(steps, dp=2),
            capture_output=True, text=True, timeout=900, env=env)

    def summary_of(proc):
        lines = [ln for ln in proc.stdout.splitlines() if "summary" in ln]
        assert lines, (proc.returncode, proc.stderr[-2000:])
        return json.loads(lines[-1])["summary"]

    first = run2(4)
    assert first.returncode == 0, first.stderr[-2000:]
    s1 = summary_of(first)
    assert s1["start_step"] == 0 and s1["final_step"] == 4

    second = run2(6)
    assert second.returncode == 0, second.stderr[-2000:]
    s2 = summary_of(second)
    assert s2["start_step"] == 4, s2  # resumed the multi-process save
    assert s2["final_step"] == 6

    # Elastic: one process, one device, restores the 2-process shards.
    solo = subprocess.run(train_cmd(8, dp=1), capture_output=True,
                          text=True, timeout=600, env=env)
    assert solo.returncode == 0, solo.stderr[-2000:]
    s3 = summary_of(solo)
    assert s3["start_step"] == 6, s3
    assert s3["final_step"] == 8


@pytest.mark.slow
def test_multihost_gspmd_axis_spans_processes(tmp_path):
    """GSPMD under a REAL 2-process job with a NON-data axis crossing the
    process boundary (VERDICT r4 Next #6 — the round-4 multi-host proof
    covered only shard_map-DP).

    Each process hosts 2 fake CPU devices (4 global); the mesh is
    fsdp=2 x tp=2 in MESH_AXES order, so the fsdp axis (ZeRO-3 parameter
    all-gather / gradient reduce-scatter) spans the two processes while tp
    stays process-local — the DCN-major layout parallel/mesh.py produces
    on a real pod. One jitted GSPMD program per process, XLA collectives
    over the boundary, loss finite, then a checkpoint save -> 2-process
    resume roundtrip. Steps stay tiny (XLA:CPU collective watchdog)."""
    import json

    ckpt = str(tmp_path / "ckpt")

    def train_cmd(steps: int) -> list:
        return [sys.executable, "train.py", "--backend", "cpu", "--model",
                "bert_tiny", "--batch-size", "4", "--fsdp", "2", "--tp",
                "2", "--synthetic", "--seq-len", "16", "--dtype",
                "float32", "--steps", str(steps), "--checkpoint-dir",
                ckpt, "--checkpoint-every", "2", "--log-every", "1000000"]

    env = dict(os.environ)
    # 2 fake devices per process: the 4-device mesh spans the processes.
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"

    def run2(steps: int):
        return subprocess.run(
            [sys.executable, "launch.py", "--num-processes", "2",
             "--port", "9411", "--"] + train_cmd(steps),
            capture_output=True, text=True, timeout=900, env=env)

    def summary_of(proc):
        lines = [ln for ln in proc.stdout.splitlines() if "summary" in ln]
        assert lines, (proc.returncode, proc.stderr[-2000:])
        return json.loads(lines[-1])["summary"]

    first = run2(2)
    assert first.returncode == 0, first.stderr[-2000:]
    s1 = summary_of(first)
    assert s1["final_step"] == 2
    import math
    assert math.isfinite(s1["final_metrics"]["loss"])

    second = run2(4)
    assert second.returncode == 0, second.stderr[-2000:]
    s2 = summary_of(second)
    assert s2["start_step"] == 2, s2  # resumed the multi-process save
    assert s2["final_step"] == 4


@pytest.mark.slow
def test_multihost_eval_uses_upfront_batch_agreement(tmp_path):
    """Multi-process eval over a REAL finite imagefolder split: the
    processes must agree on the global eval batch count via the upfront
    ``batches_hint`` collective (ADVICE r4) — and when the split holds
    fewer batches than requested, eval scores what exists on every
    process instead of deadlocking the collective eval step."""
    import json

    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(0)
    for split, count in (("train", 64), ("val", 24)):
        for i in range(count):
            cls = i % 2
            d = tmp_path / "data" / split / f"class{cls}"
            d.mkdir(parents=True, exist_ok=True)
            arr = rng.integers(0, 256, (32, 32, 3)).astype(np.uint8)
            arr[:, :, 0] = 200 if cls == 0 else 30
            Image.fromarray(arr).save(d / f"img{i}.jpg")

    env = dict(os.environ)
    env["XLA_FLAGS"] = ""  # 1 CPU device per process -> dp=2 spans procs
    env["JAX_PLATFORMS"] = "cpu"
    # global batch 8 -> per-process val shard 12 images = 3 full local
    # batches of 4; ask for 5 eval batches so the hint must clamp to 3.
    cmd = [sys.executable, "train.py", "--backend", "cpu", "--model",
           "resnet18_thin", "--batch-size", "8", "--dp", "2",
           "--data-dir", str(tmp_path / "data"), "--loader", "tf",
           "--dtype", "float32", "--steps", "4", "--eval-batches", "5",
           "--image-size", "32", "--log-every", "1000000"]
    proc = subprocess.run(
        [sys.executable, "launch.py", "--num-processes", "2",
         "--port", "9412", "--"] + cmd,
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if "summary" in ln]
    assert lines, proc.stderr[-2000:]
    summary = json.loads(lines[-1])["summary"]
    assert summary["final_step"] == 4
    # The final eval ran over the 3 available batches (clamped from 5).
    assert summary["eval_top1"] is not None
    assert "holds 3 of the 5 requested" in proc.stderr


@pytest.mark.slow
def test_max_restarts_auto_resumes(tmp_path):
    """--max-restarts closes the §5.3 loop in-launcher: the injected crash
    triggers an automatic relaunch that resumes from the checkpoint and
    finishes with rc 0 — no external wrapper needed."""
    import json

    ckpt = str(tmp_path / "ckpt")
    # --fail-at-step 3 fires on the first attempt only: the relaunch resumes
    # at step 2, and on reaching step 3 again the fault re-fires... so use a
    # fail step the resumed run skips: fail at 3, checkpoint at 2 means the
    # second attempt starts at 2 and would fail at 3 again. Instead inject
    # via a flag file the child consumes once.
    flag = tmp_path / "fail_once"
    flag.write_text("1")
    runner = tmp_path / "runner.py"
    runner.write_text(f"""
import os, subprocess, sys
cmd = [sys.executable, "train.py", "--backend", "cpu", "--model", "resnet18",
       "--batch-size", "8", "--dp", "1", "--synthetic", "--dtype", "float32",
       "--steps", "5", "--checkpoint-dir", {ckpt!r},
       "--checkpoint-every", "2", "--log-every", "1000000"]
if os.path.exists({str(flag)!r}):
    os.unlink({str(flag)!r})
    cmd += ["--fail-at-step", "3"]
sys.exit(subprocess.call(cmd))
""")
    env = dict(os.environ)
    proc = subprocess.run(
        [sys.executable, "launch.py", "--num-processes", "1",
         "--max-restarts", "2", "--", sys.executable, str(runner)],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "restart 1/2" in proc.stderr
    summary = json.loads(proc.stdout.strip().splitlines()[-1])["summary"]
    assert summary["start_step"] == 2 and summary["final_step"] == 5
