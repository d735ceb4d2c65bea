"""chip_smoke.py's control flow, rehearsed on the CPU.

``--rehearse`` shrinks sizes only, so these runs walk the same phases,
children, entry points and checks the chip run walks — they prove the
script's plumbing, and nothing about the chip. Each rehearsal runs once per
module (a fixture) and the cases below read its output.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, *, cwd=REPO, script=SMOKE, timeout=900, **env_extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(env_extra)
    proc = subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)
    recs = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.startswith("{")]
    return proc, recs


@pytest.fixture(scope="module")
def placed_cache(tmp_path_factory):
    """The rehearsals' compile cache, placed from outside and shared."""
    return str(tmp_path_factory.mktemp("smoke-cache"))


@pytest.fixture(scope="module")
def one_chip(placed_cache):
    return _run(["--rehearse"], JAX_COMPILATION_CACHE_DIR=placed_cache)


@pytest.fixture(scope="module")
def four_chips(placed_cache):
    return _run(["--rehearse", "--four-chips"],
                JAX_COMPILATION_CACHE_DIR=placed_cache)


def _phase(recs, name):
    found = [r for r in recs if r.get("phase") == name]
    assert found, f"no {name} line in {[r.get('phase') for r in recs]}"
    return found[-1]


def test_rehearsal_last_line_is_the_contract(one_chip):
    proc, _ = one_chip
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}


@pytest.mark.parametrize("phase", [
    "train_xla", "train_flash", "train_fused_block", "kernels", "serve",
    "serve_reference", "serve_warm_boot", "serve_checks"])
def test_rehearsal_runs_every_one_chip_phase(one_chip, phase):
    _, recs = one_chip
    assert _phase(recs, phase)["ok"] is True


def test_rehearsal_train_phases_go_through_train_py(one_chip):
    _, recs = one_chip
    for name in ("train_xla", "train_flash", "train_fused_block"):
        rec = _phase(recs, name)
        assert "train.py --backend cpu --synthetic" in rec["cmd"]
        assert rec["loss_last"] != rec["loss_first"]
        assert rec["compile_time_s"] > 0
    assert "--attn flash" in _phase(recs, "train_flash")["cmd"]
    assert "--fused-block" in _phase(recs, "train_fused_block")["cmd"]
    # the Pallas fused step is held to the plain XLA step, loss for loss
    vs = _phase(recs, "train_fused_block_vs_train_xla")
    assert vs["steps_compared"] == 4 and vs["max_rel_loss_diff"] < 1e-2


def test_rehearsal_serve_is_checked_against_generate_and_warm_boots(
        one_chip):
    _, recs = one_chip
    cold, warm = _phase(recs, "serve"), _phase(recs, "serve_warm_boot")
    assert cold["finished"] == cold["requests"] == 8
    assert cold["leak_check_ok"] and warm["leak_check_ok"]
    assert cold["aot"][0]["aot_misses"] > 0  # cold: compiled and saved
    assert warm["aot"][0]["aot_hits"] > 0 and not warm["aot"][0][
        "aot_misses"]
    checks = _phase(recs, "serve_checks")
    # two streams held to sequential generate: identical, or parted at a tie
    assert sorted(checks["streams_checked_against_generate"]) == ["1", "5"]
    for rec in checks["streams_checked_against_generate"].values():
        assert rec["identical_tokens"] > 0
    assert checks["warm_tokens_identical"] is True


def test_rehearsal_cache_lands_where_the_environment_placed_it(
        one_chip, placed_cache):
    _, recs = one_chip
    assert _phase(recs, "compile_cache")["dir"] == placed_cache
    assert os.path.isdir(os.path.join(placed_cache, "aot"))
    assert _phase(recs, "train_xla")["cache_entries_after"] > 0


def test_four_chip_rehearsal_on_four_virtual_devices(four_chips):
    proc, recs = four_chips
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["device"]["count"] == 4
    # that path and what it is compared with — no one-chip phase
    phases = {r.get("phase") for r in recs}
    assert not phases & {"train_xla", "train_flash", "kernels"}
    for name in ("train_dp4_vs_train_dp1", "train_dp4_zero1_vs_train_dp1"):
        assert _phase(recs, name)["max_rel_loss_diff"] < 2e-2
    layout = _phase(recs, "dp_layout")
    assert layout["dp4"]["batch_devices"] == [0, 1, 2, 3]
    assert layout["dp4_zero1"]["opt_state_devices"] == [0, 1, 2, 3]
    four = _phase(recs, "serve_4_replicas")
    assert four["finished"] == four["requests"] == 16
    assert sorted(d["visible_chips"] for d in four["replica_devices"]) == [
        "0", "1", "2", "3"]
    vs = _phase(recs, "serve_checks")["streams_4_replicas_vs_1"]
    assert len(vs) == 16 and all(r["identical_tokens"] > 0
                                 for r in vs.values())


def test_forced_phase_failure_exits_nonzero():
    proc, recs = _run(["--rehearse", "--fail-phase", "train_xla"])
    assert proc.returncode != 0
    assert recs[-1]["ok"] is False and "train_xla" in recs[-1]["error"]
    assert not any(r.get("ok") is True and "device" in r and
                   "phase" not in r for r in recs)


def test_without_a_chip_it_fails_and_prints_no_result():
    """As the driver runs it (no option) where JAX finds no accelerator:
    the first child refuses to train on the CPU, and no result is printed."""
    proc, recs = _run([], JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert recs[-1]["ok"] is False
    assert '"ok": true' not in proc.stdout


def test_alone_in_a_directory_it_fails(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc, recs = _run([], cwd=str(tmp_path), script=alone,
                      JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
