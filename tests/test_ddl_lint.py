"""ddl-lint: tier-1 gate + seeded-violation corpus (docs/static_analysis.md).

Two halves, both @pytest.mark.lint (audited by marker_audit --expect-lint):

- The gate: ``tools/ddl_lint.py`` must exit 0 on the clean repo — zero
  false positives is part of the analyzer's contract, so a new rule that
  fires on shipping code either found a real bug (fix the code) or is
  wrong (fix the rule). Never baseline your way past this test.
- The corpus: every rule must fire on its seeded violation and stay
  silent on the sanitized variant. A lint that cannot catch the bug it
  was built for (the PR 5 donation-after-restore crash, the PR 9
  snapshot-before-save corruption, a mismatched replica_groups deadlock)
  is decoration.

Plus tolerant-reader coverage: truncated HLO dumps, unknown custom-call
targets, and garbage inputs must degrade to ``errors`` entries, never
exceptions — a broken analyzer must not read as a broken repo.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from distributeddeeplearning_tpu.analysis import collectives as ca
from distributeddeeplearning_tpu.analysis import donation, lints

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT_CLI = os.path.join(REPO, "tools", "ddl_lint.py")

MESH_AXES = {"data", "fsdp"}


def _rules(findings):
    return [f["rule"] for f in findings]


def _run_cli(*args, timeout=420):
    return subprocess.run(
        [sys.executable, LINT_CLI, *args], capture_output=True,
        text=True, cwd=REPO, timeout=timeout)


# ---------------------------------------------------------------------------
# The tier-1 gate: clean repo => exit 0, zero findings
# ---------------------------------------------------------------------------

def test_clean_repo_gate(tmp_path):
    """The acceptance gate: all three passes over the shipping repo come
    back empty. Runs the real CLI (fresh interpreter, the entry CI uses);
    the fingerprint registry is pointed at a tmp
    file so ambient .cache state can neither mask nor seed a failure."""
    reg = str(tmp_path / "registry.json")
    proc = _run_cli("--json", "--no-record", "--fingerprint-registry", reg)
    assert proc.returncode == 0, f"\n{proc.stdout}\n{proc.stderr}"
    report = json.loads(proc.stdout)
    assert report["ok"] is True
    assert report["findings"] == []
    assert set(report["passes"]) == {"collectives", "donation", "lints"}
    # Both all-reduce programs traced and fingerprinted (bench provenance
    # and the AOT pairing registry consume these).
    for name in ("allreduce_psum", "allreduce_ring"):
        fp = report["collective_schedules"][name]
        assert len(fp) == 16
        int(fp, 16)  # hex
    # psum and ring are different programs; identical fingerprints would
    # mean the fingerprint is not actually a function of the schedule.
    assert (report["collective_schedules"]["allreduce_psum"]
            != report["collective_schedules"]["allreduce_ring"])


def test_checked_in_baseline_is_empty():
    """The repo lints clean, so the committed baseline must stay empty —
    a suppression sneaking in here would un-gate a real finding."""
    with open(os.path.join(REPO, "tools", "ddl_lint_baseline.json"),
              encoding="utf-8") as fh:
        baseline = json.load(fh)
    assert baseline.get("suppressions") == []


# ---------------------------------------------------------------------------
# Seeded corpus: donation pass (the PR 5 / PR 9 bug classes)
# ---------------------------------------------------------------------------

_PR5_REPRO = textwrap.dedent("""
    def run(ckpt, state, batch, rng):
        restored = ckpt.restore_latest(state)
        if restored is not None:
            state = restored
        state, metrics = train_step(state, batch, rng)
        return state, metrics
""")

_PR5_FIXED = textwrap.dedent("""
    def run(ckpt, state, batch, rng):
        restored = ckpt.restore_latest(state)
        if restored is not None:
            state = device_copy(restored)
        state, metrics = train_step(state, batch, rng)
        return state, metrics
""")


def test_donation_hazard_pr5_repro():
    """The exact PR 5 shape: orbax-restored arrays reach the donated
    train_step argument with no device_copy — donated-buffer reuse."""
    findings = donation.analyze_source(_PR5_REPRO, "seed_pr5.py")
    assert "donation-hazard" in _rules(findings), findings
    (f,) = [f for f in findings if f["rule"] == "donation-hazard"]
    assert "train_step" in f["message"]
    assert f["line"] == _PR5_REPRO[:_PR5_REPRO.index("train_step(")
                                   ].count("\n") + 1


def test_donation_hazard_sanitized_by_device_copy():
    assert donation.analyze_source(_PR5_FIXED, "fixed.py") == []


def test_donation_hazard_module_local_donor():
    """A jit with donate_argnums assigned in the module under analysis is
    a donating callee even though it is not in DONATING_CALLEES."""
    src = textwrap.dedent("""
        import jax
        step = jax.jit(_step, donate_argnums=(0,))

        def run(ckpt, state, batch):
            state = ckpt.restore_latest(state)
            return step(state, batch)
    """)
    assert "donation-hazard" in _rules(
        donation.analyze_source(src, "local_donor.py"))


def test_donation_taint_survives_branch_union():
    """Taint from ONE branch of an if/else must survive the join — the
    PR 5 bug only bit when a checkpoint actually existed."""
    src = textwrap.dedent("""
        def run(ckpt, state, batch, rng):
            if resume:
                state = ckpt.restore_latest(state)
            else:
                state = init_state()
            return train_step(state, batch, rng)
    """)
    assert "donation-hazard" in _rules(
        donation.analyze_source(src, "branchy.py"))


def test_snapshot_before_save_pr9_repro():
    """The PR 9 shape: live (donatable) state handed to an async orbax
    StandardSave with no device_copy snapshot."""
    src = textwrap.dedent("""
        def save_ckpt(mngr, state, step):
            mngr.save(step, args=StandardSave(state))
    """)
    findings = donation.analyze_source(src, "seed_pr9.py")
    assert _rules(findings) == ["snapshot-before-save"], findings


def test_snapshot_before_save_fixed_by_snapshot():
    src = textwrap.dedent("""
        def save_ckpt(mngr, state, step):
            snap = device_copy(state)
            mngr.save(step, args=StandardSave(snap))
    """)
    assert donation.analyze_source(src, "fixed_pr9.py") == []


def test_snapshot_before_save_blocking_save_exempt():
    """A save the function itself blocks on cannot race a later donation
    (tools/import_hf.py's one-shot conversion save)."""
    src = textwrap.dedent("""
        def convert(mngr, state):
            mngr.save(0, args=StandardSave(state))
            mngr.wait_until_finished()
    """)
    assert donation.analyze_source(src, "import_like.py") == []


# ---------------------------------------------------------------------------
# Seeded corpus: repo-invariant lints
# ---------------------------------------------------------------------------

def test_lint_sidecar_direct_write():
    src = textwrap.dedent("""
        import json, os

        def dump(repo, payload):
            path = os.path.join(repo, ".cache", "last_foo.json")
            with open(path, "w") as fh:
                json.dump(payload, fh)
    """)
    findings = lints.analyze_source(src, "direct.py", mesh_axes=MESH_AXES)
    assert "sidecar-direct-write" in _rules(findings), findings


def test_lint_sidecar_routed_write_clean():
    src = textwrap.dedent("""
        from distributeddeeplearning_tpu.observability import sidecars

        def dump(payload):
            sidecars.write("last_foo", payload)
    """)
    assert lints.analyze_source(src, "routed.py", mesh_axes=MESH_AXES) == []


def test_lint_fsync_before_fire():
    src = textwrap.dedent("""
        import os, signal

        def fire(sig):
            os.kill(os.getpid(), sig)
    """)
    findings = lints.analyze_source(src, "fire.py", mesh_axes=MESH_AXES)
    assert "fsync-before-fire" in _rules(findings), findings


def test_lint_fsync_before_fire_recorded_clean():
    """faults.py's actual shape: a flight record made durable before the
    self-kill is fine regardless of statement nesting order."""
    src = textwrap.dedent("""
        import os, signal

        def fire(rec, sig):
            rec.record("fault_fired", signal=sig)
            os.kill(os.getpid(), sig)
    """)
    assert lints.analyze_source(src, "fire_ok.py",
                                mesh_axes=MESH_AXES) == []


def test_lint_unpaired_span():
    src = textwrap.dedent("""
        def step(tele):
            tele.span("backward")
            run_backward()
    """)
    findings = lints.analyze_source(src, "span.py", mesh_axes=MESH_AXES)
    assert "unpaired-telemetry-span" in _rules(findings), findings


def test_lint_entered_span_clean():
    src = textwrap.dedent("""
        def step(tele):
            with tele.span("backward"):
                run_backward()
    """)
    assert lints.analyze_source(src, "span_ok.py",
                                mesh_axes=MESH_AXES) == []


def test_lint_perf_record_provenance():
    src = textwrap.dedent("""
        import json

        def emit():
            rec = {"metric": "step_time", "value": 1.0}
            print(json.dumps(rec))
    """)
    findings = lints.analyze_source(src, "perf.py", mesh_axes=MESH_AXES)
    assert "perf-record-provenance" in _rules(findings), findings


def test_lint_perf_record_annotated_clean():
    src = textwrap.dedent("""
        import json

        def emit():
            rec = {"metric": "step_time", "value": 1.0}
            print(json.dumps(perf_report.annotate(rec,
                                                  provenance="fresh")))
    """)
    assert lints.analyze_source(src, "perf_ok.py",
                                mesh_axes=MESH_AXES) == []


def test_lint_axis_name_typo():
    src = textwrap.dedent("""
        import jax

        def g(x):
            return jax.lax.psum(x, "dataa")
    """)
    findings = lints.analyze_source(src, "axes.py", mesh_axes=MESH_AXES)
    assert "axis-name-consistency" in _rules(findings), findings
    assert "dataa" in findings[0]["message"]


def test_lint_axis_names_declared_clean():
    src = textwrap.dedent("""
        import jax

        AXES = ("data", "fsdp")

        def g(x):
            a = jax.lax.psum(x, ("data", "fsdp"))
            return jax.lax.pmean(a, axis_name="data") + jax.lax.psum(
                a, AXES)
    """)
    assert lints.analyze_source(src, "axes_ok.py",
                                mesh_axes=MESH_AXES) == []


# ---------------------------------------------------------------------------
# Seeded corpus: collective-schedule pass
# ---------------------------------------------------------------------------

_HLO_RANK0 = textwrap.dedent("""\
    HloModule step

    ENTRY %main (p0: f32[2]) -> f32[16] {
      %p0 = f32[2]{0} parameter(0)
      %ag = f32[16]{0} all-gather(f32[2]{0} %p0), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
      %ar = f32[16]{0} all-reduce(f32[16]{0} %ag), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
      ROOT %out = f32[16]{0} copy(f32[16]{0} %ar)
    }
""")

# Same program shape, but rank 1's all-reduce was lowered with split
# replica groups — the classic mismatched-replica_groups deadlock.
_HLO_RANK1 = _HLO_RANK0.replace(
    "all-reduce(f32[16]{0} %ag), replica_groups={{0,1,2,3,4,5,6,7}}",
    "all-reduce(f32[16]{0} %ag), replica_groups={{0,1,2,3},{4,5,6,7}}")


def test_hlo_mismatched_replica_groups_divergence():
    schedules = {"rank0": ca.extract_from_hlo_text(_HLO_RANK0),
                 "rank1": ca.extract_from_hlo_text(_HLO_RANK1)}
    assert schedules["rank0"].errors == ()
    findings = ca.verify_uniform(schedules)
    assert _rules(findings) == ["schedule-divergence"], findings
    # Op 0 (the all-gather) agrees; the finding must park on op 1.
    assert "at op 1" in findings[0]["message"]


def test_hlo_cli_mode_gates_on_divergence(tmp_path):
    """Acceptance: seeded mismatched replica_groups through the real CLI
    exits nonzero; identical dumps exit zero."""
    a = tmp_path / "rank0.hlo.txt"
    b = tmp_path / "rank1.hlo.txt"
    a.write_text(_HLO_RANK0)
    b.write_text(_HLO_RANK1)
    proc = _run_cli("--json", "--hlo", str(a), str(b),
                    "--only", "collectives")
    assert proc.returncode == 1, proc.stdout
    report = json.loads(proc.stdout)
    assert [f["rule"] for f in report["findings"]] == [
        "schedule-divergence"]

    b.write_text(_HLO_RANK0)
    proc = _run_cli("--json", "--hlo", str(a), str(b),
                    "--only", "collectives")
    assert proc.returncode == 0, proc.stdout


def test_jaxpr_extraction_fingerprints_collectives(devices8):
    """schedule_of sees through shard_map's sub-jaxpr and the fingerprint
    is a function of the actual op sequence."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from distributeddeeplearning_tpu import compat
    from distributeddeeplearning_tpu.config import ParallelConfig
    from distributeddeeplearning_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(ParallelConfig(data=8), backend="cpu")

    def one(x):
        return jax.lax.psum(x, ("data", "fsdp"))

    def two(x):
        return jax.lax.psum(jax.lax.psum(x, "data"), ("data", "fsdp"))

    def trace(f):
        fn = compat.shard_map(f, mesh=mesh, in_specs=P(("data", "fsdp")),
                              out_specs=P())
        return ca.schedule_of(fn, jnp.ones((8, 2)))

    one_s, two_s = trace(one), trace(two)
    assert [op.kind for op in one_s.ops] == ["psum"], one_s.describe()
    assert one_s.ops[0].axes == ("data", "fsdp")
    assert one_s.errors == ()
    assert [op.kind for op in two_s.ops] == ["psum", "psum"]
    assert one_s.fingerprint() != two_s.fingerprint()


def test_aot_pairing_divergence(tmp_path):
    reg = str(tmp_path / "registry.json")
    assert ca.check_aot_pairing("cfg1", "prog", "aaaa",
                                registry_path=reg) == []
    # Same pair again: silent.
    assert ca.check_aot_pairing("cfg1", "prog", "aaaa",
                                registry_path=reg) == []
    # Same config fingerprint, different schedule: the AOT contract break.
    findings = ca.check_aot_pairing("cfg1", "prog", "bbbb",
                                    registry_path=reg)
    assert _rules(findings) == ["aot-schedule-pairing"], findings
    # A different config is a new pair, not a divergence.
    assert ca.check_aot_pairing("cfg2", "prog", "bbbb",
                                registry_path=reg) == []


# ---------------------------------------------------------------------------
# Tolerant readers: degrade, never crash
# ---------------------------------------------------------------------------

def test_truncated_hlo_degrades():
    # Tear the dump mid-replica_groups on the all-gather line: the op is
    # kept (without groups), the tear is reported, nothing raises.
    idx = _HLO_RANK0.index("replica_groups={{0,1,2,3")
    torn = _HLO_RANK0[:idx + len("replica_groups={{0,1,2")]
    sched = ca.extract_from_hlo_text(torn)
    assert any("truncated" in e for e in sched.errors), sched.errors
    assert any("mid-brace" in e for e in sched.errors), sched.errors
    assert [op.kind for op in sched.ops] == ["all-gather"]
    assert sched.ops[0].groups is None
    sched.fingerprint()  # partial schedule still fingerprints


def test_unknown_custom_call_tolerated():
    text = ('  %cc = f32[8]{0} custom-call(f32[8]{0} %x), '
            'custom_call_target="mosaic_pallas_mystery_kernel"\n')
    sched = ca.extract_from_hlo_text(text)
    assert len(sched.ops) == 1
    assert sched.ops[0].kind == "custom-call"
    assert "tolerated" in (sched.ops[0].note or "")
    assert sched.errors == ()


def test_known_custom_call_collective_kept():
    text = ('  %cc = f32[8]{0} custom-call(f32[8]{0} %x), '
            'custom_call_target="xla.gpu.AllReduceKernel"\n')
    sched = ca.extract_from_hlo_text(text)
    assert sched.ops[0].kind.startswith("custom-call:")


def test_garbage_inputs_never_raise():
    for junk in (None, 42, object(), "not a jaxpr"):
        sched = ca.extract_from_jaxpr(junk)
        assert isinstance(sched, ca.Schedule)
    sched = ca.extract_from_hlo_text(b"bytes not text")
    assert sched.ops == () and sched.errors
    assert donation.analyze_source("def broken(:", "bad.py")[0][
        "rule"] == "unparseable"
    assert lints.analyze_source("def broken(:", "bad.py")[0][
        "rule"] == "unparseable"


def test_async_hlo_pairs_count_once():
    text = textwrap.dedent("""\
        %s = f32[8]{0} all-reduce-start(f32[8]{0} %x), replica_groups={{0,1}}
        %d = f32[8]{0} all-reduce-done(f32[8]{0} %s)
    """)
    sched = ca.extract_from_hlo_text(text)
    assert [op.kind for op in sched.ops] == ["all-reduce"]


# ---------------------------------------------------------------------------
# Baseline suppression workflow
# ---------------------------------------------------------------------------

def test_baseline_suppresses_via_cli(tmp_path):
    seeded = tmp_path / "seeded_violation.py"
    seeded.write_text(_PR5_REPRO)

    proc = _run_cli("--json", "--paths", str(seeded), "--baseline", "none")
    assert proc.returncode == 1
    report = json.loads(proc.stdout)
    assert any(f["rule"] == "donation-hazard"
               for f in report["findings"])

    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({"suppressions": [
        {"rule": "donation-hazard", "file": "seeded_violation.py"}]}))
    proc = _run_cli("--json", "--paths", str(seeded),
                    "--baseline", str(baseline))
    assert proc.returncode == 0, proc.stdout
    report = json.loads(proc.stdout)
    assert report["findings"] == []
    assert any(f["rule"] == "donation-hazard"
               for f in report["suppressed"])


# ---------------------------------------------------------------------------
# Bench provenance: records name the schedule they measured under
# ---------------------------------------------------------------------------

def test_annotate_attaches_schedule_fingerprints(monkeypatch, tmp_path):
    import time as _time

    from distributeddeeplearning_tpu.observability import (perf_report,
                                                           sidecars)

    monkeypatch.setattr(sidecars, "cache_dir", lambda: str(tmp_path))
    sidecars.write("last_ddl_lint", {
        "ok": True, "collective_schedules": {"allreduce_psum": "abcd"}})

    rec = perf_report.annotate({"metric": "m", "value": 1.0},
                               provenance="fresh", with_backend=False)
    assert rec["collective_schedules"] == {"allreduce_psum": "abcd"}

    # Error records measured nothing; no schedule to name.
    err = perf_report.annotate({"metric": "m", "value": None, "error": "x"},
                               provenance="error", with_backend=False)
    assert "collective_schedules" not in err

    # A stale lint run describes some other build: not attached.
    sidecars.write("last_ddl_lint", {
        "ok": True, "collective_schedules": {"allreduce_psum": "abcd"},
        "written_at": _time.time()
        - 2 * perf_report.LINT_SCHEDULES_MAX_AGE_S})
    old = perf_report.annotate({"metric": "m", "value": 1.0},
                               provenance="fresh", with_backend=False)
    assert "collective_schedules" not in old


def test_lint_cow_before_write():
    """The serve fast path's COW audit invariant: a function dispatching
    a KV page copy with no prior flight record leaves shared-page bugs
    unattributable."""
    src = textwrap.dedent("""
        def admit(self, src_page, dst_page):
            self._run_page_copy(src_page, dst_page)
    """)
    findings = lints.analyze_source(src, "cow.py", mesh_axes=MESH_AXES)
    assert "cow-before-write" in _rules(findings), findings


# ---------------------------------------------------------------------------
# Seeded corpus: pipeline-schedule-pairing (MPMD permute deadlock class)
# ---------------------------------------------------------------------------

def _pipe_table(name="1f1b", p=2, m=4, v=2):
    from distributeddeeplearning_tpu.models import pipeline as plib
    return plib.build_schedule(name, num_stages=p, num_microbatches=m,
                               virtual_stages=v)


def test_pipeline_pairing_clean_corpus():
    """Every schedule geometry the repo ships — registry pp models' (P, M)
    under gpipe plus the interleaved variants — verifies pairing-clean.
    A finding here is a real deadlock in the shipped schedule table."""
    for name, p, m, v in (("gpipe", 2, 4, 1), ("gpipe", 4, 8, 1),
                          ("gpipe", 2, 6, 1), ("1f1b", 2, 4, 1),
                          ("1f1b", 2, 4, 2), ("1f1b", 4, 8, 2),
                          ("1f1b", 2, 8, 4)):
        table = _pipe_table(name, p, m, v)
        assert ca.verify_pipeline_pairing(f"{name}_p{p}m{m}v{v}",
                                          table) == []


def test_pipeline_pairing_fires_on_wrap_inject_collision():
    """Seeded violation: an inject flag forced onto a wrap-receive tick.
    Stage 0's program would take the ring wrap and a fresh microbatch in
    the same shift — the colliding-writes half of the deadlock class —
    and the conservation check sees a phantom injection."""
    import dataclasses

    table = _pipe_table()
    ticks = list(table.ticks)
    for i, tk in enumerate(ticks):
        if tk.occupancy[0] is not None and tk.occupancy[0][1] > 0:
            ticks[i] = dataclasses.replace(tk, inject_mb=99)
            break
    bad = dataclasses.replace(table, ticks=tuple(ticks))
    findings = ca.verify_pipeline_pairing("seeded", bad)
    assert findings and set(_rules(findings)) == {
        "pipeline-schedule-pairing"}
    assert any("waits on a send" in f["message"] for f in findings)


def test_pipeline_pairing_fires_on_divergent_stage_view():
    """Seeded violation: one tick's occupancy permuted across stages — as
    if stage programs were generated from different tables. The dataflow
    check names the tick where the per-stage schedules disagree."""
    import dataclasses

    table = _pipe_table()
    ticks = list(table.ticks)
    tk = ticks[3]
    ticks[3] = dataclasses.replace(tk, occupancy=tuple(
        reversed(tk.occupancy)))
    bad = dataclasses.replace(table, ticks=tuple(ticks))
    findings = ca.verify_pipeline_pairing("seeded", bad)
    assert any("per-stage schedules disagree" in f["message"]
               for f in findings), findings
    assert set(_rules(findings)) == {"pipeline-schedule-pairing"}


def test_permute_schedule_fingerprints_differ_by_geometry():
    """The rendered permute schedule is a function of (schedule, P, M, V):
    gpipe (no wrap traffic) and 1f1b at the same geometry must not
    collide, nor must different V."""
    fps = {(n, p, m, v): ca.permute_schedule(
               _pipe_table(n, p, m, v)).fingerprint()
           for n, p, m, v in (("gpipe", 2, 4, 1), ("1f1b", 2, 4, 2),
                              ("1f1b", 2, 8, 2))}
    assert len(set(fps.values())) == len(fps)
    ops = ca.permute_schedule(_pipe_table("1f1b", 2, 4, 2)).ops
    assert all(op.kind == "ppermute" and op.axes == ("pipeline",)
               for op in ops)


def test_hlo_source_target_pairs_extracted():
    """collective-permute pairs come out of an HLO dump and participate
    in the fingerprint — two stage programs lowered with different pair
    lists must diverge."""
    a = ('  %cp = f32[8]{0} collective-permute(f32[8]{0} %x), '
         'source_target_pairs={{0,1},{1,0}}\n')
    b = ('  %cp = f32[8]{0} collective-permute(f32[8]{0} %x), '
         'source_target_pairs={{0,1}}\n')
    sa, sb = ca.extract_from_hlo_text(a), ca.extract_from_hlo_text(b)
    assert sa.ops[0].kind == "collective-permute"
    assert sa.ops[0].pairs == ((0, 1), (1, 0))
    assert sb.ops[0].pairs == ((0, 1),)
    assert sa.fingerprint() != sb.fingerprint()
    findings = ca.verify_uniform({"stage0": sa, "stage1": sb})
    assert _rules(findings) == ["schedule-divergence"]


def test_jaxpr_ppermute_pairs_extracted(devices8):
    """jaxpr extraction captures the `perm` pairs of a ppermute — the
    shift pattern the pipeline's activation ring compiles down to."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from distributeddeeplearning_tpu import compat
    from distributeddeeplearning_tpu.config import ParallelConfig
    from distributeddeeplearning_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(ParallelConfig(data=8), backend="cpu")
    perm = [(k, (k + 1) % 8) for k in range(8)]

    def f(x):
        return jax.lax.ppermute(x, "data", perm)

    fn = compat.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P())
    sched = ca.schedule_of(fn, jnp.ones((8, 2)))
    assert [op.kind for op in sched.ops] == ["ppermute"], sched.describe()
    assert sched.ops[0].pairs == tuple(perm)


def test_lint_cow_recorded_clean():
    """engine.py's actual shape: the serve_cow_copy record precedes the
    copy dispatch."""
    src = textwrap.dedent("""
        def admit(self, rec, src_page, dst_page):
            rec.record("serve_cow_copy", src=src_page, dst=dst_page)
            self._run_page_copy(src_page, dst_page)
    """)
    assert lints.analyze_source(src, "cow_ok.py",
                                mesh_axes=MESH_AXES) == []


# ---------------------------------------------------------------------------
# Seeded corpus: master-weight-cast (ISSUE 20)
# ---------------------------------------------------------------------------

def test_lint_master_weight_cast_astype_fires():
    src = textwrap.dedent("""
        import jax.numpy as jnp

        def shrink(state):
            return state.opt_state.astype(jnp.bfloat16)
    """)
    findings = lints.analyze_source(src, "cast.py", mesh_axes=MESH_AXES)
    assert "master-weight-cast" in _rules(findings), findings


def test_lint_master_weight_cast_constructor_fires():
    """A dtype=-carrying array constructor retypes its argument just as
    silently as astype."""
    src = textwrap.dedent("""
        import jax.numpy as jnp

        def shrink(master_params):
            return jnp.asarray(master_params, dtype="float16")
    """)
    findings = lints.analyze_source(src, "ctor.py", mesh_axes=MESH_AXES)
    assert "master-weight-cast" in _rules(findings), findings


def test_lint_master_weight_cast_fp32_and_params_clean():
    """fp32 casts of masters, and sub-fp32 casts of NON-master values
    (activations, gathered params on the wire), are both fine."""
    src = textwrap.dedent("""
        import jax.numpy as jnp

        def keep(state, chunks):
            a = state.opt_state.astype(jnp.float32)
            b = chunks.astype(jnp.bfloat16)
            return a, b
    """)
    assert lints.analyze_source(src, "clean.py",
                                mesh_axes=MESH_AXES) == []


def test_lint_master_weight_cast_sanctioned_helper_clean():
    """parallel/zero.py's gather helpers legitimately cast to the wire
    dtype; their bodies are exempt by name."""
    src = textwrap.dedent("""
        import jax.numpy as jnp

        def _gather_members(opt_state_chunks, out_dtype):
            return opt_state_chunks.astype(jnp.bfloat16)
    """)
    assert lints.analyze_source(src, "sanctioned.py",
                                mesh_axes=MESH_AXES) == []


def test_lint_master_weight_cast_repo_clean():
    """The rule must hold on the real precision-policy code: steps.py and
    zero.py cast activations/gathered params, never masters."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rel in ("distributeddeeplearning_tpu/train/steps.py",
                "distributeddeeplearning_tpu/parallel/zero.py",
                "distributeddeeplearning_tpu/train/optim.py"):
        with open(os.path.join(root, rel)) as fh:
            findings = [f for f in lints.analyze_source(
                fh.read(), rel, mesh_axes=MESH_AXES)
                if f["rule"] == "master-weight-cast"]
        assert findings == [], (rel, findings)
