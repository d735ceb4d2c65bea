"""bench.py control flow with the measurement stubbed out — the suite
loop, sweep emit-only-if-faster rule, per-row error records, and the exit
code (non-zero unless a fresh measurement landed) are driver-facing
contracts that must not depend on a live chip to be tested."""

import importlib.util
import json
import os
import sys

import pytest


@pytest.fixture()
def bench(monkeypatch, tmp_path):
    spec = importlib.util.spec_from_file_location(
        "bench_under_test",
        os.path.join(os.path.dirname(__file__), "..", "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _args(bench, extra=()):
    # Parse exactly as main() would, then return the namespace.
    import argparse  # noqa: F401
    # --platform cpu: without it the child needs a TPU (pinned below).
    argv = ["--run-child", "--platform", "cpu", *extra]
    # Reuse main's parser by intercepting _child.
    ns = {}

    def fake_child(a):
        ns["args"] = a
        return 0

    bench._child, orig = fake_child, bench._child
    try:
        bench.main(argv)
    finally:
        bench._child = orig
    return ns["args"]


def test_suite_rows_reset_flags_and_filter(bench, monkeypatch, capsys):
    seen = []

    def fake_measure(row, emit_quick=True, emit_final=True, deadline=None):
        seen.append((row.model, row.batch_size, row.attention_impl,
                     row.remat))
        if row.model == "densenet121":
            raise RuntimeError("boom")  # must yield an error record
        print(json.dumps({"metric": f"{row.model}_x", "value": 1.0}),
              flush=True)
        return 1.0

    monkeypatch.setattr(bench, "_child_measure", fake_measure)
    args = _args(bench, ["--suite", "--fused-bn", "--remat",
                         "--suite-models",
                         "resnet50,densenet121,bert_base"])

    rc = bench._child(args)
    assert rc == 0
    models = [s[0] for s in seen]
    # SUITE's value-per-minute order: resnet50 + the two allreduce A/B
    # rows + the three zero-ladder rows (all resnet50), bert flash,
    # (gpt2 filtered out), bert dense, (resnet152 filtered),
    # densenet121, (vit filtered), bert 2048, then the two large-batch
    # precision A/B rows (resnet50 again; pp rows filtered).
    assert models == ["resnet50"] * 6 + ["bert_base", "bert_base",
                                         "densenet121", "bert_base",
                                         "resnet50", "resnet50"]
    # Suite rows must NOT inherit headline flags; row overrides apply.
    assert all(s[3] is False for s in seen[:3])  # remat reset
    out = [json.loads(line) for line in
           capsys.readouterr().out.strip().splitlines()]
    errors = [r for r in out if r.get("value") is None]
    assert len(errors) == 1 and "boom" in errors[0]["error"]


def test_sweep_emits_only_if_faster(bench, monkeypatch, capsys):
    rates = {512: 100.0, 256: 90.0, 128: 120.0}

    def fake_measure(row, emit_quick=True, emit_final=True):
        rate = rates[row.batch_size]
        if emit_final:
            bench._emit_metric(row, rate, protocol=f"b{row.batch_size}")
        return rate

    monkeypatch.setattr(bench, "_child_measure", fake_measure)
    args = _args(bench, ["--model", "resnet50", "--sweep", "256,128"])

    bench._child(args)
    out = [json.loads(line) for line in
           capsys.readouterr().out.strip().splitlines()]
    # Primary (100) emitted; b256 (90) silent; b128 (120) emitted.
    values = [r["value"] for r in out]
    assert values == [100.0, 120.0]
    assert "sweep" in out[-1]["protocol"]


def test_fused_block_alternate_emits_only_if_faster(bench, monkeypatch,
                                                    capsys):
    """The headline run measures the conv-epilogue-fusion variant at the
    winning batch and emits it only on a strict win (same last-line-wins
    discipline as the batch sweep)."""
    for fused_rate, expect_emitted in ((130.0, True), (80.0, False)):
        rates = {(512, False): 100.0, (256, False): 90.0,
                 (512, True): fused_rate}

        def fake_measure(row, emit_quick=True, emit_final=True):
            rate = rates[(row.batch_size, row.fused_block)]
            if emit_final:
                bench._emit_metric(row, rate, protocol=f"b{row.batch_size}")
            return rate

        monkeypatch.setattr(bench, "_child_measure", fake_measure)
        args = _args(bench, ["--model", "resnet50"])  # sweep stays "auto"

        bench._child(args)
        out = [json.loads(line) for line in
               capsys.readouterr().out.strip().splitlines()]
        fused = [r for r in out if "fusedblock" in (r.get("protocol") or "")]
        assert bool(fused) == expect_emitted, (fused_rate, out)
        if expect_emitted:
            assert out[-1]["value"] == fused_rate  # last line wins


def test_suite_budget_skips_and_admits_rows(bench, monkeypatch, capsys):
    """A row whose estimate doesn't fit the remaining suite budget is
    skipped WITH a stderr note, and cheaper rows behind it are still
    admitted (a cut run yields the best prefix, not a silent truncation)."""
    seen = []

    def fake_measure(row, emit_quick=True, emit_final=True, deadline=None):
        seen.append((row.model, deadline))
        print(json.dumps({"metric": f"{row.model}_x", "value": 1.0}),
              flush=True)
        return 1.0

    monkeypatch.setattr(bench, "_child_measure", fake_measure)
    monkeypatch.setattr(bench, "SUITE", (
        ("resnet50", "resnet50", {}, 10_000),  # can't fit: skip + note
        ("gpt2_1024", "gpt2_small",
         {"batch_size": 16, "seq_len": 1024}, 1),  # fits
    ))
    args = _args(bench, ["--suite", "--suite-budget", "5"])

    rc = bench._child(args)
    assert rc == 0
    assert [s[0] for s in seen] == ["gpt2_small"]
    # The admitted row carries a concrete per-row deadline.
    assert seen[0][1] is not None
    captured = capsys.readouterr()
    assert "SKIPPED on budget" in captured.err
    assert "resnet50" in captured.err
    out = [json.loads(line) for line in captured.out.strip().splitlines()]
    assert [r["metric"] for r in out] == ["gpt2_small_x"]


def test_suite_rows_selects_exact_rows(bench, monkeypatch, capsys):
    """--suite-rows picks SUITE entries by NAME — the only way to select
    one bert_base protocol variant."""
    seen = []

    def fake_measure(row, emit_quick=True, emit_final=True, deadline=None):
        seen.append((row.model, row.attention_impl, row.seq_len))
        return 1.0

    monkeypatch.setattr(bench, "_child_measure", fake_measure)
    args = _args(bench, ["--suite", "--suite-rows",
                         "bert512_flash,bert2048_flash"])
    bench._child(args)
    assert seen == [("bert_base", "flash", 512),
                    ("bert_base", "flash", 2048)]


def test_suite_rows_validation(bench, capsys):
    with pytest.raises(SystemExit):
        # 99 is out of range even as a deprecated positional index
        bench.main(["--suite", "--suite-rows", "0,99"])
    with pytest.raises(SystemExit):
        bench.main(["--suite", "--suite-rows", "resnet50,nope"])
    with pytest.raises(SystemExit):
        bench.main(["--suite", "--suite-rows", "bert512",
                    "--suite-models", "resnet50"])


def test_suite_rows_index_alias_deprecated(bench, capsys):
    """Positional indices predate named rows: they still resolve (old
    drivers keep working) but to the NAME at that suite position, with a
    stderr deprecation note; a name+its-index pair dedupes to one row."""
    names = [n for n, _m, _o, _e in bench.SUITE]
    args = _args(bench, ["--suite", "--suite-rows", f"4,0,{names[0]}"])
    assert args.suite_rows == f"{names[4]},{names[0]}"
    err = capsys.readouterr().err
    assert "deprecated" in err and names[4] in err


def test_suite_budget_zero_disables_gating(bench, monkeypatch, capsys):
    seen = []

    def fake_measure(row, emit_quick=True, emit_final=True, deadline=None):
        seen.append((row.model, deadline))
        return 1.0

    monkeypatch.setattr(bench, "_child_measure", fake_measure)
    monkeypatch.setattr(bench, "SUITE",
                        (("resnet50", "resnet50", {}, 10_000),))
    args = _args(bench, ["--suite", "--suite-budget", "0"])
    bench._child(args)
    assert seen == [("resnet50", None)]


def test_parent_derives_child_suite_budget(bench):
    """The parent forwards --suite-budget = --budget minus the init margin
    unless explicitly overridden, so the child's row gating always engages
    on driver-style invocations (bench.py --suite --budget N)."""
    argv = ["--suite", "--budget", "520"]
    derived = {}

    def fake_attempt(cmd, timeout, *, relay_errors):
        derived["cmd"] = list(cmd)
        return 1, 0, "", 0

    orig = bench._run_attempt
    bench._run_attempt = fake_attempt
    try:
        bench.main(argv)
    finally:
        bench._run_attempt = orig
    i = derived["cmd"].index("--suite-budget")
    # Derived per-attempt from the REMAINING budget (520 minus elapsed,
    # minus the 120s relay margin) — a second attempt gets a smaller one.
    assert 395 <= int(derived["cmd"][i + 1]) <= 400


def test_metric_line_carries_tflops_and_fused_block_field(bench, capsys):
    """MFU reporting contract + the structured fused-block marker: the
    emitted record computes tflops_per_sec from the analytic model FLOPs,
    and mfu_pct appears exactly when the devices are TPUs."""
    from distributeddeeplearning_tpu.models import flops as flopslib

    args = _args(bench, ["--model", "resnet50"])
    args.fused_block = True
    bench._emit_metric(args, 2366.0, protocol="w11+30 b512")
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    per_ex = flopslib.train_flops_per_example("resnet50")
    assert rec["tflops_per_sec"] == round(2366.0 * per_ex / 1e12, 2)
    assert rec["fused_block"] is True
    # This test runs on CPU (unknown peak): mfu_pct must be absent, not
    # wrong. On a detected TPU it must match the peak-table arithmetic.
    import jax
    peak = flopslib.bf16_peak_flops(jax.devices()[0].device_kind)
    if peak:
        assert rec["mfu_pct"] == round(
            100.0 * 2366.0 * per_ex / peak, 1)
    else:
        assert "mfu_pct" not in rec


def test_unknown_model_omits_mfu_fields(bench, capsys):
    args = _args(bench, ["--model", "resnet50"])
    args.model = "bert_tiny"  # no flops entry by design
    args.seq_len = 64
    bench._emit_metric(args, 10.0, protocol="x")
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "tflops_per_sec" not in rec and "mfu_pct" not in rec
    assert "fused_block" not in rec  # marker only when the flag is set


def test_perleaf_allreduce_gets_its_own_metric_name(bench):
    """The A/B's reference schedule (bucket_mb=0) must never be read as
    the fused row: metric-name separation + protocol markers
    (docs/fused_allreduce.md A/B protocol)."""
    fused = _args(bench, ["--model", "resnet50",
                          "--allreduce-bucket-mb", "4"])
    perleaf = _args(bench, ["--model", "resnet50",
                            "--allreduce-bucket-mb", "0"])
    default = _args(bench, ["--model", "resnet50"])
    m_fused, _ = bench._metric_name_unit(fused)
    m_perleaf, _ = bench._metric_name_unit(perleaf)
    m_default, _ = bench._metric_name_unit(default)
    assert m_fused == m_default  # fused IS the production metric
    assert "_perleaf_ar" in m_perleaf and m_perleaf != m_fused
    assert "ar4mb" in bench._protocol_suffix(fused)
    assert "perleaf-ar" in bench._protocol_suffix(perleaf)
    assert "ar" not in bench._protocol_suffix(default)
    bf16 = _args(bench, ["--model", "resnet50", "--allreduce-dtype",
                         "bfloat16"])
    assert "ar-bf16" in bench._protocol_suffix(bf16)


def test_allreduce_flag_validation_and_forwarding(bench):
    with pytest.raises(SystemExit):
        bench.main(["--allreduce-bucket-mb", "-1"])
    # The parent must forward the protocol flags to the measuring child,
    # or the A/B rows would silently measure the default schedule.
    derived = {}

    def fake_attempt(cmd, timeout, *, relay_errors):
        derived["cmd"] = list(cmd)
        return 1, 0, "", 0

    orig = bench._run_attempt
    bench._run_attempt = fake_attempt
    try:
        bench.main(["--allreduce-bucket-mb", "0",
                    "--allreduce-dtype", "bfloat16"])
    finally:
        bench._run_attempt = orig
    cmd = derived["cmd"]
    i = cmd.index("--allreduce-bucket-mb")
    assert cmd[i + 1] == "0.0"
    assert cmd[cmd.index("--allreduce-dtype") + 1] == "bfloat16"


# --- provenance schema on bench records (ISSUE 6 tentpole) ------------------

def test_metric_record_is_fresh_with_attempt_and_pct_of_peak(
        bench, monkeypatch, capsys):
    """Every live metric line carries the full perf_report schema: fresh
    provenance, the attempt that produced it, backend identity, git rev,
    and a pct_of_peak column (null off TPU — never from an assumed
    peak)."""
    from distributeddeeplearning_tpu.observability import perf_report

    monkeypatch.setenv("DDL_BENCH_ATTEMPT", "3")
    args = _args(bench, ["--model", "resnet50"])
    bench._emit_metric(args, 2366.0, protocol="w11+30 b512")
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["provenance"] == "fresh"
    assert rec["schema_version"] == perf_report.SCHEMA_VERSION
    assert rec["attempt"] == 3
    assert rec["backend"]["platform"] == "cpu"
    assert rec["backend"]["device_count"] == 8
    assert len(rec["git_rev"]) == 12
    # pct_of_peak exists on EVERY row; honest null off TPU.
    assert "pct_of_peak" in rec and rec["pct_of_peak"] is None
    assert perf_report.validate(rec) == []


def test_error_record_carries_attempt_history_no_backend(bench, capsys):
    """The parent's error record: provenance=error, the full retry
    history, NO backend block — the parent never initializes jax (it
    would hold the chip its child needs) — and no cached value."""
    from distributeddeeplearning_tpu.observability import perf_report

    args = _args(bench, ["--model", "resnet50"])
    bench._emit_error(args, "no TPU found", attempts=[
        {"attempt": 1, "rc": "timeout 480s", "relayed_lines": 0},
        {"attempt": 2, "rc": "1", "relayed_lines": 0}])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["provenance"] == "error" and rec["value"] is None
    assert [a["attempt"] for a in rec["attempts"]] == [1, 2]
    assert "backend" not in rec
    assert "last_measured_on_live_chip" not in rec
    assert "stale_age_s" not in rec
    assert perf_report.validate(rec) == []


def test_main_retry_history_lands_in_error_record(bench, monkeypatch,
                                                  capsys):
    """End-to-end through main(): each failed attempt appends to the
    history the final error record ships, and the child env carries the
    attempt number so fresh records can stamp it."""
    seen_env = []

    def fake_attempt(cmd, timeout, *, relay_errors):
        seen_env.append(os.environ.get("DDL_BENCH_ATTEMPT"))
        return 0, 0, "RuntimeError: backend 'tpu' was asked for", 1

    monkeypatch.setattr(bench, "_run_attempt", fake_attempt)
    monkeypatch.setattr(bench, "RETRY_BACKOFF_SEC", (0, 0))
    rc = bench.main(["--attempts", "2"])
    assert rc == 1  # nothing measured: never exit 0
    assert seen_env == ["1", "2"]
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["provenance"] == "error"
    assert [a["attempt"] for a in rec["attempts"]] == [1, 2]
    assert all(a["rc"] == "1" for a in rec["attempts"])


# --- no fallback that hides the device (ISSUE 21) ---------------------------

@pytest.mark.parametrize("relayed,rc,want", [
    ((1, 0), 0, 0),        # a fresh measurement and a clean child
    ((1, 0), -9, 1),       # measured, then the child died
    ((1, 0), "timeout 5s", 1),
    ((2, 1), 0, 1),        # a suite row failed
])
def test_exit_code_is_zero_only_for_a_clean_fresh_measurement(
        bench, monkeypatch, capsys, relayed, rc, want):
    def fake_attempt(cmd, timeout, *, relay_errors):
        return relayed[0], relayed[1], "", rc

    monkeypatch.setattr(bench, "_run_attempt", fake_attempt)
    assert bench.main(["--attempts", "1"]) == want
    # A partial run keeps its lines valid: no error record shadows them.
    assert capsys.readouterr().out == ""


def test_child_without_platform_needs_a_tpu(bench, monkeypatch):
    """bench.py without --platform cpu is a chip measurement: on a process
    whose devices are CPUs the child fails instead of timing the CPU."""
    monkeypatch.setattr(bench, "_child_measure",
                        lambda *a, **k: pytest.fail("must not measure"))
    args = _args(bench, ["--model", "resnet50"])
    args.platform = None
    with pytest.raises(RuntimeError, match="backend 'tpu' was asked for"):
        bench._child(args)


def test_run_attempt_relays_fresh_lines_and_counts_error_records(bench,
                                                                 capsys):
    child = ("import json; "
             "print(json.dumps({'metric': 'm', 'value': 1.5})); "
             "print(json.dumps({'metric': 'n', 'value': None, "
             "'error': 'boom'})); print('not json')")
    good, errs, _tail, rc = bench._run_attempt(
        [sys.executable, "-c", child], timeout=60, relay_errors=True)
    assert (good, errs, rc) == (1, 1, 0)
    out = [json.loads(line) for line in
           capsys.readouterr().out.strip().splitlines()]
    assert [r["metric"] for r in out] == ["m", "n"]
