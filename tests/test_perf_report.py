"""The perf-record schema contract (observability/perf_report.py): the
provenance rules every measurement surface emits under. A record is fresh
or an error; there is no state for a replayed, cached number."""

import pytest

from distributeddeeplearning_tpu.observability import perf_report


# --- annotate + validate ----------------------------------------------------

def test_annotate_stamps_schema_and_rejects_bad_provenance():
    rec = perf_report.annotate({"value": 1.0}, provenance="fresh")
    assert rec["schema_version"] == perf_report.SCHEMA_VERSION
    assert rec["provenance"] == "fresh"
    with pytest.raises(ValueError):
        perf_report.annotate({}, provenance="cached")  # not a state


def test_annotate_attempts_and_backend_identity():
    rec = perf_report.annotate(
        {"value": 2.0}, provenance="fresh",
        attempts=[{"attempt": 1, "rc": "timeout 480s"},
                  {"attempt": 2, "rc": "up"}])
    assert [a["attempt"] for a in rec["attempts"]] == [1, 2]
    # conftest pins JAX_PLATFORMS=cpu with 8 fake devices.
    assert rec["backend"]["platform"] == "cpu"
    assert rec["backend"]["device_count"] == 8
    jaxfree = perf_report.annotate({"value": 2.0}, provenance="fresh",
                                   with_backend=False)
    assert "backend" not in jaxfree


def test_annotate_config_fingerprint_matches_aot():
    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.perf import aot as aotlib
    cfg = TrainConfig(model="resnet18_thin", global_batch_size=8)
    rec = perf_report.annotate({"value": 1.0}, provenance="fresh",
                               config=cfg, total_steps=10)
    assert rec["config_fingerprint"] == aotlib.config_fingerprint(
        cfg, total_steps=10)


def test_validate_fresh_rules():
    assert not perf_report.validate({"provenance": "fresh", "value": 9.0})
    # Summaries measure through other keys; no explicit value is fine.
    assert not perf_report.validate({"provenance": "fresh",
                                     "examples_per_sec": 100.0})
    assert perf_report.validate({"provenance": "fresh", "value": None})


def test_validate_error_rules():
    assert not perf_report.validate(
        {"provenance": "error", "value": None, "error": "no TPU found"})
    assert perf_report.validate({"provenance": "error", "value": 5.0,
                                 "error": "x"})
    assert perf_report.validate({"provenance": "error", "value": None})
    assert perf_report.validate({"provenance": None})
    assert perf_report.validate({})


@pytest.mark.parametrize("state", ["stale", "expired", "cached"])
def test_no_provenance_state_for_a_replayed_number(state):
    """A measurement path that could not measure fails; it never labels an
    earlier value and re-reports it."""
    assert state not in perf_report.PROVENANCE_STATES
    with pytest.raises(ValueError):
        perf_report.annotate({"value": 1.0}, provenance=state)
    assert perf_report.validate({"provenance": state, "value": 1.0})


# --- roofline ---------------------------------------------------------------

def test_roofline_matches_flops_tables():
    from distributeddeeplearning_tpu.models import flops as flopslib
    per_ex = flopslib.train_flops_per_example("resnet50")
    out = perf_report.roofline(2366.0, "resnet50", device_kind="TPU v5e")
    assert out["tflops_per_sec"] == round(2366.0 * per_ex / 1e12, 2)
    peak = flopslib.bf16_peak_flops("TPU v5e")
    assert out["pct_of_peak"] == round(100.0 * 2366.0 * per_ex / peak, 1)
    assert out["bf16_peak_tflops"] == round(peak / 1e12, 0)


def test_roofline_fields_absent_off_tpu_and_for_unknown_models():
    assert perf_report.roofline(None, "resnet50") == {}
    assert perf_report.roofline(10.0, "no_such_model") == {}
    out = perf_report.roofline(10.0, "resnet50", device_kind="cpu")
    assert "tflops_per_sec" in out and "pct_of_peak" not in out


def test_unknown_tpu_kind_is_an_error_where_a_peak_is_needed():
    """A TPU the peak table does not list must not yield a silently
    missing MFU field: every roofline surface raises instead."""
    from distributeddeeplearning_tpu.models import flops as flopslib
    for fn in (flopslib.peak_flops, flopslib.bf16_peak_flops,
               flopslib.hbm_bw_bytes):
        with pytest.raises(ValueError, match="TPU v99"):
            fn("TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        perf_report.roofline(10.0, "resnet50", device_kind="TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        flopslib.decode_roofline("gpt2_small", context_len=128,
                                 tokens_per_sec=10.0,
                                 device_kind="TPU v99")


def test_git_rev_reads_head():
    rev = perf_report.git_rev()
    # This repo IS a git checkout; the rev must resolve and look like one.
    assert rev and len(rev) == 12
    assert all(c in "0123456789abcdef" for c in rev)
    assert perf_report.git_rev("/no/such/root") is None


def test_roofline_scores_against_own_dtype_roof():
    """The large-batch A/B contract (ISSUE 20): at EQUAL throughput the
    fp32 arm scores 6x the mixed arm's pct_of_peak (its roof is 6x
    lower) — so a mixed arm only wins the %-of-peak comparison by
    actually being faster, and peak_dtype stamps which roof was used."""
    mixed = perf_report.roofline(2366.0, "resnet50", device_kind="TPU v5e",
                                 compute_dtype="bfloat16")
    fp32 = perf_report.roofline(2366.0, "resnet50", device_kind="TPU v5e",
                                compute_dtype="float32")
    assert fp32["peak_dtype"] == "float32"
    assert mixed["peak_dtype"] == "bfloat16"
    assert fp32["pct_of_peak"] == pytest.approx(
        6.0 * mixed["pct_of_peak"], rel=0.01)
    # The bf16 arm keeps the back-compat alias next to the new fields.
    assert mixed["bf16_peak_tflops"] == mixed["peak_tflops"]
