"""ZeRO-2/3 sharding ladder (parallel/zero.py stages 2-3 + the overlapped
backward/collective schedule in train/steps.py).

Parity contract, same grounds as tests/test_zero1.py: ZeRO-2 is BITWISE
against zero1 for elementwise optimizers — its backward scatter runs the
IDENTICAL per-bucket ops as zero1's post-backward scatter, only earlier in
the schedule, and the update math never changes. ZeRO-3 is BITWISE against
zero1 likewise (same chunk values, the same update on them); against the
replicated path every sharded stage is held to what tests/zero_parity.py
states, bit for bit where the update has no multiply feeding an add. LAMB
is bounded-not-tight for the norm-summation-order reason test_zero1.py
documents. The bitwise pins hold at accum=1 (the configs here); gradient
accumulation under the overlapped schedule sums per-microbatch scatters in
a different fp order (see steps.accumulated_grads).

Memory ladder (with AdamW, N=8): replicated ~4P resident per device ->
zero1 2.25P -> zero2 1.375P -> zero3 0.5P — asserted monotonically on the
measured+modeled ``resident_bytes_per_device`` the run summaries and bench
records carry.
"""

import json

import jax
import jax.numpy as jnp
import pytest

from distributeddeeplearning_tpu.config import ParallelConfig
from distributeddeeplearning_tpu.observability import telemetry
from distributeddeeplearning_tpu.parallel import zero
from distributeddeeplearning_tpu.train import checkpoint as ckptlib
from distributeddeeplearning_tpu.train import loop
from tests import zero_parity
from tests.zero_parity import (build as _build, cfg as _cfg,
                               full_params as _full_params)

DATA_AXES = ("data", "fsdp")


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _max_abs_diff(a, b) -> float:
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(_leaves(a), _leaves(b)))


def _run(cfg, steps):
    state, train_step, source, rng = _build(cfg, steps)
    for i in range(steps):
        state, metrics = train_step(state, source.batch(i), rng)
    return state, train_step


# --------------------------------------------------------------------------
# Trajectory parity across the ladder.
# --------------------------------------------------------------------------

@pytest.mark.parametrize("optimizer", ["sgd_momentum", "adamw"])
def test_zero2_matches_zero1_bitwise(devices8, optimizer):
    """zero2's overlapped backward scatter is the SAME per-bucket ops as
    zero1's post-backward scatter — params must agree bitwise, while the
    modeled resident grad bytes drop to 1/N (the full grad tree is never
    materialized)."""
    s1, step1, params1 = zero_parity.trajectory(optimizer, "zero1")
    s2, step2, params2 = zero_parity.trajectory(optimizer, "zero2")
    assert _max_abs_diff(params1[-1], params2[-1]) == 0.0
    assert step2.zero_stage == "zero2" and step2.overlap
    assert step1.zero_stage == "zero1" and not step1.overlap
    assert step2.grad_bytes_per_device < step1.grad_bytes_per_device
    # 1/N up to per-leaf padding (each leaf pads by < N elements):
    layout = zero.build_layout(
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), s1.params), 8)
    assert step2.grad_bytes_per_device * 8 <= \
        step1.grad_bytes_per_device + 8 * 4 * layout.num_leaves


def test_zero2_serialized_schedule_bitwise(devices8):
    """--no-overlap-collectives is an A/B of the schedule only: the
    serialized zero2 step lands on the same params."""
    opt = dict(name="sgd", learning_rate=0.1, momentum=0.9)
    s1, step1 = _run(_cfg(opt, "zero1"), 2)
    s2, step2 = _run(_cfg(opt, "zero2", overlap_collectives=False), 2)
    assert not step2.overlap
    assert _max_abs_diff(_full_params(s1, step1),
                         _full_params(s2, step2)) == 0.0


@pytest.mark.parametrize("optimizer", ["sgd_momentum", "adamw"])
def test_zero3_matches_zero1_bitwise(devices8, optimizer):
    """Full FSDP-style sharding: params live 1/N-chunked, gathered per
    bucket on demand — and the trajectory is zero1's in every bit of
    parameters AND optimizer state (the gathered params ARE zero1's
    params; the scattered grads ARE the same chunks; the update runs on
    the same chunk values)."""
    s1, step1, params1 = zero_parity.trajectory(optimizer, "zero1")
    s3, step3, params3 = zero_parity.trajectory(optimizer, "zero3")
    assert step3.zero_stage == "zero3" and step3.overlap
    assert _max_abs_diff(params1[-1], params3[-1]) == 0.0
    assert _max_abs_diff(
        jax.device_get(step1.zero_converter.to_canonical(s1).opt_state),
        jax.device_get(step3.zero_converter.to_canonical(s3).opt_state),
    ) == 0.0


@pytest.mark.parametrize("optimizer", list(zero_parity.OPTIMIZERS))
def test_zero3_matches_replicated(devices8, optimizer):
    """Against the replicated path, after each of three steps, within what
    tests/zero_parity.py allows (bit for bit where the update has no
    multiply feeding an add)."""
    zero_parity.assert_matches_replicated(optimizer, "zero3")
    # Live zero3 param leaves really are 1/N resident per device.
    for leaf in _leaves(zero_parity.trajectory(optimizer, "zero3").state
                        .params):
        assert leaf.addressable_shards[0].data.size == leaf.size // 8


@pytest.mark.slow
def test_zero3_lamb_bounded(devices8):
    """LAMB's trust ratio is a norm: zero3 computes it as
    sqrt(psum(partial)) whose fp summation order differs from the
    replicated full-leaf norm — bounded gap, not bitwise (same grounds and
    bound discipline as test_zero1.py's LAMB case)."""
    opt = dict(name="lamb", learning_rate=1e-3, weight_decay=0.01)
    sr, step_r = _run(_cfg(opt, "none"), 2)
    s3, step3 = _run(_cfg(opt, "zero3"), 2)
    gap = _max_abs_diff(_full_params(sr, step_r), _full_params(s3, step3))
    assert gap < 5e-3, f"zero3 LAMB diverged: {gap}"


# --------------------------------------------------------------------------
# The memory ladder: resident bytes per device fall monotonically.
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_resident_bytes_ladder_monotonic(devices8):
    """replicated -> zero1 -> zero2 -> zero3 strictly decreases the
    per-device resident footprint (params + modeled grads + opt state) —
    the acceptance ladder, on the same resident_bytes_per_device number
    run summaries and bench records report. AdamW so opt state is 2P."""
    opt = dict(name="adamw", learning_rate=1e-3, weight_decay=0.01)
    resident = {}
    for stage in ("none", "zero1", "zero2", "zero3"):
        state, train_step, _, _ = _build(_cfg(opt, stage), 2)
        stats = loop._device_memory_stats(state, train_step)
        resident[stage] = stats["resident_bytes_per_device"]
        assert stats["grads_bytes_per_device"] > 0
    assert resident["none"] > resident["zero1"] > resident["zero2"] \
        > resident["zero3"], resident
    # Coarse shape of the AdamW ladder (P params + 2P opt + grads):
    # zero1 saves the ~1.75P of opt state, zero2 the ~7/8 of grads too,
    # zero3 the ~7/8 of params as well — each step at least 20% down.
    for hi, lo in (("none", "zero1"), ("zero1", "zero2"),
                   ("zero2", "zero3")):
        assert resident[lo] < 0.8 * resident[hi], resident


def test_modeled_grad_bytes(devices8):
    """The grads component of the ladder is MODELED (grads are transient
    in a jit program): chunked = sum of chunk rows, full = sum of leaf
    bytes, chunked ~ full/N."""
    tree = {"a": jnp.zeros((33, 5)), "b": jnp.zeros((7,))}
    layout = zero.build_layout(tree, 8)
    full = zero.modeled_grad_bytes(layout, chunked=False)
    chunked = zero.modeled_grad_bytes(layout, chunked=True)
    assert full == (33 * 5 + 7) * 4
    assert chunked == sum(layout.chunk_sizes) * 4
    assert full < chunked * 8 <= full + 8 * 4 * layout.num_leaves


# --------------------------------------------------------------------------
# Overlap telemetry: the gauge reads the schedule, not wishful thinking.
# --------------------------------------------------------------------------

def test_overlap_fraction_unit():
    ev = [
        {"ph": "X", "name": "collective:zero2/reduce_scatter/bucket00",
         "args": {"overlapped": True, "cat": "trace"}},
        {"ph": "X", "name": "collective:zero1/reduce_scatter/bucket00",
         "args": {"cat": "trace"}},
        {"ph": "X", "name": "phase:dispatch"},
        {"ph": "M", "name": "collective:zero2/reduce_scatter/bucket01",
         "args": {"overlapped": True}},  # metadata, not a span
    ]
    assert telemetry.overlap_fraction(ev) == 0.5
    assert telemetry.overlap_fraction([]) == 0.0


def test_overlap_fraction_traced(devices8):
    """Tracing a zero2 step yields overlapped reduce-scatter spans
    (fraction 1.0); the zero1 schedule yields the same spans un-marked
    (fraction 0.0). Compile cache off: an AOT hit compiles nothing and
    trace-time spans never fire — the documented gauge caveat."""
    def traced_fraction(sharding):
        tele = telemetry.configure(enabled=True)
        try:
            opt = dict(name="sgd", learning_rate=0.1)
            cfg = _cfg(opt, sharding, compile_cache=False)
            state, train_step, source, rng = _build(cfg, 2)
            state, _ = train_step(state, source.batch(0), rng)
            events = tele.snapshot()
            assert any("/reduce_scatter/" in e.get("name", "")
                       for e in events), "no scatter spans traced"
            return telemetry.overlap_fraction(events)
        finally:
            telemetry.reset()

    assert traced_fraction("zero2") == 1.0
    assert traced_fraction("zero1") == 0.0


# --------------------------------------------------------------------------
# Cross-stage checkpoint resume through the canonical layout.
# --------------------------------------------------------------------------

def _save_sharded(tmp_path, sharding, opt_kw, steps=2, **kw):
    from distributeddeeplearning_tpu.train.checkpoint import Checkpointer

    cfg = _cfg(opt_kw, sharding, **kw)
    state, train_step, source, rng = _build(cfg, steps + 2)
    for i in range(steps):
        state, _ = train_step(state, source.batch(i), rng)
    ckpt = Checkpointer(str(tmp_path / "ckpt"), every_steps=1,
                        converter=train_step.zero_converter)
    assert ckpt.maybe_save(int(state.step), state, force=True)
    ckpt.wait()
    ckpt.close()
    return cfg, state, train_step


def _restore(tmp_path, cfg):
    from distributeddeeplearning_tpu.train.checkpoint import Checkpointer

    state, train_step, source, rng = _build(cfg, 6)
    ck = Checkpointer(str(tmp_path / "ckpt"), every_steps=1,
                      converter=getattr(train_step, "zero_converter", None))
    restored = ck.restore_latest(state)
    ck.close()
    assert restored is not None
    return restored, train_step, source, rng


@pytest.mark.slow
def test_cross_stage_resume_from_zero3(devices8, tmp_path):
    """Save under zero3 on 8 shards (params AND opt state chunked on
    disk-side gather to canonical); restore (a) replicated dp=8,
    (b) zero2 dp=8, (c) zero3 dp=2. Params bitwise the save's full
    params everywhere; optimizer states agree in canonical form; one
    post-resume SGD step from (a) and (b) lands on identical params.

    Marked slow at ~59s (right at the 60s line): the zero2->zero3 edge
    keeps slow-tier coverage below, and the donation-safety bug class it
    guards stays pinned fast by test_zero1.py::test_cross_degree_resume."""
    opt = dict(name="sgd", learning_rate=0.1, momentum=0.9)
    cfg8, saved, step8 = _save_sharded(tmp_path, "zero3", opt)
    saved_params = _full_params(saved, step8)
    saved_canon = jax.device_get(
        step8.zero_converter.to_canonical(saved).opt_state)

    # (a) replicated, same degree.
    rest_r, step_r, source, rng_r = _restore(
        tmp_path, _cfg(opt, "none"))
    assert _max_abs_diff(jax.device_get(rest_r.params), saved_params) == 0.0
    assert _max_abs_diff(jax.device_get(rest_r.opt_state), saved_canon) == 0.0

    # (b) zero2, same degree: full params live, chunked opt state.
    rest_2, step_2, _, rng_2 = _restore(tmp_path, _cfg(opt, "zero2"))
    assert _max_abs_diff(jax.device_get(rest_2.params), saved_params) == 0.0
    assert _max_abs_diff(
        jax.device_get(step_2.zero_converter.to_canonical(
            rest_2).opt_state), saved_canon) == 0.0

    # (c) zero3 on HALF the degree: 1/2 chunks, same canonical content.
    cfg3 = _cfg(opt, "zero3", parallel=ParallelConfig(data=2),
                global_batch_size=16)
    rest_3, step_3, _, _ = _restore(tmp_path, cfg3)
    for leaf in _leaves(rest_3.params):
        assert leaf.addressable_shards[0].data.size == leaf.size // 2
    assert _max_abs_diff(_full_params(rest_3, step_3), saved_params) == 0.0
    assert _max_abs_diff(
        jax.device_get(step_3.zero_converter.to_canonical(
            rest_3).opt_state), saved_canon) == 0.0

    # Post-resume step parity (device_copy first: a warm AOT cache serves
    # donating executables, and orbax-restored buffers must not be donated
    # — tests/test_zero1.py::test_cross_degree_resume's bug class).
    rest_r = ckptlib.device_copy(rest_r)
    rest_2 = ckptlib.device_copy(rest_2)
    batch = source.batch(2)
    next_r, _ = step_r(rest_r, batch, rng_r)
    next_2, _ = step_2(rest_2, batch, rng_2)
    assert int(next_r.step) == int(next_2.step)
    assert _max_abs_diff(jax.device_get(next_r.params),
                         _full_params(next_2, step_2)) == 0.0


@pytest.mark.slow
def test_cross_stage_resume_zero2_to_zero3_adamw(devices8, tmp_path):
    """The remaining edge of the matrix: a zero2 AdamW checkpoint resumes
    under zero3 at the same degree, bitwise in canonical form, and the
    next step agrees with the zero2 continuation."""
    opt = dict(name="adamw", learning_rate=1e-3, weight_decay=0.01)
    cfg2, saved, step_s = _save_sharded(tmp_path, "zero2", opt)
    saved_params = _full_params(saved, step_s)
    saved_canon = jax.device_get(
        step_s.zero_converter.to_canonical(saved).opt_state)

    rest_3, step_3, source, rng_3 = _restore(tmp_path, _cfg(opt, "zero3"))
    assert _max_abs_diff(_full_params(rest_3, step_3), saved_params) == 0.0
    assert _max_abs_diff(
        jax.device_get(step_3.zero_converter.to_canonical(
            rest_3).opt_state), saved_canon) == 0.0

    rest_2, step_2, _, rng_2 = _restore(tmp_path, _cfg(opt, "zero2"))
    rest_2 = ckptlib.device_copy(rest_2)
    rest_3 = ckptlib.device_copy(rest_3)
    batch = source.batch(2)
    next_2, _ = step_2(rest_2, batch, rng_2)
    next_3, _ = step_3(rest_3, batch, rng_3)
    assert _max_abs_diff(_full_params(next_2, step_2),
                         _full_params(next_3, step_3)) == 0.0


# --------------------------------------------------------------------------
# Flags, guards, and the fsdp fold.
# --------------------------------------------------------------------------

def test_cli_flag_roundtrip():
    import train as train_cli

    cfg = train_cli.build_config(train_cli.parse_args(
        ["--optimizer-sharding", "zero3", "--no-overlap-collectives"]))
    assert cfg.optimizer_sharding == "zero3"
    assert cfg.overlap_collectives is False
    # Defaults: overlap on, and zero2 parses.
    cfg = train_cli.build_config(train_cli.parse_args(
        ["--optimizer-sharding", "zero2"]))
    assert cfg.optimizer_sharding == "zero2"
    assert cfg.overlap_collectives is True


def test_opt_state_offload_flag_is_gone():
    """--opt-state-offload placed optimizer state in pinned_host memory;
    on the installed jax host and device memory are distinct types and the
    update refused to mix them (on the TPU compiler as on the CPU), so the
    flag was removed rather than left to keep the state on the device
    behind a warning. argparse's refusal is the loud one."""
    import train as train_cli

    with pytest.raises(SystemExit):
        train_cli.parse_args(["--optimizer-sharding", "zero2",
                              "--opt-state-offload"])


def test_zero3_folds_fsdp_off_gspmd(devices8):
    """fsdp>1 alone forces the GSPMD path; with zero3 the bucket planner
    owns parameter sharding, so the same parallel config stays on the
    explicit-DP path (the sharding.py 'embed' rule folded into zero3) —
    and the dp axes product still drives the 1/N layout."""
    opt = dict(name="sgd", learning_rate=0.1)
    fsdp = ParallelConfig(data=4, fsdp=2)
    assert loop.uses_gspmd(_cfg(opt, "none", parallel=fsdp), "image")
    cfg = _cfg(opt, "zero3", parallel=fsdp)
    assert not loop.uses_gspmd(cfg, "image")
    state, train_step, source, rng = _build(cfg, 2)
    assert train_step.zero_stage == "zero3"
    for leaf in _leaves(state.params):
        assert leaf.addressable_shards[0].data.size == leaf.size // 8
    state, _ = train_step(state, source.batch(0), rng)


def test_sharding_sidecar_written(devices8, tmp_path, monkeypatch):
    """loop._write_sharding_sidecar: the doctor-readable record of which
    sharding the last run actually used."""
    opt = dict(name="sgd", learning_rate=0.1)
    cfg = _cfg(opt, "zero2")
    state, train_step, _, _ = _build(cfg, 2)
    path = tmp_path / "side.json"
    monkeypatch.setattr(loop, "_sharding_sidecar_path", lambda: str(path))
    loop._write_sharding_sidecar(cfg, train_step, 0.75)
    side = json.loads(path.read_text())
    assert side["optimizer_sharding"] == "zero2"
    assert side["overlap"] is True
    assert side["overlap_fraction"] == 0.75
    assert side["dp"] == 8
