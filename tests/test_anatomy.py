"""Step anatomy (analysis/anatomy.py): the names the program puts into its
compiled train step, read back per instruction and mapped to a phase and a
part. The tiny GPT step is compiled once here on the CPU; what the parts
cost on the chip is the benchmark's to say (`device_ms.*`).
"""

import collections
import re

import jax
import pytest

from distributeddeeplearning_tpu import data as datalib
from distributeddeeplearning_tpu.analysis import anatomy
from distributeddeeplearning_tpu.config import (
    DataConfig, OptimizerConfig, ParallelConfig, PrecisionPolicy, TrainConfig)
from distributeddeeplearning_tpu.models import model_spec
from distributeddeeplearning_tpu.parallel.mesh import use_mesh
from distributeddeeplearning_tpu.perf import aot
from distributeddeeplearning_tpu.train import loop

LAYERS = 2  # gpt_tiny


def _config(**kw):
    policy = PrecisionPolicy.mixed()
    return TrainConfig(
        model="gpt_tiny", global_batch_size=4, seed=0,
        dtype=policy.compute_dtype, precision=policy, log_every=10 ** 9,
        attention_impl="flash", parallel=ParallelConfig(data=1),
        data=DataConfig(synthetic=True, dataset="mlm", seq_len=64,
                        vocab_size=1024),
        optimizer=OptimizerConfig(
            name="adamw", learning_rate=6e-4, reference_batch=4,
            weight_decay=0.1, schedule="constant", warmup_epochs=0.0,
            beta1=0.9, beta2=0.95), **kw)


def _built(config):
    mesh, _model, batch_shd, state, step, _sched, rng = loop.build(
        config, 1000)
    spec = model_spec(config.model)
    batch = datalib.make_source(config, spec.input_kind, batch_shd,
                                objective=spec.objective).batch(0)
    return mesh, state, batch, rng, step


@pytest.fixture(scope="module")
def tiny_step():
    """The tiny GPT step as train.py builds it (mixed precision, flash
    attention with dropout), without the compile cache."""
    return _built(_config(compile_cache=False))


@pytest.fixture(scope="module")
def compiled_text(tiny_step):
    _mesh, state, batch, rng, step = tiny_step
    return step.lower(state, batch, rng).compile().as_text()


# What moves no data of its own: the compiler's bookkeeping round the work.
PLUMBING = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "copy"}


def _entry_instructions(text):
    """(name, opcode) of the entry computation's instructions."""
    lines = text.splitlines()
    start = next(i for i, ln in enumerate(lines) if ln.startswith("ENTRY"))
    out = []
    for ln in lines[start + 1:]:
        if ln.startswith("}"):
            break
        name = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = ", ln).group(1)
        opcode = re.search(r"[\s)]([a-z][\w\-]*)\(", ln.split(" = ", 1)[1])
        out.append((name, opcode.group(1)))
    return out


def test_every_entry_instruction_of_the_tiny_step_gets_a_part(compiled_text):
    table = anatomy.table(compiled_text)
    working = [(name, op) for name, op in _entry_instructions(compiled_text)
               if op not in PLUMBING]
    assert len(working) > 300
    seen = collections.Counter()
    for name, _ in working:
        phase, part = (anatomy.part_of(table[name]) if name in table
                       else ("update", "unattributed"))
        assert phase in anatomy.PHASES and part in anatomy.PARTS, name
        seen[phase, part] += 1
    unattributed = sum(n for (_, part), n in seen.items()
                       if part == "unattributed")
    assert unattributed < 0.05 * len(working), seen
    # each part of the step is there, in the phase it belongs to; the
    # backward pass is one flash kernel at this shape, so no flash_dq
    assert seen["backward", "flash_dq"] == 0, seen
    for key in [("forward", "flash_fwd"),
                ("backward", "flash_dkv"), ("forward", "head"),
                ("backward", "head"), ("forward", "loss"),
                ("backward", "loss"), ("forward", "embed"),
                ("forward", "mlp"), ("backward", "mlp"),
                ("forward", "layernorm"), ("backward", "layernorm"),
                ("forward", "attention_other"),
                ("backward", "attention_other"), ("update", "loss_scale"),
                ("update", "optimizer")]:
        assert seen[key] > 0, (key, seen)


STEP = "jit(step_fn)/"
GPT = STEP + "grads/jvp(GptLM)/"
GPT_T = STEP + "grads/transpose(jvp(GptLM))/"
CASES = [
    (GPT + "layer3/attention/flash_fwd/cond/branch_0_fun/flash_fwd/"
     "pallas_call", "forward", "flash_fwd"),
    (STEP + "grads/transpose(grads)/jvp(GptLM)/layer3/attention/flash_dq/"
     "cond/branch_0_fun/flash_dq/pallas_call", "backward", "flash_dq"),
    (STEP + "grads/transpose(grads)/jvp(GptLM)/layer0/attention/flash_dkv/"
     "cond/branch_0_fun/flash_dkv/pallas_call", "backward", "flash_dkv"),
    (GPT + "layer0/attention/query/dot_general", "forward",
     "attention_other"),
    # the array primitive `transpose` is no backward pass
    (GPT + "layer0/attention/transpose", "forward", "attention_other"),
    (GPT + "layer11/Dropout_0/jit(_bernoulli)/jit(_uniform)/add", "forward",
     "attention_other"),
    (GPT_T + "layer0/attention/key/dot_general", "backward",
     "attention_other"),
    (GPT + "layer0/mlp/mlp_in/dot_general", "forward", "mlp"),
    (GPT + "layer0/mlp/tanh", "forward", "mlp"),
    (GPT_T + "layer0/mlp/mlp_out/reduce_sum", "backward", "mlp"),
    (GPT + "layer0/ln1/rsqrt", "forward", "layernorm"),
    (GPT + "ln_f/reduce_sum", "forward", "layernorm"),
    (GPT_T + "layer5/ln2/mul", "backward", "layernorm"),
    (GPT + "embed/gather", "forward", "embed"),
    (GPT_T + "embed/scatter-add", "backward", "embed"),
    (GPT + "head/bsh,vh->bsv/dot_general", "forward", "head"),
    (GPT_T + "head/bsh,vh->bsv/dot_general", "backward", "head"),
    (STEP + "grads/jvp(loss)/reduce_max", "forward", "loss"),
    (STEP + "grads/transpose(jvp(loss))/jit(take_along_axis)/scatter-add",
     "backward", "loss"),
    # a model that scopes its attention by layer kind books the kernels there
    (STEP + "grads/jvp(AfmoeLM)/layer1.<lambda>/layer1/attention/attn_window/"
     "flash_fwd/cond/branch_0_fun/flash_fwd/pallas_call", "forward",
     "attention_window"),
    (STEP + "grads/transpose(grads)/jvp(AfmoeLM)/layer4/attention/attn_full/"
     "flash_dkv/cond/branch_0_fun/flash_dkv/pallas_call", "backward",
     "attention_full"),
    (STEP + "grads/jvp(AfmoeLM)/layer4/attention/attn_full/transpose",
     "forward", "attention_full"),
    # a delta-rule layer's convolutions, gates, chunked scan and gated norm
    # book under its scope, through the scan's loops and their recomputation
    (STEP + "grads/jvp(KimiLinearLM)/layer0/attention/attn_kda/q_conv/mul",
     "forward", "attention_kda"),
    (STEP + "grads/jvp(KimiLinearLM)/layer2/attention/attn_kda/while/body/"
     "checkpoint/while/body/dot_general", "forward", "attention_kda"),
    (STEP + "grads/transpose(jvp(KimiLinearLM))/layer2/attention/attn_kda/"
     "while/body/checkpoint/rematted_computation/exp", "backward",
     "attention_kda"),
    (STEP + "grads/jvp(KimiLinearLM)/layer2/attention/attn_kda/o_norm/rsqrt",
     "forward", "attention_kda"),
    # latent attention's kernels under its scope; its projections outside
    (STEP + "grads/jvp(KimiLinearLM)/layer3/attention/attn_mla/flash_fwd/"
     "cond/branch_0_fun/flash_fwd/pallas_call", "forward", "attention_mla"),
    (STEP + "grads/transpose(grads)/jvp(KimiLinearLM)/layer3/attention/"
     "attn_mla/flash_dq/cond/branch_0_fun/flash_dq/pallas_call", "backward",
     "attention_mla"),
    (STEP + "grads/jvp(KimiLinearLM)/layer3/attention/kv_b_proj/dot_general",
     "forward", "attention_other"),
    (STEP + "grads/jvp(KimiLinearLM)/layer3/attention/kv_a_norm/rsqrt",
     "forward", "attention_other"),
    # hyper-connections: the scope wins over the flax path round it, inside
    # the Sinkhorn loop's body and under a recomputed block alike; the
    # sub-layers inside a round keep their own parts
    (STEP + "grads/jvp(Xing4LM)/layer3/attn_hc/mhc/bsk,km->mbs/dot_general",
     "forward", "residual_mhc"),
    (STEP + "grads/jvp(Xing4LM)/layer3/ffn_hc/mhc/while/body/div", "forward",
     "residual_mhc"),
    # the kernels of the passes over the streams (ops/mhc.py) sit inside the
    # scope, forward, backward and where a recomputed block remakes them
    (STEP + "grads/jvp(Xing4LM)/layer1.<lambda>/layer1/attn_hc/mhc/"
     "mhc_in_fwd/cond/branch_0_fun/mhc_in_fwd/pallas_call", "forward",
     "residual_mhc"),
    (STEP + "grads/jvp(Xing4LM)/layer1.<lambda>/layer1/mhc/mhc_out_fwd/cond/"
     "branch_0_fun/mhc_out_fwd/pallas_call", "forward", "residual_mhc"),
    (STEP + "grads/transpose(jvp(Xing4LM))/jvp(Xing4LM)/checkpoint/"
     "rematted_computation/layer1.<lambda>/layer1/ffn_hc/mhc/mhc_in_fwd/"
     "cond/branch_0_fun/mhc_in_fwd/pallas_call", "backward", "residual_mhc"),
    (STEP + "grads/transpose(jvp(Xing4LM))/jvp(Xing4LM)/checkpoint/"
     "layer1.<lambda>/layer1/ffn_hc/mhc/mhc_in_bwd/cond/branch_0_fun/"
     "mhc_in_bwd/pallas_call", "backward", "residual_mhc"),
    (STEP + "grads/transpose(jvp(Xing4LM))/jvp(Xing4LM)/checkpoint/"
     "layer1.<lambda>/layer1/mhc/mhc_out_bwd/cond/branch_0_fun/mhc_out_bwd/"
     "pallas_call", "backward", "residual_mhc"),
    (STEP + "grads/transpose(jvp(Xing4LM))/grads/jvp(Xing4LM)/checkpoint/"
     "rematted_computation/layer3/mhc/add", "backward", "residual_mhc"),
    (STEP + "grads/jvp(Xing4LM)/mhc/concatenate", "forward", "residual_mhc"),
    (STEP + "grads/jvp(Xing4LM)/layer3/attention/q_a_norm/rsqrt", "forward",
     "attention_other"),
    (STEP + "grads/jvp(AfmoeLM)/layer2/moe/moe_router/top_k", "forward",
     "moe_routing"),
    (STEP + "grads/jvp(AfmoeLM)/layer2/moe/moe_dispatch/sort", "forward",
     "moe_routing"),
    (STEP + "grads/transpose(jvp(AfmoeLM))/layer2/moe/moe_combine/gather",
     "backward", "moe_routing"),
    (STEP + "grads/jvp(AfmoeLM)/layer2/moe/moe_experts/ragged_dot", "forward",
     "moe_experts"),
    # a compiler-named ragged product, as `table` renames it: innermost wins
    (STEP + "grads/transpose(jvp(AfmoeLM))/layer2/moe/moe_dispatch/"
     "reduce_sum/moe_experts", "backward", "moe_experts"),
    (STEP + "grads/jvp(AfmoeLM)/layer2/moe/mlp/shared_up/dot_general",
     "forward", "mlp"),
    (STEP + "grads/jvp(AfmoeLM)/layer0/pre_mlp_layernorm/rsqrt", "forward",
     "layernorm"),
    # the recompute boundary's own copies of a saved block input
    (STEP + "grads/transpose(jvp(AfmoeLM))/grads/jvp(AfmoeLM)/remat2",
     "backward", "remat"),
    (STEP + "loss_scale/reduce_sum", "update", "loss_scale"),
    (STEP + "loss_scale/jit(_where)/select_n", "update", "loss_scale"),
    (STEP + "optimizer/sqrt", "update", "optimizer"),
    (STEP + "ema/mul", "update", "ema_guard"),
    (STEP + "guard/reduce_sum", "update", "ema_guard"),
    (STEP + "grad_reduce/psum", "update", "grad_reduce"),
    (STEP + "jit(_threefry_fold_in)/slice", "update", "unattributed"),
    (STEP + "grads/jit(_threefry_fold_in)/GptLM.__call__/xor", "forward",
     "unattributed"),
    (STEP + "grads/transpose(jvp(ResNet))/conv_init/conv_general_dilated",
     "backward", "unattributed"),
]


@pytest.mark.parametrize("op_name,phase,part", CASES,
                         ids=[f"{c[1]}-{c[2]}-{i}"
                              for i, c in enumerate(CASES)])
def test_part_of(op_name, phase, part):
    assert anatomy.part_of(op_name) == (phase, part)


def test_the_cases_cover_the_vocabulary():
    assert {c[2] for c in CASES} == set(anatomy.PARTS)
    assert {c[1] for c in CASES} == set(anatomy.PHASES)


def test_table_resolves_fusions_and_the_compilers_copies():
    text = """HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[]}

%fused_computation.1 (param_0.1: f32[8]) -> f32[] {
  %param_0.1 = f32[8]{0} parameter(0)
  %mul.3 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(f)/optimizer/mul"}
  ROOT %reduce.2 = f32[] reduce(%mul.3), to_apply=%region_0, metadata={op_name="jit(f)/optimizer/reduce_sum" source_file="a.py" source_line=3}
}

ENTRY %main.9 (x.1: f32[8]) -> f32[] {
  %x.1 = f32[8]{0:T(128)} parameter(0), metadata={op_name="x"}
  %copy.4 = f32[8]{0:T(128)S(1)} copy(%x.1)
  %fusion.7 = f32[] fusion(%copy.4), kind=kInput, calls=%fused_computation.1
  %named.1 = (f32[], f32[8]{0:T(8,128)(2,1)}) custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/grads/jvp(M)/flash_fwd/pallas_call"}
  %bitcast.5 = f32[] bitcast(%named.1)
  ROOT %orphan.1 = f32[] constant(0)
}
"""
    assert anatomy.table(text) == {
        "mul.3": "jit(f)/optimizer/mul",
        "reduce.2": "jit(f)/optimizer/reduce_sum",
        # a fusion is what its root is; a copy of a parameter belongs to the
        # first instruction that reads it; a bitcast to what it reads
        "fusion.7": "jit(f)/optimizer/reduce_sum",
        "copy.4": "jit(f)/optimizer/reduce_sum",
        "named.1": "jit(f)/grads/jvp(M)/flash_fwd/pallas_call",
        "bitcast.5": "jit(f)/grads/jvp(M)/flash_fwd/pallas_call",
    }
    assert anatomy.table("torn { text\n  %a = f32[] add(") == {}


def test_by_part_joins_a_traces_operations_to_the_table():
    table = {"fusion.7": GPT_T + "head/bsh,vh->bsv/dot_general",
             "flash_fwd.3": GPT + "layer0/attention/flash_fwd/pallas_call"}
    durations = {"%fusion.7 = f32[8]{0} fusion(%a), kind=kLoop": 2.0,
                 "flash_fwd.3": 3.0,            # named by the instruction
                 "%flash_fwd.3 = bf16[4] custom-call(%q)": 0.5,
                 "%fusion.70 = f32[] fusion()": 0.25}  # not fusion.7
    assert anatomy.by_part(durations, table) == {
        ("backward", "head"): 2.0, ("forward", "flash_fwd"): 3.5,
        ("-", "unattributed"): 0.25}


def test_step_lowered_for_tpu_names_each_flash_kernel_once_a_layer(tiny_step):
    """Lowered for the TPU platform from the CPU (nothing compiles, no libtpu
    is loaded), the step holds one Mosaic call of each name per layer: the
    forward and the one backward kernel, which carries dQ (no flash_dq at
    this shape)."""
    mesh, state, batch, rng, step = tiny_step
    with use_mesh(mesh):
        text = jax.jit(step.raw_step).trace(state, batch, rng).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2 * LAYERS
    for name, n in (("flash_fwd", LAYERS), ("flash_dq", 0),
                    ("flash_dkv", LAYERS)):
        assert text.count(f'kernel_name = "{name}"') == n, name


def test_pallas_call_without_a_name_raises():
    from distributeddeeplearning_tpu.ops.pallas import pallas_call

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    shape = jax.ShapeDtypeStruct((8, 128), jax.numpy.float32)
    with pytest.raises(TypeError, match="name"):
        pallas_call(kernel, out_shape=shape)
    out = pallas_call(kernel, name="copy_tile", out_shape=shape)(
        jax.numpy.ones((8, 128)))
    assert out.shape == (8, 128)


def test_step_scopes_are_the_rules_update_scopes():
    """train/steps.py names the scopes, analysis/anatomy.py reads them: the
    two lists must agree."""
    from distributeddeeplearning_tpu.train import steps
    assert set(steps.STEP_SCOPES) - {steps.GRADS} == \
        set(anatomy._UPDATE_SCOPES)
    assert steps.LOSS_SCOPE in anatomy.PARTS


@pytest.fixture
def cache_here(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    return str(tmp_path / "cache")


def test_a_live_step_and_the_saved_file_give_the_same_table(cache_here):
    """`train_step.anatomy()` reads the executable the step runs;
    `aot.anatomy(name)` reads what `save` wrote beside the entry, and still
    does once the step is gone."""
    _mesh, state, batch, rng, step = _built(_config())
    with pytest.raises(RuntimeError, match="no executable"):
        step.anatomy()
    state, _ = step(state, batch, rng)
    live = step.anatomy()
    assert {"flash_fwd", "optimizer"} <= {
        anatomy.part_of(v)[1] for v in live.values()}
    del step, state
    assert aot.anatomy("gspmd_train_step") == live
    assert aot.anatomy("no_such_step") is None


def test_table_names_the_compilers_ragged_products_by_their_operands():
    """The TPU compiler makes kernels of its own of ``jax.lax.ragged_dot`` and
    names them itself; ``table`` gives them their operands' name stack (a
    backward operand's first) with the experts' scope at the end, and
    ``part_of`` then books the innermost model scope."""
    fwd = "jit(f)/grads/jvp(M)/layer1/moe/moe_dispatch/gather"
    bwd = "jit(f)/grads/transpose(jvp(M))/layer1/moe/moe_combine/mul"
    text = f"""HloModule jit_f

ENTRY %main.9 (x.1: bf16[8,4]) -> bf16[8,4] {{
  %x.1 = bf16[8,4]{{1,0}} parameter(0)
  %sizes.1 = s32[2]{{0}} reduce(%x.1), metadata={{op_name="{fwd}"}}
  %ragged-dot-metadata.1 = (s32[3]{{0}}, s32[1]{{0}}) custom-call(%sizes.1), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-metadata"}}
  %gte.1 = s32[3]{{0}} get-tuple-element(%ragged-dot-metadata.1), index=0
  %rows.1 = bf16[8,4]{{1,0}} fusion(%x.1), kind=kLoop, calls=%f.1, metadata={{op_name="{fwd}"}}
  %ragged-dot-none.1 = bf16[8,4]{{1,0}} custom-call(%gte.1, %rows.1), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %cot.1 = bf16[8,4]{{1,0}} fusion(%ragged-dot-none.1), kind=kLoop, calls=%f.2, metadata={{op_name="{bwd}"}}
  ROOT %ragged-dot-none.2 = bf16[8,4]{{1,0}} custom-call(%gte.1, %cot.1), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
}}
"""
    got = anatomy.table(text)
    assert got["ragged-dot-metadata.1"] == fwd + "/moe_experts"
    assert got["ragged-dot-none.1"] == fwd + "/moe_experts/moe_experts"
    assert got["ragged-dot-none.2"] == bwd + "/moe_experts"
    assert anatomy.part_of(got["ragged-dot-none.1"]) == ("forward",
                                                         "moe_experts")
    assert anatomy.part_of(got["ragged-dot-none.2"]) == ("backward",
                                                         "moe_experts")


@pytest.mark.parametrize("phase,outer", [
    ("forward", "jit(step_fn)/grads/jvp(AfmoeLM)/layer1/moe/moe_dispatch/"
                "cond/branch_1_fun/"),
    ("backward", "jit(step_fn)/grads/transpose(jvp(AfmoeLM))/grads/"
                 "jvp(AfmoeLM)/checkpoint/layer1/moe/moe_dispatch/cond/"
                 "branch_1_fun/jvp(moe_experts)/")])
def test_table_books_what_a_conditionals_branches_hold(phase, outer):
    """The routed experts' two bodies are the branches of a ``conditional``
    (models/moe.py): a branch's gather into the row buffer, the select round
    a product and the compiler's own kernel keep the parts they have in a
    single body, forward and backward; the kernel's asynchronously copied
    argument, which reads only the branch's parameter, is handed the kernel's
    name down its whole chain; the ``conditional`` itself is in the trace as
    an operation that spans its branch's, and its time is left out."""
    gather = outer + "moe_dispatch/gather"
    select = outer + "moe_experts/jit(_where)/select_n"
    pred = outer.split("cond/")[0] + "le"
    text = f"""HloModule jit_step_fn

%branch.1 (arg.1: (bf16[8,4], s32[2], bf16[2,4,4])) -> (bf16[8,4]) {{
  %arg.1 = (bf16[8,4]{{1,0}}, s32[2]{{0}}, bf16[2,4,4]{{2,1,0}}) parameter(0)
  %gte.1 = bf16[8,4]{{1,0}} get-tuple-element(%arg.1), index=0
  %gte.2 = s32[2]{{0}} get-tuple-element(%arg.1), index=1
  %gte.3 = bf16[2,4,4]{{2,1,0}} get-tuple-element(%arg.1), index=2
  %rows.1 = bf16[8,4]{{1,0}} fusion(%gte.1), kind=kCustom, calls=%f.1, metadata={{op_name="{gather}"}}
  %live.1 = bf16[8,4]{{1,0}} fusion(%rows.1), kind=kLoop, calls=%f.2, metadata={{op_name="{select}"}}
  %copy-start.1 = (bf16[2,4,4]{{2,1,0:S(1)}}, bf16[2,4,4]{{2,1,0}}, u32[]{{:S(2)}}) copy-start(%gte.3)
  %copy-done.1 = bf16[2,4,4]{{2,1,0:S(1)}} copy-done(%copy-start.1)
  %ragged-dot-none.1 = bf16[8,4]{{1,0}} custom-call(%gte.2, %live.1, %copy-done.1), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  ROOT %tuple.1 = (bf16[8,4]{{1,0}}) tuple(%ragged-dot-none.1)
}}

ENTRY %main.9 (x.1: (bf16[8,4], s32[2], bf16[2,4,4])) -> (bf16[8,4]) {{
  %x.1 = (bf16[8,4]{{1,0}}, s32[2]{{0}}, bf16[2,4,4]{{2,1,0}}) parameter(0)
  %fits.1 = s32[] fusion(%x.1), kind=kLoop, calls=%f.3, metadata={{op_name="{pred}"}}
  ROOT %conditional.1 = (bf16[8,4]{{1,0}}) conditional(%fits.1, %x.1, %x.1), branch_computations={{%branch.0, %branch.1}}
}}
"""
    got = anatomy.table(text)
    want = {"rows.1": "moe_routing", "live.1": "moe_experts",
            "ragged-dot-none.1": "moe_experts", "copy-done.1": "moe_experts",
            "copy-start.1": "moe_experts", "gte.3": "moe_experts"}
    assert {k: anatomy.part_of(got[k]) for k in want} == \
        {k: (phase, part) for k, part in want.items()}
    assert got["ragged-dot-none.1"] == select + "/moe_experts"
    assert got["conditional.1"].startswith(anatomy.SPANS_ITS_BRANCH)
    durations = {"%conditional.1 = (bf16[8,4]) conditional(%fits.1)": 3.5,
                 "%rows.1 = bf16[8,4] fusion(%gte.1)": 1.0,
                 "%ragged-dot-none.1 = bf16[8,4] custom-call(%gte.2)": 2.0,
                 "%fits.1 = s32[] fusion(%x.1)": 0.5}
    assert anatomy.by_part(durations, got) == {
        (phase, "moe_routing"): 1.5, (phase, "moe_experts"): 2.0}


def test_a_while_spans_its_body_and_is_left_out():
    """A ``lax.scan`` is a ``while`` in the compiled step, and a device trace
    holds it as one operation that spans every turn of its body (seen on the
    chip, PR 31): the body's operations book under their own names, each as
    often as it ran, and the loop's own time is left out, as a
    ``conditional``'s is. A loop inside a loop is left out the same way."""
    scope = ("jit(step_fn)/grads/jvp(KimiLinearLM)/layer1/attention/attn_kda/"
             "while/body/")
    text = f"""HloModule jit_step_fn

%inner_body.1 (arg.2: (f32[8,4])) -> (f32[8,4]) {{
  %arg.2 = (f32[8,4]{{1,0}}) parameter(0)
  %gte.2 = f32[8,4]{{1,0}} get-tuple-element(%arg.2), index=0
  %state.1 = f32[8,4]{{1,0}} fusion(%gte.2), kind=kLoop, calls=%f.1, metadata={{op_name="{scope}while/body/dot_general"}}
  ROOT %tuple.2 = (f32[8,4]{{1,0}}) tuple(%state.1)
}}

%body.1 (arg.1: (f32[8,4])) -> (f32[8,4]) {{
  %arg.1 = (f32[8,4]{{1,0}}) parameter(0)
  %gte.1 = f32[8,4]{{1,0}} get-tuple-element(%arg.1), index=0
  %pairs.1 = f32[8,4]{{1,0}} fusion(%gte.1), kind=kLoop, calls=%f.2, metadata={{op_name="{scope}checkpoint/exp"}}
  %while.2 = (f32[8,4]{{1,0}}) while(%pairs.1), condition=%cond.2, body=%inner_body.1, metadata={{op_name="{scope}checkpoint/while"}}
  ROOT %tuple.1 = (f32[8,4]{{1,0}}) tuple(%while.2)
}}

ENTRY %main.9 (x.1: (f32[8,4])) -> (f32[8,4]) {{
  %x.1 = (f32[8,4]{{1,0}}) parameter(0)
  ROOT %while.1 = (f32[8,4]{{1,0}}) while(%x.1), condition=%cond.1, body=%body.1, metadata={{op_name="{scope[:-5]}"}}
}}
"""
    got = anatomy.table(text)
    assert got["while.1"].startswith(anatomy.SPANS_ITS_BRANCH)
    assert got["while.2"].startswith(anatomy.SPANS_ITS_BRANCH)
    # it still says what it belongs to
    assert anatomy.part_of(got["while.1"]) == ("forward", "attention_kda")
    durations = {"%while.1 = (f32[8,4]) while(%x.1)": 10.0,
                 "%while.2 = (f32[8,4]) while(%pairs.1)": 6.0,
                 "%pairs.1 = f32[8,4] fusion(%gte.1)": 3.0,
                 "%state.1 = f32[8,4] fusion(%gte.2)": 6.0}
    assert anatomy.by_part(durations, got) == {
        ("forward", "attention_kda"): 9.0}
