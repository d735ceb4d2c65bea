"""The main path's Pallas kernels, compiled by the real TPU compiler.

Interpret mode (every other kernel test) cannot see what Mosaic refuses: a
cast it does not lower, a slice off the tiling, too much VMEM. The TPU's
compiler is installed here and compiles for a chip that is described, not
attached — so these cases lower each kernel at the widths the train commands
in README.md use, for one device of a described ``v5e:2x2``, and assert the
``tpu_custom_call`` is in the compiled text. Nothing runs; correctness on the
chip is chip_smoke.py's job.

Which way a kernel runs is decided at lowering time from the platform of the
devices the program is placed on (ops/pallas.py), so handing a described TPU
device is all the steering these tests need. The topology is described inside
a fixture — never at import, in a skipif or in parametrize arguments — because
only one process may load libtpu and every xdist worker imports this file.
"""

import functools
import math
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_for(one_chip):
    """``compiled_for(fn, *shapes)`` -> ``fn`` lowered and compiled for the
    described chip. The persistent compile cache is off around it: an entry
    written for a described device cannot be read back without one, and
    every later compile would warn about it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()

    yield run
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def compile_for(compiled_for):
    """``compile_for(fn, *shapes)`` -> the compiled HLO text."""
    return lambda fn, *shapes: compiled_for(fn, *shapes).as_text()


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def _flash_loss(q, k, v, mask, *, causal, dropout_rate):
    from distributeddeeplearning_tpu.ops import flash_attention
    out = flash_attention(q, k, v, mask, causal=causal,
                          dropout_rate=dropout_rate,
                          dropout_seed=jnp.int32(7) if dropout_rate else None)
    return out.astype(F32).sum()


# (B, S, H, D), causal, dropout: the gpt2_small train shape (README's
# ``--attn flash`` command, dropout 0.1 being the model default), masked
# BERT-base, the long-context D=128 shape, and a long causal sequence with
# dropout: the schedule tables in SMEM and what each kernel keeps in VMEM
# grow with S, and have to fit there too.
FLASH_CASES = [
    pytest.param((16, 1024, 12, 64), True, 0.0, id="gpt2-causal"),
    pytest.param((16, 1024, 12, 64), True, 0.1, id="gpt2-causal-dropout"),
    pytest.param((8, 512, 12, 64), False, 0.0, id="bert-masked"),
    pytest.param((8, 512, 12, 64), False, 0.1, id="bert-masked-dropout"),
    pytest.param((2, 2048, 8, 128), True, 0.0, id="d128-s2048"),
    pytest.param((1, 8192, 12, 64), True, 0.1, id="s8192-causal-dropout"),
]


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("shape,causal,rate", FLASH_CASES)
def test_flash_attention_compiles_for_v5e(compile_for, shape, causal, rate,
                                          grad):
    fn = functools.partial(_flash_loss, causal=causal, dropout_rate=rate)
    if grad:
        fn = jax.grad(fn, argnums=(0, 1, 2))
    text = compile_for(fn, (shape, BF16), (shape, BF16), (shape, BF16),
                       (shape[:2], I32))
    # forward alone is one kernel; backward adds one, the fused dK/dV/dQ
    # (every shape here fits its budget)
    assert text.count('custom_call_target="tpu_custom_call"') == (
        2 if grad else 1)
    for name in (("flash_fwd", "flash_dkv") if grad else ("flash_fwd",)):
        assert f"%{name}" in text
    assert "%flash_dq" not in text


# Trinity-Mini's attention at the benchmark cell's size: 32 Q heads of 128 on
# 4 K/V heads at S = 8192, full (causal) and under the 2048 window; and the
# grouped heads with dropout, whose hash the dK/dV kernel keys by Q head.
@pytest.mark.parametrize("window,rate", [(None, 0.0), (2048, 0.0),
                                         (2048, 0.1)],
                         ids=["full", "window", "window-dropout"])
def test_flash_attention_grouped_window_compiles_for_v5e(compile_for, window,
                                                         rate):
    from distributeddeeplearning_tpu.ops import flash_attention

    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, window=window, dropout_rate=rate,
            dropout_seed=jnp.int32(7) if rate else None)
        return out.astype(F32).sum()

    text = compile_for(jax.grad(loss, argnums=(0, 1, 2)),
                       ((1, 8192, 32, 128), BF16), ((1, 8192, 4, 128), BF16),
                       ((1, 8192, 4, 128), BF16))
    # one backward kernel: dK and dV of a K/V head and dQ of a Q head fit
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in ("flash_fwd", "flash_dkv"):
        assert f"%{name}" in text
    assert "%flash_dq" not in text


# The two backward kernels, which run where the fused kernel's accumulators
# pass its budget (long sequences), compiled at the shapes above with the
# budget set to nothing: gpt2's heads with dropout, trinity's grouped heads
# under the window, and latent attention's 192 / 128.
@pytest.mark.parametrize("q_shape,kv_heads,v_dim,window,rate", [
    pytest.param((16, 1024, 12, 64), 12, 64, None, 0.1, id="gpt2-dropout"),
    pytest.param((1, 8192, 32, 128), 4, 128, 2048, 0.0, id="grouped-window"),
    pytest.param((1, 8192, 32, 192), 32, 128, None, 0.0, id="latent")])
def test_flash_backward_two_kernels_compile_for_v5e(
        compile_for, monkeypatch, q_shape, kv_heads, v_dim, window, rate):
    import importlib

    from distributeddeeplearning_tpu.ops import flash_attention
    module = importlib.import_module(
        "distributeddeeplearning_tpu.ops.flash_attention")
    monkeypatch.setattr(module, "_FUSED_BWD_BYTES", -1)

    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, window=window, dropout_rate=rate,
            dropout_seed=jnp.int32(7) if rate else None)
        return out.astype(F32).sum()

    b, s, _, d = q_shape
    text = compile_for(jax.grad(loss, argnums=(0, 1, 2)), (q_shape, BF16),
                       ((b, s, kv_heads, d), BF16),
                       ((b, s, kv_heads, v_dim), BF16))
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert f"%{name}" in text


def test_routed_experts_two_bodies_compile_for_v5e(compiled_for):
    """One expert layer of the benchmark's trinity cell (8192 tokens of 2048,
    16 of 128 experts of width 1024 held, 8 a token), forward, recomputed and
    backward. The row buffer is 16 384 rows with the worst case's 65 536 as
    the other branch: one ``cond`` forward and one backward (the one in the
    recomputed forward is dead here: nothing reads its result), each branch
    with the 12 grouped products the single body has. Differentiated plainly
    the ``cond`` compiles too, and the small branch then writes the worst
    case's residuals as zeros on every step: 3 conditionals, and temporaries
    of 3.88 GB where the single body (``EXPECTED_ROWS_FACTOR`` 8, compiled
    the same way) has 1.16 GB and the two bodies 1.43 GB (a ``conditional``'s
    operands and results are buffers of their own), which is what the last
    line is for."""
    from distributeddeeplearning_tpu.models import moe

    layer = moe.RoutedExperts(
        hidden_size=2048, expert_width=1024, num_experts=128,
        experts_per_token=8, experts_held=(0, 16), route_scale=2.826,
        bias_update_rate=0.001)
    x = ((1, 8192, 2048), BF16)
    variables = jax.eval_shape(
        lambda: nn.unbox(layer.init(jax.random.key(0), jnp.zeros(*x),
                                    train=False)))
    leaves, tree = jax.tree_util.tree_flatten(variables)

    def loss(x, *leaves):
        apply = jax.checkpoint(
            lambda v, x: layer.apply(v, x, train=False).astype(F32).sum())
        return apply(jax.tree_util.tree_unflatten(tree, leaves), x)

    two = compiled_for(
        jax.value_and_grad(loss, argnums=tuple(range(1 + len(leaves)))),
        x, *[(leaf.shape, leaf.dtype) for leaf in leaves])
    text = two.as_text()
    assert text.count(" conditional(") == 2
    assert len(re.findall(r"= \S+ custom-call\(.*ragged-dot-none", text)) == 24
    temporaries = two.memory_analysis().temp_size_in_bytes
    print("temporaries:", temporaries)
    assert temporaries < 1.3 * 1.16e9


def test_fused_batchnorm_compiles_for_v5e(compile_for):
    """bn_act_train fwd+bwd at resnet50's stem activation, batch 256:
    (256*56*56, 64) — the lane-folded narrow-channel case."""
    from distributeddeeplearning_tpu.ops.fused_batchnorm import bn_act_train

    def loss(x, gamma, beta):
        y, _, _ = bn_act_train(x, gamma, beta, True, 1e-5)
        return y.astype(F32).sum()

    text = compile_for(jax.grad(loss, argnums=(0, 1, 2)),
                       ((256 * 56 * 56, 64), BF16), ((64,), F32),
                       ((64,), F32))
    assert text.count("tpu_custom_call") >= 4  # stats, apply, reduce, dx


# (M, K, N): resnet50 batch-256 bottleneck 1x1 convs — stage-1 conv3,
# stage-3 conv1, stage-4 conv3.
@pytest.mark.parametrize("m,k,n", [
    (802816, 64, 256), (50176, 1024, 256), (12544, 512, 2048)])
def test_fused_linear_bn_compiles_for_v5e(compile_for, m, k, n):
    from distributeddeeplearning_tpu.ops.fused_linear_bn import (
        bn_linear_stats)

    def loss(x, mu, inv, gamma, beta, w):
        y, s, ss = bn_linear_stats(x, mu, inv, gamma, beta, w, True, True)
        return y.astype(F32).sum() + s.sum() + ss.sum()

    vec = ((k,), F32)
    text = compile_for(jax.grad(loss, argnums=(0, 3, 4, 5)),
                       ((m, k), BF16), vec, vec, vec, vec, ((k, n), BF16))
    assert text.count("tpu_custom_call") >= 3  # fwd, dx, dw


@pytest.mark.parametrize("stage,grad,kernel", [
    ("in", False, "kda_in_fwd"), ("in", True, "kda_in_bwd"),
    ("out", False, "kda_out_fwd"), ("out", True, "kda_out_bwd")])
def test_kda_stages_compile_for_v5e(compile_for, stage, grad, kernel):
    """The fused stages round the chunked delta rule at the kimi cell's
    widths: one sequence of 8192 tokens, 32 heads of 128, 4 taps, bf16
    projections; a grid step is four heads' group of 8 chunks of 64."""
    from distributeddeeplearning_tpu.ops import kda_stages

    model, laid = (1, 8192, 4096), (16, 8, 32, 64, 128)
    if stage == "in":
        def fn(pq, pk, pv, pf, wq, wk, wv, a_log, dt_bias, mask):
            return kda_stages.kda_in((pq, pk, pv, pf), (wq, wk, wv), a_log,
                                     dt_bias, mask)
        shapes = ([(model, BF16)] * 4 + [((4, 4096), F32)] * 3
                  + [((32,), F32), ((4096,), F32), (model[:2], jnp.bool_)])
        wrt = tuple(range(9))
    else:
        def fn(o, gate, scale):
            return kda_stages.kda_out(o, gate, scale, eps=1e-5)
        shapes = [(laid, BF16), (model, BF16), ((128,), F32)]
        wrt = (0, 1, 2)
    if grad:
        # weighted, so that the forward is not dead under the gradient
        value = fn
        fn = jax.grad(lambda *a: sum(
            (o.astype(F32) ** 2).sum()
            for o in jax.tree_util.tree_leaves(value(*a))), argnums=wrt)
    text = compile_for(fn, *shapes)
    assert f"%{kernel}" in text
    assert text.count('custom_call_target="tpu_custom_call"') == (
        2 if grad else 1)


@pytest.mark.parametrize("grad,kernel", [(False, "kda_fwd"),
                                         (True, "kda_bwd")])
def test_kda_chunk_compiles_for_v5e(compile_for, grad, kernel):
    """The chunked delta rule's two kernels (ops/kda_chunk.py) at the kimi
    cell's shapes: 16 groups of 8 chunks of 64 tokens, 32 heads of 128
    channels, sub-chunks of 16, bf16 q, k and v, with a state handed in and
    out; a grid step is eight heads of one chunk, the chunks innermost. The
    forward alone is one kernel; the gradient is the forward and the
    backward, and no loop of XLA's."""
    from distributeddeeplearning_tpu.ops import kda

    laid = (16, 8, 32, 64, 128)

    def fn(q, k, v, g, beta, state):
        return kda.kda_groups(q, k, v, g, beta, state, return_state=True)

    if grad:
        value = fn
        fn = jax.grad(lambda *a: sum(
            (o.astype(F32) ** 2).sum() for o in value(*a)),
            argnums=tuple(range(6)))
    text = compile_for(fn, *([(laid, BF16)] * 3
                             + [(laid, F32), (laid[:4], F32),
                                ((32, 128, 128), F32)]))
    assert f"%{kernel}" in text
    assert text.count('custom_call_target="tpu_custom_call"') == (
        2 if grad else 1)
    assert not re.search(r" while\(", text)


@pytest.mark.parametrize("stage,grad,kernel", [
    ("in", False, "mhc_in_fwd"), ("in", True, "mhc_in_bwd"),
    ("out", False, "mhc_out_fwd"), ("out", True, "mhc_out_bwd")])
def test_mhc_kernels_compile_for_v5e(compile_for, stage, grad, kernel):
    """The hyper-connections' passes over the streams (ops/mhc.py) at the
    xing4 cell's widths: one sequence of 4096 tokens, four bf16 streams of
    3584 channels, 24 coefficients; a grid step is 128 tokens with all
    14336 channels in VMEM."""
    from distributeddeeplearning_tpu.ops import mhc

    t, n, c = 4096, 4, 3584
    m = n * n + 2 * n
    if stage == "in":
        def fn(x, scale, phi, alpha, bias):
            return mhc.mhc_in(x, scale, phi, alpha, bias, eps=1e-6)
        shapes = [((1, t, n * c), BF16), ((n * c,), F32), ((n * c, m), F32),
                  ((), F32), ((n,), F32)]
    else:
        fn = mhc.mhc_out
        shapes = [((1, t, n * c), BF16), ((1, t, c), BF16),
                  ((1, t, n + n * n), F32)]
    if grad:
        # weighted, so that the forward is not dead under the gradient
        value = fn
        fn = jax.grad(lambda *a: sum(
            (o.astype(F32) ** 2).sum()
            for o in jax.tree_util.tree_leaves(value(*a))),
            argnums=tuple(range(len(shapes))))
    text = compile_for(fn, *shapes)
    assert f"%{kernel}" in text
    assert text.count('custom_call_target="tpu_custom_call"') == (
        2 if grad else 1)


def _compiled_share_step(one_chip, name: str, seq: int, vocab: int):
    """(compiled step, parameters) of one chip's share `name` at one sequence
    of `seq` tokens: the mixed-precision AdamW step built as
    `train/loop.build` builds it (`make_gspmd_train_step` on a mesh of the
    described chip), lowered with shapes and compiled."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributeddeeplearning_tpu.config import (
        DataConfig, OptimizerConfig, ParallelConfig, PrecisionPolicy,
        TrainConfig)
    from distributeddeeplearning_tpu.models import model_spec
    from distributeddeeplearning_tpu.parallel import mesh as meshlib
    from distributeddeeplearning_tpu.train import optim, steps
    from distributeddeeplearning_tpu.train.state import TrainState

    policy = PrecisionPolicy.mixed()
    cfg = TrainConfig(
        model=name, backend=None, global_batch_size=1, seed=0,
        dtype=policy.compute_dtype, precision=policy, log_every=10 ** 9,
        attention_impl="flash", parallel=ParallelConfig(data=1),
        data=DataConfig(synthetic=True, dataset="mlm", seq_len=seq,
                        vocab_size=vocab),
        optimizer=OptimizerConfig(
            name="adamw", learning_rate=1e-5, reference_batch=1,
            weight_decay=0.1, schedule="constant", warmup_epochs=0.0,
            beta1=0.9, beta2=0.95))
    model = model_spec(cfg.model).build(
        vocab_size=vocab, dtype=BF16, seq_len=seq, attention_impl="flash")
    mesh = meshlib.make_mesh(cfg.parallel, devices=list(one_chip.device_set))
    tx, _ = optim.make_optimizer(cfg.optimizer, 1, 10 ** 6, 1)

    def init_fn(rng):
        variables = model.init({"params": rng, "dropout": rng},
                               jnp.zeros((1, seq), I32), train=False)
        return TrainState.create(
            params=variables["params"],
            opt_state=tx.init(variables["params"]),
            batch_stats=steps.model_state(variables), ema_params=None,
            loss_scale=steps.init_loss_scale(cfg))

    abstract = jax.eval_shape(init_fn, jax.random.key(0))
    parameters = sum(x.size for x in jax.tree_util.tree_leaves(
        abstract.params))
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        nn.logical_to_mesh(nn.get_partition_spec(abstract)),
        is_leaf=lambda x: isinstance(x, P))
    state = jax.tree_util.tree_map(   # the shardings are a prefix tree
        lambda s, sub: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            sub),
        shardings, abstract, is_leaf=lambda x: isinstance(x, NamedSharding))
    everywhere = NamedSharding(mesh, P())
    ids = jax.ShapeDtypeStruct((1, seq), I32, sharding=everywhere)
    rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=everywhere)
    step = steps.make_gspmd_train_step(model, tx, mesh, cfg, shardings,
                                       "tokens", "causal")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = step.lower(
            state, {"input_ids": ids, "attention_mask": ids}, rng).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    return compiled, parameters


def test_kimi_linear_ep32_step_compiles_for_v5e(one_chip):
    """The benchmark's kimi_linear cell: the whole mixed-precision AdamW step
    of `kimi_linear_ep32` at one sequence of 8192 tokens, built as
    `train/loop.build` builds it (`make_gspmd_train_step` on a mesh of the
    described chip), lowered with shapes and compiled. What the chip's
    compiler says of it: it fits (7.23 GB of state: float32 masters and
    Adam's two moments of 602M parameters; 4.96 GB of temporaries, the
    float32 gradients and the state that enters each chunk of each KDA
    layer, kept across remat, among them: 4.81 before the latent layer's
    backward was one flash kernel, whose dQ, dK and dV are live at once,
    where dQ could go before dK and dV came; 4.82 when a group's entering
    state was kept and XLA looped over the chunks, 4.83 before a chunk's
    stateless work was two kernels, whose (8, 32, 4, 16, 16, 128) pair
    intermediates were never the peak; 5.40 before the pointwise stages
    round the delta rule were kernels), the latent layer's two flash
    kernels (the forward and the one backward kernel, which carries dQ) are
    there at 192 / 128, and the chunked delta rule is two
    kernels a KDA layer and no `while`: its forward kernel once (not again
    for a recomputed forward, whose result and entering states the block
    keeps) and its backward kernel once."""
    compiled, parameters = _compiled_share_step(
        one_chip, "kimi_linear_ep32", seq=8192, vocab=20480)
    assert parameters == 602_449_792
    memory = compiled.memory_analysis()
    print("state:", memory.argument_size_in_bytes, "temporaries:",
          memory.temp_size_in_bytes, "whiles:",
          len(re.findall(r" while\(", compiled.as_text())))
    assert memory.argument_size_in_bytes == pytest.approx(
        12 * parameters, rel=0.001)
    assert memory.temp_size_in_bytes < 1.1 * 4.81e9
    text = compiled.as_text()
    # one backward kernel (the fused dK/dV/dQ), so no flash_dq
    for name, n in (("flash_fwd", 1), ("flash_dq", 0), ("flash_dkv", 1)):
        assert len(re.findall(rf"%{name}\S* = ", text)) == n, name
    assert re.search(r"%flash_fwd\S* = \(bf16\[32,8192,128\]", text)
    assert not re.findall(r" while\(", text)
    # the pointwise stages round the operator (ops/kda_stages.py): a KDA
    # layer's input stage forward, again under the block's remat, and
    # backward; its output stage likewise (the recomputed forward feeds the
    # output projection's weight gradient); each under the model's scope,
    # so the trace books it as `attention_kda`
    # and the chunked delta rule (ops/kda_chunk.py): the forward kernel
    # once, the backward kernel once
    calls = {name: re.findall(rf"%{name}\S* = .*", text)
             for name in ("kda_in_fwd", "kda_in_bwd", "kda_out_fwd",
                          "kda_out_bwd", "kda_fwd", "kda_bwd")}
    assert {k: len(v) for k, v in calls.items()} == {
        "kda_in_fwd": 8, "kda_in_bwd": 4, "kda_out_fwd": 8, "kda_out_bwd": 4,
        "kda_fwd": 4, "kda_bwd": 4}
    assert all("attn_kda" in line for lines in calls.values()
               for line in lines)
    # so no float32 array of a group's pair intermediates is left under the
    # scope: (8, 32, 4, 16, 16, 128) a pair and channel, (8, 32, 4, 16, 64,
    # 128) a sub-chunk's column factors
    pairs = [m.group(0)[:200] for m in re.finditer(
        r"%\S+ = f32\[[\d,]*4,16,(16|64),128\]\S* .*", text)
        if "attn_kda" in m.group(0)]
    assert not pairs, pairs
    # and with the relayouts in their index maps, no pass of its own lays a
    # float32 (8192, 4096) array of a KDA layer out anew
    entry = text[text.index("\nENTRY "):]
    relaid = [m.group(0) for m in re.finditer(
        r"%\S+ = f32\[([\d,]+)\]\S* (copy|transpose|reshape)\(.*", entry)
        if "attn_kda" in m.group(0)
        and math.prod(map(int, m.group(1).split(","))) == 8192 * 4096]
    assert not relaid, relaid



def test_xing4_ep8_step_compiles_for_v5e(one_chip):
    """The benchmark's xing4 cell: the whole mixed-precision AdamW step of
    `xing4_ep8` at one sequence of 4096 tokens. What the chip's compiler says
    of it: it fits (9.11 GB of state: float32 masters and Adam's two moments
    of 759M parameters; 4.35 GB of temporaries, the float32 gradients and
    the four residual streams' kept block inputs among them), every layer's
    two flash kernels (the forward and the one backward kernel, which
    carries dQ) are there once, at queries of 192 and values of 128 (a
    recomputed block keeps the forward kernel's result), and the Sinkhorn
    iterations are `while`s under the hyper-connections' scope: one a
    hyper-connection forward, one recomputed, one backward. The passes over
    the streams are the four kernels of ops/mhc.py under the same scope: the
    input pass's forward once a hyper-connection and again where the block
    is remade, the attention round's write-back likewise and the
    feed-forward round's once (its result is the block's, which the
    boundary keeps), each backward kernel once a hyper-connection; the
    temporaries were 4.45 GB when the passes were array lines."""
    compiled, parameters = _compiled_share_step(
        one_chip, "xing4_ep8", seq=4096, vocab=16384)
    assert parameters == 759_489_550
    memory = compiled.memory_analysis()
    print("state:", memory.argument_size_in_bytes, "temporaries:",
          memory.temp_size_in_bytes, "whiles:",
          len(re.findall(r" while\(", compiled.as_text())))
    assert memory.argument_size_in_bytes == pytest.approx(
        12 * parameters, rel=0.001)
    assert memory.temp_size_in_bytes < 1.1 * 4.34e9
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 0.9 * 16 * 1024 ** 3)
    text = compiled.as_text()
    # one backward kernel (the fused dK/dV/dQ), so no flash_dq
    for name, n in (("flash_fwd", 5), ("flash_dq", 0), ("flash_dkv", 5)):
        assert len(re.findall(rf"%{name}\S* = ", text)) == n, name
    assert re.search(r"%flash_fwd\S* = \(bf16\[32,4096,128\]", text)
    loops = re.findall(r" while\(.*", text)
    assert len(loops) == 10 * 3
    assert all("/mhc/" in line for line in loops)
    calls = {name: re.findall(rf"%{name}\S* = .*", text)
             for name in ("mhc_in_fwd", "mhc_in_bwd", "mhc_out_fwd",
                          "mhc_out_bwd")}
    assert {k: len(v) for k, v in calls.items()} == {
        "mhc_in_fwd": 20, "mhc_in_bwd": 10, "mhc_out_fwd": 15,
        "mhc_out_bwd": 10}
    assert all("/mhc/" in line for lines in calls.values()
               for line in lines)


@pytest.mark.slow
def test_nemotron3_nano_ep16_step_compiles_for_v5e(one_chip):
    """The benchmark's nemotron3_nano cell: the whole mixed-precision AdamW
    step of `nemotron3_nano_ep16` at one sequence of 8192 tokens. What the
    chip's compiler says of it: it fits (8.0 GB of state: float32 masters
    and Adam's two moments of 667M parameters, with the temporaries below
    nine tenths of the chip), the attention layer's two flash kernels (the
    forward and the one backward kernel, which carries dQ) are there once at
    32 query heads of 128, and the chunked recurrence is XLA's products with
    no loop. Marked slow: a whole step's compile, as kimi's and xing4's
    above, is a minute or two of a tier-1 run."""
    compiled, parameters = _compiled_share_step(
        one_chip, "nemotron3_nano_ep16", seq=8192, vocab=16384)
    assert parameters == 666_962_944
    memory = compiled.memory_analysis()
    print("state:", memory.argument_size_in_bytes, "temporaries:",
          memory.temp_size_in_bytes)
    assert memory.argument_size_in_bytes == pytest.approx(
        12 * parameters, rel=0.001)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 0.9 * 16 * 1024 ** 3)
    text = compiled.as_text()
    for name, n in (("flash_fwd", 1), ("flash_dq", 0), ("flash_dkv", 1)):
        assert len(re.findall(rf"%{name}\S* = ", text)) == n, name
    assert re.search(r"%flash_fwd\S* = \(bf16\[32,8192,128\]", text)
    assert not re.findall(r" while\(", text)
