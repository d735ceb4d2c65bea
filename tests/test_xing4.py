"""The Xing4.0 decoder (models/xing4.py: four residual streams under
manifold-constrained hyper-connections, latent attention with a query
bottleneck and YaRN-rotated keys, models/moe.py::RoutedExperts as it stands)
against its plain reference (benchmark/references/xing4.py) on seeded
weights, at a small size on the CPU: names and shapes, logits, loss, every
gradient leaf, the selection bias after a step; dense against flash attention
at 192 / 128 with the YaRN scale; what a recomputed block keeps; the share
test (the shares of one block add up to the uncut reference block); Sinkhorn's
result; YaRN's frequencies; the published model's parameter count; the
trainer's steps and counters."""

import json
import math
import os
import re
import sys

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from distributeddeeplearning_tpu import models  # noqa: E402
from distributeddeeplearning_tpu.models import hyper_connections as mhc  # noqa: E402
from distributeddeeplearning_tpu.models import llama, moe, xing4  # noqa: E402
from distributeddeeplearning_tpu.ops.attention import multihead_attention  # noqa: E402

ref = harness.load_module("references", "xing4")
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))
import tiny_xing4  # noqa: E402
from tests.attention_refs import flash_kernel_calls  # noqa: E402

SZ = ref.sizes(tiny_xing4.XING_TINY)
BATCH, SEQ = 2, 80
LAYERS = ("layer0", "layer1")
MOE_LAYERS = LAYERS[1:]
LEAVES = sorted(ref.init_params(SZ, jax.random.key(0)))
with open(os.path.join(REPO, "benchmark", "configs", "xing4.json")) as _fh:
    PUBLISHED_SZ = ref.sizes(json.load(_fh))


def unflatten(flat):
    return flax.traverse_util.unflatten_dict(
        {tuple(k.split("/")): v for k, v in flat.items()})


def flatten(tree):
    return {"/".join(k): v
            for k, v in flax.traverse_util.flatten_dict(tree).items()}


def router_state(extra):
    return {name: {"moe": {"bias": extra[f"{name}/moe/bias"]}}
            for name in MOE_LAYERS}


@pytest.fixture(scope="module")
def seeded():
    key = jax.random.key(3)
    params = ref.init_params(SZ, key)
    batch = ref.make_batch({"batch": BATCH, "seq_len": SEQ}, SZ, key, 0)
    return params, ref.init_extra(SZ), batch


def tiny_model(**kw):
    return models.get_model("xing4_tiny", dtype=jnp.float32,
                            vocab_size=SZ["vocab"], attention_impl="flash",
                            **kw)


def program_loss(model, tree, state, ids, mask=None):
    logits, mutated = model.apply(
        {"params": tree, moe.ROUTER_STATE: state}, ids, mask, train=True,
        mutable=[moe.ROUTER_STATE, moe.MOE_METRICS, mhc.MHC_METRICS])
    logp = jax.nn.log_softmax(logits[:, :-1])
    loss = -jnp.take_along_axis(logp, ids[:, 1:, None], -1).mean()
    return loss, (logits, mutated)


@pytest.fixture(scope="module")
def both(seeded):
    """Reference and program (flash kernels interpreted; float32) on the same
    weights and batch: losses, logits, gradients, biases."""
    params, extra, batch = seeded
    ids = batch["input_ids"]
    model = tiny_model()
    with jax.default_matmul_precision("highest"):
        want_logits = jnp.stack([ref.forward(SZ, params, extra, ids[i])
                                 for i in range(BATCH)])
        fn = ref.make_grad_fn(SZ, {"batch": BATCH, "seq_len": SEQ})
        want_loss, want_grads, want_extra = fn(params, extra, batch, None)
        (loss, (logits, mutated)), grads = jax.value_and_grad(
            lambda p: program_loss(model, p, router_state(extra), ids),
            has_aux=True)(unflatten(params))
    return dict(want_logits=want_logits, want_loss=want_loss,
                want_grads=want_grads, want_extra=want_extra, loss=loss,
                logits=logits, grads=flatten(grads), mutated=mutated)


def test_names_and_shapes_are_the_references(seeded):
    params, _, _ = seeded
    model = tiny_model()
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False))
    theirs = {k: (v.shape, v.dtype) for k, v in flatten(
        flax.core.unfreeze(flax.linen.unbox(shapes["params"]))).items()}
    assert theirs == {k: (v.shape, v.dtype) for k, v in params.items()}
    assert set(flatten(flax.linen.unbox(shapes[moe.ROUTER_STATE]))) == \
        set(ref.init_extra(SZ))


def test_the_static_leaves_start_where_the_reference_puts_them(seeded):
    """alpha, the static terms and the norm scales are not drawn: the
    program's own initialiser gives the reference's values."""
    params, _, _ = seeded
    model = tiny_model()
    mine = flatten(flax.core.unfreeze(flax.linen.unbox(model.init(
        {"params": jax.random.key(0)}, jnp.zeros((1, 8), jnp.int32),
        train=False)["params"])))
    static = [k for k in params if not k.endswith("/kernel")
              and k != "embed_tokens"]
    assert any(k.endswith("attn_hc/bias") for k in static)
    for name in static:
        np.testing.assert_array_equal(np.asarray(mine[name]),
                                      np.asarray(params[name]), err_msg=name)
    bias = params["layer0/attn_hc/bias"]
    assert float(jax.nn.sigmoid(bias[0])) == pytest.approx(0.25)  # 1 / n
    assert float(2 * jax.nn.sigmoid(bias[4])) == 1.0


def test_logits_and_loss_match_the_reference(both):
    # float32 both sides; the kernels' online softmax and the plain one, the
    # mixes' and the Sinkhorn iterations' sums in another order, part by
    # rounding only (logits up to 0.7: read 1.8e-7)
    np.testing.assert_allclose(np.asarray(both["logits"]),
                               np.asarray(both["want_logits"]),
                               rtol=0, atol=5e-6)
    assert float(both["loss"]) == pytest.approx(float(both["want_loss"]),
                                                rel=1e-6)


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_the_reference(both, leaf):
    """Float32 rounding: the widest leaf reads 8e-6 of its largest entry (a
    hyper-connection's static terms, sums over every token of both signs
    through 20 Sinkhorn iterations), the matrices under 2e-6."""
    got, want = both["grads"][leaf], both["want_grads"][leaf]
    scale = float(jnp.abs(want).max())
    assert scale > 0, "a leaf without a gradient is a part that never ran"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=3e-5 * scale)


@pytest.mark.parametrize("name", MOE_LAYERS)
def test_the_selection_bias_after_a_step_matches_the_reference(both, name):
    got = both["mutated"][moe.ROUTER_STATE][name]["moe"]["bias"]
    want = both["want_extra"][f"{name}/moe/bias"]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(jnp.abs(got).max()) > 0          # it moved,
    assert float(jnp.abs(got.mean())) < 1e-9      # and kept its mean
    sown = both["mutated"][moe.MOE_METRICS][name]["moe"]
    assert float(sown["dropped"][0]) == 0.0
    assert 0 < float(sown["tokens_here"][0]) < BATCH * SEQ * SZ["top_k"]


def test_every_hyper_connection_sows_its_row_sum_gap(both, seeded):
    """The largest |row sum - 1| after 20 iterations: by hand from the
    reference's own pieces for layer 0's first hyper-connection, whose input
    is the embedding copied to the streams."""
    params, _, batch = seeded
    p = ref._sub(params, "layer0/attn_hc/")
    gaps = []
    with jax.default_matmul_precision("highest"):
        for ids in batch["input_ids"]:
            x = params["embed_tokens"][ids]
            x = jnp.broadcast_to(x[:, None], (SEQ, SZ["streams"], 64))
            _, _, h_res = ref.hc_coefficients(SZ, p, x)
            gaps.append(jnp.abs(h_res.sum(-1) - 1).max())
            # the columns were normalised last
            assert float(jnp.abs(h_res.sum(-2) - 1).max()) < 2e-6
    sown = both["mutated"][mhc.MHC_METRICS]
    assert float(sown["layer0"]["attn_hc"]["row_sum_gap"][0]) == \
        pytest.approx(float(max(gaps)), rel=0.05, abs=2e-7)
    for name in LAYERS:
        for hc in ("attn_hc", "ffn_hc"):
            # B_res starts as a diagonal of 4: strongly diagonal matrices
            # are where Sinkhorn is slowest, and 20 iterations leave 4e-5
            assert 0 < float(sown[name][hc]["row_sum_gap"][0]) < 2e-4


# --------------------------------------------------------------------------
# Sinkhorn, YaRN, the attention's scale
# --------------------------------------------------------------------------

@pytest.mark.parametrize("scale", [0.3, 1.0, 3.0])
def test_sinkhorn_gives_doubly_stochastic_matrices(scale):
    """Random 4 x 4 inputs inside the clamp, N(0, scale^2): after 20
    iterations every column sums to 1 within 1e-5 (they were normalised
    last), every row too where the logits are a third of a unit apart (ten
    times what alpha = 0.01 gives), every entry is positive, and the program's result
    (entries as arrays of their own, under a scan) is the reference's
    (reductions, a Python loop). A unit apart the rows are within 2e-4, and
    three units apart 20 iterations are not enough (within 0.05): the step's
    counter
    `mhc_max_row_sum_gap` is there to say when training has moved Phi that
    far."""
    logits = scale * jax.random.normal(jax.random.key(5), (4, 4, 300))
    assert float(jnp.abs(logits).max()) < 30
    got = mhc.sinkhorn(logits, iters=20, eps=1e-6, clamp=(-30.0, 30.0))
    assert float(jnp.abs(got.sum(0) - 1).max()) < 1e-5
    assert float(jnp.abs(got.sum(1) - 1).max()) < {0.3: 1e-5, 1.0: 2e-4,
                                                   3.0: 0.05}[scale]
    assert float(got.min()) > 0
    want = ref.sinkhorn(jnp.moveaxis(logits, -1, 0),
                        dict(clamp=(-30, 30), sinkhorn_iters=20, hc_eps=1e-6))
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(got, -1, 0)),
                               np.asarray(want), rtol=0, atol=1e-6)
    assert float(mhc.row_sum_gap(got)) == pytest.approx(
        float(jnp.abs(got.sum(1) - 1).max()))


def test_the_clamp_sits_before_the_exponential():
    logits = jnp.array([[100.0, -100.0], [0.0, 0.0]])[:, :, None]
    got = mhc.sinkhorn(logits, iters=20, eps=1e-6, clamp=(-30.0, 30.0))
    assert bool(jnp.isfinite(got).all()) and float(got.min()) >= 0
    assert float(jnp.abs(got.sum(0) - 1).max()) < 1e-5


def test_yarn_frequencies_at_the_published_numbers():
    """low = 10 and high = 23 for 64 rotated channels, base 10000, 4096
    positions, beta 32 / 1; pairs under 10 keep plain RoPE's frequency,
    pairs from 23 on have it divided by 64, a linear ramp between."""
    d, base, span, factor = 64, 10000.0, 4096, 64.0
    low = math.floor(d * math.log(span / (2 * math.pi * 32))
                     / (2 * math.log(base)))
    high = math.ceil(d * math.log(span / (2 * math.pi * 1))
                     / (2 * math.log(base)))
    assert (low, high) == (10, 23)
    freqs, got_low, got_high = llama.yarn_frequencies(
        d, theta=base, factor=factor, original_max_position=span,
        beta_fast=32, beta_slow=1)
    assert (got_low, got_high) == (10, 23)
    i = np.arange(32)
    theta = base ** (-2.0 * i / d)
    ramp = np.clip((i - 10) / 13.0, 0.0, 1.0)
    want = theta * (1 - ramp) + theta / factor * ramp
    np.testing.assert_allclose(np.asarray(freqs), want, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(freqs[:11]), theta[:11], rtol=2e-6)
    np.testing.assert_allclose(np.asarray(freqs[23:]), theta[23:] / 64,
                               rtol=2e-6)
    ref_freqs, ref_low, ref_high, m = ref.yarn(PUBLISHED_SZ)
    assert (ref_low, ref_high) == (10, 23)
    np.testing.assert_allclose(np.asarray(ref_freqs), want, rtol=2e-6)
    assert m == pytest.approx(1.4159, abs=5e-5)
    cfg = xing4.Xing4Config()
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2,
                                              rel=1e-4)
    assert llama.yarn_mscale(64.0, 1.0) == pytest.approx(m)
    assert llama.yarn_mscale(1.0, 1.0) == 1.0


def test_apply_rope_keeps_plain_frequencies_by_default():
    x = jax.random.normal(jax.random.key(0), (1, 12, 2, 8))
    plain = 1.0 / (10000.0 ** (jnp.arange(0, 8, 2, dtype=jnp.float32) / 8))
    np.testing.assert_array_equal(
        np.asarray(llama.apply_rope(x, theta=10000.0)),
        np.asarray(llama.apply_rope(x, freqs=plain)))
    other = llama.apply_rope(x, freqs=plain / 64)
    assert float(jnp.abs(other - llama.apply_rope(x, theta=10000.0)).max()) \
        > 0.1


def test_dense_and_flash_attention_agree_at_192_128_with_the_yarn_scale():
    """The published head sizes (queries and keys 192, values 128), causal,
    at the scale 192^-1/2 x 1.4159^2: the three flash kernels (interpreted)
    against the dense path, results and gradients; and the scale reaches
    both (it is not the default's result)."""
    key = jax.random.key(7)
    b, s, h = 1, 256, 2
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (b, s, h, 192))
            for i in range(2))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, 128))
    scale = xing4.Xing4Config().softmax_scale

    def attend(impl, scale):
        def f(q, k, v):
            out = multihead_attention(q, k, v, None, impl=impl, causal=True,
                                      dtype=jnp.float32, scale=scale)
            return (out * jnp.cos(jnp.arange(out.size).reshape(out.shape))
                    ).sum(), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    with jax.default_matmul_precision("highest"):
        (_, dense), dense_grads = attend("dense", scale)
        (_, flash), flash_grads = attend("flash", scale)
        (_, default), _ = attend("flash", None)
        (_, default_dense), _ = attend("dense", 192 ** -0.5)
    assert dense.shape == (b, s, h * 128)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), rtol=0,
                               atol=2e-5)
    for got, want in zip(flash_grads, dense_grads):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=0,
            atol=2e-5 * float(jnp.abs(want).max()))
    assert float(jnp.abs(default - flash).max()) > 0.05
    np.testing.assert_allclose(np.asarray(default),
                               np.asarray(default_dense), rtol=0, atol=2e-5)
    with pytest.raises(ValueError, match="scales the scores"):
        multihead_attention(q, k, k, None, impl="ring", causal=True,
                            dtype=jnp.float32, scale=scale)


# A recomputed block keeps the routed experts' result and the flash kernel's
# (models/xing4.py): the forward kernel stands once a layer in the gradient's
# program, not twice, and the gradients are those of the blocks kept whole.

@pytest.mark.parametrize("remat", [False, True], ids=["kept", "recomputed"])
def test_each_flash_kernel_stands_once_a_layer(seeded, remat):
    params, extra, batch = seeded
    model = tiny_model(remat=remat)
    n = model.cfg.num_layers
    assert flash_kernel_calls(
        jax.grad(lambda p: program_loss(model, p, router_state(extra),
                                        batch["input_ids"])[0]),
        unflatten(params)) == {"flash_fwd": n, "flash_dq": 0,
                               "flash_dkv": n}  # one backward kernel


MHC_KERNELS = ("mhc_in_fwd", "mhc_in_bwd", "mhc_out_fwd", "mhc_out_bwd")


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "recomputed"])
def test_each_mhc_kernel_stands_once_a_hyper_connection_and_pass(seeded,
                                                                 remat):
    """The counter that the hyper-connections' kernels (ops/mhc.py) engaged:
    in the gradient's program, lowered for the TPU from the CPU, every
    hyper-connection (two a layer) runs its input pass forward and backward
    once, and its write-back backward once; a recomputed block runs both
    input passes' forward again and the attention round's write-back (whose
    result the feed-forward round reads), not the feed-forward round's. Each
    call's name stack books it under the part ``residual_mhc`` by the rule
    "a model's own scope wins" (analysis/anatomy.py), a backward kernel in
    the backward phase."""
    from distributeddeeplearning_tpu.analysis import anatomy

    params, extra, batch = seeded
    model = tiny_model(remat=remat)
    hcs = 2 * model.cfg.num_layers
    lowered = jax.jit(jax.grad(
        lambda p: program_loss(model, p, router_state(extra),
                               batch["input_ids"])[0])).trace(
        unflatten(params)).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    again = hcs // 2 if remat else 0
    assert {name: text.count(f'kernel_name = "{name}"')
            for name in MHC_KERNELS} == {
        "mhc_in_fwd": hcs + 2 * again, "mhc_in_bwd": hcs,
        "mhc_out_fwd": hcs + again, "mhc_out_bwd": hcs}
    stacks = re.findall(r'loc\("([^"]*/pallas_call)"',
                        lowered.as_text(debug_info=True))
    for name in MHC_KERNELS:
        booked = {anatomy.part_of(stack) for stack in stacks
                  if stack.endswith(f"/{name}/pallas_call")}
        assert {part for _, part in booked} == {"residual_mhc"}, booked
        if name.endswith("bwd"):
            assert {phase for phase, _ in booked} == {"backward"}, booked
        else:
            assert ("forward", "residual_mhc") in booked


@pytest.fixture(scope="module")
def recomputed(seeded):
    params, extra, batch = seeded
    model = tiny_model(remat=True)
    with jax.default_matmul_precision("highest"):
        return flatten(jax.grad(
            lambda p: program_loss(model, p, router_state(extra),
                                   batch["input_ids"])[0])(
            unflatten(params)))


@pytest.mark.parametrize("leaf", LEAVES)
def test_a_recomputed_blocks_gradient_is_the_kept_ones(both, recomputed,
                                                       leaf):
    """To rounding: a recomputed block is compiled apart from the first pass
    (the compiler fuses the mixes otherwise), and the leaves that are sums
    over every token of both signs feel it most."""
    want = both["grads"][leaf]
    np.testing.assert_allclose(
        np.asarray(recomputed[leaf]), np.asarray(want), rtol=0,
        atol=1e-5 * float(jnp.abs(want).max()))


def test_mixed_precision_stays_in_its_band(seeded, both):
    """bfloat16 streams, activations and products, float32 coefficients,
    router and parameters: logits within bf16's rounding of the reference,
    the loss within a thousandth; a token routed anew is held by the band
    too."""
    params, extra, batch = seeded
    model = models.get_model("xing4_tiny", dtype=jnp.bfloat16,
                             vocab_size=SZ["vocab"], attention_impl="flash")
    loss, (logits, _) = program_loss(model, unflatten(params),
                                     router_state(extra), batch["input_ids"])
    gap = jnp.abs(logits - both["want_logits"]).max(-1)   # by position
    print("mixed: median", float(jnp.median(gap)), "rerouted",
          float((gap > 0.05).mean()), "loss", float(loss))
    assert float(jnp.median(gap)) < 0.02
    assert float((gap > 0.05).mean()) < 0.2
    assert float(loss) == pytest.approx(float(both["want_loss"]), rel=1e-3)


@pytest.fixture(scope="module")
def mixed_grads(seeded):
    params, extra, batch = seeded
    model = models.get_model("xing4_tiny", dtype=jnp.bfloat16,
                             vocab_size=SZ["vocab"], attention_impl="flash")
    # compiled: op by op the interpreted kernels take three times as long
    return flatten(jax.jit(jax.grad(
        lambda p: program_loss(model, p, router_state(extra),
                               batch["input_ids"])[0]))(unflatten(params)))


@pytest.mark.parametrize("leaf", [l for l in LEAVES
                                  if l.endswith("phi/kernel")])
def test_bfloat16_streams_leave_phis_live_columns_alone(both, mixed_grads,
                                                        leaf):
    """Which of Phi's columns learn at the start, and what bfloat16 streams do
    to them. While the streams are still nearly copies of one another, only
    the `post` columns have a gradient: `H_pre X` scales the input of a
    sub-layer that begins with an RMSNorm, and a doubly stochastic `H_res`
    maps equal streams to themselves. Under the mixed policy the program's
    `post` columns stay within a hundredth of the float32 reference's in norm
    and within 0.995 of its direction (0.9976 to 0.99998 here, 0.997 to
    0.999 on the chip at the cell's size); the rounding of bfloat16 streams lands in the other
    twenty, whose reference gradient is a few hundredths of the live one at
    most (on the chip it lies under Adam's eps there, which is what the
    cell's `change_gap` reads: PERF.md section 2). A fault in the streams'
    backward pass would move the live columns."""
    n = SZ["streams"]
    got = np.asarray(mixed_grads[leaf], np.float64)
    want = np.asarray(both["want_grads"][leaf], np.float64)
    g, w = got[:, n:2 * n], want[:, n:2 * n]
    rest = np.delete(want, np.s_[n:2 * n], axis=1)
    cosine = (g * w).sum() / np.linalg.norm(g) / np.linalg.norm(w)
    print(leaf, "post norm", np.linalg.norm(g) / np.linalg.norm(w), "cos",
          cosine, "rest/post rms", np.sqrt((rest ** 2).mean() / (w ** 2).mean()))
    assert np.linalg.norm(g) == pytest.approx(np.linalg.norm(w), rel=0.01)
    assert cosine > 0.995
    assert np.sqrt((rest ** 2).mean()) < 0.05 * np.sqrt((w ** 2).mean())


# --------------------------------------------------------------------------
# the share test: 8 chips, one expert each, one block with experts
# --------------------------------------------------------------------------

SHARES = 8
BLOCK_CONFIG = dict(
    tiny_xing4.XING_TINY, num_hidden_layers=1, first_k_dense_replace=0,
    n_routed_experts=SHARES, num_experts_per_tok=4,
    share={"first_expert": 0, "router_width": SHARES})
BLOCK_SZ = ref.sizes(BLOCK_CONFIG)
STREAMS = BLOCK_SZ["streams"]


def program_block(p, x, bias, first, held):
    """The program's block holding experts first .. first+held-1 of 8, on
    streams (S, n, C); returns (S, n, C)."""
    cfg = xing4.Xing4Config(
        vocab_size=8, hidden_size=64, num_layers=1, num_heads=2,
        q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_original_max=32,
        intermediate_size=96, moe_intermediate_size=32, num_dense_layers=0,
        num_experts=SHARES, experts_held=(first, held), experts_per_token=4)
    block = xing4.Xing4Block(cfg, 0, jnp.float32)
    mine = {k: (v[first:first + held] if "/experts_" in k else v)
            for k, v in p.items()}
    out, _ = block.apply(
        {"params": unflatten(mine),
         moe.ROUTER_STATE: {"moe": {"bias": bias}}},
        x.reshape(1, len(x), -1), jnp.ones((1, len(x)), jnp.bool_),
        train=False, mutable=[moe.MOE_METRICS, mhc.MHC_METRICS])
    return out[0].reshape(x.shape)


@pytest.fixture(scope="module")
def one_block():
    key = jax.random.key(11)
    p = {k[len("layer0/"):]: (6.0 * v if "moe/" in k else v)
         for k, v in ref.init_params(BLOCK_SZ, key).items()
         if k.startswith("layer0/")}
    x = jax.random.normal(jax.random.fold_in(key, 99), (40, STREAMS, 64))
    bias = 0.05 * jax.random.normal(jax.random.fold_in(key, 98), (SHARES,))
    with jax.default_matmul_precision("highest"):
        whole, counts = ref.layer(BLOCK_SZ, 0, p, x, bias)
        # what every chip computes alike: the attention round, the FFN
        # round's residual mix and the shared expert written back through it
        nobody = dict(BLOCK_SZ, held=1, first_expert=0)
        no_expert = {k: (jnp.zeros_like(v[:1]) if "/experts_" in k else v)
                     for k, v in p.items()}
        alike, _ = ref.layer(nobody, 0, no_expert, x, bias)
    return p, x, bias, whole, alike, counts


def test_8_shares_add_up_to_the_uncut_block(one_block):
    """8 chips hold one expert each. What each share's block gives, with what
    every chip computes alike (the attention round, the streams' own mix and
    the shared expert) counted once, adds up to the reference's result for
    the block with all 8 experts: a hyper-connection writes the sub-layer's
    result back linearly, so the shares of y add up on every stream."""
    p, x, bias, whole, alike, counts = one_block
    assert float(counts.sum()) == x.shape[0] * 4
    total = alike
    with jax.default_matmul_precision("highest"):
        for chip in range(SHARES):
            total = total + (program_block(p, x, bias, chip, 1) - alike)
    routed = float(jnp.abs(whole - alike).max())
    assert routed > 0.1 * float(jnp.abs(whole).max())  # the experts matter
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=0,
                               atol=2e-5 * float(jnp.abs(whole).max()))


@pytest.mark.parametrize("first,held", [(0, 8), (2, 4), (6, 2)])
def test_a_share_matches_the_references_share(one_block, first, held):
    p, x, bias, _, _, _ = one_block
    sz = dict(BLOCK_SZ, held=held, first_expert=first)
    mine = {k: (v[first:first + held] if "/experts_" in k else v)
            for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.layer(sz, 0, mine, x, bias)
        got = program_block(p, x, bias, first, held)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


# --------------------------------------------------------------------------
# the published model and the share, by hand from the config
# --------------------------------------------------------------------------

def _by_hand(layers, dense_layers, experts, vocab):
    d, n = 3584, 4
    attention = (d * 768 + 768 + 768 * 32 * 192 + d * (512 + 64) + 512
                 + 512 * 32 * 256 + 32 * 128 * d)
    hyper = n * d + n * d * 24 + 24 + 3
    dense = 3 * d * 9216
    expert = 3 * d * 1024
    moe = d * 64 + experts * expert + expert
    return (layers * (attention + 2 * hyper + 2 * d) + dense_layers * dense
            + (layers - dense_layers) * moe + 2 * vocab * d + d)


@pytest.mark.parametrize("name,want", [
    ("xing4_29b", _by_hand(40, 2, 64, 131072)),
    ("xing4_ep8", _by_hand(5, 1, 8, 16384))])
def test_parameter_counts(name, want):
    model = models.get_model(name)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 64), jnp.int32), train=False))
    got = sum(math.prod(v.shape) for v in jax.tree_util.tree_leaves(
        flax.linen.unbox(shapes["params"])))
    assert got == want == models.model_spec(name).param_count
    if name == "xing4_29b":
        # "29B": 29.507B with all 64 experts, 40 layers, no MTP module
        assert 28.9e9 < got < 29.55e9
    else:
        assert got == 759_489_550 == ref.param_count(PUBLISHED_SZ)


def test_the_share_is_the_config_files():
    cfg = xing4.Xing4Config()
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.hc_mult) == (40, 2, 4)
    share = models.get_model("xing4_ep8").cfg
    assert (share.num_layers, share.num_dense_layers) == (5, 1)
    assert share.experts_held == (0, 8) and share.num_experts == 64
    assert share.remat and share.vocab_size == 16384
    assert share.hidden_size == 3584 and share.experts_per_token == 4


# --------------------------------------------------------------------------
# through train/loop.build
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained():
    from distributeddeeplearning_tpu.config import (
        DataConfig, OptimizerConfig, ParallelConfig, PrecisionPolicy,
        TrainConfig)
    from distributeddeeplearning_tpu.train import loop

    policy = PrecisionPolicy.mixed()
    cfg = TrainConfig(
        model="xing4_tiny", backend=None, global_batch_size=2, seed=0,
        dtype=policy.compute_dtype, precision=policy, log_every=10 ** 9,
        attention_impl="flash", parallel=ParallelConfig(data=1),
        data=DataConfig(synthetic=True, dataset="mlm", seq_len=SEQ,
                        vocab_size=SZ["vocab"]),
        optimizer=OptimizerConfig(
            name="adamw", learning_rate=3e-3, reference_batch=2,
            weight_decay=0.1, schedule="constant", warmup_epochs=0.0,
            beta1=0.9, beta2=0.95))
    _, _, _, state, train_step, _, rng = loop.build(cfg, 1000)
    ids = jax.random.randint(jax.random.key(1), (2, SEQ), 1, SZ["vocab"])
    batch = {"input_ids": ids, "attention_mask": jnp.ones_like(ids)}
    bias0 = jax.device_get(state.batch_stats)
    history = []
    for _ in range(4):
        state, metrics = train_step(state, batch, rng)
        history.append(jax.device_get(metrics))
    return bias0, jax.device_get(state.batch_stats), history, train_step


def test_the_trainer_carries_the_bias_and_logs_the_counters(trained):
    bias0, bias, history, _ = trained
    assert all(float(jnp.abs(b).max()) == 0
               for b in jax.tree_util.tree_leaves(bias0))
    for leaf in jax.tree_util.tree_leaves(bias):
        assert leaf.shape == (8,) and float(np.abs(leaf).max()) > 0
    for m in history:
        assert m["moe_dropped"] == 0.0
        assert 0 < m["moe_tokens_here"] <= 1 * 2 * SEQ * 2  # layers x T x k
        # four hyper-connections' matrices, one number: doubly stochastic to
        # what 20 iterations leave of a diagonal of 4
        assert 0 < m["mhc_max_row_sum_gap"] < 1e-3
        assert "kda_min_chunk_log_decay" not in m
    assert history[-1]["loss"] < history[0]["loss"]


def test_the_compiled_step_names_the_new_parts(trained):
    from distributeddeeplearning_tpu.analysis import anatomy

    table = trained[3].anatomy()
    parts = {anatomy.part_of(op_name) for op_name in table.values()}
    for part in ("residual_mhc", "attention_mla", "moe_routing",
                 "moe_experts", "attention_other", "mlp"):
        assert ("forward", part) in parts and ("backward", part) in parts
    # the Sinkhorn iterations are a loop, in the table as an operation that
    # spans its body, under the hyper-connections' scope
    assert any(op_name.startswith(anatomy.SPANS_ITS_BRANCH)
               and "/mhc/" in op_name for op_name in table.values())
    assert "residual_mhc" in anatomy.PARTS
    assert anatomy.part_of(
        "jit(step_fn)/grads/jvp(Xing4LM)/layer3/ffn_hc/mhc/dot_general") == \
        ("forward", "residual_mhc")
    assert anatomy.part_of(
        "jit(step_fn)/grads/transpose(jvp(Xing4LM))/layer3/mhc/mul") == \
        ("backward", "residual_mhc")
    # the sub-layers inside the rounds keep their own parts
    assert anatomy.part_of(
        "jit(step_fn)/grads/jvp(Xing4LM)/layer3/attention/attn_mla/"
        "flash_fwd/pallas_call") == ("forward", "attention_mla")
    assert anatomy.part_of(
        "jit(step_fn)/grads/jvp(Xing4LM)/layer3/attention/q_a_norm/mul") == \
        ("forward", "attention_other")
