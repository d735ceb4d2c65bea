"""The Kimi Linear decoder (models/kimi_linear.py: KDA and latent attention
layers, models/moe.py::RoutedExperts as it stands) against its plain
reference (benchmark/references/kimi_linear.py: the delta rule token by
token) on seeded weights, at a small size on the CPU: names and shapes,
logits, loss, every gradient leaf, the selection bias after a step; what a
recomputed block keeps; the share test (32 shares of one block add up to the
uncut reference block); the published model's parameter count; the trainer's
three steps and counters."""

import math
import os
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
from distributeddeeplearning_tpu.models import kimi_linear, moe  # noqa: E402
from distributeddeeplearning_tpu.ops import kda  # noqa: E402

ref = harness.load_module("references", "kimi_linear")
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))
import tiny_kimi  # noqa: E402
from tests.attention_refs import flash_kernel_calls  # noqa: E402

SZ = ref.sizes(tiny_kimi.KIMI_TINY)
BATCH, SEQ = 2, 80          # a chunk of 64 and a short one: the state is handed on
MOE_LAYERS = ("layer1", "layer2", "layer3")
KDA_LAYERS = ("layer0", "layer1", "layer3")
LEAVES = sorted(ref.init_params(SZ, jax.random.key(0)))


# the leaves that the gates' cotangent alone reaches
GATE_LEAVES = ("/dt_bias", "/A_log", "/f_a_proj/kernel", "/f_b_proj/kernel")


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
    return models.get_model("kimi_linear_tiny", dtype=jnp.float32,
                            vocab_size=SZ["vocab"], attention_impl="flash",
                            **kw)


def program_loss(model, tree, state, ids, mask=None):
    logits, mutated = model.apply(
        {"params": tree, moe.ROUTER_STATE: state}, ids, mask, train=True,
        mutable=[moe.ROUTER_STATE, moe.MOE_METRICS, kda.KDA_METRICS])
    logp = jax.nn.log_softmax(logits[:, :-1])
    loss = -jnp.take_along_axis(logp, ids[:, 1:, None], -1).mean()
    return loss, (logits, mutated)


@pytest.fixture(scope="module")
def both(seeded):
    """Reference (the recurrence token by token) and program (the chunked
    form, flash kernels interpreted; float32) on the same weights and batch:
    losses, logits, gradients, biases."""
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


def test_logits_and_loss_match_the_reference(both):
    # float32 both sides; the chunked form and the recurrence, the kernels'
    # online softmax and the plain one, part by rounding only: 2.9e-6 with
    # the chunked form's three bfloat16 passes (2.4e-7 on PR 34's tree,
    # whose products a CPU ran whole; the pin was 5e-6)
    np.testing.assert_allclose(np.asarray(both["logits"]),
                               np.asarray(both["want_logits"]),
                               rtol=0, atol=1e-5)
    assert float(both["loss"]) == pytest.approx(float(both["want_loss"]),
                                                rel=1e-6)


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_the_reference(both, leaf):
    """Of its largest entry: 3e-5, and 1.2e-4 for the leaves that the gates'
    cotangent alone reaches. The chunked operator's float32 products are
    three bfloat16 passes on a CPU too since PR 35 (ops/kda_chunk.py writes
    them out): the worst leaf of either kind reads 1.1e-5 and 5.2e-5 (PR
    34's tree, whole float32 on a CPU: 8.4e-7 and 1.8e-6, under the 2e-5
    this pin was)."""
    got, want = both["grads"][leaf], both["want_grads"][leaf]
    scale = float(jnp.abs(want).max())
    assert scale > 0, "a leaf without a gradient is a part that never ran"
    limit = 1.2e-4 if leaf.endswith(GATE_LEAVES) else 3e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=limit * scale)


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


def test_the_first_kda_layer_sows_its_lowest_chunk_gate(both, seeded):
    """The most negative cumulative gate inside a chunk of 64, by hand from
    the reference's own pieces for layer 0, whose input is the embedding."""
    params, _, batch = seeded
    p = ref._sub(params, "layer0/")
    a = ref._sub(p, "attention/")
    lows = []
    with jax.default_matmul_precision("highest"):
        for ids in batch["input_ids"]:
            u = ref._rms(params["embed_tokens"][ids],
                         p["input_layernorm/scale"], SZ["eps"])
            f = u @ a["f_a_proj/kernel"] @ a["f_b_proj/kernel"] + a["dt_bias"]
            g = (-jnp.exp(a["A_log"])[None, :, None]
                 * jax.nn.softplus(f).reshape(SEQ, 2, 16))
            lows += [g[:kda.CHUNK].sum(0).min(), g[kda.CHUNK:].sum(0).min()]
    sown = both["mutated"][kda.KDA_METRICS]["layer0"]["attention"]
    assert float(sown["min_chunk_log_decay"][0]) == pytest.approx(
        float(min(lows)), rel=1e-5)
    for name in KDA_LAYERS:
        sown = both["mutated"][kda.KDA_METRICS][name]["attention"]
        assert float(sown["min_chunk_log_decay"][0]) < 0.0


def padded(batch, left, right):
    ids = jnp.pad(batch["input_ids"], ((0, 0), (left, right)),
                  constant_values=7)
    return ids, jnp.pad(jnp.ones((BATCH, SEQ), jnp.int32),
                        ((0, 0), (left, right)))


def test_padding_on_the_left_leaves_the_logits_as_they_were(seeded, both):
    """A padded token decays no state, writes none and feeds no
    convolution, and latent attention masks it: the real tokens of a row
    padded on the left read what the row alone reads."""
    params, extra, batch = seeded
    with jax.default_matmul_precision("highest"):
        _, (logits, _) = program_loss(tiny_model(), unflatten(params),
                                      router_state(extra),
                                      *padded(batch, 5, 0))
    np.testing.assert_allclose(np.asarray(logits[:, 5:]),
                               np.asarray(both["logits"]), rtol=0, atol=5e-6)


def test_a_padded_tail_leaves_each_kda_layers_last_state(seeded, monkeypatch):
    """What a decode step would start from: the state after a row padded at
    the end is the state after the row alone, in every KDA layer."""
    params, extra, batch = seeded
    states, groups = [], kda.kda_groups

    def keep_state(*args):
        out, state = groups(*args, return_state=True)
        states.append(state)
        return out

    monkeypatch.setattr(kda, "kda_groups", keep_state)
    with jax.default_matmul_precision("highest"):
        for ids, mask in (padded(batch, 0, 0), padded(batch, 0, 7)):
            program_loss(tiny_model(), unflatten(params),
                         router_state(extra), ids, mask)
    assert len(states) == 2 * len(KDA_LAYERS)
    for alone, with_tail in zip(states[:3], states[3:]):
        assert float(jnp.abs(alone).max()) > 0
        np.testing.assert_allclose(np.asarray(with_tail), np.asarray(alone),
                                   rtol=0, atol=1e-6)


# A recomputed block keeps the routed experts' result and the flash kernel's
# (models/kimi_linear.py): the forward kernel stands once in the latent layer
# of the gradient's program, not twice, and the gradients are those of the
# blocks kept whole.

@pytest.mark.parametrize("remat", [False, True], ids=["kept", "recomputed"])
def test_each_flash_kernel_stands_once_a_latent_layer(seeded, remat):
    params, extra, batch = seeded
    model = tiny_model(remat=remat)
    latent = sum(k == kimi_linear.MLA for k in model.cfg.layer_kinds)
    assert latent == 1
    assert flash_kernel_calls(
        jax.grad(lambda p: program_loss(model, p, router_state(extra),
                                        batch["input_ids"])[0]),
        unflatten(params)) == {"flash_fwd": latent, "flash_dq": 0,
                               "flash_dkv": latent}  # one backward kernel


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
    """To rounding. The block keeps the operator's result and entering
    states, so its recomputed forward runs no forward kernel of the
    recurrence, and the backward kernel gives the same bits for the same
    inputs; but the two blocks hand it inputs a last bit apart (XLA computes
    the recomputed projections and the output stage's cotangent in programs
    of their own: the last KDA layer's q, k, g up to 2.4e-7 apart, every
    layer's incoming cotangent up to 9.3e-7, seed 3), and the operator's
    float32 products are three bfloat16 passes on a CPU too
    (ops/kda_chunk.py writes them out), where such a difference comes out
    as a pass's rounding. The leaves that the gates' cotangent alone reaches
    are sums of it over every token with both signs: over seeds 0 to 5 they
    read 8.3e-6 to 2.2e-5 of their largest entry (4.5e-6 to 2.8e-5 when XLA
    ran the loop over chunks) and every other leaf 4.0e-6 to 6.7e-6 (3.9e-6
    to 6.1e-6); when a CPU ran those products whole they read under the
    5e-6 and 2e-6 these pins were."""
    want = both["grads"][leaf]
    limit = 6e-5 if leaf.endswith(GATE_LEAVES) else 1.5e-5
    np.testing.assert_allclose(
        np.asarray(recomputed[leaf]), np.asarray(want), rtol=0,
        atol=limit * float(jnp.abs(want).max()))


def test_mixed_precision_stays_in_its_band(seeded, both):
    """bfloat16 activations and products, float32 state, gates, router and
    parameters: logits within bf16's rounding of the reference, the loss
    within a thousandth; a token routed anew is held by the band too."""
    params, extra, batch = seeded
    model = models.get_model("kimi_linear_tiny", dtype=jnp.bfloat16,
                             vocab_size=SZ["vocab"], attention_impl="flash")
    loss, (logits, _) = program_loss(model, unflatten(params),
                                     router_state(extra), batch["input_ids"])
    gap = jnp.abs(logits - both["want_logits"]).max(-1)   # by position
    print("mixed: median", float(jnp.median(gap)), "rerouted",
          float((gap > 0.05).mean()), "loss", float(loss))
    assert float(jnp.median(gap)) < 0.02
    assert float((gap > 0.05).mean()) < 0.2
    assert float(loss) == pytest.approx(float(both["want_loss"]), rel=1e-3)


# --------------------------------------------------------------------------
# the share test: 32 chips, one expert each, one KDA block with experts
# --------------------------------------------------------------------------

SHARES = 32
BLOCK_CONFIG = dict(
    tiny_kimi.KIMI_TINY, num_hidden_layers=1, first_k_dense_replace=0,
    linear_attn_config=dict(tiny_kimi.KIMI_TINY["linear_attn_config"],
                            kda_layers=[1], full_attn_layers=[]),
    num_experts=SHARES, num_experts_per_token=4,
    share={"first_expert": 0, "router_width": SHARES})
BLOCK_SZ = ref.sizes(BLOCK_CONFIG)


def program_block(p, x, bias, first, held):
    """The program's block holding experts first .. first+held-1 of 32."""
    cfg = kimi_linear.KimiLinearConfig(
        vocab_size=8, hidden_size=64, layer_kinds=(kimi_linear.KDA,),
        kda_heads=2, kda_head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, num_dense_layers=0, num_experts=SHARES,
        experts_held=(first, held), experts_per_token=4)
    block = kimi_linear.KimiLinearBlock(cfg, 0, jnp.float32)
    mine = {k: (v[first:first + held] if "/experts_" in k else v)
            for k, v in p.items()}
    out, _ = block.apply(
        {"params": unflatten(mine),
         moe.ROUTER_STATE: {"moe": {"bias": bias}}},
        x[None], jnp.ones((1, len(x)), jnp.bool_), train=False,
        mutable=[moe.MOE_METRICS])
    return out[0]


@pytest.fixture(scope="module")
def one_block():
    key = jax.random.key(11)
    p = {k[len("layer0/"):]: (6.0 * v if "moe/" in k else v)
         for k, v in ref.init_params(BLOCK_SZ, key).items()
         if k.startswith("layer0/")}
    x = jax.random.normal(jax.random.fold_in(key, 99), (40, 64))
    bias = 0.05 * jax.random.normal(jax.random.fold_in(key, 98), (SHARES,))
    with jax.default_matmul_precision("highest"):
        whole, counts = ref.layer(BLOCK_SZ, 0, p, x, bias)
        # what every chip computes alike: the attention half and the shared
        # expert
        h = x + ref.kda_attention(
            BLOCK_SZ, ref._sub(p, "attention/"),
            ref._rms(x, p["input_layernorm/scale"], BLOCK_SZ["eps"]))
        u = ref._rms(h, p["post_attention_layernorm/scale"], BLOCK_SZ["eps"])
        alike = h + ref._swiglu(
            lambda a, b: jnp.matmul(a, b, precision=ref.HIGHEST), u,
            p["moe/shared_gate/kernel"], p["moe/shared_up/kernel"],
            p["moe/shared_down/kernel"])
    return p, x, bias, whole, alike, counts


def test_32_shares_add_up_to_the_uncut_block(one_block):
    """32 chips hold one expert each. What each share's block gives, with
    what every chip computes alike (the KDA half, the residual and the shared
    expert) counted once, adds up to the reference's result for the block
    with all 32 experts."""
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


@pytest.mark.parametrize("first,held", [(0, 32), (8, 8), (30, 2)])
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

def _by_hand(layers_kda, layers_mla, experts, vocab):
    d, kd, r, h = 2304, 4096, 128, 32
    kda = (4 * d * kd + 3 * 4 * kd + 2 * (d * r + r * kd) + kd  # + b_g
           + d * h + h + kd + 128)            # beta, A_log, dt_bias, o_norm
    mla = d * h * 192 + d * (512 + 64) + 512 + 512 * h * 256 + h * 128 * d
    dense = 3 * d * 9216
    expert = 3 * d * 1024
    moe = d * 256 + experts * expert + expert
    layers = layers_kda + layers_mla
    return (layers_kda * kda + layers_mla * mla + layers * 2 * d + d + dense
            + (layers - 1) * moe + 2 * vocab * d)


@pytest.mark.parametrize("name,want", [
    ("kimi_linear_48b", _by_hand(20, 7, 256, 163840)),
    ("kimi_linear_ep32", _by_hand(4, 1, 8, 20480))])
def test_parameter_counts(name, want):
    model = models.get_model(name)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 64), jnp.int32), train=False))
    got = sum(math.prod(v.shape) for v in jax.tree_util.tree_leaves(
        flax.linen.unbox(shapes["params"])))
    assert got == want
    if name == "kimi_linear_48b":
        assert 48.0e9 < got < 49.5e9          # "48B"
    else:
        assert got == 602_449_792             # ISSUE 31's 602.5M


def test_the_published_layers_are_the_config_files():
    cfg = kimi_linear.KimiLinearConfig()
    full = [i + 1 for i, k in enumerate(cfg.layer_kinds)
            if k == kimi_linear.MLA]
    assert full == [4, 8, 12, 16, 20, 24, 27] and cfg.num_layers == 27
    share = models.get_model("kimi_linear_ep32").cfg
    assert share.layer_kinds == ("kda", "kda", "kda", "mla", "kda")
    assert share.experts_held == (0, 8) and share.num_experts == 256
    assert share.remat and share.vocab_size == 20480


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
        model="kimi_linear_tiny", backend=None, global_batch_size=2, seed=0,
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
        assert 0 < m["moe_tokens_here"] <= 3 * 2 * SEQ * 2  # layers x T x k
        # three KDA layers' lowest in-chunk cumulative gate, one number
        assert -kda.CHUNK * 16 * 5.0 < m["kda_min_chunk_log_decay"] < 0.0
    assert history[-1]["loss"] < history[0]["loss"]


def test_the_compiled_step_names_the_new_parts(trained):
    from distributeddeeplearning_tpu.analysis import anatomy

    table = trained[3].anatomy()
    parts = {anatomy.part_of(op_name) for op_name in table.values()}
    for part in ("attention_kda", "attention_mla", "moe_routing",
                 "moe_experts", "attention_other", "mlp"):
        assert ("forward", part) in parts and ("backward", part) in parts
    # the chunked scan's loops are in the table as operations that span
    # their bodies
    assert any(op_name.startswith(anatomy.SPANS_ITS_BRANCH)
               and "attn_kda" in op_name for op_name in table.values())
