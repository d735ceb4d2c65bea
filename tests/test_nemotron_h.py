"""The Nemotron-H decoder (models/nemotron_h.py: Mamba-2 mixers on the chunked
recurrence, relu2 routed experts, attention without positions) against its
plain reference (benchmark/references/nemotron_h.py: the recurrence token by
token) on seeded weights, at a small size on the CPU: names and shapes,
logits, loss, every gradient leaf, the selection bias after a step, the
mixers' counter, left padding; the share test (two shares of one expert
layer add up to the uncut reference layer) and RoutedExperts' relu2 option;
the published model's parameter count (tests/benchmark/test_nemotron_cell.py
runs the trainer)."""

import functools
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
from distributeddeeplearning_tpu.models import moe, nemotron_h  # noqa: E402
from distributeddeeplearning_tpu.ops import ssd  # noqa: E402

ref = harness.load_module("references", "nemotron_h")
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))
import tiny_nemotron  # noqa: E402

SZ = ref.sizes(tiny_nemotron.NEMOTRON_TINY)
BATCH, SEQ = 2, 40          # chunks of 16: two whole and a padded one
MOE_LAYERS = ("layer1",)
LEAVES = sorted(jax.eval_shape(lambda k: ref.init_params(SZ, k),
                               jax.random.key(0)))


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
    """The reference's weights with every bias, skip and scale that it
    starts at 0 or 1 drawn instead, so that each path carries a number."""
    key = jax.random.key(3)
    params = _drawn(key)
    batch = ref.make_batch({"batch": BATCH, "seq_len": SEQ}, SZ, key, 0)
    return params, ref.init_extra(SZ), batch


@jax.jit
def _drawn(key):
    """The seeded weights as one program: drawn op by op, each draw
    compiles."""
    params = ref.init_params(SZ, key)
    for n, name in enumerate(sorted(params)):
        if name.endswith(("/bias", "/D", "/scale")):
            params[name] = params[name] + 0.3 * jax.random.normal(
                jax.random.fold_in(key, 500 + n), params[name].shape)
    return params


def tiny_model(**kw):
    return models.get_model("nemotron3_nano_tiny", dtype=jnp.float32,
                            vocab_size=SZ["vocab"], attention_impl="flash",
                            **kw)


def program_loss(model, tree, state, ids, mask=None):
    logits, mutated = model.apply(
        {"params": tree, moe.ROUTER_STATE: state}, ids, mask, train=True,
        mutable=[moe.ROUTER_STATE, moe.MOE_METRICS, ssd.SSD_METRICS])
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
        want_logits = jax.jit(jax.vmap(
            lambda i: ref.forward(SZ, params, extra, i)))(ids)
        fn = ref.make_grad_fn(SZ, {"batch": BATCH, "seq_len": SEQ})
        want_loss, want_grads, want_extra = fn(params, extra, batch, None)
        step = jax.jit(jax.value_and_grad(
            lambda p, i, m: program_loss(model, p, router_state(extra), i, m),
            has_aux=True))
        (loss, (logits, mutated)), grads = step(unflatten(params), ids,
                                                jnp.ones_like(ids))
    return dict(want_logits=want_logits, want_loss=want_loss,
                want_grads=want_grads, want_extra=want_extra, loss=loss,
                logits=logits, grads=flatten(grads), mutated=mutated,
                step=step)


def test_names_and_shapes_are_the_references(seeded):
    params, _, _ = seeded
    shapes = jax.eval_shape(
        lambda: tiny_model().init({"params": jax.random.key(0)},
                                  jnp.zeros((1, 8), jnp.int32), train=False))
    theirs = {k: (v.shape, v.dtype) for k, v in flatten(
        flax.core.unfreeze(flax.linen.unbox(shapes["params"]))).items()}
    assert theirs == {k: (v.shape, v.dtype) for k, v in params.items()}
    assert set(flatten(flax.linen.unbox(shapes[moe.ROUTER_STATE]))) == \
        set(ref.init_extra(SZ))
    assert ref.param_count(SZ) == sum(v.size for v in params.values())


def test_logits_and_loss_match_the_reference(both):
    """float32 both sides; the chunked form and the recurrence, the kernels'
    online softmax and the plain one, part by rounding only."""
    np.testing.assert_allclose(np.asarray(both["logits"]),
                               np.asarray(both["want_logits"]),
                               rtol=0, atol=1e-5)
    assert float(both["loss"]) == pytest.approx(float(both["want_loss"]),
                                                rel=1e-6)


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_the_reference(both, leaf):
    """To 3e-5 of the leaf's largest entry."""
    got, want = both["grads"][leaf], both["want_grads"][leaf]
    scale = float(jnp.abs(want).max())
    assert scale > 0, leaf
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=3e-5 * scale)


@pytest.mark.parametrize("name", MOE_LAYERS)
def test_the_selection_bias_after_a_step_matches_the_reference(both, name):
    got = both["mutated"][moe.ROUTER_STATE][name]["moe"]["bias"]
    want = both["want_extra"][f"{name}/moe/bias"]
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-7)


def test_each_mixer_sows_its_least_chunk_decay(both, seeded):
    """One number a mixer: the least summed dt A over a chunk of 16, as the
    reference's own step sizes give it."""
    params, _, batch = seeded
    sown = both["mutated"][ssd.SSD_METRICS]
    assert set(sown) == {"layer0", "layer2"}
    p = ref._sub(params, "layer0/")
    inner, width = ref._widths(SZ)
    x = params["embed_tokens"][batch["input_ids"]]
    u = ref._rms(x, p["input_layernorm/scale"], SZ["eps"])
    dt = jax.nn.softplus(
        jnp.matmul(u, p["mixer/in_proj/kernel"],
                   precision=ref.HIGHEST)[..., inner + width:]
        + p["mixer/dt_bias"])
    want = ssd.min_chunk_log_decay(dt, -jnp.exp(p["mixer/A_log"]), 16)
    got = sown["layer0"]["mixer"]["min_chunk_log_decay"][0]
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert float(got) < 0


def test_padding_on_the_left_leaves_the_logits_as_they_were(seeded, both):
    """A padded token writes nothing into a mixer's state and decays
    nothing (dt = 0), puts nothing into its convolution, and is not
    attended: behind 8 padded tokens the first 32 tokens' logits are the
    unpadded run's (which no later token changes: the model is causal)."""
    params, extra, batch = seeded
    ids = batch["input_ids"]
    pad = 8
    padded = jnp.concatenate([jnp.ones((BATCH, pad), ids.dtype),
                              ids[:, :SEQ - pad]], 1)
    mask = jnp.concatenate([jnp.zeros((BATCH, pad), jnp.int32),
                            jnp.ones((BATCH, SEQ - pad), jnp.int32)], 1)
    with jax.default_matmul_precision("highest"):
        (_, (logits, _)), _ = both["step"](unflatten(params), padded, mask)
    np.testing.assert_allclose(np.asarray(logits[:, pad:]),
                               np.asarray(both["logits"][:, :SEQ - pad]),
                               rtol=0, atol=1e-5)


# --------------------------------------------------------------------------
# the share, and the relu2 experts
# --------------------------------------------------------------------------

SHARES = 8
LAYER_SZ = dict(SZ, pattern="E", held=SHARES, router=SHARES, first_expert=0)


@functools.partial(jax.jit, static_argnums=(3, 4))
def program_layer(p, x, bias, first, held):
    """The program's expert layer holding experts first .. first+held-1 of
    eight."""
    cfg = nemotron_h.NemotronHConfig(
        vocab_size=8, hidden_size=SZ["hidden"], pattern="E",
        moe_intermediate_size=SZ["expert_ffn"],
        shared_intermediate_size=SZ["shared_ffn"], num_experts=SHARES,
        experts_held=(first, held), experts_per_token=SZ["top_k"])
    block = nemotron_h.NemotronHBlock(cfg, 0, jnp.float32)
    mine = {k: (v[first:first + held] if "/experts_" in k else v)
            for k, v in p.items()}
    out, _ = block.apply(
        {"params": unflatten(mine),
         moe.ROUTER_STATE: {"moe": {"bias": bias}}},
        x[None], jnp.ones((1, len(x)), jnp.bool_), train=False,
        mutable=[moe.MOE_METRICS])
    return out[0]


@pytest.fixture(scope="module")
def one_layer():
    key = jax.random.key(11)
    p = {k[len("layer0/"):]: (6.0 * v if "moe/" in k else v)
         for k, v in jax.jit(lambda k: ref.init_params(LAYER_SZ, k))(
             key).items()
         if k.startswith("layer0/")}
    x = jax.random.normal(jax.random.fold_in(key, 99), (40, SZ["hidden"]))
    bias = 0.05 * jax.random.normal(jax.random.fold_in(key, 98), (SHARES,))
    with jax.default_matmul_precision("highest"):
        whole, counts = jax.jit(lambda *a: ref.layer(LAYER_SZ, 0, *a))(
            p, x, bias)
        # what every chip computes alike: the residual and the shared expert
        u = ref._rms(x, p["input_layernorm/scale"], SZ["eps"])
        alike = x + ref._relu2(
            lambda a, b: jnp.matmul(a, b, precision=ref.HIGHEST), u,
            p["moe/shared_up/kernel"], p["moe/shared_down/kernel"])
    return p, x, bias, whole, alike, counts


def test_two_shares_add_up_to_the_uncut_layer(one_layer):
    """Two chips hold four experts each. What each share's layer gives, with
    what every chip computes alike (the residual and the shared expert)
    counted once, adds up to the reference's result for the layer with all
    eight experts."""
    p, x, bias, whole, alike, counts = one_layer
    assert float(counts.sum()) == x.shape[0] * SZ["top_k"]
    total = alike
    with jax.default_matmul_precision("highest"):
        for first in range(0, SHARES, 4):
            total = total + (program_layer(p, x, bias, first, 4) - alike)
    routed = float(jnp.abs(whole - alike).max())
    assert routed > 0.1 * float(jnp.abs(whole).max())  # the experts matter
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=0,
                               atol=2e-5 * float(jnp.abs(whole).max()))


@pytest.mark.parametrize("first,held", [(0, 8), (6, 2)])
def test_a_share_matches_the_references_share(one_layer, first, held):
    p, x, bias, _, _, _ = one_layer
    sz = dict(LAYER_SZ, held=held, first_expert=first)
    mine = {k: (v[first:first + held] if "/experts_" in k else v)
            for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.layer(sz, 0, mine, x, bias)
        got = program_layer(p, x, bias, first, held)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("activation,leaves", [
    ("swiglu", {"router", "experts_gate", "experts_up", "experts_down",
                "shared_gate", "shared_up", "shared_down"}),
    ("relu2", {"router", "experts_up", "experts_down", "shared_up",
               "shared_down"})])
def test_routed_experts_hold_the_kernels_their_activation_needs(activation,
                                                                leaves):
    layer = moe.RoutedExperts(hidden_size=16, expert_width=8, num_experts=4,
                              experts_per_token=2, experts_held=(0, 4),
                              shared_width=12, activation=activation,
                              dtype=jnp.float32)
    shapes = jax.eval_shape(lambda: layer.init(
        jax.random.key(0), jnp.zeros((1, 5, 16)), train=False))
    assert set(shapes["params"]) == leaves


def test_an_unknown_expert_activation_is_refused():
    layer = moe.RoutedExperts(hidden_size=16, expert_width=8, num_experts=4,
                              experts_per_token=2, experts_held=(0, 4),
                              activation="gelu", dtype=jnp.float32)
    with pytest.raises(ValueError, match="unknown activation"):
        layer.init(jax.random.key(0), jnp.zeros((1, 5, 16)), train=False)


# --------------------------------------------------------------------------
# the published model and the share, by hand from the config
# --------------------------------------------------------------------------

def _by_hand(mixers, experts_layers, attention, experts, vocab):
    d, inner, width, h = 2688, 4096, 4096 + 2048, 64
    mamba = (d * (inner + width + h) + 4 * width + width + 3 * h + inner
             + inner * d)
    expert_layer = d * 128 + experts * 2 * d * 1856 + 2 * d * 3712
    gqa = 2 * d * 4096 + 2 * d * 256
    layers = mixers + experts_layers + attention
    return (mixers * mamba + experts_layers * expert_layer + attention * gqa
            + layers * d + d + 2 * vocab * d)


@pytest.mark.parametrize("name,want", [
    ("nemotron3_nano_30b", _by_hand(23, 23, 6, 128, 131072)),
    ("nemotron3_nano_ep16", _by_hand(4, 4, 1, 8, 16384))])
def test_parameter_counts(name, want):
    model = models.get_model(name)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 64), jnp.int32), train=False))
    got = sum(math.prod(v.shape) for v in jax.tree_util.tree_leaves(
        flax.linen.unbox(shapes["params"])))
    assert got == want == models.model_spec(name).param_count
    if name == "nemotron3_nano_ep16":
        assert got == 666_962_944   # 667M


def test_the_published_layers_are_the_config_files():
    cfg = nemotron_h.NemotronHConfig()
    pattern = cfg.pattern
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == \
        (23, 23, 6) and cfg.num_layers == 52
    share = models.get_model("nemotron3_nano_ep16").cfg
    assert share.pattern == pattern[:9] == "MEMEM*EME"
    assert share.experts_held == (0, 8) and share.num_experts == 128
    assert share.remat and share.vocab_size == 16384
