"""The AFMoE decoder (models/afmoe.py, models/moe.py::RoutedExperts) against
its plain reference (benchmark/references/afmoe.py) on seeded weights, at a
small size on the CPU: logits, loss, every gradient leaf and the selection
bias after a step; the share test (eight shares of one expert layer add up to
the uncut reference layer); no token dropped under a router forced onto the
held experts; the published model's parameter count; the trainer's counters.
"""

import json
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
from distributeddeeplearning_tpu.models import afmoe, moe  # noqa: E402

ref = harness.load_module("references", "afmoe")
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))
import tiny_afmoe  # noqa: E402
from tests.attention_refs import flash_kernel_calls  # noqa: E402

SZ = ref.sizes(tiny_afmoe.AFMOE_TINY)
BATCH, SEQ = 2, 64
MOE_LAYERS = ("layer1", "layer2")
LEAVES = sorted(ref.init_params(SZ, jax.random.key(0)))


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


def program_loss(model, tree, state, ids):
    logits, mutated = model.apply(
        {"params": tree, moe.ROUTER_STATE: state}, ids, train=True,
        mutable=[moe.ROUTER_STATE, moe.MOE_METRICS])
    logp = jax.nn.log_softmax(logits[:, :-1])
    loss = -jnp.take_along_axis(logp, ids[:, 1:, None], -1).mean()
    return loss, (logits, mutated)


@pytest.fixture(scope="module")
def both(seeded):
    """Reference and program (float32, flash kernels interpreted) on the
    same weights and batch: losses, logits, gradients, biases."""
    params, extra, batch = seeded
    ids = batch["input_ids"]
    model = models.get_model("afmoe_tiny", dtype=jnp.float32,
                             vocab_size=SZ["vocab"], attention_impl="flash")
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
    model = models.get_model("afmoe_tiny", dtype=jnp.float32,
                             vocab_size=SZ["vocab"])
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False))
    theirs = {k: v.shape for k, v in flatten(
        flax.core.unfreeze(flax.linen.unbox(shapes["params"]))).items()}
    assert theirs == {k: v.shape for k, v in params.items()}
    assert set(flatten(flax.linen.unbox(shapes[moe.ROUTER_STATE]))) == \
        set(ref.init_extra(SZ))


def test_logits_and_loss_match_the_reference(both):
    # float32 both sides; the kernels' online softmax and the reference's
    # plain one part by rounding only
    np.testing.assert_allclose(np.asarray(both["logits"]),
                               np.asarray(both["want_logits"]),
                               rtol=0, atol=5e-6)
    assert float(both["loss"]) == pytest.approx(float(both["want_loss"]),
                                                rel=1e-6)


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_the_reference(both, leaf):
    got, want = both["grads"][leaf], both["want_grads"][leaf]
    scale = float(jnp.abs(want).max())
    assert scale > 0, "a leaf without a gradient is a part that never ran"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-5 * scale)


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


# A recomputed block keeps the routed experts' result and the flash kernel's
# (models/afmoe.py): the forward kernel stands once a layer in the gradient's
# program, not twice, and the gradients are those of the blocks kept whole.

def _flash_model(remat):
    return models.get_model("afmoe_tiny", dtype=jnp.float32,
                            vocab_size=SZ["vocab"], attention_impl="flash",
                            remat=remat)


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "recomputed"])
def test_each_flash_kernel_stands_once_a_layer(seeded, remat):
    params, extra, batch = seeded
    model = _flash_model(remat)
    layers = model.cfg.num_layers
    assert flash_kernel_calls(
        jax.grad(lambda p: program_loss(model, p, router_state(extra),
                                        batch["input_ids"])[0]),
        unflatten(params)) == {"flash_fwd": layers, "flash_dq": 0,
                               "flash_dkv": layers}  # one backward kernel


@pytest.fixture(scope="module")
def recomputed(seeded):
    """`both`'s program with every block recomputed: its gradients."""
    params, extra, batch = seeded
    model = _flash_model(True)
    with jax.default_matmul_precision("highest"):
        return flatten(jax.grad(
            lambda p: program_loss(model, p, router_state(extra),
                                   batch["input_ids"])[0])(
            unflatten(params)))


@pytest.mark.parametrize("leaf", LEAVES)
def test_a_recomputed_blocks_gradient_is_the_kept_ones_bit_for_bit(
        both, recomputed, leaf):
    np.testing.assert_array_equal(np.asarray(recomputed[leaf]),
                                  np.asarray(both["grads"][leaf]))


def test_mixed_precision_stays_in_its_band(seeded, both):
    """bfloat16 activations and matrix products, float32 router and
    parameters: logits within bf16's rounding of the reference (2^-8
    relative a product, a few products deep; the widest logit is 0.7), and
    the loss within a thousandth. A token whose 2nd and 3rd scores are closer
    than that rounding is routed differently, which the band has to hold
    too."""
    params, extra, batch = seeded
    model = models.get_model("afmoe_tiny", dtype=jnp.bfloat16,
                             vocab_size=SZ["vocab"], attention_impl="flash")
    loss, (logits, _) = program_loss(model, unflatten(params),
                                     router_state(extra), batch["input_ids"])
    gap = jnp.abs(logits - both["want_logits"]).max(-1)   # by position
    print("mixed: median", float(jnp.median(gap)), "rerouted",
          float((gap > 0.05).mean()), "loss", float(loss))
    assert float(jnp.median(gap)) < 0.02
    assert float((gap > 0.05).mean()) < 0.2   # the positions routed anew
    assert float(loss) == pytest.approx(float(both["want_loss"]), rel=1e-3)


# --------------------------------------------------------------------------
# one expert layer: shares, and no dropped token
# --------------------------------------------------------------------------

E_ALL, WIDTH, HID, TOP = 16, 32, 64, 4
LAYER_SZ = dict(SZ, router=E_ALL, held=E_ALL, first_expert=0, top_k=TOP,
                hidden=HID, expert_ffn=WIDTH, shared=1)


def layer_params(key):
    shapes = {"router/kernel": (HID, E_ALL),
              "experts_gate/kernel": (E_ALL, HID, WIDTH),
              "experts_up/kernel": (E_ALL, HID, WIDTH),
              "experts_down/kernel": (E_ALL, WIDTH, HID),
              "shared_gate/kernel": (HID, WIDTH),
              "shared_up/kernel": (HID, WIDTH),
              "shared_down/kernel": (WIDTH, HID)}
    return {name: 0.3 * jax.random.normal(jax.random.fold_in(key, n), shape)
            for n, (name, shape) in enumerate(sorted(shapes.items()))}


def program_layer(p, u, bias, first, held):
    """RoutedExperts holding experts first .. first+held-1 of the 16."""
    layer = moe.RoutedExperts(
        hidden_size=HID, expert_width=WIDTH, num_experts=E_ALL,
        experts_per_token=TOP, experts_held=(first, held),
        route_scale=SZ["route_scale"], shared_width=WIDTH,
        dtype=jnp.float32)
    mine = {k: (v[first:first + held] if k.startswith("experts_") else v)
            for k, v in p.items()}
    out, mutated = layer.apply(
        {"params": unflatten(mine), moe.ROUTER_STATE: {"bias": bias}},
        u[None], train=False, mutable=[moe.MOE_METRICS])
    return out[0], mutated[moe.MOE_METRICS]


@pytest.fixture(scope="module")
def one_layer():
    key = jax.random.key(11)
    p = layer_params(key)
    u = jax.random.normal(jax.random.fold_in(key, 99), (96, HID))
    bias = 0.05 * jax.random.normal(jax.random.fold_in(key, 98), (E_ALL,))
    with jax.default_matmul_precision("highest"):
        whole, counts = ref.expert_ffn(LAYER_SZ, p, u, bias)
        shared = ref._swiglu(
            lambda a, b: jnp.matmul(a, b, precision=ref.HIGHEST), u,
            p["shared_gate/kernel"], p["shared_up/kernel"],
            p["shared_down/kernel"])
    return p, u, bias, whole, shared, counts


def test_eight_shares_add_up_to_the_uncut_layer(one_layer):
    """Eight chips hold two experts each. What each share's layer gives,
    with the shared expert (which every chip computes alike) counted once,
    adds up to the reference's result for the whole layer."""
    p, u, bias, whole, shared, _ = one_layer
    total, landed = shared, 0.0
    with jax.default_matmul_precision("highest"):
        for chip in range(8):
            out, sown = program_layer(p, u, bias, 2 * chip, 2)
            total = total + (out - shared)
            landed += float(sown["tokens_here"][0])
            assert float(sown["dropped"][0]) == 0.0
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=0, atol=2e-5 * float(jnp.abs(whole).max()))
    assert landed == u.shape[0] * TOP  # every assignment landed on one chip


def test_the_references_own_shares_add_up(one_layer):
    p, u, bias, whole, shared, counts = one_layer
    total = shared
    with jax.default_matmul_precision("highest"):
        for chip in range(8):
            sz = dict(LAYER_SZ, held=2, first_expert=2 * chip)
            mine = {k: (v[2 * chip:2 * chip + 2] if k.startswith("experts_")
                        else v) for k, v in p.items()}
            out, c = ref.expert_ffn(sz, mine, u, bias)
            np.testing.assert_array_equal(np.asarray(c), np.asarray(counts))
            total = total + (out - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=0, atol=2e-5 * float(jnp.abs(whole).max()))


@pytest.mark.parametrize("first,held", [(0, 16), (4, 4), (14, 2), (3, 1)])
def test_a_share_matches_the_references_share(one_layer, first, held):
    p, u, bias, _, _, _ = one_layer
    sz = dict(LAYER_SZ, held=held, first_expert=first)
    mine = {k: (v[first:first + held] if k.startswith("experts_") else v)
            for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_ffn(sz, mine, u, bias)
        got, _ = program_layer(p, u, bias, first, held)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_no_token_is_dropped_when_every_token_picks_the_held_experts(
        one_layer):
    """A bias that forces every token onto experts 4-7, all held here: the
    row buffer is full to its last row (T * k assignments land), nothing is
    dropped, and the result is still the reference's."""
    p, u, _, _, _, _ = one_layer
    bias = jnp.zeros((E_ALL,)).at[4:8].set(10.0)
    sz = dict(LAYER_SZ, held=4, first_expert=4)
    mine = {k: (v[4:8] if k.startswith("experts_") else v)
            for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        want, counts = ref.expert_ffn(sz, mine, u, bias)
        got, sown = program_layer(p, u, bias, 4, 4)
    assert float(sown["tokens_here"][0]) == u.shape[0] * TOP
    assert float(sown["dropped"][0]) == 0.0
    assert float(sown["max_expert_share"][0]) == pytest.approx(1 / TOP)
    assert np.asarray(counts)[4:8].tolist() == [u.shape[0]] * 4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("favoured,first,held,lands", [
    ((4, 8), 4, 4, 96 * TOP),      # every assignment lands here
    ((8, 12), 4, 4, 0),            # none does: the shared expert alone
    ((2, 6), 4, 4, 96 * TOP // 2)])
def test_the_products_cover_the_landed_rows_and_no_more(
        one_layer, monkeypatch, favoured, first, held, lands):
    """What the three grouped products are handed as group sizes adds up to
    the rows that landed on the held experts: no row is added to them for
    any other purpose, so a step's work is the routing's."""
    p, u, _, _, shared, _ = one_layer
    bias = jnp.zeros((E_ALL,)).at[favoured[0]:favoured[1]].set(10.0)
    seen = []
    real = jax.lax.ragged_dot

    def spy(x, w, group_sizes, **kw):
        seen.append(int(group_sizes.sum()))
        return real(x, w, group_sizes, **kw)

    monkeypatch.setattr(jax.lax, "ragged_dot", spy)
    with jax.default_matmul_precision("highest"), jax.disable_jit():
        got, sown = program_layer(p, u, bias, first, held)
    assert seen == [lands] * 3
    assert float(sown["tokens_here"][0]) == lands
    if lands == 0:
        np.testing.assert_allclose(np.asarray(got), np.asarray(shared),
                                   rtol=0, atol=1e-6)


# Two of the 16 experts held, four a token: 48 of the 96 x 4 assignments are
# expected here, so the row buffer is 96 rows where no more than that landed
# and the worst case's 192 otherwise (models/moe.py::_sized_to_what_lands).
TWO_HELD = 2   # experts 0 and 1
DIFFERENTIATED = ("router/kernel", "experts_gate/kernel",
                  "experts_up/kernel", "experts_down/kernel")


def _two_body_case(one_layer, routing):
    p, u, bias, _, _, _ = one_layer
    if routing == "onto_the_held":  # every token's first two choices
        bias = jnp.zeros((E_ALL,)).at[:TWO_HELD].set(10.0)
    cot = jax.random.normal(jax.random.key(5), u.shape)
    mine = {k: (v[:TWO_HELD] if k.startswith("experts_") else v)
            for k, v in p.items()}
    return mine, u, bias, cot


def _program_value_and_grads(mine, u, bias, cot):
    """(result, sown counters), gradients by the tokens and the kernels;
    ``mine`` holds the two held experts' kernels, which program_layer's own
    cut to experts 0 and 1 leaves as they are."""
    def loss(diff, u):
        out, sown = program_layer({**mine, **diff}, u, bias, 0, TWO_HELD)
        return jnp.sum(out * cot), (out, sown)

    (_, aux), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
        {k: mine[k] for k in DIFFERENTIATED}, u)
    return aux, grads


@pytest.fixture(scope="module", params=["fits", "onto_the_held"])
def two_bodies(request, one_layer):
    """The layer with a small and a worst-case body, under routing that fits
    the small one and under routing that does not, beside the reference."""
    mine, u, bias, cot = _two_body_case(one_layer, request.param)
    sz = dict(LAYER_SZ, held=TWO_HELD, first_expert=0)
    with jax.default_matmul_precision("highest"):
        (out, sown), grads = _program_value_and_grads(mine, u, bias, cot)
        want_out = ref.expert_ffn(sz, mine, u, bias)[0]
        want_grads = jax.grad(
            lambda diff, u: jnp.sum(
                ref.expert_ffn(sz, {**mine, **diff}, u, bias)[0] * cot),
            argnums=(0, 1))({k: mine[k] for k in DIFFERENTIATED}, u)
    return dict(routing=request.param, out=out, sown=sown, grads=grads,
                want_out=want_out, want_grads=want_grads)


def test_two_bodies_result_and_counters(two_bodies):
    fits = two_bodies["routing"] == "fits"
    sown = two_bodies["sown"]
    landed = float(sown["tokens_here"][0])
    assert (0 < landed <= 96) if fits else landed == 192
    assert float(sown["dropped"][0]) == 0.0
    assert float(sown["worst_case"][0]) == (0.0 if fits else 1.0)
    want = two_bodies["want_out"]
    np.testing.assert_allclose(np.asarray(two_bodies["out"]),
                               np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("leaf", ("tokens",) + DIFFERENTIATED)
def test_two_bodies_gradients_match_the_reference(two_bodies, leaf):
    pick = (lambda g: g[1]) if leaf == "tokens" else (lambda g: g[0][leaf])
    got, want = pick(two_bodies["grads"]), pick(two_bodies["want_grads"])
    scale = float(jnp.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-5 * scale)


def test_the_small_body_and_the_worst_case_body_agree(one_layer, monkeypatch):
    """The same input, whose rows fit the small body, through the small body
    (the ``cond`` picks it) and through the worst-case body alone (a factor
    that leaves no smaller one): the same groups go through the same
    products, so result and gradients are the same numbers."""
    case = _two_body_case(one_layer, "fits")
    with jax.default_matmul_precision("highest"):
        (small, sown), small_grads = _program_value_and_grads(*case)
        monkeypatch.setattr(moe, "EXPECTED_ROWS_FACTOR", 16)
        (worst, _), worst_grads = _program_value_and_grads(*case)
    assert float(sown["worst_case"][0]) == 0.0
    for a, b in zip(jax.tree_util.tree_leaves((small, small_grads)),
                    jax.tree_util.tree_leaves((worst, worst_grads))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-6 * float(jnp.abs(b).max()))


def _conditionals(fn, *args):
    return jax.jit(fn).lower(*args).as_text(dialect="hlo").count(
        " conditional(")


@pytest.mark.parametrize("held,conditionals", [
    ((0, TWO_HELD), 2),   # forward and backward
    ((0, E_ALL), 0), ((4, 8), 0)])  # twice the expected rows is the worst case
def test_a_layer_has_two_bodies_only_under_its_worst_case(one_layer, held,
                                                          conditionals):
    p, u, bias, _, _, _ = one_layer

    def loss(p, u):
        return program_layer(p, u, bias, *held)[0].sum()

    assert _conditionals(jax.value_and_grad(loss), p, u) == conditionals


def test_tiny_afmoe_lowers_to_one_body(seeded):
    params, extra, batch = seeded
    model = models.get_model("afmoe_tiny", dtype=jnp.float32,
                             vocab_size=SZ["vocab"])
    assert _conditionals(
        jax.value_and_grad(
            lambda p: program_loss(model, p, router_state(extra),
                                   batch["input_ids"])[0]),
        unflatten(params)) == 0


def test_the_bias_rule():
    counts = jnp.array([4.0, 0.0, 2.0, 2.0, 7.0, 1.0, 0.0, 0.0])
    got = moe.selection_bias_update(jnp.zeros(8), counts, 0.001)
    sign = np.sign(2.0 - np.asarray(counts))          # mean is 2
    want = 0.001 * sign - (0.001 * sign).mean()
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-9)
    np.testing.assert_allclose(
        np.asarray(ref.bias_update(dict(coeff=0.001), jnp.zeros(8), counts)),
        want, atol=1e-9)


# --------------------------------------------------------------------------
# the registry's entries, and the trainer
# --------------------------------------------------------------------------

def _count(name):
    model = models.get_model(name)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 16), jnp.int32), train=False))
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes["params"]))


@pytest.mark.parametrize("name,published", [("trinity_mini", 26.1e9),
                                            ("trinity_mini_ep8", 705.5e6)])
def test_parameter_counts(name, published):
    got = _count(name)
    assert got == models.model_spec(name).param_count
    assert abs(got - published) / published < 0.01


def test_the_share_is_the_published_model_cut_as_the_config_file_says():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "trinity_mini.json")) as fh:
        cfg = json.load(fh)
    share = models.get_model("trinity_mini_ep8").cfg
    whole = models.get_model("trinity_mini").cfg
    for field in ("hidden_size", "num_heads", "num_kv_heads", "head_dim",
                  "intermediate_size", "moe_intermediate_size",
                  "sliding_window", "num_experts", "experts_per_token",
                  "route_scale", "load_balance_coeff", "rms_eps"):
        assert getattr(share, field) == getattr(whole, field), field
    assert list(share.layer_types) == cfg["layer_types"]
    assert share.num_layers == cfg["num_hidden_layers"]
    assert share.num_dense_layers == cfg["num_dense_layers"]
    assert share.experts_held == (cfg["share"]["first_expert"],
                                  cfg["num_experts"])
    assert share.num_experts == cfg["share"]["router_width"] == \
        cfg["published"]["num_experts"]
    assert share.vocab_size == cfg["vocab_size"]
    assert list(whole.layer_types) == (
        [afmoe.SLIDING] * 3 + [afmoe.FULL]) * 8
    assert whole.num_layers == cfg["published"]["num_hidden_layers"]
    assert set(cfg["reduced"]) == set(cfg["published"])


@pytest.fixture(scope="module")
def trained():
    from distributeddeeplearning_tpu.config import (
        DataConfig, OptimizerConfig, ParallelConfig, PrecisionPolicy,
        TrainConfig)
    from distributeddeeplearning_tpu.train import loop

    policy = PrecisionPolicy.mixed()
    cfg = TrainConfig(
        model="afmoe_tiny", backend=None, global_batch_size=2, seed=0,
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
        assert m["moe_worst_case_layers"] == 0.0  # one body: nothing to fall to
        assert 0 < m["moe_tokens_here"] <= 2 * 2 * SEQ * 2  # layers x T x k
        assert 2 / 8 / 2 <= m["moe_max_expert_share"] <= 0.5
    assert history[-1]["loss"] < history[0]["loss"]


def test_the_compiled_step_names_the_new_parts(trained):
    from distributeddeeplearning_tpu.analysis import anatomy

    parts = {anatomy.part_of(op_name)
             for op_name in trained[3].anatomy().values()}
    for part in ("attention_window", "attention_full", "moe_routing",
                 "moe_experts"):
        assert ("forward", part) in parts and ("backward", part) in parts
