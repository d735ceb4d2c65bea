"""Plain reference for the Nemotron-H hybrid decoder (`model_type:
nemotron_h`; the configuration's `source`, and for what `config.json` does
not say the family's published modelling code: the configuration's
`assumed`).

Straightforward `jax.numpy` in float32 at matmul precision "highest". It
imports nothing of the program under test and takes nothing the program made:
weights and inputs come from the seed, through this file. The batch, the
selection bias's rule, the optimizer's decay rule and the RMSNorm are the
AFMoE reference's own functions (`references/afmoe.py`), the causal
convolution the Kimi Linear reference's (`references/kimi_linear.py`).

    x0 = E[ids]                                       (unscaled)
    layer i, by the i-th letter of hybrid_override_pattern:
        x <- x + f(RMSNorm(x))

    M, Mamba-2, u = RMSNorm(x), H heads of P, G groups of B / C of N:
        [z | xBC | dt] = u W_in                  (H P | H P + 2 G N | H)
        xBC = silu(conv4(xBC) + b_conv);  [x | B | C] = xBC
            conv4: depthwise, causal, zeros before t = 0,
            y_t[c] = sum_{j=0..3} w[j, c] xBC_{t-3+j}[c]
        dt = softplus(dt + dt_bias);  A = -exp(A_log)                 (H,)
        state h (P x N a head), h_0 = 0, token by token, head h on group
        g = h // (H / G):
            h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t[g]^T
            y_t = h_t C_t[g] + D x_t
        g = y * silu(z), RMS-normalised over G groups of H P / G channels
        out = (g * w_norm) W_out

    E, experts: s = sigmoid(u Wr) over the router's whole width; I = top-k(s
        + b); w_i = routed_scaling_factor * s_i / (sum_{j in I} s_j + 1e-20);
        FFN(u) = Shared(u) + sum_{i in I and held} w_i Expert_i(u), each
        relu2: down(relu(u up)^2)

    *, attention with no positions: q = u Wq as H heads of d; k = u Wk and
        v = u Wv as Hkv heads of d; a = softmax(q k^T / sqrt(d) + causal) v,
        Q head h on K/V head h // (H / Hkv); out = a Wo

    logits = RMSNorm(x_L) W_head; loss = mean next-token cross entropy

The share is the AFMoE reference's: `n_routed_experts` experts held of the
router's `share.router_width`, from `share.first_expert`, and a slice of the
vocabulary; what absent experts would add is left out.

So that 8192 positions fit, each layer is recomputed in the backward pass;
the recurrence is a scan over blocks of a scan over tokens with each block
recomputed, so 8192 states are never held; attention goes by blocks of query
rows and the head's logits by blocks of rows. Nothing here chunks the
recurrence as the program does. Gradients of more than `_OFFLOAD_PARAMS`
parameters go back to the host, as in the AFMoE reference.

`quant` is the hook the lower-precision control uses: it is applied to both
operands of every product: the matrices', the convolution's taps, and the
recurrence's write and read. Parameter names are '/'-joined paths, the same
as the program's own tree has.
"""

from __future__ import annotations

import functools
import math
import sys
import time

import jax
import jax.numpy as jnp

from benchmark import harness

afmoe = harness.load_module("references", "afmoe")
kimi = harness.load_module("references", "kimi_linear")

HIGHEST = jax.lax.Precision.HIGHEST
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
_OFFLOAD_PARAMS = 256 * 1024 * 1024
_QUERY_ROWS = 512
_HEAD_ROWS = 2048
_SCAN_BLOCK = 64

make_batch = afmoe.make_batch
bias_update = afmoe.bias_update
decays = afmoe.decays
_rms, _sub, _conv = afmoe._rms, afmoe._sub, kimi._conv


def sizes(config: dict) -> dict:
    """The reference's sizes, read from the configuration file's own keys."""
    share = config.get("share", {})
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == config["num_hidden_layers"]
    assert set(pattern) <= {MAMBA, EXPERTS, ATTENTION}, pattern
    return dict(
        pattern=pattern, hidden=config["hidden_size"],
        eps=config["layer_norm_epsilon"],
        m_heads=config["mamba_num_heads"], m_dim=config["mamba_head_dim"],
        groups=config["n_groups"], state=config["ssm_state_size"],
        conv=config["conv_kernel"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        expert_ffn=config["moe_intermediate_size"],
        shared_ffn=config["moe_shared_expert_intermediate_size"],
        held=config["n_routed_experts"],
        first_expert=share.get("first_expert", 0),
        router=share.get("router_width", config["n_routed_experts"]),
        top_k=config["num_experts_per_tok"],
        route_norm=config["norm_topk_prob"],
        route_scale=config["routed_scaling_factor"],
        dt_range=(config["time_step_min"], config["time_step_max"]),
        dt_floor=config["time_step_floor"],
        coeff=config["assumed"]["load_balance_coeff"],
        vocab=config["vocab_size"])


def _widths(sz: dict) -> tuple:
    """(inner, the convolution's channels) of a mixer."""
    inner = sz["m_heads"] * sz["m_dim"]
    return inner, inner + 2 * sz["groups"] * sz["state"]


def _shapes(sz: dict) -> dict:
    """The matrices and taps drawn N(0, 0.02): name -> shape."""
    d = sz["hidden"]
    inner, width = _widths(sz)
    shapes = {"embed_tokens": (sz["vocab"], d),
              "lm_head/kernel": (d, sz["vocab"])}
    for i, kind in enumerate(sz["pattern"]):
        p = f"layer{i}/"
        if kind == MAMBA:
            shapes.update({
                p + "mixer/in_proj/kernel": (d, inner + width + sz["m_heads"]),
                p + "mixer/conv1d/kernel": (sz["conv"], width),
                p + "mixer/out_proj/kernel": (inner, d)})
        elif kind == ATTENTION:
            q = sz["heads"] * sz["head_dim"]
            kv = sz["kv_heads"] * sz["head_dim"]
            shapes.update({
                p + "mixer/q_proj/kernel": (d, q),
                p + "mixer/k_proj/kernel": (d, kv),
                p + "mixer/v_proj/kernel": (d, kv),
                p + "mixer/o_proj/kernel": (q, d)})
        else:
            e, f, fs = sz["held"], sz["expert_ffn"], sz["shared_ffn"]
            shapes.update({
                p + "moe/router/kernel": (d, sz["router"]),
                p + "moe/experts_up/kernel": (e, d, f),
                p + "moe/experts_down/kernel": (e, f, d),
                p + "moe/shared_up/kernel": (d, fs),
                p + "moe/shared_down/kernel": (fs, d)})
    return shapes


def param_count(sz: dict) -> int:
    """Every parameter the program holds: the drawn ones, the norms' scales,
    and a mixer's convolution bias, A_log, dt_bias and D."""
    inner, width = _widths(sz)
    drawn = sum(math.prod(s) for s in _shapes(sz).values())
    mixers = sz["pattern"].count(MAMBA)
    return (drawn + (len(sz["pattern"]) + 1) * sz["hidden"]
            + mixers * (width + 3 * sz["m_heads"] + inner))


def init_params(sz: dict, key) -> dict:
    """N(0, 0.02) matrices, convolution taps and embedding; unit norm scales
    and D; zero convolution biases; and the recurrence's own two (the
    configuration's `assumed`): A_log = log(1, ..., heads), dt_bias the
    inverse softplus of a step drawn log-uniform in [time_step_min,
    time_step_max] a head, floored at time_step_floor. One jitted call, on
    the device, from the seed's key.

    The experts' down projections (routed and shared) are centred over
    their input axis after the draw. relu(.)^2 is never negative: its mean,
    sigma^2 / 2 a hidden unit, times a down projection's column sums would
    add one vector to every token's output, the routers of the later expert
    layers would all see it, and the rows that land on this share's experts
    would swing with the seed's weights (1791 to 4263 of the 3072 expected
    in the second expert layer on six seeds, and the step's time with them).
    Centred, every layer's rows are the expected ones to a few percent: the
    balance that the family's routing is built to hold in training
    (config.json's `topk_method` noaux_tc, the auxiliary-loss-free selection
    bias of arXiv:2408.15664, which moves each expert's load towards the
    mean after every step)."""
    out = {}
    for n, (name, shape) in enumerate(sorted(_shapes(sz).items())):
        w = 0.02 * jax.random.normal(jax.random.fold_in(key, n), shape,
                                     jnp.float32)
        if name.endswith(("experts_down/kernel", "shared_down/kernel")):
            w = w - w.mean(-2, keepdims=True)
        out[name] = w
    d, h = sz["hidden"], sz["m_heads"]
    inner, width = _widths(sz)
    lo, hi = sz["dt_range"]
    out["final_layernorm/scale"] = jnp.ones((d,), jnp.float32)
    for i, kind in enumerate(sz["pattern"]):
        p = f"layer{i}/"
        out[p + "input_layernorm/scale"] = jnp.ones((d,), jnp.float32)
        if kind != MAMBA:
            continue
        m = p + "mixer/"
        dt = jnp.exp(jax.random.uniform(
            jax.random.fold_in(key, 10_000 + i), (h,), jnp.float32,
            math.log(lo), math.log(hi)))
        dt = jnp.maximum(dt, sz["dt_floor"])
        out[m + "dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
        out[m + "A_log"] = jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32))
        out[m + "D"] = jnp.ones((h,), jnp.float32)
        out[m + "conv1d/bias"] = jnp.zeros((width,), jnp.float32)
        out[m + "norm/scale"] = jnp.ones((inner,), jnp.float32)
    return out


def init_extra(sz: dict) -> dict:
    """The routers' selection biases: zeros, one vector a layer of experts."""
    return {f"layer{i}/moe/bias": jnp.zeros((sz["router"],), jnp.float32)
            for i, kind in enumerate(sz["pattern"]) if kind == EXPERTS}


# --------------------------------------------------------------------------
# forward, one sequence at a time: u is (S, hidden)
# --------------------------------------------------------------------------

def _ident(x):
    return x


def recurrence(x, dt, a, b, c, quant=_ident):
    """The state-space recurrence token by token. x: (S, H, P); dt: (S, H);
    a: (H,); b, c: (S, G, N). Returns y (S, H, P) without the D skip. A scan
    over blocks of a scan over tokens, each block recomputed when
    differentiated."""
    s, h, p = x.shape
    rep = h // b.shape[1]
    block = min(s, _SCAN_BLOCK)
    while s % block:
        block -= 1

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = jnp.repeat(b_t, rep, 0), jnp.repeat(c_t, rep, 0)
        state = (state * jnp.exp(dt_t * a)[:, None, None]
                 + quant(dt_t[:, None] * x_t)[:, :, None]
                 * quant(b_t)[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", quant(state), quant(c_t),
                                 precision=HIGHEST)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(v.reshape((s // block, block) + v.shape[1:])
               for v in (x, dt, b, c))
    _, y = jax.lax.scan(tokens, jnp.zeros((h, p, b.shape[-1]), jnp.float32),
                        xs)
    return y.reshape(s, h, p)


def mamba(sz: dict, p: dict, u, quant=_ident):
    s = u.shape[0]
    h, pd, g, n = sz["m_heads"], sz["m_dim"], sz["groups"], sz["state"]
    inner, width = _widths(sz)

    def mm(a, b):
        return jnp.matmul(quant(a), quant(b), precision=HIGHEST)

    zxbcdt = mm(u, p["in_proj/kernel"])
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + width],
                  zxbcdt[:, inner + width:])
    xbc = jax.nn.silu(_conv(xbc, p["conv1d/kernel"], quant)
                      + p["conv1d/bias"])
    x = xbc[:, :inner].reshape(s, h, pd)
    b = xbc[:, inner:inner + g * n].reshape(s, g, n)
    c = xbc[:, inner + g * n:].reshape(s, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), b, c, quant)
    y = (y + p["D"][:, None] * x).reshape(s, inner)
    gated = (y * jax.nn.silu(z)).reshape(s, g, inner // g)
    gated = _rms(gated, 1.0, sz["eps"]).reshape(s, inner) * p["norm/scale"]
    return mm(gated, p["out_proj/kernel"])


def attention(sz: dict, p: dict, u, quant=_ident):
    s = u.shape[0]
    h, g, d = sz["heads"], sz["kv_heads"], sz["head_dim"]

    def mm(a, b):
        return jnp.matmul(quant(a), quant(b), precision=HIGHEST)

    q = mm(u, p["q_proj/kernel"]).reshape(s, h, d)
    k = mm(u, p["k_proj/kernel"]).reshape(s, g, d)
    v = mm(u, p["v_proj/kernel"]).reshape(s, g, d)
    rows = min(s, _QUERY_ROWS)
    while s % rows:
        rows -= 1
    cols = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(args):
        q_rows, row0 = args                       # (rows, h, d), scalar
        grouped = q_rows.reshape(rows, g, h // g, d)
        scores = jnp.einsum("rgjd,kgd->gjrk", quant(grouped), quant(k),
                            precision=HIGHEST) * d ** -0.5
        mask = cols <= row0 + jnp.arange(rows)[:, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gjrk,kgd->rgjd", quant(probs), quant(v),
                          precision=HIGHEST).reshape(rows, h * d)

    a = jax.lax.map(block, (q.reshape(s // rows, rows, h, d),
                            jnp.arange(0, s, rows)))
    return mm(a.reshape(s, h * d), p["o_proj/kernel"])


def _relu2(mm, u, w_up, w_down):
    return mm(jnp.square(jax.nn.relu(mm(u, w_up))), w_down)


def expert_ffn(sz: dict, p: dict, u, bias, quant=_ident):
    """(FFN(u), counts): the shared expert and this share of the routed
    ones; `counts` is how many tokens chose each expert of the router's
    whole width."""
    def mm(a, b):
        return jnp.matmul(quant(a), quant(b), precision=HIGHEST)

    scores = jax.nn.sigmoid(mm(u, p["router/kernel"]))          # (S, E)
    _, chosen = jax.lax.top_k(scores + bias, sz["top_k"])
    picked = jax.nn.one_hot(chosen, sz["router"]).sum(1)        # 0 / 1
    gates = scores * picked
    if sz["route_norm"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    gates = gates * sz["route_scale"]
    first = sz["first_expert"]
    held = gates[:, first:first + sz["held"]]                   # (S, held)

    @jax.checkpoint
    def one(total, expert):
        w_up, w_down, gate = expert
        return total + gate[:, None] * _relu2(mm, u, w_up, w_down), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (p["experts_up/kernel"], p["experts_down/kernel"], held.T))
    shared = _relu2(mm, u, p["shared_up/kernel"], p["shared_down/kernel"])
    return shared + routed, picked.sum(0)


def layer(sz: dict, i: int, p: dict, x, bias, quant=_ident):
    """One layer on (S, hidden); `p` holds the layer's own parameters.
    Returns (y, counts), counts None for a mixer."""
    u = _rms(x, p["input_layernorm/scale"], sz["eps"])
    kind = sz["pattern"][i]
    if kind == EXPERTS:
        f, counts = expert_ffn(sz, _sub(p, "moe/"), u, bias, quant)
        return x + f, counts
    mixer = mamba if kind == MAMBA else attention
    return x + mixer(sz, _sub(p, "mixer/"), u, quant), None


def hidden_states(sz: dict, params: dict, extra: dict, ids, quant=_ident):
    """(x_L, {layer: counts}) of one sequence of ids, each layer recomputed
    when differentiated."""
    x = params["embed_tokens"][ids]
    counts = {}
    for i in range(len(sz["pattern"])):
        name = f"layer{i}/"
        fn = jax.checkpoint(functools.partial(layer, sz, i, quant=quant))
        x, c = fn(_sub(params, name), x, extra.get(name + "moe/bias"))
        if c is not None:
            counts[name + "moe/bias"] = c
    return x, counts


def forward(sz: dict, params: dict, extra: dict, ids, quant=_ident):
    """(S,) ids -> (S, vocab) float32 logits."""
    x, _ = hidden_states(sz, params, extra, ids, quant)
    x = _rms(x, params["final_layernorm/scale"], sz["eps"])
    return jnp.matmul(quant(x), quant(params["lm_head/kernel"]),
                      precision=HIGHEST)


def loss_sum(sz: dict, params: dict, extra: dict, ids, quant=_ident):
    """(sum of next-token cross entropies over one sequence, counts); the
    logits are made a block of rows at a time."""
    x, counts = hidden_states(sz, params, extra, ids, quant)
    x = _rms(x, params["final_layernorm/scale"], sz["eps"])[:-1]
    targets = ids[1:]

    @jax.checkpoint
    def rows_loss(xr, tr):
        logits = jnp.matmul(quant(xr), quant(params["lm_head/kernel"]),
                            precision=HIGHEST)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, tr[:, None], axis=-1).sum()

    total = 0.0
    for r0 in range(0, x.shape[0], _HEAD_ROWS):
        total = total + rows_loss(x[r0:r0 + _HEAD_ROWS],
                                  targets[r0:r0 + _HEAD_ROWS])
    return total, counts


def make_grad_fn(sz: dict, traffic: dict, quant=_ident):
    """fn(params, extra, batch, step_key) -> (mean loss, gradients, extra) for
    one training batch: the sequences go through one at a time, their sums
    add up to the batch's mean loss and its gradient, and the selection
    biases move once, by the whole batch's counts. Nothing is random in a
    step, so `step_key` is not used."""
    b, s = traffic["batch"], traffic["seq_len"]
    offload = (param_count(sz) > _OFFLOAD_PARAMS
               and jax.default_backend() != "cpu")

    @jax.jit
    def one(params, extra, ids):
        def f(p):
            total, counts = loss_sum(sz, p, extra, ids, quant)
            return total / (b * (s - 1)), counts
        return jax.value_and_grad(f, has_aux=True)(params)

    @jax.jit
    def move(extra, counts):
        return {k: bias_update(sz, v, counts[k]) for k, v in extra.items()}

    def fn(params, extra, batch, step_key):
        del step_key
        t0 = time.perf_counter()
        if offload:
            params = jax.device_put(params, jax.devices()[0])
        loss, grads, counts = 0.0, None, None
        for row in range(b):
            (l, c), g = one(params, extra, batch["input_ids"][row])
            loss = loss + l
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
            counts = c if counts is None else jax.tree_util.tree_map(
                jnp.add, counts, c)
        if offload:
            loss = float(loss)
            t1 = time.perf_counter()
            grads = jax.block_until_ready(
                jax.device_put(grads, jax.devices("cpu")[0]))
            print(f"reference step: gradients {t1 - t0:.1f}s, to the host "
                  f"{time.perf_counter() - t1:.1f}s", file=sys.stderr)
        return loss, grads, move(extra, counts)

    return fn
