"""Plain reference for the AFMoE decoder (Arcee Trinity family; the
configuration's `source`, and for what `config.json` does not say the
family's published modelling code: the configuration's `assumed`).

Straightforward `jax.numpy` in float32 at matmul precision "highest". It
imports nothing of the program under test and takes nothing the program made:
weights and inputs come from the seed, through this file.

    x0 = E[ids] * sqrt(hidden)
    block:  h = x + N2(Attn(N1(x)));   y = h + N4(FFN(N3(h)))      (RMSNorm)
    Attn(u): q = RMSNorm_d(u Wq) as H heads of d; k = RMSNorm_d(u Wk) and
             v = u Wv as Hkv heads of d; on sliding_attention layers only,
             rotary positions (rotate-half) on q and k;
             a = softmax(q k^T / sqrt(d) + mask) v, Q head h on K/V head
             h // (H / Hkv), mask causal and, on sliding layers, i - j < window;
             out = (a * sigmoid(u Wg)) Wo
    dense FFN (leading layers): (silu(u W1) * (u W3)) W2
    expert FFN: s = sigmoid(u Wr); I = top-k(s + b); w_i = route_scale * s_i /
             (sum_{j in I} s_j + 1e-20); FFN(u) = Shared(u) + sum_{i in I and
             held} w_i Expert_i(u), experts and shared expert SwiGLU
    after a step: d = coeff * sign(mean(c) - c); b += d - mean(d), c the tokens
             each expert of the router's width was chosen for in that step
    logits = RMSNorm(x_L) W_head; loss = mean next-token cross entropy over the
             B * (S - 1) targets

The share. The configuration holds `num_experts` experts of the router's
`share.router_width`, starting at `share.first_expert`, and a slice of the
vocabulary. The router scores and chooses over its whole width; the sum runs
over the chosen experts that are held, and what the absent ones would add is
left out, here as in the program. With all experts held this is the whole
layer, which is what the share test adds eight shares up to.

So that 8192 positions fit beside the float32 weights, each layer is
recomputed in the backward pass, attention goes by blocks of query rows, the
held experts by a scan that gives every token to every expert with a weight
of nought where it was not chosen, and the head's logits by blocks of rows.
None of that changes a number that is compared beyond float32 rounding.

Where the parameters with Adam's two moments and an update's inputs and
outputs are more than a chip holds (`_OFFLOAD_PARAMS`), the gradients are
handed back in the host's memory: `optim.step` computes where its arguments
are committed, so the optimizer's update runs on the host, and the next step
brings the parameters back.

`quant` is the hook the lower-precision control uses: it is applied to both
operands of every matrix product. Parameter names are '/'-joined paths, the
same as the program's own tree has.
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
SLIDING = "sliding_attention"
_OFFLOAD_PARAMS = 256 * 1024 * 1024  # parameters; see the module's text
_QUERY_ROWS = 512
_HEAD_ROWS = 2048


def sizes(config: dict) -> dict:
    """The reference's sizes, read from the configuration file's own keys."""
    share = config.get("share", {})
    assert len(config["layer_types"]) == config["num_hidden_layers"]
    return dict(
        layer_types=tuple(config["layer_types"]),
        dense_layers=config["num_dense_layers"],
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        window=config["sliding_window"], theta=float(config["rope_theta"]),
        eps=config["rms_norm_eps"], ffn=config["intermediate_size"],
        expert_ffn=config["moe_intermediate_size"],
        shared=config["num_shared_experts"],
        held=config["num_experts"],
        first_expert=share.get("first_expert", 0),
        router=share.get("router_width", config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        route_norm=config["route_norm"], route_scale=config["route_scale"],
        coeff=config["load_balance_coeff"], mup=config["mup_enabled"],
        vocab=config["vocab_size"])


def _is_moe(sz: dict, i: int) -> bool:
    return i >= sz["dense_layers"]


def _shapes(sz: dict) -> dict:
    d, hd = sz["hidden"], sz["head_dim"]
    q, kv = sz["heads"] * hd, sz["kv_heads"] * hd
    shapes = {"embed_tokens": (sz["vocab"], d),
              "lm_head/kernel": (d, sz["vocab"])}
    for i in range(len(sz["layer_types"])):
        p = f"layer{i}/"
        shapes.update({
            p + "attention/q_proj/kernel": (d, q),
            p + "attention/k_proj/kernel": (d, kv),
            p + "attention/v_proj/kernel": (d, kv),
            p + "attention/gate_proj/kernel": (d, q),
            p + "attention/o_proj/kernel": (q, d)})
        if _is_moe(sz, i):
            e, f = sz["held"], sz["expert_ffn"]
            shapes.update({
                p + "moe/router/kernel": (d, sz["router"]),
                p + "moe/experts_gate/kernel": (e, d, f),
                p + "moe/experts_up/kernel": (e, d, f),
                p + "moe/experts_down/kernel": (e, f, d),
                p + "moe/shared_gate/kernel": (d, sz["shared"] * f),
                p + "moe/shared_up/kernel": (d, sz["shared"] * f),
                p + "moe/shared_down/kernel": (sz["shared"] * f, d)})
        else:
            shapes.update({
                p + "gate_proj/kernel": (d, sz["ffn"]),
                p + "up_proj/kernel": (d, sz["ffn"]),
                p + "down_proj/kernel": (sz["ffn"], d)})
    return shapes


def _norm_scales(sz: dict) -> dict:
    d, hd = sz["hidden"], sz["head_dim"]
    out = {"final_layernorm/scale": d}
    for i in range(len(sz["layer_types"])):
        p = f"layer{i}/"
        for name in ("input_layernorm", "post_attention_layernorm",
                     "pre_mlp_layernorm", "post_mlp_layernorm"):
            out[p + name + "/scale"] = d
        out[p + "attention/q_norm/scale"] = hd
        out[p + "attention/k_norm/scale"] = hd
    return out


def init_params(sz: dict, key) -> dict:
    """N(0, 0.02) matrices and embedding, unit norm scales (the
    configuration's `assumed`). One jitted call, on the device, from the
    seed's key."""
    out = {}
    for n, (name, shape) in enumerate(sorted(_shapes(sz).items())):
        out[name] = 0.02 * jax.random.normal(jax.random.fold_in(key, n),
                                             shape, jnp.float32)
    for name, width in _norm_scales(sz).items():
        out[name] = jnp.ones((width,), jnp.float32)
    return out


def init_extra(sz: dict) -> dict:
    """The routers' selection biases: zeros, one vector a layer of experts."""
    return {f"layer{i}/moe/bias": jnp.zeros((sz["router"],), jnp.float32)
            for i in range(len(sz["layer_types"])) if _is_moe(sz, i)}


def decays(name: str) -> bool:
    """AdamW's weight decay applies to the matrices, not to the embedding or
    the norms' scales, as the configuration's optimizer states."""
    return name.endswith("/kernel")


def make_batch(traffic: dict, sz: dict, key, step):
    """One training batch from the seed's key and the step number: uniform
    token ids in [1, vocab) of the vocabulary's slice, every row different."""
    k = jax.random.fold_in(key, step)
    b, s = traffic["batch"], traffic["seq_len"]
    ids = jax.random.randint(k, (b, s), 1, sz["vocab"], jnp.int32)
    return {"input_ids": ids, "attention_mask": jnp.ones((b, s), jnp.int32)}


# --------------------------------------------------------------------------
# forward, one sequence at a time: u is (S, hidden)
# --------------------------------------------------------------------------

def _ident(x):
    return x


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary positions, rotate-half: x is (S, heads, d)."""
    s, _, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(mm, u, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(u, w_gate)) * mm(u, w_up), w_down)


def attention(sz: dict, p: dict, u, sliding: bool, quant=_ident):
    s = u.shape[0]
    h, g, d = sz["heads"], sz["kv_heads"], sz["head_dim"]

    def mm(a, b):
        return jnp.matmul(quant(a), quant(b), precision=HIGHEST)

    q = _rms(mm(u, p["q_proj/kernel"]).reshape(s, h, d), p["q_norm/scale"],
             sz["eps"])
    k = _rms(mm(u, p["k_proj/kernel"]).reshape(s, g, d), p["k_norm/scale"],
             sz["eps"])
    v = mm(u, p["v_proj/kernel"]).reshape(s, g, d)
    if sliding:  # full layers carry no positions
        q, k = _rope(q, sz["theta"]), _rope(k, sz["theta"])
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
        at = row0 + jnp.arange(rows)[:, None]
        mask = cols <= at
        if sliding:
            mask = mask & (at - cols < sz["window"])
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gjrk,kgd->rgjd", quant(probs), quant(v),
                          precision=HIGHEST).reshape(rows, h * d)

    a = jax.lax.map(block, (q.reshape(s // rows, rows, h, d),
                            jnp.arange(0, s, rows)))
    a = a.reshape(s, h * d) * jax.nn.sigmoid(mm(u, p["gate_proj/kernel"]))
    return mm(a, p["o_proj/kernel"])


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
        w_gate, w_up, w_down, gate = expert
        return total + gate[:, None] * _swiglu(mm, u, w_gate, w_up,
                                               w_down), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (p["experts_gate/kernel"], p["experts_up/kernel"],
         p["experts_down/kernel"], held.T))
    shared = _swiglu(mm, u, p["shared_gate/kernel"], p["shared_up/kernel"],
                     p["shared_down/kernel"])
    return shared + routed, picked.sum(0)


def _sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def layer(sz: dict, i: int, p: dict, x, bias, quant=_ident):
    """One block on (S, hidden); `p` holds the layer's own parameters.
    Returns (y, counts), counts None for a dense layer."""
    def mm(a, b):
        return jnp.matmul(quant(a), quant(b), precision=HIGHEST)

    eps = sz["eps"]
    a = attention(sz, _sub(p, "attention/"),
                  _rms(x, p["input_layernorm/scale"], eps),
                  sz["layer_types"][i] == SLIDING, quant)
    h = x + _rms(a, p["post_attention_layernorm/scale"], eps)
    u = _rms(h, p["pre_mlp_layernorm/scale"], eps)
    if _is_moe(sz, i):
        f, counts = expert_ffn(sz, _sub(p, "moe/"), u, bias, quant)
    else:
        f, counts = _swiglu(mm, u, p["gate_proj/kernel"], p["up_proj/kernel"],
                            p["down_proj/kernel"]), None
    return h + _rms(f, p["post_mlp_layernorm/scale"], eps), counts


def hidden_states(sz: dict, params: dict, extra: dict, ids, quant=_ident):
    """(x_L, {layer: counts}) of one sequence of ids, each layer recomputed
    when differentiated."""
    x = params["embed_tokens"][ids]
    if sz["mup"]:
        x = x * sz["hidden"] ** 0.5
    counts = {}
    for i in range(len(sz["layer_types"])):
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


def bias_update(sz: dict, bias, counts):
    delta = sz["coeff"] * jnp.sign(jnp.mean(counts) - counts)
    return bias + delta - jnp.mean(delta)


def make_grad_fn(sz: dict, traffic: dict, quant=_ident):
    """fn(params, extra, batch, step_key) -> (mean loss, gradients, extra) for
    one training batch: the sequences go through one at a time, their sums
    add up to the batch's mean loss and its gradient, and the selection
    biases move once, by the whole batch's counts. Nothing is random in a
    step (no dropout), so `step_key` is not used."""
    b, s = traffic["batch"], traffic["seq_len"]
    offload = (sum(_size(shape) for shape in _shapes(sz).values())
               > _OFFLOAD_PARAMS and jax.default_backend() != "cpu")

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


def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n
