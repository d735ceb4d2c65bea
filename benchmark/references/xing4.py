"""Plain reference for the Xing4.0 decoder (`model_type: xing4_0`; the
configuration's `source`, and for what `config.json` does not say the mHC
report, arXiv:2512.24880, and the family's latent-attention convention: the
configuration's `assumed`).

Straightforward `jax.numpy` in float32 at matmul precision "highest". It
imports nothing of the program under test and takes nothing the program made:
weights and inputs come from the seed, through this file. The routed experts
with their shared expert, the selection bias's rule, the batch and the
optimizer's decay rule, with the RMSNorm and the SwiGLU, are the AFMoE
reference's own functions (`references/afmoe.py`), which this model's expert
layer shares to the letter.

    X_0 = E[ids] copied to the n = hc_mult streams      (S, n, C), unscaled
    a hyper-connection round a sub-layer F:
        x~ = RMSNorm(vec(X)) over all nC channels, scale in R^{nC}
        u = x~ Phi, Phi (nC, n^2 + 2n), split u_pre (n) | u_post (n) | u_res (n^2)
        H_pre = sigmoid(a_pre u_pre + b_pre);  H_post = 2 sigmoid(a_post u_post + b_post)
        M = exp(clip(a_res mat(u_res) + B_res, clamp_min, clamp_max))
        hc_sinkhorn_iters times: M <- M / (row sums + hc_eps), then
                                 M <- M / (column sums + hc_eps);  H_res = M
        y = F(sum_j H_pre[j] X_j);   X'_i = sum_j H_res[i, j] X_j + H_post[i] y
    block: one round with F = Attn(N1(.)), one with F = FFN(N2(.))   (RMSNorm)

    Attn(u), H heads:
        c_q = RMSNorm(u W_qa);  q = c_q W_qb as H heads of (qk_nope | qk_rope)
        c = u W_kva;  c_kv = RMSNorm(c[:kv_lora_rank]);  k_r = c[kv_lora_rank:]
        [k_nope | v] = c_kv W_kvb as H heads of (qk_nope | v_head)
        q's qk_rope channels and k_r are rotated (rotate-half pairing) at
        YaRN's frequencies; k = [k_nope | k_r], k_r shared by the heads
        a = softmax(q k^T * (qk_nope + qk_rope)^-1/2 * m^2 + causal) v,
            m = 0.1 mscale_all_dim ln(factor) + 1;  out = a W_o
    YaRN, d = qk_rope, L = original_max_position_embeddings:
        theta_i = rope_theta^(-2i/d), i < d/2
        low = floor(d ln(L / (2 pi beta_fast)) / (2 ln rope_theta)),
        high = ceil(d ln(L / (2 pi beta_slow)) / (2 ln rope_theta)), in [0, d/2 - 1]
        r_i = clip((i - low) / (high - low), 0, 1)
        f_i = theta_i (1 - r_i) + theta_i / factor * r_i;  angle = position * f_i

    FFN: the first `first_k_dense_replace` layers (silu(u W1) * (u W3)) W2;
    the others `afmoe.expert_ffn`: s = sigmoid(u Wr); I = top-k(s + b); w_i =
    routed_scaling_factor * s_i / sum_{j in I} s_j; Shared(u) + sum_{i in I
    and held} w_i Expert_i(u)
    logits = RMSNorm(sum_j X_L,j) W_head; loss = mean next-token cross entropy

The share is the AFMoE reference's: `n_routed_experts` experts held of the
router's `share.router_width`, from `share.first_expert`, and a slice of the
vocabulary; what absent experts would add is left out.

So that 4096 positions fit, each layer is recomputed in the backward pass;
Sinkhorn is a Python loop of `hc_sinkhorn_iters`; attention goes by blocks of
query rows and the head's logits by blocks of rows. Gradients of more than
`_OFFLOAD_PARAMS` parameters go back to the host, as in the AFMoE reference.

`quant` is the hook the lower-precision control uses: it is applied to both
operands of every matrix product, the hyper-connections' with Phi among
them; the mixes and the Sinkhorn iterations are no products and stay as they
are. Parameter names are '/'-joined paths, the same as the program's own tree
has.
"""

from __future__ import annotations

import ctypes
import functools
import math
import sys
import time

import jax
import jax.numpy as jnp

from benchmark import harness

afmoe = harness.load_module("references", "afmoe")

HIGHEST = jax.lax.Precision.HIGHEST
_OFFLOAD_PARAMS = 256 * 1024 * 1024
_QUERY_ROWS = 512
_HEAD_ROWS = 2048
_HC = ("attn_hc/", "ffn_hc/")

make_batch = afmoe.make_batch
bias_update = afmoe.bias_update
_rms, _swiglu, _sub = afmoe._rms, afmoe._swiglu, afmoe._sub


def yarn(sz: dict):
    """(frequencies (d/2,), low, high, m) by the formulas above."""
    d, base, span = sz["rope"], sz["theta"], sz["rope_original_max"]

    def pair(turns):
        return d * math.log(span / (2 * math.pi * turns)) / (2 * math.log(base))

    low = max(math.floor(pair(sz["beta_fast"])), 0)
    high = min(math.ceil(pair(sz["beta_slow"])), d // 2 - 1)
    i = jnp.arange(d // 2, dtype=jnp.float32)
    theta = base ** (-2.0 * i / d)
    r = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    m = 0.1 * sz["mscale_all_dim"] * math.log(sz["rope_factor"]) + 1.0
    return theta * (1.0 - r) + theta / sz["rope_factor"] * r, low, high, m


def sizes(config: dict) -> dict:
    """The reference's sizes, read from the configuration file's own keys."""
    share = config.get("share", {})
    scaling = config["rope_scaling"]
    assert scaling["type"] == "yarn"
    assert scaling["mscale"] == scaling["mscale_all_dim"], (
        "cos and sin carry mscale / mscale_all_dim, which is taken as 1")
    assert config["num_nextn_predict_layers"] == 0, "no MTP module here"
    assert config["n_group"] == config["topk_group"] == 1
    assert config["scoring_func"] == "sigmoid"
    return dict(
        layers=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"],
        hidden=config["hidden_size"], eps=config["rms_norm_eps"],
        streams=config["hc_mult"], sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        clamp=(config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]),
        alpha=config["assumed"]["hc_alpha_init"],
        res_diagonal=config["assumed"]["hc_res_diagonal_init"],
        heads=config["num_attention_heads"], q_rank=config["q_lora_rank"],
        kv_rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        theta=float(config["rope_theta"]), rope_factor=scaling["factor"],
        rope_original_max=scaling["original_max_position_embeddings"],
        beta_fast=scaling["beta_fast"], beta_slow=scaling["beta_slow"],
        mscale_all_dim=scaling["mscale_all_dim"],
        ffn=config["intermediate_size"],
        expert_ffn=config["moe_intermediate_size"],
        shared=config["n_shared_experts"], held=config["n_routed_experts"],
        first_expert=share.get("first_expert", 0),
        router=share.get("router_width", config["n_routed_experts"]),
        top_k=config["num_experts_per_tok"],
        route_norm=config["norm_topk_prob"],
        route_scale=config["routed_scaling_factor"],
        coeff=config["assumed"]["load_balance_coeff"],
        vocab=config["vocab_size"])


def _is_moe(sz: dict, i: int) -> bool:
    return i >= sz["dense_layers"]


def _mix_width(sz: dict) -> int:
    n = sz["streams"]
    return n * n + 2 * n


def _shapes(sz: dict) -> dict:
    """The matrices drawn N(0, 0.02): name -> shape."""
    d, h = sz["hidden"], sz["heads"]
    qk = sz["nope"] + sz["rope"]
    shapes = {"embed_tokens": (sz["vocab"], d),
              "lm_head/kernel": (d, sz["vocab"])}
    for i in range(sz["layers"]):
        p = f"layer{i}/"
        a = p + "attention/"
        shapes.update({
            a + "q_a_proj/kernel": (d, sz["q_rank"]),
            a + "q_b_proj/kernel": (sz["q_rank"], h * qk),
            a + "kv_a_proj/kernel": (d, sz["kv_rank"] + sz["rope"]),
            a + "kv_b_proj/kernel": (sz["kv_rank"],
                                     h * (sz["nope"] + sz["v_dim"])),
            a + "o_proj/kernel": (h * sz["v_dim"], d)})
        for hc in _HC:
            shapes[p + hc + "phi/kernel"] = (sz["streams"] * d,
                                             _mix_width(sz))
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


def _static(sz: dict) -> dict:
    """The leaves that are not drawn: name -> value. Unit norm scales; a
    hyper-connection's alpha (three: pre, post, res) and static terms b_pre =
    logit(1 / n), b_post = 0, B_res a diagonal on zeros (the configuration's
    `assumed`)."""
    d, n = sz["hidden"], sz["streams"]
    ones = functools.partial(jnp.ones, dtype=jnp.float32)
    bias = jnp.concatenate([
        jnp.full((n,), math.log(1.0 / (n - 1)), jnp.float32),
        jnp.zeros((n,), jnp.float32),
        (sz["res_diagonal"] * jnp.eye(n, dtype=jnp.float32)).reshape(-1)])
    out = {"final_layernorm/scale": ones((d,))}
    for i in range(sz["layers"]):
        p = f"layer{i}/"
        out[p + "input_layernorm/scale"] = ones((d,))
        out[p + "post_attention_layernorm/scale"] = ones((d,))
        out[p + "attention/q_a_norm/scale"] = ones((sz["q_rank"],))
        out[p + "attention/kv_a_norm/scale"] = ones((sz["kv_rank"],))
        for hc in _HC:
            out[p + hc + "norm/scale"] = ones((n * d,))
            out[p + hc + "bias"] = bias
            out[p + hc + "alpha"] = jnp.full((3,), sz["alpha"], jnp.float32)
    return out


def init_params(sz: dict, key) -> dict:
    """N(0, 0.02) matrices (Phi among them) and embedding, and `_static`'s
    leaves. One jitted call, on the device, from the seed's key."""
    out = {}
    for n, (name, shape) in enumerate(sorted(_shapes(sz).items())):
        out[name] = 0.02 * jax.random.normal(jax.random.fold_in(key, n),
                                             shape, jnp.float32)
    out.update(_static(sz))
    return out


def param_count(sz: dict) -> int:
    return (sum(math.prod(s) for s in _shapes(sz).values())
            + sum(math.prod(v.shape) for v in jax.eval_shape(
                lambda: _static(sz)).values()))


def init_extra(sz: dict) -> dict:
    """The routers' selection biases: zeros, one vector a layer of experts."""
    return {f"layer{i}/moe/bias": jnp.zeros((sz["router"],), jnp.float32)
            for i in range(sz["layers"]) if _is_moe(sz, i)}


def decays(name: str) -> bool:
    """AdamW's weight decay applies to the matrices (Phi among them), not to
    the embedding, the norms' scales or a hyper-connection's alpha and static
    terms, as the configuration's optimizer states."""
    return afmoe.decays(name)


# --------------------------------------------------------------------------
# forward, one sequence at a time: X is (S, n, hidden), u is (S, hidden)
# --------------------------------------------------------------------------

def _ident(x):
    return x


def sinkhorn(logits, sz: dict):
    """(..., n, n) -> (..., n, n), rows the second-last axis."""
    m = jnp.exp(jnp.clip(logits, *sz["clamp"]))
    for _ in range(sz["sinkhorn_iters"]):
        m = m / (m.sum(-1, keepdims=True) + sz["hc_eps"])
        m = m / (m.sum(-2, keepdims=True) + sz["hc_eps"])
    return m


def hc_coefficients(sz: dict, p: dict, x, quant=_ident):
    """(H_pre (S, n), H_post (S, n), H_res (S, n, n)) of streams (S, n, C)."""
    s, n, _ = x.shape
    xn = _rms(x.reshape(s, -1), p["norm/scale"], sz["eps"])
    u = jnp.matmul(quant(xn), quant(p["phi/kernel"]), precision=HIGHEST)
    a_pre, a_post, a_res = p["alpha"]
    b = p["bias"]
    h_pre = jax.nn.sigmoid(a_pre * u[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a_post * u[:, n:2 * n] + b[n:2 * n])
    h_res = sinkhorn(a_res * u[:, 2 * n:].reshape(s, n, n)
                     + b[2 * n:].reshape(n, n), sz)
    return h_pre, h_post, h_res


def hyper_connection(sz: dict, p: dict, x, fn, quant=_ident):
    """One round: X' = H_res X + H_post^T fn(H_pre X). `fn` may return (y,
    aux); returns (X', aux)."""
    h_pre, h_post, h_res = hc_coefficients(sz, p, x, quant)
    y, aux = fn(jnp.einsum("sj,sjc->sc", h_pre, x, precision=HIGHEST))
    return (jnp.einsum("sij,sjc->sic", h_res, x, precision=HIGHEST)
            + h_post[:, :, None] * y[:, None, :]), aux


def _rope(x, freqs):
    """Rotary positions, rotate-half: x is (S, heads, d)."""
    s, _, d = x.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla_attention(sz: dict, p: dict, u, quant=_ident):
    s = u.shape[0]
    h, nope, rope, dv = sz["heads"], sz["nope"], sz["rope"], sz["v_dim"]
    rank = sz["kv_rank"]
    freqs, _, _, m = yarn(sz)
    scale = (nope + rope) ** -0.5 * m * m

    def mm(a, b):
        return jnp.matmul(quant(a), quant(b), precision=HIGHEST)

    c_q = _rms(mm(u, p["q_a_proj/kernel"]), p["q_a_norm/scale"], sz["eps"])
    q = mm(c_q, p["q_b_proj/kernel"]).reshape(s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], freqs)], -1)
    c = mm(u, p["kv_a_proj/kernel"])
    c_kv = _rms(c[:, :rank], p["kv_a_norm/scale"], sz["eps"])
    k_r = jnp.broadcast_to(_rope(c[:, None, rank:], freqs), (s, h, rope))
    kv = mm(c_kv, p["kv_b_proj/kernel"]).reshape(s, h, nope + dv)
    k = jnp.concatenate([kv[..., :nope], k_r], -1)
    v = kv[..., nope:]
    rows = min(s, _QUERY_ROWS)
    while s % rows:
        rows -= 1
    cols = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(args):
        q_rows, row0 = args                       # (rows, h, nope + rope)
        scores = jnp.einsum("rhd,khd->hrk", quant(q_rows), quant(k),
                            precision=HIGHEST) * scale
        mask = cols <= row0 + jnp.arange(rows)[:, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hrk,khd->rhd", quant(probs), quant(v),
                          precision=HIGHEST).reshape(rows, h * dv)

    a = jax.lax.map(block, (q.reshape(s // rows, rows, h, nope + rope),
                            jnp.arange(0, s, rows)))
    return mm(a.reshape(s, h * dv), p["o_proj/kernel"])


def layer(sz: dict, i: int, p: dict, x, bias, quant=_ident):
    """One block on streams (S, n, hidden); `p` holds the layer's own
    parameters. Returns (X', counts), counts None for a dense layer."""
    def mm(a, b):
        return jnp.matmul(quant(a), quant(b), precision=HIGHEST)

    def attention(u):
        return mla_attention(
            sz, _sub(p, "attention/"),
            _rms(u, p["input_layernorm/scale"], sz["eps"]), quant), None

    def ffn(u):
        u = _rms(u, p["post_attention_layernorm/scale"], sz["eps"])
        if _is_moe(sz, i):
            return afmoe.expert_ffn(sz, _sub(p, "moe/"), u, bias, quant)
        return _swiglu(mm, u, p["gate_proj/kernel"], p["up_proj/kernel"],
                       p["down_proj/kernel"]), None

    x, _ = hyper_connection(sz, _sub(p, "attn_hc/"), x, attention, quant)
    return hyper_connection(sz, _sub(p, "ffn_hc/"), x, ffn, quant)


def hidden_states(sz: dict, params: dict, extra: dict, ids, quant=_ident):
    """(the summed streams after the last layer (S, hidden), {layer:
    counts}) of one sequence of ids, each layer recomputed when
    differentiated."""
    x = params["embed_tokens"][ids]
    x = jnp.broadcast_to(x[:, None, :], (x.shape[0], sz["streams"],
                                         x.shape[1]))
    counts = {}
    for i in range(sz["layers"]):
        name = f"layer{i}/"
        fn = jax.checkpoint(functools.partial(layer, sz, i, quant=quant))
        x, c = fn(_sub(params, name), x, extra.get(name + "moe/bias"))
        if c is not None:
            counts[name + "moe/bias"] = c
    return x.sum(1), counts


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


def _trim_host_heap():
    """Hand the C heap's freed pages back to the system before 3 GB of
    gradients, and then the optimizer's 21 GB, land in the host's memory:
    the compilers leave gigabytes of freed arenas behind, and a one-chip
    machine's 40 GiB were met once with them (PERF.md section 6)."""
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


@functools.lru_cache(maxsize=None)
def _programs(sizes_key: tuple, b: int, s: int, quant):
    """The two jitted programs of a step, one pair a (sizes, batch shape,
    `quant`): a process that reads several seeds (`calibrate.py`) compiles
    them once, which at the cell's size is two minutes of three a seed."""
    sz = dict(sizes_key)

    @jax.jit
    def one(params, extra, ids):
        def f(p):
            total, counts = loss_sum(sz, p, extra, ids, quant)
            return total / (b * (s - 1)), counts
        return jax.value_and_grad(f, has_aux=True)(params)

    @jax.jit
    def move(extra, counts):
        return {k: bias_update(sz, v, counts[k]) for k, v in extra.items()}

    return one, move


def make_grad_fn(sz: dict, traffic: dict, quant=_ident):
    """fn(params, extra, batch, step_key) -> (mean loss, gradients, extra) for
    one training batch: the sequences go through one at a time, their sums
    add up to the batch's mean loss and its gradient, and the selection
    biases move once, by the whole batch's counts. Nothing is random in a
    step, so `step_key` is not used. Where the gradients go back to the host,
    parameters and biases are put on the chip at every step, the first too:
    the seed's own arrays are not committed to a device and the host's are,
    and the difference alone made the second step compile the program again."""
    b, s = traffic["batch"], traffic["seq_len"]
    offload = (sum(math.prod(shape) for shape in _shapes(sz).values())
               > _OFFLOAD_PARAMS and jax.default_backend() != "cpu")
    one, move = _programs(tuple(sorted(sz.items())), b, s, quant)

    def fn(params, extra, batch, step_key):
        del step_key
        t0 = time.perf_counter()
        if offload:
            params, extra = jax.device_put((params, extra), jax.devices()[0])
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
            _trim_host_heap()
            grads = jax.block_until_ready(
                jax.device_put(grads, jax.devices("cpu")[0]))
            print(f"reference step: gradients {t1 - t0:.1f}s, to the host "
                  f"{time.perf_counter() - t1:.1f}s", file=sys.stderr)
        return loss, grads, move(extra, counts)

    return fn
