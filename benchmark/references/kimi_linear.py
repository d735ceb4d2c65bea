"""Plain reference for the Kimi Linear decoder (`model_type: kimi_linear`;
the configuration's `source`, and for what `config.json` does not say the
family's published modelling code and report, arXiv:2510.26692: the
configuration's `assumed`).

Straightforward `jax.numpy` in float32 at matmul precision "highest". It
imports nothing of the program under test and takes nothing the program made:
weights and inputs come from the seed, through this file. The routed experts
with their shared expert, the selection bias's rule, the batch and the
optimizer's decay rule, with the RMSNorm and the SwiGLU, are the AFMoE
reference's own functions (`references/afmoe.py`), which this model's expert
layer shares to the letter.

    x0 = E[ids]                                         (unscaled)
    block:  h = x + Attn(N1(x));   y = h + FFN(N2(h))   (RMSNorm, pre-norm)

    KDA layer (linear_attn_config.kda_layers, numbered from 1), u = N1(x),
    H heads of d:
        q, k, v = silu(conv4(u Wq)), silu(conv4(u Wk)), silu(conv4(u Wv))
            conv4: depthwise, causal, y_t[c] = sum_{j=0..3} w[j, c] x_{t-3+j}[c],
            zeros before t = 0
        per head: q <- q / sqrt(|q|^2 + 1e-6) * d^-1/2,  k <- k / sqrt(|k|^2 + 1e-6)
        g_t = -exp(A_log[h]) * softplus((u W_fa) W_fb + dt_bias)   (H, d), <= 0
        beta_t = sigmoid(u W_b)                                     (H,)
        state S (d x d a head), S_0 = 0, token by token:
            S' = Diag(exp g_t) S_{t-1}
            S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
            o_t = S_t^T q_t
        out = (RMSNorm_d(o_t) * sigmoid((u W_ga) W_gb + b_g)) W_o

    MLA layer (linear_attn_config.full_attn_layers), no positions:
        q = u Wq as H heads of (qk_nope + qk_rope)
        c = u W_kva;  c_kv = RMSNorm(c[:kv_lora_rank]);  k_pe = c[kv_lora_rank:]
        [k_nope, v] = c_kv W_kvb as H heads of (qk_nope + v_head);  k = [k_nope, k_pe]
        a = softmax(q k^T / sqrt(qk_nope + qk_rope) + causal) v;  out = a W_o

    FFN: the first `first_k_dense_replace` layers (silu(u W1) * (u W3)) W2;
    the others `afmoe.expert_ffn`: s = sigmoid(u Wr); I = top-k(s + b); w_i =
    routed_scaling_factor * s_i / sum_{j in I} s_j; Shared(u) + sum_{i in I
    and held} w_i Expert_i(u)
    logits = RMSNorm(x_L) W_head; loss = mean next-token cross entropy

The share is the AFMoE reference's: `num_experts` experts held of the
router's `share.router_width`, from `share.first_expert`, and a slice of the
vocabulary; what absent experts would add is left out.

So that 8192 positions fit, each layer is recomputed in the backward pass;
the recurrence is a scan over chunks of a scan over tokens with each chunk
recomputed, so 8192 states are never held; latent attention goes by blocks of
query rows and the head's logits by blocks of rows. Gradients of more than
`_OFFLOAD_PARAMS` parameters go back to the host, as in the AFMoE reference.

`quant` is the hook the lower-precision control uses: it is applied to both
operands of every product: the matrices', the convolution's taps, and the
recurrence's read, rank-one write and query. Parameter names are '/'-joined
paths, the same as the program's own tree has.
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

HIGHEST = jax.lax.Precision.HIGHEST
KDA, MLA = "kda", "mla"
_OFFLOAD_PARAMS = 256 * 1024 * 1024
_QUERY_ROWS = 512
_HEAD_ROWS = 2048
_SCAN_CHUNK = 64
_L2_EPS = 1e-6

make_batch = afmoe.make_batch
bias_update = afmoe.bias_update
_rms, _swiglu, _sub = afmoe._rms, afmoe._swiglu, afmoe._sub


def sizes(config: dict) -> dict:
    """The reference's sizes, read from the configuration file's own keys."""
    share = config.get("share", {})
    lin = config["linear_attn_config"]
    layers = config["num_hidden_layers"]
    kinds = tuple(KDA if i + 1 in lin["kda_layers"] else MLA
                  for i in range(layers))
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) == list(
        range(1, layers + 1)), "every layer is one of the two kinds"
    assert config["q_lora_rank"] is None and config["mla_use_nope"]
    return dict(
        kinds=kinds, dense_layers=config["first_k_dense_replace"],
        hidden=config["hidden_size"], eps=config["rms_norm_eps"],
        kda_heads=lin["num_heads"], kda_dim=lin["head_dim"],
        conv=lin["short_conv_kernel_size"],
        gate_rank=lin["head_dim"],   # the low-rank gates' inner width
        heads=config["num_attention_heads"], kv_rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], ffn=config["intermediate_size"],
        expert_ffn=config["moe_intermediate_size"],
        shared=config["num_shared_experts"], held=config["num_experts"],
        first_expert=share.get("first_expert", 0),
        router=share.get("router_width", config["num_experts"]),
        top_k=config["num_experts_per_token"],
        route_norm=config["moe_renormalize"],
        route_scale=config["routed_scaling_factor"],
        coeff=config["assumed"]["load_balance_coeff"], vocab=config["vocab_size"])


def _is_moe(sz: dict, i: int) -> bool:
    return i >= sz["dense_layers"]


def _shapes(sz: dict) -> dict:
    """The matrices drawn N(0, 0.02): name -> shape."""
    d = sz["hidden"]
    kd = sz["kda_heads"] * sz["kda_dim"]
    r = sz["gate_rank"]
    qk = sz["nope"] + sz["rope"]
    shapes = {"embed_tokens": (sz["vocab"], d),
              "lm_head/kernel": (d, sz["vocab"])}
    for i, kind in enumerate(sz["kinds"]):
        p = f"layer{i}/"
        a = p + "attention/"
        if kind == KDA:
            shapes.update({
                a + "q_proj/kernel": (d, kd), a + "k_proj/kernel": (d, kd),
                a + "v_proj/kernel": (d, kd),
                a + "q_conv/kernel": (sz["conv"], kd),
                a + "k_conv/kernel": (sz["conv"], kd),
                a + "v_conv/kernel": (sz["conv"], kd),
                a + "f_a_proj/kernel": (d, r), a + "f_b_proj/kernel": (r, kd),
                a + "b_proj/kernel": (d, sz["kda_heads"]),
                a + "g_a_proj/kernel": (d, r), a + "g_b_proj/kernel": (r, kd),
                a + "o_proj/kernel": (kd, d)})
        else:
            h = sz["heads"]
            shapes.update({
                a + "q_proj/kernel": (d, h * qk),
                a + "kv_a_proj/kernel": (d, sz["kv_rank"] + sz["rope"]),
                a + "kv_b_proj/kernel": (sz["kv_rank"],
                                         h * (sz["nope"] + sz["v_dim"])),
                a + "o_proj/kernel": (h * sz["v_dim"], d)})
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


def init_params(sz: dict, key) -> dict:
    """N(0, 0.02) matrices, convolution taps and embedding; unit norm
    scales; a zero gate bias; and the recurrence's own two (the
    configuration's `assumed`): A_log = log U(1, 16) a head, dt_bias the
    inverse softplus of a step drawn log-uniform in [1e-3, 0.1] a channel.
    One jitted call, on the device, from the seed's key."""
    out = {}
    for n, (name, shape) in enumerate(sorted(_shapes(sz).items())):
        out[name] = 0.02 * jax.random.normal(jax.random.fold_in(key, n),
                                             shape, jnp.float32)
    d = sz["hidden"]
    kd = sz["kda_heads"] * sz["kda_dim"]
    out["final_layernorm/scale"] = jnp.ones((d,), jnp.float32)
    for i, kind in enumerate(sz["kinds"]):
        p = f"layer{i}/"
        out[p + "input_layernorm/scale"] = jnp.ones((d,), jnp.float32)
        out[p + "post_attention_layernorm/scale"] = jnp.ones((d,),
                                                             jnp.float32)
        a = p + "attention/"
        if kind == KDA:
            ka, kb = jax.random.split(jax.random.fold_in(key, 10_000 + i))
            out[a + "A_log"] = jnp.log(jax.random.uniform(
                ka, (sz["kda_heads"],), jnp.float32, 1.0, 16.0))
            dt = jnp.exp(jax.random.uniform(
                kb, (kd,), jnp.float32, math.log(1e-3), math.log(0.1)))
            out[a + "dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            out[a + "g_b_proj/bias"] = jnp.zeros((kd,), jnp.float32)
            out[a + "o_norm/scale"] = jnp.ones((sz["kda_dim"],), jnp.float32)
        else:
            out[a + "kv_a_norm/scale"] = jnp.ones((sz["kv_rank"],),
                                                  jnp.float32)
    return out


def init_extra(sz: dict) -> dict:
    """The routers' selection biases: zeros, one vector a layer of experts."""
    return {f"layer{i}/moe/bias": jnp.zeros((sz["router"],), jnp.float32)
            for i in range(len(sz["kinds"])) if _is_moe(sz, i)}


def decays(name: str) -> bool:
    """AdamW's weight decay applies to the matrices (the convolutions' taps
    among them), not to the embedding, the norms' scales, the gate's bias,
    A_log or dt_bias, as the configuration's optimizer states."""
    return afmoe.decays(name)


# --------------------------------------------------------------------------
# forward, one sequence at a time: u is (S, hidden)
# --------------------------------------------------------------------------

def _ident(x):
    return x


def _conv(x, taps, quant):
    """Depthwise causal convolution over (S, channels): taps (K, channels),
    the last tap on the token itself, zeros before the first token."""
    s, width = x.shape[0], taps.shape[0]
    xp = jnp.pad(quant(x), ((width - 1, 0), (0, 0)))
    return sum(quant(taps[j]) * xp[j:j + s] for j in range(width))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _L2_EPS)


def recurrence(q, k, v, g, beta, quant=_ident):
    """The delta rule token by token. q, k, g: (S, H, d); v: (S, H, dv);
    beta: (S, H). Returns o (S, H, dv). A scan over chunks of a scan over
    tokens, each chunk recomputed when differentiated."""
    s, h, d = q.shape
    chunk = min(s, _SCAN_CHUNK)
    while s % chunk:
        chunk -= 1

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[:, :, None]                # S'
        read = jnp.einsum("hkv,hk->hv", quant(state), quant(k_t),
                          precision=HIGHEST)
        u = beta_t[:, None] * (v_t - read)
        state = state + quant(k_t)[:, :, None] * quant(u)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", quant(state), quant(q_t),
                                 precision=HIGHEST)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(x.reshape((s // chunk, chunk) + x.shape[1:])
               for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(tokens, jnp.zeros((h, d, v.shape[-1]),
                                            jnp.float32), xs)
    return out.reshape(s, h, v.shape[-1])


def kda_attention(sz: dict, p: dict, u, quant=_ident):
    s = u.shape[0]
    h, d = sz["kda_heads"], sz["kda_dim"]

    def mm(a, b):
        return jnp.matmul(quant(a), quant(b), precision=HIGHEST)

    def branch(name):
        x = _conv(mm(u, p[name + "_proj/kernel"]), p[name + "_conv/kernel"],
                  quant)
        return jax.nn.silu(x).reshape(s, h, d)

    q = _l2(branch("q")) * d ** -0.5
    k = _l2(branch("k"))
    v = branch("v")
    f = mm(mm(u, p["f_a_proj/kernel"]), p["f_b_proj/kernel"]) + p["dt_bias"]
    g = (-jnp.exp(p["A_log"])[None, :, None]
         * jax.nn.softplus(f).reshape(s, h, d))
    beta = jax.nn.sigmoid(mm(u, p["b_proj/kernel"]))
    o = recurrence(q, k, v, g, beta, quant)
    gate = (mm(mm(u, p["g_a_proj/kernel"]), p["g_b_proj/kernel"])
            + p["g_b_proj/bias"]).reshape(s, h, d)
    o = _rms(o, p["o_norm/scale"], sz["eps"]) * jax.nn.sigmoid(gate)
    return mm(o.reshape(s, h * d), p["o_proj/kernel"])


def mla_attention(sz: dict, p: dict, u, quant=_ident):
    s = u.shape[0]
    h, nope, rope, dv = sz["heads"], sz["nope"], sz["rope"], sz["v_dim"]
    rank = sz["kv_rank"]

    def mm(a, b):
        return jnp.matmul(quant(a), quant(b), precision=HIGHEST)

    q = mm(u, p["q_proj/kernel"]).reshape(s, h, nope + rope)
    c = mm(u, p["kv_a_proj/kernel"])
    c_kv = _rms(c[:, :rank], p["kv_a_norm/scale"], sz["eps"])
    k_pe = jnp.broadcast_to(c[:, None, rank:], (s, h, rope))
    kv = mm(c_kv, p["kv_b_proj/kernel"]).reshape(s, h, nope + dv)
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    v = kv[..., nope:]
    rows = min(s, _QUERY_ROWS)
    while s % rows:
        rows -= 1
    cols = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(args):
        q_rows, row0 = args                       # (rows, h, nope + rope)
        scores = jnp.einsum("rhd,khd->hrk", quant(q_rows), quant(k),
                            precision=HIGHEST) * (nope + rope) ** -0.5
        mask = cols <= row0 + jnp.arange(rows)[:, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hrk,khd->rhd", quant(probs), quant(v),
                          precision=HIGHEST).reshape(rows, h * dv)

    a = jax.lax.map(block, (q.reshape(s // rows, rows, h, nope + rope),
                            jnp.arange(0, s, rows)))
    return mm(a.reshape(s, h * dv), p["o_proj/kernel"])


def layer(sz: dict, i: int, p: dict, x, bias, quant=_ident):
    """One block on (S, hidden); `p` holds the layer's own parameters.
    Returns (y, counts), counts None for a dense layer."""
    def mm(a, b):
        return jnp.matmul(quant(a), quant(b), precision=HIGHEST)

    attention = kda_attention if sz["kinds"][i] == KDA else mla_attention
    h = x + attention(sz, _sub(p, "attention/"),
                      _rms(x, p["input_layernorm/scale"], sz["eps"]), quant)
    u = _rms(h, p["post_attention_layernorm/scale"], sz["eps"])
    if _is_moe(sz, i):
        f, counts = afmoe.expert_ffn(sz, _sub(p, "moe/"), u, bias, quant)
    else:
        f, counts = _swiglu(mm, u, p["gate_proj/kernel"], p["up_proj/kernel"],
                            p["down_proj/kernel"]), None
    return h + f, counts


def hidden_states(sz: dict, params: dict, extra: dict, ids, quant=_ident):
    """(x_L, {layer: counts}) of one sequence of ids, each layer recomputed
    when differentiated."""
    x = params["embed_tokens"][ids]
    counts = {}
    for i in range(len(sz["kinds"])):
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
    offload = (sum(math.prod(shape) for shape in _shapes(sz).values())
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
