"""Plain reference for GPT-2 (Radford et al. 2019; `openai-community/gpt2`).

Straightforward `jax.numpy` in float32 at matmul precision "highest": learned
token and position embeddings, pre-LayerNorm blocks (causal softmax attention,
tanh-GELU MLP), final LayerNorm, output head tied to the token embedding,
next-token cross entropy averaged over the B*(S-1) predicted tokens. No kernel,
no cache, no batching tricks. It imports nothing of the program under test and
takes nothing the program made: weights, inputs and dropout masks all come
from the seed, through this file.

Dropout. The configuration trains with dropout 0.1 at its three published
sites (embedding, residual, attention probabilities). The program draws the
masks as a pure function of the step's PRNG key, so the reference draws the
same ones from the same key, by the same public rule:

- embedding / residual: flax's `nn.Dropout` rule. The site's key is
  `fold_in(step_key, first 4 bytes of sha1(module path + call counter))`, the
  mask `bernoulli(key, 1 - rate, x.shape)`, kept values scaled by 1/(1-rate).
  Written out here (`site_key`), not imported.
- attention probabilities: a counter hash of (batch*heads + head, query, key)
  and a 32-bit seed drawn from the attention module's key (`attn_keep`),
  the murmur3 finalizer the program documents in `ops/hash_dropout.py`.

`quant` is the hook the lower-precision control uses: it is applied to both
operands of every matrix product. The reference proper passes the identity.

Parameter names are '/'-joined paths, the same as the program's own tree has,
so the runner can put each leaf in its place and refuse a tree that differs.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def sizes(config: dict) -> dict:
    """The reference's sizes, read from the configuration file's own keys."""
    return dict(layers=config["n_layer"], hidden=config["n_embd"],
                heads=config["n_head"], vocab=config["vocab_size"],
                positions=config["n_positions"],
                ffn=config.get("n_inner") or 4 * config["n_embd"],
                eps=config["layer_norm_epsilon"],
                dropout=config["resid_pdrop"])


def init_params(sz: dict, key) -> dict:
    """GPT-2's published initialisation: N(0, 0.02) matrices (0.01 for the
    positions), zero biases, unit LayerNorm scales. One jitted call, on the
    device, from the seed's key."""
    d, f = sz["hidden"], sz["ffn"]
    shapes = {"wte": ((sz["vocab"], d), 0.02),
              "wpe": ((sz["positions"], d), 0.01)}
    for i in range(sz["layers"]):
        p = f"layer{i}/"
        for name in ("query", "key", "value", "output"):
            shapes[p + f"attention/{name}/kernel"] = ((d, d), 0.02)
        shapes[p + "mlp_in/kernel"] = ((d, f), 0.02)
        shapes[p + "mlp_out/kernel"] = ((f, d), 0.02)
    out = {}
    for n, (name, (shape, std)) in enumerate(sorted(shapes.items())):
        out[name] = std * jax.random.normal(jax.random.fold_in(key, n), shape,
                                            jnp.float32)
    for i in range(sz["layers"]):
        p = f"layer{i}/"
        for name in ("query", "key", "value", "output"):
            out[p + f"attention/{name}/bias"] = jnp.zeros((d,), jnp.float32)
        out[p + "mlp_in/bias"] = jnp.zeros((f,), jnp.float32)
        out[p + "mlp_out/bias"] = jnp.zeros((d,), jnp.float32)
        for ln in ("ln1", "ln2"):
            out[p + f"{ln}/scale"] = jnp.ones((d,), jnp.float32)
            out[p + f"{ln}/bias"] = jnp.zeros((d,), jnp.float32)
    out["ln_f/scale"] = jnp.ones((d,), jnp.float32)
    out["ln_f/bias"] = jnp.zeros((d,), jnp.float32)
    return out



def decays(name: str) -> bool:
    """AdamW's weight decay applies to the dense kernels only (not to
    embeddings, biases or LayerNorm), as the configuration's optimizer
    states."""
    return name.endswith("/kernel")


# --------------------------------------------------------------------------
# dropout masks
# --------------------------------------------------------------------------

def site_key(step_key, *path):
    """flax's rule for a module's PRNG key: fold the first four bytes of the
    SHA-1 of the module path and call counter into the stream's key."""
    m = hashlib.sha1()
    for x in path:
        if isinstance(x, str):
            m.update(x.encode("utf-8"))
        else:
            m.update(int(x).to_bytes((int(x).bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(
        step_key, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def _mix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    return h ^ (h >> 16)


def attn_keep(seed_u32, rows_b, heads, pos, rate, row0):
    """(b, heads, s, s) keep mask of attention-probability dropout for batch
    rows row0 .. row0+rows_b-1: murmur3 finalizer over a linear combine of the
    global (batch*heads + head, query, key) coordinates and the seed. `pos`
    is arange(s) as uint32, handed in at run time: built inside the program
    the compiler folds the whole (s, s) hash into constants, hundreds of MB
    of them."""
    u = jnp.uint32
    b = jax.lax.broadcasted_iota(u, (rows_b, heads, 1, 1), 0) + row0
    h = jax.lax.broadcasted_iota(u, (rows_b, heads, 1, 1), 1)
    bh = b * u(heads) + h
    rows = pos[None, None, :, None]
    cols = pos[None, None, None, :]
    x = (rows * u(0x9E3779B9)) ^ (cols * u(0x85EBCA6B)) ^ (bh * u(0xC2B2AE35))
    x = _mix32(x ^ seed_u32)
    return x >= u(min(int(rate * 2.0 ** 32), 2 ** 32 - 1))


def dropout_plan(sz: dict, step_key, batch: int, seq: int) -> dict:
    """Every mask one training step draws, for the whole batch: boolean
    (B, S, hidden) masks for the embedding and the two residual sites of each
    layer, and each layer's 32-bit attention seed."""
    rate = sz["dropout"]
    shape = (batch, seq, sz["hidden"])

    def bern(*path):
        return jax.random.bernoulli(site_key(step_key, *path), 1.0 - rate,
                                    shape)

    plan = {"embd": bern("Dropout_0", 1)}
    for i in range(sz["layers"]):
        plan[f"attn{i}"] = jax.random.bits(
            site_key(step_key, f"layer{i}", "attention", 1), (), jnp.uint32)
        plan[f"resid{i}a"] = bern(f"layer{i}", "Dropout_0", 1)
        plan[f"resid{i}b"] = bern(f"layer{i}", "Dropout_1", 1)
    return plan


def plan_rows(plan: dict, row0: int, rows: int) -> dict:
    return {k: (v if v.ndim == 0 else v[row0:row0 + rows])
            for k, v in plan.items()}


# --------------------------------------------------------------------------
# forward and loss
# --------------------------------------------------------------------------

def _ln(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _ident(x):
    return x


def forward(sz: dict, params: dict, ids, *, plan=None, row0=0,
            quant=_ident, pos=None):
    """(b, s) token ids -> (b, s, vocab) float32 logits. `plan` holds this
    block of rows' dropout masks (None: no dropout, as when serving)."""
    rate = sz["dropout"] if plan is not None else 0.0
    b, s = ids.shape
    nh = sz["heads"]
    hd = sz["hidden"] // nh

    def drop(x, mask):
        if plan is None:
            return x
        return jnp.where(mask, x / (1.0 - rate), 0.0)

    def dense(x, name):
        return jnp.matmul(quant(x), quant(params[name + "/kernel"]),
                          precision=HIGHEST) + params[name + "/bias"]

    if pos is None:
        pos = jnp.arange(s, dtype=jnp.uint32)
    x = params["wte"][ids] + params["wpe"][:s]
    x = drop(x, plan and plan["embd"])
    causal = pos[None, :] <= pos[:, None]
    for i in range(sz["layers"]):
        p = f"layer{i}/"
        h = _ln(x, params[p + "ln1/scale"], params[p + "ln1/bias"], sz["eps"])
        q = dense(h, p + "attention/query").reshape(b, s, nh, hd)
        k = dense(h, p + "attention/key").reshape(b, s, nh, hd)
        v = dense(h, p + "attention/value").reshape(b, s, nh, hd)
        scores = jnp.einsum("bqhd,bkhd->bhqk", quant(q), quant(k),
                            precision=HIGHEST) * hd ** -0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        if plan is not None:
            keep = attn_keep(plan[f"attn{i}"], b, nh, pos, rate, row0)
            probs = jnp.where(keep, probs / (1.0 - rate), 0.0)
        a = jnp.einsum("bhqk,bkhd->bqhd", quant(probs), quant(v),
                       precision=HIGHEST).reshape(b, s, nh * hd)
        x = x + drop(dense(a, p + "attention/output"),
                     plan and plan[f"resid{i}a"])
        h = _ln(x, params[p + "ln2/scale"], params[p + "ln2/bias"], sz["eps"])
        h = jax.nn.gelu(dense(h, p + "mlp_in"), approximate=True)
        x = x + drop(dense(h, p + "mlp_out"), plan and plan[f"resid{i}b"])
    x = _ln(x, params["ln_f/scale"], params["ln_f/bias"], sz["eps"])
    return jnp.einsum("bsh,vh->bsv", quant(x), quant(params["wte"]),
                      precision=HIGHEST)


def loss_sum(sz, params, batch, *, plan=None, row0=0, quant=_ident,
             pos=None):
    """Sum of next-token cross entropies over this block of rows."""
    ids = batch["input_ids"]
    logits = forward(sz, params, ids, plan=plan, row0=row0, quant=quant,
                     pos=pos)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    tgt = ids[:, 1:]
    return -jnp.take_along_axis(logp, tgt[..., None], axis=-1).sum()


def loss_count(batch) -> int:
    """Number of predicted tokens the mean is taken over."""
    b, s = batch["input_ids"].shape
    return b * (s - 1)


def make_batch(traffic: dict, sz: dict, key, step):
    """One training batch from the seed's key and the step number: uniform
    token ids in [1, vocab), every row different (copied from the program's
    `data/synthetic._gen_causal_batch`; the yardstick keeps its own)."""
    k = jax.random.fold_in(key, step)
    b, s = traffic["batch"], traffic["seq_len"]
    ids = jax.random.randint(k, (b, s), 1, sz["vocab"], jnp.int32)
    return {"input_ids": ids, "attention_mask": jnp.ones((b, s), jnp.int32)}


def make_grad_fn(sz: dict, traffic: dict, quant=_ident):
    """fn(params, extra, batch, step_key) -> (mean loss, gradients, extra) for
    one training batch. Rows go through in blocks, so that float32 attention
    probabilities and logits fit beside the weights; the blocks' sums add up
    to the batch's mean loss and its gradient. `extra` is state a model keeps
    besides its parameters (none here)."""
    b, s = traffic["batch"], traffic["seq_len"]
    rows = max(1, min(b, 2048 // s))
    while b % rows:
        rows -= 1
    train = sz["dropout"] > 0

    @jax.jit
    def plan_fn(step_key):
        return dropout_plan(sz, step_key, b, s)

    @jax.jit
    def block(params, ids, plan, row0, pos):
        def f(p):
            return loss_sum(sz, p, {"input_ids": ids}, plan=plan, row0=row0,
                            quant=quant, pos=pos) / (b * (s - 1))
        return jax.value_and_grad(f)(params)

    def fn(params, extra, batch, step_key):
        pos = jnp.arange(s, dtype=jnp.uint32)
        plan = plan_fn(step_key) if train else None
        loss, grads = 0.0, None
        for row0 in range(0, b, rows):
            part = plan_rows(plan, row0, rows) if train else None
            l, g = block(params, batch["input_ids"][row0:row0 + rows], part,
                         jnp.uint32(row0), pos)
            loss = loss + l
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        return loss, grads, extra

    return fn


def init_extra(sz: dict):
    return None
