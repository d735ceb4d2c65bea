"""Plain reference for the bottleneck ResNets, v1.5 (He et al. 2016; stride on
the 3x3 convolution as in torchvision's `resnet50`).

Straightforward `jax.numpy`/`lax` in float32 at precision "highest", NHWC:
7x7/2 stem, 3x3/2 max pool, four stages of 1x1 -> 3x3 -> 1x1 bottlenecks
(expansion 4) with projection shortcuts where shape changes, global average
pool, linear classifier. BatchNorm normalises with the batch's own mean and
(biased) variance over N, H, W, epsilon 1e-5, as in training; the running
averages do not enter a training step's loss, gradient or update, so the
reference keeps none. Loss: cross entropy against labels smoothed by 0.1, mean
over the batch. It imports nothing of the program under test and takes nothing
the program made.

Each bottleneck is rematerialised (`jax.checkpoint`) so that the whole batch,
which BatchNorm needs together, fits the chip in float32.

`quant` is the hook the lower-precision control uses: it is applied to both
operands of every convolution and of the classifier's product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-5


def sizes(config: dict) -> dict:
    return dict(stages=tuple(config["stage_sizes"]), width=config["width"],
                classes=config["num_classes"],
                smoothing=config["train"]["optimizer"].get(
                    "label_smoothing", 0.0))


def _convs(sz: dict):
    """(name, kernel shape) of every convolution, in order."""
    w = sz["width"]
    yield "conv_stem", (7, 7, 3, w)
    cin = w
    for i, n in enumerate(sz["stages"]):
        f = w * 2 ** i
        for j in range(n):
            p = f"stage{i + 1}_block{j + 1}/"
            stride = 2 if i > 0 and j == 0 else 1
            yield p + "conv1", (1, 1, cin, f)
            yield p + "conv2", (3, 3, f, f)
            yield p + "conv3", (1, 1, f, 4 * f)
            if cin != 4 * f or stride != 1:
                yield p + "downsample_conv", (1, 1, cin, 4 * f)
            cin = 4 * f


def init_params(sz: dict, key) -> dict:
    """torchvision's default initialisation: He-normal (fan-out) convolution
    kernels, BatchNorm scales one and biases zero (no zero-initialised last
    scale), classifier N(0, 1/fan_in) with a zero bias."""
    out = {}
    for n, (name, shape) in enumerate(_convs(sz)):
        fan_out = shape[0] * shape[1] * shape[3]
        out[name + "/kernel"] = (2.0 / fan_out) ** 0.5 * jax.random.normal(
            jax.random.fold_in(key, n), shape, jnp.float32)
        bn = name.replace("conv_stem", "bn_stem").replace(
            "downsample_conv", "downsample_bn").replace("/conv", "/bn")
        out[bn + "/scale"] = jnp.ones((shape[3],), jnp.float32)
        out[bn + "/bias"] = jnp.zeros((shape[3],), jnp.float32)
    feat = sz["width"] * 2 ** (len(sz["stages"]) - 1) * 4
    out["classifier/kernel"] = feat ** -0.5 * jax.random.normal(
        jax.random.fold_in(key, 10_000), (feat, sz["classes"]), jnp.float32)
    out["classifier/bias"] = jnp.zeros((sz["classes"],), jnp.float32)
    return out


def decays(name: str) -> bool:
    """Weight decay goes to convolution and classifier kernels, not to
    BatchNorm parameters or biases."""
    return name.endswith("/kernel")


def init_extra(sz: dict):
    return None


def _ident(x):
    return x


def _conv(x, k, stride, pad, quant):
    return jax.lax.conv_general_dilated(
        quant(x), quant(k), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _bn(x, scale, bias):
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + EPS) * scale + bias


def forward(sz: dict, params: dict, image, quant=_ident):
    """(N, H, W, 3) images -> (N, classes) float32 logits, training mode."""
    def conv(x, name, stride=1, pad=0):
        return _conv(x, params[name + "/kernel"], stride, pad, quant)

    def bn(x, name):
        return _bn(x, params[name + "/scale"], params[name + "/bias"])

    x = image.astype(params["conv_stem/kernel"].dtype)
    x = jax.nn.relu(bn(conv(x, "conv_stem", 2, 3), "bn_stem"))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1),
                              ((0, 0), (1, 1), (1, 1), (0, 0)))
    for i, n in enumerate(sz["stages"]):
        for j in range(n):
            p = f"stage{i + 1}_block{j + 1}/"
            stride = 2 if i > 0 and j == 0 else 1

            def block(x, p=p, stride=stride):
                y = jax.nn.relu(bn(conv(x, p + "conv1"), p + "bn1"))
                y = jax.nn.relu(bn(conv(y, p + "conv2", stride, 1),
                                   p + "bn2"))
                y = bn(conv(y, p + "conv3"), p + "bn3")
                if p + "downsample_conv/kernel" in params:
                    x = bn(conv(x, p + "downsample_conv", stride),
                           p + "downsample_bn")
                return jax.nn.relu(y + x)

            x = jax.checkpoint(block)(x)
    x = x.mean((1, 2))
    return jnp.matmul(quant(x), quant(params["classifier/kernel"]),
                      precision=HIGHEST) + params["classifier/bias"]


def loss(sz: dict, params: dict, batch: dict, quant=_ident):
    logits = forward(sz, params, batch["image"], quant)
    c, a = sz["classes"], sz["smoothing"]
    target = jax.nn.one_hot(batch["label"], c) * (1.0 - a) + a / c
    return -(target * jax.nn.log_softmax(logits)).sum(-1).mean()


def make_batch(traffic: dict, sz: dict, key, step):
    """One training batch from the seed's key and the step number: standard
    normal pixels in bfloat16 and uniform labels, every row different (copied
    from the program's `data/synthetic._gen_image_batch`; the yardstick keeps
    its own)."""
    k1, k2 = jax.random.split(jax.random.fold_in(key, step))
    b, size = traffic["batch"], traffic["image_size"]
    return {"image": jax.random.normal(k1, (b, size, size, 3), jnp.bfloat16),
            "label": jax.random.randint(k2, (b,), 0, sz["classes"],
                                        jnp.int32)}


def make_grad_fn(sz: dict, traffic: dict, quant=_ident):
    """fn(params, extra, batch, step_key) -> (loss, gradients, extra). The
    whole batch goes through at once: BatchNorm's statistics are the
    batch's."""
    vg = jax.jit(jax.value_and_grad(
        lambda p, batch: loss(sz, p, batch, quant)))

    def fn(params, extra, batch, step_key):
        del step_key  # no dropout
        value, grads = vg(params, batch)
        return value, grads, extra

    return fn
