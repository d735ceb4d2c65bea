"""Train state pytree: params + optimizer state + model state + step.

A plain ``flax.struct`` pytree (not TrainState from flax.training) so the
whole state threads through ``jit``/``shard_map`` and orbax untouched.

The state carries no layout assumptions: under ZeRO-1 optimizer sharding
(parallel/zero.py) ``opt_state``'s parameter-mirroring leaves are the
chunked global form — each a padded 1-D array of length ``chunk * N``
sharded 1/N over the DP axes — while everything else stays replicated.
:func:`resident_bytes` measures what a tree actually occupies on one
device under either layout.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.struct
import jax
import jax.numpy as jnp


def resident_bytes(tree: Any, device) -> int:
    """Bytes the leaves of ``tree`` occupy on ``device``, counting only the
    shards resident there — a fully replicated leaf contributes its full
    size, a 1/N-sharded leaf contributes 1/N. This is the per-device memory
    number the ZeRO-1 A/B (bench.py, run summaries) compares, and it works
    on every backend including CPU fake devices where allocator peak stats
    are unavailable."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            total += int(getattr(leaf, "nbytes", 0))
            continue
        for sh in shards:
            if sh.device == device:
                total += int(sh.data.nbytes)
    return total


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray                 # scalar int32
    params: Any                       # model parameters (f32)
    opt_state: Any                    # optax state
    batch_stats: Any = None           # the model's state besides parameters:
                                      # BN running stats (CNNs), the routed
                                      # experts' selection biases
                                      # (models/moe.py), or None
    ema_params: Any = None            # EMA shadow params (optimizer.ema_decay
                                      # > 0); evals read these when present
    loss_scale: Any = None            # dynamic loss-scale state
                                      # ({"scale", "good_steps"}) when the
                                      # precision policy arms scaling, else
                                      # None — None keeps the pytree identical
                                      # to pre-policy checkpoints

    @classmethod
    def create(cls, *, params: Any, opt_state: Any,
               batch_stats: Optional[Any] = None,
               ema_params: Optional[Any] = None,
               loss_scale: Optional[Any] = None) -> "TrainState":
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=opt_state, batch_stats=batch_stats,
                   ema_params=ema_params, loss_scale=loss_scale)
