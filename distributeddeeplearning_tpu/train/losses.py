"""Loss functions shared by the trainers.

Float32 loss math regardless of compute dtype (logits are emitted f32 by
every model in the zoo) — bf16 softmax/CE is where mixed-precision training
silently loses accuracy, so it stays full precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax


def smoothed_softmax_ce(logits: jnp.ndarray, labels: jnp.ndarray,
                        smoothing: float = 0.1) -> jnp.ndarray:
    """Label-smoothed cross entropy, mean over the batch. (B,C) x (B,) -> ()."""
    num_classes = logits.shape[-1]
    if smoothing:
        one_hot = optax.smooth_labels(
            jnp.eye(num_classes, dtype=jnp.float32)[labels], smoothing)
        loss = optax.softmax_cross_entropy(logits, one_hot)
    else:
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    return loss.mean()


def top1_accuracy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return (jnp.argmax(logits, -1) == labels).astype(jnp.float32).mean()


def mlm_loss_sums(logits: jnp.ndarray, labels: jnp.ndarray):
    """(sum of per-token CE over masked positions, masked-position count).

    ``labels`` is (B, S) int32 with -1 at unmasked positions (the ignore
    index). The sum form aggregates exactly across shards/batches (eval
    perplexity); :func:`mlm_loss` is its mean.
    """
    weights = (labels >= 0).astype(jnp.float32)
    per_tok = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.maximum(labels, 0))
    return (per_tok * weights).sum(), weights.sum()


def mlm_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Masked-LM cross entropy: mean over masked positions, guarded
    against an all-unmasked batch."""
    total, count = mlm_loss_sums(logits, labels)
    return total / jnp.maximum(count, 1.0)


def _cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Per-position softmax cross entropy with integer labels.

    A batch of ONE sequence picks the label's logit by a select over the
    vocabulary, every other batch by the gather that optax makes. The unit
    batch axis is the cause, not a stand-in for one model: with it the TPU
    compiler folds the axis away and differentiates the gather as a scatter
    (no scatter at any batch of two or more), and from some 6000 rows on it
    lays the whole logits array out anew in a ``while`` for it, forward and
    again backward (compiled for a v5e, rows x vocabulary: 5400 x 25024 none,
    6144 x 25024, 8192 x 12512 and 8192 x 50257 two each; 2 x 16384 x 25024
    none). At 8192 x 25024 that is 820 MB each way: 10.7 ms, with 18 ms more
    of zeros, converts and the scatter beside it, where the select fuses
    into the pass that sums the exponentials (measured on the chip). Below
    those rows a batch of one was compiled, not timed. Larger batches keep
    the gather: at 16 x 1024 x 50257 the select measured 2.4 % slower end to
    end, through what else XLA then fused differently (PERF.md, PR 27)."""
    if logits.shape[0] != 1:
        return optax.softmax_cross_entropy_with_integer_labels(logits,
                                                               labels)
    vocab = jnp.arange(logits.shape[-1], dtype=labels.dtype)
    picked = jnp.sum(jnp.where(vocab == labels[..., None], logits, 0.0), -1)
    return jax.nn.logsumexp(logits, axis=-1) - picked


def causal_lm_loss_sums(logits: jnp.ndarray, input_ids: jnp.ndarray,
                        attention_mask: jnp.ndarray | None = None):
    """(sum of next-token CE, predicted-token count): logits[:, t] predicts
    input_ids[:, t+1].

    Both sides of the shift must be real tokens: a padded *query* position
    produces a garbage (uniform-over-everything) logit row, so its
    prediction must not be scored even when the target is real.
    """
    per_tok = _cross_entropy(logits[:, :-1], input_ids[:, 1:])
    if attention_mask is None:
        weights = jnp.ones(per_tok.shape, jnp.float32)
    else:
        mask = attention_mask.astype(jnp.float32)
        weights = mask[:, :-1] * mask[:, 1:]
    return (per_tok * weights).sum(), weights.sum()


def causal_lm_loss(logits: jnp.ndarray, input_ids: jnp.ndarray,
                   attention_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Next-token cross entropy, mean over predicted tokens."""
    total, count = causal_lm_loss_sums(logits, input_ids, attention_mask)
    return total / jnp.maximum(count, 1.0)
