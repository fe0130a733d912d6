"""Softmax cross-entropy as ONE op with its own backward.

``softmax_cross_entropy_with_stats(logits [..., V], targets [...]) ->
(log_likelihood [...], argmax [...])``: what every classification loss of
``algorithms/specs.py`` needs of its logits -- the target's
log-probability and the first index of the row's maximum (the accuracy
counter) -- without ``jax.nn.log_softmax``'s ``[..., V]`` array of
log-probabilities. At a language model's head (``[2, 2048, 50257]``
float32: 823 MB) that array was written, kept for the backward and read
twice more, beside two reads of the logits, a step.

Forward, a row ``x``: the maximum ``m`` (XLA emits it from the fusion
that makes the logits), then ONE pass: a variadic ``lax.reduce`` over
``(exp(x - m), where(column == target, x, 0), where(x == m, column, V))``
with ``(+, +, min)`` gives ``s``, the target's logit ``x_y`` (no gather
along the vocabulary) and the first index of the maximum together; three
separate reductions compile to three reads (on a TPU v5e 3.3 ms against
2.2 at the GPT-2 head, PERF.md PR 32). ``log_likelihood = (x_y - m) -
log(s)``, the association of ``log_softmax`` followed by a gather.
Residuals: the logits themselves (the head's output, alive anyway), the
row's log-sum-exp and the targets. Backward: ``(onehot(y) - exp(x - lse))
* g`` in plain ``jnp``, so that XLA forms it inside whatever consumes it
(the head's two gradient products, the bias gradient's sum). Integer
targets take no cotangent; the arg-max is not differentiated.

A target outside ``[0, V)`` hits no column: its ``x_y`` reads 0 (callers
mask such rows, as they did around ``take_along_axis``). A row whose
maximum is NaN has no first index (it reads ``V``); its loss is NaN
either way.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _columns(logits):
    return jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                    logits.ndim - 1)


def _row_stats(logits, targets):
    """``(m, s, x_y, first arg-max)`` of every row: the maximum, then the
    other three in one reduction, so one read of the logits."""
    vocab = logits.shape[-1]
    cols = _columns(logits)
    m = jnp.max(logits, axis=-1)
    zero = jnp.zeros((), logits.dtype)
    s, x_y, first = jax.lax.reduce(
        (jnp.exp(logits - m[..., None]),
         jnp.where(cols == targets[..., None], logits, zero),
         jnp.where(logits == m[..., None], cols, vocab)),
        (zero, zero, jnp.int32(vocab)),
        lambda a, b: (a[0] + b[0], a[1] + b[1], jnp.minimum(a[2], b[2])),
        (logits.ndim - 1,))
    return m, s, x_y, first


def _forward(logits, targets):
    targets = targets.astype(jnp.int32)
    m, s, x_y, first = _row_stats(logits, targets)
    log_s = jnp.log(s)
    return ((x_y - m) - log_s, first), (logits, m + log_s, targets)


@jax.custom_vjp
def softmax_cross_entropy_with_stats(logits, targets):
    """``(log p(target), argmax)`` of float ``logits [..., V]`` and integer
    ``targets [...]``; the arg-max takes the first index on ties, as
    ``jnp.argmax``. Works at any rank, under ``vmap`` and inside loops;
    the caller casts the logits to the precision the loss is wanted in."""
    return _forward(logits, targets)[0]


def _backward(residuals, cotangents):
    logits, lse, targets = residuals
    g, _ = cotangents   # the arg-max's is a float0: nothing flows
    onehot = _columns(logits) == targets[..., None]
    return (onehot - jnp.exp(logits - lse[..., None])) * g[..., None], None


softmax_cross_entropy_with_stats.defvjp(_forward, _backward)

__all__ = ["softmax_cross_entropy_with_stats"]
