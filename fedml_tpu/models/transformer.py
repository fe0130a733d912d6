"""Decoder-only Transformer LM for federated next-word prediction.

Capability upgrade over the reference's sequence models (2-layer LSTMs over
80-char/20-token windows, ``fedml_api/model/nlp/rnn.py:4-70``): same
task surface (Shakespeare / StackOverflow NWP -- token ids in, next-token
logits out, so it drops into the existing ``TrainSpec`` seams and data
loaders), but attention-based and built on :mod:`fedml_tpu.ops`:

- single-device: fused Pallas flash attention
  (:func:`fedml_tpu.ops.pallas_attention.flash_attention`);
- long-context: pass ``attention_fn=make_ring_attention(mesh, ...)`` to
  shard the sequence over a mesh axis with K/V rotating over ICI
  (:mod:`fedml_tpu.ops.ring_attention`).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.ops.pallas_attention import flash_attention
from fedml_tpu.ops.row_embed import RowEmbed


class _Block(nn.Module):
    """Pre-LN transformer block. ``mlp_factory`` (e.g. a bound
    :class:`fedml_tpu.models.moe.MoEMLP`) swaps the dense MLP for an
    alternative operating on flattened ``[B*T, C]`` tokens -- THE seam
    that keeps exactly one attention implementation across the dense and
    MoE transformers."""
    n_heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.float32
    attention_fn: Optional[Callable] = None
    mlp_factory: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        B, T, C = x.shape
        D = C // self.n_heads
        h = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        qkv = nn.Dense(3 * C, use_bias=False, dtype=self.dtype,
                       name="qkv")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shp = (B, T, self.n_heads, D)
        if self.attention_fn is not None:
            att = self.attention_fn(q.reshape(shp), k.reshape(shp),
                                    v.reshape(shp))
        else:
            att = flash_attention(q.reshape(shp), k.reshape(shp),
                                  v.reshape(shp), True)
        att = att.reshape(B, T, C)
        x = x + nn.Dense(C, use_bias=False, dtype=self.dtype,
                         name="proj")(att)
        h = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        if self.mlp_factory is not None:
            y = self.mlp_factory(name="moe")(h.reshape(B * T, C))
            return x + y.reshape(B, T, C)
        h = nn.gelu(nn.Dense(self.mlp_ratio * C, dtype=self.dtype,
                             name="mlp_up")(h))
        return x + nn.Dense(C, dtype=self.dtype, name="mlp_down")(h)


class TransformerLM(nn.Module):
    """Causal LM over token ids ``[B, T] -> logits [B, T, vocab]``.

    ``attention_fn(q, k, v) -> out`` (all ``[B, T, H, D]``) overrides the
    attention implementation -- plug in
    ``make_ring_attention(mesh, causal=True)`` for sequence parallelism.
    """
    vocab_size: int
    n_layers: int = 4
    n_heads: int = 4
    d_model: int = 256
    max_len: int = 2048
    mlp_ratio: int = 4
    dtype: Any = jnp.float32
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, idx, train: bool = False):
        B, T = idx.shape
        # lookups a plain SGD step may update by rows (ops/row_embed.py);
        # a table read whole (pos_embed at T = max_len) stays dense
        tok = RowEmbed(self.vocab_size, self.d_model, dtype=self.dtype,
                       name="tok_embed")(idx)
        pos = RowEmbed(self.max_len, self.d_model, dtype=self.dtype,
                       name="pos_embed")(jnp.arange(T)[None])
        x = tok + pos
        for i in range(self.n_layers):
            x = _Block(self.n_heads, self.mlp_ratio, self.dtype,
                       self.attention_fn, name=f"block{i}")(x, train)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        return nn.Dense(self.vocab_size, dtype=jnp.float32, name="head")(
            x.astype(jnp.float32))


def lm_loss(logits, tgt):
    """Masked next-token NLL: mean over positions with ``tgt >= 0``.

    THE loss convention shared by every LM training path (sp / tp / pp
    steps, their oracles in tests and the multichip dryrun) -- keep one
    definition so the implementations and their oracles cannot drift.
    """
    lp = jax.nn.log_softmax(logits.astype(jnp.float32))
    mask = (tgt >= 0).astype(jnp.float32)
    nll = -jnp.take_along_axis(
        lp, jnp.maximum(tgt, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def transformer_nwp(vocab_size: int = 10004, **kw):
    """StackOverflow-NWP-shaped config (vocab 10000 + 4 specials, matching
    ``fedml_tpu.data.stackoverflow``)."""
    return TransformerLM(vocab_size=vocab_size, **kw)


__all__ = ["TransformerLM", "transformer_nwp", "lm_loss"]
