"""A decoder read from a configuration: the ``deepseek_v3`` family.

Token ids ``[B, T]`` -> next-token logits ``[B, T, vocab]`` (the surface
:func:`fedml_tpu.algorithms.specs.make_seq_classification_spec` takes),
built from a configuration dict with the key names of the family's public
``config.json`` (:class:`DecoderConfig`). What the block is made of:

- RMSNorm before each sublayer and before the untied head, no bias
  anywhere;
- :class:`LatentAttention` (MLA without a query bottleneck): keys and
  values come out of one shared latent of ``kv_lora_rank`` columns, a
  rotary part of ``qk_rope_head_dim`` columns rides beside the
  position-free ``qk_nope_head_dim`` ones (one rotary key head shared by
  every query head, interleaved pairs), so scores are
  ``qk_nope + qk_rope`` wide and values ``v_head_dim``: the flash kernels
  take the two widths apart (:mod:`fedml_tpu.ops.pallas_attention`);
- a gated (SwiGLU) MLP in the first ``first_k_dense_replace`` layers and
  :class:`RoutedExperts` in the others.

:class:`RoutedExperts` is what expert parallelism asks of a chip: it is
told which experts it holds (``experts_held = (first, count)``) and how
many the router has, routes every token over all of them (sigmoid
scores, the choice by score plus ``e_score_correction_bias``, the weights
by score alone, renormalised and scaled), and computes its own experts'
part of the result for the tokens routed to them: assignments sorted by
expert, one grouped product a projection over stacked ``[count, d,
width]`` leaves (:mod:`fedml_tpu.ops.grouped_matmul`), gathered back by
the inverse permutation and summed by weight. No token is dropped
whatever the imbalance: the sorted buffer has a row for every
assignment. What absent experts would add is left out, and nothing here
stands in for other chips. The shared expert runs on every token.

Counters of the routing are sown into the ``metrics`` collection, one
value a layer and step (``fedml_tpu.observability.routing`` makes the
round's series of them).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.ops.grouped_matmul import grouped_matmul
from fedml_tpu.ops.pallas_attention import flash_attention

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """The family's ``config.json`` keys this decoder reads, under their
    published names. ``router_experts`` and ``experts_held`` are this
    repo's: the router's width where ``n_routed_experts`` counts only the
    experts held (a benchmark configuration's cut), and which they are."""
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    router_experts: Optional[int] = None
    experts_held: Optional[Tuple[int, int]] = None

    @classmethod
    def from_dict(cls, cfg, **overrides):
        """Refuses what this decoder does not compute, so that a file of
        another member of the family is an error and not another model."""
        cfg = {**cfg, **overrides}
        computed = {
            "q_lora_rank": (None,), "rope_scaling": (None,),
            "rope_interleave": (True,), "moe_layer_freq": (1,),
            "n_group": (1,), "topk_group": (1,),
            "scoring_func": ("sigmoid",), "topk_method": ("noaux_tc",),
            "hidden_act": ("silu",), "attention_bias": (False,),
            "tie_word_embeddings": (False,)}
        for key, allowed in computed.items():
            if key in cfg and cfg[key] not in allowed:
                raise NotImplementedError(
                    f"deepseek_v3 decoder: {key}={cfg[key]!r} is not "
                    f"computed here (only {allowed[0]!r})")
        if "n_layer" in cfg:  # the depth as run, where a file cuts it
            cfg["num_hidden_layers"] = cfg["n_layer"]
        if cfg.get("experts_held") is not None:
            cfg["experts_held"] = tuple(int(v) for v in cfg["experts_held"])
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in cfg.items() if k in names})

    @property
    def router_width(self):
        return self.router_experts or self.n_routed_experts

    @property
    def held(self):
        return self.experts_held or (0, self.n_routed_experts)


def load_config(path, **overrides):
    with open(path, encoding="utf-8") as f:
        return DecoderConfig.from_dict(json.load(f), **overrides)


def rotary_interleaved(x, theta):
    """Rotary positions over the last axis of ``[B, T, ..., D]`` in
    interleaved pairs ``(x[2i], x[2i+1])``, computed in float32."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq   # [T, D/2]
    ang = ang.reshape((1, t) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _dense(features, dtype, name):
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


class GatedMLP(nn.Module):
    """``down(silu(gate x) * up x)``."""
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = nn.silu(_dense(self.width, self.dtype, "gate_proj")(x)) \
            * _dense(self.width, self.dtype, "up_proj")(x)
        return _dense(x.shape[-1], self.dtype, "down_proj")(h)


class LatentAttention(nn.Module):
    cfg: DecoderConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        B, T, _ = x.shape
        H, nope, rope = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim)
        q = _dense(H * (nope + rope), self.dtype, "q_proj")(x)
        q = q.reshape(B, T, H, nope + rope)
        kv_a = _dense(c.kv_lora_rank + rope, self.dtype, "kv_a_proj")(x)
        c_kv = nn.RMSNorm(epsilon=c.rms_norm_eps, dtype=self.dtype,
                          name="kv_a_norm")(kv_a[..., :c.kv_lora_rank])
        kv = _dense(H * (nope + c.v_head_dim), self.dtype, "kv_b_proj")(c_kv)
        kv = kv.reshape(B, T, H, nope + c.v_head_dim)
        # one rotary key head, shared by every query head
        k_rope = rotary_interleaved(kv_a[..., None, c.kv_lora_rank:],
                                    c.rope_theta)
        q = jnp.concatenate(
            [q[..., :nope], rotary_interleaved(q[..., nope:], c.rope_theta)],
            axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (B, T, H, rope))],
            axis=-1)
        att = flash_attention(q, k, kv[..., nope:], True,
                              (nope + rope) ** -0.5)
        return _dense(x.shape[-1], self.dtype, "o_proj")(
            att.reshape(B, T, H * c.v_head_dim))


@jax.custom_vjp
def _take_permuted(x, perm, inverse):
    """``x[perm]`` for a permutation of the rows; the gradient is a gather
    by the inverse, where the transpose of a plain gather would be a
    scatter-add."""
    return x[perm]


_take_permuted.defvjp(
    lambda x, perm, inverse: (x[perm], (perm, inverse)),
    lambda res, g: (g[res[1]], None, None))


def _sum_metric(module, name, value):
    module.sow("metrics", name, jnp.asarray(value, jnp.float32),
               reduce_fn=jnp.add, init_fn=lambda: jnp.float32(0.0))


class RoutedExperts(nn.Module):
    """The held experts' part of a routed-expert layer plus the shared
    expert, over flattened tokens ``[N, d] -> [N, d]`` (module docstring).
    """
    cfg: DecoderConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        N, d = x.shape
        E, k, width = (c.router_width, c.num_experts_per_tok,
                       c.moe_intermediate_size)
        first, count = c.held
        if not 0 <= first <= first + count <= E:
            raise ValueError(f"experts_held {c.held} outside the router's "
                             f"{E} experts")
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w_gate = self.param("w_gate", init, (count, d, width))
        w_up = self.param("w_up", init, (count, d, width))
        w_down = self.param("w_down", init, (count, width, d))
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (E,))

        with jax.named_scope("moe_route"):
            scores = jax.nn.sigmoid(nn.Dense(
                E, use_bias=False, dtype=jnp.float32, precision=_HI,
                name="router")(x.astype(jnp.float32)))            # [N, E]
            # the bias steers the choice only: the weights are the scores
            _, chosen = jax.lax.top_k(
                scores + jax.lax.stop_gradient(bias), k)          # [N, k]
            weight = jnp.take_along_axis(scores, chosen, axis=-1)
            if c.norm_topk_prob:
                weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
            weight = weight * c.routed_scaling_factor
            # every assignment gets a row, sorted by held expert; the
            # assignments to experts held elsewhere sort behind them all
            local = chosen.reshape(-1) - first
            held = (local >= 0) & (local < count)
            key = jnp.where(held, local, count)
            order = jnp.argsort(key, stable=True)
            inverse = jnp.argsort(order)
            group_sizes = jnp.sum(
                key[:, None] == jnp.arange(count)[None, :], axis=0,
                dtype=jnp.int32)

        with jax.named_scope("moe_gmm"):
            xs = _take_permuted(jnp.repeat(x, k, axis=0), order, inverse)
            cast = lambda w: w.astype(self.dtype)
            h = nn.silu(grouped_matmul(xs, cast(w_gate), group_sizes)) \
                * grouped_matmul(xs, cast(w_up), group_sizes)
            ys = grouped_matmul(h, cast(w_down), group_sizes)     # [N*k, d]
            y = _take_permuted(ys, inverse, order).reshape(N, k, d)
            routed = jnp.sum(
                y * weight[..., None].astype(y.dtype), axis=1)

        with jax.named_scope("moe_shared"):
            shared = GatedMLP(c.n_shared_experts * width, self.dtype,
                              name="shared")(x)

        rows = jnp.sum(group_sizes)
        _sum_metric(self, "moe_rows_held", rows)
        _sum_metric(self, "moe_load_max", jnp.max(group_sizes))
        _sum_metric(self, "moe_load_mean", rows / count)
        _sum_metric(self, "moe_dropped", jnp.sum(held) - rows)
        return routed + shared


class _DecoderLayer(nn.Module):
    cfg: DecoderConfig
    dense: bool
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        B, T, d = x.shape
        norm = lambda name: nn.RMSNorm(epsilon=c.rms_norm_eps,
                                       dtype=self.dtype, name=name)
        with jax.named_scope("mla"):
            x = x + LatentAttention(c, self.dtype, name="attn")(
                norm("attn_norm")(x))
        h = norm("ffn_norm")(x)
        if self.dense:
            return x + GatedMLP(c.intermediate_size, self.dtype,
                                name="mlp")(h)
        return x + RoutedExperts(c, self.dtype, name="moe")(
            h.reshape(B * T, d)).reshape(B, T, d)


class DeepseekV3LM(nn.Module):
    """Causal LM ``[B, T] -> [B, T, vocab]`` from a :class:`DecoderConfig`;
    parameters float32, compute in ``dtype``, the head's logits float32."""
    cfg: DecoderConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, idx, train: bool = False):
        c = self.cfg
        x = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                     name="tok_embed")(idx)
        for i in range(c.num_hidden_layers):
            x = _DecoderLayer(c, i < c.first_k_dense_replace, self.dtype,
                              name=f"layer{i}")(x)
        x = nn.RMSNorm(epsilon=c.rms_norm_eps, dtype=self.dtype,
                       name="norm_f")(x)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="head")(x.astype(jnp.float32))


__all__ = ["DecoderConfig", "DeepseekV3LM", "LatentAttention",
           "RoutedExperts", "GatedMLP", "load_config",
           "rotary_interleaved"]
