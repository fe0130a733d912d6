"""A decoder read from a configuration: the ``deepseek_v3``, ``sdar_moe``
and ``lfm2_moe`` families.

Token ids ``[B, T]`` -> logits ``[B, T, vocab]`` (the surface
:func:`fedml_tpu.algorithms.specs.make_seq_classification_spec` takes),
built from a configuration dict with the key names of the family's public
``config.json`` (:class:`DecoderConfig`). What the block is made of:

- RMSNorm before each sublayer and before the untied head, no bias
  anywhere;
- ``deepseek_v3``: :class:`LatentAttention` (MLA without a query
  bottleneck): keys and values come out of one shared latent of
  ``kv_lora_rank`` columns, a rotary part of ``qk_rope_head_dim`` columns
  rides beside the position-free ``qk_nope_head_dim`` ones (one rotary
  key head shared by every query head, interleaved pairs), so scores are
  ``qk_nope + qk_rope`` wide and values ``v_head_dim``: the flash kernels
  take the two widths apart (:mod:`fedml_tpu.ops.pallas_attention`);
- ``sdar_moe``: :class:`GroupedQueryAttention`: ``num_attention_heads``
  query heads over ``num_key_value_heads`` key/value heads of an explicit
  ``head_dim`` (query head ``h`` reads key/value head ``h // group``),
  RMSNorm on every head's q and k, rotate-half rotary positions;
- ``lfm2_moe``: the token mixer is a property of the LAYER
  (``layer_types``, one entry a layer as run): ``full_attention`` is
  :class:`GroupedQueryAttention`, ``conv`` is :class:`GatedShortConv`
  (an in-projection to the thirds ``B``, ``C``, ``u``, a depthwise
  causal convolution of ``conv_L_cache`` taps over ``B * u`` gated by
  ``C``, an out-projection: :mod:`fedml_tpu.ops.short_conv`); the other
  families have no list and every layer takes the family's attention;
- a gated (SwiGLU) MLP in the first ``first_k_dense_replace`` layers and
  :class:`RoutedExperts` in the others (``sdar_moe``: in every layer).

**Block diffusion** (``block_length`` set; grouped-query attention only:
the latent one computes the causal mask alone). The ids
are then ``[x_0 ; x_t]``, a clean copy and a noised copy of ``T / 2``
positions each: position ``i`` has rotary position ``i mod T/2``,
attention runs under :class:`~fedml_tpu.ops.pallas_attention.BlockDiffusion`
(a clean row sees the clean blocks up to its own, a noised row the clean
blocks before its own and the noised keys of its own), and the head runs
on the noised half alone: logits ``[B, T / 2, vocab]``, the ones AT a
position predicting that position's clean token
(:func:`fedml_tpu.algorithms.specs.make_block_diffusion_lm_spec`).

:class:`RoutedExperts` is what expert parallelism asks of a chip: it is
told which experts it holds (``experts_held = (first, count)``) and how
many the router has, routes every token over all of them (``sigmoid``
scores, the choice by score plus ``e_score_correction_bias``, the weights
by score alone, renormalised and scaled; or ``softmax`` scores over all
the router's experts and no bias), and computes its own experts'
part of the result for the tokens routed to them: assignments sorted by
expert, one grouped product a projection over stacked ``[count, d,
width]`` leaves (:mod:`fedml_tpu.ops.grouped_matmul`), gathered back by
the inverse permutation and summed by weight. No token is dropped
whatever the imbalance: the sorted buffer has a row for every
assignment to a held expert. A layer that holds less than half of the
router sizes that buffer for twice what an even router sends it
(:func:`buffer_capacity`: ``C`` rows, gathered from the tokens and
summed back to them by :func:`_take_rows` / :func:`_sum_rows`: the first
run of the sorted order); when any lane of the chunk holds more, the
layer runs the whole ``tokens x top-k`` order instead, run by run of
``C`` rows through the same function: one ``lax.cond`` a layer, on a
flag reduced over the lane axis
(:func:`fedml_tpu.parallel.mesh.any_lane`), so that one branch runs for
all lanes. What absent experts would add is left out,
and nothing here stands in for other chips. The shared expert, where the
family has one, runs on every token.

Counters of the routing are sown into the ``metrics`` collection, one
value a layer and step (``fedml_tpu.observability.routing`` makes the
round's series of them; ``moe_overflow`` counts the layer-steps that
took the whole buffer, ``moe_capacity_rows`` the compact buffer's rows);
a decoder with ``layer_types`` also sows the
positions each kind of mixer ran (``conv_layer_positions``,
``attn_layer_positions``).
"""

from __future__ import annotations

import dataclasses
import functools
import json
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.ops.grouped_matmul import grouped_matmul
from fedml_tpu.ops.pallas_attention import BlockDiffusion, flash_attention
from fedml_tpu.ops.row_embed import RowEmbed
from fedml_tpu.ops.short_conv import gated_short_conv
from fedml_tpu.parallel.mesh import any_lane

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """The families' ``config.json`` keys this decoder reads, under their
    published names (``deepseek_v3``'s; ``from_dict`` maps ``sdar_moe``'s
    and ``lfm2_moe``'s onto them, and is the one place that knows a
    family by name: the modules below read features). ``layer_types``
    (``lfm2_moe``) names each layer's token mixer over the depth AS RUN,
    ``conv`` or ``full_attention``; ``None`` means the family's attention
    in every layer. ``attention`` (``latent`` or
    ``grouped``, from which keys the file has), ``router_experts`` and
    ``experts_held`` are this repo's: the router's width where
    ``n_routed_experts`` counts only the experts held (a benchmark
    configuration's cut), and which they are; ``block_length`` is block
    diffusion's (module docstring)."""
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    attention: str = "latent"
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    kv_lora_rank: int = 0
    num_key_value_heads: Optional[int] = None
    head_dim: Optional[int] = None
    intermediate_size: int = 0
    n_shared_experts: int = 0
    first_k_dense_replace: int = 1
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    norm_topk_eps: float = 0.0
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    router_experts: Optional[int] = None
    experts_held: Optional[Tuple[int, int]] = None
    block_length: Optional[int] = None
    layer_types: Optional[Tuple[str, ...]] = None
    conv_L_cache: int = 0

    #: key -> the values this decoder computes, by family
    _COMPUTED = {
        "deepseek_v3": {
            "q_lora_rank": (None,), "rope_scaling": (None,),
            "rope_interleave": (True,), "moe_layer_freq": (1,),
            "n_group": (1,), "topk_group": (1,),
            "scoring_func": ("sigmoid",), "topk_method": ("noaux_tc",),
            "hidden_act": ("silu",), "attention_bias": (False,),
            "tie_word_embeddings": (False,), "block_length": (None,)},
        "sdar_moe": {
            "rope_scaling": (None,), "decoder_sparse_step": (1,),
            "mlp_only_layers": ([], ()), "use_sliding_window": (False,),
            "sliding_window": (None,), "layer_types": (None,),
            "hidden_act": ("silu",), "attention_bias": (False,),
            "tie_word_embeddings": (False,)},
        "lfm2_moe": {
            "conv_bias": (False,), "use_expert_bias": (True,),
            "rope_scaling": (None,), "block_length": (None,),
            "hidden_act": ("silu",), "attention_bias": (False,),
            "tie_word_embeddings": (False,)}}

    _NEEDED = {
        "deepseek_v3": ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                        "kv_lora_rank", "intermediate_size",
                        "n_shared_experts"),
        "sdar_moe": ("head_dim", "num_key_value_heads", "num_experts"),
        "lfm2_moe": ("layer_types", "conv_L_cache", "num_dense_layers",
                     "num_key_value_heads", "num_experts", "norm_eps",
                     "intermediate_size")}

    #: a layer's token mixer, by its entry in ``layer_types``
    MIXERS = ("conv", "full_attention")

    @classmethod
    def from_dict(cls, cfg, **overrides):
        """Refuses what this decoder does not compute, so that a file of
        another member of a family is an error and not another model."""
        cfg = {**cfg, **overrides}
        family = cfg.get("model_type", "deepseek_v3")
        if family not in cls._COMPUTED:
            raise NotImplementedError(
                f"decoder: model_type={family!r} is not computed here "
                f"(only {sorted(cls._COMPUTED)})")
        for key, allowed in cls._COMPUTED[family].items():
            if key in cfg and cfg[key] not in allowed:
                raise NotImplementedError(
                    f"{family} decoder: {key}={cfg[key]!r} is not "
                    f"computed here (only {allowed[0]!r})")
        missing = [k for k in cls._NEEDED[family] if k not in cfg]
        if missing:
            raise ValueError(f"{family} decoder: the configuration lacks "
                             f"{missing}")
        if family == "sdar_moe":
            # every layer routed, softmax over the router's num_experts,
            # no bias, no shared expert, no scaling
            cfg.setdefault("router_experts", cfg["num_experts"])
            cfg.setdefault("n_routed_experts", cfg["num_experts"])
            cfg.update(first_k_dense_replace=0, n_shared_experts=0,
                       scoring_func="softmax", routed_scaling_factor=1.0)
        if family == "lfm2_moe":
            # the router RoutedExperts has (sigmoid, a choice-only bias,
            # renormalised over sum + 1e-6, scaled), dense FFN in the
            # leading num_dense_layers, no shared expert, heads of
            # hidden / heads where the file gives no head_dim
            cfg.setdefault("router_experts", cfg["num_experts"])
            cfg.setdefault("n_routed_experts", cfg["num_experts"])
            cfg.setdefault("head_dim", cfg["hidden_size"]
                           // cfg["num_attention_heads"])
            cfg.setdefault("norm_topk_eps", 1e-6)
            cfg.update(first_k_dense_replace=cfg["num_dense_layers"],
                       n_shared_experts=0, scoring_func="sigmoid",
                       rms_norm_eps=cfg["norm_eps"],
                       # the depth as run, where a file cuts it
                       layer_types=cfg.get("layer_types_as_run",
                                           cfg["layer_types"]))
        cfg["attention"] = "latent" if "kv_lora_rank" in cfg else "grouped"
        if "n_layer" in cfg:  # the depth as run, where a file cuts it
            cfg["num_hidden_layers"] = cfg["n_layer"]
        if cfg.get("layer_types") is not None:
            types = cfg["layer_types"] = tuple(cfg["layer_types"])
            unknown = sorted(set(types) - set(cls.MIXERS))
            if unknown:
                raise NotImplementedError(
                    f"{family} decoder: layer_types {unknown} are not "
                    f"computed here (only {list(cls.MIXERS)})")
            if len(types) != cfg["num_hidden_layers"]:
                raise ValueError(
                    f"{family} decoder: {len(types)} layer_types for "
                    f"{cfg['num_hidden_layers']} layers as run")
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
    def __call__(self, x, mask=True, positions=None):
        c = self.cfg
        if mask is not True:
            raise NotImplementedError(
                "LatentAttention computes the causal mask alone (its rotary "
                f"turn takes no positions): got {mask!r}")
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


def rotary_half(x, positions, theta):
    """Rotary positions over the last axis of ``[B, T, H, D]`` in the
    rotate-half layout (column ``i`` pairs with ``i + D/2``), row ``t`` at
    position ``positions[t]``, computed in float32."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq      # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


class GroupedQueryAttention(nn.Module):
    """``num_attention_heads`` query heads of ``head_dim`` over
    ``num_key_value_heads`` key/value heads, RMSNorm on each head's q and
    k before the rotary turn. The flash kernels read key/value head ``h
    // group`` for query head ``h`` themselves and sum a group's
    gradient back (:func:`fedml_tpu.ops.pallas_attention.flash_attention`):
    no repeated copy of keys or values is made."""
    cfg: DecoderConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, mask, positions):
        c = self.cfg
        B, T, _ = x.shape
        H, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        head_norm = lambda name: nn.RMSNorm(epsilon=c.rms_norm_eps,
                                            dtype=self.dtype, name=name)
        q = _dense(H * D, self.dtype, "q_proj")(x).reshape(B, T, H, D)
        k = _dense(KV * D, self.dtype, "k_proj")(x).reshape(B, T, KV, D)
        v = _dense(KV * D, self.dtype, "v_proj")(x).reshape(B, T, KV, D)
        q = rotary_half(head_norm("q_norm")(q), positions, c.rope_theta)
        k = rotary_half(head_norm("k_norm")(k), positions, c.rope_theta)
        with jax.named_scope("bd_attn" if isinstance(mask, BlockDiffusion)
                             else "attn"):
            att = flash_attention(q, k, v, mask, D ** -0.5)
        return _dense(x.shape[-1], self.dtype, "o_proj")(
            att.reshape(B, T, H * D))


class GatedShortConv(nn.Module):
    """LFM2's ``conv`` mixer: ``[B ; C ; u] = x W_in`` (thirds in that
    order), a depthwise causal convolution of ``conv_L_cache`` taps over
    ``B * u`` gated by ``C`` (:func:`gated_short_conv`: one op, float32
    arithmetic), ``W_out``. No bias, no activation, no positions (the
    mask and the rotary positions of the attention layers are not its)."""
    cfg: DecoderConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, mask=True, positions=None):
        if mask is not True:
            raise NotImplementedError(
                f"GatedShortConv is causal and nothing else: got {mask!r}")
        d = x.shape[-1]
        bcu = _dense(3 * d, self.dtype, "in_proj")(x)
        # a depthwise filter's fan-in is its taps, not the channels beside
        # it: the scale the benchmark's seeded weights take too
        taps = self.cfg.conv_L_cache
        w = self.param("conv_kernel",
                       nn.initializers.normal(taps ** -0.5), (d, taps))
        return _dense(d, self.dtype, "out_proj")(gated_short_conv(bcu, w))


@jax.custom_vjp
def _take_permuted(x, perm, inverse):
    """``x[perm]`` for a permutation of the rows; the gradient is a gather
    by the inverse, where the transpose of a plain gather would be a
    scatter-add."""
    return x[perm]


_take_permuted.defvjp(
    lambda x, perm, inverse: (x[perm], (perm, inverse)),
    lambda res, g: (g[res[1]], None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _take_rows(x, idx, rows):
    """``x[idx]``: rows of a sorted buffer read from the ``rows ==
    len(x)`` rows they are copies of. The gradient is :func:`_sum_rows`
    of the cotangent."""
    return x[idx]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sum_rows(z, idx, rows):
    """``out[m] = sum of z[p] over idx[p] == m`` for ``m < rows``, float32
    in and out: one scatter-add of the buffer's rows into rows of zeros
    (the form that shipped: ``scripts/moe_probe.py --dispatch`` times it
    against a gather a slot summed over the slots, which writes a
    ``tokens x top-k``-row array, and against ``top-k`` scatters at
    unique indices; PERF.md, PR 35). The transpose of :func:`_take_rows`,
    and the other way round."""
    return jnp.zeros((rows,) + z.shape[1:], z.dtype).at[idx].add(z)


_take_rows.defvjp(
    lambda x, idx, rows: (x[idx], idx),
    lambda rows, idx, g: (_sum_rows(g.astype(jnp.float32), idx,
                                    rows).astype(g.dtype), None))
_sum_rows.defvjp(
    lambda z, idx, rows: (_sum_rows(z, idx, rows), idx),
    lambda rows, idx, g: (_take_rows(g, idx, rows), None))


@jax.custom_vjp
def _fenced(x):
    """``x``; its cotangent passes an optimization barrier, so that what
    reads it stays where it is written (XLA otherwise moves the cast of a
    conditional's result into both of its branches, where it cannot fuse
    with the optimizer step that reads it)."""
    return x


_fenced.defvjp(lambda x: (x, None),
               lambda _, g: (jax.lax.optimization_barrier(g),))


@jax.jit
def _experts_on(slot, sizes, x, weight, w_gate, w_up, w_down):
    """The held experts' part of the result from one run ``slot`` of the
    sorted order (assignment ``token * top_k + choice`` each, ``sizes``
    rows an expert, the rows past them of no group): read from their
    tokens, through the experts, scaled by their assignments' weights and
    summed back to the tokens, in float32. Jitted, so that the first run,
    the loop over every run and its recomputation share one trace and
    one derivative: the grouped products are traced at ONE number of
    rows."""
    N, k = weight.shape
    token = slot // k
    xs = _take_rows(x, token, N)
    h = nn.silu(grouped_matmul(xs, w_gate, sizes)) \
        * grouped_matmul(xs, w_up, sizes)
    ys = grouped_matmul(h, w_down, sizes)                 # [len(slot), d]
    ws = _take_rows(weight.reshape(-1), slot, N * k)
    ys = ys * ws[:, None].astype(ys.dtype)
    return _sum_rows(ys.astype(jnp.float32), token, N)


def _run_sizes(ends, run, rows):
    """Rows an expert in run ``run`` of ``rows`` rows of a sorted order
    whose groups end at ``ends``."""
    return jnp.diff(jnp.clip(ends - run * rows, 0, rows), prepend=0)


def _first_run(active, slots, sizes, *operands):
    """The first run of the sorted order: held assignments sort first, so
    it is all of them whenever there are no more than its rows."""
    return _experts_on(slots[0], _run_sizes(jnp.cumsum(sizes), 0,
                                            slots.shape[1]), *operands)


def _every_run(active, slots, sizes, *operands):
    """The whole ``tokens x top-k`` sorted order, run by run: whatever
    the routing, every assignment to a held expert has its row. A run in
    which no lane of the chunk has a row (``active [runs]``) is stepped
    over: it would add nothing."""
    runs, rows = slots.shape
    ends = jnp.cumsum(sizes)

    def step(acc, run):
        return jax.lax.cond(
            active[run],
            lambda acc: acc + _experts_on(
                slots[run], _run_sizes(ends, run, rows), *operands),
            lambda acc: acc, acc), None

    acc, _ = jax.lax.scan(step, jnp.zeros(operands[0].shape, jnp.float32),
                          jnp.arange(runs))
    return acc


@jax.jit
def _held_experts(active, slots, sizes, x, weight, w_gate, w_up, w_down):
    """The first run of the sorted order ``slots [runs, rows]``, or, where
    a second one is ``active``, every active one: one conditional, whose
    fallback keeps only its inputs for the backward pass, so that the
    step that does not take it writes no residual for it. Jitted, so that
    a decoder's layers share one trace, one derivative and one lowering
    of all of it."""
    return jax.lax.cond(active[1], jax.checkpoint(_every_run), _first_run,
                        active, slots, sizes, x, weight, w_gate, w_up,
                        w_down)


#: the compact sorted buffer has whole blocks of this many rows
_CAPACITY_BLOCK = 512


def buffer_capacity(assignments, count, experts):
    """Rows of the sorted buffer of a layer that holds ``count`` of the
    router's ``experts``: twice what an even router sends here, in whole
    blocks, and never more than every assignment (which is what a layer
    that holds half the router or more gets)."""
    block = _CAPACITY_BLOCK
    return min(assignments,
               -(-2 * assignments * count // (experts * block)) * block)


def _sum_metric(module, name, value):
    module.sow("metrics", name, jnp.asarray(value, jnp.float32),
               reduce_fn=jnp.add, init_fn=lambda: jnp.float32(0.0))


class RoutedExperts(nn.Module):
    """The held experts' part of a routed-expert layer plus the shared
    expert where there is one, over flattened tokens ``[N, d] -> [N, d]``
    (module docstring)."""
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
        sigmoid = c.scoring_func == "sigmoid"
        if sigmoid:
            bias = self.param("e_score_correction_bias",
                              nn.initializers.zeros, (E,))

        with jax.named_scope("moe_route"):
            logits = nn.Dense(
                E, use_bias=False, dtype=jnp.float32, precision=_HI,
                name="router")(x.astype(jnp.float32))             # [N, E]
            if sigmoid:
                scores = jax.nn.sigmoid(logits)
                # the bias steers the choice only: the weights are the
                # scores
                _, chosen = jax.lax.top_k(
                    scores + jax.lax.stop_gradient(bias), k)      # [N, k]
            else:
                scores = jax.nn.softmax(logits, axis=-1)
                _, chosen = jax.lax.top_k(scores, k)
            weight = jnp.take_along_axis(scores, chosen, axis=-1)
            if c.norm_topk_prob:
                total = jnp.sum(weight, axis=-1, keepdims=True)
                if c.norm_topk_eps:
                    total = total + c.norm_topk_eps
                weight = weight / total
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

        cast = lambda w: w.astype(self.dtype)
        cap = buffer_capacity(N * k, count, E)
        overflow = False
        with jax.named_scope("moe_gmm"):
            if cap == N * k:
                # a row for every assignment: whatever the routing, it fits
                xs = _take_permuted(jnp.repeat(x, k, axis=0), order, inverse)
                h = nn.silu(grouped_matmul(xs, cast(w_gate), group_sizes)) \
                    * grouped_matmul(xs, cast(w_up), group_sizes)
                ys = grouped_matmul(h, cast(w_down), group_sizes)  # [N*k, d]
                y = _take_permuted(ys, inverse, order).reshape(N, k, d)
                routed = jnp.sum(
                    y * weight[..., None].astype(y.dtype), axis=1)
            else:
                # the sorted order in runs of ``cap`` rows (the last one
                # padded with rows of no group): the first run, or, when
                # some lane of the chunk holds more rows than that, every
                # run in which some lane has a row, for all lanes at once. The leaves go in as the
                # products take them, so that their gradients come out of
                # the conditional as small
                runs = -(-N * k // cap)
                slots = jnp.pad(order, (0, runs * cap - N * k))
                active = any_lane(
                    jnp.sum(group_sizes) > jnp.arange(runs) * cap)
                overflow = active[1]
                routed = _held_experts(
                    active, slots.reshape(runs, cap), group_sizes, x,
                    weight, *(_fenced(cast(w))
                              for w in (w_gate, w_up, w_down))
                ).astype(x.dtype)

        if c.n_shared_experts:
            with jax.named_scope("moe_shared"):
                routed = routed + GatedMLP(c.n_shared_experts * width,
                                           self.dtype, name="shared")(x)

        rows = jnp.sum(group_sizes)
        _sum_metric(self, "moe_rows_held", rows)
        _sum_metric(self, "moe_load_max", jnp.max(group_sizes))
        _sum_metric(self, "moe_load_mean", rows / count)
        _sum_metric(self, "moe_dropped", jnp.sum(held) - rows)
        _sum_metric(self, "moe_overflow", overflow)
        _sum_metric(self, "moe_capacity_rows", cap)
        return routed


#: a layer's token mixer -> the module, its scope in a trace and its name
#: in the parameter tree (its norm is ``<name>_norm``); ``latent`` and
#: ``grouped`` are ``DecoderConfig.attention``, what ``full_attention``
#: (and every layer of a configuration without ``layer_types``) means
_MIXERS = {"latent": (LatentAttention, "mla", "attn"),
           "grouped": (GroupedQueryAttention, "gqa", "attn"),
           "conv": (GatedShortConv, "short_conv", "conv")}


class _DecoderLayer(nn.Module):
    cfg: DecoderConfig
    dense: bool
    dtype: Any = jnp.float32
    mixer: str = "full_attention"

    @nn.compact
    def __call__(self, x, mask=True, positions=None):
        c = self.cfg
        B, T, d = x.shape
        norm = lambda name: nn.RMSNorm(epsilon=c.rms_norm_eps,
                                       dtype=self.dtype, name=name)
        mixer, scope, name = _MIXERS[
            c.attention if self.mixer == "full_attention" else self.mixer]
        with jax.named_scope(scope):
            x = x + mixer(c, self.dtype, name=name)(
                norm(name + "_norm")(x), mask, positions)
        if c.layer_types:
            # which mix of layers a step ran: positions through this mixer
            _sum_metric(self, f"{name}_layer_positions", B * T)
        h = norm("ffn_norm")(x)
        if self.dense:
            return x + GatedMLP(c.intermediate_size, self.dtype,
                                name="mlp")(h)
        return x + RoutedExperts(c, self.dtype, name="moe")(
            h.reshape(B * T, d)).reshape(B, T, d)


class DecoderLM(nn.Module):
    """LM ``[B, T] -> [B, T, vocab]`` from a :class:`DecoderConfig`,
    causal; with ``block_length`` set, ``[x_0 ; x_t] -> [B, T / 2,
    vocab]`` under the block-diffusion mask (module docstring).
    Parameters float32, compute in ``dtype``, the head's logits float32."""
    cfg: DecoderConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, idx, train: bool = False):
        c = self.cfg
        T = idx.shape[1]
        mask, positions, keep = True, jnp.arange(T), 0
        if c.block_length:
            if T % (2 * c.block_length):
                raise ValueError(
                    "block diffusion: ids [x_0 ; x_t] in whole blocks of "
                    f"{c.block_length} (got {T} ids)")
            keep = T // 2
            mask, positions = BlockDiffusion(keep, c.block_length), \
                positions % keep
        x = RowEmbed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                     name="tok_embed")(idx)   # ops/row_embed.py
        types = c.layer_types or ("full_attention",) * c.num_hidden_layers
        for i, mixer in enumerate(types):
            x = _DecoderLayer(c, i < c.first_k_dense_replace, self.dtype,
                              mixer, name=f"layer{i}")(x, mask, positions)
        x = nn.RMSNorm(epsilon=c.rms_norm_eps, dtype=self.dtype,
                       name="norm_f")(x[:, keep:])
        return nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="head")(x.astype(jnp.float32))


DeepseekV3LM = DecoderLM    # the name the first family's callers know


__all__ = ["DecoderConfig", "DecoderLM", "DeepseekV3LM", "LatentAttention",
           "GroupedQueryAttention", "GatedShortConv", "RoutedExperts",
           "GatedMLP",
           "load_config", "rotary_interleaved", "rotary_half"]
