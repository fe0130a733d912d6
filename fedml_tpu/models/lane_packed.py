"""Lane-packed conv models: the MXU-shaped lowering of per-lane convs
(:data:`PACKED_FAMILIES`: the CIFAR ResNets and the FedAvg-paper CNN).

Why this exists (docs/PERFORMANCE.md, round-4 analysis): the packed-lane
engine (``parallel/engine.py`` LaneRunner) trains L independent per-lane
model replicas by ``jax.vmap`` over lane-stacked params. XLA lowers the
lane-batched convolutions as ``feature_group_count=L`` grouped convs with
per-group input channels equal to the MODEL's channel count -- 16/32/64
for ResNet-56/CIFAR -- against the MXU's K-granularity of 128, wasting
8x/4x/2x of every systolic pass (measured 8.9% MFU, ~25-30% shape
ceiling).

This module re-expresses the same L-replica computation with the lane
axis folded into channels *under our control*:

- activations live as ``[B, H, W, L*C]`` (lane-major channels);
- each conv merges ``g = 128 // C_in`` lanes per group into ONE grouped
  conv whose per-group K is ``g*C_in = 128`` (a full MXU tile), with the
  per-lane weights embedded block-diagonally inside each group. The
  extra multiply-adds against the off-diagonal zero blocks are FLOPs the
  MXU was already wasting on underfilled tiles in the grouped form --
  now they ride full tiles with no group loop;
- BatchNorm over merged channels IS per-lane BatchNorm (the reduction
  set per (lane, channel) is identical); the head is a per-lane einsum.

Numerics match ``jax.vmap(model.apply)`` over lane-stacked params to
float reassociation (oracle: ``tests/test_lane_packed.py``); autodiff
extracts per-lane weight grads through the block-diagonal embedding's
transpose (a gather of the diagonal blocks of the dense dW).

No reference analog: the reference trains one client per GPU process
(``FedAVGAggregator.py:58-87``) and never faces batched-weight lowering.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models.cnn import CNNOriginalFedAvg
from fedml_tpu.models.resnet import CifarResNet

_BN_MOMENTUM = 0.9
_BN_EPS = 1e-5
#: MXU lane width: per-group input channels are padded up to this by
#: merging lanes (K granularity of the systolic array).
MXU_K = 128


def lane_merge(x):
    """``[L, B, H, W, C] -> [B, H, W, L*C]`` (lane-major channels)."""
    L, B, H, W, C = x.shape
    return jnp.transpose(x, (1, 2, 3, 0, 4)).reshape(B, H, W, L * C)


def lane_unmerge(x, L):
    """``[B, H, W, L*C] -> [L, B, H, W, C]``."""
    B, H, W, LC = x.shape
    return jnp.transpose(x.reshape(B, H, W, L, LC // L), (3, 0, 1, 2, 4))


def _lanes_per_group(L, ci, min_k=MXU_K):
    """Largest divisor of ``L`` with ``g*ci`` closest to (>= if possible)
    ``min_k``: how many lanes merge into one conv group."""
    g = max(1, min(L, min_k // max(ci, 1)))
    while L % g:
        g -= 1
    return g


#: PROVISIONAL per-conv strategy threshold for ``lowering="auto"``. The
#: corrected r5 shoot-out (``scripts/bench_lane_conv.py``, --inner 200,
#: docs/PERFORMANCE.md, 2026-07-31 on an earlier machine) only measured
#: s1 (Ci=16): bgc wins FORWARD-only there, and fwd+bwd is a tie (bgc
#: 0.259 ms vs blockdiag 0.244 ms). The Ci=32/64 crossover comes from
#: the floor-biased first run PERFORMANCE.md calls misleading; treat
#: this threshold as unverified until the s2/s3 rows are measured
#: (ROADMAP S6).
BGC_MAX_CI = 32


def merged_to_stacked(x, L):
    """``[B, H, W, L*C] -> [L*B, H, W, C]`` (batch-stacked lanes)."""
    B, H, W, LC = x.shape
    return lane_unmerge(x, L).reshape(L * B, H, W, LC // L)


def lane_conv_bgc(x, w, L, strides=(1, 1), padding=((1, 1), (1, 1))):
    """Per-lane conv via ``batch_group_count=L``: ZERO FLOP redundancy.

    ``x``: ``[L*B, H, W, Ci]`` batch-stacked (lane-major batch);
    ``w``: ``[L, kh, kw, Ci, Co]``. Returns **merged** ``[B, H', W',
    L*Co]`` -- XLA's batch-group conv writes feature group ``l`` from
    batch group ``l``, which IS the lane-major merged channel layout the
    rest of the packed pipeline (BN/relu/residual/head) runs on.
    """
    _, kh, kw, ci, co = w.shape
    rhs = jnp.transpose(w, (1, 2, 3, 0, 4)).reshape(kh, kw, ci, L * co)
    return jax.lax.conv_general_dilated(
        x, rhs, window_strides=strides, padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), batch_group_count=L)


def lane_conv(x, w, L, strides=(1, 1), padding=((1, 1), (1, 1)),
              min_k=MXU_K, strategy="blockdiag"):
    """Per-lane conv over merged activations.

    ``x``: ``[B, H, W, L*Ci]`` lane-major; ``w``: ``[L, kh, kw, Ci, Co]``
    per-lane HWIO kernels. Returns ``[B, H', W', L*Co]``.

    ``strategy="blockdiag"``: ``g`` lanes merge per group (``g*Ci ~
    128``); the group's weights are the g x g block-diagonal embedding
    of the lanes' kernels, so the grouped conv computes exactly the
    per-lane convs -- on full MXU K-tiles instead of ``Ci``-wide ones
    (g x redundant FLOPs riding otherwise-idle tiles).

    ``strategy="bgc"``: re-stack lanes into the batch (one transpose)
    and run the zero-redundancy ``batch_group_count`` conv
    (:func:`lane_conv_bgc`) -- measured faster at Ci<=32 where
    block-diag redundancy is 8x/4x (r5 shoot-out).
    """
    _, kh, kw, ci, co = w.shape
    if strategy == "bgc":
        return lane_conv_bgc(merged_to_stacked(x, L), w, L,
                             strides=strides, padding=padding)
    g = _lanes_per_group(L, ci, min_k)
    G = L // g
    wg = w.reshape(G, g, kh, kw, ci, co)
    # wd[j, h, w, l*ci+i, m*co+o] = wg[j, m, h, w, i, o] * (l == m):
    # inputs of lane l contribute only to outputs of lane m == l. The
    # einsum has no contraction -- every output element is one product
    # with 1.0 or 0.0, so the embedding is exact in any dtype.
    eye = jnp.eye(g, dtype=w.dtype)
    wd = jnp.einsum("gmhwio,lm->ghwlimo", wg, eye)
    rhs = (wd.reshape(G, kh, kw, g * ci, g * co)
           .transpose(1, 2, 3, 0, 4)
           .reshape(kh, kw, g * ci, G * g * co))
    return jax.lax.conv_general_dilated(
        x, rhs, window_strides=strides, padding=padding,
        feature_group_count=G,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def lane_bn(x, p, ra, L, train, dtype):
    """Per-lane BatchNorm on merged activations; flax semantics
    (fp32 stats, fast variance, clip-negative, momentum 0.9, eps 1e-5).

    ``p``: ``{"scale","bias"} [L, C]``; ``ra``: ``{"mean","var"} [L, C]``
    running stats. Returns ``(y, new_ra)``.
    """
    scale = p["scale"].reshape(-1)  # [L*C], lane-major like x's channels
    bias = p["bias"].reshape(-1)
    if train:
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=(0, 1, 2))
        mu2 = jnp.mean(xf * xf, axis=(0, 1, 2))
        var = jnp.maximum(0.0, mu2 - mu * mu)
        new_ra = {
            "mean": _BN_MOMENTUM * ra["mean"]
            + (1 - _BN_MOMENTUM) * mu.reshape(ra["mean"].shape),
            "var": _BN_MOMENTUM * ra["var"]
            + (1 - _BN_MOMENTUM) * var.reshape(ra["var"].shape),
        }
    else:
        mu, var = ra["mean"].reshape(-1), ra["var"].reshape(-1)
        new_ra = ra
    # flax _normalize: y = (x - mean) * (rsqrt(var+eps) * scale) + bias
    # in fp32, then cast to the module dtype
    y = (x.astype(jnp.float32) - mu) * (
        jax.lax.rsqrt(var + _BN_EPS) * scale) + bias
    return y.astype(dtype), new_ra


def make_lane_packed_apply(model, L: int, lowering: str = "blockdiag"):
    """Build the packed apply for ``L`` lanes of a supported model.

    Returns ``apply_fn(stacked_vars, x, train) -> (logits, new_stats)``
    where ``stacked_vars`` is ``{"params"[, "batch_stats"]}`` with every
    leaf lane-stacked (leading ``L`` -- the exact layout the LaneRunner
    carries), ``x`` is ``[L, B, ...]``, ``logits`` ``[L, B, classes]``
    and ``new_stats`` is the lane-stacked batch_stats pytree (``{}`` for
    stat-free families).

    ``lowering`` selects the per-lane conv strategy (CifarResNet only):
    ``"blockdiag"`` everywhere, ``"bgc"`` everywhere, or ``"auto"`` --
    per conv by
    input channel count (:data:`BGC_MAX_CI`): batch-group convs for the
    narrow stages (Ci<=32) and the block-diagonal embedding for the wide
    one (Ci=64).

    Supported families: :class:`CifarResNet` (the ResNet-56 flagship)
    and :class:`CNNOriginalFedAvg` (the FedAvg-paper FEMNIST CNN, whose
    1-channel stem underfills the MXU's K dim 128x in the vmap lowering
    -- the merge is worth the most there).
    """
    if isinstance(model, CNNOriginalFedAvg):
        return _make_cnn_apply(model, L)
    if not isinstance(model, CifarResNet):
        raise TypeError(
            f"lane-packed apply supports "
            f"{', '.join(c.__name__ for c in PACKED_FAMILIES)}, "
            f"got {type(model).__name__}")
    if lowering not in ("blockdiag", "bgc", "auto"):
        raise ValueError(f"unknown lane lowering {lowering!r}")
    n = (model.depth - 2) // 6
    dtype = model.dtype

    def apply_fn(stacked_vars, x, train=False):
        p, bs = stacked_vars["params"], stacked_vars["batch_stats"]
        new_bs = {}
        x = lane_merge(x.astype(dtype))

        def conv(name, xin, w, strides=1, padding=1):
            del name
            s = (strides, strides)
            pad = ((padding, padding), (padding, padding))
            ci = w.shape[-2]
            strat = ("bgc" if lowering == "bgc"
                     or (lowering == "auto" and ci <= BGC_MAX_CI)
                     else "blockdiag")
            return lane_conv(xin, w.astype(dtype), L, strides=s, padding=pad,
                             strategy=strat)

        def bn(name, xin):
            y, ra = lane_bn(xin, p[name], bs[name], L, train, dtype)
            new_bs[name] = ra
            return y

        def bn_in(block, name, xin):
            y, ra = lane_bn(xin, p[block][name], bs[block][name], L, train,
                            dtype)
            new_bs.setdefault(block, {})[name] = ra
            return y

        x = conv("conv1", x, p["conv1"]["kernel"])
        x = bn("bn1", x)
        x = jax.nn.relu(x)
        for stage, (_, strides) in enumerate([(16, 1), (32, 2), (64, 2)]):
            for block in range(n):
                name = f"layer{stage + 1}_block{block}"
                blk = p[name]
                s = strides if block == 0 else 1
                residual = x
                y = conv("conv1", x, blk["conv1"]["kernel"], strides=s)
                y = bn_in(name, "bn1", y)
                y = jax.nn.relu(y)
                y = conv("conv2", y, blk["conv2"]["kernel"])
                y = bn_in(name, "bn2", y)
                if "downsample_conv" in blk:
                    residual = conv("downsample", x,
                                    blk["downsample_conv"]["kernel"],
                                    strides=s, padding=0)
                    residual = bn_in(name, "downsample_bn", residual)
                x = jax.nn.relu(y + residual)
        x = jnp.mean(x, axis=(1, 2))  # [B, L*64]
        B = x.shape[0]
        feat = x.reshape(B, L, -1).astype(jnp.float32)
        # per-lane head: fc kernel [L, 64, classes], bias [L, classes]
        logits = (jnp.einsum("blc,lco->lbo", feat,
                             p["fc"]["kernel"].astype(jnp.float32))
                  + p["fc"]["bias"][:, None, :].astype(jnp.float32))
        return logits, new_bs

    return apply_fn


def _make_cnn_apply(model: CNNOriginalFedAvg, L: int):
    """Packed apply for :class:`CNNOriginalFedAvg` (``models/cnn.py``):
    conv5x5(32) + pool + conv5x5(64) + pool + dense512 + head, biased
    convs, no norm layers. The 1-input-channel stem merges ALL lanes
    into one dense conv (per-group K: 25 -> 25L); conv2 merges
    ``128//32 = 4`` lanes (K: 800 -> 3200, whole 128-wide tiles)."""
    dtype = model.dtype

    def apply_fn(stacked_vars, x, train=False):
        del train  # no dropout / batch stats in this family
        p = stacked_vars["params"]
        if x.ndim == 4:  # [L, B, 28, 28] -> add channel dim
            x = x[..., None]
        x = lane_merge(x.astype(dtype))  # [B, 28, 28, L*1]

        def biased_conv(name, xin, padding):
            w = p[name]["kernel"].astype(dtype)
            y = lane_conv(xin, w, L, strides=(1, 1), padding=padding)
            return y + p[name]["bias"].astype(dtype).reshape(-1)

        x = biased_conv("conv1", x, ((2, 2), (2, 2)))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))  # per merged channel
        x = biased_conv("conv2", x, ((2, 2), (2, 2)))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        # per-lane flatten in the reference's (H, W, C) order
        x = lane_unmerge(x, L)  # [L, B, H, W, C]
        x = x.reshape(x.shape[0], x.shape[1], -1)  # [L, B, HWC]
        h = jnp.einsum("lbi,lio->lbo", x,
                       p["fc1"]["kernel"].astype(dtype))
        h = nn.relu(h + p["fc1"]["bias"][:, None, :].astype(dtype))
        logits = (jnp.einsum("lbi,lio->lbo", h.astype(jnp.float32),
                             p["fc2"]["kernel"].astype(jnp.float32))
                  + p["fc2"]["bias"][:, None, :].astype(jnp.float32))
        return logits, {}

    return apply_fn


def make_lane_loss_builder(model, augment_fn=None, lowering="blockdiag"):
    """TrainSpec ``lane_loss_builder`` for classification over any
    :data:`PACKED_FAMILIES` model (see ``core/trainer.py``): called with
    the lane count, returns ``lane_loss_fn(stacked_state, batch,
    step_keys, train) -> (loss_sum, (new_stacked_state,
    per_lane_metrics))`` -- the whole-lane-block loss the packed
    LaneRunner differentiates in one program.

    Per-lane loss/metrics reproduce ``make_classification_spec`` exactly
    (masked mean CE, argmax-correct, count), just batched over the
    leading lane axis; ``loss_sum`` is the sum of per-lane losses, whose
    gradient w.r.t. the lane-stacked params is the per-lane gradients
    (lanes are computationally independent).
    """
    del augment_fn  # augmentation stays in the engine body (per-lane vmap)

    if not isinstance(model, CifarResNet) and lowering != "blockdiag":
        # only the ResNet family dispatches on the conv strategy; letting a
        # non-default request pass silently would label an A/B run "bgc"
        # while measuring blockdiag
        import logging
        logging.warning(
            "lane_lowering=%r is ignored for %s (only CifarResNet "
            "dispatches per-conv strategies); running the default lowering",
            lowering, type(model).__name__)

    def builder(L):
        packed_apply = (make_lane_packed_apply(model, L, lowering)
                        if isinstance(model, CifarResNet)
                        else make_lane_packed_apply(model, L))

        def lane_loss_fn(stacked_state, batch, rng, train):
            del rng  # no PACKED_FAMILIES model uses dropout rngs
            logits, new_bs = packed_apply(stacked_state, batch["x"], train)
            y, mask = batch["y"], batch["mask"]  # [L, B]
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            ll = jnp.take_along_axis(
                logp, y[..., None].astype(jnp.int32), axis=-1)[..., 0]
            per_sample = -ll
            count = jnp.sum(mask, axis=1)  # [L]
            loss_sum_l = jnp.sum(per_sample * mask, axis=1)
            loss_l = loss_sum_l / jnp.maximum(count, 1.0)
            correct = jnp.sum(
                (jnp.argmax(logits, axis=-1) == y) * mask, axis=1)
            metrics = {"loss_sum": loss_sum_l, "correct": correct,
                       "count": count}
            new_state = dict(stacked_state)
            if new_bs:  # stat-free families (the CNN) return {}
                new_state["batch_stats"] = new_bs
            return jnp.sum(loss_l), (new_state, metrics)

        return lane_loss_fn

    return builder


#: model families with a lane-packed lowering -- the ONE list to extend
#: (both the apply dispatch and the spec-facing registry derive from it)
PACKED_FAMILIES = (CifarResNet, CNNOriginalFedAvg)


def builder_for(model, lowering=None):
    """Registry: the packed-lowering ``lane_loss_builder`` for a model
    instance, or None when the family has no lane-packed apply. Spec
    builders call this instead of type-checking models themselves.
    ``lowering`` overrides the conv strategy (default ``"blockdiag"``,
    the lowering behind the measured 114.5 rph flagship number; the r5
    per-layer shoot-out puts ``bgc`` within noise of it, so the default
    only moves on a full-model A/B win). An explicit ``lowering`` for a
    family that does not dispatch on it logs a warning (see
    ``make_lane_loss_builder``) rather than silently mislabeling A/B
    runs."""
    if isinstance(model, PACKED_FAMILIES):
        return make_lane_loss_builder(
            model, lowering=lowering or "blockdiag")
    return None


__all__ = ["lane_merge", "lane_unmerge", "merged_to_stacked", "lane_conv",
           "lane_conv_bgc", "lane_bn", "make_lane_packed_apply",
           "make_lane_loss_builder", "builder_for", "MXU_K", "BGC_MAX_CI"]
