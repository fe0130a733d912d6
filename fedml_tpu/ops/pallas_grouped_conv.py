"""Pallas TPU kernel for the backward-dW of per-lane (grouped) convs.

Why (docs/PERFORMANCE.md round 5): packed lanes run 1.56x above the
single-model ceiling, and the measured cost center is the backward
weight gradient of the per-lane convolutions. XLA's dW for the
block-diagonal lowering computes a DENSE ``[kh, kw, g*Ci, G*g*Co]``
gradient and gathers the diagonal blocks -- ``g``x redundant FLOPs in
the one pass where the redundancy is NOT riding otherwise-idle MXU
tiles; the ``batch_group_count`` lowering avoids the redundancy but
lowers dW through a grouped conv whose per-group K is the model's
channel count (16/32/64 for ResNet-56) against the MXU's 128-wide
systolic passes.

This kernel computes the per-lane dW directly as ``kh*kw`` matmuls whose
CONTRACTION axis is the flattened ``batch*H*W`` sample axis -- thousands
long at the flagship shapes, so every systolic pass streams a full
128-deep K block regardless of channel count:

    dW[l, dh, dw, i, o] = sum_{b,h,w} x_pad[l, b, h+dh, w+dw, i]
                                      * dy[l, b, h, w, o]

The grid walks batch blocks of ``_SAMPLES_PER_STEP`` samples (a
reduction into one resident ``[kh*kw, Co, Ci]`` output block); every tap
is a static window of the block. The lane axis rides the same
leading-axis ``vmap`` the flash-attention kernels use (Mosaic turns it
into a leading grid dim). fp32 accumulation via
``preferred_element_type``.

Compiled on the TPU v5e and matched against XLA's dW at ResNet-56's
three stride-1 stage shapes (``chip_smoke.py`` Leg B).

Scope (documented, enforced in code): stride-1 convs only -- ResNet-56
has 4 strided convs out of 57 (stage-boundary + 1x1 downsamples), which
use XLA's dW; dX always stays with XLA (it was never the cost center,
and the conv transpose is already well-lowered). On the CPU backend the
kernel runs in interpret mode so tier-1 pins numerics against the XLA
reference lowering (``tests/test_lane_packed.py``). It is not fast yet:
the one full-model run on the chip (PERF.md, PR 21) took 9x the
default lowering's round time; ROADMAP S6 decides its fate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from fedml_tpu.ops.pallas_attention import _use_interpret


#: samples (``batch * H * W`` positions) contracted per grid step. Sized so
#: the float32 input block -- whose channel dim pads to 128 lanes in VMEM
#: -- stays within a few MB at every ResNet-56 stage shape.
_SAMPLES_PER_STEP = 2048


def _dw_kernel(x_ref, dyt_ref, out_ref, *, kh, kw, h_out, w_out):
    """One batch block's contribution to every tap: ``out[t] += dy^T @
    x_tap`` with the flattened ``[Bb*Ho*Wo]`` sample axis contracted.
    ``dyt_ref`` arrives transposed (``[Co, samples]``, samples in lanes)
    so the product is a plain NN matmul; the tap windows are static
    slices of the padded input block."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = dyt_ref[...]                                    # [Co, N]
    bb, ci = x_ref.shape[0], x_ref.shape[-1]
    for t in range(kh * kw):
        dh, dw = divmod(t, kw)
        xt = x_ref[:, dh:dh + h_out, dw:dw + w_out, :]  # [Bb, Ho, Wo, Ci]
        a = xt.reshape(bb * h_out * w_out, ci).astype(g.dtype)
        out_ref[t] += jax.lax.dot_general(
            g, a, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [Co, Ci]


def _dw_one_lane(x_pad, dy_t, *, kh, kw, h_out, w_out, interpret):
    """``x_pad [B, Hp, Wp, Ci]`` float32, ``dy_t [Co, B*Ho*Wo]`` ->
    ``[kh*kw, Co, Ci]`` float32 (stride 1). The grid walks batch blocks
    (a reduction: the output block stays resident and accumulates)."""
    B, Hp, Wp, Ci = x_pad.shape
    Co = dy_t.shape[0]
    bb = max(1, min(B, _SAMPLES_PER_STEP // (h_out * w_out)))
    while B % bb:
        bb -= 1
    n_blk = bb * h_out * w_out
    kernel = functools.partial(_dw_kernel, kh=kh, kw=kw, h_out=h_out,
                               w_out=w_out)
    return pl.pallas_call(
        kernel,
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, Hp, Wp, Ci), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((Co, n_blk), lambda b: (0, b)),
        ],
        out_specs=pl.BlockSpec((kh * kw, Co, Ci), lambda b: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((kh * kw, Co, Ci), jnp.float32),
        interpret=interpret,
    )(x_pad, dy_t)


def grouped_conv_dw(x_lanes, dy_lanes, kh, kw, padding):
    """Per-lane conv weight gradient (stride 1) as a Pallas kernel.

    ``x_lanes [L, B, H, W, Ci]`` raw (unpadded) inputs, ``dy_lanes
    [L, B, Ho, Wo, Co]`` output cotangents, ``padding``
    ``((pt, pb), (pl, pr))``. Returns ``dW [L, kh, kw, Ci, Co]`` in
    float32 (callers cast to the weight dtype).

    Layout choices made for the TPU compiler (Mosaic): the input block
    is float32 so the tap windows -- sublane slices at offsets 0..kw-1 --
    are 32-bit loads (packed bf16 rows cannot be sliced at odd offsets);
    it is cast back to the cotangent dtype for the MXU. The cotangent is
    transposed here, outside the kernel, to ``[Co, B*Ho*Wo]`` so the
    long sample axis rides the 128 lanes and the kernel needs no
    transposed contraction."""
    (pt, pb), (pl_, pr) = padding
    L, B, Ho, Wo, Co = dy_lanes.shape
    x_pad = jnp.pad(x_lanes.astype(jnp.float32),
                    ((0, 0), (0, 0), (pt, pb), (pl_, pr), (0, 0)))
    dy_t = jnp.transpose(dy_lanes.reshape(L, B * Ho * Wo, Co), (0, 2, 1))
    fn = functools.partial(_dw_one_lane, kh=kh, kw=kw, h_out=Ho, w_out=Wo,
                           interpret=_use_interpret())
    dw = jax.vmap(fn)(x_pad, dy_t)                # [L, kh*kw, Co, Ci]
    Ci = x_lanes.shape[-1]
    return jnp.transpose(dw.reshape(L, kh, kw, Co, Ci), (0, 1, 2, 4, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def lane_conv_pallas(x, w, L, strides, padding):
    """Per-lane conv, ``batch_group_count`` forward + Pallas dW backward.

    Same contract as :func:`fedml_tpu.models.lane_packed.lane_conv_bgc`:
    ``x [L*B, H, W, Ci]`` batch-stacked lane-major, ``w [L, kh, kw, Ci,
    Co]``, returns merged ``[B, H', W', L*Co]``. The forward IS the
    zero-redundancy bgc conv (bitwise, same XLA program); only the
    weight-gradient rule changes -- dX keeps XLA's transpose conv, dW
    goes through :func:`grouped_conv_dw` when ``strides == (1, 1)`` and
    through XLA's dW otherwise (the 4 strided ResNet convs)."""
    from fedml_tpu.models.lane_packed import lane_conv_bgc

    return lane_conv_bgc(x, w, L, strides=strides, padding=padding)


def _lcp_fwd(x, w, L, strides, padding):
    return lane_conv_pallas(x, w, L, strides, padding), (x, w)


def _lcp_bwd(L, strides, padding, res, g):
    from fedml_tpu.models.lane_packed import lane_conv_bgc, lane_unmerge

    x, w = res
    # dX: XLA's conv transpose (never the cost center). The conv is
    # linear in x, so the primal recompute inside vjp is dead code XLA
    # removes -- only the transpose conv remains in the program.
    _, vjp_x = jax.vjp(
        lambda xx: lane_conv_bgc(xx, w, L, strides=strides,
                                 padding=padding), x)
    (dx,) = vjp_x(g)
    _, kh, kw, ci, _ = w.shape
    if strides == (1, 1):
        B = x.shape[0] // L
        x_lanes = x.reshape((L, B) + x.shape[1:])
        dy_lanes = lane_unmerge(g, L)
        dw = grouped_conv_dw(x_lanes, dy_lanes, kh, kw,
                             padding).astype(w.dtype)
    else:
        _, vjp_w = jax.vjp(
            lambda ww: lane_conv_bgc(x, ww, L, strides=strides,
                                     padding=padding), w)
        (dw,) = vjp_w(g)
    return dx, dw


lane_conv_pallas.defvjp(_lcp_fwd, _lcp_bwd)

__all__ = ["lane_conv_pallas", "grouped_conv_dw"]
