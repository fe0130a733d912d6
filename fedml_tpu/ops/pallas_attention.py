"""Flash attention (forward + backward) as fused Pallas TPU kernels.

The hot op for long-context transformer workloads. Forward: one kernel
instance computes a ``[BLOCK_Q, D]`` output tile by streaming KV blocks
through VMEM with the online-softmax recurrence -- scores never touch HBM --
and emits the per-row logsumexp. Backward: two kernels re-form the
probabilities from the saved logsumexp (no second online pass needed) and
accumulate ``dq`` (query-tile outer loop) and ``dk``/``dv`` (KV-tile outer
loop), the standard flash-attention backward decomposition. All matmuls hit
the MXU in the input dtype (bf16-friendly) with fp32 accumulation
(``preferred_element_type``); softmax state lives in fp32 VMEM scratch.

``interpret=True`` is used on the CPU backend only, so the same code
paths test on CPU against the materializing oracle (``tests/test_ops.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.attention import NEG_INF

# lse/delta ride as [T, LANES] lane-replicated fp32 (the fp32 VMEM tile is
# (8, 128); a [T, 1] operand would fight the layout) -- column 0 is the
# value. Lane replication in HBM costs 128x on a per-row scalar; it is the
# same layout the upstream TPU flash kernel uses for its l/m outputs
# (jax/experimental/pallas/ops/tpu/flash_attention.py: NUM_LANES-wide l/m),
# trading HBM for never relayouting sublanes<->lanes inside the kernel.
_LANES = 128


def _use_interpret() -> bool:
    """Pallas interpret mode is for the CPU backend and nothing else (the
    CPU tests pin numerics against the materializing oracle); any other
    backend compiles the kernel or raises."""
    return jax.default_backend() == "cpu"


def _mask(s, *, qi, kj, block_q, block_k, seq_len, causal):
    """NEG_INF-mask invalid scores: zero-padded keys always, upper triangle
    when causal. Static no-op when nothing can be invalid."""
    ragged = seq_len % block_k != 0
    if not (causal or ragged):
        return s
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = kpos < seq_len
    if causal:
        valid = valid & (kpos <= qpos)
    return jnp.where(valid, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k, seq_len):
    qi = pl.program_id(0)   # query tile
    kj = pl.program_id(1)   # kv tile (innermost grid dim)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[:]                      # [block_q, D]
        k = k_ref[:]                      # [block_k, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        s = _mask(s, qi=qi, kj=kj, block_q=block_q, block_k=block_k,
                  seq_len=seq_len, causal=causal)

        m_prev = m_ref[:, :1]             # [bq, 1]
        blk_max = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, blk_max)
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, D]
        acc_ref[:] = acc_ref[:] * corr + pv
        m_keep = jnp.where(m_new <= NEG_INF / 2, m_prev, m_new)
        m_ref[:] = jnp.broadcast_to(m_keep, m_ref.shape)

    if causal:
        # skip KV tiles strictly above the diagonal band
        pl.when(kj * block_k <= qi * block_q + (block_q - 1))(_body)
    else:
        _body()

    @pl.when(kj == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[:] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # fully-masked rows (l == 0): any finite lse works -- the backward
        # re-masks scores to NEG_INF, so exp(s - lse) is 0 regardless
        lse = jnp.where(l > 0, m_ref[:, :1] + jnp.log(jnp.maximum(l, 1e-30)),
                        0.0)
        lse_ref[:] = jnp.broadcast_to(lse, lse_ref.shape)


def _fwd_one_head(q, k, v, *, scale, causal, block_q, block_k, k_len,
                  interpret):
    Tq, D = q.shape          # the score width (q and k)
    Tk, Dv = v.shape         # the value width (v and o)
    grid = (pl.cdiv(Tq, block_q), pl.cdiv(Tk, block_k))
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_len=k_len)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, D), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, D), lambda i, j: (j, 0)),
            pl.BlockSpec((block_k, Dv), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, Dv), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, _LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tq, Dv), q.dtype),
            jax.ShapeDtypeStruct((Tq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


def _probs_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *, qi, kj,
                  scale, causal, block_q, block_k, seq_len):
    """Shared backward re-formation: rebuild ``p = exp(s - lse)`` from the
    saved logsumexp and form ``ds = p * (dO v^T - delta)`` -- the one block
    both backward kernels must compute identically."""
    s = jax.lax.dot_general(
        q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    s = _mask(s, qi=qi, kj=kj, block_q=block_q, block_k=block_k,
              seq_len=seq_len, causal=causal)
    p = jnp.exp(s - lse_ref[:, :1])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    dov = jax.lax.dot_general(
        do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # [bq, bk]
    ds = p * (dov - dl_ref[:, :1])
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
               acc_ref, *, scale, causal, block_q, block_k, seq_len):
    """Query-tile outer loop: accumulate ``dq = sum_k ds @ k * scale``."""
    qi = pl.program_id(0)
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _body():
        _, ds = _probs_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                              qi=qi, kj=kj, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k,
                              seq_len=seq_len)
        acc_ref[:] += scale * jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(kj * block_k <= qi * block_q + (block_q - 1))(_body)
    else:
        _body()

    @pl.when(kj == pl.num_programs(1) - 1)
    def _finalize():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, scale, causal, block_q, block_k,
                seq_len):
    """KV-tile outer loop: ``dv = sum_q p^T @ dO``, ``dk = sum_q ds^T @ q``."""
    kj = pl.program_id(0)
    qi = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _body():
        p, ds = _probs_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                              qi=qi, kj=kj, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k,
                              seq_len=seq_len)
        # dv += p^T dO : contract over the q rows
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += scale * jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # tiles entirely above the diagonal contribute nothing
        pl.when(qi * block_q + (block_q - 1) >= kj * block_k)(_body)
    else:
        _body()

    @pl.when(qi == pl.num_programs(1) - 1)
    def _finalize():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_one_head(q, k, v, do, lse, dl, *, scale, causal, block_q, block_k,
                  k_len, interpret):
    Tq, D = q.shape          # the score width (q, k, dq, dk)
    Tk, Dv = v.shape         # the value width (v, dO, dv)
    nq, nk = pl.cdiv(Tq, block_q), pl.cdiv(Tk, block_k)
    q_spec = pl.BlockSpec((block_q, D), lambda i, j: (i, 0))
    k_spec = pl.BlockSpec((block_k, D), lambda i, j: (j, 0))
    v_spec = pl.BlockSpec((block_k, Dv), lambda i, j: (j, 0))
    do_spec = pl.BlockSpec((block_q, Dv), lambda i, j: (i, 0))
    r_spec = pl.BlockSpec((block_q, _LANES), lambda i, j: (i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=k_len),
        grid=(nq, nk),
        in_specs=[q_spec, k_spec, v_spec, do_spec, r_spec, r_spec],
        out_specs=pl.BlockSpec((block_q, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, dl)
    # kv-outer grid: index maps see (kj, qi)
    qk_spec = pl.BlockSpec((block_q, D), lambda j, i: (i, 0))
    kk_spec = pl.BlockSpec((block_k, D), lambda j, i: (j, 0))
    vk_spec = pl.BlockSpec((block_k, Dv), lambda j, i: (j, 0))
    dok_spec = pl.BlockSpec((block_q, Dv), lambda j, i: (i, 0))
    rk_spec = pl.BlockSpec((block_q, _LANES), lambda j, i: (i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=k_len),
        grid=(nk, nq),
        in_specs=[qk_spec, kk_spec, vk_spec, dok_spec, rk_spec, rk_spec],
        out_specs=[pl.BlockSpec((block_k, D), lambda j, i: (j, 0)),
                   pl.BlockSpec((block_k, Dv), lambda j, i: (j, 0))],
        out_shape=[jax.ShapeDtypeStruct((Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((Tk, Dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, dl)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                    block_k=128):
    """Fused attention: q, k ``[B, T, H, Dqk]``, v ``[B, T, H, Dv]`` ->
    ``[B, T, H, Dv]``.

    Forward and backward are Pallas kernels (per ``(batch, head)`` via a
    double vmap -- each kernel grid covers query x kv tiles). Ragged
    sequence lengths are padded here and masked in-kernel. The score width
    ``Dqk`` may differ from the value width ``Dv`` (latent attention:
    keys wider than values). On hardware ``Dv`` must fill 128-wide tiles;
    a ``Dqk`` that does not is zero-padded to the next multiple of 128
    here (exact: the extra columns add 0 to every score), and ``scale``
    defaults to ``Dqk ** -0.5`` of the width given, never the padded one.
    """
    return _fa_fwd(q, k, v, causal, scale, block_q, block_k)[0]


def _pad_t(x, pad):
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else x


# [B, T, H, D] <-> [B, H, T, D]: self-inverse, used at every kernel boundary
def _swap_th(x):
    return jnp.transpose(x, (0, 2, 1, 3))


def _double_vmap(fn):
    """[B, H, T, ...] operands -> per-(batch, head) kernel calls. Both
    mapped axes are LEADING: on hardware Mosaic turns each vmapped axis
    into a squeezed block dim, and squeezed dims are only legal outside
    the trailing two block dims -- vmapping the middle head axis of a
    [B, T, H, D] array makes the block's last-two dims (Squeezed(H), D),
    which the TPU lowering rejects (r5 hardware run). Callers transpose
    to [B, H, T, D] at the boundary instead."""
    return jax.vmap(jax.vmap(fn))


def _require_hw_head_dim(D, interpret):
    """On real TPU hardware the kernel's lane layout requires the value
    head dim to fill 128-wide tiles; interpret mode (CPU tests) takes any
    D. Fail loudly up front instead of leaving a Mosaic layout error to
    decipher (ADVICE r3)."""
    if not interpret and D % 128:
        raise ValueError(
            f"flash_attention on TPU hardware requires head_dim D to be a "
            f"multiple of 128 (got D={D}); use "
            "fedml_tpu.ops.attention.blockwise_attention for small head "
            "dims (same flash semantics, XLA-scheduled)")


def _score_pad(Dqk, Dv, interpret):
    """Zero columns to add to q and k: none where the score width is the
    value width (which ``_require_hw_head_dim`` has checked) or in
    interpret mode, else up to the next 128-wide tile (192 -> 256)."""
    return 0 if interpret or Dqk == Dv else (-Dqk) % _LANES


def _pad_d(x, pad):
    return jnp.pad(x, ((0, 0),) * 3 + ((0, pad),)) if pad else x


def _block_sizes(block_q, block_k, Tq, Tk):
    """Blocks never exceed the sequence, but stay on the TPU tiling when a
    short sequence clips them: q rows are the sublanes of every tile (16
    for packed bf16), k rows become the LANES of the ``[bq, bk]`` score
    tile (128). ``T=80`` gives ``(80, 128)``; the caller zero-pads K/V to
    the block and the in-kernel key mask covers the pad."""
    up = lambda n, m: -(-n // m) * m
    return min(block_q, up(Tq, 16)), min(block_k, up(Tk, _LANES))


def _fa_fwd(q, k, v, causal, scale, block_q, block_k):
    B, Tq, H, D = q.shape
    Tk, Dv = k.shape[1], v.shape[-1]
    scale_ = scale if scale is not None else D ** -0.5
    interpret = _use_interpret()
    _require_hw_head_dim(Dv, interpret)
    pad_d = _score_pad(D, Dv, interpret)
    bq, bk = _block_sizes(block_q, block_k, Tq, Tk)
    qp = _swap_th(_pad_d(_pad_t(q, (-Tq) % bq), pad_d))
    kp = _swap_th(_pad_d(_pad_t(k, (-Tk) % bk), pad_d))
    vp = _swap_th(_pad_t(v, (-Tk) % bk))
    fn = functools.partial(_fwd_one_head, scale=scale_, causal=causal,
                           block_q=bq, block_k=bk, k_len=Tk,
                           interpret=interpret)
    out, lse = _double_vmap(fn)(qp, kp, vp)
    out = _swap_th(out)[:, :Tq]                       # back to [B,T,H,D]
    lse = jnp.transpose(lse[..., 0], (0, 2, 1))[:, :Tq]      # [B,T,H]
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale_ = scale if scale is not None else D ** -0.5
    interpret = _use_interpret()
    pad_d = _score_pad(D, v.shape[-1], interpret)
    bq, bk = _block_sizes(block_q, block_k, Tq, Tk)
    pad_q, pad_k = (-Tq) % bq, (-Tk) % bk
    # delta_i = dO_i . O_i (the -sum_j ds_ij term of the softmax backward)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    rep = lambda x: jnp.broadcast_to(  # [B, T, H] -> lane-replicated
        x[..., None], x.shape + (_LANES,))
    qp = _swap_th(_pad_d(_pad_t(q, pad_q), pad_d))
    dop = _swap_th(_pad_t(g.astype(q.dtype), pad_q))
    kp = _swap_th(_pad_d(_pad_t(k, pad_k), pad_d))
    vp = _swap_th(_pad_t(v, pad_k))
    # padded q rows: dO rows are zero => ds rows are zero => no dk/dv
    # contribution; their dq rows are sliced off below
    lse_p = _swap_th(_pad_t(rep(lse), pad_q))
    dl_p = _swap_th(_pad_t(rep(delta), pad_q))
    fn = functools.partial(_bwd_one_head, scale=scale_, causal=causal,
                           block_q=bq, block_k=bk, k_len=Tk,
                           interpret=interpret)
    dq, dk, dv = _double_vmap(fn)(qp, kp, vp, dop, lse_p, dl_p)
    # the padded score columns' gradients are sliced off with the padded rows
    return (_swap_th(dq)[:, :Tq, :, :D], _swap_th(dk)[:, :Tk, :, :D],
            _swap_th(dv)[:, :Tk])


flash_attention.defvjp(_fa_fwd, _fa_bwd)

__all__ = ["flash_attention"]
