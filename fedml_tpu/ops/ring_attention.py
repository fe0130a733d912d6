"""Ring attention: sequence-parallel attention over a mesh axis.

Long-context design for the TPU rebuild (net-new -- the reference's longest
sequence is an 80-char Shakespeare window, SURVEY.md section 5.7): the
sequence dimension shards over a ``seq`` mesh axis. Every device keeps its
own Q shard for the whole computation while K/V shards rotate one hop per
ring step via ``jax.lax.ppermute`` (ICI neighbor traffic only -- no
all-gather, so HBM never holds more than ``T / n_devices`` of K/V). Each
step folds the visiting KV shard into the flash-style online softmax
(:func:`fedml_tpu.ops.attention._online_step` semantics via
``blockwise_attention`` with global position offsets), so the result is
exactly ``softmax(QK^T)V`` for the full sequence.

Communication/compute overlap note: the matmuls of ring step ``s`` and the
ppermute delivering step ``s+1``'s KV are independent; under ``jit`` XLA's
latency-hiding scheduler overlaps them. (An explicit double-buffered
variant -- prefetch the next KV shard while computing on the current one --
is the standard Ring Attention formulation, Liu et al. 2023,
arXiv:2310.01889; see PAPERS.md.)
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from fedml_tpu.ops.attention import (NEG_INF, _finalize, _online_step,
                                     blockwise_attention)

SEQ_AXIS = "seq"


def _ring_body(q, k, v, axis_name, causal, scale, block_size):
    """Runs inside shard_map: local shards ``q/k/v [B, T_local, H, D]``."""
    n_dev = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, Tl, H, D = q.shape
    scale_ = scale if scale is not None else D ** -0.5

    def step(carry, s):
        acc, rsum, rmax, kv = carry
        kcur, vcur = kv
        # the shard visiting us at ring step s started at device my - s
        src = (my - s) % n_dev
        k_off = src * Tl
        # one blockwise pass of the visiting shard, merged via the same
        # online-softmax update the local blocks use
        blk = min(block_size, Tl)
        nb = -(-Tl // blk)
        pad = nb * blk - Tl  # ragged shard: pad, mask the tail below
        if pad:
            kcur_b = jnp.pad(kcur, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vcur_b = jnp.pad(vcur, ((0, 0), (0, pad), (0, 0), (0, 0)))
        else:
            kcur_b, vcur_b = kcur, vcur
        kb = kcur_b.reshape(B, nb, blk, H, D)
        vb = vcur_b.reshape(B, nb, blk, H, D)

        def inner(carry_i, xs):
            kblk, vblk, j = xs
            bias_blk = None
            local = j * blk + jnp.arange(blk)[None, :]  # index within shard
            if causal:
                qpos = my * Tl + jnp.arange(Tl)[:, None]
                kpos = k_off + local
                bias_blk = jnp.where((kpos <= qpos)[None] & (local < Tl),
                                     0.0, NEG_INF)
            elif pad:
                bias_blk = jnp.where(local < Tl, 0.0, NEG_INF)[None]

            def one_b(c, qb, kb_, vb_):
                return _online_step(c, qb, kb_, vb_, scale_, bias_blk)

            new_c = jax.vmap(one_b)(carry_i, q, kblk, vblk)
            return new_c, None

        (acc, rsum, rmax), _ = jax.lax.scan(
            inner, (acc, rsum, rmax),
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0),
             jnp.arange(nb)))
        # rotate KV one hop around the ring (last step's rotate feeds no
        # one, but keeping it unconditional keeps the loop body uniform)
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        kv = (jax.lax.ppermute(kcur, axis_name, perm),
              jax.lax.ppermute(vcur, axis_name, perm))
        return (acc, rsum, rmax, kv), None

    acc0 = jnp.zeros((B, H, Tl, D), jnp.float32)
    sum0 = jnp.zeros((B, H, Tl), jnp.float32)
    max0 = jnp.full((B, H, Tl), NEG_INF, jnp.float32)
    (acc, rsum, _, _), _ = jax.lax.scan(
        step, (acc0, sum0, max0, (k, v)), jnp.arange(n_dev))
    out = jax.vmap(_finalize)(acc, rsum)  # [B, H, Tl, D]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def make_ring_attention(mesh, axis_name: str = SEQ_AXIS,
                        causal: bool = False,
                        scale: Optional[float] = None,
                        block_size: int = 512,
                        batch_axis: Optional[str] = None):
    """Build ``fn(q, k, v) -> out`` with ``[B, T, H, D]`` arrays whose T is
    sharded over ``mesh[axis_name]`` (and, when ``batch_axis`` is given, B
    sharded over that axis too -- dp x sp without gathering the batch).
    The returned fn is jittable and differentiable (JAX transposes the
    ppermutes automatically)."""
    body = partial(_ring_body, axis_name=axis_name, causal=causal,
                   scale=scale, block_size=block_size)
    spec = P(batch_axis, axis_name, None, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)


def ring_attention(q, k, v, mesh, axis_name: str = SEQ_AXIS,
                   causal: bool = False, scale: Optional[float] = None,
                   block_size: int = 512):
    """One-shot convenience wrapper over :func:`make_ring_attention`."""
    return make_ring_attention(mesh, axis_name, causal, scale,
                               block_size)(q, k, v)


__all__ = ["ring_attention", "make_ring_attention", "SEQ_AXIS",
           "blockwise_attention"]
