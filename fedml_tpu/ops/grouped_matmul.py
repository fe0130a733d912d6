"""Grouped matrix product over the experts a chip holds, without drops.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G]) -> [M, N]``:
the rows of ``lhs`` are sorted by group (group ``g`` owns the
``group_sizes[g]`` rows after those of the groups before it) and each row
is multiplied by its own group's matrix. ``sum(group_sizes)`` may be
anything up to ``M``: the rows past it belong to no group and come out as
zeros, forward and backward, so a caller sizes ``M`` for the worst
imbalance and pays only for the rows that are there (the kernels' grids
are as long as the groups' tiles, not as ``M``).

The products are the Pallas TPU kernels that ship with JAX
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` and its transposed
sibling ``tgmm``), called from three jitted functions of this module so
that each reads by its own name in a device trace: ``moe_gmm_fwd`` (the
forward product), ``moe_gmm_dlhs`` (the gradient to the rows, the same
kernel over the transposed matrices) and ``moe_gmm_drhs`` (the gradient
to the matrices, one ``[K, N]`` block a group). Group sizes ride as a
scalar-prefetch operand; under ``jax.vmap`` (the client-update program's
lane axis) Pallas batches such a call as a loop over the lanes, one
kernel launch a lane, each over that lane's own groups.

``interpret=True`` on the CPU backend only, as for the flash kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as _megablox_ops

from fedml_tpu.ops.pallas_attention import _use_interpret

_gmm = _megablox_ops.backend.gmm.__wrapped__
_tgmm = _megablox_ops.backend.tgmm.__wrapped__


def _up(n, m):
    return -(-n // m) * m


def _tiling(m, k, n):
    """(rows, contraction, columns) of a kernel tile. Rows: 128, the MXU's
    height, so that a group of a couple of hundred rows wastes little of
    its last tile (a smaller ``m`` takes one tile of whole sublanes).
    Contraction and columns: whole where they fit a tile of 1024 (2048 x
    768 is cut 1024 x 768). On the chip this read 3.83 ms for one expert
    layer's nine products at 3,072 rows; 128 x 1024 x 768 for all three
    kernels, 128 x 2048 x 768, 256 x 1024 x 768, 128 x 512 x 768 and 512
    x 1024 x 768 read 3.99-4.55 ms (PERF.md, PR 27)."""
    tm = 128 if m >= 128 else _up(m, 8)
    return tm, min(k, 1024), min(n, 1024)


def _zero_tail(out, group_sizes):
    """Rows that belong to no group were never written by the kernel."""
    row = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
    return jnp.where(row < jnp.sum(group_sizes), out, jnp.zeros_like(out))


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def moe_gmm_fwd(lhs, rhs, group_sizes, tiling, interpret):
    return _gmm(lhs, rhs, group_sizes, lhs.dtype, tiling,
                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def moe_gmm_dlhs(grad, rhs, group_sizes, tiling, interpret):
    return _gmm(grad, rhs, group_sizes, grad.dtype, tiling,
                transpose_rhs=True, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def moe_gmm_drhs(lhs, grad, group_sizes, tiling, interpret):
    return _tgmm(lhs.swapaxes(0, 1), grad, group_sizes, lhs.dtype, tiling,
                 interpret=interpret)


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """``[M, K] x [G, K, N] -> [M, N]`` by groups of sorted rows (module
    docstring)."""
    return _fwd(lhs, rhs, group_sizes)[0]


def _padded(x, m):
    return jnp.pad(x, ((0, m - x.shape[0]), (0, 0))) if m > x.shape[0] else x


def _fwd(lhs, rhs, group_sizes):
    m, k = lhs.shape
    n = rhs.shape[2]
    tiles = _tiling(m, k, n)
    group_sizes = group_sizes.astype(jnp.int32)
    mp = _up(m, tiles[0])
    out = moe_gmm_fwd(_padded(lhs, mp), rhs, group_sizes, tiles,
                      _use_interpret())
    return _zero_tail(out, group_sizes)[:m], (lhs, rhs, group_sizes)


def _bwd(res, grad):
    lhs, rhs, group_sizes = res
    m, k = lhs.shape
    n = rhs.shape[2]
    interpret = _use_interpret()
    tiles = _tiling(m, k, n)
    mp = _up(m, tiles[0])
    grad = _padded(grad.astype(lhs.dtype), mp)
    # the same kernel over the transposed matrices: n is contracted
    dlhs = moe_gmm_dlhs(grad, rhs, group_sizes, _tiling(m, n, k), interpret)
    # rows of no group lie outside every group's tiles: never read
    drhs = moe_gmm_drhs(_padded(lhs, mp), grad, group_sizes, tiles,
                        interpret)
    return (_zero_tail(dlhs, group_sizes)[:m], drhs.astype(rhs.dtype), None)


grouped_matmul.defvjp(_fwd, _bwd)

__all__ = ["grouped_matmul"]
