"""The gated short convolution of an LFM2 ``conv`` layer as one op with
Pallas TPU kernels for its forward and its backward.

``gated_short_conv(bcu, w)``: ``bcu`` ``[n, T, 3 d]`` holds the thirds
``B``, ``C``, ``u`` of the in-projection side by side, ``w`` ``[d, L]`` one
causal filter of ``L`` taps a channel. With ``v = B * u``::

    z[t, c] = sum_k w[c, k] * v[t - (L - 1 - k), c]      (v[s] = 0, s < 0)
    y = C * z                                            -> [n, T, d]

No activation and no bias. The arithmetic is float32 whatever ``bcu``'s
dtype (a v5e's vector unit has no bf16); ``y`` comes back in ``bcu``'s.

**The kernels.** ``short_conv_fwd`` and ``short_conv_bwd`` own one group
of 128 channels (the lanes) and the WHOLE sequence (the sublanes) a grid
step, so no step needs rows of another's block: at T 4,096 a step's blocks
are 1 MiB each. Inside, the body walks the sequence in chunks of 512
rows and loads each chunk with a HALO of 16 rows on the sides it has
neighbours on; a tap is a sublane rotation (``pltpu.roll``) of the chunk,
whose wrapped rows land in the halo and are not stored. Only the first
chunk (no earlier rows: zeros) and, in the backward, the last (no later
rows) mask the wrapped rows. The backward recomputes ``z`` from the
residual ``bcu`` (no second ``[n, T, d]`` array is saved), returns the
three thirds of ``d bcu`` and the filter's gradient as float32 sums over
``n`` and ``T`` (the output block stays resident while ``n`` turns).

A shape whose blocks do not fit :data:`_VMEM_BUDGET` is refused by name:
nothing routes it elsewhere. ``interpret=True`` on the CPU backend only,
as the flash kernels (:mod:`fedml_tpu.ops.pallas_attention`).

Names and outputs are what the benchmark's trace reader finds the kernels
by: ``short_conv_fwd`` -> ``y``; ``short_conv_bwd`` -> ``(dw f32, dB, dC,
du)``, the float32 array FIRST so that the output-signature pattern of
``flash_fwd_roofline`` (``(bf16, f32)``) cannot take it for a flash
forward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.pallas_attention import _up, _use_interpret

_LANES = 128
_ROWS = 512     # rows of a chunk of the body's walk
_HALO = 16      # rows of a packed bf16 tile: what a chunk loads beside it
#: what a kernel's blocks (twice: the pipeline's double buffers) and a
#: chunk's float32 temporaries may take; the backward at T 4,096 takes
#: 15.6 MiB, over the 16 MiB a v5e scopes to a kernel by default (of
#: 128), so the kernels ask for what they count and no more
_VMEM_BUDGET = 40 * 2 ** 20


def _vmem_bytes(arrays, T, itemsize):
    """``arrays`` whole-sequence blocks of 128 lanes, double-buffered,
    and a dozen chunk-sized float32 temporaries."""
    return 2 * arrays * T * _LANES * itemsize \
        + 12 * (_ROWS + 2 * _HALO) * _LANES * 4


def _shift(x, s, edge):
    """``x[t - s]`` along the rows: ``s > 0`` reads earlier rows, ``s <
    0`` later ones. The rows the rotation wraps are zeros where the chunk
    has no neighbour on that side (``edge``); else they lie in the halo."""
    if s == 0:
        return x
    rows = x.shape[0]
    y = pltpu.roll(x, s % rows, 0)
    if not edge:
        return y
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(row >= s if s > 0 else row < rows + s, y, 0.0)


def _chunks(T, body):
    """``body(r0, first, last)`` over the chunks of ``T`` rows: the first
    and the last traced on their own (their edges are static), the ones
    between in one loop."""
    n = pl.cdiv(T, _ROWS)
    body(0, True, n == 1)
    if n > 2:
        jax.lax.fori_loop(
            1, n - 1, lambda i, c: (body(i * _ROWS, False, False), c)[1], 0)
    if n > 1:
        body((n - 1) * _ROWS, False, True)


def _load(refs, r0, first, last, T):
    """Rows ``[r0 - halo, r0 + rows + halo)`` of every ref in float32
    (no halo past an edge), and where the chunk's own rows sit in them."""
    rows = min(_ROWS, T)
    before, after = (0 if first else _HALO), (0 if last else _HALO)
    lo = r0 - before
    if not isinstance(lo, int):
        lo = pl.multiple_of(lo, _HALO)
    size = before + rows + after
    return [r[pl.ds(lo, size), :].astype(jnp.float32) for r in refs], \
        slice(before, before + rows)


def _earlier(v, L, first):
    """``[v[t - (L - 1 - k)] for k in range(L)]``: what tap ``k`` reads."""
    return [_shift(v, L - 1 - k, first) for k in range(L)]


def _taps(shifted, w_ref):
    """``z`` of the module docstring over a chunk's rows."""
    return sum(w_ref[pl.ds(k, 1), :] * x for k, x in enumerate(shifted))


def _fwd_kernel(b_ref, c_ref, u_ref, w_ref, y_ref, *, T, L):
    rows = min(_ROWS, T)

    def chunk(r0, first, last):
        # later rows play no part in a causal filter: no halo behind
        (b, c, u), own = _load((b_ref, c_ref, u_ref), r0, first, True, T)
        y = c * _taps(_earlier(b * u, L, first), w_ref)
        y_ref[pl.ds(r0, rows), :] = y[own].astype(y_ref.dtype)

    _chunks(T, chunk)


def _bwd_kernel(b_ref, c_ref, u_ref, w_ref, g_ref, dw_ref, db_ref, dc_ref,
                du_ref, *, T, L):
    rows = min(_ROWS, T)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    def chunk(r0, first, last):
        (b, c, u, g), own = _load((b_ref, c_ref, u_ref, g_ref), r0, first,
                                  last, T)
        dz, earlier = g * c, _earlier(b * u, L, first)
        # dv[t] = sum_k w[k] dz[t + (L - 1 - k)]: the filter run backwards
        dv = sum(w_ref[pl.ds(k, 1), :] * _shift(dz, -(L - 1 - k), last)
                 for k in range(L))
        at = lambda x: x[own].astype(db_ref.dtype)
        here = pl.ds(r0, rows)
        dc_ref[here, :] = at(g * _taps(earlier, w_ref))
        db_ref[here, :] = at(dv * u)
        du_ref[here, :] = at(dv * b)
        for k, x in enumerate(earlier):
            dw_ref[pl.ds(k, 1), :] += jnp.sum((dz * x)[own], axis=0,
                                              keepdims=True)

    _chunks(T, chunk)


def _specs(T, d):
    whole = lambda index: pl.BlockSpec((None, T, _LANES), index)
    third = lambda i: whole(lambda j, n: (n, 0, i * (d // _LANES) + j))
    own = whole(lambda j, n: (n, 0, j))
    taps = lambda L: pl.BlockSpec((L, _LANES), lambda j, n: (0, j))
    return [third(0), third(1), third(2)], own, taps


def _check(bcu, w, arrays):
    n, T, d3 = bcu.shape
    d, L = w.shape
    if d3 != 3 * d or d % _LANES:
        raise ValueError(
            f"gated_short_conv: bcu {bcu.shape} against w {w.shape}: the "
            f"thirds B, C, u of [n, T, 3 d] in whole groups of {_LANES} "
            "channels")
    if not 1 <= L <= _HALO:
        raise ValueError(f"gated_short_conv: {L} taps (1 to {_HALO})")
    Tp = _up(T, _HALO if T <= _ROWS else _ROWS)
    need = _vmem_bytes(arrays, Tp, bcu.dtype.itemsize)
    if need > _VMEM_BUDGET:
        raise ValueError(
            f"gated_short_conv: T={T} does not fit the kernel's VMEM budget "
            f"({need} > {_VMEM_BUDGET} bytes: {arrays} whole-sequence "
            "blocks of 128 channels, double-buffered)")
    return Tp, need


def _params(need):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=int(need) + 4 * 2 ** 20)


def _pad_rows(x, Tp):
    return x if x.shape[1] == Tp else jnp.pad(
        x, ((0, 0), (0, Tp - x.shape[1]), (0, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward(bcu, w, *, interpret):
    n, T, _ = bcu.shape
    d, L = w.shape
    Tp, need = _check(bcu, w, arrays=4)
    thirds, own, taps = _specs(Tp, d)
    x = _pad_rows(bcu, Tp)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, T=Tp, L=L),
        grid=(d // _LANES, n),
        in_specs=thirds + [taps(L)],
        out_specs=own,
        out_shape=jax.ShapeDtypeStruct((n, Tp, d), bcu.dtype),
        compiler_params=_params(need),
        interpret=interpret,
        name="short_conv_fwd",
    )(x, x, x, w.T.astype(jnp.float32))
    return y[:, :T]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(bcu, w, g, *, interpret):
    n, T, _ = bcu.shape
    d, L = w.shape
    Tp, need = _check(bcu, w, arrays=7)
    thirds, own, taps = _specs(Tp, d)
    x = _pad_rows(bcu, Tp)
    third = jax.ShapeDtypeStruct((n, Tp, d), bcu.dtype)
    # padded rows: g is zero there, so dz is, and nothing reaches dw
    dw, db, dc, du = pl.pallas_call(
        functools.partial(_bwd_kernel, T=Tp, L=L),
        grid=(d // _LANES, n),
        in_specs=thirds + [taps(L), own],
        out_specs=[taps(L), own, own, own],
        out_shape=[jax.ShapeDtypeStruct((L, d), jnp.float32), third, third,
                   third],
        compiler_params=_params(need),
        interpret=interpret,
        name="short_conv_bwd",
    )(x, x, x, w.T.astype(jnp.float32), _pad_rows(g.astype(bcu.dtype), Tp))
    return (jnp.concatenate([db, dc, du], axis=-1)[:, :T],
            dw.T.astype(w.dtype))


@jax.custom_vjp
def gated_short_conv(bcu, w):
    """``[n, T, 3 d]``, ``[d, L]`` -> ``[n, T, d]`` (module docstring)."""
    return _forward(bcu, w, interpret=_use_interpret())


gated_short_conv.defvjp(
    lambda bcu, w: (gated_short_conv(bcu, w), (bcu, w)),
    lambda res, g: _backward(*res, g, interpret=_use_interpret()))

__all__ = ["gated_short_conv"]
