"""Flash attention (forward + backward) as fused Pallas TPU kernels.

The hot op for long-context transformer workloads. Three kernels, the
standard flash decomposition: ``flash_fwd`` streams keys and values past a
tile of queries with the online-softmax recurrence (scores never touch
HBM) and emits the per-row logsumexp; ``flash_bwd_dq`` and
``flash_bwd_dkv`` re-form the probabilities from the saved logsumexp and
accumulate ``dq`` (a tile of queries, keys streamed) and ``dk``/``dv`` (a
tile of keys, queries streamed). All matmuls hit the MXU in the input
dtype (bf16-friendly) with fp32 accumulation (``preferred_element_type``);
softmax state, ``exp``, logsumexp and delta are fp32.

**What one grid step holds, and why.** A Pallas grid step has a fixed
price (block copies issued and awaited, semaphores, the ``pl.when`` tests)
of 0.4-0.5 us on a v5e, whatever the step computes; a 128 x 128 score tile
is 0.04-0.15 us of MXU time, so a 16 x 16 grid of such tiles spent
four to nine tenths of its time on steps and not on arithmetic (PERF.md,
PR 30). So a step owns a tile of hundreds of rows (:class:`Tile` ``rows``)
and a MAJOR block of the streamed sequence (``major``: at T 2048 all of
it, so the grid has no inner axis left and K/V of a head are fetched
once), and loops over MINOR blocks of it (``minor``) inside the body. The
loop's bounds come from the causal band: blocks above it are never
visited, blocks wholly under it run a body with no mask, and only the
blocks the diagonal crosses (or that hold padded keys) build the iotas.
Where the major block does not cover the sequence the grid keeps its
inner axis; a step the band does not reach runs no pass of the loop AND
names the block already resident (clamped index maps), so nothing is
fetched for it. :func:`flash_schedule` chooses the tiles from the shape.

**Mask kinds.** ``causal`` is ``False`` (none), ``True`` (the lower
triangle) or a :class:`BlockDiffusion` ``(length, block)``: block-diffusion
training's mask over keys and queries laid out ``[x_0 ; x_t]`` (a clean
copy and a noised copy of ``length`` positions each, in blocks of
``block``). A clean row sees the clean keys of its own and earlier
blocks; a noised row sees the clean keys of STRICTLY earlier blocks and
the noised keys of its own block; nothing else. That is a quarter of the
``(2 length)^2`` pairs, so the band of a tile is TWO ranges of minor
blocks here (:func:`_bd_spans`): for a noised query tile the clean keys
before its first block (the block the boundary crosses is masked in the
body, as the causal diagonal is) and its own noised blocks; for a clean
key tile of ``flash_bwd_dkv`` the clean rows from its block on and the
noised rows of later blocks. Blocks outside the ranges are not visited,
and where the grid keeps its inner axis they are not fetched either.

``flash_bwd_dkv`` works on the TRANSPOSED score tile (keys on sublanes,
queries on lanes): ``dv += p^T dO`` and ``dk += ds^T q`` are then plain
products, no score-sized transpose, and logsumexp/delta are rows.

``interpret=True`` is used on the CPU backend only, so the same code
paths test on CPU against the materializing oracle (``tests/test_ops.py``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.attention import NEG_INF

# Per-row softmax state inside the kernels (running max, running sum, the
# logsumexp and delta columns of the dq kernel) is [rows, LANES]
# lane-replicated fp32: the fp32 VMEM tile is (8, 128), and a replicated
# column meets a [rows, n * 128] score tile by ``jnp.tile``, which moves
# nothing (the layout the upstream TPU flash kernel keeps its l/m in).
# In HBM logsumexp and delta are compact [B, H, 1, T] ROWS: a kernel turns
# a row into a replicated column (or back) with one 32-bit transpose a
# query tile, so no 128-fold array is written or re-read (PR 30).
_LANES = 128
_SUBLANES = 16      # rows of a packed bf16 tile

#: what a kernel's blocks (twice: the pipeline's double buffers),
#: accumulators and live score tiles may take together: inside the 16 MiB
#: of VMEM a v5e scopes to a kernel by default (of 128 MiB), so no
#: ``vmem_limit_bytes`` is asked for. The cells' shapes take 7.7-9.5 MiB
#: by :func:`_vmem_bytes`; the probe's sweep never wanted more.
_VMEM_BUDGET = 12 * 2 ** 20
#: rows of a tile and of a minor block the chip preferred in every
#: kernel at both widths (``scripts/flash_probe.py``; PERF.md, PR 30)
_ROWS = 512


class Tile(NamedTuple):
    """One kernel's tile schedule. ``rows``: rows of the tile a grid row
    owns (queries in ``flash_fwd`` / ``flash_bwd_dq``, keys in
    ``flash_bwd_dkv``). ``major``: rows of the OTHER sequence resident in
    VMEM in one grid step. ``minor``: rows of it one pass of the body's
    loop takes (``major`` is a multiple of it)."""
    rows: int
    major: int
    minor: int


class Schedule(NamedTuple):
    fwd: Tile
    dq: Tile
    dkv: Tile


class BlockDiffusion(NamedTuple):
    """The mask of block-diffusion training (module docstring): keys and
    queries are ``[x_0 ; x_t]``, ``length`` positions each (a multiple of
    ``block``)."""
    length: int
    block: int


def _use_interpret() -> bool:
    """Pallas interpret mode is for the CPU backend and nothing else (the
    CPU tests pin numerics against the materializing oracle); any other
    backend compiles the kernel or raises."""
    return jax.default_backend() == "cpu"


def _up(n, m):
    return -(-n // m) * m


def _uniform(block_q, block_k):
    """Explicit integers: one ``block_q x block_k`` score tile a grid
    step in all three kernels, no inner loop (what they always meant)."""
    return Schedule(Tile(block_q, block_k, block_k),
                    Tile(block_q, block_k, block_k),
                    Tile(block_k, block_q, block_q))


def _clip(tile, own, other):
    """A tile never exceeds the (padded) sequence, but stays on the TPU
    tiling when a short sequence clips it: ``rows`` are sublanes of every
    block (16 for packed bf16), ``minor`` rows become the LANES of the
    score tile (128). ``T=80`` gives rows 80 over keys of 128; the caller
    zero-pads to the blocks and the in-kernel key mask covers the pad."""
    minor = min(tile.minor, _up(other, _LANES))
    major = min(_up(tile.major, minor), _up(other, minor))
    return Tile(min(tile.rows, _up(own, _SUBLANES)), major, minor)


def _block_sizes(schedule, Tq, Tk):
    return Schedule(_clip(schedule.fwd, Tq, Tk), _clip(schedule.dq, Tq, Tk),
                    _clip(schedule.dkv, Tk, Tq))


def _vmem_bytes(kernel, tile, Dqk, Dv, itemsize):
    """What ``kernel`` keeps in VMEM under ``tile``: its blocks twice (the
    pipeline's double buffers), its fp32 accumulators and replicated
    columns, and the fp32 score-sized tiles live in one pass of the loop."""
    own, streamed = tile.rows, tile.major
    # (a block of 64 columns takes a whole tile's lanes in VMEM)
    Dqk, Dv = _up(Dqk, _LANES), _up(Dv, _LANES)
    if kernel == "fwd":     # q, o | k, v | acc, m, l | s, p
        blocks = own * (Dqk + Dv) + streamed * (Dqk + Dv)
        state = own * (Dv + 2 * _LANES)
        live = 3
    elif kernel == "dq":    # q, dO, dq | k, v | acc, lse, delta | s, p, dov
        blocks = own * (2 * Dqk + Dv) + streamed * (Dqk + Dv)
        state = own * (Dqk + 2 * _LANES)
        live = 4
    else:                   # k, v, dk, dv | q, dO | dk_acc, dv_acc | ...
        blocks = own * 2 * (Dqk + Dv) + streamed * (Dqk + Dv)
        state = own * (Dqk + Dv)
        live = 4
    return 2 * blocks * itemsize + 4 * state \
        + 4 * live * tile.rows * tile.minor


def _steps(schedule, Tq, Tk):
    """Grid steps one (batch, head) takes in each kernel."""
    f, q, kv = schedule
    return (pl.cdiv(Tq, f.rows) * pl.cdiv(Tk, f.major),
            pl.cdiv(Tq, q.rows) * pl.cdiv(Tk, q.major),
            pl.cdiv(Tk, kv.rows) * pl.cdiv(Tq, kv.major))


def flash_schedule(Tq, Tk, Dqk, Dv, dtype):
    """The tiles ``flash_attention`` runs a call of this shape with, and
    the grid steps a (batch, head) then takes in ``(flash_fwd,
    flash_bwd_dq, flash_bwd_dkv)``: a function of what the call can
    observe and nothing else. ``Dqk`` is the score width as the kernels
    see it (192 arrives as 256). The preferred tiles are the chip's
    answer to ``scripts/flash_probe.py`` (PERF.md, PR 30); the streamed
    sequence stays whole in VMEM while :data:`_VMEM_BUDGET` allows and is
    halved (in whole minor blocks) while it does not."""
    itemsize = jnp.dtype(dtype).itemsize
    want = Schedule(fwd=Tile(_ROWS, Tk, _ROWS), dq=Tile(_ROWS, Tk, _ROWS),
                    dkv=Tile(_ROWS, Tq, _ROWS))
    tiles = []
    for kernel, tile in zip(Schedule._fields, _block_sizes(want, Tq, Tk)):
        while tile.major > tile.minor and _vmem_bytes(
                kernel, tile, Dqk, Dv, itemsize) > _VMEM_BUDGET:
            tile = tile._replace(
                major=_up(tile.major // 2, tile.minor))
        tiles.append(tile)
    schedule = Schedule(*tiles)
    return schedule, _steps(schedule, Tq, Tk)


# -- what the three bodies share ----------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _lanes(col, n):
    """A lane-replicated ``[rows, LANES]`` column against ``n`` lanes."""
    if n % _LANES == 0:
        return jnp.tile(col, (1, n // _LANES))
    return jnp.broadcast_to(col[:, :1], (col.shape[0], n))


def _col(row):
    """``[1, rows]`` -> the lane-replicated column ``[rows, LANES]``."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


def _mask(s, *, q0, k0, k_len, causal, keys_on_rows=False):
    """NEG_INF-mask invalid scores: zero-padded keys always, upper
    triangle when causal, the three rules of :class:`BlockDiffusion`.
    Only the bodies of tiles a boundary of the mask crosses or that hold
    padded keys call this."""
    if isinstance(causal, BlockDiffusion):
        return _bd_mask(s, q0=q0, k0=k0, k_len=k_len, bd=causal,
                        keys_on_rows=keys_on_rows)
    qa, ka = (1, 0) if keys_on_rows else (0, 1)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, ka)
    valid = kpos < k_len
    if causal:
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, qa)
        valid = valid & (kpos <= qpos)
    return jnp.where(valid, s, NEG_INF)


def _block_of(pos, block):
    """``pos // block`` for positions that are not negative; a shift where
    ``block`` is a power of two (an integer division a score would cost
    the vector unit more than the mask is worth)."""
    if block & (block - 1) == 0:
        return jax.lax.shift_right_logical(
            pos, jnp.full_like(pos, block.bit_length() - 1))
    return pos // block


def _bd_mask(s, *, q0, k0, k_len, bd, keys_on_rows):
    """The block-diffusion mask of one score tile. Every per-position
    quantity is worked out on a COLUMN (lane-replicated, ``[rows,
    LANES]``) or a ROW (``[1, lanes]``) and meets the tile in two
    compares: a key of the clean copy is seen by rows whose ``below``
    exceeds its block (own block and earlier for a clean row, strictly
    earlier for a noised one), a key of the noised copy by the noised
    rows of its own block."""
    length, block = bd
    sub, lanes = s.shape
    col = lambda p0: p0 + jax.lax.broadcasted_iota(jnp.int32, (sub, _LANES), 0)
    row = lambda p0: p0 + jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    qpos, kpos = (row(q0), col(k0)) if keys_on_rows else (col(q0), row(k0))
    q_noised, k_noised = qpos >= length, kpos >= length
    qb = _block_of(jnp.where(q_noised, qpos - length, qpos), block)
    kb = _block_of(jnp.where(k_noised, kpos - length, kpos), block)
    below = jnp.where(q_noised, qb, qb + 1)
    own = jnp.where(q_noised, qb, -1)
    clean = jnp.where(k_noised, 2 ** 30, kb)
    noised = jnp.where(k_noised & (kpos < k_len), kb, -2)
    wide = lambda x: _lanes(x, lanes) if x.shape[0] == sub else x
    valid = (wide(clean) < wide(below)) | (wide(noised) == wide(own))
    return jnp.where(valid, s, NEG_INF)


def _keys_needed(i, tile, k_len, causal):
    """Minor key blocks, from the first, that query tile ``i`` reaches:
    those that hold real keys, up to the one its last row's diagonal is in."""
    needed = pl.cdiv(k_len, tile.minor)
    if causal:
        needed = jnp.minimum(needed,
                             (i * tile.rows + tile.rows - 1) // tile.minor + 1)
    return needed


def _bd_spans(i, tile, bd, *, own_len, other_len, keys_own):
    """The minor blocks of the streamed sequence that tile ``i`` needs
    under a :class:`BlockDiffusion` mask: ``(plain, masked, ranges)``.
    ``ranges`` are the TWO runs of blocks the tile reaches at all
    (``[(lo, hi), (lo, hi)]``, the second may be empty); ``plain`` and
    ``masked`` are the pieces of them whose every pair is seen (no mask
    in the body) and the others. A tile that holds rows of both copies,
    which only a ``length`` that is no multiple of the tile gives, takes
    every block masked: the body's mask is exact whatever the band."""
    length, block = bd
    rows, _, m = tile
    start = lambda pos: (pos // block) * block      # first position of pos's block
    valid = pl.cdiv(other_len, m)
    p0, p1 = i * rows, jnp.minimum(i * rows + rows, own_len) - 1
    is_clean, is_noised = p1 < length, p0 >= length
    pick = lambda clean, noised, both: jnp.where(
        is_clean, clean, jnp.where(is_noised, noised, both))
    if not keys_own:
        # clean rows: clean keys through their own block; noised rows:
        # clean keys before their block, then the noised keys of it
        t0, t1 = p0 - length, p1 - length
        hi = pl.cdiv(pick(start(p1) + block, start(t1), other_len), m)
        split = pick(start(p0) + block, start(t0), 0) // m
        lo2 = jnp.maximum((length + start(t0)) // m, hi)
        hi2 = pick(0, pl.cdiv(length + start(t1) + block, m), 0)
        lo2 = jnp.minimum(lo2, hi2)
        return ([(0, split)], [(split, hi), (lo2, hi2)],
                [(0, hi), (lo2, hi2)])
    # a tile of clean keys: the clean rows from its first key's block on
    # (those from its last key's block on see all of it), then the noised
    # rows of later blocks; a tile of noised keys: the rows of its blocks
    s1, e1 = start(p0) // m, pl.cdiv(length, m)
    u1 = jnp.minimum(pl.cdiv(start(p1), m), length // m)
    s2 = jnp.maximum((length + start(p0) + block) // m, e1)
    u2 = jnp.clip(pl.cdiv(length + start(p1) + block, m), s2, valid)
    lo = pick(s1, (length + start(p0 - length)) // m, 0)
    hi = pick(e1, pl.cdiv(length + start(p1 - length) + block, m), valid)
    only = lambda v: jnp.where(is_clean, v, 0)
    plain = [(only(u1), only(length // m)), (only(u2), only(valid))]
    masked = [(lo, jnp.where(is_clean, u1, hi)),
              (only(length // m), only(e1)), (only(s2), only(u2))]
    return plain, masked, [(lo, hi), (only(s2), only(valid))]


def _band(i, jm, tile, *, own_len, other_len, causal, keys_own):
    """Which minor blocks of the streamed sequence the body visits in grid
    step ``(i, jm)``: ``(plain, masked)``, each a list of ``(lo, hi)``
    runs in minor blocks from the start of the sequence; ``plain`` runs
    lie wholly inside the mask (the body builds none), ``masked`` ones
    are crossed by a boundary of it or hold padded keys. No mask or
    causal, one run each. ``keys_own`` False (``fwd``, ``dq``): tile
    ``i`` of queries against key blocks, the blocks under the diagonal
    then those it crosses. ``keys_own`` True (``dkv``): tile ``i`` of
    keys against query blocks, the crossed ones then those under it --
    unless the key tile holds padded keys, then every block is masked.
    :class:`BlockDiffusion`: :func:`_bd_spans`."""
    rows, major, minor = tile
    per_step = major // minor
    first, last = jm * per_step, (jm + 1) * per_step
    valid = pl.cdiv(other_len, minor)       # blocks that hold real rows
    if isinstance(causal, BlockDiffusion):
        plain, masked, _ = _bd_spans(i, tile, causal, own_len=own_len,
                                     other_len=other_len, keys_own=keys_own)
        hi = jnp.minimum(valid, last)
        clip = lambda runs: [(jnp.clip(a, first, hi), jnp.clip(b, first, hi))
                             for a, b in runs]
        return clip(plain), clip(masked)
    if not keys_own:
        full = other_len // minor
        if causal:
            full = jnp.minimum(full, (i * rows + 1) // minor)
        split = jnp.clip(full, first, last)
        return [(first, split)], [(split, jnp.clip(
            _keys_needed(i, tile, other_len, causal), first, last))]
    start = (i * rows) // minor if causal else 0
    under = (i * rows + rows + minor - 2) // minor if causal else 0
    under = jnp.where((i + 1) * rows > own_len, valid, under)  # ragged keys
    hi = jnp.minimum(valid, last)
    split = jnp.clip(under, first, hi)
    return [(split, hi)], [(jnp.clip(start, first, hi), split)]


def _loop(runs, body):
    """``body(block)`` for every block of ``runs`` (``[(lo, hi)]``, bounds
    traced: the band's), one pass a minor block, none where ``hi <= lo``.
    Several runs share ONE loop, whose counter is mapped onto them, so
    the body is traced once however many runs there are."""
    (lo, hi), *more = runs
    if not more:
        jax.lax.fori_loop(lo, hi, lambda b, c: (body(b), c)[1], 0)
        return
    ends, total = [], 0
    for a, b in runs:
        total = total + jnp.maximum(b - a, 0)
        ends.append(total)

    def nth(n):
        block = runs[-1][0] + n - ends[-2]
        for k in range(len(runs) - 2, -1, -1):
            block = jnp.where(n < ends[k],
                              runs[k][0] + n - (ends[k - 1] if k else 0),
                              block)
        return block

    jax.lax.fori_loop(0, total, lambda n, c: (body(nth(n)), c)[1], 0)


def band_passes(kernel, tile, Tq, Tk, causal):
    """Passes of the inner loop one (batch, head) takes in ``kernel``
    (``"fwd"``, ``"dq"``, ``"dkv"``) under ``tile``: the minor blocks its
    grid steps visit, counted on the host from :func:`_band`."""
    keys_own = kernel == "dkv"
    own, other = (Tk, Tq) if keys_own else (Tq, Tk)
    tile = _clip(tile, own, other)
    total = 0
    for i in range(pl.cdiv(own, tile.rows)):
        for jm in range(pl.cdiv(other, tile.major)):
            for runs in _band(i, jm, tile, own_len=own, other_len=other,
                              causal=causal, keys_own=keys_own):
                total += sum(max(int(b) - int(a), 0) for a, b in runs)
    return total


def _local(block, jm, tile):
    """Minor block ``block``'s place inside major block ``jm``."""
    return block - jm * (tile.major // tile.minor)


def _streamed(ref, jm, block, tile):
    """Rows of minor block ``block`` in a ref that holds major block ``jm``."""
    start = _local(block, jm, tile) * tile.minor
    return ref[pl.ds(pl.multiple_of(start, tile.minor), tile.minor), :]


# -- forward ------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, tile, q_len, k_len):
    qi = pl.program_id(0)   # query tile
    jm = pl.program_id(1)   # major key block (innermost grid dim)

    @pl.when(jm == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def step(block, masked):
        k, v = (_streamed(r, jm, block, tile) for r in (k_ref, v_ref))
        s = _dot(q_ref[:], k, _NT) * scale             # [rows, minor]
        if masked:
            s = _mask(s, q0=qi * tile.rows, k0=block * tile.minor,
                      k_len=k_len, causal=causal)
        m_prev = m_ref[:]                              # [rows, LANES]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, tile.minor))
        if masked:
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * _lanes(corr, acc_ref.shape[1]) \
            + _dot(p.astype(v.dtype), v, _NN)          # [rows, Dv]
        m_ref[:] = m_new

    plain, masked = _band(qi, jm, tile, own_len=q_len, other_len=k_len,
                          causal=causal, keys_own=False)
    _loop(plain, functools.partial(step, masked=False))
    _loop(masked, functools.partial(step, masked=True))

    @pl.when(jm == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[:]
        o_ref[:] = (acc_ref[:] / _lanes(jnp.maximum(l, 1e-30),
                                        acc_ref.shape[1])).astype(o_ref.dtype)
        # fully-masked rows (l == 0): any finite lse works -- the backward
        # re-masks scores to NEG_INF, so exp(s - lse) is 0 regardless
        lse = jnp.where(l > 0, m_ref[:] + jnp.log(jnp.maximum(l, 1e-30)),
                        0.0)
        lse_ref[:] = lse.T[:1]                         # the column as a row


def _resident(j, ranges, per_step):
    """The major block grid step ``j`` names where its tile reaches the
    two ``ranges`` of minor blocks (:func:`_bd_spans`): itself inside a
    range, else the block already resident (the end of the range behind
    it) or, before the first range, the first block it will need."""
    (a, b), (a2, b2) = ranges
    in_first = jnp.clip(j, a // per_step, jnp.maximum(b - 1, a) // per_step)
    return jnp.where((b2 > a2) & (j >= a2 // per_step),
                     jnp.minimum(j, (b2 - 1) // per_step), in_first)


def _resident_keys(tile, q_len, k_len, causal):
    """Index map of K and V in the query-tile grids: a step above the band
    names the last major block the band of its tile reaches, which is the
    block already resident, so the pipeline copies nothing for it."""
    per_step = tile.major // tile.minor
    if isinstance(causal, BlockDiffusion):
        return lambda i, j: (_resident(j, _bd_spans(
            i, tile, causal, own_len=q_len, other_len=k_len,
            keys_own=False)[2], per_step), 0)
    return lambda i, j: (jnp.minimum(
        j, (_keys_needed(i, tile, k_len, causal) - 1) // per_step), 0)


def _fwd_one_head(q, k, v, *, scale, causal, tile, q_len, k_len, interpret):
    Tq, D = q.shape          # the score width (q and k)
    Tk, Dv = v.shape         # the value width (v and o)
    rows, major, _ = tile
    kv = _resident_keys(tile, q_len, k_len, causal)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          tile=tile, q_len=q_len, k_len=k_len),
        grid=(Tq // rows, Tk // major),
        in_specs=[
            pl.BlockSpec((rows, D), lambda i, j: (i, 0)),
            pl.BlockSpec((major, D), kv),
            pl.BlockSpec((major, Dv), kv),
        ],
        out_specs=[
            pl.BlockSpec((rows, Dv), lambda i, j: (i, 0)),
            pl.BlockSpec((1, rows), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tq, Dv), q.dtype),
            jax.ShapeDtypeStruct((1, Tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, Dv), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


# -- backward -----------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
               acc_ref, lse_col, dl_col, *, scale, causal, tile, q_len,
               k_len):
    """A tile of queries, keys streamed: ``dq = scale * sum_k ds @ k``
    with ``p = exp(s - lse)`` re-formed from the saved logsumexp and
    ``ds = p * (dO v^T - delta)``."""
    qi = pl.program_id(0)
    jm = pl.program_id(1)

    @pl.when(jm == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        lse_col[:] = _col(lse_ref[:])
        dl_col[:] = _col(dl_ref[:])

    def step(block, masked):
        k, v = (_streamed(r, jm, block, tile) for r in (k_ref, v_ref))
        s = _dot(q_ref[:], k, _NT) * scale             # [rows, minor]
        if masked:
            s = _mask(s, q0=qi * tile.rows, k0=block * tile.minor,
                      k_len=k_len, causal=causal)
        p = jnp.exp(s - _lanes(lse_col[:], tile.minor))
        if masked:
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        ds = p * (_dot(do_ref[:], v, _NT) - _lanes(dl_col[:], tile.minor))
        acc_ref[:] += _dot(ds.astype(k.dtype), k, _NN)

    plain, masked = _band(qi, jm, tile, own_len=q_len, other_len=k_len,
                          causal=causal, keys_own=False)
    _loop(plain, functools.partial(step, masked=False))
    _loop(masked, functools.partial(step, masked=True))

    @pl.when(jm == pl.num_programs(1) - 1)
    def _finalize():
        dq_ref[:] = (scale * acc_ref[:]).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, scale, causal, tile, q_len,
                k_len):
    """A tile of keys, queries streamed, on the transposed score tile
    (keys on sublanes, queries on lanes): ``dv = sum_q p^T @ dO``,
    ``dk = scale * sum_q ds^T @ q``; logsumexp and delta are rows."""
    kj = pl.program_id(0)
    im = pl.program_id(1)

    @pl.when(im == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def step(block, masked):
        q, do = (_streamed(r, im, block, tile) for r in (q_ref, do_ref))
        st = _dot(k_ref[:], q, _NT) * scale            # [rows, minor]
        if masked:
            st = _mask(st, q0=block * tile.minor, k0=kj * tile.rows,
                       k_len=k_len, causal=causal, keys_on_rows=True)
        pt = jnp.exp(st - lse_ref[_local(block, im, tile)])
        if masked:
            pt = jnp.where(st <= NEG_INF / 2, 0.0, pt)
        dst = pt * (_dot(v_ref[:], do, _NT)
                    - dl_ref[_local(block, im, tile)])
        dv_acc[:] += _dot(pt.astype(do.dtype), do, _NN)
        dk_acc[:] += _dot(dst.astype(q.dtype), q, _NN)

    plain, masked = _band(kj, im, tile, own_len=k_len, other_len=q_len,
                          causal=causal, keys_own=True)
    _loop(masked, functools.partial(step, masked=True))
    _loop(plain, functools.partial(step, masked=False))

    @pl.when(im == pl.num_programs(1) - 1)
    def _finalize():
        dk_ref[:] = (scale * dk_acc[:]).astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _dq_one_head(q, k, v, do, lse, dl, *, scale, causal, tile, q_len, k_len,
                 interpret):
    Tq, D = q.shape          # the score width (q, k, dq)
    Tk, Dv = v.shape         # the value width (v, dO)
    rows, major, _ = tile
    kv = _resident_keys(tile, q_len, k_len, causal)
    own = lambda width: pl.BlockSpec((rows, width), lambda i, j: (i, 0))
    row = pl.BlockSpec((1, rows), lambda i, j: (0, i))
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, tile=tile,
                          q_len=q_len, k_len=k_len),
        grid=(Tq // rows, Tk // major),
        in_specs=[own(D), pl.BlockSpec((major, D), kv),
                  pl.BlockSpec((major, Dv), kv), own(Dv), row, row],
        out_specs=own(D),
        out_shape=jax.ShapeDtypeStruct((Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32),
                        pltpu.VMEM((rows, _LANES), jnp.float32),
                        pltpu.VMEM((rows, _LANES), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, dl)


def _dkv_one_head(q, k, v, do, lse, dl, *, scale, causal, tile, q_len, k_len,
                  interpret):
    Tq, D = q.shape          # the score width (q, k, dk)
    Tk, Dv = v.shape         # the value width (v, dO, dv)
    rows, major, minor = tile
    per_step = major // minor
    # kv-outer grid: index maps see (key tile, major query block); a step
    # the band has not reached yet names the first block it will need
    if isinstance(causal, BlockDiffusion):
        qs = lambda j, i: (_resident(i, _bd_spans(
            j, tile, causal, own_len=k_len, other_len=q_len,
            keys_own=True)[2], per_step), 0)
    else:
        first = ((lambda j: (j * rows) // minor // per_step) if causal
                 else (lambda j: 0))
        qs = lambda j, i: (jnp.minimum(jnp.maximum(i, first(j)),
                                       Tq // major - 1), 0)
    own = lambda width: pl.BlockSpec((rows, width), lambda j, i: (j, 0))
    # logsumexp / delta: one [1, minor] row a minor block, picked by its
    # leading index in the body
    rowed = lambda x: x.reshape(Tq // minor, 1, minor)
    row = pl.BlockSpec((per_step, 1, minor), lambda j, i: qs(j, i) + (0,))
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, tile=tile,
                          q_len=q_len, k_len=k_len),
        grid=(Tk // rows, Tq // major),
        in_specs=[pl.BlockSpec((major, D), qs), own(D), own(Dv),
                  pl.BlockSpec((major, Dv), qs), row, row],
        out_specs=[own(D), own(Dv)],
        out_shape=[jax.ShapeDtypeStruct((Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((Tk, Dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32),
                        pltpu.VMEM((rows, Dv), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, rowed(lse), rowed(dl))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, schedule=None):
    """Fused attention: q ``[B, T, H, Dqk]``, k ``[B, T, KV, Dqk]``, v
    ``[B, T, KV, Dv]`` -> ``[B, T, H, Dv]``; ``KV`` divides ``H`` and
    query head ``h`` reads key/value head ``h // (H / KV)`` where it lies
    (:func:`_per_head`: no repeated copy; the gradient of a key/value
    head is its group's sum, taken right after ``flash_bwd_dkv``).

    Forward and backward are Pallas kernels (per ``(batch, head)`` via a
    double vmap -- each kernel grid covers tiles of one sequence x major
    blocks of the other). The tiles come from :func:`flash_schedule`
    unless given: integers ``block_q`` / ``block_k`` mean one such score
    tile a grid step in every kernel, a :class:`Schedule` means itself.
    ``causal`` is the mask kind: ``False``, ``True`` or a
    :class:`BlockDiffusion` (module docstring).
    Ragged sequence lengths are padded here and masked in-kernel. The
    score width ``Dqk`` may differ from the value width ``Dv`` (latent
    attention: keys wider than values). On hardware ``Dv`` must fill
    128-wide tiles or be 64 (blocks of the array's own 64 columns); a
    ``Dqk`` that differs from it and fills no tile is zero-padded to the next
    multiple of 128 here (exact: the extra columns add 0 to every score),
    and ``scale`` defaults to ``Dqk ** -0.5`` of the width given, never
    the padded one.
    """
    return _fa_fwd(q, k, v, causal, scale, block_q, block_k, schedule)[0]


def _pad_t(x, multiple, axis=1):
    pad = (-x.shape[axis]) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# [B, T, H, D] <-> [B, H, T, D]: self-inverse, used at every kernel boundary
def _swap_th(x):
    return jnp.transpose(x, (0, 2, 1, 3))


def _per_head(fn, group=1, in_axes=0):
    """[B, H, T, ...] operands -> per-(batch, head) kernel calls. Both
    mapped axes are LEADING: on hardware Mosaic turns each vmapped axis
    into a squeezed block dim, and squeezed dims are only legal outside
    the trailing two block dims -- vmapping the middle head axis of a
    [B, T, H, D] array makes the block's last-two dims (Squeezed(H), D),
    which the TPU lowering rejects (r5 hardware run). Callers transpose
    to [B, H, T, D] at the boundary instead.

    Grouped-query heads (``group`` query heads a key/value head): the
    per-query-head operands come as ``[B, KV, group, T, ...]`` and keys
    and values as ``[B, KV, T, ...]``. A third, innermost map runs over
    the group by ``in_axes``, which leaves keys and values unmapped
    (``None``): their index maps ignore that grid axis, so
    query head ``h`` READS key/value head ``h // group`` where it lies
    (no repeated copy), and while the group turns the block index stands
    and nothing is fetched again."""
    if group > 1:
        fn = jax.vmap(fn, in_axes=in_axes)
    return jax.vmap(jax.vmap(fn))


#: the group's map over (q, k, v) and over (q, k, v, dO, lse, delta)
_Q_OF_QKV = (0, None, None)
_Q_OF_BWD = (0, None, None, 0, 0, 0)


def _split_group(x, group):
    """``[B, H, ...] -> [B, KV, group, ...]`` (nothing where ``group`` is 1)."""
    return x if group == 1 else x.reshape(
        (x.shape[0], x.shape[1] // group, group) + x.shape[2:])


def _merge_group(x, group):
    return x if group == 1 else x.reshape(
        (x.shape[0], x.shape[1] * group) + x.shape[3:])


def _require_hw_head_dim(D, interpret):
    """On real TPU hardware the kernels run a value width that fills
    128-wide tiles, or 64 (blocks of the array's own 64 columns: half a
    tile's lanes, the width the chip was probed at, PERF.md PR 34);
    interpret mode (CPU tests) takes any D. Fail loudly up front instead
    of leaving a Mosaic layout error to decipher (ADVICE r3)."""
    if not interpret and D % 128 and D != 64:
        raise ValueError(
            f"flash_attention on TPU hardware requires head_dim D to be a "
            f"multiple of 128, or 64 (got D={D}); use "
            "fedml_tpu.ops.attention.blockwise_attention for other head "
            "dims (same flash semantics, XLA-scheduled)")


def _score_pad(Dqk, Dv, interpret):
    """Zero columns to add to q and k: none where the score width is the
    value width (which ``_require_hw_head_dim`` has checked) or in
    interpret mode, else up to the next 128-wide tile (192 -> 256)."""
    return 0 if interpret or Dqk == Dv else (-Dqk) % _LANES


def _pad_d(x, pad):
    return jnp.pad(x, ((0, 0),) * 3 + ((0, pad),)) if pad else x


def _schedule_of(block_q, block_k, schedule, Tq, Tk, Dqk, Dv, dtype):
    if schedule is not None:
        return _block_sizes(schedule, Tq, Tk)
    if block_q is None and block_k is None:
        return flash_schedule(Tq, Tk, Dqk, Dv, dtype)[0]
    return _block_sizes(_uniform(block_q, block_k), Tq, Tk)


# The kernels' launches with the pads and transposes around them are jitted
# on their own: a model calls one attention shape in every layer of every
# program of a round, and the kernels' bodies are the slowest thing in the
# step to trace; jit's cache hands the later calls the first one's jaxpr
# (set-up, not the window: the compiled program is the same, inlined).
_STATIC = dict(static_argnames=("causal", "scale", "block_q", "block_k",
                                "schedule", "interpret"))


@functools.partial(jax.jit, **_STATIC)
def _forward(q, k, v, *, causal, scale, block_q, block_k, schedule,
             interpret):
    B, Tq, H, D = q.shape
    Tk, Dv = k.shape[1], v.shape[-1]
    scale_ = scale if scale is not None else D ** -0.5
    pad_d = _score_pad(D, Dv, interpret)
    tile = _schedule_of(block_q, block_k, schedule, Tq, Tk, D + pad_d, Dv,
                        q.dtype).fwd
    qp = _swap_th(_pad_d(_pad_t(q, tile.rows), pad_d))
    kp = _swap_th(_pad_d(_pad_t(k, tile.major), pad_d))
    vp = _swap_th(_pad_t(v, tile.major))
    fn = functools.partial(_fwd_one_head, scale=scale_, causal=causal,
                           tile=tile, q_len=Tq, k_len=Tk,
                           interpret=interpret)
    group = H // k.shape[2]
    out, lse = (_merge_group(x, group) for x in _per_head(
        fn, group, _Q_OF_QKV)(_split_group(qp, group), kp, vp))
    # back to [B,T,H,D]; the logsumexp stays [B,H,T]
    return _swap_th(out)[:, :Tq], lse[:, :, 0, :Tq]


@functools.partial(jax.jit, **_STATIC)
def _backward(q, k, v, out, lse, g, *, causal, scale, block_q, block_k,
              schedule, interpret):
    B, Tq, H, D = q.shape
    Tk, Dv = k.shape[1], v.shape[-1]
    scale_ = scale if scale is not None else D ** -0.5
    pad_d = _score_pad(D, Dv, interpret)
    tiles = _schedule_of(block_q, block_k, schedule, Tq, Tk, D + pad_d, Dv,
                         q.dtype)
    # delta_i = dO_i . O_i (the -sum_j ds_ij term of the softmax backward)
    delta = jnp.transpose(jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1), (0, 2, 1))
    qh, kh = _swap_th(_pad_d(q, pad_d)), _swap_th(_pad_d(k, pad_d))
    vh, doh = _swap_th(v), _swap_th(g.astype(q.dtype))
    rows = lambda x, m: _pad_t(x, m, axis=2)          # [B,H,T,...] along T
    # padded q rows: dO rows are zero => ds rows are zero => no dk/dv
    # contribution; their dq rows are sliced off below
    row = lambda x, m: rows(x, m)[:, :, None]         # [B,H,1,T]
    kw = dict(scale=scale_, causal=causal, q_len=Tq, k_len=Tk,
              interpret=interpret)
    group = H // k.shape[2]
    mine = lambda x: _split_group(x, group)           # a query head's own
    t = tiles.dq
    dq = _per_head(functools.partial(_dq_one_head, tile=t, **kw), group,
                   _Q_OF_BWD)(
        mine(rows(qh, t.rows)), rows(kh, t.major), rows(vh, t.major),
        mine(rows(doh, t.rows)), mine(row(lse, t.rows)),
        mine(row(delta, t.rows)))
    dq = _merge_group(dq, group)
    t = tiles.dkv
    dk, dv = _per_head(functools.partial(_dkv_one_head, tile=t, **kw), group,
                       _Q_OF_BWD)(
        mine(rows(qh, t.major)), rows(kh, t.rows), rows(vh, t.rows),
        mine(rows(doh, t.major)), mine(row(lse, t.major)),
        mine(row(delta, t.major)))
    if group > 1:
        # a key/value head's gradient: its group's query heads' parts,
        # [B, KV, group, T, D], summed right after the kernel
        dk, dv = (jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)
                  for x in (dk, dv))
    # the padded score columns' gradients are sliced off with the padded rows
    return (_swap_th(dq)[:, :Tq, :, :D], _swap_th(dk)[:, :Tk, :, :D],
            _swap_th(dv)[:, :Tk])


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, schedule):
    interpret = _use_interpret()
    _require_hw_head_dim(v.shape[-1], interpret)
    if k.shape[2] != v.shape[2] or q.shape[2] % k.shape[2]:
        raise ValueError(
            f"flash_attention: {q.shape[2]} query heads over {k.shape[2]} "
            f"key and {v.shape[2]} value heads (a whole group of query "
            "heads reads each key/value head)")
    if isinstance(causal, BlockDiffusion):
        length, block = causal
        if length % block or not q.shape[1] == k.shape[1] == 2 * length:
            raise ValueError(
                f"{causal}: queries and keys are [x_0 ; x_t], 2 * length "
                f"positions each in whole blocks (got {q.shape[1]} and "
                f"{k.shape[1]})")
    out, lse = _forward(q, k, v, causal=causal, scale=scale,
                        block_q=block_q, block_k=block_k, schedule=schedule,
                        interpret=interpret)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, block_q, block_k, schedule, res, g):
    return _backward(*res, g, causal=causal, scale=scale, block_q=block_q,
                     block_k=block_k, schedule=schedule,
                     interpret=_use_interpret())


flash_attention.defvjp(_fa_fwd, _fa_bwd)

__all__ = ["flash_attention", "flash_schedule", "band_passes",
           "BlockDiffusion", "Schedule", "Tile"]
