"""The flash kernels' tile schedule (PR 30): whatever tiles a call runs
with -- explicit integers, a major block looped over in minor blocks, the
shape-chosen default -- it computes the attention the materialising
oracle computes; a tile the causal band does not reach is neither
computed nor read; and ``flash_schedule`` stays on the TPU tiling, inside
the VMEM budget and inside the sequence. Interpret mode on the CPU: the
described-v5e compiles of the same kernels are ``tests/test_tpu_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import pallas_attention as pa
from fedml_tpu.ops.attention import blockwise_attention, mha

Tile, Schedule = pa.Tile, pa.Schedule
WHOLE = 10 ** 6     # a major block clipped to the sequence

#: how the tiles are given: (block_q, block_k, schedule)
TILES = {
    "16x16": (16, 16, None),
    "32x16": (32, 16, None),
    "16x32": (16, 32, None),
    "major32_minor16": (None, None, Schedule(Tile(16, 32, 16),
                                             Tile(32, 32, 16),
                                             Tile(16, 32, 16))),
    "whole_minor16": (None, None, Schedule(Tile(32, WHOLE, 16),
                                           Tile(16, WHOLE, 16),
                                           Tile(32, WHOLE, 16))),
    "chosen": (None, None, None),
}
#: (Tq, Tk, Dqk, Dv): square and ragged; more keys than queries, ragged
#: at 32; more queries than keys (rows past the last key), ragged at 16
#: and at 32; scores wider than values
SHAPES = {"t40": (40, 40, 16, 16), "tq48_tk80": (48, 80, 16, 16),
          "tq64_tk40": (64, 40, 16, 16), "wide_scores": (40, 40, 24, 16)}


def _qkv(tq, tk, dqk, dv, seed=30):
    key = jax.random.PRNGKey(seed)
    shape = lambda t, d: (1, t, 2, d)
    return (jax.random.normal(jax.random.fold_in(key, 0), shape(tq, dqk)),
            jax.random.normal(jax.random.fold_in(key, 1), shape(tk, dqk)),
            jax.random.normal(jax.random.fold_in(key, 2), shape(tk, dv)))


def _oracle(q, k, v, causal):
    """``mha`` end-aligns a causal mask when ``Tq != Tk``; the kernels
    count positions from the start, which for ``Tq <= Tk`` is ``mha`` on
    the first ``Tq`` keys (the later ones are above every row's band) and
    for ``Tq > Tk`` what ``blockwise_attention`` computes in one block."""
    tq, tk = q.shape[1], k.shape[1]
    if not causal:
        return mha(q, k, v)
    if tq > tk:
        return blockwise_attention(q, k, v, causal=True, block_size=tq)
    return mha(q, k[:, :tq], v[:, :tq], causal=True)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("tiles", list(TILES))
def test_every_tile_choice_computes_the_oracles_attention(tiles, shape,
                                                          causal):
    q, k, v = _qkv(*SHAPES[shape])
    weight = jnp.cos(jnp.arange(v.shape[-1]))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * weight)

    flash = lambda q, k, v: pa.flash_attention(q, k, v, causal, None,
                                               *TILES[tiles])
    want = _oracle(q, k, v, causal)
    got = flash(q, k, v)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    got_g = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(loss(lambda q, k, v: _oracle(q, k, v, causal)),
                      argnums=(0, 1, 2))(q, k, v)
    for g, w, like in zip(got_g, want_g, (q, k, v)):
        assert g.shape == like.shape
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("tiles", ["16x16", "major32_minor16", "chosen"])
def test_a_tile_above_the_band_is_neither_computed_nor_read(tiles):
    """T 64 under 16-row tiles leaves whole tiles above the diagonal: a
    grid step there names the block already resident (the clamped index
    maps). Rows before the cut depend on no key from the cut on, bit
    for bit, and the gradients of keys from the cut on depend on no
    query before it."""
    q, k, v = _qkv(64, 64, 16, 16)
    cut = 32
    weight = jnp.cos(jnp.arange(16))
    flash = lambda q, k, v: pa.flash_attention(q, k, v, True, None,
                                               *TILES[tiles])
    run = lambda q, k, v: (flash(q, k, v),) + jax.grad(
        lambda q, k, v: jnp.sum(flash(q, k, v) * weight),
        argnums=(0, 1, 2))(q, k, v)
    noise = jax.random.normal(jax.random.PRNGKey(31), (1, 64, 2, 16))
    late = (jnp.arange(64) >= cut)[None, :, None, None]
    o, dq, dk, dv = run(q, k, v)
    o_k, dq_k, _, _ = run(q, jnp.where(late, k + noise, k),
                          jnp.where(late, v - noise, v))
    np.testing.assert_array_equal(np.asarray(o_k[:, :cut]),
                                  np.asarray(o[:, :cut]))
    np.testing.assert_array_equal(np.asarray(dq_k[:, :cut]),
                                  np.asarray(dq[:, :cut]))
    assert not np.array_equal(np.asarray(o_k[:, cut:]), np.asarray(o[:, cut:]))
    _, _, dk_q, dv_q = run(jnp.where(late, q, q + noise), k, v)
    np.testing.assert_array_equal(np.asarray(dk_q[:, cut:]),
                                  np.asarray(dk[:, cut:]))
    np.testing.assert_array_equal(np.asarray(dv_q[:, cut:]),
                                  np.asarray(dv[:, cut:]))
    assert not np.array_equal(np.asarray(dk_q[:, :cut]),
                              np.asarray(dk[:, :cut]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("width", [128, 256])
@pytest.mark.parametrize("t", [80, 128, 2048, 4096])
def test_flash_schedule_stays_on_the_tiling_and_inside_the_budget(
        t, width, dtype):
    schedule, steps = pa.flash_schedule(t, t, width, 128, dtype)
    up = lambda n, m: -(-n // m) * m
    for kernel, tile in zip(schedule._fields, schedule):
        # rows are sublanes of every block, minor rows the lanes of the
        # score tile; the loop takes whole minor blocks
        assert tile.rows % 16 == 0 and tile.minor % 128 == 0
        assert tile.major % tile.minor == 0
        # never larger than the padded sequence
        assert tile.rows <= up(t, 16) and tile.minor <= up(t, 128)
        assert tile.major <= up(t, tile.minor)
        assert pa._vmem_bytes(kernel, tile, width, 128,
                              jnp.dtype(dtype).itemsize) <= pa._VMEM_BUDGET
        # what the chip's sweep preferred: hundreds of rows a step
        assert tile.rows == min(512, up(t, 16))
    assert steps == tuple(-(-t // tile.rows) * -(-t // tile.major)
                          for tile in schedule)
    # explicit integers keep meaning one such tile a step in every kernel
    fixed = pa._block_sizes(pa._uniform(16, 16), t, t)
    assert fixed.fwd == fixed.dq == fixed.dkv == Tile(16, 16, 16)


def test_a_short_sequence_clips_the_tiles_to_the_tiling():
    """T 80: 80 rows (sublanes of 16) over keys of 128 (lanes)."""
    schedule, steps = pa.flash_schedule(80, 80, 128, 128, jnp.bfloat16)
    assert schedule.fwd == schedule.dq == schedule.dkv == Tile(80, 128, 128)
    assert steps == (1, 1, 1)
    assert pa._block_sizes(pa._uniform(128, 128), 80, 80).fwd \
        == Tile(80, 128, 128)
