"""The ``sdar_moe`` decoder (grouped-query attention with per-head q/k
norms, softmax-routed experts held in part, no shared expert) trained by
block diffusion, against its plain reference, which is loaded by path
from beside the benchmark's configuration and imports nothing of the
program: seeded random weights, float32, a small size on the CPU. And the
flash kernels under the block-diffusion mask against a dense masked
softmax, in interpret mode."""

import dataclasses
import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families.common import nest
from fedml_tpu.algorithms.specs import (block_diffusion_counters,
                                        make_block_diffusion_lm_spec)
from fedml_tpu.models import deepseek_v3 as dec
from fedml_tpu.ops import pallas_attention as pa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BD, Tile, Schedule = pa.BlockDiffusion, pa.Tile, pa.Schedule


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _load(os.path.join(ROOT, "benchmarks", "configs",
                         "sdar_moe_lm_reference.py"),
            "sdar_moe_lm_reference_for_tests")

TOY = {
    "model_type": "sdar_moe", "hidden_size": 64, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 16,
    "num_experts_per_tok": 4, "num_hidden_layers": 2, "vocab_size": 97,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "rope_scaling": None,
    "use_sliding_window": False, "sliding_window": None,
    "tie_word_embeddings": False, "attention_bias": False,
    "hidden_act": "silu", "block_length": 4,
}
LEAVES = sorted(REF.param_shapes(TOY))
N, L = 2, 16
MASK_ID = REF.mask_id(TOY)


def flat_of(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _batch(n=N, length=L, seed=31):
    """Clean ids and a corruption as the reference's ``make_clients``
    draws them."""
    shard = REF.make_clients(TOY, {"sequences_per_client": [n],
                                   "seq_len": length}, seed)[0]
    return jnp.asarray(shard["x"]), jnp.asarray(shard["y"])


@pytest.fixture(scope="module")
def toy():
    weights = REF.make_weights(TOY, 31)
    model = dec.DecoderLM(dec.DecoderConfig.from_dict(TOY))
    x, y = _batch()
    return types.SimpleNamespace(s=REF.sizes(TOY), weights=weights,
                                 model=model, x=x, y=y,
                                 params=nest(weights))


@pytest.fixture(scope="module")
def grads(toy):
    spec = make_block_diffusion_lm_spec(toy.model, toy.x[:1], 4, MASK_ID)
    batch = {"x": toy.x, "y": toy.y, "mask": jnp.ones((N,), jnp.float32)}

    def loss(p):
        value, (_, metrics) = spec.loss_fn({"params": p}, batch, None, True)
        return value, metrics

    (value, metrics), g = jax.value_and_grad(loss, has_aux=True)(toy.params)
    (ref_value, ref_sums), ref_g = jax.value_and_grad(
        lambda p: REF.step_loss(p, toy.x, toy.y, jnp.ones((N,)), toy.s,
                                MASK_ID), has_aux=True)(toy.weights)
    return types.SimpleNamespace(value=value, metrics=metrics,
                                 prog=flat_of(g), ref_value=ref_value,
                                 ref_sums=ref_sums, ref=ref_g)


# -- the model against the reference -----------------------------------------

def test_the_parameter_tree_is_the_references(toy):
    init = toy.model.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 2 * L), jnp.int32))
    shapes = {k: v.shape for k, v in flat_of(init["params"]).items()}
    assert shapes == {k: tuple(v) for k, v in REF.param_shapes(TOY).items()}
    # no bias of the choice, no shared expert: the family has neither
    assert not [k for k in shapes if "bias" in k or "shared" in k]


def test_logits_match_the_reference(toy):
    ids = jnp.concatenate([toy.x, jnp.where(toy.y > 0, MASK_ID, toy.x)], 1)
    logits = toy.model.apply({"params": toy.params}, ids)
    assert logits.shape == (N, L, TOY["vocab_size"])   # the noised half's
    np.testing.assert_allclose(logits, REF.forward(toy.weights, ids, toy.s),
                               atol=2e-5, rtol=2e-5)


def test_the_loss_matches_the_reference(grads):
    assert float(grads.value) == pytest.approx(float(grads.ref_value),
                                               rel=2e-6)
    total, count = grads.ref_sums
    assert float(grads.metrics["loss_sum"]) == pytest.approx(float(total),
                                                             rel=2e-6)
    assert float(grads.metrics["count"]) == float(count)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(grads, leaf):
    got, want = np.asarray(grads.prog[leaf]), np.asarray(grads.ref[leaf])
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, atol=3e-6 + 1e-4 * np.abs(
        want).max(), rtol=2e-4)


# -- the block-diffusion objective on a hand-made batch ------------------------

def test_the_specs_loss_on_a_hand_made_batch():
    """A model that returns known logits: the loss is ``sum y CE / (n L)``
    at the masked positions against the CLEAN id of the same position (no
    shift), and the model was fed ``[x ; x_t]``."""
    length, vocab, mask_id = 8, 5, 4
    seen = {}

    class Fixed:
        cfg = types.SimpleNamespace(block_length=4)

        def init(self, rng, ids, train=False):
            return {"params": {}}

        def apply(self, variables, ids, train=False, **kw):
            seen["ids"] = ids
            # position t prefers id t % 4 by a margin of 2
            logits = 2.0 * jax.nn.one_hot(jnp.arange(length) % 4, vocab)
            out = jnp.broadcast_to(logits, (ids.shape[0], length, vocab))
            return (out, {}) if kw.get("mutable") else out

    x = jnp.asarray([[0, 1, 2, 3, 0, 0, 0, 0],
                     [3, 3, 3, 3, 0, 1, 2, 3]], jnp.int32)
    y = jnp.asarray([[4.0, 0, 0, 0, 0, 2.0, 2.0, 0],
                     [0, 0, 0, 0, 1.0, 1.0, 1.0, 1.0]])
    spec = make_block_diffusion_lm_spec(Fixed(), x[:1], 4, mask_id)
    batch = {"x": x, "y": y, "mask": jnp.ones((2,))}
    loss, (_, m) = spec.loss_fn({"params": {}}, batch, None, True)
    np.testing.assert_array_equal(
        np.asarray(seen["ids"]),
        [[0, 1, 2, 3, 0, 0, 0, 0, 4, 1, 2, 3, 0, 4, 4, 0],
         [3, 3, 3, 3, 0, 1, 2, 3, 3, 3, 3, 3, 4, 4, 4, 4]])
    hit = -np.log(np.exp(2) / (np.exp(2) + 4))    # the preferred id
    miss = -np.log(1 / (np.exp(2) + 4))
    # row 0: position 0 (hit, weight 4), 5 and 6 (clean id 0: misses);
    # row 1: positions 4..7 hit
    want = (4 * hit + 2 * miss + 2 * miss + 4 * hit) / (2 * length)
    assert float(loss) == pytest.approx(want, rel=1e-6)
    assert float(m["count"]) == 7 and float(m["correct"]) == 5
    assert float(m["loss_sum"]) == pytest.approx(5 * hit + 2 * miss, rel=1e-6)
    assert float(m["bd_positions"]) == 2 * 2 * length
    assert block_diffusion_counters(m) == {"bd.loss_tokens": 7.0,
                                           "bd.positions": 32.0}
    assert block_diffusion_counters({"loss_sum": 1.0}) == {}
    # a row of padding counts nothing and the mean is over the other
    batch["mask"] = jnp.asarray([1.0, 0.0])
    loss, (_, m) = spec.loss_fn({"params": {}}, batch, None, True)
    assert float(loss) == pytest.approx((4 * hit + 4 * miss) / length,
                                        rel=1e-6)
    assert float(m["count"]) == 3 and float(m["bd_positions"]) == 2 * length
    with pytest.raises(ValueError, match="blocks of 8"):
        make_block_diffusion_lm_spec(Fixed(), x[:1], 8, mask_id)


# -- the mask's two properties, through the whole model ------------------------

def test_changing_the_noised_copy_never_moves_a_clean_output(toy):
    """``x_0`` never sees ``x_t``: the hidden states of the clean half
    (read before the head keeps the noised half) stay bit for bit."""
    ids = jnp.concatenate([toy.x, jnp.where(toy.y > 0, MASK_ID, toy.x)], 1)
    other = ids.at[:, L:].set((ids[:, L:] + 7) % 90 + 1)
    layer = dec._DecoderLayer(toy.model.cfg, False)
    embed = toy.weights["tok_embed/embedding"]
    run = lambda i: layer.apply(
        {"params": toy.params["layer0"]}, embed[i], BD(L, 4),
        jnp.arange(2 * L) % L)
    a, b = run(ids), run(other)
    np.testing.assert_array_equal(np.asarray(a[:, :L]), np.asarray(b[:, :L]))
    assert not np.array_equal(np.asarray(a[:, L:]), np.asarray(b[:, L:]))


def test_changing_a_later_block_never_moves_an_earlier_blocks_logits(toy):
    ids = jnp.concatenate([toy.x, jnp.where(toy.y > 0, MASK_ID, toy.x)], 1)
    cut = 8                                  # blocks 2 and 3 change
    late = (jnp.arange(2 * L) % L) >= cut
    other = jnp.where(late, (ids + 11) % 90 + 1, ids)
    run = lambda i: toy.model.apply({"params": toy.params}, i)
    a, b = run(ids), run(other)
    np.testing.assert_array_equal(np.asarray(a[:, :cut]),
                                  np.asarray(b[:, :cut]))
    assert not np.array_equal(np.asarray(a[:, cut:]), np.asarray(b[:, cut:]))


# -- the experts' share --------------------------------------------------------

def _expert_layer(seed=5):
    s = REF.sizes(TOY)
    full = REF.make_weights(TOY, seed)
    p = {k[len("layer1/moe/"):]: v for k, v in full.items()
         if k.startswith("layer1/moe/")}
    x = jax.random.normal(jax.random.PRNGKey(seed), (N * 2 * L, 64))
    return s, p, x


def _apply_experts(cfg, p, x):
    module = dec.RoutedExperts(dec.DecoderConfig.from_dict(cfg))
    out, sown = module.apply({"params": nest(p)}, x, mutable=["metrics"])
    return out, {k: float(v) for k, v in sown["metrics"].items()}


@pytest.mark.parametrize("renormalise", [True, False])
def test_softmax_router_and_experts_match_the_reference(renormalise):
    s, p, x = _expert_layer()
    cfg = dict(TOY, norm_topk_prob=renormalise)
    got, sown = _apply_experts(cfg, p, x)
    np.testing.assert_allclose(
        got, REF.experts(p, "", x, dict(s, norm_topk=renormalise)),
        atol=5e-6)
    weight = np.asarray(REF.route(p, "", x, dict(s, norm_topk=renormalise)))
    assert ((weight > 0).sum(axis=1) == 4).all()
    if renormalise:
        np.testing.assert_allclose(weight.sum(axis=1), 1.0, rtol=1e-5)
    assert sown["moe_rows_held"] == 4 * x.shape[0]
    assert sown["moe_dropped"] == 0
    assert sown["moe_overflow"] == 0
    assert sown["moe_capacity_rows"] == 4 * x.shape[0]


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips with 2 of the 16 experts each: their parts of one
    layer's output add up to the uncut reference's layer (no part is
    computed on every chip: the family has no shared expert)."""
    s, p, x = _expert_layer()
    whole = REF.experts(p, "", x, s)
    total, rows = jnp.zeros_like(whole), 0.0
    for first in range(0, 16, 2):
        mine = dict(p, **{k: p[k][first:first + 2]
                          for k in ("w_gate", "w_up", "w_down")})
        share = dict(TOY, router_experts=16, n_routed_experts=2,
                     experts_held=[first, 2])
        out, sown = _apply_experts(share, mine, x)
        # the reference is given the same share
        np.testing.assert_allclose(
            out, REF.experts(mine, "", x, REF.sizes(share)), atol=5e-6)
        total, rows = total + out, rows + sown["moe_rows_held"]
    np.testing.assert_allclose(total, whole, atol=1e-5)
    assert rows == x.shape[0] * 4   # every assignment computed on some chip


# -- what is not computed is refused -------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
    ("use_sliding_window", True), ("sliding_window", 4096),
    ("rope_scaling", {"type": "yarn"}), ("tie_word_embeddings", True),
    ("layer_types", ["full_attention", "sliding_attention"]),
    ("attention_bias", True), ("model_type", "qwen3_next")])
def test_a_file_of_another_member_of_the_family_is_refused(key, value):
    with pytest.raises(NotImplementedError, match=key):
        dec.DecoderConfig.from_dict(dict(TOY, **{key: value}))


def test_what_a_family_needs_is_asked_for_and_the_other_familys_is_not():
    with pytest.raises(ValueError, match="head_dim"):
        dec.DecoderConfig.from_dict(
            {k: v for k, v in TOY.items() if k != "head_dim"})
    cfg = dec.DecoderConfig.from_dict(TOY)
    assert (cfg.router_width, cfg.held, cfg.scoring_func) \
        == (16, (0, 16), "softmax")
    assert cfg.n_shared_experts == 0 and cfg.first_k_dense_replace == 0
    # block diffusion is the second family's: latent attention refuses it
    with pytest.raises(NotImplementedError, match="block_length"):
        dec.DecoderConfig.from_dict({
            "model_type": "deepseek_v3", "block_length": 4})
    # whole blocks, both copies
    model = dec.DecoderLM(cfg)
    with pytest.raises(ValueError, match="whole blocks"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 12), jnp.int32))


def test_the_layers_read_features_not_the_familys_name():
    cfg = dec.DecoderConfig.from_dict(TOY)
    assert cfg.attention == "grouped" and not hasattr(cfg, "model_type")
    # a latent layer under the block mask is refused for what it lacks
    # (positions in its rotary turn), by the module that lacks it
    latent = dataclasses.replace(
        cfg, attention="latent", qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, kv_lora_rank=16)
    with pytest.raises(NotImplementedError, match="causal mask alone"):
        dec.DecoderLM(latent).init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 16), jnp.int32))


# -- the flash kernels under the block-diffusion mask ---------------------------

def _dense(q, k, v, length, block):
    """Grouped-query attention under the three rules, materialised: query
    head ``h`` reads key/value head ``h // group``."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    seen = REF.visible(length, block)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


WHOLE = 10 ** 6
BD_TILES = {
    "16x16": (16, 16, None),
    "major32_minor16": (None, None, Schedule(Tile(16, 32, 16),
                                             Tile(32, 32, 16),
                                             Tile(16, 32, 16))),
    "whole_minor16": (None, None, Schedule(Tile(32, WHOLE, 16),
                                           Tile(16, WHOLE, 16),
                                           Tile(32, WHOLE, 16))),
    "chosen": (None, None, None),
}
#: (L, block, query heads, key/value heads): L a multiple of the 16-row
#: tile and not (40, 36: a tile holds rows of both copies), the block
#: length of the release and a long one, 32 heads over 4
BD_SHAPES = {"L32_b4": (32, 4, 2, 2), "L40_b4": (40, 4, 2, 2),
             "L64_b32": (64, 32, 2, 2), "L36_b6": (36, 6, 2, 2),
             "L32_b4_32over4": (32, 4, 32, 4), "L64_b32_8over2": (64, 32, 8, 2)}


@pytest.mark.parametrize("shape", list(BD_SHAPES))
@pytest.mark.parametrize("tiles", list(BD_TILES))
def test_flash_under_the_block_mask_is_the_dense_masked_softmax(tiles, shape):
    length, block, heads, kv_heads = BD_SHAPES[shape]
    key = jax.random.PRNGKey(31)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (1, 2 * length, h, 16))
               for i, h in enumerate((heads, kv_heads, kv_heads)))
    weight = jnp.cos(jnp.arange(16))

    def flash(q, k, v):
        k, v = (jnp.repeat(a, heads // kv_heads, axis=2) for a in (k, v))
        return pa.flash_attention(q, k, v, BD(length, block), None,
                                  *BD_TILES[tiles])

    want = _dense(q, k, v, length, block)
    np.testing.assert_allclose(flash(q, k, v), want, atol=2e-5, rtol=2e-5)
    loss = lambda fn: lambda q, k, v: jnp.sum(fn(q, k, v) * weight)
    got_g = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(loss(lambda q, k, v: _dense(q, k, v, length, block)),
                      argnums=(0, 1, 2))(q, k, v)
    for g, w, like in zip(got_g, want_g, (q, k, v)):
        assert g.shape == like.shape
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


def test_the_mask_kind_is_checked_against_the_shapes():
    q = jnp.zeros((1, 24, 2, 16))
    with pytest.raises(ValueError, match="whole blocks"):
        pa.flash_attention(q, q, q, BD(16, 4))       # 24 is not 2 x 16
    with pytest.raises(ValueError, match="whole blocks"):
        pa.flash_attention(q, q, q, BD(12, 8))       # 12 is not whole blocks


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_no_block_outside_the_two_ranges_is_visited(kernel):
    """The inner loop's passes, counted from the schedule: with 512 x 512
    tiles at L 2048 (4,096 positions, an 8 x 8 square of tiles) 24 of the
    64, not the 36 of a causal band over 2 L: 10 for the clean rows'
    block-causal band, and for every noised tile its clean tiles up to
    the boundary one and its own."""
    schedule, steps = pa.flash_schedule(4096, 4096, 128, 128, jnp.bfloat16)
    tile = getattr(schedule, kernel)
    assert tile == Tile(512, 4096, 512) and steps == (8, 8, 8)
    assert pa.band_passes(kernel, tile, 4096, 4096, BD(2048, 4)) == 24
    assert pa.band_passes(kernel, tile, 4096, 4096, True) == 36
    assert pa.band_passes(kernel, tile, 4096, 4096, False) == 64
    # the same count where the grid keeps its inner axis
    inner = Tile(512, 1024, 512)
    assert pa.band_passes(kernel, inner, 4096, 4096, BD(2048, 4)) == 24
    # a block as long as the tile: the boundary falls between tiles, so a
    # noised tile takes the clean tiles before it and its own
    assert pa.band_passes(kernel, tile, 4096, 4096, BD(2048, 512)) == 20
    # L no multiple of the tile (1,280: 5 tiles of 512 over both copies):
    # the one tile that holds rows of both copies takes every block,
    # masked; the others keep their two ranges
    ragged = pa.band_passes(kernel, Tile(512, 2560, 512), 2560, 2560,
                            BD(1280, 4))
    assert ragged == (16 if kernel == "dkv" else 15)


@pytest.mark.parametrize("t,width,want", [
    (2048, 128, (Tile(512, 2048, 512), (4, 4, 4))),
    (2048, 256, (Tile(512, 2048, 512), (4, 4, 4))),
    (8192, 256, (None, (32, 64, 64))),
    (80, 128, (Tile(80, 128, 128), (1, 1, 1)))])
def test_flash_schedules_causal_result_is_what_it_was(t, width, want):
    """The schedule does not know the mask kind: the accepted cells'
    shapes get the tiles PR 30 pinned, and their causal band the passes
    it had (10 of 16 at T 2048)."""
    schedule, steps = pa.flash_schedule(t, t, width, 128, jnp.bfloat16)
    tile, want_steps = want
    assert steps == want_steps
    if tile is not None:
        assert schedule == Schedule(tile, tile, tile)
    if t == 2048:
        for kernel in ("fwd", "dq", "dkv"):
            assert pa.band_passes(kernel, getattr(schedule, kernel), t, t,
                                  True) == 10
            assert pa.band_passes(kernel, getattr(schedule, kernel), t, t,
                                  False) == 16


def _pallas_calls(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, out)
    return out


def test_one_forward_and_two_backward_launches_a_layer_step(toy):
    spec = make_block_diffusion_lm_spec(toy.model, toy.x[:1], 4, MASK_ID)
    batch = {"x": toy.x, "y": toy.y, "mask": jnp.ones((N,), jnp.float32)}
    grad = jax.grad(lambda p: spec.loss_fn({"params": p}, batch, None,
                                           True)[0])
    calls = _pallas_calls(jax.make_jaxpr(grad)(toy.params).jaxpr, [])
    flash = [c for c in calls if c and c.startswith("flash_")]
    layers = TOY["num_hidden_layers"]
    assert sorted(flash) == sorted(
        ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"] * layers)


# -- through the factory's family and through a federated round ----------------

@pytest.fixture(scope="module")
def federated():
    """Two rounds through ``FedAvgAPI`` and the bucketed stream, built as
    the benchmark's family builds its cell, beside the reference's."""
    from benchmarks.families import sdar_moe_lm as family
    from fedml_tpu.observability.tracing import Tracer, set_tracer

    config = dict(TOY, router_experts=16, n_routed_experts=8,
                  experts_held=[4, 8], as_run={"compute_dtype": "float32"})
    traffic = {"sequences_per_client": [3, 5], "seq_len": L,
               "batch_size": 1, "epochs": 1, "client_chunk": 1, "lr": 0.1,
               "wd": 0.0}
    seed = 3_100_000_123
    tracer = Tracer()
    before = set_tracer(tracer)
    try:
        cell = family.build(config, traffic, seed, REF)
        rounds = [cell.api.train_one_round() for _ in range(2)]
        state = cell.snapshot()
        mode, info = cell.api.runner.mode, cell.api._last_info
    finally:
        set_tracer(before)
    want = REF.run_rounds(config, traffic, seed, 2, cell.feed(2))
    clients = REF.make_clients(config, traffic, seed)
    return types.SimpleNamespace(
        rounds=rounds, state=state, want=want, tracer=tracer, mode=mode,
        info=info, masked=sum(int((c["y"] > 0).sum()) for c in clients),
        work=cell.work_per_round)


def test_a_federated_round_matches_the_references(federated):
    f = federated
    assert f.mode == "bucketed" and f.info["fold"] == "device"
    for got, want in zip(f.rounds, f.want["loss"]):
        assert got["Train/Loss"] == pytest.approx(want, rel=2e-6)
    for leaf, norm in f.want["change_norms"][-1].items():
        change = np.linalg.norm(np.asarray(f.state[leaf], np.float64)
                                - np.asarray(f.want["init"][leaf]))
        # (a scale leaf of 64 elements near 1 moves by 1e-5 an element:
        # float32's spacing there is 1e-7)
        assert change == pytest.approx(norm, rel=2e-4, abs=3e-7), leaf


def test_the_local_train_span_carries_the_objectives_counters(federated):
    f = federated
    trains = [s for s in f.tracer.finished_spans()
              if s.name == "local-train"]
    assert len(trains) == 2
    for span, record in zip(trains, f.rounds):
        assert span.attrs["bd.loss_tokens"] == f.masked
        assert span.attrs["bd.positions"] == 2 * f.work["tokens"]
        assert span.attrs["moe_dropped"] == 0
        assert span.attrs["moe_rows_held"] == record["moe_rows_held"]
        # half the router held: every assignment has its row and there
        # is no fallback to take
        assert span.attrs["moe_overflow"] == record["moe_overflow"] == 0
        assert span.attrs["moe_capacity_rows"] \
            == 2 * 2 * f.work["tokens"] * 4
        # 2 layers, 2 L positions a sequence; 4 of 16 a position, 8 held
        assert 0.6 < record["moe_rows_held"] \
            / (2 * 2 * f.work["tokens"] * 4 * 8 / 16) < 1.4
        assert span.attrs["moe_load_max_over_mean"] >= 1.0
