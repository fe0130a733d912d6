"""The ``deepseek_v3`` decoder (latent attention, routed experts held in
part, a shared expert) against its plain reference, which is loaded by
path from beside the benchmark's configuration and imports nothing of
the program: seeded random weights, float32, a small size on the CPU."""

import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families.common import nest
from fedml_tpu.algorithms.specs import make_seq_classification_spec
from fedml_tpu.models import deepseek_v3 as dsv3
from fedml_tpu.observability.routing import routing_counters
from fedml_tpu.ops import pallas_attention as pa
from fedml_tpu.ops.grouped_matmul import grouped_matmul

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _load(os.path.join(ROOT, "benchmarks", "configs",
                         "deepseek_v3_lm_reference.py"),
            "deepseek_v3_lm_reference_for_tests")

TOY = {
    "hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 16, "n_shared_experts": 1, "num_experts_per_tok": 3,
    "first_k_dense_replace": 1, "num_hidden_layers": 3, "vocab_size": 97,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "norm_topk_prob": True,
    "q_lora_rank": None, "rope_interleave": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
}
LEAVES = sorted(REF.param_shapes(TOY))
B, T = 2, 16


def flat_of(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def toy():
    s = REF.sizes(TOY)
    weights = REF.make_weights(TOY, 27)
    model = dsv3.DeepseekV3LM(dsv3.DecoderConfig.from_dict(TOY))
    key = jax.random.PRNGKey(1)
    x = jax.random.randint(key, (B, T), 1, TOY["vocab_size"])
    y = jax.random.randint(jax.random.fold_in(key, 1), (B, T), 1,
                           TOY["vocab_size"])
    return types.SimpleNamespace(s=s, weights=weights, model=model, x=x,
                                 y=y, params=nest(weights))


@pytest.fixture(scope="module")
def grads(toy):
    spec = make_seq_classification_spec(toy.model, toy.x[:1])
    batch = {"x": toy.x, "y": toy.y, "mask": jnp.ones((B,), jnp.float32)}

    def loss(p):
        value, (_, metrics) = spec.loss_fn({"params": p}, batch, None, True)
        return value, metrics

    (value, metrics), g = jax.value_and_grad(loss, has_aux=True)(toy.params)
    (ref_value, _), ref_g = jax.value_and_grad(
        lambda p: REF.step_loss(p, toy.x, toy.y, toy.s), has_aux=True)(
            toy.weights)
    return types.SimpleNamespace(value=value, metrics=metrics,
                                 prog=flat_of(g), ref_value=ref_value,
                                 ref=ref_g)


def test_the_parameter_tree_is_the_references(toy):
    init = toy.model.init(jax.random.PRNGKey(0), toy.x)
    shapes = {k: v.shape for k, v in flat_of(init["params"]).items()}
    assert shapes == {k: tuple(v)
                      for k, v in REF.param_shapes(TOY).items()}
    # stacked expert leaves, one row a held expert
    assert shapes["layer1/moe/w_gate"] == (16, 64, 32)


def test_logits_match_the_reference(toy):
    logits = toy.model.apply({"params": toy.params}, toy.x)
    want = REF.forward(toy.weights, toy.x, toy.s)
    assert logits.shape == (B, T, TOY["vocab_size"])
    assert logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want, atol=2e-6)


def test_the_loss_matches_the_reference(grads):
    np.testing.assert_allclose(grads.value, grads.ref_value, rtol=1e-6)
    assert float(grads.metrics["count"]) == B * T


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(grads, leaf):
    want = np.asarray(grads.ref[leaf])
    got = np.asarray(grads.prog[leaf])
    scale = max(float(np.max(np.abs(want))), 1e-12)
    if leaf.endswith("e_score_correction_bias"):
        # the bias steers the choice only: the loss gives it no gradient
        assert not want.any() and not got.any()
        return
    assert float(np.max(np.abs(want))) > 0
    assert float(np.max(np.abs(got - want))) <= 2e-5 * scale, leaf


def test_reference_blocks_of_rows_change_nothing(toy):
    s = dict(toy.s, rows_at_a_time=1)
    np.testing.assert_allclose(REF.forward(toy.weights, toy.x, s),
                               REF.forward(toy.weights, toy.x, toy.s),
                               atol=1e-6)


# -- rotary positions and the shared rotary key head -------------------------

def test_rotary_turns_interleaved_pairs():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 3, 8))
    got = np.asarray(dsv3.rotary_interleaved(x, 1e6))
    xn = np.asarray(x, np.float64)
    for t in range(5):
        for i in range(4):
            ang = t * 1e6 ** (-2 * i / 8)
            a, b = xn[:, t, :, 2 * i], xn[:, t, :, 2 * i + 1]
            np.testing.assert_allclose(
                got[:, t, :, 2 * i], a * np.cos(ang) - b * np.sin(ang),
                atol=1e-5)
            np.testing.assert_allclose(
                got[:, t, :, 2 * i + 1], b * np.cos(ang) + a * np.sin(ang),
                atol=1e-5)
    # the reference's own writing of it, position on the axis before last
    want = REF.rope(jnp.swapaxes(x, 1, 2), 1e6)
    np.testing.assert_allclose(got, np.swapaxes(want, 1, 2), atol=1e-5)


def test_latent_attention_and_its_one_shared_rotary_key_head(toy):
    cfg = dsv3.DecoderConfig.from_dict(TOY)
    attn = dsv3.LatentAttention(cfg)
    p = {k[len("layer1/attn/"):]: v for k, v in toy.weights.items()
         if k.startswith("layer1/attn/")}
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, 64))
    got = attn.apply({"params": nest(p)}, x)
    want = REF.attention(toy.weights, "layer1/attn/", x, toy.s)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the rotary key columns of kv_a_proj are ONE head: a change to them
    # reaches every head's rows of the output projection's input
    bumped = dict(p)
    kernel = np.array(p["kv_a_proj/kernel"])
    kernel[:, cfg.kv_lora_rank:] += 0.5
    bumped["kv_a_proj/kernel"] = jnp.asarray(kernel)
    eye = dict(bumped, **{"o_proj/kernel": jnp.eye(64)})
    base = dict(p, **{"o_proj/kernel": jnp.eye(64)})
    delta = np.abs(np.asarray(attn.apply({"params": nest(eye)}, x)
                              - attn.apply({"params": nest(base)}, x)))
    per_head = delta.reshape(B, T, 4, 16).max(axis=(0, 1, 3))
    assert (per_head > 1e-4).all(), per_head


# -- the router and the experts held -----------------------------------------

def _layer(cfg_over=None, bias=None, seed=5, held=None):
    """One expert layer's parameters under the reference's names, the
    program's module over them, and its tokens."""
    cfg = dict(TOY, **(cfg_over or {}))
    if held is not None:
        cfg.update(router_experts=16, n_routed_experts=held[1],
                   experts_held=list(held))
    s = REF.sizes(cfg)
    full = REF.make_weights(dict(TOY, **(cfg_over or {})), seed)
    p = {k[len("layer1/moe/"):]: v for k, v in full.items()
         if k.startswith("layer1/moe/")}
    if bias is not None:
        p["e_score_correction_bias"] = jnp.asarray(bias, jnp.float32)
    if held is not None:   # the stacked leaves hold the share's experts
        p.update({k: p[k][held[0]:held[0] + held[1]]
                  for k in ("w_gate", "w_up", "w_down")})
    x = jax.random.normal(jax.random.PRNGKey(seed), (B * T, 64))
    return cfg, s, p, x


def _apply_experts(cfg, p, x):
    module = dsv3.RoutedExperts(dsv3.DecoderConfig.from_dict(cfg))
    out, sown = module.apply({"params": nest(p)}, x, mutable=["metrics"])
    return out, {k: float(v) for k, v in sown["metrics"].items()}


@pytest.mark.parametrize("case", [
    "as_published", "no_renormalisation", "scaling_factor_one",
    "a_bias_that_steers"])
def test_router_and_experts_match_the_reference(case):
    over = {"no_renormalisation": {"norm_topk_prob": False},
            "scaling_factor_one": {"routed_scaling_factor": 1.0}}.get(case)
    bias = None
    if case == "a_bias_that_steers":
        bias = 0.5 * np.random.default_rng(0).standard_normal(16)
    cfg, s, p, x = _layer(over, bias)
    got, _ = _apply_experts(cfg, p, x)
    want = REF.expert_ffn(p, "", x, s)
    np.testing.assert_allclose(got, want, atol=5e-6)


def test_the_bias_is_in_the_choice_and_not_in_the_weight():
    bias = np.zeros(16)
    bias[[2, 7, 11]] = 10.0     # every token chooses experts 2, 7 and 11
    cfg, s, p, x = _layer(None, bias)
    weight = np.asarray(REF.route(p, "", x, s))
    scores = np.asarray(jax.nn.sigmoid(
        jnp.matmul(x, p["router/kernel"], precision="highest")))
    assert ((weight > 0).sum(axis=1) == 3).all()
    assert (weight[:, [2, 7, 11]] > 0).all()
    chosen = scores[:, [2, 7, 11]]
    np.testing.assert_allclose(
        weight[:, [2, 7, 11]],
        2.448 * chosen / chosen.sum(axis=1, keepdims=True), rtol=1e-5)
    got, _ = _apply_experts(cfg, p, x)
    np.testing.assert_allclose(got, REF.expert_ffn(p, "", x, s), atol=5e-6)


def test_no_token_is_dropped_when_all_choose_the_same_experts():
    bias = np.zeros(16)
    bias[[0, 1, 5]] = 10.0
    cfg, s, p, x = _layer(None, bias)
    got, sown = _apply_experts(cfg, p, x)
    n = B * T
    assert sown["moe_rows_held"] == 3 * n     # every assignment has a row
    assert sown["moe_load_max"] == n          # three experts hold them all
    assert sown["moe_dropped"] == 0
    np.testing.assert_allclose(got, REF.expert_ffn(p, "", x, s), atol=5e-6)
    # and when every chosen expert is held elsewhere, nothing is computed
    cfg2, s2, p2, _ = _layer(None, bias, held=(8, 8))
    got2, sown2 = _apply_experts(cfg2, p2, x)
    assert sown2["moe_rows_held"] == 0 and sown2["moe_dropped"] == 0
    shared_only = REF._gated(x, p2, "shared/", "f32")
    np.testing.assert_allclose(got2, shared_only, atol=5e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips with 4 of the 16 experts each: their routed parts, with
    the shared expert counted once, are the uncut reference's layer."""
    cfg, s, p, x = _layer()
    whole = REF.expert_ffn(p, "", x, s)
    shared = REF._gated(x, p, "shared/", "f32")
    total, rows = jnp.zeros_like(whole), 0.0
    for first in (0, 4, 8, 12):
        mine = dict(p, **{k: p[k][first:first + 4]
                          for k in ("w_gate", "w_up", "w_down")})
        share_cfg = dict(TOY, router_experts=16, n_routed_experts=4,
                         experts_held=[first, 4])
        out, sown = _apply_experts(share_cfg, mine, x)
        # the reference is given the same share
        np.testing.assert_allclose(
            out, REF.expert_ffn(mine, "", x, REF.sizes(share_cfg)),
            atol=5e-6)
        total = total + (out - shared)
        rows += sown["moe_rows_held"]
    np.testing.assert_allclose(total + shared, whole, atol=1e-5)
    assert rows == B * T * 3   # every assignment computed on some chip


def test_experts_held_outside_the_router_is_an_error():
    cfg = dict(TOY, router_experts=16, n_routed_experts=4,
               experts_held=[14, 4])
    module = dsv3.RoutedExperts(dsv3.DecoderConfig.from_dict(cfg))
    with pytest.raises(ValueError, match="outside the router"):
        module.init(jax.random.PRNGKey(0), jnp.zeros((4, 64)))


# -- the three counters ------------------------------------------------------

def test_counters_equal_a_numpy_count(toy, grads):
    """``moe_rows_held`` against a count made from the reference's own
    routing of the same tokens, layer by layer."""
    rows = load_max = 0.0
    x = toy.weights["tok_embed/embedding"][toy.x]
    for i in range(3):
        pre = f"layer{i}/"
        x = x + REF.attention(toy.weights, pre + "attn/", REF._rms_norm(
            x, toy.weights[pre + "attn_norm/scale"], 1e-6), toy.s)
        y = REF._rms_norm(x, toy.weights[pre + "ffn_norm/scale"], 1e-6)
        if i == 0:
            x = x + REF._gated(y, toy.weights, pre + "mlp/", "f32")
            continue
        tokens = y.reshape(B * T, 64)
        chosen = np.asarray(REF.route(toy.weights, pre + "moe/", tokens,
                                      toy.s)) > 0
        rows += chosen.sum()
        load_max += chosen.sum(axis=0).max()
        x = x + REF.expert_ffn(toy.weights, pre + "moe/", tokens,
                               toy.s).reshape(B, T, 64)
    assert rows == 2 * B * T * 3   # the toy holds all 16 of its experts
    counters = routing_counters(grads.metrics)
    assert set(counters) == {"moe_rows_held", "moe_load_max_over_mean",
                             "moe_dropped", "moe_overflow",
                             "moe_capacity_rows"}
    assert counters["moe_rows_held"] == rows
    assert counters["moe_dropped"] == 0
    # all 16 experts held: the buffer is every assignment's, nothing else
    assert counters["moe_overflow"] == 0
    assert counters["moe_capacity_rows"] == rows
    assert counters["moe_load_max_over_mean"] == pytest.approx(
        load_max / (rows / 16))
    assert routing_counters({"loss_sum": 1.0}) == {}


def test_a_step_of_padding_counts_no_rows(toy):
    spec = make_seq_classification_spec(toy.model, toy.x[:1])
    batch = {"x": toy.x, "y": toy.y, "mask": jnp.zeros((B,), jnp.float32)}
    _, (_, metrics) = spec.loss_fn({"params": toy.params}, batch, None, True)
    assert float(metrics["moe_rows_held"]) == 0.0
    state = spec.init_fn(jax.random.PRNGKey(0))
    assert set(state) == {"params"}   # sown counters are no model state


# -- the grouped product -----------------------------------------------------

def _dense_groups(lhs, rhs, sizes):
    ends = np.cumsum(sizes)
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float64)
    for g, (a, b) in enumerate(zip(ends - sizes, ends)):
        out[a:b] = np.asarray(lhs[a:b], np.float64) \
            @ np.asarray(rhs[g], np.float64)
    return out


@pytest.mark.parametrize("sizes", [(10, 0, 37, 20), (96, 0, 0, 0),
                                   (0, 0, 0, 0), (24, 24, 24, 24)])
def test_grouped_matmul_against_a_loop(sizes):
    key = jax.random.PRNGKey(6)
    lhs = jax.random.normal(key, (96, 64))
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (4, 64, 48))
    gs = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul(lhs, rhs, gs)
    np.testing.assert_allclose(got, _dense_groups(lhs, rhs, np.array(sizes)),
                               atol=1e-4)
    assert not np.asarray(got[sum(sizes):]).any()   # rows of no group

    def loss(fn):
        return lambda a, b: jnp.sum(jnp.sin(fn(a, b)))

    def plain(a, b):
        ends = jnp.cumsum(gs)
        row = jnp.arange(96)[:, None]
        return sum(jnp.where((row >= ends[g] - gs[g]) & (row < ends[g]),
                             jnp.matmul(a, b[g], precision="highest"), 0.0)
                   for g in range(4))

    got_g = jax.grad(loss(lambda a, b: grouped_matmul(a, b, gs)),
                     argnums=(0, 1))(lhs, rhs)
    want_g = jax.grad(loss(plain), argnums=(0, 1))(lhs, rhs)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(g, w, atol=1e-4)


def test_grouped_matmul_batches_over_lanes_with_their_own_groups():
    key = jax.random.PRNGKey(7)
    lhs = jax.random.normal(key, (2, 40, 16))          # 40: not a tile
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (2, 3, 16, 24))
    gs = jnp.asarray([[5, 20, 7], [0, 40, 0]], jnp.int32)

    def loss(a, b, g):
        return jnp.sum(grouped_matmul(a, b, g) ** 2)

    got = jax.vmap(jax.grad(loss, argnums=(0, 1)))(lhs, rhs, gs)
    for lane in range(2):
        want = jax.grad(loss, argnums=(0, 1))(lhs[lane], rhs[lane], gs[lane])
        for g, w in zip(got, want):
            np.testing.assert_allclose(g[lane], w, atol=1e-5)
    out = jax.vmap(grouped_matmul)(lhs, rhs, gs)
    np.testing.assert_allclose(
        out[0], _dense_groups(lhs[0], rhs[0], np.array([5, 20, 7])),
        atol=1e-4)


# -- the widened flash kernels -----------------------------------------------

def _plain_attention(q, k, v, causal, scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


def _qkv(dqk, dv, t=40):
    key = jax.random.PRNGKey(27)
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (2, t, 2, dqk))
            for i in (0, 1))
    return q, k, jax.random.normal(jax.random.fold_in(key, 2), (2, t, 2, dv))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("widths", [(24, 16), (16, 24)])
def test_flash_takes_the_score_width_apart_from_the_value_width(
        causal, widths):
    q, k, v = _qkv(*widths)
    scale = widths[0] ** -0.5
    want = _plain_attention(q, k, v, causal, scale)
    got = pa.flash_attention(q, k, v, causal, None, 16, 16)
    assert got.shape == v.shape
    np.testing.assert_allclose(got, want, atol=2e-5)
    weight = jnp.cos(jnp.arange(widths[1]))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * weight)

    got_g = jax.grad(loss(lambda q, k, v: pa.flash_attention(
        q, k, v, causal, None, 16, 16)), argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(loss(lambda q, k, v: _plain_attention(
        q, k, v, causal, scale)), argnums=(0, 1, 2))(q, k, v)
    for g, w, like in zip(got_g, want_g, (q, k, v)):
        assert g.shape == like.shape
        np.testing.assert_allclose(g, w, atol=5e-5)


def test_zero_columns_on_the_scores_are_exact_and_the_scale_is_passed():
    q, k, v = _qkv(24, 16)
    pad = lambda x: jnp.pad(x, ((0, 0),) * 3 + ((0, 8),))
    want = pa.flash_attention(q, k, v, True, 24 ** -0.5, 16, 16)
    got = pa.flash_attention(pad(q), pad(k), v, True, 24 ** -0.5, 16, 16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # what the wrapper pads on hardware, and what it leaves alone
    assert pa._score_pad(192, 128, False) == 64
    assert pa._score_pad(128, 128, False) == 0
    assert pa._score_pad(256, 128, False) == 0
    assert pa._score_pad(24, 16, True) == 0
    pa._require_hw_head_dim(128, False)          # values of 128: runs
    with pytest.raises(ValueError, match="multiple of 128"):
        pa._require_hw_head_dim(16, False)       # still fails loudly


def _pallas_calls(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"], eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, out)
    return out


@pytest.mark.parametrize("tag,causal", [("causal", True), ("full", False)])
def test_flash_at_one_width_is_the_program_it_was(tag, causal):
    """Until PR 30 this pinned a hash of the traced programs, which a
    change of the tile schedule cannot keep. What it stood for stays:
    explicit blocks ``(16, 16)`` mean one 16 x 16 score tile a grid step
    in all three kernels -- the parent's grids -- and give the parent's
    values (``flash_t32_parent.npz``: PR 30's parent commit at the second
    length the hash covered, T 32, where no tile is ragged; T 40 is the
    next test's)."""
    f = lambda q, k, v: pa.flash_attention(q, k, v, causal, None, 16, 16)
    weighted = lambda q, k, v: jnp.sum(f(q, k, v) * jnp.cos(jnp.arange(16)))
    grad = jax.grad(weighted, argnums=(0, 1, 2))
    for t in (32, 40):
        s = jax.ShapeDtypeStruct((2, t, 2, 16), jnp.float32)
        n = -(-t // 16)
        assert _pallas_calls(jax.make_jaxpr(grad)(s, s, s).jaxpr, []) == [
            ("flash_fwd", (2, 2, n, n)), ("flash_bwd_dq", (2, 2, n, n)),
            ("flash_bwd_dkv", (2, 2, n, n))]
    gold = np.load(os.path.join(ROOT, "tests", "fixtures",
                                "flash_t32_parent.npz"))
    key = jax.random.PRNGKey(27)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, 32, 2, 16),
                                 jnp.float32) for i in range(3))
    for name, arr in zip(("o", "dq", "dk", "dv"),
                         [f(q, k, v)] + list(grad(q, k, v))):
        np.testing.assert_allclose(np.asarray(arr), gold[f"{tag}_{name}"],
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("tag,causal", [("causal", True), ("full", False)])
def test_flash_at_one_width_gives_the_parents_values(tag, causal):
    gold = np.load(os.path.join(ROOT, "tests", "fixtures",
                                "flash_dqk_eq_dv_parent.npz"))
    key = jax.random.PRNGKey(27)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, 40, 2, 16),
                                 jnp.float32) for i in range(3))
    f = lambda q, k, v: pa.flash_attention(q, k, v, causal, None, 16, 16)
    got = [f(q, k, v)] + list(jax.grad(
        lambda q, k, v: jnp.sum(f(q, k, v) * jnp.cos(jnp.arange(16))),
        argnums=(0, 1, 2))(q, k, v))
    for name, arr in zip(("o", "dq", "dk", "dv"), got):
        np.testing.assert_allclose(np.asarray(arr), gold[f"{tag}_{name}"],
                                   rtol=1e-6, atol=1e-7)


# -- through the factory and through a federated round -----------------------

def test_create_model_builds_it_from_a_configuration_file(tmp_path):
    from fedml_tpu.models.factory import create_model

    path = tmp_path / "toy.json"
    path.write_text(json.dumps(dict(TOY, n_layer=2, vocab_size=1234)))
    args = types.SimpleNamespace(model_config=str(path), model_dtype="bf16")
    model = create_model(args, "deepseek_v3", output_dim=97)
    assert isinstance(model, dsv3.DeepseekV3LM)
    assert model.cfg.vocab_size == 97 and model.cfg.num_hidden_layers == 2
    assert model.dtype == jnp.bfloat16
    logits = model.apply(model.init(jax.random.PRNGKey(0), jnp.ones(
        (1, 8), jnp.int32)), jnp.ones((1, 8), jnp.int32))
    assert logits.shape == (1, 8, 97)
    with pytest.raises(ValueError, match="--model_config"):
        create_model(types.SimpleNamespace(), "deepseek_v3", output_dim=97)


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("rope_interleave", False), ("n_group", 8),
    ("scoring_func", "softmax"), ("rope_scaling", {"type": "yarn"})])
def test_a_file_of_another_member_of_the_family_is_refused(key, value):
    with pytest.raises(NotImplementedError, match=key):
        dsv3.DecoderConfig.from_dict(dict(TOY, **{key: value}))


@pytest.fixture(scope="module")
def federated():
    """Two rounds through ``FedAvgAPI`` and the bucketed stream, built as
    the benchmark's family builds its cell, beside the reference's."""
    from benchmarks.families import deepseek_v3_lm as family
    from fedml_tpu.observability.registry import (MetricsRegistry,
                                                  set_registry)
    from fedml_tpu.observability.tracing import Tracer, set_tracer

    config = dict(TOY, router_experts=16, n_routed_experts=8,
                  experts_held=[4, 8],
                  as_run={"compute_dtype": "float32"})
    traffic = {"sequences_per_client": [4, 6], "seq_len": T,
               "batch_size": 2, "epochs": 1, "client_chunk": 1, "lr": 0.1,
               "wd": 0.0}
    seed = 2_700_000_123
    registry, tracer = MetricsRegistry(), Tracer()
    before, before_tracer = set_registry(registry), set_tracer(tracer)
    try:
        cell = family.build(config, traffic, seed, REF)
        rounds = [cell.api.train_one_round() for _ in range(2)]
        state = cell.snapshot()
    finally:
        set_registry(before)
        set_tracer(before_tracer)
    want = REF.run_rounds(config, traffic, seed, 2, cell.feed(2))
    return types.SimpleNamespace(rounds=rounds, state=state, want=want,
                                 registry=registry, tracer=tracer,
                                 tokens=10 * T)


def test_a_federated_round_matches_the_references(federated):
    f = federated
    for got, want in zip(f.rounds, f.want["loss"]):
        assert got["Train/Loss"] == pytest.approx(want, rel=2e-6)
    for leaf, norm in f.want["change_norms"][-1].items():
        change = np.linalg.norm(np.asarray(f.state[leaf], np.float64)
                                - np.asarray(f.want["init"][leaf]))
        assert change == pytest.approx(norm, rel=2e-4, abs=1e-9), leaf


def test_the_round_carries_the_three_counters(federated):
    from fedml_tpu.observability.routing import SERIES as routing_series
    f = federated
    assert len(routing_series) == 5     # the three, and PR 35's two
    for record in f.rounds:
        assert record["moe_dropped"] == 0
        # half the router held: the capacity is every assignment of the
        # 2 expert layers, and no layer-step has a fallback to take
        assert record["moe_overflow"] == 0
        assert record["moe_capacity_rows"] == 2 * f.tokens * 3
        # 2 expert layers; 3 of 16 experts a token, 8 of the 16 held
        assert 0.6 < record["moe_rows_held"] \
            / (2 * f.tokens * 3 * 8 / 16) < 1.4
        assert record["moe_load_max_over_mean"] >= 1.0
    gauges = f.registry.render_prometheus()
    for name in routing_series:
        assert name in gauges
    trains = [s for s in f.tracer.finished_spans()
              if s.name == "local-train"]
    assert len(trains) == 2
    assert trains[-1].attrs["moe_rows_held"] \
        == f.rounds[-1]["moe_rows_held"]
    assert trains[-1].attrs["moe_dropped"] == 0
    for name in routing_series:
        assert trains[-1].attrs[name] == f.rounds[-1][name]
