"""The ``lfm2_moe`` decoder (a token mixer a layer: gated short
convolutions beside grouped-query attention; a dense FFN, then
sigmoid-routed experts held in part) against its plain reference, which
is loaded by path from beside the benchmark's configuration and imports
nothing of the program: seeded random weights, float32, a small size on
the CPU. And what the model stands on: the short-convolution op against
the equations in ``jax.numpy``, the flash kernels at width 64 with
key/value heads read by group against a dense softmax, in interpret
mode."""

import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families.common import nest
from fedml_tpu.algorithms.specs import make_seq_classification_spec
from fedml_tpu.models import deepseek_v3 as dec
from fedml_tpu.ops import pallas_attention as pa
from fedml_tpu.ops import short_conv as sc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    path = os.path.join(ROOT, "benchmarks", "configs", name + ".py")
    spec = importlib.util.spec_from_file_location(name + "_for_lfm2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _load("lfm2_moe_lm_reference")

TOY = {
    "model_type": "lfm2_moe", "hidden_size": 128, "num_attention_heads": 8,
    "num_key_value_heads": 2, "intermediate_size": 192,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "num_dense_layers": 1, "vocab_size": 97,
    "layer_types": ["conv", "full_attention", "conv"], "conv_L_cache": 3,
    "conv_bias": False, "use_expert_bias": True, "norm_eps": 1e-5,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "rope_theta": 1000000, "max_position_embeddings": 128000,
}
LEAVES = sorted(REF.param_shapes(TOY))
B, T = 2, 24


def flat_of(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


# -- the op against the equations ----------------------------------------------

def conv_equations(bcu, w):
    """``y = C * z``, ``z[t] = sum_k w[:, k] v[t - (L - 1 - k)]``, ``v = B
    * u`` and zeros before the sequence (ISSUE 34, Tentpole 1)."""
    b, c, u = jnp.split(bcu, 3, axis=-1)
    v, (t, taps) = b * u, (bcu.shape[1], w.shape[1])
    return c * sum(
        w[:, k] * jnp.pad(v, ((0, 0), (taps - 1 - k, 0), (0, 0)))[:, :t]
        for k in range(taps))


#: T 2 and 3: taps that fall before the sequence; 40: one chunk, padded
#: to the tile; 1100: three chunks (first, one in the loop, last), ragged
@pytest.mark.parametrize("t", [2, 3, 40, 1100])
def test_gated_short_conv_is_the_equations(t):
    key = jax.random.PRNGKey(t)
    bcu = jax.random.normal(key, (2, t, 3 * 128), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (128, 3))
    g = jax.random.normal(jax.random.fold_in(key, 2), (2, t, 128))
    y, vjp = jax.vjp(sc.gated_short_conv, bcu, w)
    want, want_vjp = jax.vjp(conv_equations, bcu, w)
    # float32 on both sides: what differs is the order of three products
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=1e-5)
    (dbcu, dw), (want_dbcu, want_dw) = vjp(g), want_vjp(g)
    for got, ref in zip(jnp.split(dbcu, 3, -1), jnp.split(want_dbcu, 3, -1)):
        np.testing.assert_allclose(got, ref, atol=5e-5, rtol=1e-5)
    # sums over n and T of float32 products: the sum's length sets the room
    np.testing.assert_allclose(dw, want_dw, rtol=1e-5,
                               atol=1e-6 * np.abs(want_dw).max() * t ** 0.5)


def test_gated_short_conv_on_bf16_thirds_computes_in_float32():
    key = jax.random.PRNGKey(7)
    bcu = jax.random.normal(key, (1, 48, 3 * 128), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (128, 3))
    y = sc.gated_short_conv(bcu, w)
    assert y.dtype == jnp.bfloat16
    want = conv_equations(bcu.astype(jnp.float32), w)
    # one rounding of the result to bf16 (2^-9 relative)
    np.testing.assert_allclose(y.astype(jnp.float32), want, rtol=4e-3,
                               atol=1e-3)


def test_a_shape_the_conv_kernel_does_not_hold_is_refused_by_name():
    w = jnp.zeros((128, 3))
    with pytest.raises(ValueError, match="VMEM budget"):
        sc.gated_short_conv(jnp.zeros((1, 65536, 384), jnp.bfloat16), w)
    with pytest.raises(ValueError, match="thirds"):
        sc.gated_short_conv(jnp.zeros((1, 16, 128)), w)
    with pytest.raises(ValueError, match="groups of 128"):
        sc.gated_short_conv(jnp.zeros((1, 16, 3 * 64)), jnp.zeros((64, 3)))


# -- the flash kernels at width 64, key/value heads read by group --------------

def dense_attention(q, k, v, scale):
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    t = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _qkv(heads, kv_heads, width, t=80, seed=3):
    key = jax.random.PRNGKey(seed)
    shape = lambda h: (2, t, h, width)
    return (jax.random.normal(key, shape(heads)),
            jax.random.normal(jax.random.fold_in(key, 1), shape(kv_heads)),
            jax.random.normal(jax.random.fold_in(key, 2), shape(kv_heads)),
            jax.random.normal(jax.random.fold_in(key, 3), shape(heads)))


@pytest.mark.parametrize("blocks", [(16, 16), (None, None)],
                         ids=["16x16", "scheduled"])
def test_flash_at_width_64_reads_key_value_heads_by_group(blocks):
    q, k, v, g = _qkv(8, 2, 64)
    out, vjp = jax.vjp(lambda q, k, v: pa.flash_attention(
        q, k, v, True, 64 ** -0.5, *blocks), q, k, v)
    want, want_vjp = jax.vjp(lambda q, k, v: dense_attention(
        q, k, v, 64 ** -0.5), q, k, v)
    # float32 operands, online softmax against a materialised one
    np.testing.assert_allclose(out, want, atol=5e-6)
    for got, ref in zip(vjp(g), want_vjp(g)):
        assert got.shape == ref.shape      # dk, dv at the 2 key/value heads
        np.testing.assert_allclose(got, ref, atol=2e-5)


def test_the_128_wide_grouped_call_is_what_the_parent_computed():
    """The parent repeated the key/value heads to the query heads before
    the kernels; the kernels reading head ``h // group`` themselves give
    the same bits forward and in ``dq`` (the same tiles over the same
    numbers) and the key/value gradients to a float32 sum's order."""
    q, k, v, g = _qkv(8, 2, 128)
    grouped = lambda q, k, v: pa.flash_attention(q, k, v, True, None, 16, 16)
    repeated = lambda q, k, v: grouped(
        q, *(jnp.repeat(a, 4, axis=2) for a in (k, v)))
    out, vjp = jax.vjp(grouped, q, k, v)
    want, want_vjp = jax.vjp(repeated, q, k, v)
    np.testing.assert_array_equal(out, want)
    (dq, dk, dv), (want_dq, want_dk, want_dv) = vjp(g), want_vjp(g)
    np.testing.assert_array_equal(dq, want_dq)
    np.testing.assert_allclose(dk, want_dk, atol=2e-6, rtol=1e-6)
    np.testing.assert_allclose(dv, want_dv, atol=2e-6, rtol=1e-6)


def test_query_heads_that_fill_no_whole_group_are_refused():
    q, k, v, _ = _qkv(8, 3, 64, t=16)
    with pytest.raises(ValueError, match="whole group"):
        pa.flash_attention(q, k, v, True)


@pytest.mark.parametrize("width,ok", [(64, True), (128, True), (256, True),
                                      (32, False), (96, False), (80, False)])
def test_the_widths_the_kernels_run_on_hardware(width, ok):
    if ok:
        pa._require_hw_head_dim(width, interpret=False)
    else:
        with pytest.raises(ValueError, match="multiple of 128, or 64"):
            pa._require_hw_head_dim(width, interpret=False)
    pa._require_hw_head_dim(width, interpret=True)   # the CPU takes any


def test_the_schedule_at_the_cells_shape_keeps_the_sequence_resident():
    tile = pa.Tile(rows=512, major=4096, minor=512)
    assert pa.flash_schedule(4096, 4096, 64, 64, jnp.bfloat16) \
        == (pa.Schedule(tile, tile, tile), (8, 8, 8))


# -- the model against the reference -------------------------------------------

@pytest.fixture(scope="module")
def toy():
    weights = REF.make_weights(TOY, 34)
    model = dec.DecoderLM(dec.DecoderConfig.from_dict(TOY))
    shard = REF.make_clients(TOY, {"sequences_per_client": [B],
                                   "seq_len": T}, 34)[0]
    return types.SimpleNamespace(
        s=REF.sizes(TOY), weights=weights, model=model, params=nest(weights),
        x=jnp.asarray(shard["x"]), y=jnp.asarray(shard["y"]))


@pytest.fixture(scope="module")
def grads(toy):
    spec = make_seq_classification_spec(toy.model, toy.x[:1], name="lm")
    batch = {"x": toy.x, "y": toy.y, "mask": jnp.ones((B,), jnp.float32)}

    def loss(p):
        value, (_, metrics) = spec.loss_fn({"params": p}, batch, None, True)
        return value, metrics

    (value, metrics), g = jax.value_and_grad(loss, has_aux=True)(toy.params)
    (ref_value, ref_sums), ref_g = jax.value_and_grad(
        lambda p: REF.step_loss(p, toy.x, toy.y, toy.s), has_aux=True)(
            toy.weights)
    return types.SimpleNamespace(value=value, metrics=metrics,
                                 prog=flat_of(g), ref_value=ref_value,
                                 ref_sums=ref_sums, ref=ref_g)


def test_the_parameter_tree_is_the_references(toy):
    init = toy.model.init(jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32))
    shapes = {k: v.shape for k, v in flat_of(init["params"]).items()}
    assert shapes == {k: tuple(v) for k, v in REF.param_shapes(TOY).items()}
    # both layer types, a dense and a routed layer
    assert shapes["layer0/conv/conv_kernel"] == (128, 3)
    assert shapes["layer0/mlp/gate_proj/kernel"] == (128, 192)
    assert shapes["layer1/attn/k_proj/kernel"] == (128, 2 * 16)
    assert shapes["layer2/moe/w_gate"] == (8, 128, 32)
    assert "layer1/conv/in_proj/kernel" not in shapes
    # the filter's own initialiser has the scale of the benchmark's seeded
    # weights: unit variance through a fan-in of 3 taps, not of d channels
    taps = np.asarray(flat_of(init["params"])["layer0/conv/conv_kernel"])
    assert float(taps.std()) == pytest.approx(3 ** -0.5, rel=0.15)
    seeded = np.asarray(REF.make_weights(TOY, 7)["layer0/conv/conv_kernel"])
    assert float(seeded.std()) == pytest.approx(3 ** -0.5, rel=0.15)


def test_logits_match_the_reference(toy):
    logits = toy.model.apply({"params": toy.params}, toy.x)
    assert logits.shape == (B, T, TOY["vocab_size"])
    # float32 both; kernels' sums against materialised ones
    np.testing.assert_allclose(logits, REF.forward(toy.weights, toy.x, toy.s),
                               atol=2e-5, rtol=2e-5)


def test_the_loss_matches_the_reference(grads):
    assert float(grads.value) == pytest.approx(float(grads.ref_value),
                                               rel=2e-6)
    total, count = grads.ref_sums
    assert float(grads.metrics["loss_sum"]) == pytest.approx(float(total),
                                                             rel=2e-6)
    assert float(grads.metrics["count"]) == float(count)
    # the mix of layers a step ran: 2 conv layers, 1 attention layer
    assert float(grads.metrics["conv_layer_positions"]) == 2 * B * T
    assert float(grads.metrics["attn_layer_positions"]) == B * T


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(grads, leaf):
    got, want = np.asarray(grads.prog[leaf]), np.asarray(grads.ref[leaf])
    if leaf.endswith("e_score_correction_bias"):
        assert not got.any() and not want.any()   # the choice alone
        return
    assert np.abs(want).max() > 0
    # float32 on both sides: a ten-thousandth of the leaf's largest entry
    np.testing.assert_allclose(got, want, atol=3e-6 + 1e-4 * np.abs(
        want).max(), rtol=2e-4)


def test_the_center_tap_fault_is_another_model(toy):
    """The benchmark's planted fault (the convolution without its earlier
    taps) moves the logits: the comparison sees the mechanism."""
    whole = REF.forward(toy.weights, toy.x, toy.s)
    fault = REF.forward(toy.weights, toy.x, toy.s, "center_tap")
    assert float(jnp.abs(whole - fault).max()) > 1e-3


# -- the router and the shares ---------------------------------------------------

def _expert_layer(seed=5):
    s = REF.sizes(TOY)
    full = REF.make_weights(TOY, seed)
    p = {k[len("layer2/moe/"):]: v for k, v in full.items()
         if k.startswith("layer2/moe/")}
    # a bias large enough to change the choice of some tokens
    p["e_score_correction_bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), (8,))
    x = jax.random.normal(jax.random.PRNGKey(seed), (B * T, 128))
    return s, p, x


def _apply_experts(cfg, p, x):
    module = dec.RoutedExperts(dec.DecoderConfig.from_dict(cfg))
    out, sown = module.apply({"params": nest(p)}, x, mutable=["metrics"])
    return out, {k: float(v) for k, v in sown["metrics"].items()}


def test_the_router_is_sigmoid_bias_for_the_choice_and_a_sum_plus_1e_6():
    s, p, x = _expert_layer()
    got, sown = _apply_experts(TOY, p, x)
    np.testing.assert_allclose(got, REF.experts(p, "", x, s), atol=5e-6)
    weight = np.asarray(REF.route(p, "", x, s))
    assert ((weight > 0).sum(axis=1) == 2).all()
    # the chosen scores over their sum PLUS 1e-6: a hair under 1
    total = weight.sum(axis=1)
    assert (total < 1.0).all() and (total > 1.0 - 1e-5).all()
    unbiased = np.asarray(REF.route(dict(p, e_score_correction_bias=jnp.zeros(
        (8,))), "", x, s))
    assert ((weight > 0) != (unbiased > 0)).any()   # the bias did steer
    assert sown["moe_rows_held"] == 2 * x.shape[0]
    assert dec.DecoderConfig.from_dict(TOY).norm_topk_eps == 1e-6


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips with 2 of the 8 experts each (the deployment's split of
    32 into 8): their parts of one layer's output add up to the uncut
    reference's layer."""
    s, p, x = _expert_layer()
    whole = REF.experts(p, "", x, s)
    total, rows = jnp.zeros_like(whole), 0.0
    for first in range(0, 8, 2):
        mine = dict(p, **{k: p[k][first:first + 2]
                          for k in ("w_gate", "w_up", "w_down")})
        share = dict(TOY, router_experts=8, n_routed_experts=2,
                     experts_held=[first, 2])
        out, sown = _apply_experts(share, mine, x)
        np.testing.assert_allclose(
            out, REF.experts(mine, "", x, REF.sizes(share)), atol=5e-6)
        total, rows = total + out, rows + sown["moe_rows_held"]
    np.testing.assert_allclose(total, whole, atol=1e-5)
    assert rows == x.shape[0] * 2   # every assignment computed on some chip


# -- what is not computed is refused; what was computed stays ------------------

@pytest.mark.parametrize("key,value,error", [
    ("layer_types", ["conv", "sliding_attention", "conv"],
     NotImplementedError),
    ("conv_bias", True, NotImplementedError),
    ("use_expert_bias", False, NotImplementedError),
    ("tie_word_embeddings", True, NotImplementedError),
    ("layer_types", ["conv", "full_attention"], ValueError),
    ("layer_types_as_run", ["conv"] * 4, ValueError)])
def test_a_file_this_decoder_does_not_compute_is_refused(key, value, error):
    with pytest.raises(error, match="layer_types" if "layer" in key else key):
        dec.DecoderConfig.from_dict(dict(TOY, **{key: value}))


def test_a_cut_file_names_the_layers_as_run():
    """The benchmark's file keeps the published list of 24 and names the 5
    layers it runs; ``head_dim`` is hidden / heads where the file has
    none; ``num_dense_layers`` is the leading dense layers."""
    published = ["conv", "conv", "full_attention"] + ["conv"] * 21
    cut = dict(TOY, num_hidden_layers=24, layer_types=published, n_layer=3,
               layer_types_as_run=["conv", "full_attention", "conv"])
    cfg = dec.DecoderConfig.from_dict(cut)
    assert cfg.layer_types == ("conv", "full_attention", "conv")
    assert (cfg.num_hidden_layers, cfg.head_dim, cfg.first_k_dense_replace,
            cfg.rms_norm_eps, cfg.conv_L_cache) == (3, 16, 1, 1e-5, 3)
    assert (cfg.router_width, cfg.held, cfg.scoring_func) \
        == (8, (0, 8), "sigmoid")
    with pytest.raises(ValueError, match="layer_types"):   # 24 for 3 layers
        dec.DecoderConfig.from_dict(dict(TOY, num_hidden_layers=24, n_layer=3,
                                         layer_types=published))
    with pytest.raises(ValueError, match="conv_L_cache"):
        dec.DecoderConfig.from_dict(
            {k: v for k, v in TOY.items() if k != "conv_L_cache"})


@pytest.mark.parametrize("family", ["deepseek_v3", "sdar_moe"])
def test_the_other_families_build_the_trees_they_built(family):
    """A file without ``layer_types`` builds the modules it built: the
    tree its own reference names, leaf for leaf, no ``conv`` in it, and
    no layer-mix counter sown."""
    ref = _load({"deepseek_v3": "deepseek_v3_lm_reference",
                 "sdar_moe": "sdar_moe_lm_reference"}[family])
    toy = _OTHER[family]
    cfg = dec.DecoderConfig.from_dict(toy)
    assert cfg.layer_types is None and cfg.norm_topk_eps == 0.0
    init = dec.DecoderLM(cfg).init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 16), jnp.int32))
    shapes = {k: v.shape for k, v in flat_of(init["params"]).items()}
    assert shapes == {k: tuple(v) for k, v in ref.param_shapes(toy).items()}
    assert not [k for k in shapes if "conv" in k]
    assert not [k for k in flat_of(init.get("metrics", {}))
                if "layer_positions" in k]


_OTHER = {
    "deepseek_v3": {
        "model_type": "deepseek_v3", "hidden_size": 64,
        "num_attention_heads": 4, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_routed_experts": 16, "n_shared_experts": 1,
        "num_experts_per_tok": 3, "first_k_dense_replace": 1,
        "num_hidden_layers": 2, "vocab_size": 97, "rms_norm_eps": 1e-6,
        "rope_theta": 1000000, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5},
    "sdar_moe": {
        "model_type": "sdar_moe", "hidden_size": 64,
        "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_experts": 16, "num_experts_per_tok": 4,
        "num_hidden_layers": 2, "vocab_size": 97, "rms_norm_eps": 1e-6,
        "rope_theta": 1000000, "norm_topk_prob": True, "block_length": 4},
}


def _pallas_calls(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, out)
    return out


def test_the_kernels_a_step_launches_by_layer_type(toy):
    """A conv layer launches ``short_conv_fwd`` and ``short_conv_bwd``,
    an attention layer the three flash kernels; no ``jnp.repeat`` of keys
    or values stands before them (their operands keep 2 heads)."""
    spec = make_seq_classification_spec(toy.model, toy.x[:1], name="lm")
    batch = {"x": toy.x, "y": toy.y, "mask": jnp.ones((B,), jnp.float32)}
    grad = jax.grad(lambda p: spec.loss_fn({"params": p}, batch, None,
                                           True)[0])
    jaxpr = jax.make_jaxpr(grad)(toy.params).jaxpr
    calls = [c for c in _pallas_calls(jaxpr, []) if c]
    assert sorted(c for c in calls if c.startswith(("flash", "short"))) \
        == sorted(["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
                  + ["short_conv_fwd", "short_conv_bwd"] * 2)


# -- through the factory's family and through a federated round ----------------

@pytest.fixture(scope="module")
def federated():
    """Two rounds through ``FedAvgAPI`` and the bucketed stream, built as
    the benchmark's family builds its cell, beside the reference's."""
    from benchmarks.families import lfm2_moe_lm as family
    from fedml_tpu.observability.tracing import Tracer, set_tracer

    config = dict(TOY, router_experts=8, n_routed_experts=4,
                  experts_held=[2, 4], as_run={"compute_dtype": "float32"})
    traffic = {"sequences_per_client": [3, 5], "seq_len": T,
               "batch_size": 1, "epochs": 1, "client_chunk": 1, "lr": 0.1,
               "wd": 0.0}
    seed = 3_400_000_123
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
    return types.SimpleNamespace(
        rounds=rounds, state=state, want=want, tracer=tracer, mode=mode,
        info=info, work=cell.work_per_round)


def test_a_federated_round_matches_the_references(federated):
    f = federated
    assert f.mode == "bucketed" and f.info["fold"] == "device"
    for got, want in zip(f.rounds, f.want["loss"]):
        assert got["Train/Loss"] == pytest.approx(want, rel=2e-6)
    for leaf, norm in f.want["change_norms"][-1].items():
        change = np.linalg.norm(np.asarray(f.state[leaf], np.float64)
                                - np.asarray(f.want["init"][leaf]))
        # (a scale leaf near 1 moves by 1e-5 an element: float32's
        # spacing there is 1e-7)
        assert change == pytest.approx(norm, rel=2e-4, abs=3e-7), leaf


def test_the_local_train_span_says_which_mix_of_layers_ran(federated):
    f = federated
    trains = [s for s in f.tracer.finished_spans()
              if s.name == "local-train"]
    assert len(trains) == 2
    for span, record in zip(trains, f.rounds):
        # 2 conv layers and 1 attention layer over the round's positions
        assert span.attrs["conv.layer_positions"] == 2 * f.work["tokens"]
        assert span.attrs["attn.layer_positions"] == f.work["tokens"]
        assert span.attrs["moe_dropped"] == 0
        assert span.attrs["moe_rows_held"] == record["moe_rows_held"]
        assert span.attrs["moe_overflow"] == record["moe_overflow"] == 0
        assert span.attrs["moe_capacity_rows"] == 2 * f.work["tokens"] * 2
        # 2 routed layers; 2 of 8 a position, 4 held
        assert 0.5 < record["moe_rows_held"] \
            / (2 * f.work["tokens"] * 2 * 4 / 8) < 1.5


def test_a_model_without_layer_types_sets_no_layer_mix():
    from fedml_tpu.observability.routing import layer_mix_counters

    assert layer_mix_counters({"count": np.ones(2)}) == {}
    assert layer_mix_counters(None) == {}
    assert layer_mix_counters({"conv_layer_positions": np.array([8., 8.]),
                               "attn_layer_positions": np.array([4.])}) \
        == {"conv.layer_positions": 16.0, "attn.layer_positions": 4.0}
