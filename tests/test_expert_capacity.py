"""The compact sorted buffer of ``RoutedExperts`` (PR 35): a layer that
holds less than half of the router works on the first ``buffer_capacity``
rows of the sorted order and keeps the whole ``tokens x top-k`` order
(every run of that many rows) as the fallback behind one conditional
that all lanes of a chunk take together.

Every case runs the three scoring variants the one module serves. The
whole-buffer path is the same module with the capacity's block made so
large that the capacity is every assignment (what a layer that holds
half the router or more gets without any patch)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import deepseek_v3 as dsv3
from fedml_tpu.parallel.mesh import LANE_AXIS, any_lane

N, D, WIDTH, E = 256, 32, 16, 32

VARIANTS = {
    # kanana-2's: sigmoid scores, a bias in the choice, a shared expert
    "sigmoid_bias_shared": dict(scoring_func="sigmoid", n_shared_experts=1,
                                routed_scaling_factor=2.448),
    # sdar's: softmax over the router, renormalised, no bias
    "softmax": dict(scoring_func="softmax"),
    # lfm2's: sigmoid, renormalised over the sum + 1e-6
    "sigmoid_eps": dict(scoring_func="sigmoid", norm_topk_eps=1e-6),
}
SHARES = {"an_eighth": 4, "a_quarter": 8}     # experts held of 32
TOP_K = 3
ROWS = N * TOP_K                               # 768 assignments a step
CAPACITY = 512     # twice 768 / 8 or 768 / 4, in whole blocks of 512


def _config(variant, count, first=8):
    return dsv3.DecoderConfig(
        vocab_size=64, hidden_size=D, num_hidden_layers=1,
        num_attention_heads=2, moe_intermediate_size=WIDTH,
        n_routed_experts=count, num_experts_per_tok=TOP_K,
        router_experts=E, experts_held=(first, count), **VARIANTS[variant])


def _layer(variant, count, seed=0, crowd=False):
    """Module, parameters and tokens. ``crowd``: a router that sends every
    token to held experts (one column of the tokens is constant and the
    held experts' router weights read it)."""
    cfg = _config(variant, count)
    module = dsv3.RoutedExperts(cfg)
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (N, D))
    params = module.init(jax.random.fold_in(key, 1), x)["params"]
    if "e_score_correction_bias" in params:
        params["e_score_correction_bias"] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, 2), (E,))
    if crowd:
        x = x.at[:, 0].set(4.0)
        kernel = params["router"]["kernel"]
        params["router"]["kernel"] = kernel.at[0, 8:8 + count].set(4.0)
        if "e_score_correction_bias" in params:   # it steers the same way
            params["e_score_correction_bias"] = jnp.zeros((E,)).at[
                8:8 + count].set(10.0)
    return module, params, x


def _apply(module, params, x):
    out, sown = module.apply({"params": params}, x, mutable=["metrics"])
    return out, sown["metrics"]


def _loss(module):
    def loss(params, x):
        out = module.apply({"params": params}, x)
        return jnp.sum(out * jnp.cos(jnp.arange(D, dtype=out.dtype)))
    return loss


def _flat(tree):
    return {jax.tree_util.keystr(k): np.asarray(v)
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.fixture
def whole_buffer(monkeypatch):
    """Switches the module to the whole buffer for the rest of a test."""
    def switch():
        monkeypatch.setattr(dsv3, "_CAPACITY_BLOCK", 1 << 30)
    return switch


CASES = [(v, s) for v in VARIANTS for s in SHARES]


def test_the_capacity_rule():
    assert dsv3.buffer_capacity(32768, 16, 128) == 8192    # sdar
    assert dsv3.buffer_capacity(24576, 16, 128) == 6144    # kanana2
    assert dsv3.buffer_capacity(16384, 8, 32) == 8192      # lfm2
    assert dsv3.buffer_capacity(ROWS, 4, E) == 512
    assert dsv3.buffer_capacity(ROWS, 8, E) == 512
    # half the router or more: every assignment, and no conditional
    assert dsv3.buffer_capacity(32768, 64, 128) == 32768
    assert dsv3.buffer_capacity(192, 16, 16) == 192
    assert dsv3.buffer_capacity(1000, 1, 1000) == 512


@pytest.mark.parametrize("variant,share", CASES)
def test_the_compact_path_gives_the_whole_buffers_output(
        variant, share, whole_buffer):
    module, params, x = _layer(variant, SHARES[share])
    got, sown = _apply(module, params, x)
    assert float(sown["moe_overflow"]) == 0
    assert float(sown["moe_capacity_rows"]) == CAPACITY
    assert 0 < float(sown["moe_rows_held"]) <= CAPACITY
    assert float(sown["moe_dropped"]) == 0
    whole_buffer()
    want, sown_whole = _apply(module, params, x)
    assert float(sown_whole["moe_capacity_rows"]) == ROWS
    assert float(sown_whole["moe_overflow"]) == 0
    assert float(sown_whole["moe_rows_held"]) == float(sown["moe_rows_held"])
    np.testing.assert_allclose(got, want, atol=5e-6)


@pytest.mark.parametrize("variant,share", CASES)
def test_the_compact_path_gives_the_whole_buffers_gradients(
        variant, share, whole_buffer):
    module, params, x = _layer(variant, SHARES[share], seed=1)
    grad = jax.grad(_loss(module), argnums=(0, 1))
    got = _flat(grad(params, x))
    whole_buffer()
    want = _flat(grad(params, x))
    assert set(got) == set(want)
    for leaf in want:
        np.testing.assert_allclose(got[leaf], want[leaf], atol=2e-5,
                                   err_msg=leaf)
    assert np.abs(want["[1]"]).max() > 1e-3        # the tokens' gradient


@pytest.mark.parametrize("variant", VARIANTS)
def test_a_crowded_router_takes_the_fallback_and_drops_nothing(
        variant, whole_buffer):
    module, params, x = _layer(variant, 4, seed=2, crowd=True)
    got, sown = _apply(module, params, x)
    assert float(sown["moe_rows_held"]) == ROWS    # every token, top-3 held
    assert float(sown["moe_overflow"]) == 1        # this one layer-step
    assert float(sown["moe_dropped"]) == 0
    grads = _flat(jax.grad(_loss(module), argnums=(0, 1))(params, x))
    whole_buffer()
    want, _ = _apply(module, params, x)
    np.testing.assert_allclose(got, want, atol=1e-6)
    want_grads = _flat(jax.grad(_loss(module), argnums=(0, 1))(params, x))
    for leaf in want_grads:
        np.testing.assert_allclose(grads[leaf], want_grads[leaf], rtol=1e-5,
                                   atol=1e-6, err_msg=leaf)


@pytest.mark.parametrize("variant", VARIANTS)
def test_two_lanes_of_which_one_overflows_each_give_their_own_result(
        variant):
    module, calm_params, calm = _layer(variant, 4, seed=3)
    _, crowded_params, crowded = _layer(variant, 4, seed=3, crowd=True)
    lanes = [(calm_params, calm), (crowded_params, crowded)]
    stacked = jax.tree.map(lambda *v: jnp.stack(v), *lanes)

    def one(params, x):
        out, sown = _apply(module, params, x)
        grad = jax.grad(_loss(module), argnums=(0, 1))(params, x)
        return out, grad, sown["moe_overflow"], sown["moe_rows_held"]

    out, grad, overflow, rows = jax.vmap(one, axis_name=LANE_AXIS)(*stacked)
    # the calm lane fits its buffer, and runs the fallback with the other
    assert float(rows[0]) <= CAPACITY < float(rows[1]) == ROWS
    assert overflow.tolist() == [1.0, 1.0]
    # without the axis name each lane decides for itself (both branches
    # run and one is selected)
    out_own, grad_own, overflow_own, _ = jax.vmap(one)(*stacked)
    assert overflow_own.tolist() == [0.0, 1.0]
    for lane, (params, x) in enumerate(lanes):
        want_out, want_grad, lane_overflow, _ = one(params, x)
        assert float(lane_overflow) == lane
        for got_out, got_grad in ((out, grad), (out_own, grad_own)):
            np.testing.assert_allclose(got_out[lane], want_out, atol=5e-6)
            got, want = _flat(got_grad), _flat(want_grad)
            for leaf in want:
                np.testing.assert_allclose(got[leaf][lane], want[leaf],
                                           rtol=1e-5, atol=2e-5,
                                           err_msg=leaf)


def test_any_lane_outside_a_named_axis_is_the_flag_itself():
    assert bool(any_lane(jnp.bool_(True))) is True
    assert bool(any_lane(jnp.bool_(False))) is False
    flags = jnp.asarray([False, True, False])
    assert jax.vmap(any_lane)(flags).tolist() == [False, True, False]
    assert jax.vmap(any_lane, axis_name=LANE_AXIS)(flags).tolist() \
        == [True] * 3
    assert jax.vmap(any_lane, axis_name=LANE_AXIS)(~flags[:1] & False) \
        .tolist() == [False]


def _walk(jaxpr, inside, found):
    """Every equation, with whether it lies inside a conditional's
    fallback (``branches[1]``: the branch taken on True)."""
    for eqn in jaxpr.eqns:
        found.append((eqn, inside))
        if eqn.primitive.name == "pallas_call":    # a kernel's own body
            continue
        if eqn.primitive.name == "cond":
            for index, branch in enumerate(eqn.params["branches"]):
                _walk(branch.jaxpr, inside or index == 1, found)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _walk(sub, inside, found)
    return found


@pytest.mark.parametrize("variant", VARIANTS)
def test_under_named_lanes_the_conditional_stays_and_holds_the_big_arrays(
        variant):
    module, params, x = _layer(variant, 4)
    step = jax.vmap(jax.grad(_loss(module), argnums=(0, 1)),
                    in_axes=(None, 0), axis_name=LANE_AXIS)
    jaxpr = jax.make_jaxpr(step)(params, x[None]).jaxpr
    found = _walk(jaxpr, False, [])
    conds = [eqn for eqn, _ in found if eqn.primitive.name == "cond"]
    assert len(conds) >= 2                         # forward and backward
    wide = [(str(eqn.primitive), v.aval.shape)
            for eqn, inside in found if not inside
            for v in list(eqn.invars) + list(eqn.outvars)
            if hasattr(v, "aval") and getattr(v.aval, "ndim", 0) >= 2
            and ROWS in v.aval.shape[:-1] and v.aval.shape[-1] in (D, WIDTH)]
    assert wide == []
    # a lane's own flag (no axis name) is batched into selects: no cond
    own = jax.make_jaxpr(jax.vmap(
        jax.grad(_loss(module), argnums=(0, 1)), in_axes=(None, 0)))(
            params, x[None]).jaxpr
    assert not [e for e, _ in _walk(own, False, [])
                if e.primitive.name == "cond"]


@pytest.mark.parametrize("variant", VARIANTS)
def test_a_layer_that_holds_half_the_router_traces_no_conditional(
        variant, whole_buffer):
    """At ``2 * count >= E`` the capacity is every assignment and the
    module traces the program it traced before there was a capacity: the
    text of the whole-buffer path forced by the block, with no ``cond``
    outside the kernels and no ``pmax``."""
    module, params, x = _layer(variant, 16)
    grad = jax.grad(_loss(module), argnums=(0, 1))
    text = lambda: re.sub(r"0x[0-9a-f]+", "0x",
                          str(jax.make_jaxpr(grad)(params, x)))
    eqns = [e.primitive.name for e, _ in _walk(
        jax.make_jaxpr(grad)(params, x).jaxpr, False, [])]
    assert "cond" not in eqns and "pmax" not in eqns
    assert "pallas_call" in eqns and "gather" in eqns
    traced = text()
    whole_buffer()
    assert text() == traced


@pytest.mark.parametrize("lanes", [1, 2])
def test_the_bucketed_streams_chunk_program_keeps_the_conditional(lanes):
    """``BucketedStreamRunner`` names its lane ``vmap``, so the client
    update of a decoder whose expert layers hold an eighth of the router
    holds one ``cond`` a layer forward and one backward, whatever the
    chunk; its counters come out with the step's other metric sums."""
    from fedml_tpu.algorithms.specs import make_seq_classification_spec
    from fedml_tpu.parallel.engine import (BucketedStreamRunner,
                                           ClientUpdateConfig)

    cfg = dsv3.DecoderConfig(
        vocab_size=64, hidden_size=D, num_hidden_layers=2,
        num_attention_heads=2, moe_intermediate_size=WIDTH,
        n_routed_experts=4, num_experts_per_tok=TOP_K, router_experts=E,
        experts_held=(8, 4), attention="grouped", num_key_value_heads=2,
        head_dim=16, intermediate_size=32, first_k_dense_replace=1,
        scoring_func="softmax")
    t, b, steps = N // 2, 2, 2
    spec = make_seq_classification_spec(
        dsv3.DecoderLM(cfg), jnp.zeros((1, t), jnp.int32), name="lm")
    runner = BucketedStreamRunner(
        spec, ClientUpdateConfig(optimizer="sgd", lr=0.1), client_chunk=lanes,
        batch_size=b, epochs=1, edges=(steps,))
    state = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    x = jnp.zeros((lanes, steps, b, t), jnp.int32)
    args = (state, {"x": x, "y": x, "mask": jnp.zeros((lanes, steps, b))},
            jnp.zeros((lanes,), jnp.int32), jnp.zeros((), jnp.int32),
            jax.random.split(jax.random.PRNGKey(0), lanes))
    traced = jax.make_jaxpr(runner._chunk_fn)(*args)
    found = _walk(traced.jaxpr, False, [])
    names = [e.primitive.name for e, _ in found]
    # one expert layer: a conditional forward and one backward (the
    # fallback has its own inside, which steps over a run without rows)
    assert [inside for e, inside in found
            if e.primitive.name == "cond"].count(False) == 2
    assert "pmax" in names
    sums = jax.eval_shape(runner._chunk_fn, *args)[2]
    assert {"moe_overflow", "moe_capacity_rows", "moe_dropped"} <= set(sums)
