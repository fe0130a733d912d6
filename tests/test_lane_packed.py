"""Lane-packed CIFAR ResNet (models/lane_packed.py): the MXU-shaped
lowering must be numerically the vmap-over-lane-stacked-params path it
replaces -- forward, batch_stats update, gradients, and whole federated
rounds (wave_mode=3 vs wave_mode=2)."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.lane_packed import (_lanes_per_group, lane_conv,
                                          lane_merge, lane_unmerge,
                                          make_lane_packed_apply)
from fedml_tpu.models.resnet import CifarResNet


def _stacked_params(model, L, H, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), L)
    return jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[model.init(k, jnp.zeros((1, H, H, 3))) for k in keys])


def test_lanes_per_group_targets_mxu_k():
    # ResNet-56 stages at L=8: 16ch -> all 8 lanes merge (K=128),
    # 32ch -> 4 (K=128), 64ch -> 2 (K=128); >=128ch stays per-lane
    assert _lanes_per_group(8, 16) == 8
    assert _lanes_per_group(8, 32) == 4
    assert _lanes_per_group(8, 64) == 2
    assert _lanes_per_group(8, 128) == 1
    assert _lanes_per_group(8, 3) == 8  # stem: best possible is dense
    # g always divides L (falls back toward 1 for awkward lane counts)
    assert _lanes_per_group(6, 32) == 3


def test_lane_conv_matches_vmap_conv():
    L, B, H, ci, co = 4, 2, 8, 16, 8
    w = jax.random.normal(jax.random.PRNGKey(0), (L, 3, 3, ci, co))
    x = jax.random.normal(jax.random.PRNGKey(1), (L, B, H, H, ci))

    def one(xx, ww):
        return jax.lax.conv_general_dilated(
            xx, ww, (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    ref = jax.vmap(one)(x, w)
    got = lane_unmerge(lane_conv(lane_merge(x), w, L), L)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_merge_unmerge_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 2, 4, 4, 5))
    np.testing.assert_array_equal(
        np.asarray(lane_unmerge(lane_merge(x), 3)), np.asarray(x))


@pytest.mark.parametrize("lowering", ["blockdiag", "bgc", "auto"])
@pytest.mark.parametrize("train", [False, True])
def test_packed_apply_matches_vmap(train, lowering):
    L, B, H = 4, 8, 16
    model = CifarResNet(depth=8, num_classes=10)  # has downsample blocks
    stacked = _stacked_params(model, L, H)
    x = jax.random.normal(jax.random.PRNGKey(2), (L, B, H, H, 3))

    def one(v, xx):
        if train:
            out, mut = model.apply(v, xx, train=True,
                                   mutable=["batch_stats"])
            return out, mut["batch_stats"]
        return model.apply(v, xx, train=False), v["batch_stats"]

    ref_logits, ref_bs = jax.vmap(one)(stacked, x)
    packed = make_lane_packed_apply(model, L, lowering)
    got_logits, got_bs = packed(stacked, x, train=train)
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(ref_logits), atol=1e-5)
    for a, b in zip(jax.tree.leaves(ref_bs), jax.tree.leaves(got_bs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("lowering", ["blockdiag", "bgc", "auto"])
def test_packed_grads_match_vmap(lowering):
    import optax

    L, B, H = 4, 4, 8
    model = CifarResNet(depth=8, num_classes=10)
    stacked = _stacked_params(model, L, H, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (L, B, H, H, 3))
    y = jax.random.randint(jax.random.PRNGKey(5), (L, B), 0, 10)
    packed = make_lane_packed_apply(model, L, lowering)

    def ref_loss(p):
        def per_lane(v, xx, yy):
            out, _ = model.apply(v, xx, train=True,
                                 mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                out.astype(jnp.float32), yy).mean()
        return jnp.sum(jax.vmap(per_lane)(p, x, y))

    def packed_loss(p):
        out, _ = packed(p, x, train=True)
        per = optax.softmax_cross_entropy_with_integer_labels(
            out.astype(jnp.float32).reshape(L * B, -1), y.reshape(-1))
        return jnp.sum(per.reshape(L, B).mean(axis=1))

    g_ref = jax.grad(ref_loss)(stacked)
    g_got = jax.grad(packed_loss)(stacked)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_packed_apply_rejects_unsupported_model():
    from fedml_tpu.models.linear import LogisticRegression

    with pytest.raises(TypeError, match="CifarResNet"):
        make_lane_packed_apply(LogisticRegression(num_classes=3), 4)


def test_packed_cnn_matches_vmap():
    """CNNOriginalFedAvg (FEMNIST config): packed forward AND grads match
    the vmap path -- biased convs, max pools, per-lane flatten order."""
    import optax

    from fedml_tpu.models.cnn import CNNOriginalFedAvg

    L, B = 4, 6
    model = CNNOriginalFedAvg(only_digits=True)
    keys = jax.random.split(jax.random.PRNGKey(8), L)
    stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[model.init(k, jnp.zeros((1, 28, 28, 1))) for k in keys])
    x = jax.random.normal(jax.random.PRNGKey(9), (L, B, 28, 28, 1))
    y = jax.random.randint(jax.random.PRNGKey(10), (L, B), 0, 10)

    ref = jax.vmap(lambda v, xx: model.apply(v, xx, train=True))(stacked, x)
    packed = make_lane_packed_apply(model, L)
    got, stats = packed(stacked, x, train=True)
    assert stats == {}
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)

    def ref_loss(p):
        out = jax.vmap(lambda v, xx: model.apply(v, xx, train=True))(p, x)
        return jnp.sum(jax.vmap(
            lambda o, yy: optax.softmax_cross_entropy_with_integer_labels(
                o.astype(jnp.float32), yy).mean())(out, y))

    def packed_loss(p):
        out, _ = packed(p, x, train=True)
        return jnp.sum(jax.vmap(
            lambda o, yy: optax.softmax_cross_entropy_with_integer_labels(
                o.astype(jnp.float32), yy).mean())(out, y))

    g_ref = jax.grad(ref_loss)(stacked)
    g_got = jax.grad(packed_loss)(stacked)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_cnn_spec_gets_lane_loss_builder():
    from fedml_tpu.algorithms.specs import make_classification_spec
    from fedml_tpu.models.cnn import CNNOriginalFedAvg

    spec = make_classification_spec(CNNOriginalFedAvg(),
                                    jnp.zeros((1, 28, 28, 1)))
    assert spec.lane_loss_builder is not None
    lane_loss = spec.lane_loss_builder(2)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 28, 28, 1))
    y = jnp.zeros((2, 4), jnp.int32)
    mask = jnp.ones((2, 4), jnp.float32)
    state = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[spec.init_fn(k) for k in jax.random.split(
            jax.random.PRNGKey(1), 2)])
    loss, (new_state, metrics) = lane_loss(
        state, {"x": x, "y": y, "mask": mask}, None, True)
    assert jnp.isfinite(loss)
    assert metrics["count"].shape == (2,)
    assert set(new_state) == set(state)


def _run_fedavg(wave_mode, rounds=2):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.algorithms.specs import make_classification_spec
    from fedml_tpu.data.augment import make_cifar_augment
    from fedml_tpu.data.synthetic import load_synthetic_images

    dataset = load_synthetic_images(client_num=5, n_train=260, n_test=64,
                                    image_size=8, partition="hetero",
                                    partition_alpha=0.5, seed=0)
    model = CifarResNet(depth=8, num_classes=10)
    spec = make_classification_spec(
        model, jnp.zeros((1, 8, 8, 3)),
        augment_fn=make_cifar_augment(pad=2, cutout_length=4))
    args = types.SimpleNamespace(
        # batch 64 >= every client shard (260/5 = 52): ONE step per
        # client per round, so the packed-vs-vmap comparison stays at
        # reassociation scale -- multi-step trajectories through BN at
        # lr 0.1 are chaotic (measured ~1e4x amplification of a 1e-6
        # seed over 3 steps) and would make any tight tolerance flaky
        client_num_in_total=5, client_num_per_round=5, comm_round=rounds,
        epochs=1, batch_size=64, lr=0.1, wd=0.001, client_optimizer="sgd",
        frequency_of_the_test=10 ** 9, seed=0, client_chunk=4,
        wave_mode=wave_mode, device_resident="auto",
        device_data_cap_gb=4.0, device_dtype=None)
    api = FedAvgAPI(dataset, spec, args)
    if wave_mode == 3:
        assert api.runner.mode == "mxu-lanes", (
            "CifarResNet spec must provide the packed lane path")
    metrics = [api.train_one_round() for _ in range(rounds)]
    return api.global_state, metrics


@pytest.mark.slow
def test_sharded_packed_lanes_equal_flat():
    """wave_mode=3 over a mesh: rows sharded over the 8-device CPU mesh,
    every shard runs its residents through the MXU-packed lowering, psum
    aggregation -- result equals the flat single-device round."""
    from fedml_tpu.algorithms.specs import make_classification_spec
    from fedml_tpu.parallel.engine import (ClientUpdateConfig,
                                           ShardedLaneRunner,
                                           make_indexed_sim_round)
    from fedml_tpu.parallel.mesh import make_client_mesh
    from fedml_tpu.parallel.multihost import global_cohort
    from fedml_tpu.parallel.packing import pack_schedule, stack_clients

    rnd = np.random.default_rng(11)
    sizes = (20, 8, 14, 5, 16, 9, 11, 7, 13, 6, 10)  # 11 clients
    clients = [{"x": rnd.normal(size=(n, 8, 8, 3)).astype(np.float32),
                "y": rnd.integers(0, 10, n).astype(np.int64)}
               for n in sizes]
    model = CifarResNet(depth=8, num_classes=10)
    # blockdiag pinned: this oracle checks the SHARDING machinery
    # (shard_map + psum vs flat), so the conv lowering is held to the
    # one whose contraction order matches the flat reference exactly;
    # bgc/auto lowering equivalence is covered at 1e-5 by the
    # apply/grads oracles above (BN amplifies their ~1e-6 conv
    # reassociation into run-varying 1e-4-scale param diffs here).
    spec = make_classification_spec(model, jnp.zeros((1, 8, 8, 3)),
                                    lane_lowering="blockdiag")
    state = spec.init_fn(jax.random.PRNGKey(0))
    cfg = ClientUpdateConfig(optimizer="sgd", lr=0.1)
    stacked = stack_clients(clients)
    # batch 32 >= the largest shard (20): one step per client, keeping
    # the equality oracle at reassociation scale (multi-step BN
    # trajectories are chaotic; see test above)
    sched = pack_schedule(list(sizes), 32, 1,
                          rng=np.random.default_rng(5))
    rng = jax.random.PRNGKey(3)

    # both round paths donate their state args: hand each a fresh copy
    fresh = lambda t: jax.tree.map(jnp.copy, t)
    flat = make_indexed_sim_round(spec, cfg)
    dd = {"x": jnp.asarray(stacked["x"]), "y": jnp.asarray(stacked["y"])}
    js = {k: jnp.asarray(v) for k, v in sched.items()}
    s_flat, _, _ = flat(fresh(state), (), dd, js, rng)

    mesh = make_client_mesh(8)
    placed = global_cohort(mesh, {"x": stacked["x"], "y": stacked["y"]})
    slr = ShardedLaneRunner(spec, cfg, mesh, n_lanes=2, packed=True)
    s_sh, _, _ = slr.run_schedule(
        fresh(state), (), placed, list(range(len(sizes))), sched, rng)
    for a, b in zip(jax.tree.leaves(s_flat), jax.tree.leaves(s_sh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5)


@pytest.mark.slow
def test_fedavg_round_packed_matches_vmap_lanes():
    """wave_mode=3 (MXU-packed) and wave_mode=2 (vmap lanes) run the SAME
    schedule, RNG, and math -- whole multi-round trajectories must agree
    to float reassociation."""
    state2, metrics2 = _run_fedavg(wave_mode=2)
    state3, metrics3 = _run_fedavg(wave_mode=3)
    for a, b in zip(jax.tree.leaves(state2), jax.tree.leaves(state3)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-4)
    for m2, m3 in zip(metrics2, metrics3):
        np.testing.assert_allclose(m2["Train/Acc"], m3["Train/Acc"],
                                   atol=2e-3)
        np.testing.assert_allclose(m2["Train/Loss"], m3["Train/Loss"],
                                   atol=2e-3)
