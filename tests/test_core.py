import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import pytree, robust
from fedml_tpu.core.message import Message, params_to_lists, lists_to_params
from fedml_tpu.core.partition import (
    homo_partition,
    hetero_fix_partition,
    non_iid_partition_with_dirichlet_distribution,
    record_data_stats,
)
from fedml_tpu.core.topology import SymmetricTopologyManager, AsymmetricTopologyManager


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (4, 3)), "b": jnp.ones((3,))},
        "batch_stats": {"mean": jnp.full((3,), 2.0)},
    }


class TestPytree:
    def test_weighted_mean_matches_numpy(self):
        trees = [_tree(i) for i in range(3)]
        n = jnp.array([10.0, 30.0, 60.0])
        stacked = pytree.tree_stack(trees)
        avg = pytree.tree_weighted_mean(stacked, n)
        expect = sum((n[i] / 100.0) * trees[i]["params"]["w"] for i in range(3))
        np.testing.assert_allclose(avg["params"]["w"], expect, rtol=1e-5)

    def test_stack_unstack_roundtrip(self):
        trees = [_tree(i) for i in range(4)]
        back = pytree.tree_unstack(pytree.tree_stack(trees), 4)
        for a, b in zip(trees, back):
            np.testing.assert_allclose(a["params"]["w"], b["params"]["w"])

    def test_vector_roundtrip(self):
        t = _tree()
        vec = pytree.tree_flatten_to_vector(t)
        assert vec.shape == (4 * 3 + 3 + 3,)
        back = pytree.tree_unflatten_from_vector(vec, t)
        np.testing.assert_allclose(back["params"]["w"], t["params"]["w"], rtol=1e-6)

    def test_norm_and_dot(self):
        t = {"a": jnp.array([3.0, 4.0])}
        assert float(pytree.tree_l2_norm(t)) == pytest.approx(5.0)

    def test_weighted_psum_mean_under_shard_map(self):
        from jax.sharding import Mesh, PartitionSpec as P

        devs = np.array(jax.devices()[:8])
        mesh = Mesh(devs, ("clients",))
        local = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)  # per-client scalar
        weights = jnp.array([1.0, 2, 3, 4, 5, 6, 7, 8]).reshape(8, 1)

        def f(x, w):
            return pytree.tree_weighted_psum_mean(x[0], w[0, 0], "clients")[None]

        out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("clients"), P("clients")),
                                out_specs=P("clients")))(local, weights)
        expect = float(np.sum(np.arange(8) * np.arange(1, 9)) / 36.0)
        np.testing.assert_allclose(np.asarray(out)[0], expect, rtol=1e-6)


class TestPartition:
    def test_lda_partition_covers_all_samples(self):
        labels = np.random.default_rng(0).integers(0, 10, size=2000)
        parts = non_iid_partition_with_dirichlet_distribution(
            labels, client_num=8, classes=10, alpha=0.5, seed=0)
        all_idx = np.concatenate([parts[i] for i in range(8)])
        assert sorted(all_idx.tolist()) == list(range(2000))
        assert all(len(parts[i]) >= 10 for i in range(8))

    def test_lda_alpha_controls_skew(self):
        labels = np.tile(np.arange(10), 500)
        skewed = non_iid_partition_with_dirichlet_distribution(
            labels, 10, 10, alpha=0.05, seed=1)
        uniform = non_iid_partition_with_dirichlet_distribution(
            labels, 10, 10, alpha=100.0, seed=1)

        def entropy(parts):
            es = []
            for i in parts:
                _, cnt = np.unique(labels[parts[i]], return_counts=True)
                p = cnt / cnt.sum()
                es.append(-(p * np.log(p)).sum())
            return np.mean(es)

        assert entropy(skewed) < entropy(uniform)

    def test_homo_partition(self):
        parts = homo_partition(100, 7, seed=0)
        sizes = [len(parts[i]) for i in range(7)]
        assert sum(sizes) == 100 and max(sizes) - min(sizes) <= 1

    def test_hetero_fix(self):
        labels = np.tile(np.arange(10), 100)
        parts = hetero_fix_partition(labels, 5, seed=0)
        assert sum(len(p) for p in parts.values()) == 1000
        # each client sees few classes
        for i in range(5):
            assert len(np.unique(labels[parts[i]])) <= 4

    def test_segmentation_task(self):
        cats = [list(np.random.default_rng(i).choice(5, size=2, replace=False))
                for i in range(400)]
        parts = non_iid_partition_with_dirichlet_distribution(
            cats, client_num=4, classes=5, alpha=1.0, task="segmentation", seed=0)
        stats = record_data_stats(cats, parts, task="segmentation")
        assert set(parts.keys()) == {0, 1, 2, 3}
        assert all(len(v) > 0 for v in stats.values())
        # each sample assigned exactly once, no duplicates within or across clients
        all_idx = np.concatenate([parts[i] for i in range(4)])
        assert sorted(all_idx.tolist()) == list(range(400))

    def test_infeasible_partition_raises(self):
        labels = np.zeros(50, dtype=np.int64)
        with pytest.raises(ValueError, match="infeasible"):
            non_iid_partition_with_dirichlet_distribution(labels, 20, 1, 0.5, seed=0)

    def test_empty_class_does_not_nan(self):
        # class 9 has zero samples; partition must still cover everything
        labels = np.random.default_rng(0).integers(0, 9, size=1000)
        parts = non_iid_partition_with_dirichlet_distribution(
            labels, client_num=4, classes=10, alpha=0.5, seed=0)
        all_idx = np.concatenate([parts[i] for i in range(4)])
        assert sorted(all_idx.tolist()) == list(range(1000))


class TestTopology:
    def test_symmetric_rows_normalized(self):
        tm = SymmetricTopologyManager(8, neighbor_num=3, seed=0)
        topo = tm.generate_topology()
        np.testing.assert_allclose(topo.sum(axis=1), np.ones(8), rtol=1e-6)
        # symmetric support
        assert ((topo > 0) == (topo.T > 0)).all()
        assert len(tm.get_in_neighbor_idx_list(0)) >= 2
        # neighbor_num=3 must add random links beyond the pure ring, and the
        # seed must matter
        assert (topo > 0).sum() > 8 * 3  # ring+self = 3 nonzeros/row
        other = SymmetricTopologyManager(8, neighbor_num=3, seed=7).generate_topology()
        assert not np.allclose(topo, other)

    def test_asymmetric_connected(self):
        tm = AsymmetricTopologyManager(8, neighbor_num=4, out_neighbor_num=2, seed=0)
        topo = tm.generate_topology()
        np.testing.assert_allclose(topo.sum(axis=1), np.ones(8), rtol=1e-6)
        # ring preserved -> strongly connected
        for i in range(8):
            assert topo[i, (i + 1) % 8] > 0


class TestRobust:
    def test_vectorize_excludes_batch_stats(self):
        t = _tree()
        vec = robust.vectorize_weights(t)
        assert vec.shape == (15,)  # 12 + 3, excluding 3 batch_stats entries

    def test_norm_clipping_bounds_delta(self):
        g = _tree(0)
        local = jax.tree.map(lambda x: x + 10.0, g)
        clipped = robust.norm_diff_clipping(local, g, norm_bound=1.0)
        delta_vec = robust.vectorize_weights(clipped) - robust.vectorize_weights(g)
        assert float(jnp.linalg.norm(delta_vec)) == pytest.approx(1.0, rel=1e-4)
        # batch stats pass through from local, unclipped
        np.testing.assert_allclose(clipped["batch_stats"]["mean"],
                                   local["batch_stats"]["mean"])

    def test_noclip_when_inside_ball(self):
        g = _tree(0)
        local = jax.tree.map(lambda x: x + 1e-4, g)
        clipped = robust.norm_diff_clipping(local, g, norm_bound=10.0)
        np.testing.assert_allclose(clipped["params"]["w"], local["params"]["w"], rtol=1e-5)

    def test_non_dict_pytrees_supported(self):
        g = [jnp.zeros((4,)), jnp.zeros((2, 2))]
        local = [jnp.ones((4,)), jnp.ones((2, 2))]
        clipped = robust.norm_diff_clipping(local, g, norm_bound=1.0)
        assert isinstance(clipped, list)
        noised = robust.add_gaussian_noise(local, 0.1, jax.random.PRNGKey(0))
        assert isinstance(noised, list)

    def test_gaussian_noise(self):
        t = _tree()
        noised = robust.add_gaussian_noise(t, 0.1, jax.random.PRNGKey(0))
        assert not np.allclose(noised["params"]["w"], t["params"]["w"])
        np.testing.assert_allclose(noised["batch_stats"]["mean"], t["batch_stats"]["mean"])


class TestMessage:
    def test_json_roundtrip(self):
        m = Message(type=2, sender_id=0, receiver_id=3)
        m.add_params("model_params", np.arange(4.0))
        s = m.to_json()
        m2 = Message()
        m2.init_from_json_string(s)
        assert m2.get_sender_id() == 0 and m2.get_receiver_id() == 3
        assert m2.get("model_params") == [0.0, 1.0, 2.0, 3.0]

    def test_mobile_codec_roundtrip(self):
        params = {"w": np.ones((2, 2), np.float32)}
        back = lists_to_params(params_to_lists(params))
        np.testing.assert_allclose(back["w"], params["w"])


class TestLocalComm:
    def test_two_rank_ping_pong(self):
        from fedml_tpu.core.comm.local import LocalCommNetwork, run_ranks_in_threads
        from fedml_tpu.core.managers import ServerManager, ClientManager

        net = LocalCommNetwork(2)
        log = []

        class Server(ServerManager):
            def register_message_receive_handlers(self):
                self.register_message_receive_handler("pong", self.on_pong)

            def run(self):
                self.register_message_receive_handlers()
                self.send_message(Message("ping", 0, 1))
                self.com_manager.handle_receive_message()

            def on_pong(self, msg):
                log.append("server got pong from %d" % msg.get_sender_id())
                self.finish()

        class Client(ClientManager):
            def register_message_receive_handlers(self):
                self.register_message_receive_handler("ping", self.on_ping)

            def on_ping(self, msg):
                log.append("client got ping")
                self.send_message(Message("pong", 1, 0))
                self.finish()

        s = Server(None, net.manager(0), rank=0, size=2)
        c = Client(None, net.manager(1), rank=1, size=2)
        run_ranks_in_threads([s.run, c.run])
        assert log == ["client got ping", "server got pong from 1"]
