import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flax.linen as nn

from fedml_tpu import models
from fedml_tpu.algorithms.specs import make_classification_spec
from fedml_tpu.algorithms.fedavg import FedAvgAPI, client_sampling
from fedml_tpu.core import pytree
from fedml_tpu.data import load_synthetic_federated
from fedml_tpu.parallel.engine import (
    ClientUpdateConfig, LaneRunner, ShardedLaneRunner, WaveRunner,
    make_client_update, make_indexed_sim_round, make_sim_round,
    make_sharded_round, make_eval_fn)
from fedml_tpu.parallel.mesh import make_client_mesh
from fedml_tpu.parallel.packing import (
    pack_cohort, pack_eval, pack_schedule, stack_clients)


def _args(**kw):
    base = dict(client_num_per_round=4, comm_round=2, epochs=1, batch_size=16,
                lr=0.1, client_optimizer="sgd", wd=0.0,
                frequency_of_the_test=1, ci=0, seed=0)
    base.update(kw)
    return types.SimpleNamespace(**base)


def _fresh(tree):
    """Deep-copy a state pytree. The engine round fns donate their state
    arguments (fedlint FL104 burn-down): a donated buffer is deleted when
    the call returns, so A/B comparisons that invoke two round paths from
    one initial state must hand each its own buffers."""
    return jax.tree.map(jnp.copy, tree)


def _lr_spec(feature_dim=60, classes=10):
    model = models.LogisticRegression(num_classes=classes, apply_sigmoid=False)
    return make_classification_spec(model, jnp.zeros((1, feature_dim)))


class TestClientUpdate:
    def test_padded_steps_are_noops(self):
        spec = _lr_spec()
        cfg = ClientUpdateConfig(lr=0.1)
        update = make_client_update(spec, cfg)
        rng = jax.random.PRNGKey(0)
        state = spec.init_fn(rng)

        x = np.random.default_rng(0).normal(size=(10, 60)).astype(np.float32)
        y = np.zeros(10, np.int64)
        # same data packed with different amounts of padding must agree
        p1 = pack_cohort([{"x": x, "y": y}], batch_size=10, epochs=1,
                         step_bucket=1)
        p2 = pack_cohort([{"x": x, "y": y}], batch_size=10, epochs=1,
                         step_bucket=16)
        s1, aux1, _ = update(state, jax.tree.map(lambda a: a[0], p1), rng)
        s2, aux2, _ = update(state, jax.tree.map(lambda a: a[0], p2), rng)
        np.testing.assert_allclose(s1["params"]["linear"]["kernel"],
                                   s2["params"]["linear"]["kernel"], atol=1e-6)
        assert float(aux1["steps"]) == 1 and float(aux2["steps"]) == 1

    def test_ragged_batches_masked_mean(self):
        # 10 samples, batch 4 -> batches of 4,4,2; last batch mean over 2
        spec = _lr_spec()
        update = make_client_update(spec, ClientUpdateConfig(lr=0.05))
        state = spec.init_fn(jax.random.PRNGKey(0))
        x = np.random.default_rng(1).normal(size=(10, 60)).astype(np.float32)
        y = np.arange(10) % 10
        p = pack_cohort([{"x": x, "y": y}], batch_size=4, epochs=2,
                        step_bucket=1)
        assert p["mask"].shape[1] == 6  # 3 steps x 2 epochs
        s, aux, metrics = update(state, jax.tree.map(lambda a: a[0], p),
                                 jax.random.PRNGKey(1))
        assert float(aux["n"]) == 10
        assert float(metrics["count"]) == 20  # 10 samples x 2 epochs


class TestFederatedEqualsCentralized:
    """The CI equivalence invariant (reference ``CI-script-fedavg.sh:42-47``):
    full-batch, 1-local-epoch FedAvg over all clients == one centralized
    full-batch SGD step. Exact algebra of weighted psum aggregation."""

    def test_equivalence(self):
        spec = _lr_spec()
        cfg = ClientUpdateConfig(lr=0.5)
        rng = jax.random.PRNGKey(42)
        state = spec.init_fn(rng)

        rnd = np.random.default_rng(0)
        clients = []
        for n in (7, 13, 29, 11):  # ragged on purpose
            clients.append({
                "x": rnd.normal(size=(n, 60)).astype(np.float32),
                "y": rnd.integers(0, 10, n).astype(np.int64)})
        pooled = {"x": np.concatenate([c["x"] for c in clients]),
                  "y": np.concatenate([c["y"] for c in clients])}

        round_fn = make_sim_round(spec, cfg)
        packed = pack_cohort(clients, batch_size=64, epochs=1)
        fed_state, _, _ = round_fn(_fresh(state), (), packed, rng)

        central_packed = pack_cohort([pooled], batch_size=64, epochs=1)
        central_state, _, _ = round_fn(_fresh(state), (), central_packed,
                                       rng)

        for a, b in zip(jax.tree.leaves(fed_state["params"]),
                        jax.tree.leaves(central_state["params"])):
            np.testing.assert_allclose(a, b, atol=2e-5)

    def test_sim_equals_sharded(self):
        spec = _lr_spec()
        cfg = ClientUpdateConfig(lr=0.3)
        state = spec.init_fn(jax.random.PRNGKey(7))
        rnd = np.random.default_rng(3)
        clients = [{"x": rnd.normal(size=(n, 60)).astype(np.float32),
                    "y": rnd.integers(0, 10, n).astype(np.int64)}
                   for n in (16, 8, 24, 12, 16, 8, 8, 20)]
        packed = pack_cohort(clients, batch_size=8, epochs=1)

        sim = make_sim_round(spec, cfg)
        mesh = make_client_mesh(8)
        sharded = make_sharded_round(spec, cfg, mesh)

        s1, _, _ = sim(_fresh(state), (), packed, jax.random.PRNGKey(5))
        s2, _, _ = sharded(_fresh(state), (), packed,
                           jax.random.PRNGKey(5))
        for a, b in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s2["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_sharded_multiple_clients_per_shard(self):
        spec = _lr_spec()
        cfg = ClientUpdateConfig(lr=0.3)
        state = spec.init_fn(jax.random.PRNGKey(7))
        rnd = np.random.default_rng(3)
        clients = [{"x": rnd.normal(size=(8, 60)).astype(np.float32),
                    "y": rnd.integers(0, 10, 8).astype(np.int64)}
                   for _ in range(16)]  # 16 clients over 8 shards -> 2 each
        packed = pack_cohort(clients, batch_size=8, epochs=1)
        sim = make_sim_round(spec, cfg)
        sharded = make_sharded_round(spec, cfg, make_client_mesh(8))
        s1, _, _ = sim(_fresh(state), (), packed, jax.random.PRNGKey(5))
        s2, _, _ = sharded(_fresh(state), (), packed,
                           jax.random.PRNGKey(5))
        np.testing.assert_allclose(
            np.asarray(s1["params"]["linear"]["kernel"]),
            np.asarray(s2["params"]["linear"]["kernel"]), atol=1e-5)


class TestWaveRunner:
    """The wave path must reproduce the flat indexed round: same schedules
    (identical ``pack_schedule`` draw), same per-client rngs, aggregation
    equal up to float reassociation."""

    def _setup(self, sizes, seed=0, lr=0.2):
        spec = _lr_spec()
        cfg = ClientUpdateConfig(lr=lr)
        state = spec.init_fn(jax.random.PRNGKey(seed))
        rnd = np.random.default_rng(seed)
        clients = [{"x": rnd.normal(size=(n, 60)).astype(np.float32),
                    "y": rnd.integers(0, 10, n).astype(np.int64)}
                   for n in sizes]
        stacked = stack_clients(clients)
        dd = {"x": jnp.asarray(stacked["x"]), "y": jnp.asarray(stacked["y"])}
        sched = pack_schedule([len(c["y"]) for c in clients], 8, epochs=2,
                              rng=np.random.default_rng(1))
        return spec, cfg, state, dd, sched

    @pytest.mark.parametrize("chunk", [2, 3, 64])
    def test_wave_equals_flat(self, chunk):
        sizes = (40, 8, 24, 16, 5)
        spec, cfg, state, dd, sched = self._setup(sizes)
        rng = jax.random.PRNGKey(3)

        flat = make_indexed_sim_round(spec, cfg)
        js = {k: jnp.asarray(v) for k, v in sched.items()}
        s_flat, _, info_flat = flat(_fresh(state), (), dd, js, rng)

        wr = WaveRunner(spec, cfg, client_chunk=chunk)
        s_wave, _, info_wave = wr.run_schedule(
            _fresh(state), (), dd, list(range(len(sizes))), sched, rng)

        for a, b in zip(jax.tree.leaves(s_flat), jax.tree.leaves(s_wave)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)
        mf = jax.tree.map(lambda x: np.asarray(x).sum(0),
                          info_flat["metrics"])
        mw = jax.tree.map(np.asarray, info_wave["metrics"])
        np.testing.assert_allclose(mf["count"], mw["count"], rtol=1e-6)
        np.testing.assert_allclose(mf["loss_sum"], mw["loss_sum"], rtol=1e-4)
        # aux comes back in cohort order despite size-sorted dispatch
        np.testing.assert_array_equal(info_wave["aux"]["n"], sched["n"])
        steps_expected = (np.asarray(sched["mask"]).sum(2) > 0).sum(1)
        np.testing.assert_array_equal(info_wave["aux"]["steps"],
                                      steps_expected)

    def test_wave_with_server_hook(self):
        # FedOpt-style pseudo-gradient server step flows through waves
        from fedml_tpu.core import pytree as pt

        def payload_fn(local_state, global_state, aux):
            return pt.tree_sub(global_state["params"], local_state["params"])

        def server_fn(global_state, avg_delta, server_state, rng):
            new = dict(global_state)
            new["params"] = pt.tree_sub(
                global_state["params"], pt.tree_scale(avg_delta, 0.5))
            return new, server_state

        sizes = (12, 30, 7, 21)
        spec, cfg, state, dd, sched = self._setup(sizes)
        rng = jax.random.PRNGKey(11)
        flat = make_indexed_sim_round(spec, cfg, payload_fn, server_fn)
        js = {k: jnp.asarray(v) for k, v in sched.items()}
        s_flat, _, _ = flat(_fresh(state), (), dd, js, rng)
        wr = WaveRunner(spec, cfg, payload_fn, server_fn, client_chunk=2)
        s_wave, _, _ = wr.run_schedule(
            _fresh(state), (), dd, list(range(len(sizes))), sched, rng)
        for a, b in zip(jax.tree.leaves(s_flat), jax.tree.leaves(s_wave)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)

    @pytest.mark.parametrize("n_lanes", [1, 3, 8])
    def test_lanes_equal_flat(self, n_lanes):
        """Packed lanes (one dispatch, flush/reset at client boundaries)
        must reproduce the flat round exactly: same schedules, same
        per-client-step RNG stream, weighted aggregation equal up to
        reassociation."""
        sizes = (40, 8, 24, 16, 5, 31)
        spec, cfg, state, dd, sched = self._setup(sizes)
        rng = jax.random.PRNGKey(3)

        flat = make_indexed_sim_round(spec, cfg)
        js = {k: jnp.asarray(v) for k, v in sched.items()}
        s_flat, _, info_flat = flat(_fresh(state), (), dd, js, rng)

        lr_ = LaneRunner(spec, cfg, n_lanes=n_lanes)
        s_lane, _, info_lane = lr_.run_schedule(
            _fresh(state), (), dd, list(range(len(sizes))), sched, rng)

        for a, b in zip(jax.tree.leaves(s_flat), jax.tree.leaves(s_lane)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)
        mf = jax.tree.map(lambda x: np.asarray(x).sum(0),
                          info_flat["metrics"])
        ml = jax.tree.map(np.asarray, info_lane["metrics"])
        np.testing.assert_allclose(mf["count"], ml["count"], rtol=1e-6)
        np.testing.assert_allclose(mf["loss_sum"], ml["loss_sum"],
                                   rtol=1e-4)
        np.testing.assert_array_equal(info_lane["aux"]["n"], sched["n"])

    def test_lanes_with_server_hook(self):
        from fedml_tpu.core import pytree as pt

        def payload_fn(local_state, global_state, aux):
            tau = jnp.maximum(aux["steps"].astype(jnp.float32), 1.0)
            return {"d": pt.tree_scale(
                pt.tree_sub(global_state["params"], local_state["params"]),
                1.0 / tau), "tau": tau}

        def server_fn(global_state, avg, server_state, rng):
            new = dict(global_state)
            new["params"] = pt.tree_sub(
                global_state["params"],
                pt.tree_scale(avg["d"], avg["tau"]))
            return new, server_state

        sizes = (12, 30, 7, 21, 16)
        spec, cfg, state, dd, sched = self._setup(sizes)
        rng = jax.random.PRNGKey(11)
        flat = make_indexed_sim_round(spec, cfg, payload_fn, server_fn)
        js = {k: jnp.asarray(v) for k, v in sched.items()}
        s_flat, _, _ = flat(_fresh(state), (), dd, js, rng)
        lr_ = LaneRunner(spec, cfg, payload_fn, server_fn, n_lanes=2)
        s_lane, _, _ = lr_.run_schedule(
            _fresh(state), (), dd, list(range(len(sizes))), sched, rng)
        for a, b in zip(jax.tree.leaves(s_flat), jax.tree.leaves(s_lane)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)

    def test_pack_lanes_covers_every_step_once(self):
        from fedml_tpu.parallel.packing import pack_lanes, pack_schedule
        ns = [37, 5, 18, 64, 9, 27]
        sched = pack_schedule(ns, 8, epochs=2, rng=np.random.default_rng(2))
        lanes = pack_lanes(sched, 4)
        steps_pc = (np.asarray(sched["mask"]).sum(2) > 0).sum(1)
        # every client's real steps appear exactly once across all lanes
        total = (lanes["mask"].sum(2) > 0).sum()
        assert total == steps_pc.sum()
        assert lanes["flush"].sum() == len(ns)
        np.testing.assert_allclose(sorted(lanes["flush_n"][lanes["flush"] > 0]),
                                   sorted(np.asarray(ns, np.float32)))
        # LPT balance: max lane load < total/K + max client load
        K = lanes["idx"].shape[0]
        assert lanes["trip"] <= steps_pc.sum() / K + steps_pc.max()

    def test_sharded_lanes_equal_flat(self):
        """Multi-chip lanes: rows sharded over an 8-device mesh, every
        shard runs its residents as packed lanes, psum aggregation --
        result equals the flat single-device round."""
        from fedml_tpu.parallel.multihost import global_cohort

        sizes = (40, 8, 24, 16, 5, 31, 12, 9, 27, 14, 6)  # 11 clients
        spec, cfg, state, dd, sched = self._setup(sizes)
        rng = jax.random.PRNGKey(3)

        flat = make_indexed_sim_round(spec, cfg)
        js = {k: jnp.asarray(v) for k, v in sched.items()}
        s_flat, _, info_flat = flat(_fresh(state), (), dd, js, rng)

        mesh = make_client_mesh(8)
        placed = global_cohort(mesh, {"x": np.asarray(dd["x"]),
                                      "y": np.asarray(dd["y"])})
        slr = ShardedLaneRunner(spec, cfg, mesh, n_lanes=2)
        s_sh, _, info_sh = slr.run_schedule(
            _fresh(state), (), placed, list(range(len(sizes))), sched, rng)

        for a, b in zip(jax.tree.leaves(s_flat), jax.tree.leaves(s_sh)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)
        mf = jax.tree.map(lambda x: np.asarray(x).sum(0),
                          info_flat["metrics"])
        ms = jax.tree.map(np.asarray, info_sh["metrics"])
        np.testing.assert_allclose(mf["count"], ms["count"], rtol=1e-6)

    def test_sharded_lanes_subset_cohort_with_hook(self):
        """Cohort subset (some shards own zero members) + FedOpt-style
        server hook through the sharded lanes."""
        from fedml_tpu.core import pytree as pt
        from fedml_tpu.parallel.multihost import global_cohort

        def payload_fn(local_state, global_state, aux):
            return pt.tree_sub(global_state["params"], local_state["params"])

        def server_fn(global_state, avg_delta, server_state, rng):
            new = dict(global_state)
            new["params"] = pt.tree_sub(
                global_state["params"], pt.tree_scale(avg_delta, 0.5))
            return new, server_state

        sizes = (10, 40, 6, 28, 18, 22, 9, 33)
        spec, cfg, state, dd, _ = self._setup(sizes)
        cohort = [1, 6, 2]  # rows land on a strict subset of shards
        ns = [40, 9, 6]
        sched = pack_schedule(ns, 8, epochs=1,
                              rng=np.random.default_rng(5))
        rng = jax.random.PRNGKey(9)

        flat = make_indexed_sim_round(spec, cfg, payload_fn, server_fn)
        sel = np.asarray(cohort)
        dd_sub = {k: jnp.asarray(np.asarray(v)[sel]) for k, v in dd.items()}
        js = {k: jnp.asarray(v) for k, v in sched.items()}
        s_flat, _, _ = flat(_fresh(state), (), dd_sub, js, rng)

        mesh = make_client_mesh(8)
        placed = global_cohort(mesh, {"x": np.asarray(dd["x"]),
                                      "y": np.asarray(dd["y"])})
        slr = ShardedLaneRunner(spec, cfg, mesh, payload_fn, server_fn,
                                n_lanes=2)
        s_sh, _, info = slr.run_schedule(_fresh(state), (), placed, cohort,
                                      sched, rng)
        assert float(np.asarray(info["metrics"]["count"])) == sum(ns)
        for a, b in zip(jax.tree.leaves(s_flat), jax.tree.leaves(s_sh)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)

    def test_wave_subset_cohort(self):
        # cohort is a subset of device rows, in non-sorted order
        sizes = (10, 40, 6, 28, 18)
        spec, cfg, state, dd, _ = self._setup(sizes)
        cohort = [3, 0, 4]
        ns = [28, 10, 18]
        sched = pack_schedule(ns, 8, epochs=1,
                              rng=np.random.default_rng(5))
        wr = WaveRunner(spec, cfg, client_chunk=2)
        s_wave, _, info = wr.run_schedule(state, (), dd, cohort, sched,
                                       jax.random.PRNGKey(9))
        assert float(np.asarray(info["metrics"]["count"])) == sum(ns)
        for leaf in jax.tree.leaves(s_wave):
            assert np.isfinite(np.asarray(leaf)).all()


class TestDonationSafety:
    """The FL104 burn-down contract: round fns donate their state args
    (old + new model state must not be live simultaneously on TPU), and
    that must change nothing about the math -- re-invocation on fresh
    buffers reproduces the identical trajectory, outputs stay readable,
    and the only thing that dies is the donated input."""

    def _setup(self):
        spec = _lr_spec()
        cfg = ClientUpdateConfig(lr=0.4)
        state = spec.init_fn(jax.random.PRNGKey(2))
        rnd = np.random.default_rng(9)
        clients = [{"x": rnd.normal(size=(n, 60)).astype(np.float32),
                    "y": rnd.integers(0, 10, n).astype(np.int64)}
                   for n in (12, 20, 8, 16)]
        packed = pack_cohort(clients, batch_size=8, epochs=1)
        return spec, cfg, state, packed

    @staticmethod
    def _backend_donates():
        probe = jnp.ones((4,))
        jax.jit(lambda v: v * 2, donate_argnums=(0,))(probe)
        return probe.is_deleted()

    def test_donation_is_real_and_input_is_deleted(self):
        if not self._backend_donates():
            pytest.skip("backend ignores buffer donation")
        spec, cfg, state, packed = self._setup()
        round_fn = make_sim_round(spec, cfg)
        arg = _fresh(state)
        out, _, _ = round_fn(arg, (), packed, jax.random.PRNGKey(0))
        # the HBM claim is real: the donated input buffers are gone...
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(arg))
        # ...and reading one raises rather than returning stale data
        with pytest.raises(RuntimeError, match="deleted"):
            np.asarray(jax.tree.leaves(arg)[0])
        # outputs are live, finite, and the original template untouched
        assert all(np.isfinite(np.asarray(x)).all()
                   for x in jax.tree.leaves(out))
        assert all(not leaf.is_deleted() for leaf in jax.tree.leaves(state))

    def test_reinvocation_on_fresh_buffers_is_deterministic(self):
        # the A/B guarantee donation must not break: two invocations from
        # fresh copies of the same initial state are bit-identical
        spec, cfg, state, packed = self._setup()
        round_fn = make_sim_round(spec, cfg)
        rng = jax.random.PRNGKey(7)
        s1, _, _ = round_fn(_fresh(state), (), packed, rng)
        s2, _, _ = round_fn(_fresh(state), (), packed, rng)
        for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_round_chaining_through_donated_state(self):
        # the production idiom: state = round_fn(state, ...) chains rounds
        # through donated buffers without copies
        spec, cfg, state, packed = self._setup()
        round_fn = make_sim_round(spec, cfg)
        chained = _fresh(state)
        for r in range(3):
            chained, _, _ = round_fn(chained, (), packed,
                                     jax.random.fold_in(jax.random.PRNGKey(1),
                                                        r))
        # reference trajectory without ever donating the caller's copy
        ref = _fresh(state)
        for r in range(3):
            ref, _, _ = round_fn(_fresh(ref), (), packed,
                                 jax.random.fold_in(jax.random.PRNGKey(1), r))
        for a, b in zip(jax.tree.leaves(chained), jax.tree.leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_indexed_round_does_not_donate_device_data(self):
        # device-resident shards persist across rounds: only the state
        # args are donated, never the HBM dataset or the schedule
        spec, cfg, state, _ = self._setup()
        rnd = np.random.default_rng(3)
        clients = [{"x": rnd.normal(size=(n, 60)).astype(np.float32),
                    "y": rnd.integers(0, 10, n).astype(np.int64)}
                   for n in (10, 14, 6)]
        stacked = stack_clients(clients)
        dd = {"x": jnp.asarray(stacked["x"]), "y": jnp.asarray(stacked["y"])}
        sched = {k: jnp.asarray(v) for k, v in pack_schedule(
            [len(c["y"]) for c in clients], 8, epochs=1,
            rng=np.random.default_rng(1)).items()}
        flat = make_indexed_sim_round(spec, cfg)
        s = _fresh(state)
        for r in range(2):  # second round re-reads dd/sched: must be live
            s, _, _ = flat(s, (), dd, sched,
                           jax.random.fold_in(jax.random.PRNGKey(4), r))
        assert not dd["x"].is_deleted() and not sched["idx"].is_deleted()
        assert all(np.isfinite(np.asarray(x)).all()
                   for x in jax.tree.leaves(s))


class TestBatchNormState:
    def test_batch_stats_travel_through_round(self):
        class TinyBN(nn.Module):
            @nn.compact
            def __call__(self, x, train=False):
                x = nn.Dense(8)(x)
                x = nn.BatchNorm(use_running_average=not train)(x)
                return nn.Dense(3)(x)

        model = TinyBN()
        spec = make_classification_spec(model, jnp.zeros((1, 5)))
        state = spec.init_fn(jax.random.PRNGKey(0))
        assert "batch_stats" in state
        rnd = np.random.default_rng(0)
        clients = [{"x": rnd.normal(size=(12, 5)).astype(np.float32),
                    "y": rnd.integers(0, 3, 12).astype(np.int64)}
                   for _ in range(4)]
        packed = pack_cohort(clients, batch_size=4, epochs=1)
        round_fn = make_sim_round(spec, ClientUpdateConfig(lr=0.1))
        new_state, _, _ = round_fn(_fresh(state), (), packed,
                                   jax.random.PRNGKey(1))
        # running stats must have moved away from init (mean 0)
        assert not np.allclose(
            np.asarray(jax.tree.leaves(new_state["batch_stats"])[0]),
            np.asarray(jax.tree.leaves(state["batch_stats"])[0]))


class TestFedAvgAPI:
    def test_sampling_parity(self):
        # reference reseeds np.random with the round index
        a = client_sampling(3, 100, 10)
        b = client_sampling(3, 100, 10)
        assert a == b
        np.random.seed(3)
        expect = list(np.random.choice(range(100), 10, replace=False))
        assert a == expect

    def test_learning_happens(self):
        dataset = load_synthetic_federated(client_num=8, n_train=800,
                                           n_test=200, seed=0)
        spec = _lr_spec()
        args = _args(client_num_per_round=8, comm_round=8, lr=0.5,
                     frequency_of_the_test=100)
        api = FedAvgAPI(dataset, spec, args)
        first = api.train_one_round()
        for _ in range(7):
            last = api.train_one_round()
        final = api.evaluate_global()
        assert last["Train/Acc"] > first["Train/Acc"]
        # per-client labeling functions (LEAF synthetic) cap global accuracy;
        # 0.25 is well above the 0.1 chance level
        assert final["Test/Acc"] > 0.25

    def test_mesh_lanes_match_classic_mesh_path(self):
        """FedAvgAPI with mesh + wave_mode=2 (sharded lanes) must match
        the classic sharded round (pack_cohort path): both consume the
        same one-draw schedule contract, so trajectories agree."""
        dataset = load_synthetic_federated(client_num=8, n_train=640,
                                           n_test=160, seed=0)
        spec = _lr_spec()
        mesh = make_client_mesh(8)

        def run(mode):
            args = _args(client_num_per_round=8, comm_round=2, lr=0.3,
                         frequency_of_the_test=100, wave_mode=mode,
                         client_chunk=2, device_resident="auto")
            api = FedAvgAPI(dataset, spec, args, mesh=mesh)
            if mode == 2:
                assert api.runner.mode == "sharded-lanes"
            api.train_one_round()
            api.train_one_round()
            return api.global_state

        classic = run(1)   # pack_cohort + make_sharded_round
        lanes = run(2)     # sharded device residency + packed lanes
        for a, b in zip(jax.tree.leaves(classic), jax.tree.leaves(lanes)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)

    def test_wave_mode_2_lane_rounds(self):
        dataset = load_synthetic_federated(client_num=8, n_train=800,
                                           n_test=200, seed=0)
        spec = _lr_spec()
        args = _args(client_num_per_round=8, comm_round=4, lr=0.5,
                     frequency_of_the_test=100, wave_mode=2, client_chunk=3,
                     device_resident="auto")
        api = FedAvgAPI(dataset, spec, args)
        assert api.runner.mode == "lanes"
        first = api.train_one_round()
        for _ in range(3):
            last = api.train_one_round()
        assert last["Train/Acc"] > first["Train/Acc"]

    @pytest.mark.parametrize("mode", [1, 2])
    def test_mesh_round_compiles_once(self, mode):
        """The initial state must already live where the sharded rounds
        return it (replicated over the mesh): a state left on device 0
        hands round 1 a new input sharding and the whole round program
        compiles a second time -- seconds here, most of a minute for
        ResNet-56 on four chips."""
        from fedml_tpu.observability.jaxmon import watch_compiles

        dataset = load_synthetic_federated(client_num=8, n_train=640,
                                           n_test=160, seed=0)
        args = _args(client_num_per_round=8, comm_round=3, wave_mode=mode,
                     client_chunk=2, device_resident="auto")
        api = FedAvgAPI(dataset, _lr_spec(), args, mesh=make_client_mesh(8))
        with watch_compiles() as watch:
            for _ in range(3):
                api.train_one_round()
        assert watch.compiles_per_round[1:] == [0, 0], \
            watch.compiles_per_round

    def test_wave_mode_3_without_packed_lowering_is_an_error(self):
        # no silent mode 2: the LR spec has no lane_loss_builder, so
        # asking for MXU-packed lanes fails at construction, on one chip
        # and on a mesh alike
        dataset = load_synthetic_federated(client_num=8, n_train=640,
                                           n_test=160, seed=0)
        spec = _lr_spec()
        assert spec.lane_loss_builder is None
        args = _args(client_num_per_round=8, wave_mode=3, client_chunk=2,
                     device_resident="auto")
        for mesh in (None, make_client_mesh(8)):
            with pytest.raises(ValueError, match="lane-packed lowering"):
                FedAvgAPI(dataset, spec, args, mesh=mesh)

    def test_lanes_over_the_data_cap_is_an_error(self):
        # lanes run over device-resident data only: over the cap the API
        # says so (size and cap) instead of switching to the host-packed
        # round; wave_mode 1 keeps its documented auto behaviour
        dataset = load_synthetic_federated(client_num=8, n_train=640,
                                           n_test=160, seed=0)
        spec = _lr_spec()
        lanes = _args(client_num_per_round=8, wave_mode=2, client_chunk=2,
                      device_resident="auto", device_data_cap_gb=1e-6)
        with pytest.raises(ValueError, match=r"GB and "
                                             r"--device_data_cap_gb is 1e-06"):
            FedAvgAPI(dataset, spec, lanes)
        waves = _args(client_num_per_round=8, wave_mode=1, client_chunk=2,
                      device_resident="auto", device_data_cap_gb=1e-6)
        assert FedAvgAPI(dataset, spec, waves).runner.mode == "packed"

    @pytest.mark.parametrize("bypass, match", [
        (dict(device_resident="0"), "--device_resident 0"),
        (dict(compressor="topk"), "--compressor"),
        (dict(bucket_edges="geometric"), "--bucket_edges"),
    ])
    def test_lanes_with_residency_bypassed_is_an_error(self, bypass, match):
        # an option that bypasses residency would run the host-packed,
        # compressed or bucketed round under the lane mode's name
        dataset = load_synthetic_federated(client_num=8, n_train=640,
                                           n_test=160, seed=0)
        args = _args(client_num_per_round=8, wave_mode=2, client_chunk=2,
                     **{"device_resident": "auto", **bypass})
        with pytest.raises(ValueError, match=match):
            FedAvgAPI(dataset, _lr_spec(), args)

    def test_partial_participation(self):
        dataset = load_synthetic_federated(client_num=10, n_train=500,
                                           n_test=100, seed=0)
        spec = _lr_spec()
        args = _args(client_num_per_round=3, comm_round=2)
        api = FedAvgAPI(dataset, spec, args)
        api.train()
        assert len(api.history) == 2
