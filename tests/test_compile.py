"""fedwarm (fedml_tpu.compile): AOT round-program warmup through the
persistent compilation cache, and the warm-restart gate.

The headline test mirrors a production restart: run k rounds, "kill"
the server, resume a FRESH process-equivalent (new FedAvgAPI, new jit
caches) via ``RoundRecovery`` over the SAME ``--compile_cache_dir`` --
the resumed run must see ZERO persistent-cache misses (every compile is
a cache load; a hit still fires the
backend-compile event with the deserialization time, so the honest gate
is misses == 0, not compile events == 0), zero steady-state compiles,
and a bitwise-identical trajectory vs an uninterrupted run.
"""

import functools
import tempfile
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import models
from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.algorithms.specs import make_classification_spec
from fedml_tpu.compile import (enumerate_round_programs, warm_restart,
                               warmup_api)
from fedml_tpu.data.synthetic import load_synthetic_images
from fedml_tpu.observability.jaxmon import watch_compiles
from fedml_tpu.resilience.recovery import RoundRecovery
from fedml_tpu.utils.compile_cache import enable_compilation_cache


def _dataset():
    return load_synthetic_images(client_num=4, n_train=64, n_test=32,
                                 image_size=8, partition="hetero",
                                 partition_alpha=0.5, seed=0)


def _spec():
    model = models.LogisticRegression(num_classes=10, apply_sigmoid=False)
    return make_classification_spec(model, jnp.zeros((1, 8, 8, 3)))


def _args(**kw):
    base = dict(client_num_in_total=4, client_num_per_round=4,
                comm_round=10 ** 9, epochs=1, batch_size=8, lr=0.05,
                wd=0.0, client_optimizer="sgd",
                frequency_of_the_test=10 ** 9, seed=0, client_chunk=2,
                wave_mode=1, device_resident="auto",
                device_data_cap_gb=2.0)
    base.update(kw)
    return types.SimpleNamespace(**base)


@pytest.fixture(scope="module")
def shared():
    return {"dataset": _dataset(), "spec": _spec()}


class TestEnumeration:
    def test_bucket_path_programs(self, shared):
        api = FedAvgAPI(shared["dataset"], shared["spec"],
                        _args(device_resident="0",
                              bucket_edges="geometric"))
        names = [p.name for p in enumerate_round_programs(api)]
        assert any(n.startswith("bucket_chunk_s") for n in names)
        assert "advance" in names and "eval" in names
        # one chunk program per bucket edge
        edges = [n for n in names if n.startswith("bucket_chunk_s")]
        assert len(edges) == len(api.runner.edges)

    @pytest.mark.parametrize("mode,expect", [
        (1, "wave"), (2, "lane_round"), (0, "indexed_round")])
    def test_device_resident_programs(self, shared, mode, expect):
        api = FedAvgAPI(shared["dataset"], shared["spec"],
                        _args(wave_mode=mode))
        names = [p.name for p in enumerate_round_programs(api)]
        assert expect in names, names
        assert "eval" in names

    def test_packed_sim_path(self, shared):
        api = FedAvgAPI(shared["dataset"], shared["spec"],
                        _args(device_resident="0"))
        names = [p.name for p in enumerate_round_programs(api)]
        assert "sim_round" in names

    def test_warmup_never_touches_dispatch_cache(self, shared):
        """The AOT probes must not populate the jit dispatch cache:
        compiled_shapes() (the retrace-audit anchor) stays 0 through a
        full warmup and only counts real dispatches."""
        api = FedAvgAPI(shared["dataset"], shared["spec"],
                        _args(device_resident="0",
                              bucket_edges="geometric"))
        report = warmup_api(api)
        assert report["warmup/programs"] >= 3
        assert api.runner.compiled_shapes() == 0
        m = api.train_one_round()
        assert api.runner.compiled_shapes() == m["bucket/shapes"] > 0


class TestWarmRestart:
    def test_two_scope_warm_restart_bitwise(self):
        """k rounds -> kill -> RoundRecovery resume over the same
        compile cache dir: 0 warmup cache misses, 0 steady compiles,
        bitwise-identical trajectory vs uninterrupted."""
        cache_dir = tempfile.mkdtemp(prefix="fedwarm_cache_")
        ckpt_dir = tempfile.mkdtemp(prefix="fedwarm_ckpt_")
        # sub-1s CPU programs MUST persist or nothing round-trips the
        # cache off-TPU -- the exposed threshold (PR 9 note, closed here)
        enable_compilation_cache(cache_dir, min_compile_time_secs=0.0)

        def build():
            return FedAvgAPI(_dataset(), _spec(), _args())

        # uninterrupted reference: 4 rounds (also seeds the cache, as a
        # prior server generation would have)
        ref = build()
        warmup_api(ref)
        for _ in range(4):
            ref.train_one_round()
        ref_final = jax.tree.map(np.asarray, ref.global_state)

        # generation 1: k=2 rounds, snapshot, "kill -9"
        gen1 = build()
        warmup_api(gen1)
        rec = RoundRecovery(ckpt_dir)
        for _ in range(2):
            gen1.train_one_round()
        rec.maybe_save(gen1.round_idx,
                       jax.tree.map(np.asarray, gen1.global_state),
                       server_state=gen1.server_state,
                       rng=np.asarray(gen1.rng), data_rng=gen1._data_rng)
        rec.close()
        del gen1

        # generation 2: fresh API (fresh jit caches -- the in-process
        # stand-in for a new server process), recovery + warm restart
        gen2 = build()
        rec2 = RoundRecovery(
            ckpt_dir,
            warmup_fn=functools.partial(warm_restart, gen2, cache_dir,
                                        0.0))
        with watch_compiles() as restart_watch:
            saved = rec2.restore_latest()
            assert saved is not None and rec2.resumes == 1
            # the warm-restart hook ran and every AOT compile was a
            # cache LOAD, not an XLA compile
            assert rec2.last_warmup is not None
            assert rec2.last_warmup["warmup/cache_misses"] == 0
            assert rec2.last_warmup["warmup/cache_hits"] >= \
                rec2.last_warmup["warmup/programs"]
            gen2.global_state = jax.tree.map(jnp.asarray,
                                             saved["global_state"])
            gen2.server_state = saved["server_state"]
            gen2.rng = jnp.asarray(saved["rng"], dtype=jnp.uint32)
            gen2._data_rng = saved["data_rng"]
            gen2.round_idx = saved["round_idx"]
            gen2.train_one_round()  # round 3: dispatch = cache hits
        with watch_compiles() as steady_watch:
            gen2.train_one_round()  # round 4: steady state
        rec2.close()

        # the whole restarted generation -- warmup AND first dispatch --
        # never missed the cache, and steady state compiles nothing
        assert restart_watch.cache_misses == 0, (
            restart_watch.cache_misses, restart_watch.cache_hits)
        assert steady_watch.total_compiles == 0
        # warmup wall time is cache-load time: pinned by the miss count
        # above (a duration threshold would be flaky on a loaded CI host)
        got_final = jax.tree.map(np.asarray, gen2.global_state)
        for a, b in zip(jax.tree.leaves(ref_final),
                        jax.tree.leaves(got_final)):
            np.testing.assert_array_equal(a, b)

    def test_warm_restart_returns_report_without_hook(self):
        rec = RoundRecovery(tempfile.mkdtemp(prefix="fedwarm_nohook_"))
        assert rec.warm_restart() is None
        rec.close()


class TestCacheCounters:
    def test_watcher_counts_hits_and_misses(self):
        cache_dir = tempfile.mkdtemp(prefix="fedwarm_cnt_")
        enable_compilation_cache(cache_dir, min_compile_time_secs=0.0)

        def make_probe():
            # a FRESH jit object per call: re-compiling the same object
            # is served from jax's in-memory caches with no cache
            # events, while a fresh object with the same code/name is
            # exactly the restart case -- same persistent key, cold
            # in-memory state
            @jax.jit
            def fedwarm_counter_probe(x):
                return jnp.sin(x) @ x.T
            return fedwarm_counter_probe

        a = jax.ShapeDtypeStruct((16, 16), jnp.float32)
        with watch_compiles() as w1:
            make_probe().lower(a).compile()
        assert w1.cache_misses >= 1
        with watch_compiles() as w2:
            make_probe().lower(a).compile()
        assert w2.cache_misses == 0 and w2.cache_hits >= 1
        rep = w2.report()
        assert rep["compile/cache_hits"] == w2.cache_hits
        assert w2.record_fields()["compile_cache_misses"] == 0
