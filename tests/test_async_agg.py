"""Massive-cohort rounds: FedBuff-style buffered async aggregation
(fedml_tpu/resilience/async_agg.py) + bucketed ragged streaming
(fedml_tpu/parallel/engine.py BucketedStreamRunner).

The load-bearing contracts pinned here:

- **Bitwise oracle**: async with an unbounded buffer, staleness decay 0
  and one flush equals the synchronous ``aggregate_reports`` / fp64
  stream fold bit-for-bit, regardless of arrival order (both sides
  flush through the same sorted-key ``fold_entries_fp64``); the TCP
  async server's whole trajectory equals the synchronous server's.
- **Staleness weighting**: polynomial, monotone, exactly 1 at decay 0.
- **Bucketing**: a step count exactly ON an edge lands in that edge's
  bucket; edges with no members are skipped (never compiled); compiled
  chunk programs == bucket shapes on round 1 and ZERO retraces after.
"""

import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from stream_helpers import stream_round

from fedml_tpu import models
from fedml_tpu.algorithms.specs import make_classification_spec
from fedml_tpu.parallel.engine import BucketedStreamRunner, ClientUpdateConfig
from fedml_tpu.parallel.packing import (_steps_for, bucket_edge_for,
                                        pack_schedule, parse_bucket_edges)
from fedml_tpu.program.aggregation import fold_entries_fp64
from fedml_tpu.core.comm.base import MSG_TYPE_PEER_LOST
from fedml_tpu.core.message import Message
from fedml_tpu.resilience import (AsyncAggPolicy,
                                  AsyncBufferedFedAvgServer,
                                  BufferedAggregator,
                                  FaultPlan, FaultRule, RoundPolicy,
                                  aggregate_reports, run_async_tcp_fedavg,
                                  run_tcp_fedavg, staleness_weight)


def _params(seed, shape=(4, 3)):
    rng = np.random.default_rng(seed)
    return {"w": rng.standard_normal(shape).astype(np.float32),
            "b": rng.standard_normal(shape[-1]).astype(np.float32)}


def _lr_spec(dim=6, classes=4):
    model = models.LogisticRegression(num_classes=classes,
                                      apply_sigmoid=False)
    return make_classification_spec(model, jnp.zeros((1, dim)))


def _ragged_datasets(C, dim=6, classes=4, seed=0, n_lo=1, n_hi=40):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(C):
        n = int(rng.integers(n_lo, n_hi))
        out.append({"x": rng.standard_normal((n, dim)).astype(np.float32),
                    "y": rng.integers(0, classes, n).astype(np.int32)})
    return out


# ---------------------------------------------------------------------------
# BufferedAggregator: the fold/flush machinery
# ---------------------------------------------------------------------------
class TestBufferedAggregator:
    def test_oracle_bitwise_vs_aggregate_reports(self):
        """Oracle settings + SHUFFLED arrival == aggregate_reports bitwise
        (the flush is the same sorted-rank fp64 fold)."""
        reports = {r: (float(3 * r + 1), _params(r)) for r in (8, 2, 5, 11)}
        agg = BufferedAggregator(AsyncAggPolicy(buffer_k=4,
                                                staleness_decay=0.0))
        for r in (5, 11, 2, 8):  # arrival order != rank order
            agg.fold(r, reports[r][0], reports[r][1])
        assert agg.ready()
        res = agg.flush()
        want, total = aggregate_reports(reports)
        for k in want:
            np.testing.assert_array_equal(res.params[k], want[k])
        assert res.weight == total
        assert agg.version == 1 and agg.depth == 0

    def test_arrival_order_independent(self):
        reports = {r: (float(r + 1), _params(100 + r)) for r in range(6)}

        def run(order):
            agg = BufferedAggregator(AsyncAggPolicy(buffer_k=6))
            for r in order:
                agg.fold(r, reports[r][0], reports[r][1])
            return agg.flush().params

        a = run([0, 1, 2, 3, 4, 5])
        b = run([5, 3, 0, 4, 2, 1])
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])

    def test_staleness_weight_monotone(self):
        for decay in (0.25, 0.5, 1.0, 2.0):
            ws = [staleness_weight(s, decay) for s in range(6)]
            assert ws[0] == 1.0
            assert all(a >= b for a, b in zip(ws, ws[1:]))
            assert ws[-1] < 1.0
        # decay 0 (the oracle setting) weights every staleness exactly 1
        assert all(staleness_weight(s, 0.0) == 1.0 for s in range(6))

    def test_staleness_decay_downweights_stale_update(self):
        """Higher decay pulls the flushed average monotonically toward
        the fresh contributor."""
        fresh = {"w": np.zeros((2, 2), np.float32)}
        stale = {"w": np.ones((2, 2), np.float32)}
        got = []
        for decay in (0.0, 0.5, 1.0, 2.0):
            agg = BufferedAggregator(
                AsyncAggPolicy(buffer_k=2, staleness_decay=decay))
            agg.fold("fresh", 1.0, fresh, staleness=0)
            agg.fold("stale", 1.0, stale, staleness=3)
            got.append(float(agg.flush().params["w"][0, 0]))
        assert got[0] == pytest.approx(0.5)  # no discount: plain average
        assert all(a > b for a, b in zip(got, got[1:]))  # monotone in decay
        assert got[-1] < 0.1  # (1+3)**-2 = 1/16 of the fresh weight

    def test_ready_caps_at_target_and_counts_clients(self):
        agg = BufferedAggregator(AsyncAggPolicy(buffer_k=10))
        agg.fold(1, 1.0, _params(1))
        assert not agg.ready()
        assert agg.ready(target=1)  # only 1 client still alive
        agg.fold(2, 1.0, _params(2), clients=1)
        assert agg.ready(target=2)
        # preweighted partials count their member clients toward K
        agg2 = BufferedAggregator(AsyncAggPolicy(buffer_k=5))
        agg2.fold(0, 7.0, _params(3), clients=5, preweighted=True)
        assert agg2.ready()

    def test_overwrite_same_key_newest_wins(self):
        agg = BufferedAggregator(AsyncAggPolicy(buffer_k=4))
        agg.fold(1, 1.0, {"w": np.zeros(2, np.float32)})
        agg.fold(1, 1.0, {"w": np.ones(2, np.float32)})
        assert agg.counters["overwrites"] == 1
        assert agg.counters["clients_folded"] == 1  # distinct clients
        agg.fold(2, 1.0, {"w": np.zeros(2, np.float32)})
        res = agg.flush()
        assert float(res.params["w"][0]) == pytest.approx(0.5)

    def test_flush_empty_raises(self):
        agg = BufferedAggregator(AsyncAggPolicy())
        with pytest.raises(ValueError):
            agg.flush()

    def test_observability_gauges_and_span_pair(self):
        """With fedtrace armed, folds/flushes emit the buffer-fold /
        buffer-flush span pair and the fed_buffer_depth /
        fed_update_staleness gauges (what --trace shows when the round
        barrier disappears)."""
        from fedml_tpu.observability.registry import (MetricsRegistry,
                                                      set_registry)
        from fedml_tpu.observability.tracing import Tracer, set_tracer

        reg, tr = MetricsRegistry(), Tracer()
        prev_r, prev_t = set_registry(reg), set_tracer(tr)
        try:
            agg = BufferedAggregator(AsyncAggPolicy(buffer_k=2,
                                                    staleness_decay=0.5))
            agg.fold(1, 1.0, _params(0), staleness=2)
            assert reg.get("fed_buffer_depth") == 1
            assert reg.get("fed_update_staleness") == 2
            agg.fold(2, 1.0, _params(1))
            agg.flush()
            assert reg.get("fed_buffer_depth") == 0
            assert reg.get("fed_buffer_flushes_total",
                           reason="buffer_k") == 1
            names = [s.name for s in tr.finished_spans()]
            assert names.count("buffer-fold") == 2
            assert names.count("buffer-flush") == 1
        finally:
            set_registry(prev_r)
            set_tracer(prev_t)

    def test_record_carries_depth_and_staleness(self):
        agg = BufferedAggregator(AsyncAggPolicy(buffer_k=4,
                                                staleness_decay=0.5))
        agg.fold(1, 1.0, _params(0), staleness=2)
        rec = agg.record()
        assert rec["async/buffer_depth"] == 1
        assert rec["async/max_staleness"] == 2
        assert rec["async/depth_peak"] == 1

    def test_fold_many_bitwise_vs_per_report(self):
        """The batched-entry fold (ISSUE 14): fold_many over a chunk ==
        the same folds one call at a time -- identical flush boundaries,
        counters, and flushed bytes -- while costing one lock
        acquisition per flush window."""
        reports = [(r, float(2 * r + 1), _params(50 + r),
                    0 if r % 3 else 1) for r in range(9)]

        def run_batched():
            agg = BufferedAggregator(AsyncAggPolicy(buffer_k=4,
                                                    staleness_decay=0.5))
            flushed = []
            i = 0
            while i < len(reports):
                consumed, _depth = agg.fold_many(reports[i:])
                i += consumed
                if agg.ready():
                    flushed.append(agg.flush())
            return agg, flushed

        def run_single():
            agg = BufferedAggregator(AsyncAggPolicy(buffer_k=4,
                                                    staleness_decay=0.5))
            flushed = []
            for key, w, p, s in reports:
                agg.fold(key, w, p, staleness=s)
                if agg.ready():
                    flushed.append(agg.flush())
            return agg, flushed

        agg_b, fb = run_batched()
        agg_s, fs = run_single()
        assert agg_b.counters == agg_s.counters
        assert agg_b.depth == agg_s.depth  # the 9th report stays buffered
        assert len(fb) == len(fs) == 2
        for a, b in zip(fb, fs):
            assert a.contributors == b.contributors
            assert a.weight == b.weight
            assert a.max_staleness == b.max_staleness
            for k in a.params:
                np.testing.assert_array_equal(a.params[k], b.params[k])

    def test_fold_many_stops_at_ready_target(self):
        # the flush boundary lands on exactly the entry that fills the
        # (target-capped) buffer, never past it
        agg = BufferedAggregator(AsyncAggPolicy(buffer_k=64))
        entries = [(r, 1.0, _params(r), 0) for r in range(5)]
        consumed, depth = agg.fold_many(entries, ready_target=3)
        assert consumed == 3 and depth == 3
        assert agg.ready(target=3)
        consumed2, depth2 = agg.fold_many(entries[consumed:],
                                          ready_target=10)
        assert consumed2 == 2 and depth2 == 5

    def test_fold_many_overwrites_do_not_advance_ready(self):
        # re-folding an existing key never counts toward K (newest wins,
        # clients unchanged) -- same rule as per-report folds
        agg = BufferedAggregator(AsyncAggPolicy(buffer_k=3))
        entries = [(1, 1.0, _params(0), 0), (1, 2.0, _params(1), 0),
                   (2, 1.0, _params(2), 0), (3, 1.0, _params(3), 0)]
        consumed, depth = agg.fold_many(entries)
        assert consumed == 4 and depth == 3
        assert agg.counters["overwrites"] == 1


# ---------------------------------------------------------------------------
# Distributed FSM: AsyncBufferedFedAvgServer over real TCP
# ---------------------------------------------------------------------------
class TestAsyncServer:
    def test_oracle_trajectory_matches_sync_server_bitwise(self):
        """No deadline, decay 0, K = cohort: every flush collects every
        client exactly once -- the whole trajectory equals the
        synchronous ResilientFedAvgServer's, bit for bit."""
        w0 = {"w": np.zeros((4, 4), np.float32),
              "b": np.ones(4, np.float32)}
        a = run_async_tcp_fedavg(
            4, 3, AsyncAggPolicy(buffer_k=3, staleness_decay=0.0), w0)
        s = run_tcp_fedavg(4, 3, RoundPolicy(), w0)
        assert a.failed is None and s.failed is None
        assert len(a.history) == 3 == len(s.history)
        for got, want in zip(a.history, s.history):
            for k in got:
                np.testing.assert_array_equal(got[k], want[k])
        # every flush window collected the full cohort
        assert a.flush_log == [(1, 2, 3)] * 3

    def test_deadline_flush_completes_degraded_without_straggler(self):
        """A stalled client must not hold the buffer: the flush deadline
        produces a below-K (degraded) server update from the fast
        clients, barrier-free."""
        w0 = {"w": np.zeros((3, 3), np.float32)}
        plan = FaultPlan(seed=5, rules=(
            FaultRule("stall", rank=3, msg_type="res_report", nth=1,
                      delay_s=6.0),))
        srv = run_async_tcp_fedavg(
            4, 2, AsyncAggPolicy(buffer_k=3, staleness_decay=0.5,
                                 flush_deadline_s=0.5),
            w0, fault_plan=plan, join_timeout=60)
        assert srv.failed is None
        assert len(srv.history) == 2
        assert srv.agg.counters["deadline_flushes"] >= 1
        # the first flush went out without rank 3's stalled report
        assert 3 not in srv.flush_log[0]

    def test_peer_lost_after_run_end_ignored(self):
        """Teardown race: a peer-lost dispatched after the final flush
        must not mark a completed run failed or flush past
        total_updates."""
        class _Comm:
            def add_observer(self, o):
                pass

            def stop_receive_message(self):
                pass

        srv = AsyncBufferedFedAvgServer(
            None, _Comm(), 3, {"w": np.zeros(2, np.float32)}, 1,
            AsyncAggPolicy(buffer_k=2))
        srv.agg.fold(1, 1.0, {"w": np.ones(2, np.float32)})
        srv.agg.flush()  # run complete (version == total_updates)
        srv._on_peer_lost(Message(MSG_TYPE_PEER_LOST, 2, 0))
        assert srv.failed is None
        assert srv.alive == {1, 2}          # not mutated post-run
        assert srv.agg.version == 1         # no flush past the end

    def test_peer_loss_mid_buffer_flushes_survivors(self):
        """K > survivors: the lost peer triggers the capped-ready check
        instead of deadlocking the buffer."""
        w0 = {"w": np.zeros((3, 3), np.float32)}
        plan = FaultPlan(seed=9, rules=(
            FaultRule("kill", rank=3, msg_type="res_report", nth=1),))
        srv = run_async_tcp_fedavg(
            4, 2, AsyncAggPolicy(buffer_k=3, staleness_decay=0.0),
            w0, fault_plan=plan, join_timeout=60)
        assert srv.failed is None
        assert len(srv.history) == 2
        assert srv.counters["clients_dropped"] == 1
        assert all(3 not in ranks for ranks in srv.flush_log)


# ---------------------------------------------------------------------------
# Bucketed ragged streaming (engine) + async composition
# ---------------------------------------------------------------------------
class TestBucketEdges:
    def test_geometric_covers_s_max(self):
        assert parse_bucket_edges("geometric", 50) == [8, 16, 32, 64]
        assert parse_bucket_edges(None, 7) == [8]
        assert parse_bucket_edges("geo", 8) == [8]

    def test_explicit_list_extends_to_cover(self):
        assert parse_bucket_edges("8,24", 20) == [8, 24]
        # short lists extend geometrically rather than truncating clients
        assert parse_bucket_edges("8,16", 100) == [8, 16, 32, 64, 128]
        with pytest.raises(ValueError):
            parse_bucket_edges("0,8", 10)

    def test_boundary_client_lands_on_its_edge(self):
        """A step count exactly ON an edge belongs to that edge's bucket
        -- no off-by-one into the next (2x padding) bucket. This is the
        rule the runner dispatches through (bucket_edge_for)."""
        got = bucket_edge_for([16, 8, 17, 1, 32], [8, 16, 32])
        assert list(got) == [16, 8, 32, 8, 32]

    def test_oversized_client_raises(self):
        with pytest.raises(ValueError):
            bucket_edge_for([100], [8, 16])

    def test_pack_schedule_s_max_guard(self):
        with pytest.raises(ValueError):
            pack_schedule([100], 8, 1, s_max=8)
        out = pack_schedule([100], 8, 1, s_max=16)
        assert out["idx"].shape[1] == 16


class TestBucketedStreamRunner:
    def _build(self, C=13, chunk=4, seed=0, epochs=1, bs=4, edges=None):
        spec = _lr_spec()
        datasets = _ragged_datasets(C, seed=seed)
        s_max = max(_steps_for(len(d["y"]), bs, epochs) for d in datasets)
        runner = BucketedStreamRunner(
            spec, ClientUpdateConfig(lr=0.1), client_chunk=chunk,
            batch_size=bs, epochs=epochs,
            edges=edges or parse_bucket_edges("geometric", s_max))
        gs0 = spec.init_fn(jax.random.PRNGKey(1))
        return runner, datasets, gs0

    @pytest.mark.parametrize("path", ["buffered", "sync_stream"])
    def test_async_oracle_bitwise_vs_sync_stream(self, path):
        """One round's chunk payload sums through the canonical fold
        (``fold_entries_fp64`` over the ordinals, what the server paths
        run) against the runner's two folds of the same sums: the
        buffered path (unbounded buffer + decay 0: one drain flush)
        byte for byte; the synchronous stream, which folds on the device
        in two float32 words, within its one-ulp contract."""
        runner, datasets, gs0 = self._build()
        rng = jax.random.PRNGKey(7)
        # the oracle's entries: the chunk program's own outputs, taken
        # by a buffer that never flushes through the runner's fold
        entries = {}

        class Recorder(BufferedAggregator):
            def fold(self, key, weight, payload, **kw):
                entries[key] = (key, float(weight), payload, 1.0)
                return super().fold(key, weight, payload, **kw)

        oracle = Recorder(
            AsyncAggPolicy(buffer_k=10 ** 9, staleness_decay=0.0))
        stream_round(
            runner, jax.tree.map(jnp.copy, gs0), (), datasets, rng,
            data_rng=np.random.default_rng(3), aggregator=oracle)
        want, _ = fold_entries_fp64(entries.values())
        assert len(entries) == -(-len(datasets) // runner.client_chunk)

        agg = None
        if path == "buffered":
            agg = BufferedAggregator(
                AsyncAggPolicy(buffer_k=10 ** 9, staleness_decay=0.0))
        gs, _, info = stream_round(
            runner, jax.tree.map(jnp.copy, gs0), (), datasets, rng,
            data_rng=np.random.default_rng(3), aggregator=agg)
        # FedAvg's server step hands the average on as the new state
        got, want = jax.tree.leaves(gs), jax.tree.leaves(want)
        assert len(got) == len(want)
        for a, b in zip(got, want):
            if path == "buffered":
                np.testing.assert_array_equal(np.asarray(a), b)
            else:
                np.testing.assert_array_max_ulp(np.asarray(a), b, maxulp=1)
        if path == "buffered":
            assert info["fold"] == "host"
            assert info["async"]["async/flushes"] == 1
            assert info["async"]["async/max_staleness"] == 0
        else:
            assert info["fold"] == "device" and "async" not in info

    def test_matches_flat_round_numerically(self):
        """Full-batch single-step cohort: the streamed result equals the
        flat vmapped round (same schedules, same per-client keys) up to
        the fp64-fold-vs-device-f32 aggregation difference."""
        from fedml_tpu.parallel.engine import make_sim_round
        from fedml_tpu.parallel.packing import pack_cohort

        spec = _lr_spec()
        datasets = _ragged_datasets(9, seed=2, n_hi=30)
        bs = max(len(d["y"]) for d in datasets)
        s_max = max(_steps_for(len(d["y"]), bs, 1) for d in datasets)
        cfg = ClientUpdateConfig(lr=0.1)
        runner = BucketedStreamRunner(
            spec, cfg, client_chunk=4, batch_size=bs, epochs=1,
            edges=parse_bucket_edges(None, s_max))
        gs0 = spec.init_fn(jax.random.PRNGKey(1))
        rng = jax.random.PRNGKey(7)
        gs_b, _, _ = stream_round(
            runner, jax.tree.map(jnp.copy, gs0), (), datasets, rng,
            data_rng=np.random.default_rng(3))
        flat = make_sim_round(spec, cfg)
        packed = {k: jnp.asarray(v) for k, v in
                  pack_cohort(datasets, bs, 1,
                              rng=np.random.default_rng(3)).items()}
        gs_f, _, _ = flat(jax.tree.map(jnp.copy, gs0), (), packed, rng)
        for a, b in zip(jax.tree.leaves(gs_b), jax.tree.leaves(gs_f)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)

    def test_zero_reporting_bucket_skipped(self):
        """Edges with no members are never dispatched (and never
        compiled): single-step clients against [8, 16, 32] edges compile
        exactly one program."""
        runner, datasets, gs0 = self._build(
            C=6, chunk=4, edges=[8, 16, 32])
        # n_hi=40 / bs=4 / 1 epoch -> max 10 steps; rebuild with tiny
        # shards so every client fits the first edge
        datasets = _ragged_datasets(6, seed=4, n_hi=4)
        gs, _, info = stream_round(
            runner, jax.tree.map(jnp.copy, gs0), (), datasets,
            jax.random.PRNGKey(0), data_rng=np.random.default_rng(0))
        per = {b["edge"]: b for b in info["bucket"]["per_bucket"]}
        assert per[8]["skipped"] == 0 and per[8]["clients"] == 6
        assert per[16]["skipped"] == 1 and per[32]["skipped"] == 1
        assert info["bucket"]["buckets_used"] == 1
        assert runner.compiled_shapes() == 1
        assert all(np.isfinite(x).all() for x in
                   map(np.asarray, jax.tree.leaves(gs)))

    def test_retraces_equal_bucket_shapes_then_zero(self):
        """Round 1 compiles one program per bucket shape; rounds 2+ are
        retrace-free even with different cohorts (edges are sized from
        the population, so shapes are stable)."""
        from fedml_tpu.analysis.runtime import audit

        spec = _lr_spec()
        population = _ragged_datasets(24, seed=5)
        bs, epochs = 4, 1
        s_max = max(_steps_for(len(d["y"]), bs, epochs) for d in population)
        edges = parse_bucket_edges("geometric", s_max)
        runner = BucketedStreamRunner(
            spec, ClientUpdateConfig(lr=0.1), client_chunk=4,
            batch_size=bs, epochs=epochs, edges=edges)
        gs = spec.init_fn(jax.random.PRNGKey(1))
        ss = ()
        data_rng = np.random.default_rng(0)
        cohort_rng = np.random.default_rng(7)
        report = {}
        with audit(metrics_logger=report.update) as auditor:
            shapes_after_r1 = None
            for r in range(3):
                cohort = sorted(cohort_rng.choice(24, 16, replace=False))
                gs, ss, _ = stream_round(
                    runner, gs, ss, [population[i] for i in cohort],
                    jax.random.PRNGKey(r), data_rng=data_rng)
                auditor.sync_and_mark_round(gs)
                if r == 0:
                    shapes_after_r1 = runner.compiled_shapes()
        assert shapes_after_r1 >= 1
        assert runner.compiled_shapes() == shapes_after_r1  # no growth
        assert report["audit/retraces_per_round"][1:] == [0, 0], report
        assert report["audit/steady_state_retraces"] == 0

    def test_mid_round_flushes_produce_staleness(self):
        """Small K + in-flight window: the buffer flushes mid-round and
        later folds observe staleness > 0 (and a staleness discount
        changes the result vs decay 0). K = 3 chunks against a 4-chunk
        window makes flush boundaries cross version bumps, so at least
        one flush window holds MIXED staleness -- a uniform-staleness
        window would cancel the discount in the ratio."""
        runner, datasets, gs0 = self._build(C=16, chunk=2)
        rng = jax.random.PRNGKey(3)

        def run(decay):
            agg = BufferedAggregator(
                AsyncAggPolicy(buffer_k=6, staleness_decay=decay))
            gs, _, info = stream_round(
                runner, jax.tree.map(jnp.copy, gs0), (), datasets, rng,
                data_rng=np.random.default_rng(3), aggregator=agg,
                async_window=4)
            return gs, info

        gs_a, info = run(0.0)
        assert info["async"]["async/flushes"] > 1
        assert info["async"]["async/max_staleness"] >= 1
        gs_b, _ = run(2.0)
        diffs = [float(np.abs(np.asarray(a) - np.asarray(b)).max())
                 for a, b in zip(jax.tree.leaves(gs_a),
                                 jax.tree.leaves(gs_b))]
        assert max(diffs) > 0  # the discount is live, not cosmetic

    def test_full_batch_convention_pins_B_across_cohorts(self):
        """batch_size=-1 resolves ONCE and stays pinned: re-sampled
        cohorts with different max shards must not change the compiled
        [C, S, B] shape (the zero-steady-state-retrace invariant)."""
        spec = _lr_spec()
        population = _ragged_datasets(12, seed=8, n_hi=30)
        runner = BucketedStreamRunner(
            spec, ClientUpdateConfig(lr=0.1), client_chunk=4,
            batch_size=-1, epochs=1,
            edges=parse_bucket_edges("geometric", 32))
        gs = spec.init_fn(jax.random.PRNGKey(0))
        ss = ()
        data_rng = np.random.default_rng(0)
        gs, ss, _ = stream_round(runner, gs, ss, population[:6],
                                 jax.random.PRNGKey(1), data_rng=data_rng)
        pinned = runner.batch_size
        assert pinned == max(len(d["y"]) for d in population[:6])
        gs, ss, _ = stream_round(runner, gs, ss, population[6:],
                                 jax.random.PRNGKey(2), data_rng=data_rng)
        assert runner.batch_size == pinned  # not re-derived per cohort

    def test_weight_accounting_is_honest(self):
        """Total folded weight over a sync round equals the cohort's
        sample total (per-client n_i weighting survives the partial-sum
        streaming)."""
        runner, datasets, gs0 = self._build(C=11, chunk=3)
        agg = BufferedAggregator(
            AsyncAggPolicy(buffer_k=10 ** 9, staleness_decay=0.0))
        stream_round(runner, jax.tree.map(jnp.copy, gs0), (), datasets,
                     jax.random.PRNGKey(0),
                     data_rng=np.random.default_rng(0), aggregator=agg)
        assert agg.counters["clients_folded"] == 11


class TestFedAvgAPIWiring:
    def _args(self, **kw):
        base = dict(client_num_in_total=10, client_num_per_round=10,
                    comm_round=3, epochs=1, batch_size=4, lr=0.1, wd=0.0,
                    client_optimizer="sgd", frequency_of_the_test=100,
                    seed=0, client_chunk=4, bucket_edges="geometric",
                    async_agg=0, buffer_k=4, staleness_decay=0.5,
                    async_window=4, device_resident="0")
        base.update(kw)
        return types.SimpleNamespace(**base)

    def _dataset(self, C=10):
        datasets = _ragged_datasets(C, dim=6, classes=4, seed=1)
        local = dict(enumerate(datasets))
        nums = {c: len(d["y"]) for c, d in local.items()}
        test = datasets[0]
        total = sum(nums.values())
        return [total, len(test["y"]), None, test, nums, local,
                {0: test}, 4]

    def test_round_records_carry_bucket_and_async_series(self):
        from fedml_tpu.algorithms.fedavg import FedAvgAPI
        api = FedAvgAPI(self._dataset(), _lr_spec(), self._args(async_agg=1))
        m = api.train_one_round()
        assert m["bucket/shapes"] >= 1
        assert 0 <= m["bucket/waste_frac"] < 1
        assert "async/depth_peak" in m and "async/version" in m
        m2 = api.train_one_round()
        assert m2["async/version"] > m["async/version"]  # carries across

    def test_bucket_rejects_mesh_but_composes_with_compressor(self):
        # fedsqueeze (ISSUE 15): the former compressor guard is LIFTED --
        # --bucket_edges + --compressor runs streaming-EF (the chunk
        # program compresses each lane's delta); only mesh still rejects
        from fedml_tpu.algorithms.fedavg import FedAvgAPI
        with pytest.raises(ValueError, match="mesh"):
            FedAvgAPI(self._dataset(), _lr_spec(), self._args(),
                      mesh=object())
        api = FedAvgAPI(self._dataset(), _lr_spec(),
                        self._args(compressor="qsgd:8"))
        assert api.runner.mode == "bucketed"
        assert api.runner.compressor is api.compressor is not None
        m = api.train_one_round()
        # byte accounting present (this toy model is header-dominated,
        # so the RATIO is no gate here -- the sized gates are the soak's)
        assert m["bytes_on_wire"] > 0 and m["compression_ratio"] > 0


class TestStreamingEF:
    """fedsqueeze tentpole (2): the BucketedStreamRunner's compressor
    composition -- EF inside the jitted chunk program, residuals keyed
    by stable client id through a ResidualStore, the compiled-shape and
    zero-retrace contracts intact."""

    def _args(self, **kw):
        base = dict(client_num_in_total=14, client_num_per_round=14,
                    comm_round=10, epochs=1, batch_size=4, lr=0.1, wd=0.0,
                    client_optimizer="sgd", frequency_of_the_test=100,
                    seed=0, client_chunk=4, bucket_edges="geometric",
                    async_agg=0, buffer_k=4, staleness_decay=0.5,
                    async_window=4, device_resident="0")
        base.update(kw)
        return types.SimpleNamespace(**base)

    def _dataset(self, C=14):
        datasets = _ragged_datasets(C, dim=6, classes=4, seed=1)
        local = dict(enumerate(datasets))
        nums = {c: len(d["y"]) for c, d in local.items()}
        test = datasets[0]
        return [sum(nums.values()), len(test["y"]), None, test, nums,
                local, {0: test}, 4]

    def _api(self, **kw):
        from fedml_tpu.algorithms.fedavg import FedAvgAPI
        return FedAvgAPI(self._dataset(), _lr_spec(), self._args(**kw))

    def test_compressor_none_bitwise_identical_to_plain(self):
        api_p, api_n = self._api(), self._api(compressor="none")
        assert api_n.compressor is None  # identity: the plain program
        for _ in range(2):
            api_p.train_one_round()
            api_n.train_one_round()
        for a, b in zip(jax.tree.leaves(api_p.global_state),
                        jax.tree.leaves(api_n.global_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_zero_retraces_and_shapes_equal_buckets_compressed(self):
        from fedml_tpu.analysis.runtime import audit
        report = {}
        with audit(metrics_logger=report.update) as auditor:
            api = self._api(compressor="topk:0.25",
                            client_num_per_round=10)  # re-sampled cohorts
            m = None
            for _ in range(3):
                m = api.train_one_round()
                auditor.sync_and_mark_round(api.global_state)
        assert report["audit/steady_state_retraces"] == 0, report
        assert api.runner.compiled_shapes() == m["bucket/shapes"] > 0

    def test_async_oracle_bitwise_with_compressor(self):
        # unbounded buffer + decay 0 == the synchronous compressed fold,
        # bit for bit (both run the same chunk program + fp64 fold)
        api_s = self._api(compressor="qsgd:4")
        api_a = self._api(compressor="qsgd:4", async_agg=1,
                          buffer_k=10 ** 9, staleness_decay=0.0)
        for _ in range(2):
            api_s.train_one_round()
            api_a.train_one_round()
        for a, b in zip(jax.tree.leaves(api_s.global_state),
                        jax.tree.leaves(api_a.global_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_dense_and_host_spill_residual_stores_bitwise(self):
        # the unbounded-population path: the lazy host-spill backing
        # produces the identical trajectory to dense device rows
        from fedml_tpu.compression import ResidualStore
        api_d = self._api(compressor="topk:0.25")
        assert api_d.runner.residual_store.dense
        api_s = self._api(compressor="topk:0.25")
        api_s.runner.residual_store = ResidualStore(api_s.global_state["params"],
                                        dense=False)
        for _ in range(3):
            api_d.train_one_round()
            api_s.train_one_round()
        for a, b in zip(jax.tree.leaves(api_d.global_state),
                        jax.tree.leaves(api_s.global_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_residuals_keyed_by_stable_id_across_resampled_cohorts(self):
        # a client outside round 2's cohort must keep its round-1
        # residual byte-for-byte (id-keyed, never cohort-slot-keyed)
        api = self._api(compressor="topk:0.25", client_num_per_round=7,
                        seed=3)
        api.train_one_round()
        from fedml_tpu.algorithms.fedavg import client_sampling
        c1 = set(client_sampling(0, 14, 7))
        c2 = set(client_sampling(1, 14, 7))
        touched = sorted(c1)
        r1 = {i: api.runner.residual_store.peek(i) for i in range(14)}
        for i in range(14):  # round 1 touched exactly its cohort
            nz = any(np.any(v) for v in jax.tree.leaves(r1[i]))
            assert nz == (i in touched), i
        api.train_one_round()
        for i in sorted(set(range(14)) - c2):
            for a, b in zip(jax.tree.leaves(r1[i]),
                            jax.tree.leaves(api.runner.residual_store.peek(i))):
                np.testing.assert_array_equal(a, b)

    def test_ef_converges_close_to_plain(self):
        # the convergence gate: biased compressors + EF track the plain
        # trajectory (docs/COMPRESSION.md tolerance; seeds matched)
        api_p, api_c = self._api(), self._api(compressor="topk:0.25")
        mp = mc = None
        for _ in range(8):
            mp = api_p.train_one_round()
            mc = api_c.train_one_round()
        assert abs(mp["Train/Loss"] - mc["Train/Loss"]) < 0.2, (mp, mc)

    def test_runner_requires_residual_store(self):
        from fedml_tpu.compression.compressors import get_compressor
        spec = _lr_spec()
        runner = BucketedStreamRunner(
            spec, ClientUpdateConfig(lr=0.1), client_chunk=4,
            batch_size=4, epochs=1, edges=[8],
            compressor=get_compressor("qsgd:8"))
        gs = spec.init_fn(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="residual_store"):
            stream_round(runner, gs, (), _ragged_datasets(4, n_hi=4),
                         jax.random.PRNGKey(1))
