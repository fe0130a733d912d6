"""RoundProgram conformance suite (ISSUE 16 satellite).

The tentpole promise: ONE ``RoundProgram`` behind both paradigms. Both
consumers are thin over it -- the sim engine jits the program
(``program.compile_sim``), the distributed control plane drives the same
program through its jax-free ``host_view()`` -- so every cell of the
{sync, async} x {none, qsgd, topk} x {full cohort, degraded subset}
matrix must fold the same reports to the same bytes.

What each layer pins, and where "bitwise" is promised by pre-existing
gates (this suite re-asserts, never weakens, those promises):

- **contract** -- ``from_args``/``replace``/codec coercion; the
  compatibility aliases (``RoundPolicy``, ``AsyncAggPolicy``) ARE the
  program's legs (identity, not copies); the cohort vocabulary
  (``client_sampling``/``sample_ranks``/``attempt_seed``) is single-homed
  in ``program.cohort`` and every consumer re-exports it.
- **host-fold matrix** -- for every codec x cohort cell, the sync leg's
  ``fold_reports`` equals the async leg's oracle flush (decay 0,
  ``buffer_k`` >= cohort, one window) bit for bit, under arbitrary
  arrival order -- the async-oracle gate, now stated once against the
  program instead of per consumer.
- **sim consumer** -- ``FedAvgAPI`` exposes the program it compiled;
  rebuilding the same program yields a bitwise-identical trajectory
  (compile_sim is a pure function of the program + data).
- **distributed consumer** -- the TCP server's round folds are exactly
  ``program.host_view().fold_reports`` (re-derived bitwise from the
  reporting log), and both paradigms complete over compressed wire
  specs end to end. Degraded-subset exactness over real faults stays
  pinned in tests/test_resilience.py (chaos A/B); here the degraded
  dimension is the subset-renormalized fold cells.
"""

import random

import numpy as np
import pytest

from fedml_tpu.compression.wire import CompressedUpdate, encode_rng
from fedml_tpu.program import (AGG_ASYNC, AGG_SYNC, AggregationPolicy,
                               BufferedAggregator, CodecSpec, CohortPolicy,
                               RoundProgram, attempt_seed, client_sampling)

CODECS = ["none", "qsgd:4", "topk:0.25"]
COHORTS = ["full", "degraded"]
WORLD = 6
DEGRADED_DROP = {2, 5}


def _tree(seed):
    rng = np.random.default_rng(seed)
    return {"b": rng.standard_normal(5).astype(np.float32),
            "w": rng.standard_normal((4, 5)).astype(np.float32)}


def _reports(codec, cohort):
    """One round's reports for a matrix cell: ``{rank: (n, payload)}``.

    Dense payloads for the ``none`` cell; for wire codecs the payload is
    what the decode stage hands the fold -- a :class:`CompressedUpdate`
    (encoded delta + shared base), encoded with the keyed rng rule.
    """
    base = _tree(99)
    ranks = [r for r in range(WORLD)
             if cohort == "full" or r not in DEGRADED_DROP]
    spec = CodecSpec(codec)
    reports = {}
    for r in ranks:
        n = 10 + 3 * r
        delta = _tree(r)
        if spec.enabled:
            enc = spec.host().encode(delta, encode_rng((r, 0, 0)))
            payload = CompressedUpdate(enc=enc, spec=codec, base=base)
        else:
            payload = {k: base[k] + delta[k] for k in base}
        reports[r] = (n, payload)
    return base, reports


class TestProgramContract:
    def test_defaults_are_the_sync_barrier_program(self):
        p = RoundProgram()
        assert p.aggregation.mode == AGG_SYNC and not p.is_async
        assert not p.codec.enabled
        assert p.cohort == CohortPolicy()

    def test_from_args_builds_both_paradigms(self):
        import types
        sync = RoundProgram.from_args(types.SimpleNamespace())
        assert not sync.is_async
        asyn = RoundProgram.from_args(types.SimpleNamespace(
            async_agg=1, buffer_k=7, staleness_decay=0.25,
            compressor="topk:0.1", deadline=2.0, overselect=0.5))
        assert asyn.is_async and asyn.aggregation.mode == AGG_ASYNC
        assert asyn.aggregation.buffer_k == 7
        assert asyn.cohort.deadline_s == 2.0
        assert asyn.cohort.overselect == 0.5
        assert asyn.codec.enabled and asyn.codec.name == "topk"

    def test_codec_coercion(self):
        for off in ("none", "", "off", None, CodecSpec("false")):
            assert not RoundProgram(codec=off).codec.enabled
        assert RoundProgram(codec="qsgd:2").codec.name == "qsgd"
        with pytest.raises(TypeError):
            CodecSpec.coerce(3.14)

    def test_replace_is_how_steering_evolves_the_program(self):
        # frozen value semantics: steering replaces, never mutates
        p = RoundProgram()
        q = p.replace(cohort=CohortPolicy(overselect=0.5))
        assert p.cohort.overselect == 0.0  # original untouched
        assert q.cohort.overselect == 0.5
        assert q.host_view().select_count(4, 10) == 6

    def test_compat_aliases_are_the_program_legs(self):
        # the shims re-export, they do not fork: identity, not equality
        from fedml_tpu.algorithms import fedavg
        from fedml_tpu.program import aggregation, cohort
        from fedml_tpu.resilience import async_agg, policy
        assert policy.RoundPolicy is CohortPolicy
        assert async_agg.AsyncAggPolicy is AggregationPolicy
        assert policy.fold_entries_fp64 is aggregation.fold_entries_fp64
        assert policy.aggregate_reports is aggregation.aggregate_reports
        assert fedavg.client_sampling is cohort.client_sampling
        assert fedavg.attempt_seed is cohort.attempt_seed
        assert async_agg.BufferedAggregator is aggregation.BufferedAggregator

    def test_manifest_roundtrip_pinned(self):
        # status.json / run manifests serialize the ACTIVE program via
        # manifest() -- always with sort_keys=True (the FL135-clean
        # reference shape). The byte pin keeps the operator-facing
        # format from drifting silently; from_manifest round-trips
        # everything but the opaque client_update.
        import json
        p = RoundProgram(
            cohort=CohortPolicy(deadline_s=2.0, overselect=0.5,
                                quorum=0.4),
            aggregation=AggregationPolicy(buffer_k=8,
                                          staleness_decay=0.25),
            codec="qsgd:4", client_update=object())
        m = p.manifest()
        assert "client_update" not in json.dumps(m)
        assert json.dumps(m, sort_keys=True) == (
            '{"aggregation": {"async_window": 4, "buffer_k": 8, '
            '"flush_deadline_s": 0.0, "mode": "async", '
            '"staleness_decay": 0.25}, '
            '"codec": {"enabled": true, "spec": "qsgd:4"}, '
            '"cohort": {"deadline_s": 2.0, "max_round_retries": 3, '
            '"overselect": 0.5, "quorum": 0.4}, '
            '"dp": null, "robust": null}')
        back = RoundProgram.from_manifest(
            json.loads(json.dumps(m, sort_keys=True)))
        assert back == p.replace(client_update=None)
        # defaults round-trip too (the sync barrier program)
        assert RoundProgram.from_manifest(
            RoundProgram().manifest()) == RoundProgram()
        # version skew surfaces instead of being swallowed
        bad = RoundProgram().manifest()
        bad["cohort"]["warp_factor"] = 9
        with pytest.raises(TypeError):
            RoundProgram.from_manifest(bad)

    def test_cohort_vocabulary_single_homed(self):
        # the distributed sampler under its historical name == the
        # program's; the sim sampler == the host view's -- one cohort
        # language across both consumers
        from fedml_tpu.resilience.integration import _sample_ranks
        host = RoundProgram().host_view()
        ranks = [1, 2, 4, 5, 7]
        assert _sample_ranks(3, 1, ranks, 3) == host.sample_ranks(
            3, 1, ranks, 3)
        assert client_sampling(2, 10, 4) == host.sample_cohort(2, 10, 4)
        assert attempt_seed(5, 0) == 5
        assert attempt_seed(5, 2) == 5 + 2 * 1_000_003


class TestFoldConformanceMatrix:
    """Every {codec} x {cohort} cell: the sync leg and the async oracle
    leg of the SAME program fold the same reports to the same bytes."""

    @pytest.mark.parametrize("cohort", COHORTS)
    @pytest.mark.parametrize("codec", CODECS)
    def test_sync_fold_equals_async_oracle_flush(self, codec, cohort):
        _, reports = _reports(codec, cohort)
        program = RoundProgram(codec=codec)
        want, total = program.host_view().fold_reports(reports)
        assert total == float(sum(n for n, _ in reports.values()))

        oracle = AggregationPolicy(buffer_k=len(reports),
                                   staleness_decay=0.0)
        aprog = program.replace(aggregation=oracle)
        for seed in (0, 1):  # two adversarial arrival orders
            agg = aprog.host_view().make_aggregator()
            order = list(reports)
            random.Random(seed).shuffle(order)
            for r in order:
                n, payload = reports[r]
                agg.fold(r, n, payload)
            assert agg.ready()
            out = agg.flush()
            assert out.weight == total
            assert set(out.contributors) == set(reports)
            for k in want:
                np.testing.assert_array_equal(want[k], out.params[k],
                                              err_msg=f"{codec}/{cohort}/{k}")

    @pytest.mark.parametrize("codec", CODECS)
    def test_stale_entries_with_decay_zero_stay_oracle_exact(self, codec):
        # the oracle promise is about WEIGHTS (decay 0 => 1.0 exactly),
        # not about staleness being zero: stale entries under decay 0
        # must not perturb a single bit
        _, reports = _reports(codec, "full")
        program = RoundProgram(codec=codec)
        want, _ = program.host_view().fold_reports(reports)
        agg = BufferedAggregator(AggregationPolicy(buffer_k=len(reports),
                                                   staleness_decay=0.0))
        for r, (n, payload) in reports.items():
            agg.fold(r, n, payload, staleness=3 + r)
        out = agg.flush()
        for k in want:
            np.testing.assert_array_equal(want[k], out.params[k])

    @pytest.mark.parametrize("cohort", COHORTS)
    @pytest.mark.parametrize("codec", CODECS)
    def test_fold_tracks_dense_reconstruction(self, codec, cohort):
        # semantic anchor for the sparse fold: the O(k) compressed fold
        # equals the dense f64 weighted average of (base + decode(enc))
        # to float tolerance (its own canonical combine order is the
        # bitwise contract -- docs/COMPRESSION.md)
        base, reports = _reports(codec, cohort)
        spec = CodecSpec(codec)
        got, _ = RoundProgram(codec=codec).host_view().fold_reports(reports)
        num = {k: np.zeros_like(base[k], np.float64) for k in base}
        den = 0.0
        for r, (n, payload) in sorted(reports.items()):
            if spec.enabled:
                dec = spec.host().decode(payload.enc)
                dense = {k: base[k].astype(np.float64) + dec[k]
                         for k in base}
            else:
                dense = payload
            for k in num:
                num[k] += float(n) * np.asarray(dense[k], np.float64)
            den += float(n)
        for k in got:
            np.testing.assert_allclose(got[k], (num[k] / den), rtol=1e-5,
                                       atol=1e-6)

    def test_degraded_cell_renormalizes_over_reporters(self):
        # the subset average, never the zero-padded cohort average
        _, full = _reports("none", "full")
        _, sub = _reports("none", "degraded")
        host = RoundProgram().host_view()
        pf, tf = host.fold_reports(full)
        ps, ts = host.fold_reports(sub)
        assert ts == float(sum(n for n, _ in sub.values())) < tf
        assert any(not np.array_equal(pf[k], ps[k]) for k in pf)


class TestSimConsumer:
    """FedAvgAPI is a thin builder over ``program.compile_sim``."""

    def _setup(self):
        jnp = pytest.importorskip("jax.numpy")
        from fedml_tpu import models
        from fedml_tpu.algorithms.specs import make_classification_spec
        from fedml_tpu.data import load_synthetic_federated
        spec = make_classification_spec(
            models.LogisticRegression(num_classes=10, apply_sigmoid=False),
            jnp.zeros((1, 60)))
        ds = load_synthetic_federated(client_num=6, n_train=600,
                                      n_test=150, alpha=0.0, beta=0.0,
                                      seed=0)
        return ds, spec

    @staticmethod
    def _args(**kw):
        import types
        base = dict(client_num_per_round=6, comm_round=3, epochs=1,
                    batch_size=16, lr=0.3, client_optimizer="sgd", wd=0.0,
                    frequency_of_the_test=100, ci=0, seed=0)
        base.update(kw)
        return types.SimpleNamespace(**base)

    def test_api_exposes_the_program_it_compiled(self):
        from fedml_tpu.algorithms.fedavg import FedAvgAPI
        ds, spec = self._setup()
        plain = FedAvgAPI(ds, spec, self._args())
        assert not plain.program.codec.enabled
        assert not plain.program.is_async
        comp = FedAvgAPI(ds, spec, self._args(compressor="qsgd:8"))
        assert comp.program.codec.name == "qsgd"
        asyn = FedAvgAPI(ds, spec, self._args(async_agg=1, buffer_k=2))
        assert asyn.program.is_async
        assert asyn.runner.aggregator.policy is asyn.program.aggregation

    @pytest.mark.parametrize("codec", ["none", "topk:0.25"])
    def test_recompiling_the_program_is_bitwise_reproducible(self, codec):
        # compile_sim is a pure function of (program, data): a second
        # API over the same args replays the identical trajectory
        import jax
        from fedml_tpu.algorithms.fedavg import FedAvgAPI
        ds, spec = self._setup()
        a = FedAvgAPI(ds, spec, self._args(compressor=codec))
        b = FedAvgAPI(ds, spec, self._args(compressor=codec))
        assert a.program == b.program
        for _ in range(2):
            a.train_one_round()
            b.train_one_round()
        for x, y in zip(jax.tree.leaves(a.global_state["params"]),
                        jax.tree.leaves(b.global_state["params"])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestDistributedConsumer:
    """The TCP control plane drives the SAME program via host_view."""

    W0 = {"w": np.zeros((2, 3), np.float32), "b": np.ones(3, np.float32)}

    def test_sync_server_rounds_are_host_view_folds(self):
        from fedml_tpu.resilience.integration import (quadratic_trainer,
                                                      run_tcp_fedavg)
        trainer = quadratic_trainer()
        srv = run_tcp_fedavg(4, 2, CohortPolicy(), dict(self.W0),
                             trainer=trainer, join_timeout=60)
        assert srv.failed is None and len(srv.history) == 2
        # the server's live policy IS its program's cohort leg
        assert srv.program.cohort is srv.round_policy
        # re-derive every round bitwise through a fresh host view
        host = RoundProgram(cohort=CohortPolicy()).host_view()
        expected = dict(self.W0)
        for rnd, subset in enumerate(srv.reporting_log):
            reports = {}
            for r in subset:
                p, n = trainer(expected, rnd, r)
                reports[r] = (n, p)
            expected, _ = host.fold_reports(reports)
            for k in expected:
                np.testing.assert_array_equal(expected[k],
                                              srv.history[rnd][k])

    @pytest.mark.parametrize("codec", CODECS)
    def test_sync_wire_cell_completes(self, codec):
        from fedml_tpu.resilience.integration import run_tcp_fedavg
        srv = run_tcp_fedavg(4, 2, CohortPolicy(), dict(self.W0),
                             join_timeout=60, compressor=codec)
        assert srv.failed is None and len(srv.history) == 2
        assert not srv.program.is_async

    @pytest.mark.parametrize("codec", CODECS)
    def test_async_wire_cell_completes_on_the_oracle(self, codec):
        from fedml_tpu.resilience.async_agg import run_async_tcp_fedavg
        pol = AggregationPolicy(buffer_k=10 ** 9, staleness_decay=0.0)
        srv = run_async_tcp_fedavg(4, 2, pol, dict(self.W0),
                                   join_timeout=60, compressor=codec)
        assert srv.failed is None and len(srv.history) == 2
        assert srv.program.is_async
        assert srv.agg.policy is srv.program.aggregation


class TestPrivacyProgramLegs:
    """DPPolicy/RobustPolicy -- the fedpriv-verified legs (ISSUE 20).

    Mechanism pins (clip THEN keyed noise, epsilon accounting, robust
    folds' sorted-traversal determinism), the widened manifest byte pin,
    and the {dp} x {robust} x {codec} conformance matrix: every round a
    dp/robust-armed TCP server folds re-derives bitwise through the
    program's host twin (privatize -> EF-encode -> fold, all keyed).
    """

    W0 = {"w": np.zeros((2, 3), np.float32), "b": np.ones(3, np.float32)}

    def _delta(self, seed=3):
        rng = np.random.default_rng(seed)
        return {"b": rng.standard_normal(5).astype(np.float32) * 4,
                "w": rng.standard_normal((4, 5)).astype(np.float32) * 4}

    def test_dp_clip_then_noise_order_pinned(self):
        from fedml_tpu.program import DPPolicy
        delta = self._delta()
        clip_only = DPPolicy(clip_norm=0.5, noise_multiplier=0.0)
        out = clip_only.privatize(delta, rank=1, round_idx=0)
        norm = np.sqrt(sum(float(np.sum(np.asarray(v, np.float64) ** 2))
                           for v in out.values()))
        assert norm <= 0.5 * (1 + 1e-6)
        for k in delta:  # clip-only == clip (no noise leg at sigma 0)
            np.testing.assert_array_equal(out[k], clip_only.clip(delta)[k])
        dp = DPPolicy(clip_norm=0.5, noise_multiplier=1.0)
        got = dp.privatize(delta, rank=1, round_idx=0)
        want = dp.noise(dp.clip(delta), rank=1, round_idx=0)
        for k in delta:  # THE order: noise over the CLIPPED delta
            np.testing.assert_array_equal(got[k], want[k])

    def test_dp_noise_stream_keyed_and_replayable(self):
        from fedml_tpu.program import DPPolicy
        delta = self._delta()
        dp = DPPolicy(clip_norm=1.0, noise_multiplier=0.7)
        a = dp.privatize(delta, rank=2, round_idx=5, attempt=1)
        b = dp.privatize(delta, rank=2, round_idx=5, attempt=1)
        for k in delta:  # same (rank, round, attempt) -> same bytes
            np.testing.assert_array_equal(a[k], b[k])
        for other in (dict(rank=3, round_idx=5, attempt=1),
                      dict(rank=2, round_idx=6, attempt=1),
                      dict(rank=2, round_idx=5, attempt=2)):
            c = dp.privatize(delta, **other)
            assert any(not np.array_equal(a[k], c[k]) for k in delta)
        # domain separation from the codec stream over the same key
        from fedml_tpu.program.privacy import DP_SEED_SALT
        assert dp.noise_rng(1, 0).integers(0, 2 ** 31) \
            != encode_rng((1, 0)).integers(0, 2 ** 31)
        assert DP_SEED_SALT != 0x5EED

    def test_dp_epsilon_accounting(self):
        import math
        from fedml_tpu.program import DPPolicy
        off = DPPolicy(clip_norm=1.0, noise_multiplier=0.0)
        assert off.epsilon(10) == math.inf
        assert off.record(10)["dp/epsilon"] == -1.0
        dp = DPPolicy(clip_norm=1.0, noise_multiplier=1.2, delta=1e-5)
        assert dp.epsilon(5) == pytest.approx(5 * dp.epsilon(1))
        rec = dp.record(3)
        assert rec["dp/rounds"] == 3
        assert rec["dp/epsilon"] == pytest.approx(dp.epsilon(3))

    def test_robust_folds_deterministic_and_correct(self):
        from fedml_tpu.program import RobustPolicy
        rng = np.random.default_rng(0)
        reports = {r: (10.0 + r,
                       {"w": rng.standard_normal(4).astype(np.float32)})
                   for r in range(5)}
        med = RobustPolicy(mode="coordinate_median")
        params, total = med.fold_reports(reports)
        assert total == float(sum(n for n, _ in reports.values()))
        stacked = np.stack([reports[r][1]["w"] for r in sorted(reports)])
        np.testing.assert_array_equal(
            params["w"], np.median(stacked, axis=0).astype(np.float32))
        # arrival order never reaches the fold: reversed dict == sorted
        rev = dict(sorted(reports.items(), reverse=True))
        params2, _ = med.fold_reports(rev)
        np.testing.assert_array_equal(params["w"], params2["w"])
        with pytest.raises(ValueError):  # base is the norm_clip anchor
            RobustPolicy(mode="norm_clip").fold_reports(reports)
        with pytest.raises(ValueError):  # empty cohort: abandon instead
            med.fold_reports({})
        with pytest.raises(ValueError):
            RobustPolicy(mode="krum")

    def test_manifest_roundtrip_dp_robust_pinned(self):
        import json
        from fedml_tpu.program import DPPolicy, RobustPolicy
        p = RoundProgram(
            dp=DPPolicy(clip_norm=0.5, noise_multiplier=1.1, delta=1e-6),
            robust=RobustPolicy(mode="trimmed_mean", trim_ratio=0.2))
        m = json.dumps(p.manifest(), sort_keys=True)
        assert ('"dp": {"clip_norm": 0.5, "delta": 1e-06, '
                '"noise_multiplier": 1.1}') in m
        assert ('"robust": {"clip_bound": 10.0, "mode": "trimmed_mean", '
                '"trim_ratio": 0.2}') in m
        assert RoundProgram.from_manifest(json.loads(m)) == p
        # the unarmed legs stay explicit nulls (a run with NO defense
        # must say so in its manifest, not omit the keys)
        bare = RoundProgram().manifest()
        assert bare["dp"] is None and bare["robust"] is None

    def test_sim_lowering_gates_the_inexpressible_legs(self):
        from fedml_tpu.program import DPPolicy, RobustPolicy
        from fedml_tpu.program.sim import _apply_privacy_legs
        # clip-only DP and norm_clip lower onto the payload hook
        fn = _apply_privacy_legs(
            RoundProgram(dp=DPPolicy(clip_norm=1.0),
                         robust=RobustPolicy(mode="norm_clip")), None)
        assert callable(fn)
        with pytest.raises(ValueError):  # noise needs a derived stream
            _apply_privacy_legs(
                RoundProgram(dp=DPPolicy(noise_multiplier=1.0)), None)
        with pytest.raises(ValueError):  # order statistics != weighted avg
            _apply_privacy_legs(
                RoundProgram(robust=RobustPolicy(mode="trimmed_mean")),
                None)

    @pytest.mark.parametrize("codec", [None, "qsgd"])
    @pytest.mark.parametrize("robust_mode",
                             [None, "norm_clip", "coordinate_median"])
    @pytest.mark.parametrize("with_dp", [False, True])
    def test_conformance_matrix_distributed_equals_host_twin(
            self, with_dp, robust_mode, codec):
        from fedml_tpu.compression.wire import ef_step
        from fedml_tpu.program import DPPolicy, RobustPolicy
        from fedml_tpu.resilience.integration import (quadratic_trainer,
                                                      run_tcp_fedavg)
        dp = DPPolicy(clip_norm=0.5, noise_multiplier=0.8) if with_dp \
            else None
        robust = None
        if robust_mode == "norm_clip":
            robust = RobustPolicy(mode="norm_clip", clip_bound=0.3)
        elif robust_mode is not None:
            robust = RobustPolicy(mode=robust_mode)
        trainer = quadratic_trainer()
        srv = run_tcp_fedavg(4, 2, CohortPolicy(), dict(self.W0),
                             trainer=trainer, join_timeout=60,
                             compressor=codec, dp=dp, robust=robust)
        assert srv.failed is None and len(srv.history) == 2
        prog = RoundProgram(cohort=CohortPolicy(),
                            codec=codec or "none", dp=dp, robust=robust)
        host = prog.host_view()
        comp = prog.codec.host() if prog.codec.enabled else None
        expected = dict(self.W0)
        residuals = {}
        for rnd, subset in enumerate(srv.reporting_log):
            reports = {}
            base32 = {k: np.asarray(expected[k], np.float32)
                      for k in expected}
            for r in subset:
                p, n = trainer(expected, rnd, r)
                if dp is not None:
                    p = dp.privatize_params(expected, p, r, rnd, 0)
                if comp is not None:
                    delta = {k: np.asarray(p[k], np.float32) - base32[k]
                             for k in base32}
                    enc, _dec, residuals[r] = ef_step(
                        comp, delta, residuals.get(r, {}),
                        encode_rng((r, rnd, 0)))
                    p = CompressedUpdate(enc=enc, spec=prog.codec.spec,
                                         base=expected)
                reports[r] = (n, p)
            if robust is None:
                expected, _ = host.fold_reports(reports)
            else:
                expected, _ = host.fold_reports(reports, base=expected)
            for k in expected:
                np.testing.assert_array_equal(
                    expected[k], srv.history[rnd][k],
                    err_msg=f"dp={with_dp}/{robust_mode}/{codec}/"
                            f"round{rnd}/{k}")
