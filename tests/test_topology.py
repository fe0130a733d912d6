"""Process-tree federation (fedml_tpu/topology): TreeSpec arithmetic,
the orchestrator's spawn/supervise/teardown contract, per-tier
observability, and the cross-process fold pinned BITWISE against
single-tier host replication -- two and three tiers, plain and
compressed upstream, both transports."""

import json

import numpy as np
import pytest

from fedml_tpu.topology import TreeSpec, run_tree
from fedml_tpu.topology.tree import manifest_core

INIT = {"w": np.arange(8, dtype=np.float32).reshape(2, 4),
        "b": np.zeros(4, np.float32)}


def _leaf_round(params, gids):
    """One host-side leaf round: the swarm's quadratic step per GLOBAL
    id, folded the way the edge's host program folds it."""
    from fedml_tpu.net.soak import _quadratic_step
    from fedml_tpu.program.aggregation import aggregate_reports
    reps = {gid: _quadratic_step(params, gid) for gid in gids}
    return aggregate_reports({r: (n, p) for r, (p, n) in reps.items()})


def _edge_gids(spec, path):
    base, stride = spec.leaf_slice(path)
    return [base + i * stride for i in range(spec.leaves_per_edge)]


class TestTreeSpec:
    """The declarative shape: pure arithmetic, no processes."""

    def test_leaf_slice_is_the_nested_round_robin_slice(self):
        from fedml_tpu.net.fanin import round_robin_groups
        spec = TreeSpec(fanout=(2, 3), leaves_per_edge=4)
        ids = list(range(1, spec.n_leaves + 1))
        top = round_robin_groups(ids, 2)
        bottoms = [p for p in spec.edge_paths() if len(p) == spec.tiers]
        assert len(bottoms) == spec.n_bottom_edges == 6
        for e1, e2 in bottoms:
            want = round_robin_groups(top[e1], 3)[e2]
            assert _edge_gids(spec, (e1, e2)) == want

    def test_json_round_trip_and_unknown_keys(self):
        spec = TreeSpec(fanout=(2, 2), leaves_per_edge=5,
                        compressor="qsgd", steering=True,
                        bounds={"deadline_s": [0.5, 60.0]})
        text = spec.to_json()
        # FL135 discipline: the document is sort_keys-stable
        assert text == json.dumps(json.loads(text), sort_keys=True,
                                  indent=2)
        assert TreeSpec.from_json(text) == spec
        with pytest.raises(ValueError, match="unknown keys"):
            TreeSpec.from_json('{"fan_out": [2]}')

    def test_shape_validation(self):
        with pytest.raises(ValueError):
            TreeSpec(fanout=())
        with pytest.raises(ValueError):
            TreeSpec(fanout=(2, 0))
        with pytest.raises(ValueError):
            TreeSpec(leaves_per_edge=0)
        spec = TreeSpec(fanout=(2, 2))
        with pytest.raises(ValueError):
            spec.leaf_slice((0,))  # not a bottom path
        with pytest.raises(ValueError):
            spec.leaf_slice((0, 5))  # outside the fan-out

    def test_pace_bounds_tier_clamped_inside_coordinator(self):
        spec = TreeSpec(bounds={"deadline_s": [1.0, 10.0]},
                        tier_bounds={"deadline_s": [0.1, 100.0]})
        assert spec.pace_bounds(0).deadline_s == (1.0, 10.0)
        # a tier cannot steer outside the coordinator's envelope
        assert spec.pace_bounds(1).deadline_s == (1.0, 10.0)
        tight = TreeSpec(bounds={"deadline_s": [1.0, 10.0]},
                         tier_bounds={"deadline_s": [2.0, 5.0]})
        assert tight.pace_bounds(2).deadline_s == (2.0, 5.0)

    def test_manifest_core_drops_only_steered_knobs(self):
        spec = TreeSpec(fanout=(2,), edge_deadline_s=4.0,
                        compressor="qsgd", flush_deadline_s=9.0)
        prog = spec.round_program()
        steered = prog.replace(
            cohort=prog.cohort.__class__(deadline_s=0.5,
                                         quorum=prog.cohort.quorum))
        assert steered.manifest() != prog.manifest()
        assert manifest_core(steered.manifest()) == \
            manifest_core(prog.manifest())
        # the invariant identity keeps the codec and quorum legs
        core = manifest_core(prog.manifest())
        assert core["codec"] == prog.manifest()["codec"]
        assert core["cohort"]["quorum"] == prog.cohort.quorum
        assert "deadline_s" not in core["cohort"]


class TestTreeFoldBitwise:
    """The headline invariant: a real process tree computes the same
    bits as single-tier host replication of the same RoundProgram."""

    @pytest.mark.parametrize("transport", ["tcp", "eventloop"])
    @pytest.mark.parametrize("codec", [None, "qsgd"])
    def test_two_tier_process_fold_bitwise(self, transport, codec,
                                           tmp_path):
        from fedml_tpu.compression.wire import (CompressedUpdate,
                                                ef_step, encode_rng,
                                                host_compressor)
        from fedml_tpu.program.aggregation import aggregate_reports
        spec = TreeSpec(fanout=(2,), leaves_per_edge=3, total_updates=2,
                        transport=transport, compressor=codec)
        res = run_tree(spec, str(tmp_path), init_params=INIT,
                       join_timeout=180)
        srv = res["server"]
        assert srv.failed is None
        assert srv.agg.version == 2
        assert res["zombies"] == 0 and res["killed"] == 0
        assert srv.counters["stale_base_reports"] == 0

        comp = host_compressor(codec)
        params = {k: np.asarray(v) for k, v in INIT.items()}
        residuals = [None] * spec.fanout[0]
        for rnd in range(2):
            entries = {}
            for e in range(spec.fanout[0]):
                ep, etot = _leaf_round(params, _edge_gids(spec, (e,)))
                if comp is None:
                    entries[e + 1] = (etot, ep)
                    continue
                base32 = {k: np.asarray(v, np.float32)
                          for k, v in params.items()}
                delta = {k: np.asarray(ep[k], np.float32) - base32[k]
                         for k in base32}
                # fault-free runs: the edge's rng ordinal == version
                enc, _dec, residuals[e] = ef_step(
                    comp, delta, residuals[e],
                    encode_rng((e + 1, rnd, rnd)))
                entries[e + 1] = (etot, CompressedUpdate(
                    enc=enc, spec=comp.spec, base=params, base_key=rnd))
            params, _ = aggregate_reports(entries)
            for k in params:
                assert (np.asarray(params[k])
                        == np.asarray(srv.history[rnd][k])).all(), \
                    (transport, codec, rnd, k)

    def test_three_tier_process_fold_bitwise(self, tmp_path):
        # edges-of-edges: fanout (2, 2), compressed only on the
        # coordinator-facing hop; inner tier forwards plain folds
        from fedml_tpu.compression.wire import (CompressedUpdate,
                                                ef_step, encode_rng,
                                                host_compressor)
        from fedml_tpu.net.fanin import round_robin_groups
        from fedml_tpu.program.aggregation import aggregate_reports
        spec = TreeSpec(fanout=(2, 2), leaves_per_edge=2,
                        total_updates=2, compressor="qsgd")
        res = run_tree(spec, str(tmp_path), init_params=INIT,
                       join_timeout=240)
        srv = res["server"]
        assert srv.failed is None
        assert srv.agg.version == 2
        assert res["zombies"] == 0 and res["killed"] == 0
        # one status.json per process: coordinator + 2 + 4 edges
        assert len(res["statuses"]) == 7

        comp = host_compressor("qsgd")
        groups = round_robin_groups(range(1, spec.n_leaves + 1), 2)
        params = {k: np.asarray(v) for k, v in INIT.items()}
        residuals = [None, None]
        for rnd in range(2):
            entries = {}
            for e, g in enumerate(groups):
                subs = round_robin_groups(g, 2)
                sub_entries = {}
                for s, sg in enumerate(subs, start=1):
                    p, tot = _leaf_round(params, sg)
                    sub_entries[s] = (tot, p)
                ep, etot = aggregate_reports(sub_entries)
                base32 = {k: np.asarray(v, np.float32)
                          for k, v in params.items()}
                delta = {k: np.asarray(ep[k], np.float32) - base32[k]
                         for k in base32}
                enc, _dec, residuals[e] = ef_step(
                    comp, delta, residuals[e],
                    encode_rng((e + 1, rnd, rnd)))
                entries[e + 1] = (etot, CompressedUpdate(
                    enc=enc, spec=comp.spec, base=params, base_key=rnd))
            params, _ = aggregate_reports(entries)
            for k in params:
                assert (np.asarray(params[k])
                        == np.asarray(srv.history[rnd][k])).all(), \
                    (rnd, k)


class TestTreeFaults:
    """Edge-process death: renormalization without it, rejoin with
    supervision, and no zombies either way."""

    def test_edge_process_kill_mid_round_renormalizes_exactly(
            self, tmp_path):
        # kill the WHOLE second edge process before its first report:
        # the coordinator sheds it, every flush renormalizes over the
        # exact surviving subset, and the run still completes
        from fedml_tpu.program.aggregation import aggregate_reports
        rows = []
        spec = TreeSpec(fanout=(2,), leaves_per_edge=3, total_updates=2,
                        jitter_s=0.5, flush_deadline_s=15.0)
        res = run_tree(spec, str(tmp_path), init_params=INIT,
                       supervise=False, join_timeout=180,
                       metrics_logger=rows.append,
                       on_spawned=lambda ch: ch["tier1-edge1"].proc
                       .kill())
        srv = res["server"]
        assert srv.failed is None
        assert srv.agg.version == 2
        assert srv.counters["clients_dropped"] == 1
        # the exact renormalized subset: only edge rank 1 contributes
        assert srv.flush_log == [(1,), (1,)]
        assert res["zombies"] == 0
        # bitwise: each update IS the surviving edge's own fold
        params = {k: np.asarray(v) for k, v in INIT.items()}
        for rnd in range(2):
            ep, etot = _leaf_round(params, _edge_gids(spec, (0,)))
            params, _ = aggregate_reports({1: (etot, ep)})
            for k in params:
                assert (np.asarray(params[k])
                        == np.asarray(srv.history[rnd][k])).all(), \
                    (rnd, k)
        flushes = [r for r in rows if "async/flush_clients" in r]
        assert flushes and all(r["async/flush_clients"] == 1
                               for r in flushes)

    def test_supervised_respawn_rejoins_same_slot(self, tmp_path,
                                                  monkeypatch):
        # with supervision ON the dead edge's argv is respawned, the
        # fresh process re-dials the same rank, and the coordinator's
        # rejoin path readmits it -- the run completes with the full
        # tree again. A fresh process needs seconds to come up and the
        # surviving edge none to finish every update alone, so no clock
        # decides this race: the coordinator sets the reports it gets
        # aside until it has seen the rejoin, and handles them then (an
        # edge reports once a sync, so the survivor waits with it)
        from fedml_tpu.resilience.async_agg import \
            AsyncBufferedFedAvgServer as Server
        held = []
        on_report, on_join = Server._on_report, Server._on_peer_join

        def hold_until_rejoined(self, msg):
            if self.counters["clients_rejoined"]:
                on_report(self, msg)
            else:
                held.append(msg)

        def rejoin_then_release(self, msg):
            on_join(self, msg)
            while held and self.counters["clients_rejoined"]:
                on_report(self, held.pop(0))

        monkeypatch.setattr(Server, "_on_report", hold_until_rejoined)
        monkeypatch.setattr(Server, "_on_peer_join", rejoin_then_release)
        # a run of reports in one chunk: one by one, through the above
        monkeypatch.setattr(
            Server, "_on_report_batch",
            lambda self, msgs: [self._on_report(m) for m in msgs])
        spec = TreeSpec(fanout=(2,), leaves_per_edge=2, total_updates=3,
                        flush_deadline_s=8.0)
        res = run_tree(spec, str(tmp_path), init_params=INIT,
                       supervise=True, join_timeout=240,
                       on_spawned=lambda ch: ch["tier1-edge1"].proc
                       .kill())
        srv = res["server"]
        assert srv.failed is None
        assert srv.agg.version == 3
        assert res["respawned"] >= 1
        assert srv.counters["clients_rejoined"] >= 1
        assert not held
        assert res["zombies"] == 0


class TestPerTierObservability:
    """Each process writes its own status.json; the ledger carries one
    reports/sec row per tier member."""

    def test_status_and_ledger_per_tier(self, tmp_path):
        from fedml_tpu.observability.perfmon import ledger_records
        ledger = str(tmp_path / "ledger.jsonl")
        spec = TreeSpec(fanout=(2,), leaves_per_edge=4, total_updates=2,
                        compressor="qsgd", steering=True,
                        edge_deadline_s=10.0,
                        tier_bounds={"deadline_s": [0.25, 120.0]})
        res = run_tree(spec, str(tmp_path), init_params=INIT,
                       join_timeout=180, ledger_path=ledger)
        assert res["server"].failed is None
        assert sorted(res["statuses"]) == [
            "tier0-coordinator.status.json",
            "tier1-edge0.status.json", "tier1-edge1.status.json"]
        coord = res["statuses"]["tier0-coordinator.status.json"]
        assert coord["server"] == "async-buffered"
        cores = []
        for name, st in sorted(res["statuses"].items()):
            assert "program" in st, name
            cores.append(manifest_core(st["program"]))
            if name == "tier0-coordinator.status.json":
                continue
            assert st["server"] == "edge"
            assert st["tier"] == 1
            assert st["rounds_forwarded"] >= 2
            # per-tier steering: this tier's controller, this tier's
            # evidence
            assert st["pace"]["decisions"] >= 1
        # one program: every tier's manifest agrees on the invariant
        # core (steering may move the steered knobs apart)
        assert all(c == cores[0] for c in cores)
        recs = ledger_records(ledger)
        edge_rows = [r for r in recs
                     if r["metric"].startswith("tree-edge reports/sec")]
        soak_rows = [r for r in recs
                     if r["metric"].startswith("tree-soak leaf")]
        assert len(edge_rows) == 2
        assert len(soak_rows) == 1
        assert all(r["value"] > 0 for r in edge_rows + soak_rows)
        assert "tier 1" in edge_rows[0]["metric"]
        assert "qsgd" in edge_rows[0]["metric"]


class TestTreeSoak:
    """The population-scale shape of the headline gate. The 2x500 CI
    smoke lives in ci.sh (bench.py --tree_soak); this is the 10k+
    variant on the slow tier."""

    @pytest.mark.slow
    def test_tree_soak_10k(self, tmp_path):
        """10,000 leaves across a real 2-edge process tree replaying
        the diurnal trace, steered per tier, qsgd-compressed upstream:
        every update completes, nothing is force-killed, no zombies,
        and every tier's status.json parses with a matching program
        core."""
        from fedml_tpu.resilience.faults import DiurnalTrace

        trace = DiurnalTrace.example(dropout=0.0).to_file(
            str(tmp_path / "trace.json"))
        spec = TreeSpec(fanout=(2,), leaves_per_edge=5_000,
                        total_updates=3, compressor="qsgd",
                        trace=trace, steering=True,
                        edge_deadline_s=30.0, flush_deadline_s=60.0,
                        tier_bounds={"deadline_s": [0.25, 300.0]})
        res = run_tree(spec, str(tmp_path), init_params=INIT,
                       join_timeout=600)
        srv = res["server"]
        assert srv.failed is None
        assert srv.agg.version == 3
        assert res["zombies"] == 0 and res["killed"] == 0
        leaf_reports = sum(s.get("reports", 0)
                           for ss in res["swarm_summaries"].values()
                           for s in ss)
        assert leaf_reports == 30_000
        assert len(res["statuses"]) == 3
        cores = [manifest_core(st["program"])
                 for _, st in sorted(res["statuses"].items())]
        assert all(c == cores[0] for c in cores)
