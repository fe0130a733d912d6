"""fedtrace: span tracing, metrics registry, flight recorder.

Unit coverage for each piece plus the two integration contracts from the
PR's acceptance criteria: (1) a TCP chaos run with tracing on yields ONE
Chrome-trace file whose client-rank spans stitch under their round's
server span via propagated trace ids, and one flight-recorder dump for
the killed peer; (2) the same scenario with observability disabled is
bitwise identical to an uninstrumented run.
"""

import json
import math
import os
import re
import sys
import threading

import numpy as np
import pytest

from fedml_tpu.core.message import Message
from fedml_tpu.observability import (CostModel, FlightRecorder,
                                     MetricsRegistry, NOOP_TRACER, PerfMonitor,
                                     StatusWriter, TRACE_KEY, Tracer, enable,
                                     get_cost_model, get_flight_recorder,
                                     get_perf_monitor, get_registry,
                                     get_tracer, set_cost_model,
                                     set_registry)
from fedml_tpu.observability.perfmon import (append_ledger, check_regression,
                                             ledger_records)
from fedml_tpu.utils.metrics import MetricsLogger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # bench.py lives at the repo root
    sys.path.insert(0, REPO)


# -- tracer ----------------------------------------------------------------

class TestTracer:
    def test_nested_spans_parent_on_thread_context(self):
        t = Tracer()
        with t.span("outer") as outer:
            with t.span("inner") as inner:
                assert t.current().span_id == inner.span_id
        spans = {s.name: s for s in t.finished_spans()}
        assert spans["inner"].parent_id == spans["outer"].span_id
        assert spans["inner"].trace_id == spans["outer"].trace_id
        assert spans["outer"].parent_id is None
        assert spans["outer"].t1 >= spans["outer"].t0

    def test_detached_span_cross_thread_end_and_root(self):
        t = Tracer()
        with t.span("ambient"):
            s = t.start_span("round", root=True, round=3)
            assert s.parent_id is None  # root even under an active ctx
        done = threading.Event()

        def closer():
            s.set(outcome="complete").end()
            done.set()

        threading.Thread(target=closer).start()
        assert done.wait(5)
        rec = [x for x in t.finished_spans() if x.name == "round"][0]
        assert rec.attrs == {"round": 3, "outcome": "complete"}

    def test_end_is_idempotent(self):
        t = Tracer()
        s = t.start_span("x")
        s.end()
        first = s.t1
        s.end()
        assert s.t1 == first
        assert len(t.finished_spans()) == 1

    def test_concurrent_end_records_exactly_once(self):
        # the check-and-set runs under the tracer lock: N racing end()
        # calls on one detached span must record one span, not N
        t = Tracer()
        s = t.start_span("round")
        start = threading.Barrier(8)

        def racer():
            start.wait()
            s.end()

        threads = [threading.Thread(target=racer) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert len(t.finished_spans()) == 1

    def test_inject_extract_roundtrip_through_binary_codec(self):
        t = Tracer()
        with t.span("round") as sp:
            m = Message("sync", 0, 1)
            m.add("params", {"w": np.ones(3, np.float32)})
            t.inject(m)
        m2 = Message.from_bytes(m.to_bytes())
        ctx = Tracer.extract(m2)
        assert ctx.trace_id == sp.trace_id
        assert ctx.span_id == sp.span_id
        # receive side: adopt the remote context, spans stitch under it
        with t.remote_context(ctx):
            with t.span("local-train") as child:
                assert child.parent_id == sp.span_id
                assert child.trace_id == sp.trace_id

    def test_chrome_export_balanced_and_jsonl(self, tmp_path):
        t = Tracer()
        with t.span("a", round=1):
            with t.span("b"):
                pass
        chrome = t.export_chrome(str(tmp_path / "trace.json"))
        doc = json.load(open(chrome))
        evs = doc["traceEvents"]
        assert sum(1 for e in evs if e.get("ph") == "B") == \
            sum(1 for e in evs if e.get("ph") == "E") == 2
        b_a = next(e for e in evs if e.get("ph") == "B" and e["name"] == "a")
        assert b_a["args"]["round"] == 1 and "trace_id" in b_a["args"]
        lines = [json.loads(l) for l in
                 open(t.export_jsonl(str(tmp_path / "spans.jsonl")))]
        assert {l["name"] for l in lines} == {"a", "b"}

    def test_retention_bound(self):
        t = Tracer(max_spans=10)
        for i in range(25):
            with t.span(f"s{i}"):
                pass
        assert len(t.finished_spans()) <= 10
        assert t._dropped > 0

    def test_noop_tracer_is_inert_and_leaves_messages_untouched(self):
        t = NOOP_TRACER
        m = Message("sync", 0, 1)
        before = m.to_bytes()
        with t.span("x") as s:
            t.inject(m)  # must not add __trace__: disabled runs put
            assert s.context is None  # bit-identical frames on the wire
        assert TRACE_KEY not in m.get_params()
        assert m.to_bytes() == before
        assert t.extract(m) is None and t.current() is None
        assert t.finished_spans() == [] and t.durations_by_name() == {}


# -- registry --------------------------------------------------------------

PROM_LINE = re.compile(
    r"^(# HELP [a-zA-Z_:][a-zA-Z0-9_:]* .*"
    r"|# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? "
    r"(-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?|[+-]Inf|NaN))$")


class TestRegistry:
    def test_counter_gauge_histogram_with_labels(self):
        r = MetricsRegistry()
        r.inc("wire_bytes_total", 10, transport="tcp", direction="sent")
        r.inc("wire_bytes_total", 5, transport="tcp", direction="sent")
        r.set_gauge("alive_clients", 7)
        r.observe("round_seconds", 0.2)
        r.observe("round_seconds", 3.0)
        assert r.get("wire_bytes_total", transport="tcp",
                     direction="sent") == 15
        assert r.get("alive_clients") == 7
        assert r.get("round_seconds") == (3.2, 2)

    def test_type_conflict_and_bad_name_raise(self):
        r = MetricsRegistry()
        r.inc("x_total")
        with pytest.raises(ValueError):
            r.set_gauge("x_total", 1)
        with pytest.raises(ValueError):
            r.inc("bad name")
        with pytest.raises(ValueError):
            r.inc("neg_total", -1)

    def test_prometheus_exposition_grammar(self):
        r = MetricsRegistry()
        r.inc("wire_bytes_total", 10, help="bytes", transport="tcp")
        r.set_gauge("alive", 3.5, help="who lives")
        r.set_gauge("ratio", float("nan"))  # must render 'NaN', not 'nan'
        r.observe("lat_seconds", 0.007, help="latency")
        text = r.render_prometheus()
        for line in text.strip().split("\n"):
            assert PROM_LINE.match(line), line
        # histogram: cumulative buckets end at +Inf == count
        assert 'lat_seconds_bucket{le="+Inf"} 1' in text
        assert "lat_seconds_count 1" in text

    def test_snapshot_into_emits_only_deltas(self):
        r = MetricsRegistry()
        r.inc("a_total", 3)
        rec = r.snapshot_into({"round": 0})
        assert rec["m/a_total"] == 3
        rec2 = r.snapshot_into({"round": 1})  # unchanged: not re-emitted
        assert "m/a_total" not in rec2
        r.inc("a_total", 2)
        rec3 = r.snapshot_into({"round": 2})
        assert rec3["m/a_total"] == 5

    def test_metrics_logger_snapshots_registry_per_record(self, tmp_path):
        run_dir = str(tmp_path / "run")
        with enable(trace=True, trace_dir=str(tmp_path),
                    compile_events=False):
            logger = MetricsLogger(run_dir=run_dir)
            get_registry().inc("demo_total", 4)
            logger({"round": 0})
            logger.close()
        recs = [json.loads(l)
                for l in open(os.path.join(run_dir, "metrics.jsonl"))]
        assert recs[0]["m/demo_total"] == 4
        prom = open(os.path.join(tmp_path, "metrics.prom")).read()
        assert "demo_total 4" in prom


# -- flight recorder -------------------------------------------------------

class TestFlightRecorder:
    def test_ring_bound_and_dump(self, tmp_path):
        fr = FlightRecorder(out_dir=str(tmp_path), capacity=8)
        for i in range(20):
            fr.record("send", seq_no=i)
        path = fr.dump("peer_lost", extra={"peer": 3})
        events = [json.loads(l) for l in open(path)]
        # bounded: only the 8 newest survive, plus the dump_info trailer
        assert len(events) == 9
        assert events[0]["seq_no"] == 12 and events[-2]["seq_no"] == 19
        assert events[-1]["kind"] == "dump_info"
        assert os.path.basename(path) == "flightrec_peer_lost.jsonl"

    def test_repeat_reasons_suffix_and_max_dumps(self, tmp_path):
        fr = FlightRecorder(out_dir=str(tmp_path), max_dumps=3)
        fr.record("x")
        p1 = fr.dump("crash")
        p2 = fr.dump("crash")
        p3 = fr.dump("peer_lost")
        assert os.path.basename(p1) == "flightrec_crash.jsonl"
        assert os.path.basename(p2) == "flightrec_crash_2.jsonl"
        assert os.path.basename(p3) == "flightrec_peer_lost.jsonl"
        assert fr.dump("crash") is None  # capped

    def test_enable_scope_installs_and_restores_globals(self, tmp_path):
        assert get_flight_recorder() is None
        assert get_registry() is None
        default = get_tracer()  # the recorder level, not the no-op
        assert isinstance(default, Tracer) and not default.enabled
        with enable(trace=True, trace_dir=str(tmp_path), flightrec=True,
                    compile_events=False) as obs:
            assert get_flight_recorder() is obs.recorder
            assert get_registry() is obs.registry
            assert get_tracer() is obs.tracer
        assert get_flight_recorder() is None
        assert get_registry() is None
        assert get_tracer() is default
        assert os.path.exists(obs.chrome_path)
        assert os.path.exists(obs.prom_path)


# -- integration: the acceptance scenario ---------------------------------

def _chaos(world=4, rounds=3, fault=True, deadline=1.0, **kw):
    from fedml_tpu.resilience import (FaultPlan, FaultRule, RoundPolicy,
                                      run_tcp_fedavg)

    w0 = {"w": np.zeros((4, 4), np.float32), "b": np.ones(4, np.float32)}
    plan = None
    if fault:
        plan = FaultPlan(seed=7, rules=(
            FaultRule("kill", rank=3, msg_type="res_report", nth=2),
            FaultRule("stall", rank=2, msg_type="res_report", nth=1,
                      delay_s=4.0)))
    return run_tcp_fedavg(world, rounds,
                          RoundPolicy(deadline_s=deadline, quorum=0.3), w0,
                          fault_plan=plan, join_timeout=90, **kw)


class TestCrossRankTracing:
    def test_chaos_run_stitches_spans_and_dumps_flight_recorder(
            self, tmp_path):
        d = str(tmp_path)
        with enable(trace=True, trace_dir=d, flightrec=True,
                    flightrec_dir=d, compile_events=False) as obs:
            srv = _chaos()
            spans = obs.tracer.finished_spans()
        assert srv.failed is None and len(srv.history) == 3

        rounds = {s.span_id: s for s in spans if s.name == "round"}
        assert len(rounds) == 3
        assert all(s.parent_id is None for s in rounds.values())
        assert all(s.attrs.get("outcome") in ("complete", "degraded")
                   for s in rounds.values())
        # every client local-train span hangs under a server round span
        # with the SAME trace id -- the Dapper stitch across ranks
        lts = [s for s in spans if s.name == "local-train"]
        assert lts, "client spans missing"
        for s in lts:
            assert s.parent_id in rounds, s.as_dict()
            assert s.trace_id == rounds[s.parent_id].trace_id
        # report-recv hangs under the client's report span
        by_id = {s.span_id: s for s in spans}
        recvs = [s for s in spans if s.name == "report-recv"]
        assert recvs
        for s in recvs:
            assert by_id[s.parent_id].name == "report"

        # exactly one flight-recorder dump TRIGGERED by the killed peer,
        # identified by the dump_info trailer -- the ring's retained
        # events (incl. the kill) also appear in any later dump, e.g.
        # when the stalled client's wedged report outlives the run and
        # observes the server's teardown as a lost peer.
        kill_dumps = []
        for p in obs.recorder.dumps:
            events = [json.loads(l) for l in open(p)]
            info = [e for e in events if e["kind"] == "dump_info"]
            if info and info[-1].get("peer") == 3:
                kill_dumps.append(events)
        assert len(kill_dumps) == 1
        events = kill_dumps[0]
        assert any(e["kind"] == "peer_lost" and e.get("peer") == 3
                   for e in events)
        assert any(e["kind"] == "send" for e in events)
        assert any(e["kind"] == "round_decision" for e in events)

        # the exported Chrome trace parses with balanced B/E events
        doc = json.load(open(obs.chrome_path))
        evs = doc["traceEvents"]
        assert sum(1 for e in evs if e.get("ph") == "B") == \
            sum(1 for e in evs if e.get("ph") == "E") > 0
        # registry absorbed the transports' wire counters
        prom = open(obs.prom_path).read()
        assert re.search(
            r'comm_bytes_total\{direction="sent",transport="tcp"\} \d+',
            prom)

    def test_disabled_path_is_bitwise_identical(self, tmp_path):
        # no faults, generous deadline: a deterministic scenario. The
        # observability-enabled run must not perturb the protocol's
        # arithmetic; the disabled run must equal a plain run bitwise.
        # The enabled side arms EVERYTHING incl. the PR-10 pieces
        # (perfmon histograms/status.json + cost model) -- extending
        # PR 7's noop contract to the new instrumentation points.
        srv_plain = _chaos(fault=False, deadline=30.0)
        with enable(trace=True, flightrec=True, compile_events=False,
                    perfmon=True, status_path=str(tmp_path / "status.json"),
                    cost_model=True):
            srv_obs = _chaos(fault=False, deadline=30.0)
        srv_off = _chaos(fault=False, deadline=30.0)
        assert srv_plain.reporting_log == srv_obs.reporting_log \
            == srv_off.reporting_log
        for a, b, c in zip(srv_plain.history, srv_obs.history,
                           srv_off.history):
            for k in a:
                assert (a[k] == b[k]).all(), k
                assert (a[k] == c[k]).all(), k

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_crash_hook_dumps_on_thread_exception(self, tmp_path):
        with enable(flightrec=True, flightrec_dir=str(tmp_path),
                    compile_events=False) as obs:
            obs.recorder.record("send", type="sync")

            def boom():
                raise RuntimeError("injected worker crash")

            th = threading.Thread(target=boom)
            th.start()
            th.join()
        crash = [p for p in obs.recorder.dumps if "crash" in p]
        assert len(crash) == 1
        events = [json.loads(l) for l in open(crash[0])]
        assert any(e["kind"] == "crash"
                   and "injected worker crash" in e.get("error", "")
                   for e in events)


# -- XLA cost model (PR 10) -------------------------------------------------

class TestCostModel:
    def test_program_cost_counts_matmul_flops_exactly(self):
        import jax
        import jax.numpy as jnp

        from fedml_tpu.observability.costmodel import program_cost

        f = jax.jit(lambda a, b: a @ b)
        pc = program_cost(f, jax.ShapeDtypeStruct((8, 64), jnp.float32),
                          jax.ShapeDtypeStruct((64, 32), jnp.float32))
        assert pc is not None and pc.source == "xla"
        assert pc.flops == 2 * 8 * 64 * 32  # one MAC = 2 flops
        assert pc.bytes_accessed > 0

    def test_train_step_cost_cross_checks_bench_analytic_constant(self):
        # THE rot guard for bench.py's hand-derived TRAIN_FLOPS_PER_SAMPLE:
        # the XLA cost model of the real smoke-shape ResNet-56 train step
        # (bf16 model, recipe augmentation -- exactly what bench --smoke
        # compiles) must agree with the analytic constant within the
        # documented tolerance (FLOPS_XCHECK_TOL, docs/PERFORMANCE.md
        # round 7). If either side drifts, this fails loudly.
        import jax
        import jax.numpy as jnp

        import bench
        from fedml_tpu import models
        from fedml_tpu.algorithms.specs import make_classification_spec
        from fedml_tpu.data.augment import make_cifar_augment
        from fedml_tpu.observability.costmodel import train_step_cost
        from fedml_tpu.parallel.engine import ClientUpdateConfig

        image, bs = 16, 8  # the bench --smoke shape (compiles in ~15 s)
        model = models.resnet56(class_num=10, dtype=jnp.bfloat16)
        spec = make_classification_spec(
            model, jnp.zeros((1, image, image, 3)),
            augment_fn=make_cifar_augment(pad=2, cutout_length=4))
        cfg = ClientUpdateConfig(optimizer="sgd", lr=0.001,
                                 weight_decay=0.001)
        batch = {"x": jax.ShapeDtypeStruct((bs, image, image, 3),
                                           jnp.float32),
                 "y": jax.ShapeDtypeStruct((bs,), jnp.int32),
                 "mask": jax.ShapeDtypeStruct((bs,), jnp.float32)}
        pc = train_step_cost(spec, cfg, batch)
        assert pc is not None, "cost analysis unavailable on this backend"
        per_sample = pc.flops / bs
        analytic = bench.TRAIN_FLOPS_PER_SAMPLE * (image / 32) ** 2
        ratio = per_sample / analytic
        assert abs(ratio - 1.0) <= bench.FLOPS_XCHECK_TOL, (
            f"cost-model/analytic ratio {ratio:.3f} outside "
            f"+-{bench.FLOPS_XCHECK_TOL}: the analytic constant (or the "
            "model) drifted -- update bench.py's derivation and "
            "docs/PERFORMANCE.md round 7")

    def test_train_step_cost_unknown_optimizer_returns_none(self):
        import jax
        import jax.numpy as jnp

        from fedml_tpu import models
        from fedml_tpu.algorithms.specs import make_classification_spec
        from fedml_tpu.observability.costmodel import train_step_cost
        from fedml_tpu.parallel.engine import ClientUpdateConfig

        spec = make_classification_spec(
            models.LogisticRegression(num_classes=2),
            jnp.zeros((1, 4)))
        pc = train_step_cost(
            spec, ClientUpdateConfig(optimizer="nope"),
            {"x": jax.ShapeDtypeStruct((2, 4), jnp.float32),
             "y": jax.ShapeDtypeStruct((2,), jnp.int32),
             "mask": jax.ShapeDtypeStruct((2,), jnp.float32)})
        assert pc is None  # degrade to the analytic fallback, never raise

    def test_bucket_runner_attributes_per_bucket_flops(self):
        # cost model armed: per-bucket FLOPs + FLOP-weighted waste ride
        # the round record; identical run with it off carries no flops
        # fields AND produces bitwise-identical params (disabled-path
        # contract at the engine level)
        import types

        import jax
        import jax.numpy as jnp

        import bench
        from fedml_tpu import models
        from fedml_tpu.algorithms.fedavg import FedAvgAPI
        from fedml_tpu.algorithms.specs import make_classification_spec

        C = 300
        dataset = bench._ragged_lr_clients(C)
        spec = make_classification_spec(
            models.LogisticRegression(num_classes=4, apply_sigmoid=False),
            jnp.zeros((1, 16)))
        run_args = types.SimpleNamespace(
            client_num_in_total=C, client_num_per_round=C,
            comm_round=10 ** 9, epochs=1, batch_size=8, lr=0.05, wd=0.0,
            client_optimizer="sgd", frequency_of_the_test=10 ** 9, seed=0,
            client_chunk=64, bucket_edges="geometric", device_resident="0")

        api_off = FedAvgAPI(dataset, spec, run_args)
        m_off = api_off.train_one_round()
        assert "bucket/executed_flops" not in m_off
        p_off = jax.tree.map(np.asarray, api_off.global_state)

        cm = CostModel()
        prev = set_cost_model(cm)
        try:
            api_on = FedAvgAPI(dataset, spec, run_args)
            m_on = api_on.train_one_round()
        finally:
            set_cost_model(prev)
        assert get_cost_model() is prev
        p_on = jax.tree.map(np.asarray, api_on.global_state)
        for a, b in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_on)):
            assert (a == b).all(), "cost model perturbed the round"

        assert m_on["bucket/executed_flops"] > m_on["bucket/true_flops"] > 0
        assert 0.0 <= m_on["bucket/flops_waste_frac"] < 1.0
        info = api_on._last_info["bucket"]
        assert info["flops_source"] == "xla"
        used = [b for b in info["per_bucket"] if not b["skipped"]]
        assert used and all("flops_per_step" in b and
                            b["executed_flops"] >= b["true_flops"]
                            for b in used)
        # the per-bucket rows sum to the round totals
        assert math.isclose(sum(b["executed_flops"] for b in used),
                            info["executed_flops"], rel_tol=1e-9)
        # the AOT probes never polluted the dispatch cache: compiled
        # programs still == bucket shapes (the ci.sh massive-gate anchor)
        assert api_on.runner.compiled_shapes() == m_on["bucket/shapes"]
        # catalog rode the armed CostModel
        rec = cm.record()
        assert rec["cost/programs"] == len(used)


# -- perf monitor (PR 10) ---------------------------------------------------

class TestPerfMonitor:
    def test_round_histograms_and_rolling_rph_gauge(self):
        reg = MetricsRegistry()
        prev = set_registry(reg)
        try:
            mon = PerfMonitor(window=8)
            for _ in range(3):
                mon.observe_round(0.5, steps=100)
            mon.observe_report_latency(0.2)
            mon.observe_fold(staleness=3, depth=7)
        finally:
            set_registry(prev)
        assert reg.get("fed_round_seconds") == (1.5, 3)
        s, n = reg.get("fed_step_seconds")
        assert n == 3 and abs(s - 3 * 0.005) < 1e-9
        assert reg.get("fed_report_latency_seconds") == (0.2, 1)
        assert reg.get("fed_staleness_levels") == (3.0, 1)
        assert reg.get("fed_buffer_depth_levels") == (7.0, 1)
        assert reg.get("fed_rounds_per_hour") > 0
        rec = mon.record()
        assert rec["perf/rounds_observed"] == 3
        assert rec["perf/reports_observed"] == 1

    def test_monitor_without_registry_is_inert(self):
        # perfmon armed but no registry (programmatic use): observations
        # must not crash and the rolling record still works
        assert get_registry() is None
        mon = PerfMonitor()
        mon.observe_round(0.1)
        mon.observe_round(0.1)
        mon.observe_fold(0, 1)
        assert mon.record()["perf/rounds_observed"] == 2

    def test_status_writer_throttle_force_and_merge(self, tmp_path):
        p = str(tmp_path / "status.json")
        w = StatusWriter(p, min_interval_s=3600)
        assert w.update(round=1, outcome="running") == p  # first: written
        assert w.update(round=2) is None  # high-rate update: throttled...
        assert json.load(open(p))["round"] == 1
        assert w.update(force=True, round=3) == p  # ...force writes
        doc = json.load(open(p))
        # fields MERGE across updates (incl. the throttled one's round=2
        # -> round=3); the write is atomic (always a full JSON document)
        assert doc["round"] == 3 and doc["outcome"] == "running"
        assert doc["status_version"] == 1 and "updated_at" in doc
        assert w.writes == 2

    def test_status_writer_bad_path_never_raises(self):
        w = StatusWriter("/proc/definitely/not/writable/status.json",
                         min_interval_s=0)
        assert w.update(force=True, round=1) is None  # logged, not fatal

    def test_xprof_fires_only_on_its_round_and_once(self, tmp_path):
        calls = []
        mon = PerfMonitor(xprof_dir=str(tmp_path), xprof_round=2)
        import jax
        orig_start = jax.profiler.start_trace
        orig_stop = jax.profiler.stop_trace
        jax.profiler.start_trace = lambda d: calls.append(("start", d))
        jax.profiler.stop_trace = lambda: calls.append(("stop",))
        try:
            with mon.xprof(0):
                pass
            assert calls == []  # wrong round: nullcontext
            with mon.xprof(2):
                pass
            assert [c[0] for c in calls] == ["start", "stop"]
            with mon.xprof(2):
                pass
            assert len(calls) == 2  # one-shot
        finally:
            jax.profiler.start_trace = orig_start
            jax.profiler.stop_trace = orig_stop

    def test_xprof_noops_cleanly_when_profiler_unavailable(self, tmp_path):
        mon = PerfMonitor(xprof_dir=str(tmp_path), xprof_round=0)
        import jax
        orig = jax.profiler.start_trace

        def boom(d):
            raise RuntimeError("profiler busy / unavailable")

        jax.profiler.start_trace = boom
        try:
            with mon.xprof(0):
                ran = True  # the round body must still run
        finally:
            jax.profiler.start_trace = orig
        assert ran and mon._xprof_done

    def test_async_fold_feeds_histograms_and_flush_status(self, tmp_path):
        # BufferedAggregator.fold with the monitor armed: staleness/depth
        # distributions land in the registry next to PR 9's point gauges
        from fedml_tpu.resilience.async_agg import (AsyncAggPolicy,
                                                    BufferedAggregator)

        w = {"w": np.ones(2, np.float32)}
        with enable(perfmon=True, flightrec_dir=str(tmp_path),
                    compile_events=False) as obs:
            agg = BufferedAggregator(AsyncAggPolicy(buffer_k=2,
                                                    staleness_decay=0.0))
            agg.fold(1, 10.0, w, staleness=0)
            agg.fold(2, 10.0, w, staleness=5)
            agg.flush("buffer_k")
            reg = obs.registry
            assert reg.get("fed_staleness_levels") == (5.0, 2)
            _, n = reg.get("fed_buffer_depth_levels")
            assert n == 2
        assert get_perf_monitor() is None  # scope restored

    def test_tcp_run_writes_status_with_final_outcome(self, tmp_path):
        from fedml_tpu.resilience import RoundPolicy, run_tcp_fedavg

        w0 = {"w": np.zeros((2, 2), np.float32)}
        with enable(perfmon=True, flightrec_dir=str(tmp_path),
                    compile_events=False) as obs:
            srv = run_tcp_fedavg(3, 2,
                                 RoundPolicy(deadline_s=30.0, quorum=0.3),
                                 w0, join_timeout=60)
            reg = obs.registry
            _, nlat = reg.get("fed_report_latency_seconds")
        assert srv.failed is None and len(srv.history) == 2
        assert nlat == 4  # 2 clients x 2 rounds: the straggler-tail feed
        doc = json.load(open(obs.status_path))
        assert doc["last_outcome"] == "complete"
        assert doc["round"] == 2 and doc["alive_ranks"] == [1, 2]
        assert doc["outcome_counts"]["complete"] == 2
        assert doc["final"] is True  # the scope's forced exit write


# -- histogram rendering (PR 10 satellite) ----------------------------------

class TestHistogramRendering:
    def _grammar_check(self, text):
        for line in text.strip().split("\n"):
            assert PROM_LINE.match(line), line

    def test_bucket_sum_count_lines_and_cumulative_monotone(self):
        r = MetricsRegistry()
        for v in (0.003, 0.02, 0.02, 9.0, 100.0):
            r.observe("lat_seconds", v, buckets=(0.01, 0.05, 10.0),
                      help="latency", route="a")
        text = r.render_prometheus()
        self._grammar_check(text)
        assert 'lat_seconds_bucket{route="a",le="0.01"} 1' in text
        assert 'lat_seconds_bucket{route="a",le="0.05"} 3' in text
        assert 'lat_seconds_bucket{route="a",le="10.0"} 4' in text
        assert 'lat_seconds_bucket{route="a",le="+Inf"} 5' in text
        assert 'lat_seconds_count{route="a"} 5' in text
        # cumulative bucket counts never decrease
        counts = [int(m.group(1)) for m in re.finditer(
            r'lat_seconds_bucket\{[^}]*\} (\d+)', text)]
        assert counts == sorted(counts)

    def test_empty_histogram_renders_zero_series(self):
        # declare_histogram pre-registers a series with no observations:
        # all-zero buckets, sum 0.0, count 0 -- and still grammar-valid
        r = MetricsRegistry()
        r.declare_histogram("fed_round_seconds", buckets=(1.0, 5.0),
                            help="pre-declared")
        text = r.render_prometheus()
        self._grammar_check(text)
        assert 'fed_round_seconds_bucket{le="+Inf"} 0' in text
        assert "fed_round_seconds_count 0" in text
        assert r.get("fed_round_seconds") == (0.0, 0)
        # idempotent: re-declaring never resets an observed series
        r.observe("fed_round_seconds", 0.5, buckets=(1.0, 5.0))
        r.declare_histogram("fed_round_seconds", buckets=(1.0, 5.0))
        assert r.get("fed_round_seconds") == (0.5, 1)

    def test_nan_observation_stays_grammar_valid(self):
        # a NaN observation falls through every finite bucket into +Inf
        # (NaN <= le is False) and poisons the sum -- which must render
        # as Prometheus's 'NaN', never repr's 'nan'
        r = MetricsRegistry()
        r.observe("odd_seconds", float("nan"), buckets=(1.0,))
        r.observe("odd_seconds", 0.5, buckets=(1.0,))
        text = r.render_prometheus()
        self._grammar_check(text)
        assert 'odd_seconds_bucket{le="1.0"} 1' in text
        assert 'odd_seconds_bucket{le="+Inf"} 2' in text
        assert "odd_seconds_sum NaN" in text
        assert "odd_seconds_count 2" in text


# -- perf-regression ledger (PR 10) -----------------------------------------

class TestLedger:
    REC = {"metric": "m rounds/hour", "value": 100.0, "unit": "rounds/hour"}

    def test_append_stamps_and_roundtrips(self, tmp_path):
        p = str(tmp_path / "ledger.jsonl")
        append_ledger(self.REC, p)
        append_ledger({**self.REC, "value": 101.0}, p)
        recs = ledger_records(p)
        assert [r["value"] for r in recs] == [100.0, 101.0]
        assert all("ledger_ts" in r for r in recs)

    def test_fresh_ledger_passes_and_regression_fails(self, tmp_path):
        p = str(tmp_path / "ledger.jsonl")
        ok, d = check_regression(p)
        assert ok and d["fresh_ledger"]
        append_ledger(self.REC, p)
        ok, d = check_regression(p)
        assert ok and d["fresh_ledger"]  # one record: no baseline yet
        append_ledger({**self.REC, "value": 97.0}, p)
        ok, d = check_regression(p)  # -3%: inside the 15% noise band
        assert ok and not d["fresh_ledger"]
        append_ledger({**self.REC, "value": 50.0}, p)  # the 2x slowdown
        ok, d = check_regression(p)
        assert not ok
        assert d["latest_value"] == 50.0
        assert d["baseline_median"] == pytest.approx(98.5)

    def test_other_metrics_never_judge_each_other(self, tmp_path):
        # a smoke record must not drag a flagship baseline (and vice
        # versa): baselines group by the exact metric string
        p = str(tmp_path / "ledger.jsonl")
        append_ledger({"metric": "flagship", "value": 100.0}, p)
        append_ledger({"metric": "smoke [SMOKE]", "value": 5.0}, p)
        ok, d = check_regression(p)
        assert ok and d["fresh_ledger"]  # no same-metric predecessor

    def test_unparseable_lines_are_skipped_not_fatal(self, tmp_path):
        p = str(tmp_path / "ledger.jsonl")
        append_ledger(self.REC, p)
        with open(p, "a") as f:
            f.write("not json\n")
        append_ledger({**self.REC, "value": 40.0}, p)
        ok, d = check_regression(p)
        assert not ok and d["records"] == 2

    def test_bench_check_regress_cli_both_ways(self, tmp_path):
        # the exact ci.sh gate, as subprocesses: green on a fresh ledger,
        # red after a fixture record with an injected 2x slowdown
        import subprocess

        p = str(tmp_path / "ledger.jsonl")
        append_ledger({"metric": "clients/sec", "value": 50000.0}, p)
        r = subprocess.run(
            [sys.executable, "bench.py", "--check-regress", "--ledger", p],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 0, (r.stdout, r.stderr)
        assert json.loads(r.stdout)["pass"] is True
        append_ledger({"metric": "clients/sec", "value": 25000.0}, p)
        r = subprocess.run(
            [sys.executable, "bench.py", "--check-regress", "--ledger", p],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 1, (r.stdout, r.stderr)
        assert json.loads(r.stdout)["pass"] is False


class TestBenchFailsLoudly:
    """bench.py measures the configuration asked for on the device it
    finds, or exits non-zero: no assumed peak, no swallowed round, no
    fallback to another platform."""

    def test_peak_flops_raises_on_unknown_device_kind(self):
        import types

        import bench

        v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
        assert bench.peak_flops(v5e) == 197.0e12
        for kind in ("cpu", "TPU v9 mystery", ""):
            with pytest.raises(ValueError, match="no peak FLOP/s"):
                bench.peak_flops(types.SimpleNamespace(device_kind=kind))

    def test_cpu_smoke_record_carries_no_mfu(self):
        import bench

        assert bench.mfu_fields(1e12, None) == {
            "mfu": None, "assumed_peak_tflops": None}
        assert bench.mfu_fields(98.5e12, 197.0e12) == {
            "mfu": 0.5, "assumed_peak_tflops": 197.0}

    def _main(self, monkeypatch, argv, measure=None):
        import bench

        # the real watchdog would os._exit the test process later
        monkeypatch.setattr(
            bench, "arm_watchdog",
            lambda *a, **kw: threading.Timer(0, lambda: None))
        if measure is not None:
            monkeypatch.setattr(bench, "measure", measure)
        monkeypatch.setattr(sys, "argv", ["bench.py"] + argv)
        bench.main()

    def test_no_accelerator_without_platform_cpu_fails(self, monkeypatch):
        # tier-1 runs on the host platform: without --platform cpu the
        # flagship must refuse it before measuring anything
        def never(*a, **kw):
            raise AssertionError("measured on a device with no peak")

        with pytest.raises(ValueError, match="no peak FLOP/s"):
            self._main(monkeypatch, ["--smoke"], measure=never)

    def test_failed_round_fails_the_run(self, monkeypatch):
        import types

        import bench

        class Api:
            rounds = 0
            spec = cfg = None
            _last_metrics = {"count": np.ones(1)}

            def train_one_round(self):
                Api.rounds += 1
                if Api.rounds == 3:  # warmup + 1 measured round pass
                    raise RuntimeError("device fell over")
                return {"Train/Acc": 0.0}

        monkeypatch.setattr(bench, "build_api", lambda *a: Api())
        args = types.SimpleNamespace(smoke=False, rounds=3, warmup=0,
                                     profile_dir=None, batch_size=8)
        with pytest.raises(RuntimeError, match="device fell over"):
            bench.measure(args, 1, 2, 3)
        # ... and main() lets it end the process (uncaught -> exit != 0)
        Api.rounds = 2
        with pytest.raises(RuntimeError, match="device fell over"):
            self._main(monkeypatch, ["--smoke", "--platform", "cpu"],
                       measure=lambda *a: Api().train_one_round())


def test_compile_watch_scopes_leave_no_listener_behind():
    # jax.monitoring's listener lists are process-global: every
    # watch_compiles()/audit() scope must take its own listeners back out
    import jax
    import jax.numpy as jnp
    from jax._src import monitoring as mon

    from fedml_tpu.analysis.runtime import audit
    from fedml_tpu.observability.jaxmon import watch_compiles

    before = (list(mon.get_event_duration_listeners()),
              list(mon.get_event_listeners()))
    for _ in range(3):
        with watch_compiles() as w:
            jax.jit(lambda x: x + 1)(jnp.ones(3)).block_until_ready()
        assert w.total_traces >= 1
        with audit():
            pass
    assert (list(mon.get_event_duration_listeners()),
            list(mon.get_event_listeners())) == before

