#!/usr/bin/env bash
# Fast CI tier -- the runnable analog of the reference's CI scripts
# (CI-script-fedavg.sh:31-58: a short federated run plus the
# federated==centralized equivalence asserts), targeted at ~2 minutes on
# a CPU host (attention micro-correctness included; heavy parallel-step
# tests are slow-marked). The full suite (including the slow-marked algorithm-family
# integration tests) is `python -m pytest tests/ -q`.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== fedlint gate (JAX/FL static analysis + fedcheck protocol/"
echo "   concurrency passes incl. the v2 interprocedural generation"
echo "   FL126-FL128, over the package AND the bench/driver scripts;"
echo "   fails on findings not in fedlint_baseline.json, on ANY"
echo "   remaining baseline debt, on a non-idempotent --fix, and on a"
echo "   blown wall-time budget) =="
mkdir -p bench_results
LINT_SCOPE="fedml_tpu/ bench.py __graft_entry__.py scripts/"
# the interprocedural passes (cross-class callgraph, FSM sequencing,
# payload schemas) must not silently regress lint latency as the tree
# grows: the whole project-wide run is budgeted. The committed tree
# lints in ~5 s on the CI-class host; 60 s is the alarm threshold, not
# a target.
FEDLINT_BUDGET_S=60
# one lint run, two reports: JSON (the gate's input) on stdout, SARIF
# 2.1.0 (PR annotation upload) via --sarif-out
if ! python -m fedml_tpu.analysis $LINT_SCOPE --format json \
        --max-seconds "$FEDLINT_BUDGET_S" \
        --sarif-out bench_results/fedlint_report.sarif \
        > bench_results/fedlint_report.json; then
    # fail LOUD: echo the findings into the CI log, don't make the
    # maintainer reproduce locally to learn which rule fired
    cat bench_results/fedlint_report.json
    echo "fedlint gate: new findings or blown budget (see above)"
    exit 1
fi
python - <<'EOF'
import json
rep = json.load(open("bench_results/fedlint_report.json"))
assert rep["summary"]["new"] == 0, ("new fedlint findings", rep["summary"])
# the FL104 donation debt was burned to zero; the gate now also holds the
# baseline itself at zero -- re-accepting debt means re-arguing for it in
# a baseline diff, not silently growing the register
assert rep["summary"]["baselined"] == 0, (
    "baseline debt must stay at zero", rep["summary"])
bl = json.load(open("fedml_tpu/analysis/fedlint_baseline.json"))
assert bl["findings"] == [], "fedlint_baseline.json must stay empty"
sarif = json.load(open("bench_results/fedlint_report.sarif"))
assert sarif["version"] == "2.1.0" and sarif["runs"][0]["results"] == []
rules = {r["id"]: r for r in sarif["runs"][0]["tool"]["driver"]["rules"]}
for code in ("FL126", "FL127", "FL128"):
    tags = rules[code]["properties"]["tags"]
    assert tags and tags[0].startswith("fedcheck-"), (code, tags)
# the determinism pass (FL131-FL135) is gated at zero like every other
# pass, and its SARIF rules must carry the fedcheck-determinism tag so
# PR-annotation UIs group fold/cohort/control-law findings together
for code in ("FL131", "FL132", "FL133", "FL134", "FL135"):
    tags = rules[code]["properties"]["tags"]
    assert tags == ["fedcheck-determinism"], (code, tags)
assert rules["FL136"]["properties"]["tags"][0] == "fedcheck-concurrency", \
    rules["FL136"]["properties"]["tags"]
# the model-checking pass (FL140-FL143) is gated at zero on the tree --
# the bounded exploration of every discovered server x clients (and
# two-tier) composition finds no deadlock, hung fair path, inert
# delivery, or stranded rejoin -- and its rules carry the
# fedcheck-model tag
for code in ("FL140", "FL141", "FL142", "FL143"):
    tags = rules[code]["properties"]["tags"]
    assert tags == ["fedcheck-model"], (code, tags)
# the privacy information-flow pass (FL150-FL153) is gated at zero on
# the tree -- no raw-update telemetry leak, no reversed clip/noise
# ordering or underived noise rng, no mask/codec commutation, no
# declared-but-bypassed DP leg -- and its rules carry the
# fedcheck-privacy tag
for code in ("FL150", "FL151", "FL152", "FL153"):
    tags = rules[code]["properties"]["tags"]
    assert tags == ["fedcheck-privacy"], (code, tags)
print("fedlint gate: 0 findings (incl. FL126-FL128, the determinism "
      "pass FL131-FL135, the fedmc model-checking pass FL140-FL143, "
      "and the fedpriv privacy pass FL150-FL153 at zero), baseline "
      "empty, sarif rules carry fedcheck metadata")
EOF
echo "-- fedmc mutation fixture (deleting the MSG_C2S_REPORT"
echo "   registration must yield exactly one FL141 naming the hung"
echo "   round; the unmutated module must verify clean -- gated both"
echo "   ways, same wall-time budget family as the lint gate) --"
python - <<'EOF'
from fedml_tpu.analysis.linter import lint_source
rel = "fedml_tpu/resilience/integration.py"
src = open(rel, encoding="utf-8").read()
needle = ("        self.register_message_receive_handler(MSG_C2S_REPORT,\n"
          "                                              self._on_report)\n")
assert needle in src, "integration.py registration shape changed"
assert lint_source(src, path=rel, select={"FL141"}) == [], \
    "unmutated integration.py must verify clean"
found = lint_source(src.replace(needle, ""), path=rel, select={"FL141"})
assert [f.code for f in found] == ["FL141"], found
assert "round 0" in found[0].message and "res_report" in found[0].message, \
    found[0].message
print("fedmc mutation fixture: FL141 fires exactly once on the deleted "
      "registration (trace names the hung round), clean tree verifies "
      "clean")
EOF
echo "-- fedpriv mutation fixtures (un-fixing each privacy invariant in"
echo "   the real tree must yield exactly one finding of exactly its"
echo "   rule; the unmutated modules must verify clean -- all four rules"
echo "   gated both ways) --"
python - <<'EOF'
from fedml_tpu.analysis.linter import lint_source

def both_ways(rel, needle, mutation, code):
    src = open(rel, encoding="utf-8").read()
    assert needle in src, (code, rel, "needle shape changed")
    assert lint_source(src, path=rel, select={code}) == [], \
        (code, "unmutated must verify clean")
    found = lint_source(src.replace(needle, mutation, 1), path=rel,
                        select={code})
    assert [f.code for f in found] == [code], (code, found)

# FL150: a payload log planted beside the real server's controller
# handoff is a raw per-client tensor crossing into a telemetry sink
both_ways(
    "fedml_tpu/resilience/integration.py",
    '            self._controller.report(\n'
    '                msg.get("round"), msg.get("attempt"),'
    ' msg.get_sender_id(),\n'
    '                msg.get("num_samples"), self._report_payload(msg))',
    '            payload = self._report_payload(msg)\n'
    '            logging.info("report from %d: %r",\n'
    '                         msg.get_sender_id(), payload)\n'
    '            self._controller.report(\n'
    '                msg.get("round"), msg.get("attempt"),'
    ' msg.get_sender_id(),\n'
    '                msg.get("num_samples"), payload)',
    "FL150")
# FL151: reversing DPPolicy.privatize's clip->noise order voids the
# sensitivity bound the epsilon accountant depends on
both_ways(
    "fedml_tpu/program/privacy.py",
    "        clipped = self.clip(delta)\n"
    "        if self.noise_multiplier == 0:\n"
    "            return clipped\n"
    "        return self.noise(clipped, rank, round_idx, attempt)",
    "        noised = self.noise(delta, rank, round_idx, attempt)\n"
    "        return self.clip(noised)",
    "FL151")
# FL151 (rng half): a constant-seeded noise stream replays the same
# noise every round -- averaging cancels it
both_ways(
    "fedml_tpu/program/privacy.py",
    "        rng = self.noise_rng(rank, round_idx, attempt)",
    "        rng = np.random.default_rng(0)",
    "FL151")
# FL152: dequantizing shares before reconstruction commutes a float op
# inside the mask -- the field arithmetic no longer cancels the masks
both_ways(
    "fedml_tpu/core/mpc.py",
    "    total_q = reconstruct_additive(partials, p)\n"
    "    return dequantize(total_q, scale, p)",
    "    total = reconstruct_additive(\n"
    "        [dequantize(s, scale, p) for s in partials], p)\n"
    "    return total",
    "FL152")
# FL153: deleting the client's privatize block leaves the declared DP
# leg bypassed on the material send path
both_ways(
    "fedml_tpu/resilience/integration.py",
    '            if self.dp is not None:\n'
    '                # DP before codec, always: the mechanism\'s'
    ' clip->noise\n'
    '                # runs on the raw delta, then the (lossy,'
    ' NON-private)\n'
    '                # uplink encode sees only the privatized'
    ' update --\n'
    '                # fedcheck FL153 pins this order statically\n'
    '                params = self.dp.privatize_params(\n'
    '                    msg.get("params"), params, self.rank,'
    ' rnd, attempt)\n',
    '',
    "FL153")
print("fedpriv mutation fixtures: FL150-FL153 each fire exactly once "
      "on their un-fixed invariant, clean tree verifies clean")
EOF
echo "-- fedpriv pass isolation (--select FL150 must run ONLY the"
echo "   privacy pass: zero findings on the tree, and the report names"
echo "   no other pass's rules) --"
python -m fedml_tpu.analysis $LINT_SCOPE --select FL150 --format json \
    --max-seconds "$FEDLINT_BUDGET_S" \
    > bench_results/fedlint_privacy_select.json
python - <<'EOF'
import json
rep = json.load(open("bench_results/fedlint_privacy_select.json"))
assert rep["summary"]["new"] == 0, rep["summary"]
assert all(f["code"] == "FL150" for f in rep["findings"]), rep["findings"]
print("fedpriv --select FL150: privacy pass runs in isolation, 0 findings")
EOF
echo "-- fedlint --fix idempotence (clean tree => empty diff; same"
echo "   wall-time budget -- the fixer's FL110 simulation is budgeted too) --"
python -m fedml_tpu.analysis $LINT_SCOPE --fix --diff \
    --max-seconds "$FEDLINT_BUDGET_S"

echo "== fast test tier (engine / core / utils / native / data-extra / online;"
echo "   includes the federated==centralized + wave/lane==flat equivalence asserts) =="
python -m pytest tests/ -q -m "not slow" -p no:cacheprovider

echo "== codec size-regression gate (binary framing >= 5x smaller than"
echo "   JSON lists for a ResNet-sized pytree; bench.py --check) =="
python bench.py --check

echo "== CLI smoke: --ci equivalence run under --audit (reference"
echo "   CI-script-fedavg.sh); gates on zero steady-state retraces and"
echo "   zero guarded-transfer violations =="
python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")  # CI hosts have no TPU
from fedml_tpu.experiments import main_fedavg
from fedml_tpu.analysis.runtime import audit

report = {}
with audit(metrics_logger=report.update) as auditor:
    main_fedavg.main([
        "--dataset", "synthetic", "--model", "lr", "--comm_round", "2",
        "--epochs", "1", "--client_num_in_total", "4",
        "--client_num_per_round", "4", "--batch_size", "-1", "--ci", "1"])
assert report["audit/rounds"] == 2, report
assert report["audit/steady_state_retraces"] == 0, (
    "round loop retraced after warm-up", report)
assert report["audit/transfer_guard_violations"] == 0, report
print("CI CLI smoke + runtime audit: OK", report)
EOF

echo "== chaos smoke (fedml_tpu.resilience): 3-round TCP FedAvg with one"
echo "   injected client kill and one stall past the deadline, run under"
echo "   the --race-audit sanitizer (instrumented control-plane locks)"
echo "   AND fedtrace (--trace --flightrec equivalent) -- must complete"
echo "   DEGRADED (no hang; bounded by timeout), the final model must"
echo "   equal the reporting-subset weighted average exactly (A/B vs a"
echo "   no-fault run over the same subsets), the race audit must report"
echo "   ZERO lock-order cycles and ZERO held-while-blocking events, the"
echo "   Chrome trace must parse with balanced B/E events, the kill must"
echo "   produce exactly one flight-recorder dump holding its PEER_LOST"
echo "   event, metrics.prom must match the exposition grammar, and the"
echo "   perfmon must leave a parseable status.json with the final round"
echo "   outcome plus live report-latency/rounds-per-hour series."
echo "   fedlint must stay at zero findings on the resilience +"
echo "   observability packages =="
python -m fedml_tpu.analysis fedml_tpu/resilience/ fedml_tpu/observability/ \
    > /dev/null \
    && echo "fedlint on resilience/ + observability/: 0 findings"
timeout -k 10 180 python - <<'EOF'
import json, re, tempfile
import numpy as np
from fedml_tpu.analysis.runtime import race_audit
from fedml_tpu.observability import enable
from fedml_tpu.resilience import (FaultPlan, FaultRule, RoundPolicy,
                                  run_tcp_fedavg)

w0 = {"w": np.zeros((4, 4), np.float32), "b": np.ones(4, np.float32)}
plan = FaultPlan(seed=7, rules=(
    # client 3 dies just before its round-1 report; client 2's first
    # report stalls well past the 1 s deadline
    FaultRule("kill", rank=3, msg_type="res_report", nth=2),
    FaultRule("stall", rank=2, msg_type="res_report", nth=1, delay_s=4.0),
))
d = tempfile.mkdtemp(prefix="fedtrace_smoke_")
with enable(trace=True, trace_dir=d, flightrec=True, flightrec_dir=d,
            compile_events=False, perfmon=True) as obs:
    with race_audit() as ra:
        srv = run_tcp_fedavg(4, 3, RoundPolicy(deadline_s=1.0, quorum=0.3),
                             w0, fault_plan=plan, join_timeout=90)
    spans = obs.tracer.finished_spans()
assert srv.failed is None and len(srv.history) == 3, (
    srv.failed, len(srv.history))
assert srv.counters["rounds_degraded"] >= 1, srv.counters
race = ra.report()
assert race["race/locks_created"] > 0, race  # the factories were live
assert race["race/lock_order_cycles"] == [], race
assert race["race/held_while_blocking"] == [], race

# fedtrace: the Chrome trace parses as JSON with balanced B/E events,
# and client local-train spans stitch under server round spans
doc = json.load(open(obs.chrome_path))
evs = doc["traceEvents"]
nb = sum(1 for e in evs if e.get("ph") == "B")
ne = sum(1 for e in evs if e.get("ph") == "E")
assert nb == ne > 0, (nb, ne)
rounds = {s.span_id: s for s in spans if s.name == "round"}
lts = [s for s in spans if s.name == "local-train"]
assert lts and all(s.parent_id in rounds and
                   s.trace_id == rounds[s.parent_id].trace_id
                   for s in lts), "cross-rank span stitching broken"

# flight recorder: the kill produced exactly ONE dump TRIGGERED by rank
# 3's PEER_LOST -- identified by the dump_info trailer, since the ring's
# retained events (incl. the kill) also appear in any later dump (e.g.
# the stalled client observing teardown). The kill dump must hold the
# peer_lost event plus surrounding traffic.
kill_dumps = []
for p in obs.recorder.dumps:
    events = [json.loads(l) for l in open(p)]
    info = [e for e in events if e["kind"] == "dump_info"]
    if info and info[-1].get("peer") == 3:
        kill_dumps.append(events)
assert len(kill_dumps) == 1, obs.recorder.dumps
assert any(e["kind"] == "peer_lost" and e.get("peer") == 3
           for e in kill_dumps[0])

# perfmon (PR 10): the chaos run left a parseable status.json carrying
# the FINAL round outcome (the kill+stall scenario degrades at least one
# round, visible in the outcome counts), the straggler-tail histogram
# saw every report, and the rolling rounds/hour gauge is live
status = json.load(open(obs.status_path))
assert status["last_outcome"] in ("complete", "degraded"), status
assert status["round"] == 3 and status["final"] is True, status
assert status["outcome_counts"]["degraded"] >= 1, status
# feddet (PR 17): status.json names the ACTIVE round program -- the
# manifest minus client_update, written sort_keys (the FL135-clean
# serialization reference), so an operator reads WHICH round definition
# the fleet executed, not just how fast it went
assert status["program"]["aggregation"]["mode"] == "sync", status
assert status["program"]["cohort"]["quorum"] == 0.3, status
assert status["program"]["cohort"]["deadline_s"] == 1.0, status
assert obs.registry.get("fed_report_latency_seconds")[1] > 0
assert obs.registry.get("fed_rounds_per_hour") > 0

# metrics.prom: every line matches the exposition grammar
prom_line = re.compile(
    r"^(# HELP [a-zA-Z_:][a-zA-Z0-9_:]* .*"
    r"|# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? "
    r"(-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?|[+-]Inf|NaN))$")
prom = open(obs.prom_path).read()
for line in prom.strip().split("\n"):
    assert prom_line.match(line), line
assert "comm_bytes_total" in prom

subsets = srv.reporting_log
ref = run_tcp_fedavg(4, 3, RoundPolicy(deadline_s=10.0, quorum=0.3), w0,
                     cohort_override=lambda r, a: subsets[r],
                     join_timeout=90)
for got, want in zip(srv.history, ref.history):
    for k in got:
        assert (got[k] == want[k]).all(), k
print("chaos smoke: degraded completion + exact subset average + clean "
      "race audit + stitched trace + one PEER_LOST dump + valid prom OK",
      {"reporting": subsets, "spans": len(spans),
       "race_acquisitions": race["race/acquisitions"], **srv.counters})
EOF

echo "== event-loop transport chaos smoke (fedml_tpu.net): the SAME"
echo "   kill+stall scenario over --transport eventloop under the"
echo "   --race_audit sanitizer -- must complete DEGRADED with ZERO"
echo "   lock-order cycles and ZERO held-while-blocking events, the"
echo "   final model must equal the reporting-subset weighted average"
echo "   exactly, small-rank trajectories must be BITWISE-equal to the"
echo "   threaded-tcp transport under oracle settings, client spans"
echo "   must stitch under server round spans THROUGH the new"
echo "   transport, and the kill's flight-recorder dump + the"
echo "   comm_bytes_total{transport=eventloop} series must exist."
echo "   fedlint/fedcheck (incl. the new FL129 event-loop readiness"
echo "   rule -- now also rooting decode-stage callbacks -- and"
echo "   container-element FL126 chains) must stay at zero findings on"
echo "   the ingest pipeline's whole span: net/ + compression/ +"
echo "   resilience/ =="
python -m fedml_tpu.analysis fedml_tpu/net/ fedml_tpu/compression/ \
    fedml_tpu/resilience/ > /dev/null \
    && echo "fedlint on net/ + compression/ + resilience/: 0 findings"
timeout -k 10 180 python - <<'EOF'
import json, tempfile
import numpy as np
from fedml_tpu.analysis.runtime import race_audit
from fedml_tpu.observability import enable
from fedml_tpu.resilience import (FaultPlan, FaultRule, RoundPolicy,
                                  run_tcp_fedavg)

w0 = {"w": np.zeros((4, 4), np.float32), "b": np.ones(4, np.float32)}
plan = FaultPlan(seed=7, rules=(
    FaultRule("kill", rank=3, msg_type="res_report", nth=2),
    FaultRule("stall", rank=2, msg_type="res_report", nth=1, delay_s=4.0),
))
d = tempfile.mkdtemp(prefix="evloop_smoke_")
with enable(trace=True, trace_dir=d, flightrec=True, flightrec_dir=d,
            compile_events=False) as obs:
    with race_audit() as ra:
        srv = run_tcp_fedavg(4, 3, RoundPolicy(deadline_s=1.0, quorum=0.3),
                             w0, fault_plan=plan, join_timeout=90,
                             transport="eventloop")
    spans = obs.tracer.finished_spans()
assert srv.failed is None and len(srv.history) == 3, (
    srv.failed, len(srv.history))
assert srv.counters["rounds_degraded"] >= 1, srv.counters
race = ra.report()
assert race["race/locks_created"] > 0, race
assert race["race/lock_order_cycles"] == [], race
assert race["race/held_while_blocking"] == [], race

# cross-rank stitching works through the event loop (same __trace__)
rounds = {s.span_id: s for s in spans if s.name == "round"}
lts = [s for s in spans if s.name == "local-train"]
assert lts and all(s.parent_id in rounds and
                   s.trace_id == rounds[s.parent_id].trace_id
                   for s in lts), "span stitching broken over eventloop"

# the kill's dump exists and its PEER_LOST names the new transport
kill = []
for p in obs.recorder.dumps:
    events = [json.loads(l) for l in open(p)]
    info = [e for e in events if e["kind"] == "dump_info"]
    if info and info[-1].get("peer") == 3:
        kill.append(events)
assert len(kill) == 1, obs.recorder.dumps
assert any(e["kind"] == "peer_lost" and e.get("peer") == 3
           and e.get("transport") == "eventloop" for e in kill[0])
sent = obs.registry.get("comm_bytes_total", transport="eventloop",
                        direction="sent")
assert sent and sent > 0

# degraded-round exactness (A/B over the same reporting subsets)
ref = run_tcp_fedavg(4, 3, RoundPolicy(deadline_s=10.0, quorum=0.3), w0,
                     cohort_override=lambda r, a: srv.reporting_log[r],
                     join_timeout=90, transport="eventloop")
for got, want in zip(srv.history, ref.history):
    for k in got:
        assert (got[k] == want[k]).all(), k

# small-rank bitwise transport A/B: same FSMs, same trajectory, both
# paradigms (oracle settings: no faults / unbounded buffer, decay 0)
from fedml_tpu.resilience.async_agg import AsyncAggPolicy, run_async_tcp_fedavg
a = run_tcp_fedavg(4, 2, RoundPolicy(), w0, transport="tcp", join_timeout=60)
b = run_tcp_fedavg(4, 2, RoundPolicy(), w0, transport="eventloop",
                   join_timeout=60)
pol = AsyncAggPolicy(buffer_k=10 ** 9, staleness_decay=0.0)
c = run_async_tcp_fedavg(4, 2, pol, w0, transport="tcp", join_timeout=60)
e = run_async_tcp_fedavg(4, 2, pol, w0, transport="eventloop",
                         join_timeout=60)
for x, y in ((a, b), (c, e)):
    assert x.failed is None and y.failed is None
    for gx, gy in zip(x.history, y.history):
        for k in gx:
            assert (gx[k] == gy[k]).all(), ("transport A/B bitwise", k)
print("eventloop chaos smoke: degraded + exact subset average + clean "
      "race audit + stitched spans + eventloop PEER_LOST dump + "
      "sync/async tcp-vs-eventloop bitwise A/B OK",
      {"reporting": srv.reporting_log, **srv.counters})
EOF

echo "== massive-cohort smoke (bucketed ragged streaming + buffered async"
echo "   aggregation): one chip runs 2 rounds of 50,000 ragged simulated"
echo "   clients (honest per-client n_i weighting); the async path under"
echo "   the oracle settings (unbounded buffer, staleness decay 0) is the"
echo "   canonical fp64 fold, and the synchronous stream's device fold"
echo "   (two float32 words) must equal it within ONE float32 ulp after a"
echo "   round from the same state; the retrace audit must"
echo "   report zero steady-state retraces and the compiled chunk-program"
echo "   count must equal the number of bucket shapes; async round records"
echo "   must carry the buffer-depth/staleness series. fedlint must stay"
echo "   at zero findings on the async + engine files =="
python -m fedml_tpu.analysis fedml_tpu/resilience/ fedml_tpu/parallel/ \
    fedml_tpu/compression/ \
    && echo "fedlint on resilience/ + parallel/ + compression/: 0 findings"
timeout -k 10 300 python - <<'EOF'
import types

import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np

import bench
from fedml_tpu import models
from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.algorithms.specs import make_classification_spec
from fedml_tpu.analysis.runtime import audit

C = 50_000
dataset = bench._ragged_lr_clients(C)
spec = make_classification_spec(
    models.LogisticRegression(num_classes=4, apply_sigmoid=False),
    jnp.zeros((1, 16)))

def build(async_on):
    run_args = types.SimpleNamespace(
        client_num_in_total=C, client_num_per_round=C, comm_round=10 ** 9,
        epochs=1, batch_size=8, lr=0.05, wd=0.0, client_optimizer="sgd",
        frequency_of_the_test=10 ** 9, seed=0, client_chunk=512,
        bucket_edges="geometric", async_agg=async_on,
        # oracle settings: unbounded buffer (one drain flush per round),
        # staleness weight exactly 1
        buffer_k=10 ** 9, staleness_decay=0.0, async_window=4,
        device_resident="0")
    return FedAvgAPI(dataset, spec, run_args)

report = {}
with audit(metrics_logger=report.update):
    api = build(0)
    api.train_one_round()
    sync_params = jax.tree.map(np.asarray, api.global_state)
    m = api.train_one_round()
assert report["audit/rounds"] == 2, report
assert report["audit/steady_state_retraces"] == 0, (
    "bucketed streaming retraced after round 1", report)
assert report["audit/transfer_guard_violations"] == 0, report
shapes = api.runner.compiled_shapes()
assert shapes == m["bucket/shapes"] > 0, (shapes, m)

api2 = build(1)
a1 = api2.train_one_round()
# one round from the same state: the same chunk payload sums through the
# host's float64 fold and through the device's two-word float32 fold
async_params = jax.tree.map(np.asarray, api2.global_state)
for s, a in zip(jax.tree.leaves(sync_params), jax.tree.leaves(async_params)):
    np.testing.assert_array_max_ulp(s, a, maxulp=1)
a2 = api2.train_one_round()
for rec in (a1, a2):  # buffer-depth/staleness series on async records
    assert "async/depth_peak" in rec and "async/max_staleness" in rec, rec
print("massive-cohort smoke:", C, "clients/round, bucket shapes =", shapes,
      "waste_frac =", m["bucket/waste_frac"],
      "| device fold within 1 ulp of the fp64 oracle | retrace audit clean")
EOF

echo "== massive-cohort bench record (clients/sec JSON line, XLA"
echo "   cost-model per-bucket FLOPs + FLOP-weighted padding waste;"
echo "   the record seeds the throwaway perf-regression ledger) =="
CI_LEDGER=bench_results/ci_ledger.jsonl
rm -f "$CI_LEDGER"
timeout -k 10 300 python bench.py --massive_cohort 12000 --rounds 1 \
    --platform cpu --ledger "$CI_LEDGER" \
    > bench_results/bench_massive_smoke.json
python - <<'EOF'
import json
with open("bench_results/bench_massive_smoke.json") as f:
    rec = json.loads(f.readline())
assert rec["unit"] == "clients/sec" and rec["value"] > 0, rec
assert rec["bucket_shapes"] > 0 and rec["steady_compiles"] == 0, rec
# cost-model attribution (PR 10): per-bucket-shape FLOPs + the padded
# waste reported in FLOPs, from the compiled programs (flops_source xla)
assert rec["flops_source"] == "xla", rec.get("flops_source")
assert rec["executed_flops"] > rec["true_flops"] > 0, rec
assert 0.0 <= rec["flops_waste_frac"] < 1.0, rec
used = rec["per_bucket"]
assert used and all("executed_flops" in b and "flops_per_step" in b
                    for b in used), used
print("bench --massive_cohort:", rec["value"], "clients/sec,",
      rec["bucket_shapes"], "bucket shapes, step waste",
      rec["bucket_waste_frac"], "/ flop waste", rec["flops_waste_frac"])
EOF

echo "== event-loop soak smoke (bench.py --soak): 1,000 swarm"
echo "   connections through a real buffered-async server over the"
echo "   selector transport, 3 async windows -- the record (reports/sec"
echo "   headline + fed_report_latency_seconds p50/p90/p99 tail + the"
echo "   ingest stage's decode-seconds-per-report) feeds the same"
echo "   throwaway perf-regression ledger, as TWO rows: reports/sec and"
echo "   decode frames/sec (so a decode slowdown is gated even when"
echo "   wall-clock reports/sec is masked by reply jitter). The swarm"
echo "   replays the DIURNAL trace (day/outage/night/flash arrival"
echo "   curve, fedml_tpu.resilience.faults.DiurnalTrace) instead of"
echo "   uniform jitter, so the latency histogram carries a realistic"
echo "   tail. The 10k headline soak is the slow-marked"
echo "   tests/test_net.py::TestSoak::test_soak_10k (evidence in"
echo "   docs/NETWORKING.md) =="
timeout -k 10 300 python bench.py --soak 1000 --soak_trace diurnal \
    --ledger "$CI_LEDGER" \
    > bench_results/bench_soak_smoke.json
python - <<'EOF'
import json
with open("bench_results/bench_soak_smoke.json") as f:
    rec = json.loads(f.readline())
    dec = json.loads(f.readline())
assert rec["unit"] == "reports/sec" and rec["value"] > 0, rec
assert rec["connections"] == 1000 and rec["updates"] == 3, rec
assert rec["status_outcome"] == "complete", rec
assert rec["report_latency_p99_s"] is not None, rec
assert rec["jitter_model"] == "diurnal-trace", rec
# ingest pipeline accounting (ISSUE 14): every report went through the
# counted batch-decode path
assert rec["ingest_frames"] >= rec["reports"], rec
assert rec["decode_s_per_report"] and rec["decode_s_per_report"] > 0, rec
assert dec["unit"] == "frames/decode-sec" and dec["value"] > 0, dec
print("bench --soak:", rec["value"], "reports/sec over",
      rec["connections"], "connections (diurnal trace);",
      "p50/p99 report latency", rec["report_latency_p50_s"], "/",
      rec["report_latency_p99_s"], "s; decode",
      round(rec["decode_s_per_report"] * 1e6, 1), "us/report")
EOF

echo "== fedsqueeze compressed-reporting smoke (bench.py --soak/"
echo "   --massive_cohort --compressor qsgd): the 1k soak re-runs as a"
echo "   plain/compressed pair over the REAL eventloop wire (swarm"
echo "   clients ship EF-compressed deltas, the async server folds them"
echo "   sparsely against each report's base version) and the bucketed"
echo "   massive-cohort bench re-runs with streaming-EF inside the"
echo "   jitted chunk program. Gates: (a) measured bytes-on-wire"
echo "   reduction >= 8x vs the plain row (qsgd:2 packs ternary codes at"
echo "   2 bits/element -- measured ~15x); (b) reports/sec >= 0.9x the"
echo "   plain row on multi-core hosts (the swarm's own encode runs in"
echo "   its subprocess; 0.9 absorbs two independent runs' jitter);"
echo "   1-core hosts gate a 0.6x floor instead -- there"
echo "   the swarm's encode burst serializes with the server on the one"
echo "   core and loopback bytes are free, the regime the NETWORKING.md"
echo "   table documents; (c) the compressed massive record holds the"
echo "   zero-steady-compile + shapes==buckets contract WITH the"
echo "   compressor fused in, and carries bytes_on_wire/ratio; (d) all"
echo "   three compressed rows land on the throwaway ledger (own metric"
echo "   strings -- compressed trends never judge plain rows) and a"
echo "   planted 2x wire-reduction regression turns --check-regress red"
echo "   (below, with the other fixtures). EF convergence is tier-1"
echo "   (test_compression/test_resilience: compressed final quality"
echo "   within tolerance of plain on matched seeds; --compressor none"
echo "   bitwise-identical to no flag) =="
timeout -k 10 300 python bench.py --soak 1000 --soak_jitter 0.35 \
    --ledger "$CI_LEDGER" > bench_results/bench_soak_plain_pair.json
timeout -k 10 300 python bench.py --soak 1000 --soak_jitter 0.35 \
    --compressor qsgd --ledger "$CI_LEDGER" \
    > bench_results/bench_soak_qsgd.json
timeout -k 10 300 python bench.py --massive_cohort 8000 --rounds 1 \
    --platform cpu --compressor qsgd --ledger "$CI_LEDGER" \
    > bench_results/bench_massive_qsgd.json
python - <<'EOF'
import json, os
plain = json.loads(
    open("bench_results/bench_soak_plain_pair.json").readline())
with open("bench_results/bench_soak_qsgd.json") as f:
    comp = json.loads(f.readline())
    rows = [json.loads(l) for l in f if l.strip()]
assert comp["compressor"] == "qsgd:2", comp
assert comp["reports"] == plain["reports"] == 3000, (comp, plain)
# (a) the headline byte gate: measured uplink bytes per report vs the
# plain frame floor for the SAME model
assert comp["wire_reduction"] >= 8.0, comp["wire_reduction"]
assert comp["measured_bytes_per_report"] < plain[
    "measured_bytes_per_report"] / 8.0, (comp, plain)
# the wire-reduction ledger row exists (the planted-ratio fixture's prey)
ratio_rows = [r for r in rows if r["unit"] == "x-vs-plain-frames"]
assert ratio_rows and ratio_rows[0]["value"] >= 8.0, rows
# (b) reports/sec vs plain, host-class honest (0.9 not 1.0 on
# multi-core: two independently measured rates carry run-to-run
# jitter; the ledger's --check-regress trend line is the tight gate)
floor = 0.9 if (os.cpu_count() or 1) >= 2 else 0.6
assert comp["value"] >= floor * plain["value"], (
    f"compressed {comp['value']} rps vs plain {plain['value']} "
    f"(floor {floor}x, {os.cpu_count()} cpu)")
# (c) compressed massive-cohort: the streaming-EF chunk program holds
# the compile-shape contract and accounts its bytes
m = json.loads(open("bench_results/bench_massive_qsgd.json").readline())
assert m["compressor"] == "qsgd" and m["steady_compiles"] == 0, m
assert m["bucket_shapes"] > 0 and m["value"] > 0, m
assert m["compression_ratio"] > 1.0 and m["bytes_on_wire"] > 0, m
print("fedsqueeze smoke: soak", comp["value"], "rps compressed vs",
      plain["value"], "plain,", comp["wire_reduction"],
      "x fewer wire bytes; massive", m["value"],
      "clients/sec streaming-EF, ratio", m["compression_ratio"],
      ", 0 steady compiles,", m["bucket_shapes"], "bucket shapes")
EOF

echo "== perf-regression ledger gate (bench.py --check-regress, both"
echo "   ways): the massive + soak smokes seeded a throwaway ledger --"
echo "   the gate must pass GREEN on it (fresh: no same-metric"
echo "   predecessor), then fail RED on a planted 2x DECODE slowdown"
echo "   (the ingest pipeline's own metric -- the win can never"
echo "   silently rot), then RED again on the classic 2x clients/sec"
echo "   slowdown =="
python bench.py --check-regress --ledger "$CI_LEDGER"
python - <<'EOF'
import json
from fedml_tpu.observability.perfmon import append_ledger
with open("bench_results/bench_soak_smoke.json") as f:
    f.readline()
    dec = json.loads(f.readline())
slow = dict(dec)
slow["value"] = dec["value"] / 2.0       # planted 2x decode slowdown
slow["decode_s_per_report"] = dec["decode_s_per_report"] * 2.0
slow["injected_fixture"] = "2x-decode-slowdown"
append_ledger(slow, "bench_results/ci_ledger.jsonl")
EOF
if python bench.py --check-regress --ledger "$CI_LEDGER"; then
    echo "perf-regression gate FAILED to fire on the 2x decode slowdown"
    exit 1
fi
echo "perf-regression gate: red on planted 2x decode slowdown OK"
python - <<'EOF'
import json
from fedml_tpu.observability.perfmon import append_ledger
rows = [json.loads(l)
        for l in open("bench_results/bench_soak_qsgd.json") if l.strip()]
ratio = [r for r in rows if r["unit"] == "x-vs-plain-frames"][0]
slow = dict(ratio)
slow["value"] = ratio["value"] / 2.0  # planted compression-ratio rot
slow["injected_fixture"] = "2x-wire-reduction-drop"
append_ledger(slow, "bench_results/ci_ledger.jsonl")
EOF
if python bench.py --check-regress --ledger "$CI_LEDGER"; then
    echo "perf-regression gate FAILED to fire on the wire-reduction drop"
    exit 1
fi
echo "perf-regression gate: red on planted 2x wire-reduction drop OK"
python - <<'EOF'
import json
from fedml_tpu.observability.perfmon import append_ledger
rec = json.loads(open("bench_results/bench_massive_smoke.json").readline())
slow = dict(rec)
slow["value"] = rec["value"] / 2.0       # the injected 2x slowdown
slow["round_s"] = rec["round_s"] * 2.0
slow["injected_fixture"] = "2x-slowdown"
append_ledger(slow, "bench_results/ci_ledger.jsonl")
EOF
if python bench.py --check-regress --ledger "$CI_LEDGER"; then
    echo "perf-regression gate FAILED to fire on the 2x-slowdown fixture"
    exit 1
fi
echo "perf-regression gate: green on fresh ledger, red on 2x slowdown OK"
rm -f "$CI_LEDGER"

echo "== fedpace steering smoke (bench.py --steering): on one seeded"
echo "   diurnal trace (day / flash crowd / latency outage / night with"
echo "   correlated dropouts), a sweep of fixed (deadline, overselect)"
echo "   configs vs one --pace_steering run over the real TCP control"
echo "   plane with the perf monitor armed. Gates: (a) the short/mid"
echo "   fixed deadlines are DISQUALIFIED by the outage (abandon-out,"
echo "   recorded as failed) -- the reason an operator cannot just pick"
echo "   a small deadline; (b) steered completes >= 1.10x the rounds/"
echo "   hour of the best surviving fixed config (measured ~1.9x) with"
echo "   final-model quality within tolerance of the unshaped full-"
echo "   participation reference; (c) the steered record lands on the"
echo "   throwaway ledger, --check-regress is green fresh and goes red"
echo "   on a planted 2x rph drop. fedlint zero on resilience/ (incl."
echo "   steering.py) is gated by the chaos-smoke section above =="
PACE_LEDGER=bench_results/ci_pace_ledger.jsonl
rm -f "$PACE_LEDGER"
timeout -k 10 600 python bench.py --steering --ledger "$PACE_LEDGER" \
    > bench_results/bench_steering_smoke.json
python - <<'EOF'
import json
rec = json.loads(open("bench_results/bench_steering_smoke.json").readline())
assert rec["unit"] == "rounds/hour" and rec["value"] > 0, rec
assert rec["pass"] is True, rec
assert rec["speedup_vs_best_fixed"] >= rec["speedup_threshold"] == 1.10, rec
assert rec["steered"]["quality_rel"] <= rec["quality_tol"], rec
failed = [f for f in rec["fixed_sweep"] if "failed" in f]
survived = [f for f in rec["fixed_sweep"] if "rph" in f]
assert failed and survived, \
    "the sweep must both disqualify short deadlines and keep a best-fixed"
led = [json.loads(l) for l in open("bench_results/ci_pace_ledger.jsonl")]
assert led and led[-1]["metric"] == rec["metric"], \
    "steered record did not land on the ledger"
print("fedpace steering smoke:", rec["value"], "rph steered vs",
      rec["best_fixed_rph"], "best fixed ->",
      rec["speedup_vs_best_fixed"], "x; quality",
      rec["steered"]["quality_rel"], "; disqualified fixed configs:",
      [f["config"] for f in failed])
EOF
python bench.py --check-regress --ledger "$PACE_LEDGER"
python - <<'EOF'
import json
from fedml_tpu.observability.perfmon import append_ledger
rec = json.loads(open("bench_results/bench_steering_smoke.json").readline())
slow = dict(rec)
slow["value"] = rec["value"] / 2.0          # the planted 2x rph drop
slow["injected_fixture"] = "2x-rph-drop"
append_ledger(slow, "bench_results/ci_pace_ledger.jsonl")
EOF
if python bench.py --check-regress --ledger "$PACE_LEDGER"; then
    echo "steering perf-regression gate FAILED to fire on the 2x rph drop"
    exit 1
fi
echo "fedpace ledger gate: green on the real record, red on 2x drop OK"
rm -f "$PACE_LEDGER"

echo "== fedwarm + federated-LM flagship smoke (bench.py --lm --warmup):"
echo "   a tiny TransformerLM federated run through FedAvgAPI + the"
echo "   bucketed streaming engine, TWICE over one --compile_cache_dir."
echo "   Gates: (a) the LM record carries cost-model-sourced FLOPs"
echo "   (flops_source: xla-cost-model) and, on the CPU, no MFU;"
echo "   (b) THE warm-restart gate --"
echo "   the second run's AOT warmup takes ZERO persistent-cache misses"
echo "   (every compile event is a cache load; jax fires the compile"
echo "   event on hits too, with the deserialization time) and zero"
echo "   steady-state compiles, with warmup compile seconds collapsed"
echo "   to cache-load time; (c) the LM ledger gate fires both ways --"
echo "   green on the two real runs, red on a planted 2x rate drop."
echo "   fedlint must stay at zero findings on the new compile/ + ops/"
echo "   kernel files =="
python -m fedml_tpu.analysis fedml_tpu/compile/ fedml_tpu/ops/ > /dev/null \
    && echo "fedlint on compile/ + ops/: 0 findings"
LM_LEDGER=bench_results/ci_lm_ledger.jsonl
WARM_CACHE=$(mktemp -d)
rm -f "$LM_LEDGER"
# FEDML_TPU_COMPILE_MIN_S=0: sub-1s CPU programs must persist or the
# warm-restart path is untestable off-TPU (the exposed threshold)
timeout -k 10 300 env FEDML_TPU_COMPILE_MIN_S=0 python bench.py --lm \
    --smoke --platform cpu --warmup 1 --compile_cache_dir "$WARM_CACHE" \
    --ledger "$LM_LEDGER" > bench_results/bench_lm_smoke_cold.json
timeout -k 10 300 env FEDML_TPU_COMPILE_MIN_S=0 python bench.py --lm \
    --smoke --platform cpu --warmup 1 --compile_cache_dir "$WARM_CACHE" \
    --ledger "$LM_LEDGER" > bench_results/bench_lm_smoke_warm.json
python - <<'EOF'
import json
cold = json.loads(open("bench_results/bench_lm_smoke_cold.json").readline())
warm = json.loads(open("bench_results/bench_lm_smoke_warm.json").readline())
for rec in (cold, warm):
    assert rec["unit"] == "rounds/hour" and rec["value"] > 0, rec
    assert rec["flops_source"] == "xla-cost-model", rec
    # --platform cpu: no accelerator peak to stand against, so no MFU
    assert rec["mfu"] is None and rec["lm_rounds_per_hour"] > 0, rec
    assert rec["achieved_tflops"] >= 0, rec
    assert rec["steady_compiles"] == 0, rec
    assert rec["warmup_programs"] >= 3, rec
assert cold["warmup_cache_misses"] > 0, cold  # fresh cache: real compiles
assert warm["warmup_cache_misses"] == 0, warm
assert warm["warmup_compile_s"] < cold["warmup_compile_s"], (warm, cold)
print("fedwarm warm-restart gate: cold", cold["warmup_compile_s"], "s ->",
      "warm", warm["warmup_compile_s"], "s, 0 warm cache misses, 0 steady",
      "compiles | FLOPs from", warm["flops_source"])
EOF
python bench.py --check-regress --ledger "$LM_LEDGER" --regress_band 0.4
python - <<'EOF'
import json
from fedml_tpu.observability.perfmon import append_ledger
rec = json.loads(open("bench_results/bench_lm_smoke_warm.json").readline())
slow = dict(rec)
slow["value"] = rec["value"] / 2.0          # the planted 2x rate drop
slow["lm_rounds_per_hour"] = rec["lm_rounds_per_hour"] / 2.0
slow["injected_fixture"] = "2x-rate-drop"
append_ledger(slow, "bench_results/ci_lm_ledger.jsonl")
EOF
if python bench.py --check-regress --ledger "$LM_LEDGER" --regress_band 0.4; then
    echo "LM perf-regression gate FAILED to fire on the 2x rate drop"
    exit 1
fi
echo "LM ledger gate: green on real runs, red on 2x rate drop OK"
rm -f "$LM_LEDGER"
rm -rf "$WARM_CACHE"

echo "== fedtree process-tree soak smoke (bench.py --tree_soak): 1,000"
echo "   leaves sharded across 2 REAL edge processes (each edge: a"
echo "   500-leaf eventloop star below, one qsgd-compressed EF wire"
echo "   above), replaying the diurnal trace with one PaceController"
echo "   per tier (edge bounds clamped inside the coordinator's)."
echo "   Gates: (a) the coordinator completes every update with zero"
echo "   zombie and zero force-killed processes; (b) every tier wrote"
echo "   its own parseable status.json and all tiers agree on the"
echo "   RoundProgram core (topology.tree.manifest_core -- steered"
echo "   knobs excluded), asserted inside the bench and surfaced on"
echo "   the record; (c) the throwaway ledger carries one reports/sec"
echo "   row PER TIER MEMBER plus the tree headline, and"
echo "   --check-regress fires both ways (green fresh, red on a"
echo "   planted 2x throughput drop). The 10k+ tree is the slow-marked"
echo "   tests/test_topology.py soak. fedlint (incl. the determinism"
echo "   + fedmc model-checking passes) must stay at zero findings on"
echo "   the new topology/ package =="
python -m fedml_tpu.analysis fedml_tpu/topology/ > /dev/null \
    && echo "fedlint on topology/: 0 findings"
TREE_LEDGER=bench_results/ci_tree_ledger.jsonl
rm -f "$TREE_LEDGER"
timeout -k 10 600 python bench.py --tree_soak 1000 --tree_fanout 2 \
    --soak_updates 3 --soak_trace diurnal --tree_steering \
    --compressor qsgd --ledger "$TREE_LEDGER" \
    > bench_results/bench_tree_smoke.json
python - <<'EOF'
import json
rec = json.loads(open("bench_results/bench_tree_smoke.json").readline())
assert rec["unit"] == "reports/sec" and rec["value"] > 0, rec
assert rec["leaves"] == 1000 and rec["fanout"] == [2], rec
assert rec["updates"] == 3, rec
assert rec["zombies"] == 0 and rec["killed"] == 0, rec
assert rec["statuses"] == 3 and rec["program_cores_match"] is True, rec
led = [json.loads(l) for l in open("bench_results/ci_tree_ledger.jsonl")]
tiers = [r for r in led if r["metric"].startswith("tree-edge")]
head = [r for r in led if r["metric"].startswith("tree-soak")]
assert len(tiers) == 2 and all(r["value"] > 0 for r in tiers), led
assert len(head) == 1 and led[-1] is head[0], \
    "the tree headline row must close the ledger"
print("fedtree smoke:", rec["value"], "leaf reports/sec across the",
      "process tree;", len(tiers), "per-tier ledger rows; statuses:",
      rec["statuses"], "(program cores match)")
EOF
python bench.py --check-regress --ledger "$TREE_LEDGER"
python - <<'EOF'
import json
from fedml_tpu.observability.perfmon import append_ledger
led = [json.loads(l) for l in open("bench_results/ci_tree_ledger.jsonl")]
head = [r for r in led if r["metric"].startswith("tree-soak")][-1]
slow = dict(head)
slow["value"] = head["value"] / 2.0  # the planted 2x throughput drop
slow["injected_fixture"] = "2x-throughput-drop"
append_ledger(slow, "bench_results/ci_tree_ledger.jsonl")
EOF
if python bench.py --check-regress --ledger "$TREE_LEDGER"; then
    echo "tree perf-regression gate FAILED to fire on the 2x drop"
    exit 1
fi
echo "fedtree ledger gate: green on the real record, red on 2x drop OK"
rm -f "$TREE_LEDGER"

echo "ci.sh: all green"
