"""The recorder level of the one ``Tracer`` (ISSUE 36): the process's
default tracer times context-managed spans into a bounded ring and does
nothing else; compile events hang under the span that paid them; the
start-up report and the stalled round's warning are made from that
record alone; ``NOOP_TRACER`` turns all of it off."""

import json
import logging
import time
import types

import numpy as np
import pytest

from fedml_tpu.core.message import Message
from fedml_tpu.observability import (NOOP_TRACER, MetricsRegistry, RoundLog,
                                     Tracer, get_tracer, set_registry,
                                     set_tracer, startup_report, tracing)
from fedml_tpu.observability.jaxmon import watch_compiles
from fedml_tpu.observability.tracing import JAX_SPANS, TRACE_KEY

#: the report's keys, fixed by ISSUE 36 for the benchmark issue that will
#: read them
REPORT_KEYS = {"total_s", "import_s", "build_s", "trace_s", "lower_s",
               "compile_s", "cache_load_s", "device_s", "unattributed_s",
               "sites", "rounds"}
SITE_KEYS = {"site", "edge", "trace_s", "lower_s", "compile_s",
             "cache_load_s"}
#: spans a compile event of a bucketed run may hang under. ``round`` is
#: the split of the trainer's own key, the first statement of a round's
#: body: it has no leaf of its own
PAYERS = {"bucket-chunk", "fold.add", "fold.finalize", "fold.apply",
          "prepare", "init-state", "build", "round"}


@pytest.fixture
def recorder(monkeypatch):
    """A fresh recorder as the current tracer and a start-up that begins
    now (the process's own began, and maybe ended, tests ago)."""
    monkeypatch.setattr(tracing, "_startup", tracing._Startup())
    tracing.begin_startup()
    tracer = Tracer(max_spans=4096, exporting=False)
    prev = set_tracer(tracer)
    yield tracer
    set_tracer(prev)


def _lm_api(sequences=(4, 8), edges="2,4"):
    """A toy LM through the bucketed stream: one client a chunk, step
    counts 2 and 4, so a cohort of both lands on two bucket edges."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.algorithms.specs import make_seq_classification_spec
    from fedml_tpu.models.transformer import TransformerLM

    def attention(q, k, v):  # plain causal softmax: no kernel to interpret
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        mask = jnp.tril(jnp.ones(s.shape[-2:], bool))
        p = jax.nn.softmax(jnp.where(mask, s, -1e9), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    t, vocab = 16, 64
    model = TransformerLM(vocab_size=vocab, n_layers=1, n_heads=2,
                          d_model=32, max_len=t, attention_fn=attention)
    spec = make_seq_classification_spec(
        model, jnp.zeros((1, t), jnp.int32), name="lm")
    rng = np.random.default_rng(0)
    clients = {i: {"x": rng.integers(0, vocab, (n, t)).astype(np.int32),
                   "y": rng.integers(0, vocab, (n, t)).astype(np.int32)}
               for i, n in enumerate(sequences)}
    nums = {i: len(c["y"]) for i, c in clients.items()}
    dataset = [sum(nums.values()), 0, None, None, nums, clients, {}, vocab]
    args = types.SimpleNamespace(
        client_num_in_total=len(nums), client_num_per_round=len(nums),
        comm_round=10 ** 9, epochs=1, batch_size=2, lr=0.1, wd=0.0,
        client_optimizer="sgd", frequency_of_the_test=10 ** 9, seed=0,
        client_chunk=1, bucket_edges=edges, device_resident="0")
    return FedAvgAPI(dataset, spec, args)


def _lr_api():
    import test_stream_spans

    return test_stream_spans._api()


# -- (a) the default tracer -------------------------------------------------

def test_default_tracer_is_the_recorder_level():
    prev = set_tracer(None)  # None restores the process's default
    try:
        default = get_tracer()
    finally:
        set_tracer(prev)
    assert isinstance(default, Tracer) and default is not NOOP_TRACER
    assert default.enabled is False
    assert set_tracer(NOOP_TRACER) is prev and set_tracer(prev) is NOOP_TRACER


def test_recorder_keeps_the_wire_and_the_detached_spans_off(recorder):
    m = Message("sync", 0, 1)
    before = m.to_bytes()
    with recorder.span("round", round=3) as s:
        recorder.inject(m)
        assert recorder.start_span("attempt", root=True).context is None
        with recorder.remote_context(s.context) as ctx:
            assert ctx.context is None  # the no-op's scope
            with recorder.span("inner"):
                pass
    assert TRACE_KEY not in m.get_params() and m.to_bytes() == before
    inner, outer = recorder.finished_spans()
    assert (inner.name, outer.name) == ("inner", "round")
    assert inner.parent_id == outer.span_id and outer.parent_id is None
    assert 0 <= inner.t0 - outer.t0 and inner.t1 <= outer.t1
    assert outer.attrs == {"round": 3}


def test_the_ring_is_bounded():
    tracer = Tracer(max_spans=64, exporting=False)
    for i in range(640):
        with tracer.span("s", i=i):
            pass
    spans = tracer.finished_spans()
    assert len(spans) == 64 and tracer._dropped == 576
    assert [s.attrs["i"] for s in spans] == list(range(576, 640))


def test_record_hangs_a_finished_span_under_the_open_one(recorder):
    with recorder.span("bucket-chunk", edge=4) as parent:
        recorder.record("jax.trace", 0.25, fun="inner")
        recorder.record("jax.trace", 0.5, absorb=("jax.trace",),
                        fun="outer")
        recorder.record("jax.lower", 0.125, absorb=("jax.lower",))
    trace, lower, _ = recorder.finished_spans()
    assert trace.attrs == {"fun": "outer", "nested": 1}
    assert trace.parent_id == lower.parent_id == parent.span_id
    assert trace.t1 - trace.t0 == pytest.approx(0.5e6)
    assert "nested" not in lower.attrs  # another kind stays beside it


# -- (b) compile events under the span that paid them -----------------------

@pytest.fixture(scope="module")
def lm_rounds():
    """Two rounds of the toy LM from a fresh start-up, under a watcher:
    (api, spans, watcher's report, the start-up report)."""
    patch = pytest.MonkeyPatch()
    patch.setattr(tracing, "_startup", tracing._Startup())
    tracing.begin_startup()
    tracer = Tracer(max_spans=4096, exporting=False)
    prev = set_tracer(tracer)
    try:
        with watch_compiles() as watch:
            api = _lm_api()
            api.train_one_round()
            api.train_one_round()
        report = startup_report()
    finally:
        set_tracer(prev)
        patch.undo()
    return api, tracer.finished_spans(), watch, report


def test_every_compile_event_hangs_under_the_span_that_paid(lm_rounds):
    _, spans, watch, _ = lm_rounds
    by_id = {s.span_id: s for s in spans}
    build, = [s for s in spans if s.name == "build"]
    # what fired before the trainer was built is the caller's (the
    # example input made for the spec): the report's site "caller"
    events = [s for s in spans if s.name in JAX_SPANS and s.t0 >= build.t0]
    assert {s.name for s in events} >= {"jax.trace", "jax.lower",
                                        "jax.compile"}
    for e in events:
        assert by_id[e.parent_id].name in PAYERS, (e.name, e.attrs)
    for e in events:
        if e.name == "jax.compile":
            assert e.attrs["cache"] in ("hit", "miss", "none")
    # the watcher armed beside the tracer counted the same events
    compiles = [e for e in spans if e.name == "jax.compile"]
    assert len(compiles) == watch.total_compiles
    assert sum(e.t1 - e.t0 for e in compiles) / 1e6 == pytest.approx(
        watch.total_compile_seconds, rel=1e-3)


def test_each_first_seen_edge_traces_and_the_second_round_does_not(
        lm_rounds):
    _, spans, _, _ = lm_rounds
    rounds = sorted((s for s in spans if s.name == "round"),
                    key=lambda s: s.t0)
    assert len(rounds) == 2
    first, second = rounds
    chunks = [s for s in spans if s.name == "bucket-chunk"]
    traced = {by.attrs["edge"] for by in chunks for e in spans
              if e.name == "jax.trace" and e.parent_id == by.span_id}
    assert traced == {2, 4} == {c.attrs["edge"] for c in chunks}
    assert not [e for e in spans if e.name in JAX_SPANS
                and e.t0 >= second.t0]
    assert [e for e in spans if e.name in JAX_SPANS and e.t0 >= first.t0]


def test_the_exporting_level_gets_the_compile_events_too():
    import jax
    import jax.numpy as jnp

    tracer = Tracer()
    prev = set_tracer(tracer)
    try:
        with watch_compiles():
            with tracer.span("fold.add"):
                jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(5))
    finally:
        set_tracer(prev)
    spans = tracer.finished_spans()
    parent, = [s for s in spans if s.name == "fold.add"]
    events = [s for s in spans if s.name in JAX_SPANS]
    assert {"jax.trace", "jax.lower", "jax.compile"} <= {
        s.name for s in events}
    assert all(e.parent_id == parent.span_id
               and e.trace_id == parent.trace_id for e in events)


# -- (c) the start-up report ------------------------------------------------

def test_startup_report_has_its_keys_and_leaves_little_unattributed(
        lm_rounds):
    _, _, _, report = lm_rounds
    assert REPORT_KEYS <= set(report) and report["closed"] is True
    assert all(set(row) == SITE_KEYS for row in report["sites"])
    assert report["total_s"] > 0 and report["build_s"] > 0
    assert report["unattributed_s"] < 0.10 * report["total_s"]
    assert report["build_s"] >= report["init_state_s"] > 0
    # one start-up round (the second compiled nothing and closed it)
    row, = report["rounds"]
    assert row["round"] == 0 and REPORT_KEYS - {
        "total_s", "import_s", "build_s", "rounds"} <= set(row)
    assert row["wall_s"] >= row["trace_s"] > 0
    assert report["total_s"] >= report["build_s"] + row["wall_s"]
    json.dumps(report)  # a record of metrics.jsonl


def test_startup_report_names_both_bucket_edges(lm_rounds):
    _, _, _, report = lm_rounds
    chunk_sites = {(r["site"], r["edge"]) for r in report["sites"]
                   if r["site"] == "bucket-chunk"}
    assert chunk_sites == {("bucket-chunk", 2), ("bucket-chunk", 4)}
    for r in report["sites"]:
        if r["site"] == "bucket-chunk":
            assert r["trace_s"] > 0 and r["lower_s"] > 0
    assert {r["site"] for r in report["sites"]} <= PAYERS | {"caller"}


def test_startup_report_is_made_once(lm_rounds, recorder):
    api, _, _, report = lm_rounds
    # the fixture's start-up is open and fresh: nothing built under it
    open_report = startup_report()
    assert open_report["closed"] is False and open_report["rounds"] == []
    api.train_one_round()  # compiles nothing: closes it at its start
    closed = startup_report()
    assert closed["closed"] is True and closed is startup_report()
    assert closed["rounds"] == [] and closed["build_s"] == 0


def test_enable_pushes_the_startup_record(recorder):
    from fedml_tpu.observability import enable

    records = []
    with enable(metrics_logger=records.append):
        api = _lr_api()
        api.train_one_round()
    pushed = [r["startup"] for r in records if "startup" in r]
    assert len(pushed) == 1 and REPORT_KEYS <= set(pushed[0])
    assert pushed[0]["closed"] is False  # one round: it compiled


# -- (d) the stalled round --------------------------------------------------

def _stalls(caplog):
    return [json.loads(r.getMessage().split(" ", 1)[1])
            for r in caplog.records
            if r.levelno == logging.WARNING
            and r.getMessage().startswith("round_stall ")]


def _ten_rounds(api, slow_round=None, slow=None):
    for i in range(10):
        if i == slow_round:
            with slow():
                api.train_one_round()
        else:
            api.train_one_round()


def test_ten_clean_rounds_give_no_warning(recorder, caplog):
    api = _lr_api()
    with caplog.at_level(logging.WARNING):
        _ten_rounds(api)
    assert _stalls(caplog) == []
    assert startup_report()["closed"] is True


def test_a_sleep_in_the_feed_is_a_host_stall_that_names_pack(
        recorder, caplog, monkeypatch):
    import contextlib

    from fedml_tpu.parallel import packing

    real = packing.pack_schedule

    @contextlib.contextmanager
    def slow():
        calls = []

        def sleepy(*a, **k):
            if not calls:
                time.sleep(0.6)
            calls.append(1)
            return real(*a, **k)
        monkeypatch.setattr(packing, "pack_schedule", sleepy)
        yield
        monkeypatch.setattr(packing, "pack_schedule", real)

    registry = MetricsRegistry()
    prev = set_registry(registry)
    try:
        api = _lr_api()
        with caplog.at_level(logging.WARNING):
            _ten_rounds(api, slow_round=6, slow=slow)
    finally:
        set_registry(prev)
    stall, = _stalls(caplog)
    assert (stall["round"], stall["verdict"], stall["where"]) \
        == (6, "host", "pack")
    assert stall["excess_s"] == pytest.approx(0.6, abs=0.1)
    assert stall["wall_s"] - stall["median_s"] == pytest.approx(
        stall["excess_s"], abs=1e-3)
    here, usual = stall["spans"]["pack"]
    assert here - usual == pytest.approx(0.6, abs=0.1)
    assert len(stall["chunks"]) == 3 and stall["compile"] == []
    assert len(stall["gc_s"]) == 3 and min(stall["gc_s"]) >= 0
    assert 0 < stall["cpu_over_wall"] < 0.9  # the host slept
    assert registry.get("fed_round_stalls_total") == 1


def test_a_sleep_in_a_chunks_first_fetch_is_a_device_stall(
        recorder, caplog, monkeypatch):
    import contextlib

    import jax

    real = jax.tree.map

    @contextlib.contextmanager
    def slow():
        calls = []

        def sleepy(f, *a, **k):
            if f is np.asarray and not calls:  # fold_oldest's first fetch
                calls.append(1)
                time.sleep(0.6)
            return real(f, *a, **k)
        monkeypatch.setattr(jax.tree, "map", sleepy)
        yield
        monkeypatch.setattr(jax.tree, "map", real)

    api = _lr_api()
    with caplog.at_level(logging.WARNING):
        _ten_rounds(api, slow_round=5, slow=slow)
    stall, = _stalls(caplog)
    assert (stall["round"], stall["verdict"]) == (5, "device")
    here, usual = stall["device_s"]
    assert here - usual == pytest.approx(0.6, abs=0.1)
    assert max(blocked for _, blocked in stall["chunks"]) \
        == pytest.approx(0.6, abs=0.1)


def test_a_round_that_compiles_after_the_startup_names_its_events(
        recorder, caplog):
    import jax
    import jax.numpy as jnp

    api = _lr_api()
    with caplog.at_level(logging.WARNING):
        for i in range(6):
            api.train_one_round()
        real = api.runner.run_round

        def recompiling(*a, **k):  # a new program in the middle of a run
            time.sleep(0.3)
            jax.jit(lambda x: jnp.tanh(x) * 7.0)(jnp.ones(11))
            return real(*a, **k)
        api.runner.run_round = recompiling
        api.train_one_round()
    stall, = _stalls(caplog)
    assert stall["verdict"] == "host" and stall["where"] == "local-train"
    assert {"jax.trace", "jax.compile"} <= {e[0] for e in stall["compile"]}
    assert all(e[1] == "local-train" for e in stall["compile"])


# -- (e) everything off -----------------------------------------------------

def test_noop_tracer_records_nothing_and_reports_nothing(recorder, caplog):
    api = _lr_api()
    built = len(recorder.finished_spans())
    prev = set_tracer(NOOP_TRACER)
    try:
        with caplog.at_level(logging.INFO):
            for _ in range(3):
                api.train_one_round()
        assert startup_report() is None
    finally:
        set_tracer(prev)
    assert len(recorder.finished_spans()) == built
    assert not [r for r in caplog.records
                if r.getMessage().startswith(("round_stall", "startup "))]
    assert startup_report()["closed"] is False  # no round closed it


def test_round_log_without_a_round_is_silent():
    log = RoundLog()
    assert log.end(get_tracer(), object()) is None  # nothing began
    log.begin()
    assert log.end(NOOP_TRACER, NOOP_TRACER.span("round")) is None
