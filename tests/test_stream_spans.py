"""Spans inside the bucketed round (ISSUE 25): the fold's
steps, the feed and the wait each have a span where the work happens, a
real ``Tracer``'s spans stand on the profiler's timeline too, and the
no-op tracer leaves the round bitwise what it was. Since ISSUE 36 the
round's preamble is a leaf (``prepare``) and the default tracer records
the same tree less what only a traced round does."""

import os
import subprocess
import sys
import types

import numpy as np
import pytest

from fedml_tpu.observability import NOOP_TRACER, Tracer, set_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLIENTS, CHUNK = 10, 4
CHUNKS = -(-CLIENTS // CHUNK)
#: span -> how many a synchronous round of CHUNKS chunks holds: the first
#: chunk's payload sum becomes the device accumulator's high word (no
#: span: nothing is done), every later chunk's is added to it by a
#: program of its own (``fold.add``, the dispatch)
SYNC_SPANS = {"prepare": 1,
              "pack": CHUNKS, "h2d": CHUNKS, "bucket-chunk": CHUNKS,
              "fold.wait": CHUNKS, "fold.d2h": CHUNKS,
              "fold.add": CHUNKS - 1,
              "fold.finalize": 1, "fold.apply": 1}


def _api(**extra):
    import jax.numpy as jnp

    import bench
    from fedml_tpu import models
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.algorithms.specs import make_classification_spec

    spec = make_classification_spec(
        models.LogisticRegression(num_classes=4, apply_sigmoid=False),
        jnp.zeros((1, 16)))
    args = types.SimpleNamespace(
        client_num_in_total=CLIENTS, client_num_per_round=CLIENTS,
        comm_round=10 ** 9, epochs=1, batch_size=8, lr=0.05, wd=0.0,
        client_optimizer="sgd", frequency_of_the_test=10 ** 9, seed=0,
        client_chunk=CHUNK, bucket_edges="geometric", device_resident="0",
        **extra)
    return FedAvgAPI(bench._ragged_lr_clients(CLIENTS), spec, args)


def _round(tracer=None, **extra):
    """One round of a fresh trainer; returns (api, metrics)."""
    api = _api(**extra)
    prev = set_tracer(tracer)
    try:
        metrics = api.train_one_round()
    finally:
        set_tracer(prev)
    return api, metrics


@pytest.fixture(scope="module")
def traced():
    """One synchronous round under a real tracer."""
    tracer = Tracer()
    api, metrics = _round(tracer)
    return api, metrics, tracer.finished_spans()


def _by_name(spans):
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    return by


def _ancestors(span, by_id):
    while span.parent_id in by_id:
        span = by_id[span.parent_id]
        yield span.name


def test_round_yields_the_stream_spans_under_local_train(traced):
    _, _, spans = traced
    by_id = {s.span_id: s for s in spans}
    for name, count in SYNC_SPANS.items():
        mine = [s for s in spans if s.name == name]
        assert len(mine) == count, name
        for s in mine:
            assert "local-train" in _ancestors(s, by_id), name
    waits = sorted(s.attrs["ordinal"] for s in spans
                   if s.name == "fold.wait")
    assert waits == list(range(CHUNKS))
    assert "fold.convert" not in {s.name for s in spans}


def test_attributes_say_where_the_payload_sums_were_combined(traced):
    _, _, spans = traced
    by = _by_name(spans)
    local, = by["local-train"]
    assert local.attrs["fold"] == "device"
    assert [s.attrs["on"] for s in by["fold.add"]] \
        == ["device"] * (CHUNKS - 1)
    # each add is dispatched right after its chunk's program, before the
    # next chunk is packed: the combine order is the ordinal order
    order = sorted(by["bucket-chunk"] + by["fold.add"] + by["pack"],
                   key=lambda s: s.t0)
    assert [s.name for s in order] == \
        ["pack", "bucket-chunk"] \
        + ["pack", "bucket-chunk", "fold.add"] * (CHUNKS - 1)


def test_span_attributes_say_what_moved(traced):
    api, _, spans = traced
    by = _by_name(spans)
    assert sum(s.attrs["clients"] for s in by["pack"]) == CLIENTS
    assert sum(s.attrs["rows"] for s in by["pack"]) \
        == sum(api.train_data_local_num_dict.values())
    for name in ("h2d", "fold.d2h"):
        assert all(s.attrs["bytes"] > 0 for s in by[name]), name
    assert not any("bytes" in s.attrs for s in by["fold.add"])


def test_byte_attributes_are_what_the_shapes_predict(traced):
    import jax

    api, _, spans = traced
    by = _by_name(spans)
    metrics = jax.tree.leaves(api._last_metrics)
    extras = 4 + sum(np.asarray(v).astype(np.float32).nbytes
                     for v in metrics)
    # the weight and the metric sums: no payload sum crosses to the host
    assert [(s.attrs["bytes"], s.attrs["arrays"])
            for s in by["fold.d2h"]] == [(extras, 1 + len(metrics))] * CHUNKS
    apply, = by["fold.apply"]  # and no average crosses back
    assert (apply.attrs["bytes"], apply.attrs["arrays"]) == (0, 0)
    assert all(s.attrs["arrays"] == 5 for s in by["h2d"])


def test_non_scalar_attributes_stay_out_of_the_annotation(tmp_path):
    import jax

    from benchmarks import trace_reader

    tracer = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench_round"):
            with tracer.span("listed", ranks=[1, 2], edge=8, note=None):
                pass
    finally:
        jax.profiler.stop_trace()
    span, = tracer.finished_spans()
    assert span.attrs == {"ranks": [1, 2], "edge": 8, "note": None}
    summary = trace_reader.read(trace_reader.find_xplane(str(tmp_path)))
    assert "listed" in {n for n, _, _ in summary.host}


def test_prepare_is_the_leaf_before_the_first_pack(traced):
    _, _, spans = traced
    by = _by_name(spans)
    prepare, = by["prepare"]
    local, = by["local-train"]
    assert prepare.parent_id == local.span_id
    assert prepare.attrs == {"clients": CLIENTS}
    first_pack = min(by["pack"], key=lambda s: s.t0)
    assert local.t0 <= prepare.t0 and prepare.t1 <= first_pack.t0
    # a leaf: no span opens inside it; the first round's compile events
    # (the key split's program) hang under it as the span that paid them
    inside = {s.name for s in spans if s.parent_id == prepare.span_id}
    assert inside <= {"jax.trace", "jax.lower", "jax.compile",
                      "jax.cache_load"}


def test_the_default_tracer_records_the_round_less_the_traced_work():
    """The recorder level: the same tree, but ``enabled`` is False, so no
    explicit wait (it lands in ``fold.d2h``) and no walk over leaves."""
    recorder = Tracer(max_spans=512, exporting=False)
    _round(recorder)
    spans = recorder.finished_spans()
    by = _by_name(spans)
    expect = dict(SYNC_SPANS, **{"fold.wait": 0})
    assert {n: len(by.get(n, ())) for n in expect} == expect
    assert not any("bytes" in s.attrs or "arrays" in s.attrs
                   for s in by["h2d"] + by["fold.d2h"])
    apply, = by["fold.apply"]
    assert 0 <= apply.attrs["blocked_s"] <= (apply.t1 - apply.t0) / 1e6
    by_id = {s.span_id: s for s in spans}
    for name in SYNC_SPANS:
        for s in by.get(name, ()):
            assert "local-train" in _ancestors(s, by_id), name


@pytest.mark.parametrize("off", [None, NOOP_TRACER],
                         ids=["recorder", "noop"])
def test_noop_and_real_tracer_rounds_are_bitwise_equal(traced, off):
    import jax

    api_on, m_on, _ = traced
    api_off, m_off = _round(off)
    on = jax.tree.leaves(jax.tree.map(np.asarray, api_on.global_state))
    off = jax.tree.leaves(jax.tree.map(np.asarray, api_off.global_state))
    assert len(on) == len(off)
    for a, b in zip(on, off):
        assert a.dtype == b.dtype and (a == b).all()
    drop = {"round_time_s"}
    assert {k: v for k, v in m_on.items() if k not in drop} \
        == {k: v for k, v in m_off.items() if k not in drop}


def test_buffered_path_folds_under_fold_add():
    import jax

    tracer = Tracer()
    api, _ = _round(tracer, async_agg=1, buffer_k=2, staleness_decay=0.5,
                    async_window=4)
    spans = tracer.finished_spans()
    by_id = {s.span_id: s for s in spans}
    names = [s.name for s in spans]
    assert names.count("fold.add") == CHUNKS
    assert all(s.attrs["on"] == "host" for s in spans
               if s.name == "fold.add")
    local, = [s for s in spans if s.name == "local-train"]
    assert local.attrs["fold"] == "host"
    assert "fold.finalize" not in names
    applies = [s for s in spans if s.name == "fold.apply"]
    assert applies and all(s.attrs["bytes"] > 0 for s in applies)
    payload = sum(a.nbytes for a in jax.tree.leaves(api.global_state))
    assert all(s.attrs["bytes"] > payload for s in spans
               if s.name == "fold.d2h")  # the host fold takes the payload
    folds = [s for s in spans if s.name == "buffer-fold"]
    assert len(folds) == CHUNKS
    assert all(by_id[s.parent_id].name == "fold.add" for s in folds)


def test_spans_are_host_events_of_the_profilers_trace(tmp_path):
    import jax

    from benchmarks import trace_reader

    api = _api()
    api.train_one_round()  # compile outside the traced round
    tracer = Tracer()
    prev = set_tracer(tracer)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench_round", round=0):
            api.train_one_round()
    finally:
        jax.profiler.stop_trace()
        set_tracer(prev)
    summary = trace_reader.read(trace_reader.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in summary.host]
    for name, count in SYNC_SPANS.items():
        assert names.count(name) == count, name
    assert names.count("round") == 1 and names.count("local-train") == 1
    # the profiler's clock and the tracer's agree on every span's length
    for name in ("local-train", "fold.finalize"):
        span = next(s for s in tracer.finished_spans() if s.name == name)
        start, end = next((a, b) for n, a, b in summary.host if n == name)
        assert end - start == pytest.approx((span.t1 - span.t0) / 1e6,
                                            abs=2e-3)


def test_detached_spans_stay_off_the_profilers_timeline(tmp_path):
    import jax

    from benchmarks import trace_reader

    tracer = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench_round"):
            detached = tracer.start_span("attempt", root=True)
            with tracer.span("managed", rank=3):
                pass
            detached.end()
    finally:
        jax.profiler.stop_trace()
    summary = trace_reader.read(trace_reader.find_xplane(str(tmp_path)))
    names = {n for n, _, _ in summary.host}
    assert "managed" in names and "attempt" not in names
    assert {s.name for s in tracer.finished_spans()} \
        == {"managed", "attempt"}


def test_observability_imports_and_traces_without_jax():
    """``tracing.py`` is stdlib-only at import and a span of a process
    that never imported jax is no annotation (``import jax`` is made to
    fail there, so nothing can bring it in behind the test's back)."""
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "import fedml_tpu.observability as obs\n"
        "from fedml_tpu.observability import flightrec, registry, tracing\n"
        "t = obs.Tracer()\n"
        "with t.span('round', round=1):\n"
        "    with t.span('fold.d2h'):\n"
        "        pass\n"
        "assert [s.name for s in t.finished_spans()] == ['fold.d2h', "
        "'round']\n"
        "assert sys.modules['jax'] is None\n"
        "assert not any(m.startswith('jax.') for m in sys.modules)\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_compile_watcher_reports_trace_lower_and_cache_load_seconds(
        tmp_path, restore_cache_config):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from fedml_tpu.observability.jaxmon import watch_compiles

    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()

    def fresh():  # a new function each time: traced and lowered again
        def body(x):
            return jnp.tanh(x @ x.T).sum() * 25.0
        return jax.jit(body)

    x = jnp.ones((32, 32))
    with watch_compiles() as cold:
        fresh()(x).block_until_ready()
    with watch_compiles() as warm:
        fresh()(x).block_until_ready()
    rc, rw = cold.report(), warm.report()
    for r in (rc, rw):
        assert r["compile/trace_seconds"] > 0
        assert r["compile/lower_seconds"] > 0
    assert rc["compile/cache_load_seconds"] == 0 and cold.cache_hits == 0
    assert warm.cache_hits >= 1
    assert 0 < rw["compile/cache_load_seconds"] <= rw["compile/total_seconds"]
    # what the benchmark's harness reads keeps its meaning: backend
    # compile seconds alone
    assert rc["compile/total_seconds"] == round(
        cold.total_compile_seconds, 4)


def test_flash_kernels_carry_their_names():
    import re

    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.pallas_attention import flash_attention

    x = jnp.zeros((1, 128, 1, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(x, x, x)
    assert set(re.findall(r"flash_\w+", str(jaxpr))) \
        == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
