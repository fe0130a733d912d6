"""The in-place float64 fold (ISSUE 26): ``Float64Accumulator`` gives,
byte for byte, what the plain out-of-place formula gives through
``fold_entries_fp64``, and writes to no payload. The bucketed stream's
synchronous fold left it for the device (ISSUE 28): its runner-level
tests are at the end."""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from stream_helpers import stream_round

from fedml_tpu import models
from fedml_tpu.algorithms.specs import make_classification_spec
from fedml_tpu.observability import Tracer, set_tracer
from fedml_tpu.parallel.engine import BucketedStreamRunner, ClientUpdateConfig
from fedml_tpu.parallel.packing import _steps_for, parse_bucket_edges
from fedml_tpu.program.aggregation import (Float64Accumulator,
                                           fold_entries_fp64)


def plain_fold(entries):
    """The formula the fold is held to, out of place, one new array a
    step: ``float64(p) * scale``, ``acc + c``, ``(acc / total)`` cast."""
    acc, total = None, 0.0
    for _key, weight, payload, scale in sorted(entries, key=lambda e: e[0]):
        total += float(weight)
        c = {k: np.asarray(v, np.float64) * float(scale)
             for k, v in payload.items()}
        acc = c if acc is None else {k: acc[k] + c[k] for k in acc}
    return {k: (acc[k] / total).astype(np.float32) for k in acc}, total


def _payload(kind, rng):
    wide = rng.standard_normal((7, 5)) * 1e3
    if kind == "float32":
        return {"w": wide.astype(np.float32),
                "b": rng.standard_normal(5).astype(np.float32)}
    if kind == "bfloat16":
        return {"w": wide.astype(ml_dtypes.bfloat16)}
    if kind == "integer":
        return {"steps": rng.integers(-9, 9, (3, 4), dtype=np.int64),
                "seen": rng.integers(0, 2 ** 40, 6, dtype=np.uint64),
                "mask": rng.integers(0, 2, 4).astype(bool)}
    if kind == "zero_d":
        return {"count": np.asarray(rng.integers(1, 99), np.int32),
                "loss": np.asarray(rng.standard_normal(), np.float32)}
    if kind == "longdouble":  # not promotable to float64: converted first
        return {"w": wide.astype(np.longdouble)}
    assert kind == "mixed"
    return {"w": wide.astype(np.float32),
            "h": rng.standard_normal(3).astype(np.float16),
            "d": rng.standard_normal((2, 2)),
            "n": np.asarray(rng.integers(1, 99), np.int64)}


def _entries(kind, scales, n, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for key in range(n):
        weight = float(rng.integers(1, 50))
        scale = 1.0 if scales == "ones" else weight * 0.37
        out.append((key, weight, _payload(kind, rng), scale))
    return out


def _assert_same_bytes(got, want):
    assert sorted(got) == sorted(want)
    for k in want:
        g = np.asarray(got[k])
        assert g.dtype == np.float32 and g.shape == want[k].shape, k
        assert g.tobytes() == np.asarray(want[k]).tobytes(), k


@pytest.mark.parametrize("order", ["sorted", "shuffled"])
@pytest.mark.parametrize("n", [1, 6])
@pytest.mark.parametrize("scales", ["ones", "weights"])
@pytest.mark.parametrize("kind", ["float32", "bfloat16", "integer",
                                  "zero_d", "longdouble", "mixed"])
def test_fold_is_the_plain_formula_byte_for_byte(kind, scales, n, order):
    entries = _entries(kind, scales, n)
    want, want_total = plain_fold(entries)
    if order == "shuffled":
        np.random.default_rng(5).shuffle(entries)
    got, total = fold_entries_fp64(entries)
    assert total == want_total
    _assert_same_bytes(got, want)


def _frozen(kind):
    """Payload leaves the fold must not write to, by where they live."""
    rng = np.random.default_rng(11)
    x = rng.standard_normal((6, 4))
    if kind == "jax_host_copy":  # the runtime's cached read-only copy
        leaf = np.asarray(jnp.asarray(x, jnp.float32))
        assert not leaf.flags.writeable
        return leaf
    if kind == "jax_array":
        return jnp.asarray(x, jnp.float32)
    if kind == "float64":  # np.asarray(x, np.float64) is x itself
        return x.copy()
    assert kind == "float32"
    return x.astype(np.float32)


@pytest.mark.parametrize("scale", [1.0, 0.25])
@pytest.mark.parametrize("kind", ["jax_host_copy", "jax_array", "float64",
                                  "float32"])
def test_fold_leaves_every_payload_as_it_was(kind, scale):
    payloads = [{"w": _frozen(kind)} for _ in range(3)]
    before = [np.asarray(p["w"]).tobytes() for p in payloads]
    entries = [(i, 2.0, p, scale) for i, p in enumerate(payloads)]
    got, _ = fold_entries_fp64(entries)
    want, _ = plain_fold(
        [(i, 2.0, {"w": np.asarray(p["w"])}, scale)
         for i, p in enumerate(payloads)])
    _assert_same_bytes(got, want)
    assert [np.asarray(p["w"]).tobytes() for p in payloads] == before
    for p in payloads:
        assert not np.shares_memory(np.asarray(got["w"]),
                                    np.asarray(p["w"]))


@pytest.mark.parametrize("scales", ["ones", "weights"])
def test_standing_accumulator_keeps_nothing_of_the_fold_before(scales):
    first = _entries("mixed", scales, 4, seed=1)
    second = _entries("mixed", scales, 3, seed=2)
    acc = Float64Accumulator()
    results, reused = [], []
    for entries in (first, second):
        _, _, payload, scale = entries[0]
        reused.append(acc.start(payload, scale))
        for _, _, payload, scale in entries[1:]:
            acc.add(payload, scale)
        results.append(acc.finish(sum(e[1] for e in entries)))
    assert reused == [False, True]
    for entries, got in zip((first, second), results):
        _assert_same_bytes(got, plain_fold(entries)[0])
    # the averages handed out are fresh arrays, not views of one buffer
    assert not np.shares_memory(results[0]["w"], results[1]["w"])


def test_accumulator_reallocates_when_the_shapes_change():
    acc = Float64Accumulator()
    rng = np.random.default_rng(3)
    small = {"w": rng.standard_normal((2, 3)).astype(np.float32)}
    large = {"w": rng.standard_normal((4, 3)).astype(np.float32)}
    other = {"w": large["w"], "b": np.zeros(3, np.float32)}
    assert acc.start(small) is False and acc.nbytes == 2 * 3 * 8
    acc.finish(1.0)
    assert acc.start(large) is False and acc.nbytes == 4 * 3 * 8
    acc.add(large, 0.5)
    got = acc.finish(3.0)
    _assert_same_bytes(
        got, plain_fold([(0, 1.0, large, 1.0), (1, 2.0, large, 0.5)])[0])
    assert acc.start(large) is True
    assert acc.start(other) is False and acc.arrays == 2  # another tree


@pytest.mark.parametrize("call", ["add", "finish"])
def test_accumulator_refuses_a_fold_that_was_not_started(call):
    acc = Float64Accumulator()
    args = ({"w": np.ones(2, np.float32)},) if call == "add" else (1.0,)
    with pytest.raises(ValueError, match="not started"):
        getattr(acc, call)(*args)
    acc.start({"w": np.ones(2, np.float32)})
    acc.finish(1.0)
    with pytest.raises(ValueError, match="not started"):
        getattr(acc, call)(*args)


# ---------------------------------------------------------------------------
# the bucketed stream's accumulator: on the device since ISSUE 28, made
# anew every round (two float32 words; tests/test_device_fold.py holds
# its arithmetic to the float64 fold), so a runner carries nothing of one
# round's fold into the next
# ---------------------------------------------------------------------------
CLIENTS, CHUNK, BATCH = 11, 3, 4


def _spec(dim):
    return make_classification_spec(
        models.LogisticRegression(num_classes=4, apply_sigmoid=False),
        jnp.zeros((1, dim)))


def _datasets(dim, seed):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 40, CLIENTS)
    return [{"x": rng.standard_normal((n, dim)).astype(np.float32),
             "y": rng.integers(0, 4, n).astype(np.int32)} for n in sizes]


def _runner(dim=6):
    return BucketedStreamRunner(
        _spec(dim), ClientUpdateConfig(lr=0.1), client_chunk=CHUNK,
        batch_size=BATCH, epochs=1,
        edges=parse_bucket_edges("geometric", _steps_for(40, BATCH, 1)))


def _round(runner, gs, r, dim=6, aggregator=None):
    """Round ``r`` from ``gs`` under a real tracer; returns the new
    state on the host and where ``fold.add`` said the sums were added."""
    tracer = Tracer()
    prev = set_tracer(tracer)
    try:
        gs, _, info = stream_round(
            runner, jax.tree.map(jnp.copy, gs), (), _datasets(dim, seed=r),
            jax.random.PRNGKey(r), data_rng=np.random.default_rng(r),
            aggregator=aggregator)
    finally:
        set_tracer(prev)
    on = {s.attrs["on"] for s in tracer.finished_spans()
          if s.name == "fold.add"}
    assert on == {info["fold"]}
    return jax.tree.map(np.asarray, gs), info["fold"]


def _assert_states_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes()


def _host_fold_round(gs, r, dim=6):
    """The same round through the canonical host fold: an unbounded
    buffer with decay 0 flushes once, through ``fold_entries_fp64``."""
    from fedml_tpu.resilience.async_agg import (AsyncAggPolicy,
                                                BufferedAggregator)

    agg = BufferedAggregator(
        AsyncAggPolicy(buffer_k=10 ** 9, staleness_decay=0.0))
    got, fold = _round(_runner(dim), gs, r, dim, aggregator=agg)
    assert fold == "host"
    return got


def _assert_within_an_ulp(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype == np.float32
        np.testing.assert_array_max_ulp(g, w, maxulp=1)


def test_two_rounds_of_one_runner_equal_a_fresh_runner_each():
    gs0 = _spec(6).init_fn(jax.random.PRNGKey(1))
    one = _runner()
    gs1, fold1 = _round(one, gs0, 1)
    gs2, fold2 = _round(one, gs1, 2)
    assert (fold1, fold2) == ("device", "device")
    assert not hasattr(one, "_sync_acc")  # nothing stands between rounds
    fresh1, _ = _round(_runner(), gs0, 1)
    fresh2, _ = _round(_runner(), gs1, 2)
    _assert_states_equal(gs1, fresh1)
    _assert_states_equal(gs2, fresh2)
    # and the same round again from the same runner: the same bytes
    again2, _ = _round(one, gs1, 2)
    _assert_states_equal(gs2, again2)
    assert any((a != b).any() for a, b in zip(jax.tree.leaves(gs1),
                                              jax.tree.leaves(gs2)))
    _assert_within_an_ulp(gs2, _host_fold_round(gs1, 2))


def test_round_after_the_payload_changed_shape_reallocates_and_is_right():
    runner = _runner()
    _round(runner, _spec(6).init_fn(jax.random.PRNGKey(1)), 1)
    wide0 = _spec(9).init_fn(jax.random.PRNGKey(2))
    got, fold = _round(runner, wide0, 2, dim=9)
    assert fold == "device"
    assert [a.shape for a in jax.tree.leaves(got)] \
        == [a.shape for a in jax.tree.leaves(wide0)]
    want, _ = _round(_runner(dim=9), wide0, 2, dim=9)
    _assert_states_equal(got, want)
    _assert_within_an_ulp(got, _host_fold_round(wide0, 2, dim=9))
    again, _ = _round(runner, got, 3, dim=9)
    _assert_states_equal(again, _round(_runner(dim=9), got, 3, dim=9)[0])
