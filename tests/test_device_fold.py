"""The bucketed stream's device fold (ISSUE 28): a two-word float32
accumulator and a corrected quotient, held to the canonical float64 fold
of the same entries -- within one float32 ulp everywhere and equal on all
but 1e-5 of the elements, whatever the number of chunks -- by the
programs the runner dispatches. A one-word float32 sum is shown to fail
the same assertion."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import models
from fedml_tpu.algorithms.specs import make_classification_spec
from fedml_tpu.parallel.engine import BucketedStreamRunner, ClientUpdateConfig
from fedml_tpu.program.aggregation import (float32_ulps, fold_entries_fp64,
                                           split_total)


@pytest.fixture(scope="module")
def runner():
    """Any runner: its fold programs know nothing of its model."""
    spec = make_classification_spec(
        models.LogisticRegression(num_classes=4, apply_sigmoid=False),
        jnp.zeros((1, 6)))
    return BucketedStreamRunner(spec, ClientUpdateConfig(lr=0.1),
                                client_chunk=2, batch_size=4, edges=(8,))


def assert_within_the_contract(got, want):
    """Both ``{name: float32 array}``: at most one ulp apart everywhere,
    equal on all but 1e-5 of the elements (of the payload as a whole)."""
    differing = elements = 0
    for k in want:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        assert g.dtype == np.float32 and g.shape == w.shape, k
        assert np.isfinite(g).all(), k
        d = float32_ulps(g, w)
        assert d.max(initial=0) <= 1, (k, int(d.max()))
        differing += int((d > 0).sum())
        elements += d.size
    assert differing <= math.ceil(1e-5 * elements), (differing, elements)


def _entries(kind, n, size, weights, seed):
    """``n`` pre-weighted payload sums as the stream's chunks hand them
    over (``scale`` 1.0), adversarial by ``kind``."""
    rng = np.random.default_rng(seed)
    # one magnitude per element, spread over 2^-20 .. 2^20
    spread = np.exp2(rng.integers(-20, 21, size)).astype(np.float32)
    out, prev = [], None
    for key in range(n):
        w = (float(rng.integers(1, 50)) if weights == "integers"
             else float(np.float32(rng.uniform(0.3, 50.0))))
        x = rng.standard_normal(size).astype(np.float32)
        if kind == "spread":
            x *= spread
        elif kind == "cancelling" and key % 2:
            # nearly the addend before, negated: the partial sums fall
            # three orders under the addends and rise again
            x = (-prev * np.float32(1.0 - 1e-3)
                 + x * np.float32(1e-3)).astype(np.float32)
        prev = x
        payload = {"w": x * np.float32(w),
                   "mean": np.float32(rng.standard_normal() * w)}  # 0-d
        out.append((key, w, payload, 1.0))
    return out


def device_fold(runner, entries, dtypes=None):
    """The entries through the runner's three programs, as ``run_round``
    dispatches them: chunk 0's sum is the high word, the first add makes
    the low one, the quotient divides by the two-word total."""
    hi = lo = None
    total = 0.0
    for _key, weight, payload, _scale in entries:
        total += weight
        p = jax.tree.map(jnp.asarray, payload)
        if hi is None:
            hi = p
        elif lo is None:
            hi, lo = runner._fold_first(hi, p)
        else:
            hi, lo = runner._fold_next(hi, lo, p)
    if dtypes is None:
        dtypes = jax.tree.map(lambda a: jnp.zeros((), a.dtype), hi)
    avg = runner._fold_quotient(hi, lo, *split_total(total), dtypes)
    return jax.tree.map(np.asarray, avg)


@pytest.mark.parametrize("weights", ["integers", "fractions"])
@pytest.mark.parametrize("kind", ["spread", "cancelling"])
@pytest.mark.parametrize("n,size", [(1, 4096), (2, 4096), (6, 1 << 20),
                                    (64, 1 << 16), (1000, 1 << 15)])
def test_device_fold_is_the_float64_fold_to_an_ulp(runner, n, size, kind,
                                                   weights):
    entries = _entries(kind, n, size, weights, seed=n)
    want, _ = fold_entries_fp64(entries)
    assert_within_the_contract(device_fold(runner, entries), want)


def test_one_word_float32_sum_fails_the_same_assertion():
    """The teeth: 1,000 chunks summed in plain float32 and divided in
    float32 are NOT within the contract, so the test above cannot pass
    by the data being easy."""
    entries = _entries("spread", 1000, 1 << 15, "fractions", seed=1000)
    want, total = fold_entries_fp64(entries)
    acc = None
    for _key, _w, payload, _scale in entries:
        acc = dict(payload) if acc is None else \
            {k: acc[k] + payload[k] for k in acc}
    plain = {k: np.asarray(acc[k] / np.float32(total), np.float32)
             for k in acc}
    with pytest.raises(AssertionError):
        assert_within_the_contract(plain, want)


def test_quotient_casts_through_the_payload_dtype_template(runner):
    """A bfloat16-templated leaf leaves the quotient as bfloat16: the
    float32 average rounded once more, on the device, as ``apply_avg``
    rounds the host fold's."""
    entries = [(k, w, {"w": p["w"][:4096], "mean": p["mean"]}, s)
               for k, w, p, s in _entries("spread", 6, 4096, "fractions", 3)]
    dtypes = {"w": jnp.zeros((), jnp.bfloat16),
              "mean": jnp.zeros((), jnp.float32)}
    got = device_fold(runner, entries, dtypes)
    want, _ = fold_entries_fp64(entries)
    assert got["w"].dtype == jnp.bfloat16 and got["mean"].dtype == np.float32
    cast = np.asarray(jnp.asarray(want["w"], jnp.bfloat16))
    assert (got["w"] == cast).mean() >= 1 - 1e-3  # ties of the 2nd rounding
    np.testing.assert_allclose(got["w"].astype(np.float32),
                               cast.astype(np.float32), rtol=2 ** -7)
    assert_within_the_contract({"mean": got["mean"]},
                               {"mean": want["mean"]})


def test_total_weight_beyond_float32_divides_exactly(runner):
    """A total that float32 cannot hold (2^25 + 1) divides as the
    float64 fold divides it: the divisor's second word is used."""
    rng = np.random.default_rng(8)
    entries = [(0, float(2 ** 25), {"w": rng.standard_normal(1 << 16)
                                    .astype(np.float32) * 1e6}, 1.0),
               (1, 1.0, {"w": rng.standard_normal(1 << 16)
                         .astype(np.float32)}, 1.0)]
    hi, lo = split_total(2.0 ** 25 + 1.0)
    assert (float(hi), float(lo)) == (2.0 ** 25, 1.0)
    want, _ = fold_entries_fp64(entries)
    assert_within_the_contract(device_fold(runner, entries), want)
    # one word of the total is off by a float32 half-ulp: measurably worse
    one_word = np.asarray(
        (np.asarray(entries[0][2]["w"], np.float64)
         + entries[1][2]["w"]) / float(hi), np.float32)
    assert (one_word != want["w"]).mean() > 1e-3


@pytest.mark.parametrize("hi,lo,want", [
    ("0x1.82e9b0p-8", "-0x1.ep-33", "0x1.9cb500p-14"),
    ("0x1.66a6c0p-9", "-0x1.6p-34", "0x1.7e8fbcp-15"),
    ("-0x1.4f3e9ep-8", "0x1.ep-33", "-0x1.659820p-14"),
])
def test_exact_ties_go_to_even_where_division_is_correctly_rounded(
        runner, hi, lo, want):
    """Three accumulator words of a real round (411 M parameters, total
    weight 60; PERF.md section 6, PR 28) whose quotient lies EXACTLY
    between two floats: float64 rounds such a tie to even, and so does
    the quotient program on a backend whose float32 division is
    correctly rounded (this CPU). A TPU v5e's is not, and reads the
    other neighbour there: one ulp, inside the contract."""
    hi, lo, want = (np.float32(float.fromhex(x)) for x in (hi, lo, want))
    exact = (float(hi) + float(lo)) / 60.0
    below = float(np.nextafter(want, np.float32(-np.inf)))
    above = float(np.nextafter(want, np.float32(np.inf)))
    assert exact in ((float(want) + below) / 2, (float(want) + above) / 2)
    assert np.float32(exact) == want  # float64's answer: half to even
    got = runner._fold_quotient(
        {"w": jnp.full((8,), hi)}, {"w": jnp.full((8,), lo)},
        *split_total(60.0), {"w": jnp.zeros((), jnp.float32)})["w"]
    assert (np.asarray(got) == want).all()


def test_fold_programs_are_their_own_programs(runner):
    """The fold is not part of the client-update program: the metrics
    read ``jit_chunk_fn``'s device time by its name as the client
    update's alone."""
    names = {f.__name__ for f in (runner._fold_first, runner._fold_next,
                                  runner._fold_quotient)}
    assert names == {"fold_first", "fold_next", "fold_quotient"}
    assert runner._chunk_fn.__name__ == "chunk_fn"
