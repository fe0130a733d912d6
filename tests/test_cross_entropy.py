"""``ops/cross_entropy.py`` against the three lines it replaced in
``algorithms/specs.py``: ``jax.nn.log_softmax`` + ``take_along_axis`` +
``jnp.argmax``.

The tolerances are float32 rounding, and on THIS backend that depends on
the row's length: XLA:CPU runs the op's variadic reduction as one
sequential float32 sum, so the sum of 50,257 exponentials reads up to
6e-6 of itself away from ``jnp.sum``'s (which sums in vector lanes), and
every probability of the backward with it. A TPU tiles both reductions;
there the benchmark's ``correct`` holds the op (PERF.md, PR 32)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.cross_entropy import softmax_cross_entropy_with_stats

VOCABS = [10, 129, 50257, 16032]
LEAD = {2: (6,), 3: (2, 5), 4: (2, 2, 3)}


def value_rtol(vocab):
    return 1e-6 if vocab < 1024 else 2e-5


def gradient_rtol(vocab):
    return 1e-5 if vocab < 1024 else 3e-4


def three_lines(logits, targets):
    logp = jax.nn.log_softmax(logits)
    ll = jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    return ll, jnp.argmax(logits, axis=-1)


def draw(vocab, lead, seed=0, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed + vocab), 3)
    logits = (3.0 * jax.random.normal(k1, lead + (vocab,))).astype(dtype)
    targets = jax.random.randint(k2, lead, 0, vocab)
    weights = (jax.random.uniform(k3, lead) > 0.3).astype(jnp.float32)
    return logits, targets, weights


def masked_mean(fn, targets, weights):
    return lambda logits: jnp.sum(-fn(logits, targets)[0] * weights) \
        / jnp.maximum(jnp.sum(weights), 1.0)


def assert_same_gradient(got, want):
    """Equal to float32 rounding: an element is ``(onehot - p) * g`` here
    and ``onehot * g - p * g`` there, with ``p`` an ``exp`` of a sum
    associated otherwise, so elements differ in their last digits (and
    by the sequential sum's share on long rows)."""
    np.testing.assert_allclose(
        got, want, rtol=gradient_rtol(want.shape[-1]),
        atol=1e-6 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("rank", sorted(LEAD))
@pytest.mark.parametrize("vocab", VOCABS)
def test_value_and_argmax_are_the_three_lines(vocab, rank):
    logits, targets, _ = draw(vocab, LEAD[rank])
    ll, pred = softmax_cross_entropy_with_stats(logits, targets)
    want_ll, want_pred = three_lines(logits, targets)
    assert ll.shape == pred.shape == LEAD[rank] and pred.dtype == jnp.int32
    np.testing.assert_allclose(ll, want_ll, rtol=value_rtol(vocab))
    np.testing.assert_array_equal(pred, want_pred)


@pytest.mark.parametrize("rank", sorted(LEAD))
@pytest.mark.parametrize("vocab", VOCABS)
def test_gradient_of_a_masked_mean(vocab, rank):
    logits, targets, weights = draw(vocab, LEAD[rank], seed=1)
    got = jax.grad(masked_mean(softmax_cross_entropy_with_stats, targets,
                               weights))(logits)
    want = jax.grad(masked_mean(three_lines, targets, weights))(logits)
    assert got.dtype == logits.dtype
    assert_same_gradient(got, want)
    # a masked row takes no gradient at all
    assert not np.any(np.asarray(got)[np.asarray(weights) == 0])


@pytest.mark.parametrize("vocab", VOCABS)
def test_under_vmap_as_the_lane_axis_has_it(vocab):
    logits, targets, weights = draw(vocab, (3, 2, 4), seed=2)
    ll, pred = jax.vmap(softmax_cross_entropy_with_stats)(logits, targets)
    want_ll, want_pred = three_lines(logits, targets)
    np.testing.assert_allclose(ll, want_ll, rtol=value_rtol(vocab))
    np.testing.assert_array_equal(pred, want_pred)
    lane = lambda fn: jax.vmap(lambda x, y, w: jax.grad(
        masked_mean(fn, y, w))(x))
    assert_same_gradient(
        lane(softmax_cross_entropy_with_stats)(logits, targets, weights),
        lane(three_lines)(logits, targets, weights))


@pytest.mark.parametrize("vocab", VOCABS)
def test_inside_a_fori_loop_as_the_trip_loop_has_it(vocab):
    """Three SGD steps on the logits themselves, the loss and the
    accuracy summed in the carry."""
    logits, targets, weights = draw(vocab, (2, 3), seed=3)

    def run(fn):
        def body(_, carry):
            x, total, correct = carry
            loss, grad = jax.value_and_grad(
                masked_mean(fn, targets, weights))(x)
            correct += jnp.sum(fn(x, targets)[1] == targets)
            return x - 0.5 * grad, total + loss, correct
        return jax.jit(lambda x: jax.lax.fori_loop(
            0, 3, body, (x, 0.0, 0)))(logits)

    got, want = run(softmax_cross_entropy_with_stats), run(three_lines)
    np.testing.assert_allclose(got[0], want[0], rtol=gradient_rtol(vocab),
                               atol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=value_rtol(vocab))
    assert int(got[2]) == int(want[2])


@pytest.mark.parametrize("vocab", VOCABS)
def test_bf16_logits_cast_by_the_caller(vocab):
    """What the specs do with a bf16 model's logits: the cast is the
    caller's, the cotangent comes back in the model's dtype."""
    logits, targets, weights = draw(vocab, (2, 4), seed=4,
                                    dtype=jnp.bfloat16)
    up = lambda fn: lambda x, y: fn(x.astype(jnp.float32), y)
    ll, pred = up(softmax_cross_entropy_with_stats)(logits, targets)
    want_ll, want_pred = up(three_lines)(logits, targets)
    assert ll.dtype == jnp.float32
    np.testing.assert_allclose(ll, want_ll, rtol=value_rtol(vocab))
    # bf16 values tie often: the first index, as argmax on the bf16 array
    np.testing.assert_array_equal(pred, want_pred)
    np.testing.assert_array_equal(pred, jnp.argmax(logits, axis=-1))
    got = jax.grad(masked_mean(up(softmax_cross_entropy_with_stats),
                               targets, weights))(logits)
    want = jax.grad(masked_mean(up(three_lines), targets, weights))(logits)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=2e-2,
                               atol=1e-6)


@pytest.mark.parametrize("vocab", VOCABS)
def test_a_row_of_equal_logits(vocab):
    logits = jnp.full((2, vocab), 1.5)
    targets = jnp.array([0, vocab - 1])
    ll, pred = softmax_cross_entropy_with_stats(logits, targets)
    np.testing.assert_allclose(ll, -np.log(vocab), rtol=value_rtol(vocab))
    np.testing.assert_array_equal(pred, [0, 0])   # ties: the first index
    grad = jax.grad(lambda x: jnp.sum(
        softmax_cross_entropy_with_stats(x, targets)[0]))(logits)
    want = jax.nn.one_hot(targets, vocab) - 1.0 / vocab
    np.testing.assert_allclose(grad, want, rtol=gradient_rtol(vocab),
                               atol=1e-9)


@pytest.mark.parametrize("vocab", VOCABS)
def test_ties_go_to_the_first_index(vocab):
    logits, targets, _ = draw(vocab, (5,), seed=5)
    top = jnp.max(logits) + 1.0
    logits = logits.at[:, vocab - 1].set(top).at[:, vocab // 2].set(top) \
        .at[2:, 3].set(top)
    pred = softmax_cross_entropy_with_stats(logits, targets)[1]
    np.testing.assert_array_equal(pred, [vocab // 2] * 2 + [3] * 3)
    np.testing.assert_array_equal(pred, jnp.argmax(logits, axis=-1))


@pytest.mark.parametrize("at_target", [False, True])
@pytest.mark.parametrize("vocab", VOCABS)
def test_a_row_with_one_minus_inf(vocab, at_target):
    logits, targets, _ = draw(vocab, (3,), seed=6)
    where = targets if at_target else (targets + 1) % vocab
    logits = logits.at[jnp.arange(3), where].set(-jnp.inf)
    ll, pred = softmax_cross_entropy_with_stats(logits, targets)
    want_ll, want_pred = three_lines(logits, targets)
    np.testing.assert_allclose(ll, want_ll, rtol=value_rtol(vocab))
    assert bool(jnp.all(jnp.isinf(ll))) == at_target
    np.testing.assert_array_equal(pred, want_pred)
    grad = jax.grad(lambda x: jnp.sum(
        softmax_cross_entropy_with_stats(x, targets)[0]))(logits)
    want = jax.grad(lambda x: jnp.sum(three_lines(x, targets)[0]))(logits)
    assert bool(jnp.all(jnp.isfinite(grad)))
    assert_same_gradient(grad, want)


def test_targets_take_no_cotangent_and_the_argmax_none():
    logits, targets, _ = draw(129, (4,), seed=7)
    (ll, pred), vjp = jax.vjp(softmax_cross_entropy_with_stats, logits,
                              targets)
    d_logits, d_targets = vjp((jnp.ones_like(ll),
                               np.zeros(pred.shape, jax.dtypes.float0)))
    assert d_logits.shape == logits.shape
    assert d_targets is None or d_targets.dtype == jax.dtypes.float0
    # each row's gradient sums to zero: onehot less a distribution
    np.testing.assert_allclose(jnp.sum(d_logits, axis=-1), 0.0, atol=1e-6)


def test_a_target_outside_the_vocabulary_hits_no_column():
    """``take_along_axis`` filled such a row with NaN; here its ``x_y``
    is 0 and the callers' masks drop the row as they did."""
    logits, _, _ = draw(10, (2,), seed=8)
    ll, _ = softmax_cross_entropy_with_stats(logits, jnp.array([255, -1]))
    np.testing.assert_allclose(ll, -jax.nn.logsumexp(logits, axis=-1),
                               rtol=1e-6)


def test_the_specs_hold_the_three_lines_nowhere():
    import inspect

    from fedml_tpu.algorithms import specs

    source = inspect.getsource(specs)
    for gone in ("log_softmax(", "take_along_axis(", "argmax("):
        assert gone not in source
    assert source.count("softmax_cross_entropy_with_stats(") == 4
