"""The order in which a client's rows are fed, from the seed.

A federated round's result depends on which rows each local step sees,
so the benchmark states the order and the reference follows this
statement, not the program's tables. The trainer owns one
``numpy.random.default_rng(seed)``. The streamed path builds one schedule
per chunk of ``chunk`` clients, the cohort sorted by local step count
ascending (stable); every schedule draws ONE integer from the trainer's
generator and shuffles each of its clients' rows once per local epoch,
cutting the permutation into consecutive batches (the last may be short).
How that integer becomes permutations is the program's schedule
generator's rule, and it has two (``fedml_tpu/parallel/packing.py``):

- ``native``, the default wherever the C++ shim builds (it does on the
  chip's machine): client number ``c`` of the schedule gets its own
  xoshiro256** generator, seeded through splitmix64 from ``integer *
  0x9e3779b97f4a7c15 + c + 1``, and every epoch starts from the identity
  and takes a Fisher-Yates shuffle from the top down with Lemire's
  unbiased bounded draw;
- ``python``, the numpy fallback: one ``default_rng(integer)`` visits the
  clients in turn and draws ``permutation(n)`` per epoch.

The harness does not choose: it asks the program which one is in force
(``packing_backend()``) and the reference follows that rule, so the
default path is the one that is timed and compared.

A program change that feeds rows in another order is a change of the
result and fails ``correct``; it needs a benchmark PR that restates the
rule. ``benchmarks/tests`` pins both copies against the program's.
"""

from __future__ import annotations

import math

import numpy as np

BACKENDS = ("native", "python")
_MASK = 2 ** 64 - 1
_GOLDEN = 0x9e3779b97f4a7c15


def steps_of(n, batch, epochs):
    return max(1, math.ceil(n / batch)) * epochs


def _rotl(x, k):
    return ((x << k) | (x >> (64 - k))) & _MASK


class _Xoshiro:
    """xoshiro256** seeded through splitmix64, in 64-bit arithmetic."""

    def __init__(self, seed):
        z, self.s = seed & _MASK, []
        for _ in range(4):
            z = (z + _GOLDEN) & _MASK
            t = ((z ^ (z >> 30)) * 0xbf58476d1ce4e5b9) & _MASK
            t = ((t ^ (t >> 27)) * 0x94d049bb133111eb) & _MASK
            self.s.append(t ^ (t >> 31))

    def next(self):
        s = self.s
        r = (_rotl((s[1] * 5) & _MASK, 7) * 9) & _MASK
        t = (s[1] << 17) & _MASK
        s[2] ^= s[0]
        s[3] ^= s[1]
        s[1] ^= s[2]
        s[0] ^= s[3]
        s[2] ^= t
        s[3] = _rotl(s[3], 45)
        return r

    def bounded(self, n):
        """Lemire's unbiased draw from ``range(n)``."""
        m = self.next() * n
        if (m & _MASK) < n:
            t = (2 ** 64 - n) % n
            while (m & _MASK) < t:
                m = self.next() * n
        return m >> 64

    def permutation(self, n):
        order = list(range(n))
        for i in range(n, 1, -1):
            j = self.bounded(i)
            order[i - 1], order[j] = order[j], order[i - 1]
        return np.asarray(order, np.int64)


def _client_steps(rng, n, batch, epochs):
    steps = []
    for _ in range(epochs):
        order = rng.permutation(n)
        for b in range(max(1, math.ceil(n / batch))):
            steps.append(order[b * batch:(b + 1) * batch])
    return steps


def _schedule(owner, members, ns, batch, epochs, backend):
    drawn = int(owner.integers(0, 2 ** 63 - 1))
    if backend == "native":
        return {c: _client_steps(_Xoshiro(drawn * _GOLDEN + i + 1), ns[c],
                                 batch, epochs)
                for i, c in enumerate(members)}
    if backend != "python":
        raise ValueError(f"feed rule {backend!r}: feed.py has {BACKENDS}")
    rng = np.random.default_rng(drawn)
    return {c: _client_steps(rng, ns[c], batch, epochs) for c in members}


def streamed(ns, batch, epochs, chunk, seed, rounds, backend):
    """``feed[r][c]``: client ``c``'s per-step row indices in round ``r``
    on the streamed (bucketed) path."""
    owner = np.random.default_rng(seed)
    steps = np.asarray([steps_of(n, batch, epochs) for n in ns], np.int64)
    order = [int(i) for i in np.argsort(steps, kind="stable")]
    feed = []
    for _ in range(rounds):
        rnd = {}
        for c0 in range(0, len(ns), chunk):
            rnd.update(_schedule(owner, order[c0:c0 + chunk], ns, batch,
                                 epochs, backend))
        feed.append([rnd[c] for c in range(len(ns))])
    return feed
