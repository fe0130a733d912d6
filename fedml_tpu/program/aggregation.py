"""Aggregation: the canonical fold + the one buffered-async aggregator.

The ``RoundProgram``'s aggregation leg. Two regimes behind one policy
object:

- **sync partial** (Bonawitz): the round barrier collects reports and
  :func:`aggregate_reports` renormalizes over the *reporting* subset.
- **FedBuff buffered** (Nguyen et al., AISTATS 2022): no barrier --
  :class:`BufferedAggregator` folds updates as they arrive, staleness-
  weighted, and flushes every K folds (or on a deadline).

Both flush through :func:`fold_entries_fp64` -- the sorted-key float64
normalize-late fold -- which is what makes the async oracle exact: with
an infinite flush deadline, staleness decay 0 (weight 1) and
``buffer_k`` = cohort size, one flush IS ``aggregate_reports`` of the
same reports, bit for bit. Every consumer (the sim engine's bucketed
streaming, both distributed servers, the fan-in edges) folds through
THIS module; fedlint FL130 flags new out-of-band folds.

Host-importable without jax at module scope (the fold imports jax
lazily -- its ``jax.tree.flatten`` over numpy leaves never touches a
device), which is what keeps ``RoundProgram.host_view()`` jax-free.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from fedml_tpu.core.locks import audited_lock
from fedml_tpu.observability.perfmon import get_perf_monitor
from fedml_tpu.observability.registry import get_registry
from fedml_tpu.observability.tracing import get_tracer

#: AggregationPolicy.mode values.
AGG_SYNC = "sync"    # barrier round: partial aggregation over reporters
AGG_ASYNC = "async"  # FedBuff: buffered, staleness-weighted, K/deadline


@dataclass(frozen=True)
class AggregationPolicy:
    """Aggregation knobs for one :class:`~fedml_tpu.program.RoundProgram`.
    ``resilience.AsyncAggPolicy`` is this class (a compatibility alias;
    its historical positional field order is preserved, with ``mode``
    appended last).

    Args:
      buffer_k: server update every K buffered client updates (FedBuff's
        K; the flush also fires early when every still-alive client has
        reported -- a buffer that can never fill must not deadlock).
      staleness_decay: polynomial staleness exponent ``a``; an update
        ``s`` versions stale is weighted ``(1 + s) ** -a``. ``0`` weights
        every update 1 (the oracle setting); ``0.5`` is FedBuff's
        ``1/sqrt(1+s)``.
      flush_deadline_s: wall-clock bound from the first fold of a window
        to its flush; ``0`` disables (flush only on K). The async analog
        of the synchronous report deadline: a deadline flush below K is
        counted ``degraded``.
      async_window: simulation only -- how many in-flight bucket chunks
        the streaming engine keeps dispatched before folding the oldest
        (the simulated client concurrency; staleness appears when
        ``buffer_k`` flushes fall inside the window).
      mode: ``"async"`` (FedBuff buffered -- the historical meaning of
        constructing this policy at all) or ``"sync"`` (barrier round;
        the buffered knobs are inert and the program folds through
        :func:`aggregate_reports`).
    """

    buffer_k: int = 64
    staleness_decay: float = 0.5
    flush_deadline_s: float = 0.0
    async_window: int = 4
    mode: str = AGG_ASYNC

    @classmethod
    def sync(cls) -> "AggregationPolicy":
        """The barrier-round policy: fold reports at the round boundary
        through :func:`aggregate_reports`, no buffer."""
        return cls(buffer_k=0, staleness_decay=0.0, flush_deadline_s=0.0,
                   async_window=0, mode=AGG_SYNC)

    @property
    def is_async(self) -> bool:
        return self.mode == AGG_ASYNC

    @classmethod
    def from_args(cls, args) -> Optional["AggregationPolicy"]:
        if not int(getattr(args, "async_agg", 0) or 0):
            return None
        return cls(
            buffer_k=int(getattr(args, "buffer_k", 64) or 64),
            staleness_decay=float(getattr(args, "staleness_decay", 0.5)),
            flush_deadline_s=float(getattr(args, "flush_deadline", 0.0)
                                   or 0.0),
            async_window=int(getattr(args, "async_window", 4) or 4))


def staleness_weight(staleness, decay) -> float:
    """Polynomial staleness discount ``(1 + s) ** -decay`` (monotone
    non-increasing in ``s``; exactly 1.0 at ``s == 0`` or ``decay == 0``,
    so the oracle settings multiply by a float64-exact 1.0)."""
    s = max(0, int(staleness))
    if s == 0 or decay == 0:
        return 1.0
    return float((1.0 + s) ** -float(decay))


def _addend(x):
    """``x`` as a numpy array that a ufunc can widen to float64 as it
    reads it (no float64 copy of the leaf). A dtype numpy cannot promote
    against float64 inside a ufunc, or would promote beyond it, is
    converted first -- the values are then what ``np.asarray(x,
    np.float64)`` gives, as they always were."""
    x = np.asarray(x)
    try:
        direct = np.result_type(x.dtype, np.float64) == np.float64
    except TypeError:
        direct = False
    return x if direct else x.astype(np.float64)


class Float64Accumulator:
    """The float64 numerator of THE fold, written where it stands.

    One float64 array per payload leaf, allocated by the accumulator and
    kept from one fold to the next while the payload's tree and shapes
    stay what they were: :meth:`start` writes ``float64(payload) *
    scale`` into those arrays, :meth:`add` adds ``float64(payload) *
    scale`` to them in place, :meth:`finish` divides and rounds to a
    fresh float32 tree in one pass. The sums and their order are exactly
    those of the out-of-place formula (``acc = f64(p0) * s0``; ``acc =
    acc + f64(p) * s``; ``(acc / total).astype(float32)``): a float32
    leaf is widened as it is read, a scale of exactly 1.0 skips the
    multiply (``x * 1.0 == x``), any other scale goes through ONE
    float64 scratch array (never a fused multiply-add), and the quotient
    is computed in float64 and rounded once.

    No payload is ever written to or kept, and the accumulator's arrays
    are never anyone else's memory (``np.asarray`` of a jax array is the
    runtime's read-only host copy; of a float64 array it is the array
    itself). Host-only: nothing here is handed to a device.
    """

    def __init__(self):
        self._treedef = None
        self._acc = None      # [float64 ndarray], one per payload leaf
        self._scratch = None  # flat float64, the largest leaf's size
        self.started = False  # between start() and finish()

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self._acc or ())

    @property
    def arrays(self) -> int:
        return len(self._acc or ())

    def start(self, payload, scale=1.0) -> bool:
        """Begin a fold from ``float64(payload) * scale``. True when the
        standing arrays took it, False when they had to be allocated
        (the first fold, or a payload of another tree or other shapes)."""
        import jax

        leaves, treedef = jax.tree.flatten(payload)
        leaves = [_addend(x) for x in leaves]
        reused = (self._acc is not None and treedef == self._treedef
                  and [a.shape for a in self._acc]
                  == [x.shape for x in leaves])
        if not reused:
            self._treedef = treedef
            self._acc = [np.empty(x.shape, np.float64) for x in leaves]
            self._scratch = None
        scale = float(scale)
        for a, x in zip(self._acc, leaves):
            if scale == 1.0:
                np.copyto(a, x)
            else:
                np.multiply(x, scale, out=a, dtype=np.float64)
        self.started = True
        return reused

    def add(self, payload, scale=1.0) -> None:
        """``acc += float64(payload) * scale``, in place."""
        if not self.started:
            raise ValueError("add() to an accumulator that was not started")
        scale = float(scale)
        leaves = [_addend(x) for x in self._treedef.flatten_up_to(payload)]
        if scale != 1.0 and self._scratch is None:
            self._scratch = np.empty(
                max((a.size for a in self._acc), default=0), np.float64)
        for a, x in zip(self._acc, leaves):
            if scale != 1.0:
                x = np.multiply(
                    x, scale, dtype=np.float64,
                    out=self._scratch[:a.size].reshape(a.shape))
            np.add(a, x, out=a)

    def finish(self, total):
        """``float32(acc / total)`` as a fresh tree; ends the fold."""
        if not self.started:
            raise ValueError("finish() of an accumulator that was not "
                             "started")
        self.started = False
        return self._treedef.unflatten([
            np.divide(a, total, out=np.empty(a.shape, np.float32),
                      casting="same_kind")
            for a in self._acc])


# ---------------------------------------------------------------------------
# The two-word float32 fold: the same numerator where float64 is not to be
# had (a TPU, ``jax_enable_x64`` off). A sum is held as two float32 words,
# ``hi + lo`` with ``|lo| <= ulp(hi) / 2``; every step below is either
# error-free or rounds at the second word's scale (2^-48 of the sum), so
# the error does not grow with the number of addends as a one-word float32
# sum's does. Traced under ``jax.jit`` by ``BucketedStreamRunner`` (jax is
# imported where a function needs it, so the module stays host-importable
# without it).
# ---------------------------------------------------------------------------
def _two_sum(a, b):
    """``a + b`` as ``(rounded sum, its rounding error)``, exactly."""
    t = a + b
    bb = t - a
    return t, (a - (t - bb)) + (b - bb)


def _split12(a):
    """A float32 as two floats of at most 12 significant bits each (the
    low 12 bits of the significand masked off, and what they held), so
    that a product of two halves is exact in float32."""
    import jax.numpy as jnp
    from jax import lax

    hi = lax.bitcast_convert_type(
        lax.bitcast_convert_type(a, jnp.uint32) & jnp.uint32(0xFFFFF000),
        jnp.float32)
    return hi, a - hi


def _by_leaf(acc_hi, acc_lo, other):
    """``(treedef, [(hi, lo, other)])`` leaf by leaf; ``lo`` is None in
    every triple while the sum is one word (``acc_lo`` None)."""
    import jax

    flat_hi, treedef = jax.tree.flatten(acc_hi)
    flat_lo = ([None] * len(flat_hi) if acc_lo is None
               else treedef.flatten_up_to(acc_lo))
    return treedef, list(zip(flat_hi, flat_lo,
                             treedef.flatten_up_to(other)))


def two_word_add(acc_hi, acc_lo, payload):
    """``(hi, lo) + payload`` leaf by leaf, as ``(hi, lo)`` again.

    All three are pytrees of float32 leaves of one structure; ``acc_lo``
    is None for a sum that is still one word (then it is the first add
    that makes the second). The addend's rounding error is caught whole
    (TwoSum, six additions), joins the low word, and the pair is
    renormalised, so the low word stays under half an ulp of the high
    one and what is lost a step is of the order of 2^-48 of the sum.
    """
    def leaf(hi, lo, p):
        t, err = _two_sum(hi, p)
        if lo is not None:
            err = lo + err
        new_hi = t + err
        return new_hi, err - (new_hi - t)

    treedef, triples = _by_leaf(acc_hi, acc_lo, payload)
    pairs = [leaf(*x) for x in triples]
    return (treedef.unflatten([h for h, _ in pairs]),
            treedef.unflatten([l for _, l in pairs]))


def two_word_quotient(acc_hi, acc_lo, total_hi, total_lo, dtypes):
    """``(hi + lo) / (total_hi + total_lo)`` rounded ONCE to float32,
    then cast leaf by leaf to the dtypes of the template ``dtypes``.

    The divisor is the float64 total weight as two float32 scalars
    (``float32(total)`` and ``float32(total - that)``). A float32
    division alone is good to an ulp; here the quotient of the high
    words is corrected by its exact remainder -- the product
    ``q * total_hi`` taken without error from halves of 12 bits (Dekker),
    the low words brought in -- divided once more. With IEEE float32
    arithmetic what is rounded at the end is within about 2^-46 of the
    true quotient and exact where the correction is representable, so
    the result is float64's: 0 of a million elements differed on the
    CPU, exact ties (which go to even) included. A TPU v5e divides by
    multiplying with a rounded reciprocal, so its correction can be an
    ulp off where the exact one is representable; that tips an EXACT tie
    of the final rounding to the other neighbour. Real payloads (sums of
    small-integer multiples of float32 values) hit exact ties a few
    times in a million: 1.2e-6 to 3.6e-6 of the elements of a round read
    one ulp off there (the 479 of one reading were brought back: every
    one such a tie), from accumulator words that were bit for bit IEEE's
    (PERF.md section 6, PR 28). The
    contract is one ulp everywhere and equality on all but 1e-5 of the
    elements. ``acc_lo`` may be
    None (a one-word sum).
    """
    t_h, t_l = _split12(total_hi)

    def leaf(hi, lo, d):
        if lo is not None:
            hi, lo = _two_sum(hi, lo)
        q = hi / total_hi
        p = q * total_hi
        q_h, q_l = _split12(q)
        p_err = ((q_h * t_h - p) + q_h * t_l + q_l * t_h) + q_l * t_l
        rem = (hi - p) - p_err
        if lo is not None:
            rem = rem + lo
        rem = rem - q * total_lo
        return (q + rem / total_hi).astype(d.dtype)

    treedef, triples = _by_leaf(acc_hi, acc_lo, dtypes)
    return treedef.unflatten([leaf(*x) for x in triples])


def split_total(total):
    """The float64 total weight as the two float32 scalars
    :func:`two_word_quotient` divides by."""
    hi = np.float32(total)
    return hi, np.float32(float(total) - float(hi))


def float32_ulps(a, b):
    """How many floats apart two float32 arrays are, element by element
    (int64; ``-0.0`` and ``0.0`` are one float). The unit the two-word
    fold's contract is stated in; host-only."""
    def ordered(x):
        i = np.ascontiguousarray(x, np.float32).view(np.int32) \
            .astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)
    return np.abs(ordered(a) - ordered(b))


def fold_entries_fp64(entries) -> tuple:
    """THE canonical weighted fold: sorted-key, float64, normalize-late.

    ``entries``: iterable of ``(sort_key, weight, payload_pytree, scale)``
    where the entry contributes ``float64(payload) * scale`` to the
    numerator and ``weight`` to the denominator. Per-client reports use
    ``scale == weight == n_i`` (a plain weighted average); the bucketed
    streaming engine feeds PRE-WEIGHTED partial sums with
    ``scale == staleness_weight`` and ``weight == w_sum * staleness_weight``.

    A payload may also be a
    :class:`~fedml_tpu.compression.wire.CompressedUpdate` (a compressed
    report's encoded delta + the base params it is relative to): its
    logical contribution is ``scale * float64(base + decoded_delta)``,
    folded WITHOUT densifying per report -- the decoded delta
    accumulates sparsely/quantized (O(k) for a topk report) in sorted
    entry order, and each DISTINCT base is added exactly once, scaled by
    the sum of its entries' scales, in sorted ``base_key`` order. The
    fold stays arrival-order independent; what "bitwise" means under
    lossy compression is pinned in docs/COMPRESSION.md ("Distributed
    wire path"): the compressed fold is its own canonical f64 order --
    NOT bit-equal to reconstructing each report in f32 first -- and the
    async oracle (decay 0) still equals the synchronous compressed fold
    bit for bit, because both run this exact function over the same
    entries.

    Returns ``(params_f32, weight_total)``. Folding in sorted-key order
    (never arrival order) is what makes the result bitwise deterministic:
    :class:`BufferedAggregator` flushes through this exact function, so
    the async path with staleness weight 1 and one flush reproduces
    :func:`aggregate_reports` bit-for-bit no matter which order the
    reports raced in.

    The numerator lives in one :class:`Float64Accumulator` for the call
    (the bucketed stream's synchronous fold keeps one across rounds):
    every dense entry, base and the delta accumulator is added to it in
    place, so a fold allocates one float64 tree and at most one scratch
    array, whatever the number of entries. No payload is written to.
    """
    from fedml_tpu.compression.wire import CompressedUpdate

    entries = sorted(entries, key=lambda e: e[0])
    if not entries:
        raise ValueError("weighted fold over an empty entry set "
                         "(abandon/skip instead)")
    total = 0.0
    acc = Float64Accumulator()  # dense contributions

    def fold_in(payload, scale):
        (acc.add if acc.started else acc.start)(payload, scale)

    cacc = None         # compressed-delta contributions ({name: f64})
    base_acc = {}       # base_key -> [scale_sum, base params]
    for _key, weight, payload, scale in entries:
        total += float(weight)
        if isinstance(payload, CompressedUpdate):
            cacc = payload.fold_delta(cacc, float(scale))
            slot = base_acc.setdefault(payload.base_key,
                                       [0.0, payload.base])
            slot[0] += float(scale)
            continue
        fold_in(payload, scale)
    # canonical combine order: dense entries (sorted), then each distinct
    # base (sorted by key), then the sparse delta accumulator
    for bk in sorted(base_acc):
        scale_sum, base = base_acc[bk]
        fold_in(base, scale_sum)
    if cacc is not None:
        fold_in(cacc, 1.0)
    if total <= 0:
        raise ValueError("weighted fold has zero total weight")
    return acc.finish(total), total


def aggregate_reports(reports) -> tuple:
    """Weighted average over the *reporting* subset, renormalized.

    ``reports``: ``{rank: (num_samples, params_pytree)}`` (numpy leaves --
    this is the host-side control plane). Returns ``(params, total_n)``.
    Delegates to :func:`fold_entries_fp64` -- sorted-rank float64 fold, so
    two runs over the same subset are bitwise identical (the chaos smoke's
    A/B oracle) AND the buffered async aggregator (which flushes through
    the same fold) matches it bit-for-bit under the oracle settings.
    Weights divide by the reporters' sample total -- never the selected
    cohort's -- so a dropped client renormalizes instead of zero-biasing;
    an empty subset fails fast (parity with the engine's empty-cohort
    guard, ``engine.py:325``).
    """
    if not reports:
        raise ValueError("aggregate_reports over an empty reporting subset "
                         "(abandon the round instead)")
    # sorted-rank order for the guard sum too: the returned total must be
    # arrival-order deterministic, exactly like the fold's denominator
    total = float(sum(float(reports[r][0]) for r in sorted(reports)))
    if total <= 0:
        raise ValueError("reporting subset has zero total samples")
    params, fold_total = fold_entries_fp64(
        (r, float(n), payload, float(n))
        for r, (n, payload) in reports.items())
    assert fold_total == total  # same addends, same (sorted) order
    return params, total


@dataclass(frozen=True)
class FlushResult:
    """One server update produced by :meth:`BufferedAggregator.flush`."""

    params: dict          # f32 pytree (the fold output)
    weight: float         # renormalization denominator (post-staleness)
    version: int          # server version AFTER this flush
    contributors: tuple   # entry keys folded (ranks / chunk ordinals)
    clients: int          # client updates represented by those entries
    reason: str           # "buffer_k" | "deadline" | "drain" | "peer_lost"
    max_staleness: int


class BufferedAggregator:
    """Thread-safe staleness-weighted update buffer with K/deadline flush.

    ``fold`` accepts either per-client reports (``weight`` = the client's
    sample count, payload = its params) or pre-weighted partial sums from
    the streaming engine (``preweighted=True``: payload is already
    ``sum_i n_i * p_i`` over ``clients`` members, ``weight`` their
    ``sum_i n_i``). Entries are retained until ``flush`` folds them in
    sorted-key order through :func:`fold_entries_fp64` -- memory is
    O(buffer_k) payloads and the flushed bytes are arrival-order
    independent. Re-folding an existing key overwrites (newest wins --
    the older update trained on strictly staler params) and is counted.
    """

    def __init__(self, policy: AggregationPolicy, fold_fn=None):
        self.policy = policy
        # the flush fold; None = the canonical fold_entries_fp64. The
        # RoundProgram's robust leg hands its order-statistic variant
        # here (HostProgram.make_aggregator) -- same (entries) ->
        # (params, weight) contract, still sorted-key deterministic.
        self._fold_fn = fold_fn
        self._lock = audited_lock()
        self._entries = {}        # key -> (weight, payload, scale)
        self._entry_clients = {}  # key -> client count
        self._entry_staleness = {}
        self.version = 0
        self.counters = {"folds": 0, "flushes": 0, "drain_flushes": 0,
                         "deadline_flushes": 0, "overwrites": 0,
                         "clients_folded": 0, "max_staleness": 0,
                         "depth_peak": 0}

    @property
    def depth(self) -> int:
        """Distinct buffered entries (the ``fed_buffer_depth`` gauge)."""
        with self._lock:
            return len(self._entries)

    def clients_buffered(self) -> int:
        with self._lock:
            return sum(self._entry_clients.values())

    def fold(self, key, weight, payload, staleness=0, clients=1,
             preweighted=False) -> int:
        """Buffer one update; returns the post-fold distinct-entry depth.

        ``staleness`` = server versions elapsed since the update's model
        was issued (``version_now - version_born``); the entry's weight
        (and, for pre-weighted partials, its numerator scale) is
        multiplied by :func:`staleness_weight`.
        """
        with get_tracer().span("buffer-fold", staleness=int(staleness),
                               clients=int(clients)) as sp:
            with self._lock:
                depth = self._fold_locked(key, weight, payload, staleness,
                                          clients, preweighted)
            sp.set(depth=depth)
        self._note_fold(staleness, depth)
        return depth

    def _fold_locked(self, key, weight, payload, staleness, clients,
                     preweighted):
        """One entry into the buffer; callers hold ``_lock``."""
        sw = staleness_weight(staleness, self.policy.staleness_decay)
        w = float(weight) * sw
        scale = sw if preweighted else w
        if key in self._entries:
            self.counters["overwrites"] += 1
        else:
            self.counters["clients_folded"] += int(clients)
        self._entries[key] = (w, payload, scale)
        self._entry_clients[key] = int(clients)
        self._entry_staleness[key] = int(staleness)
        self.counters["folds"] += 1
        self.counters["max_staleness"] = max(
            self.counters["max_staleness"], int(staleness))
        depth = len(self._entries)
        self.counters["depth_peak"] = max(
            self.counters["depth_peak"], depth)
        return depth

    def _note_fold(self, staleness, depth):
        reg = get_registry()
        if reg is not None:
            reg.set_gauge("fed_buffer_depth", depth,
                          help="distinct updates buffered awaiting flush")
            reg.set_gauge("fed_update_staleness", int(staleness),
                          help="staleness (server versions) of the last "
                               "folded update")
        mon = get_perf_monitor()
        if mon is not None:
            # the histogram complement of the point gauges above (pace
            # steering reads distributions, not last values)
            mon.observe_fold(staleness, depth)

    def fold_many(self, entries, ready_target=None):
        """Batched-entry fold: buffer ``entries`` (a list of ``(key,
        weight, payload, staleness)`` per-client reports) under ONE lock
        acquisition, stopping after the entry that brings the buffered
        client count to the flush threshold (``buffer_k`` capped by
        ``ready_target``, exactly :meth:`ready`'s rule). Returns
        ``(consumed, depth)``: the caller flushes and re-enters with the
        remainder. Fold order is the list order, the flush boundary is
        the same entry it would be folding one at a time, and
        :meth:`flush` sorts by key anyway -- so a chunk of reports costs
        one lock acquisition per flush window instead of one per report
        while staying bitwise-identical to the per-report path (pinned
        in tests/test_async_agg.py)."""
        k = self.policy.buffer_k
        if ready_target is not None:
            k = min(k, int(ready_target))
        k = max(1, k)
        consumed = 0
        depth = 0
        noted = []
        with get_tracer().span("buffer-fold", batch=len(entries)) as sp:
            with self._lock:
                for key, weight, payload, staleness in entries:
                    depth = self._fold_locked(key, weight, payload,
                                              staleness, 1, False)
                    noted.append((staleness, depth))
                    consumed += 1
                    if sum(self._entry_clients.values()) >= k:
                        break
            sp.set(depth=depth, consumed=consumed)
        for staleness, d in noted:
            self._note_fold(staleness, d)
        return consumed, depth

    def ready(self, target=None) -> bool:
        """True when the buffered client count reaches ``buffer_k`` --
        capped by ``target`` (e.g. the number of still-alive clients)
        so a buffer that can never fill does not deadlock the plane."""
        k = self.policy.buffer_k
        if target is not None:
            k = min(k, int(target))
        with self._lock:
            return sum(self._entry_clients.values()) >= max(1, k)

    def flush(self, reason="buffer_k") -> FlushResult:
        """Fold + clear the buffer, bump the server version."""
        with self._lock:
            if not self._entries:
                raise ValueError("flush of an empty update buffer")
            entries = [(k, w, p, s)
                       for k, (w, p, s) in self._entries.items()]
            clients = sum(self._entry_clients.values())
            max_stale = max(self._entry_staleness.values())
            self._entries = {}
            self._entry_clients = {}
            self._entry_staleness = {}
            self.version += 1
            version = self.version
            self.counters["flushes"] += 1
            if reason == "deadline":
                self.counters["deadline_flushes"] += 1
            elif reason == "drain":
                self.counters["drain_flushes"] += 1
        with get_tracer().span("buffer-flush", reason=reason,
                               entries=len(entries), clients=clients,
                               version=version):
            params, weight = (self._fold_fn or fold_entries_fp64)(entries)
        reg = get_registry()
        if reg is not None:
            reg.set_gauge("fed_buffer_depth", 0,
                          help="distinct updates buffered awaiting flush")
            reg.inc("fed_buffer_flushes_total",
                    help="server updates produced by the async buffer",
                    reason=reason)
        return FlushResult(params=params, weight=weight, version=version,
                           contributors=tuple(k for k, _, _, _ in entries),
                           clients=clients, reason=reason,
                           max_staleness=max_stale)

    def record(self, prefix="async/") -> dict:
        """Cumulative counters as a metrics-record fragment (rides every
        round record on async runs -- the buffer-depth/staleness series
        lands in metrics.jsonl even with observability off)."""
        with self._lock:
            out = {prefix + k: v for k, v in self.counters.items()}
            out[prefix + "version"] = self.version
            out[prefix + "buffer_depth"] = len(self._entries)
        return out


__all__ = ["AGG_SYNC", "AGG_ASYNC", "AggregationPolicy",
           "staleness_weight", "Float64Accumulator", "fold_entries_fp64",
           "aggregate_reports",
           "FlushResult", "BufferedAggregator"]
