"""XLA cost-model performance attribution: FLOPs from the program run.

Until now every MFU number in this repo came from a hand-maintained
analytic constant (``bench.py TRAIN_FLOPS_PER_SAMPLE``: ResNet-56 MACs
counted off the reference topology, times the 3x fwd/bwd rule of thumb).
That constant silently rots the moment the model, the lowering, or the
augmentation pipeline changes. XLA already knows what it compiled:
``lowered.compile().cost_analysis()`` reports FLOPs and bytes accessed
for the exact HLO the device executes. This module turns that into the
repo's FLOPs source of record:

- :func:`program_cost` -- cost of one jitted callable at given arg
  shapes (``ShapeDtypeStruct`` args work: no allocation, no execution).
- :func:`train_step_cost` -- cost of ONE local-SGD training step built
  from a ``TrainSpec`` + ``ClientUpdateConfig`` exactly the way the
  engine's trip-loop builds it (value_and_grad + optimizer update +
  the spec's augmentation), so per-sample train FLOPs come from the
  program actually run. ``bench.py`` divides by the batch size for its
  MFU; the analytic constant remains as the cross-checked fallback
  (``tests/test_observability.py`` pins agreement within the tolerance
  documented in docs/PERFORMANCE.md round 7).
- :class:`CostModel` -- a default-OFF process global (same switchboard
  discipline as the tracer/registry/recorder): when armed,
  ``BucketedStreamRunner`` attributes per-bucket-shape FLOPs and
  FLOP-weighted padding waste into its round info, and the
  ``enable()`` scope pushes the per-program catalog to the metrics
  sink on exit. Disabled cost: one module-global read per round.

Dynamic-trip caveat (measured on XLA CPU and TPU): cost analysis
of a ``while``/``fori_loop`` with a traced trip count charges the loop
body ONCE. For the bucket chunk programs that is exactly the useful
number -- the cost of one step across all ``client_chunk`` lanes (plus
the per-dispatch aggregation epilogue, which step-dominated chunks
amortize) -- so per-bucket executed FLOPs are
``program_flops / client_chunk * executed_lane_steps``.
"""

from __future__ import annotations

import logging
import threading
from dataclasses import dataclass
from typing import Optional

#: Failure modes of AOT lowering / compilation / cost introspection that
#: must degrade to the analytic fallback, never crash a bench or a round
#: (cost_analysis is not part of jax's stable API surface).
_COST_ERRORS = (TypeError, ValueError, RuntimeError, NotImplementedError,
                AttributeError, KeyError, IndexError, ImportError)


@dataclass(frozen=True)
class ProgramCost:
    """Cost of one compiled XLA program (the whole dispatch)."""

    flops: float
    bytes_accessed: float
    source: str = "xla"  # "xla" (cost model) | "analytic" (fallback)


def compiled_cost(compiled) -> Optional[ProgramCost]:
    """``ProgramCost`` from a ``jax.stages.Compiled``, or None when the
    backend exposes no usable cost analysis."""
    try:
        ca = compiled.cost_analysis()
    except _COST_ERRORS:
        return None
    if not isinstance(ca, dict):
        return None
    flops = float(ca.get("flops", -1.0) or -1.0)
    if flops <= 0:
        return None
    return ProgramCost(flops=flops,
                       bytes_accessed=float(ca.get("bytes accessed", 0.0)
                                            or 0.0),
                       source="xla")


def program_cost(jitted_fn, *args, **kwargs) -> Optional[ProgramCost]:
    """Cost-analyze ``jitted_fn`` at these arg shapes via AOT
    ``lower().compile()``. Args may be concrete arrays or
    ``jax.ShapeDtypeStruct`` templates (nothing executes either way).

    The AOT compile does NOT populate the jit dispatch cache (pinned in
    tests -- ``compiled_shapes()``-style cache counts stay honest), but
    it IS a real XLA compile: callers cache per shape (see
    :class:`CostModel`) and the persistent compilation cache dedupes it
    against the dispatch-path compile on TPU-scale programs. Returns
    None on any failure -- callers fall back to their analytic number.
    """
    try:
        compiled = jitted_fn.lower(*args, **kwargs).compile()
    except _COST_ERRORS as e:
        logging.info("costmodel: lowering failed (%s: %s) -- falling back "
                     "to analytic FLOPs", type(e).__name__, e)
        return None
    return compiled_cost(compiled)


def train_step_cost(spec, cfg, batch) -> Optional[ProgramCost]:
    """Cost of ONE local training step for ``spec``/``cfg`` at ``batch``
    shapes -- the exact step the engine's trip loop runs: the spec's
    augmentation (when present), ``value_and_grad`` of the loss, and the
    optimizer update (optimizer state initialized in-program, as every
    client update does).

    ``batch``: ``{"x", "y", "mask"}`` of concrete arrays or
    ``jax.ShapeDtypeStruct``; model/optimizer state shapes are derived
    with ``jax.eval_shape`` so nothing ever touches a device. Divide
    ``flops`` by the batch size for per-sample train FLOPs.
    """
    import jax
    import optax

    # lazy: costmodel must stay importable without pulling the engine in
    # (engine imports get_cost_model from here at module top)
    from fedml_tpu.parallel.engine import make_optimizer

    try:
        optimizer = make_optimizer(cfg)

        def step(state, batch, rng):
            params = state["params"]
            rest = {k: v for k, v in state.items() if k != "params"}
            opt_state = optimizer.init(params)
            if spec.augment_fn is not None:
                batch = dict(batch)
                batch["x"] = spec.augment_fn(
                    batch["x"], jax.random.fold_in(rng, 13))

            def loss_wrapper(p):
                s = dict(rest)
                s["params"] = p
                return spec.loss_fn(s, batch, rng, True)

            (_, (new_state, metrics)), grads = jax.value_and_grad(
                loss_wrapper, has_aux=True)(params)
            updates, _ = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), metrics

        state_shapes = jax.eval_shape(
            lambda: spec.init_fn(jax.random.PRNGKey(0)))
        rng_shape = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    except _COST_ERRORS as e:
        logging.info("costmodel: train-step construction failed (%s: %s)",
                     type(e).__name__, e)
        return None
    return program_cost(jax.jit(step), state_shapes, batch, rng_shape)


class CostModel:
    """Per-program cost catalog, armed via :func:`set_cost_model`.

    Instrumentation points (the bucketed stream runner, bench) call
    :meth:`note` once per distinct program they attribute; :meth:`record`
    renders the catalog as a metrics-record fragment
    (``cost/<name>_flops`` / ``_bytes``) that the ``enable()`` scope
    pushes to the metrics sink on exit. Thread-safe; a None cost is
    remembered too, so a backend without cost analysis is probed once,
    not once per round.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.programs = {}  # name -> ProgramCost | None

    def note(self, name, cost: Optional[ProgramCost]):
        with self._lock:
            self.programs.setdefault(name, cost)
        return cost

    def known(self, name) -> bool:
        with self._lock:
            return name in self.programs

    def get(self, name) -> Optional[ProgramCost]:
        with self._lock:
            return self.programs.get(name)

    def record(self, prefix="cost/") -> dict:
        with self._lock:
            out = {prefix + "programs": len(self.programs)}
            for name, pc in sorted(self.programs.items()):
                if pc is None:
                    out[prefix + name + "_flops"] = None
                else:
                    out[prefix + name + "_flops"] = pc.flops
                    out[prefix + name + "_bytes"] = pc.bytes_accessed
        return out


_cost_model = None


def get_cost_model():
    """The process-wide cost model, or None when attribution is off --
    instrumentation points guard with ``if cm is not None``."""
    return _cost_model


def set_cost_model(cm):
    global _cost_model
    prev = _cost_model
    _cost_model = cm
    return prev


__all__ = ["ProgramCost", "compiled_cost", "program_cost",
           "train_step_cost", "CostModel", "get_cost_model",
           "set_cost_model"]
