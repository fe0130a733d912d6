"""Counters of expert routing, per round.

A routed-expert layer (``fedml_tpu.models.deepseek_v3.RoutedExperts``)
sows six sums a step into the client-update program's metrics; the round
sums them over steps, layers and clients. ``routing_counters`` makes the
round's five series of them:

- ``moe_rows_held``: assignments (token x chosen expert) that landed on
  the experts held here: the rows the grouped products computed;
- ``moe_load_max_over_mean``: the fullest held expert's rows over the
  mean held expert's, both summed over the round's layer-steps (1.0 is a
  perfectly even load);
- ``moe_dropped``: assignments to a held expert that got no row. The
  layer has a row for every assignment, so this stays 0;
- ``moe_overflow``: layer-steps that ran the whole ``tokens x top-k``
  buffer because some lane of the chunk held more rows than the compact
  buffer has (0 in a layer that holds half the router or more: it has
  no other buffer);
- ``moe_capacity_rows``: the compact buffer's rows summed over the
  layer-steps, so that ``moe_rows_held / moe_capacity_rows`` is its fill.

``note_routing`` also sets the registry's gauges of the same names; the
round (``algorithms/fedavg.py``) puts the five into its record
(``metrics.jsonl``) and, on the bucketed stream, whose metrics reach the
host inside it, onto the ``local-train`` span.
"""

from __future__ import annotations

import numpy as np

from fedml_tpu.observability.registry import get_registry

SERIES = ("moe_rows_held", "moe_load_max_over_mean", "moe_dropped",
          "moe_overflow", "moe_capacity_rows")


def routing_counters(metrics) -> dict:
    """``{}`` for a model that routes nothing."""
    if not metrics or "moe_rows_held" not in metrics:
        return {}
    total = {k: float(np.sum(np.asarray(metrics[k])))
             for k in ("moe_rows_held", "moe_load_max", "moe_load_mean",
                       "moe_dropped", "moe_overflow", "moe_capacity_rows")}
    return {"moe_rows_held": total["moe_rows_held"],
            "moe_load_max_over_mean":
                total["moe_load_max"] / max(total["moe_load_mean"], 1e-30),
            "moe_dropped": total["moe_dropped"],
            "moe_overflow": total["moe_overflow"],
            "moe_capacity_rows": total["moe_capacity_rows"]}


def layer_mix_counters(metrics) -> dict:
    """``conv.layer_positions`` and ``attn.layer_positions`` of a round:
    positions run through conv mixers and through attention mixers
    (layers of the kind x positions), from the sums a decoder with
    ``layer_types`` sows; ``{}`` for any other model."""
    if not metrics or "conv_layer_positions" not in metrics:
        return {}
    total = lambda k: float(np.sum(np.asarray(metrics.get(k, 0.0))))
    return {"conv.layer_positions": total("conv_layer_positions"),
            "attn.layer_positions": total("attn_layer_positions")}


def note_routing(metrics) -> dict:
    counters = routing_counters(metrics)
    reg = get_registry()
    if counters and reg is not None:
        for name, value in counters.items():
            reg.set_gauge(name, value, help="expert routing, last round "
                                            "(observability/routing.py)")
    return counters


__all__ = ["SERIES", "routing_counters", "layer_mix_counters",
           "note_routing"]
