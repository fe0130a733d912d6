"""FedOpt: server-side adaptive optimization (reference
``fedml_api/distributed/fedopt/FedOptAggregator.py:91-122``).

The reference averages client weights, treats ``global - avg`` as a
pseudo-gradient, and feeds it to a reflected ``torch.optim`` subclass
(``optrepo.py:7-64``). Here the server optimizer is an optax transformation
applied inside the jitted round -- ``get_server_optimizer`` replaces the
OptRepo reflection registry.
"""

from __future__ import annotations

import optax

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.core import pytree


def get_server_optimizer(name, lr, momentum=0.9, **kw):
    """Name -> optax transformation (reference ``--server_optimizer`` flag,
    ``main_fedopt.py:54-60``; FedAvgM = sgd+momentum, FedAdam, FedAdagrad per
    'Adaptive Federated Optimization', arXiv:2003.00295)."""
    name = name.lower()
    if name in ("sgd", "fedavgm"):
        return optax.sgd(lr, momentum=momentum)
    if name in ("adam", "fedadam"):
        return optax.adam(lr, b1=kw.get("b1", 0.9), b2=kw.get("b2", 0.99),
                          eps=kw.get("eps", 1e-3))
    if name in ("adagrad", "fedadagrad"):
        return optax.adagrad(lr, eps=kw.get("eps", 1e-3))
    if name in ("yogi", "fedyogi"):
        return optax.yogi(lr)
    raise ValueError(f"unknown server optimizer: {name}")


def make_fedopt_hooks(server_tx):
    """Aggregator hooks implementing the pseudo-gradient server step."""

    def payload_fn(local_state, global_state, aux):
        return local_state

    def server_fn(global_state, avg_state, server_opt_state, rng):
        pseudo_grad = pytree.tree_sub(global_state["params"],
                                      avg_state["params"])
        updates, new_opt_state = server_tx.update(
            pseudo_grad, server_opt_state, global_state["params"])
        new_params = optax.apply_updates(global_state["params"], updates)
        new_global = dict(avg_state)  # batch_stats et al. take the average
        new_global["params"] = new_params
        return new_global, new_opt_state

    return payload_fn, server_fn


class FedOptAPI(FedAvgAPI):
    """FedAvg loop + server optimizer (reference ``fedopt_api.py:62-109``).
    Extra args: ``server_optimizer`` (default ``sgd``), ``server_lr``
    (default 1.0), ``server_momentum``.

    Resilience (``--overselect`` / ``--straggler_p`` / ``--quorum``)
    composes through the inherited round loop: the pseudo-gradient is
    ``global - avg`` where ``avg`` is already the renormalized average
    over the *reporting* subset, so a degraded round steps the server
    optimizer on the surviving cohort's consensus -- exactly the
    Bonawitz-style partial aggregate, never a zero-biased one."""

    def __init__(self, dataset, spec, args, mesh=None, metrics_logger=None,
                 compressor=None):
        server_tx = get_server_optimizer(
            getattr(args, "server_optimizer", "sgd"),
            getattr(args, "server_lr", 1.0),
            momentum=getattr(args, "server_momentum", 0.9))
        payload_fn, server_fn = make_fedopt_hooks(server_tx)
        # compressor= composes transparently: the compressed round feeds
        # RECONSTRUCTED client states through payload_fn, so the server
        # optimizer steps on the pseudo-gradient that survived compression
        super().__init__(dataset, spec, args, mesh=mesh,
                         payload_fn=payload_fn, server_fn=server_fn,
                         metrics_logger=metrics_logger, compressor=compressor)
        self.server_state = self.place_state(
            server_tx.init(self.global_state["params"]))
