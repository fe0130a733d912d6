"""FL algorithms on the common round engine.

Each module provides aggregator hooks (payload_fn / server_fn) and a
user-facing API class matching the reference's per-algorithm surface
(SURVEY.md sections 2.2-2.3).
"""

from fedml_tpu.observability.tracing import import_span

# the program's import, as the start-up report has it: these modules
# bring in jax, flax, optax and the engine where the caller has not
with import_span(package=__name__):
    from fedml_tpu.algorithms.specs import (  # noqa: F401
        make_classification_spec,
        make_seq_classification_spec,
        make_multilabel_spec,
    )
    from fedml_tpu.algorithms.fedavg import FedAvgAPI  # noqa: F401
    from fedml_tpu.algorithms.fedopt import FedOptAPI  # noqa: F401
    from fedml_tpu.algorithms.fednova import FedNovaAPI  # noqa: F401
    from fedml_tpu.algorithms.fedavg_robust import FedAvgRobustAPI  # noqa: F401
    from fedml_tpu.algorithms.hierarchical import HierarchicalFedAvgAPI  # noqa: F401
    from fedml_tpu.algorithms.decentralized import DecentralizedFedAPI  # noqa: F401
    from fedml_tpu.algorithms.splitnn import SplitNNAPI  # noqa: F401
    from fedml_tpu.algorithms.fedgkt import FedGKTAPI  # noqa: F401
    from fedml_tpu.algorithms.vertical import VerticalFLAPI  # noqa: F401
    from fedml_tpu.algorithms.turboaggregate import TurboAggregateAPI  # noqa: F401
    from fedml_tpu.algorithms.fednas import FedNASAPI, FedNASConfig  # noqa: F401
