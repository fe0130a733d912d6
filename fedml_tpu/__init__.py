"""fedml_tpu: a TPU-native federated learning framework.

A ground-up JAX/XLA/pjit re-design of the capabilities of FedML
(arXiv:2007.13518; reference layout documented in SURVEY.md). Instead of
one-OS-process-per-client exchanging pickled state dicts over MPI, a federated
round here is a single SPMD program: per-client local training is vmapped (one
chip) or shard_mapped over a ``clients`` mesh axis (pod slice), and the
server's weighted average is an XLA ``psum`` riding the ICI.

Layers (mirroring reference layers, see SURVEY.md section 1):
  - ``fedml_tpu.core``       -- L0/L1: pytree math, message/control plane,
                                 partitioners, topology, robustness, trainer seam.
  - ``fedml_tpu.models``     -- L2a: Flax model zoo.
  - ``fedml_tpu.data``       -- L2b: federated dataset loaders (8-tuple contract).
  - ``fedml_tpu.algorithms`` -- L3: FL algorithms on the common round engine.
  - ``fedml_tpu.parallel``   -- mesh construction + the SPMD round engine.
  - ``fedml_tpu.experiments``-- L4: argparse-compatible entry points.
  - ``fedml_tpu.observability`` -- fedtrace: round tracing, metrics
                                 registry, control-plane flight recorder.
"""

__version__ = "0.1.0"

# the process's start-up is on the record from here (stdlib only: the
# transports stay importable without jax); observability/tracing.py
# `startup_report` says where it ends
from fedml_tpu.observability.tracing import begin_startup as _begin_startup

_begin_startup()
