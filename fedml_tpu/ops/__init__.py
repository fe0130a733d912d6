"""TPU compute ops: blockwise/flash attention, ring (sequence-parallel)
attention, and Pallas TPU kernels.

The reference has no attention anywhere -- its sequence models are 2-layer
LSTMs over short fixed windows (SURVEY.md section 5.7) and its only
"long-context" story is truncation in preprocessing. This package is the
net-new long-context layer the TPU rebuild makes first-class:

- :mod:`fedml_tpu.ops.attention` -- single-device blockwise attention with an
  online softmax (flash semantics, O(T) memory in the sequence).
- :mod:`fedml_tpu.ops.ring_attention` -- the same computation with the
  sequence sharded over a mesh axis; K/V blocks rotate around the ring via
  ``ppermute`` over ICI while every shard keeps only its own Q.
- :mod:`fedml_tpu.ops.pallas_attention` -- fused flash attention as three
  Pallas TPU kernels (forward, dq, dk/dv: VMEM-blocked, MXU matmuls), their
  tiles chosen from the shape by ``flash_schedule``.
- :mod:`fedml_tpu.ops.grouped_matmul` -- the grouped matrix product over the
  experts a chip holds (rows sorted by expert, no drop), forward and backward
  (imported as a module: its function has the module's name).
- :mod:`fedml_tpu.ops.cross_entropy` -- softmax cross-entropy with the
  accuracy counter's arg-max as one op with its own backward: no
  ``[..., vocab]`` array of log-probabilities is written or kept.
"""

from fedml_tpu.ops.attention import blockwise_attention, mha
from fedml_tpu.ops.pallas_attention import flash_attention
from fedml_tpu.ops.ring_attention import make_ring_attention, ring_attention

__all__ = ["blockwise_attention", "mha", "ring_attention",
           "make_ring_attention", "flash_attention"]
