"""TurboAggregate: secure aggregation for FedAvg (reference
``fedml_api/distributed/turboaggregate/``: Lagrange/BGW MPC primitives in
``mpc_function.py`` + a plain weighted-average aggregator in
``TA_Aggregator.py:56-85`` -- the shipped aggregate is FedAvg in the clear,
with the MPC machinery alongside; SURVEY.md section 2.2).

Here the local-training phase runs on-device via the shared engine, and the
aggregation phase runs through the additive-masking secure sum
(``fedml_tpu.core.mpc.secure_aggregate``): the server only ever combines
masked shares, never an individual client's update.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.core import mpc
from fedml_tpu.parallel.engine import make_client_update
from fedml_tpu.parallel.packing import pack_cohort


class TurboAggregateAPI(FedAvgAPI):
    """FedAvg loop with the aggregation step replaced by a secure masked sum.
    Extra args: ``mpc_scale`` (fixed-point scale, default 2**16)."""

    def __init__(self, dataset, spec, args, metrics_logger=None):
        super().__init__(dataset, spec, args, metrics_logger=metrics_logger)
        self._client_update = jax.jit(
            jax.vmap(make_client_update(spec, self.cfg),
                     in_axes=(None, 0, 0)))
        self.mpc_scale = getattr(args, "mpc_scale", 2 ** 16)
        # the masking stream: derived from the run seed through the MPC
        # salt (mpc.mask_rng), never an unseeded or constant default
        self._mpc_rng = mpc.mask_rng(getattr(args, "seed", 0))

    def train_one_round(self):
        t0 = time.time()
        packed = pack_cohort(
            [self.train_data_local_dict[i]
             for i in self._sample_cohort(self.round_idx)],
            self.args.batch_size, self.args.epochs, rng=self._data_rng)
        self.rng, round_rng = jax.random.split(self.rng)
        C = packed["mask"].shape[0]
        rngs = jax.random.split(round_rng, C)
        local_states, aux, metrics = self._client_update(
            self.global_state, packed, rngs)

        # host-side secure aggregation of n_i-weighted updates; float64 is
        # deliberate: sample counts are exact integers and the fixed-point
        # encode/decode needs the full 53-bit mantissa for the weight
        # normalization to round-trip (FL105's device-code concern does
        # not apply on the host path)
        ns = np.asarray(aux["n"], np.float64)  # fedlint: disable=FL105
        total_n = max(ns.sum(), 1e-12)
        leaves, treedef = jax.tree.flatten(
            jax.tree.map(np.asarray, local_states))
        agg_leaves = []
        for leaf_idx in range(len(leaves)):
            weighted = [leaves[leaf_idx][c] * (ns[c] / total_n)
                        for c in range(C)]
            agg = mpc.secure_aggregate(weighted, scale=self.mpc_scale,
                                       rng=self._mpc_rng)
            agg_leaves.append(agg.astype(leaves[leaf_idx].dtype))
        self.global_state = jax.tree.unflatten(treedef, agg_leaves)

        m = jax.tree.map(np.asarray, metrics)
        out = {"round": self.round_idx,
               "Train/Loss": float(m["loss_sum"].sum() / max(m["count"].sum(), 1)),
               "Train/Acc": float(m["correct"].sum() / max(m["count"].sum(), 1)),
               "round_time_s": time.time() - t0}
        self.round_idx += 1
        return out
