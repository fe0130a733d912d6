"""One round of a directly-built ``BucketedStreamRunner`` over a list of
client shards: the runner owns its feed (``shards``, ``data_rng``) and its
fold's policy (``aggregator``, ``async_window``, ``residual_store``), so
the tests that vary them per round set them here and call the contract's
``run_round(global_state, server_state, client_indexes, rng)``."""

import numpy as np


def stream_round(runner, global_state, server_state, datasets, rng,
                 data_rng=None, aggregator=None, async_window=4,
                 residual_store=None, client_ids=None):
    ids = list(client_ids if client_ids is not None
               else range(len(datasets)))
    runner.shards = dict(zip(ids, datasets))
    runner.data_rng = data_rng or np.random.default_rng(0)
    runner.aggregator, runner.async_window = aggregator, async_window
    runner.residual_store = residual_store
    return runner.run_round(global_state, server_state, ids, rng)
