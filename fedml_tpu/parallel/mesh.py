"""Device-mesh construction for federated rounds.

The reference maps one FL client to one OS process via ``mpirun -np N+1``
(``run_fedavg_distributed_pytorch.sh:18-38``). Here clients map to shards of a
``clients`` mesh axis; aggregation collectives ride ICI within a slice and DCN
across slices. A second optional ``model`` axis supports tensor-sharding large
server models (FedGKT) without changing the round program.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


CLIENT_AXIS = "clients"
MODEL_AXIS = "model"
#: the name ``BucketedStreamRunner``'s ``chunk_fn`` gives its lane ``vmap``
LANE_AXIS = "lanes"


def any_lane(flag):
    """``flag`` (a boolean scalar of one lane) in ANY lane of the chunk.

    Under a ``jax.vmap`` that names its axis ``LANE_AXIS`` the result is
    the same in every lane, so a ``lax.cond`` on it stays a conditional:
    one branch runs, for all lanes at once. A ``cond`` on a lane's own
    flag is batched into a select and both branches run. Where no such
    axis is bound (a direct call, the other runners) the flag comes back
    as it is: the same results, both branches paid for under a ``vmap``."""
    try:
        return jax.lax.pmax(flag.astype(jnp.int32), LANE_AXIS) > 0
    except NameError:
        return flag


def make_2d_mesh(n_a: int, n_b: int, axis_names, devices=None):
    """Generic ``(n_a, n_b)`` device grid -- the shared constructor behind
    the dp x sp / dp x tp / dp x ep meshes (each just names the axes)."""
    devices = list(jax.devices()) if devices is None else list(devices)
    need = n_a * n_b
    if need > len(devices):
        raise ValueError(f"mesh needs {need} devices, have {len(devices)}")
    return Mesh(np.array(devices[:need]).reshape(n_a, n_b),
                tuple(axis_names))


def make_client_mesh(n_client_shards=None, n_model_shards=1, devices=None):
    """Build a ``(clients, model)`` mesh over available devices.

    ``n_client_shards`` defaults to all devices / n_model_shards. On a single
    chip this yields a 1x1 mesh -- the same round program runs unchanged, which
    is how standalone simulation and pod execution share one code path.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    if n_client_shards is None:
        n_client_shards = len(devices) // n_model_shards
    need = n_client_shards * n_model_shards
    if need > len(devices):
        raise ValueError(
            f"mesh needs {need} devices, have {len(devices)}")
    grid = np.array(devices[:need]).reshape(n_client_shards, n_model_shards)
    return Mesh(grid, (CLIENT_AXIS, MODEL_AXIS))


def client_sharding(mesh):
    """Sharding for arrays with a leading client axis."""
    return NamedSharding(mesh, P(CLIENT_AXIS))


def replicated_sharding(mesh):
    return NamedSharding(mesh, P())


def zero_pad_leading(tree, pad, xp=np):
    """Zero-pad every leaf's leading (client) axis by ``pad`` rows.

    THE dummy-client invariant, shared by every engine path (WaveRunner
    waves, the flat indexed round's chunk padding, mesh sharding): padded
    clients carry ``n``=0 and fully-masked schedules, so they are
    zero-weight in aggregation and every training step they touch is
    guarded to a no-op. ``xp`` selects numpy (host) or jax.numpy
    (inside jit)."""
    if not pad:
        return tree
    z = lambda a: xp.concatenate(
        [a, xp.zeros((pad,) + a.shape[1:], a.dtype)])
    return jax.tree.map(z, tree)


def pad_cohort_to_multiple(cohort_data, multiple):
    """Pad the cohort's client axis to a multiple of ``multiple`` with
    zero-weight dummy clients, so cohorts that don't divide the mesh still
    shard (``shard_map`` needs even shards)."""
    C = len(next(iter(cohort_data.values())))
    cohort_data = {k: np.asarray(v) for k, v in cohort_data.items()}
    return zero_pad_leading(cohort_data, (-C) % multiple)


def shard_cohort(mesh, cohort_data):
    """Place a packed cohort dict (leading axis = clients) onto the mesh,
    padding to the mesh's client-axis size first when needed."""
    cohort_data = pad_cohort_to_multiple(cohort_data, mesh.shape[CLIENT_AXIS])
    sh = client_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sh), cohort_data)
