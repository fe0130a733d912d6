"""Pipeline parallelism (pp): GPipe-style stage-sharded transformer.

Completes the mesh-parallelism inventory next to client-DP
(:mod:`fedml_tpu.parallel.engine`), sp (:mod:`.seq_parallel`) and tp
(:mod:`.tensor_parallel`): transformer blocks shard over a ``stage`` mesh
axis -- ``k = n_layers / n_stages`` consecutive blocks per stage, applied
as one weight-scanned ``lax.scan`` -- and microbatches flow through the
ring: each tick every stage applies its blocks to the activation it holds
and ``ppermute``s the result one hop downstream; after ``M + S - 1`` ticks
all ``M`` microbatches have drained. Backward is ``jax.grad`` straight
through the scanned body: JAX transposes ``ppermute`` to the reverse
rotation (which IS the backward pipeline schedule) and psum-reduces
cotangents of the shared embed/head params, so every device steps
identically.

Embed and head/loss execute ONLY on their owning stages (first and last)
via ``lax.cond`` on ``axis_index`` -- per-device control flow is legal
inside ``shard_map`` as long as no collective hides in a branch; the other
stages skip those FLOPs entirely. Their parameters stay replicated (O(V d)
memory, the price of a uniform optimizer step), but the redundant compute
of the one-block-per-stage prototype is gone.

The reference has no pipeline concept -- its biggest model is served by
replicating it per GPU (``GKTServerTrainer.py:28-29``). This is the
TPU-native answer for models deeper than one chip's HBM.

Restrictions (by design, to stay one compiled program): ``n_layers`` must
be a multiple of ``n_stages`` and the global batch must split into
``n_micro`` equal microbatches.
"""

from __future__ import annotations

import re
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.models.transformer import TransformerLM, _Block, lm_loss

STAGE_AXIS = "stage"


def make_pp_mesh(n_stages: int, devices=None):
    devices = list(jax.devices()) if devices is None else list(devices)
    if n_stages > len(devices):
        raise ValueError(f"mesh needs {n_stages} devices, "
                         f"have {len(devices)}")
    return Mesh(np.array(devices[:n_stages]), (STAGE_AXIS,))


def _count_blocks(params) -> int:
    pat = re.compile(r"^block(\d+)$")
    idxs = sorted(int(m.group(1)) for k in params
                  if (m := pat.match(k)) is not None)
    if idxs != list(range(len(idxs))):
        raise ValueError(f"non-contiguous block keys in params: {idxs}")
    return len(idxs)


def init_pp_params(mesh, rng, example_idx, *, vocab_size, n_heads=4,
                   d_model=256, max_len=2048, mlp_ratio=4,
                   dtype=jnp.float32, attention_fn=None, n_layers=None):
    """Init a ``TransformerLM`` with ``n_layers`` blocks (default: one per
    pipeline stage) and re-layout: per-block params stacked to
    ``[S, k, ...]`` (stage-major, sharded over ``stage``), embeddings /
    final-LN / head replicated.

    Returns ``(params, model)`` where ``model`` carries the architecture
    config the step builder needs. ``model.apply`` on the UN-stacked
    params is the single-device oracle.
    """
    S = mesh.shape[STAGE_AXIS]
    n_layers = S if n_layers is None else int(n_layers)
    if n_layers % S:
        raise ValueError(f"n_layers={n_layers} must be a multiple of the "
                         f"{S}-stage mesh")
    model = TransformerLM(vocab_size=vocab_size, n_layers=n_layers,
                          n_heads=n_heads, d_model=d_model, max_len=max_len,
                          mlp_ratio=mlp_ratio, dtype=dtype,
                          attention_fn=attention_fn)
    vs = model.init(rng, example_idx)
    host = stack_pp_params(vs["params"], S)
    st_sh = jax.tree.map(
        lambda _: NamedSharding(mesh, P(STAGE_AXIS)), host["stages"])
    rep_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                          host["shared"])
    params = {"stages": jax.tree.map(jax.device_put, host["stages"], st_sh),
              "shared": jax.tree.map(jax.device_put, host["shared"],
                                     rep_sh)}
    return params, model


def stack_pp_params(params, n_stages):
    """Single-device TransformerLM params -> the pp layout (host-side, no
    mesh placement): block ``s*k + j`` becomes ``stages[s, j]`` -- stage
    ``s`` owns ``k`` consecutive blocks. For oracle comparisons in tests.
    """
    p = dict(params)
    n_blocks = _count_blocks(p)
    if n_blocks == 0 or n_blocks % n_stages:
        raise ValueError(
            f"model has {n_blocks} blocks -- pp requires a nonzero "
            f"multiple of n_stages={n_stages} (a remainder would silently "
            "ride in 'shared' untrained)")
    k = n_blocks // n_stages
    blocks = [p.pop(f"block{i}") for i in range(n_blocks)]
    stages = [jax.tree.map(lambda *xs: jnp.stack(xs),
                           *blocks[s * k:(s + 1) * k])
              for s in range(n_stages)]
    return {"stages": jax.tree.map(lambda *xs: jnp.stack(xs), *stages),
            "shared": p}


def unstack_pp_params(pp_params, n_stages):
    """Inverse of :func:`stack_pp_params` (e.g. to checkpoint in the
    standard TransformerLM layout)."""
    out = dict(pp_params["shared"])
    k = jax.tree.leaves(pp_params["stages"])[0].shape[1]
    for s in range(n_stages):
        for j in range(k):
            out[f"block{s * k + j}"] = jax.tree.map(
                lambda a, s=s, j=j: a[s, j], pp_params["stages"])
    return out


def make_pp_lm_step(model: TransformerLM, mesh, tx: Optional[Any] = None,
                    n_micro: int = 4):
    """Build ``(prep_fn, step_fn)`` for pp training.

    ``prep_fn(idx, tgt)`` splits ``[B, T]`` into ``[M, B/M, T]``
    microbatches; ``step_fn(params, opt_state, idx_m, tgt_m) -> (params,
    opt_state, loss)`` with params from :func:`init_pp_params`.
    """
    tx = tx if tx is not None else optax.sgd(1e-3)
    S = mesh.shape[STAGE_AXIS]
    if model.n_layers % S:
        raise ValueError(
            f"pp requires whole blocks per stage: model.n_layers="
            f"{model.n_layers} is not a multiple of the {S}-stage mesh")
    block = _Block(model.n_heads, model.mlp_ratio, model.dtype,
                   model.attention_fn)
    tok = nn.Embed(model.vocab_size, model.d_model, dtype=model.dtype)
    pos = nn.Embed(model.max_len, model.d_model, dtype=model.dtype)
    ln_f = nn.LayerNorm(dtype=model.dtype)
    head = nn.Dense(model.vocab_size, dtype=jnp.float32)

    def _body(stage_params, shared, idx, tgt):
        me = jax.lax.axis_index(STAGE_AXIS)
        my_blocks = jax.tree.map(lambda a: a[0], stage_params)  # [k, ...]
        M, mB, T = idx.shape

        def embed(t_idx):
            x = tok.apply({"params": shared["tok_embed"]}, t_idx)
            x = x + pos.apply({"params": shared["pos_embed"]},
                              jnp.arange(T)[None])
            return x.astype(jnp.float32)

        def apply_my_blocks(x):
            # k consecutive blocks, weight-scanned over the leading axis
            def one(h, bp):
                return block.apply({"params": bp}, h), None
            h, _ = jax.lax.scan(one, x.astype(model.dtype), my_blocks)
            return h.astype(jnp.float32)

        zeros = jnp.zeros((mB, T, model.d_model), jnp.float32)
        outs0 = jnp.zeros((M, mB, T, model.d_model), jnp.float32)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t while the queue lasts; other
            # stages skip the embed FLOPs entirely (owning-stage compute)
            x = jax.lax.cond(
                me == 0,
                lambda: jnp.where(t < M,
                                  embed(idx[jnp.minimum(t, M - 1)]), zeros),
                lambda: buf)
            h = apply_my_blocks(x)
            # last stage banks microbatch t - (S - 1) as it completes
            oi = t - (S - 1)
            outs = jnp.where(
                (me == S - 1) & (oi >= 0),
                jax.lax.dynamic_update_index_in_dim(
                    outs, h, jnp.maximum(oi, 0), axis=0),
                outs)
            buf = jax.lax.ppermute(
                h, STAGE_AXIS, [(i, (i + 1) % S) for i in range(S)])
            return (buf, outs), None

        (_, outs), _ = jax.lax.scan(tick, (zeros, outs0),
                                    jnp.arange(M + S - 1))

        # head + loss ONLY on the owning (last) stage; psum replicates the
        # value (and its transpose psum-reduces the shared-param
        # cotangents, so embed/head grads come out replicated too)
        def head_loss(o):
            x = ln_f.apply({"params": shared["ln_f"]},
                           o.reshape(M * mB, T, -1).astype(model.dtype))
            logits = head.apply({"params": shared["head"]},
                                x.astype(jnp.float32))
            return lm_loss(logits, tgt.reshape(M * mB, T))

        local = jax.lax.cond(me == S - 1, head_loss,
                             lambda o: jnp.float32(0.0), outs)
        return jax.lax.psum(local, STAGE_AXIS)

    def prep_fn(idx, tgt):
        B = idx.shape[0]
        if B % n_micro:
            raise ValueError(f"batch {B} not divisible by "
                             f"n_micro={n_micro}")
        shp = (n_micro, B // n_micro) + idx.shape[1:]
        return idx.reshape(shp), tgt.reshape(shp)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step_fn(params, opt_state, idx_m, tgt_m):
        def lf(p):
            sm = jax.shard_map(
                _body, mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P(STAGE_AXIS),
                                       p["stages"]),
                          jax.tree.map(lambda _: P(), p["shared"]),
                          P(), P()),
                out_specs=P(), check_vma=False)
            return sm(p["stages"], p["shared"], idx_m, tgt_m)

        loss, grads = jax.value_and_grad(lf)(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt, loss

    return prep_fn, step_fn


__all__ = ["make_pp_mesh", "init_pp_params", "make_pp_lm_step",
           "stack_pp_params", "unstack_pp_params", "STAGE_AXIS"]
