"""TrainSpec builders: wrap a Flax model into the pure-function trainer triple.

These are the TPU equivalents of the reference's task-specific ModelTrainers
(``my_model_trainer_classification.py`` / ``..._nwp.py`` / selected per
dataset at ``fedml_experiments/standalone/fedavg/main_fedavg.py:269-275``):
the loss/metric conventions match so accuracy curves are comparable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.core.trainer import TrainSpec
from fedml_tpu.ops.cross_entropy import softmax_cross_entropy_with_stats
from fedml_tpu.ops.row_embed import ROW_STEP


def _apply_model(model, state, x, rng, train, with_sown=False,
                 with_metrics=False):
    """Apply with train-time collection handling.

    ``with_sown=True`` (the loss_fn path in every spec) also collects
    losses the model sows (the MoE load-balancing aux, ``models/moe.py``)
    and returns ``(out, new_state, aux_scalar)``; aux is 0.0 for models
    that sow nothing, so non-MoE behavior is unchanged. ``with_sown=
    False`` (eval/metrics path) returns ``(out, new_state)`` -- sow is a
    no-op when the collection is not mutable. ``with_metrics=True`` (with
    ``with_sown``) appends the counters the model sows into ``metrics``
    (the routing counters of ``models/deepseek_v3.py``), summed by name
    over the modules that sowed them: ``{}`` for a model that sows none.
    A state that carries the row step's collection (``ops/row_embed.py``
    ``ROW_STEP``, put there by the client-update loop alone) gets it back
    mutated: the ids each lookup read.
    """
    variables = dict(state)
    rngs = ({"dropout": rng, "droppath": jax.random.fold_in(rng, 7)}
            if (train and rng is not None) else None)
    mutable = ((["losses"] if with_sown else [])
               + (["metrics"] if with_metrics else [])
               + (["batch_stats"]
                  if ("batch_stats" in state and train) else [])
               + ([ROW_STEP] if ROW_STEP in state else []))
    if not mutable:
        out = model.apply(variables, x, train=train, rngs=rngs)
        return out, state
    out, mutated = model.apply(variables, x, train=train, mutable=mutable,
                               rngs=rngs)
    new_state = state
    for kept in ("batch_stats", ROW_STEP):
        if kept in mutated:
            new_state = dict(new_state)
            new_state[kept] = mutated[kept]
    if not with_sown:
        return out, new_state
    aux = sum(jax.tree.leaves(mutated.get("losses", {})), 0.0)
    if not with_metrics:
        return out, new_state, aux
    sown = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            mutated.get("metrics", {}))[0]:
        name = str(getattr(path[-1], "key", path[-1]))
        sown[name] = sown.get(name, 0.0) + leaf
    return out, new_state, aux, sown


def _init_state(model, example_x, rng):
    """Shared spec init: sown diagnostics (e.g. the MoE aux loss) are
    per-apply values, not model state -- they must not enter the
    aggregated pytree."""
    variables = dict(model.init(rng, example_x, train=False))
    variables.pop("losses", None)
    variables.pop("metrics", None)
    return variables


def make_classification_spec(model, example_x, num_classes=None,
                             name="classification", augment_fn=None,
                             aux_loss_weight=0.01, lane_lowering=None):
    """Softmax cross-entropy classification over ``[B, C]`` logits.

    Applying the softmax to whatever the model emits reproduces the reference
    LR quirk automatically (sigmoid output fed to torch CrossEntropyLoss,
    ``lr.py:10-11``). Metrics are *sums* (loss-weighted, correct, count);
    divide on host -- matching the reference's test accumulation
    (``my_model_trainer_classification.py`` test loop).

    ``augment_fn(x, rng)``: optional on-device train-time augmentation
    (``fedml_tpu.data.augment``), applied per step inside client updates.
    """

    def init_fn(rng):
        return _init_state(model, example_x, rng)

    def _loss_and_metrics(logits, y, mask):
        ll, pred = softmax_cross_entropy_with_stats(
            logits.astype(jnp.float32), y)
        per_sample = -ll
        count = jnp.sum(mask)
        loss = jnp.sum(per_sample * mask) / jnp.maximum(count, 1.0)
        correct = jnp.sum((pred == y) * mask)
        metrics = {"loss_sum": jnp.sum(per_sample * mask),
                   "correct": correct, "count": count}
        return loss, metrics

    def loss_fn(state, batch, rng, train):
        logits, new_state, aux = _apply_model(model, state, batch["x"],
                                              rng, train, with_sown=True)
        loss, metrics = _loss_and_metrics(logits, batch["y"], batch["mask"])
        return loss + aux_loss_weight * aux, (new_state, metrics)

    def metrics_fn(state, batch):
        logits, _ = _apply_model(model, state, batch["x"], None, False)
        _, metrics = _loss_and_metrics(logits, batch["y"], batch["mask"])
        return metrics

    # MXU-shaped packed-lane path (wave_mode=3): the lane_packed registry
    # owns which model families have a packed lowering (None otherwise --
    # runners fall back to the vmap lane path); this module stays
    # model-agnostic
    from fedml_tpu.models.lane_packed import builder_for

    if lane_lowering not in (None, "blockdiag", "bgc", "auto"):
        # fail at the API boundary, not hours later at lane setup
        raise ValueError(f"unknown lane_lowering {lane_lowering!r}; "
                         "choose blockdiag, bgc or auto")
    return TrainSpec(init_fn=init_fn, loss_fn=loss_fn, metrics_fn=metrics_fn,
                     name=name, augment_fn=augment_fn,
                     lane_loss_builder=builder_for(
                         model, lowering=lane_lowering))


def make_seq_classification_spec(model, example_x, ignore_index=0,
                                 name="nwp", aux_loss_weight=0.01):
    """Per-token cross-entropy over ``[B, T, V]`` logits with padding-id
    masking -- semantics of the reference NWP trainer
    (``my_model_trainer_nwp.py:24``: ``CrossEntropyLoss(ignore_index=0)``).
    Token mask = sample mask x (y != ignore_index).

    Losses the model sows (the MoE load-balancing aux,
    ``models/moe.py``) are added at ``aux_loss_weight`` during training
    -- federated MoE trains with balanced routing out of the box.
    Counters the model sows into ``metrics`` (``models/deepseek_v3.py``'s
    routing counters) join the step's metric sums under their own names.
    """

    def init_fn(rng):
        return _init_state(model, example_x, rng)

    def _loss_and_metrics(logits, y, mask):
        tok_mask = (y != ignore_index).astype(jnp.float32) * mask[:, None]
        ll, pred = softmax_cross_entropy_with_stats(
            logits.astype(jnp.float32), y)
        count = jnp.sum(tok_mask)
        loss = jnp.sum(-ll * tok_mask) / jnp.maximum(count, 1.0)
        correct = jnp.sum((pred == y) * tok_mask)
        return loss, {"loss_sum": jnp.sum(-ll * tok_mask),
                      "correct": correct, "count": count}

    def loss_fn(state, batch, rng, train):
        logits, new_state, aux, sown = _apply_model(
            model, state, batch["x"], rng, train, with_sown=True,
            with_metrics=True)
        loss, metrics = _loss_and_metrics(logits, batch["y"], batch["mask"])
        # a step of padding only (a ragged lane's tail) counts nothing
        live = (jnp.sum(batch["mask"]) > 0).astype(jnp.float32)
        metrics.update({k: v * live for k, v in sown.items()})
        return loss + aux_loss_weight * aux, (new_state, metrics)

    def metrics_fn(state, batch):
        logits, _ = _apply_model(model, state, batch["x"], None, False)
        _, metrics = _loss_and_metrics(logits, batch["y"], batch["mask"])
        return metrics

    return TrainSpec(init_fn=init_fn, loss_fn=loss_fn, metrics_fn=metrics_fn,
                     name=name)


def make_block_diffusion_lm_spec(model, example_x, block_length, mask_id,
                                 name="block_diffusion_lm"):
    """Block-diffusion LM training (BD3-LM's vectorised form): one pass
    over a clean copy and a noised copy of every sequence.

    Batch: ``x`` ``[n, L]`` int32 clean ids; ``y`` ``[n, L]`` float32, the
    corruption as data: ``block_length / k`` at the ``k`` positions of a
    block that are masked, 0 elsewhere (mask and weight in one array).
    The spec builds ``x_t = where(y > 0, mask_id, x)`` and feeds ``[x ;
    x_t]`` (``2 L`` ids) to ``model``, which returns the noised half's
    logits ``[n, L, V]`` (``models/deepseek_v3.py`` ``DecoderLM`` with
    ``block_length``); the logits AT a masked position predict that
    position's clean id, no shift. Loss: ``sum_i y_i CE_i / (n L)`` over
    the rows that count. Metric sums: ``loss_sum`` (the cross-entropy at
    the masked positions, unweighted), ``count`` (masked positions),
    ``correct``, ``bd_positions`` (the ``2 L`` positions a row that
    counts runs through the model) and the routing counters the model
    sows, as the sequence spec has them."""
    length = example_x.shape[1]
    theirs = getattr(getattr(model, "cfg", None), "block_length",
                     block_length)
    if length % block_length or theirs != block_length:
        raise ValueError(
            f"block diffusion: sequences of {length} ids in blocks of "
            f"{block_length}; the model masks blocks of {theirs}")

    def both_copies(x, y):
        return jnp.concatenate([x, jnp.where(y > 0, mask_id, x)], axis=1)

    def init_fn(rng):
        return _init_state(model, both_copies(
            example_x, jnp.zeros(example_x.shape, jnp.float32)), rng)

    def _loss_and_metrics(logits, x, y, mask):
        weight = y.astype(jnp.float32) * mask[:, None]
        masked = (weight > 0).astype(jnp.float32)
        ll, pred = softmax_cross_entropy_with_stats(
            logits.astype(jnp.float32), x)
        nll = -ll
        rows = jnp.sum(mask)
        loss = jnp.sum(nll * weight) / jnp.maximum(rows * length, 1.0)
        return loss, {
            "loss_sum": jnp.sum(nll * masked), "count": jnp.sum(masked),
            "correct": jnp.sum((pred == x) * masked),
            "bd_positions": 2.0 * length * rows}

    def loss_fn(state, batch, rng, train):
        logits, new_state, _, sown = _apply_model(
            model, state, both_copies(batch["x"], batch["y"]), rng, train,
            with_sown=True, with_metrics=True)
        loss, metrics = _loss_and_metrics(logits, batch["x"], batch["y"],
                                          batch["mask"])
        # a step of padding only (a ragged lane's tail) counts nothing
        live = (jnp.sum(batch["mask"]) > 0).astype(jnp.float32)
        metrics.update({k: v * live for k, v in sown.items()})
        return loss, (new_state, metrics)

    def metrics_fn(state, batch):
        logits, _ = _apply_model(
            model, state, both_copies(batch["x"], batch["y"]), None, False)
        return _loss_and_metrics(logits, batch["x"], batch["y"],
                                 batch["mask"])[1]

    return TrainSpec(init_fn=init_fn, loss_fn=loss_fn, metrics_fn=metrics_fn,
                     name=name)


def block_diffusion_counters(metrics) -> dict:
    """The round's ``bd.loss_tokens`` (masked positions that carried loss)
    and ``bd.positions`` (positions run through the model, both copies)
    from the metric sums of :func:`make_block_diffusion_lm_spec`; ``{}``
    for any other spec."""
    if not metrics or "bd_positions" not in metrics:
        return {}
    total = lambda k: float(np.sum(np.asarray(metrics[k])))
    return {"bd.loss_tokens": total("count"),
            "bd.positions": total("bd_positions")}


def make_segmentation_spec(model, example_x, num_classes,
                           ignore_index=255, name="segmentation",
                           aux_loss_weight=0.01):
    """Per-pixel cross-entropy over ``[B, H, W, C]`` logits with
    ignore-label masking (reference FedSeg ``MyModelTrainer`` loss). Metrics
    carry a summed ``[C, C]`` confusion matrix so the aggregator computes
    mIoU/FWIoU exactly (``fedseg/utils.py:246-288``)."""
    from fedml_tpu.core.seg_eval import confusion_matrix

    def init_fn(rng):
        return _init_state(model, example_x, rng)

    def _loss_and_metrics(logits, y, mask):
        y = y.astype(jnp.int32)
        pix_mask = ((y != ignore_index) & (y >= 0) &
                    (y < num_classes)).astype(jnp.float32)
        pix_mask = pix_mask * mask.reshape(mask.shape + (1,) * (y.ndim - 1))
        # (an ignore label outside the classes hits no column of the op)
        ll, pred = softmax_cross_entropy_with_stats(
            logits.astype(jnp.float32), y)
        count = jnp.sum(pix_mask)
        loss = jnp.sum(-ll * pix_mask) / jnp.maximum(count, 1.0)
        correct = jnp.sum((pred == y) * pix_mask)
        cm = confusion_matrix(jnp.where(pix_mask > 0, y, -1), pred,
                              num_classes)
        metrics = {"loss_sum": jnp.sum(-ll * pix_mask), "correct": correct,
                   "count": count, "confusion": cm}
        return loss, metrics

    def loss_fn(state, batch, rng, train):
        logits, new_state, aux = _apply_model(model, state, batch["x"],
                                              rng, train, with_sown=True)
        loss, metrics = _loss_and_metrics(logits, batch["y"], batch["mask"])
        return loss + aux_loss_weight * aux, (new_state, metrics)

    def metrics_fn(state, batch):
        logits, _ = _apply_model(model, state, batch["x"], None, False)
        _, metrics = _loss_and_metrics(logits, batch["y"], batch["mask"])
        return metrics

    return TrainSpec(init_fn=init_fn, loss_fn=loss_fn, metrics_fn=metrics_fn,
                     name=name)


def make_multilabel_spec(model, example_x, name="tag_prediction",
                         aux_loss_weight=0.01):
    """Sigmoid BCE multilabel (reference ``my_model_trainer_tag_prediction.py``
    for stackoverflow_lr: BCELoss + top-k precision/recall style counts)."""

    def init_fn(rng):
        return _init_state(model, example_x, rng)

    def _loss_and_metrics(probs, y, mask):
        probs = jnp.clip(probs.astype(jnp.float32), 1e-7, 1 - 1e-7)
        per_sample = -jnp.sum(y * jnp.log(probs) + (1 - y) * jnp.log(1 - probs),
                              axis=-1)
        count = jnp.sum(mask)
        loss = jnp.sum(per_sample * mask) / jnp.maximum(count, 1.0)
        pred = (probs > 0.5).astype(jnp.float32)
        tp = jnp.sum(pred * y * mask[:, None])
        fp = jnp.sum(pred * (1 - y) * mask[:, None])
        fn = jnp.sum((1 - pred) * y * mask[:, None])
        return loss, {"loss_sum": jnp.sum(per_sample * mask), "tp": tp,
                      "fp": fp, "fn": fn, "count": count,
                      "correct": tp}  # correct == true positives for acc parity

    def loss_fn(state, batch, rng, train):
        probs, new_state, aux = _apply_model(model, state, batch["x"],
                                             rng, train, with_sown=True)
        loss, metrics = _loss_and_metrics(probs, batch["y"], batch["mask"])
        return loss + aux_loss_weight * aux, (new_state, metrics)

    def metrics_fn(state, batch):
        probs, _ = _apply_model(model, state, batch["x"], None, False)
        _, metrics = _loss_and_metrics(probs, batch["y"], batch["mask"])
        return metrics

    return TrainSpec(init_fn=init_fn, loss_fn=loss_fn, metrics_fn=metrics_fn,
                     name=name)
