"""FedGKT: Group Knowledge Transfer (reference ``fedml_api/distributed/
fedgkt/``: clients train a small edge CNN and upload per-batch feature maps +
logits + labels; the server trains a large CNN on those features with
CE + temperature-KL distillation and returns per-client server logits --
``GKTClientTrainer.py:49-129``, ``GKTServerTrainer.py:101-120``, KL
temperature at ``GKTServerTrainer.py:48-49``).

TPU re-design: the client phase is the engine's vmapped local training with a
distillation-augmented loss; the feature-extraction pass and the server phase
are jitted scans. Pass ``mesh=`` (with a ``model`` axis,
``parallel.mesh.make_client_mesh(1, n)``) and the server phase runs under
``shard_map``: each step's sample batch splits over the ``model`` axis,
gradients are ``psum``-averaged and BN statistics ``pmean``-merged across
shards -- the TPU-native form of the reference's ``nn.DataParallel`` over 4
GPUs (``GKTServerTrainer.py:28-29``). ``evaluate()`` is one jitted program
scoring the combined edge->server pipeline over EVERY client's own
extractor and local test shard (the reference server tests on each
client's uploaded test features, ``GKTServerTrainer.py:216-244``).
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from fedml_tpu.parallel.engine import ClientUpdateConfig, make_optimizer
from fedml_tpu.parallel.mesh import MODEL_AXIS
from fedml_tpu.parallel.packing import pack_cohort, pack_eval


def kl_divergence(student_logits, teacher_logits, T):
    """KL(softmax(teacher/T) || softmax(student/T)) * T^2 (Hinton
    distillation, reference ``utils.KL_Loss`` with temperature 3.0)."""
    t = jax.nn.softmax(teacher_logits.astype(jnp.float32) / T)
    log_s = jax.nn.log_softmax(student_logits.astype(jnp.float32) / T)
    log_t = jax.nn.log_softmax(teacher_logits.astype(jnp.float32) / T)
    return jnp.sum(t * (log_t - log_s), axis=-1) * (T * T)


def _masked_ce(logits, y, mask):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ll = jnp.take_along_axis(logp, y[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    return -ll * mask


class FedGKTAPI:
    """Args: ``temperature`` (default 3.0), ``alpha_distill`` (KL weight,
    default 1.0), ``epochs`` (client), ``server_epochs``."""

    def __init__(self, dataset, client_model, server_model, args,
                 mesh=None, metrics_logger=None):
        (_, _, _, self.test_data_global, _, self.train_data_local_dict,
         self.test_data_local_dict, self.class_num) = dataset
        self.args = args
        self.client_model = client_model
        self.server_model = server_model
        self.mesh = None
        if mesh is not None and MODEL_AXIS in mesh.axis_names:
            n_shards = mesh.shape[MODEL_AXIS]
            if n_shards > 1 and args.batch_size % n_shards:
                logging.warning(
                    "fedgkt: batch_size %d not divisible by %d model "
                    "shards; server phase runs unsharded",
                    args.batch_size, n_shards)
            elif n_shards > 1:
                self.mesh = mesh
        self.metrics_logger = metrics_logger or (lambda d: None)
        self.n_clients = len(self.train_data_local_dict)
        self.T = getattr(args, "temperature", 3.0)
        self.alpha = getattr(args, "alpha_distill", 1.0)
        self.server_epochs = getattr(args, "server_epochs", 1)

        cfg = ClientUpdateConfig(
            optimizer=getattr(args, "client_optimizer", "sgd"),
            lr=args.lr, weight_decay=getattr(args, "wd", 0.0))
        self.client_tx = make_optimizer(cfg)
        self.server_tx = make_optimizer(ClientUpdateConfig(
            optimizer=getattr(args, "server_optimizer_gkt", "sgd"),
            lr=getattr(args, "server_lr", args.lr),
            weight_decay=getattr(args, "wd", 0.0)))

        rng = jax.random.PRNGKey(getattr(args, "seed", 0))
        example = jnp.asarray(self.train_data_local_dict[0]["x"][:1])
        self.client_states = jax.vmap(
            lambda k: client_model.init(k, example, train=False)
        )(jax.random.split(jax.random.fold_in(rng, 1), self.n_clients))
        feats, _ = client_model.apply(
            jax.tree.map(lambda v: v[0], self.client_states), example,
            train=False)
        self.server_state = server_model.init(
            jax.random.fold_in(rng, 2), feats, train=False)
        self.server_opt = self.server_tx.init(self.server_state["params"])
        self.rng = rng
        self._data_rng = np.random.default_rng(getattr(args, "seed", 0))
        self.round_idx = 0
        # per-sample teacher logits [C, max_n, classes], aligned to each
        # client's canonical sample order -- round r's server logits are
        # scattered back by slot index so round r+1's reshuffled packing
        # gathers the teacher for the *same sample* (the reference keeps a
        # fixed extraction order for exactly this alignment)
        self._max_n = max(len(d["y"]) for d in self.train_data_local_dict.values())
        self.teacher_logits = np.zeros(
            (self.n_clients, self._max_n, self.class_num), np.float32)
        self.server_logits = None  # last round's per-slot server logits

        self._client_round = jax.jit(self._make_client_round())
        self._server_round = jax.jit(self._make_server_round())
        self._eval_fn = None  # built lazily (jitted all-client pipeline)

    # -- client phase ------------------------------------------------------
    def _make_client_round(self):
        cm, T, alpha = self.client_model, self.T, self.alpha
        tx = self.client_tx

        def one_client(state, data, teacher_logits, rng):
            params = state["params"]
            rest = {k: v for k, v in state.items() if k != "params"}
            opt = tx.init(params)

            def step(carry, xs):
                params, rest, opt = carry
                batch, t_logits = xs

                def loss_fn(p):
                    st = dict(rest); st["params"] = p
                    variables = dict(st)
                    (feats, logits), mut = cm.apply(
                        variables, batch["x"], train=True,
                        mutable=["batch_stats"])
                    ce = _masked_ce(logits, batch["y"], batch["mask"])
                    kl = kl_divergence(logits, t_logits, T) * batch["mask"]
                    count = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
                    loss = (jnp.sum(ce) + alpha * jnp.sum(kl)) / count
                    new_st = dict(st); new_st["batch_stats"] = mut["batch_stats"]
                    correct = jnp.sum(
                        (jnp.argmax(logits, -1) == batch["y"]) * batch["mask"])
                    return loss, (new_st, {"loss_sum": jnp.sum(ce),
                                           "correct": correct,
                                           "count": jnp.sum(batch["mask"])})

                (loss, (new_st, metrics)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                updates, new_opt = tx.update(grads, opt, params)
                new_params = optax.apply_updates(params, updates)
                valid = jnp.sum(batch["mask"]) > 0
                new_rest = {k: new_st[k] for k in rest}
                out = jax.tree.map(lambda a, b: jnp.where(valid, a, b),
                                   (new_params, new_rest, new_opt),
                                   (params, rest, opt))
                return out, metrics

            batches = {k: data[k] for k in ("x", "y", "mask")}
            (params, rest, _), metrics = jax.lax.scan(
                step, (params, rest, opt), (batches, teacher_logits))
            state = dict(rest); state["params"] = params

            # extraction pass: features + logits for every batch (eval mode)
            def extract(_, batch):
                feats, logits = cm.apply(state, batch["x"], train=False)
                return _, (feats, logits)

            _, (feats, logits) = jax.lax.scan(extract, 0, batches)
            msum = jax.tree.map(lambda m: jnp.sum(m, axis=0), metrics)
            return state, feats, logits, msum

        def client_round(client_states, cohort, teacher_logits, rng):
            rngs = jax.random.split(rng, cohort["mask"].shape[0])
            return jax.vmap(one_client)(client_states, cohort,
                                        teacher_logits, rngs)

        return client_round

    # -- server phase ------------------------------------------------------
    def _make_server_round(self):
        sm, T, alpha = self.server_model, self.T, self.alpha
        tx = self.server_tx
        mesh = self.mesh

        n_epochs = self.server_epochs  # static under jit
        sharded = mesh is not None

        def server_round(server_state, server_opt, feats, client_logits,
                         ys, masks):
            """feats [C,S,B,h,w,c] pooled over clients; trains with
            CE + KL vs client logits, returns per-batch server logits.
            Under shard_map the B axis arrives pre-split over the ``model``
            mesh axis; sums/grads/BN stats are psum/pmean-merged so every
            shard steps identically (DataParallel semantics)."""
            C, S = feats.shape[0], feats.shape[1]
            flat = lambda a: a.reshape((C * S,) + a.shape[2:])
            fb, lb, yb, mb = flat(feats), flat(client_logits), flat(ys), flat(masks)

            def epoch(carry, _):
                state, opt = carry

                def step(carry2, xs):
                    state, opt = carry2
                    f, cl, y, m = xs

                    def loss_fn(p):
                        st = dict(state); st["params"] = p
                        logits, mut = sm.apply(st, f, train=True,
                                               mutable=["batch_stats"])
                        ce = _masked_ce(logits, y, m)
                        kl = kl_divergence(logits, cl, T) * m
                        # SUM form: normalized after the (possibly psummed)
                        # count so sharded and unsharded grads agree
                        loss_sum = jnp.sum(ce) + alpha * jnp.sum(kl)
                        new_st = dict(st)
                        if "batch_stats" in mut:
                            new_st["batch_stats"] = mut["batch_stats"]
                        return loss_sum, (new_st, jnp.sum(m))

                    (_, (new_st, cnt)), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(state["params"])
                    if sharded:
                        cnt = jax.lax.psum(cnt, MODEL_AXIS)
                        grads = jax.tree.map(
                            lambda g: jax.lax.psum(g, MODEL_AXIS), grads)
                        if "batch_stats" in new_st:
                            new_st = dict(new_st)
                            new_st["batch_stats"] = jax.tree.map(
                                lambda s: jax.lax.pmean(s, MODEL_AXIS),
                                new_st["batch_stats"])
                    grads = jax.tree.map(
                        lambda g: g / jnp.maximum(cnt, 1.0), grads)
                    updates, new_opt = tx.update(grads, opt, state["params"])
                    new_params = optax.apply_updates(state["params"], updates)
                    new_state = dict(new_st); new_state["params"] = new_params
                    valid = cnt > 0
                    out = jax.tree.map(lambda a, b: jnp.where(valid, a, b),
                                       (new_state, new_opt), (state, opt))
                    return out, ()

                (state, opt), _ = jax.lax.scan(step, (state, opt),
                                               (fb, lb, yb, mb))
                return (state, opt), 0.0

            (server_state, server_opt), _ = jax.lax.scan(
                epoch, (server_state, server_opt), jnp.arange(n_epochs))

            # produce fresh server logits for each client batch (teacher signal)
            def infer(_, xs):
                f, _m = xs
                logits = sm.apply(server_state, f, train=False)
                return _, logits

            _, out_logits = jax.lax.scan(infer, 0, (fb, mb))
            out_logits = out_logits.reshape((C, S) + out_logits.shape[1:])
            return server_state, server_opt, out_logits

        if not sharded:
            return server_round

        # batch-dim sharding over the `model` axis: [C,S,B,...] splits on
        # axis 2; model/optimizer state replicated; logits return sharded
        # on their B axis and reassemble transparently
        data_spec = P(None, None, MODEL_AXIS)
        return jax.shard_map(
            server_round, mesh=mesh,
            in_specs=(P(), P(), data_spec, data_spec, data_spec, data_spec),
            out_specs=(P(), P(), data_spec),
            check_vma=False)

    def train_one_round(self):
        packed = pack_cohort(
            [self.train_data_local_dict[i] for i in range(self.n_clients)],
            self.args.batch_size, self.args.epochs, rng=self._data_rng,
            return_indices=True)
        # gather per-sample teacher logits into this round's slot layout
        ci = np.arange(self.n_clients)[:, None, None]
        teacher = jnp.asarray(self.teacher_logits[ci, packed["idx"]])
        self.rng, rng = jax.random.split(self.rng)
        self.client_states, feats, logits, metrics = self._client_round(
            self.client_states, packed, teacher, rng)
        self.server_state, self.server_opt, self.server_logits = \
            self._server_round(self.server_state, self.server_opt, feats,
                               logits, jnp.asarray(packed["y"]),
                               jnp.asarray(packed["mask"]))
        # scatter fresh server logits back to per-sample alignment
        sl = np.asarray(self.server_logits, np.float32)
        m = packed["mask"] > 0
        client_ids = np.broadcast_to(ci, m.shape)[m]
        self.teacher_logits[client_ids, packed["idx"][m]] = sl[m]
        m = jax.tree.map(np.asarray, metrics)
        out = {"round": self.round_idx,
               "Train/Loss": float(m["loss_sum"].sum() / max(m["count"].sum(), 1)),
               "Train/Acc": float(m["correct"].sum() / max(m["count"].sum(), 1))}
        self.round_idx += 1
        self.metrics_logger(out)
        return out

    def _make_eval(self):
        cm, sm = self.client_model, self.server_model

        @jax.jit
        def eval_fn(client_states, server_state, data):
            def one_client(cstate, d):
                def step(_, batch):
                    feats, _l = cm.apply(cstate, batch["x"], train=False)
                    logits = sm.apply(server_state, feats, train=False)
                    correct = jnp.sum(
                        (jnp.argmax(logits, -1) == batch["y"]) * batch["mask"])
                    return _, {"correct": correct,
                               "count": jnp.sum(batch["mask"])}

                _, ms = jax.lax.scan(step, 0, d)
                return jax.tree.map(jnp.sum, ms)

            ms = jax.vmap(one_client)(client_states, data)
            return jax.tree.map(jnp.sum, ms)

        return eval_fn

    def evaluate(self):
        """End-to-end eval of the combined edge->server pipeline, one jitted
        program over ALL clients: each client's own extractor feeds the
        server model on that client's local test shard (reference
        ``GKTServerTrainer`` tests on every client's uploaded test
        features). Falls back to the global test set routed through every
        extractor when local shards are absent."""
        if self._eval_fn is None:
            self._eval_fn = self._make_eval()
        shards, sel = [], []
        for i in range(self.n_clients):
            d = self.test_data_local_dict.get(i)
            if d is not None and len(d["y"]):
                shards.append(d)
                sel.append(i)
        if not shards:
            shards = [self.test_data_global] * self.n_clients
            sel = list(range(self.n_clients))
        packs = [pack_eval(d, self.args.batch_size) for d in shards]
        S = max(p["mask"].shape[0] for p in packs)

        def pad(p):
            w = S - p["mask"].shape[0]
            return {k: np.concatenate(
                [v, np.zeros((w,) + v.shape[1:], v.dtype)]) if w else v
                for k, v in p.items()}

        data = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)),
                            *[pad(p) for p in packs])
        states = jax.tree.map(lambda v: v[np.asarray(sel)],
                              self.client_states)
        m = jax.tree.map(np.asarray,
                         self._eval_fn(states, self.server_state, data))
        return {"Test/Acc": float(m["correct"] / max(m["count"], 1)),
                "Test/Samples": float(m["count"]),
                "Test/Correct": float(m["correct"])}

    def train(self):
        for _ in range(self.args.comm_round):
            out = self.train_one_round()
        return out

