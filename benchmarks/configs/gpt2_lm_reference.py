"""Plain reference of the federated GPT-2-shaped LM round, and its inputs.

Imports nothing of fedml_tpu. Everything a run needs from ``--seed`` is
made here: the clients' token shards, the initial weights, and (through
``benchmarks/feed.py``) the order in which rows are fed. The model is the
GPT-2 block as the configuration file states it *as run* (pre-LN, learned
positions, fused qkv without bias, tanh GELU, untied head), written in
straightforward ``jax.numpy`` float32 at matmul precision ``highest``:
no kernel, no cache, no lanes. A federated round is: every client starts
from the global weights, takes its local SGD steps over its own rows in
feed order, and the server takes the sample-weighted mean.

``variant`` selects the reference itself (``f32``), the control
(``fp8``: every matmul operand rounded to e4m3 with a per-tensor scale,
straight-through gradient; the step below bf16 that would tempt a later
PR) or a planted fault (``half_batch``: the second half of every batch
left out, the mean taken over the rest).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

VARIANTS = ("f32", "fp8", "half_batch")
_HI = jax.lax.Precision.HIGHEST


def sizes(config):
    """The sizes the reference needs, from the configuration file."""
    run = config.get("as_run", {})
    return {
        "d": int(config["n_embd"]), "heads": int(config["n_head"]),
        "inner": int(config["n_inner"]), "layers": int(config["n_layer"]),
        "vocab": int(config["vocab_size"]),
        "positions": int(config["n_positions"]),
        "eps": float(run.get("layer_norm_epsilon",
                             config["layer_norm_epsilon"])),
        "init_std": float(config.get("initializer_range", 0.02)),
    }


def param_shapes(config):
    """Canonical leaf name -> shape; the names are the program's tree
    paths joined by '/', so the family's mapping is a plain rename."""
    s = sizes(config)
    d, v = s["d"], s["vocab"]
    shapes = {"tok_embed/embedding": (v, d),
              "pos_embed/embedding": (s["positions"], d),
              "ln_f/scale": (d,), "ln_f/bias": (d,),
              "head/kernel": (d, v), "head/bias": (v,)}
    for i in range(s["layers"]):
        b = f"block{i}/"
        shapes.update({
            b + "ln1/scale": (d,), b + "ln1/bias": (d,),
            b + "qkv/kernel": (d, 3 * d), b + "proj/kernel": (d, d),
            b + "ln2/scale": (d,), b + "ln2/bias": (d,),
            b + "mlp_up/kernel": (d, s["inner"]),
            b + "mlp_up/bias": (s["inner"],),
            b + "mlp_down/kernel": (s["inner"], d),
            b + "mlp_down/bias": (d,)})
    return shapes


def make_weights(config, seed):
    """Initial weights from the seed, float32, in one jitted call on the
    default device (GPT-2's scheme: normal(0, std), residual outputs
    scaled by 1/sqrt(2 layers), LayerNorm scale 1; biases get a small
    normal instead of 0 so that every leaf's rows differ)."""
    s = sizes(config)
    shapes = param_shapes(config)
    names = sorted(shapes)
    resid = s["init_std"] / math.sqrt(2 * s["layers"])

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(names):
            k = jax.random.fold_in(key, i)
            shape = shapes[name]
            if name.endswith("/scale"):
                out[name] = 1.0 + 0.02 * jax.random.normal(k, shape)
            elif name.endswith("/bias"):
                out[name] = 0.002 * jax.random.normal(k, shape)
            elif name.endswith(("proj/kernel", "mlp_down/kernel")):
                out[name] = resid * jax.random.normal(k, shape)
            else:
                out[name] = s["init_std"] * jax.random.normal(k, shape)
        return out

    return make(jax.random.PRNGKey(np.uint32(seed % (2 ** 32))))


def _seeded_order(rng, workload):
    counts = [int(n) for n in workload["sequences_per_client"]]
    return [counts[i] for i in rng.permutation(len(counts))]


def client_sizes(workload, seed):
    """Sequences per client in this seed's order: the cell's fixed list,
    permuted (the first draw of ``make_clients``'s generator)."""
    return _seeded_order(np.random.default_rng([int(seed), 1]), workload)


def make_clients(config, workload, seed):
    """Token shards: ``[{"x": [n, T] int32, "y": [n, T] int32}]``, one per
    client. The shard sizes are the cell's fixed list in a seeded order;
    ids are drawn from [1, vocab) (0 is the loss's ignore id, so every
    token counts); ``y`` is ``x`` shifted by one with a fresh last id."""
    s = sizes(config)
    t = int(workload["seq_len"])
    rng = np.random.default_rng([int(seed), 1])
    counts = _seeded_order(rng, workload)
    clients = []
    for n in counts:
        ids = rng.integers(1, s["vocab"], size=(n, t + 1), dtype=np.int32)
        clients.append({"x": np.ascontiguousarray(ids[:, :-1]),
                        "y": np.ascontiguousarray(ids[:, 1:])})
    return clients


# -- the model ---------------------------------------------------------------

def _ste_e4m3(x):
    """Round to float8 e4m3 with a per-tensor scale; identity gradient."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def _dot(a, b, variant):
    if variant == "fp8":
        a, b = _ste_e4m3(a), _ste_e4m3(b)
    return jnp.matmul(a, b, precision=_HI)


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(params, idx, s, variant="f32"):
    """Token ids ``[B, T]`` -> logits ``[B, T, vocab]`` (float32)."""
    b, t = idx.shape
    h, d = s["heads"], s["d"]
    hd = d // h
    x = params["tok_embed/embedding"][idx] + params["pos_embed/embedding"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(s["layers"]):
        p = f"block{i}/"
        y = _layer_norm(x, params[p + "ln1/scale"], params[p + "ln1/bias"],
                        s["eps"])
        qkv = _dot(y, params[p + "qkv/kernel"], variant)
        q, k, v = (z.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
                   for z in jnp.split(qkv, 3, axis=-1))
        att = _dot(q, k.transpose(0, 1, 3, 2), variant) / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        y = _dot(att, v, variant).transpose(0, 2, 1, 3).reshape(b, t, d)
        x = x + _dot(y, params[p + "proj/kernel"], variant)
        y = _layer_norm(x, params[p + "ln2/scale"], params[p + "ln2/bias"],
                        s["eps"])
        y = _gelu_tanh(_dot(y, params[p + "mlp_up/kernel"], variant)
                       + params[p + "mlp_up/bias"])
        x = x + _dot(y, params[p + "mlp_down/kernel"], variant) \
            + params[p + "mlp_down/bias"]
    x = _layer_norm(x, params["ln_f/scale"], params["ln_f/bias"], s["eps"])
    return _dot(x, params["head/kernel"], variant) + params["head/bias"]


def step_loss(params, x, y, s, variant="f32"):
    """Mean next-token NLL over the tokens that count (``y != 0``) of the
    rows that count, and the (sum, count) the round's loss is made of."""
    rows = jnp.ones((x.shape[0],), jnp.float32)
    if variant == "half_batch":
        rows = rows.at[(x.shape[0] + 1) // 2:].set(0.0)
    logits = forward(params, x, s, variant)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    w = (y != 0).astype(jnp.float32) * rows[:, None]
    total, count = jnp.sum(nll * w), jnp.sum(w)
    return total / jnp.maximum(count, 1.0), (total, count)


@functools.lru_cache(maxsize=None)
def _client_update(config_key, variant):
    """One compiled local-SGD loop for every client of a cell: the rows
    are padded to the longest client and ``trip`` (traced) says how many
    steps are real."""
    s = dict(config_key)

    @jax.jit
    def run(params, xs, ys, trip, lr, wd):
        def body(i, carry):
            p, tot, cnt = carry
            (_, (t, c)), g = jax.value_and_grad(
                lambda q: step_loss(q, xs[i], ys[i], s, variant),
                has_aux=True)(p)
            p = jax.tree.map(lambda a, b: a - lr * (b + wd * a), p, g)
            return p, tot + t, cnt + c

        return jax.lax.fori_loop(0, trip, body, (params, 0.0, 0.0))

    return run


def run_rounds(config, workload, seed, rounds, feed, variant="f32"):
    """Follow ``rounds`` federated rounds from the seed.

    ``feed[r][c]`` is client ``c``'s list of per-step row indices in
    round ``r`` (``benchmarks/feed.py``). Returns ``{"loss": [per round],
    "change_norms": [per round: leaf -> norm of (global weights minus the
    initial ones)], "init": leaf -> initial weights (float32, on the
    device)}``. Only norms are kept of each round's weights, so that the
    reference fits beside its own activations."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    s = sizes(config)
    clients = make_clients(config, workload, seed)
    init = make_weights(config, seed)
    lr, wd = float(workload["lr"]), float(workload.get("wd", 0.0))
    update = _client_update(tuple(sorted(s.items())), variant)
    s_max = max(len(steps) for rnd in feed for steps in rnd)
    batch = int(workload["batch_size"])
    t = int(workload["seq_len"])
    n_total = float(sum(len(c["y"]) for c in clients))
    zero = jax.tree.map(jnp.zeros_like, init)
    norms_of = jax.jit(lambda a, b: {
        k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a})
    glob, losses, norms = init, [], []
    for rnd in feed[:rounds]:
        acc, tot, cnt = zero, 0.0, 0.0
        for c, steps in enumerate(rnd):
            xs = np.zeros((s_max, batch, t), np.int32)
            ys = np.zeros((s_max, batch, t), np.int32)
            for i, rows in enumerate(steps):
                xs[i, :len(rows)] = clients[c]["x"][rows]
                ys[i, :len(rows)] = clients[c]["y"][rows]
            local, t_c, c_c = update(glob, jnp.asarray(xs), jnp.asarray(ys),
                                     jnp.int32(len(steps)), lr, wd)
            w = len(clients[c]["y"]) / n_total
            acc = jax.tree.map(lambda a, p, g: a + w * (p - g),
                               acc, local, glob)
            tot, cnt = tot + float(t_c), cnt + float(c_c)
        glob = jax.tree.map(jnp.add, glob, acc)
        losses.append(tot / max(cnt, 1.0))
        norms.append({k: float(v) for k, v in norms_of(glob, init).items()})
    return {"loss": losses, "change_norms": norms, "init": init}
