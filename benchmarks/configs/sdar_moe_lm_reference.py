"""Plain reference of the federated ``sdar_moe``-shaped block-diffusion LM
round, and its inputs.

Imports nothing of fedml_tpu. Everything a run needs from ``--seed`` is
made here: the clients' token shards WITH their corruption, the initial
weights, and (through ``benchmarks/feed.py``) the order in which rows are
fed. The model is the family's decoder as its public ``config.json``
describes it (``model_type`` ``sdar_moe``, which derives from the
Qwen3-MoE modelling code), written in straightforward ``jax.numpy``
float32 at matmul precision ``highest``: no kernel, no cache, no lanes, no
sort.

- Block, every layer alike: ``h = x + Attn(RMSNorm(x))``, ``y = h +
  MoE(RMSNorm(h))``; a final RMSNorm; a head untied from the embedding; no
  bias anywhere.
- Attention: ``q = x W_q`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k = x W_k`` and ``v = x W_v`` as ``num_key_value_heads``
  heads; RMSNorm with a scale of its own on every head's q and k;
  rotate-half rotary positions (column ``i`` pairs with ``i + head_dim /
  2``) at position ``p(i)``; query head ``h`` reads key/value head ``h //
  (heads / kv heads)``; a materialised softmax of ``q k^T /
  sqrt(head_dim)`` under a dense boolean mask; ``W_o``.
- MoE: ``p = softmax(x W_r)`` over all the router's experts; the
  ``num_experts_per_tok`` largest are chosen; their weights are ``p`` over
  the chosen ones' sum (``norm_topk_prob``); each expert is a gated MLP
  ``W_down(silu(W_gate x) * W_up x)``. No shared expert, no bias, no
  scaling.
- Block-diffusion training (BD3-LM's vectorised form): a sequence ``x_0``
  of ``L`` ids in blocks of ``B``; ``y`` holds the corruption (``B / k``
  at the ``k`` masked positions of a block, 0 elsewhere); ``x_t`` is
  ``x_0`` with the mask id at the masked positions. The model sees ``[x_0
  ; x_t]``, ``2 L`` positions, ``p(i) = i mod L``. With ``b(i) = (i mod L)
  // B`` query ``i`` sees key ``j`` iff both are in ``x_0`` and ``b(j) <=
  b(i)``; or ``i`` is in ``x_t``, ``j`` in ``x_0`` and ``b(j) < b(i)``;
  or both are in ``x_t`` and ``b(j) = b(i)``. The head runs on the
  ``x_t`` half; the logits AT a masked position predict that position's
  clean id (no shift). Loss ``sum_i y_i CE_i / (n L)``.

The chip's share (the configuration's ``deployment``): the router keeps
its published width, and of its experts the ``experts_held = (first,
count)`` are here: a dense masked sum over them, every token through
every held expert and multiplied by its weight there, which is 0 where
the token did not choose it (the held experts side by side in one product
a projection, so that the program stays small). What the absent experts
would add is left out. The vocabulary is the configuration's slice: ids,
logits and loss are over it, and its last id is the mask id.

``variant`` selects the reference itself (``f32``), the control (``fp8``:
every matmul operand rounded to e4m3 with a per-tensor scale,
straight-through gradient) or a planted fault: ``half_batch`` (the second
half of every batch left out and the mean taken over the rest: of its
rows, or, where a batch is one row, of that row's positions) and
``causal_mask`` (the rows of the ``x_t`` half under a plain causal mask
over the ``2 L`` positions).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

VARIANTS = ("f32", "fp8", "half_batch", "causal_mask")
_HI = jax.lax.Precision.HIGHEST


def sizes(config):
    """The sizes the reference needs, from the configuration file (the
    published key names; ``n_layer``, ``n_routed_experts``,
    ``router_experts``, ``experts_held`` and ``block_length`` are the
    file's own, see its ``key_mapping``)."""
    router = int(config.get("router_experts", config["num_experts"]))
    count = int(config.get("n_routed_experts", router))
    held = config.get("experts_held") or (0, count)
    s = {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "expert": int(config["moe_intermediate_size"]),
        "router": router, "first": int(held[0]), "held": int(held[1]),
        "top_k": int(config["num_experts_per_tok"]),
        "layers": int(config.get("n_layer", config["num_hidden_layers"])),
        "vocab": int(config["vocab_size"]),
        "block": int(config["block_length"]),
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "init_std": float(config.get("initializer_range", 0.02)),
    }
    if s["held"] != count:
        raise ValueError("experts_held counts another number of experts "
                         "than n_routed_experts says are here")
    return s


def mask_id(config):
    """The last id of the vocabulary's slice."""
    return int(config["vocab_size"]) - 1


def param_shapes(config):
    """Canonical leaf name -> shape; the names are the program's tree
    paths joined by '/', so the family's mapping is a plain rename."""
    s = sizes(config)
    d, h, kv, hd = s["d"], s["heads"], s["kv_heads"], s["head_dim"]
    shapes = {"tok_embed/embedding": (s["vocab"], d),
              "norm_f/scale": (d,), "head/kernel": (d, s["vocab"])}
    for i in range(s["layers"]):
        p = f"layer{i}/"
        shapes.update({
            p + "attn_norm/scale": (d,), p + "ffn_norm/scale": (d,),
            p + "attn/q_proj/kernel": (d, h * hd),
            p + "attn/k_proj/kernel": (d, kv * hd),
            p + "attn/v_proj/kernel": (d, kv * hd),
            p + "attn/q_norm/scale": (hd,), p + "attn/k_norm/scale": (hd,),
            p + "attn/o_proj/kernel": (h * hd, d),
            p + "moe/router/kernel": (d, s["router"]),
            p + "moe/w_gate": (s["held"], d, s["expert"]),
            p + "moe/w_up": (s["held"], d, s["expert"]),
            p + "moe/w_down": (s["held"], s["expert"], d)})
    return shapes


def make_weights(config, seed):
    """Initial weights from the seed, float32, in one jitted call on the
    default device (normal(0, std), residual outputs scaled by 1/sqrt(2
    layers), norm scales about 1)."""
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
            elif name.endswith(("o_proj/kernel", "w_down")):
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
    """Token shards with their corruption: ``[{"x": [n, L] int32, "y": [n,
    L] float32}]``, one per client. The shard sizes are the cell's fixed
    list in a seeded order; ids are drawn from [1, vocab - 1) of the
    configuration's slice (the last id is the mask id). The corruption
    is drawn ONCE a sequence: in every block ``k`` uniform on {1, ...,
    block}, which ``k`` positions uniform; ``y`` is ``block / k`` there
    and 0 elsewhere."""
    s = sizes(config)
    t, b = int(workload["seq_len"]), s["block"]
    if t % b:
        raise ValueError(f"seq_len {t} is not whole blocks of {b}")
    rng = np.random.default_rng([int(seed), 1])
    counts = _seeded_order(rng, workload)
    clients = []
    for n in counts:
        ids = rng.integers(1, s["vocab"] - 1, size=(n, t), dtype=np.int32)
        k = rng.integers(1, b + 1, size=(n, t // b, 1))
        rank = np.argsort(np.argsort(rng.random((n, t // b, b)), axis=-1),
                          axis=-1)
        y = np.where(rank < k, b / k, 0.0).astype(np.float32)
        clients.append({"x": ids, "y": y.reshape(n, t)})
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


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope(x, positions, theta):
    """Rotate-half rotary positions on ``[..., T, D]`` (position on the
    axis before the last): column ``i`` and column ``i + D/2`` turned by
    ``positions[t] * theta^(-2i/D)``."""
    d = x.shape[-1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.asarray(positions, np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def visible(length, block, variant="f32"):
    """The dense ``[2 L, 2 L]`` boolean mask of the three rules, query on
    the rows."""
    i = jnp.arange(2 * length)[:, None]
    j = jnp.arange(2 * length)[None, :]
    bi, bj = (i % length) // block, (j % length) // block
    qt, kt = i >= length, j >= length
    seen = (~qt & ~kt & (bj <= bi)) | (qt & ~kt & (bj < bi)) \
        | (qt & kt & (bj == bi))
    if variant == "causal_mask":
        seen = jnp.where(qt, j <= i, seen)
    return seen


def attention(p, prefix, x, s, variant="f32"):
    """``[B, 2 L, d] -> [B, 2 L, d]``: one layer's attention over ``[x_0 ;
    x_t]``, one key/value head with its group of query heads at a time
    (recomputed in the backward pass), so that the scores fit."""
    b, t, _ = x.shape
    h, kv, hd = s["heads"], s["kv_heads"], s["head_dim"]
    positions = np.arange(t) % (t // 2)
    heads = lambda y, n: y.reshape(b, t, n, hd).transpose(0, 2, 1, 3)
    q = heads(_dot(x, p[prefix + "q_proj/kernel"], variant), h)
    k = heads(_dot(x, p[prefix + "k_proj/kernel"], variant), kv)
    v = heads(_dot(x, p[prefix + "v_proj/kernel"], variant), kv)
    q = rope(_rms_norm(q, p[prefix + "q_norm/scale"], s["eps"]), positions,
             s["theta"])
    k = rope(_rms_norm(k, p[prefix + "k_norm/scale"], s["eps"]), positions,
             s["theta"])
    seen = visible(t // 2, s["block"], variant)

    @jax.checkpoint
    def group(qkv):
        qg, kg, vg = qkv              # [B, G, T, hd], [B, T, hd], [B, T, hd]
        scores = _dot(qg, kg[:, None].transpose(0, 1, 3, 2), variant) \
            / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _dot(att, vg[:, None], variant)

    grouped = q.reshape(b, kv, h // kv, t, hd).transpose(1, 0, 2, 3, 4)
    y = jax.lax.map(group, (grouped, k.transpose(1, 0, 2, 3),
                            v.transpose(1, 0, 2, 3)))   # [KV, B, G, T, hd]
    y = y.transpose(1, 3, 0, 2, 4).reshape(b, t, h * hd)
    return _dot(y, p[prefix + "o_proj/kernel"], variant)


def route(p, prefix, x, s, variant="f32"):
    """``[N, d]`` tokens -> ``[N, router]`` weights: 0 where an expert was
    not chosen, else its share of the chosen probabilities."""
    prob = jax.nn.softmax(_dot(x, p[prefix + "router/kernel"], variant),
                          axis=-1)
    kth = jnp.sort(prob, axis=-1)[:, -s["top_k"]][:, None]
    weight = prob * (prob >= kth).astype(prob.dtype)
    if s["norm_topk"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return weight


def experts(p, prefix, x, s, variant="f32", held=None):
    """The part of a layer's output that the experts ``held = (first,
    count)`` give (default: the configuration's share) for tokens ``[N,
    d]``; the stacked leaves hold exactly those experts. Dense and
    masked: every token goes through every held expert (the experts side
    by side in one product a projection), and each expert's activation is
    multiplied by the token's weight there, 0 where it was not chosen."""
    first, count = held or (s["first"], s["held"])
    d, width = x.shape[-1], p[prefix + "w_gate"].shape[-1]
    weight = route(p, prefix, x, s, variant)[:, first:first + count]
    side_by_side = lambda w: w.transpose(1, 0, 2).reshape(d, count * width)
    gate = _dot(x, side_by_side(p[prefix + "w_gate"]), variant)
    up = _dot(x, side_by_side(p[prefix + "w_up"]), variant)
    h = (jax.nn.silu(gate) * up).reshape(-1, count, width) \
        * weight[:, :, None]
    return _dot(h.reshape(-1, count * width),
                p[prefix + "w_down"].reshape(count * width, d), variant)


def forward(params, idx, s, variant="f32"):
    """Ids ``[B, 2 L]`` (``[x_0 ; x_t]``) -> the noised half's logits
    ``[B, L, vocab]`` (float32), each layer recomputed in the backward
    pass (its activations at float32 would not fit beside the weights)."""
    x = params["tok_embed/embedding"][idx]

    def layer(i, x, p):
        pre = f"layer{i}/"
        b, t, d = x.shape
        x = x + attention(p, pre + "attn/", _rms_norm(
            x, p[pre + "attn_norm/scale"], s["eps"]), s, variant)
        y = _rms_norm(x, p[pre + "ffn_norm/scale"], s["eps"])
        return x + experts(p, pre + "moe/", y.reshape(b * t, d), s,
                           variant).reshape(b, t, d)

    for i in range(s["layers"]):
        mine = {k: v for k, v in params.items()
                if k.startswith(f"layer{i}/")}
        x = jax.checkpoint(functools.partial(layer, i))(x, mine)
    x = _rms_norm(x[:, idx.shape[1] // 2:], params["norm_f/scale"], s["eps"])
    return _dot(x, params["head/kernel"], variant)


def step_loss(params, x, y, live, s, mask, variant="f32"):
    """``sum_i y_i CE_i / (n L)`` over the rows that count (``live``
    ``[n]``), and the (sum, count) the round's loss is made of: the
    cross-entropy at the masked positions, unweighted, and their number."""
    weight = y * live[:, None]
    if variant == "half_batch":
        if x.shape[0] > 1:
            weight = weight.at[(x.shape[0] + 1) // 2:].set(0.0)
            live = live.at[(x.shape[0] + 1) // 2:].set(0.0)
            positions = x.shape[1]
        else:
            positions = (x.shape[1] + 1) // 2
            weight = weight.at[:, positions:].set(0.0)
    else:
        positions = x.shape[1]
    ids = jnp.concatenate([x, jnp.where(y > 0, mask, x)], axis=1)
    logits = forward(params, ids, s, variant)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, x[..., None], axis=-1)[..., 0]
    masked = (weight > 0).astype(jnp.float32)
    loss = jnp.sum(nll * weight) / jnp.maximum(jnp.sum(live) * positions,
                                               1.0)
    return loss, (jnp.sum(nll * masked), jnp.sum(masked))


@functools.lru_cache(maxsize=None)
def _client_update(config_key, mask, variant):
    """One compiled local-SGD loop for every client of a cell: the rows
    are padded to the longest client and ``trip`` (traced) says how many
    steps are real."""
    s = dict(config_key)

    @jax.jit
    def run(params, xs, ys, lives, trip, lr, wd):
        def body(i, carry):
            p, tot, cnt = carry
            (_, (t, c)), g = jax.value_and_grad(
                lambda q: step_loss(q, xs[i], ys[i], lives[i], s, mask,
                                    variant), has_aux=True)(p)
            p = jax.tree.map(lambda a, b: a - lr * (b + wd * a), p, g)
            return p, tot + t, cnt + c

        return jax.lax.fori_loop(0, trip, body, (params, 0.0, 0.0))

    return run


@functools.partial(jax.jit, donate_argnums=(0,))
def _fold(acc, local, glob, w):
    return jax.tree.map(lambda a, p, g: a + w * (p - g), acc, local, glob)


def run_rounds(config, workload, seed, rounds, feed, variant="f32"):
    """Follow ``rounds`` federated rounds from the seed.

    ``feed[r][c]`` is client ``c``'s list of per-step row indices in
    round ``r`` (``benchmarks/feed.py``). Returns ``{"loss": [per round],
    "change_norms": [per round: leaf -> norm of (global weights minus the
    initial ones)], "init": leaf -> initial weights (float32, on the
    host)}``. Only norms are kept of each round's weights, and the
    initial ones wait on the host, so that the reference fits beside its
    own gradients and activations."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    s = sizes(config)
    clients = make_clients(config, workload, seed)
    glob = make_weights(config, seed)
    init = jax.device_get(glob)
    lr, wd = float(workload["lr"]), float(workload.get("wd", 0.0))
    update = _client_update(tuple(sorted(s.items())), mask_id(config),
                            variant)
    s_max = max(len(steps) for rnd in feed for steps in rnd)
    batch = int(workload["batch_size"])
    t = int(workload["seq_len"])
    n_total = float(sum(len(c["y"]) for c in clients))
    norm_of = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    losses, norms = [], []
    for rnd in feed[:rounds]:
        acc, tot, cnt = None, 0.0, 0.0
        for c, steps in enumerate(rnd):
            xs = np.zeros((s_max, batch, t), np.int32)
            ys = np.zeros((s_max, batch, t), np.float32)
            lives = np.zeros((s_max, batch), np.float32)
            for i, rows in enumerate(steps):
                xs[i, :len(rows)] = clients[c]["x"][rows]
                ys[i, :len(rows)] = clients[c]["y"][rows]
                lives[i, :len(rows)] = 1.0
            local, t_c, c_c = update(
                glob, jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(lives),
                jnp.int32(len(steps)), lr, wd)
            w = len(clients[c]["y"]) / n_total
            if acc is None:
                acc = jax.tree.map(jnp.zeros_like, local)
            acc = _fold(acc, local, glob, w)
            del local
            tot, cnt = tot + float(t_c), cnt + float(c_c)
        glob = jax.tree.map(jnp.add, glob, acc)
        del acc
        losses.append(tot / max(cnt, 1.0))
        norms.append({k: float(norm_of(glob[k], init[k])) for k in glob})
    return {"loss": losses, "change_norms": norms, "init": init}
