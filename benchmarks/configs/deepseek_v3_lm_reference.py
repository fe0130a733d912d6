"""Plain reference of the federated ``deepseek_v3``-shaped LM round, and
its inputs.

Imports nothing of fedml_tpu. Everything a run needs from ``--seed`` is
made here: the clients' token shards, the initial weights, and (through
``benchmarks/feed.py``) the order in which rows are fed. The model is the
family's decoder as its public ``config.json`` describes it, written in
straightforward ``jax.numpy`` float32 at matmul precision ``highest``: no
kernel, no cache, no lanes, no sort.

- Block: ``x += Attn(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``; a final
  RMSNorm; a head untied from the embedding; no bias anywhere.
- Attention (latent, no query bottleneck): ``q = x W_q`` is cut per head
  into a position-free part and a rotary part; ``x W_kv_a`` is cut into
  the latent ``c_kv`` and ONE rotary key head; ``RMSNorm(c_kv) W_kv_b``
  gives every head its position-free key part and its value; rotary
  positions in interleaved pairs ``(x[2i], x[2i+1])`` on the rotary parts;
  scores are a materialised causal softmax of ``q k^T / sqrt(nope +
  rope)``.
- FFN: ``W_down(silu(W_gate x) * W_up x)`` in the first
  ``first_k_dense_replace`` layers. In the others: ``s = sigmoid(W_g x)``
  over all the router's experts; the ``num_experts_per_tok`` largest of
  ``s + b`` are chosen; their weights are ``s`` (without ``b``) over the
  chosen ones' sum, times ``routed_scaling_factor``; each expert is a
  gated MLP; the shared expert, one gated MLP of ``n_shared_experts``
  widths, takes every token.

The chip's share (the configuration's ``deployment``): the router keeps
its published width, and of its experts the ``experts_held = (first,
count)`` are here: a dense masked sum over them, every token through
every held expert and multiplied by its weight there, which is 0 where
the token did not choose it (the held experts side by side in one
product a projection, so that the program stays small). What the absent experts would add is left
out. The vocabulary is the configuration's slice: ids, logits and loss
are over it.

``variant`` selects the reference itself (``f32``), the control
(``fp8``: every matmul operand rounded to e4m3 with a per-tensor scale,
straight-through gradient) or a planted fault (``half_batch``: the second
half of every batch left out, the mean taken over the rest).
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
    """The sizes the reference needs, from the configuration file (the
    published key names; ``n_layer``, ``router_experts`` and
    ``experts_held`` are the file's own, see its ``key_mapping``)."""
    run = config.get("as_run", {})
    held = config.get("experts_held") or (0, config["n_routed_experts"])
    s = {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "vdim": int(config["v_head_dim"]),
        "latent": int(config["kv_lora_rank"]),
        "dense": int(config["intermediate_size"]),
        "expert": int(config["moe_intermediate_size"]),
        "shared": int(config["n_shared_experts"]),
        "router": int(config.get("router_experts",
                                 config["n_routed_experts"])),
        "first": int(held[0]), "held": int(held[1]),
        "top_k": int(config["num_experts_per_tok"]),
        "lead": int(config["first_k_dense_replace"]),
        "layers": int(config.get("n_layer", config["num_hidden_layers"])),
        "vocab": int(config["vocab_size"]),
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "scaling": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "init_std": float(config.get("initializer_range", 0.02)),
        # rows of a batch that go through the layers at a time (0: all)
        "rows_at_a_time": int(run.get("reference_rows_at_a_time", 0)),
    }
    if s["held"] != int(config["n_routed_experts"]):
        raise ValueError("experts_held counts another number of experts "
                         "than n_routed_experts says are here")
    return s


def param_shapes(config):
    """Canonical leaf name -> shape; the names are the program's tree
    paths joined by '/', so the family's mapping is a plain rename."""
    s = sizes(config)
    d, h = s["d"], s["heads"]
    shapes = {"tok_embed/embedding": (s["vocab"], d),
              "norm_f/scale": (d,), "head/kernel": (d, s["vocab"])}
    for i in range(s["layers"]):
        p = f"layer{i}/"
        shapes.update({
            p + "attn_norm/scale": (d,), p + "ffn_norm/scale": (d,),
            p + "attn/q_proj/kernel": (d, h * (s["nope"] + s["rope"])),
            p + "attn/kv_a_proj/kernel": (d, s["latent"] + s["rope"]),
            p + "attn/kv_a_norm/scale": (s["latent"],),
            p + "attn/kv_b_proj/kernel":
                (s["latent"], h * (s["nope"] + s["vdim"])),
            p + "attn/o_proj/kernel": (h * s["vdim"], d)})
        if i < s["lead"]:
            mlp, width = p + "mlp/", s["dense"]
        else:
            mlp, width = p + "moe/shared/", s["shared"] * s["expert"]
            shapes.update({
                p + "moe/router/kernel": (d, s["router"]),
                p + "moe/e_score_correction_bias": (s["router"],),
                p + "moe/w_gate": (s["held"], d, s["expert"]),
                p + "moe/w_up": (s["held"], d, s["expert"]),
                p + "moe/w_down": (s["held"], s["expert"], d)})
        shapes.update({mlp + "gate_proj/kernel": (d, width),
                       mlp + "up_proj/kernel": (d, width),
                       mlp + "down_proj/kernel": (width, d)})
    return shapes


def make_weights(config, seed):
    """Initial weights from the seed, float32, in one jitted call on the
    default device (normal(0, std), residual outputs scaled by 1/sqrt(2
    layers), norm scales about 1; the router's bias a small normal so
    that it does steer the choice)."""
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
            elif name.endswith(("o_proj/kernel", "down_proj/kernel",
                                "w_down")):
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
    ids are drawn from [1, vocab) of the configuration's slice (0 is the
    loss's ignore id, so every token counts); ``y`` is ``x`` shifted by
    one with a fresh last id."""
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


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope(x, theta):
    """Rotary positions on ``[..., T, D]`` (position on the axis before
    the last), pairs ``(x[2i], x[2i+1])`` turned by ``t * theta^(-2i/D)``.
    """
    t, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(t, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                       axis=-1)
    return turned.reshape(x.shape)


def _gated(x, p, prefix, variant):
    gate = _dot(x, p[prefix + "gate_proj/kernel"], variant)
    up = _dot(x, p[prefix + "up_proj/kernel"], variant)
    return _dot(jax.nn.silu(gate) * up, p[prefix + "down_proj/kernel"],
                variant)


def attention(p, prefix, x, s, variant="f32"):
    """``[B, T, d] -> [B, T, d]``: latent attention of one layer."""
    b, t, _ = x.shape
    h, nope, rp, vd = s["heads"], s["nope"], s["rope"], s["vdim"]
    q = _dot(x, p[prefix + "q_proj/kernel"], variant)
    q = q.reshape(b, t, h, nope + rp).transpose(0, 2, 1, 3)      # [B,H,T,.]
    kv_a = _dot(x, p[prefix + "kv_a_proj/kernel"], variant)
    c_kv = _rms_norm(kv_a[..., :s["latent"]],
                     p[prefix + "kv_a_norm/scale"], s["eps"])
    k_rope = rope(kv_a[..., s["latent"]:], s["theta"])           # [B,T,rp]
    kv = _dot(c_kv, p[prefix + "kv_b_proj/kernel"], variant)
    kv = kv.reshape(b, t, h, nope + vd).transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], s["theta"])
    scores = _dot(q_nope, k_nope.transpose(0, 1, 3, 2), variant) \
        + _dot(q_rope, k_rope[:, None].transpose(0, 1, 3, 2), variant)
    scores = scores / math.sqrt(nope + rp)
    causal = jnp.tril(jnp.ones((t, t), bool))
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    y = _dot(att, v, variant).transpose(0, 2, 1, 3).reshape(b, t, h * vd)
    return _dot(y, p[prefix + "o_proj/kernel"], variant)


def route(p, prefix, x, s, variant="f32"):
    """``[N, d]`` tokens -> ``[N, router]`` weights: 0 where an expert was
    not chosen, else its share of the chosen scores, scaled."""
    scores = jax.nn.sigmoid(_dot(x, p[prefix + "router/kernel"], variant))
    biased = scores + jax.lax.stop_gradient(
        p[prefix + "e_score_correction_bias"])
    kth = jnp.sort(biased, axis=-1)[:, -s["top_k"]][:, None]
    chosen = (biased >= kth).astype(scores.dtype)
    weight = scores * chosen
    if s["norm_topk"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return weight * s["scaling"]


def experts(p, prefix, x, s, variant="f32", held=None):
    """The routed part that the experts ``held = (first, count)`` give
    (default: the configuration's share) for tokens ``[N, d]``; the
    stacked leaves hold exactly those experts. Dense and masked: every
    token goes through every held expert (the experts side by side in
    one product a projection), and each expert's activation is
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


def expert_ffn(p, prefix, x, s, variant="f32"):
    """A whole expert layer's FFN over ``[N, d]``: the held experts' part
    plus the shared expert."""
    return experts(p, prefix, x, s, variant) \
        + _gated(x, p, prefix + "shared/", variant)


def trunk(params, idx, s, variant="f32"):
    """Token ids ``[B, T]`` -> the last hidden states ``[B, T, d]``, each
    layer recomputed in the backward pass (its activations at float32
    would not fit beside the weights)."""
    x = params["tok_embed/embedding"][idx]

    def layer(i, x, p):
        pre = f"layer{i}/"
        b, t, d = x.shape
        x = x + attention(p, pre + "attn/", _rms_norm(
            x, p[pre + "attn_norm/scale"], s["eps"]), s, variant)
        y = _rms_norm(x, p[pre + "ffn_norm/scale"], s["eps"])
        if i < s["lead"]:
            return x + _gated(y, p, pre + "mlp/", variant)
        return x + expert_ffn(p, pre + "moe/", y.reshape(b * t, d), s,
                              variant).reshape(b, t, d)

    for i in range(s["layers"]):
        mine = {k: v for k, v in params.items()
                if k.startswith(f"layer{i}/")}
        x = jax.checkpoint(functools.partial(layer, i))(x, mine)
    return x


def forward(params, idx, s, variant="f32"):
    """Token ids ``[B, T]`` -> logits ``[B, T, vocab]`` (float32)."""
    n = s["rows_at_a_time"]
    if n and idx.shape[0] > n and idx.shape[0] % n == 0:
        blocks = idx.reshape(idx.shape[0] // n, n, idx.shape[1])
        x = jax.lax.map(lambda ids: trunk(params, ids, s, variant), blocks)
        x = x.reshape(idx.shape + x.shape[-1:])
    else:
        x = trunk(params, idx, s, variant)
    x = _rms_norm(x, params["norm_f/scale"], s["eps"])
    return _dot(x, params["head/kernel"], variant)


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
    update = _client_update(tuple(sorted(s.items())), variant)
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
            ys = np.zeros((s_max, batch, t), np.int32)
            for i, rows in enumerate(steps):
                xs[i, :len(rows)] = clients[c]["x"][rows]
                ys[i, :len(rows)] = clients[c]["y"][rows]
            local, t_c, c_c = update(glob, jnp.asarray(xs), jnp.asarray(ys),
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
