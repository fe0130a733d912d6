"""Plain reference of the federated ``lfm2_moe``-shaped LM round, and its
inputs.

Imports nothing of fedml_tpu. Everything a run needs from ``--seed`` is
made here: the clients' token shards, the initial weights, and (through
``benchmarks/feed.py``) the order in which rows are fed. The model is the
family's decoder as its public ``config.json`` describes it (``model_type``
``lfm2_moe``), written in straightforward ``jax.numpy`` float32 at matmul
precision ``highest``: no kernel, no cache, no lanes, no sort.

- Block: ``x += mixer(RMSNorm(x))``, ``x += ffn(RMSNorm(x))``; a final
  RMSNorm; a head untied from the embedding; no bias anywhere. A layer's
  mixer is its entry in ``layer_types`` (as run).
- ``conv`` mixer: ``[B ; C ; u] = split3(x W_in)`` with ``W_in`` ``[d, 3
  d]``, thirds in that order; ``v = B * u``; a depthwise causal
  convolution of ``conv_L_cache`` taps along time, per channel ``c``:
  ``z[t, c] = sum_k w[c, k] v[t - (L - 1 - k), c]`` with ``v[s] = 0`` for
  ``s < 0``; ``y = (C * z) W_out``. No activation, no position encoding.
- ``full_attention`` mixer: ``q`` as ``num_attention_heads`` heads of
  ``head_dim`` (``hidden_size / num_attention_heads`` where the file has
  none), ``k`` and ``v`` as ``num_key_value_heads`` heads; RMSNorm with a
  scale of its own over every head's q and k; rotate-half rotary
  positions; query head ``h`` reads key/value head ``h // (heads / kv
  heads)``; a materialised causal softmax of ``q k^T / sqrt(head_dim)``;
  ``W_o``.
- FFN: ``W_2(silu(W_1 x) * W_3 x)`` in the leading ``num_dense_layers``
  layers. In the others: ``s = sigmoid(x W_r)`` over all the router's
  experts; the ``num_experts_per_tok`` largest of ``s + expert_bias`` are
  chosen; their weights are ``s`` (without the bias) over the chosen
  ones' sum plus ``1e-6``, times ``routed_scaling_factor``; each expert
  is a gated MLP. The bias gets no gradient and no update rule.

The chip's share (the configuration's ``deployment``): the router keeps
its published width, and of its experts the ``experts_held = (first,
count)`` are here: a dense masked sum over them, every token through
every held expert (side by side in one product a projection) and
multiplied by its weight there, 0 where the token did not choose it.
What the absent experts would add is left out. The vocabulary is the
configuration's slice: ids, logits and loss are over it.

``variant`` selects the reference itself (``f32``), the control
(``fp8``: every matmul operand rounded to e4m3 with a per-tensor scale,
straight-through gradient) or a planted fault: ``half_batch`` (the second
half of every batch left out and the mean taken over the rest: of its
rows, or, where a batch is one row, of that row's positions) and
``center_tap`` (the convolution with every tap but the last left out: a
model that looks at no earlier position in its ``conv`` layers).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

VARIANTS = ("f32", "fp8", "half_batch", "center_tap")
MIXERS = ("conv", "full_attention")
_HI = jax.lax.Precision.HIGHEST


def sizes(config):
    """The sizes the reference needs, from the configuration file (the
    published key names; ``n_layer``, ``layer_types_as_run``,
    ``n_routed_experts``, ``router_experts`` and ``experts_held`` are the
    file's own, see its ``key_mapping``)."""
    router = int(config.get("router_experts", config["num_experts"]))
    count = int(config.get("n_routed_experts", router))
    held = config.get("experts_held") or (0, count)
    layers = int(config.get("n_layer", config["num_hidden_layers"]))
    types = tuple(config.get("layer_types_as_run", config["layer_types"]))
    if len(types) != layers or set(types) - set(MIXERS):
        raise ValueError(f"{len(types)} layer types {sorted(set(types))} "
                         f"for {layers} layers of {MIXERS}")
    if int(held[1]) != count:
        raise ValueError("experts_held counts another number of experts "
                         "than n_routed_experts says are here")
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {
        "d": d, "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim") or d // heads),
        "taps": int(config["conv_L_cache"]),
        "dense": int(config["intermediate_size"]),
        "expert": int(config["moe_intermediate_size"]),
        "router": router, "first": int(held[0]), "held": count,
        "top_k": int(config["num_experts_per_tok"]),
        "lead": int(config["num_dense_layers"]),
        "layers": layers, "types": types,
        "vocab": int(config["vocab_size"]),
        "eps": float(config["norm_eps"]),
        "theta": float(config["rope_theta"]),
        "scaling": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "init_std": float(config.get("initializer_range", 0.02)),
    }


def param_shapes(config):
    """Canonical leaf name -> shape; the names are the program's tree
    paths joined by '/', so the family's mapping is a plain rename."""
    s = sizes(config)
    d, h, kv, hd = s["d"], s["heads"], s["kv_heads"], s["head_dim"]
    shapes = {"tok_embed/embedding": (s["vocab"], d),
              "norm_f/scale": (d,), "head/kernel": (d, s["vocab"])}
    for i, mixer in enumerate(s["types"]):
        p = f"layer{i}/"
        shapes[p + "ffn_norm/scale"] = (d,)
        if mixer == "conv":
            shapes.update({
                p + "conv_norm/scale": (d,),
                p + "conv/in_proj/kernel": (d, 3 * d),
                p + "conv/conv_kernel": (d, s["taps"]),
                p + "conv/out_proj/kernel": (d, d)})
        else:
            shapes.update({
                p + "attn_norm/scale": (d,),
                p + "attn/q_proj/kernel": (d, h * hd),
                p + "attn/k_proj/kernel": (d, kv * hd),
                p + "attn/v_proj/kernel": (d, kv * hd),
                p + "attn/q_norm/scale": (hd,),
                p + "attn/k_norm/scale": (hd,),
                p + "attn/o_proj/kernel": (h * hd, d)})
        if i < s["lead"]:
            shapes.update({
                p + "mlp/gate_proj/kernel": (d, s["dense"]),
                p + "mlp/up_proj/kernel": (d, s["dense"]),
                p + "mlp/down_proj/kernel": (s["dense"], d)})
        else:
            shapes.update({
                p + "moe/router/kernel": (d, s["router"]),
                p + "moe/e_score_correction_bias": (s["router"],),
                p + "moe/w_gate": (s["held"], d, s["expert"]),
                p + "moe/w_up": (s["held"], d, s["expert"]),
                p + "moe/w_down": (s["held"], s["expert"], d)})
    return shapes


def make_weights(config, seed):
    """Initial weights from the seed, float32, in one jitted call on the
    default device: normal(0, std), residual outputs (``o_proj``,
    ``out_proj``, ``down_proj``, ``w_down``) scaled by 1/sqrt(2 layers),
    norm scales about 1, the router's bias a small normal so that it does
    steer the choice, and the convolution's taps normal(0, 1/sqrt(taps)):
    a depthwise filter's fan-in is its taps, and at 0.02 the conv mixers,
    four layers of five, would hardly take part."""
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
            elif name.endswith("conv_kernel"):
                out[name] = jax.random.normal(k, shape) \
                    / math.sqrt(s["taps"])
            elif name.endswith(("o_proj/kernel", "out_proj/kernel",
                                "down_proj/kernel", "w_down")):
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
    """Rotate-half rotary positions on ``[..., T, D]`` (position on the
    axis before the last): column ``i`` and column ``i + D/2`` turned by
    ``t * theta^(-2i/D)``."""
    t, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(t, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def short_conv(p, prefix, x, s, variant="f32"):
    """``[B, T, d] -> [B, T, d]``: the gated short convolution of one
    ``conv`` layer (the products alone are rounded in the control)."""
    t, taps = x.shape[1], s["taps"]
    b, c, u = jnp.split(_dot(x, p[prefix + "in_proj/kernel"], variant), 3,
                        axis=-1)
    v, w = b * u, p[prefix + "conv_kernel"]                      # [d, L]
    kept = (taps - 1,) if variant == "center_tap" else range(taps)
    z = sum(w[:, k] * jnp.pad(v, ((0, 0), (taps - 1 - k, 0), (0, 0)))[:, :t]
            for k in kept)
    return _dot(c * z, p[prefix + "out_proj/kernel"], variant)


def attention(p, prefix, x, s, variant="f32"):
    """``[B, T, d] -> [B, T, d]``: one layer's causal attention, one
    key/value head with its group of query heads at a time (recomputed in
    the backward pass), so that the scores fit."""
    b, t, _ = x.shape
    h, kv, hd = s["heads"], s["kv_heads"], s["head_dim"]
    heads = lambda y, n: y.reshape(b, t, n, hd).transpose(0, 2, 1, 3)
    q = heads(_dot(x, p[prefix + "q_proj/kernel"], variant), h)
    k = heads(_dot(x, p[prefix + "k_proj/kernel"], variant), kv)
    v = heads(_dot(x, p[prefix + "v_proj/kernel"], variant), kv)
    q = rope(_rms_norm(q, p[prefix + "q_norm/scale"], s["eps"]), s["theta"])
    k = rope(_rms_norm(k, p[prefix + "k_norm/scale"], s["eps"]), s["theta"])
    seen = jnp.tril(jnp.ones((t, t), bool))

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


def _gated(x, p, prefix, variant):
    gate = _dot(x, p[prefix + "gate_proj/kernel"], variant)
    up = _dot(x, p[prefix + "up_proj/kernel"], variant)
    return _dot(jax.nn.silu(gate) * up, p[prefix + "down_proj/kernel"],
                variant)


def route(p, prefix, x, s, variant="f32"):
    """``[N, d]`` tokens -> ``[N, router]`` weights: 0 where an expert was
    not chosen, else its share of the chosen scores, scaled."""
    scores = jax.nn.sigmoid(_dot(x, p[prefix + "router/kernel"], variant))
    biased = scores + jax.lax.stop_gradient(
        p[prefix + "e_score_correction_bias"])
    kth = jnp.sort(biased, axis=-1)[:, -s["top_k"]][:, None]
    weight = scores * (biased >= kth).astype(scores.dtype)
    if s["norm_topk"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-6)
    return weight * s["scaling"]


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


def layer(i, x, p, s, variant="f32"):
    """Layer ``i`` over ``[B, T, d]``; ``p`` holds its leaves under their
    names WITHOUT the ``layer<i>/`` prefix."""
    b, t, d = x.shape
    if s["types"][i] == "conv":
        x = x + short_conv(p, "conv/", _rms_norm(
            x, p["conv_norm/scale"], s["eps"]), s, variant)
    else:
        x = x + attention(p, "attn/", _rms_norm(
            x, p["attn_norm/scale"], s["eps"]), s, variant)
    y = _rms_norm(x, p["ffn_norm/scale"], s["eps"])
    if i < s["lead"]:
        return x + _gated(y, p, "mlp/", variant)
    return x + experts(p, "moe/", y.reshape(b * t, d), s,
                       variant).reshape(b, t, d)


def _runs(s):
    """The depth as runs of layers alike in mixer and FFN: ``[(first,
    count)]``; a run of several goes through ONE ``scan`` (its body is
    compiled once)."""
    kind = lambda i: (s["types"][i], i < s["lead"])
    runs = []
    for i in range(s["layers"]):
        if runs and kind(runs[-1][0]) == kind(i):
            runs[-1][1] += 1
        else:
            runs.append([i, 1])
    return [tuple(r) for r in runs]


def forward(params, idx, s, variant="f32"):
    """Token ids ``[B, T]`` -> logits ``[B, T, vocab]`` (float32), each
    layer recomputed in the backward pass (its activations at float32
    would not fit beside the weights)."""
    x = params["tok_embed/embedding"][idx]
    of = lambda i: {k[len(f"layer{i}/"):]: v for k, v in params.items()
                    if k.startswith(f"layer{i}/")}
    for first, count in _runs(s):
        step = jax.checkpoint(
            functools.partial(layer, first, s=s, variant=variant))
        if count == 1:
            x = step(x, of(first))
        else:
            stacked = jax.tree.map(lambda *a: jnp.stack(a),
                                   *[of(first + j) for j in range(count)])
            x, _ = jax.lax.scan(lambda x, p: (step(x, p), None), x, stacked)
    x = _rms_norm(x, params["norm_f/scale"], s["eps"])
    return _dot(x, params["head/kernel"], variant)


def step_loss(params, x, y, s, variant="f32"):
    """Mean next-token NLL over the tokens that count (``y != 0``) of the
    rows that count, and the (sum, count) the round's loss is made of."""
    n, t = x.shape
    w = (y != 0).astype(jnp.float32)
    if variant == "half_batch":
        # the planted fault: the second half of the batch left out (of
        # its rows; of the one row's positions where a batch is one row)
        live = (np.arange(t)[None, :] < (t + 1) // 2 if n == 1
                else np.arange(n)[:, None] < (n + 1) // 2)
        w = w * jnp.asarray(live, jnp.float32)
    logits = forward(params, x, s, variant)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
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
