"""The row step for lookup tables (ISSUE 38, ``ops/row_embed.py`` and
``parallel/engine.py`` ``_make_trip_loop_core``): under plain SGD a token
table read by one lookup is stepped by the rows it looked up, and the
result is the dense step's, float32 on the CPU.

The oracle is the parent's dense step as this tree still runs it:
``make_client_update`` (the scan variant, which no row step touches) over
the same batches and keys."""

import re
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.specs import (make_block_diffusion_lm_spec,
                                        make_seq_classification_spec)
from fedml_tpu.models import deepseek_v3 as dec
from fedml_tpu.models import transformer as tfm
from fedml_tpu.ops import row_embed
from fedml_tpu.parallel.engine import (ClientUpdateConfig, make_client_update,
                                       make_streamed_client_update)
from fedml_tpu.parallel.mesh import LANE_AXIS

V, T, B, S, LANES = 61, 8, 2, 5, 2
LR = 0.5

DECODER = {
    "model_type": "sdar_moe", "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 64,
    "moe_intermediate_size": 16, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 2, "vocab_size": 53,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "rope_scaling": None,
    "use_sliding_window": False, "sliding_window": None,
    "tie_word_embeddings": False, "attention_bias": False,
    "hidden_act": "silu",
}


def _causal(q, k, v):
    return jax.nn.dot_product_attention(q, k, v, is_causal=True)


def _transformer(max_len=16, dtype=jnp.float32):
    return tfm.TransformerLM(vocab_size=V, n_layers=1, n_heads=2, d_model=32,
                             max_len=max_len, dtype=dtype,
                             attention_fn=_causal)


def _spec(kind, max_len=16, dtype=jnp.float32):
    if kind == "transformer":
        return make_seq_classification_spec(_transformer(max_len, dtype),
                                            jnp.zeros((1, T), jnp.int32))
    cfg = dict(DECODER, block_length=4) if kind == "block_diffusion" \
        else DECODER
    model = dec.DecoderLM(dec.DecoderConfig.from_dict(cfg))
    if kind == "block_diffusion":
        return make_block_diffusion_lm_spec(
            model, jnp.zeros((1, T), jnp.int32), 4, DECODER["vocab_size"] - 1)
    return make_seq_classification_spec(model, jnp.zeros((1, T), jnp.int32))


def _batches(kind, seed=5):
    """``LANES`` lanes of ``S`` steps: repeated ids in every step, step 2
    fully masked, one sample of step 3 masked."""
    vocab = V if kind == "transformer" else DECODER["vocab_size"] - 1
    rng = np.random.default_rng(seed)
    x = rng.integers(1, vocab, (LANES, S, B, T)).astype(np.int32)
    x[:, :, 0, :3] = 7       # the same id thrice in a row, in every step
    x[:, :, 1, -2:] = 7      # and in the other sample
    if kind == "block_diffusion":
        k = rng.integers(1, 5, (LANES, S, B, T // 4))
        masked = rng.random((LANES, S, B, T // 4, 4)).argsort(-1) < \
            k[..., None]
        y = np.where(masked, (4.0 / k)[..., None], 0.0).reshape(x.shape)
        y = y.astype(np.float32)
    else:
        y = rng.integers(1, vocab, x.shape).astype(np.int32)
    mask = np.ones((LANES, S, B), np.float32)
    mask[:, 2] = 0.0
    mask[:, 3, 1] = 0.0
    return {"x": jnp.asarray(x), "y": jnp.asarray(y),
            "mask": jnp.asarray(mask)}


def _streamed(spec, cfg, state, batches):
    """The stream's client update under the lane ``vmap``, as
    ``BucketedStreamRunner.chunk_fn`` runs it."""
    update = make_streamed_client_update(spec, cfg)
    run = jax.jit(jax.vmap(update, in_axes=(None, 0, 0, None, 0),
                           axis_name=LANE_AXIS))
    keys = jax.random.split(jax.random.PRNGKey(3), LANES)
    local, _, metrics = run(state, batches, jnp.ones((LANES,)), jnp.int32(S),
                            keys)
    return local["params"], metrics, update.row_plan, keys


def _dense(spec, cfg, state, batches, keys):
    """The dense oracle, lane by lane."""
    update = jax.jit(make_client_update(spec, cfg))
    lanes = []
    for lane in range(LANES):
        data = {k: v[lane] for k, v in batches.items()}
        data["n"] = jnp.float32(1)
        local, _, metrics = update(state, data, keys[lane])
        lanes.append((local["params"], metrics))
    return lanes


def _table(params, name="tok_embed"):
    return np.asarray(params[name]["embedding"])


def _check_against_dense(kind, ids_of):
    spec = _spec(kind)
    state = spec.init_fn(jax.random.PRNGKey(0))
    batches = _batches(kind)
    cfg = ClientUpdateConfig(lr=LR)
    got, metrics, plan, keys = _streamed(spec, cfg, state, batches)
    assert plan["tok_embed"] == B * ids_of(T)
    start = _table(state["params"])
    for lane, (want, want_metrics) in enumerate(
            _dense(spec, cfg, state, batches, keys)):
        mine = jax.tree.map(lambda a: np.asarray(a[lane]), got)
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(mine)[0],
                                jax.tree.leaves(want)):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6,
                                       err_msg=jax.tree_util.keystr(path))
        for key in want_metrics:
            np.testing.assert_allclose(metrics[key][lane], want_metrics[key],
                                       rtol=2e-5)
        # rows no valid step looked up: bit for bit where they began
        live = np.asarray(batches["mask"][lane]).sum(-1) > 0
        looked = np.zeros(start.shape[0], bool)
        looked[np.unique(ids_of(np.asarray(batches["x"][lane]), np.asarray(
            batches["y"][lane]))[live])] = True
        assert looked.sum() < start.shape[0]
        np.testing.assert_array_equal(_table(mine)[~looked], start[~looked])
        np.testing.assert_array_equal(np.asarray(want["tok_embed"][
            "embedding"])[~looked], start[~looked])
        # (a row read only by a masked sample, or a clean position that
        # no noised one attends, takes a zero cotangent)
        assert np.mean(np.any(_table(mine)[looked] != start[looked],
                              axis=1)) > 0.5
    return start, got


@pytest.mark.parametrize("kind", ["transformer", "decoder"])
def test_the_row_step_is_the_dense_step(kind):
    _check_against_dense(kind, lambda x, y=None: x)


def test_block_diffusion_steps_both_copies_by_rows():
    """The ids the lookup reads are ``[x_0 ; x_t]``, built inside the
    loss: the mask id's row moves though no batch holds it."""
    mask_id = DECODER["vocab_size"] - 1

    def ids_of(x, y=None):
        if y is None:       # positions a step: both copies
            return 2 * x
        return np.concatenate([x, np.where(y > 0, mask_id, x)], axis=-1)

    start, got = _check_against_dense("block_diffusion", ids_of)
    assert not np.any(_batches("block_diffusion")["x"] == mask_id)
    for lane in range(LANES):
        assert np.any(_table(got)[lane, mask_id] != start[mask_id])


def test_a_fully_masked_trip_changes_nothing():
    spec = _spec("transformer")
    state = spec.init_fn(jax.random.PRNGKey(0))
    batches = _batches("transformer")
    batches["mask"] = jnp.zeros_like(batches["mask"])
    got, _, plan, _ = _streamed(spec, ClientUpdateConfig(lr=LR), state,
                                batches)
    assert plan["tok_embed"] > 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(state["params"])):
        for lane in range(LANES):
            np.testing.assert_array_equal(np.asarray(a[lane]), b)


@pytest.mark.parametrize("cfg", [
    ClientUpdateConfig(lr=LR, momentum=0.9),
    ClientUpdateConfig(lr=LR, weight_decay=1e-3),
    ClientUpdateConfig(lr=LR, grad_clip=5.0),
    ClientUpdateConfig(optimizer="adam", lr=1e-3),
], ids=["momentum", "weight_decay", "grad_clip", "adam"])
def test_every_other_optimizer_steps_densely(cfg):
    spec = _spec("transformer")
    state = spec.init_fn(jax.random.PRNGKey(0))
    batches = _batches("transformer")
    got, _, plan, keys = _streamed(spec, cfg, state, batches)
    assert plan == {}        # no lookup was asked for its ids
    for lane, (want, _) in enumerate(_dense(spec, cfg, state, batches, keys)):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a[lane]), b, rtol=1e-6,
                                       atol=1e-7)


def test_a_table_read_whole_steps_densely():
    """``pos_embed`` at ``T == max_len`` reads every row a step: dense;
    below it (the toy above) the row step takes it too."""
    spec = _spec("transformer", max_len=T)
    state = spec.init_fn(jax.random.PRNGKey(0))
    _, _, plan, _ = _streamed(spec, ClientUpdateConfig(lr=LR), state,
                              _batches("transformer"))
    assert plan == {"tok_embed": B * T, "pos_embed": 0}
    spec = _spec("transformer")
    _, _, plan, _ = _streamed(spec, ClientUpdateConfig(lr=LR),
                              spec.init_fn(jax.random.PRNGKey(0)),
                              _batches("transformer"))
    assert plan == {"tok_embed": B * T, "pos_embed": T}


class _TwiceRead(nn.Module):
    @nn.compact
    def __call__(self, idx, train=False):
        embed = row_embed.RowEmbed(V, 16, name="tok_embed")
        x = embed(idx) + embed(jnp.flip(idx, axis=1))
        return nn.Dense(V, name="head")(x)


def test_a_table_looked_up_twice_steps_densely():
    spec = make_seq_classification_spec(_TwiceRead(),
                                        jnp.zeros((1, T), jnp.int32))
    state = spec.init_fn(jax.random.PRNGKey(0))
    batches = _batches("transformer")
    cfg = ClientUpdateConfig(lr=LR)
    got, _, plan, keys = _streamed(spec, cfg, state, batches)
    assert plan == {"tok_embed": 0}
    for lane, (want, _) in enumerate(_dense(spec, cfg, state, batches, keys)):
        np.testing.assert_allclose(_table(got)[lane], _table(want),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("lanes,table_axis,ids_axis", [
    (1, 0, 0), (1, 0, None), (1, None, 0), (2, 0, 0), (2, None, 0),
    (2, 0, None)])
def test_take_rows_is_take_under_the_lane_vmap(lanes, table_axis, ids_axis):
    rng = np.random.default_rng(lanes)
    table = jnp.asarray(rng.standard_normal((lanes, V, 8)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, V, (lanes, B, T)), jnp.int32)
    args = (table if table_axis == 0 else table[0],
            ids if ids_axis == 0 else ids[0])
    axes = (table_axis, ids_axis)
    got = jax.jit(jax.vmap(row_embed.take_rows, in_axes=axes))(*args)
    want = jax.vmap(lambda t, i: jnp.take(t, i, axis=0), in_axes=axes)(*args)
    np.testing.assert_array_equal(got, want)


def test_a_tied_head_is_refused():
    with pytest.raises(TypeError, match="read by its lookup alone"):
        row_embed.RowEmbed(V, 16).init(jax.random.PRNGKey(0),
                                       jnp.zeros((2, 16)),
                                       method="attend")


@pytest.mark.parametrize("kind", ["transformer", "decoder"])
def test_the_parameters_and_their_values_are_the_parents(kind, monkeypatch):
    """The same tree paths, shapes, dtypes and values from a seed as the
    parent's ``nn.Embed``."""
    spec = _spec(kind)
    mine = spec.init_fn(jax.random.PRNGKey(11))
    monkeypatch.setattr(tfm, "RowEmbed", nn.Embed)
    monkeypatch.setattr(dec, "RowEmbed", nn.Embed)
    theirs = _spec(kind).init_fn(jax.random.PRNGKey(11))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_the_federated_round_reports_how_the_tables_stepped():
    """``local-train``'s ``embed.step`` / ``embed.rows`` through a real
    bucketed round: rows under plain SGD (positions a step times the
    cohort's steps), dense under momentum."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.observability.tracing import Tracer, set_tracer

    rng = np.random.default_rng(0)
    sizes = [3, 5, 2]
    local = {c: {"x": rng.integers(1, V, (n, T)).astype(np.int32),
                 "y": rng.integers(1, V, (n, T)).astype(np.int32)}
             for c, n in enumerate(sizes)}
    data = [sum(sizes), 2, local[0], local[0], dict(enumerate(sizes)), local,
            {0: local[0]}, V]
    seen = {}
    for momentum in (0.0, 0.9):
        args = types.SimpleNamespace(
            client_num_in_total=3, client_num_per_round=3,
            comm_round=10 ** 9, epochs=1, batch_size=B, lr=0.1, wd=0.0,
            momentum=momentum, client_optimizer="sgd",
            frequency_of_the_test=10 ** 9, seed=0, client_chunk=2,
            bucket_edges="geometric", device_resident="0")
        api = FedAvgAPI(data, _spec("transformer", max_len=T), args)
        tracer = Tracer()
        before = set_tracer(tracer)
        try:
            api.train_one_round()
        finally:
            set_tracer(before)
        span, = [s for s in tracer.finished_spans()
                 if s.name == "local-train"]
        seen[momentum] = (span.attrs["embed.step"], span.attrs["embed.rows"],
                          api._last_info["bucket"]["true_steps"])
    step, rows, steps = seen[0.0]
    assert (step, rows) == ("rows", B * T * steps) and steps == 2 + 3 + 1
    assert seen[0.9][:2] == ("dense", 0)


def _loop_body(text):
    """The instructions of the step loop's body, fused ones included:
    ``[(opcode, output type)]``."""
    comps, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            lines = comps.setdefault(head.group(1), [])
        elif lines is not None:
            lines.append(line)
    todo = re.findall(r"while\(.*?body=%([\w.\-]+)", text)
    seen, out = set(), []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps.get(name, ()):
            m = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (\S+) ([a-z][\w\-]*)\(",
                         line)
            if m:
                out.append((m.group(2), re.sub(r"\{[^}]*\}", "",
                                                m.group(1))))
            todo += re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", line)
    return out


class _Lookup(nn.Module):
    """A table of 61 x 24 and nothing else of that shape (a head's
    transposed kernel would be): bf16 compute, float32 parameters."""
    @nn.compact
    def __call__(self, idx, train=False):
        x = row_embed.RowEmbed(V, 24, dtype=jnp.bfloat16,
                               name="tok_embed")(idx)
        x = nn.gelu(nn.Dense(48, dtype=jnp.bfloat16, name="up")(x))
        return nn.Dense(37, name="head")(x.astype(jnp.float32))


@pytest.mark.parametrize("momentum", [0.0, 0.9], ids=["rows", "dense"])
def test_the_loop_body_writes_the_table_once_in_place(momentum):
    """A small ``chunk_fn``'s optimized CPU HLO: under the row step the
    step loop's body holds no table-shaped convert, select or broadcast
    (the zeroed gradient) and ONE table-shaped scatter; the dense step
    (momentum) shows the three it took away."""
    spec = make_seq_classification_spec(_Lookup(),
                                        jnp.zeros((1, T), jnp.int32))
    state = spec.init_fn(jax.random.PRNGKey(0))
    update = make_streamed_client_update(
        spec, ClientUpdateConfig(lr=LR, momentum=momentum))
    run = jax.jit(jax.vmap(update, in_axes=(None, 0, 0, None, 0),
                           axis_name=LANE_AXIS))
    batches = {k: v[:1] for k, v in _batches("transformer").items()}
    batches["y"] = batches["y"] % 37
    text = run.lower(state, batches, jnp.ones((1,)), jnp.int32(S),
                     jax.random.split(jax.random.PRNGKey(3), 1)
                     ).compile().as_text()
    table = re.compile(rf"(f32|bf16)\[(1,)?{V},24\]")
    ops = [op for op, out in _loop_body(text) if table.fullmatch(out)]
    if momentum:
        assert {"convert", "select", "broadcast"} <= set(ops), ops
    else:
        assert ops.count("scatter") == 1, ops
        assert not {"convert", "select", "broadcast"} & set(ops), ops
