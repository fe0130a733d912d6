"""The main path's Pallas kernels compiled at their real widths for a
TPU v5e that is described and not attached: what interpret mode cannot
show (tilings, lane layouts, fast-memory limits). Nothing runs, so these
say nothing of results or times; a compile that passes is not a chip run.

All such compiles live in this one file: the topology is described
inside a fixture, by the one worker that is given the file."""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from fedml_tpu.ops import grouped_matmul as gm
from fedml_tpu.ops import pallas_attention as pa
from fedml_tpu.ops import short_conv as sc


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def hardware_path(monkeypatch):
    """The kernels ask the default backend whether to interpret; here it
    is the CPU's, so the test steers them onto the compiled path."""
    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    monkeypatch.setattr(gm, "_use_interpret", lambda: False)
    monkeypatch.setattr(sc, "_use_interpret", lambda: False)


def _load_script(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _custom_calls(compiled):
    return re.findall(r"%(\S+) = (\S+)[^\n]*custom_call_target="
                      r"\"tpu_custom_call\"", compiled.as_text())


def _flash_calls(compiled):
    """``{kernel: output signature}`` of the program's Pallas calls, the
    layouts left out: what the benchmark's roofline patterns tell the
    flash kernels by (``benchmarks/layer_metrics/flash_*_roofline.json``)."""
    calls = re.findall(r"^\s*%(\S+) = (.*?) custom-call\([^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"",
                       compiled.as_text(), re.M)
    kernel = lambda name: re.search(r"flash_(fwd|bwd_dq|bwd_dkv)", name)
    assert all(kernel(n) for n, _ in calls), calls
    return [(kernel(n).group(0), re.sub(r"\{[^}]*\}", "", sig))
            for n, sig in calls]


def _attention_fwd_bwd(one_chip, b, t, heads, dqk, dv, mask=True,
                       kv_heads=None):
    shape = lambda d, h=kv_heads or heads: jax.ShapeDtypeStruct(
        (b, t, h, d), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):   # no blocks given: the tiles flash_schedule chooses
        out = pa.flash_attention(q, k, v, mask, dqk ** -0.5)
        return jnp.sum(out.astype(jnp.float32))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(dqk, heads), shape(dqk), shape(dv)).compile()


@pytest.mark.parametrize("heads,dqk,dv", [(16, 128, 128), (32, 192, 128)],
                         ids=["gpt2_128", "latent_192_128"])
def test_flash_kernels_compile_at_real_widths(one_chip, hardware_path,
                                              heads, dqk, dv):
    """One attention's forward and backward at the two cells' shapes under
    the DEFAULT tiles: exactly three Pallas calls, by the names and the
    output signatures the benchmark finds them by -- ``(bf16 o, f32
    logsumexp)``, ``bf16 dq``, ``(bf16 dk, bf16 dv)``."""
    b, t = 2, 2048
    # scores of 192 reach the kernel as 256 columns, values stay 128
    width = 256 if dqk == 192 else dqk
    calls = _flash_calls(_attention_fwd_bwd(one_chip, b, t, heads, dqk, dv))
    assert sorted(calls) == sorted([
        ("flash_fwd", f"(bf16[{b},{heads},{t},{dv}], f32[{b},{heads},1,{t}])"),
        ("flash_bwd_dq", f"bf16[{b},{heads},{t},{width}]"),
        ("flash_bwd_dkv", f"(bf16[{b},{heads},{t},{width}], "
                          f"bf16[{b},{heads},{t},{dv}])")])
    # the schedule of the cells' shapes, pinned: a 4 x 1 grid a head in
    # every kernel (it was 16 x 16), the whole sequence resident
    tile = pa.Tile(rows=512, major=2048, minor=512)
    assert pa.flash_schedule(t, t, width, dv, jnp.bfloat16) \
        == (pa.Schedule(tile, tile, tile), (4, 4, 4))


@pytest.mark.parametrize("t,dqk,steps", [(80, 128, (1, 1, 1)),
                                         (8192, 192, (32, 64, 64))],
                         ids=["t80_clipped", "t8192_inner_grid_axis"])
def test_flash_kernels_compile_at_other_lengths(one_chip, hardware_path,
                                                t, dqk, steps):
    """A sequence that clips the tiles (80 rows, keys padded to 128) and
    one whose keys do not stay resident (the grid keeps its inner axis,
    with the clamped index maps): Mosaic takes both."""
    width = 256 if dqk == 192 else dqk
    assert pa.flash_schedule(t, t, width, 128, jnp.bfloat16)[1] == steps
    calls = _flash_calls(_attention_fwd_bwd(one_chip, 1, t, 2, dqk, 128))
    assert sorted(n for n, _ in calls) == ["flash_bwd_dkv", "flash_bwd_dq",
                                           "flash_fwd"]


@pytest.mark.parametrize("length,block", [(2048, 4), (2048, 32),
                                          (1280, 4)],
                         ids=["L2048_b4", "L2048_b32", "L1280_ragged"])
def test_flash_kernels_compile_under_the_block_diffusion_mask(
        one_chip, hardware_path, length, block):
    """``sdar-30b-a3b-ep8``'s attention, one sequence as ``[x_0 ; x_t]``
    (4,096 positions at L 2048), 32 heads of 128, under the two-range
    band: the forward and both backward kernels, still three Pallas calls
    by the names and output signatures the benchmark finds them by, the
    whole streamed sequence resident (an 8 x 1 grid a head); a block of
    32 and an ``L`` that is no multiple of the tile (a tile with rows of
    both copies) compile too."""
    b, t, heads, d = 1, 2 * length, 32, 128
    calls = _flash_calls(_attention_fwd_bwd(
        one_chip, b, t, heads, d, d, pa.BlockDiffusion(length, block)))
    # (a batch of one: the compiler drops the axis)
    assert sorted(calls) == sorted([
        ("flash_fwd", f"(bf16[{heads},{t},{d}], f32[{heads},1,{t}])"),
        ("flash_bwd_dq", f"bf16[{heads},{t},{d}]"),
        ("flash_bwd_dkv", f"(bf16[{heads},{t},{d}], bf16[{heads},{t},{d}])")])
    if length == 2048:
        tile = pa.Tile(rows=512, major=4096, minor=512)
        assert pa.flash_schedule(t, t, d, d, jnp.bfloat16) \
            == (pa.Schedule(tile, tile, tile), (8, 8, 8))


@pytest.mark.parametrize("kv_heads,d,mask", [
    (8, 64, True), (4, 128, pa.BlockDiffusion(2048, 4))],
    ids=["lfm2_32over8_64", "sdar_32over4_128_bd"])
def test_flash_kernels_compile_with_key_value_heads_read_by_group(
        one_chip, hardware_path, kv_heads, d, mask):
    """``lfm2-8b-a1b-ep4``'s attention (32 query heads over 8 key/value
    heads of 64, T 4,096, causal: blocks of the array's own 64 columns)
    and ``sdar-30b-a3b-ep8``'s (32 over 4 of 128 under the block mask):
    the group's map leaves keys and values unmapped, so the kernels'
    outputs are ``[KV, group, T, D]`` and no repeated copy exists; the
    names and the forward's ``(bf16, f32)`` signature are what the
    benchmark's patterns find."""
    t, heads = 4096, 32
    group = heads // kv_heads
    calls = _flash_calls(_attention_fwd_bwd(one_chip, 1, t, heads, d, d,
                                            mask, kv_heads=kv_heads))
    own = f"bf16[{kv_heads},{group},{t},{d}]"
    assert sorted(calls) == sorted([
        ("flash_fwd", f"({own}, f32[{kv_heads},{group},1,{t}])"),
        ("flash_bwd_dq", own), ("flash_bwd_dkv", f"({own}, {own})")])
    tile = pa.Tile(rows=512, major=4096, minor=512)
    assert pa.flash_schedule(t, t, d, d, jnp.bfloat16) \
        == (pa.Schedule(tile, tile, tile), (8, 8, 8))


def test_short_conv_kernels_compile_at_the_cells_shape(one_chip,
                                                       hardware_path):
    """``gated_short_conv`` over ``[1, 4096, 3 x 2048]`` bf16, forward and
    backward: two Pallas calls by the names the benchmark's patterns
    match, and outputs that ``flash_fwd_roofline``'s ``(bf16, f32)``
    signature pattern cannot take for a flash forward (the backward's
    float32 sums come first)."""
    import json

    x = jax.ShapeDtypeStruct((1, 4096, 6144), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((2048, 3), jnp.float32, sharding=one_chip)
    loss = lambda bcu, w: jnp.sum(
        sc.gated_short_conv(bcu, w).astype(jnp.float32))
    # (the value too: the backward recomputes z and needs no forward)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        x, w).compile().as_text()
    calls = dict(re.findall(
        r"^\s*%(\S*short_conv_(?:fwd|bwd)\S*) = (.*?) custom-call\(", text,
        re.M))
    sigs = {re.search(r"short_conv_(fwd|bwd)", n).group(0):
            re.sub(r"\{[^}]*\}", "", sig) for n, sig in calls.items()}
    third = "bf16[1,4096,2048]"
    assert sigs == {"short_conv_fwd": third,
                    "short_conv_bwd": f"(f32[3,2048], {third}, {third}, "
                                      f"{third})"}
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "layer_metrics",
            "flash_fwd_roofline.json")) as f:
        flash_fwd = re.compile(json.load(f)["pattern"])
    lines = [ln.strip() for ln in text.splitlines() if "short_conv" in ln
             and "custom-call(" in ln]
    assert len(lines) == 2 and not any(flash_fwd.match(ln) for ln in lines)


def test_flash_refuses_a_value_width_the_hardware_cannot_run(hardware_path):
    q = jnp.zeros((1, 128, 2, 192), jnp.bfloat16)
    with pytest.raises(ValueError, match="multiple of 128"):
        pa.flash_attention(q, q, jnp.zeros((1, 128, 2, 96), jnp.bfloat16))


def test_grouped_products_compile_under_the_lane_vmap(one_chip,
                                                      hardware_path):
    """One expert layer's three products and their six backward products
    at ``kanana-2-30b-a3b-ep8``'s shapes (a row for each of 4,096 x 6
    assignments, 16 experts of 2048 x 768), under ``jax.vmap`` over one
    lane as the client-update program has them; the device events'
    names are what the benchmark's metric files match."""
    m, d, width, held = 24576, 2048, 768, 16
    s = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        (1,) + shape, dtype, sharding=one_chip)

    def loss(x, wg, wu, wd, sizes):
        h = jax.nn.silu(gm.grouped_matmul(x, wg, sizes)) \
            * gm.grouped_matmul(x, wu, sizes)
        y = gm.grouped_matmul(h, wd, sizes).astype(jnp.float32)
        return jnp.sum(y * y)   # so that the last product's value is needed

    compiled = jax.jit(jax.vmap(jax.grad(loss, argnums=(0, 1, 2, 3)))).lower(
        s((m, d)), s((held, d, width)), s((held, d, width)),
        s((held, width, d)), s((held,), jnp.int32)).compile()
    names = [n for n, _ in _custom_calls(compiled)]
    count = lambda part: sum(part in n for n in names)
    assert count("moe_gmm_fwd") == 3
    assert count("moe_gmm_dlhs") == 3 and count("moe_gmm_drhs") == 3


@pytest.mark.parametrize("cell,tokens,top_k,held,router,width,capacity", [
    ("sdar", 4096, 8, 16, 128, 768, 8192),
    ("kanana2", 4096, 6, 16, 128, 768, 6144),
    ("lfm2", 4096, 4, 8, 32, 1792, 8192)])
def test_an_expert_layer_keeps_the_whole_buffer_inside_its_fallback(
        topo, one_chip, hardware_path, cell, tokens, top_k, held, router,
        width, capacity):
    """One ``RoutedExperts`` layer at an expert cell's shapes, forward and
    backward under a lane ``vmap`` that names its axis as the stream's
    ``chunk_fn`` does, compiled for the described v5e: the conditional
    stays a conditional (one forward, one backward; the fallback has its
    own inside, which steps over empty runs), the grouped products
    stand in both of its branches, and OUTSIDE the fallback no
    instruction reads or writes an array of ``tokens x top-k`` rows but
    the route's index vectors (PERF.md, PR 35)."""
    from fedml_tpu.models import deepseek_v3 as dsv3
    from fedml_tpu.parallel.mesh import LANE_AXIS

    d = 2048
    cfg = dsv3.DecoderConfig(
        vocab_size=64, hidden_size=d, num_hidden_layers=1,
        num_attention_heads=2, moe_intermediate_size=width,
        n_routed_experts=held, num_experts_per_tok=top_k,
        router_experts=router, experts_held=(0, held),
        scoring_func="softmax")
    assert dsv3.buffer_capacity(tokens * top_k, held, router) == capacity
    module = dsv3.RoutedExperts(cfg, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, tokens, d), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((1,) + a.shape, a.dtype,
                                       sharding=one_chip),
        jax.eval_shape(module.init, jax.random.PRNGKey(0),
                       jnp.zeros((tokens, d), jnp.bfloat16))["params"])

    def loss(params, x):
        out = module.apply({"params": params}, x)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    step = jax.jit(jax.vmap(jax.grad(loss, argnums=(0, 1)),
                            axis_name=LANE_AXIS))
    text = step.lower(params, x).compile().as_text()
    names = _load_script("hlo_names")
    # the layer's conditional forward and backward, and inside its
    # fallback the one that steps over a run without rows: in the loop
    # over the runs, in its recomputation and in its backward
    assert text.count(" conditional(") == 2 + 3
    calls = re.findall(r"%(\S+) = \S+[^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    # nine products a branch, and the fallback's three forward products
    # again in its backward (it is checkpointed: it keeps no residual)
    assert sum("moe_gmm" in c for c in calls) == 9 + 9 + 3
    assert names.fallback_computations(text)
    assert names.wide_rows(text, tokens * top_k, within="") == []


@pytest.mark.parametrize("program", ["fold_first", "fold_next",
                                     "fold_quotient"])
def test_fold_programs_keep_their_arithmetic_and_allocate_nothing(
        one_chip, program):
    """The bucketed stream's device fold at a GPT-2 embedding's size:
    the v5e compiler keeps every operation of the error-free sums (an
    algebraic simplifier that cancelled ``(a + b) - a`` would leave a
    plain float32 sum), needs no scratch memory, and writes every output
    over a donated input."""
    from fedml_tpu import models
    from fedml_tpu.algorithms.specs import make_classification_spec
    from fedml_tpu.parallel.engine import (BucketedStreamRunner,
                                           ClientUpdateConfig)

    spec = make_classification_spec(
        models.LogisticRegression(num_classes=4, apply_sigmoid=False),
        jnp.zeros((1, 6)))
    runner = BucketedStreamRunner(spec, ClientUpdateConfig(lr=0.1),
                                  client_chunk=2, batch_size=4, edges=(8,))
    word = lambda: {"wte": jax.ShapeDtypeStruct((50257, 2048), jnp.float32,
                                                sharding=one_chip)}
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    args, adds, subtracts, outputs = {
        "fold_first": ((word(), word()), 3, 6, 2),
        "fold_next": ((word(), word(), word()), 4, 6, 2),
        "fold_quotient": ((word(), word(), scalar, scalar,
                           {"wte": scalar}), None, None, 1),
    }[program]
    compiled = getattr(runner, "_" + program).lower(*args).compile()
    mem = compiled.memory_analysis()
    leaf = 50257 * 2048 * 4
    assert mem.temp_size_in_bytes == 0
    # (the chip pads a leaf to its tiles; the output's tuple is its own)
    assert mem.alias_size_in_bytes >= outputs * leaf
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 4096
    text = compiled.as_text()
    count = lambda op: len(re.findall(rf"\b{op}\(", text))
    if program == "fold_quotient":
        # TwoSum of the words, the division and its correction's, the
        # masks of the three splits, Dekker's products
        assert count("divide") == 2 and count("and") >= 2
        assert count("subtract") >= 9 and count("multiply") >= 6
    else:
        assert (count("add"), count("subtract")) == (adds, subtracts)


@pytest.fixture(scope="module")
def gpt2_program(topo):
    """``cgpt1.3b-silo4-long``'s client-update program (the stream's
    ``chunk_fn``: ``make_streamed_client_update`` under the lane ``vmap``;
    d 2048, 4 layers, vocabulary 50257, batch 2 x 2048, from the
    benchmark's own files through ``scripts/hlo_names.py``) compiled once
    for the described v5e: ``(the script, the compiled program)``."""
    from benchmarks.manifest import Manifest

    names = _load_script("hlo_names")
    man = Manifest()
    entry = man.cell("cgpt1.3b-silo4-long")
    config, traffic = man.config(entry["config"]), \
        man.traffic(entry["traffic"])
    with pytest.MonkeyPatch.context() as mp:
        for kernels in (pa, gm, sc):
            mp.setattr(kernels, "_use_interpret", lambda: False)
        compiled = names.compile_chunk_program(
            names.spec_of(config, traffic, man.reference(config)), traffic,
            steps=8, device=topo.devices[0])
    return names, compiled


def test_gpt2_client_update_passes_over_the_logits_once(gpt2_program):
    """The counter that says the one cross-entropy op engaged. Exactly
    ONE instruction writes an f32 array of the logits' size (the head's
    forward product; ``log_softmax`` kept a second one for its backward)
    and it is read by four: the loss's one forward pass, the head's two
    gradient products, which form ``dlogits`` inside their fusions, and
    the bias gradient's sum."""
    names, compiled = gpt2_program
    instrs = names.instructions(compiled.as_text())
    plain = lambda s: re.sub(r"\{[^}]*\}", "", s)
    logits = re.compile(r"f32\[(1,)*2,2048,50257\]")   # not [1,2048,50257]
    moves = {n: rec for n, rec in instrs.items() if rec[1] not in (
        "get-tuple-element", "bitcast", "parameter", "tuple")}
    writers = [n for n, rec in moves.items() if logits.search(plain(rec[0]))]
    assert len(writers) == 1, writers
    assert instrs[writers[0]][3].endswith("jvp(TransformerLM)/head/"
                                          "dot_general")
    reads = lambda rec: any(
        o in instrs and logits.fullmatch(plain(instrs[o][0]))
        for o in rec[2])
    readers = {n: rec[3].split("body/")[-1] for n, rec in moves.items()
               if reads(rec)}
    assert len(readers) <= 4, readers
    products = [n for n, op in readers.items()
                if op == "transpose(jvp(TransformerLM))/head/dot_general"]
    assert len(products) == 2, readers
    # ISSUE 32's bound. The compiler's temp is a schedule's, not a count of
    # arrays: 3.45 GB here against the parent's 2.62 with ``logp`` in it
    # and 1.80 for three separate reductions (PERF.md, PR 32)
    assert compiled.memory_analysis().temp_size_in_bytes < 4.0e9


def test_gpt2_client_update_steps_the_token_table_by_rows(gpt2_program):
    """The row step (ISSUE 38) as the v5e compiler leaves it: in the step
    loop's body ONE instruction writes an array of the token table's
    shape, a fusion whose root is the scatter into its table operand. No
    copy or cast of the table (a gather batched over the one lane asks
    for the table retiled, and the compiler casts it to bf16 on the way:
    ``ops/row_embed.py`` ``take_rows`` takes the lane axis off; the CPU's
    compiler makes no such copy, so only this compile shows it), no
    zeroed dense gradient, no dense select. Outside the loop: the copy of
    the carried table and the payload's weighted sum."""
    names, compiled = gpt2_program
    text = compiled.as_text()
    entry = re.search(r"^ENTRY %([\w.\-]+)", text, re.M).group(1)
    body = [(name, opcode) for comp, name, opcode, _, _ in
            names.table_shaped(text, 50257, 2048) if comp != entry]
    assert len(body) == 1 and body[0][1] == "fusion", body
    called = re.search(rf"%{re.escape(body[0][0])} = .*?calls=%([\w.\-]+)",
                       text).group(1)
    root = names._computations(text)[called]
    assert any(re.match(r"\s+ROOT %\S+ = f32\[50257,2048\]\S* scatter\(",
                        line) for line in root), called
