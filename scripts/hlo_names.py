"""Name the device operations of a cell's client-update program without
a chip (PERF.md section 5, PR 32).

A ledger ``breakdown`` lists a traced round's longest device operations
by XLA's instruction names (``multiply_reduce_fusion.36``, ``fusion.720``).
This script compiles the same program -- the bucketed stream's
``chunk_fn`` over the cell's model and spec, from the cell's
configuration and traffic files, shapes only -- for a TPU v5e that is
described and not attached, and prints for each name the instruction's
``op_name`` (the JAX source path that made it), its output shape and its
large operands. The numbering is the compiler's own: for the GPT-2 cells
nine of the ledger's ten names stood in the compile to the digit (PR
32); where one is off by a few, ``--like`` finds its neighbours by
``op_name``.

    python3 scripts/hlo_names.py --workload cgpt1.3b-silo4-long
    python3 scripts/hlo_names.py --workload kanana2-a3b-ep8-silo2-long \
        --names fusion.2566,add_select_fusion.106
    python3 scripts/hlo_names.py --workload cgpt1.3b-silo4-long --like head/

Default names: the ten of the workload's newest ledger line that has a
``breakdown``. Nothing runs: this says what an operation IS, never how
long it takes. The first line also sums the compiler's own
``estimated_cycles`` over the program's instructions: not a time either,
but a cheap first word on a change (it ranked four forms of the loss as
the chip had, and agrees in sign with the 0.4 % that ``kanana2`` lost;
PERF.md, PR 32). 20-90 s on a CPU core. ``--text <file>`` keeps
the whole optimized HLO; ``--table 50257,2048`` lists what writes an
array of the token table's shape (PR 38: the loop body keeps one, the
in-place scatter of the row step).
"""

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: an operand or output is "large" from here on (bytes)
LARGE = 32 << 20
_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
          "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8}
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def spec_of(config, traffic, reference):
    """The cell's model and train spec as its family builds them
    (``benchmarks/families/<family>.py`` ``build``), without weights."""
    import jax.numpy as jnp

    from fedml_tpu.algorithms import specs

    dtype = jnp.dtype(config["as_run"]["compute_dtype"])
    example = jnp.zeros((1, int(traffic["seq_len"])), jnp.int32)
    family = config["family"]
    if family == "gpt2_lm":
        from fedml_tpu.models.transformer import TransformerLM

        d = int(config["n_embd"])
        model = TransformerLM(
            vocab_size=int(config["vocab_size"]),
            n_layers=int(config["n_layer"]), n_heads=int(config["n_head"]),
            d_model=d, max_len=int(config["n_positions"]),
            mlp_ratio=int(config["n_inner"]) // d, dtype=dtype)
        return specs.make_seq_classification_spec(model, example, name="lm")
    from fedml_tpu.models import deepseek_v3 as dsv3

    decoder = dsv3.DecoderConfig.from_dict(config)
    if family in ("deepseek_v3_lm", "lfm2_moe_lm"):
        return specs.make_seq_classification_spec(
            dsv3.DecoderLM(decoder, dtype=dtype), example, name="lm")
    if family == "sdar_moe_lm":
        return specs.make_block_diffusion_lm_spec(
            dsv3.DecoderLM(decoder, dtype=dtype), example,
            int(config["block_length"]), reference.mask_id(config))
    raise KeyError(f"no builder for family {family!r}")


def compile_chunk_program(spec, traffic, steps, device=None):
    """The stream's ``chunk_fn`` (``make_streamed_client_update`` under the
    lane ``vmap``, the payload sum behind it) compiled for ``device`` of a
    described v5e, at a bucket of ``steps`` local steps. The caller has
    steered the Pallas kernels off interpret mode (``main`` below; a test
    by its own fixture)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from fedml_tpu.parallel.engine import (BucketedStreamRunner,
                                           ClientUpdateConfig)

    if device is None:
        from jax.experimental import topologies

        device = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    on = SingleDeviceSharding(device)
    lanes, b = int(traffic["client_chunk"]), int(traffic["batch_size"])
    runner = BucketedStreamRunner(
        spec, ClientUpdateConfig(optimizer=traffic.get("optimizer", "sgd"),
                                 lr=float(traffic["lr"]),
                                 weight_decay=float(traffic.get("wd", 0.0))),
        client_chunk=lanes, batch_size=b, epochs=int(traffic["epochs"]),
        edges=(steps,))
    shaped = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on), tree)
    state = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    x = jnp.zeros((lanes, steps, b, int(traffic["seq_len"])), jnp.int32)
    # the batch's "y" as the spec's loss takes it: ids, or the block
    # diffusion's float32 weights
    y = x.astype(jnp.float32) if spec.name == "block_diffusion_lm" else x
    args = (state,
            {"x": x, "y": y, "mask": jnp.zeros((lanes, steps, b))},
            jnp.zeros((lanes,), jnp.int32), jnp.zeros((), jnp.int32),
            jax.random.split(jax.random.PRNGKey(0), lanes))
    return runner._chunk_fn.lower(*shaped(args)).compile()


def _nbytes(dtype, dims):
    n = _BYTES.get(dtype, 4)
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


def _computations(text):
    """``{name: its lines}`` of an optimized HLO module's text."""
    out, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            lines = out.setdefault(head.group(1), [])
        elif lines is not None:
            lines.append(line)
    return out


def fallback_computations(text):
    """The computations that only a conditional's second branch reaches
    (the one taken on true; ``RoutedExperts``' whole-buffer fallback,
    PERF.md PR 35), whatever they call included."""
    comps = _computations(text)
    branches_of = lambda lines: [
        re.findall(r"%([\w.\-]+)", m) for m in re.findall(
            r"branch_computations=\{([^}]*)\}", "\n".join(lines))]
    called = lambda lines: set(re.findall(
        r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", "\n".join(lines))
    ) | {name for b in branches_of(lines) for name in b}
    # a conditional inside a fallback (the step over an empty run) is
    # reached from it with both of its branches
    out, todo = set(), [b[1] for lines in comps.values()
                        for b in branches_of(lines) if len(b) == 2]
    while todo:
        name = todo.pop()
        if name not in out:
            out.add(name)
            todo += list(called(comps.get(name, ())))
    return out


def instructions(text, skip=()):
    """``{name: (output type, opcode, operand names, op_name, estimated
    cycles)}`` of every instruction of an optimized HLO module's text
    that stands in a computation of its own right (the entry, a loop's
    body, a conditional's branch; not the computations named in
    ``skip``): what a device trace shows as one event. The insides of
    fusions are left out. The cycles are the TPU compiler's own estimate
    (0 where it gives none): no timing, but their sum ranked four forms
    of the GPT-2 loss as the chip did (PERF.md, PR 32)."""
    fused = set(re.findall(r"\bfusion\(.*?calls=%([\w.\-]+)", text))
    out = {}
    for comp, lines in _computations(text).items():
        if comp in fused or comp in skip:
            continue
        for line in lines:
            m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (.*?) "
                         r"([a-z][\w\-]*)\((.*)", line)
            if not m:
                continue
            name, result, opcode, rest = m.groups()
            op_name = re.search(r'op_name="([^"]*)"', rest)
            cycles = re.search(r'"estimated_cycles":"(\d+)"', rest)
            out[name] = (result, opcode,
                         re.findall(r"%([\w.\-]+)", rest.split(")")[0]),
                         op_name.group(1) if op_name else "",
                         int(cycles.group(1)) if cycles else 0)
    return out


_MOVES_NOTHING = ("parameter", "get-tuple-element", "tuple", "bitcast",
                  "conditional", "while")


def wide_rows(text, rows, within="/moe/", columns=64):
    """The instructions OUTSIDE the conditionals' fallback whose result
    or an operand has ``rows`` rows of ``columns`` or more elements: the
    ``tokens x top-k``-row arrays that ``RoutedExperts``' compact path is
    there to avoid (the route's index vectors over the assignments have
    one column and are not counted). ``within``: only instructions whose
    ``op_name`` holds this (a vocabulary slice may have as many rows)."""
    instrs = instructions(text, skip=fallback_computations(text))
    plain = lambda s: re.sub(r"\{[^}]*\}", "", s)

    def wide(type_text):
        for _, dims in _SHAPE.findall(plain(type_text)):
            dims = [int(d) for d in dims.split(",") if d]
            if rows in dims and max(
                    [d for d in dims if d != rows] or [1]) >= columns:
                return True
        return False

    return [name for name, rec in instrs.items()
            if rec[1] not in _MOVES_NOTHING and within in rec[3]
            and (wide(rec[0]) or any(o in instrs and wide(instrs[o][0])
                                     for o in rec[2]))]


def table_shaped(text, rows, columns):
    """``[(computation, name, opcode, output, op_name)]`` of every
    instruction that stands in a computation of its own right (as
    :func:`instructions` counts them) and writes an array of ``rows`` x
    ``columns`` (a leading lane axis of 1 allowed): a token table's
    shape. Since PR 38 the loop body of a row-stepped cell holds one,
    the in-place scatter into the carried table."""
    fused = set(re.findall(r"\bfusion\(.*?calls=%([\w.\-]+)", text))
    shapes = {f"[{rows},{columns}]", f"[1,{rows},{columns}]"}
    out = []
    for comp, lines in _computations(text).items():
        if comp in fused:
            continue
        for line in lines:
            m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = ([a-z]+[0-9]*)"
                         r"(\[[0-9,]*\])\S* ([a-z][\w\-]*)\((.*)", line)
            if m and m.group(3) in shapes \
                    and m.group(4) not in _MOVES_NOTHING:
                op_name = re.search(r'op_name="([^"]*)"', m.group(5))
                out.append((comp, m.group(1), m.group(4),
                            m.group(2) + m.group(3),
                            op_name.group(1) if op_name else ""))
    return out


def describe(name, instrs):
    result, opcode, operands, op_name, cycles = instrs[name]
    plain = lambda s: re.sub(r"\{[^}]*\}", "", s)
    large = [f"{d}[{dims}]" for o in operands if o in instrs
             for d, dims in _SHAPE.findall(plain(instrs[o][0]))
             if _nbytes(d, dims) >= LARGE]
    return {"name": name, "opcode": opcode, "op_name": op_name,
            "output": plain(result), "large_operands": large,
            "estimated_cycles": cycles}


def ledger_names(workload, path=os.path.join(ROOT, "PERF_LEDGER.jsonl")):
    """The ten longest device operations of the workload's newest ledger
    line that has a ``breakdown``, the event suffix (``_fusion``,
    ``_convert``, ``_custom-call``) taken off."""
    best = None
    with open(path, encoding="utf-8") as f:
        for line in f:
            rec = json.loads(line)
            ops = (rec.get("breakdown") or {}).get("device_ops")
            if rec.get("workload") == workload and ops:
                best = ops
    if best is None:
        raise SystemExit(f"no ledger line of {workload} has a breakdown: "
                         "give --names")
    return [re.sub(r"_(fusion|convert|custom-call|copy|while)$", "", n)
            for n, _ in best]


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", default="cgpt1.3b-silo4-long")
    ap.add_argument("--names", default=None,
                    help="comma list of instruction names (default: the "
                         "ledger's ten)")
    ap.add_argument("--like", default=None,
                    help="also list every instruction whose op_name "
                         "holds this text")
    ap.add_argument("--steps", type=int, default=8,
                    help="the bucket edge compiled (shapes of the batches; "
                         "the loop body is the same at every edge)")
    ap.add_argument("--text", default=None,
                    help="write the optimized HLO here")
    ap.add_argument("--table", default=None,
                    help="ROWS,COLUMNS: also list every instruction that "
                         "writes an array of a token table's shape, with "
                         "the computation it stands in")
    ap.add_argument("--rows", type=int, default=0,
                    help="also count the instructions outside the "
                         "conditionals' fallback that touch an array of "
                         "this many rows (an expert cell's tokens x top-k)")
    args = ap.parse_args(argv)
    # before JAX is imported: no chip is asked for, the compiler logs nowhere
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from benchmarks.manifest import Manifest
    from fedml_tpu.ops import grouped_matmul as gm
    from fedml_tpu.ops import pallas_attention as pa
    from fedml_tpu.ops import short_conv as sc

    # the kernels ask the default backend whether to interpret; it is the
    # CPU's here, and the program wanted is the chip's
    pa._use_interpret = gm._use_interpret = sc._use_interpret = \
        lambda: False
    man = Manifest(ROOT)
    entry = man.cell(args.workload)
    config = man.config(entry["config"])
    traffic = man.traffic(entry["traffic"])
    names = args.names.split(",") if args.names \
        else ledger_names(args.workload)
    compiled = compile_chunk_program(
        spec_of(config, traffic, man.reference(config)), traffic, args.steps)
    text = compiled.as_text()
    if args.text:
        with open(args.text, "w", encoding="utf-8") as f:
            f.write(text)
    instrs = instructions(text)
    mem = compiled.memory_analysis()
    print(json.dumps({"workload": args.workload, "instructions": len(instrs),
                      "estimated_cycles": sum(r[4] for r in instrs.values()),
                      "argument_bytes": mem.argument_size_in_bytes,
                      "output_bytes": mem.output_size_in_bytes,
                      "temp_bytes": mem.temp_size_in_bytes}))
    if args.rows:
        fallback = fallback_computations(text)
        outside = instructions(text, skip=fallback)
        wide = wide_rows(text, args.rows)
        print(json.dumps({
            "rows": args.rows, "conditionals": text.count(" conditional("),
            "instructions_outside_fallback": len(outside),
            "estimated_cycles_outside_fallback":
                sum(r[4] for r in outside.values()),
            "wide_outside_fallback": len(wide)}))
        for name in wide:
            print(json.dumps(describe(name, instrs)))
    if args.table:
        rows, columns = (int(v) for v in args.table.split(","))
        for comp, name, opcode, output, op_name in table_shaped(
                text, rows, columns):
            print(json.dumps({"table_shaped": name, "opcode": opcode,
                              "output": output, "computation": comp,
                              "op_name": op_name}))
    for name in names:
        print(json.dumps(describe(name, instrs) if name in instrs
                         else {"name": name, "missing": True}))
    if args.like:
        for name, rec in instrs.items():
            if args.like in rec[3] and rec[1] != "parameter":
                print(json.dumps(describe(name, instrs)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
