"""fedlint static rules + runtime retrace/transfer auditor.

Every lint rule gets a positive (finding fires) and negative (clean idiom
stays clean) snippet; the runtime auditor is exercised on real 2-round
FedAvg simulations -- one healthy (zero steady-state retraces), one with
an intentionally-introduced retrace (batch size changed between rounds)
that the auditor must catch.
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu import models
from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.algorithms.specs import make_classification_spec
from fedml_tpu.analysis import RULES, audit, current_auditor, lint_source
from fedml_tpu.analysis.cli import main as fedlint_main
from fedml_tpu.analysis.linter import (apply_baseline, lint_paths,
                                       load_baseline, render_json,
                                       render_text, rule_tags,
                                       write_baseline)
from fedml_tpu.data import load_synthetic_federated
from fedml_tpu.utils.profiling import end_of_round_sync

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMM_PATH = "fedml_tpu/core/comm/fake.py"  # in FL107's transport scope
LIB_PATH = "fedml_tpu/core/fake.py"


def codes(src, path=LIB_PATH):
    return [f.code for f in lint_source(src, path=path)]


class TestLintRules:
    def test_rule_catalog_has_at_least_seven_codes(self):
        assert len(RULES) >= 7
        assert all(code.startswith("FL") for code in RULES)

    # FL101 ---------------------------------------------------------------
    def test_fl101_host_sync_in_jit(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return float(x) + x.item()\n")
        assert codes(src) == ["FL101", "FL101"]

    def test_fl101_np_asarray_in_jit(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return np.asarray(x)\n")
        assert codes(src) == ["FL101"]

    def test_fl101_negative_outside_jit_and_literals(self):
        src = (
            "import jax\n"
            "def g(x):\n"
            "    return float(x)\n"  # not jitted: a legitimate host read
            "@jax.jit\n"
            "def f(x):\n"
            "    return x * float(2)\n")  # literal: no sync
        assert codes(src) == []

    # FL102 ---------------------------------------------------------------
    def test_fl102_if_on_tracer(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    if x > 0:\n"
            "        return x\n"
            "    return -x\n")
        assert codes(src) == ["FL102"]

    def test_fl102_for_over_tracer(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(xs):\n"
            "    acc = 0\n"
            "    for x in xs:\n"
            "        acc = acc + x\n"
            "    return acc\n")
        assert codes(src) == ["FL102"]

    def test_fl102_negative_structural_and_none_checks(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x, mask=None):\n"
            "    if mask is None:\n"       # identity check: static
            "        return x\n"
            "    if x.shape[0] > 2:\n"     # shape: static under trace
            "        return x + 1\n"
            "    for i in range(3):\n"     # static bound
            "        x = x + i\n"
            "    return x\n")
        assert codes(src) == []

    def test_fl102_static_argname_params_exempt(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnames=('n',))\n"
            "def f(x, n):\n"
            "    if n > 2:\n"
            "        return x\n"
            "    return -x\n")
        assert codes(src) == []

    # FL103 ---------------------------------------------------------------
    def test_fl103_scalar_params_without_static(self):
        src = (
            "import jax\n"
            "def g(x, n=4):\n"
            "    return x * n\n"
            "step = jax.jit(g)\n")
        assert codes(src) == ["FL103"]

    def test_fl103_negative_with_static_argnums(self):
        src = (
            "import jax\n"
            "def g(x, n=4):\n"
            "    return x * n\n"
            "step = jax.jit(g, static_argnums=(1,))\n")
        assert codes(src) == []

    # FL104 ---------------------------------------------------------------
    def test_fl104_aggregation_jit_without_donation(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def round_fn(state, data):\n"
            "    return state\n")
        assert codes(src) == ["FL104"]

    def test_fl104_negative_donated_or_not_aggregation(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, donate_argnums=(0,))\n"
            "def round_fn(state, data):\n"
            "    return state\n"
            "@jax.jit\n"
            "def predict(state, data):\n"  # not an aggregation name
            "    return state\n")
        assert codes(src) == []

    # FL105 ---------------------------------------------------------------
    def test_fl105_numpy_compute_in_jit(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return np.mean(x)\n")
        assert codes(src) == ["FL105"]

    def test_fl105_float64_dtype_in_jit(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return jnp.zeros((2,), dtype=np.float64) + x\n")
        assert codes(src) == ["FL105"]

    def test_fl105_negative_jnp_inside_np_outside(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return jnp.mean(x)\n"
            "def pack(x):\n"
            "    return np.mean(x)\n")  # host-side packing: numpy is right
        assert codes(src) == []

    # FL106 ---------------------------------------------------------------
    def test_fl106_dict_values_into_stack(self):
        src = (
            "import jax.numpy as jnp\n"
            "def f(d):\n"
            "    return jnp.stack(list(d.values()))\n")
        assert codes(src) == ["FL106"]

    def test_fl106_negative_sorted_iteration(self):
        src = (
            "import jax.numpy as jnp\n"
            "def f(d):\n"
            "    return jnp.stack([v for _, v in sorted(d.items())])\n")
        assert codes(src) == []

    # FL107 ---------------------------------------------------------------
    def test_fl107_broad_except_in_comm_code(self):
        src = (
            "def recv(sock):\n"
            "    try:\n"
            "        return sock.recv(4)\n"
            "    except Exception:\n"
            "        pass\n")
        assert codes(src, path=COMM_PATH) == ["FL107"]
        assert "swallows" in lint_source(src, path=COMM_PATH)[0].message

    def test_fl107_scoped_to_transport_paths(self):
        src = (
            "def f():\n"
            "    try:\n"
            "        return 1\n"
            "    except Exception:\n"
            "        pass\n")
        assert codes(src, path="fedml_tpu/models/cnn.py") == []
        # segment-anchored: "common.py" must not match the comm scope
        assert codes(src, path="fedml_tpu/experiments/common.py") == []

    def test_fl107_negative_specific_types(self):
        src = (
            "import logging\n"
            "def recv(sock):\n"
            "    try:\n"
            "        return sock.recv(4)\n"
            "    except (OSError, ConnectionError):\n"
            "        logging.warning('peer died')\n")
        assert codes(src, path=COMM_PATH) == []

    # FL108 ---------------------------------------------------------------
    def test_fl108_debug_output_in_library(self):
        src = (
            "import jax\n"
            "def f(x):\n"
            "    print('x =', x)\n"
            "    jax.debug.print('traced {}', x)\n"
            "    return x\n")
        assert codes(src) == ["FL108", "FL108"]

    def test_fl108_negative_cli_paths_exempt(self):
        src = "def main():\n    print('usage: ...')\n"
        assert codes(src, path="fedml_tpu/experiments/main_fedavg.py") == []
        assert codes(src, path="fedml_tpu/data/prepare.py") == []

    def test_syntax_error_reported_not_raised(self):
        assert codes("def f(:\n") == ["FL100"]


class TestShardingScanRules:
    """FL109 (unpartitioned shard_map/pjit), FL111 (weak scan carry),
    FL112 (large captured constants) -- pos + neg each."""

    # FL109 ---------------------------------------------------------------
    def test_fl109_all_replicated_specs(self):
        src = (
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "def build(f, mesh):\n"
            "    return jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),\n"
            "                         out_specs=P())\n")
        assert codes(src) == ["FL109"]

    def test_fl109_negative_partitioned_and_unresolvable(self):
        src = (
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "def build(f, mesh):\n"
            "    return jax.shard_map(f, mesh=mesh,\n"
            "                         in_specs=(P(), P('clients')),\n"
            "                         out_specs=P())\n")
        assert codes(src) == []
        # specs bound to caller-supplied PARAMETERS are out of static
        # reach: judge nothing
        src = (
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "def build(f, mesh, spec):\n"
            "    return jax.shard_map(f, mesh=mesh, in_specs=(spec, P()),\n"
            "                         out_specs=P())\n")
        assert codes(src) == []

    def test_fl109_name_bound_spec_resolved_one_hop(self):
        # `spec = P()` in the enclosing scope resolves through one
        # assignment hop and still fires
        src = (
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "def build(f, mesh):\n"
            "    spec = P()\n"
            "    return jax.shard_map(f, mesh=mesh, in_specs=(spec, spec),\n"
            "                         out_specs=spec)\n")
        assert codes(src) == ["FL109"]
        # module-level binding resolves too
        src = (
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "SPEC = P()\n"
            "def build(f, mesh):\n"
            "    return jax.shard_map(f, mesh=mesh, in_specs=(SPEC,),\n"
            "                         out_specs=SPEC)\n")
        assert codes(src) == ["FL109"]

    def test_fl109_name_bound_partitioned_spec_negative(self):
        # the ring_attention idiom: a name-bound spec that DOES partition
        src = (
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "def build(f, mesh, axis):\n"
            "    spec = P('seq', axis, None, None)\n"
            "    return jax.shard_map(f, mesh=mesh,\n"
            "                         in_specs=(spec, spec, spec),\n"
            "                         out_specs=spec)\n")
        assert codes(src) == []

    def test_fl109_name_of_a_name_resolves_two_hops(self):
        # name-of-a-name (`spec = a` where `a = P()`): the second
        # single-binding hop now resolves and fires
        src = (
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "def build(f, mesh):\n"
            "    a = P()\n"
            "    spec = a\n"
            "    return jax.shard_map(f, mesh=mesh, in_specs=(spec,),\n"
            "                         out_specs=a)\n")
        assert codes(src) == ["FL109"]
        # ...and a partitioned spec through the same chain stays clean
        src_part = src.replace("a = P()", "a = P('clients')")
        assert codes(src_part) == []

    def test_fl109_name_resolution_stops_at_two_hops_and_single_binding(self):
        # three-hop chain: out of static reach, judge nothing
        src = (
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "def build(f, mesh):\n"
            "    b = P()\n"
            "    a = b\n"
            "    spec = a\n"
            "    return jax.shard_map(f, mesh=mesh, in_specs=(spec,),\n"
            "                         out_specs=spec)\n")
        assert codes(src) == []
        # rebound name: ambiguous, judge nothing
        src = (
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "def build(f, mesh, flag):\n"
            "    spec = P()\n"
            "    if flag:\n"
            "        spec = P('clients')\n"
            "    return jax.shard_map(f, mesh=mesh, in_specs=(spec,),\n"
            "                         out_specs=spec)\n")
        assert codes(src) == []
        # two hops where the FIRST name is rebound: still ambiguous
        src = (
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "def build(f, mesh, flag):\n"
            "    a = P()\n"
            "    if flag:\n"
            "        a = P('clients')\n"
            "    spec = a\n"
            "    return jax.shard_map(f, mesh=mesh, in_specs=(spec,),\n"
            "                         out_specs=spec)\n")
        assert codes(src) == []

    # FL111 ---------------------------------------------------------------
    def test_fl111_weak_scalar_carry_rebuilt_by_body(self):
        src = (
            "import jax\n"
            "def f(xs):\n"
            "    def body(c, x):\n"
            "        return c + x, x\n"
            "    return jax.lax.scan(body, 0, xs)\n")
        assert codes(src) == ["FL111"]

    def test_fl111_negative_dummy_carry_and_explicit_dtype(self):
        # the `scan(step, 0, xs)` dummy-carry idiom: carry untouched
        src = (
            "import jax\n"
            "def f(xs):\n"
            "    def body(c, x):\n"
            "        return c, x * 2\n"
            "    return jax.lax.scan(body, 0, xs)\n")
        assert codes(src) == []
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def f(xs):\n"
            "    def body(c, x):\n"
            "        return c + x, x\n"
            "    return jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)\n")
        assert codes(src) == []

    def test_fl111_resolves_nearest_body_def(self):
        # two same-named bodies: only the scan whose OWN `body` rebuilds
        # the carry fires -- flat name lookup would cross-wire them
        src = (
            "import jax\n"
            "def clean(xs):\n"
            "    def body(c, x):\n"
            "        return c, x\n"
            "    return jax.lax.scan(body, 0, xs)\n"
            "def dirty(xs):\n"
            "    def body(c, x):\n"
            "        return c + x, x\n"
            "    return jax.lax.scan(body, 0, xs)\n")
        found = lint_source(src, path=LIB_PATH)
        assert [f.code for f in found] == ["FL111"]
        assert found[0].line == 9

    # FL112 ---------------------------------------------------------------
    def test_fl112_large_captured_constant(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "table = jnp.zeros((512, 512))\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x + table\n")
        assert codes(src) == ["FL112"]

    def test_fl112_negative_small_or_passed(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "small = jnp.zeros((8,))\n"          # tiny: idiomatic
            "@jax.jit\n"
            "def f(x, table):\n"                  # large data as an arg
            "    return x + table + small\n")
        assert codes(src) == []


class TestUseAfterDonate:
    """FL110: the project-wide dataflow rule behind the --fix safety
    gate."""

    DONATING = (
        "import jax\n"
        "from functools import partial\n"
        "@partial(jax.jit, donate_argnums=(0,))\n"
        "def round_fn(state, data):\n"
        "    return state\n")

    def test_read_after_donate_fires(self):
        src = self.DONATING + (
            "def caller(state, data):\n"
            "    out = round_fn(state, data)\n"
            "    return state\n")
        found = lint_source(src, path=LIB_PATH)
        assert [f.code for f in found] == ["FL110"]
        assert "donated" in found[0].message

    def test_rebind_idiom_is_clean(self):
        src = self.DONATING + (
            "def caller(state, data):\n"
            "    state = round_fn(state, data)\n"
            "    return state\n")
        assert codes(src) == []

    def test_donating_call_in_loop_without_rebind(self):
        src = self.DONATING + (
            "def caller(state, datas):\n"
            "    outs = [0]\n"
            "    for d in datas:\n"
            "        outs.append(round_fn(state, d))\n"
            "    return outs\n")
        assert codes(src) == ["FL110"]

    def test_loop_with_rebind_is_clean(self):
        src = self.DONATING + (
            "def caller(state, datas):\n"
            "    for d in datas:\n"
            "        state = round_fn(state, d)\n"
            "    return state\n")
        assert codes(src) == []

    def test_mutually_exclusive_branches_do_not_cross_poison(self):
        # a donation in the if-body must not flag the orelse (the two
        # paths never both execute) -- but a read AFTER the statement
        # still sees the body's donation
        src = self.DONATING + (
            "def caller(state, data):\n"
            "    if data is not None:\n"
            "        out = round_fn(state, data)\n"
            "    else:\n"
            "        out = state\n"
            "    return out\n")
        assert codes(src) == []
        src_after = self.DONATING + (
            "def caller(state, data):\n"
            "    if data is not None:\n"
            "        out = round_fn(state, data)\n"
            "    return state\n")
        assert codes(src_after) == ["FL110"]

    def test_self_attribute_jit_resolved_across_methods(self):
        src = (
            "import jax\n"
            "class API:\n"
            "    def __init__(self):\n"
            "        def round_fn(states, w, data, rng):\n"
            "            return states, w\n"
            "        self._round_fn = jax.jit(round_fn,\n"
            "                                 donate_argnums=(0, 1))\n"
            "    def train(self, data, rng):\n"
            "        out = self._round_fn(self.states, self.w, data, rng)\n"
            "        return self.states\n")
        found = lint_source(src, path=LIB_PATH)
        assert [f.code for f in found] == ["FL110"]
        # the rebind idiom every API in this repo uses stays clean
        fixed = src.replace(
            "        out = self._round_fn(self.states, self.w, data, rng)\n"
            "        return self.states\n",
            "        self.states, self.w = self._round_fn(\n"
            "            self.states, self.w, data, rng)\n"
            "        return self.states\n")
        assert lint_source(fixed, path=LIB_PATH) == []

    def test_cross_module_builder_contract(self, tmp_path):
        # the donation contract travels through a builder return and an
        # import edge: mod_b's bad caller is caught project-wide
        (tmp_path / "mod_a.py").write_text(
            "import jax\n"
            "from functools import partial\n"
            "def make_round(cfg):\n"
            "    @partial(jax.jit, donate_argnums=(0,))\n"
            "    def round_fn(state, data):\n"
            "        return state\n"
            "    return round_fn\n")
        (tmp_path / "mod_b.py").write_text(
            "from mod_a import make_round\n"
            "def caller(state, data):\n"
            "    fn = make_round(None)\n"
            "    out = fn(state, data)\n"
            "    return state\n")
        found = lint_paths([str(tmp_path)])
        assert [(f.code, f.path.endswith("mod_b.py")) for f in found] == [
            ("FL110", True)]

    def test_shard_map_wrapped_jit_params_resolved(self):
        src = (
            "import jax\n"
            "class Runner:\n"
            "    def __init__(self, mesh, fn):\n"
            "        def shard_fn(state, server, data, rng):\n"
            "            return state, server\n"
            "        sharded = jax.shard_map(shard_fn, mesh=mesh,\n"
            "                                in_specs=None, out_specs=None)\n"
            "        self._round_fn = jax.jit(sharded,\n"
            "                                 donate_argnums=(0, 1))\n"
            "    def run(self, state, server, data, rng):\n"
            "        out = self._round_fn(state, server, data, rng)\n"
            "        return state\n")
        assert codes(src) == ["FL110"]


class TestDonationFix:
    """The FL104 --fix engine: inference, rewriting, idempotence, and the
    caller-safety gate."""

    def test_infer_donate_argnums_state_vs_data_params(self):
        import ast as ast_mod
        from fedml_tpu.analysis.dataflow import infer_donate_argnums
        fn = ast_mod.parse(
            "def round_fn(global_state, server_state, cohort_data,\n"
            "             residuals, rng):\n"
            "    pass\n").body[0]
        assert infer_donate_argnums(fn) == (0, 1, 3)
        fn = ast_mod.parse(
            "def round_fn(sp, s_opt, cps, c_opts, cohort, rng):\n"
            "    pass\n").body[0]
        assert infer_donate_argnums(fn) == (0, 1, 2, 3)
        fn = ast_mod.parse(
            "def round_fn(global_state, server_state, device_x, device_y,\n"
            "             rows, lanes, step_keys, trip, dtypes, rng):\n"
            "    pass\n").body[0]
        assert infer_donate_argnums(fn) == (0, 1)

    def test_fix_wrap_form_inserts_kwarg(self):
        from fedml_tpu.analysis.dataflow import plan_donation_fixes
        src = (
            "import jax\n"
            "def round_fn(state, data):\n"
            "    return state\n"
            "step_round = jax.jit(round_fn)\n")
        plan = plan_donation_fixes("m.py", src)
        fixed = plan.apply()
        assert "jax.jit(round_fn, donate_argnums=(0,))" in fixed
        # idempotent: the fixed source plans no further edits
        assert not plan_donation_fixes("m.py", fixed).edits

    def test_fix_decorator_form_adds_partial_and_import(self):
        from fedml_tpu.analysis.dataflow import plan_donation_fixes
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def round_fn(state, data):\n"
            "    return state\n")
        fixed = plan_donation_fixes("m.py", src).apply()
        assert "@partial(jax.jit, donate_argnums=(0,))" in fixed
        assert "from functools import partial" in fixed
        assert not plan_donation_fixes("m.py", fixed).edits

    def test_fix_handles_trailing_comma_and_multiline_calls(self):
        import ast as ast_mod
        from fedml_tpu.analysis.dataflow import plan_donation_fixes
        for src in (
            "import jax\n"
            "def round_fn(state, data):\n"
            "    return state\n"
            "step = jax.jit(round_fn,)\n",
            # black-style multi-line wrap with trailing comma
            "import jax\n"
            "def round_fn(state, data):\n"
            "    return state\n"
            "step = jax.jit(\n"
            "    round_fn,\n"
            ")\n",
        ):
            fixed = plan_donation_fixes("m.py", src).apply()
            ast_mod.parse(fixed)  # must stay syntactically valid
            assert "donate_argnums=(0,)" in fixed
            assert not plan_donation_fixes("m.py", fixed).edits

    def test_fix_respects_suppressions_and_existing_donation(self):
        from fedml_tpu.analysis.dataflow import plan_donation_fixes
        src = (
            "import jax\n"
            "from functools import partial\n"
            "def a(state, data):\n"
            "    return state\n"
            "round_a = jax.jit(a)  # fedlint: disable=FL104\n"
            "@partial(jax.jit, donate_argnums=(0,))\n"
            "def round_b(state, data):\n"
            "    return state\n")
        plan = plan_donation_fixes("m.py", src)
        assert not plan.edits and not plan.skipped

    def test_fix_skips_when_caller_would_break(self):
        # caller re-reads the would-be-donated state: the fixer must
        # refuse rather than introduce FL110
        from fedml_tpu.analysis.dataflow import (ProjectIndex,
                                                 plan_donation_fixes)
        from fedml_tpu.analysis.linter import _Aliases
        import ast as ast_mod
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def round_fn(state, data):\n"
            "    return state\n"
            "def caller(state, data):\n"
            "    out = round_fn(state, data)\n"
            "    return state + out\n")
        index = ProjectIndex()
        tree = ast_mod.parse(src)
        index.add_module("m.py", tree, _Aliases(tree))
        plan = plan_donation_fixes("m.py", src, index=index)
        assert not plan.edits
        assert plan.skipped and "re-reads" in plan.skipped[0][2]

    def test_cli_fix_diff_roundtrip(self, tmp_path, capsys):
        mod = tmp_path / "mod.py"
        mod.write_text(
            "import jax\n"
            "def round_fn(state, data):\n"
            "    return state\n"
            "step = jax.jit(round_fn)\n")
        # dry run: pending fix -> exit 1, diff on stdout, file untouched
        assert fedlint_main([str(mod), "--fix", "--diff"]) == 1
        out = capsys.readouterr().out
        assert "+step = jax.jit(round_fn, donate_argnums=(0,))" in out
        assert "donate_argnums" not in mod.read_text()
        # apply, then the diff dry run is empty and exits 0 (the CI
        # idempotence gate)
        assert fedlint_main([str(mod), "--fix"]) == 0
        assert "donate_argnums=(0,)" in mod.read_text()
        assert fedlint_main([str(mod), "--fix", "--diff"]) == 0
        assert capsys.readouterr().out.strip().endswith("mod.py")
        # and the fixed file lints FL104-clean
        assert fedlint_main([str(mod), "--baseline", ""]) == 0
        capsys.readouterr()

    def test_diff_without_fix_is_usage_error(self, capsys):
        assert fedlint_main(["--diff"]) == 2
        capsys.readouterr()


class TestSuppressions:
    SRC = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x)  # fedlint: disable=FL101\n")

    def test_line_suppression(self):
        assert codes(self.SRC) == []

    def test_line_suppression_is_code_specific(self):
        src = self.SRC.replace("FL101", "FL105")
        assert codes(src) == ["FL101"]

    def test_bare_disable_suppresses_all_codes(self):
        src = self.SRC.replace("disable=FL101", "disable")
        assert codes(src) == []

    def test_file_level_suppression(self):
        src = ("# fedlint: disable-file=FL101\n"
               + self.SRC.replace("  # fedlint: disable=FL101", ""))
        assert codes(src) == []


class TestBaseline:
    SRC = (
        "import jax\n"
        "@jax.jit\n"
        "def round_fn(state, data):\n"
        "    return state\n")

    def _findings(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(self.SRC)
        return lint_paths([str(mod)])

    def test_baseline_roundtrip_tolerates_known_findings(self, tmp_path):
        findings = self._findings(tmp_path)
        assert [f.code for f in findings] == ["FL104"]
        bl = tmp_path / "baseline.json"
        write_baseline(findings, str(bl))
        fresh = self._findings(tmp_path)
        new = apply_baseline(fresh, load_baseline(str(bl)))
        assert new == [] and fresh[0].baselined

    def test_new_findings_not_in_baseline_fail(self, tmp_path):
        bl = tmp_path / "baseline.json"
        write_baseline([], str(bl))
        new = apply_baseline(self._findings(tmp_path),
                            load_baseline(str(bl)))
        assert [f.code for f in new] == ["FL104"]

    def test_baseline_keys_on_text_not_line_numbers(self, tmp_path):
        findings = self._findings(tmp_path)
        bl = tmp_path / "baseline.json"
        write_baseline(findings, str(bl))
        # unrelated edit above the finding shifts every line number
        (tmp_path / "mod.py").write_text("# a new leading comment\n"
                                         + self.SRC)
        new = apply_baseline(self._findings(tmp_path),
                            load_baseline(str(bl)))
        assert new == []

    def test_missing_baseline_file_is_empty(self, tmp_path):
        assert load_baseline(str(tmp_path / "nope.json")) == {}


class TestCli:
    SRC = TestBaseline.SRC

    def test_exit_1_on_new_findings_0_with_baseline(self, tmp_path, capsys):
        mod = tmp_path / "mod.py"
        mod.write_text(self.SRC)
        bl = tmp_path / "baseline.json"
        assert fedlint_main([str(mod), "--baseline", ""]) == 1
        assert fedlint_main([str(mod), "--baseline", str(bl),
                             "--write-baseline"]) == 0
        assert fedlint_main([str(mod), "--baseline", str(bl)]) == 0
        capsys.readouterr()

    def test_json_reporter(self, tmp_path, capsys):
        mod = tmp_path / "mod.py"
        mod.write_text(self.SRC)
        rc = fedlint_main([str(mod), "--baseline", "", "--format", "json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert out["summary"]["new"] == 1
        assert out["findings"][0]["code"] == "FL104"

    def test_select_and_ignore(self, tmp_path, capsys):
        mod = tmp_path / "mod.py"
        mod.write_text(self.SRC)
        assert fedlint_main([str(mod), "--baseline", "",
                             "--select", "FL101"]) == 0
        assert fedlint_main([str(mod), "--baseline", "",
                             "--ignore", "FL104"]) == 0
        capsys.readouterr()

    def test_list_rules(self, capsys):
        assert fedlint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for code in RULES:
            assert code in out

    def test_reporters_render(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(self.SRC)
        findings = lint_paths([str(mod)])
        assert "FL104" in render_text(findings)
        assert json.loads(render_json(findings))["summary"]["total"] == 1

    def test_repo_is_clean_against_shipped_baseline(self, monkeypatch,
                                                    capsys):
        # the ci.sh gate, as a test: the tree must lint clean against the
        # checked-in baseline -- new antipatterns fail here first. Scope
        # matches ci.sh: the package plus the bench/driver scripts.
        monkeypatch.chdir(REPO_ROOT)
        # --max-seconds 60 is the ci.sh wall-time pin: it must keep
        # holding with the model-checking pass enabled
        assert fedlint_main(["fedml_tpu", "bench.py", "__graft_entry__.py",
                             "scripts", "--max-seconds", "60"]) == 0
        capsys.readouterr()

    def test_select_runs_one_pass_in_isolation(self, monkeypatch):
        # pass-level gating: a --select set disjoint from a pass's codes
        # must skip that pass entirely, not just filter its findings
        import fedml_tpu.analysis.modelcheck as mc
        import fedml_tpu.analysis.determinism as det

        def boom(*_a, **_k):
            raise AssertionError("pass ran despite disjoint --select")
        monkeypatch.setattr(mc, "check_model", boom)
        monkeypatch.setattr(det, "check_determinism", boom)
        src = "import time\n"
        assert lint_source(src, path=LIB_PATH, select={"FL120"}) == []
        # and the ignore side: dropping every code of a pass skips it
        assert lint_source(
            src, path=LIB_PATH,
            ignore={"FL131", "FL132", "FL133", "FL134", "FL135",
                    "FL140", "FL141", "FL142", "FL143"}) == []
        with pytest.raises(AssertionError):
            lint_source(src, path=LIB_PATH, select={"FL141"})

    def test_fix_path_parses_each_file_once(self, tmp_path, monkeypatch,
                                            capsys):
        # the fix driver parses once for the project index and hands the
        # tree to plan_donation_fixes: a second parse of the same source
        # would be the old double-parse regressing
        import ast as ast_mod
        mod = tmp_path / "agg.py"
        mod.write_text(
            "import jax\n"
            "@jax.jit\n"
            "def aggregate(params, grads):\n"
            "    return jax.tree_util.tree_map(lambda p, g: p + g,\n"
            "                                  params, grads)\n")
        real_parse = ast_mod.parse
        calls = []

        def counting_parse(*a, **k):
            calls.append(a[0] if a else k.get("source"))
            return real_parse(*a, **k)
        monkeypatch.setattr(ast_mod, "parse", counting_parse)
        from fedml_tpu.analysis.cli import run_fix
        assert run_fix([str(tmp_path)], diff=True) in (0, 1)
        monkeypatch.setattr(ast_mod, "parse", real_parse)
        assert len(calls) == 1, \
            "fix path parsed a file more than once per run"
        capsys.readouterr()

    def test_default_baseline_is_package_anchored(self):
        # the installed `fedlint` entry point must resolve its baseline
        # from any cwd, not relative to wherever it was launched
        from fedml_tpu.analysis.cli import DEFAULT_BASELINE
        assert os.path.isabs(DEFAULT_BASELINE)
        assert os.path.exists(DEFAULT_BASELINE)

    def test_shipped_baseline_is_empty(self):
        # the FL104 donation debt is PAID (this PR's acceptance
        # criterion); any future debt must argue its way back in through
        # a baseline diff, starting from zero
        from fedml_tpu.analysis.cli import DEFAULT_BASELINE
        with open(DEFAULT_BASELINE, encoding="utf-8") as fh:
            assert json.load(fh)["findings"] == []

    def test_repo_fix_dry_run_is_empty(self, monkeypatch, capsys):
        # fedlint --fix --diff on the committed tree must be a no-op:
        # every FL104 site already carries its donate_argnums
        monkeypatch.chdir(REPO_ROOT)
        assert fedlint_main(["fedml_tpu", "bench.py", "__graft_entry__.py",
                             "scripts", "--fix", "--diff"]) == 0
        assert capsys.readouterr().out == ""


class TestProtocolRules:
    """FL120-FL122: the fedcheck FSM protocol pass."""

    FSM_PATH = "fedml_tpu/core/fsm_fake.py"

    PAIRED = (
        "from fedml_tpu.core.managers import ClientManager, ServerManager\n"
        "from fedml_tpu.core.comm.base import MSG_TYPE_PEER_LOST\n"
        "from fedml_tpu.core.message import Message\n"
        "MSG_SYNC = 'sync'\n"
        "MSG_REPORT = 'report'\n"
        "class Srv(ServerManager):\n"
        "    def register_message_receive_handlers(self):\n"
        "        self.register_message_receive_handler(MSG_REPORT,\n"
        "                                              self._on_report)\n"
        "        self.register_message_receive_handler(MSG_TYPE_PEER_LOST,\n"
        "                                              self._on_lost)\n"
        "    def open_round(self):\n"
        "        m = Message(MSG_SYNC, 0, 1)\n"
        "        self.send_message(m)\n"
        "class Cli(ClientManager):\n"
        "    def register_message_receive_handlers(self):\n"
        "        self.register_message_receive_handler(MSG_SYNC,\n"
        "                                              self._on_sync)\n"
        "        self.register_message_receive_handler(MSG_TYPE_PEER_LOST,\n"
        "                                              self._on_lost)\n"
        "    def _on_sync(self, msg):\n"
        "        self.send_message(Message(MSG_REPORT, 1, 0))\n")

    def test_paired_protocol_is_clean(self):
        assert codes(self.PAIRED, path=self.FSM_PATH) == []

    def test_fl120_sent_type_without_counterpart_handler(self):
        # drop the server's report handler: the client's send has nobody
        # listening -- exactly one FL120, at the send's construction
        src = self.PAIRED.replace(
            "        self.register_message_receive_handler(MSG_REPORT,\n"
            "                                              self._on_report)\n",
            "")
        found = lint_source(src, path=self.FSM_PATH)
        # the model checker co-fires: with nobody folding the report the
        # fair path hangs (FL141). The faulted run no longer wedges into
        # FL140 under the widened budget: a second kill is always an
        # enabled transition out of the old dead state, and losing the
        # whole cohort decides the round via the shed policy (verified
        # decided + uncapped)
        assert sorted(f.code for f in found) == ["FL120", "FL141"]
        f120 = [f for f in found if f.code == "FL120"][0]
        assert "report" in f120.message
        assert "`Cli`" in f120.message

    def test_fl121_fsm_without_peer_lost_handler(self):
        # strip only the SERVER's peer-lost registration (first occurrence)
        src = self.PAIRED.replace(
            "        self.register_message_receive_handler(MSG_TYPE_PEER_LOST,\n"
            "                                              self._on_lost)\n",
            "", 1)
        found = lint_source(src, path=self.FSM_PATH)
        assert [f.code for f in found] == ["FL121"]
        assert "`Srv`" in found[0].message

    def test_fl121_credits_peer_lost_by_name_when_unresolvable(self):
        # MSG_TYPE_PEER_LOST is imported from a module OUTSIDE the linted
        # set: the registration must still count (name-based credit)
        assert codes(self.PAIRED, path=self.FSM_PATH) == []

    def test_fl122_handler_for_type_nothing_sends(self):
        src = self.PAIRED.replace(
            "        self.register_message_receive_handler(MSG_SYNC,\n"
            "                                              self._on_sync)\n",
            "        self.register_message_receive_handler(MSG_SYNC,\n"
            "                                              self._on_sync)\n"
            "        self.register_message_receive_handler('zombie',\n"
            "                                              self._on_sync)\n")
        found = lint_source(src, path=self.FSM_PATH)
        assert [f.code for f in found] == ["FL122"]
        assert "zombie" in found[0].message

    def test_reserved_transport_types_exempt(self):
        # "__stop__" etc. are transport-internal: sending one is not
        # FL120, handling peer-lost is not FL122
        src = self.PAIRED.replace(
            "        m = Message(MSG_SYNC, 0, 1)\n",
            "        m = Message(MSG_SYNC, 0, 1)\n"
            "        self.send_message(Message('__stop__', 0, 1))\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_non_fsm_classes_ignored(self):
        src = (
            "from fedml_tpu.core.message import Message\n"
            "class Codec:\n"  # constructs Messages but is no FSM
            "    def decode(self, b):\n"
            "        m = Message('anything', 0, 0)\n"
            "        self.send_message(m)\n")
        assert codes(src) == []

    def test_constants_resolve_across_modules(self, tmp_path):
        (tmp_path / "proto_consts.py").write_text(
            "MSG_PING = 'ping'\nMSG_PONG = 'pong'\n")
        (tmp_path / "proto_fsms.py").write_text(
            "from proto_consts import MSG_PING, MSG_PONG\n"
            "from fedml_tpu.core.managers import (ClientManager,\n"
            "                                     ServerManager)\n"
            "from fedml_tpu.core.message import Message\n"
            "class Srv(ServerManager):\n"
            "    def register_message_receive_handlers(self):\n"
            "        self.register_message_receive_handler(MSG_PONG, self.h)\n"
            "        self.register_message_receive_handler(\n"
            "            MSG_TYPE_PEER_LOST, self.h)\n"
            "    def kick(self):\n"
            "        self.send_message(Message(MSG_PING, 0, 1))\n"
            "class Cli(ClientManager):\n"
            "    def register_message_receive_handlers(self):\n"
            "        self.register_message_receive_handler(MSG_PING, self.h)\n"
            "        self.register_message_receive_handler(\n"
            "            MSG_TYPE_PEER_LOST, self.h)\n"
            "    def h(self, msg):\n"
            "        self.send_message(Message(MSG_PONG, 1, 0))\n")
        assert lint_paths([str(tmp_path)]) == []
        # now rename the server's handled constant: the cross-module
        # resolution must notice the client's 'pong' is unhandled
        (tmp_path / "proto_fsms.py").write_text(
            (tmp_path / "proto_fsms.py").read_text().replace(
                "register_message_receive_handler(MSG_PONG",
                "register_message_receive_handler('pong2'"))
        found = lint_paths([str(tmp_path)])
        # FL141 rides along: the unresolved reply also hangs the
        # composed round's fair path (temporal view of the same
        # rename). No FL140 under the widened budget -- the second
        # kill keeps every faulted strand live until the shed policy
        # decides the round
        assert sorted(f.code for f in found) == ["FL120", "FL122",
                                                 "FL141"]

    def test_inherited_peer_lost_handler_credits_subclass(self):
        src = self.PAIRED + (
            "class CliSub(Cli):\n"
            "    def register_message_receive_handlers(self):\n"
            "        super().register_message_receive_handlers()\n"
            "        self.register_message_receive_handler(MSG_SYNC,\n"
            "                                              self._on_sync)\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_acceptance_deleting_report_registration_in_integration(self):
        # the ISSUE's acceptance fixture: deleting the MSG_C2S_REPORT
        # registration in resilience/integration.py produces exactly one
        # FL120 (and the committed file produces zero)
        path = os.path.join(REPO_ROOT,
                            "fedml_tpu/resilience/integration.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        needle = ("        self.register_message_receive_handler("
                  "MSG_C2S_REPORT,\n"
                  "                                              "
                  "self._on_report)\n")
        assert needle in src, "integration.py registration shape changed"
        clean = lint_source(src, path="fedml_tpu/resilience/integration.py")
        assert [f.code for f in clean] == []
        found = lint_source(src.replace(needle, ""),
                            path="fedml_tpu/resilience/integration.py")
        # rule view (FL120) plus the model checker's temporal twin: the
        # fair exploration hangs round 0 on the unfolded report
        assert sorted(f.code for f in found) == ["FL120", "FL141"]
        f120 = [f for f in found if f.code == "FL120"][0]
        assert "res_report" in f120.message


class TestConcurrencyRules:
    """FL123-FL125: the fedcheck thread-safety pass."""

    HEADER = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self, register):\n"
        "        self._lock = threading.Lock()\n"
        "        self.state = 0\n"
        "        self.count = 0\n"
        "        register(self._on_msg)\n")  # bound method escapes: root

    # FL123 ---------------------------------------------------------------
    def test_fl123_owned_attr_read_without_lock(self):
        src = self.HEADER + (
            "    def _on_msg(self, m):\n"
            "        with self._lock:\n"
            "            self.state = m\n"
            "    def snapshot(self):\n"
            "        return self.state\n")
        found = lint_source(src, path=LIB_PATH)
        assert [f.code for f in found] == ["FL123"]
        assert "self._lock" in found[0].message

    def test_fl123_negative_all_accesses_guarded(self):
        src = self.HEADER + (
            "    def _on_msg(self, m):\n"
            "        with self._lock:\n"
            "            self.state = m\n"
            "    def snapshot(self):\n"
            "        with self._lock:\n"
            "            return self.state\n")
        assert codes(src) == []

    def test_fl123_unowned_counter_aug_on_handler_path(self):
        src = self.HEADER + (
            "    def _on_msg(self, m):\n"
            "        self.count += 1\n")
        found = lint_source(src, path=LIB_PATH)
        assert [f.code for f in found] == ["FL123"]
        assert "lose updates" in found[0].message

    def test_fl123_negative_plain_flag_store_not_flagged(self):
        # benign racy bool flags (self._running = False) are out of
        # scope: no owning lock, no read-modify-write
        src = self.HEADER + (
            "    def _on_msg(self, m):\n"
            "        self.running = False\n"
            "    def stop(self):\n"
            "        self.running = True\n")
        assert codes(src) == []

    def test_fl123_negative_init_writes_exempt(self):
        # __init__ happens-before the threads exist
        src = self.HEADER + (
            "    def _on_msg(self, m):\n"
            "        with self._lock:\n"
            "            self.state = m\n")
        assert codes(src) == []

    def test_fl123_locked_helper_call_propagation(self):
        # the *_locked idiom: a private helper whose every call site
        # holds the lock is analyzed as holding it too
        src = self.HEADER + (
            "    def _on_msg(self, m):\n"
            "        with self._lock:\n"
            "            self._apply(m)\n"
            "    def _apply(self, m):\n"
            "        self.state = m\n"
            "    def snapshot(self):\n"
            "        with self._lock:\n"
            "            return self.state\n")
        assert codes(src) == []

    def test_fl123_negative_lock_free_class_out_of_scope(self):
        # no locks created => no declared concurrency contract to check
        src = (
            "class C:\n"
            "    def __init__(self, register):\n"
            "        self.count = 0\n"
            "        register(self._on_msg)\n"
            "    def _on_msg(self, m):\n"
            "        self.count += 1\n")
        assert codes(src) == []

    # FL124 ---------------------------------------------------------------
    def test_fl124_lock_order_cycle(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def one(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "    def two(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n")
        found = lint_source(src, path=LIB_PATH)
        assert [f.code for f in found] == ["FL124"]
        assert "_a" in found[0].message and "_b" in found[0].message

    def test_fl124_negative_consistent_order(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def one(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "    def two(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n")
        assert codes(src) == []

    def test_fl124_cycle_through_locked_helper(self):
        # the nesting is split across a call: one() holds _a and calls a
        # helper that takes _b; two() nests them directly the other way
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def one(self):\n"
            "        with self._a:\n"
            "            self._grab_b()\n"
            "    def _grab_b(self):\n"
            "        with self._b:\n"
            "            pass\n"
            "    def two(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n")
        assert codes(src) == ["FL124"]

    # FL125 ---------------------------------------------------------------
    def test_fl125_blocking_send_under_state_lock(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def send(self, sock, payload):\n"
            "        with self._lock:\n"
            "            sock.sendall(payload)\n")
        found = lint_source(src, path=LIB_PATH)
        assert [f.code for f in found] == ["FL125"]
        assert "io_lock" in found[0].message

    def test_fl125_negative_io_lock_exempt(self):
        # a dedicated send-serialization lock exists to be held across
        # the blocking write
        src = (
            "from fedml_tpu.analysis.locks import io_lock\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._wire = io_lock()\n"
            "    def send(self, sock, payload):\n"
            "        with self._wire:\n"
            "            sock.sendall(payload)\n")
        assert codes(src) == []

    def test_fl125_negative_blocking_outside_lock(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def send(self, sock, payload):\n"
            "        with self._lock:\n"
            "            dest = self.route\n"
            "        sock.sendall(payload)\n")
        assert codes(src) == []

    def test_fl125_through_locked_helper(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def send(self, sock, payload):\n"
            "        with self._lock:\n"
            "            self._write(sock, payload)\n"
            "    def _write(self, sock, payload):\n"
            "        sock.sendall(payload)\n")
        assert codes(src) == ["FL125"]

    def test_repo_control_plane_is_clean(self, monkeypatch):
        # the audited surface of this PR: zero unbaselined findings on
        # the comm transports, the managers, and the resilience package
        monkeypatch.chdir(REPO_ROOT)
        found = lint_paths(["fedml_tpu/core/comm", "fedml_tpu/core/managers.py",
                            "fedml_tpu/resilience"])
        assert found == [f for f in found if f.baselined]
        assert [f.code for f in found] == []


class TestFl113Captures:
    def test_fl113_jnp_asarray_capture(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "table = jnp.asarray(make_table())\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x + table\n")
        assert codes(src) == ["FL113"]

    def test_fl113_np_load_capture(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "weights = np.load('weights.npy')\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x + weights\n")
        assert codes(src) == ["FL113"]

    def test_fl113_negative_literal_table_and_argument(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "lut = jnp.asarray([1.0, 2.0, 3.0])\n"  # bounded literal
            "@jax.jit\n"
            "def f(x, table):\n"                      # big data as an arg
            "    return x + lut + table\n")
        assert codes(src) == []

    def test_fl113_negative_scalar_constant(self):
        # jnp.asarray over a scalar literal is trivially bounded
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "eps = jnp.asarray(1e-6)\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x + eps\n")
        assert codes(src) == []

    def test_fl112_still_wins_on_statically_sized_captures(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "table = jnp.zeros((512, 512))\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x + table\n")
        assert codes(src) == ["FL112"]


class TestFl114WallclockTiming:
    JIT = ("import time\n"
           "import jax\n"
           "@jax.jit\n"
           "def step(x):\n"
           "    return x * 2\n")

    def test_fl114_unsynced_delta_around_jitted_call(self):
        src = self.JIT + (
            "def measure(x):\n"
            "    t0 = time.time()\n"
            "    y = step(x)\n"
            "    dt = time.time() - t0\n"
            "    return y, dt\n")
        assert codes(src) == ["FL114"]

    def test_fl114_wrap_form_and_from_import_perf_counter(self):
        src = (
            "from time import perf_counter\n"
            "import jax\n"
            "f = jax.jit(lambda x: x + 1)\n"
            "def measure(x):\n"
            "    t0 = perf_counter()\n"
            "    y = f(x)\n"
            "    return perf_counter() - t0\n")
        assert codes(src) == ["FL114"]

    def test_fl114_negative_block_until_ready(self):
        src = self.JIT + (
            "def measure(x):\n"
            "    t0 = time.time()\n"
            "    y = jax.block_until_ready(step(x))\n"
            "    return time.time() - t0\n")
        assert codes(src) == []

    def test_fl114_negative_end_of_round_sync(self):
        src = self.JIT + (
            "from fedml_tpu.utils.profiling import end_of_round_sync\n"
            "def measure(x):\n"
            "    t0 = time.time()\n"
            "    y = step(x)\n"
            "    end_of_round_sync(y)\n"
            "    return time.time() - t0\n")
        assert codes(src) == []

    def test_fl114_negative_value_fetch_is_a_sync(self):
        # float(...) blocks on the producing computation: the measured
        # timing is honest (the bench scripts' value-fetch idiom)
        src = self.JIT + (
            "def measure(x):\n"
            "    t0 = time.perf_counter()\n"
            "    loss = float(step(x))\n"
            "    return time.perf_counter() - t0\n")
        assert codes(src) == []

    def test_fl114_negative_no_jitted_call_in_region(self):
        src = self.JIT + (
            "def measure(x):\n"
            "    t0 = time.time()\n"
            "    y = host_work(x)\n"
            "    return time.time() - t0\n")
        assert codes(src) == []

    def test_fl114_inner_reassignment_reports_exactly_once(self):
        # the loop re-times with its own t0: the unsynced inner delta is
        # ONE finding (from the loop suite's scan) -- the outer, stale t0
        # must not double-report it through the nested suite
        src = self.JIT + (
            "def measure(x):\n"
            "    t0 = time.time()\n"
            "    for _ in range(3):\n"
            "        t0 = time.perf_counter()\n"
            "        y = step(x)\n"
            "        dt_in = time.perf_counter() - t0\n")
        assert codes(src) == ["FL114"]


class TestFl115MetricLabelCardinality:
    REG = ("from fedml_tpu.observability.registry import get_registry\n"
           "reg = get_registry()\n")

    def test_fl115_rank_label_on_counter(self):
        src = self.REG + (
            "def on_report(rank):\n"
            "    reg.inc('fed_reports_total', rank=rank)\n")
        assert codes(src) == ["FL115"]

    def test_fl115_client_id_label_on_gauge(self):
        src = self.REG + (
            "def note(client_id, s):\n"
            "    reg.set_gauge('fed_staleness', s, client=client_id)\n")
        assert codes(src) == ["FL115"]

    def test_fl115_sender_id_call_under_any_label_name(self):
        # the label NAME is innocuous ('src'); the VALUE derives from
        # msg.get_sender_id() -- still one series per sender
        src = self.REG + (
            "def handler(msg):\n"
            "    reg.inc('fed_reports_total', src=msg.get_sender_id())\n")
        assert codes(src) == ["FL115"]

    def test_fl115_cohort_loop_variable(self):
        src = self.REG + (
            "def fan_out(self):\n"
            "    for r in sorted(self.alive):\n"
            "        reg.inc('fed_syncs_total', target=r)\n")
        assert codes(src) == ["FL115"]

    def test_fl115_attribute_receiver_and_self_rank(self):
        src = ("from fedml_tpu.observability.registry import MetricsRegistry\n"
               "class C:\n"
               "    def __init__(self):\n"
               "        self.registry = MetricsRegistry()\n"
               "    def f(self):\n"
               "        self.registry.observe('lat_seconds', 0.1,\n"
               "                              worker=self.rank)\n")
        assert codes(src) == ["FL115"]

    def test_fl115_negative_bounded_labels(self):
        # transport/direction/outcome/reason: bounded enums, the intended
        # label idiom -- and per-client values in the VALUE position
        # (not a label) are fine
        src = self.REG + (
            "def ok(n, outcome, staleness):\n"
            "    reg.inc('comm_bytes_total', n, transport='tcp',\n"
            "            direction='sent')\n"
            "    reg.inc('fed_round_attempts_total', outcome=outcome)\n"
            "    reg.set_gauge('fed_update_staleness', staleness)\n"
            "    reg.observe('lat_seconds', 0.1, buckets=(1, 2))\n")
        assert codes(src) == []

    def test_fl115_negative_unrelated_receiver(self):
        # a non-registry object with an `inc` method is out of scope --
        # only receivers bound from get_registry()/MetricsRegistry()
        # (or a `registry` attribute) are judged
        src = ("def f(counters, rank):\n"
               "    counters.inc('x_total', rank=rank)\n")
        assert codes(src) == []

    def test_fl115_negative_loop_taint_is_function_scoped(self):
        # a cohort loop's short `r` in ONE method must not taint an
        # unrelated `r` used as a label value in another function
        src = self.REG + (
            "def fan_out(self):\n"
            "    for r in sorted(self.alive):\n"
            "        send(r)\n"
            "def elsewhere(r):\n"
            "    reg.inc('retries_total', route=r)\n")
        assert codes(src) == []

    def test_fl115_negative_chunk_range_loop_is_not_a_cohort(self):
        # `range(0, C, self.client_chunk)` iterates chunk offsets, not
        # clients: exact-name collection matching must not taint c0
        src = self.REG + (
            "def stream(self, C):\n"
            "    for c0 in range(0, C, self.client_chunk):\n"
            "        reg.inc('fed_chunks_total', offset_bucket=c0 // 512)\n")
        assert codes(src) == []


class TestSarif:
    SRC = TestBaseline.SRC

    def test_sarif_structure_and_result(self, tmp_path):
        from fedml_tpu.analysis.linter import render_sarif
        mod = tmp_path / "mod.py"
        mod.write_text(self.SRC)
        findings = lint_paths([str(mod)])
        doc = json.loads(render_sarif(findings))
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "fedlint"
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert {"FL104", "FL120", "FL123"} <= rule_ids
        res = run["results"][0]
        assert res["ruleId"] == "FL104"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("mod.py")
        assert loc["region"]["startLine"] == 3
        assert "suppressions" not in res

    def test_sarif_marks_baselined_as_suppressed(self, tmp_path):
        from fedml_tpu.analysis.linter import render_sarif
        mod = tmp_path / "mod.py"
        mod.write_text(self.SRC)
        findings = lint_paths([str(mod)])
        bl = tmp_path / "bl.json"
        write_baseline(findings, str(bl))
        fresh = lint_paths([str(mod)])
        apply_baseline(fresh, load_baseline(str(bl)))
        doc = json.loads(render_sarif(fresh))
        assert doc["runs"][0]["results"][0]["suppressions"]

    def test_cli_sarif_out_single_run_two_reports(self, tmp_path, capsys):
        # the ci.sh shape: one lint run emits JSON on stdout AND the
        # SARIF file via --sarif-out
        mod = tmp_path / "mod.py"
        mod.write_text(self.SRC)
        out = tmp_path / "rep.sarif"
        rc = fedlint_main([str(mod), "--baseline", "", "--format", "json",
                           "--sarif-out", str(out)])
        json_doc = json.loads(capsys.readouterr().out)
        assert rc == 1 and json_doc["summary"]["new"] == 1
        sarif = json.loads(out.read_text())
        assert sarif["runs"][0]["results"][0]["ruleId"] == "FL104"

    def test_cli_sarif_format(self, tmp_path, capsys):
        mod = tmp_path / "mod.py"
        mod.write_text(self.SRC)
        rc = fedlint_main([str(mod), "--baseline", "", "--format", "sarif"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert doc["runs"][0]["results"][0]["ruleId"] == "FL104"
        # clean tree: valid empty SARIF, exit 0
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        assert fedlint_main([str(clean), "--baseline", "",
                             "--format", "sarif"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["runs"][0]["results"] == []


class TestRaceAudit:
    """The runtime sanitizer: instrumented locks + blocking chokepoints."""

    def test_factories_return_plain_locks_outside_audit(self):
        import threading as _t
        from fedml_tpu.analysis.locks import (audited_lock, audited_rlock,
                                              io_lock)
        assert isinstance(audited_lock(), type(_t.Lock()))
        assert isinstance(audited_rlock(), type(_t.RLock()))
        assert isinstance(io_lock(), type(_t.Lock()))

    def test_lock_order_cycle_detected(self):
        from fedml_tpu.analysis import race_audit
        from fedml_tpu.analysis.locks import audited_lock
        with race_audit() as ra:
            a = audited_lock()
            b = audited_lock()
            with a:
                with b:
                    pass
            with b:
                with a:
                    pass
        rep = ra.report()
        assert rep["race/locks_created"] == 2
        assert rep["race/acquisitions"] == 4
        assert len(rep["race/lock_order_cycles"]) == 1

    def test_consistent_order_is_clean(self):
        from fedml_tpu.analysis import race_audit
        from fedml_tpu.analysis.locks import audited_lock
        with race_audit() as ra:
            a, b = audited_lock(), audited_lock()
            for _ in range(3):
                with a:
                    with b:
                        pass
        assert ra.report()["race/lock_order_cycles"] == []

    def test_held_while_blocking_state_vs_io(self):
        from fedml_tpu.analysis import race_audit
        from fedml_tpu.analysis.locks import audited_lock, io_lock
        with race_audit() as ra:
            state, wire = audited_lock(), io_lock()
            with wire:
                ra.blocking("fake.send")   # io lock: exempt
            assert ra.held_while_blocking == []
            with state:
                ra.blocking("fake.send")   # state lock: violation
        events = ra.report()["race/held_while_blocking"]
        assert len(events) == 1 and events[0][0] == "fake.send"

    def test_tcp_frame_chokepoints_patched(self):
        import socket
        from fedml_tpu.analysis import race_audit
        from fedml_tpu.analysis.locks import audited_lock
        from fedml_tpu.core.comm import tcp as tcp_mod
        orig = tcp_mod._send_frame
        left, right = socket.socketpair()
        try:
            with race_audit() as ra:
                assert tcp_mod._send_frame is not orig  # patched
                lock = audited_lock()
                with lock:
                    tcp_mod._send_frame(left, b"x")  # blocking under state
            assert tcp_mod._send_frame is orig  # restored
            assert len(ra.held_while_blocking) == 1
            assert ra.held_while_blocking[0][0] == "tcp._send_frame"
        finally:
            left.close()
            right.close()

    def test_reentrant_state_lock_no_self_edge(self):
        from fedml_tpu.analysis import race_audit
        from fedml_tpu.analysis.locks import audited_rlock
        with race_audit() as ra:
            rl = audited_rlock()
            with rl:
                with rl:  # reentrant re-acquire: not an order edge
                    pass
        rep = ra.report()
        assert rep["race/order_edges"] == []
        assert rep["race/lock_order_cycles"] == []

    def test_report_goes_to_metrics_logger_and_disabled_passthrough(self):
        from fedml_tpu.analysis import race_audit
        records = []
        with race_audit(metrics_logger=records.append):
            pass
        assert records and "race/locks_created" in records[0]
        with race_audit(enabled=False) as ra:
            assert ra is None


# -- runtime auditor ------------------------------------------------------

def _args(**kw):
    base = dict(client_num_per_round=2, comm_round=2, epochs=1,
                batch_size=16, lr=0.3, client_optimizer="sgd", wd=0.0,
                frequency_of_the_test=100, ci=0, seed=0)
    base.update(kw)
    return types.SimpleNamespace(**base)


def _spec():
    return make_classification_spec(
        models.LogisticRegression(num_classes=10, apply_sigmoid=False),
        jnp.zeros((1, 60)))


def _dataset():
    return load_synthetic_federated(client_num=2, n_train=80, n_test=20,
                                    alpha=0.0, beta=0.0, seed=0)


class TestRuntimeAuditor:
    def test_healthy_two_round_fedavg_no_steady_state_retraces(self):
        api = FedAvgAPI(_dataset(), _spec(), _args())
        with audit() as auditor:
            api.train_one_round()
            api.train_one_round()
        report = auditor.report()
        assert report["audit/rounds"] == 2
        assert len(report["audit/retraces_per_round"]) == 2
        assert report["audit/retraces_per_round"][0] > 0  # warm-up compile
        assert report["audit/steady_state_retraces"] == 0
        assert report["audit/transfer_guard_violations"] == 0

    def test_detects_intentional_retrace(self):
        # shrinking the batch size between rounds changes the packed
        # cohort shapes -> round 2 must re-trace, and the auditor must see
        # it in round 2's bucket
        api = FedAvgAPI(_dataset(), _spec(), _args())
        with audit() as auditor:
            api.train_one_round()
            api.runner.batch_size = 8
            api.train_one_round()
        assert auditor.retraces_per_round[1] > 0
        assert auditor.report()["audit/steady_state_retraces"] > 0

    def test_transfer_guard_violation_counted_not_raised(self):
        with audit(transfer_guard="all") as auditor:
            with auditor.guard():
                jnp.ones((4,)) + np.ones((4,), np.float32)  # implicit h2d
        assert auditor.transfer_guard_violations == 1

    def test_report_goes_to_metrics_logger(self):
        records = []
        with audit(metrics_logger=records.append) as auditor:
            jax.block_until_ready(jax.jit(lambda x: x + 1)(jnp.ones(3)))
            auditor.mark_round()
        assert len(records) == 1
        assert records[0]["audit/rounds"] == 1
        assert records[0]["audit/retraces_per_round"][0] > 0

    def test_disabled_audit_yields_none(self):
        with audit(enabled=False) as auditor:
            assert auditor is None
        assert current_auditor() is None

    def test_end_of_round_sync_without_auditor(self):
        state = jax.jit(lambda x: x * 2)(jnp.ones(3))
        assert end_of_round_sync(state) is state

    def test_end_of_round_sync_marks_rounds_on_active_auditor(self):
        with audit() as auditor:
            end_of_round_sync(jnp.ones(3))
            end_of_round_sync(jnp.ones(3))
        assert auditor.rounds == 2

    def test_midrun_eval_does_not_pollute_round_buckets(self):
        # eval runs BETWEEN round syncs (frequency_of_the_test=1 fires it
        # after every round): its first-time compile must be booked as
        # trailing, not as a phantom retrace in the next round's bucket
        api = FedAvgAPI(_dataset(), _spec(),
                        _args(frequency_of_the_test=1))
        with audit() as auditor:
            api.train()
        report = auditor.report()
        assert report["audit/rounds"] == 2
        assert report["audit/steady_state_retraces"] == 0
        assert report["audit/trailing_traces"] > 0  # the eval compile
        assert report["audit/transfer_guard_violations"] == 0

    def test_off_round_work_without_auditor_is_noop(self):
        from fedml_tpu.utils.profiling import off_round_work
        with off_round_work():
            pass
        assert current_auditor() is None

    def test_trailing_activity_reported_separately(self):
        with audit() as auditor:
            end_of_round_sync(jnp.ones(3))
            jax.block_until_ready(jax.jit(lambda x: x - 1)(jnp.ones(7)))
        report = auditor.report()
        assert report["audit/rounds"] == 1
        assert report["audit/trailing_traces"] > 0
        # post-round work (final eval, teardown) is not a round retrace
        assert report["audit/steady_state_retraces"] == 0

    def test_nested_audit_restores_outer(self):
        with audit() as outer:
            with audit() as inner:
                assert current_auditor() is inner
            assert current_auditor() is outer
        assert current_auditor() is None


class TestCrossClass:
    """FL126: the fedcheck v2 interprocedural pass -- cross-class
    lock-order cycles and held-lock blocking chains."""

    BLOCKING = (
        "from fedml_tpu.core.locks import audited_lock, io_lock\n"
        "class Transport:\n"
        "    def __init__(self):\n"
        "        self._send_lock = io_lock()\n"
        "    def stop(self):\n"
        "        with self._send_lock:\n"
        "            self.sock.sendall(b'')\n"
        "class Server:\n"
        "    def __init__(self):\n"
        "        self._lock = audited_lock()\n"
        "        self.t = Transport()\n"
        "    def on_report(self, msg):\n"
        "        with self._lock:\n"
        "            self.shutdown()\n"
        "    def shutdown(self):\n"
        "        self.t.stop()\n")

    def test_fl126_blocking_chain_through_field(self):
        found = lint_source(self.BLOCKING, path=LIB_PATH)
        assert [f.code for f in found] == ["FL126"]
        msg = found[0].message
        # anchored at the call under the lock, citing the creation site
        # and the blocking label reached two classes away
        assert "`Server.on_report` calls `self.shutdown()`" in msg
        assert "fake.py:10" in msg         # audited_lock() creation site
        assert "sendall" in msg and "Transport" in msg

    def test_fl126_negative_call_outside_lock(self):
        src = self.BLOCKING.replace(
            "        with self._lock:\n"
            "            self.shutdown()\n",
            "        with self._lock:\n"
            "            pass\n"
            "        self.shutdown()\n")
        assert codes(src) == []

    def test_fl126_negative_direct_blocking_stays_fl125(self):
        # blocking directly under the class's own lock is the
        # class-local FL125 finding, not a duplicate FL126
        src = (
            "from fedml_tpu.core.locks import audited_lock\n"
            "class Server:\n"
            "    def __init__(self):\n"
            "        self._lock = audited_lock()\n"
            "    def on_report(self, msg):\n"
            "        with self._lock:\n"
            "            self.sock.sendall(b'')\n")
        assert codes(src) == ["FL125"]

    CYCLE = (
        "from fedml_tpu.core.locks import audited_lock\n"
        "class Left:\n"
        "    def __init__(self):\n"
        "        self._la = audited_lock()\n"
        "        self.peer = Right(self)\n"
        "    def step(self):\n"
        "        with self._la:\n"
        "            self.peer.poke()\n"
        "    def nudge(self):\n"
        "        with self._la:\n"
        "            pass\n"
        "class Right:\n"
        "    def __init__(self, owner):\n"
        "        self._lb = audited_lock()\n"
        "        self.owner = owner\n"
        "    def poke(self):\n"
        "        with self._lb:\n"
        "            pass\n"
        "    def kick(self):\n"
        "        with self._lb:\n"
        "            self.owner.nudge()\n")

    def test_fl126_cross_class_cycle(self):
        # Left holds la and takes Right's lb; Right holds lb and takes
        # la back through the owner field -- neither class's AST alone
        # shows the cycle (FL124 is silent), the global graph does
        found = lint_source(self.CYCLE, path=LIB_PATH)
        assert [f.code for f in found] == ["FL126"]
        assert "cycle" in found[0].message
        assert "fake.py:4" in found[0].message  # la's creation site
        assert "fake.py:14" in found[0].message  # lb's creation site

    def test_fl126_negative_consistent_cross_class_order(self):
        src = self.CYCLE.replace(
            "    def kick(self):\n"
            "        with self._lb:\n"
            "            self.owner.nudge()\n",
            "    def kick(self):\n"
            "        self.owner.nudge()\n")
        assert codes(src) == []

    def test_fl126_ctor_param_flow_through_super_init(self):
        # the com_manager shape: the field is assigned in the BASE
        # __init__ from a forwarded ctor param; its type comes from the
        # instantiation site two classes away
        src = (
            "from fedml_tpu.core.locks import audited_lock\n"
            "class Pipe:\n"
            "    def send(self, b):\n"
            "        self.sock.sendall(b)\n"
            "class BaseMgr:\n"
            "    def __init__(self, comm):\n"
            "        self.comm = comm\n"
            "    def flush(self):\n"
            "        self.comm.send(b'')\n"
            "class Sub(BaseMgr):\n"
            "    def __init__(self, comm):\n"
            "        super().__init__(comm)\n"
            "        self._lock = audited_lock()\n"
            "    def handler(self, msg):\n"
            "        with self._lock:\n"
            "            self.flush()\n"
            "def build():\n"
            "    p = Pipe()\n"
            "    return Sub(p)\n")
        found = lint_source(src, path=LIB_PATH)
        assert [f.code for f in found] == ["FL126"]
        assert "`Sub.handler` calls `self.flush()`" in found[0].message
        # sever the flow: nobody instantiates Sub with a Pipe -> the
        # field is untyped and the pass judges nothing
        severed = src.replace("    p = Pipe()\n    return Sub(p)\n",
                              "    return None\n")
        assert codes(severed) == []

    def test_fl126_callback_field_cycle_and_fixed_shape(self):
        # the RoundController shape: a bound method handed to another
        # class's constructor; invoking it UNDER that class's lock while
        # the method takes its own class's lock closes a cycle
        src = (
            "from fedml_tpu.core.locks import audited_lock\n"
            "class Ctl:\n"
            "    def __init__(self, cb):\n"
            "        self._cl = audited_lock()\n"
            "        self._cb = cb\n"
            "    def begin(self):\n"
            "        with self._cl:\n"
            "            pass\n"
            "    def fire(self):\n"
            "        with self._cl:\n"
            "            self._cb()\n"
            "class Srv:\n"
            "    def __init__(self):\n"
            "        self._sl = audited_lock()\n"
            "        self.ctl = Ctl(self._advance)\n"
            "    def _advance(self):\n"
            "        with self._sl:\n"
            "            self.ctl.begin()\n")
        found = lint_source(src, path=LIB_PATH)
        assert [f.code for f in found] == ["FL126"]
        assert "cycle" in found[0].message
        # the shipped fix shape: fire the callback OUTSIDE the lock
        fixed = src.replace(
            "    def fire(self):\n"
            "        with self._cl:\n"
            "            self._cb()\n",
            "    def fire(self):\n"
            "        with self._cl:\n"
            "            cb = self._cb\n"
            "        cb()\n")
        assert codes(fixed) == []

    def test_creation_site_identity_matches_runtime(self, tmp_path):
        # satellite: the static FL126 lock identity and the runtime
        # auditor's instrumented-lock identity are the SAME string, so a
        # static finding and a held_while_blocking flight-recorder event
        # cross-reference by equality
        import ast
        import importlib.util
        from fedml_tpu.analysis.crossclass import CrossClassIndex
        from fedml_tpu.analysis.runtime import race_audit
        src = ("from fedml_tpu.core.locks import audited_lock\n"
               "class C:\n"
               "    def __init__(self):\n"
               "        self._lock = audited_lock()\n")
        mod_file = tmp_path / "idmod.py"
        mod_file.write_text(src)
        index = CrossClassIndex()
        index.add_module(str(mod_file), ast.parse(src))
        cls = next(iter(index.modules.values()))["classes"]["C"]
        static_site = cls.families["_lock"][1]
        assert static_site == "idmod.py:4"
        spec = importlib.util.spec_from_file_location("idmod",
                                                      str(mod_file))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        with race_audit() as ra:
            inst = mod.C()
        assert inst._lock.site == static_site
        assert ra.locks_created == 1

    def _subset_paths(self, tmp_path, integration_src):
        import shutil
        files = ["fedml_tpu/core/managers.py",
                 "fedml_tpu/core/comm/base.py",
                 "fedml_tpu/core/comm/tcp.py",
                 "fedml_tpu/core/locks.py",
                 "fedml_tpu/core/message.py",
                 "fedml_tpu/resilience/policy.py"]
        for f in files:
            dst = tmp_path / f
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(os.path.join(REPO_ROOT, f), dst)
        dst = tmp_path / "fedml_tpu/resilience/integration.py"
        dst.parent.mkdir(parents=True, exist_ok=True)
        dst.write_text(integration_src)
        return str(tmp_path)

    def test_acceptance_reverting_finish_under_advance_lock(self, tmp_path):
        # THE acceptance fixture: reverting the PR-5 fix (finish() ran
        # the transport STOP wave -- blocking per-peer writes -- under
        # _advance_lock) must produce exactly one FL126, statically,
        # over the real control-plane sources. The committed tree is
        # clean.
        path = os.path.join(REPO_ROOT,
                            "fedml_tpu/resilience/integration.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        fixed = (
            "            done = done or self.failed is not None\n"
            "        if done:                    "
            "# see start(): no STOP wave under the\n"
            "            self.finish()           # turnover lock\n"
            "            self._report_health()\n"
            "            return\n"
            "        self._send_syncs(syncs, span)\n"
            "        self._report_health()\n"
            "\n"
            "    def _on_round_abandoned")
        reverted = (
            "            done = done or self.failed is not None\n"
            "            if done:\n"
            "                self.finish()\n"
            "                return\n"
            "        self._send_syncs(syncs, span)\n"
            "        self._report_health()\n"
            "\n"
            "    def _on_round_abandoned")
        assert fixed in src, "integration.py turnover shape changed"
        clean_root = self._subset_paths(tmp_path, src)
        assert [f.code for f in lint_paths([clean_root])] == []
        mutated = src.replace(fixed, reverted, 1)
        found = lint_paths([self._subset_paths(tmp_path, mutated)])
        assert [f.code for f in found] == ["FL126"]
        msg = found[0].message
        assert "`ResilientFedAvgServer._on_round_complete` " \
               "calls `self.finish()`" in msg
        # the cited identity is _advance_lock's creation site -- the
        # same string race_audit()/the flight recorder would report
        # (line shifts when integration.py grows above __init__; PR 11
        # moved it 307 -> 321 adding the --transport flag, PR 13 moved
        # it 321 -> 333 adding the pace-steering/rejoin state, PR 15
        # moved it 333 -> 374 adding the wire-compression client half,
        # PR 16 moved it 374 -> 383 wiring the server onto RoundProgram,
        # the fedpriv PR moved it 383 -> 399 adding the dp/robust legs)
        assert "integration.py:399" in msg
        assert "_send_frame" in msg and "TcpCommManager" in msg


class TestFsmSequencing:
    """FL127: path-sensitive handler analysis -- a handler path that
    neither replies, advances the controller, terminates, nor logs is a
    silently hung round."""

    FSM_PATH = "fedml_tpu/core/fsm_fake.py"

    HEADER = (
        "import logging\n"
        "from fedml_tpu.core.managers import ClientManager, ServerManager\n"
        "from fedml_tpu.core.comm.base import MSG_TYPE_PEER_LOST\n"
        "from fedml_tpu.core.message import Message\n"
        "MSG_A = 'a'\n"
        "MSG_B = 'b'\n"
        "class Cli(ClientManager):\n"
        "    def register_message_receive_handlers(self):\n"
        "        self.register_message_receive_handler(MSG_A, self._on_a)\n"
        "        self.register_message_receive_handler(\n"
        "            MSG_TYPE_PEER_LOST, self._on_lost)\n"
        "    def _on_a(self, msg):\n"
        "        m = Message(MSG_B, 1, 0)\n"
        "        m.add('flag', 1)\n"
        "        self.send_message(m)\n"
        "    def _on_lost(self, msg):\n"
        "        self.finish()\n"
        "class Srv(ServerManager):\n"
        "    def register_message_receive_handlers(self):\n"
        "        self.register_message_receive_handler(MSG_B, self._on_b)\n"
        "        self.register_message_receive_handler(\n"
        "            MSG_TYPE_PEER_LOST, self._on_lost)\n"
        "    def _on_lost(self, msg):\n"
        "        self.finish()\n")

    def _with_on_b(self, body):
        return self.HEADER + "    def _on_b(self, msg):\n" + body

    def test_fl127_silent_fall_through_branch(self):
        src = self._with_on_b(
            "        if msg.get('flag'):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n")
        found = lint_source(src, path=self.FSM_PATH)
        assert [f.code for f in found] == ["FL127"]
        assert "`Srv._on_b`" in found[0].message
        assert "falls off the end" in found[0].message

    def test_fl127_silent_early_return(self):
        src = self._with_on_b(
            "        if not msg.get('flag'):\n"
            "            return\n"
            "        self.send_message(Message(MSG_A, 0, 1))\n")
        found = lint_source(src, path=self.FSM_PATH)
        assert [f.code for f in found] == ["FL127"]
        assert "returns early" in found[0].message

    def test_fl127_negative_logged_ignore_is_a_decision(self):
        src = self._with_on_b(
            "        if not msg.get('flag'):\n"
            "            logging.info('stale report ignored')\n"
            "            return\n"
            "        self.send_message(Message(MSG_A, 0, 1))\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_fl127_negative_raise_terminates(self):
        src = self._with_on_b(
            "        if not msg.get('flag'):\n"
            "            raise RuntimeError('protocol violation')\n"
            "        self.send_message(Message(MSG_A, 0, 1))\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_fl127_negative_finish_terminates(self):
        src = self._with_on_b(
            "        if msg.get('flag'):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n"
            "        else:\n"
            "            self.finish()\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_fl127_negative_controller_advance(self):
        src = self.HEADER.replace(
            "class Srv(ServerManager):\n",
            "class RoundController:\n"
            "    pass\n"
            "class Srv(ServerManager):\n"
            "    def __init__(self, args, comm):\n"
            "        super().__init__(args, comm)\n"
            "        self._controller = RoundController()\n") + (
            "    def open_round(self):\n"
            "        self.send_message(Message(MSG_A, 0, 1))\n"
            "    def _on_b(self, msg):\n"
            "        self._controller.report(msg.get('flag'))\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_fl127_helper_transitivity(self):
        # a same-class helper that acts on all of ITS paths acts for the
        # handler; a helper with a silent path does not. (The helper
        # reads 'flag': FL128's helper-following walk -- fedsqueeze --
        # sees through the forward, so an unread key would correctly be
        # a set-never-read finding, not an opaque escape.)
        acting = self._with_on_b(
            "        self._reply(msg)\n") + (
            "    def _reply(self, msg):\n"
            "        logging.info('flag=%s', msg.get('flag'))\n"
            "        self.send_message(Message(MSG_A, 0, 1))\n")
        assert codes(acting, path=self.FSM_PATH) == []
        silent = self._with_on_b(
            "        self._reply(msg)\n") + (
            "    def _reply(self, msg):\n"
            "        if msg.get('flag'):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n")
        assert codes(silent, path=self.FSM_PATH) == ["FL127"]

    def test_fl127_try_except_paths(self):
        # an except path that only swallows is silent; logging it passes
        silent = self._with_on_b(
            "        try:\n"
            "            self.send_message(Message(MSG_A, 0,\n"
            "                                      msg.get('flag')))\n"
            "        except OSError:\n"
            "            pass\n")
        assert codes(silent, path=self.FSM_PATH) == ["FL127"]
        logged = self._with_on_b(
            "        try:\n"
            "            self.send_message(Message(MSG_A, 0,\n"
            "                                      msg.get('flag')))\n"
            "        except OSError:\n"
            "            logging.warning('send failed')\n")
        assert codes(logged, path=self.FSM_PATH) == []

    def test_fl127_loop_body_cannot_guarantee(self):
        # a for-loop may run zero times: an act only inside it does not
        # cover the zero-iteration path
        src = self._with_on_b(
            "        for r in msg.get('flag') or []:\n"
            "            self.send_message(Message(MSG_A, 0, r))\n")
        assert codes(src, path=self.FSM_PATH) == ["FL127"]

    def test_acceptance_deleting_reply_in_report_handler(self):
        # the ISSUE's mutation fixture: deleting the controller advance
        # on the report handler's path in resilience/integration.py
        # yields exactly one FL127 (the committed file yields zero)
        path = os.path.join(REPO_ROOT,
                            "fedml_tpu/resilience/integration.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        needle = (
            "            self._controller.report(\n"
            "                msg.get(\"round\"), msg.get(\"attempt\"), "
            "msg.get_sender_id(),\n"
            "                msg.get(\"num_samples\"), "
            "self._report_payload(msg))")
        assert needle in src, "integration.py report handler changed"
        clean = lint_source(src, path="fedml_tpu/resilience/integration.py")
        assert [f.code for f in clean] == []
        found = lint_source(src.replace(needle, "            pass"),
                            path="fedml_tpu/resilience/integration.py")
        assert [f.code for f in found].count("FL127") == 1
        f127 = [f for f in found if f.code == "FL127"][0]
        assert "`ResilientFedAvgServer._on_report`" in f127.message
        # the orphaned payload keys surface as FL128 companions: the
        # deleted reads leave num_samples/attempt/params set-never-read;
        # the model checker adds the temporal view of the same gutting
        # (inert delivery FL142, hung fair round FL141)
        assert {f.code for f in found} == {"FL127", "FL128", "FL141",
                                           "FL142"}


class TestPayloadSchema:
    """FL128: handler payload reads paired against the counterpart
    role's Message.add() schemas."""

    FSM_PATH = "fedml_tpu/core/fsm_fake.py"
    HEADER = TestFsmSequencing.HEADER

    def _with_on_b(self, body):
        return self.HEADER + "    def _on_b(self, msg):\n" + body

    def test_fl128_renamed_key_produces_the_pair(self):
        # rename the sender's add(): the read goes never-set, the new
        # key goes never-read -- exactly one of each
        src = self._with_on_b(
            "        if msg.get('flag'):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n"
            "        else:\n"
            "            self.finish()\n")
        assert codes(src, path=self.FSM_PATH) == []
        renamed = src.replace("m.add('flag', 1)", "m.add('flagg', 1)")
        found = lint_source(renamed, path=self.FSM_PATH)
        assert [f.code for f in found] == ["FL128", "FL128"]
        msgs = " | ".join(f.message for f in found)
        assert "reads payload key 'flag'" in msgs
        assert "key 'flagg' of message type 'b' is set here" in msgs

    def test_fl128_negative_open_schema_non_literal_key(self):
        # a computed add() key opens the schema: read-never-set judges
        # nothing for that type
        src = self._with_on_b(
            "        if msg.get('flag'):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n"
            "        else:\n"
            "            self.finish()\n").replace(
            "        m.add('flag', 1)\n",
            "        k = 'fl' + 'ag'\n"
            "        m.add(k, 1)\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_fl128_negative_escaping_message_opens_schema(self):
        # the built message flowing into an unknown call may gain keys
        # the pass cannot see -- no read-never-set for its type
        src = self._with_on_b(
            "        if msg.get('flag') and msg.get('extra'):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n"
            "        else:\n"
            "            self.finish()\n").replace(
            "        self.send_message(m)\n",
            "        self.decorate(m)\n"
            "        self.send_message(m)\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_fl128_negative_opaque_handler_suppresses_set_never_read(self):
        # the handler passes its message on: reads are unknowable, so a
        # set key is not judged dead
        src = self._with_on_b(
            "        self.process(msg)\n"
            "        self.finish()\n") + (
            "    def open_round(self):\n"
            "        self.send_message(Message(MSG_A, 0, 1))\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_fl128_set_never_read_by_transparent_handler(self):
        src = self._with_on_b(
            "        if msg.get('flag'):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n"
            "        else:\n"
            "            self.finish()\n").replace(
            "        m.add('flag', 1)\n",
            "        m.add('flag', 1)\n"
            "        m.add('debug_blob', 2)\n")
        found = lint_source(src, path=self.FSM_PATH)
        assert [f.code for f in found] == ["FL128"]
        assert "'debug_blob'" in found[0].message
        assert "ever reads it" in found[0].message

    def test_fl128_reserved_and_control_keys_exempt(self):
        # __-prefixed control fields (the tracer's __trace__) and the
        # envelope keys are never judged
        src = self._with_on_b(
            "        if msg.get('flag'):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n"
            "        else:\n"
            "            self.finish()\n").replace(
            "        m.add('flag', 1)\n",
            "        m.add('flag', 1)\n"
            "        m.add('__trace__', {})\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_acceptance_renaming_add_key_in_integration(self):
        # the ISSUE's mutation fixture: renaming ONE Message.add() key in
        # resilience/integration.py yields exactly one FL128 read-never-
        # set and exactly one set-never-read companion
        path = os.path.join(REPO_ROOT,
                            "fedml_tpu/resilience/integration.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        needle = 'out.add("num_samples", float(n))'
        assert needle in src, "integration.py report build changed"
        clean = lint_source(src, path="fedml_tpu/resilience/integration.py")
        assert [f.code for f in clean] == []
        found = lint_source(
            src.replace(needle, 'out.add("n_samples", float(n))'),
            path="fedml_tpu/resilience/integration.py")
        assert [f.code for f in found] == ["FL128", "FL128"]
        msgs = " | ".join(f.message for f in found)
        assert "reads payload key 'num_samples'" in msgs
        assert "'n_samples' of message type 'res_report' is set" in msgs


class TestPayloadSchemaNamedKeys:
    """FL128 named-key resolution (fedsqueeze satellite): payload keys
    spelled as module constants (the compressed-report vocabulary --
    WIRE_DELTA_KEY/'cdelta') resolve through the constant/import index,
    pair by NAME when out of static reach (single-file runs), and the
    walk follows the message into same-class helpers."""

    FSM_PATH = "fedml_tpu/core/fsm_fake.py"

    HEADER = (
        "import logging\n"
        "from fedml_tpu.core.managers import ClientManager, ServerManager\n"
        "from fedml_tpu.core.comm.base import MSG_TYPE_PEER_LOST\n"
        "from fedml_tpu.core.message import Message\n"
        "MSG_A = 'a'\n"
        "MSG_B = 'b'\n"
        "K_DELTA = 'cdelta'\n"
        "K_SPEC = 'compressor'\n"
        "class Cli(ClientManager):\n"
        "    def register_message_receive_handlers(self):\n"
        "        self.register_message_receive_handler(MSG_A, self._on_a)\n"
        "        self.register_message_receive_handler(\n"
        "            MSG_TYPE_PEER_LOST, self._on_lost)\n"
        "    def _on_a(self, msg):\n"
        "        m = Message(MSG_B, 1, 0)\n"
        "        m.add(K_DELTA, 1)\n"
        "        m.add(K_SPEC, 'qsgd')\n"
        "        self.send_message(m)\n"
        "    def _on_lost(self, msg):\n"
        "        self.finish()\n"
        "class Srv(ServerManager):\n"
        "    def register_message_receive_handlers(self):\n"
        "        self.register_message_receive_handler(MSG_B, self._on_b)\n"
        "        self.register_message_receive_handler(\n"
        "            MSG_TYPE_PEER_LOST, self._on_lost)\n"
        "    def _on_lost(self, msg):\n"
        "        self.finish()\n")

    def _with_on_b(self, body):
        return self.HEADER + "    def _on_b(self, msg):\n" + body

    def test_named_keys_resolve_and_pair_clean(self):
        # the compressed-report shape: constant-named adds paired with
        # constant-named reads -- zero findings, schema fully judged
        src = self._with_on_b(
            "        if msg.get(K_DELTA) and msg.get(K_SPEC):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n"
            "        else:\n"
            "            self.finish()\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_named_key_read_never_set_fires(self):
        # the schema is RESOLVED, not open: a named read with no
        # counterpart add is caught (the old behavior -- dynamic key ->
        # opaque -- would have silently suppressed this)
        src = self._with_on_b(
            "        if msg.get(K_DELTA) and msg.get(K_SPEC):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n"
            "        else:\n"
            "            self.finish()\n").replace(
            "        m.add(K_SPEC, 'qsgd')\n", "")
        found = lint_source(src, path=self.FSM_PATH)
        assert [f.code for f in found] == ["FL128"]
        assert "reads payload key 'compressor'" in found[0].message

    def test_named_key_set_never_read_fires(self):
        src = self._with_on_b(
            "        if msg.get(K_DELTA):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n"
            "        else:\n"
            "            self.finish()\n")
        found = lint_source(src, path=self.FSM_PATH)
        assert [f.code for f in found] == ["FL128"]
        assert "'compressor' of message type 'b' is set" in found[0].message

    def test_unresolvable_names_pair_by_name(self):
        # constants imported from OUTSIDE the fileset (single-file runs:
        # the real FSMs import WIRE_DELTA_KEY from compression.wire):
        # same-named add/read pair by NAME, zero findings -- and the
        # schema stays judged for the literal keys around them
        src = self._with_on_b(
            "        if msg.get(EXT_KEY):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n"
            "        else:\n"
            "            self.finish()\n").replace(
            "K_DELTA = 'cdelta'\n",
            "from fedml_tpu.compression.wire import EXT_KEY\n"
            "K_DELTA = 'cdelta'\n").replace(
            "        m.add(K_DELTA, 1)\n"
            "        m.add(K_SPEC, 'qsgd')\n",
            "        m.add(EXT_KEY, 1)\n"
            "        m.add('n', 2.0)\n")
        found = lint_source(src, path=self.FSM_PATH)
        # EXT_KEY pairs by name; the literal 'n' is genuinely unread
        assert [f.code for f in found] == ["FL128"]
        assert "'n' of message type 'b' is set" in found[0].message

    def test_unpaired_unresolvable_named_add_opens_schema(self):
        # an out-of-reach named add with NO matching named read could be
        # setting any key: read-never-set must stay conservative
        src = self._with_on_b(
            "        if msg.get('something'):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n"
            "        else:\n"
            "            self.finish()\n").replace(
            "K_DELTA = 'cdelta'\n",
            "from fedml_tpu.compression.wire import EXT_KEY\n"
            "K_DELTA = 'cdelta'\n").replace(
            "        m.add(K_DELTA, 1)\n", "        m.add(EXT_KEY, 1)\n")
        found = lint_source(src, path=self.FSM_PATH)
        # 'something' is NOT judged read-never-set (EXT_KEY might be it)
        # but K_SPEC's resolved 'compressor' is still set-never-read?
        # no -- the unpaired named READ-side is empty; the reader reads
        # 'something' only, so 'compressor' IS set-never-read... except
        # the reader's reads are fully visible; assert exactly that one
        assert [f.code for f in found] == ["FL128"]
        assert "'compressor'" in found[0].message

    def test_locally_bound_name_key_stays_opaque(self):
        # a key named by a LOCAL variable is a runtime value, never the
        # module constant of the same spelling (the FL115 scoping
        # lesson): no resolution, schema opens, zero findings
        src = self._with_on_b(
            "        for K_DELTA in ('x', 'y'):\n"
            "            logging.info('%s', msg.get(K_DELTA))\n"
            "        self.send_message(Message(MSG_A, 0, 1))\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_helper_following_sees_through_report_payload_split(self):
        # the fedsqueeze server shape: the handler forwards msg to a
        # same-class helper that does the payload reads -- the walk
        # follows it, so the schema stays judged (and a renamed key
        # still fires the pair THROUGH the helper)
        src = self._with_on_b(
            "        payload = self._payload(msg)\n"
            "        if payload:\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n"
            "        else:\n"
            "            self.finish()\n") + (
            "    def _payload(self, msg):\n"
            "        if msg.get(K_DELTA) is None:\n"
            "            return msg.get(K_SPEC)\n"
            "        return msg.get(K_DELTA)\n")
        assert codes(src, path=self.FSM_PATH) == []
        renamed = src.replace("        m.add(K_DELTA, 1)\n",
                              "        m.add('cdeltaa', 1)\n")
        found = lint_source(renamed, path=self.FSM_PATH)
        assert sorted(f.code for f in found) == ["FL128", "FL128"]
        msgs = " | ".join(f.message for f in found)
        assert "reads payload key 'cdelta'" in msgs
        assert "'cdeltaa' of message type 'b' is set" in msgs

    def test_acceptance_compressed_report_keys_in_integration(self):
        # the real tree: resilience/integration.py's compressed-report
        # keys (cdelta/compressor via WIRE_DELTA_KEY/WIRE_SPEC_KEY) are
        # covered -- single-file lint stays clean (name-pairing), and
        # renaming the CONSTANT on just the send side fires the pair
        path = os.path.join(REPO_ROOT,
                            "fedml_tpu/resilience/integration.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        assert "out.add(WIRE_DELTA_KEY, enc)" in src
        assert [f.code for f in lint_source(
            src, path="fedml_tpu/resilience/integration.py")] == []
        # rename the add-side constant: the read half goes never-set by
        # NAME (WIRE_DELTA_KEY read has no same-named add anymore); the
        # renamed named add is unpaired -> conservative open on the
        # OTHER side, so exactly the read-side finding appears... the
        # unpaired named add suppresses read-never-set; what fires is
        # the set-never-read of the renamed key? also name-suppressed.
        # The honest pin: single-file mutation is conservative (no FP,
        # no finding); the FULL-TREE lint resolves values and fires.
        mutated = src.replace("out.add(WIRE_DELTA_KEY, enc)",
                              "out.add(WIRE_DELTA_KEY_X, enc)")
        assert [f.code for f in lint_source(
            mutated, path="fedml_tpu/resilience/integration.py")] == []
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            pkg = os.path.join(d, "fedml_tpu")
            for rel in ("core/managers.py", "core/comm/base.py",
                        "core/message.py", "compression/wire.py",
                        "resilience/integration.py"):
                dst = os.path.join(pkg, rel)
                os.makedirs(os.path.dirname(dst), exist_ok=True)
                with open(os.path.join(REPO_ROOT, "fedml_tpu", rel),
                          encoding="utf-8") as fh:
                    body = fh.read()
                if rel.endswith("integration.py"):
                    body = body.replace(
                        "out.add(WIRE_DELTA_KEY, enc)",
                        "out.add(\"cdelta_v2\", enc)")
                with open(dst, "w", encoding="utf-8") as fh:
                    fh.write(body)
                init = os.path.join(os.path.dirname(dst), "__init__.py")
                open(init, "a").close()
            open(os.path.join(pkg, "__init__.py"), "a").close()
            found = [f for f in lint_paths([pkg]) if f.code == "FL128"]
        msgs = " | ".join(f.message for f in found)
        assert "reads payload key 'cdelta'" in msgs, msgs
        assert "'cdelta_v2' of message type 'res_report' is set" in msgs


class TestBodyDonationInference:
    """The --fix upgrade: donation argnums inferred from which params
    flow into the returned pytree, replacing the name heuristic where
    the body evidence is unambiguous."""

    def _body(self, src):
        import ast as ast_mod
        from fedml_tpu.analysis.dataflow import (
            infer_donate_argnums_from_body)
        return infer_donate_argnums_from_body(ast_mod.parse(src).body[0])

    def test_flow_into_return_is_the_donation_set(self):
        assert self._body(
            "def round_fn(state, data):\n"
            "    new = state * 2\n"
            "    return new\n") == (0,)
        assert self._body(
            "def round_fn(state, opt, data):\n"
            "    g = grad(state, data)\n"
            "    s2, o2 = update(state, opt, g)\n"
            "    return s2, o2\n") == (0, 1, 2)

    def test_loop_carried_rebind_keeps_taint(self):
        # iteration 2's `state` taint must survive the strong update
        assert self._body(
            "def round_fn(state, xs):\n"
            "    for x in xs:\n"
            "        state = step(state, x)\n"
            "    return state\n") == (0, 1)

    def test_ambiguity_bails_to_none(self):
        assert self._body(
            "def round_fn(state, *rest):\n"
            "    return state\n") is None
        assert self._body(
            "def round_fn(state, data):\n"
            "    f = lambda v: v + 1\n"
            "    return f(state)\n") is None
        assert self._body(
            "def round_fn(state, data):\n"
            "    state.update(data)\n") is None  # no returned value

    def test_fix_body_overrides_name_heuristic_both_ways(self):
        from fedml_tpu.analysis.dataflow import plan_donation_fixes
        # `n_state` is name-ineligible ('n' segment) but flows into the
        # return: the body evidence donates it
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def agg_round(n_state, acc):\n"
            "    return n_state + acc\n")
        fixed = plan_donation_fixes("m.py", src).apply()
        assert "donate_argnums=(0, 1)" in fixed
        # `residuals` is name-eligible but never flows into the return:
        # the body evidence excludes it (donating it aliases nothing)
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def agg_round(state, residuals):\n"
            "    log_norm(residuals)\n"
            "    return state * 2\n")
        fixed = plan_donation_fixes("m.py", src).apply()
        assert "donate_argnums=(0,)" in fixed

    def test_fix_falls_back_to_names_when_ambiguous(self):
        from fedml_tpu.analysis.dataflow import plan_donation_fixes
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def agg_round(state, cohort_data):\n"
            "    f = lambda v: v\n"
            "    return f(state)\n")
        fixed = plan_donation_fixes("m.py", src).apply()
        # name heuristic: state donated, cohort_data never
        assert "donate_argnums=(0,)" in fixed

    def test_fix_skips_when_nothing_flows(self):
        from fedml_tpu.analysis.dataflow import plan_donation_fixes
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def agg_round(state, data):\n"
            "    return jnp.zeros(4)\n")
        plan = plan_donation_fixes("m.py", src)
        assert not plan.edits
        assert plan.skipped \
            and "flows into the returned" in plan.skipped[0][2]


class TestSarifRuleMetadata:
    """Satellite: SARIF rule metadata for the fedcheck passes."""

    def test_rules_carry_pass_tags(self, tmp_path):
        from fedml_tpu.analysis.linter import render_sarif
        doc = json.loads(render_sarif([]))
        rules = {r["id"]: r for r in doc["runs"][0]["tool"]["driver"]["rules"]}
        for code in ("FL126", "FL127", "FL128"):
            assert code in rules, code
        assert rules["FL126"]["properties"]["tags"] == [
            "fedcheck-concurrency", "race-audit-crossref"]
        assert rules["FL127"]["properties"]["tags"] == ["fedcheck-protocol"]
        assert rules["FL128"]["properties"]["tags"] == ["fedcheck-protocol"]
        assert rules["FL120"]["properties"]["tags"] == ["fedcheck-protocol"]
        assert rules["FL124"]["properties"]["tags"] == [
            "fedcheck-concurrency", "race-audit-crossref"]
        assert rules["FL101"]["properties"]["tags"] == ["fedlint-jax"]

    def test_catalog_has_the_new_rules(self):
        for code in ("FL126", "FL127", "FL128"):
            assert code in RULES
            title, rationale = RULES[code]
            assert title and rationale


class TestWallTimeBudget:
    """Satellite: the CI wall-time budget flag."""

    def test_within_budget_exits_zero(self, tmp_path, capsys):
        mod = tmp_path / "ok.py"
        mod.write_text("x = 1\n")
        assert fedlint_main([str(mod), "--baseline", "",
                             "--max-seconds", "300"]) == 0
        err = capsys.readouterr().err
        assert "wall time" in err and "budget 300.0s" in err

    def test_blown_budget_exits_nonzero(self, tmp_path, capsys):
        mod = tmp_path / "ok.py"
        mod.write_text("x = 1\n")
        assert fedlint_main([str(mod), "--baseline", "",
                             "--max-seconds", "0"]) == 1
        assert "budget exceeded" in capsys.readouterr().err


class TestReviewHardening:
    """Regression pins for the precision defects found in review: FL128
    read-surface opacity, FL127 inherited-context acts, FL126 reach
    through recursion cycles, and the taint fixpoint."""

    FSM_PATH = "fedml_tpu/core/fsm_fake.py"
    HEADER = TestFsmSequencing.HEADER

    def _with_on_b(self, body):
        return self.HEADER + "    def _on_b(self, msg):\n" + body

    def test_fl128_get_params_makes_reader_opaque(self):
        # the whole payload dict walks away: a set key must NOT be
        # judged dead (the reads are invisible, not absent)
        src = self._with_on_b(
            "        p = msg.get_params()\n"
            "        self.use(p)\n"
            "        self.finish()\n") + (
            "    def open_round(self):\n"
            "        self.send_message(Message(MSG_A, 0, 1))\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_fl128_dynamic_get_key_makes_reader_opaque(self):
        src = self._with_on_b(
            "        for k in ('flag',):\n"
            "            if msg.get(k):\n"
            "                self.send_message(Message(MSG_A, 0, 1))\n"
            "                return\n"
            "        self.finish()\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_fl128_subscript_write_is_not_a_read(self):
        # msg['relayed'] = True is a mutation: no read-never-set FP for
        # 'relayed', and the mutated message marks the reader opaque
        src = self._with_on_b(
            "        msg['relayed'] = True\n"
            "        if msg.get('flag'):\n"
            "            self.send_message(Message(MSG_A, 0, 1))\n"
            "        else:\n"
            "            self.finish()\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_fl127_inherited_helper_acts(self):
        # the handler lives in a subclass, the acting helper on the base
        src = self.HEADER.replace(
            "class Srv(ServerManager):\n",
            "class SrvBase(ServerManager):\n"
            "    def _broadcast(self):\n"
            "        self.send_message(Message(MSG_A, 0, 1))\n"
            "class Srv(SrvBase):\n") + (
            "    def _on_b(self, msg):\n"
            "        _ = msg.get('flag')\n"
            "        self._broadcast()\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_fl127_subclass_controller_acts_for_base_handler(self):
        # the handler is defined (and registered) on the base; the
        # controller field is assigned only in the registering subclass
        src = self.HEADER.replace(
            "class Srv(ServerManager):\n",
            "class RoundController:\n"
            "    pass\n"
            "class SrvBase(ServerManager):\n"
            "    def open_round(self):\n"
            "        self.send_message(Message(MSG_A, 0, 1))\n"
            "    def _on_b(self, msg):\n"
            "        self._controller.report(msg.get('flag'))\n"
            "class Srv(SrvBase):\n"
            "    def __init__(self, args, comm):\n"
            "        super().__init__(args, comm)\n"
            "        self._controller = RoundController()\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_fl126_reach_survives_recursion_cycle(self):
        # A.ping <-> B.pong recurse; the blocking op hangs off the
        # cycle. A memoized DFS freezes an empty partial result for the
        # cycle partner; the fixpoint must still see the block when a
        # third class enters through it under a lock.
        src = (
            "from fedml_tpu.core.locks import audited_lock\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self.b = B(self)\n"
            "    def ping(self, n):\n"
            "        self.sock.sendall(b'')\n"
            "        self.b.pong(n)\n"
            "class B:\n"
            "    def __init__(self, a):\n"
            "        self.a = a\n"
            "    def pong(self, n):\n"
            "        self.a.ping(n)\n"
            "class H:\n"
            "    def __init__(self):\n"
            "        self._lock = audited_lock()\n"
            "        self.b = B(A())\n"
            "    def handler(self, msg):\n"
            "        with self._lock:\n"
            "            self.enterhelper()\n"
            "    def enterhelper(self):\n"
            "        self.b.pong(0)\n")
        found = lint_source(src, path=LIB_PATH)
        assert [f.code for f in found] == ["FL126"]
        assert "sendall" in found[0].message

    def test_taint_fixpoint_reaches_three_link_loop_chain(self):
        import ast as ast_mod
        from fedml_tpu.analysis.dataflow import (
            infer_donate_argnums_from_body)
        fn = ast_mod.parse(
            "def round_fn(state, xs):\n"
            "    out = 0\n"
            "    acc = 0\n"
            "    tmp = 0\n"
            "    for x in xs:\n"
            "        out = norm(tmp)\n"
            "        tmp = mix(acc, x)\n"
            "        acc = step(state)\n"
            "    return out\n").body[0]
        # state -> acc -> tmp -> out needs one pass per link
        assert infer_donate_argnums_from_body(fn) == (0, 1)

    def test_taint_branch_join_unions_if_else(self):
        import ast as ast_mod
        from fedml_tpu.analysis.dataflow import (
            infer_donate_argnums_from_body)
        # state flows to the return via the if branch only; a
        # sequential walk would let the else branch overwrite it
        fn = ast_mod.parse(
            "def round_fn(state, data):\n"
            "    if cond():\n"
            "        out = state\n"
            "    else:\n"
            "        out = data\n"
            "    return out\n").body[0]
        assert infer_donate_argnums_from_body(fn) == (0, 1)
        # try/except branches join the same way
        fn = ast_mod.parse(
            "def round_fn(state, fallback):\n"
            "    try:\n"
            "        out = step(state)\n"
            "    except ValueError:\n"
            "        out = fallback\n"
            "    return out\n").body[0]
        assert infer_donate_argnums_from_body(fn) == (0, 1)

    def test_fl127_act_in_loop_header_covers_all_paths(self):
        # the iterable/test evaluates even on the zero-iteration path
        src = self._with_on_b(
            "        for r in self.mk(msg.get('flag')):\n"
            "            pass\n").replace(
            "class Srv(ServerManager):\n",
            "class RoundController:\n"
            "    pass\n"
            "class Srv(ServerManager):\n"
            "    def __init__(self, args, comm):\n"
            "        super().__init__(args, comm)\n"
            "        self._controller = RoundController()\n"
            "    def open_round(self):\n"
            "        self.send_message(Message(MSG_A, 0, 1))\n"
            "    def mk(self, flag):\n"
            "        return self._controller.drain(flag)\n")
        assert codes(src, path=self.FSM_PATH) == []

    def test_max_seconds_applies_to_fix_path(self, tmp_path, capsys):
        mod = tmp_path / "ok.py"
        mod.write_text("x = 1\n")
        assert fedlint_main([str(mod), "--fix", "--max-seconds", "0"]) == 1
        assert "budget exceeded" in capsys.readouterr().err
        assert fedlint_main([str(mod), "--fix",
                             "--max-seconds", "300"]) == 0
        capsys.readouterr()


class TestEventLoopReadiness:
    """FL129: blocking calls reachable from event-loop callbacks (the
    single-thread analog of FL125) -- shipped AHEAD of the transport it
    guards (fedml_tpu/net/eventloop.py), per docs/ANALYSIS.md's former
    'Future rules' entry."""

    def test_blocking_in_registered_callback_and_closure(self):
        # sleep in the registered callback itself AND sendall one
        # self-call deep: both flagged (closure, not just roots). The
        # callback rides selector-style tuple data.
        src = (
            "import selectors, time\n"
            "class Loop:\n"
            "    def __init__(self):\n"
            "        self._sel = selectors.DefaultSelector()\n"
            "        self._sel.register(0, selectors.EVENT_READ,\n"
            "                           (self._on_read, None))\n"
            "    def _on_read(self, conn, mask):\n"
            "        time.sleep(0.1)\n"
            "        self._drain(conn)\n"
            "    def _drain(self, conn):\n"
            "        conn.sock.sendall(b'x')\n")
        assert codes(src) == ["FL129", "FL129"]

    def test_nonblocking_loop_shape_passes(self):
        # recv_into/accept/send on ready fds ARE the loop's correct
        # form; a dispatcher-thread method (not registered) may block.
        src = (
            "import selectors, time\n"
            "class Loop:\n"
            "    def __init__(self):\n"
            "        self._sel = selectors.DefaultSelector()\n"
            "        self._sel.register(0, selectors.EVENT_READ,\n"
            "                           self._on_read)\n"
            "    def _on_read(self, conn, mask):\n"
            "        conn.sock.recv_into(conn.buf)\n"
            "        conn.sock.send(b'x')\n"
            "    def handle_receive_message(self):\n"
            "        time.sleep(1)\n")
        assert codes(src) == []

    def test_unregistered_class_out_of_scope(self):
        # no selector registration, no coroutine: plain threaded code
        # blocking freely is FL125's business (when locks are held),
        # never FL129's
        src = (
            "import time\n"
            "class Worker:\n"
            "    def run(self):\n"
            "        time.sleep(1)\n"
            "        self.sock.sendall(b'x')\n")
        assert codes(src) == []

    def test_coroutine_blocking_flagged(self):
        # module-level coroutine: time.sleep instead of asyncio.sleep
        src = (
            "import time\n"
            "async def pump(q):\n"
            "    time.sleep(1)\n")
        assert codes(src) == ["FL129"]
        # async method on a class: rooted without any registration
        src = (
            "import time\n"
            "class S:\n"
            "    async def pump(self):\n"
            "        self._step()\n"
            "    def _step(self):\n"
            "        time.sleep(1)\n")
        assert codes(src) == ["FL129"]
        # blocking DIRECTLY in an async method: exactly ONE finding --
        # the class checker owns it; the free-coroutine branch must not
        # double-report class-nested AsyncFunctionDefs (review finding)
        src = (
            "import time\n"
            "class S:\n"
            "    async def pump(self):\n"
            "        time.sleep(1)\n")
        assert codes(src) == ["FL129"]

    def test_asyncio_scheduler_args_root(self):
        src = (
            "class S:\n"
            "    def arm(self, loop):\n"
            "        loop.call_soon(self._tick)\n"
            "    def _tick(self):\n"
            "        self.q.join()\n")
        assert codes(src) == ["FL129"]

    def test_mutation_eventloop_sendall(self):
        # revert-mutation fixture over the REAL transport: swapping the
        # loop's non-blocking send for sendall must produce exactly one
        # FL129; the committed source is clean.
        path = os.path.join(REPO_ROOT, "fedml_tpu/net/eventloop.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        assert [f for f in lint_source(src, path=path)
                if f.code == "FL129"] == []
        good = "                n = conn.sock.send(buf)"
        assert src.count(good) == 1, "eventloop _flush_conn shape changed"
        mutated = src.replace(
            good, "                n = len(buf); conn.sock.sendall(buf)")
        found = [f for f in lint_source(mutated, path=path)
                 if f.code == "FL129"]
        assert len(found) == 1, found
        assert "sendall" in found[0].message
        assert "_flush_conn" in found[0].message

    def test_decode_stage_callback_rooted(self):
        # ISSUE 14: a method handed to DecodeStage(...) runs on shard
        # decode workers -- one blocked decode stalls every peer hashed
        # to that shard, so the callback is held to FL129's grammar
        # (directly and through its self-call closure)
        src = (
            "import time\n"
            "from fedml_tpu.net.ingest import DecodeStage\n"
            "class T:\n"
            "    def __init__(self, q):\n"
            "        self._stage = DecodeStage(4, self._decode, q)\n"
            "    def _decode(self, item):\n"
            "        self._slow()\n"
            "        return item\n"
            "    def _slow(self):\n"
            "        time.sleep(0.1)\n")
        assert codes(src) == ["FL129"]
        # non-blocking decode callbacks stay clean, and a method NOT
        # handed to the stage may block freely
        src = (
            "import time\n"
            "from fedml_tpu.net.ingest import DecodeStage\n"
            "class T:\n"
            "    def __init__(self, q):\n"
            "        self._stage = DecodeStage(4, self._decode, q)\n"
            "    def _decode(self, item):\n"
            "        return item\n"
            "    def dispatcher(self):\n"
            "        time.sleep(0.1)\n")
        assert codes(src) == []

    def test_mutation_decode_worker_blocking(self):
        # revert-mutation fixture for the decode-worker stage: a
        # blocking call planted in the REAL transport's decode callback
        # (rooted through the DecodeStage construction) must produce
        # exactly one FL129; the committed source is clean.
        path = os.path.join(REPO_ROOT, "fedml_tpu/net/eventloop.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        assert [f for f in lint_source(src, path=path)
                if f.code == "FL129"] == []
        good = ("                payload = message_from_header(header, "
                "frame, off)")
        assert src.count(good) == 1, "eventloop _decode_item shape changed"
        mutated = src.replace(
            good, "                time.sleep(0.001)\n" + good)
        found = [f for f in lint_source(mutated, path=path)
                 if f.code == "FL129"]
        assert len(found) == 1, found
        assert "sleep" in found[0].message
        assert "_decode_item" in found[0].message


class TestContainerElementTyping:
    """Cross-class container-element typing (the former 'Future rules'
    entry): `_observers`-style lists and handler dicts carry element
    types, so FL126 walks transport -> manager dispatch -> registered
    handler chains statically."""

    DRIVER = (
        "from fedml_tpu.core.locks import audited_lock\n"
        "class Manager:\n"
        "    def __init__(self, comm):\n"
        "        self.com_manager = comm\n"
        "        self.com_manager.add_observer(self)\n"
        "        self.handlers = {}\n"
        "    def register_handler(self, t, fn):\n"
        "        self.handlers[t] = fn\n"
        "    def receive_message(self, t, msg):\n"
        "        handler = self.handlers.get(t)\n"
        "        handler(msg)\n"
        "class Fsm(Manager):\n"
        "    def __init__(self, comm):\n"
        "        super().__init__(comm)\n"
        "        self.register_handler('sync', self._on_sync)\n"
        "    def _on_sync(self, msg):\n"
        "        self.com_manager.send_message(msg)\n"
        "class Transport:\n"
        "    def __init__(self):\n"
        "        self._lock = audited_lock()\n"
        "        self._observers = []\n"
        "    def add_observer(self, obs):\n"
        "        self._observers.append(obs)\n"
        "    def send_message(self, msg):\n"
        "        self._socket.sendall(msg)\n"
        "    def dispatch(self, msg):\n"
        "%s"
        "def driver():\n"
        "    t = Transport()\n"
        "    fsm = Fsm(t)\n")

    def test_observer_dispatch_under_lock_flagged(self):
        # the full statically-walked chain: Transport.dispatch (holding
        # its state lock) -> element of _observers (Manager, via the
        # add_observer(self) argument flow) -> receive_message ->
        # handler-dict element (Fsm._on_sync, via register_handler's
        # argument flow) -> com_manager.send_message -> blocking sendall
        src = self.DRIVER % (
            "        with self._lock:\n"
            "            for obs in list(self._observers):\n"
            "                obs.receive_message('sync', msg)\n")
        found = [f for f in lint_source(src, path=LIB_PATH)
                 if f.code == "FL126"]
        assert len(found) == 1, found
        assert "element of `self._observers`" in found[0].message
        assert "Transport.dispatch" in found[0].message

    def test_dispatch_outside_lock_clean(self):
        src = self.DRIVER % (
            "        with self._lock:\n"
            "            pending = list(self._observers)\n"
            "        for obs in pending:\n"
            "            obs.receive_message('sync', msg)\n")
        assert [f for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL126"] == []

    def test_elem_types_resolved(self):
        # introspection: the index types _observers' elements as the
        # Manager subclass family and the handler dict's as the bound
        # handler -- the two hops the docstring promises
        import ast as ast_mod

        from fedml_tpu.analysis.crossclass import CrossClassIndex
        src = self.DRIVER % (
            "        for obs in list(self._observers):\n"
            "            obs.receive_message('sync', msg)\n")
        idx = CrossClassIndex()
        idx.add_module(LIB_PATH, ast_mod.parse(src))
        mod = CrossClassIndex.module_name(LIB_PATH)
        transport = idx.modules[mod]["classes"]["Transport"]
        manager = idx.modules[mod]["classes"]["Manager"]
        obs_types = idx.container_elem_types(transport, "_observers")
        assert ("cls", (mod, "Manager")) in obs_types
        handler_types = idx.container_elem_types(manager, "handlers")
        assert ("mref", (mod, "Fsm"), "_on_sync") in handler_types

    def test_init_param_sink_reuses_ctor_flow(self):
        # an __init__ parameter appended into a container resolves
        # through the existing constructor-argument flows
        import ast as ast_mod

        from fedml_tpu.analysis.crossclass import CrossClassIndex
        src = (
            "class Sink:\n"
            "    def __init__(self, first):\n"
            "        self.items = []\n"
            "        self.items.append(first)\n"
            "class Payload:\n"
            "    def go(self):\n"
            "        pass\n"
            "def driver():\n"
            "    s = Sink(Payload())\n")
        idx = CrossClassIndex()
        idx.add_module(LIB_PATH, ast_mod.parse(src))
        mod = CrossClassIndex.module_name(LIB_PATH)
        sink = idx.modules[mod]["classes"]["Sink"]
        assert ("cls", (mod, "Payload")) in idx.container_elem_types(
            sink, "items")

    def _subset_paths(self, tmp_path, eventloop_src, extra=()):
        import shutil
        files = ["fedml_tpu/core/managers.py",
                 "fedml_tpu/core/comm/base.py",
                 "fedml_tpu/core/comm/tcp.py",
                 "fedml_tpu/core/locks.py",
                 "fedml_tpu/core/message.py",
                 "fedml_tpu/resilience/policy.py",
                 "fedml_tpu/resilience/integration.py"] + list(extra)
        for f in files:
            dst = tmp_path / f
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(os.path.join(REPO_ROOT, f), dst)
        dst = tmp_path / "fedml_tpu/net/eventloop.py"
        dst.parent.mkdir(parents=True, exist_ok=True)
        dst.write_text(eventloop_src)
        return str(tmp_path)

    def test_mutation_eventloop_observer_dispatch_under_lock(self,
                                                             tmp_path):
        # THE acceptance fixture for container typing: moving the event
        # loop's peer-lost observer dispatch under its state lock must
        # produce exactly one FL126 over the real control-plane sources
        # -- the chain (transport -> DistributedManager.receive_message
        # -> registered handler -> send_with_retry) only exists through
        # container elements. The committed tree is clean.
        path = os.path.join(REPO_ROOT, "fedml_tpu/net/eventloop.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        fixed = (
            "        with self._lock:\n"
            "            if peer_rank in self._lost_notified:\n"
            "                return\n"
            "            self._lost_notified.add(peer_rank)\n")
        assert fixed in src, "eventloop _notify_peer_lost shape changed"
        clean_root = self._subset_paths(tmp_path, src)
        assert [f.code for f in lint_paths([clean_root])] == []
        # revert: run the observer fan-out back under the state lock
        tail = (
            "        lost = Message(MSG_TYPE_PEER_LOST, peer_rank, "
            "self.rank)\n"
            "        for obs in list(self._observers):\n"
            "            obs.receive_message(MSG_TYPE_PEER_LOST, lost)\n")
        assert tail in src, "eventloop _notify_peer_lost tail changed"
        mutated = src.replace(tail, (
            "        lost = Message(MSG_TYPE_PEER_LOST, peer_rank, "
            "self.rank)\n"
            "        with self._lock:\n"
            "            for obs in list(self._observers):\n"
            "                obs.receive_message(MSG_TYPE_PEER_LOST, "
            "lost)\n"))
        assert mutated != src
        found = lint_paths([self._subset_paths(tmp_path, mutated)])
        assert [f.code for f in found] == ["FL126"], found
        msg = found[0].message
        assert "element of `self._observers`" in msg
        assert "EventLoopCommManager._notify_peer_lost" in msg

    def test_mutation_batch_dispatch_under_lock(self, tmp_path):
        # ISSUE 14 fixture: the worker->handler BATCH dispatch chain.
        # Moving _dispatch_batch's observer fan-out under the transport
        # state lock must produce exactly one FL126 over the real
        # sources -- the chain (dispatcher -> element of _observers ->
        # receive_message -> registered handler -> send_with_retry)
        # only exists through container elements, now including the
        # async server's batched-fold FSM. The committed tree is clean.
        extra = ("fedml_tpu/resilience/async_agg.py",
                 "fedml_tpu/net/ingest.py")
        path = os.path.join(REPO_ROOT, "fedml_tpu/net/eventloop.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        clean_root = self._subset_paths(tmp_path, src, extra=extra)
        assert [f.code for f in lint_paths([clean_root])] == []
        tail = ('            for m in msgs:\n'
                '                try:\n'
                '                    obs.receive_message(mtype, m)\n')
        assert tail in src, "eventloop _dispatch_batch shape changed"
        mutated = src.replace(tail, (
            '            with self._lock:\n'
            '             for m in msgs:\n'
            '                try:\n'
            '                    obs.receive_message(mtype, m)\n'))
        assert mutated != src
        found = lint_paths([self._subset_paths(tmp_path, mutated,
                                               extra=extra)])
        assert [f.code for f in found] == ["FL126"], found
        msg = found[0].message
        assert "element of `self._observers`" in msg
        assert "EventLoopCommManager._dispatch_batch" in msg


class TestParadigmBypass:
    """FL130: round machinery constructed outside fedml_tpu/program/.

    ISSUE 16 fixture: the RoundProgram subsystem made cohort/aggregation/
    codec logic single-home; this rule is the regression fence. The legacy
    spellings (RoundPolicy/AsyncAggPolicy ctors, raw fold_entries_fp64
    calls) flag anywhere but the program package; the program's own
    vocabulary never does."""

    def test_legacy_spellings_flagged(self):
        src = (
            "from fedml_tpu.resilience.policy import (RoundPolicy,\n"
            "                                         fold_entries_fp64)\n"
            "from fedml_tpu.resilience.async_agg import AsyncAggPolicy\n"
            "def f(entries):\n"
            "    pol = RoundPolicy(deadline_s=1.0)\n"
            "    apol = AsyncAggPolicy(buffer_k=4)\n"
            "    return fold_entries_fp64(entries), pol, apol\n")
        found = [f for f in lint_source(src, path=LIB_PATH)
                 if f.code == "FL130"]
        assert len(found) == 3, found
        assert "RoundProgram" in found[0].message
        assert "host_view" in found[0].message

    def test_dotted_call_flagged(self):
        # the name is matched on the trailing attribute, so a re-exported
        # module-dotted call is still a bypass
        src = (
            "from fedml_tpu.resilience import policy\n"
            "def f(entries):\n"
            "    return policy.fold_entries_fp64(entries)\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL130"] == ["FL130"]

    def test_program_package_exempt(self):
        # inside fedml_tpu/program/ constructing the legs IS the job
        src = (
            "def f(entries):\n"
            "    return fold_entries_fp64(entries)\n")
        assert [f.code for f in
                lint_source(src, path="fedml_tpu/program/aggregation.py")
                if f.code == "FL130"] == []

    def test_program_vocabulary_clean(self):
        # the blessed spellings: program-leg ctors, classmethod
        # constructors, dataclasses.replace evolution, host-view folds
        src = (
            "import dataclasses\n"
            "from fedml_tpu.program import (AggregationPolicy, CohortPolicy,\n"
            "                               RoundProgram)\n"
            "from fedml_tpu.resilience.async_agg import AsyncAggPolicy\n"
            "def f(args, reports):\n"
            "    prog = RoundProgram(cohort=CohortPolicy(overselect=0.2),\n"
            "                        aggregation=AggregationPolicy(buffer_k=8))\n"
            "    prog = prog.replace(\n"
            "        cohort=dataclasses.replace(prog.cohort, quorum=0.6))\n"
            "    apol = AsyncAggPolicy.from_args(args)\n"
            "    host = prog.host_view()\n"
            "    return host.fold_reports(reports), apol\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL130"] == []

    def test_alias_assignment_clean(self):
        # `RoundPolicy = CohortPolicy` (the shims' compatibility alias)
        # is an assignment, not a construction
        src = (
            "from fedml_tpu.program.cohort import CohortPolicy\n"
            "RoundPolicy = CohortPolicy\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL130"] == []

    def test_post_refactor_consumers_pinned_zero(self):
        # the tentpole's acceptance: both paradigms' consumer modules
        # drive the ONE program -- no legacy construction survives
        for rel in ("fedml_tpu/resilience/integration.py",
                    "fedml_tpu/resilience/async_agg.py",
                    "fedml_tpu/resilience/policy.py",
                    "fedml_tpu/net/fanin.py",
                    "fedml_tpu/net/soak.py",
                    "fedml_tpu/algorithms/fedavg.py"):
            with open(os.path.join(REPO_ROOT, rel), encoding="utf-8") as fh:
                src = fh.read()
            assert [f for f in lint_source(src, path=rel)
                    if f.code == "FL130"] == [], rel


class TestDeterminism:
    """FL131-FL135: the feddet bitwise-determinism pass over the fold,
    cohort, and control-law regions (analysis/determinism.py)."""

    # -- FL131: unordered-iteration float folds ---------------------------
    def test_fl131_dict_values_sum_flagged(self):
        src = (
            "def fold_reports(reports):\n"
            "    return sum(float(v[0]) for v in reports.values())\n")
        found = [f for f in lint_source(src, path=LIB_PATH)
                 if f.code == "FL131"]
        assert len(found) == 1
        assert "unordered" in found[0].message
        assert "sorted" in found[0].message

    def test_fl131_bare_mapping_loop_flagged(self):
        src = (
            "def aggregate(reports):\n"
            "    total = 0.0\n"
            "    for r in reports:\n"
            "        total += float(reports[r][0])\n"
            "    return total\n")
        found = [f for f in lint_source(src, path=LIB_PATH)
                 if f.code == "FL131"]
        assert len(found) == 1
        assert "arrival-order" in found[0].message

    def test_fl131_sorted_iteration_clean(self):
        src = (
            "def fold_reports(reports):\n"
            "    return sum(float(reports[r][0]) for r in "
            "sorted(reports))\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL131"] == []

    def test_fl131_int_tally_clean(self):
        # no float evidence: integer addition commutes exactly
        src = (
            "def flush_stats(counts):\n"
            "    return sum(counts.values())\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL131"] == []

    def test_fl131_outside_aggregation_region_clean(self):
        # same hazard shape, but no aggregation entry reaches it: FL131
        # is a region rule, not a style rule (render order is cosmetic)
        src = (
            "def render(stats):\n"
            "    return sum(float(v) for v in stats.values())\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL131"] == []

    def test_fl131_reachable_through_module_function_call(self):
        # the callgraph enters module-level function bodies: the hazard
        # sits in a helper the aggregation entry calls by bare name
        src = (
            "def fold_entries(entries):\n"
            "    return _combine(entries)\n"
            "def _combine(entries):\n"
            "    acc = 0.0\n"
            "    for k in entries:\n"
            "        acc += float(entries[k])\n"
            "    return acc\n")
        found = [f for f in lint_source(src, path=LIB_PATH)
                 if f.code == "FL131"]
        assert len(found) == 1
        assert "_combine" in found[0].message

    # -- FL132: wall-clock control-law decisions --------------------------
    STEER = "fedml_tpu/resilience/steering.py"

    def test_fl132_clock_decision_flagged(self):
        src = (
            "import time\n"
            "class PaceLaw:\n"
            "    def decide(self, obs):\n"
            "        now = time.time()\n"
            "        if now - self._last > 30.0:\n"
            "            return self._backoff()\n"
            "        return None\n")
        found = [f for f in lint_source(src, path=self.STEER)
                 if f.code == "FL132"]
        assert len(found) == 1
        assert "deterministic" in found[0].message

    def test_fl132_measurement_delta_clean(self):
        # measurement-only reads feeding a histogram never reach a
        # decision point -- the legal observability idiom
        src = (
            "import time\n"
            "class PaceLaw:\n"
            "    def decide(self, obs):\n"
            "        t0 = time.time()\n"
            "        out = self._law(obs)\n"
            "        self.mon.observe(time.time() - t0)\n"
            "        return out\n")
        assert [f.code for f in lint_source(src, path=self.STEER)
                if f.code == "FL132"] == []

    def test_fl132_out_of_scope_deadline_controller_clean(self):
        # RoundController-style deadline timers are SUPPOSED to read the
        # clock; the rule scopes by path, not by class-name pattern
        src = (
            "import time\n"
            "class RoundController:\n"
            "    def expired(self):\n"
            "        return time.time() > self._deadline\n")
        assert [f.code for f in lint_source(
            src, path="fedml_tpu/resilience/policy.py")
            if f.code == "FL132"] == []

    # -- FL133: unseeded/constant randomness ------------------------------
    COHORT = "fedml_tpu/program/fake_cohort.py"

    def test_fl133_unseeded_global_draw_flagged(self):
        src = (
            "import numpy as np\n"
            "def sample(ranks, k):\n"
            "    return np.random.choice(ranks, k)\n")
        found = [f for f in lint_source(src, path=self.COHORT)
                 if f.code == "FL133"]
        assert len(found) == 1
        assert "attempt_seed" in found[0].message

    def test_fl133_constant_seed_flagged(self):
        src = (
            "import numpy as np\n"
            "def sample(ranks, k):\n"
            "    np.random.seed(42)\n"
            "    return np.random.choice(ranks, k)\n")
        found = [f for f in lint_source(src, path=self.COHORT)
                 if f.code == "FL133"]
        assert [f.line for f in found] == [3]  # the seed, not the draw

    def test_fl133_unseeded_default_rng_flagged(self):
        src = (
            "import numpy as np\n"
            "def jitter(ranks):\n"
            "    rng = np.random.default_rng()\n"
            "    return rng.choice(ranks)\n")
        found = [f for f in lint_source(src, path=self.COHORT)
                 if f.code == "FL133"]
        assert len(found) == 1

    def test_fl133_constant_prngkey_flagged(self):
        src = (
            "import jax\n"
            "def trace_key():\n"
            "    return jax.random.PRNGKey(0)\n")
        found = [f for f in lint_source(src, path=self.COHORT)
                 if f.code == "FL133"]
        assert len(found) == 1

    def test_fl133_derived_reseed_idiom_clean(self):
        # the historical cohort idiom: np.random.seed(attempt_seed(...))
        # legalizes the global draw that follows it
        src = (
            "import numpy as np\n"
            "from fedml_tpu.program.cohort import attempt_seed\n"
            "def sample(round_idx, attempt, ranks, k):\n"
            "    np.random.seed(attempt_seed(round_idx, attempt))\n"
            "    return np.random.choice(ranks, k)\n")
        assert [f.code for f in lint_source(src, path=self.COHORT)
                if f.code == "FL133"] == []

    def test_fl133_out_of_scope_path_clean(self):
        # core/ is not a cohort/fault/trace path: mpc blinding noise and
        # test utilities draw however they like
        src = (
            "import numpy as np\n"
            "def blind(x):\n"
            "    return x + np.random.normal(size=x.shape)\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL133"] == []

    # -- FL134: handler-thread float accumulation -------------------------
    def test_fl134_handler_fold_flagged(self):
        src = (
            "class AggServer:\n"
            "    def handle_receive_message(self, msg):\n"
            "        self._fold_in(msg)\n"
            "    def _fold_in(self, msg):\n"
            "        self.total += float(msg.get('weight'))\n")
        found = [f for f in lint_source(src, path=LIB_PATH)
                 if f.code == "FL134"]
        assert len(found) == 1
        assert "arrival order" in found[0].message
        assert "_fold_in" in found[0].message

    def test_fl134_buffered_fold_clean(self):
        # the canonical shape: buffer on the handler path, fold through
        # the program's sorted-key machinery
        src = (
            "class AggServer:\n"
            "    def handle_receive_message(self, msg):\n"
            "        self.buffer.add(msg.get('rank'), msg.get('weight'))\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL134"] == []

    def test_fl134_non_handler_method_clean(self):
        # same accumulation off the handler reach: single-threaded
        src = (
            "class Summary:\n"
            "    def tally(self, xs):\n"
            "        for x in xs:\n"
            "            self.total += float(x)\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL134"] == []

    # -- FL135: nondeterministic serialization ----------------------------
    STATUS = "fedml_tpu/observability/fake_status.py"

    def test_fl135_dumps_without_sort_keys_flagged(self):
        src = (
            "import json\n"
            "def write(path, snapshot):\n"
            "    with open(path, 'w') as f:\n"
            "        json.dump(snapshot, f, indent=2)\n")
        found = [f for f in lint_source(src, path=self.STATUS)
                 if f.code == "FL135"]
        assert len(found) == 1
        assert "sort_keys" in found[0].message

    def test_fl135_sorted_keys_clean(self):
        src = (
            "import json\n"
            "def write(path, snapshot):\n"
            "    with open(path, 'w') as f:\n"
            "        json.dump(snapshot, f, indent=2, sort_keys=True)\n")
        assert [f.code for f in lint_source(src, path=self.STATUS)
                if f.code == "FL135"] == []

    def test_fl135_out_of_scope_path_clean(self):
        # diagnostic streams off the manifest/status/wire paths are out
        # of scope: their consumers are humans, not byte-equality gates
        src = (
            "import json\n"
            "def debug_dump(obj):\n"
            "    return json.dumps(obj)\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL135"] == []

    def test_fl135_unsorted_listdir_flagged_everywhere(self):
        # filesystem order is never deterministic: checked on EVERY
        # path, not just the serialization scope
        src = (
            "import os\n"
            "def parties(d):\n"
            "    return [p for p in os.listdir(d) if p.endswith('.csv')]\n")
        found = [f for f in lint_source(src, path=LIB_PATH)
                 if f.code == "FL135"]
        assert len(found) == 1
        assert "filesystem" in found[0].message

    def test_fl135_sorted_listdir_clean(self):
        src = (
            "import os\n"
            "def parties(d):\n"
            "    out = sorted(os.listdir(d))\n"
            "    late = os.listdir(d)\n"
            "    late.sort()\n"
            "    return out + late\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL135"] == []

    # -- FL132 attribute hop + fixpoint local taint -----------------------
    def test_fl132_attribute_hop_flagged(self):
        # the clock is stored by one method and DECIDES in a sibling:
        # the per-class attribute hop catches both ends (the store is a
        # decision shape itself, the load is the hop)
        src = (
            "import time\n"
            "class PaceLaw:\n"
            "    def arm(self):\n"
            "        self._last = time.time()\n"
            "    def decide(self, obs):\n"
            "        if obs.now - self._last > 30.0:\n"
            "            return self._backoff()\n"
            "        return None\n")
        found = [f for f in lint_source(src, path=self.STEER)
                 if f.code == "FL132"]
        assert sorted(f.line for f in found) == [4, 6]

    def test_fl132_local_chain_fixpoint_flagged(self):
        # two local bindings deep: the one-level taint of the original
        # rule missed this laundering; the fixpoint closes it
        src = (
            "import time\n"
            "class PaceLaw:\n"
            "    def decide(self, obs):\n"
            "        t = time.time()\n"
            "        elapsed = t - obs.started\n"
            "        if elapsed > 30.0:\n"
            "            return self._backoff()\n"
            "        return None\n")
        found = [f for f in lint_source(src, path=self.STEER)
                 if f.code == "FL132"]
        assert [f.line for f in found] == [6]

    def test_fl132_untainted_attribute_decision_clean(self):
        # a non-clock attribute deciding next to measurement-only clock
        # reads: the hop must not taint by mere co-residence
        src = (
            "import time\n"
            "class PaceLaw:\n"
            "    def arm(self, budget):\n"
            "        self._budget = float(budget)\n"
            "    def decide(self, obs):\n"
            "        t0 = time.time()\n"
            "        out = self._law(obs)\n"
            "        self.mon.observe(time.time() - t0)\n"
            "        if self._budget > 1.0:\n"
            "            return out\n"
            "        return None\n")
        assert [f.code for f in lint_source(src, path=self.STEER)
                if f.code == "FL132"] == []

    # -- FL135 cross-function manifest tracking ---------------------------
    def _cross_modules(self, tmp_path, dump_line):
        (tmp_path / "status_manifest.py").write_text(
            "def make_manifest(rounds):\n"
            "    return {'schema': 1, 'rounds': rounds}\n")
        (tmp_path / "writer.py").write_text(
            "import json\n"
            "from status_manifest import make_manifest\n"
            "def write(path, rounds):\n"
            "    manifest = make_manifest(rounds)\n"
            "    with open(path, 'w') as f:\n"
            f"        {dump_line}\n")
        return [f for f in lint_paths([str(tmp_path)])
                if f.code == "FL135"]

    def test_fl135_cross_module_producer_payload_flagged(self, tmp_path):
        # the dump site sits in an UNSCOPED module, but its payload is
        # the dict built by a scoped manifest producer: the record stays
        # a manifest wherever it is written
        found = self._cross_modules(tmp_path,
                                    "json.dump(manifest, f, indent=2)")
        assert len(found) == 1
        assert "make_manifest" in found[0].message
        assert "sort_keys" in found[0].message

    def test_fl135_cross_module_sorted_payload_clean(self, tmp_path):
        found = self._cross_modules(
            tmp_path, "json.dump(manifest, f, sort_keys=True)")
        assert found == []

    def test_fl135_cross_module_non_producer_payload_clean(self, tmp_path):
        # unscoped module dumping its own local dict: out of scope, and
        # the cross tracker must not over-reach past producer payloads
        (tmp_path / "notes.py").write_text(
            "import json\n"
            "def debug(obj):\n"
            "    return json.dumps({'obj': repr(obj)})\n")
        assert [f for f in lint_paths([str(tmp_path)])
                if f.code == "FL135"] == []

    # -- mutation-acceptance fixtures: each reverted historical fix (or
    # -- planted hazard) yields exactly one finding of exactly its rule
    def _real(self, rel):
        with open(os.path.join(REPO_ROOT, rel), encoding="utf-8") as fh:
            return fh.read()

    def test_mutation_fl131_aggregate_reports_arrival_order(self):
        # THE historical bug (PR 9, third review pass): the guard total
        # summed in dict arrival order instead of sorted(reports)
        rel = "fedml_tpu/program/aggregation.py"
        src = self._real(rel)
        fixed = ("float(sum(float(reports[r][0]) "
                 "for r in sorted(reports)))")
        assert fixed in src, "aggregate_reports guard-total shape changed"
        mutated = src.replace(
            fixed, "float(sum(float(v[0]) for v in reports.values()))")
        assert [f.code for f in lint_source(src, path=rel,
                                            select={"FL131"})] == []
        found = lint_source(mutated, path=rel, select={"FL131"})
        assert [f.code for f in found] == ["FL131"]

    def test_mutation_fl132_steering_decides_on_wall_clock(self):
        # the steering law's contract is wall-clock-free replay; moving
        # a decision onto time.time() is exactly one FL132
        rel = "fedml_tpu/resilience/steering.py"
        src = self._real(rel)
        anchor = ("obs = dict(obs or {})\n"
                  "        p90 = obs.get(\"latency_p90\")")
        assert anchor in src, "PaceController.decide head changed"
        mutated = src.replace(anchor, (
            "import time\n"
            "        obs = dict(obs or {})\n"
            "        if time.time() - self._wall_anchor > 30.0:\n"
            "            outcome = \"abandoned\"\n"
            "        p90 = obs.get(\"latency_p90\")"))
        assert [f.code for f in lint_source(src, path=rel,
                                            select={"FL132"})] == []
        found = lint_source(mutated, path=rel, select={"FL132"})
        assert [f.code for f in found] == ["FL132"]

    def test_mutation_fl133_cohort_loses_its_reseed(self):
        # deleting the derived reseed before the cohort draw makes the
        # global np.random stream's arrival-order state pick the cohort
        rel = "fedml_tpu/program/cohort.py"
        src = self._real(rel)
        seed_line = "    np.random.seed(attempt_seed(round_idx, attempt))\n"
        assert src.count(seed_line) >= 1, "cohort reseed idiom changed"
        mutated = src.replace(seed_line, "", 1)
        assert [f.code for f in lint_source(src, path=rel,
                                            select={"FL133"})] == []
        found = lint_source(mutated, path=rel, select={"FL133"})
        assert [f.code for f in found] == ["FL133"]

    def test_mutation_fl134_async_handler_inline_fold(self):
        # planting an inline float accumulation on the async server's
        # report handler (beside the BufferedAggregator fold the fix
        # installed) is exactly one FL134
        rel = "fedml_tpu/resilience/async_agg.py"
        src = self._real(rel)
        anchor = "            depth = self.agg.fold(rank,"
        assert anchor in src, "_on_report fold shape changed"
        mutated = src.replace(anchor, (
            "            self._mean_acc += "
            "float(msg.get(\"num_samples\"))\n" + anchor))
        assert [f.code for f in lint_source(src, path=rel,
                                            select={"FL134"})] == []
        found = lint_source(mutated, path=rel, select={"FL134"})
        assert [f.code for f in found] == ["FL134"]

    def test_mutation_fl135_status_writer_loses_sort_keys(self):
        # StatusWriter.update is the FL135-clean reference; dropping its
        # sort_keys is exactly one FL135
        rel = "fedml_tpu/observability/perfmon.py"
        src = self._real(rel)
        fixed = "json.dump(snapshot, f, indent=2, sort_keys=True,"
        assert fixed in src, "StatusWriter.update shape changed"
        mutated = src.replace(fixed, "json.dump(snapshot, f, indent=2,")
        assert [f.code for f in lint_source(src, path=rel,
                                            select={"FL135"})] == []
        found = lint_source(mutated, path=rel, select={"FL135"})
        assert [f.code for f in found] == ["FL135"]

    def test_determinism_pass_zero_on_critical_packages(self, monkeypatch):
        # the zero-baseline acceptance, scoped to the determinism-
        # critical packages (the full-tree zero is ci.sh's gate)
        monkeypatch.chdir(REPO_ROOT)
        found = lint_paths(
            ["fedml_tpu/program", "fedml_tpu/resilience",
             "fedml_tpu/observability", "fedml_tpu/utils",
             "fedml_tpu/compression"],
            select={"FL131", "FL132", "FL133", "FL134", "FL135"})
        assert [f.code for f in found] == []

    def test_rules_catalog_and_sarif_tags(self):
        for code in ("FL131", "FL132", "FL133", "FL134", "FL135"):
            assert code in RULES
            assert rule_tags(code) == ["fedcheck-determinism"]
        assert rule_tags("FL136") == ["fedcheck-concurrency"]


class TestEventLoopWritePath:
    """FL136: FL129's write-path complement -- busy loops and unbounded
    buffer growth in selector/loop callbacks."""

    def _loop(self, body):
        return (
            "import selectors\n"
            "class Loop:\n"
            "    def start(self):\n"
            "        self._sel.register(self._wake, selectors.EVENT_READ,\n"
            "                           (self._on_event, None))\n"
            + body)

    def test_fl136_busy_flag_poll_flagged(self):
        src = self._loop(
            "    def _on_event(self, conn, mask):\n"
            "        while not self._ready:\n"
            "            pass\n")
        found = [f for f in lint_source(src, path=LIB_PATH)
                 if f.code == "FL136"]
        assert len(found) == 1
        assert "busy loop" in found[0].message

    def test_fl136_drain_loop_clean(self):
        # a call in the TEST is progress: the canonical wake-pipe drain
        src = self._loop(
            "    def _on_event(self, conn, mask):\n"
            "        while self._wake.recv_into(self._buf):\n"
            "            pass\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL136"] == []

    def test_fl136_local_progress_loop_clean(self):
        # a name in the test assigned in the body: bounded local loop
        src = self._loop(
            "    def _on_event(self, conn, mask):\n"
            "        i = 0\n"
            "        while i < 4:\n"
            "            i += 1\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL136"] == []

    def test_fl136_unbounded_growth_flagged(self):
        src = self._loop(
            "    def _on_event(self, conn, mask):\n"
            "        conn.rx.extend(conn.sock.recv(4096))\n")
        found = [f for f in lint_source(src, path=LIB_PATH)
                 if f.code == "FL136"]
        assert len(found) == 1
        assert "watermark" in found[0].message

    def test_fl136_watermarked_growth_clean(self):
        # the eventloop transport's reference shape: growth paired with
        # a byte-counter watermark compare (tx / tx_bytes name-prefix)
        src = self._loop(
            "    def _on_event(self, conn, mask):\n"
            "        conn.tx.extend(frame)\n"
            "        conn.tx_bytes += len(frame)\n"
            "        if conn.tx_bytes > self.high_watermark:\n"
            "            self._congest(conn)\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL136"] == []

    def test_fl136_outside_callback_clean(self):
        # the same growth off the loop-callback reach is the sender
        # threads' business (and the class-local lock rules')
        src = (
            "class Buffered:\n"
            "    def enqueue(self, conn, frame):\n"
            "        conn.rx.extend(frame)\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL136"] == []

    def test_eventloop_transport_stays_clean(self):
        path = os.path.join(REPO_ROOT, "fedml_tpu/net/eventloop.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        assert [f.code for f in lint_source(
            src, path="fedml_tpu/net/eventloop.py",
            select={"FL136"})] == []


class TestModuleFunctionCallgraph:
    """The cross-class callgraph enters module-level function bodies (a
    former 'Future rules' soundness limit): bare-name calls resolve
    through the synthetic <module> scope and one import hop."""

    def test_blocking_chain_through_module_function(self):
        src = (
            "from fedml_tpu.core.locks import audited_lock\n"
            "class Server:\n"
            "    def __init__(self):\n"
            "        self._lock = audited_lock()\n"
            "    def on_report(self, msg):\n"
            "        with self._lock:\n"
            "            retry_send(self.sock, msg)\n"
            "def retry_send(sock, msg):\n"
            "    sock.sendall(msg)\n")
        found = [f for f in lint_source(src, path=LIB_PATH)
                 if f.code == "FL126"]
        assert len(found) == 1
        assert "`retry_send()`" in found[0].message
        assert "<module>" in found[0].message

    def test_call_outside_lock_clean(self):
        src = (
            "from fedml_tpu.core.locks import audited_lock\n"
            "class Server:\n"
            "    def __init__(self):\n"
            "        self._lock = audited_lock()\n"
            "    def on_report(self, msg):\n"
            "        with self._lock:\n"
            "            pass\n"
            "        retry_send(self.sock, msg)\n"
            "def retry_send(sock, msg):\n"
            "    sock.sendall(msg)\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL126"] == []

    def test_import_hop_resolution(self, tmp_path):
        # the helper lives one ImportFrom away: project-wide lint
        # resolves the bare-name call across the module boundary
        pkg = tmp_path / "fedml_tpu"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "retry.py").write_text(
            "def retry_send(sock, msg):\n"
            "    sock.sendall(msg)\n")
        (pkg / "server.py").write_text(
            "from fedml_tpu.core.locks import audited_lock\n"
            "from fedml_tpu.retry import retry_send\n"
            "class Server:\n"
            "    def __init__(self):\n"
            "        self._lock = audited_lock()\n"
            "    def on_report(self, msg):\n"
            "        with self._lock:\n"
            "            retry_send(self.sock, msg)\n")
        found = [f for f in lint_paths([str(pkg)]) if f.code == "FL126"]
        assert len(found) == 1
        assert "`retry_send()`" in found[0].message

    def test_str_join_is_not_a_thread_join(self):
        # the guard the module-function walk made necessary: formatting
        # helpers full of '","\.join(...)' are not blocking
        src = (
            "from fedml_tpu.core.locks import audited_lock\n"
            "class Registry:\n"
            "    def __init__(self):\n"
            "        self._lock = audited_lock()\n"
            "    def render(self):\n"
            "        with self._lock:\n"
            "            return fmt_labels(self._items)\n"
            "def fmt_labels(items):\n"
            "    return ','.join(str(i) for i in items)\n")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL126"] == []


class TestNonSelfReceiverFlow:
    """Container-element typing through non-self receivers: a
    ctor-typed LOCAL (`comm = TcpCommManager(...)`) carries class
    identity, so `comm.add_observer(server)` in a module-level driver
    closes the last untyped observer hop."""

    DRIVER = (
        "from fedml_tpu.core.locks import audited_lock\n"
        "class Fsm:\n"
        "    def receive_message(self, t, msg):\n"
        "        self.sock.sendall(msg)\n"
        "class Transport:\n"
        "    def __init__(self):\n"
        "        self._lock = audited_lock()\n"
        "        self._observers = []\n"
        "    def add_observer(self, obs):\n"
        "        self._observers.append(obs)\n"
        "    def dispatch(self, msg):\n"
        "        with self._lock:\n"
        "            for obs in list(self._observers):\n"
        "                obs.receive_message('sync', msg)\n"
        "def driver():\n"
        "    t = Transport()\n"
        "    fsm = Fsm()\n"
        "    t.add_observer(fsm)\n")

    def test_typed_local_receiver_flows_elements(self):
        # without the localcls flow the observer list is untyped and
        # the dispatch-under-lock chain is invisible; with it, the
        # chain reaches Fsm.receive_message's blocking sendall
        found = [f for f in lint_source(self.DRIVER, path=LIB_PATH)
                 if f.code == "FL126"]
        assert len(found) == 1
        assert "element of `self._observers`" in found[0].message
        assert "Fsm" in found[0].message

    def test_without_registration_clean(self):
        src = self.DRIVER.replace("    t.add_observer(fsm)\n", "")
        assert [f.code for f in lint_source(src, path=LIB_PATH)
                if f.code == "FL126"] == []

    def test_index_introspection_typed_local(self):
        # the flow itself, independent of any finding: the driver's
        # add_observer call lands Fsm on Transport._observers
        from fedml_tpu.analysis.crossclass import CrossClassIndex
        import ast as ast_mod
        idx = CrossClassIndex()
        idx.add_module(LIB_PATH, ast_mod.parse(self.DRIVER))
        idx.finalize()
        mod = CrossClassIndex.module_name(LIB_PATH)
        transport = idx.modules[mod]["classes"]["Transport"]
        elems = idx.container_elem_types(transport, "_observers")
        assert ("cls", (mod, "Fsm")) in elems


class TestModelCheck:
    """FL140-FL143: the fedmc bounded model checking pass.

    Fixtures compose a minimal server x 2 clients protocol; each rule's
    positive mutation is judged in isolation via ``select`` (the
    temporal rules deliberately co-fire with their rule-based twins on
    shared seeds)."""

    FSM_PATH = "fedml_tpu/core/fsm_fake.py"

    BASE = (
        "import logging\n"
        "from fedml_tpu.core.managers import ClientManager, ServerManager\n"
        "from fedml_tpu.core.comm.base import MSG_TYPE_PEER_LOST\n"
        "from fedml_tpu.core.message import Message\n"
        "MSG_SYNC = 'sync'\n"
        "MSG_REPORT = 'report'\n"
        "class Srv(ServerManager):\n"
        "    def register_message_receive_handlers(self):\n"
        "        self.register_message_receive_handler(MSG_REPORT,\n"
        "                                              self._on_report)\n"
        "        self.register_message_receive_handler(MSG_TYPE_PEER_LOST,\n"
        "                                              self._on_lost)\n"
        "    def open_round(self):\n"
        "        self.send_message(Message(MSG_SYNC, 0, 1))\n"
        "    def _on_report(self, msg):\n"
        "        logging.debug('report from %s', msg.get_sender_id())\n"
        "        self.folded.add(msg.get_sender_id())\n"
        "    def _on_lost(self, msg):\n"
        "        logging.warning('rank %s lost', msg.get_sender_id())\n"
        "        self.cohort.discard(msg.get_sender_id())\n"
        "class Cli(ClientManager):\n"
        "    def register_message_receive_handlers(self):\n"
        "        self.register_message_receive_handler(MSG_SYNC,\n"
        "                                              self._on_sync)\n"
        "        self.register_message_receive_handler(MSG_TYPE_PEER_LOST,\n"
        "                                              self._on_cli_lost)\n"
        "    def _on_sync(self, msg):\n"
        "        self.send_message(Message(MSG_REPORT, 1, 0))\n"
        "    def _on_cli_lost(self, msg):\n"
        "        self.finish()\n")

    def _select(self, src, code):
        return lint_source(src, path=self.FSM_PATH, select={code})

    def test_base_protocol_verifies_clean(self):
        # liveness + safety both hold on the healthy composition
        assert codes(self.BASE, path=self.FSM_PATH) == []

    # FL140 ---------------------------------------------------------------
    def test_fl140_inert_peer_lost_handler_wedges_round(self):
        # the peer-lost policy is log-and-ignore and there is no deadline
        # machinery: killing one client leaves the round waiting on a
        # report that can never come -- a reachable deadlock
        src = self.BASE.replace(
            "        logging.warning('rank %s lost', msg.get_sender_id())\n"
            "        self.cohort.discard(msg.get_sender_id())\n",
            "        logging.warning('rank %s lost', msg.get_sender_id())\n")
        found = self._select(src, "FL140")
        assert [f.code for f in found] == ["FL140"]
        assert "kill" in found[0].message
        assert "no enabled transition" in found[0].message
        # the fair path still decides: no FL141 on the same seed
        assert self._select(src, "FL141") == []

    def test_fl140_shedding_peer_lost_handler_clean(self):
        assert self._select(self.BASE, "FL140") == []

    # FL141 ---------------------------------------------------------------
    def test_fl141_unfolded_report_hangs_fair_path(self):
        # the server's report handler goes log-only: every frame is
        # delivered, nothing advances -- round 0 never decides
        src = self.BASE.replace(
            "        logging.debug('report from %s', msg.get_sender_id())\n"
            "        self.folded.add(msg.get_sender_id())\n",
            "        logging.debug('report from %s', msg.get_sender_id())\n")
        found = self._select(src, "FL141")
        assert [f.code for f in found] == ["FL141"]
        assert "round 0" in found[0].message
        assert "fault-free" in found[0].message

    def test_fl141_replying_protocol_clean(self):
        assert self._select(self.BASE, "FL141") == []

    # FL142 ---------------------------------------------------------------
    def test_fl142_inert_drive_handler_flagged(self):
        # type-level pairing is clean (the class does send MSG_REPORT,
        # from late_report) but the REGISTERED sync handler is inert:
        # the delivery is consumed in-state without progress
        src = self.BASE.replace(
            "    def _on_sync(self, msg):\n"
            "        self.send_message(Message(MSG_REPORT, 1, 0))\n",
            "    def _on_sync(self, msg):\n"
            "        logging.debug('sync seen (round %s)',\n"
            "                      msg.get('round'))\n"
            "    def late_report(self):\n"
            "        self.send_message(Message(MSG_REPORT, 1, 0))\n")
        found = self._select(src, "FL142")
        assert len(found) == 1
        assert "`Cli._on_sync`" in found[0].message
        assert "'sync'" in found[0].message or "sync" in found[0].message

    def test_fl142_delegating_handler_clean(self):
        # delegation through own state (self.trainer.step) is progress
        src = self.BASE.replace(
            "    def _on_sync(self, msg):\n"
            "        self.send_message(Message(MSG_REPORT, 1, 0))\n",
            "    def _on_sync(self, msg):\n"
            "        self.trainer.step(msg.get('params'))\n"
            "        self.send_message(Message(MSG_REPORT, 1, 0))\n")
        assert src != self.BASE
        assert self._select(src, "FL142") == []

    # FL143 ---------------------------------------------------------------
    JOIN_IMPORT = ("from fedml_tpu.core.comm.base import "
                   "MSG_TYPE_PEER_LOST\n")
    JOIN_BOTH = ("from fedml_tpu.core.comm.base import (MSG_TYPE_PEER_JOIN,\n"
                 "                                      MSG_TYPE_PEER_LOST)\n")

    def test_fl143_missing_join_handler_strands_rejoiner(self):
        # the module speaks the rejoin vocabulary but the server never
        # registers PEER_JOIN: a shed rank that dials back in stays
        # outside every future cohort
        src = self.BASE.replace(self.JOIN_IMPORT, self.JOIN_BOTH)
        found = self._select(src, "FL143")
        assert [f.code for f in found] == ["FL143"]
        assert "PEER_JOIN" in found[0].message
        assert "stranded" in found[0].message

    def test_fl143_readmitting_join_handler_clean(self):
        src = self.BASE.replace(self.JOIN_IMPORT, self.JOIN_BOTH).replace(
            "        self.register_message_receive_handler(MSG_TYPE_PEER_LOST,\n"
            "                                              self._on_lost)\n"
            "    def open_round(self):\n",
            "        self.register_message_receive_handler(MSG_TYPE_PEER_LOST,\n"
            "                                              self._on_lost)\n"
            "        self.register_message_receive_handler(MSG_TYPE_PEER_JOIN,\n"
            "                                              self._on_join)\n"
            "    def _on_join(self, msg):\n"
            "        logging.warning('rank %s rejoined', msg.get_sender_id())\n"
            "        self.cohort.add(msg.get_sender_id())\n"
            "    def open_round(self):\n")
        assert self._select(src, "FL143") == []

    # -- the ISSUE's temporal acceptance fixture --------------------------
    def test_acceptance_fl141_deleted_report_registration_names_round(self):
        # the temporal twin of the FL120 revert fixture: deleting the
        # MSG_C2S_REPORT registration must yield exactly one FL141 whose
        # trace names the hung round and the delivery nobody folds
        rel = "fedml_tpu/resilience/integration.py"
        with open(os.path.join(REPO_ROOT, rel), encoding="utf-8") as fh:
            src = fh.read()
        needle = ("        self.register_message_receive_handler("
                  "MSG_C2S_REPORT,\n"
                  "                                              "
                  "self._on_report)\n")
        assert needle in src, "integration.py registration shape changed"
        assert lint_source(src, path=rel, select={"FL141"}) == []
        found = lint_source(src.replace(needle, ""), path=rel,
                            select={"FL141"})
        assert [f.code for f in found] == ["FL141"]
        assert "round 0" in found[0].message
        assert "res_report" in found[0].message

    # -- two-tier fan-in composition (net/fanin.py) -----------------------
    def _two_tier_index(self):
        import ast as ast_mod
        from fedml_tpu.analysis.protocol import ProtocolIndex
        index = ProtocolIndex()
        for rel in ("fedml_tpu/net/fanin.py",
                    "fedml_tpu/resilience/async_agg.py",
                    "fedml_tpu/resilience/integration.py",
                    "fedml_tpu/resilience/policy.py"):
            with open(os.path.join(REPO_ROOT, rel),
                      encoding="utf-8") as fh:
                index.add_module(rel, ast_mod.parse(fh.read()))
        return index

    def test_two_tier_healthy_topology_verifies_clean(self):
        from fedml_tpu.analysis.modelcheck import verify_two_tier
        out = verify_two_tier(self._two_tier_index(),
                              coordinator="AsyncBufferedFedAvgServer")
        assert out["decided"]
        assert [c.code for c in out["findings"]] == []
        assert out["relay"] == "_EdgeDownlink"

    def test_two_tier_below_quorum_edge_fl141_clean(self):
        # pre-seed edge 0's whole leaf star dead: the edge round resolves
        # abandoned and forwards NOTHING -- the coordinator's flush
        # deadline / staleness machinery must absorb the hole (the
        # behavior the multi-tier arc relies on)
        from fedml_tpu.analysis.modelcheck import verify_two_tier
        out = verify_two_tier(self._two_tier_index(),
                              coordinator="AsyncBufferedFedAvgServer",
                              lost_leaves=(100, 101))
        assert out["decided"]
        assert [c.code for c in out["findings"]
                if c.code == "FL141"] == []
        assert [c.code for c in out["findings"]] == []

    # -- three-tier edges-of-edges (topology/'s process tree) -------------
    def test_three_tier_healthy_topology_verifies_clean(self):
        # the relay stacked under itself: coordinator <- 2 edges <- 2
        # sub-edges each <- leaves, fair + drops-only faulted runs
        from fedml_tpu.analysis.modelcheck import verify_three_tier
        out = verify_three_tier(self._two_tier_index(),
                                coordinator="AsyncBufferedFedAvgServer")
        assert out["decided"]
        assert [c.code for c in out["findings"]] == []
        assert out["relay"] == "_EdgeDownlink"

    def test_three_tier_lost_leaf_abandon_cascade_clean(self):
        # pre-seed sub-edge (0,1)'s only leaf dead: that tier-2 edge
        # abandons and forwards nothing, its tier-1 parent's deadline
        # absorbs the hole one tier up, the coordinator's one tier
        # above that -- the cascade must still decide round 0
        from fedml_tpu.analysis.modelcheck import verify_three_tier
        out = verify_three_tier(self._two_tier_index(),
                                coordinator="AsyncBufferedFedAvgServer",
                                lost_leaves=(10100,), fair_only=True)
        assert out["decided"]
        assert [c.code for c in out["findings"]] == []

    def test_acceptance_fl141_deleted_edge_report_registration(self):
        # the ISSUE's revert fixture for the deeper tree: deleting the
        # edge downlink's MSG_C2S_REPORT registration must yield
        # exactly one FL141 naming the hung round and the report frame
        # nobody folds (the per-site dedup collapses the per-client
        # compositions onto the one defect)
        import ast as ast_mod
        from fedml_tpu.analysis.modelcheck import check_model
        from fedml_tpu.analysis.protocol import ProtocolIndex
        rel = "fedml_tpu/net/fanin.py"
        with open(os.path.join(REPO_ROOT, rel), encoding="utf-8") as fh:
            src = fh.read()
        needle = ("        self.register_message_receive_handler("
                  "MSG_C2S_REPORT,\n"
                  "                                              "
                  "self._on_report)\n")
        assert needle in src, "fanin.py registration shape changed"

        def run(fanin_src):
            index = ProtocolIndex()
            index.add_module(rel, ast_mod.parse(fanin_src))
            for other in ("fedml_tpu/resilience/async_agg.py",
                          "fedml_tpu/resilience/integration.py",
                          "fedml_tpu/resilience/policy.py"):
                with open(os.path.join(REPO_ROOT, other),
                          encoding="utf-8") as fh:
                    index.add_module(other, ast_mod.parse(fh.read()))
            out = []
            check_model(index,
                        lambda m, n, c, msg: out.append((c, msg)))
            return out

        assert run(src) == []
        found = run(src.replace(needle, ""))
        assert [c for c, _m in found] == ["FL141"]
        assert "round 0" in found[0][1]
        assert "res_report" in found[0][1]

    def test_real_topologies_verify_clean(self):
        # composed sync + async-buffered + two- and three-tier fan-in:
        # the whole resilience/net control plane under the model
        # checker alone
        found = lint_paths(
            [os.path.join(REPO_ROOT, "fedml_tpu/resilience"),
             os.path.join(REPO_ROOT, "fedml_tpu/net")],
            select={"FL140", "FL141", "FL142", "FL143"})
        assert [f.code for f in found] == []

    def test_rules_catalog_and_sarif_tags(self):
        from fedml_tpu.analysis.linter import RULES, rule_tags
        for code in ("FL140", "FL141", "FL142", "FL143"):
            assert code in RULES
            assert rule_tags(code) == ["fedcheck-model"]
