"""The seam for the execution path (``fedml_tpu/parallel/runners.py``):
one contract for the eight modes, one function that chooses.

(a) ``select_runner`` (through ``FedAvgAPI``) returns the runner whose
    ``mode`` the arguments ask for and builds no other;
(b) every refused combination raises its message;
(c) after warming ``runner.programs(...)`` one round misses the compile
    cache for none of the runner's own jitted functions;
(d) the ``local-train`` span's ``mode`` is ``runner.mode``.
"""

import logging
import re
import types

import jax
import jax.numpy as jnp
import pytest

from fedml_tpu import models
from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.algorithms.specs import make_classification_spec
from fedml_tpu.compile import enumerate_round_programs, warmup_programs
from fedml_tpu.data.synthetic import load_synthetic_images
from fedml_tpu.observability import Tracer, set_tracer
from fedml_tpu.parallel import engine, runners
from fedml_tpu.parallel.mesh import make_client_mesh
from fedml_tpu.utils.compile_cache import enable_compilation_cache

#: mode -> the arguments that select it (over the defaults of ``_args``)
MODES = {
    "bucketed": dict(device_resident="0", bucket_edges="geometric"),
    "sharded-lanes": dict(wave_mode=2, mesh=True),
    "mxu-lanes": dict(wave_mode=3),
    "lanes": dict(wave_mode=2),
    "waves": dict(wave_mode=1),
    "flat": dict(wave_mode=0),
    "compressed": dict(compressor="topk:0.25"),
    "packed": dict(device_resident="0"),
}
SINGLE_CHIP = [m for m in MODES if m != "sharded-lanes"]

#: the classes a path is built from: exactly one is constructed
PATH_CLASSES = (engine.BucketedStreamRunner, engine.WaveRunner,
                engine.LaneRunner, engine.ShardedLaneRunner,
                runners.FlatRounds, runners.PackedRunner)
BUILT_FOR = {
    "bucketed": engine.BucketedStreamRunner,
    "sharded-lanes": engine.ShardedLaneRunner,
    "mxu-lanes": engine.LaneRunner, "lanes": engine.LaneRunner,
    "waves": engine.WaveRunner, "flat": runners.FlatRounds,
    "compressed": runners.PackedRunner, "packed": runners.PackedRunner,
}


@pytest.fixture(scope="module")
def dataset():
    return load_synthetic_images(client_num=8, n_train=128, n_test=32,
                                 image_size=8, partition="hetero",
                                 partition_alpha=0.5, seed=0)


def _spec(lane_packed=False):
    # the MXU-packed lanes need a family with a lane-packed lowering
    model = (models.CifarResNet(depth=8, num_classes=10) if lane_packed
             else models.LogisticRegression(num_classes=10,
                                            apply_sigmoid=False))
    return make_classification_spec(model, jnp.zeros((1, 8, 8, 3)))


def _api(dataset, mesh=False, spec=None, **kw):
    base = dict(client_num_in_total=8, client_num_per_round=8,
                comm_round=10 ** 9, epochs=1, batch_size=8, lr=0.05,
                wd=0.0, client_optimizer="sgd",
                frequency_of_the_test=10 ** 9, seed=0, client_chunk=2,
                wave_mode=1, device_resident="auto",
                device_data_cap_gb=2.0)
    base.update(kw)
    return FedAvgAPI(
        dataset, spec or _spec(lane_packed=base["wave_mode"] == 3),
        types.SimpleNamespace(**base),
        mesh=make_client_mesh(4) if mesh else None)


@pytest.mark.parametrize("mode", MODES)
def test_selects_the_mode_asked_for_and_builds_no_other(
        dataset, mode, monkeypatch):
    built = []
    for cls in PATH_CLASSES:
        def counting(self, *a, _init=cls.__init__, _cls=cls, **k):
            built.append(_cls)
            _init(self, *a, **k)
        monkeypatch.setattr(cls, "__init__", counting)
    api = _api(dataset, **MODES[mode])
    assert api.runner.mode == mode
    assert built == [BUILT_FOR[mode]]
    for member in ("run_round", "programs"):
        assert callable(getattr(api.runner, member))


@pytest.mark.parametrize("kw, message", [
    (dict(wave_mode=2, device_resident="0"),
     "--wave_mode 2 runs lanes over device-resident data, which "
     "--device_resident 0 bypasses; drop one of the two (--wave_mode 1 is "
     "the default)"),
    (dict(wave_mode=3, device_resident="0"),
     "--wave_mode 3 runs lanes over device-resident data, which "
     "--device_resident 0 bypasses; drop one of the two (--wave_mode 1 is "
     "the default)"),
    (dict(wave_mode=2, compressor="topk:0.25"),
     "--wave_mode 2 runs lanes over device-resident data, which "
     "--compressor bypasses; drop one of the two (--wave_mode 1 is the "
     "default)"),
    (dict(wave_mode=3, bucket_edges="geometric"),
     "--wave_mode 3 runs lanes over device-resident data, which "
     "--bucket_edges/--async_agg bypasses; drop one of the two "
     "(--wave_mode 1 is the default)"),
    (dict(wave_mode=3, spec=_spec()),
     "--wave_mode 3 (MXU-packed lanes) needs a model family with a "
     "lane-packed lowering (models/lane_packed.py); spec 'classification' "
     "has none -- use --wave_mode 2 for the generic vmap lanes"),
    (dict(bucket_edges="geometric", mesh=True),
     "--bucket_edges/--async_agg run the single-chip bucketed streaming "
     "path; it does not compose with --mesh (the sharded-lane path owns "
     "multi-chip)"),
    (dict(wave_mode=2, device_data_cap_gb=1e-6),
     "--wave_mode 2 runs lanes over device-resident data, but the stacked "
     "client shards need 0.00 GB and --device_data_cap_gb is 1e-06; raise "
     "the cap or use --wave_mode 1"),
    (dict(compressor="topk:0.25", mesh=True),
     "compressor= applies to the single-chip simulation and the "
     "distributed control-plane paths; mesh rounds aggregate over ICI "
     "collectives, where the wire bottleneck being compressed does not "
     "exist"),
], ids=["lanes-resident0", "mxu-resident0", "lanes-compressor",
        "mxu-buckets", "mxu-no-lowering", "buckets-mesh", "lanes-over-cap",
        "compressor-mesh"])
def test_refused_combinations_raise_their_message(dataset, kw, message):
    with pytest.raises(ValueError) as err:
        _api(dataset, **kw)
    assert str(err.value) == message


def _own_programs(obj):
    """``jit_<name>`` of every jitted function a runner holds (through the
    parts under ``fedml_tpu.parallel`` it is composed of): found by
    looking, not by asking ``programs()``."""
    names = set()
    for v in vars(obj).values():
        if callable(v) and hasattr(v, "lower"):
            names.add("jit_" + v.__name__)
        elif type(v).__module__.startswith("fedml_tpu.parallel"):
            names |= _own_programs(v)
    return names


def _round_cache_misses(api):
    """Module names of the persistent-cache misses of one round."""
    missed = []

    class Misses(logging.Handler):
        def emit(self, record):
            m = re.match(r"PERSISTENT COMPILATION CACHE MISS for '([^']+)'",
                         record.getMessage())
            if m:
                missed.append(m.group(1))

    handler, logger = Misses(), logging.getLogger("jax._src.compiler")
    level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.DEBUG)
    try:
        api.train_one_round()
    finally:
        logger.removeHandler(handler)
        logger.setLevel(level)
    return set(missed)


@pytest.mark.parametrize("mode", SINGLE_CHIP)
def test_a_round_after_warmup_compiles_none_of_the_runners_programs(
        dataset, mode, tmp_path, restore_cache_config):
    # warmed means: whatever of its own the round asks the compiler for is
    # in the cache (jax's in-memory one or the persistent one), so none
    # of it is a persistent-cache MISS, which is a compile. The eager
    # helpers of a round (key splits, gathers of a row) are jax's,
    # compile in milliseconds and are nobody's to list
    enable_compilation_cache(str(tmp_path), min_compile_time_secs=0.0)
    api = _api(dataset, **MODES[mode])
    own = _own_programs(api.runner)
    listed = enumerate_round_programs(api)
    assert {"jit_" + p.fn.__name__ for p in listed} >= own, (
        "programs() leaves out a jitted function the runner holds")
    warmup_programs(listed)
    cold = own & _round_cache_misses(api)
    assert not cold, f"cold after warm-up: {sorted(cold)}"


def test_without_warmup_the_same_round_compiles_them(
        dataset, tmp_path, restore_cache_config):
    """The control of the test above: the same look at the same round
    sees every program of the stream when nothing was warmed."""
    enable_compilation_cache(str(tmp_path), min_compile_time_secs=0.0)
    api = _api(dataset, **MODES["bucketed"])
    assert _round_cache_misses(api) >= {
        "jit_chunk_fn", "jit_fold_first", "jit_fold_next",
        "jit_fold_quotient", "jit_advance_fn"} == _own_programs(api.runner)


@pytest.mark.parametrize("mode", MODES)
def test_local_train_span_carries_the_runners_mode(dataset, mode):
    api = _api(dataset, **MODES[mode])
    tracer = Tracer()
    prev = set_tracer(tracer)
    try:
        api.train_one_round()
    finally:
        set_tracer(prev)
    span, = [s for s in tracer.finished_spans() if s.name == "local-train"]
    assert span.attrs["mode"] == api.runner.mode == mode
    assert all(jnp.isfinite(x).all()
               for x in jax.tree.leaves(api.global_state))
