"""fedml_tpu.compression: binary wire codec + client-update compressors.

Tier-1 (fast, CPU): codec roundtrips for every wire dtype including
bfloat16 and bit-packed bools; compressor exactness/bounds (exact for
``none``/``topk`` kept entries, bounded error for ``qsgd``); the
error-feedback residual identity; a compressed-FedAvg convergence smoke
against uncompressed; and transport roundtrips (local serialize + a real
TCP FedAvg protocol round) asserting binary frames beat the legacy
JSON-list codec by the acceptance margin (>=8x for qsgd on a CNN-sized
pytree) with the traffic logged through ``MetricsLogger``.
"""

import json
import socket
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.compression import (ErrorFeedback, decode_array, decode_tree,
                                   encode_array, encode_tree, get_compressor,
                                   message_from_wire, message_to_wire,
                                   tree_wire_nbytes)
from fedml_tpu.compression.compressors import (NoneCompressor,
                                               QSGDCompressor,
                                               SignSGDCompressor,
                                               TopKCompressor)
from fedml_tpu.core.message import Message, params_to_lists


def _cnn_sized_params(rng_seed=0):
    """CNNOriginalFedAvg-shaped conv/fc kernels (~430k params): big enough
    that codec ratios are dominated by payload, small enough for tier-1."""
    rng = np.random.default_rng(rng_seed)
    shapes = {"conv1": {"kernel": (5, 5, 1, 32), "bias": (32,)},
              "conv2": {"kernel": (5, 5, 32, 64), "bias": (64,)},
              "fc1": {"kernel": (1024, 384), "bias": (384,)},
              "fc2": {"kernel": (384, 10), "bias": (10,)}}
    return jax.tree.map(
        lambda s: rng.normal(0, 0.1, s).astype(np.float32), shapes,
        is_leaf=lambda x: isinstance(x, tuple))


class TestCodec:
    @pytest.mark.parametrize("dtype", [
        "float32", "float64", "float16", "bfloat16", "int8", "uint8",
        "int32", "int64", "bool"])
    def test_array_roundtrip_all_dtypes(self, dtype):
        rng = np.random.default_rng(0)
        if dtype == "bool":
            arr = rng.random((3, 7, 5)) > 0.5
        elif dtype == "bfloat16":
            import ml_dtypes
            arr = rng.normal(size=(4, 9)).astype(ml_dtypes.bfloat16)
        elif np.issubdtype(np.dtype(dtype), np.floating):
            arr = rng.normal(size=(4, 9)).astype(dtype)
        else:
            arr = rng.integers(0, 100, (4, 9)).astype(dtype)
        out, off = decode_array(encode_array(arr))
        assert off == len(encode_array(arr))
        assert out.dtype == arr.dtype
        np.testing.assert_array_equal(out, arr)

    def test_zero_dim_and_empty(self):
        for arr in (np.float32(3.5).reshape(()), np.zeros((0,), np.int32),
                    np.zeros((2, 0, 3), np.float32)):
            out, _ = decode_array(encode_array(arr))
            assert out.shape == arr.shape and out.dtype == arr.dtype
            np.testing.assert_array_equal(out, arr)

    def test_bool_bitpacking_on_wire(self):
        # 1 bit/element: 8000 bools must frame in ~1000 payload bytes
        arr = np.ones(8000, np.bool_)
        assert len(encode_array(arr)) < 1100
        out, _ = decode_array(encode_array(arr))
        np.testing.assert_array_equal(out, arr)

    def test_tree_roundtrip_mixed(self):
        import ml_dtypes
        tree = {"params": {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
                           "b": np.ones(3, ml_dtypes.bfloat16)},
                "mask": np.array([True, False, True]),
                "round": 7, "name": "cohort", "lst": [1, 2.5, "x"]}
        out = decode_tree(encode_tree(tree))
        np.testing.assert_array_equal(out["params"]["w"],
                                      tree["params"]["w"])
        assert out["params"]["b"].dtype == np.dtype(ml_dtypes.bfloat16)
        np.testing.assert_array_equal(out["mask"], tree["mask"])
        assert out["round"] == 7 and out["name"] == "cohort"
        assert out["lst"] == [1, 2.5, "x"]

    def test_tree_wire_nbytes_exact(self):
        tree = {"a": np.zeros((17, 3), np.float32),
                "b": {"c": np.ones(100, np.bool_)}}
        assert tree_wire_nbytes(tree) == len(encode_tree(tree))
        # and from abstract shapes (eval_shape structs have shape/dtype)
        shapes = jax.eval_shape(lambda t: t, tree)
        assert tree_wire_nbytes(shapes) == len(encode_tree(tree))

    def test_version_byte_and_legacy_json_sniff(self):
        m = Message("sync", 0, 1)
        m.add("w", np.arange(4, dtype=np.float32))
        wire = message_to_wire(m)
        assert wire[0] == 0x9E and wire[1] == 1  # magic + version
        back = message_from_wire(wire)
        assert back.get_type() == "sync"
        np.testing.assert_array_equal(back.get("w"),
                                      np.arange(4, dtype=np.float32))
        # legacy all-JSON frames still decode through the same entry point
        legacy = message_from_wire(Message("stop", 2, 0).to_json().encode())
        assert legacy.get_type() == "stop" and legacy.get_sender_id() == 2
        # and a frame claiming an unknown version is rejected, not misread
        with pytest.raises(ValueError):
            decode_tree(bytes([0x9E, 99]) + wire[2:])

    def test_reserved_marker_key_rejected(self):
        m = Message("x", 0, 1)
        m.add("payload", {"__nd__": 3})
        with pytest.raises(ValueError):
            message_to_wire(m)

    def test_binary_beats_json_lists(self):
        params = _cnn_sized_params()
        m = Message("model", 1, 0)
        m.add("params", params)
        json_bytes = len(Message("model", 1, 0).to_json()) + len(
            json.dumps(params_to_lists(params)))
        assert json_bytes >= 5 * len(message_to_wire(m))


class TestCompressors:
    def _params(self):
        rng = np.random.default_rng(1)
        return {"w": jnp.asarray(rng.normal(size=(40, 25)).astype(np.float32)),
                "b": jnp.asarray(rng.normal(size=(25,)).astype(np.float32)),
                "step": jnp.asarray(3, jnp.int32)}

    def test_spec_parsing(self):
        assert get_compressor(None) is None
        assert get_compressor("") is None
        assert isinstance(get_compressor("none"), NoneCompressor)
        assert get_compressor("topk:0.05").ratio == 0.05
        assert get_compressor("qsgd:4").bits == 4
        assert isinstance(get_compressor("signsgd"), SignSGDCompressor)
        c = get_compressor("topk:0.1")
        assert get_compressor(c) is c  # instances pass through
        with pytest.raises(ValueError):
            get_compressor("gzip")
        with pytest.raises(ValueError):
            get_compressor("topk:1.5")
        with pytest.raises(ValueError):
            get_compressor("signsgd:2")

    def test_none_exact(self):
        p = self._params()
        c = NoneCompressor()
        dec = c.decompress(c.compress(p, jax.random.PRNGKey(0)), p)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), dec, p)

    def test_topk_keeps_largest_exactly(self):
        p = self._params()
        c = TopKCompressor(ratio=0.1)
        dec = c.decompress(c.compress(p, jax.random.PRNGKey(0)), p)
        for name in ("w", "b"):
            x = np.asarray(p[name]).reshape(-1)
            y = np.asarray(dec[name]).reshape(-1)
            k = max(1, int(np.ceil(0.1 * x.size)))
            top = np.argsort(np.abs(x))[-k:]
            np.testing.assert_array_equal(y[top], x[top])  # kept: exact
            rest = np.setdiff1d(np.arange(x.size), top)
            np.testing.assert_array_equal(y[rest], 0)  # dropped: zero
        # integer leaves pass through every compressor untouched
        assert int(dec["step"]) == 3

    def test_qsgd_bounded_error_and_int8_storage(self):
        p = self._params()
        c = QSGDCompressor(bits=8)
        enc = c.compress(p, jax.random.PRNGKey(0))
        assert enc["w"]["q"].dtype == jnp.int8
        dec = c.decompress(enc, p)
        for name in ("w", "b"):
            x = np.asarray(p[name])
            scale = float(np.max(np.abs(x)))
            err = np.max(np.abs(np.asarray(dec[name]) - x))
            assert err <= scale / c.levels + 1e-6  # one quantization step

    def test_signsgd_one_bit(self):
        p = self._params()
        c = SignSGDCompressor()
        enc = c.compress(p, jax.random.PRNGKey(0))
        assert enc["w"]["sign"].dtype == jnp.bool_
        dec = c.decompress(enc, p)
        x, y = np.asarray(p["w"]), np.asarray(dec["w"])
        np.testing.assert_array_equal(np.sign(y), np.where(x >= 0, 1, -1))
        assert np.allclose(np.abs(y), np.mean(np.abs(x)))

    def test_randk_unbiased_scaling(self):
        p = {"w": jnp.ones((100,), jnp.float32)}
        c = get_compressor("randk:0.25")
        enc = c.compress(p, jax.random.PRNGKey(0))
        # kept entries carry 1/ratio scaling so E[decode] == input
        np.testing.assert_allclose(np.asarray(enc["w"]["values"]), 4.0)
        assert np.asarray(enc["w"]["indices"]).size == 25

    def test_compress_is_jittable(self):
        p = self._params()
        for spec in ("topk:0.2", "randk:0.2", "qsgd:8", "signsgd"):
            c = get_compressor(spec)
            enc = jax.jit(lambda t, r: c.compress(t, r))(
                p, jax.random.PRNGKey(0))
            dec = jax.jit(lambda e: c.decompress(e, p))(enc)
            assert np.asarray(dec["w"]).shape == (40, 25)

    def test_encoded_tree_survives_wire(self):
        # the full client->server hop: compress -> binary frame -> decode
        # -> decompress reproduces the device-side reconstruction exactly
        p = self._params()
        c = get_compressor("qsgd:8")
        enc = c.compress(p, jax.random.PRNGKey(7))
        direct = c.decompress(enc, p)
        host_enc = jax.tree.map(np.asarray, enc)
        over_wire = c.decompress(decode_tree(encode_tree(host_enc)), p)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), direct, over_wire)

    def test_error_feedback_residual_identity(self):
        p = self._params()
        ef = ErrorFeedback(get_compressor("topk:0.1"))
        res = ef.init(p)
        _, dec, new_res = ef.step(p, res, p, jax.random.PRNGKey(0))
        jax.tree.map(
            lambda x, d, r: np.testing.assert_allclose(
                np.asarray(x) - np.asarray(d), np.asarray(r), atol=1e-6),
            p, dec, new_res)


def _fed_args(**kw):
    base = dict(client_num_per_round=6, comm_round=3, epochs=1,
                batch_size=16, lr=0.3, client_optimizer="sgd", wd=0.0,
                frequency_of_the_test=100, ci=0, seed=0)
    base.update(kw)
    return types.SimpleNamespace(**base)


class TestCompressedFedAvg:
    def _setup(self):
        from fedml_tpu import models
        from fedml_tpu.algorithms.specs import make_classification_spec
        from fedml_tpu.data import load_synthetic_federated
        spec = make_classification_spec(
            models.LogisticRegression(num_classes=10, apply_sigmoid=False),
            jnp.zeros((1, 60)))
        ds = load_synthetic_federated(client_num=6, n_train=600, n_test=150,
                                      alpha=0.0, beta=0.0, seed=0)
        return ds, spec

    def test_none_compressor_matches_uncompressed(self):
        # two rounds on purpose: the compressed round fn donates its
        # state AND residual args (fedlint FL104 burn-down), and round 2
        # re-gathers the cohort residuals from the full per-client store
        # -- proving the donated round-1 buffers were never re-read
        from fedml_tpu.algorithms.fedavg import FedAvgAPI
        ds, spec = self._setup()
        a = FedAvgAPI(ds, spec, _fed_args(compressor="none"))
        b = FedAvgAPI(ds, spec, _fed_args())
        for _ in range(2):
            a.train_one_round()
            b.train_one_round()
        for x, y in zip(jax.tree.leaves(a.global_state["params"]),
                        jax.tree.leaves(b.global_state["params"])):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-6)

    def test_error_feedback_convergence_smoke(self):
        """Compressed FedAvg (with EF) reaches a loss within tolerance of
        uncompressed after the same number of rounds."""
        from fedml_tpu.algorithms.fedavg import FedAvgAPI
        ds, spec = self._setup()
        rounds = 10
        base = FedAvgAPI(ds, spec, _fed_args())
        for _ in range(rounds):
            ref = base.train_one_round()
        comp = FedAvgAPI(ds, spec, _fed_args(compressor="qsgd:8"))
        for _ in range(rounds):
            got = comp.train_one_round()
        assert got["Train/Loss"] <= ref["Train/Loss"] * 1.25 + 0.05
        assert got["compression_ratio"] > 2.5
        assert got["bytes_on_wire"] > 0
        # residuals are live state, not zeros: EF is actually engaged
        # (per-client accumulators live in the id-keyed ResidualStore)
        assert any(
            float(np.max(np.abs(r))) > 0
            for c in range(6)
            for r in jax.tree.leaves(comp.runner.residual_store.peek(c)))

    def test_mesh_plus_compressor_rejected(self):
        from fedml_tpu.algorithms.fedavg import FedAvgAPI
        ds, spec = self._setup()
        mesh = object()  # only reachability of the guard is under test
        with pytest.raises(ValueError, match="compressor"):
            FedAvgAPI(ds, spec, _fed_args(compressor="qsgd:8"), mesh=mesh)

    def test_decentralized_compressed_round(self):
        from fedml_tpu.algorithms.decentralized import DecentralizedFedAPI
        ds, spec = self._setup()
        api = DecentralizedFedAPI(ds, spec,
                                  _fed_args(compressor="topk:0.25"))
        m1 = api.train_one_round()
        m2 = api.train_one_round()
        assert m1["bytes_on_wire"] > 0 and m1["compression_ratio"] > 1.5
        assert np.isfinite(m2["Train/Loss"])


class _Recorder:
    def __init__(self):
        self.received = []

    def receive_message(self, msg_type, msg):
        self.received.append((msg_type, msg))


class TestTransportRoundtrip:
    def test_local_serialize_binary_beats_json(self):
        from fedml_tpu.core.comm.local import LocalCommNetwork
        net = LocalCommNetwork(2, serialize=True)
        m0, m1 = net.manager(0), net.manager(1)
        rec = _Recorder()
        m1.add_observer(rec)
        params = _cnn_sized_params()
        msg = Message("model", 0, 1)
        msg.add("params", params)
        m0.send_message(msg)
        m1.stop_receive_message()  # queue: payload then STOP
        m1.handle_receive_message()
        got = rec.received[0][1].get("params")
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                     got, params)
        json_cost = len(json.dumps(params_to_lists(params)))
        assert m0.bytes_sent == m1.bytes_received > 0
        assert json_cost >= 5 * m0.bytes_sent

    def test_tcp_compressed_round_8x_fewer_bytes(self, tmp_path):
        """Acceptance: a distributed round over real TCP sockets with qsgd
        payloads moves >=8x fewer bytes than the JSON-list codec would for
        the same update, measured from transport counters and logged via
        MetricsLogger."""
        from fedml_tpu.core.comm.tcp import TcpCommManager
        from fedml_tpu.utils.metrics import MetricsLogger

        s = socket.socket()
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
        s.close()

        params = _cnn_sized_params()
        comp = get_compressor("qsgd:8")
        server_rec = _Recorder()

        def client():
            comm = TcpCommManager("localhost", port, 1, 2, timeout=30.0)
            enc = jax.tree.map(np.asarray,
                               comp.compress(params, jax.random.PRNGKey(0)))
            out = Message("send_model_to_server", 1, 0)
            out.add("encoded", enc)
            out.add("num_samples", 100)
            comm.send_message(out)
            comm.handle_receive_message()  # until the server's STOP

        t = threading.Thread(target=client, daemon=True)
        t.start()
        server = TcpCommManager("localhost", port, 0, 2, timeout=30.0)
        server.add_observer(server_rec)
        stop_after = {"n": 0}

        class _Stopper:
            def receive_message(self, msg_type, msg):
                stop_after["n"] += 1
                server.stop_receive_message()

        server.add_observer(_Stopper())
        server.handle_receive_message()
        t.join(timeout=30)
        assert not t.is_alive()
        assert server_rec.received[0][0] == "send_model_to_server"

        # server-side reconstruction from what actually crossed the socket
        enc = server_rec.received[0][1].get("encoded")
        dec = comp.decompress(enc, params)
        scale = max(float(np.max(np.abs(np.asarray(v))))
                    for v in jax.tree.leaves(params))
        err = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                  for a, b in zip(jax.tree.leaves(dec),
                                  jax.tree.leaves(params)))
        assert err <= scale / comp.levels + 1e-6

        json_cost = len(json.dumps(params_to_lists(params)))
        wire_cost = server.bytes_received
        assert wire_cost > 0
        assert json_cost >= 8 * wire_cost, (json_cost, wire_cost)

        logger = MetricsLogger(run_dir=str(tmp_path))
        logger.count_wire(wire_cost, json_cost)
        logger.log({"round": 0})
        assert logger.summary["bytes_on_wire"] == wire_cost
        assert logger.summary["compression_ratio"] >= 8
        logger.close()


class TestMetricsLoggerWire:
    def test_counters_attach_once_and_reset(self, tmp_path):
        from fedml_tpu.utils.metrics import MetricsLogger
        logger = MetricsLogger(run_dir=str(tmp_path))
        logger.count_wire(1000, 4000)
        logger.log({"round": 0})
        assert logger.summary["bytes_on_wire"] == 1000
        assert logger.summary["compression_ratio"] == 4.0
        logger.log({"round": 1, "Train/Loss": 1.0})
        # no new traffic counted: round-1 record carries no wire keys
        with open(tmp_path / "metrics.jsonl") as f:
            records = [json.loads(line) for line in f]
        assert "bytes_on_wire" not in records[1]
        # explicit keys in the record win over the counters
        logger.count_wire(7, 7)
        logger.log({"round": 2, "bytes_on_wire": 123})
        assert logger.summary["bytes_on_wire"] == 123
        logger.close()


class TestResidualStore:
    """EF residuals key by STABLE client id, never cohort slot: re-sampled
    cohorts (incl. resilience re-attempts with different reporting
    subsets) must not cross-contaminate per-client accumulators."""

    def _template(self):
        return {"w": jnp.zeros((3, 2), jnp.float32),
                "b": jnp.zeros((2,), jnp.float32)}

    def _mark(self, ids):
        """Stacked update whose rows encode their OWNER id -- any slot-
        keyed indexing scrambles the values detectably."""
        return {"w": jnp.stack([jnp.full((3, 2), float(i)) for i in ids]),
                "b": jnp.stack([jnp.full((2,), float(i)) for i in ids])}

    @pytest.mark.parametrize("dense", [True, False])
    def test_resampled_cohorts_do_not_cross_contaminate(self, dense):
        from fedml_tpu.compression import ResidualStore
        store = ResidualStore(self._template(), num_clients=10, dense=dense)
        # round 1 samples {3, 7, 1}; round 2 re-samples {7, 2} with client
        # 7 at a DIFFERENT cohort slot (slot 1 -> slot 0)
        store.scatter([3, 7, 1], self._mark([3, 7, 1]))
        store.scatter([7, 2], self._mark([70, 2]))
        assert float(store.peek(7)["w"][0, 0]) == 70.0   # updated in place
        assert float(store.peek(3)["w"][0, 0]) == 3.0    # untouched carry
        assert float(store.peek(1)["w"][0, 0]) == 1.0
        assert float(store.peek(2)["w"][0, 0]) == 2.0
        # never-sampled clients stay zero
        for c in (0, 4, 5, 6, 8, 9):
            assert float(jnp.max(jnp.abs(store.peek(c)["w"]))) == 0.0

    @pytest.mark.parametrize("dense", [True, False])
    def test_gather_follows_ids_not_slots(self, dense):
        from fedml_tpu.compression import ResidualStore
        store = ResidualStore(self._template(), num_clients=8, dense=dense)
        store.scatter([5, 0, 6], self._mark([5, 0, 6]))
        got = store.gather([6, 5])  # reshuffled + subset cohort
        assert float(got["w"][0, 0, 0]) == 6.0
        assert float(got["w"][1, 0, 0]) == 5.0
        # gather of an untouched client materializes zeros (sparse lazily)
        fresh = store.gather([7])
        assert float(jnp.max(jnp.abs(fresh["w"]))) == 0.0

    def test_dense_sparse_equivalence(self):
        from fedml_tpu.compression import ResidualStore
        dense = ResidualStore(self._template(), num_clients=6, dense=True)
        sparse = ResidualStore(self._template(), dense=False)
        for ids in ([1, 4], [4, 2, 0], [5]):
            upd = self._mark([10 * i + 1 for i in ids])
            dense.scatter(ids, upd)
            sparse.scatter(ids, upd)
        for c in range(6):
            for a, b in zip(jax.tree.leaves(dense.peek(c)),
                            jax.tree.leaves(sparse.peek(c))):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fedavg_compressed_round_uses_id_keying(self):
        """End-to-end regression: run two compressed rounds whose cohorts
        re-sample (client_num_per_round < total) and assert every client
        NOT in a round's cohort kept its residual bytes unchanged."""
        from fedml_tpu.algorithms.fedavg import FedAvgAPI
        from fedml_tpu.algorithms.specs import make_classification_spec
        from fedml_tpu.data.synthetic import load_synthetic_federated
        from fedml_tpu import models

        spec = make_classification_spec(
            models.LogisticRegression(num_classes=10, apply_sigmoid=False),
            jnp.zeros((1, 60)))
        ds = load_synthetic_federated(client_num=8, n_train=400, n_test=80,
                                      alpha=0.0, beta=0.0, seed=0)
        api = FedAvgAPI(ds, spec, _fed_args(compressor="qsgd:8",
                                            client_num_in_total=8,
                                            client_num_per_round=3))
        from fedml_tpu.algorithms.fedavg import client_sampling
        cohort0 = set(client_sampling(0, 8, 3))
        api.train_one_round()
        before = {c: jax.tree.map(np.copy, api.runner.residual_store.peek(c))
                  for c in range(8)}
        cohort1 = set(client_sampling(1, 8, 3))
        api.train_one_round()
        assert cohort0 != cohort1  # the regression needs a re-sample
        for c in range(8):
            after = api.runner.residual_store.peek(c)
            if c in cohort1:
                continue
            for a, b in zip(jax.tree.leaves(before[c]),
                            jax.tree.leaves(after)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and the sampled clients' residuals are live (EF engaged)
        assert any(float(np.max(np.abs(r))) > 0
                   for c in cohort1
                   for r in jax.tree.leaves(api.runner.residual_store.peek(c)))


class TestZeroCopyViews:
    """The binary codec's zero-copy encode path (PR 11): buffer views
    whose concatenation IS the wire frame, with tensor payloads aliasing
    the source arrays (no copy until -- unless -- a transport joins)."""

    def test_views_join_equals_encode_tree(self):
        import ml_dtypes
        from fedml_tpu.compression.codec import (encode_tree,
                                                 encode_tree_views)
        rng = np.random.default_rng(0)
        tree = {
            "w": rng.standard_normal((17, 9)).astype(np.float32),
            "h": rng.standard_normal((4, 3)).astype(ml_dtypes.bfloat16),
            "mask": rng.random(37) > 0.5,          # bit-packed payload
            "scale": np.float32(0.125),            # numpy scalar -> JSON
            "zero_d": np.asarray(3.5, np.float64),  # framed 0-d leaf
            "nested": {"ids": np.arange(11, dtype=np.int32)},
            "note": "control",
        }
        views = encode_tree_views(tree)
        assert len(views) > 1
        assert b"".join(views) == encode_tree(tree)

    def test_payload_views_alias_source_arrays(self):
        # the hot property: a contiguous little-endian array's payload
        # buffer is a VIEW over the array's own memory, not a copy
        from fedml_tpu.compression.codec import encode_array_views
        a = np.arange(24, dtype=np.float32).reshape(4, 6)
        header, payload = encode_array_views(a)
        assert isinstance(payload, memoryview)
        assert np.shares_memory(np.frombuffer(payload, np.float32), a)
        # bool arrays bit-pack (inherent conversion copy) but still
        # concatenate to the exact wire bytes
        from fedml_tpu.compression.codec import encode_array
        b = np.array([True, False, True] * 5)
        assert b"".join(bytes(p) for p in
                        encode_array_views(b)) == encode_array(b)

    def test_message_views_roundtrip(self):
        from fedml_tpu.compression.codec import (message_from_wire,
                                                 message_to_wire,
                                                 message_to_wire_views)
        from fedml_tpu.core.message import Message
        msg = Message("res_report", 3, 0)
        msg.add("params", {"w": np.ones((5, 2), np.float32)})
        msg.add("num_samples", 30.0)
        views = message_to_wire_views(msg)
        wire = b"".join(views)
        assert wire == message_to_wire(msg)
        back = message_from_wire(wire)
        assert back.get_type() == "res_report"
        assert (back.get("params")["w"] == 1.0).all()
        assert back.get("num_samples") == 30.0

    def test_noncontiguous_and_bigendian_fall_back_exactly(self):
        from fedml_tpu.compression.codec import (decode_array,
                                                 encode_array,
                                                 encode_array_views)
        base = np.arange(40, dtype=np.float32).reshape(5, 8)
        strided = base[:, ::2]                     # non-contiguous
        be = np.arange(6, dtype=">i4")             # explicit big-endian
        for a in (strided, be):
            wire = b"".join(bytes(p) for p in encode_array_views(a))
            assert wire == encode_array(a)
            out, _ = decode_array(wire)
            np.testing.assert_array_equal(out, np.ascontiguousarray(a))


class TestZeroCopyDecode:
    """The decode twin of TestZeroCopyViews (ISSUE 14): wire frames
    decoded over ``memoryview``s of the receive buffer alias it --
    zero payload copies from the wire to the aggregator fold -- with
    the exotic layouts (bool bit-pack, bf16, big-endian) falling back
    to the copying path byte-equal."""

    def _fuzz_tree(self):
        import ml_dtypes
        rng = np.random.default_rng(7)
        return {
            "w": rng.standard_normal((13, 5)).astype(np.float32),
            "h": rng.standard_normal((3, 4)).astype(ml_dtypes.bfloat16),
            "mask": rng.random(41) > 0.5,            # bool bit-pack
            "zero_d": np.asarray(2.25, np.float64),  # framed 0-d leaf
            "ids": np.arange(9, dtype=np.int64),
            "strided": np.arange(24, dtype=np.float32).reshape(4, 6)[:, ::2],
            "be": np.arange(5, dtype=">i4"),         # big-endian input
            "n": 30.0,
            "note": "control",
        }

    def test_memoryview_vs_bytes_decode_byte_equal(self):
        # the parity fuzz: the SAME wire bytes decoded as bytes, as a
        # bytearray, and as a memoryview over a bytearray produce
        # byte-identical trees across the full codec matrix
        import jax
        from fedml_tpu.compression.codec import decode_tree, encode_tree
        wire = encode_tree(self._fuzz_tree())
        ref = decode_tree(wire)
        for form in (bytearray(wire), memoryview(bytearray(wire))):
            got = decode_tree(form)
            for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
                if isinstance(a, np.ndarray):
                    assert a.dtype == b.dtype and a.shape == b.shape
                    assert a.tobytes() == b.tobytes()
                else:
                    assert a == b

    def test_legacy_json_sniff_from_memoryview(self):
        from fedml_tpu.compression.codec import message_from_wire
        from fedml_tpu.core.message import Message
        legacy = Message("res_sync", 0, 3)
        legacy.add("round", 2)
        wire = legacy.to_json().encode()
        for form in (wire, bytearray(wire), memoryview(bytearray(wire))):
            back = message_from_wire(form)
            assert back.get_type() == "res_sync"
            assert back.get("round") == 2

    def test_decoded_payload_shares_receive_buffer(self):
        # THE zero-copy pin: a contiguous native-dtype tensor decoded
        # from a memoryview over the receive buffer is an aliasing view
        # (np.shares_memory), marked read-only because the buffer is
        # mutable; bool/bf16 leaves are the documented copying fallback
        import ml_dtypes
        from fedml_tpu.compression.codec import decode_tree, encode_tree
        tree = {"w": np.arange(20, dtype=np.float32).reshape(4, 5),
                "ids": np.arange(6, dtype=np.int32),
                "mask": np.array([True, False] * 9),
                "h": np.ones((2, 3), ml_dtypes.bfloat16)}
        buf = bytearray(encode_tree(tree))
        raw = np.frombuffer(buf, np.uint8)
        out = decode_tree(memoryview(buf))
        for k in ("w", "ids"):
            assert np.shares_memory(out[k], raw), k
            assert not out[k].flags.writeable, k
        for k in ("mask", "h"):
            assert not np.shares_memory(out[k], raw), k
        # bytes input (immutable) also aliases; numpy already freezes it
        out2 = decode_tree(bytes(buf))
        assert not out2["w"].flags.writeable

    def test_alias_safety_fold_contract(self):
        # the buffer-retention contract, pinned: (a) a decoded view is
        # READ-ONLY, so no consumer can mutate it into a folded entry;
        # (b) the view keeps its frame buffer alive by reference, so
        # "recycling" can only mean the transport allocating a FRESH
        # buffer per frame (which the event loop does -- rx_buf is a new
        # bytearray per frame) -- dropping every external reference to
        # the buffer cannot invalidate a buffered entry's bytes.
        import gc
        from fedml_tpu.compression.codec import decode_tree, encode_tree
        from fedml_tpu.resilience.async_agg import (AsyncAggPolicy,
                                                    BufferedAggregator)
        tree = {"w": np.full((8,), 3.0, np.float32)}
        buf = bytearray(encode_tree(tree))
        out = decode_tree(memoryview(buf))
        with pytest.raises((ValueError, RuntimeError)):
            out["w"][0] = 99.0  # decoded views cannot be written through
        agg = BufferedAggregator(AsyncAggPolicy(buffer_k=1,
                                                staleness_decay=0.0))
        agg.fold(1, 10.0, out)
        del buf, out  # the transport/dispatcher drop their references
        gc.collect()
        res = agg.flush()
        assert (res.params["w"] == 3.0).all()

    def test_peek_wire_envelope_routes_without_payload_decode(self):
        from fedml_tpu.compression.codec import (message_to_wire,
                                                 peek_wire_envelope)
        from fedml_tpu.core.message import Message
        msg = Message("res_report", 3, 0)
        msg.add("params", {"w": np.ones((64, 64), np.float32)})
        wire = message_to_wire(msg)
        assert peek_wire_envelope(wire) == ("res_report", 3, 0)
        # corrupt every array byte: the envelope still routes (the hub
        # relays raw; the DESTINATION validates payloads)
        corrupt = bytearray(wire)
        corrupt[-16:] = b"\xff" * 16
        assert peek_wire_envelope(corrupt) == ("res_report", 3, 0)
        # legacy JSON frames peek too
        legacy = Message("__goodbye__", 5, 0).to_json().encode()
        assert peek_wire_envelope(legacy) == ("__goodbye__", 5, 0)

    def test_decode_frames_batch_matches_single(self):
        from fedml_tpu.compression.codec import (decode_frames,
                                                 message_from_wire,
                                                 message_to_wire)
        from fedml_tpu.core.message import Message
        frames = []
        for r in range(1, 4):
            m = Message("res_report", r, 0)
            m.add("params", {"w": np.full((4,), float(r), np.float32)})
            m.add("num_samples", 10.0 * r)
            frames.append(bytearray(message_to_wire(m)))
        frames.append(bytearray(b"\x9e\x01junkjunkjunk"))  # undecodable
        out = decode_frames(frames)
        assert isinstance(out[3], Exception)
        for r, got in enumerate(out[:3], start=1):
            want = message_from_wire(frames[r - 1])
            assert got.get_type() == want.get_type() == "res_report"
            assert got.get_sender_id() == r
            assert (got.get("params")["w"]
                    == want.get("params")["w"]).all()
            assert got.get("num_samples") == want.get("num_samples")


# ---------------------------------------------------------------------------
# fedsqueeze (ISSUE 15): host wire compressors + sparse compressed folds
# ---------------------------------------------------------------------------
class TestWireCompressors:
    """compression/wire.py: the numpy-only twins of the jit compressors
    for the DISTRIBUTED uplink -- sub-byte code packing, spec grammar,
    error feedback, deterministic keyed encode rngs."""

    @pytest.mark.parametrize("bits", [2, 3, 4, 5, 7, 8])
    def test_pack_unpack_roundtrip(self, bits):
        from fedml_tpu.compression.wire import (pack_codes, packed_nbytes,
                                                unpack_codes)
        rng = np.random.default_rng(bits)
        L = 2 ** (bits - 1) - 1
        for n in (0, 1, 3, 17, 4096):
            codes = rng.integers(-L, L + 1, n).astype(np.int8)
            packed = pack_codes(codes, bits)
            assert len(packed) == packed_nbytes(n, bits)
            np.testing.assert_array_equal(
                unpack_codes(packed, n, bits), codes)

    @pytest.mark.parametrize("bits", [2, 4])
    def test_fast_even_width_pack_byte_equal_to_generic(self, bits):
        # the arithmetic fast path must emit EXACTLY the generic
        # unpackbits path's bytes -- it is a wire format, not a cache
        from fedml_tpu.compression.wire import pack_codes
        rng = np.random.default_rng(9)
        L = 2 ** (bits - 1) - 1
        codes = rng.integers(-L, L + 1, 4097).astype(np.int8)
        u = (codes.astype(np.int16).reshape(-1) + L).astype(np.uint8)
        bitmat = np.unpackbits(u[:, None], axis=1)[:, 8 - bits:]
        generic = np.packbits(bitmat.reshape(-1))
        np.testing.assert_array_equal(pack_codes(codes, bits), generic)

    def test_qsgd_roundtrip_bounded_error(self):
        from fedml_tpu.compression.wire import host_compressor
        rng = np.random.default_rng(3)
        x = rng.standard_normal(4096).astype(np.float32)
        for bits in (2, 4, 8):
            comp = host_compressor(f"qsgd:{bits}")
            enc = comp.encode_leaf(x, np.random.default_rng(0))
            dec = comp.decode_leaf(enc)
            assert dec.shape == x.shape and dec.dtype == x.dtype
            # one quantization cell of error, scale/levels wide
            cell = float(np.abs(x).max()) / (2 ** (bits - 1) - 1)
            assert float(np.abs(dec - x).max()) <= cell + 1e-6

    def test_topk_sorted_indices_and_kept_exactness(self):
        from fedml_tpu.compression.wire import host_compressor
        comp = host_compressor("topk:0.1")
        rng = np.random.default_rng(5)
        x = rng.standard_normal((32, 8)).astype(np.float32)
        enc = comp.encode_leaf(x, None)
        idx = np.asarray(enc["indices"])
        assert (np.diff(idx) > 0).all()  # canonical sorted form
        assert len(idx) == int(np.ceil(0.1 * x.size))
        dec = comp.decode_leaf(enc)
        flat, dflat = x.reshape(-1), dec.reshape(-1)
        np.testing.assert_array_equal(dflat[idx], flat[idx])  # kept exact
        mask = np.ones(x.size, bool)
        mask[idx] = False
        assert (dflat[mask] == 0).all()
        # and the kept set IS the magnitude top-k
        assert np.abs(flat[idx]).min() >= np.abs(flat[mask]).max()

    def test_signsgd_roundtrip(self):
        from fedml_tpu.compression.wire import host_compressor
        comp = host_compressor("signsgd")
        x = np.asarray([1.5, -2.0, 0.25, -0.25], np.float32)
        enc = comp.encode_leaf(x, None)
        dec = comp.decode_leaf(enc)
        scale = float(np.mean(np.abs(x)))
        np.testing.assert_allclose(dec, np.where(x >= 0, scale, -scale),
                                   rtol=1e-6)

    def test_host_compressor_grammar(self):
        from fedml_tpu.compression.wire import HostQSGD, host_compressor
        assert host_compressor(None) is None
        assert host_compressor("none") is None
        assert host_compressor("off") is None
        assert host_compressor("qsgd").bits == 2  # wire default: ternary
        assert host_compressor("qsgd:4").bits == 4
        assert host_compressor("topk:0.05").ratio == 0.05
        inst = HostQSGD(4)
        assert host_compressor(inst) is inst
        with pytest.raises(ValueError, match="randk"):
            host_compressor("randk:0.1")
        with pytest.raises(ValueError, match="unknown"):
            host_compressor("zip")
        with pytest.raises(ValueError):
            host_compressor("qsgd:1")

    def test_ef_step_qsgd_is_unbiased_path_no_residual(self):
        # qsgd is unbiased stochastic rounding: ef_step encodes the RAW
        # delta and never accumulates a residual (feedback through a
        # wide-cell unbiased quantizer is an amplifier -- see
        # test_qsgd_closed_loop_is_stable for the divergence it causes)
        from fedml_tpu.compression.wire import (ef_step, encode_rng,
                                                host_compressor)
        comp = host_compressor("qsgd")
        assert comp.ef is False
        rng = np.random.default_rng(5)
        delta = {"w": rng.standard_normal(64).astype(np.float32)}
        enc, dec, res = ef_step(comp, delta, None, encode_rng((1, 0, 0)))
        assert res is None
        direct = comp.encode({"w": delta["w"]}, encode_rng((1, 0, 0)))
        np.testing.assert_array_equal(enc["w"]["qp"], direct["w"]["qp"])

    def test_qsgd_closed_loop_is_stable(self):
        # the regression that forced ef=False: drive the federated
        # fixed-point recurrence w' = w + avg_r 0.25*(t_r - w) through
        # the ternary wire quantizer for 60 rounds. Unbiased-no-feedback
        # stays in a bounded noise floor; forcing EF through the same
        # quantizer amplifies the residual EXPONENTIALLY (the scale of
        # round t's encode includes round t-1's noise, which is of
        # magnitude scale itself -- measured 0.98 -> 647 over 60 rounds
        # before the fix).
        from fedml_tpu.compression.wire import (ef_step, encode_rng,
                                                host_compressor)
        comp = host_compressor("qsgd")
        ranks, weights = [1, 2, 3], np.array([1 / 6, 2 / 6, 3 / 6])
        w = np.linspace(-1, 1, 256).astype(np.float32)
        tbar = float((weights * np.array(ranks)).sum())
        res = {r: None for r in ranks}
        for rnd in range(60):
            agg = np.zeros_like(w, np.float64)
            for r, wt in zip(ranks, weights):
                d = {"w": (0.25 * (np.float32(r) - w)).astype(np.float32)}
                _, dec, res[r] = ef_step(comp, d, res[r],
                                         encode_rng((r, rnd, 0)))
                agg += wt * (w.astype(np.float64) + dec["w"])
            w = agg.astype(np.float32)
        assert float(np.abs(w - tbar).max()) < 1.0  # bounded noise floor
        # counterexample: the SAME loop with feedback forced through the
        # quantizer diverges past any bound the stable loop ever nears
        w2 = np.linspace(-1, 1, 256).astype(np.float32)
        res2 = {r: {"w": np.zeros_like(w2)} for r in ranks}
        for rnd in range(60):
            agg = np.zeros_like(w2, np.float64)
            for r, wt in zip(ranks, weights):
                d = (0.25 * (np.float32(r) - w2)).astype(np.float32)
                comp_in = d + res2[r]["w"]
                enc = comp.encode({"w": comp_in}, encode_rng((r, rnd, 0)))
                dec = comp.decode(enc)["w"]
                res2[r]["w"] = comp_in - dec
                agg += wt * (w2.astype(np.float64) + dec)
            w2 = agg.astype(np.float32)
        assert float(np.abs(w2 - tbar).max()) > 10.0  # the amplifier

    def test_ef_step_residual_identity(self):
        from fedml_tpu.compression.wire import (ef_step, encode_rng,
                                                host_compressor)
        comp = host_compressor("topk:0.25")
        rng = np.random.default_rng(11)
        delta = {"w": rng.standard_normal(64).astype(np.float32)}
        enc, dec, res = ef_step(comp, delta, None, encode_rng((1, 0, 0)))
        # residual' = (delta + 0) - decoded, exactly
        np.testing.assert_array_equal(res["w"], delta["w"] - dec["w"])
        # second step carries it: compressed input is delta2 + residual
        delta2 = {"w": rng.standard_normal(64).astype(np.float32)}
        enc2, dec2, res2 = ef_step(comp, delta2, res,
                                   encode_rng((1, 1, 0)))
        np.testing.assert_array_equal(
            res2["w"], (delta2["w"] + res["w"]) - dec2["w"])

    def test_encode_rng_keyed_determinism(self):
        from fedml_tpu.compression.wire import encode_rng, host_compressor
        comp = host_compressor("qsgd")
        x = np.random.default_rng(0).standard_normal(512).astype(np.float32)
        a = comp.encode_leaf(x, encode_rng((3, 7, 1)))
        b = comp.encode_leaf(x, encode_rng((3, 7, 1)))
        c = comp.encode_leaf(x, encode_rng((3, 7, 2)))
        np.testing.assert_array_equal(a["qp"], b["qp"])
        assert not np.array_equal(a["qp"], c["qp"])

    def test_qsgd_wire_bytes_at_least_8x_smaller(self):
        # the headline byte gate at a measurable model size: qsgd:2 on a
        # 16k-float template is >= 8x below the raw binary frame
        from fedml_tpu.compression.wire import (host_compressor,
                                                wire_payload_nbytes)
        template = {"w": np.zeros(16384, np.float32)}
        raw = tree_wire_nbytes(template)
        comp_bytes = wire_payload_nbytes(host_compressor("qsgd"), template)
        assert raw / comp_bytes >= 8.0, (raw, comp_bytes)
        # signsgd (1 bit + scale) lands near 32x
        sign_bytes = wire_payload_nbytes(host_compressor("signsgd"),
                                         template)
        assert raw / sign_bytes >= 20.0, (raw, sign_bytes)


class TestCompressedFold:
    """fold_entries_fp64's CompressedUpdate path: sparse O(k) delta
    accumulation + each distinct base added exactly once, sorted-key
    deterministic, mixing freely with dense entries."""

    def _mk_update(self, spec, base, seed, base_key=0):
        from fedml_tpu.compression.wire import (CompressedUpdate, ef_step,
                                                encode_rng, host_compressor)
        comp = host_compressor(spec)
        rng = np.random.default_rng(seed)
        delta = {k: rng.standard_normal(np.shape(v)).astype(np.float32)
                 for k, v in base.items()}
        enc, dec, _ = ef_step(comp, delta, None, encode_rng((seed, 0, 0)))
        return CompressedUpdate(enc=enc, spec=comp.spec, base=base,
                                base_key=base_key), dec

    def test_fold_equals_manual_reference(self):
        from fedml_tpu.resilience.policy import fold_entries_fp64
        base = {"w": np.random.default_rng(0).standard_normal(
            (8, 4)).astype(np.float32)}
        entries, ref_num, total = [], None, 0.0
        for rank, spec in ((1, "qsgd"), (2, "topk:0.25"), (3, "signsgd")):
            upd, dec = self._mk_update(spec, base, rank)
            n = 10.0 * rank
            entries.append((rank, n, upd, n))
            total += n
            contrib = {k: n * (np.asarray(base[k], np.float64)
                               + np.asarray(dec[k], np.float64))
                       for k in base}
            ref_num = contrib if ref_num is None else {
                k: ref_num[k] + contrib[k] for k in contrib}
        got, w = fold_entries_fp64(entries)
        assert w == total
        # same VALUE as the densified reference (the fold's own f64
        # combine order differs -- allclose, not bitwise, vs this ref)
        for k in base:
            np.testing.assert_allclose(
                np.asarray(got[k], np.float64),
                ref_num[k] / total, rtol=1e-6)

    def test_fold_arrival_order_independent_bitwise(self):
        import random
        from fedml_tpu.resilience.policy import fold_entries_fp64
        base = {"w": np.random.default_rng(1).standard_normal(
            32).astype(np.float32)}
        entries = []
        for rank in range(1, 6):
            upd, _ = self._mk_update("topk:0.5", base, rank)
            entries.append((rank, float(rank), upd, float(rank)))
        ref, _ = fold_entries_fp64(list(entries))
        for seed in range(3):
            random.Random(seed).shuffle(entries)
            got, _ = fold_entries_fp64(list(entries))
            for k in base:
                np.testing.assert_array_equal(got[k], ref[k])

    def test_mixed_dense_and_compressed_entries(self):
        from fedml_tpu.resilience.policy import fold_entries_fp64
        base = {"w": np.ones(16, np.float32)}
        upd, dec = self._mk_update("qsgd:4", base, 7)
        dense = {"w": np.full(16, 3.0, np.float32)}
        got, w = fold_entries_fp64([
            (1, 10.0, dense, 10.0), (2, 30.0, upd, 30.0)])
        assert w == 40.0
        want = (10.0 * dense["w"].astype(np.float64)
                + 30.0 * (base["w"].astype(np.float64)
                          + dec["w"].astype(np.float64))) / 40.0
        np.testing.assert_allclose(np.asarray(got["w"], np.float64),
                                   want, rtol=1e-7)

    def test_distinct_bases_added_once_each(self):
        from fedml_tpu.resilience.policy import fold_entries_fp64
        b0 = {"w": np.full(8, 1.0, np.float32)}
        b1 = {"w": np.full(8, 2.0, np.float32)}
        u0a, d0a = self._mk_update("topk:0.5", b0, 1, base_key=0)
        u0b, d0b = self._mk_update("topk:0.5", b0, 2, base_key=0)
        u1, d1 = self._mk_update("topk:0.5", b1, 3, base_key=1)
        got, w = fold_entries_fp64([
            (1, 1.0, u0a, 1.0), (2, 2.0, u0b, 2.0), (3, 3.0, u1, 3.0)])
        want = ((1.0 + 2.0) * b0["w"].astype(np.float64)
                + 3.0 * b1["w"].astype(np.float64)
                + 1.0 * d0a["w"].astype(np.float64)
                + 2.0 * d0b["w"].astype(np.float64)
                + 3.0 * d1["w"].astype(np.float64)) / 6.0
        np.testing.assert_allclose(np.asarray(got["w"], np.float64),
                                   want, rtol=1e-7)

    def test_topk_fold_leaf_is_sparse_and_exact(self):
        # fold_leaf == scale * f64(decode) without densifying: only the
        # kept coordinates move
        from fedml_tpu.compression.wire import host_compressor
        comp = host_compressor("topk:0.1")
        x = np.random.default_rng(2).standard_normal(256).astype(np.float32)
        enc = comp.encode_leaf(x, None)
        acc = np.zeros(256, np.float64)
        comp.fold_leaf(acc, enc, 2.5)
        np.testing.assert_array_equal(
            acc, 2.5 * comp.decode_leaf(enc).astype(np.float64))

    def test_buffered_aggregator_compressed_oracle(self):
        # async flush over compressed entries == aggregate_reports over
        # the SAME reports, bit for bit (decay 0, one flush)
        from fedml_tpu.resilience.async_agg import (AsyncAggPolicy,
                                                    BufferedAggregator)
        from fedml_tpu.resilience.policy import aggregate_reports
        base = {"w": np.random.default_rng(4).standard_normal(
            64).astype(np.float32)}
        reports = {}
        agg = BufferedAggregator(AsyncAggPolicy(buffer_k=10 ** 9,
                                                staleness_decay=0.0))
        for rank in (3, 1, 2):  # racy arrival order
            upd, _ = self._mk_update("qsgd", base, rank)
            reports[rank] = (10.0 * rank, upd)
            agg.fold(rank, 10.0 * rank, upd)
        res = agg.flush("drain")
        want, total = aggregate_reports(reports)
        assert res.weight == total
        for k in base:
            np.testing.assert_array_equal(res.params[k], want[k])

    def test_staleness_weighting_applies_to_compressed_entries(self):
        from fedml_tpu.resilience.async_agg import (AsyncAggPolicy,
                                                    BufferedAggregator,
                                                    staleness_weight)
        from fedml_tpu.resilience.policy import fold_entries_fp64
        base = {"w": np.full(16, 2.0, np.float32)}
        upd, _ = self._mk_update("qsgd", base, 1)
        agg = BufferedAggregator(AsyncAggPolicy(buffer_k=10 ** 9,
                                                staleness_decay=0.5))
        agg.fold(1, 10.0, upd, staleness=3)
        res = agg.flush("drain")
        sw = staleness_weight(3, 0.5)
        want, _ = fold_entries_fp64([(1, 10.0 * sw, upd, 10.0 * sw)])
        for k in base:
            np.testing.assert_array_equal(res.params[k], want[k])


class TestCompressedWireFuzz:
    """Satellite: decode-parity fuzz extended to compressed frames --
    qsgd/topk/signsgd report payloads through the message_from_wire
    memoryview path, byte-equal across buffer forms, alias-safety
    (read-only views) held."""

    def _report(self, spec, seed=0):
        from fedml_tpu.compression.wire import (WIRE_DELTA_KEY,
                                                WIRE_SPEC_KEY, ef_step,
                                                encode_rng, host_compressor)
        comp = host_compressor(spec)
        rng = np.random.default_rng(seed)
        delta = {"w": rng.standard_normal((16, 8)).astype(np.float32),
                 "b": rng.standard_normal(8).astype(np.float32)}
        enc, _, _ = ef_step(comp, delta, None, encode_rng((1, 0, 0)))
        msg = Message("res_report", 1, 0)
        msg.add(WIRE_DELTA_KEY, enc)
        msg.add(WIRE_SPEC_KEY, comp.spec)
        msg.add("num_samples", 10.0)
        msg.add("round", 2)
        msg.add("attempt", 0)
        return msg, enc, comp

    @pytest.mark.parametrize("spec", ["qsgd", "qsgd:5", "topk:0.1",
                                      "signsgd"])
    def test_compressed_report_roundtrip_all_buffer_forms(self, spec):
        msg, enc, comp = self._report(spec)
        wire = message_to_wire(msg)
        ref = message_from_wire(wire)
        for form in (bytearray(wire), memoryview(bytearray(wire))):
            back = message_from_wire(form)
            assert back.get_type() == "res_report"
            assert back.get("compressor") == comp.spec
            got, want = back.get("cdelta"), ref.get("cdelta")
            for k in enc:
                for field in enc[k]:
                    a, b = got[k][field], want[k][field]
                    if isinstance(a, np.ndarray):
                        assert a.dtype == b.dtype
                        assert a.tobytes() == b.tobytes()
                    else:
                        assert a == b
            # the decoded update survives the wire exactly
            np.testing.assert_array_equal(
                comp.decode(got)["w"], comp.decode(enc)["w"])

    def test_compressed_payload_aliases_and_is_readonly(self):
        msg, enc, comp = self._report("qsgd")
        buf = bytearray(message_to_wire(msg))
        raw = np.frombuffer(buf, np.uint8)
        back = message_from_wire(memoryview(buf))
        qp = back.get("cdelta")["w"]["qp"]
        assert np.shares_memory(qp, raw)       # zero-copy ingest
        assert not qp.flags.writeable          # alias-safety contract
        # the sparse fold accumulates FROM the read-only view fine
        acc = {k: np.zeros(np.shape(v), np.float64)
               for k, v in {"w": np.zeros((16, 8)),
                            "b": np.zeros(8)}.items()}
        for k in acc:
            comp.fold_leaf(acc[k], back.get("cdelta")[k], 1.0)
        np.testing.assert_array_equal(
            acc["w"], comp.decode_leaf(enc["w"]).astype(np.float64))


class TestSecureAggCommutation:
    """Satellite: where TurboAggregate-style additive masking commutes
    with the qsgd/topk codec -- and exactly where it cannot (the
    scenario-matrix seed, docs/COMPRESSION.md "Distributed wire path").

    The composition rule this pins: masking must happen on DECODED
    updates (server side of the codec, before the additive fold), where
    zero-sum mask groups cancel up to f64 reassociation. Masking BEFORE
    the encode does NOT commute: topk's support selection and qsgd's
    max-|x| scale both depend on the masked values."""

    def test_masking_decoded_updates_commutes_with_additive_fold(self):
        from fedml_tpu.compression.wire import encode_rng, host_compressor
        rng = np.random.default_rng(0)
        x = [rng.standard_normal(128).astype(np.float32) for _ in range(4)]
        for spec in ("qsgd", "topk:0.1"):
            comp = host_compressor(spec)
            dec = [comp.decode_leaf(comp.encode_leaf(
                xi, encode_rng((i, 0, 0)))) for i, xi in enumerate(x)]
            # pairwise zero-sum masks (TurboAggregate's additive shares)
            masks = [rng.standard_normal(128).astype(np.float64)
                     for _ in range(3)]
            masks.append(-np.sum(masks, axis=0))
            plain = np.sum([d.astype(np.float64) for d in dec], axis=0)
            masked = np.sum([d.astype(np.float64) + m
                             for d, m in zip(dec, masks)], axis=0)
            # commutes up to f64 reassociation (NOT bitwise: floating
            # addition is not associative -- the documented limit)
            np.testing.assert_allclose(masked, plain, atol=1e-9)

    def test_masking_before_encode_does_not_commute(self):
        # the "exactly where it cannot" half: enc(delta + mask) is NOT
        # enc(delta) shifted by mask -- topk picks a different support,
        # qsgd quantizes against a different scale
        from fedml_tpu.compression.wire import encode_rng, host_compressor
        rng = np.random.default_rng(1)
        delta = rng.standard_normal(256).astype(np.float32) * 0.01
        mask = rng.standard_normal(256).astype(np.float32)  # mask >> delta
        topk = host_compressor("topk:0.05")
        idx_plain = np.asarray(topk.encode_leaf(delta, None)["indices"])
        idx_masked = np.asarray(
            topk.encode_leaf(delta + mask, None)["indices"])
        assert not np.array_equal(idx_plain, idx_masked)  # support moved
        qsgd = host_compressor("qsgd")
        r = encode_rng((0, 0, 0))
        dec_plain = qsgd.decode_leaf(qsgd.encode_leaf(delta, r))
        dec_masked = qsgd.decode_leaf(
            qsgd.encode_leaf(delta + mask, encode_rng((0, 0, 0)))) - mask
        # un-masking after a masked encode does NOT recover the plain
        # decode: the quantization grid scaled to the mask's magnitude
        assert float(np.abs(dec_masked - dec_plain).max()) > 0.1
