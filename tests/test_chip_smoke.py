"""CPU dry run of ``chip_smoke.py``'s legs at toy sizes, so the commands
are known to parse and run end to end before chip time is spent on them.
The legs are imported and handed a toy ``Sizes``; ``chip_smoke.main``
itself (the preamble that refuses anything but a TPU) is covered by the
exit-code test."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = chip_smoke.Sizes(
    model="cnn", n_train=256, n_test=32, image_size=28, clients=4,
    batch_size=8, client_chunk=2, rounds=3, mesh=4, mesh_rounds=3,
    attn_seq_lens=(40,), attn_batch=1, attn_heads=1, head_dim=32,
    lm_d_model=32, lm_layers=1, lm_seq=16, lm_clients=6, lm_batch=2,
    lm_chunk=4, lm_rounds=2)


@pytest.fixture
def cache_dir(tmp_path, monkeypatch, restore_cache_config):
    # the legs enable the compile cache through the normal entry points,
    # with no directory argument: point the default at a tmp dir so the
    # dry run's entries stay out of the checkout's .jax_cache
    from fedml_tpu.utils import compile_cache
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR",
                        str(tmp_path / "cache"))


def _live_array_bytes(devices, key):
    # XLA:CPU keeps no memory_stats(): stand in for the allocator with
    # the bytes of the live arrays' shards, so Leg C's growth check runs
    # here too (the peak is then the live value)
    import jax

    live = {d: 0 for d in devices}
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            if shard.device in live:
                live[shard.device] += shard.data.nbytes
    return [live[d] for d in devices]


def test_leg_a_and_c_toy(tmp_path, cache_dir, monkeypatch):
    import jax

    assert chip_smoke._memory_stat(jax.devices()[:1], "bytes_in_use") is None
    monkeypatch.setattr(chip_smoke, "_memory_stat", _live_array_bytes)
    a = chip_smoke.leg_a(TOY, str(tmp_path))
    assert a["mode"] == "mxu-lanes"
    assert a["compiles_per_round"][2:] == [0]
    c = chip_smoke.leg_c(TOY, str(tmp_path), a["train_loss"][0])
    assert c["sharded_lanes"]["mode"] == "sharded-lanes"
    assert c["sharded_round"]["mode"] == "packed"
    for ev in c.values():
        assert len(ev["cohort_bytes"]) == TOY.mesh and all(ev["cohort_bytes"])
        assert all(g >= d + s for g, d, s in zip(
            ev["live_bytes_grown"], ev["cohort_bytes"], ev["state_bytes"]))


def test_leg_c_fails_when_a_device_gains_nothing(tmp_path, cache_dir,
                                                 monkeypatch):
    # the allocator check is on growth over the run, so memory an earlier
    # leg left behind on a device cannot satisfy it
    def device_1_flat(devices, key):
        out = _live_array_bytes(devices, key)
        return [7 if i == 1 else b for i, b in enumerate(out)]

    monkeypatch.setattr(chip_smoke, "_memory_stat", device_1_flat)
    with pytest.raises(chip_smoke.SmokeError, match="live bytes grew"):
        chip_smoke.fedavg_leg(TOY, str(tmp_path / "run"), mesh=TOY.mesh,
                              wave_mode=1, rounds=TOY.mesh_rounds)


def test_leg_b_toy(cache_dir):
    b = chip_smoke.leg_b(TOY)
    assert set(b) == {"flash_attention", "federated_lm"}
    assert len(b["federated_lm"]["train_loss"]) == TOY.lm_rounds


def test_result_line_is_the_contract():
    # the driver refuses a last line with any other key (it refused
    # "legs"): exactly ok + device{platform, kind, count}
    import json

    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_main_refuses_cpu(tmp_path):
    # no accelerator: non-zero exit and no result line
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--out_dir", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "platform=cpu" in r.stdout
