"""A toy checkout for the tests: the real harness, readers and references
over tiny configurations and cells, so that a whole run fits a CPU test.

``make_root(tmp)`` writes ``<tmp>/BENCHMARK.json`` and ``<tmp>/benchmarks``
(the data files only: the code is imported from the real checkout) and
returns ``tmp``. The toy cells keep the real cells' shape: two check
rounds, the same metrics, limits of their own (a toy's rounding is not the
chip's).
"""

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REAL = os.path.dirname(os.path.dirname(HERE))

LM_CELL = "toy-lm.toy3"

CONFIGS = {
    "toy-lm": {
        "name": "toy-lm", "family": "gpt2_lm",
        "reference": "gpt2_lm_reference.py", "n_embd": 128, "n_head": 1,
        "n_inner": 512, "n_positions": 64, "vocab_size": 256, "n_layer": 2,
        "layer_norm_epsilon": 1e-5,
        "as_run": {"layer_norm_epsilon": 1e-6,
                   "compute_dtype": "bfloat16"}},
}
TRAFFIC = {
    "toy3": {"sequences_per_client": [4, 6, 10], "seq_len": 64,
             "batch_size": 2, "epochs": 1, "client_chunk": 1, "lr": 0.1,
             "wd": 0.0},
}
CELLS = {
    LM_CELL: {"config": "toy-lm", "traffic": "toy3", "check_rounds": 2,
              "trace_rounds": 1,
              "limits": {"loss_r1": 3e-4, "loss_r2": 3e-4,
                         "first_update_gap": 4e-3, "change_gap": 4e-3}},
}


def make_root(tmp):
    home = os.path.join(tmp, "benchmarks")
    for d in ("end_to_end", "layer_metrics"):
        shutil.copytree(os.path.join(REAL, "benchmarks", d),
                        os.path.join(home, d))
    for d in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(home, d))
    for cfg in CONFIGS.values():
        shutil.copy(os.path.join(REAL, "benchmarks", "configs",
                                 cfg["reference"]),
                    os.path.join(home, "configs"))
    for folder, files in (("configs", CONFIGS), ("traffic", TRAFFIC),
                          ("workloads", CELLS)):
        for name, data in files.items():
            with open(os.path.join(home, folder, name + ".json"), "w") as f:
                json.dump(data, f)
    with open(os.path.join(REAL, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["configs"] = [{"name": n, "source": "toy", "reduced": [],
                       "file": f"benchmarks/configs/{n}.json", "why": "toy"}
                      for n in CONFIGS]
    man["workloads"] = [{"name": n, "config": c["config"],
                         "traffic": c["traffic"], "chips": 1, "why": "toy"}
                        for n, c in CELLS.items()]
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if "workloads" in m:
                m["workloads"] = [LM_CELL]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return tmp
