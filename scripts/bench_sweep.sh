#!/usr/bin/env bash
# Flagship-bench sweep on a machine with a TPU. Each run prints bench.py's
# one-JSON-line result; bench.py itself exits non-zero when it finds no
# accelerator, so there is no separate device probe. The runs are
# sequential processes (one process owns the chip at a time). Through the
# chip tool: chiprun -- bash scripts/bench_sweep.sh chiprun_out/sweep
#
# Usage: bash scripts/bench_sweep.sh [outdir]   (default ./bench_results)
set -uo pipefail
cd "$(dirname "$0")/.."
out="${1:-bench_results}"
mkdir -p "$out"

run() { # name, extra bench.py flags...
    local name="$1"; shift
    echo "== $name: bench.py $* =="
    timeout 2400 python bench.py --rounds 2 "$@" \
        >"$out/$name.json" 2>"$out/$name.err"
    cat "$out/$name.json"
}

# 1. current default (lanes K8, bf16 convs) -- reproduces the 83.4 rph row
run lanes_k8 --client_chunk 8
# 2. halve HBM data residency (gather traffic) on top of it
run lanes_k8_data_bf16 --client_chunk 8 --device_dtype bf16
# 3. more lanes: K=12 (K=16 was pathological; bisect the knee)
run lanes_k12 --client_chunk 12
# 4. op-level profile of the default config for the MFU breakdown
run lanes_k8_profile --client_chunk 8 --profile_dir "$out/trace"

echo "sweep done -> $out/"
