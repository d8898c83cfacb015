#!/usr/bin/env bash
# Tier-1 CI gate: flamecheck static analysis, the repo's own test suite
# (tests/test_tpu_compile.py among it: compiles for a described TPU v5e,
# no chip needed), a docs-reference check, and CPU serving smokes.  CI has
# no chip, so nothing here runs chip_smoke.py.  Run from the repo root:
#   bash scripts/ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# CPU backend everywhere (kernels in interpret mode), even where libtpu is
# installed for the TPU compile tests
export JAX_PLATFORMS=cpu

echo "== flamecheck: static analysis (strict) =="
python -m repro.analysis --strict

echo "== tier-1: pytest (incl. TPU compile tests) =="
python -m pytest -x -q

echo "== docs: reference check =="
python scripts/check_docs.py

echo "== smoke: examples/serve_e2e.py =="
python examples/serve_e2e.py

echo "== smoke: quantized + incremental history-KV pool =="
python -m repro.launch.serve --engine flame --history-cache \
    --incremental-history --pool-dtype int8 --pool-budget-mb 64 \
    --pool-slots 64 --users 4 --requests 12 --history 64 \
    --buckets 16,8 --counts 8,16 --d-model 64

echo "== smoke: FKE fused serving (impl=fused, int8 pool, drift cap) =="
python -m repro.launch.serve --engine flame --impl fused --history-cache \
    --incremental-history --extend-refresh-limit 4 --pool-dtype int8 \
    --pool-slots 64 --users 4 --requests 12 --history 64 \
    --buckets 16,8 --counts 8,16 --d-model 64

echo "== smoke: DSO v2 segment packing + deadline-aware flushing =="
python -m repro.launch.serve --engine flame --impl fused --history-cache \
    --pack-tails --deadline-ms 250 --distribution lognormal \
    --pool-slots 64 --users 4 --requests 12 --history 64 \
    --buckets 16 --counts 3,5,9,15 --d-model 64

echo "== smoke: generative top-k decode from pooled KV =="
python -m repro.launch.serve --engine flame --generate topk \
    --gen-steps 4 --beam-width 2 --pool-slots 64 --users 4 \
    --requests 12 --history 64 --buckets 16,8 --counts 8,16 --d-model 64

echo "== smoke: fused generative decode (impl=fused, int8 pool) =="
python -m repro.launch.serve --engine flame --generate topk --impl fused \
    --pack-tails --pool-dtype int8 --gen-steps 4 --beam-width 2 \
    --pool-slots 64 --users 4 --requests 12 --history 64 \
    --buckets 16,8 --counts 8,16 --d-model 64

echo "== smoke: chaos serving (fault injection, shed, degrade, watchdog) =="
python -m repro.launch.serve --engine flame --history-cache \
    --fault-spec "dispatch:0.2,stall:0.1:0.005,evict:0.15" --fault-seed 7 \
    --shed-policy tiered --slo-tier-defaults \
    "interactive=250,standard=1500,bulk=10000" \
    --slo-mix "interactive=0.3,standard=0.4,bulk=0.3" --degrade 50 \
    --watchdog-grace-ms 2000 --distribution lognormal \
    --pool-slots 64 --users 4 --requests 16 --history 64 \
    --buckets 16,8 --counts 8,16 --d-model 64

echo "== smoke: mesh-sharded serving (forced 4-device host mesh, 2x2) =="
XLA_FLAGS="--xla_force_host_platform_device_count=4" \
python -m repro.launch.serve --engine flame --history-cache --mesh 2,2 \
    --pool-slots 64 --users 4 --requests 12 --history 64 \
    --buckets 16,8 --counts 8,16 --d-model 64

echo "CI OK"
