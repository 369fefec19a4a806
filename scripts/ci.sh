#!/usr/bin/env bash
# Tier-1 CI: the full test suite plus a serving smoke run.
# Usage: bash scripts/ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1: pytest (slow-marked tests excluded; --runslow adds them) =="
python -m pytest -x -q

echo "== smoke: continuous-batching serve (open-loop) =="
python -m repro.launch.serve --smoke --preset nss_shortcut --load open \
    --requests 4 --slots 2 --prompt-len 16 --gen-len 16

echo "== smoke: paged KV engine (open-loop, shared prefix) =="
python -m repro.launch.serve --smoke --preset nss_shortcut --load open \
    --requests 4 --slots 2 --prompt-len 16 --gen-len 16 \
    --kv paged --block-size 8 --shared-prefix-len 8

echo "== smoke: slotted-vs-paged token identity (incl. chunked prefill,"
echo "          the two-tier swap/warm-start engines under pool pressure,"
echo "          and speculative decode vs its plain-decode twins),"
echo "          every engine traced + schema-validated; the bf16 matrix is"
echo "          the bit-identical control for the int8 tolerance cells"
echo "          (quantized lifecycle + teacher-forced flip gate), plus the"
echo "          fleet cells (1-replica identity, disaggregated-vs-colocated"
echo "          handoffs, shared-prefix-store warm hit) =="
python scripts/paged_smoke.py --chunked --swap --spec-decode --async-swap --fleet --trace --kv-dtype int8

echo "== smoke: sharded serving (2 virtual devices, 1x2 data,model mesh, "
echo "          two-phase + chunked + swap/warm-start + spec engines,"
echo "          plus the int8 cells over sharded scale tables) =="
python scripts/paged_smoke.py --chunked --swap --spec-decode --async-swap --fleet --mesh 1,2 --trace --kv-dtype int8

echo "== smoke: chunked-prefill serve launcher (open-loop) =="
python -m repro.launch.serve --smoke --preset nss_shortcut --load open \
    --requests 4 --slots 2 --prompt-len 16 --gen-len 16 \
    --kv paged --block-size 8 --chunked --budget 16

echo "== smoke: swap-preemption serve launcher (pool pressure, host tier) =="
python -m repro.launch.serve --smoke --preset nss_shortcut --load closed \
    --requests 4 --slots 2 --prompt-len 8 --gen-len 12 --decode-steps 4 \
    --kv paged --block-size 8 --num-blocks 5 --preempt swap

echo "== smoke: quantized-KV serve launcher (int8 blocks, swap pressure) =="
python -m repro.launch.serve --smoke --preset nss_shortcut --load closed \
    --requests 4 --slots 2 --prompt-len 8 --gen-len 12 --decode-steps 4 \
    --kv paged --block-size 8 --num-blocks 5 --preempt swap --kv-dtype int8

echo "== smoke: speculative-decode serve launcher (n-gram drafts) =="
python -m repro.launch.serve --smoke --preset nss_shortcut --load closed \
    --requests 4 --slots 2 --prompt-len 18 --gen-len 14 --decode-steps 3 \
    --kv paged --block-size 8 --spec-decode ngram --spec-width 6

echo "== smoke: fleet serve launcher (2 replicas, disaggregated) =="
python -m repro.launch.fleet --smoke --preset nss_shortcut --load open \
    --requests 4 --slots 2 --prompt-len 16 --gen-len 8 --decode-steps 4 \
    --replicas 2 --disaggregate 1 --block-size 8

echo "== smoke: telemetry — traced chunked launcher + trace_summary =="
CI_TRACE_DIR="$(mktemp -d)"
trap 'rm -rf "$CI_TRACE_DIR"' EXIT
python -m repro.launch.serve --smoke --preset nss_shortcut --load open \
    --requests 4 --slots 2 --prompt-len 16 --gen-len 16 \
    --kv paged --block-size 8 --chunked --budget 16 \
    --trace "$CI_TRACE_DIR/trace.json" \
    --metrics "$CI_TRACE_DIR/metrics.prom" --log-interval 0.5
python scripts/trace_summary.py "$CI_TRACE_DIR/trace.json"
grep -q '^engine_steps_total' "$CI_TRACE_DIR/metrics.prom"

echo "CI OK"
