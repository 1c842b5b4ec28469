#!/usr/bin/env bash
# One-command CI gate (round-2 verdict item 10).
#
# Reference counterpart: ci/docker/runtime_functions.sh (unittest_ubuntu_*
# stages run by the Jenkins matrix). Here one script gates the tree:
#
#   ./ci/run.sh            # full gate: suite + multichip dryrun + bench
#   ./ci/run.sh quick      # suite only (fail-fast)
#
# Stages:
#   1. pytest tests/ on the 8-device virtual CPU mesh (includes the
#      examples smoke set, tests/test_examples_tools.py)
#   2. driver contract: dryrun_multichip(8) + entry() compile check
#   3. bench.py on the attached chip; on a CPU-only host the stage is
#      SKIPPED by name (bench.py refuses to run without an accelerator,
#      and a refusal is not a pass)
#
# Any stage failing fails the gate.

set -uo pipefail
cd "$(dirname "$0")/.."

MODE="${1:-full}"
FAILED=0

stage() {
    echo "==== [ci] $1 ===="
}

stage "pytest (8-device virtual CPU mesh)"
# nightly-class large-tensor tests self-enable when the host has the
# RAM (the gate lives in tests/test_large_tensor.py — one source of
# truth; MXNET_RUN_LARGE_TENSOR=1/0 forces either way)
if ! python -m pytest tests/ -q -x --durations=10; then
    echo "[ci] FAIL: test suite"
    exit 1
fi

if [ "$MODE" = "quick" ]; then
    echo "[ci] quick gate PASSED"
    exit 0
fi

stage "driver contract: dryrun_multichip(8) + entry()"
if ! python __graft_entry__.py; then
    echo "[ci] FAIL: __graft_entry__ contract"
    FAILED=1
fi

stage "driver contract: dryrun_multichip(16) (ep AND dp both sharded)"
if ! python __graft_entry__.py 16; then
    echo "[ci] FAIL: __graft_entry__ 16-device contract"
    FAILED=1
fi

if python -c 'import sys, jax; sys.exit(jax.devices()[0].platform == "cpu")'; then
    stage "bench (attached accelerator)"
    if ! python bench.py; then
        echo "[ci] FAIL: bench.py"
        FAILED=1
    fi
else
    echo "==== [ci] bench SKIPPED: no accelerator on this host ===="
fi

if [ $FAILED -ne 0 ]; then
    echo "[ci] gate FAILED"
    exit 1
fi
echo "[ci] gate PASSED"
