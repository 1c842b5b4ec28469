#!/usr/bin/env bash
# Tier-1 gate: the ROADMAP verify command + the dispatch-overhead smoke,
# run STRICTLY SERIALLY: on a host with few cores a concurrent
# pytest/bench process starves multi-device CPU collective rendezvous
# into 40 s-timeout aborts — so this script never backgrounds a stage,
# and it FAILS LOUDLY on any stage rather than degrading.
#
#   ./ci/tier1.sh            # tier-1 suite + dispatch smoke
#   TIER1_OBS=1 ./ci/tier1.sh  # + MXNET_OBS=1 telemetry smoke lane
#   TIER1_CHAOS=1 ./ci/tier1.sh  # + fault-injection recovery smoke lane
#
# (The full matrix — examples smoke, driver contract, bench — stays in
# ci/run.sh; this is the cheap gate every PR must keep green.)

set -uo pipefail
cd "$(dirname "$0")/.."

echo "==== [tier1] pytest tests/ -m 'not slow' (870 s budget) ===="
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider \
        -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
if [ $rc -ne 0 ]; then
    echo "[tier1] FAIL: test suite rc=$rc"
    exit $rc
fi

echo "==== [tier1] paged megakernel lane (MXNET_PAGED_DECODE_PALLAS=1, interpret mode) ===="
# the batched-lane Pallas decode/verify kernel must be a DROP-IN: the
# kernel parity matrix plus the whole existing paged-serving contract
# suite re-run with the flag forced on (streams bit-exact vs solo
# generate(), spec/chunk/pipeline composition unchanged). Interpret
# mode on CPU — the same kernel code the chip compiles.
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu MXNET_PAGED_DECODE_PALLAS=1 \
        python -m pytest tests/test_paged_kernel.py tests/test_serving_paged.py \
            -q -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly; then
    echo "[tier1] FAIL: paged megakernel lane"
    exit 1
fi

echo "==== [tier1] dispatch-overhead smoke (benchmark/opperf.py --dispatch) ===="
# serial, after the suite has fully exited; a wedged/slow ladder is a
# real regression signal, not something to skip
if ! env JAX_PLATFORMS=cpu python benchmark/opperf.py --dispatch; then
    echo "[tier1] FAIL: dispatch smoke"
    exit 1
fi

if [ "${TIER1_OBS:-0}" = "1" ]; then
    echo "==== [tier1] observability smoke (MXNET_OBS=1 train step + trace validation) ===="
    # opt-in lane: one instrumented Trainer.step; the emitted chrome
    # trace JSON must parse and carry the step-phase spans + collective
    # counters (tools/obs_smoke.py exits non-zero otherwise)
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/obs_smoke.py; then
        echo "[tier1] FAIL: observability smoke"
        exit 1
    fi

    echo "==== [tier1] per-operator attribution smoke (block scopes in trace) ===="
    # the two-block conv+dense workload must emit ops.* per-scope
    # gauges naming both blocks, with >=90% of the compiled step's
    # flops and HBM bytes attributed (docs/OBSERVABILITY.md
    # "Per-operator attribution")
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/obs_smoke.py --ops; then
        echo "[tier1] FAIL: per-operator attribution smoke"
        exit 1
    fi

    echo "==== [tier1] perf-regression sentinel (obs_regression vs committed baseline) ===="
    # same workload, diffed against ci/obs_baseline.json with
    # per-metric tolerances; a PR that grows the bytes a block moves
    # past tolerance fails HERE with the scope named, not weeks later
    # as a slow BENCH row. Intentional change? re-commit the baseline:
    #   python tools/obs_regression.py --baseline ci/obs_baseline.json --update
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/obs_regression.py \
            --baseline ci/obs_baseline.json; then
        echo "[tier1] FAIL: perf-regression sentinel"
        exit 1
    fi

    echo "==== [tier1] megakernel perf sentinel (paged Pallas scopes vs baseline) ===="
    # the PR 16 paged decode/verify megakernel, forced on via
    # MXNET_PAGED_DECODE_PALLAS=1 (interpret mode on CPU), must keep
    # its paged_decode_kernel / paged_verify_kernel flop/byte rows
    # within tolerance of the baseline's "kernels" section. Refresh:
    #   python tools/obs_regression.py --baseline ci/obs_baseline.json \
    #       --kernels --update
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 MXNET_PAGED_DECODE_PALLAS=1 \
            python tools/obs_regression.py \
            --baseline ci/obs_baseline.json --kernels; then
        echo "[tier1] FAIL: megakernel perf sentinel"
        exit 1
    fi

    echo "==== [tier1] performance-archive smoke (profile store + timeline + --history) ===="
    # ISSUE 18: two synthetic runs through the CRC-framed profile
    # store must merge into ONE timeline (perf_timeline renders both
    # runs), and obs_regression --history must flag the second run's
    # injected 2x per-scope slowdown by name against the rolling
    # window. The committed-baseline sentinel above is unchanged —
    # --history guards drift the snapshot diff cannot see.
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/obs_smoke.py --store; then
        echo "[tier1] FAIL: performance-archive smoke"
        exit 1
    fi

    echo "==== [tier1] goodput-ledger smoke (wall accounting + badput taxonomy) ===="
    # ISSUE 19: a deterministic single-rank run with one injected
    # stall per badput class (chaos io.read delay, detector-narrated
    # recompile, checkpoint save) must come back with >=95% of the
    # wall attributed, every injected category within 20% of its
    # injected duration, the mxnet_obs_goodput_* Prometheus series
    # exported, and tools/obs_goodput.py --check green on the dumped
    # trace (docs/OBSERVABILITY.md "Goodput & critical path")
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/obs_smoke.py --goodput; then
        echo "[tier1] FAIL: goodput-ledger smoke"
        exit 1
    fi

    echo "==== [tier1] critical-path smoke (2-process merged-trace attribution) ===="
    # the merged 2-rank trace's per-step lattice walk must name which
    # rank+phase bounds the step (the cross-rank critical path);
    # serial like everything else on the 1-core host
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/obs_smoke.py --goodput --nproc 2; then
        echo "[tier1] FAIL: critical-path smoke"
        exit 1
    fi

    echo "==== [tier1] distributed observability smoke (2-process gloo merge) ===="
    # two gloo workers train against dist_tpu_sync (clock-anchor
    # handshake at kvstore creation), dump rank-local traces, and the
    # parent merges them — the merged chrome trace must carry BOTH
    # rank lanes on the aligned timebase AND the bucket-wise merged
    # trainer.step_ms histogram (per-rank counts sum; obs_smoke exits
    # non-zero otherwise). Serial like everything else on the 1-core
    # host.
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/obs_smoke.py --nproc 2; then
        echo "[tier1] FAIL: distributed observability smoke"
        exit 1
    fi

    echo "==== [tier1] serving observability smoke (request lifecycle + live scrape) ===="
    # a pipelined ContinuousBatcher run, scraped live mid-run, must
    # land the full request lifecycle in the emitted trace: dispatch/
    # sync/patch/prefill/queue-wait spans, complete per-request flow
    # chains, TTFT/ITL/e2e/queue histograms (mergeable bucket states
    # included), occupancy/goodput gauges, and /metrics + /healthz
    # must answer with the serving series (docs/OBSERVABILITY.md
    # "Serving observability")
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/obs_smoke.py --serving; then
        echo "[tier1] FAIL: serving observability smoke"
        exit 1
    fi
fi

if [ "${TIER1_CHAOS:-0}" = "1" ]; then
    echo "==== [tier1] chaos smoke (one injected fault per class, recovery asserted) ===="
    # docs/ROBUSTNESS.md recovery matrix, exercised end to end: NaN
    # grad -> step guard skip (weights bit-identical), io read error ->
    # retry, serving dispatch failure -> lane free + requeue
    # (bit-exact streams), collective hang -> watchdog post-mortem +
    # emergency checkpoint + abort(43), SIGTERM -> emergency save
    # (exit 143), hard crash -> resume-from-latest with a bit-exact
    # loss trajectory. Serial like everything else on the 1-core host.
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/chaos_smoke.py; then
        echo "[tier1] FAIL: chaos smoke"
        exit 1
    fi

    echo "==== [tier1] elastic smoke (rank kill -> shrink -> bit-exact resume -> regrow) ===="
    # docs/ROBUSTNESS.md "Elastic recovery", end to end on the CPU
    # mesh: one injected rank kill in a 2-process gloo job; the
    # supervisor (tools/elastic_launch.py) must shrink to world 1,
    # the survivor's post-shrink loss trajectory must be BIT-exact vs
    # a clean world-1 run resumed from the same shard set with zero
    # skipped/replayed samples, the world must regrow to 2, and the
    # merged trace must carry elastic.time_to_recovery_ms. Serial like
    # everything else on the 1-core host.
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/chaos_smoke.py --elastic; then
        echo "[tier1] FAIL: elastic smoke"
        exit 1
    fi

    echo "==== [tier1] overload smoke (priority storm -> preempt/shed/expire -> breaker recovery) ===="
    # docs/ROBUSTNESS.md "Serving overload & graceful degradation",
    # end to end: a seeded mixed-priority burst at ~4x KV-block
    # capacity over a 2-replica router while a chaos spec kills r1
    # mid-storm. Must complete with zero deadlocks and zero leaked
    # blocks at quiesce, only priority-0 work shed/expired, the
    # brownout ladder climbing and recovering, r1 returning through
    # the breaker's HALF_OPEN canary, and every completed stream
    # bit-exact vs solo generate().
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/chaos_smoke.py --overload; then
        echo "[tier1] FAIL: overload smoke"
        exit 1
    fi

    echo "==== [tier1] integrity smoke (one injected flip per corruption class) ===="
    # docs/ROBUSTNESS.md "Silent corruption", end to end: a gradient-
    # bucket flip caught by the replay audit (quarantine exit 46 with
    # bucket evidence, then a bit-exact resume from the last verified
    # checkpoint), a replicated-weight flip on one of three gloo ranks
    # named by the fingerprint majority vote, a checkpoint byte flip
    # refused by name with fallback to the verified ancestor, and a
    # recordio record flip named (path, record index) — transient
    # retried clean, at-rest exhausting into the enriched IOError.
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/chaos_smoke.py --integrity; then
        echo "[tier1] FAIL: integrity smoke"
        exit 1
    fi

    echo "==== [tier1] memory-pressure smoke (one injected OOM per recovery path) ===="
    # docs/ROBUSTNESS.md "Memory pressure", end to end on the CPU
    # mesh: a deterministic RESOURCE_EXHAUSTED at each of the four
    # sites — trainer.step (accum re-lower at 2x, global-batch loss
    # trajectory preserved and deterministic), serving.dispatch (pool
    # shrink-and-retry, streams bit-exact, zero leaked blocks),
    # kv.pool.grow (a failed grow degrades capacity instead of
    # crashing), checkpoint.snapshot (serial-gather retry, the
    # committed checkpoint reloads bit-exact). No process may die.
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/chaos_smoke.py --oom; then
        echo "[tier1] FAIL: memory-pressure smoke"
        exit 1
    fi

    echo "==== [tier1] durable-serving smoke (kill-9 journal replay + rollout rollback) ===="
    # docs/ROBUSTNESS.md "Durable serving & zero-downtime rollout",
    # end to end: a hard kill (exit 9, no cleanup) at a journal
    # commit point under paged x spec x pipeline (greedy AND
    # sampled), replayed BIT-exactly by a fresh batcher's recover();
    # torn-tail and CRC-flipped records skipped with named evidence
    # while the records behind them survive; a chaos-failed canary
    # rolling the whole fleet back to the prior verified fingerprint
    # with zero dropped in-flight requests; and a hot-swap whose
    # manifest fingerprint mismatches refused before touching a
    # replica.
    if ! env JAX_PLATFORMS=cpu MXNET_OBS=1 python tools/chaos_smoke.py --durable; then
        echo "[tier1] FAIL: durable-serving smoke"
        exit 1
    fi
fi

echo "[tier1] gate PASSED"
