#!/usr/bin/env bash
# Tier-1 test entry point (ROADMAP.md): run from the repo root.
#
#   scripts/ci.sh             full tier-1 suite
#   scripts/ci.sh fast        quick subset (-m fast) for per-push feedback
#   scripts/ci.sh bench       agg micro-bench smoke + comm-efficiency grid
#                             + buffered-async throughput grid + the
#                             training-throughput smoke: writes
#                             BENCH_agg.json, BENCH_comm.json,
#                             BENCH_async.json and BENCH_train.smoke.json
#                             and FAILS if the pruned selection network
#                             is slower than 0.7x the XLA-sort median
#                             baseline at m=32, if any comm cell violates
#                             its (codec-scaled) core/theory.py bound, if
#                             tau>=4 local-update rounds save less than
#                             4x bytes vs tau=1 under ALIE, if int8
#                             compression saves less than 3x bytes vs
#                             uncompressed at matched error under ALIE,
#                             if any async cell
#                             breaks its effective-m bound, if the
#                             k/m=0.5 buffer closes rounds < 2x faster
#                             than sync under heavy-tailed latency at
#                             matched clean error, if any trainer-window
#                             HLO structure check fails (collective
#                             counts, xdevice_steps byte scaling, no host
#                             transfer in the scan window), or if the
#                             COMMITTED BENCH_train.json stops showing
#                             <10% robust-aggregation step-time overhead
#                             vs plain data-parallel at the largest
#                             config (run.py --gate-train; the committed
#                             full grid is regenerated offline with
#                             python -m benchmarks.train_throughput
#                             --json BENCH_train.json — don't clobber it
#                             with the smoke artifact), or if the
#                             COMMITTED BENCH_serve.json stops showing
#                             <15% robust-cadence tokens/s overhead vs
#                             serve-only at the largest slot count
#                             (run.py --gate-serve; regenerated offline
#                             with python -m benchmarks.serve_throughput
#                             --json BENCH_serve.json), or if any serve
#                             cell recompiled mid-stream
#   scripts/ci.sh serve       serving smoke: continuous-batching engine +
#                             robust continual adaptation end-to-end
#                             twice on the debug mesh, FAILS unless both
#                             runs print the same "final iterate sha256"
#                             line (seeded traffic, poisoned feedback,
#                             robust rounds, hot-swaps — all
#                             bit-deterministic)
#   scripts/ci.sh docs        registry-generated README tables
#                             (python -m repro.docs --check): FAILS if the
#                             attack/aggregator/strategy/compression/policy
#                             tables drifted from the registries
#                             (regenerate: python -m repro.docs)
#   scripts/ci.sh robustness  attack x aggregator x alpha scenario matrix
#                             plus the compressed-payload codec cells,
#                             the buffered-async stale-exploit cells and
#                             the poisoned-feedback serving cells
#                             (repro.attacks.matrix --smoke): writes
#                             ROBUSTNESS.smoke.json (the committed
#                             ROBUSTNESS.json is the full grid — don't
#                             clobber it) and FAILS if any gated cell's
#                             final error violates its core/theory.py
#                             bound (sync rate, or the effective-m async
#                             rate for buffered cells)
#   scripts/ci.sh resume      kill-and-resume smoke on the fed CLI: run 6
#                             rounds uninterrupted, then 4 rounds with
#                             --ckpt-dir (the "kill") and --resume to 6,
#                             and FAIL unless both print the same
#                             "final iterate sha256" line (the
#                             rounds.engine bit-for-bit resume contract,
#                             DESIGN.md §Round engine)
#   scripts/ci.sh lint        ruff check (F + E9 repo-wide, pyproject.toml)
#                             + ruff format check on scripts/ — requires
#                             ruff on PATH; the GitHub lint job installs it
#
# The GitHub workflow enforces the pass floor and failure ceiling.
set -uo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

if [ "${1:-}" = "fast" ]; then
    exec python -m pytest -q -m fast
fi
if [ "${1:-}" = "bench" ]; then
    # agg timings are --smoke (wall-clock budget); the comm and async
    # grids are deterministic statistics, so they run their committed
    # full configs for clean per-cell diffs against the BENCH_comm.json
    # and BENCH_async.json baselines
    python -m benchmarks.run --only agg --json BENCH_agg.json --smoke --gate-agg || exit 1
    python -m benchmarks.run --only comm --json-comm BENCH_comm.json || exit 1
    python -m benchmarks.run --only async --json-async BENCH_async.json || exit 1
    # train: the smoke grid re-verifies the HLO structure gates on this
    # host; the <10% overhead gate is a deterministic re-check of the
    # COMMITTED full-grid numbers (immune to runner wall-clock noise)
    python -m benchmarks.run --only train --smoke \
        --json-train BENCH_train.smoke.json --gate-train BENCH_train.json || exit 1
    # serve: same split — the smoke grid re-verifies the no-recompile
    # contract live; the <15% robust-cadence overhead gate re-checks the
    # COMMITTED BENCH_serve.json (regenerated offline with
    # python -m benchmarks.serve_throughput --json BENCH_serve.json)
    exec python -m benchmarks.run --only serve --smoke \
        --json-serve BENCH_serve.smoke.json --gate-serve BENCH_serve.json
fi
if [ "${1:-}" = "docs" ]; then
    exec python -m repro.docs --check
fi
if [ "${1:-}" = "robustness" ]; then
    exec python -m repro.attacks.matrix --smoke --json ROBUSTNESS.smoke.json
fi
if [ "${1:-}" = "resume" ]; then
    tmp="$(mktemp -d)"
    trap 'rm -rf "$tmp"' EXIT
    common="--clients 64 --cohort 32 --chunk 8 --dim 12 --rounds 6 --lr 0.3
            --alpha 0.25 --attack alie,sign_flip --schedule greedy
            --method median"
    full=$(python -m repro.fed.run $common | grep 'final iterate sha256') || exit 1
    python -m repro.fed.run $common --rounds 4 --ckpt-dir "$tmp/ck" \
        >/dev/null || exit 1
    res=$(python -m repro.fed.run $common --ckpt-dir "$tmp/ck" --resume \
        | grep 'final iterate sha256') || exit 1
    echo "uninterrupted: $full"
    echo "resumed:       $res"
    if [ "$full" != "$res" ]; then
        echo "resume smoke FAILED: final iterate digests differ" >&2
        exit 1
    fi
    echo "resume smoke OK (bit-for-bit)"
    exit 0
fi
if [ "${1:-}" = "serve" ]; then
    # serving smoke: run the continuous-batching engine + robust
    # continual adaptation end-to-end TWICE on the debug mesh and FAIL
    # unless both print the same "final iterate sha256" line — the
    # traffic, feedback corruption, robust rounds, and hot-swaps are all
    # seeded, so the served iterate is bit-deterministic
    common="--smoke --arch llama3_2_3b --requests 24 --slots 3 --shards 2
            --num-users 1000 --alpha 0.5 --attack feedback_flip
            --adapt-every 8 --batch-per-shard 2 --method median"
    one=$(python -m repro.serve.run $common | grep 'final iterate sha256') || exit 1
    two=$(python -m repro.serve.run $common | grep 'final iterate sha256') || exit 1
    echo "run 1: $one"
    echo "run 2: $two"
    if [ "$one" != "$two" ]; then
        echo "serve smoke FAILED: final iterate digests differ" >&2
        exit 1
    fi
    echo "serve smoke OK (bit-deterministic)"
    exit 0
fi
if [ "${1:-}" = "lint" ]; then
    if ! command -v ruff >/dev/null 2>&1; then
        echo "scripts/ci.sh lint: ruff not installed (pip install ruff)" >&2
        exit 1
    fi
    ruff check . || exit 1
    exec ruff format --check scripts
fi
exec python -m pytest -q
