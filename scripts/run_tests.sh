#!/usr/bin/env bash
# Tier-1 test runner.
#
#   scripts/run_tests.sh            fast suite: the static-analysis gate
#                                   (see --lint) followed by pytest with
#                                   the >10s `slow` train-loop tests
#                                   deselected
#   scripts/run_tests.sh --all      full tier-1 suite (pytest only)
#   scripts/run_tests.sh --lint     static-analysis gate only: the
#                                   serving-invariant linter over src/
#                                   plus the registry contract verifier
#                                   (`python -m repro.analysis`); non-zero
#                                   on any finding not covered by the
#                                   checked-in baseline
#                                   (src/repro/analysis/baseline.json,
#                                   empty on the merged tree) or any
#                                   contract violation; extra args forward
#                                   to the analysis CLI (--contracts-only,
#                                   --family TAG, --rules, paths...)
#   scripts/run_tests.sh --kernels  interpret-mode Pallas kernel smoke:
#                                   runs the kernel bodies (block_quant +
#                                   dequant_matmul incl. nibble-packed and
#                                   the transposed tied-embeddings variant
#                                   dequant_matmul_t) against the jnp
#                                   oracles
#   scripts/run_tests.sh --serve    serving tests only (engine, packed
#                                   serving, ragged slot reuse / reset,
#                                   chunked prefill, ring-buffer windowed
#                                   caches) — fast iteration on the
#                                   continuous-batching path
#   scripts/run_tests.sh --windowed gemma3 ring-cache parity subset only
#                                   (ring vs masked-full-cache greedy
#                                   parity, wrap-crossing prefill, cache
#                                   accounting)
#   scripts/run_tests.sh --faults   serving fault-tolerance tests only
#                                   (checkpoint integrity rejection, slot
#                                   quarantine + survivor parity, deadlines,
#                                   watchdog, step retry, dense fallback,
#                                   admission faults)
#   scripts/run_tests.sh --traffic  scheduler front-end tests only
#                                   (shared-prefix fork parity per family,
#                                   pool eviction, priority/aging admission,
#                                   submit/stream lifecycle, expiry
#                                   accounting, bit-determinism of a fixed
#                                   arrival list)
#   scripts/run_tests.sh --kv       quantised KV cache tests only (fused
#                                   flash-decode kernel parity vs oracle,
#                                   write-path bit identity, format parsing
#                                   + cache accounting, Fisher format
#                                   allocation, per-family greedy drift,
#                                   quantised prefix forks, slot-reset
#                                   isolation)
#   scripts/run_tests.sh [pytest args...]   any first argument that is not
#                                   a target flag above (e.g. -k, -x, a
#                                   test path) forwards untouched to the
#                                   fast-suite pytest invocation
#
# Works offline: tests/conftest.py shims `hypothesis` when it is missing.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [ "${1:-}" = "--all" ]; then
    shift
    exec python -m pytest -q "$@"
fi
if [ "${1:-}" = "--kernels" ]; then
    shift
    exec python -m pytest -q tests/test_kernels.py "$@"
fi
if [ "${1:-}" = "--serve" ]; then
    shift
    exec python -m pytest -q tests/test_serve.py tests/test_serve_ragged.py \
        tests/test_serve_windowed.py tests/test_serve_faults.py \
        tests/test_serve_traffic.py tests/test_serve_kv_quant.py "$@"
fi
if [ "${1:-}" = "--windowed" ]; then
    shift
    exec python -m pytest -q tests/test_serve_windowed.py "$@"
fi
if [ "${1:-}" = "--faults" ]; then
    shift
    exec python -m pytest -q tests/test_serve_faults.py "$@"
fi
if [ "${1:-}" = "--traffic" ]; then
    shift
    exec python -m pytest -q tests/test_serve_traffic.py "$@"
fi
if [ "${1:-}" = "--kv" ]; then
    shift
    exec python -m pytest -q tests/test_serve_kv_quant.py "$@"
fi
if [ "${1:-}" = "--lint" ]; then
    shift
    exec python -m repro.analysis "$@"
fi
# default fast target: static-analysis gate first (set -e aborts on red),
# then the fast pytest suite
python -m repro.analysis -q
exec python -m pytest -q -m "not slow" "$@"
