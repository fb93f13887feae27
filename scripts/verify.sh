#!/usr/bin/env bash
# One-command verification gate: the ROADMAP tier-1 test recipe followed by
# a tiny CPU bench whose row feeds the perf-regression sentinel
# (python -m rdfind_tpu.obs.sentinel --check).
#
# Usage:
#   scripts/verify.sh                  # tests + tiny bench + sentinel gate
#   VERIFY_SKIP_BENCH=1 scripts/verify.sh   # tests only (fast pre-commit)
#   BENCH_HISTORY=/path/h.jsonl scripts/verify.sh   # custom history file
#
# Exit codes: the tier-1 suite's rc when tests fail; 1 when the tiny bench
# dies or the sentinel flags a regression; 0 otherwise.  The sentinel
# compares the newest history row against the trailing rows with the SAME
# (n_cores, backend, knob-set) key, so a laptop and CI keep separate
# baselines in one file; the first run on a fresh machine passes by default
# (no baseline yet).

set -o pipefail
cd "$(dirname "$0")/.."

echo "== native ingest engine: build from source =="
# The checked-in .so must never go stale against the grown C API: rebuild
# from source when a compiler is present (a build FAILURE is fatal — it
# means rdfind_native.cpp no longer compiles); skip gracefully on
# compiler-less boxes (the Python fallback path still runs under tier-1,
# and io/native.py's _bind AttributeErrors a stale .so into that fallback).
if command -v "${CXX:-g++}" >/dev/null 2>&1; then
    if ! make -C native; then
        echo "verify: native build FAILED" >&2
        exit 1
    fi
else
    echo "verify: no C++ compiler (${CXX:-g++}); native build skipped"
fi

echo "== tier-1 test suite (ROADMAP recipe) =="
rm -f /tmp/_t1.log
timeout -k 10 2400 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)"
if [ "$rc" -ne 0 ]; then
    echo "verify: tier-1 suite FAILED (rc=$rc)" >&2
    exit "$rc"
fi

echo "== run console smoke (--console-port 0) =="
# Tiny CPU run with an ephemeral console port: fetch /metrics + /progress
# while it executes and assert both parse.  Skips itself (exit 0) when the
# sandbox forbids loopback listening; VERIFY_SKIP_CONSOLE=1 skips outright.
if [ "${VERIFY_SKIP_CONSOLE:-0}" = "1" ]; then
    echo "verify: console smoke skipped (VERIFY_SKIP_CONSOLE=1)"
elif ! JAX_PLATFORMS=cpu timeout -k 10 600 python scripts/console_smoke.py; then
    echo "verify: console smoke FAILED" >&2
    exit 1
fi

echo "== rung-3 kernel parity (planes {8,4,2} x emit on/off) =="
# Every knob combination must produce bit-identical containment outputs and
# dense CIND pair sets on a tiny planted workload — knobs move schedules,
# never results.  VERIFY_SKIP_KERNEL_RUNGS=1 opts out.
if [ "${VERIFY_SKIP_KERNEL_RUNGS:-0}" = "1" ]; then
    echo "verify: kernel-rung parity skipped (VERIFY_SKIP_KERNEL_RUNGS=1)"
elif ! JAX_PLATFORMS=cpu timeout -k 10 600 python scripts/kernel_rung_parity.py; then
    echo "verify: kernel-rung parity FAILED" >&2
    exit 1
fi

echo "== sharded half-approx parity (RDFIND_SHARDED_HALF_APPROX on/off) =="
# The distributed two-round count-min cut must be bit-identical to the
# exact path on a tiny planted workload (mesh 8 flat + 2-host hierarchical
# sketch reduce, which must also cut DCN bytes).  The knob only moves
# bytes, never results.  VERIFY_SKIP_HALF_APPROX=1 opts out.
if [ "${VERIFY_SKIP_HALF_APPROX:-0}" = "1" ]; then
    echo "verify: half-approx parity skipped (VERIFY_SKIP_HALF_APPROX=1)"
elif ! JAX_PLATFORMS=cpu timeout -k 10 900 python scripts/half_approx_parity.py; then
    echo "verify: half-approx parity FAILED" >&2
    exit 1
fi

echo "== elastic-resume parity (preempt at mesh 8, resume at mesh 2) =="
# Mesh-portable snapshots: a preempted run resumed on a different mesh size
# must replay its committed passes and stay bit-identical to a clean run
# (both shrink and grow directions).  VERIFY_SKIP_ELASTIC=1 opts out.
if [ "${VERIFY_SKIP_ELASTIC:-0}" = "1" ]; then
    echo "verify: elastic-resume parity skipped (VERIFY_SKIP_ELASTIC=1)"
elif ! JAX_PLATFORMS=cpu timeout -k 10 900 python scripts/elastic_resume_parity.py; then
    echo "verify: elastic-resume parity FAILED" >&2
    exit 1
fi

echo "== integrity parity (digests on/off, planted flip, verified resume) =="
# The integrity plane must never change results: knob on/off bit-identical,
# a planted bit flip detected+named+repaired, and an 8 -> 2 resume with
# every snapshot pass digest-verified.  VERIFY_SKIP_INTEGRITY=1 opts out.
if [ "${VERIFY_SKIP_INTEGRITY:-0}" = "1" ]; then
    echo "verify: integrity parity skipped (VERIFY_SKIP_INTEGRITY=1)"
elif ! JAX_PLATFORMS=cpu timeout -k 10 900 python scripts/integrity_parity.py; then
    echo "verify: integrity parity FAILED" >&2
    exit 1
fi

echo "== delta parity (base -> planted insert+delete batch -> --delta) =="
# Incremental discovery must be bit-identical to from-scratch on the updated
# dataset, chain its certificate onto the base run, and actually reuse
# passes (proportional-to-change).  VERIFY_SKIP_DELTA=1 opts out.
if [ "${VERIFY_SKIP_DELTA:-0}" = "1" ]; then
    echo "verify: delta parity skipped (VERIFY_SKIP_DELTA=1)"
elif ! JAX_PLATFORMS=cpu timeout -k 10 900 python scripts/delta_parity.py; then
    echo "verify: delta parity FAILED" >&2
    exit 1
fi

echo "== watchdog parity (injected wedge -> bounded recoverable preemption) =="
# A wedged collective must convert to Preempted within the watchdog timeout
# (never an indefinite stall), flush committed passes, and the re-entered
# run must resume bit-identical.  VERIFY_SKIP_WATCHDOG=1 opts out.
if [ "${VERIFY_SKIP_WATCHDOG:-0}" = "1" ]; then
    echo "verify: watchdog parity skipped (VERIFY_SKIP_WATCHDOG=1)"
elif ! JAX_PLATFORMS=cpu timeout -k 10 900 python scripts/watchdog_parity.py; then
    echo "verify: watchdog parity FAILED" >&2
    exit 1
fi

echo "== serve parity (index answers vs discovery output + hot swap) =="
# The mmap'd CIND index must answer bit-consistently with the run that
# wrote it (all four strategies), and a delta-committed generation must
# hot-swap with answers identical to a from-scratch index (corrupt
# candidates refused by section name).  VERIFY_SKIP_SERVE=1 opts out.
if [ "${VERIFY_SKIP_SERVE:-0}" = "1" ]; then
    echo "verify: serve parity skipped (VERIFY_SKIP_SERVE=1)"
elif ! JAX_PLATFORMS=cpu timeout -k 10 900 python scripts/serve_parity.py; then
    echo "verify: serve parity FAILED" >&2
    exit 1
fi

echo "== serve observability parity (live admin plane: /metrics, SLOs) =="
# The live serving process's telemetry must be exact (Prometheus scrape
# counts == fired requests, malformed traffic counted), planted slow/stale
# conditions must burn the NAMED SLO on /slo + heartbeat + tpu_watch, and
# answers must be byte-identical with obs off.  VERIFY_SKIP_SERVE_OBS=1
# opts out.
if [ "${VERIFY_SKIP_SERVE_OBS:-0}" = "1" ]; then
    echo "verify: serve obs parity skipped (VERIFY_SKIP_SERVE_OBS=1)"
elif ! JAX_PLATFORMS=cpu timeout -k 10 900 python scripts/serve_obs_parity.py; then
    echo "verify: serve obs parity FAILED" >&2
    exit 1
fi

if [ "${VERIFY_SKIP_BENCH:-0}" = "1" ]; then
    echo "verify: tier-1 green; bench + sentinel skipped (VERIFY_SKIP_BENCH=1)"
    exit 0
fi

echo "== tiny bench -> BENCH_HISTORY -> regression sentinel =="
hist="${BENCH_HISTORY:-BENCH_HISTORY.jsonl}"
if ! JAX_PLATFORMS=cpu \
     BENCH_TRIPLES="${VERIFY_BENCH_TRIPLES:-400}" BENCH_MIN_SUPPORT=2 \
     BENCH_PIPELINE_TRIPLES=600 BENCH_EXCHANGE_TRIPLES=600 \
     BENCH_HISTORY="$hist" \
     timeout -k 10 1800 python bench.py > /tmp/_verify_bench.json; then
    echo "verify: tiny bench FAILED (see /tmp/_verify_bench.json)" >&2
    exit 1
fi
if ! python -m rdfind_tpu.obs.sentinel --check --history "$hist"; then
    exit 1
fi

echo "== tiny delta bench -> BENCH_HISTORY -> regression sentinel =="
# Incremental-discovery speedup rows (delta_speedup_*, frac_passes_rerun):
# the proportional-to-change claim, regression-gated like every other
# metric.  Appends to the SAME history file; the rows carry a distinct
# workload stamp so output digests never cross-compare with bench.py's.
if ! JAX_PLATFORMS=cpu \
     BENCH_DELTA_TRIPLES="${VERIFY_BENCH_DELTA_TRIPLES:-1200}" \
     BENCH_HISTORY="$hist" \
     timeout -k 10 1800 python bench_delta.py > /tmp/_verify_bench_delta.json; then
    echo "verify: tiny delta bench FAILED (see /tmp/_verify_bench_delta.json)" >&2
    exit 1
fi
if ! python -m rdfind_tpu.obs.sentinel --check --history "$hist"; then
    exit 1
fi

echo "== serve bench -> BENCH_HISTORY -> regression sentinel =="
# Query-plane rows (serve_qps / serve_open_ms / serve_p99_us): the mmap'd
# index's open must stay O(header) and holds() must stay >= the QPS floor;
# regressions gate like kernel regressions.
if ! JAX_PLATFORMS=cpu \
     BENCH_HISTORY="$hist" \
     timeout -k 10 900 python bench_serve.py > /tmp/_verify_bench_serve.json; then
    echo "verify: serve bench FAILED (see /tmp/_verify_bench_serve.json)" >&2
    exit 1
fi
python -m rdfind_tpu.obs.sentinel --check --history "$hist"
