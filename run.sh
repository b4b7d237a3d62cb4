#!/usr/bin/env bash
# Reproducible perf environment for benchmarks and CI (ISSUE 9).
#
# Every recorded number (BENCH_score.json, roofline tuning sweeps) and the
# perf-regression CI step run under THIS wrapper so two runs differ only in
# the code, never in the allocator, XLA runtime knobs, device layout or the
# LCS diagonal dtype:
#
#   tcmalloc         LD_PRELOADed when present — the glibc allocator's
#                    page-level churn adds multi-percent noise to the
#                    gather-heavy score stage.  Gated on file existence:
#                    absent (as in the slim CI image) the run proceeds
#                    on glibc, it is never an error.
#   XLA_FLAGS        under JAX_PLATFORMS=cpu, fake an 8-device host
#                    platform so the shard_map paths (sharded parity
#                    tests, the overlap benchmark section) exercise real
#                    collectives.  An inherited XLA_FLAGS wins; without
#                    JAX_PLATFORMS=cpu the run keeps the machine's own
#                    devices (an accelerator is never forced onto the
#                    host platform).
#   REPRO_LCS_DTYPE  pinned (default int8) so the wavefront's diagonal
#                    carry dtype is an explicit, recorded choice rather
#                    than the env-probe default.  Inherited values win.
#
# Usage:  ./run.sh <python args...>        e.g.
#         ./run.sh -m benchmarks.bench_score --smoke
#         ./run.sh -m benchmarks.roofline --tune --smoke
#         ./run.sh -m pytest -x -q
set -euo pipefail

cd "$(dirname "$0")"

for so in /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
          /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
          /usr/lib/libtcmalloc_minimal.so.4; do
    if [ -e "$so" ]; then
        export LD_PRELOAD="$so${LD_PRELOAD:+:$LD_PRELOAD}"
        # keep huge-alloc spam out of benchmark stdout
        export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=10737418240
        break
    fi
done

# silence absl/XLA chatter that would interleave with benchmark output
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"

# on the CPU backend (JAX_PLATFORMS=cpu), fake 8 host devices unless
# XLA_FLAGS is already pinned; any other platform keeps its own devices
if [ "${JAX_PLATFORMS:-}" = "cpu" ] && [ -z "${XLA_FLAGS:-}" ]; then
    export XLA_FLAGS="--xla_force_host_platform_device_count=8"
fi

export REPRO_LCS_DTYPE="${REPRO_LCS_DTYPE:-int8}"
export PYTHONPATH="$PWD/src${PYTHONPATH:+:$PYTHONPATH}"

exec python "$@"
