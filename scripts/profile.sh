#!/usr/bin/env bash
# Compile-path profiling env harness (olmax-style, SNIPPETS.md §3): wraps
# any repo entrypoint with the XLA/runtime knobs that make kernel numbers
# interpretable, then labels the backend so benchmark rows can never be
# mistaken for the wrong execution path:
#
#   scripts/profile.sh python -m benchmarks.run --only paged_kernel
#   scripts/profile.sh --dump python benchmarks/kernels_micro.py
#   scripts/profile.sh --smoke        # CI: env sanity + one tiny bench
#
# On an accelerator backend (TPU/GPU) the Pallas kernels compile natively
# (kernels.auto_interpret) and the step-marker/dump flags below feed the
# profiler; on CPU the same command runs interpret-mode and says so.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

DUMP_DIR=""
SMOKE=0
while [ $# -gt 0 ]; do
  case "$1" in
    --dump) DUMP_DIR="experiments/xla_dump"; shift ;;
    --dump=*) DUMP_DIR="${1#--dump=}"; shift ;;
    --smoke) SMOKE=1; shift ;;
    *) break ;;
  esac
done

# faster malloc when available (olmax preloads tcmalloc unconditionally;
# we probe so the harness also runs on minimal images)
for so in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
          /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4; do
  if [ -e "$so" ]; then
    export LD_PRELOAD="$so${LD_PRELOAD:+:$LD_PRELOAD}"
    export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000
    break
  fi
done

export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"  # no dataset warnings

# probe the backend BEFORE exporting flags: step markers are a TPU-only
# XLA flag and CPU/GPU jaxlib aborts at flag parse if it sees them. One
# probe process, which exits (and frees the chip) before the real command;
# Pallas interprets exactly on the CPU (kernels.auto_interpret).
BACKEND=$(python -c 'import jax; print(jax.default_backend())')
if [ "$BACKEND" = cpu ]; then INTERP=interpret; else INTERP=compile; fi

# step markers bracket the outer loop for the TPU profiler; dump flags
# write the optimized HLO so kernel fusions can be inspected offline
XLA_EXTRA=""
if [ "$BACKEND" = tpu ]; then
  XLA_EXTRA="--xla_step_marker_location=1"
fi
if [ -n "$DUMP_DIR" ]; then
  mkdir -p "$DUMP_DIR"
  XLA_EXTRA="${XLA_EXTRA:+$XLA_EXTRA }--xla_dump_to=$DUMP_DIR --xla_dump_hlo_as_text"
fi
if [ -n "$XLA_EXTRA" ]; then
  export XLA_FLAGS="$XLA_EXTRA${XLA_FLAGS:+ $XLA_FLAGS}"
fi
echo "# profile.sh: backend=$BACKEND pallas=$INTERP XLA_FLAGS=${XLA_FLAGS:-<unset>}" >&2

if [ "$SMOKE" = 1 ]; then
  # env sanity + the kernel-parity micro bench under the profiling env
  python -m benchmarks.run --only paged_kernel
  echo "profile.sh smoke OK (backend=$BACKEND, pallas=$INTERP)"
  exit 0
fi

if [ $# -eq 0 ]; then
  echo "usage: scripts/profile.sh [--dump[=DIR]] [--smoke] <command...>" >&2
  exit 2
fi
exec "$@"
