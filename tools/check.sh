#!/usr/bin/env bash
# Single-entry gate: the two checks a change must pass, in cost order,
# fail-fast. Run from the repo root:
#
#   tools/check.sh            # pilint full tree -> tier-1 pytest
#   tools/check.sh --changed  # pilint incremental (vs HEAD) first instead
#
# Each stage's exit code stops the gate; the summary line at the end is
# what CI (and a builder's eyeball) keys on.
set -u -o pipefail

cd "$(dirname "$0")/.."

MODE="full"
if [ "${1:-}" = "--changed" ]; then
    MODE="changed"
fi

stage() {
    echo "==> $1"
}

fail() {
    echo "check.sh: FAIL at $1"
    exit 1
}

stage "pilint ($MODE tree)"
if [ "$MODE" = "changed" ]; then
    python -m tools.pilint --changed HEAD || fail "pilint"
else
    python -m tools.pilint pilosa_tpu/ || fail "pilint"
fi

stage "tier-1 pytest (-m 'not slow')"
# CHECK_TOLERATE_KNOWN=1 accepts pytest rc 1 ("some tests failed") for
# environments carrying the documented jax multi-process API gap (two
# two-process tests; see ROADMAP "compare DOTS_PASSED, not rc"). Any
# other exit (collection error, crash) still fails the gate.
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    -p no:cacheprovider
rc=$?
if [ "$rc" -ne 0 ]; then
    if [ "$rc" -eq 1 ] && [ "${CHECK_TOLERATE_KNOWN:-0}" = "1" ]; then
        echo "check.sh: WARNING tolerating pytest rc 1 (CHECK_TOLERATE_KNOWN=1)"
    else
        fail "pytest"
    fi
fi

echo "check.sh: OK (pilint + tier-1)"
