#!/usr/bin/env bash
# One-command gate: static analysis (scripts/check.sh — ruff when present
# + the JAX-aware analyzer ratcheted against analysis_baseline.json) + the
# tier-1 test suite, serially.  The driver's form — six xdist workers,
# `--dist loadfile`, ~5 min — is the command in /root/TESTS_LAST_RUN.json.
# Usage: scripts/test.sh [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."

scripts/check.sh

exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider "$@"
