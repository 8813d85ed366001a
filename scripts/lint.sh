#!/usr/bin/env bash
# Mechanical style/correctness gate: ruff over deepfm_tpu/ + tests/ +
# scripts/ (config: ruff.toml at the repo root).
# Usage: scripts/lint.sh [--fix]
set -euo pipefail
cd "$(dirname "$0")/.."

if ! command -v ruff >/dev/null 2>&1; then
    # the CI/dev image may not bundle ruff; a missing linter should read
    # as "not run", not "passed" — but must not break test-only environments
    echo "lint: ruff not found on PATH; skipping (install ruff to enable)" >&2
    exit 0
fi

exec ruff check "$@" deepfm_tpu tests scripts
