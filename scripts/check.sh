#!/usr/bin/env bash
# Local mirror of the CI `static-analysis` job (scripts/tier1.sh is the
# test half). dascheck is stdlib-only and always runs; ruff is optional
# locally and skipped with a warning when absent (CI pins its version).
set -euo pipefail
cd "$(dirname "$0")/.."
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m repro.analysis src
python scripts/check_metrics.py
if command -v ruff >/dev/null 2>&1; then
  ruff check src tests
else
  echo "check.sh: ruff not installed; skipping (CI runs the pinned ruff)" >&2
fi
