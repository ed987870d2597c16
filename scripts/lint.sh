#!/usr/bin/env bash
# Lint gate: ruff over the package + tests (config in ruff.toml).
#
# Degrades honestly when ruff is not installed (the hermetic TPU image
# does not ship it): falls back to a full-tree compile check so syntax
# errors are still caught, and says so.  CI images with ruff get the
# real lint.
set -euo pipefail
cd "$(dirname "$0")/.."

if command -v ruff >/dev/null 2>&1; then
    exec ruff check gol_tpu tests
fi

echo "lint: ruff not installed; falling back to compile-only check" >&2
python -m compileall -q gol_tpu tests
echo "lint: compile check passed (install ruff for the full lint)"
