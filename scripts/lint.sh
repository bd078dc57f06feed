#!/bin/sh
# Static-analysis gate: sxt-check (the repo's invariant analyzer) + ruff.
#
# sxt-check is self-contained (stdlib-only AST pass, no jax import) and
# always runs — all rules incl. the ISSUE 13 lock-order pass (SXT009
# lock-order cycles, SXT010 blocking-under-lock; see analysis/RULES.md
# and `--lock-graph` for the harvested acquisition graph). ruff is the
# mechanical-hygiene baseline (ruff.toml) and is skipped with a notice
# when the binary is not installed — the driver container does not ship
# it, CI images may.
#
# Exit: nonzero when either tool reports findings.
set -e
cd "$(dirname "$0")/.."

echo "== sxt-check (shuffle_exchange_tpu/analysis) =="
python -m shuffle_exchange_tpu.analysis shuffle_exchange_tpu/ "$@"

if command -v ruff >/dev/null 2>&1; then
    echo "== ruff (baseline: ruff.toml) =="
    ruff check shuffle_exchange_tpu/ tests/ scripts/
else
    echo "== ruff not installed; skipping the baseline lint (config: ruff.toml) =="
fi
