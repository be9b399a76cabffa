#!/usr/bin/env bash
# dfdlint gate — the static-analysis half of verification (the dynamic
# half is the tier-1 pytest run; see ROADMAP.md "Tier-1 verify").
#
#   scripts/lint.sh              # strict gate: new violations OR rot fail
#   scripts/lint.sh --fix-hints  # same, with per-finding fix hints
#
# Runs jax-free (stdlib ast/symtable only): the whole pass is ~3 s.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env PYTHONPATH= python tools/dfdlint.py \
    deepfake_detection_tpu tools --strict "$@"
