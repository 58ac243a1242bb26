#!/usr/bin/env bash
# Lint + syntax + test gate (reference: format.sh running black/isort/
# mypy/pylint + the unit/smoke test split, SURVEY §4). The image ships
# none of those linters, so this runs the offline equivalents:
# compileall (syntax across the tree) + tools/lint.py, the skyanalyze
# CLI (tools/analysis — AST passes: the nine classic rules plus
# lock-discipline, async-blocking, tracer-safety, env-registry, and
# registry-consistency; docs/static_analysis.md). Exit-code gated.
#
# Test tiers:
#   ./format.sh         fast tier: lint + non-heavy unit tests
#                       + the on-TPU lowering gate (skipped off-TPU)
#   ./format.sh --full  everything: adds the compile-heavy JAX suites
#                       and subprocess integration tests — run before
#                       snapshots/releases.
# The markers `heavy` and `integration` choose this script's fast tier and
# nothing else; the driver's tier-1 (`-m 'not slow'`, ROADMAP D10) reads only
# `slow`, and what carries `slow` still runs here under --full.
set -e
cd "$(dirname "$0")"

FULL=0
ARGS=()
for a in "$@"; do
  if [ "$a" = "--full" ]; then FULL=1; else ARGS+=("$a"); fi
done

python -m compileall -q skypilot_tpu tests tests_tpu tools __graft_entry__.py
python tools/lint.py "${ARGS[@]}"
if [ "$FULL" = "1" ]; then
  python -m pytest tests/ -q
else
  python -m pytest tests/ -q -m "not heavy and not integration"
fi
# On-TPU lowering gate (skips itself where JAX selects the CPU): Mosaic
# must accept the Pallas kernels — interpret-mode CPU tests cannot catch
# a BlockSpec the real compiler rejects.
python -m pytest tests_tpu/ -q
echo "format.sh: clean"
