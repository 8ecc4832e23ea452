#!/usr/bin/env bash
# Repo-wide Python lint with a pinned, minimal rule set.
#
# Only rules that flag definite defects are enabled — this gate must
# stay green on a healthy tree, so style-opinion rules are out:
#   F63x — invalid comparisons (is-literal, ==/!= against tuples)
#   F7xx — misplaced statements (return/yield/break outside scope)
#   F82x — undefined names
#
# ruff is optional tooling: when it is not installed the script reports
# SKIP and exits 0 so environments without it (including CI base
# images) are not broken; exit 97 distinguishes the skip for callers
# that want to require the tool.
#
# Before the ruff stage, a SELF-LINT stage runs with no external deps:
# the repo's own analyzer (`cli lint --werror`) over every committed
# example graph (accepted warnings baselined in lint_baseline.json,
# never silenced in code) and the concurrency lint (check_locks.py,
# including the LK007 whole-repo lock-order graph) over the full tree.
set -uo pipefail

REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
RULES="F63,F7,F82"

# ---- self-lint stage (runs wherever the repo's own deps import) -------
PYTHON=""
for cand in python python3; do
    if command -v "$cand" >/dev/null 2>&1 \
        && "$cand" -c 'import jax, pathway_tpu' >/dev/null 2>&1; then
        PYTHON="$cand"
        break
    fi
done
if [ -z "$PYTHON" ]; then
    echo "lint_repo: no python with pathway_tpu importable, self-lint SKIP" >&2
else
    echo "lint_repo: self-lint stage" >&2
    SELF_FAIL=0
    # capacity gate: the plan-aware memory report runs per example with a
    # concrete per-worker budget — a blown budget is a PW-M002 warning
    # (baselineable), O(stream) state reaching a sink is a PW-M001 error
    # (never baselineable)
    # --device adds the PW-J device-safety sweep over the example AND
    # the repo device surface (parallel/, ops/, serving/): PW-J001/J004
    # are errors and never baselineable — a recompile storm or a
    # collective deadlock does not get grandfathered in
    for ex in "$REPO"/examples/*.py; do
        if ! JAX_PLATFORMS=cpu \
            PATHWAY_MEMORY_BUDGET="${PATHWAY_MEMORY_BUDGET:-4GiB}" \
            "$PYTHON" -m pathway_tpu.cli lint --werror --memory --device \
            --baseline "$REPO/scripts/lint_baseline.json" "$ex"; then
            SELF_FAIL=1
        fi
    done
    if ! "$PYTHON" "$REPO/scripts/check_locks.py"; then
        SELF_FAIL=1
    fi
    if [ "$SELF_FAIL" != "0" ]; then
        echo "lint_repo: self-lint FAILED" >&2
        exit 1
    fi
    echo "lint_repo: self-lint clean" >&2
fi

RUFF=""
if command -v ruff >/dev/null 2>&1; then
    RUFF="ruff"
elif python -c 'import ruff' >/dev/null 2>&1; then
    RUFF="python -m ruff"
fi

if [ -z "$RUFF" ]; then
    echo "lint_repo: ruff not available, SKIP" >&2
    if [ "${LINT_REPO_REQUIRE:-0}" = "1" ]; then
        exit 97
    fi
    exit 0
fi

set -e
$RUFF check --select "$RULES" --no-cache \
    "$REPO/pathway_tpu" "$REPO/scripts" "$REPO/tests"
echo "lint_repo: clean ($RULES)" >&2
