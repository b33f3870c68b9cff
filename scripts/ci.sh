#!/usr/bin/env bash
# Tier-1 verification in named stages (see ROADMAP.md).
#
#   scripts/ci.sh                    # all stages: lint verify tests
#   scripts/ci.sh lint verify        # just these stages, in order
#   scripts/ci.sh tests -- -k session  # stage args after -- go to pytest
#   scripts/ci.sh -k session         # back-compat: bare pytest args run all
#                                    # stages with those args forwarded
#
# Stages (the GitHub Actions workflow runs them as separate steps so a
# compileall or verify failure fails fast before paying for the full suite):
#   lint   - byte-compile everything + refuse tracked bytecode +
#            concurrency lint (repro.analysis.lint_concurrency) + ruff
#            (style/import order; skipped gracefully where not installed)
#   verify - static analysis gate (python -m repro.analysis): chunk-dataflow
#            verification of every generator, round feasibility, circuit
#            realizability, plan/concurrent-plan accounting invariants,
#            plus the Pallas kernel analyzer (--kernels): coverage,
#            write-race, bounds and scratch-carry proofs per pallas_call
#   tests  - the full pytest suite (hypothesis property suites run when
#            requirements-dev.txt is installed; they auto-skip otherwise)
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

stage_lint() {
  # fast lint: every module must at least byte-compile
  python -m compileall -q src scripts tests
  # committed bytecode must never reappear (purged in PR 5; see .gitignore)
  if git ls-files | grep -E '(^|/)__pycache__/|\.pyc$'; then
    echo "lint: tracked bytecode detected — purge it and rely on .gitignore" >&2
    return 1
  fi
  # concurrency lint: shared caches mutated outside their owning lock,
  # function-attribute state, mutable defaults (see src/repro/analysis/)
  python -m repro.analysis.lint_concurrency src/repro
  # style/import-order lint; requirements-dev installs ruff in CI, but the
  # dev image may not have it — degrade to a notice rather than fail
  if command -v ruff >/dev/null 2>&1; then
    ruff check src scripts tests
  else
    echo "lint: ruff not installed; skipping style checks (CI runs them)"
  fi
}

stage_verify() {
  # static analysis gate: dataflow-verify every generator, check round
  # feasibility + circuit realizability, replay plan accounting
  python -m repro.analysis
  # kernel analyzer over the shipped Pallas kernels (separate invocation:
  # it needs JAX for capture, the schedule passes above stay jax-free)
  python -m repro.analysis --kernels
}

stage_tests() {
  # --durations keeps slow planner tests visible as the suite grows
  # ${arr[@]+...} keeps `set -u` happy on bash < 4.4 when no args were given
  python -m pytest -x -q --durations=10 ${PYTEST_ARGS[@]+"${PYTEST_ARGS[@]}"}
}

# ---- argument parsing: stage names, then optional -- pytest args ----------
STAGES=()
PYTEST_ARGS=()
seen_sep=0
for arg in "$@"; do
  if [ "$seen_sep" = 1 ] || [ "$arg" = "--" ]; then
    [ "$arg" = "--" ] && [ "$seen_sep" = 0 ] && { seen_sep=1; continue; }
    PYTEST_ARGS+=("$arg")
  else
    case "$arg" in
      lint|verify|tests) STAGES+=("$arg") ;;
      *)
        # back-compat with the pre-stage interface: the first word that is
        # not a stage name (a pytest flag, test path, -k expression, ...)
        # and everything after it forwards to pytest
        seen_sep=1
        PYTEST_ARGS+=("$arg")
        ;;
    esac
  fi
done
if [ "${#STAGES[@]}" -eq 0 ]; then
  STAGES=(lint verify tests)
fi

for stage in "${STAGES[@]}"; do
  echo "==> ci stage: $stage"
  "stage_$stage"
done
