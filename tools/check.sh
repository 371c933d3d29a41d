#!/usr/bin/env bash
# One-command PR gate: tree-wide lint, fixture sanity, fast tier-1
# slice. Builders and future PRs run this instead of remembering the
# pieces; tests/test_lint.py invokes `check.sh --lint-only` so the
# gate itself stays tested (the flag stops before pytest — otherwise
# the gate would recurse into the test that runs it).
set -euo pipefail
cd "$(dirname "$0")/.."

# 1. the whole tree must be invariant-clean
python -m tools.osselint

# 2. fixture sanity via the CLI: clean fixtures lint clean, violation
#    fixtures actually produce findings (the exact-line marker match
#    lives in tests/test_lint.py)
python -m tools.osselint tests/lint_fixtures/clean_parallel.py \
    tests/lint_fixtures/clean_jit.py tests/lint_fixtures/clean_mesh.py \
    tests/lint_fixtures/clean_tenancy.py \
    tests/lint_fixtures/clean_devbuild.py \
    tests/lint_fixtures/clean_statsname.py \
    tests/lint_fixtures/clean_sched.py
for f in tests/lint_fixtures/violations_*.py; do
    if python -m tools.osselint "$f" > /dev/null 2>&1; then
        echo "check.sh: $f produced no findings" >&2
        exit 1
    fi
done

if [ "${1:-}" = "--lint-only" ]; then
    echo "check.sh: lint gate OK"
    exit 0
fi

# 3. fast tier-1 slice: the lint gate, the jit plane, the query
#    stack (the layers a typical PR touches), and the seeded chaos
#    smoke — deterministic fault schedules, deadline propagation, twin
#    failover; the full soak scenario stays behind `-m slow`
#    (tests/test_chaos.py::test_soak_gate)
JAX_PLATFORMS=cpu python -m pytest tests/test_lint.py \
    tests/test_jitwatch.py tests/test_query.py tests/test_chaos.py \
    tests/test_statsplane.py tests/test_devwatch.py \
    tests/test_schedcheck.py \
    -q -m 'not slow' -p no:cacheprovider

# 3b. schedule exploration: the five protocol scenario suites plus the
#     seeded historical-bug regressions under the armed explorer — 64
#     seeded interleavings per suite, deterministic and replayable
#     (the 1024-schedule deep run is the same file under `-m slow`)
OSSE_SCHED=1 OSSE_SCHED_BUDGET=64 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_schedcheck.py \
    -q -m 'not slow' -p no:cacheprovider

echo "check.sh: OK"
