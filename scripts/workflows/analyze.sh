#!/usr/bin/env bash
# CI job: static-analysis gate — whole-program, blocking.
#
# Phase 1 indexes every module (process pool, content-hash cache);
# phase 2 runs the cross-module rule families (BE-DIST-2xx contract
# drift, BE-ASYNC-006..008 interprocedural async-safety) over the full
# fact base. Any finding not covered by .analyze-baseline.json fails
# the job.
#
# On pull requests pass the base ref as $1 (e.g. origin/main): module-
# local findings then narrow to changed files while the cross-module
# rules still evaluate the whole project — an unchanged module can
# break a contract a changed one relied on. The push-to-main run does
# the full scan so baseline drift can't hide.
#
# The gate scan includes the BE-PERF-3xx hot-path cost pass and the
# BE-LIFE-4xx lifecycle contract pass — both blocking like every other
# rule family: any unbaselined finding fails the job.
#
# Also emitted:
#   - analyze.sarif        code-scanning annotations (SARIF 2.1.0) —
#     exported BEFORE the job fails, so a red run still annotates
#   - hot-path-report.json the BE-PERF-3xx overhead map (reachable
#     functions ranked by finding count x call-graph depth;
#     docs/performance.md)
#   - analyze-stats.json   machine-readable run stats (wall, cache
#     hits, per-pass timings) — the CI perf-budget probe
#   - a docs drift guard: BIOENGINE_* knobs and flight-event/metric
#     catalogs must match the docs (BE-DIST-204/205) with NO baseline
#     escape hatch — the knob tables and docs/observability.md
#     catalogs are operator-facing contracts.
#   - a leak drift guard: BE-LIFE-401 (unswept keyed registry — the
#     PR 8/14 leak class) also runs with NO baseline escape hatch:
#     new registries must be swept or carry an inline justification,
#     never baselined.
#
# Run locally from the repo root:  scripts/workflows/analyze.sh
set -euo pipefail
cd "$(dirname "$0")/../.."

BASE_REF="${1:-}"
SARIF_OUT="${SARIF_OUT:-analyze.sarif}"
HOTPATH_OUT="${HOTPATH_OUT:-hot-path-report.json}"
STATS_OUT="${STATS_OUT:-analyze-stats.json}"

gate_rc=0
if [[ -n "$BASE_REF" ]]; then
    echo "analyze: whole-program scan (module findings vs $BASE_REF)"
    python -m bioengine_tpu.analysis bioengine_tpu/ apps/ \
        --changed "$BASE_REF" --stats \
        --stats-json "$STATS_OUT" \
        --hot-path-report "$HOTPATH_OUT" || gate_rc=$?
else
    echo "analyze: whole-program full scan"
    python -m bioengine_tpu.analysis bioengine_tpu/ apps/ --stats \
        --stats-json "$STATS_OUT" \
        --hot-path-report "$HOTPATH_OUT" || gate_rc=$?
fi
if [[ "$gate_rc" -ge 2 ]]; then
    echo "analyze: analyzer error (rc=$gate_rc)" >&2
    exit "$gate_rc"
fi

# export annotations even when the gate found something — that is
# exactly when a CI consumer needs them (rc 1 = findings, still a
# valid document; rc >= 2 = real error)
echo "analyze: exporting SARIF -> $SARIF_OUT"
sarif_rc=0
python -m bioengine_tpu.analysis bioengine_tpu/ apps/ \
    --format sarif > "$SARIF_OUT" || sarif_rc=$?
if [[ "$sarif_rc" -ge 2 ]]; then
    echo "analyze: SARIF export failed (rc=$sarif_rc)" >&2
    exit "$sarif_rc"
fi
python - "$SARIF_OUT" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["version"] == "2.1.0", "SARIF export is not 2.1.0"
print(f"analyze: SARIF ok ({len(doc['runs'][0]['results'])} result(s))")
EOF

python - "$HOTPATH_OUT" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["schema"] == "bioengine.hot-path-report/v1", doc.get("schema")
assert doc["totals"]["roots"] > 0, "no request-path roots resolved"
print(
    f"analyze: hot-path report ok ({doc['totals']['roots']} roots, "
    f"{doc['totals']['reachable_functions']} reachable, "
    f"{doc['totals']['findings']} finding(s))"
)
EOF

echo "analyze: docs drift guard (env knobs + observability catalogs)"
python -m bioengine_tpu.analysis bioengine_tpu/ apps/ \
    --rule BE-DIST-204 --rule BE-DIST-205 --no-baseline

echo "analyze: leak drift guard (BE-LIFE-401, no baseline escape)"
python -m bioengine_tpu.analysis bioengine_tpu/ apps/ \
    --rule BE-LIFE-401 --no-baseline

if [[ "$gate_rc" -ne 0 ]]; then
    echo "analyze: gate FAILED (new findings above)" >&2
    exit "$gate_rc"
fi
echo "analyze: gate passed"
