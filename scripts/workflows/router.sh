#!/usr/bin/env bash
# Router-tier gate (the scale-out routing job): the router unit suite
# (table publication, epoch fencing, gate/kill semantics, the shared-
# contract pins), the router_loss scenario (a router killed mid-traffic
# must lose ZERO idempotent requests — clients hop typed to a sibling
# — while table staleness stays bounded).
#
# Knobs:
#   BIOENGINE_SCENARIO_SEED   workload seed (default 7)
#   BIOENGINE_SCENARIO_SCALE  time-compression stretch for slow CI boxes
set -euo pipefail

cd "$(dirname "$0")/../.."

export JAX_PLATFORMS=cpu
SEED="${BIOENGINE_SCENARIO_SEED:-7}"

echo "== router unit suite =="
timeout -k 10 300 python -m pytest tests/test_router.py -q \
    -p no:cacheprovider

echo "== router_loss scenario (seed ${SEED}) =="
out="$(mktemp)"
timeout -k 10 300 python -m bioengine_tpu.cli scenarios run router_loss \
    --seed "$SEED" --out "$out" > /dev/null
python - "$out" <<'EOF'
import json
import sys

with open(sys.argv[1]) as f:
    d = json.load(f)
res = d["result"]
inv = res["invariants"]
for name in (
    "zero_failed_idempotent",
    "router_failover_observed",
    "router_staleness_bounded",
):
    assert inv[name]["ok"], (name, inv[name])
routers = res["routers"]
assert routers["killed"] == ["r1"], routers["killed"]
assert routers["client_failovers"] > 0, "no client ever hopped routers"
print(
    f"router_loss OK: {routers['client_failovers']} failover hop(s), "
    f"max table age {1000 * routers['staleness_max_s']:.0f}ms"
)
EOF

echo "router gate OK"
