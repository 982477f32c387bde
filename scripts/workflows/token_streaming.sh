#!/usr/bin/env bash
# Token-streaming gate (the generative-serving job): the decode unit
# suite (paged KV cache, step-level continuous batching, golden-pinned
# toy decoder incl. dp-mesh parity), the streaming integration suite
# (RPC stream plane, idempotent mid-stream resume, the generate app
# end-to-end), and the token_streaming scenario (a host SIGKILL'd
# mid-generation: exact token sequences survive resume, co-batching
# observed, chip accounting exact).
#
# Knobs:
#   BIOENGINE_SCENARIO_SEED   workload seed (default 7)
#   BIOENGINE_SCENARIO_SCALE  time-compression stretch for slow CI boxes
set -euo pipefail

cd "$(dirname "$0")/../.."

export JAX_PLATFORMS=cpu
SEED="${BIOENGINE_SCENARIO_SEED:-7}"

echo "== decode + streaming suites =="
timeout -k 10 600 python -m pytest tests/test_decode.py tests/test_streaming.py -q \
    -p no:cacheprovider

echo "== token_streaming scenario, determinism double-run (seed ${SEED}) =="
out="$(mktemp)"
timeout -k 10 300 python -m bioengine_tpu.cli scenarios run token_streaming \
    --seed "$SEED" --check-determinism --out "$out" > /dev/null
python - "$out" <<'EOF'
import json
import sys

with open(sys.argv[1]) as f:
    d = json.load(f)
res = d["result"]
assert d["deterministic"] is True, (
    "token_streaming is not replay-deterministic for one seed"
)
inv = res["invariants"]
for name in (
    "zero_failed_idempotent",
    "chip_accounting_exact",
    "decode_cobatch_observed",
    "stream_resume_observed",
    "slo_attainment",
):
    assert inv[name]["ok"], (name, inv[name])
assert res["passed"], inv
assert res["counts"] == {"ok": res["requests"]}, res["counts"]
print(
    f"token_streaming OK: {res['requests']} stream(s), "
    f"{inv['decode_cobatch_observed']['detail']}, "
    f"{inv['stream_resume_observed']['detail']}"
)
EOF

echo "token streaming gate OK"
