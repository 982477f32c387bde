#!/usr/bin/env bash
# CI job: topology-portable multi-host meshes — fails fast on cross-host
# placement/execution regressions without waiting for the slow suite.
#
# Two checks on a forced 4-virtual-device CPU layout (the same trick
# as tests/conftest.py and the MULTICHIP dryruns):
#   1. the full multichip dryrun (__graft_entry__.dryrun_multichip),
#      which now ends with a cross-host mesh phase: the CrossHostEngine
#      pipeline composition over two per-device-group engine shards,
#      parity-pinned against the composed reference;
#   2. the mesh suite (tests/test_mesh.py): planner + config units,
#      CrossHostEngine composition, 2-in-process-host serving with
#      parity + the RpcStats OOB pin, mesh1 capability gating, and the
#      kill-a-shard-host chaos leg with exact chip accounting.
#
# Run locally from the repo root:  scripts/workflows/multihost.sh
set -euo pipefail
cd "$(dirname "$0")/../.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4"

echo "multihost: multichip dryrun with cross-host mesh phase (4-device CPU)"
python __graft_entry__.py 4

echo "multihost: mesh planner/engine/serving/chaos suite"
python -m pytest tests/test_mesh.py -q -p no:cacheprovider
