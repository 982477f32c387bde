#!/usr/bin/env bash
# Cold-start gate: the shared compile-cache tier, streamed weight
# loading, and warm-pool suites (tier entry protocol, persistent-hit
# tagging, streamed-vs-eager bit parity, pool fill/promote/sweep, and
# the preemption chaos test), then an in-process multi-host DRYRUN
# proving a second replica start hits the compile tier (first replica
# compiles for real; its entry rides host→controller-tier→host and the
# second replica's compile is tagged cache_hit).
set -euo pipefail

cd "$(dirname "$0")/../.."

export JAX_PLATFORMS=cpu

echo "== cold-start test suite =="
timeout -k 10 600 python -m pytest tests/test_cold_start.py -q -rA \
    -p no:cacheprovider

echo "== compile-tier dryrun (second replica start hits the tier) =="
timeout -k 10 300 python - <<'EOF'
import asyncio
import os
import tempfile

root = tempfile.mkdtemp(prefix="coldstart-dryrun-")
dir_a = os.path.join(root, "xla-a")
dir_b = os.path.join(root, "xla-b")
os.makedirs(dir_b)
os.environ["JAX_COMPILATION_CACHE_DIR"] = dir_a
# 8 virtual host devices so each in-process "host" can lease 3 chips
# (same forced layout the test suite runs under)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

from bioengine_tpu.utils import flight
from bioengine_tpu.utils.compile_cache import (
    enable_persistent_compilation_cache,
    list_entries,
)

assert enable_persistent_compilation_cache() == dir_a

APP_MANIFEST = """\
name: Cold Start Dryrun
id: coldstart-dryrun
id_emoji: "\\u2744"
description: second replica start must hit the compile tier
type: tpu-serve
version: 1.0.0
deployments:
  - warm_dep:WarmDep
authorized_users: ["*"]
deployment_config:
  warm_dep:
    num_replicas: 2
    min_replicas: 2
    max_replicas: 2
    chips: 3
    autoscale: false
"""

# each replica compiles the same UNet program through its OWN
# CompiledProgramCache: replica 1 pays the real XLA compile (entry
# lands in the persistent dir + the tier), replica 2's "compile" is a
# near-zero persistent-cache read and must be tagged cache_hit
APP_SOURCE = '''\
import jax
import jax.numpy as jnp

from bioengine_tpu.models.unet import UNet2D
from bioengine_tpu.rpc import schema_method
from bioengine_tpu.runtime.program_cache import CompiledProgramCache


class WarmDep:
    async def async_init(self):
        model = UNet2D(features=(8, 16), out_channels=1)
        x = jnp.zeros((1, 64, 64, 1), jnp.float32)
        params = model.init(jax.random.key(0), x)["params"]
        cache = CompiledProgramCache()

        def build():
            f = jax.jit(lambda p, t: model.apply({"params": p}, t))
            f(params, x).block_until_ready()
            return f

        cache.get_or_compile(("dryrun-unet", 64), build)
        self.persistent_hits = cache.stats.persistent_hits

    @schema_method
    async def ping(self, context=None):
        """Liveness."""
        return {"ok": True}
'''


async def main():
    from pathlib import Path

    from bioengine_tpu.apps.builder import AppBuilder
    from bioengine_tpu.cluster.state import ClusterState
    from bioengine_tpu.cluster.topology import TpuTopology
    from bioengine_tpu.rpc.server import RpcServer
    from bioengine_tpu.serving import ServeController
    from bioengine_tpu.serving.compile_tier import CompileCacheTier
    from bioengine_tpu.worker_host import WorkerHost

    server = RpcServer(host="127.0.0.1", admin_users=["admin"])
    await server.start()
    token = server.issue_token("admin", is_admin=True)
    controller = ServeController(
        ClusterState(TpuTopology(chips=(), n_hosts=1, platform="cpu")),
        health_check_period=3600,
    )
    controller.compile_tier = CompileCacheTier(os.path.join(root, "tier"))
    controller.attach_rpc(server, admin_users=["admin"])
    h1 = WorkerHost(
        server_url=server.url, token=token, host_id="h1",
        workspace_dir=os.path.join(root, "ws1"), compile_cache_dir=dir_a,
    )
    h2 = WorkerHost(
        server_url=server.url, token=token, host_id="h2",
        workspace_dir=os.path.join(root, "ws2"), compile_cache_dir=dir_b,
    )
    await h1.start()
    await h2.start()
    app_dir = Path(root) / "app-src"
    app_dir.mkdir()
    (app_dir / "manifest.yaml").write_text(APP_MANIFEST)
    (app_dir / "warm_dep.py").write_text(APP_SOURCE)
    builder = AppBuilder(workdir_root=Path(root) / "apps")
    built = builder.build(app_id="coldstart-dryrun", local_path=app_dir)
    await controller.deploy("coldstart-dryrun", built.specs)

    compiles = [
        e for e in flight.get_record(limit=2000)["events"]
        if e["type"] == "program.compile"
        and "dryrun-unet" in e["attrs"].get("key", "")
    ]
    assert len(compiles) == 2, compiles
    assert compiles[0]["attrs"]["cache_hit"] is False, compiles[0]
    # THE assertion: the second in-process replica start hit the tier
    assert compiles[1]["attrs"]["cache_hit"] is True, compiles[1]
    tier_stats = controller.compile_tier.stats()
    assert tier_stats["stored"] >= 1, tier_stats
    fetched_b = list_entries(dir_b)
    assert fetched_b, "h2 fetched no tier entries"
    print(
        f"dryrun OK: real_compile={round(compiles[0]['attrs']['seconds'], 3)}s "
        f"tier_hit={round(compiles[1]['attrs']['seconds'], 3)}s "
        f"tier_entries={tier_stats['entries']} "
        f"h2_fetched={len(fetched_b)}"
    )
    await h1.stop()
    await h2.stop()
    await controller.stop()
    await server.stop()


asyncio.run(main())
EOF

echo "cold-start gate OK"
