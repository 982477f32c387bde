"""Hermetic test fixtures.

All tests run on the CPU XLA backend with 8 virtual devices so sharding
code paths (dp/sp meshes, halo exchange, ring attention) are exercised
without TPU hardware. This must happen before jax is imported anywhere.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    from bioengine_tpu.parallel.mesh import make_mesh

    return make_mesh(axes={"dp": 2, "sp": 4}, devices=devices)


@pytest.fixture()
def cpu_not_asked_for():
    """``jax_platforms`` as on a machine where nobody named a platform
    and JAX fell back to the CPU on its own (utils/devices.py). The
    already-initialised CPU backend keeps serving ``jax.devices()``."""
    asked = jax.config.jax_platforms
    jax.config.update("jax_platforms", "")
    yield
    jax.config.update("jax_platforms", asked)


@pytest.fixture()
def tmp_workspace(tmp_path):
    ws = tmp_path / "workspace"
    ws.mkdir()
    return ws


@pytest.fixture(scope="session")
def anyio_backend():
    # async tests run via the anyio pytest plugin on plain asyncio
    return "asyncio"


REPO_APPS = Path(__file__).resolve().parent.parent / "apps"


@pytest.fixture
async def stack(tmp_path):
    """controller + rpc server + apps manager wired together in-process,
    sharing one artifact store — the hermetic analog of the reference's
    real-cluster session fixture (ref tests/conftest.py:136-161)."""
    from bioengine_tpu.apps.artifacts import LocalArtifactStore
    from bioengine_tpu.apps.builder import AppBuilder
    from bioengine_tpu.apps.manager import AppsManager
    from bioengine_tpu.cluster.state import ClusterState
    from bioengine_tpu.rpc.server import RpcServer
    from bioengine_tpu.serving.controller import ServeController

    server = RpcServer(admin_users=["admin"])
    await server.start()
    controller = ServeController(ClusterState(), health_check_period=3600)
    store = LocalArtifactStore(tmp_path / "store")
    builder = AppBuilder(
        store=store,
        workdir_root=tmp_path / "workdirs",
        admin_users=["admin"],
        log_file="off",
    )
    manager = AppsManager(
        controller=controller,
        server=server,
        store=store,
        builder=builder,
        admin_users=["admin"],
        log_file="off",
    )
    yield manager, controller, server, store
    await controller.stop()
    await server.stop()
