"""End-to-end tests for the bundled applications (ref tests/apps/ — the
reference tests its apps against live deployments; here the same flows
run against the in-process controller + RPC server stack)."""

import asyncio
import io
import os
import time
from pathlib import Path

import numpy as np
import pytest

from bioengine_tpu.utils.permissions import create_context

pytestmark = [pytest.mark.integration, pytest.mark.anyio]

REPO_APPS = Path(__file__).resolve().parent.parent / "apps"
ADMIN = create_context("admin")


async def deploy(manager, app_dir, **kwargs):
    result = await manager.deploy_app(
        local_path=str(REPO_APPS / app_dir), context=ADMIN, **kwargs
    )
    await asyncio.sleep(0.05)
    return result


async def call(server, service_id, method, **kwargs):
    caller = server.validate_token(server.issue_token("user"))
    return await server.call_service_method(
        service_id, method, kwargs=kwargs, caller=caller
    )


# ---- model-runner -----------------------------------------------------------


@pytest.fixture(scope="module")
def model_collection(tmp_path_factory):
    """A local bioimage.io-style collection: a jax_params UNet, a
    pytorch_state_dict model, and one that failed inference checks."""
    import jax
    import jax.numpy as jnp
    import yaml

    from bioengine_tpu.models.unet import UNet2D

    root = tmp_path_factory.mktemp("collection")

    # tiny-unet: TPU-native jax_params weights
    d = root / "tiny-unet"
    d.mkdir()
    model = UNet2D(features=(8, 16), out_channels=1)
    x = np.random.default_rng(0).normal(size=(1, 64, 64, 1)).astype(np.float32)
    params = model.init(jax.random.key(0), jnp.asarray(x))["params"]
    # jit to match the inference engine's compiled program bit-for-bit
    # (bf16 rounding differs between eager and fused execution)
    expected = np.asarray(
        jax.jit(lambda p, a: model.apply({"params": p}, a))(params, jnp.asarray(x))
    )
    from bioengine_tpu.runtime.convert import save_params_npz

    save_params_npz(str(d / "weights.npz"), params)
    np.save(d / "test_input.npy", x)
    np.save(d / "test_output.npy", expected)
    (d / "rdf.yaml").write_text(
        yaml.safe_dump(
            {
                "type": "model",
                "name": "Tiny UNet",
                "description": "tiny segmentation test model",
                "tags": ["segmentation", "nuclei"],
                "inputs": [{"name": "input0", "axes": "byxc"}],
                "outputs": [{"name": "output0", "axes": "byxc"}],
                "test_inputs": ["test_input.npy"],
                "test_outputs": ["test_output.npy"],
                "documentation": "README.md",
                "weights": {
                    "jax_params": {
                        "source": "weights.npz",
                        "architecture": {
                            "name": "unet2d",
                            "kwargs": {"features": [8, 16], "out_channels": 1},
                        },
                    }
                },
            }
        )
    )
    (d / "README.md").write_text("# Tiny UNet\ntest model docs")

    # torch-square: pytorch_state_dict via architecture source exec
    import torch

    d2 = root / "torch-square"
    d2.mkdir()
    (d2 / "arch.py").write_text(
        "import torch\n"
        "class SquareNet(torch.nn.Module):\n"
        "    def __init__(self, scale=1.0):\n"
        "        super().__init__()\n"
        "        self.scale = torch.nn.Parameter(torch.tensor(float(scale)))\n"
        "    def forward(self, x):\n"
        "        return x * x * self.scale\n"
    )
    ns: dict = {}
    exec((d2 / "arch.py").read_text(), ns)
    module = ns["SquareNet"](scale=2.0)
    torch.save(module.state_dict(), d2 / "weights.pt")
    x2 = np.random.default_rng(1).normal(size=(1, 32, 32, 1)).astype(np.float32)
    np.save(d2 / "test_input.npy", x2)
    np.save(d2 / "test_output.npy", (x2 * x2 * 2.0).astype(np.float32))
    (d2 / "rdf.yaml").write_text(
        yaml.safe_dump(
            {
                "type": "model",
                "name": "Torch Square",
                "description": "elementwise square model",
                "inputs": [{"name": "input0", "axes": "byxc"}],
                "outputs": [{"name": "output0", "axes": "byxc"}],
                "test_inputs": ["test_input.npy"],
                "test_outputs": ["test_output.npy"],
                "weights": {
                    "pytorch_state_dict": {
                        "source": "weights.pt",
                        "architecture": {
                            "callable": "SquareNet",
                            "source": "arch.py",
                            "kwargs": {"scale": 2.0},
                        },
                    }
                },
            }
        )
    )

    # tiny-unet3d: volumetric jax_params model (axes bczyx)
    from bioengine_tpu.models.unet3d import UNet3D

    d4 = root / "tiny-unet3d"
    d4.mkdir()
    model3d = UNet3D(features=(2, 4), out_channels=1)
    # exact bucket sizes (z=8 on the z-ladder, xy=64 on the xy-ladder):
    # GroupNorm statistics are volume-global, so zero-padding to a
    # bucket would legitimately change the expected output
    x3 = (
        np.random.default_rng(2)
        .normal(size=(1, 1, 8, 64, 64))
        .astype(np.float32)
    )  # bczyx
    vol = np.transpose(x3, (0, 2, 3, 4, 1))  # engine layout bzyxc
    params3d = model3d.init(jax.random.key(0), jnp.asarray(vol))["params"]
    expected3 = np.asarray(
        jax.jit(lambda p, a: model3d.apply({"params": p}, a))(
            params3d, jnp.asarray(vol)
        )
    )
    save_params_npz(str(d4 / "weights.npz"), params3d)
    np.save(d4 / "test_input.npy", x3)
    np.save(d4 / "test_output.npy", np.transpose(expected3, (0, 4, 1, 2, 3)))
    (d4 / "rdf.yaml").write_text(
        yaml.safe_dump(
            {
                "type": "model",
                "name": "Tiny UNet3D",
                "description": "tiny volumetric segmentation test model",
                "tags": ["segmentation", "3d"],
                "inputs": [{"name": "input0", "axes": "bczyx"}],
                "outputs": [{"name": "output0", "axes": "bczyx"}],
                "test_inputs": ["test_input.npy"],
                "test_outputs": ["test_output.npy"],
                "documentation": "README.md",
                "weights": {
                    "jax_params": {
                        "source": "weights.npz",
                        "architecture": {
                            "name": "unet3d",
                            "kwargs": {
                                "features": [2, 4],
                                "out_channels": 1,
                            },
                        },
                    }
                },
            }
        )
    )

    # failed-check model (exists but did not pass inference checks)
    d3 = root / "secret-model"
    d3.mkdir()
    (d3 / "rdf.yaml").write_text(
        yaml.safe_dump(
            {
                "type": "model",
                "name": "Secret",
                "description": "did not pass checks",
                "inputs": [{"name": "input0", "axes": "byxc"}],
                "outputs": [{"name": "output0", "axes": "byxc"}],
                "weights": {"jax_params": {"source": "missing.npz"}},
            }
        )
    )

    (root / "collection.yaml").write_text(
        yaml.safe_dump(
            {
                "bioengine_inference": {
                    "tiny-unet": {"status": "passed"},
                    "tiny-unet3d": {"status": "passed"},
                    "torch-square": {"status": "passed"},
                    "secret-model": {"status": "failed"},
                }
            }
        )
    )
    return root


@pytest.fixture
async def model_runner(stack, model_collection, tmp_path, monkeypatch):
    monkeypatch.setenv("BIOENGINE_LOCAL_MODEL_PATH", str(model_collection))
    manager, _, server, _ = stack
    result = await deploy(
        manager,
        "model-runner",
        deployment_kwargs={
            "entry_deployment": {"cache_dir": str(tmp_path / "model-cache")}
        },
    )
    return result, server


class TestModelRunner:
    async def test_busy_runtime_does_not_fail_entry_health(
        self, model_runner, stack
    ):
        """The entry replica's health is its own: with the runtime
        replica's request queue saturated (a first compile takes tens
        of seconds on a TPU) a probe through that queue times out, the
        controller restarts the entry replica and the public service
        drops mid-traffic — found on the chip. Stand-in for the busy
        queue: a runtime handle that never answers."""
        result, _ = model_runner
        _, controller, _, _ = stack
        (replica,) = controller.apps[result["app_id"]].replicas[
            "entry_deployment"
        ]

        class NeverAnswers:
            async def call(self, *args, **kwargs):
                await asyncio.sleep(3600)

        replica.instance.runtime_deployment = NeverAnswers()
        state = await asyncio.wait_for(replica.check_health(), timeout=2)
        assert state.value == "HEALTHY"

    async def test_search_models(self, model_runner):
        result, server = model_runner
        sid = result["service_id"]
        out = await call(server, sid, "search_models")
        ids = {m["model_id"] for m in out}
        assert ids == {"tiny-unet", "tiny-unet3d", "torch-square"}  # checks filter applied

        out = await call(server, sid, "search_models", keywords=["nuclei"])
        assert [m["model_id"] for m in out] == ["tiny-unet"]

        out = await call(server, sid, "search_models", ignore_checks=True)
        assert {m["model_id"] for m in out} == {
            "tiny-unet", "tiny-unet3d", "torch-square", "secret-model",
        }

    async def test_rdf_and_documentation(self, model_runner):
        result, server = model_runner
        sid = result["service_id"]
        rdf = await call(server, sid, "get_model_rdf", model_id="tiny-unet")
        assert rdf["name"] == "Tiny UNet"
        doc = await call(
            server, sid, "get_model_documentation", model_id="tiny-unet"
        )
        assert "Tiny UNet" in doc
        none_doc = await call(
            server, sid, "get_model_documentation", model_id="torch-square"
        )
        assert none_doc is None

    async def test_validate(self, model_runner):
        result, server = model_runner
        sid = result["service_id"]
        good = await call(
            server, sid, "validate",
            rdf_dict={
                "name": "m", "type": "model",
                "inputs": [{"axes": "byxc"}], "outputs": [{"axes": "byxc"}],
                "weights": {"jax_params": {"source": "w.npz"}},
            },
        )
        assert good["success"]
        bad = await call(server, sid, "validate", rdf_dict={"name": "m"})
        assert not bad["success"]
        assert "inputs" in bad["details"]

    async def test_model_test_and_report_cache(self, model_runner, tmp_path):
        result, server = model_runner
        sid = result["service_id"]
        report = await call(server, sid, "test", model_id="tiny-unet")
        assert report["status"] == "passed"
        assert report["backend"] == "xla"
        assert report["output_matches_expected"] is True
        cache_file = (
            tmp_path / "model-cache" / "tiny-unet" / ".test_cache.json"
        )
        assert cache_file.exists()
        again = await call(server, sid, "test", model_id="tiny-unet")
        assert again == report

    async def test_infer_jax_model(self, model_runner, model_collection):
        result, server = model_runner
        sid = result["service_id"]
        x = np.load(model_collection / "tiny-unet" / "test_input.npy")
        expected = np.load(model_collection / "tiny-unet" / "test_output.npy")
        out = await call(server, sid, "infer", model_id="tiny-unet", inputs=x)
        assert out["_meta"]["backend"] == "xla"
        np.testing.assert_allclose(out["output0"], expected, rtol=1e-4, atol=1e-4)

    async def test_infer_volumetric_jax_model(self, model_runner, model_collection):
        # 3D family end to end: bczyx axes -> engine volume path -> back
        result, server = model_runner
        sid = result["service_id"]
        x = np.load(model_collection / "tiny-unet3d" / "test_input.npy")
        expected = np.load(model_collection / "tiny-unet3d" / "test_output.npy")
        out = await call(server, sid, "infer", model_id="tiny-unet3d", inputs=x)
        assert out["_meta"]["backend"] == "xla"
        assert np.asarray(out["output0"]).shape == expected.shape
        np.testing.assert_allclose(
            out["output0"], expected, rtol=1e-4, atol=1e-4
        )

    async def test_infer_torch_fallback(self, model_runner):
        result, server = model_runner
        sid = result["service_id"]
        x = np.full((1, 32, 32, 1), 3.0, np.float32)
        out = await call(server, sid, "infer", model_id="torch-square", inputs=x)
        assert out["_meta"]["backend"] == "torch"
        np.testing.assert_allclose(out["output0"], np.full_like(x, 18.0), rtol=1e-5)

    async def test_unpublished_model_rejected(self, model_runner):
        result, server = model_runner
        sid = result["service_id"]
        with pytest.raises(Exception, match="inference check"):
            await call(
                server, sid, "infer",
                model_id="secret-model",
                inputs=np.zeros((1, 32, 32, 1), np.float32),
            )

    async def test_upload_roundtrip(self, model_runner):
        result, server = model_runner
        sid = result["service_id"]
        slot = await call(server, sid, "get_upload_url", file_type=".npy")
        x = np.full((1, 32, 32, 1), 2.0, np.float32)
        buf = io.BytesIO()
        np.save(buf, x)
        await call(
            server, sid, "upload_image",
            file_path=slot["file_path"], data=buf.getvalue(),
        )
        out = await call(
            server, sid, "infer",
            model_id="torch-square", inputs=slot["file_path"],
        )
        np.testing.assert_allclose(out["output0"], np.full_like(x, 8.0), rtol=1e-5)

    async def test_upload_traversal_rejected(self, model_runner):
        result, server = model_runner
        sid = result["service_id"]
        for evil in ("../../etc/shadow", "../uploads-evil/x.npy"):
            with pytest.raises(Exception, match="escapes"):
                await call(
                    server, sid, "upload_image", file_path=evil, data=b"x"
                )

    async def test_list_cached_models(self, model_runner):
        result, server = model_runner
        sid = result["service_id"]
        await call(
            server, sid, "infer",
            model_id="tiny-unet",
            inputs=np.zeros((1, 64, 64, 1), np.float32),
        )
        cached = await call(server, sid, "list_cached_models")
        assert any(m["model_id"] == "tiny-unet" for m in cached)


class TestModelCacheProtocol:
    """ModelCache unit-level behavior (ref entry_deployment.py:73-1009)."""

    def _load_entry_module(self):
        import importlib.util

        path = REPO_APPS / "model-runner" / "entry_deployment.py"
        spec = importlib.util.spec_from_file_location("mr_entry", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    async def test_lru_eviction_respects_in_use(
        self, model_collection, tmp_path
    ):
        mod = self._load_entry_module()
        source = mod.LocalCollectionSource(model_collection)
        cache = mod.ModelCache(
            tmp_path / "cache", source, max_size_bytes=1  # force eviction
        )
        pkg = await cache.get_model_package("tiny-unet", allow_unpublished=True)
        async with pkg:
            # tiny-unet is in use: fetching another model must not evict it
            await cache.get_model_package("torch-square", allow_unpublished=True)
            assert pkg.path.exists()
        # not in use anymore: the next download evicts the LRU package
        await cache.get_model_package(
            "torch-square", allow_unpublished=True, skip_cache=True
        )
        assert not (tmp_path / "cache" / "tiny-unet").exists()

    async def test_stale_marker_recovery(self, model_collection, tmp_path):
        mod = self._load_entry_module()
        source = mod.LocalCollectionSource(model_collection)
        cache = mod.ModelCache(tmp_path / "cache", source)
        marker = cache._marker("tiny-unet", False)
        marker.touch()
        old = time.time() - mod.STALE_DOWNLOAD_SECONDS - 10
        os.utime(marker, (old, old))
        pkg = await cache.get_model_package("tiny-unet", allow_unpublished=True)
        assert pkg.path.exists()
        assert not marker.exists()

    async def test_url_as_model_id_rejected(self, model_collection, tmp_path):
        mod = self._load_entry_module()
        cache = mod.ModelCache(
            tmp_path / "cache", mod.LocalCollectionSource(model_collection)
        )
        with pytest.raises(ValueError, match="not a model id"):
            await cache.get_model_package("https://example.com/model")


# ---- cellpose-finetuning ----------------------------------------------------


def _synthetic_cells(n=2, size=64, seed=0):
    """Images with gaussian-blob cells + matching instance masks."""
    rng = np.random.default_rng(seed)
    images, masks = [], []
    yy, xx = np.mgrid[:size, :size]
    for _ in range(n):
        img = rng.normal(0.1, 0.02, (size, size)).astype(np.float32)
        mask = np.zeros((size, size), np.int32)
        for lbl, (cy, cx) in enumerate(
            [(16, 16), (16, 48), (48, 16), (48, 48)], start=1
        ):
            r2 = (yy - cy) ** 2 + (xx - cx) ** 2
            disk = r2 < 8**2
            img[disk] += 1.0
            mask[disk] = lbl
        images.append(img)
        masks.append(mask)
    return images, masks


FAST_CFG = {
    "features": [8, 16],
    "epochs": 2,
    "batch_size": 4,
    "tile": 32,
    "learning_rate": 1e-3,
}


@pytest.fixture
async def cellpose_app(stack, tmp_path):
    manager, _, server, _ = stack
    result = await deploy(
        manager,
        "cellpose-finetuning",
        deployment_kwargs={
            "main": {"sessions_root": str(tmp_path / "sessions")}
        },
    )
    return result, server


async def wait_for_status(server, sid, session_id, states, timeout=120):
    deadline = time.time() + timeout
    while time.time() < deadline:
        status = await call(
            server, sid, "get_training_status", session_id=session_id
        )
        if status["status"] in states:
            return status
        await asyncio.sleep(0.2)
    raise TimeoutError(f"session never reached {states}: {status}")


class TestCellposeFinetune:
    async def test_full_session_lifecycle(self, cellpose_app, tmp_path):
        result, server = cellpose_app
        sid = result["service_id"]
        images, masks = _synthetic_cells()

        started = await call(
            server, sid, "start_training",
            train_images=images, train_labels=masks, config=FAST_CFG,
            session_id="session-test",
        )
        assert started["status"] == "started"
        final = await wait_for_status(
            server, sid, "session-test", {"completed", "failed"}
        )
        assert final["status"] == "completed", final.get("error")
        assert final["current_epoch"] == 2
        assert len(final["losses"]) == 2
        # loss must decrease on this trivially-learnable data
        assert final["losses"][-1] < final["losses"][0]

        sessions = await call(server, sid, "list_sessions")
        assert sessions[0]["session_id"] == "session-test"
        assert sessions[0]["snapshots"] == 2

        out = await call(
            server, sid, "infer", session_id="session-test", images=images[:1]
        )
        assert out["masks"][0].shape == (64, 64)
        assert out["snapshot"] == "epoch_0001.npz"

        exported = await call(
            server, sid, "export_model", session_id="session-test"
        )
        export_dir = Path(exported["model_path"])
        assert (export_dir / "rdf.yaml").exists()
        assert (export_dir / "weights.npz").exists()

        # the export is a servable model-runner package: load it through
        # the runtime pipeline and predict
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "mr_rt", REPO_APPS / "model-runner" / "runtime_deployment.py"
        )
        rt = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(rt)
        pipeline = rt.Pipeline(export_dir)
        x = np.stack([np.stack([images[0], np.zeros_like(images[0])], -1)])
        pred = pipeline.predict(x)["output0"]
        assert pred.shape == (1, 64, 64, 3)

    async def test_infer_3d_do3d_recipe(self, cellpose_app):
        """Volumetric segmentation via the do_3D recipe: the 2D model
        runs over three slice orientations and voxels follow the
        aggregated 3D flow field."""
        result, server = cellpose_app
        sid = result["service_id"]
        images, masks = _synthetic_cells()
        await call(
            server, sid, "start_training",
            train_images=images, train_labels=masks, config=FAST_CFG,
            session_id="session-3d",
        )
        final = await wait_for_status(
            server, sid, "session-3d", {"completed", "failed"}
        )
        assert final["status"] == "completed", final.get("error")

        # a bright cube in a dim volume — shape checks, not accuracy
        # (FAST_CFG trains 2 epochs on synthetic blobs)
        vol = np.full((8, 32, 32), 0.1, np.float32)
        vol[2:6, 10:22, 10:22] = 1.0
        out = await call(
            server, sid, "infer_3d", session_id="session-3d",
            volumes=[vol.tolist()],
        )
        m = np.asarray(out["masks"][0])
        assert m.shape == (8, 32, 32)
        assert m.dtype.kind in "iu"
        assert out["n_cells"] == [int(m.max())]

        # anisotropic stacks resample along z and come back at the
        # caller's original depth
        out = await call(
            server, sid, "infer_3d", session_id="session-3d",
            volumes=[vol.tolist()], anisotropy=2.0,
        )
        assert np.asarray(out["masks"][0]).shape == (8, 32, 32)

        # extreme downsampling clamps to >= 1 plane instead of crashing
        out = await call(
            server, sid, "infer_3d", session_id="session-3d",
            volumes=[vol.tolist()], anisotropy=0.05,
        )
        assert np.asarray(out["masks"][0]).shape == (8, 32, 32)

        with pytest.raises(Exception, match="grayscale volumes"):
            await call(
                server, sid, "infer_3d", session_id="session-3d",
                volumes=[np.zeros((4, 4)).tolist()],
            )
        with pytest.raises(Exception, match="anisotropy"):
            await call(
                server, sid, "infer_3d", session_id="session-3d",
                volumes=[vol.tolist()], anisotropy=0.0,
            )

    async def test_stop_and_restart(self, cellpose_app):
        result, server = cellpose_app
        sid = result["service_id"]
        images, masks = _synthetic_cells()
        cfg = {**FAST_CFG, "epochs": 50}

        await call(
            server, sid, "start_training",
            train_images=images, train_labels=masks, config=cfg,
            session_id="session-stop",
        )
        # let at least one snapshot land, then stop
        deadline = time.time() + 120
        while time.time() < deadline:
            status = await call(
                server, sid, "get_training_status", session_id="session-stop"
            )
            if status.get("current_epoch", 0) >= 1:
                break
            await asyncio.sleep(0.2)
        stopped = await call(server, sid, "stop_training", session_id="session-stop")
        assert stopped["status"] in ("stopped", "completed")

        restarted = await call(
            server, sid, "restart_training", session_id="session-stop"
        )
        assert restarted["status"] == "restarted"
        status = await wait_for_status(
            server, sid, "session-stop",
            {"training", "completed", "stopped", "failed"},
        )
        assert status["status"] != "failed"
        await call(server, sid, "stop_training", session_id="session-stop")

    async def test_odd_image_size_tile_aligned(self, cellpose_app):
        """Images whose size is not a multiple of the U-Net divisor must
        train (tile rounds down to the divisor) instead of crashing on a
        skip-connection shape mismatch."""
        result, server = cellpose_app
        sid = result["service_id"]
        images, masks = _synthetic_cells(size=70)
        cfg = {**FAST_CFG, "features": [8, 16, 32], "tile": 30, "epochs": 1}

        await call(
            server, sid, "start_training",
            train_images=images, train_labels=masks, config=cfg,
            session_id="session-odd",
        )
        final = await wait_for_status(
            server, sid, "session-odd", {"completed", "failed"}
        )
        assert final["status"] == "completed", final.get("error")

    async def test_session_id_reuse_starts_fresh(self, cellpose_app):
        result, server = cellpose_app
        sid = result["service_id"]
        images, masks = _synthetic_cells(n=1)
        await call(
            server, sid, "start_training",
            train_images=images, train_labels=masks, config=FAST_CFG,
            session_id="session-reuse",
        )
        await wait_for_status(server, sid, "session-reuse", {"completed"})
        # reuse the id: stale snapshots from the first run must be gone
        await call(
            server, sid, "start_training",
            train_images=images, train_labels=masks,
            config={**FAST_CFG, "epochs": 1},
            session_id="session-reuse",
        )
        final = await wait_for_status(
            server, sid, "session-reuse", {"completed", "failed"}
        )
        assert final["status"] == "completed"
        assert final["current_epoch"] == 1
        sessions = await call(server, sid, "list_sessions")
        entry = next(
            s for s in sessions if s["session_id"] == "session-reuse"
        )
        assert entry["snapshots"] == 1

    async def test_unknown_session_rejected(self, cellpose_app):
        result, server = cellpose_app
        sid = result["service_id"]
        with pytest.raises(Exception, match="unknown session"):
            await call(server, sid, "get_training_status", session_id="nope")

    async def test_delete_session(self, cellpose_app, tmp_path):
        result, server = cellpose_app
        sid = result["service_id"]
        images, masks = _synthetic_cells(n=1)
        await call(
            server, sid, "start_training",
            train_images=images, train_labels=masks, config=FAST_CFG,
            session_id="session-del",
        )
        await wait_for_status(server, sid, "session-del", {"completed", "failed"})
        out = await call(server, sid, "delete_session", session_id="session-del")
        assert out == {"deleted": "session-del"}
        assert not (tmp_path / "sessions" / "session-del").exists()


class TestCellposeSettled:
    """Unit coverage for the status-file/task wind-down race: a terminal
    status.json lands a beat before the asyncio task resolves, and
    delete/restart/start must wait it out instead of erroring."""

    @pytest.fixture
    def app_cls(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "cellpose_main_unit", REPO_APPS / "cellpose-finetuning" / "main.py"
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _session(self, mod, tmp_path, status):
        s = mod.TrainingSession(tmp_path, "s1", {})
        s.write_status(status=status)
        return s

    async def test_terminal_status_waits_for_task_windup(self, app_cls, tmp_path):
        app = app_cls.CellposeFinetune(sessions_root=str(tmp_path))
        s = self._session(app_cls, tmp_path, "completed")
        s.task = asyncio.create_task(asyncio.sleep(0.3))  # still winding down
        app.sessions["s1"] = s
        out = await app.delete_session(session_id="s1")
        assert out == {"deleted": "s1"}
        assert not s.dir.exists()

    async def test_running_session_rejected_immediately(self, app_cls, tmp_path):
        app = app_cls.CellposeFinetune(sessions_root=str(tmp_path))
        s = self._session(app_cls, tmp_path, "training")
        s.task = asyncio.create_task(asyncio.sleep(30))
        app.sessions["s1"] = s
        with pytest.raises(RuntimeError, match="stop session"):
            await app.delete_session(session_id="s1")
        with pytest.raises(RuntimeError, match="still running"):
            await app.restart_training(session_id="s1")
        s.task.cancel()

    async def test_preparing_session_not_deletable(self, app_cls, tmp_path):
        app = app_cls.CellposeFinetune(sessions_root=str(tmp_path))
        s = self._session(app_cls, tmp_path, "initializing")
        s.preparing = True
        app.sessions["s1"] = s
        with pytest.raises(RuntimeError, match="stop session"):
            await app.delete_session(session_id="s1")

    async def test_concurrent_deletes_serialized(self, app_cls, tmp_path):
        # both suspend in the wind-down wait; the lifecycle lock makes
        # exactly one win — the loser gets a clean unknown-session error
        app = app_cls.CellposeFinetune(sessions_root=str(tmp_path))
        s = self._session(app_cls, tmp_path, "completed")
        s.task = asyncio.create_task(asyncio.sleep(0.3))
        app.sessions["s1"] = s
        results = await asyncio.gather(
            app.delete_session(session_id="s1"),
            app.delete_session(session_id="s1"),
            return_exceptions=True,
        )
        oks = [r for r in results if r == {"deleted": "s1"}]
        errs = [r for r in results if isinstance(r, KeyError)]
        assert len(oks) == 1 and len(errs) == 1, results

    async def test_readopted_session_deletable(self, app_cls, tmp_path):
        # re-adopted after an app restart: terminal status, no task
        app = app_cls.CellposeFinetune(sessions_root=str(tmp_path))
        s = self._session(app_cls, tmp_path, "interrupted")
        app.sessions["s1"] = s
        out = await app.delete_session(session_id="s1")
        assert out == {"deleted": "s1"}


class TestTpuTest:
    async def test_ping_and_device_probe(self, stack):
        manager, _, server, _ = stack
        result = await deploy(manager, "tpu-test")
        sid = result["service_id"]

        out = await call(server, sid, "ping")
        assert out["status"] == "ok"

        info = await call(server, sid, "tpu_info")
        # a dead backend raises; it is never a reply with an error string
        assert "error" not in info
        # hermetic suite runs on the 8-virtual-device CPU backend
        assert info["backend"] == "cpu"
        assert info["device_count"] == 8
        assert info["matmul_norm"] == pytest.approx(128.0 * 128.0, rel=1e-2)

        mem = await call(server, sid, "memory_info")
        assert len(mem["devices"]) == 8


class TestCellposeFrontend:
    """Browser-frontend e2e: the static page is served through the
    framework and its fetch endpoints (the JSON HTTP bridge) drive a
    full session lifecycle — parity target ref
    apps/cellpose-finetuning/frontend/index.html:1-1967."""

    async def test_static_page_served(self, cellpose_app):
        import aiohttp

        result, server = cellpose_app
        base = f"http://{server.host}:{server.port}"
        async with aiohttp.ClientSession() as http:
            async with http.get(f"{base}/apps/{result['app_id']}/") as r:
                assert r.status == 200
                text = await r.text()
            assert "Cellpose Fine-Tuning" in text
            # the page derives the service id from its own URL
            assert "/apps/" in text and "/call/" in text
            # interactive annotation (the reference UI's core workflow)
            assert 'data-tab="annotate"' in text
            assert "addToTrainingSet" in text
            # path escape is rejected
            async with http.get(
                f"{base}/apps/{result['app_id']}/..%2f..%2fmanifest.yaml"
            ) as r:
                assert r.status in (403, 404)

    async def test_frontend_url_in_deploy_and_status(self, cellpose_app):
        result, server = cellpose_app
        assert result["frontend_url"] == f"/apps/{result['app_id']}/"

    async def test_fetch_endpoints_full_lifecycle(self, cellpose_app):
        import aiohttp

        result, server = cellpose_app
        app_id = result["app_id"]
        base = f"http://{server.host}:{server.port}"
        images, masks = _synthetic_cells()
        # what the browser sends: nested JSON lists from canvas pixels
        images_json = [img.tolist() for img in images]
        masks_json = [m.tolist() for m in masks]

        async def post(method, **kwargs):
            async with http.post(
                f"{base}/call/{app_id}/{method}", json={"kwargs": kwargs}
            ) as r:
                data = await r.json()
                assert r.status == 200, data
                return data["result"]

        async with aiohttp.ClientSession() as http:
            cfg = await post("get_default_config")
            assert "epochs" in cfg

            started = await post(
                "start_training",
                train_images=images_json,
                train_labels=masks_json,
                config=FAST_CFG,
                session_id="frontend-run",
            )
            assert started["status"] == "started"

            deadline = time.time() + 120
            while True:
                status = await post(
                    "get_training_status", session_id="frontend-run"
                )
                if status["status"] in ("completed", "failed"):
                    break
                assert time.time() < deadline, status
                await asyncio.sleep(0.2)
            assert status["status"] == "completed", status.get("error")
            assert len(status["losses"]) == FAST_CFG["epochs"]

            sessions = await post("list_sessions")
            assert sessions[0]["session_id"] == "frontend-run"

            out = await post(
                "infer", session_id="frontend-run", images=images_json[:1]
            )
            # JSON bridge converts the numpy masks to nested lists
            assert isinstance(out["masks"][0], list)
            assert len(out["masks"][0]) == 64
            assert out["n_cells"][0] >= 0

            exported = await post("export_model", session_id="frontend-run")
            assert Path(exported["model_path"]).joinpath("rdf.yaml").exists()

    async def test_http_bridge_auth_errors(self, stack):
        """Bad token -> 401; unknown service -> 404."""
        import aiohttp

        _, _, server, _ = stack
        base = f"http://{server.host}:{server.port}"
        async with aiohttp.ClientSession() as http:
            async with http.post(
                f"{base}/call/nope/ping",
                json={},
                headers={"Authorization": "Bearer bogus"},
            ) as r:
                assert r.status == 401
            async with http.post(f"{base}/call/nope/ping", json={}) as r:
                assert r.status == 404


SAM_CFG = {
    "backbone": "sam",
    "patch_size": 4,
    "dim": 64,
    "depth": 2,
    "num_heads": 4,
    "epochs": 2,
    "batch_size": 4,
    "tile": 32,
    "learning_rate": 1e-3,
}


class TestCellposeSamBackbone:
    """The transformer backbone rides the whole session protocol: train,
    resume, live inference, export as a servable cellpose-sam package."""

    async def test_sam_session_train_infer_export(self, cellpose_app):
        result, server = cellpose_app
        sid = result["service_id"]
        images, masks = _synthetic_cells()

        started = await call(
            server, sid, "start_training",
            train_images=images, train_labels=masks, config=SAM_CFG,
            session_id="sam-run",
        )
        assert started["status"] == "started"
        final = await wait_for_status(
            server, sid, "sam-run", {"completed", "failed"}
        )
        assert final["status"] == "completed", final.get("error")
        assert final["losses"][-1] < final["losses"][0]

        out = await call(
            server, sid, "infer", session_id="sam-run", images=images[:1]
        )
        assert out["masks"][0].shape == (64, 64)

        exported = await call(
            server, sid, "export_model", session_id="sam-run",
            model_name="sam-export",
        )
        import yaml as _yaml

        rdf = _yaml.safe_load(
            (Path(exported["model_path"]) / "rdf.yaml").read_text()
        )
        arch = rdf["weights"]["jax_params"]["architecture"]
        assert arch["name"] == "cellpose-sam"
        assert arch["kwargs"]["patch_size"] == 4

        # the export is servable by the model-runner registry path
        from bioengine_tpu.models import get_model
        from bioengine_tpu.runtime.convert import load_params_npz

        import jax

        model = get_model(arch["name"], **arch["kwargs"])
        params = load_params_npz(
            str(Path(exported["model_path"]) / "weights.npz")
        )
        pred = model.apply(
            {"params": params},
            jax.numpy.zeros((1, 32, 32, 2), jax.numpy.float32),
        )
        assert pred.shape == (1, 32, 32, 3)


class TestStardistBackbone:
    """Star-convex polygons as a fine-tuning family — beyond the
    reference app (cellpose-only): targets are edt-prob + ray
    distances, the train step is the stardist objective, and inference
    reconstructs instances through polygon NMS."""

    # steps_per_epoch is tiny on 2 images (2 steps at tile 32), and the
    # stardist objective needs ~100 steps before polygons clear NMS on
    # this data (verified against a direct-train baseline), hence the
    # higher epoch count — each epoch is milliseconds at this size
    CFG = {
        "backbone": "stardist",
        "features": [8, 16],
        "n_rays": 8,
        "epochs": 50,
        "batch_size": 4,
        "tile": 32,
        "learning_rate": 2e-3,
    }

    async def test_stardist_session_train_infer_export(self, cellpose_app):
        result, server = cellpose_app
        sid = result["service_id"]
        images, masks = _synthetic_cells()

        started = await call(
            server, sid, "start_training",
            train_images=images, train_labels=masks, config=self.CFG,
            session_id="stardist-run",
        )
        assert started["status"] == "started"
        final = await wait_for_status(
            server, sid, "stardist-run", {"completed", "failed"}
        )
        assert final["status"] == "completed", final.get("error")
        assert final["losses"][-1] < final["losses"][0]

        # a few epochs on tiny data leave prob logits shy of 0 — the
        # caller-facing logit threshold works for stardist exactly like
        # for cellpose, so a permissive smoke threshold finds polygons
        out = await call(
            server, sid, "infer", session_id="stardist-run",
            images=images[:1], cellprob_threshold=-3.0,
        )
        assert out["masks"][0].shape == (64, 64)
        assert out["n_cells"][0] >= 1

        # volumetric recipe needs flows — clean rejection, not a crash
        with pytest.raises(Exception, match="do_3D|polygons"):
            await call(
                server, sid, "infer_3d", session_id="stardist-run",
                volumes=[np.zeros((4, 32, 32), np.float32)],
            )

        exported = await call(
            server, sid, "export_model", session_id="stardist-run",
            model_name="stardist-export",
        )
        import yaml as _yaml

        rdf = _yaml.safe_load(
            (Path(exported["model_path"]) / "rdf.yaml").read_text()
        )
        arch = rdf["weights"]["jax_params"]["architecture"]
        assert arch["name"] == "stardist2d"
        assert arch["kwargs"]["n_rays"] == 8

        # the export is servable by the model-runner registry path
        import jax

        from bioengine_tpu.models import get_model
        from bioengine_tpu.runtime.convert import load_params_npz

        model = get_model(arch["name"], **arch["kwargs"])
        params = load_params_npz(
            str(Path(exported["model_path"]) / "weights.npz")
        )
        pred = model.apply(
            {"params": params},
            jax.numpy.zeros((1, 32, 32, 2), jax.numpy.float32),
        )
        assert pred.shape == (1, 32, 32, 1 + 8)

    async def test_odd_n_rays_rejected_synchronously(self, cellpose_app):
        """Config validation happens in start_training itself — before
        the expensive target derivation runs — not asynchronously in
        the train thread."""
        result, server = cellpose_app
        sid = result["service_id"]
        images, masks = _synthetic_cells()
        with pytest.raises(Exception, match="n_rays must be an even"):
            await call(
                server, sid, "start_training",
                train_images=images, train_labels=masks,
                config={**self.CFG, "n_rays": 7},
                session_id="stardist-odd",
            )


class TestFinetuneExportServedByModelRunner:
    """Cross-app path the reference implements via the BioImage Model
    Zoo: a model fine-tuned in one app is exported and served by the
    model-runner (ref main.py:4413+ uploads to the zoo; here the
    export directory IS a collection entry)."""

    async def test_stardist_export_roundtrips_through_model_runner(
        self, cellpose_app, stack, tmp_path, monkeypatch
    ):
        result, server = cellpose_app
        sid = result["service_id"]
        images, masks = _synthetic_cells()
        cfg = {
            "backbone": "stardist", "features": [8, 16], "n_rays": 8,
            "epochs": 2, "batch_size": 4, "tile": 32,
            "learning_rate": 1e-3,
        }
        await call(
            server, sid, "start_training",
            train_images=images, train_labels=masks, config=cfg,
            session_id="sd-export",
        )
        final = await wait_for_status(
            server, sid, "sd-export", {"completed", "failed"}
        )
        assert final["status"] == "completed", final.get("error")
        exported = await call(
            server, sid, "export_model", session_id="sd-export",
            model_name="sd-served",
        )

        # the export dir is a collection entry: point the model-runner
        # at its parent and serve it
        collection = Path(exported["model_path"]).parent
        monkeypatch.setenv("BIOENGINE_LOCAL_MODEL_PATH", str(collection))
        manager, _, _, _ = stack
        mr = await deploy(
            manager,
            "model-runner",
            deployment_kwargs={
                "entry_deployment": {
                    "cache_dir": str(tmp_path / "model-cache")
                }
            },
        )
        x = np.stack(
            [images[0], np.zeros_like(images[0])], axis=-1
        )[None].astype(np.float32)
        out = await call(
            server, mr["service_id"], "infer",
            model_id="sd-served", inputs=x,
        )
        assert out["_meta"]["backend"] == "xla"
        assert np.asarray(out["output0"]).shape == (1, 64, 64, 9)


CPSAM_TINY = {
    "patch_size": 8,
    "dim": 32,
    "depth": 2,
    "num_heads": 2,
    "window_size": 2,
    "global_attn_indexes": [1],
    "neck_dim": 16,
    "pretrain_grid": 4,
}


class TestCellposeCpsamPretrained:
    """Fine-tuning starts from CONVERTED pretrained weights — the
    reference app's entire value proposition (it fine-tunes the cpsam
    foundation model, ref apps/cellpose-finetuning/main.py:2248). A
    synthetic checkpoint in the cpsam torch layout is converted to
    jax_params and a session launched with ``pretrained_path`` must
    train FROM those weights, not random init."""

    def _converted(self, tmp_path):
        from bioengine_tpu.runtime.convert import (
            convert_state_dict,
            cpsam_name_map,
            save_params_npz,
            synthetic_cpsam_state_dict,
        )

        sd = synthetic_cpsam_state_dict(
            **{k: (tuple(v) if isinstance(v, list) else v)
               for k, v in CPSAM_TINY.items()}
        )
        params = convert_state_dict(sd, cpsam_name_map(depth=2), strict=True)
        path = tmp_path / "cpsam_tiny.npz"
        save_params_npz(str(path), params)
        return path, params

    async def test_session_starts_from_converted_weights(
        self, cellpose_app, tmp_path
    ):
        result, server = cellpose_app
        sid = result["service_id"]
        images, masks = _synthetic_cells()
        path, converted = self._converted(tmp_path)

        # lr=0 freezes training: the session's snapshot must equal the
        # converted checkpoint EXACTLY — proof it started from it
        cfg = {
            **CPSAM_TINY,
            "backbone": "cpsam",
            "pretrained_path": str(path),
            "learning_rate": 0.0,
            "weight_decay": 0.0,
            "epochs": 1,
            "batch_size": 2,
            "tile": 16,
        }
        started = await call(
            server, sid, "start_training",
            train_images=images, train_labels=masks, config=cfg,
            session_id="cpsam-pre",
        )
        assert started["status"] == "started"
        final = await wait_for_status(
            server, sid, "cpsam-pre", {"completed", "failed"}
        )
        assert final["status"] == "completed", final.get("error")

        from bioengine_tpu.runtime.convert import (
            flatten_params,
            load_params_npz,
        )

        exported = await call(
            server, sid, "export_model", session_id="cpsam-pre",
            model_name="cpsam-pre-export",
        )
        got = flatten_params(
            load_params_npz(str(Path(exported["model_path"]) / "weights.npz"))
        )
        want = flatten_params(converted)
        assert set(got) == set(want)
        np.testing.assert_allclose(
            got["encoder/block0/attn/qkv/kernel"],
            want["encoder/block0/attn/qkv/kernel"],
            rtol=0, atol=0,
        )
        np.testing.assert_allclose(
            got["out/kernel"], want["out/kernel"], rtol=0, atol=0
        )

        # live inference works off the pretrained-initialized snapshot
        out = await call(
            server, sid, "infer", session_id="cpsam-pre", images=images[:1]
        )
        assert out["masks"][0].shape == (64, 64)

    async def test_wrong_architecture_checkpoint_fails_loudly(
        self, cellpose_app, tmp_path
    ):
        result, server = cellpose_app
        sid = result["service_id"]
        images, masks = _synthetic_cells()
        path, _ = self._converted(tmp_path)

        cfg = {
            **CPSAM_TINY,
            "dim": 64,  # architecture no longer matches the checkpoint
            "backbone": "cpsam",
            "pretrained_path": str(path),
            "epochs": 1,
            "batch_size": 2,
            "tile": 16,
        }
        await call(
            server, sid, "start_training",
            train_images=images, train_labels=masks, config=cfg,
            session_id="cpsam-bad",
        )
        final = await wait_for_status(
            server, sid, "cpsam-bad", {"completed", "failed"}
        )
        assert final["status"] == "failed"
        assert "does not match the configured architecture" in final["error"]


class TestAppFrontends:
    """Every bundled app with a reference-frontend analog ships one,
    staged by the builder and served at /apps/{app_id}/ (parity: the
    reference has frontends for demo-app, composition-demo,
    cell-image-search, fibsem-mito-analysis, cellpose-finetuning)."""

    FRONTEND_APPS = [
        "demo-app",
        "composition-demo",
        "cell-image-search",
        "fibsem-mito-analysis",
        "cellpose-finetuning",
    ]

    def test_all_frontends_exist_and_are_selfcontained(self):
        for app in self.FRONTEND_APPS:
            page = (REPO_APPS / app / "frontend" / "index.html").read_text()
            assert "/call/" in page, app          # drives the HTTP bridge
            assert "http://" not in page.replace(
                "http://localhost", ""
            ) or "cdn" not in page.lower(), app   # no external CDNs
            assert "<script>" in page, app

    async def test_demo_app_frontend_served_and_driven(self, stack):
        import aiohttp

        from bioengine_tpu.utils.permissions import create_context

        manager, _, server, _ = stack
        result = await manager.deploy_app(
            local_path=str(REPO_APPS / "demo-app"),
            context=create_context("admin"),
        )
        app_id = result["app_id"]
        base = f"http://{server.host}:{server.port}"
        async with aiohttp.ClientSession() as http:
            async with http.get(f"{base}/apps/{app_id}/") as r:
                assert r.status == 200
                assert "Demo App" in await r.text()
            # the page's calls: ping + echo through the bridge
            async with http.post(
                f"{base}/call/{app_id}/ping", json={}
            ) as r:
                assert (await r.json())["result"]["pong"] is True
            async with http.post(
                f"{base}/call/{app_id}/echo",
                json={"kwargs": {"message": "ui"}},
            ) as r:
                assert (await r.json())["result"]["echo"] == "ui"

    async def test_composition_frontend_served_and_driven(self, stack):
        import aiohttp

        from bioengine_tpu.utils.permissions import create_context

        manager, _, server, _ = stack
        result = await manager.deploy_app(
            local_path=str(REPO_APPS / "composition-demo"),
            context=create_context("admin"),
        )
        app_id = result["app_id"]
        base = f"http://{server.host}:{server.port}"
        async with aiohttp.ClientSession() as http:
            async with http.get(f"{base}/apps/{app_id}/") as r:
                assert r.status == 200
                assert "Composition" in await r.text()
            async with http.post(
                f"{base}/call/{app_id}/fan_out",
                json={"kwargs": {"value": 7}},
            ) as r:
                data = (await r.json())["result"]
                assert data["sum"] == data["a"] + data["b"]


class TestContinuousBatchingInRuntime:
    """Concurrent predicts against the same model+shape run as one
    batched engine call (serving/batching.py wired into the runtime —
    the reference forwards each request individually)."""

    async def test_concurrent_predicts_batch_and_match_direct(
        self, model_collection
    ):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "mr_rt2", REPO_APPS / "model-runner" / "runtime_deployment.py"
        )
        rt = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(rt)

        dep = rt.RuntimeDeployment(batch_max=8, batch_wait_ms=50.0)
        await dep.async_init()
        rdf_path = str(model_collection / "tiny-unet")
        rng = np.random.default_rng(0)
        xs = [
            rng.normal(size=(1, 64, 64, 1)).astype(np.float32)
            for _ in range(6)
        ]

        # direct (unbatched) references, one by one
        direct = []
        for x in xs:
            out = await dep.predict(rdf_path, x)
            direct.append(out["output0"])

        # concurrent: all six in flight -> grouped flushes
        before = dep._batcher.stats
        outs = await asyncio.gather(
            *[dep.predict(rdf_path, x) for x in xs]
        )
        after = dep._batcher.stats
        grouped_requests = after["batched_requests"] - before["batched_requests"]
        grouped_batches = after["batches"] - before["batches"]
        assert grouped_requests == 6
        assert grouped_batches < 6, "no batching happened"

        for got, want in zip(outs, direct):
            np.testing.assert_allclose(
                got["output0"], want, rtol=1e-4, atol=1e-4
            )
        assert all(o["_meta"]["backend"] for o in outs)

    async def test_mismatched_shapes_do_not_cross_batch(
        self, model_collection
    ):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "mr_rt3", REPO_APPS / "model-runner" / "runtime_deployment.py"
        )
        rt = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(rt)

        dep = rt.RuntimeDeployment(batch_max=8, batch_wait_ms=50.0)
        await dep.async_init()
        rdf_path = str(model_collection / "tiny-unet")
        rng = np.random.default_rng(1)
        a = rng.normal(size=(1, 64, 64, 1)).astype(np.float32)
        b = rng.normal(size=(1, 32, 32, 1)).astype(np.float32)
        ra, rb = await asyncio.gather(
            dep.predict(rdf_path, a), dep.predict(rdf_path, b)
        )
        assert ra["output0"].shape[1:3] == (64, 64)
        assert rb["output0"].shape[1:3] == (32, 32)


class TestTutorialNotebook:
    """The cellpose tutorial notebook executes end to end (the
    reference ships a tutorial notebook against hosted Hypha; ours is
    self-contained and therefore runnable in CI)."""

    async def _run_notebook(self, nb_path, tmp_path, must_contain):
        import json
        import subprocess
        import sys

        nb = json.loads(nb_path.read_text())
        code = "\n\n".join(
            "".join(c["source"])
            for c in nb["cells"]
            if c["cell_type"] == "code"
        )
        script = tmp_path / (nb_path.stem + ".py")
        script.write_text(code)
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            BIOENGINE_WORKSPACE=str(tmp_path / "ws"),
            PYTHONPATH=str(REPO_APPS.parent),
        )
        env.pop("BIOENGINE_SERVER_URL", None)
        proc = await asyncio.to_thread(
            subprocess.run,
            [sys.executable, str(script)],
            capture_output=True,
            text=True,
            timeout=420,
            env=env,
            cwd=str(REPO_APPS.parent),
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "done" in proc.stdout
        for needle in must_contain:
            assert needle in proc.stdout, proc.stdout[-1500:]

    async def test_cellpose_notebook_executes(self, tmp_path):
        await self._run_notebook(
            REPO_APPS / "cellpose-finetuning"
            / "tutorial_cellpose_finetuning.ipynb",
            tmp_path,
            ["cells found:"],
        )

    async def test_search_notebook_executes(self, tmp_path):
        await self._run_notebook(
            REPO_APPS / "cell-image-search"
            / "tutorial_cell_image_search.ipynb",
            tmp_path,
            ["index:", "matches:", "projection points:"],
        )

    async def test_demo_notebook_executes(self, tmp_path):
        await self._run_notebook(
            REPO_APPS / "demo-app" / "tutorial.ipynb",
            tmp_path,
            ["over websocket", "over http", "over mcp"],
        )
