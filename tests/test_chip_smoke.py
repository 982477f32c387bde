"""chip_smoke.py rehearsed on the CPU mesh.

The script itself has no CPU mode (run with JAX_PLATFORMS=cpu it fails
at its device gate). Its phase functions take the platform and the
model width as arguments, so the serving half — start → package →
deploy → serve → stop — runs here at toy width before chip time is
spent on it, and the kernel phase with the kernel interpreted at toy
shapes. The trace phase, and Mosaic, need the chip.
"""

from __future__ import annotations

import pytest

import chip_smoke

pytestmark = [pytest.mark.integration, pytest.mark.anyio]


def test_script_refuses_the_cpu():
    with pytest.raises(RuntimeError, match="platform is 'cpu', need 'tpu'"):
        chip_smoke.main([])


async def test_serving_phases_at_toy_width(tmp_path, monkeypatch, capsys):
    # make_package points this variable at its collection; restore it
    monkeypatch.setenv("BIOENGINE_LOCAL_MODEL_PATH", "")
    cfg = chip_smoke.SmokeConfig(
        platform="cpu",
        features=(8, 16),
        tile=64,
        big=1100,  # above EngineConfig.max_tile: the tiled pipeline runs
        out_dir=tmp_path,
    )
    report = chip_smoke.Report()
    try:
        chip_smoke.device_gate(cfg, report)
        async with chip_smoke.worker_session(cfg, report) as (conn, worker_sid):
            model, params = chip_smoke.make_package(cfg, report)
            app_id, app_sid = await chip_smoke.deploy(
                cfg, report, conn, worker_sid
            )
            await chip_smoke.serve(
                cfg, report, conn, worker_sid, app_id, app_sid, model, params
            )
    finally:
        report.close()
    assert report.device == {"platform": "cpu", "kind": "cpu", "count": 8}
    phases = [
        line.split()[1].rstrip(":")
        for line in capsys.readouterr().out.splitlines()
        if line.startswith("[chip_smoke]") and " ok " in line
    ]
    assert phases == ["gate", "start", "package", "deploy", "serve", "stop"]
    assert (tmp_path / "outputs-chips1.npz").is_file()


def test_kernel_phase_at_toy_size(tmp_path, capsys):
    """The kernel phase with the kernel interpreted: a plain shape whose
    N is no multiple of the block, and the folded form the served cpsam
    program ran (q/k depth != v depth, scale 1), forward and gradient,
    both also causal; the packed call it runs now, on a grid of
    unequal extents; and the MLP kernel over three row tiles and three
    hidden blocks."""
    cfg = chip_smoke.SmokeConfig(
        platform="cpu",
        out_dir=tmp_path,
        kernel_shapes=((1, 2, 100, 32, 32, None), (2, 2, 64, 48, 16, 1.0)),
        packed_shapes=((1, 2, (16, 48)),),
        mlp_shapes=(((3, 8, 16, 128), 384),),
    )
    report = chip_smoke.Report()
    try:
        chip_smoke.kernel(cfg, report)
    finally:
        report.close()
    (line,) = [
        line
        for line in capsys.readouterr().out.splitlines()
        if line.startswith("[chip_smoke] kernel")
    ]
    assert " ok " in line
    for tag in (
        "1x2x100x32/32", "2x2x64x48/16-causal", "packed-1x2x16x48",
        "mlp-3x8x16x128/384",
    ):
        assert tag in line
