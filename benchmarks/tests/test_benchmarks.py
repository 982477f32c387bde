"""The yardstick, checked where no chip is needed.

Run with ``python -m pytest benchmarks/tests -q`` (CPU, about two
minutes). The harness is rehearsed end to end at toy widths through
``run_cell(platform="cpu")``, an argument the command line does not
have; nothing here prints or asserts a device metric.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import re
from pathlib import Path

import numpy as np
import pytest

from benchmarks import harness, trace_reduce, window_metrics
from benchmarks.generators import closed_loop
from benchmarks.paths import infer
from benchmarks.references import _common, cpsam
from benchmarks.tests import toy_overlay
from benchmarks.work import cpsam as cpsam_work

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SEED = 2**31 + 4242  # the driver's seeds pass 32 signed bits
PARAMS = 304_651_267  # cpsam-vitl: 63-row relative-position tables in every block

# 64 px native tiles (8x8 tokens), global attention in both blocks
TOY_CPSAM = dict(
    dim=64, depth=2, num_heads=2, neck_dim=32, pretrain_grid=8,
    window_size=0, global_attn_indexes=[0, 1],
)


def toy_cell() -> harness.Cell:
    """The real cell with its widths and sizes cut to what a CPU holds."""
    cell = harness.load_cell("cpsam-vitl.fov")
    cell.config.update(TOY_CPSAM, native_tile=64)
    # blocksize 64 makes the program tile at toy size; at a blocksize
    # no larger than the 64 px overlap it uses blocksize // 8
    cell.config["engine"] = {
        "tile": 64, "max_tile": 96, "tile_overlap": 8, "tile_batch": 16,
    }
    cell.traffic = {
        "generator": "closed_loop", "clients": 3, "blocksize": 64, "order": 1,
        "lead_in_s": 0.5,
        "deck": [{"items": 1, "size": 128, "count": 2},
                 {"items": 1, "size": 160, "count": 1}],
        "pool": 2, "check_per_size": 2,
    }
    return cell


# ---- BENCHMARK.json ------------------------------------------------------------


def expected_of(cell: str) -> dict:
    """What the tests expect of a cell: a file of the cell's own, so that
    a PR that adds a cell adds this file and edits no test."""
    path = BENCH / "fixtures" / "cells" / f"{cell}.json"
    assert path.is_file(), (
        f"cell {cell!r} has no benchmarks/fixtures/cells/{cell}.json: add it, with "
        '"programs" (the program keys its mix can form), "limits" (the keys of '
        'its configuration\'s limits) and, for the image path, "tiles" (side -> '
        "count of pieces)"
    )
    return json.loads(path.read_text())


def test_manifest_names_files_and_moves():
    m = harness.load_manifest()
    assert set(m) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= m["run_seconds"] <= 51
    in_paths = lambda f: any(f.startswith(p + "/") for p in m["paths"])  # noqa: E731
    assert in_paths(m["command"][1])
    configs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        assert NAME.match(c["name"]) and in_paths(c["file"])
        body = json.loads((REPO / c["file"]).read_text())
        assert body["reduced"] == c["reduced"]
        for module in ("references", "work"):
            key = "reference" if module == "references" else "work"
            assert (BENCH / module / f"{body[key]}.py").is_file()
        assert (BENCH / "paths" / f"{body['deployment']['path']}.py").is_file()
    cells = {w["name"] for w in m["workloads"]}
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (BENCH / "generators" / f"{traffic['generator']}.py").is_file()
    assert {w["config"] for w in m["workloads"]} == set(configs)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e
    for e in m["end_to_end"]:
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher") and 0 < e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    layers = set()
    for p in m["per_layer"]:
        assert NAME.match(p["name"]) and UNIT.match(p["unit"])
        assert set(p) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads",
        }
        assert (BENCH / "layer_metrics" / f"{p['name']}.py").is_file()
        assert p["moves"] in e2e and set(p["workloads"]) <= cells
        # every cell that reports the metric reports what it moves
        moved = e2e[p["moves"]].get("workloads", sorted(cells))
        assert set(p["workloads"]) <= set(moved)
        layers.add(p["layer"])
    perf = (REPO / "PERF.md").read_text()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
    for cell in cells:
        assert any(cell in p["workloads"] for p in m["per_layer"])


def cells() -> list[str]:
    return [w["name"] for w in harness.load_manifest()["workloads"]]


def test_every_cell_loads_and_its_programs_enumerate():
    assert cells()
    for name in cells():
        want = expected_of(name)
        cell = harness.load_cell(name)
        assert sorted(map(list, cell.path.programs(cell))) == sorted(want["programs"])
        assert sorted(cell.config["limits"]) == sorted(want["limits"])
        assert set(cell.end_to_end) >= {"latency_p50_ms", "setup_s"}
        if cell.path.THROUGHPUT:
            assert cell.path.THROUGHPUT[0] in cell.end_to_end
    with pytest.raises(AssertionError, match="fixtures/cells/no-such.cell.json: add it"):
        expected_of("no-such.cell")


def test_the_image_path_enumerates_the_co_batched_buckets():
    # requests the client has cut itself are co-batched by the runtime:
    # every bucket up to slots x the largest request
    cell = harness.load_cell("cpsam-vitl.fov")
    cell.traffic = {
        "blocksize": None,
        "deck": [{"items": n, "size": 256, "count": 1} for n in (1, 2, 4)],
    }
    assert set(infer.programs(cell)) == {
        (b, 256, 256, 3) for b in (1, 2, 4, 8, 16)
    }


def test_tile_counts_of_the_mixes():
    counted = 0
    for name in cells():
        tiles = expected_of(name).get("tiles")
        if tiles:  # a cell of the image path whose requests the server cuts
            tile, _, overlap = infer.tiling_of(harness.load_cell(name))
            for side, count in tiles.items():
                assert _common.n_tiles(int(side), int(side), tile, overlap) == count
                counted += 1
    assert counted
    assert [_common.n_tiles(s, s, 512, 64) for s in (1200, 1536, 2048)] == [9, 16, 25]
    assert _common.tile_starts(1024, 256, 64) == [0, 192, 384, 576, 768]


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


# ---- traffic -------------------------------------------------------------------


def test_images_follow_the_seed_and_the_order_of_sizes_does_not():
    traffic = json.loads((BENCH / "traffic" / "fov.json").read_text())
    traffic["deck"] = [{**e, "size": e["size"] // 8} for e in traffic["deck"]]
    config = {"in_channels": 3}
    a = closed_loop.plan(traffic, config, SEED)
    b = closed_loop.plan(traffic, config, SEED)
    c = closed_loop.plan(traffic, config, SEED + 1)
    assert a.clients == b.clients
    for kind in a.pool:
        for x, y in zip(a.pool[kind], b.pool[kind]):
            np.testing.assert_array_equal(x, y)
        assert not np.array_equal(a.pool[kind][0], c.pool[kind][0])
    # every seed sends the same sizes in the same order, client by client
    sizes = lambda plan: [[r.kind for r in cl] for cl in plan.clients]  # noqa: E731
    assert sizes(a) == sizes(c)
    assert a.clients != c.clients  # which image of the pool: the seed's
    assert len({tuple(cl) for cl in sizes(a)}) == 8  # an order of each client's own
    assert len(a.clients) == 8 and len(a.clients[0]) == 10
    assert sorted(sizes(a)[0]) == [(1, 64)] * 4 + [(1, 96)] * 3 + [(1, 128)] * 3
    other = closed_loop.plan({**traffic, "order": traffic["order"] + 1}, config, SEED)
    assert sizes(other) != sizes(a)


def test_the_sample_is_drawn_by_deck_item_from_the_seed():
    """``correct`` compares the last reply of (client, position in its
    deck) items drawn from the seed before the run: two plans of one seed
    draw the same items, which send the same inputs however the replies
    are timed, and every kind is in the sample ``per_size`` times."""
    traffic = json.loads((BENCH / "traffic" / "fov.json").read_text())
    traffic["deck"] = [{**e, "size": e["size"] // 8} for e in traffic["deck"]]
    a, b = (closed_loop.plan(traffic, {"in_channels": 3}, SEED) for _ in "ab")
    drawn = harness.draw_sample(a, 2, SEED)
    assert drawn == harness.draw_sample(b, 2, SEED)
    assert [a.clients[c][p] for c, p in drawn] == [b.clients[c][p] for c, p in drawn]
    assert [a.clients[c][p].kind for c, p in drawn] == sorted(closed_loop.kinds(traffic) * 2)
    assert len(set(drawn)) == len(drawn)
    assert harness.draw_sample(a, 2, SEED + 1) != drawn


def test_a_request_of_the_sample_never_answered_is_not_correct(monkeypatch, tmp_path):
    """The sample holds a position no client reaches: nothing to compare
    there, so the run is not correct, whatever the others read."""
    drawn = harness.draw_sample
    monkeypatch.setattr(
        harness, "draw_sample", lambda plan, n, seed: [*drawn(plan, n, seed), (0, 10**6)]
    )
    line = harness.run_cell(
        toy_cell(), SEED + 5, 1.0, False, platform="cpu", out_dir=tmp_path
    )
    assert line["correct"] is False and line["failed"] == 0 < line["attempted"]
    assert line["checks"]["rel_l2"][0] == harness.NOT_COMPARABLE


def test_the_tail_reader_reads_every_request_of_the_window():
    """``request_p95_ms`` is the 95th percentile over the window's
    requests, a failed one counted as the window's length, and reads
    nothing where the window holds no request."""
    from benchmarks.layer_metrics import request_p95_ms

    def record(end, latency, ok=True):
        return dict(start=end - latency / 1000, end=end, latency_ms=latency, ok=ok, work=1.0)

    inside = [record(1.0 + i / 100, 100.0 + i) for i in range(19)]
    run = harness.RunData(
        cell=None, seconds=2.0, window=(1.0, 3.0), counters={},
        compiles_in_window=0,
        requests=[record(0.5, 9999.0), *inside, record(2.9, 50.0, ok=False)],
    )
    latencies = [100.0 + i for i in range(19)] + [2000.0]
    assert request_p95_ms.read(run) == window_metrics.percentile(latencies, 95)
    assert request_p95_ms.read(dataclasses.replace(run, requests=[])) is None


def test_the_window_opens_on_clients_in_their_stride():
    """The clients loop through the lead-in before the window opens;
    ``on_open`` runs at its opening, and the requests keep coming until
    its close."""
    import asyncio
    import time

    traffic = json.loads((BENCH / "traffic" / "fov.json").read_text())
    traffic.update(lead_in_s=0.2, deck=[{"items": 1, "size": 8, "count": 2}])
    plan = closed_loop.plan(traffic, {"in_channels": 3}, SEED)
    sent, opened = [], []

    async def send(c, n, request):
        sent.append((time.perf_counter(), c, n))
        await asyncio.sleep(0.01)
        return {}

    async def on_open():
        opened.append(time.perf_counter())

    began = time.perf_counter()
    start, end = asyncio.run(closed_loop.drive(plan, send, 0.3, on_open))
    assert end - start == pytest.approx(0.3) and start - began == pytest.approx(0.2, abs=0.05)
    assert len(opened) == 1 and 0 <= opened[0] - start < 0.1
    before = [s for s in sent if s[0] < start]
    assert {c for _, c, _ in before} == set(range(8))  # every client, already looping
    assert max(t for t, _, _ in sent) < end and any(t > end - 0.05 for t, _, _ in sent)
    # each client counts on through its deck, lead-in and window alike
    assert all(
        [n for _, c, n in sent if c == client] == list(range(sum(c == client for _, c, _ in sent)))
        for client in range(8)
    )


# ---- the command line ------------------------------------------------------------


def test_command_line_refuses_a_machine_without_the_chip(capsys):
    from benchmarks import run

    with pytest.raises(RuntimeError, match="need 'tpu'"):
        run.main(["--workload", "cpsam-vitl.fov", "--seed", "1", "--seconds", "1"])
    assert capsys.readouterr().out == ""


# ---- references against the program, f32, toy size --------------------------------


def test_reference_equals_the_program_in_float32():
    import jax
    import jax.numpy as jnp

    from bioengine_tpu.models.registry import get_model
    from bioengine_tpu.runtime.convert import unflatten_params

    ref, kwargs, shape = cpsam, dict(TOY_CPSAM, pretrain_grid=16), (2, 128, 128, 3)
    model = get_model("cpsam", dtype=jnp.float32, **kwargs)
    weights = _common.make_weights(ref.param_shapes(kwargs, shape[-1]), SEED)
    declared = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros(shape))
    )["params"]
    assert {
        "/".join(str(k.key) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(declared)[0]
    } == {k: v.shape for k, v in weights.items()}
    x = jax.random.normal(jax.random.key(1), shape)
    with jax.default_matmul_precision("highest"):
        got = model.apply(
            {"params": unflatten_params({k: np.asarray(v) for k, v in weights.items()})}, x
        )
    want = ref.forward(weights, x, kwargs, "f32")
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))
    # the lower precisions move the answer, each more than the one above
    bf16 = ref.forward(weights, x, kwargs, "bf16")
    fp8 = ref.forward(weights, x, kwargs, "fp8")
    gap = lambda y: float(jnp.linalg.norm(y - want) / jnp.linalg.norm(want))  # noqa: E731
    assert 1e-4 < gap(bf16) < gap(fp8) / 3


def test_the_reference_has_no_windows():
    """cpsam attends globally in every block; SAM's own pattern (the
    program's default for CpSAM) is another model and is refused."""
    with pytest.raises(ValueError, match="globally"):
        cpsam.param_shapes(dict(window_size=14, global_attn_indexes=[5, 11, 17, 23]), 3)
    config = harness.load_cell("cpsam-vitl.fov").config
    assert config["window_size"] == 0
    assert config["global_attn_indexes"] == list(range(config["depth"]))
    shapes = cpsam.param_shapes(_common.model_kwargs(config), 3)
    assert {shapes[f"encoder/block{i}/attn/rel_pos_h"] for i in range(24)} == {(63, 64)}


def test_weights_follow_the_seed():
    shapes = {"a/kernel": (4, 8), "a/bias": (8,), "n/scale": (8,)}
    a, b = _common.make_weights(shapes, SEED), _common.make_weights(shapes, SEED)
    c = _common.make_weights(shapes, SEED + 2**31)
    for k in shapes:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
        assert not np.array_equal(np.asarray(a[k]), np.asarray(c[k]))
    assert abs(float(np.mean(np.asarray(a["n/scale"]))) - 1.0) < 0.2


# ---- work counts -----------------------------------------------------------------


def test_work_count_at_the_real_shape():
    """Against the figure XLA's own count gave when the program was
    compiled here for a described v5e (PR 25; nothing ran): 11.878 TFLOP
    for the (16,256,256,3) chunk with global attention in all 24 blocks.
    Ours counts matrix products only, so it may lie up to 5 % under
    (norms, softmax, GELU) and never over. (ISSUE 25's 25.8 TFLOP for 32
    tiles was the count of SAM's windowed pattern, which is not cpsam.)"""
    vit = _common.model_kwargs(harness.load_cell("cpsam-vitl.fov").config)
    assert 0.95 * 11.878e12 < cpsam_work.flops((16, 256, 256, 3), vit) <= 11.878e12
    assert cpsam_work.param_count(vit, 3) == PARAMS
    per_tile = cpsam_work.flops((1, 256, 256, 3), vit)
    dense = 2 * 1024 * 12 * 1024 * 1024 * 24  # qkv+proj+mlp on 1024 tokens
    scores = 2 * 2 * 16 * 1024 * 1024 * 64 * 24  # QK^T and PV over 1024 tokens
    assert dense + scores < per_tile < 1.02 * (dense + scores)


def test_work_count_against_cost_analysis_at_toy_size():
    import jax
    import jax.numpy as jnp

    ref, work = cpsam, cpsam_work
    kwargs, shape = dict(TOY_CPSAM, pretrain_grid=16), (1, 128, 128, 3)
    weights = _common.make_weights(ref.param_shapes(kwargs, shape[-1]), 1)
    fn = jax.jit(functools.partial(ref.forward, kwargs=kwargs, precision="bf16"))
    cost = fn.lower(weights, jnp.zeros(shape)).compile().cost_analysis()
    ours = work.flops(shape, kwargs)
    # toy widths make the elementwise share large: ours is under, within 35 %
    assert 0.65 * cost["flops"] < ours <= 1.02 * cost["flops"]


# ---- trace reduction ---------------------------------------------------------------


def test_interval_arithmetic_on_a_known_case():
    ivs = [(0, 10), (5, 20), (30, 40), (40, 45), (100, 110), (50, 50)]
    assert trace_reduce.merge(ivs) == [(0, 20), (30, 45), (100, 110)]
    assert trace_reduce.union_length(ivs) == 45
    assert trace_reduce.gaps(ivs, 0, 120) == [(20, 30), (45, 100), (110, 120)]
    assert trace_reduce.clip(ivs, 8, 35) == [(8, 10), (8, 20), (30, 35)]
    reduced = trace_reduce.Reduced(
        devices=[
            trace_reduce.DeviceTrace(
                0,
                ops=[("fusion.1", 0, 10), ("fusion.2", 5, 15), ("copy", 30, 15),
                     ("fusion.1", 100, 10)],
                modules=[("jit_f(1)", 0, 45), ("jit_f(1)", 100, 10)],
            )
        ],
        host=[("wait", 18, 40), ("stitch", 50, 45)],
        lo=0, hi=110,
    )
    assert reduced.busy_s() == pytest.approx(45e-9)
    assert reduced.busy_s((0, 40)) == pytest.approx(30e-9)
    assert reduced.module_seconds() == {"jit_f(1)": [45e-9, 10e-9]}
    assert reduced.top_ops(2) == [["fusion.1", 20e-9], ["fusion.2", 15e-9]]
    assert reduced.top_ops(3, (8, 35)) == [["fusion.2", 12e-9], ["copy", 5e-9], ["fusion.1", 2e-9]]
    assert trace_reduce.op_kind(
        "%fusion.7 = (bf16[8,4]{1,0:T(8,128)}, f32[4]{0}) fusion(bf16[8,4]{1,0} %p), kind=kLoop"
    ) == "fusion (bf16[8,4], f32[4])"
    # gaps (20,30) -> "wait", (45,100) -> "stitch"
    assert reduced.idle_gaps() == [["stitch", 55e-9], ["wait", 10e-9]]
    assert reduced.host_seconds(2) == [["stitch", 45e-9], ["wait", 40e-9]]


def test_reduction_of_the_recorded_chip_trace(tmp_path):
    """One lone 512 px request (9 tiles in the (16,256,256,3) program),
    traced on a TPU v5 lite in PR 25 from a git-archive tree (the
    labelling trace of a traced run), xz-packed."""
    import lzma

    packed = BENCH / "fixtures" / "fov-16x256.label.xplane.pb.xz"
    fixture = tmp_path / "fixture.xplane.pb"
    fixture.write_bytes(lzma.decompress(packed.read_bytes()))
    expected = json.loads((BENCH / "fixtures" / "expected.json").read_text())
    reduced = trace_reduce.reduce(fixture)
    assert [d.device for d in reduced.devices] == [0]
    assert len(reduced.devices[0].ops) == expected["ops"]
    assert {k: len(v) for k, v in reduced.module_seconds().items()} == expected["modules"]
    assert reduced.busy_s() == pytest.approx(expected["busy_s"], rel=1e-9)
    assert reduced.span_s() == pytest.approx(expected["span_s"], rel=1e-9)
    assert reduced.busy_s() <= reduced.span_s()
    assert reduced.top_ops(3)[0][0] == expected["top_op"]
    assert sum(s for _, s in reduced.top_ops(10**6)) == pytest.approx(expected["busy_s"], rel=1e-9)
    assert reduced.host_seconds(1)[0][0] == "np.asarray(jax.Array)"
    # clipped to its first half, the one execution is busy throughout
    half = (reduced.lo, (reduced.lo + reduced.hi) // 2)
    assert reduced.busy_s(half) == pytest.approx((half[1] - half[0]) / 1e9, rel=1e-3)
    assert reduced.module_seconds((reduced.hi, reduced.hi + 1)) == {}
    # the operations are clipped to the same span as the busy seconds, so
    # that the two can be divided
    top = reduced.top_ops(10**6, half)
    assert top[0] == [expected["top_op"], pytest.approx(expected["top_op_first_half_s"], rel=1e-9)]
    assert sum(s for _, s in top) == pytest.approx(expected["busy_first_half_s"], rel=1e-9)
    assert reduced.busy_s(half) == pytest.approx(expected["busy_first_half_s"], rel=1e-9)
    assert reduced.top_ops(3, (reduced.hi, reduced.hi + 1)) == []
    # the trace's start on the wall clock: a time.time_ns() of the traced
    # process lands on the trace's own clock
    assert reduced.started_wall_ns == expected["started_wall_ns"]
    assert reduced.at(expected["started_wall_ns"] + 5) == 5


# ---- the harness, end to end, toy widths, CPU ---------------------------------------


@pytest.fixture(scope="module")
def toy_runs(tmp_path_factory):
    """One traced and one untraced rehearsal, shared by the tests below."""
    out = tmp_path_factory.mktemp("bench")
    plain = harness.run_cell(
        toy_cell(), SEED, 2.0, False, platform="cpu", out_dir=out / "a"
    )
    traced = harness.run_cell(
        toy_cell(), SEED + 1, 2.0, True, platform="cpu", out_dir=out / "b"
    )
    return plain, traced


def test_rehearsal_prints_the_contract_s_line(toy_runs):
    plain, traced = toy_runs
    for line in toy_runs:
        assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
        assert list(line)[-1] == "checks"
        assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
        assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 8
        assert json.loads(json.dumps(line)) == line
        for name in ("rel_l2", "max_err"):
            value, limit = line["checks"][name]
            assert 0 < value <= limit
    assert set(plain["metrics"]) == {"throughput_mpx_s", "latency_p50_ms", "setup_s"}
    # the tail is the traced line's, under its per-layer name
    assert traced["metrics"]["request_p95_ms"]["unit"] == "ms"
    assert all(m["value"] > 0 for m in plain["metrics"].values())


def test_counter_readers_read_and_trace_readers_stay_silent_off_the_chip(toy_runs):
    _, traced = toy_runs
    got = traced["metrics"]
    # no device plane on a CPU: nothing under a device metric's name
    assert set(got) == {"batch_occupancy", "compiles_in_window", "request_p95_ms"}
    assert got["compiles_in_window"]["value"] == 0
    assert got["batch_occupancy"]["value"] >= 1
    assert got["request_p95_ms"]["value"] > 0
    assert "busy_s" not in traced["device"] and "breakdown" not in traced


def _broken(kind: str):
    """The timed path broken underneath the harness."""
    from bioengine_tpu.runtime.engine import InferenceEngine

    sound = InferenceEngine._predict_impl

    def altered(self, images):  # an answer altered where it is produced
        return sound(self, images) + 0.05

    def half(self, images):  # half of the batch left out
        images = np.asarray(images)
        out = sound(self, images)
        if len(images) > 1:
            out[len(images) // 2 :] = 0
        else:  # one item: half of its tiles
            out[:, out.shape[1] // 2 :] = 0
        return out

    return {"altered": altered, "half": half}[kind]


@pytest.mark.parametrize("fault", ["altered", "half"])
def test_a_broken_timed_path_comes_out_not_correct(fault, monkeypatch, tmp_path):
    from bioengine_tpu.runtime.engine import InferenceEngine

    monkeypatch.setattr(InferenceEngine, "_predict_impl", _broken(fault))
    line = harness.run_cell(
        toy_cell(), SEED + 7, 1.5, False, platform="cpu", out_dir=tmp_path
    )
    assert line["correct"] is False and line["attempted"] > 0
    assert any(
        value > limit for value, limit in
        (v for v in line["checks"].values() if isinstance(v, list))
    )


def test_the_precision_control_fails_where_the_stated_precision_passes():
    """The control is the reference in the program's place, computed one
    step below the precision the configuration states (bf16 -> fp8). Kept
    here at toy size; read on the chip at the cells' own sizes (PERF.md)."""
    cell = toy_cell()
    plan = closed_loop.plan(cell.traffic, cell.config, SEED)
    sample = infer.control_sample(cell, plan)
    assert [e["kind"] for e in sample] == closed_loop.kinds(cell.traffic)
    stated = infer.compare(cell, SEED, sample, plan.pool, precision="bf16")
    control = infer.compare(cell, SEED, sample, plan.pool, precision="fp8")
    limits = cell.config["limits"]
    assert harness.is_correct(harness.judge(stated, limits, len(sample)))
    assert not harness.is_correct(harness.judge(control, limits, len(sample)))
    assert control["rel_l2"] > 3 * stated["rel_l2"]


# ---- the seam: what a configuration may name and set --------------------------------


def test_a_configuration_names_its_path_and_sets_only_what_the_path_lists():
    cell = harness.load_cell("cpsam-vitl.fov")
    assert cell.path is infer and cell.config["deployment"]["path"] == "infer"
    assert harness.operator_env(cell) == {"BIOENGINE_RPC_STORE_MB": "1024"}
    # no default path: a configuration without the key is refused
    nameless = {**cell.config, "deployment": {"app": "apps/model-runner"}}
    with pytest.raises(ValueError, match="names no served path: set deployment.path"):
        harness.path_module(nameless)
    # a variable the path does not list is refused, by name
    cell.config["deployment"]["env"] = {"BIOENGINE_DECODE_KV_BLOCKS": "64"}
    with pytest.raises(ValueError, match="sets BIOENGINE_DECODE_KV_BLOCKS; its path 'infer'"):
        harness.operator_env(cell)
    token_cell = toy_overlay.toy_cell()
    assert harness.operator_env(token_cell) == {"BIOENGINE_DECODE_KV_BLOCKS": "512"}
    token_cell.config["deployment"]["env"] = {"BIOENGINE_RPC_STORE_MB": "64"}
    with pytest.raises(ValueError, match="its path 'generate'"):
        harness.operator_env(token_cell)


def test_every_operator_variable_of_a_path_is_documented():
    """``docs/OPERATIONS.md`` names every variable a path lets a
    configuration set (the real paths and the test double alike)."""
    toy_overlay.mount()
    operations = (REPO / "docs" / "OPERATIONS.md").read_text()
    listed = 0
    for directory in (BENCH / "paths", toy_overlay.OVERLAY / "benchmarks" / "paths"):
        for file in sorted(directory.glob("[a-z]*.py")):
            path = harness.path_module({"deployment": {"path": file.stem}})
            for variable in path.OPERATOR_ENV:
                assert f"`{variable}`" in operations, f"{file.name} lists {variable}"
                listed += 1
    assert listed >= 2


def test_the_harness_names_nothing_of_one_path():
    """What one served path does has left ``harness.py`` (ISSUE 28's own
    grep, comments and all)."""
    words = re.compile(
        r"infer|model-runner|runtime_deployment|entry_deployment|pixels|blocksize|tile"
    )
    found = [
        line for line in (BENCH / "harness.py").read_text().splitlines()
        if words.search(line)
    ]
    assert found == []


# ---- work in the path's unit: the old readers against the new ------------------------


def test_old_and_new_readers_agree_to_the_last_digit(tmp_path):
    """``step_mfu`` and ``program_roofline`` on one recorded run (the
    chip trace of the fixture, with requests laid around it), computed
    as the readers did before the seam (pixels, ``flops_per_pixel``, the
    program's shape and the model's kwargs) and as they do now (work,
    ``flops_per_unit``, the path's program key and the configuration)."""
    import importlib
    import lzma

    fixture = tmp_path / "fixture.xplane.pb"
    fixture.write_bytes(
        lzma.decompress((BENCH / "fixtures" / "fov-16x256.label.xplane.pb.xz").read_bytes())
    )
    reduced = trace_reduce.reduce(fixture)
    cell = harness.load_cell("cpsam-vitl.fov")
    (program,) = reduced.module_seconds()
    shape = (16, 256, 256, 3)
    t0, t1 = 100.0, 100.0 + reduced.span_s()
    span = (reduced.lo - 1, reduced.hi + 10**6)  # the program's event ends after its last operation
    requests = [
        {"start": t0 - 0.11, "end": t0 + 0.07, "ok": True, "pixels": 512 * 512},
        {"start": t0 + 0.013, "end": t0 + 0.29, "ok": True, "pixels": 768 * 768},
        {"start": t0 + 0.2, "end": t1 + 0.4, "ok": True, "pixels": 1024 * 1024},
        {"start": t0 + 0.1, "end": t0 + 0.2, "ok": False, "pixels": 1024 * 1024},
    ]
    for r in requests:
        r.update(work=r["pixels"], latency_ms=(r["end"] - r["start"]) * 1000.0)
    peaks = harness.peaks_for("TPU v5 lite")
    run = harness.RunData(
        cell=cell, seconds=t1 - t0, window=(t0, t1), requests=requests,
        counters={}, compiles_in_window=0,
        trace={
            "reduced": reduced, "span": span,
            "host_window": (t0, t1), "peaks": peaks, "programs": {program: shape},
        },
    )
    kwargs = _common.model_kwargs(cell.config)
    # as PR 25 to 27 computed them
    pixels = 0.0
    for r in requests:
        if r["ok"] and r["end"] > r["start"]:
            inside = max(0.0, min(r["end"], t1) - max(r["start"], t0))
            pixels += r["pixels"] * inside / (r["end"] - r["start"])
    per_pixel = cpsam_work.flops_per_pixel(kwargs, 3, 256)
    old_mfu = 100.0 * pixels * per_pixel / ((t1 - t0) * (peaks["bf16_flops_per_s"] * 1))
    durations = reduced.module_seconds(span)[program]
    least = max(
        cpsam_work.flops(shape, kwargs) / peaks["bf16_flops_per_s"],
        cpsam_work.min_bytes(shape, kwargs) / peaks["hbm_bytes_per_s"],
    )
    old_roofline = 100.0 * (0.0 + least * len(durations)) / (0.0 + sum(durations))
    new_mfu = importlib.import_module("benchmarks.layer_metrics.step_mfu").read(run)
    new_roofline = importlib.import_module(
        "benchmarks.layer_metrics.program_roofline"
    ).read(run)
    assert repr(new_mfu) == repr(old_mfu) and 0 < new_mfu < 100
    assert repr(new_roofline) == repr(old_roofline) and 0 < new_roofline < 100
    assert window_metrics.work_served(run, t0, t1) == pixels
    # the throughput is the same work over the window, under the path's name
    assert window_metrics.end_to_end(run, 1.0)["throughput_mpx_s"] == (
        pixels / 1e6 / (t1 - t0)
    )


# ---- a second served path, so that the seam is not shaped by one user -----------------


@pytest.fixture(scope="module")
def token_runs(tmp_path_factory):
    """The toy token path (``toy_path/``: ``apps/generate`` as shipped,
    through a streamed call), rehearsed untraced and traced; the traced
    rehearsal's ``RunData`` is kept as the readers saw it."""
    out = tmp_path_factory.mktemp("tokens")
    seen = []
    read = harness.per_layer

    def watched(run):
        seen.append(run)
        return read(run)

    plain = harness.run_cell(
        toy_overlay.toy_cell(), SEED + 2, 1.5, False, platform="cpu", out_dir=out / "a"
    )
    harness.per_layer = watched
    try:
        traced = harness.run_cell(
            toy_overlay.toy_cell(), SEED + 3, 1.5, True, platform="cpu", out_dir=out / "b"
        )
    finally:
        harness.per_layer = read
    return plain, traced, seen[0]


def test_the_token_path_prints_its_own_line(token_runs):
    plain, traced, run = token_runs
    for line in (plain, traced):
        assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
        assert list(line)[-1] == "checks"
        assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
        assert set(line["checks"]) == {"logit_gap", "compared"}
        value, limit = line["checks"]["logit_gap"]
        assert 0 <= value <= limit and line["checks"]["compared"] == 4
        assert json.loads(json.dumps(line)) == line
    # latency and set-up as every path; no throughput in the image path's unit
    assert set(plain["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in plain["metrics"].values())
    # none of the image path's counter metrics, no device metric off the chip
    assert set(traced["metrics"]) == {"tokens_per_s", "first_item_ms", "compiles_in_window"}
    assert traced["metrics"]["tokens_per_s"] == {
        "value": traced["metrics"]["tokens_per_s"]["value"], "unit": "tokens/s",
    }
    assert traced["metrics"]["tokens_per_s"]["value"] > 0


def test_the_token_path_s_records_carry_the_stream_s_stamps(token_runs):
    _, _, run = token_runs
    assert run.requests and run.cell.path.THROUGHPUT is None
    for r in run.requests:
        assert r["ok"] and r["work"] in (8, 12) and r["kind"] == (16, r["work"])
        assert "pixels" not in r and "server_ms" not in r
        assert r["start"] < r["first_item"] <= r["items"][-1] == r["end"]
        assert r["latency_ms"] == (r["end"] - r["start"]) * 1000.0
    assert window_metrics.work_served(run, *run.window) > 0


def test_a_streamed_reply_is_stamped_item_by_item():
    """The app's service carries no stream to an outside client (it
    publishes ``generate`` alone), so the per-item stamps are proven on a
    service that publishes a generator, as ``tests/test_streaming.py``
    builds one: the path's ``perform``, unchanged, stamps every token."""
    import asyncio
    import importlib
    import time

    from bioengine_tpu.rpc.client import connect_to_server
    from bioengine_tpu.rpc.server import RpcServer

    toy_overlay.mount()
    from benchmarks.paths import generate

    async def generate_stream(prompt: str, max_new_tokens: int = 4, context=None):
        for index in range(max_new_tokens):
            await asyncio.sleep(0.002)
            yield {"token": (ord(prompt[0]) + index) % 256, "index": index}

    async def served() -> tuple[float, dict]:
        server = RpcServer(admin_users=["admin"])
        await server.start()
        try:
            conn = await connect_to_server({
                "server_url": f"http://127.0.0.1:{server.port}",
                "token": server.issue_token("admin"),
            })
            await conn.register_service(
                {"id": "toy-stream", "generate_stream": generate_stream}
            )
            before = time.perf_counter()
            reply = await generate.perform(
                conn, "toy-stream", {"method": "generate_stream"}, None,
                {"prompt": "abc", "max_new_tokens": 6}, "s-0",
            )
            await conn.disconnect()
            return before, reply
        finally:
            await server.stop()

    before, reply = asyncio.run(served())
    assert reply["ok"] and reply["output"] == [97, 98, 99, 100, 101, 102]
    assert len(reply["items"]) == 6 and reply["items"] == sorted(reply["items"])
    assert before < reply["first_item"] == reply["items"][0]
    assert reply["end"] == reply["items"][-1]
    # the tokens came 2 ms apart and were stamped as they came, not at the end
    assert reply["items"][-1] - reply["items"][0] >= 5 * 0.0015
    run = harness.RunData(
        cell=None, seconds=1.0, window=(before, reply["end"]),
        requests=[{**reply, "start": before}], counters={}, compiles_in_window=0,
    )
    gap = importlib.import_module("benchmarks.layer_metrics.item_gap_ms")
    assert gap.read(run) >= 1.5


def test_a_broken_token_path_comes_out_not_correct(monkeypatch, tmp_path):
    """A token altered where it is produced: the decode engine's step
    hands back the next character instead of the one it chose."""
    from bioengine_tpu.runtime.decode_engine import DecodeEngine

    sound = DecodeEngine.step

    def altered(self, seq_ids, tokens):
        return [(t + 1) % 256 for t in sound(self, seq_ids, tokens)]

    monkeypatch.setattr(DecodeEngine, "step", altered)
    line = harness.run_cell(
        toy_overlay.toy_cell(), SEED + 9, 1.0, False, platform="cpu", out_dir=tmp_path
    )
    assert line["correct"] is False and line["attempted"] > 0
    value, limit = line["checks"]["logit_gap"]
    assert value > limit


def test_nothing_to_compare_reads_not_comparable_under_the_limits_own_keys():
    assert harness.not_comparable({"logit_gap": 0.5}) == {"logit_gap": harness.NOT_COMPARABLE}
    checks = harness.judge(harness.not_comparable({"a": 1.0, "b": 2.0}), {"a": 1.0, "b": 2.0}, 0)
    assert not harness.is_correct(checks)


# ---- a new path and its cell arrive as new files and new entries only ----------------


def test_a_new_path_and_its_cell_are_added_without_an_edit(tmp_path):
    """The benchmark copied to a temporary root, the toy path's files
    added beside it (none may be there already) and its entries appended
    to ``BENCHMARK.json``; the copy's own manifest and loading tests pass
    there, in a process that sees the copy alone."""
    import os
    import subprocess
    import sys

    added = toy_overlay.merged_tree(tmp_path)
    assert {a.split("/")[1] for a in added} == {
        "paths", "generators", "traffic", "configs", "references", "work",
        "layer_metrics", "fixtures",
    }
    for file in sorted((REPO / "benchmarks").rglob("*")):
        if file.is_file() and "__pycache__" not in file.parts:
            copy = tmp_path / file.relative_to(REPO)
            assert copy.read_bytes() == file.read_bytes(), f"{file} was edited"
    real, merged = harness.load_manifest(), harness.load_manifest(tmp_path)
    assert merged["configs"][:-1] == real["configs"]
    assert merged["workloads"][:-1] == real["workloads"]
    assert merged["end_to_end"] == real["end_to_end"]
    assert [w["name"] for w in merged["workloads"]][-1] == toy_overlay.CELL
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path))
    done = subprocess.run(
        [
            sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
            "-p", "no:xdist", "-p", "no:randomly",
            "benchmarks/tests/test_benchmarks.py", "-k",
            "manifest_names_files_and_moves or every_cell_loads or tile_counts",
        ],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-2000:]
    assert "3 passed" in done.stdout
