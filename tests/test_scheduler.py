"""Global scheduler: cross-replica continuous batching, admission
control with priority classes, and predictive autoscaling.

Unit layers run against local replicas on a plain controller; the
cross-host layers (one ``__batch__`` round trip per coalesced group,
the mixed-priority soak with a mid-soak host kill) run on the
in-process multi-host harness from tests/test_chaos.py — real
websockets, deterministic kills.

Capacity arithmetic the queue-pressure tests rely on: a lone request
on an idle deployment takes the inline fast path (no group), and the
queued path keeps at most ``2 x routable replicas`` groups in flight —
everything beyond that waits in the fair queues, which is where
admission budgets and weighted shares become observable.
"""

import asyncio
import time
from pathlib import Path

import pytest

from bioengine_tpu.apps.builder import AppBuildError, AppBuilder
from bioengine_tpu.apps.manifest import ManifestError, validate_manifest
from bioengine_tpu.cluster.state import ClusterState
from bioengine_tpu.cluster.topology import TpuTopology
from bioengine_tpu.rpc.server import RpcServer
from bioengine_tpu.serving import (
    AdmissionRejectedError,
    DeploymentSpec,
    ReplicaState,
    RequestOptions,
    SchedulingConfig,
    ServeController,
)
from bioengine_tpu.serving.errors import (
    DeadlineExceeded,
    FailureKind,
    RetryableTransportError,
    classify_exception,
)
from bioengine_tpu.serving.scheduler import (
    HeuristicCostModel,
    LoadPredictor,
    batch_signature,
)
from bioengine_tpu.testing import faults
from bioengine_tpu.utils import flight
from bioengine_tpu.utils import metrics as umetrics
from bioengine_tpu.worker_host import WorkerHost

pytestmark = [pytest.mark.integration, pytest.mark.anyio]


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture
async def controller():
    c = ServeController(ClusterState(), health_check_period=3600)
    yield c
    await c.stop()


def sched_spec(factory, **kw):
    scheduling = kw.pop("scheduling", None) or SchedulingConfig()
    defaults = dict(
        name="entry",
        instance_factory=factory,
        autoscale=False,
        scheduling=scheduling,
    )
    defaults.update(kw)
    return DeploymentSpec(**defaults)


class EchoApp:
    """~1 ms of awaited work per call: a request must actually SUSPEND
    for concurrent submits to overlap (a coroutine that never awaits
    runs to completion synchronously, so every call would ride the
    uncontended fast path and nothing would ever coalesce)."""

    def __init__(self):
        self.calls = 0

    async def echo(self, value=0):
        self.calls += 1
        await asyncio.sleep(0.001)
        return {"echo": value}


class GatedApp:
    """Calls block on a class-level gate — the lever for building
    deterministic queue pressure."""

    gate: asyncio.Event = None
    entered: int = 0

    def __init__(self):
        self.calls = 0

    @classmethod
    def reset(cls):
        cls.gate = asyncio.Event()
        cls.entered = 0

    async def work(self, tag=0):
        self.calls += 1
        GatedApp.entered += 1
        await GatedApp.gate.wait()
        return tag


# ---------------------------------------------------------------------------
# config + signature
# ---------------------------------------------------------------------------


class TestConfigAndSignature:
    def test_unknown_scheduling_key_rejected(self):
        with pytest.raises(ValueError, match="unknown scheduling"):
            SchedulingConfig.from_config({"max_batchs": 4})

    def test_default_class_must_exist(self):
        with pytest.raises(ValueError, match="default_class"):
            SchedulingConfig.from_config(
                {"class_weights": {"gold": 1.0}, "default_class": "silver"}
            )

    def test_weights_must_be_positive(self):
        with pytest.raises(ValueError, match="positive"):
            SchedulingConfig.from_config({"class_weights": {"a": 0.0}})

    def test_signature_model_and_bucket(self):
        import numpy as np

        base = batch_signature(
            "predict", (), {"rdf_path": "m1", "inputs": np.zeros((1, 8, 8))}
        )
        # a different batch size of the same per-item shape co-batches
        assert base == batch_signature(
            "predict", (), {"rdf_path": "m1", "inputs": np.zeros((5, 8, 8))}
        )
        # a different model / bucket / method never does
        assert base != batch_signature(
            "predict", (), {"rdf_path": "m2", "inputs": np.zeros((1, 8, 8))}
        )
        assert base != batch_signature(
            "predict", (), {"rdf_path": "m1", "inputs": np.zeros((1, 16, 16))}
        )
        assert base != batch_signature(
            "embed", (), {"rdf_path": "m1", "inputs": np.zeros((1, 8, 8))}
        )

    def test_manifest_validates_batching_block(self):
        base = {
            "name": "x", "id": "x", "id_emoji": "x", "description": "x",
            "type": "tpu-serve", "deployments": ["d:D"],
        }
        with pytest.raises(ManifestError, match="unknown"):
            validate_manifest(
                {**base, "deployment_config": {"d": {"batching": {"maxb": 2}}}}
            )
        with pytest.raises(ManifestError, match="mapping"):
            validate_manifest(
                {**base, "deployment_config": {"d": {"scheduling": "yes"}}}
            )
        # a scalar where a mapping belongs is a MANIFEST error, not an
        # AttributeError out of the validator
        with pytest.raises(ManifestError, match="mapping"):
            validate_manifest(
                {**base, "deployment_config": {"d": "fast"}}
            )
        m = validate_manifest(
            {
                **base,
                "deployment_config": {
                    "d": {
                        "batching": {"max_batch": 4, "max_wait_ms": 2},
                        "scheduling": {"max_queue_depth": 16},
                    }
                },
            }
        )
        assert m.deployment_config["d"]["batching"]["max_batch"] == 4


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


class TestAdmission:
    async def test_queue_full_sheds_typed(self, controller):
        GatedApp.reset()
        await controller.deploy(
            "adm-1",
            [
                sched_spec(
                    GatedApp,
                    max_ongoing_requests=1,
                    scheduling=SchedulingConfig(
                        max_batch=1, max_wait_ms=1, max_queue_depth=1
                    ),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("adm-1")
        # distinct tags -> distinct signatures -> one group each: the
        # first rides the fast path, two fill dispatch capacity (2x1
        # routable), the fourth occupies the whole queue budget
        tasks = [
            asyncio.create_task(handle.call("work", tag=i)) for i in range(4)
        ]
        await asyncio.sleep(0.05)
        with pytest.raises(AdmissionRejectedError, match="queue_full") as ei:
            await handle.call("work", tag=99)
        assert ei.value.reason == "queue_full"
        # load shedding is terminal backpressure: never failed over
        assert classify_exception(ei.value) is FailureKind.APPLICATION
        GatedApp.gate.set()
        assert sorted(await asyncio.gather(*tasks)) == [0, 1, 2, 3]

    async def test_tenant_quota(self, controller):
        GatedApp.reset()
        await controller.deploy(
            "adm-2",
            [
                sched_spec(
                    GatedApp,
                    max_ongoing_requests=1,
                    scheduling=SchedulingConfig(
                        max_batch=1, max_wait_ms=1, tenant_quota=1
                    ),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("adm-2")
        opts = RequestOptions(tenant="acme")
        # saturate the fast path + both dispatch slots, so tenant
        # requests actually WAIT (quota counts waiting requests)
        blockers = [
            asyncio.create_task(handle.call("work", tag=100 + i))
            for i in range(3)
        ]
        await asyncio.sleep(0.05)
        waiting = asyncio.create_task(
            handle.call("work", tag=1, options=opts)
        )
        await asyncio.sleep(0.05)
        with pytest.raises(AdmissionRejectedError, match="tenant_quota"):
            await handle.call("work", tag=2, options=opts)
        # a different tenant is NOT shed by acme's quota
        other = asyncio.create_task(
            handle.call(
                "work", tag=3, options=RequestOptions(tenant="other")
            )
        )
        await asyncio.sleep(0.05)
        assert not other.done()
        GatedApp.gate.set()
        await asyncio.gather(*blockers, waiting, other)

    async def test_deadline_infeasible_rejected_at_admission(self, controller):
        class SlowApp:
            async def work(self, tag=0):
                await asyncio.sleep(0.05)
                return tag

        await controller.deploy(
            "adm-3",
            [sched_spec(SlowApp, scheduling=SchedulingConfig(max_wait_ms=1))],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("adm-3")
        # prime the service-time EWMA
        for i in range(2):
            await handle.call("work", tag=i)
        sched = controller._schedulers[("adm-3", "entry")]
        assert sched.predictor.service_estimate_s() > 0.02
        with pytest.raises(
            AdmissionRejectedError, match="deadline_infeasible"
        ):
            await handle.call(
                "work", tag=9, options=RequestOptions(deadline_s=0.001)
            )

    async def test_poisoned_estimate_recovers_via_probe(self, controller):
        """Regression: one huge service-time outlier (a cold compile)
        must not shed ALL deadlined traffic forever — every Nth
        infeasible verdict probes through, completes at the true speed,
        and re-grounds the estimate."""
        from bioengine_tpu.serving.scheduler import INFEASIBLE_PROBE_EVERY

        class FastApp:
            async def work(self, x=0):
                await asyncio.sleep(0.001)
                return x

        await controller.deploy(
            "probe-1",
            [sched_spec(FastApp, scheduling=SchedulingConfig(max_wait_ms=1))],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("probe-1")
        sched = controller._schedulers[("probe-1", "entry")]
        # poison: as if the first call hit a 120s cold compile
        sched.predictor.note_service(1, 120.0)
        opts = RequestOptions(deadline_s=1.0)
        outcomes = []
        for i in range(3 * INFEASIBLE_PROBE_EVERY):
            try:
                outcomes.append(await handle.call("work", x=i, options=opts))
            except AdmissionRejectedError:
                outcomes.append("shed")
        # probes got through and completed...
        served = [o for o in outcomes if o != "shed"]
        assert served, outcomes
        # ...and their measured service time re-grounded the estimate:
        # once corrected, deadlined traffic flows again
        assert sched.predictor.service_estimate_s() < 1.0
        assert await handle.call("work", x=99, options=opts) == 99

    async def test_reject_recorded_in_flight_and_metrics(self, controller):
        GatedApp.reset()
        await controller.deploy(
            "adm-4",
            [
                sched_spec(
                    GatedApp,
                    max_ongoing_requests=1,
                    scheduling=SchedulingConfig(
                        max_batch=1, max_wait_ms=1, max_queue_depth=1
                    ),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("adm-4")
        tasks = [
            asyncio.create_task(handle.call("work", tag=i)) for i in range(4)
        ]
        await asyncio.sleep(0.05)
        with pytest.raises(AdmissionRejectedError):
            await handle.call("work", tag=99)
        events = flight.get_events(types=["admission.reject"])
        assert events and events[-1]["attrs"]["app"] == "adm-4"
        fam = umetrics.collect().get("scheduler_rejected_total", {})
        assert any(
            s["labels"].get("reason") == "queue_full"
            for s in fam.get("series", [])
        ), fam
        GatedApp.gate.set()
        await asyncio.gather(*tasks)


# ---------------------------------------------------------------------------
# coalescing
# ---------------------------------------------------------------------------


class TestCoalescing:
    async def test_concurrent_compatible_requests_share_one_group(
        self, controller
    ):
        await controller.deploy(
            "co-1",
            [
                sched_spec(
                    EchoApp,
                    max_ongoing_requests=16,
                    scheduling=SchedulingConfig(max_batch=8, max_wait_ms=40),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("co-1")
        sched = controller._schedulers[("co-1", "entry")]
        # a lone warmup call rides the inline fast path (no group, no
        # coalescing window — the uncontended-latency contract)
        await handle.call("echo", value=7)
        assert sched.stats["fast_path"] == 1
        results = await asyncio.gather(
            *(handle.call("echo", value=7) for _ in range(8))
        )
        assert all(r == {"echo": 7} for r in results)
        # the concurrent compatible burst coalesced instead of riding
        # 8 separate dispatches
        assert sched.stats["dispatched_requests"] >= 7
        assert sched.stats["dispatched_groups"] <= 2, sched.stats

    async def test_incompatible_signatures_never_share(self, controller):
        await controller.deploy(
            "co-2",
            [
                sched_spec(
                    EchoApp,
                    max_ongoing_requests=16,
                    scheduling=SchedulingConfig(max_batch=8, max_wait_ms=20),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("co-2")
        sched = controller._schedulers[("co-2", "entry")]
        await handle.call("echo", value=0)  # fast-path warmup
        before = sched.stats["dispatched_groups"]
        results = await asyncio.gather(
            *(handle.call("echo", value=i % 3) for i in range(6))
        )
        assert sorted(r["echo"] for r in results) == [0, 0, 1, 1, 2, 2]
        # 3 distinct values -> at least 3 groups (argument values are
        # part of the compatibility key: a different "model"/config
        # kwarg must never co-batch)
        assert sched.stats["dispatched_groups"] - before >= 3

    async def test_group_respects_max_batch(self, controller):
        await controller.deploy(
            "co-3",
            [
                sched_spec(
                    EchoApp,
                    max_ongoing_requests=32,
                    scheduling=SchedulingConfig(max_batch=4, max_wait_ms=40),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("co-3")
        sched = controller._schedulers[("co-3", "entry")]
        await handle.call("echo", value=1)
        before_g = sched.stats["dispatched_groups"]
        before_r = sched.stats["dispatched_requests"]
        await asyncio.gather(*(handle.call("echo", value=1) for _ in range(8)))
        # the first of the burst may ride the fast path; the rest
        # coalesce in groups capped at max_batch=4
        assert sched.stats["dispatched_requests"] - before_r >= 7
        assert sched.stats["dispatched_groups"] - before_g >= 2  # 4-cap

    async def test_member_failure_isolated_in_group(self, controller):
        class FlakyThird:
            count = [0]

            async def echo(self, value=0):
                FlakyThird.count[0] += 1
                mine = FlakyThird.count[0]
                await asyncio.sleep(0.001)
                if mine == 4:
                    raise ValueError("member boom")
                return value

        FlakyThird.count = [0]
        await controller.deploy(
            "co-4",
            [
                sched_spec(
                    FlakyThird,
                    max_ongoing_requests=16,
                    scheduling=SchedulingConfig(max_batch=8, max_wait_ms=30),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("co-4")
        await handle.call("echo", value=5)  # fast-path warmup (call 1)
        results = await asyncio.gather(
            *(handle.call("echo", value=5) for _ in range(6)),
            return_exceptions=True,
        )
        # one member of the coalesced group failed; its groupmates all
        # got their results — per-member isolation, no poisoned batch
        errors = [r for r in results if isinstance(r, Exception)]
        assert len(errors) == 1 and "member boom" in str(errors[0])
        assert [r for r in results if r == 5] == [5] * 5


# ---------------------------------------------------------------------------
# fairness + deadlines
# ---------------------------------------------------------------------------


class TestFairnessAndDeadlines:
    async def test_weighted_fair_shares_and_no_starvation(self, controller):
        GatedApp.reset()
        await controller.deploy(
            "fair-1",
            [
                sched_spec(
                    GatedApp,
                    max_ongoing_requests=1,
                    scheduling=SchedulingConfig(
                        max_batch=1,
                        max_wait_ms=1,
                        max_queue_depth=256,
                        class_weights={"interactive": 4.0, "bulk": 1.0},
                    ),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("fair-1")
        order: list[str] = []

        async def one(cls: str, i: int):
            await handle.call(
                "work",
                tag=f"{cls}-{i}",
                options=RequestOptions(priority=cls),
            )
            order.append(cls)

        # hold the gate so everything queues; bulk is submitted FIRST
        # (FIFO would serve it all before interactive)
        blocker = asyncio.create_task(handle.call("work", tag="blocker"))
        await asyncio.sleep(0.05)
        tasks = []
        for i in range(16):
            tasks.append(asyncio.create_task(one("bulk", i)))
        for i in range(16):
            tasks.append(asyncio.create_task(one("interactive", i)))
        await asyncio.sleep(0.1)  # all queued behind the blocker
        GatedApp.gate.set()
        await asyncio.gather(blocker, *tasks)
        # weighted share: the first half of completions is dominated by
        # the 4x-weighted interactive class despite bulk arriving first
        first_half = order[: len(order) // 2]
        inter = first_half.count("interactive")
        assert inter >= len(first_half) * 0.55, order
        # ...and bulk is never starved: it makes progress while
        # interactive work is still pending
        assert order[:12].count("bulk") >= 1, order

    async def test_edf_within_class(self, controller):
        GatedApp.reset()
        await controller.deploy(
            "edf-1",
            [
                sched_spec(
                    GatedApp,
                    max_ongoing_requests=1,
                    scheduling=SchedulingConfig(max_batch=1, max_wait_ms=1),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("edf-1")
        order = []

        async def one(tag, deadline_s):
            await handle.call(
                "work", tag=tag,
                options=RequestOptions(deadline_s=deadline_s),
            )
            order.append(tag)

        # fast path + both dispatch slots consumed -> probes QUEUE
        blocker = asyncio.create_task(handle.call("work", tag="blocker"))
        await asyncio.sleep(0.05)
        fillers = [
            asyncio.create_task(handle.call("work", tag=f"fill-{i}"))
            for i in range(2)
        ]
        await asyncio.sleep(0.05)
        loose = asyncio.create_task(one("loose", 30.0))
        await asyncio.sleep(0.02)
        tight = asyncio.create_task(one("tight", 5.0))
        await asyncio.sleep(0.05)
        GatedApp.gate.set()
        await asyncio.gather(blocker, *fillers, loose, tight)
        # the later-arriving but tighter-deadline request overtook
        assert order.index("tight") < order.index("loose"), order

    async def test_member_timeout_not_inherited_from_group(self, controller):
        """Regression: a tight-budget member co-batched with a
        no-timeout companion must still be cut at ITS budget — the
        group's max-of-members host abort must not become the
        caller-side wait."""
        release = asyncio.Event()

        class Hang:
            async def work(self, x=0):
                await release.wait()
                return x

        await controller.deploy(
            "mt-1",
            [
                sched_spec(
                    Hang,
                    max_ongoing_requests=8,
                    scheduling=SchedulingConfig(max_batch=8, max_wait_ms=30),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("mt-1")
        try:
            # occupy the fast path so both probes co-batch
            blocker = asyncio.create_task(handle.call("work", x=1))
            await asyncio.sleep(0.02)
            unbounded = asyncio.create_task(handle.call("work", x=1))
            t0 = time.monotonic()
            # same typed surface as the router's per-attempt timeout
            with pytest.raises(RetryableTransportError):
                await handle.call(
                    "work", x=1, options=RequestOptions(timeout_s=0.2)
                )
            waited = time.monotonic() - t0
            assert waited < 1.0, waited  # cut at ~0.2s, not the group's pace
        finally:
            release.set()  # teardown must never inherit a closed gate
        assert await asyncio.gather(blocker, unbounded) == [1, 1]

    async def test_member_transport_failure_feeds_breaker(self, controller):
        """Regression: transport-classified failures inside a member
        envelope are replica-health evidence — repeated sick dispatches
        must trip the breaker exactly like the router path would."""

        class AlwaysBroken:
            async def work(self, x=0):
                await asyncio.sleep(0.001)
                raise ConnectionError("instance transport down")

        app = await controller.deploy(
            "mb-1",
            [
                sched_spec(
                    AlwaysBroken,
                    scheduling=SchedulingConfig(max_batch=4, max_wait_ms=1),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("mb-1")
        # single-attempt calls: exactly one dispatch (one breaker
        # strike) each; the third consecutive strike must eject
        opts = RequestOptions(idempotent=True, max_attempts=1)
        for _ in range(3):
            with pytest.raises(RetryableTransportError):
                await handle.call("work", x=1, options=opts)
        replica = app.replicas["entry"][0]
        assert replica.state == ReplicaState.UNHEALTHY, replica.state

    async def test_joining_member_tightens_coalescing_window(
        self, controller
    ):
        """Regression: a deadline-pressed member JOINING an open group
        must pull the group's dispatch forward — not silently wait out
        the opener's full (bulk-tuned) window past its own deadline."""

        class Quick:
            async def work(self, x=0):
                await asyncio.sleep(0.001)
                return x

        await controller.deploy(
            "tw-1",
            [
                sched_spec(
                    Quick,
                    max_ongoing_requests=8,
                    scheduling=SchedulingConfig(max_batch=32, max_wait_ms=500),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("tw-1")
        sched = controller._schedulers[("tw-1", "entry")]
        sched.predictor.note_service(1, 0.005)  # known service time
        # occupy the fast path, then open a group with a deadline-free
        # request (timer armed for the full 500 ms window)
        blocker = asyncio.create_task(handle.call("work", x=1))
        await asyncio.sleep(0.01)
        opener = asyncio.create_task(handle.call("work", x=1))
        await asyncio.sleep(0.02)
        # a joiner with ~150 ms of slack must dispatch the group well
        # before the opener's 500 ms window
        t0 = time.monotonic()
        result = await handle.call(
            "work", x=1, options=RequestOptions(deadline_s=0.15)
        )
        waited = time.monotonic() - t0
        assert result == 1
        assert waited < 0.3, waited
        assert await asyncio.gather(blocker, opener) == [1, 1]

    async def test_abandoned_request_releases_admission_depth(
        self, controller
    ):
        """Regression: a caller whose own budget expired leaves a
        zombie in the queue — it must stop counting against queue/
        tenant admission budgets immediately, not at dispatch."""
        GatedApp.reset()
        await controller.deploy(
            "zb-1",
            [
                sched_spec(
                    GatedApp,
                    max_ongoing_requests=1,
                    scheduling=SchedulingConfig(
                        max_batch=1, max_wait_ms=1, tenant_quota=2
                    ),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("zb-1")
        sched = controller._schedulers[("zb-1", "entry")]
        # saturate the fast path and both dispatch slots
        blockers = [
            asyncio.create_task(handle.call("work", tag=100 + i))
            for i in range(3)
        ]
        await asyncio.sleep(0.05)
        opts = RequestOptions(tenant="acme", timeout_s=0.05)
        with pytest.raises(Exception):
            await handle.call("work", tag=1, options=opts)
        with pytest.raises(Exception):
            await handle.call("work", tag=2, options=opts)
        # both of acme's requests are zombies now — the quota must be
        # free again for its next LIVE request
        assert sched._waiting_by_tenant.get("acme", 0) == 0
        live = asyncio.create_task(
            handle.call("work", tag=3, options=RequestOptions(tenant="acme"))
        )
        await asyncio.sleep(0.05)
        assert not live.done()  # admitted (queued), not quota-shed
        GatedApp.gate.set()
        await asyncio.gather(*blockers, live)

    async def test_unknown_priority_is_flagged(self, controller):
        await controller.deploy(
            "up-1", [sched_spec(EchoApp)]
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("up-1")
        r = await handle.call(
            "echo", value=1, options=RequestOptions(priority="Bulk")
        )
        assert r == {"echo": 1}  # served (default class), but flagged
        sched = controller._schedulers[("up-1", "entry")]
        assert sched.stats["unknown_priority"] == 1
        events = flight.get_events(types=["admission.unknown_priority"])
        assert any(e["attrs"].get("priority") == "Bulk" for e in events)

    async def test_doomed_request_fails_fast_not_late(self, controller):
        class SlowApp:
            async def work(self, tag=0):
                await asyncio.sleep(0.08)
                return tag

        await controller.deploy(
            "doom-1",
            [
                sched_spec(
                    SlowApp,
                    max_ongoing_requests=1,
                    scheduling=SchedulingConfig(max_batch=1, max_wait_ms=1),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("doom-1")
        await handle.call("work", tag=0)  # prime the service estimate
        sched = controller._schedulers[("doom-1", "entry")]
        assert sched.predictor.service_estimate_s() > 0.04
        # saturate, then submit a request whose deadline fits admission
        # but expires while it waits — it is shed the moment it becomes
        # unservable instead of burning a replica slot on a doomed call
        busy = [
            asyncio.create_task(handle.call("work", tag=1 + i))
            for i in range(4)
        ]
        await asyncio.sleep(0.02)
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded):
            await handle.call(
                "work", tag=9, options=RequestOptions(deadline_s=0.12)
            )
        waited = time.monotonic() - t0
        assert waited < 0.3, waited  # failed fast, not after the queue
        assert await asyncio.gather(*busy) == [1, 2, 3, 4]


# ---------------------------------------------------------------------------
# cost model + failover
# ---------------------------------------------------------------------------


class TestScorerAndFailover:
    async def test_scorer_is_pluggable_and_steers_placement(self, controller):
        seen_features = []

        class PinFirst:
            """A deliberately dumb policy — proves the scorer seam
            controls placement and sees the feature contract."""

            def score(self, features):
                assert {"load", "breaker_failures", "signature_affinity",
                        "avoided", "group_size"} <= set(features)
                seen_features.append(features)
                return 0.0  # all tie -> first candidate always wins

        controller.scorer_factory = PinFirst
        app = await controller.deploy(
            "scr-1",
            [
                sched_spec(
                    EchoApp,
                    num_replicas=2,
                    scheduling=SchedulingConfig(max_batch=1, max_wait_ms=1),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("scr-1")
        for i in range(6):
            await handle.call("echo", value=i)
        instances = [r.instance for r in app.replicas["entry"]]
        # every call landed on the same (first) replica: the policy,
        # not least-loaded round robin, decided
        assert sorted(i.calls for i in instances) == [0, 6]
        assert seen_features

    async def test_affinity_prefers_warm_replica(self, controller):
        app = await controller.deploy(
            "scr-2",
            [
                sched_spec(
                    EchoApp,
                    num_replicas=2,
                    scheduling=SchedulingConfig(max_batch=1, max_wait_ms=1),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("scr-2")
        for _ in range(5):
            await handle.call("echo", value=42)
            await asyncio.sleep(0.005)  # sequential: no load pressure
        instances = [r.instance for r in app.replicas["entry"]]
        # with equal load, the affinity bonus keeps one signature's
        # traffic on the replica whose programs/batcher are warm
        assert max(i.calls for i in instances) == 5, [
            i.calls for i in instances
        ]

    async def test_fast_path_app_error_never_feeds_breaker(self, controller):
        """Regression: bad client input on the uncontended fast path is
        an APPLICATION failure — it must not accumulate breaker strikes
        and eject a healthy replica."""

        class Picky:
            async def work(self, x=0):
                await asyncio.sleep(0.001)
                if x < 0:
                    raise ValueError("bad input")
                return x

        app = await controller.deploy(
            "fpb-1",
            [sched_spec(Picky, scheduling=SchedulingConfig(max_batch=1))],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("fpb-1")
        for _ in range(controller.breaker_threshold + 2):
            with pytest.raises(ValueError, match="bad input"):
                await handle.call("work", x=-1)
        replica = app.replicas["entry"][0]
        assert replica.state == ReplicaState.HEALTHY
        assert controller._breaker_counts.get(replica.replica_id, 0) == 0
        assert await handle.call("work", x=3) == 3

    async def test_signature_diverse_backlog_stays_in_fair_queues(
        self, controller
    ):
        """Regression: a burst of distinct-signature requests must not
        drain the fair queues into unbounded open groups — committed
        (open + in-flight) groups stay within dispatch capacity so
        later high-priority arrivals can still overtake."""
        GatedApp.reset()
        await controller.deploy(
            "cap-1",
            [
                sched_spec(
                    GatedApp,
                    max_ongoing_requests=1,
                    scheduling=SchedulingConfig(max_batch=4, max_wait_ms=50),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("cap-1")
        sched = controller._schedulers[("cap-1", "entry")]
        tasks = [
            asyncio.create_task(handle.call("work", tag=i)) for i in range(20)
        ]
        await asyncio.sleep(0.02)
        committed = len(sched._open) + len(sched._inflight)
        assert committed <= sched._dispatch_capacity(), (
            committed,
            sched._dispatch_capacity(),
        )
        assert sched.waiting > 0  # the backlog is IN the queues
        GatedApp.gate.set()
        assert sorted(await asyncio.gather(*tasks)) == list(range(20))

    async def test_transport_failure_fails_over_with_avoid(self, controller):
        class FlakyOnce:
            failures = [0]

            def __init__(self):
                self.calls = 0

            async def echo(self, value=0):
                self.calls += 1
                if FlakyOnce.failures[0] < 1:
                    FlakyOnce.failures[0] += 1
                    raise ConnectionError("synthetic transport failure")
                return value

        FlakyOnce.failures = [0]
        app = await controller.deploy(
            "fo-1",
            [
                sched_spec(
                    FlakyOnce,
                    num_replicas=2,
                    scheduling=SchedulingConfig(max_batch=1, max_wait_ms=1),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("fo-1")
        result = await handle.call(
            "echo", value=7, options=RequestOptions(idempotent=True)
        )
        assert result == 7
        instances = [r.instance for r in app.replicas["entry"]]
        # exactly one failover, and it landed on the OTHER replica (the
        # failed one was stamped on the exception and avoided)
        assert sorted(i.calls for i in instances) == [1, 1]


# ---------------------------------------------------------------------------
# predictive autoscaling
# ---------------------------------------------------------------------------


class TestPredictiveAutoscale:
    async def test_scale_up_before_queue_saturation(self, controller):
        GatedApp.reset()
        app = await controller.deploy(
            "pa-1",
            [
                DeploymentSpec(
                    name="entry",
                    instance_factory=GatedApp,
                    num_replicas=1,
                    max_replicas=3,
                    max_ongoing_requests=8,
                    autoscale=True,
                    scheduling=SchedulingConfig(
                        max_batch=1, max_wait_ms=1, target_wait_s=0.02
                    ),
                )
            ],
        )
        await asyncio.sleep(0.05)
        sched = controller._schedulers[("pa-1", "entry")]
        # a measured service time (deterministic stand-in for the EWMA
        # the completions would feed)
        sched.predictor.note_service(1, 0.05)
        handle = controller.get_handle("pa-1")
        tasks = [
            asyncio.create_task(handle.call("work", tag=i)) for i in range(7)
        ]
        await asyncio.sleep(0.1)
        # NOT saturated: depth is far under the legacy trigger
        # (healthy x max_ongoing = 8) and avg load is low — only the
        # PREDICTOR (projected wait 4 x 0.05 s > 0.02 s) fires
        depth_at_tick = controller._queue_depth[("pa-1", "entry")]
        assert depth_at_tick <= 8
        load = app.replicas["entry"][0].load
        assert load < 0.7
        await controller.health_tick()
        assert len(app.replicas["entry"]) == 2, (
            f"predictive scale-up did not fire "
            f"(depth={depth_at_tick}, load={load})"
        )
        events = flight.get_events(types=["scale.predict"])
        assert any(
            e["attrs"].get("app") == "pa-1"
            and e["attrs"].get("direction") == "up"
            for e in events
        )
        GatedApp.gate.set()
        assert sorted(await asyncio.gather(*tasks)) == list(range(7))

    async def test_scale_down_needs_hysteresis(self, controller):
        app = await controller.deploy(
            "pa-2",
            [
                DeploymentSpec(
                    name="entry",
                    instance_factory=EchoApp,
                    num_replicas=2,
                    min_replicas=1,
                    autoscale=True,
                    scheduling=SchedulingConfig(
                        max_batch=1, max_wait_ms=1, scale_down_ticks=3
                    ),
                )
            ],
        )
        await asyncio.sleep(0.05)
        # idle ticks: the first two verdicts HOLD (hysteresis), the
        # third retires one replica down toward min_replicas
        await controller.health_tick()
        assert len(app.replicas["entry"]) == 2
        await controller.health_tick()
        assert len(app.replicas["entry"]) == 2
        await controller.health_tick()
        assert len(app.replicas["entry"]) == 1
        events = flight.get_events(types=["scale.predict"])
        assert any(
            e["attrs"].get("app") == "pa-2"
            and e["attrs"].get("direction") == "down"
            for e in events
        )

    def test_predictor_projection_math(self):
        p = LoadPredictor(alpha=1.0)
        now = time.monotonic()
        p.note_service(4, 0.4)          # 0.1 s/request
        assert p.service_estimate_s() == pytest.approx(0.1)
        p.note_arrival(now - 0.05)
        p.note_arrival(now)             # 20 req/s instantaneous
        proj = p.projection(now, queue_depth=10, n_replicas=2)
        # wait = depth * s / n = 10 * 0.1 / 2
        assert proj["projected_wait_s"] == pytest.approx(0.5)
        assert proj["utilization"] == pytest.approx(20 * 0.1 / 2, rel=0.01)
        # an idle gap caps the EWMA: a traffic stop decays the rate
        assert p.current_rate(now + 10.0) <= 0.11

    def test_heuristic_cost_model_ordering(self):
        m = HeuristicCostModel()
        idle_warm = m.score(
            {"load": 0.0, "signature_affinity": True, "breaker_failures": 0}
        )
        idle_cold = m.score(
            {"load": 0.0, "signature_affinity": False, "breaker_failures": 0}
        )
        busy = m.score({"load": 0.9, "signature_affinity": False})
        flaky = m.score({"load": 0.0, "breaker_failures": 2})
        avoided = m.score({"load": 0.0, "avoided": True})
        assert idle_warm < idle_cold < busy < flaky < avoided


# ---------------------------------------------------------------------------
# status surfaces
# ---------------------------------------------------------------------------


class TestStatus:
    async def test_scheduler_in_app_status_and_metrics(self, controller):
        await controller.deploy("st-1", [sched_spec(EchoApp)])
        await asyncio.sleep(0.05)
        handle = controller.get_handle("st-1")
        await handle.call("echo", value=1)
        status = controller.get_app_status("st-1")
        sched = status["deployments"]["entry"]["scheduler"]
        assert sched is not None
        assert sched["stats"]["admitted"] == 1
        assert "projected_wait_s" in sched["prediction"]
        assert set(sched["queue_depth"]) == {
            "interactive", "bulk", "background",
        }
        snap = umetrics.collect()
        assert "scheduler_admitted_total" in snap
        # scrape-time gauges from the scheduler InstanceSet
        assert "scheduler_projected_wait_seconds" in snap
        assert "scheduler_queue_depth" in snap

    async def test_unscheduled_deployment_reports_none(self, controller):
        await controller.deploy(
            "st-2",
            [DeploymentSpec(name="entry", instance_factory=EchoApp)],
        )
        await asyncio.sleep(0.05)
        status = controller.get_app_status("st-2")
        assert status["deployments"]["entry"]["scheduler"] is None


# ---------------------------------------------------------------------------
# router-state leak (satellite) — scheduler lifecycle rides along
# ---------------------------------------------------------------------------


class TestRouterStateLifecycle:
    async def test_undeploy_clears_router_state(self, controller):
        for i in range(5):
            app_id = f"churn-{i}"
            await controller.deploy(
                app_id,
                [
                    sched_spec(EchoApp),
                    DeploymentSpec(name="side", instance_factory=EchoApp),
                ],
            )
            await asyncio.sleep(0.02)
            handle = controller.get_handle(app_id)
            await handle.call("echo", value=i)
            # seed the side deployment's router state too
            controller.get_handle(app_id, "side")
            controller._pick_replica(app_id, "side")
            await controller.undeploy(app_id)
        # churn left NOTHING behind: queue-depth entries, rr counters,
        # and schedulers are all swept on undeploy
        assert dict(controller._queue_depth) == {}
        assert controller._rr_counters == {}
        assert controller._schedulers == {}

    async def test_inflight_request_does_not_resurrect_depth_entry(
        self, controller
    ):
        release = asyncio.Event()
        entered = asyncio.Event()

        class SlowApp:
            async def slow(self):
                entered.set()
                await release.wait()
                return "done"

        await controller.deploy(
            "leak-2",
            [DeploymentSpec(name="entry", instance_factory=SlowApp,
                            autoscale=False)],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("leak-2")
        in_flight = asyncio.create_task(handle.call("slow"))
        await asyncio.wait_for(entered.wait(), 2)
        undeploy = asyncio.create_task(controller.undeploy("leak-2"))
        await asyncio.sleep(0.05)
        release.set()
        assert await asyncio.wait_for(in_flight, 2) == "done"
        await asyncio.wait_for(undeploy, 2)
        # the in-flight call's bookkeeping decrement must not re-create
        # the swept entry (previously: defaultdict resurrection at -1)
        assert ("leak-2", "entry") not in controller._queue_depth

    async def test_queued_requests_fail_typed_on_undeploy(self, controller):
        GatedApp.reset()
        await controller.deploy(
            "leak-3",
            [
                sched_spec(
                    GatedApp,
                    max_ongoing_requests=1,
                    scheduling=SchedulingConfig(max_batch=1, max_wait_ms=1),
                )
            ],
        )
        await asyncio.sleep(0.05)
        handle = controller.get_handle("leak-3")
        tasks = [
            asyncio.create_task(handle.call("work", tag=i)) for i in range(5)
        ]
        await asyncio.sleep(0.05)
        GatedApp.gate.set()  # let dispatched work drain
        await controller.undeploy("leak-3", drain_timeout_s=2)
        results = await asyncio.gather(*tasks, return_exceptions=True)
        # dispatched members completed; queued members failed TYPED —
        # never hung, never a raw internal error
        for r in results:
            if isinstance(r, Exception):
                assert isinstance(
                    r, (RuntimeError, asyncio.TimeoutError, KeyError)
                ), r
            else:
                assert r in range(5)


# ---------------------------------------------------------------------------
# batching knobs through spec + manifest (satellite)
# ---------------------------------------------------------------------------


class TestBatchKnobSurfacing:
    async def test_spec_injects_batch_config(self, controller):
        seen = {}

        class BatchAware:
            async def async_init(self):
                seen["cfg"] = getattr(self, "bioengine_batch_config", None)

            async def echo(self, value=0):
                return value

        await controller.deploy(
            "bk-1",
            [
                DeploymentSpec(
                    name="entry",
                    instance_factory=BatchAware,
                    max_batch=3,
                    max_wait_ms=2.5,
                )
            ],
        )
        await asyncio.sleep(0.05)
        assert seen["cfg"] == {"max_batch": 3, "max_wait_ms": 2.5}

    def test_builder_parses_batching_and_scheduling(self, tmp_path):
        app_dir = tmp_path / "src"
        app_dir.mkdir()
        (app_dir / "manifest.yaml").write_text(
            """\
name: Knobs
id: knobs
id_emoji: "k"
description: knob surfacing
type: tpu-serve
deployments:
  - dep:Dep
deployment_config:
  dep:
    batching:
      max_batch: 5
      max_wait_ms: 3
    scheduling:
      max_queue_depth: 32
      class_weights:
        interactive: 6
        bulk: 1
      tenant_quota: 4
"""
        )
        (app_dir / "dep.py").write_text(
            "from bioengine_tpu.rpc import schema_method\n"
            "class Dep:\n"
            "    @schema_method\n"
            "    async def ping(self, context=None):\n"
            "        \"\"\"ping\"\"\"\n"
            "        return 'pong'\n"
        )
        built = AppBuilder(workdir_root=tmp_path / "apps").build(
            app_id="knobs", local_path=app_dir
        )
        spec = built.specs[0]
        assert spec.max_batch == 5
        assert spec.max_wait_ms == 3.0
        assert spec.batch_config() == {"max_batch": 5, "max_wait_ms": 3.0}
        assert spec.scheduling is not None
        assert spec.scheduling.max_queue_depth == 32
        assert spec.scheduling.tenant_quota == 4
        assert spec.scheduling.class_weights == {
            "interactive": 6.0, "bulk": 1.0,
        }

    def test_builder_rejects_non_numeric_batching_value(self, tmp_path):
        app_dir = tmp_path / "src"
        app_dir.mkdir()
        (app_dir / "manifest.yaml").write_text(
            """\
name: BadVal
id: badval
id_emoji: "b"
description: bad batching value
type: tpu-serve
deployments:
  - dep:Dep
deployment_config:
  dep:
    batching:
      max_batch: many
"""
        )
        (app_dir / "dep.py").write_text(
            "from bioengine_tpu.rpc import schema_method\n"
            "class Dep:\n"
            "    @schema_method\n"
            "    async def ping(self, context=None):\n"
            "        \"\"\"ping\"\"\"\n"
            "        return 'pong'\n"
        )
        # a typed build failure naming the deployment — never a raw
        # ValueError traceback out of int()
        with pytest.raises(AppBuildError, match="dep"):
            AppBuilder(workdir_root=tmp_path / "apps").build(
                app_id="badval", local_path=app_dir
            )

    def test_builder_rejects_bad_scheduling(self, tmp_path):
        app_dir = tmp_path / "src"
        app_dir.mkdir()
        (app_dir / "manifest.yaml").write_text(
            """\
name: Bad
id: bad
id_emoji: "b"
description: bad scheduling
type: tpu-serve
deployments:
  - dep:Dep
deployment_config:
  dep:
    scheduling:
      max_batchez: 5
"""
        )
        (app_dir / "dep.py").write_text(
            "from bioengine_tpu.rpc import schema_method\n"
            "class Dep:\n"
            "    @schema_method\n"
            "    async def ping(self, context=None):\n"
            "        \"\"\"ping\"\"\"\n"
            "        return 'pong'\n"
        )
        with pytest.raises(AppBuildError, match="scheduling"):
            AppBuilder(workdir_root=tmp_path / "apps").build(
                app_id="bad", local_path=app_dir
            )


# ---------------------------------------------------------------------------
# multi-host: one __batch__ round trip per group; mixed-priority soak
# ---------------------------------------------------------------------------

SCHED_MANIFEST = """\
name: Sched App {n}
id: sched-app-{n}
id_emoji: "\U0001F39B"
description: scheduled arithmetic for soak traffic
type: tpu-serve
version: 1.0.0
deployments:
  - sched_dep:SchedDep
authorized_users: ["*"]
deployment_config:
  sched_dep:
    num_replicas: 2
    min_replicas: 2
    max_replicas: 2
    chips: 1
    autoscale: false
    batching:
      max_batch: 8
      max_wait_ms: 4
    scheduling:
      max_batch: 8
      max_wait_ms: 4
      max_queue_depth: 512
"""

SCHED_SOURCE = '''\
from bioengine_tpu.rpc import schema_method


class SchedDep:
    def __init__(self):
        self.calls = 0

    @schema_method
    async def add(self, a: int, b: int, context=None):
        """Idempotent arithmetic."""
        self.calls += 1
        return {"sum": a + b}

    @schema_method
    async def flaky_add(self, a: int, b: int, context=None):
        """Raises on every 4th call on this replica."""
        self.calls += 1
        if self.calls % 4 == 0:
            raise ValueError("flaky member")
        return {"sum": a + b}
'''


def _write_sched_app(tmp_path: Path, n: int) -> Path:
    app_dir = tmp_path / f"sched-src-{n}"
    app_dir.mkdir(exist_ok=True)
    (app_dir / "manifest.yaml").write_text(SCHED_MANIFEST.format(n=n))
    (app_dir / "sched_dep.py").write_text(SCHED_SOURCE)
    return app_dir


def _no_local_chips() -> ClusterState:
    return ClusterState(TpuTopology(chips=(), n_hosts=1, platform="cpu"))


@pytest.fixture()
async def sched_plane(tmp_path):
    server = RpcServer(host="127.0.0.1", admin_users=["admin"])
    await server.start()
    token = server.issue_token("admin", is_admin=True)
    controller = ServeController(_no_local_chips(), health_check_period=3600)
    controller.attach_rpc(server, admin_users=["admin"])
    hosts = []

    async def spawn_host(host_id: str, rejoin: bool = True) -> WorkerHost:
        host = WorkerHost(
            server_url=server.url,
            token=token,
            host_id=host_id,
            workspace_dir=tmp_path / f"ws-{host_id}",
            rejoin=rejoin,
        )
        await host.start()
        hosts.append(host)
        return host

    try:
        yield server, controller, spawn_host, tmp_path
    finally:
        for host in hosts:
            try:
                await host.stop()
            except Exception:
                pass
        await controller.stop()
        await server.stop()


async def _kill_host(host: WorkerHost) -> None:
    host.rejoin = False
    host.connection.auto_reconnect = False
    host.connection._closing = True
    await host.connection._abort_connection()


async def _deploy_sched_app(controller, tmp_path, n=1):
    builder = AppBuilder(workdir_root=tmp_path / f"apps-{n}")
    built = builder.build(
        app_id=f"sched-app-{n}", local_path=_write_sched_app(tmp_path, n)
    )
    await controller.deploy(f"sched-app-{n}", built.specs)
    return controller.apps[f"sched-app-{n}"].replicas["sched_dep"]


class TestCrossHostBatching:
    async def test_coalesced_group_is_one_wire_round_trip(self, sched_plane):
        """K compatible requests to a REMOTE replica ride one
        ``replica_call`` frame (the ``__batch__`` verb), not K: the
        ``host.replica_call`` fault point counts round trips."""
        server, controller, spawn_host, tmp_path = sched_plane
        await spawn_host("h1")
        await spawn_host("h2")
        replicas = await _deploy_sched_app(controller, tmp_path)
        assert all(r.is_remote for r in replicas)
        handle = controller.get_handle("sched-app-1")
        r = await handle.call("add", 1, 1)  # warm fast path
        assert r["sum"] == 2
        # arm a never-triggering spec purely to count round trips
        # (configure resets the hit counter)
        faults.configure("host.replica_call", "delay", nth=1 << 30, delay_s=0)
        results = await asyncio.gather(
            *(handle.call("add", 7, 5) for _ in range(8))
        )
        assert all(r["sum"] == 12 for r in results)
        round_trips = faults.hits("host.replica_call")
        # 8 requests crossed the wire in <= 3 round trips (fast path +
        # coalesced group(s)), not 8
        assert round_trips <= 3, round_trips
        sched = controller._schedulers[("sched-app-1", "sched_dep")]
        assert sched.stats["dispatched_requests"] >= 7

    async def test_remote_member_failure_isolated_on_wire(self, sched_plane):
        """A member failure inside a remote ``__batch__`` group rides
        back as a typed per-member envelope: its caller gets the app
        error (never retried), groupmates get their results."""
        server, controller, spawn_host, tmp_path = sched_plane
        await spawn_host("h1")
        await spawn_host("h2")
        await _deploy_sched_app(controller, tmp_path)
        handle = controller.get_handle("sched-app-1")
        results = await asyncio.gather(
            *(handle.call("flaky_add", 2, 3) for _ in range(8)),
            return_exceptions=True,
        )
        ok = [r for r in results if isinstance(r, dict)]
        errors = [r for r in results if isinstance(r, Exception)]
        assert len(ok) + len(errors) == 8
        assert len(ok) >= 5 and all(r["sum"] == 5 for r in ok)
        assert errors, "the every-4th-call failure never surfaced"
        assert all("flaky member" in str(e) for e in errors), errors


class TestMixedPrioritySoak:
    async def test_soak_with_host_kill_and_replan(self, sched_plane):
        """Satellite acceptance: 2 scheduled apps x 2 replicas across 2
        hosts under sustained mixed-priority traffic; one host dies
        mid-soak. Asserts: zero failed idempotent requests (queued work
        re-planned onto the survivor), both classes make progress
        throughout (no starvation), the scheduler coalesced
        cross-replica groups, and chip accounting survives the kill."""
        server, controller, spawn_host, tmp_path = sched_plane
        h1 = await spawn_host("h1")
        h2 = await spawn_host("h2")
        await _deploy_sched_app(controller, tmp_path, n=1)
        await _deploy_sched_app(controller, tmp_path, n=2)
        handles = {
            1: controller.get_handle("sched-app-1"),
            2: controller.get_handle("sched-app-2"),
        }
        per_worker = 10
        workers = 3  # parallel streams per (app, class): compatible
        #              requests must OVERLAP for coalescing to happen
        opts = {
            "interactive": RequestOptions(
                idempotent=True, deadline_s=30, max_attempts=8,
                priority="interactive",
            ),
            "bulk": RequestOptions(
                idempotent=True, deadline_s=30, max_attempts=8,
                priority="bulk",
            ),
        }
        failures: list = []
        completions: list[tuple[str, int]] = []
        kill_at = asyncio.Event()

        # per-class CONSTANT args: requests within a class are
        # batch-compatible (same signature), so overlapping streams
        # coalesce; the class code doubles as the result check
        cls_code = {"interactive": 10, "bulk": 20}

        async def traffic(app_n: int, cls: str, worker: int):
            for i in range(per_worker):
                try:
                    r = await handles[app_n].call(
                        "add", app_n, cls_code[cls], options=opts[cls]
                    )
                    assert r["sum"] == app_n + cls_code[cls]
                    completions.append((cls, app_n))
                except Exception as e:  # noqa: BLE001 — counted, not raised
                    failures.append((cls, app_n, e))
                if (
                    cls == "interactive"
                    and app_n == 1
                    and worker == 0
                    and i == 4
                ):
                    kill_at.set()
                await asyncio.sleep(0.004)

        tasks = [
            asyncio.create_task(traffic(n, cls, w))
            for n in (1, 2)
            for cls in ("interactive", "bulk")
            for w in range(workers)
        ]
        await asyncio.wait_for(kill_at.wait(), 15)
        await _kill_host(h1)

        recovered = False
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            await controller.health_tick()
            routable = [
                r
                for n in (1, 2)
                for r in controller.apps[f"sched-app-{n}"].replicas[
                    "sched_dep"
                ]
                if r.state in (ReplicaState.HEALTHY, ReplicaState.TESTING)
            ]
            if len(routable) == 4 and all(
                r.host_id == "h2" for r in routable
            ):
                recovered = True
                break
            await asyncio.sleep(0.1)
        await asyncio.gather(*tasks)

        total = 2 * 2 * workers * per_worker
        assert failures == [], failures[:5]
        assert len(completions) == total
        assert recovered, "replicas were not re-planned onto the survivor"
        # zero starvation: every bulk request completed, and both
        # classes made progress in the first half of the soak
        bulk = [c for c in completions if c[0] == "bulk"]
        assert len(bulk) == total // 2
        first_half = completions[: len(completions) // 2]
        assert any(c[0] == "bulk" for c in first_half)
        assert any(c[0] == "interactive" for c in first_half)
        # cross-replica batching actually happened during the soak
        coalesced = False
        for n in (1, 2):
            s = controller._schedulers[(f"sched-app-{n}", "sched_dep")].stats
            if (
                s["dispatched_requests"] > 0
                and s["dispatched_groups"] < s["dispatched_requests"]
            ):
                coalesced = True
        assert coalesced, "no cross-replica batching observed during soak"
        # chip accounting survived the kill: the dead host holds
        # nothing, the survivor leases all four replicas
        assert controller.cluster_state.hosts["h1"].chips_in_use == {}
        h2_leases = controller.cluster_state.hosts["h2"].chips_in_use
        assert len(set(h2_leases.values())) == 4
