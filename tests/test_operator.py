"""Operator tests (dynamo_tpu/operator/): spec → manifests rendering,
create/update/GC reconciliation against the FakeKube double, and status
write-back — the envtest-style coverage of the reference's Go operator
(reference: deploy/cloud/operator/test/e2e) without a cluster."""

import asyncio
import json

import pytest

from dynamo_tpu.operator import (
    FakeKube,
    GraphDeployment,
    GraphOperator,
    STATUS_BUCKET,
    render,
)
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.sdk.api_store import DEPLOYMENT_BUCKET

pytestmark = pytest.mark.anyio


SPEC = {
    "namespace": "dynamo",
    "services": {
        "ControlPlane": {"role": "control-plane"},
        "Frontend": {"role": "frontend", "port": 8080},
        "Worker": {
            "role": "worker",
            "replicas": 2,
            "chips": 4,
            "args": {"model_path": "/models/llama", "mesh": "tp=4"},
        },
    },
}


def test_render_manifests():
    dep = GraphDeployment.from_record({"name": "graph", "spec": SPEC})
    manifests = render(dep)
    kinds = [(m["kind"], m["metadata"]["name"]) for m in manifests]
    assert ("Deployment", "graph-worker") in kinds
    assert ("Deployment", "graph-frontend") in kinds
    assert ("Service", "graph-frontend") in kinds
    assert ("Service", "graph-controlplane") in kinds
    worker = next(
        m for m in manifests if m["metadata"]["name"] == "graph-worker"
    )
    assert worker["spec"]["replicas"] == 2
    container = worker["spec"]["template"]["spec"]["containers"][0]
    assert container["resources"]["limits"]["google.com/tpu"] == "4"
    assert "--model-path=/models/llama" in container["command"]
    # the dialed control-plane DNS name must be exactly the rendered
    # control-plane Service's name (spec names it "ControlPlane")
    assert "--control-plane=graph-controlplane:6380" in container["command"]


def test_render_rejects_unknown_role():
    with pytest.raises(ValueError):
        GraphDeployment.from_record(
            {"name": "x", "spec": {"services": {"Z": {"role": "gpu"}}}}
        )


async def _put_spec(drt, name, spec):
    await drt.bus.put_object(
        DEPLOYMENT_BUCKET, name,
        json.dumps({"name": name, "spec": spec, "revision": 1}).encode(),
    )


async def test_reconcile_create_update_gc_status():
    drt = await DistributedRuntime.in_process()
    kube = FakeKube()
    op = GraphOperator(drt, kube)
    try:
        await _put_spec(drt, "graph", SPEC)
        status = await op.reconcile_once()
        assert kube.get("Deployment", "dynamo", "graph-worker") is not None
        assert kube.get("Service", "dynamo", "graph-frontend") is not None
        assert status["graph"]["ready"] is False  # nothing ready yet
        assert status["graph"]["services"]["Worker"]["desired"] == 2

        # Unchanged spec → no re-apply (spec-hash short-circuits).
        applies = kube.apply_count
        await op.reconcile_once()
        assert kube.apply_count == applies

        # Replica bump patches the child Deployment.
        spec2 = json.loads(json.dumps(SPEC))
        spec2["services"]["Worker"]["replicas"] = 3
        await _put_spec(drt, "graph", spec2)
        await op.reconcile_once()
        worker = kube.get("Deployment", "dynamo", "graph-worker")
        assert worker["spec"]["replicas"] == 3

        # Readiness reaches the status bucket once replicas come up.
        for name in ("graph-controlplane", "graph-frontend", "graph-worker"):
            kube.mark_ready("Deployment", "dynamo", name)
        status = await op.reconcile_once()
        assert status["graph"]["ready"] is True
        raw = await drt.bus.get_object(STATUS_BUCKET, "graph")
        assert json.loads(raw)["ready"] is True

        # Removing a service garbage-collects its children; deleting the
        # spec garbage-collects everything + the status entry.
        spec3 = json.loads(json.dumps(spec2))
        del spec3["services"]["Frontend"]
        await _put_spec(drt, "graph", spec3)
        await op.reconcile_once()
        assert kube.get("Deployment", "dynamo", "graph-frontend") is None
        assert kube.get("Service", "dynamo", "graph-frontend") is None

        await drt.bus.delete_object(DEPLOYMENT_BUCKET, "graph")
        await op.reconcile_once()
        assert kube.get("Deployment", "dynamo", "graph-worker") is None
        assert await drt.bus.get_object(STATUS_BUCKET, "graph") is None
    finally:
        await drt.shutdown()


async def test_broken_spec_update_protects_running_children():
    """Updating a live deployment with an unparseable spec must hold
    state, not garbage-collect the running pods."""
    drt = await DistributedRuntime.in_process()
    kube = FakeKube()
    op = GraphOperator(drt, kube)
    try:
        await _put_spec(drt, "graph", SPEC)
        await op.reconcile_once()
        assert kube.get("Deployment", "dynamo", "graph-worker") is not None
        # typo'd role in an update
        bad = json.loads(json.dumps(SPEC))
        bad["services"]["Worker"]["role"] = "gpu"
        await _put_spec(drt, "graph", bad)
        status = await op.reconcile_once()
        assert "error" in status["graph"]
        assert kube.get("Deployment", "dynamo", "graph-worker") is not None
        # fixing the spec resumes reconciliation
        await _put_spec(drt, "graph", SPEC)
        status = await op.reconcile_once()
        assert "error" not in status["graph"]
    finally:
        await drt.shutdown()


async def test_service_port_change_reapplies_service():
    drt = await DistributedRuntime.in_process()
    kube = FakeKube()
    op = GraphOperator(drt, kube)
    try:
        await _put_spec(drt, "graph", SPEC)
        await op.reconcile_once()
        svc = kube.get("Service", "dynamo", "graph-frontend")
        assert svc["spec"]["ports"][0]["port"] == 8080
        spec2 = json.loads(json.dumps(SPEC))
        spec2["services"]["Frontend"]["port"] = 9090
        await _put_spec(drt, "graph", spec2)
        await op.reconcile_once()
        svc = kube.get("Service", "dynamo", "graph-frontend")
        assert svc["spec"]["ports"][0]["port"] == 9090
    finally:
        await drt.shutdown()


async def test_gc_covers_non_default_namespace():
    """Children rendered into a spec's own namespace are garbage-collected
    after the spec is deleted (the namespace rides the status record)."""
    drt = await DistributedRuntime.in_process()
    kube = FakeKube()
    op = GraphOperator(drt, kube)  # operator namespace stays "dynamo"
    try:
        spec = json.loads(json.dumps(SPEC))
        spec["namespace"] = "prod"
        await _put_spec(drt, "graph", spec)
        await op.reconcile_once()
        assert kube.get("Deployment", "prod", "graph-worker") is not None
        await drt.bus.delete_object(DEPLOYMENT_BUCKET, "graph")
        await op.reconcile_once()
        assert kube.get("Deployment", "prod", "graph-worker") is None
        assert kube.get("Service", "prod", "graph-frontend") is None
    finally:
        await drt.shutdown()


async def test_reconcile_survives_bad_spec():
    drt = await DistributedRuntime.in_process()
    kube = FakeKube()
    op = GraphOperator(drt, kube)
    try:
        await _put_spec(drt, "bad", {"services": {"X": {"role": "gpu"}}})
        await _put_spec(drt, "good", SPEC)
        status = await op.reconcile_once()
        assert "error" in status["bad"]
        # the good deployment still reconciles
        assert kube.get("Deployment", "dynamo", "good-worker") is not None
    finally:
        await drt.shutdown()


async def test_watch_driven_reconcile_reacts_without_resync():
    """the loop is watch-driven, not a fixed-interval
    poll. With a resync interval of ONE HOUR, (a) a spec PUT through the
    api-store's notification subject and (b) an out-of-band child
    deletion seen by the cluster watch must each trigger a reconcile
    within milliseconds."""
    from dynamo_tpu.operator.operator import SPEC_EVENTS_SUBJECT

    drt = await DistributedRuntime.in_process()
    kube = FakeKube()
    op = GraphOperator(drt, kube, interval_s=3600.0)
    try:
        await op.start()
        await asyncio.sleep(0.05)  # first (startup) pass
        base = op.reconcile_count
        assert base >= 1

        # (a) Spec event: put the spec, then publish the api-store kick.
        await _put_spec(drt, "graph", SPEC)
        await drt.bus.publish(SPEC_EVENTS_SUBJECT, b"graph")
        for _ in range(100):
            await asyncio.sleep(0.01)
            if kube.get("Deployment", "dynamo", "graph-worker"):
                break
        assert kube.get("Deployment", "dynamo", "graph-worker") is not None
        assert op.reconcile_count > base

        # (b) Cluster event: an out-of-band deletion fires the watch; the
        # reconciler must restore the child with no resync wait.
        count = op.reconcile_count
        kube.external_delete("Deployment", "dynamo", "graph-worker")
        for _ in range(100):
            await asyncio.sleep(0.01)
            if kube.get("Deployment", "dynamo", "graph-worker"):
                break
        assert kube.get("Deployment", "dynamo", "graph-worker") is not None
        assert op.reconcile_count > count
    finally:
        await op.stop()
        await drt.shutdown()


async def test_api_store_put_kicks_operator():
    """End-to-end: a deployment created through the api-store REST surface
    reconciles immediately (the store publishes SPEC_EVENTS_SUBJECT)."""
    import httpx

    from dynamo_tpu.sdk.api_store import ApiStore

    drt = await DistributedRuntime.in_process()
    kube = FakeKube()
    op = GraphOperator(drt, kube, interval_s=3600.0)
    store = await ApiStore(drt, host="127.0.0.1", port=0).start()
    try:
        await op.start()
        await asyncio.sleep(0.05)
        async with httpx.AsyncClient() as client:
            r = await client.post(
                f"http://127.0.0.1:{store.port}/v1/deployments",
                json={"name": "graph", "spec": SPEC},
            )
            assert r.status_code == 201
        for _ in range(100):
            await asyncio.sleep(0.01)
            if kube.get("Deployment", "dynamo", "graph-worker"):
                break
        assert kube.get("Deployment", "dynamo", "graph-worker") is not None
    finally:
        await op.stop()
        await store.stop()
        await drt.shutdown()


def test_crd_style_validation_messages():
    """The schema rejects malformed specs with precise, field-scoped
    messages (the kubebuilder validation-marker role)."""
    from dynamo_tpu.operator.resources import validate_record

    assert validate_record({"name": "ok", "spec": {
        "services": {"worker": {"role": "worker", "replicas": 1}}
    }}) == []

    errs = validate_record({"name": "Bad_Name", "spec": {
        "namespace": "ALSO BAD",
        "services": {
            "w": {"role": "worker", "replicas": -1, "chips": True,
                  "port": 99999, "args": []},
            "cp1": {"role": "control-plane"},
            "cp2": {"role": "control-plane"},
        },
    }})
    text = "\n".join(errs)
    assert "DNS-1123" in text
    assert "replicas" in text and "chips" in text
    assert "port" in text and "args" in text
    assert "at most one control-plane" in text
    assert validate_record({"name": "x", "spec": {"services": {}}}) == [
        "spec.services must be a non-empty object"
    ]
