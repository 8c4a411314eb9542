"""Operator e2e over the REAL Kubernetes REST protocol: GraphOperator + operator/restkube.py against tests/k8s_apiserver.py
— bearer auth, server-side-apply PATCH, label-selector lists, streaming
watches, and CRD-gated GraphDeployment mirroring, all over an actual HTTP
socket. (No kubectl/kind/egress exists in this environment — see the
emulator's docstring for exactly what is and isn't real here; the same
RestKube client pointed at a genuine apiserver needs only
RestKube.in_cluster().)

Ports the FakeKube suite's happy path + drift repair; the drift-repair
leg goes through the REAL watch stream (HTTP chunked events → reader
thread → reconcile kick), not a test callback.
"""

import asyncio
import json

import pytest

from dynamo_tpu.operator import GraphOperator, STATUS_BUCKET
from dynamo_tpu.operator.restkube import RestKube
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.sdk.api_store import DEPLOYMENT_BUCKET

from k8s_apiserver import TOKEN, ApiServerEmulator

pytestmark = pytest.mark.anyio

SPEC = {
    "namespace": "dynamo",
    "services": {
        "ControlPlane": {"role": "control-plane"},
        "Frontend": {"role": "frontend", "port": 8080},
        "Worker": {"role": "worker", "replicas": 2, "chips": 4},
    },
}


async def _put_spec(drt, name, spec):
    await drt.bus.put_object(
        DEPLOYMENT_BUCKET, name,
        json.dumps({"name": name, "spec": spec, "revision": 1}).encode(),
    )


async def test_rest_operator_happy_path_and_drift_repair():
    api = await ApiServerEmulator().start()
    drt = await DistributedRuntime.in_process()
    kube = RestKube(api.url, token=TOKEN)
    # Short resync only as a safety net — drift repair below must arrive
    # via the watch stream well before it.
    op = GraphOperator(drt, kube, interval_s=5.0)
    try:
        await _put_spec(drt, "graph", SPEC)
        await op.start()
        status = await op.reconcile_once()

        # CRD installed over POST; custom-resource paths now serve.
        assert "graphdeployments.dynamo.tpu" in api.crds
        # Children exist in the emulator's store via server-side apply.
        assert ("deployments", "dynamo", "graph-worker") in api.objects
        assert ("services", "dynamo", "graph-frontend") in api.objects
        # GraphDeployment mirror carries spec + status.
        gd = api.objects[("graphdeployments", "dynamo", "graph")]
        assert gd["spec"]["services"]["Worker"]["replicas"] == 2
        assert gd["status"]["ready"] is False
        assert status["graph"]["ready"] is False

        # Steady state: a second pass applies nothing (spec-hash +
        # mirror-diff short-circuits).
        patches = api.patch_count
        await op.reconcile_once()
        assert api.patch_count == patches

        # Kubelet brings replicas up -> ready in status bucket AND mirror.
        for svc in ("controlplane", "frontend", "worker"):
            api.mark_ready("dynamo", f"graph-{svc}")
        status = await op.reconcile_once()
        assert status["graph"]["ready"] is True
        gd = api.objects[("graphdeployments", "dynamo", "graph")]
        assert gd["status"]["ready"] is True

        # Drift repair via the REAL watch: delete a child out-of-band;
        # the streamed DELETED event must kick a reconcile that restores
        # it, with no manual reconcile_once here.
        api.external_delete("deployments", "dynamo", "graph-worker")
        async def _restored():
            while ("deployments", "dynamo", "graph-worker") not in api.objects:
                await asyncio.sleep(0.05)
        await asyncio.wait_for(_restored(), 30)

        # Spec deletion garbage-collects children AND the mirror.
        await drt.bus.delete_object(DEPLOYMENT_BUCKET, "graph")
        await op.reconcile_once()
        assert not any(p == "deployments" for p, _, _ in api.objects)
        assert not any(
            p == "graphdeployments" for p, _, _ in api.objects
        )
        assert await drt.bus.list_objects(STATUS_BUCKET) == []
    finally:
        await op.stop()
        await drt.shutdown()
        await api.stop()


def test_crd_yaml_matches_packaged_constant():
    """deploy/k8s/crd-graphdeployment.yaml (manual installs) must stay in
    sync with resources.GRAPHDEPLOYMENT_CRD (what the operator actually
    installs — packaged trees have no deploy/ directory)."""
    import yaml
    from pathlib import Path

    from dynamo_tpu.operator.resources import GRAPHDEPLOYMENT_CRD

    on_disk = yaml.safe_load(
        (Path(__file__).resolve().parent.parent / "deploy" / "k8s"
         / "crd-graphdeployment.yaml").read_text()
    )
    assert on_disk == GRAPHDEPLOYMENT_CRD


async def test_rest_client_wire_discipline():
    """Protocol details a kubectl shim would hide: bearer auth is
    enforced, apply uses server-side-apply semantics, unknown custom
    resources 404 until their CRD lands. (Every client call runs in a
    worker thread — the emulator serves on this test's event loop, and
    blocking it would deadlock; the operator does the same via
    asyncio.to_thread.)"""
    import httpx

    def call(fn, *a):
        return asyncio.to_thread(fn, *a)

    api = await ApiServerEmulator().start()
    try:
        # Wrong token -> 401 surfaces as an HTTP error, not silence.
        bad = RestKube(api.url, token="wrong")
        with pytest.raises(httpx.HTTPStatusError):
            await call(bad.apply, {
                "apiVersion": "apps/v1", "kind": "Deployment",
                "metadata": {"name": "x", "namespace": "d"},
            })

        kube = RestKube(api.url, token=TOKEN)
        # Custom resource before CRD: 404, like a real cluster.
        with pytest.raises(httpx.HTTPStatusError):
            await call(kube.apply, {
                "apiVersion": "dynamo.tpu/v1alpha1",
                "kind": "GraphDeployment",
                "metadata": {"name": "g", "namespace": "d"},
            })
        import yaml
        from pathlib import Path

        crd = yaml.safe_load(
            (Path(__file__).resolve().parent.parent / "deploy" / "k8s"
             / "crd-graphdeployment.yaml").read_text()
        )
        await call(kube.ensure_crd, crd)
        await call(kube.ensure_crd, crd)  # idempotent (409 swallowed)
        await call(kube.apply, {
            "apiVersion": "dynamo.tpu/v1alpha1",
            "kind": "GraphDeployment",
            "metadata": {"name": "g", "namespace": "d",
                         "labels": {"app": "dynamo-tpu"}},
            "spec": {"services": {}},
        })
        assert await call(kube.get, "GraphDeployment", "d", "g") is not None
        assert len(await call(
            kube.list, "GraphDeployment", "d", {"app": "dynamo-tpu"}
        )) == 1
        assert await call(kube.delete, "GraphDeployment", "d", "g") is True
        assert await call(kube.delete, "GraphDeployment", "d", "g") is False
    finally:
        await api.stop()
