"""The one-command local cluster (``cli mini up`` — mini-langstream parity)
and its process-kubelet.

The e2e smoke drives the ENTIRE production deploy path with processes as
pods: embedded kube API server over HTTP → control plane in k8s mode →
operator (Application CR → setup/deployer Jobs → Agent CRs → StatefulSets)
→ process-kubelet (real pod entrypoint subprocesses) → tsbroker transport →
websocket chat through the api-gateway. Reference parity:
``mini-langstream`` + the e2e suite's K3s container
(``LocalK3sContainer.java``) — the closest this image can get to a real
cluster without a container runtime.
"""

from __future__ import annotations

import base64
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# ProcessKubelet unit behavior (fast, no cluster)
# ---------------------------------------------------------------------------


@pytest.fixture()
def kube():
    from langstream_tpu.k8s.apiserver import FakeKubeApiServer
    from langstream_tpu.k8s.client import HttpKubeApi

    server = FakeKubeApiServer().start()
    api = HttpKubeApi(server.url)
    api.apply({"apiVersion": "v1", "kind": "Namespace",
               "metadata": {"name": "ns1"}})
    yield api
    server.stop()


def _job(ns: str, name: str, argv: list[str], volumes=None, mounts=None):
    return {
        "apiVersion": "batch/v1", "kind": "Job",
        "metadata": {"name": name, "namespace": ns},
        "spec": {"template": {"spec": {
            "containers": [{
                "name": "main",
                "command": ["python", "-c"] + argv,
                "volumeMounts": mounts or [],
            }],
            "volumes": volumes or [],
        }}},
    }


def test_kubelet_runs_job_to_completion_and_patches_status(kube, tmp_path):
    from langstream_tpu.k8s.kubelet import ProcessKubelet

    kube.apply(_job("ns1", "ok-job", ["print('job ran')"]))
    kube.apply(_job("ns1", "bad-job", ["raise SystemExit(3)"]))
    kubelet = ProcessKubelet(kube, root=tmp_path)
    deadline = time.time() + 30
    while time.time() < deadline:
        kubelet.reconcile_once()
        ok = kube.get("Job", "ns1", "ok-job")
        bad = kube.get("Job", "ns1", "bad-job")
        if (ok.get("status") or {}).get("succeeded") and (
            bad.get("status") or {}
        ).get("failed"):
            break
        time.sleep(0.2)
    else:
        pytest.fail("jobs did not reach terminal status")
    kubelet.stop()
    log = (tmp_path / "pods" / "ns1" / "ok-job" / "pod.log").read_text()
    assert "job ran" in log


def test_kubelet_hands_the_chip_to_one_requesting_pod(kube, tmp_path):
    """One process per chip: only a container that requests google.com/tpu
    sees the chip, one pod at a time; everything else is pinned to the CPU
    (and on a chipless node, everything)."""
    from langstream_tpu.k8s.kubelet import ProcessKubelet, _Pod

    wants = {"resources": {"limits": {"google.com/tpu": "1"}}}
    laptop = ProcessKubelet(kube, root=tmp_path / "laptop")
    pod = _Pod(name="a-0", namespace="ns1", kind="StatefulSet", owner="a", template_hash="h")
    assert laptop._container_env(pod, wants)["JAX_PLATFORMS"] == "cpu"

    node = ProcessKubelet(
        kube, root=tmp_path / "node", env_extra={"JAX_PLATFORMS": "tpu,cpu"},
        tpu_chips=1,
    )
    assert node._container_env(pod, {})["JAX_PLATFORMS"] == "cpu"
    assert node._container_env(pod, wants)["JAX_PLATFORMS"] == "tpu,cpu"
    # the first requester runs and holds the chip; a second is refused
    pod.proc = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    node.pods[("ns1", "a-0")] = pod
    try:
        other = _Pod(name="b-0", namespace="ns1", kind="StatefulSet", owner="b", template_hash="h")
        with pytest.raises(RuntimeError, match="one process at a time"):
            node._container_env(other, wants)
    finally:
        pod.proc.kill()
        pod.proc.wait(timeout=10)
    # the holder exited: the chip is free again
    assert node._container_env(other, wants)["JAX_PLATFORMS"] == "tpu,cpu"


def test_kubelet_statefulset_pods_env_volumes_and_scale(kube, tmp_path):
    """STS pods get the downward-API pod name, secret volumes as files with
    mountPaths rewritten, readyReplicas status; scale-down kills pods."""
    from langstream_tpu.k8s.kubelet import ProcessKubelet

    kube.apply({
        "apiVersion": "v1", "kind": "Secret",
        "metadata": {"name": "cfg", "namespace": "ns1"},
        "data": {"config": base64.b64encode(b'{"hello": "world"}').decode()},
    })
    script = (
        "import os, sys, time, json; "
        "cfg = json.load(open(sys.argv[1])); "
        "print('pod', os.environ['LS_POD_NAME'], cfg['hello'], flush=True); "
        "time.sleep(3600)"
    )
    kube.apply({
        "apiVersion": "apps/v1", "kind": "StatefulSet",
        "metadata": {"name": "agent", "namespace": "ns1"},
        "spec": {
            "replicas": 2,
            "template": {"spec": {
                "containers": [{
                    "name": "runtime",
                    "command": ["python", "-c", script, "/app-config/config"],
                    "env": [
                        {"name": "LS_POD_NAME", "valueFrom": {"fieldRef": {
                            "fieldPath": "metadata.name"}}},
                    ],
                    "volumeMounts": [
                        {"name": "app-config", "mountPath": "/app-config"},
                    ],
                }],
                "volumes": [
                    {"name": "app-config", "secret": {"secretName": "cfg"}},
                ],
            }},
        },
    })
    kubelet = ProcessKubelet(kube, root=tmp_path)
    deadline = time.time() + 30
    while time.time() < deadline:
        kubelet.reconcile_once()
        sts = kube.get("StatefulSet", "ns1", "agent")
        if (sts.get("status") or {}).get("readyReplicas") == 2:
            break
        time.sleep(0.2)
    else:
        pytest.fail("statefulset pods never became ready")
    # pod python startup can take seconds (site machinery): poll the logs
    deadline = time.time() + 30
    pending = {0, 1}
    while pending and time.time() < deadline:
        for i in list(pending):
            log_path = tmp_path / "pods" / "ns1" / f"agent-{i}" / "pod.log"
            if (
                log_path.exists()
                and f"pod agent-{i} world" in log_path.read_text()
            ):
                pending.discard(i)
        time.sleep(0.3)
    assert not pending, f"pods {pending} never logged their config"
    # scale down to 1: pod agent-1 must die
    sts = kube.get("StatefulSet", "ns1", "agent")
    sts["spec"]["replicas"] = 1
    kube.apply(sts)
    deadline = time.time() + 20
    while time.time() < deadline:
        kubelet.reconcile_once()
        if ("ns1", "agent-1") not in kubelet.pods:
            break
        time.sleep(0.2)
    else:
        pytest.fail("scale-down did not remove the pod")
    assert ("ns1", "agent-0") in kubelet.pods
    kubelet.stop()


def test_logs_endpoint_surfaces_pod_log_files(kube, tmp_path, run_async):
    """k8s-mode /logs appends each pod's pod.log tail (the files the
    kubelet writes) after the framework lines — and only this app's pods."""
    import aiohttp

    from langstream_tpu.controlplane.server import ControlPlaneServer
    from langstream_tpu.controlplane.stores import InMemoryApplicationStore
    from langstream_tpu.k8s.compute import KubernetesComputeRuntime

    pods_root = tmp_path / "kubelet"
    pod_dir = pods_root / "pods" / "langstream-t1" / "chat-app-step1-0"
    pod_dir.mkdir(parents=True)
    (pod_dir / "pod.log").write_text("agent booted\ndecode step 1 ok\n")
    # a second app whose pod dir sits in the same namespace — including a
    # dash-prefix collision ("chat-app" vs "chat-app-2") that defeats
    # name-prefix matching; pod ownership must come from the
    # langstream-application label instead
    other = pods_root / "pods" / "langstream-t1" / "chat-app-2-step1-0"
    other.mkdir(parents=True)
    (other / "pod.log").write_text("other app line\n")
    kube.apply({"apiVersion": "v1", "kind": "Namespace",
                "metadata": {"name": "langstream-t1"}})
    for app, sts_name in (
        ("chat-app", "chat-app-step1"),
        ("chat-app-2", "chat-app-2-step1"),
    ):
        kube.apply({
            "apiVersion": "apps/v1",
            "kind": "StatefulSet",
            "metadata": {
                "name": sts_name,
                "namespace": "langstream-t1",
                "labels": {"langstream-application": app},
            },
            "spec": {"replicas": 1, "template": {"spec": {"containers": []}}},
        })

    compute = KubernetesComputeRuntime(kube, pods_root=pods_root)
    compute.append_log("t1", "chat-app", "wrote 1 agent CRs")
    store = InMemoryApplicationStore()
    store.put_tenant("t1")

    async def main():
        control = ControlPlaneServer(
            store=store, compute=compute, port=18347
        )
        await control.start()
        try:
            async with aiohttp.ClientSession() as session:
                url = (
                    "http://127.0.0.1:18347"
                    "/api/applications/t1/chat-app/logs"
                )
                async with session.get(url) as r:
                    assert r.status == 200
                    return await r.text()
        finally:
            await control.stop()

    body = run_async(main())
    assert "wrote 1 agent CRs" in body
    assert "---- pod chat-app-step1-0 (pod.log) ----" in body
    assert "decode step 1 ok" in body
    assert "other app line" not in body  # chat-app-2's pod stays isolated


# ---------------------------------------------------------------------------
# full mini-cluster smoke (slow: real subprocesses + engine compile)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_mini_up_once_smoke(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [
            sys.executable, "-m", "langstream_tpu.cli", "mini", "up",
            "--once", "--data-dir", str(tmp_path / "mini"),
            "--api-port", "18290", "--gateway-port", "18291",
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert "smoke chat answered" in proc.stdout
    # the deploy really went through the k8s path: jobs + agent pod dirs
    pods_root = tmp_path / "mini" / "kubelet" / "pods" / "langstream-default"
    names = [p.name for p in pods_root.iterdir()]
    assert any("setup" in n for n in names), names
    assert any("deployer" in n for n in names), names
    assert any(n.startswith("mini-chat-") for n in names), names
