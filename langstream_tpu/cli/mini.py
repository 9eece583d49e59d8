"""``cli mini`` — the one-command local cluster (mini-langstream parity).

The reference's ``mini-langstream`` stands up minikube + helm + its whole
control plane and deploys apps into real pods. This image has no container
runtime, so ``mini up`` assembles the same production topology from the
in-tree components, with PROCESSES as pods:

  embedded kube API server (k8s/apiserver.py — real HTTP, real 409s/watches)
    ← control plane in k8s mode (Application CRs + Agent CRs + Secrets)
    ← operator (CRs → setup/deployer Jobs → StatefulSets)
    ← process-kubelet (k8s/kubelet.py — Jobs + STS pods as subprocesses
       running the REAL pod entrypoint `python -m langstream_tpu.runtime.pod`)
  native tsbroker (C++ epoll broker) as the streaming cluster
  api-gateway with registry sync off the control plane

Nothing is mocked in the data path: the deployed app's agents run in their
own OS processes, consume/produce over the broker's TCP protocol, and the
chat gateway serves real websockets.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import sys
from pathlib import Path

import click

log = logging.getLogger("langstream_tpu.mini")

REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_APP = REPO_ROOT / "examples" / "applications" / "mini-chat"


def _instance_yaml(broker_port: int) -> str:
    return (
        "instance:\n"
        "  streamingCluster:\n"
        '    type: "tpustream"\n'
        "    configuration:\n"
        f'      bootstrap: "127.0.0.1:{broker_port}"\n'
    )


async def _mini_up(
    app_dir: Path,
    name: str,
    tenant: str,
    api_port: int,
    gateway_port: int,
    data_dir: Path,
    use_tpu: bool,
    once: bool,
) -> None:
    from langstream_tpu.controlplane.server import ControlPlaneServer
    from langstream_tpu.controlplane.stores import StoredApplication
    from langstream_tpu.gateway.__main__ import _sync_registry
    from langstream_tpu.gateway.server import GatewayRegistry, GatewayServer
    from langstream_tpu.k8s.apiserver import FakeKubeApiServer
    from langstream_tpu.k8s.client import HttpKubeApi
    from langstream_tpu.k8s.compute import KubernetesComputeRuntime
    from langstream_tpu.k8s.crds import crd_manifests
    from langstream_tpu.k8s.kubelet import ProcessKubelet
    from langstream_tpu.k8s.operator import Operator
    from langstream_tpu.k8s.stores import (
        GLOBAL_NAMESPACE,
        KubernetesApplicationStore,
    )
    from langstream_tpu.native import BrokerProcess

    data_dir.mkdir(parents=True, exist_ok=True)

    # 1. embedded API server + cluster bootstrap (the helm install's job)
    kube = FakeKubeApiServer().start()
    api = HttpKubeApi(kube.url)
    for manifest in crd_manifests():
        api.apply(manifest)
    for ns in ("langstream-tpu", GLOBAL_NAMESPACE):
        api.apply({
            "apiVersion": "v1", "kind": "Namespace",
            "metadata": {"name": ns},
        })
    click.echo(f"✔ kube API server      {kube.url}")

    # 2. the native broker (streaming cluster)
    broker = BrokerProcess().start()
    click.echo(f"✔ tsbroker             127.0.0.1:{broker.port}")

    # 3. control plane in k8s mode + operator + process-kubelet
    code_storage = {
        "type": "local",
        "configuration": {"path": str(data_dir / "code-storage")},
    }
    store = KubernetesApplicationStore(api, code_storage_config=code_storage)
    compute = KubernetesComputeRuntime(
        api, code_storage_config=code_storage,
        pods_root=data_dir / "kubelet",
    )
    control = ControlPlaneServer(
        store=store, compute=compute, port=api_port
    )
    await control.start()
    click.echo(f"✔ control plane        http://127.0.0.1:{api_port}")

    operator = Operator(api, interval=1.0, watch=True)
    operator_task = asyncio.ensure_future(operator.run())

    pod_env = {
        "LS_KUBE_API_URL": kube.url,
        "PYTHONPATH": str(REPO_ROOT)
        + os.pathsep
        + os.environ.get("PYTHONPATH", ""),
    }
    # a chip belongs to one process: the kubelet pins every pod to the CPU
    # except the one whose agent requests google.com/tpu (`--tpu`: one chip)
    kubelet = ProcessKubelet(
        HttpKubeApi(kube.url), root=data_dir / "kubelet", env_extra=pod_env,
        tpu_chips=1 if use_tpu else 0,
    ).start()
    click.echo(f"✔ operator + kubelet   pods under {data_dir / 'kubelet'}")

    # 4. api gateway with registry sync off the control plane
    registry = GatewayRegistry()
    gw = GatewayServer(registry=registry, port=gateway_port)
    await gw.start()
    sync_task = asyncio.ensure_future(
        _sync_registry(registry, f"http://127.0.0.1:{api_port}")
    )
    click.echo(f"✔ api gateway          ws://127.0.0.1:{gateway_port}")

    # 5. deploy the app through the control plane's own deploy path
    store.put_tenant(tenant)
    files = {
        p.name: p.read_text()
        for p in sorted(app_dir.iterdir())
        if p.is_file() and p.suffix in (".yaml", ".yml")
    }
    python_dir = app_dir / "python"
    if python_dir.is_dir():
        files.update({
            f"python/{p.name}": p.read_text()
            for p in sorted(python_dir.iterdir()) if p.suffix == ".py"
        })
    stored = StoredApplication(
        tenant=tenant, name=name, files=files,
        instance=_instance_yaml(broker.port),
    )
    stored.status = "DEPLOYING"
    store.put_application(stored)
    await compute.deploy(stored)  # stamps stored.code_archive_id
    stored.status = "DEPLOYED"
    store.put_application(stored)
    click.echo(f"✔ application {name!r} deployed (tenant {tenant!r})")

    # 6. wait for the agent pods to come up (Agent CR statuses → DEPLOYED)
    deadline = asyncio.get_event_loop().time() + 120
    while True:
        agents = compute.agent_info(tenant, name)
        statuses = [a["status"].get("status") for a in agents]
        if agents and all(s == "DEPLOYED" for s in statuses):
            break
        if asyncio.get_event_loop().time() > deadline:
            raise RuntimeError(
                f"agents not ready after 120s: {statuses} "
                f"(pod logs under {data_dir / 'kubelet' / 'pods'})"
            )
        await asyncio.sleep(1.0)
    click.echo(f"✔ {len(agents)} agent pod(s) running")
    click.echo("")
    click.echo("chat (new terminal):")
    click.echo(
        f"  python -m langstream_tpu.cli gateway chat {tenant} {name} "
        f"-g user-input --consume-from bot-output "
        f"--gateway-url ws://127.0.0.1:{gateway_port}"
    )
    click.echo("or serve the chat UI:")
    click.echo(
        f"  python -m langstream_tpu.cli apps ui {name} "
        f"--gateway-url ws://127.0.0.1:{gateway_port}"
    )

    try:
        if once:
            # smoke mode: drive one message through the full path and exit
            await _smoke_chat(gateway_port, tenant, name)
        else:
            while True:
                await asyncio.sleep(3600)
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        click.echo("tearing down ...")
        sync_task.cancel()
        kubelet.stop()
        operator.stop()
        operator_task.cancel()
        await gw.stop()
        await control.stop()
        broker.stop()
        kube.stop()


async def _smoke_chat(gateway_port: int, tenant: str, name: str) -> None:
    """--once: one produce → one streamed answer over the real websocket."""
    import aiohttp

    session_id = "mini-smoke"
    base = f"ws://127.0.0.1:{gateway_port}"
    async with aiohttp.ClientSession() as session:
        async with session.ws_connect(
            f"{base}/v1/consume/{tenant}/{name}/bot-output"
            f"?param:sessionId={session_id}"
        ) as consumer:
            async with session.ws_connect(
                f"{base}/v1/produce/{tenant}/{name}/user-input"
                f"?param:sessionId={session_id}"
            ) as producer:
                await producer.send_json({"value": "hello mini cluster"})
                ack = await producer.receive_json(timeout=30)
                if ack.get("status", "OK") != "OK":
                    raise RuntimeError(f"produce failed: {ack}")
            chunks = []
            while True:
                msg = await consumer.receive_json(timeout=60)
                record = msg.get("record") or {}
                chunks.append(str(record.get("value") or ""))
                headers = record.get("headers") or {}
                if str(headers.get("stream-last-message")).lower() == "true":
                    break
    click.echo(f"✔ smoke chat answered ({len(chunks)} stream chunks)")


@click.group()
def mini() -> None:
    """One-command local cluster (parity: mini-langstream)."""


@mini.command("up")
@click.option("-app", "--application", "app", default=str(DEFAULT_APP),
              type=click.Path(exists=True),
              help="application directory (default: the mini-chat demo)")
@click.option("--name", default="mini-chat")
@click.option("--tenant", default="default")
@click.option("--api-port", default=8090)
@click.option("--gateway-port", default=8091)
@click.option("--data-dir", default=None,
              help="cluster state root (default ~/.langstream-tpu/mini)")
@click.option("--tpu", "use_tpu", is_flag=True, default=False,
              help="offer the host's chip to the ONE agent pod that "
                   "requests google.com/tpu (an agent with a device "
                   "mesh); a second requester is refused — a chip belongs "
                   "to one process. Default: every pod pins "
                   "JAX_PLATFORMS=cpu")
@click.option("--once", is_flag=True, default=False,
              help="smoke mode: drive one chat message through the "
                   "cluster, then tear down (CI-able)")
def mini_up(app, name, tenant, api_port, gateway_port, data_dir, use_tpu,
            once) -> None:
    """Boot the full local cluster and deploy an application."""
    data = Path(data_dir) if data_dir else Path.home() / ".langstream-tpu" / "mini"
    try:
        asyncio.run(_mini_up(
            Path(app), name, tenant, api_port, gateway_port, data,
            use_tpu, once,
        ))
    except KeyboardInterrupt:
        click.echo("\nstopped")
    except RuntimeError as e:
        click.echo(f"mini cluster failed: {e}", err=True)
        sys.exit(1)
