"""The CLI.

Parity: the reference's picocli CLI (``langstream-cli``): profiles,
``tenants``, ``apps deploy/update/get/delete/list/logs``, ``gateway
produce/consume/chat`` (WebSocket clients), and the single-process dev mode
(``langstream docker run`` → here ``run``, no container needed — the broker,
control plane, gateway, and TPU engine are all in-tree).

Usage: ``python -m langstream_tpu.cli <command>``.
"""

from __future__ import annotations

import asyncio
import json
import sys
from pathlib import Path

import click

DEFAULT_API = "http://127.0.0.1:8090"
DEFAULT_GATEWAY = "http://127.0.0.1:8091"
PROFILE_PATH = Path.home() / ".langstream-tpu" / "config.json"


def _profile() -> dict:
    if PROFILE_PATH.exists():
        return json.loads(PROFILE_PATH.read_text())
    return {}


def _api_url(ctx_value: str | None) -> str:
    return ctx_value or _profile().get("api-url", DEFAULT_API)


def _gateway_url(ctx_value: str | None) -> str:
    return ctx_value or _profile().get("gateway-url", DEFAULT_GATEWAY)


def _ws_connect(session, url: str):
    """ws_connect wrapper that turns handshake failures into CLI errors."""
    import aiohttp

    class _Ctx:
        def __init__(self):
            self._inner = session.ws_connect(url)

        async def __aenter__(self):
            try:
                return await self._inner.__aenter__()
            except aiohttp.WSServerHandshakeError as e:
                raise click.ClickException(
                    f"gateway refused connection ({e.status}): {e.message} [{url}]"
                )

        async def __aexit__(self, *exc):
            return await self._inner.__aexit__(*exc)

    return _Ctx()


async def _request(method: str, url: str, **kwargs):
    """All CLI HTTP goes through the AdminClient facade (retry policies,
    auth header) — parity: the reference CLI delegating to admin-client.
    The bearer token comes from the profile (``token``) or
    ``LS_ADMIN_TOKEN``; ``apps update``'s PATCH is revalidated server-side,
    so it rides the retry-safe lane the facade marks for it."""
    import asyncio as _asyncio
    import os as _os
    from urllib.parse import urlsplit

    import aiohttp

    from langstream_tpu.admin import AdminApiError, AdminClient

    parts = urlsplit(url)
    base = f"{parts.scheme}://{parts.netloc}"
    path = parts.path + (f"?{parts.query}" if parts.query else "")
    token = (
        kwargs.pop("token", None)
        or _profile().get("token")
        or _os.environ.get("LS_ADMIN_TOKEN")
    )
    client = AdminClient(base, token=token)
    try:
        return await client.request(
            method, path,
            retry_safe=True if method.upper() == "PATCH" else None,
            **kwargs,
        )
    except AdminApiError as e:
        raise click.ClickException(str(e))
    except (OSError, aiohttp.ClientError, _asyncio.TimeoutError) as e:
        raise click.ClickException(f"control plane unreachable: {e}")
    finally:
        await client.close()


@click.group()
def cli() -> None:
    """langstream-tpu: TPU-native event-driven LLM application platform."""
    # `run` compiles in this process and `mini up` in pod children that
    # inherit the environment: place the compile cache before either
    from langstream_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()


@cli.command()
@click.option("--api-url", default=None)
@click.option("--gateway-url", default=None)
@click.option("--tenant", default=None)
def configure(api_url: str | None, gateway_url: str | None, tenant: str | None) -> None:
    """Save connection profile to ~/.langstream-tpu/config.json."""
    profile = _profile()
    if api_url:
        profile["api-url"] = api_url
    if gateway_url:
        profile["gateway-url"] = gateway_url
    if tenant:
        profile["tenant"] = tenant
    PROFILE_PATH.parent.mkdir(parents=True, exist_ok=True)
    PROFILE_PATH.write_text(json.dumps(profile, indent=2))
    click.echo(f"profile saved: {PROFILE_PATH}")


# ---------------------------------------------------------------------------
# tenants
# ---------------------------------------------------------------------------


@cli.group()
def tenants() -> None:
    """Manage tenants."""


@tenants.command("put")
@click.argument("name")
@click.option("--api-url", default=None)
def tenants_put(name: str, api_url: str | None) -> None:
    out = asyncio.run(_request("PUT", f"{_api_url(api_url)}/api/tenants/{name}"))
    click.echo(json.dumps(out))


@tenants.command("list")
@click.option("--api-url", default=None)
def tenants_list(api_url: str | None) -> None:
    out = asyncio.run(_request("GET", f"{_api_url(api_url)}/api/tenants"))
    click.echo(json.dumps(out, indent=2))


@tenants.command("delete")
@click.argument("name")
@click.option("--api-url", default=None)
def tenants_delete(name: str, api_url: str | None) -> None:
    out = asyncio.run(_request("DELETE", f"{_api_url(api_url)}/api/tenants/{name}"))
    click.echo(json.dumps(out))


# ---------------------------------------------------------------------------
# apps
# ---------------------------------------------------------------------------


def _collect_files(app_dir: Path) -> dict[str, str]:
    files = {}
    for path in sorted(app_dir.glob("*.yaml")) + sorted(app_dir.glob("*.yml")):
        files[path.name] = path.read_text()
    if not files:
        raise click.ClickException(f"no YAML files in {app_dir}")
    # custom agent code ships with the app (python/ + python/lib/)
    for pattern in ("python/*.py", "python/lib/*.py"):
        for path in sorted(app_dir.glob(pattern)):
            files[path.relative_to(app_dir).as_posix()] = path.read_text()
    return files


@cli.group()
def apps() -> None:
    """Manage applications."""


def _app_payload(app: str, instance: str | None, secrets: str | None) -> dict:
    payload: dict = {"files": _collect_files(Path(app))}
    if instance:
        payload["instance"] = Path(instance).read_text()
    if secrets:
        payload["secrets"] = Path(secrets).read_text()
    return payload


@apps.command("deploy")
@click.argument("name")
@click.option("-app", "--application", "app", required=True, type=click.Path(exists=True))
@click.option("-i", "--instance", default=None, type=click.Path(exists=True))
@click.option("-s", "--secrets", default=None, type=click.Path(exists=True))
@click.option("--tenant", default=None)
@click.option("--api-url", default=None)
def apps_deploy(name, app, instance, secrets, tenant, api_url) -> None:
    tenant = tenant or _profile().get("tenant", "default")
    out = asyncio.run(
        _request(
            "POST",
            f"{_api_url(api_url)}/api/applications/{tenant}/{name}",
            json=_app_payload(app, instance, secrets),
        )
    )
    click.echo(json.dumps(out, indent=2))


@apps.command("update")
@click.argument("name")
@click.option("-app", "--application", "app", required=True, type=click.Path(exists=True))
@click.option("-i", "--instance", default=None, type=click.Path(exists=True))
@click.option("-s", "--secrets", default=None, type=click.Path(exists=True))
@click.option("--tenant", default=None)
@click.option("--api-url", default=None)
def apps_update(name, app, instance, secrets, tenant, api_url) -> None:
    tenant = tenant or _profile().get("tenant", "default")
    out = asyncio.run(
        _request(
            "PATCH",
            f"{_api_url(api_url)}/api/applications/{tenant}/{name}",
            json=_app_payload(app, instance, secrets),
        )
    )
    click.echo(json.dumps(out, indent=2))


@apps.command("get")
@click.argument("name")
@click.option("--tenant", default=None)
@click.option("--api-url", default=None)
def apps_get(name, tenant, api_url) -> None:
    tenant = tenant or _profile().get("tenant", "default")
    out = asyncio.run(
        _request("GET", f"{_api_url(api_url)}/api/applications/{tenant}/{name}")
    )
    click.echo(json.dumps(out, indent=2))


@apps.command("list")
@click.option("--tenant", default=None)
@click.option("--api-url", default=None)
def apps_list(tenant, api_url) -> None:
    tenant = tenant or _profile().get("tenant", "default")
    out = asyncio.run(
        _request("GET", f"{_api_url(api_url)}/api/applications/{tenant}")
    )
    click.echo(json.dumps(out, indent=2))


@apps.command("delete")
@click.argument("name")
@click.option("--tenant", default=None)
@click.option("--api-url", default=None)
def apps_delete(name, tenant, api_url) -> None:
    tenant = tenant or _profile().get("tenant", "default")
    out = asyncio.run(
        _request("DELETE", f"{_api_url(api_url)}/api/applications/{tenant}/{name}")
    )
    click.echo(json.dumps(out))


@apps.command("download")
@click.argument("name")
@click.option("-o", "--output", default=None, type=click.Path(),
              help="output zip path (default <name>.zip)")
@click.option("--tenant", default=None)
@click.option("--api-url", default=None)
def apps_download(name, output, tenant, api_url) -> None:
    """Download the deployed application's code archive as a zip."""
    tenant = tenant or _profile().get("tenant", "default")
    data = asyncio.run(
        _request(
            "GET",
            f"{_api_url(api_url)}/api/applications/{tenant}/{name}/code",
            binary=True,
        )
    )
    target = Path(output or f"{name}.zip")
    target.write_bytes(data)
    click.echo(f"wrote {target} ({len(data)} bytes)")


@apps.command("logs")
@click.argument("name")
@click.option("--tenant", default=None)
@click.option("--api-url", default=None)
def apps_logs(name, tenant, api_url) -> None:
    tenant = tenant or _profile().get("tenant", "default")
    out = asyncio.run(
        _request("GET", f"{_api_url(api_url)}/api/applications/{tenant}/{name}/logs")
    )
    click.echo(out)


@apps.command("ui")
@click.argument("name")
@click.option("--tenant", default=None)
@click.option("--gateway", "gateway_id", default="chat",
              help="chat gateway id in the app's gateways.yaml")
@click.option("--gateway-url", default=None,
              help="websocket gateway base (default: profile / ws://localhost:8091)")
@click.option("--port", default=8092, show_default=True,
              help="local port to serve the UI on (0 = ephemeral)")
@click.option("--open/--no-open", "open_browser", default=True,
              help="open the page in a browser")
@click.option("--once", is_flag=True, hidden=True,
              help="serve a single request then exit (tests)")
def apps_ui(name, tenant, gateway_id, gateway_url, port, open_browser, once) -> None:
    """Serve the bundled chat UI against an app's chat gateway (parity:
    `langstream apps ui` serving langstream-cli's app-ui/index.html)."""
    import http.server
    import threading
    import urllib.parse
    import webbrowser

    tenant = tenant or _profile().get("tenant", "default")
    ws_base = _gateway_url(gateway_url)
    page = (Path(__file__).parent / "app_ui.html").read_bytes()

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 (stdlib naming)
            self.send_response(200)
            self.send_header("Content-Type", "text/html; charset=utf-8")
            self.send_header("Content-Length", str(len(page)))
            self.end_headers()
            self.wfile.write(page)

        def log_message(self, *a):  # quiet
            pass

    server = http.server.ThreadingHTTPServer(("127.0.0.1", port), Handler)
    actual_port = server.server_address[1]
    query = urllib.parse.urlencode(
        {"tenant": tenant, "app": name, "gw": gateway_id, "gateway": ws_base}
    )
    url = f"http://127.0.0.1:{actual_port}/?{query}"
    click.echo(f"chat UI: {url}")
    if open_browser:
        threading.Thread(
            target=webbrowser.open, args=(url,), daemon=True
        ).start()
    try:
        if once:
            server.handle_request()
        else:
            server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()


@apps.command("diagram")
@click.option("-app", "--application", "app", required=True, type=click.Path(exists=True))
@click.option("-i", "--instance", default=None, type=click.Path(exists=True))
@click.option("-s", "--secrets", default=None, type=click.Path(exists=True))
def apps_diagram(app, instance, secrets) -> None:
    """Render the planned pipeline as a Mermaid flowchart (parity:
    MermaidAppDiagramGenerator)."""
    from langstream_tpu.core.deployer import ApplicationDeployer
    from langstream_tpu.core.diagram import mermaid_diagram
    from langstream_tpu.core.parser import build_application_from_directory

    application = build_application_from_directory(app, instance, secrets)
    plan = ApplicationDeployer().create_implementation("app", application)
    click.echo(mermaid_diagram(plan))


# ---------------------------------------------------------------------------
# archetypes + docs
# ---------------------------------------------------------------------------


@cli.group()
def archetypes() -> None:
    """Parameterized application templates."""


@archetypes.command("list")
@click.option("--tenant", default=None)
@click.option("--api-url", default=None)
def archetypes_list(tenant, api_url) -> None:
    tenant = tenant or _profile().get("tenant", "default")
    out = asyncio.run(
        _request("GET", f"{_api_url(api_url)}/api/archetypes/{tenant}")
    )
    click.echo(json.dumps(out, indent=2))


@archetypes.command("get")
@click.argument("archetype_id")
@click.option("--tenant", default=None)
@click.option("--api-url", default=None)
def archetypes_get(archetype_id, tenant, api_url) -> None:
    tenant = tenant or _profile().get("tenant", "default")
    out = asyncio.run(
        _request(
            "GET", f"{_api_url(api_url)}/api/archetypes/{tenant}/{archetype_id}"
        )
    )
    click.echo(json.dumps(out, indent=2))


@archetypes.command("deploy")
@click.argument("archetype_id")
@click.argument("name")
@click.option("-p", "--parameter", "parameters", multiple=True,
              help="name=value (repeatable)")
@click.option("-i", "--instance", default=None, type=click.Path(exists=True))
@click.option("-s", "--secrets", default=None, type=click.Path(exists=True))
@click.option("--tenant", default=None)
@click.option("--api-url", default=None)
def archetypes_deploy(
    archetype_id, name, parameters, instance, secrets, tenant, api_url
) -> None:
    tenant = tenant or _profile().get("tenant", "default")
    payload: dict = {
        "parameters": dict(p.split("=", 1) for p in parameters),
    }
    if instance:
        payload["instance"] = Path(instance).read_text()
    if secrets:
        payload["secrets"] = Path(secrets).read_text()
    out = asyncio.run(
        _request(
            "POST",
            f"{_api_url(api_url)}/api/archetypes/{tenant}/{archetype_id}"
            f"/applications/{name}",
            json=payload,
        )
    )
    click.echo(json.dumps(out, indent=2))


@cli.group("python")
def python_group() -> None:
    """Per-application Python tooling (parity: `langstream python ...`)."""


@python_group.command("install-requirements")
@click.option("-app", "--application", "app", required=True,
              type=click.Path(exists=True))
def python_install_requirements(app) -> None:
    """Provision the app's isolated venv from python/requirements.txt and
    print the interpreter its sidecar agents will run on (parity:
    load-pip-requirements; here deps install into a venv-per-app instead
    of the shared lib dir, the NAR-isolation answer)."""
    from langstream_tpu.runtime.isolation import (
        ensure_app_interpreter,
        requirements_file,
    )

    if requirements_file(app) is None:
        click.echo("no python/requirements.txt: sidecars use the base "
                   "interpreter")
    interpreter = ensure_app_interpreter(app)
    click.echo(interpreter)


@python_group.command(
    "run-tests",
    context_settings={"ignore_unknown_options": True},
)
@click.option("-app", "--application", "app", required=True,
              type=click.Path(exists=True))
@click.argument("pytest_args", nargs=-1, type=click.UNPROCESSED)
def python_run_tests(app, pytest_args) -> None:
    """Run the application's python/ test suite on the app's interpreter
    (the venv when requirements are pinned)."""
    import subprocess

    from langstream_tpu.runtime.isolation import ensure_app_interpreter

    code_dir = Path(app) / "python"
    if not code_dir.is_dir():
        raise click.ClickException(f"{app} has no python/ directory")
    interpreter = ensure_app_interpreter(app)
    result = subprocess.run(
        [interpreter, "-m", "pytest", *(pytest_args or ("-q",))],
        cwd=code_dir,
    )
    raise SystemExit(result.returncode)


@cli.group()
def docs() -> None:
    """Generated documentation."""


@docs.command("agents")
@click.option("--format", "fmt", type=click.Choice(["markdown", "json"]),
              default="markdown")
@click.option("-o", "--output", default=None, type=click.Path())
def docs_agents(fmt, output) -> None:
    """Agent-type reference generated from the registry (parity:
    DocumentationGenerator)."""
    from langstream_tpu.core.docsgen import render_json, render_markdown

    text = render_markdown() if fmt == "markdown" else render_json()
    if output:
        Path(output).write_text(text)
        click.echo(f"wrote {output}")
    else:
        click.echo(text)


# ---------------------------------------------------------------------------
# gateway clients
# ---------------------------------------------------------------------------


def _gw_ws_url(base: str, kind: str, tenant: str, app: str, gateway: str,
               params: tuple[str, ...], credentials: str | None,
               options: dict | None = None) -> str:
    from urllib.parse import quote

    url = base.replace("http://", "ws://").replace("https://", "wss://")
    qs = []
    for p in params:
        k, _, v = p.partition("=")
        qs.append(f"param:{quote(k, safe='')}={quote(v, safe='')}")
    if credentials:
        qs.append(f"credentials={quote(credentials, safe='')}")
    for k, v in (options or {}).items():
        qs.append(f"option:{quote(str(k), safe='')}={quote(str(v), safe='')}")
    query = ("?" + "&".join(qs)) if qs else ""
    return f"{url}/v1/{kind}/{tenant}/{app}/{gateway}{query}"


@cli.group()
def gateway() -> None:
    """Interact with application gateways."""


@gateway.command("produce")
@click.argument("application")
@click.argument("gateway_id")
@click.option("-v", "--value", required=True)
@click.option("-k", "--key", default=None)
@click.option("-p", "--param", multiple=True, help="name=value")
@click.option("--credentials", default=None)
@click.option("--tenant", default=None)
@click.option("--gateway-url", default=None)
def gateway_produce(application, gateway_id, value, key, param, credentials,
                    tenant, gateway_url) -> None:
    tenant = tenant or _profile().get("tenant", "default")

    async def run():
        import aiohttp

        url = _gw_ws_url(
            _gateway_url(gateway_url), "produce", tenant, application, gateway_id,
            param, credentials,
        )
        async with aiohttp.ClientSession() as session:
            async with _ws_connect(session, url) as ws:
                await ws.send_json({"value": value, "key": key})
                reply = await ws.receive_json()
                click.echo(json.dumps(reply))

    asyncio.run(run())


@gateway.command("consume")
@click.argument("application")
@click.argument("gateway_id")
@click.option("-p", "--param", multiple=True)
@click.option("--position", default="latest")
@click.option("-n", "--num-messages", default=0, help="0 = forever")
@click.option("--credentials", default=None)
@click.option("--tenant", default=None)
@click.option("--gateway-url", default=None)
def gateway_consume(application, gateway_id, param, position, num_messages,
                    credentials, tenant, gateway_url) -> None:
    tenant = tenant or _profile().get("tenant", "default")

    async def run():
        import aiohttp

        url = _gw_ws_url(
            _gateway_url(gateway_url), "consume", tenant, application, gateway_id,
            param, credentials, {"position": position},
        )
        count = 0
        async with aiohttp.ClientSession() as session:
            async with _ws_connect(session, url) as ws:
                async for msg in ws:
                    if msg.type == aiohttp.WSMsgType.TEXT:
                        click.echo(msg.data)
                        count += 1
                        if num_messages and count >= num_messages:
                            return

    asyncio.run(run())


@gateway.command("chat")
@click.argument("application")
@click.argument("gateway_id")
@click.option("-p", "--param", multiple=True)
@click.option("--credentials", default=None)
@click.option("--tenant", default=None)
@click.option("--gateway-url", default=None)
def gateway_chat(application, gateway_id, param, credentials, tenant,
                 gateway_url) -> None:
    """Interactive chat: reads prompts from stdin, prints streamed answers."""
    tenant = tenant or _profile().get("tenant", "default")

    async def run():
        import aiohttp

        url = _gw_ws_url(
            _gateway_url(gateway_url), "chat", tenant, application, gateway_id,
            param, credentials,
        )
        async with aiohttp.ClientSession() as session:
            async with _ws_connect(session, url) as ws:
                loop = asyncio.get_event_loop()
                # stdin is read on a dedicated daemon thread (NOT the default
                # executor): when the server closes the socket mid-readline,
                # asyncio.run's shutdown would otherwise join the blocked
                # executor thread and hang the CLI until the next keypress
                lines: asyncio.Queue[str | None] = asyncio.Queue()

                def _read_stdin():
                    while True:
                        line = sys.stdin.readline()
                        loop.call_soon_threadsafe(lines.put_nowait, line or None)
                        if not line:
                            return

                import threading

                threading.Thread(target=_read_stdin, daemon=True).start()

                async def pump_stdin():
                    while True:
                        line = await lines.get()
                        if line is None:
                            await ws.close()
                            return
                        await ws.send_json({"value": line.strip()})

                stdin_task = asyncio.ensure_future(pump_stdin())
                try:
                    async for msg in ws:
                        if msg.type == aiohttp.WSMsgType.TEXT:
                            data = json.loads(msg.data)
                            if "record" in data:
                                value = data["record"].get("value")
                                if isinstance(value, str):
                                    click.echo(value, nl=False)
                                    headers = data["record"].get("headers", {})
                                    if headers.get("stream-last-message") == "true":
                                        click.echo("")
                                else:
                                    click.echo(json.dumps(value))
                finally:
                    stdin_task.cancel()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# dev mode: everything in one process
# ---------------------------------------------------------------------------


@cli.command("run")
@click.argument("name")
@click.option("-app", "--application", "app", required=True, type=click.Path(exists=True))
@click.option("-i", "--instance", default=None, type=click.Path(exists=True))
@click.option("-s", "--secrets", default=None, type=click.Path(exists=True))
@click.option("--api-port", default=8090)
@click.option("--gateway-port", default=8091)
@click.option("--archetypes", "archetypes_path", default=None,
              type=click.Path(exists=True), help="archetype templates root")
def run_local(name, app, instance, secrets, api_port, gateway_port,
              archetypes_path) -> None:
    """Single-process dev mode (parity: ``langstream docker run``): boots the
    control plane + gateway in-process, deploys the app, serves until ^C."""
    from langstream_tpu.controlplane.server import (
        ControlPlaneServer,
        LocalComputeRuntime,
    )
    from langstream_tpu.controlplane.stores import (
        InMemoryApplicationStore,
        StoredApplication,
    )
    from langstream_tpu.gateway.server import GatewayRegistry, GatewayServer

    async def run():
        registry = GatewayRegistry()
        compute = LocalComputeRuntime(gateway_registry=registry)
        store = InMemoryApplicationStore()
        store.put_tenant("default")
        control = ControlPlaneServer(
            store=store, compute=compute, port=api_port,
            archetypes_path=archetypes_path,
        )
        gw = GatewayServer(registry=registry, port=gateway_port)
        await control.start()
        await gw.start()
        stored = StoredApplication(
            tenant="default",
            name=name,
            files=_collect_files(Path(app)),
            instance=Path(instance).read_text() if instance else None,
            secrets=Path(secrets).read_text() if secrets else None,
        )
        store.put_application(stored)
        await compute.deploy(stored)
        stored.status = "DEPLOYED"
        click.echo(f"application {name!r} deployed")
        click.echo(f"control plane: http://127.0.0.1:{api_port}")
        click.echo(f"gateway:       ws://127.0.0.1:{gateway_port}")
        try:
            while True:
                await asyncio.sleep(3600)
        except (KeyboardInterrupt, asyncio.CancelledError):
            pass
        finally:
            await gw.stop()
            await control.stop()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        click.echo("\nstopped")


from langstream_tpu.cli.mini import mini  # noqa: E402  (click group)

cli.add_command(mini)


if __name__ == "__main__":
    cli()
