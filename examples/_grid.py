"""Shared example plumbing: arg parsing, wait-for-node, optional ephemeral
in-process grid (the reference examples assume the compose grid is up;
``--spawn`` removes that requirement)."""

from __future__ import annotations

import argparse
import asyncio
import socket
import threading
import time

import requests


def wait_for(url: str, timeout: float = 60.0) -> None:
    """Poll until the server answers (compose services race their deps)."""
    deadline = time.monotonic() + timeout
    while True:
        try:
            requests.get(url + "/", timeout=2)
            return
        except requests.ConnectionError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.5)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _Server:
    def __init__(self, app, port: int) -> None:
        self.port = port
        self.url = f"http://127.0.0.1:{port}"
        self._loop = asyncio.new_event_loop()
        self._ready = threading.Event()
        self._thread = threading.Thread(
            target=self._run, args=(app,), daemon=True
        )
        self._thread.start()
        self._ready.wait(15)

    def _run(self, app) -> None:
        from aiohttp import web

        asyncio.set_event_loop(self._loop)

        async def go():
            runner = web.AppRunner(app)
            await runner.setup()
            await web.TCPSite(runner, "127.0.0.1", self.port).start()
            self._ready.set()

        self._loop.run_until_complete(go())
        self._loop.run_forever()


def spawn_grid(n_nodes: int = 4):
    """Ephemeral in-process grid; returns (network_url, {name: node_url})."""
    from pygrid_tpu.network import create_app as network_app
    from pygrid_tpu.node import create_app as node_app

    network = _Server(network_app("example-network"), _free_port())
    nodes = {}
    for name in ["alice", "bob", "charlie", "dan"][:n_nodes]:
        server = _Server(node_app(name), _free_port())
        requests.post(
            network.url + "/join",
            json={"node-id": name, "node-address": server.url},
            timeout=10,
        ).raise_for_status()
        nodes[name] = server.url
    return network.url, nodes


def example_args(description: str, need_network: bool = False):
    parser = argparse.ArgumentParser(description=description)
    parser.add_argument("--node", default="http://localhost:5000")
    parser.add_argument("--network", default="http://localhost:7000")
    parser.add_argument("--spawn", action="store_true",
                        help="spawn an ephemeral in-process grid")
    parser.add_argument("--wait", type=float, default=60.0,
                        help="seconds to wait for servers")
    return parser
