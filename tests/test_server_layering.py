"""The store server knows nothing of the engine above it.

The server is its own process with no JAX in it; what an engine counts is
read from ``ContinuousBatchingHarness.metrics()`` in the engine's process.
These tests hold that without a clock: the server's source names no engine,
and a manage plane that shares an interpreter with an imported engine serves
none of its state.
"""

import ast
import asyncio
import pathlib
import sys
import urllib.error
import urllib.request

import infinistore_tpu as its
from infinistore_tpu import lib as its_lib
from infinistore_tpu.server import ManageServer

SERVER_PY = pathlib.Path(its.__file__).parent / "server.py"


def test_server_source_names_no_engine():
    """No import of the engine, no ``sys.modules`` lookup of it, no name or
    attribute ``engine`` anywhere in ``server.py``'s syntax tree."""
    names, strings = [], []
    for node in ast.walk(ast.parse(SERVER_PY.read_text())):
        if isinstance(node, ast.Import):
            names += [(a.name, node.lineno) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names += [(node.module or "", node.lineno)]
            names += [(a.name, node.lineno) for a in node.names]
        elif isinstance(node, ast.Name):
            names.append((node.id, node.lineno))
        elif isinstance(node, ast.Attribute):
            names.append((node.attr, node.lineno))
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            strings.append((node.value, node.lineno))
    assert [(n, at) for n, at in names if n.split(".")[-1] == "engine"] == []
    assert [
        (v, at) for v, at in strings if "infinistore_tpu.engine" in v or v == "/wave"
    ] == []


def test_a_manage_plane_beside_an_engine_serves_none_of_its_state():
    """With ``infinistore_tpu.engine`` imported in the manage plane's own
    interpreter, ``/metrics`` carries no ``infinistore_engine_wave_`` line
    and ``GET /wave`` is a 404 like any unknown route."""
    import infinistore_tpu.engine  # noqa: F401 - the point: it is loaded

    assert "infinistore_tpu.engine" in sys.modules
    srv = its.start_local_server(prealloc_bytes=16 << 20, block_bytes=16 << 10)
    cfg = its.ServerConfig(
        host="127.0.0.1", service_port=0, manage_port=1, prealloc_size=1,
        minimal_allocate_size=16, pin_memory=False, log_level="error",
    )

    def get(port, path):
        try:
            with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    async def run():
        manage = ManageServer(cfg)
        manage._server = await asyncio.start_server(manage._handle, host="127.0.0.1", port=0)
        port = manage._server.sockets[0].getsockname()[1]
        try:
            return (
                await asyncio.to_thread(get, port, "/metrics"),
                await asyncio.to_thread(get, port, "/wave"),
                await asyncio.to_thread(get, port, "/nope"),
            )
        finally:
            manage._server.close()
            await manage._server.wait_closed()

    old = its_lib._server_handle
    its_lib._server_handle = srv.handle
    try:
        (status, metrics), wave, unknown = asyncio.run(run())
    finally:
        its_lib._server_handle = old
        srv.stop()
    assert status == 200 and "infinistore_kvmap_entries" in metrics
    assert "infinistore_engine_wave_" not in metrics
    assert wave[0] == unknown[0] == 404
    assert wave[1] == unknown[1]
