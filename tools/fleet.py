"""Fleet-of-subprocesses harness, shared by the bench legs and tests.

Two populations, one spawn/readiness/kill/restart protocol:

- **server members** (``spawn_fleet_servers``): real
  ``python -m infinistore_tpu.server`` store processes with their own
  manage planes (the PR 8 two-subprocess pattern — bench telemetry leg +
  tests/test_telemetry.py drive the same argv).
- **client members** (``spawn_fleet_client``): real
  ``python -m infinistore_tpu.fleet_client`` cluster-client processes —
  each owning a ``ClusterKVConnector`` with a durable journal, a manage
  plane, and a gossip agent. The crash-recovery bench leg and
  tests (docs/membership.md) kill these with ``kill -9`` mid-reshard and
  restart them **with the same argv** (``restart_member``), which is the
  whole point: a member dict remembers its ``argv``, so a restart is a
  faithful crash-recovery, not a reconfiguration.

Members that import JAX (fleet clients, the disagg prefill engine) are
spawned with ``hostmesh.cpu_child_env()``: the spawning process may hold the
machine's accelerator, and a chip belongs to one process at a time. Server
members never import JAX.

Every member dict carries ``{"argv", "proc", ...ports}``; ``kill_member``
is SIGKILL (no shutdown handlers — the crash the durable journal exists
to survive), ``restart_member`` re-Popens the recorded argv and waits for
the member's own readiness probe.
"""

import json
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

from infinistore_tpu.hostmesh import cpu_child_env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# Manage-plane HTTP helpers (bench + tests poll membership/health/events).
# ---------------------------------------------------------------------------


def manage_json(port: int, path: str, timeout_s: float = 2.0) -> dict:
    """GET a manage-plane JSON endpoint on 127.0.0.1:``port``."""
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout_s
    ) as resp:
        return json.loads(resp.read(8 << 20))


def manage_post_json(port: int, path: str, payload: dict,
                     timeout_s: float = 10.0) -> dict:
    """POST JSON to a manage-plane endpoint; returns the parsed body
    (structured error bodies included — callers read ``reason``/``epoch``
    instead of matching prose)."""
    body = json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body,
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return json.loads(resp.read(8 << 20))
    except urllib.error.HTTPError as e:
        return json.loads(e.read() or b"{}")


def wait_manage(port: int, path: str = "/health", timeout_s: float = 30.0,
                predicate=None, proc=None) -> dict:
    """Poll a manage endpoint until it answers (and ``predicate(doc)``
    holds, when given). Fails fast when ``proc`` exits first — a crashed
    member must raise, not eat the whole timeout."""
    deadline = time.time() + timeout_s
    last = None
    while time.time() < deadline:
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(
                f"member exited (rc={proc.returncode}) while waiting for "
                f"{path}"
            )
        try:
            doc = manage_json(port, path, timeout_s=1.0)
            if predicate is None or predicate(doc):
                return doc
            last = doc
        except (OSError, ValueError):
            pass
        time.sleep(0.05)
    raise RuntimeError(
        f"manage endpoint {path} on :{port} not ready in {timeout_s}s "
        f"(last: {str(last)[:200]})"
    )


# ---------------------------------------------------------------------------
# Server members.
# ---------------------------------------------------------------------------


def _server_argv(service_port: int, manage_port: int):
    return [
        sys.executable, "-m", "infinistore_tpu.server",
        "--host", "127.0.0.1",
        "--service-port", str(service_port),
        "--manage-port", str(manage_port),
        "--prealloc-size", "1", "--minimal-allocate-size", "16",
        "--no-pin-memory", "--log-level", "error",
    ]


def _wait_server_ready(member: dict, timeout_s: float):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        try:
            with socket.create_connection(
                ("127.0.0.1", member["service_port"]), timeout=0.3
            ):
                pass
            urllib.request.urlopen(
                f"http://127.0.0.1:{member['manage_port']}/health",
                timeout=0.5,
            )
            return
        except OSError:
            time.sleep(0.1)
    raise RuntimeError("server member did not come up")


def spawn_fleet_servers(n: int = 2, timeout_s: float = 20.0):
    """``n`` REAL server subprocesses (own manage planes), ready to serve:
    the service socket accepts and ``GET /health`` answers. Returns
    ``[{"service_port", "manage_port", "proc", "argv"}]``; on a readiness
    timeout every spawned process is killed and RuntimeError raised."""
    members = []
    for _ in range(n):
        service_port, manage_port = free_port(), free_port()
        argv = _server_argv(service_port, manage_port)
        members.append({
            "service_port": service_port, "manage_port": manage_port,
            "proc": subprocess.Popen(argv), "argv": argv,
        })
    try:
        for m in members:
            _wait_server_ready(m, timeout_s)
    except RuntimeError:
        for m in members:
            m["proc"].kill()
        raise
    return members


# ---------------------------------------------------------------------------
# Disaggregated prefill engine (infinistore_tpu.disagg subprocess).
# ---------------------------------------------------------------------------


def spawn_disagg_prefill(port: int, **kw):
    """One prefill-ENGINE subprocess (``python -m infinistore_tpu.disagg``,
    one-shot mode), stdout piped: it prints ``shipped layer N`` as each
    layer's KV becomes durable in the store at ``port`` and ``prefill done
    wrote=...`` at the end. The chaos test reads the per-layer markers to
    know how far the handoff got, then ``kill_member``s it mid-stream;
    ``kw`` passes through to ``disagg.prefill_argv`` (``stall_after_layer``
    / ``stall_s`` hold the window open). Returns the usual member dict."""
    from infinistore_tpu import disagg

    argv = disagg.prefill_argv(port, **kw)
    proc = subprocess.Popen(
        argv, stdout=subprocess.PIPE, text=True, env=cpu_child_env()
    )
    return {"service_port": port, "proc": proc, "argv": argv}


def read_until_marker(member: dict, marker: str, timeout_s: float = 120.0):
    """Read the member's piped stdout line by line until ``marker`` is a
    substring; returns the matching line. The caller owns the deadline
    semantics (a dead process raises RuntimeError — its stream EOFs)."""
    return read_until_markers(member, [marker], timeout_s=timeout_s)[marker]


def read_until_markers(
    member: dict, markers, timeout_s: float = 120.0
) -> dict:
    """Read piped stdout until EVERY marker in ``markers`` has appeared,
    in ANY order; returns ``{marker: matching line}``. The order-free
    contract matters for durability gating: ``stream_prefill`` ships
    layers concurrently (``max_inflight_ships``), so ``shipped layer 1``
    can legally print before ``shipped layer 0`` under load — a caller
    that waits for the LAST marker alone can act while an earlier
    layer's puts are still in flight."""
    want = {m: None for m in markers}
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        line = member["proc"].stdout.readline()
        if not line:
            raise RuntimeError(
                f"stdout EOF before markers {list(want)!r} "
                f"(exit={member['proc'].poll()})"
            )
        for m in want:
            if want[m] is None and m in line:
                want[m] = line.strip()
        if all(v is not None for v in want.values()):
            return want
    missing = [m for m, v in want.items() if v is None]
    raise RuntimeError(f"timeout waiting for markers {missing!r}")


# ---------------------------------------------------------------------------
# Client members (infinistore_tpu.fleet_client subprocesses).
# ---------------------------------------------------------------------------


def client_argv(
    manage_port: int,
    stores=(),
    journal: str = "",
    peers=(),
    seed: int = 23,
    roots: int = 0,
    replicas: int = 2,
    gossip_interval_s: float = 0.25,
    crash_after_moved: int = 0,
    bootstrap: bool = False,
    verify: bool = False,
    reshard_batch_bytes: int = 0,
):
    """The fleet-client argv (one place — restart_member replays it
    verbatim, which is what makes a restart a crash-recovery)."""
    argv = [
        sys.executable, "-m", "infinistore_tpu.fleet_client",
        "--manage-port", str(manage_port),
        "--seed", str(seed),
        "--roots", str(roots),
        "--replicas", str(replicas),
        "--gossip-interval", str(gossip_interval_s),
    ]
    if stores:
        argv += ["--stores", ",".join(stores)]
    if journal:
        argv += ["--journal", journal]
    if peers:
        argv += ["--peers", ",".join(peers)]
    if crash_after_moved:
        argv += ["--crash-after-moved", str(crash_after_moved)]
    if reshard_batch_bytes:
        argv += ["--reshard-batch-bytes", str(reshard_batch_bytes)]
    if bootstrap:
        argv += ["--bootstrap"]
    if verify:
        argv += ["--verify"]
    return argv


def spawn_fleet_client(manage_port: int = 0, wait_ready: bool = True,
                       timeout_s: float = 60.0, capture: bool = False,
                       **kw):
    """One cluster-client subprocess. ``capture=True`` pipes stdout (the
    ``--verify`` report is a single JSON line). Returns
    ``{"manage_port", "proc", "argv"}``; with ``wait_ready`` the member's
    ``GET /membership`` must answer before this returns."""
    manage_port = manage_port or free_port()
    argv = client_argv(manage_port, **kw)
    proc = subprocess.Popen(
        argv, stdout=subprocess.PIPE if capture else None,
        env=cpu_child_env(),
    )
    member = {"manage_port": manage_port, "proc": proc, "argv": argv}
    if wait_ready:
        try:
            wait_manage(manage_port, "/membership", timeout_s, proc=proc)
        except RuntimeError:
            proc.kill()
            raise
    return member


# ---------------------------------------------------------------------------
# Kill -9 / restart-with-same-argv (the crash-recovery primitives).
# ---------------------------------------------------------------------------


def kill_member(member: dict, timeout_s: float = 10.0) -> int:
    """``kill -9`` a member (server or client): SIGKILL, reaped. No
    shutdown handlers run — the in-memory catalog/view die with the
    process, which is the failure the durable journal exists to survive.
    Returns the (negative-signal) exit code."""
    proc = member["proc"]
    proc.kill()
    proc.wait(timeout=timeout_s)
    return proc.returncode


def wait_member_exit(member: dict, timeout_s: float = 60.0) -> int:
    """Block until a member exits ON ITS OWN (e.g. a scripted
    ``faults.crash_process`` mid-reshard); returns the exit code
    (``-9`` for a SIGKILL self-crash)."""
    return member["proc"].wait(timeout=timeout_s)


def restart_member(member: dict, timeout_s: float = 60.0,
                   ready: str = "auto"):
    """Restart a dead member **with the same argv** it was first spawned
    with — crash recovery, not reconfiguration: a fleet client re-reads
    its durable journal and resumes; a server re-binds its ports. The
    member dict is updated in place (fresh ``proc``) and returned.
    ``ready``: ``"auto"`` picks the member's own readiness probe
    (``/membership`` for clients, service socket + ``/health`` for
    servers), ``None`` skips waiting."""
    if member["proc"].poll() is None:
        raise RuntimeError("member still running — kill_member first")
    member["proc"] = subprocess.Popen(member["argv"], env=cpu_child_env())
    if ready == "auto":
        if "service_port" in member:
            _wait_server_ready(member, timeout_s)
        else:
            wait_manage(member["manage_port"], "/membership", timeout_s,
                        proc=member["proc"])
    return member


def stop_members(members, grace_s: float = 5.0):
    """Best-effort teardown for any member list (SIGINT, then SIGKILL)."""
    for m in members:
        if m["proc"].poll() is None:
            try:
                m["proc"].send_signal(2)
            except OSError:
                pass
    for m in members:
        try:
            m["proc"].wait(timeout=grace_s)
        except Exception:
            m["proc"].kill()
