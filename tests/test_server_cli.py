"""Server CLI subprocess + HTTP management plane + benchmark CLI (reference
launches the server as a subprocess the same way,
reference infinistore/test_infinistore.py:29-54, and exercises
/purge + /kvmap_len; /selftest is new — advertised in the reference README but
never implemented there)."""

import json
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import infinistore_tpu as its


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def cli_server():
    service_port, manage_port = _free_port(), _free_port()
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "infinistore_tpu.server",
            "--host", "127.0.0.1",
            "--service-port", str(service_port),
            "--manage-port", str(manage_port),
            # dataclass units: GB / KB; keep the test pool tiny
            "--prealloc-size", "1",
            "--minimal-allocate-size", "16",
            "--no-pin-memory",
            "--evict-enabled",
            "--evict-interval", "0.2",
            "--log-level", "error",
        ],
    )
    # Wait for both planes to come up.
    deadline = time.time() + 15
    while time.time() < deadline:
        try:
            with socket.create_connection(("127.0.0.1", service_port), timeout=0.3):
                pass
            urllib.request.urlopen(
                f"http://127.0.0.1:{manage_port}/health", timeout=0.5
            )
            break
        except OSError:
            time.sleep(0.1)
    else:
        proc.terminate()
        pytest.fail("CLI server did not come up")
    yield {"service_port": service_port, "manage_port": manage_port, "proc": proc}
    proc.send_signal(2)  # SIGINT, as the reference fixture does
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


@pytest.fixture()
def cli_conn(cli_server):
    conn = its.InfinityConnection(
        its.ClientConfig(
            host_addr="127.0.0.1",
            service_port=cli_server["service_port"],
            log_level="error",
        )
    )
    conn.connect()
    yield conn
    conn.close()


def _manage(cli_server, path, method="GET"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{cli_server['manage_port']}{path}", method=method
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, json.loads(resp.read())


def test_roundtrip_via_cli_server(cli_conn):
    data = np.random.randint(0, 256, size=64 << 10, dtype=np.uint8)
    cli_conn.tcp_write_cache("cli-key", data.ctypes.data, data.nbytes)
    assert np.array_equal(cli_conn.tcp_read_cache("cli-key"), data)


def test_manage_kvmap_len_and_purge(cli_server, cli_conn):
    data = np.zeros(1024, dtype=np.uint8)
    for i in range(3):
        cli_conn.tcp_write_cache(f"mg-{i}", data.ctypes.data, data.nbytes)
    status, body = _manage(cli_server, "/kvmap_len")
    assert status == 200 and body["len"] >= 3
    status, body = _manage(cli_server, "/purge", method="POST")
    assert status == 200 and body["status"] == "ok"
    status, body = _manage(cli_server, "/kvmap_len")
    assert body["len"] == 0


def test_manage_selftest(cli_server):
    status, body = _manage(cli_server, "/selftest")
    assert status == 200
    assert body["status"] == "ok"


def test_manage_stats(cli_server, cli_conn):
    data = np.zeros(1024, dtype=np.uint8)
    cli_conn.tcp_write_cache("stats-probe", data.ctypes.data, data.nbytes)
    status, body = _manage(cli_server, "/stats")
    assert status == 200
    assert "ops" in body and body["total_bytes"] > 0


def test_manage_prometheus_metrics(cli_server, cli_conn):
    data = np.zeros(1024, dtype=np.uint8)
    cli_conn.tcp_write_cache("metrics-probe", data.ctypes.data, data.nbytes)
    req = urllib.request.Request(
        f"http://127.0.0.1:{cli_server['manage_port']}/metrics"
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
    assert "infinistore_kvmap_entries" in text
    assert "infinistore_pool_usage_ratio" in text
    assert 'infinistore_op_count{op="P",result="ok"}' in text


def test_manage_unknown_and_wrong_method(cli_server):
    with pytest.raises(urllib.error.HTTPError) as e:
        _manage(cli_server, "/nope")
    assert e.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as e:
        _manage(cli_server, "/purge", method="GET")
    assert e.value.code == 405


def test_manage_serves_no_wave_route(cli_server):
    """The store server serves no engine's state: ``GET /wave`` is a 404
    like any unknown route, and no ``/metrics`` line names an engine's
    waves."""
    with pytest.raises(urllib.error.HTTPError) as e:
        _manage(cli_server, "/wave")
    assert e.value.code == 404
    with urllib.request.urlopen(
        f"http://127.0.0.1:{cli_server['manage_port']}/metrics", timeout=10
    ) as resp:
        assert "infinistore_engine_wave_" not in resp.read().decode()


def test_benchmark_cli_rdma(cli_server):
    out = subprocess.run(
        [
            sys.executable, "-m", "infinistore_tpu.benchmark",
            "--service-port", str(cli_server["service_port"]),
            "--size", "16", "--block-size", "64", "--steps", "4", "--json",
        ],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["verified"] is True
    assert result["write_mb_s"] > 0 and result["read_mb_s"] > 0


def test_benchmark_cli_tcp(cli_server):
    out = subprocess.run(
        [
            sys.executable, "-m", "infinistore_tpu.benchmark",
            "--service-port", str(cli_server["service_port"]),
            "--size", "4", "--block-size", "64", "--type", "tcp", "--json",
        ],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["verified"] is True
