"""Shared example plumbing: connect to a running server, or spin up an
in-process one so every example is self-contained (the reference examples
assume `infinistore` is already running on localhost;
reference example/client.py)."""

import argparse
import os
import sys

# Allow running straight from a repo checkout without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import infinistore_tpu as its
from infinistore_tpu import compile_cache


def parse_args():
    # Every example starts here; the ones that jit (engine_serving,
    # prefix_reuse, disagg_prefill_decode) find their compiled programs
    # again on the next run.
    compile_cache.enable()
    p = argparse.ArgumentParser()
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument(
        "--service-port", type=int, default=0,
        help="port of a running server; 0 = start one in-process",
    )
    return p.parse_args()


def make_connection(args):
    """Build (but do not connect) a client, starting an in-process server if
    no --service-port was given. For async examples that `await
    conn.connect_async()` themselves."""
    srv = None
    port = args.service_port
    if port == 0:
        srv = its.start_local_server()
        port = srv.port
        print(f"(started in-process server on :{port})")
    conn = its.InfinityConnection(
        its.ClientConfig(host_addr=args.host, service_port=port)
    )

    def cleanup():
        conn.close()
        if srv is not None:
            srv.stop()

    return conn, cleanup


def get_connection(args):
    conn, cleanup = make_connection(args)
    conn.connect()
    return conn, cleanup
