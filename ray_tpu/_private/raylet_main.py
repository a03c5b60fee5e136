"""Node process entry point: runs a raylet, plus the GCS when started as head.

Design parity: reference `src/ray/raylet/main.cc` (raylet binary hosting NodeManager +
ObjectManager) and `src/ray/gcs/gcs_server_main.cc` (gcs_server binary). Both services
share one asyncio loop in one process per node; the head node hosts both.
"""

from __future__ import annotations

import argparse
import asyncio
import faulthandler
import json
import os
import signal
import sys

from ray_tpu._private import rpc
from ray_tpu._private.config import bind_host_for, get_node_ip
from ray_tpu._private.gcs import GcsService
from ray_tpu._private.ids import NodeID
from ray_tpu._private.raylet import Raylet


async def amain(args):
    from ray_tpu._private.gcs_replication import parse_addrs

    gcs_addrs = parse_addrs(args.gcs_addrs) if args.gcs_addrs else []
    if not gcs_addrs and args.gcs_port:
        gcs_addrs = [(args.gcs_host, args.gcs_port)]
    if args.head and not gcs_addrs:
        # Fallback for direct invocation: host the GCS in-process. The normal path
        # (node.py) runs the GCS as its own restartable process via gcs_main.
        gcs = GcsService()
        gcs_server = rpc.RpcServer(lambda conn: gcs)
        await gcs_server.start(
            host=bind_host_for(args.node_ip or get_node_ip()), port=0
        )
        gcs.start_background()
        gcs_addrs = [(args.gcs_host, gcs_server.port)]
    gcs_port = gcs_addrs[0][1]

    node_id = NodeID.from_hex(args.node_id) if args.node_id else NodeID.from_random()
    raylet = Raylet(
        node_id=node_id,
        gcs_addr=gcs_addrs,
        resources=json.loads(args.resources),
        labels=json.loads(args.labels),
        is_head=args.head,
        session_dir=args.session_dir,
        object_store_bytes=args.object_store_bytes or None,
        worker_env=json.loads(args.worker_env),
        node_ip=args.node_ip or None,
    )
    await raylet.start(port=args.port)

    # Report the bound ports to the parent via a ready file.
    ready = {
        "node_id": node_id.hex(),
        "raylet_port": raylet.port,
        "gcs_port": gcs_port,
        "pid": os.getpid(),
    }
    if args.ready_file:
        tmp = args.ready_file + ".tmp"
        with open(tmp, "w") as f:
            json.dump(ready, f)
        os.replace(tmp, args.ready_file)

    stop = asyncio.Event()

    def _sig(*_a):
        stop.set()

    loop = asyncio.get_running_loop()
    for s in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(s, _sig)
    await stop.wait()
    await raylet.shutdown()


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--head", action="store_true")
    p.add_argument("--gcs-host", default="127.0.0.1")
    p.add_argument("--gcs-port", type=int, default=0)
    p.add_argument("--gcs-addrs", default="",
                   help="comma host:port list of GCS candidates (replicated "
                        "mode lists every head candidate)")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--node-id", default="")
    p.add_argument("--node-ip", default="")
    p.add_argument("--resources", default="{}")
    p.add_argument("--labels", default="{}")
    p.add_argument("--worker-env", default="{}")
    p.add_argument("--session-dir", default="/tmp/ray_tpu")
    p.add_argument("--object-store-bytes", type=int, default=0)
    p.add_argument("--ready-file", default="")
    args = p.parse_args()
    faulthandler.register(signal.SIGUSR1, all_threads=True)  # `kill -USR1`: stacks to the log
    asyncio.run(amain(args))


if __name__ == "__main__":
    sys.exit(main())
