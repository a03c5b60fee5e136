"""GCS server process entry point.

Design parity: reference `src/ray/gcs/gcs_server_main.cc:51` — the cluster control
plane runs as its own process so it can crash and restart independently of any raylet;
with a persistent store (--store-dir) a restarted GCS re-learns cluster state from
storage plus raylet re-registration (reference `gcs_init_data.cc`).

With `--peers` naming more than one candidate this process instead runs one
replicated-GCS head candidate (`gcs_replication.GcsCandidate`): a warm standby
that replays the primary's log and serves clients only while it holds the
quorum lease (docs/fault_tolerance.md). A single-candidate invocation is the
classic single GcsService, unchanged.
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
from ray_tpu._private.gcs_replication import GcsCandidate, parse_addrs
from ray_tpu._private.gcs_store import FileStoreClient, InMemoryStoreClient


def _write_ready(path: str, port: int):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"gcs_port": port, "pid": os.getpid()}, f)
    os.replace(tmp, path)


async def amain(args):
    peers = parse_addrs(args.peers)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for s in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(s, stop.set)

    if len(peers) > 1:
        if not args.store_dir:
            raise SystemExit("replicated GCS candidates require --store-dir")
        cand = GcsCandidate(args.candidate_id, peers, args.store_dir)
        server = rpc.RpcServer(lambda conn: cand.facade(conn))
        # Raylets on other hosts must be able to register: listen beyond
        # loopback whenever this node advertises a routable IP.
        await server.start(host=bind_host_for(get_node_ip()), port=args.port)
        cand.server = server
        cand.start_background()
        if args.ready_file:
            _write_ready(args.ready_file, server.port)
        await stop.wait()
        await cand.shutdown()
        return

    store = FileStoreClient(args.store_dir) if args.store_dir else InMemoryStoreClient()
    gcs = GcsService(store=store)
    server = rpc.RpcServer(lambda conn: gcs)
    await server.start(host=bind_host_for(get_node_ip()), port=args.port)
    gcs.start_background()

    if args.ready_file:
        _write_ready(args.ready_file, server.port)

    await stop.wait()
    await server.close()
    store.close()


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--store-dir", default="")
    p.add_argument("--ready-file", default="")
    p.add_argument("--candidate-id", type=int, default=0)
    p.add_argument("--peers", default="",
                   help="comma host:port list of ALL candidates (self included); "
                        "more than one entry enables quorum-HA candidate mode")
    args = p.parse_args()
    faulthandler.register(signal.SIGUSR1, all_threads=True)  # `kill -USR1`: stacks to the log
    asyncio.run(amain(args))


if __name__ == "__main__":
    sys.exit(main())
