"""Worker process entry point.

Design parity: reference `python/ray/_private/workers/default_worker.py` — connect the
CoreWorker, then block in the task loop (here the loop is the event-driven io thread).
"""

from __future__ import annotations

import faulthandler
import os
import signal
import threading

from ray_tpu._private.ids import WorkerID
from ray_tpu._private.worker import CoreWorker, set_global_worker
from ray_tpu.util.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()  # whatever this worker compiles, its successor reads back
    # `kill -USR1 <pid>` writes every thread's stack to the worker's log: the way to
    # see where a worker hangs on a machine that is thrown away afterwards.
    faulthandler.register(signal.SIGUSR1, all_threads=True)
    worker_id = WorkerID.from_hex(os.environ["RAY_TPU_WORKER_ID"])
    raylet_port = int(os.environ["RAY_TPU_RAYLET_PORT"])
    worker = CoreWorker(
        mode="worker",
        raylet_addr=("127.0.0.1", raylet_port),
        # Comma-separated candidate list under a replicated GCS; CoreWorker
        # normalizes and fails over between them.
        gcs_addr=os.environ["RAY_TPU_GCS_ADDR"],
        worker_id=worker_id,
    )
    set_global_worker(worker)
    worker.connect()
    threading.Event().wait()  # serve tasks until the raylet connection closes


if __name__ == "__main__":
    main()
