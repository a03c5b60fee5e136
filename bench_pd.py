"""PD KV-transfer benchmark: chunked tensor stream vs the host-pickle blob.

Measures the make-or-break cost of prefill/decode disaggregation (DistServe /
Mooncake: the KV handoff must be pipelined and copy-free) at realistic prefix
sizes, across REAL actor processes on one node:

- host_pickle: the seed-shape path — device -> host -> cloudpickle -> ONE
  RPC frame -> unpickle -> host -> device. The monolithic blob every copy of
  which is serial.
- object_plane: the pre-round-11 device_objects path — one full-tensor host
  materialization through the shared-memory object store.
- chunked_stream: the round-11 DeviceChannel path (docs/device_channels.md):
  raw chunk frames through a shm ring, D2H / wire / assembly pipelined at
  `llm_channel_chunk_bytes` granularity, no pickling of tensor bytes.

Per mode: transfer_s (descriptor resolution + payload to a host/continuous
buffer on the consumer) and attach_s (staging the prefix into device memory,
`block_until_ready` — the decode-side `_attach_kv` feed). Writes
BENCH_PD.json. Acceptance (ISSUE 8): chunked_stream total <= 0.5x host_pickle
total at >= 16 MB.
"""

from __future__ import annotations

import json
import time

KV_SHAPES = {
    # [L, 2, P, Hkv, D] float32; row cost L*2*Hkv*D*4 = 4096 B/token.
    "4MB": (4, 2, 1024, 2, 64),
    "16MB": (4, 2, 4096, 2, 64),
    "64MB": (4, 2, 16384, 2, 64),
}


def main():
    import numpy as np

    import ray_tpu

    ray_tpu.init(
        num_cpus=4, num_tpus=0,
        worker_env={"JAX_PLATFORMS": "cpu"},
    )

    @ray_tpu.remote
    class Prefill:
        """Owns the pinned KV prefixes (the prefill replica role)."""

        def pin(self, shape):
            import jax.numpy as jnp
            import numpy as np

            from ray_tpu.experimental import device_objects as dev

            rng = np.random.default_rng(0)
            kv = rng.standard_normal(shape).astype(np.float32)
            return dev.put(jnp.asarray(kv))

        def open_blob_channel(self, ref):
            """The host-pickle baseline's wire: one cloudpickled blob pushed
            through an RpcChannel (device->host->pickle->one RPC frame)."""
            import pickle
            import threading

            import cloudpickle
            import numpy as np

            from ray_tpu._private.worker import global_worker
            from ray_tpu.experimental import device_objects as dev
            from ray_tpu.experimental.channel import RpcChannel

            w = global_worker()
            ch = RpcChannel(num_readers=1, num_slots=2,
                            owner=("actor", w.actor_id))

            def pump():
                arr = dev.get(ref)  # owner-local: zero transfer
                blob = cloudpickle.dumps(
                    np.asarray(arr), protocol=pickle.HIGHEST_PROTOCOL
                )
                ch.write_bytes(blob, timeout=120.0)
                ch.drain(timeout=120.0)
                ch.destroy()

            threading.Thread(target=pump, daemon=True).start()
            return ch

    @ray_tpu.remote
    class Decode:
        """Pulls + attaches (the decode replica role); timings measured HERE,
        inside the consuming process."""

        def measure(self, owner, ref, mode):
            import cloudpickle
            import jax.numpy as jnp

            import ray_tpu as rt
            from ray_tpu.experimental import device_objects as dev

            t0 = time.perf_counter()
            if mode == "host_pickle":
                ch = rt.get(owner.open_blob_channel.remote(ref))
                kv = cloudpickle.loads(ch.read_bytes(timeout=120.0))
            elif mode == "object_plane":
                kv = dev.get(ref, _legacy=True)
            elif mode == "chunked_stream":
                # Direct stream call: get() itself gates small payloads onto
                # the blob path (devobj_stream_min_bytes); the bench measures
                # the raw stream at every size to show WHERE the gate sits.
                kv = dev._stream_fetch(ref, to_device=False)
            else:
                raise ValueError(mode)
            t1 = time.perf_counter()
            dev_kv = jnp.asarray(kv)
            dev_kv.block_until_ready()
            t2 = time.perf_counter()
            assert dev_kv.shape == ref.shape
            return {"transfer_s": t1 - t0, "attach_s": t2 - t1,
                    "total_s": t2 - t0}

    prefill, decode = Prefill.remote(), Decode.remote()
    results = []
    for label, shape in KV_SHAPES.items():
        ref = ray_tpu.get(prefill.pin.remote(shape), timeout=300)
        nbytes = int(np.prod(shape)) * 4
        row = {"metric": "pd_kv_transfer_attach", "prefix": label,
               "prefix_tokens": shape[2], "kv_bytes": nbytes}
        for mode in ("host_pickle", "object_plane", "chunked_stream"):
            best = None
            for _ in range(3):
                t = ray_tpu.get(
                    decode.measure.remote(prefill, ref, mode), timeout=600
                )
                if best is None or t["total_s"] < best["total_s"]:
                    best = t
            row[mode] = {k: round(v, 4) for k, v in best.items()}
        row["speedup_vs_host_pickle"] = round(
            row["host_pickle"]["total_s"] / row["chunked_stream"]["total_s"], 2
        )
        row["speedup_vs_object_plane"] = round(
            row["object_plane"]["total_s"] / row["chunked_stream"]["total_s"], 2
        )
        results.append(row)
        print(json.dumps(row))

    import jax

    from ray_tpu._private.config import CONFIG

    out = {
        "bench": "pd_kv_transfer",
        "backend": jax.default_backend(),
        "chunk_bytes": CONFIG.llm_channel_chunk_bytes,
        "stream_slots": CONFIG.devobj_stream_slots,
        "results": results,
        "stream_min_bytes": CONFIG.devobj_stream_min_bytes,
        "note": "same-node actor pair; chunked_stream rides the shm "
                "DeviceChannel ring (docs/device_channels.md), host_pickle "
                "is the seed-shape monolithic cloudpickle blob over one RPC "
                "frame, object_plane the pre-round-11 device_objects blob; "
                "production get() takes the blob below devobj_stream_min_"
                "bytes (stream setup only amortizes on multi-MB tensors)",
    }
    with open("BENCH_PD.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
