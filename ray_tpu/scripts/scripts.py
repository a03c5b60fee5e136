"""Operator CLI: `python -m ray_tpu.scripts.scripts <command>`.

Parity: reference `python/ray/scripts/scripts.py` — start/stop/status/list/summary,
job submit/status/logs, microbenchmark. The head address is written to a well-known
file so follow-on commands (and `ray_tpu.init(address="auto")` semantics) find it.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

_ADDR_FILE = os.path.join(
    os.environ.get("TMPDIR", "/tmp"), "ray_tpu", "head_address.json"
)


def _write_addr(gcs_port: int, raylet_port: int, gcs_ports=None):
    os.makedirs(os.path.dirname(_ADDR_FILE), exist_ok=True)
    with open(_ADDR_FILE, "w") as f:
        json.dump({"gcs_port": gcs_port, "raylet_port": raylet_port,
                   "gcs_ports": list(gcs_ports or [gcs_port]),
                   "pid": os.getpid()}, f)


def read_addr():
    try:
        with open(_ADDR_FILE) as f:
            return json.load(f)
    except FileNotFoundError:
        return None


def _connect_from_file():
    import ray_tpu

    addr = read_addr()
    if addr is None:
        print("no running head found (start one with: ... start --head)", file=sys.stderr)
        sys.exit(1)
    os.environ["RAY_TPU_RAYLET_PORT"] = str(addr["raylet_port"])
    ports = addr.get("gcs_ports") or [addr["gcs_port"]]
    ray_tpu.init(address=",".join(f"127.0.0.1:{p}" for p in ports))


def cmd_start(args):
    from ray_tpu._private import node as node_mod

    if not args.head and not args.address:
        print("worker nodes need --address=host:gcs_port", file=sys.stderr)
        sys.exit(1)
    session_dir = node_mod.make_session_dir()
    resources = {"CPU": float(args.num_cpus or (os.cpu_count() or 1))}
    if args.resources:
        resources.update(json.loads(args.resources))
    if args.head:
        handle = node_mod.start_node(
            head=True, gcs_addr=None, resources=resources, labels=None,
            session_dir=session_dir,
            object_store_bytes=args.object_store_memory or 0,
            worker_env=None,
        )
        _write_addr(handle.gcs_port, handle.raylet_port,
                    gcs_ports=handle.gcs_ports)
        print(f"head started: gcs=127.0.0.1:{handle.gcs_port} "
              f"raylet_port={handle.raylet_port}")
    else:
        handle = node_mod.start_node(
            head=False, gcs_addr=args.address, resources=resources,
            labels=None, session_dir=session_dir,
            object_store_bytes=args.object_store_memory or 0, worker_env=None,
        )
        print(f"node started, joined {args.address}; raylet_port={handle.raylet_port}")
    if args.block or args.head:
        stop = []
        signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
        signal.signal(signal.SIGINT, lambda *a: stop.append(1))
        try:
            while not stop:
                time.sleep(0.5)
        finally:
            handle.terminate()
            if args.head:
                try:
                    os.remove(_ADDR_FILE)
                except OSError:
                    pass


def _load_cluster_yaml(path: str) -> dict:
    import yaml

    with open(path) as f:
        cfg = yaml.safe_load(f) or {}
    cfg.setdefault("cluster_name", "ray-tpu")
    cfg.setdefault("provider", {"type": "local"})
    cfg.setdefault("head", {})
    cfg.setdefault("workers", {})
    return cfg


class _LocalWorkerProvider:
    """`ray_tpu up` local provider: worker nodes as raylet processes joined to
    the head this command just started (NodeProvider SPI)."""

    def __init__(self, gcs_addr: tuple):
        self._gcs_addr = gcs_addr
        self._nodes = {}
        self._counter = 0

    def create_node(self, resources):
        from ray_tpu._private import node as node_mod

        handle = node_mod.start_node(
            head=False, gcs_addr=self._gcs_addr,
            resources={k: float(v) for k, v in resources.items()}, labels=None,
            session_dir=node_mod.make_session_dir(), object_store_bytes=0,
            worker_env=None,
        )
        self._counter += 1
        name = f"local-{self._counter}"
        self._nodes[name] = handle
        return name

    def terminate_node(self, node_id):
        handle = self._nodes.pop(node_id, None)
        if handle is not None:
            handle.terminate()

    def non_terminated_nodes(self):
        return list(self._nodes)

    def cluster_address(self, node_id):
        handle = self._nodes.get(node_id)
        return None if handle is None else ("127.0.0.1", handle.raylet_port)


def _head_ip() -> str:
    """The head's network-reachable address for worker startup scripts —
    loopback would make remote slices join themselves."""
    import socket

    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("8.8.8.8", 80))  # no traffic sent; picks the egress iface
        ip = s.getsockname()[0]
        s.close()
        return ip
    except OSError:
        return socket.gethostbyname(socket.gethostname())


def _build_provider(cfg: dict, head_address: str, gcs_addr: tuple | None = None):
    provider_cfg = dict(cfg["provider"])
    ptype = provider_cfg.pop("type", "local")
    if ptype in ("gcp", "gcp_tpu", "tpu"):
        from ray_tpu.autoscaler.gcp import GCETPUNodeProvider

        return GCETPUNodeProvider(
            head_address=head_address, cluster_name=cfg["cluster_name"],
            **provider_cfg,
        )
    if ptype == "local":
        if gcs_addr is None:
            addr = read_addr()
            if addr is None:
                raise RuntimeError("no running head found for the local provider")
            gcs_addr = ("127.0.0.1", addr["gcs_port"])
        return _LocalWorkerProvider(gcs_addr)
    if ptype == "ssh":
        from ray_tpu.autoscaler.ssh import SSHNodeProvider

        return SSHNodeProvider(provider_cfg, head_address=head_address)
    raise ValueError(f"unknown provider type {ptype!r}")


def cmd_up(args):
    """Launch a cluster from a YAML config: start the head HERE and run the
    autoscaler against the configured provider (reference: `ray up` +
    commands.py; the SSH-to-remote-head provisioning step is collapsed — run
    this on the head host, e.g. the first TPU VM)."""
    from ray_tpu._private import node as node_mod
    from ray_tpu.autoscaler import Autoscaler, AutoscalingConfig

    cfg = _load_cluster_yaml(args.config)
    head_cfg = cfg["head"]
    session_dir = node_mod.make_session_dir()
    resources = {"CPU": float(head_cfg.get("num_cpus", os.cpu_count() or 1))}
    resources.update(head_cfg.get("resources") or {})
    handle = node_mod.start_node(
        head=True, gcs_addr=None, resources=resources, labels=None,
        session_dir=session_dir, object_store_bytes=0, worker_env=None,
    )
    _write_addr(handle.gcs_port, handle.raylet_port,
                gcs_ports=handle.gcs_ports)
    local_address = f"127.0.0.1:{handle.gcs_port}"
    # Remote workers (TPU slices) must dial a reachable address, not loopback.
    # head.address pins host:port outright; head.host pins the host while the
    # GCS port stays dynamic (single-host/test topologies).
    public_address = head_cfg.get("address") or (
        f"{head_cfg.get('host') or _head_ip()}:{handle.gcs_port}"
    )
    print(f"head started: gcs={local_address} (workers join {public_address})")

    import ray_tpu

    ray_tpu.init(address=local_address, _raylet_port=handle.raylet_port)
    workers = cfg["workers"]
    provider = _build_provider(
        cfg, public_address, gcs_addr=("127.0.0.1", handle.gcs_port)
    )
    autoscaler = Autoscaler(provider, AutoscalingConfig(
        min_workers=int(workers.get("min_workers", 0)),
        max_workers=int(workers.get("max_workers", 4)),
        worker_resources=workers.get("resources") or {"CPU": 1},
        idle_timeout_s=float(workers.get("idle_timeout_s", 60.0)),
    ))
    autoscaler.start()
    print(f"autoscaler running: {workers.get('min_workers', 0)}-"
          f"{workers.get('max_workers', 4)} workers of "
          f"{workers.get('resources') or {'CPU': 1}}")
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.5)
    finally:
        autoscaler.stop()
        for nid in provider.non_terminated_nodes():
            try:
                provider.terminate_node(nid)
            except Exception:
                pass
        handle.terminate()
        try:
            os.remove(_ADDR_FILE)
        except OSError:
            pass


def cmd_down(args):
    """Terminate every provider node of the YAML cluster, then stop the head."""
    cfg = _load_cluster_yaml(args.config)
    provider = _build_provider(cfg, head_address="")
    for nid in provider.non_terminated_nodes():
        print(f"terminating {nid}")
        try:
            provider.terminate_node(nid)
        except Exception as e:  # noqa: BLE001
            print(f"  failed: {e}", file=sys.stderr)
    cmd_stop(args)


def cmd_stop(_args):
    addr = read_addr()
    if addr is None:
        print("no running head found")
        return
    try:
        os.kill(addr["pid"], signal.SIGTERM)
        print(f"sent SIGTERM to head pid {addr['pid']}")
    except ProcessLookupError:
        print("head process already gone")
    try:
        os.remove(_ADDR_FILE)
    except OSError:
        pass


def cmd_client_proxy(args):
    """Run a ClientProxy fronting the cluster for ray_tpu+proxy:// clients
    (reference: util/client/server/proxier.py as `ray client-server`)."""
    import time as _time

    from ray_tpu.util.client.proxier import serve_proxy

    if args.address:
        host, port = args.address.split(":")
        gcs_addr = (host, int(port))
    else:
        addr = read_addr()
        if addr is None:
            print("no running head found; pass --address host:gcs_port")
            return
        gcs_addr = ("127.0.0.1", addr["gcs_port"])
    try:
        proxy, _loop = serve_proxy(gcs_addr, host=args.host, port=args.port,
                                   token=args.token,
                                   insecure=args.insecure_no_token)
    except ValueError as e:
        print(e)
        sys.exit(1)
    auth = f"{args.token}@" if args.token else ""
    print(f"client proxy listening on {args.host}:{proxy.port} "
          f"(clients: ray_tpu+proxy://{auth}<this-host>:{proxy.port})")
    try:
        while True:
            _time.sleep(3600)
    except KeyboardInterrupt:
        pass


def _fmt_bytes(n) -> str:
    try:
        n = float(n)
    except (TypeError, ValueError):
        return str(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}TiB"


def _render_programs(lines, report, indent="  "):
    totals = (report or {}).get("totals") or {}
    lines.append(f"{indent}programs={totals.get('programs', 0)} "
                 f"compiles={totals.get('compiles_total', 0)} "
                 f"recompiles={totals.get('recompiles_total', 0)} "
                 f"compile_s={totals.get('compile_s_total', 0.0):.2f}")
    for row in (report or {}).get("programs") or []:
        lines.append(
            f"{indent}  {row.get('owner')} {row.get('key')}: "
            f"compiles={row.get('compiles')} recompiles={row.get('recompiles')} "
            f"invocations={row.get('invocations')} "
            f"compile_s={row.get('compile_s', 0.0):.2f}")


def _render_memory(lines, report, indent="  "):
    rep = report or {}
    lines.append(f"{indent}tracked_total="
                 f"{_fmt_bytes(rep.get('tracked_bytes_total', 0))}")
    owners = rep.get("owners") or {}
    ranked = sorted(owners.items(),
                    key=lambda kv: -(kv[1].get("bytes", 0)
                                     if isinstance(kv[1], dict) else 0))
    for name, row in ranked:
        if not isinstance(row, dict):
            continue
        extra = ""
        comps = row.get("components")
        if comps:
            extra = " (" + ", ".join(
                f"{k}={_fmt_bytes(v)}" for k, v in comps.items()) + ")"
        lines.append(f"{indent}  {name}: "
                     f"{_fmt_bytes(row.get('bytes', 0))}{extra}")
    for dev in rep.get("devices") or []:
        ms = dev.get("memory_stats") or {}
        detail = ""
        if ms:
            detail = (f" in_use={_fmt_bytes(ms.get('bytes_in_use', 0))}"
                      f" peak={_fmt_bytes(ms.get('peak_bytes_in_use', 0))}"
                      f" limit={_fmt_bytes(ms.get('bytes_limit', 0))}")
        lines.append(f"{indent}  device {dev.get('id')} "
                     f"({dev.get('platform')}){detail}")


def render_status(status: dict) -> str:
    """Render a `util.state.cluster_status()` snapshot as sectioned text
    (the non-`--json` body of `ray_tpu status`)."""
    lines = []
    summary = status.get("summary") or {}

    lines.append("== nodes ==")
    lines.append(f"  {summary.get('alive_nodes', 0)}/{summary.get('nodes', 0)}"
                 " alive")
    for node in status.get("nodes") or []:
        nid = str(node.get("node_id", "?"))[:12]
        alive = "ALIVE" if node.get("alive", True) else "DEAD"
        res = node.get("resources_total") or node.get("resources") or {}
        res_s = " ".join(f"{k}={v:g}" if isinstance(v, float) else f"{k}={v}"
                         for k, v in sorted(res.items()))
        lines.append(f"  {nid} {alive} {res_s}")

    lines.append("== resources ==")
    total = summary.get("resources_total") or {}
    avail = summary.get("resources_available") or {}
    for k in sorted(total):
        lines.append(f"  {k}: {avail.get(k, 0):g}/{total[k]:g} available")

    lines.append("== tasks ==")
    for state_name, n in sorted((summary.get("tasks") or {}).items()):
        lines.append(f"  {state_name}: {n}")

    lines.append("== actors ==")
    for state_name, n in sorted((summary.get("actors") or {}).items()):
        lines.append(f"  {state_name}: {n}")
    for actor in status.get("actors") or []:
        if "error" in actor and len(actor) == 1:
            lines.append(f"  (listing error: {actor['error']})")
            continue
        aid = str(actor.get("actor_id", "?"))[:12]
        lines.append(f"  {aid} {actor.get('class_name', '?')} "
                     f"{actor.get('state', '?')}")

    serve = status.get("serve") or {}
    lines.append("== serve ==")
    apps = serve.get("apps") or {}
    if not apps:
        lines.append("  (no serve apps)")
    for app, stats in apps.items():
        lines.append(f"  app {app} (ingress={stats.get('ingress')})")
        sched = stats.get("scheduler_stats")
        sched_list = sched if isinstance(sched, list) else [sched]
        for i, s in enumerate(sched_list):
            if not isinstance(s, dict):
                continue
            tag = f" replica {i}" if len(sched_list) > 1 else ""
            lines.append(f"   {tag} running={s.get('running')} "
                         f"queued={s.get('queued')} "
                         f"free_slots={s.get('free_slots')}")
            if s.get("programs"):
                lines.append(f"   {tag} programs:")
                _render_programs(lines, s["programs"], indent="      ")
            if s.get("memory"):
                lines.append(f"   {tag} memory:")
                _render_memory(lines, s["memory"], indent="      ")

    lines.append("== transport ==")
    transport = serve.get("transport") or {}
    for k, v in sorted(transport.items()):
        lines.append(f"  {k}: {v}")

    lines.append("== autopilot ==")
    ap = serve.get("autopilot") or {}
    if not ap.get("enabled"):
        lines.append("  (off)")
    else:
        for key, target in sorted((ap.get("targets") or {}).items()):
            lines.append(f"  target {key}: {target}")
        for app, tenants in sorted((ap.get("weights") or {}).items()):
            kv = " ".join(f"{t}={w:.2f}" for t, w in sorted(tenants.items()))
            lines.append(f"  weights {app}: {kv}")
        counts = ap.get("counts") or {}
        if counts:
            kv = " ".join(f"{r}={n}" for r, n in sorted(counts.items()))
            lines.append(f"  decisions: {kv}")
        for d in (ap.get("decisions") or [])[-5:]:
            lines.append(f"  [{d.get('seq')}] {d.get('rule')} "
                         f"{d.get('app')}/{d.get('deployment') or d.get('tenant')} "
                         f"-> {d.get('outcome')}")
    if "error" in ap:
        lines.append(f"  (error: {ap['error']})")

    lines.append("== control plane ==")
    cp = serve.get("control_plane") or {}
    for section in ("store", "repl"):
        row = cp.get(section)
        if isinstance(row, dict):
            kv = " ".join(f"{k}={v}" for k, v in sorted(row.items()))
            lines.append(f"  {section}: {kv}")
    if "error" in cp:
        lines.append(f"  (error: {cp['error']})")

    lines.append("== programs (driver) ==")
    _render_programs(lines, status.get("programs"))

    lines.append("== memory (driver) ==")
    _render_memory(lines, status.get("memory"))
    return "\n".join(lines)


def cmd_status(args):
    """One-shot operator snapshot (docs/observability.md "compute plane"):
    joins the state API (nodes/resources/actors), control-plane and serve
    stats, transport counters, and the xprof program registry + device-memory
    ledger into a readable cluster status. Reuses an already-initialized
    driver connection when present (in-process use / tests) instead of
    connecting from the address file."""
    import ray_tpu
    from ray_tpu.util import state

    owned = not ray_tpu.is_initialized()
    if owned:
        _connect_from_file()
    try:
        status = state.cluster_status()
        if getattr(args, "json", False):
            print(json.dumps(status, indent=2, default=str))
        else:
            print(render_status(status))
    finally:
        if owned:
            ray_tpu.shutdown()


def cmd_timeline(args):
    """Export task events as Chrome trace-event JSON (reference: `ray
    timeline`, python/ray/scripts/scripts.py). Loads in Perfetto."""
    import ray_tpu
    from ray_tpu.util import state

    _connect_from_file()
    out = args.output or "ray_tpu_timeline.json"
    events = state.timeline(out)
    spans = sum(1 for e in events if e.get("ph") == "X")
    print(f"wrote {spans} spans to {out} (open in https://ui.perfetto.dev "
          f"or chrome://tracing)")
    ray_tpu.shutdown()


def cmd_memory(_args):
    """Summarize object-store contents by owner (reference: `ray memory`,
    python/ray/_private/internal_api.py)."""
    import ray_tpu
    from ray_tpu.util import state

    _connect_from_file()
    summary = state.memory_summary()
    cap = " (listing capped; totals are a lower bound)" if summary.get(
        "truncated") else ""
    print(f"{summary['num_objects']} objects, "
          f"{summary['total_bytes'] / (1 << 20):.1f} MiB total{cap}")
    for owner, agg in sorted(summary["by_owner"].items(),
                             key=lambda kv: -kv[1]["bytes"]):
        print(f"  owner {owner[:12]}: {agg['count']} objects, "
              f"{agg['bytes'] / (1 << 20):.2f} MiB")
    for obj in summary["objects"][:50]:
        print(json.dumps(obj, default=str))
    ray_tpu.shutdown()


def cmd_debug(args):
    """Attach to a parked post-mortem session (reference: `ray debug`,
    python/ray/scripts/scripts.py:239 + util/rpdb.py). Workers park failing
    tasks when RAY_TPU_POST_MORTEM=1; this lists the advertised sessions and
    bridges this terminal to the chosen worker's pdb."""
    import ray_tpu
    from ray_tpu._private import debugger
    from ray_tpu._private.worker import global_worker

    _connect_from_file()
    try:
        sessions = debugger.list_sessions(global_worker())
        if not sessions:
            print("no active post-mortem sessions (set RAY_TPU_POST_MORTEM=1 "
                  "on workers to park failing tasks)")
            return
        if args.task_id:
            chosen = next(
                (s for s in sessions if s["task_id"].startswith(args.task_id)),
                None,
            )
            if chosen is None:
                print(f"no session matching task id {args.task_id!r}",
                      file=sys.stderr)
                sys.exit(1)
        else:
            for i, s in enumerate(sessions):
                print(f"[{i}] task {s['task_id'][:16]} {s.get('name')!r} "
                      f"pid={s.get('pid')} error={s.get('error')}")
            if len(sessions) == 1:
                chosen = sessions[0]
            else:
                try:
                    idx = int(input("attach to which session? "))
                    chosen = sessions[idx]
                except (ValueError, IndexError, EOFError):
                    print("pass a session number from the list above (or the "
                          "task id as an argument)", file=sys.stderr)
                    sys.exit(1)
        print(f"attaching to task {chosen['task_id'][:16]} at "
              f"{chosen['ip']}:{chosen['port']} (q or c to detach)")
        try:
            debugger.attach(chosen)
        except OSError as e:
            # SIGKILLed (or already-released) workers never deregister their
            # advertisement: clean the ghost up instead of tracebacking.
            debugger.drop_session(global_worker(), chosen)
            print(f"session is gone ({e}); removed the stale advertisement",
                  file=sys.stderr)
            sys.exit(1)
    finally:
        ray_tpu.shutdown()


def cmd_serve_deploy(args):
    """Apply a declarative serve config file (reference: `serve deploy`,
    python/ray/serve/scripts.py:333). PUT semantics: the file is the whole
    desired state."""
    import yaml

    import ray_tpu
    from ray_tpu.serve import schema as serve_schema

    with open(args.config_file) as f:
        config = yaml.safe_load(f)
    _connect_from_file()
    try:
        outcomes = serve_schema.apply_config(config, wait_ready=args.wait)
    except serve_schema.ServeConfigError as e:
        print(f"invalid config: {e}", file=sys.stderr)
        sys.exit(1)
    for app, outcome in sorted(outcomes.items()):
        print(f"{app}: {outcome}")
    print(f"applied {args.config_file!r}; check progress with: "
          "ray_tpu serve status")
    ray_tpu.shutdown()


def cmd_serve_status(_args):
    """Live per-app/deployment status (reference: `serve status`,
    python/ray/serve/scripts.py:696)."""
    import yaml

    import ray_tpu
    from ray_tpu.serve import schema as serve_schema

    _connect_from_file()
    print(yaml.safe_dump(serve_schema.status_report(), sort_keys=False).rstrip())
    ray_tpu.shutdown()


def cmd_serve_build(args):
    """Scaffold a deployable config from bound applications (reference:
    `serve build`, python/ray/serve/scripts.py:814). Needs no cluster."""
    import yaml

    from ray_tpu.serve import schema as serve_schema

    config = serve_schema.build_config(args.import_paths)
    text = yaml.safe_dump(config, sort_keys=False)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
        print(f"wrote {args.output}")
    else:
        print(text.rstrip())


def cmd_serve_shutdown(_args):
    import ray_tpu
    from ray_tpu import serve

    _connect_from_file()
    serve.shutdown()
    print("serve shut down")
    ray_tpu.shutdown()


def cmd_serve_delete(args):
    import ray_tpu
    from ray_tpu import serve

    _connect_from_file()
    serve.delete(args.name)
    print(f"deleted application {args.name!r}")
    ray_tpu.shutdown()


def cmd_list(args):
    import ray_tpu
    from ray_tpu.util import state

    _connect_from_file()
    fn = {
        "nodes": state.list_nodes,
        "actors": state.list_actors,
        "tasks": state.list_tasks,
        "objects": state.list_objects,
        "placement-groups": state.list_placement_groups,
        "jobs": state.list_jobs,
    }[args.entity]
    for row in fn():
        print(json.dumps(row, default=str))
    ray_tpu.shutdown()


def cmd_job_submit(args):
    import ray_tpu
    from ray_tpu.job_submission import JobSubmissionClient, JobStatus

    _connect_from_file()
    client = JobSubmissionClient()
    # Drop only the LEADING argparse separator; later '--' tokens belong to the
    # user's command line.
    entrypoint = args.entrypoint
    if entrypoint and entrypoint[0] == "--":
        entrypoint = entrypoint[1:]
    job_id = client.submit_job(entrypoint=" ".join(entrypoint))
    print(f"submitted {job_id}")
    if args.no_wait:
        ray_tpu.shutdown()
        return
    status = client.wait_until_status(job_id, timeout=args.timeout)
    print(client.get_job_logs(job_id), end="")
    print(f"job {job_id}: {status}")
    ray_tpu.shutdown()
    sys.exit(0 if status == JobStatus.SUCCEEDED else 1)


def cmd_job_logs(args):
    import ray_tpu
    from ray_tpu.job_submission import JobSubmissionClient

    _connect_from_file()
    print(JobSubmissionClient().get_job_logs(args.job_id), end="")
    ray_tpu.shutdown()


def cmd_microbenchmark(_args):
    """Parity: `ray microbenchmark` (python/ray/_private/ray_perf.py) — core op rates."""
    import numpy as np

    import ray_tpu

    # Core-op rates measure the runtime: workers stay off the accelerator.
    ray_tpu.init(num_cpus=4, num_tpus=0, worker_env={"JAX_PLATFORMS": "cpu"})

    def rate(n, fn):
        t0 = time.monotonic()
        fn(n)
        return n / (time.monotonic() - t0)

    @ray_tpu.remote
    def noop():
        return None

    # Prewarm the worker pool: spawn time must not pollute steady-state rates.
    ray_tpu.get([noop.remote() for _ in range(100)])
    print(f"single_client_tasks_sync: "
          f"{rate(300, lambda n: [ray_tpu.get(noop.remote()) for _ in range(n)]):.1f}/s")
    print(f"single_client_tasks_async: "
          f"{rate(1000, lambda n: ray_tpu.get([noop.remote() for _ in range(n)])):.1f}/s")

    @ray_tpu.remote
    class A:
        def f(self):
            return None

    a = A.remote()
    ray_tpu.get(a.f.remote())
    print(f"1_1_actor_calls_sync: "
          f"{rate(300, lambda n: [ray_tpu.get(a.f.remote()) for _ in range(n)]):.1f}/s")
    print(f"1_1_actor_calls_async: "
          f"{rate(1000, lambda n: ray_tpu.get([a.f.remote() for _ in range(n)])):.1f}/s")

    arr = np.zeros(1024 * 1024, dtype=np.uint8)
    # Warm: fault in the source pages and the arena blocks the loop will reuse.
    del [ray_tpu.put(arr) for _ in range(100)][:]
    print(f"single_client_put_1MiB: "
          f"{rate(100, lambda n: [ray_tpu.put(arr) for _ in range(n)]):.1f}/s")
    big = np.zeros(256 << 20, dtype=np.uint8)
    for _ in range(2):
        ray_tpu.get(ray_tpu.put(big))  # steady state: source + arena pages warm
    t0 = time.monotonic()
    for _ in range(8):
        ray_tpu.get(ray_tpu.put(big))
    gib = 8 * big.nbytes / (time.monotonic() - t0) / 2**30
    print(f"put+get bandwidth: {gib:.2f} GiB/s")
    ray_tpu.shutdown()


def main(argv=None):
    parser = argparse.ArgumentParser(prog="ray_tpu")
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("start", help="start a head or worker node")
    p.add_argument("--head", action="store_true")
    p.add_argument("--address", help="gcs address host:port to join")
    p.add_argument("--num-cpus", type=int)
    p.add_argument("--resources", help='JSON, e.g. \'{"TPU": 4}\'')
    p.add_argument("--object-store-memory", type=int)
    p.add_argument("--block", action="store_true")
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("up", help="launch a cluster from a YAML config")
    p.add_argument("config")
    p.set_defaults(fn=cmd_up)
    p = sub.add_parser("down", help="tear down a YAML-configured cluster")
    p.add_argument("config")
    p.set_defaults(fn=cmd_down)
    sub.add_parser("stop", help="stop the local head").set_defaults(fn=cmd_stop)
    p = sub.add_parser("status", help="cluster snapshot: nodes, actors, "
                       "serve plane, XLA programs, device memory")
    p.add_argument("--json", action="store_true",
                   help="emit the raw cluster_status() dict as JSON")
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser("timeline",
                       help="export task events as Chrome trace JSON")
    p.add_argument("output", nargs="?", help="output file "
                   "(default ray_tpu_timeline.json)")
    p.set_defaults(fn=cmd_timeline)

    sub.add_parser(
        "memory", help="object-store contents by owner"
    ).set_defaults(fn=cmd_memory)

    p = sub.add_parser("list", help="list cluster entities")
    p.add_argument("entity", choices=["nodes", "actors", "tasks", "objects",
                                      "placement-groups", "jobs"])
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser("job", help="job commands")
    jsub = p.add_subparsers(dest="job_command", required=True)
    ps = jsub.add_parser("submit")
    ps.add_argument("--no-wait", action="store_true")
    ps.add_argument("--timeout", type=float, default=600)
    ps.add_argument("entrypoint", nargs=argparse.REMAINDER)
    ps.set_defaults(fn=cmd_job_submit)
    pl = jsub.add_parser("logs")
    pl.add_argument("job_id")
    pl.set_defaults(fn=cmd_job_logs)

    p = sub.add_parser("debug",
                       help="attach pdb to a parked post-mortem task")
    p.add_argument("task_id", nargs="?", default=None,
                   help="task id (prefix) to attach to")
    p.set_defaults(fn=cmd_debug)

    p = sub.add_parser("serve", help="declarative serving commands")
    ssub = p.add_subparsers(dest="serve_command", required=True)
    pd = ssub.add_parser("deploy", help="apply a serve config YAML")
    pd.add_argument("config_file")
    pd.add_argument("--wait", action="store_true",
                    help="block until every application is ready")
    pd.set_defaults(fn=cmd_serve_deploy)
    ssub.add_parser("status", help="per-app deployment status").set_defaults(
        fn=cmd_serve_status)
    pb = ssub.add_parser("build", help="scaffold a config from applications")
    pb.add_argument("import_paths", nargs="+",
                    help="module:attr of bound Applications or builders")
    pb.add_argument("-o", "--output", default=None)
    pb.set_defaults(fn=cmd_serve_build)
    ssub.add_parser("shutdown", help="tear down serve").set_defaults(
        fn=cmd_serve_shutdown)
    pdel = ssub.add_parser("delete", help="delete one application")
    pdel.add_argument("name")
    pdel.set_defaults(fn=cmd_serve_delete)

    p = sub.add_parser("client-proxy",
                       help="proxy ray_tpu+proxy:// clients into the cluster")
    p.add_argument("--address", help="gcs address host:port (default: local head)")
    p.add_argument("--host", default="127.0.0.1",
                   help="bind address; non-loopback without --token is refused "
                        "unless --insecure-no-token is also passed")
    p.add_argument("--port", type=int, default=10001)
    p.add_argument("--insecure-no-token", action="store_true",
                   help="allow binding a non-loopback host with no --token "
                        "(any network peer gets in-cluster-driver trust)")
    p.add_argument("--token", help="shared secret clients must present "
                                   "(ray_tpu+proxy://<token>@host:port)")
    p.set_defaults(fn=cmd_client_proxy)

    sub.add_parser("microbenchmark", help="core op throughput").set_defaults(
        fn=cmd_microbenchmark
    )

    args = parser.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
