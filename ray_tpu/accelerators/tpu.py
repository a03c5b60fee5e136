"""TPU accelerator manager: chip/topology/slice discovery and slice resources.

Design parity: reference `python/ray/_private/accelerators/tpu.py` (:199 TPUAcceleratorManager)
— detects chips via env/GCE metadata (TPU_ACCELERATOR_TYPE, TPU_TOPOLOGY, TPU_NAME,
TPU_WORKER_ID), sets TPU_VISIBLE_CHIPS for workers, and publishes three resource kinds:
  - "TPU": chips on this host,
  - pod-type resource, e.g. "TPU-v4-16" (tpu.py:326),
  - per-slice head resource "TPU-<pod>-head" on worker 0 (tpu.py:482-547), which makes
    slice-atomic gang scheduling expressible as a placement-group bundle.
"""

from __future__ import annotations

import functools
import glob
import os
import urllib.request


def _env(name: str) -> str | None:
    v = os.environ.get(name)
    return v if v else None


def _on_gce() -> bool:
    """True where the firmware says this is a Google Compute Engine VM. A file
    read: it cannot stall, which a name lookup on a machine with no network can."""
    try:
        with open("/sys/class/dmi/id/product_name") as f:
            return "Google" in f.read()
    except OSError:
        return False


@functools.lru_cache(maxsize=None)
def _gce_metadata(key: str) -> str | None:
    """GCE instance metadata lookup (reference tpu.py:199-250). Asked only on a
    GCE VM, by the server's link-local address (no DNS), once per process; None
    elsewhere or when the server does not answer within the timeout."""
    if _env("TPU_SKIP_MDS_QUERY") or not _on_gce():  # libtpu's own "ask nobody" switch
        return None
    req = urllib.request.Request(
        f"http://169.254.169.254/computeMetadata/v1/instance/attributes/{key}",
        headers={"Metadata-Flavor": "Google"},
    )
    try:
        with urllib.request.urlopen(req, timeout=0.5) as resp:
            return resp.read().decode() or None
    except OSError:  # URLError, timeouts and refused connections are all OSError
        return None


def _accelerator_type() -> str | None:
    return _env("TPU_ACCELERATOR_TYPE") or _gce_metadata("accelerator-type")


def _count_device_files() -> int:
    """TPU chips as the kernel exposes them: `/dev/accel*` (v2-v4) or the numbered
    entries of `/dev/vfio` (v5e and later). Reference tpu.py does the same. This
    never loads libtpu, so the process that asks does not take the chip."""
    accel = glob.glob("/dev/accel*")
    if accel:
        return len(accel)
    try:
        return sum(1 for e in os.listdir("/dev/vfio") if e.isdigit())
    except OSError:
        return 0


def _chips_per_host(accel: str) -> int:
    """Chips this host contributes to the slice, derived from the accelerator type.

    v2/v3/v4/v5p name slices by TensorCore count (2 cores/chip, up to 4 chips per
    host); v5e (v5litepod) and v6e name them by chip count (1 core/chip). A
    single-host v5e/v6e slice packs up to 8 chips (v5e-8 = one 8-chip host), but
    multi-host slices are built from 4-chip hosts (v5e-16 = 4 hosts x 4 chips).
    Reference: python/ray/_private/accelerators/tpu.py:199-547.
    """
    parts = accel.split("-")
    gen = parts[0].lower()
    try:
        num = int(parts[-1])
    except ValueError:
        return 4
    if gen in ("v5e", "v5litepod", "v6e") or gen.endswith("litepod"):
        return num if num <= 8 else 4
    return min(max(num // 2, 1), 4)


class TPUAcceleratorManager:
    """Discovery + visibility for TPU chips on this host."""

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        """Chips this host can hand out. Never `jax.devices()`: this runs in the
        driver, and a chip belongs to the one process that initialises it."""
        explicit = _env("TPU_CHIPS_PER_HOST")
        if explicit:
            return int(explicit)
        # Device files first: the accelerator type names the slice the host belongs
        # to, not what this machine was given (seen: v5litepod-4 with one chip).
        attached = _count_device_files()
        if attached:
            return attached
        accel = _accelerator_type()  # e.g. "v4-16", "v5e-8"
        return _chips_per_host(accel) if accel else 0

    @staticmethod
    def get_current_node_accelerator_type() -> str | None:
        accel = _accelerator_type()
        if accel is None:
            return None
        return "TPU-" + accel.split("-")[0].upper()  # e.g. TPU-V4

    @staticmethod
    def get_current_pod_type_resource() -> str | None:
        """e.g. TPU_ACCELERATOR_TYPE=v4-16 -> 'TPU-v4-16'."""
        accel = _accelerator_type()
        if accel is None:
            return None
        return f"TPU-{accel}"

    @staticmethod
    def get_worker_id() -> int:
        return int(_env("TPU_WORKER_ID") or 0)

    @staticmethod
    def get_slice_name() -> str | None:
        return _env("TPU_NAME")

    @staticmethod
    def is_slice_head() -> bool:
        return TPUAcceleratorManager.get_worker_id() == 0

    @staticmethod
    def chip_bounds(n_chips: int, chips_on_host: int) -> str | None:
        """`TPU_CHIPS_PER_HOST_BOUNDS` for a process granted `n_chips` of a host's
        chips; None when it is granted all of them and libtpu's defaults hold.
        Raises for a count libtpu has no topology for (three of four, say)."""
        if n_chips == chips_on_host:
            return None
        bounds = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}.get(n_chips)
        if bounds is None or n_chips > chips_on_host:
            raise ValueError(
                f"cannot pin a process to {n_chips} of {chips_on_host} TPU chips: "
                "ask for 1, 2, 4 or all of a host's chips")
        return bounds

    @staticmethod
    def set_visible_chips(chip_ids: list[int], env: dict, *, chips_on_host: int) -> None:
        """Pin the process that gets `env` to `chip_ids` (reference tpu.py
        set_current_process_visible_accelerator_ids). A process granted every chip
        keeps the host's environment as it is."""
        bounds = TPUAcceleratorManager.chip_bounds(len(chip_ids), chips_on_host)
        if bounds is None:
            return
        env["TPU_VISIBLE_CHIPS"] = ",".join(str(c) for c in chip_ids)
        env["TPU_CHIPS_PER_HOST_BOUNDS"] = bounds
        env["TPU_HOST_BOUNDS"] = "1,1,1"

    @staticmethod
    def node_resources() -> dict[str, float]:
        """All TPU-related resources this host should advertise."""
        n = TPUAcceleratorManager.get_current_node_num_accelerators()
        if n <= 0:
            return {}
        resources: dict[str, float] = {"TPU": float(n)}
        pod_type = TPUAcceleratorManager.get_current_pod_type_resource()
        if pod_type:
            resources[pod_type] = 1.0
            if TPUAcceleratorManager.is_slice_head():
                resources[f"{pod_type}-head"] = 1.0
        slice_name = TPUAcceleratorManager.get_slice_name()
        if slice_name:
            resources[f"TPU-{slice_name}"] = 1.0
        return resources


def detect_accelerator_resources(num_tpus: int | None = None) -> dict[str, float]:
    """Resources to advertise for the local node; num_tpus overrides discovery."""
    if num_tpus is not None:
        res = {"TPU": float(num_tpus)} if num_tpus else {}
        pod_type = TPUAcceleratorManager.get_current_pod_type_resource()
        if num_tpus and pod_type:
            res[pod_type] = 1.0
            if TPUAcceleratorManager.is_slice_head():
                res[f"{pod_type}-head"] = 1.0
        return res
    return TPUAcceleratorManager.node_resources()


def reserve_tpu_slice(pod_type: str):
    """Create a placement group that atomically reserves one TPU slice.

    Parity: reference tpu.py:131-197 reserve_tpu_slice/fetch_tpu_slice_name_from_pg —
    a STRICT_PACK bundle on the slice-head resource gates the whole slice.
    """
    from ray_tpu.util.placement_group import placement_group

    return placement_group(
        bundles=[{f"{pod_type}-head": 1.0}], strategy="STRICT_PACK", name=f"slice-{pod_type}"
    )
