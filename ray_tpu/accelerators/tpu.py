"""TPU accelerator manager: detection, topology model, chip isolation.

Reference parity: python/ray/_private/accelerators/tpu.py —
TPUAcceleratorManager (:267): chip detection via /dev/accel* or /dev/vfio
(:294-313), resource name "TPU" (:271), valid chip counts {1,2,4,8} (:17,363),
TPU_VISIBLE_CHIPS + TPU_CHIPS_PER_HOST_BOUNDS/TPU_HOST_BOUNDS sub-host
isolation (:377-417), GKE env / GCE metadata pod discovery (:420-527), slice
resources {tpu_name: 1} on every slice worker + "TPU-{pod}-head" on worker 0
(:576-639), node labels ray.io/tpu-* (:641-672), type/topology tables v2-v6e
(:65,88-102) and chips-per-host rules (:135-148,184-210).
"""

from __future__ import annotations

import glob
import logging
import os

logger = logging.getLogger(__name__)

RESOURCE_NAME = "TPU"
NUM_TPUS_PER_HOST_DEFAULT = 4
VALID_CHIP_COUNTS = (1, 2, 4, 8)

# generation -> cores per chip (v4/v5p have 2 cores/chip megacore'd; v5e/v6e 1)
GENERATION_CORES_PER_CHIP = {
    "v2": 2,
    "v3": 2,
    "v4": 2,
    "v5p": 2,
    "v5litepod": 1,
    "v5e": 1,
    "v6e": 1,
}

# accelerator type -> list of valid topology strings (subset; reference
# tpu.py:88-102 keeps similar tables)
VALID_TOPOLOGIES = {
    "v2": {"2x2", "4x4", "4x8", "8x8", "8x16", "16x16"},
    "v3": {"2x2", "4x4", "4x8", "8x8", "8x16", "16x16", "16x32", "32x32"},
    "v4": {"2x2x1", "2x2x2", "2x2x4", "2x4x4", "4x4x4", "4x4x8", "4x8x8", "8x8x8", "8x8x16"},
    "v5p": {"2x2x1", "2x2x2", "2x2x4", "2x4x4", "4x4x4", "4x4x8", "4x8x8", "8x8x8", "8x16x16"},
    "v5litepod": {"1x1", "2x2", "2x4", "4x4", "4x8", "8x8", "8x16", "16x16"},
    "v6e": {"1x1", "2x2", "2x4", "4x4", "4x8", "8x8", "8x16", "16x16"},
}


def _chips_from_topology(topology: str) -> int:
    n = 1
    for part in topology.lower().split("x"):
        n *= int(part)
    return n


def pod_type_chip_count(pod_type: str) -> int:
    """'v5litepod-64' -> 64 cores -> chips depend on generation."""
    gen, _, cores = pod_type.partition("-")
    cores = int(cores)
    cpc = GENERATION_CORES_PER_CHIP.get(gen, 1)
    return max(cores // cpc, 1)


def chips_per_host(pod_type: str, topology: str | None = None) -> int:
    """Hosts have 4 chips except single-host slices and 8-chip v5e/v6e hosts
    (reference rules: tpu.py:135-148,184-210)."""
    gen = pod_type.partition("-")[0]
    total = pod_type_chip_count(pod_type)
    if total <= 4:
        return total
    if gen in ("v5litepod", "v6e") and total == 8:
        return 8
    return NUM_TPUS_PER_HOST_DEFAULT


def num_hosts(pod_type: str, topology: str | None = None) -> int:
    total = pod_type_chip_count(pod_type)
    return max(total // chips_per_host(pod_type, topology), 1)


class TPUAcceleratorManager:
    """Per-node TPU detection + worker-env isolation."""

    @staticmethod
    def get_resource_name() -> str:
        return RESOURCE_NAME

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        """Chips attached to this host. A TPU VM exposes one device node
        per chip: ``/dev/accel<N>`` on older images, ``/dev/vfio/<N>`` on
        the v5e hosts this repo runs on (seen on the chip machine: one
        numbered node, ``/dev/vfio/3``, beside the ``/dev/vfio/vfio``
        control node — the number is the host's, not a chip index)."""
        env = os.environ.get("RT_NUM_TPUS")
        if env is not None:
            return int(env)
        n = len(glob.glob("/dev/accel*"))
        if n == 0:
            n = len(glob.glob("/dev/vfio/[0-9]*"))
        return n

    @staticmethod
    def get_current_node_accelerator_type() -> str | None:
        # GKE sets these; GCE metadata would be queried on real TPU VMs
        accel = os.environ.get("TPU_ACCELERATOR_TYPE")
        if accel:
            return accel
        return None

    @staticmethod
    def validate_resource_request_quantity(quantity: float) -> tuple[bool, str | None]:
        if quantity not in VALID_CHIP_COUNTS:
            return (
                False,
                f"TPU request must be one of {VALID_CHIP_COUNTS} (got {quantity}): "
                "sub-host slices must align to chip-bounds",
            )
        return True, None

    @classmethod
    def set_current_process_visible_accelerators(cls, chip_ids: list):
        """Isolation env for the current process (reference: tpu.py:377-417)."""
        os.environ.update(cls.worker_env_for_chips(chip_ids))

    @staticmethod
    def worker_env_for_chips(chip_ids: list[int]) -> dict:
        n = len(chip_ids)
        # JAX_PLATFORMS=tpu: a worker that was given chips and cannot open
        # them fails; it never runs on the CPU backend in their place
        env = {"TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chip_ids), "JAX_PLATFORMS": "tpu"}
        if n == 1:
            env["TPU_CHIPS_PER_HOST_BOUNDS"] = "1,1,1"
            env["TPU_HOST_BOUNDS"] = "1,1,1"
        elif n == 2:
            env["TPU_CHIPS_PER_HOST_BOUNDS"] = "1,2,1"
            env["TPU_HOST_BOUNDS"] = "1,1,1"
        elif n == 4:
            env["TPU_CHIPS_PER_HOST_BOUNDS"] = "2,2,1"
            env["TPU_HOST_BOUNDS"] = "1,1,1"
        return env

    # ---- slice discovery (env-driven; GCE metadata on real pods) ----
    @staticmethod
    def get_current_node_tpu_pod_type() -> str | None:
        accel = os.environ.get("TPU_ACCELERATOR_TYPE")  # e.g. "v5litepod-16"
        return accel

    @staticmethod
    def get_current_node_tpu_name() -> str | None:
        return os.environ.get("TPU_NAME")

    @staticmethod
    def get_current_node_tpu_worker_id() -> int | None:
        wid = os.environ.get("TPU_WORKER_ID")
        return int(wid) if wid is not None else None

    @staticmethod
    def get_current_node_tpu_topology() -> str | None:
        return os.environ.get("TPU_TOPOLOGY")

    @classmethod
    def get_current_node_additional_resources(cls) -> dict:
        """Per-slice gang-scheduling resources (reference: tpu.py:576-639)."""
        out = {}
        name = cls.get_current_node_tpu_name()
        pod = cls.get_current_node_tpu_pod_type()
        wid = cls.get_current_node_tpu_worker_id()
        if name:
            out[name] = 1.0
        if pod and wid == 0:
            out[f"TPU-{pod}-head"] = 1.0
        return out

    @classmethod
    def get_current_node_labels(cls) -> dict:
        out = {}
        name = cls.get_current_node_tpu_name()
        if name:
            out["ray_tpu.io/tpu-slice-name"] = name
        wid = cls.get_current_node_tpu_worker_id()
        if wid is not None:
            out["ray_tpu.io/tpu-worker-id"] = str(wid)
        topo = cls.get_current_node_tpu_topology()
        if topo:
            out["ray_tpu.io/tpu-topology"] = topo
        pod = cls.get_current_node_tpu_pod_type()
        if pod:
            out["ray_tpu.io/tpu-pod-type"] = pod
        return out
