"""TPU accelerator manager — chip detection, topology, visibility, slices.

Reference: python/ray/_private/accelerators/tpu.py:345 (TPUAcceleratorManager):
resource name "TPU", TPU_VISIBLE_CHIPS, GCE-metadata topology detection
(tpu.py:125), pod-type inference (tpu.py:204). Here TPU is first-class: the
scheduler, worker pool and placement groups all understand chips and
pod-slice head resources natively.
"""

from __future__ import annotations

import glob
import logging
import os
import re
from typing import Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

TPU_RESOURCE_NAME = "TPU"
TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
# GKE/GCE env hints (reference tpu.py: TPU_ACCELERATOR_TYPE / metadata server)
TPU_ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"  # e.g. "v5litepod-16"
TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
TPU_NAME_ENV = "TPU_NAME"
TPU_TOPOLOGY_ENV = "TPU_TOPOLOGY"  # e.g. "4x4"
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
# per-process share of a host's chips (libtpu): chips -> x,y,z bounds
TPU_CHIPS_PER_PROCESS_BOUNDS_ENV = "TPU_CHIPS_PER_PROCESS_BOUNDS"
TPU_PROCESS_BOUNDS_ENV = "TPU_PROCESS_BOUNDS"
_SUBSET_BOUNDS: Dict[int, str] = {1: "1,1,1", 2: "1,2,1"}
# test/dev override
FAKE_TPU_CHIPS_ENV = "RAY_TPU_FAKE_CHIPS"

# generation -> chips per host (single-host VM); reference tpu.py pod-type math
_CHIPS_PER_HOST: Dict[str, int] = {
    "v2": 4,
    "v3": 4,
    "v4": 4,
    "v5litepod": 4,
    "v5p": 4,
    "v6e": 4,
    "v7x": 4,
}

# accelerator-type string constants (reference:
# python/ray/util/accelerators/accelerators.py:32-38)
TPU_V2 = "TPU-V2"
TPU_V3 = "TPU-V3"
TPU_V4 = "TPU-V4"
TPU_V5E = "TPU-V5E"
TPU_V5P = "TPU-V5P"
TPU_V6E = "TPU-V6E"
TPU_V7X = "TPU-V7X"


def _detect_chips_from_devfs() -> int:
    """Count TPU chips from /dev (accel or vfio), like the reference's
    _get_current_node_tpu_chips (tpu.py)."""
    for pattern in ("/dev/accel*", "/dev/vfio/*"):
        paths = [p for p in glob.glob(pattern) if not p.endswith("vfio")]
        if paths:
            return len(paths)
    return 0


def jax_backend_is_up() -> bool:
    """Whether this process has initialised a JAX backend — asked without
    importing JAX or initialising one."""
    import sys

    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def _detect_chips_from_jax() -> int:
    """Last-resort detection via an initialized jax runtime — only if a
    backend ALREADY exists. jax.devices() on a cold runtime would
    initialize the TPU backend here, inside resource detection: slow at
    best, and it would make the detecting process (driver or raylet) the
    chip's owner, so no worker could have it."""
    if not jax_backend_is_up():
        return 0  # never trigger init here
    import jax

    try:
        return len([d for d in jax.devices() if "tpu" in d.platform.lower() or "TPU" in str(d)])
    except Exception:
        return 0


def pin_jax_platforms(platforms: str) -> None:
    """Hold this process's JAX to ``platforms`` (the JAX_PLATFORMS
    syntax; "" = JAX's own choice). The environment covers a JAX not yet
    imported, the config a JAX that is (the zygote imports it before the
    fork). A backend that already exists is not undone by this."""
    import sys

    os.environ["JAX_PLATFORMS"] = platforms
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", platforms or None)


def parse_pod_type(accelerator_type: str) -> Tuple[str, int]:
    """'v5litepod-16' -> ('v5litepod', 16 chips)."""
    m = re.match(r"^(v\d+[a-z]*(?:pod)?)-(\d+)$", accelerator_type)
    if not m:
        raise ValueError(f"Unrecognized TPU accelerator type: {accelerator_type}")
    return m.group(1), int(m.group(2))


def pod_type_to_ray_accelerator_type(accelerator_type: str) -> str:
    gen = parse_pod_type(accelerator_type)[0]
    return {
        "v2": TPU_V2,
        "v3": TPU_V3,
        "v4": TPU_V4,
        "v5litepod": TPU_V5E,
        "v5p": TPU_V5P,
        "v6e": TPU_V6E,
        "v7x": TPU_V7X,
    }.get(gen, f"TPU-{gen.upper()}")


def num_hosts_in_slice(accelerator_type: str) -> int:
    gen, chips = parse_pod_type(accelerator_type)
    per_host = _CHIPS_PER_HOST.get(gen, 4)
    return max(1, chips // per_host)


def slice_head_resource_name(accelerator_type: str) -> str:
    """The whole-slice gang resource, e.g. 'TPU-v5litepod-16-head'
    (reference: tpu.py — TPU-{pod_type}-head used by SlicePlacementGroup)."""
    return f"TPU-{accelerator_type}-head"


class TPUAcceleratorManager:
    @staticmethod
    def get_resource_name() -> str:
        return TPU_RESOURCE_NAME

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return TPU_VISIBLE_CHIPS_ENV

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        fake = os.environ.get(FAKE_TPU_CHIPS_ENV)
        if fake:
            return int(fake)
        n = _detect_chips_from_devfs()
        if n:
            return n
        return _detect_chips_from_jax()

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        at = os.environ.get(TPU_ACCELERATOR_TYPE_ENV)
        if at:
            try:
                return pod_type_to_ray_accelerator_type(at)
            except ValueError:
                return None
        return None

    @staticmethod
    def get_current_process_visible_accelerator_ids() -> Optional[List[str]]:
        v = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
        if v is None:
            return None
        if v == "":
            return []
        return v.split(",")

    @staticmethod
    def set_current_process_visible_accelerator_ids(ids: List[str]) -> None:
        os.environ[TPU_VISIBLE_CHIPS_ENV] = ",".join(str(i) for i in ids)
        os.environ["TPU_VISIBLE_DEVICES"] = os.environ[TPU_VISIBLE_CHIPS_ENV]
        # A process given SOME of its host's chips must also be told the
        # shape of its share, or libtpu waits for the whole host's
        # topology. A whole-host lease keeps the host's own description.
        bounds = _SUBSET_BOUNDS.get(len(ids))
        total = TPUAcceleratorManager.get_current_node_num_accelerators()
        if bounds is not None and len(ids) < total:
            os.environ[TPU_CHIPS_PER_PROCESS_BOUNDS_ENV] = bounds
            os.environ[TPU_PROCESS_BOUNDS_ENV] = "1,1,1"
        else:
            os.environ.pop(TPU_CHIPS_PER_PROCESS_BOUNDS_ENV, None)
            os.environ.pop(TPU_PROCESS_BOUNDS_ENV, None)

    @staticmethod
    def validate_resource_request_quantity(quantity: float) -> Tuple[bool, str]:
        if quantity != int(quantity):
            return False, "TPU resource quantity must be whole chips"
        return True, ""

    @staticmethod
    def get_current_node_additional_resources() -> Dict[str, float]:
        """Expose the slice-head resource on worker 0 of a pod slice
        (reference: tpu.py — only worker 0 advertises TPU-{pod}-head)."""
        out: Dict[str, float] = {}
        at = os.environ.get(TPU_ACCELERATOR_TYPE_ENV)
        if not at:
            return out
        worker_id = os.environ.get(TPU_WORKER_ID_ENV)
        try:
            if worker_id is None or worker_id == "0":
                out[slice_head_resource_name(at)] = 1.0
            out[f"accelerator_type:{pod_type_to_ray_accelerator_type(at)}"] = 1.0
        except ValueError:
            logger.warning("Unrecognized TPU_ACCELERATOR_TYPE=%r; ignoring", at)
        return out
