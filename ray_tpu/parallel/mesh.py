"""Device-mesh construction and TPU slice topology.

The TPU-native replacement for the reference's collective *group* runtime
(python/ray/util/collective/collective.py): instead of constructing an NCCL
communicator object at runtime, parallelism is expressed by (a) building a
`jax.sharding.Mesh` whose axes map onto the ICI torus, and (b) compiling
programs whose collectives (psum/ppermute/all_to_all) ride those axes. Mesh
axes, in canonical order:

    ("data", "fsdp", "expert", "pipeline", "sequence", "tensor")

"tensor" is innermost so tensor-parallel collectives use the
fastest/nearest ICI links; "data" is outermost so pure-DP gradient
reductions tolerate DCN hops in multi-slice deployments (scaling-book
mesh-ordering recipe).

Slice topology detection mirrors the reference's TPU accelerator manager
(python/ray/_private/accelerators/tpu.py:75 TPUAcceleratorManager): TPU env
vars / GCE metadata name the slice and its chip count; a v4-16 slice shows up
as a gang-schedulable unit with one `TPU-<gen>-head` bundle.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

AXIS_ORDER = ("data", "fsdp", "expert", "pipeline", "sequence", "tensor")


@dataclass(frozen=True)
class MeshConfig:
    """Sizes for each parallelism axis; -1 on `data` means "the rest"."""

    data: int = -1
    fsdp: int = 1
    expert: int = 1
    pipeline: int = 1
    sequence: int = 1
    tensor: int = 1

    def axis_sizes(self, n_devices: int) -> Dict[str, int]:
        sizes = {"data": self.data, "fsdp": self.fsdp, "expert": self.expert,
                 "pipeline": self.pipeline, "sequence": self.sequence,
                 "tensor": self.tensor}
        fixed = math.prod(v for v in sizes.values() if v > 0)
        n_auto = sum(1 for v in sizes.values() if v <= 0)
        if n_auto > 1:
            raise ValueError("at most one axis may be -1")
        if n_auto == 1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}")
            auto = n_devices // fixed
            sizes = {k: (auto if v <= 0 else v) for k, v in sizes.items()}
        total = math.prod(sizes.values())
        if total > n_devices:
            raise ValueError(
                f"mesh axes {sizes} need {total} devices, have {n_devices}")
        return sizes

    @classmethod
    def from_dict(cls, d: Dict[str, int]) -> "MeshConfig":
        unknown = set(d) - set(AXIS_ORDER)
        if unknown:
            raise ValueError(f"unknown mesh axes {sorted(unknown)}")
        return cls(**{k: d[k] for k in AXIS_ORDER if k in d})


def build_mesh(config: Optional[MeshConfig] = None,
               devices: Optional[Sequence] = None,
               axis_sizes: Optional[Dict[str, int]] = None):
    """Build a Mesh with the canonical axis order.

    Axes of size 1 are kept (harmless; PartitionSpecs may reference them
    uniformly), so one strategy's specs work on any mesh shape.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if axis_sizes is None:
        config = config or MeshConfig()
        axis_sizes = config.axis_sizes(n)
    import numpy as np
    shape = tuple(axis_sizes[a] for a in AXIS_ORDER)
    # A config whose axis product is smaller than the device count uses the
    # first prod(shape) devices (e.g. a pipeline=4 experiment on an
    # 8-device host). Warn: silent under-subscription would hide a 4x
    # throughput loss from a mis-sized axis.
    used = math.prod(shape)
    if used < n:
        import logging
        logging.getLogger(__name__).warning(
            "mesh axes %s use %d of %d devices; the rest are idle",
            dict(axis_sizes), used, n)
    dev_array = np.asarray(devices[:used]).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def fake_mesh(n_devices: int = 8, **axis_sizes):
    """CPU mesh with virtual devices for tests/CI (the `_fake_gpus` analogue).

    Must be called before any other JAX backend initialization in the
    process; see tests/conftest.py.
    """
    import jax
    cpus = [d for d in jax.devices() if d.platform == "cpu"]
    if len(cpus) < n_devices:
        raise RuntimeError(
            f"need {n_devices} CPU devices; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_devices} before "
            f"importing jax")
    cfg = MeshConfig(**axis_sizes) if axis_sizes else None
    return build_mesh(cfg, cpus[:n_devices])


# ---------------------------------------------------------------------------
# Slice topology (scheduler-facing; no jax import needed)
# ---------------------------------------------------------------------------

# chips per host for each generation (reference tpu.py:37 consts).
CHIPS_PER_HOST = {"v2": 4, "v3": 4, "v4": 4, "v5e": 8, "v5p": 4, "v6e": 8}

# Node label under which a host advertises its slice fault domain. All
# hosts of one ICI domain share the value; the GCS groups them into one
# gang for drain/recovery (a preempted host kills the whole slice).
SLICE_LABEL = "ray_tpu.io/slice"
# Node label for the DCN locality domain (pod / cloud zone): slices in
# one zone talk over the same data-center network fabric, so replacement
# domains in the SAME zone are preferred when a gang (or a compiled DAG
# pinned to it) migrates off a preempted slice.
ZONE_LABEL = "ray_tpu.io/zone"
# Real accelerator-type strings use pod aliases (v5e-16 => "v5litepod-16").
GEN_ALIASES = {"v5litepod": "v5e", "v6litepod": "v6e"}


@dataclass
class SliceInfo:
    name: str                 # e.g. "v4-16" or "" for single host
    generation: str = ""      # v4 / v5e / ...
    num_chips: int = 0        # chips in the whole slice
    num_hosts: int = 1
    chips_per_host: int = 4
    worker_id: int = 0        # this host's index within the slice
    topology: str = ""        # e.g. "2x2x2"

    def head_resource(self) -> str:
        """Resource that exists only on host 0 of the slice, used to
        gang-schedule one coordinator per slice (reference
        tpu.py `TPU-<type>-head` pattern)."""
        return f"TPU-{self.name}-head" if self.name else "TPU-head"


def get_slice_info() -> SliceInfo:
    """Detect the TPU slice this host belongs to from standard TPU env vars
    (set on TPU VMs by the runtime; reference reads GCE metadata the same
    way, tpu.py:52)."""
    accel_type = os.environ.get("TPU_ACCELERATOR_TYPE", "")  # e.g. v4-16
    worker_id = int(os.environ.get("TPU_WORKER_ID", "0") or 0)
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    topology = os.environ.get("TPU_TOPOLOGY", "")
    gen = accel_type.split("-")[0] if accel_type else ""
    gen = GEN_ALIASES.get(gen, gen)
    cph = CHIPS_PER_HOST.get(gen, 4)
    if accel_type:
        try:
            total = int(accel_type.split("-")[1])
        except (IndexError, ValueError):
            total = cph
        # v2/v3 accelerator counts are cores (2/chip); v4+ are chips.
        chips = total // 2 if gen in ("v2", "v3") else total
        hosts = max(1, len(hostnames.split(","))) if hostnames \
            else max(1, chips // cph)
        return SliceInfo(name=accel_type, generation=gen, num_chips=chips,
                         num_hosts=hosts, chips_per_host=cph,
                         worker_id=worker_id, topology=topology)
    return SliceInfo(name="", generation=gen, chips_per_host=cph,
                     worker_id=worker_id, topology=topology)


def detect_slice_id(labels: Optional[Dict[str, str]] = None) -> str:
    """Fault-domain key for this host — unique PER SLICE, shared by every
    host of one ICI domain, "" when the host is not part of a gang.

    Precedence: an explicit `ray_tpu.io/slice` label (tests,
    heterogeneous deployments), then the TPU resource name from the
    runtime (`TPU_NAME`, suffixed with `MEGASCALE_SLICE_ID` so each slice
    of a multislice job is its own domain), then a fingerprint of
    `TPU_WORKER_HOSTNAMES` (identical on every host of one slice,
    distinct across slices). The accelerator type alone
    (`SliceInfo.name`, e.g. "v4-16") is deliberately NOT a fallback: two
    independent slices of the same type would merge into one fault
    domain and a single-host preemption would gang-drain both."""
    explicit = (labels or {}).get(SLICE_LABEL, "")
    if explicit:
        return explicit
    tpu_name = os.environ.get("TPU_NAME", "")
    ms_slice = os.environ.get("MEGASCALE_SLICE_ID", "")
    if tpu_name:
        return f"{tpu_name}/{ms_slice}" if ms_slice else tpu_name
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if hostnames and "," in hostnames:
        import hashlib
        digest = hashlib.sha1(hostnames.encode()).hexdigest()[:12]
        return f"hosts:{digest}"
    return ""


def detect_zone(labels: Optional[Dict[str, str]] = None) -> str:
    """DCN locality key for this host — shared by every slice in one
    pod/zone, "" when unknown. Precedence: an explicit
    `ray_tpu.io/zone` label (tests, heterogeneous deployments), then
    the cloud runtime's zone env (`RAY_TPU_ZONE`, `CLOUD_ZONE`,
    `TPU_ZONE`). Multi-slice DCN topology awareness: gang recovery and
    compiled-DAG migration prefer replacement domains in the SAME zone,
    so cross-slice traffic stays on the local fabric."""
    explicit = (labels or {}).get(ZONE_LABEL, "")
    if explicit:
        return explicit
    for env in ("RAY_TPU_ZONE", "CLOUD_ZONE", "TPU_ZONE"):
        v = os.environ.get(env, "")
        if v:
            return v
    return ""


def slice_bundles(slice_info: SliceInfo) -> List[Dict[str, float]]:
    """Placement-group bundles that gang-reserve a whole slice: one bundle
    per host, chips_per_host TPU each; bundle 0 additionally carries the
    slice-head resource (reference: BackendExecutor's TPU pod scheduling)."""
    per_host = float(min(slice_info.chips_per_host,
                         slice_info.num_chips or slice_info.chips_per_host))
    bundles = []
    for i in range(slice_info.num_hosts):
        b = {"TPU": per_host}
        if i == 0:
            b[slice_info.head_resource()] = 1.0
        bundles.append(b)
    return bundles
