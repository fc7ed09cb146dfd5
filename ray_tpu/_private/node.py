"""Node bootstrap: assembles GCS + raylet (+ session dir) for a head or worker
node (reference: python/ray/_private/node.py, services.py).

The default topology for `init()` runs the GCS and the head raylet on the
driver's background event loop (real TCP servers, so workers and other nodes
connect identically); `cluster_utils.Cluster` adds more raylets on the same
loop to emulate multi-node clusters in one process, mirroring the reference's
`ray.cluster_utils.Cluster` test harness.
"""

from __future__ import annotations

import asyncio
import os
import time
import uuid
from typing import Dict, Optional

from ray_tpu._private import flightrec
from ray_tpu._private.config import Config
from ray_tpu._private.gcs import GcsServer
from ray_tpu._private.raylet import Raylet


def new_session_dir(config: Config) -> str:
    stamp = time.strftime("%Y%m%d-%H%M%S")
    path = os.path.join(config.session_dir_root,
                        f"session_{stamp}_{os.getpid()}_{uuid.uuid4().hex[:6]}")
    os.makedirs(os.path.join(path, "logs"), exist_ok=True)
    return path


class HeadNode:
    """GCS + head raylet living on the current asyncio loop."""

    def __init__(self, config: Config,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None,
                 object_store_memory: Optional[int] = None,
                 session_dir: str = ""):
        self.config = config
        self.session_dir = session_dir or new_session_dir(config)
        self.gcs = GcsServer(config, self.session_dir)
        self.raylet: Optional[Raylet] = None
        # Optional ray_tpu:// proxy (util/client); owned by this node's
        # lifecycle when attached (cli --client-server-port).
        self.client_server = None
        self._resources = resources
        self._labels = labels
        self._object_store_memory = object_store_memory
        # phase -> its two edges, each a flightrec.stamp(), taken by
        # start(): worker_api.init's gauge and runtime:* spans read them
        self.boot_phases: Dict[str, tuple] = {}

    async def start(self, port: int = 0) -> str:
        at_gcs = flightrec.stamp()
        gcs_address = await self.gcs.start(port=port)
        at_raylet = flightrec.stamp()
        self.raylet = Raylet(
            self.config, gcs_address, self.session_dir,
            resources=self._resources, labels=self._labels, is_head=True,
            object_store_memory=self._object_store_memory, node_name="head")
        await self.raylet.start()
        self.boot_phases = {"gcs": (at_gcs, at_raylet),
                            "raylet": (at_raylet, flightrec.stamp())}
        return gcs_address

    async def stop(self):
        if self.client_server is not None:
            try:
                await self.client_server.stop()
            except Exception:
                pass
        if self.raylet:
            await self.raylet.stop()
        await self.gcs.stop()


def detect_node_resources(num_cpus: Optional[float] = None,
                          num_tpus: Optional[float] = None,
                          resources: Optional[Dict[str, float]] = None,
                          config: Optional[Config] = None) -> Dict[str, float]:
    """Auto-detect CPU/TPU/memory resources (reference:
    python/ray/_private/accelerators/tpu.py for TPU counting)."""
    res: Dict[str, float] = dict(resources or {})
    res.setdefault("CPU", float(num_cpus if num_cpus is not None
                                else (os.cpu_count() or 1)))
    if num_tpus is not None:
        res.setdefault("TPU", float(num_tpus))
    else:
        ntpu = _detect_tpu_chips()
        if ntpu:
            res.setdefault("TPU", float(ntpu))
    try:
        import psutil
        res.setdefault("memory", float(psutil.virtual_memory().available))
    except Exception:
        res.setdefault("memory", 8 * 1024**3)
    cfg = config or Config.load()
    res.setdefault("object_store_memory", float(cfg.object_store_memory))
    return res


def _detect_tpu_chips() -> int:
    """Count local TPU chips without initializing a JAX backend.

    Mirrors TPUAcceleratorManager.get_current_node_num_accelerators
    (reference python/ray/_private/accelerators/tpu.py:75): check
    TPU_VISIBLE_CHIPS, then the chips' device nodes — /dev/accel<n> or
    /dev/vfio/<n>, by generation and driver — not jax (opening the
    backend takes the chip). No device node means no chip: 0.
    """
    vis = os.environ.get("TPU_VISIBLE_CHIPS")
    if vis:
        return len([c for c in vis.split(",") if c.strip()])
    if os.environ.get("RAY_TPU_FAKE_TPU_CHIPS"):
        return int(os.environ["RAY_TPU_FAKE_TPU_CHIPS"])
    for directory, prefix in (("/dev", "accel"), ("/dev/vfio", "")):
        try:
            entries = os.listdir(directory)
        except OSError:
            continue
        chips = [e for e in entries if e.startswith(prefix)
                 and e[len(prefix):].isdigit()]
        if chips:
            return len(chips)
    return 0
