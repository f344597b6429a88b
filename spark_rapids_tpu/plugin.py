"""Plugin shell: process lifecycle for the TPU engine.

Rebuild of Plugin.scala (SURVEY §2.1: RapidsDriverPlugin :282 /
RapidsExecutorPlugin :348): one idempotent initialization that
a) acquires the device and sizes the HBM batch budget from conf
   (GpuDeviceManager.initializeGpuAndMemory, :150),
b) initializes the concurrency semaphore,
c) installs the fatal-error contract: an unrecoverable device error
   logs diagnostics and (configurably) exits the process so an external
   supervisor replaces the worker (Plugin.scala:518-541 exit-code
   behavior).
"""

from __future__ import annotations

import logging
import os
import threading
from dataclasses import dataclass
from typing import List, Optional

from .conf import (CONCURRENT_TASKS, DEVICE_MEMORY_FRACTION,
                   DEVICE_MEMORY_LIMIT, SrtConf, active_conf, conf)

log = logging.getLogger("spark_rapids_tpu")

# exit codes mirroring the reference's fatal-error contract
EXIT_FATAL_DEVICE_ERROR = 20


@dataclass
class DeviceInfo:
    platform: str
    device_kind: str
    num_local_devices: int
    hbm_bytes: int


_STATE = {"initialized": False, "info": None}
_LOCK = threading.Lock()


def initialize(conf_obj: Optional[SrtConf] = None) -> DeviceInfo:
    """Idempotent executor-side init (RapidsExecutorPlugin.init)."""
    with _LOCK:
        if _STATE["initialized"]:
            return _STATE["info"]
        c = conf_obj or active_conf()
        import jax
        devices = jax.devices()
        dev = devices[0]
        # HBM budget: explicit poolSize, else allocFraction of device
        from .memory.budget import device_hbm_bytes, reset_device_budget
        hbm = device_hbm_bytes(dev)
        limit = c.get(DEVICE_MEMORY_LIMIT)
        if limit <= 0:
            limit = int(hbm * c.get(DEVICE_MEMORY_FRACTION))
        reset_device_budget(limit)
        # concurrency semaphore warms up from conf
        from .exec.base import device_semaphore
        device_semaphore()
        info = DeviceInfo(platform=dev.platform,
                          device_kind=dev.device_kind,
                          num_local_devices=len(devices),
                          hbm_bytes=hbm)
        from .shims import load_extra_plugins
        _STATE["extra_plugins"] = load_extra_plugins(conf_obj
                                                     or active_conf())
        _STATE["initialized"] = True
        _STATE["info"] = info
        log.info("spark_rapids_tpu initialized: %s", info)
        return info


def shutdown() -> None:
    with _LOCK:
        from .memory.spill import _CATALOG
        if _CATALOG is not None:
            n = _CATALOG.log_leaks()
            if n:
                log.warning("%d spillable batches leaked (enable "
                            "srt.memory.leakDetection.enabled for "
                            "creation stacks)", n)
        _STATE["initialized"] = False
        _STATE["info"] = None


class FatalDeviceError(RuntimeError):
    """Unrecoverable accelerator failure (CudaFatalException role)."""


def handle_fatal_error(exc: BaseException,
                       exit_process: bool = False) -> None:
    """Log diagnostics and optionally exit so the cluster manager
    replaces this worker (Plugin.scala:518-541: the executor must NOT
    keep running on a wedged device)."""
    log.error("FATAL device error: %s", exc, exc_info=exc)
    try:
        import jax
        for d in jax.devices():
            log.error("device %s stats: %s", d,
                      getattr(d, "memory_stats", lambda: None)())
    except Exception:
        pass
    if exit_process:
        os._exit(EXIT_FATAL_DEVICE_ERROR)


def is_fatal(exc: BaseException) -> bool:
    """Classify accelerator errors the way the reference classifies
    CudaFatalException vs retryable OOMs."""
    from .memory.budget import OutOfDeviceMemory
    if isinstance(exc, OutOfDeviceMemory):
        return False
    text = str(exc).lower()
    return any(s in text for s in ("internal: ", "device halt",
                                   "data loss", "hardware"))
