"""Device discovery, selection, and initialization guards.

Reference: GpuDeviceManager.scala:150 (initializeGpuAndMemory — device
acquisition, RMM pool sizing, spill-store bootstrap) and the executor
plugin's init-time environment guards (Plugin.scala:314-388: compute
capability check, cudf version check, fatal-error exit).  The TPU redesign:
PJRT owns allocation, so "pool sizing" becomes computing the spill catalog's
HBM budget from the backend's reported memory; device selection takes the
TPU, or another platform only when it was asked for by name, and pins all
uploads to one chip.
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

log = logging.getLogger("spark_rapids_tpu")

__all__ = ["DeviceManager", "DeviceInfo", "device_memory_bytes"]

# the CPU test platform reports no memory stats; budgets there are sized
# against this figure.  A TPU reports its own limit or initialization fails.
_CPU_ASSUMED_BYTES = 8 << 30


def device_memory_bytes(dev) -> int:
    """Bytes of device memory the spill budget is a fraction of."""
    if dev.platform == "cpu":
        return _CPU_ASSUMED_BYTES
    stats = dev.memory_stats() or {}
    total = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if not total:
        raise RuntimeError(
            f"{dev} reports no bytes_limit in memory_stats() "
            f"(got {sorted(stats)}): cannot size the device spill budget")
    return int(total)


class DeviceInfo:
    def __init__(self, device, platform: str, memory_bytes: int):
        self.device = device
        self.platform = platform
        self.memory_bytes = memory_bytes

    def __repr__(self):
        return (f"DeviceInfo({self.device}, {self.platform}, "
                f"{self.memory_bytes / (1 << 30):.1f} GiB)")


class DeviceManager:
    """Process-wide device acquisition + init checks (one chip per session,
    mirroring the reference's one-GPU-per-executor model,
    Plugin.scala:355-357)."""

    _lock = threading.Lock()
    _info: Optional[DeviceInfo] = None

    @classmethod
    def initialize(cls, conf) -> DeviceInfo:
        with cls._lock:
            if cls._info is not None:
                return cls._info
            import jax
            # persistent executable cache: compiled programs survive
            # restarts.
            # Routed through the warm-start subsystem: the dir is probed
            # for writability, and an unusable path emits
            # warmstore_errors_total{kind=cache_dir} instead of the
            # fleet silently proceeding cold
            from .warmstore import setup_jax_cache
            setup_jax_cache(conf)
            requested = conf["spark.rapids.tpu.device.platform"]
            dev = cls._select_device(jax, requested)
            cls._check_environment(jax)
            mem = device_memory_bytes(dev)
            cls._info = DeviceInfo(dev, dev.platform, mem)
            frac = conf["spark.rapids.tpu.memory.tpu.poolFraction"]
            log.info("device initialized: %s (spill budget %.1f GiB)",
                     cls._info, mem * frac / (1 << 30))
            return cls._info

    @staticmethod
    def _select_device(jax, requested: str):
        """The conf's platform when set, else the TPU.  Another platform
        is taken only when ``JAX_PLATFORMS`` names it: with the variable
        unset JAX falls back to the CPU when the TPU client fails to
        start, and a broken chip must not turn into a slow green run."""
        if requested:
            devs = jax.devices(requested)
            if not devs:
                raise RuntimeError(
                    f"no devices for requested platform {requested!r}")
            return devs[0]
        devs = jax.devices()
        for d in devs:
            if d.platform == "tpu":
                return d
        named = [p.strip() for p in
                 (jax.config.jax_platforms or "").split(",")]
        if devs[0].platform in named:
            return devs[0]
        raise RuntimeError(
            f"no TPU found: JAX offers {len(devs)} "
            f"{devs[0].platform!r} device(s) ({devs[0].device_kind}). "
            f"The {devs[0].platform} backend is used only when "
            f"JAX_PLATFORMS or spark.rapids.tpu.device.platform names it")

    @staticmethod
    def _check_environment(jax) -> None:
        """Init-time guards (Plugin.scala:323-352 analog): x64 must be on
        (FLOAT64/INT64 column parity) or results silently degrade."""
        if not jax.config.read("jax_enable_x64"):
            raise RuntimeError(
                "jax_enable_x64 is off — import spark_rapids_tpu before "
                "touching jax, or set JAX_ENABLE_X64=1 "
                "(64-bit columns would silently truncate)")

    @classmethod
    def info(cls) -> Optional[DeviceInfo]:
        return cls._info

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            cls._info = None
