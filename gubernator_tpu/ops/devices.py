"""Device selection: which JAX devices the table lives on, and the
report of what it got (the `device` block of /debug/vars)."""
from __future__ import annotations

from typing import Optional, Sequence

import jax


def platform_devices(platform: Optional[str]) -> list:
    """The devices of the configured platform (GUBER_TPU_PLATFORM /
    DeviceConfig.platform); None = JAX's default backend.  A named
    platform JAX cannot bring up is an error that names it — the table
    never lands on the CPU because the chip was missing."""
    if platform is None:
        return jax.devices()
    try:
        return jax.devices(platform)
    except RuntimeError as e:
        raise RuntimeError(
            f"device platform {platform!r} was requested "
            "(GUBER_TPU_PLATFORM) but JAX found no such device: "
            f"{e}"
        ) from e


def device_info(table_devices: Sequence[jax.Device],
                platform: Optional[str]) -> dict:
    """What the table runs on, as JAX reports it — the `device` block
    of /debug/vars and the daemon's start-up log line."""
    d0 = table_devices[0]
    return {
        "platform": d0.platform,
        "device_kind": d0.device_kind,
        "device_count": len(platform_devices(platform)),
        "table_device_ids": [int(d.id) for d in table_devices],
    }
