"""The device check and the table of published chip peaks.

A run needs an accelerator: with no TPU, or fewer chips than the cell asks
for, ``require_chips`` raises before anything is built, and there is no
fallback to the CPU.
"""
from __future__ import annotations

from typing import Dict, List

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
PEAKS_SOURCE = (
    'Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16, '
    "393 TOP/s int8, 16 GB HBM at 819 GB/s"
)
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def require_chips(n: int) -> List:
    """The first ``n`` TPU devices; raises :class:`NoAccelerator` otherwise."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX found platform {platform!r}")
    if len(devices) < n:
        raise NoAccelerator(f"needs {n} chips; JAX found {len(devices)}")
    return devices[:n]


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def describe(devices: List) -> Dict:
    """The ``device`` object of the result line (without trace fields)."""
    d0 = devices[0]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
