"""Published peaks of each device kind, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (one chip)",
    },
}


class UnknownDevice(KeyError):
    """No published peaks for this ``device_kind``."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
