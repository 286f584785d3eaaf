"""Published peaks of each accelerator the benchmark may run on, keyed by
``jax.Device.device_kind``. A device that is not in the table is an error:
a roofline or utilization share against a guessed peak means nothing."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
    "TPU v5 lite": {
        "int8_ops_s": 393e12,
        "bf16_flops_s": 197e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU documentation, TPU v5e",
    },
}


class UnknownDevice(LookupError):
    """The device kind has no entry in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
