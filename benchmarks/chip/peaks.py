"""Peak rates of the chips the benchmark runs on, keyed by JAX's
`device_kind`. Source: Google Cloud documentation, "TPU v5e" (system
architecture page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at
819 GB/s per chip. A device that is not in the table is an error: no
share of a peak is ever computed against a guess."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


class UnknownDevice(KeyError):
    """The device kind has no entry in the peak table."""


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peak rates for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
