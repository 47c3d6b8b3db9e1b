"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports. A device that is not in the table is an
error: a share of a peak needs the peak of the chip it ran on.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB of HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16 MXU peak
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    """The peak table entry for `device_kind`; raises UnknownDevice."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """Least time the chip could take for the work, and which bound
    sets it: ("compute" | "memory", seconds)."""
    t_c = flops / peaks["flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return ("compute", t_c) if t_c >= t_m else ("memory", t_m)
