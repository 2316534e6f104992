"""Published peaks of the chips this benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device without a row is an error, never a
default (copied from ``tenzing_tpu/bench/roofline.py`` ``PEAKS`` so that a
later PR to the program cannot move the yardstick)."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 819 GB/s HBM per chip",
    },
}


class UnknownDeviceError(RuntimeError):
    """No row in :data:`PEAKS` for the device kind."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device kind {device_kind!r} "
            f"(benchmarks/harness/peaks.py has {sorted(PEAKS)})") from None
