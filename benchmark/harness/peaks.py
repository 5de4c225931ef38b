"""Peaks of the chips the benchmark knows, keyed by ``device_kind``.

A device that is not here is an error, never a default: a roofline share
against the wrong peak is a wrong number.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB of HBM2e at 819 GB/s,
    # 197 TFLOP/s bf16 (the SQL kernels are bandwidth-bound: only the
    # bandwidth is used here)
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(
            f"no peak {what!r} for device kind {device_kind!r} in "
            f"benchmark/harness/peaks.py: add the chip with its source"
        ) from None
