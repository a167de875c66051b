"""Published peaks by exact `device_kind` (copied from
geomx_tpu/telemetry/roofline.DEVICE_PEAKS so that no later PR can move
the yardstick).  "TPU v5 lite": Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.  A device that is not listed is
an error, never a default."""

DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peaks(device_kind: str) -> dict:
    if device_kind not in DEVICE_PEAKS:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"benchmark/peaks.py lists {sorted(DEVICE_PEAKS)}")
    return DEVICE_PEAKS[device_kind]
