"""Published peaks, keyed by ``device_kind``.  A device that is not in
the table is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB of HBM2e at 819 GB/s,
    # 197 TFLOP/s in bf16 (not used: this system runs no model, its
    # kernels are gather / scatter / segment-min passes, bound by memory)
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; the table has {sorted(PEAKS)}")
    return PEAKS[device_kind][what]
