"""The one table of device peaks, keyed by `device_kind` as JAX reports
it. A device that is not here is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture): one
    # chip has 197 TFLOP/s in bf16, 16 GB of HBM2e at 819 GB/s.
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add "
            f"a sourced row to benchmark/chip/peaks.py (known: "
            f"{sorted(PEAKS)})") from None
