"""Published peaks, keyed by ``device_kind`` as jax reports it.  A device
that is not here is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s
    "TPU v5 lite": {"flops_bf16": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16e9},
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind [{device_kind}]:"
                       " a share of a roofline cannot be computed")
    return PEAKS[device_kind]


def least_seconds(device_kind: str, n_bytes: float, n_flops: float) -> float:
    """The least time the chip could take for this work: the higher of
    the memory roof and the compute roof."""
    p = peaks_of(device_kind)
    return max(n_bytes / p["bytes_per_s"], n_flops / p["flops_bf16"])
