"""Published peaks of the chips the benchmark may run on, by JAX's
``device_kind``.  A device that is not in the table is an error, never
a default (copied from ``bench.py DEVICE_PEAKS``; see PERF.md, Open
questions, for deleting the original)."""

DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    # HBM, 16 GB per chip
    "TPU v5 lite": {"bf16_flops": 197.0e12, "hbm_bytes_s": 819.0e9,
                    "hbm_bytes": 16.0e9},
}


def device_peaks(device_kind: str) -> dict:
    if device_kind not in DEVICE_PEAKS:
        raise RuntimeError(
            f"no published peaks for device_kind {device_kind!r}: add it "
            "to benchmarks/harness/peaks.py with its source")
    return DEVICE_PEAKS[device_kind]


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take for ``flops`` operations over
    ``nbytes`` bytes, and which of the two binds."""
    t_f = flops / peaks["bf16_flops"]
    t_b = nbytes / peaks["hbm_bytes_s"]
    return max(t_f, t_b), "flops" if t_f >= t_b else "bytes"
