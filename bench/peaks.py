"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least seconds the chip needs for ``flops`` bf16 operations and
    ``nbytes`` of HBM traffic: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def peak_bytes() -> int:
    """Peak bytes in use on the fullest device so far (0 where the
    backend keeps no statistics, as the CPU does)."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    return max((s or {}).get("peak_bytes_in_use", 0) for s in stats)
