"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default.

Source for TPU v5e: Google Cloud documentation, "TPU v5e" (system
architecture page): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2
at 819 GB/s, 1,600 Gbit/s inter-chip interconnect.  JAX names the chip
"TPU v5 lite".
"""

from __future__ import annotations

SOURCE_V5E = "Google Cloud documentation, TPU v5e system architecture"

_V5E = {
    "bf16_flops": 197e12,       # FLOP/s, bf16 matmul
    "int8_ops": 393e12,         # OP/s, int8 matmul
    "hbm_bytes_per_s": 819e9,   # HBM bandwidth, bytes/s
    "hbm_bytes": 16e9,          # HBM capacity, bytes
    "ici_bits_per_s": 1.6e12,   # chip-to-chip interconnect, bits/s
    "source": SOURCE_V5E,
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> dict:
    """The peak table of ``device_kind``; raises KeyError when unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py with "
                       f"their source") from None


def roofline_s(flops: float, nbytes: float, device_kind: str):
    """Least time the chip could take for ``flops`` operations moving
    ``nbytes`` bytes, and which bound sets it ("compute" or "memory")."""
    p = peaks(device_kind)
    t_c = flops / p["bf16_flops"]
    t_m = nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
