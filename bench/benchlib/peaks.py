"""Peak rates of the chips the benchmark may run on, keyed by the
``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture
table): 197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip, 16 GB
of HBM.  A device kind that is not in the table is an error: a share of
a peak is never computed against a guessed peak.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks_for(device_kind: str) -> dict:
    """Peak rates of ``device_kind``; raises KeyError for a kind that the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
