"""Published peaks per device kind, as JAX names the kind."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str) -> dict:
    """The kind's peaks; a kind that is not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add them to bench/harness/peaks.py") from None
