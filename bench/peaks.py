"""Published peaks of each accelerator, keyed by ``device_kind`` as JAX
reports it.  A kind that is not in the table is an error, not a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "source": "Google Cloud documentation, TPU v5e",
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"have {sorted(PEAKS)}")
    return PEAKS[device_kind]
