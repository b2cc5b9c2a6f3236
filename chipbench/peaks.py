"""Published peaks of the accelerators the benchmark accepts, keyed by
``jax.Device.device_kind``.  A kind that is not in the table is an error:
there is no default and no CPU entry."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per-chip specifications)",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for any other kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in the peaks table "
                       f"(known: {sorted(PEAKS)})") from None
