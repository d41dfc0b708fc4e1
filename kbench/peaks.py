"""Peak rates of each device kind, as JAX names it in ``device_kind``."""
from __future__ import annotations

#: device_kind -> peaks of ONE chip.
PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,             # bf16 FLOP/s
        "hbm_bytes_per_s": 819e9,    # HBM bandwidth
        "hbm_bytes": 16e9,           # HBM capacity
        "source": "Google Cloud documentation, 'TPU v5e' system "
                  "architecture page",
    },
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"has {sorted(PEAKS)}") from None
