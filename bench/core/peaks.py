"""Published peaks of each device kind, keyed as JAX reports `device_kind`.

A kind that is not here is an error: no metric is read against a guess.
"""

from __future__ import annotations

_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "819 GB/s HBM per chip"}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def lookup(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
