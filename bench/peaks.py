"""Published peaks per chip, keyed by ``jax.Device.device_kind``. A kind
that is not here is an error, not a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,          # bf16 matrix units
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': per chip 197 "
                  "TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def least_seconds(flops: float, nbytes: float, device_kind: str,
                  chips: int = 1) -> float:
    """The least time ``chips`` chips could take for the work: the larger
    of its operations over peak FLOP/s and its bytes over peak HBM
    bandwidth."""
    p = peaks(device_kind)
    return max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"]) \
        / chips
