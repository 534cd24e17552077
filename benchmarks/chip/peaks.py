"""Published peaks of the chips the benchmark runs on, keyed by `device_kind`.

A device that is not in the table is an error, never a default: a roofline
share or an MFU is only as true as the peak it divides by.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float        # FLOP/s, dense bf16 matrix units
    hbm_bytes_per_s: float   # bytes/s
    source: str


PEAKS: dict[str, Peak] = {
    # JAX reports a v5e chip as "TPU v5 lite".
    "TPU v5 lite": Peak(
        bf16_flops=197e12, hbm_bytes_per_s=819e9,
        source="Google Cloud documentation, 'TPU v5e' (per chip)"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
