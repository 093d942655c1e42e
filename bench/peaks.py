"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.

Copied from the repository's ``launch/roofline.py`` so that a later change
to the program cannot move the yardstick. A kind that is not here has no
roofline: :func:`chip_peaks` raises rather than borrow another chip's.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float           # FLOP/s
    hbm_bytes: float            # B
    hbm_bw: float               # B/s
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bytes=16e9, hbm_bw=819e9,
        source='Google Cloud documentation, "TPU v5e"'),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
