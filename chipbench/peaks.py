"""Published peaks of each device, keyed by ``device_kind`` as JAX reports it.

A device that is not in the table is an error: a roofline share against a
guessed peak is not a measurement.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # FLOP/s
    hbm_bytes_s: float  # bytes/s
    hbm_bytes: int  # bytes of device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12,
        hbm_bytes_s=819e9,
        hbm_bytes=16 * 10**9,
        source="Google Cloud documentation, 'TPU v5e' system architecture",
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}") from None


def roofline_share(flops: float, nbytes: float, seconds: float, peaks: Peaks) -> float | None:
    """Least time the chip could take for the work, as a % of ``seconds``.
    None where nothing was timed."""
    if seconds <= 0:
        return None
    least = max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_s)
    return 100.0 * least / seconds
