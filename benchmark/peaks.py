"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

The benchmark's own table (copied from ``fedml_tpu/ops/flops.py`` so that a
later PR can change the program and not the yardstick), with HBM and
interconnect added.  Source: Google Cloud TPU documentation, system
architecture page "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s,
1,600 Gbit/s inter-chip interconnect per chip.  A kind that is not listed is
an error, never a default.
"""

from __future__ import annotations

PEAKS_BY_KIND = {
    "tpu v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud TPU documentation, 'TPU v5e' system architecture",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS_BY_KIND[str(device_kind).lower()]
    except KeyError:
        raise ValueError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            f"benchmark/peaks.py with its source (known: {sorted(PEAKS_BY_KIND)})"
        ) from None
