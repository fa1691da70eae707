"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

A device that is not in the table is an error, not a default: a share of a
peak is only worth printing against the right peak.
"""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
#: 197 TFLOP/s bf16, 819 GB/s HBM2e, 16 GB per chip. JAX names the chip
#: "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}

#: What a ``--rehearse`` run on a CPU divides by so that the same code walks
#: to the end. Not a peak of anything; a rehearsal's numbers go nowhere.
REHEARSAL = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 1e10, "source": "rehearsal placeholder"}


def lookup(device_kind: str, rehearse: bool = False) -> dict:
    if device_kind in PEAKS:
        return PEAKS[device_kind]
    if rehearse:
        return REHEARSAL
    raise KeyError(
        f"no published peak for device_kind {device_kind!r}: add it to "
        "benchmarks/harness/peaks.py with its source")
