"""Published per-chip peaks, for the "% of roof" figures.

The table and its accessor alone: where a step's device time goes is read by
``hvd.metrics.profile_step`` (``metrics/device_profile.py``).
"""

from __future__ import annotations

# Published per-chip peaks for the "% of roof" columns, keyed by
# ``jax.devices()[0].device_kind``. Source: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM). A device that is not in the
# table is an error, not a default: the roofs of one chip say nothing about
# another.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbs": 819.0, "bf16_tflops": 197.0},
}


def device_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; raises on a kind the table
    does not know."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add it to "
            "horovod_tpu.utils.roofline.DEVICE_PEAKS with its source"
        ) from None
