"""Mamba-2 mixer layer: device time per step of the chunked state-space scan
alone, the labels of ``breakdown`` holding ``hvd_ssd`` (among the reduction's
ten longest: a lower bound)."""

from benchmarks.ssd_cost import SSD_LABELS, seconds_per_step


def read(run):
    seconds = seconds_per_step(run["trace"], SSD_LABELS)
    return None if seconds is None else seconds * 1e3
