"""Latent attention layer: device time per step of the three flash kernels
at 192 | 128, the labels of ``breakdown`` holding ``hvd_flash_`` (among the
reduction's ten longest: exact while all three are); a program whose trace
has no such label gives nothing."""

from benchmarks.mla_cost import FLASH_LABELS, seconds_per_step


def read(run):
    seconds = seconds_per_step(run["trace"], FLASH_LABELS)
    return None if seconds is None else seconds * 1e3
