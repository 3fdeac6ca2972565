"""Latent attention layer: device time per step under the labels of
``breakdown`` that name the mixer (``hvd_mla_proj``: four projections and the
latent's norm; ``hvd_mla_rope``: split, rotary, assembling q and k;
``hvd_flash_*``: the kernels). The reduction keeps its ten longest labels, so
this is a lower bound; a program without these names gives nothing."""

from benchmarks.mla_cost import MLA_LABELS, seconds_per_step


def read(run):
    seconds = seconds_per_step(run["trace"], MLA_LABELS)
    return None if seconds is None else seconds * 1e3
