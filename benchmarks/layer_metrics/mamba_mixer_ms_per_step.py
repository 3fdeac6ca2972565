"""Mamba-2 mixer layer: device time per step under the labels of
``breakdown`` that name the mixer (``hvd_mamba_*``: projections, convolution,
gated norm; ``hvd_ssd_*``: the scan). The reduction keeps its ten longest
labels, so this is a lower bound, exact while the mixer's are among them; a
program without these scopes gives nothing."""

from benchmarks.ssd_cost import MIXER_LABELS, seconds_per_step


def read(run):
    seconds = seconds_per_step(run["trace"], MIXER_LABELS)
    return None if seconds is None else seconds * 1e3
