"""Entry layer: host clock around the un-fenced ``step(...)`` call, median
over the traced window's steps."""
import statistics


def read(run):
    return statistics.median(run["dispatch_s"]) * 1e3 if run["dispatch_s"] else None
