"""Selected attention layer: (query, key) pairs the layers selected a step
(gauge ``horovod_dsa_selected_pairs_per_step``, which the configuration's step
feeds from what each layer sows, through ``metrics.record_dsa_census``: from
the data) over the causal pairs of the same layers and rows (from shapes,
``benchmarks/dsa_cost.py``): 0.234 for 2,048 of a 16,384-token row. A program
without the gauge gives nothing."""

from benchmarks.program_counters import gauge


def read(run):
    pairs = gauge("horovod_dsa_selected_pairs_per_step")
    causal = (run["cost"].get("dsa_pairs") or {}).get("causal")
    return pairs / causal if pairs and causal else None
