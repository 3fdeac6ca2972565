"""Compile-cache layer: seconds of real compilation during set-up (compile
ledger, phase ``backend`` less the ``cache_load`` seconds inside it): cache
misses, and the programs under JAX's one-second caching threshold."""

from benchmarks.program_counters import setup_compiles


def read(run):
    setup = setup_compiles()
    return None if setup is None else setup["seconds"]["backend"] - setup["seconds"]["cache_load"]
