"""Compile-cache layer: programs the backend compiled during set-up instead of
loading them from the cache (compile ledger: ``backend`` entries less
``cache_load`` entries)."""

from benchmarks.program_counters import setup_compiles


def read(run):
    setup = setup_compiles()
    return None if setup is None else float(setup["count"]["backend"] - setup["count"]["cache_load"])
