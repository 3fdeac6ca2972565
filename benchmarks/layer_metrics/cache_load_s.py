"""Compile-cache layer: seconds spent loading executables from the persistent
compilation cache during set-up (compile ledger, phase ``cache_load``)."""

from benchmarks.program_counters import setup_compiles


def read(run):
    setup = setup_compiles()
    return None if setup is None else setup["seconds"]["cache_load"]
