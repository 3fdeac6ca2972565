"""Compile-cache layer: seconds the program spent tracing its functions to
jaxprs and lowering them to modules during set-up (compile ledger, phases
``trace`` + ``lower``): paid on every run, whatever the cache holds."""

from benchmarks.program_counters import setup_compiles


def read(run):
    setup = setup_compiles()
    return None if setup is None else setup["seconds"]["trace"] + setup["seconds"]["lower"]
