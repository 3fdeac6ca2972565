"""Compile-cache layer: host clock around the first call of the step (trace,
lower, and compile or load from the cache, plus one step)."""


def read(run):
    return run["step_compile_s"]
