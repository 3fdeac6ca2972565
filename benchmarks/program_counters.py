"""What the ``program_counter`` readers share: horovod_tpu's own compile
ledger (``utils/compile_cache.compile_ledger``) cut to the run's set-up, and
the gauges of its metrics registry. Both are asked in-process, after the
window; a program that has neither gives ``None``, and so does the reader."""


def setup_compiles():
    """``{"seconds": {phase: s}, "count": {phase: n}}`` of the ledger up to
    the window's opening. Nothing compiles inside a window (the group fails),
    but in a traced run the harness lowers and compiles the step once more
    AFTER it, for the module's text: that work is the ledger's LAST entries,
    under the step's name. So the entries at the end that carry the last
    entry's ``fun_name`` are left out - as far back as they follow that
    function's first ``backend`` entry, which is the set-up's own compilation
    of the step and stays in."""
    try:
        from horovod_tpu.utils.compile_cache import compile_ledger
    except ImportError:     # a program older than the ledger
        return None
    ledger = compile_ledger()
    entries = ledger["entries"]
    if not entries:
        return None
    name = entries[-1]["fun_name"]
    first_backend = next((i for i, e in enumerate(entries) if
                          e["phase"] == "backend" and e["fun_name"] == name),
                         len(entries))
    cut = len(entries)
    while cut - 1 > first_backend and entries[cut - 1]["fun_name"] == name:
        cut -= 1
    seconds, count = dict(ledger["seconds"]), dict(ledger["count"])
    for entry in entries[cut:]:
        seconds[entry["phase"]] -= entry["seconds"]
        count[entry["phase"]] -= 1
    return {"seconds": seconds, "count": count}


def gauge(name):
    """The value of one gauge of the program's registry, or ``None``."""
    import horovod_tpu as hvd

    return hvd.metrics.registry().snapshot()["gauges"].get(name)
