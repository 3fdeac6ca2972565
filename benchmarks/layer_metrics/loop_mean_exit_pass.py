"""Loop layer: the mean pass after which a token exits, ``sum_t t x mass[t]``
over the gauges ``horovod_loop_exit_mass{loop_pass="t"}`` (the mean ``p_t`` over
the tokens of the window's last steps, which the configuration's step writes
and ``metrics.overlap.record_loop_exit_mass`` publishes), t up to
``horovod_loop_passes``: between 1 and the number of passes; a gate that died
reads 1.0 (every token leaves after the first pass) or the number of passes
(none leaves early). It is data, not a time: ``better`` says only which way
a live gate moves it. A program without the gauges gives nothing."""

from benchmarks.program_counters import gauge


def read(run):
    passes = range(1, int(gauge("horovod_loop_passes") or 0) + 1)
    mass = [gauge(f'horovod_loop_exit_mass{{loop_pass="{t}"}}') for t in passes]
    if not mass or None in mass or not sum(mass):
        return None
    return sum(t * m for t, m in zip(passes, mass)) / sum(mass)
