"""Models layer: sigmoid gate values a token on the output of the latest
traced gated softmax attention layer (gauge ``horovod_attn_gate_width``, set
at trace time): heads x head_dim where the gate is one an element (1,024 at 8
held heads of 128), the heads where it is one a head. A program without the
gauge, or one that traced no gated layer, gives nothing."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_attn_gate_width") or None
