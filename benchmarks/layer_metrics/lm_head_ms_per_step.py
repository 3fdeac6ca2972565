"""Models layer: device time per step of the MAIN head's vocabulary products
(``hvd_lm_head``: the ``lm_head`` product where the model returns logits, the
chunked loss's loop - logits, softmax cross-entropy, d-hidden, d-kernel - where
the step calls ``chunked_lm_loss``; a multi-token-prediction module's pass goes
by ``hvd_mtp``), by the program's own names from the whole trace
(``benchmarks/named_device_time.py``); 0.0 where the window never ran them,
nothing for a program that does not know the name."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_lm_head")
