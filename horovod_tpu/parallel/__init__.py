"""horovod_tpu.parallel — meshes, in-jit collectives, fusion, pipelining,
ZeRO-sharded data parallelism (``sharded.py``)."""

from .pipeline import (  # noqa: F401
    last_stage_value,
    masked_last_stage_loss,
    pipeline_apply,
    stack_stage_params,
)
