"""Runtime knob controller — live retuning on both planes (ISSUE 16).

The paper's promise is "as fast as the hardware allows" with zero per-job
tuning effort, but until now the 5-dimensional knob space (fusion
threshold, buckets, wire dtype, hierarchical ladder, mesh shape) only paid
off after an *offline* ``jax/autotune.tune`` run, and the serving plane's
SLO knobs were static while the anomaly detector watched them drift. This
package closes the loop: a per-job controller consumes the deterministic
sensor stream the repo already emits — ``horovod_critical_path_wire_seconds
{tier}``, straggler attribution, anomaly firings — and re-tunes
value-affecting knobs mid-job, one change at a time, through primitives
that already exist:

- **Safe switch**: every training-plane change lands atomically on all
  ranks via the coordinator's knob epoch (``PyEngine.set_knobs``) — the
  demote/re-promote machinery of ISSUE 8 generalized from "plane" to "any
  value-affecting knob". Interrupted collectives replay bitwise under
  their old format; later steps quantize under the new one.
- **Canary**: each change is measured for K steps against the pre-change
  throughput baseline and ROLLED BACK on regression
  (:class:`~horovod_tpu.control.core.ControlLoop`).
- **Warm start**: proposals for the continuous knobs come from the same
  GP/EI acquisition the offline autotuner uses
  (:class:`~horovod_tpu.jax.autotune.OnlineTuner`), optionally seeded
  from an offline ``TuneReport``.
- **Explainability**: every decision is a flight-ring event + trace span,
  so ``python -m horovod_tpu.tracing.bundle`` explains every retune.

``HOROVOD_CONTROLLER=1`` arms the serving-side controller in the routers
(serving/server.py, serving/llm/server.py); the training-side controller
is constructed explicitly (tools/controller_smoke.py) because it needs
the job's step loop.
"""

from .core import ControlLoop, Knob, Proposal
from .serving import ServingController, maybe_start_serving_controller
from .training import TrainingController

__all__ = [
    "ControlLoop",
    "Knob",
    "Proposal",
    "ServingController",
    "TrainingController",
    "maybe_start_serving_controller",
]
