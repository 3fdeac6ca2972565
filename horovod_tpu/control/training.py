"""Training-plane runtime controller (ISSUE 16 tentpole).

One per job (rank 0 drives it; the coordinator knob epoch lands every
change world-wide). Sensors, all of which the repo already emits:

- per-step throughput (the caller feeds ``on_step(steps_per_s)``);
- ``horovod_critical_path_wire_seconds{tier}`` — where the wire time is;
- ``horovod_straggler_seconds`` / ``horovod_straggler_rank`` (PRs 6/7);
- anomaly firings (``wire_drift``, ``demotion_storm``) via
  ``AnomalyDetector.subscribe``.

Actuators, all of which already exist:

- **engine knobs** (wire dtype, top-k ratio) through
  ``PyEngine.set_knobs`` — the coordinator knob epoch applies them
  atomically on all ranks, interrupted collectives replay bitwise, and
  the post-switch values are pinned to the same ``common/protocol.py``
  ``reduce_plan`` oracle as a job launched with that table;
- **compiled knobs** (fusion threshold, bucket count, hierarchical
  ladder) through a ``rejit`` callback — re-jitting IS the switch
  mechanism for trace-time constants, exactly as in ``jax/autotune``;
- **eager plane choice** through the same knob table (consumers read
  ``plane`` from the committed table).

Policy, deterministic and one change at a time (the ControlLoop canaries
each against the pre-change throughput baseline and rolls back on
regression):

1. degradation response — throughput collapses below ``baseline /
   HOROVOD_ANOMALY_FACTOR``-style factor for ``COLLAPSE_TICKS`` steps
   while the cross tier owns the wire time (or ``wire_drift`` fired):
   step the wire format DOWN the byte ladder (none -> bf16 -> fp16 ->
   topk@ratio) — the DCN tier goes sparse;
2. recovery probe — after a degradation-driven commit, periodically
   canary one step BACK UP the ladder; the canary machinery keeps the
   wider format only if throughput holds (this is what restores full
   width when a transient fault clears);
3. hill climb — otherwise, warm-started GP/EI over (fusion threshold,
   num_buckets) proposes the next continuous candidate
   (:class:`~horovod_tpu.jax.autotune.OnlineTuner`), so a cold job
   converges toward the offline-autotuned optimum without ever running
   the offline sweep.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Optional

from .core import ControlLoop, Knob

#: the wire-format byte ladder, widest first; degradation steps right
#: (fewer bytes), recovery probes step left (full width).
WIRE_LADDER = ("none", "bf16", "fp16", "topk@0.01")

#: throughput must sit below baseline/COLLAPSE_FACTOR for this many
#: consecutive on_step calls before the degradation rule fires.
COLLAPSE_TICKS = 3
COLLAPSE_FACTOR = 1.5

#: idle observations between recovery probes back up the ladder.
RECOVERY_PROBE_OBS = 8

KNOBS = {
    "compression": Knob("compression", "choice", choices=WIRE_LADDER),
    "topk_ratio": Knob("topk_ratio", "float", lo=0.001, hi=0.1),
    "fusion_threshold": Knob("fusion_threshold", "int",
                             lo=1 << 20, hi=256 << 20),
    "num_buckets": Knob("num_buckets", "int", lo=1, hi=32),
    "hierarchical": Knob("hierarchical", "bool"),
    "plane": Knob("plane", "choice", choices=("auto", "ring", "star")),
}

#: which actuator lands each knob. "mesh" (the 3-D ('batch','shard',
#: 'model') cube, ISSUE 19) only registers when the controller is built
#: with mesh_choices= — reshaping the mesh re-partitions parameters, so
#: it is strictly a rejit-class change.
ENGINE_KNOBS = frozenset({"compression", "topk_ratio", "plane"})
REJIT_KNOBS = frozenset({"fusion_threshold", "num_buckets", "hierarchical",
                         "mesh"})


def _tier(gauges: dict, name: str, t: str) -> float:
    return float(gauges.get(f'{name}{{tier="{t}"}}', 0) or 0)


class TrainingController:
    """The per-job training control loop. Drive it from the step loop:
    call :meth:`on_step` once per step (or measurement window) with the
    observed steps/s; everything else — sensing, proposing, canarying,
    committing, rolling back — happens inside."""

    def __init__(self, engine=None,
                 rejit: Optional[Callable[[dict], None]] = None,
                 canary_steps: Optional[int] = None,
                 cooldown_s: Optional[float] = None,
                 tolerance: Optional[float] = None,
                 warm_start=None,
                 anomaly=None,
                 reg=None,
                 mesh_choices=None) -> None:
        self.engine = engine
        self.rejit = rejit
        if reg is None:
            from ..metrics import registry as _registry

            reg = _registry()
        self.reg = reg
        knobs = dict(KNOBS)
        # The 3-D mesh cube as a controller-visible knob (ISSUE 19): the
        # legal shapes are job-specific (device count, divisibility of the
        # TP hidden dims), so the caller enumerates them; each is a
        # HOROVOD_MESH spec string validated by parse_mesh_spec.
        self.mesh_choices = tuple(mesh_choices) if mesh_choices else ()
        if self.mesh_choices:
            import jax as _jax

            from ..parallel.mesh import parse_mesh_spec

            for spec in self.mesh_choices:
                parse_mesh_spec(spec, _jax.device_count())
            knobs["mesh"] = Knob("mesh", "choice",
                                 choices=self.mesh_choices)
        self.loop = ControlLoop(knobs, self._apply, plane="training",
                                canary_steps=canary_steps,
                                cooldown_s=cooldown_s,
                                tolerance=tolerance, reg=reg)
        # Launch values: the engine's own table where one is attached.
        self.loop.set_current("compression", "none")
        self.loop.set_current("topk_ratio", 0.01)
        self.loop.set_current("fusion_threshold", 64 << 20)
        self.loop.set_current("num_buckets", 1)
        self.loop.set_current("hierarchical", False)
        self.loop.set_current("plane", "auto")
        if self.mesh_choices:
            cur = os.environ.get("HOROVOD_MESH", "").strip()
            if cur not in self.mesh_choices:
                cur = self.mesh_choices[0]
            self.loop.set_current("mesh", cur)
        if engine is not None:
            knobs = getattr(engine, "_knobs", None) or {}
            if knobs.get("compression") in WIRE_LADDER:
                self.loop.set_current("compression", knobs["compression"])
            if knobs.get("topk_ratio"):
                self.loop.set_current("topk_ratio", knobs["topk_ratio"])
        from ..jax.autotune import OnlineTuner

        self.tuner = OnlineTuner(seed=warm_start)
        self._low_ticks = 0
        self._anomalies: list[str] = []     # pending firings, drained per step
        self._degraded = False              # a degradation rule committed
        self._idle_obs = 0
        self._anomaly = anomaly
        if anomaly is not None:
            anomaly.subscribe(self._on_anomaly)

    # -- actuation -----------------------------------------------------------

    def _apply(self, name: str, value: Any) -> None:
        if name in ENGINE_KNOBS:
            if self.engine is not None:
                self.engine.set_knobs({name: value})
            elif self.rejit is not None:
                # Compiled-plane-only job (no eager engine): the wire
                # format is a trace-time constant there, so re-jitting is
                # the switch mechanism for it too.
                self.rejit({name: value})
            else:
                raise RuntimeError(f"no actuator attached for {name}")
        if name in REJIT_KNOBS:
            if self.rejit is None:
                raise RuntimeError(
                    f"{name} is a trace-time constant: attach a rejit "
                    "callback to retune it")
            self.rejit({name: value})

    def _on_anomaly(self, kind: str, detail: dict) -> None:
        if kind in ("wire_drift", "demotion_storm"):
            self._anomalies.append(kind)

    # -- the loop ------------------------------------------------------------

    def on_step(self, steps_per_s: float) -> Optional[str]:
        """One observation; returns "commit"/"rollback" on a canary verdict
        (None otherwise). Call from the training loop after each step or
        measurement window."""
        verdict = self.loop.observe(steps_per_s)
        if verdict == "commit":
            p = self.loop.history[-1]
            if p["knob"] in ("fusion_threshold", "num_buckets"):
                self.tuner.observe(self.loop.values["fusion_threshold"],
                                   self.loop.values["num_buckets"],
                                   self.loop.baseline or steps_per_s)
            if p["knob"] == "compression" and "degradation" in p["reason"]:
                self._degraded = True
            if p["knob"] == "compression" and "recovery" in p["reason"]:
                # Full recovery = back at the ladder's widest live format.
                if p["value"] == WIRE_LADDER[0]:
                    self._degraded = False
        if verdict == "rollback":
            p = self.loop.history[-1]
            if p["knob"] in ("fusion_threshold", "num_buckets"):
                # Teach the model the rejected point so EI moves on.
                mean = p.get("canary_mean", 0.0)
                th = p["value"] if p["knob"] == "fusion_threshold" \
                    else self.loop.values["fusion_threshold"]
                nb = p["value"] if p["knob"] == "num_buckets" \
                    else self.loop.values["num_buckets"]
                self.tuner.observe(int(th), int(nb), float(mean))
        if self.loop.in_canary:
            return verdict
        self._sense(steps_per_s)
        return verdict

    def _sense(self, steps_per_s: float) -> None:
        """Deterministic rule pass: at most one proposal."""
        baseline = self.loop.baseline or 0.0
        collapsed = baseline > 0 and \
            steps_per_s < baseline / COLLAPSE_FACTOR
        self._low_ticks = self._low_ticks + 1 if collapsed else 0
        fired = self._anomalies
        self._anomalies = []

        # Rule 1: degradation — wire time on the cross tier (or the
        # anomaly stream says the wire drifted) while throughput collapsed.
        gauges = self.reg.snapshot().get("gauges", {})
        cross_s = _tier(gauges, "horovod_critical_path_wire_seconds",
                        "cross")
        local_s = _tier(gauges, "horovod_critical_path_wire_seconds",
                        "local")
        cross_dominant = cross_s > local_s
        if (self._low_ticks >= COLLAPSE_TICKS and
                (cross_dominant or fired or not (cross_s or local_s))):
            cur = self.loop.values["compression"]
            nxt = KNOBS["compression"].step(cur, +1)
            if nxt is not None and self.loop.propose(
                    "compression", nxt,
                    f"degradation: {steps_per_s:.3g}/s vs baseline "
                    f"{baseline:.3g}/s, cross wire {cross_s:.3g}s"):
                self._low_ticks = 0
                self._idle_obs = 0
                return
        # Rule 2: recovery probe — degraded mode, throughput steady:
        # periodically canary one step back toward full width; the canary
        # keeps it only if the fault really cleared.
        self._idle_obs += 1
        if self._degraded and self._idle_obs >= RECOVERY_PROBE_OBS:
            cur = self.loop.values["compression"]
            prv = KNOBS["compression"].step(cur, -1)
            if prv is not None and self.loop.propose(
                    "compression", prv, "recovery probe toward full width"):
                self._idle_obs = 0
                return
            self._idle_obs = 0
        # Rule 3: hill climb — warm-started GP/EI over the continuous
        # knobs (only when an actuator for them is attached).
        if self.rejit is not None and not self._degraded \
                and self._idle_obs >= self.loop.canary_steps:
            self.tuner.observe(self.loop.values["fusion_threshold"],
                               self.loop.values["num_buckets"],
                               baseline or steps_per_s)
            nxt = self.tuner.suggest()
            if nxt is not None:
                th, nb = nxt
                # One knob per canary: land the bucket coordinate first —
                # a suggested threshold differs from the current value
                # almost always, so splitting threshold-first would starve
                # the bucket dimension of any spread and the joint EI
                # would never activate.
                if nb != self.loop.values["num_buckets"]:
                    name, val = "num_buckets", nb
                else:
                    name, val = "fusion_threshold", th
                if self.loop.propose(name, val,
                                     "GP/EI hill climb (warm-started)"):
                    self._idle_obs = 0

    # -- reporting -----------------------------------------------------------

    def report(self) -> dict:
        return {
            "values": dict(self.loop.values),
            "baseline": self.loop.baseline,
            "degraded": self._degraded,
            "decisions": list(self.loop.history),
        }

    def close(self) -> None:
        if self._anomaly is not None:
            try:
                self._anomaly.unsubscribe(self._on_anomaly)
            except Exception:  # noqa: BLE001
                pass


def controller_enabled() -> bool:
    """The HOROVOD_CONTROLLER master switch (off by default: the
    controller changes value-affecting knobs mid-job)."""
    return (os.environ.get("HOROVOD_CONTROLLER", "") or "0") not in (
        "0", "false", "")


__all__ = ["TrainingController", "KNOBS", "WIRE_LADDER",
           "controller_enabled"]
