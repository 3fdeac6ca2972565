"""Adaptive compression policy — per tensor, per fabric tier (ISSUE 9).

``HOROVOD_COMPRESSION=adaptive`` hands the wire-format choice to this
controller instead of one global knob. The insight from PowerSGD (Vogels
et al., 2019) is that *which* compressor
wins is tensor- and bandwidth-dependent: the ICI/intra-host fabric is
rarely the bottleneck (full width is free there), while the DCN/cross-pod
hop is the scaling cliff — worth paying topk's select/merge cost for a
~100x byte cut on large gradients, and at least a 16-bit cast on the rest.

Two kinds of decision live here, with deliberately different safety rules:

- **Value-changing** decisions (which format quantizes/sparsifies the
  tensor at enqueue) are a *deterministic* function of (size, dtype,
  fabric topology, config). Every rank evaluates the same inputs, so the
  cross-rank wire-format agreement the coordinator validates
  ("Mismatched wire compression") holds by construction — no negotiation
  round is spent on policy.
- **Value-neutral** decisions (whether a topk hop frames its payload
  sparse or dense on a given tier) may react to *live metrics* freely:
  both framings carry identical f32 values (compression.py frame
  contract), so ranks can disagree without any correctness consequence.
  :meth:`CompressionPolicy.refresh` reads the per-tier wire-byte counters
  and the critical-path wire-seconds gauges (docs/tracing.md) and moves
  the sparse framing to wherever the wire time actually is.

The per-tier decision table (docs/compression.md has the full story):

    tier  | tensor                                   | format
    ------+------------------------------------------+-------
    any   | non-float, <=2-byte, < min_bytes          | none
    ici   | everything else                           | none  (full width)
    dcn   | float32 >= HOROVOD_TOPK_MIN_BYTES         | topk
    dcn   | other floats >= min_bytes                 | bf16
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .config import _env_int
from ..compression import (
    DEFAULT_TOPK_RATIO,
    topk_eligible,
    topk_ratio_from_env,
)

# Below this dense size the sparse frame's select/merge overhead outweighs
# the byte cut even on DCN; the 16-bit cast still pays.
DEFAULT_TOPK_MIN_BYTES = 1 << 16

# Canonical tier spellings: the eager planes tag links "local"/"cross",
# the compiled plane and the docs say "ici"/"dcn".
TIER_ALIASES = {"local": "ici", "ici": "ici", "cross": "dcn", "dcn": "dcn"}


class CompressionPolicy:
    """The HOROVOD_COMPRESSION=adaptive controller.

    ``decide`` is the per-(tensor, tier) table; ``resolve`` collapses it to
    the single value-changing format the eager engine applies at enqueue
    (the decision for the most aggressive fabric the topology actually
    crosses); ``sparse_tiers``/``refresh`` steer the value-neutral hop
    framing from live telemetry."""

    def __init__(self, config=None, topo=None) -> None:
        self.min_bytes = int(getattr(config, "compression_min_bytes", 4096)
                             or 4096)
        self.topk_ratio = float(getattr(config, "topk_ratio", 0.0)
                                or topk_ratio_from_env(DEFAULT_TOPK_RATIO))
        self.topk_min_bytes = max(
            self.min_bytes,
            _env_int("HOROVOD_TOPK_MIN_BYTES", DEFAULT_TOPK_MIN_BYTES))
        # Does this world cross a host boundary at all? Single-host worlds
        # have no DCN hop, so adaptive resolves to full width everywhere.
        self.has_dcn = bool(topo is None or getattr(topo, "cross_size", 1) > 1)
        # Where topk frames ship sparse (value-neutral; see module doc).
        # DCN by default — loopback links move dense f32 faster than they
        # select/merge — until refresh() sees the wire time move.
        self._sparse_tiers = {"cross"}
        self._diag: dict = {}

    # -- the deterministic table (value-changing: must agree across ranks)

    def decide(self, nbytes: int, dtype, tier: str) -> str:
        """Wire format for a tensor of ``nbytes``/``dtype`` on ``tier``."""
        dtype = np.dtype(dtype)
        if dtype.kind != "f" or dtype.itemsize <= 2 \
                or nbytes < self.min_bytes:
            return "none"
        if TIER_ALIASES.get(tier, "dcn") == "ici":
            return "none"
        if nbytes >= self.topk_min_bytes and topk_eligible(
                dtype, nbytes, self.topk_ratio, self.min_bytes):
            return "topk"
        return "bf16"

    def resolve(self, nbytes: int, dtype) -> str:
        """The single value-changing format the eager engine quantizes a
        tensor to: the decision for the slowest fabric its bytes will
        cross. (A topk tensor still frames DENSE on tiers whose decision
        is 'none' — that is the value-neutral half, see sparse_tiers.)"""
        return self.decide(nbytes, dtype, "dcn" if self.has_dcn else "ici")

    # -- live-metrics half (value-neutral)

    def sparse_tiers(self) -> frozenset:
        """Link tiers ('local'/'cross') where topk hops frame sparse."""
        return frozenset(self._sparse_tiers)

    def refresh(self, snapshot: dict) -> dict:
        """Re-read the live per-tier wire telemetry and steer the sparse
        framing. Input is a metrics-registry snapshot; reads
        ``horovod_wire_bytes_total{tier=...}`` counters and the
        ``horovod_critical_path_wire_seconds{tier=...}`` gauges the tracing
        analyzer exports. Returns (and stores) the diagnosis dict."""
        counters = snapshot.get("counters", {}) or {}
        gauges = snapshot.get("gauges", {}) or {}

        def tier(series: dict, name: str, t: str) -> float:
            return float(series.get(f'{name}{{tier="{t}"}}', 0) or 0)

        local_b = tier(counters, "horovod_wire_bytes_total", "local")
        cross_b = tier(counters, "horovod_wire_bytes_total", "cross")
        local_s = tier(gauges, "horovod_critical_path_wire_seconds", "local")
        cross_s = tier(gauges, "horovod_critical_path_wire_seconds", "cross")
        # Which fabric is the wire time on? Critical-path seconds when the
        # analyzer ran; byte share as the fallback signal.
        if local_s or cross_s:
            bottleneck = "dcn" if cross_s >= local_s else "ici"
        elif local_b or cross_b:
            bottleneck = "dcn" if cross_b >= local_b else "ici"
        else:
            bottleneck = "dcn" if self.has_dcn else "ici"
        tiers = {"cross"}
        if bottleneck == "ici" and (local_s > 0 or local_b > 0):
            # The local fabric is where the wire time is (shared-core CI
            # boxes, oversubscribed hosts): sparse-frame it too — value-
            # neutral, so ranks may flip this at different moments.
            tiers.add("local")
        self._sparse_tiers = tiers
        self._diag = {
            "bottleneck_tier": bottleneck,
            "wire_bytes": {"local": local_b, "cross": cross_b},
            "wire_seconds": {"local": local_s, "cross": cross_s},
            "sparse_tiers": sorted(tiers),
        }
        return dict(self._diag)

    # -- reporting (cache_stats / smoke assertions / docs)

    def report(self, nbytes: int = 1 << 22,
               dtype=np.float32) -> dict:
        """The policy table for a representative large gradient plus the
        live diagnosis — what ``cache_stats()['policy']`` and the sparse
        smoke read to prove the tiers resolve differently."""
        return {
            "ici": self.decide(nbytes, dtype, "ici"),
            "dcn": self.decide(nbytes, dtype, "dcn"),
            "resolved": self.resolve(nbytes, dtype),
            "topk_ratio": self.topk_ratio,
            "has_dcn": self.has_dcn,
            "sparse_tiers": sorted(self._sparse_tiers),
            "diag": dict(self._diag),
        }


#: The dense format the compiled plane substitutes when the adaptive
#: table answers 'topk' for a fused bucket. This substitution is BY
#: DESIGN, not a gap (ISSUE 16 closes the ROADMAP open question): XLA
#: collectives have static shapes, so a runtime-sparse frame is
#: structurally unservable there — the nearest value-reducing format on
#: the same tier is the bf16 cast, and `adaptive` promises "the policy's
#: best SERVABLE format per tier", not "identical bytes to eager".
#: `horovod_compiled_adaptive_fallback_total` keeps counting the
#: substituting traces purely for observability.
COMPILED_TOPK_SUBSTITUTE = "bf16"


def compiled_tier_format(nbytes: int, dtype, tier: str,
                         with_fallback: bool = False):
    """The compiled plane's per-bucket tier resolve (ISSUE 13 satellite):
    the SAME value-changing table the eager engines evaluate per tensor,
    applied to one fused bucket on one fabric tier, with the 'topk'
    answer substituted by :data:`COMPILED_TOPK_SUBSTITUTE` — see its note
    for why that substitution is the designed behaviour. Returns the
    servable format NAME ('none'/'bf16'), or ``(format, substituted)``
    when ``with_fallback`` so the caller can count substituting traces.
    Evaluated at trace time only."""
    fmt = CompressionPolicy().decide(int(nbytes), dtype, tier)
    substituted = fmt == "topk"
    if substituted:
        fmt = COMPILED_TOPK_SUBSTITUTE
    return (fmt, substituted) if with_fallback else fmt


def resolve_format(compression: Optional[str], policy,
                   nbytes: int, dtype) -> str:
    """One-stop eager-side resolution: an explicit HOROVOD_COMPRESSION name
    passes through; 'adaptive' consults the policy. Returns a concrete
    format name ('none'/'fp16'/'bf16'/'topk')."""
    from ..compression import normalize

    name = normalize(compression)
    if name != "adaptive":
        return name
    if policy is None:
        return "none"
    return policy.resolve(nbytes, dtype)
