"""Gradient compression — parity with the reference's Compression classes
(horovod/tensorflow/compression.py and horovod/torch/compression.py: the
none/fp16 pair), plus a bf16 compressor because bf16 is the TPU-native 16-bit
format (same exponent range as fp32; the MXU natively consumes it).

Usage matches the reference: ``Compression.fp16.compress(t)`` returns
``(compressed, ctx)``; ``decompress(compressed, ctx)`` restores dtype.

Since ISSUE 5 this module is also the single source of truth for the *wire
dtype* every data plane uses:

- the compiled plane (parallel/fusion.py) casts gradient buckets to the wire
  dtype around each ``psum``;
- the eager Python engine (common/engine.py) quantizes contributions and
  ring hops to it;
- the native C++ engine reads the same ``HOROVOD_COMPRESSION`` env knob
  (cc/src/engine.cc) and casts at enqueue.

The helpers here are deliberately importable WITHOUT jax (the eager engine's
workers never import a backend): jax.numpy is only
pulled in lazily by the Compressor classes, and the numpy-side wire-dtype
resolution uses ml_dtypes for bfloat16.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

# HOROVOD_COMPRESSION values -> numpy dtype *name* of the wire format.
# "topk" and "adaptive" (ISSUE 9) are first-class names but not dtype
# casts: topk ships indices+values frames (the eager engines implement it;
# the compiled plane stays dense), and adaptive is the per-tensor,
# per-fabric-tier policy in common/policy.py that resolves to one of the
# concrete formats.
WIRE_DTYPES = {"none": None, "fp16": "float16", "bf16": "bfloat16",
               "topk": None, "adaptive": None}

# Default HOROVOD_TOPK_RATIO: keep the top 1% of entries by magnitude —
# the Deep Gradient Compression operating point (Lin et al., 2018).
DEFAULT_TOPK_RATIO = 0.01


def parse_spec(name: Optional[str]) -> tuple[str, Optional[float]]:
    """Split a compression spec into ``(name, topk_ratio | None)``.

    ``"topk"`` -> ``("topk", None)`` (ratio comes from HOROVOD_TOPK_RATIO);
    ``"topk@0.05"`` -> ``("topk", 0.05)`` — the spelling the joint autotune
    uses to put the topk ratio on the categorical compression dimension.
    Anything unknown degrades to ``("none", None)``."""
    s = (name or "none").lower()
    if s.startswith("topk@"):
        try:
            ratio = float(s.split("@", 1)[1])
        except ValueError:
            return "none", None
        return ("topk", ratio) if 0.0 < ratio else ("none", None)
    return (s, None) if s in WIRE_DTYPES else ("none", None)


def normalize(name: Optional[str]) -> str:
    """Normalize a HOROVOD_COMPRESSION value; unknown values mean 'none'
    (callers warn — config parsing must never take the job down)."""
    return parse_spec(name)[0]


def topk_ratio_from_env(default: float = DEFAULT_TOPK_RATIO) -> float:
    """HOROVOD_TOPK_RATIO: fraction of entries the topk wire keeps,
    clamped to (0, 0.5] — past half the entries a sparse frame (8 bytes
    per kept element) is bigger than the dense chunk it replaces."""
    v = os.environ.get("HOROVOD_TOPK_RATIO")
    if v in (None, ""):
        return default
    try:
        ratio = float(v)
    except ValueError:
        return default
    if ratio <= 0.0:
        return default
    return min(ratio, 0.5)


def numpy_wire_dtype(compression: Optional[str],
                     dtype) -> Optional[np.dtype]:
    """The numpy dtype gradient bytes travel as, or None when compression
    is a no-op for ``dtype`` (non-float input, already at/below wire width,
    or compression 'none').

    bfloat16 resolves through ml_dtypes (numpy has no native bf16); fp16 is
    plain ``np.float16``. Only *wider* floats are compressed — casting an
    f16 tensor to bf16 would lose mantissa for zero byte savings.
    """
    name = normalize(compression)
    wire_name = WIRE_DTYPES[name]
    if wire_name is None:
        return None
    dtype = np.dtype(dtype)
    if dtype.kind != "f" or dtype.itemsize <= 2:
        return None
    if wire_name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(np.float16)


def numpy_dtype_by_name(name: str) -> np.dtype:
    """np.dtype from a wire-dtype name, routing 'bfloat16' through ml_dtypes
    (``np.dtype('bfloat16')`` raises even with ml_dtypes imported)."""
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


# ------------------------------------------------------------- top-k sparse
#
# Numpy-first (no jax import) helpers for the topk wire format (ISSUE 9):
# a gradient ships as (indices, values) of its k largest-magnitude entries;
# the un-sent remainder rides the engine's per-tensor error-feedback
# residual so no mass is lost across steps (Deep Gradient Compression).
#
# Wire frame, little-endian, self-describing so a receiver needs only the
# chunk's element count from protocol position:
#
#   kind 0 (sparse): u8 0 | u32 k | i32 idx[k] (ascending) | f32 val[k]
#   kind 1 (dense):  u8 1 | f32 val[n]
#
# The dense kind is the densify-on-overflow escape: ring hops merge
# sparse+sparse by index union, and once the union stops saving bytes the
# partial travels dense. Values are exact float32 either way — unlike the
# dtype casts above, sparsification changes WHICH entries ship, never how
# precisely — so any mix of sparse/dense hop encodings produces bitwise
# identical results (the per-tier policy depends on this).
#
# Exact zeros (including -0.0) are never selected: every shipped value is
# nonzero, which is what makes the sparse index-merge bitwise identical to
# the dense float32 fold the canonical oracles perform (x + 0.0 == x for
# every x that is not -0.0, and cancellation yields +0.0).

_F_KIND_SPARSE = 0
_F_KIND_DENSE = 1
# topk supports float32 tensors only (gradients): an i32 index + f32 value
# costs 8 bytes per kept entry vs 4 dense, so the format needs ratio < 0.5
# to pay; wider/narrower floats fall back to the dense formats.
TOPK_DTYPE = np.dtype(np.float32)


def topk_k(n: int, ratio: float) -> int:
    """Entries to keep for an n-element tensor: ratio of n, floor 1."""
    return max(1, min(int(round(n * float(ratio))), int(n)))


def topk_eligible(arr_dtype, nbytes: int, ratio: float,
                  min_bytes: int) -> bool:
    """Whether a tensor sparsifies at all: float32 only, at least
    HOROVOD_COMPRESSION_MIN_BYTES dense bytes (the floor), and a k small
    enough that the sparse frame actually beats the dense one."""
    if np.dtype(arr_dtype) != TOPK_DTYPE or nbytes < max(int(min_bytes), 1):
        return False
    n = nbytes // TOPK_DTYPE.itemsize
    return topk_k(n, ratio) * 8 + 8 < n * 4


def topk_select(flat: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic top-k selection of a flat float32 array: magnitude
    descending, ties broken toward the lower index, exact zeros never
    selected (k shrinks to the nonzero count). Returns ``(idx, val)`` with
    idx int32 ascending — the canonical selection the oracle replays."""
    flat = np.ascontiguousarray(flat, dtype=TOPK_DTYPE).ravel()
    nz = np.flatnonzero(flat)
    if nz.size > k:
        order = np.lexsort((nz, -np.abs(flat[nz])))[:k]
        nz = np.sort(nz[order])
    return nz.astype(np.int32), flat[nz]


def topk_densify(idx: np.ndarray, val: np.ndarray, n: int) -> np.ndarray:
    """Dense float32 vector of a sparse (idx, val) pair (zeros elsewhere)."""
    out = np.zeros(int(n), dtype=TOPK_DTYPE)
    if len(idx):
        out[np.asarray(idx, dtype=np.int64)] = val
    return out


def topk_sparsify(dense: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(idx, val) of a dense float32 chunk's nonzero entries, idx ascending
    (np.flatnonzero order). The hop-side inverse of :func:`topk_densify`."""
    dense = np.ascontiguousarray(dense, dtype=TOPK_DTYPE).ravel()
    idx = np.flatnonzero(dense)
    return idx.astype(np.int32), dense[idx]


def topk_pack(idx: np.ndarray, val: np.ndarray) -> np.ndarray:
    """Sparse wire frame (kind 0) as a uint8 array."""
    idx = np.ascontiguousarray(idx, dtype="<i4")
    val = np.ascontiguousarray(val, dtype="<f4")
    head = np.empty(5, dtype=np.uint8)
    head[0] = _F_KIND_SPARSE
    head[1:5] = np.frombuffer(
        np.uint32(len(idx)).astype("<u4").tobytes(), np.uint8)
    return np.concatenate([head, idx.view(np.uint8), val.view(np.uint8)])


def topk_pack_dense(dense: np.ndarray) -> np.ndarray:
    """Dense wire frame (kind 1) as a uint8 array."""
    dense = np.ascontiguousarray(dense, dtype="<f4").ravel()
    head = np.array([_F_KIND_DENSE], dtype=np.uint8)
    return np.concatenate([head, dense.view(np.uint8)])


def topk_unpack(buf, n: int) -> tuple:
    """Parse a wire frame back into a state tuple: ``("sparse", idx, val)``
    or ``("dense", arr)``. ``n`` is the chunk's element count (protocol
    position); every length is validated before any allocation trusts it."""
    if isinstance(buf, np.ndarray):
        buf = np.ascontiguousarray(buf).view(np.uint8)
    else:
        buf = np.frombuffer(buf, dtype=np.uint8)
    if buf.size < 1:
        raise ValueError("empty topk frame")
    kind = int(buf[0])
    if kind == _F_KIND_DENSE:
        body = buf[1:]
        if body.size != n * 4:
            raise ValueError(
                f"dense topk frame carries {body.size} bytes, expected {n * 4}")
        return ("dense", body.view("<f4").astype(TOPK_DTYPE, copy=False))
    if kind != _F_KIND_SPARSE:
        raise ValueError(f"unknown topk frame kind {kind}")
    if buf.size < 5:
        raise ValueError("truncated topk frame header")
    k = int(buf[1:5].view("<u4")[0])
    if k > n or buf.size != 5 + 8 * k:
        raise ValueError(
            f"sparse topk frame k={k} size={buf.size} inconsistent with n={n}")
    idx = buf[5:5 + 4 * k].view("<i4")
    val = buf[5 + 4 * k:].view("<f4").astype(TOPK_DTYPE, copy=False)
    # Authenticated frames can't be hostile (HMAC), but a protocol bug must
    # fail HERE, not as a silent scatter into the wrong offsets: indices
    # strictly ascending and in range is the frame invariant.
    if k and (int(idx[0]) < 0 or int(idx[-1]) >= n
              or (k > 1 and not (np.diff(idx) > 0).all())):
        raise ValueError("sparse topk frame indices invalid")
    return ("sparse", idx.astype(np.int32, copy=False), val)


def topk_merge(i1: np.ndarray, v1: np.ndarray, i2: np.ndarray,
               v2: np.ndarray, n: int, max_nnz: Optional[int] = None
               ) -> tuple:
    """Index-merge two sparse chunks: union of supports, values summed
    (first-argument-first, the hop's ``incoming + mine`` order) where they
    overlap. Densify-on-overflow: past ``max_nnz`` (default n/2, the byte
    break-even) the result is returned dense instead."""
    if max_nnz is None:
        max_nnz = max(int(n) // 2, 1)
    if not len(i1):
        st = ("sparse", np.asarray(i2, np.int32), np.asarray(v2, TOPK_DTYPE))
    elif not len(i2):
        st = ("sparse", np.asarray(i1, np.int32), np.asarray(v1, TOPK_DTYPE))
    else:
        idx = np.concatenate([i1, i2])
        val = np.concatenate([v1, v2]).astype(TOPK_DTYPE, copy=False)
        order = np.argsort(idx, kind="stable")  # stable: i1 entry adds first
        idx, val = idx[order], val[order]
        first = np.empty(idx.size, dtype=bool)
        first[0] = True
        np.not_equal(idx[1:], idx[:-1], out=first[1:])
        starts = np.flatnonzero(first)
        st = ("sparse", idx[starts].astype(np.int32),
              np.add.reduceat(val, starts))
    if len(st[1]) > max_nnz:
        return ("dense", topk_densify(st[1], st[2], n))
    return st


def topk_state_add(state: tuple, idx, val, n: int) -> tuple:
    """Fold one more sparse contribution ``(idx, val)`` into an accumulator
    state (``incoming + mine`` order, bitwise identical to the dense f32
    fold whichever representation the state is in)."""
    if state[0] == "dense":
        acc = np.array(state[1], dtype=TOPK_DTYPE, copy=True)
        if len(idx):
            np.add.at(acc, np.asarray(idx, dtype=np.int64), val)
        return ("dense", acc)
    return topk_merge(state[1], state[2], idx, val, n)


def topk_state_dense(state: tuple, n: int) -> np.ndarray:
    """Dense float32 view of a state tuple."""
    if state[0] == "dense":
        return np.ascontiguousarray(state[1], dtype=TOPK_DTYPE)
    return topk_densify(state[1], state[2], n)


def topk_state_slice(state: tuple, lo: int, hi: int) -> tuple:
    """Sub-chunk [lo, hi) of a state, indices re-based to the slice."""
    if state[0] == "dense":
        return ("dense", state[1][lo:hi])
    idx, val = state[1], state[2]
    lo_i = int(np.searchsorted(idx, lo, side="left"))
    hi_i = int(np.searchsorted(idx, hi, side="left"))
    return ("sparse", (idx[lo_i:hi_i] - np.int32(lo)).astype(np.int32),
            val[lo_i:hi_i])


def topk_state_scale(state: tuple, world: int) -> tuple:
    """Divide every carried value by ``world`` (the AVERAGE finish) —
    elementwise the same f32 op the dense oracle applies, so zeros stay
    +0.0 implicitly."""
    if state[0] == "dense":
        return ("dense", (state[1] / world).astype(TOPK_DTYPE, copy=False))
    return ("sparse", state[1],
            (state[2] / world).astype(TOPK_DTYPE, copy=False))


def topk_encode(state: tuple, n: int, prefer_sparse: bool = True
                ) -> np.ndarray:
    """Pick the wire frame for a state: sparse when preferred AND smaller
    than dense, else dense. Pure transport choice — both frames carry the
    identical f32 values, so per-tier preferences (sparse on DCN, dense on
    loopback) never affect the reduction result. A dense state (from an
    overflow densify or a dense-preferring upstream tier) re-sparsifies
    here when the next tier prefers sparse — value-neutral, since the
    nonzero support densifies back to the same +0.0-filled vector."""
    if prefer_sparse:
        if state[0] == "dense":
            state = ("sparse", *topk_sparsify(state[1]))
        if len(state[1]) * 8 + 5 < n * 4 + 1:
            return topk_pack(state[1], state[2])
    return topk_pack_dense(topk_state_dense(state, n))


def compiled_formats(name: Optional[str]) -> tuple[str, str]:
    """(ici, dcn) dense wire formats the COMPILED plane substitutes for the
    policy names: ``adaptive`` = full width on ICI, bf16 on the DCN psum
    (the compiled half of common/policy.py's tier table); ``topk`` = dense
    on both (XLA collectives cannot ship runtime-sparse frames — the eager
    engines own sparsification; callers warn)."""
    base = normalize(name)
    if base == "adaptive":
        return ("none", "bf16")
    if base == "topk":
        return ("none", "none")
    return (base, base)


class Compressor:
    """Interface matching the reference's Compressor staticmethod pair."""

    # HOROVOD_COMPRESSION spelling of this compressor ("none"/"fp16"/"bf16").
    name = "none"

    @staticmethod
    def compress(tensor):
        raise NotImplementedError

    @staticmethod
    def decompress(tensor, ctx):
        raise NotImplementedError


class NoneCompressor(Compressor):
    """Pass-through (reference NoneCompressor)."""

    name = "none"

    @staticmethod
    def compress(tensor):
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor


class _CastCompressor(Compressor):
    wire_dtype_name: str = ""

    @classmethod
    def _wire_dtype(cls):
        import jax.numpy as jnp

        return jnp.dtype(cls.wire_dtype_name)

    @classmethod
    def compress(cls, tensor):
        import jax.numpy as jnp

        dtype = tensor.dtype
        wire = cls._wire_dtype()
        if jnp.issubdtype(dtype, jnp.floating) and dtype != wire:
            return tensor.astype(wire), dtype
        return tensor, None

    @classmethod
    def decompress(cls, tensor, ctx):
        return tensor.astype(ctx) if ctx is not None else tensor


class FP16Compressor(_CastCompressor):
    """Cast float tensors to fp16 for the wire (reference FP16Compressor)."""

    name = "fp16"
    wire_dtype_name = "float16"


class BF16Compressor(_CastCompressor):
    """Cast float tensors to bf16 — preferred on TPU: halves ICI/DCN bytes
    with fp32 exponent range, so no loss-scaling is needed."""

    name = "bf16"
    wire_dtype_name = "bfloat16"


class TopKCompressor(Compressor):
    """Top-k sparsification (ISSUE 9). The actual select/pack/merge lives in
    the eager engines (common/engine.py) where frames are a runtime
    concept; as a jax-level Compressor this is the identity — the compiled
    plane ships dense (XLA collectives have static shapes) and
    ``fused_allreduce`` warns when asked to sparsify."""

    name = "topk"

    @staticmethod
    def compress(tensor):
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor


class AdaptiveCompressor(Compressor):
    """HOROVOD_COMPRESSION=adaptive: the per-tensor, per-fabric-tier policy
    (common/policy.py) picks {none, bf16/fp16, topk} at runtime. Identity
    at the jax level; the compiled plane substitutes the policy's dense
    tier table (full width on ICI, bf16 on the DCN psum)."""

    name = "adaptive"

    @staticmethod
    def compress(tensor):
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor


class Compression:
    """Optional gradient compression algorithm used during allreduce
    (mirrors the reference's selector class)."""

    none = NoneCompressor
    fp16 = FP16Compressor
    bf16 = BF16Compressor
    topk = TopKCompressor
    adaptive = AdaptiveCompressor

    @classmethod
    def by_name(cls, name: Optional[str]) -> type[Compressor]:
        """Resolve a HOROVOD_COMPRESSION value to its compressor class
        (``topk@<ratio>`` specs resolve to the topk compressor)."""
        return {"none": cls.none, "fp16": cls.fp16, "bf16": cls.bf16,
                "topk": cls.topk, "adaptive": cls.adaptive}[normalize(name)]


def compression_name(compression) -> str:
    """Normalize a compression spec — a Compressor class, an instance, or a
    HOROVOD_COMPRESSION string — to its canonical name."""
    if compression is None:
        return "none"
    if isinstance(compression, str):
        return normalize(compression)
    return normalize(getattr(compression, "name", "none"))
