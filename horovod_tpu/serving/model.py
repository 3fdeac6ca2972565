"""Replica-side model machinery: serving-checkpoint loading, builder
resolution, and the scan-per-dispatch decode loop.

A **builder** is what turns restored checkpoint state into a callable the
replica can jit: ``builder(state) -> apply_fn`` with
``apply_fn(x: [batch, ...]) -> y``. Replicas are separate processes, so
builders are named by an importable ``"module:function"`` spec (the same
convention the launcher uses for entry points) rather than passed as
closures. :func:`mlp_builder` is the built-in used by the smoke tests and
tools/serve_smoke.py; real deployments point at their own model module.

jax imports stay inside functions: the ROUTER process imports this module
for the builder-spec validation and must never pay (or wedge on) backend
startup — only replicas touch jax.
"""

from __future__ import annotations

import importlib
import re
from typing import Any, Callable

import numpy as np


def load_for_serving(path: str, template: Any = None) -> Any:
    """Restore a serving checkpoint written by
    :func:`horovod_tpu.checkpoint.export_for_inference`.

    Refuses a raw *training* checkpoint: optimizer state in the restored
    tree means the export step never ran — which also means per-rank batch
    statistics were never consolidated, so serving it would silently serve
    one rank's stats (docs/inference.md). The error names the fix."""
    from ..checkpoint import load_for_inference

    state = load_for_inference(path, template)
    if isinstance(state, dict) and "opt_state" in state:
        raise ValueError(
            f"checkpoint at {path!r} is a raw TRAINING checkpoint (it "
            "contains 'opt_state'): the serving plane refuses it because "
            "optimizer state was never stripped and per-rank batch "
            "statistics were never consolidated. Export it first with "
            "horovod_tpu.checkpoint.export_for_inference(path, state) and "
            "serve the exported copy.")
    return state


def resolve_builder(spec: str) -> Callable:
    """``"pkg.module:function"`` -> the function. Import errors surface
    with the spec named (a typo'd builder must fail the replica loudly at
    startup, not at the first request)."""
    mod_name, sep, fn_name = spec.partition(":")
    if not sep or not mod_name or not fn_name:
        raise ValueError(
            f"builder spec {spec!r} must look like 'pkg.module:function'")
    try:
        mod = importlib.import_module(mod_name)
    except ImportError as e:
        raise ImportError(f"cannot import builder module {mod_name!r} "
                          f"(from spec {spec!r}): {e}") from e
    try:
        return getattr(mod, fn_name)
    except AttributeError as e:
        raise AttributeError(
            f"builder module {mod_name!r} has no attribute "
            f"{fn_name!r} (from spec {spec!r})") from e


def make_decode_fn(apply_fn: Callable, steps: int = 1) -> Callable:
    """Jit ``apply_fn``; with ``steps > 1`` wrap it in a ``lax.scan`` so
    ONE dispatch runs K model steps — the ``make_scan_train_loop``
    amortization of per-dispatch host latency, applied to multi-step
    decode. The scanned form
    feeds each step's output to the next (``y_k = f(y_{k-1})``), so the
    model's output must be shaped like its input."""
    import jax

    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    if steps == 1:
        return jax.jit(apply_fn)

    def scanned(x):
        def body(carry, _):
            y = apply_fn(carry)
            return y, None

        y, _ = jax.lax.scan(body, x, None, length=steps)
        return y

    return jax.jit(scanned)


def shard_batch(x, mesh=None):
    """Lay a host batch out across this replica's local devices (batch-dim
    sharding) when the bucket size divides the device count's multiple —
    the 'jitted forward step across the mesh' piece on multi-chip
    replicas. Single-device replicas (and indivisible buckets) return the
    array unchanged; jit handles committed single-device inputs fine."""
    import jax

    n_dev = len(jax.local_devices())
    if n_dev <= 1 or x.shape[0] % n_dev != 0:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is None:
        mesh = jax.make_mesh((n_dev,), ("batch",))
    return jax.device_put(x, NamedSharding(mesh, PartitionSpec("batch")))


# -- token-level serving: the reference LM + the paged decode step ----------
#
# The LLM plane (serving/llm/, ISSUE 12) needs a *deterministic*
# autoregressive model whose paged-KV decode can be checked bitwise
# against a contiguous-cache oracle, and whose prefill/decode replicas —
# separate processes — derive identical weights with no checkpoint
# shipping. TinyLM is that reference: a single-head attention LM in plain
# numpy (replica processes never pay a jax/XLA backend start), weights
# seeded from HOROVOD_SERVE_LLM_SEED, greedy argmax decoding (ties to the
# lowest index) so every token is a pure function of the prompt. Real
# deployments point HVD_SERVE_BUILDER at their own params loader; the
# decode-step contract below is what the scheduler drives either way.


def tiny_lm_params(vocab: int = 64, dim: int = 16, max_context: int = 512,
                   seed: int = 0) -> dict:
    """Deterministic TinyLM weights: embedding, positional table, one
    attention head (wq/wk/wv) and the output head (wo). Same (vocab, dim,
    max_context, seed) -> bitwise-identical weights in every process —
    the property that makes prefill->decode handoff and kill->re-prefill
    recovery exact."""
    rs = np.random.RandomState(seed)
    s = 1.0 / np.sqrt(dim)
    return {
        "vocab": vocab, "dim": dim, "max_context": max_context,
        "embed": rs.uniform(-s, s, (vocab, dim)).astype(np.float32),
        "pos": rs.uniform(-s, s, (max_context, dim)).astype(np.float32),
        "wq": rs.uniform(-s, s, (dim, dim)).astype(np.float32),
        "wk": rs.uniform(-s, s, (dim, dim)).astype(np.float32),
        "wv": rs.uniform(-s, s, (dim, dim)).astype(np.float32),
        "wo": rs.uniform(-s, s, (dim, vocab)).astype(np.float32),
    }


def _lm_softmax(x: np.ndarray) -> np.ndarray:
    # ndarray-method reductions, not np.max/np.sum: same ufunc.reduce
    # kernel (bitwise-identical result) minus the module-level dispatch
    # overhead — this runs once per decoded token on the serving path.
    e = np.exp(x - x.max())
    return e / e.sum()


def lm_context_step(params: dict, token: int, pos: int,
                    k_ctx: np.ndarray, v_ctx: np.ndarray) -> tuple:
    """ONE decode step against an explicit gathered context — the
    decode-step fn the paged scheduler drives with block-table-gathered
    K/V (kv_cache.PagedKVCache.gather): feed ``token`` at position
    ``pos`` attending over ``k_ctx``/``v_ctx`` (positions 0..pos-1) plus
    itself; returns ``(next_token, k_vec, v_vec)`` where k/v are this
    position's cache entries. Because the gather materializes the same
    values in the same order a contiguous cache holds, paged and
    contiguous decode are bitwise identical."""
    if pos >= len(params["pos"]):
        raise ValueError(f"position {pos} exceeds max_context "
                         f"{len(params['pos'])}")
    h = params["embed"][token] + params["pos"][pos]
    k = h @ params["wk"]
    v = h @ params["wv"]
    q = h @ params["wq"]
    ks = np.concatenate([k_ctx, k[None]]) if len(k_ctx) else k[None]
    vs = np.concatenate([v_ctx, v[None]]) if len(v_ctx) else v[None]
    att = _lm_softmax((ks @ q) / np.sqrt(len(h)).astype(np.float32)) @ vs
    logits = (h + att) @ params["wo"]
    return int(np.argmax(logits)), k, v


_GEMM_ROWS_EXACT: dict = {}


def _gemm_rows_exact(dim: int) -> bool:
    """Probe (once per dim per process) whether this BLAS produces
    bitwise-identical rows for a batched ``[m, dim] @ [dim, dim]``
    matmul and the per-row matvec. True on every mainstream x86/ARM
    OpenBLAS/MKL build at TinyLM sizes (small inner dimension, same
    sequential accumulation order), but the batched verify forward must
    DEGRADE to per-row projections rather than silently break the
    oracle contract anywhere it does not hold."""
    ok = _GEMM_ROWS_EXACT.get(dim)
    if ok is None:
        rs = np.random.RandomState(7)
        hm = rs.uniform(-1, 1, (5, dim)).astype(np.float32)
        wm = rs.uniform(-1, 1, (dim, dim)).astype(np.float32)
        batched = hm @ wm
        ok = all(np.array_equal(batched[i], hm[i] @ wm) for i in range(5))
        _GEMM_ROWS_EXACT[dim] = ok
    return ok


def lm_verify_chain(params: dict, feed: int, proposals, pos0: int,
                    buf_k: np.ndarray, buf_v: np.ndarray,
                    eos_id: int = -1) -> list:
    """The target side of speculative decoding (Leviathan et al.,
    arXiv:2211.17192) as ONE chained call: feed ``feed`` at ``pos0``,
    then walk the draft's ``proposals`` first-mismatch-wins — each step
    checks the draft's guess against the target argmax; on a mismatch
    the target's own token is already the correct emission, so only the
    remaining guesses are discarded. Returns the emitted tokens (between
    1 and ``len(proposals) + 1`` of them) and fills ``buf_k``/``buf_v``
    rows ``pos0 .. pos0+len(out)-1`` in place.

    ``buf_k``/``buf_v`` must hold the gathered context in rows
    ``[:pos0]`` with capacity ``pos0 + len(proposals) + 1``. Two
    amortizations make this the paper's "one batched forward": the fed
    chain is known up front (teacher forcing — ``feed`` plus the
    proposals), so all K/V/Q projections run as ONE matmul batch
    (guarded by :func:`_gemm_rows_exact`); and each step attends over
    ``buf[:pos+1]`` views instead of re-materializing O(context) arrays
    per token. Both are bitwise :func:`lm_context_step` on the same
    values, so speculation inherits the oracle contract; with an empty
    proposal list this is exactly one plain decode step."""
    last = pos0 + len(proposals)
    if last >= len(params["pos"]):
        raise ValueError(f"position {last} exceeds max_context "
                         f"{len(params['pos'])}")
    embed, posv, wo = params["embed"], params["pos"], params["wo"]
    dim = buf_k.shape[1]
    feeds = [feed] + list(proposals)
    if _gemm_rows_exact(dim):
        hs = embed[feeds] + posv[pos0:last + 1]
        kb = hs @ params["wk"]
        vb = hs @ params["wv"]
        qb = hs @ params["wq"]
    else:
        hs = np.empty((len(feeds), dim), np.float32)
        kb = np.empty_like(hs)
        vb = np.empty_like(hs)
        qb = np.empty_like(hs)
        for j, t in enumerate(feeds):
            h = embed[t] + posv[pos0 + j]
            hs[j] = h
            kb[j] = h @ params["wk"]
            vb[j] = h @ params["wv"]
            qb[j] = h @ params["wq"]
    scale = np.sqrt(dim).astype(np.float32)
    out = []
    pos = pos0
    for j in range(len(feeds)):
        # row j was fed feeds[j], which is committed iff every earlier
        # proposal matched — the loop only reaches j in that case, so
        # rows written to the buffer always belong to the real chain.
        buf_k[pos] = kb[j]
        buf_v[pos] = vb[j]
        ks = buf_k[:pos + 1]
        vs = buf_v[:pos + 1]
        att = _lm_softmax((ks @ qb[j]) / scale) @ vs
        nxt = int(((hs[j] + att) @ wo).argmax())
        out.append(nxt)
        pos += 1
        if nxt == eos_id or j >= len(proposals) or proposals[j] != nxt:
            break
    return out


def lm_draft_chain(params: dict, feed: int, pos0: int,
                   steps: int, eos_id: int = -1) -> list:
    """The draft side of speculative decoding: up to ``steps`` greedy
    self-fed proposals from the EMBEDDING PATH alone —
    ``argmax((embed[tok] + pos[p]) @ wo)`` — no attention, no K/V, no
    state. This is the "small draft" of Leviathan et al.: the target's
    (float16-rounded) token and position tables already rank the
    likeliest continuation well enough for a useful acceptance rate,
    and skipping attention makes a proposal ~6x cheaper than a target
    step — the asymmetry speculation needs to pay for itself (a draft
    as expensive as the target can never win: it burns k draft steps
    to save at most k of k+1 target steps' overhead). The verify loop
    guarantees OUTPUT correctness regardless of what is proposed; the
    draft's only job is guessing the target's argmax, so it needs no
    bitwise contract and no KV scratch to rebuild on preemption.
    Stops early at ``eos_id`` — nothing meaningful to propose past the
    end of a sequence. Returns the proposed tokens."""
    if pos0 + steps - 1 >= len(params["pos"]):
        raise ValueError(f"position {pos0 + steps - 1} exceeds "
                         f"max_context {len(params['pos'])}")
    embed, posv, wo = params["embed"], params["pos"], params["wo"]
    out = []
    tok, pos = feed, pos0
    for _ in range(steps):
        nxt = int(((embed[tok] + posv[pos]) @ wo).argmax())
        out.append(nxt)
        pos += 1
        if nxt == eos_id:
            break
        tok = nxt
    return out


def lm_prefill(params: dict, tokens) -> tuple:
    """Run the prompt through the model sequentially: returns
    ``(K, V, next_token)`` with K/V of shape ``[len(tokens), dim]`` —
    the payload a prefill replica hands off to the decode pool (the last
    position's logits already name the first generated token, so TTFT is
    the prefill round trip)."""
    if not len(tokens):
        raise ValueError("prefill needs at least one prompt token")
    dim = params["dim"]
    n = len(tokens)
    ks = np.zeros((n, dim), np.float32)
    vs = np.zeros((n, dim), np.float32)
    nxt = -1
    for i, t in enumerate(tokens):
        nxt, ks[i], vs[i] = lm_context_step(params, int(t), i,
                                            ks[:i], vs[:i])
    return ks, vs, nxt


def lm_prefill_from(params: dict, tokens, k_prefix, v_prefix) -> tuple:
    """Prefill resuming from cached K/V rows (radix prefix hit,
    kv_cache.RadixPrefixCache): positions ``0..len(k_prefix)-1`` are
    already materialized, so only positions ``len(k_prefix)..n-1`` run
    through the model. Returns ``(K_new, V_new, next_token)`` with K/V
    covering just the NEW positions. With an empty prefix this is
    bitwise :func:`lm_prefill`; with any prefix the result is bitwise
    identical too, because a position's K/V depends only on (token,
    position) and the attention gather sees the same values either way."""
    n = len(tokens)
    start = len(k_prefix)
    if not (0 <= start < n):
        raise ValueError(f"prefix covers {start} of {n} prompt positions "
                         f"(need at least one position to compute)")
    dim = params["dim"]
    ks = np.zeros((n, dim), np.float32)
    vs = np.zeros((n, dim), np.float32)
    ks[:start] = np.asarray(k_prefix, np.float32).reshape(start, dim)
    vs[:start] = np.asarray(v_prefix, np.float32).reshape(start, dim)
    nxt = -1
    for i in range(start, n):
        nxt, ks[i], vs[i] = lm_context_step(params, int(tokens[i]), i,
                                            ks[:i], vs[:i])
    return ks[start:], vs[start:], nxt


def draft_lm_params(params) -> dict:
    """The DRAFT model for speculative decoding (scheduler.py verify
    loop; Leviathan et al., arXiv:2211.17192): the target's weights
    rounded through float16 and back. Deterministic in every process (a
    pure function of the target params, which are themselves seeded), so
    prefill/decode replicas and kill->respawn recovery agree bitwise; the
    ~1e-3 relative perturbation leaves almost every greedy argmax
    unchanged (TinyLM's top-2 logit gaps are orders of magnitude larger),
    which is what buys the high acceptance rate — while the verify loop
    guarantees the OUTPUT is the target's regardless. Materializes
    ``ShardedLMParams`` transparently (drafting runs on the scheduler,
    which already holds the gathered view)."""
    out = {}
    for key in params.keys():
        v = params[key]
        if isinstance(v, np.ndarray):
            out[key] = v.astype(np.float16).astype(np.float32)
        else:
            out[key] = v
    return out


def lm_generate(params: dict, prompt, max_new_tokens: int,
                eos_id: int = -1) -> list:
    """The sequential oracle: greedy generation over a contiguous cache,
    no paging, no batching, no scheduler. The serving plane must
    reproduce this token-for-token for every request — ANY cross-sequence
    KV contamination, block-table corruption, or preempt/resume drift
    changes some argmax and diverges from it (the smoke's
    zero-contamination bar)."""
    k, v, nxt = lm_prefill(params, prompt)
    out = [nxt]
    ks, vs = list(k), list(v)
    while nxt != eos_id and len(out) < max_new_tokens:
        pos = len(ks)
        nxt, kv_k, kv_v = lm_context_step(
            params, out[-1], pos,
            np.asarray(ks, np.float32), np.asarray(vs, np.float32))
        ks.append(kv_k)
        vs.append(kv_v)
        out.append(nxt)
    return out


def lm_params_nbytes(params) -> int:
    """Persistent parameter bytes of a TinyLM params dict (arrays only;
    the scalars are free)."""
    return int(sum(v.nbytes for v in params.values()
                   if isinstance(v, np.ndarray)))


class ShardedLMParams:
    """A TinyLM sharded across a multi-chip serving replica's model axis
    (ISSUE 19) — dict-like, so the scheduler's decode step and
    ``lm_prefill``/``lm_context_step`` run UNCHANGED against it.

    Each of the ``model_shards`` chips persistently holds a 1/s row-slice
    of every weight; ``__getitem__`` reassembles the full array on access
    (one concatenate — the simulated all-gather of ZeRO-Inference-style
    weight streaming) and the reassembled array is BITWISE the original,
    so sharded serving is token-for-token exact by construction. The
    gather is transient: per-chip PERSISTENT bytes
    (:meth:`per_chip_nbytes`) is what the chip-budget gate counts, the
    same convention the training plane's ``gather_params`` refresh uses."""

    def __init__(self, shards) -> None:
        shards = list(shards)
        if not shards:
            raise ValueError("need at least one shard")
        keys = set(shards[0])
        if any(set(s) != keys for s in shards):
            raise ValueError("shards disagree on param keys")
        self._shards = shards

    @property
    def model_shards(self) -> int:
        return len(self._shards)

    def __getitem__(self, key):
        v = self._shards[0][key]
        if not isinstance(v, np.ndarray):
            return v            # replicated scalar (vocab/dim/max_context)
        if len(self._shards) == 1:
            return v
        return np.concatenate([s[key] for s in self._shards], axis=0)

    def __contains__(self, key) -> bool:
        return key in self._shards[0]

    def get(self, key, default=None):
        return self[key] if key in self else default

    def keys(self):
        return self._shards[0].keys()

    def shard(self, rank: int) -> dict:
        """One chip's persistent slice tree."""
        return self._shards[rank]

    def per_chip_nbytes(self) -> int:
        """Persistent parameter bytes the LARGEST chip holds — the figure
        the HOROVOD_SERVE_LLM_CHIP_BUDGET_BYTES gate checks."""
        return max(lm_params_nbytes(s) for s in self._shards)


def shard_lm_params(params: dict, model_shards: int) -> ShardedLMParams:
    """Slice a full TinyLM params dict into ``model_shards`` per-chip row
    slices (every weight's dim 0: embed/pos rows, wq/wk/wv/wo input rows).
    Row-slicing makes the access-time gather a plain concatenate — bitwise
    exact — and every dim-0 size of the reference model (vocab, dim,
    max_context) must divide evenly, mirroring the training plane's
    uniform-slice discipline (tensor.tp_pair_slices)."""
    if model_shards < 1:
        raise ValueError(f"model_shards must be >= 1, got {model_shards}")
    if model_shards == 1:
        return ShardedLMParams([params])
    for key, v in params.items():
        if isinstance(v, np.ndarray) and v.shape[0] % model_shards:
            raise ValueError(
                f"param {key!r} dim 0 ({v.shape[0]}) not divisible by "
                f"model_shards {model_shards}: sharded serving slices "
                f"must be uniform")
    shards = []
    for r in range(model_shards):
        shard = {}
        for key, v in params.items():
            if isinstance(v, np.ndarray):
                per = v.shape[0] // model_shards
                shard[key] = v[r * per:(r + 1) * per]
            else:
                shard[key] = v
        shards.append(shard)
    return ShardedLMParams(shards)


def lm_builder(state: Any) -> dict:
    """Builder for the LLM serving plane (``HVD_SERVE_BUILDER`` default
    for llm replicas): returns the TinyLM params dict. A checkpointed
    state supplies ``state["lm_params"]`` verbatim; with no checkpoint the
    weights derive from the HOROVOD_SERVE_LLM_{VOCAB,DIM,MAX_CONTEXT,
    SEED} env contract — which is how prefill and decode pool processes
    agree bitwise with zero weight shipping."""
    import os

    if state is not None and "lm_params" in state:
        return state["lm_params"]
    return tiny_lm_params(
        vocab=int(os.environ.get("HOROVOD_SERVE_LLM_VOCAB", "") or 64),
        dim=int(os.environ.get("HOROVOD_SERVE_LLM_DIM", "") or 16),
        max_context=int(
            os.environ.get("HOROVOD_SERVE_LLM_MAX_CONTEXT", "") or 512),
        seed=int(os.environ.get("HOROVOD_SERVE_LLM_SEED", "") or 0))


def mlp_builder(state: Any) -> Callable:
    """Built-in builder for :class:`horovod_tpu.models.MLP` serving
    checkpoints: layer widths are re-derived from the kernel shapes, so
    the replica needs no side-channel architecture file."""
    import jax.numpy as jnp

    from ..models import MLP

    params = state["params"]
    names = sorted((k for k in params if re.fullmatch(r"Dense_\d+", k)),
                   key=lambda k: int(k.split("_")[1]))
    if not names:
        raise ValueError(
            f"mlp_builder: no Dense_* layers in params (keys: "
            f"{sorted(params)})")
    features = tuple(int(params[k]["kernel"].shape[1]) for k in names)
    model = MLP(features=features)

    def apply_fn(x):
        return model.apply({"params": params}, jnp.asarray(x))

    return apply_fn
