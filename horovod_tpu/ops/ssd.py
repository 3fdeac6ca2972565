"""Mamba-2's state-space layer in its chunked ("state-space dual") form, and
the causal depthwise convolution in front of it (Dao & Gu, arXiv:2405.21060
§6-7).

Per head ``h`` with state ``S`` (P x N), ``S_0 = 0``::

    a_t = exp(dt_t * A_h)
    S_t = a_t S_{t-1} + dt_t * u_t B_t^T
    y_t = S_t C_t + D_h u_t

``B`` and ``C`` belong to a group of heads (``G`` groups, Granite 4.0-H: 1).
:func:`ssd` computes the same ``y`` without a step per token. The row is cut
into chunks of ``chunk`` positions and, with ``cum`` the running sum of
``dt * A`` inside a chunk:

- a state per chunk: what the chunk's own inputs leave at its end,
  ``sum_s exp(cum_last - cum_s) dt_s u_s B_s^T``;
- the recurrence over chunks: ``S <- exp(cum_last) S + state``, carried in
  float32, which hands each chunk the state it starts from;
- the outputs, written once: inside a chunk ``sum_{s<=l} (C_l . B_s)
  exp(cum_l - cum_s) dt_s u_s`` — one (chunk x chunk) masked score matrix a
  head, times the chunk's inputs — plus the carried state's part
  ``exp(cum_l) S_start C_l``, plus ``D u``.

A row of many chunks runs as one ``lax.scan`` over blocks of ``CHUNK_BLOCK``
chunks (``hvd_ssd_scan``) whose carry is the state: a block computes its
chunks' states, steps the recurrence through them and writes their outputs.

Numerics: ``dt``, ``A``, every sum of ``dt * A``, every decay and the carried
state are float32 whatever the activations' dtype. The three products with a
``chunk``-long or ``N``-long contraction take their operands in ``u``'s dtype
(bf16 as trained: the masked scores and the carried state are rounded to it
for the product, as the published kernels do) and accumulate in float32;
they follow ``jax.default_matmul_precision`` as a plain ``@`` does.

The scan is plain ``jax.numpy`` / ``lax``: differentiable by JAX, no kernel.
The masked scores of all heads (heads x chunk x chunk a chunk) go through
HBM; a block runs under ``jax.checkpoint`` so that no more than
``CHUNK_BLOCK`` chunks' scores are alive at once, forward or backward
(PERF.md §6, PR 30). :func:`causal_depthwise_conv` is the convolution's
DEFINITION, in ``jax.numpy`` too; shapes that tile run it fused with the silu
that follows it as a kernel pair (``ops/mamba_fused.py`` ``conv_silu``, PR 31).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..common import device_names

# Chunks whose (heads x chunk x chunk) masked scores are alive at once: 64
# chunks x 64 heads x 256 x 256 f32 are 1 GiB a layer, several times over in
# the backward; 8 chunks are 128 MiB and still 512 matrix products. Chunks,
# not heads: the chunk axis is the row's major axis, so a block of chunks is a
# slice of every operand as it lies in memory (a block of heads cost a
# transposing copy of u and of y, 35 ms a step at Granite's widths).
CHUNK_BLOCK = 8


def causal_depthwise_conv(x, kernel, bias):
    """``out[t, c] = bias[c] + sum_j kernel[j, c] * x[t - (K - 1) + j, c]``
    with zeros before the row's start. x: (B, T, C); kernel: (K, C); bias:
    (C,). Computed as K shifted multiply-adds in float32; returns x's dtype.
    The padded row goes through HBM in float32 and its K slices are not
    aligned to a tile: 9x the bytes' time at Granite's widths, which is why
    ``Mamba2Mixer`` takes ``mamba_fused.conv_silu`` where the shape tiles."""
    k, t = kernel.shape[0], x.shape[1]
    with jax.named_scope(device_names.MAMBA_CONV):
        padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
        out = bias.astype(jnp.float32)
        for j in range(k):
            out = out + kernel[j].astype(jnp.float32) * padded[:, j:j + t]
        return out.astype(x.dtype)


def _dot(a, b, contract, batch):
    """``lax.dot_general`` accumulating in float32; the result's axes are the
    batch axes, then a's free axes, then b's. (Not ``jnp.einsum``: it puts its
    subscripts, commas and all, into every ``op_name``.)"""
    return lax.dot_general(a, b, (contract, batch),
                           preferred_element_type=jnp.float32)


def _decay(total):
    """``exp`` of a sum of ``dt * A`` (<= 0, or -inf above the diagonal): every
    decay of the layer, in float32."""
    return jnp.exp(total.astype(jnp.float32))


def _carry_over_chunks(keep, left, start):
    """The recurrence over chunks. keep: (c, ...) what a chunk keeps of the
    state it starts from; left: (c, ..., p, n) what its own inputs leave at
    its end; start: (..., p, n) the state before the first of them. Returns
    (the state after the last, the state each chunk STARTS from), float32."""
    def carry_on(state, chunk_in):
        keep_c, left_c = chunk_in
        return keep_c[..., None, None] * state + left_c, state

    return lax.scan(carry_on, start.astype(jnp.float32), (keep, left))


def _chunk_states(Bc, cum, dt, u):
    """The state each chunk's own inputs leave at its end. Bc: (b, c, l, g,
    n); cum, dt: (b, c, l, h) f32; u: (b, c, l, h, p). Returns (b, c, g,
    h / g, p, n) f32."""
    b, c, l, h = cum.shape
    g, p = Bc.shape[3], u.shape[-1]
    to_end = _decay(cum[:, :, -1:, :] - cum) * dt               # (b,c,l,h)
    weighted = (u.astype(jnp.float32) * to_end[..., None]).astype(u.dtype)
    return _dot(Bc, weighted.reshape(b, c, l, g, h // g, p), ((2,), (2,)),
                ((0, 1, 3), (0, 1, 3))).transpose(0, 1, 2, 4, 5, 3)


def _chunk_outputs(Cc, Bc, cum, dt, u, starts, D):
    """y of some chunks, given the state each starts from. Cc, Bc: (b, c, l,
    g, n); cum, dt: (b, c, l, h) f32; u: (b, c, l, h, p); starts: (b, c, g,
    h / g, p, n) f32; D: (h,). Returns (b, c, l, h, p) in u's dtype."""
    b, c, l, h = cum.shape
    g, p = Cc.shape[3], u.shape[-1]
    scores = _dot(Cc, Bc, ((4,), (4,)), ((0, 1, 3), (0, 1, 3)))  # (b,c,g,l,s)
    if 1 < g < h:
        scores = jnp.repeat(scores, h // g, axis=2)
    cum_h, dt_h = cum.swapaxes(2, 3), dt.swapaxes(2, 3)         # (b,c,h,l)
    # exp(cum_l - cum_s) on s <= l; the argument is <= 0 there, and masked
    # BEFORE exp so that the dead triangle's positive sums never overflow
    seg = cum_h[..., :, None] - cum_h[..., None, :]             # (b,c,h,l,s)
    decay = _decay(jnp.where(jnp.tril(jnp.ones((l, l), bool)), seg, -jnp.inf))
    masked = scores * decay * dt_h[..., None, :]
    within = _dot(masked.astype(u.dtype), u, ((4,), (2,)),
                  ((0, 1, 2), (0, 1, 3))).swapaxes(2, 3)        # (b,c,l,h,p)
    carried = _dot(Cc, starts.astype(u.dtype), ((4,), (5,)),
                   ((0, 1, 3), (0, 1, 2))).swapaxes(2, 3)       # (b,c,l,g,h/g,p)
    y = (within + carried.reshape(b, c, l, h, p) * _decay(cum)[..., None]
         + D.astype(jnp.float32)[:, None] * u.astype(jnp.float32))
    return y.astype(u.dtype)


def _some_chunks(state, chunks, D):
    """Some consecutive chunks of a row, given the state before the first:
    (the state after the last, their y). chunks = (Cc, Bc, cum, dt, u), the
    chunks on axis 1."""
    Cc, Bc, cum, dt, u = chunks
    b, c, l, h = cum.shape
    g = Bc.shape[3]
    keep = _decay(cum[:, :, -1, :]).reshape(b, c, g, h // g)
    state, starts = _carry_over_chunks(
        jnp.moveaxis(keep, 1, 0),
        jnp.moveaxis(_chunk_states(Bc, cum, dt, u), 1, 0), state)
    return state, _chunk_outputs(Cc, Bc, cum, dt, u,
                                 jnp.moveaxis(starts, 0, 1), D)


def ssd(u, dt, A, B, C, D, chunk: int):
    """The state-space layer above on whole rows.

    u: (b, T, H, P) inputs; dt: (b, T, H) step sizes, already positive
    (softplus applied); A: (H,) negative; B, C: (b, T, G, N) with ``H % G ==
    0``; D: (H,). Returns y: (b, T, H, P) in u's dtype. A row that is not a
    whole number of chunks is padded at its end with ``dt = 0`` positions,
    which leave the state as it is and are cut off again; a row shorter than
    ``chunk`` is one chunk of its own length."""
    from ..metrics import record_ssd_plan

    b, t, h, p = u.shape
    g, n = B.shape[2], B.shape[3]
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    if chunk <= 0:
        raise ValueError(f"ssd chunk must be positive, got {chunk}")
    chunk = min(chunk, t)
    record_ssd_plan(chunk)
    pad = -t % chunk
    if pad:
        u, dt, B, C = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                       for x in (u, dt, B, C))
    c = (t + pad) // chunk
    f32 = jnp.float32
    dt = dt.astype(f32).reshape(b, c, chunk, h)
    uc = u.reshape(b, c, chunk, h, p)
    Bc, Cc = (x.reshape(b, c, chunk, g, n) for x in (B, C))
    cum = jnp.cumsum(dt * A.astype(f32), axis=2)            # (b,c,l,h), <= 0

    # CHUNK_BLOCK chunks at a time, the state carried from block to block:
    # ONE device op (a ``while``) that carries the scope. The ops of its body
    # are events of the device trace too, and their names end in
    # ``while/body/...`` with the scope further up, where a label that keeps
    # an ``op_name``'s last segments does not see it: a reader that sums the
    # labels holding ``hvd_ssd`` counts the scan once.
    chunks = (Cc, Bc, cum, dt, uc)
    start = jnp.zeros((b, g, h // g, p, n), f32)
    with jax.named_scope(device_names.SSD_SCAN):
        if c <= CHUNK_BLOCK or c % CHUNK_BLOCK:
            _, y = _some_chunks(start, chunks, D)
        else:
            def blocked(x):         # (b, c, ..) -> (c / CB, b, CB, ..)
                return jnp.moveaxis(x.reshape(
                    (b, c // CHUNK_BLOCK, CHUNK_BLOCK) + x.shape[2:]), 1, 0)

            _, y = lax.scan(
                jax.checkpoint(lambda state, xs: _some_chunks(state, xs, D)),
                start, tuple(map(blocked, chunks)))
            y = jnp.moveaxis(y, 0, 1).reshape(b, c, chunk, h, p)
    return y.reshape(b, c * chunk, h, p)[:, :t]
