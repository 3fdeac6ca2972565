"""Plain causal attention in float32 at ``highest``, and the blocked check
that holds a fused kernel at a long sequence without a T x T array.

``plain_causal_attention`` is the reference the ``lm217m`` step check swaps in
for the kernel. ``check_kernel_slice`` holds a kernel at the cell's own
(T, heads, head_dim): the reference takes only the LAST ``slice_len`` query
positions against the whole context, one head at a time, so its largest array
is slice_len x T. Under a causal mask that slice is enough for exact
references of out and dq on those queries and of dk and dv on the same
positions as keys (a key is only seen by queries at or after it, and those
are all in the slice).
"""

from __future__ import annotations

import numpy as np

# A share of the reference's largest magnitude (copy of
# chip_smoke.KERNEL_HELD["bfloat16"]): the kernel runs as the trainer runs it,
# bf16 operands at the default precision and bf16 outputs, where one rounding
# alone is 4e-3; on the chip PR 21 observed <= 7e-3.
KERNEL_REL_TOL = 2e-2


def plain_causal_attention(q, k, v, q_offset=0):
    """q: (B, Tq, H, D); k, v: (B, Tk, H, D). Query i sits at position
    ``q_offset + i``. float32 at "highest"; returns q's dtype."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        scale = q.shape[-1] ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(f32), k.astype(f32)) * scale
        q_pos = jnp.arange(q.shape[1]) + q_offset
        mask = q_pos[:, None] >= jnp.arange(k.shape[1])[None, :]
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(f32)).astype(q.dtype)


def check_kernel_slice(kernel, seq, heads, head_dim, seed, slice_len=4096,
                       dtype="bfloat16", rel_tol=KERNEL_REL_TOL):
    """``kernel(q, k, v)`` is the fused causal attention under test on
    (1, seq, heads, head_dim) inputs. Returns the observed errors as shares of
    max|reference|; raises ``AssertionError`` beyond ``rel_tol``."""
    import jax
    import jax.numpy as jnp

    slice_len = min(slice_len, seq)
    start = seq - slice_len
    shape = (1, seq, heads, head_dim)

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 4)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                   for kk in ks[:3])
        return q, k, v, jax.random.normal(ks[3], shape, jnp.float32)

    @jax.jit
    def system(q, k, v, g):
        out, vjp = jax.vjp(kernel, q, k, v)
        dq, dk, dv = vjp(g.astype(out.dtype))
        return tuple(t[:, start:] for t in (out, dq, dk, dv))

    @jax.jit
    def reference(q, k, v, g):
        f32 = jnp.float32

        def one_head(args):
            qs, kh, vh, gs = args       # (slice, D), (T, D), (T, D), (slice, D)
            out, vjp = jax.vjp(
                lambda a, b, c: plain_causal_attention(
                    a[None, :, None], b[None, :, None], c[None, :, None],
                    q_offset=start)[0, :, 0], qs, kh, vh)
            dq, dk, dv = vjp(gs)
            return out, dq, dk[start:], dv[start:]

        heads_first = lambda t: jnp.moveaxis(t[0].astype(f32), 1, 0)
        outs = jax.lax.map(one_head, (heads_first(q[:, start:]), heads_first(k),
                                      heads_first(v), heads_first(g[:, start:])))
        return tuple(jnp.moveaxis(t, 0, 1)[None] for t in outs)

    q, k, v, g = inputs(jax.random.PRNGKey(seed))
    got, want = system(q, k, v, g), reference(q, k, v, g)
    observed = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        observed[name] = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        if not observed[name] <= rel_tol:
            raise AssertionError(
                f"kernel at seq={seq} heads={heads} d={head_dim} {dtype}: "
                f"{name} on the last {slice_len} positions is off the f32 "
                f"reference by {observed[name]:.3e} of max|ref| (> {rel_tol})")
    return observed
