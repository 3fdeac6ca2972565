"""The exchange against the analytic mean (copy of ``chip_smoke.phase_collective``).

A rank-dependent gradient tree goes through ``hvd.jax.allreduce_gradients`` in
several buckets; every leaf must equal the mean over ranks worked out by hand.
A synthetic batch that is the same on every rank cannot tell a broken
all-reduce from a correct one; this can. The system under test is the only
thing taken from the program.
"""

from __future__ import annotations

import numpy as np

SHAPES = ((10, 100), (257,), (64, 33), (3000,), (7, 11, 13), (1,))
# f32 sums of at most a few ranks of O(1) values: rounding only.
REL_TOL = 1e-5


def check_exchange(mesh, seed, num_buckets=3, threshold=16 << 10):
    """Returns the worst absolute error; raises ``AssertionError`` when a leaf
    is off the analytic mean."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.compat import shard_map

    n = mesh.size
    rng = np.random.default_rng(seed)
    base = {f"leaf{i}": jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for i, shape in enumerate(SHAPES)}

    def body(tree):
        r = jax.lax.axis_index(hvd.HVD_AXIS).astype(jnp.float32)
        grads = jax.tree_util.tree_map(lambda t: t * (r + 1.0) + r, tree)
        return hvd.jax.allreduce_gradients(
            grads, num_buckets=num_buckets, fusion_threshold=threshold)

    got = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                            check_vma=False))(base)
    worst = 0.0
    for k, t in base.items():
        # mean over ranks r of t*(r+1)+r
        want = np.asarray(t) * (n + 1) / 2.0 + (n - 1) / 2.0
        err = float(np.max(np.abs(np.asarray(got[k]) - want)))
        worst = max(worst, err)
        if not err <= REL_TOL * (1.0 + float(np.max(np.abs(want)))):
            raise AssertionError(
                f"allreduce of {k} over {n} rank(s) is off the analytic mean "
                f"by {err:.3e}")
    return worst
