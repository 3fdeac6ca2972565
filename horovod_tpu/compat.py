"""One import site for the JAX entry points the other ~20 modules (library,
tests, examples, bench) share.

The code is written for the one installation there is (jax/jaxlib 0.9.0):
``jax.shard_map`` with ``check_vma=``, ``jax.lax.axis_size``,
``jax_num_cpu_devices`` and ``jax.distributed.is_initialized`` all exist
there, so these are plain aliases — kept so the import sites need not move.
"""

from __future__ import annotations

import jax as _jax

shard_map = _jax.shard_map
axis_size = _jax.lax.axis_size


def set_num_cpu_devices(n: int) -> None:
    """Request ``n`` virtual CPU devices. Raises RuntimeError if a backend
    is already up (the option is read at backend init)."""
    _jax.config.update("jax_num_cpu_devices", n)


def distributed_is_initialized() -> bool:
    return bool(_jax.distributed.is_initialized())


__all__ = ["shard_map", "axis_size", "distributed_is_initialized",
           "set_num_cpu_devices"]
