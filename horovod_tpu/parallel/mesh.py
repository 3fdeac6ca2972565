"""Device-mesh construction — the TPU-native replacement for the reference's
communicator setup (operations.cc:1728-1797: mpi_comm / local_comm /
cross_comm).

Where the reference splits MPI_COMM_WORLD into node-local and cross-node
communicators, we lay devices out on a named :class:`jax.sharding.Mesh`:

- ``data_parallel_mesh``: 1-D ``('hvd',)`` over all chips — the plain
  data-parallel world, equivalent to mpi_comm/NCCL world comm.
- ``hierarchical_mesh``: 2-D ``('dcn', 'ici')`` — the ICI axis plays the role
  of local_comm (NCCL intra-node) and the DCN axis plays cross_comm
  (MPI inter-node), giving the reference's hierarchical allreduce ladder
  (operations.cc:1284-1446) as a mesh-axis composition.
- ``training_mesh``: general ``(dp, fsdp, pp, tp, sp, ep)`` builder for the
  model-parallel families layered on top of the Horovod-parity core.

``data_parallel_mesh`` and ``hierarchical_mesh`` keep ``jax.devices()``
order, which is process order: rank i is device i, and a row of the
hierarchical mesh is ``ici_size`` consecutive devices — one host's chips at
the default ``ici_size`` — so the ``psum`` over 'ici' never leaves the host.
(On the four chips of a v5e 2x2 host that order has two 2-hop ring
neighbours where the topology-aware 0-1-3-2 has none; a 64 MiB/device ring
``ppermute`` took 2.4-2.5 ms in either order — one observation, PR 21.)
``training_mesh`` and ``sharded_mesh`` go through
``mesh_utils.create_device_mesh``, which maps the most-minor mesh axis to
physically adjacent chips; a shape that does not fit the physical topology
raises — there is no fallback to a plain reshape. Off-TPU (the virtual CPU
test mesh) ``create_device_mesh`` itself is the plain reshape.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from ..compat import axis_size

HVD_AXIS = "hvd"
DCN_AXIS = "dcn"
ICI_AXIS = "ici"
# The sharded-data-parallel mesh (ISSUEs 14/19, docs/sharded.md): gradients
# average over 'batch' (plain DP replicas), parameters/grads/optimizer
# state shard 1/shard_size over 'shard' (the ZeRO wire pattern), and the
# third 'model' axis partitions the model itself — tensor-parallel
# column/row matmul pairs (parallel/tensor.py). A spec that never names the
# model axis gets model=1 and the 2-D mesh, bit-for-bit as before ISSUE 19.
BATCH_AXIS = "batch"
SHARD_AXIS = "shard"
MODEL_AXIS = "model"


def _devices(devices=None):
    return list(devices) if devices is not None else jax.devices()


def data_parallel_mesh(devices=None) -> Mesh:
    """All chips on one named axis ``'hvd'`` — rank i of the reference maps to
    mesh position i."""
    devs = _devices(devices)
    return Mesh(np.asarray(devs), (HVD_AXIS,))


def hierarchical_mesh(devices=None, ici_size: int | None = None) -> Mesh:
    """2-D ``('dcn', 'ici')`` mesh.

    ``ici_size`` defaults to the number of chips per process (pod-slice host),
    the analog of the reference's local_size from MPI_Comm_split_type(SHARED)
    (operations.cc:1761-1770).
    """
    devs = _devices(devices)
    n = len(devs)
    if ici_size is None:
        ici_size = max(jax.local_device_count(), 1)
        if n % ici_size != 0:
            ici_size = math.gcd(n, ici_size) or 1
    if n % ici_size != 0:
        raise ValueError(f"device count {n} not divisible by ici_size {ici_size}")
    arr = np.asarray(devs).reshape(n // ici_size, ici_size)
    return Mesh(arr, (DCN_AXIS, ICI_AXIS))


def training_mesh(
    dp: int = 1,
    fsdp: int = 1,
    pp: int = 1,
    tp: int = 1,
    sp: int = 1,
    ep: int = 1,
    devices=None,
    axis_names: Sequence[str] = ("dp", "fsdp", "pp", "tp", "sp", "ep"),
) -> Mesh:
    """General multi-parallel mesh. Axes of size 1 are kept (they cost
    nothing and make sharding specs uniform). ``-1`` in exactly one position
    means 'use all remaining devices'."""
    devs = _devices(devices)
    n = len(devs)
    sizes = [dp, fsdp, pp, tp, sp, ep]
    if len(axis_names) != len(sizes):
        raise ValueError(
            f"axis_names must name all {len(sizes)} axes (rename, don't "
            f"drop — size-1 axes cost nothing); got {axis_names}")
    if sizes.count(-1) > 1:
        raise ValueError("at most one axis may be -1")
    if -1 in sizes:
        known = math.prod(s for s in sizes if s != -1)
        if n % known != 0:
            raise ValueError(f"{n} devices not divisible by fixed axes product {known}")
        sizes[sizes.index(-1)] = n // known
    if math.prod(sizes) != n:
        raise ValueError(f"mesh {dict(zip(axis_names, sizes))} needs {math.prod(sizes)} devices, have {n}")
    arr = mesh_utils.create_device_mesh(tuple(sizes), devices=devs)
    return Mesh(arr, tuple(axis_names))


def parse_mesh_spec(spec: str, n_devices: int) -> tuple[int, int, int]:
    """Parse a ``HOROVOD_MESH`` value into concrete ``(batch, shard, model)``
    sizes for ``n_devices`` chips.

    Accepted spellings, newest last:

    - ``"<batch>"`` — pure DP (shard=1, model=1);
    - ``"<batch>x<shard>"`` — the ISSUE 14 2-D mesh (model=1);
    - ``"<batch>x<shard>x<model>"`` — the full 3-D mesh (ISSUE 19).

    Exactly one size may be ``-1`` ("use all remaining devices"); an empty
    spec resolves to the degenerate pure-DP mesh ``(n_devices, 1, 1)``.
    Raises on a malformed spec or a shape that does not tile the device
    count — the mesh is a value-affecting knob, and a silently-misparsed
    shape would train a different model layout than the operator asked
    for."""
    s = (spec or "").strip().lower().replace("×", "x")
    if not s:
        return n_devices, 1, 1
    parts = s.split("x")
    if not 1 <= len(parts) <= 3:
        raise ValueError(
            f"HOROVOD_MESH={spec!r}: expected '<batch>', '<batch>x<shard>' "
            f"or '<batch>x<shard>x<model>' (e.g. '4x2x1')")
    try:
        sizes = [int(p) for p in parts]
    except ValueError:
        raise ValueError(
            f"HOROVOD_MESH={spec!r}: sizes must be integers (or -1)") from None
    sizes += [1] * (3 - len(sizes))
    if sizes.count(-1) > 1:
        raise ValueError(f"HOROVOD_MESH={spec!r}: at most one size may be -1")
    if -1 in sizes:
        known = math.prod(v for v in sizes if v != -1)
        if known <= 0 or n_devices % known:
            raise ValueError(
                f"HOROVOD_MESH={spec!r}: {n_devices} devices not divisible "
                f"by the fixed sizes' product {known}")
        sizes[sizes.index(-1)] = n_devices // known
    batch, shard, model = sizes
    if batch <= 0 or shard <= 0 or model <= 0 or \
            batch * shard * model != n_devices:
        raise ValueError(
            f"HOROVOD_MESH={spec!r} needs {batch}x{shard}x{model}="
            f"{batch * shard * model} devices, have {n_devices}")
    return batch, shard, model


def _spec_names_model(spec: str) -> bool:
    """Whether a ``HOROVOD_MESH`` spelling explicitly names the third
    (model) axis — ``"4x2x1"`` builds the 3-D mesh even at model=1 (the
    bitwise-identity shape), ``"4x2"`` keeps the 2-D mesh."""
    return (spec or "").strip().lower().replace("×", "x").count("x") >= 2


def sharded_mesh(batch: int | None = None, shard: int | None = None,
                 model: int | None = None, devices=None) -> Mesh:
    """``('batch', 'shard')`` or ``('batch', 'shard', 'model')`` mesh for
    sharded data parallelism (docs/sharded.md). With all sizes ``None``
    the shape comes from ``HOROVOD_MESH`` (``"<batch>x<shard>[x<model>]"``;
    unset = pure DP, shard=model=1).

    The mesh is 3-D exactly when the model axis is NAMED — ``model=`` passed
    (any value, including 1) or a 3-axis env spec — so every pre-ISSUE-19
    caller keeps the bit-identical 2-D mesh, while ``model=1`` callers get
    the degenerate 3-D shape the bitwise-identity test compiles.

    The model axis is laid out as the MOST minor (fast-varying) dimension:
    the per-matmul-pair ``psum('model')`` is the hottest collective, then
    the every-step reduce-scatter/allgather over 'shard', then the
    once-per-step batch psum across the slowest boundaries — the same
    reasoning that keeps the ICI axis minor in ``hierarchical_mesh``."""
    devs = _devices(devices)
    n = len(devs)
    want_model_axis = model is not None
    if batch is None and shard is None and model is None:
        import os

        spec = os.environ.get("HOROVOD_MESH", "")
        batch, shard, model = parse_mesh_spec(spec, n)
        want_model_axis = _spec_names_model(spec)
    elif batch is None and shard is None:
        # Only the model size given: the remainder is pure DP (the same
        # default an empty spec picks for the other two axes).
        batch, shard, model = parse_mesh_spec(f"-1x1x{model}", n)
    elif batch is None:
        batch, shard, model = parse_mesh_spec(
            f"-1x{shard}x{1 if model is None else model}", n)
    elif shard is None:
        batch, shard, model = parse_mesh_spec(
            f"{batch}x-1x{1 if model is None else model}", n)
    elif model is None:
        # Both data axes pinned, no model axis named: exact 2-D tiling
        # required, exactly as before the third axis existed.
        batch, shard, model = parse_mesh_spec(f"{batch}x{shard}x1", n)
    else:
        batch, shard, model = parse_mesh_spec(f"{batch}x{shard}x{model}", n)
    three_d = want_model_axis or model != 1
    shape = (batch, shard, model) if three_d else (batch, shard)
    names = (BATCH_AXIS, SHARD_AXIS, MODEL_AXIS)[:len(shape)]
    return Mesh(mesh_utils.create_device_mesh(shape, devices=devs), names)


def mesh_rank(axis_name: str = HVD_AXIS):
    """Inside shard_map/pmap: this device's index along ``axis_name`` — the
    in-jit analog of hvd.rank()."""
    return jax.lax.axis_index(axis_name)


def mesh_size(mesh_or_axis, axis_name: str | None = None) -> int:
    """Static axis size, from a Mesh (host side) or by name (inside jit via
    ``jax.lax.axis_size``)."""
    if isinstance(mesh_or_axis, Mesh):
        return mesh_or_axis.shape[axis_name or HVD_AXIS]
    return axis_size(mesh_or_axis)
