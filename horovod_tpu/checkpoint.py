"""Checkpoint / resume helpers — the rank-0-writes + broadcast-on-restore
contract (SURVEY.md §5.4).

The reference delegates serialization to the framework and supplies the
consistency pieces: save only on rank 0 (reference README.md:117-119),
restore everywhere and re-broadcast (BroadcastGlobalVariablesHook,
hvd.broadcast_parameters / broadcast_optimizer_state, resume-epoch broadcast
in examples/pytorch_imagenet_resnet50.py). Here serialization is orbax (the
JAX checkpoint library), and the same contract is packaged as two calls:

    hvd.checkpoint.save(path, {"params": params, "opt_state": opt_state,
                               "epoch": epoch})          # writes on rank 0
    state = hvd.checkpoint.restore(path)                 # every rank reads;
    # restore() allgathers a digest of the restored leaves and fails loudly
    # if any rank read divergent state. Alternative on non-shared
    # filesystems: restore(path, verify=False) on rank 0 only, then
    # hvd.jax.broadcast_parameters / broadcast_resume_state.
"""

from __future__ import annotations

import os
from typing import Any, Optional

from .common import basics


def _ocp():
    import orbax.checkpoint as ocp

    return ocp


# -- crash-consistent commits (ISSUE 8) --------------------------------------
#
# The elastic ladder's restore point is only as good as its worst write: a
# worker killed mid-commit (exactly the fault the escalation ladder and the
# chaos harness exercise) must never leave a half-written directory where the
# last good checkpoint stood. So every save stages into a sibling temp
# directory, fsyncs it, marks it complete (a sibling ``.ok`` file, written
# after the data is durable), and swaps it into place with renames — the only
# atomic primitive POSIX gives us for directories. Every crash window leaves
# either the old checkpoint, or the new one, or a complete staged copy that
# the next save()/restore() adopts (_heal_interrupted).


def _fsync_tree(path: str) -> None:
    """Best-effort fsync of every file and directory under ``path`` — the
    rename below publishes the commit, so the data must be durable first.
    Filesystems that reject directory fsync (some network mounts) are
    tolerated: the rename ordering still bounds the damage to 'old or new'."""
    for root, dirs, files in os.walk(path, topdown=False):
        for name in files + [os.curdir]:
            try:
                fd = os.open(os.path.join(root, name) if name != os.curdir
                             else root, os.O_RDONLY)
            except OSError:
                continue
            try:
                os.fsync(fd)
            except OSError:
                pass
            finally:
                os.close(fd)


def _test_pause(point: str) -> None:
    """Deterministic kill window for the crash-consistency tests: when
    ``HOROVOD_CKPT_TEST_STALL`` names this pipeline point (``stage`` —
    staged copy exists but carries no ``.ok`` yet; ``rename`` — between
    the swap's two renames, the brief no-target window), the commit holds
    for ``HOROVOD_CKPT_TEST_STALL_S`` so the test can SIGKILL the writer
    exactly there. No-op unless explicitly armed."""
    if os.environ.get("HOROVOD_CKPT_TEST_STALL", "") == point:
        import time

        time.sleep(float(os.environ.get("HOROVOD_CKPT_TEST_STALL_S", "30")))


def _heal_interrupted(target: str) -> None:
    """Adopt or discard leftovers of an interrupted commit next to
    ``target``: a complete staged copy (``.tmp.* + .ok``) replaces a missing
    target (the crash hit between the two swap renames); incomplete stages
    and displaced old checkpoints (``.trash.*``) are deleted. Races between
    ranks healing a shared filesystem are benign — every rename is wrapped,
    and whoever wins leaves a valid target."""
    import shutil

    parent, base = os.path.split(target)
    try:
        names = os.listdir(parent or os.curdir)
    except OSError:
        return
    stale: list[str] = []
    for n in sorted(names):
        p = os.path.join(parent, n)
        if n.startswith(base + ".tmp.") and not n.endswith(".ok"):
            if os.path.exists(p + ".ok") and not os.path.exists(target):
                try:
                    os.rename(p, target)
                    os.unlink(p + ".ok")
                    continue
                except OSError:  # another rank adopted first
                    pass
            stale.append(p)
        elif n.startswith(base + ".trash."):
            stale.append(p)
    for p in stale:
        shutil.rmtree(p, ignore_errors=True)
        try:
            os.unlink(p + ".ok")
        except OSError:
            pass


def _swap_into_place(tmp: str, target: str) -> None:
    """Atomic publish: mark the staged copy complete, move any existing
    checkpoint aside, rename the stage in, then clean up. A kill at ANY
    point leaves a restorable state (the ``.ok`` marker makes the stage
    adoptable during the brief no-target window)."""
    import shutil

    ok = tmp + ".ok"
    with open(ok, "w") as f:
        f.write("complete\n")
        f.flush()
        os.fsync(f.fileno())
    trash = f"{target}.trash.{os.path.basename(tmp).rsplit('.', 1)[-1]}"
    if os.path.exists(target):
        os.rename(target, trash)
    _test_pause("rename")
    os.rename(tmp, target)
    try:  # publish the renames before declaring the commit durable
        fd = os.open(os.path.dirname(target) or os.curdir, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    except OSError:
        pass
    try:
        os.unlink(ok)
    except OSError:
        pass
    shutil.rmtree(trash, ignore_errors=True)


def save_local(path: str, state: Any, step: Optional[int] = None) -> None:
    """The single-writer commit pipeline — stage, fsync, ``.ok``, atomic
    rename — with NO rank gate and NO completion barrier. This is the core
    :func:`save` wraps, and what the background writer
    (:class:`horovod_tpu.ckpt_async.AsyncCheckpointer`) runs off the step
    path: collectives may only run on the training thread, so the async
    writer must use the barrier-free form."""
    import numpy as np

    import jax

    ocp = _ocp()
    ckptr = ocp.StandardCheckpointer()
    target = os.path.join(os.path.abspath(path), f"step_{step}") \
        if step is not None else os.path.abspath(path)
    # numpy SCALARS (np.int64(7) epoch counters and friends) are not
    # ndarrays, and orbax's StandardCheckpointHandler rejects them on
    # some versions ("Unsupported type: <class 'numpy.int64'>") — lift
    # them to 0-d arrays, which restore round-trips (int() on a 0-d
    # array works) and every orbax accepts.
    state = jax.tree_util.tree_map(
        lambda x: np.asarray(x) if isinstance(x, np.generic) else x,
        state)
    # Crash-consistent commit (ISSUE 8): stage next to the target, make
    # it durable, then swap with atomic renames — a worker killed
    # mid-commit can never corrupt the restore point the elastic ladder
    # depends on. Also adopts/cleans leftovers of a previous kill.
    _heal_interrupted(target)
    os.makedirs(os.path.dirname(target) or os.curdir, exist_ok=True)
    tmp = f"{target}.tmp.{os.getpid()}"
    ckptr.save(tmp, state, force=True)
    ckptr.wait_until_finished()
    _test_pause("stage")
    _fsync_tree(tmp)
    _swap_into_place(tmp, target)


def save(path: str, state: Any, step: Optional[int] = None, force: bool = True) -> None:
    """Write a checkpoint from rank 0 only; other ranks return immediately
    (reference contract: 'save checkpoints only on worker 0 to prevent other
    workers from corrupting them', README.md:117-119). A marker barrier via
    the eager engine keeps ranks from racing ahead of an unfinished save."""
    import numpy as np

    # Uninitialized == single-process (a plain post-training export script);
    # rank 0 writes, and only a multi-rank world needs the barrier.
    if not basics.is_initialized() or basics.rank() == 0:
        save_local(path, state, step)
    if basics.is_initialized() and basics.size() > 1:
        # barrier: everyone waits until rank 0's save completed
        basics.engine().run("allreduce", np.zeros(1), f"ckpt.barrier.{path}.{step}")


def restore(path: str, template: Any = None, step: Optional[int] = None,
            verify: bool = True) -> Any:
    """Read a checkpoint on every rank (all ranks share the filesystem on a
    pod slice). ``template`` gives dtypes/shapes for orbax.

    With ``verify=True`` (default) every rank hashes the restored leaves and
    the digests are allgathered and compared, so ranks that read divergent
    files (stale NFS caches, non-shared filesystems) fail loudly instead of
    training from inconsistent state. The check is collective: it requires
    every rank to call restore(). If you instead restore on rank 0 only and
    broadcast (hvd.jax.broadcast_parameters / broadcast_resume_state), pass
    ``verify=False`` — the broadcast itself is the consistency guarantee."""
    ocp = _ocp()
    ckptr = ocp.StandardCheckpointer()
    target = os.path.join(os.path.abspath(path), f"step_{step}") \
        if step is not None else os.path.abspath(path)
    if not os.path.exists(target):
        # The writer may have been killed between the commit's two renames:
        # adopt a complete staged copy if one is waiting (crash-consistent
        # commits, ISSUE 8).
        _heal_interrupted(target)
    state = ckptr.restore(target, template) if template is not None \
        else ckptr.restore(target)
    if verify:
        _verify_cross_rank_digest(state, f"{path}.{step}")
    return state


def _verify_cross_rank_digest(state: Any, tag: str) -> None:
    """SHA-256 over every restored leaf (dtype + shape + bytes), allgathered
    through the eager engine; raises if any rank restored different state.
    Uninitialized == single-process (the same plain-export convention as
    save()): there is no peer to diverge from, so nothing to verify."""
    if not basics.is_initialized() or basics.size() == 1:
        return
    import hashlib

    import jax
    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(state):
        arr = np.asarray(leaf)
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    mine = np.frombuffer(h.digest(), dtype=np.uint8).astype(np.float64)
    # Bounded: the check is collective, and a caller that restores on rank 0
    # only (the verify=False flow) must get an actionable error, not a hang.
    eng = basics.engine()
    handle = eng.enqueue("allgather", mine, f"ckpt.digest.{tag}")
    timeout = float(os.environ.get("HOROVOD_CKPT_VERIFY_TIMEOUT", "120"))
    try:
        gathered = np.asarray(eng.synchronize(handle, timeout=timeout))
    except Exception as exc:
        from .common.engine import HorovodInternalError

        raise HorovodInternalError(
            f"checkpoint digest verification did not complete within "
            f"{timeout:.0f}s — restore(verify=True) is collective and every "
            f"rank must call it; if you restore on rank 0 only and "
            f"broadcast, pass verify=False"
        ) from exc
    gathered = gathered.reshape(basics.size(), mine.size)
    bad = [r for r in range(basics.size())
           if not np.array_equal(gathered[r], gathered[0])]
    if bad:
        from .common.engine import HorovodInternalError

        raise HorovodInternalError(
            f"checkpoint restore diverged across ranks: ranks {bad} read "
            f"different state than rank 0 (non-shared or stale filesystem?); "
            f"restore on rank 0 only and broadcast, or fix the filesystem"
        )


def save_sharded(path: str, state: Any, plan, step: Optional[int] = None) -> None:
    """Checkpoint a SHARDED training state (ISSUE 14, docs/sharded.md).

    ``state`` is any pytree whose sharded sub-states are
    :class:`horovod_tpu.parallel.sharded.ShardedBuckets` (params, optimizer
    moments — whatever ``optimizer.init`` produced); ``plan`` is the
    :class:`ShardPlan` they were partitioned with. The checkpoint stores
    the CONSOLIDATED full leaves, so it is mesh-shape independent: restore
    onto any ('batch','shard') shape, including plain DP. Consolidation
    also drops the zero-pad tail — pad garbage can never be carried in a
    checkpoint. Rank-0-writes + completion barrier, exactly like
    :func:`save`."""
    from .parallel import sharded as _sharded

    save(path, _sharded.unshard_tree(state, plan), step)


def restore_sharded(path: str, template: Any, plan,
                    step: Optional[int] = None, verify: bool = True) -> Any:
    """Restore a checkpoint written by :func:`save_sharded` (or a plain DP
    :func:`save` of the same pytree) INTO a sharded layout: the full leaves
    are read with the consolidated template, then re-partitioned to
    ``plan`` with fresh zero padding. ``template`` is the live sharded
    state (it locates every :class:`ShardedBuckets` position); ``plan``
    may differ from the one the checkpoint was written under — that is
    what makes resume-after-reshape work. Same cross-rank digest
    verification contract as :func:`restore`."""
    from .parallel import sharded as _sharded

    full = restore(path, _sharded.unshard_tree(template, plan), step,
                   verify=verify)
    out = _sharded.reshard_tree(full, template, plan)
    # Re-place every restored leaf on the template leaf's sharding: a
    # restored host array left on the default device would make the next
    # jitted step compile a second executable (different input placement),
    # and two executables are allowed to differ by an ULP — which would
    # break the save->restore->resume bitwise-exactness contract the tests
    # pin. With matching shardings the resumed step reuses the SAME
    # compiled program as the uncheckpointed run.
    import jax

    def _place(t, r):
        if isinstance(t, jax.Array) and not isinstance(t, jax.core.Tracer):
            try:
                return jax.device_put(r, t.sharding)
            except (ValueError, AttributeError):
                return r
        return r

    return jax.tree_util.tree_map(_place, template, out)


def merge_stacked_stats(stats: Any, axis: int = 0) -> Any:
    """Consolidate per-device batch statistics that carry a leading device
    dimension (the single-process sharded layout: chip_smoke.py's ResNet
    step keeps one BN-stat row per mesh position) into single-replica values
    by averaging over ``axis``. Pure function — usable inside or outside
    jit."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda t: jnp.mean(t, axis=axis), stats)


def average_stats_across_ranks(stats: Any) -> Any:
    """Consolidate per-PROCESS batch statistics (the multi-process eager
    layout: each rank tracked its own BN running stats, reference-style) by
    averaging through the eager engine. Collective: every rank must call."""
    import numpy as np

    if _world_size() == 1:
        return stats
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(stats)
    # Enqueue everything, then synchronize: the leaves pipeline through the
    # engine's fusion machinery in one pass instead of paying one collective
    # round trip per BN layer (same pattern as _verify_cross_rank_digest).
    eng = basics.engine()
    arrs = [np.asarray(leaf) for leaf in leaves]
    handles = [eng.enqueue("allreduce", a.astype(np.float64),
                           f"export.stats.{i}", average=True)
               for i, a in enumerate(arrs)]
    out = [np.asarray(eng.synchronize(h)).reshape(a.shape).astype(a.dtype)
           for h, a in zip(handles, arrs)]
    return jax.tree_util.tree_unflatten(treedef, out)


def export_for_inference(path: str, state: Any, *,
                         drop: tuple = ("opt_state",),
                         stats_key: str = "batch_stats",
                         stacked_stats_axis: Optional[int] = None,
                         cross_rank: Optional[bool] = None) -> Any:
    """Strip the distributed machinery from a training state and write a
    single-replica serving checkpoint (the reference's optimize-for-inference
    step, /root/reference/docs/inference.md:1-16 — there a TF graph pass that
    removes HorovodAllreduce ops; here the training-only state).

    - ``drop``: top-level keys removed (optimizer state, step counters you
      don't serve with).
    - ``stats_key``: per-rank/per-device batch statistics to consolidate.
      With ``stacked_stats_axis`` the leaves carry a leading device dim and
      are averaged over it (single-process sharded layout); with
      ``cross_rank`` (default: whenever the world is larger than one) each
      process's stats are averaged through the eager engine (collective —
      every rank must call export_for_inference).
    - Writes on rank 0 only, with the same completion barrier as
      :func:`save`; returns the serving state on every rank.

    The result restores with :func:`load_for_inference` in a process that
    never imports the distributed pieces, let alone calls ``hvd.init()``.
    """
    if not isinstance(state, dict):
        raise TypeError(f"state must be a dict of top-level keys, got {type(state)}")
    serving = {k: v for k, v in state.items() if k not in set(drop)}
    if stats_key in serving:
        stats = serving[stats_key]
        if stacked_stats_axis is not None:
            stats = merge_stacked_stats(stats, axis=stacked_stats_axis)
        if cross_rank if cross_rank is not None else _world_size() > 1:
            stats = average_stats_across_ranks(stats)
        serving[stats_key] = stats
    save(path, serving)
    return serving


def _world_size() -> int:
    return basics.size() if basics.is_initialized() else 1


def load_for_inference(path: str, template: Any = None) -> Any:
    """Restore a serving checkpoint written by :func:`export_for_inference`.
    Standalone by design: no ``hvd.init()``, no collectives, no engine — a
    fresh serving process restores and runs a plain single-replica forward
    (the property the reference's inference doc is about: the serving side
    must not need the Horovod library's ops)."""
    ocp = _ocp()
    ckptr = ocp.StandardCheckpointer()
    target = os.path.abspath(path)
    return ckptr.restore(target, template) if template is not None \
        else ckptr.restore(target)


def latest_step(path: str) -> Optional[int]:
    """Highest step_N subdirectory under ``path`` (resume-epoch discovery,
    reference examples/pytorch_imagenet_resnet50.py scans for existing
    checkpoint files the same way)."""
    try:
        steps = [int(d.split("_", 1)[1]) for d in os.listdir(path)
                 if d.startswith("step_") and d.split("_", 1)[1].isdigit()]
    except OSError:
        return None
    return max(steps) if steps else None


def broadcast_resume_state(state: Any, root_rank: int = 0) -> Any:
    """Host-side broadcast of restored state (epoch counters, small pytrees)
    through the eager engine — for values needed OUTSIDE jit (the in-jit
    path is hvd.jax.broadcast_parameters)."""
    import numpy as np

    if basics.size() == 1:
        return state
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(state)
    out = []
    for i, leaf in enumerate(leaves):
        arr = np.asarray(leaf)
        res = basics.engine().run("broadcast", arr, f"ckpt.resume.{i}",
                                  root_rank=root_rank)
        out.append(np.asarray(res).reshape(arr.shape).astype(arr.dtype)
                   if arr.shape else type(leaf)(res))
    return jax.tree_util.tree_unflatten(treedef, out)
