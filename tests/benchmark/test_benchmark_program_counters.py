"""The six ``program_counter`` readers: each against a hand-made compile
ledger or registry gives the hand sum, leaves out the harness's after-window
re-lowering of the step, and gives ``None`` where the program has nothing to
say; the two exchange readers equal the plan of a real optimizer step."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import run  # noqa: E402
from horovod_tpu.utils import compile_cache  # noqa: E402

READERS = os.path.join(REPO, "benchmarks", "layer_metrics")
TRACE, LOWER, BACKEND = (f"/jax/core/compile/{e}_duration" for e in (
    "jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile"))
LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


def read(name, context=None):
    return run.load_module(os.path.join(READERS, name + ".py")).read(context or {})


def compiled(name, trace, lower, backend, load=None):
    """One function through its phases, as JAX reports them."""
    for event, seconds, wrapped in ((TRACE, trace, name), (LOWER, lower, f"jit({name})")):
        if seconds is not None:
            compile_cache._on_start(event, 0.0, fun_name=wrapped)
            compile_cache._on_duration(event, seconds, fun_name=wrapped)
    if backend is not None:
        compile_cache._on_start(BACKEND, 0.0, fun_name=f"jit({name})")
        if load is not None:
            compile_cache._on_duration(LOAD, load)
        compile_cache._on_duration(BACKEND, backend, fun_name=f"jit({name})")


@pytest.fixture()
def ledger(monkeypatch):
    monkeypatch.setattr(compile_cache, "_ledger", compile_cache._Ledger())


def set_up():
    compiled("init", 1.0, 2.0, 4.0)                 # compiled: a miss
    compiled("grad_fn", 3.0, 1.5, 2.5, load=2.0)    # loaded from the cache
    compiled("train_step", 5.0, 2.0, 3.0, load=2.5)
    compiled("_take", 0.25, 0.25, 0.5)              # sub-second: never cached


SET_UP = {"trace_lower_s": 1 + 2 + 3 + 1.5 + 5 + 2 + 0.25 + 0.25,
          "backend_compile_s": (4 + 2.5 + 3 + 0.5) - (2 + 2.5),
          "cache_load_s": 2 + 2.5,
          "compile_cache_miss_count": 2.0}
AFTER_WINDOW = {
    "nothing": lambda: None,
    "relowered and loaded": lambda: compiled("train_step", 0.0, 2.0, 3.0, load=2.75),
    "relowered and compiled": lambda: compiled("train_step", 4.0, 2.0, 30.0),
    "traced only, all cached in process": lambda: compiled("train_step", 0.0, None, None),
}


@pytest.mark.parametrize("after", sorted(AFTER_WINDOW))
@pytest.mark.parametrize("name", sorted(SET_UP))
def test_compile_readers_give_the_set_up_sums(name, after, ledger):
    set_up()
    AFTER_WINDOW[after]()
    assert read(name) == pytest.approx(SET_UP[name])


def test_the_step_compiled_last_in_set_up_stays_in(ledger):
    """Set-up's own compilation of the step is never taken for the
    harness's: the cut stops at the function's first backend entry."""
    compiled("init", 1.0, 2.0, 4.0)
    compiled("train_step", 5.0, 2.0, 3.0)
    assert read("trace_lower_s") == pytest.approx(10.0)
    compiled("train_step", 0.0, 2.0, 3.0, load=2.0)
    assert read("trace_lower_s") == pytest.approx(10.0)
    assert read("cache_load_s") == 0.0
    assert read("compile_cache_miss_count") == 2.0


@pytest.mark.parametrize("name", sorted(SET_UP))
def test_compile_readers_give_none_on_an_empty_ledger(name, ledger):
    assert read(name) is None


@pytest.mark.parametrize("name", sorted(SET_UP))
def test_compile_readers_give_none_for_a_program_without_a_ledger(name, monkeypatch):
    monkeypatch.delattr(compile_cache, "compile_ledger")
    assert read(name) is None


def test_exchange_readers_against_a_hand_made_registry(hvd):
    reg = hvd.metrics.registry()
    reg.reset()
    assert read("exchange_bytes_per_step") is None
    assert read("exchange_calls_per_step") is None
    hvd.metrics.record_wire_plan("bf16", [(4 << 20, True, 2 << 20),
                                          (1 << 20, False, 0)])
    reg.gauge("horovod_fusion_buckets").set(2)
    assert read("exchange_bytes_per_step") == 3.0
    assert read("exchange_calls_per_step") == 2.0
    reg.gauge("horovod_compiled_hierarchical").set(1)
    assert read("exchange_calls_per_step") == 4.0      # one per tier
    reg.reset()


@pytest.mark.parametrize("num_buckets", [1, 4])
def test_exchange_readers_equal_the_plan_of_an_optimizer_step(hvd, num_buckets):
    mesh = hvd.data_parallel_mesh(jax.devices()[:4])
    opt = hvd.jax.DistributedOptimizer(optax.sgd(0.1), num_buckets=num_buckets)
    params = {f"w{i}": jnp.ones((32, 8 + i)) for i in range(6)}

    def train_step(params, opt_state, x):
        grads = jax.grad(lambda p: sum(jnp.mean(x @ w) for w in p.values()))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    step = jax.jit(hvd.compat.shard_map(
        train_step, mesh=mesh, in_specs=(P(), P(), P(hvd.HVD_AXIS)),
        out_specs=(P(), P()), check_vma=False))
    step(params, opt.init(params), jnp.ones((8, 32)))
    plan = hvd.metrics.last_plan()
    assert len(plan) == num_buckets
    assert read("exchange_calls_per_step") == float(num_buckets)
    assert read("exchange_bytes_per_step") == pytest.approx(
        sum(nbytes for _, nbytes in plan) / 2 ** 20)
    assert sum(nbytes for _, nbytes in plan) == sum(4 * 32 * (8 + i) for i in range(6))


@pytest.mark.parametrize("cleared", [False, True])
def test_the_harness_relowering_of_a_real_step_moves_no_compile_reader(hvd, cleared):
    """What ``run.py`` does after a traced window, on a real jitted step: all
    of it cached in the process (one ``trace`` entry of no length), or, with
    JAX's caches cleared, traced, lowered and compiled again."""
    compile_cache.install_compile_ledger()
    mesh = hvd.data_parallel_mesh(jax.devices()[:4])
    opt = hvd.jax.DistributedOptimizer(optax.sgd(0.1))
    rep, data = NamedSharding(mesh, P()), NamedSharding(mesh, P(hvd.HVD_AXIS))
    params = {"w": jnp.ones((32, 8))}
    state = jax.device_put([params, opt.init(params)], rep)
    batch = (jax.device_put(jnp.ones((8, 32)), data),)

    def train_step(params, opt_state, x):
        grads = jax.grad(lambda p: jnp.mean(jnp.tanh(x @ p["w"])))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    step = jax.jit(hvd.compat.shard_map(
        train_step, mesh=mesh, in_specs=(P(), P(), P(hvd.HVD_AXIS)),
        out_specs=(P(), P()), check_vma=False))
    *state, = step(*state, *batch)

    # set-up goes on after the step's compile, as in a run: under a name this
    # process has not compiled before (the readers leave out the ledger's LAST
    # name back to its first compile, and a worker that has run other tests
    # has compiled ``train_step`` and ``_reduce_sum`` many times)
    def set_up_goes_on(w):
        return w.sum()

    set_up_goes_on.__name__ = f"set_up_goes_on_{cleared}"
    float(jax.jit(set_up_goes_on)(state[0]["w"]))
    assert compile_cache.compile_ledger()["entries"][-1]["fun_name"] == (
        set_up_goes_on.__name__)
    before = {name: read(name) for name in SET_UP}
    # by the ledger's totals: ``entries`` is a deque capped at
    # ``LEDGER_ENTRIES``, full in a whole run of the tests, so its length
    # says nothing about what one call added
    entries = sum(compile_cache.compile_ledger()["count"].values())
    if cleared:
        jax.clear_caches()
    step.lower(*run.abstract(state), *run.abstract(batch)).compile().as_text()
    ledger = compile_cache.compile_ledger()
    n_added = sum(ledger["count"].values()) - entries
    assert 0 < n_added < compile_cache.LEDGER_ENTRIES
    added = ledger["entries"][-n_added:]
    assert added and {e["fun_name"] for e in added} == {"train_step"}
    assert ("backend" in {e["phase"] for e in added}) == cleared
    assert {name: read(name) for name in SET_UP} == pytest.approx(before)
