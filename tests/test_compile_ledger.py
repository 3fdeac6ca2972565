"""The compile ledger (utils/compile_cache.py): one entry per outermost
tracing, lowering, backend and cache-load phase that JAX reports, the same
totals in the metrics registry, and listeners installed once."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
from jax._src import monitoring

from horovod_tpu.metrics import registry, validate_snapshot
from horovod_tpu.utils import compile_cache
from horovod_tpu.utils.compile_cache import (PHASES, compile_ledger,
                                             install_compile_ledger)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def entries_of(name):
    return [e for e in compile_ledger()["entries"] if e["fun_name"] == name]


def test_a_fresh_function_adds_one_entry_per_phase_and_a_second_call_none():
    install_compile_ledger()

    def ledger_probe_fresh(x):
        return jnp.tanh(x) * 3 + jnp.sin(x)     # nested jits: folded in

    step = jax.jit(ledger_probe_fresh)
    before = compile_ledger()
    step(jnp.ones((5, 3)))
    mine = entries_of("ledger_probe_fresh")
    assert [e["phase"] for e in mine if e["phase"] != "cache_load"] == [
        "trace", "lower", "backend"]
    assert all(e["seconds"] >= 0 for e in mine)
    stamps = [e["stamp"] for e in mine]
    assert stamps == sorted(stamps)     # perf_counter, in order
    after = compile_ledger()
    for phase in ("trace", "lower", "backend"):
        assert after["count"][phase] > before["count"][phase]
        assert after["seconds"][phase] >= before["seconds"][phase]
    step(jnp.ones((5, 3)))
    assert compile_ledger()["count"] == after["count"]
    assert entries_of("ledger_probe_fresh") == mine


def test_nested_phases_are_folded_into_the_outermost():
    install_compile_ledger()
    inner = jax.jit(lambda x: x * 2)

    def ledger_probe_outer(x):
        return inner(inner(x)) + 1

    x = jnp.ones(7)     # made before the count: an eager op compiles too
    before = compile_ledger()["count"]
    jax.jit(ledger_probe_outer)(x)
    after = compile_ledger()["count"]
    assert after["trace"] == before["trace"] + 1
    assert after["lower"] == before["lower"] + 1
    assert after["backend"] == before["backend"] + 1


def test_installing_twice_registers_the_listeners_once():
    install_compile_ledger()
    install_compile_ledger()
    assert monitoring.get_event_duration_listeners().count(
        compile_cache._on_duration) == 1
    assert monitoring.get_event_listeners().count(compile_cache._on_event) == 1
    assert monitoring.get_scalar_listeners().count(compile_cache._on_start) == 1


def test_the_registry_carries_the_three_series_and_the_snapshot_is_valid():
    install_compile_ledger()
    jax.jit(lambda x: x - 41)(jnp.ones(3))
    # the two cache counters appear with their first event: stand one in
    compile_cache._on_event("/jax/compilation_cache/cache_hits")
    compile_cache._on_event("/jax/compilation_cache/cache_misses")
    snap = registry().snapshot()
    assert validate_snapshot(snap) == []
    counters = snap["counters"]
    for phase in ("trace", "lower", "backend"):
        assert counters[f'horovod_compile_seconds_total{{phase="{phase}"}}'] >= 0
    assert counters["horovod_compile_cache_hits_total"] >= 1
    assert counters["horovod_compile_cache_misses_total"] >= 1
    text = registry().render_prometheus()
    assert "# TYPE horovod_compile_seconds_total counter" in text


def test_the_ledger_keeps_the_newest_entries_and_counts_all(monkeypatch):
    monkeypatch.setattr(compile_cache, "LEDGER_ENTRIES", 4)
    monkeypatch.setattr(compile_cache, "_ledger", compile_cache._Ledger())
    for i in range(6):
        compile_cache._on_duration(
            "/jax/core/compile/backend_compile_duration", 0.5,
            fun_name=f"jit(f{i})")
    ledger = compile_ledger()
    assert [e["fun_name"] for e in ledger["entries"]] == ["f2", "f3", "f4", "f5"]
    assert ledger["count"]["backend"] == 6
    assert ledger["seconds"]["backend"] == 3.0
    assert set(ledger["seconds"]) == set(ledger["count"]) == set(PHASES)


CACHE_SCRIPT = textwrap.dedent('''
    import json, sys
    import jax, jax.numpy as jnp
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_compilation_cache_dir", sys.argv[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from horovod_tpu.utils.compile_cache import (compile_ledger,
                                                 install_compile_ledger)
    install_compile_ledger()

    def ledger_probe_cached(x):
        return jnp.cos(x) @ x.T

    x = jnp.ones((4, 4))
    jax.jit(ledger_probe_cached)(x).block_until_ready()
    first = compile_ledger()
    jax.clear_caches()      # the same program once more, in this process
    jax.jit(ledger_probe_cached)(x).block_until_ready()
    print("LEDGERS", json.dumps([first, compile_ledger()]))
''')


def test_a_second_compile_of_the_same_program_loads_from_the_cache(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", CACHE_SCRIPT, str(tmp_path / "cache")],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("LEDGERS "))
    first, second = json.loads(line[len("LEDGERS "):])

    def mine(ledger, phase):
        return [e for e in ledger["entries"]
                if e["fun_name"] == "ledger_probe_cached" and e["phase"] == phase]

    assert first["cache_hits"] == 0 and first["cache_misses"] >= 1
    assert not mine(first, "cache_load") and len(mine(first, "backend")) == 1
    assert second["cache_hits"] >= 1
    loads, backends = mine(second, "cache_load"), mine(second, "backend")
    assert len(loads) == 1 and len(backends) == 2
    assert 0 < loads[0]["seconds"] <= backends[1]["seconds"]
    assert loads[0]["stamp"] <= backends[1]["stamp"]
    assert second["seconds"]["cache_load"] > 0
    # programs compiled, not loaded: every backend entry less every load
    compiled = lambda l: l["count"]["backend"] - l["count"]["cache_load"]
    assert second["count"]["cache_load"] == second["cache_hits"]
    assert compiled(second) >= compiled(first) >= 1
