"""``BENCHMARK.json`` against the contract it is written to, and the proof that
the harness is driven by data: a throw-away configuration, traffic mix and
per-layer metric, added as NEW files plus NEW entries in a temporary copy,
make a new cell that runs - with no edit to any file that was there."""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import sys
import textwrap

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|head_dim|"
                   r"head_size|expansion|experts_per_tok|^dim$|num_filters)")


@pytest.fixture(scope="module")
def manifest():
    return run.load_manifest()


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(manifest["paths"]) <= 16
    for path in manifest["paths"]:
        assert PATH.match(path) and not path.startswith("/") and ".." not in path
        assert os.path.isdir(os.path.join(REPO, path))
    assert 1 <= len(manifest["command"]) <= 32
    for word in manifest["command"]:
        assert one_line(word) and not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(REPO, word)):
            assert any(word.startswith(p + "/") for p in manifest["paths"])
    seconds = manifest["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    # a full check of the full 24 cells has to fit the driver's 43200 s
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_file_under_paths_has_an_allowed_name(manifest):
    for path in manifest["paths"]:
        for folder, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), REPO)
                assert PATH.match(rel), rel


def test_configs(manifest):
    configs = manifest["configs"]
    assert 1 <= len(configs) <= 24
    assert len({c["name"] for c in configs}) == len(configs)
    assert len({c["file"] for c in configs}) == len(configs)
    used = {w["config"] for w in manifest["workloads"]}
    for c in configs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
        data = run.load_json(os.path.join(REPO, c["file"]))
        assert data["source"] == c["source"]
        assert os.path.isfile(os.path.join(REPO, os.path.splitext(c["file"])[0] + ".py"))


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        resolved = run.resolve_cell(manifest, w["name"])
        assert resolved["traffic"]["fence_every"] >= 1
        assert resolved["traffic"]["fence_lag"] >= 0
        for function in ("build", "reference", "cost"):
            assert callable(getattr(resolved["module"], function))
        # every cell reports setup_s, another end-to-end metric and a layer's
        names = {m["name"] for m in resolved["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2 and resolved["per_layer"]
        assert resolved["config"]["throughput_metric"] in names
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def test_metrics(manifest):
    end_to_end, per_layer = manifest["end_to_end"], manifest["per_layer"]
    assert 1 <= len(end_to_end) <= 16 and 1 <= len(per_layer) <= 128
    names = [m["name"] for m in end_to_end + per_layer]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in manifest["workloads"]}

    def reported_in(metric):
        assert set(metric.get("workloads", cells)) <= cells
        return set(metric.get("workloads", cells))

    for m in end_to_end:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in end_to_end)
    by_name = {m["name"]: m for m in end_to_end}
    readers = os.path.join(REPO, "benchmarks", "layer_metrics")
    for m in per_layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and one_line(m["layer"])
        assert callable(run.load_module(os.path.join(readers, m["name"] + ".py")).read)
        # reported only where the end-to-end metric it moves is
        assert reported_in(m) <= reported_in(by_name[m["moves"]]), m["name"]
        if m["name"].endswith("_roofline_pct") or m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in end_to_end + per_layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    # metrics of one layer give the same layer, letter for letter: a layer
    # that differs only by case or spacing from another is a typo
    layers = {m["layer"] for m in per_layer}
    assert len({re.sub(r"\W", "", l).lower() for l in layers}) == len(layers)


TOY_CONFIG = textwrap.dedent('''
    """A throw-away configuration: a two-layer MLP under the same optimizer
    wrapper, with the same three functions as every configuration module."""
    from benchmarks.reduce_trace import SCOPE_FWD_BWD, SCOPE_OPTIMIZER


    def _parts(config, traffic, seed):
        import jax, jax.numpy as jnp, optax

        def init(key):
            k1, k2 = jax.random.split(key)
            return {"w1": jax.random.normal(k1, (config["features"], config["width"])) * 0.1,
                    "w2": jax.random.normal(k2, (config["width"], 1)) * 0.1}

        def batch(key):
            return (jax.random.normal(key, (traffic["global_batch"], config["features"])),)

        def loss_fn(params, x):
            return jnp.mean((jnp.tanh(x @ params["w1"]) @ params["w2"] - 1.0) ** 2)

        key = jax.random.PRNGKey(seed)
        return init, batch, loss_fn, optax.sgd(config["learning_rate"]), key


    def build(config, traffic, mesh, seed, **overrides):
        import jax, optax
        from jax.sharding import NamedSharding, PartitionSpec as P
        import horovod_tpu as hvd
        from horovod_tpu.compat import shard_map

        init, batch, loss_fn, sgd, key = _parts(config, traffic, seed)
        opt = hvd.jax.DistributedOptimizer(sgd)
        rep, data = NamedSharding(mesh, P()), NamedSharding(mesh, P(hvd.HVD_AXIS))
        params = jax.jit(init, out_shardings=rep)(key)
        opt_state = jax.jit(opt.init, out_shardings=rep)(params)
        x, = jax.jit(batch, out_shardings=(data,))(jax.random.fold_in(key, 1))

        def train_step(params, opt_state, x):
            with jax.named_scope(SCOPE_FWD_BWD):
                loss, grads = jax.value_and_grad(loss_fn)(params, x)
            with jax.named_scope(SCOPE_OPTIMIZER):
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, jax.lax.pmean(loss, hvd.HVD_AXIS)

        step = jax.jit(shard_map(train_step, mesh=mesh,
                                 in_specs=(P(), P(), P(hvd.HVD_AXIS)),
                                 out_specs=(P(), P(), P()), check_vma=False),
                       donate_argnums=(0, 1))
        return {"step": step, "state": [params, opt_state], "batch": (x,),
                "samples_per_step": traffic["global_batch"]}


    def reference(config, traffic, mesh, seed, **overrides):
        import jax
        from benchmarks.reference import plain_step

        init, batch, loss_fn, sgd, key = _parts(config, traffic, seed)
        params = jax.jit(init)(key)
        x, = jax.jit(batch)(jax.random.fold_in(key, 1))
        per = traffic["global_batch"] // mesh.size
        shards = [(x[r * per:(r + 1) * per],) for r in range(mesh.size)]

        @jax.jit
        def grad_fn(params, aux, x):
            loss, grads = jax.value_and_grad(loss_fn)(params, x)
            return loss, aux, grads

        return plain_step.reference_steps(
            grad_fn, sgd, params, [None] * len(shards), shards, seed)


    def cost(config, traffic, chips):
        per_chip = traffic["global_batch"] // chips
        return {"model_flops": 3 * 2 * per_chip * config["features"] * config["width"],
                "kernel": None}
''')
TOY_READER = textwrap.dedent('''
    """A throw-away per-layer metric: dispatches counted in the window."""


    def read(run):
        return float(len(run["dispatch_s"])) or None
''')


def _digests(root):
    out = {}
    for folder, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_a_new_cell_is_new_files_and_new_entries_only(manifest, tmp_path, hvd,
                                                      monkeypatch, capsys):
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "benchmarks"), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(root)

    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", "toy.json"), "w") as f:
        json.dump({"source": "throw-away", "features": 16, "width": 32,
                   "learning_rate": 0.1, "throughput_metric": "toy_rows_per_s",
                   "tolerance": {"loss_rel": 1e-5, "update_rel": 1e-4,
                                 "why": "f32 on both sides"}}, f)
    with open(os.path.join(bench, "configs", "toy.py"), "w") as f:
        f.write(TOY_CONFIG)
    with open(os.path.join(bench, "traffic", "toy_mix.json"), "w") as f:
        json.dump({"global_batch": 64, "fence_every": 3, "fence_lag": 1,
                   "warmup_groups": 1,
                   "trace_groups": 1, "reference": "step"}, f)
    with open(os.path.join(bench, "layer_metrics", "toy_dispatches.py"), "w") as f:
        f.write(TOY_READER)
    # new ENTRIES; every entry that was there stays as it was
    grown = json.loads(json.dumps(manifest))
    grown["configs"].append({"name": "toy", "source": "throw-away",
                             "file": "benchmarks/configs/toy.json",
                             "reduced": [], "why": "proves the harness is data-driven"})
    grown["workloads"].append({"name": "toy_4chip", "config": "toy",
                               "traffic": "toy_mix", "chips": 4, "why": "toy"})
    grown["end_to_end"].append({"name": "toy_rows_per_s", "unit": "rows/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock", "workloads": ["toy_4chip"]})
    grown["per_layer"].append({"name": "toy_dispatches", "unit": "steps",
                               "better": "higher", "source": "program_counter",
                               "layer": "Entry: the step loop",
                               "moves": "toy_rows_per_s", "workloads": ["toy_4chip"]})
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert grown[kind][:len(manifest[kind])] == manifest[kind]

    resolved = run.resolve_cell(grown, "toy_4chip", root=root)
    # peak_hbm_gib, step_ms and setup_s list no cells, so the new one reports them
    assert {m["name"] for m in resolved["end_to_end"]} == {
        "toy_rows_per_s", "step_ms", "peak_hbm_gib", "setup_s"}
    monkeypatch.setattr(run, "hbm_bytes", lambda devices: 1 << 30)
    result = run.run_cell(resolved, jax.devices()[:4], seed=5, seconds=0.0, trace=0)
    assert result["correct"] is True and result["failed"] == 0, capsys.readouterr().out
    assert result["metrics"]["toy_rows_per_s"]["value"] > 0
    # its new reader is found by name, beside the readers that were there
    toy_only = dict(resolved, per_layer=[m for m in resolved["per_layer"]
                                         if m["name"] in ("toy_dispatches",
                                                          "host_dispatch_ms_per_step")])
    values = run.read_layer_metrics(toy_only, {"dispatch_s": [0.001] * 6})
    assert values["toy_dispatches"] == {"value": 6.0, "unit": "steps"}
    assert values["host_dispatch_ms_per_step"]["value"] == pytest.approx(1.0)

    after = _digests(root)
    assert {k: after[k] for k in before} == before      # no existing file edited
    assert sorted(set(after) - set(before)) == [
        "benchmarks/configs/toy.json", "benchmarks/configs/toy.py",
        "benchmarks/layer_metrics/toy_dispatches.py", "benchmarks/traffic/toy_mix.json"]
