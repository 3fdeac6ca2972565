"""The ``granite_4_0_h_micro`` configuration at a tiny size on the 4-device
virtual CPU mesh: the cell end to end through ``run.run_cell``, wrong variants
of the model that are not ``correct``, a lower precision in the scan that
fails the float32 limit, the file's keys against the catalog's, the cost
functions against hand counts, the four ``Mamba-2 mixer`` readers on a
hand-made ``breakdown``, and the two copies of the plain reference held to
the same outputs."""

from __future__ import annotations

import copy
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

from benchmarks import run, ssd_cost  # noqa: E402

CELL = "granite4h_long_1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# Tiny sizes under the file's own keys; six layers as the cell runs them
# (five Mamba-2, the sixth attention), every kind of leaf present.
TINY = {"vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "mamba_n_heads": 4, "mamba_d_head": 32,
        "mamba_d_state": 16, "mamba_chunk_size": 32,
        "shared_intermediate_size": 96, "layers": 6}
TRAFFIC = {"seq": 128, "global_rows": 4, "fence_every": 2, "fence_lag": 1,
           "warmup_groups": 1, "trace_groups": 1, "reference_prefix": 96,
           "scan_slice": 32, "flash_slice": 64}


def resolved_tiny():
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    resolved["config"] = {**resolved["config"], **copy.deepcopy(TINY)}
    resolved["traffic"] = dict(TRAFFIC)
    return resolved


@pytest.fixture()
def cpu_memory(monkeypatch):
    monkeypatch.setattr(run, "hbm_bytes", lambda devices: 3 << 30)


def observed_of(out):
    return json.loads(out.split("kernels vs f32 reference (share of "
                                "max|ref|): ")[1].splitlines()[0])


def test_cell_end_to_end_tiny(hvd, cpu_memory, capsys):
    resolved = resolved_tiny()
    result = run.run_cell(resolved, jax.devices()[:4], seed=3, seconds=0.0,
                          trace=0, flash_interpret=True)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    assert "INCORRECT" not in out
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tok_per_s_per_chip", "step_ms",
                                      "peak_hbm_gib", "setup_s"}
    for metric in result["metrics"].values():
        assert math.isfinite(metric["value"]) and metric["value"] > 0
    json.dumps(result)
    observed = observed_of(out)
    assert observed["scan"]["f32"] <= 2e-6 < observed["scan"]["bf16"] <= 1e-2
    assert set(observed["flash"]) == {"out", "dq", "dk", "dv"}
    assert observed["reference_forms"]["logits"] <= 2e-6
    assert observed["f32"]["logits"] <= 2e-6 and observed["f32"]["loss"] <= 1e-6
    leaves = observed["f32"]["grads_rel"]
    assert len(leaves) == 5 * 13 + 9 + 2       # every leaf, the tied E once
    assert max(leaves.values()) <= 2e-5
    assert set(observed["bf16"]["grads_l2_rel"]) == set(leaves)
    assert 1e-3 < max(observed["bf16"]["grads_l2_rel"].values()) <= 1e-1
    assert 1e-4 < observed["bf16"]["logits"] <= 2e-2
    # the step traced the scan at the file's chunk: the fourth reader's gauge
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_ssd_chunk_len"] == TINY["mamba_chunk_size"]


FAULTS = {
    "rotary_left_on": {"rope": True},
    "logits_scaling_dropped": {"logits_scaling": 1.0},
    "residual_multiplier_dropped": {"residual_multiplier": 1.0},
}


def check_alone(hvd, **tiny):
    """The configuration's three checks without the step: two layers, one of
    each kind, so that a fault costs two compilations and not six."""
    resolved = resolved_tiny()
    resolved["config"].update(layer_types=["mamba", "attention"], layers=2,
                              **tiny)
    mesh = hvd.data_parallel_mesh(jax.devices()[:4])
    return resolved, lambda: resolved["module"].reference(
        resolved["config"], resolved["traffic"], mesh, 3, flash_interpret=True)


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["norm_before_gate"])
def test_a_wrong_variant_is_not_correct(hvd, monkeypatch, fault):
    """What the float32 leg exists for: a model that is not Granite's. The
    reference stays what it is; the system's model is built wrong."""
    resolved, check = check_alone(hvd)
    module = resolved["module"]
    real_model = module._model
    if fault == "norm_before_gate":     # Mamba-1's order: rms(y) * silu(z)
        from horovod_tpu.models import mamba

        def norm_first(y, z, scale, groups, eps):
            b, t, inner = y.shape
            grouped = y.astype(jnp.float32).reshape(b, t, groups, inner // groups)
            normed = grouped * jax.lax.rsqrt(
                jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
            return (normed.reshape(b, t, inner) * scale
                    * jax.nn.silu(z.astype(jnp.float32)))

        monkeypatch.setattr(mamba, "gated_rms_norm", norm_first)
    else:
        monkeypatch.setattr(module, "_model", lambda config, **kw: real_model(
            config, **{**kw, **FAULTS[fault]}))
    with pytest.raises(AssertionError, match="against its float32 "
                                             "references: .*f32 logits = "):
        check()


@pytest.mark.parametrize("what", ["decays", "state"])
def test_a_bf16_scan_fails_the_float32_limit(hvd, monkeypatch, what):
    """The configuration states float32 decays and a float32 carried state:
    either in bf16 is beyond the float32 leg's limits (at this size in the
    gradients of ``A_log`` and ``dt_bias`` first; on the chip at the published
    widths in the scan alone too, PERF.md §6)."""
    from horovod_tpu.ops import ssd

    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    if what == "decays":
        real = ssd._decay
        monkeypatch.setattr(ssd, "_decay", lambda total: bf16(real(total)))
    else:
        def rounded(keep, left, start):
            def carry_on(state, chunk_in):
                keep_c, left_c = chunk_in
                return bf16(keep_c[..., None, None] * state + left_c), state
            return jax.lax.scan(carry_on, bf16(start), (keep, left))

        monkeypatch.setattr(ssd, "_carry_over_chunks", rounded)
    # 16 chunks of 8: the carried state is rounded sixteen times, as it is 64
    # times at the published sizes
    _, check = check_alone(hvd, mamba_chunk_size=8)
    with pytest.raises(AssertionError, match="(scan f32 = |f32 gradient of "
                                             "layer0.(A_log|dt_bias) = )"):
        check()


def test_every_catalog_key_is_in_the_file_as_published():
    config = run.resolve_cell(run.load_manifest(), CELL)["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"granite-4.0-h-micro"' in line)
    for key, value in row["config"].items():
        assert config[key] == value, key
    entry = next(c for c in run.load_manifest()["configs"]
                 if c["name"] == "granite_4_0_h_micro")
    assert entry["reduced"] == ["layers"] and entry["source"].startswith(
        row["source_url"])
    assert config["layers"] == 6 and "40" in config["cut"]["layers"]
    assert config["layer_types"][:6] == ["mamba"] * 5 + ["attention"]
    for word in ("5:1", "9:1", "14.19 GiB"):
        assert word in config["cut"]["layers"]
    assert set(config["tolerance"]) >= {
        "f32_logits_rel", "f32_grads_rel", "f32_loss_rel", "f32_scan_rel",
        "bf16_logits_rel", "bf16_grads_l2_rel", "bf16_loss_rel",
        "bf16_scan_rel", "flash_rel", "why"}


def test_costs_against_hand_counts():
    # one chunk of 256: scores 256*256*128, masked scores x u 64 heads x
    # 256*256*64, state and carried-state products 2 x 2*256*64*64*128
    per_chunk = 256 * 256 * 128 + 64 * 256 * 256 * 64 + 4 * 256 * 64 * 64 * 128
    assert ssd_cost.ssd_forward_flops(16384, 64, 64, 128, 1, 256) == 64 * per_chunk
    assert ssd_cost.ssd_forward_flops(100, 2, 8, 16, 1, 256) == (
        100 * 100 * 16 + 2 * 100 * 100 * 8 + 4 * 100 * 2 * 8 * 16)
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    cost = resolved["module"].cost(resolved["config"], resolved["traffic"], 1)
    assert cost["ssd"]["flops"] == 3 * 5 * 64 * per_chunk           # 0.78 TFLOP
    assert 0.77e12 < cost["ssd"]["flops"] < 0.79e12
    one_pass = 16384 * (2 * (2 * 4096 + 2 * 128) + 4 * 64)
    assert cost["ssd"]["bytes"] == 3 * 5 * one_pass
    # a token, forward: Mamba-2 layer 2*2048*8512 + 2*4096*2048 + 3*2*2048*8192;
    # attention layer 2*2*2048*2048 + 2*2*2048*512 + MLP + 2*16384*64*32 (causal
    # half of 2 products); head 2*2048*100352
    mlp = 3 * 2 * 2048 * 8192
    mamba = 2 * 2048 * 8512 + 2 * 4096 * 2048 + mlp
    attention = 4 * 2048 * 2048 + 4 * 2048 * 512 + mlp + 2 * 16384 * 64 * 32
    head = 2 * 2048 * 100352
    want = 3 * (16384 * (5 * mamba + attention + head) + 5 * 64 * per_chunk)
    assert cost["model_flops"] == want
    assert 67.6e12 < want < 67.8e12                                 # ISSUE 30
    assert cost["kernel"]["flops"] == 7 * 16384 * 16384 * 64 * 32   # one layer


def test_mixer_readers_on_a_hand_made_breakdown(hvd, monkeypatch):
    from benchmarks import named_device_time

    trace = {"steps": 4, "breakdown": {"device_ops": [
        ["fusion [bench_fwd_bwd] hvd_mamba_proj/in_proj/dot_general", 2.0],
        ["fusion [bench_fwd_bwd] block_N/mlp_gate/dot_general", 1.6],
        ["while [bench_fwd_bwd] mixer/hvd_ssd_scan/while", 0.4],
        ["while [bench_fwd_bwd] hvd_ssd_scan/closed_call/while", 0.2],
        ["fusion [bench_fwd_bwd] mixer/hvd_mamba_conv/add", 0.4]]}}
    # the scan by the program's names, whatever the rank of its labels: the
    # roofline's divisor since PR 58 (``ssd_ms_per_step``, which stood on the
    # ten longest labels and read nothing since PR 40, went with it)
    table = {"seconds": {"hvd_ssd_scan": 0.01896, "hvd_mamba_proj": 0.1091},
             "unnamed": 0.02}
    monkeypatch.setattr(named_device_time, "_tables", [table])
    lines = []
    context = {"trace": trace, "log": lines.append,
               "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               "cost": {"ssd": {"flops": 0.788e12, "bytes": 4.1e9}}}

    def read(name):
        return run.load_module(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py")).read(context)

    assert read("mamba_mixer_ms_per_step") == pytest.approx(750.0)
    assert read("ssd_scan_ms_per_step") == pytest.approx(18.96)
    # bound by bytes: 4.1e9 / 819e9 = 5.006 ms against 4.0 ms by operations
    assert read("ssd_roofline_pct") == pytest.approx(100 * 5.00610 / 18.96, rel=1e-4)
    assert "bound by HBM bandwidth" in lines[-1]
    hvd.metrics.registry().gauge("horovod_ssd_chunk_len").set(256)
    assert read("ssd_chunk_len") == 256
    # a program without the scopes or the gauge (the parent): nothing, no raise
    trace["breakdown"]["device_ops"] = [["fusion x/mlp_in/dot_general", 1.0]]
    table["seconds"] = {"hvd_mamba_proj": 0.1091}
    hvd.metrics.registry().gauge("horovod_ssd_chunk_len").set(0)
    for name in ("mamba_mixer_ms_per_step", "ssd_scan_ms_per_step",
                 "ssd_roofline_pct", "ssd_chunk_len"):
        assert read(name) is None
    # a window that never ran the scan gives no share, not 0 nor a division
    table["seconds"] = {"hvd_ssd_scan": 0.0}
    assert read("ssd_roofline_pct") is None
    table["seconds"] = {"hvd_ssd_scan": 0.01896}
    context["cost"] = {}
    assert read("ssd_roofline_pct") is None


def test_no_reader_stands_on_a_name_the_manifest_dropped():
    """``ssd_ms_per_step`` left ``BENCHMARK.json`` with its reader (PR 58):
    every listed metric has a reader, every reader is listed, and granite's
    roofline share is still asked for in its cell."""
    manifest = run.load_manifest()
    listed = {m["name"] for m in manifest["per_layer"]}
    readers = {os.path.splitext(f)[0] for f in os.listdir(os.path.join(
        REPO, "benchmarks", "layer_metrics")) if f.endswith(".py")}
    assert listed == readers
    assert "ssd_ms_per_step" not in listed
    roofline = next(m for m in manifest["per_layer"]
                    if m["name"] == "ssd_roofline_pct")
    assert roofline["workloads"] == ["granite4h_long_1chip"]
    assert (roofline["unit"], roofline["moves"]) == ("%", "step_ms")


def test_the_two_reference_copies_agree():
    from benchmarks.reference import granite_hybrid as bench_copy
    from references import granite_hybrid as test_copy

    with open(bench_copy.__file__) as a, open(test_copy.__file__) as b:
        assert a.read() == b.read()
    cfg = dict(layer_types=("mamba", "attention"), heads=2, kv_heads=1,
               mamba_heads=2, mamba_head_dim=4, mamba_state=8, mamba_groups=1,
               eps=1e-5, emb_mult=12.0, attn_mult=0.25, res_mult=0.22,
               logits_scaling=8.0)
    key = jax.random.split(jax.random.PRNGKey(0), 40)
    n = iter(range(40))

    def w(*shape):
        return 0.3 * jax.random.normal(key[next(n)], shape)

    mlp = lambda: {"mlp_norm": 1 + w(8), "w_gate": w(8, 12), "w_up": w(8, 12),
                   "w_down": w(12, 8)}
    params = {"embed": w(16, 8), "final_norm": 1 + w(8), "layers": [
        {"norm": 1 + w(8), "w_in": w(8, 8 + 8 + 16 + 2), "conv_w": w(4, 24),
         "conv_b": w(24), "dt_bias": w(2), "A_log": w(2), "D": 1 + w(2),
         "gate_norm": 1 + w(8), "w_out": w(8, 8), **mlp()},
        {"norm": 1 + w(8), "wq": w(8, 8), "wk": w(8, 4), "wv": w(8, 4),
         "wo": w(8, 8), **mlp()}]}
    tokens = jnp.arange(12).reshape(1, 12) % 16
    for ssm in ("ssm_recurrence", "ssm_quadratic"):
        outs = [m.loss_and_grads(params, tokens, cfg, getattr(m, ssm))
                for m in (bench_copy, test_copy)]
        for a, b in zip(*(jax.tree_util.tree_leaves(o) for o in outs)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    # the two forms of the state-space layer give the same model
    (la, ga), (lb, gb) = (test_copy.loss_and_grads(params, tokens, cfg,
                                                   getattr(test_copy, ssm))
                          for ssm in ("ssm_recurrence", "ssm_quadratic"))
    for a, b in zip(jax.tree_util.tree_leaves((la, ga)),
                    jax.tree_util.tree_leaves((lb, gb))):
        assert np.max(np.abs(np.asarray(a) - np.asarray(b))) <= 2e-5 * max(
            np.max(np.abs(np.asarray(b))), 1e-30)
