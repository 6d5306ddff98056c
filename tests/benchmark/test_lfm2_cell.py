"""The drawn configuration ``lfm2_24b_a2b`` and its cell, as the benchmark
holds them (CPU, tier 1): the configuration's file against the published
config and the harness's rules; ``lfm2_counts.py`` against hand counts; the
trace reduction's classes on the new decoder's op names; and a tiny-size
rehearsal of ``runners/lfm2_step.py`` through ``run_cell`` - sound, traced,
with the control and each planted fault in the program's place, and with the
timed path broken underneath."""

from __future__ import annotations

import importlib
import json
import os
import shutil
import sys

import pytest

import test_harness as th
from test_harness import bench, manifest  # noqa: F401  (fixtures)

CELL = "lfm2_t8192_b2_ep8share_pretrain"
CONFIG = "lfm2_24b_a2b"
# the numbers of https://huggingface.co/LiquidAI/LFM2-24B-A2B config.json
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9
    + ["full_attention", "conv"]}
# float32 against float32, measured on the CPU at this size: 1e-7 / 5e-7 /
# 8e-6; the bfloat16 control reads 1.3e-5 / 2e-3 / 2e-3 and the faults more
TINY_LIMITS = {"loss_gap": 2e-6, "grad_gap": 3e-5, "change_gap": 2e-4}
TINY_CELL = "tiny_lfm2.loop"
TINY_CONFIG = dict(
    hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
    router_width=16, num_experts=4, experts_held_offset=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=128,
    num_hidden_layers=3, layer_types=["conv", "full_attention", "conv"],
    compute_dtype="float32", limits=TINY_LIMITS)
TINY_TRAFFIC = dict(kind="closed_loop_steps", batch=4, seq_len=32,
                    token_pool_batches=8, check_steps=3, trace_start_s=0.1,
                    trace_slice_s=0.2)


@pytest.fixture(scope="module")
def cell(bench):
    return bench.load_cell(th.ROOT, CELL)


@pytest.fixture(scope="module")
def counts():
    return th._load(os.path.join(th.BENCH, "lfm2_counts.py"), "bench_lfm2_counts")


def test_the_configuration_is_the_published_one_but_for_the_cut(cell, manifest):
    cfg = cell.config
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    numbers = th._load(os.path.join(th.BENCH, "compare.py"), "bench_compare").NUMBERS
    th.check_config(cfg, entry, numbers)
    th.check_runner(th.BENCH, cfg["runner"])
    assert sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
        "vocab_size"])
    assert len(PUBLISHED["layer_types"]) == 40
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:  # every width, the router's top-k, the norms: as published
            assert cfg[key] == value, key
    # the floors: a whole period after the dense layer, 8 experts, 1/8 of
    # the vocabulary; the kept layers are published layers 1-5
    assert cfg["layer_types"] == PUBLISHED["layer_types"][1:6]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 5
    assert cfg["num_experts"] >= 8 and cfg["router_width"] == 64
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    assert {"tie_embedding", "head_dim", "expert_bias"} <= set(cfg["assumed"])
    w = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert w["chips"] == 1 and "8 x their share" in w["why"]
    assert cell.traffic["batch"] * cell.traffic["seq_len"] == 16384
    assert CELL in next(m for m in manifest["end_to_end"]
                        if m["name"] == "tokens_per_s")["workloads"]


def test_counts_reproduce_the_hand_counts(cell, counts):
    cfg, traffic = cell.config, cell.traffic
    # embedding 16,777,216 + final norm 2,048; the dense conv layer
    # 89,139,200; the attention expert layer 86,118,528; three conv expert
    # layers of 92,416,000 (75,497,472 of each in its 8 experts)
    assert counts.parameters(cfg) == 469_284_992
    assert counts.held_assignments_per_token(cfg) == 0.5
    # 2 x 186,122,240 matmul parameters a token (convs 67,108,864, attention
    # 10,485,760, dense 72,351,744, four expert layers of 131,072 + half of
    # 9,437,184, head 16,777,216) + the causal core 2 x 8192 x 2048
    assert counts.forward_flops_per_token(cfg, traffic) == 405_798_912
    assert counts.train_flops_per_token(cfg, traffic) == 1_217_396_736
    rows, item = 8192, 2  # tokens x 4 x 8 / 64 a layer; bfloat16
    experts = counts.moe_experts(cfg, traffic)
    assert experts["ops"] == 4 * 4 * rows * 6 * 2048 * 1536  # 4 layers x 4 passes
    weights = 8 * 3 * 2048 * 1536
    assert experts["hbm_bytes"] == 4 * (
        4 * (weights * item + rows * (2 * 2048 + 3 * 1536) * item) + weights * 4)
    conv = counts.short_conv_core(cfg, traffic)
    elements = 2 * 8192 * 2048
    assert conv == {"ops": 4 * elements * 8 * 5,
                    "hbm_bytes": 4 * elements * item * 15}
    square = 2 * 32 * 8192 * 8192 * 64  # B x H x T^2 x Dh, one attention layer
    q_side, kv_side = 2 * 8192 * 32 * 64 * item, 2 * 8192 * 8 * 64 * item
    assert counts.causal_attention_fwd(cfg, traffic) == {
        "ops": 2 * 2 * square, "hbm_bytes": 2 * (2 * q_side + 2 * kv_side)}
    assert counts.causal_attention_bwd(cfg, traffic) == {
        "ops": 5 * square, "hbm_bytes": 4 * q_side + 4 * kv_side}


def test_every_metric_of_the_cell_names_a_reader_and_a_count(cell, counts):
    names = {m["name"] for m in cell.per_layer}
    assert {"step_mfu.lfm2", "device_idle_share.lfm2", "compiles_in_window.lfm2",
            "dispatch_ms.lfm2", "moe_ms.lfm2", "moe_shuffle_ms.lfm2",
            "moe_experts_roofline.lfm2", "short_conv_ms.lfm2",
            "short_conv_roofline.lfm2", "attn_core_ms.lfm2",
            "attn_flash_share.lfm2", "flash_fwd_roofline.lfm2",
            "flash_bwd_roofline.lfm2", "moe_held_share.lfm2",
            "input_put_ms.lfm2", "loss_wait_ms.lfm2", "step_host_ms_max.lfm2",
            "trainer_init_s.lfm2"} == names
    for m in cell.per_layer:
        with open(os.path.join(th.BENCH, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        args = spec["args"]
        if "module" in args:  # the counts are this configuration's own
            assert args["module"] == "lfm2_counts"
            assert callable(getattr(counts, args.get("fn") or args["flops_fn"]))
        if m["name"].endswith("_roofline.lfm2"):
            assert m["unit"] == "%" and spec["reader"] == "kernel_roofline"


def test_the_decoders_ops_fall_in_the_trace_reductions_classes():
    """``classify`` knows a first forward pass by ``TransformerLM`` in the op
    name, which ``HybridLM`` does not hold: those ops read ``other`` until a
    ``benchmark`` PR widens it, so the forward kernel's share, which counts
    both forward passes, reads that class too."""
    scope_reduce = th._load(os.path.join(th.BENCH, "scope_reduce.py"),
                            "bench_scope_reduce")
    step = "jit(train_step)/"
    layer = "HybridLM/layer_2/"
    for op_name, cls in [
            (step + "jvp(" + layer + "moe/moe.experts/pallas_call", "other"),
            (step + "jvp(HybridLM)/layer_1/attn/attn._local_attention/"
             "pallas_call", "other"),
            (step + "transpose(jvp(" + layer + "moe/moe.shuffle.combine/gather",
             "backward"),
            (step + "transpose(jvp(HybridLM/layer_1/attn/"
             "attn._local_attention/pallas_call", "backward"),
            (step + "jvp(HybridLM)/rematted_computation/layer_2/"
             "moe/moe.shuffle.dispatch/sort", "recompute"),
            (step + "jvp(HybridLM)/head/dot_general", "head_ce"),
            (step + "lm.optimizer/mul", "optimizer")]:
        assert scope_reduce.classify(op_name) == cls, op_name
    with open(os.path.join(th.BENCH, "layer_metrics",
                           "flash_fwd_roofline.lfm2.json")) as f:
        assert set(json.load(f)["args"]["classes"]) == {
            "forward", "recompute", "other"}


# --- tiny-size rehearsal of the runner, window and comparison --------------

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, manifest):
    """A copy of the benchmark with the configuration at a tiny size ADDED
    beside it, and a cell that reports the real cell's metrics."""
    root = str(tmp_path_factory.mktemp("bench_lfm2"))
    shutil.copytree(th.BENCH, os.path.join(root, "benchmark"))
    with open(os.path.join(th.BENCH, "configs", CONFIG + ".json")) as f:
        cfg = dict(json.load(f), name="tiny_lfm2", **TINY_CONFIG)
    th._write(root, "benchmark/configs/tiny_lfm2.json", cfg)
    th._write(root, "benchmark/traffic/tiny_lfm2_loop.json", TINY_TRAFFIC)
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "tiny_lfm2", "source": cfg["source"],
                         "file": "benchmark/configs/tiny_lfm2.json",
                         "reduced": cfg["reduced"], "why": "test"})
    m["workloads"].append({"name": TINY_CELL, "config": "tiny_lfm2",
                           "traffic": "tiny_lfm2_loop", "chips": 1,
                           "why": "test"})
    for entry in m["end_to_end"] + m["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append(TINY_CELL)
    th._write(root, "BENCHMARK.json", m)
    th.check_manifest(m)
    return root


def test_tiny_cell_runs_its_window_and_is_correct(bench, tiny_root, capsys):
    result, err = th._drive(bench, tiny_root, TINY_CELL)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "step_ms_p90", "setup_s"}
    for name, limit in TINY_LIMITS.items():
        assert 0 <= result["compared"][name]["value"] <= limit
    # the runner's last word on routing: nothing dropped
    routing = next(line for line in capsys.readouterr().err.splitlines()
                   if line.startswith("routing:"))
    assert "fedml_moe_dropped_total = 0" in routing
    assert err.strip().splitlines()[-1] == "correct: true"


def test_tiny_traced_run_reports_the_cells_metrics(bench, tiny_root):
    result, _ = th._drive(bench, tiny_root, TINY_CELL, trace=True, seed=7)
    assert result["correct"] is True, result["compared"]
    metrics = result["metrics"]
    assert 0 < metrics["step_mfu.lfm2"]["value"] < 100
    assert metrics["compiles_in_window.lfm2"]["value"] == 0
    # the trainer's host spans, as the GPT-2 cell reads them
    host = {"dispatch_ms.lfm2", "input_put_ms.lfm2", "loss_wait_ms.lfm2",
            "step_host_ms_max.lfm2", "trainer_init_s.lfm2"}
    assert all(metrics[m]["value"] > 0 for m in host)
    # 4 of 16 experts held, the selection biases balanced on the last batch
    # of the pool (128 tokens, so the other batches stray by a few points);
    # the dense core off the chip
    assert 20 < metrics["moe_held_share.lfm2"]["value"] < 32
    assert metrics["attn_flash_share.lfm2"]["value"] == 0.0
    # the CPU's trace has no device plane: those readers return nothing
    assert not {m for m in metrics if m.endswith(("_roofline.lfm2", "_ms.lfm2"))
                and m not in host}
    assert "device_idle_share.lfm2" not in metrics


def test_control_and_planted_faults_come_out_not_correct(bench, tiny_root):
    cell = bench.load_cell(tiny_root, TINY_CELL)
    sys.path.insert(0, cell.bench_dir)
    runner = importlib.import_module("runners." + cell.config["runner"])
    compare = importlib.import_module("compare")
    assert set(runner.FAULTS) == {"half_batch", "capacity_drop"}
    for seed in (11, 3_300_000_033):
        ctx = bench.types.SimpleNamespace(
            config=cell.config, traffic=cell.traffic, seed=seed, chips=1)
        ref = runner.reference(ctx)
        assert compare.decide(ref, ref, cell.config["limits"])[0]
        for kw in [{"compute": "bf16"}, *runner.FAULTS.values()]:
            ok, compared = compare.decide(
                runner.reference(ctx, **kw), ref, cell.config["limits"])
            assert not ok, (kw, compared)
            if "compute" not in kw:
                assert compared["grad_gap"]["value"] > 0.05, (kw, compared)


def test_a_layer_that_holds_other_experts_reads_not_correct(
        bench, tiny_root, monkeypatch):
    """The timed path broken underneath: the program computes the part of
    experts 5-8 with the weights of 4-7."""
    cell = bench.load_cell(tiny_root, TINY_CELL)
    sys.path.insert(0, cell.bench_dir)
    runner = importlib.import_module("runners." + cell.config["runner"])
    sound = runner.decoder_config
    monkeypatch.setattr(runner, "decoder_config", lambda cfg: sound(
        dict(cfg, experts_held_offset=cfg["experts_held_offset"] + 1)))
    result, err = th._drive(bench, tiny_root, TINY_CELL, seed=2_900_000_029)
    assert result["correct"] is False and result["attempted"] >= 1
    assert err.strip().splitlines()[-1] == "correct: false"


def test_balanced_biases_even_the_seeded_routers_load(bench, tiny_root):
    """The reference's selection biases spread each layer's choices over all
    16 experts alike on the batch they were found on, the last of the seed's
    pool (the seeded start does not), and the same call gives the same
    biases: both sides of a run share them."""
    import jax
    import numpy as np

    cell = bench.load_cell(tiny_root, TINY_CELL)
    sys.path.insert(0, cell.bench_dir)
    ref = importlib.import_module("reference.lfm2")
    cfg, traffic = cell.config, dict(cell.traffic, batch=16)
    weights, start = ref.init_weights(21, cfg)
    _, biases = ref.seeded(21, cfg, traffic)
    again = ref.seeded(21, cfg, traffic)[1]
    assert all((np.asarray(biases[k]) == np.asarray(again[k])).all() for k in biases)
    tokens = jax.numpy.asarray(ref.make_batches(21, cfg, traffic)[-1][:, :-1])

    def worst_load(b):
        choices = ref.forward(weights, b, tokens, ref.shape_of(cfg))[1]["choices"]
        loads = [np.bincount(np.asarray(c).ravel(), minlength=16) for c in choices]
        return max(load.max() / load.mean() for load in loads)

    assert worst_load(biases) < 1.1 < worst_load(start)
