"""The drawn configuration ``smallthinker_21b_a3b`` and its cell, as the
benchmark holds them (CPU, tier 1): the configuration's file against the
published config and the harness's rules; ``smallthinker_counts.py`` against
hand counts and against the program's own parameter count; the metrics the
cell reports; and a tiny-size rehearsal of ``runners/st21_step.py`` through
``run_cell`` - sound, traced, and with the control and each planted fault
(``no_window`` among them) in the program's place."""

from __future__ import annotations

import importlib
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import pytest

import test_harness as th
from test_harness import bench, manifest  # noqa: F401  (fixtures)

CELL = "st21_t16384_b1_ep8share_pretrain"
CONFIG = "smallthinker_21b_a3b"
PERIOD = [0, 1, 1, 1]
# https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct config.json
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": PERIOD * 13, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": PERIOD * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
# float32 against float32, measured on the CPU at this size over the five
# seeds below: 1e-7 / 6e-7 / 3e-5; the bfloat16 control reads 8e-6 to 3e-5 /
# 3e-3 to 2e-2 / 2e-3 to 2e-2, and each fault 0.28 or more by its gradient
TINY_LIMITS = {"loss_gap": 2e-6, "grad_gap": 3e-5, "change_gap": 4e-4}
TINY_CELL = "tiny_st21.loop"
TINY_CONFIG = dict(
    hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=48,
    moe_num_active_primary_experts=3, router_width=16,
    moe_num_primary_experts=4, experts_held_offset=4, sliding_window_size=8,
    vocab_size=128, compute_dtype="float32", limits=TINY_LIMITS)
TINY_TRAFFIC = dict(kind="closed_loop_steps", batch=2, seq_len=32,
                    token_pool_batches=8, check_steps=3, trace_start_s=0.1,
                    trace_slice_s=0.2)


@pytest.fixture(scope="module")
def cell(bench):
    return bench.load_cell(th.ROOT, CELL)


@pytest.fixture(scope="module")
def counts():
    return th._load(os.path.join(th.BENCH, "smallthinker_counts.py"),
                    "bench_smallthinker_counts")


def test_the_configuration_is_the_published_one_but_for_the_cut(cell, manifest):
    cfg = cell.config
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    numbers = th._load(os.path.join(th.BENCH, "compare.py"), "bench_compare").NUMBERS
    th.check_config(cfg, entry, numbers)
    th.check_runner(th.BENCH, cfg["runner"])
    th.check_manifest(manifest)
    assert cfg["reduced"] == ["num_hidden_layers", "sliding_window_layout",
                              "rope_layout", "moe_num_primary_experts",
                              "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:  # every width, head count, the window, the router: as published
            assert cfg[key] == value, key
    # one whole period of both layouts: the global layer, three windowed ones
    assert cfg["num_hidden_layers"] == 4
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == PERIOD
    assert cfg["published"]["sliding_window_layout"][:4] == PERIOD
    # 8 of 64 experts over 8 chips, an eighth of the vocabulary
    assert cfg["moe_num_primary_experts"] * 8 == cfg["router_width"] == 64
    assert cfg["experts_held_offset"] == 0
    assert cfg["vocab_size"] * 8 == 151936
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    assert {"expert_form", "router_input", "window", "qk_norm", "rotary",
            "router", "optimizer", "weights"} <= set(cfg["assumed"])
    assert (cfg["mlp_hidden_act"], cfg["early_router"]) == ("relu", True)
    assert cfg["optimizer"]["warmup_steps"] == 2000 and cfg["remat"] == "full"
    w = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert w["chips"] == 1 and w["traffic"] == "t16384_b1_pretrain"
    assert (cell.traffic["batch"], cell.traffic["seq_len"]) == (1, 16384)
    assert cell.traffic["kind"] == "closed_loop_steps"
    assert CELL in next(m for m in manifest["end_to_end"]
                        if m["name"] == "tokens_per_s")["workloads"]


def test_counts_reproduce_the_hand_counts_and_the_programs_tree(cell, counts):
    cfg, traffic = cell.config, cell.traffic
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    expert = 3 * 2560 * 768
    assert (attn, 2560 * 64, expert) == (20_971_520, 163_840, 5_898_240)
    layer = attn + 163_840 + 2 * 2560 + 8 * expert
    assert counts.parameters(cfg) == 370_547_200 == (
        4 * layer + 2 * 18_992 * 2560 + 2560)
    # the published model, every tensor: 21.51 B, the card's 21B
    assert counts.parameters(PUBLISHED) == 21_506_562_560 == (
        52 * (attn + 163_840 + 2 * 2560 + 64 * expert)
        + 2 * 151_936 * 2560 + 2560)
    # the band's pairs at T = 16384: 58.7 M of the triangle's 134.2 M a head
    assert counts.pairs(16384, 4096) == 4096 * 4097 // 2 + 12288 * 4096
    assert counts.pairs(16384, 4096) == 58_722_304
    assert counts.pairs(16384) == counts.pairs(16384, 16384) == 134_225_920
    assert counts.pairs(8, 3) == sum(min(i + 1, 3) for i in range(8))
    # a token, forward: the global layer's core 117.4 M, the three windowed
    # 154.1 M, projections and router 168.4 M, the held experts' expected
    # share 35.4 M (6 x 8 / 64 of one expert), the head 97.2 M
    heads = 28 * 128
    core = 4 * heads * (134_225_920 + 3 * 58_722_304) / 16384
    assert counts.attention_core_flops_per_token(cfg, traffic) == core
    assert counts.attention_core_flops_per_token(
        cfg, traffic, windowed_only=True) == 4 * heads * 3 * 58_722_304 / 16384
    fwd = (2.0 * (4 * (attn + 163_840 + 0.75 * expert) + 2560 * 18_992)
           + core)
    assert counts.forward_flops_per_token(cfg, traffic) == fwd
    assert fwd == pytest.approx(573.1e6, rel=1e-3)
    assert counts.train_flops_per_token(cfg, traffic) == 3 * fwd
    kept = 28 * 128 * (134_225_920 + 3 * 58_722_304)
    band = 28 * 128 * 3 * 58_722_304
    q_side, kv_side = 16384 * 28 * 128 * 2, 16384 * 4 * 128 * 2
    assert counts.causal_attention_fwd(cfg, traffic) == {
        "ops": 4 * kept * 2, "hbm_bytes": (2 * q_side + 2 * kv_side) * 4 * 2}
    assert counts.causal_attention_bwd(cfg, traffic) == {
        "ops": 10 * kept, "hbm_bytes": (4 * q_side + 4 * kv_side) * 4}
    assert counts.window_attention_fwd(cfg, traffic) == {
        "ops": 4 * band * 2, "hbm_bytes": (2 * q_side + 2 * kv_side) * 3 * 2}
    assert counts.window_attention_bwd(cfg, traffic) == {
        "ops": 10 * band, "hbm_bytes": (4 * q_side + 4 * kv_side) * 3}
    rows = 16384 * 6 * 8 / 64  # 12,288 rows a layer
    weights = 8 * 3 * 2560 * 768
    assert counts.moe_experts(cfg, traffic) == {
        "ops": 4 * 4 * rows * 6 * 2560 * 768,
        "hbm_bytes": 4 * (4 * (weights * 2 + rows * (2 * 2560 + 2 * 768) * 2)
                          + weights * 4)}
    # and the program's own tree at the cell's configuration, shapes only
    sys.path.insert(0, th.BENCH)
    runner = importlib.import_module("runners." + cfg["runner"])
    from fedml_tpu.models.hybrid_lm import HybridLM

    shapes = jax.eval_shape(HybridLM(runner.decoder_config(cfg)).init,
                            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert set(shapes) == {"params"}  # the softmax router has no bias
    assert sum(a.size for a in jax.tree.leaves(shapes)) == counts.parameters(cfg)


def test_every_metric_of_the_cell_names_a_reader_and_a_count(cell, counts):
    names = {m["name"] for m in cell.per_layer}
    assert {f"{m}.st21" for m in (
        "step_mfu", "device_idle_share", "compiles_in_window", "dispatch_ms",
        "input_put_ms", "loss_wait_ms", "step_host_ms_max", "trainer_init_s",
        "attn_core_ms", "attn_flash_share", "flash_fwd_roofline",
        "flash_bwd_roofline", "moe_ms", "moe_shuffle_ms",
        "moe_experts_roofline", "moe_held_share", "lm_loss_ms",
        "step_trace_s", "step_lower_s", "step_compile_s", "step_traces",
        "attn_window_ms", "flash_window_fwd_roofline",
        "flash_window_bwd_roofline", "flash_window_share",
        "flash_striped_share", "moe_row_move_pallas_share")} <= names  # <=: the next metric needs no edit
    for m in cell.per_layer:
        with open(os.path.join(th.BENCH, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(
            th.BENCH, "readers", spec["reader"] + ".py"))
        args = spec["args"]
        if "module" in args:  # the counts are this configuration's own
            assert args["module"] == "smallthinker_counts"
            assert callable(getattr(counts, args.get("fn") or args["flops_fn"]))
        if m["name"].endswith("_roofline.st21"):
            assert m["unit"] == "%" and spec["reader"] == "kernel_roofline"
        if m["name"].startswith(("attn_window", "flash_window_")) and (
                spec["reader"] != "program_counter"):
            assert args["scope"] == "attn.window"
        assert m["moves"] == ("setup_s" if m["name"].startswith(
            ("trainer_init_s", "step_trace", "step_lower", "step_compile"))
            else "tokens_per_s")
    with open(os.path.join(th.BENCH, "layer_metrics",
                           "flash_window_share.st21.json")) as f:
        share = json.load(f)["args"]
    assert share["value"] == str(cell.config["sliding_window_size"])


# --- tiny-size rehearsal of the runner, window and comparison --------------

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, manifest):
    """A copy of the benchmark with the configuration at a tiny size ADDED
    beside it, and a cell that reports the real cell's metrics."""
    root = str(tmp_path_factory.mktemp("bench_st21"))
    shutil.copytree(th.BENCH, os.path.join(root, "benchmark"))
    with open(os.path.join(th.BENCH, "configs", CONFIG + ".json")) as f:
        cfg = dict(json.load(f), name="tiny_st21", **TINY_CONFIG)
    th._write(root, "benchmark/configs/tiny_st21.json", cfg)
    th._write(root, "benchmark/traffic/tiny_st21_loop.json", TINY_TRAFFIC)
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "tiny_st21", "source": cfg["source"],
                         "file": "benchmark/configs/tiny_st21.json",
                         "reduced": cfg["reduced"], "why": "test"})
    m["workloads"].append({"name": TINY_CELL, "config": "tiny_st21",
                           "traffic": "tiny_st21_loop", "chips": 1,
                           "why": "test"})
    for entry in m["end_to_end"] + m["per_layer"]:
        if CELL in entry.get("workloads", []):
            entry["workloads"].append(TINY_CELL)
    th._write(root, "BENCHMARK.json", m)
    th.check_manifest(m)
    return root


def test_tiny_cell_runs_its_window_and_is_correct(bench, tiny_root, capsys):
    result, err = th._drive(bench, tiny_root, TINY_CELL)
    assert list(result)[-1] == "compared"  # run.py's protocol, kept
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "step_ms_p90", "setup_s"}
    for name, limit in TINY_LIMITS.items():
        assert 0 <= result["compared"][name]["value"] <= limit
    said = next(line for line in capsys.readouterr().err.splitlines()
                if line.startswith("routing and bands:"))
    assert "fedml_moe_assignments_total{held=yes}" in said
    assert err.strip().splitlines()[-1] == "correct: true"


def test_tiny_traced_run_reports_the_cells_metrics(bench, tiny_root):
    result, _ = th._drive(bench, tiny_root, TINY_CELL, trace=True, seed=7)
    assert result["correct"] is True, result["compared"]
    metrics = result["metrics"]
    assert 0 < metrics["step_mfu.st21"]["value"] < 100
    assert metrics["compiles_in_window.st21"]["value"] == 0
    host = {"dispatch_ms.st21", "input_put_ms.st21", "loss_wait_ms.st21",
            "step_host_ms_max.st21", "trainer_init_s.st21"}
    assert all(metrics[m]["value"] > 0 for m in host)
    # 3 held of 16 experts, top-3: about 4 / 16 of the assignments land here
    assert 0 < metrics["moe_held_share.st21"]["value"] < 100
    assert metrics["attn_flash_share.st21"]["value"] == 0.0  # dense off the chip
    # the dense path takes the window: the flash kernels count nothing here
    assert "flash_window_share.st21" not in metrics
    # the CPU's trace has no device plane: those readers return nothing
    assert not {m for m in metrics if m.endswith(("_roofline.st21", "_ms.st21"))
                and m not in host}
    assert "device_idle_share.st21" not in metrics


def test_control_and_planted_faults_come_out_not_correct(bench, tiny_root):
    cell = bench.load_cell(tiny_root, TINY_CELL)
    sys.path.insert(0, cell.bench_dir)
    runner = importlib.import_module("runners." + cell.config["runner"])
    compare = importlib.import_module("compare")
    assert set(runner.FAULTS) == {"half_batch", "no_window", "late_router"}
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
                assert compared["grad_gap"]["value"] > 5e-3, (kw, compared)


def test_a_window_left_out_of_the_program_reads_not_correct(
        bench, tiny_root, monkeypatch):
    """The timed path broken underneath: the program's windowed layers see
    every earlier key, which is what ``no_window`` plants in the
    reference."""
    import dataclasses

    cell = bench.load_cell(tiny_root, TINY_CELL)
    sys.path.insert(0, cell.bench_dir)
    runner = importlib.import_module("runners." + cell.config["runner"])
    sound = runner.decoder_config
    monkeypatch.setattr(runner, "decoder_config", lambda cfg: dataclasses.replace(
        sound(cfg), sliding_window_layout=None, sliding_window_size=None))
    result, err = th._drive(bench, tiny_root, TINY_CELL, seed=2_900_000_029)
    assert result["correct"] is False and result["attempted"] >= 1
    assert err.strip().splitlines()[-1] == "correct: false"
    compare = importlib.import_module("compare")
    ctx = bench.types.SimpleNamespace(
        config=cell.config, traffic=cell.traffic, seed=2_900_000_029, chips=1)
    run = runner.Run(bench.types.SimpleNamespace(**vars(ctx), span=bench.no_span))
    got = run.readings
    run.close()
    assert compare.decide(got, runner.reference(ctx, no_window=True),
                          cell.config["limits"])[0]
