"""The drawn configuration ``ouro_2_6b`` and its cell, as the benchmark holds
them (CPU, tier 1): the configuration's file against the published config
and the harness's rules; ``ouro_counts.py`` against hand counts and against
the program's own parameter count; and a tiny-size rehearsal of
``runners/ouro_step.py`` through ``run_cell`` - sound, traced, and with the
control and each planted fault in the program's place."""

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

CELL = "ouro_t4096_b1_ut4_pretrain"
CONFIG = "ouro_2_6b"
# https://huggingface.co/ByteDance/Ouro-2.6B config.json
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152}
# float32 against float32, measured on the CPU at this size over the seeds
# below: 1e-7 / 9e-7 / 7e-6; the bfloat16 control reads 3e-5 / 2e-3 to 5e-3 /
# 1.3e-3, and each fault 0.4 or more by its gradient
TINY_LIMITS = {"loss_gap": 2e-6, "grad_gap": 3e-5, "change_gap": 4e-4}
TINY_CELL = "tiny_ouro.loop"
TINY_CONFIG = dict(
    hidden_size=64, num_hidden_layers=2, layer_types=["full_attention"] * 2,
    intermediate_size=160, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, vocab_size=128, compute_dtype="float32", limits=TINY_LIMITS)
TINY_TRAFFIC = dict(kind="closed_loop_steps", batch=2, seq_len=32,
                    token_pool_batches=8, check_steps=3, trace_start_s=0.1,
                    trace_slice_s=0.2)


@pytest.fixture(scope="module")
def cell(bench):
    return bench.load_cell(th.ROOT, CELL)


@pytest.fixture(scope="module")
def counts():
    return th._load(os.path.join(th.BENCH, "ouro_counts.py"), "bench_ouro_counts")


def test_the_configuration_is_the_published_one_but_for_its_depth(cell, manifest):
    cfg = cell.config
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    numbers = th._load(os.path.join(th.BENCH, "compare.py"), "bench_compare").NUMBERS
    th.check_config(cfg, entry, numbers)
    th.check_runner(th.BENCH, cfg["runner"])
    th.check_manifest(manifest)
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:  # every width, head count, the vocabulary, the passes: as published
            assert cfg[key] == value, key
    # depth alone: one of six equal pipeline stages, every layer whole here
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 8 == 48 // 6
    assert set(cfg["layer_types"]) == {"full_attention"}
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    assert "17%" in cfg["deployment"]["head_share"]
    assert {"equations", "sandwich_norm", "final_norm_in_loop", "exit_gate",
            "exit_distribution", "exit_entropy_beta", "optimizer"} <= set(
                cfg["assumed"])
    assert cfg["exit_entropy_beta"] == 0.1 and cfg["optimizer"]["warmup_steps"] == 0
    w = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert w["chips"] == 1 and w["traffic"] == "t4096_b1_pretrain"
    assert (cell.traffic["batch"], cell.traffic["seq_len"]) == (1, 4096)
    assert cell.traffic["kind"] == "closed_loop_steps"
    assert CELL in next(m for m in manifest["end_to_end"]
                        if m["name"] == "tokens_per_s")["workloads"]


def test_counts_reproduce_the_hand_counts_and_the_programs_tree(cell, counts):
    cfg, traffic = cell.config, cell.traffic
    # a layer: four projections 4 x 2048^2, SwiGLU 3 x 2048 x 5632, four norms
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert counts.parameters(cfg) == 612_438_017 == (
        8 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1)
    # the published model, every tensor: 2.67 B, the card's 2.6 B
    assert counts.parameters(PUBLISHED) == 2_667_974_657 == (
        48 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1)
    assert counts.layer_applications(cfg) == 32
    # a token, forward: 32 applications of 2 x 51,380,224 matmul parameters
    # and a causal core of 2 x 4096 x 2048; four heads of 2 x 2048 x 49152
    # and four gates of 2 x 2048. Not 2 x parameters: the embedding has none
    # and everything else is met four times
    assert counts.forward_flops_per_token(cfg, traffic) == (
        32 * (2 * 51_380_224 + 2 * 4096 * 2048)
        + 4 * 2 * (2048 * 49152 + 2048)) == 4_630_528_000
    assert counts.train_flops_per_token(cfg, traffic) == 3 * 4_630_528_000
    assert 4 * 2 * 2048 * 49152 / 4_630_528_000 == pytest.approx(0.174, abs=1e-3)
    square = 16 * 4096 * 4096 * 128  # B x H x T^2 x Dh, one application
    side = 4096 * 16 * 128 * 2       # q's bytes, and k's: as many KV heads
    assert counts.causal_attention_fwd(cfg, traffic) == {
        "ops": 2 * 2 * 32 * square, "hbm_bytes": 2 * 32 * 4 * side}
    assert counts.causal_attention_bwd(cfg, traffic) == {
        "ops": 5 * 32 * square, "hbm_bytes": 32 * 8 * side}
    # and the program's own tree at the cell's configuration, shapes only
    sys.path.insert(0, th.BENCH)
    runner = importlib.import_module("runners." + cfg["runner"])
    from fedml_tpu.models.hybrid_lm import HybridLM

    shapes = jax.eval_shape(HybridLM(runner.decoder_config(cfg)).init,
                            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert set(shapes) == {"params"}
    assert sum(a.size for a in jax.tree.leaves(shapes)) == counts.parameters(cfg)


def test_every_metric_of_the_cell_names_a_reader_and_a_count(cell, counts):
    names = {m["name"] for m in cell.per_layer}
    assert {"step_mfu.ouro", "device_idle_share.ouro",
            "compiles_in_window.ouro", "dispatch_ms.ouro", "input_put_ms.ouro",
            "loss_wait_ms.ouro", "step_host_ms_max.ouro", "trainer_init_s.ouro",
            "attn_core_ms.ouro", "attn_flash_share.ouro",
            "flash_fwd_roofline.ouro", "flash_bwd_roofline.ouro",
            "ut_stack_ms.ouro", "ut_head_ms.ouro", "ut_exit_ms.ouro",
            "lm_loss_ms.ouro", "ut_exit_last_share.ouro"} <= names  # <=: the next metric needs no edit
    for m in cell.per_layer:
        with open(os.path.join(th.BENCH, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(
            th.BENCH, "readers", spec["reader"] + ".py"))
        args = spec["args"]
        if "module" in args:  # the counts are this configuration's own
            assert args["module"] == "ouro_counts"
            assert callable(getattr(counts, args.get("fn") or args["flops_fn"]))
        if m["name"].endswith("_roofline.ouro"):
            assert m["unit"] == "%" and spec["reader"] == "kernel_roofline"
        assert m["moves"] == ("setup_s" if m["name"] == "trainer_init_s.ouro"
                              else "tokens_per_s")


# --- tiny-size rehearsal of the runner, window and comparison --------------

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, manifest):
    """A copy of the benchmark with the configuration at a tiny size ADDED
    beside it, and a cell that reports the real cell's metrics."""
    root = str(tmp_path_factory.mktemp("bench_ouro"))
    shutil.copytree(th.BENCH, os.path.join(root, "benchmark"))
    with open(os.path.join(th.BENCH, "configs", CONFIG + ".json")) as f:
        cfg = dict(json.load(f), name="tiny_ouro", **TINY_CONFIG)
    th._write(root, "benchmark/configs/tiny_ouro.json", cfg)
    th._write(root, "benchmark/traffic/tiny_ouro_loop.json", TINY_TRAFFIC)
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "tiny_ouro", "source": cfg["source"],
                         "file": "benchmark/configs/tiny_ouro.json",
                         "reduced": cfg["reduced"], "why": "test"})
    m["workloads"].append({"name": TINY_CELL, "config": "tiny_ouro",
                           "traffic": "tiny_ouro_loop", "chips": 1,
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
    # the runner's last word: the passes run and where the exit mass lies
    said = next(line for line in capsys.readouterr().err.splitlines()
                if line.startswith("passes and exits:"))
    steps = result["attempted"] + 3
    assert f"fedml_lm_ut_passes_total = {4 * steps}" in said
    assert all(f"fedml_lm_exit_mass_total{{ut={t}}}" in said
               and f"fedml_lm_ut_nll{{ut={t}}}" in said for t in (1, 2, 3, 4))
    assert err.strip().splitlines()[-1] == "correct: true"


def test_tiny_traced_run_reports_the_cells_metrics(bench, tiny_root):
    result, _ = th._drive(bench, tiny_root, TINY_CELL, trace=True, seed=7)
    assert result["correct"] is True, result["compared"]
    metrics = result["metrics"]
    assert 0 < metrics["step_mfu.ouro"]["value"] < 100
    assert metrics["compiles_in_window.ouro"]["value"] == 0
    host = {"dispatch_ms.ouro", "input_put_ms.ouro", "loss_wait_ms.ouro",
            "step_host_ms_max.ouro", "trainer_init_s.ouro"}
    assert all(metrics[m]["value"] > 0 for m in host)
    # the seeded gate (normal(0, 0.02), bias 0) leaves each gate near a half:
    # 1/2, 1/4, 1/8 and, on the last pass, the 1/8 that is left; from there
    # the entropy term pulls the distribution towards a quarter a pass, step
    # by step, which is the drift this metric is there to show
    assert 10 < metrics["ut_exit_last_share.ouro"]["value"] < 30
    assert metrics["attn_flash_share.ouro"]["value"] == 0.0  # dense off the chip
    # the CPU's trace has no device plane: those readers return nothing
    assert not {m for m in metrics if m.endswith(("_roofline.ouro", "_ms.ouro"))
                and m not in host}
    assert "device_idle_share.ouro" not in metrics


def test_control_and_planted_faults_come_out_not_correct(bench, tiny_root):
    cell = bench.load_cell(tiny_root, TINY_CELL)
    sys.path.insert(0, cell.bench_dir)
    runner = importlib.import_module("runners." + cell.config["runner"])
    compare = importlib.import_module("compare")
    assert set(runner.FAULTS) == {"half_batch", "pass_dropped",
                                  "last_pass_only", "gate_stopped"}
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


def test_a_stack_run_once_too_few_reads_not_correct(bench, tiny_root, monkeypatch):
    """The timed path broken underneath: the program's decoder applies its
    stack three times where the configuration says four, which is what
    ``pass_dropped`` plants in the reference."""
    import dataclasses

    cell = bench.load_cell(tiny_root, TINY_CELL)
    sys.path.insert(0, cell.bench_dir)
    runner = importlib.import_module("runners." + cell.config["runner"])
    sound = runner.decoder_config
    monkeypatch.setattr(runner, "decoder_config", lambda cfg: dataclasses.replace(
        sound(cfg), total_ut_steps=cfg["total_ut_steps"] - 1))
    result, err = th._drive(bench, tiny_root, TINY_CELL, seed=2_900_000_029)
    assert result["correct"] is False and result["attempted"] >= 1
    assert err.strip().splitlines()[-1] == "correct: false"
    # and it is the planted fault's twin: against the reference that drops a
    # pass, the same run is within the limits
    compare = importlib.import_module("compare")
    ctx = bench.types.SimpleNamespace(
        config=cell.config, traffic=cell.traffic, seed=2_900_000_029, chips=1)
    run = runner.Run(bench.types.SimpleNamespace(**vars(ctx), span=bench.no_span))
    got = run.readings
    run.close()
    assert compare.decide(got, runner.reference(ctx, pass_dropped=True),
                          cell.config["limits"])[0]
