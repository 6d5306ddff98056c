"""The drawn configuration ``nemotron3_nano_30b_a3b`` and its cell, as the
benchmark holds them (CPU, tier 1): the configuration's file against the
published config and the harness's rules; ``nemotron_h_counts.py`` against
hand counts; and a tiny-size rehearsal of ``runners/nemo3_step.py`` through
``run_cell`` - sound, traced, and with the control and each planted fault
(``state_reset`` among them) in the program's place."""

from __future__ import annotations

import importlib
import json
import os
import shutil
import sys

import pytest

import test_harness as th
from test_harness import bench, manifest  # noqa: F401  (fixtures)

CELL = "nemo3_t8192_b1_ep16share_pretrain"
CONFIG = "nemotron3_nano_30b_a3b"
# https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "n_group": 1, "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52, "num_key_value_heads": 2,
    "partial_rotary_factor": 1, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "vocab_size": 131072}
# float32 against float32, measured on the CPU at this size over the seeds
# below: 2e-7 / 4e-7 / 3e-5; the bfloat16 control reads 2e-5 / 1e-2 / 4e-3
# and each fault more by its gradient (state_reset the least: 6e-3 to 1e-2)
TINY_LIMITS = {"loss_gap": 2e-6, "grad_gap": 3e-5, "change_gap": 4e-4}
TINY_CELL = "tiny_nemo3.loop"
TINY_CONFIG = dict(
    hidden_size=48, hybrid_override_pattern="MEM*E", num_hidden_layers=5,
    mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
    chunk_size=8, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=40, moe_shared_expert_intermediate_size=72,
    router_width=16, n_routed_experts=4, experts_held_offset=4, vocab_size=128,
    compute_dtype="float32", limits=TINY_LIMITS)
TINY_TRAFFIC = dict(kind="closed_loop_steps", batch=2, seq_len=32,
                    token_pool_batches=8, check_steps=3, trace_start_s=0.1,
                    trace_slice_s=0.2)


@pytest.fixture(scope="module")
def cell(bench):
    return bench.load_cell(th.ROOT, CELL)


@pytest.fixture(scope="module")
def counts():
    return th._load(os.path.join(th.BENCH, "nemotron_h_counts.py"),
                    "bench_nemotron_h_counts")


def test_the_configuration_is_the_published_one_but_for_the_cut(cell, manifest):
    cfg = cell.config
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    numbers = th._load(os.path.join(th.BENCH, "compare.py"), "bench_compare").NUMBERS
    th.check_config(cfg, entry, numbers)
    th.check_runner(th.BENCH, cfg["runner"])
    th.check_manifest(manifest)
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:  # every width, the router's top-k, the scan's sizes: as published
            assert cfg[key] == value, key
    # the floors: a whole period (the driver's count, 9), 8 experts, 1/8 of
    # the vocabulary; the kept blocks are published blocks 0-8
    pattern = PUBLISHED["hybrid_override_pattern"]
    assert len(pattern) == 52 and cfg["hybrid_override_pattern"] == pattern[:9]
    assert cfg["num_hidden_layers"] == len(cfg["hybrid_override_pattern"]) == 9
    assert [cfg["hybrid_override_pattern"].count(k) for k in "ME*"] == [4, 4, 1]
    assert cfg["n_routed_experts"] >= 8 and cfg["router_width"] == 128
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    assert {"layer_equations", "attention_positions", "expert_bias",
            "mamba_init", "inner_width"} <= set(cfg["assumed"])
    w = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert w["chips"] == 1 and "16 x their share" in w["why"]
    assert cell.traffic["batch"] * cell.traffic["seq_len"] == 8192
    assert CELL in next(m for m in manifest["end_to_end"]
                        if m["name"] == "tokens_per_s")["workloads"]


def test_counts_reproduce_the_hand_counts(cell, counts):
    cfg, traffic = cell.config, cell.traffic
    # a Mamba block: W_in 2688 x 10304 + W_out 4096 x 2688 = 38,707,200; the
    # convolution 6144 x 4 taps + 6144 bias; A_log, D, dt_bias 3 x 64; the
    # gated norm 4096; the block's norm 2688
    mamba = 38_707_200 + 6144 * 5 + 192 + 4096 + 2688
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    expert = 2 * 2688 * 1856
    moe = lambda held: held * expert + 2 * 2688 * 3712 + 2688 * 128 + 2688  # noqa: E731
    assert (mamba, attn, moe(8)) == (38_744_896, 23_399_040, 100_125_312)
    assert counts.parameters(cfg) == 666_962_944 == (
        4 * mamba + attn + 4 * moe(8) + 2 * 16384 * 2688 + 2688)
    # the published model, every tensor: 31.6 B as its card says
    assert counts.parameters(PUBLISHED) == 31_577_937_344 == (
        23 * mamba + 6 * attn + 23 * moe(128) + 2 * 131072 * 2688 + 2688)
    assert counts.held_assignments_per_token(cfg) == 0.375
    # 2 x 318,431,232 matmul parameters a token (Mamba projections
    # 154,828,800, attention 23,396,352, four expert blocks of the shared
    # expert 19,955,712 + router 344,064 + 0.375 x 9,977,856, head
    # 44,040,192), the recurrence 4 x 64 x (5 x 64 x 128 + 3 x 64), and the
    # causal core 2 x 8192 x 4096
    ssd = 64 * (5 * 64 * 128 + 192)
    assert counts.forward_flops_per_token(cfg, traffic) == (
        2 * 318_431_232 + 4 * ssd + 2 * 8192 * 4096) == 714_506_240
    assert counts.train_flops_per_token(cfg, traffic) == 3 * 714_506_240
    item, tokens = 2, 8192
    assert counts.ssd_core(cfg, traffic) == {
        "ops": 4 * tokens * ssd * 4,  # 4 blocks x (2 forward + 2) passes
        "hbm_bytes": 4 * tokens * item * (
            2 * (6144 + 64 + 4096) + 2 * (6144 + 64) + 4096)}
    rows = 3072  # tokens x 6 x 8 / 128 a block
    experts = counts.moe_experts(cfg, traffic)
    assert experts["ops"] == 4 * 4 * rows * 4 * 2688 * 1856
    weights = 8 * expert
    assert experts["hbm_bytes"] == 4 * (
        4 * (weights * item + rows * (2 * 2688 + 2 * 1856) * item) + weights * 4)
    square = 32 * 8192 * 8192 * 128  # B x H x T^2 x Dh, one attention block
    q_side, kv_side = 8192 * 32 * 128 * item, 8192 * 2 * 128 * item
    assert counts.causal_attention_fwd(cfg, traffic) == {
        "ops": 2 * 2 * square, "hbm_bytes": 2 * (2 * q_side + 2 * kv_side)}
    assert counts.causal_attention_bwd(cfg, traffic) == {
        "ops": 5 * square, "hbm_bytes": 4 * q_side + 4 * kv_side}


def test_every_metric_of_the_cell_names_a_reader_and_a_count(cell, counts):
    names = {m["name"] for m in cell.per_layer}
    assert {"step_mfu.nemo3", "device_idle_share.nemo3",
            "compiles_in_window.nemo3", "dispatch_ms.nemo3",
            "input_put_ms.nemo3", "loss_wait_ms.nemo3",
            "step_host_ms_max.nemo3", "trainer_init_s.nemo3", "ssd_ms.nemo3",
            "ssd_roofline.nemo3", "ssd_chunked_share.nemo3",
            "shared_expert_ms.nemo3", "moe_ms.nemo3", "moe_shuffle_ms.nemo3",
            "moe_experts_roofline.nemo3", "moe_held_share.nemo3",
            "attn_core_ms.nemo3", "attn_flash_share.nemo3",
            "flash_fwd_roofline.nemo3", "flash_bwd_roofline.nemo3",
            "lm_loss_ms.nemo3"} <= names  # <=: the next metric needs no edit
    for m in cell.per_layer:
        with open(os.path.join(th.BENCH, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(
            th.BENCH, "readers", spec["reader"] + ".py"))
        args = spec["args"]
        if "module" in args:  # the counts are this configuration's own
            assert args["module"] == "nemotron_h_counts"
            assert callable(getattr(counts, args.get("fn") or args["flops_fn"]))
        if m["name"].endswith("_roofline.nemo3"):
            assert m["unit"] == "%" and spec["reader"] == "kernel_roofline"


# --- tiny-size rehearsal of the runner, window and comparison --------------

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, manifest):
    """A copy of the benchmark with the configuration at a tiny size ADDED
    beside it, and a cell that reports the real cell's metrics."""
    root = str(tmp_path_factory.mktemp("bench_nemo3"))
    shutil.copytree(th.BENCH, os.path.join(root, "benchmark"))
    with open(os.path.join(th.BENCH, "configs", CONFIG + ".json")) as f:
        cfg = dict(json.load(f), name="tiny_nemo3", **TINY_CONFIG)
    th._write(root, "benchmark/configs/tiny_nemo3.json", cfg)
    th._write(root, "benchmark/traffic/tiny_nemo3_loop.json", TINY_TRAFFIC)
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "tiny_nemo3", "source": cfg["source"],
                         "file": "benchmark/configs/tiny_nemo3.json",
                         "reduced": cfg["reduced"], "why": "test"})
    m["workloads"].append({"name": TINY_CELL, "config": "tiny_nemo3",
                           "traffic": "tiny_nemo3_loop", "chips": 1,
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
    # the runner's last word: nothing dropped, the scan's choices by length
    said = next(line for line in capsys.readouterr().err.splitlines()
                if line.startswith("routing and scan:"))
    assert "fedml_moe_dropped_total = 0" in said
    assert "fedml_ssd_dispatch_total{chunk=8,impl=chunked,seq_len=32}" in said
    assert err.strip().splitlines()[-1] == "correct: true"


def test_tiny_traced_run_reports_the_cells_metrics(bench, tiny_root):
    result, _ = th._drive(bench, tiny_root, TINY_CELL, trace=True, seed=7)
    assert result["correct"] is True, result["compared"]
    metrics = result["metrics"]
    assert 0 < metrics["step_mfu.nemo3"]["value"] < 100
    assert metrics["compiles_in_window.nemo3"]["value"] == 0
    host = {"dispatch_ms.nemo3", "input_put_ms.nemo3", "loss_wait_ms.nemo3",
            "step_host_ms_max.nemo3", "trainer_init_s.nemo3"}
    assert all(metrics[m]["value"] > 0 for m in host)
    # every Mamba block at the traffic's length took the chunked scan (the
    # trainer's 8-token init, at its own length, stays out of the share)
    assert metrics["ssd_chunked_share.nemo3"]["value"] == 100.0
    # 4 of 16 experts held, the biases balanced on the pool's last batch
    assert 15 < metrics["moe_held_share.nemo3"]["value"] < 35
    assert metrics["attn_flash_share.nemo3"]["value"] == 0.0  # dense off the chip
    # the CPU's trace has no device plane: those readers return nothing
    assert not {m for m in metrics if m.endswith(("_roofline.nemo3", "_ms.nemo3"))
                and m not in host}
    assert "device_idle_share.nemo3" not in metrics


def test_control_and_planted_faults_come_out_not_correct(bench, tiny_root):
    cell = bench.load_cell(tiny_root, TINY_CELL)
    sys.path.insert(0, cell.bench_dir)
    runner = importlib.import_module("runners." + cell.config["runner"])
    compare = importlib.import_module("compare")
    assert set(runner.FAULTS) == {"half_batch", "capacity_drop", "state_reset"}
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


def test_a_scan_that_drops_its_carried_state_reads_not_correct(
        bench, tiny_root, monkeypatch):
    """The timed path broken underneath: the program's chunks each start
    from an empty state, which is what ``state_reset`` plants in the
    reference."""
    import jax

    from fedml_tpu.ops import ssd

    scan = jax.lax.scan

    def forgetful(f, init, xs, **kw):
        carried, handed = scan(f, init, xs, **kw)
        return carried, jax.tree.map(jax.numpy.zeros_like, handed)

    sound = ssd._chunked

    def broken(*args):
        monkeypatch.setattr(jax.lax, "scan", forgetful)
        try:
            return sound(*args)
        finally:
            monkeypatch.setattr(jax.lax, "scan", scan)

    monkeypatch.setattr(ssd, "_chunked", broken)
    result, err = th._drive(bench, tiny_root, TINY_CELL, seed=2_900_000_029)
    assert result["correct"] is False and result["attempted"] >= 1
    assert err.strip().splitlines()[-1] == "correct: false"
    # and it is the planted fault's twin: against the reference that resets
    # its state, the same run is within the limits
    cell = bench.load_cell(tiny_root, TINY_CELL)
    runner = importlib.import_module("runners." + cell.config["runner"])
    compare = importlib.import_module("compare")
    ctx = bench.types.SimpleNamespace(
        config=cell.config, traffic=cell.traffic, seed=2_900_000_029, chips=1)
    run = runner.Run(bench.types.SimpleNamespace(
        **vars(ctx), span=bench.no_span))
    got = run.readings
    run.close()
    assert compare.decide(got, runner.reference(ctx, state_reset=True),
                          cell.config["limits"])[0]
