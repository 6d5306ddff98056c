"""chip_smoke.py: its contract off the chip, and its stage functions at a
tiny size on the CPU (kernels in interpret mode) — so a chip run never
spends its minutes on a typo in the smoke itself."""

import functools
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_FL = dict(
    dataset="mnist", model="lr", debug_small_data=True,
    partition_method="hetero", partition_alpha=0.5,
    client_num_in_total=20, client_num_per_round=5, epochs=1,
    batch_size=10, learning_rate=0.1, random_seed=0,
)
TINY_LM = dict(vocab_size=64, dim=32, num_heads=4, num_layers=2)


def test_exits_nonzero_naming_the_platform_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "not 'tpu'" in r.stderr
    assert r.stdout.strip() == "", "no result may be printed off the chip"
    assert not os.path.exists(os.path.join(REPO, ".jax_cache")), \
        "a CPU run must leave no compile cache in the checkout"


def test_stage_flagship_tiny():
    out = chip_smoke.stage_flagship(TINY_FL, rounds=3)
    assert any(ln.startswith("FedSimulator: schedule=") for ln in out["engaged"])
    assert len(out["train_loss"]) == 3 and out["test_acc"]


def test_stage_flagship_reports_a_loss_that_does_not_fall():
    with pytest.raises(chip_smoke.CheckFailed, match="did not fall"):
        chip_smoke.stage_flagship(dict(TINY_FL, learning_rate=0.0), rounds=2)


def test_stage_lm_and_flash_check_tiny():
    out = chip_smoke.stage_lm(TINY_LM, seq=16, batch=2, steps=3)
    assert out["attention_impl"] == "dense" and out["param_devices"] == 1
    # on the CPU the kernel runs interpreted: numerics hold, no Mosaic call
    fvd = chip_smoke.check_flash_vs_dense(seq=128, heads=2, dh=64)
    assert fvd["mosaic_calls_lowered"] == 0


def test_windowed_flash_check_tiny():
    """The window stage's check at a band of half of T (blocks of 128, a
    lower-edge block straddling the band, the diagonal apart)."""
    band = dict(chip_smoke.FLASH_WINDOW, seq=512, heads=2, dh=128, window=256)
    out = chip_smoke.check_flash_vs_dense(**band)
    assert out["mosaic_calls_lowered"] == 0
    assert max(out["rel_err"].values()) <= 2e-2


def test_stage_hybrid_lm_tiny():
    tiny = dict(chip_smoke.HYBRID_LM, vocab_size=64, hidden_size=32,
                intermediate_size=64, moe_intermediate_size=16,
                num_attention_heads=4)
    out = chip_smoke.stage_hybrid_lm(tiny, seq=16, batch=2, steps=3)
    assert out["attention_impl"] == "dense" and out["mosaic_calls_lowered"] == 0
    assert out["assignments"] == 3 * 2 * 2 * 16 * 4


def test_stage_looped_lm_tiny():
    tiny = dict(chip_smoke.LOOPED_LM, vocab_size=64, hidden_size=32,
                intermediate_size=64, num_attention_heads=4,
                num_key_value_heads=4, head_dim=8)
    out = chip_smoke.stage_hybrid_lm(tiny, seq=16, batch=2, steps=3)
    assert out["attention_impl"] == "dense" and out["mosaic_calls_lowered"] == 0
    assert out["assignments"] == 0  # nothing routes; 3 steps x 4 passes counted


def test_stage_hybrid_mixers_and_the_scan_check_tiny():
    tiny = dict(chip_smoke.HYBRID_MIXERS, vocab_size=64, hidden_size=32,
                moe_intermediate_size=16, num_attention_heads=4,
                mamba_num_heads=8, mamba_head_dim=4, ssm_state_size=8,
                n_groups=2, chunk_size=8, moe_shared_expert_intermediate_size=24)
    out = chip_smoke.stage_hybrid_lm(tiny, seq=16, batch=2, steps=3)
    assert out["attention_impl"] == "dense" and out["mosaic_calls_lowered"] == 0
    assert out["assignments"] == 3 * 1 * 2 * 16 * 6  # one expert block
    scan = chip_smoke.check_ssd_vs_sequential(2, 32, 8, 4, 2, 8, 8)
    assert scan["impl"] == "chunked" and max(scan["rel_err"].values()) <= 2e-2
    with pytest.raises(ValueError, match="multiple of the chunk"):
        chip_smoke.check_ssd_vs_sequential(2, 36, 8, 4, 2, 8, 8)


def test_kernel_checks_tiny_interpret(monkeypatch):
    from jax.experimental import pallas as pl

    assert chip_smoke.check_gram((10, 64), interpret=True)[
        "rel_err_vs_reference"] == 0.0
    assert "agg_kernels off" in chip_smoke.check_gram_refuses(
        chip_smoke.GRAM_REFUSED, interpret=False)["refused"]
    with pytest.raises(chip_smoke.CheckFailed, match="without an error"):
        # the CPU's default dispatch is the reference: not a refusal
        chip_smoke.check_gram_refuses(chip_smoke.GRAM_REFUSED)
    krum = chip_smoke.check_sanitize_krum(
        chip_smoke.model_param_shapes(TINY_FL, (28, 28, 1), 10), cohort=10,
        interpret=True)
    assert krum["width"] == 7850 and krum["quarantined"] == [1, 2]
    for bits in (8, 4):
        assert chip_smoke.check_quant((5, 700), bits, interpret=True)[
            "wire_rows_checked"] == 5
    # conv2d_pallas has no interpret argument of its own
    monkeypatch.setattr(
        pl, "pallas_call", functools.partial(pl.pallas_call, interpret=True))
    assert chip_smoke.check_conv((2, 8, 8))["rel_err"]["dw"] <= 2e-2


def _snapshot(peaks, in_use=None):
    return [{"peak_bytes_in_use": p, "bytes_in_use": b}
            for p, b in zip(peaks, in_use or peaks)]


# per-device counters of the four-chip run of stage 5 (chip tool, PR 21)
CHIP_BEFORE = _snapshot([27136] * 4)
CHIP_FL = _snapshot([1056694784, 925604352, 925604352, 925604352])
CHIP_LM = _snapshot([3094960128, 2347167744, 2347167744, 2347167744],
                    [1971065856, 1958284288, 1958284288, 1958284288])


def test_four_chip_checks_tiny_on_virtual_devices(monkeypatch):
    """Both stage-5 runs at a tiny size on four virtual devices; the CPU
    keeps no allocator counters, so the check gets the chip's own."""
    monkeypatch.setattr(jax, "devices", lambda *_, four=jax.devices()[:4]: four)
    assert chip_smoke.memory_stats() == [{}] * 4
    fl = chip_smoke.stage_flagship(
        dict(TINY_FL, client_num_per_round=10), 2, backend="TPU")
    lm = chip_smoke.stage_lm(TINY_LM, seq=16, batch=4, steps=2, dp=2, tp=2)
    with pytest.raises(KeyError, match="peak_bytes_in_use"):
        chip_smoke.check_four_chips(fl["memory"], fl, lm)
    fl, lm = dict(fl, memory=CHIP_FL), dict(lm, memory=CHIP_LM)
    out = chip_smoke.check_four_chips(CHIP_BEFORE, fl, lm)
    assert out["lm_mesh"] == {"data": 2, "seq": 1, "model": 2}
    assert "over 4 of 4 devices" in out["flagship_mesh"]
    # a mesh that leaves devices idle is refused, whatever the log says of it
    idle = dict(fl, engaged=["SimulatorTPU: mesh {'client': 2} over 2 of 4 "
                             "devices, cohort of 10; left idle: [...]"])
    with pytest.raises(chip_smoke.CheckFailed, match="idle"):
        chip_smoke.check_four_chips(CHIP_BEFORE, idle, lm)
    two_chips = dict(fl, memory=_snapshot(
        [1056694784, 925604352, 27136, 27136]))
    with pytest.raises(chip_smoke.CheckFailed,
                       match=r"flagship: devices \[2, 3\] did no work"):
        chip_smoke.check_four_chips(CHIP_BEFORE, two_chips, lm)
    # a counter an earlier stage already raised cannot vouch for this one
    with pytest.raises(chip_smoke.CheckFailed,
                       match=r"lm: devices \[0\] did no work"):
        chip_smoke.check_four_chips(
            CHIP_BEFORE, fl, dict(lm, memory=_snapshot(
                [1056694784] + [2347167744] * 3)))


@pytest.mark.parametrize("extra,axis", [
    ({}, 4),                                               # padded
    (dict(federated_optimizer="FedAvg_robust", defense_type="krum"), 2),
    (dict(attack_type="sign_flip"), 2),
    (dict(federated_optimizer="HierarchicalFL"), 2),
])
def test_facade_shrinks_the_client_mesh_only_where_the_engine_cannot_pad(
        extra, axis, monkeypatch):
    """A cohort of 10 on four devices: the facade asks the engine's own
    rule (fed_sim.pads_cohort) and never builds a mesh the engine then
    refuses."""
    import fedml_tpu
    from fedml_tpu.simulation import SimulatorTPU

    monkeypatch.setattr(jax, "devices", lambda *_, four=jax.devices()[:4]: four)
    args = fedml_tpu.init(config=dict(
        TINY_FL, client_num_per_round=10, comm_round=1, backend="TPU",
        **extra))
    assert dict(SimulatorTPU(args).mesh.shape) == {"client": axis}


@pytest.mark.parametrize("width", [256, 2688], ids=["a_slab", "a_padded_slab"])
def test_row_moves_check_tiny_interpret(width):
    assert {shape[-1] for shape in chip_smoke.ROW_MOVES} == {2048, 2688}
    out = chip_smoke.check_row_moves(64, 4, 16, 4, width)
    assert out["slots"] == 256 and out["rows"] == 512 and out["width"] == width
    assert 0 < out["rows_held"] < out["rows"]
    assert out["mosaic_calls_lowered"] == 0     # interpreted on the CPU


def test_compiles_since_reads_the_programs_counter_and_compile_spans():
    import time

    import jax.numpy as jnp

    from fedml_tpu.core import telemetry

    telemetry.configure(enabled=True, reset=True)
    assert telemetry.install_jax_collectors()
    seen = chip_smoke.compiles_since({}, time.time())
    wall0 = time.time()
    with telemetry.get_tracer().span("chip_smoke.tiny"):
        jax.jit(lambda x: x * 11.0 - 2.0)(jnp.ones(5)).block_until_ready()
    got = chip_smoke.compiles_since(seen, wall0)
    assert got["compiled"] >= 1 and got["cache"] == 0
    assert got["xla_s"] >= 0 and got["xla_over_1s"] == 0
    assert got["cache_load_s"] == 0
    assert chip_smoke.compiles_since(
        {"compiled": got["compiled"] + seen["compiled"]}, time.time()) == {
            "compiled": 0, "cache": 0, "xla_s": 0, "xla_over_1s": 0,
            "cache_load_s": 0}
    telemetry.configure(enabled=True, reset=True)
