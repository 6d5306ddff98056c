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


def test_kernel_checks_tiny_interpret(monkeypatch):
    from jax.experimental import pallas as pl

    assert chip_smoke.check_gram((10, 64), interpret=True)[
        "rel_err_vs_reference"] == 0.0
    for bits in (8, 4):
        assert chip_smoke.check_quant((5, 700), bits, interpret=True)[
            "bit_identical"]
    # conv2d_pallas has no interpret argument of its own
    monkeypatch.setattr(
        pl, "pallas_call", functools.partial(pl.pallas_call, interpret=True))
    assert chip_smoke.check_conv((2, 8, 8))["rel_err"]["dw"] <= 2e-2


def test_four_chip_checks_tiny_on_virtual_devices():
    assert len(jax.devices()) >= 4
    before = chip_smoke.memory_stats()
    fl = chip_smoke.stage_flagship(
        dict(TINY_FL, client_num_per_round=10), 2, backend="TPU")
    lm = chip_smoke.stage_lm(TINY_LM, seq=16, batch=4, steps=2, dp=2, tp=2)
    out = chip_smoke.check_four_chips(before, fl, lm)
    assert out["lm_mesh"] == {"data": 2, "seq": 1, "model": 2}
    assert f"over {len(before)} of {len(before)} devices" in out["flagship_mesh"]
    # a mesh that leaves devices idle is refused
    idle = dict(fl, engaged=["SimulatorTPU: mesh {'client': 2} over 2 of 8 "
                             "devices, cohort of 10; left idle: [...]"])
    with pytest.raises(chip_smoke.CheckFailed, match="idle"):
        chip_smoke.check_four_chips(before, idle, lm)
